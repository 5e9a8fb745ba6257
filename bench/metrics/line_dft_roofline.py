"""The ``fftb.line_dft`` layer's share of its roofline, in %: the least time
for one step's work under the scope (the kind's ``scope_work``) over
the scope's device time per step.  Read for ``line_dft_roofline.transform``
and ``line_dft_roofline.scf`` alike."""
from bench import scopes


def read(tr, info):
    return scopes.roofline_share(tr, info, "fftb.line_dft")
