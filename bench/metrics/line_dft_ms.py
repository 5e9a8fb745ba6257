"""Device time of the operations under the program's ``fftb.line_dft``
scope per step (one batched round trip, or one SCF iteration), mean over
the devices, in ms.  Read for ``line_dft_ms.transform`` and
``line_dft_ms.scf`` alike."""
from bench import scopes


def read(tr, info):
    return scopes.per_step_ms(tr, info, "fftb.line_dft")
