"""Device time of the operations under the program's ``fftb.pack``
scope per step (one batched round trip, or one SCF iteration), mean over
the devices, in ms.  Read for ``pack_ms.transform`` and
``pack_ms.scf`` alike."""
from bench import scopes


def read(tr, info):
    return scopes.per_step_ms(tr, info, "fftb.pack")
