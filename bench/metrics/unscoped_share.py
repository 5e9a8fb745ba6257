"""Busy time of the operations that no ``fftb.*`` or ``scf.*`` scope
names, as % of the device's busy time in the window, mean over the
devices.  Read for ``unscoped_share.transform`` and
``unscoped_share.scf`` alike."""
from bench import scopes


def read(tr, info):
    return scopes.unscoped_share(tr, info)
