"""Share of the steady window in which no operation ran on the device, in
%, mean over the devices.  Read for ``idle_share.transform`` and
``idle_share.scf`` alike: the split names only the end-to-end metric the
share moves in each cell."""
from bench import trace


def read(tr, info):
    return trace.idle_share(tr, info["programs"])
