"""Device time of the all-to-all operations per step (one batched round
trip), mean over the devices, in ms.  None on a grid without them."""
from bench import trace


def read(tr, info):
    ns = trace.op_time_ns(tr, "all-to-all", info["programs"])
    runs = trace.calls(tr, "bench_inverse")
    steps = max((len(r) for r in runs), default=0)
    if ns is None or not steps:
        return None
    return ns / steps / 1e6
