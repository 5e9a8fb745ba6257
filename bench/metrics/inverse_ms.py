"""Device time of one run of the benchmark's ``bench_inverse`` program
(its span on the device's ``XLA Modules`` line), mean over runs and
devices, in ms."""
from bench import trace


def read(tr, info):
    ns = trace.mean_call_ns(tr, "bench_inverse")
    return None if ns is None else ns / 1e6
