"""Device time of one run of the benchmark's ``bench_forward`` program,
mean over runs and devices, in ms."""
from bench import trace


def read(tr, info):
    ns = trace.mean_call_ns(tr, "bench_forward")
    return None if ns is None else ns / 1e6
