"""The whole transform's share of its roofline, in %.

Least time the chips could take for one step's required work — the larger
of flops over the bf16 peak and bytes over the HBM bandwidth, both from
``bench/peaks.json`` times the chips, the work from the traffic kind's
``required_work`` (not the implementation's own count) — over the device
time of one step's ``bench_inverse`` and ``bench_forward`` runs."""
from bench import trace


def read(tr, info):
    t_inv = trace.mean_call_ns(tr, "bench_inverse")
    t_fwd = trace.mean_call_ns(tr, "bench_forward")
    peaks = info["peaks"]
    if t_inv is None or t_fwd is None or peaks is None:
        return None
    flops, nbytes = info["work"]
    units, chips = info["units_per_step"], info["chips"]
    t_flops = units * flops / (chips * peaks["bf16_flops_per_s"])
    t_bytes = units * nbytes / (chips * peaks["hbm_bytes_per_s"])
    bound = "memory" if t_bytes >= t_flops else "compute"
    info["log"](f"roofline_share: {bound}-bound, {units} units/step, "
                f"least {max(t_flops, t_bytes) * 1e3:.4f} ms/step against "
                f"{(t_inv + t_fwd) / 1e6:.4f} ms on the device")
    return 100.0 * max(t_flops, t_bytes) / ((t_inv + t_fwd) / 1e9)
