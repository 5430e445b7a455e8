"""Share of its roofline the paged-attention kernel reaches
(``kernels/paged_attention``), from the device trace: the least time
(the larger of FLOPs over peak FLOP/s and bytes over peak bandwidth, with
the FLOPs and logical bytes each traced tick's segments need,
``bench/flops.py``) over the summed duration of the kernel's events.
Lane padding of 64-dim heads counts as lost share, not as work."""
from bench import flops


def read(run):
    tr = run.trace
    if not tr or not tr["kernel_s"].get("paged_attention"):
        return None
    least = 0.0
    for t in run.window_ticks():
        fl, nb = flops.paged_kernel_cost(run.model, t["segments"])
        least += flops.roofline_seconds(fl, nb, run.peak)
    return 100.0 * least / tr["kernel_s"]["paged_attention"]
