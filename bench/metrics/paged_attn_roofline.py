"""Share of its roofline the paged-attention kernel reaches
(``kernels/paged_attention``), from the device trace: the least time
(the larger of FLOPs over peak FLOP/s and bytes over peak bandwidth, with
the FLOPs and logical bytes each traced tick's segments need, from the
architecture module's ``kernel_cost``) over the summed duration of the
kernel's events (``RunData.roofline_share``).  Lane padding of 64-dim
heads counts as lost share, not as work."""


def read(run):
    return run.roofline_share("paged_attention")
