"""Model FLOP utilization of the window (model step,
``serving/paged_attn.py``): the FLOPs the algorithm needs for every token
the window's ticks processed (the architecture module's ``step_flops``:
for a dense model 2 x matmul parameters per token, attention over each
token's context, the output head at each emitting row), over the
window's length times the chip's bf16 peak."""


def read(run):
    ticks = run.window_ticks()
    if not ticks:
        return None
    total = sum(run.arch.step_flops(run.model, t["segments"],
                                    t["logits_rows"]) for t in ticks)
    span = run.t_close - run.t_open
    return 100.0 * total / (span * run.peak["bf16_flops_per_s"])
