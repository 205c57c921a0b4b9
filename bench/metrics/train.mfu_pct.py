"""The whole step's share of the chip's peak: the operations that the
window's real tokens require (``bench/flops/``) over the traced window
times the chips times the bf16 peak. Recomputation and padding do not
count. Layer: model step (train/trainer.py)."""
UNIT = "%"


def read(ctx):
    flops = 0.0
    for ranks, batches, degree in ctx.jobs:
        flops += ctx.flops.job_flops(ctx.spec, ctx.seq, list(zip(batches, ranks)))
    flops *= ctx.steps_per_job * ctx.passes
    return 100.0 * flops / (ctx.traced_window_s * ctx.chips
                            * ctx.peaks["bf16_flops"])
