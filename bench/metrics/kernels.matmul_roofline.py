"""Matrix products' share of their roofline: the least time of the
required products (each at the larger of operations over the bf16 peak and
bytes over HBM bandwidth; base, LoRA, attention and LM head together,
``bench/flops/``) over the device time of every operation that can carry a
product (dot or convolution fusions and custom calls, Pallas kernels among
them). The same work is read whatever implements it. Layer: kernels."""
UNIT = "%"


def read(ctx):
    from bench.trace import reduce as tr

    least = 0.0
    for ranks, batches, degree in ctx.jobs:
        # chip-seconds: a job sharded over several chips shares one least
        # time between them, and the device time below sums over chips
        least += ctx.flops.job_least_seconds(
            ctx.spec, ctx.seq, list(zip(batches, ranks)),
            ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"])
    least *= ctx.steps_per_job * ctx.passes
    spent = sum(tr.op_seconds(ops, *ctx.window_ns, cls)
                for ops in ctx.trace["devices"].values()
                for cls in ("matmul", "pallas"))
    return 100.0 * least / spent if spent > 0 else None
