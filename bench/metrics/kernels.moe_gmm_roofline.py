"""The routed experts' grouped matmul against its roofline: the least time
of the routed-expert products the window's real tokens require
(``expert_least_seconds`` of ``bench/flops/``, ``top_k`` rows a token,
forward and input-gradient, each product at the larger of operations over
the bf16 peak and bytes over HBM bandwidth) over the device time of the
operations named ``moe_gmm``. Nothing where the model has no routed experts
or the trace no such operation. Layer: kernels."""
UNIT = "%"


def read(ctx):
    from bench.trace import reduce as tr

    least_fn = getattr(ctx.flops, "expert_least_seconds", None)
    if least_fn is None:
        return None
    spent = sum(tr.op_seconds([op for op in ops if "moe_gmm" in op[2]],
                              *ctx.window_ns)
                for ops in ctx.trace["devices"].values())
    if spent <= 0:
        return None
    least = sum(least_fn(ctx.spec, ctx.seq, list(zip(batches, ranks)),
                         ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"])
                for ranks, batches, degree in ctx.jobs)
    return 100.0 * least * ctx.steps_per_job * ctx.passes / spent
