"""Device time of the Pallas kernels (``tpu_custom_call``) per training
step, summed over the window's jobs and divided by their steps. Layer:
kernels."""
UNIT = "ms"


def read(ctx):
    from bench.trace import reduce as tr

    spent = sum(tr.op_seconds(ops, *ctx.window_ns, "pallas")
                for ops in ctx.trace["devices"].values())
    steps = len(ctx.jobs) * ctx.steps_per_job * ctx.passes
    return 1e3 * spent / steps if spent > 0 and steps else None
