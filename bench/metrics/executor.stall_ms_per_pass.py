"""Idle time of the chip inside ``executor.train`` spans, when the host
has dispatched a job's steps or waits for them, mean over chips, per pass
of the window. Read from the device trace and the program's spans mapped
onto its clock. Layer: cluster (runner, executor)."""
UNIT = "ms"


def read(ctx):
    from bench.trace import attribution

    if not attribution.count(ctx.trace, "span.executor.train"):
        return None
    _, inside = attribution.idle_split(ctx.trace, ctx.window_ns,
                                       "span.executor.train")
    return 1e3 * inside / ctx.passes
