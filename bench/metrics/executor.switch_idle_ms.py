"""What a job switch costs the chip: the window's idle time (as
``device.idle_pct`` counts it) outside every ``executor.train`` span, mean
over chips, per ``runner.segment`` span of the window. Read from the device
trace and the program's spans mapped onto its clock. ``executor.switch_ms``
is what a switch costs the host. Layer: cluster (runner, executor)."""
UNIT = "ms"


def read(ctx):
    from bench.trace import attribution

    segments = attribution.count(ctx.trace, "span.runner.segment")
    if not segments:
        return None
    outside, _ = attribution.idle_split(ctx.trace, ctx.window_ns,
                                        "span.executor.train")
    return 1e3 * outside / segments
