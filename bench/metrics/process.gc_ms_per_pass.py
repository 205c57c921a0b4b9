"""Python garbage-collection pauses of 1 ms or more (``process.gc``
spans, recorded while the runner's dispatch loop is open), summed over the
window and divided by its passes. A program whose tracer cannot watch the
collector reports nothing. Layer: process (Python runtime)."""
UNIT = "ms"


def read(ctx):
    from bench.trace import attribution

    from repro.obs import Tracer

    if not hasattr(Tracer, "watch_gc"):
        return None
    total = attribution.span_ns(ctx.trace, ctx.window_ns, "span.process.gc")
    return total / 1e6 / ctx.passes
