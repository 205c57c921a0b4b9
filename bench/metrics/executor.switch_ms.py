"""Mean time of a job switch: over the window's segments, the
``runner.segment`` span less the ``executor.train`` span inside it (template
copy, placement, the first batches, the checkpoint pool). Read from the
program's spans. Layer: cluster (runner, executor)."""
UNIT = "ms"


def read(ctx):
    segs = [s for s in ctx.spans if s.name == "runner.segment"]
    trains = [s for s in ctx.spans if s.name == "executor.train"]
    out = []
    for seg in segs:
        inner = [t for t in trains if seg.start <= t.start and t.end <= seg.end
                 and t.track == seg.track]
        if len(inner) == 1:
            out.append((seg.end - seg.start) - (inner[0].end - inner[0].start))
    return 1e3 * sum(out) / len(out) if out else None
