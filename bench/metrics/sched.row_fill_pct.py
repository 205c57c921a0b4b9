"""Real adapter rows over computed rows, over the window's steps.

A packed job computes ``n * max_batch`` rows each step; the adapters'
own batches fill ``sum(batch)`` of them, the rest is padding. Read from the
plan that the engine ran. Layer: sched (planner, engine)."""
UNIT = "%"


def read(ctx):
    real = computed = 0
    for ranks, batches, degree in ctx.jobs:
        real += sum(batches)
        computed += len(batches) * max(batches)
    return 100.0 * real / computed if computed else None
