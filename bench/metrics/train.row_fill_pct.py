"""Rows the training step computed that hold an adapter's real samples,
over all rows it computed, weighted by steps: 100 x sum(real_rows x
n_steps) / sum(rows x n_steps) over the window's ``executor.train`` spans.
A program whose spans do not carry ``rows`` and ``real_rows`` reports
nothing. Layer: model step (train/trainer.py)."""
UNIT = "%"


def read(ctx):
    real = rows = 0
    for s in ctx.spans:
        if s.name != "executor.train" or "rows" not in s.args:
            continue
        real += s.args["real_rows"] * s.args["n_steps"]
        rows += s.args["rows"] * s.args["n_steps"]
    return 100.0 * real / rows if rows else None
