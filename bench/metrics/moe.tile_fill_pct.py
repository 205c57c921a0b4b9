"""Rows of the routed experts' grouped matmuls that hold a routed
(token, expert) pair, over the rows their grids computed, whole m-tiles
per expert: 100 x sum(routed_rows x n_steps) / sum(routed_rows_computed x
n_steps) over the window's ``executor.train`` spans. A program whose spans
do not carry them reports nothing. Layer: model step (train/trainer.py)."""
UNIT = "%"


def read(ctx):
    routed = computed = 0
    for s in ctx.spans:
        if s.name != "executor.train" or "routed_rows_computed" not in s.args:
            continue
        routed += s.args["routed_rows"] * s.args["n_steps"]
        computed += s.args["routed_rows_computed"] * s.args["n_steps"]
    return 100.0 * routed / computed if computed else None
