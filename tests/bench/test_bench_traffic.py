"""The traffic generator: grids and rows come from the mix and the seed."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench.traffic import generator as gen  # noqa: E402


def test_grid10_mixed_points():
    pts = gen.grid_points(gen.load_mix("grid10-mixed"))
    assert len(pts) == 10
    assert [p["rank"] for p in pts] == [8, 8, 16, 16, 32, 32, 64, 64, 128, 128]
    for p in pts:
        if p["batch_size"] == 1:
            assert (p["learning_rate"], p["alpha"]) == (1e-4, p["rank"])
        else:
            assert (p["learning_rate"], p["alpha"]) == (4e-4, p["rank"] / 4)


def test_single_mix():
    pts = gen.grid_points(gen.load_mix("single-r8-bs8"))
    assert pts == [{"rank": 8, "batch_size": 8, "learning_rate": 1e-4,
                    "alpha": 8.0}]


@pytest.mark.parametrize("seed", [0, 2**31 + 17, 2**33 + 5])
def test_rows_follow_the_seed(seed):
    p = {"rank": 8, "batch_size": 2, "learning_rate": 1e-4, "alpha": 8.0}
    a = gen.adapter_rows(seed, p, n_steps=2, seq=64, vocab=1000, noise=0.1)
    b = gen.adapter_rows(seed, p, n_steps=2, seq=64, vocab=1000, noise=0.1)
    c = gen.adapter_rows(seed + 1, p, n_steps=2, seq=64, vocab=1000, noise=0.1)
    assert all((x["tokens"] == y["tokens"]).all() for x, y in zip(a, b))
    assert not (a[0]["tokens"] == c[0]["tokens"]).all()
    assert a[0]["tokens"].shape == (2, 64)
    assert (a[0]["labels"][:, :-1] == a[0]["tokens"][:, 1:]).all()
    assert (a[0]["labels"][:, -1] == gen.IGNORE).all()
    assert a[0]["tokens"].max() < 1000


def test_packed_rows_do_not_depend_on_the_pack():
    class C:
        def __init__(self, rank, b):
            self.rank, self.batch_size = rank, b

        def key(self):
            return (self.rank, float(self.rank), 1e-4, self.batch_size)

    pts = [{"rank": r, "batch_size": b, "learning_rate": 1e-4,
            "alpha": float(r)} for r, b in ((8, 1), (16, 2))]
    bank = gen.RowBank(5, pts, n_steps=3, seq=32, vocab=500, noise=0.1)
    packed = list(bank.iterator(None, [C(16, 2), C(8, 1)], 32))
    alone = list(bank.iterator(None, [C(8, 1)], 32))
    assert len(packed) == 3
    for p, a in zip(packed, alone):
        assert p["tokens"].shape == (4, 32)
        np.testing.assert_array_equal(p["tokens"][2:3], a["tokens"])
        assert (p["labels"][3] == gen.IGNORE).all()  # a padding row
