"""The benchmark end to end on the CPU at a tiny size: a configuration, a
traffic mix, a metric and a limits file added as new files are found by
name; a sound run is correct; the control and each planted fault are not.

The chip check is skipped (the harness is called below ``main``); the
rest of a run is driven as on the chip, with limits read at this size."""
import io
import json
import os
import shutil
import sys
from contextlib import redirect_stdout

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import pytest  # noqa: E402

from bench import faults, harness  # noqa: E402
from bench.program import RecordingExecutor  # noqa: E402

CELL = "tiny-qwen.tiny-grid"
# limits for this size, set from CPU readings of it (PERF.md, section 2)
LIMITS = {"loss_gap": 1e-3, "grad_gap": 0.02, "update_gap": 0.02}
TINY = dict(hidden_size=128, intermediate_size=256, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32, num_hidden_layers=2,
            vocab_size=512, name="tiny-qwen")
MIX = {"name": "tiny-grid", "seq": 128, "steps_per_job": 4, "noise": 0.1,
       "grid": [
           {"ranks": [8, 16], "batch_sizes": [1], "learning_rate": 1e-3,
            "alpha_over_rank": 1.0},
           {"ranks": [8, 16], "batch_sizes": [2], "learning_rate": 4e-3,
            "alpha_over_rank": 0.25}]}
METRIC = '''"""A metric added as a file of its own."""
UNIT = "count"


def read(ctx):
    return 42.0
'''


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark with a new configuration, mix, metric, cell
    and limits file, each added as a new file (and one line in the index)."""
    root = tmp_path_factory.mktemp("bench_root")
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    with open(os.path.join(ROOT, "bench", "configs", "qwen25-7b-l6.json")) as f:
        cfg = dict(json.load(f), **TINY)
    (root / "bench" / "configs" / "tiny-qwen.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "tiny-grid.json").write_text(json.dumps(MIX))
    (root / "bench" / "metrics" / "test.extra_metric.py").write_text(METRIC)
    (root / "bench" / "limits" / f"{CELL}.json").write_text(
        json.dumps({"limits": LIMITS}))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        index = json.load(f)
    index["workloads"].append({"name": CELL, "config": "tiny-qwen",
                               "traffic": "tiny-grid", "chips": 1,
                               "why": "test"})
    index["per_layer"].append({"name": "test.extra_metric", "unit": "count",
                               "better": "lower", "source": "program_counter",
                               "layer": "test", "moves": "setup_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(index))
    # nothing that was there changed
    assert all(p.read_bytes() == b for p, b in before.items())
    return str(root)


def run(root, seed=7):
    bench, cell, cfg_file, mix = harness.find_cell(CELL, root=root)
    return harness.run_cell(
        cell, cfg_file, mix, seed=seed, seconds=0.2, trace=False,
        devices=jax.devices()[:1], kind="TPU v5 lite",
        peaks=harness.peaks_for("TPU v5 lite", root=root),
        limits=harness.load_limits(cell, root=root), root=root)


@pytest.fixture(scope="module")
def sound(root):
    return run(root)


def test_new_files_are_found_by_name(root):
    bench, cell, cfg_file, mix = harness.find_cell(CELL, root=root)
    assert cfg_file["hidden_size"] == 128 and mix["seq"] == 128
    readers = harness.metric_readers(bench, cell, root=root)
    assert readers["test.extra_metric"].read(None) == 42.0
    assert "device.idle_pct" in readers
    assert set(harness.load_limits(cell, root=root)) == {
        "loss_gap", "grad_gap", "update_gap"}


def test_sound_run_is_correct(sound):
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] >= 4 and sound["failed"] == 0
    assert sound["metrics"]["sweep_tokens_per_s"]["value"] > 0
    assert sound["metrics"]["setup_s"]["unit"] == "s"


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "altered_answer"])
def test_planted_fault_is_not_correct(root, sound, fault, monkeypatch):
    orig = RecordingExecutor.step_fn

    def step_fn(self, cfg, n_pack, *a, **k):
        step, dist = orig(self, cfg, n_pack, *a, **k)
        return faults.broken(step, fault, n_pack), dist

    monkeypatch.setattr(RecordingExecutor, "step_fn", step_fn)
    res = run(root)
    assert not res["correct"], res["checks"]


def test_control_is_not_correct(root):
    """The reference at float8 in the program's place fails the limits."""
    bench, cell, cfg_file, mix = harness.find_cell(CELL, root=root)
    st = harness.setup(cfg_file, mix, seed=7, devices=jax.devices()[:1],
                       kind="TPU v5 lite")
    refs = harness.reference_results(st, root=root)
    low = harness.as_records(harness.reference_results(st, lowp=True,
                                                       root=root))
    checks = harness.compare(harness.pair(low, refs),
                             harness.load_limits(cell, root=root))
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


def test_main_refuses_a_cpu_backend():
    out = io.StringIO()
    with redirect_stdout(out):
        rc = harness.main(["--workload", "qwen25-7b.grid10-mixed", "--seed",
                           str(2**31 + 5), "--seconds", "1", "--trace", "0"])
    assert rc == 2 and out.getvalue() == ""


def test_unknown_chip_kind_is_refused():
    with pytest.raises(harness.Refused):
        harness.peaks_for("TPU v9 imaginary")
    assert harness.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12


def test_missing_or_short_device_list_is_refused():
    cpu = jax.devices("cpu")
    with pytest.raises(harness.Refused):
        harness.check_devices(1, cpu)

    class Tpu:
        platform = "tpu"

    with pytest.raises(harness.Refused):
        harness.check_devices(4, [Tpu()])
    assert len(harness.check_devices(1, [Tpu(), Tpu()])) == 1
