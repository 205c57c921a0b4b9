"""DeepSeek-V2 through the benchmark on the CPU at a tiny size (d_model 128,
1 dense + 2 MoE layers, 8 experts top-3, 1 shared expert, kv_lora_rank 32,
seq 64, vocab 512): the program against the plain reference
(``bench/reference/deepseek_v2.py``) through the harness's check. A sound
run is correct; the control and each planted fault are not. The flop count
and the two readers of the new per-layer metrics are checked here too."""
import json
import os
import shutil
import sys
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import faults, harness  # noqa: E402
from bench.program import RecordingExecutor  # noqa: E402

CELL = "tiny-deepseek.tiny-grid"
# limits for this size, set from CPU readings of it (PERF.md, section 2)
LIMITS = {"loss_gap": 3e-3, "grad_gap": 0.04, "update_gap": 0.1}
TINY = dict(hidden_size=128, intermediate_size=256, num_attention_heads=4,
            num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=16, v_head_dim=32, moe_intermediate_size=64,
            n_routed_experts=8, num_experts_per_tok=3, n_shared_experts=1,
            num_hidden_layers=3, vocab_size=512, name="tiny-deepseek")
MIX = {"name": "tiny-grid", "seq": 64, "steps_per_job": 4, "noise": 0.1,
       "grid": [
           {"ranks": [8, 16], "batch_sizes": [1], "learning_rate": 1e-3,
            "alpha_over_rank": 1.0},
           {"ranks": [8, 16], "batch_sizes": [2], "learning_rate": 4e-3,
            "alpha_over_rank": 0.25}]}


def _load(name):
    return harness.load_module(os.path.join(ROOT, "bench", *name.split("/")))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark with the tiny configuration, its mix, cell
    and limits file."""
    root = tmp_path_factory.mktemp("bench_root")
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "bench", "configs",
                           "deepseek-v2-lite-l5.json")) as f:
        cfg = dict(json.load(f), **TINY)
    (root / "bench" / "configs" / "tiny-deepseek.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "tiny-grid.json").write_text(json.dumps(MIX))
    (root / "bench" / "limits" / f"{CELL}.json").write_text(
        json.dumps({"limits": LIMITS}))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        index = json.load(f)
    index["workloads"].append({"name": CELL, "config": "tiny-deepseek",
                               "traffic": "tiny-grid", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(index))
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


def test_sound_run_is_correct(sound):
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] >= 4 and sound["failed"] == 0
    assert sound["metrics"]["sweep_tokens_per_s"]["value"] > 0


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


def test_program_matches_reference_in_float32():
    """With float32 weights and products at ``highest`` the program's loss
    and each adapted matrix's gradient norm equal the reference's: the
    layer equations are the same, down to YaRN, the softmax scale, the
    router's un-normalised gates and the shared experts."""
    from repro.configs.base import LoraConfig
    from repro.core.adapter import pack_meta
    from repro.kernels.ops import KernelConfig
    from repro.models.model import init_lora
    from repro.train.trainer import packed_loss_fn

    with open(os.path.join(ROOT, "bench", "configs",
                           "deepseek-v2-lite-l5.json")) as f:
        cfg_file = dict(json.load(f), **TINY)
    model, ref = _load("models/deepseek_v2.py"), _load("reference/deepseek_v2.py")
    spec, cfg = model.spec(cfg_file), model.program_config(cfg_file)
    w = jax.tree.map(lambda x: x.astype(np.float32), model.make_weights(5, spec))
    base = model.base_tree(w, cfg)
    meta = pack_meta([LoraConfig(rank=8, alpha=16.0, batch_size=1)])
    lora = init_lora(jax.random.PRNGKey(3), cfg, meta)
    lora = jax.tree_util.tree_map_with_path(  # B != 0, so the deltas count
        lambda p, x: (0.05 * jax.random.normal(jax.random.PRNGKey(len(str(p))),
                                               x.shape)
                      if p[-1].key == "b" else x), lora)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 64), 0, 512)
    labels = np.roll(np.asarray(toks), -1, 1)
    dec = lora["decoder"]
    head, blk = dec["head"]["l0"], dec["blocks"]["l0"]
    rl = {nm: {ab: np.concatenate([head["attn"][nm][ab], blk["attn"][nm][ab][:, 0]])
               for ab in "ab"} for nm in ("q", "kv_a", "o")}
    for nm in ("gate", "up", "down"):
        rl[nm] = {ab: head["mlp"][nm][ab] for ab in "ab"}
        rl[f"shared_{nm}"] = {ab: blk["moe"]["shared"][nm][ab][:, 0] for ab in "ab"}
    with jax.default_matmul_precision("highest"):
        loss, g = jax.value_and_grad(lambda lo: packed_loss_fn(
            lo, base, {"tokens": toks, "labels": labels}, cfg, 1,
            meta.scales(), kcfg=KernelConfig(impl="xla"))[0])(lora)
        want, g_ref = ref.loss_and_grad(rl, w, toks, labels, 2.0, spec)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    got = jax.tree.map(lambda x: np.asarray(x)[:, 0], model.adapter_norms(g))
    np.testing.assert_allclose(
        np.concatenate([np.ravel(x) for x in jax.tree.leaves(got)]),
        np.concatenate([np.ravel(x) for x in jax.tree.leaves(ref.leaf_norms(g_ref))]),
        rtol=2e-3)


def test_published_config_counts():
    """The 5-layer cut's flop count: about 2.6 GFLOP a token of required
    products at seq 1024, the routed experts about a third of them."""
    with open(os.path.join(ROOT, "bench", "configs",
                           "deepseek-v2-lite-l5.json")) as f:
        cfg_file = json.load(f)
    spec = _load("models/deepseek_v2.py").spec(cfg_file)
    flops = _load("flops/deepseek_v2.py")
    per_token = flops.job_flops(spec, 1024, [(1, 8)]) / 1024
    experts = sum(f for _, f, _ in flops.expert_products(spec, 1024, [(1, 8)]))
    assert 2.0e9 < per_token < 3.2e9, per_token
    assert 0.25 < experts / 1024 / per_token < 0.45
    least = flops.expert_least_seconds(spec, 1024, [(1, 8)], 197e12, 819e9)
    assert 0 < least < flops.job_least_seconds(spec, 1024, [(1, 8)], 197e12,
                                               819e9)


@pytest.mark.parametrize("change", [{"routed_scaling_factor": 2.5},
                                    {"rope_scaling": {"mscale": 1.0}},
                                    {"moe_layer_freq": 2}])
def test_spec_refuses_what_the_program_does_not_map(change):
    """A file whose gates are scaled, whose rope tables would be rescaled
    (mscale unequal to mscale_all_dim) or whose MoE layers alternate is
    refused before any weight is made."""
    with open(os.path.join(ROOT, "bench", "configs",
                           "deepseek-v2-lite-l5.json")) as f:
        cfg_file = json.load(f)
    spec = _load("models/deepseek_v2.py").spec
    spec(cfg_file)
    for key, value in change.items():
        cfg_file[key] = ({**cfg_file[key], **value} if isinstance(value, dict)
                         else value)
    with pytest.raises(ValueError):
        spec(cfg_file)


def test_tile_fill_reader():
    mod = _load("metrics/moe.tile_fill_pct.py")
    span = lambda **a: SimpleNamespace(name="executor.train", args=a)  # noqa: E731
    ctx = SimpleNamespace(spans=[
        span(routed_rows=300, routed_rows_computed=400, n_steps=4),
        span(routed_rows=100, routed_rows_computed=100, n_steps=2),
        SimpleNamespace(name="executor.place", args={})])
    assert mod.read(ctx) == pytest.approx(100.0 * 1400 / 1800)
    assert mod.read(SimpleNamespace(spans=[span(rows=2, real_rows=2,
                                                n_steps=1)])) is None


def test_moe_gmm_roofline_reader():
    mod = _load("metrics/kernels.moe_gmm_roofline.py")
    with open(os.path.join(ROOT, "bench", "configs",
                           "deepseek-v2-lite-l5.json")) as f:
        spec = _load("models/deepseek_v2.py").spec(json.load(f))
    flops = _load("flops/deepseek_v2.py")
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least = flops.expert_least_seconds(spec, 1024, [(1, 8)], 197e12, 819e9)
    ops = [[0, int(2 * least * 1e9), "%moe_gmm.3 bf16[6144,1408]", "pallas"],
           [0, 10**9, "%fusion.1 bf16[1024,2048]", "matmul"]]
    ctx = SimpleNamespace(flops=flops, spec=spec, seq=1024, peaks=peaks,
                          jobs=[((8,), (1,), 1)], steps_per_job=1, passes=1,
                          window_ns=(0, 10**10), trace={"devices": {"0": ops}})
    assert mod.read(ctx) == pytest.approx(50.0, rel=1e-3)
    # a dense model, or a trace without the kernel, reads nothing
    assert mod.read(SimpleNamespace(**{**vars(ctx), "flops": object()})) is None
    ctx.trace = {"devices": {"0": ops[1:]}}
    assert mod.read(ctx) is None
