"""Per-model modules (``bench/models/``): the dense model's weights and sizes
are pinned, a model of a new name is taken as new files only, a model with a
missing module is refused by path, and the check compares matrices by name
whatever layers each name counts."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import harness  # noqa: E402
from bench.models import dense_lora  # noqa: E402
from bench.program import ADAM_B1, RecordingExecutor  # noqa: E402
from repro.cluster import SliceExecutor  # noqa: E402
from repro.cluster.executor import PackResult  # noqa: E402
from repro.configs.base import LoraConfig  # noqa: E402

TINY = dict(hidden_size=128, intermediate_size=256, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32, num_hidden_layers=2,
            vocab_size=512)
# sha256 of make_weights(7, spec) on the CPU for each configuration's file
# with TINY over it (name, shape, dtype and bf16 bits of every array in name
# order), taken before the dense code moved here from bench/weights.py
WEIGHTS_SHA256 = {
    "qwen25-7b-l6":
        "098026816d476589f6c4d4cb8a90a271ab7bf13eca7dce63e0441902e05345cb",
    "starcoder2-7b-l8":
        "c3b9a85879b65b561d59248d64ddb77d44b5bdb8b9a289a1453716d7373e2bea",
}
# spec() of each configuration file, as bench/weights.py's model_spec read it
SPECS = {
    "qwen25-7b-l6": {
        "d": 3584, "H": 28, "KV": 4, "hd": 128, "F": 18944, "L": 6,
        "V": 152064, "theta": 1000000.0, "eps": 1e-06, "norm": "rmsnorm",
        "mlp": "swiglu", "biases": ("q", "k", "v"),
        "targets": ("q", "k", "v", "o", "gate", "up", "down")},
    "starcoder2-7b-l8": {
        "d": 4608, "H": 36, "KV": 4, "hd": 128, "F": 18432, "L": 8,
        "V": 49152, "theta": 100000.0, "eps": 1e-06, "norm": "layernorm",
        "mlp": "gelu2", "biases": ("q", "k", "v", "up", "down"),
        "targets": ("q", "k", "v", "o", "up", "down")},
}


def config_file(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def digest(weights):
    h = hashlib.sha256()
    for name in sorted(weights):
        a = np.asarray(weights[name])
        h.update(name.encode())
        h.update(str(a.shape).encode())
        h.update(str(a.dtype).encode())
        h.update(a.view(np.uint16).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(WEIGHTS_SHA256))
def test_dense_weights_are_pinned(name):
    spec = dense_lora.spec(dict(config_file(name), **TINY))
    assert digest(dense_lora.make_weights(7, spec)) == WEIGHTS_SHA256[name]


@pytest.mark.parametrize("name", sorted(SPECS))
def test_dense_spec_is_pinned(name):
    assert dense_lora.spec(config_file(name)) == SPECS[name]


def test_reference_and_flops_import_nothing_of_the_program():
    """The reference reads the model module's shapes; the program comes in
    only with the functions that build the program's side."""
    code = ("import sys; import bench.reference.dense_lora, "
            "bench.flops.dense_lora; "
            "print([m for m in sys.modules if m.split('.')[0] == 'repro'])")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT))
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"


# ---------------------------------------------------------------------------
# a new model as new files only
# ---------------------------------------------------------------------------

NEW_MODEL = "grouped_lora"
CELL = "tiny-grouped.tiny-grid"
LIMITS = {"loss_gap": 1e-3, "grad_gap": 0.02, "update_gap": 0.02}
MIX = {"name": "tiny-grid", "seq": 128, "steps_per_job": 4, "noise": 0.1,
       "grid": [
           {"ranks": [8, 16], "batch_sizes": [1], "learning_rate": 1e-3,
            "alpha_over_rank": 1.0},
           {"ranks": [8, 16], "batch_sizes": [2], "learning_rate": 4e-3,
            "alpha_over_rank": 0.25}]}
GROUP = '''

def group(tree):
    """{attn.q | mlp.up | ...: x} of a {q | up | ...: x} tree."""
    return {("attn." if k in ("q", "k", "v", "o") else "mlp.") + k: v
            for k, v in tree.items()}
'''
MODEL_FILES = {
    "models": '''"""The dense model under grouped matrix names."""
from bench.models import dense_lora
from bench.models.dense_lora import (  # noqa: F401
    base_tree, make_weights, program_config, spec)
''' + GROUP + '''

def adapter_norms(tree):
    return group(dense_lora.adapter_norms(tree))
''',
    "reference": '''"""The dense reference under grouped matrix names."""
from bench.reference import dense_lora as ref
from bench.reference.dense_lora import init_adapters  # noqa: F401
''' + GROUP + '''

def train(*args, **kw):
    r = ref.train(*args, **kw)
    return dict(r, grads=[group(g) for g in r["grads"]],
                update=group(r["update"]))
''',
    "flops": '''"""The dense model's required work."""
from bench.flops.dense_lora import (  # noqa: F401
    job_flops, job_least_seconds, job_products)
''',
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark with a model of a new name: its three
    modules, a configuration, a mix, a limits file and one index line."""
    root = tmp_path_factory.mktemp("bench_root")
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    for part, text in MODEL_FILES.items():
        (root / "bench" / part / f"{NEW_MODEL}.py").write_text(text)
    cfg = dict(config_file("qwen25-7b-l6"), **TINY, name="tiny-grouped",
               model=NEW_MODEL)
    (root / "bench" / "configs" / "tiny-grouped.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "traffic" / "tiny-grid.json").write_text(json.dumps(MIX))
    (root / "bench" / "limits" / f"{CELL}.json").write_text(
        json.dumps({"limits": LIMITS}))
    index = json.loads((root / "BENCHMARK.json").read_text())
    index["workloads"].append({"name": CELL, "config": "tiny-grouped",
                               "traffic": "tiny-grid", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(index))
    changed = [str(p) for p, b in before.items()
               if p.name != "BENCHMARK.json" and p.read_bytes() != b]
    assert changed == []
    added = json.loads((root / "BENCHMARK.json").read_text())
    assert added["workloads"][:-1] == json.loads(before[
        root / "BENCHMARK.json"])["workloads"]
    return str(root)


def test_new_model_modules_are_found_by_name(root):
    _, _, cfg_file, _ = harness.find_cell(CELL, root=root)
    files = harness.model_files(cfg_file, root=root)
    assert files == {part: os.path.join(root, "bench", part,
                                        f"{NEW_MODEL}.py")
                     for part in harness.MODEL_PARTS}


def test_new_model_run_is_correct(root, monkeypatch):
    seen = {}
    pair = harness.pair

    def keep(records, refs):
        seen["readings"] = pair(records, refs)
        return seen["readings"]

    monkeypatch.setattr(harness, "pair", keep)
    bench, cell, cfg_file, mix = harness.find_cell(CELL, root=root)
    res = harness.run_cell(
        cell, cfg_file, mix, seed=7, seconds=0.2, trace=False,
        devices=jax.devices()[:1], kind="TPU v5 lite",
        peaks=harness.peaks_for("TPU v5 lite", root=root),
        limits=harness.load_limits(cell, root=root), root=root)
    assert res["correct"], res["checks"]
    assert res["metrics"]["sweep_tokens_per_s"]["value"] > 0
    # the program's records and the reference's readings came from the new
    # modules: both carry the grouped names
    for grad, upd, ref in seen["readings"].values():
        assert set(grad["norms"]) == set(upd["norms"]) == set(ref["update"]) \
            == {"attn.q", "attn.k", "attn.v", "attn.o", "mlp.gate", "mlp.up",
                "mlp.down"}


@pytest.mark.parametrize("missing", ["models", "reference", "flops"])
def test_a_missing_model_module_is_refused_by_path(tmp_path, missing):
    cfg = dict(config_file("qwen25-7b-l6"), name="lonely", model="lonely")
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "configs" / "lonely.json").write_text(json.dumps(cfg))
    (tmp_path / "bench" / "traffic" / "tiny-grid.json").write_text(
        json.dumps(MIX))
    for part in harness.MODEL_PARTS:
        if part != missing:
            (tmp_path / "bench" / part).mkdir()
            (tmp_path / "bench" / part / "lonely.py").write_text("")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({"workloads": [
        {"name": "lonely.tiny-grid", "config": "lonely",
         "traffic": "tiny-grid", "chips": 1, "why": "test"}]}))
    with pytest.raises(harness.Refused,
                       match=f"bench/{missing}/lonely.py is missing"):
        harness.find_cell("lonely.tiny-grid", root=str(tmp_path))


# ---------------------------------------------------------------------------
# names that count different layers
# ---------------------------------------------------------------------------

# two matrices of different depths: a layer kind of 5 and one of 4
LAYERS = {"attn.q": 5, "shared.up": 4}
D, R, N_STEPS = 16, 8, 3


class FakeSlice(SliceExecutor):
    """A slice executor whose packed run returns set trees: Adam's first
    moment ``(1 - b1) g`` of gradients ``g``, and the adapters moved by
    ``delta``, each {name: {a|b: (layers, slots, ...)}}, with ``losses``
    (steps, slots)."""

    def set(self, g, delta, losses):
        self.g, self.delta, self.losses = g, delta, losses
        self.tmpl = jax.tree.map(jnp.ones_like, delta)

    def pack_template(self, cfg, configs, seed=0):
        return self.tmpl, None

    def train_pack(self, cfg, configs, *, n_steps, step_callback=None, **kw):
        for i in range(n_steps):
            step_callback(i, {"per_adapter_loss": self.losses[i]})
        return PackResult(
            lora=jax.tree.map(jnp.add, self.tmpl, self.delta),
            opt={"m": jax.tree.map(lambda x: (1 - ADAM_B1) * x, self.g)},
            losses=self.losses[n_steps - 1], wall_seconds=0.0)


class Driven(RecordingExecutor, FakeSlice):
    pass


def norms(tree):
    """{name: {a|b: (layers, slots)}}: the stub model's adapter_norms."""
    return {k: {ab: jnp.sqrt(jnp.sum(jnp.square(x), (2, 3)))
                for ab, x in d.items()} for k, d in tree.items()}


def stub_tree(rng, n):
    # shared.up twice the scale, so its norms lie above the median matrix's
    return {name: {"a": jnp.asarray(rng.randn(L, n, D, R) * (1 + i),
                                    jnp.float32),
                   "b": jnp.asarray(rng.randn(L, n, R, D) * (1 + i),
                                    jnp.float32)}
            for i, (name, L) in enumerate(LAYERS.items())}


@pytest.mark.parametrize("flip", [None, "shared.up", "attn.q"])
def test_gaps_compare_names_of_different_depths(flip):
    """RecordingExecutor keeps what the model's ``adapter_norms`` names;
    ``gaps`` compares each name with the reference's reading of that name,
    and a 10% flip of one name's norm shows in ``grad_gap``."""
    rng = np.random.RandomState(0)
    configs = [LoraConfig(rank=R, alpha=R, learning_rate=1e-3, batch_size=b,
                          seq_len=128) for b in (1, 2)]
    g, delta = stub_tree(rng, 2), stub_tree(rng, 2)
    losses = rng.rand(N_STEPS, 2).astype(np.float32) + 1.0

    def adapter_norms(tree):
        out = norms(tree)
        if flip is not None:
            out[flip] = jax.tree.map(lambda x: 1.1 * x, out[flip])
        return out

    ex = Driven(adapter_norms)
    ex.set(g, delta, losses)
    for mode, n in (("grad", 1), ("update", N_STEPS)):
        ex.mode = mode
        ex.train_pack(None, configs, n_steps=n)
    refs = {}
    for slot, c in enumerate(configs):
        grad = jax.tree.map(lambda x: np.asarray(x[:, slot]), norms(g))
        refs[c.key()] = {
            "losses": losses[:, slot],
            "grads": [grad] * N_STEPS,
            "update": jax.tree.map(lambda x: np.asarray(x[:, slot]),
                                   norms(delta)),
        }
        rec = ex.records[("grad", c.key())]["norms"]
        assert {k: v["a"].shape for k, v in rec.items()} == {
            k: (L,) for k, L in LAYERS.items()}
    got = harness.gaps(harness.pair(ex.records, refs))
    assert got["loss_gap"] == 0.0
    if flip is None:
        assert got["grad_gap"] < 1e-6 and got["update_gap"] < 1e-6
    elif flip == "shared.up":  # above the median: the gap is the flip's
        assert got["grad_gap"] == pytest.approx(0.1, rel=1e-4)
        assert got["update_gap"] == pytest.approx(0.1, rel=1e-4)
    else:  # partly under the median: the gap is less, and still seen
        assert 0.02 < got["grad_gap"] <= 0.1 + 1e-6
