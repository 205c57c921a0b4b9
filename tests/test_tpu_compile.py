"""Compile rehearsals of the Pallas kernels for a TPU v5e chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology. Each test compiles one kernel call at
qwen25-7b projection shapes (M = 1024 tokens per adapter, 4 adapters, rank
16) with ``interpret=False`` and asserts that the Mosaic kernel is in the
program (``tpu_custom_call``). This is what interpret mode cannot show:
block shapes the TPU tiling refuses, operations Mosaic cannot lower, and
kernels that ask for more VMEM than it allows.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused import DEFAULT_BLOCKS, fused_matmul
from repro.kernels.packed_matmul import packed_matmul

N, M, R = 4, 1024, 16
# (d_in, d_out) of qwen25-7b's projections: q/o, k/v, gate/up, down
PROJ = {
    "q": (3584, 3584),
    "kv": (3584, 512),
    "up": (3584, 18944),
    "down": (18944, 3584),
}
NF4_BLOCK = 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the persistent
    # cache, so keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("proj", sorted(PROJ))
def test_fused_dense_compiles(one_chip, proj, dtype):
    """Dense base at the default blocks, in both activation dtypes."""
    k, l = PROJ[proj]
    s = lambda shape, dt=dtype: _shape(one_chip, shape, dt)  # noqa: E731
    text = _compile(
        lambda x, w, a, b, al: fused_matmul(x, w, a, b, al, interpret=False),
        s((N, M, k)), s((k, l)), s((N, k, R)), s((N, R, l)),
        s((N,), jnp.float32),
    )
    assert "tpu_custom_call" in text
    assert DEFAULT_BLOCKS == (256, 256, 512)


@pytest.mark.parametrize("mode", ["int8", "nf4"])
@pytest.mark.parametrize("proj", sorted(PROJ))
def test_fused_quantized_compiles(one_chip, proj, mode):
    """Quantized base: codes and scales dequantized inside the K-loop."""
    k, l = PROJ[proj]
    s = lambda shape, dt=jnp.bfloat16: _shape(one_chip, shape, dt)  # noqa: E731
    if mode == "int8":
        codes, scales = s((k, l), jnp.int8), s((1, l), jnp.float32)
    else:
        codes = s((k // 2, l), jnp.uint8)
        scales = s((k // NF4_BLOCK, l), jnp.float32)
    text = _compile(
        lambda x, w, a, b, al, ws: fused_matmul(
            x, w, a, b, al, ws, interpret=False
        ),
        s((N, M, k)), codes, s((N, k, R)), s((N, R, l)),
        s((N,), jnp.float32), scales,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("proj", sorted(PROJ))
def test_fused_backward_dx_compiles(one_chip, proj):
    """The backward's ``dx = g @ W^T + d(xA) @ A^T``: the fused call on the
    transposed operands, as ``kernels/fused.py``'s backward makes it."""
    k, l = PROJ[proj]
    s = lambda shape, dt=jnp.bfloat16: _shape(one_chip, shape, dt)  # noqa: E731
    text = _compile(
        lambda g, w, a, b, al: fused_matmul(
            g, jnp.swapaxes(w, 0, 1), jnp.swapaxes(b, 1, 2),
            jnp.swapaxes(a, 1, 2), al, interpret=False,
        ),
        s((N, M, l)), s((k, l)), s((N, k, R)), s((N, R, l)),
        s((N,), jnp.float32),
    )
    assert "tpu_custom_call" in text


# the four backward dataflows of the two-pass delta (kernels/ops.py), at
# the up projection: (x, w) shapes of each grouped GEMM
_K, _L = PROJ["up"]
BACKWARD_CASES = {
    "dB = (xA)^T g": ((N, R, M), (N, M, _L)),
    "d(xA) = g B^T": ((N, M, _L), (N, _L, R)),
    "dA = x^T d(xA)": ((N, _K, M), (N, M, R)),
    "dx = d(xA) A^T": ((N, M, R), (N, R, _K)),
}


@pytest.mark.parametrize("case", sorted(BACKWARD_CASES))
def test_packed_matmul_backward_cases_compile(one_chip, case):
    xs, ws = BACKWARD_CASES[case]
    s = lambda shape, dt=jnp.bfloat16: _shape(one_chip, shape, dt)  # noqa: E731
    text = _compile(
        lambda x, w, al: packed_matmul(x, w, al, interpret=False),
        s(xs), s(ws), s((N,), jnp.float32),
    )
    assert "tpu_custom_call" in text


# DeepSeek-V2-Lite's routed experts: 64 experts of width 1408 over d 2048,
# top-6 rows of a (1, 2) pack at seq 1024
E_MOE, D_MOE, F_MOE, ROWS_MOE = 64, 2048, 1408, 3 * 1024 * 6


@pytest.mark.parametrize("zero_rest", [False, True])
def test_moe_gmm_compiles(one_chip, zero_rest):
    """The grouped matmul's three expert products forward and their input
    gradients (the same kernel against transposed weights); the kernel is
    named ``moe_gmm`` in the program, as the device trace names it."""
    from repro.kernels import moe_gmm as mg

    s = lambda shape, dt=jnp.bfloat16: _shape(one_chip, shape, dt)  # noqa: E731

    def experts(x, wg, wu, wd, sizes):
        def fwd(x):
            g = mg._gmm(x, wg, sizes, zero_rest=zero_rest, interpret=False)
            u = mg._gmm(x, wu, sizes, zero_rest=zero_rest, interpret=False)
            return mg._gmm(jax.nn.silu(g) * u, wd, sizes,
                           zero_rest=zero_rest, interpret=False)
        y = fwd(x)
        dh = mg._gmm(y, wd, sizes, transpose_rhs=True, zero_rest=zero_rest,
                     interpret=False)
        return y, mg._gmm(dh, wg, sizes, transpose_rhs=True,
                          zero_rest=zero_rest, interpret=False)

    text = _compile(
        experts, s((ROWS_MOE, D_MOE)), s((E_MOE, D_MOE, F_MOE)),
        s((E_MOE, D_MOE, F_MOE)), s((E_MOE, F_MOE, D_MOE)),
        s((E_MOE,), jnp.int32),
    )
    assert "tpu_custom_call" in text and "%moe_gmm" in text


def test_deepseek_grid_plan_fits_one_chip(one_chip, monkeypatch):
    """The planner's plan for the ten-point grid (ranks 8-128 x batch 1-2 at
    seq 1024) on DeepSeek-V2-Lite cut to 5 layers puts each pair on one
    chip, and the largest pack's step program, compiled for the chip with
    its Pallas kernels, fits in its memory with the frozen base."""
    from repro.configs.base import LoraConfig, get_config
    from repro.core.adapter import pack_meta
    from repro.kernels import fused, moe_gmm, ops
    from repro.models.model import init_lora, init_model
    from repro.sched.cost_model import CostModel, tpu_prior
    from repro.sched.planner import plan
    from repro.train.optimizer import init_opt_state
    from repro.train.trainer import make_packed_step

    for mod in (fused, moe_gmm, ops):  # compile the kernels, do not interpret
        monkeypatch.setattr(mod, "pallas_interpret", lambda: False)
    cfg = get_config("deepseek-v2-lite").replace(n_layers=5)
    configs = [LoraConfig(rank=r, alpha=a * r, learning_rate=lr, batch_size=b,
                          seq_len=1024)
               for b, lr, a in ((1, 1e-4, 1.0), (2, 4e-4, 0.25))
               for r in (8, 16, 32, 64, 128)]
    sched = plan(CostModel(cfg, tpu_prior("TPU v5 lite")), configs, 1, 1024, 4)
    jobs = [[configs[i] for i in j.config_ids] for j in sched.jobs]
    assert all(j.degree == 1 for j in sched.jobs)
    assert sorted(len(j) for j in jobs) == [2] * 5
    pack = max(jobs, key=lambda j: max(c.rank for c in j))
    meta = pack_meta(pack)
    put = lambda t: jax.tree.map(  # noqa: E731
        lambda x: _shape(one_chip, x.shape, x.dtype), t)
    lora = jax.eval_shape(lambda: init_lora(jax.random.PRNGKey(0), cfg, meta))
    base = jax.eval_shape(
        lambda: init_model(jax.random.PRNGKey(0), cfg, None, jnp.bfloat16)[0])
    opt = jax.eval_shape(lambda: init_opt_state(lora, n_pack=meta.n))
    nb = meta.n * meta.max_batch
    batch = {k: jax.ShapeDtypeStruct((nb, 1024), jnp.int32)
             for k in ("tokens", "labels")}
    vecs = (jax.ShapeDtypeStruct((meta.n,), jnp.float32),) * 2 + (
        jax.ShapeDtypeStruct((meta.n,), jnp.int32),)
    step = make_packed_step(cfg, meta.n, impl="pallas",
                            batch_sizes=meta.batch_sizes)
    compiled = step.lower(*put((base, lora, opt, batch) + vecs)).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert used < 15.75e9, used / 1e9
    text = compiled.as_text()
    assert "%moe_gmm" in text
    # the named scopes the device trace's operations carry in their metadata
    for scope in ("moe.route", "moe.dispatch", "moe.experts", "moe.combine",
                  "mla.attend"):
        assert f"/{scope}/" in text, scope
