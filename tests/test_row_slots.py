"""Row slots: a mixed-batch pack's step computes only its adapters' real rows.

The padded step (``batch_sizes=None``) runs every adapter at the pack's
largest batch, with the missing rows ignored by the loss. The row-slot step
takes the real rows out and runs each as one slot carrying its owner's LoRA
weights and scale. Both must train each adapter alike: the same per-adapter
losses, gradients, adapters and Adam moments, in float32 to 1e-5 relative.
A pack whose batch sizes are all equal keeps the padded trace bit for bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import LoraConfig, get_config, reduced
from repro.core.adapter import pack_meta
from repro.models.model import init_model
from repro.train.data import packed_batch_iterator
from repro.train.optimizer import init_opt_state
from repro.train.trainer import (
    make_packed_step,
    make_train_step,
    packed_loss_fn,
    row_slots,
    step_rows,
)

CFG = reduced(get_config("qwen25-7b"), d_model=128)
SEQ = 16
RTOL = 1e-5
B2 = 0.999  # AdamW's second-moment decay in the packed step
ADAM_ILL = 1e-6  # root second moment under which Adam's update is ill-posed
ADAM_REACH = 2 * 3e-3  # the most one step moves an element: 2 x largest lr


def _configs(ranks, batches):
    return [
        LoraConfig(rank=r, alpha=2.0 * r, learning_rate=1e-3 * (i + 1),
                   batch_size=b)
        for i, (r, b) in enumerate(zip(ranks, batches))
    ]


def _setup(configs):
    meta = pack_meta(configs)
    base, lora = init_model(jax.random.PRNGKey(0), CFG, meta)
    # B starts at zero: give it values so every gradient path carries signal
    lora = jax.tree_util.tree_map_with_path(
        lambda p, x: (0.05 * jax.random.normal(jax.random.PRNGKey(1), x.shape)
                      if getattr(p[-1], "key", None) == "b" else x),
        lora,
    )
    it = packed_batch_iterator(CFG, configs, seq=SEQ)
    return meta, base, lora, [next(it) for _ in range(3)]


def _vecs(meta):
    return meta.scales(), meta.lr_vector(), jnp.full((meta.n,), 2**31 - 1,
                                                      jnp.int32)


def _step(meta, batch_sizes, impl, ranks):
    return make_packed_step(CFG, meta.n, impl=impl, ranks=ranks,
                            batch_sizes=batch_sizes)


def _losses(step, meta, base, lora, batches):
    """Per-adapter losses of consecutive steps from ``lora``."""
    scales, lr, budg = _vecs(meta)
    lora = _copy(lora)  # the step donates its state
    opt = init_opt_state(lora, n_pack=meta.n)
    out = []
    for b in batches:
        lora, opt, m = step(base, lora, opt, b, scales, lr, budg)
        out.append(np.asarray(m["per_adapter_loss"]))
    return np.stack(out)


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


def _grads(meta, base, lora, batch, *, batch_sizes, impl, ranks):
    from repro.kernels.ops import KernelConfig

    kcfg = KernelConfig(impl=impl, ranks=ranks)
    return jax.jit(jax.grad(
        lambda lo: packed_loss_fn(lo, base, batch, CFG, meta.n, meta.scales(),
                                  kcfg=kcfg, batch_sizes=batch_sizes)[0]
    ))(lora)


def _close(a, b, ill=None):
    """Every leaf of ``a`` within RTOL of ``b``'s largest magnitude; where
    ``ill`` marks elements, within the reach of one Adam step there."""
    ill = jax.tree.leaves(ill) if ill is not None else [None] * len(
        jax.tree.leaves(b))
    for x, y, bad in zip(jax.tree.leaves(a), jax.tree.leaves(b), ill):
        x, y = np.asarray(x), np.asarray(y)
        d = np.abs(x - y)
        scale = max(float(np.abs(y).max()), 1e-30)
        if bad is not None:
            assert bad.mean() < 1e-3, bad.mean()
            assert (d[bad] <= ADAM_REACH).all(), d[bad].max()
            d = d[~bad]
        assert d.max() <= RTOL * scale, (d.max(), scale)


def _adam_ill(opt):
    """Elements whose Adam update divided by a root second moment under
    ADAM_ILL: there the update g / (sqrt(v) + 1e-8) turns float32 rounding
    of a near-zero gradient into a change of up to the learning rate."""
    step = np.asarray(opt["step"]).max()
    return jax.tree.map(
        lambda v: (0 < np.sqrt(np.asarray(v) / (1 - B2 ** step)))
        & (np.sqrt(np.asarray(v) / (1 - B2 ** step)) < ADAM_ILL),
        opt["v"])


CASES = [
    ((8, 8), (1, 2), "xla"),
    ((8, 8), (2, 1), "xla"),
    ((8, 8, 8), (1, 2, 2), "xla"),
    ((8, 16), (1, 2), "xla"),
    ((8, 8), (1, 2), "pallas"),
    ((8, 8), (2, 1), "pallas"),
    ((8, 8, 8), (1, 2, 2), "pallas"),
    ((8, 16), (1, 2), "pallas"),
]


@pytest.mark.parametrize("ranks,batches,impl", CASES)
def test_row_slot_step_matches_padded_step(ranks, batches, impl):
    configs = _configs(ranks, batches)
    meta, base, lora, data = _setup(configs)
    kr = meta.ranks if len(set(meta.ranks)) > 1 else None
    g_pad = _grads(meta, base, lora, data[0], batch_sizes=None, impl=impl,
                   ranks=kr)
    g_slot = _grads(meta, base, lora, data[0], batch_sizes=meta.batch_sizes,
                    impl=impl, ranks=kr)
    _close(g_slot, g_pad)
    pad = _step(meta, None, impl, kr)
    slot = _step(meta, meta.batch_sizes, impl, kr)
    # three steps each way from the same start
    np.testing.assert_allclose(_losses(slot, meta, base, lora, data),
                               _losses(pad, meta, base, lora, data), rtol=RTOL)
    # and each of the three steps from the padded run's state: the adapters
    # and Adam moments a step returns
    scales, lr, budg = _vecs(meta)
    opt = init_opt_state(lora, n_pack=meta.n)
    for b in data:
        lo_p, opt_p, m_p = pad(base, _copy(lora), _copy(opt), b, scales, lr,
                               budg)
        lo_s, opt_s, m_s = slot(base, _copy(lora), _copy(opt), b, scales, lr,
                                budg)
        np.testing.assert_allclose(np.asarray(m_s["per_adapter_loss"]),
                                   np.asarray(m_p["per_adapter_loss"]),
                                   rtol=RTOL)
        _close(lo_s, lo_p, _adam_ill(opt_p))
        _close(opt_s["m"], opt_p["m"])
        _close(opt_s["v"], opt_p["v"])
        assert np.array_equal(np.asarray(opt_s["step"]),
                              np.asarray(opt_p["step"]))
        lora, opt = lo_p, opt_p


def test_uniform_batch_pack_is_bit_identical_to_the_padded_step():
    configs = _configs((8, 16), (2, 2))
    meta, base, lora, data = _setup(configs)
    a = _losses(_step(meta, None, "xla", meta.ranks), meta, base, lora, data)
    b = _losses(_step(meta, meta.batch_sizes, "xla", meta.ranks), meta, base,
                lora, data)
    assert np.array_equal(a, b)
    # the jitted programs are the same program
    args = (base, lora, init_opt_state(lora, n_pack=meta.n), data[0],
            *_vecs(meta))  # lowered only: nothing is donated
    assert (_step(meta, None, "xla", meta.ranks).lower(*args).as_text()
            == _step(meta, meta.batch_sizes, "xla", meta.ranks)
            .lower(*args).as_text())


def test_make_train_step_runs_row_slots_on_mixed_batches():
    """``launch/train.py``'s step shares the loss: mixed packs take slots."""
    configs = _configs((8, 8), (1, 2))
    meta, base, lora, data = _setup(configs)
    step = make_train_step(CFG, meta, impl="xla", jit=False)
    _, _, m = step(base, lora, init_opt_state(lora, n_pack=meta.n), data[0])
    pad = _losses(_step(meta, None, "xla", None), meta, base, lora, data[:1])
    np.testing.assert_allclose(np.asarray(m["per_adapter_loss"]), pad[0],
                               rtol=RTOL)


def test_row_slots_and_step_rows():
    assert row_slots((2, 2)) is None and step_rows((2, 2)) == 4
    assert row_slots((1, 2)) == (0, 1, 1) and step_rows((1, 2)) == 3
    assert row_slots((2, 1)) == (0, 0, 1)
    assert row_slots((1, 2, 2)) == (0, 1, 1, 2, 2)
    assert row_slots((3,)) is None and step_rows((3,)) == 3
