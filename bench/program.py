"""The system under test, as the benchmark drives it.

Everything here calls the program (``src/repro``): its configuration, the
planner, ``ExecutionEngine.run_local`` with ``ClusterRunner`` and
``SliceExecutor``. The benchmark gives the program its weights and its rows;
``RecordingExecutor`` reads what the timed path returns during the check
passes and adds no work to the window.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.cluster import ClusterRunner, DevicePool, SliceExecutor
from repro.configs.base import LoraConfig, get_config
from repro.sched.cost_model import CostModel, tpu_prior
from repro.sched.engine import ExecutionEngine
from repro.sched.planner import plan

ADAM_B1 = 0.9


def program_config(cfg_file: dict):
    """The program's ModelConfig with every size taken from the file."""
    cfg = get_config(cfg_file["arch"])
    attn = dataclasses.replace(
        cfg.attention,
        n_heads=cfg_file["num_attention_heads"],
        n_kv_heads=cfg_file["num_key_value_heads"],
        head_dim=cfg_file["head_dim"],
        rope_theta=float(cfg_file["rope_theta"]),
    )
    cfg = cfg.replace(
        n_layers=cfg_file["num_hidden_layers"],
        d_model=cfg_file["hidden_size"],
        d_ff=cfg_file["intermediate_size"],
        vocab_size=cfg_file["vocab_size"],
        attention=attn,
    )
    want = {"norm_kind": cfg_file["norm"], "mlp_kind": cfg_file["mlp"],
            "tie_embeddings": False,
            "lora_targets": tuple(cfg_file["lora_targets"])}
    # the program puts an adapter on each target its layers have
    has = ("q", "k", "v", "o") + (("up", "down") if cfg.mlp_kind == "gelu2"
                                  else ("gate", "up", "down"))
    got = {"norm_kind": cfg.norm_kind, "mlp_kind": cfg.mlp_kind,
           "tie_embeddings": cfg.tie_embeddings,
           "lora_targets": tuple(t for t in cfg.lora_targets if t in has)}
    if want != got:
        raise ValueError(f"{cfg_file['name']}: the program's {cfg.name} runs "
                         f"{got}, the file says {want}")
    return cfg


def lora_configs(points, seq):
    return [LoraConfig(rank=p["rank"], alpha=p["alpha"],
                       learning_rate=p["learning_rate"],
                       batch_size=p["batch_size"], seq_len=seq)
            for p in points]


def base_tree(weights: dict, cfg):
    """The program's base-parameter tree, filled with the benchmark's
    weights. A bias the program carries and the architecture lacks is
    zero."""
    from repro.models.model import init_model

    shapes = jax.eval_shape(
        lambda: init_model(jax.random.PRNGKey(0), cfg, None, jnp.bfloat16)[0])
    blocks = shapes["decoder"]["blocks"]
    if set(blocks) != {"l0"} or shapes["decoder"]["rest"]:
        raise ValueError("the benchmark maps stacks of one repeated layer only")

    def name_of(path):
        keys = [getattr(k, "key", None) for k in path]
        if keys[:1] == ["embed"]:
            return "embed"
        if keys[:1] == ["lm_head"]:
            return "lm_head"
        if keys[0] == "final_norm":
            return f"final_norm_{keys[1]}"
        return f"{keys[-2]}_{keys[-1]}"  # norm1_scale, q_w, up_b, ...

    def fill(path, sds):
        name = name_of(path)
        if name in weights:
            w = weights[name]
            if w.shape != sds.shape:  # vocabulary padded to a 256 multiple
                pad = [(0, t - s) for s, t in zip(w.shape, sds.shape)]
                w = jnp.pad(w, pad)
            return w
        if name.endswith("_b") or name.endswith("_bias"):
            return jnp.zeros(sds.shape, sds.dtype)
        raise KeyError(f"no benchmark weight for the program's {name}")

    return jax.tree_util.tree_map_with_path(fill, shapes)


def _per_leaf_norms(tree):
    """{proj: {a|b: (L, n)}} Frobenius norms of each layer's matrices of
    every adapter slot, from the program's packed adapter tree."""
    blocks = tree["decoder"]["blocks"]["l0"]
    out = {}
    for part in blocks.values():
        for proj, ab in part.items():
            out[proj] = {k: jnp.sqrt(jnp.sum(jnp.square(v), (2, 3)))
                         for k, v in ab.items()}
    return out


class RecordingExecutor(SliceExecutor):
    """A ``SliceExecutor`` that, while ``mode`` is set, keeps what each
    packed run returned: the per-adapter loss of every step, and per-matrix
    norms of the first gradient (``mode="grad"``, read from Adam's first
    moment) or of the change after the run (``mode="update"``). With
    ``mode=None`` it is the plain executor."""

    def __init__(self, *, tracer=None):
        super().__init__(tracer=tracer)
        self.mode = None
        self.records = {}

    def drop_templates(self):
        """Forget the cached initial adapters and optimizer state, so that
        a new LoRA seed's templates do not pile up on the device."""
        self._templates.clear()

    def train_pack(self, cfg, configs, **kw):
        if self.mode is None:
            return super().train_pack(cfg, configs, **kw)
        losses = []
        kw["step_callback"] = lambda i, m: losses.append(
            np.asarray(m["per_adapter_loss"]))
        res = super().train_pack(cfg, configs, **kw)
        if self.mode == "grad":
            norms = _per_leaf_norms(res.opt["m"])
            norms = jax.tree.map(lambda x: x / (1 - ADAM_B1), norms)
        else:
            tmpl, _ = self.pack_template(cfg, configs, kw.get("seed", 0))
            delta = jax.tree.map(
                lambda a, b: a - jax.device_put(b, a.sharding), res.lora, tmpl)
            norms = _per_leaf_norms(delta)
        norms = jax.tree.map(np.asarray, norms)
        ranks = tuple(c.rank for c in configs)
        for slot, c in enumerate(configs):
            self.records[(self.mode, c.key())] = {
                "losses": np.asarray([step[slot] for step in losses]),
                "norms": jax.tree.map(lambda x: x[:, slot], norms),
                "pack_ranks": ranks,
                "slot": slot,
            }
        return res


class Sweep:
    """One planned sweep on ``devices``: the plan, the engine and the
    runner whose compiled steps and templates every pass reuses."""

    def __init__(self, cfg, points, devices, *, seq, steps, kind,
                 tracer=None):
        self.cfg = cfg
        self.seq = seq
        self.configs = lora_configs(points, seq)
        self.cm = CostModel(cfg, tpu_prior(kind))
        self.schedule = plan(self.cm, self.configs, len(devices), seq, steps)
        self.executor = RecordingExecutor(tracer=tracer)
        self.runner = ClusterRunner(self.executor, DevicePool(list(devices)),
                                    tracer=tracer)
        self.engine = ExecutionEngine(self.cm, len(devices), tracer=tracer)

    def run_pass(self, base, *, n_steps, data_iter_fn, lora_seed):
        return self.engine.run_local(
            self.schedule, self.configs, self.cfg, base, n_steps=n_steps,
            seq=self.seq, data_iter_fn=data_iter_fn, seed=lora_seed,
            runner=self.runner)

    def jobs(self):
        """[(ranks, batch sizes, degree)] of each planned job."""
        return [
            (tuple(self.configs[i].rank for i in j.config_ids),
             tuple(self.configs[i].batch_size for i in j.config_ids),
             j.degree)
            for j in self.schedule.jobs
        ]
