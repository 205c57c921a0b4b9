"""The system under test, as the benchmark drives it.

Everything here calls the program (``src/repro``): the planner,
``ExecutionEngine.run_local`` with ``ClusterRunner`` and ``SliceExecutor``.
The benchmark gives the program its configuration, weights and rows; what
depends on the model's shape comes from its module (``bench/models/``).
``RecordingExecutor`` reads what the timed path returns during the check
passes and adds no work to the window.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.cluster import ClusterRunner, DevicePool, SliceExecutor
from repro.configs.base import LoraConfig
from repro.sched.cost_model import CostModel, tpu_prior
from repro.sched.engine import ExecutionEngine
from repro.sched.planner import plan

ADAM_B1 = 0.9


def lora_configs(points, seq):
    return [LoraConfig(rank=p["rank"], alpha=p["alpha"],
                       learning_rate=p["learning_rate"],
                       batch_size=p["batch_size"], seq_len=seq)
            for p in points]


class RecordingExecutor(SliceExecutor):
    """A ``SliceExecutor`` that, while ``mode`` is set, keeps what each
    packed run returned: the per-adapter loss of every step, and per-matrix
    norms of the first gradient (``mode="grad"``, read from Adam's first
    moment) or of the change after the run (``mode="update"``), as the
    model module's ``adapter_norms`` reads them from the adapter tree. With
    ``mode=None`` it is the plain executor."""

    def __init__(self, adapter_norms, *, tracer=None):
        super().__init__(tracer=tracer)
        self.adapter_norms = adapter_norms
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
            norms = self.adapter_norms(res.opt["m"])
            norms = jax.tree.map(lambda x: x / (1 - ADAM_B1), norms)
        else:
            tmpl, _ = self.pack_template(cfg, configs, kw.get("seed", 0))
            delta = jax.tree.map(
                lambda a, b: a - jax.device_put(b, a.sharding), res.lora, tmpl)
            norms = self.adapter_norms(delta)
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
    runner whose compiled steps and templates every pass reuses.
    ``adapter_norms`` is the model module's."""

    def __init__(self, cfg, points, devices, *, seq, steps, kind,
                 adapter_norms, tracer=None):
        self.cfg = cfg
        self.seq = seq
        self.configs = lora_configs(points, seq)
        self.cm = CostModel(cfg, tpu_prior(kind))
        self.schedule = plan(self.cm, self.configs, len(devices), seq, steps)
        self.executor = RecordingExecutor(adapter_norms, tracer=tracer)
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
