"""Continuous-batching multi-LoRA serving engine (train-to-serve tier).

The decode batch has a fixed width of ``rows`` independent slots. Each row
carries its *own* adapter — the packed-LoRA delta dispatch that accelerates
tuning (``lora_linear`` over ``n_pack`` adapters) runs here at row
granularity: ``n_pack == rows`` with a per-row batch of 1, per-row scales,
and per-row decode positions (the vector-``pos`` path of
``models.model.decode_step``). Admission and retirement are per *token
step*: when a row finishes its request, the next queued request is admitted
into that row on the following step — the batch never drains. With
``prefill_chunk`` set, admission streams the prompt into a row-private
exact-capacity cache in bounded chunks interleaved with decode steps
(``models.model.prefill_chunk``), so other rows keep emitting while a long
prompt fills; the default (None) is the legacy synchronous one-shot prefill.
Either way the resulting row state — and every emitted token — is bitwise
identical to the sequential baseline's.

Three pieces:

``AdapterSlotCache``
    Fixed-capacity host-side staging for adapter weights, LRU-evicted.
    Misses load from a :class:`~repro.train.checkpoint.CheckpointPool`;
    ``publish()`` injects an adapter straight from a finished training job
    (the tune-then-serve handoff — no disk round trip). Adapters referenced
    by active rows are pinned and never evicted.

``ServeExecutor``
    The compile cache for serving, mirroring ``SliceExecutor``'s keyed-
    closure idiom: one jitted prefill and one jitted decode step per
    ``(cfg, n_rows, dist, ...)`` key, with ``scales`` as a *runtime*
    argument so admission never recompiles. ``serve.decode.generate`` routes
    through the process-default instance (``default_executor()``) instead of
    rebuilding its closures per call.

``ServeEngine``
    The event loop. It also implements the
    :class:`~repro.cluster.api.Runner` protocol: ``run()`` executes planned
    *training* segments through an inner
    :class:`~repro.cluster.runner.ClusterRunner` on the engine's own
    ``DevicePool``, so a live decode loop (holding ``serve_lease()``) and a
    training schedule share one pool — training blocks at planned-unit
    acquisition when serving holds capacity (serve priority), and rebalances
    at the budget-capped preemption boundaries the planner already emits.

Bit-exactness: decode rows are computed independently (batched einsums), so
a row served in a width-``rows`` continuous batch emits exactly the tokens
the same request emits under width-1 sequential decode — for dense models.
MoE capacity couples rows; serve bit-exactness claims use non-MoE configs.
"""
from __future__ import annotations

import time
from collections import OrderedDict, deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import LoraConfig, ModelConfig
from repro.core.adapter import PackMeta, pack_meta
from repro.core.packed_lora import extract_adapter, inject_adapter
from repro.models.model import decode_step, init_lora, prefill, prefill_chunk
from repro.obs import NULL_TRACER, Histogram
from repro.serve.decode import align_prefill_chunk, pad_caches


# ---------------------------------------------------------------------------
# Request / result / stats surface
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ServeRequest:
    """One decode request against one adapter.

    ``arrival`` is in virtual time (decode steps since trace start) so
    admission order is deterministic and replayable; wall-clock SLO numbers
    are measured separately on the result. ``rank``/``alpha`` override the
    adapter checkpoint's own metadata when that lacks them.

    ``temperature``/``top_k`` select per-request sampling: 0.0 temperature
    (the default) is greedy argmax — the engine's bit-exactness baseline —
    and any positive temperature switches that row to top-k/temperature
    sampling. Both are *runtime* values of the jitted sample step, so mixing
    greedy and sampled rows in one batch never recompiles.

    ``deadline_ms`` is a wall-clock SLO measured from the moment the request
    entered the engine's queue: a queued request already past it is rejected
    before any prefill work (``error="deadline"``, zero tokens), and an
    in-flight row that goes overdue retires as a *partial* result — tokens
    emitted so far, pins released — with the same ``error`` marker."""

    request_id: int
    adapter_id: str
    prompt: np.ndarray  # (S,) int32 token ids
    max_new_tokens: int = 16
    arrival: float = 0.0
    rank: Optional[int] = None
    alpha: Optional[float] = None
    extra: Optional[dict] = None  # extra prefill batch fields (VLM frames..)
    temperature: float = 0.0  # 0.0 = greedy (bit-exactness baseline)
    top_k: int = 0  # 0 = full vocabulary (no top-k truncation)
    deadline_ms: Optional[float] = None  # wall SLO from enqueue; None = none


@dataclass
class ServeResult:
    """Emitted tokens + admission/latency accounting for one request.

    ``error`` is None for a served request. A request the engine *rejects at
    admission* (oversized prompt, unresolvable rank/alpha) comes back with
    ``error`` set, zero tokens, and admitted == finished at the rejection
    point — the drain keeps serving every other request instead of raising
    mid-flight with active rows abandoned. ``tokens`` may also be shorter
    than ``max_new_tokens`` (with ``error`` None) when a ``max_steps`` bound
    retired the row early — a partial result, not a failure. A blown
    ``deadline_ms`` marks the result ``error="deadline"``: zero tokens if it
    expired in the queue, the partial tokens if it expired in flight."""

    request_id: int
    adapter_id: str
    tokens: np.ndarray  # (<= max_new_tokens,) int32
    n_prompt: int
    arrival: float  # virtual steps (copied from the request)
    admitted_step: int  # virtual step at admission
    finished_step: int  # virtual step when the last token was emitted
    admitted_wall: float  # seconds since serve() start
    finished_wall: float
    error: Optional[str] = None  # admission-rejection reason

    @property
    def queue_steps(self) -> float:
        """Admission delay in decode steps (the virtual-time SLO)."""
        return self.admitted_step - self.arrival

    @property
    def latency_wall(self) -> float:
        return self.finished_wall - self.admitted_wall


@dataclass
class ServeStats:
    """Aggregate outcome of one ``ServeEngine.serve`` drain.

    The latency histograms are always on (a histogram record is one lock +
    one float append, tracer or not): ``ttft`` is seconds from a request
    entering the engine's queue (``submit()`` or trace arrival) to its
    first emitted token, ``itl`` is the gap between a row's consecutive
    emitted tokens — recorded once per decoding row per step, with any
    admission/prefill work that ran between the two tokens included, so
    prefill stalls show up where the request actually felt them — and
    ``queue_wait`` is seconds from enqueue to the start of admission
    (rejected requests record neither). Percentiles via e.g.
    ``stats.ttft.summary()["p95"]``."""

    results: List[ServeResult] = field(default_factory=list)
    steps: int = 0  # decode steps executed
    tokens_emitted: int = 0
    occupancy_sum: int = 0  # sum over steps of active rows
    wall_seconds: float = 0.0
    cache_hits: int = 0
    cache_misses: int = 0
    cache_evictions: int = 0
    ttft: Histogram = field(default_factory=lambda: Histogram("serve.ttft"))
    itl: Histogram = field(default_factory=lambda: Histogram("serve.itl"))
    queue_wait: Histogram = field(
        default_factory=lambda: Histogram("serve.queue_wait")
    )

    @property
    def adapters_served(self) -> int:
        return len({r.adapter_id for r in self.results})

    @property
    def tokens_per_s(self) -> float:
        return self.tokens_emitted / self.wall_seconds if self.wall_seconds else 0.0

    @property
    def mean_occupancy(self) -> float:
        return self.occupancy_sum / self.steps if self.steps else 0.0

    def latency_summaries(self) -> Dict[str, Dict[str, float]]:
        """``{ttft, itl, queue_wait}`` percentile summaries, in seconds."""
        return {
            "ttft": self.ttft.summary(),
            "itl": self.itl.summary(),
            "queue_wait": self.queue_wait.summary(),
        }


def poisson_requests(
    adapter_ids: Sequence[str],
    prompts: Sequence[np.ndarray],
    mean_interarrival: float,
    *,
    max_new_tokens: int = 16,
    seed: int = 0,
) -> List[ServeRequest]:
    """A Poisson request trace (arrival gaps ~ Exp(mean_interarrival), in
    decode steps) — the serving analogue of ``sched.engine.poisson_trace``,
    shifted so the first request arrives at t=0."""
    assert len(adapter_ids) == len(prompts)
    rng = np.random.RandomState(seed)
    gaps = rng.exponential(mean_interarrival, size=len(adapter_ids))
    times = np.cumsum(gaps) - gaps[0]
    return [
        ServeRequest(
            request_id=i,
            adapter_id=aid,
            prompt=np.asarray(p, np.int32),
            max_new_tokens=max_new_tokens,
            arrival=float(t),
        )
        for i, (aid, p, t) in enumerate(zip(adapter_ids, prompts, times))
    ]


# ---------------------------------------------------------------------------
# Adapter slot cache
# ---------------------------------------------------------------------------


class AdapterSlotCache:
    """Fixed-capacity LRU cache of host-side adapter weights.

    ``get`` loads from the checkpoint pool on miss; ``publish`` inserts an
    in-memory adapter directly (tune-then-serve: the training job's final
    weights go straight into a serve slot, no disk round trip). ``pin``ned
    adapters (referenced by active decode rows) are never evicted; if every
    slot is pinned the cache refuses a new insert rather than silently
    growing past capacity."""

    def __init__(self, capacity: int, pool=None, *, metrics=None):
        assert capacity >= 1
        self.capacity = capacity
        self.pool = pool
        self._slots: "OrderedDict[str, Tuple[dict, dict]]" = OrderedDict()
        self._pins: Dict[str, int] = {}
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        # optional MetricsRegistry: mirrors the local counters into the
        # run-wide registry (serve.adapter_cache_*) when tracing is on
        self.metrics = metrics if metrics is not None else NULL_TRACER.metrics

    def __contains__(self, adapter_id: str) -> bool:
        return adapter_id in self._slots

    def __len__(self) -> int:
        return len(self._slots)

    def ids(self) -> List[str]:
        """Slot ids in LRU order (least-recently-used first)."""
        return list(self._slots)

    def pin(self, adapter_id: str) -> None:
        self._pins[adapter_id] = self._pins.get(adapter_id, 0) + 1

    def unpin(self, adapter_id: str) -> None:
        n = self._pins.get(adapter_id, 0) - 1
        if n <= 0:
            self._pins.pop(adapter_id, None)
        else:
            self._pins[adapter_id] = n

    def _evict_to_fit(self) -> None:
        while len(self._slots) >= self.capacity:
            victim = next(
                (aid for aid in self._slots if aid not in self._pins), None
            )
            if victim is None:
                raise RuntimeError(
                    f"all {self.capacity} adapter slots are pinned by active "
                    "rows; cannot admit a new adapter (raise slot_capacity "
                    "or lower rows)"
                )
            self._slots.pop(victim)
            self.evictions += 1
            self.metrics.counter("serve.adapter_cache_evictions").inc()

    def publish(self, adapter_id: str, adapter_tree: dict, meta: dict) -> None:
        """Insert (or refresh) an adapter from memory — no pool involved."""
        if adapter_id in self._slots:
            self._slots.pop(adapter_id)
        else:
            self._evict_to_fit()
        self._slots[adapter_id] = (adapter_tree, dict(meta))

    def get(self, adapter_id: str) -> Tuple[dict, dict]:
        if adapter_id in self._slots:
            self.hits += 1
            self.metrics.counter("serve.adapter_cache_hits").inc()
            self._slots.move_to_end(adapter_id)
            return self._slots[adapter_id]
        self.misses += 1
        self.metrics.counter("serve.adapter_cache_misses").inc()
        if self.pool is None or not self.pool.has(adapter_id):
            raise KeyError(
                f"adapter {adapter_id!r} is neither staged nor in the "
                "checkpoint pool"
            )
        tree = self.pool.load_adapter(adapter_id)
        meta = self.pool.load_meta(adapter_id)
        self._evict_to_fit()
        self._slots[adapter_id] = (tree, dict(meta))
        return self._slots[adapter_id]


# ---------------------------------------------------------------------------
# Compile-cached serve executor
# ---------------------------------------------------------------------------


def sample_tokens(lg, temp, topk, rng):
    """Per-row temperature/top-k sampling over last-position logits.

    lg: (R, V) f32; temp: (R,) f32; topk: (R,) int32 (0 = full vocab);
    rng: one PRNG key (rows draw independent streams from it via the
    batched categorical). Rows with ``temp == 0`` return the greedy argmax
    bit-exactly — the where() keeps greedy rows on the identical argmax
    value even inside a mixed batch. All of temp/topk/rng are runtime
    values: changing them never recompiles the step."""
    v = lg.shape[-1]
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    # top-k as a sort threshold: keep logits >= the k-th largest, -inf the
    # rest. k is clamped per row; 0 means "no truncation" (k = V).
    k_eff = jnp.clip(jnp.where(topk > 0, topk, v), 1, v)
    sorted_lg = jnp.sort(lg, axis=-1)  # ascending
    thresh = jnp.take_along_axis(sorted_lg, (v - k_eff)[:, None], axis=-1)
    masked = jnp.where(lg >= thresh, lg, -jnp.inf)
    t = jnp.maximum(temp, 1e-6)[:, None]
    sampled = jax.random.categorical(rng, masked / t, axis=-1).astype(
        jnp.int32
    )
    return jnp.where(temp > 0.0, sampled, greedy)


class ServeExecutor:
    """Keyed compile cache for serving (the ``SliceExecutor`` idiom).

    ``scales`` is a runtime argument of both closures, so adapter churn
    (admission changes a row's effective alpha/r) never recompiles; jax's
    own shape specialization inside each jitted callable handles scalar- vs
    vector-``pos`` and varying prompt lengths."""

    def __init__(self):
        self._fns: Dict[Tuple, Callable] = {}

    @property
    def cache_size(self) -> int:
        return len(self._fns)

    def step_fn(self, cfg: ModelConfig, n_rows: int, *, dist=None, kcfg=None):
        """Jitted one-token decode: ``(base, lora, scales, caches, token
        (R,1), pos () or (R,)) -> (next_tok (R,), logits, caches)``."""
        key = ("step", cfg, n_rows, dist, kcfg)
        if key not in self._fns:

            def step(base, lora, scales, caches, token, pos):
                lg, caches = decode_step(
                    base, lora, scales, token, caches, pos, cfg,
                    n_pack=n_rows, dist=dist, kcfg=kcfg,
                )
                next_tok = jnp.argmax(lg[:, -1, :], axis=-1).astype(jnp.int32)
                return next_tok, lg, caches

            self._fns[key] = jax.jit(step, donate_argnums=(3,))
        return self._fns[key]

    def sample_step_fn(
        self, cfg: ModelConfig, n_rows: int, *, dist=None, kcfg=None
    ):
        """Jitted one-token decode with per-row temperature/top-k sampling:
        ``(base, lora, scales, caches, token, pos, temp (R,), topk (R,),
        rng key) -> (next_tok (R,), logits, caches)``. Compiled once per
        (cfg, n_rows, dist, kcfg) like ``step_fn`` — temp/topk/rng are
        runtime arguments, so per-request sampling churn never recompiles;
        rows with ``temp == 0`` stay greedy (``sample_tokens``)."""
        key = ("sample_step", cfg, n_rows, dist, kcfg)
        if key not in self._fns:

            def step(base, lora, scales, caches, token, pos, temp, topk, rng):
                lg, caches = decode_step(
                    base, lora, scales, token, caches, pos, cfg,
                    n_pack=n_rows, dist=dist, kcfg=kcfg,
                )
                next_tok = sample_tokens(lg[:, -1, :], temp, topk, rng)
                return next_tok, lg, caches

            self._fns[key] = jax.jit(step, donate_argnums=(3,))
        return self._fns[key]

    def prefill_fn(
        self, cfg: ModelConfig, n_rows: int, *, dist=None,
        chunk_q: int = 512, kcfg=None,
    ):
        """Jitted prefill: ``(base, lora, scales, batch) -> (last-pos logits
        (R,1,V), caches)``."""
        key = ("prefill", cfg, n_rows, dist, chunk_q, kcfg)
        if key not in self._fns:

            def prefill_(base, lora, scales, batch):
                return prefill(
                    base, lora, scales, batch, cfg,
                    n_pack=n_rows, dist=dist, chunk_q=chunk_q, kcfg=kcfg,
                )

            self._fns[key] = jax.jit(prefill_)
        return self._fns[key]

    def prefill_chunk_fn(
        self, cfg: ModelConfig, n_rows: int, *, dist=None, kcfg=None
    ):
        """Jitted chunk-resumable prefill step: ``(base, lora, scales,
        tokens (R,C), caches, pos) -> (last-pos logits (R,1,V), caches)``,
        caches donated (the engine advances a row's in-progress cache in
        place). One closure per (cfg, n_rows, dist, kcfg); jit's shape
        specialization keys the compiled executables on the (chunk, cache
        capacity) shapes, so a burst of same-shaped admissions reuses them —
        and each compiled unit is chunk-sized, unlike ``prefill_fn`` which
        specializes (and stalls) per full prompt length."""
        key = ("prefill_chunk", cfg, n_rows, dist, kcfg)
        if key not in self._fns:

            def chunk_(base, lora, scales, tokens, caches, pos):
                return prefill_chunk(
                    base, lora, scales, tokens, caches, pos, cfg,
                    n_pack=n_rows, dist=dist, kcfg=kcfg,
                )

            self._fns[key] = jax.jit(chunk_, donate_argnums=(4,))
        return self._fns[key]


_DEFAULT_EXECUTOR: Optional[ServeExecutor] = None


def default_executor() -> ServeExecutor:
    """Process-wide ServeExecutor — ``generate()`` and every engine that
    doesn't bring its own share one compile cache."""
    global _DEFAULT_EXECUTOR
    if _DEFAULT_EXECUTOR is None:
        _DEFAULT_EXECUTOR = ServeExecutor()
    return _DEFAULT_EXECUTOR


# ---------------------------------------------------------------------------
# Row-granular cache write
# ---------------------------------------------------------------------------


def write_row_caches(caches, row_caches, row):
    """Write a width-1 tree into row ``row`` of a width-R tree (decode
    caches *or* packed lora params — both share the layout convention).
    Under a scan-stacked ``"blocks"`` subtree every leaf carries an extra
    leading layer axis, shifting the batch/pack axis from 0 to 1; with that
    one shift a single ``dynamic_update_slice`` at batch-index ``row``
    (zeros elsewhere) covers every leaf kind — seq-indexed k/v/ckv/k_rope
    (update spans ``[0, s_prompt)`` of the seq axis, stale tail is masked by
    the row's position), fixed-size ssm conv/state, cross_kv, and lora a/b.
    jit-safe with ``row`` traced (the engine jits it with the width-R tree
    donated, so admission is an in-place device row write, not a host
    round trip)."""

    def walk(t, s, in_blocks):
        if isinstance(t, dict):
            return {
                k: walk(t[k], s[k], in_blocks or k == "blocks") for k in t
            }
        if t is None or s is None:
            return t
        start = [0] * t.ndim
        start[1 if in_blocks else 0] = row
        return jax.lax.dynamic_update_slice(
            t, s.astype(t.dtype), tuple(start)
        )

    return walk(caches, row_caches, False)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


@dataclass
class _PrefillState:
    """Per-row progress of a chunked, decode-interleaved prefill.

    The row owns a width-1 f32 cache sized *exactly* to its prompt — the
    shapes every chunk's attention sees are then identical to the one-shot
    prefill's, which is what makes the interleaved path bitwise equal to
    the synchronous one (see ``models.model.prefill_chunk``). The cache is
    zero-padded to ``smax`` and row-written (with the engine-wide bf16
    cast) only once the whole prompt is in."""

    lora1: Any  # width-1 device lora tree for this row's adapter
    scale: float
    caches: Any  # width-1 f32 cache tree, capacity == len(prompt)
    prompt: np.ndarray  # (S,) int32
    filled: int = 0  # tokens already written into the cache
    logits: Any = None  # last chunk's final-position logits (1,1,V)


@dataclass
class _ActiveRow:
    request: ServeRequest
    emitted: List[int]
    admitted_step: int
    admitted_wall: float
    n_prompt: int
    # wall (serve-relative) of this row's last emitted token: consecutive-
    # token gaps — the ITL each request actually observes, admission stalls
    # included — are measured against it
    last_emit_wall: float = 0.0
    # in-progress chunked prefill; None once the row is decoding
    prefill: Optional[_PrefillState] = None


class ServeEngine:
    """Continuous-batching decode over ``rows`` adapter slots.

    Also a :class:`~repro.cluster.api.Runner`: ``run()`` executes planned
    training segments through an inner ``ClusterRunner`` on this engine's
    ``device_pool``, so serving (which reserves capacity via
    ``serve_lease()``) and training share devices — the tune side of
    tune-then-serve runs concurrently with the serve side."""

    def __init__(
        self,
        cfg: ModelConfig,
        base_params,
        *,
        rows: int = 4,
        smax: int = 64,
        r_bucket: int = 8,
        slot_capacity: int = 8,
        prefill_chunk: Optional[int] = None,
        checkpoint_pool=None,
        device_pool=None,
        serve_executor: Optional[ServeExecutor] = None,
        train_executor=None,
        dist=None,
        impl: Optional[str] = None,
        remat: Optional[str] = None,
        base_dtype: Optional[str] = None,
        seed: int = 0,
        tracer=None,
    ):
        from repro.cluster.pool import DevicePool
        from repro.cluster.runner import ClusterRunner

        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.cfg = cfg
        self.rows = rows
        self.smax = smax
        self.dist = dist
        # chunked, decode-interleaved admission: at most this many prompt
        # tokens are prefilled per engine iteration (rounded up to the SSD
        # sub-chunk grid on SSM stacks — bitwise-safe resume boundaries);
        # None = legacy synchronous one-shot prefill at admission
        self.prefill_chunk = align_prefill_chunk(cfg, prefill_chunk)
        # uniform engine-wide rank bucket: every admitted adapter is
        # zero-padded to r_bucket at injection, so the pack shape — and the
        # compiled step — never changes across admissions
        self.meta = pack_meta(
            [LoraConfig(rank=r_bucket, alpha=float(r_bucket))] * rows
        )
        self.meta1 = pack_meta([LoraConfig(rank=r_bucket, alpha=float(r_bucket))])
        # per-adapter delta dispatch at row granularity: the pack's kernel
        # policy rides into prefill and every decode step. ``base_dtype``
        # marks a quantized base (kernels/quant.py): prefill, every decode
        # row, and the training Runner side all share the SAME quantized
        # base_params tree — quantize once, serve + tune from it.
        self.kcfg = (
            self.meta.kernel_config(impl=impl, remat=remat,
                                    base_dtype=base_dtype)
            if (impl or remat or base_dtype) else None
        )
        self.kcfg1 = (
            self.meta1.kernel_config(impl=impl, remat=remat,
                                     base_dtype=base_dtype)
            if (impl or remat or base_dtype) else None
        )
        self.base = base_params
        key = jax.random.PRNGKey(seed)
        lora = init_lora(key, cfg, self.meta)
        # device-resident R-row pack + width-1 host template (B = 0: empty
        # rows contribute exactly zero delta even before their scale is
        # zeroed). Admission writes one pack row device-side.
        self._lora = lora
        lora1 = init_lora(key, cfg, self.meta1)
        self._lora1_host = jax.tree.map(np.asarray, lora1)
        # one jitted row write per tree structure (caches / lora), width-R
        # argument donated: admission mutates device state in place
        self._row_write = jax.jit(write_row_caches, donate_argnums=(0,))
        self._scales = np.zeros((rows,), np.float32)
        self._caches = None  # allocated lazily on first serve()
        self._tok = np.zeros((rows, 1), np.int32)
        self._pos = np.zeros((rows,), np.int32)
        self._rows: List[Optional[_ActiveRow]] = [None] * rows
        # per-row sampling state (0 temperature = greedy row); the engine
        # only routes through the sample step while some row has temp > 0,
        # so an all-greedy drain runs the *identical* compiled step_fn —
        # the bit-exactness baseline is preserved by construction
        self._temp = np.zeros((rows,), np.float32)
        self._topk = np.zeros((rows,), np.int32)
        self._sample_key = jax.random.fold_in(
            jax.random.PRNGKey(seed), 0x5EED
        )

        self.slot_cache = AdapterSlotCache(
            slot_capacity, pool=checkpoint_pool,
            metrics=self.tracer.metrics,
        )
        self.queue: "deque[ServeRequest]" = deque()
        # absolute perf_counter at which each queued request entered the
        # engine, for the TTFT / queue-wait histograms. Absolute (not
        # serve-relative) so a request submit()ted before serve() starts
        # still measures from its true enqueue, not from serve-start.
        self._enq_abs: Dict[int, float] = {}
        self._serve_t0 = 0.0  # perf_counter origin of the live serve() call
        self.serve_executor = serve_executor or default_executor()

        # Runner surface: training side
        self.device_pool = device_pool or DevicePool()
        if train_executor is None:
            from repro.cluster.executor import SliceExecutor

            train_executor = SliceExecutor(tracer=self.tracer)
        self.executor = train_executor
        self._runner = ClusterRunner(
            self.executor, self.device_pool, concurrent=None,
            tracer=self.tracer,
        )
        self.concurrent = self._runner.concurrent

    # ---------------- Runner protocol (training side) ----------------------

    def run(
        self,
        segments: Sequence,
        configs_by_cid: Dict,
        total_steps: Dict[int, int],
        cfg,
        base_params,
        *,
        seq: int,
        pool=None,
        data_iter_fn: Optional[Callable] = None,
        seed: int = 0,
        estimator=None,
        impl: Optional[str] = None,
        remat: Optional[str] = None,
        base_dtype: Optional[str] = None,
    ):
        """Execute planned *training* segments on the shared device pool
        (delegates to the inner ``ClusterRunner``). A concurrent decode loop
        holding ``serve_lease()`` keeps its units; training segments planned
        onto the remaining units proceed in parallel and block — serve
        priority — if the planner oversubscribes."""
        return self._runner.run(
            segments, configs_by_cid, total_steps, cfg, base_params,
            seq=seq, pool=pool, data_iter_fn=data_iter_fn, seed=seed,
            estimator=estimator, impl=impl, remat=remat,
            base_dtype=base_dtype,
        )

    @contextmanager
    def serve_lease(self, n: int = 1):
        """Reserve the *last* ``n`` pool units for decoding. The training
        planner allocates units from 0 upward, so a schedule planned over
        ``device_pool.total - n`` units never touches the reserved ones."""
        total = self.device_pool.total
        assert 1 <= n <= total
        sl = self.device_pool.acquire_units(list(range(total - n, total)))
        try:
            yield sl
        finally:
            self.device_pool.release(sl)

    # ---------------- adapter staging --------------------------------------

    def publish(self, adapter_id: str, adapter_tree: dict, meta: dict) -> None:
        """Tune-then-serve handoff: stage a finished training job's adapter
        directly (no disk round trip)."""
        self.slot_cache.publish(adapter_id, adapter_tree, meta)

    def publish_from_packed_state(
        self, pool, state_id: str, idx: int, adapter_id: str,
        *, rank: int, alpha: float,
    ) -> None:
        """Stage adapter ``idx`` out of a whole-pack training snapshot
        (``CheckpointPool.save_packed_state``)."""
        lora, _opt, _meta = pool.load_packed_state(state_id)
        adapter = extract_adapter(lora, idx, ranks=None)
        self.publish(adapter_id, adapter, {"rank": rank, "alpha": alpha})

    # ---------------- admission / retirement --------------------------------

    def submit(self, req: ServeRequest) -> None:
        """Enqueue a request ahead of (or during) a ``serve()`` drain. The
        enqueue instant is recorded here — queue-wait and TTFT span from the
        moment the request entered the engine, not from serve-start."""
        self._enq_abs[req.request_id] = time.perf_counter()
        self.queue.append(req)

    def _deadline_blown(self, req: ServeRequest) -> bool:
        """Is ``req`` past its wall-clock SLO, measured from the instant it
        entered the engine's queue (``submit()`` or trace arrival)?"""
        if req.deadline_ms is None:
            return False
        enq = self._enq_abs.get(req.request_id)
        if enq is None:
            return False
        return (time.perf_counter() - enq) * 1e3 > req.deadline_ms

    def _scale_for(self, req: ServeRequest, meta: dict) -> float:
        rank = req.rank if req.rank is not None else meta.get("rank")
        alpha = req.alpha if req.alpha is not None else meta.get("alpha")
        if rank is None or alpha is None:
            raise ValueError(
                f"request {req.request_id} for adapter {req.adapter_id!r}: "
                "rank/alpha neither on the request nor in adapter metadata"
            )
        return float(alpha) / float(rank)

    def _admit(self, req: ServeRequest, row: int, step: int, wall: float,
               stats: Optional[ServeStats] = None) -> Optional[ServeResult]:
        """Admit ``req`` into free row ``row`` — or reject it.

        Validation (prompt budget, adapter resolution) runs *before* any
        latency accounting or pinning: a rejected request comes back as an
        errored :class:`ServeResult` (the drain keeps serving everything
        else), never records a queue-wait/TTFT sample, and never leaks a
        slot-cache pin. Returns None on successful admission — the row is
        then either decoding (synchronous one-shot prefill) or filling its
        cache chunk-by-chunk (``prefill_chunk`` set)."""
        prompt = np.asarray(req.prompt, np.int32)
        n_patch = self.cfg.n_patch_tokens or 0
        s_total = prompt.shape[0] + n_patch
        err = adapter = ameta = scale = None
        if s_total + req.max_new_tokens > self.smax:
            err = (
                f"request {req.request_id}: prompt {s_total} + "
                f"{req.max_new_tokens} new tokens exceeds smax={self.smax}"
            )
        else:
            try:
                adapter, ameta = self.slot_cache.get(req.adapter_id)
                scale = self._scale_for(req, ameta)
            except (KeyError, ValueError) as e:
                err = str(e)
        if err is not None:
            self._enq_abs.pop(req.request_id, None)
            return ServeResult(
                request_id=req.request_id,
                adapter_id=req.adapter_id,
                tokens=np.zeros((0,), np.int32),
                n_prompt=int(prompt.shape[0]),
                arrival=req.arrival,
                admitted_step=step,
                finished_step=step,
                admitted_wall=wall,
                finished_wall=wall,
                error=err,
            )
        if stats is not None:
            stats.queue_wait.record(
                max(0.0, time.perf_counter() - self._enq_abs[req.request_id])
            )
        with self.tracer.span(
            "serve.admit", cat="serve", track=f"row{row}",
            request_id=req.request_id, adapter=req.adapter_id, step=step,
        ):
            self.slot_cache.pin(req.adapter_id)
            # weights: rank-pad into the width-1 template (prefill — the
            # bit-identical twin of the sequential baseline's), then write
            # that row into the device-resident R-row pack; rows are
            # independent thereafter
            lora1 = jax.tree.map(
                jnp.asarray, inject_adapter(self._lora1_host, adapter, 0)
            )
            self._lora = self._row_write(self._lora, lora1, row)
            if (
                self.prefill_chunk is not None
                and not req.extra
                and not n_patch
                and not self.cfg.is_encdec
            ):
                # chunked interleaved admission: allocate the row's private
                # f32 cache at capacity == prompt length (the bitwise
                # invariant) and let the drain loop stream chunks into it
                # between decode steps; the row flips to decode — and the
                # first token / TTFT land — once the prompt is fully cached
                from repro.models.model import init_caches

                self._rows[row] = _ActiveRow(
                    request=req, emitted=[], admitted_step=step,
                    admitted_wall=wall, n_prompt=prompt.shape[0],
                    prefill=_PrefillState(
                        lora1=lora1, scale=scale,
                        caches=init_caches(
                            self.cfg, 1, s_total, dtype=jnp.float32
                        ),
                        prompt=prompt,
                    ),
                )
                return None
            batch = {"tokens": jnp.asarray(prompt[None, :])}
            if req.extra:
                batch.update(req.extra)
            # the prefill-stall span: decode is paused while this row fills
            with self.tracer.span(
                "serve.prefill", cat="serve", track=f"row{row}",
                request_id=req.request_id, n_prompt=int(prompt.shape[0]),
            ):
                pf = self.serve_executor.prefill_fn(
                    self.cfg, 1, dist=self.dist, kcfg=self.kcfg1
                )
                lg, c1 = pf(
                    self.base, lora1, jnp.full((1,), scale, jnp.float32),
                    batch,
                )
                c1 = pad_caches(c1, self.smax)
                self._caches = self._row_write(self._caches, c1, row)
                temp = float(req.temperature)
                topk = int(req.top_k)
                if temp > 0.0:
                    # the first token comes from prefill, outside the jitted
                    # step — sample it eagerly with the same formula, keyed
                    # by request id so admission order doesn't change it
                    first = int(sample_tokens(
                        lg[:, -1, :],
                        jnp.full((1,), temp, jnp.float32),
                        jnp.full((1,), topk, jnp.int32),
                        jax.random.fold_in(self._sample_key, req.request_id),
                    )[0])
                else:
                    first = int(jnp.argmax(lg[0, -1, :]))
        now = time.perf_counter()
        if stats is not None:
            # the prefill above emitted the request's first token
            stats.ttft.record(max(0.0, now - self._enq_abs[req.request_id]))
        self._scales[row] = scale
        self._temp[row] = temp
        self._topk[row] = topk
        self._tok[row, 0] = first
        self._pos[row] = s_total
        self._rows[row] = _ActiveRow(
            request=req, emitted=[first], admitted_step=step,
            admitted_wall=wall, n_prompt=prompt.shape[0],
            last_emit_wall=now - self._serve_t0,
        )
        return None

    def _prefill_advance(
        self, row: int, step: int, stats: ServeStats
    ) -> bool:
        """Run ONE prefill chunk for ``row``'s in-progress request.

        On the final chunk the row flips into the decode set: the exact-
        capacity f32 cache is zero-padded to ``smax`` and row-written (same
        pad + bf16-cast path as one-shot admission, so the engine state is
        bitwise identical), the first token is emitted, and TTFT is
        recorded. Returns True once the row is decoding."""
        a = self._rows[row]
        ps = a.prefill
        req = a.request
        c = min(self.prefill_chunk, len(ps.prompt) - ps.filled)
        with self.tracer.span(
            "serve.prefill_chunk", cat="serve", track=f"row{row}",
            request_id=req.request_id, step=step, pos=ps.filled,
            chunk=int(c), n_prompt=len(ps.prompt),
        ):
            fn = self.serve_executor.prefill_chunk_fn(
                self.cfg, 1, dist=self.dist, kcfg=self.kcfg1
            )
            lg, ps.caches = fn(
                self.base, ps.lora1,
                jnp.full((1,), ps.scale, jnp.float32),
                jnp.asarray(ps.prompt[None, ps.filled : ps.filled + c]),
                ps.caches, jnp.int32(ps.filled),
            )
            ps.filled += c
            if ps.filled < len(ps.prompt):
                # sync so the span measures the chunk (and the iteration's
                # overhead stays the one bounded chunk, not deferred work)
                jax.block_until_ready(lg)
                return False
            c1 = pad_caches(ps.caches, self.smax)
            self._caches = self._row_write(self._caches, c1, row)
            temp = float(req.temperature)
            topk = int(req.top_k)
            if temp > 0.0:
                first = int(sample_tokens(
                    lg[:, -1, :],
                    jnp.full((1,), temp, jnp.float32),
                    jnp.full((1,), topk, jnp.int32),
                    jax.random.fold_in(self._sample_key, req.request_id),
                )[0])
            else:
                first = int(jnp.argmax(lg[0, -1, :]))
        now = time.perf_counter()
        stats.ttft.record(max(0.0, now - self._enq_abs[req.request_id]))
        self._scales[row] = ps.scale
        self._temp[row] = temp
        self._topk[row] = topk
        self._tok[row, 0] = first
        self._pos[row] = len(ps.prompt)
        a.emitted.append(first)
        a.last_emit_wall = now - self._serve_t0
        a.prefill = None
        return True

    def _retire(
        self, row: int, step: int, wall: float,
        error: Optional[str] = None,
    ) -> ServeResult:
        active = self._rows[row]
        assert active is not None
        self._rows[row] = None
        self._scales[row] = 0.0
        self._temp[row] = 0.0
        self._topk[row] = 0
        self.slot_cache.unpin(active.request.adapter_id)
        self._enq_abs.pop(active.request.request_id, None)
        # the request's whole residency on its row, admit -> retire
        self.tracer.add_span(
            "serve.request",
            self._serve_t0 + active.admitted_wall,
            self._serve_t0 + wall,
            cat="serve",
            track=f"row{row}",
            request_id=active.request.request_id,
            adapter=active.request.adapter_id,
            tokens=len(active.emitted),
        )
        return ServeResult(
            request_id=active.request.request_id,
            adapter_id=active.request.adapter_id,
            tokens=np.asarray(active.emitted, np.int32),
            n_prompt=active.n_prompt,
            arrival=active.request.arrival,
            admitted_step=active.admitted_step,
            finished_step=step,
            admitted_wall=active.admitted_wall,
            finished_wall=wall,
            error=error,
        )

    # ---------------- the decode loop ---------------------------------------

    def serve(
        self,
        requests: Optional[Sequence[ServeRequest]] = None,
        *,
        max_steps: Optional[int] = None,
    ) -> ServeStats:
        """Drain a request trace (plus anything already ``submit()``ted).

        Virtual time is the decode-step counter: a request becomes
        admissible once ``step >= arrival``; freed rows are refilled before
        the next step, so the batch never drains while work is queued."""
        from repro.models.model import init_caches

        pending = deque(
            sorted(requests or (), key=lambda r: (r.arrival, r.request_id))
        )
        if self._caches is None:
            self._caches = init_caches(self.cfg, self.rows, self.smax)
        stats = ServeStats()
        with self.tracer.span(
            "serve.drain", cat="serve", track="serve",
            n_requests=len(pending) + len(self.queue), rows=self.rows,
        ):
            self._serve_drain(pending, stats, max_steps)
        stats.cache_hits = self.slot_cache.hits
        stats.cache_misses = self.slot_cache.misses
        stats.cache_evictions = self.slot_cache.evictions
        stats.results.sort(key=lambda r: r.request_id)
        return stats

    def _serve_drain(
        self,
        pending: "deque[ServeRequest]",
        stats: ServeStats,
        max_steps: Optional[int],
    ) -> None:
        tracer = self.tracer
        qdepth = tracer.metrics.gauge("serve.queue_depth")
        t0 = time.perf_counter()
        self._serve_t0 = t0
        step = 0
        while True:
            wall = time.perf_counter() - t0
            while pending and pending[0].arrival <= step:
                req = pending.popleft()
                self._enq_abs.setdefault(req.request_id, time.perf_counter())
                self.queue.append(req)
            qdepth.set(len(self.queue))
            for row in range(self.rows):
                while self._rows[row] is None and self.queue:
                    req = self.queue.popleft()
                    if self._deadline_blown(req):
                        # already overdue in the queue: no prefill is ever
                        # spent on it — reject crisply, try the next one
                        self._enq_abs.pop(req.request_id, None)
                        stats.results.append(ServeResult(
                            request_id=req.request_id,
                            adapter_id=req.adapter_id,
                            tokens=np.zeros((0,), np.int32),
                            n_prompt=int(np.asarray(req.prompt).shape[0]),
                            arrival=req.arrival,
                            admitted_step=step,
                            finished_step=step,
                            admitted_wall=wall,
                            finished_wall=wall,
                            error="deadline",
                        ))
                        continue
                    rejected = self._admit(req, row, step, wall, stats)
                    if rejected is not None:
                        # row is still free — surface the rejection and try
                        # the next queued request instead of aborting
                        stats.results.append(rejected)
                        continue
                    a = self._rows[row]
                    if (
                        a.prefill is None
                        and len(a.emitted) >= req.max_new_tokens
                    ):
                        # single-token request: prefill already emitted it
                        stats.tokens_emitted += len(a.emitted)
                        stats.results.append(self._retire(row, step, wall))
            # one prefill chunk per still-filling row: admission cost is
            # paid in bounded slices interleaved with decode steps, not as
            # one stall that freezes every in-flight row
            for row in range(self.rows):
                a = self._rows[row]
                if a is None or a.prefill is None:
                    continue
                if self._prefill_advance(row, step, stats):
                    if len(a.emitted) >= a.request.max_new_tokens:
                        wall = time.perf_counter() - t0
                        stats.tokens_emitted += len(a.emitted)
                        stats.results.append(self._retire(row, step, wall))
            # deadline SLO: an overdue in-flight row retires as a *partial*
            # result — tokens emitted so far kept, pins released — exactly
            # the bounded-drain (max_steps) early-exit contract; its row
            # refills from the queue on the next pass
            for row in range(self.rows):
                a = self._rows[row]
                if a is None or not self._deadline_blown(a.request):
                    continue
                wall = time.perf_counter() - t0
                stats.tokens_emitted += len(a.emitted)
                stats.results.append(
                    self._retire(row, step, wall, error="deadline")
                )
            active = [r for r in range(self.rows) if self._rows[r] is not None]
            if not active:
                if self.queue:
                    continue  # rows freed this pass; admit more
                if pending:
                    step = int(np.ceil(pending[0].arrival))
                    continue
                break
            if max_steps is not None and stats.steps >= max_steps:
                # bounded drain: retire in-flight rows into partial results
                # (tokens emitted so far, pins released) instead of
                # dropping them from stats with their adapters pinned
                wall = time.perf_counter() - t0
                for row in active:
                    stats.tokens_emitted += len(self._rows[row].emitted)
                    stats.results.append(self._retire(row, step, wall))
                break
            decoding = [r for r in active if self._rows[r].prefill is None]
            if not decoding:
                # chunk-only iteration: virtual time still advances, so
                # trace arrivals keep landing in free rows mid-prefill
                step += 1
                continue
            with tracer.span(
                "serve.step", cat="serve", track="serve",
                step=step, batch=len(decoding),
            ):
                if self._temp.any():
                    fn = self.serve_executor.sample_step_fn(
                        self.cfg, self.rows, dist=self.dist, kcfg=self.kcfg
                    )
                    next_tok, _lg, self._caches = fn(
                        self.base, self._lora, jnp.asarray(self._scales),
                        self._caches, jnp.asarray(self._tok),
                        jnp.asarray(self._pos), jnp.asarray(self._temp),
                        jnp.asarray(self._topk),
                        jax.random.fold_in(self._sample_key, step),
                    )
                else:
                    fn = self.serve_executor.step_fn(
                        self.cfg, self.rows, dist=self.dist, kcfg=self.kcfg
                    )
                    next_tok, _lg, self._caches = fn(
                        self.base, self._lora, jnp.asarray(self._scales),
                        self._caches, jnp.asarray(self._tok),
                        jnp.asarray(self._pos),
                    )
                next_tok = np.asarray(next_tok)
            step += 1
            stats.steps += 1
            stats.occupancy_sum += len(decoding)
            wall = time.perf_counter() - t0
            # each decoding row emitted exactly one token this iteration;
            # the gap since the row's previous token — admission/chunk work
            # in between included — is the inter-token latency that row's
            # request actually observed
            for row in decoding:
                a = self._rows[row]
                stats.itl.record(max(0.0, wall - a.last_emit_wall))
                a.last_emit_wall = wall
                a.emitted.append(int(next_tok[row]))
                self._tok[row, 0] = int(next_tok[row])
                self._pos[row] += 1
                if len(a.emitted) >= a.request.max_new_tokens:
                    stats.tokens_emitted += len(a.emitted)
                    stats.results.append(self._retire(row, step, wall))
        stats.wall_seconds = time.perf_counter() - t0

    # ---------------- sequential baseline -----------------------------------

    def serve_sequential(
        self, requests: Sequence[ServeRequest]
    ) -> ServeStats:
        """One request at a time at batch width 1 — the pre-engine serving
        path (``generate()`` semantics), through the same compile cache.
        The benchmark's baseline and the bit-exactness reference."""
        stats = ServeStats()
        t0 = time.perf_counter()
        order = sorted(requests, key=lambda r: (r.arrival, r.request_id))
        for req in order:
            # all requests are in hand at t0, so the time spent behind
            # earlier requests is this one's queue wait
            stats.queue_wait.record(time.perf_counter() - t0)
            adapter, ameta = self.slot_cache.get(req.adapter_id)
            scale = self._scale_for(req, ameta)
            lora1 = jax.tree.map(
                jnp.asarray, inject_adapter(self._lora1_host, adapter, 0)
            )
            prompt = np.asarray(req.prompt, np.int32)
            n_patch = self.cfg.n_patch_tokens or 0
            s_total = prompt.shape[0] + n_patch
            batch = {"tokens": jnp.asarray(prompt[None, :])}
            if req.extra:
                batch.update(req.extra)
            scales = jnp.full((1,), scale, jnp.float32)
            pf = self.serve_executor.prefill_fn(
                self.cfg, 1, dist=self.dist, kcfg=self.kcfg1
            )
            lg, caches = pf(self.base, lora1, scales, batch)
            caches = pad_caches(caches, s_total + req.max_new_tokens)
            admitted = time.perf_counter() - t0
            stats.ttft.record(admitted)  # prefill just emitted token one
            tok = jnp.argmax(lg[:, -1, :], -1).astype(jnp.int32)
            out = [int(tok[0])]
            fn = self.serve_executor.step_fn(
                self.cfg, 1, dist=self.dist, kcfg=self.kcfg1
            )
            t_prev = time.perf_counter()
            for i in range(req.max_new_tokens - 1):
                tok, _lg, caches = fn(
                    self.base, lora1, scales, caches, tok[:, None],
                    jnp.int32(s_total + i),
                )
                out.append(int(tok[0]))  # syncs the device step
                stats.steps += 1
                stats.occupancy_sum += 1
                t_now = time.perf_counter()
                stats.itl.record(t_now - t_prev)
                t_prev = t_now
            wall = time.perf_counter() - t0
            stats.tokens_emitted += len(out)
            stats.results.append(
                ServeResult(
                    request_id=req.request_id,
                    adapter_id=req.adapter_id,
                    tokens=np.asarray(out, np.int32),
                    n_prompt=prompt.shape[0],
                    arrival=req.arrival,
                    admitted_step=stats.steps,
                    finished_step=stats.steps,
                    admitted_wall=admitted,
                    finished_wall=wall,
                )
            )
        stats.wall_seconds = time.perf_counter() - t0
        stats.results.sort(key=lambda r: r.request_id)
        return stats
