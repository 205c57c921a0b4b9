"""Multi-host dispatch tier: process-per-host scale-out of the segment protocol.

The single-host :class:`~repro.cluster.runner.ClusterRunner` is thread-per-
slice inside one process — every plan is capped at one host's devices. This
module scales the *same* segment protocol out across simulated (or, with a
different transport, real) hosts:

  * :class:`HostWorker` — one subprocess per simulated host. Each worker
    self-forces its own CPU device count (``XLA_FLAGS=--xla_force_host_
    platform_device_count=N``, inherited through the environment at spawn
    time) and runs the existing :class:`~repro.cluster.executor.SliceExecutor`
    + :class:`~repro.cluster.pool.DevicePool` over its local devices — the
    per-host execution stack is exactly the single-host one.
  * a **message protocol** replaces the runner's in-memory shared state:
    segments, resumed adapter state, and checkpoint-pool traffic are
    serialized over a pipe/queue transport (:func:`encode_segment` /
    :func:`encode_tree` / :func:`encode_record`). Workers never touch the
    central :class:`~repro.train.checkpoint.CheckpointPool`; a
    :class:`MemoryPool` captures their checkpoint writes and the dispatcher
    applies them *atomically on segment success* — which is what makes a
    killed worker recoverable (no partial state ever lands in the pool, so
    the segment's residual simply re-enters the existing preempt/resume
    path on a fresh worker).
  * :class:`HostDispatcher` — extends :class:`DevicePool` addressing to
    ``(host, unit)`` pairs (:class:`HostUnit`) and implements the
    :class:`~repro.cluster.api.Runner` protocol: ``run`` executes planned
    segments process-per-host, and ``.executor``/``.device_pool`` plug
    straight into ``ExecutionEngine._run_adaptive`` — real device-free and
    checkpoint-ready events surface back into the engine's online/adaptive
    loops unchanged, so ``plan_online``, migration, probes, and the
    ``ProfiledCostModel`` feedback all work across hosts.

Plan host-aware (``ExecutionEngine(cm, g, host_size=...)``) so every
segment's device units stay within one host; the dispatcher rejects
host-spanning slices.

This module is import-light on purpose: the spawn'd child imports it before
any jax backend initializes. The dispatcher checks only that its own backend
is the CPU (:func:`require_cpu_parent`).
"""
from __future__ import annotations

import itertools
import os
import pickle
import threading
import time
import traceback
from dataclasses import dataclass
from queue import Empty
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.obs import NULL_TRACER, TraceCtx

# ---------------------------------------------------------------------------
# Wire protocol
# ---------------------------------------------------------------------------
#
# Every message is ``(kind, payload)``; payload *contents* are the typed
# dataclasses below (:class:`SegmentMsg`, :class:`RecordMsg`,
# :class:`CheckpointWrite`, :class:`KernelPolicy`,
# :class:`~repro.obs.TraceCtx`) plus plain-python / numpy scalars and
# ``encode_tree``'d arrays, so the protocol survives pickling across process
# boundaries bit-exactly AND a field rename breaks loudly at construction
# instead of silently at a remote KeyError.
#
#   dispatcher -> worker:  ("init", state) ("run", request) ("stop", {})
#   worker -> dispatcher:  ("ready", info) ("done", result) ("err", failure)
#                          ("fatal", failure)   # startup / loop death
#
# A "run" payload optionally carries ``"trace"``, a :class:`TraceCtx`
# naming the dispatcher-side parent span; the matching "done" reply then
# carries ``"spans"`` (the worker's finished span tree, as
# :meth:`repro.obs.Span.to_dict` dicts) and ``"span_t0"`` (the worker root
# span's start on the *worker's* monotonic clock) so the dispatcher can
# rebase and stitch them under its own trace.


class TransportError(RuntimeError):
    """The transport to a host worker failed."""


class WorkerDied(TransportError):
    """The host worker process died (crash / kill) with requests in flight."""


class RemoteSegmentError(RuntimeError):
    """A segment raised inside the worker; carries the remote traceback."""


def encode_tree(tree):
    """Nested-dict tree with every leaf forced to host ``np.ndarray`` —
    the only array type the wire carries (bit-exact, device-free)."""
    if isinstance(tree, dict):
        return {k: encode_tree(v) for k, v in tree.items()}
    return np.asarray(tree)


@dataclass(frozen=True)
class SegmentMsg:
    """One :class:`~repro.sched.engine.JobSegment` on the wire — same
    fields, but a plain frozen dataclass so the wire format is decoupled
    from the scheduler's type (and picklable without importing it)."""

    job_id: int
    config_ids: Tuple[int, ...]
    degree: int
    start: float
    end: float
    start_steps: Tuple[int, ...]
    run_steps: int
    done_ids: Tuple[int, ...]
    preempted: bool
    units: Tuple[int, ...]


@dataclass(frozen=True)
class RecordMsg:
    """A finished segment's :class:`~repro.sched.engine.JobRecord` on the
    wire (losses as host numpy; wall time measured on the worker clock)."""

    config_ids: Tuple[int, ...]
    degree: int
    start: float
    end: float
    wall_seconds: float
    losses: Optional[np.ndarray]


@dataclass(frozen=True)
class CheckpointWrite:
    """One captured checkpoint-pool write: a finished adapter
    (``kind="adapter"``) or preempted per-adapter training state
    (``kind="state"``). ``tree`` is ``encode_tree``'d (host numpy)."""

    kind: str  # "adapter" | "state"
    adapter_id: str
    tree: dict
    meta: dict


@dataclass(frozen=True)
class HeartbeatMsg:
    """Dispatcher -> worker health ping. ``t_send`` is the dispatcher's
    monotonic clock at send time; the worker echoes it untouched so the RTT
    is computed on one clock (worker clocks aren't comparable)."""

    seq: int
    t_send: float


@dataclass(frozen=True)
class HealthReply:
    """Worker -> dispatcher pong: answered *inline* by the worker's message
    loop (segments run on a thread pool), so a missing reply means the loop
    itself is wedged or the process is gone — hung and crashed workers look
    identical to the watchdog, which is the point."""

    host: int
    seq: int
    t_send: float
    in_flight: int


# membership states of one host, as seen by the dispatcher's watchdog
HOST_ALIVE = "ALIVE"        # answering heartbeats (or heartbeats disabled)
HOST_SUSPECT = "SUSPECT"    # missed a heartbeat deadline; backoff running
HOST_DEAD = "DEAD"          # declared dead (backoff exhausted / drained out)
HOST_DRAINING = "DRAINING"  # graceful retirement in progress


@dataclass(frozen=True)
class KernelPolicy:
    """The kernel policy a segment must run under (``--impl`` / ``--remat``).

    Shipped with every run request so host workers execute the same kernel
    tier the caller (and their autotuned cost model) selected — previously
    multi-host dispatch rejected any non-default policy."""

    impl: Optional[str] = None  # None/"auto" = executor default
    remat: Optional[str] = None  # None = executor default ("save")
    base_dtype: Optional[str] = None  # "int8"/"nf4" = quantized frozen base


_SEGMENT_FIELDS = (
    "job_id", "config_ids", "degree", "start", "end",
    "start_steps", "run_steps", "done_ids", "preempted", "units",
)


def encode_segment(seg) -> SegmentMsg:
    return SegmentMsg(**{f: getattr(seg, f) for f in _SEGMENT_FIELDS})


def decode_segment(m: SegmentMsg):
    from repro.sched.engine import JobSegment

    return JobSegment(**{f: getattr(m, f) for f in _SEGMENT_FIELDS})


def encode_record(rec) -> RecordMsg:
    return RecordMsg(
        config_ids=tuple(rec.job.config_ids),
        degree=rec.job.degree,
        start=rec.job.start,
        end=rec.job.end,
        wall_seconds=rec.wall_seconds,
        losses=(
            None if rec.final_losses is None else np.asarray(rec.final_losses)
        ),
    )


def decode_record(m: RecordMsg):
    from repro.sched.engine import JobRecord
    from repro.sched.planner import ScheduledJob

    return JobRecord(
        ScheduledJob(tuple(m.config_ids), m.degree, m.start, m.end),
        m.wall_seconds,
        m.losses,
    )


class MemoryPool:
    """Worker-side stand-in for the central checkpoint pool.

    Reads come from the states the dispatcher shipped with the segment;
    writes are *captured*, not applied — the dispatcher replays them onto the
    real pool only after the segment's ``done`` message arrives. A worker
    killed mid-segment therefore leaves the central pool exactly as it was,
    and the re-dispatched segment resumes from unchanged state."""

    def __init__(self, states: Optional[Dict[str, Tuple[dict, dict]]] = None):
        self.states = dict(states or {})
        self.writes: List[CheckpointWrite] = []

    def has_adapter_state(self, adapter_id: str) -> bool:
        return adapter_id in self.states

    def load_adapter_state(self, adapter_id: str):
        tree, meta = self.states[adapter_id]
        return tree, meta

    def save_adapter_state(self, adapter_id: str, state_tree, meta: dict):
        self.writes.append(
            CheckpointWrite("state", adapter_id, encode_tree(state_tree), meta)
        )

    def save_adapter(self, adapter_id: str, adapter_tree, meta: dict):
        self.writes.append(
            CheckpointWrite(
                "adapter", adapter_id, encode_tree(adapter_tree), meta
            )
        )


# ---------------------------------------------------------------------------
# Worker process (one simulated host)
# ---------------------------------------------------------------------------


def _worker_main(host_id: int, n_devices: int, inbox, outbox) -> None:
    """Entry point of one simulated host. The parent set ``XLA_FLAGS`` /
    ``JAX_PLATFORMS`` in the environment *around* ``Process.start()`` — the
    spawn'd child inherits them before any jax backend initializes, so this
    process sees exactly ``n_devices`` forced CPU devices regardless of how
    the parent's jax was configured."""
    try:
        import jax

        devs = jax.devices()
        if len(devs) < n_devices:
            raise RuntimeError(
                f"host {host_id} expected {n_devices} forced devices but "
                f"jax initialized {len(devs)} — XLA_FLAGS not inherited?"
            )
        from concurrent.futures import ThreadPoolExecutor

        from repro.cluster.executor import SliceExecutor
        from repro.cluster.pool import DevicePool

        executor = SliceExecutor()
        dpool = DevicePool(devs[:n_devices])
        outbox.put(("ready", {"host": host_id, "devices": len(devs)}))
    except BaseException as e:  # noqa: BLE001 — shipped to the dispatcher
        outbox.put(
            ("fatal", {
                "host": host_id,
                "error": repr(e),
                "traceback": traceback.format_exc(),
            })
        )
        return

    state: Dict[str, Any] = {}
    # one worker-side tracer shared by every traced request: span stacks
    # are thread-local and pop_root flushes one request's tree, so
    # concurrent do_run threads don't interleave. Created lazily on the
    # first traced request; untraced runs never pay for it.
    wtracer_box: List[Any] = [None]
    wtracer_lock = threading.Lock()

    def do_run(payload: Dict[str, Any]) -> None:
        rid = payload["req"]
        try:
            seg = decode_segment(payload["seg"])
            policy = payload.get("policy") or KernelPolicy()
            trace_ctx = payload.get("trace")
            mempool = (
                MemoryPool(payload["states"]) if payload["has_pool"] else None
            )
            spans = span_t0 = None
            if trace_ctx is not None:
                from repro.obs import Tracer

                with wtracer_lock:
                    if wtracer_box[0] is None:
                        wtracer_box[0] = Tracer()
                        executor.tracer = wtracer_box[0]
                wtracer = wtracer_box[0]
                root_cm = wtracer.span(
                    f"host{host_id}.segment", cat="host",
                    job_id=seg.job_id, req=rid,
                )
            else:
                root_cm = None
            with dpool.lease_units(payload["units"]) as slice_:
                if root_cm is not None:
                    root = root_cm.__enter__()
                try:
                    rec = executor.run_segment(
                        seg,
                        state["configs_by_cid"],
                        state["total_steps"],
                        state["cfg"],
                        state["base"],
                        seq=state["seq"],
                        pool=mempool,
                        data_iter_fn=state["data_iter_fn"],
                        seed=state["seed"],
                        slice_=slice_,
                        impl=policy.impl,
                        remat=policy.remat,
                        # getattr: a worker may receive a policy pickled by
                        # an older caller without the base_dtype field
                        base_dtype=getattr(policy, "base_dtype", None),
                    )
                finally:
                    if root_cm is not None:
                        root_cm.__exit__(None, None, None)
                        spans = wtracer.pop_root(root.span_id)
                        span_t0 = root.start
            done = {
                "req": rid,
                "host": host_id,
                "record": encode_record(rec),
                "writes": mempool.writes if mempool is not None else [],
            }
            if spans is not None:
                done["spans"] = spans
                done["span_t0"] = span_t0
            outbox.put(("done", done))
        except BaseException as e:  # noqa: BLE001 — shipped to the dispatcher
            outbox.put(
                ("err", {
                    "req": rid,
                    "host": host_id,
                    "error": repr(e),
                    "traceback": traceback.format_exc(),
                })
            )

    tpe = ThreadPoolExecutor(max_workers=max(n_devices, 1))
    n_running = [0]

    def counted_run(payload):
        try:
            do_run(payload)
        finally:
            n_running[0] -= 1

    try:
        while True:
            kind, payload = inbox.get()
            if kind == "stop":
                break
            if kind == "init":
                state = dict(payload)
            elif kind == "run":
                n_running[0] += 1
                tpe.submit(counted_run, payload)
            elif kind == "ping":
                # answered inline, never queued behind segments: a worker
                # that stops ponging has a wedged loop, not a busy one
                outbox.put(("pong", HealthReply(
                    host=host_id, seq=payload.seq, t_send=payload.t_send,
                    in_flight=n_running[0],
                )))
    finally:
        tpe.shutdown(wait=True)


def _forced_xla_flags(n_devices: int) -> str:
    """Parent's XLA_FLAGS with the forced-host-device count replaced."""
    kept = [
        f
        for f in os.environ.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    ]
    kept.append(f"--xla_force_host_platform_device_count={n_devices}")
    return " ".join(kept)


# serializes the env-set -> spawn -> env-restore dance when several hosts
# (possibly with different device counts) start concurrently
_SPAWN_LOCK = threading.Lock()


class ProcessTransport:
    """Pipe/queue transport to one :func:`_worker_main` subprocess."""

    def __init__(self, host_id: int, n_devices: int):
        import multiprocessing as mp

        ctx = mp.get_context("spawn")  # fresh interpreter: no inherited jax
        self._inbox = ctx.Queue()
        self._outbox = ctx.Queue()
        self.proc = ctx.Process(
            target=_worker_main,
            args=(host_id, n_devices, self._inbox, self._outbox),
            daemon=True,  # never outlive the dispatcher process
            name=f"plora-host-{host_id}",
        )
        with _SPAWN_LOCK:
            saved_xla = os.environ.get("XLA_FLAGS")
            saved_plat = os.environ.get("JAX_PLATFORMS")
            os.environ["XLA_FLAGS"] = _forced_xla_flags(n_devices)
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
            try:
                self.proc.start()
            finally:
                for key, saved in (
                    ("XLA_FLAGS", saved_xla), ("JAX_PLATFORMS", saved_plat)
                ):
                    if saved is None:
                        os.environ.pop(key, None)
                    else:
                        os.environ[key] = saved

    def send(self, msg) -> None:
        self._inbox.put(msg)

    def recv(self, timeout: Optional[float] = None):
        return self._outbox.get(timeout=timeout)  # raises queue.Empty

    def alive(self) -> bool:
        return self.proc.is_alive()

    def kill(self) -> None:
        if self.proc.is_alive():
            self.proc.kill()
        self.proc.join(timeout=5)

    def join(self, timeout: Optional[float] = None) -> None:
        self.proc.join(timeout)


# ---------------------------------------------------------------------------
# Dispatcher side
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class HostUnit:
    """One device unit addressed as a ``(host, local unit)`` pair — the
    virtual 'device' objects backing the dispatcher's :class:`DevicePool`."""

    host: int
    local: int


def _send_with_retry(
    transport, msg, *, deadline: float = 30.0, retries: int = 2
) -> None:
    """Wire send with a per-message deadline and bounded retry: transient
    transport hiccups back off and retry; a send still failing at the
    deadline (or out of attempts) raises :class:`TransportError`."""
    t0 = time.perf_counter()
    last: Optional[BaseException] = None
    for attempt in range(retries + 1):
        try:
            transport.send(msg)
            return
        except Exception as e:  # noqa: BLE001 — retried, then re-raised
            last = e
            if time.perf_counter() - t0 >= deadline or attempt >= retries:
                break
            time.sleep(min(0.05 * (2 ** attempt), 0.5))
    raise TransportError(
        f"send failed after {attempt + 1} attempt(s): {last!r}"
    ) from last


class _Reply:
    """Future for one in-flight segment request."""

    __slots__ = ("_evt", "_kind", "_payload", "_err")

    def __init__(self):
        self._evt = threading.Event()
        self._kind = self._payload = self._err = None

    def resolve(self, kind: str, payload: Dict[str, Any]) -> None:
        self._kind, self._payload = kind, payload
        self._evt.set()

    def fail(self, err: BaseException) -> None:
        self._err = err
        self._evt.set()

    def wait(self, timeout: Optional[float] = None) -> Dict[str, Any]:
        if not self._evt.wait(timeout):
            raise TransportError(
                f"no reply within the {timeout:.0f}s request deadline"
            )
        if self._err is not None:
            raise self._err
        if self._kind == "err":
            raise RemoteSegmentError(
                f"segment failed on host {self._payload['host']}: "
                f"{self._payload['error']}\n--- remote traceback ---\n"
                f"{self._payload['traceback']}"
            )
        return self._payload


class HostWorker:
    """Dispatcher-side handle for one host: transport + pump thread + the
    in-flight request table. A dead worker fails all in-flight requests with
    :class:`WorkerDied`; the dispatcher then spawns a *new* ``HostWorker``
    for the host (the handle itself is never resurrected)."""

    def __init__(
        self, host_id: int, n_devices: int, transport,
        *, on_pong: Optional[Callable] = None,
        send_deadline: float = 30.0, send_retries: int = 2,
    ):
        self.host_id = host_id
        self.n_devices = n_devices
        self.transport = transport
        self.on_pong = on_pong
        self.send_deadline = send_deadline
        self.send_retries = send_retries
        self.ready = threading.Event()
        self.fatal: Optional[Dict[str, Any]] = None
        self.init_version = -1
        self.dead = False
        # did this worker die with requests in flight? Idle deaths (e.g. a
        # spot reclaim between segments) don't burn a restart credit.
        self.died_in_flight = False
        self._lock = threading.Lock()
        self._pending: Dict[int, _Reply] = {}
        self._pump = threading.Thread(
            target=self._pump_loop, name=f"pump-host-{host_id}", daemon=True
        )
        self._pump.start()

    # -- request lifecycle --------------------------------------------------

    def send(self, msg) -> None:
        """Deadline-bounded wire send (shared by requests / init / pings)."""
        _send_with_retry(
            self.transport, msg,
            deadline=self.send_deadline, retries=self.send_retries,
        )

    def request(self, rid: int, msg) -> _Reply:
        reply = _Reply()
        with self._lock:
            if self.dead:
                raise WorkerDied(f"host {self.host_id} worker is dead")
            self._pending[rid] = reply
        try:
            self.send(msg)
        except Exception as e:  # queue to a dead process
            with self._lock:
                self._pending.pop(rid, None)
            raise WorkerDied(f"host {self.host_id} send failed: {e!r}") from e
        return reply

    def in_flight(self) -> int:
        with self._lock:
            return len(self._pending)

    def wait_ready(self, timeout: float) -> None:
        if not self.ready.wait(timeout):
            raise TransportError(
                f"host {self.host_id} worker not ready after {timeout:.0f}s"
            )
        if self.fatal is not None:
            # the worker reported a startup exception: deterministic, so a
            # respawn would just fail the same way — no retry
            raise TransportError(
                f"host {self.host_id} worker failed to start: "
                f"{self.fatal['error']}\n{self.fatal['traceback']}"
            )
        if self.dead:
            # hard-died before 'ready' (SIGKILL / OOM / segfault during
            # startup): possibly transient, so surface it as WorkerDied —
            # the segment retry loop respawns, bounded by max_restarts
            raise WorkerDied(
                f"host {self.host_id} worker died during startup"
            )

    # -- pump ---------------------------------------------------------------

    def _fail_all(self) -> None:
        with self._lock:
            self.dead = True
            pending = list(self._pending.values())
            self._pending.clear()
            if pending:
                self.died_in_flight = True
        err = WorkerDied(f"host {self.host_id} worker died")
        for reply in pending:
            reply.fail(err)
        self.ready.set()  # unblock wait_ready; fatal/dead is checked there

    def _pump_loop(self) -> None:
        while True:
            try:
                msg = self.transport.recv(timeout=0.2)
            except Empty:
                if not self.transport.alive():
                    self._fail_all()
                    return
                continue
            except Exception:  # truncated pickle from a killed writer, EOF
                self._fail_all()
                return
            kind, payload = msg
            if kind == "ready":
                self.ready.set()
            elif kind == "pong":
                if self.on_pong is not None:
                    self.on_pong(self.host_id, payload)
            elif kind == "fatal":
                self.fatal = payload
                self._fail_all()
                return
            else:  # "done" / "err"
                with self._lock:
                    reply = self._pending.pop(payload["req"], None)
                if reply is not None:
                    reply.resolve(kind, payload)


class DispatchExecutor:
    """`SliceExecutor`-shaped facade that executes segments *remotely*.

    ``run_segment`` ships the segment (plus any resumed adapter state read
    from the central pool) to the worker owning the slice's host, blocks on
    the reply, applies the returned checkpoint writes to the central pool,
    and returns a ``JobRecord`` — so ``ClusterRunner``'s dispatch loop and
    the engine's adaptive loop drive multi-host execution without changes.
    A :class:`WorkerDied` mid-segment restarts the host (bounded by the
    dispatcher's ``max_restarts``) and re-dispatches: the segment's inputs
    are still in the pool (writes are success-atomic), so the retry is the
    existing preempt/resume path and no step is lost or double-counted."""

    def __init__(self, dispatcher: "HostDispatcher"):
        self.disp = dispatcher
        # settable so ClusterRunner's tracer-adoption contract applies to
        # the remote executor exactly like the local one
        self.tracer = dispatcher.tracer

    def pack_template(self, cfg, configs, seed: int = 0):
        """Pre-warm hook: templates are built inside each worker (their
        cache lives with the devices), so the dispatcher side is a no-op."""
        return None

    def run_segment(
        self,
        seg,
        configs_by_cid: Dict,
        total_steps: Dict[int, int],
        cfg,
        base_params,
        *,
        seq: int,
        pool,
        data_iter_fn: Optional[Callable] = None,
        seed: int = 0,
        slice_=None,
        impl: Optional[str] = None,
        remat: Optional[str] = None,
        base_dtype: Optional[str] = None,
    ):
        d = self.disp
        if slice_ is None:
            raise ValueError(
                "multi-host dispatch needs an explicit mesh slice "
                "(unplanned segments have no host)"
            )
        hosts = {dev.host for dev in slice_.devices}
        if len(hosts) != 1:
            raise RuntimeError(
                f"segment units {slice_.units} span hosts {sorted(hosts)}; "
                "plan with ExecutionEngine(..., host_size=...) so every "
                "job's units stay on one host"
            )
        host = hosts.pop()
        local_units = tuple(sorted(dev.local for dev in slice_.devices))
        d._prepare(
            cfg, configs_by_cid, total_steps, base_params, seq, seed,
            data_iter_fn,
        )
        states: Dict[str, Tuple[dict, dict]] = {}
        for cid, st0 in zip(seg.config_ids, seg.start_steps):
            if st0 > 0 and pool is not None:
                aid = f"{cid:04d}"
                if pool.has_adapter_state(aid):
                    tree, meta = pool.load_adapter_state(aid)
                    states[aid] = (encode_tree(tree), dict(meta))
        base_payload = {
            "seg": encode_segment(seg),
            "units": local_units,
            "states": states,
            "has_pool": pool is not None,
            # the caller's kernel policy rides with every segment: workers
            # run exactly the tier the dispatcher-side planner selected
            "policy": KernelPolicy(
                impl=None if impl == "auto" else impl, remat=remat,
                base_dtype=base_dtype,
            ),
        }
        tracer = self.tracer
        with tracer.span(
            "dispatch.segment", cat="dispatch", track=f"host{host}",
            job_id=seg.job_id, host=host, units=list(slice_.units),
        ) as dspan:
            if tracer.enabled:
                base_payload["trace"] = tracer.context()
            t_start = time.perf_counter()
            last_died: Optional[WorkerDied] = None
            for _attempt in range(d.max_restarts + 1):
                rid = next(d._rid)
                try:
                    worker = d._ensure_host(host)
                    t_send = time.perf_counter()
                    reply = worker.request(
                        rid, ("run", dict(base_payload, req=rid))
                    )
                    out = reply.wait()
                except WorkerDied as e:
                    last_died = e
                    continue  # respawn + re-dispatch: preempt/resume path
                rec = decode_record(out["record"])
                if pool is not None:
                    for w in out["writes"]:
                        if w.kind == "adapter":
                            pool.save_adapter(w.adapter_id, w.tree, w.meta)
                        else:
                            pool.save_adapter_state(
                                w.adapter_id, w.tree, w.meta
                            )
                if tracer.enabled and out.get("spans"):
                    # worker clocks aren't comparable: rebase so the
                    # worker's root span starts at the moment this side
                    # handed the request to the transport
                    tracer.ingest(
                        out["spans"],
                        offset=t_send - out["span_t0"],
                        parent_id=dspan.span_id,
                        track_prefix=f"host{host}/",
                    )
                # dispatcher-clock interval (worker clocks aren't
                # comparable); ClusterRunner/_run_adaptive re-base these
                # against their t0
                rec.real_start = t_start
                rec.real_end = time.perf_counter()
                return rec
            raise WorkerDied(
                f"host {host} died {d.max_restarts + 1} times executing job "
                f"{seg.job_id} (segment of configs {seg.config_ids})"
            ) from last_died


def require_cpu_parent() -> None:
    """Refuse to simulate hosts from a process whose JAX runs on a chip.

    Workers are CPU subprocesses. A chip belongs to one process, so a parent
    that holds one would leave the workers nothing but the CPU: the run
    would quietly train there. The chips of one host are units of one
    in-process :class:`~repro.cluster.pool.DevicePool` instead."""
    import jax

    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"the multi-host tier simulates hosts as CPU subprocesses, but "
            f"this process runs JAX on {backend!r} and holds its chips; run "
            "the chips of this host as units of one DevicePool (ClusterRunner"
            "; launch/train.py without --hosts/--devices-per-host)"
        )


class HostDispatcher:
    """Process-per-host execution of planned segments.

    Implements the :class:`~repro.cluster.api.Runner` protocol: ``run``
    executes a batch of segments (via an internal ``ClusterRunner`` whose
    executor is remote), and ``.executor`` / ``.device_pool`` /
    ``.concurrent`` plug into ``ExecutionEngine._run_adaptive`` directly.

    ``hosts`` is either a per-host device-count list (``[4, 4]`` = two
    4-device hosts) or an int paired with ``devices_per_host``. Global unit
    ``u`` maps to ``(host, local)`` via the cumulative offsets; plans must
    keep each job on one host (``ExecutionEngine(host_size=...)``).

    ``transport_factory(host_id, n_devices)`` defaults to spawning a real
    subprocess (:class:`ProcessTransport`); tests inject in-memory fakes.
    Workers are started lazily, restarted on death (``max_restarts`` per
    segment), and torn down by ``close()`` / the context manager. The
    parent's JAX must run on the CPU (:func:`require_cpu_parent`)."""

    def __init__(
        self,
        hosts: Union[int, Sequence[int]],
        devices_per_host: int = 1,
        *,
        transport_factory: Optional[Callable] = None,
        max_restarts: int = 2,
        start_timeout: float = 300.0,
        tracer=None,
        host_classes: Optional[Sequence[str]] = None,
        heartbeat_interval: float = 0.0,
        heartbeat_timeout: Optional[float] = None,
        heartbeat_dead_after: int = 3,
        send_deadline: float = 30.0,
        send_retries: int = 2,
    ):
        require_cpu_parent()
        if isinstance(hosts, int):
            hosts = [devices_per_host] * hosts
        self.hosts: Tuple[int, ...] = tuple(int(n) for n in hosts)
        if not self.hosts or any(n <= 0 for n in self.hosts):
            raise ValueError(f"bad host layout {self.hosts}")
        if host_classes is None:
            host_classes = [""] * len(self.hosts)
        if len(host_classes) != len(self.hosts):
            raise ValueError(
                f"{len(host_classes)} host classes for {len(self.hosts)} hosts"
            )
        self.host_classes: Tuple[str, ...] = tuple(str(c) for c in host_classes)
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.max_restarts = max_restarts
        self.start_timeout = start_timeout
        self._transport_factory = transport_factory or ProcessTransport
        self.n_restarts = 0
        self._rid = itertools.count()
        self._workers: List[Optional[HostWorker]] = [None] * len(self.hosts)
        self._host_locks = [threading.Lock() for _ in self.hosts]
        self._payload: Optional[Dict[str, Any]] = None
        self._payload_token = None
        self._payload_refs: Tuple = ()  # pins id()s used in the memo token
        self._payload_version = 0
        self._prep_lock = threading.Lock()
        self.send_deadline = send_deadline
        self.send_retries = send_retries

        from repro.cluster.pool import DevicePool

        units = [
            HostUnit(h, i)
            for h, n in enumerate(self.hosts)
            for i in range(n)
        ]
        self.device_pool = DevicePool(devices=units)
        # global unit ids per host (stable: add_host only appends)
        self._host_units: List[Tuple[int, ...]] = []
        off = 0
        for n in self.hosts:
            self._host_units.append(tuple(range(off, off + n)))
            off += n
        self.executor = DispatchExecutor(self)
        self.concurrent = True
        self.last_result = None

        # -- membership / health ------------------------------------------
        self._membership_lock = threading.Lock()
        self._membership_subs: List[Callable] = []
        self._host_state: List[str] = [HOST_ALIVE] * len(self.hosts)
        self._last_pong: List[float] = [0.0] * len(self.hosts)
        self._hb_misses: List[int] = [0] * len(self.hosts)
        self.heartbeat_interval = float(heartbeat_interval)
        self.heartbeat_timeout = (
            float(heartbeat_timeout)
            if heartbeat_timeout is not None
            else 3.0 * self.heartbeat_interval
        )
        self.heartbeat_dead_after = int(heartbeat_dead_after)
        self._hb_seq = itertools.count()
        self._closing = threading.Event()
        self._watchdog: Optional[threading.Thread] = None
        self._hosts_alive_gauge()
        if self.heartbeat_interval > 0:
            self._watchdog = threading.Thread(
                target=self._watchdog_loop, name="plora-watchdog", daemon=True
            )
            self._watchdog.start()

    # -- topology -----------------------------------------------------------

    @property
    def total_units(self) -> int:
        return sum(self.hosts)

    @property
    def host_size(self) -> Optional[int]:
        """Uniform per-host width (what ``ExecutionEngine(host_size=...)``
        wants), or None when hosts are heterogeneous."""
        return self.hosts[0] if len(set(self.hosts)) == 1 else None

    def units_of_host(self, host: int) -> Tuple[int, ...]:
        """Global pool unit ids backing one host."""
        return self._host_units[host]

    def host_of_unit(self, unit: int) -> int:
        return self.device_pool.devices[unit].host

    def in_flight(self, host: int) -> int:
        w = self._workers[host]
        return 0 if w is None else w.in_flight()

    # -- membership / health ------------------------------------------------

    def host_state(self, host: int) -> str:
        return self._host_state[host]

    @property
    def hosts_alive(self) -> int:
        return sum(
            1 for s in self._host_state if s in (HOST_ALIVE, HOST_SUSPECT)
        )

    def _hosts_alive_gauge(self) -> None:
        self.tracer.metrics.gauge("cluster.hosts_alive").set(self.hosts_alive)

    def _set_host_state(self, host: int, state: str, **why) -> None:
        prev = self._host_state[host]
        if prev == state:
            return
        self._host_state[host] = state
        self.tracer.instant(
            f"host{host}.{state}", cat="host", track="membership",
            host=host, state=state, prev=prev, **why,
        )
        self._hosts_alive_gauge()

    def membership_subscribe(self, cb: Callable) -> Callable:
        """Register ``cb(event_dict)`` for join/drain notifications (called
        from the announcing thread). Returns an unsubscribe callable. The
        engine's adaptive loop uses this to replan onto joining hosts and
        off draining ones."""
        with self._membership_lock:
            self._membership_subs.append(cb)

        def unsubscribe():
            with self._membership_lock:
                if cb in self._membership_subs:
                    self._membership_subs.remove(cb)

        return unsubscribe

    def _announce(self, event: Dict[str, Any]) -> None:
        with self._membership_lock:
            subs = list(self._membership_subs)
        for cb in subs:
            cb(dict(event))

    def add_host(
        self, n_devices: Optional[int] = None, *, host_class: str = "",
    ) -> int:
        """Admit a new host mid-run: extend the layout, register its units
        with the device pool (free immediately — blocked acquires wake), and
        announce a ``join`` event so the engine replans onto it. The worker
        itself spawns lazily on first dispatch, like every other host.
        Returns the new host id."""
        n = int(n_devices) if n_devices is not None else self.hosts[0]
        if n <= 0:
            raise ValueError(f"bad device count {n}")
        host = len(self.hosts)
        self.hosts = self.hosts + (n,)
        self.host_classes = self.host_classes + (str(host_class),)
        self._workers.append(None)
        self._host_locks.append(threading.Lock())
        self._host_state.append(HOST_ALIVE)
        self._last_pong.append(0.0)
        self._hb_misses.append(0)
        units = self.device_pool.add_devices(
            [HostUnit(host, i) for i in range(n)]
        )
        self._host_units.append(units)
        self.tracer.instant(
            f"host{host}.{HOST_ALIVE}", cat="host", track="membership",
            host=host, state=HOST_ALIVE, reason="join",
            host_class=host_class, units=list(units),
        )
        self._hosts_alive_gauge()
        self._announce({
            "action": "join", "host": host, "units": units,
            "host_class": str(host_class), "n_devices": n,
        })
        return host

    def drain_host(self, host: int, *, timeout: float = 120.0) -> None:
        """Gracefully retire one host: announce ``drain`` (the engine stops
        assigning and force-replans residuals off the host), let in-flight
        segments finish — their checkpoint writes land through the normal
        success-atomic path, so no step is lost — then retire the units from
        the pool and stop the worker. The graceful sibling of
        :meth:`kill_host`."""
        if self._host_state[host] in (HOST_DRAINING, HOST_DEAD):
            return
        self._set_host_state(host, HOST_DRAINING, reason="drain")
        self._announce({
            "action": "drain", "host": host,
            "units": self.units_of_host(host),
            "host_class": self.host_classes[host],
        })
        deadline = time.perf_counter() + timeout
        while True:
            # re-read each pass: a mid-drain death respawns the worker (the
            # retry path re-runs the killed segment from its last checkpoint)
            # and the drain must wait out the *current* worker's in-flight.
            w = self._workers[host]
            if w is None or w.dead or w.in_flight() == 0:
                break
            if time.perf_counter() > deadline:
                raise TimeoutError(
                    f"host {host} still has {w.in_flight()} segment(s) in "
                    f"flight after {timeout:.0f}s drain window"
                )
            time.sleep(0.01)
        # in-flight work done; now the units must come home to the pool
        # (the engine releases each slice as its segment completes)
        self.device_pool.retire_units(
            self.units_of_host(host),
            timeout=max(deadline - time.perf_counter(), 0.01),
        )
        w = self._workers[host]
        if w is not None:
            try:
                if w.transport.alive():
                    w.send(("stop", {}))
                    w.transport.join(timeout=10)
            except Exception:
                pass
            try:
                w.transport.kill()
            except Exception:
                pass
        self._set_host_state(host, HOST_DEAD, reason="drained")

    # -- heartbeat watchdog -------------------------------------------------

    def _on_pong(self, host: int, payload) -> None:
        rtt = time.perf_counter() - payload.t_send
        self.tracer.metrics.histogram("cluster.heartbeat_rtt").record(rtt)
        self._last_pong[host] = time.perf_counter()
        self._hb_misses[host] = 0
        if self._host_state[host] == HOST_SUSPECT:
            self._set_host_state(host, HOST_ALIVE, reason="pong")

    def _watchdog_loop(self) -> None:
        """Ping every live worker each interval; a host missing its deadline
        goes SUSPECT, each further miss doubles the grace (exponential
        backoff — a paused/hung worker can still come back), and after
        ``heartbeat_dead_after`` misses the host is declared DEAD: its
        in-flight replies fail with :class:`WorkerDied` (so ``run()`` never
        hangs on a hung-but-alive process) and the existing restart path
        respawns it on the next dispatch."""
        while not self._closing.wait(self.heartbeat_interval):
            now = time.perf_counter()
            for host in range(len(self.hosts)):
                w = self._workers[host]
                if w is None or w.dead or not w.ready.is_set():
                    continue
                if self._host_state[host] == HOST_DEAD:
                    continue
                if self._last_pong[host] == 0.0:
                    self._last_pong[host] = now  # first ping epoch
                try:
                    w.send(("ping", HeartbeatMsg(
                        seq=next(self._hb_seq), t_send=time.perf_counter(),
                    )))
                except Exception:
                    pass  # counted as a miss below
                misses = self._hb_misses[host]
                due = self._last_pong[host] + (
                    self.heartbeat_timeout * (2 ** misses)
                )
                if now <= due:
                    continue
                self._hb_misses[host] = misses + 1
                if self._host_state[host] == HOST_ALIVE:
                    self._set_host_state(
                        host, HOST_SUSPECT, reason="heartbeat_timeout",
                        misses=misses + 1,
                    )
                if self._hb_misses[host] >= self.heartbeat_dead_after:
                    self._set_host_state(
                        host, HOST_DEAD, reason="heartbeat_expired",
                        misses=self._hb_misses[host],
                    )
                    w._fail_all()
                    try:
                        w.transport.kill()
                    except Exception:
                        pass

    # -- worker lifecycle ---------------------------------------------------

    def _prepare(
        self, cfg, configs_by_cid, total_steps, base_params, seq, seed,
        data_iter_fn,
    ) -> None:
        """Cache the run-level init payload (model config, base params,
        budgets) once per workload; (re)started workers receive it before
        any segment. One dispatcher serves one workload at a time.

        The memo token holds configs/budgets *by value* (LoraConfig is
        hashable) and pins ``base_params``/``data_iter_fn`` alive on
        ``_payload_refs`` so their id()s cannot be recycled by a later
        workload — an id-only token could silently reuse stale state."""
        with self._prep_lock:
            token = (
                cfg, id(base_params), id(data_iter_fn), seq, seed,
                tuple(sorted(configs_by_cid.items())),
                tuple(sorted(total_steps.items())),
            )
            if token == self._payload_token:
                return
            if data_iter_fn is not None:
                try:
                    pickle.dumps(data_iter_fn)
                except Exception as e:
                    raise ValueError(
                        "data_iter_fn must be picklable (a module-level "
                        "callable) to cross the host boundary"
                    ) from e
            self._payload = {
                "cfg": cfg,
                "configs_by_cid": dict(configs_by_cid),
                "total_steps": {int(k): int(v) for k, v in total_steps.items()},
                "base": encode_tree(base_params),
                "seq": int(seq),
                "seed": int(seed),
                "data_iter_fn": data_iter_fn,
            }
            self._payload_token = token
            self._payload_refs = (base_params, data_iter_fn)
            self._payload_version += 1

    def _ensure_host(self, host: int) -> HostWorker:
        """Live, initialized worker for ``host`` — spawning or respawning
        (counted in ``n_restarts``) as needed. Safe to call from concurrent
        segment threads; only one respawn happens per death."""
        with self._host_locks[host]:
            w = self._workers[host]
            if w is not None and not w.dead and w.transport.alive():
                if self._payload is not None and (
                    w.init_version != self._payload_version
                ):
                    w.send(("init", self._payload))
                    w.init_version = self._payload_version
                return w
            if w is not None:
                # restart credits are for failures that cost work: a worker
                # that died *idle* (no request in flight) lost nothing, so
                # its respawn is free — see test_multihost.py regression pair
                if w.died_in_flight:
                    self.n_restarts += 1
                try:
                    w.transport.kill()
                except Exception:
                    pass
            w = HostWorker(
                host, self.hosts[host],
                self._transport_factory(host, self.hosts[host]),
                on_pong=self._on_pong,
                send_deadline=self.send_deadline,
                send_retries=self.send_retries,
            )
            self._workers[host] = w
            w.wait_ready(self.start_timeout)
            self._last_pong[host] = time.perf_counter()
            self._hb_misses[host] = 0
            if self._host_state[host] in (HOST_SUSPECT, HOST_DEAD):
                self._set_host_state(host, HOST_ALIVE, reason="respawn")
            if self._payload is not None:
                w.send(("init", self._payload))
                w.init_version = self._payload_version
            return w

    def kill_host(self, host: int) -> None:
        """Fault injection / hard teardown: SIGKILL the host's worker. Any
        in-flight segment fails with :class:`WorkerDied` and is re-dispatched
        onto a fresh worker by :meth:`DispatchExecutor.run_segment`."""
        w = self._workers[host]
        if w is not None:
            w.transport.kill()

    def close(self) -> None:
        """Graceful stop of every worker (kill as fallback)."""
        self._closing.set()
        if self._watchdog is not None:
            self._watchdog.join(timeout=5)
        for w in self._workers:
            if w is None:
                continue
            try:
                if w.transport.alive():
                    w.transport.send(("stop", {}))
                    w.transport.join(timeout=10)
            except Exception:
                pass
            try:
                w.transport.kill()
            except Exception:
                pass

    def __enter__(self) -> "HostDispatcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- Runner protocol ----------------------------------------------------

    def run(
        self,
        segments,
        configs_by_cid,
        total_steps,
        cfg,
        base_params,
        *,
        seq: int,
        pool=None,
        data_iter_fn=None,
        seed: int = 0,
        estimator=None,
        impl: Optional[str] = None,
        remat: Optional[str] = None,
        base_dtype: Optional[str] = None,
    ):
        """Execute planned segments across the hosts — same contract as
        :meth:`ClusterRunner.run` (dispatch order, resume dependencies,
        device-free events from real completions, timings feedback), with
        each segment running in its host's worker process. ``impl``/``remat``
        ship to the workers as a :class:`KernelPolicy` with every segment."""
        from repro.cluster.runner import ClusterRunner

        runner = ClusterRunner(
            self.executor, self.device_pool, concurrent=True,
            tracer=self.tracer,
        )
        result = runner.run(
            segments,
            configs_by_cid,
            total_steps,
            cfg,
            base_params,
            seq=seq,
            pool=pool,
            data_iter_fn=data_iter_fn,
            seed=seed,
            estimator=estimator,
            impl=impl,
            remat=remat,
            base_dtype=base_dtype,
        )
        self.last_result = result
        return result
