"""One run of one benchmark cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a model configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<mix>.json``). The configuration's ``model``
names three modules, each ``<model>.py``, and a cell whose model lacks one is
refused before set-up:

- ``bench/models/``: the model's shape, as ``bench/models/__init__.py`` sets
  out: ``spec(cfg_file)`` (sizes, with the vocabulary ``"V"``),
  ``make_weights(seed, spec, device)``, ``program_config(cfg_file)``,
  ``base_tree(weights, cfg)`` and ``adapter_norms(tree)``;
- ``bench/reference/``: the plain reference, ``init_adapters`` and ``train``;
- ``bench/flops/``: the required work of a step, for the per-layer readers.

The run:

1. set-up: makes the frozen base from the seed on the chip, plans the mix's
   grid with the program's planner and the chip's prior, and drives the
   planned sweep through ``ExecutionEngine.run_local`` twice, for 1 step and
   for 3 steps. These check passes compile every program the window uses,
   and what they return is what ``correct`` compares;
2. window: whole passes of the planned sweep, ``steps_per_job`` steps per
   job from fresh adapters, until ``--seconds`` have gone by;
3. with ``--trace 1`` the window runs under the JAX profiler with the
   program's tracer on, and the per-layer readers (``bench/metrics/``) take
   their numbers from the trace, the spans and the counters;
4. the check: the program's state is freed and the plain reference
   (``bench/reference/<model>.py``) trains each of ``CHECK_ADAPTERS``
   adapters of the grid, drawn from the seed, alone;
   the gaps are compared with ``bench/limits/<cell>.json``.

The last line of stdout is one JSON object. A backend that is not a TPU, too
few chips or a chip kind missing from ``bench/peaks.json`` exits 2 and prints
no result.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from types import SimpleNamespace

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
CHECK_STEPS = 3
# adapters the reference trains per run, drawn from the seed: the float32
# reference takes ~3 s per adapter of the qwen grid on a v5e, so the whole
# grid would take twice the window
CHECK_ADAPTERS = 4


class Refused(Exception):
    """The run cannot be measured here; exit 2 with no result."""


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name=None):
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + os.path.basename(path)[:-3].replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


MODEL_PARTS = ("models", "reference", "flops")


def model_files(cfg_file, root=ROOT):
    """{part: path} of the configuration's model modules,
    ``bench/<part>/<model>.py``; Refused, naming the path, where one is
    missing."""
    model = cfg_file["model"]
    files = {part: os.path.join(root, "bench", part, f"{model}.py")
             for part in MODEL_PARTS}
    for path in files.values():
        if not os.path.isfile(path):
            raise Refused(f"configuration {cfg_file['name']!r} names model "
                          f"{model!r}, and {os.path.relpath(path, root)} is "
                          f"missing")
    return files


def model_module(cfg_file, part, root=ROOT):
    """The configuration's model module of ``part`` (``MODEL_PARTS``)."""
    return load_module(model_files(cfg_file, root)[part],
                       f"bench_{part}_{cfg_file['model']}")


def find_cell(name, root=ROOT):
    """(benchmark, cell, config file, traffic mix) of the cell ``name``;
    Refused where the configuration's model lacks a module."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_file = load_json(os.path.join(root, "bench", "configs",
                                      f"{cell['config']}.json"))
    mix = load_json(os.path.join(root, "bench", "traffic",
                                 f"{cell['traffic']}.json"))
    model_files(cfg_file, root)
    return bench, cell, cfg_file, mix


def load_limits(cell, root=ROOT):
    """{number: limit} of the cell's check (``bench/limits/<cell>.json``)."""
    return load_json(os.path.join(root, "bench", "limits",
                                  f"{cell['name']}.json"))["limits"]


def metric_readers(bench, cell, root=ROOT):
    """{metric: reader module} of every per-layer metric this cell reports."""
    return {m["name"]: load_module(
                os.path.join(root, "bench", "metrics", f"{m['name']}.py"))
            for m in bench["per_layer"]}


def peaks_for(kind, root=ROOT):
    table = load_json(os.path.join(root, "bench", "peaks.json"))["kinds"]
    if kind not in table:
        raise Refused(f"chip kind {kind!r} is not in bench/peaks.json "
                      f"(known: {sorted(table)})")
    return table[kind]


def check_devices(chips, devices):
    """The devices of a cell: a TPU with enough chips, or Refused."""
    if not devices or devices[0].platform != "tpu":
        plat = devices[0].platform if devices else "none"
        raise Refused(f"JAX found no TPU (platform {plat!r})")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found "
                      f"{len(devices)}")
    return devices[:chips]


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def log(msg):
    print(msg, file=sys.stderr, flush=True)


def pin_compile_cache(root=ROOT):
    """JAX's persistent compilation cache at one fixed directory of the
    checkout, with no size cap, whatever the machine's environment says:
    a shared or capped cache evicts the sweep's programs and turns a warm
    set-up into a compile. Every program is cached, however small. The
    program's own ``launch.cache.enable_compile_cache`` takes the same
    directory from the environment. Returns the directory."""
    import jax

    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Times of the compiles and compile-cache loads of this process."""

    NAMES = ("/jax/core/compile/backend_compile_duration",
             "/jax/compilation_cache/cache_hits")

    def __init__(self):
        import jax

        self.times = []
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name in self.NAMES:
            self.times.append(time.perf_counter())

    def _duration(self, name, secs, **_):
        if name in self.NAMES:
            self.times.append(time.perf_counter())

    def between(self, a, b):
        return sum(1 for t in self.times if a <= t <= b)


def setup(cfg_file, mix, *, seed, devices, kind, trace=False, root=ROOT):
    """Everything before the window: the plan and its sweep, then
    ``reseed``. Returns the run's state."""
    from bench import program
    from bench.traffic.generator import grid_points

    model = model_module(cfg_file, "models", root)
    cfg = model.program_config(cfg_file)
    points = grid_points(mix)
    seq, k_steps = int(mix["seq"]), int(mix["steps_per_job"])
    tracer = None
    if trace:
        from repro.obs import Tracer

        tracer = Tracer()
    st = SimpleNamespace(
        cfg_file=cfg_file, mix=mix, model=model, spec=model.spec(cfg_file),
        cfg=cfg, points=points, seq=seq, k_steps=k_steps, tracer=tracer,
        devices=devices,
        sweep=program.Sweep(cfg, points, devices, seq=seq, steps=k_steps,
                            kind=kind, adapter_norms=model.adapter_norms,
                            tracer=tracer),
    )
    log(f"plan: {st.sweep.jobs()}")
    reseed(st, seed)
    return st


def reseed(st, seed):
    """The weights, rows and adapters of ``seed`` on the built sweep, and
    the check passes over them (which compile what the window runs)."""
    import jax
    import numpy as np

    from bench.traffic.generator import RowBank

    st.sweep.executor.drop_templates()
    st.weights = st.base = st.records = None  # the last seed's, freed first
    st.weights = st.model.make_weights(seed, st.spec, st.devices[0])
    st.base = st.model.base_tree(st.weights, st.cfg)
    st.bank = RowBank(seed, st.points,
                      n_steps=max(st.k_steps, CHECK_STEPS), seq=st.seq,
                      vocab=st.spec["V"], noise=float(st.mix["noise"]))
    st.lora_seed = seed % (2**31 - 1)
    pick = np.random.RandomState(st.lora_seed).choice(
        len(st.points), min(CHECK_ADAPTERS, len(st.points)), replace=False)
    st.check_points = [st.points[i] for i in sorted(pick)]
    jax.block_until_ready(st.base)
    st.records = check_passes(st)


def check_passes(st):
    """Drive the planned sweep for 1 step and for CHECK_STEPS steps through
    the window's own call and rows; returns what the executor recorded."""
    ex = st.sweep.executor
    try:
        for mode, n in (("grad", 1), ("update", CHECK_STEPS)):
            ex.mode = mode
            st.sweep.run_pass(st.base, n_steps=n,
                              data_iter_fn=st.bank.iterator,
                              lora_seed=st.lora_seed)
    finally:
        ex.mode = None
    records, ex.records = ex.records, {}
    return records


def run_window(st, seconds, trace):
    """Whole passes of the sweep until ``seconds`` have gone by; returns
    (passes, window start, window end) on the perf_counter clock."""
    import jax

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    passes = 0
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation(f"bench.pass.{passes}"):
                st.sweep.run_pass(st.base, n_steps=st.k_steps,
                                  data_iter_fn=st.bank.iterator,
                                  lora_seed=st.lora_seed)
            passes += 1
            if time.perf_counter() - t0 >= seconds:
                break
        t1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    return passes, t0, t1


def run_cell(cell, cfg_file, mix, *, seed, seconds, trace, devices, kind,
             peaks, limits, readers=None, t_process=None, root=ROOT):
    """Set-up, window, trace and check of one cell; returns the result
    dict. ``devices`` are the chips to use; the caller has checked them."""
    t_process = time.perf_counter() if t_process is None else t_process
    counter = CompileCounter()
    st = setup(cfg_file, mix, seed=seed, devices=devices, kind=kind,
               trace=trace, root=root)
    passes, t0, t1 = run_window(st, seconds, trace)
    setup_s, window_s = t0 - t_process, t1 - t0
    memory_peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
                      for d in devices) if devices[0].platform == "tpu" else 0
    log(f"window: {passes} passes in {window_s:.4f} s; set-up {setup_s:.4f} s")
    tokens_per_pass = sum(p["batch_size"] for p in st.points) * st.seq \
        * st.k_steps
    result = {"attempted": passes * len(st.points), "failed": 0}
    if trace:
        ctx = SimpleNamespace(
            spec=st.spec, seq=st.seq, steps_per_job=st.k_steps, passes=passes,
            window_s=window_s, chips=len(devices), peaks=peaks,
            jobs=st.sweep.jobs(), memory_peak_bytes=memory_peak,
            compiles_in_window=counter.between(t0, t1),
            flops=model_module(cfg_file, "flops", root),
            spans=[s for s in st.tracer.spans() if t0 <= s.start <= t1],
        )
        metrics, breakdown = read_trace(ctx, readers or {}, t0)
        result["breakdown"] = breakdown
        result["metrics"] = metrics
    else:
        result["metrics"] = {
            "sweep_tokens_per_s": {"value": passes * tokens_per_pass / window_s,
                                   "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    # free the program's state, then the reference
    st.sweep = st.base = None
    gc.collect()
    t_ref = time.perf_counter()
    refs = reference_results(st, root=root)
    log(f"reference: {time.perf_counter() - t_ref:.4f} s")
    checks = compare(pair(st.records, refs), limits)
    result["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    if trace:
        device.update(busy_s=ctx.busy_s, window_s=ctx.traced_window_s)
    result["device"] = device
    result["checks"] = checks
    return result


def read_trace(ctx, readers, t0_pc):
    """Reduce the window's profiler trace; returns (metrics, breakdown)."""
    from bench.trace import reduce as tr

    record = tr.load_xplane(TRACE_DIR)
    win = tr.window(record)
    if win is None:
        raise RuntimeError("the trace holds no bench.window annotation")
    # program spans onto the profiler's clock, by the window's start
    offset = win[0] - int(t0_pc * 1e9)
    for s in ctx.spans:
        record["host"].append([int(s.start * 1e9) + offset,
                               int((s.end - s.start) * 1e9), f"span.{s.name}"])
    record["host"].sort()
    ctx.trace = tr.clip(record, *win)
    ctx.window_ns = win
    busy = [tr.busy_ns(ops, *win) / 1e9 for ops in ctx.trace["devices"].values()]
    if not busy or sum(busy) <= 0:
        raise RuntimeError("no device operation in the traced window")
    ctx.busy_s = sum(busy) / len(busy)
    ctx.traced_window_s = (win[1] - win[0]) / 1e9
    metrics = {}
    for name, mod in readers.items():
        value = mod.read(ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": mod.UNIT}
    breakdown = {"device_ops": tr.top_ops(ctx.trace, *win),
                 "idle_gaps": tr.longest_gaps(ctx.trace, *win)}
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return metrics, breakdown


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def reference_results(st, *, lowp=False, root=ROOT):
    """{adapter key: reading} of the reference trained on each adapter of
    ``st.check_points`` alone, from the adapter's initial state in its pack.
    ``lowp`` trains it in the control's precision."""
    from bench.traffic.generator import point_key

    ref = model_module(st.cfg_file, "reference", root)
    inits = ref.init_adapters(
        st.lora_seed, st.spec, {r["pack_ranks"] for r in st.records.values()},
        max(p["rank"] for p in st.points))
    out = {}
    for p in st.check_points:
        key = point_key(p)
        upd = st.records[("update", key)]
        out[key] = ref.train(
            st.weights, st.spec, p, st.bank.rows[key][:CHECK_STEPS],
            inits[(upd["pack_ranks"], upd["slot"])], n_steps=CHECK_STEPS,
            lowp=lowp)
    return out


def pair(records, refs):
    """{adapter key: (grad record, update record, reference reading)}."""
    return {key: (records[("grad", key)], records[("update", key)], r)
            for key, r in refs.items()}


def as_records(refs):
    """Reference readings in the program's record form, so that one
    reference (the control) can stand in the program's place."""
    out = {}
    for key, r in refs.items():
        out[("grad", key)] = {"losses": r["losses"][:1], "norms": r["grads"][0]}
        out[("update", key)] = {"losses": r["losses"], "norms": r["update"]}
    return out


def _leaves(tree):
    """{name.a|b: (layers,)} of a {name: {a|b: (layers,)}} tree; names
    may count different layers."""
    return {f"{p}.{ab}": v for p, d in tree.items() for ab, v in d.items()}


def gaps(readings):
    """The three numbers compared: the widest relative gap of a step's
    loss, of a matrix's first-gradient norm and of a matrix's change after
    the check steps, each over every adapter of the grid."""
    import numpy as np

    loss_gap = grad_gap = update_gap = 0.0
    for grad, upd, ref in readings.values():
        ref_l = ref["losses"]
        prog_l = np.concatenate([grad["losses"][:1], upd["losses"]])
        want = np.concatenate([ref_l[:1], ref_l])
        loss_gap = max(loss_gap, float(np.max(np.abs(prog_l - want)
                                              / np.abs(want))))
        g_ref, g_prog = _leaves(ref["grads"][0]), _leaves(grad["norms"])
        med = float(np.median(np.concatenate(list(g_ref.values()))))
        for k, r in g_ref.items():
            den = np.maximum(r, med)
            grad_gap = max(grad_gap, float(np.max(np.abs(g_prog[k] - r) / den)))
        # a matrix whose gradient is nought to rounding at every step moves
        # under Adam by round-off alone: left out by this rule, not by name
        steps = [_leaves(g) for g in ref["grads"]]
        meds = [float(np.median(np.concatenate(list(s.values()))))
                for s in steps]
        u_ref, u_prog = _leaves(ref["update"]), _leaves(upd["norms"])
        u_med = float(np.median(np.concatenate(list(u_ref.values()))))
        for k, r in u_ref.items():
            moved = np.any([s[k] >= 1e-3 * m for s, m in zip(steps, meds)],
                           axis=0)
            if not moved.any():
                continue
            den = np.maximum(r, u_med)
            gap = np.abs(u_prog[k] - r) / den
            update_gap = max(update_gap, float(np.max(gap[moved])))
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "update_gap": update_gap}


def compare(readings, limits):
    values = gaps(readings)
    return {k: {"value": v, "limit": float(limits[k])}
            for k, v in values.items()}


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def parse(argv):
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, t_process=None):
    args = parse(argv)
    try:
        bench, cell, cfg_file, mix = find_cell(args.workload)
        limits = load_limits(cell)
        import jax

        devices = check_devices(int(cell["chips"]), jax.devices())
        kind = devices[0].device_kind
        peaks = peaks_for(kind)
    except Refused as e:
        log(f"bench: {e}; nothing was run")
        return 2
    log(f"compile cache: {pin_compile_cache()}")
    readers = metric_readers(bench, cell) if args.trace else {}
    result = run_cell(cell, cfg_file, mix, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      devices=devices, kind=kind, peaks=peaks,
                      limits=limits, readers=readers,
                      t_process=t_process)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    line = {k: result[k] for k in ("correct", "attempted", "failed",
                                   "metrics", "device")}
    if "breakdown" in result:
        line["breakdown"] = result["breakdown"]
    line["checks"] = result["checks"]
    print(json.dumps(line), flush=True)
    return 0
