"""From a ``jax.profiler`` trace to the numbers the metric readers need.

``load_xplane`` turns the profiler's ``.xplane.pb`` into a plain record:

    {"devices": {"0": [[start_ns, dur_ns, name, class], ...], ...},
     "host": [[start_ns, dur_ns, name], ...]}

``devices`` holds the operations that ran on each chip (the "XLA Ops" line
of each ``/device:TPU:<n>`` plane), each with a short name (``%fusion.522
bf16[4,512,152064]``) and its class (``op_class``); ``host`` holds the
annotations the benchmark and the program opened on the host. Both are on the profiler's clock. The
rest works on that record alone, so a small recorded one
(``recorded_small.json.gz``) tests it without a chip.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# the host events kept: the benchmark's own annotations and program spans
HOST_PREFIXES = ("bench.", "span.")


def short_name(text: str) -> str:
    """``%fusion.522 bf16[4,512,152064]`` from an operation's HLO text."""
    head, _, rest = text.partition(" = ")
    m = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return f"{head} {m.group(1)}" if m else head


def op_class(text: str) -> str:
    """The class of an operation, from its HLO text: ``control`` (a loop
    or conditional whose body's operations appear on their own), ``pallas``
    (a Mosaic kernel), ``matmul`` (a dot or convolution, alone or as the
    root of an output fusion), ``collective``, ``copy`` or ``other``."""
    head = text.partition(" = ")[0]
    if re.match(r"%(while|conditional|call)\b", head):
        return "control"
    if 'custom_call_target="tpu_custom_call"' in text:
        return "pallas"
    if re.match(r"%(convolution|dot)", head) or "kind=kOutput" in text:
        return "matmul"
    if re.match(r"%(all-reduce|all-gather|reduce-scatter|all-to-all|"
                r"collective-permute)", head):
        return "collective"
    if re.match(r"%(copy|slice|dynamic|bitcast|reshape|transpose|pad)", head):
        return "copy"
    return "other"


def load_xplane(trace_dir: str) -> dict:
    """The plain record of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: Dict[str, list] = {}
    host = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = devices.setdefault(m.group(1), [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append([int(ev.start_ns), int(ev.duration_ns),
                                short_name(ev.name), op_class(ev.name)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        host.append([int(ev.start_ns), int(ev.duration_ns),
                                     ev.name])
    for ops in devices.values():
        ops.sort()
    host.sort()
    return {"devices": devices, "host": host}


def save(record: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(record, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def clip(record: dict, start_ns: int, end_ns: int) -> dict:
    """The part of a record inside [start_ns, end_ns)."""
    def inside(ev):
        return ev[0] < end_ns and ev[0] + ev[1] > start_ns
    return {
        "devices": {d: [e for e in ops if inside(e)]
                    for d, ops in record["devices"].items()},
        "host": [e for e in record["host"] if inside(e)],
    }


def window(record: dict, name: str = "bench.window") -> Optional[Tuple[int, int]]:
    """[start, end) of the host annotation ``name``, if the trace has it."""
    for start, dur, n in record["host"]:
        if n == name:
            return start, start + dur
    return None


def busy_intervals(ops: Sequence, start_ns: int, end_ns: int):
    """The union of the operations' intervals, clipped to the window."""
    out: List[List[int]] = []
    for s, d, *_ in sorted(ops):
        a, b = max(s, start_ns), min(s + d, end_ns)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_ns(ops, start_ns, end_ns) -> int:
    return sum(b - a for a, b in busy_intervals(ops, start_ns, end_ns))


def idle_gaps(ops, start_ns, end_ns):
    """[(start, end)] of every stretch of the window with no operation."""
    gaps, t = [], start_ns
    for a, b in busy_intervals(ops, start_ns, end_ns):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if end_ns > t:
        gaps.append((t, end_ns))
    return gaps


def op_seconds(ops, start_ns, end_ns, cls: Optional[str] = None) -> float:
    """Device seconds of the operations (of one class) in the window."""
    total = 0
    for s, d, name, c in ops:
        a, b = max(s, start_ns), min(s + d, end_ns)
        if b > a and c != "control" and (cls is None or c == cls):
            total += b - a
    return total / 1e9


def top_ops(record, start_ns, end_ns, n=10):
    """The ``n`` operations that took the most device time, by class, kind
    and output shape, summed over chips and divided by their number:
    [["matmul %fusion bf16[4,1024,18944]", seconds], ...]."""
    acc: Dict[str, int] = {}
    for ops in record["devices"].values():
        for s, d, name, c in ops:
            a, b = max(s, start_ns), min(s + d, end_ns)
            if b > a and c != "control":
                key = f"{c} " + re.sub(r"\.\d+(?= |$)", "", name)
                acc[key] = acc.get(key, 0) + (b - a)
    k = max(1, len(record["devices"]))
    return [[name, ns / 1e9 / k]
            for name, ns in sorted(acc.items(), key=lambda x: -x[1])[:n]]


def host_label(record, t_ns: int) -> str:
    """The innermost host annotation open at ``t_ns``."""
    best = None
    for s, d, name in record["host"]:
        if s <= t_ns < s + d and (best is None or d < best[1]):
            best = (s, d, name)
    return best[2] if best else "unannotated"


def longest_gaps(record, start_ns, end_ns, n=10):
    """The ``n`` longest idle gaps over all chips, each named by what the
    host was doing in its middle: [[label, seconds], ...]."""
    gaps = []
    for ops in record["devices"].values():
        for a, b in idle_gaps(ops, start_ns, end_ns):
            gaps.append((b - a, host_label(record, (a + b) // 2)))
    gaps.sort(key=lambda g: -g[0])
    return [[label, ns / 1e9] for ns, label in gaps[:n]]
