"""Idle time and program spans of a reduced trace, on one clock.

Works on the record of ``reduce.py`` after the harness has mapped the
program's spans into ``host`` as ``span.<name>`` events: every number here
comes from ``record["devices"]`` and ``record["host"]`` alone, so the small
recorded trace tests it without a chip.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

from bench.trace import reduce as tr


def intervals(record: dict, name: str, start_ns: int, end_ns: int):
    """The union of the host events ``name``, clipped to the window."""
    spans = [[s, d] for s, d, n in record["host"] if n == name]
    return tr.busy_intervals(spans, start_ns, end_ns)


def count(record: dict, name: str) -> int:
    """How many host events ``name`` the record holds."""
    return sum(1 for *_, n in record["host"] if n == name)


def overlap_ns(a: Sequence[Tuple[int, int]], b: Sequence[Sequence[int]]) -> int:
    """Length of the intersection of two sorted unions of intervals."""
    total, j = 0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += max(0, min(e, b[k][1]) - max(s, b[k][0]))
            k += 1
    return total


def idle_split(record: dict, window_ns: Tuple[int, int], name: str):
    """(outside, inside) seconds: each chip's idle time in the window
    outside and inside the host events ``name``, mean over chips. The two
    add up to the idle time ``device.idle_pct`` counts."""
    a, b = window_ns
    spans = intervals(record, name, a, b)
    chips = list(record["devices"].values())
    outside = inside = 0
    for ops in chips:
        gaps: List[Tuple[int, int]] = tr.idle_gaps(ops, a, b)
        idle = sum(e - s for s, e in gaps)
        within = overlap_ns(gaps, spans)
        inside += within
        outside += idle - within
    n = max(1, len(chips))
    return outside / n / 1e9, inside / n / 1e9


def span_ns(record: dict, window_ns: Tuple[int, int], name: str) -> int:
    """Summed length of the host events ``name``, clipped to the window."""
    a, b = window_ns
    return sum(max(0, min(s + d, b) - max(s, a))
               for s, d, n in record["host"] if n == name)
