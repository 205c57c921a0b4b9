"""Readings that the check's limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ... \
        [--controls 3] [--out readings.jsonl]

For each seed, in one process: the cell's set-up and check passes, then
the reference, and the three gaps of the sound program. For the first
``--controls`` seeds also the control (the reference in the program's
place at float8) and the planted faults (``bench/faults.py``). One JSON
line per reading. No window is measured.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--faults", nargs="*",
                    default=["half_batch", "altered_answer"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    from bench import faults, harness

    _, cell, cfg_file, mix = harness.find_cell(args.workload)
    devices = harness.check_devices(int(cell["chips"]), jax.devices())
    kind = devices[0].device_kind
    harness.pin_compile_cache()

    def emit(**kw):
        line = json.dumps(kw)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")

    st = None
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        if st is None:
            st = harness.setup(cfg_file, mix, seed=seed, devices=devices,
                               kind=kind)
        else:  # the same compiled sweep; new weights, rows and adapters
            harness.reseed(st, seed)
        t1 = time.perf_counter()
        planted = {}
        if i < args.controls:
            for fault in args.faults:
                with faults.planted(st.sweep.executor, fault):
                    planted[fault] = harness.check_passes(st)
        st.sweep.executor.drop_templates()
        t2 = time.perf_counter()
        refs = harness.reference_results(st)
        t3 = time.perf_counter()
        emit(seed=seed, kind="program",
             gaps=harness.gaps(harness.pair(st.records, refs)),
             program_s=t1 - t0, reference_s=t3 - t2)
        for fault, rec in planted.items():
            emit(seed=seed, kind=fault, gaps=harness.gaps(harness.pair(rec, refs)))
        if i < args.controls:
            low = harness.as_records(harness.reference_results(st, lowp=True))
            emit(seed=seed, kind="control",
                 gaps=harness.gaps(harness.pair(low, refs)),
                 control_s=time.perf_counter() - t3)
    return 0


if __name__ == "__main__":
    sys.exit(main())
