#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` for the program and for each
of a model's named controls, on one seed, at a cell's own size.

    python3 perfbench/tools/controls.py --workload granite4_h_micro.chat \
        --seconds 10 --seed 101

It runs the cell's timed path once and prints one JSON line: the
program's numbers (its scores against the plain reference) and, for each
name in the reference module's ``CONTROLS``, that control's scores
against the same reference.  One process a seed, so that no seed's
weights are held while the next seed's are made.  A limit lies above the
program's largest reading and below the least reading of the control it
is set against.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from perfbench import harness  # noqa: E402
from perfbench.tools.calibrate import readings  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()
    cell = harness.load_cell(ROOT, args.workload)
    harness.device_info(cell.chips)
    harness.enable_compile_cache(ROOT)
    counter = harness.CompileCounter()
    ctx = harness.Context(cell=cell, seed=args.seed, seconds=args.seconds,
                          trace=False, t_start=time.perf_counter(),
                          compiles=counter, trace_dir=None, chips=cell.chips)
    out = cell.kind.run(ctx)
    model, cfg = cell.model, cell.config
    want = model.scores(out.params, out.windows, cfg)
    row = {"workload": args.workload, "seed": args.seed,
           "compared": int(len(out.scores)), "missing": out.missing,
           "program": readings(out.scores, want)}
    for name in model.CONTROLS:
        row[name] = readings(
            model.scores(out.params, out.windows, cfg, control=name), want)
    print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
