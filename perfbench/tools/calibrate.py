#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` for the program and for the
control, on several seeds, at a cell's own size, in one process.

    python3 perfbench/tools/calibrate.py --workload gw_nominal.fleet \
        --seconds 3 --seeds 101 102 103

For each seed it runs the cell's timed path for ``--seconds`` and prints
one JSON line with the program's numbers (its scores against the plain
reference) and the control's (the reference computed with one-pass bf16
dots, put in the program's place, against the same reference).  A limit
lies above the program's largest reading and below the control's smallest.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from perfbench import harness  # noqa: E402


def readings(got, want) -> dict:
    rel = np.abs(np.asarray(got, np.float64) - want) / np.abs(want)
    return {"score_rms_rel_err": float(np.sqrt(np.mean(rel**2))),
            "score_max_rel_err": float(np.max(rel))}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    cell = harness.load_cell(ROOT, args.workload)
    harness.device_info(cell.chips)
    harness.enable_compile_cache(ROOT)
    counter = harness.CompileCounter()
    for seed in args.seeds:
        ctx = harness.Context(cell=cell, seed=seed, seconds=args.seconds,
                              trace=False, t_start=time.perf_counter(),
                              compiles=counter, trace_dir=None,
                              chips=cell.chips)
        out = cell.kind.run(ctx)
        model, cfg = cell.model, cell.config
        want = model.scores(out.params, out.windows, cfg)
        ctl = model.scores(out.params, out.windows, cfg, control=True)
        if out.index is not None:
            want, ctl = want[out.index], ctl[out.index]
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "compared": int(len(out.scores)), "missing": out.missing,
            "program": readings(out.scores, want),
            "control": readings(ctl, want),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
