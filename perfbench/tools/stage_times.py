#!/usr/bin/env python3
"""Host time by program stage: run a cell and print the program's span
recorder (``repro.serve.telemetry``) after each run.

    python3 perfbench/tools/stage_times.py --workload gw_small.live \
        --seconds 10 --seeds 1 2 3 [--trace 0|1]

runs the cell once per seed, in one process on the chip, and prints one
JSON line per run: the seed, ``correct``, the run's metrics, and the
recorder's ``snapshot()`` (count, p50/p99/max and self-time p50 of every
span; the counters), reset before each run.  With ``--trace 0`` no
profiler runs, so the spans time the program as the end-to-end runs meet
it; with ``--trace 1`` they include what the profiler's session costs.
The recorder also holds the run's warm-up and lead-in: medians, not
means, read it.
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

from perfbench import harness, telemetry  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    from repro.serve import telemetry as recorder

    for seed in args.seeds:
        recorder.reset()
        result = harness.run_cell(ROOT, args.workload, seed, args.seconds,
                                  bool(args.trace),
                                  t_start=time.perf_counter())
        print(json.dumps({
            "line": "stage_times", "workload": args.workload, "seed": seed,
            "trace": args.trace, "correct": result["correct"],
            "metrics": {k: m["value"] for k, m in result["metrics"].items()},
            "window_s": result["device"].get("window_s"),
            "snapshot": telemetry.snapshot(),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
