#!/usr/bin/env python3
"""Find the highest stream count a stream cell's server sustains.

    python3 perfbench/tools/knee_sweep.py --workload gw_nominal.fleet \
        --seed 5 --seconds 20 --streams 4 5 6 7 8

runs the cell's traffic once per stream count, in one process on the chip,
and prints one JSON line per count: p50/p95 latency, how late the generator
ran, windows missing, and the p95 of the first and last quarter of the
window (a backlog that grows shows as a rising tail).  The knee is the
highest count at which the tail does not rise and p95 stays within one
window period (timesteps / sample_rate).
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


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--streams", type=int, nargs="+", required=True)
    args = ap.parse_args()
    cell = harness.load_cell(ROOT, args.workload)
    harness.device_info(cell.chips)
    harness.enable_compile_cache(ROOT)
    counter = harness.CompileCounter()
    period_ms = 1e3 * cell.config["timesteps"] / cell.traffic["sample_rate"]
    for n in args.streams:
        cell.traffic["streams"] = n
        ctx = harness.Context(cell=cell, seed=args.seed, seconds=args.seconds,
                              trace=False, t_start=time.perf_counter(),
                              compiles=counter, trace_dir=None,
                              chips=cell.chips)
        out = cell.kind.run(ctx)
        info = {line.pop("line"): line for line in out.info}
        print(json.dumps({
            "streams": n, "window_period_ms": period_ms,
            **out.values, "attempted": out.attempted, "failed": out.missing,
            "late_p99_ms": info["generator"]["late_p99_ms"],
            "stalls_over_20ms": info["host_stalls"].get("over_20ms"),
            **info["latency_trend"],
            "compiles_in_window": info["compiles_in_window"]["compiles"],
            "batch_fill": info["server"]["batch_fill"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
