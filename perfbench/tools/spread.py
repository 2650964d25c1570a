#!/usr/bin/env python3
"""Spread of a cell's end-to-end metrics over sets of runs.

    python3 perfbench/tools/spread.py set1.jsonl set2.jsonl

Each file holds the result lines of one set of runs (the last stdout line
of ``perfbench/run.py``, one per line).  For each metric it prints each
set's median and spread (quartile distance over the median, with
``statistics.quantiles(values, n=4)``), the wider spread, and five times
the wider spread as a suggested bound (never under 1%).
"""

from __future__ import annotations

import json
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from perfbench.stats import spread  # noqa: E402


def main(paths: list[str]) -> int:
    sets = []
    for p in paths:
        rows = [json.loads(line) for line in Path(p).read_text().splitlines()
                if line.startswith("{")]
        sets.append(rows)
    names = sorted({m for rows in sets for r in rows for m in r["metrics"]})
    for m in names:
        per = []
        for rows in sets:
            vals = [r["metrics"][m]["value"] for r in rows if m in r["metrics"]]
            per.append({"n": len(vals), "median": statistics.median(vals),
                        "spread": spread(vals) if len(vals) >= 2 else None,
                        "values": vals})
        wide = max(s["spread"] for s in per if s["spread"] is not None)
        print(json.dumps({"metric": m, "sets": per, "widest_spread": wide,
                          "bound_5x": max(0.01, 5 * wide)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
