"""Shared helpers of the benchmark's tests: the repo root on the path, and
a scratch checkout of the benchmark in a temporary directory."""

from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def scratch_checkout(tmp: Path) -> Path:
    """``BENCHMARK.json`` and ``perfbench/`` copied to ``tmp``, with the
    program under ``tmp/src`` as a link to this checkout's."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(ROOT / "perfbench", tmp / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp / "src").symlink_to(ROOT / "src")
    return tmp


def add_traffic(root: Path, name: str, traffic: dict) -> None:
    (root / "perfbench" / "traffic" / f"{name}.json").write_text(
        json.dumps(traffic))


def add_cell(root: Path, cell: dict, per_layer: list[dict] = (),
             like: str | None = None) -> None:
    """Append a workload (and per-layer metric entries) to the scratch
    checkout's BENCHMARK.json, and write the cell's own file of limits,
    copied from the cell ``like`` (by default the ``gw_small`` cell of the
    traffic's kind).  Every end-to-end metric of ``like`` lists the cell."""
    path = root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["workloads"].append(cell)
    if like is None:
        like = {"stream": "gw_small.live", "archive": "gw_small.archive"}[
            json.loads((root / "perfbench" / "traffic" /
                        f"{cell['traffic']}.json").read_text())["kind"]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(cell["name"])
    bench["per_layer"].extend(per_layer)
    path.write_text(json.dumps(bench))
    cells = root / "perfbench" / "cells"
    (cells / f"{cell['name']}.json").write_bytes(
        (cells / f"{like}.json").read_bytes())


TINY_STREAM = {
    "kind": "stream", "streams": 2, "sample_rate": 4096, "chunk": 25,
    "phases": "spread", "lead_s": 0.1, "grace_s": 30.0, "server": {},
    "strain": {"f_low": 30.0, "f_high": 200.0, "snr_range": [5.0, 15.0],
               "events_per_s": 2.0},
}
TINY_ARCHIVE = {
    "kind": "archive", "windows_per_call": 32, "pool": 2,
    "sample_rate": 4096,
    "strain": {"f_low": 30.0, "f_high": 200.0, "snr_range": [5.0, 15.0],
               "events_per_s": 2.0},
}


def run(root: Path, cell: str, seconds: float, trace: bool = False,
        seed: int = 2**31 + 11) -> dict:
    """One run of ``cell`` on the CPU, past the look for a chip."""
    from perfbench import harness

    return harness.run_cell(root, cell, seed, seconds, trace,
                            t_start=time.perf_counter(), require_tpu=False,
                            compile_cache=False)
