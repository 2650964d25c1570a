"""The general harness: finds a cell's configuration, traffic, runner and
metric readers by name, runs the cell once, checks its answers against the
plain reference, and assembles the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* a configuration: the ``file`` of its ``configs`` entry, whose
  ``architecture`` names its plain reference, ``perfbench/models/<arch>.py``;
* a traffic mix: ``perfbench/traffic/<traffic>.json``, whose ``kind`` names
  the general generator and runner that reads it, ``perfbench/kinds/<kind>.py``;
* a cell's limits for ``correct``: ``perfbench/cells/<workload name>.json``;
* a per-layer metric: ``perfbench/metrics/<metric name>.py`` with a
  ``read(run)`` that returns the number, or ``None`` where it finds nothing
  to read.

So a later change adds a cell, a mix or a metric by adding files and
entries, without editing any file that is here.
"""

from __future__ import annotations

import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType

import numpy as np

PKG = "perfbench"


class NoChipError(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    def module(self, sub: str, name: str) -> ModuleType:
        return load_module(self.root / PKG / sub / f"{name}.py")

    @property
    def kind(self) -> ModuleType:
        return self.module("kinds", self.traffic["kind"])

    @property
    def model(self) -> ModuleType:
        return self.module("models", self.config["architecture"])

    @property
    def limits(self) -> dict:
        """The numbers compared for ``correct`` and their limits, from the
        cell's own file."""
        path = self.root / PKG / "cells" / f"{self.name}.json"
        return json.loads(path.read_text())["limits"]


def load_module(path: Path) -> ModuleType:
    """Import one file by path, under a name of its own."""
    name = "pb_" + "_".join(path.with_suffix("").parts[-2:]).replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / PKG / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        root=root, name=name, chips=int(w["chips"]),
        config_name=w["config"], config=config,
        traffic_name=w["traffic"], traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


# ---------------------------------------------------------------------------
# what a runner hands back
# ---------------------------------------------------------------------------

@dataclass
class Outcome:
    """One run of a cell's runner.

    ``windows``/``scores``: every answer due in the window that came, and
    the inputs they answer (``index``), for the comparison with the
    reference;
    ``missing``: answers due that never came.  ``values``: end-to-end
    metrics the runner measured (the harness adds ``setup_s``).
    ``layer``: what the per-layer readers read (``LayerRun``)."""

    params: dict
    attempted: int
    missing: int
    windows: np.ndarray
    scores: np.ndarray
    values: dict
    setup_s: float
    memory_peak_bytes: int | None
    info: list[dict] = field(default_factory=list)
    layer: "LayerRun | None" = None
    #: ``scores[i]`` answers ``windows[index[i]]`` (default: ``windows[i]``)
    index: np.ndarray | None = None


@dataclass
class LayerRun:
    """What a per-layer reader reads: the reduced trace and its window (in
    trace nanoseconds), the runner's counts over the same window, the
    cell, and the chip's peaks."""

    cell: Cell
    trace: object
    lo: float
    hi: float
    counts: dict
    device_kind: str
    chips: int

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def peak(self) -> dict:
        from perfbench.peaks import peaks

        return peaks(self.device_kind)


# ---------------------------------------------------------------------------
# running one cell
# ---------------------------------------------------------------------------

def enable_compile_cache(root: Path) -> Path:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, holding every program however small or fast to compile."""
    import jax

    path = root / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChipError(
            f"cell needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform!r} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int | None:
    """Peak bytes in use on the fullest chip, where the backend says."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts backend compiles (persistent-cache loads included) and
    traces, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.traces = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "traces": self.traces,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._dur)
        jax.monitoring.unregister_event_listener(self._event)


@dataclass
class Context:
    """What a runner is given."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float           # perf_counter at process start
    compiles: CompileCounter
    trace_dir: Path | None
    chips: int


def checks(cell: Cell, out: Outcome) -> dict:
    """Every number compared, each with its limit: the answers that never
    came, and the scores against the plain reference by the numbers that
    the cell's ``limits`` list (only numbers that the correctness control
    was shown to fail are listed)."""
    limits = cell.limits
    res = {"windows_missing": {"value": out.missing, "limit": 0}}
    if len(out.scores):
        want = cell.model.scores(out.params, out.windows, cell.config)
        if out.index is not None:
            want = want[out.index]
        got = np.asarray(out.scores, np.float64)
        rel = np.abs(got - want) / np.abs(want)
        values = {"score_rms_rel_err": float(np.sqrt(np.mean(rel**2))),
                  "score_max_rel_err": float(np.max(rel))}
        for name, limit in limits.items():
            res[name] = {"value": values[name], "limit": limit}
    return res


def is_correct(res: dict) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in res.values())


def run_cell(root: Path, name: str, seed: int, seconds: float, trace: bool,
             *, t_start: float, require_tpu: bool = True,
             compile_cache: bool = True) -> dict:
    """Run one cell once; returns the result line's object.  Informational
    lines go to stdout on the way, the compared numbers to stderr last."""
    cell = load_cell(root, name)
    device = device_info(cell.chips, require_tpu)
    if compile_cache:
        enable_compile_cache(root)
    counter = CompileCounter()
    trace_dir = Path(tempfile.mkdtemp(prefix="pb_trace_")) if trace else None
    try:
        ctx = Context(cell=cell, seed=seed, seconds=seconds, trace=trace,
                      t_start=t_start, compiles=counter, trace_dir=trace_dir,
                      chips=cell.chips)
        out = cell.kind.run(ctx)
        for line in out.info:
            print(json.dumps(line), flush=True)
        res = checks(cell, out)
        metrics = {}
        if trace:
            device["busy_s"] = out.layer.trace.busy_s(out.layer.lo,
                                                      out.layer.hi)
            device["window_s"] = out.layer.window_s
            for m in cell.per_layer:
                value = load_module(
                    root / PKG / "metrics" / f"{m['name']}.py").read(out.layer)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            out.values["setup_s"] = out.setup_s
            for m in cell.end_to_end:
                if m["name"] in out.values:
                    metrics[m["name"]] = {"value": out.values[m["name"]],
                                          "unit": m["unit"]}
    finally:
        counter.close()
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    device["memory_peak_bytes"] = out.memory_peak_bytes
    result = {
        "correct": is_correct(res),
        "attempted": out.attempted,
        "failed": out.missing,
        "metrics": metrics,
        "device": device,
    }
    if trace:
        tr, lo, hi = out.layer.trace, out.layer.lo, out.layer.hi
        result["breakdown"] = {
            "device_ops": [list(kv) for kv in tr.top_ops(lo, hi)],
            "idle_gaps": [list(kv) for kv in tr.idle_by_span(lo, hi)],
        }
    result["checks"] = res
    for k, c in res.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return result


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(
        prog="perfbench/run.py",
        description="Run one benchmark cell once on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parent.parent
    src = root / "src"
    if not (src / "repro").is_dir():
        print(f"perfbench: the program under test is not at {src}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    try:
        result = run_cell(root, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start)
    except NoChipError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0
