"""The harness is driven by data: a cell, a traffic mix and a per-layer
metric are added as new files and entries in a scratch checkout, and run
without any file that was there being edited.  Also: no chip, no result."""

import json
import os
import subprocess
import sys

import pytest

import _pb

NEW_METRIC = '''
def read(run):
    calls = run.counts.get("push_many_calls")
    return None if not calls else run.counts["step_rows"] / calls
'''


@pytest.fixture
def checkout(tmp_path):
    root = _pb.scratch_checkout(tmp_path)
    before = {p: p.read_bytes() for p in (root / "perfbench").rglob("*")
              if p.is_file()}
    yield root
    after = {p: p.read_bytes() for p in before}
    assert after == before, "a file that was there was edited"


def test_a_new_cell_mix_and_metric_are_files_and_entries(checkout):
    _pb.add_traffic(checkout, "tiny_stream", _pb.TINY_STREAM)
    (checkout / "perfbench" / "metrics" / "rows_per_call.tiny.py").write_text(
        NEW_METRIC)
    _pb.add_cell(
        checkout,
        {"name": "gw_small.tiny", "config": "gw_small",
         "traffic": "tiny_stream", "chips": 1, "why": "test"},
        per_layer=[{"name": "rows_per_call.tiny", "unit": "streams",
                    "better": "higher", "source": "program_span",
                    "layer": "engine", "moves": "latency_p95_ms",
                    "workloads": ["gw_small.tiny"]}])
    res = _pb.run(checkout, "gw_small.tiny", 0.3)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"latency_p50_ms", "latency_p95_ms",
                                   "setup_s"}
    assert list(res)[-1] == "checks"

    traced = _pb.run(checkout, "gw_small.tiny", 0.3, trace=True)
    assert traced["correct"]
    # the CPU has no device trace: only the host-side readers find anything
    assert set(traced["metrics"]) == {"rows_per_call.tiny",
                                      "batch_fill.fleet",
                                      "push_many_ms.stream"}
    assert 1.0 <= traced["metrics"]["rows_per_call.tiny"]["value"] <= 2.0
    assert traced["device"]["window_s"] > 0


def test_a_new_archive_mix_on_an_existing_kind(checkout):
    _pb.add_traffic(checkout, "tiny_archive", _pb.TINY_ARCHIVE)
    _pb.add_cell(checkout, {"name": "gw_nominal.tiny_archive",
                            "config": "gw_nominal", "traffic": "tiny_archive",
                            "chips": 1, "why": "test"})
    res = _pb.run(checkout, "gw_nominal.tiny_archive", 0.2)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"windows_per_s", "setup_s"}


NEW_KIND = '''
"""Archive rescoring, reached under a kind name of its own."""
from perfbench.kinds.archive import run  # noqa: F401
'''


def test_a_new_kind_on_an_existing_configuration(checkout):
    (checkout / "perfbench" / "kinds" / "rescore.py").write_text(NEW_KIND)
    _pb.add_traffic(checkout, "tiny_rescore",
                    dict(_pb.TINY_ARCHIVE, kind="rescore"))
    _pb.add_cell(checkout, {"name": "gw_nominal.tiny_rescore",
                            "config": "gw_nominal", "traffic": "tiny_rescore",
                            "chips": 1, "why": "test"},
                 like="gw_nominal.archive")
    res = _pb.run(checkout, "gw_nominal.tiny_rescore", 0.2)
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == {"windows_missing", "score_rms_rel_err",
                                  "score_max_rel_err"}
    assert set(res["metrics"]) == {"windows_per_s", "setup_s"}


def _cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "gw_small.live",
         "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_without_a_chip_it_exits_nonzero_and_prints_no_result():
    proc = _cli(_pb.ROOT)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "TPU" in proc.stderr


def test_without_the_program_it_exits_nonzero_and_prints_no_result(tmp_path):
    (tmp_path / "BENCHMARK.json").write_bytes(
        (_pb.ROOT / "BENCHMARK.json").read_bytes())
    import shutil
    shutil.copytree(_pb.ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _cli(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_benchmark_json_keys_and_files():
    bench = json.loads((_pb.ROOT / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in bench["configs"]:
        cfg = json.loads((_pb.ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"] == []
        assert (_pb.ROOT / "perfbench" / "models" /
                f"{cfg['architecture']}.py").is_file()
    for w in bench["workloads"]:
        traffic = json.loads((_pb.ROOT / "perfbench" / "traffic" /
                              f"{w['traffic']}.json").read_text())
        assert (_pb.ROOT / "perfbench" / "kinds" /
                f"{traffic['kind']}.py").is_file()
        cell = json.loads((_pb.ROOT / "perfbench" / "cells" /
                           f"{w['name']}.json").read_text())
        assert cell["limits"], w["name"]
    for m in bench["per_layer"]:
        assert (_pb.ROOT / "perfbench" / "metrics" /
                f"{m['name']}.py").is_file()
