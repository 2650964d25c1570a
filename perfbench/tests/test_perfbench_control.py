"""The correctness control comes out not correct, the program correct.

The control is the plain reference with every dot in one bf16 pass (what
``Precision.DEFAULT`` computes for fp32 operands on a TPU), put in the
program's place.  Every cell of ``BENCHMARK.json`` is held to its own
limits, here at a size a test run holds; the readings the limits were set
from were taken on the chip at the cells' own sizes (``PERF.md``)."""

import json

import numpy as np
import pytest

import _pb
from perfbench import harness, sut
from perfbench.gwdata import StrainSource
from perfbench.models import lstm_autoencoder as ae

CELLS = [w["name"] for w in
         json.loads((_pb.ROOT / "BENCHMARK.json").read_text())["workloads"]]


def setup(name, seed, windows=256):
    cell = harness.load_cell(_pb.ROOT, name)
    src = StrainSource(4096, 30.0, 200.0, (5.0, 15.0), 2.0)
    rng = np.random.default_rng(seed)
    windows = src.strain(rng, 1, windows * 100).reshape(windows, 100, 1)
    return cell, ae.init_params(seed, cell.config), windows


def compare(cell, params, windows, got):
    out = harness.Outcome(params=params, attempted=len(got),
                          missing=0, windows=windows, scores=got, values={},
                          setup_s=0.0, memory_peak_bytes=None)
    return harness.checks(cell, out)


@pytest.mark.parametrize("seed", [3, 2**31 + 5, 2**40 + 9])
@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name, seed):
    cell, params, windows = setup(name, seed, windows=1024)
    ctl = ae.scores(params, windows, cell.config, control=True)
    res = compare(cell, params, windows, ctl)
    assert not harness.is_correct(res), res


@pytest.mark.parametrize("name", CELLS)
def test_program_is_correct(name):
    cell, params, windows = setup(name, 17)
    got = sut.build_engine(params, cell.config).score(windows)
    res = compare(cell, params, windows, got)
    assert harness.is_correct(res), res


def test_control_dot_is_one_bf16_pass():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((16, 32)).astype(np.float32)
    b = rng.standard_normal((32, 8)).astype(np.float32)
    exact = a.astype(np.float64) @ b.astype(np.float64)
    err1 = np.abs(np.asarray(ae.control_dot(a, b)) - exact).max()
    err6 = np.abs(np.asarray(ae.highest_dot(a, b)) - exact).max()
    # operands rounded to 8 significant bits: ~2^-9 relative, far above fp32
    assert 1e-4 < err1 / np.abs(exact).max() < 1e-2
    assert err6 < err1 / 1000
    rounded = [np.asarray(ae._to_bf16(x)) for x in (a, b)]
    want = rounded[0].astype(np.float64) @ rounded[1].astype(np.float64)
    assert np.abs(np.asarray(ae.control_dot(a, b)) - want).max() < err1 / 100
