"""A run with the timed path broken underneath comes out not correct.

Each case skips the look for a chip, plants one fault in the program and
drives the rest of a run at a small size: a step that returns its state
unchanged, half of the batch left out, and an answer altered where it is
produced.  (No cell spans chips, so no exchange between chips can be left
out.)"""

import numpy as np
import pytest

import _pb

from repro.kernels.lstm_stack import ops as stack_ops
from repro.kernels.lstm_stack import step as step_ops
from repro.serve.engine import StreamingAnomalyEngine


def state_unchanged(monkeypatch):
    """Both kernels hand back the state they were given, and a hidden
    sequence that never left it."""
    real_step, real_stack = step_ops.lstm_stack_step_op, stack_ops.lstm_stack_op

    def step(xs, stacked, h, c, **kw):
        hs, _, _ = real_step(xs, stacked, h, c, **kw)
        return hs, h, c

    def stack(xs, stacked, h, c, **kw):
        hs, _, _ = real_stack(xs, stacked, h, c, **kw)
        return hs * 0, h, c

    monkeypatch.setattr(step_ops, "lstm_stack_step_op", step)
    monkeypatch.setattr(stack_ops, "lstm_stack_op", stack)


def half_left_out(monkeypatch):
    """Only the first half of each batch is computed; the other half
    gets nothing (streams) or the first half's answers (archive)."""
    real_push, real_score = (StreamingAnomalyEngine.push_many,
                             StreamingAnomalyEngine.score)

    def push_many(self, ids, chunks):
        ids = list(ids)
        keep = (len(ids) + 1) // 2
        out = real_push(self, ids[:keep], chunks[:keep])
        out.update({sid: [] for sid in ids[keep:]})
        return out

    def score(self, windows):
        half = real_score(self, windows[: (len(windows) + 1) // 2])
        return np.concatenate([half, half])[: len(windows)]

    monkeypatch.setattr(StreamingAnomalyEngine, "push_many", push_many)
    monkeypatch.setattr(StreamingAnomalyEngine, "score", score)


def answer_altered(monkeypatch):
    """One score in each group the engine produces is off by 1e-4."""
    real_finish, real_score = (StreamingAnomalyEngine._finish_streams,
                               StreamingAnomalyEngine.score)

    def finish(self, slots):
        out = real_finish(self, slots)
        out[0] = out[0] * np.float32(1 + 1e-4)
        return out

    def score(self, windows):
        out = np.array(real_score(self, windows))
        out[0] *= np.float32(1 + 1e-4)
        return out

    monkeypatch.setattr(StreamingAnomalyEngine, "_finish_streams", finish)
    monkeypatch.setattr(StreamingAnomalyEngine, "score", score)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = _pb.scratch_checkout(tmp_path_factory.mktemp("faults"))
    _pb.add_traffic(root, "tiny_stream", _pb.TINY_STREAM)
    _pb.add_traffic(root, "tiny_archive", _pb.TINY_ARCHIVE)
    _pb.add_cell(root, {"name": "gw_small.tiny", "config": "gw_small",
                        "traffic": "tiny_stream", "chips": 1, "why": "test"})
    _pb.add_cell(root, {"name": "gw_nominal.tiny_archive",
                        "config": "gw_nominal", "traffic": "tiny_archive",
                        "chips": 1, "why": "test"})
    return root


@pytest.mark.parametrize("cell,seconds", [("gw_small.tiny", 0.3),
                                          ("gw_nominal.tiny_archive", 0.2)])
def test_sound_run_is_correct(checkout, cell, seconds):
    res = _pb.run(checkout, cell, seconds)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault", [state_unchanged, half_left_out,
                                   answer_altered])
@pytest.mark.parametrize("cell,seconds", [("gw_small.tiny", 0.3),
                                          ("gw_nominal.tiny_archive", 0.2)])
def test_fault_makes_the_run_not_correct(checkout, monkeypatch, cell,
                                         seconds, fault):
    fault(monkeypatch)
    res = _pb.run(checkout, cell, seconds)
    assert not res["correct"], res["checks"]
