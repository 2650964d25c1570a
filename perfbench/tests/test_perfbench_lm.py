"""The ``lm_chat`` kind on the CPU at a small size: its closed loop counts
every generated token, compiles nothing in the window, runs the same
request list on every seed but for its offset, and is held by the cell's
limits against the plain reference, which its correctness control fails.

The small model keeps granite-4.0-h-micro's 40 layers in their pattern,
its d_state 128, head widths and multipliers, at d_model 256 and a
vocabulary of 4096, in bf16 as the configuration states; the engine has 4
slots.  The control's error grows with depth (at one period of 10 layers
it reads 0.009, under the cell's limit): the depth is kept so that the
limit set at the published size holds here too."""

import json

import numpy as np
import pytest

import _pb
from perfbench import harness

CELL = "granite4_h_micro.chat"


def small_config() -> dict:
    cfg = json.loads((_pb.ROOT / "perfbench" / "configs" /
                      "granite4_h_micro.json").read_text())
    cfg.update(name="granite_small", hidden_size=256, num_attention_heads=4,
               num_key_value_heads=1, intermediate_size=1024,
               shared_intermediate_size=1024, mamba_n_heads=8,
               vocab_size=4096, slots=4, slot_capacity=160)
    return cfg


def small_traffic() -> dict:
    traffic = json.loads(
        (_pb.ROOT / "perfbench" / "traffic" / "chat.json").read_text())
    traffic.update(lead_s=0.2, tracked=3, requests=[
        [24, 3], [60, 2], [7, 4], [100, 2], [33, 2], [12, 3]])
    return traffic


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = _pb.scratch_checkout(tmp_path_factory.mktemp("lm"))
    (root / "perfbench" / "configs" / "granite_small.json").write_text(
        json.dumps(small_config()))
    _pb.add_traffic(root, "chat_small", small_traffic())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "granite_small", "source": "test",
                             "file": "perfbench/configs/granite_small.json",
                             "reduced": [], "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    _pb.add_cell(root, {"name": "granite_small.chat", "config": "granite_small",
                        "traffic": "chat_small", "chips": 1, "why": "test"},
                 like=CELL)
    return root


def run_kind(root, seed, seconds=3.0):
    """The kind's own outcome, as the harness gets it."""
    import time

    cell = harness.load_cell(root, "granite_small.chat")
    counter = harness.CompileCounter()
    try:
        ctx = harness.Context(cell=cell, seed=seed, seconds=seconds,
                              trace=False, t_start=time.perf_counter(),
                              compiles=counter, trace_dir=None, chips=1)
        return cell, cell.kind.run(ctx)
    finally:
        counter.close()


@pytest.fixture(scope="module")
def outcome(checkout):
    return run_kind(checkout, 2**31 + 11)


def info(out, line):
    return next(i for i in out.info if i["line"] == line)


def test_counts_every_generated_token_and_compiles_nothing(outcome):
    cell, out = outcome
    tokens = info(out, "tokens")
    # every step emits one token for each live slot, an admission one
    assert tokens["tokens"] == tokens["slot_steps"] + tokens["admitted"]
    assert tokens["slot_steps"] == 4 * tokens["steps"]   # the slots kept full
    assert out.values["windows_per_s"] == pytest.approx(
        tokens["tokens"] / tokens["window_s"])
    assert out.attempted == tokens["admitted"] > 0
    assert out.missing == 0
    compiles = info(out, "compiles_in_window")
    assert compiles["compiles"] == 0 and compiles["programs_built"] == 0
    assert out.setup_s >= cell.traffic["lead_s"]


def test_program_is_correct_and_the_control_is_not(outcome):
    """The cell's limits hold the bf16 program and fail the control: the
    reference with every activation rounded to fp8."""
    cell, out = outcome
    assert len(out.windows) == cell.traffic["tracked"]
    for w in out.windows:
        assert len(w["ids"]) == cell.traffic["logprob_ids"]
    assert harness.is_correct(harness.checks(cell, out))
    control = cell.model.scores(out.params, out.windows, cell.config,
                                control=True)
    ctl = harness.Outcome(params=out.params, attempted=out.attempted,
                          missing=0, windows=out.windows, scores=control,
                          values={}, setup_s=0.0, memory_peak_bytes=None)
    assert not harness.is_correct(harness.checks(cell, ctl))


def test_the_request_list_is_fixed_but_for_its_offset():
    """Every seed walks the traffic's own list, cyclically, from an offset
    of its own; the seed draws only the ids."""
    from perfbench.kinds.lm_chat import RequestList

    traffic = json.loads(
        (_pb.ROOT / "perfbench" / "traffic" / "chat.json").read_text())
    pairs = [tuple(p) for p in traffic["requests"]]
    offsets, prompts = set(), []
    for seed in (3, 2**31 + 5, 2**40 + 9):
        reqs = RequestList(traffic, 4096, np.random.default_rng(seed))
        assert [reqs.lengths(i - reqs.offset) for i in range(len(pairs))] \
            == pairs
        made = [reqs.next() for _ in range(len(pairs) + 2)]
        assert [(len(r.prompt), r.max_new) for r in made] == \
            [pairs[(reqs.offset + i) % len(pairs)] for i in range(len(made))]
        offsets.add(reqs.offset)
        prompts.append(made[0].prompt[:8])
    assert len(offsets) == 3
    assert not np.array_equal(prompts[0], prompts[1])


def test_the_cell_runs_through_the_harness(checkout):
    res = _pb.run(checkout, "granite_small.chat", 0.5, trace=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    # the CPU has no device trace: only the program's counter reads
    assert set(res["metrics"]) == {"slot_fill.lm"}
    assert res["metrics"]["slot_fill.lm"]["value"] == 4.0
