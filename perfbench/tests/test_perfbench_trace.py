"""The trace reduction on a synthetic trace, and on a recorded one."""

import pytest

import _pb  # noqa: F401
from perfbench import trace as tr


def synthetic():
    # window 0..100 ns; device busy 10-30 (step), 25-40 (fusion), 60-70
    # (wavefront); host spans: push_many 5-45, submit 50-65, score 80-95
    t = tr.Trace(
        device_ops={0: [("lstm_stack_step.1", 10, 30), ("fusion.3", 25, 40),
                        ("lstm_stack_wavefront", 60, 70),
                        ("lstm_stack_step.2", 95, 120),
                        ("lstm_stack_step_copy.1", 96, 97)]},
        spans=[("pb.window", 0, 100), ("pb.push_many", 5, 45),
               ("pb.submit", 50, 65), ("pb.score", 80, 95)])
    return t


def test_busy_is_the_union_of_device_ops_in_the_window():
    t = synthetic()
    lo, hi = t.window()
    assert (lo, hi) == (0, 100)
    # 10-40 (30) + 60-70 (10) + 95-100 (5) = 45 ns
    assert t.busy_s(lo, hi) == pytest.approx(45e-9)
    assert tr.merge([(3, 5), (1, 2), (2, 4)]) == [(1, 5)]
    assert tr.gaps([(10, 40), (60, 70)], 0, 100) == [(0, 10), (40, 60),
                                                      (70, 100)]


def test_overlap_of_two_interval_lists_matches_a_pointwise_count():
    import random

    rng = random.Random(5)
    for _ in range(50):
        xs = tr.merge((a, a + rng.randint(1, 9))
                      for a in rng.sample(range(200), 20))
        ys = tr.merge((a, a + rng.randint(1, 9))
                      for a in rng.sample(range(200), 20))
        inside = [any(a <= t < b for a, b in xs) and
                  any(a <= t < b for a, b in ys) for t in range(220)]
        assert tr.overlap(xs, ys) == sum(inside)


def test_kernel_time_by_name_is_clipped_to_the_window():
    t = synthetic()
    assert t.kernel_s("lstm_stack_step", 0, 100) == pytest.approx(25e-9)
    assert t.kernel_count("lstm_stack_step", 0, 100) == 2
    assert t.kernel_s("lstm_stack_wavefront", 0, 100) == pytest.approx(10e-9)
    assert t.top_ops(0, 100)[0] == ("lstm_stack_step.1", pytest.approx(20e-9))


def test_op_names_are_the_ops_own_not_their_operands():
    text = ("%fusion.2 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} "
            "%lstm_stack_step.1), kind=kLoop")
    assert tr.op_name(text) == "fusion.2"
    assert tr.op_name("%lstm_stack_step.1 = (f32[25,8,128]) custom-call()") \
        == "lstm_stack_step.1"
    assert not tr.is_kernel("fusion.2", "lstm_stack_step")
    assert tr.is_kernel("lstm_stack_step", "lstm_stack_step")
    assert not tr.is_kernel("lstm_stack_step_copy.1", "lstm_stack_step")


def test_idle_gaps_are_labelled_by_the_host_span_open_in_them():
    t = synthetic()
    idle = dict(t.idle_by_span(0, 100))
    # idle: 0-10, 40-60, 70-95
    assert idle["pb.push_many"] == pytest.approx(10e-9)   # 5-10, 40-45
    assert idle["pb.submit"] == pytest.approx(10e-9)      # 50-60
    assert idle["pb.score"] == pytest.approx(15e-9)       # 80-95
    assert idle["(no span)"] == pytest.approx(20e-9)      # 0-5, 45-50, 70-80
    assert "pb.window" not in idle


def test_a_recorded_trace_yields_the_window_and_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("pb.window"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("pb.score"):
                    f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    t = tr.Trace.from_dir(tmp_path)
    lo, hi = t.window()
    assert hi > lo
    assert [n for n, _, _ in t.spans].count("pb.score") == 2
    assert t.device_ops == {}  # no TPU plane on the CPU
    assert t.busy_s(lo, hi) == 0.0
