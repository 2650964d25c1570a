"""Continuous-batching stream server: scheduler policy, backpressure,
lifecycle, metrics, and the determinism contract.

The contract under test (CPU interpret): the deadline coalescer only ever
(a) preserves per-stream chunk FIFO order and (b) batches *distinct*
streams of one chunk length into a single ``push_many`` call — so **any**
arrival order / batch-fill sequence it produces must score bit-equal to
sequential per-stream pushes, including mid-run joins and drops
(property-tested through the ``_hypothesis_compat`` shim).

Scheduling itself is tested deterministically in manual-tick mode with an
injectable fake clock (no sleeps); one threaded smoke covers the
production drive mode end to end.
"""

import threading
import time

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # hermetic container: deterministic fixed-example sweep
    from _hypothesis_compat import given, settings, st

from repro.core.autoencoder import AutoencoderConfig, init_autoencoder
from repro.kernels.lstm_scan.ops import SUBLANES
from repro.serve.engine import StreamingAnomalyEngine
from repro.latency import (
    SUB_BINS,
    ArrivalRateEstimator,
    LatencyHistogram,
)
from repro.serve.server import (
    AdaptiveConfig,
    QueueFullError,
    ServerConfig,
    StreamServer,
    _pad_width,
)


def _gw_cfg(**kw):
    return AutoencoderConfig(
        hidden=(9, 9), latent_boundary=1, timesteps=12, **kw
    )


_CFG = _gw_cfg()
_PARAMS = init_autoencoder(jax.random.PRNGKey(7), _CFG)


def _engine(**kw):
    return StreamingAnomalyEngine(_PARAMS, _CFG, batch=1, **kw)


def _sequential_scores(chunk_lists: dict) -> dict:
    """Ground truth: each stream replayed solo through engine.push."""
    seq = _engine()
    out = {}
    for sid, chunks in chunk_lists.items():
        seq.reset()
        scores = []
        for c in chunks:
            scores += seq.push(c[None])
        out[sid] = scores
    return out


def _assert_scores_equal(got: dict, want: dict):
    assert set(got) == set(want), (sorted(got), sorted(want))
    for sid in want:
        assert len(got[sid]) == len(want[sid]), sid
        for g, w in zip(got[sid], want[sid]):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


class FakeClock:
    """Injectable monotonic clock (seconds), advanced by hand."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance_us(self, us: float):
        self.t += us * 1e-6


class TestLatencyHistogram:
    def test_percentiles_bound_samples(self):
        h = LatencyHistogram()
        samples = [10, 50, 120, 121, 130, 5000, 80000]
        h.record_many(samples)
        assert h.count == len(samples)
        assert h.min_us == 10 and h.max_us == 80000
        # geometric bins: value at q is within one bin (~9%) above truth
        assert 120 <= h.percentile(50) <= 121 * 2 ** (1 / 8)
        assert h.percentile(100) == 80000
        assert h.percentile(0) == 10

    def test_single_sample_exact(self):
        h = LatencyHistogram()
        h.record(137.0)
        assert h.percentile(50) == 137.0 == h.percentile(99)

    @pytest.mark.parametrize("q", [10, 50, 90, 99])
    def test_quantiles_lie_within_one_bin_above_the_truth(self, q):
        h = LatencyHistogram()
        samples = np.geomspace(10, 10000, 301)
        h.record_many(samples)
        truth = np.percentile(samples, q, method="inverted_cdf")
        assert truth <= h.percentile(q) <= truth * 2 ** (1 / SUB_BINS)

    def test_empty(self):
        h = LatencyHistogram()
        assert h.count == 0 and h.percentile(99) == 0.0
        assert h.summary("x")["x.p50_us"] == 0.0

    def test_merge_adds(self):
        a, b = LatencyHistogram(), LatencyHistogram()
        a.record_many([100, 200])
        b.record_many([400, 800])
        a.merge(b)
        assert a.count == 4 and a.max_us == 800 and a.min_us == 100

    def test_summary_keys(self):
        h = LatencyHistogram()
        h.record(42.0)
        s = h.summary("latency")
        for k in ("count", "mean_us", "p50_us", "p90_us", "p99_us", "max_us"):
            assert f"latency.{k}" in s

    def test_bad_quantile_raises(self):
        with pytest.raises(ValueError, match="percentile"):
            LatencyHistogram().percentile(101)


class TestArrivalRateEstimator:
    """Satellite: the EWMA inter-arrival estimator under bursty, Poisson,
    and silent-then-burst traces (injectable clock = plain timestamps)."""

    def test_first_chunk_no_estimate_no_div_by_zero(self):
        est = ArrivalRateEstimator()
        est.observe(1.0)
        assert est.gap_us is None and est.rate_hz is None
        assert est.observed == 1

    def test_steady_trace_converges_to_gap(self):
        est = ArrivalRateEstimator(alpha=0.25)
        for i in range(50):
            est.observe(i * 100e-6)  # 100us apart
        assert est.gap_us == pytest.approx(100.0, rel=1e-6)
        assert est.rate_hz == pytest.approx(10_000.0, rel=1e-6)

    def test_simultaneous_arrivals_zero_gap(self):
        est = ArrivalRateEstimator(alpha=1.0)
        est.observe(0.0)
        est.observe(0.0)  # same instant (sub-clock-resolution burst)
        assert est.gap_us == 0.0
        assert est.rate_hz == float("inf")

    def test_poisson_trace_tracks_mean(self):
        rng = np.random.RandomState(0)
        est = ArrivalRateEstimator(alpha=0.05)
        t = 0.0
        for gap in rng.exponential(200e-6, size=2000):
            t += gap
            est.observe(t)
        assert 100.0 < est.gap_us < 400.0  # smoothed toward the 200us mean

    def test_bursty_trace_weights_recent(self):
        est = ArrivalRateEstimator(alpha=0.5)
        t = 0.0
        for gap_us in [500.0] * 10 + [10.0] * 10:
            t += gap_us * 1e-6
            est.observe(t)
        assert est.gap_us < 50.0  # the recent fast burst dominates

    def test_silent_then_burst_resets(self):
        est = ArrivalRateEstimator(alpha=0.5, idle_reset_factor=50.0)
        t = 0.0
        for _ in range(5):
            t += 100e-6
            est.observe(t)
        assert est.gap_us == pytest.approx(100.0)
        t += 10.0  # 10s of silence: >> 50x the 100us estimate
        est.observe(t)
        # the idle gap neither becomes a sample nor leaves a stale
        # estimate behind
        assert est.gap_us is None and est.rate_hz is None
        t += 20e-6
        est.observe(t)  # the next in-burst gap re-seeds
        assert est.gap_us == pytest.approx(20.0)

    def test_long_idle_after_single_chunk(self):
        est = ArrivalRateEstimator()
        est.observe(0.0)
        est.observe(100.0)  # 100s later: seeds a huge gap estimate...
        est.observe(100.0 + 50e-6)
        # ...which the next in-burst arrival re-seeds away at once
        # (EWMA-decaying a 1e8us artifact would take hundreds of samples)
        assert est.gap_us == pytest.approx(50.0)
        est2 = ArrivalRateEstimator()
        est2.observe(0.0)
        est2.observe(0.0)
        assert est2.rate_hz == float("inf")  # 0-gap guarded

    def test_bad_params_raise(self):
        with pytest.raises(ValueError):
            ArrivalRateEstimator(alpha=0.0)
        with pytest.raises(ValueError):
            ArrivalRateEstimator(alpha=1.5)
        with pytest.raises(ValueError):
            ArrivalRateEstimator(idle_reset_factor=1.0)


class TestServerConfig:
    def test_max_coalesce_honored_as_requested(self):
        """The requested value is the gather cap verbatim (max_coalesce=1
        really is no coalescing); program shapes are the pad ladder's
        concern, not the cap's."""
        assert ServerConfig(max_coalesce=1).max_coalesce == 1
        assert ServerConfig(max_coalesce=12).max_coalesce == 12
        assert ServerConfig(max_coalesce=SUBLANES).max_coalesce == SUBLANES

    def test_pad_width_ladder_is_bounded(self):
        # powers of two below one sublane tile, sublane multiples above
        assert [_pad_width(n) for n in (1, 2, 3, 5, 8)] == [1, 2, 4, 8, 8]
        assert _pad_width(SUBLANES + 1) == 2 * SUBLANES
        assert _pad_width(3 * SUBLANES) == 3 * SUBLANES
        # the ladder never pads by a full tile or more
        for n in range(1, 65):
            assert n <= _pad_width(n) < n + SUBLANES

    @pytest.mark.parametrize(
        "kw",
        [
            dict(max_coalesce=0),
            dict(deadline_us=0),
            dict(queue_capacity=0),
            dict(overflow="spill"),
            dict(adaptive="yes"),
        ],
    )
    def test_invalid_config_raises(self, kw):
        with pytest.raises(ValueError):
            ServerConfig(**kw)

    @pytest.mark.parametrize(
        "kw",
        [
            dict(max_deadline_us=0),
            dict(min_deadline_us=1000.0),  # > default max_deadline_us
            dict(ewma_alpha=0.0),
            dict(ewma_alpha=1.5),
            dict(idle_reset_factor=1.0),
            dict(fill_headroom=0.0),
            dict(min_coalesce=0),
        ],
    )
    def test_invalid_adaptive_config_raises(self, kw):
        with pytest.raises(ValueError):
            AdaptiveConfig(**kw)

    def test_adaptive_true_builds_defaults(self):
        cfg = ServerConfig(adaptive=True)
        assert isinstance(cfg.adaptive, AdaptiveConfig)
        assert ServerConfig(adaptive=False).adaptive is None
        assert ServerConfig().adaptive is None

    def test_engine_must_be_batch_one(self):
        multi = StreamingAnomalyEngine(_PARAMS, _CFG, batch=2)
        with pytest.raises(ValueError, match="batch=1"):
            StreamServer(multi)


class TestManualScheduling:
    def test_drain_bit_equal_sequential_ragged(self):
        """Ragged per-stream chunking through the queue scores exactly like
        solo replays (the server acceptance contract, small edition)."""
        eng = _engine()
        srv = StreamServer(eng, ServerConfig(deadline_us=1e9))
        T = eng.window
        x = np.random.RandomState(3).randn(3, 2 * T, 1).astype(np.float32)
        bounds = (0, 5, 11, 16, 2 * T)
        chunk_lists = {
            f"s{i}": [x[i, a:b] for a, b in zip(bounds, bounds[1:])]
            for i in range(3)
        }
        for j in range(len(bounds) - 1):
            for sid in chunk_lists:
                srv.submit(sid, chunk_lists[sid][j])
        srv.drain()
        _assert_scores_equal(srv.pop_scores(), _sequential_scores(chunk_lists))
        st_ = srv.stats
        assert st_.processed == st_.submitted == 12
        assert st_.windows_scored == 6

    def test_tick_policy_waits_then_deadline_flushes(self):
        clock = FakeClock()
        eng = _engine()
        srv = StreamServer(
            eng, ServerConfig(deadline_us=200.0), clock=clock
        )
        x = np.zeros((4, 1), np.float32)
        # "c" joins the engine but has no pending chunk afterward — with a
        # joined stream still missing, waiting *can* improve fill, so the
        # all-joined-pending fast path must not preempt the deadline
        srv.submit("c", x)
        srv.drain()
        srv.submit("a", x)
        srv.submit("b", x)
        # young + under-filled: the policy holds the batch back
        assert srv.tick() == 0
        assert srv.pending == 2
        clock.advance_us(199.0)
        assert srv.tick() == 0
        # oldest chunk's age hits the deadline: flush whatever is pending
        clock.advance_us(2.0)
        assert srv.tick() == 2
        assert srv.stats.deadline_flushes == 1
        assert srv.stats.batch_fill == {1: 1, 2: 1}

    def test_all_joined_pending_flushes_immediately(self):
        """The 1-stream fast path: when every joined stream already has a
        pending chunk, waiting out the deadline cannot improve batch fill
        — flush at once, at any deadline."""
        clock = FakeClock()
        eng = _engine()
        srv = StreamServer(
            eng, ServerConfig(deadline_us=1e9), clock=clock
        )
        x = np.zeros((4, 1), np.float32)
        srv.submit("a", x)
        assert srv.tick() == 1  # no clock advance, 1e9us deadline
        assert srv.stats.fastpath_flushes == 1
        assert eng.stream_ids == ("a",)
        # now "a" is joined: a chunk from "b" alone must NOT fast-path
        # (waiting could still pick up a's next chunk)...
        srv.submit("b", x)
        assert srv.tick() == 0
        # ...until "a" submits too, making every joined stream pending
        srv.submit("a", x)
        assert srv.tick() == 2
        assert srv.stats.fastpath_flushes == 2

    def test_fastpath_holds_per_bucket_fifo(self):
        """The fast path flushes the *oldest* bucket; per-stream FIFO and
        per-bucket gathering still hold (satellite: must hold per
        chunk-length bucket)."""
        clock = FakeClock()
        eng = _engine()
        srv = StreamServer(eng, ServerConfig(deadline_us=1e9), clock=clock)
        T = eng.window
        x = np.random.RandomState(13).randn(2, T, 1).astype(np.float32)
        srv.submit("a", x[0, :5])
        clock.advance_us(10.0)
        srv.submit("b", x[1, :6])     # different bucket, younger
        # both joined streams pending -> fast path; only the oldest
        # bucket (t=5) flushes this tick
        assert srv.tick() == 1
        assert srv.stats.fastpath_flushes == 1
        # "a" is now resident but silent: b's bucket must wait (a's next
        # chunk could still arrive — and does, re-arming the fast path,
        # which then flushes the *older* t=6 bucket before the fresh tails)
        assert srv.tick() == 0
        clock.advance_us(5.0)
        srv.submit("a", x[0, 5:T])
        clock.advance_us(5.0)
        srv.submit("b", x[1, 6:T])
        assert srv.tick() == 1        # b's t=6 chunk (oldest bucket)
        assert srv.tick() == 1        # a's tail (older than b's tail)
        # only b's tail is left; a is resident-silent again -> hold
        assert srv.tick() == 0
        assert srv.stats.fastpath_flushes == 3
        srv.drain()
        assert srv.pending == 0
        want = _sequential_scores({
            "a": [x[0, :5], x[0, 5:T]], "b": [x[1, :6], x[1, 6:T]],
        })
        _assert_scores_equal(srv.pop_scores(), want)

    def test_nonhead_bucket_cannot_overstay_deadline(self):
        """Regression (two-bucket starvation): a chunk whose length
        buckets it behind a repeatedly-flushing head bucket still flushes
        within ITS deadline — oldest-pending age is tracked per bucket,
        not just at queue[0]."""
        clock = FakeClock()
        eng = _engine()
        srv = StreamServer(
            eng,
            ServerConfig(max_coalesce=2, deadline_us=200.0),
            clock=clock,
        )
        T = eng.window
        x = np.random.RandomState(14).randn(4, T, 1).astype(np.float32)
        # j joins the engine and goes silent: fast path stays off
        srv.submit("j", x[3, :2])
        srv.drain()
        # t=0: stream b's t=6 chunk enqueues (head of the queue, even)
        srv.submit("b", x[2, :6])
        # t=5 traffic from a and d keeps filling and flushing its bucket
        for i, t_now in enumerate((50.0, 130.0)):
            clock.t = t_now * 1e-6
            srv.submit(f"a{i}", x[0, :5])
            srv.submit(f"d{i}", x[1, :5])
            # the t=5 bucket is full (2 distinct streams == max_coalesce):
            # it flushes, b's t=6 chunk stays behind
            assert srv.tick() == 2
            assert srv.stats.full_flushes == i + 1
        assert srv.pending == 1  # b still queued
        # ... but b's own age (205us > 200us deadline) must now win over
        # any fresh head-bucket traffic
        clock.t = 205e-6
        srv.submit("a2", x[0, :5])  # young t=5 chunk at the head bucket
        assert srv.tick() == 1      # flushes the t=6 bucket, not t=5
        assert srv.stats.deadline_flushes == 1
        assert srv.stats.latency.max_us <= 206.0

    def test_full_batch_flushes_without_deadline(self):
        clock = FakeClock()
        eng = _engine()
        srv = StreamServer(
            eng, ServerConfig(max_coalesce=SUBLANES, deadline_us=1e9),
            clock=clock,
        )
        x = np.zeros((2, 1), np.float32)
        for i in range(SUBLANES):
            srv.submit(f"s{i}", x)
        assert srv.tick() == SUBLANES  # no clock advance needed
        assert srv.stats.full_flushes == 1
        assert srv.stats.deadline_flushes == 0

    def test_chunk_length_bucketing_preserves_fifo(self):
        """Mixed chunk lengths split into per-length ticks; a stream's
        later chunk never overtakes its earlier one."""
        eng = _engine()
        srv = StreamServer(eng, ServerConfig(deadline_us=1e9))
        T = eng.window
        x = np.random.RandomState(4).randn(2, T, 1).astype(np.float32)
        srv.submit("a", x[0, :5])     # head: t=5 bucket
        srv.submit("b", x[1, :6])     # t=6: stays queued this tick
        srv.submit("a", x[0, 5:T])    # same stream: must wait for a's head
        assert srv.tick(force=True) == 1          # only a's first chunk
        assert srv.pending == 2
        assert srv.tick(force=True) == 1          # b's t=6 chunk
        assert srv.tick(force=True) == 1          # a's tail
        got = srv.pop_scores()
        want = _sequential_scores({
            "a": [x[0, :5], x[0, 5:T]], "b": [x[1, :6]],
        })
        # b completes no window (6 < T): only presence and a's score match
        _assert_scores_equal(got, {k: v for k, v in want.items() if v})

    def test_same_stream_twice_in_queue_splits_ticks(self):
        eng = _engine()
        srv = StreamServer(eng, ServerConfig(deadline_us=1e9))
        T = eng.window
        x = np.random.RandomState(5).randn(1, 2 * T, 1).astype(np.float32)
        srv.submit("a", x[0, :T])
        srv.submit("a", x[0, T:])
        assert srv.tick(force=True) == 1
        assert srv.tick(force=True) == 1
        got = srv.pop_scores()
        want = _sequential_scores({"a": [x[0, :T], x[0, T:]]})
        _assert_scores_equal(got, want)

    def test_pad_streams_never_leak(self):
        eng = _engine()
        srv = StreamServer(
            eng, ServerConfig(deadline_us=1e9, pad_to_sublanes=True)
        )
        srv.submit("a", np.zeros((3, 1), np.float32))
        srv.drain()
        assert eng.stream_ids == ("a",)  # pads dropped after the tick

    def test_close_stream_discards_pending_and_slot(self):
        eng = _engine()
        srv = StreamServer(eng, ServerConfig(deadline_us=1e9))
        T = eng.window
        x = np.random.RandomState(6).randn(1, T, 1).astype(np.float32)
        srv.submit("a", x[0, :5])
        srv.drain()                       # "a" now mid-window in the engine
        srv.submit("a", x[0, 5:8])
        srv.submit("a", x[0, 8:])
        assert srv.close_stream("a") == 2
        assert srv.stats.cancelled == 2
        assert srv.pending == 0
        assert eng.stream_ids == ()
        # rejoin: fresh state, scores like a brand-new stream
        srv.submit("a", x[0, :T])
        srv.drain()
        _assert_scores_equal(srv.pop_scores(),
                             _sequential_scores({"a": [x[0, :T]]}))

    def test_submit_shape_validation(self):
        srv = StreamServer(_engine())
        with pytest.raises(ValueError, match="chunk must be"):
            srv.submit("a", np.zeros((0, 1), np.float32))
        with pytest.raises(ValueError, match="chunk must be"):
            srv.submit("a", np.zeros((4, 2), np.float32))
        srv.submit("a", np.zeros((1, 4, 1), np.float32))  # push shape ok
        assert srv.pending == 1

    def test_submit_errors_name_the_stream_and_shape(self):
        """Satellite fix: a bad chunk fails in the producer's own submit
        call with the stream and offending shape/dtype named — not as an
        opaque jit error from inside a coalesced batch."""
        srv = StreamServer(_engine())
        with pytest.raises(ValueError, match=r"stream 'det-7'.*\(3, 9\)"):
            srv.submit("det-7", np.zeros((3, 9), np.float32))
        with pytest.raises(ValueError, match=r"stream 'det-7'.*complex64"):
            srv.submit("det-7", np.zeros((4, 1), np.complex64))
        with pytest.raises(ValueError, match=r"stream 'det-7'.*<U1"):
            srv.submit("det-7", np.array([["x"]]))
        # integer chunks are fine (upcast by the engine like any numeric)
        srv.submit("det-7", np.zeros((4, 1), np.int32))
        assert srv.pending == 1

    def test_throwing_callback_counted_not_fatal_manual(self):
        """Satellite fix: a raising on_score callback is counted + logged;
        the tick completes and later windows still deliver."""
        boom = {"n": 0}

        def cb(sid, score):
            boom["n"] += 1
            raise RuntimeError("user bug")

        eng = _engine()
        srv = StreamServer(eng, on_score=cb)
        T = eng.window
        x = np.random.RandomState(3).randn(1, 2 * T, 1).astype(np.float32)
        srv.submit("a", x[0, :T])
        srv.drain()  # callback raises inside this tick
        assert boom["n"] == 1
        assert srv.stats.callback_errors == 1
        srv.submit("a", x[0, T:])
        srv.drain()
        assert boom["n"] == 2  # still delivering after the raise
        assert srv.stats.callback_errors == 2
        assert srv.stats.windows_scored == 2

    def test_latency_histogram_records_per_chunk(self):
        clock = FakeClock()
        eng = _engine()
        srv = StreamServer(eng, ServerConfig(deadline_us=50.0), clock=clock)
        srv.submit("a", np.zeros((2, 1), np.float32))
        clock.advance_us(100.0)
        srv.submit("b", np.zeros((2, 1), np.float32))
        srv.tick()  # deadline expired for "a"
        assert srv.stats.latency.count == 2
        # "a" waited 100us (fake clock froze during the tick); "b" ~0
        assert srv.stats.latency.max_us >= 99.0


class TestAdaptiveScheduling:
    """The self-tuning policy: deadline from the per-bucket arrival-rate
    EWMA (capped by max_deadline_us), effective width narrowed/widened
    between ticks, and bit-equality preserved throughout."""

    def _srv(self, clock, **adaptive_kw):
        cfg = ServerConfig(
            max_coalesce=SUBLANES,
            adaptive=AdaptiveConfig(**adaptive_kw),
        )
        return StreamServer(_engine(), cfg, clock=clock)

    def _join_silent(self, srv, clock, sid="silent"):
        """Park one engine-resident stream with nothing pending, so the
        all-joined-pending fast path stays out of the way."""
        srv.submit(sid, np.zeros((2, 1), np.float32))
        srv.drain()

    def test_deadline_follows_arrival_rate(self):
        """With a measured gap, the scheduler holds for ~gap*need*headroom
        instead of the full max_deadline_us budget."""
        clock = FakeClock()
        srv = self._srv(clock, max_deadline_us=100_000.0,
                        fill_headroom=1.0, ewma_alpha=1.0)
        # park six silent residents: joined = 8, so filling the batch
        # needs 6 more distinct arrivals after a and b
        for i in range(6):
            self._join_silent(srv, clock, sid=f"silent{i}")
        x = np.zeros((4, 1), np.float32)
        srv.submit("a", x)
        clock.advance_us(100.0)
        srv.submit("b", x)              # gap estimate: 100us
        # need = min(width 8, joined 8) - fill 2 = 6 -> predicted fill
        # 600us, measured from the oldest pending ("a" at t=0)
        assert srv.tick() == 0          # a's age 100 < 600
        clock.advance_us(499.0)
        assert srv.tick() == 0          # a's age 599 < 600
        clock.advance_us(2.0)
        assert srv.tick() == 2          # expired at the predicted fill
        assert srv.stats.deadline_flushes == 1

    def test_deadline_expires_at_predicted_fill(self):
        clock = FakeClock()
        srv = self._srv(clock, max_deadline_us=100_000.0,
                        fill_headroom=1.0, ewma_alpha=1.0)
        self._join_silent(srv, clock)
        x = np.zeros((4, 1), np.float32)
        srv.submit("a", x)
        clock.advance_us(100.0)
        srv.submit("b", x)              # gap estimate: 100us
        # need = min(width 8, joined 3) - fill 2 = 1 -> deadline 100us,
        # measured from the oldest pending ("a", age already 100)
        assert srv.tick() == 2
        assert srv.stats.deadline_flushes == 1

    def test_unfillable_batch_flushes_immediately(self):
        """When the estimated fill time exceeds max_deadline_us, waiting
        buys nothing — the batch flushes at min_deadline_us instead of
        burning the whole budget (the fixed-policy pathology)."""
        clock = FakeClock()
        srv = self._srv(clock, max_deadline_us=500.0, fill_headroom=1.0,
                        ewma_alpha=1.0)
        for i in range(6):
            self._join_silent(srv, clock, sid=f"silent{i}")
        x = np.zeros((4, 1), np.float32)
        # 400us gaps: filling 8 needs ~6*400 = 2400us >> 500us cap
        srv.submit("a", x)
        clock.advance_us(400.0)
        srv.submit("b", x)
        assert srv.tick() == 2          # flush now: zero extra wait
        assert srv.stats.deadline_flushes == 1
        # the fast chunks never waited out the 500us cap
        assert srv.stats.latency.max_us <= 401.0

    def test_cold_bucket_uses_max_deadline(self):
        clock = FakeClock()
        srv = self._srv(clock, max_deadline_us=500.0)
        self._join_silent(srv, clock)
        x = np.zeros((4, 1), np.float32)
        srv.submit("a", x)              # first-ever t=4 chunk: no gap yet
        assert srv.tick() == 0          # conservative: hold
        clock.advance_us(499.0)
        assert srv.tick() == 0
        clock.advance_us(2.0)
        assert srv.tick() == 1          # the cap still bounds the wait
        assert srv.stats.deadline_flushes == 1

    def test_width_narrows_when_queue_grows_and_rewidens(self):
        """Engine-bottleneck shrink: queue depth growing across a tick
        halves the effective width (>= min_coalesce); full batches with
        backlog widen it back toward max_coalesce."""
        clock = FakeClock()
        cfg = ServerConfig(
            max_coalesce=4 * SUBLANES,
            adaptive=AdaptiveConfig(min_coalesce=SUBLANES),
        )
        srv = StreamServer(_engine(), cfg, clock=clock)
        assert srv.effective_coalesce == 4 * SUBLANES
        x = np.zeros((2, 1), np.float32)
        n = 4 * SUBLANES
        for i in range(n):
            srv.submit(f"s{i}", x)
        # during this tick 2n more chunks "arrive": depth grows across
        # the tick -> engine-bound -> width halves
        fired = {"n": 0}
        orig = srv.engine.push_many

        def push_and_arrive(ids, chunks):
            res = orig(ids, chunks)
            if fired["n"] == 0:
                fired["n"] = 1
                for i in range(2 * n):
                    srv.submit(f"t{i}", x)
            return res

        srv.engine.push_many = push_and_arrive
        assert srv.tick(force=True) == n
        assert srv.effective_coalesce == 2 * SUBLANES
        # draining the backlog with no new arrivals: full fills + backlog
        # left -> width doubles back up (and no further shrink)
        assert srv.tick(force=True) == 2 * SUBLANES
        assert srv.effective_coalesce == 4 * SUBLANES
        srv.drain()
        assert srv.pending == 0

    def test_adaptive_schedule_bit_equal_sequential(self):
        """The whole adaptive machinery is numerically free: scripted
        joins, ragged fills and drops under adaptive scheduling score
        bit-equal to per-stream sequential replays."""
        clock = FakeClock()
        srv = StreamServer(
            _engine(),
            ServerConfig(max_coalesce=SUBLANES, adaptive=True),
            clock=clock,
        )
        T = srv.engine.window
        x = np.random.RandomState(21).randn(5, 2 * T, 1).astype(np.float32)
        bounds = (0, 5, 11, 16, 2 * T)
        chunk_lists = {
            f"s{i}": [x[i, a:b] for a, b in zip(bounds, bounds[1:])]
            for i in range(5)
        }
        rng = np.random.RandomState(22)
        for j in range(len(bounds) - 1):
            for sid in chunk_lists:
                srv.submit(sid, chunk_lists[sid][j])
                clock.advance_us(float(rng.randint(0, 300)))
                srv.tick()  # adaptive policy decides; any outcome is legal
        srv.drain()
        srv.close_stream("s2")
        rejoin = rng.randn(T, 1).astype(np.float32)
        srv.submit("s2", rejoin[: T // 2])
        srv.submit("s2", rejoin[T // 2 :])
        srv.drain()
        want = _sequential_scores(chunk_lists)
        want["s2"] = want["s2"] + _sequential_scores(
            {"s2": [rejoin[: T // 2], rejoin[T // 2 :]]}
        )["s2"]
        _assert_scores_equal(srv.pop_scores(), want)
        assert srv.stats.processed == srv.stats.submitted


class TestOverflow:
    def _small(self, policy, clock=None):
        eng = _engine()
        return StreamServer(
            eng,
            ServerConfig(
                queue_capacity=2, overflow=policy, deadline_us=1e9
            ),
            clock=clock or time.perf_counter,
        )

    def test_drop_oldest_sheds_stalest(self):
        srv = self._small("drop_oldest")
        T = 12
        x = np.random.RandomState(8).randn(3, T, 1).astype(np.float32)
        srv.submit("a", x[0])
        srv.submit("b", x[1])
        srv.submit("c", x[2])  # capacity 2: "a" is shed
        assert srv.stats.drops == 1
        srv.drain()
        got = srv.pop_scores()
        assert set(got) == {"b", "c"}
        _assert_scores_equal(
            got, _sequential_scores({"b": [x[1]], "c": [x[2]]})
        )

    def test_error_raises_queue_full(self):
        srv = self._small("error")
        srv.submit("a", np.zeros((1, 1), np.float32))
        srv.submit("b", np.zeros((1, 1), np.float32))
        with pytest.raises(QueueFullError):
            srv.submit("c", np.zeros((1, 1), np.float32))
        assert srv.stats.submitted == 2

    def test_block_without_scheduler_raises(self):
        srv = self._small("block")
        srv.submit("a", np.zeros((1, 1), np.float32))
        srv.submit("b", np.zeros((1, 1), np.float32))
        with pytest.raises(RuntimeError, match="no scheduler thread"):
            srv.submit("c", np.zeros((1, 1), np.float32))

    def test_block_unblocks_when_scheduler_drains(self):
        srv = self._small("block")
        srv.config.deadline_us = 100.0  # let the thread actually flush
        with srv:
            for i in range(6):  # 3x capacity: must block and recover
                srv.submit(f"s{i}", np.zeros((2, 1), np.float32))
        assert srv.stats.processed == 6
        assert srv.stats.drops == 0


class TestThreaded:
    def test_concurrent_producers_bit_equal(self):
        eng = _engine()
        srv = StreamServer(
            eng, ServerConfig(deadline_us=500.0, max_coalesce=SUBLANES)
        )
        T = eng.window
        x = np.random.RandomState(9).randn(6, 2 * T, 1).astype(np.float32)
        bounds = (0, 4, 9, 12, 2 * T)
        chunk_lists = {
            f"s{i}": [x[i, a:b] for a, b in zip(bounds, bounds[1:])]
            for i in range(6)
        }

        def produce(ids):
            for j in range(len(bounds) - 1):
                for sid in ids:
                    srv.submit(sid, chunk_lists[sid][j])

        with srv:
            t1 = threading.Thread(target=produce, args=(["s0", "s1", "s2"],))
            t2 = threading.Thread(target=produce, args=(["s3", "s4", "s5"],))
            t1.start(); t2.start()
            t1.join(); t2.join()
        # stop() drained: every chunk processed, every window scored
        assert srv.pending == 0
        assert srv.stats.processed == srv.stats.submitted == 24
        _assert_scores_equal(srv.pop_scores(), _sequential_scores(chunk_lists))

    def test_on_score_callback_delivery(self):
        eng = _engine()
        seen = []
        srv = StreamServer(
            eng, ServerConfig(deadline_us=100.0),
            on_score=lambda sid, s: seen.append((sid, float(s[0]))),
        )
        T = eng.window
        x = np.random.RandomState(10).randn(1, T, 1).astype(np.float32)
        with srv:
            srv.submit("a", x[0])
        assert len(seen) == 1 and seen[0][0] == "a"
        assert srv.pop_scores() == {}  # callback mode: nothing accumulated

    def test_stop_without_drain_abandons_queue(self):
        eng = _engine()
        srv = StreamServer(eng, ServerConfig(deadline_us=1e9))
        srv.start()
        srv.submit("a", np.zeros((2, 1), np.float32))
        srv.stop(drain=False)
        assert srv.pending == 0
        assert srv.stats.processed == 0
        assert srv.stats.cancelled >= 1

    def test_restart_after_stop(self):
        eng = _engine()
        srv = StreamServer(eng, ServerConfig(deadline_us=100.0))
        T = eng.window
        x = np.random.RandomState(11).randn(1, T, 1).astype(np.float32)
        with srv:
            srv.submit("a", x[0, : T // 2])
        with srv:
            srv.submit("a", x[0, T // 2 :])
        _assert_scores_equal(
            srv.pop_scores(),
            _sequential_scores({"a": [x[0, : T // 2], x[0, T // 2 :]]}),
        )


class TestSchedulerDeterminism:
    """Satellite: ANY arrival order / batch-fill sequence the scheduler can
    produce scores bit-equal to sequential per-stream pushes — including
    mid-run joins and drops (property-style via the hypothesis shim)."""

    #: chunk boundaries drawn from a small set so the step program shapes
    #: stay cached across examples (interpret-mode compiles are the cost)
    _SPLITS = [3, 4, 6, 12]

    @settings(max_examples=5)
    @given(st.integers(min_value=0, max_value=10_000))
    def test_random_schedule_bit_equal(self, seed):
        rng = np.random.RandomState(seed)
        eng = _engine()
        srv = StreamServer(
            eng,
            ServerConfig(max_coalesce=SUBLANES, deadline_us=1e9),
        )
        T = eng.window
        n_streams = int(rng.randint(2, 5))
        data = rng.randn(n_streams, 2 * T, 1).astype(np.float32)

        # random per-stream chunkings from the fixed split set
        chunk_lists: dict = {}
        pending: dict = {}
        for i in range(n_streams):
            chunks, pos = [], 0
            while pos < 2 * T:
                t = min(int(rng.choice(self._SPLITS)), 2 * T - pos)
                chunks.append(data[i, pos : pos + t])
                pos += t
            chunk_lists[f"s{i}"] = chunks
            pending[f"s{i}"] = list(chunks)

        # one stream joins late: hold its chunks back until others started
        late = f"s{n_streams - 1}"
        # interleave submissions in random order; randomly tick mid-run so
        # the scheduler sees every batch-fill level
        while any(pending.values()):
            ready = [
                sid for sid, q in pending.items()
                if q and (sid != late or sum(
                    len(p) for s2, p in pending.items() if s2 != late
                ) <= len(pending) // 2)
            ]
            if not ready:
                ready = [sid for sid, q in pending.items() if q]
            sid = ready[int(rng.randint(len(ready)))]
            srv.submit(sid, pending[sid].pop(0))
            if rng.rand() < 0.35:
                srv.tick(force=bool(rng.rand() < 0.5))
        srv.drain()

        # mid-run drop + rejoin: s0 leaves (partial window discarded) and
        # rejoins with fresh data — must score like a brand-new stream
        srv.close_stream("s0")
        rejoin = rng.randn(T, 1).astype(np.float32)
        cut = int(rng.choice([s for s in self._SPLITS if s < T]))
        srv.submit("s0", rejoin[:cut])
        srv.submit("s0", rejoin[cut:])
        srv.drain()

        got = srv.pop_scores()
        want = _sequential_scores(chunk_lists)
        want_rejoin = _sequential_scores(
            {"s0": [rejoin[:cut], rejoin[cut:]]}
        )["s0"]
        for sid in chunk_lists:
            expect = want[sid] + (want_rejoin if sid == "s0" else [])
            assert len(got.get(sid, [])) == len(expect), sid
            for g, w in zip(got[sid], expect):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        # sanity on the instrumentation: everything submitted was scored
        assert srv.stats.processed == srv.stats.submitted
        assert srv.stats.drops == 0
