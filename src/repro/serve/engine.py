"""Serving engines: batched scoring, stateful streaming, and LM decode.

The paper's serving scenario is latency-critical batch-1 streaming (LIGO
events arrive when they arrive); LM serving adds batched decode.  Three
engines cover the space:

* ``AnomalyStreamEngine`` — one-shot batch scoring: a batch of strain
  windows scored by autoencoder reconstruction error against a calibrated
  threshold (FPR-targeted, like the paper's loss-spike flagging).
* ``StreamingAnomalyEngine`` — the paper's true deployment unit: strain
  arrives as a continuous stream of small chunks at batch 1 (or a few
  parallel streams).  Per-stream LSTM ``(h, c)`` state stays resident
  across calls, weights are packed ONCE at engine init, and the per-chunk
  state buffers are donated — the hot loop re-fills nothing.
* ``LmEngine`` — prefill once, then token-by-token decode with the cache
  donated between steps (no per-step reallocation).

Streaming state lifecycle (``StreamingAnomalyEngine``):

    push(chunk) -> encoder (h, c) advances      [donated, kernel-aliased]
    ... window fills up (cfg.timesteps samples) ...
    window complete -> latent -> decode + head -> score; encoder state
    resets to zero (default, matches one-shot window scoring) or carries
    on (``carry_state=True``, the continuous-stream mode)

Donation caveat: after ``push`` returns, the previous state arrays are
deleted (their buffers were reused) — callers must never hold references
to engine state across calls.  The pre-packed weight cache is keyed on
params *identity*: a functional params update (new leaf objects) re-packs
automatically; use ``update_params`` to swap params on a live engine.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.configs.base import ArchConfig
from repro.core.autoencoder import (
    AutoencoderConfig,
    reconstruction_error,
    reconstruction_error_from_latent,
    segment_executors,
)
# the legality rules live in core.backends now; the old names stay
# importable from here (several tests and downstream callers do)
from repro.core.backends import (  # noqa: F401  (re-exports)
    quantized_weight_storage,
    resolve_impl,
)
from repro.kernels.lstm_scan.ops import SUBLANES
from repro.models.api import get_model
from repro.serve.health import (
    SNAPSHOT_VERSION,
    check_fingerprint,
    read_snapshot,
    write_snapshot,
)


def _pad_width(n: int) -> int:
    """Program-shape ladder: the width a batch of ``n`` independent rows
    is padded up to — {1, 2, 4} below one sublane tile, then sublane
    multiples.  A bounded set of compiled shapes across every fill level,
    without forcing a lone stream through a sublane-wide program (the
    step kernel already pads its batch axis to sublane multiples
    *internally*, so the narrow rungs stay bit-equal to the wide ones).
    """
    if n >= SUBLANES:
        return (n + SUBLANES - 1) // SUBLANES * SUBLANES
    w = 1
    while w < n:
        w *= 2
    return w

logger = logging.getLogger(__name__)


@dataclass
class AnomalyStreamEngine:
    """Score strain windows; flag anomalies above an FPR-calibrated threshold."""

    params: dict
    cfg: AutoencoderConfig
    threshold: float = float("inf")
    #: inference backend for the jit'd score path; None keeps cfg.impl.
    #: Serving defaults to the fused wavefront stack — the whole encoder
    #: (and decoder) runs as one Pallas call, no per-layer HBM round-trips.
    #: The upgrade is skipped when cfg.acts is not kernel-exact; the path
    #: actually taken is exposed as ``effective_impl`` (and the fallback is
    #: logged), so serving configs can assert what they run.
    impl: str | None = "fused_stack"
    #: stage placement for the fused path: "local" (one device) or
    #: "sharded" (sub-stacks on mesh devices, ``fused_stack_sharded``)
    placement: str = "local"
    #: "cached" resolves plan knobs from the autotune store (measured-best
    #: for this geometry/backend/device); "default" keeps hand-set knobs
    tune: str = "default"
    #: backend the engine actually runs (output-only, set in __post_init__).
    effective_impl: str = field(init=False, default="")
    #: non-None iff the requested impl was declined (the logged reason).
    fallback_reason: str | None = field(init=False, default=None)

    def __post_init__(self):
        self.cfg, self.effective_impl, self.fallback_reason = resolve_impl(
            self.cfg, self.impl
        )
        if self.fallback_reason is not None:
            logger.warning("AnomalyStreamEngine: %s", self.fallback_reason)

        self._score = jax.jit(
            lambda p, ex_enc, ex_dec, x: reconstruction_error(
                p, x, self.cfg, exec_enc=ex_enc, exec_dec=ex_dec
            )
        )
        # plan + bind eagerly: an illegal impl/placement/weight_dtype combo
        # must raise at construction (plan time), not on the first score()
        self._execs()

    def _execs(self):
        """Current params' bound segment executors (plan cached, pack
        identity-cached, built eagerly — never traced into the score
        graph; re-binds automatically if params were swapped)."""
        return segment_executors(
            self.params, self.cfg,
            impl=self.effective_impl, placement=self.placement,
            tune=self.tune,
        )

    def calibrate(self, background: np.ndarray, fpr: float = 0.01):
        """Set the anomaly threshold at a target false-positive rate
        (the paper: 'threshold ... by setting a false positive rate on
        noise events')."""
        self.threshold = float(np.quantile(self.score(background), 1.0 - fpr))
        return self.threshold

    def score(self, windows: np.ndarray) -> np.ndarray:
        exec_enc, exec_dec = self._execs()
        return np.asarray(
            self._score(self.params, exec_enc, exec_dec,
                        jnp.asarray(windows))
        )

    def flag(self, windows: np.ndarray) -> np.ndarray:
        return self.score(windows) > self.threshold


@dataclass
class _StreamSlot:
    """One named stream's resident state in the coalescing pool: its
    encoder ``(h, c)`` at B=1, the chunks of its partially-filled window,
    and the fill count.  Plain host-side bookkeeping — the arrays are the
    same backend-native state layout ``push`` carries."""

    state: object
    chunks: list = field(default_factory=list)
    filled: int = 0


class StreamingAnomalyEngine:
    """Persistent-state chunked scoring: the paper's continuous-stream mode.

    Strain chunks of any length (including single samples, T=1) arrive via
    ``push``; the encoder's per-layer ``(h, c)`` advances in place without
    re-scoring earlier samples.  Every ``window`` accumulated samples the
    engine emits one anomaly score — numerically equivalent to scoring that
    window one-shot through ``AnomalyStreamEngine`` (tested to fp
    tolerance across impls and chunkings).

    Serving-path specifics (vs the one-shot engine):

    * **pre-packed weights** — on the fused path the stack is packed once
      at init (``pack_stack_cached``, keyed on params identity) and the
      jitted chunk step consumes the packed arrays directly, so
      ``pack_lstm_stack`` is never traced into the per-call graph;
    * **donated state** — the chunk step donates the (h, c) buffers
      (``donate_argnums``), and inside the kernel ``input_output_aliases``
      maps h0->h_final / c0->c_final: steady-state pushes allocate no new
      state;
    * **B parallel streams** — ``batch`` independent streams advance in
      lock-step (the paper's multi-detector case); scores come back (B,).
    * **coalesced independent streams** — ``push_many(stream_ids, chunks)``
      keeps a pool of named B=1 streams at *independent* window fill
      levels and advances any subset with one gathered B=N step call
      (bit-equal to sequential pushes; the fleet-serving shape for
      millions of concurrent streams).

    By default the engine plans ``impl="fused_step"``: chunks up to the
    plan's ``chunk_len`` run the low-latency step kernel (layer-0
    projection in-kernel, one grid step), longer pushes the wavefront
    kernel — both on the same pre-packed weights and resident state.

    ``carry_state=True`` carries encoder state across window boundaries
    (continuous monitoring with no pipeline re-fill); the default resets
    per window, matching one-shot batch semantics bit-for-bit.
    """

    def __init__(
        self,
        params: dict,
        cfg: AutoencoderConfig,
        *,
        batch: int = 1,
        window: int | None = None,
        impl: str | None = "fused_step",
        placement: str = "local",
        chunk_len: int | None = None,
        tune: str = "default",
        carry_state: bool = False,
        donate: bool = True,
        threshold: float = float("inf"),
    ):
        self.cfg, self.effective_impl, self.fallback_reason = resolve_impl(
            cfg, impl
        )
        if self.fallback_reason is not None:
            logger.warning("StreamingAnomalyEngine: %s", self.fallback_reason)
        if self.cfg.boundary < 1:
            raise ValueError("streaming engine needs >= 1 encoder layer")
        self._params = params
        self.batch = batch
        self.placement = placement
        self.chunk_len = chunk_len
        self.tune = tune
        self.window = int(window or self.cfg.timesteps)
        self.carry_state = carry_state
        self.threshold = threshold
        self._donate = donate
        self._build()
        self.reset()

    # -- engine construction -------------------------------------------------

    def _build(self) -> None:
        """Plan + bind both segments; everything else is jit plumbing.

        The per-push encoder step is the executor's *bound* jitted callable
        (``StackExecutor.step_jit``): the weights are jit constants, so
        per-push dispatch flattens only (chunk, state) — routing the
        executor through the jit as a pytree argument instead costs ~1.46x
        a direct kernel call (``exec.step_dispatch_ratio`` gates the bound
        path at <= 1.10x).  The scoring paths still take executors as
        arguments (they run once per window, not per push).
        """
        cfg = self.cfg
        from repro.core.backends import get_backend

        chunk_len = self.chunk_len
        if (
            chunk_len is not None
            and self.fallback_reason is not None
            and not get_backend(self.effective_impl).chunked_step
        ):
            # the impl request already fell back gracefully (logged); the
            # chunk_len that came with it falls back the same way instead
            # of turning the fallback into a plan-time crash.  With NO
            # fallback in play (the caller explicitly picked a non-chunked
            # impl AND a chunk_len) the value passes through and plan_stack
            # raises its usual plan-time error.
            logger.warning(
                "StreamingAnomalyEngine: ignoring chunk_len=%d — resolved "
                "impl=%r has no chunked-step capability", chunk_len,
                self.effective_impl,
            )
            chunk_len = None
        self._exec_enc, self._exec_dec = segment_executors(
            self.params, cfg,
            impl=self.effective_impl, placement=self.placement,
            chunk_len=chunk_len, tune=self.tune,
        )
        self._enc_step = self._exec_enc.step_jit(donate=self._donate)
        # push_many's gather -> step -> scatter runs as ONE jitted call per
        # pool size (cached below): done per-stream with eager ops, the
        # host-side dispatch of N slices dwarfs the coalesced kernel call
        # (measured ~2/3 of push_many wall time at N=64 on CPU)
        self._coalesce_jits: dict = {}
        # zero state through a cached jit: a window completion resets state
        # on the hot path, and two eager jnp.zeros dispatches per window
        # cost more than the compiled call that allocates both at once
        # (fresh buffers every call — donation-safe)
        self._zero_state_jit = jax.jit(
            lambda: self._exec_enc.zero_state(self.batch)
        )
        self._zero_state1_jit = jax.jit(
            lambda: self._exec_enc.zero_state(1)
        )
        # post-step numeric watchdog helpers: one jitted batched abs-max
        # per pool size (see state_absmax)
        self._absmax_jits: dict = {}
        # window completion (gather states -> latent slice -> pad -> decode
        # + score) compiled as ONE call per done-group size: done eagerly,
        # the tree concat + last_hidden getitem + pad concats cost ~5 host
        # dispatches per window — measured as ~45% of a lone stream's
        # server wall time (see _finish_fn); the lock-step push path gets
        # the same fusion (lazy, below)
        self._finish_jits: dict = {}
        self._finishw_jit = None
        self._score_window = jax.jit(
            lambda params, ex_dec, latent, x: reconstruction_error_from_latent(
                params, latent, x, cfg, exec_dec=ex_dec
            )
        )
        self._score_batch = jax.jit(
            lambda params, ex_enc, ex_dec, x: reconstruction_error(
                params, x, cfg, exec_enc=ex_enc, exec_dec=ex_dec
            )
        )

    @property
    def _packed_enc(self):
        """The encoder's bound ``PackedStack`` (None off the packed paths)."""
        return self._exec_enc.packed

    @property
    def _packed_dec(self):
        return self._exec_dec.packed

    def _zero_state(self):
        return self._zero_state_jit()

    # -- state lifecycle -----------------------------------------------------

    def reset(self) -> None:
        """Zero the encoder state, drop any partially-filled window, and
        clear the named-stream pool (``push_many``)."""
        self._state = self._zero_state()
        self._chunks: list[np.ndarray] = []
        self._filled = 0
        self._streams: dict = {}

    @property
    def params(self) -> dict:
        return self._params

    @params.setter
    def params(self, params: dict) -> None:
        # a bare ``engine.params = new`` must never leave the engine scoring
        # with a hybrid of new dense head + stale packed LSTM stacks
        self.update_params(params)

    def update_params(self, params: dict) -> None:
        """Swap params on a live engine: re-bind each segment executor
        (the identity cache misses on the new leaves; the executor's
        lifecycle API evicts its superseded pack), reset stream state.

        The scoring paths take executors as jit *arguments*, so they
        re-trace nothing.  The per-push encoder step is the new executor's
        *bound* jit (weights are constants — that is what keeps per-push
        dispatch at direct-call cost), so the first push after a swap pays
        one re-trace; steady-state pushes are untouched.
        """
        from repro.core.autoencoder import decoder_layers, encoder_layers

        self._params = params
        enc_p, _ = encoder_layers(params, self.cfg)
        dec_p, _ = decoder_layers(params, self.cfg)
        self._exec_enc = self._exec_enc.update_params(enc_p)
        self._exec_dec = self._exec_dec.update_params(dec_p)
        self._enc_step = self._exec_enc.step_jit(donate=self._donate)
        self._coalesce_jits = {}  # closed over the superseded executor
        self._absmax_jits = {}
        self._finish_jits = {}
        self._finishw_jit = None
        self.reset()

    @property
    def filled(self) -> int:
        """Samples accumulated toward the current window."""
        return self._filled

    # -- streaming -----------------------------------------------------------

    def push(self, chunk: np.ndarray) -> list[np.ndarray]:
        """Advance every stream by ``chunk``: (B, t, input_dim), any t >= 1.

        Returns one (B,) score array per window completed during this push
        (empty list while a window is still filling).  Chunks may span
        window boundaries; they are split internally.
        """
        chunk = np.asarray(chunk)
        # a wrong feature dim would be silently zero-padded by the packed
        # kernel, so this must hold even under python -O: raise, not assert
        if (
            chunk.ndim != 3
            or chunk.shape[0] != self.batch
            or chunk.shape[2] != self.cfg.input_dim
        ):
            raise ValueError(
                f"chunk must be (batch={self.batch}, t, "
                f"{self.cfg.input_dim}), got {chunk.shape}"
            )
        scores: list[np.ndarray] = []
        pos = 0
        while pos < chunk.shape[1]:
            take = min(chunk.shape[1] - pos, self.window - self._filled)
            # copy, not view: the caller may reuse its chunk buffer between
            # pushes, and this slice is held until the window completes
            piece = np.array(chunk[:, pos : pos + take])
            self._advance(jnp.asarray(piece))
            self._chunks.append(piece)
            self._filled += take
            pos += take
            if self._filled == self.window:
                scores.append(self._finish_window())
        return scores

    def _advance(self, piece: jax.Array) -> None:
        self._state = self._enc_step(piece, self._state)

    # -- multi-stream coalescing ---------------------------------------------

    @property
    def stream_ids(self) -> tuple:
        """Streams currently resident in the ``push_many`` pool."""
        return tuple(self._streams)

    def drop_stream(self, stream_id) -> None:
        """Release one named stream's state and partial window."""
        self._streams.pop(stream_id, None)

    # -- fault tolerance: snapshot/restore + numeric watchdog ----------------

    def fingerprint(self) -> dict:
        """The geometry + dtype identity a snapshot must match to be
        restorable into this engine: every key here changes either the
        state leaves' shapes/dtypes or the meaning of their values."""
        cfg = self.cfg
        packed = self._packed_enc
        if packed is None:
            wd = "native"
        elif isinstance(packed, tuple):
            # mixed plans bind one PackedStack per homogeneous segment; the
            # per-layer storage signature is what the state values mean
            wd = "+".join(
                str(w) for w in self._exec_enc.plan.weight_dtype
            )
        else:
            wd = packed.weight_dtype
        fp = {
            "hidden": list(cfg.hidden),
            "boundary": int(cfg.boundary),
            "input_dim": int(cfg.input_dim),
            "timesteps": int(cfg.timesteps),
            "window": int(self.window),
            "batch": int(self.batch),
            "dtype": str(jnp.dtype(cfg.dtype)),
            "acts": cfg.acts.name,
            "carry_state": bool(self.carry_state),
            "state_layout": self._exec_enc.plan.backend.state_layout,
            "weight_dtype": wd,
        }
        act_bits = self._exec_enc.plan.act_bits
        if act_bits is not None:
            # activation fake-quant changes the numeric meaning of carried
            # state: a snapshot from a differently-quantized engine must be
            # rejected, but fp32-path snapshots keep their pre-knob shape
            fp["act_bits"] = int(act_bits)
        return fp

    def snapshot(self) -> dict:
        """Serialize every stream's resident state to host memory: the
        lock-step ``push`` path's (h, c)/partial window and the whole
        ``push_many`` pool, plus the calibrated threshold and the
        ``fingerprint()`` that gates ``restore``.  All arrays are copied
        (``np.array``) — donation of the live buffers on the next push
        cannot invalidate a snapshot already taken.  Pair with
        ``save_snapshot``/``restore`` for the on-disk round trip; a
        restored engine resumes **bit-equal** to an uninterrupted run
        (hard-gated in ``server.restore_bitequal``)."""

        def host_leaves(state) -> list[np.ndarray]:
            return [
                np.array(leaf) for leaf in jax.tree_util.tree_leaves(state)
            ]

        return {
            "version": SNAPSHOT_VERSION,
            "fingerprint": self.fingerprint(),
            "threshold": float(self.threshold),
            "state": host_leaves(self._state),
            "chunks": [np.array(c) for c in self._chunks],
            "filled": int(self._filled),
            "streams": {
                sid: {
                    "state": host_leaves(slot.state),
                    "chunks": [np.array(c) for c in slot.chunks],
                    "filled": int(slot.filled),
                }
                for sid, slot in self._streams.items()
            },
        }

    def save_snapshot(self, path) -> None:
        """``snapshot()`` to ``path`` as a versioned ``.npz`` (atomic
        write: temp file + rename)."""
        write_snapshot(path, self.snapshot())

    def restore(self, snap) -> None:
        """Load a snapshot (in-memory dict or a path from
        ``save_snapshot``) into this engine, replacing all stream state.

        The snapshot's version and geometry/``weight_dtype`` fingerprint
        are checked first (``SnapshotMismatchError`` on any disagreement)
        — state arrays from a differently-shaped or differently-quantized
        engine are never installed.  After ``restore`` the engine scores
        bit-equal to one that was never interrupted: the state leaves,
        partial-window chunks, fill counts, and threshold all round-trip
        exactly.
        """
        if isinstance(snap, (str, bytes)) or hasattr(snap, "__fspath__"):
            snap = read_snapshot(snap)
        if snap.get("version") != SNAPSHOT_VERSION:
            from repro.serve.health import SnapshotMismatchError

            raise SnapshotMismatchError(
                f"snapshot schema version {snap.get('version')!r} != "
                f"{SNAPSHOT_VERSION} supported by this engine"
            )
        check_fingerprint(self.fingerprint(), snap["fingerprint"])

        def device_state(template, leaves):
            treedef = jax.tree_util.tree_structure(template)
            return jax.tree_util.tree_unflatten(
                treedef, [jnp.asarray(leaf) for leaf in leaves]
            )

        self.threshold = float(snap["threshold"])
        self._state = device_state(self._zero_state_jit(), snap["state"])
        self._chunks = [np.array(c) for c in snap["chunks"]]
        self._filled = int(snap["filled"])
        self._streams = {}
        zero1 = self._zero_state1_jit()
        for sid, s in snap["streams"].items():
            self._streams[sid] = _StreamSlot(
                state=device_state(zero1, s["state"]),
                chunks=[np.array(c) for c in s["chunks"]],
                filled=int(s["filled"]),
            )

    def state_absmax(self, stream_ids) -> np.ndarray:
        """Max ``|h|, |c|`` per named stream — the post-step numeric
        watchdog's probe.  NaN propagates (a poisoned stream reads NaN,
        Inf reads inf), so ``not (value <= limit)`` catches non-finite
        and exploded states in one comparison.  Streams not resident in
        the pool read 0.  Batched: one jitted gather + reduce per pool
        size (cached), not one host round-trip per stream.
        """
        ids = list(stream_ids)
        out = np.zeros(len(ids), dtype=np.float64)
        present = [
            (i, self._streams[sid])
            for i, sid in enumerate(ids)
            if sid in self._streams
        ]
        if not present:
            return out
        n = len(present)
        fn = self._absmax_jits.get(n)
        if fn is None:
            ax = self._state_batch_axis()

            def absmax_n(states):
                batched = jax.tree_util.tree_map(
                    lambda *leaves: jnp.concatenate(leaves, axis=ax), *states
                )
                per_leaf = [
                    jnp.max(
                        jnp.abs(leaf.astype(jnp.float32)),
                        axis=tuple(d for d in range(leaf.ndim) if d != ax),
                    )
                    for leaf in jax.tree_util.tree_leaves(batched)
                ]
                return jnp.max(jnp.stack(per_leaf, axis=0), axis=0)

            fn = jax.jit(absmax_n)
            self._absmax_jits[n] = fn
        vals = np.asarray(fn(tuple(slot.state for _, slot in present)))
        for (i, _), v in zip(present, vals):
            out[i] = v
        return out

    def _state_batch_axis(self) -> int:
        # packed layout carries (L, B, W) pairs; layers layout [(B, H), ...]
        return 1 if self._exec_enc.plan.backend.state_layout == "packed" else 0

    def _stream_slot(self, stream_id) -> _StreamSlot:
        slot = self._streams.get(stream_id)
        if slot is None:
            slot = _StreamSlot(state=self._new_state1())
            self._streams[stream_id] = slot
        return slot

    def _new_state1(self):
        """A fresh B=1 zero state (a new stream, a pad stream, or a window
        that just finished without ``carry_state``)."""
        with telemetry.span("engine.new_state"):
            telemetry.count("engine.states_created")
            return self._zero_state1_jit()

    def _coalesced_step(self, n: int):
        """One jitted gather->step->scatter for an ``n``-stream pool.

        Per-stream eager ops are the coalescer's real tax at fleet sizes:
        N ``slice_in_dim`` dispatches per piece cost more host time than
        the single B=N kernel call they surround.  Compiling the concat,
        the bound step, and the N-way split as one program makes the
        per-piece dispatch count independent of N.  The input states are
        donated (the slots are re-pointed at the outputs immediately), so
        steady-state coalesced pushes allocate no transient pool state.
        """
        fn = self._coalesce_jits.get(n)
        if fn is None:
            telemetry.count("engine.programs_built")
            ax = self._state_batch_axis()
            exec_enc = self._exec_enc

            def step_n(piece, states):
                batched = jax.tree_util.tree_map(
                    lambda *leaves: jnp.concatenate(leaves, axis=ax), *states
                )
                new_state = exec_enc.step(piece, batched)
                return tuple(
                    jax.tree_util.tree_map(
                        lambda x: jax.lax.slice_in_dim(x, i, i + 1, axis=ax),
                        new_state,
                    )
                    for i in range(n)
                )

            fn = jax.jit(
                step_n, donate_argnums=(1,) if self._donate else ()
            )
            self._coalesce_jits[n] = fn
        return fn

    def push_many(self, stream_ids, chunks: np.ndarray) -> dict:
        """Advance N *independent* B=1 streams with ONE coalesced step call.

        ``chunks``: (N, t, input_dim), row i belonging to
        ``stream_ids[i]``.  The N streams' resident ``(h, c)`` are gathered
        into the batch axis of a single fused step call and scattered back,
        turning N B=1 pushes into one B=N call.  On the step path (pieces
        up to the plan's ``chunk_len``) the kernel pads every batch to the
        same sublane-rounded program shape, so a pool of up to 8 streams
        is **bit-equal** to N sequential single-stream pushes
        (regression-tested and benchmark-gated over 8 streams); larger
        pools and wavefront-kernel fallbacks agree to fp tolerance.
        Streams are created on first use (zero state, empty window) and
        may sit at different window fill levels: the chunk is internally
        split at every stream's window boundary, and streams completing a
        window in the same piece are scored by one batched decode.

        Returns ``{stream_id: [scores...]}`` with one ``(1,)`` score array
        per window the stream completed during this call (empty list while
        its window is still filling).  Requires ``batch == 1`` — the
        lock-step ``push`` axis and the coalescing pool do not mix.
        """
        with telemetry.span("engine.push_many"):
            if self.batch != 1:
                raise ValueError(
                    "push_many coalesces independent B=1 streams; construct "
                    f"the engine with batch=1 (got batch={self.batch})"
                )
            ids = list(stream_ids)
            if len(set(ids)) != len(ids):
                raise ValueError("push_many: duplicate stream ids in one call")
            chunks = np.asarray(chunks)
            if (
                chunks.ndim != 3
                or chunks.shape[0] != len(ids)
                or chunks.shape[2] != self.cfg.input_dim
            ):
                raise ValueError(
                    f"chunks must be (n_streams={len(ids)}, t, "
                    f"{self.cfg.input_dim}), got {chunks.shape}"
                )
            slots = [self._stream_slot(sid) for sid in ids]
            out: dict = {sid: [] for sid in ids}
            step_n = self._coalesced_step(len(slots))
            pos, t_total = 0, chunks.shape[1]
            while pos < t_total:
                # one engine.step span per piece: the piece copy and the
                # step's dispatch with its host-to-device copy
                with telemetry.span("engine.step"):
                    take = min(
                        t_total - pos,
                        min(self.window - s.filled for s in slots),
                    )
                    piece = np.array(chunks[:, pos : pos + take])
                    # gather -> one B=N step -> scatter, compiled as one
                    # call: the per-piece host cost no longer scales with
                    # the pool size (the numpy piece transfers inside the
                    # jit — no eager device_put)
                    new_states = step_n(piece, tuple(s.state for s in slots))
                    for i, slot in enumerate(slots):
                        slot.state = new_states[i]
                        slot.chunks.append(piece[i : i + 1])
                        slot.filled += take
                pos += take
                done = [
                    (sid, s) for sid, s in zip(ids, slots)
                    if s.filled == self.window
                ]
                if done:
                    for (sid, _), score in zip(
                        done, self._finish_streams([s for _, s in done])
                    ):
                        out[sid].append(score)
            return out

    def _finish_fn(self, n: int):
        """One jitted gather->latent->pad->decode->score per done-group
        size ``n``.

        The whole window-completion pipeline compiles as a single program:
        the per-stream state concat, the ``last_hidden`` slice, the pad up
        the program-shape ladder, and the decode + MSE tail.  Done with
        eager ops those are ~5 host dispatches per completed window — on a
        lone stream that was ~45% of the server's per-window wall time.
        The pad rows are inert zeros: any batch-fill level scores through
        an already-compiled decode program (rows are independent, so the
        real scores are unchanged — a continuously-batching server would
        otherwise pay one trace/compile stall per distinct completion-
        group size), while a lone stream decodes one row, not eight.
        """
        fn = self._finish_jits.get(n)
        if fn is None:
            telemetry.count("engine.programs_built")
            ax = self._state_batch_axis()
            exec_enc, exec_dec, cfg = self._exec_enc, self._exec_dec, self.cfg
            pad = _pad_width(n) - n

            def fin(params, states, xs):
                batched = (
                    states[0] if n == 1 else jax.tree_util.tree_map(
                        lambda *leaves: jnp.concatenate(leaves, axis=ax),
                        *states,
                    )
                )
                latent = exec_enc.last_hidden(batched)
                if pad:
                    latent = jnp.concatenate(
                        [latent,
                         jnp.zeros((pad,) + latent.shape[1:], latent.dtype)]
                    )
                    xs = jnp.concatenate(
                        [xs, jnp.zeros((pad,) + xs.shape[1:], xs.dtype)]
                    )
                return reconstruction_error_from_latent(
                    params, latent, xs, cfg, exec_dec=exec_dec
                )

            fn = jax.jit(fin)
            self._finish_jits[n] = fn
        return fn

    def _finish_streams(self, slots: list) -> list[np.ndarray]:
        """Score the streams that just completed a window — one batched
        decode for the whole group (bit-equal to per-stream scoring: the
        decode + MSE tail is row-independent)."""
        k = len(slots)
        with telemetry.span("engine.finish"):
            xs = np.concatenate(
                [np.concatenate(s.chunks, axis=1) for s in slots], axis=0
            )
            scores = self._finish_fn(k)(
                self.params, tuple(s.state for s in slots), xs
            )
            # the wait for the decode on the device and the copy back
            with telemetry.span("engine.finish_sync"):
                scores = np.asarray(scores)[:k]
        for slot in slots:
            slot.chunks, slot.filled = [], 0
            if not self.carry_state:
                slot.state = self._new_state1()
        return [scores[i : i + 1] for i in range(k)]

    def _latent(self) -> jax.Array:
        """Last encoder layer's current hidden — the RepeatVector input."""
        return self._exec_enc.last_hidden(self._state)

    def _finish_window(self) -> np.ndarray:
        # latent slice + decode + score as ONE jitted call, like the pool
        # path's _finish_fn — eager last_hidden/asarray per window was the
        # lock-step path's largest host cost
        fn = self._finishw_jit
        if fn is None:
            exec_enc, exec_dec, cfg = self._exec_enc, self._exec_dec, self.cfg

            def fin(params, state, xs):
                return reconstruction_error_from_latent(
                    params, exec_enc.last_hidden(state), xs, cfg,
                    exec_dec=exec_dec,
                )

            fn = self._finishw_jit = jax.jit(fin)
        x = np.concatenate(self._chunks, axis=1)
        scores = np.asarray(fn(self.params, self._state, x))
        self._chunks, self._filled = [], 0
        if not self.carry_state:
            self._state = self._zero_state()
        return scores

    # -- batch path (calibration / offline) ----------------------------------

    def score(self, windows: np.ndarray) -> np.ndarray:
        """One-shot batch scoring on the same pre-bound executors (does not
        touch stream state); equals chunked scoring to fp tolerance.

        Spans: ``engine.score`` the call, inside it ``engine.score_put``
        (the batch's copy to the device) and ``engine.score_sync`` (the
        wait for the result and its copy back); ``engine.score_host``
        records the call less that wait: the host's own time in the call,
        in which a caller that waits for each call leaves the device idle.
        """
        with telemetry.span("engine.score") as call:
            with telemetry.span("engine.score_put"):
                x = jnp.asarray(windows)
            scores = self._score_batch(
                self.params, self._exec_enc, self._exec_dec, x
            )
            with telemetry.span("engine.score_sync") as sync:
                scores = np.asarray(scores)
        telemetry.record("engine.score_host", call.seconds - sync.seconds)
        return scores

    def flag(self, windows: np.ndarray) -> np.ndarray:
        return self.score(windows) > self.threshold

    def calibrate(self, background: np.ndarray, fpr: float = 0.01) -> float:
        """FPR-targeted threshold on background windows (batch path; chunked
        scoring yields the same threshold — regression-tested)."""
        scores = self.score(background)
        self.threshold = float(np.quantile(scores, 1.0 - fpr))
        return self.threshold


class LmEngine:
    """Prefill + greedy decode with donated cache."""

    def __init__(self, params, cfg: ArchConfig, max_len: int = 256):
        self.cfg = cfg
        self.params = params
        self.api = get_model(cfg)
        self.max_len = max_len
        self._prefill = jax.jit(
            lambda p, b: self.api.prefill(p, b, cfg, max_len)
        )
        self._step = jax.jit(
            lambda p, c, b: self.api.decode_step(p, c, b, cfg),
            donate_argnums=(1,),
        )

    def generate(self, tokens: np.ndarray, n_new: int) -> np.ndarray:
        """tokens: (B, S_prompt) -> (B, n_new) greedy continuation."""
        logits, cache = self._prefill(self.params, {"tokens": jnp.asarray(tokens)})
        out = []
        nxt = jnp.argmax(logits[:, -1, : self.cfg.vocab], axis=-1)[:, None]
        for _ in range(n_new):
            out.append(np.asarray(nxt))
            logits, cache = self._step(self.params, cache, {"tokens": nxt})
            nxt = jnp.argmax(logits[:, -1, : self.cfg.vocab], axis=-1)[:, None]
        return np.concatenate(out, axis=1)
