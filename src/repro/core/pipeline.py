"""Coarse-grained time-wavefront pipeline for stacked recurrent layers.

This is the paper's Sec. III-B/III-D executed at cluster granularity:
layer *l+1* starts consuming hidden states as soon as layer *l* emits them
(Fig. 7 "timestep overlapping"), so a stack of L recurrent layers processes
a length-T sequence in ``T/C + L - 1`` ticks of C timesteps instead of
``L * T/C`` — the coarse-grained seamless pipeline whose II the balance
solver (stage_balance.py) minimizes.

Two interchangeable executions of the same tick schedule:

* ``wavefront``            — single-program form: stages are a vmapped axis,
  chunk hand-off is a ``jnp.roll`` along it.  Runs on one device (tests,
  reference) and under ``jit`` on any mesh.
* ``wavefront_shard_map``  — distributed form: stages live on mesh devices
  along a "stage" axis, hand-off is ``jax.lax.ppermute`` — the TPU
  translation of the paper's per-layer FPGA units streaming h_t onward.

Both compute bit-identical results to sequential layer-by-layer execution
(tests/test_pipeline.py), because the wavefront only reorders when each
(layer, chunk) cell is evaluated — the dependency structure is untouched.

Stage weights must be shape-homogeneous (pad heterogeneous LSTM layers to
the max width; ``pack_lstm_stack`` does this, zero-padding is exact for the
LSTM equations as padded W rows/columns stay zero).  The encoder->decoder
boundary of the GW autoencoder is a hard sync point: pipeline each segment
separately (core/ii_model.Segment semantics).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core.lstm import LstmConfig
from repro.core.quant import ActivationSet, EXACT


# ---------------------------------------------------------------------------
# homogeneous stage packing for LSTM stacks
# ---------------------------------------------------------------------------

#: Number of times ``pack_lstm_stack`` has run (eagerly, or traced into a
#: jit).  Serving code pre-packs once per params identity; benchmarks and
#: tests read this counter to assert the pack is NOT re-traced per call.
PACK_TRACE_COUNT: int = 0


def pack_lstm_stack(params_list: list[dict], in_dims: list[int],
                    hidden_dims: list[int], d_target: int | None = None,
                    h_target: int | None = None) -> tuple[dict, int, int]:
    """Zero-pad per-layer LSTM weights to common (D, H) and stack.

    Returns (stacked params with leading stage axis, D_max, H_max).
    Zero padding is exact: padded input columns multiply zero W_x rows,
    padded hidden lanes multiply zero W_h rows, and padded gate outputs
    never feed back into real lanes.
    """
    global PACK_TRACE_COUNT
    PACK_TRACE_COUNT += 1
    d_max = d_target or max(in_dims)
    h_max = h_target or max(hidden_dims)

    def pad_layer(p, lx, lh):
        w_x = jnp.zeros((d_max, 4 * h_max), p["w_x"].dtype)
        w_h = jnp.zeros((h_max, 4 * h_max), p["w_h"].dtype)
        b = jnp.zeros((4 * h_max,), p["b"].dtype)
        # gate-aware placement: [i|f|g|o] segments each pad lh -> h_max
        def place(dst, src, rows):
            src4 = src.reshape(rows, 4, lh)
            return dst.reshape(-1, 4, h_max).at[:rows, :, :lh].set(src4).reshape(dst.shape)

        w_x = place(w_x, p["w_x"], lx)
        w_h = place(w_h, p["w_h"], lh)
        b = b.reshape(4, h_max).at[:, :lh].set(p["b"].reshape(4, lh)).reshape(-1)
        return {"w_x": w_x, "w_h": w_h, "b": b}

    padded = [pad_layer(p, lx, lh)
              for p, lx, lh in zip(params_list, in_dims, hidden_dims)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *padded)
    return stacked, d_max, h_max


def _lstm_chunk_step(p: dict, h: jax.Array, c: jax.Array, xs: jax.Array,
                     acts: ActivationSet):
    """Run one chunk of timesteps through one LSTM stage (paper split form)."""
    h_max = h.shape[-1]
    xw = (xs @ p["w_x"]).astype(jnp.float32) + p["b"]

    def step(carry, xw_t):
        h, c = carry
        gates = xw_t + (h @ p["w_h"]).astype(jnp.float32)
        i = acts.sigma(gates[..., 0 * h_max:1 * h_max])
        f = acts.sigma(gates[..., 1 * h_max:2 * h_max])
        g = acts.tanh(gates[..., 2 * h_max:3 * h_max])
        o = acts.sigma(gates[..., 3 * h_max:4 * h_max])
        c = f * c + i * g
        h = (o * acts.tanh(c)).astype(h.dtype)
        return (h, c), h

    (h, c), hs = jax.lax.scan(step, (h, c.astype(jnp.float32)),
                              jnp.swapaxes(xw, 0, 1))
    return h, c, jnp.swapaxes(hs, 0, 1)


# ---------------------------------------------------------------------------
# single-program wavefront (vmap over stages, roll hand-off)
# ---------------------------------------------------------------------------

def wavefront(
    stacked: dict,          # stage-stacked LSTM params (S, ...)
    xs: jax.Array,          # (B, T, D) input to stage 0 (pre-padded to D_max)
    n_chunks: int,
    acts: ActivationSet = EXACT,
) -> jax.Array:
    """Returns the LAST stage's hidden sequence (B, T, H_max)."""
    n_stages = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    b, t, d_max = xs.shape
    h_max = stacked["w_h"].shape[1]
    assert t % n_chunks == 0
    ct = t // n_chunks
    chunks = xs.reshape(b, n_chunks, ct, d_max)

    assert d_max == h_max, "pack_uniform guarantees a common stage width"
    step = functools.partial(_lstm_chunk_step, acts=acts)
    vstep = jax.vmap(step, in_axes=(0, 0, 0, 0))
    stage_ids = jnp.arange(n_stages)

    def tick(carry, k):
        h, c, inbox = carry
        # stage 0 reads the k-th input chunk (zeros once chunks run out)
        x_k = jax.lax.dynamic_index_in_dim(
            chunks, jnp.clip(k, 0, n_chunks - 1), axis=1, keepdims=False
        )
        inbox = inbox.at[0].set(x_k)
        h_new, c_new, out = vstep(stacked, h, c, inbox)
        # stage s is ACTIVE at tick k iff s <= k < s + n_chunks: idle stages
        # must not advance their recurrent state on fill/drain ticks (an
        # LSTM step on a zero chunk still moves (h, c) through the biases)
        active = ((stage_ids <= k) & (k < stage_ids + n_chunks))[:, None, None]
        h = jnp.where(active, h_new, h)
        c = jnp.where(active, c_new, c)
        # hand chunks forward one stage; emit the last stage's output
        nxt = jnp.roll(out, 1, axis=0)
        inbox_next = jnp.zeros_like(inbox).at[1:].set(nxt[1:])
        return (h, c, inbox_next), out[-1]

    h0 = jnp.zeros((n_stages, b, h_max), xs.dtype)
    c0 = jnp.zeros((n_stages, b, h_max), jnp.float32)
    inbox0 = jnp.zeros((n_stages, b, ct, d_max), xs.dtype)
    n_ticks = n_chunks + n_stages - 1
    _, outs = jax.lax.scan(tick, (h0, c0, inbox0), jnp.arange(n_ticks))
    # chunk j of the last stage emerges at tick j + (n_stages - 1)
    valid = outs[n_stages - 1:]
    return jnp.moveaxis(valid, 0, 1).reshape(b, t, h_max)


# ---------------------------------------------------------------------------
# distributed wavefront (shard_map over a "stage" mesh axis)
# ---------------------------------------------------------------------------

def wavefront_shard_map(
    stacked: dict,
    xs: jax.Array,
    n_chunks: int,
    mesh,
    acts: ActivationSet = EXACT,
    axis: str = "stage",
) -> jax.Array:
    """Same schedule with stages on devices and ppermute hand-off."""
    from jax.sharding import PartitionSpec as P

    n_stages = mesh.shape[axis]
    b, t, d_max = xs.shape
    h_max = stacked["w_h"].shape[1]
    ct = t // n_chunks
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def program(stacked_local, xs_local):
        # stacked_local: this stage's weights, leading axis 1; xs_local is
        # the full input on stage 0, zeros elsewhere (P(None) would
        # replicate; we give every stage the input and mask by stage id)
        p = jax.tree_util.tree_map(lambda a: a[0], stacked_local)
        sid = jax.lax.axis_index(axis)
        chunks = xs_local.reshape(b, n_chunks, ct, d_max)

        def tick(carry, k):
            h, c, inbox = carry
            x_k = jax.lax.dynamic_index_in_dim(
                chunks, jnp.clip(k, 0, n_chunks - 1), 1, keepdims=False
            )
            feed = jnp.where(sid == 0, x_k, inbox)
            h_new, c_new, out = _lstm_chunk_step(p, h, c, feed, acts)
            active = (sid <= k) & (k < sid + n_chunks)
            h = jnp.where(active, h_new, h)
            c = jnp.where(active, c_new, c)
            inbox_next = jax.lax.ppermute(out, axis, perm)
            return (h, c, inbox_next), out

        h0 = jnp.zeros((b, h_max), xs.dtype)
        c0 = jnp.zeros((b, h_max), jnp.float32)
        inbox0 = jnp.zeros((b, ct, d_max), xs.dtype)
        n_ticks = n_chunks + n_stages - 1
        _, outs = jax.lax.scan(tick, (h0, c0, inbox0), jnp.arange(n_ticks))
        return outs[None]  # (1, ticks, B, ct, H)

    out_ticks = jax.shard_map(
        program,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(axis),
        check_vma=False,
    )(stacked, xs)
    # take the last stage's outputs, drop the fill ticks
    valid = out_ticks[-1, n_stages - 1:]
    return jnp.moveaxis(valid, 0, 1).reshape(b, t, h_max)


# ---------------------------------------------------------------------------
# distributed wavefront over FUSED sub-stacks (each stage = one Pallas call)
# ---------------------------------------------------------------------------

def wavefront_shard_map_fused(
    packed,                 # kernels.lstm_stack.PackedStack for the WHOLE stack
    xs: jax.Array,          # (B, T, D) input at its real width, D <= W
    h0: jax.Array,          # (L, B, W) packed-layout initial hidden
    c0: jax.Array,          # (L, B, W) fp32 initial cell
    n_chunks: int,
    mesh,
    axis: str = "stage",
    timesteps: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The ``wavefront_shard_map`` schedule with the fused Pallas stack
    kernel as every stage's body (backend ``fused_stack_sharded``).

    The L-layer pack splits into ``n_stages`` contiguous sub-stacks along
    its leading layer axis (shard_map's P("stage") sharding of the packed
    weight arrays does the split — quantized int8 packs shard their
    per-layer scales the same way).  Per tick each device advances its
    whole sub-stack over one chunk of timesteps in ONE ``pallas_call``
    (weights and per-layer (h, c) VMEM-resident inside the stage), and
    ``ppermute`` carries only the segment-boundary hidden chunk
    ``(B, ct, W)`` to the next stage — no inner layer's hidden sequence
    ever crosses devices.

    Bit-for-bit equal to the local ``fused_stack`` backend (tested on a
    CPU mesh; ``chip_smoke.py --chips 4`` checks the GW nominal scores on
    four v5e chips): chunked sub-stack execution performs the identical per-step
    math in the identical order; only *where* each (layer, chunk) cell
    evaluates changes.  Stage 0 takes the input at its real width, so
    its layer 0 takes the form the local kernel takes (``ops.layer0_form``);
    the later stages take the hand-off, as wide as the pack.  With
    ``timesteps=T``, ``xs`` is ``(B, D)``, the input of every step, and
    stage 0 projects it once per chunk (the ``repeat`` form).  Returns
    (hs_last (B, T, W), h_final (L, B, W), c_final fp32 (L, B, W)).
    """
    from jax.sharding import PartitionSpec as P

    from repro.kernels.lstm_stack.ops import lstm_stack_op

    n_stages = mesh.shape[axis]
    n_layers = packed.n_layers
    assert n_layers % n_stages == 0, (n_layers, n_stages)
    b, d_in = xs.shape[0], xs.shape[-1]
    t = xs.shape[1] if timesteps is None else timesteps
    w = packed.width_p
    assert t % n_chunks == 0, (t, n_chunks)
    ct = t // n_chunks
    perm = [(i, i + 1) for i in range(n_stages - 1)]
    acts, weight_dtype = packed.acts, packed.weight_dtype

    def program(stacked_local, h0_l, c0_l, xs_local):
        # stacked_local: this stage's contiguous sub-stack (L/S, W, 4W);
        # xs_local is the full input on every stage, masked by stage id
        # (same scheme as wavefront_shard_map)
        sid = jax.lax.axis_index(axis)
        def tick(carry, k):
            h, c, inbox = carry

            def stage(feed, steps=None):
                # the stage body: the whole sub-stack, one Pallas wavefront
                # call
                return lstm_stack_op(
                    feed, stacked_local, h, c, timesteps=steps,
                    acts=acts, weight_dtype=weight_dtype,
                )

            if timesteps is None:
                x_k = jax.lax.dynamic_index_in_dim(
                    xs_local.reshape(b, n_chunks, ct, d_in),
                    jnp.clip(k, 0, n_chunks - 1), 1, keepdims=False,
                )
                first = lambda x, _: stage(x)  # noqa: E731
            else:
                x_k = xs_local
                first = lambda x, _: stage(x, ct)  # noqa: E731
            hs, h_new, c_new = jax.lax.cond(
                sid == 0, first, lambda _, i: stage(i), x_k, inbox)
            # idle stages (fill/drain ticks) must not advance their state
            active = (sid <= k) & (k < sid + n_chunks)
            h = jnp.where(active, h_new, h)
            c = jnp.where(active, c_new, c)
            # only the segment-BOUNDARY hidden chunk crosses devices
            inbox_next = jax.lax.ppermute(hs, axis, perm)
            return (h, c, inbox_next), hs

        inbox0 = jnp.zeros((b, ct, w), h0_l.dtype)
        n_ticks = n_chunks + n_stages - 1
        (h, c, _), outs = jax.lax.scan(
            tick, (h0_l, c0_l, inbox0), jnp.arange(n_ticks)
        )
        return outs[None], h, c  # (1, ticks, B, ct, W), (L/S, B, W) x2

    out_ticks, h_f, c_f = jax.shard_map(
        program,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P()),
        out_specs=(P(axis), P(axis), P(axis)),
        check_vma=False,
    )(packed.stacked, h0, c0, xs)
    valid = out_ticks[-1, n_stages - 1:]
    return jnp.moveaxis(valid, 0, 1).reshape(b, t, w), h_f, c_f


# ---------------------------------------------------------------------------
# convenience: run a whole (possibly heterogeneous) LSTM stack
# ---------------------------------------------------------------------------

def pack_uniform(params_list: list[dict], in_dims: list[int],
                 hidden_dims: list[int]) -> tuple[dict, int]:
    """Pad every stage to one common width W = max(all dims).

    The wavefront hand-off carries a (B, ct, W) buffer between stages, so
    input and hidden widths must coincide across the stack.  Returns
    (stage-stacked params, W).
    """
    width = max(max(in_dims), max(hidden_dims))
    stacked, _, _ = pack_lstm_stack(
        params_list, in_dims, hidden_dims, d_target=width, h_target=width
    )
    return stacked, width


def pipeline_lstm_stack(
    params_list: list[dict],
    cfgs: list[LstmConfig],
    xs: jax.Array,          # (B, T, in_dim of layer 0)
    n_chunks: int,
    acts: ActivationSet = EXACT,
) -> jax.Array:
    """Wavefront the stack; returns last layer's (B, T, hidden[-1]).

    A call site of the executor API: builds a (cached) ``wavefront`` plan
    and executes it.  The wavefront backend packs per call at the exact
    max width (``pack_uniform`` — no Pallas lane rounding), matching this
    function's historical behavior; bind-once packing is a property of the
    fused backends, not this XLA-level reference path.
    """
    import dataclasses

    from repro.core.executor import plan_stack

    if any(c.acts is not acts for c in cfgs):
        cfgs = [dataclasses.replace(c, acts=acts) for c in cfgs]
    plan = plan_stack(cfgs, impl="wavefront", n_chunks=n_chunks)
    return plan.bind(params_list)(xs, return_state=False)
