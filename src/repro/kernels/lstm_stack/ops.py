"""Jit'd wrapper around the fused stack wavefront: pack, pad, dispatch.

Public entry points:

* ``lstm_stack_op(xs, stacked, h0, c0)`` — batch-major convenience wrapper
  over an already homogeneous-packed stack (``core/pipeline.pack_lstm_stack``
  output), handling feature and batch padding/blocking and choosing layer
  0's form from the input's shape (``layer0_form``): a narrow input is
  projected in-kernel, a repeated one once per row, any other by the
  layer-0 ``mvm_x`` matmul whose gate tensor the kernel streams.
  Threads an explicit ``(h0, c0) -> (h_f, c_f)`` so callers can carry state
  across calls; with ``alias_state`` (default) the kernel writes the finals
  in place over the initials.
* ``pack_stack_cached(params_list, cfgs)`` — one-time homogeneous packing
  with an identity-keyed cache: serving engines pack at init and every
  subsequent score call feeds the same ``PackedStack`` straight to
  ``lstm_stack_op``, so ``pack_lstm_stack`` (pad + scatter + stack) is
  traced exactly once per params identity instead of riding inside every
  jitted score call.  Packs carry a ``weight_dtype`` axis (fp32|bf16|int8):
  int8 packs quantize per layer onto a power-of-two ``fixed_quant`` grid
  and store the [s_x, s_h] dequant scales alongside the codes (the kernel
  keeps them in SMEM); the cache keys on the weight dtype, so fp32 and
  int8 packs of the same params are distinct entries.
* ``lstm_stack_forward_fused(params_list, xs, cfgs, initial_state)`` —
  drop-in backend for ``core.lstm.lstm_stack_forward(..., impl="fused_stack")``:
  packs a heterogeneous stack (e.g. the GW autoencoder's (32, 8, 8, 32))
  straight to the lane-padded common width, runs ONE kernel for the whole
  segment, and slices per-layer real widths back out.

Contrast with per-layer ``impl="kernel"``: padding + batch/time transposes
happen once per *segment* instead of once per *layer*, and no intermediate
``(T, B, H)`` hidden sequence ever touches HBM.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.core.quant import (
    WEIGHT_DTYPES,
    ActivationSet,
    EXACT,
    int8_symmetric_quant,
    kernel_safe,
    make_act_quant,
    native_weight_dtype,
)
from repro.kernels.lstm_scan.ops import (
    LANES,
    _on_cpu,
    _round_up,
    choose_blocking,
)

from .lstm_stack import NARROW_MAX_IN, dot_precision, lstm_stack

#: weight storage dtype -> the jnp dtype the packed arrays must hold
_WEIGHT_JNP = {"fp32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}


def normalize_scales(scales: jax.Array, n_layers: int) -> jax.Array:
    """Canonical per-gate ``(L, 2, 4)`` dequant scales.

    New packs quantize each [i|f|g|o] 4W-slice on its own grid; legacy
    per-matrix ``(L, 2)`` packs broadcast — multiplying every gate's
    accumulator by the same scalar reproduces the historical
    whole-accumulator scaling bit-for-bit.
    """
    if scales.ndim == 2:
        scales = scales[:, :, None]
    return jnp.broadcast_to(scales, (n_layers, 2, 4)).astype(jnp.float32)


def apply_gate_scales(x: jax.Array, gate_scales: jax.Array) -> jax.Array:
    """Scale a ``(..., 4W)`` gate accumulator per gate. ``gate_scales``: (4,).

    Elementwise this multiplies gate ``g``'s lanes by ``gate_scales[g]`` —
    with four equal scales it is bit-for-bit the old whole-tensor multiply.
    """
    lead, w4 = x.shape[:-1], x.shape[-1]
    x = x.reshape(*lead, 4, w4 // 4) * gate_scales[:, None]
    return x.reshape(*lead, w4)


def resolve_weight_dtype(cfg, override: str | None = None) -> str:
    """Canonical weight-storage dtype for a layer config.

    ``cfg.weight_dtype=None`` means native storage: weights live at the
    compute dtype (the pre-quantization behaviour).  Explicit values are
    validated: storage wider than compute ('fp32' weights under a bf16
    compute config) is refused — it would silently downcast every tile on
    the way into the MXU, the worst of both worlds.
    """
    wd = override if override is not None else getattr(cfg, "weight_dtype", None)
    if wd is None:
        native = native_weight_dtype(cfg.dtype)
        if native is None:
            raise ValueError(
                f"no native weight storage for compute dtype "
                f"{jnp.dtype(cfg.dtype)}; set weight_dtype explicitly "
                f"(one of {WEIGHT_DTYPES})"
            )
        return native
    if wd not in WEIGHT_DTYPES:
        raise ValueError(
            f"unknown weight_dtype {wd!r}; choose from {WEIGHT_DTYPES}"
        )
    _check_not_wider(wd, cfg.dtype)
    return wd


def _check_not_wider(weight_dtype: str, compute_dtype) -> None:
    if weight_dtype == "fp32" and jnp.dtype(compute_dtype) != jnp.dtype(
        jnp.float32
    ):
        raise ValueError(
            f"weight_dtype='fp32' disagrees with compute dtype "
            f"{jnp.dtype(compute_dtype)}: storage must not be wider than "
            "compute; use 'bf16' or 'int8'"
        )


def check_packed_weight_dtype(stacked: dict, weight_dtype: str, compute_dtype) -> None:
    """Refuse a stacked-weights/weight_dtype disagreement up front.

    Without this the mismatch surfaces as a Pallas/Mosaic shape-or-dtype
    failure deep inside the wavefront call (or, worse, a silent wrong-scale
    matmul when int8 codes are fed through the unscaled path).
    """
    if weight_dtype not in _WEIGHT_JNP:
        raise ValueError(
            f"unknown weight_dtype {weight_dtype!r}; choose from {WEIGHT_DTYPES}"
        )
    want = jnp.dtype(_WEIGHT_JNP[weight_dtype])
    have = jnp.dtype(stacked["w_h"].dtype)
    if have != want:
        raise ValueError(
            f"packed stack stores {have} weights but weight_dtype="
            f"{weight_dtype!r} was requested; re-pack via "
            "pack_stack(..., weight_dtype=...) instead of reusing a pack "
            "built for a different storage dtype"
        )
    if weight_dtype == "int8" and "scales" not in stacked:
        raise ValueError(
            "int8 packed stack is missing its per-layer dequant 'scales'; "
            "pack with pack_stack(weight_dtype='int8'), do not cast weights "
            "to int8 by hand"
        )
    # re-checked at the jit boundary as defense for hand-built stacked dicts
    # (internal callers already validated via resolve_weight_dtype)
    _check_not_wider(weight_dtype, compute_dtype)


def layer0_form(in_width: int, pack_width: int, repeat: bool) -> str:
    """Which of the wavefront kernel's ``LAYER0_FORMS`` a layer-0 input
    takes, from its shape alone: ``repeat`` for a time-invariant input,
    ``narrow`` for one at most ``NARROW_MAX_IN`` features wide, ``stream``
    otherwise.  An input as wide as the pack may already be zero-padded
    (its real width is then unknown), so it streams."""
    if repeat:
        return "repeat"
    if in_width < pack_width and in_width <= NARROW_MAX_IN:
        return "narrow"
    return "stream"


@functools.partial(
    jax.jit,
    static_argnames=(
        "timesteps", "block_b", "acts", "interpret", "alias_state",
        "weight_dtype", "act_bits",
    ),
)
def lstm_stack_op(
    xs: jax.Array,       # (B, T, D) layer-0 input, D <= W; or (B, D) + timesteps
    stacked: dict,       # {"w_x": (L, W, 4W), "w_h": (L, W, 4W), "b": (L, 4W)[, "scales": (L, 2)]}
    h0: jax.Array,       # (L, B, W)
    c0: jax.Array,       # (L, B, W)
    *,
    timesteps: int | None = None,
    block_b: int | None = None,
    acts: ActivationSet = EXACT,
    interpret: bool | None = None,
    alias_state: bool = True,
    weight_dtype: str = "fp32",
    act_bits: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (hs_last: (B, T, W), h_final: (L, B, W), c_final fp32).

    ``xs`` is layer 0's input at its real width D (at most the pack width
    W; this op casts it to the compute dtype and pads it).  With
    ``timesteps=T``, ``xs`` is ``(B, D)``: the same input at each of T steps
    (the decoder's RepeatVector), never broadcast to ``(B, T, D)``.  The
    kernel's layer-0 form follows from the shape (``layer0_form``).  The
    telemetry counter ``wavefront.layer0_<form>`` counts it once per
    distinct trace of this op: two programs that call it with the same
    abstract arguments share one trace and one count.
    """
    if interpret is None:
        interpret = _on_cpu()
    batch, width = xs.shape[0], stacked["w_h"].shape[1]
    assert xs.shape[-1] <= width, (xs.shape, stacked["w_h"].shape)
    check_packed_weight_dtype(stacked, weight_dtype, h0.dtype)
    quantized = weight_dtype == "int8"
    form = layer0_form(xs.shape[-1], width, repeat=timesteps is not None)
    telemetry.count(f"wavefront.layer0_{form}")

    batch_p, block_b = choose_blocking(batch, block_b, interpret=interpret)
    compute = h0.dtype
    xs = xs.astype(compute)
    pad_b = (0, batch_p - batch)
    h0_p = jnp.pad(h0, ((0, 0), pad_b, (0, 0)))
    c0_p = jnp.pad(c0, ((0, 0), pad_b, (0, 0)))

    if form == "narrow":
        x0 = jnp.pad(xs, (pad_b, (0, 0), (0, 0)))
    else:
        # sub-layer 1 for layer 0 (paper mvm_x): ONE big MXU matmul + bias
        # ("stream": then time-major for the sequential wavefront axis;
        # "repeat": one row per window, the kernel reuses it every step).
        # Same dequant order as the kernel's inner layers: cast codes to
        # the compute dtype, matmul, scale the fp32 result.
        pad_f = (0, width - xs.shape[-1])
        xs_p = jnp.pad(xs, (pad_b,) + ((0, 0),) * (xs.ndim - 2) + (pad_f,))
        if form == "repeat" and interpret:
            # CPU only: XLA:CPU picks a dot's kernel by its shape, and rows
            # of a dot with few rows round differently from the same rows
            # of a large one, so a lone stream's window would not decode as
            # it does in a batch.  Here the rows are projected broadcast
            # over time, as the stream form does, and the first step kept.
            # The chip projects the (B, D) rows alone (its matmul rounds
            # each row alike at any row count); only chip_smoke.py, which
            # holds streamed scores to the batch's bit for bit on the chip,
            # checks that projection's rounding
            xs_p = jnp.broadcast_to(xs_p[:, None], (batch_p, timesteps, width))
        w0 = stacked["w_x"][0]
        if w0.dtype != compute:
            w0 = w0.astype(compute)
        # the accumulator is rounded to the compute dtype before widening;
        # the explicit round trip keeps XLA from folding the widen into the
        # dot, which would skip the bf16 rounding the step kernel performs
        x0 = jnp.dot(
            xs_p, w0, preferred_element_type=jnp.float32,
            precision=dot_precision(compute),
        ).astype(compute).astype(jnp.float32)
        if quantized:
            scales = normalize_scales(stacked["scales"], stacked["w_h"].shape[0])
            x0 = apply_gate_scales(x0, scales[0, 0])
        x0 = x0 + stacked["b"][0]
        if form == "stream":
            x0 = jnp.swapaxes(x0, 0, 1)  # (T, Bp, 4W)
        elif interpret:
            x0 = x0[:, 0]

    acts_k = kernel_safe(acts)
    hs, h_f, c_f = lstm_stack(
        x0,
        stacked["w_x"],
        stacked["w_h"],
        stacked["b"].astype(jnp.float32),
        h0_p,
        c0_p.astype(jnp.float32),
        form=form,
        t_len=timesteps,
        scales=stacked["scales"] if quantized else None,
        block_b=block_b,
        sigma=acts_k.sigma,
        tanh=acts_k.tanh,
        interpret=interpret,
        alias_state=alias_state,
        act_quant=make_act_quant(act_bits) if act_bits is not None else None,
    )
    hs = jnp.swapaxes(hs, 0, 1)[:batch]
    return hs, h_f[:, :batch], c_f[:, :batch]


# ---------------------------------------------------------------------------
# one-time weight packing for the serve path
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class PackedStack:
    """A homogeneous-packed LSTM stack ready for ``lstm_stack_op``.

    ``stacked`` holds the lane-padded weights with a leading layer axis;
    the remaining fields record the real (unpadded) geometry needed to
    slice results back out and to build zero/padded state buffers.
    Registered as a pytree (weights are children, geometry is static) so a
    ``PackedStack`` can be passed through ``jax.jit`` boundaries — serving
    engines pack once at init and pass the same arrays to every call.
    """

    stacked: dict[str, jax.Array]
    width_p: int                 # common padded width W
    in_dims: tuple[int, ...]
    hidden: tuple[int, ...]
    dtype: Any
    cell_dtype: Any
    acts: ActivationSet
    #: weight *storage* dtype in VMEM: fp32 | bf16 | int8 (int8 packs carry
    #: per-layer dequant scales in ``stacked["scales"]``)
    weight_dtype: str = "fp32"
    #: strong refs to the source param leaves — keep the cache key's ids
    #: valid and let lookups verify identity (see ``pack_stack_cached``)
    src_leaves: tuple = field(default=(), compare=False)

    @property
    def n_layers(self) -> int:
        return len(self.hidden)

    @property
    def packed_bytes(self) -> int:
        """Bytes the packed stack occupies in VMEM (weights+bias+scales)."""
        return sum(int(a.size) * a.dtype.itemsize for a in self.stacked.values())

    def zero_state(self, batch: int) -> tuple[jax.Array, jax.Array]:
        """Packed-layout zero state: h (L, B, W) compute dtype, c fp32."""
        shape = (self.n_layers, batch, self.width_p)
        return jnp.zeros(shape, self.dtype), jnp.zeros(shape, jnp.float32)

    def pad_input(self, xs: jax.Array) -> jax.Array:
        """Pad (B, T, in_dims[0]) features up to the pack width."""
        return jnp.pad(
            xs.astype(self.dtype),
            ((0, 0), (0, 0), (0, self.width_p - xs.shape[-1])),
        )

    def pack_state(
        self, states: Sequence[tuple[jax.Array, jax.Array]]
    ) -> tuple[jax.Array, jax.Array]:
        """Per-layer [(h, c), ...] at real widths -> packed (L, B, W) pair."""
        def pad(arr, real, dtype):
            return jnp.pad(arr.astype(dtype), ((0, 0), (0, self.width_p - real)))

        h = jnp.stack([pad(h, w, self.dtype) for (h, _), w in zip(states, self.hidden)])
        c = jnp.stack([pad(c, w, jnp.float32) for (_, c), w in zip(states, self.hidden)])
        return h, c

    def unpack_state(
        self, h_f: jax.Array, c_f: jax.Array
    ) -> list[tuple[jax.Array, jax.Array]]:
        """Packed (L, B, W) finals -> per-layer [(h, c), ...] at real widths."""
        return [
            (
                h_f[l, :, :w].astype(self.dtype),
                c_f[l, :, :w].astype(self.cell_dtype),
            )
            for l, w in enumerate(self.hidden)
        ]


def _pack_width(cfgs: Sequence) -> int:
    width = max(max(c.in_dim for c in cfgs), max(c.hidden for c in cfgs))
    return width if _on_cpu() else _round_up(width, LANES)


def _check_homogeneous(cfgs: Sequence) -> None:
    cfg0 = cfgs[0]
    # one kernel executes every layer: activations and dtypes must be
    # stack-wide (a mixed-precision stack would silently compute every
    # layer in cfgs[0].dtype otherwise)
    assert all(c.acts.name == cfg0.acts.name for c in cfgs), (
        "fused_stack requires homogeneous activations across the segment"
    )
    assert all(
        c.dtype == cfg0.dtype and c.cell_dtype == cfg0.cell_dtype for c in cfgs
    ), "fused_stack requires homogeneous dtypes across the segment"
    assert all(
        getattr(c, "weight_dtype", None) == getattr(cfg0, "weight_dtype", None)
        for c in cfgs
    ), "fused_stack requires a homogeneous weight_dtype across the segment"


def pack_stack(
    params_list: Sequence[dict], cfgs: Sequence,
    weight_dtype: str | None = None,
) -> PackedStack:
    """Pack a (possibly heterogeneous) stack to the kernel's common width.

    ``weight_dtype`` picks the VMEM storage for ``W_x``/``W_h`` (default:
    the cfgs' ``weight_dtype``, falling back to native storage at the
    compute dtype).  int8 packs quantize each layer's matrices **per
    gate**: every [i|f|g|o] 4W-slice gets its own symmetric power-of-two
    grid (``core.quant.int8_symmetric_quant`` — the ``fixed_quant`` <8, f>
    grid that covers that gate's range), so a layer whose forget gate spans
    a very different range from its modulation gate no longer wastes grid
    resolution on the wider one.  The ``(L, 2, 4)`` ``[s_x, s_h]`` scales
    ride in ``stacked["scales"]`` (kernels keep them in SMEM; legacy
    ``(L, 2)`` packs stay accepted via broadcast); biases and the cell
    carry stay fp32 (paper Sec. IV-A).
    """
    from repro.core.pipeline import pack_lstm_stack

    _check_homogeneous(cfgs)
    cfg0 = cfgs[0]
    wd = resolve_weight_dtype(cfg0, override=weight_dtype)
    in_dims = tuple(c.in_dim for c in cfgs)
    hidden = tuple(c.hidden for c in cfgs)
    width_p = _pack_width(cfgs)
    stacked, _, _ = pack_lstm_stack(
        list(params_list), list(in_dims), list(hidden),
        d_target=width_p, h_target=width_p,
    )
    if wd == "int8":
        # per-layer, per-GATE symmetric quantization over the lane-padded
        # matrices (zero padding cannot raise a gate's amax, so padded
        # lanes do not distort real lanes' scales)
        def quant_gates(w):  # (W, 4W) -> (codes (W, 4W), scales (4,))
            per_gate = jnp.moveaxis(w.reshape(w.shape[0], 4, -1), 1, 0)
            q, s = jax.vmap(int8_symmetric_quant)(per_gate)
            return jnp.moveaxis(q, 0, 1).reshape(w.shape), s

        q_x, s_x = jax.vmap(quant_gates)(stacked["w_x"])
        q_h, s_h = jax.vmap(quant_gates)(stacked["w_h"])
        stacked = {
            "w_x": q_x, "w_h": q_h, "b": stacked["b"],
            "scales": jnp.stack([s_x, s_h], axis=1).astype(jnp.float32),
        }
    else:
        store = _WEIGHT_JNP[wd]
        stacked = {
            "w_x": stacked["w_x"].astype(store),
            "w_h": stacked["w_h"].astype(store),
            "b": stacked["b"],
        }
    return PackedStack(
        stacked=stacked, width_p=width_p, in_dims=in_dims, hidden=hidden,
        dtype=cfg0.dtype, cell_dtype=cfg0.cell_dtype, acts=cfg0.acts,
        weight_dtype=wd,
        src_leaves=tuple(
            leaf for p in params_list for leaf in jax.tree_util.tree_leaves(p)
        ),
    )


jax.tree_util.register_pytree_node(
    PackedStack,
    lambda ps: (
        (ps.stacked,),
        (ps.width_p, ps.in_dims, ps.hidden, ps.dtype, ps.cell_dtype, ps.acts,
         ps.weight_dtype),
    ),
    lambda aux, ch: PackedStack(ch[0], *aux),
)


#: identity-keyed pack cache: key -> PackedStack.  The PackedStack keeps
#: strong refs to the source leaves, so their id()s stay valid for the
#: lifetime of the entry and a hit can verify ``is``-identity leaf by leaf.
_PACK_CACHE: dict[tuple, PackedStack] = {}
_PACK_CACHE_MAX = 16


def pack_stack_cached(params_list: Sequence[dict], cfgs: Sequence) -> PackedStack:
    """``pack_stack`` memoized on *params identity* (plus geometry).

    A functional update (``{**params, "lstm_0": new}`` / dataclass
    ``replace``) produces new leaf objects, so it misses the cache and
    re-packs — stale packs cannot be served after a params update.  Traced
    values (inside jit) bypass the cache entirely: caching by ``id`` of a
    tracer would leak across traces.
    """
    leaves = [
        leaf for p in params_list for leaf in jax.tree_util.tree_leaves(p)
    ]
    if any(isinstance(leaf, jax.core.Tracer) for leaf in leaves):
        return pack_stack(params_list, cfgs)
    # geometry AND semantics in the key: the same param leaves packed under
    # different acts/dtypes/weight storage are distinct PackedStacks
    # (packed.acts drives the kernel's activations, packed.weight_dtype its
    # VMEM weight layout — an fp32 and an int8 pack of the same params must
    # never collide)
    key = (
        tuple(id(leaf) for leaf in leaves),
        tuple((c.in_dim, c.hidden) for c in cfgs),
        tuple(
            (c.acts.name, c.dtype, c.cell_dtype, resolve_weight_dtype(c))
            for c in cfgs
        ),
        _pack_width(cfgs),
    )
    hit = _PACK_CACHE.get(key)
    if hit is not None and len(hit.src_leaves) == len(leaves) and all(
        a is b for a, b in zip(hit.src_leaves, leaves)
    ):
        return hit
    packed = pack_stack(params_list, cfgs)
    while len(_PACK_CACHE) >= _PACK_CACHE_MAX:
        _PACK_CACHE.pop(next(iter(_PACK_CACHE)))
    _PACK_CACHE[key] = packed
    return packed


def pack_cache_evict(*packs: PackedStack | None) -> None:
    """Drop cache entries holding the given PackedStacks.

    The cache keeps strong refs to source param leaves (that is what makes
    identity keys sound), so a long-lived server that swaps params should
    evict the superseded packs instead of waiting for FIFO turnover —
    ``StreamingAnomalyEngine.update_params`` does.  Evicting is only a
    memory release: engines still holding the PackedStack keep using it.
    """
    dead = {id(p) for p in packs if p is not None}
    for key in [k for k, v in _PACK_CACHE.items() if id(v) in dead]:
        del _PACK_CACHE[key]


def check_packed_matches_cfgs(packed: PackedStack, cfgs: Sequence) -> None:
    """Refuse a ``PackedStack`` built for different configs (geometry,
    activations, dtypes or weight storage).  A mismatched pack silently
    computes with the pack's semantics, so this must hold even under
    python -O — the executor runs it once at bind time."""
    _check_homogeneous(cfgs)
    cfg0 = cfgs[0]
    want = (
        tuple(c.hidden for c in cfgs), tuple(c.in_dim for c in cfgs),
        cfg0.acts.name, cfg0.dtype, cfg0.cell_dtype,
        resolve_weight_dtype(cfg0),
    )
    have = (
        packed.hidden, packed.in_dims,
        packed.acts.name, packed.dtype, packed.cell_dtype,
        packed.weight_dtype,
    )
    if want != have:
        raise ValueError(f"packed stack mismatches cfgs: {have} != {want}")


def lstm_stack_forward_fused(
    params_list: Sequence[dict[str, Any]],
    xs: jax.Array,  # (B, T, in_dim of layer 0), or (B, in_dim) + timesteps
    cfgs: Sequence,  # list[LstmConfig], one per layer
    initial_state: Sequence[tuple[jax.Array, jax.Array]] | None = None,
    *,
    packed: PackedStack | None = None,
    block_b: int | None = None,
    act_bits: int | None = None,
    timesteps: int | None = None,
) -> tuple[jax.Array, list[tuple[jax.Array, jax.Array]]]:
    """Backend for core.lstm.lstm_stack_forward(impl="fused_stack").

    Packs the (possibly heterogeneous) stack to one lane-padded width and
    executes the whole segment as a single wavefront kernel.  Returns
    (hs of the LAST layer: (B, T, hidden[-1]), per-layer (h_f, c_f) finals).

    Pass a pre-built ``packed`` (``pack_stack_cached``) to skip the in-trace
    pack entirely — the serve path does this once at engine init.
    ``block_b`` overrides the kernel's hand-set batch tile (a tuned plan's
    knob rides through here; None keeps ``choose_blocking``'s default).
    ``timesteps=T`` takes ``xs`` as one ``(B, in_dim)`` input repeated at
    every step (``lstm_stack_op``'s ``repeat`` form).
    """
    if packed is None:
        packed = pack_stack_cached(params_list, cfgs)
    else:
        check_packed_matches_cfgs(packed, cfgs)
    batch = xs.shape[0]

    if initial_state is None:
        h0, c0 = packed.zero_state(batch)
    else:
        h0, c0 = packed.pack_state(initial_state)

    hs, h_f, c_f = lstm_stack_op(
        xs, packed.stacked, h0, c0, timesteps=timesteps, acts=packed.acts,
        weight_dtype=packed.weight_dtype, block_b=block_b,
        act_bits=act_bits,
    )
    return hs[..., : packed.hidden[-1]], packed.unpack_state(h_f, c_f)
