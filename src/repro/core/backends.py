"""Backend registry + the legality rules every LSTM execution surface shares.

The paper's flow (and hls4ml's RNN flow) is configure-once / run-many: reuse
factors, precision and placement are fixed at synthesis time, then a fixed
low-latency engine streams data.  This module is the software analogue's
single source of truth for the *configure* half:

* ``BACKENDS`` — one table of every way a stacked LSTM segment can execute
  (``naive``/``split``/``kernel`` layer-by-layer, ``fused_stack`` one Pallas
  wavefront call, ``fused_step`` the same plus a low-latency step kernel
  for short streaming chunks, ``fused_stack_sharded`` the multi-device
  shard_map wavefront over fused sub-stacks, ``wavefront`` the XLA-level
  single-host pipeline), each declaring its capabilities: does it consume a
  ``PackedStack``, may it honour quantized weight storage, does it thread
  per-layer ``(h, c)`` state, does it swap activations for kernel-safe
  twins, can it place stages on mesh devices, does it honour a plan-time
  ``chunk_len`` step specialization.
* the quantized-storage legality check (``check_weight_storage``) and the
  engine-level backend resolution (``resolve_impl``) — previously one copy
  in ``core/lstm.lstm_stack_forward`` and another in ``serve.engine``;
  both now classify against this module (``serve.engine`` re-exports the
  old names).

``core.executor.plan_stack`` consults this table exactly once per plan;
call-time code never re-derives legality.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

from .quant import native_weight_dtype


@dataclass(frozen=True)
class BackendSpec:
    """Capabilities of one stacked-LSTM execution backend.

    ``forward`` is attached by ``core.executor`` at registration time —
    this module stays import-light (no kernels) so the legality rules can
    be consulted without pulling Pallas in.
    """

    name: str
    #: consumes a homogeneous ``PackedStack`` (bound once, never per call)
    packs: bool = False
    #: may honour non-native weight storage (bf16/int8 codes + scales)
    quantized: bool = False
    #: threads per-layer (h, c) initial/final state (streaming serving)
    stateful: bool = True
    #: swaps non-kernel-safe activations (LUT sigmoid) for their PWL twins
    kernel_acts: bool = False
    #: can place pipeline stages on mesh devices (placement="sharded")
    sharded: bool = False
    #: native streaming-state layout: "layers" (per-layer [(h, c), ...] at
    #: real widths — the portable default) or "packed" (the bound
    #: PackedStack's (L, B, W) pair — donation-friendly, no per-chunk
    #: pack/unpack)
    state_layout: str = "layers"
    #: honours a plan-time ``chunk_len``: chunks with T <= chunk_len run the
    #: low-latency step kernel (one grid step, in-kernel layer-0 mvm_x),
    #: longer ones fall back to the wavefront kernel
    chunked_step: bool = False
    #: honours the plan-time ``act_bits`` knob: in-kernel activation
    #: fake-quant on the layer hand-off (paper: 16-bit activations, 32-bit
    #: cell).  Only the local fused kernels implement it; other backends
    #: reject ``act_bits`` at plan time
    act_quant: bool = False
    #: executes per-layer heterogeneous sub-plans (the ``mixed`` backend):
    #: per-layer weight_dtype/geometry, chained through native-layout state
    heterogeneous: bool = False
    #: plan-time knobs the autotuner may sweep for this backend — the
    #: single source of sweep legality (``autotune.space`` builds grids
    #: from this, ``plan_stack`` rejects explicit knobs outside it):
    #: "chunk_len" (step-kernel threshold), "block_b" (batch tile of the
    #: local packed kernels), "fuse_gates" (step kernel's single gate
    #: matmul), "n_chunks" (wavefront hand-off granularity)
    knobs: tuple[str, ...] = ()
    #: (executor, xs, state) -> (h_seq, finals | None), and with
    #: ``timesteps=T`` on packed backends (a time-invariant ``(B, D)``
    #: input, never broadcast over time); filled in by core.executor when
    #: it registers the implementations
    forward: Any = None
    #: optional native-state hot-path hook: (executor, xs, state) -> state;
    #: backends without one fall back to ``forward`` with portable state
    step: Any = None


#: default ``chunk_len`` for chunked-step backends: long enough to cover
#: realistic streaming chunk sizes, short enough that the fully-unrolled
#: T*L step kernel stays a small program (the wavefront kernel wins beyond
#: this anyway — its grid's per-step pipeline needs window-scale T to
#: amortize)
DEFAULT_CHUNK_LEN = 32


#: the one backend table; ``core.executor`` populates ``forward`` fields.
BACKENDS: dict[str, BackendSpec] = {}

#: the degenerate empty-segment backend (latent_boundary=0 style plans)
IDENTITY = "identity"


def register_backend(spec: BackendSpec) -> BackendSpec:
    BACKENDS[spec.name] = spec
    return spec


def _ensure_registered() -> None:
    # executor.py registers the forward implementations on import; make a
    # bare ``get_backend``/``resolve_impl`` caller see the full table
    if not BACKENDS:
        from . import executor  # noqa: F401  (import side effect)


def available_backends() -> tuple[str, ...]:
    _ensure_registered()
    return tuple(n for n in BACKENDS if n != IDENTITY)


def get_backend(name: str) -> BackendSpec:
    _ensure_registered()
    spec = BACKENDS.get(name)
    if spec is None:
        raise ValueError(
            f"unknown impl {name!r}; registered backends: "
            f"{', '.join(available_backends())}"
        )
    return spec


# ---------------------------------------------------------------------------
# quantized weight-storage legality (the single implementation)
# ---------------------------------------------------------------------------

def requested_weight_storage(cfgs) -> str | None:
    """First non-native weight storage requested by a list of layer configs."""
    for c in cfgs:
        wd = getattr(c, "weight_dtype", None)
        if wd is not None and wd != native_weight_dtype(c.dtype):
            return wd
    return None


def quantized_weight_storage(cfg) -> str | None:
    """The first non-native weight storage an AutoencoderConfig requests.

    (Historically lived in ``serve.engine``; kept re-exported there.)
    """
    native = native_weight_dtype(cfg.dtype)
    per_layer = getattr(cfg, "weight_dtypes", None) or ()
    for wd in (cfg.weight_dtype, cfg.dec_weight_dtype, *per_layer):
        if wd is not None and wd != native:
            return wd
    return None


def heterogeneous_weight_storage(cfg) -> bool:
    """True when an AutoencoderConfig pins more than one distinct per-layer
    weight storage — only the ``mixed`` backend can execute that; every
    homogeneous backend's pack would refuse it."""
    per_layer = getattr(cfg, "weight_dtypes", None)
    if not per_layer:
        return False
    return len({wd or "native" for wd in per_layer}) > 1


def check_weight_storage(wd: str | None, impl: str) -> None:
    """Refuse quantized weight storage on a backend that cannot honour it.

    One implementation for every surface (plan_stack, the deprecated
    ``lstm_stack_forward`` shim, and the serve engines' ``resolve_impl``):
    quantized packed weights exist only on the fused wavefront backends —
    any other impl must raise here instead of silently scoring full-width.
    """
    if wd is None:
        return
    if isinstance(wd, (tuple, list)):
        # per-layer storage request (mixed plans): quantized capability is
        # needed as soon as ANY layer asks for narrow storage
        narrow = [w for w in wd if w is not None and w != "fp32"]
        if not narrow:
            return
        wd = narrow[0]
    if not get_backend(impl).quantized:
        legal = ", ".join(
            f"{n!r}" for n, s in BACKENDS.items() if s.quantized
        )
        raise ValueError(
            f"weight_dtype={wd!r} requires a quantized-capable backend "
            f"(impl in {{{legal}}}); got impl={impl!r}: quantized packed "
            "weights only exist on the fused wavefront path"
        )


# ---------------------------------------------------------------------------
# engine-level backend resolution (moved verbatim from serve.engine)
# ---------------------------------------------------------------------------

def resolve_impl(cfg, impl: str | None):
    """Resolve a requested inference backend against kernel-safety.

    Returns ``(cfg, effective_impl, fallback_reason)``.  Kernel backends
    (any spec with ``kernel_acts``) swap non-kernel-safe activations (e.g.
    PAPER_HW's LUT sigmoid) for their PWL twins in-kernel, which would make
    scores inconsistent with thresholds calibrated on ``cfg.impl`` — in
    that case the request is declined, ``cfg.impl`` is kept, and the reason
    is returned (and logged by the engines).  Set ``cfg.impl`` directly to
    opt in regardless.

    Quantized weight storage (``cfg.weight_dtype``/``dec_weight_dtype``)
    exists only on the fused packed stack, so a config that requests it but
    resolves to any other backend is an error *here*, not a late Pallas (or
    silent full-width) failure at score time.
    """
    from .quant import kernel_safe

    if impl is None or impl == cfg.impl:
        cfg, effective, reason = cfg, cfg.impl, None
    elif get_backend(impl).kernel_acts and kernel_safe(cfg.acts) is not cfg.acts:
        reason = (
            f"requested impl={impl!r} would swap acts={cfg.acts.name!r} for "
            f"its kernel-safe twin; keeping impl={cfg.impl!r} so scores stay "
            f"consistent with thresholds calibrated on it"
        )
        effective = cfg.impl
    elif heterogeneous_weight_storage(cfg) and not get_backend(impl).heterogeneous:
        reason = (
            f"config pins heterogeneous per-layer weight_dtypes, which only "
            f"the mixed backend executes; keeping impl={cfg.impl!r} over the "
            f"requested impl={impl!r}"
        )
        effective = cfg.impl
    else:
        cfg, effective, reason = replace(cfg, impl=impl), impl, None
    wd = quantized_weight_storage(cfg)
    if wd is not None and not get_backend(effective).quantized:
        raise ValueError(
            f"weight_dtype={wd!r} requires the fused_stack backend, but the "
            f"engine resolved impl={effective!r}"
            + (f" ({reason})" if reason else "")
            + "; drop the quantized weight_dtype or fix the config so the "
            "fused path is eligible"
        )
    return cfg, effective, reason
