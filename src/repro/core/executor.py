"""Plan / bind / execute: the one call surface for every LSTM backend.

The paper's deployment model makes every decision at *compile* time — reuse
factors, precision, placement are fixed once, then a fixed low-latency
engine streams data (hls4ml's RNN flow has the same shape: configure,
synthesize, stream).  This module is that lifecycle for the TPU
reproduction:

    plan = plan_stack(cfgs, impl="fused_stack", weight_dtype="int8",
                      placement="local")        # resolve ONCE (cached)
    ex = plan.bind(params_list)                 # pack weights exactly once
    h_seq, finals = ex(xs)                      # the only call-time surface
    state = ex.zero_state(batch)                # streaming serving loop:
    state = ex.step(chunk, state)               #   native-layout hot path

``plan_stack`` resolves backend legality (the rules live in
``core.backends``), weight-storage dtype, packing strategy and placement
exactly once and caches the plan — call-time code never re-checks
impl-dependent kwargs, never ``dataclasses.replace``s configs, and never
re-packs weights.  ``StackExecutor`` is a registered pytree (params/packed
are leaves, the plan is static aux data), so serving engines pass bound
executors straight through ``jax.jit`` boundaries and a params swap is a
re-``bind`` — the jitted step re-traces zero times.

Backends (see ``core.backends.BACKENDS``):

    naive / split / kernel   layer-by-layer (XLA scans / per-layer Pallas)
    fused_stack              whole segment in ONE Pallas wavefront call
    fused_step               fused_stack + a low-latency step kernel for
                             chunks with T <= plan.chunk_len (in-kernel
                             layer-0 mvm_x, one grid step) — the streaming
                             serving default
    fused_stack_sharded      stages on mesh devices, each stage's body the
                             fused Pallas kernel, ppermute carrying only
                             segment-boundary hidden chunks
    wavefront                XLA-level single-host pipeline (vmap + roll)
    mixed                    per-layer heterogeneous: maximal homogeneous
                             runs become ordinary fused_step sub-plans
                             (per-layer weight_dtype / chunk geometry)
                             chained through native-layout state hand-off;
                             tune="balanced" picks the int8/fp32 split that
                             equalizes roofline-predicted per-segment cost

``core.lstm.lstm_stack_forward`` survives as a deprecated shim that builds
a (cached) plan per call, so pre-executor call sites keep working.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from .backends import (
    BackendSpec,
    DEFAULT_CHUNK_LEN,
    IDENTITY,
    check_weight_storage,
    get_backend,
    register_backend,
    requested_weight_storage,
)
from .lstm import LstmConfig, lstm_forward, zero_state as layer_zero_state

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# StackPlan — everything resolved, nothing bound
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class StackPlan:
    """A fully-resolved execution plan for one LSTM segment.

    Immutable and hashable: it rides as the static aux data of the
    ``StackExecutor`` pytree, so two executors with equal plans share jit
    traces.  ``cfgs`` already carry the resolved ``weight_dtype`` — the
    per-call ``dataclasses.replace`` the old dispatch did is paid once,
    here, at plan time.
    """

    cfgs: tuple[LstmConfig, ...]
    impl: str
    #: resolved weight *storage* ("fp32"|"bf16"|"int8") for packed
    #: backends; a per-layer tuple for ``impl="mixed"``; None for
    #: layer-by-layer backends (native storage)
    weight_dtype: Any = None
    placement: str = "local"
    #: jax Mesh with a "stage" axis (sharded placement only)
    mesh: Any = None
    #: time chunks per wavefront tick (sharded/wavefront; None = auto)
    n_chunks: int | None = None
    #: chunked-step backends only: chunks with T <= chunk_len run the
    #: low-latency step kernel instead of the wavefront kernel
    chunk_len: int | None = None
    #: batch tile of the local packed kernels (None = choose_blocking's
    #: hand-set default); a tuned value comes from the autotune cache
    block_b: int | None = None
    #: step kernel's single [x;h] @ [W_x;W_h] gate matmul (None = the
    #: kernel's documented default: fused on compiled TPU, separate dots
    #: in interpret mode and always for int8)
    fuse_gates: bool | None = None
    #: in-kernel activation fake-quant on the layer hand-off (paper: 16-bit
    #: activations, fp32 cell); None = full-precision hand-off.  Only legal
    #: on backends with the ``act_quant`` capability flag
    act_bits: int | None = None
    #: ``impl="mixed"`` split knob: layers [0, split) store int8, the rest
    #: fp32 (the autotune sweep's one-dimensional split axis); None when
    #: the per-layer dtypes came from an explicit tuple or the balancer
    split: int | None = None
    #: ``impl="mixed"`` only: the maximal homogeneous sub-plans (each an
    #: ordinary fused_step StackPlan) the executor chains through
    #: native-layout state hand-off
    segments: tuple = ()
    #: where each resolved knob came from ("explicit" | "tuned" |
    #: "default" | "balanced") — provenance metadata for operators
    #: (--plan-only), excluded from equality/hash so tuned and hand-set
    #: plans with equal knob values share jit traces
    knob_sources: tuple = dataclasses.field(default=(), compare=False)

    @property
    def backend(self) -> BackendSpec:
        return get_backend(self.impl)

    def knob_provenance(self) -> dict[str, tuple[Any, str]]:
        """{knob: (resolved value, source)} for the backend's tunable knobs.

        The audit surface behind ``launch/serve.py --plan-only``: operators
        see exactly which knobs a serving engine resolved from the tuned
        cache versus the hand-set defaults.
        """
        sources = dict(self.knob_sources)
        out = {
            k: (getattr(self, k), sources.get(k, "default"))
            for k in self.backend.knobs
        }
        if self.act_bits is not None:
            out["act_bits"] = (
                self.act_bits, sources.get("act_bits", "default")
            )
        if self.backend.heterogeneous:
            # per-layer storage is the mixed backend's defining knob: show
            # it (and where the split came from) alongside the others
            out["weight_dtype"] = (
                self.weight_dtype, sources.get("weight_dtype", "default")
            )
        return out

    def layer_assignment(self) -> list[dict[str, Any]]:
        """Per-layer split of a mixed plan: one row per layer with its
        resolved dtype, chunk_len and stage (= segment index) — what
        ``launch/serve.py --plan-only`` prints for heterogeneous plans."""
        if not self.backend.heterogeneous:
            raise ValueError(
                f"layer_assignment() is a mixed-plan surface; "
                f"impl={self.impl!r} is homogeneous"
            )
        rows, layer = [], 0
        for stage, seg in enumerate(self.segments):
            for c in seg.cfgs:
                rows.append({
                    "layer": layer, "hidden": c.hidden, "stage": stage,
                    "weight_dtype": seg.weight_dtype,
                    "chunk_len": seg.chunk_len,
                })
                layer += 1
        return rows

    @property
    def n_layers(self) -> int:
        return len(self.cfgs)

    @property
    def hidden(self) -> tuple[int, ...]:
        return tuple(c.hidden for c in self.cfgs)

    def bind(self, params_list: Sequence[Params], *,
             packed: Any = None) -> "StackExecutor":
        """Bind parameters: pack weights exactly once, return the executor.

        Packing goes through ``pack_stack_cached`` (identity-keyed), so
        binding the same param leaves twice reuses the same ``PackedStack``
        and binding under a jit trace packs in-trace without touching the
        cache.  An explicitly supplied ``packed`` is validated against the
        plan's configs here, at bind time — never deep inside a Pallas call.
        """
        spec = self.backend
        params = tuple(params_list)
        if packed is not None and not spec.packs:
            raise ValueError(
                f"packed weights only apply to packing backends "
                f"(impl={self.impl!r})"
            )
        if spec.heterogeneous and self.cfgs:
            from repro.kernels.lstm_stack.ops import (
                check_packed_matches_cfgs,
                pack_stack_cached,
            )

            # one PackedStack per homogeneous segment — each packed exactly
            # as a hand-built fused_step plan over that segment would pack
            if packed is None:
                packs, i = [], 0
                for seg in self.segments:
                    n = seg.n_layers
                    packs.append(pack_stack_cached(
                        list(params[i:i + n]), list(seg.cfgs)))
                    i += n
                packed = tuple(packs)
            else:
                packed = tuple(packed)
                if len(packed) != len(self.segments):
                    raise ValueError(
                        f"mixed plan has {len(self.segments)} segments but "
                        f"{len(packed)} packs were supplied"
                    )
                for seg, pk in zip(self.segments, packed):
                    check_packed_matches_cfgs(pk, seg.cfgs)
            return StackExecutor(self, params, packed)
        if spec.packs and self.cfgs:
            from repro.kernels.lstm_stack.ops import (
                check_packed_matches_cfgs,
                pack_stack_cached,
            )

            if packed is None:
                packed = pack_stack_cached(list(params), list(self.cfgs))
            else:
                check_packed_matches_cfgs(packed, self.cfgs)
        return StackExecutor(self, params, packed)

    def describe(self) -> str:
        """One-line human summary (the launch --plan-only smoke prints it)."""
        dims = "->".join(str(c.hidden) for c in self.cfgs) or "(identity)"
        step = f" chunk_len={self.chunk_len}" if self.chunk_len else ""
        if self.block_b is not None:
            step += f" block_b={self.block_b}"
        if self.fuse_gates is not None:
            step += f" fuse_gates={self.fuse_gates}"
        if self.act_bits is not None:
            step += f" act_bits={self.act_bits}"
        if self.segments:
            step += f" segments={len(self.segments)}"
        wd = self.weight_dtype
        if isinstance(wd, tuple):
            wd = "+".join(wd)
        return (
            f"impl={self.impl} placement={self.placement} "
            f"layers={self.n_layers} [{dims}] "
            f"weight_dtype={wd or 'native'}{step}"
        )


def _default_stage_mesh(n_layers: int):
    """Largest device count that divides the stack into whole sub-stacks."""
    n = max(1, min(len(jax.devices()), n_layers))
    while n > 1 and n_layers % n:
        n -= 1
    # Auto axes: the sharded backends rely on GSPMD to place the operands
    # and to slice shard_map's output, which Explicit axes refuse
    return jax.make_mesh((n,), ("stage",), axis_types=(AxisType.Auto,))


@functools.lru_cache(maxsize=128)
def _plan_stack_cached(cfgs: tuple[LstmConfig, ...], impl: str,
                       weight_dtype: str | None, placement: str,
                       mesh, n_chunks: int | None,
                       chunk_len: int | None, block_b: int | None,
                       fuse_gates: bool | None, act_bits: int | None,
                       knob_sources: tuple) -> StackPlan:
    get_backend(impl)  # raises for unknown impl, even on empty segments
    if placement not in ("local", "sharded"):
        raise ValueError(
            f"unknown placement {placement!r}; choose 'local' or 'sharded'"
        )
    if not cfgs:  # empty segment (e.g. latent_boundary=0): identity plan
        return StackPlan(cfgs=(), impl=IDENTITY)
    sources = dict(knob_sources)

    # -- placement normalization -------------------------------------------
    if impl == "fused_stack_sharded":
        placement = "sharded"
    if placement == "sharded":
        if impl in ("fused_stack", "fused_step", "fused_stack_sharded"):
            # the step specialization is single-host; sharded placement
            # degrades fused_step to the sharded wavefront (serving configs
            # keep one impl default across placements) — and drops the
            # whole step-kernel knob bundle with it (chunk_len, fuse_gates,
            # block_b), like the rest of the step request
            if impl == "fused_step":
                chunk_len = None
            fuse_gates = None
            block_b = None
            sources.update(chunk_len="default", fuse_gates="default",
                           block_b="default")
            impl = "fused_stack_sharded"
        else:
            raise ValueError(
                f"placement='sharded' requires the fused_stack backend "
                f"(got impl={impl!r}); only fused sub-stacks can place "
                "pipeline stages on mesh devices"
            )
    elif mesh is not None:
        # an explicit stage mesh under local placement would be silently
        # ignored — that can only be a forgotten placement='sharded'
        raise ValueError(
            "a stage mesh was supplied but placement='local'; pass "
            "placement='sharded' to place sub-stacks on mesh devices"
        )
    spec = get_backend(impl)

    # -- tunable-knob legality (the capability table decides) ---------------
    if block_b is not None:
        if "block_b" not in spec.knobs:
            raise ValueError(
                f"block_b only applies to the local packed-kernel backends "
                f"(those declaring it in BackendSpec.knobs); got "
                f"impl={impl!r}"
            )
        if block_b < 1:
            raise ValueError(f"block_b must be >= 1, got {block_b}")
    if fuse_gates is not None and "fuse_gates" not in spec.knobs:
        raise ValueError(
            f"fuse_gates only applies to the chunked-step backend "
            f"(impl='fused_step'); got impl={impl!r}"
        )
    if n_chunks is not None and "n_chunks" not in spec.knobs:
        raise ValueError(
            f"n_chunks only applies to wavefront-pipelined backends "
            f"(impl='wavefront' or sharded placement); got impl={impl!r}"
        )
    if act_bits is not None:
        # numerics knob: never silently dropped — backends that cannot
        # fake-quant the hand-off in-kernel (sharded, layer-by-layer,
        # wavefront) refuse at plan time.  Note the sharded degrade above
        # runs first, so fused_step + placement='sharded' + act_bits lands
        # here with the sharded backend and raises as required.
        if not spec.act_quant:
            raise ValueError(
                f"act_bits only applies to backends with in-kernel "
                f"activation quantization (BackendSpec.act_quant: the local "
                f"fused kernels); got impl={impl!r}"
            )
        from .quant import ACT_BITS

        if act_bits not in ACT_BITS:
            raise ValueError(
                f"act_bits={act_bits!r} unsupported; choose from {ACT_BITS}"
            )

    # -- step-chunk resolution ---------------------------------------------
    if chunk_len is not None and not spec.chunked_step:
        raise ValueError(
            f"chunk_len only applies to chunked-step backends "
            f"(impl='fused_step'); got impl={impl!r}"
        )
    if spec.chunked_step:
        from repro.kernels.lstm_stack.step import MAX_STEP_UNROLL

        if chunk_len is None:
            # clamp the default so deep stacks stay under the kernel's
            # sequential-cell ceiling (the explicit-value check below then
            # holds for defaulted plans too — legality stays plan-time)
            chunk_len = max(1, min(DEFAULT_CHUNK_LEN,
                                   MAX_STEP_UNROLL // len(cfgs)))
        if chunk_len < 1:
            raise ValueError(f"chunk_len must be >= 1, got {chunk_len}")
        if chunk_len * len(cfgs) > MAX_STEP_UNROLL:
            raise ValueError(
                f"chunk_len={chunk_len} x {len(cfgs)} layers exceeds the "
                f"step kernel's {MAX_STEP_UNROLL} sequential-cell ceiling; "
                "long chunks belong to the wavefront kernel"
            )

    # -- weight-storage resolution (ONCE, not per traced call) -------------
    if weight_dtype is not None:
        cfgs = tuple(
            c if c.weight_dtype == weight_dtype
            else dataclasses.replace(c, weight_dtype=weight_dtype)
            for c in cfgs
        )
    # quantized storage is only legal on backends that apply the scales
    # (no-op when the backend is quantized-capable — the table decides)
    check_weight_storage(requested_weight_storage(cfgs), impl)
    if spec.packs:
        from repro.kernels.lstm_stack.ops import (
            _check_homogeneous,
            resolve_weight_dtype,
        )

        _check_homogeneous(cfgs)
        resolved_wd = resolve_weight_dtype(cfgs[0])
    else:
        resolved_wd = None
    if fuse_gates and resolved_wd == "int8":
        # the step kernel would refuse this at call time; fail at plan time
        # like every other impl-dependent legality rule
        raise ValueError(
            "fuse_gates=True is incompatible with int8 packs: s_x and s_h "
            "scale two different fp32 accumulators, which a single fused "
            "[x;h] contraction would mix; drop fuse_gates or the int8 "
            "weight_dtype"
        )

    # -- placement resolution ----------------------------------------------
    if placement == "sharded":
        if mesh is None:
            mesh = _default_stage_mesh(len(cfgs))
        n_stages = mesh.shape["stage"]
        if len(cfgs) % n_stages:
            raise ValueError(
                f"sharded placement needs the {len(cfgs)}-layer stack to "
                f"split into whole sub-stacks across {n_stages} stage "
                "devices; pass a mesh whose 'stage' axis divides the layer "
                "count"
            )
    else:
        mesh = None

    return StackPlan(
        cfgs=cfgs, impl=impl, weight_dtype=resolved_wd,
        placement=placement, mesh=mesh, n_chunks=n_chunks,
        chunk_len=chunk_len, block_b=block_b, fuse_gates=fuse_gates,
        act_bits=act_bits,
        knob_sources=tuple(sorted(sources.items())),
    )


#: the knobs ``tune="cached"`` may resolve from the autotune store (must
#: stay in sync with ``repro.autotune.cache.KNOB_NAMES``)
_TUNABLE_KNOBS = ("chunk_len", "block_b", "fuse_gates", "n_chunks", "split")


def _normalize_per_layer(name: str, value, n: int) -> tuple:
    """Broadcast a scalar knob to per-layer, validate a sequence's length."""
    if not isinstance(value, (tuple, list)):
        return (value,) * n
    value = tuple(value)
    if len(value) != n:
        raise ValueError(
            f"per-layer {name} needs one entry per layer ({n}); got "
            f"{len(value)}"
        )
    return value


@functools.lru_cache(maxsize=64)
def _plan_mixed_cached(cfgs: tuple[LstmConfig, ...], wds: tuple,
                       chunk_lens: tuple, block_bs: tuple,
                       fuse_gatess: tuple, act_bits: int | None,
                       split: int | None,
                       knob_sources: tuple) -> StackPlan:
    """Build the mixed plan: segment on per-layer signature, sub-plan each.

    Layers with equal (weight_dtype, chunk_len, block_b, fuse_gates,
    compute dtype, cell dtype, activations) signature merge into one
    maximal run; each run becomes an ordinary ``fused_step`` sub-plan via
    ``_plan_stack_cached`` — so a mixed plan's segments are *identical*
    (same memo entries) to the plans a caller would build by hand-chaining
    homogeneous fused_step stacks, which is what makes the executor's
    bit-equality guarantee hold by construction.
    """
    def sig(i: int):
        c = cfgs[i]
        return (wds[i], chunk_lens[i], block_bs[i], fuse_gatess[i],
                c.dtype, c.cell_dtype, c.acts.name)

    bounds, start = [], 0
    for i in range(1, len(cfgs)):
        if sig(i) != sig(i - 1):
            bounds.append((start, i))
            start = i
    bounds.append((start, len(cfgs)))

    subs = tuple(
        _plan_stack_cached(
            cfgs[a:b], "fused_step", wds[a], "local", None, None,
            chunk_lens[a], block_bs[a], fuse_gatess[a], act_bits, (),
        )
        for a, b in bounds
    )
    # the sub-plans carry the resolved storage (native resolution applied);
    # re-expand to per-layer for the top-level plan's weight_dtype tuple
    resolved_wds = tuple(
        sub.weight_dtype for sub in subs for _ in sub.cfgs
    )
    new_cfgs = tuple(c for sub in subs for c in sub.cfgs)

    def uniform(values):
        vals = {v for v in values if v is not None}
        return vals.pop() if len(vals) == 1 else None

    return StackPlan(
        cfgs=new_cfgs, impl="mixed", weight_dtype=resolved_wds,
        placement="local",
        # conservative top-level chunk_len: chunks at or under it take the
        # step kernel in EVERY segment (each segment still routes on its own)
        chunk_len=min(sub.chunk_len for sub in subs),
        block_b=uniform(block_bs), fuse_gates=uniform(fuse_gatess),
        act_bits=act_bits, split=split, segments=subs,
        knob_sources=knob_sources,
    )


def _plan_mixed(cfgs: tuple[LstmConfig, ...], weight_dtype, placement: str,
                mesh, n_chunks, chunk_len, block_b, fuse_gates,
                act_bits: int | None, split: int | None,
                tune: str) -> StackPlan:
    """Resolve per-layer weight storage for ``impl="mixed"`` and delegate.

    Storage resolution precedence (first match wins, recorded in
    ``knob_sources``):
      1. explicit ``split=k`` (int8 layers [0, k), fp32 the rest) or an
         explicit per-layer ``weight_dtype`` sequence / broadcast scalar
      2. ``tune="cached"``: a tuned-store entry's ``split``
      3. ``tune="balanced"``: the roofline-model balancer
         (``core.stage_balance.choose_mixed_split``)
      4. each cfg's own ``weight_dtype`` (native resolution)
    """
    if not cfgs:
        return StackPlan(cfgs=(), impl=IDENTITY)
    if placement != "local" or mesh is not None:
        raise ValueError(
            "impl='mixed' is single-host: heterogeneous segments chain "
            "through local native-layout state hand-off; use "
            "placement='local' (shard each homogeneous segment instead)"
        )
    if n_chunks is not None:
        raise ValueError(
            "n_chunks only applies to wavefront-pipelined backends; "
            "impl='mixed' chains local fused_step segments"
        )
    n = len(cfgs)
    sources = {
        k: ("explicit" if v is not None else "default")
        for k, v in (("chunk_len", chunk_len), ("block_b", block_b),
                     ("fuse_gates", fuse_gates), ("split", split))
    }
    if act_bits is not None:
        sources["act_bits"] = "explicit"

    wds = None
    if split is not None:
        if weight_dtype is not None:
            raise ValueError(
                "pass either split= or weight_dtype=, not both: split is "
                "shorthand for the int8-early/fp32-late prefix assignment"
            )
        if not 0 <= split <= n:
            raise ValueError(
                f"split={split} outside [0, {n}] for a {n}-layer stack"
            )
        wds = ("int8",) * split + ("fp32",) * (n - split)
        sources["weight_dtype"] = "explicit"
    elif isinstance(weight_dtype, tuple):
        if len(weight_dtype) != n:
            raise ValueError(
                f"per-layer weight_dtype needs one entry per layer ({n}); "
                f"got {len(weight_dtype)}"
            )
        wds = weight_dtype
        sources["weight_dtype"] = "explicit"
    elif weight_dtype is not None:
        wds = (weight_dtype,) * n
        sources["weight_dtype"] = "explicit"

    if tune == "cached":
        from repro.autotune.cache import lookup_tuned

        tuned = lookup_tuned(cfgs, "mixed", weight_dtype) or {}
        for k, v in (("chunk_len", chunk_len), ("block_b", block_b),
                     ("fuse_gates", fuse_gates)):
            if v is None and tuned.get(k) is not None:
                sources[k] = "tuned"
        chunk_len = chunk_len if chunk_len is not None else tuned.get("chunk_len")
        block_b = block_b if block_b is not None else tuned.get("block_b")
        fuse_gates = (
            fuse_gates if fuse_gates is not None else tuned.get("fuse_gates")
        )
        if wds is None and tuned.get("split") is not None:
            split = int(tuned["split"])
            if 0 <= split <= n:
                wds = ("int8",) * split + ("fp32",) * (n - split)
                sources["split"] = sources["weight_dtype"] = "tuned"
            else:  # stale entry for a different depth: ignore, keep defaults
                split = None

    if wds is None:
        if tune == "balanced":
            from .stage_balance import choose_mixed_split

            choice = choose_mixed_split(cfgs)
            wds = tuple(choice.dtypes)
            split = choice.split
            sources["split"] = sources["weight_dtype"] = "balanced"
        else:
            from repro.kernels.lstm_stack.ops import resolve_weight_dtype

            wds = tuple(resolve_weight_dtype(c) for c in cfgs)

    return _plan_mixed_cached(
        cfgs, wds,
        _normalize_per_layer("chunk_len", chunk_len, n),
        _normalize_per_layer("block_b", block_b, n),
        _normalize_per_layer("fuse_gates", fuse_gates, n),
        act_bits, split, tuple(sorted(sources.items())),
    )


def plan_stack(cfgs: Sequence[LstmConfig], impl: str = "split", *,
               weight_dtype=None, placement: str = "local",
               mesh=None, n_chunks: int | None = None,
               chunk_len=None, block_b=None,
               fuse_gates=None, act_bits: int | None = None,
               split: int | None = None,
               tune: str = "default") -> StackPlan:
    """Resolve an execution plan for a stacked LSTM segment — exactly once.

    All impl-dependent legality lives here (plan time), not at call time:
    unknown backends, quantized storage on a non-fused backend, storage
    wider than compute, heterogeneous fused segments, non-divisible
    sharded stage splits, ``act_bits`` on a backend without in-kernel
    activation quant, and a knob on a backend that does not declare it
    (``chunk_len``/``block_b``/``fuse_gates``/``n_chunks``/``split`` — see
    ``BackendSpec.knobs``) all raise *now*.  Plans are cached on their
    full argument tuple, so hot paths (including the deprecated
    ``lstm_stack_forward`` shim) re-resolve nothing.

    ``impl="mixed"`` accepts per-layer heterogeneity: ``weight_dtype`` may
    be a per-layer sequence (as may ``chunk_len``/``block_b``/
    ``fuse_gates``), ``split=k`` is shorthand for int8 layers [0, k) and
    fp32 for the rest, and ``tune="balanced"`` asks the fitted roofline
    model to choose the split that equalizes per-segment predicted cost
    (``core.stage_balance.choose_mixed_split``).  The plan carries one
    ordinary ``fused_step`` sub-plan per maximal homogeneous run in
    ``StackPlan.segments``; execution chains them through native-layout
    state hand-off, bit-equal to hand-chaining the segments.

    ``act_bits`` turns on in-kernel fake-quant of the layer hand-off
    activations (the paper fixes activations to 16 bits with an fp32 cell
    carry); only backends with the ``act_quant`` capability accept it.

    ``tune="cached"`` consults the autotune store
    (``repro.autotune.cache``) for measured-best knobs keyed by (geometry,
    backend, weight dtype, device fingerprint): any knob not passed
    explicitly resolves from the cache when an entry exists, falling back
    to the deterministic hand-set defaults otherwise — a missing or stale
    cache can never change behaviour, only speed.  Explicit knob arguments
    always win (manual pinning).  The resolution is recorded per knob in
    ``StackPlan.knob_sources`` ("explicit" | "tuned" | "default" |
    "balanced") so ``--plan-only`` can audit what a serving engine will
    actually run.
    """
    if tune not in ("default", "cached", "balanced"):
        raise ValueError(
            f"unknown tune mode {tune!r}; choose 'default' (hand-set knob "
            "defaults), 'cached' (consult the autotune store) or "
            "'balanced' (mixed plans: roofline-model split)"
        )
    if isinstance(weight_dtype, list):
        weight_dtype = tuple(weight_dtype)
    if get_backend(impl).heterogeneous:
        return _plan_mixed(
            tuple(cfgs), weight_dtype, placement, mesh, n_chunks,
            chunk_len, block_b, fuse_gates, act_bits, split, tune,
        )
    if any(isinstance(v, (tuple, list))
           for v in (weight_dtype, chunk_len, block_b, fuse_gates)):
        raise ValueError(
            "per-layer knob sequences (weight_dtype/chunk_len/block_b/"
            f"fuse_gates) require impl='mixed'; got impl={impl!r}"
        )
    if split is not None:
        raise ValueError(
            f"split= is the mixed backend's per-layer storage knob; got "
            f"impl={impl!r}"
        )
    if tune == "balanced":
        raise ValueError(
            "tune='balanced' chooses a per-layer storage split, which only "
            f"impl='mixed' can execute; got impl={impl!r}"
        )
    knobs = {"chunk_len": chunk_len, "block_b": block_b,
             "fuse_gates": fuse_gates, "n_chunks": n_chunks}
    sources = {
        k: ("explicit" if v is not None else "default")
        for k, v in knobs.items()
    }
    if act_bits is not None:
        sources["act_bits"] = "explicit"
    if tune == "cached" and cfgs:
        from repro.autotune.cache import lookup_tuned

        tuned = lookup_tuned(cfgs, impl, weight_dtype)
        if tuned:
            for k in _TUNABLE_KNOBS:
                if k not in knobs:
                    continue
                v = tuned.get(k)
                if v is not None and knobs[k] is None:
                    knobs[k] = v
                    sources[k] = "tuned"
    return _plan_stack_cached(
        tuple(cfgs), impl, weight_dtype, placement, mesh,
        knobs["n_chunks"], knobs["chunk_len"], knobs["block_b"],
        knobs["fuse_gates"], act_bits, tuple(sorted(sources.items())),
    )


def clear_plan_cache() -> None:
    """Drop memoized plans.  Not required for correctness after mutating
    the autotune store — ``plan_stack`` resolves tuned knobs *before* the
    memo, so a new cache entry simply produces a new memo key — but tests
    and long sweeps use it to keep plan identities fresh and bounded."""
    _plan_stack_cached.cache_clear()
    _plan_mixed_cached.cache_clear()


# ---------------------------------------------------------------------------
# StackExecutor — bound and ready to run
# ---------------------------------------------------------------------------

class StackExecutor:
    """A plan bound to parameters: the only call-time surface.

    Registered as a pytree — ``params``/``packed`` are leaves, the plan is
    static — so engines pass executors through ``jax.jit`` boundaries and
    donate state without re-tracing.  Construct via ``StackPlan.bind``.
    """

    __slots__ = ("plan", "params", "packed", "_jit_steps", "_subs")

    def __init__(self, plan: StackPlan, params: tuple,
                 packed: Any = None) -> None:
        self.plan = plan
        self.params = params
        self.packed = packed
        # bind-time cache for the jitted step callables (see ``step_jit``);
        # never a pytree leaf — rebuilt lazily after unflatten
        self._jit_steps: dict[bool, Any] = {}
        # lazy per-segment sub-executors (mixed plans only)
        self._subs: tuple | None = None

    def _segment_executors(self) -> tuple["StackExecutor", ...]:
        """One ordinary homogeneous executor per mixed-plan segment, over
        this executor's own param/pack slices (cheap object construction —
        safe to rebuild after pytree unflatten, including in-trace)."""
        subs = self._subs
        if subs is None:
            built, i = [], 0
            for sp, pk in zip(self.plan.segments, self.packed or ()):
                n = sp.n_layers
                built.append(StackExecutor(sp, self.params[i:i + n], pk))
                i += n
            subs = self._subs = tuple(built)
        return subs

    # -- full-sequence execution -------------------------------------------

    def __call__(self, xs: jax.Array, initial_state=None, *,
                 return_state: bool = True):
        """Run the segment. xs: (B, T, in_dim) -> (B, T, hidden[-1]).

        ``initial_state``/finals are the portable per-layer
        ``[(h, c), ...]`` at real widths — identical across backends, so
        feeding one backend's finals as another's initial state is exact.
        """
        h_seq, finals = self.plan.backend.forward(self, xs, initial_state)
        if not return_state:
            return h_seq
        if finals is None:
            raise ValueError(
                f"impl={self.plan.impl!r} does not thread per-layer state; "
                "call with return_state=False (and no initial_state)"
            )
        return h_seq, finals

    # -- streaming-serving hot path (backend-native state layout) ----------

    def _require_stateful(self) -> None:
        if not self.plan.backend.stateful:
            raise ValueError(
                f"impl={self.plan.impl!r} does not thread per-layer state; "
                "the streaming surfaces (zero_state/step/last_hidden) need "
                "a stateful backend such as 'fused_stack'"
            )

    def zero_state(self, batch: int):
        """Backend-native zero state in the registered ``state_layout``
        ("packed": the bound stack's (L, B, W) pair; "layers": per-layer
        [(h, c), ...] at real widths) — the layout ``step`` carries,
        donation-friendly."""
        self._require_stateful()
        plan = self.plan
        if plan.impl == IDENTITY:
            return []
        if plan.backend.heterogeneous:
            return tuple(pk.zero_state(batch) for pk in self.packed)
        if plan.backend.state_layout == "packed":
            return self.packed.zero_state(batch)
        return [layer_zero_state(batch, c) for c in plan.cfgs]

    def step(self, xs: jax.Array, state):
        """Advance native state by one chunk; returns only the new state
        (the streaming engines' per-push call — no hidden sequence
        materialized for the caller).  Dispatches on the backend's
        registered ``step`` hook; backends without one run their
        ``forward`` with portable state."""
        self._require_stateful()
        plan = self.plan
        if plan.impl == IDENTITY:
            return state
        spec = plan.backend
        if spec.step is not None:
            return spec.step(self, xs, state)
        _, finals = spec.forward(self, xs, state)
        return finals

    def step_with_output(self, xs: jax.Array, state):
        """``step`` that also returns the last layer's hidden sequence at
        real width — the segment hand-off the mixed backend chains
        (``(h_seq (B, T, hidden[-1]), new native state)``).  Same kernels
        and routing as ``step``, so chaining homogeneous executors through
        this surface is bit-equal to running them standalone."""
        self._require_stateful()
        plan = self.plan
        if plan.impl == IDENTITY:
            return xs, state
        spec = plan.backend
        if spec.heterogeneous:
            return _mixed_seq_call(self, xs, state)
        if spec.state_layout == "packed":
            if plan.placement == "sharded":
                h, c = state
                hs, h_f, c_f = _sharded_call(self, xs, h, c)
            else:
                hs, h_f, c_f = _fused_seq_call(self, xs, state)
            return hs[..., : plan.hidden[-1]], (h_f, c_f)
        # layer-by-layer backends: portable state IS native state
        h_seq, finals = spec.forward(self, xs, state)
        return h_seq, finals

    def step_jit(self, donate: bool = True):
        """The executor's own jitted ``step`` — cached at the executor, so a
        serving engine binds once and calls a plain ``fn(xs, state)``.

        Routing ``step`` through a jit that takes the *executor* as a pytree
        argument pays a per-call flatten/hash of the whole plan + every
        param/pack leaf — measured at ~1.46x a direct kernel call
        (``exec.dispatch_ratio``).  Here the bound arrays are closed over
        (jit constants), so per-call dispatch flattens only ``(xs, state)``
        — the same cost as jitting the kernel call by hand
        (``exec.step_dispatch_ratio`` gates this at <= 1.10x).

        ``donate=True`` donates the state argument: with the kernel's
        h0->h_f/c0->c_f aliasing, steady-state streaming allocates no new
        state.  Callables are cached per ``donate`` flag; a params swap
        goes through ``update_params``/``bind``, which returns a *new*
        executor with an empty cache — stale weights can never be served.
        """
        self._require_stateful()
        fn = self._jit_steps.get(donate)
        if fn is None:
            fn = jax.jit(
                lambda xs, state: self.step(xs, state),
                donate_argnums=(1,) if donate else (),
            )
            self._jit_steps[donate] = fn
        return fn

    def last_hidden(self, state) -> jax.Array:
        """Last layer's current hidden at real width — the latent the GW
        autoencoder's RepeatVector bridge consumes."""
        self._require_stateful()
        plan = self.plan
        if plan.impl == IDENTITY:
            raise ValueError("identity executor has no hidden state")
        if plan.backend.heterogeneous:
            h, _ = state[-1]
            return h[-1, :, : plan.hidden[-1]]
        if plan.backend.state_layout == "packed":
            h, _ = state
            return h[-1, :, : plan.hidden[-1]]
        return state[-1][0]

    # -- lifecycle ----------------------------------------------------------

    def update_params(self, params_list: Sequence[Params]) -> "StackExecutor":
        """Re-bind on new parameters and evict this executor's superseded
        pack from the identity cache (long-lived servers must not leak
        strong refs to dead param leaves)."""
        new = self.plan.bind(params_list)
        if self.packed is not None:
            from repro.kernels.lstm_stack.ops import pack_cache_evict

            old = (self.packed if isinstance(self.packed, tuple)
                   else (self.packed,))
            cur = (new.packed if isinstance(new.packed, tuple)
                   else (new.packed,))
            stale = [p for p in old if all(p is not q for q in cur)]
            if stale:
                pack_cache_evict(*stale)
        return new

    @property
    def packed_bytes(self) -> int:
        """Bytes the bound pack occupies (0 for non-packing backends);
        mixed executors sum their per-segment packs."""
        if self.packed is None:
            return 0
        if isinstance(self.packed, tuple):
            return sum(p.packed_bytes for p in self.packed)
        return self.packed.packed_bytes

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"StackExecutor({self.plan.describe()})"


jax.tree_util.register_pytree_node(
    StackExecutor,
    lambda ex: ((ex.params, ex.packed), ex.plan),
    lambda plan, ch: StackExecutor(plan, ch[0], ch[1]),
)


# ---------------------------------------------------------------------------
# backend forward implementations
# ---------------------------------------------------------------------------

def _forward_identity(ex: StackExecutor, xs, state):
    return xs, (state if state is not None else [])


def _forward_layerwise(ex: StackExecutor, xs, state):
    h_seq, finals = xs, []
    for i, (p, cfg) in enumerate(zip(ex.params, ex.plan.cfgs)):
        s = None if state is None else state[i]
        h_seq, final = lstm_forward(p, h_seq, cfg, s, impl=ex.plan.impl)
        finals.append(final)
    return h_seq, finals


def _forward_fused(ex: StackExecutor, xs, state):
    from repro.kernels.lstm_stack.ops import lstm_stack_forward_fused

    # bind() already validated the pack against the plan's cfgs; the helper
    # is the single fused dispatch shared with the deprecated shim
    return lstm_stack_forward_fused(
        list(ex.params), xs, list(ex.plan.cfgs), state, packed=ex.packed,
        block_b=ex.plan.block_b, act_bits=ex.plan.act_bits,
    )


def _resolve_n_chunks(plan: StackPlan, t_len: int) -> int:
    n_stages = plan.mesh.shape["stage"]
    if plan.n_chunks is not None:
        if t_len % plan.n_chunks:
            raise ValueError(
                f"n_chunks={plan.n_chunks} does not divide T={t_len}"
            )
        return plan.n_chunks
    # auto: one chunk per stage keeps the wavefront balanced; fall back to
    # a single chunk (coarse hand-off) when T does not split evenly
    return n_stages if t_len % n_stages == 0 else 1


def _sharded_call(ex: StackExecutor, xs, h0, c0):
    from repro.core.pipeline import wavefront_shard_map_fused

    packed = ex.packed
    return wavefront_shard_map_fused(
        packed, packed.pad_input(xs), h0, c0,
        n_chunks=_resolve_n_chunks(ex.plan, xs.shape[1]),
        mesh=ex.plan.mesh,
    )


def _forward_sharded(ex: StackExecutor, xs, state):
    packed = ex.packed
    if state is None:
        h0, c0 = packed.zero_state(xs.shape[0])
    else:
        h0, c0 = packed.pack_state(state)
    hs, h_f, c_f = _sharded_call(ex, xs, h0, c0)
    return hs[..., : packed.hidden[-1]], packed.unpack_state(h_f, c_f)


def _fused_seq_call(ex: StackExecutor, xs, state):
    """The plan-routed local fused kernel call, keeping the hidden sequence:
    (hs (B, T, W_padded), h_f, c_f).  Chunked-step plans route short chunks
    to the step kernel exactly as ``_step_chunked`` does — the T comparison
    is static (shape), so each jit trace contains exactly one kernel."""
    plan = ex.plan
    h, c = state
    if plan.backend.chunked_step and xs.shape[1] <= plan.chunk_len:
        from repro.kernels.lstm_stack.step import lstm_stack_step_op

        return lstm_stack_step_op(
            ex.packed.pad_input(xs), ex.packed.stacked, h, c,
            acts=ex.packed.acts, weight_dtype=ex.packed.weight_dtype,
            block_b=plan.block_b, fuse_gates=plan.fuse_gates,
            act_bits=plan.act_bits,
        )
    from repro.kernels.lstm_stack.ops import lstm_stack_op

    return lstm_stack_op(
        ex.packed.pad_input(xs), ex.packed.stacked, h, c,
        acts=ex.packed.acts, weight_dtype=ex.packed.weight_dtype,
        block_b=plan.block_b, act_bits=plan.act_bits,
    )


def _step_fused(ex: StackExecutor, xs, state):
    _, h_f, c_f = _fused_seq_call(ex, xs, state)
    return h_f, c_f


def _step_chunked(ex: StackExecutor, xs, state):
    """fused_step's hot path: short chunks hit the step kernel (one grid
    step, in-kernel layer-0 mvm_x, no time-major transpose); anything
    longer than the plan's chunk_len falls back to the wavefront kernel.
    The routing lives in ``_fused_seq_call`` (shared with the mixed
    backend's segment hand-off)."""
    _, h_f, c_f = _fused_seq_call(ex, xs, state)
    return h_f, c_f


def _step_sharded(ex: StackExecutor, xs, state):
    h, c = state
    _, h_f, c_f = _sharded_call(ex, xs, h, c)
    return h_f, c_f


def _mixed_seq_call(ex: StackExecutor, xs, state):
    """Chain the mixed plan's segments through native-layout hand-off:
    each segment's real-width hidden sequence feeds the next segment's
    ``pad_input``.  Returns (last segment's h_seq, tuple of new per-segment
    native states)."""
    h_seq, new = xs, []
    for sub, st in zip(ex._segment_executors(), state):
        h_seq, st_new = sub.step_with_output(h_seq, st)
        new.append(st_new)
    return h_seq, tuple(new)


def _forward_mixed(ex: StackExecutor, xs, state):
    """Batch path: chain segment ``__call__``s with portable per-layer
    state slices — identical to hand-chaining the homogeneous segments."""
    h_seq, finals, i = xs, [], 0
    for sub in ex._segment_executors():
        n = sub.plan.n_layers
        s = None if state is None else list(state[i:i + n])
        h_seq, f = sub(h_seq, s)
        finals.extend(f)
        i += n
    return h_seq, finals


def _step_mixed(ex: StackExecutor, xs, state):
    _, new = _mixed_seq_call(ex, xs, state)
    return new


def _forward_wavefront(ex: StackExecutor, xs, state):
    from repro.core.pipeline import pack_uniform, wavefront

    if state is not None:
        raise ValueError(
            "impl='wavefront' does not thread state; use 'fused_stack' (or "
            "a layer-by-layer backend) for the streaming path"
        )
    cfgs = ex.plan.cfgs
    # exact max-width pack (NOT the Pallas lane-rounded PackedStack: the
    # XLA-level wavefront gains nothing from 128-lane padding and would pay
    # its FLOPs — W=128 vs W=32 is ~16x on the nominal GW stack)
    stacked, width = pack_uniform(
        list(ex.params), [c.in_dim for c in cfgs], [c.hidden for c in cfgs]
    )
    xs_p = jnp.pad(xs, ((0, 0), (0, 0), (0, width - xs.shape[-1])))
    n_chunks = ex.plan.n_chunks if ex.plan.n_chunks is not None else 1
    out = wavefront(stacked, xs_p, n_chunks, cfgs[0].acts)
    return out[..., : cfgs[-1].hidden], None


register_backend(BackendSpec(
    name=IDENTITY, forward=_forward_identity))
register_backend(BackendSpec(
    name="naive", forward=_forward_layerwise))
register_backend(BackendSpec(
    name="split", forward=_forward_layerwise))
register_backend(BackendSpec(
    name="kernel", kernel_acts=True, forward=_forward_layerwise))
register_backend(BackendSpec(
    name="fused_stack", packs=True, quantized=True, kernel_acts=True,
    state_layout="packed", act_quant=True, knobs=("block_b",),
    forward=_forward_fused, step=_step_fused))
register_backend(BackendSpec(
    name="fused_step", packs=True, quantized=True, kernel_acts=True,
    state_layout="packed", chunked_step=True, act_quant=True,
    knobs=("chunk_len", "block_b", "fuse_gates"),
    forward=_forward_fused, step=_step_chunked))
register_backend(BackendSpec(
    name="mixed", packs=True, quantized=True, kernel_acts=True,
    state_layout="packed", chunked_step=True, act_quant=True,
    heterogeneous=True,
    knobs=("chunk_len", "block_b", "fuse_gates", "split"),
    forward=_forward_mixed, step=_step_mixed))
register_backend(BackendSpec(
    name="fused_stack_sharded", packs=True, quantized=True,
    kernel_acts=True, sharded=True, state_layout="packed",
    knobs=("n_chunks",),
    forward=_forward_sharded, step=_step_sharded))
register_backend(BackendSpec(
    name="wavefront", stateful=False, knobs=("n_chunks",),
    forward=_forward_wavefront))
