"""LSTM autoencoder for gravitational-wave anomaly detection (paper Sec. III-A).

Structure (Moreno et al. / paper Fig. 3):

    encoder : LSTM(in -> h0) -> ... -> LSTM(-> h_latent)   [last layer returns
                                                            only the final h]
    bridge  : RepeatVector(T)                               [hard sync point]
    decoder : LSTM(latent -> ...) -> LSTM(-> h_last)        [return sequences]
    head    : TimeDistributed Dense(h_last -> in)

Trained unsupervised on detector background; an event is flagged anomalous
when the reconstruction error spikes.  The encoder->decoder boundary is the
pipeline sync point modelled by ``ii_model.Segment`` — only the final latent
crosses, so decoder timestep overlap cannot begin before the encoder drains
(paper Sec. III-D).

The nominal model is hidden=(32, 8, 8, 32) with a 1-d strain input; the small
model is hidden=(9, 9).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from .lstm import LstmConfig, init_lstm
from .quant import EXACT, ActivationSet

Params = dict[str, Any]


@dataclass(frozen=True)
class AutoencoderConfig:
    input_dim: int = 1
    hidden: tuple[int, ...] = (32, 8, 8, 32)
    latent_boundary: int | None = None  # index of first decoder layer
    timesteps: int = 100                # paper default TS for accuracy studies
    dtype: Any = jnp.float32
    cell_dtype: Any = jnp.float32
    acts: ActivationSet = EXACT
    impl: str = "split"                 # naive | split | kernel | fused_stack
    #: fused-stack weight storage: "fp32" | "bf16" | "int8" (None = native at
    #: ``dtype``).  The encoder and decoder are separate packed segments, so
    #: ``dec_weight_dtype`` may override the decoder independently (None =
    #: same as ``weight_dtype``) — e.g. int8 encoder, fp32 decoder head.
    weight_dtype: str | None = None
    dec_weight_dtype: str | None = None
    #: per-LAYER weight storage (one entry per ``hidden`` layer; None entries
    #: fall back to the segment-level fields above).  More than one distinct
    #: storage inside a segment needs ``impl="mixed"`` — the heterogeneous
    #: backend chains homogeneous sub-plans; every other backend packs one
    #: dtype per segment and refuses at plan time.
    weight_dtypes: tuple[str | None, ...] | None = None
    #: in-kernel activation fake-quant on layer hand-offs (paper: 16-bit
    #: activations, fp32 cell carry); plan-time knob of the fused backends
    act_bits: int | None = None

    def __post_init__(self) -> None:
        if self.weight_dtypes is not None and len(self.weight_dtypes) != len(
            self.hidden
        ):
            raise ValueError(
                f"weight_dtypes needs one entry per hidden layer "
                f"({len(self.hidden)}); got {len(self.weight_dtypes)}"
            )

    @property
    def boundary(self) -> int:
        return (
            self.latent_boundary
            if self.latent_boundary is not None
            else len(self.hidden) // 2
        )

    def layer_cfgs(self) -> list[LstmConfig]:
        cfgs, lx = [], self.input_dim
        dec_wd = (
            self.dec_weight_dtype
            if self.dec_weight_dtype is not None
            else self.weight_dtype
        )
        for i, h in enumerate(self.hidden):
            # the first decoder layer consumes the repeated latent
            if i == self.boundary:
                lx = self.hidden[self.boundary - 1]
            wd = self.weight_dtype if i < self.boundary else dec_wd
            if self.weight_dtypes is not None and self.weight_dtypes[i] is not None:
                wd = self.weight_dtypes[i]
            cfgs.append(
                LstmConfig(
                    in_dim=lx, hidden=h, dtype=self.dtype,
                    cell_dtype=self.cell_dtype, acts=self.acts,
                    weight_dtype=wd,
                )
            )
            lx = h
        return cfgs


GW_NOMINAL_CONFIG = AutoencoderConfig(hidden=(32, 8, 8, 32))
GW_SMALL_CONFIG = AutoencoderConfig(hidden=(9, 9), latent_boundary=1)


def init_autoencoder(key: jax.Array, cfg: AutoencoderConfig) -> Params:
    cfgs = cfg.layer_cfgs()
    keys = jax.random.split(key, len(cfgs) + 1)
    params: Params = {
        f"lstm_{i}": init_lstm(k, c) for i, (k, c) in enumerate(zip(keys, cfgs))
    }
    lim = (6.0 / (cfg.hidden[-1] + cfg.input_dim)) ** 0.5
    params["dense"] = {
        "w": jax.random.uniform(
            keys[-1], (cfg.hidden[-1], cfg.input_dim), jnp.float32, -lim, lim
        ).astype(cfg.dtype),
        "b": jnp.zeros((cfg.input_dim,), jnp.float32),
    }
    return params


#: per-segment streaming state: per-layer [(h, c), ...] at real widths
SegmentState = list


def encoder_layers(params: Params, cfg: AutoencoderConfig):
    cfgs = cfg.layer_cfgs()[: cfg.boundary]
    return [params[f"lstm_{i}"] for i in range(cfg.boundary)], cfgs


def decoder_layers(params: Params, cfg: AutoencoderConfig):
    cfgs = cfg.layer_cfgs()
    return (
        [params[f"lstm_{i}"] for i in range(cfg.boundary, len(cfgs))],
        cfgs[cfg.boundary :],
    )


def _segment_executor(
    params: Params, cfg: AutoencoderConfig, segment: str,
    *, placement: str = "local", mesh: Any = None, impl: str | None = None,
    chunk_len: int | None = None, tune: str = "default",
):
    """Plan + bind ONE segment ("enc" | "dec") — encode/decode build only
    the executor they run, so a one-shot forward never packs the other
    segment's weights into its trace."""
    from .executor import plan_stack

    plist, cfgs = (
        encoder_layers(params, cfg) if segment == "enc"
        else decoder_layers(params, cfg)
    )
    impl = cfg.impl if impl is None else impl
    return plan_stack(
        cfgs, impl=impl, placement=placement, mesh=mesh,
        chunk_len=chunk_len, act_bits=cfg.act_bits, tune=tune,
    ).bind(plist)


def segment_executors(
    params: Params, cfg: AutoencoderConfig,
    *, placement: str = "local", mesh: Any = None, impl: str | None = None,
    chunk_len: int | None = None, tune: str = "default",
):
    """(encoder, decoder) ``StackExecutor``s for an autoencoder config.

    The one place the autoencoder turns configs into execution: both
    segments get their own plan (they pack independently — the sync
    boundary between them is the ``ii_model.Segment`` semantics) and are
    bound once per params identity.  Serving engines call this at init and
    pass the executors through their jitted steps; one-shot callers get the
    same executors implicitly via ``encode``/``decode``.
    """
    kw = dict(placement=placement, mesh=mesh, impl=impl,
              chunk_len=chunk_len, tune=tune)
    return (
        _segment_executor(params, cfg, "enc", **kw),
        _segment_executor(params, cfg, "dec", **kw),
    )


def encode(
    params: Params, x: jax.Array, cfg: AutoencoderConfig,
    initial_state: SegmentState | None = None,
    *, return_state: bool = False, executor: Any = None,
) -> Any:
    """Run the encoder segment. x: (B, T, input_dim) -> (B, T, h_enc_last).

    ``initial_state``/``return_state`` thread the per-layer (h, c) finals
    so a streaming caller can push a window chunk-by-chunk: the encoder is
    causal, so K chunked calls that carry state equal one full-window call.
    ``executor`` is an optional pre-bound ``StackExecutor`` (the serve path
    binds once at engine init); default: plan from ``cfg.impl`` per call.
    """
    if executor is None:
        executor = _segment_executor(params, cfg, "enc")
    return executor(x, initial_state, return_state=return_state)


def decode(
    params: Params, latent: jax.Array, cfg: AutoencoderConfig,
    t: int | None = None,
    initial_state: SegmentState | None = None,
    *, return_state: bool = False, executor: Any = None,
) -> Any:
    """Decoder segment + dense head. latent: (B, h_latent) -> (B, T, input_dim).

    The bridge (RepeatVector) feeds the latent to every decoder timestep,
    so decoding needs only the latent and a length — the streaming engine
    calls this once per completed window.  The executor takes the latent
    with the length (``timesteps``), so the fused kernel projects it once
    per window instead of once per step.
    """
    t = cfg.timesteps if t is None else t
    if executor is None:
        executor = _segment_executor(params, cfg, "dec")
    out = executor(latent, initial_state, return_state=return_state,
                   timesteps=t)
    h_seq, finals = out if return_state else (out, None)
    # ---- TimeDistributed dense head ----------------------------------------
    # full precision: on the TPU a default fp32 dot is one bf16 pass
    rec = jnp.dot(
        h_seq.astype(cfg.dtype), params["dense"]["w"],
        precision=jax.lax.Precision.HIGHEST,
    ) + params["dense"]["b"]
    return (rec, finals) if return_state else rec


def autoencoder_forward(
    params: Params, x: jax.Array, cfg: AutoencoderConfig,
    *, exec_enc: Any = None, exec_dec: Any = None,
) -> jax.Array:
    """Reconstruct x. x: (B, T, input_dim) -> (B, T, input_dim).

    ``exec_enc``/``exec_dec`` are optional pre-bound ``StackExecutor``s for
    the two segments (the serve path binds once at engine init).
    """
    # The encoder->decoder bottleneck is the ii_model.Segment sync boundary:
    # only the final latent crosses, so each segment runs (and, under
    # impl="fused_stack", wavefront-fuses) independently.
    h_seq = encode(params, x, cfg, executor=exec_enc)
    # bottleneck: only the last hidden vector crosses (RepeatVector)
    latent = h_seq[:, -1, :]
    rec = decode(params, latent, cfg, t=x.shape[1], executor=exec_dec)
    return rec.astype(x.dtype)


def reconstruction_error_from_latent(
    params: Params, latent: jax.Array, x: jax.Array, cfg: AutoencoderConfig,
    *, exec_dec: Any = None,
) -> jax.Array:
    """Anomaly score given an already-computed latent: decode + fp32 MSE
    against x.  The single definition of the score tail — one-shot scoring
    and the streaming engine (whose latent comes from resident encoder
    state) must agree bit-for-bit, so both route through here. (B,)"""
    rec = decode(
        params, latent, cfg, t=x.shape[1], executor=exec_dec
    ).astype(x.dtype)
    err = (rec.astype(jnp.float32) - x.astype(jnp.float32)) ** 2
    return jnp.mean(err, axis=(1, 2))


def reconstruction_error(
    params: Params, x: jax.Array, cfg: AutoencoderConfig,
    *, exec_enc: Any = None, exec_dec: Any = None,
) -> jax.Array:
    """Per-example anomaly score: mean squared reconstruction error. (B,)"""
    h_seq = encode(params, x, cfg, executor=exec_enc)
    return reconstruction_error_from_latent(
        params, h_seq[:, -1, :], x, cfg, exec_dec=exec_dec
    )


def mse_loss(params: Params, x: jax.Array, cfg: AutoencoderConfig) -> jax.Array:
    return jnp.mean(reconstruction_error(params, x, cfg))


def auc_score(scores_neg: jnp.ndarray, scores_pos: jnp.ndarray) -> float:
    """AUC via the Mann-Whitney U statistic (threshold-free, like the paper).

    ``scores_pos`` are anomaly scores on signal (GW) events, ``scores_neg``
    on background; AUC = P(score_pos > score_neg) + 0.5 P(tie).
    """
    import numpy as np

    neg = np.asarray(scores_neg, dtype=np.float64)
    pos = np.asarray(scores_pos, dtype=np.float64)
    order = np.concatenate([neg, pos]).argsort(kind="mergesort")
    ranks = np.empty_like(order, dtype=np.float64)
    ranks[order] = np.arange(1, len(order) + 1)
    # average ranks for ties
    allv = np.concatenate([neg, pos])
    sorted_v = allv[order]
    i = 0
    while i < len(sorted_v):
        j = i
        while j + 1 < len(sorted_v) and sorted_v[j + 1] == sorted_v[i]:
            j += 1
        if j > i:
            ranks[order[i : j + 1]] = ranks[order[i : j + 1]].mean()
        i = j + 1
    r_pos = ranks[len(neg) :].sum()
    n_pos, n_neg = len(pos), len(neg)
    return float((r_pos - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg))
