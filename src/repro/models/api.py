"""Uniform model API: family dispatch + input_specs for every (arch x shape).

``get_model(cfg)`` returns a ``ModelApi`` with the five entry points every
family implements; ``input_specs(cfg, shape)`` builds the ShapeDtypeStruct
stand-ins the dry-run lowers against (weak-type-correct, shardable, zero
allocation).  ``make_abstract_state`` builds abstract params/optimizer/cache
pytrees via ``jax.eval_shape`` so 132B-parameter models can be lowered on a
CPU host.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, InputShape
from repro.models import encdec, hybrid, moe, seq_hybrid, ssm, transformer
from repro.models.encdec import ENC_LEN_DECODE


@dataclass(frozen=True)
class ModelApi:
    family: str
    init_params: Callable
    loss_fn: Callable          # (params, batch, cfg, ctx) -> scalar
    forward: Callable          # (params, batch, cfg, ctx) -> logits
    prefill: Callable          # (params, batch, cfg, max_len, ctx) -> (logits, cache)
    decode_step: Callable      # (params, cache, batch, cfg, ctx) -> (logits, cache)
    init_cache: Callable       # (cfg, batch, max_len) -> cache


_FAMILIES = {
    "dense": transformer,
    "moe": moe,
    "ssm": ssm,
    "hybrid": hybrid,
    "seq_hybrid": seq_hybrid,
    "encdec": encdec,
}


def get_model(cfg: ArchConfig) -> ModelApi:
    mod = _FAMILIES[cfg.family]

    def _forward(params, batch, cfg, ctx=None, **kw):
        out = mod.forward(params, batch, cfg, *( (ctx,) if ctx is not None else () ), **kw)
        return out[0] if isinstance(out, tuple) else out

    return ModelApi(
        family=cfg.family,
        init_params=mod.init_params,
        loss_fn=mod.loss_fn,
        forward=_forward,
        prefill=mod.prefill,
        decode_step=mod.decode_step,
        init_cache=mod.init_cache,
    )


# ---------------------------------------------------------------------------
# input specs (the dry-run contract)
# ---------------------------------------------------------------------------

def _tok(shape) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def input_specs(cfg: ArchConfig, shape: InputShape) -> dict:
    """ShapeDtypeStruct stand-ins for one (arch x shape) cell.

    train:   {"tokens", "labels"} (+ frontend embeds for vlm/audio)
    prefill: {"tokens"} (+ frontend embeds)
    decode:  {"tokens": (B, 1)} — the cache is built separately
             (``abstract_cache``) because it is carried state, not input.
    """
    b, s = shape.global_batch, shape.seq_len
    emb = jnp.bfloat16 if cfg.dtype == jnp.bfloat16 else jnp.float32

    if shape.kind == "train":
        if cfg.encdec:
            # half the budget to the encoder (frames), half to the decoder
            se, sd = s // 2, s // 2
            return {
                "frontend_embeds": jax.ShapeDtypeStruct((b, se, cfg.d_model), emb),
                "tokens": _tok((b, sd)),
                "labels": _tok((b, sd)),
            }
        if cfg.frontend is not None:
            p = cfg.frontend_tokens
            return {
                "frontend_embeds": jax.ShapeDtypeStruct((b, p, cfg.d_model), emb),
                "tokens": _tok((b, s - p)),
                "labels": _tok((b, s - p)),
            }
        return {"tokens": _tok((b, s)), "labels": _tok((b, s))}

    if shape.kind == "prefill":
        if cfg.encdec:
            se, sd = s // 2, s // 2
            return {
                "frontend_embeds": jax.ShapeDtypeStruct((b, se, cfg.d_model), emb),
                "tokens": _tok((b, sd)),
            }
        if cfg.frontend is not None:
            p = cfg.frontend_tokens
            return {
                "frontend_embeds": jax.ShapeDtypeStruct((b, p, cfg.d_model), emb),
                "tokens": _tok((b, s - p)),
            }
        return {"tokens": _tok((b, s))}

    if shape.kind == "decode":
        return {"tokens": _tok((b, 1))}

    raise ValueError(shape.kind)


def abstract_params(cfg: ArchConfig, seed: int = 0):
    """Parameter pytree as ShapeDtypeStructs (zero allocation)."""
    api = get_model(cfg)
    return jax.eval_shape(lambda: api.init_params(jax.random.PRNGKey(seed), cfg))


def abstract_cache(cfg: ArchConfig, shape: InputShape):
    api = get_model(cfg)
    return jax.eval_shape(
        lambda: api.init_cache(cfg, shape.global_batch, shape.seq_len)
    )
