"""Per-architecture smoke tests: reduced config, one forward + train step on
CPU, shape/NaN assertions, and prefill-vs-decode consistency.

Every assigned arch instantiates a REDUCED same-family config (2 layers,
d_model 64, tiny vocab) and must:
  1. run ``forward`` with the right logits shape and no NaNs,
  2. take one gradient step (finite grads),
  3. decode: prefill(prompt) + decode_step == forward(prompt+token) logits.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES
from repro.configs.base import cell_supported
from repro.models.api import get_model, input_specs

ARCH_IDS = sorted(ARCHS)


def _reduced(name):
    return ARCHS[name].reduced()


def _batch_for(cfg, b=2, s=16):
    key = jax.random.PRNGKey(0)
    batch = {}
    if cfg.encdec:
        batch["frontend_embeds"] = jax.random.normal(key, (b, s, cfg.d_model), jnp.float32)
        batch["tokens"] = jax.random.randint(key, (b, s), 0, cfg.vocab)
    elif cfg.frontend is not None:
        p = cfg.frontend_tokens
        batch["frontend_embeds"] = jax.random.normal(key, (b, p, cfg.d_model), jnp.float32)
        batch["tokens"] = jax.random.randint(key, (b, s - p), 0, cfg.vocab)
    else:
        batch["tokens"] = jax.random.randint(key, (b, s), 0, cfg.vocab)
    batch["labels"] = jax.random.randint(jax.random.fold_in(key, 1),
                                         batch["tokens"].shape, 0, cfg.vocab)
    return batch


@pytest.mark.parametrize("name", ARCH_IDS)
class TestArchSmoke:
    def test_forward_shapes_no_nan(self, name):
        cfg = _reduced(name)
        api = get_model(cfg)
        params = api.init_params(jax.random.PRNGKey(0), cfg)
        batch = _batch_for(cfg)
        logits = api.forward(params, batch, cfg)
        b = batch["tokens"].shape[0]
        s_out = batch["tokens"].shape[1] + (
            cfg.frontend_tokens if (cfg.frontend and not cfg.encdec) else 0
        )
        assert logits.shape == (b, s_out, cfg.vocab)
        assert not bool(jnp.any(jnp.isnan(logits.astype(jnp.float32))))

    def test_one_train_step(self, name):
        cfg = _reduced(name)
        api = get_model(cfg)
        params = api.init_params(jax.random.PRNGKey(1), cfg)
        batch = _batch_for(cfg)
        loss, grads = jax.value_and_grad(api.loss_fn)(params, batch, cfg)
        assert jnp.isfinite(loss), f"{name}: loss not finite"
        flat = jax.tree_util.tree_leaves(grads)
        assert all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32)))) for g in flat)
        # apply a tiny SGD step; loss must change (graph is connected)
        params2 = jax.tree_util.tree_map(
            lambda p, g: p - 0.1 * g.astype(p.dtype), params, grads
        )
        loss2 = api.loss_fn(params2, batch, cfg)
        assert float(loss2) != float(loss)

    def test_decode_matches_forward(self, name):
        """prefill(x[:t]) then decode_step(x[t]) must equal forward(x[:t+1])
        at the last position — the KV-cache/state correctness invariant."""
        cfg = _reduced(name)
        api = get_model(cfg)
        params = api.init_params(jax.random.PRNGKey(2), cfg)
        b, s = 2, 16  # leaves text tokens after the vlm frontend splice
        batch = _batch_for(cfg, b=b, s=s)
        full_logits = api.forward(params, batch, cfg)

        prompt = dict(batch)
        prompt.pop("labels")
        prompt["tokens"] = batch["tokens"][:, : s - 1] if not cfg.encdec else batch["tokens"][:, : s - 1]
        if cfg.frontend is not None and not cfg.encdec:
            prompt["tokens"] = batch["tokens"][:, : batch["tokens"].shape[1] - 1]
        logits_pre, cache = api.prefill(params, prompt, cfg, max_len=s + 4)
        np.testing.assert_allclose(
            np.asarray(logits_pre[:, 0], np.float32),
            np.asarray(full_logits[:, -2], np.float32),
            rtol=2e-2, atol=2e-2,
        )
        step_batch = {"tokens": batch["tokens"][:, -1:]}
        logits_dec, cache = api.decode_step(params, cache, step_batch, cfg)
        np.testing.assert_allclose(
            np.asarray(logits_dec[:, 0], np.float32),
            np.asarray(full_logits[:, -1], np.float32),
            rtol=2e-2, atol=2e-2,
        )

    def test_input_specs_wellformed(self, name):
        cfg = ARCHS[name]  # FULL config: specs only, no allocation
        for shape in SHAPES.values():
            ok, reason = cell_supported(cfg, shape)
            if not ok:
                assert reason
                continue
            specs = input_specs(cfg, shape)
            assert "tokens" in specs
            for v in jax.tree_util.tree_leaves(specs):
                assert isinstance(v, jax.ShapeDtypeStruct)
            if shape.kind == "decode":
                assert specs["tokens"].shape == (shape.global_batch, 1)


class TestGrid:
    def test_40_cells(self):
        """The 40 assigned cells, and granite-4.0-h-micro's four."""
        from repro.configs import all_cells

        cells = list(all_cells())
        assert len(cells) == 44
        skipped = [c for c in cells if not c[2]]
        # 8 pure full-attention archs skip long_500k (assignment rule), and
        # granite-4.0-h-micro, whose 4 attention layers are full
        assert len(skipped) == 9
        assert all(c[1].name == "long_500k" for c in skipped)
        sub_q = {c[0].name for c in cells if c[1].name == "long_500k" and c[2]}
        assert sub_q == {"mamba2-130m", "hymba-1.5b"}

    def test_param_counts_sane(self):
        """n_params() within ~35 % of the nameplate size (vlm/audio backbones
        and fine-grained MoE naming aside)."""
        approx = {
            "yi-9b": 8.8e9, "qwen1.5-4b": 4e9, "granite-3-2b": 2.5e9,
            "smollm-360m": 3.6e8, "mamba2-130m": 1.3e8, "hymba-1.5b": 1.5e9,
            "dbrx-132b": 1.32e11, "granite-4.0-h-micro": 3e9,
        }
        for name, target in approx.items():
            got = ARCHS[name].n_params()
            assert 0.6 * target < got < 1.6 * target, (name, got, target)

    def test_moe_active_params(self):
        dbrx = ARCHS["dbrx-132b"]
        assert dbrx.n_active_params() < 0.45 * dbrx.n_params()
