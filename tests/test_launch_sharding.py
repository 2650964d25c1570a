"""Sharding rule engine + mesh helpers + HLO analyzer unit tests."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis.hlo import analyze_hlo
from repro.launch.sharding import _DP_RULES, _SERVE_RULES, _TRAIN_RULES, _spec_for


class _Leaf:
    def __init__(self, shape):
        self.shape = shape
        self.ndim = len(shape)


class TestRuleEngine:
    def test_train_2d_fsdp(self):
        assert _spec_for("layers/attn/wq", _Leaf((4, 64, 512)), _TRAIN_RULES) \
            == P(None, "data", "model")
        assert _spec_for("layers/mlp/w_down", _Leaf((4, 512, 64)), _TRAIN_RULES) \
            == P(None, "model", "data")
        assert _spec_for("embed", _Leaf((1024, 64)), _TRAIN_RULES) == P("model", "data")

    def test_moe_vs_dense_disambiguation(self):
        # same leaf name under moe/ is the 3-D expert tensor
        assert _spec_for("layers/moe/w_gate", _Leaf((4, 16, 64, 128)), _TRAIN_RULES) \
            == P(None, "model", "data", None)
        assert _spec_for("layers/mlp/w_gate", _Leaf((4, 64, 128)), _TRAIN_RULES) \
            == P(None, "data", "model")

    def test_norms_replicated(self):
        assert _spec_for("layers/ln1", _Leaf((4, 64)), _TRAIN_RULES) == P()

    def test_sanitizer_drops_nondivisible(self):
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        # vocab 49155 % 1 == 0 so nothing dropped at size-1 axes
        spec = _spec_for("embed", _Leaf((49155, 64)), _TRAIN_RULES, mesh)
        assert spec == P("model", "data")

    def test_serve_candidates_fallback(self):
        """60 experts don't divide a 16-way model axis -> fall through to
        the (d, ff) candidate."""

        class FakeMesh:
            shape = {"data": 16, "model": 16}

        spec = _spec_for("layers/moe/w_gate", _Leaf((24, 60, 2048, 1408)),
                         _SERVE_RULES, FakeMesh())
        assert spec == P(None, None, "data", "model")

    def test_dp_rules_strip_model(self):
        assert _spec_for("layers/attn/wq", _Leaf((4, 64, 512)), _DP_RULES) \
            == P(None, "data", None)


class TestHloAnalyzer:
    def test_scan_trip_multiplier(self):
        """dot FLOPs from a scan of L matmuls must scale with L (the
        cost_analysis undercount this module exists to fix)."""
        d = 64

        def f(x, ws):
            def body(c, w):
                return jnp.tanh(c @ w), None
            y, _ = jax.lax.scan(body, x, ws)
            return y.sum()

        x = jax.ShapeDtypeStruct((d, d), jnp.float32)
        flops = {}
        for L in (2, 8):
            ws = jax.ShapeDtypeStruct((L, d, d), jnp.float32)
            comp = jax.jit(f).lower(x, ws).compile()
            a = analyze_hlo(comp.as_text())
            flops[L] = a.dot_flops
            from repro.analysis.hlo import cost_analysis_dict

            raw = cost_analysis_dict(comp)["flops"]
            assert a.dot_flops > raw  # scan-corrected > raw for L > 1
        assert flops[8] == pytest.approx(4 * flops[2], rel=0.05)
        assert flops[8] == pytest.approx(8 * 2 * d**3, rel=0.05)

    def test_no_dots_no_flops(self):
        comp = jax.jit(lambda x: jnp.sin(x).sum()).lower(
            jax.ShapeDtypeStruct((128,), jnp.float32)
        ).compile()
        assert analyze_hlo(comp.as_text()).dot_flops == 0.0


_DRYRUN_SMOKE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_arch
from repro.configs.base import InputShape
from repro.launch.sharding import batch_shardings, opt_shardings, param_shardings
from repro.models.api import abstract_params, get_model, input_specs
from repro.models.layers import ShardCtx
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.step import make_train_step

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = dataclasses.replace(get_arch("granite-3-2b").reduced(), vocab=512)
api = get_model(cfg)
shape = InputShape("smoke", seq_len=64, global_batch=8, kind="train")
ctx = ShardCtx(mesh=mesh, data_axes=("data",))

params_abs = abstract_params(cfg)
p_sh = param_shardings(mesh, params_abs, mode="train")
opt_abs = jax.eval_shape(lambda p: init_opt_state(p), params_abs)
o_sh = opt_shardings(mesh, opt_abs, p_sh)
batch_abs = input_specs(cfg, shape)
b_sh = batch_shardings(mesh, batch_abs, shape)
step = make_train_step(lambda p, b: api.loss_fn(p, b, cfg, ctx), AdamWConfig())
fn = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
             out_shardings=(NamedSharding(mesh, P()), p_sh, o_sh),
             donate_argnums=(0, 1))
compiled = fn.lower(params_abs, opt_abs, batch_abs).compile()
assert compiled.memory_analysis().temp_size_in_bytes > 0
# and actually EXECUTE one sharded step on the 8 placeholder devices
params = api.init_params(jax.random.PRNGKey(0), cfg)
opt = init_opt_state(params)
import numpy as np
batch = {k: jnp.zeros(v.shape, v.dtype) for k, v in batch_abs.items()}
loss, params, opt = fn(
    jax.device_put(params, p_sh), jax.device_put(opt, o_sh),
    jax.device_put(batch, b_sh),
)
assert bool(jnp.isfinite(loss)), loss
print("DRYRUN_SMOKE_OK", float(loss))
"""


class TestDryrunSmoke:
    def test_sharded_train_step_compiles_and_runs(self):
        """The full launch path (rules -> jit -> compile -> EXECUTE) on 8
        placeholder devices with a reduced config — the in-suite twin of
        launch/dryrun.py."""
        from repro.launch.subproc import child_env

        r = subprocess.run(
            [sys.executable, "-c", _DRYRUN_SMOKE],
            capture_output=True, text=True, timeout=600,
            env=child_env(),
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert "DRYRUN_SMOKE_OK" in r.stdout, (r.stdout[-500:], r.stderr[-2000:])
