"""The plan/bind/execute API: StackPlan resolution, StackExecutor dispatch,
and the sharded fused wavefront backend (ISSUE 4).

Covers the executor edge paths the redesign promises:
* plan-time (not Pallas-time) errors for illegal impl/weight_dtype combos
* the empty segment (latent_boundary=0 style) identity plan
* bind -> update_params pack-cache eviction
* steady-state executor calls re-trace and re-pack ZERO times
* fused_stack_sharded == local fused_stack bit-for-bit on a 2-device CPU
  mesh (subprocess, JAX_PLATFORMS threaded through like test_pipeline.py)
"""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pipeline
from repro.core.backends import (
    available_backends,
    check_weight_storage,
    quantized_weight_storage,
    requested_weight_storage,
)
from repro.core.executor import StackExecutor, StackPlan, plan_stack
from repro.core.lstm import LstmConfig, init_lstm, lstm_stack_forward


def _stack(key, dims):
    cfgs = [LstmConfig(in_dim=a, hidden=b) for a, b in dims]
    keys = jax.random.split(key, len(dims))
    return [init_lstm(k, c) for k, c in zip(keys, cfgs)], cfgs


@pytest.fixture(scope="module")
def gw_stack():
    """The GW nominal encoder-like heterogeneous stack."""
    params, cfgs = _stack(jax.random.PRNGKey(0), [(1, 32), (32, 8), (8, 8)])
    xs = jax.random.normal(jax.random.PRNGKey(1), (3, 12, 1))
    return params, cfgs, xs


class TestPlanResolution:
    def test_unknown_impl_raises_listing_backends(self, gw_stack):
        _, cfgs, _ = gw_stack
        with pytest.raises(ValueError, match="registered backends"):
            plan_stack(cfgs, impl="bogus")

    def test_registry_contents(self):
        names = available_backends()
        for name in ("naive", "split", "kernel", "fused_stack",
                     "fused_stack_sharded", "wavefront"):
            assert name in names

    def test_plans_are_cached_identities(self, gw_stack):
        """Same arguments -> the SAME plan object: legality resolution and
        the weight_dtype config rewrite happen once, never per call."""
        _, cfgs, _ = gw_stack
        p1 = plan_stack(cfgs, impl="fused_stack", weight_dtype="int8")
        p2 = plan_stack(list(cfgs), impl="fused_stack", weight_dtype="int8")
        assert p1 is p2
        assert all(c.weight_dtype == "int8" for c in p1.cfgs)

    def test_quantized_on_non_fused_raises_at_plan_time(self, gw_stack):
        _, cfgs, _ = gw_stack
        for impl in ("naive", "split", "kernel", "wavefront"):
            with pytest.raises(ValueError, match="fused_stack"):
                plan_stack(cfgs, impl=impl, weight_dtype="int8")

    def test_storage_wider_than_compute_raises_at_plan_time(self):
        cfgs = [LstmConfig(in_dim=2, hidden=4, dtype=jnp.bfloat16)]
        with pytest.raises(ValueError, match="wider than compute"):
            plan_stack(cfgs, impl="fused_stack", weight_dtype="fp32")

    def test_sharded_placement_requires_fused(self, gw_stack):
        _, cfgs, _ = gw_stack
        with pytest.raises(ValueError, match="sharded"):
            plan_stack(cfgs, impl="split", placement="sharded")

    def test_unknown_placement_raises(self, gw_stack):
        _, cfgs, _ = gw_stack
        with pytest.raises(ValueError, match="placement"):
            plan_stack(cfgs, impl="fused_stack", placement="orbital")

    def test_mesh_without_sharded_placement_raises(self, gw_stack):
        """An explicit stage mesh under local placement can only be a
        forgotten placement='sharded' — refuse, never silently ignore."""
        _, cfgs, _ = gw_stack
        mesh = jax.make_mesh((1,), ("stage",))
        with pytest.raises(ValueError, match="placement='sharded'"):
            plan_stack(cfgs, impl="fused_stack", mesh=mesh)

    def test_empty_segment_still_validates_impl_and_placement(self):
        with pytest.raises(ValueError, match="registered backends"):
            plan_stack([], impl="bogus")
        with pytest.raises(ValueError, match="placement"):
            plan_stack([], impl="fused_stack", placement="orbital")

    def test_sharded_impl_normalizes_placement(self, gw_stack):
        _, cfgs, _ = gw_stack
        # 3 layers on a 1-device CPU mesh: default mesh degenerates to 1 stage
        plan = plan_stack(cfgs, impl="fused_stack_sharded")
        assert plan.placement == "sharded"
        assert plan.mesh is not None

    def test_weight_storage_rules_shared(self):
        """The single backends.py implementation serves both surfaces."""
        cfgs = [LstmConfig(in_dim=2, hidden=4, weight_dtype="int8")]
        assert requested_weight_storage(cfgs) == "int8"
        check_weight_storage("int8", "fused_stack")  # legal: no raise
        with pytest.raises(ValueError, match="fused_stack"):
            check_weight_storage("int8", "split")
        from repro.core.autoencoder import AutoencoderConfig

        acfg = AutoencoderConfig(hidden=(9, 9), latent_boundary=1,
                                 weight_dtype="int8")
        assert quantized_weight_storage(acfg) == "int8"
        # and serve.engine still re-exports the old names
        from repro.serve import engine as serve_engine

        assert serve_engine.quantized_weight_storage is quantized_weight_storage


class TestIdentityPlan:
    def test_empty_segment_is_identity(self):
        xs = jnp.ones((2, 5, 3))
        plan = plan_stack([], impl="fused_stack")
        assert plan.impl == "identity" and plan.n_layers == 0
        ex = plan.bind([])
        h, finals = ex(xs)
        assert h is xs and finals == []
        assert ex(xs, return_state=False) is xs
        assert ex.zero_state(2) == []
        assert ex.step(xs, []) == []
        assert ex.packed_bytes == 0

    def test_shim_empty_segment(self):
        xs = jnp.ones((2, 5, 3))
        for impl in ("naive", "split", "kernel", "fused_stack"):
            h, finals = lstm_stack_forward([], xs, [], impl=impl)
            assert h is xs and finals == []


class TestExecutorDispatch:
    @pytest.mark.parametrize("impl", ["naive", "split", "kernel",
                                      "fused_stack"])
    def test_matches_shim_bitwise(self, gw_stack, impl):
        params, cfgs, xs = gw_stack
        ref, finals_ref = lstm_stack_forward(params, xs, cfgs, impl=impl)
        ex = plan_stack(cfgs, impl=impl).bind(params)
        out, finals = ex(xs)
        np.testing.assert_array_equal(out, ref)
        for (h, c), (hr, cr) in zip(finals, finals_ref):
            np.testing.assert_array_equal(h, hr)
            np.testing.assert_array_equal(c, cr)

    def test_cross_backend_state_portability(self, gw_stack):
        """Finals are per-layer real-width (h, c) on every backend: one
        backend's finals feed another's initial_state exactly."""
        params, cfgs, xs = gw_stack
        _, finals = plan_stack(cfgs, impl="split").bind(params)(xs)
        fused = plan_stack(cfgs, impl="fused_stack").bind(params)
        split = plan_stack(cfgs, impl="split").bind(params)
        out_f, _ = fused(xs, finals)
        out_s, _ = split(xs, finals)
        np.testing.assert_allclose(out_f, out_s, rtol=2e-5, atol=2e-5)

    def test_step_equals_call_finals(self, gw_stack):
        """The native-state hot path advances exactly like __call__."""
        params, cfgs, xs = gw_stack
        for impl in ("split", "fused_stack"):
            ex = plan_stack(cfgs, impl=impl).bind(params)
            _, finals = ex(xs)
            state = ex.zero_state(xs.shape[0])
            state = ex.step(xs, state)
            latent = ex.last_hidden(state)
            np.testing.assert_allclose(
                latent, finals[-1][0], rtol=1e-6, atol=1e-7
            )

    def test_wavefront_backend_refuses_state(self, gw_stack):
        params, _, xs = gw_stack
        # wavefront needs a uniform hand-off width: use a homogeneous stack
        params, cfgs = _stack(jax.random.PRNGKey(5), [(1, 8), (8, 8)])
        ex = plan_stack(cfgs, impl="wavefront", n_chunks=2).bind(params)
        out = ex(xs, return_state=False)
        assert out.shape == (3, 12, 8)
        with pytest.raises(ValueError, match="state"):
            ex(xs)  # return_state=True has no finals to return

    def test_executor_is_a_pytree(self, gw_stack):
        """Executors cross jit boundaries as arguments: leaves are the
        params/pack arrays, the plan is static aux data."""
        params, cfgs, xs = gw_stack
        ex = plan_stack(cfgs, impl="fused_stack").bind(params)
        leaves, treedef = jax.tree_util.tree_flatten(ex)
        assert leaves, "params/pack must be pytree leaves"
        rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
        assert isinstance(rebuilt, StackExecutor)
        assert rebuilt.plan is ex.plan
        f = jax.jit(lambda e, x: e(x, return_state=False))
        np.testing.assert_array_equal(f(ex, xs), ex(xs, return_state=False))

    def test_bind_rejects_packed_on_non_packing_backend(self, gw_stack):
        params, cfgs, _ = gw_stack
        from repro.kernels.lstm_stack.ops import pack_stack

        packed = pack_stack(params, cfgs)
        with pytest.raises(ValueError, match="packed"):
            plan_stack(cfgs, impl="split").bind(params, packed=packed)


class TestTraceAndPackCounts:
    def test_steady_state_executor_retraces_and_repacks_zero_times(
        self, gw_stack
    ):
        """The satellite regression: after warm-up, executor calls must not
        re-trace the jitted step nor re-run pack_lstm_stack (the per-call
        ``dataclasses.replace`` of every LstmConfig is gone — the plan is a
        cached identity, so the jit cache keys stay stable)."""
        params, cfgs, xs = gw_stack
        ex = plan_stack(cfgs, impl="fused_stack",
                        weight_dtype="int8").bind(params)
        traces = []

        @jax.jit
        def scored(e, x):
            traces.append(1)  # python side effect: runs at TRACE time only
            return e(x, return_state=False)

        jax.block_until_ready(scored(ex, xs))
        packs_before = pipeline.PACK_TRACE_COUNT
        n_traces = len(traces)
        for _ in range(5):
            # re-bind per call, like a serving loop would: the plan cache
            # and the identity-keyed pack cache keep everything stable
            ex_i = plan_stack(cfgs, impl="fused_stack",
                              weight_dtype="int8").bind(params)
            jax.block_until_ready(scored(ex_i, xs))
        assert len(traces) == n_traces, "steady-state calls re-traced"
        assert pipeline.PACK_TRACE_COUNT == packs_before, (
            "steady-state calls re-packed"
        )

    def test_update_params_evicts_superseded_pack(self, gw_stack):
        from repro.kernels.lstm_stack.ops import _PACK_CACHE

        params, cfgs, _ = gw_stack
        ex = plan_stack(cfgs, impl="fused_stack").bind(params)
        old_pack = ex.packed
        assert any(v is old_pack for v in _PACK_CACHE.values())
        params2, _ = _stack(jax.random.PRNGKey(7), [(1, 32), (32, 8), (8, 8)])
        ex2 = ex.update_params(params2)
        assert ex2.packed is not old_pack
        assert all(v is not old_pack for v in _PACK_CACHE.values()), (
            "update_params must evict the superseded pack"
        )
        assert any(v is ex2.packed for v in _PACK_CACHE.values())

    def test_update_params_same_identity_keeps_pack(self, gw_stack):
        params, cfgs, _ = gw_stack
        ex = plan_stack(cfgs, impl="fused_stack").bind(params)
        ex2 = ex.update_params(params)  # same leaves: identity-cache hit
        assert ex2.packed is ex.packed


_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.core.executor import plan_stack
from repro.core.lstm import LstmConfig, init_lstm

assert len(jax.devices()) == 2
dims = [(1, 8), (8, 8), (8, 8), (8, 8)]
cfgs = [LstmConfig(in_dim=a, hidden=b) for a, b in dims]
keys = jax.random.split(jax.random.PRNGKey(0), 4)
params = [init_lstm(k, c) for k, c in zip(keys, cfgs)]
xs = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 1))

for wd in (None, "int8"):
    local = plan_stack(cfgs, impl="fused_stack", weight_dtype=wd).bind(params)
    sharded = plan_stack(cfgs, impl="fused_stack", weight_dtype=wd,
                         placement="sharded").bind(params)
    assert sharded.plan.mesh.shape["stage"] == 2, sharded.plan.describe()
    h_l, f_l = local(xs)
    h_s, f_s = sharded(xs)
    # bit-for-bit: the sharded wavefront only relocates WHERE each
    # (layer, chunk) cell evaluates, never the math or its order
    np.testing.assert_array_equal(np.asarray(h_s), np.asarray(h_l))
    for (h1, c1), (h2, c2) in zip(f_s, f_l):
        np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    # nonzero initial state threads identically
    h_l2, _ = local(xs, f_l)
    h_s2, _ = sharded(xs, f_l)
    np.testing.assert_array_equal(np.asarray(h_s2), np.asarray(h_l2))
    # a repeated input (RepeatVector): stage 0 projects it as local does
    np.testing.assert_array_equal(
        np.asarray(sharded(xs[:, 0], timesteps=16, return_state=False)),
        np.asarray(local(xs[:, 0], timesteps=16, return_state=False)))

# every legal chunking is equivalent
ref = np.asarray(plan_stack(cfgs, impl="fused_stack").bind(params)(
    xs, return_state=False))
for nc in (1, 2, 4, 8):
    p = plan_stack(cfgs, impl="fused_stack", placement="sharded",
                   n_chunks=nc).bind(params)
    np.testing.assert_array_equal(
        np.asarray(p(xs, return_state=False)), ref)

# plan-time divisibility error on a real 2-stage mesh
mesh2 = jax.make_mesh((2,), ("stage",), axis_types=(jax.sharding.AxisType.Auto,))
cfgs3 = cfgs[:3]
try:
    plan_stack(cfgs3, impl="fused_stack", placement="sharded", mesh=mesh2)
    raise SystemExit("expected a divisibility ValueError")
except ValueError as e:
    assert "sub-stacks" in str(e), e
print("SHARDED_EXEC_OK")
"""


class TestShardedFusedWavefront:
    def test_sharded_matches_local_bitwise_on_cpu_mesh(self):
        """fused_stack_sharded == fused_stack bit-for-bit, 2 CPU devices."""
        from repro.launch.subproc import child_env

        r = subprocess.run(
            [sys.executable, "-c", _SHARDED_SCRIPT],
            capture_output=True, text=True, timeout=600,
            env=child_env(),
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert "SHARDED_EXEC_OK" in r.stdout, r.stderr[-3000:]

    def test_single_stage_sharded_matches_local_inline(self, gw_stack):
        """The degenerate 1-device mesh still routes through shard_map."""
        params, cfgs, xs = gw_stack
        local = plan_stack(cfgs, impl="fused_stack").bind(params)
        sharded = plan_stack(
            cfgs, impl="fused_stack", placement="sharded"
        ).bind(params)
        np.testing.assert_array_equal(
            sharded(xs, return_state=False), local(xs, return_state=False)
        )

    def test_n_chunks_must_divide_time(self, gw_stack):
        params, cfgs, xs = gw_stack  # T = 12
        ex = plan_stack(cfgs, impl="fused_stack", placement="sharded",
                        n_chunks=5).bind(params)
        with pytest.raises(ValueError, match="n_chunks"):
            ex(xs)


class TestEngineOnExecutors:
    def test_streaming_engine_sharded_placement(self):
        """placement= rides resolve_impl -> plan_stack -> shard_map (one
        device here; the 2-device path is covered by the subprocess)."""
        from repro.core.autoencoder import AutoencoderConfig, init_autoencoder
        from repro.serve.engine import StreamingAnomalyEngine

        cfg = AutoencoderConfig(hidden=(9, 9), latent_boundary=1,
                                timesteps=12)
        params = init_autoencoder(jax.random.PRNGKey(11), cfg)
        x = np.random.RandomState(0).randn(2, 12, 1).astype("float32")
        local = StreamingAnomalyEngine(params, cfg, batch=2, window=12)
        sharded = StreamingAnomalyEngine(
            params, cfg, batch=2, window=12, placement="sharded"
        )
        assert sharded._exec_enc.plan.impl == "fused_stack_sharded"
        (s_local,) = local.push(x)
        (s_sharded,) = sharded.push(x)
        np.testing.assert_array_equal(s_sharded, s_local)

    def test_oneshot_engine_validates_plan_at_init(self):
        """Illegal impl/placement combos raise at engine construction
        (plan time), not on the first score()."""
        from repro.core.autoencoder import AutoencoderConfig, init_autoencoder
        from repro.core.quant import PAPER_HW
        from repro.serve.engine import AnomalyStreamEngine

        # PAPER_HW declines the fused upgrade -> effective impl is 'split',
        # which cannot take sharded placement: must fail HERE
        cfg = AutoencoderConfig(hidden=(9, 9), latent_boundary=1,
                                timesteps=12, acts=PAPER_HW)
        params = init_autoencoder(jax.random.PRNGKey(12), cfg)
        with pytest.raises(ValueError, match="sharded"):
            AnomalyStreamEngine(params, cfg, placement="sharded")
