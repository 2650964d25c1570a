"""Wavefront pipeline == sequential stack execution (the paper's Fig. 7)."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # offline container: fixed-example stand-ins
    from _hypothesis_compat import given, settings, st

from repro.core.lstm import LstmConfig, init_lstm, lstm_forward
from repro.core.pipeline import (
    pack_lstm_stack,
    pack_uniform,
    pipeline_lstm_stack,
    wavefront,
)


def _stack(key, dims):
    """dims: [(lx, lh), ...] -> (params_list, cfgs)."""
    cfgs = [LstmConfig(in_dim=lx, hidden=lh) for lx, lh in dims]
    keys = jax.random.split(key, len(dims))
    return [init_lstm(k, c) for k, c in zip(keys, cfgs)], cfgs


def _sequential(params_list, cfgs, xs):
    h = xs
    for p, c in zip(params_list, cfgs):
        h, _ = lstm_forward(p, h, c)
    return h


class TestPacking:
    def test_pad_exactness(self):
        """A padded layer computes identically on the real lanes."""
        key = jax.random.PRNGKey(0)
        params, cfgs = _stack(key, [(3, 5)])
        stacked, width = pack_uniform(params, [3], [5])
        xs = jax.random.normal(jax.random.fold_in(key, 1), (2, 8, 3))
        ref, _ = lstm_forward(params[0], xs, cfgs[0])
        out = wavefront(
            stacked, jnp.pad(xs, ((0, 0), (0, 0), (0, width - 3))), n_chunks=2
        )
        np.testing.assert_allclose(out[..., :5], ref, rtol=1e-5, atol=1e-5)

    def test_pack_shapes(self):
        params, _ = _stack(jax.random.PRNGKey(1), [(1, 32), (32, 8)])
        stacked, d, h = pack_lstm_stack(params, [1, 32], [32, 8])
        assert stacked["w_x"].shape == (2, 32, 4 * 32)
        assert stacked["w_h"].shape == (2, 32, 4 * 32)


class TestWavefrontEquivalence:
    @pytest.mark.parametrize("dims", [
        [(1, 8), (8, 8)],                    # homogeneous pair
        [(1, 32), (32, 8), (8, 8), (8, 32)], # the GW nominal stack (no sync)
        [(4, 16), (16, 16), (16, 16)],
    ])
    @pytest.mark.parametrize("n_chunks", [1, 2, 5, 10])
    def test_matches_sequential(self, dims, n_chunks):
        key = jax.random.PRNGKey(hash(str(dims)) % 2**31)
        params, cfgs = _stack(key, dims)
        xs = jax.random.normal(jax.random.fold_in(key, 9), (3, 20, dims[0][0]))
        ref = _sequential(params, cfgs, xs)
        out = pipeline_lstm_stack(params, cfgs, xs, n_chunks=n_chunks)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    @given(
        n_layers=st.integers(1, 4), hidden=st.integers(2, 12),
        n_chunks=st.sampled_from([1, 2, 4]), seed=st.integers(0, 99),
    )
    @settings(max_examples=15, deadline=None)
    def test_property_chunk_invariance(self, n_layers, hidden, n_chunks, seed):
        dims = [(2, hidden)] + [(hidden, hidden)] * (n_layers - 1)
        key = jax.random.PRNGKey(seed)
        params, cfgs = _stack(key, dims)
        xs = jax.random.normal(jax.random.fold_in(key, 1), (2, 8, 2))
        ref = _sequential(params, cfgs, xs)
        out = pipeline_lstm_stack(params, cfgs, xs, n_chunks=n_chunks)
        np.testing.assert_allclose(out, ref, rtol=3e-5, atol=3e-5)


_SHARD_MAP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.core.lstm import LstmConfig, init_lstm, lstm_forward
from repro.core.pipeline import pack_uniform, wavefront_shard_map

dims = [(1, 8), (8, 8), (8, 8), (8, 8)]
cfgs = [LstmConfig(in_dim=a, hidden=b) for a, b in dims]
keys = jax.random.split(jax.random.PRNGKey(0), 4)
params = [init_lstm(k, c) for k, c in zip(keys, cfgs)]
xs = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 1))

ref = xs
for p, c in zip(params, cfgs):
    ref, _ = lstm_forward(p, ref, c)

stacked, width = pack_uniform(params, [d[0] for d in dims], [d[1] for d in dims])
mesh = jax.make_mesh((4,), ("stage",), axis_types=(jax.sharding.AxisType.Auto,))
xs_p = jnp.pad(xs, ((0, 0), (0, 0), (0, width - 1)))
out = wavefront_shard_map(stacked, xs_p, n_chunks=4, mesh=mesh)
np.testing.assert_allclose(out[..., :8], ref, rtol=2e-5, atol=2e-5)
print("SHARD_MAP_OK")
"""


class TestShardMapWavefront:
    def test_distributed_matches_sequential(self):
        """4 stages on 4 (placeholder) devices, ppermute hand-off."""
        from repro.launch.subproc import child_env

        r = subprocess.run(
            [sys.executable, "-c", _SHARD_MAP_SCRIPT],
            capture_output=True, text=True, timeout=300,
            env=child_env(),
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert "SHARD_MAP_OK" in r.stdout, r.stderr[-2000:]
