"""Compile rehearsals: the serving path's Pallas kernels, compiled for a v5e.

Nothing here runs on a chip.  The TPU compiler compiles each kernel for a
described (not attached) ``v5e:2x2`` topology, which refuses what the
interpreter accepts: unaligned slices, value-level dynamic indexing,
broadcasts of unsupported layouts, more VMEM than a kernel may use.  Shapes
are the chip's own: every GW segment packs to one 128-lane width there
(``ops._pack_width``), and a served batch pads to the 8-row sublane tile.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every pytest
worker imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.lstm_stack.ops import lstm_stack_op
from repro.kernels.lstm_stack.step import lstm_stack_step_op

LANES = 128          # packed width of every GW segment on the chip
SERVE_BATCH = 8      # a served pool pads to the sublane tile
WINDOW = 100         # gw_nominal / gw_small window (configs/gw.py)

#: layers per packed segment: gw_nominal's encoder (1->32->8) and decoder
#: (8->8->32) are 2-layer segments, gw_small's (1->9 | 9->9) 1-layer ones
SEGMENTS = {"gw_nominal": 2, "gw_small": 1}
WEIGHTS = {"fp32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _stacked(one_chip, n_layers, weight_dtype):
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    w = (n_layers, LANES, 4 * LANES)
    stacked = {
        "w_x": spec(w, WEIGHTS[weight_dtype]),
        "w_h": spec(w, WEIGHTS[weight_dtype]),
        "b": spec((n_layers, 4 * LANES), jnp.float32),
    }
    if weight_dtype == "int8":
        stacked["scales"] = spec((n_layers, 2, 4), jnp.float32)
    state = spec((n_layers, SERVE_BATCH, LANES), jnp.float32)
    return spec, stacked, state


def _assert_kernel(compiled):
    # the Pallas kernel survived as a Mosaic custom call: no interpreter,
    # no reference stand-in
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("weight_dtype", list(WEIGHTS))
@pytest.mark.parametrize("model", list(SEGMENTS))
def test_wavefront_compiles_for_v5e(one_chip, model, weight_dtype):
    """Calibration / window decode: the wavefront kernel over a window."""
    spec, stacked, state = _stacked(one_chip, SEGMENTS[model], weight_dtype)
    xs = spec((SERVE_BATCH, WINDOW, LANES), jnp.float32)
    compiled = lstm_stack_op.lower(
        xs, stacked, state, state, interpret=False, weight_dtype=weight_dtype,
    ).compile()
    _assert_kernel(compiled)


STEP_CASES = [
    # gw_nominal: the full matrix — fp32/bf16 with and without the fused
    # gate matmul, int8 (always separate dots), straight-line T=1 and the
    # loop body at T=25
    *[("gw_nominal", wd, fg, t) for wd in ("fp32", "bf16")
      for fg in (False, True) for t in (1, 25)],
    *[("gw_nominal", "int8", False, t) for t in (1, 25)],
    # gw_small: the chip's default fuse_gates per dtype
    *[("gw_small", wd, wd != "int8", t) for wd in WEIGHTS for t in (1, 25)],
]


@pytest.mark.parametrize("model,weight_dtype,fuse_gates,t_len", STEP_CASES)
def test_step_kernel_compiles_for_v5e(
    one_chip, model, weight_dtype, fuse_gates, t_len
):
    """Streaming push: the one-grid-step kernel at serving chunk lengths."""
    spec, stacked, state = _stacked(one_chip, SEGMENTS[model], weight_dtype)
    xs = spec((SERVE_BATCH, t_len, LANES), jnp.float32)
    compiled = lstm_stack_step_op.lower(
        xs, stacked, state, state, interpret=False, weight_dtype=weight_dtype,
        fuse_gates=fuse_gates,
    ).compile()
    _assert_kernel(compiled)
