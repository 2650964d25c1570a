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

from repro import telemetry
from repro.configs.gw import GW_MODELS
from repro.core.autoencoder import init_autoencoder
from repro.kernels.lstm_stack import ops
from repro.kernels.lstm_stack.ops import lstm_stack_op
from repro.kernels.lstm_stack.step import lstm_stack_step_op
from repro.serve.engine import StreamingAnomalyEngine

LANES = 128          # packed width of every GW segment on the chip
SERVE_BATCH = 8      # a served pool pads to the sublane tile
WINDOW = 100         # gw_nominal / gw_small window (configs/gw.py)
ARCHIVE_BATCH = 4096  # windows per archive rescoring call
ARCHIVE_BLOCK = 256   # the wavefront kernel's batch tile there

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


def _stacked(one_chip, n_layers, weight_dtype, batch=SERVE_BATCH):
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    w = (n_layers, LANES, 4 * LANES)
    stacked = {
        "w_x": spec(w, WEIGHTS[weight_dtype]),
        "w_h": spec(w, WEIGHTS[weight_dtype]),
        "b": spec((n_layers, 4 * LANES), jnp.float32),
    }
    if weight_dtype == "int8":
        stacked["scales"] = spec((n_layers, 2, 4), jnp.float32)
    state = spec((n_layers, batch, LANES), jnp.float32)
    return spec, stacked, state


def _assert_kernel(compiled):
    _assert_kernel_text(compiled.as_text())


def _assert_kernel_text(text):
    # the Pallas kernel survived as a Mosaic custom call: no interpreter,
    # no reference stand-in
    assert "tpu_custom_call" in text


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


#: layer-0 input width per segment and form: the encoder's strain sample
#: (narrow), the decoder's latent (repeat: gw_nominal's 8, gw_small's 9)
LAYER0_INPUT = {("gw_nominal", "narrow"): 1, ("gw_nominal", "repeat"): 8,
                ("gw_small", "narrow"): 1, ("gw_small", "repeat"): 9}


@pytest.mark.parametrize("weight_dtype", list(WEIGHTS))
@pytest.mark.parametrize("model", list(SEGMENTS))
@pytest.mark.parametrize("form", ["narrow", "repeat"])
def test_wavefront_layer0_forms_compile_for_v5e(
    one_chip, form, model, weight_dtype
):
    """Archive rescoring: each segment's layer-0 form at the archive's
    shape (4096 windows of 100 samples, batch tile 256)."""
    spec, stacked, state = _stacked(
        one_chip, SEGMENTS[model], weight_dtype, batch=ARCHIVE_BATCH)
    d_in = LAYER0_INPUT[model, form]
    if form == "narrow":
        xs, kw = spec((ARCHIVE_BATCH, WINDOW, d_in), jnp.float32), {}
    else:
        xs, kw = spec((ARCHIVE_BATCH, d_in), jnp.float32), {"timesteps": WINDOW}
    compiled = lstm_stack_op.lower(
        xs, stacked, state, state, interpret=False, weight_dtype=weight_dtype,
        block_b=ARCHIVE_BLOCK, **kw,
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("model", list(SEGMENTS))
def test_archive_score_program_streams_no_gate_tensor(
    one_chip, monkeypatch, model
):
    """The engine's batch score program, compiled for the chip at the
    archive's shape, holds no time-major (T, B, 4W) layer-0 gate tensor:
    the encoder takes the narrow form and the decoder the repeat form."""
    # the chip's geometry: 128-lane packs and compiled (not interpreted)
    # kernels, though JAX runs on the CPU here
    monkeypatch.setattr(ops, "_on_cpu", lambda: False)
    cfg = GW_MODELS[model]
    engine = StreamingAnomalyEngine(
        init_autoencoder(jax.random.PRNGKey(0), cfg), cfg, batch=1)
    assert engine._exec_enc.packed.width_p == LANES
    to_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    args = to_chip((engine.params, engine._exec_enc, engine._exec_dec))
    before = telemetry.snapshot()["counters"]
    lowered = engine._score_batch.lower(*args, jax.ShapeDtypeStruct(
        (ARCHIVE_BATCH, WINDOW, cfg.input_dim), jnp.float32,
        sharding=one_chip))
    after = telemetry.snapshot()["counters"]
    assert {form: after.get(f"wavefront.layer0_{form}", 0)
            - before.get(f"wavefront.layer0_{form}", 0)
            for form in ("narrow", "repeat", "stream")} == {
        "narrow": 1, "repeat": 1, "stream": 0}
    text = lowered.compile().as_text()
    _assert_kernel_text(text)
    assert f"[{WINDOW},{ARCHIVE_BATCH},{4 * LANES}]" not in text


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


def test_slot_programs_compile_for_v5e_at_published_widths(topo):
    """granite-4.0-h-micro's slot engine on one chip, at its published
    widths with 48 slots of 2560 tokens: the longest prefill rung and the
    decode step compile, and the weights, the pool and the larger program's
    temporaries fit the chip's 16 GB."""
    from repro.launch.dryrun import compile_slot_programs

    mem = compile_slot_programs("granite-4.0-h-micro", topo.devices[0],
                                rungs=(2048,))
    assert set(mem) == {2048, "step"}
    for m in mem.values():
        # donated pool: the state comes back in place
        assert m.alias_size_in_bytes > 4e9
        assert m.argument_size_in_bytes + m.temp_size_in_bytes < 16e9
