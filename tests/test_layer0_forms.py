"""The wavefront kernel's layer-0 forms (CPU interpret).

``lstm_stack_op`` picks one of three forms for layer 0's gate
pre-activations from the input's shape (``ops.layer0_form``):

* ``narrow`` — an input at most ``NARROW_MAX_IN`` features wide (the GW
  encoder's strain) is projected in-kernel, one multiply-add per column;
* ``repeat`` — a time-invariant input (the decoder's RepeatVector latent,
  ``timesteps=T``) is projected once per row;
* ``stream`` — anything else streams a ``(T, B, 4W)`` gate tensor.

Both new forms must give today's ``stream`` results bit for bit here (the
same product, rounding, per-gate scale and bias, in the same order), match
the ``lstm_stack_ref`` oracle, and be counted once per distinct trace of
``lstm_stack_op`` in the telemetry counter ``wavefront.layer0_<form>``.

The bit-for-bit equality with ``stream`` holds under the interpreter
only.  On the chip the ``narrow`` form's VPU product is the correctly
rounded fp32 one, where the stream form's HIGHEST matmul is not quite
(2.1e-6 apart after 100 steps, ``benchmarks/layer0_forms.py``); and the
interpreter projects a ``repeat`` input broadcast over time (see
``lstm_stack_op``), where the chip projects its ``(B, D)`` rows alone.
The chip's own arithmetic is checked by ``chip_smoke.py``: every score
against an on-chip reference, streamed scores against batch scores bit
for bit.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.configs.gw import GW_MODELS
from repro.core.autoencoder import (
    AutoencoderConfig,
    decoder_layers,
    encoder_layers,
    init_autoencoder,
)
from repro.core.executor import plan_stack
from repro.core.lstm import LstmConfig, init_lstm
from repro.kernels.lstm_stack.lstm_stack import NARROW_MAX_IN
from repro.kernels.lstm_stack.ops import (
    apply_gate_scales,
    layer0_form,
    lstm_stack_op,
    normalize_scales,
    pack_stack,
)
from repro.kernels.lstm_stack.ref import lstm_stack_ref
from repro.serve.engine import StreamingAnomalyEngine

BATCH = 16
T_LEN = 10


def _segment(model, segment, weight_dtype):
    """A GW segment packed at ``weight_dtype``: gw_nominal's are 2-layer
    (1->32->8 | 8->8->32), gw_small's 1-layer (1->9 | 9->9)."""
    cfg = dataclasses.replace(GW_MODELS[model], weight_dtype=weight_dtype)
    params = init_autoencoder(jax.random.PRNGKey(0), cfg)
    plist, cfgs = (encoder_layers if segment == "enc" else decoder_layers)(
        params, cfg)
    return pack_stack(plist, cfgs)


def _state(ps, seed=7):
    """A non-zero packed initial state: real lanes only, padding zero."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    states = [
        (jax.random.normal(jax.random.fold_in(k1, i), (BATCH, w)) * 0.5,
         jax.random.normal(jax.random.fold_in(k2, i), (BATCH, w)) * 0.5)
        for i, w in enumerate(ps.hidden)
    ]
    return ps.pack_state(states)


def _ref(ps, xs, h0, c0):
    """``lstm_stack_ref`` with the layer-0 projection written out:
    (B, T, in) -> (hs (B, T, W), h_f, c_f)."""
    st = ps.stacked
    xw0 = (ps.pad_input(xs) @ st["w_x"][0].astype(ps.dtype)).astype(
        jnp.float32)
    if "scales" in st:
        xw0 = apply_gate_scales(
            xw0, normalize_scales(st["scales"], ps.n_layers)[0, 0])
    xw0 = xw0 + st["b"][0]
    hs, h_f, c_f = lstm_stack_ref(
        jnp.swapaxes(xw0, 0, 1), st["w_x"], st["w_h"], st["b"], h0, c0,
        scales=st.get("scales"),
    )
    return jnp.swapaxes(hs, 0, 1), h_f, c_f


def _assert_bitwise(got, want):
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("alias_state", [True, False])
@pytest.mark.parametrize("block_b", [8, 16])
@pytest.mark.parametrize("weight_dtype", ["fp32", "bf16", "int8"])
@pytest.mark.parametrize("model", ["gw_nominal", "gw_small"])
@pytest.mark.parametrize("form", ["narrow", "repeat"])
def test_form_bitwise_vs_stream_and_near_ref(
    form, model, weight_dtype, block_b, alias_state
):
    """Each new form against today's stream form (the same input handed
    over padded to the pack width, so its real width is unknown): bit for
    bit under the interpreter, from a non-zero initial state; and against
    the oracle."""
    ps = _segment(model, "enc" if form == "narrow" else "dec", weight_dtype)
    key = jax.random.PRNGKey(3)
    kw = dict(acts=ps.acts, weight_dtype=ps.weight_dtype, block_b=block_b,
              alias_state=alias_state)
    h0, c0 = _state(ps)
    if form == "narrow":
        xs = jax.random.normal(key, (BATCH, T_LEN, ps.in_dims[0]))
        got = lstm_stack_op(xs, ps.stacked, h0, c0, **kw)
    else:
        latent = jax.random.normal(key, (BATCH, ps.in_dims[0]))
        got = lstm_stack_op(latent, ps.stacked, h0, c0, timesteps=T_LEN,
                            **kw)
        xs = jnp.broadcast_to(latent[:, None], (BATCH, T_LEN, latent.shape[1]))
    assert layer0_form(xs.shape[-1], ps.width_p, form == "repeat") == form
    stream = lstm_stack_op(ps.pad_input(xs), ps.stacked, h0, c0, **kw)
    _assert_bitwise(got, stream)
    for g, r in zip(got, _ref(ps, xs, h0, c0)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-5, atol=1e-5)


def _counts():
    return {form: telemetry.snapshot()["counters"].get(
        f"wavefront.layer0_{form}", 0) for form in ("narrow", "repeat",
                                                    "stream")}


def _traced(fn, *args):
    """Counts each layer-0 form gains while ``fn`` is traced."""
    before = _counts()
    jax.jit(fn).lower(*args)
    after = _counts()
    return {k: after[k] - before[k] for k in after}


def test_wide_input_keeps_stream_form():
    """A real input wider than ``NARROW_MAX_IN`` streams, and still agrees
    with the oracle."""
    d_in = NARROW_MAX_IN + 1
    cfgs = [LstmConfig(in_dim=d_in, hidden=16), LstmConfig(in_dim=16,
                                                           hidden=16)]
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    ps = pack_stack([init_lstm(k, c) for k, c in zip(keys, cfgs)], cfgs)
    assert layer0_form(d_in, ps.width_p, repeat=False) == "stream"
    xs = jax.random.normal(jax.random.PRNGKey(2), (BATCH, 11, d_in))
    h0, c0 = _state(ps)
    fn = lambda x: lstm_stack_op(x, ps.stacked, h0, c0, acts=ps.acts)  # noqa: E731
    assert _traced(fn, xs) == {"narrow": 0, "repeat": 0, "stream": 1}
    for g, r in zip(fn(xs), _ref(ps, xs, h0, c0)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("model", ["gw_nominal", "gw_small"])
def test_counts_per_program(model):
    """The engine's batch score program traces the encoder narrow and the
    decoder repeat; its window finish traces the decoder repeat; a stack
    handed a pack-wide (padded) input streams.  The counter counts traces
    of ``lstm_stack_op``, which programs calling it with the same abstract
    arguments share: shapes unique to this test force fresh ones."""
    cfg = GW_MODELS[model]
    engine = StreamingAnomalyEngine(
        init_autoencoder(jax.random.PRNGKey(0), cfg), cfg, batch=1)
    windows = jnp.zeros((13, 17, cfg.input_dim))
    assert _traced(engine._score_batch, engine.params, engine._exec_enc,
                   engine._exec_dec, windows) == {
        "narrow": 1, "repeat": 1, "stream": 0}
    finish = engine._finish_fn(3)
    states = tuple(engine._exec_enc.zero_state(1) for _ in range(3))
    assert _traced(finish, engine.params, states,
                   jnp.zeros((3, 19, cfg.input_dim))) == {
        "narrow": 0, "repeat": 1, "stream": 0}
    ps = engine._exec_enc.packed
    h0, c0 = ps.zero_state(5)
    assert _traced(
        lambda x: lstm_stack_op(x, ps.stacked, h0, c0, acts=ps.acts),
        jnp.zeros((5, 23, ps.width_p)),
    ) == {"narrow": 0, "repeat": 0, "stream": 1}


@pytest.mark.parametrize(
    "impl", ["naive", "split", "kernel", "wavefront", "fused_stack",
             "fused_step", "mixed"])
def test_executor_timesteps_equals_broadcast(impl):
    """``StackExecutor(latent, timesteps=T)`` is the broadcast latent's
    result bit for bit: the fused backends project it once per row, the
    others see it broadcast by one shared fallback."""
    cfg = AutoencoderConfig(hidden=(32, 8, 8, 32), timesteps=T_LEN)
    params = init_autoencoder(jax.random.PRNGKey(0), cfg)
    plist, cfgs = decoder_layers(params, cfg)
    kw = {"weight_dtype": ("int8", "fp32")} if impl == "mixed" else {}
    ex = plan_stack(cfgs, impl=impl, **kw).bind(plist)
    latent = jax.random.normal(jax.random.PRNGKey(4), (BATCH, cfgs[0].in_dim))
    got = ex(latent, timesteps=T_LEN, return_state=False)
    want = ex(jnp.broadcast_to(latent[:, None], (BATCH, T_LEN, latent.shape[1])),
              return_state=False)
    assert got.shape == (BATCH, T_LEN, cfgs[-1].hidden)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
