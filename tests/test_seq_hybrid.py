"""granite-4.0-h-micro's sequential hybrid at a small size on the CPU:
the program against the plain fp32 reference that the benchmark keeps
(``perfbench/models/granite_hybrid.py``, which imports nothing of the
program), and the slot engine's state handling.

The small model: 2 periods of [Mamba, Mamba, attention], d_model 64,
d_state 16, 4 SSD heads of 32, GQA 4:2 of width 16, vocabulary 512, the
published multipliers and SSD chunk 256, on the reference's seeded random
weights.  The program runs in fp32 here (the weights are the reference's
bf16 values, widened), so the two differ only by the order of fp32
operations: the chunked SSD against the sequential recurrence, and
blocked against plain attention.
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perfbench.models import granite_hybrid as ref  # noqa: E402
from repro import telemetry  # noqa: E402
from repro.configs.base import ArchConfig  # noqa: E402
from repro.kernels.ssd_scan.ref import ssd_scan_ref  # noqa: E402
from repro.models import seq_hybrid, ssm  # noqa: E402
from repro.serve.slots import PREFILL_LADDER, Request, SlotEngine, generate  # noqa: E402

TINY = {
    "name": "granite_tiny", "num_hidden_layers": 6,
    "layer_types": ["mamba", "mamba", "attention"] * 2,
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "shared_intermediate_size": 128, "vocab_size": 512,
    "tie_word_embeddings": True, "rms_norm_eps": 1e-5,
    "mamba_n_heads": 4, "mamba_d_head": 32, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "attention_multiplier": 0.015625, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 8,
}
#: fp32 program against the fp32 reference: they differ by the order of
#: fp32 operations over 6 layers, ~1e-6 of the logits' scale (~0.1)
LOGIT_ATOL = 2e-6
#: a probability's relative error is its log-probability's absolute error
PROB_RTOL = 2e-5




def arch_config(c: dict) -> ArchConfig:
    """The program's fp32 ``ArchConfig`` from the reference's published
    keys."""
    d, heads = c["hidden_size"], c["num_attention_heads"]
    return ArchConfig(
        name=c["name"], family="seq_hybrid",
        n_layers=c["num_hidden_layers"], d_model=d, n_heads=heads,
        n_kv_heads=c["num_key_value_heads"],
        d_ff=c["shared_intermediate_size"], vocab=c["vocab_size"],
        head_dim=d // heads, tie_embeddings=c["tie_word_embeddings"],
        norm_eps=c["rms_norm_eps"], ssm_state=c["mamba_d_state"],
        ssm_head_dim=c["mamba_d_head"], ssm_expand=c["mamba_expand"],
        ssm_groups=c["mamba_n_groups"], conv_kernel=c["mamba_d_conv"],
        ssm_chunk=c["mamba_chunk_size"], conv_bias=c["mamba_conv_bias"],
        layer_types=tuple(c["layer_types"]),
        attention_multiplier=c["attention_multiplier"],
        embedding_multiplier=c["embedding_multiplier"],
        residual_multiplier=c["residual_multiplier"],
        logits_scaling=c["logits_scaling"], dtype=jnp.float32)


@pytest.fixture(scope="module")
def model():
    cfg = arch_config(TINY)
    params = ref.init_params(7, TINY)
    return cfg, params, jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(0, TINY["vocab_size"], n).astype(np.int32)


@pytest.mark.parametrize("length", [1, 37, 300])
def test_forward_equals_reference(model, length):
    """(a) The program's full forward is the reference's, at lengths below,
    across and past one SSD chunk."""
    cfg, params, params32 = model
    toks = _tokens(length, (2, length))
    got = np.asarray(seq_hybrid.forward(params32, {"tokens": jnp.asarray(toks)}, cfg))
    want = ref.logits(params, toks, TINY)
    np.testing.assert_allclose(got, want, rtol=0, atol=LOGIT_ATOL)


# (prompt, output) per request and slots: different lengths, admitted at
# different steps, slots reused after release
SLOT_CASES = {
    "staggered": (2, [(5, 9), (40, 3), (17, 6), (3, 1), (70, 4)]),
    "one_slot_reused": (1, [(12, 5), (3, 4), (30, 2)]),
}


@pytest.mark.parametrize("case", sorted(SLOT_CASES))
def test_slot_engine_equals_reference(model, case):
    """(b) Admission prefill and then decode through the slots equal the
    reference's full forward at every generated position."""
    cfg, params, params32 = model
    slots, shape = SLOT_CASES[case]
    engine = SlotEngine(params32, cfg, slots=slots, capacity=96)
    reqs = [Request(_tokens(i, n), m, _tokens(100 + i, 16))
            for i, (n, m) in enumerate(shape)]
    windows, got = [], []
    for req, (emitted, lp, ids) in zip(reqs, generate(engine, reqs)):
        assert len(emitted) == req.max_new
        windows.append({"prompt": req.prompt, "emitted": emitted,
                        "ids": req.logprob_ids})
        got.append(np.exp(np.concatenate([ids, lp[:, None]], axis=1)).ravel())
    want = ref.scores(params, windows, TINY)
    np.testing.assert_allclose(np.concatenate(got), want, rtol=PROB_RTOL)
    # greedy: each emitted token is the reference's most likely one
    for w in windows:
        seq = np.concatenate([w["prompt"], w["emitted"][:-1]])[None]
        best = ref.logits(params, seq, TINY)[0, len(w["prompt"]) - 1:].argmax(-1)
        np.testing.assert_array_equal(w["emitted"], best)


@pytest.mark.parametrize("length", [1, 4, 37, 256])
def test_padded_prefill_leaves_the_unpadded_state(model, length):
    """(c) A prompt right-padded to its rung leaves the SSD state, the conv
    window and the KV as the unpadded prompt does, and no key on a padded
    position.  Up to fp32 rounding: the unpadded prompt's matrix products
    have other shapes (one row is a matrix-vector product) and its SSD one
    chunk of its own length, so results differ by a few ulp per layer,
    ~1e-5 relative after six."""
    cfg, _, params32 = model
    toks = _tokens(length, length)
    width = PREFILL_LADDER[0]
    padded = np.zeros((1, width), np.int32)
    padded[0, :length] = toks
    _, got = seq_hybrid.prefill_states(params32, jnp.asarray(padded),
                                       jnp.asarray([length]), cfg)
    _, want = seq_hybrid.prefill_states(params32, jnp.asarray(toks[None]),
                                        jnp.asarray([length]), cfg)
    for name in ("ssd", "conv"):
        np.testing.assert_allclose(got[name], want[name], rtol=1e-4, atol=1e-5)
    for name in ("k", "v"):   # positions on the minor axis
        np.testing.assert_allclose(got[name][..., :length], want[name],
                                   rtol=1e-4, atol=1e-5)
        assert not np.any(np.asarray(got[name][..., length:]))


def test_reused_slot_holds_nothing_of_its_last_occupant(model):
    """(b) A slot released and admitted again holds only the new request:
    its state equals a fresh pool's after the same admission."""
    cfg, _, params32 = model
    used = SlotEngine(params32, cfg, slots=1, capacity=96)
    fresh = SlotEngine(params32, cfg, slots=1, capacity=96)
    used.admit(Request(_tokens(1, 60), 3))
    while used.live_slots:
        used.step()
    later = Request(_tokens(2, 9), 4)
    used.admit(later)
    fresh.admit(later)
    for a, b in zip(jax.tree_util.tree_leaves(used._state),
                    jax.tree_util.tree_leaves(fresh._state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("t_len,with_s0", [(256, False), (600, False), (300, True)])
def test_chunked_ssd_equals_recurrence(t_len, with_s0):
    """(d) The chunked SSD at chunk 256 (the program's prefill) equals the
    sequential recurrence, over one chunk, over a padded third chunk, and
    from a non-zero state; fp32 order of operations only."""
    b, h, p, n = 2, 4, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(t_len), 6)
    x = jax.random.normal(ks[0], (b, t_len, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t_len, h)) - 3.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.7))
    bm = jax.random.normal(ks[3], (b, t_len, 1, n))
    cm = jax.random.normal(ks[4], (b, t_len, 1, n))
    s0 = (jax.random.normal(ks[5], (b, h, p, n)) if with_s0
          else jnp.zeros((b, h, p, n)))
    y, s_f = ssm.ssd_chunked(x, dt, a, bm, cm, s0=s0, chunk=256)

    fold = lambda v: jnp.moveaxis(v, 2, 1).reshape(b * h, t_len, *v.shape[3:])
    rep = lambda v: jnp.repeat(v, h, axis=2)
    y_ref, s_ref = ssd_scan_ref(
        fold(x), fold(dt[..., None])[..., 0],
        fold((dt * a)[..., None])[..., 0], fold(rep(bm)), fold(rep(cm)),
        s0.reshape(b * h, p, n))
    np.testing.assert_allclose(
        y, jnp.moveaxis(y_ref.reshape(b, h, t_len, p), 1, 2), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s_f, s_ref.reshape(b, h, p, n), rtol=1e-4, atol=1e-4)


def test_spans_and_counters_are_recorded(model):
    """(e) Admission, step and read-back spans; prompt tokens, tokens out,
    live slots per step and programs built, counted."""
    cfg, _, params32 = model
    before = telemetry.snapshot()
    engine = SlotEngine(params32, cfg, slots=2, capacity=64)
    engine.compile()
    reqs = [Request(_tokens(i, n), m) for i, (n, m) in enumerate([(5, 3), (9, 2), (4, 4)])]
    outs = generate(engine, reqs)
    after = telemetry.snapshot()

    def grew(name):
        return after["counters"].get(name, 0) - before["counters"].get(name, 0)

    def spans(name):
        return (after["spans"].get(name, {}).get("count", 0)
                - before["spans"].get(name, {}).get("count", 0))

    assert [len(o[0]) for o in outs] == [3, 2, 4]
    assert grew("lm.prefill_tokens") == 18
    assert grew("lm.tokens_out") == 9
    # the first token comes from the admission: steps emit the rest
    assert grew("lm.slot_steps") == 9 - 3
    assert grew("lm.programs_built") == 2   # the 256 rung and the step
    assert spans("lm.admit") == 3
    assert spans("lm.step") >= 3
    assert spans("lm.sync") == 3 + spans("lm.step")


def test_the_control_rounds_the_ssd_state():
    """The reference's bf16-state control is its recurrence with each
    step's state rounded to bf16: apart by more than fp32 order, and by
    less than a different recurrence would be."""
    rng = np.random.default_rng(0)
    b, t, h, p, n = 2, 64, 4, 8, 16
    xh = rng.standard_normal((b, t, h, p)).astype(np.float32)
    dt = rng.uniform(1e-3, 1e-1, (b, t, h)).astype(np.float32)
    a = -rng.uniform(1.0, 16.0, h).astype(np.float32)
    bm, cm = (rng.standard_normal((b, t, n)).astype(np.float32) for _ in "bc")
    exact = np.asarray(ref._ssd(xh, dt, a, bm, cm, False))
    control = np.asarray(ref._ssd(xh, dt, a, bm, cm, True))
    rel = np.abs(control - exact).max() / np.abs(exact).max()
    # bf16 keeps 8 significant bits: 2**-9 a rounding, compounded by the
    # recurrence; fp32 order alone would be ~1e-7
    assert 1e-4 < rel < 1e-1
