"""Closed-loop chat serving through the program's slot engine
(``repro.serve.slots.SlotEngine``), for a configuration whose sequences
carry recurrent state (``architecture`` ``granite_hybrid``).

Traffic file keys: ``requests`` (a fixed list of ``[prompt, output]``
token lengths), ``lead_s`` (seconds of traffic before the window, counted
in set-up), ``tracked`` (requests whose log-probabilities are compared
with the reference) and ``logprob_ids`` (ids each request names).  The
configuration gives ``slots`` and ``slot_capacity``.

Every slot is kept busy: a finished request's slot takes the next request
of the list at once.  The seed chooses the weights, the token ids, the ids
each request names and where in the list the run starts, and nothing else,
so every run does the same work.  Each loop admits into every free slot,
dispatches one decode step of every slot, then reads back the step before
(so the chip has the next step queued while the host reads).

``windows_per_s`` counts generated tokens: every token emitted by an
admission or a step dispatched in the window, over the window's seconds
(the last step read back).  For this cell one "window" is one generated
token.  ``attempted`` is the requests admitted in the window; ``failed``
the requests that finished in it with fewer tokens read back than their
output length.  Compared with the reference: ``tracked`` requests chosen
by the seed among those that finished in the window, every generated
position, the probabilities at their named ids and at the emitted id.
"""

from __future__ import annotations

import gc
import time
from contextlib import nullcontext

import numpy as np

from perfbench import harness

#: the program's device modules (``slots.lower`` names them)
STEP_MODULE, ADMIT_MODULE = "jit_lm_step", "jit_lm_admit_"
MODULES_LINE = "XLA Modules"


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` from the published keys."""
    import jax.numpy as jnp
    from repro.configs.base import ArchConfig

    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return ArchConfig(
        name=cfg["name"], family="seq_hybrid",
        n_layers=cfg["num_hidden_layers"], d_model=d, n_heads=heads,
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["shared_intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=d // heads, tie_embeddings=cfg["tie_word_embeddings"],
        norm_eps=cfg["rms_norm_eps"], ssm_state=cfg["mamba_d_state"],
        ssm_head_dim=cfg["mamba_d_head"], ssm_expand=cfg["mamba_expand"],
        ssm_groups=cfg["mamba_n_groups"], conv_kernel=cfg["mamba_d_conv"],
        ssm_chunk=cfg["mamba_chunk_size"], conv_bias=cfg["mamba_conv_bias"],
        layer_types=tuple(cfg["layer_types"]),
        attention_multiplier=cfg["attention_multiplier"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=cfg["logits_scaling"], dtype=jnp.dtype(cfg["dtype"]))


class RequestList:
    """The traffic's fixed lengths, from an offset chosen by the seed; the
    ids of each request drawn from the seed as it is made."""

    def __init__(self, traffic: dict, vocab: int, rng):
        self.pairs = [tuple(p) for p in traffic["requests"]]
        self.n_ids = traffic["logprob_ids"]
        self.vocab, self.rng = vocab, rng
        self.offset = int(rng.integers(len(self.pairs)))
        self.made = 0

    def lengths(self, i: int) -> tuple:
        return self.pairs[(self.offset + i) % len(self.pairs)]

    def next(self):
        from repro.serve.slots import Request

        n, m = self.lengths(self.made)
        self.made += 1
        ints = lambda k: self.rng.integers(0, self.vocab, k).astype(np.int32)
        return Request(ints(n), m, ints(self.n_ids))


class Loop:
    """The closed loop: every slot busy, each step read back one loop
    later.  ``rows[r]`` holds request ``r``'s (token, logprob, id logprobs)
    as read back; ``released`` the requests in the order they finished."""

    def __init__(self, engine, requests: RequestList, annotate):
        self.engine, self.requests, self.annotate = engine, requests, annotate
        self.reqs: list = []
        self.rows: list[list] = []
        self.owner: dict[int, int] = {}       # slot -> request
        self.released: list[int] = []         # requests, as they finished
        self.pending: list = []               # (Emitted, owners) unread
        self.loops = 0
        self.tokens = 0                       # emitted by dispatches
        self.admitted = 0
        self.steps = self.slot_steps = self.context = 0
        self.prefill_tokens = self.prefill_context = 0
        self.length: dict[int, int] = {}      # slot -> tokens it holds

    def _admit(self) -> None:
        eng = self.engine
        while eng.free_slots:
            req = self.requests.next()
            r = len(self.reqs)
            self.reqs.append(req)
            self.rows.append([])
            with self.annotate("pb.admit"):
                slot, em = eng.admit(req)
            n = len(req.prompt)
            self.pending.append((em, (r,)))
            self.tokens += 1
            self.admitted += 1
            self.prefill_tokens += n
            self.prefill_context += n * (n + 1) // 2
            if slot in eng.live_slots:
                self.owner[slot] = r
                self.length[slot] = n
            else:
                self.released.append(r)

    def _step(self) -> None:
        live = self.engine.live_slots
        if not live:
            return
        with self.annotate("pb.step"):
            em, done = self.engine.step()
        self.pending.append((em, tuple(self.owner[s] for s in em.slots)))
        self.steps += 1
        self.slot_steps += len(live)
        self.tokens += len(live)
        for s in live:   # the token enters at the slot's length and sees it
            self.length[s] += 1
            self.context += self.length[s]
        for s in done:
            self.released.append(self.owner.pop(s))
            del self.length[s]

    def _read(self, n: int) -> None:
        """Read back the ``n`` oldest dispatches."""
        for em, owners in self.pending[:n]:
            with self.annotate("pb.fetch"):
                cols = em.fetch()
            for r, *row in zip(owners, *cols):
                self.rows[r].append(row)
        del self.pending[:n]

    def tick(self) -> None:
        """Admit into every free slot, dispatch a step, read what the loop
        before dispatched."""
        earlier = len(self.pending)
        self._admit()
        self._step()
        self._read(earlier)
        self.loops += 1

    def drain(self) -> None:
        self._read(len(self.pending))

    def counters(self) -> dict:
        return {k: getattr(self, k) for k in (
            "loops", "tokens", "admitted", "steps", "slot_steps", "context",
            "prefill_tokens", "prefill_context")}


def module_device_s(trace_dir, lo: float, hi: float) -> dict:
    """Device seconds and count of the step and admission modules in
    ``[lo, hi]``, from the ``XLA Modules`` line of chip 0's plane."""
    from jax.profiler import ProfileData

    from perfbench.trace import DEVICE_PLANE_PREFIX

    found = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    out = {"step_device_s": 0.0, "step_modules": 0,
           "admit_device_s": 0.0, "admit_modules": 0}
    if not found:
        return out
    data = ProfileData.from_file(str(found[-1]))
    for plane in data.planes:
        if (not plane.name.startswith(DEVICE_PLANE_PREFIX) or plane.name[
                len(DEVICE_PLANE_PREFIX):].split()[0] != "0"):
            continue
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for ev in line.events:
                a = max(ev.start_ns, lo)
                b = min(ev.start_ns + ev.duration_ns, hi)
                if b <= a:
                    continue
                key = ("step" if ev.name.startswith(STEP_MODULE) else
                       "admit" if ev.name.startswith(ADMIT_MODULE) else None)
                if key:
                    out[f"{key}_device_s"] += (b - a) * 1e-9
                    out[f"{key}_modules"] += 1
    return out


def _recorder() -> dict:
    from perfbench import telemetry

    return telemetry.snapshot() or {"spans": {}, "counters": {}}


def _grew(before: dict, after: dict) -> dict:
    """The program's counters and span totals over the window."""
    out = {}
    for name, n in after["counters"].items():
        if name.startswith("lm."):
            out[name] = n - before["counters"].get(name, 0)
    for name, st in after["spans"].items():
        if name.startswith("lm."):
            was = before["spans"].get(name, {})
            out[name + ".count"] = st["count"] - was.get("count", 0)
            out[name + ".total_s"] = st["total_s"] - was.get("total_s", 0.0)
    return out


def run(ctx) -> "harness.Outcome":
    import jax

    from repro.serve.slots import SlotEngine

    cell, seed = ctx.cell, ctx.seed
    cfg, tr = cell.config, cell.traffic
    annotate = jax.profiler.TraceAnnotation if ctx.trace else \
        (lambda name: nullcontext())

    params = cell.model.init_params(seed, cfg)
    jax.block_until_ready(params)
    rng = np.random.default_rng(seed)
    requests = RequestList(tr, cfg["vocab_size"], rng)
    engine = SlotEngine(params, arch_config(cfg), slots=cfg["slots"],
                        capacity=cfg["slot_capacity"])
    engine.compile()
    loop = Loop(engine, requests, annotate)
    t_lead = time.perf_counter()
    while time.perf_counter() - t_lead < tr["lead_s"]:
        loop.tick()
    loop.drain()

    before, c0 = _recorder(), ctx.compiles.snapshot()
    n0, r0 = loop.counters(), len(loop.released)
    if ctx.trace:
        jax.profiler.start_trace(str(ctx.trace_dir))
    try:
        with annotate("pb.window"):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < ctx.seconds:
                loop.tick()
            loop.drain()
            t1 = time.perf_counter()
        c1, after = ctx.compiles.snapshot(), _recorder()
    finally:
        if ctx.trace:
            jax.profiler.stop_trace()
    mem = harness.memory_peak(ctx.chips)
    n1 = loop.counters()
    win = {k: n1[k] - n0[k] for k in n1}
    grew = _grew(before, after)
    finished = loop.released[r0:]
    failed = [r for r in finished
              if len(loop.rows[r]) < loop.reqs[r].max_new]
    pick = np.random.default_rng(
        [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, 1])
    tracked = sorted(pick.choice(finished, min(tr["tracked"], len(finished)),
                                 replace=False).tolist()) if finished else []

    values = {"windows_per_s": win["tokens"] / (t1 - t0)}
    info = [
        {"line": "compiles_in_window", **{k: c1[k] - c0[k] for k in c0},
         "programs_built": grew.get("lm.programs_built", 0)},
        {"line": "tokens", **win, "window_s": t1 - t0,
         "offset": requests.offset, "finished_in_window": len(finished),
         "tracked": tracked},
    ]
    layer = None
    if ctx.trace:
        from perfbench.trace import Trace

        trace = Trace.from_dir(ctx.trace_dir)
        lo, hi = trace.window()
        modules = module_device_s(ctx.trace_dir, lo, hi)
        info.append({"line": "trace", **modules})
        layer = harness.LayerRun(
            cell=cell, trace=trace, lo=lo, hi=hi, chips=ctx.chips,
            device_kind=jax.devices()[0].device_kind,
            counts={**win, **grew, **modules})

    windows, got = [], []
    for r in tracked:
        req, rows = loop.reqs[r], loop.rows[r]
        tok, lp, ids = (np.asarray(c) for c in zip(*rows))
        windows.append({"prompt": req.prompt, "emitted": tok,
                        "ids": req.logprob_ids})
        got.append(np.exp(np.concatenate(
            [ids.astype(np.float64), lp.astype(np.float64)[:, None]],
            axis=1)).ravel())
    del engine, loop
    gc.collect()
    return harness.Outcome(
        params=params, attempted=win["admitted"], missing=len(failed),
        windows=windows,
        scores=np.concatenate(got) if got else np.zeros(0),
        values=values, setup_s=t0 - ctx.t_start, memory_peak_bytes=mem,
        info=info, layer=layer)
