"""Slot engine: continuous batching for a model whose sequences carry
recurrent state (``models.seq_hybrid``: Mamba-2 SSD state and conv window
beside attention KV).

A fixed number of slots live on the device in one pool (``seq_hybrid``'s
layout), with, per slot, the token that goes in next and the ids whose
log-probabilities the request asked for.  Three operations:

* ``admit(request)`` prefills the prompt into a free slot: one program per
  rung of a prompt-length ladder (multiples of 256 up to 2048).  The prompt
  is right-padded to its rung; padding leaves the state exactly as the
  unpadded prompt would.  The prefill's last logits give the request's
  first token.
* ``step()`` decodes every slot by one token, each at its own length, in
  one program: the greedy token, its log-probability and the
  log-probabilities at the request's ids, all gathered on the device.
  A slot whose request has all its tokens is released; EOS is not looked
  for, so a request's ``max_new`` sets its length.
* ``release(slot)`` frees a slot by hand.

Programs are compiled ahead of time (``compile``: every rung and the
step); the pool and the per-slot tokens are donated to each program, so
serving allocates nothing.  Results stay on the device until
``Emitted.fetch``, so a caller can dispatch the next step before reading
the last.

Spans and counters (``repro/telemetry.py``): ``lm.admit`` (an admission's
prefill and state write, as dispatched), ``lm.step`` (a decode step's
dispatch), ``lm.sync`` (reading emitted tokens back); ``lm.prefill_tokens``
(prompt tokens admitted), ``lm.tokens_out`` (tokens emitted to requests),
``lm.slot_steps`` (live slots summed over steps), ``lm.programs_built``.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.configs.base import ArchConfig
from repro.models import seq_hybrid

#: prompt lengths are padded up to the next rung
PREFILL_LADDER = tuple(range(256, 2049, 256))
#: log-probabilities a request may ask for besides its emitted token's
MAX_LOGPROB_IDS = 16


@dataclass
class Request:
    prompt: np.ndarray                   # (n,) token ids
    max_new: int                         # tokens to generate
    logprob_ids: np.ndarray = field(     # up to MAX_LOGPROB_IDS ids
        default_factory=lambda: np.zeros(0, np.int32))


@dataclass
class Emitted:
    """What one program emitted for ``slots``, on the device until
    ``fetch``: per row the token, its log-probability and the
    log-probabilities at the request's ids (``rows[i]`` is slot
    ``slots[i]``'s row)."""

    slots: tuple
    rows: tuple
    arrays: tuple

    def fetch(self):
        """(tokens (n,), logprobs (n,), id logprobs (n, 16)) as numpy."""
        with telemetry.span("lm.sync"):
            tok, lp, ids = (np.asarray(a) for a in self.arrays)
        r = list(self.rows)
        return tok[r], lp[r], ids[r]


def _emit(logits, ids):
    """Greedy token, its log-probability, log-probabilities at ``ids``."""
    lp = jax.nn.log_softmax(logits, axis=-1)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return (tok, jnp.take_along_axis(lp, tok[:, None], axis=-1)[:, 0],
            jnp.take_along_axis(lp, ids, axis=-1))


def admit_program(params, state, tokens, length, slot, ids, *, cfg):
    """Prefill one right-padded prompt (1, L) into ``slot``."""
    logits, seq = seq_hybrid.prefill_states(params, tokens, length[None], cfg)
    out = _emit(logits, ids[None])
    return {
        "pool": seq_hybrid.write_slot(state["pool"], seq, slot, length),
        "tok": state["tok"].at[slot].set(out[0][0]),
        "ids": state["ids"].at[slot].set(ids),
    }, out


def step_program(params, state, *, cfg):
    """Decode every slot by one token."""
    logits, pool = seq_hybrid.decode(params, state["pool"], state["tok"], cfg)
    out = _emit(logits, state["ids"])
    return dict(state, pool=pool, tok=out[0]), out


def init_state(cfg: ArchConfig, slots: int, capacity: int) -> dict:
    return {
        "pool": seq_hybrid.init_cache(cfg, slots, capacity),
        "tok": jnp.zeros((slots,), jnp.int32),
        "ids": jnp.zeros((slots, MAX_LOGPROB_IDS), jnp.int32),
    }


def lower(cfg: ArchConfig, key, params, state, sharding=None):
    """The program ``key`` ("step", or a ladder rung for an admission)
    lowered for ``params`` and ``state``: arrays, or ``ShapeDtypeStruct``s
    (then the admission's small arguments are placed by ``sharding``)."""
    if key == "step":
        fn, args = step_program, ()
    else:
        spec = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                                  sharding=sharding)
        fn = admit_program
        args = (spec((1, key)), spec(()), spec(()), spec((MAX_LOGPROB_IDS,)))

    def program(p, s, *a):
        return fn(p, s, *a, cfg=cfg)

    # the module's name in a profile: jit_lm_step, jit_lm_admit_<rung>
    program.__name__ = "lm_step" if key == "step" else f"lm_admit_{key}"
    return jax.jit(program, donate_argnums=(1,)).lower(params, state, *args)


def rung(n: int) -> int:
    """The ladder's rung for a prompt of ``n`` tokens."""
    for r in PREFILL_LADDER:
        if n <= r:
            return r
    raise ValueError(f"prompt of {n} tokens exceeds the ladder's "
                     f"{PREFILL_LADDER[-1]}")


class SlotEngine:
    """``slots`` sequences of up to ``capacity`` tokens (prompt and
    output), served together."""

    def __init__(self, params, cfg: ArchConfig, *, slots: int, capacity: int):
        self.params, self.cfg = params, cfg
        self.slots, self.capacity = slots, capacity
        self._state = init_state(cfg, slots, capacity)
        self._programs: dict = {}
        self._free = list(range(slots - 1, -1, -1))
        self._live: dict[int, list] = {}   # slot -> [request, tokens left]

    # -- programs -------------------------------------------------------------

    def _program(self, key):
        prog = self._programs.get(key)
        if prog is None:
            prog = self._programs[key] = lower(
                self.cfg, key, self.params, self._state).compile()
            telemetry.count("lm.programs_built")
        return prog

    def compile(self) -> None:
        """Build every program serving can call: each rung that fits the
        capacity, and the step."""
        top = rung(min(self.capacity, PREFILL_LADDER[-1]))
        for r in PREFILL_LADDER:
            if r <= top:
                self._program(r)
        self._program("step")

    # -- serving --------------------------------------------------------------

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def live_slots(self) -> tuple:
        return tuple(sorted(self._live))

    def admit(self, req: Request) -> tuple[int, Emitted]:
        """Prefill ``req`` into a free slot; its first token is emitted."""
        n = len(req.prompt)
        if not 0 < n or n + req.max_new - 1 > self.capacity or req.max_new < 1:
            raise ValueError(f"prompt {n} + {req.max_new} new tokens do not "
                             f"fit a slot of {self.capacity}")
        if len(req.logprob_ids) > MAX_LOGPROB_IDS:
            raise ValueError(f"at most {MAX_LOGPROB_IDS} logprob ids")
        if not self._free:
            raise RuntimeError("no free slot")
        slot = self._free.pop()
        with telemetry.span("lm.admit"):
            width = rung(n)
            tokens = np.zeros((1, width), np.int32)
            tokens[0, :n] = req.prompt
            ids = np.zeros(MAX_LOGPROB_IDS, np.int32)
            ids[:len(req.logprob_ids)] = req.logprob_ids
            self._state, out = self._program(width)(
                self.params, self._state, tokens, np.int32(n), np.int32(slot),
                ids)
        telemetry.count("lm.prefill_tokens", n)
        telemetry.count("lm.tokens_out")
        self._live[slot] = [req, req.max_new - 1]
        if req.max_new == 1:
            self.release(slot)
        return slot, Emitted((slot,), (0,), out)

    def step(self) -> tuple[Emitted, tuple]:
        """Decode every live slot by one token.  Returns what was emitted
        and the slots whose requests it finished (released)."""
        live = self.live_slots
        with telemetry.span("lm.step"):
            self._state, out = self._program("step")(self.params, self._state)
        telemetry.count("lm.slot_steps", len(live))
        telemetry.count("lm.tokens_out", len(live))
        done = []
        for s in live:
            entry = self._live[s]
            entry[1] -= 1
            if entry[1] == 0:
                done.append(s)
                self.release(s)
        return Emitted(live, live, out), tuple(done)

    def release(self, slot: int) -> None:
        del self._live[slot]
        self._free.append(slot)


def generate(engine: SlotEngine, requests) -> list[tuple]:
    """Serve ``requests`` to the end, keeping every slot busy while any
    wait.  Per request, in request order: its greedy tokens (n,), their
    log-probabilities (n,) and the log-probabilities at its ids (n, 16)."""
    pending = list(enumerate(requests))[::-1]
    owner: dict[int, int] = {}
    rows: list[list] = [[] for _ in pending]

    def admit_all():
        while pending and engine.free_slots:
            i, req = pending.pop()
            slot, em = engine.admit(req)
            rows[i].append([a[0] for a in em.fetch()])
            if slot in engine.live_slots:   # not finished by its first token
                owner[slot] = i

    admit_all()
    while owner:
        em, done = engine.step()
        for s, *row in zip(em.slots, *em.fetch()):
            rows[owner[s]].append(row)
        for s in done:
            del owner[s]
        admit_all()
    return [tuple(np.asarray(col) for col in zip(*r)) for r in rows]
