"""Executor API rows: dispatch overhead, steady-state pack gate, sharding.

Four claims the execution API must keep true, as rows in the shared
``BENCH_kernels.json`` artifact (``make bench-exec`` merges them):

* ``exec.bound_call_us`` vs ``exec.direct_call_us`` — a jitted call through
  a bound ``StackExecutor`` (executor as a pytree argument) against the
  kernel-level ``lstm_stack_forward_fused`` jitted directly: both lower to
  the same fused kernel, so the executor indirection must cost ~nothing
  (``exec.dispatch_ratio`` row; interpret-mode CPU noise dominates it).
* ``exec.packs_steady`` — steady-state executor calls re-trace and re-pack
  ZERO times (reuses ``core.pipeline.PACK_TRACE_COUNT``; hard gate like the
  streaming benchmark's).
* ``exec.step_dispatch_ratio`` — the executor's bind-time-cached jitted
  step (``StackExecutor.step_jit``: bound arrays are jit constants,
  per-call dispatch flattens only (xs, state)) vs jitting the identical
  kernel call by hand.  **Hard-gated at <= 1.10** — the pre-PR5 pattern
  (executor as a jit pytree argument) measured 1.456x
  (``exec.dispatch_ratio``); a bound step that re-grows a dispatch tax
  regresses the serving hot path.
* ``exec.sharded_wavefront_us`` — the ``fused_stack_sharded`` backend on
  the devices present (in-process; one device gives a 1-stage mesh)
  alongside the local fused backend, gated on bit-equality.
  Interpret-mode timings are correctness-grade; on real hardware the
  sharded win is VMEM capacity and per-stage weight residency, not CPU
  wall clock.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import pipeline
from repro.core.executor import plan_stack
from repro.core.lstm import LstmConfig, init_lstm
from repro.kernels.lstm_stack.ops import lstm_stack_forward_fused

DIMS = [(1, 32), (32, 32), (32, 32), (32, 32)]


def _timeit(f, *a, n=20):
    jax.block_until_ready(f(*a))  # warm up / compile
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*a)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def _sharded_row(cfgs, params, xs) -> tuple:
    """The sharded backend on the devices this process holds, in-process:
    a JAX child started after this process touched JAX could not reach an
    accelerator the parent already holds.  With one device the stage mesh
    is the degenerate 1-stage one (still routed through shard_map)."""
    import numpy as np

    local = plan_stack(cfgs, impl="fused_stack").bind(params)
    sharded = plan_stack(
        cfgs, impl="fused_stack", placement="sharded"
    ).bind(params)
    n_stages = sharded.plan.mesh.shape["stage"]
    run_ex = jax.jit(lambda ex, x: ex(x, return_state=False))
    us_l = _timeit(run_ex, local, xs, n=5)
    us_s = _timeit(run_ex, sharded, xs, n=5)
    equal = int(
        (np.asarray(run_ex(sharded, xs)) == np.asarray(run_ex(local, xs))).all()
    )
    platform = jax.devices()[0].platform
    print(f"fused_stack_sharded ({n_stages}-stage {platform} mesh, 4L W32 "
          f"T100): {us_s:.0f}us vs local fused {us_l:.0f}us, "
          f"bit-equal={'OK' if equal else 'FAIL'}")
    if not equal:  # hard gate: the sharded backend must match local exactly
        raise RuntimeError(
            "fused_stack_sharded diverged from the local fused backend"
        )
    return ("exec.sharded_wavefront_us", us_s,
            f"local={us_l:.0f}us|stages={n_stages}|equal={equal}")


def run() -> list[tuple]:
    rows = []
    print("\n== executor API: dispatch overhead + pack/trace gates ==")
    cfgs = [LstmConfig(in_dim=a, hidden=b) for a, b in DIMS]
    keys = jax.random.split(jax.random.PRNGKey(0), len(DIMS))
    params = [init_lstm(k, c) for k, c in zip(keys, cfgs)]
    xs = jax.random.normal(jax.random.PRNGKey(1), (8, 100, 1))

    ex = plan_stack(cfgs, impl="fused_stack").bind(params)
    f_exec = jax.jit(lambda e, x: e(x, return_state=False))
    f_direct = jax.jit(
        lambda ps, x: lstm_stack_forward_fused(ps, x, cfgs)[0]
    )
    us_exec = _timeit(f_exec, ex, xs)
    us_direct = _timeit(f_direct, params, xs)
    ratio = us_exec / us_direct
    print(f"bound executor call : {us_exec:8.0f} us")
    print(f"direct shim call    : {us_direct:8.0f} us  "
          f"(executor/direct = {ratio:.3f}x)")
    rows.append(("exec.bound_call_us", us_exec, ""))
    rows.append(("exec.direct_call_us", us_direct, ""))
    rows.append(("exec.dispatch_ratio", 0.0, f"ratio={ratio:.3f}"))

    # -- streaming step dispatch: bound jitted step vs hand-jitted kernel ---
    from repro.kernels.lstm_stack.step import lstm_stack_step_op

    ex_step = plan_stack(cfgs, impl="fused_step").bind(params)
    packed = ex_step.packed
    bound = ex_step.step_jit(donate=False)
    f_direct_step = jax.jit(
        lambda xs, state: lstm_stack_step_op(
            packed.pad_input(xs), packed.stacked, state[0], state[1],
            acts=packed.acts, weight_dtype=packed.weight_dtype,
        )[1:]
    )
    x1 = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1))
    state = ex_step.zero_state(1)
    # interleave the two timed loops and keep the best of 3 rounds each:
    # the ratio gate must not flake on scheduler noise
    best_b, best_d = float("inf"), float("inf")
    for _ in range(3):
        best_b = min(best_b, _timeit(bound, x1, state, n=50))
        best_d = min(best_d, _timeit(f_direct_step, x1, state, n=50))
    step_ratio = best_b / best_d
    print(f"bound step call     : {best_b:8.0f} us")
    print(f"direct step call    : {best_d:8.0f} us  "
          f"(bound/direct = {step_ratio:.3f}x, gate <= 1.10)")
    rows.append(("exec.step_bound_us", best_b, ""))
    rows.append(("exec.step_direct_us", best_d, ""))
    rows.append(("exec.step_dispatch_ratio", 0.0,
                 f"ratio={step_ratio:.3f}|ok={int(step_ratio <= 1.10)}"))
    if step_ratio > 1.10:  # hard gate: the bound step must stay dispatch-free
        raise RuntimeError(
            f"exec.step_dispatch_ratio {step_ratio:.3f} > 1.10 — the bound "
            "jitted step re-grew a dispatch tax over a direct kernel call"
        )

    # steady-state: repeated bound-executor calls must re-pack zero times
    before = pipeline.PACK_TRACE_COUNT
    for _ in range(5):
        jax.block_until_ready(f_exec(ex, xs))
    packs_steady = pipeline.PACK_TRACE_COUNT - before
    ok = packs_steady == 0
    print(f"pack traces across 5 steady-state executor calls: {packs_steady} "
          f"({'OK' if ok else 'REGRESSION'})")
    rows.append(("exec.packs_steady", 0.0,
                 f"packs_steady={packs_steady}|ok={int(ok)}"))
    if not ok:  # hard gate, like bench.stream_b1_vs_batch
        raise RuntimeError(
            f"steady-state executor calls re-traced pack_lstm_stack "
            f"{packs_steady}x — the bind-once contract regressed"
        )

    rows.append(_sharded_row(cfgs, params, xs))
    return rows


if __name__ == "__main__":
    run()
