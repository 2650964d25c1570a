# Tier-1 verification + benchmark entry points.  The tests run on the CPU
# (JAX_PLATFORMS=cpu: Pallas kernels in interpret mode, plus compile-only
# rehearsals for a v5e in tests/test_tpu_compile.py).  The chip run is
# `python chip_smoke.py [--chips 4]` on a TPU host.

PYTHON ?= python
PYTEST = JAX_PLATFORMS=cpu $(PYTHON) -m pytest
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test test-fast test-serve test-quant test-exec test-step test-server test-chaos test-autotune test-mixed tune bench-kernels bench-stream bench-quant bench-exec bench-step bench-server bench-autotune bench-mixed bench

test:
	$(PYTEST) -x -q

# skip the slow end-to-end training test
test-fast:
	$(PYTEST) -x -q --deselect tests/test_gw_e2e.py

# the stateful streaming serving path (equivalence, cache, donation)
test-serve:
	$(PYTEST) -x -q tests/test_serve_streaming.py

# the quantized packed-weight fused stack (grid, kernel, cache, serving)
test-quant:
	$(PYTEST) -x -q tests/test_quant_stack.py

# the plan/bind/execute API (plans, executors, sharded fused wavefront)
test-exec:
	$(PYTEST) -x -q tests/test_executor.py

# the low-latency step kernel + multi-stream coalescing (bitwise contract)
test-step:
	$(PYTEST) -x -q tests/test_step_kernel.py

# the continuous-batching stream server (deadline coalescer, backpressure,
# scheduler determinism, latency histogram)
test-server:
	$(PYTEST) -x -q tests/test_stream_server.py

# the fault-injection suite (glitch quarantine, engine faults + watchdog,
# snapshot/restore, scheduler supervision, close-vs-batch race)
test-chaos:
	$(PYTEST) -x -q tests/test_chaos.py

# the autotune subsystem (knob spaces, tuned-plan cache, cached planning,
# sweep harness, roofline model, HLO custom-call costs)
test-autotune:
	$(PYTEST) -x -q tests/test_autotune.py

# the heterogeneous mixed backend (per-layer storage splits, segment
# chaining bit-equality, balancer, act_bits, tuned split, mixed serving)
test-mixed:
	$(PYTEST) -x -q tests/test_mixed_stack.py

# measure the standard smoke grid on THIS machine and populate the
# tuned-plan cache (runs/autotune/tuned.json) that `--tune cached` serving
# reads; run on the hardware you serve on
tune:
	$(PYTHON) -m repro.launch.tune --smoke

# kernel + pipeline + streaming-serve rows, with the machine-readable artifact
bench-kernels:
	$(PYTHON) -m benchmarks.run --only kernels_bench,pipeline_balance,stream --json BENCH_kernels.json

# fast path: just the streaming B=1 vs batch serving rows
bench-stream:
	$(PYTHON) -m benchmarks.run --only stream --json BENCH_stream.json

# quant.* rows (packed bytes ratio, fused latency, AUC parity, serving gate)
# merged into the shared artifact next to the kernel rows
bench-quant:
	$(PYTHON) -m benchmarks.run --only quant --json BENCH_kernels.json --merge

# exec.* rows (dispatch overhead, pack gate, sharded wavefront) merged
# into the shared artifact next to the kernel + quant rows
bench-exec:
	$(PYTHON) -m benchmarks.run --only exec --json BENCH_kernels.json --merge

# step.* rows (streamed-vs-batch gap gate, T=1 kernel latency, coalescing
# bit-equality gate) merged into the shared artifact
bench-step:
	$(PYTHON) -m benchmarks.run --only step --json BENCH_kernels.json --merge

# server.* / serve.* rows (fleet throughput gate >= 3x at 64 streams,
# p50/p99 under load, scheduler bit-equality gate) merged into the artifact
bench-server:
	$(PYTHON) -m benchmarks.run --only server --json BENCH_kernels.json --merge

# autotune.* rows (smoke sweep best-vs-default hard gate >= 1.0x, roofline
# model-gated predicted-vs-measured rows) merged into the artifact.  CI
# runs this BEFORE bench-kernels (which rewrites BENCH_kernels.json), so
# it redirects to its own artifact with AUTOTUNE_JSON=BENCH_autotune.json.
AUTOTUNE_JSON ?= BENCH_kernels.json
bench-autotune:
	$(PYTHON) -m benchmarks.run --only autotune --json $(AUTOTUNE_JSON) --merge

# mixed.* rows (chained bit-equality hard gate, measured-best split vs
# best homogeneous hard gate >= 1.0x, fitted-balancer gate=model row)
# merged into the shared artifact
bench-mixed:
	$(PYTHON) -m benchmarks.run --only mixed --json BENCH_kernels.json --merge

bench:
	$(PYTHON) -m benchmarks.run --fast --json BENCH_kernels.json
