"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes, prove memory fit, and emit roofline inputs.

``main`` pins 512 placeholder host devices before JAX first looks for
devices (jax locks the device count on first init); importing the module
pins nothing, so a test may import ``compile_slot_programs``.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multipod-only-train4k]
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh pod --out runs/
    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-4.0-h-micro

An arch served by the slot engine (``serve/slots.py``), given without
``--shape``, has its slot programs (every prefill rung and the decode
step, 48 slots of 2560 tokens) compiled for one described v5e chip at its
published widths, with the memory each program needs.

Per cell it reports/serializes:
    bytes per device (arguments / outputs / temps from memory_analysis),
    HLO_flops raw (cost_analysis) + scan-corrected dot FLOPs (analysis.hlo),
    collective schedule (per-op-type bytes, scan-corrected),
    and writes the per-cell JSON consumed by benchmarks/roofline_table.py.
"""

import argparse
import os
import gc
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo import analyze_hlo, cost_analysis_dict
from repro.configs import ARCHS, SHAPES, cell_supported, get_arch
from repro.launch.mesh import data_axes, make_production_mesh
from repro.launch.sharding import (
    batch_shardings,
    cache_shardings,
    opt_shardings,
    param_shardings,
)
from repro.models.api import abstract_cache, abstract_params, get_model, input_specs
from repro.models.layers import ShardCtx
from repro.train.optimizer import AdamWConfig, init_opt_state


def _ctx(mesh, variant: str | None = None) -> ShardCtx:
    residual = "seq" if variant == "seq_residual" else "d"
    if variant in ("dp_all", "dp_all_compress"):  # model axis -> extra DP
        return ShardCtx(mesh=mesh, data_axes=(*data_axes(mesh), "model"),
                        model_axis=None, residual=residual)
    return ShardCtx(mesh=mesh, data_axes=data_axes(mesh), residual=residual)


def build_cell(arch_name: str, shape_name: str, mesh, variant: str | None = None):
    """Returns (jitted_fn, example_args) for one cell.

    ``variant`` selects a §Perf hillclimb configuration:
      fsdp_once    — gather FSDP weights once per step (outside the
                     microbatch loop) instead of per microbatch
      fp8_cache    — KV cache stored in float8_e4m3 (decode shapes)
      replicated   — pure data-parallel params (no FSDP; small models)
      compress     — bf16 gradient compression with error feedback
    """
    cfg = get_arch(arch_name)
    shape = SHAPES[shape_name]
    api = get_model(cfg)
    ctx = _ctx(mesh, variant)
    params_abs = abstract_params(cfg)

    if shape.kind == "train":
        if variant == "replicated":
            p_sh = jax.tree_util.tree_map(
                lambda _: NamedSharding(mesh, P()), params_abs
            )
        elif variant in ("dp_all", "dp_all_compress"):
            p_sh = param_shardings(mesh, params_abs, mode="dp")
        else:
            p_sh = param_shardings(mesh, params_abs, mode="train")
        opt_cfg = AdamWConfig(compress_grads=(variant in ("compress", "dp_all_compress")))
        opt_abs = jax.eval_shape(lambda p: init_opt_state(p, opt_cfg), params_abs)
        if variant == "replicated":
            o_sh = jax.tree_util.tree_map(
                lambda _: NamedSharding(mesh, P()), opt_abs
            )
        else:
            o_sh = opt_shardings(mesh, opt_abs, p_sh,
                                 mode="dp" if variant in ("dp_all", "dp_all_compress") else "train")
        batch_abs = input_specs(cfg, shape)
        b_sh = batch_shardings(
            mesh, batch_abs, shape,
            extra_axes=("model",) if variant in ("dp_all", "dp_all_compress") else (),
        )
        from repro.train.step import make_train_step

        loss = lambda p, b: api.loss_fn(p, b, cfg, ctx)
        if variant == "fsdp_once":
            # constrain weights to 1-D (model-only) sharding INSIDE the
            # step: the all-gather from the FSDP layout becomes loop-
            # invariant w.r.t. the microbatch scan and is hoisted to run
            # once per step instead of once per microbatch
            gather_sh = param_shardings(mesh, params_abs, mode="serve")

            def loss(p, b):  # noqa: F811
                p = jax.tree_util.tree_map(
                    lambda x, s: jax.lax.with_sharding_constraint(x, s),
                    p, gather_sh,
                )
                return api.loss_fn(p, b, cfg, ctx)

        mbs = cfg.train_microbatches
        if variant == 'mb2':
            mbs = max(mbs // 2, 1)
        train_step = make_train_step(loss, opt_cfg, microbatches=mbs)
        fn = jax.jit(
            train_step,
            in_shardings=(p_sh, o_sh, b_sh),
            out_shardings=(NamedSharding(mesh, P()), p_sh, o_sh),
            donate_argnums=(0, 1),
        )
        return fn, (params_abs, opt_abs, batch_abs)

    # serve_2d only helps DECODE (weights resident vs per-layer gathers);
    # in prefill XLA hoists the gather of the loop-invariant stacked
    # weights out of the layer scan, materializing all layers at once
    serve_mode = ("serve_2d" if cfg.serve_2d and shape.kind == "decode"
                  else "serve")
    p_sh = param_shardings(mesh, params_abs, mode=serve_mode)

    if shape.kind == "prefill":
        batch_abs = input_specs(cfg, shape)
        b_sh = batch_shardings(mesh, batch_abs, shape)

        def prefill_fn(params, batch):
            return api.prefill(params, batch, cfg, None, ctx)

        fn = jax.jit(prefill_fn, in_shardings=(p_sh, b_sh))
        return fn, (params_abs, batch_abs)

    # decode: one token against a seq_len cache
    cache_abs = abstract_cache(cfg, shape)
    if variant == "fp8_cache":
        import jax.numpy as _jnp

        cache_abs = jax.tree_util.tree_map(
            lambda a: (jax.ShapeDtypeStruct(a.shape, _jnp.float8_e4m3fn)
                       if a.dtype == _jnp.bfloat16 else a),
            cache_abs,
        )
    if variant == "naive_cache":
        # counterfactual baseline: batch-only cache sharding (no sequence
        # sharding) — what a naive GPU-style port would do
        from jax.sharding import NamedSharding as _NS, PartitionSpec as _P
        from repro.launch.mesh import data_axes as _da

        da = _da(mesh)

        def _naive(path, leaf):
            if leaf.ndim == 0:
                return _NS(mesh, _P())
            if leaf.ndim >= 2 and leaf.shape[1] == shape.global_batch:
                return _NS(mesh, _P(None, da, *(None,) * (leaf.ndim - 2)))
            return _NS(mesh, _P(*(None,) * leaf.ndim))

        c_sh = jax.tree_util.tree_map_with_path(_naive, cache_abs)
    else:
        c_sh = cache_shardings(mesh, cache_abs, cfg, shape)
    batch_abs = input_specs(cfg, shape)
    b_sh = batch_shardings(mesh, batch_abs, shape)

    def decode_fn(params, cache, batch):
        return api.decode_step(params, cache, batch, cfg, ctx)

    fn = jax.jit(
        decode_fn,
        in_shardings=(p_sh, c_sh, b_sh),
        out_shardings=(NamedSharding(mesh, P()), c_sh),
        donate_argnums=(1,),
    )
    return fn, (params_abs, cache_abs, batch_abs)


def run_cell(arch_name: str, shape_name: str, multi_pod: bool,
             out_dir: Path | None = None, keep_hlo: bool = False,
             variant: str | None = None) -> dict:
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    cell_id = f"{arch_name}.{shape_name}.{mesh_name}"
    if variant:
        cell_id += f".{variant}"
    cfg = get_arch(arch_name)
    shape = SHAPES[shape_name]
    ok, reason = cell_supported(cfg, shape)
    rec = {
        "cell": cell_id, "arch": arch_name, "shape": shape_name,
        "mesh": mesh_name, "chips": 512 if multi_pod else 256,
    }
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        fn, args = build_cell(arch_name, shape_name, mesh, variant=variant)
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = cost_analysis_dict(compiled)
        text = compiled.as_text()
        hlo = analyze_hlo(text)

        rec.update(
            status="ok",
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            memory={
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            },
            cost_analysis_raw={
                "flops": cost.get("flops"),
                "bytes_accessed": cost.get("bytes accessed"),
            },
            hlo_dot_flops=hlo.dot_flops,
            collective_bytes=dict(hlo.collective_bytes),
            collective_count=hlo.collective_count,
            cpu_convert_artifact_bytes=hlo.convert_artifact_bytes,
            n_params=cfg.n_params(),
            n_active_params=cfg.n_active_params(),
        )
        if keep_hlo and out_dir is not None:
            (out_dir / f"{cell_id}.hlo.txt").write_text(text)
        del compiled, lowered, fn
        gc.collect()
    except Exception as e:  # a failing cell is a bug — surface it loudly
        rec.update(status="FAILED", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
    if out_dir is not None:
        out_dir.mkdir(parents=True, exist_ok=True)
        (out_dir / f"{cell_id}.json").write_text(json.dumps(rec, indent=1))
    return rec


def compile_slot_programs(arch_name: str, device, *, slots: int = 48,
                          capacity: int = 2560, rungs=None) -> dict:
    """Compile the slot engine's programs for ``arch_name`` at its published
    widths for ``device`` (a described chip; nothing is allocated):
    ``{program: memory_analysis}`` for each ladder rung (``rungs``, default
    all) and the decode step."""
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    from repro.serve import slots as slot_engine

    cfg = get_arch(arch_name)
    sharding = SingleDeviceSharding(device)
    # row-major, as the engine's arrays are: left to choose, the compiler
    # picks layouts that no array on the chip has
    place = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=Format(Layout(tuple(range(a.ndim))), sharding)),
        tree)
    params = place(abstract_params(cfg))
    state = place(jax.eval_shape(
        lambda: slot_engine.init_state(cfg, slots, capacity)))
    out = {}
    for key in (*(rungs or slot_engine.PREFILL_LADDER), "step"):
        compiled = slot_engine.lower(cfg, key, params, state, sharding).compile()
        out[key] = compiled.memory_analysis()
    return out


def _slot_dryrun(arch_name: str) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for key, mem in compile_slot_programs(arch_name, topo.devices[0]).items():
        print(f"[ok] {arch_name} slot program {key}: "
              f"args={mem.argument_size_in_bytes / 2**30:.2f}GiB "
              f"temp={mem.temp_size_in_bytes / 2**30:.2f}GiB "
              f"alias={mem.alias_size_in_bytes / 2**30:.2f}GiB", flush=True)


def main():
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("XLA_FLAGS", "")
    )
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"], default="both")
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--variant", default=None)
    args = ap.parse_args()
    out_dir = Path(args.out)

    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]
    cells = []
    if args.all:
        for arch in sorted(ARCHS):
            for shape in SHAPES:
                cells.append((arch, shape))
    elif args.arch and not args.shape and get_arch(args.arch).family == "seq_hybrid":
        return _slot_dryrun(args.arch)
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            # skip cells whose JSON already exists (resumable sweep)
            mesh_name = "multipod_2x16x16" if mp else "pod_16x16"
            done = out_dir / f"{arch}.{shape}.{mesh_name}.json"
            if args.all and done.exists():
                rec = json.loads(done.read_text())
                print(f"[cached] {rec['cell']}: {rec['status']}")
                continue
            rec = run_cell(arch, shape, mp, out_dir, args.keep_hlo,
                           variant=args.variant)
            ok = rec["status"]
            extra = ""
            if ok == "ok":
                mb = (rec["memory"]["argument_bytes"] or 0) / 2**20
                adj = ((rec["memory"]["temp_bytes"] or 0)
                       - rec.get("cpu_convert_artifact_bytes", 0)) / 2**20
                extra = (f" args={mb:.0f}MiB/dev temp="
                         f"{(rec['memory']['temp_bytes'] or 0) / 2**20:.0f}MiB"
                         f" (tpu-adj={adj:.0f}MiB)"
                         f" dotF={rec['hlo_dot_flops']:.2e}"
                         f" coll={sum(rec['collective_bytes'].values()):.2e}B"
                         f" compile={rec['compile_s']}s")
            elif ok == "FAILED":
                failures += 1
                extra = " " + rec["error"][:160]
            print(f"[{ok}] {rec['cell']}{extra}", flush=True)
    if failures:
        raise SystemExit(f"{failures} cells FAILED")


if __name__ == "__main__":
    main()
