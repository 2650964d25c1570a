"""Serving launcher: LM decode or GW anomaly streaming.

LM mode (batched prefill + decode):

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --reduced \
        --prompt-len 16 --new-tokens 16

An arch whose sequences carry recurrent state beside their KV cache
(``family="seq_hybrid"``: granite-4.0-h-micro) is served by the slot
engine (``serve/slots.py``): ``--batch`` slots, each prefilled on
admission and decoded at its own length; ``2 * --batch`` requests of
``--prompt-len`` prompt and ``--new-tokens`` output tokens pass through
them, and the run ends with the host time by stage:

    PYTHONPATH=src python -m repro.launch.serve --mode lm \
        --arch granite-4.0-h-micro --batch 48 --prompt-len 512 \
        --new-tokens 192

Anomaly mode (the paper's use case — persistent-state B=1 streaming on the
fused stack, weights pre-packed at engine init, state donated per chunk;
short chunks ride the ``fused_step`` low-latency step kernel):

    PYTHONPATH=src python -m repro.launch.serve --mode anomaly \
        --gw-model gw_small --windows 50 --chunk 25 --weight-dtype int8

``--weight-dtype {fp32,bf16,int8}`` picks the fused stack's VMEM weight
storage (int8: per-gate symmetric scales in SMEM, fp32 cell carry kept).
``--placement {local,sharded}`` routes through ``plan_stack``: sharded
places fused sub-stacks on mesh devices (``fused_stack_sharded``).
``--chunk-len N`` overrides the plan's step-kernel threshold (chunks with
T <= N run the one-grid-step kernel instead of the wavefront).
``--streams N`` serves N *independent* streams through the multi-stream
coalescer: every chunk advances all N with ONE gathered B=N step call
(``push_many``) instead of N B=1 pushes.
``--server`` runs the continuous-batching ``StreamServer`` instead of the
synchronous loops: a synthetic Poisson-arrival driver submits chunks for
``--streams`` independent streams at ``--arrival-hz`` aggregate rate
(0 = as fast as possible, the saturation test) and the deadline scheduler
coalesces whatever is pending into ``push_many`` batches
(``--deadline-us`` fixed budget, ``--max-coalesce`` gather cap,
``--overflow`` backpressure policy).  ``--adaptive`` replaces the fixed
deadline with the self-tuning policy: per-bucket arrival-rate EWMAs pick
a deadline that fills the batch with high probability (capped by
``--max-deadline-us``), flushing immediately when every joined stream is
already pending or the batch cannot fill within the cap.  Per-chunk
latency from enqueue to the end of its ``push_many`` lands in a fixed-bin
histogram; the run prints p50/p99/max plus the scheduler's tick, flush,
batch-fill, and drop counters.  Both anomaly loops end with the host time
by stage: p50/p99 and count of each span of ``repro/telemetry.py`` (in
server mode over the run: submit, queue wait, scheduling, tick, the
engine's step, zero-state creation, window finish and its sync,
delivery; without it, the calibration's ``score`` call and, with
``--streams``, ``push_many``), with the zero states created, the
programs built and the layer-0 forms of the wavefront kernel, counted
once per distinct kernel trace.
``--sanitize {off,reject,hold,reset}`` screens every submitted chunk for
NaN/Inf (and ``--saturation-limit``) before it can enter a batch, with
the chosen quarantine policy; ``--checkpoint PATH`` snapshots the engine
(every ``--checkpoint-interval-s`` seconds, from the scheduler thread)
so a crashed server can resume; ``--restore PATH`` restores the engine
from such a snapshot before serving (geometry/weight-dtype fingerprint
checked — see ``serve/health.py``).  Any of these flags also turns on
the post-step state watchdog and supervised scheduler restarts; the run
then prints the health counters (rejected/held/resets/restarts/...).
``--plan-only`` prints the resolved execution plan for both segments
(backend, placement, weight dtype, pack bytes) and exits without scoring —
the dryrun-style smoke for serving configs.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import telemetry
from repro.configs import get_arch
from repro.kernels.lstm_stack.lstm_stack import LAYER0_FORMS
from repro.latency import LatencyHistogram
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import get_model
from repro.serve.engine import LmEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("lm", "anomaly"), default="lm")
    # lm mode
    ap.add_argument("--arch", help="LM arch id (lm mode)")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    # anomaly mode
    ap.add_argument("--gw-model", default="gw_small",
                    help="GW_MODELS key (anomaly mode)")
    ap.add_argument("--windows", type=int, default=50)
    ap.add_argument("--chunk", type=int, default=0,
                    help="chunk length per push; 0 = full windows")
    ap.add_argument("--fpr", type=float, default=0.01)
    ap.add_argument("--weight-dtype", choices=("fp32", "bf16", "int8"),
                    default=None,
                    help="fused-stack weight storage (anomaly mode); int8 "
                         "keeps per-layer dequant scales in SMEM and shrinks "
                         "VMEM-resident weights ~4x")
    ap.add_argument("--weight-dtypes", default=None, metavar="D0,D1,...",
                    help="per-layer weight storage (comma list, one entry "
                         "per LSTM layer, e.g. int8,fp32,fp32,int8); a "
                         "heterogeneous assignment routes both segments "
                         "through the mixed backend")
    ap.add_argument("--placement", choices=("local", "sharded"),
                    default="local",
                    help="fused-stack stage placement (anomaly mode): "
                         "'sharded' runs fused sub-stacks on mesh devices "
                         "with ppermute hand-off (fused_stack_sharded)")
    ap.add_argument("--tune", choices=("default", "cached", "balanced"),
                    default="default",
                    help="'cached' resolves plan knobs from the autotune "
                         "store (runs/autotune/tuned.json; populate with "
                         "python -m repro.launch.tune) — --plan-only shows "
                         "which knobs came from the cache; 'balanced' (mixed "
                         "backend only) lets the roofline model pick the "
                         "int8/fp32 split that equalizes per-stage cost")
    ap.add_argument("--chunk-len", type=int, default=None,
                    help="step-kernel threshold: pushes with T <= chunk_len "
                         "run the low-latency step kernel (default: the "
                         "plan's DEFAULT_CHUNK_LEN)")
    ap.add_argument("--streams", type=int, default=1,
                    help="number of independent streams; > 1 coalesces "
                         "them into one B=N step call per chunk "
                         "(push_many)")
    ap.add_argument("--plan-only", action="store_true",
                    help="resolve and print the execution plan (backend, "
                         "weight dtype, pack bytes) without scoring")
    # continuous-batching server mode
    ap.add_argument("--server", action="store_true",
                    help="serve through the continuous-batching "
                         "StreamServer (arrival queue + deadline "
                         "coalescer) with a Poisson-arrival driver")
    ap.add_argument("--deadline-us", type=float, default=200.0,
                    help="fixed coalescing budget: flush as soon as the "
                         "oldest pending chunk is this old (server mode; "
                         "ignored under --adaptive)")
    ap.add_argument("--max-coalesce", type=int, default=8,
                    help="most streams gathered into one step call, "
                         "honored exactly (partial batches are padded up "
                         "the bounded program-shape ladder separately)")
    ap.add_argument("--adaptive", action="store_true",
                    help="self-tuning scheduler: pick each bucket's "
                         "deadline from the observed arrival rate (EWMA "
                         "over inter-arrival gaps) and let the effective "
                         "coalescing width adapt between ticks")
    ap.add_argument("--max-deadline-us", type=float, default=500.0,
                    help="adaptive mode's hard cap on the chosen deadline "
                         "(no chunk waits longer than this for its batch "
                         "to fill)")
    ap.add_argument("--overflow", choices=("block", "drop_oldest", "error"),
                    default="block",
                    help="bounded-queue backpressure policy (server mode)")
    ap.add_argument("--queue-capacity", type=int, default=4096,
                    help="arrival queue bound (server mode)")
    ap.add_argument("--arrival-hz", type=float, default=0.0,
                    help="aggregate Poisson chunk-arrival rate across the "
                         "fleet; 0 submits as fast as possible (server "
                         "mode saturation test)")
    # fault tolerance (server mode; any of these enables the health layer)
    ap.add_argument("--sanitize", choices=("off", "reject", "hold", "reset"),
                    default="off",
                    help="per-chunk NaN/Inf/saturation quarantine policy "
                         "applied in submit, before a chunk can enter a "
                         "coalesced batch (server mode)")
    ap.add_argument("--saturation-limit", type=float, default=None,
                    help="|x| above this screens as a saturated glitch "
                         "(with --sanitize; default: amplitude unchecked)")
    ap.add_argument("--checkpoint", default=None, metavar="PATH",
                    help="periodically snapshot the engine (streams, "
                         "partial windows, threshold) to PATH from the "
                         "scheduler thread (server mode)")
    ap.add_argument("--checkpoint-interval-s", type=float, default=5.0,
                    help="seconds between --checkpoint snapshots")
    ap.add_argument("--restore", default=None, metavar="PATH",
                    help="restore the engine from a snapshot before "
                         "serving (fingerprint-checked; server mode)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.mode == "anomaly":
        return serve_anomaly(args)

    if not args.arch:
        ap.error("--arch is required in lm mode")
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    if cfg.family == "seq_hybrid":
        return serve_slots(args, params, cfg)

    engine = LmEngine(params, cfg, max_len=args.prompt_len + args.new_tokens)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)).astype(np.int32)

    t0 = time.perf_counter()
    out = engine.generate(prompts, args.new_tokens)
    dt = time.perf_counter() - t0
    tok_s = args.batch * args.new_tokens / dt
    print(f"{args.arch}: generated {out.shape} in {dt:.2f}s "
          f"({tok_s:.1f} tok/s on this host)")
    print("sample:", out[0][:12].tolist())


def serve_slots(args, params, cfg):
    """Requests through the slot engine, every slot kept busy."""
    from repro.serve.slots import Request, SlotEngine, generate

    engine = SlotEngine(params, cfg, slots=args.batch,
                        capacity=args.prompt_len + args.new_tokens)
    t0 = time.perf_counter()
    engine.compile()
    t_compile = time.perf_counter() - t0
    rng = np.random.default_rng(0)
    requests = [
        Request(rng.integers(0, cfg.vocab, args.prompt_len).astype(np.int32),
                args.new_tokens)
        for _ in range(2 * args.batch)]
    t0 = time.perf_counter()
    out = [tokens for tokens, _, _ in generate(engine, requests)]
    dt = time.perf_counter() - t0
    n_out = sum(len(o) for o in out)
    print(f"{args.arch}: {len(out)} requests through {args.batch} slots, "
          f"{n_out} tokens in {dt:.2f}s ({n_out / dt:.1f} tok/s on "
          f"{jax.devices()[0].device_kind}); programs built in "
          f"{t_compile:.1f}s")
    print("sample:", out[0][:12].tolist())
    print_stages(lm=True)


def serve_anomaly(args):
    """Continuous B=1 strain scoring with resident state (paper Table III)."""
    import dataclasses

    from repro.configs.gw import GW_MODELS
    from repro.core.autoencoder import init_autoencoder
    from repro.data.gw import GwDataConfig, GwDataset
    from repro.serve.engine import StreamingAnomalyEngine

    cfg = GW_MODELS[args.gw_model]
    if args.weight_dtype is not None:
        cfg = dataclasses.replace(cfg, weight_dtype=args.weight_dtype)
    if args.weight_dtypes is not None or args.tune == "balanced":
        # per-layer storage (and the model-chosen split) only execute on
        # the heterogeneous backend — pin it so resolve_impl keeps it
        wds = None
        if args.weight_dtypes is not None:
            wds = tuple(
                None if w in ("", "native") else w
                for w in args.weight_dtypes.split(",")
            )
        cfg = dataclasses.replace(cfg, weight_dtypes=wds, impl="mixed")
    params = init_autoencoder(jax.random.PRNGKey(0), cfg)

    if args.plan_only:
        return print_plan(args, params, cfg)

    ds = GwDataset(GwDataConfig(timesteps=cfg.timesteps))

    if args.server:
        return serve_server(args, params, cfg, ds)

    engine = StreamingAnomalyEngine(
        params, cfg, batch=1, placement=args.placement,
        chunk_len=args.chunk_len, tune=args.tune,
        impl=("mixed" if cfg.impl == "mixed" else "fused_step"),
    )
    packed = engine._packed_enc
    if packed is None:
        wd = "n/a"
    elif isinstance(packed, tuple):  # mixed: one pack per segment
        wd = "+".join(p.weight_dtype for p in packed)
    else:
        wd = packed.weight_dtype
    print(f"{args.gw_model}: impl={engine.effective_impl} "
          f"(requested fused_step), placement={args.placement}, "
          f"weights={wd}, window={engine.window}, "
          f"chunk_len={engine._exec_enc.plan.chunk_len}")
    thr = engine.calibrate(ds.background(256), fpr=args.fpr)
    print(f"calibrated threshold ({args.fpr:.0%} FPR): {thr:.4f}")

    chunk = args.chunk or cfg.timesteps
    rng = np.random.default_rng(1)
    lat, flagged = [], 0
    if args.streams > 1:
        # the fleet shape: N independent streams, ONE coalesced step call
        # per chunk (push_many gathers their states into the batch axis)
        ids = [f"stream-{i}" for i in range(args.streams)]
        for _ in range(args.windows):
            w = np.concatenate([
                ds.events(1) if rng.random() < 0.1 else ds.background(1)
                for _ in ids
            ])
            t0 = time.perf_counter()
            scores = {sid: [] for sid in ids}
            for pos in range(0, cfg.timesteps, chunk):
                res = engine.push_many(ids, w[:, pos : pos + chunk])
                for sid in ids:
                    scores[sid] += res[sid]
            lat.append(time.perf_counter() - t0)
            flagged += sum(int(scores[sid][0][0] > thr) for sid in ids)
    else:
        for _ in range(args.windows):
            w = ds.events(1) if rng.random() < 0.1 else ds.background(1)
            t0 = time.perf_counter()
            scores = []
            for pos in range(0, cfg.timesteps, chunk):
                scores += engine.push(w[:, pos : pos + chunk])
            lat.append(time.perf_counter() - t0)
            flagged += int(scores[0][0] > thr)
    warmup = min(5, len(lat) - 1)  # keep at least one sample
    hist = LatencyHistogram()
    hist.record_many(np.asarray(lat[warmup:]) * 1e6)
    tag = f", {args.streams} coalesced streams" if args.streams > 1 else ""
    print(f"{args.windows} windows ({chunk}-sample chunks{tag}): "
          f"{flagged} flagged; latency p50={hist.percentile(50):.0f}us "
          f"p99={hist.percentile(99):.0f}us "
          f"max={hist.max_us:.0f}us on this host")
    print_stages()


def serve_server(args, params, cfg, ds):
    """Continuous-batching serving: Poisson arrivals through the deadline
    coalescer (``serve/server.py``), scheduler metrics as the output."""
    from repro.serve.engine import StreamingAnomalyEngine
    from repro.serve.health import HealthConfig
    from repro.serve.server import AdaptiveConfig, ServerConfig, StreamServer

    engine = StreamingAnomalyEngine(
        params, cfg, batch=1, placement=args.placement,
        chunk_len=args.chunk_len, tune=args.tune,
        impl=("mixed" if cfg.impl == "mixed" else "fused_step"),
    )
    health = None
    if args.sanitize != "off" or args.checkpoint or args.restore:
        health = HealthConfig(
            sanitize=args.sanitize,
            saturation_limit=args.saturation_limit,
            checkpoint_path=args.checkpoint,
            checkpoint_interval_s=(
                args.checkpoint_interval_s if args.checkpoint else None
            ),
        )
    server_cfg = ServerConfig(
        max_coalesce=args.max_coalesce,
        deadline_us=args.deadline_us,
        queue_capacity=args.queue_capacity,
        overflow=args.overflow,
        adaptive=(AdaptiveConfig(max_deadline_us=args.max_deadline_us)
                  if args.adaptive else None),
        health=health,
    )
    if args.restore:
        server = StreamServer.restart_from(args.restore, engine, server_cfg)
        print(f"restored engine from {args.restore}: "
              f"{len(engine.stream_ids)} stream(s) resident, "
              f"threshold={engine.threshold}")
    else:
        server = StreamServer(engine, server_cfg)
    n_streams = max(1, args.streams)
    chunk = args.chunk or cfg.timesteps
    rng = np.random.default_rng(2)

    # each stream serves --windows windows, chopped into fixed chunks; the
    # fleet's chunks arrive in one Poisson-merged order (random stream
    # picked per arrival, each stream's own chunks in order)
    queues = []
    for _ in range(n_streams):
        w = np.concatenate([
            ds.events(1) if rng.random() < 0.1 else ds.background(1)
            for _ in range(args.windows)
        ], axis=1)[0]  # (windows*T, input_dim)
        queues.append([w[pos : pos + chunk]
                       for pos in range(0, w.shape[0], chunk)])
    total_chunks = sum(len(q) for q in queues)

    policy = (f"adaptive (deadline <= {args.max_deadline_us:.0f}us from "
              "arrival-rate EWMA)" if args.adaptive
              else f"fixed deadline={args.deadline_us:.0f}us")
    print(f"{args.gw_model}: StreamServer impl={engine.effective_impl}, "
          f"{n_streams} streams x {args.windows} windows "
          f"({chunk}-sample chunks, {total_chunks} total), "
          f"{policy} max_coalesce={server.config.max_coalesce} "
          f"overflow={args.overflow}"
          + (f", ~{args.arrival_hz:.0f} chunks/s Poisson"
             if args.arrival_hz > 0 else ", max-rate arrivals"))

    # compile the full-batch step + batched decode shapes before timing:
    # the latency histogram should measure scheduling, not the first
    # tick's trace/compile stall
    warm_ids = [f"warm-{i}" for i in range(server.config.max_coalesce)]
    for pos in range(0, engine.window, chunk):
        t = min(chunk, engine.window - pos)
        engine.push_many(warm_ids, np.zeros(
            (len(warm_ids), t, cfg.input_dim), np.float32))
    for wid in warm_ids:
        engine.drop_stream(wid)

    # the stage line covers the run, not the warm-up; the layer-0 forms are
    # facts of the kernel traces made, so the warm-up's carry over
    forms = {k: n for k, n in telemetry.snapshot()["counters"].items()
             if k.startswith("wavefront.layer0_")}
    telemetry.reset()
    for name, n in forms.items():
        telemetry.count(name, n)
    t0 = time.perf_counter()
    with server:
        live = [i for i, q in enumerate(queues) if q]
        while live:
            i = live[int(rng.integers(len(live)))]
            server.submit(f"stream-{i}", queues[i].pop(0))
            if not queues[i]:
                live.remove(i)
            if args.arrival_hz > 0:
                time.sleep(rng.exponential(1.0 / args.arrival_hz))
    wall = time.perf_counter() - t0

    scores = server.pop_scores()
    n_scores = sum(len(v) for v in scores.values())
    s = server.stats
    print(f"{total_chunks} chunks -> {n_scores} window scores in "
          f"{wall:.2f}s ({total_chunks / wall:.0f} chunks/s)")
    print(f"scheduler: {s.ticks} ticks ({s.full_flushes} full, "
          f"{s.deadline_flushes} deadline, {s.fastpath_flushes} fastpath, "
          f"{s.drain_flushes} drain), {s.drops} dropped, batch fill "
          f"{dict(sorted(s.batch_fill.items()))}"
          + (f", effective width {server.effective_coalesce}"
             if args.adaptive else ""))
    print(f"enqueue->push_many latency: p50={s.latency.percentile(50):.0f}us "
          f"p99={s.latency.percentile(99):.0f}us "
          f"max={s.latency.max_us:.0f}us over {s.latency.count} chunks")
    print_stages()
    if health is not None:
        print(f"health: {s.rejected} rejected, {s.held} held, "
              f"{s.sanitize_resets} sanitize resets, "
              f"{s.watchdog_resets} watchdog resets, "
              f"{s.holddown_suppressed} scores held down, "
              f"{s.engine_errors} engine errors, "
              f"{s.callback_errors} callback errors, "
              f"{s.scheduler_restarts} scheduler restarts, "
              f"{s.checkpoints} checkpoints"
              + (f" -> {args.checkpoint}" if args.checkpoint else ""))


def print_stages(lm: bool = False) -> None:
    """One line: the host time of each serving stage (p50/p99 us and
    count, from ``repro/telemetry.py``) and the recorder's counters: for
    the slot engine (``lm``) its ``lm.*`` counters, else the stream
    engine's, among them the wavefront kernel's layer-0 forms, one count
    per distinct kernel trace (``wavefront.layer0_<form>``)."""
    snap = telemetry.snapshot()
    stages = ", ".join(
        f"{name} {st['p50_us']:.0f}/{st['p99_us']:.0f}us x{st['count']}"
        for name, st in snap["spans"].items()
    )
    counters = snap["counters"]
    if lm:
        print(f"host time by stage (p50/p99, count): {stages}; "
              + ", ".join(f"{name} {counters.get(name, 0)}" for name in (
                  "lm.prefill_tokens", "lm.tokens_out", "lm.slot_steps",
                  "lm.programs_built")))
        return
    forms = ", ".join(
        f"{form} {counters.get(f'wavefront.layer0_{form}', 0)}"
        for form in LAYER0_FORMS
    )
    print(f"host time by stage (p50/p99, count): {stages}; "
          f"{counters.get('engine.states_created', 0)} zero states created, "
          f"{counters.get('engine.programs_built', 0)} programs built, "
          f"wavefront layer-0 forms traced: {forms}")


def print_plan(args, params, cfg) -> None:
    """Dryrun-style smoke: resolve both segment plans, bind, print, exit.

    Exercises the full plan->bind path (legality, packing, placement) so a
    bad serving config fails here with a plan-time error — but never runs
    a scoring step.
    """
    from repro.core.backends import resolve_impl
    from repro.core.autoencoder import segment_executors

    requested = "mixed" if cfg.impl == "mixed" else "fused_step"
    cfg, effective, reason = resolve_impl(cfg, requested)
    if reason is not None:
        print(f"note: {reason}")
    exec_enc, exec_dec = segment_executors(
        params, cfg, impl=effective, placement=args.placement,
        chunk_len=args.chunk_len, tune=args.tune,
    )
    print(f"{args.gw_model}: resolved serving plan "
          f"(window={cfg.timesteps}, requested {requested}, "
          f"tune={args.tune})")
    for name, ex in (("encoder", exec_enc), ("decoder", exec_dec)):
        print(f"  {name}: {ex.plan.describe()} "
              f"pack_bytes={ex.packed_bytes}")
        # per-knob provenance: which values a serving engine would really
        # run, and whether each came from the tuned cache, an explicit
        # flag, or the hand-set default
        for knob, (value, source) in sorted(
            ex.plan.knob_provenance().items()
        ):
            shown = "auto" if value is None else value
            print(f"    {knob:<10} = {shown!s:<6} [{source}]")
        if ex.plan.backend.heterogeneous:
            # the mixed plan's defining output: which storage each layer
            # resolved to, and which chain segment (stage) executes it
            src = dict(ex.plan.knob_sources).get("weight_dtype", "default")
            for row in ex.plan.layer_assignment():
                print(f"    layer {row['layer']} (hidden={row['hidden']:<3})"
                      f" -> {row['weight_dtype']:<5} "
                      f"stage={row['stage']} "
                      f"chunk_len={row['chunk_len']} [{src}]")


if __name__ == "__main__":
    main()
