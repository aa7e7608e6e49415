"""Serving launcher: batched prefill + greedy decode, plus the FFT-conv
network serving path (whole-net planning + prepared kernels).

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-2.7b --smoke \
        --batch 4 --prompt-len 32 --gen 16

    # the paper's VGG conv trunk through plan_network/prepare:
    PYTHONPATH=src python -m repro.launch.serve --convnet vgg --smoke \
        --batch 2 --gen 4

    # continuous batching: shape-bucketed dynamic batcher over per-bucket
    # prepared plans on a synthetic ragged Poisson trace
    # (repro.launch.batcher; --serve-compare A/Bs the pad-to-max and
    # re-plan-per-shape baselines and asserts the bucketed engine wins):
    PYTHONPATH=src python -m repro.launch.serve --convnet vgg --smoke \
        --serve-trace --max-batch 4 --replicas 1 --serve-compare
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_NAMES, get_config
from repro.launch.env import compile_cache_dir
from repro.models import lm as LM
from repro.models import whisper as WH
from repro.train import make_prefill_step, make_decode_step


# Table-I VGG entries chain into a sequential trunk with a 2x2 max-pool
# after each of these layers (the Table geometries already reflect it).
VGG_POOL_AFTER = frozenset(
    {"Vconv1.2", "Vconv2.2", "Vconv3.2", "Vconv4.2", "Vconv5"})


def _vgg_scale(image):
    """Table-I VGG geometries scaled to a square ``image`` input."""
    from repro.configs.paper_convs import TABLE1
    if image % 32:
        raise SystemExit("--image must be a multiple of 32 (5 pool halvings)")
    return [dataclasses.replace(l, H=l.H * image // 224,
                                W=l.W * image // 224)
            for l in TABLE1 if l.name.startswith("V")]


def vgg_forward(biases):
    """Prepared-network forward for the VGG trunk: chained prepared
    layers with fused bias+ReLU epilogues, 2x2 max-pool after each
    block (closure-held biases are batch-independent, so one callable
    serves every bucket)."""
    def forward(prepared, x):
        from repro.models.layers import maxpool2x2
        for name in prepared:
            x = prepared[name](x, bias=biases[name])
            if name in VGG_POOL_AFTER:
                x = maxpool2x2(x)
        return x
    return forward


def serve_convnet(args):
    """Serve the paper's VGG conv trunk through the network planner.

    The whole net is planned once (``plan_network``), every kernel is
    transformed once per weights version (``NetworkPlan.prepare``), and each
    request batch runs through the prepared, epilogue-fused plans —
    the serving lifecycle the ROADMAP north-star targets.  A weight
    update is one invalidation sweep (new ``weights_version``).
    ``--serve-trace`` switches to the continuous-batching engine
    (``repro.launch.batcher``) on a synthetic ragged trace.
    """
    from repro.configs.paper_convs import network_convs
    from repro.conv import autotune, plan_network, prepared_cache_info

    if args.serve_trace:
        return serve_trace(args)

    image = args.image if args.image else (64 if args.smoke else 224)
    scale = _vgg_scale(image)
    layers = network_convs(scale, args.batch)
    backend = "tuned" if args.tune else args.conv_backend
    t0 = time.time()
    net = plan_network(layers, backend=backend, overlap=args.overlap)
    if args.tune:
        # the tuned planning sweep IS the cache warm-up: every distinct
        # layer geometry was measured (or served from the persistent
        # cache) before the first request executes
        print(f"autotune sweep: {time.time() - t0:.1f}s "
              f"(cache: {autotune.cache_path()})")
        for name, r in net.tuning_report().items():
            us = "cached/unmeasured" if r["us_per_call"] is None \
                else f"{r['us_per_call']:.0f}us"
            print(f"  {name}: {r['backend']}/{r['schedule']} "
                  f"bm={r['bm']} bn={r['bn']} bk={r['bk']} "
                  f"dft_bt={r['dft_bt']} overlap={r['overlap']} "
                  f"{us} [{r['source']}]")
    print(net.describe())
    if args.analyze:
        prof = net.analyze().raise_if_failed()
        t = prof.total_collectives
        print(f"plan-lint: OK — {len(prof.layers)} layers certified, "
              f"collectives/pass: all_to_all={t.get('all_to_all', 0)} "
              f"psum={t.get('psum', 0)}, "
              f"peak live ~{prof.peak_live_bytes / 1e6:.1f} MB/rank")

    rng = np.random.default_rng(args.seed)
    def init(shape, s=0.05):
        return jnp.asarray(s * rng.standard_normal(shape), jnp.float32)
    kernels = {n: init(net[n].k_shape) for n in net}
    biases = {n: init((net[n].spec.Cout,)) for n in net}

    forward = vgg_forward(biases)

    t0 = time.time()
    prepared = net.prepare(kernels, weights_version=0)
    t_prepare = time.time() - t0
    x = init((args.batch,) + net[net.layer_names[0]].x_shape[1:], 1.0)
    t0 = time.time()
    if args.timing == "per-request":
        # synchronized per-batch latencies: every iteration blocks, so
        # percentiles describe real request completion, not dispatch
        lats = []
        for _ in range(args.gen):
            tb = time.perf_counter()
            y = forward(prepared, x)
            jax.block_until_ready(y)
            lats.append(time.perf_counter() - tb)
    else:
        # throughput mode: async dispatch, ONE final sync — t_serve is a
        # wall-clock total and per-request latency is NOT derivable
        for _ in range(args.gen):
            y = forward(prepared, x)
        jax.block_until_ready(y)
        lats = None
    t_serve = time.time() - t0

    # weight update -> ONE invalidation sweep; transforms re-run once/layer
    kernels2 = {n: k + 0.01 for n, k in kernels.items()}
    prepared2 = net.prepare(kernels2, weights_version=1)
    jax.block_until_ready(forward(prepared2, x))
    info = prepared_cache_info()
    print(f"convnet=vgg image={image} batch={args.batch} "
          f"prepare={t_prepare*1e3:.0f}ms "
          f"serve={t_serve*1e3:.0f}ms/{args.gen} batches "
          f"(prepared cache: {info.hits} hits, {info.misses} misses, "
          f"{info.invalidations} invalidations)")
    if lats is not None:
        from repro.launch.batcher import _percentile
        print(f"per-request latency: p50={_percentile(lats, 50)*1e3:.1f}ms "
              f"p99={_percentile(lats, 99)*1e3:.1f}ms over {len(lats)} "
              "synchronized batches")
    print("output:", tuple(y.shape), float(jnp.mean(y)))
    return y


def serve_trace(args):
    """Continuous batching on a synthetic ragged Poisson trace.

    Buckets ragged request batches into padded power-of-two shapes,
    plans + prepares one network per bucket at startup, then drains the
    queue through jit-compiled per-bucket executors — zero re-planning
    or re-tracing on the hot path.  ``--serve-compare`` additionally
    replays the SAME trace through the two degenerate strategies the
    seed serve loop forced (pad everything to ``--max-batch``; re-plan
    per exact shape) and asserts the bucketed engine beats both.
    """
    from repro.configs.paper_convs import network_convs
    from repro.launch.batcher import (
        BucketPolicy, ServeEngine, run_trace, synthetic_trace)

    image = args.image if args.image else (64 if args.smoke else 224)
    scale = _vgg_scale(image)

    def make_layers(batch):
        return network_convs(scale, batch)

    rng = np.random.default_rng(args.seed)

    def init(shape, s=0.05):
        return jnp.asarray(s * rng.standard_normal(shape), jnp.float32)

    probe = make_layers(1)
    kernels = {l.name: init(l.k_shape) for l in probe}
    biases = {l.name: init((l.k_shape[0],)) for l in probe}
    forward = vgg_forward(biases)
    backend = "tuned" if args.tune else args.conv_backend

    policy = BucketPolicy(max_batch=args.max_batch)
    trace = synthetic_trace(n_requests=args.trace_requests,
                            max_batch=args.max_batch,
                            rate_rps=args.trace_rate or 1.0,
                            seed=args.seed)
    inputs = {}                     # one array per batch size, reused

    def make_input(batch, image_size):
        if batch not in inputs:
            inputs[batch] = init(
                (batch,) + probe[0].x_shape[1:], 1.0)
        return inputs[batch]

    modes = ("bucketed", "pad-max", "replan") if args.serve_compare \
        else ("bucketed",)
    reports = {}
    engines = {}
    for mode in modes:
        eng = ServeEngine(
            make_layers, kernels, policy=policy, forward=forward,
            replicas=args.replicas,
            window_s=args.batch_window_ms * 1e-3, mode=mode,
            # the A/B compares real completion latencies, so --serve-compare
            # forces synchronized per-batch timing
            timing="async" if (args.timing == "async"
                               and not args.serve_compare) else "per-batch",
            collect_results=False, backend=backend,
            overlap=args.overlap,
            load_plans=(args.load_plans or None) if mode == "bucketed"
            else None)
        t_start = eng.startup_s
        rep = run_trace(eng, trace, make_input=make_input,
                        realtime=args.trace_rate > 0)
        reports[mode] = rep
        engines[mode] = eng
        occ = rep["occupancy"]
        print(f"serve-trace mode={mode} [{eng.plan_source}]: "
              f"startup={t_start:.1f}s "
              f"wall={rep['wall_s']:.3f}s "
              f"tput={rep['throughput_rows_s']:.1f} rows/s "
              f"p50={rep['p50_us']/1e3:.1f}ms p99={rep['p99_us']/1e3:.1f}ms "
              f"occupancy={occ:.2f} "
              f"queue_max={rep['queue_depth_max']} "
              f"plan_misses_after_warmup="
              f"{rep['plan_cache_misses_after_warmup']}")
        for label, b in sorted(rep["buckets"].items()):
            print(f"    {label}: n={b['n_requests']} "
                  f"batches={b['n_batches']} "
                  f"p50={b['p50_us']/1e3:.1f}ms "
                  f"p99={b['p99_us']/1e3:.1f}ms occ={b['occupancy']:.2f}")
        if args.replicas > 1:
            print(f"    replica batches: {rep['replica_batches']}")
    bucketed = engines["bucketed"]
    if bucketed.nets:
        br = bucketed.bucket_report()
        print(f"buckets: {policy.batch_buckets()} x image={image} — "
              f"{br['n_layer_plans']} layer plans, "
              f"{br['n_distinct_plans']} distinct (shared-cache dedupe)")
    else:
        print(f"buckets: {policy.batch_buckets()} x image={image} — "
              f"rehydrated from plan artifact {args.load_plans}")

    if args.export_plans:
        p = bucketed.export_plans(args.export_plans)
        print(f"exported plan artifact: {p}")

    fingerprints_ok = None
    if args.load_plans and bucketed.plan_source == "aot":
        # plan-lint certificate: live re-plan of every stored config must
        # reproduce the export-time PlanProfile fingerprints (run AFTER
        # the trace so the re-plan never pollutes the hot-path miss count
        # snapshotted in the report)
        from repro.conv import export as planx
        v = planx.verify(args.load_plans)
        fingerprints_ok = v["ok"]
        rep = reports["bucketed"]
        fails = []
        if not v["ok"]:
            fails.append(f"export fingerprints diverge from a live "
                         f"re-plan: {v['mismatches']}")
        if rep["plan_cache_misses_after_warmup"] != 0:
            fails.append(
                f"AOT-loaded engine planned on the hot path: "
                f"{rep['plan_cache_misses_after_warmup']} plan-cache "
                "misses after warmup")
        if fails:
            raise SystemExit("load-plans certification FAILED:\n  "
                             + "\n  ".join(fails))
        print(f"load-plans OK: {v['n_checked']} layer fingerprints "
              "match a live re-plan, zero plan-cache misses after "
              "warmup")
    elif args.load_plans:
        print(f"load-plans: artifact fell back to live planning "
              f"(source={bucketed.plan_source})")

    if args.coldstart_out:
        import json
        rep = reports["bucketed"]
        payload = {
            "coldstart_s": bucketed.startup_s,
            "source": bucketed.plan_source,
            "plan_cache_misses_after_warmup":
                rep["plan_cache_misses_after_warmup"],
            "fingerprints_verified": fingerprints_ok,
            "n_buckets": len(policy.batch_buckets()),
            "image": image,
        }
        with open(args.coldstart_out, "w") as fh:
            json.dump(payload, fh, indent=1, sort_keys=True)
        print(f"wrote cold-start report to {args.coldstart_out}")

    if args.bench_out:
        import json
        rows = engines["bucketed"].bench_rows()
        with open(args.bench_out, "w") as fh:
            json.dump(rows, fh, indent=1, sort_keys=True)
        print(f"wrote {len(rows)} serve/* bench rows to {args.bench_out}")

    if args.serve_compare:
        b, pm, rp = (reports[m] for m in
                     ("bucketed", "pad-max", "replan"))
        fails = []
        if not b["throughput_rows_s"] >= 1.05 * pm["throughput_rows_s"]:
            fails.append(
                f"bucketed throughput {b['throughput_rows_s']:.1f} rows/s "
                f"does not beat pad-max {pm['throughput_rows_s']:.1f} "
                "by >= 1.05x")
        if not b["p99_us"] <= rp["p99_us"] / 2:
            fails.append(
                f"bucketed p99 {b['p99_us']/1e3:.1f}ms not <= half of "
                f"replan p99 {rp['p99_us']/1e3:.1f}ms")
        if b["plan_cache_misses_after_warmup"] != 0:
            fails.append(
                f"bucketed engine planned on the hot path: "
                f"{b['plan_cache_misses_after_warmup']} plan-cache misses "
                "after warmup")
        tput_x = b["throughput_rows_s"] / max(pm["throughput_rows_s"],
                                              1e-9)
        print(f"serve-compare: bucketed tput {tput_x:.2f}x pad-max, p99 "
              f"{rp['p99_us']/max(b['p99_us'], 1e-9):.2f}x better than "
              "replan")
        if fails:
            raise SystemExit("serve-compare FAILED:\n  " +
                             "\n  ".join(fails))
        print("serve-compare OK: bucketed beats pad-max on throughput "
              "and replan on p99, zero plan-cache misses after warmup")
    return reports


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="qwen3-14b")
    ap.add_argument("--convnet", choices=["vgg"], default=None,
                    help="serve the paper's conv trunk via plan_network "
                         "instead of an LM arch")
    # "auto" matches the planner's default, so untuned smoke runs resolve
    # per-geometry (the backend with the smaller predicted time) instead
    # of forcing one backend; --tune overrides this
    # with measured per-geometry winners (backend="tuned").
    ap.add_argument("--conv-backend", default="auto")
    ap.add_argument("--serve-trace", action="store_true",
                    help="continuous batching: run the shape-bucketed "
                         "dynamic batcher (repro.launch.batcher) on a "
                         "synthetic ragged Poisson trace")
    ap.add_argument("--serve-compare", action="store_true",
                    help="with --serve-trace: replay the same trace "
                         "through the pad-to-max and re-plan-per-shape "
                         "baselines and FAIL unless the bucketed engine "
                         "beats both (throughput / p99) with zero "
                         "plan-cache misses after warmup")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="largest batch bucket (powers of two up to "
                         "this; requests above it are rejected)")
    ap.add_argument("--batch-window-ms", type=float, default=2.0,
                    help="batching window: a queued request is flushed "
                         "after waiting this long even if its bucket "
                         "is not full")
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel replicas (one prepared network "
                         "per replica, round-robin dispatch; pair with "
                         "repro.launch.env emulated devices)")
    ap.add_argument("--trace-requests", type=int, default=0,
                    help="synthetic trace length (default 64, smoke 24)")
    ap.add_argument("--trace-rate", type=float, default=0.0,
                    help="Poisson arrival rate in requests/s; 0 replays "
                         "the trace instantaneously (deterministic)")
    ap.add_argument("--timing", choices=["async", "per-request"],
                    default=None,
                    help="async: throughput mode, one final sync (per-"
                         "request latency NOT derivable); per-request: "
                         "synchronize every batch and report p50/p99. "
                         "Defaults: async for the fixed-shape loop, "
                         "per-request for --serve-trace (the SLO rows "
                         "must measure completion, not dispatch)")
    ap.add_argument("--bench-out", default="",
                    help="with --serve-trace: write the serve/* bench "
                         "rows (BENCH_conv.json schema) to this path")
    ap.add_argument("--export-plans", default="",
                    help="with --serve-trace: AOT-export every bucket's "
                         "planned+prepared network to this plan artifact "
                         "(.rpa) after the run")
    ap.add_argument("--load-plans", default="",
                    help="with --serve-trace: start the bucketed engine "
                         "from an AOT plan artifact (zero retracing) "
                         "instead of plan+prepare+compile; falls back to "
                         "live planning with a warning on mismatch")
    ap.add_argument("--coldstart-out", default="",
                    help="with --serve-trace: write a cold-start JSON "
                         "report (coldstart_s, source, plan-cache misses "
                         "after warmup, fingerprint verification)")
    ap.add_argument("--overlap", default="off",
                    help="conv sub-slab comm/compute overlap: off | "
                         "slab:<k> | auto (sharded schedules only; see "
                         "docs/conv_api.md)")
    ap.add_argument("--tune", action="store_true",
                    help="autotune every distinct conv geometry (measured, "
                         "persistently cached) to warm the tuning cache "
                         "before serving; implies --convnet backend=tuned")
    ap.add_argument("--image", type=int, default=0,
                    help="convnet input size (default 224, smoke 64)")
    ap.add_argument("--analyze", action="store_true",
                    help="plan-lint the planned convnet (static analyzer, "
                         "repro.conv.analyze) before serving; aborts if "
                         "any structural invariant is violated")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache_dir()

    if (args.tune or args.serve_trace) and not args.convnet:
        args.convnet = "vgg"        # conv-only flags imply the convnet path
    if not args.trace_requests:
        args.trace_requests = 24 if args.smoke else 64
    if args.timing is None:
        args.timing = "per-request" if args.serve_trace else "async"

    if args.convnet:
        return serve_convnet(args)

    cfg = get_config(args.arch, smoke=args.smoke)
    key = jax.random.PRNGKey(args.seed)
    B, Sp = args.batch, args.prompt_len
    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(rng.integers(1, cfg.vocab, (B, Sp)), jnp.int32)
    max_len = Sp + args.gen + (cfg.n_meta_tokens or 0) + 8

    if cfg.encdec:
        params = WH.init_whisper_params(cfg, key)
        frames = jnp.asarray(rng.standard_normal((B, 64, cfg.d_model)),
                             jnp.float32)
        cache = WH.init_dec_cache(cfg, B, 64)
        prefill = jax.jit(make_prefill_step(cfg))
        decode = jax.jit(make_decode_step(cfg))
        t0 = time.time()
        logits, cache = prefill(params, {"frames": frames,
                                         "tokens": prompts[:, :1]}, cache)
        pos = 1
    else:
        params = LM.init_lm_params(cfg, key)
        cache = LM.init_cache(cfg, B, max_len)
        prefill = jax.jit(make_prefill_step(cfg, use_flash=False))
        decode = jax.jit(make_decode_step(cfg))
        t0 = time.time()
        logits, cache = prefill(params, {"tokens": prompts}, cache)
        pos = Sp + (cfg.n_meta_tokens or 0) \
            + (cfg.n_frontend_tokens if cfg.frontend == "vision_stub" else 0)
    t_prefill = time.time() - t0

    toks = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    out = [toks]
    t0 = time.time()
    for i in range(args.gen - 1):
        logits, cache = decode(params, toks, jnp.int32(pos + i), cache)
        toks = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        out.append(toks)
    jax.block_until_ready(toks)
    t_decode = time.time() - t0
    gen = jnp.concatenate(out, axis=1)
    tput = B * (args.gen - 1) / max(t_decode, 1e-9)
    print(f"arch={cfg.name} prefill={t_prefill*1e3:.0f}ms "
          f"decode={t_decode*1e3:.0f}ms ({tput_fmt(tput)})")
    print("sample tokens:", np.asarray(gen[0])[:16])
    return gen


def tput_fmt(x):
    return f"{x:.1f} tok/s"


if __name__ == "__main__":
    main()
