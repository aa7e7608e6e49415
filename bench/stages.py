#!/usr/bin/env python3
"""Device time per conv stage of a cell, on the chip, in one process.

    python3 bench/stages.py --workload <cell> --seeds 1,2 [--seconds 10]

For each of ``--seeds`` it runs the cell as ``bench/run.py --trace 1``
does (same loop, same profiled window) and reduces the trace twice: with
``bench/lib/trace.py`` as the benchmark does (``scope_s``, ``busy_s``,
``idle_gaps``) and with ``bench/lib/stages.py`` (device time under each
conv stage scope, per layer, and the host event under each idle gap of
1 ms or more).  One JSON line per seed: the stages' device milliseconds a
step, each layer's stage seconds, the fft layers' seconds under no stage
scope, the share of the layers' device time that some stage covers, and
the window's ``images_per_s`` with the profiler on.  The benchmark's own
runs never run this.
"""
import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def reading(ctx, scopes, backends) -> dict:
    """The stage reading of one traced run's context (``loop.run``)."""
    from bench.lib import stages, trace
    pb = [f for f in ctx["trace_files"][:-1] if f.endswith(".xplane.pb")]
    if not pb:
        return {"trace": None}
    hlo = ctx.get("hlo_texts", ())
    ops, spans = trace.load(pb[0], scopes, hlo)
    base = trace.reduce(ops, spans)
    red = stages.reduce(ops, spans, stages.stage_map(hlo),
                        stages.host_events(pb[0]))
    if not base or not red:
        return {"trace": None}
    steps = ctx["steps"]
    return {
        "steps": steps,
        "images_per_s": ctx["images"] / ctx["window_s"],
        "busy_s": base["busy_s"], "window_s": base["window_s"],
        "stage_ms": {s: stages.stage_ms(red, s, steps)
                     for s in stages.STAGES},
        "layer_stage_s": red["layer_stage_s"],
        "unstaged_s": {l: v for l, v in red["layer_unstaged_s"].items()
                       if backends.get(l, "").startswith("fft")},
        "staged_share": stages.staged_share(red, base["scope_s"]),
        "scope_s": base["scope_s"],
        "idle_gaps": base["idle_gaps"],
        "idle_gap_host": red["idle_gap_host"],
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    from bench import lib
    from bench import run as bench_run
    spec = lib.benchmark_spec()
    bench_run.prepare_jax()
    from bench.lib import check, trace
    from bench.lib.device import require_chips
    from repro.conv import clear_prepared_cache
    cell, cfg, structure, traffic, limits = bench_run.load_cell(
        spec, args.workload)
    devs = require_chips(cell["chips"])
    loop = lib.load_module("loops", traffic["loop"])
    scopes = [l["name"] for l in cfg["layers"]]

    for seed in args.seeds:
        t = time.perf_counter()
        ns = argparse.Namespace(seed=seed, seconds=args.seconds, trace=1)
        ctx = loop.run(bench_run.Run(ns, cfg, structure, traffic, cell,
                                     devs))
        clear_prepared_cache()
        try:
            out = reading(ctx, scopes, ctx["info"]["backends"])
        finally:
            trace.discard(ctx.get("trace_files"))
        correct, _ = check.verdict(ctx["readings"], limits["limits"])
        print(json.dumps(dict({"workload": args.workload, "seed": seed,
                               "correct": correct},
                              **out, s=time.perf_counter() - t),
                         sort_keys=True), flush=True)


if __name__ == "__main__":
    main()
