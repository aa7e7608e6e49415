#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 4,5,6] [--seconds 2]

For each of ``--seeds`` it runs the cell as ``bench/run.py`` does, with a
short window at the cell's own load, and prints the numbers compared
(the program's readings).  For each of ``--control-seeds`` it prints the
control's reading: the plain reference computed at ``high`` (bf16_3x) in
the program's place, against the reference at ``HIGHEST``, on the inputs
a run of that seed compares.  The benchmark's own runs never run this.
One JSON line per reading; the last line sums them up.
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


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)

    from bench import lib
    from bench import run as bench_run
    spec = lib.benchmark_spec()
    bench_run.prepare_jax()
    from bench.lib.device import require_chips
    from repro.conv import clear_prepared_cache
    cell, cfg, structure, traffic, limits = bench_run.load_cell(
        spec, args.workload)
    devs = require_chips(cell["chips"])
    loop = lib.load_module("loops", traffic["loop"])

    def make_run(seed):
        ns = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0)
        return bench_run.Run(ns, cfg, structure, traffic, cell, devs)

    program, control = {}, {}
    for seed in args.seeds:
        t = time.perf_counter()
        ctx = loop.run(make_run(seed))
        clear_prepared_cache()
        program[seed] = ctx["readings"]
        print(json.dumps({"seed": seed, "program": ctx["readings"],
                          "s": time.perf_counter() - t}), flush=True)
    for seed in args.control_seeds:
        t = time.perf_counter()
        control[seed] = loop.control(make_run(seed))
        print(json.dumps({"seed": seed, "control": control[seed],
                          "s": time.perf_counter() - t}), flush=True)
    errs = [p["max_rel_err"] for p in program.values()]
    print(json.dumps({
        "workload": args.workload, "limits": limits["limits"],
        "program_max": max(errs) if errs else None,
        "control_min": min(control.values()) if control else None,
        "n_program": len(errs), "n_control": len(control)}), flush=True)


if __name__ == "__main__":
    main()
