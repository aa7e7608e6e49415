#!/usr/bin/env python3
"""Chip benchmark of the conv engine: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <t>

The cell is a ``workloads`` entry of ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json``, wired by ``bench/structures/<s>.py``)
under a traffic mix (``bench/traffic/<traffic>.json``, driven by
``bench/loops/<loop>.py``), with its limits in ``bench/cells/<cell>.json``.
A run checks for the chips the cell asks for (exit 2 without them), makes
weights and inputs on the device from ``--seed``, plans with
``backend="auto"``, prepares and warms the cell's own shapes with JAX's
persistent compilation cache in ``<checkout>/.jax_cache``, measures for
``--seconds``, then compares what the window produced with the plain
reference.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiled window.  Earlier lines of standard
output are ``info: {...}``; the last is the result, one JSON object.  The
numbers compared, each beside its limit, are the last lines of standard
error and the result's last key, ``check``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]


class Run:
    """What a loop needs of one run."""

    def __init__(self, args, cfg, structure, traffic, cell, devs):
        self.seed, self.seconds, self.trace = (args.seed, args.seconds,
                                               bool(args.trace))
        self.cfg, self.structure, self.traffic = cfg, structure, traffic
        self.cell, self.devs, self.t_start = cell, devs, T_START

    def peak_bytes(self):
        from bench.lib.device import peak_bytes
        return peak_bytes(self.devs)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare_jax():
    """The persistent compilation cache at the benchmark's fixed path,
    keeping every program, however quick its compile."""
    from bench import lib
    os.environ["JAX_COMPILATION_CACHE_DIR"] = lib.CACHE_DIR
    from repro.launch.env import compile_cache_dir
    compile_cache_dir()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench.lib import compiles
    compiles.install()


def load_cell(spec, workload, files=None):
    """(cell, cfg, structure, traffic, limits) of ``workload``; ``files``
    may name other config, traffic and cell files (tests)."""
    from bench import lib
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r} "
                         f"(known: {sorted(cells)})")
    cell = cells[workload]
    files = files or {}
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = _json(files.get("config") or os.path.join(_ROOT, conf["file"]))
    traffic = _json(files.get("traffic") or os.path.join(
        lib.BENCH, "traffic", cell["traffic"] + ".json"))
    limits = _json(files.get("cell") or os.path.join(
        lib.BENCH, "cells", workload + ".json"))
    structure = lib.load_module("structures", cfg["structure"])
    return cell, cfg, structure, traffic, limits


def _json(path):
    with open(path) as f:
        return json.load(f)


def measure(args, spec, devs, files=None, kind=None):
    """Run the cell; returns the result line as a dict (``check`` last).
    ``kind`` names the ``PEAKS`` row in place of the device's own
    (tests on the CPU)."""
    from bench import lib
    from bench.lib import check, device, peaks, trace
    cell, cfg, structure, traffic, limits = load_cell(spec, args.workload,
                                                       files)
    loop = lib.load_module("loops", traffic["loop"])
    run = Run(args, cfg, structure, traffic, cell, devs)
    ctx = loop.run(run)
    ctx.update(cfg=cfg, peaks=peaks.peaks(kind or devs[0].device_kind))
    scopes = [l["name"] for l in cfg["layers"]]
    reduced = None
    if run.trace:
        files_ = ctx.get("trace_files") or []
        pb = [f for f in files_[:-1] if f.endswith(".xplane.pb")]
        if pb:
            reduced = trace.reduce(*trace.load(
                pb[0], scopes, ctx.get("hlo_texts", ())))
        trace.discard(files_)
    ctx["trace"] = reduced
    section = "per_layer" if run.trace else "end_to_end"
    metrics = {}
    for m in lib.cell_metrics(spec, args.workload, section):
        v = lib.load_module("metrics", m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    print("info: " + json.dumps(dict(ctx.get("info", {}),
                                     setup_s=ctx["setup_s"]),
                                sort_keys=True), flush=True)
    correct, chk = check.verdict(ctx["readings"], limits["limits"])
    dev = device.describe(devs, ctx["memory_peak"])
    out = {"correct": correct, "attempted": ctx["attempted"],
           "failed": ctx["failed"], "metrics": metrics, "device": dev}
    if run.trace:
        if reduced:
            dev.update(busy_s=reduced["busy_s"],
                       window_s=reduced["window_s"])
            out["breakdown"] = {"device_ops": reduced["device_ops"],
                                "idle_gaps": reduced["idle_gaps"]}
            print("info: " + json.dumps({"scope_s": reduced["scope_s"]},
                                        sort_keys=True), flush=True)
    out["check"] = chk
    return out


def main(argv=None):
    args = parse(argv)
    from bench import lib
    if not os.path.exists(lib.SPEC) or not os.path.isdir(
            os.path.join(_ROOT, "src", "repro")):
        raise SystemExit("bench: needs BENCHMARK.json and the program "
                         "(src/repro) in the checkout")
    spec = lib.benchmark_spec()
    prepare_jax()
    from bench.lib.device import require_chips
    cell = {w["name"]: w for w in spec["workloads"]}.get(args.workload)
    if cell is None:
        raise SystemExit(f"bench: no workload {args.workload!r}")
    devs = require_chips(cell["chips"])
    out = measure(args, spec, devs)
    from bench.lib.check import print_check
    print_check(out["check"])
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
