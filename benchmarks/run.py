"""Benchmark harness: one entry per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--tuned] \
        [--json-out PATH]

Prints ``name,us_per_call,derived`` CSV rows (plus context columns) and
writes the same numbers as machine-readable JSON (``BENCH_conv.json``) so
the perf trajectory accumulates across runs.  Entries are either a bare
``us_per_call`` float or — for ``--tuned`` autotuner rows — a
``{"us_per_call": float, "config": {...}}`` dict recording the measured
winner alongside its timing (see ``benchmarks.bench_schema`` for the
tolerant schema every consumer shares).  The CI perf gate
(``benchmarks.compare_baseline``) diffs this file against the committed
``benchmarks/BENCH_baseline.json``.  Full-scale (arch x shape x mesh)
numbers come from the dry-run (`repro.launch.dryrun --all`).
"""
from __future__ import annotations

import argparse
import io
import json
import sys


class _Tee(io.TextIOBase):
    """Pass stdout through while capturing it for CSV-row parsing."""

    def __init__(self, wrapped):
        self.wrapped = wrapped
        self.captured = io.StringIO()

    def write(self, s):
        self.captured.write(s)
        return self.wrapped.write(s)

    def flush(self):
        self.wrapped.flush()


def parse_csv_rows(text: str) -> dict:
    """``name,us_per_call[,...]`` rows -> {name: us_per_call} (header and
    ``#`` comment lines skipped; non-numeric second columns skipped)."""
    rows = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#") or line.startswith("name,"):
            continue
        parts = line.split(",")
        if len(parts) < 2:
            continue
        try:
            rows[parts[0]] = float(parts[1])
        except ValueError:
            continue
    return rows


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer layers / reps (CI-sized)")
    ap.add_argument("--tuned", action="store_true",
                    help="add measured-autotuner rows (winner config "
                         "recorded alongside the timing)")
    ap.add_argument("--json-out", default="BENCH_conv.json",
                    help="machine-readable name->us_per_call output "
                         "('' disables)")
    ap.add_argument("--analyze-out", default="",
                    help="also write the plan-lint profile sweep "
                         "(repro.conv.analyze) as a JSON artifact riding "
                         "the benchmark run ('' disables)")
    args = ap.parse_args(argv)

    from repro.launch.env import compile_cache_dir
    compile_cache_dir()
    # the cold-start workers need the device in fresh processes: run them
    # while this process has not touched JAX (a parent holding a TPU
    # would leave them none)
    coldstart = _coldstart_rows(quick=args.quick)

    tee = _Tee(sys.stdout)
    sys.stdout = tee
    try:
        from benchmarks import table1_layers, fig56_speedup, fig78_memrate
        print("name,us_per_call,derived")
        table1_layers.main(["--batch", "1", "--reps", "2"] if args.quick
                           else ["--batch", "2", "--reps", "3"])
        sys.stdout.flush()
        fig56_speedup.main(["--quick", "--reps", "3"] if args.quick
                           else ["--reps", "5"])
        sys.stdout.flush()
        fig78_memrate.main()
        sys.stdout.flush()
    finally:
        sys.stdout = tee.wrapped

    rows = parse_csv_rows(tee.captured.getvalue())
    rows.update(_overlap_rows(quick=args.quick))
    rows.update(_serve_rows(quick=args.quick))
    rows.update(coldstart)
    if args.tuned:
        rows.update(_tuned_rows(quick=args.quick))
    if args.json_out:
        with open(args.json_out, "w") as fh:
            json.dump(rows, fh, indent=1, sort_keys=True)
        print(f"# wrote {len(rows)} entries to {args.json_out}")
    if args.analyze_out:
        _analyze_artifact(args.analyze_out, quick=args.quick)
    return rows


def _analyze_artifact(path: str, quick: bool = False) -> None:
    """Plan-lint profile artifact riding the benchmark run: every
    registered backend x schedule swept over the paper geometries, so the
    perf numbers ship with the structural facts (collective counts, dtype
    flow, peak live bytes) that make them interpretable.  Violations are
    fatal — a timing for a plan that breaks its invariants is
    meaningless."""
    from repro.conv.analyze import sweep
    profiles, violations = sweep(batch=2, limit=3 if quick else None,
                                 progress=lambda s: print(f"# {s}"))
    payload = {k: p.to_dict() for k, p in profiles.items()}
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=1, sort_keys=True)
    print(f"# wrote {len(payload)} plan-lint profiles to {path}")
    if violations:
        raise SystemExit(
            f"plan-lint: {len(violations)} violation(s) during the "
            f"benchmark analyze sweep")


def _tuned_rows(quick: bool = True) -> dict:
    """Measured-autotuner entries: the winner's timing plus the chosen
    (backend, schedule, block) config, in the dict entry form."""
    from repro.conv import autotune

    shapes = [("autotune/c8o16s32", (1, 8, 32, 32), (16, 8, 3, 3), 1)]
    if not quick:
        shapes.append(
            ("autotune/c16o32s64", (1, 16, 64, 64), (32, 16, 3, 3), 1))
    out = {}
    for name, x_shape, k_shape, padding in shapes:
        w = autotune.tune(x_shape, k_shape, padding=padding)
        us = w.us_per_call
        config = {"backend": w.backend, "schedule": w.schedule,
                  "bm": w.bm, "bn": w.bn, "bk": w.bk, "dft_bt": w.dft_bt,
                  "source": w.source}
        if us is None:
            # cost-model fallback (measurement disabled): time the pick so
            # the row still carries a number
            import jax.numpy as jnp
            import numpy as np
            from repro.conv import plan_conv
            plan = plan_conv(x_shape, k_shape, padding=padding,
                             backend=w.backend, schedule=w.schedule)
            rng = np.random.default_rng(0)
            x = jnp.asarray(rng.standard_normal(x_shape), jnp.float32)
            k = jnp.asarray(rng.standard_normal(k_shape), jnp.float32)
            us = autotune.measure_us(plan, x, k)
        print(f"{name},{us:.1f},{config['backend']}/{config['schedule']}")
        out[name] = {"us_per_call": float(us), "config": config}
    return out


_OVERLAP_WORKER = r"""
import sys, json, time
import jax, jax.numpy as jnp, numpy as np
from repro.compat import make_mesh
from repro.conv import plan_conv
spec = json.loads(sys.argv[1])
assert jax.device_count() == spec["ndev"], jax.device_count()
mesh = make_mesh((spec["ndev"], 1), ("data", "model"))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal(
    (spec["B"], spec["C"], spec["H"], spec["W"])), jnp.float32)
k = jnp.asarray(rng.standard_normal(
    (spec["Co"], spec["C"], spec["kh"], spec["kh"])), jnp.float32)
out = {}
for ov in spec["overlaps"]:
    plan = plan_conv(x.shape, k.shape, padding=spec["pad"],
                     schedule="nfft", mesh=mesh, overlap=ov)
    f = jax.jit(plan)
    jax.block_until_ready(f(x, k))
    ts = []
    for _ in range(spec["reps"]):
        t0 = time.perf_counter()
        jax.block_until_ready(f(x, k))
        ts.append(time.perf_counter() - t0)
    out[ov] = float(np.median(ts)) * 1e6
print("RESULT" + json.dumps(out))
"""


def _overlap_rows(quick: bool = True) -> dict:
    """Comm/compute-overlapped nfft vs the synchronous baseline on a
    4-device emulated NUMA mesh (device-count forcing + latency-hiding
    scheduler flags from ``repro.launch.env``; subprocess so the parent
    keeps its real device).  The worker is CPU emulation by design and
    runs with ``JAX_PLATFORMS=cpu``, so it never competes with the parent
    for an accelerator.  Dict entries record the slab count next to the
    timing."""
    import os
    import subprocess

    from repro.configs.paper_convs import TABLE1
    from repro.launch.env import xla_flags

    ndev, batch = 4, 16                 # b_loc=4: slab:4 doesn't clamp
    # Rconv2.2 is the comm-heavy geometry (Cout=64: a2a bytes per cgemm
    # flop is Table I's highest) where overlap wins on an otherwise-idle
    # host; the compute-heavy layers in the full sweep are the honest
    # neutral cases (auto picks off there — trust the measurement).
    names = ["Rconv2.2"] if quick else ["Rconv2.2", "Rconv4.2", "Vconv5"]
    overlaps = ["off", "slab:2", "slab:4"]
    byname = {l.name: l for l in TABLE1}
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["XLA_FLAGS"] = xla_flags(ndev)
    env["JAX_PLATFORMS"] = "cpu"        # emulated host devices, by design
    print(f"# overlap: nfft sub-slab pipelines on a {ndev}-device emulated "
          "mesh — name,us_per_call,overlap")
    out = {}
    for name in names:
        lay = byname[name]
        spec = dict(B=batch, C=lay.C, Co=lay.Cout, H=lay.H, W=lay.W,
                    kh=lay.kh, pad=lay.pad, ndev=ndev, overlaps=overlaps,
                    reps=9 if name == "Rconv2.2" else 5)
        r = subprocess.run(
            [sys.executable, "-c", _OVERLAP_WORKER, json.dumps(spec)],
            env=env, capture_output=True, text=True, timeout=1200)
        if r.returncode != 0:
            print(f"# overlap/{name}: worker failed: {r.stderr[-500:]}")
            continue
        line = [ln for ln in r.stdout.splitlines()
                if ln.startswith("RESULT")][0]
        for ov, us in json.loads(line[len("RESULT"):]).items():
            tag = ov.replace("slab:", "slab")   # off | slab2 | slab4
            print(f"overlap/{name}/{tag},{us:.1f},{ov}")
            out[f"overlap/{name}/{tag}"] = {
                "us_per_call": float(us),
                "config": {"schedule": "nfft", "overlap": ov,
                           "num_slabs": 1 if ov == "off"
                           else int(ov.split(":")[1]),
                           "ndev": ndev, "batch": batch}}
    return out


_COLDSTART_WORKER = r"""
import sys, json
import jax.numpy as jnp, numpy as np
from repro.conv import Epilogue, NetworkConv
from repro.launch.batcher import BucketPolicy, ServeEngine

spec = json.loads(sys.argv[1])
ep = Epilogue(bias=True, activation="relu")

def make_layers(b):
    return (
        NetworkConv("s1", (b, 16, 32, 32), (32, 16, 3, 3),
                    padding=1, epilogue=ep),
        NetworkConv("s2", (b, 32, 32, 32), (32, 32, 3, 3),
                    padding=1, epilogue=ep),
    )

rng = np.random.default_rng(0)
def init(shape, s=0.05):
    return jnp.asarray(s * rng.standard_normal(shape), jnp.float32)
kernels = {l.name: init(l.k_shape) for l in make_layers(1)}
biases = {l.name: init((l.k_shape[0],)) for l in make_layers(1)}

def forward(prepared, x):
    for name in prepared:
        x = prepared[name](x, bias=biases[name])
    return x

engine = ServeEngine(
    make_layers, kernels, policy=BucketPolicy(max_batch=spec["max_batch"]),
    forward=forward, timing="per-batch", collect_results=False,
    backend="fft-xla",
    load_plans=spec["artifact"] if spec["mode"] == "aot" else None)
assert engine.plan_source == spec["mode"], engine.plan_source
if spec["mode"] == "live":
    engine.export_plans(spec["artifact"])
print("RESULT" + json.dumps({"startup_s": engine.startup_s}))
"""


def _coldstart_rows(quick: bool = True) -> dict:
    """Fleet cold-start: ServeEngine startup wall-time (plan + prepare +
    compile + warm, measured inside the constructor) in a FRESH process,
    live-planned vs rehydrated from the AOT plan artifact the live
    worker exported (``repro.conv.export``).  Two subprocesses so both
    sides pay real process cold-start — no warm jax caches leak in from
    the parent.  Each worker runs on the real device, so ``main`` calls
    this before the parent imports JAX."""
    import os
    import subprocess
    import tempfile

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    max_batch = 4 if quick else 8
    out = {}
    print("# coldstart: ServeEngine startup in a fresh process, live "
          "plan+prepare+compile vs AOT plan-artifact rehydration — "
          "name,us_per_call,source")
    with tempfile.TemporaryDirectory() as td:
        artifact = os.path.join(td, "plans.rpa")
        for mode in ("live", "aot"):
            spec = {"mode": mode, "artifact": artifact,
                    "max_batch": max_batch}
            r = subprocess.run(
                [sys.executable, "-c", _COLDSTART_WORKER,
                 json.dumps(spec)],
                env=env, capture_output=True, text=True, timeout=1200)
            if r.returncode != 0:
                print(f"# coldstart/{mode}: worker failed: "
                      f"{r.stderr[-500:]}")
                return out
            line = [ln for ln in r.stdout.splitlines()
                    if ln.startswith("RESULT")][0]
            s = json.loads(line[len("RESULT"):])["startup_s"]
            print(f"coldstart/{mode},{s * 1e6:.1f},{mode}")
            out[f"coldstart/{mode}"] = {
                "us_per_call": float(s) * 1e6,
                "config": {"source": mode, "max_batch": max_batch,
                           "n_layers": 2, "artifact": "plans.rpa"}}
    live = out.get("coldstart/live", {}).get("us_per_call")
    aot = out.get("coldstart/aot", {}).get("us_per_call")
    if live is not None and aot is not None and not aot < live:
        raise SystemExit(
            f"coldstart: AOT rehydration ({aot / 1e6:.2f}s) not faster "
            f"than live planning ({live / 1e6:.2f}s)")
    return out


def _serve_rows(quick: bool = True) -> dict:
    """Serving-SLO rows: the continuous-batching engine
    (``repro.launch.batcher``) on a reproducible ragged burst trace,
    emitting ``serve/<bucket>/{p50,p99,occupancy}`` in the dict entry
    form (percentiles riding the tolerated ``percentiles`` field) so
    the baseline gate holds serving latency, not just kernel time."""
    import jax.numpy as jnp
    import numpy as np
    from repro.conv import Epilogue, NetworkConv
    from repro.launch.batcher import (
        BucketPolicy, ServeEngine, run_trace, synthetic_trace)

    max_batch = 4 if quick else 8
    n_requests = 16 if quick else 32
    ep = Epilogue(bias=True, activation="relu")

    def make_layers(b):
        return (
            NetworkConv("s1", (b, 16, 32, 32), (32, 16, 3, 3),
                        padding=1, epilogue=ep),
            NetworkConv("s2", (b, 32, 32, 32), (32, 32, 3, 3),
                        padding=1, epilogue=ep),
        )

    rng = np.random.default_rng(0)

    def init(shape, s=0.05):
        return jnp.asarray(s * rng.standard_normal(shape), jnp.float32)

    kernels = {l.name: init(l.k_shape) for l in make_layers(1)}
    biases = {l.name: init((l.k_shape[0],)) for l in make_layers(1)}

    def forward(prepared, x):
        for name in prepared:
            x = prepared[name](x, bias=biases[name])
        return x

    engine = ServeEngine(make_layers, kernels,
                         policy=BucketPolicy(max_batch=max_batch),
                         forward=forward, timing="per-batch",
                         collect_results=False, backend="fft-xla")
    trace = synthetic_trace(n_requests=n_requests, max_batch=max_batch,
                            rate_rps=1.0, seed=0)
    inputs = {}

    def make_input(b, image):
        if b not in inputs:
            inputs[b] = init((b, 16, 32, 32), 1.0)
        return inputs[b]

    rep = run_trace(engine, trace, make_input=make_input,
                    realtime=False)        # deterministic burst replay
    assert rep["plan_cache_misses_after_warmup"] == 0, \
        "serve bench planned on the hot path"
    rows = engine.bench_rows(prefix="serve")
    print("# serve: continuous-batching engine, ragged burst trace "
          f"(n={n_requests}, max_batch={max_batch}) — "
          "name,us_per_call,metric")
    for name in sorted(rows):
        metric = name.rsplit("/", 1)[1]
        print(f"{name},{rows[name]['us_per_call']:.1f},{metric}")
    return rows


if __name__ == "__main__":
    main()
