"""Paper Figs. 5-6: nFFT vs wFFT speedup.

The paper measures wall time on 8 NUMA nodes of an FT-2000plus. Here the 8
"NUMA nodes" are 8 forced host devices on a (2 data x 4 model) mesh — a real
multi-device execution of both schedules (spawned in a subprocess so the
parent keeps one device). Two measurements per layer:

  * wall-time speedup nFFT/wFFT on the 8-way host mesh (the paper's Fig 5-6
    quantity, hardware-adapted),
  * hot-stage collective bytes per strategy from the compiled HLO (the
    TPU-relevant proxy for the paper's remote-memory-access reduction).

CSV: name,us_per_call,derived   (derived = speedup nFFT over wFFT)
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

_WORKER = r"""
import sys, json, time
import jax, jax.numpy as jnp, numpy as np
from repro.conv import plan_conv
from repro.compat import make_mesh
from repro.launch.roofline import parse_collectives
mesh = make_mesh((2, 4), ("data", "model"))
spec = json.loads(sys.argv[1])
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal(
    (spec["B"], spec["C"], spec["H"], spec["W"])), jnp.float32)
k = jnp.asarray(rng.standard_normal(
    (spec["Co"], spec["C"], spec["kh"], spec["kh"])), jnp.float32)
out = {}
for strat in ("nfft", "wfft"):
    f = jax.jit(plan_conv(x.shape, k.shape, schedule=strat, mesh=mesh,
                          padding=spec["pad"]))
    y = f(x, k)
    jax.block_until_ready(y)
    ts = []
    for _ in range(spec["reps"]):
        t0 = time.perf_counter()
        jax.block_until_ready(f(x, k))
        ts.append(time.perf_counter() - t0)
    coll = parse_collectives(f.lower(x, k).compile().as_text())
    out[strat] = {"t": float(np.median(ts)),
                  "coll_bytes": coll["total_bytes"],
                  "coll_counts": coll["counts"]}
print("RESULT" + json.dumps(out))
"""


def run_layer(name, B, C, Co, H, W, kh, pad, reps=5):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    # the 8 "NUMA nodes" are emulated host devices: CPU by design
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    spec = dict(B=B, C=C, Co=Co, H=H, W=W, kh=kh, pad=pad, reps=reps)
    r = subprocess.run([sys.executable, "-c", _WORKER, json.dumps(spec)],
                       env=env, capture_output=True, text=True, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(f"{name}: {r.stderr[-2000:]}")
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("RESULT")][0]
    return json.loads(line[len("RESULT"):])


# reduced-batch versions of representative Table-I layers (CPU-tractable)
LAYERS = [
    ("Vconv3.1", 4, 128, 256, 56, 56, 3, 1),
    ("Vconv4.2", 4, 512, 512, 28, 28, 3, 1),
    ("Vconv5", 8, 512, 512, 14, 14, 3, 1),
    ("Aconv3", 8, 256, 384, 13, 13, 3, 1),
    ("Rconv4.2", 8, 256, 256, 14, 14, 3, 1),
    ("Rconv5.2", 8, 512, 512, 7, 7, 3, 1),
]


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args(argv)
    layers = LAYERS[:3] if args.quick else LAYERS
    print("# Fig 5-6 — name,us_per_call(nFFT),derived(speedup nFFT/wFFT)"
          ",wfft_us,coll_bytes_nfft,coll_bytes_wfft")
    for (name, B, C, Co, H, W, kh, pad) in layers:
        res = run_layer(name, B, C, Co, H, W, kh, pad, reps=args.reps)
        sp = res["wfft"]["t"] / res["nfft"]["t"]
        print(f"fig56/{name},{res['nfft']['t']*1e6:.0f},{sp:.2f},"
              f"{res['wfft']['t']*1e6:.0f},"
              f"{res['nfft']['coll_bytes']},{res['wfft']['coll_bytes']}")


if __name__ == "__main__":
    main()
