"""Paper Figs. 7-8: L2-cache miss-rate comparison, hardware-adapted.

The FT-2000plus PMU events have no TPU (or dry-run host) equivalent. The
quantity the paper actually demonstrates is "nFFT's CGEMM touches only local
memory". The TPU-measurable analogue is the *hot-stage traffic ratio*:

    remote_fraction(strategy) = collective bytes attributable to the CGEMM
                                stage / total bytes the CGEMM stage accesses

computed from the compiled HLO of each stage jitted in isolation on the
8-way host mesh. nFFT's CGEMM should show ~0 collective bytes (pure local),
wFFT's should show the psum of Z.

CSV: name,us_per_call,derived   (derived = wFFT remote fraction - nFFT's)
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

_WORKER = r"""
import sys, json, time
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core import make_spec
from repro.core.cgemm import cgemm
from repro.compat import make_mesh, shard_map
from repro.launch.roofline import parse_collectives
mesh = make_mesh((2, 4), ("data", "model"))
spec = json.loads(sys.argv[1])
B, C, Co, H, W, kh, pad = (spec[k] for k in
                           ("B", "C", "Co", "H", "W", "kh", "pad"))
cs = make_spec((B, C, H, W), (Co, C, kh, kh), pad)
n_model = 4
# spectrum points, padded to a model-axis multiple as the nfft schedule does
NP = cs.freq_points() + (-cs.freq_points()) % n_model
rng = np.random.default_rng(0)


def mk(shape, pspec):
    a = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    return jax.device_put(a, NamedSharding(mesh, pspec))


out = {}
# --- nFFT hot stage: P sharded over model, M over data; local einsum ------
Dr = mk((NP, cs.M, C), P("model", "data", None))
Di = mk((NP, cs.M, C), P("model", "data", None))
Gr = mk((NP, C, Co), P("model", None, None))
Gi = mk((NP, C, Co), P("model", None, None))
f_n = jax.jit(
    shard_map(lambda a, b, c, d: cgemm(a, b, c, d),
              mesh=mesh,
              in_specs=(P("model", "data", None), P("model", "data", None),
                        P("model", None, None), P("model", None, None)),
              out_specs=(P("model", "data", None),
                         P("model", "data", None))))
# --- wFFT hot stage: C sharded over model -> psum inside ------------------
Dr2 = mk((NP, cs.M, C), P(None, "data", "model"))
Di2 = mk((NP, cs.M, C), P(None, "data", "model"))
Gr2 = mk((NP, C, Co), P(None, "model", None))
Gi2 = mk((NP, C, Co), P(None, "model", None))


def wfft_body(a, b, c, d):
    zr, zi = cgemm(a, b, c, d)
    return (jax.lax.psum(zr, "model"), jax.lax.psum(zi, "model"))


f_w = jax.jit(
    shard_map(wfft_body, mesh=mesh,
              in_specs=(P(None, "data", "model"), P(None, "data", "model"),
                        P(None, "model", None), P(None, "model", None)),
              out_specs=(P(None, "data", None), P(None, "data", None))))

for name, f, args in (("nfft", f_n, (Dr, Di, Gr, Gi)),
                      ("wfft", f_w, (Dr2, Di2, Gr2, Gi2))):
    comp = f.lower(*args).compile()
    coll = parse_collectives(comp.as_text())
    ca = comp.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    jax.block_until_ready(f(*args))
    ts = []
    for _ in range(spec["reps"]):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        ts.append(time.perf_counter() - t0)
    out[name] = {"coll_bytes": coll["total_bytes"],
                 "hbm_bytes": float(ca.get("bytes accessed", 0.0)),
                 "t": float(np.median(ts))}
print("RESULT" + json.dumps(out))
"""

LAYERS = [
    ("Vconv4.2", 4, 512, 512, 28, 28, 3, 1),
    ("Aconv3", 8, 256, 384, 13, 13, 3, 1),
    ("Rconv5.2", 8, 512, 512, 7, 7, 3, 1),
]


def run_layer(name, B, C, Co, H, W, kh, pad, reps=3):
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


def main(argv=None):
    print("# Fig 7-8 — name,us_per_call(nfft cgemm),derived(remote-frac "
          "delta wfft-nfft),nfft_remote_frac,wfft_remote_frac")
    for (name, *args) in LAYERS:
        res = run_layer(name, *args)
        fr = {}
        for s in ("nfft", "wfft"):
            denom = res[s]["hbm_bytes"] + res[s]["coll_bytes"]
            fr[s] = res[s]["coll_bytes"] / denom if denom else 0.0
        print(f"fig78/{name},{res['nfft']['t']*1e6:.0f},"
              f"{fr['wfft']-fr['nfft']:.3f},{fr['nfft']:.3f},"
              f"{fr['wfft']:.3f}")


if __name__ == "__main__":
    main()
