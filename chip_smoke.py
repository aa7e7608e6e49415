#!/usr/bin/env python3
"""Chip smoke test: the conv engine's main path, end to end, on a TPU.

    python3 chip_smoke.py [--seed N]     # one chip: every phase below
    python3 chip_smoke.py --chips 4      # four chips: the sharded path only

One process, through the entry points a user calls (``plan_network`` ->
``NetworkPlan.prepare`` -> forward, ``ServeEngine``, ``plan_conv``):

  device  JAX must find a TPU; anything else exits non-zero before any
          work, so this never passes on a CPU fallback.
  trunk   the VGG conv trunk (the nine Table-I V layers, bias+ReLU, 2x2
          max-pools) at 224x224, batch 32, ``backend="auto"``.
  serve   a ``ServeEngine`` over the same trunk (``max_batch=32``) answers a
          short ragged trace; every answer equals the fixed-shape forward
          on the same rows, with zero plan-cache misses after warm-up.
  pallas  Vconv4.2, batch 32, ``fft-pallas`` on ``local`` (Pallas CGEMM +
          the fused compact-spectrum inverse): compiled, not interpreted.
  grad    ``jax.value_and_grad`` through the plan-level VJP on Vconv4.2,
          batch 32, with bias.
  mesh    (``--chips 4`` only) ``nfft`` and its ``wfft`` baseline on a 2x2
          (data, model) mesh, Vconv2.2 and Vconv4.2 at batch 32, against
          the one-chip ``local`` output; the prepared P-slab and the output
          must span all four devices.

Correctness bound: every output is compared with ``conv2d_direct`` run in
float32 under ``jax.default_matmul_precision("highest")`` on the same
inputs, as ``max|y - ref| / max|ref|`` <= ``BOUND`` = 1e-4.  Reason: a
float32 plan computes in float32 (every engine matmul runs at HIGHEST
precision), where rounding through nine layers of 16-point transforms and
contractions over up to 4608 terms stays far below 1e-4 of the output's
scale; one bf16 rounding (unit roundoff 2^-8 = 3.9e-3), as XLA's default
TPU precision applies, exceeds it by more than an order of magnitude.
XLA's own conv at default precision is run beside the trunk and printed,
so the bound can be read against it; it is not held to the bound.

Earlier lines are ``<phase>: {json}`` records; the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
A failed phase raises, and the process exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

BOUND = 1e-4
BATCH = 32
IMAGE = 224


def _fail(msg):
    raise SystemExit(f"chip_smoke FAILED: {msg}")


def _check(cond, msg):
    if not cond:
        _fail(msg)


def _record(phase, **fields):
    print(f"{phase}: {json.dumps(fields, sort_keys=True)}", flush=True)


def _device_check():
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r} "
              f"({devs[0].device_kind})", file=sys.stderr)
        sys.exit(2)
    return devs


def _rel_err(y, ref):
    import numpy as np
    y = np.asarray(y, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(y - ref)) / np.max(np.abs(ref)))


def _weights(layers, rng):
    """He-scaled kernels and small biases from ``rng`` (float32)."""
    import jax.numpy as jnp
    import numpy as np
    kernels, biases = {}, {}
    for l in layers:
        co, c, kh, kw = l.k_shape
        kernels[l.name] = jnp.asarray(
            rng.standard_normal(l.k_shape) * np.sqrt(2.0 / (c * kh * kw)),
            jnp.float32)
        biases[l.name] = jnp.asarray(0.01 * rng.standard_normal((co,)),
                                     jnp.float32)
    return kernels, biases


def _direct_chain(conv):
    """The VGG trunk as a chain of ``conv(x, k)`` + bias + ReLU + pools."""
    import jax
    from repro.launch.serve import VGG_POOL_AFTER
    from repro.models.layers import maxpool2x2

    def forward(kernels, biases, x):
        for name in kernels:
            x = jax.nn.relu(conv(x, kernels[name])
                            + biases[name][None, :, None, None])
            if name in VGG_POOL_AFTER:
                x = maxpool2x2(x)
        return x
    return forward


def _oracle_conv(x, k):
    from repro.core import conv2d_direct
    return conv2d_direct(x, k, padding=1)


def _xla_default_conv(x, k):
    """XLA's own conv at its default precision (no precision argument)."""
    import jax
    return jax.lax.conv_general_dilated(
        x, k, (1, 1), [(1, 1), (1, 1)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def _timed_compile(fn, *args):
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    return compiled, time.perf_counter() - t0


def _timed_run(compiled, *args):
    import jax
    t0 = time.perf_counter()
    y = jax.block_until_ready(compiled(*args))
    return y, time.perf_counter() - t0


def _oracle(forward, *args):
    import jax
    with jax.default_matmul_precision("highest"):
        return jax.block_until_ready(jax.jit(forward)(*args))


def phase_trunk(kernels, biases, x):
    """VGG trunk via plan_network -> prepare -> forward vs the oracle."""
    import jax
    from repro.configs.paper_convs import vgg_network
    from repro.conv import plan_network
    from repro.launch.serve import vgg_forward

    t0 = time.perf_counter()
    net = plan_network(vgg_network(BATCH), backend="auto")
    prepared = net.prepare(kernels, weights_version=0)
    jax.block_until_ready(prepared)
    t_prepare = time.perf_counter() - t0
    forward = jax.jit(vgg_forward(biases))
    compiled, t_compile = _timed_compile(forward, prepared, x)
    y, t_first = _timed_run(compiled, prepared, x)
    _, t_second = _timed_run(compiled, prepared, x)

    ref = _oracle(_direct_chain(_oracle_conv), kernels, biases, x)
    xla_default = jax.jit(_direct_chain(_xla_default_conv))(kernels, biases,
                                                            x)
    err = _rel_err(y, ref)
    _record("trunk", batch=BATCH, image=IMAGE,
            backends={n: net[n].backend for n in net},
            out_shape=list(y.shape), err=err, bound=BOUND,
            xla_conv_default_precision_err=_rel_err(xla_default, ref),
            prepare_s=t_prepare, compile_s=t_compile, first_run_s=t_first,
            run_s=t_second)
    _check(y.shape == (BATCH, 512, IMAGE // 32, IMAGE // 32),
           f"trunk output shape {y.shape}")
    _check(err <= BOUND, f"trunk error {err:.3e} > bound {BOUND:.0e}")
    return compiled, prepared


def phase_serve(kernels, biases, trunk_compiled, trunk_prepared, rng, seed):
    """ServeEngine over the trunk; each answer vs the fixed-shape forward."""
    import jax.numpy as jnp
    from repro.configs.paper_convs import vgg_network
    from repro.launch.batcher import (
        BucketPolicy, ServeEngine, run_trace, synthetic_trace)
    from repro.launch.serve import vgg_forward

    engine = ServeEngine(vgg_network, kernels,
                         policy=BucketPolicy(max_batch=BATCH),
                         forward=vgg_forward(biases), backend="auto",
                         timing="per-batch")
    trace = synthetic_trace(n_requests=8, max_batch=BATCH, rate_rps=100.0,
                            seed=seed)
    sent = []

    def make_input(batch, image):
        x = jnp.asarray(rng.standard_normal((batch, 3, IMAGE, IMAGE)),
                        jnp.float32)
        sent.append(x)
        return x

    rep = run_trace(engine, trace, make_input=make_input, realtime=False)
    worst = 0.0
    for rid, x in enumerate(sent):
        _check(rid in engine.results, f"request {rid} was not answered")
        ans = engine.results[rid]
        pad = jnp.zeros((BATCH - x.shape[0],) + x.shape[1:], x.dtype)
        fixed = trunk_compiled(trunk_prepared, jnp.concatenate([x, pad]))
        worst = max(worst, _rel_err(ans, fixed[:x.shape[0]]))
    misses = rep["plan_cache_misses_after_warmup"]
    _record("serve", max_batch=BATCH, buckets=list(
                engine.policy.batch_buckets()),
            n_requests=rep["n_requests"],
            request_rows=[int(x.shape[0]) for x in sent],
            answered=len(engine.results),
            plan_cache_misses_after_warmup=misses,
            startup_s=engine.startup_s, wall_s=rep["wall_s"],
            p50_ms=rep["p50_us"] / 1e3, p99_ms=rep["p99_us"] / 1e3,
            max_err_vs_fixed_shape=worst, bound=BOUND)
    _check(len(engine.results) == len(trace) == rep["n_requests"],
           "not every request was answered")
    _check(misses == 0, f"{misses} plan-cache misses after warm-up")
    _check(worst <= BOUND, f"served answers differ from the fixed-shape "
                           f"forward by {worst:.3e}")


def _layer(name):
    from repro.configs.paper_convs import TABLE1
    return next(l for l in TABLE1 if l.name == name)


def _layer_inputs(name, rng):
    """One Table-I layer at ``BATCH``: (layer, x, He-scaled k, bias)."""
    import jax.numpy as jnp
    import numpy as np
    l = _layer(name)
    x = jnp.asarray(rng.standard_normal((BATCH, l.C, l.H, l.W)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((l.Cout, l.C, l.kh, l.kw))
                    * np.sqrt(2.0 / (l.C * l.kh * l.kw)), jnp.float32)
    b = jnp.asarray(0.01 * rng.standard_normal((l.Cout,)), jnp.float32)
    return l, x, k, b


def phase_pallas(rng):
    """fft-pallas on local: both Pallas kernels compiled for the TPU."""
    import jax
    from repro.conv import Epilogue, plan_conv

    l, x, k, b = _layer_inputs("Vconv4.2", rng)
    plan = plan_conv(x.shape, k.shape, padding=l.pad, backend="fft-pallas",
                     schedule="local",
                     epilogue=Epilogue(bias=True, activation="relu"))
    f = jax.jit(lambda x, k, b: plan(x, k, bias=b))
    compiled, t_compile = _timed_compile(f, x, k, b)
    n_kernels = compiled.as_text().count('"tpu_custom_call"')
    y, t_first = _timed_run(compiled, x, k, b)
    _, t_second = _timed_run(compiled, x, k, b)
    ref = _oracle(lambda x, k, b: jax.nn.relu(
        _oracle_conv(x, k) + b[None, :, None, None]), x, k, b)
    err = _rel_err(y, ref)
    _record("pallas", layer=l.name, batch=BATCH, backend=plan.backend,
            schedule=plan.schedule, tpu_custom_calls=n_kernels, err=err,
            bound=BOUND, compile_s=t_compile, first_run_s=t_first,
            run_s=t_second)
    # one CGEMM kernel + one fused compact-spectrum inverse kernel
    _check(n_kernels >= 2, f"{n_kernels} tpu_custom_call ops in the compiled"
                           " fft-pallas program (expected the CGEMM and the"
                           " fused inverse)")
    _check(err <= BOUND, f"fft-pallas error {err:.3e} > bound {BOUND:.0e}")


def phase_grad(rng):
    """value_and_grad through the plan-level VJP vs jax.grad of the oracle."""
    import jax
    import jax.numpy as jnp
    from repro.conv import Epilogue, plan_conv

    l, x, k, b = _layer_inputs("Vconv4.2", rng)
    w = jnp.asarray(rng.standard_normal((BATCH, l.Cout, l.H, l.W)),
                    jnp.float32)
    plan = plan_conv(x.shape, k.shape, padding=l.pad, backend="fft-xla",
                     epilogue=Epilogue(bias=True))

    # w is an argument, not a closure: a captured array would be compiled
    # into the executable as a constant
    def loss(x, k, b, w):
        return jnp.sum(plan(x, k, bias=b) * w)

    def loss_ref(x, k, b, w):
        return jnp.sum((_oracle_conv(x, k) + b[None, :, None, None]) * w)

    f = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    compiled, t_compile = _timed_compile(f, x, k, b, w)
    (val, grads), t_first = _timed_run(compiled, x, k, b, w)
    val_ref, grads_ref = _oracle(jax.value_and_grad(loss_ref,
                                                    argnums=(0, 1, 2)),
                                 x, k, b, w)
    errs = {n: _rel_err(g, r)
            for n, g, r in zip(("dx", "dk", "dbias"), grads, grads_ref)}
    val_err = abs(float(val) - float(val_ref)) / abs(float(val_ref))
    _record("grad", layer=l.name, batch=BATCH, backend=plan.backend,
            loss_rel_err=val_err, grad_errs=errs, bound=BOUND,
            compile_s=t_compile, run_s=t_first)
    for n, e in errs.items():
        _check(e <= BOUND, f"{n} error {e:.3e} > bound {BOUND:.0e}")
    _check(val_err <= BOUND, f"loss error {val_err:.3e} > bound")


def phase_mesh(devs, rng):
    """nfft / wfft on a 2x2 (data, model) mesh vs the one-chip output."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.compat import make_mesh
    from repro.conv import Epilogue, plan_conv

    _check(len(devs) >= 4, f"--chips 4 needs four devices, found {len(devs)}")
    mesh = make_mesh((2, 2), ("data", "model"), devices=devs[:4])
    ep = Epilogue(bias=True, activation="relu")
    for name in ("Vconv2.2", "Vconv4.2"):
        l, x, k, b = _layer_inputs(name, rng)
        local = plan_conv(x.shape, k.shape, padding=l.pad,
                          backend="fft-xla", schedule="local", epilogue=ep)
        y_local = jax.jit(lambda x, k, b: local(x, k, bias=b))(x, k, b)
        x_mesh = jax.device_put(x, NamedSharding(mesh,
                                                 P("data", "model")))
        for sched in ("nfft", "wfft"):
            plan = plan_conv(x.shape, k.shape, padding=l.pad,
                             backend="fft-xla", schedule=sched, mesh=mesh,
                             epilogue=ep)
            prepared = plan.prepare(k)
            f = jax.jit(lambda p, x, b: p(x, bias=b))
            compiled, t_compile = _timed_compile(f, prepared, x_mesh, b)
            y, t_first = _timed_run(compiled, prepared, x_mesh, b)
            _, t_second = _timed_run(compiled, prepared, x_mesh, b)
            err = _rel_err(y, y_local)
            slab_devs = {d.id for g in prepared.state
                         for d in g.sharding.device_set}
            out_devs = {d.id for d in y.sharding.device_set}
            _record("mesh", layer=name, batch=BATCH, schedule=sched,
                    mesh={"data": 2, "model": 2}, err_vs_local=err,
                    bound=BOUND, slab_devices=sorted(slab_devs),
                    out_devices=sorted(out_devs), compile_s=t_compile,
                    first_run_s=t_first, run_s=t_second)
            _check(err <= BOUND, f"{name}/{sched} differs from the one-chip "
                                 f"output by {err:.3e}")
            _check(len(slab_devs) == 4, f"{name}/{sched} prepared slab on "
                                        f"devices {sorted(slab_devs)}")
            _check(len(out_devs) == 4, f"{name}/{sched} output on devices "
                                       f"{sorted(out_devs)}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded nfft/wfft phase on a 2x2 "
                         "mesh (default 1: every one-chip phase)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every weight and input")
    args = ap.parse_args(argv)

    from repro.launch.env import compile_cache_dir
    cache = compile_cache_dir()
    devs = _device_check()

    import jax
    import jax.numpy as jnp
    import numpy as np

    t_start = time.perf_counter()
    rng = np.random.default_rng(args.seed)
    _record("device", platform=devs[0].platform, kind=devs[0].device_kind,
            count=len(devs), jax=jax.__version__, compile_cache=cache)
    if args.chips == 4:
        phase_mesh(devs, rng)
    else:
        from repro.configs.paper_convs import vgg_network
        from repro.conv import clear_prepared_cache
        kernels, biases = _weights(vgg_network(BATCH), rng)
        x = jnp.asarray(rng.standard_normal((BATCH, 3, IMAGE, IMAGE)),
                        jnp.float32)
        compiled, prepared = phase_trunk(kernels, biases, x)
        phase_serve(kernels, biases, compiled, prepared, rng, args.seed)
        del compiled, prepared
        clear_prepared_cache()          # free the buckets' kernel slabs
        phase_pallas(rng)
        phase_grad(rng)
    stats = devs[0].memory_stats() or {}
    _record("done", total_s=time.perf_counter() - t_start,
            peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
