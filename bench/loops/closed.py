"""Closed loop: back-to-back steps of a fixed batch, one
``block_until_ready`` at the end of each step.

Traffic parameters:
  batch              images per step (every input of the step)
  distinct_batches   input batches made from the seed, used in turn

One step is the configuration's whole forward over
``plan_network(backend="auto") -> NetworkPlan.prepare``, jitted once.
The window runs steps until ``--seconds`` have passed; a rate is taken
over all its steps and all its time.  Afterwards the last output of every
input batch is compared with the plain reference.
"""
from __future__ import annotations

import time

import jax

from bench.lib import check, compiles, weights
from bench.lib import reference as R
from bench.lib.trace import annotate, capture


def inputs(r):
    """(kernels, biases, [input tuple per distinct batch]) from the seed."""
    cfg, tr = r.cfg, r.traffic
    n = tr.get("distinct_batches", 2)
    shapes = r.structure.input_shapes(cfg, tr["batch"])
    flat = weights.normal_arrays(shapes * n, r.seed)
    xs = [flat[i * len(shapes):(i + 1) * len(shapes)] for i in range(n)]
    return (*weights.params(cfg["layers"], r.seed), xs)


def setup(r):
    from repro.conv import plan_network
    kernels, biases, xs = inputs(r)
    t = time.perf_counter()
    net = plan_network(r.structure.network_convs(r.cfg, r.traffic["batch"]),
                       backend="auto")
    prepared = jax.block_until_ready(net.prepare(kernels, weights_version=0))
    prepare_s = time.perf_counter() - t
    step = jax.jit(r.structure.forward(r.cfg))
    for x in xs:                         # every shape the window runs
        jax.block_until_ready(step(prepared, biases, x))
    # a traced run names the layer of each device op from the step's HLO
    hlo = ([step.lower(prepared, biases, xs[0]).compile().as_text()]
           if r.trace else [])
    return {"kernels": kernels, "biases": biases, "xs": xs, "step": step,
            "prepared": prepared, "prepare_s": prepare_s, "hlo": hlo,
            "backends": {n: net[n].backend for n in net}}


def window(st, seconds, traced):
    step, prepared, biases, xs = (st["step"], st["prepared"], st["biases"],
                                  st["xs"])
    outs = [None] * len(xs)
    steps = 0
    with compiles.window() as built, capture(traced) as files:
        t0 = time.perf_counter()
        with annotate(traced, "window"):
            while True:
                i = steps % len(xs)
                with annotate(traced, "dispatch"):
                    y = step(prepared, biases, xs[i])
                with annotate(traced, "block"):
                    jax.block_until_ready(y)
                outs[i] = y
                steps += 1
                if time.perf_counter() - t0 >= seconds:
                    break
        t1 = time.perf_counter()
    return {"t0": t0, "window_s": t1 - t0, "steps": steps, "outs": outs,
            "trace_files": files, "window_builds": built}


def readings(cfg, structure, kernels, biases, xs, outs, conv) -> float:
    """``max_rel_err`` of ``outs`` (one output tuple per input tuple of
    ``xs``; ``None`` where a batch never ran) against the reference built
    on ``conv``."""
    ref = jax.jit(structure.reference(cfg, conv))
    pairs = []
    for x, out in zip(xs, outs):
        if out is not None:
            pairs.extend(zip(out, ref(kernels, biases, x)))
    return check.max_rel_err(pairs)


def run(r):
    from repro.conv import clear_prepared_cache
    st = setup(r)
    w = window(st, r.seconds, r.trace)
    peak = r.peak_bytes()
    outs = w.pop("outs")
    kernels, biases, xs = st["kernels"], st["biases"], st["xs"]
    info = {"prepare_s": st["prepare_s"], "backends": st["backends"],
            "steps": w["steps"], "window_builds": w["window_builds"]}
    hlo = st["hlo"]
    st.clear()                            # free the program's state
    clear_prepared_cache()
    err = readings(r.cfg, r.structure, kernels, biases, xs, outs,
                   R.conv_highest)
    batch = r.traffic["batch"]
    return dict(
        w, setup_s=w["t0"] - r.t_start, memory_peak=peak,
        prepare_s=info["prepare_s"], images=w["steps"] * batch,
        batch=batch, attempted=w["steps"], failed=0, hlo_texts=hlo,
        readings={"max_rel_err": err}, info=info)


def control(r) -> float:
    """The control's reading: the reference computed at ``high`` in the
    program's place, on the run's own inputs."""
    kernels, biases, xs = inputs(r)
    ctrl = jax.jit(r.structure.reference(r.cfg, R.conv_high))
    outs = [ctrl(kernels, biases, x) for x in xs]
    return readings(r.cfg, r.structure, kernels, biases, xs, outs,
                    R.conv_highest)
