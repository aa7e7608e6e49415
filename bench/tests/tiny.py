"""Drive a whole run of the harness on the CPU at a tiny size: every step
of ``bench/run.py`` but the look for a chip, with small configurations and
traffic in place of the cell's files."""
from __future__ import annotations

import argparse
import copy
import json
import os
import tempfile

import jax

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")

CELLS = {
    "chain.closed": ("tiny_chain", "tiny_closed"),
    "parallel.closed": ("tiny_parallel", "tiny_closed"),
}


def fixture(name: str) -> dict:
    with open(os.path.join(FIXTURES, name + ".json")) as f:
        return json.load(f)


def real_config(name: str, image: int = 32) -> dict:
    """A configuration of ``BENCHMARK.json`` at its published widths and
    depth, a chain's input cut to ``image`` pixels (layer sizes scaled)."""
    from bench import lib
    cfg = copy.deepcopy(lib.load_json("configs", name + ".json"))
    if "image" in cfg:
        for layer in cfg["layers"]:
            layer["H"] = layer["H"] * image // cfg["image"]
            layer["W"] = layer["W"] * image // cfg["image"]
        cfg["image"] = image
    return cfg


def spec(cell: str) -> dict:
    """A one-cell benchmark spec whose metrics are the real ones of the
    repository's ``BENCHMARK.json``."""
    from bench import lib
    real = lib.benchmark_spec()
    return {"configs": [{"name": "tiny", "file": ""}],
            "workloads": [{"name": cell, "config": "tiny",
                           "traffic": "tiny", "chips": 1}],
            "end_to_end": [dict(m, workloads=[cell])
                           for m in real["end_to_end"]],
            "per_layer": [dict(m, workloads=[cell])
                          for m in real["per_layer"]]}


def run(cell: str, seed: int = 7, seconds: float = 0.5, trace: int = 0,
        cfg: dict | None = None, limits: dict | None = None):
    """The result line of one tiny run (``correct`` is read there);
    ``cfg`` and ``limits`` replace the fixture's configuration and the
    fixture's limits."""
    from bench.lib import compiles
    from bench.run import measure
    compiles.install()
    config, traffic = CELLS[cell]
    with tempfile.TemporaryDirectory() as d:
        files = {}
        for key, value in (
                ("config", cfg or fixture(config)),
                ("traffic", fixture(traffic)),
                ("cell", limits or fixture("tiny_closed_limits"))):
            files[key] = os.path.join(d, key + ".json")
            with open(files[key], "w") as f:
                json.dump(value, f)
        args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                                  trace=trace)
        return measure(args, spec(cell), jax.devices()[:1], files,
                       kind="TPU v5 lite")


def make_run(cell: str, seed: int, cfg: dict | None = None):
    """(Run, loop module) of a tiny cell, for the control's reading."""
    from bench import lib
    from bench import run as bench_run
    config, traffic = CELLS[cell]
    cfg = cfg or fixture(config)
    tr = fixture(traffic)
    ns = argparse.Namespace(seed=seed, seconds=0.5, trace=0)
    return (bench_run.Run(ns, cfg, lib.load_module("structures",
                                                   cfg["structure"]),
                          tr, {}, None),
            lib.load_module("loops", tr["loop"]))
