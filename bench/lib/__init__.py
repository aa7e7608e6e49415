"""Shared code of the chip benchmark.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

    bench/configs/<config>.json      sizes of a configuration, as run
    bench/structures/<structure>.py  how a configuration's layers are
                                     wired: the program's forward and the
                                     plain reference beside it
    bench/traffic/<traffic>.json     parameters of a traffic mix
    bench/loops/<loop>.py            the driver a traffic mix names
    bench/metrics/<metric>.py        one reader per metric
    bench/cells/<cell>.json          the limits of a cell's comparison

A new cell adds such files and entries; no file here names a cell.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
SPEC = os.path.join(ROOT, "BENCHMARK.json")
# fixed path inside the checkout: the path is part of the cache's key
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def load_json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots),
    loaded once."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark_spec(path: str = SPEC) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_metrics(spec: dict, workload: str, section: str) -> list:
    """The ``section`` metrics (``end_to_end`` or ``per_layer``) that the
    cell reports: those that list it, and those that list no cells."""
    return [m for m in spec[section]
            if workload in m.get("workloads", [workload])]
