"""Process environment: the compile cache, and the emulated-NUMA mesh for
the overlapped conv schedules.

``compile_cache_dir()`` places JAX's persistent compilation cache.  An
entry point calls it first thing, so every process of a run (children
included) reuses the programs earlier runs compiled.

The rest of this module emulates the paper's NUMA mesh on a CPU host.

The paper's target is a many-core ARMv8 CPU whose NUMA nodes each own a
slice of the batch/channel axes; this repo emulates that mesh on one host
by splitting the CPU into N XLA host devices.  Device-count forcing and
the scheduler flags that let XLA actually *overlap* the sub-slab boundary
collectives with the hot cgemm (``ConvPlan.overlap="slab:<k>"``) are all
``XLA_FLAGS`` — which XLA reads ONCE, at backend initialization.  They
must therefore be in the environment **before jax is imported**:

    # parent shell / CI step
    export XLA_FLAGS="$(python -m repro.launch.env --ndev 4 --print)"
    python my_script.py

    # or at the very top of an entrypoint, before ``import jax``
    from repro.launch import env
    env.apply(ndev=4)
    import jax

This module is deliberately import-light (no jax at module level) so it
can be imported to *compose* the environment without initializing the
backend it is trying to configure.  ``apply`` raises if jax was already
imported, because the flags would be silently ignored.

Flags (all verified against the pinned jax build — unknown ``XLA_FLAGS``
are fatal at init):

  ``--xla_force_host_platform_device_count=N``
      Split the host CPU into N devices: the emulated NUMA mesh that
      ``repro.launch.mesh`` / ``shard_map`` shard over.
  ``--xla_cpu_use_thunk_runtime=true``
      The thunk-based CPU runtime: collectives execute as their own
      thunks instead of inline calls, which is what makes the sub-slab
      a2a/psum of slab i+1 schedulable alongside slab i's cgemm.
  ``--xla_cpu_enable_concurrency_optimized_scheduler=true``
      Latency-hiding instruction order: XLA schedules for overlap
      (issue collectives early, sink their consumers late) instead of
      minimizing live ranges.
"""
from __future__ import annotations

import argparse
import os
import sys
from typing import Optional, Tuple

# <checkout>/.jax_cache: a fixed path, because the path is part of the
# cache's key — a directory that moves between runs never hits.
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def compile_cache_dir() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins and nothing is changed.
    Otherwise the cache goes to ``<checkout>/.jax_cache``: exported in the
    environment (child processes inherit it, and jax reads it when it is
    imported) and, if jax is already imported, set in its config.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = _DEFAULT_CACHE_DIR
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_compilation_cache_dir", path)
    return path


_OVERLAP_FLAGS = (
    "--xla_cpu_use_thunk_runtime=true",
    "--xla_cpu_enable_concurrency_optimized_scheduler=true",
)


def xla_flags(ndev: int, *, overlap: bool = True,
              extra: Tuple[str, ...] = ()) -> str:
    """The ``XLA_FLAGS`` value for an ``ndev``-device emulated NUMA mesh.

    ``overlap=False`` drops the scheduler flags (device-count forcing
    only — the synchronous baseline for A/B timing).  ``extra`` appends
    caller flags verbatim.
    """
    ndev = int(ndev)
    if ndev < 1:
        raise ValueError(f"ndev must be >= 1, got {ndev}")
    flags = [f"--xla_force_host_platform_device_count={ndev}"]
    if overlap:
        flags.extend(_OVERLAP_FLAGS)
    flags.extend(extra)
    return " ".join(flags)


def apply(ndev: int, *, overlap: bool = True,
          extra: Tuple[str, ...] = (), env: Optional[dict] = None) -> str:
    """Install the emulated-mesh ``XLA_FLAGS`` into the process env.

    Must run before jax is imported (XLA reads the flags once, at
    backend init) — raises RuntimeError if ``jax`` is already in
    ``sys.modules``.  Existing ``XLA_FLAGS`` content is preserved
    (prepended), so user-set flags survive; a flag given twice keeps the
    last occurrence, so ours win.  Returns the value installed.
    """
    if env is None:
        if "jax" in sys.modules:
            raise RuntimeError(
                "repro.launch.env.apply() called after jax was imported: "
                "XLA_FLAGS is read once at backend init, so these flags "
                "would be silently ignored.  Call apply() before "
                "`import jax`, or export XLA_FLAGS in the parent shell "
                "(`python -m repro.launch.env --ndev N --print`).")
        env = os.environ
    value = xla_flags(ndev, overlap=overlap, extra=extra)
    prior = env.get("XLA_FLAGS", "").strip()
    if prior:
        value = f"{prior} {value}"
    env["XLA_FLAGS"] = value
    return value


def mesh_shape(ndev: int, *, model: int = 1) -> Tuple[int, int]:
    """(data, model) mesh shape over ``ndev`` emulated devices: all
    parallelism on the data (batch) axis unless ``model`` divides it
    out (``ndev=8, model=2`` -> ``(4, 2)``)."""
    ndev, model = int(ndev), int(model)
    if model < 1 or ndev % model:
        raise ValueError(f"model={model} must divide ndev={ndev}")
    return (ndev // model, model)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="emulated-NUMA XLA environment (see repro.launch.env)")
    ap.add_argument("--ndev", type=int, default=4,
                    help="emulated host device count (default 4)")
    ap.add_argument("--no-overlap", action="store_true",
                    help="device-count forcing only; drop the "
                         "latency-hiding scheduler flags")
    ap.add_argument("--print", action="store_true", dest="print_flags",
                    help="print the XLA_FLAGS value and exit (for "
                         "`export XLA_FLAGS=$(... --print)`)")
    args = ap.parse_args(argv)
    value = xla_flags(args.ndev, overlap=not args.no_overlap)
    if args.print_flags:
        print(value)
        return 0
    # no --print: show what apply() would install, plus the mesh it implies
    print(f"XLA_FLAGS={value}")
    print(f"mesh_shape(data, model) = {mesh_shape(args.ndev)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
