"""On-device measured autotuner for the conv engine (``backend="tuned"``).

The cost model behind ``backend="auto"`` predicts each backend's time, but the
direct/FFT crossover — and the best (schedule, block) configuration — is
machine-dependent (Zlateski et al.).  This module *measures* instead:

    from repro.conv import autotune
    winner = autotune.tune(x_shape, k_shape, padding=1)
    # -> TunedConfig(backend='fft-xla', schedule='local', ..., us_per_call=…)

or, threaded through the planner:

    plan = plan_conv(x_shape, k_shape, padding=1, backend="tuned")

``tune`` times every candidate (backend, schedule, sub-slab ``overlap``,
cgemm ``bm/bn/bk``, ``dft_tile`` ``dft_bt``) configuration on the actual
device — warmup then median-of-k, under a wall-clock budget — and persists
the winner in a JSON tuning cache so the tuning cost is paid once per
machine.  Cache entries are keyed by the spec signature + device kind +
jax version: a new device or a jax upgrade invalidates naturally (old
keys simply never match).

Candidates are timed through the real planner with a representative
bias+relu epilogue, so the ``fft-pallas``/``local`` fused ``dft_tile``
inverse tail is part of the measurement (its ``dft_bt`` tile is a real
tuning axis, not a guess).

Environment knobs:

  ``REPRO_AUTOTUNE``            "0"/"false"/"off" disables measurement;
                                ``tune`` then falls back to the cost model
                                (cold cache + offline -> same answer as
                                ``backend="auto"``).  Cache *hits* are still
                                served.
  ``REPRO_AUTOTUNE_CACHE``      cache file path
                                (default ``~/.cache/repro_autotune.json``).
  ``REPRO_AUTOTUNE_BUDGET_MS``  wall-clock tuning budget per spec (default
                                2000).  The cost-model pick is always
                                measured; further candidates run until the
                                budget is spent.
  ``REPRO_AUTOTUNE_REPS``       timed repetitions per candidate (default 3,
                                median taken; 1 warmup/compile call first).

CI runs ``python -m repro.conv.autotune --selfcheck`` with the budget
clamped low: it tunes one small spec, drops the in-memory store, re-reads
the cache file and asserts the reloaded winner is identical (write ->
reload -> same winners), so the tuner never bit-rots headlessly.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import os
import threading
import time
from typing import Optional

from repro.core.conv_spec import ConvSpec
# shared with the planner so cache signatures can never drift from
# planner semantics (safe: repro.conv.plan never imports this module at
# module level — only lazily inside plan_conv)
from repro.conv.plan import _build_spec as _make_spec
from repro.conv.plan import _normalize_padding

CACHE_VERSION = 4

_DEFAULT_CACHE = os.path.join("~", ".cache", "repro_autotune.json")
_DEFAULT_BUDGET_MS = 2000.0
_DEFAULT_REPS = 3

AutotuneInfo = collections.namedtuple(
    "AutotuneInfo", ["hits", "misses", "fallbacks", "measured"])


@dataclasses.dataclass(frozen=True)
class TunedConfig:
    """One (backend, schedule, block) point of the tuning space.

    ``us_per_call`` is the measured median (``None`` for cost-model
    fallbacks, which are never written to the cache).  ``source`` records
    provenance: ``"measured"`` | ``"cost-model"`` | ``"seeded"``.
    """
    backend: str
    schedule: str
    bm: Optional[int] = None           # Pallas CGEMM blocks
    bn: Optional[int] = None
    bk: Optional[int] = None
    dft_bt: Optional[int] = None       # dft_tile tile-batch block
    overlap: str = "off"               # sub-slab comm/compute overlap
    us_per_call: Optional[float] = None
    source: str = "measured"

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "TunedConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    def block_kwargs(self) -> dict:
        return dict(bm=self.bm, bn=self.bn, bk=self.bk, dft_bt=self.dft_bt)


# --------------------------------------------------------------------------
# Environment knobs
# --------------------------------------------------------------------------

def cache_path() -> str:
    """Tuning-cache file (env ``REPRO_AUTOTUNE_CACHE``)."""
    return os.path.expanduser(
        os.environ.get("REPRO_AUTOTUNE_CACHE", _DEFAULT_CACHE))


def autotune_enabled() -> bool:
    """Whether ``tune`` may *measure* (env ``REPRO_AUTOTUNE``); cache hits
    are served either way."""
    return os.environ.get("REPRO_AUTOTUNE", "1").strip().lower() \
        not in ("0", "false", "off", "no")


def budget_ms() -> float:
    try:
        return float(os.environ.get("REPRO_AUTOTUNE_BUDGET_MS",
                                    _DEFAULT_BUDGET_MS))
    except ValueError:
        return _DEFAULT_BUDGET_MS


def _env_reps() -> int:
    try:
        return max(1, int(os.environ.get("REPRO_AUTOTUNE_REPS",
                                         _DEFAULT_REPS)))
    except ValueError:
        return _DEFAULT_REPS


# --------------------------------------------------------------------------
# Persistent cache store
# --------------------------------------------------------------------------

class TuningCache:
    """JSON-file-backed key -> ``TunedConfig`` store (write-through,
    atomic replace; tolerant of a missing/corrupt/old-version file)."""

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()
        self._entries: dict = self._load()

    def _load(self) -> dict:
        try:
            with open(self.path) as fh:
                data = json.load(fh)
            if not isinstance(data, dict) \
                    or data.get("version") != CACHE_VERSION:
                return {}
            entries = data.get("entries", {})
            return {k: TunedConfig.from_json(v)
                    for k, v in entries.items() if isinstance(v, dict)}
        except (OSError, ValueError, TypeError):
            return {}

    def get(self, key: str) -> Optional[TunedConfig]:
        with self._lock:
            return self._entries.get(key)

    def put(self, key: str, cfg: TunedConfig) -> None:
        with self._lock:
            self._entries[key] = cfg
            self._flush()

    def _flush(self) -> None:
        payload = {"version": CACHE_VERSION,
                   "entries": {k: v.to_json()
                               for k, v in sorted(self._entries.items())}}
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = f"{self.path}.tmp.{os.getpid()}"
        with open(tmp, "w") as fh:
            json.dump(payload, fh, indent=1, sort_keys=True)
        os.replace(tmp, self.path)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


_lock = threading.RLock()
_stores: dict = {}                      # resolved path -> TuningCache
_hits = _misses = _fallbacks = _measured = 0


def _store() -> TuningCache:
    path = cache_path()
    with _lock:
        store = _stores.get(path)
        if store is None:
            store = _stores[path] = TuningCache(path)
        return store


def autotune_info() -> AutotuneInfo:
    with _lock:
        return AutotuneInfo(_hits, _misses, _fallbacks, _measured)


def reset() -> None:
    """Drop the in-memory store and counters (cache *files* are kept —
    the next ``tune`` re-reads them from disk)."""
    global _hits, _misses, _fallbacks, _measured
    with _lock:
        _stores.clear()
        _hits = _misses = _fallbacks = _measured = 0


# --------------------------------------------------------------------------
# Cache keys
# --------------------------------------------------------------------------

def _device_kind() -> str:
    import jax
    return str(jax.devices()[0].device_kind).replace("|", "/")


def _jax_version() -> str:
    import jax
    return jax.__version__


def _mesh_signature(mesh) -> str:
    if mesh is None:
        return "none"
    axes = ",".join(f"{a}:{n}" for a, n in mesh.shape.items())
    ids = ",".join(str(d.id) for d in mesh.devices.flat)
    return f"{axes};dev[{ids}]"


def _dtype_name(dtype) -> str:
    if dtype is None:
        return "none"
    try:
        import numpy as np
        return np.dtype(dtype).name
    except TypeError:
        return str(dtype)


def spec_signature(x_shape, k_shape, *, padding=(0, 0), delta: int = 16,
                   stride: int = 1, schedule: str = "auto", mesh=None,
                   three_m: bool = True, compute_dtype=None,
                   data_axis: str = "data", model_axis: str = "model",
                   replicate_kernel_transform: bool = False,
                   overlap: str = "off",
                   bm=None, bn=None, bk=None, dft_bt=None) -> str:
    """Device-independent part of the cache key: the problem + the
    constraints the caller put on the tuner (requested schedule, mesh,
    precision, kernel-transform placement, pinned blocks).  Two calls that
    could legally get different winners must get different signatures — a
    pin-constrained sweep must never answer for an unconstrained one.  Unit
    stride adds nothing to the signature, so persisted unit-stride entries
    keep their keys."""
    pad = _normalize_padding(padding)
    strided = f"|stride={int(stride)}" if int(stride) != 1 else ""
    return (f"v{CACHE_VERSION}"
            f"|x={tuple(map(int, x_shape))}|k={tuple(map(int, k_shape))}"
            f"|pad={pad}{strided}|delta={int(delta)}|sched={schedule}"
            f"|mesh={_mesh_signature(mesh)}|3m={int(bool(three_m))}"
            f"|dtype={_dtype_name(compute_dtype)}"
            f"|axes={data_axis},{model_axis}"
            f"|rkt={int(bool(replicate_kernel_transform))}"
            f"|ov={overlap}"
            f"|pins={bm},{bn},{bk},{dft_bt}")


def cache_key(x_shape, k_shape, **kwargs) -> str:
    """Full cache key: spec signature + device kind + jax version."""
    return (spec_signature(x_shape, k_shape, **kwargs)
            + f"|dev={_device_kind()}|jax={_jax_version()}")


# --------------------------------------------------------------------------
# Candidate generation
# --------------------------------------------------------------------------

def _clamp_edge(v: int) -> int:
    return max(8, min(128, v))


def _block_candidates(spec: ConvSpec) -> list:
    """(bm, bn, bk) candidates for the Pallas CGEMM: the rounded default
    plus a half- and double-sized variant (clamped to the 8..128 edges)."""
    from repro.kernels.cgemm.ops import default_blocks
    base = default_blocks(spec.M, spec.Cout, spec.C)
    cands = [(None, None, None)]
    for f in (0.5, 2.0):
        alt = tuple(_clamp_edge(int(v * f)) for v in base)
        if alt != base and alt not in cands:
            cands.append(alt)
    return cands


def _merge_pins(cand: TunedConfig, bm, bn, bk, dft_bt) -> TunedConfig:
    """User-pinned block values override candidate values."""
    return dataclasses.replace(
        cand,
        bm=bm if bm is not None else cand.bm,
        bn=bn if bn is not None else cand.bn,
        bk=bk if bk is not None else cand.bk,
        dft_bt=dft_bt if dft_bt is not None else cand.dft_bt)


def candidates(spec: ConvSpec, *, schedule: str = "auto", mesh=None,
               overlap: str = "off",
               bm=None, bn=None, bk=None, dft_bt=None) -> list:
    """Enumerate the tuning space, cost-model pick first (so a clamped
    budget still measures the sane default), Pallas configs last (interpret
    mode on CPU makes them the most expensive to time).

    ``overlap="auto"`` adds the sub-slab comm/compute-overlap axis
    (``off``/``slab:2``/``slab:4``) for the sharded FFT schedules; local
    schedules and ``direct`` have nothing to overlap and stay ``off``.
    Overlapped Pallas candidates are timed at default blocks only (the
    planner re-pins blocks against the sub-slab shape, so sweeping block
    variants per slab count would square the Pallas tail of the sweep).
    A spec only ``direct`` can run (a stride) has one candidate,
    ``direct`` on ``local``."""
    from repro.conv import plan
    if plan._direct_only(spec.kh, spec.kw, spec.delta, spec.stride):
        return [_merge_pins(TunedConfig("direct", "local"),
                            bm, bn, bk, dft_bt)]
    if schedule != "auto":
        scheds = [schedule]
    else:
        scheds = ["nfft", "wfft"] if mesh is not None else ["local"]
    out = []
    for sched in scheds:
        local = sched == "local"
        if local:
            ovs = ["off"] if overlap in ("auto", "off") else [overlap]
        elif overlap == "auto":
            ovs = ["off", "slab:2", "slab:4"]
        else:
            ovs = [overlap]
        backends = (["direct", "fft-xla", "fft-pallas"] if local
                    else ["fft-xla", "fft-pallas"])
        for be in backends:
            if be == "direct":
                out.append(TunedConfig(be, sched))
                continue
            for ov in ovs:
                if be != "fft-pallas" or ov != "off":
                    # overlapped Pallas re-pins blocks per sub-slab — time
                    # only the default-block point
                    out.append(TunedConfig(be, sched, overlap=ov))
                    continue
                bts = [None, 64] if local else [None]
                for blocks in _block_candidates(spec):
                    for bt in bts:
                        out.append(TunedConfig(be, sched, *blocks,
                                               dft_bt=bt, overlap=ov))
    out = [_merge_pins(c, bm, bn, bk, dft_bt) for c in out]
    # dedupe (pins can collapse block variants) preserving order
    seen, uniq = set(), []
    for c in out:
        key = (c.backend, c.schedule, c.bm, c.bn, c.bk, c.dft_bt,
               c.overlap)
        if key not in seen:
            seen.add(key)
            uniq.append(c)
    # cost-model pick first (``_auto_backend`` never picks Pallas, so the
    # pick is always a single candidate), Pallas variants last
    pick = _cost_model_pick(spec, scheds[0])
    uniq.sort(key=lambda c: 0 if ((c.backend, c.schedule) == pick
                                  and c.overlap == "off")
              else 1 if c.backend != "fft-pallas" else 2)
    return uniq


def _cost_model_pick(spec: ConvSpec, sched: str) -> tuple:
    from repro.conv.plan import _auto_backend
    if sched != "local":
        return ("fft-xla", sched)
    return (_auto_backend(spec), sched)


# --------------------------------------------------------------------------
# Timing harness
# --------------------------------------------------------------------------

def measure_us(fn, *args, reps: int = _DEFAULT_REPS, **kwargs) -> float:
    """Warmup (compile) once, then median-of-``reps`` wall microseconds."""
    import jax
    jax.block_until_ready(fn(*args, **kwargs))
    ts = []
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kwargs))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


def _measure_candidate(cand: TunedConfig, x_shape, k_shape, *, padding,
                       delta, stride, mesh, three_m, compute_dtype,
                       data_axis, model_axis, replicate_kernel_transform,
                       reps) -> float:
    """Time one candidate through the real planner with a representative
    bias+relu epilogue (exercises the fused ``dft_tile`` tail, so
    ``dft_bt`` is a measured axis)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.conv.epilogue import Epilogue
    from repro.conv.plan import plan_conv
    plan = plan_conv(x_shape, k_shape, padding=padding, delta=delta,
                     stride=stride, backend=cand.backend,
                     schedule=cand.schedule, mesh=mesh, three_m=three_m,
                     bm=cand.bm, bn=cand.bn,
                     bk=cand.bk, dft_bt=cand.dft_bt, overlap=cand.overlap,
                     compute_dtype=compute_dtype, data_axis=data_axis,
                     model_axis=model_axis,
                     replicate_kernel_transform=replicate_kernel_transform,
                     epilogue=Epilogue(bias=True, activation="relu"),
                     cache=False)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(x_shape), jnp.float32)
    k = jnp.asarray(rng.standard_normal(k_shape), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k_shape[0],)), jnp.float32)
    return measure_us(plan, x, k, reps=reps, bias=b)


# --------------------------------------------------------------------------
# The tuner
# --------------------------------------------------------------------------

def _cost_model_config(spec: ConvSpec, schedule: str, mesh,
                       overlap, bm, bn, bk, dft_bt) -> TunedConfig:
    if schedule == "auto":
        schedule = "nfft" if mesh is not None else "local"
    backend, _ = _cost_model_pick(spec, schedule)
    if overlap == "auto":
        overlap = "off"                 # the cost model never bets on overlap
    return TunedConfig(backend, schedule, bm=bm, bn=bn, bk=bk,
                       dft_bt=dft_bt, overlap=overlap,
                       us_per_call=None, source="cost-model")


def tune(spec, k_shape=None, *, padding=None, delta: Optional[int] = None,
         stride: Optional[int] = None, schedule: str = "auto", mesh=None,
         three_m: bool = True, compute_dtype=None, data_axis: str = "data",
         model_axis: str = "model",
         replicate_kernel_transform: bool = False,
         overlap: str = "off",
         bm=None, bn=None, bk=None, dft_bt=None,
         budget: Optional[float] = None,
         reps: Optional[int] = None) -> TunedConfig:
    """Return the winning config for this spec: warm-cache hit, measured
    sweep, or cost-model fallback (measurement disabled), in that order.
    A candidate that raises fails the sweep with its error.  Only measured
    winners are persisted — a cost-model fallback stays cold so enabling
    measurement later re-tunes.

    ``spec`` is the same first positional ``plan_conv`` takes: either a
    ``ConvSpec`` (geometry + padding + delta + stride in one object) or
    the input shape ``(B, C, H, W)`` with ``k_shape``/``padding``/
    ``delta``/``stride`` given separately.
    """
    global _hits, _misses, _fallbacks, _measured
    if isinstance(spec, ConvSpec):
        if (k_shape is not None or padding is not None or delta is not None
                or stride is not None):
            raise TypeError(
                "tune(spec, ...): a ConvSpec already carries k_shape/"
                "padding/delta/stride — pass them only with the shape-tuple "
                "form")
        x_shape = (spec.B, spec.C, spec.H, spec.W)
        k_shape = (spec.Cout, spec.C, spec.kh, spec.kw)
        padding = (spec.pad_h, spec.pad_w)
        delta = spec.delta
        stride = spec.stride
    else:
        if k_shape is None:
            raise TypeError(
                "tune(x_shape, k_shape, ...): k_shape is required with "
                "the shape-tuple form (or pass a ConvSpec)")
        x_shape = spec
        padding = (0, 0) if padding is None else padding
        delta = 16 if delta is None else delta
        stride = 1 if stride is None else int(stride)
    x_shape = tuple(map(int, x_shape))
    k_shape = tuple(map(int, k_shape))
    padding = _normalize_padding(padding)
    key_kwargs = dict(padding=padding, delta=delta, stride=stride,
                      schedule=schedule, mesh=mesh, three_m=three_m,
                      compute_dtype=compute_dtype, data_axis=data_axis,
                      model_axis=model_axis,
                      replicate_kernel_transform=replicate_kernel_transform,
                      overlap=overlap,
                      bm=bm, bn=bn, bk=bk, dft_bt=dft_bt)
    key = cache_key(x_shape, k_shape, **key_kwargs)
    store = _store()
    hit = store.get(key)
    if hit is not None:
        with _lock:
            _hits += 1
        return hit

    spec = _make_spec(x_shape, k_shape, padding, delta, stride)
    if not autotune_enabled():
        with _lock:
            _fallbacks += 1
        return _cost_model_config(spec, schedule, mesh,
                                  overlap, bm, bn, bk, dft_bt)
    with _lock:
        _misses += 1

    cands = candidates(spec, schedule=schedule, mesh=mesh,
                       overlap=overlap,
                       bm=bm, bn=bn, bk=bk, dft_bt=dft_bt)
    budget = budget_ms() if budget is None else float(budget)
    reps = _env_reps() if reps is None else max(1, int(reps))
    best = None
    t0 = time.perf_counter()
    for i, cand in enumerate(cands):
        if i > 0 and (time.perf_counter() - t0) * 1e3 > budget:
            break
        try:
            us = _measure_candidate(
                cand, x_shape, k_shape, padding=padding, delta=delta,
                stride=stride, mesh=mesh, three_m=three_m,
                compute_dtype=compute_dtype, data_axis=data_axis,
                model_axis=model_axis,
                replicate_kernel_transform=replicate_kernel_transform,
                reps=reps)
        except Exception as e:
            # every candidate is a legal plan: one that fails to compile
            # or run is an engine fault, never a silently skipped point
            raise RuntimeError(
                f"autotune candidate {cand} failed on "
                f"{_device_kind()}: {type(e).__name__}: {e}") from e
        if best is None or us < best.us_per_call:
            best = dataclasses.replace(cand, us_per_call=us,
                                       source="measured")
    with _lock:
        _measured += 1
    store.put(key, best)
    return best


def lookup(x_shape, k_shape, **key_kwargs) -> Optional[TunedConfig]:
    """Warm-cache lookup only (no measurement, no fallback)."""
    return _store().get(cache_key(x_shape, k_shape, **key_kwargs))


def seed(x_shape, k_shape, config: TunedConfig, **key_kwargs) -> str:
    """Force a winner into the cache (tests / pre-baked fleet configs);
    returns the cache key it was stored under."""
    key = cache_key(x_shape, k_shape, **key_kwargs)
    _store().put(key, config)
    return key


# --------------------------------------------------------------------------
# CLI selfcheck (CI: cache write -> reload -> same winners)
# --------------------------------------------------------------------------

def _selfcheck(x_shape, k_shape, padding) -> int:
    print(f"autotune selfcheck: cache={cache_path()} "
          f"enabled={autotune_enabled()} budget={budget_ms():.0f}ms "
          f"dev={_device_kind()} jax={_jax_version()}")
    reset()
    w1 = tune(x_shape, k_shape, padding=padding)
    print(f"  first tune : {w1}")
    if not autotune_enabled():
        w2 = tune(x_shape, k_shape, padding=padding)
        assert w2 == w1, f"cost-model fallback not deterministic: {w2}"
        print("  measurement disabled; deterministic cost-model fallback OK")
        return 0
    assert w1.source == "measured", f"expected a measured winner, got {w1}"
    assert os.path.exists(cache_path()), "tuning cache file was not written"
    reset()                             # drop memory; force re-read of disk
    w2 = tune(x_shape, k_shape, padding=padding)
    print(f"  reloaded   : {w2}")
    assert w2 == w1, f"cache round-trip changed the winner: {w1} != {w2}"
    info = autotune_info()
    assert info.hits == 1 and info.misses == 0, \
        f"reload did not hit the cache: {info}"
    with open(cache_path()) as fh:
        raw = json.load(fh)
    assert raw.get("version") == CACHE_VERSION and raw.get("entries"), \
        "cache file is not round-trippable"
    print(f"  selfcheck OK: winner {w2.backend}/{w2.schedule} "
          f"@ {w2.us_per_call:.0f}us, {len(raw['entries'])} cache entries")
    return 0


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="repro conv autotuner (see repro.conv.autotune)")
    ap.add_argument("--selfcheck", action="store_true",
                    help="tune one small spec; assert the cache file "
                         "round-trips (write -> reload -> same winners)")
    ap.add_argument("--x-shape", type=int, nargs=4, default=(1, 4, 16, 16),
                    metavar=("B", "C", "H", "W"))
    ap.add_argument("--k-shape", type=int, nargs=4, default=(8, 4, 3, 3),
                    metavar=("CO", "C", "KH", "KW"))
    ap.add_argument("--padding", type=int, default=1)
    args = ap.parse_args(argv)
    if args.selfcheck:
        return _selfcheck(tuple(args.x_shape), tuple(args.k_shape),
                          args.padding)
    w = tune(tuple(args.x_shape), tuple(args.k_shape), padding=args.padding)
    print(w)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
