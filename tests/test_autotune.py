"""Measured autotuner: cache hit/miss/invalidation, ``backend="tuned"``
parity with ``"auto"``, and block-override plumbing into the Pallas
cgemm/dft_tile kernel ops."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.conv import (
    Epilogue, TunedConfig, autotune, autotune_info, clear_plan_cache,
    plan_conv, plan_network, NetworkConv,
)
from repro.core import conv2d_direct

X_SHAPE = (1, 4, 16, 16)
K_SHAPE = (8, 4, 3, 3)


def _rand(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


@pytest.fixture
def tune_env(tmp_path, monkeypatch):
    """Isolated tuning cache + small budget; engine caches cleared."""
    path = tmp_path / "tune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    monkeypatch.setenv("REPRO_AUTOTUNE_BUDGET_MS", "400")
    monkeypatch.setenv("REPRO_AUTOTUNE_REPS", "1")
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    autotune.reset()
    clear_plan_cache()
    yield path
    autotune.reset()
    clear_plan_cache()


# --------------------------------------------------------------------------
# Cache semantics
# --------------------------------------------------------------------------

def test_tune_miss_then_hit_and_persistence(tune_env):
    w1 = autotune.tune(X_SHAPE, K_SHAPE, padding=1)
    assert w1.source == "measured" and w1.us_per_call > 0
    info = autotune_info()
    assert info.misses == 1 and info.hits == 0 and info.measured == 1
    assert os.path.exists(tune_env)

    w2 = autotune.tune(X_SHAPE, K_SHAPE, padding=1)
    assert w2 == w1                              # in-memory hit
    assert autotune_info().hits == 1

    # round-trip: drop the in-memory store, reload from disk, same winner
    autotune.reset()
    w3 = autotune.tune(X_SHAPE, K_SHAPE, padding=1)
    assert w3 == w1
    info = autotune_info()
    assert info.hits == 1 and info.misses == 0 and info.measured == 0


def test_cache_file_schema(tune_env):
    autotune.tune(X_SHAPE, K_SHAPE, padding=1)
    raw = json.load(open(tune_env))
    assert raw["version"] == autotune.CACHE_VERSION
    (key, entry), = raw["entries"].items()
    assert f"dev={autotune._device_kind()}" in key
    assert f"jax={jax.__version__}" in key
    assert entry["source"] == "measured"
    assert TunedConfig.from_json(entry).backend in (
        "direct", "fft-xla", "fft-pallas")


def test_key_invalidation_on_device_kind_and_jax_version(tune_env):
    autotune.tune(X_SHAPE, K_SHAPE, padding=1)
    assert autotune_info().misses == 1

    with pytest.MonkeyPatch.context() as mp:
        # a different device kind never matches the old key -> re-measure
        mp.setattr(autotune, "_device_kind", lambda: "tpu-v9")
        autotune.tune(X_SHAPE, K_SHAPE, padding=1)
        assert autotune_info().misses == 2

        # ... and a jax upgrade likewise
        mp.setattr(autotune, "_jax_version", lambda: "99.0.0")
        autotune.tune(X_SHAPE, K_SHAPE, padding=1)
        assert autotune_info().misses == 3

    # back to the real key: still warm from the first measurement
    autotune.tune(X_SHAPE, K_SHAPE, padding=1)
    assert autotune_info().hits == 1


def test_spec_signature_separates_geometry_and_constraints(tune_env):
    s1 = autotune.spec_signature(X_SHAPE, K_SHAPE, padding=1)
    assert s1 == autotune.spec_signature(X_SHAPE, K_SHAPE, padding=(1, 1))
    assert s1 != autotune.spec_signature(X_SHAPE, K_SHAPE, padding=0)
    assert s1 != autotune.spec_signature((2, 4, 16, 16), K_SHAPE, padding=1)
    assert s1 != autotune.spec_signature(X_SHAPE, K_SHAPE, padding=1,
                                         schedule="local")
    assert s1 != autotune.spec_signature(X_SHAPE, K_SHAPE, padding=1,
                                         compute_dtype=jnp.bfloat16)
    # a pin-constrained sweep must never answer for an unconstrained one
    assert s1 != autotune.spec_signature(X_SHAPE, K_SHAPE, padding=1, bm=8)
    assert s1 != autotune.spec_signature(X_SHAPE, K_SHAPE, padding=1,
                                         dft_bt=64)
    # kernel-transform placement changes the measured nfft pipeline
    assert s1 != autotune.spec_signature(
        X_SHAPE, K_SHAPE, padding=1, replicate_kernel_transform=True)


def test_parent_format_cache_entry_is_not_served(tune_env, monkeypatch):
    """Entries written while the frequency layout was a tuning axis carry
    a v3 key with a ``spec=`` field and may name ``spectrum="complex"``:
    they must miss, never be served as a plan — whether the file is the
    old version or the key alone is old."""
    key = autotune.cache_key(X_SHAPE, K_SHAPE, padding=1)
    old_key = key.replace(f"v{autotune.CACHE_VERSION}|", "v3|", 1) \
        .replace("|ov=", "|spec=auto|ov=", 1)
    entry = {"backend": "fft-pallas", "schedule": "local", "bm": 8,
             "bn": 8, "bk": 8, "dft_bt": None, "spectrum": "complex",
             "overlap": "off", "us_per_call": 1.0, "source": "seeded"}
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    for version in (3, autotune.CACHE_VERSION):
        tune_env.write_text(json.dumps(
            {"version": version, "entries": {old_key: entry}}))
        autotune.reset()
        assert autotune.lookup(X_SHAPE, K_SHAPE, padding=1) is None
        w = autotune.tune(X_SHAPE, K_SHAPE, padding=1)
        assert w.source == "cost-model" and w.bm is None
        assert autotune_info().hits == 0


def test_failing_candidate_is_reported_not_skipped(tune_env, monkeypatch):
    """A candidate that fails to compile or run fails the sweep with the
    candidate and its error — the tuner never silently measures around a
    broken kernel."""
    real = autotune._measure_candidate

    def flaky(cand, *args, **kwargs):
        if cand.backend == "fft-pallas":
            raise ValueError("Mosaic refused the block shape")
        return real(cand, *args, **kwargs)

    monkeypatch.setattr(autotune, "_measure_candidate", flaky)
    with pytest.raises(RuntimeError, match="fft-pallas.*Mosaic refused"):
        autotune.tune(X_SHAPE, K_SHAPE, padding=1, budget=1e9)
    assert not os.path.exists(tune_env)     # nothing persisted


def test_device_kind_is_the_real_device(tune_env):
    assert autotune._device_kind() == \
        str(jax.devices()[0].device_kind).replace("|", "/")


def test_corrupt_cache_file_is_tolerated(tune_env):
    tune_env.write_text("{not json!!")
    w = autotune.tune(X_SHAPE, K_SHAPE, padding=1)     # re-measures
    assert w.source == "measured"
    assert json.load(open(tune_env))["entries"]        # rewritten clean


# --------------------------------------------------------------------------
# Disabled / cold-cache fallback
# --------------------------------------------------------------------------

def test_disabled_falls_back_to_cost_model(tune_env, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    w = autotune.tune(X_SHAPE, K_SHAPE, padding=1)
    assert w.source == "cost-model" and w.us_per_call is None
    assert not os.path.exists(tune_env)     # fallbacks are never persisted
    assert autotune_info().fallbacks == 1

    # plan_conv(backend="tuned") resolves to exactly what "auto" picks
    p_tuned = plan_conv(X_SHAPE, K_SHAPE, padding=1, backend="tuned")
    p_auto = plan_conv(X_SHAPE, K_SHAPE, padding=1, backend="auto")
    assert (p_tuned.backend, p_tuned.schedule) \
        == (p_auto.backend, p_auto.schedule)
    x, k = _rand(X_SHAPE), _rand(K_SHAPE, 1)
    np.testing.assert_allclose(p_tuned(x, k), p_auto(x, k), rtol=0, atol=0)


def test_fallback_plan_is_not_frozen_in(tune_env, monkeypatch):
    """A cost-model fallback must not be memoized under the tuned key:
    once the tuning cache warms, the next plan adopts the winner."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    p_cold = plan_conv(X_SHAPE, K_SHAPE, padding=1, backend="tuned")
    assert p_cold.backend == "direct"          # cost-model pick
    # the cache warms (e.g. serve --tune on this machine, or measurement
    # re-enabled) with a different winner...
    autotune.seed(X_SHAPE, K_SHAPE,
                  TunedConfig("fft-xla", "local", source="seeded"),
                  padding=(1, 1))
    # ...and the very next tuned plan picks it up — no stale memoization
    p_warm = plan_conv(X_SHAPE, K_SHAPE, padding=1, backend="tuned")
    assert p_warm.backend == "fft-xla"


def test_pinned_tune_does_not_poison_unpinned_cache(tune_env):
    """tune(bm=8) keys separately from tune(); plan-level pins overlay
    the unconstrained winner instead of constraining the sweep."""
    w_pinned = autotune.tune(X_SHAPE, K_SHAPE, padding=1, bm=8, bn=8, bk=8)
    w_free = autotune.tune(X_SHAPE, K_SHAPE, padding=1)
    assert autotune_info().misses == 2         # distinct cache entries
    assert w_pinned.source == w_free.source == "measured"
    assert autotune.cache_key(X_SHAPE, K_SHAPE, padding=(1, 1), bm=8) \
        != autotune.cache_key(X_SHAPE, K_SHAPE, padding=(1, 1))


def test_disabled_still_serves_warm_cache(tune_env, monkeypatch):
    w1 = autotune.tune(X_SHAPE, K_SHAPE, padding=1)
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    autotune.reset()
    w2 = autotune.tune(X_SHAPE, K_SHAPE, padding=1)
    assert w2 == w1 and autotune_info().hits == 1


# --------------------------------------------------------------------------
# backend="tuned" through the planner
# --------------------------------------------------------------------------

def test_tuned_plan_resolves_and_matches_oracle(tune_env):
    plan = plan_conv(X_SHAPE, K_SHAPE, padding=1, backend="tuned")
    assert plan.backend in ("direct", "fft-xla", "fft-pallas")
    assert plan.schedule == "local"
    x, k = _rand(X_SHAPE), _rand(K_SHAPE, 1)
    np.testing.assert_allclose(plan(x, k),
                               conv2d_direct(x, k, padding=(1, 1)),
                               atol=2e-4)


@pytest.mark.parametrize("backend,schedule", [
    ("direct", "local"), ("fft-xla", "local"), ("fft-pallas", "local"),
    ("fft-xla", "nfft"), ("fft-xla", "wfft"),
    ("fft-pallas", "nfft"), ("fft-pallas", "wfft"),
])
def test_tuned_parity_with_auto_for_every_pair(tune_env, backend, schedule):
    """Whatever pair the tuner crowns, execution must match ``auto``'s
    numerics: seed the cache with each pair as the winner and compare."""
    mesh = make_mesh((1, 1), ("data", "model")) \
        if schedule in ("nfft", "wfft") else None
    autotune.seed(X_SHAPE, K_SHAPE,
                  TunedConfig(backend, schedule, source="seeded"),
                  padding=(1, 1), mesh=mesh)
    plan = plan_conv(X_SHAPE, K_SHAPE, padding=1, backend="tuned",
                     mesh=mesh)
    assert (plan.backend, plan.schedule) == (backend, schedule)
    auto = plan_conv(X_SHAPE, K_SHAPE, padding=1, backend="auto",
                     mesh=mesh)
    x, k = _rand(X_SHAPE), _rand(K_SHAPE, 1)
    np.testing.assert_allclose(plan(x, k), auto(x, k), atol=2e-4)


def test_tuned_plan_carries_seeded_blocks(tune_env):
    autotune.seed(X_SHAPE, K_SHAPE,
                  TunedConfig("fft-pallas", "local", bm=16, bn=16, bk=8,
                              dft_bt=32, source="seeded"),
                  padding=(1, 1))
    plan = plan_conv(X_SHAPE, K_SHAPE, padding=1, backend="tuned")
    assert (plan.backend, plan.bm, plan.bn, plan.bk, plan.dft_bt) \
        == ("fft-pallas", 16, 16, 8, 32)


def test_tuned_oversize_kernel_goes_direct(tune_env):
    plan = plan_conv((1, 2, 32, 32), (2, 2, 20, 20), backend="tuned")
    assert plan.backend == "direct"
    assert autotune_info() == (0, 0, 0, 0)     # no tuner involvement


def test_explicit_blocks_beat_tuned_blocks(tune_env):
    autotune.seed(X_SHAPE, K_SHAPE,
                  TunedConfig("fft-pallas", "local", bm=64, bn=64, bk=64,
                              source="seeded"),
                  padding=(1, 1))
    plan = plan_conv(X_SHAPE, K_SHAPE, padding=1, backend="tuned", bm=8)
    assert plan.bm == 8 and plan.bn == 64      # pin wins, rest tuned


# --------------------------------------------------------------------------
# Block-override plumbing into the kernel ops
# --------------------------------------------------------------------------

def test_resolve_blocks_defaults_and_validation():
    from repro.kernels.cgemm import default_blocks, resolve_blocks
    # heuristic defaults round UP; the resolver shrinks them to fit the
    # dim (same grid-step count, at most one lane of padding) so padding
    # is applied once, not re-grown at every stage
    assert default_blocks(100, 24, 3) == (128, 32, 8)
    assert resolve_blocks(100, 24, 3) == (104, 24, 8)
    assert resolve_blocks(128, 32, 8) == (128, 32, 8)  # exact fit: verbatim
    # legal explicit pins are honored verbatim; unpinned dims still shrink
    assert resolve_blocks(100, 24, 3, bm=16, bk=64) == (16, 24, 64)
    for bad in (0, -8, 2.5, True, "16"):
        with pytest.raises(ValueError, match="positive int"):
            resolve_blocks(100, 24, 3, bn=bad)


def test_resolve_bt_defaults_clamp_and_validation():
    from repro.kernels.dft_tile import DEFAULT_BT, resolve_bt
    # default shrinks to fit: same step count as DEFAULT_BT, balanced,
    # and sublane-aligned so the block stays TPU-legal (4 x 256 >= 1000)
    assert resolve_bt(1000) == 256
    assert resolve_bt(DEFAULT_BT) == DEFAULT_BT
    assert resolve_bt(10) == 10                # smaller than the default
    assert resolve_bt(1000, 64) == 64          # explicit pin: verbatim
    assert resolve_bt(48, 64) == 48            # ... clamped to tile count
    for bad in (0, -1, True, 1.5):
        with pytest.raises(ValueError, match="positive int"):
            resolve_bt(100, bad)


def test_plan_blocks_reach_cgemm_kernel(tune_env, monkeypatch):
    from repro.kernels import cgemm as cgemm_mod
    seen = {}
    real = cgemm_mod.cgemm_pallas

    def spy(Dr, Di, Gr, Gi, **kw):
        seen.update(bm=kw.get("bm"), bn=kw.get("bn"), bk=kw.get("bk"))
        return real(Dr, Di, Gr, Gi, **kw)

    monkeypatch.setattr(cgemm_mod, "cgemm_pallas", spy)
    plan = plan_conv(X_SHAPE, K_SHAPE, padding=1, backend="fft-pallas",
                     bm=16, bn=8, bk=8, cache=False)
    y = plan(_rand(X_SHAPE), _rand(K_SHAPE, 1))
    jax.block_until_ready(y)
    assert (seen["bm"], seen["bn"], seen["bk"]) == (16, 8, 8)


def test_plan_dft_bt_reaches_fused_inverse(tune_env, monkeypatch):
    from repro.kernels import dft_tile as dft_mod
    seen = {}
    real = dft_mod.tile_irfft_epilogue_pallas

    def spy(Zr, Zi, bias, **kw):
        seen["bt"] = kw.get("bt")
        return real(Zr, Zi, bias, **kw)

    monkeypatch.setattr(dft_mod, "tile_irfft_epilogue_pallas", spy)
    plan = plan_conv(X_SHAPE, K_SHAPE, padding=1, backend="fft-pallas",
                     dft_bt=32, cache=False,
                     epilogue=Epilogue(bias=True, activation="relu"))
    y = plan(_rand(X_SHAPE), _rand(K_SHAPE, 1), bias=_rand((K_SHAPE[0],), 2))
    jax.block_until_ready(y)
    assert seen["bt"] == 32


def test_block_overrides_keep_numerics():
    clear_plan_cache()
    x, k = _rand(X_SHAPE), _rand(K_SHAPE, 1)
    base = plan_conv(X_SHAPE, K_SHAPE, padding=1, backend="fft-pallas",
                     cache=False)(x, k)
    odd = plan_conv(X_SHAPE, K_SHAPE, padding=1, backend="fft-pallas",
                    bm=8, bn=8, bk=8, dft_bt=16, cache=False)(x, k)
    np.testing.assert_allclose(base, odd, atol=1e-4)


# --------------------------------------------------------------------------
# Candidate generation + network sweep
# --------------------------------------------------------------------------

def test_candidates_cover_the_space_and_order_cheap_first(tune_env):
    spec = autotune._make_spec(X_SHAPE, K_SHAPE, (1, 1), 16)
    local = autotune.candidates(spec)
    assert all(c.schedule == "local" for c in local)
    assert {c.backend for c in local} \
        == {"direct", "fft-xla", "fft-pallas"}
    assert local[0].backend != "fft-pallas"    # interpret mode goes last
    assert any(c.dft_bt for c in local)        # dft_tile tile is an axis

    mesh = make_mesh((1, 1), ("data", "model"))
    sharded = autotune.candidates(spec, mesh=mesh)
    assert {c.schedule for c in sharded} == {"nfft", "wfft"}
    assert "direct" not in {c.backend for c in sharded}

    pinned = autotune.candidates(spec, bm=8, bn=8, bk=8, dft_bt=32)
    assert all((c.bm, c.dft_bt) == (8, 32)
               for c in pinned if c.backend == "fft-pallas")


def test_plan_network_tuned_sweep_and_report(tune_env):
    layers = [
        NetworkConv("c1", X_SHAPE, K_SHAPE, padding=1),
        NetworkConv("c2", X_SHAPE, K_SHAPE, padding=1),   # same geometry
    ]
    net = plan_network(layers, backend="tuned")
    # one sweep: the duplicate geometry was tuned once, not twice
    assert autotune_info().misses == 1 and autotune_info().hits >= 0
    rep = net.tuning_report()
    assert set(rep) == {"c1", "c2"}
    for r in rep.values():
        assert r["source"] == "measured"
        assert r["us_per_call"] > 0
        assert r["backend"] in ("direct", "fft-xla", "fft-pallas")
