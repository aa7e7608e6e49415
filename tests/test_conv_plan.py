"""Plan/execute conv engine: cache semantics, registry validation,
(backend, schedule) equivalence grid, and auto's predicted-time crossover."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.conv import (
    analyze, plan_conv, conv2d, plan_cache_info, clear_plan_cache,
    plan_cache_capacity, available_backends, available_schedules,
    register_backend,
)
from repro.conv.plan import predicted_s
from repro.core import conv2d_direct


def _rand(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


# --------------------------------------------------------------------------
# Plan cache
# --------------------------------------------------------------------------

def test_plan_cache_hit_and_reuse():
    clear_plan_cache()
    p1 = plan_conv((2, 3, 16, 16), (4, 3, 3, 3), padding=1)
    info = plan_cache_info()
    assert info.misses == 1 and info.hits == 0 and info.size == 1
    p2 = plan_conv((2, 3, 16, 16), (4, 3, 3, 3), padding=1)
    assert p2 is p1                       # same frozen object, not a copy
    assert plan_cache_info().hits == 1
    # different geometry -> different plan, new cache entry
    p3 = plan_conv((2, 3, 16, 16), (4, 3, 5, 5), padding=1)
    assert p3 is not p1
    assert plan_cache_info() == (1, 2, 2)
    # padding normalization: int 1 and (1, 1) share a key
    p4 = plan_conv((2, 3, 16, 16), (4, 3, 3, 3), padding=(1, 1))
    assert p4 is p1
    # cache=False bypasses
    p5 = plan_conv((2, 3, 16, 16), (4, 3, 3, 3), padding=1, cache=False)
    assert p5 is not p1 and p5 == p1
    clear_plan_cache()
    assert plan_cache_info() == (0, 0, 0)


def test_plan_cache_is_lru_bounded(monkeypatch):
    monkeypatch.setenv("REPRO_CONV_PLAN_CACHE_SIZE", "4")
    assert plan_cache_capacity() == 4
    clear_plan_cache()
    plans = [plan_conv((1, 2, 8 + i, 8), (2, 2, 3, 3)) for i in range(6)]
    assert plan_cache_info().size == 4          # two oldest evicted
    # newest entries still hit...
    assert plan_conv((1, 2, 13, 8), (2, 2, 3, 3)) is plans[5]
    assert plan_cache_info().hits == 1
    # ...the evicted oldest re-plans (miss, equal-but-new object)
    p0 = plan_conv((1, 2, 8, 8), (2, 2, 3, 3))
    assert p0 == plans[0] and p0 is not plans[0]
    clear_plan_cache()


def test_plan_cache_keys_mesh_by_value():
    """Two equal meshes (same axes/devices) must share one cache entry."""
    clear_plan_cache()
    mesh_a = make_mesh((1, 1), ("data", "model"))
    mesh_b = make_mesh((1, 1), ("data", "model"))
    pa = plan_conv((1, 2, 8, 8), (2, 2, 3, 3), mesh=mesh_a)
    pb = plan_conv((1, 2, 8, 8), (2, 2, 3, 3), mesh=mesh_b)
    assert pb is pa
    assert plan_cache_info() == (1, 1, 1)
    clear_plan_cache()


# --------------------------------------------------------------------------
# Registry validation
# --------------------------------------------------------------------------

def test_registry_rejects_unknown_names():
    with pytest.raises(ValueError, match="unknown conv backend"):
        plan_conv((1, 2, 8, 8), (2, 2, 3, 3), backend="nope")
    with pytest.raises(ValueError, match="unknown conv schedule"):
        plan_conv((1, 2, 8, 8), (2, 2, 3, 3), schedule="nope")


def test_registry_validates_combinations():
    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="requires a mesh"):
        plan_conv((1, 2, 8, 8), (2, 2, 3, 3), schedule="nfft")
    with pytest.raises(ValueError, match="ignores the mesh"):
        plan_conv((1, 2, 8, 8), (2, 2, 3, 3), schedule="local", mesh=mesh)
    with pytest.raises(ValueError, match="does not support schedule"):
        plan_conv((1, 2, 8, 8), (2, 2, 3, 3), backend="direct",
                  schedule="nfft", mesh=mesh)
    with pytest.raises(ValueError, match="channel mismatch"):
        plan_conv((1, 2, 8, 8), (2, 3, 3, 3))
    with pytest.raises(ValueError, match="no axis"):
        plan_conv((1, 2, 8, 8), (2, 2, 3, 3), schedule="nfft", mesh=mesh,
                  model_axis="tensor")


def test_registry_accepts_custom_backend():
    calls = []

    def _exec(plan, x, k):
        calls.append(plan.backend)
        return conv2d_direct(x, k, padding=plan.padding)

    register_backend("test-direct", _exec, schedules=("local",))
    assert "test-direct" in available_backends()
    x, k = _rand((1, 2, 8, 8), 1), _rand((2, 2, 3, 3), 2)
    y = plan_conv(x.shape, k.shape, padding=1, backend="test-direct")(x, k)
    assert calls == ["test-direct"]
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(conv2d_direct(x, k, padding=1)))


def test_plan_rejects_mismatched_shapes():
    plan = plan_conv((2, 3, 16, 16), (4, 3, 3, 3), padding=1)
    x, k = _rand((2, 3, 16, 16)), _rand((4, 3, 3, 3))
    with pytest.raises(ValueError, match="plan was built for input"):
        plan(x[:1], k)
    with pytest.raises(ValueError, match="plan was built for kernel"):
        plan(x, k[:2])


# --------------------------------------------------------------------------
# (backend, schedule) equivalence grid vs the direct oracle
# --------------------------------------------------------------------------

CASES = [
    # B, C, Co, H, W, kh, kw, pad, delta
    (2, 3, 4, 20, 20, 3, 3, 1, 16),
    (1, 4, 2, 17, 23, 5, 5, 2, 16),
    (2, 2, 2, 12, 12, 3, 3, 1, 8),
]
LOCAL_PAIRS = [("direct", "local"), ("fft-xla", "local"),
               ("fft-pallas", "local")]
SHARDED_PAIRS = [("fft-xla", "nfft"), ("fft-xla", "wfft"),
                 ("fft-pallas", "nfft"), ("fft-pallas", "wfft")]


@pytest.mark.parametrize("backend,schedule", LOCAL_PAIRS + SHARDED_PAIRS)
@pytest.mark.parametrize("case", CASES, ids=lambda c: "x".join(map(str, c)))
def test_backend_schedule_equivalence(backend, schedule, case):
    B, C, Co, H, W, kh, kw, pad, delta = case
    x, k = _rand((B, C, H, W), 1), _rand((Co, C, kh, kw), 2)
    kwargs = dict(padding=pad, delta=delta, backend=backend,
                  schedule=schedule)
    if schedule != "local":
        # degenerate 1x1 mesh: same collective program, single real device
        kwargs["mesh"] = make_mesh((1, 1), ("data", "model"))
    y = plan_conv(x.shape, k.shape, **kwargs)(x, k)
    y0 = conv2d_direct(x, k, padding=pad)
    assert y.shape == y0.shape
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0),
                               rtol=3e-4, atol=3e-4)


def test_asymmetric_padding_all_backends():
    """(pad_h, pad_w) means symmetric-per-axis everywhere (regression:
    conv2d_direct used to read it as lax (lo, hi) on both dims)."""
    x, k = _rand((1, 2, 10, 10), 11), _rand((2, 2, 3, 3), 12)
    plans = [plan_conv(x.shape, k.shape, padding=(1, 2), backend=be)
             for be in ("direct", "fft-xla", "fft-pallas")]
    ys = [np.asarray(p(x, k)) for p in plans]
    assert all(p.out_shape == (1, 2, 10, 12) for p in plans)
    for y in ys:
        assert y.shape == (1, 2, 10, 12)
        np.testing.assert_allclose(y, ys[0], rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("schedule", ["nfft", "wfft"])
def test_compute_dtype_reaches_hot_stage(schedule):
    """Regression: plan_conv(schedule="wfft", compute_dtype=bf16) used to be
    silently dropped.  Both sharded schedules must now cast the CGEMM
    operands — certified by the analyzer's dtype-flow facts: the cast must
    land on the CGEMM operands AND before the hot collective — and stay
    near the f32 result (f32 accumulation)."""
    mesh = make_mesh((1, 1), ("data", "model"))
    x, k = _rand((2, 4, 16, 16), 21), _rand((4, 4, 3, 3), 22)
    plan_bf16 = plan_conv(x.shape, k.shape, padding=1, schedule=schedule,
                          mesh=mesh, compute_dtype=jnp.bfloat16)
    profile = analyze(plan_bf16)
    assert profile.cgemm_dtypes == ("bfloat16",), \
        f"{schedule}: compute_dtype never reached the hot stage"
    hot = "psum" if schedule == "wfft" else "all_to_all"
    assert profile.collective_dtypes[hot].get("bfloat16", 0) >= 2, \
        f"{schedule}: cast landed after the hot collective"
    profile.check().raise_if_failed()
    y16 = plan_bf16(x, k)
    y32 = plan_conv(x.shape, k.shape, padding=1, schedule=schedule,
                    mesh=mesh)(x, k)
    assert y16.dtype == x.dtype
    rel = float(jnp.max(jnp.abs(y16 - y32))) / float(jnp.max(jnp.abs(y32)))
    assert rel < 0.05, f"{schedule}: bf16 hot stage diverged ({rel})"


def test_compute_dtype_honored_by_direct_backend():
    """Regression (same bug class as the wfft drop): compute_dtype must not
    be silently ignored when the plan resolves to the direct backend.
    direct is an opaque backend (no stage hooks for the analyzer to read),
    so the bf16 evidence here is numeric: the result must differ from f32
    but stay close (casts applied, f32 accumulated)."""
    x, k = _rand((1, 3, 16, 16), 23), _rand((4, 3, 1, 1), 24)
    plan = plan_conv(x.shape, k.shape, compute_dtype=jnp.bfloat16)
    assert plan.backend == "direct"           # tiny kernel -> cost model
    y16, y32 = plan(x, k), plan_conv(x.shape, k.shape)(x, k)
    assert y16.dtype == x.dtype
    rel = float(jnp.max(jnp.abs(y16 - y32))) / float(jnp.max(jnp.abs(y32)))
    assert 0 < rel < 0.05                     # casts applied, f32 accumulated


def test_replicate_kernel_transform_single_device():
    x, k = _rand((2, 3, 14, 14), 3), _rand((4, 3, 3, 3), 4)
    mesh = make_mesh((1, 1), ("data", "model"))
    plan = plan_conv(x.shape, k.shape, padding=1, schedule="nfft", mesh=mesh,
                     replicate_kernel_transform=True)
    np.testing.assert_allclose(
        np.asarray(plan(x, k)),
        np.asarray(conv2d_direct(x, k, padding=1)), rtol=3e-4, atol=3e-4)


# --------------------------------------------------------------------------
# Auto selection (cost-model crossover) and plan metadata
# --------------------------------------------------------------------------

def test_auto_backend_crossover():
    # tiny 1x1 kernel: the transforms dwarf the direct cost -> direct
    small = plan_conv((1, 3, 16, 16), (4, 3, 1, 1))
    assert small.backend == "direct"
    # 128 channels at 56 wide: direct fills the MXU, the FFT stages are
    # bound by their bytes -> direct
    mid = plan_conv((4, 128, 56, 56), (128, 128, 3, 3), padding=1)
    assert mid.backend == "direct"
    # 512 channels, 28 wide at batch 32 (VGG conv4_2): the FFT path's
    # 2.8x fewer FLOPs outweigh its bytes -> fft-xla
    big = plan_conv((32, 512, 28, 28), (512, 512, 3, 3), padding=1)
    assert big.backend == "fft-xla"
    for plan in (small, mid, big):
        t = predicted_s(plan.spec)
        assert min(t, key=t.get) == plan.backend
        assert f"direct {t['direct'] * 1e3:.3f}" in plan.describe()
    # both backends execute the mid geometry correctly
    fft = plan_conv(mid.x_shape, mid.k_shape, padding=1, backend="fft-xla")
    for plan, seed in ((small, 5), (mid, 7), (fft, 7)):
        x = _rand(plan.x_shape, seed)
        k = _rand(plan.k_shape, seed + 1)
        np.testing.assert_allclose(
            np.asarray(plan(x, k)),
            np.asarray(conv2d_direct(x, k, padding=plan.padding)),
            rtol=2e-3, atol=2e-3)


# Every unit-stride layer geometry of the benchmark's three configurations
# at its cell's batch, with the backend that ran it faster on a TPU v5e
# (PERF.md, "auto's table"; three ties within 2% go to direct), then
# measured cases on both sides of the crossover.
# B, C, Cout, H (= W), k, pad -> backend
AUTO_PICKS = [
    ((32, 3, 64, 224, 3, 1), "direct"),         # VGG-16 conv1_1
    ((32, 64, 64, 224, 3, 1), "direct"),        # conv1_2
    ((32, 64, 128, 112, 3, 1), "direct"),       # conv2_1
    ((32, 128, 128, 112, 3, 1), "direct"),      # conv2_2
    ((32, 128, 256, 56, 3, 1), "direct"),       # conv3_1
    ((32, 256, 256, 56, 3, 1), "direct"),       # conv3_2, conv3_3
    ((32, 256, 512, 28, 3, 1), "direct"),       # conv4_1 (fft 1.8% faster)
    ((32, 512, 512, 28, 3, 1), "fft-xla"),      # conv4_2, conv4_3
    ((32, 512, 512, 14, 3, 1), "direct"),       # conv5_x
    ((128, 64, 64, 56, 3, 1), "direct"),        # ResNet-34 layer1, Rconv2.2
    ((128, 128, 128, 28, 3, 1), "direct"),      # layer2, Rconv3.2
    ((128, 256, 256, 14, 3, 1), "direct"),      # layer3 (fft 0.9% faster)
    ((128, 512, 512, 7, 3, 1), "direct"),       # layer4, Rconv5.2
    ((128, 48, 128, 27, 5, 2), "direct"),       # AlexNet Aconv2
    ((128, 256, 384, 13, 3, 1), "direct"),      # Aconv3 (fft 1.9% faster)
    ((128, 192, 192, 13, 3, 1), "direct"),      # Aconv4
    ((128, 192, 128, 13, 3, 1), "direct"),      # Aconv5
    # conv4_2's geometry at batch 1: G's bytes are not amortized
    ((1, 512, 512, 28, 3, 1), "direct"),
    # 256 channels at 28 wide, batch 128: too few channels for the FFT
    ((128, 256, 256, 28, 3, 1), "direct"),
    ((128, 160, 160, 28, 3, 1), "direct"),
    ((4, 64, 64, 224, 3, 1), "direct"),
]


@pytest.mark.parametrize("geom,want", AUTO_PICKS,
                         ids=lambda v: "x".join(map(str, v))
                         if isinstance(v, tuple) else v)
def test_auto_picks_the_faster_backend(geom, want):
    B, C, Co, H, k, pad = geom
    plan = plan_conv((B, C, H, H), (Co, C, k, k), padding=pad)
    assert plan.backend == want
    t = predicted_s(plan.spec)
    assert min(t, key=t.get) == want


def test_oversize_kernel_routes_to_direct():
    """Kernels larger than delta are FFT-impossible but fine directly."""
    plan = plan_conv((1, 2, 32, 32), (3, 2, 17, 17), delta=16)
    assert plan.backend == "direct"
    x, k = _rand(plan.x_shape, 15), _rand(plan.k_shape, 16)
    np.testing.assert_allclose(
        np.asarray(plan(x, k)), np.asarray(conv2d_direct(x, k)),
        rtol=3e-4, atol=3e-4)
    with pytest.raises(ValueError, match="exceeds tile size"):
        plan_conv((1, 2, 32, 32), (3, 2, 17, 17), delta=16,
                  backend="fft-xla")


def test_auto_schedule_follows_mesh():
    mesh = make_mesh((1, 1), ("data", "model"))
    assert plan_conv((1, 2, 8, 8), (2, 2, 3, 3)).schedule == "local"
    assert plan_conv((1, 2, 8, 8), (2, 2, 3, 3),
                     mesh=mesh).schedule == "nfft"


def test_plan_metadata_and_flops():
    plan = plan_conv((2, 8, 20, 20), (4, 8, 3, 3), padding=1,
                     backend="fft-xla")
    assert plan.out_shape == (2, 4, 20, 20)
    assert plan.differentiable
    assert plan.flops() == sum(plan.spec.stage_flops().values())
    # the CGEMM runs one complex product per compact Hermitian point
    s = plan.spec
    assert s.freq_points() == 130
    assert s.cgemm_flops(three_m=False) \
        == 130 * (8 * s.M * s.C * s.Cout + 2 * s.M * s.Cout)
    direct = plan_conv((2, 8, 20, 20), (4, 8, 3, 3), padding=1,
                       backend="direct")
    assert direct.flops() == direct.spec.direct_flops()
    assert "backend=fft-xla" in plan.describe()
    # differentiability is derived from the stage pipeline: every backend
    # composed over stages is differentiable on every schedule it supports.
    pallas = plan_conv((2, 8, 20, 20), (4, 8, 3, 3), padding=1,
                       backend="fft-pallas")
    assert pallas.differentiable


def _xla_flops(f, *shapes):
    c = jax.jit(f).lower(*(jax.ShapeDtypeStruct(s, jnp.float32)
                           for s in shapes)).compile()
    cost = c.cost_analysis()
    return (cost[0] if isinstance(cost, list) else cost)["flops"]


@pytest.mark.parametrize("three_m", [True, False])
@pytest.mark.parametrize("x_shape,k_shape,pad", [
    ((2, 8, 20, 20), (16, 8, 3, 3), 1),
    ((1, 5, 13, 13), (7, 5, 5, 5), 2),
])
def test_stage_flops_match_xla_cost_analysis(x_shape, k_shape, pad, three_m):
    """``ConvSpec.stage_flops`` counts what the folded stage functions
    lower to: XLA's own count of each stage agrees within 1%."""
    from repro.core import fftconv as F
    from repro.core.cgemm import cgemm
    spec = plan_conv(x_shape, k_shape, padding=pad, backend="fft-xla").spec
    P, M, C, Co = spec.freq_points(), spec.M, spec.C, spec.Cout
    want = spec.stage_flops(three_m=three_m)
    got = {
        "input_transform": _xla_flops(
            lambda x: F.input_transform(x, spec), x_shape),
        "kernel_transform": _xla_flops(
            lambda k: F.kernel_transform(k, spec), k_shape),
        "cgemm": _xla_flops(
            lambda a, b, c, d: cgemm(a, b, c, d, three_m=three_m),
            (P, M, C), (P, M, C), (P, C, Co), (P, C, Co)),
        "output_inverse": _xla_flops(
            lambda zr, zi: F.output_inverse(zr, zi, spec),
            (P, M, Co), (P, M, Co)),
    }
    for stage, n in want.items():
        assert got[stage] == pytest.approx(n, rel=0.01), stage


def test_plan_gradients_match_direct():
    x, k = _rand((2, 3, 12, 12), 5), _rand((4, 3, 3, 3), 6)
    plan = plan_conv(x.shape, k.shape, padding=1, backend="fft-xla")

    def loss(f):
        return lambda x, k: jnp.sum(jnp.sin(f(x, k)))

    g1 = jax.grad(loss(plan), argnums=(0, 1))(x, k)
    g0 = jax.grad(loss(lambda x, k: conv2d_direct(x, k, padding=1)),
                  argnums=(0, 1))(x, k)
    for a, b in zip(g1, g0):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


def test_conv2d_one_shot_uses_cache():
    clear_plan_cache()
    x, k = _rand((1, 2, 10, 10), 7), _rand((2, 2, 3, 3), 8)
    y1 = conv2d(x, k, padding=1, backend="fft-xla")
    y2 = conv2d(x, k, padding=1, backend="fft-xla")
    assert plan_cache_info().hits >= 1
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2))
    np.testing.assert_allclose(
        np.asarray(y1), np.asarray(conv2d_direct(x, k, padding=1)),
        rtol=2e-4, atol=2e-4)


def test_plans_jit_and_registry_listing():
    assert {"direct", "fft-xla", "fft-pallas"} <= set(available_backends())
    assert {"local", "nfft", "wfft"} <= set(available_schedules())
    x, k = _rand((1, 2, 12, 12), 9), _rand((3, 2, 3, 3), 10)
    plan = plan_conv(x.shape, k.shape, padding=1, backend="fft-xla")
    y = jax.jit(plan)(x, k)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(conv2d_direct(x, k, padding=1)),
        rtol=2e-4, atol=2e-4)
