"""Comm/compute-overlapped sub-slab execution (``ConvPlan.overlap``).

The overlapped schedules split the batch into ``slab:<k>`` sub-slabs and
double-buffer: slab i+1's boundary collective is issued before slab i's
hot cgemm, so a latency-hiding XLA schedule can run them concurrently.
These tests certify the *semantics* are untouched — overlapped output,
prepared execution and plan-level gradients must match the sequential
(``overlap="off"``) twin and the direct oracle, on even AND odd slab
remainders — plus knob validation, plan-cache separation, and the
analyzer's overlap invariants (collective counts, bytes parity vs the
sequential twin, uniform Pallas blocks, seeded-violation negative path).

In-process tests run the full collective program on a degenerate 1x1
mesh; the real 2- and 4-way emulated-NUMA meshes (device-count forcing +
scheduler flags from ``repro.launch.env``) run in slow subprocess tests,
keeping the main pytest process single-device (conftest contract).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.conv import analyze, plan_conv
from repro.conv.analyze import seeded_violation
from repro.core import conv2d_direct


def _rand(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


BACKENDS = ["fft-xla", "fft-pallas"]
SCHEDULES = ["nfft", "wfft"]


# --------------------------------------------------------------------------
# Parity: overlapped == sequential == oracle (even and odd slab remainders)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("schedule", SCHEDULES)
@pytest.mark.parametrize("batch", [4, 5])   # 5: odd remainder, slabs 3+2
def test_overlap_matches_sequential_and_oracle(backend, schedule, batch):
    x, k = _rand((batch, 3, 12, 12), 1), _rand((4, 3, 3, 3), 2)
    kw = dict(padding=1, backend=backend, schedule=schedule, mesh=_mesh())
    seq = plan_conv(x.shape, k.shape, overlap="off", **kw)
    ovl = plan_conv(x.shape, k.shape, overlap="slab:2", **kw)
    assert seq.num_slabs == 1 and ovl.num_slabs == 2
    y_seq, y_ovl = seq(x, k), ovl(x, k)
    # same stage math, same reduction order per slab -> tight parity
    np.testing.assert_allclose(np.asarray(y_ovl), np.asarray(y_seq),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y_ovl),
                               np.asarray(conv2d_direct(x, k, padding=1)),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_overlap_prepared_matches_one_shot(schedule):
    x, k = _rand((5, 3, 12, 12), 3), _rand((4, 3, 3, 3), 4)
    plan = plan_conv(x.shape, k.shape, padding=1, schedule=schedule,
                     mesh=_mesh(), overlap="slab:2")
    prepared = plan.prepare(k)
    np.testing.assert_allclose(np.asarray(prepared(x)),
                               np.asarray(plan(x, k)),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(jax.jit(prepared)(x)),
                               np.asarray(prepared(x)),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_overlap_grads_match_sequential_and_oracle(schedule):
    """The plan-level VJP transposes the overlap knob with the plan, so
    training through an overlapped schedule matches the sequential twin."""
    x, k = _rand((5, 3, 12, 12), 5), _rand((4, 3, 3, 3), 6)
    kw = dict(padding=1, backend="fft-xla", schedule=schedule, mesh=_mesh())
    seq = plan_conv(x.shape, k.shape, overlap="off", **kw)
    ovl = plan_conv(x.shape, k.shape, overlap="slab:2", **kw)
    assert ovl.differentiable

    def loss(f):
        return lambda a, b: jnp.sum(jnp.sin(f(a, b)))

    g_seq = jax.grad(loss(seq), argnums=(0, 1))(x, k)
    g_ovl = jax.grad(loss(ovl), argnums=(0, 1))(x, k)
    g_dir = jax.grad(loss(lambda a, b: conv2d_direct(a, b, padding=1)),
                     argnums=(0, 1))(x, k)
    for a, b in zip(g_ovl, g_seq):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
    for a, b in zip(g_ovl, g_dir):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


# --------------------------------------------------------------------------
# Knob validation + plan-cache separation
# --------------------------------------------------------------------------

def test_overlap_validation_and_normalization():
    shp = ((4, 3, 12, 12), (4, 3, 3, 3))
    with pytest.raises(ValueError, match="unknown overlap"):
        plan_conv(*shp, padding=1, schedule="nfft", mesh=_mesh(),
                  overlap="slabs:2")
    with pytest.raises(ValueError, match="unknown overlap"):
        plan_conv(*shp, padding=1, schedule="nfft", mesh=_mesh(),
                  overlap="slab:x")
    # local schedules have no boundary collective to overlap
    with pytest.raises(ValueError, match="sharded stage-pipeline"):
        plan_conv(*shp, padding=1, backend="fft-xla", overlap="slab:2")
    with pytest.raises(ValueError, match="sharded stage-pipeline"):
        plan_conv(*shp, padding=1, backend="direct", overlap="slab:2")
    # slab:1 never exists — it normalizes to off (and off is always legal)
    p = plan_conv(*shp, padding=1, backend="fft-xla", overlap="off")
    assert p.overlap == "off" and p.num_slabs == 1
    # an oversize slab count clamps once to the per-rank batch
    p = plan_conv(*shp, padding=1, schedule="nfft", mesh=_mesh(),
                  overlap="slab:8")
    assert p.overlap == "slab:4" and p.num_slabs == 4


def test_overlap_auto_resolution():
    mesh = _mesh()
    # enough per-rank batch: auto engages slab:2
    p = plan_conv((4, 3, 12, 12), (4, 3, 3, 3), padding=1, schedule="nfft",
                  mesh=mesh, overlap="auto")
    assert p.overlap == "slab:2"
    # tiny batch: slabbing 1-row slabs cannot amortize latency -> off
    p = plan_conv((2, 3, 12, 12), (4, 3, 3, 3), padding=1, schedule="nfft",
                  mesh=mesh, overlap="auto")
    assert p.overlap == "off"
    # local plans resolve auto to off instead of raising
    p = plan_conv((4, 3, 12, 12), (4, 3, 3, 3), padding=1,
                  backend="fft-xla", overlap="auto")
    assert p.overlap == "off"


def test_overlap_is_part_of_the_plan_cache_key():
    shp = ((4, 3, 12, 12), (4, 3, 3, 3))
    kw = dict(padding=1, schedule="nfft", mesh=_mesh())
    seq = plan_conv(*shp, overlap="off", **kw)
    ovl = plan_conv(*shp, overlap="slab:2", **kw)
    assert seq is not ovl
    assert seq is plan_conv(*shp, overlap="off", **kw)
    assert ovl is plan_conv(*shp, overlap="slab:2", **kw)
    assert f"overlap={ovl.overlap}" in ovl.describe()


# --------------------------------------------------------------------------
# Block resolution against sub-slab shapes (satellite: no per-slab padding)
# --------------------------------------------------------------------------

def test_resolve_blocks_and_bt_respect_slabs():
    from repro.kernels.cgemm.ops import resolve_blocks
    from repro.kernels.dft_tile.ops import resolve_bt
    bm_full, _, _ = resolve_blocks(512, 64, 64)
    bm_slab, _, _ = resolve_blocks(512, 64, 64, slabs=8)
    assert bm_slab <= bm_full
    assert bm_slab <= -(-((512 // 8)) // 8) * 8   # lane-aligned slab fit
    for bad in (0, -1, 1.5, True):
        with pytest.raises(ValueError, match="slabs"):
            resolve_blocks(64, 64, 64, slabs=bad)
    assert resolve_bt(256, slabs=4) <= resolve_bt(256)
    assert resolve_bt(8, 64, slabs=4) <= 8        # explicit bt clamps too
    with pytest.raises(ValueError, match="slabs"):
        resolve_bt(64, slabs=0)


def test_overlap_pallas_blocks_pinned_at_plan_time():
    """fft-pallas overlap plans must carry concrete, slab-fitting blocks
    (pinned once in _resolve) instead of per-call defaults."""
    p = plan_conv((5, 3, 12, 12), (4, 3, 3, 3), padding=1,
                  backend="fft-pallas", schedule="nfft", mesh=_mesh(),
                  overlap="slab:2")
    assert None not in (p.bm, p.bn, p.bk)
    m_min = (5 // 2) * p.spec.n_tiles
    assert p.bm <= -(-m_min // 8) * 8


# --------------------------------------------------------------------------
# Analyzer: overlap invariants + seeded negative path
# --------------------------------------------------------------------------

@pytest.mark.parametrize("schedule", SCHEDULES)
def test_analyzer_certifies_overlap(schedule):
    plan = plan_conv((4, 4, 20, 20), (4, 4, 3, 3), padding=1,
                     schedule=schedule, mesh=_mesh(), overlap="slab:2")
    p = analyze(plan)
    assert p.num_slabs == 2 and p.overlap == "slab:2"
    assert "slab:2" in p.describe_key()
    if schedule == "nfft":
        assert p.collectives["all_to_all"] == 4 * 2 + 2   # 4k+2
    else:
        assert p.collectives["psum"] == 2 * 2             # 2k
    # overlapping must not move more bytes than the sequential twin
    assert p.overlap_delta is not None
    assert p.overlap_delta["ratio"] <= 1.005
    p.check().raise_if_failed()
    # prepared overlap still elides exactly the kernel boundary
    prep = analyze(plan.prepare(_rand((4, 4, 3, 3), 9)))
    prep.check().raise_if_failed()
    if schedule == "nfft":
        assert prep.collectives["all_to_all"] == 4 * 2
        assert prep.elision["all_to_all"] == 2


def test_overlap_oversend_violation_is_caught():
    plan = plan_conv((4, 4, 20, 20), (4, 4, 3, 3), padding=1,
                     schedule="nfft", mesh=_mesh(), overlap="slab:2")
    with seeded_violation("overlap-oversend"):
        report = analyze(plan).check()
    assert not report.ok
    assert any(v.invariant == "overlap-bytes-parity"
               for v in report.violations)
    with pytest.raises(AssertionError, match="plan-lint"):
        report.raise_if_failed()
    # the same seed leaves sequential plans untouched (their collectives
    # never route through the slab ops)
    seq = plan_conv((4, 4, 20, 20), (4, 4, 3, 3), padding=1,
                    schedule="nfft", mesh=_mesh(), overlap="off")
    with seeded_violation("overlap-oversend"):
        assert analyze(seq).check().ok


def test_sequential_plans_have_no_overlap_delta():
    p = analyze(plan_conv((4, 4, 20, 20), (4, 4, 3, 3), padding=1,
                          schedule="nfft", mesh=_mesh(), overlap="off"))
    assert p.num_slabs == 1 and p.overlap_delta is None


# --------------------------------------------------------------------------
# Emulated-NUMA meshes (slow: subprocess keeps pytest single-device)
# --------------------------------------------------------------------------

_SCRIPT_MESH = r"""
import os, sys
sys.path.insert(0, {srcpath!r})
from repro.launch import env
env.apply({ndev})
import jax, jax.numpy as jnp, numpy as np
assert jax.device_count() == {ndev}, jax.device_count()
from repro.compat import make_mesh
mesh = make_mesh({mesh_shape}, ("data", "model"))
from repro.conv import analyze, plan_conv
from repro.core import conv2d_direct
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((9, 8, 20, 20)), jnp.float32)  # odd B
k = jnp.asarray(rng.standard_normal((8, 8, 3, 3)), jnp.float32)
y0 = conv2d_direct(x, k, padding=1)
for sched in ("nfft", "wfft"):
    kw = dict(padding=1, schedule=sched, mesh=mesh)
    seq = plan_conv(x.shape, k.shape, overlap="off", **kw)
    ovl = plan_conv(x.shape, k.shape, overlap="slab:2", **kw)
    ys, yo = jax.jit(seq)(x, k), jax.jit(ovl)(x, k)
    d_seq = float(jnp.max(jnp.abs(yo - ys))) / float(jnp.max(jnp.abs(ys)))
    d_dir = float(jnp.max(jnp.abs(yo - y0))) / float(jnp.max(jnp.abs(y0)))
    assert d_seq < 1e-5, (sched, d_seq)
    assert d_dir < 1e-4, (sched, d_dir)
    p = analyze(ovl)
    assert p.num_slabs == 2
    assert p.overlap_delta["ratio"] <= 1.005, p.overlap_delta
    p.check().raise_if_failed()
print("MESH_OVERLAP_OK", {ndev})
"""


def _run_mesh(ndev, mesh_shape):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = _SCRIPT_MESH.format(srcpath=os.path.abspath(src), ndev=ndev,
                                 mesh_shape=mesh_shape)
    r = subprocess.run([sys.executable, "-c", script],
                       env={k: v for k, v in os.environ.items()
                            if k != "XLA_FLAGS"},
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert f"MESH_OVERLAP_OK {ndev}" in r.stdout


@pytest.mark.slow
def test_overlap_on_two_way_emulated_mesh():
    _run_mesh(2, (2, 1))


@pytest.mark.slow
def test_overlap_on_four_way_emulated_mesh():
    _run_mesh(4, (2, 2))
