"""The Pallas kernels and the folded transform stages compile for a TPU
v5e, checked without a chip.

The TPU compiler is installed with jax; it compiles for a chip that is
described (``v5e:2x2``) and not attached, and refuses what the chip would
refuse (block shapes off the (8, 128) tiling, in-kernel vector gathers)
— which interpret mode on the CPU cannot show.  Nothing here runs a
kernel.  This is the only file that describes the topology: the library
that holds it is loaded by one process at a time, so the description is
made inside a fixture, never while a module is imported.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.cgemm import cgemm_pallas, resolve_blocks
from repro.kernels.dft_tile import tile_irfft_epilogue_pallas

P_REAL = 130                                # compact spectrum points at 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache: keep these out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _arg(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


# (layer, P, M, C, N) of the local fft pipeline at batch 32: M counts the
# 14x14-stride tiles of 32 images (Vconv1.2: 16x16 per 224 image)
CGEMM_SHAPES = [
    ("Vconv4.2", P_REAL, 32 * 2 * 2, 512, 512),
    ("Vconv1.2", P_REAL, 32 * 16 * 16, 64, 64),
    ("Aconv4", P_REAL, 32 * 1 * 1, 192, 192),
]


@pytest.mark.parametrize("layer,P,M,C,N", CGEMM_SHAPES,
                         ids=[s[0] for s in CGEMM_SHAPES])
def test_cgemm_compiles_for_tpu(one_chip, layer, P, M, C, N):
    d, g = _arg((P, M, C), one_chip), _arg((P, C, N), one_chip)
    text = _compiled_text(
        functools.partial(cgemm_pallas, interpret=False), d, d, g, g)
    assert "tpu_custom_call" in text


# (layer, tiles) of the fused inverse at batch 32: B * C' * tiles/image
IRFFT_SHAPES = [
    ("Vconv4.2", 32 * 512 * 2 * 2),
    ("Vconv1.2", 32 * 64 * 16 * 16),
]


@pytest.mark.parametrize("layer,n", IRFFT_SHAPES,
                         ids=[s[0] for s in IRFFT_SHAPES])
def test_irfft_epilogue_compiles_for_tpu(one_chip, layer, n):
    z = _arg((n, P_REAL), one_chip)
    text = _compiled_text(
        functools.partial(tile_irfft_epilogue_pallas, activation="relu",
                          interpret=False),
        z, z, _arg((n,), one_chip))
    assert "tpu_custom_call" in text


def test_fft_pallas_plan_compiles_both_kernels(one_chip, monkeypatch):
    """A whole fft-pallas/local Vconv4.2 layer at batch 32 with the
    bias+ReLU epilogue: the CGEMM and the fused compact inverse both reach
    the TPU compiler (the host backend here is the CPU, so the kernels'
    interpret-mode default is turned off for this compile)."""
    from repro.conv import Epilogue, plan_conv
    import repro.kernels.cgemm.ops as cgemm_ops
    import repro.kernels.dft_tile.ops as dft_ops
    monkeypatch.setattr(cgemm_ops, "interpret_default", lambda: False)
    monkeypatch.setattr(dft_ops, "interpret_default", lambda: False)
    x_shape, k_shape = (32, 512, 28, 28), (512, 512, 3, 3)
    plan = plan_conv(x_shape, k_shape, padding=1, backend="fft-pallas",
                     schedule="local",
                     epilogue=Epilogue(bias=True, activation="relu"),
                     cache=False)
    text = _compiled_text(lambda x, k, b: plan(x, k, bias=b),
                          _arg(x_shape, one_chip), _arg(k_shape, one_chip),
                          _arg((512,), one_chip))
    assert text.count('"tpu_custom_call"') == 2


VCONV12 = ((32, 64, 224, 224), (64, 64, 3, 3))     # Vconv1.2 at batch 32


def _stage1(spec):
    from repro.core import fftconv as F
    return (lambda x: F.input_transform(x, spec),
            [(spec.B, spec.C, spec.H, spec.W)])


def _stage4(spec):
    from repro.core import fftconv as F
    z = (P_REAL, spec.M, spec.Cout)
    return (lambda zr, zi: F.output_inverse(zr, zi, spec), [z, z])


# stage -> (builder, most bytes the compiled stage may access): the
# compiler's cost model counted 32.7 GB (stage 1) and 21.5 GB (stage 4)
# for the separable chain with its compact-layout gather and scatter, and
# 11.7 / 6.0 GB for the folded form; a bound between the two keeps the
# folded form from sliding back
FOLDED_STAGES = {"input_transform": (_stage1, 20e9),
                 "output_inverse": (_stage4, 12e9)}


@pytest.mark.parametrize("stage", sorted(FOLDED_STAGES))
def test_folded_stage_compiles_for_tpu(one_chip, stage):
    """Stages 1 and 4 of Vconv1.2 at the chip's size: the tile DFT as one
    folded matmul per tile."""
    from repro.core import fftconv as F
    build, max_bytes = FOLDED_STAGES[stage]
    fn, shapes = build(F.make_spec(*VCONV12, padding=1))
    compiled = jax.jit(fn).lower(
        *[_arg(s, one_chip) for s in shapes]).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert 0 < cost["bytes accessed"] < max_bytes


def _lane_legal(block, dim, align):
    """Mosaic's rule for a block edge: a multiple of the (8, 128) tiling
    or one block spanning the whole (padded) dim."""
    return block >= dim or block % align == 0


@pytest.mark.parametrize("pins", [
    (None, None, None), (16, None, None), (None, 64, 96), (12, 8, 8),
    (200, 300, 100), (8, 256, 384),
])
def test_resolve_blocks_results_are_tpu_legal(pins):
    """Every resolved (bm, bn, bk) is legal on a TPU, default or pinned:
    bm is a sublane edge (of D and Z), bn and bk are lane edges."""
    dims = [1, 3, 8, 13, 48, 96, 100, 128, 130, 192, 200, 256, 384, 512,
            1000, 8192]
    for M in dims:
        for N in (3, 48, 64, 130, 192, 384, 512):
            for C in (3, 48, 192, 384, 512):
                for slabs in (1, 4):
                    bm, bn, bk = resolve_blocks(M, N, C, *pins,
                                                slabs=slabs)
                    m_fit = max(1, M // slabs)
                    assert _lane_legal(bm, m_fit, 8), (M, slabs, pins, bm)
                    assert _lane_legal(bn, N, 128), (N, pins, bn)
                    assert _lane_legal(bk, C, 128), (C, pins, bk)


def test_resolve_bt_results_are_tpu_legal():
    from repro.kernels.dft_tile import resolve_bt
    for n in (1, 7, 100, 1000, 6144, 65536, 524288):
        for bt in (None, 1, 12, 64, 250, 4096):
            r = resolve_bt(n, bt)
            assert r >= n or r % 8 == 0, (n, bt, r)


def test_interpret_mode_is_for_the_cpu_backend_only():
    from repro.kernels import interpret_default
    assert interpret_default() == (jax.default_backend() == "cpu")
