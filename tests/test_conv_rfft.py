"""Real-input FFT (rfft) half-spectrum path.

The compact Hermitian frequency layout the FFT engine runs must agree
with the direct oracle on every registered backend x schedule pair, for
even AND odd tile sizes (the DC/Nyquist self-conjugate bins differ), and
its plan-level VJP must match the oracle gradients.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import HAVE_HYPOTHESIS, requires_hypothesis

if HAVE_HYPOTHESIS:
    from hypothesis import given, settings, strategies as st

from repro.compat import make_mesh
from repro.conv import Epilogue, plan_conv
from repro.conv.registry import backend_schedule_pairs
from repro.core import conv2d_direct
from repro.core.dft import num_freq_real


def _rand(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


def _assert_close(y, y0, tol=2e-4):
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0),
                               rtol=tol, atol=tol)


# --------------------------------------------------------------------------
# Parity with the direct oracle, every backend x schedule
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend,schedule", backend_schedule_pairs())
def test_rfft_parity_every_backend_schedule(backend, schedule):
    mesh = _mesh() if schedule != "local" else None
    x, k = _rand((2, 3, 18, 18), 1), _rand((4, 3, 3, 3), 2)
    y0 = conv2d_direct(x, k, padding=1)
    plan = plan_conv(x.shape, k.shape, padding=1, backend=backend,
                     schedule=schedule, mesh=mesh)
    _assert_close(plan(x, k), y0)


@pytest.mark.parametrize("delta,hw", [(16, 18), (16, 23), (15, 19),
                                      (8, 14), (5, 11)])
def test_rfft_even_and_odd_tile_sizes(delta, hw):
    """Odd delta has NO Nyquist column — the self-conjugate fold weights
    differ from the even case and the compact layout must still invert."""
    x, k = _rand((1, 2, hw, hw), 3), _rand((3, 2, 3, 3), 4)
    y0 = conv2d_direct(x, k, padding=1)
    plan = plan_conv(x.shape, k.shape, padding=1, delta=delta,
                     backend="fft-xla")
    _assert_close(plan(x, k), y0)


def test_rfft_fused_epilogue_parity():
    """fft-pallas/local runs stage 4 through the fused irfft+epilogue
    dft_tile kernel — bias and activation must match the oracle."""
    x, k = _rand((2, 3, 18, 18), 5), _rand((4, 3, 3, 3), 6)
    b = _rand((4,), 7)
    y0 = jax.nn.relu(conv2d_direct(x, k, padding=1)
                     + b[None, :, None, None])
    plan = plan_conv(x.shape, k.shape, padding=1, backend="fft-pallas",
                     epilogue=Epilogue(bias=True, activation="relu"))
    _assert_close(plan(x, k, bias=b), y0)


# --------------------------------------------------------------------------
# Gradients through the plan-level VJP
# --------------------------------------------------------------------------

def test_rfft_gradients_match_oracle():
    x, k = _rand((1, 2, 14, 14), 8), _rand((3, 2, 3, 3), 9)
    plan = plan_conv(x.shape, k.shape, padding=1, backend="fft-xla")

    def loss(f):
        return lambda a, b: jnp.sum(f(a, b) ** 2)

    gx, gk = jax.grad(loss(plan), argnums=(0, 1))(x, k)
    gx0, gk0 = jax.grad(
        loss(lambda a, b: conv2d_direct(a, b, padding=1)),
        argnums=(0, 1))(x, k)
    _assert_close(gx, gx0, tol=2e-3)
    _assert_close(gk, gk0, tol=2e-3)


# --------------------------------------------------------------------------
# Kernel-level parity: Pallas rfft tiles vs the jnp reference
# --------------------------------------------------------------------------

@pytest.mark.parametrize("delta", [16, 15, 8])
def test_tile_rfft_pallas_matches_ref(delta):
    from repro.kernels.dft_tile import (
        tile_irfft_pallas, tile_irfft_ref, tile_rfft_pallas, tile_rfft_ref,
    )
    x = _rand((7, delta, delta), 12)
    Tr, Ti = tile_rfft_pallas(x, delta=delta, bt=4)
    Tr0, Ti0 = tile_rfft_ref(x, delta)
    assert Tr.shape == (7, num_freq_real(delta))
    _assert_close(Tr, Tr0, tol=1e-4)
    _assert_close(Ti, Ti0, tol=1e-4)
    y = tile_irfft_pallas(Tr, Ti, delta=delta, bt=4)
    _assert_close(y, x, tol=1e-4)
    _assert_close(tile_irfft_ref(Tr0, Ti0, delta), x, tol=1e-4)


def test_tile_irfft_pallas_ignores_trailing_padding():
    """nfft pads the P axis for all-to-all divisibility; the inverse must
    treat rows past num_freq_real as inert."""
    from repro.kernels.dft_tile import tile_irfft_pallas, tile_rfft_pallas
    x = _rand((5, 16, 16), 13)
    Tr, Ti = tile_rfft_pallas(x, delta=16)
    pad = ((0, 0), (0, 6))
    yp = tile_irfft_pallas(jnp.pad(Tr, pad) + 0,
                           jnp.pad(Ti, pad) + 0, delta=16)
    _assert_close(yp, x, tol=1e-4)


@pytest.mark.parametrize("activation", ["none", "relu", "gelu", "silu"])
def test_tile_irfft_epilogue_pallas_fuses_bias_relu(activation):
    """The fused stage-4 tail of fft-pallas/local: inverse + bias +
    every registered activation in one kernel pass."""
    from repro.conv.epilogue import ACTIVATIONS
    from repro.kernels.dft_tile import (
        tile_irfft_epilogue_pallas, tile_irfft_ref, tile_rfft_pallas,
    )
    x = _rand((6, 16, 16), 14)
    b = _rand((6,), 15)
    Tr, Ti = tile_rfft_pallas(x, delta=16)
    y = tile_irfft_epilogue_pallas(Tr, Ti, b, activation=activation,
                                   delta=16)
    y0 = ACTIVATIONS[activation](tile_irfft_ref(Tr, Ti, 16)
                                 + b[:, None, None])
    _assert_close(y, y0, tol=1e-4)


# --------------------------------------------------------------------------
# Property: random geometries (hypothesis)
# --------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    @requires_hypothesis
    @settings(max_examples=12, deadline=None)
    @given(st.integers(1, 2), st.integers(1, 3), st.integers(1, 3),
           st.integers(6, 24), st.integers(6, 24),
           st.sampled_from([1, 3, 5]), st.integers(0, 2),
           st.sampled_from([16, 15, 8]))
    def test_rfft_random_geometry(B, C, Co, H, W, ksz, pad, delta):
        x = _rand((B, C, H, W), H * W + ksz)
        k = _rand((Co, C, ksz, ksz), H + W)
        y0 = conv2d_direct(x, k, padding=pad)
        y = plan_conv(x.shape, k.shape, padding=pad, delta=delta,
                      backend="fft-xla")(x, k)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y0),
                                   rtol=5e-4, atol=5e-4)
