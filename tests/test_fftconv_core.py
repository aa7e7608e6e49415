"""Core FFT-convolution vs the direct oracle (+ properties via hypothesis).

The property tests need ``hypothesis``; environments without it still run
the example-based tests (the property tests report as skipped).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import HAVE_HYPOTHESIS, requires_hypothesis

if HAVE_HYPOTHESIS:
    from hypothesis import given, settings, strategies as st

from repro.conv import plan_conv
from repro.core import conv2d_direct, make_spec


def fft_conv2d(x, k, *, padding=0, delta=16, three_m=True):
    """Planned fft-xla conv with the old helper signature (test shorthand)."""
    return plan_conv(tuple(x.shape), tuple(k.shape), padding=padding,
                     delta=delta, three_m=three_m, backend="fft-xla")(x, k)


def _rand(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


CASES = [
    # B, C, Co, H, W, kh, kw, pad, delta
    (2, 3, 4, 20, 20, 3, 3, 0, 16),
    (1, 4, 2, 17, 23, 5, 5, 2, 16),
    (2, 2, 2, 14, 14, 3, 3, 1, 16),
    (1, 1, 1, 16, 16, 1, 1, 0, 16),
    (2, 3, 2, 7, 9, 3, 3, 1, 16),
    (1, 2, 3, 30, 30, 7, 7, 3, 16),
    (2, 2, 2, 12, 12, 3, 3, 1, 8),
    (1, 2, 2, 40, 40, 5, 5, 2, 32),
]


@pytest.mark.parametrize("B,C,Co,H,W,kh,kw,pad,delta", CASES)
def test_matches_direct(B, C, Co, H, W, kh, kw, pad, delta):
    x = _rand((B, C, H, W), 1)
    k = _rand((Co, C, kh, kw), 2)
    y = fft_conv2d(x, k, padding=pad, delta=delta)
    y0 = conv2d_direct(x, k, padding=pad)
    assert y.shape == y0.shape
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("three_m", [True, False])
def test_3m_equals_4m(three_m):
    x, k = _rand((2, 4, 20, 20), 3), _rand((4, 4, 3, 3), 4)
    y = fft_conv2d(x, k, padding=1, three_m=three_m)
    y0 = conv2d_direct(x, k, padding=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0),
                               rtol=2e-4, atol=2e-4)


def test_gradients_match_direct():
    x, k = _rand((2, 3, 12, 12), 5), _rand((4, 3, 3, 3), 6)

    def loss(f):
        return lambda x, k: jnp.sum(jnp.sin(f(x, k)))

    g1 = jax.grad(loss(lambda x, k: fft_conv2d(x, k, padding=1)),
                  argnums=(0, 1))(x, k)
    g0 = jax.grad(loss(lambda x, k: conv2d_direct(x, k, padding=1)),
                  argnums=(0, 1))(x, k)
    for a, b in zip(g1, g0):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


def test_spec_geometry():
    spec = make_spec((4, 8, 56, 56), (16, 8, 3, 3), padding=1)
    assert spec.Ho == 56 and spec.Wo == 56
    assert spec.t_h == 14 and spec.X == 4 and spec.D == 4
    assert spec.freq_points() == 16 * 16 // 2 + 2 and spec.M == 4 * 16


if HAVE_HYPOTHESIS:
    @requires_hypothesis
    @settings(max_examples=20, deadline=None)
    @given(
        B=st.integers(1, 2), C=st.integers(1, 4), Co=st.integers(1, 4),
        H=st.integers(5, 24), W=st.integers(5, 24),
        k=st.sampled_from([1, 3, 5]), pad=st.integers(0, 2),
    )
    def test_property_matches_oracle(B, C, Co, H, W, k, pad):
        if H < k or W < k:
            return
        x = _rand((B, C, H, W), H * 31 + W)
        kk = _rand((Co, C, k, k), k)
        y = fft_conv2d(x, kk, padding=pad)
        y0 = conv2d_direct(x, kk, padding=pad)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y0),
                                   rtol=3e-4, atol=3e-4)

    @requires_hypothesis
    @settings(max_examples=10, deadline=None)
    @given(a=st.floats(-2, 2), b=st.floats(-2, 2))
    def test_property_linearity(a, b):
        """conv(a x1 + b x2, k) == a conv(x1, k) + b conv(x2, k)."""
        x1, x2 = _rand((1, 2, 18, 18), 7), _rand((1, 2, 18, 18), 8)
        k = _rand((3, 2, 3, 3), 9)
        lhs = fft_conv2d(a * x1 + b * x2, k, padding=1)
        rhs = a * fft_conv2d(x1, k, padding=1) \
            + b * fft_conv2d(x2, k, padding=1)
        np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs),
                                   rtol=1e-3, atol=1e-3)
else:
    @requires_hypothesis
    def test_property_matches_oracle():
        pass

    @requires_hypothesis
    def test_property_linearity():
        pass


def test_pallas_backend_matches_direct():
    """End-to-end conv with the Pallas CGEMM kernel (interpret on CPU)."""
    x, k = _rand((2, 8, 20, 20), 11), _rand((8, 8, 3, 3), 12)
    y = plan_conv(x.shape, k.shape, padding=1, backend="fft-pallas")(x, k)
    y0 = conv2d_direct(x, k, padding=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0),
                               rtol=3e-4, atol=3e-4)
