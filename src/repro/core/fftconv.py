"""FFT-based convolution (the paper's algorithm): the stage primitives.

Four stages, kept as separate functions so the stage graph in
``repro.conv.stages`` can place collectives *between* stages (nFFT) or
inside stage 3 (the wFFT baseline), and so the kernel transform can run
once per weight version (``ConvPlan.prepare``):

  1. ``input_transform``   I (B,C,H,W)      -> D (P, M, C)   [rfft2 of 16x16 tiles]
  2. ``kernel_transform``  K (C',C,kh,kw)   -> G (P, C, C')  [conjugate rfft2]
  3. ``cgemm``             Z[p] = D[p] @ G[p]                [hot stage]
  4. ``output_inverse``    Z (P, M, C')     -> O (B,C',Ho,Wo) [irfft2 + crop]

For ``spectrum="real"`` (the plan default) stages 1, 2 and 4 apply each
tile's 2-D DFT as ONE matmul against a fixed folded matrix
(``dft.compact_forward_mat``, ``dft.cropped_inverse_mats``): the packing,
the conj-mirror scatter and the overlap-save crop are folded into the
weights, so every large intermediate is written once.  The ``rect`` and
``complex`` layouts keep the separable 16-wide matmul chain.

All complex tensors are (real, imag) pairs of float arrays. ``M = B*X*Delta``
(tile count), ``P = delta*(delta//2+1)`` frequency points.

Convolution here is ML cross-correlation; ``conv2d_direct`` is the oracle.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp

from repro.core.conv_spec import ConvSpec
from repro.core import dft
from repro.core.dft import (
    rfft2_tiles, irfft2_tiles, fft2_full_tiles, ifft2_full_tiles,
)


# --------------------------------------------------------------------------
# Spectrum layouts
# --------------------------------------------------------------------------
#
# Three frequency-axis layouts share the (P, M, C)-shaped stage interface:
#
#   "rect"    P = delta * (delta//2 + 1)  — the historical rfft2 grid; still
#             carries u-redundant rows in its self-conjugate columns
#             (0.5625x the full spectrum at delta=16).
#   "real"    P = num_freq_real(delta)    — compact Hermitian frequency list
#             (~0.51x at delta=16); the ConvPlan default.
#   "complex" P = delta^2                 — full spectrum; the measurement
#             twin the analyze invariants compare collective bytes against.
#
# Plans only use "real"/"complex"; "rect" remains the no-argument default of
# the raw stage primitives for direct callers.

SPECTRA = ("real", "complex")


def freq_count(spec: ConvSpec, spectrum: str = "rect") -> int:
    """Stored frequency points P for a spectrum layout."""
    if spectrum == "rect":
        return spec.P
    if spectrum == "real":
        return dft.num_freq_real(spec.delta)
    if spectrum == "complex":
        return dft.num_freq_full(spec.delta)
    raise ValueError(f"unknown spectrum {spectrum!r}")


# --------------------------------------------------------------------------
# Oracle
# --------------------------------------------------------------------------

def conv2d_direct(x, k, *, padding=0, stride=1, compute_dtype=None):
    """Direct convolution oracle: lax.conv_general_dilated, NCHW/OIHW.

    ``padding`` is an int or ``(pad_h, pad_w)``, symmetric per axis —
    the same convention as the FFT path (lax wants (lo, hi) per dim).
    ``stride`` is the same in both spatial axes.
    ``compute_dtype`` casts the operands (f32 accumulation, result back in
    ``x.dtype``) — the direct-backend analogue of the FFT schedules' hot
    CGEMM operand cast.  Runs at the engine's matmul precision
    (``repro.core.dft.PRECISION``), so float32 operands stay float32 on a
    TPU.
    """
    pad = (padding, padding) if isinstance(padding, int) else padding
    out_dtype = x.dtype
    acc = {}
    if compute_dtype is not None:
        x, k = x.astype(compute_dtype), k.astype(compute_dtype)
        acc = dict(preferred_element_type=jnp.float32)
    y = jax.lax.conv_general_dilated(
        x, k, window_strides=(stride, stride),
        padding=[(pad[0], pad[0]), (pad[1], pad[1])],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=dft.PRECISION, **acc,
    )
    return y.astype(out_dtype) if compute_dtype is not None else y


# --------------------------------------------------------------------------
# Stage 1: input transform
# --------------------------------------------------------------------------

def _tile_grid(spec: ConvSpec):
    """((top, bottom), (left, right)) padding of H and W, and the
    overlap-save row and column indices (X, delta) / (Delta, delta) of
    every tile in the padded input."""
    d = spec.delta
    pads = ((spec.pad_h, spec.Hp - spec.H - spec.pad_h),
            (spec.pad_w, spec.Wp - spec.W - spec.pad_w))
    h_idx = jnp.arange(spec.X)[:, None] * spec.t_h + jnp.arange(d)[None, :]
    w_idx = jnp.arange(spec.D)[:, None] * spec.t_w + jnp.arange(d)[None, :]
    return pads, h_idx[:, :, None, None], w_idx[None, None, :, :]


def extract_tiles(x, spec: ConvSpec):
    """(B, C, H, W) -> overlap-save patches (B, C, X, Delta, delta, delta)."""
    pads, h_idx, w_idx = _tile_grid(spec)
    patches = jnp.pad(x, ((0, 0), (0, 0), *pads))[:, :, h_idx, w_idx]
    # (B, C, X, delta, Delta, delta) -> (B, C, X, Delta, delta, delta)
    return patches.transpose(0, 1, 2, 4, 3, 5)


def extract_tiles_nhwc(x, spec: ConvSpec):
    """(B, C, H, W) -> overlap-save patches (B, X, delta, Delta, delta, C),
    channels last, so a tile's delta*delta pixels contract against
    ``dft.compact_forward_mat`` with the channels riding along."""
    pads, h_idx, w_idx = _tile_grid(spec)
    x = jnp.pad(x.transpose(0, 2, 3, 1), ((0, 0), *pads, (0, 0)))
    return x[:, h_idx, w_idx, :]


def _folded_forward(tiles, delta: int, eq: str):
    """The folded tile DFT of ``tiles`` (pixel axes ``i j`` in ``eq``):
    (real, imag) planes of the compact spectrum, the point axis ``p``."""
    A = dft.compact_forward_mat(delta)
    P = A.shape[-1] // 2
    A = A.reshape(delta, delta, 2 * P)
    return (jnp.einsum(eq, tiles, A[..., :P], precision=dft.PRECISION),
            jnp.einsum(eq, tiles, A[..., P:], precision=dft.PRECISION))


def transform_form(spectrum: str) -> str:
    """How stages 1, 2 and 4 apply the tile DFT for a spectrum layout:
    ``"folded"`` (one matmul per tile, ``spectrum="real"``) or
    ``"separable"`` (row then column matmuls)."""
    return "folded" if spectrum == "real" else "separable"


def _tiles_to_spectrum(tiles, spec: ConvSpec, spectrum: str):
    """Real tile batch (..., delta, delta) -> flat spectrum planes (..., P)."""
    if spectrum == "real":
        return _folded_forward(tiles, spec.delta, "...ij,ijp->...p")
    if spectrum == "complex":
        Tr, Ti = fft2_full_tiles(tiles, spec.delta)
        P = spec.delta * spec.delta
        return Tr.reshape(*Tr.shape[:-2], P), Ti.reshape(*Ti.shape[:-2], P)
    Tr, Ti = rfft2_tiles(tiles, spec.delta)
    if spectrum == "rect":
        P = spec.P
        return Tr.reshape(*Tr.shape[:-2], P), Ti.reshape(*Ti.shape[:-2], P)
    raise ValueError(f"unknown spectrum {spectrum!r}")


def input_transform(x, spec: ConvSpec, *, dtype=jnp.float32,
                    spectrum: str = "rect"):
    """Stage 1: I -> D (P, M, C) as (real, imag)."""
    if spectrum == "real":
        tiles = extract_tiles_nhwc(x.astype(dtype), spec)
        return tuple(T.reshape(T.shape[0], spec.M, spec.C) for T in
                     _folded_forward(tiles, spec.delta,
                                     "bxiyjc,ijp->pbxyc"))
    patches = extract_tiles(x.astype(dtype), spec)     # (B, C, X, Dl, d, d)
    Tr, Ti = _tiles_to_spectrum(patches, spec, spectrum)
    P = Tr.shape[-1]                                   # == freq_count(...)
    def to_pmc(T):                                     # (B, C, X, Dl, P)
        T = T.transpose(4, 0, 2, 3, 1)                 # (P, B, X, Dl, C)
        return T.reshape(P, spec.M, spec.C)
    return to_pmc(Tr), to_pmc(Ti)


# --------------------------------------------------------------------------
# Stage 2: kernel transform
# --------------------------------------------------------------------------

def kernel_transform(k, spec: ConvSpec, *, dtype=jnp.float32,
                     spectrum: str = "rect"):
    """Stage 2: K -> G (P, C, C') as (real, imag); imag is conjugated."""
    d = spec.delta
    kp = jnp.pad(k.astype(dtype), ((0, 0), (0, 0),
                                   (0, d - spec.kh), (0, d - spec.kw)))
    Tr, Ti = _tiles_to_spectrum(kp, spec, spectrum)    # (C', C, P)
    P = Tr.shape[-1]                                   # == freq_count(...)
    def to_pcc(T):
        return T.transpose(2, 1, 0).reshape(P, spec.C, spec.Cout)
    return to_pcc(Tr), to_pcc(-Ti)                     # conj: F*(K)


# --------------------------------------------------------------------------
# Stage 4: inverse transform
# --------------------------------------------------------------------------

def z_to_tiles(Z, spec: ConvSpec):
    """(P, M, C') frequency layout -> per-tile (B, C', X, Dl, d, dh)."""
    d, dh = spec.delta, spec.delta_h
    Z = Z.reshape(d, dh, spec.B, spec.X, spec.D, spec.Cout)
    return Z.transpose(2, 5, 3, 4, 0, 1)               # (B, C', X, Dl, d, dh)


def z_to_flat_tiles(Z, spec: ConvSpec, P: int):
    """(P', M, C') flat frequency layout -> per-tile (B, C', X, Dl, P).

    ``P`` is the layout's true point count; rows past it (all-to-all
    divisibility padding added by the nfft schedule) are dropped.
    """
    Z = Z[:P].reshape(P, spec.B, spec.X, spec.D, spec.Cout)
    return Z.transpose(1, 4, 2, 3, 0)                  # (B, C', X, Dl, P)


def assemble_output_tiles(y, spec: ConvSpec):
    """Inverse-transformed tiles (B, C', X, Dl, d, d) -> O (B, C', Ho, Wo)
    (overlap-save crop + spatial reassembly)."""
    y = y[..., :spec.t_h, :spec.t_w]
    y = y.transpose(0, 1, 2, 4, 3, 5).reshape(
        spec.B, spec.Cout, spec.X * spec.t_h, spec.D * spec.t_w)
    return y[:, :, :spec.Ho, :spec.Wo]


def _folded_inverse(Zr, Zi, spec: ConvSpec):
    """The compact-layout inverse as ``zr @ Kr + zi @ Ki`` per tile, with
    the conj-mirror scatter and the overlap-save crop folded into the
    ``(P_real, t_h * t_w)`` matrices, then spatial reassembly."""
    Kr, Ki = dft.cropped_inverse_mats(spec.delta, spec.t_h, spec.t_w)
    P = Kr.shape[0]
    eq = "pmc,pq->mcq"
    y = (jnp.einsum(eq, Zr[:P], Kr, precision=dft.PRECISION)
         + jnp.einsum(eq, Zi[:P], Ki, precision=dft.PRECISION))
    y = y.reshape(spec.B, spec.X, spec.D, spec.Cout, spec.t_h, spec.t_w)
    y = y.transpose(0, 3, 1, 4, 2, 5).reshape(
        spec.B, spec.Cout, spec.X * spec.t_h, spec.D * spec.t_w)
    return y[:, :, :spec.Ho, :spec.Wo]


def output_inverse(Zr, Zi, spec: ConvSpec, *, spectrum: str = "rect"):
    """Stage 4: Z (P, M, C') -> O (B, C', Ho, Wo).

    The P axis may carry trailing padding past the layout's point count
    (nfft all-to-all divisibility); it is sliced off here.
    """
    if spectrum == "real":
        return _folded_inverse(Zr, Zi, spec)
    d = spec.delta
    if spectrum == "rect":
        y = irfft2_tiles(z_to_tiles(Zr[:spec.P], spec),
                         z_to_tiles(Zi[:spec.P], spec), d)
    elif spectrum == "complex":
        P = d * d
        shape = (spec.B, spec.Cout, spec.X, spec.D, d, d)
        y = ifft2_full_tiles(z_to_flat_tiles(Zr, spec, P).reshape(shape),
                             z_to_flat_tiles(Zi, spec, P).reshape(shape), d)
    else:
        raise ValueError(f"unknown spectrum {spectrum!r}")
    return assemble_output_tiles(y, spec)


# --------------------------------------------------------------------------
# Full algorithm
# --------------------------------------------------------------------------

def make_spec(x_shape, k_shape, padding=0, delta=16) -> ConvSpec:
    B, C, H, W = x_shape
    Cout, C2, kh, kw = k_shape
    if C != C2:
        raise ValueError(f"channel mismatch: input C={C}, kernel C={C2}")
    pad = (padding, padding) if isinstance(padding, int) else padding
    return ConvSpec(B=B, C=C, Cout=Cout, H=H, W=W, kh=kh, kw=kw,
                    pad_h=pad[0], pad_w=pad[1], delta=delta)


def fft_conv2d(x, k, *, padding=0, delta=16, three_m: bool = True):
    """Deprecated: use ``repro.conv.plan_conv(..., backend="fft-xla")``.

    FFT-based 2-D convolution (cross-correlation), differentiable.
    Thin shim over the plan API with the old signature.
    """
    warnings.warn(
        "fft_conv2d is deprecated; use repro.conv.plan_conv(x.shape, "
        "k.shape, backend='fft-xla') and call the plan",
        DeprecationWarning, stacklevel=2)
    from repro.conv import plan_conv
    plan = plan_conv(tuple(x.shape), tuple(k.shape), padding=padding,
                     delta=delta, backend="fft-xla", three_m=three_m)
    return plan(x, k)


def fft_conv2d_pallas(x, k, *, padding=0, delta=16, three_m: bool = True,
                      bm=None, bn=None, bk=None):
    """Deprecated: use ``repro.conv.plan_conv(..., backend="fft-pallas")``.

    fft_conv2d with the hot CGEMM running through the Pallas TPU kernel
    (kernels/cgemm; interpret mode on CPU). Inference path — no custom VJP.
    """
    warnings.warn(
        "fft_conv2d_pallas is deprecated; use repro.conv.plan_conv(x.shape,"
        " k.shape, backend='fft-pallas', bm=..., bn=..., bk=...) and call "
        "the plan", DeprecationWarning, stacklevel=2)
    from repro.conv import plan_conv
    plan = plan_conv(tuple(x.shape), tuple(k.shape), padding=padding,
                     delta=delta, backend="fft-pallas", three_m=three_m,
                     bm=bm, bn=bn, bk=bk)
    return plan(x, k)
