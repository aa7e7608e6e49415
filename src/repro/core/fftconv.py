"""FFT-based convolution (the paper's algorithm): the stage primitives.

Four stages, kept as separate functions so the stage graph in
``repro.conv.stages`` can place collectives *between* stages (nFFT) or
inside stage 3 (the wFFT baseline), and so the kernel transform can run
once per weight version (``ConvPlan.prepare``):

  1. ``input_transform``   I (B,C,H,W)      -> D (P, M, C)   [tile DFT]
  2. ``kernel_transform``  K (C',C,kh,kw)   -> G (P, C, C')  [conj. DFT]
  3. ``cgemm``             Z[p] = D[p] @ G[p]                [hot stage]
  4. ``output_inverse``    Z (P, M, C')     -> O (B,C',Ho,Wo) [inverse + crop]

Stages 1, 2 and 4 apply each tile's 2-D DFT as ONE matmul against a
fixed folded matrix (``dft.compact_forward_mat``,
``dft.cropped_inverse_mats``): the spectrum is the compact Hermitian list
of ``P = spec.freq_points()`` points, and the packing, the conj-mirror
scatter and the overlap-save crop are folded into the weights, so every
large intermediate is written once.

All complex tensors are (real, imag) pairs of float arrays. ``M = B*X*Delta``
(tile count).

Convolution here is ML cross-correlation; ``conv2d_direct`` is the oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.conv_spec import ConvSpec
from repro.core import dft


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


def input_transform(x, spec: ConvSpec, *, dtype=jnp.float32):
    """Stage 1: I -> D (P, M, C) as (real, imag)."""
    tiles = extract_tiles_nhwc(x.astype(dtype), spec)
    return tuple(T.reshape(T.shape[0], spec.M, spec.C) for T in
                 _folded_forward(tiles, spec.delta, "bxiyjc,ijp->pbxyc"))


# --------------------------------------------------------------------------
# Stage 2: kernel transform
# --------------------------------------------------------------------------

def kernel_transform(k, spec: ConvSpec, *, dtype=jnp.float32):
    """Stage 2: K -> G (P, C, C') as (real, imag); imag is conjugated."""
    d = spec.delta
    kp = jnp.pad(k.astype(dtype), ((0, 0), (0, 0),
                                   (0, d - spec.kh), (0, d - spec.kw)))
    Tr, Ti = _folded_forward(kp, d, "...ij,ijp->...p")   # (C', C, P)
    P = Tr.shape[-1]
    def to_pcc(T):
        return T.transpose(2, 1, 0).reshape(P, spec.C, spec.Cout)
    return to_pcc(Tr), to_pcc(-Ti)                     # conj: F*(K)


# --------------------------------------------------------------------------
# Stage 4: inverse transform
# --------------------------------------------------------------------------

def z_to_flat_tiles(Z, spec: ConvSpec, P: int):
    """(P', M, C') flat frequency layout -> per-tile (B, C', X, Dl, P).

    ``P`` is the spectrum's true point count; rows past it (all-to-all
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


def output_inverse(Zr, Zi, spec: ConvSpec):
    """Stage 4: Z (P, M, C') -> O (B, C', Ho, Wo).

    Each tile's inverse is ``zr @ Kr + zi @ Ki``, with the conj-mirror
    scatter and the overlap-save crop folded into the ``(P, t_h * t_w)``
    matrices, then spatial reassembly.  The P axis may carry trailing
    padding (nfft all-to-all divisibility); it is sliced off here.
    """
    Kr, Ki = dft.cropped_inverse_mats(spec.delta, spec.t_h, spec.t_w)
    P = Kr.shape[0]
    eq = "pmc,pq->mcq"
    y = (jnp.einsum(eq, Zr[:P], Kr, precision=dft.PRECISION)
         + jnp.einsum(eq, Zi[:P], Ki, precision=dft.PRECISION))
    y = y.reshape(spec.B, spec.X, spec.D, spec.Cout, spec.t_h, spec.t_w)
    y = y.transpose(0, 3, 1, 4, 2, 5).reshape(
        spec.B, spec.Cout, spec.X * spec.t_h, spec.D * spec.t_w)
    return y[:, :, :spec.Ho, :spec.Wo]


# --------------------------------------------------------------------------
# Geometry
# --------------------------------------------------------------------------

def make_spec(x_shape, k_shape, padding=0, delta=16) -> ConvSpec:
    B, C, H, W = x_shape
    Cout, C2, kh, kw = k_shape
    if C != C2:
        raise ValueError(f"channel mismatch: input C={C}, kernel C={C2}")
    pad = (padding, padding) if isinstance(padding, int) else padding
    return ConvSpec(B=B, C=C, Cout=Cout, H=H, W=W, kh=kh, kw=kw,
                    pad_h=pad[0], pad_w=pad[1], delta=delta)
