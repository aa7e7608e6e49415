"""DFT-as-matmul: the TPU-native replacement for NEON FFT butterflies.

The paper computes 16x16 tile FFTs with hand-vectorised butterflies. A
systolic MXU hates butterfly networks but eats dense 16x16 matmuls, so we
express every (i)rfft2 of a tile as two small matrix products against
precomputed DFT matrices:

    rfft2(x)  = F_full @ x @ F_half^T            (x real, delta x delta)
    irfft2(Z) = Re( (Finv @ Z) @ Wr^T )          (Z complex, delta x delta_h)

where delta_h = delta//2 + 1 and Wr folds the Hermitian-redundant columns
back with weight 2 (columns 0 and Nyquist with weight 1).

The engine stores a tile's spectrum as the compact Hermitian list and
folds both factors further, into ONE matrix per direction
(``compact_forward_mat``, ``compact_inverse_mats``,
``cropped_inverse_mats``): a tile's delta^2 pixels contract against it
in a single matmul, so no separable intermediate is written.  The
separable functions (``rfft2_tiles``, ``irfft2_tiles``,
``pack_half_spectrum``, ``unpack_half_spectrum``) are the references the
folded matrices and the ``dft_tile`` kernels are checked against.

All complex arithmetic is struct-of-arrays (separate real/imag planes);
neither the MXU nor Pallas has a native complex dtype.

Every matmul of the engine runs at ``PRECISION`` (``HIGHEST``): XLA's
default on a TPU rounds float32 operands to bf16 for a single MXU pass,
which would make a float32 plan a bf16 one.  A plan asks for bf16
operands explicitly, through ``compute_dtype``.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

PRECISION = jax.lax.Precision.HIGHEST


def _ein(eq, a, b):
    return jnp.einsum(eq, a, b, precision=PRECISION)


@functools.lru_cache(maxsize=None)
def _dft_mats_np(delta: int):
    """Precompute (numpy, float64 -> float32) all DFT matrices for a tile size."""
    dh = delta // 2 + 1
    u = np.arange(delta)
    # Forward full DFT: F[u, h] = exp(-2i pi u h / delta)
    ang = -2.0 * np.pi * np.outer(u, u) / delta
    F = np.cos(ang) + 1j * np.sin(ang)
    F_half = F[:dh, :]                      # rfft over the last axis
    # Inverse full DFT (axis 0): Finv[h, u] = exp(+2i pi u h / delta) / delta
    Finv = np.conj(F).T / delta
    # Weighted inverse-rfft (last axis): x[., w] = Re(sum_v c_v Y[., v] e^{2i pi v w/delta})/delta
    # Fold weight 1 only for self-conjugate bins: DC always, Nyquist only
    # when delta is even (odd delta has no Nyquist bin — v == delta//2 there
    # still has a dropped conjugate partner and needs weight 2).
    v = np.arange(dh)
    self_conj = (v == 0) | ((delta % 2 == 0) & (v == delta // 2))
    c = np.where(self_conj, 1.0, 2.0)
    angw = 2.0 * np.pi * np.outer(np.arange(delta), v) / delta
    W = (np.cos(angw) + 1j * np.sin(angw)) * c[None, :] / delta   # (delta, dh)
    return (
        F.real.astype(np.float32), F.imag.astype(np.float32),
        F_half.real.astype(np.float32), F_half.imag.astype(np.float32),
        Finv.real.astype(np.float32), Finv.imag.astype(np.float32),
        W.real.astype(np.float32), W.imag.astype(np.float32),
    )


def dft_mats(delta: int):
    """jnp copies of all DFT matrices for tile size ``delta``."""
    return tuple(jnp.asarray(m) for m in _dft_mats_np(delta))


def rfft2_tiles(x, delta: int):
    """Batched rfft2 of real tiles via matmul.

    x: (..., delta, delta) real -> (Tr, Ti): (..., delta, delta_h).
    """
    Fr, Fi, Fhr, Fhi, *_ = dft_mats(delta)
    # A = F @ x  (x real): 2 real matmuls
    Ar = _ein("uh,...hw->...uw", Fr, x)
    Ai = _ein("uh,...hw->...uw", Fi, x)
    # T = A @ F_half^T: (Ar + iAi)(Fhr^T + iFhi^T)
    Tr = _ein("...uw,vw->...uv", Ar, Fhr) - _ein("...uw,vw->...uv", Ai, Fhi)
    Ti = _ein("...uw,vw->...uv", Ar, Fhi) + _ein("...uw,vw->...uv", Ai, Fhr)
    return Tr, Ti


def irfft2_tiles(Zr, Zi, delta: int):
    """Batched irfft2 via matmul. (Zr, Zi): (..., delta, delta_h) -> (..., delta, delta) real."""
    *_, Fvr, Fvi, Wr, Wi = dft_mats(delta)
    # Y = Finv @ Z (complex x complex)
    Yr = _ein("hu,...uv->...hv", Fvr, Zr) - _ein("hu,...uv->...hv", Fvi, Zi)
    Yi = _ein("hu,...uv->...hv", Fvr, Zi) + _ein("hu,...uv->...hv", Fvi, Zr)
    # x = Re( Y @ W^T ) = Yr @ Wr^T - Yi @ Wi^T
    return _ein("...hv,wv->...hw", Yr, Wr) - _ein("...hv,wv->...hw", Yi, Wi)


def num_freq_real(delta: int) -> int:
    """Frequency points in the compact Hermitian layout (the width of the
    folded matrices; ``ConvSpec.freq_points`` counts the same for a spec).

    The rect rfft2 layout (delta x delta_h) still stores u-redundant rows in
    its self-conjugate columns (v = 0, and v = delta/2 for even delta):
    T[u, v] = conj(T[delta-u, v]) there.  Dropping them leaves
    delta^2/2 + 2 points for even delta and (delta^2 + 1)/2 for odd — just
    over half the full spectrum, vs 0.5625x for the rect layout at delta=16.
    """
    return len(_compact_layout_np(delta)[0])


@functools.lru_cache(maxsize=None)
def _compact_layout_np(delta: int):
    """Gather/scatter index maps between the rect rfft2 layout and the
    compact Hermitian frequency list.

    Returns ``(store, src, sgn)`` numpy arrays:

    - ``store`` (P_real,) int32: flat rect indices (u * delta_h + v) kept in
      the compact layout, in stored order.
    - ``src``   (delta * delta_h,) int32: for every rect point, the compact
      index holding its value (its own slot, or its u-conjugate mirror
      ``(delta - u) % delta`` for dropped points).
    - ``sgn``   (delta * delta_h,) float32: +1 for stored points, -1 for
      dropped ones (imag plane is negated when reading through the mirror).
    """
    d = delta
    dh = d // 2 + 1
    keep = np.ones((d, dh), dtype=bool)
    # Self-conjugate columns: only u in [0, d//2] carries information.
    keep[d // 2 + 1:, 0] = False
    if d % 2 == 0:
        keep[d // 2 + 1:, d // 2] = False
    store = np.flatnonzero(keep.ravel())
    comp_of_rect = -np.ones(d * dh, dtype=np.int64)
    comp_of_rect[store] = np.arange(store.size)
    src = np.empty(d * dh, dtype=np.int64)
    sgn = np.empty(d * dh, dtype=np.float32)
    for u in range(d):
        for v in range(dh):
            r = u * dh + v
            if comp_of_rect[r] >= 0:
                src[r], sgn[r] = comp_of_rect[r], 1.0
            else:
                m = ((d - u) % d) * dh + v
                src[r], sgn[r] = comp_of_rect[m], -1.0
    return (store.astype(np.int32), src.astype(np.int32), sgn)


def compact_layout(delta: int):
    """jnp copies of the (store, src, sgn) compact-layout index maps."""
    store, src, sgn = _compact_layout_np(delta)
    return jnp.asarray(store), jnp.asarray(src), jnp.asarray(sgn)


@functools.lru_cache(maxsize=None)
def _compact_inverse_np(delta: int):
    """The compact-layout inverse folded into one matmul pair.

    Returns ``(Kr, Ki)`` float32, each ``(P_real, delta * delta)``, with
    ``zr @ Kr + zi @ Ki`` equal to the flattened
    ``irfft2_tiles(*unpack_half_spectrum(zr, zi, delta), delta)``: the
    conj-mirror scatter and both inverse DFT factors become fixed
    weights, so a kernel needs no gather.
    """
    d = delta
    dh = d // 2 + 1
    _, src, sgn = _compact_layout_np(d)
    u = np.arange(d)
    finv = np.exp(2j * np.pi * np.outer(u, u) / d) / d              # (h, u)
    v = np.arange(dh)
    self_conj = (v == 0) | ((d % 2 == 0) & (v == d // 2))
    w = (np.exp(2j * np.pi * np.outer(u, v) / d)
         * np.where(self_conj, 1.0, 2.0)[None, :] / d)              # (w, v)
    # y[h, w] = Re sum_{u,v} finv[h, u] Z[u, v] w[w, v], one row per (u, v)
    a = np.einsum("hu,wv->uvhw", finv, w).reshape(d * dh, d * d)
    P = int(src.max()) + 1
    kr = np.zeros((P, d * d))
    ki = np.zeros((P, d * d))
    np.add.at(kr, src, a.real)
    np.add.at(ki, src, -sgn[:, None] * a.imag)
    return kr.astype(np.float32), ki.astype(np.float32)


def compact_inverse_mats(delta: int):
    """jnp copies of the folded compact-layout inverse ``(Kr, Ki)``."""
    return tuple(jnp.asarray(m) for m in _compact_inverse_np(delta))


@functools.lru_cache(maxsize=None)
def _cropped_inverse_np(delta: int, t_h: int, t_w: int):
    """``_compact_inverse_np`` keeping only the overlap-save output pixels:
    ``(Kr, Ki)``, each ``(P_real, t_h * t_w)`` — the crop is folded in too,
    so the inverse writes no pixel that is thrown away."""
    return tuple(
        np.ascontiguousarray(
            k.reshape(-1, delta, delta)[:, :t_h, :t_w].reshape(len(k), -1))
        for k in _compact_inverse_np(delta))


def cropped_inverse_mats(delta: int, t_h: int, t_w: int):
    """jnp copies of the folded, cropped compact-layout inverse."""
    return tuple(jnp.asarray(m) for m in _cropped_inverse_np(delta, t_h, t_w))


@functools.lru_cache(maxsize=None)
def _folded_forward_np(delta: int, compact: bool = True):
    """The tile rfft2 and its packing folded into one matrix.

    Returns a float32 ``(delta * delta, 2 * P)`` matrix ``A``: for a tile
    ``x`` flattened row-major, ``x @ A`` holds the real parts of its
    spectrum at the ``P`` stored points, then the imaginary parts.  With
    ``compact`` the points are the compact Hermitian list
    (``_compact_layout_np``'s ``store``, so ``x @ A`` equals
    ``pack_half_spectrum(*rfft2_tiles(x))``); without, the whole rect
    ``delta x delta_h`` grid.
    """
    d = delta
    dh = d // 2 + 1
    u = np.arange(d)
    f = np.exp(-2j * np.pi * np.outer(u, u) / d)                   # (u, h)
    # T[u, v] = sum_{h, w} x[h, w] f[u, h] f[v, w], one column per (u, v)
    a = np.einsum("uh,vw->hwuv", f, f[:dh]).reshape(d * d, d * dh)
    if compact:
        a = a[:, _compact_layout_np(d)[0]]
    return np.concatenate([a.real, a.imag], axis=1).astype(np.float32)


def compact_forward_mat(delta: int):
    """jnp copy of the folded compact-layout forward ``A``."""
    return jnp.asarray(_folded_forward_np(delta))


def pack_half_spectrum(Tr, Ti, delta: int):
    """Rect rfft2 planes (..., delta, delta_h) -> compact (..., P_real)."""
    store, _, _ = compact_layout(delta)
    dh = delta // 2 + 1
    Tr = jnp.take(Tr.reshape(*Tr.shape[:-2], delta * dh), store, axis=-1)
    Ti = jnp.take(Ti.reshape(*Ti.shape[:-2], delta * dh), store, axis=-1)
    return Tr, Ti


def unpack_half_spectrum(Zr, Zi, delta: int):
    """Compact planes (..., P >= P_real) -> rect rfft2 (..., delta, delta_h).

    Trailing padding past P_real (e.g. all-to-all divisibility padding) is
    ignored: every ``src`` index points below P_real.
    """
    _, src, sgn = compact_layout(delta)
    dh = delta // 2 + 1
    shape = (*Zr.shape[:-1], delta, dh)
    Zr = jnp.take(Zr, src, axis=-1).reshape(shape)
    Zi = (jnp.take(Zi, src, axis=-1) * sgn.astype(Zi.dtype)).reshape(shape)
    return Zr, Zi
