"""jit'd wrappers for the fused tile-DFT Pallas kernels."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import math

from repro.core.dft import (
    compact_inverse_mats, compact_layout, dft_mats, num_freq_real,
)
from repro.kernels import interpret_default
from repro.kernels.dft_tile.kernel import (
    tile_fft_call, tile_ifft_call, tile_ifft_epilogue_call,
    tile_rfft_call, tile_irfft_call, tile_irfft_epilogue_call,
)


DEFAULT_BT = 256                        # tile-batch block (grid rows/step)
_SUBLANE = 8                            # bt is the sublane axis of a block


def _pad_tiles(x, bt):
    n = x.shape[0]
    rem = (-n) % bt
    if rem:
        x = jnp.pad(x, ((0, rem),) + ((0, 0),) * (x.ndim - 1))
    return x


def resolve_bt(n: int, bt=None, slabs: int = 1) -> int:
    """Merge an explicit tile-batch block override over ``DEFAULT_BT``.

    ``None`` means "use the default"; explicit values must be positive
    ints (clamped to the tile count — padding a 6-tile problem to a
    256-wide block would be pure waste).  The default additionally
    *shrinks to fit*: it keeps the grid-step count the full-size default
    would need and balances the block across those steps, so padding is
    applied at most once for the whole batch instead of up to ``bt - 1``
    ghost tiles per call.  Every result is legal on a TPU: one block
    spanning all tiles, or a multiple of 8 (n=1000 gets bt=256 in four
    steps; an explicit bt=12 becomes 16).

    ``slabs > 1`` resolves for overlapped (sub-slab) execution: ``n`` is
    the un-slabbed tile count and the block is fitted to the *smallest*
    sub-slab, so one plan-time resolution covers every per-slab call
    without re-padding (mirrors ``cgemm.resolve_blocks(slabs=...)``).
    """
    if isinstance(slabs, bool) or not isinstance(slabs, int) or slabs < 1:
        raise ValueError(f"slabs must be a positive int, got {slabs!r}")
    n_fit = max(1, n // slabs)
    if bt is None:
        steps = max(1, math.ceil(n_fit / DEFAULT_BT))
        bt = max(1, math.ceil(n_fit / steps))
    elif isinstance(bt, bool) or not isinstance(bt, int) or bt <= 0:
        raise ValueError(
            f"dft_tile block override bt must be a positive int or None, "
            f"got {bt!r}")
    if bt >= n_fit:
        return n_fit                      # one block spans every tile
    return -(-bt // _SUBLANE) * _SUBLANE


@functools.partial(jax.jit, static_argnames=("delta", "bt", "interpret"))
def tile_fft_pallas(x, *, delta: int = 16, bt: int | None = None,
                    interpret: bool | None = None):
    """Forward DFT of tiles: (n, delta, delta) -> 2x (n, delta, dh)."""
    if interpret is None:
        interpret = interpret_default()
    n = x.shape[0]
    bt = resolve_bt(n, bt)
    xp = _pad_tiles(x, bt)
    Fr, Fi, Fhr, Fhi, *_ = dft_mats(delta)
    call = tile_fft_call(xp.shape[0], delta, x.dtype, bt=bt,
                         interpret=interpret)
    Tr, Ti = call(xp, Fr, Fi, Fhr, Fhi)
    return Tr[:n], Ti[:n]


@functools.partial(jax.jit, static_argnames=("delta", "bt", "interpret"))
def tile_ifft_pallas(Zr, Zi, *, delta: int = 16, bt: int | None = None,
                     interpret: bool | None = None):
    """Inverse DFT of tiles: 2x (n, delta, dh) -> (n, delta, delta)."""
    if interpret is None:
        interpret = interpret_default()
    n = Zr.shape[0]
    bt = resolve_bt(n, bt)
    Zrp, Zip = _pad_tiles(Zr, bt), _pad_tiles(Zi, bt)
    *_, Fvr, Fvi, Wr, Wi = dft_mats(delta)
    call = tile_ifft_call(Zrp.shape[0], delta, Zr.dtype, bt=bt,
                          interpret=interpret)
    return call(Zrp, Zip, Fvr, Fvi, Wr, Wi)[:n]


@functools.partial(jax.jit, static_argnames=("activation", "delta", "bt",
                                             "interpret"))
def tile_ifft_epilogue_pallas(Zr, Zi, bias, *, activation: str = "none",
                              delta: int = 16, bt: int | None = None,
                              interpret: bool | None = None):
    """Inverse DFT of tiles with the conv epilogue fused into the tail.

    ``bias`` is one scalar per tile — the bias of the output channel the
    tile belongs to — added (and the activation applied) while the block is
    still VMEM-resident: 2x (n, delta, dh) + (n,) -> (n, delta, delta).
    """
    if interpret is None:
        interpret = interpret_default()
    n = Zr.shape[0]
    bt = resolve_bt(n, bt)
    Zrp, Zip = _pad_tiles(Zr, bt), _pad_tiles(Zi, bt)
    bp = _pad_tiles(bias.reshape(n, 1).astype(Zr.dtype), bt)
    *_, Fvr, Fvi, Wr, Wi = dft_mats(delta)
    call = tile_ifft_epilogue_call(Zrp.shape[0], delta, Zr.dtype, bt=bt,
                                   activation=activation,
                                   interpret=interpret)
    return call(Zrp, Zip, Fvr, Fvi, Wr, Wi, bp)[:n]


# --------------------------------------------------------------------------
# Compact-Hermitian (rfft) variants: flat (n, P) spectrum planes
# --------------------------------------------------------------------------

def _layout_operands(delta):
    """(store (1,P), src (1,rect), sgn (1,rect)) kernel operands."""
    store, src, sgn = compact_layout(delta)
    return store[None, :], src[None, :], sgn[None, :]


@functools.partial(jax.jit, static_argnames=("delta", "bt", "interpret"))
def tile_rfft_pallas(x, *, delta: int = 16, bt: int | None = None,
                     interpret: bool | None = None):
    """Forward DFT + compact-Hermitian pack: (n, delta, delta) -> 2x (n, P)
    with ``P = num_freq_real(delta)`` (~delta^2/2; see
    ``repro.core.dft.compact_layout``).  DC/Nyquist self-conjugate columns
    keep only their non-redundant rows, for even and odd delta alike."""
    if interpret is None:
        interpret = interpret_default()
    n = x.shape[0]
    bt = resolve_bt(n, bt)
    xp = _pad_tiles(x, bt)
    Fr, Fi, Fhr, Fhi, *_ = dft_mats(delta)
    store, _, _ = _layout_operands(delta)
    P = num_freq_real(delta)
    call = tile_rfft_call(xp.shape[0], delta, P, x.dtype, bt=bt,
                          interpret=interpret)
    Tr, Ti = call(xp, Fr, Fi, Fhr, Fhi, store)
    return Tr[:n], Ti[:n]


@functools.partial(jax.jit, static_argnames=("delta", "bt", "interpret"))
def tile_irfft_pallas(Zr, Zi, *, delta: int = 16, bt: int | None = None,
                      interpret: bool | None = None):
    """Compact-layout inverse DFT: 2x (n, P) -> (n, delta, delta) real.
    Accepts ``P >= num_freq_real(delta)`` (trailing padding is ignored)."""
    if interpret is None:
        interpret = interpret_default()
    n, P = Zr.shape
    bt = resolve_bt(n, bt)
    Zrp, Zip = _pad_tiles(Zr, bt), _pad_tiles(Zi, bt)
    *_, Fvr, Fvi, Wr, Wi = dft_mats(delta)
    _, src, sgn = _layout_operands(delta)
    call = tile_irfft_call(Zrp.shape[0], delta, P, Zr.dtype, bt=bt,
                           interpret=interpret)
    return call(Zrp, Zip, Fvr, Fvi, Wr, Wi, src, sgn)[:n]


@functools.partial(jax.jit, static_argnames=("activation", "delta", "bt",
                                             "interpret"))
def tile_irfft_epilogue_pallas(Zr, Zi, bias, *, activation: str = "none",
                               delta: int = 16, bt: int | None = None,
                               interpret: bool | None = None):
    """Compact-layout inverse DFT with the conv epilogue fused into the
    tail: 2x (n, P) + (n,) bias -> (n, delta, delta), bias-shifted and
    activated while the block is VMEM-resident."""
    if interpret is None:
        interpret = interpret_default()
    n, P = Zr.shape
    bt = resolve_bt(n, bt)
    Zrp, Zip = _pad_tiles(Zr, bt), _pad_tiles(Zi, bt)
    bp = _pad_tiles(bias.reshape(n, 1).astype(Zr.dtype), bt)
    # rows past the layout's point count (all-to-all padding) get zero
    # weights, so the padded frequencies drop out of the matmul
    Kr, Ki = (jnp.pad(m, ((0, P - m.shape[0]), (0, 0)))
              for m in compact_inverse_mats(delta))
    call = tile_irfft_epilogue_call(Zrp.shape[0], delta, P, Zr.dtype, bt=bt,
                                    activation=activation,
                                    interpret=interpret)
    return call(Zrp, Zip, Kr, Ki, bp)[:n].reshape(n, delta, delta)
