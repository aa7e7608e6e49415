"""Pallas TPU kernel: fused 2-D DFT of a block of tiles (stages 1/2/4).

Replaces NEON FFT butterflies with MXU matmuls: for each 16x16 tile x,
  forward:  T = (F @ x) @ F_half^T        (real input -> complex output)
  inverse:  y = Re((Finv @ Z) @ W^T)      (complex input -> real output)

A block of ``bt`` tiles is processed per grid step; both matmul stages happen
in VMEM, so the intermediate (F @ x) never touches HBM — that is the fusion
the kernel buys over the unfused einsum path.

The compact-spectrum inverse with the fused epilogue (the one kernel here
on a plan path) instead folds the conj-mirror scatter and both inverse DFT
factors into two fixed ``(P, delta*delta)`` matrices
(``repro.core.dft.compact_inverse_mats``): one lane-dense matmul pair per
block, no in-kernel gather.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import dot_precision


def _fwd_kernel(x_ref, fr_ref, fi_ref, fhr_ref, fhi_ref, tr_ref, ti_ref):
    x = x_ref[...]                       # (bt, d, d) real
    fr, fi = fr_ref[...], fi_ref[...]    # (d, d)
    fhr, fhi = fhr_ref[...], fhi_ref[...]  # (dh, d)
    # A = F @ x per tile: contract F's h with x's h (axis 1 of tile).
    ar = jnp.einsum("uh,nhw->nuw", fr, x, preferred_element_type=jnp.float32)
    ai = jnp.einsum("uh,nhw->nuw", fi, x, preferred_element_type=jnp.float32)
    # T = A @ F_half^T
    tr = jnp.einsum("nuw,vw->nuv", ar, fhr,
                    preferred_element_type=jnp.float32) \
        - jnp.einsum("nuw,vw->nuv", ai, fhi,
                     preferred_element_type=jnp.float32)
    ti = jnp.einsum("nuw,vw->nuv", ar, fhi,
                    preferred_element_type=jnp.float32) \
        + jnp.einsum("nuw,vw->nuv", ai, fhr,
                     preferred_element_type=jnp.float32)
    tr_ref[...] = tr.astype(tr_ref.dtype)
    ti_ref[...] = ti.astype(ti_ref.dtype)


def _rfwd_kernel(x_ref, fr_ref, fi_ref, fhr_ref, fhi_ref, store_ref,
                 tr_ref, ti_ref):
    """Forward tile DFT + compact-Hermitian gather in one VMEM pass.

    The rect rfft2 result (bt, d, dh) never reaches HBM: the kernel gathers
    the ``store`` frequency list (see ``repro.core.dft.compact_layout``)
    while the block is VMEM-resident, emitting (bt, P) flat planes.
    """
    x = x_ref[...]                       # (bt, d, d) real
    fr, fi = fr_ref[...], fi_ref[...]
    fhr, fhi = fhr_ref[...], fhi_ref[...]
    store = store_ref[...][0]            # (1, P) -> (P,)
    ar = jnp.einsum("uh,nhw->nuw", fr, x, preferred_element_type=jnp.float32)
    ai = jnp.einsum("uh,nhw->nuw", fi, x, preferred_element_type=jnp.float32)
    tr = jnp.einsum("nuw,vw->nuv", ar, fhr,
                    preferred_element_type=jnp.float32) \
        - jnp.einsum("nuw,vw->nuv", ai, fhi,
                     preferred_element_type=jnp.float32)
    ti = jnp.einsum("nuw,vw->nuv", ar, fhi,
                    preferred_element_type=jnp.float32) \
        + jnp.einsum("nuw,vw->nuv", ai, fhr,
                     preferred_element_type=jnp.float32)
    bt = tr.shape[0]
    tr_ref[...] = jnp.take(tr.reshape(bt, -1), store,
                           axis=1).astype(tr_ref.dtype)
    ti_ref[...] = jnp.take(ti.reshape(bt, -1), store,
                           axis=1).astype(ti_ref.dtype)


def _scatter_to_rect(zr, zi, src, sgn, delta):
    """Compact flat planes (bt, P) -> rect (bt, d, dh) via the conj-mirror
    scatter: dropped points read their mirror with the imag plane negated."""
    bt, dh = zr.shape[0], delta // 2 + 1
    zr_rect = jnp.take(zr, src, axis=1).reshape(bt, delta, dh)
    zi_rect = (jnp.take(zi, src, axis=1)
               * sgn.astype(zi.dtype)).reshape(bt, delta, dh)
    return zr_rect, zi_rect


def _rinv_kernel(zr_ref, zi_ref, fvr_ref, fvi_ref, wr_ref, wi_ref,
                 src_ref, sgn_ref, y_ref, *, delta):
    zr, zi = _scatter_to_rect(zr_ref[...], zi_ref[...], src_ref[...][0],
                              sgn_ref[...][0], delta)
    y = _inverse_block(zr, zi, fvr_ref[...], fvi_ref[...],
                       wr_ref[...], wi_ref[...])
    y_ref[...] = y.astype(y_ref.dtype)


def _rinv_epilogue_kernel(zr_ref, zi_ref, kr_ref, ki_ref, b_ref, y_ref, *,
                          activation):
    """Compact-layout inverse tile DFT + bias/activation tail on the
    VMEM-resident block (the ``fft-pallas`` stage-4 tail on ``local``):
    (bt, P) planes @ the folded (P, delta*delta) inverse -> flat tiles."""
    dot = functools.partial(jnp.dot, precision=dot_precision(zr_ref.dtype),
                            preferred_element_type=jnp.float32)
    y = dot(zr_ref[...], kr_ref[...]) + dot(zi_ref[...], ki_ref[...])
    y = _TAIL_ACTIVATIONS[activation](y + b_ref[...])
    y_ref[...] = y.astype(y_ref.dtype)


def _inverse_block(zr, zi, fvr, fvi, wr, wi):
    """The shared inverse-DFT math: Z (bt, d, dh) -> y (bt, d, d) real.
    ``_inv_kernel`` and ``_inv_epilogue_kernel`` differ only in the tail
    they apply to this block's result."""
    yr = jnp.einsum("hu,nuv->nhv", fvr, zr,
                    preferred_element_type=jnp.float32) \
        - jnp.einsum("hu,nuv->nhv", fvi, zi,
                     preferred_element_type=jnp.float32)
    yi = jnp.einsum("hu,nuv->nhv", fvr, zi,
                    preferred_element_type=jnp.float32) \
        + jnp.einsum("hu,nuv->nhv", fvi, zr,
                     preferred_element_type=jnp.float32)
    return jnp.einsum("nhv,wv->nhw", yr, wr,
                      preferred_element_type=jnp.float32) \
        - jnp.einsum("nhv,wv->nhw", yi, wi,
                     preferred_element_type=jnp.float32)


def _inv_kernel(zr_ref, zi_ref, fvr_ref, fvi_ref, wr_ref, wi_ref, y_ref):
    y = _inverse_block(zr_ref[...], zi_ref[...], fvr_ref[...], fvi_ref[...],
                       wr_ref[...], wi_ref[...])
    y_ref[...] = y.astype(y_ref.dtype)


# Epilogue activations implementable in the kernel tail (VPU-only ops; the
# tanh-approximate gelu matches repro.conv.epilogue.ACTIVATIONS exactly).
_TAIL_ACTIVATIONS = {
    "none": lambda y: y,
    "relu": lambda y: jnp.maximum(y, 0.0),
    "gelu": lambda y: jax.nn.gelu(y, approximate=True),
    "silu": jax.nn.silu,
}


def _inv_epilogue_kernel(zr_ref, zi_ref, fvr_ref, fvi_ref, wr_ref, wi_ref,
                         b_ref, y_ref, *, activation):
    """Inverse tile DFT with the conv epilogue fused into the tail.

    The second matmul's result never round-trips to HBM before the
    bias/activation pass — the whole epilogue happens on the VMEM-resident
    block, which is the memory-traffic saving the fusion buys (the inverse
    transform is bandwidth-bound, per Zlateski et al.).
    ``b_ref`` holds one bias scalar per tile (the tile's output channel).
    """
    y = _inverse_block(zr_ref[...], zi_ref[...], fvr_ref[...], fvi_ref[...],
                       wr_ref[...], wi_ref[...])
    y = y + b_ref[...][:, :, None]             # (bt, 1) -> per-tile scalar
    y = _TAIL_ACTIVATIONS[activation](y)
    y_ref[...] = y.astype(y_ref.dtype)


def _mat_spec(shape):
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape))


def tile_fft_call(n: int, delta: int, dtype, *, bt: int,
                  interpret: bool = False):
    """Forward tile DFT over (n, delta, delta) -> 2x (n, delta, dh)."""
    assert n % bt == 0
    dh = delta // 2 + 1
    x_spec = pl.BlockSpec((bt, delta, delta), lambda i: (i, 0, 0))
    t_spec = pl.BlockSpec((bt, delta, dh), lambda i: (i, 0, 0))
    return pl.pallas_call(
        _fwd_kernel,
        grid=(n // bt,),
        in_specs=[x_spec, _mat_spec((delta, delta)), _mat_spec((delta, delta)),
                  _mat_spec((dh, delta)), _mat_spec((dh, delta))],
        out_specs=[t_spec, t_spec],
        out_shape=[jax.ShapeDtypeStruct((n, delta, dh), dtype)] * 2,
        interpret=interpret,
    )


def tile_ifft_call(n: int, delta: int, dtype, *, bt: int,
                   interpret: bool = False):
    """Inverse tile DFT over 2x (n, delta, dh) -> (n, delta, delta) real."""
    assert n % bt == 0
    dh = delta // 2 + 1
    z_spec = pl.BlockSpec((bt, delta, dh), lambda i: (i, 0, 0))
    y_spec = pl.BlockSpec((bt, delta, delta), lambda i: (i, 0, 0))
    return pl.pallas_call(
        _inv_kernel,
        grid=(n // bt,),
        in_specs=[z_spec, z_spec, _mat_spec((delta, delta)),
                  _mat_spec((delta, delta)), _mat_spec((delta, dh)),
                  _mat_spec((delta, dh))],
        out_specs=y_spec,
        out_shape=jax.ShapeDtypeStruct((n, delta, delta), dtype),
        interpret=interpret,
    )


def tile_rfft_call(n: int, delta: int, P: int, dtype, *, bt: int,
                   interpret: bool = False):
    """Forward tile DFT + compact gather: (n, delta, delta) -> 2x (n, P)."""
    assert n % bt == 0
    dh = delta // 2 + 1
    x_spec = pl.BlockSpec((bt, delta, delta), lambda i: (i, 0, 0))
    t_spec = pl.BlockSpec((bt, P), lambda i: (i, 0))
    return pl.pallas_call(
        _rfwd_kernel,
        grid=(n // bt,),
        in_specs=[x_spec, _mat_spec((delta, delta)), _mat_spec((delta, delta)),
                  _mat_spec((dh, delta)), _mat_spec((dh, delta)),
                  _mat_spec((1, P))],
        out_specs=[t_spec, t_spec],
        out_shape=[jax.ShapeDtypeStruct((n, P), dtype)] * 2,
        interpret=interpret,
    )


def tile_irfft_call(n: int, delta: int, P: int, dtype, *, bt: int,
                    interpret: bool = False):
    """Compact-layout inverse tile DFT: 2x (n, P) -> (n, delta, delta).

    ``P`` may exceed the layout's true point count (all-to-all padding);
    every scatter index points below it, so trailing rows are ignored.
    """
    assert n % bt == 0
    dh = delta // 2 + 1
    z_spec = pl.BlockSpec((bt, P), lambda i: (i, 0))
    y_spec = pl.BlockSpec((bt, delta, delta), lambda i: (i, 0, 0))
    rect = delta * dh
    return pl.pallas_call(
        functools.partial(_rinv_kernel, delta=delta),
        grid=(n // bt,),
        in_specs=[z_spec, z_spec, _mat_spec((delta, delta)),
                  _mat_spec((delta, delta)), _mat_spec((delta, dh)),
                  _mat_spec((delta, dh)), _mat_spec((1, rect)),
                  _mat_spec((1, rect))],
        out_specs=y_spec,
        out_shape=jax.ShapeDtypeStruct((n, delta, delta), dtype),
        interpret=interpret,
    )


def tile_irfft_epilogue_call(n: int, delta: int, P: int, dtype, *, bt: int,
                             activation: str = "none",
                             interpret: bool = False):
    """Compact-layout inverse tile DFT with the fused bias+activation tail:
    2x (n, P) planes + 2x (P, delta*delta) folded inverse + (n, 1) bias
    -> (n, delta*delta) real, one flattened tile per row."""
    assert n % bt == 0
    if activation not in _TAIL_ACTIVATIONS:
        raise ValueError(f"unsupported kernel-tail activation "
                         f"{activation!r}: {tuple(_TAIL_ACTIVATIONS)}")
    dd = delta * delta
    z_spec = pl.BlockSpec((bt, P), lambda i: (i, 0))
    y_spec = pl.BlockSpec((bt, dd), lambda i: (i, 0))
    b_spec = pl.BlockSpec((bt, 1), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_rinv_epilogue_kernel, activation=activation),
        grid=(n // bt,),
        in_specs=[z_spec, z_spec, _mat_spec((P, dd)), _mat_spec((P, dd)),
                  b_spec],
        out_specs=y_spec,
        out_shape=jax.ShapeDtypeStruct((n, dd), dtype),
        interpret=interpret,
    )


def tile_ifft_epilogue_call(n: int, delta: int, dtype, *, bt: int,
                            activation: str = "none",
                            interpret: bool = False):
    """Inverse tile DFT with a fused bias+activation tail.

    Inputs: 2x (n, delta, dh) complex planes + (n, 1) per-tile bias;
    output (n, delta, delta) real, already bias-shifted and activated.
    """
    assert n % bt == 0
    if activation not in _TAIL_ACTIVATIONS:
        raise ValueError(f"unsupported kernel-tail activation "
                         f"{activation!r}: {tuple(_TAIL_ACTIVATIONS)}")
    dh = delta // 2 + 1
    z_spec = pl.BlockSpec((bt, delta, dh), lambda i: (i, 0, 0))
    y_spec = pl.BlockSpec((bt, delta, delta), lambda i: (i, 0, 0))
    b_spec = pl.BlockSpec((bt, 1), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_inv_epilogue_kernel, activation=activation),
        grid=(n // bt,),
        in_specs=[z_spec, z_spec, _mat_spec((delta, delta)),
                  _mat_spec((delta, delta)), _mat_spec((delta, dh)),
                  _mat_spec((delta, dh)), b_spec],
        out_specs=y_spec,
        out_shape=jax.ShapeDtypeStruct((n, delta, delta), dtype),
        interpret=interpret,
    )
