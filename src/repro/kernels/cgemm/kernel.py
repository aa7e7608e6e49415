"""Pallas TPU kernel: P-batched complex GEMM (the paper's hot stage).

Z[p] = D[p] @ G[p], complex held as separate real/imag planes.

Grid: (P, M/bm, N/bn, C/bk); the contraction dimension kk is innermost so the
output block stays resident in VMEM across the K loop (accumulator pattern).
This is the TPU analogue of the paper's three-level parallelisation:

  node-level   -> grid dim p (frequency points; sharded over the mesh by
                  repro.parallel.nfft so each chip sees a contiguous P/N slab)
  core-level   -> grid dims (i, j) tiling M x N per chip
  vector-level -> the MXU contraction itself (128x128 systolic)

Block sizes default to MXU-aligned (128) and are clamped/padded by ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import dot_precision


def _cgemm_kernel(dr_ref, di_ref, gr_ref, gi_ref, zr_ref, zi_ref,
                  *, three_m: bool):
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        zr_ref[...] = jnp.zeros_like(zr_ref)
        zi_ref[...] = jnp.zeros_like(zi_ref)

    dr = dr_ref[0]          # (bm, bk)
    di = di_ref[0]
    gr = gr_ref[0]          # (bk, bn)
    gi = gi_ref[0]
    dot = functools.partial(jnp.dot, precision=dot_precision(dr.dtype),
                            preferred_element_type=jnp.float32)
    if three_m:
        t1 = dot(dr, gr)
        t2 = dot(di, gi)
        t3 = dot(dr + di, gr + gi)
        zr, zi = t1 - t2, t3 - t1 - t2
    else:
        zr = dot(dr, gr) - dot(di, gi)
        zi = dot(dr, gi) + dot(di, gr)
    zr_ref[0] += zr.astype(zr_ref.dtype)
    zi_ref[0] += zi.astype(zi_ref.dtype)


def cgemm_pallas_call(P: int, M: int, N: int, C: int, dtype,
                      *, bm: int, bn: int, bk: int,
                      three_m: bool = True, interpret: bool = False):
    """Build the pallas_call for pre-padded operands (bm|M, bn|N, bk|C)."""
    assert M % bm == 0 and N % bn == 0 and C % bk == 0
    grid = (P, M // bm, N // bn, C // bk)
    d_spec = pl.BlockSpec((1, bm, bk), lambda p, i, j, k: (p, i, k))
    g_spec = pl.BlockSpec((1, bk, bn), lambda p, i, j, k: (p, k, j))
    z_spec = pl.BlockSpec((1, bm, bn), lambda p, i, j, k: (p, i, j))
    out_shape = [jax.ShapeDtypeStruct((P, M, N), dtype)] * 2
    kernel = functools.partial(_cgemm_kernel, three_m=three_m)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[d_spec, d_spec, g_spec, g_spec],
        out_specs=[z_spec, z_spec],
        out_shape=out_shape,
        interpret=interpret,
    )
