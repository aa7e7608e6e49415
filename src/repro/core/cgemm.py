"""Batched complex matrix multiplication (stage 3, the hot stage).

Z[p] = D[p] @ G[p] for every frequency point p, with complex operands kept
as separate real/imag planes (struct-of-arrays).

Two arithmetic schedules:
  * 4M: Zr = DrGr - DiGi ; Zi = DrGi + DiGr          (4 real matmuls)
  * 3M (Karatsuba): T1 = DrGr ; T2 = DiGi ; T3 = (Dr+Di)(Gr+Gi)
       Zr = T1 - T2 ; Zi = T3 - T1 - T2              (3 real matmuls, -25% MXU FLOPs)

Shapes: D (P, M, C), G (P, C, N) -> Z (P, M, N).  Products run at the
engine's matmul precision (``repro.core.dft.PRECISION``).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.dft import PRECISION


def _mm(a, b, precision, acc):
    return jnp.einsum("pmc,pcn->pmn", a, b, precision=precision,
                      preferred_element_type=acc)


def cgemm_4m(Dr, Di, Gr, Gi, *, precision=PRECISION, acc=jnp.float32):
    Zr = _mm(Dr, Gr, precision, acc) - _mm(Di, Gi, precision, acc)
    Zi = _mm(Dr, Gi, precision, acc) + _mm(Di, Gr, precision, acc)
    return Zr, Zi


def cgemm_3m(Dr, Di, Gr, Gi, *, precision=PRECISION, acc=jnp.float32):
    T1 = _mm(Dr, Gr, precision, acc)
    T2 = _mm(Di, Gi, precision, acc)
    T3 = _mm(Dr + Di, Gr + Gi, precision, acc)
    return T1 - T2, T3 - T1 - T2


def cgemm(Dr, Di, Gr, Gi, *, three_m: bool = True, precision=PRECISION,
          acc=jnp.float32):
    f = cgemm_3m if three_m else cgemm_4m
    return f(Dr, Di, Gr, Gi, precision=precision, acc=acc)
