"""jit'd public wrapper for the Pallas batched complex GEMM.

Pads (M, N, C) up to block multiples, invokes the kernel, slices back.
On the CPU backend the kernel body runs in interpret mode (Python emulation)
— TPU is the target, CPU validates correctness.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_default
from repro.kernels.cgemm.kernel import cgemm_pallas_call


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads)


# Candidate block edges: power-of-two steps up to the 128-wide MXU/lane
# width.  Small dims round UP to the next edge (operands are zero-padded to
# block multiples) instead of taking the raw dim — a C=3 layer (VGG
# conv1.1) gets an 8-wide block, not a degenerate 3-wide one.
_BLOCK_EDGES = (8, 16, 32, 64, 128)


def _round_block(dim):
    for edge in _BLOCK_EDGES:
        if edge >= dim:
            return edge
    return _BLOCK_EDGES[-1]


def _default_blocks(M, N, C):
    # MXU-aligned when the problem allows; lane-friendly for small operands.
    return _round_block(M), _round_block(N), _round_block(C)


def default_blocks(M, N, C):
    """Heuristic (bm, bn, bk) for a (P, M, C) x (P, C, N) CGEMM — the
    blocks used when no explicit override is given (autotune candidate
    generation seeds its block search from this)."""
    return _default_blocks(M, N, C)


# Mosaic's block rule: the last two dims of every block are a multiple of
# (8, 128) or span the whole (padded) array dim.  bm is a sublane axis
# (of D and Z); bn and bk are lane axes (of G and Z, and of D).
_SUBLANE, _LANE = 8, 128


def _shrink_block(dim, block, align):
    """Shrink a heuristic default block to fit ``dim``, keeping the
    grid-step count the full-size block would need.  One step covers the
    whole dim (8-aligned: a 100-wide dim under a 128 default becomes 104
    instead of zero-padding 28 ghost columns); several steps stay
    ``align``-aligned, so odd half-spectrum slabs (e.g. P_real=130 rows of
    M) stop re-padding at every stage that touches them."""
    steps = max(1, -(-dim // block))
    fitted = -(-dim // steps)             # ceil: balanced across steps
    step_align = _SUBLANE if steps == 1 else align
    return min(block, -(-fitted // step_align) * step_align)


def _legal_block(dim, block, align):
    """Round ``block`` up to an edge the TPU accepts: one block spanning
    the padded ``dim``, or a multiple of ``align``."""
    if block >= dim:
        return block
    return -(-block // align) * align


def resolve_blocks(M, N, C, bm=None, bn=None, bk=None, slabs: int = 1):
    """Merge explicit block overrides over the heuristic defaults.

    ``None`` means "use the default", shrunk to fit the dim (see
    ``_shrink_block`` — padding is applied once, not per stage); explicit
    values must be positive ints.  Every result is legal on a TPU: an
    explicit edge that is neither a lane/sublane multiple nor covering its
    dim is rounded up to the next multiple (operands are zero-padded up
    to block multiples; the autotuner decides what's *fast*).

    ``slabs > 1`` resolves for comm/compute-overlapped execution where the
    M axis is subdivided into that many batch sub-slabs: the default bm is
    shrunk against the *smallest* sub-slab's rows, so ONE block config
    (clamped once at plan time) covers every slab — per-slab re-resolution
    would pick a bigger block for the larger slabs and re-pad the smaller
    ones on every call.
    """
    if isinstance(slabs, bool) or not isinstance(slabs, int) or slabs < 1:
        raise ValueError(f"slabs must be a positive int, got {slabs!r}")
    m_fit = max(1, M // slabs)            # smallest sub-slab's row count
    resolved = []
    for name, v, dim, d, align in zip(
            ("bm", "bn", "bk"), (bm, bn, bk), (m_fit, N, C),
            _default_blocks(m_fit, N, C), (_SUBLANE, _LANE, _LANE)):
        if v is None:
            v = _shrink_block(dim, d, align)
        if isinstance(v, bool) or not isinstance(v, int) or v <= 0:
            raise ValueError(
                f"cgemm block override {name} must be a positive int or "
                f"None, got {v!r}")
        resolved.append(_legal_block(dim, v, align))
    return tuple(resolved)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "three_m",
                                             "interpret"))
def cgemm_pallas(Dr, Di, Gr, Gi, *, bm=None, bn=None, bk=None,
                 three_m: bool = True, interpret: bool | None = None):
    """Batched complex GEMM: (P,M,C) x (P,C,N) -> (P,M,N) (real, imag)."""
    if interpret is None:
        interpret = interpret_default()
    P, M, C = Dr.shape
    N = Gr.shape[-1]
    bm, bn, bk = resolve_blocks(M, N, C, bm, bn, bk)
    Drp = _pad_to(_pad_to(Dr, 1, bm), 2, bk)
    Dip = _pad_to(_pad_to(Di, 1, bm), 2, bk)
    Grp = _pad_to(_pad_to(Gr, 1, bk), 2, bn)
    Gip = _pad_to(_pad_to(Gi, 1, bk), 2, bn)
    call = cgemm_pallas_call(P, Drp.shape[1], Grp.shape[2], Drp.shape[2],
                             Dr.dtype, bm=bm, bn=bn, bk=bk,
                             three_m=three_m, interpret=interpret)
    Zr, Zi = call(Drp, Dip, Grp, Gip)
    return Zr[:, :M, :N], Zi[:, :M, :N]
