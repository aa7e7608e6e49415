"""Built-in backends/schedules for the plan-execute convolution engine.

Schedules:
  local  single device, no collectives.
  nfft   the paper's NUMA-aware tuple partitioning: transforms run where
         the data lives, one all_to_all per stage boundary, collective-free
         hot CGEMM.
  wfft   the Wang et al. baseline: channel-sharded CGEMM with an
         all-reduce inside the hot stage.

Backends:
  direct      lax.conv_general_dilated (the oracle path; auto's pick up
              to a few hundred channels by predicted time).  Opaque
              execute, native XLA autodiff; the plan epilogue is applied
              right after the conv (XLA fuses the elementwise tail).
  fft-xla     the paper's 4-stage pipeline composed from repro.conv.stages
              with the XLA einsum CGEMM.
  fft-pallas  the same stage graph with the hot CGEMM swapped for the
              Pallas TPU kernel (interpret mode on CPU); plan bm/bn/bk
              select its blocks.  On the ``local`` schedule a bias/
              activation epilogue is fused into the ``dft_tile``
              output-inverse kernel tail (the inverse never round-trips to
              HBM before the elementwise pass).

The two FFT backends differ *only* in the stage ops they inject into the
pipeline — everything else (transforms, collectives, prepare/execute, the
plan-level VJP, epilogue fusion) is shared composition, which is why both
are differentiable on every schedule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.conv import stages
from repro.conv.epilogue import apply_epilogue
from repro.conv.registry import register_backend, register_schedule
from repro.core import fftconv as F


def _pallas_cgemm_fn(plan):
    from repro.kernels.cgemm import cgemm_pallas
    return functools.partial(cgemm_pallas, three_m=plan.three_m,
                             bm=plan.bm, bn=plan.bn, bk=plan.bk)


def _pallas_fused_inverse_real(Zr, Zi, spec, epilogue, bias, *, bt=None):
    """Stage 4 through the fused ``dft_tile`` kernel: compact-spectrum
    scatter + inverse DFT + bias + activation in one VMEM-resident pass.

    The activation runs on whole tiles before the overlap-save crop; the
    crop only *selects* elements, so elementwise-before-crop equals
    crop-then-elementwise on everything kept.  ``bt`` is the plan's
    ``dft_bt`` tile-batch block override (autotuned or user-pinned).
    """
    from repro.kernels.dft_tile import tile_irfft_epilogue_pallas
    P = spec.freq_points()
    Zrt = F.z_to_flat_tiles(Zr, spec, P)    # (B, C', X, Dl, P)
    Zit = F.z_to_flat_tiles(Zi, spec, P)
    B, Co, X, Dl = Zrt.shape[:4]
    n = B * Co * X * Dl
    d = spec.delta
    b = bias if bias is not None else jnp.zeros((Co,), Zr.dtype)
    b_tile = jnp.broadcast_to(b.astype(Zr.dtype)[None, :, None, None],
                              (B, Co, X, Dl)).reshape(n)
    y = tile_irfft_epilogue_pallas(Zrt.reshape(n, P), Zit.reshape(n, P),
                                   b_tile, activation=epilogue.activation,
                                   delta=d, bt=bt)
    return F.assemble_output_tiles(y.reshape(B, Co, X, Dl, d, d), spec)


def _exec_direct(plan, x, k, bias=None, residual=None):
    # named like the fft stage ops (``repro.conv.stages._stage``), so a
    # profiler trace attributes the conv and its epilogue to ``direct``
    stages.count_stride(plan.spec.stride)
    with jax.named_scope("direct"):
        y = F.conv2d_direct(x, k, padding=plan.padding,
                            stride=plan.spec.stride,
                            compute_dtype=plan.compute_dtype)
        out_dtype = y.dtype
        return apply_epilogue(y, plan.epilogue, bias=bias,
                              residual=residual).astype(out_dtype)


def _fft_xla_pipeline(plan):
    return stages.pipeline_for(plan.schedule, cgemm_fn=None)


def _fft_pallas_pipeline(plan):
    inverse_fn = functools.partial(_pallas_fused_inverse_real,
                                   bt=plan.dft_bt) \
        if plan.schedule == "local" else None
    return stages.pipeline_for(plan.schedule,
                               cgemm_fn=_pallas_cgemm_fn(plan),
                               inverse_fn=inverse_fn)


def register_builtin() -> None:
    register_schedule("local", requires_mesh=False,
                      description="single device, no collectives")
    register_schedule("nfft", requires_mesh=True,
                      description="paper: tuple partitioning, a2a at stage "
                                  "boundaries, collective-free CGEMM")
    register_schedule("wfft", requires_mesh=True,
                      description="baseline: all-reduce inside the hot CGEMM")

    register_backend("direct", _exec_direct, schedules=("local",),
                     native_autodiff=True, supports_epilogue=True,
                     description="lax.conv_general_dilated")
    register_backend("fft-xla", pipeline_factory=_fft_xla_pipeline,
                     schedules=("local", "nfft", "wfft"),
                     description="FFT conv stage graph, XLA einsum CGEMM")
    register_backend("fft-pallas", pipeline_factory=_fft_pallas_pipeline,
                     schedules=("local", "nfft", "wfft"),
                     description="FFT conv stage graph, Pallas CGEMM kernel"
                                 " (+ fused epilogue inverse on local)")
