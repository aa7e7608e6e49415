"""Plan-level reverse-mode autodiff for the stage-graph conv engine.

Differentiability is a property of the *plan*, not of one backend's
implementation: every backend that executes through a stage pipeline gets
the same custom VJP, defined once here over the whole pipeline —

  dx : a *transposed* plan (same backend, schedule, mesh and precision as
       the forward) applied to the conv-output cotangent and the spatially
       flipped, channel-transposed kernel, "full"-correlation padding,
       cropped by the forward padding;
  dk : direct correlation of x with the conv-output cotangent, batch as
       the contraction axis (dy's spatial extent exceeds the FFT tile, so
       the direct path is the right algorithm — one oracle call).

Fused-epilogue plans train through the same machinery: the forward (under
differentiation) computes the *pre-activation* value ``z`` via a plan
whose epilogue keeps bias/residual fused but drops the activation, the
activation is applied outside, and the backward pass first pulls ``dy``
back through the activation at ``z`` —

  dz       = dy * act'(z)        (the conv-output cotangent)
  d_bias   = sum dz over (B, H, W)
  d_residual = dz
  dx, dk   = the unfused rules above, driven by dz.

Because the backward pass is expressed as plans, it runs through the same
schedules as the forward: the gradient of an ``nfft`` conv is itself an
``nfft`` conv (collectives and all), which is what makes training *through*
the NUMA-aware schedule possible.  The Pallas backend is shielded by the
VJP (its kernels are never differentiated through), so ``fft-pallas``
trains too — including the fused ``dft_tile`` epilogue tail.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.conv.epilogue import ACTIVATIONS, activation_vjp, bias_grad
from repro.core.dft import PRECISION


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def pipeline_conv(plan, x, k, bias=None, residual=None):
    """Differentiable execution of a stage-pipeline plan (epilogue fused)."""
    return _pipeline(plan).full(plan, x, k, bias=bias, residual=residual)


def _pipeline(plan):
    from repro.conv import registry
    return registry.get_backend(plan.backend).make_pipeline(plan)


def _pre_activation_plan(plan):
    """The same plan with the activation dropped from its epilogue (bias
    and residual stay fused): its output is the pre-activation ``z`` the
    backward pass needs."""
    return dataclasses.replace(
        plan, epilogue=dataclasses.replace(plan.epilogue, activation="none"))


def _transposed_plan(plan):
    """The plan computing dx: conv of dy (B, C', Ho, Wo) with the flipped,
    transposed kernel (C, C', kh, kw) at full-correlation padding, on the
    same backend x schedule (and mesh/precision knobs) as the forward.
    No epilogue — cotangents propagate through the raw conv."""
    from repro.conv.plan import plan_conv
    s = plan.spec
    return plan_conv(
        (s.B, s.Cout, s.Ho, s.Wo), (s.C, s.Cout, s.kh, s.kw),
        padding=(s.kh - 1, s.kw - 1), delta=s.delta, backend=plan.backend,
        schedule=plan.schedule, mesh=plan.mesh, three_m=plan.three_m,
        bm=plan.bm, bn=plan.bn, bk=plan.bk,
        compute_dtype=plan.compute_dtype, data_axis=plan.data_axis,
        model_axis=plan.model_axis,
        replicate_kernel_transform=plan.replicate_kernel_transform,
        overlap=plan.overlap)


def _dx_via_transposed_plan(plan, k, dz):
    """dx: transposed plan on the flipped/channel-transposed kernel; the
    recursive pipeline_conv call keeps higher-order grads working."""
    s, pad = plan.spec, plan.padding
    kt = jnp.flip(k, axis=(-2, -1)).transpose(1, 0, 2, 3)  # (C, C', kh, kw)
    dx_full = pipeline_conv(_transposed_plan(plan), dz, kt, None, None)
    return jax.lax.dynamic_slice(
        dx_full, (0, 0, pad[0], pad[1]), (s.B, s.C, s.H, s.W))


def _dk_direct(plan, x, dz, k_dtype):
    """dk: correlation of x with dz, batch as the contraction axis. The
    "kernel" (dz) spatial extent exceeds the tile, so use the direct path."""
    pad = plan.padding
    xp = jnp.pad(x, ((0, 0), (0, 0), (pad[0], pad[0]), (pad[1], pad[1])))
    return jax.lax.conv_general_dilated(
        xp.transpose(1, 0, 2, 3),                  # (C, B, Hp, Wp)
        dz.transpose(1, 0, 2, 3),                  # (C', B, Ho, Wo)
        window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=PRECISION,
    ).transpose(1, 0, 2, 3).astype(k_dtype)        # (C', C, kh, kw)


def _fwd(plan, x, k, bias, residual):
    ep = plan.epilogue
    if ep.activation == "none":
        # no activation: the fused output IS the pre-activation value
        return pipeline_conv(plan, x, k, bias, residual), \
            (x, k, bias, residual, None)
    z = pipeline_conv(_pre_activation_plan(plan), x, k, bias, residual)
    return ACTIVATIONS[ep.activation](z), (x, k, bias, residual, z)


def _bwd(plan, res, dy):
    x, k, bias, residual, z = res
    ep = plan.epilogue
    # activation grad first: the conv-output cotangent dz drives everything
    dz = dy if z is None else activation_vjp(ep, z, dy)
    dx = _dx_via_transposed_plan(plan, k, dz)
    dk = _dk_direct(plan, x, dz, k.dtype)
    dbias = bias_grad(dz).astype(bias.dtype) if ep.bias else None
    dres = dz.astype(residual.dtype) if ep.residual else None
    return dx.astype(x.dtype), dk, dbias, dres


pipeline_conv.defvjp(_fwd, _bwd)


# --------------------------------------------------------------------------
# Prepared execution: differentiable w.r.t. x (and the epilogue operands)
# on every pipeline backend
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def prepared_conv(plan, state, kernel, x, bias=None, residual=None):
    """Execute a prepared plan (``state`` is its transformed kernel) with
    grads w.r.t. ``x`` (and bias / residual, when the epilogue carries
    them) defined by the same transposed-plan VJP as ``pipeline_conv`` —
    which also shields the Pallas kernels from being differentiated
    through, so prepared ``fft-pallas`` trains its inputs too.  The conv
    kernel is frozen in a prepared plan: ``state`` and ``kernel`` get no
    cotangent.  Both are primal arguments, not static ones, so a jitted
    caller can pass them in as traced inputs."""
    return _pipeline(plan).execute(plan, x, state, bias=bias,
                                   residual=residual)


def _prep_fwd(plan, state, kernel, x, bias, residual):
    ep = plan.epilogue
    if ep.activation == "none":
        return prepared_conv(plan, state, kernel, x, bias, residual), \
            (kernel, bias, residual, None)
    z = prepared_conv(_pre_activation_plan(plan), state, kernel, x, bias,
                      residual)
    return ACTIVATIONS[ep.activation](z), (kernel, bias, residual, z)


def _prep_bwd(plan, res, dy):
    kernel, bias, residual, z = res
    ep = plan.epilogue
    dz = dy if z is None else activation_vjp(ep, z, dy)
    dx = _dx_via_transposed_plan(plan, kernel, dz)
    dbias = bias_grad(dz).astype(bias.dtype) if ep.bias else None
    dres = dz.astype(residual.dtype) if ep.residual else None
    # execution returns x.dtype, so dy carries the input dtype
    return None, None, dx.astype(dy.dtype), dbias, dres


prepared_conv.defvjp(_prep_fwd, _prep_bwd)
