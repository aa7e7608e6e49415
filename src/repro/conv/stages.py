"""Composable stage graph for the FFT-convolution engine.

The paper's pipeline is four stage *ops* —

  1. input transform    I (B,C,H,W)    -> D (P, M, C)
  2. kernel transform   K (C',C,kh,kw) -> G (P, C, C')
  3. CGEMM              Z[p] = D[p] @ G[p]            (hot stage)
  4. output inverse     Z (P, M, C')   -> O (B,C',Ho,Wo)

— and a *schedule* is a composition of those ops with data movement in
between: ``local`` runs them back-to-back on one device, ``nfft`` places an
``all_to_all`` at each stage boundary (the paper's NUMA-aware tuple
partitioning), ``wfft`` leaves the contraction axis sharded and pays a
``psum`` inside stage 3.  This module defines the stage ops once (thin,
counted wrappers over ``repro.core.fftconv``) plus one pipeline class per
schedule.

Every pipeline accepts a plan-frozen ``Epilogue`` (bias add, activation,
residual add — see ``repro.conv.epilogue``) executed *inside* stage 4 on
the local output slab: zero extra collectives (the operands enter
``shard_map`` pre-sharded), zero extra stage-op invocations (the
elementwise tail rides the existing ``output_inverse`` op), and the work
happens before the f32 -> x.dtype cast.

Every pipeline exposes the prepare/execute split:

  ``prepare(plan, k)``   run stage 2 once, returning the transformed kernel
                         ``G`` in the exact layout execution consumes — for
                         the sharded schedules that is the *post-boundary*
                         layout, so prepared execution runs stage 2 AND
                         boundary all-to-all #2 zero times;
  ``execute(plan, x, G)``run stages 1/3/4 (+ remaining collectives) against
                         a prepared ``G``;
  ``full(plan, x, k)``   the one-shot path: stage 2 inline.

The frequency axis is the compact Hermitian half-spectrum of
``spec.freq_points()`` points (see ``repro.core.fftconv``) through the
whole graph: stage 1's output, G, Z, the nfft boundary all-to-alls and
the wfft hot psum pair all carry it.

Each forward stage op (1, 3 and 4) also opens a ``jax.named_scope`` of
its counter's name — ``input_transform``, ``cgemm``, ``output_inverse``
— so its device ops carry ``<caller scope>/<stage>/…`` in the compiled
HLO's ``op_name`` metadata, where a profiler trace can attribute them.
Stage 2 runs only in ``prepare`` and the collectives sit between stages:
neither is scoped.

Stage-op invocations are counted at trace time via the thread-safe
context manager::

    with stage_trace() as counts:
        jax.make_jaxpr(plan)(x, k)
    assert counts["cgemm"] == 1

Traces also record dtype facts as ``("cgemm_dtype", <dtype>)`` tuple keys
alongside the plain string op counts — the static analyzer reads these to
certify that ``compute_dtype`` actually reached the hot stage — and, from
stage 1 or a ``direct`` layer whose spec has ``stride > 1``,
``("stride", s)``.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import threading

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import shard_map
from repro.core.conv_spec import ConvSpec
from repro.core import fftconv as F
from repro.core.cgemm import cgemm
from repro.conv.epilogue import Epilogue, apply_epilogue


# --------------------------------------------------------------------------
# Stage-op trace counters (thread-safe, context-managed)
# --------------------------------------------------------------------------

_tls = threading.local()                 # per-thread stack of active traces


def _count(name: str) -> None:
    for counter in getattr(_tls, "stack", ()):
        counter[name] += 1


@contextlib.contextmanager
def stage_trace():
    """Scoped, thread-local stage-op counter.

    Counts only the stage ops traced by *this* thread while the context is
    active, so concurrent planners/tracers don't bleed into each other.
    Nested traces each observe the ops traced inside them.
    """
    counts: collections.Counter = collections.Counter()
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    stack.append(counts)
    try:
        yield counts
    finally:
        # remove by IDENTITY: ``with`` exits are LIFO, and equality-based
        # removal would pop the wrong Counter when two traces hold equal
        # contents (e.g. both still empty)
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] is counts:
                del stack[i]
                break


# --------------------------------------------------------------------------
# Stage ops (counted)
# --------------------------------------------------------------------------

@contextlib.contextmanager
def _stage(name: str):
    """Count a stage op and name its device ops ``<caller scope>/<name>/…``
    in the compiled HLO's ``op_name`` metadata (trace-time only: the
    compiled program is otherwise the same)."""
    _count(name)
    with jax.named_scope(name):
        yield


def count_stride(stride: int) -> None:
    """Record a strided layer for the analyzer; unit stride records
    nothing."""
    if stride != 1:
        _count(("stride", stride))


def stage_input_transform(x, spec: ConvSpec):
    count_stride(spec.stride)
    with _stage("input_transform"):
        return F.input_transform(x, spec)


def stage_kernel_transform(k, spec: ConvSpec):
    _count("kernel_transform")
    return F.kernel_transform(k, spec)


def stage_cgemm(Dr, Di, Gr, Gi, *, three_m: bool, cgemm_fn=None):
    # dtype-flow fact for the analyzer: which dtype the hot stage actually
    # consumed (tuple keys ride the same counters as the op counts)
    _count(("cgemm_dtype", str(jnp.result_type(Dr, Gr))))
    # shape fact: (M, N, K) of this invocation — the analyzer certifies
    # that every sub-slab of an overlapped plan resolves the SAME Pallas
    # block config (no per-slab re-padding)
    _count(("cgemm_shape",
            (int(Dr.shape[-2]), int(Gr.shape[-1]), int(Dr.shape[-1]))))
    mm = cgemm_fn if cgemm_fn is not None else functools.partial(
        cgemm, three_m=three_m)
    with _stage("cgemm"):
        return mm(Dr, Di, Gr, Gi)


def stage_output_inverse(Zr, Zi, spec: ConvSpec, *, epilogue: Epilogue = None,
                         bias=None, residual=None, inverse_fn=None):
    """Stage 4 with the fused elementwise epilogue.

    The epilogue rides inside this single stage op (the counter increments
    once, fused or not).  ``inverse_fn`` is a backend-supplied fused
    inverse+epilogue kernel ``(Zr, Zi, spec, epilogue, bias) -> y`` (the
    Pallas ``dft_tile`` tail); it cannot fold a residual — the residual
    lives in output layout, not tile layout — so residual epilogues fall
    back to the composed path.
    """
    with _stage("output_inverse"):
        if (inverse_fn is not None and epilogue is not None
                and not epilogue.is_noop and not epilogue.residual):
            return inverse_fn(Zr, Zi, spec, epilogue, bias)
        y = F.output_inverse(Zr, Zi, spec)
        return apply_epilogue(y, epilogue, bias=bias, residual=residual)


def _boundary_a2a(Tr, Ti, axis_name, split, concat):
    """One nfft stage-boundary all-to-all (re/im pair, counted once)."""
    _count("boundary_a2a")
    Tr = jax.lax.all_to_all(Tr, axis_name, split, concat, tiled=True)
    Ti = jax.lax.all_to_all(Ti, axis_name, split, concat, tiled=True)
    return Tr, Ti


def _slab_a2a(Tr, Ti, axis_name, split, concat):
    """The boundary all-to-all as issued by the overlapped (sub-slab)
    path.  Functionally identical to ``_boundary_a2a`` — a separate
    module-level indirection so the ``overlap-oversend`` seeded violation
    can inflate per-slab collective bytes without touching the sequential
    twin the analyzer compares against."""
    return _boundary_a2a(Tr, Ti, axis_name, split, concat)


def _slab_psum(Zr, Zi, axis_name):
    """The wfft hot-stage all-reduce pair as issued by the overlapped
    (sub-slab) path; see ``_slab_a2a`` for why this is patchable."""
    return jax.lax.psum(Zr, axis_name), jax.lax.psum(Zi, axis_name)


# --------------------------------------------------------------------------
# Shared helpers
# --------------------------------------------------------------------------

def _pad_axis(x, axis, mult):
    rem = (-x.shape[axis]) % mult
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads)


def _local_spec(spec: ConvSpec, b_loc: int, c_loc: int, co_loc: int):
    return ConvSpec(B=b_loc, C=c_loc, Cout=co_loc, H=spec.H, W=spec.W,
                    kh=spec.kh, kw=spec.kw, pad_h=spec.pad_h,
                    pad_w=spec.pad_w, delta=spec.delta, stride=spec.stride)


def padded_sharded_spec(plan) -> ConvSpec:
    """The ConvSpec of the mesh-padded problem the sharded bodies see.

    Channel/batch axes are zero-padded up to mesh-axis multiples (e.g. VGG
    conv1.1's C=3); padded channels multiply zeros and are sliced away.
    """
    s = plan.spec
    n_data = plan.mesh.shape[plan.data_axis]
    n_model = plan.mesh.shape[plan.model_axis]
    return ConvSpec(
        B=s.B + (-s.B) % n_data, C=s.C + (-s.C) % n_model,
        Cout=s.Cout + (-s.Cout) % n_model, H=s.H, W=s.W, kh=s.kh, kw=s.kw,
        pad_h=s.pad_h, pad_w=s.pad_w, delta=s.delta, stride=s.stride)


def _place(pair, plan, pspec):
    """Lay a prepared (re, im) pair out on the plan's mesh as ``pspec``,
    so prepared execution starts from distributed slabs instead of
    re-sharding one device's copy on every call."""
    sharding = NamedSharding(plan.mesh, pspec)
    return tuple(jax.device_put(t, sharding) for t in pair)


def _maybe_cast(pair, dtype):
    if dtype is None:
        return pair
    return pair[0].astype(dtype), pair[1].astype(dtype)


def _slab_sizes(n: int, k: int) -> tuple:
    """Static batch sub-slab sizes for overlapped execution: ``k`` slabs
    covering ``n`` rows, the remainder spread over the leading slabs so
    sizes differ by at most one (k is clamped to n — never an empty
    slab)."""
    k = max(1, min(int(k), int(n)))
    base, rem = divmod(int(n), k)
    return tuple(base + (1 if i < rem else 0) for i in range(k))


def _slab_splits(x, sizes, axis=0):
    """Slice ``x`` into static sub-slabs of the given sizes along
    ``axis``."""
    out, start = [], 0
    for n in sizes:
        out.append(jax.lax.slice_in_dim(x, start, start + n, axis=axis))
        start += n
    return out


def _epilogue_operands(plan, bias, residual):
    """Pad + spec the epilogue operands for shard_map entry.

    Bias is C'-sharded over the model axis and the residual is sharded
    exactly like the output, so the epilogue costs ZERO collectives: every
    rank receives precisely the slab its local stage-4 output needs.
    """
    ep = plan.epilogue
    n_data = plan.mesh.shape[plan.data_axis]
    n_model = plan.mesh.shape[plan.model_axis]
    args, specs = [], []
    if ep.bias:
        args.append(_pad_axis(bias, 0, n_model))
        specs.append(P(plan.model_axis))
    if ep.residual:
        args.append(_pad_axis(_pad_axis(residual, 0, n_data), 1, n_model))
        specs.append(P(plan.data_axis, plan.model_axis, None, None))
    return tuple(args), tuple(specs)


def _unpack_epilogue_args(plan, ep_args):
    ep = plan.epilogue
    it = iter(ep_args)
    bias = next(it) if ep.bias else None
    residual = next(it) if ep.residual else None
    return bias, residual


# --------------------------------------------------------------------------
# local schedule
# --------------------------------------------------------------------------

class LocalPipeline:
    """Single device: stages back-to-back, no collectives.  The epilogue is
    fused into stage 4; ``inverse_fn`` (Pallas backend) fuses it into the
    tile-inverse kernel tail itself."""

    def __init__(self, cgemm_fn=None, inverse_fn=None):
        self.cgemm_fn = cgemm_fn
        self.inverse_fn = inverse_fn

    def prepare(self, plan, k):
        return stage_kernel_transform(k, plan.spec)

    def execute(self, plan, x, G, bias=None, residual=None):
        spec = plan.spec
        Dr, Di = stage_input_transform(x, spec)
        Gr, Gi = G
        Dr, Di = _maybe_cast((Dr, Di), plan.compute_dtype)
        Gr, Gi = _maybe_cast((Gr, Gi), plan.compute_dtype)
        Zr, Zi = stage_cgemm(Dr, Di, Gr, Gi, three_m=plan.three_m,
                             cgemm_fn=self.cgemm_fn)
        Zr, Zi = Zr.astype(jnp.float32), Zi.astype(jnp.float32)
        y = stage_output_inverse(Zr, Zi, spec, epilogue=plan.epilogue,
                                 bias=bias, residual=residual,
                                 inverse_fn=self.inverse_fn)
        return y.astype(x.dtype)

    def full(self, plan, x, k, bias=None, residual=None):
        return self.execute(plan, x, self.prepare(plan, k), bias=bias,
                            residual=residual)


# --------------------------------------------------------------------------
# nfft schedule (the paper's NUMA-aware tuple partitioning)
# --------------------------------------------------------------------------

class NfftPipeline:
    """Transforms where the data lives; one all-to-all per stage boundary;
    collective-free hot CGEMM.  Prepared form: ``G`` in the post-boundary
    layout — global (P, C, C') with the P axis sharded over ``model`` — so
    prepared execution skips stage 2 and boundary a2a #2 entirely.  The
    epilogue runs inside the body on each rank's C'/N stage-4 slab."""

    def __init__(self, cgemm_fn=None, inverse_fn=None):
        self.cgemm_fn = cgemm_fn
        # inverse_fn is a local-schedule fusion (tile-kernel tail); the
        # sharded bodies fuse the epilogue at the stage level instead.

    # ---- bodies (per-device, under shard_map) -----------------------------

    def _body_full(self, x, k, *ep_args, plan, spec, n_model):
        """x: (B_loc, C_loc, H, W); k: C'-sharded (or replicated)."""
        Gr, Gi = self._stage2(k, plan, spec, n_model)
        return self._slabbed(x, Gr, Gi, ep_args, plan, spec, n_model)

    def _body_prepared(self, x, Gr, Gi, *ep_args, plan, spec, n_model):
        """x: (B_loc, C_loc, H, W); Gr/Gi: the local (P/N, C, C') slab."""
        return self._slabbed(x, Gr, Gi, ep_args, plan, spec, n_model)

    def _slabbed(self, x, Gr, Gi, ep_args, plan, spec, n_model):
        """Stages 1/3/4 against a boundary-layout G, in ``plan.num_slabs``
        batch sub-slabs.

        With ``overlap="off"`` (one slab) this is the sequential path.
        With ``overlap="slab:k"`` the batch is split into k static
        sub-slabs, double-buffered: the stage-1 transform AND boundary
        all-to-all #1 of slab i+1 are issued *before* the hot cgemm +
        boundary a2a #3 + stage-4 tail of slab i, so the async collective
        of one slab overlaps the compute of another under XLA's
        latency-hiding scheduler (``repro.launch.env`` sets the flags).
        The kernel-side work (stage 2 / boundary a2a #2) is shared by all
        slabs and never slabbed; total collective bytes are unchanged vs
        the sequential twin (each per-slab a2a moves 1/k of the rows).
        """
        bias, residual = _unpack_epilogue_args(plan, ep_args)
        sizes = _slab_sizes(x.shape[0], getattr(plan, "num_slabs", 1))
        if len(sizes) == 1:
            Dr, Di = self._stage1_and_boundary1(x, plan, spec)
            return self._hot_and_tail(x, Dr, Di, Gr, Gi, bias, residual,
                                      plan, spec, n_model)
        xs = _slab_splits(x, sizes)
        rs = _slab_splits(residual, sizes) if residual is not None \
            else [None] * len(xs)
        staged = self._stage1_and_boundary1(xs[0], plan, spec, slab=True)
        outs = []
        for i, xi in enumerate(xs):
            nxt = None
            if i + 1 < len(xs):
                # issue slab i+1's transform + boundary a2a before slab
                # i's hot stage consumes its own staged operands
                nxt = self._stage1_and_boundary1(xs[i + 1], plan, spec,
                                                 slab=True)
            outs.append(self._hot_and_tail(xi, *staged, Gr, Gi, bias,
                                           rs[i], plan, spec, n_model,
                                           slab=True))
            staged = nxt
        return jnp.concatenate(outs, axis=0)

    def _stage1_and_boundary1(self, x, plan, spec, slab=False):
        b_loc, c_loc = x.shape[0], x.shape[1]
        sp1 = _local_spec(spec, b_loc, c_loc, spec.Cout)
        Dr, Di = stage_input_transform(x, sp1)
        # The tiled all-to-all splits the P axis N ways: pad the frequency
        # list up to a model-axis multiple ONCE here (padded rows are zero,
        # flow inertly through the CGEMM, and stage 4 slices them off).
        n_model = plan.mesh.shape[plan.model_axis]
        Dr, Di = _pad_axis(Dr, 0, n_model), _pad_axis(Di, 0, n_model)
        if plan.compute_dtype is not None:
            # cast BEFORE the boundary a2a so the collective moves half the
            # bytes
            Dr, Di = _maybe_cast((Dr, Di), plan.compute_dtype)
        # Boundary a2a #1 (tuple partitioning): (P, M, C_loc) -> (P/N, M, C)
        a2a = _slab_a2a if slab else _boundary_a2a
        return a2a(Dr, Di, plan.model_axis, 0, 2)

    def _stage2(self, k, plan, spec, n_model):
        c_full = k.shape[1]
        sp2 = _local_spec(spec, spec.B, c_full, k.shape[0])
        if plan.replicate_kernel_transform:
            # Stage 2': full kernel transform on every rank, local P-slab
            # slice — removes boundary a2a #2 (beyond-paper optimization).
            Gr, Gi = stage_kernel_transform(k, sp2)
            Gr, Gi = _pad_axis(Gr, 0, n_model), _pad_axis(Gi, 0, n_model)
            p_loc = Gr.shape[0] // n_model
            idx = jax.lax.axis_index(plan.model_axis) * p_loc
            Gr = jax.lax.dynamic_slice_in_dim(Gr, idx, p_loc, axis=0)
            Gi = jax.lax.dynamic_slice_in_dim(Gi, idx, p_loc, axis=0)
            return Gr, Gi
        # Stage 2: transform the local C'_loc kernels -> G (P, C, C'_loc)
        Gr, Gi = stage_kernel_transform(k, sp2)
        Gr, Gi = _pad_axis(Gr, 0, n_model), _pad_axis(Gi, 0, n_model)
        # Boundary a2a #2: (P, C, C'_loc) -> (P/N, C, C')
        return _boundary_a2a(Gr, Gi, plan.model_axis, 0, 2)

    def _hot_and_tail(self, x, Dr, Di, Gr, Gi, bias, residual, plan, spec,
                      n_model, slab=False):
        b_loc, c_full = x.shape[0], spec.C
        # Stage 3 (HOT): local P/N-slab complex GEMM — no collectives.
        Gr, Gi = _maybe_cast((Gr, Gi), plan.compute_dtype)
        Zr, Zi = stage_cgemm(Dr, Di, Gr, Gi, three_m=plan.three_m,
                             cgemm_fn=self.cgemm_fn)  # f32 accumulation
        if plan.compute_dtype is not None:
            Zr, Zi = _maybe_cast((Zr, Zi), plan.compute_dtype)
        # Boundary a2a #3 (gather tuples for the inverse):
        # (P/N, M_loc, C') -> (P, M_loc, C'/N)
        a2a = _slab_a2a if slab else _boundary_a2a
        Zr, Zi = a2a(Zr, Zi, plan.model_axis, 2, 0)
        Zr, Zi = Zr.astype(jnp.float32), Zi.astype(jnp.float32)
        # Stage 4: each model rank inverts its C'/N output-channel slab and
        # applies the fused epilogue on that 1/N slab (pre-sharded operands,
        # zero collectives), before the output dtype cast.
        sp4 = _local_spec(spec, b_loc, c_full, spec.Cout // n_model)
        return stage_output_inverse(Zr, Zi, sp4, epilogue=plan.epilogue,
                                    bias=bias, residual=residual)

    # ---- global entry points ----------------------------------------------

    def prepare(self, plan, k):
        """Stage 2 (+ its boundary movement), once: global (P, C, C').

        The P axis is padded up to a model-axis multiple so the prepared
        slab enters shard_map P-sharded (matching the post-boundary layout
        the a2a padding produces on the inline path), and is placed in
        that layout: each rank holds its own P-slab.
        """
        spec = padded_sharded_spec(plan)
        n_model = plan.mesh.shape[plan.model_axis]
        kp = _pad_axis(_pad_axis(k, 0, n_model), 1, n_model)
        Gr, Gi = stage_kernel_transform(kp, spec)
        return _place((_pad_axis(Gr, 0, n_model), _pad_axis(Gi, 0, n_model)),
                      plan, P(plan.model_axis, None, None))

    def execute(self, plan, x, G, bias=None, residual=None):
        spec = padded_sharded_spec(plan)
        mesh = plan.mesh
        n_model = mesh.shape[plan.model_axis]
        xp = _pad_axis(_pad_axis(x, 0, mesh.shape[plan.data_axis]), 1,
                       n_model)
        Gr, Gi = G
        ep_args, ep_specs = _epilogue_operands(plan, bias, residual)
        body = functools.partial(self._body_prepared, plan=plan, spec=spec,
                                 n_model=n_model)
        in_specs = (P(plan.data_axis, plan.model_axis, None, None),
                    P(plan.model_axis, None, None),    # G: P-slab per rank
                    P(plan.model_axis, None, None),
                    *ep_specs)
        out_spec = P(plan.data_axis, plan.model_axis, None, None)
        y = shard_map(body, mesh=mesh, in_specs=in_specs,
                      out_specs=out_spec)(xp, Gr, Gi, *ep_args)
        return y[:plan.spec.B, :plan.spec.Cout].astype(x.dtype)

    def full(self, plan, x, k, bias=None, residual=None):
        spec = padded_sharded_spec(plan)
        mesh = plan.mesh
        n_model = mesh.shape[plan.model_axis]
        xp = _pad_axis(_pad_axis(x, 0, mesh.shape[plan.data_axis]), 1,
                       n_model)
        kp = _pad_axis(_pad_axis(k, 0, n_model), 1, n_model)
        ep_args, ep_specs = _epilogue_operands(plan, bias, residual)
        body = functools.partial(self._body_full, plan=plan, spec=spec,
                                 n_model=n_model)
        k_spec = P(None, None, None, None) \
            if plan.replicate_kernel_transform \
            else P(plan.model_axis, None, None, None)   # k: C' sharded
        in_specs = (P(plan.data_axis, plan.model_axis, None, None), k_spec,
                    *ep_specs)
        out_spec = P(plan.data_axis, plan.model_axis, None, None)
        y = shard_map(body, mesh=mesh, in_specs=in_specs,
                      out_specs=out_spec)(xp, kp, *ep_args)
        return y[:plan.spec.B, :plan.spec.Cout].astype(x.dtype)


# --------------------------------------------------------------------------
# wfft schedule (Wang et al. baseline)
# --------------------------------------------------------------------------

class WfftPipeline:
    """No tuple partitioning: the CGEMM contracts a channel axis spread over
    ``model``, so a psum (all-reduce of the whole Z) sits inside the hot
    stage.  Prepared form: global (P, C, C') with the C axis sharded.  The
    epilogue is fused into each rank's C'/N stage-4 slab like nfft."""

    def __init__(self, cgemm_fn=None, inverse_fn=None):
        self.cgemm_fn = cgemm_fn

    def _body(self, x, Gr, Gi, *ep_args, plan, spec, n_model):
        """x: (B_loc, C_loc, H, W); Gr/Gi: the local (P, C_loc, C') slab.

        With ``overlap="slab:k"`` the batch is split into k static
        sub-slabs, double-buffered: the stage-1 transform + partial cgemm
        of slab i+1 are issued *before* the hot-stage psum + stage-4 tail
        of slab i, so the all-reduce of one slab overlaps the compute of
        another (each per-slab psum moves 1/k of the rows — total bytes
        unchanged vs the sequential twin).
        """
        bias, residual = _unpack_epilogue_args(plan, ep_args)
        Gr, Gi = _maybe_cast((Gr, Gi), plan.compute_dtype)
        sizes = _slab_sizes(x.shape[0], getattr(plan, "num_slabs", 1))
        if len(sizes) == 1:
            return self._psum_and_tail(
                x, *self._partial_z(x, Gr, Gi, plan, spec), bias, residual,
                plan, spec, n_model)
        xs = _slab_splits(x, sizes)
        rs = _slab_splits(residual, sizes) if residual is not None \
            else [None] * len(xs)
        staged = self._partial_z(xs[0], Gr, Gi, plan, spec)
        outs = []
        for i, xi in enumerate(xs):
            nxt = None
            if i + 1 < len(xs):
                # issue slab i+1's transform + partial cgemm before slab
                # i's hot-stage all-reduce
                nxt = self._partial_z(xs[i + 1], Gr, Gi, plan, spec)
            outs.append(self._psum_and_tail(xi, *staged, bias, rs[i],
                                            plan, spec, n_model, slab=True))
            staged = nxt
        return jnp.concatenate(outs, axis=0)

    def _partial_z(self, x, Gr, Gi, plan, spec):
        """Stage 1 + the partial (C-sharded contraction) cgemm for one
        batch slab; G enters already cast to compute_dtype."""
        sp1 = _local_spec(spec, x.shape[0], x.shape[1], spec.Cout)
        Dr, Di = stage_input_transform(x, sp1)  # (P, M, C_loc)
        Dr, Di = _maybe_cast((Dr, Di), plan.compute_dtype)
        Zr, Zi = stage_cgemm(Dr, Di, Gr, Gi, three_m=plan.three_m,
                             cgemm_fn=self.cgemm_fn)  # partial sums, f32 acc
        if plan.compute_dtype is not None:
            # cast BEFORE the hot-stage psum so the all-reduce moves half
            # the bytes (parity with the nfft boundary-a2a cast)
            Zr, Zi = _maybe_cast((Zr, Zi), plan.compute_dtype)
        return Zr, Zi

    def _psum_and_tail(self, x, Zr, Zi, bias, residual, plan, spec, n_model,
                       slab=False):
        # HOT-STAGE collective: all-reduce the full Z across the model axis.
        if slab:
            Zr, Zi = _slab_psum(Zr, Zi, plan.model_axis)
        else:
            Zr = jax.lax.psum(Zr, plan.model_axis)
            Zi = jax.lax.psum(Zi, plan.model_axis)
        Zr, Zi = Zr.astype(jnp.float32), Zi.astype(jnp.float32)

        # Each rank inverts its C'/N slice (avoids duplicate stage-4 work)
        # and applies the fused epilogue on that slab only.
        co_loc = spec.Cout // n_model
        idx = jax.lax.axis_index(plan.model_axis)
        Zr = jax.lax.dynamic_slice_in_dim(Zr, idx * co_loc, co_loc, axis=2)
        Zi = jax.lax.dynamic_slice_in_dim(Zi, idx * co_loc, co_loc, axis=2)
        sp4 = _local_spec(spec, x.shape[0], x.shape[1], co_loc)
        return stage_output_inverse(Zr, Zi, sp4, epilogue=plan.epilogue,
                                    bias=bias, residual=residual)

    def _body_full(self, x, k, *ep_args, plan, spec, n_model):
        """k: (C'_full, C_loc, kh, kw) — stage 2 inline on the local slab."""
        sp2 = _local_spec(spec, x.shape[0], k.shape[1], k.shape[0])
        Gr, Gi = stage_kernel_transform(k, sp2)
        return self._body(x, Gr, Gi, *ep_args, plan=plan, spec=spec,
                          n_model=n_model)

    def prepare(self, plan, k):
        """Stage 2, once: global (P, C, C'), placed C-sharded the way
        execution consumes it."""
        spec = padded_sharded_spec(plan)
        n_model = plan.mesh.shape[plan.model_axis]
        kp = _pad_axis(_pad_axis(k, 0, n_model), 1, n_model)
        return _place(stage_kernel_transform(kp, spec), plan,
                      P(None, plan.model_axis, None))

    def _run(self, plan, x, args, body, extra_in_specs):
        mesh = plan.mesh
        xp = _pad_axis(_pad_axis(x, 0, mesh.shape[plan.data_axis]), 1,
                       mesh.shape[plan.model_axis])
        in_specs = (P(plan.data_axis, plan.model_axis, None, None),
                    *extra_in_specs)
        out_spec = P(plan.data_axis, plan.model_axis, None, None)
        y = shard_map(body, mesh=mesh, in_specs=in_specs,
                      out_specs=out_spec)(xp, *args)
        return y[:plan.spec.B, :plan.spec.Cout].astype(x.dtype)

    def execute(self, plan, x, G, bias=None, residual=None):
        spec = padded_sharded_spec(plan)
        n_model = plan.mesh.shape[plan.model_axis]
        ep_args, ep_specs = _epilogue_operands(plan, bias, residual)
        body = functools.partial(self._body, plan=plan, spec=spec,
                                 n_model=n_model)
        g_spec = P(None, plan.model_axis, None)        # G: C sharded
        return self._run(plan, x, (*G, *ep_args), body,
                         (g_spec, g_spec, *ep_specs))

    def full(self, plan, x, k, bias=None, residual=None):
        spec = padded_sharded_spec(plan)
        n_model = plan.mesh.shape[plan.model_axis]
        kp = _pad_axis(_pad_axis(k, 0, n_model), 1, n_model)
        ep_args, ep_specs = _epilogue_operands(plan, bias, residual)
        body = functools.partial(self._body_full, plan=plan, spec=spec,
                                 n_model=n_model)
        k_spec = P(None, plan.model_axis, None, None)  # k: C sharded
        return self._run(plan, x, (kp, *ep_args), body, (k_spec, *ep_specs))


PIPELINES = {"local": LocalPipeline, "nfft": NfftPipeline,
             "wfft": WfftPipeline}


def pipeline_for(schedule: str, cgemm_fn=None, inverse_fn=None):
    return PIPELINES[schedule](cgemm_fn, inverse_fn)
