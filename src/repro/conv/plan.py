"""Plan/execute convolution engine (FFTW-style).

The best convolution algorithm is geometry-dependent (direct vs FFT
crossover; tile size; 3M vs 4M complex product; nFFT tuple partitioning vs
wFFT), so selection lives in a planner rather than at call sites:

    plan = plan_conv(x.shape, k.shape, padding=1)   # plan once
    y = plan(x, k)                                  # execute many times

``ConvPlan`` freezes everything the execution needs: the geometry
(``ConvSpec``), the (backend, schedule) pair, precision, and tuning
parameters (``three_m``, CGEMM block sizes, mesh axes).  Plans are
memoized in a keyed LRU cache so repeated layer shapes pay planning once.

On top of the one-shot ``plan(x, k)`` there is a prepare/execute split for
fixed kernels (inference / serving):

    prepared = plan.prepare(k, weights_version=step)   # stage 2 runs here
    y = prepared(x)                                    # stage 2 never again

``prepare`` caches the transformed kernel ``G`` in the exact layout the
schedule consumes — for ``nfft`` the post-all-to-all P-slab form, so
prepared sharded execution runs the kernel transform AND boundary
all-to-all #2 zero times.  The cache is keyed by ``weights_version``:
prepare with a new version recomputes (invalidation), with the same
version returns the cached ``PreparedConv``.

``backend="auto"`` picks direct or FFT by predicted device time
(``predicted_s``, from ``ConvSpec``'s FLOP and byte counts);
``schedule="auto"`` picks ``nfft`` when a mesh is given, else ``local``.
``backend="tuned"`` replaces the cost model with *measured* selection
(``repro.conv.autotune``): candidate (backend, schedule, block) configs are
timed on the actual device, the winner is cached persistently per machine,
and the chosen blocks ride the plan down into the Pallas kernels.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import threading
from typing import Any, Optional

import jax

from repro.core.conv_spec import ConvSpec
from repro.conv import registry
from repro.conv import autodiff
from repro.conv.epilogue import Epilogue


@dataclasses.dataclass(frozen=True)
class ConvPlan:
    """Frozen, executable schedule for one convolution geometry.

    Execute with ``plan(x, k)``; ``x`` must be ``(B, C, H, W)`` and ``k``
    ``(C', C, kh, kw)`` matching the planned shapes exactly (plan again
    for a new geometry — planning is cached, so this is cheap).
    """
    spec: ConvSpec
    backend: str                       # resolved registry name
    schedule: str                      # resolved registry name
    padding: tuple                     # (pad_h, pad_w)
    three_m: bool = True               # 3M (Karatsuba) vs 4M complex product
    bm: Optional[int] = None           # Pallas CGEMM block sizes
    bn: Optional[int] = None
    bk: Optional[int] = None
    dft_bt: Optional[int] = None       # Pallas dft_tile tile-batch block
    compute_dtype: Any = None          # CGEMM operand dtype (e.g. bf16)
    mesh: Any = None                   # jax Mesh for sharded schedules
    data_axis: str = "data"
    model_axis: str = "model"
    replicate_kernel_transform: bool = False
    epilogue: Epilogue = Epilogue()    # fused elementwise tail (stage 4)
    overlap: str = "off"               # "off" | "slab:<k>" sub-slab overlap

    @property
    def num_slabs(self) -> int:
        """Batch sub-slab count of the overlapped execution (1 = off)."""
        return _parse_overlap(self.overlap)

    # ---- execution --------------------------------------------------------
    def __call__(self, x, k, *, bias=None, residual=None):
        """Execute the plan.  Plans with a non-noop ``epilogue`` take the
        epilogue *operands* here: ``plan(x, k, bias=b, residual=r)`` —
        fused into stage 4 inside the pipeline (sharded schedules touch
        only their local 1/N output slab, zero extra collectives)."""
        self._check_x(x)
        if tuple(k.shape) != self.k_shape:
            raise ValueError(
                f"plan was built for kernel {self.k_shape}, got "
                f"{tuple(k.shape)}; call plan_conv for the new geometry")
        self._check_epilogue_operands(bias, residual)
        be = registry.get_backend(self.backend)
        if be.pipeline_factory is not None:
            return autodiff.pipeline_conv(self, x, k, bias, residual)
        if not self.epilogue.is_noop:
            return be.execute(self, x, k, bias=bias, residual=residual)
        return be.execute(self, x, k)

    def _check_x(self, x):
        if tuple(x.shape) != self.x_shape:
            raise ValueError(
                f"plan was built for input {self.x_shape}, got "
                f"{tuple(x.shape)}; call plan_conv for the new geometry")

    def _check_epilogue_operands(self, bias, residual):
        ep = self.epilogue
        if ep.bias != (bias is not None):
            raise ValueError(
                f"plan epilogue declares bias={ep.bias} but bias "
                f"{'was not' if ep.bias else 'was'} passed at execution")
        if ep.residual != (residual is not None):
            raise ValueError(
                f"plan epilogue declares residual={ep.residual} but "
                f"residual {'was not' if ep.residual else 'was'} passed "
                "at execution")
        if bias is not None and tuple(bias.shape) != (self.spec.Cout,):
            raise ValueError(
                f"epilogue bias must have shape ({self.spec.Cout},), got "
                f"{tuple(bias.shape)}")
        if residual is not None and tuple(residual.shape) != self.out_shape:
            raise ValueError(
                f"epilogue residual must match the output {self.out_shape},"
                f" got {tuple(residual.shape)}")

    # ---- prepare/execute split --------------------------------------------
    def prepare(self, k, *, weights_version=None) -> "PreparedConv":
        """Run the kernel transform (stage 2) once; return a ``PreparedConv``
        executing the remaining stages against the cached result.

        The prepared cache is keyed by (plan, kernel object): each layer's
        kernel gets its own entry even when same-geometry layers share a
        plan.  ``weights_version`` is the staleness check — preparing the
        same kernel under the same version returns the memoized
        ``PreparedConv`` without re-transforming; a different version
        recomputes and replaces it (weight update -> invalidation).
        ``None`` always recomputes and is never cached.  Call outside
        ``jit`` — the transform runs eagerly here so execution never
        re-traces it.
        """
        if tuple(k.shape) != self.k_shape:
            raise ValueError(
                f"plan was built for kernel {self.k_shape}, got "
                f"{tuple(k.shape)}; call plan_conv for the new geometry")
        if isinstance(k, jax.core.Tracer):
            raise ValueError(
                "plan.prepare must run outside jit/grad (it caches the "
                "concrete transformed kernel); prepare eagerly and close "
                "over the PreparedConv, or use plan(x, k) when k is traced")
        global _prepared_hits, _prepared_misses, _prepared_invalidations
        # Key by (plan, kernel object): same-geometry layers share one
        # ConvPlan, so the plan alone would hand layer B layer A's cached
        # transform.  The PreparedConv pins k, so id(k) is unambiguous for
        # as long as its entry lives.
        cache_key = (self, id(k))
        if weights_version is not None:
            with _prepared_lock:
                slot = _prepared_cache.get(cache_key)
                if slot is not None and slot[0] == weights_version:
                    _prepared_hits += 1
                    _prepared_cache.move_to_end(cache_key)
                    return slot[1]
        be = registry.get_backend(self.backend)
        if be.pipeline_factory is not None:
            state = be.make_pipeline(self).prepare(self, k)
        else:
            state = k              # opaque backend: nothing to pre-transform
        prepared = PreparedConv(plan=self, state=state, kernel=k,
                                weights_version=weights_version)
        if weights_version is not None:
            with _prepared_lock:
                if cache_key in _prepared_cache:
                    _prepared_invalidations += 1
                    _prepared_cache.move_to_end(cache_key)
                _prepared_misses += 1
                _prepared_cache[cache_key] = (weights_version, prepared)
                # same LRU bound as the plan cache: prepared G pytrees are
                # the big arrays, don't let them accumulate unboundedly
                cap = plan_cache_capacity()
                while len(_prepared_cache) > cap:
                    _prepared_cache.popitem(last=False)
        return prepared

    # ---- introspection ----------------------------------------------------
    def analyze(self, *, prepared: bool = False):
        """Static analysis of this plan's traced program: collective
        counts, dtype flow, fusion/elision facts, peak live bytes — see
        ``repro.conv.analyze``.  ``analyze(prepared=True)`` profiles the
        prepared-execute path (kernel layout derived abstractly; no
        transform FLOPs run).  Certify with ``plan.analyze().check()``."""
        from repro.conv.analyze import analyze
        return analyze(self, prepared=prepared)

    @property
    def x_shape(self) -> tuple:
        s = self.spec
        return (s.B, s.C, s.H, s.W)

    @property
    def k_shape(self) -> tuple:
        s = self.spec
        return (s.Cout, s.C, s.kh, s.kw)

    @property
    def out_shape(self) -> tuple:
        s = self.spec
        return (s.B, s.Cout, s.Ho, s.Wo)

    @property
    def differentiable(self) -> bool:
        be = registry.get_backend(self.backend)
        return self.schedule in be.differentiable

    def flops(self) -> int:
        """Cost-model FLOPs of the planned path (for rooflines)."""
        if self.backend == "direct":
            return self.spec.direct_flops()
        return sum(self.spec.stage_flops(three_m=self.three_m).values())

    def describe(self) -> str:
        s = self.spec
        lines = [
            f"ConvPlan {self.x_shape} * {self.k_shape} -> {self.out_shape}",
            f"  backend={self.backend} schedule={self.schedule} "
            f"stride={s.stride} three_m={self.three_m} delta={s.delta} "
            f"epilogue={self.epilogue.describe()}",
            "  predicted ms: " + ", ".join(
                f"{be} {t * 1e3:.3f}" for be, t in predicted_s(s).items()),
        ]
        if self.mesh is not None:
            n_data = self.mesh.shape[self.data_axis]
            n_model = self.mesh.shape[self.model_axis]
            lines.append(
                f"  mesh axes: {self.data_axis}={n_data} "
                f"x {self.model_axis}={n_model}, replicate_kernel_transform="
                f"{self.replicate_kernel_transform}, overlap={self.overlap}")
        if self.bm or self.bn or self.bk or self.dft_bt:
            lines.append(f"  blocks bm={self.bm} bn={self.bn} bk={self.bk} "
                         f"dft_bt={self.dft_bt}")
        if self.compute_dtype is not None:
            lines.append(f"  compute_dtype={self.compute_dtype}")
        return "\n".join(lines)


@dataclasses.dataclass(frozen=True, eq=False)   # identity hash: jit-able
class PreparedConv:
    """A plan bound to a prepared (already-transformed) kernel.

    ``prepared(x)`` runs stages 1/3/4 (+ the schedule's remaining
    collectives); stage 2 and — for ``nfft`` — boundary all-to-all #2 were
    paid once in ``plan.prepare``.  Pipeline backends are differentiable
    w.r.t. ``x`` (the plan-level VJP, so ``fft-pallas`` included); the
    kernel is frozen — to train it, use ``plan(x, k)``.

    A pytree whose leaves are ``state`` and ``kernel``: pass it to a jitted
    function as an argument, so the transformed kernel is an input of the
    executable rather than a constant compiled into it.
    """
    plan: ConvPlan
    state: Any                          # pipeline G pytree, or raw k (opaque)
    kernel: Any = None                  # original k (for the x-grad VJP)
    weights_version: Any = None

    def __call__(self, x, *, bias=None, residual=None):
        self.plan._check_x(x)
        self.plan._check_epilogue_operands(bias, residual)
        be = registry.get_backend(self.plan.backend)
        if be.pipeline_factory is not None:
            return autodiff.prepared_conv(self.plan, self.state, self.kernel,
                                          x, bias, residual)
        if not self.plan.epilogue.is_noop:
            return be.execute(self.plan, x, self.state, bias=bias,
                              residual=residual)
        return be.execute(self.plan, x, self.state)

    @property
    def out_shape(self) -> tuple:
        return self.plan.out_shape

    def analyze(self):
        """Static analysis of the prepared execution path (stage 2 and —
        for nfft — boundary all-to-all #2 must be absent from the traced
        program); see ``repro.conv.analyze``."""
        from repro.conv.analyze import analyze
        return analyze(self)


jax.tree_util.register_pytree_node(
    PreparedConv,
    lambda p: ((p.state, p.kernel), (p.plan, p.weights_version)),
    lambda aux, children: PreparedConv(plan=aux[0], state=children[0],
                                       kernel=children[1],
                                       weights_version=aux[1]))


# --------------------------------------------------------------------------
# Plan cache (bounded LRU) + prepared-kernel cache
# --------------------------------------------------------------------------

PlanCacheInfo = collections.namedtuple("PlanCacheInfo",
                                       ["hits", "misses", "size"])
PreparedCacheInfo = collections.namedtuple(
    "PreparedCacheInfo", ["hits", "misses", "invalidations", "size"])

_DEFAULT_CACHE_SIZE = 256

_cache_lock = threading.Lock()
_plan_cache: "collections.OrderedDict" = collections.OrderedDict()
_cache_hits = 0
_cache_misses = 0

_prepared_lock = threading.Lock()
# plan -> (weights_version, prepared); LRU-bounded like the plan cache
_prepared_cache: "collections.OrderedDict" = collections.OrderedDict()
_prepared_hits = 0
_prepared_misses = 0
_prepared_invalidations = 0


def plan_cache_capacity() -> int:
    """Max cached plans (env ``REPRO_CONV_PLAN_CACHE_SIZE``, default 256)."""
    try:
        cap = int(os.environ.get("REPRO_CONV_PLAN_CACHE_SIZE",
                                 _DEFAULT_CACHE_SIZE))
    except ValueError:
        cap = _DEFAULT_CACHE_SIZE
    return max(1, cap)


def plan_cache_info() -> PlanCacheInfo:
    with _cache_lock:
        return PlanCacheInfo(_cache_hits, _cache_misses, len(_plan_cache))


def clear_plan_cache() -> None:
    global _cache_hits, _cache_misses
    with _cache_lock:
        _plan_cache.clear()
        _cache_hits = 0
        _cache_misses = 0


def prepared_cache_info() -> PreparedCacheInfo:
    with _prepared_lock:
        return PreparedCacheInfo(_prepared_hits, _prepared_misses,
                                 _prepared_invalidations,
                                 len(_prepared_cache))


def clear_prepared_cache() -> None:
    global _prepared_hits, _prepared_misses, _prepared_invalidations
    with _prepared_lock:
        _prepared_cache.clear()
        _prepared_hits = 0
        _prepared_misses = 0
        _prepared_invalidations = 0


def _mesh_cache_key(mesh):
    """Value key for a mesh: two distinct Mesh objects over the same devices
    and axes share plan-cache entries (object identity would duplicate)."""
    if mesh is None:
        return None
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(d.id for d in mesh.devices.flat))


# --------------------------------------------------------------------------
# Planner
# --------------------------------------------------------------------------

def _normalize_padding(padding) -> tuple:
    if isinstance(padding, int):
        return (padding, padding)
    ph, pw = padding
    return (int(ph), int(pw))


def _build_spec(x_shape, k_shape, padding, delta, stride=1) -> ConvSpec:
    """Validated ``ConvSpec`` for a conv geometry (shared with the
    autotuner so cache signatures can never drift from planner
    semantics).  Kernels larger than the tile get a widened (then-unused)
    tile so the spec validates; only ``direct`` can execute them."""
    B, C, H, W = x_shape
    Cout, C2, kh, kw = k_shape
    if C != C2:
        raise ValueError(f"channel mismatch: input C={C}, kernel C={C2}")
    return ConvSpec(B=B, C=C, Cout=Cout, H=H, W=W, kh=kh, kw=kw,
                    pad_h=padding[0], pad_w=padding[1],
                    delta=max(delta, kh, kw), stride=stride)


def _direct_only(kh: int, kw: int, delta: int, stride: int):
    """Why only ``direct`` can run a geometry, or ``None``.  Overlap-save
    computes every unit-stride output, so the FFT pipelines take neither
    a kernel larger than the tile nor a stride."""
    if max(kh, kw) > delta:
        return f"kernel {kh}x{kw} exceeds tile size delta={delta}"
    if stride != 1:
        return (f"stride {stride} is not expressible by overlap-save "
                "(it computes every unit-stride output)")
    return None


# backend="auto" predicts each backend's device time from ConvSpec's
# counts: every stage takes max(FLOPs x passes / (peak x MXU fill),
# bytes / bandwidth).  TPU v5e (Google Cloud, "TPU v5e"): 197 TFLOP/s
# bf16, 819 GB/s HBM, a 128 x 128 MXU; an f32 matmul at HIGHEST takes about
# six bf16 passes (direct runs at ~32 TFLOP/s of direct count with 512
# channels).  The FFT stages move ~3x the bytes ConvSpec counts (pads and
# layout copies around the matmuls): the one constant fitted to both
# backends' per-layer times on one v5e (PERF.md, "auto's table").
_PEAK_FLOPS = 197e12
_PASSES = 6
_HBM_BYTES_S = 819e9
_MXU = 128
_FFT_BYTES_SCALE = 3.0


def _fill(n: int) -> float:
    """Share of the MXU's rows (or columns) a dimension of ``n`` fills."""
    return n / (_MXU * -(-n // _MXU))


def _stage_s(flops: int, nbytes: float, fill: float) -> float:
    return max(flops * _PASSES / (_PEAK_FLOPS * fill), nbytes / _HBM_BYTES_S)


def predicted_s(spec: ConvSpec) -> dict:
    """Predicted device seconds of one prepared forward on ``direct`` and
    on ``fft-xla`` (stage 2 runs once per weight version, so it is left
    out).  ``direct`` contracts C x kh x kw against Cout; the transforms
    put the P' spectrum points (and t_h x t_w outputs) on the MXU, the
    CGEMM C and Cout."""
    direct = _stage_s(spec.direct_flops(), spec.direct_bytes(),
                      _fill(spec.C * spec.kh * spec.kw) * _fill(spec.Cout))
    f, b = spec.stage_flops(), spec.stage_bytes()
    P = spec.freq_points()
    fft = sum(_stage_s(f[s], b[s] * _FFT_BYTES_SCALE, fill) for s, fill in (
        ("input_transform", _fill(P)),
        ("cgemm", _fill(spec.C) * _fill(spec.Cout)),
        ("output_inverse", _fill(P) * _fill(spec.t_h * spec.t_w))))
    return {"direct": direct, "fft-xla": fft}


def _auto_backend(spec: ConvSpec) -> str:
    """The backend with the smaller predicted time (``predicted_s``)."""
    if _direct_only(spec.kh, spec.kw, spec.delta, spec.stride):
        return "direct"
    t = predicted_s(spec)
    return "direct" if t["direct"] <= t["fft-xla"] else "fft-xla"


# overlap="auto" picks "off" below this per-rank batch: slabbing a tiny
# batch leaves each slab too small to amortize its collective's latency
# (and k=2 on b_loc<4 would pipeline 1-row slabs).
_AUTO_OVERLAP_MIN_B = 4


def _parse_overlap(overlap) -> int:
    """Sub-slab count encoded by a (resolved) overlap knob value:
    ``"off"`` -> 1, ``"slab:<k>"`` -> k (k >= 2).  ``"auto"`` must be
    resolved by the planner before it reaches here."""
    if overlap == "off":
        return 1
    if isinstance(overlap, str) and overlap.startswith("slab:"):
        try:
            k = int(overlap[len("slab:"):])
        except ValueError:
            k = 0
        if k >= 2:
            return k
    raise ValueError(
        f"unknown overlap {overlap!r} (choose 'off', 'slab:<k>' with "
        "k >= 2, or 'auto')")


def _resolve_overlap(overlap, spec, sched, be, backend, schedule, mesh,
                     data_axis) -> str:
    """Validate + normalize the overlap knob against the resolved
    (backend, schedule, mesh): ``"auto"`` picks ``"slab:2"`` on sharded
    pipelines with enough per-rank batch (else ``"off"``), and explicit
    slab counts are clamped once to the per-rank batch so every slab is
    non-empty (``"slab:1"`` never exists — it normalizes to ``"off"``)."""
    sharded_pipeline = sched.requires_mesh and be.pipeline_factory is not None
    b_loc = 0
    if sharded_pipeline:
        n_data = mesh.shape[data_axis]
        b_loc = (spec.B + (-spec.B) % n_data) // n_data
    if overlap == "auto":
        return "slab:2" if sharded_pipeline \
            and b_loc >= _AUTO_OVERLAP_MIN_B else "off"
    num_slabs = _parse_overlap(overlap)
    if num_slabs == 1:
        return "off"
    if not sharded_pipeline:
        raise ValueError(
            f"overlap={overlap!r} requires a sharded stage-pipeline "
            f"schedule (backend {backend!r} / schedule {schedule!r} has "
            "no boundary collectives to overlap); use overlap='off'")
    num_slabs = min(num_slabs, b_loc)
    return f"slab:{num_slabs}" if num_slabs > 1 else "off"


def _resolve(x_shape, k_shape, padding, delta, backend, schedule, mesh,
             three_m, bm, bn, bk, dft_bt, compute_dtype, data_axis,
             model_axis, replicate_kernel_transform, epilogue,
             overlap="off", stride=1) -> ConvPlan:
    _, _, kh, kw = k_shape
    # Kernels larger than the FFT tile and strides rule out the FFT
    # backends but are fine for direct conv: _build_spec widens the
    # (then-unused) tile so the spec validates, and auto resolves to
    # direct below.
    direct_only = _direct_only(kh, kw, delta, stride)
    if direct_only and backend not in ("auto", "direct"):
        registry.get_backend(backend)        # unknown names error first
        raise ValueError(
            f"{direct_only}; only the 'direct' backend supports it "
            f"(requested {backend!r})")
    spec = _build_spec(x_shape, k_shape, padding, delta, stride)

    # -- schedule -----------------------------------------------------------
    if schedule == "auto":
        schedule = "nfft" if mesh is not None else "local"
    sched = registry.get_schedule(schedule)
    if sched.requires_mesh and mesh is None:
        raise ValueError(f"schedule {schedule!r} requires a mesh")
    if not sched.requires_mesh and mesh is not None:
        raise ValueError(
            f"schedule {schedule!r} ignores the mesh; pass schedule='nfft' "
            "or 'wfft' (or drop the mesh)")
    if sched.requires_mesh:
        for axis in (data_axis, model_axis):
            if axis not in mesh.shape:
                raise ValueError(
                    f"mesh has no axis {axis!r} (axes: {tuple(mesh.shape)})")
        # Channel axes are zero-padded up to model-axis multiples inside
        # the pipelines, and the frequency (P) axis is padded once before
        # the nfft boundary all-to-alls — no divisibility precondition.

    # -- backend ------------------------------------------------------------
    if backend == "auto":
        if direct_only:
            backend = "direct"
        else:
            backend = "fft-xla" if sched.requires_mesh \
                else _auto_backend(spec)
    be = registry.get_backend(backend)
    if schedule not in be.schedules:
        raise ValueError(
            f"backend {backend!r} does not support schedule {schedule!r} "
            f"(supported: {be.schedules})")
    if not epilogue.is_noop and not be.epilogue_capable:
        raise ValueError(
            f"backend {backend!r} cannot fuse an epilogue "
            f"({epilogue.describe()}); register it with "
            "supports_epilogue=True or use a stage-pipeline backend")

    # -- overlap (comm/compute-overlapped sub-slab execution) ---------------
    overlap = _resolve_overlap(overlap, spec, sched, be, backend, schedule,
                               mesh, data_axis)
    num_slabs = _parse_overlap(overlap)
    if num_slabs > 1 and backend == "fft-pallas":
        # Pin the Pallas CGEMM blocks ONCE against the smallest sub-slab's
        # geometry so every slab shares one block config — per-slab
        # resolution would re-pad the small slabs on every call (certified
        # by the analyzer's overlap-uniform-blocks invariant).  Explicit
        # caller pins pass through resolve_blocks verbatim.
        from repro.kernels.cgemm.ops import resolve_blocks
        n_data = mesh.shape[data_axis]
        n_model = mesh.shape[model_axis]
        b_loc = (spec.B + (-spec.B) % n_data) // n_data
        c_pad = spec.C + (-spec.C) % n_model
        co_pad = spec.Cout + (-spec.Cout) % n_model
        m_min = (b_loc // num_slabs) * spec.n_tiles
        k_dim = c_pad if schedule == "nfft" else max(1, c_pad // n_model)
        bm, bn, bk = resolve_blocks(m_min, co_pad, k_dim, bm, bn, bk)

    return ConvPlan(spec=spec, backend=backend, schedule=schedule,
                    padding=padding, three_m=three_m, bm=bm, bn=bn, bk=bk,
                    dft_bt=dft_bt, compute_dtype=compute_dtype, mesh=mesh,
                    data_axis=data_axis, model_axis=model_axis,
                    replicate_kernel_transform=replicate_kernel_transform,
                    epilogue=epilogue, overlap=overlap)


def plan_conv(spec, k_shape=None, *, padding=None, delta: Optional[int] = None,
              backend: str = "auto", schedule: str = "auto", mesh=None,
              three_m: bool = True, bm=None, bn=None, bk=None, dft_bt=None,
              compute_dtype=None, data_axis: str = "data",
              model_axis: str = "model",
              replicate_kernel_transform: bool = False,
              epilogue: Optional[Epilogue] = None,
              overlap: str = "off",
              stride: Optional[int] = None,
              cache: bool = True) -> ConvPlan:
    """Create (or fetch from the plan cache) a ``ConvPlan``.

    Args:
      spec: a ``ConvSpec`` (geometry + padding + delta in one object —
        the same spec ``autotune.tune`` accepts), or the input shape
        ``(B, C, H, W)`` with ``k_shape``/``padding``/``delta`` given
        separately.
      k_shape: kernel shape ``(C', C, kh, kw)`` with ``kh, kw <= delta``
        (shape-tuple form only — a ``ConvSpec`` already carries it).
      padding: int or ``(ph, pw)`` zero padding (default 0).
      delta: FFT tile size (the paper uses 16).
      stride: output subsampling, the same in both axes (default 1).
        Only ``direct`` runs ``stride > 1``: ``"auto"`` and ``"tuned"``
        resolve to it and the FFT backends raise ``ValueError``.
      backend: ``"direct"`` | ``"fft-xla"`` | ``"fft-pallas"`` | ``"auto"``
        (the smaller predicted time, ``predicted_s``; never auto-selects
        Pallas) | ``"tuned"``
        (measured on-device selection via ``repro.conv.autotune`` — warm
        persistent cache, cost-model fallback when measurement is
        disabled; the tuner also picks schedule and blocks unless pinned
        here).
      schedule: ``"local"`` | ``"nfft"`` | ``"wfft"`` | ``"auto"``
        (``nfft`` when a mesh is given, else ``local``; with
        ``backend="tuned"`` the tuner measures nfft vs wfft).
      mesh: jax Mesh with ``data_axis``/``model_axis``; required by the
        sharded schedules.  Cached plans key meshes by value
        ``(axis_names, shape, device ids)``, so equal meshes share entries.
      three_m: 3-matmul (Karatsuba) vs 4-matmul complex product.
      bm, bn, bk: Pallas CGEMM block sizes (``fft-pallas`` only).
      dft_bt: Pallas ``dft_tile`` tile-batch block (``fft-pallas`` fused
        inverse tail only).
      compute_dtype: CGEMM operand dtype (e.g. bf16; f32 accumulation).
        On the sharded schedules the cast happens before the hot-path
        collective (nfft boundary a2a / wfft in-stage psum), halving its
        bytes.
      replicate_kernel_transform: nfft only — replicate the cheap kernel
        transform on every model rank instead of all-to-all-ing it.
      epilogue: ``Epilogue`` fused into stage 4 (bias add, activation,
        residual add) on the local output slab, before the output dtype
        cast — zero extra collectives, zero extra stage ops.  The operand
        values are execution arguments: ``plan(x, k, bias=b, residual=r)``.
      overlap: comm/compute-overlapped execution for the sharded
        schedules.  ``"slab:<k>"`` splits the per-rank batch into k
        sub-slabs inside the shard_map body and double-buffers, so the
        boundary collective of slab i+1 overlaps the hot cgemm of slab i
        (requires the async-collective / latency-hiding XLA flags —
        ``repro.launch.env``).  ``"auto"`` picks ``"slab:2"`` on sharded
        pipelines with per-rank batch >= 4, else ``"off"``; slab counts
        are clamped to the per-rank batch.  ``"off"`` (default) is the
        sequential path.  With ``backend="tuned"`` and ``overlap="auto"``
        the tuner measures the overlap axis.
      cache: memoize the plan under its argument key (bounded LRU, see
        ``plan_cache_capacity``).

    Returns:
      A frozen ``ConvPlan``; call it as ``plan(x, k)`` or split with
      ``plan.prepare(k)``.
    """
    global _cache_hits, _cache_misses
    if isinstance(spec, ConvSpec):
        if (k_shape is not None or padding is not None or delta is not None
                or stride is not None):
            raise TypeError(
                "plan_conv(spec, ...): a ConvSpec already carries k_shape/"
                "padding/delta/stride — pass them only with the shape-tuple "
                "form")
        x_shape = (spec.B, spec.C, spec.H, spec.W)
        k_shape = (spec.Cout, spec.C, spec.kh, spec.kw)
        padding = (spec.pad_h, spec.pad_w)
        delta = spec.delta
        stride = spec.stride
    else:
        if k_shape is None:
            raise TypeError(
                "plan_conv(x_shape, k_shape, ...): k_shape is required "
                "with the shape-tuple form (or pass a ConvSpec)")
        x_shape = spec
        padding = 0 if padding is None else padding
        delta = 16 if delta is None else delta
        stride = 1 if stride is None else int(stride)
    x_shape, k_shape = tuple(map(int, x_shape)), tuple(map(int, k_shape))
    padding = _normalize_padding(padding)
    epilogue = Epilogue() if epilogue is None else epilogue
    if backend == "tuned":
        # Measured selection resolves BEFORE the plan cache, so the plan
        # is memoized under the *resolved* config: a cost-model fallback
        # (measurement disabled / cold-and-offline) is never frozen in —
        # once the tuning cache warms, the next call adopts the winner.
        if _direct_only(k_shape[2], k_shape[3], delta, stride):
            backend = "direct"      # oversize kernel or stride: only direct
        else:
            from repro.conv import autotune
            # tune unpinned: pins constrain the *plan*, not the machine's
            # measured winner (pinned tune() calls get their own cache key)
            tuned = autotune.tune(
                x_shape, k_shape, padding=padding, delta=delta,
                schedule=schedule, mesh=mesh, three_m=three_m,
                compute_dtype=compute_dtype, data_axis=data_axis,
                model_axis=model_axis,
                replicate_kernel_transform=replicate_kernel_transform,
                overlap=overlap)
            backend = tuned.backend
            if schedule == "auto":
                schedule = tuned.schedule
            if overlap == "auto":
                overlap = tuned.overlap
            # explicit caller overrides beat tuned blocks
            bm = bm if bm is not None else tuned.bm
            bn = bn if bn is not None else tuned.bn
            bk = bk if bk is not None else tuned.bk
            dft_bt = dft_bt if dft_bt is not None else tuned.dft_bt
    key = (x_shape, k_shape, padding, delta, backend, schedule,
           _mesh_cache_key(mesh), three_m, bm, bn, bk, dft_bt,
           compute_dtype, data_axis, model_axis,
           replicate_kernel_transform, epilogue, overlap, stride)
    if cache:
        with _cache_lock:
            plan = _plan_cache.get(key)
            if plan is not None:
                _cache_hits += 1
                _plan_cache.move_to_end(key)
                return plan
    plan = _resolve(x_shape, k_shape, padding, delta, backend, schedule,
                    mesh, three_m, bm, bn, bk, dft_bt, compute_dtype,
                    data_axis, model_axis, replicate_kernel_transform,
                    epilogue, overlap, stride)
    if cache:
        with _cache_lock:
            _cache_misses += 1
            _plan_cache[key] = plan
            _plan_cache.move_to_end(key)
            cap = plan_cache_capacity()
            while len(_plan_cache) > cap:
                _plan_cache.popitem(last=False)
    return plan


def conv2d(x, k, **kwargs):
    """One-shot convenience: ``plan_conv(x.shape, k.shape, **kwargs)(x, k)``.

    The plan cache makes repeated same-shape calls pay planning once.
    """
    return plan_conv(tuple(x.shape), tuple(k.shape), **kwargs)(x, k)
