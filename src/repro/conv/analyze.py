"""Plan-lint: jaxpr-level static analysis of convolution plans.

The paper's NUMA-aware claim is *structural*: data reordering plus the
three-level cgemm parallelization bound how many remote accesses
(all-to-alls / reductions) each schedule performs.  That property can be
certified statically — trace the plan, walk the equation graph, count —
instead of measured, and instead of string-matching the jaxpr pretty
printer (which breaks whenever jax changes its formatting).

``analyze(plan)`` traces a ``ConvPlan`` / ``PreparedConv`` to a closed
jaxpr and walks the equation tree — recursing through ``shard_map``
bodies, ``custom_vjp`` / ``custom_jvp`` call jaxprs, ``pjit`` sub-jaxprs
and any other sub-jaxpr-carrying primitive — into a structured
``PlanProfile``:

  * per-collective equation counts (``all_to_all``, ``psum``,
    ``ppermute``, ``all_gather``) and the bytes they move;
  * dtype-flow facts: the operand dtype of every collective (did the
    ``compute_dtype`` cast land *before* the hot collective?), the CGEMM
    operand dtypes (did ``compute_dtype`` actually reach the hot stage?),
    and whether any f64 silently appeared;
  * stage-op invocation counts (via ``stage_trace``);
  * epilogue-fusion facts: the collective/stage-count delta vs the same
    plan with its epilogue stripped (must be zero — fusion is free);
  * prepared-plan elision facts: which stages/collectives a prepared
    execution skips vs the one-shot plan (nfft: stage 2 and one boundary
    all-to-all);
  * an estimated peak live-buffer footprint per rank (liveness walk over
    the traced program).

On top of the profile sits a declarative invariant registry keyed by
``(backend, schedule)`` (``"*"`` wildcards), evaluated by
``analyze(plan).check()``:

    backend x schedule        invariant
    ----------------------    ------------------------------------------
    *        local            0 collectives of any kind
    *        nfft (full)      6 all_to_all (3 boundaries x re/im), 0 psum
    *        nfft (prepared)  4 all_to_all, stage 2 traced zero times
    *        nfft (repl. G)   4 all_to_all (kernel boundary elided)
    *        wfft             exactly the hot psum pair, 0 all_to_all
    *        * + compute_dtype casts placed before the hot collective,
                              CGEMM operands in compute_dtype
    *        * + epilogue     zero extra collectives, zero extra stage ops
    *        *                no f64 anywhere in the traced program
    *        nfft             collective bytes == the count from the
                              geometry (``counted_collective_bytes``)
    *        wfft             collective bytes == the count from the
                              geometry

Overlapped plans (``overlap="slab:k"``) scale the count rules per slab —
nfft traces ``4k + 2`` all_to_all eqns (D/Z boundaries per slab, kernel
boundary once), wfft ``2k`` psums, each stage op ``k`` times (stage 2
once) — and add two rules of their own: total collective bytes must stay
<= 1.0x the sequential (``overlap="off"``) twin's (the slabs repartition
the rows, they must never re-send them), and on ``fft-pallas`` every
sub-slab's cgemm must resolve the one plan-pinned block config (no
per-slab re-padding).

The collective-bytes rules are *exact*: ``counted_collective_bytes``
counts what each sharded schedule's collectives must move from the
plan's geometry — ``spec.freq_points()`` compact spectrum points, the
mesh padding of batch, channels and (nfft) the point axis,
``compute_dtype``, and the prepared / replicated / sub-slab form — and
the traced operand bytes must equal it, so a spectrum that carries one
redundant point more than the compact list trips the gate.

``python -m repro.conv.analyze --check`` sweeps every registered
backend x schedule pair over the paper geometries
(``configs/paper_convs.py``) x {full, prepared, fused-epilogue,
compute-dtype, strided, overlapped} variants and exits non-zero on any
violation — the CI gate
that keeps future perf work honest.  ``seeded_violation(...)`` breaks the
pipelines on purpose so the gate itself is testable.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro.compat import jaxpr_types

COLLECTIVES = ("all_to_all", "psum", "ppermute", "all_gather")


# --------------------------------------------------------------------------
# Jaxpr walking (structural, pretty-printer-independent)
# --------------------------------------------------------------------------

def _sub_jaxprs(eqn):
    """Every sub-jaxpr a primitive carries, whatever the param is called
    (``jaxpr`` for pjit/shard_map, ``fun_jaxpr`` for custom_vjp,
    ``call_jaxpr`` for custom_jvp/xla_call, ``branches`` for cond, ...)."""
    Jaxpr, ClosedJaxpr = jaxpr_types()
    for val in eqn.params.values():
        items = val if isinstance(val, (tuple, list)) else (val,)
        for item in items:
            if isinstance(item, ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, Jaxpr):
                yield item


def _aval_bytes(aval) -> int:
    try:
        return int(aval.size) * int(aval.dtype.itemsize)
    except (AttributeError, TypeError):
        return 0


def _walk(jaxpr, visit: Callable[[Any], None]) -> None:
    """Depth-first visit of every equation, recursing into sub-jaxprs."""
    for eqn in jaxpr.eqns:
        visit(eqn)
        for sub in _sub_jaxprs(eqn):
            _walk(sub, visit)


def _peak_live_bytes(jaxpr) -> int:
    """Estimated peak of simultaneously-live buffer bytes in a traced
    program (liveness walk: a value lives from its defining equation to
    its last use).  Inside ``shard_map`` bodies the avals are per-rank, so
    for sharded schedules this is a per-rank footprint estimate; an
    equation carrying a sub-jaxpr contributes its own peak on top of the
    caller's live set."""
    last_use: Dict[Any, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not hasattr(v, "val"):          # skip Literals
                last_use[v] = i
    n = len(jaxpr.eqns)
    for v in jaxpr.outvars:
        if not hasattr(v, "val"):
            last_use[v] = n
    live: Dict[Any, int] = {
        v: _aval_bytes(v.aval)
        for v in (*jaxpr.constvars, *jaxpr.invars) if not hasattr(v, "val")
    }
    cur = sum(live.values())
    peak = cur
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.outvars:
            b = _aval_bytes(v.aval)
            live[v] = b
            cur += b
        sub_peak = max((_peak_live_bytes(s) for s in _sub_jaxprs(eqn)),
                       default=0)
        peak = max(peak, cur + sub_peak)
        for v in [v for v, j in last_use.items() if j <= i]:
            cur -= live.pop(v, 0)
            del last_use[v]
        for v in [v for v in eqn.outvars if v in live and v not in last_use]:
            cur -= live.pop(v)                 # dead outputs free at once
    return peak


# --------------------------------------------------------------------------
# PlanProfile
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class Violation:
    invariant: str
    message: str

    def __str__(self) -> str:
        return f"[{self.invariant}] {self.message}"


@dataclasses.dataclass(frozen=True, eq=False)
class CheckReport:
    """Result of evaluating the invariant registry against a profile."""
    profile: "PlanProfile"
    violations: Tuple[Violation, ...]
    checked: Tuple[str, ...]                   # invariant names evaluated

    @property
    def ok(self) -> bool:
        return not self.violations

    def raise_if_failed(self) -> "CheckReport":
        if self.violations:
            detail = "\n  ".join(str(v) for v in self.violations)
            raise AssertionError(
                f"plan-lint: {self.profile.describe_key()} violates "
                f"{len(self.violations)} invariant(s):\n  {detail}")
        return self


@dataclasses.dataclass(frozen=True, eq=False)
class PlanProfile:
    """Structured static-analysis facts for one traced plan execution."""
    backend: str
    schedule: str
    prepared: bool
    is_pipeline: bool                          # stage-graph backend
    replicate_kernel_transform: bool
    epilogue: str                              # Epilogue.describe()
    compute_dtype: Optional[str]               # canonical name or None
    collectives: Dict[str, int]                # name -> eqn count
    collective_dtypes: Dict[str, Dict[str, int]]   # name -> dtype -> count
    collective_bytes: int                      # operand bytes entering them
    stage_counts: Dict[str, int]               # trace-time stage-op counts
    cgemm_dtypes: Tuple[str, ...]              # operand dtypes at stage 3
    has_f64: bool
    peak_live_bytes: int
    n_eqns: int
    epilogue_delta: Optional[Dict[str, Dict[str, int]]] = None
    elision: Optional[Dict[str, int]] = None   # full minus prepared counts
    counted_collective_bytes: Optional[int] = None   # from the geometry
    overlap: str = "off"                       # plan overlap knob (resolved)
    num_slabs: int = 1                         # sub-slab count (1 = off)
    blocks: Optional[Tuple] = None             # plan (bm, bn, bk) pins
    cgemm_shapes: Tuple = ()                   # distinct (M, N, K) at stage 3
    overlap_delta: Optional[Dict[str, Any]] = None   # vs sequential twin
    strides: Tuple[int, ...] = ()              # distinct strides > 1 traced

    def describe_key(self) -> str:
        tags = [self.backend, self.schedule]
        if self.prepared:
            tags.append("prepared")
        if self.num_slabs > 1:
            tags.append(self.overlap)
        if self.epilogue != "none":
            tags.append(f"ep={self.epilogue}")
        if self.compute_dtype:
            tags.append(self.compute_dtype)
        return "/".join(tags)

    def check(self, *, extra=()) -> CheckReport:
        """Evaluate every registered invariant applying to this profile."""
        violations: List[Violation] = []
        invs = list(invariants_for(self.backend, self.schedule)) + list(extra)
        for inv in invs:
            msg = inv.rule(self)
            if msg:
                violations.append(Violation(inv.name, msg))
        return CheckReport(profile=self, violations=tuple(violations),
                           checked=tuple(i.name for i in invs))

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["cgemm_dtypes"] = list(self.cgemm_dtypes)
        d["blocks"] = list(self.blocks) if self.blocks else None
        d["cgemm_shapes"] = [list(s) for s in self.cgemm_shapes]
        d["strides"] = list(self.strides)
        return d


# --------------------------------------------------------------------------
# Invariant registry (declarative, keyed backend x schedule)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class Invariant:
    """One named structural rule.  ``rule(profile)`` returns ``None`` when
    the invariant holds, else a human-readable violation message."""
    name: str
    rule: Callable[[PlanProfile], Optional[str]]
    description: str = ""


_REGISTRY: Dict[Tuple[str, str], List[Invariant]] = {}


def register_invariant(backend: str, schedule: str, name: str,
                       rule: Callable[[PlanProfile], Optional[str]],
                       description: str = "") -> Invariant:
    """Register a structural invariant for ``(backend, schedule)``;
    ``"*"`` wildcards either key.  Third-party backends registered via
    ``repro.conv.register_backend`` add their rules here so the
    ``--check`` sweep certifies them too."""
    inv = Invariant(name=name, rule=rule, description=description)
    _REGISTRY.setdefault((backend, schedule), []).append(inv)
    return inv


def invariants_for(backend: str, schedule: str) -> Tuple[Invariant, ...]:
    out: List[Invariant] = []
    for key in (("*", "*"), ("*", schedule), (backend, "*"),
                (backend, schedule)):
        out.extend(_REGISTRY.get(key, ()))
    return tuple(out)


def _expect_counts(**expected):
    """Rule factory: exact collective-equation counts.  Values are ints or
    ``callable(profile) -> int`` for prepared/replicated variants."""
    def rule(p: PlanProfile) -> Optional[str]:
        bad = []
        for name, want in expected.items():
            want_n = want(p) if callable(want) else want
            got = p.collectives.get(name, 0)
            if got != want_n:
                bad.append(f"{name}: expected {want_n}, traced {got}")
        return "; ".join(bad) or None
    return rule


def _nfft_a2a(p: PlanProfile) -> int:
    # per slab: D boundary #1 + Z boundary #3 (re/im pairs = 4 eqns);
    # kernel boundary #2 is shared by all slabs and traced once (2 eqns) —
    # prepared elides it (stage 2 was paid at prepare time) and
    # replicate_kernel_transform never emits it.  num_slabs=1 recovers the
    # sequential 6 full / 4 prepared-or-replicated counts.
    s = max(1, p.num_slabs)
    return 4 * s + (0 if (p.prepared or p.replicate_kernel_transform)
                    else 2)


def _wfft_psum(p: PlanProfile) -> int:
    # the hot-stage all-reduce pair, once per sub-slab
    return 2 * max(1, p.num_slabs)


def _rule_local_collective_free(p: PlanProfile) -> Optional[str]:
    extra = {k: v for k, v in p.collectives.items() if v}
    if extra:
        return f"local schedule traced collectives: {extra}"
    return None


def _rule_stage_ops_once(p: PlanProfile) -> Optional[str]:
    if not p.is_pipeline:
        return None
    s = max(1, p.num_slabs)
    # stages 1/3/4 run once per sub-slab; the kernel transform is shared
    # by all slabs (never duplicated) and elided entirely when prepared
    want = {"input_transform": s, "cgemm": s, "output_inverse": s,
            "kernel_transform": 0 if p.prepared else 1}
    bad = [f"{k}: expected {v}, traced {p.stage_counts.get(k, 0)}"
           for k, v in want.items() if p.stage_counts.get(k, 0) != v]
    return "; ".join(bad) or None


def _rule_no_f64(p: PlanProfile) -> Optional[str]:
    if p.has_f64:
        return "f64 values appeared in the traced program (silent upcast)"
    return None


def _rule_compute_dtype_reaches_cgemm(p: PlanProfile) -> Optional[str]:
    if p.compute_dtype is None or not p.is_pipeline:
        return None
    if set(p.cgemm_dtypes) != {p.compute_dtype}:
        return (f"CGEMM operands traced as {sorted(set(p.cgemm_dtypes))}, "
                f"expected compute_dtype={p.compute_dtype}")
    return None


def _rule_cast_before_hot_collective(hot: str, expected_n):
    """The compute_dtype cast must land BEFORE the hot collective so it
    moves half the bytes: ``expected_n`` of the ``hot`` collective's
    equations must carry operands in compute_dtype."""
    def rule(p: PlanProfile) -> Optional[str]:
        if p.compute_dtype is None:
            return None
        want = expected_n(p) if callable(expected_n) else expected_n
        got = p.collective_dtypes.get(hot, {}).get(p.compute_dtype, 0)
        if got != want:
            return (f"{hot} in {p.compute_dtype}: expected {want} eqns, "
                    f"traced {got} "
                    f"(dtypes seen: {p.collective_dtypes.get(hot, {})})")
        return None
    return rule


def _rule_epilogue_free(p: PlanProfile) -> Optional[str]:
    if not p.epilogue_delta:
        return None
    bad = []
    for kind, deltas in p.epilogue_delta.items():
        extra = {k: v for k, v in deltas.items() if v}
        if extra:
            bad.append(f"epilogue added {kind}: {extra}")
    return "; ".join(bad) or None


def _rule_collective_bytes_counted(p: PlanProfile) -> Optional[str]:
    if p.counted_collective_bytes is None:
        return None
    if p.collective_bytes != p.counted_collective_bytes:
        return (f"collectives moved {p.collective_bytes} operand bytes; "
                f"the geometry counts {p.counted_collective_bytes} "
                f"(spec.freq_points() points, mesh padding, compute_dtype)")
    return None


def _rule_no_strided_fft(p: PlanProfile) -> Optional[str]:
    if p.is_pipeline and p.strides:
        return (f"stage pipeline traced stride(s) {list(p.strides)}; "
                "overlap-save computes unit-stride outputs only (strided "
                "layers belong on 'direct')")
    return None


def _rule_prepared_elides_boundary(p: PlanProfile) -> Optional[str]:
    if not (p.prepared and p.elision):
        return None
    if p.elision.get("all_to_all", 0) != 2:
        return (f"prepared nfft must skip exactly one boundary all-to-all "
                f"(re/im pair); elision traced {p.elision}")
    return None


# Overlapped execution repartitions the batch rows across sub-slab
# collectives — it must never re-send them.  Exact parity is expected
# (the per-slab paddings are proportional); the epsilon only absorbs
# float division.
_OVERLAP_BYTES_RATIO = 1.005


def _rule_overlap_bytes_parity(p: PlanProfile) -> Optional[str]:
    if p.num_slabs <= 1 or not p.overlap_delta:
        return None
    ratio = p.overlap_delta.get("ratio")
    if ratio is not None and ratio > _OVERLAP_BYTES_RATIO:
        return (f"overlapped plan moves {ratio:.4f}x the collective bytes "
                f"of its sequential (overlap='off') twin "
                f"({p.overlap_delta.get('collective_bytes')} vs "
                f"{p.overlap_delta.get('twin_collective_bytes')}); "
                f"sub-slabbing must repartition rows, not duplicate them")
    return None


def _rule_overlap_uniform_blocks(p: PlanProfile) -> Optional[str]:
    """Every sub-slab's cgemm must resolve to the ONE block config pinned
    at plan time — differing per-slab resolutions mean distinct compiled
    kernels and re-padding on every call (the bug the plan-time clamp
    fixes)."""
    if p.num_slabs <= 1 or not p.cgemm_shapes:
        return None
    from repro.kernels.cgemm.ops import resolve_blocks
    bm, bn, bk = p.blocks if p.blocks else (None, None, None)
    resolved = {resolve_blocks(m, n, c, bm, bn, bk)
                for (m, n, c) in p.cgemm_shapes}
    if len(resolved) > 1:
        return (f"sub-slab cgemm shapes {sorted(p.cgemm_shapes)} resolve "
                f"different block configs {sorted(resolved)}; blocks must "
                f"be clamped once at plan time")
    rbm = next(iter(resolved))[0]
    m_min = min(m for m, _, _ in p.cgemm_shapes)
    lane_fit = -(-m_min // 8) * 8
    if rbm > lane_fit:
        return (f"resolved bm={rbm} exceeds the smallest sub-slab's "
                f"lane-aligned rows (M={m_min} -> {lane_fit}): the small "
                f"slabs re-pad on every call")
    return None


def _register_builtin_invariants() -> None:
    register_invariant(
        "*", "local", "local-collective-free", _rule_local_collective_free,
        "the local schedule performs zero collectives of any kind")
    register_invariant(
        "*", "nfft", "nfft-a2a-count",
        _expect_counts(all_to_all=_nfft_a2a, psum=0, ppermute=0,
                       all_gather=0),
        "tuple partitioning: one a2a pair per live stage boundary and a "
        "collective-free hot CGEMM (6 full / 4 prepared or replicated; "
        "the D/Z boundary pairs scale per sub-slab when overlapped)")
    register_invariant(
        "*", "nfft", "nfft-prepared-elision", _rule_prepared_elides_boundary,
        "prepared nfft skips stage 2 AND boundary all-to-all #2")
    register_invariant(
        "*", "nfft", "nfft-hot-cast",
        _rule_cast_before_hot_collective("all_to_all",
                                         lambda p: 4 * max(1, p.num_slabs)),
        "compute_dtype cast lands before the D/Z boundary a2a pairs "
        "(the kernel boundary stays f32)")
    register_invariant(
        "*", "wfft", "wfft-hot-psum-pair",
        _expect_counts(psum=_wfft_psum, all_to_all=0, ppermute=0,
                       all_gather=0),
        "baseline: exactly the hot-stage all-reduce pair (per sub-slab "
        "when overlapped), nothing else")
    register_invariant(
        "*", "wfft", "wfft-hot-cast",
        _rule_cast_before_hot_collective("psum", _wfft_psum),
        "compute_dtype cast lands before the hot-stage psum pair")
    register_invariant(
        "*", "nfft", "nfft-collective-bytes",
        _rule_collective_bytes_counted,
        "the boundary all-to-alls move exactly the bytes counted from the "
        "compact spectrum, the mesh padding and compute_dtype")
    register_invariant(
        "*", "wfft", "wfft-collective-bytes",
        _rule_collective_bytes_counted,
        "the hot psum pair moves exactly the bytes counted from the "
        "compact spectrum, the mesh padding and compute_dtype")
    register_invariant(
        "*", "*", "no-strided-fft", _rule_no_strided_fft,
        "no layer with stride > 1 runs on an FFT stage pipeline")
    register_invariant(
        "*", "*", "stage-ops-once", _rule_stage_ops_once,
        "each pipeline stage op traces exactly once (stage 2 zero times "
        "when prepared)")
    register_invariant(
        "*", "*", "no-f64", _rule_no_f64,
        "no silent f64 upcast anywhere in the traced program")
    register_invariant(
        "*", "*", "compute-dtype-reaches-cgemm",
        _rule_compute_dtype_reaches_cgemm,
        "compute_dtype actually reaches the hot CGEMM operands")
    register_invariant(
        "*", "*", "epilogue-fusion-free", _rule_epilogue_free,
        "a fused epilogue adds zero collectives and zero stage ops")
    register_invariant(
        "*", "*", "overlap-bytes-parity", _rule_overlap_bytes_parity,
        "an overlapped plan's total collective bytes stay <= 1.0x its "
        "sequential (overlap='off') twin's — sub-slabbing repartitions "
        "the rows, it never re-sends them")
    register_invariant(
        "fft-pallas", "*", "overlap-uniform-blocks",
        _rule_overlap_uniform_blocks,
        "every sub-slab's cgemm resolves the one plan-pinned block "
        "config (no per-slab re-resolution / re-padding)")


_register_builtin_invariants()


# --------------------------------------------------------------------------
# Tracing -> PlanProfile
# --------------------------------------------------------------------------

def _canon_dtype(dt) -> Optional[str]:
    if dt is None:
        return None
    import numpy as np
    return str(np.dtype(dt))


def _epilogue_arg_structs(plan):
    import jax
    import jax.numpy as jnp
    keys, structs = [], []
    if plan.epilogue.bias:
        keys.append("bias")
        structs.append(jax.ShapeDtypeStruct((plan.spec.Cout,), jnp.float32))
    if plan.epilogue.residual:
        keys.append("residual")
        structs.append(jax.ShapeDtypeStruct(plan.out_shape, jnp.float32))
    return keys, structs


def _trace_full(plan):
    """Jaxpr + stage counts of the one-shot ``plan(x, k)`` path.  The
    closure is built fresh on every call: jax memoizes custom-VJP traces
    per (plan, avals), and a reused callable would skip the Python-level
    stage counters on the second trace."""
    import jax
    import jax.numpy as jnp
    from repro.conv.stages import stage_trace
    keys, ep_structs = _epilogue_arg_structs(plan)
    args = [jax.ShapeDtypeStruct(plan.x_shape, jnp.float32),
            jax.ShapeDtypeStruct(plan.k_shape, jnp.float32), *ep_structs]
    with stage_trace() as counts:
        jaxpr = jax.make_jaxpr(
            lambda x, k, *ep: plan(x, k, **dict(zip(keys, ep))))(*args)
    return jaxpr, dict(counts)


def _trace_prepared(plan, state=None):
    """Jaxpr + stage counts of the prepared-execute path.  With no
    concrete ``state`` the prepared kernel layout is derived abstractly
    (``jax.eval_shape`` over the pipeline's ``prepare``) so no transform
    FLOPs run — analysis stays static."""
    import jax
    import jax.numpy as jnp
    from repro.conv import registry
    from repro.conv.stages import stage_trace
    be = registry.get_backend(plan.backend)
    k_struct = jax.ShapeDtypeStruct(plan.k_shape, jnp.float32)
    if be.pipeline_factory is not None:
        pipe = be.make_pipeline(plan)
        if state is None:
            state = jax.eval_shape(lambda k: pipe.prepare(plan, k), k_struct)

        def run(x, st, bias=None, residual=None):
            return pipe.execute(plan, x, st, bias=bias, residual=residual)
    else:
        if state is None:
            state = k_struct                  # opaque: state IS the kernel

        def run(x, st, bias=None, residual=None):
            if plan.epilogue.is_noop:
                return be.execute(plan, x, st)
            return be.execute(plan, x, st, bias=bias, residual=residual)

    state_structs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
    keys, ep_structs = _epilogue_arg_structs(plan)
    args = [jax.ShapeDtypeStruct(plan.x_shape, jnp.float32), state_structs,
            *ep_structs]
    with stage_trace() as counts:
        jaxpr = jax.make_jaxpr(
            lambda x, st, *ep: run(x, st, **dict(zip(keys, ep))))(*args)
    return jaxpr, dict(counts)


def counted_collective_bytes(plan, *, prepared: bool) -> Optional[int]:
    """Operand bytes a sharded stage pipeline's collectives must move on
    one rank, counted from the geometry (``None`` off nfft / wfft).

    The spectrum has ``P = spec.freq_points()`` points; batch and both
    channel axes are padded to mesh-axis multiples, and nfft pads P to a
    model-axis multiple before its boundaries.  Every (re, im) pair
    counts twice.  D and Z travel in ``compute_dtype`` when set, the
    kernel boundary always in float32; sub-slabs split the batch rows,
    so their sum is the sequential count.
    """
    import numpy as np
    from repro.conv import registry
    from repro.conv.stages import padded_sharded_spec
    if registry.get_backend(plan.backend).pipeline_factory is None \
            or plan.schedule not in ("nfft", "wfft"):
        return None
    spec = padded_sharded_spec(plan)
    n_data = plan.mesh.shape[plan.data_axis]
    n_model = plan.mesh.shape[plan.model_axis]
    rows = spec.B // n_data * spec.n_tiles          # M on one rank
    hot = 4 if plan.compute_dtype is None \
        else np.dtype(plan.compute_dtype).itemsize
    P = spec.freq_points()
    if plan.schedule == "wfft":
        return 2 * P * rows * spec.Cout * hot        # the psum pair on Z
    P_pad = P + (-P) % n_model
    d = 2 * P_pad * rows * (spec.C // n_model) * hot     # boundary 1
    z = 2 * (P_pad // n_model) * rows * spec.Cout * hot  # boundary 3
    g = 0 if (prepared or plan.replicate_kernel_transform) \
        else 2 * P_pad * spec.C * (spec.Cout // n_model) * 4  # boundary 2
    return d + z + g


def _profile_from_trace(plan, jaxpr, counts, *, prepared: bool):
    import numpy as np
    from repro.conv import registry
    colls = {name: 0 for name in COLLECTIVES}
    coll_dtypes: Dict[str, Dict[str, int]] = {}
    coll_bytes = 0
    f64 = [False]

    def visit(eqn):
        name = eqn.primitive.name
        for v in (*eqn.invars, *eqn.outvars):
            aval = getattr(v, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is not None and np.dtype(dt).itemsize == 8 and \
                    np.issubdtype(np.dtype(dt), np.floating):
                f64[0] = True
        if name in colls:
            colls[name] += 1
            nonlocal coll_bytes
            for v in eqn.invars:
                aval = getattr(v, "aval", None)
                coll_bytes += _aval_bytes(aval)
            dt = _canon_dtype(getattr(eqn.invars[0].aval, "dtype", None))
            if dt is not None:
                coll_dtypes.setdefault(name, {})
                coll_dtypes[name][dt] = coll_dtypes[name].get(dt, 0) + 1

    n_eqns = [0]

    def visit_all(eqn):
        n_eqns[0] += 1
        visit(eqn)

    _walk(jaxpr.jaxpr, visit_all)
    stage_counts = {k: v for k, v in counts.items() if isinstance(k, str)}
    cgemm_dtypes = tuple(sorted(
        k[1] for k in counts if isinstance(k, tuple) and k[0] == "cgemm_dtype"
    ))
    cgemm_shapes = tuple(sorted(
        k[1] for k in counts if isinstance(k, tuple) and k[0] == "cgemm_shape"
    ))
    strides = tuple(sorted(
        k[1] for k in counts if isinstance(k, tuple) and k[0] == "stride"))
    be = registry.get_backend(plan.backend)
    return PlanProfile(
        backend=plan.backend, schedule=plan.schedule, prepared=prepared,
        is_pipeline=be.pipeline_factory is not None,
        replicate_kernel_transform=plan.replicate_kernel_transform,
        epilogue=plan.epilogue.describe(),
        compute_dtype=_canon_dtype(plan.compute_dtype),
        collectives=colls, collective_dtypes=coll_dtypes,
        collective_bytes=coll_bytes, stage_counts=stage_counts,
        cgemm_dtypes=cgemm_dtypes, has_f64=f64[0],
        peak_live_bytes=_peak_live_bytes(jaxpr.jaxpr), n_eqns=n_eqns[0],
        counted_collective_bytes=counted_collective_bytes(
            plan, prepared=prepared),
        overlap=getattr(plan, "overlap", "off"),
        num_slabs=getattr(plan, "num_slabs", 1),
        blocks=(plan.bm, plan.bn, plan.bk), cgemm_shapes=cgemm_shapes,
        strides=strides)


def analyze(target, *, prepared: bool = False) -> PlanProfile:
    """Statically analyze a ``ConvPlan``, ``PreparedConv`` or
    ``NetworkPlan`` into a structured profile (no conv FLOPs run — the
    plan is traced abstractly and the equation tree is walked).

    ``analyze(plan)`` profiles the one-shot path; ``analyze(plan,
    prepared=True)`` profiles the prepared-execute path with the kernel
    layout derived abstractly; ``analyze(prepared_conv)`` profiles an
    existing prepared plan.  Evaluate the invariant registry with
    ``analyze(...).check()``.
    """
    from repro.conv.netplan import NetworkPlan
    from repro.conv.plan import ConvPlan, PreparedConv
    if isinstance(target, NetworkPlan):
        return target.analyze()
    if isinstance(target, PreparedConv):
        plan, state, prepared = target.plan, target.state, True
    elif isinstance(target, ConvPlan):
        plan, state = target, None
    else:
        raise TypeError(
            f"analyze() takes a ConvPlan, PreparedConv or NetworkPlan; "
            f"got {type(target).__name__}")

    if not prepared:
        jaxpr, counts = _trace_full(plan)
        profile = _profile_from_trace(plan, jaxpr, counts, prepared=False)
    else:
        jaxpr, counts = _trace_prepared(plan, state)
        profile = _profile_from_trace(plan, jaxpr, counts, prepared=True)
        full = _profile_from_trace(plan, *_trace_full(plan), prepared=False)
        elision = {
            name: full.collectives.get(name, 0)
            - profile.collectives.get(name, 0) for name in COLLECTIVES}
        elision["kernel_transform"] = \
            full.stage_counts.get("kernel_transform", 0) \
            - profile.stage_counts.get("kernel_transform", 0)
        profile = dataclasses.replace(profile, elision=elision)

    if not plan.epilogue.is_noop:
        from repro.conv.epilogue import Epilogue
        bare = dataclasses.replace(plan, epilogue=Epilogue())
        if prepared:
            bp = _profile_from_trace(bare, *_trace_prepared(bare),
                                     prepared=True)
        else:
            bp = _profile_from_trace(bare, *_trace_full(bare),
                                     prepared=False)
        delta = {
            "collectives": {
                n: profile.collectives.get(n, 0) - bp.collectives.get(n, 0)
                for n in COLLECTIVES},
            "stage_counts": {
                n: profile.stage_counts.get(n, 0)
                - bp.stage_counts.get(n, 0)
                for n in set(profile.stage_counts) | set(bp.stage_counts)},
        }
        profile = dataclasses.replace(profile, epilogue_delta=delta)

    # Overlapped plans get a bytes-parity profile against their sequential
    # twin (same plan, overlap="off"): the sub-slab collectives must
    # repartition the rows the synchronous path moves, never re-send them.
    if profile.is_pipeline and profile.num_slabs > 1:
        seq = dataclasses.replace(plan, overlap="off")
        if prepared:
            sq = _profile_from_trace(seq, *_trace_prepared(seq),
                                     prepared=True)
        else:
            sq = _profile_from_trace(seq, *_trace_full(seq), prepared=False)
        ratio = (profile.collective_bytes / sq.collective_bytes
                 if sq.collective_bytes else None)
        profile = dataclasses.replace(profile, overlap_delta={
            "collective_bytes": profile.collective_bytes,
            "twin_collective_bytes": sq.collective_bytes,
            "ratio": ratio,
            "collectives": dict(profile.collectives),
            "twin_collectives": dict(sq.collectives)})
    return profile


# --------------------------------------------------------------------------
# Seeded violations (negative testing of the gate itself)
# --------------------------------------------------------------------------

VIOLATION_MODES = ("extra-collective", "extra-stage", "skip-cast",
                   "rfft-unpacked", "overlap-oversend", "strided-fft")


@contextlib.contextmanager
def seeded_violation(mode: str = "extra-collective"):
    """Deliberately break the stage pipelines so ``--check`` has something
    to catch (negative self-test of the gate; never use outside tests).

      extra-collective  every nfft boundary all-to-all also psums (the
                        hot path gains reductions it must not have);
      extra-stage       the kernel transform runs twice per trace;
      skip-cast         compute_dtype casts silently dropped (collectives
                        move full-width bytes again);
      rfft-unpacked     the folded forward keeps the whole rect
                        half-plane (delta x (delta//2+1) points) — the
                        plans ship the redundant self-conjugate rows
                        again and the collective-bytes invariants must
                        trip;
      overlap-oversend  every sub-slab collective pads its M rows 2x
                        before the wire and slices back after — only
                        overlapped plans are hit (the sequential twin is
                        untouched), so the overlap-bytes-parity invariant
                        must trip;
      strided-fft       the planner stops sending strided layers to
                        ``direct``: an FFT backend accepts ``stride > 1``
                        (and computes the wrong outputs), so the
                        no-strided-fft invariant must trip.
    """
    from repro.conv import stages
    if mode == "overlap-oversend":
        import jax.numpy as jnp
        orig_a2a = stages._slab_a2a
        orig_psum = stages._slab_psum

        def _oversend(T):
            return jnp.concatenate([T, jnp.zeros_like(T)], axis=1)

        def broken_a2a(Tr, Ti, axis_name, split, concat):
            m = Tr.shape[1]          # M rides axis 1 across both boundaries
            Tr, Ti = orig_a2a(_oversend(Tr), _oversend(Ti), axis_name,
                              split, concat)
            return Tr[:, :m], Ti[:, :m]

        def broken_psum(Zr, Zi, axis_name):
            m = Zr.shape[1]
            Zr, Zi = orig_psum(_oversend(Zr), _oversend(Zi), axis_name)
            return Zr[:, :m], Zi[:, :m]

        stages._slab_a2a = broken_a2a
        stages._slab_psum = broken_psum
        try:
            yield
        finally:
            stages._slab_a2a = orig_a2a
            stages._slab_psum = orig_psum
    elif mode == "extra-collective":
        import jax
        orig = stages._boundary_a2a

        def broken(Tr, Ti, axis_name, split, concat):
            Tr, Ti = orig(Tr, Ti, axis_name, split, concat)
            return jax.lax.psum(Tr, axis_name), jax.lax.psum(Ti, axis_name)

        stages._boundary_a2a = broken
        try:
            yield
        finally:
            stages._boundary_a2a = orig
    elif mode == "extra-stage":
        orig = stages.stage_kernel_transform

        def broken(k, spec):
            orig(k, spec)
            return orig(k, spec)

        stages.stage_kernel_transform = broken
        try:
            yield
        finally:
            stages.stage_kernel_transform = orig
    elif mode == "rfft-unpacked":
        import jax.numpy as jnp
        from repro.core import dft

        orig = dft.compact_forward_mat

        def broken(delta):
            # the rect half-plane's points, redundant conjugate rows and
            # all: shape-consistent downstream (the folded inverse reads a
            # prefix) but those rows ride every collective again
            return jnp.asarray(dft._folded_forward_np(delta, compact=False))

        dft.compact_forward_mat = broken
        try:
            yield
        finally:
            dft.compact_forward_mat = orig
    elif mode == "strided-fft":
        from repro.conv import plan as plan_mod
        orig = plan_mod._direct_only

        def broken(kh, kw, delta, stride):
            return orig(kh, kw, delta, 1)

        plan_mod._direct_only = broken
        try:
            yield
        finally:
            plan_mod._direct_only = orig
    elif mode == "skip-cast":
        orig = stages._maybe_cast

        def broken(pair, dtype):
            return pair

        stages._maybe_cast = broken
        try:
            yield
        finally:
            stages._maybe_cast = orig
    else:
        raise ValueError(
            f"unknown violation mode {mode!r}; known: {VIOLATION_MODES}")


# --------------------------------------------------------------------------
# CLI: sweep every backend x schedule over the paper geometries
# --------------------------------------------------------------------------

def _paper_geometries(batch: int, limit: Optional[int] = None):
    """Table-I layers as (name, x_shape, k_shape, padding).  Structure is
    batch-invariant, so the sweep uses a small batch to keep tracing
    fast; ``limit`` trims the set for quick runs."""
    from repro.configs.paper_convs import TABLE1
    layers = TABLE1[:limit] if limit else TABLE1
    return [(l.name, (batch, l.C, l.H, l.W), (l.Cout, l.C, l.kh, l.kw),
             l.pad) for l in layers]


def sweep(*, batch: int = 4, limit: Optional[int] = None,
          compute_dtype="bfloat16", progress=print, pairs=None):
    """Profile + check every registered backend x schedule pair over the
    paper geometries x {full, prepared, fused-epilogue, compute-dtype,
    strided, overlapped (slab:2)} variants.  Returns
    ``(profiles, violations)`` where ``profiles`` maps
    ``"backend/schedule/layer/variant"`` to a ``PlanProfile``.  ``pairs``
    restricts the sweep to a subset of (backend, schedule) pairs — the
    ``--jobs`` process-parallel tracer partitions the registry this way."""
    import jax.numpy as jnp
    from repro.compat import make_mesh
    from repro.conv import plan as plan_mod
    from repro.conv import registry
    from repro.conv.epilogue import Epilogue
    from repro.conv.plan import plan_conv

    mesh = None
    profiles: Dict[str, PlanProfile] = {}
    violations: List[Tuple[str, Violation]] = []
    cdt = jnp.dtype(compute_dtype) if compute_dtype else None
    if pairs is None:
        pairs = registry.backend_schedule_pairs()
    for backend, schedule in pairs:
        needs_mesh = registry.get_schedule(schedule).requires_mesh
        if needs_mesh and mesh is None:
            mesh = make_mesh((1, 1), ("data", "model"))
        for name, x_shape, k_shape, padding in _paper_geometries(batch,
                                                                 limit):
            base = dict(padding=padding, backend=backend, schedule=schedule,
                        mesh=mesh if needs_mesh else None)
            variants = [
                ("full", {}, False),
                ("prepared", {}, True),
                ("epilogue",
                 {"epilogue": Epilogue(bias=True, activation="relu")},
                 False),
            ]
            if cdt is not None:
                variants.append(("cdtype", {"compute_dtype": cdt}, False))
            # a strided layer, where the planner allows one: on direct,
            # and on a pipeline only when _direct_only stops refusing it
            # (--inject strided-fft), which must trip no-strided-fft
            pipeline = registry.get_backend(backend).pipeline_factory
            if pipeline is None or plan_mod._direct_only(
                    k_shape[2], k_shape[3], 16, 2) is None:
                variants.append(("stride2", {"stride": 2}, False))
            if pipeline is not None and needs_mesh:
                # overlapped sub-slab execution: slab-scaled collective
                # counts + bytes parity vs the sequential twin
                variants.append(("overlap", {"overlap": "slab:2"}, False))
            for variant, extra, as_prepared in variants:
                key = f"{backend}/{schedule}/{name}/{variant}"
                plan = plan_conv(x_shape, k_shape, **base, **extra)
                profile = analyze(plan, prepared=as_prepared)
                profiles[key] = profile
                report = profile.check()
                for v in report.violations:
                    violations.append((key, v))
                    progress(f"VIOLATION {key}: {v}")
    return profiles, violations


def _sweep_worker(payload):
    """Module-level (picklable) worker for ``--jobs``: sweep a subset of
    the backend x schedule pairs in a spawned process, returning plain
    JSON-able results (profiles as dicts, violations as tuples)."""
    pairs, batch, limit, inject = payload
    ctx = seeded_violation(inject) if inject else contextlib.nullcontext()
    with ctx:
        profiles, violations = sweep(batch=batch, limit=limit, pairs=pairs,
                                     progress=lambda s: None)
    return ({k: p.to_dict() for k, p in profiles.items()},
            [(k, v.invariant, v.message) for k, v in violations])


def _sweep_parallel(jobs: int, batch: int, limit, inject):
    """Partition the registered pairs round-robin over ``jobs`` spawned
    processes (each re-imports jax cleanly — seeded violations are applied
    inside the worker, after its own module state exists)."""
    import multiprocessing as mp
    from repro.conv import registry
    pairs = list(registry.backend_schedule_pairs())
    chunks = [c for c in (pairs[i::jobs] for i in range(jobs)) if c]
    ctx = mp.get_context("spawn")
    with ctx.Pool(processes=len(chunks)) as pool:
        results = pool.map(_sweep_worker,
                           [(c, batch, limit, inject) for c in chunks])
    payload: Dict[str, dict] = {}
    violations: List[Tuple[str, str, str]] = []
    for prof, viols in results:
        payload.update(prof)
        violations.extend(viols)
    return payload, violations


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.conv.analyze",
        description="Plan-lint: certify the conv engine's structural "
                    "invariants (collectives / dtype flow / fusion) for "
                    "every registered backend x schedule.")
    ap.add_argument("--check", action="store_true",
                    help="sweep backend x schedule x paper geometries and "
                         "exit non-zero on any violated invariant")
    ap.add_argument("--batch", type=int, default=4,
                    help="trace batch size (structure is batch-invariant)")
    ap.add_argument("--limit", type=int, default=None,
                    help="only the first N Table-I geometries")
    ap.add_argument("--json-out", default="",
                    help="write every profile as JSON to this path")
    ap.add_argument("--inject", choices=VIOLATION_MODES, default=None,
                    help="seed a deliberate pipeline violation first "
                         "(negative self-test: --check must then FAIL)")
    ap.add_argument("--jobs", type=int, default=1,
                    help="process-parallel tracing: partition the backend "
                         "x schedule pairs over N spawned workers (the "
                         "full sweep is tracing-bound)")
    args = ap.parse_args(argv)
    if not args.check and not args.json_out:
        ap.print_help()
        return 2

    if args.jobs > 1:
        payload, raw_violations = _sweep_parallel(
            args.jobs, args.batch, args.limit, args.inject)
        for key, inv, msg in raw_violations:
            print(f"VIOLATION {key}: [{inv}] {msg}")
        n_violations = len(raw_violations)
    else:
        ctx = seeded_violation(args.inject) if args.inject \
            else contextlib.nullcontext()
        with ctx:
            profiles, violations = sweep(batch=args.batch, limit=args.limit)
        payload = {k: p.to_dict() for k, p in profiles.items()}
        n_violations = len(violations)

    if args.json_out:
        with open(args.json_out, "w") as fh:
            json.dump(payload, fh, indent=1, sort_keys=True)
        print(f"# wrote {len(payload)} profiles to {args.json_out}")

    n = len(payload)
    if n_violations:
        print(f"plan-lint: {n_violations} violation(s) across "
              f"{n} profiles", file=sys.stderr)
        return 1
    print(f"plan-lint: OK — {n} profiles, 0 violations "
          f"(invariants certified for "
          f"{len({(d['backend'], d['schedule']) for d in payload.values()})} "
          f"backend x schedule pairs)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
