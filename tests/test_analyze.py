"""Static analyzer (plan-lint) acceptance: every registered
backend x schedule pair certified against the invariant registry (full,
prepared and fused-epilogue variants), dtype-flow facts, prepared-plan
elision, network-wide aggregation, the seeded-violation negative path
(the gate must FAIL when a pipeline is deliberately broken), and the
``python -m repro.conv.analyze`` CLI exit codes."""
import json

import jax.numpy as jnp
import pytest

from repro.compat import make_mesh
from repro.conv import (
    Epilogue, NetworkConv, PlanProfile, analyze, backend_schedule_pairs,
    invariants_for, plan_conv, plan_network, register_invariant,
)
from repro.conv.analyze import (
    _REGISTRY, VIOLATION_MODES, main, seeded_violation,
)

# collected at import time: the builtin pairs only (tests that register
# extra backends run later and must not widen this grid)
PAIRS = backend_schedule_pairs()


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


def _plan(backend, schedule, **kw):
    mesh = _mesh() if schedule != "local" else None
    return plan_conv((2, 3, 18, 18), (4, 3, 3, 3), padding=1,
                     backend=backend, schedule=schedule, mesh=mesh, **kw)


# --------------------------------------------------------------------------
# Every registered pair certifies, in every variant
# --------------------------------------------------------------------------

@pytest.mark.parametrize("variant", ["full", "prepared", "epilogue"])
@pytest.mark.parametrize("backend,schedule", PAIRS,
                         ids=[f"{b}-{s}" for b, s in PAIRS])
def test_every_pair_certifies(backend, schedule, variant):
    kw = {}
    if variant == "epilogue":
        kw["epilogue"] = Epilogue(bias=True, activation="relu")
    plan = _plan(backend, schedule, **kw)
    profile = analyze(plan, prepared=(variant == "prepared"))
    assert isinstance(profile, PlanProfile)
    profile.check().raise_if_failed()
    assert profile.n_eqns > 0
    assert profile.peak_live_bytes > 0
    if variant == "prepared":
        assert profile.prepared
        assert profile.elision is not None
    else:
        assert not profile.prepared
    if variant == "epilogue":
        assert profile.epilogue_delta is not None
    else:
        assert profile.epilogue_delta is None


def test_analyze_existing_prepared_conv():
    """analyze(PreparedConv) profiles the already-bound prepared state."""
    import numpy as np
    plan = _plan("fft-xla", "nfft")
    k = jnp.asarray(np.random.default_rng(0).standard_normal(plan.k_shape),
                    jnp.float32)
    profile = analyze(plan.prepare(k))
    assert profile.prepared
    assert profile.collectives["all_to_all"] == 4
    assert profile.stage_counts.get("kernel_transform", 0) == 0
    profile.check().raise_if_failed()


def test_analyze_rejects_non_plans():
    with pytest.raises(TypeError, match="ConvPlan"):
        analyze(object())


# --------------------------------------------------------------------------
# Collective / dtype-flow facts (the paper's structural claims)
# --------------------------------------------------------------------------

def test_nfft_collective_and_dtype_facts():
    """nfft pays one a2a pair per live stage boundary; with bf16 compute
    the D and Z boundary pairs (4 eqns) move half-width bytes while the
    kernel boundary stays f32."""
    p32 = analyze(_plan("fft-xla", "nfft"))
    assert p32.collectives == {"all_to_all": 6, "psum": 0, "ppermute": 0,
                               "all_gather": 0}
    p16 = analyze(_plan("fft-xla", "nfft", compute_dtype=jnp.bfloat16))
    assert p16.compute_dtype == "bfloat16"
    assert p16.cgemm_dtypes == ("bfloat16",)
    assert p16.collective_dtypes["all_to_all"] == {"bfloat16": 4,
                                                   "float32": 2}
    assert p16.collective_bytes < p32.collective_bytes  # casts shrink bytes
    assert not p16.has_f64 and not p32.has_f64
    p16.check().raise_if_failed()


def test_wfft_hot_psum_pair_in_compute_dtype():
    p = analyze(_plan("fft-pallas", "wfft", compute_dtype=jnp.bfloat16))
    assert p.collectives == {"all_to_all": 0, "psum": 2, "ppermute": 0,
                             "all_gather": 0}
    assert p.collective_dtypes["psum"] == {"bfloat16": 2}
    assert p.cgemm_dtypes == ("bfloat16",)
    p.check().raise_if_failed()


def test_prepared_and_replicated_elide_kernel_boundary():
    prep = analyze(_plan("fft-xla", "nfft"), prepared=True)
    assert prep.collectives["all_to_all"] == 4
    assert prep.elision == {"all_to_all": 2, "psum": 0, "ppermute": 0,
                            "all_gather": 0, "kernel_transform": 1}
    repl = analyze(_plan("fft-xla", "nfft",
                         replicate_kernel_transform=True))
    assert repl.collectives["all_to_all"] == 4
    repl.check().raise_if_failed()


def _counted_bytes(form, prepared, itemsize):
    """Collective operand bytes of ``_plan``'s geometry on a (1, 1) mesh,
    written out: (2, 3, 18, 18) * (4, 3, 3, 3) at pad 1 is 2 x 2 tiles
    of 14 outputs, so M = 2 * 4 rows; the compact spectrum at delta 16
    has 130 points; every (re, im) pair counts twice."""
    P, M, C, Co = 130, 8, 3, 4
    if form == "wfft":
        return 2 * P * M * Co * itemsize               # the hot psum pair
    d = 2 * P * M * C * itemsize                       # D boundary
    z = 2 * P * M * Co * itemsize                      # Z boundary
    g = 0 if prepared or form == "nfft-rkt" \
        else 2 * P * C * Co * 4                        # kernel, in f32
    return d + z + g


_BYTES_CASES = [(form, prepared, overlap, None)
                for form in ("nfft", "nfft-rkt", "wfft")
                for prepared in (False, True)
                for overlap in ("off", "slab:2")] \
    + [(form, False, "off", jnp.bfloat16)
       for form in ("nfft", "nfft-rkt", "wfft")]


@pytest.mark.parametrize("form,prepared,overlap,cdt", _BYTES_CASES)
def test_collective_bytes_match_the_count(form, prepared, overlap, cdt):
    """The traced collective bytes equal the geometry's count exactly, on
    every sharded form; the exact-bytes rule holds."""
    kw = {"overlap": overlap, "compute_dtype": cdt}
    if form == "nfft-rkt":
        kw["replicate_kernel_transform"] = True
    p = analyze(_plan("fft-xla", form[:4], **kw), prepared=prepared)
    want = _counted_bytes(form, prepared, 4 if cdt is None else 2)
    assert p.collective_bytes == p.counted_collective_bytes == want
    assert f"{form[:4]}-collective-bytes" not in \
        [v.invariant for v in p.check().violations]


def test_epilogue_delta_is_zero_everywhere():
    ep = Epilogue(bias=True, activation="silu", residual=True)
    p = analyze(_plan("fft-xla", "wfft", epilogue=ep))
    assert p.epilogue == ep.describe()
    assert all(v == 0 for v in p.epilogue_delta["collectives"].values())
    assert all(v == 0 for v in p.epilogue_delta["stage_counts"].values())


# --------------------------------------------------------------------------
# Invariant registry: wildcards, extension, custom rules
# --------------------------------------------------------------------------

def test_register_invariant_wildcard_merge():
    inv = register_invariant(
        "fft-xla", "local", "test-eqn-budget",
        lambda p: None if p.n_eqns < 10 ** 6 else "program too large",
        "session-local test rule")
    try:
        names = [i.name for i in invariants_for("fft-xla", "local")]
        assert "test-eqn-budget" in names
        assert "no-f64" in names                       # ("*", "*") merged in
        assert "test-eqn-budget" not in [
            i.name for i in invariants_for("fft-pallas", "local")]
        report = analyze(_plan("fft-xla", "local")).check()
        assert "test-eqn-budget" in report.checked
        assert report.ok
    finally:
        _REGISTRY[("fft-xla", "local")].remove(inv)


def test_check_extra_rules_and_failure_raises():
    from repro.conv.analyze import Invariant
    p = analyze(_plan("fft-xla", "local"))
    bad = Invariant("always-fails", lambda p: "boom")
    report = p.check(extra=[bad])
    assert not report.ok
    assert report.violations[0].invariant == "always-fails"
    with pytest.raises(AssertionError, match=r"(?s)plan-lint: .*always-fails"):
        report.raise_if_failed()


# --------------------------------------------------------------------------
# Network-wide aggregation
# --------------------------------------------------------------------------

def test_network_profile_aggregates_and_certifies():
    net = plan_network(
        [NetworkConv("c1", (2, 3, 18, 18), (4, 3, 3, 3), padding=1),
         NetworkConv("c2", (2, 4, 18, 18), (4, 4, 3, 3), padding=1,
                     epilogue=Epilogue(bias=True, activation="relu"))],
        backend="fft-xla", schedule="nfft", mesh=_mesh())
    prof = net.analyze()
    assert list(prof.layers) == ["c1", "c2"]
    assert prof.total_collectives["all_to_all"] == sum(
        p.collectives["all_to_all"] for p in prof.layers.values()) == 12
    assert prof.peak_live_bytes == max(
        p.peak_live_bytes for p in prof.layers.values())
    assert prof.check() == []
    assert prof.raise_if_failed() is prof
    d = prof.to_dict()
    assert set(d["layers"]) == {"c1", "c2"}
    json.dumps(d)                                  # artifact-serializable
    # analyze() dispatches NetworkPlan to the same path
    assert list(analyze(net).layers) == ["c1", "c2"]


# --------------------------------------------------------------------------
# Negative path: a deliberately broken pipeline MUST be caught
# --------------------------------------------------------------------------

@pytest.mark.parametrize("mode", VIOLATION_MODES)
def test_seeded_violation_is_caught(mode):
    kw = {"compute_dtype": jnp.bfloat16} if mode == "skip-cast" else {}
    if mode == "overlap-oversend":
        kw["overlap"] = "slab:2"       # only overlapped plans hit the slab ops
    if mode == "strided-fft":
        kw["stride"] = 2               # only strided plans reach the guard
    with seeded_violation(mode):
        p = analyze(plan_conv((2, 4, 22, 22), (4, 4, 3, 3), padding=1,
                              backend="fft-xla", schedule="nfft",
                              mesh=_mesh(), **kw))
    report = p.check()
    assert not report.ok
    if mode == "rfft-unpacked":
        # the rect half-plane ships 144 points where the count has 130
        assert "nfft-collective-bytes" in \
            [v.invariant for v in report.violations]
    with pytest.raises(AssertionError, match="plan-lint"):
        report.raise_if_failed()


def test_seeded_violation_unknown_mode_and_restore():
    from repro.conv import stages
    orig = stages._boundary_a2a
    with seeded_violation("extra-collective"):
        assert stages._boundary_a2a is not orig
    assert stages._boundary_a2a is orig            # restored on exit
    with pytest.raises(ValueError, match="unknown violation mode"):
        with seeded_violation("nope"):
            pass                                   # pragma: no cover


# --------------------------------------------------------------------------
# CLI gate (the CI entry point)
# --------------------------------------------------------------------------

def test_cli_check_passes_and_writes_json(tmp_path, capsys):
    out = tmp_path / "profiles.json"
    rc = main(["--check", "--limit", "1", "--batch", "2",
               "--json-out", str(out)])
    assert rc == 0
    assert "plan-lint: OK" in capsys.readouterr().out
    payload = json.loads(out.read_text())
    assert payload
    sample = payload[next(iter(payload))]
    for field in ("collectives", "stage_counts", "peak_live_bytes",
                  "cgemm_dtypes"):
        assert field in sample


def test_cli_seeded_violation_fails_the_gate(capsys):
    """Acceptance: the gate exits non-zero when an invariant is broken."""
    rc = main(["--check", "--limit", "1", "--batch", "2",
               "--inject", "extra-collective"])
    assert rc == 1
    assert "VIOLATION" in capsys.readouterr().out


def test_cli_without_action_exits_2(capsys):
    assert main([]) == 2


# --------------------------------------------------------------------------
# Canary: THE one retained string-based jaxpr check
# --------------------------------------------------------------------------

def test_string_canary_agrees_with_analyzer():
    """Deliberately kept string-based (the only such test left): if jax's
    pretty printer ever stops agreeing with the structural equation walk,
    this fails loudly and the analyzer needs a look.  Every other count
    assertion in the suite goes through ``repro.conv.analyze``."""
    import jax
    plan = _plan("fft-xla", "nfft")
    profile = analyze(plan)
    jaxpr = str(jax.make_jaxpr(lambda x, k: plan(x, k))(
        jnp.zeros(plan.x_shape, jnp.float32),
        jnp.zeros(plan.k_shape, jnp.float32)))
    assert jaxpr.count("all_to_all") \
        == profile.collectives["all_to_all"] == 6
