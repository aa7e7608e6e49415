"""The stage reduction: device time per conv stage scope, per layer, and
the host event under each idle gap, on hand-made ops and on the recorded
TPU trace (``fixtures/tiny_chain.xplane.pb``, whose program opened no
stage scope)."""
import os
import shutil

import pytest

from bench import stages as stages_tool
from bench.lib import stages, trace
from bench.lib.trace import Op, Span

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
MS = 1_000_000
PB = os.path.join(FIXTURES, "tiny_chain.xplane.pb")

# instruction -> stage, as ``stage_map`` reads it from a compiled HLO
STAGE_OF = {"fusion.1": "input_transform", "fusion.2": "input_transform",
            "convolution.3": "cgemm", "fusion.4": "output_inverse",
            "convolution.6": "direct"}


def _hand():
    ops = [Op("d0", "fusion.1", 0, 10 * MS, "L1"),
           Op("d0", "fusion.2", 5 * MS, 15 * MS, "L1"),
           Op("d0", "convolution.3", 15 * MS, 30 * MS, "L1"),
           Op("d0", "fusion.4", 30 * MS, 40 * MS, "L1"),
           Op("d0", "copy.5", 40 * MS, 45 * MS, "L1"),
           Op("d0", "convolution.6", 50 * MS, 60 * MS, "L2"),
           Op("d0", "reduce-window.7", 60 * MS, 62 * MS, "")]
    spans = [Span("bench:window", 0, 100 * MS),
             Span("bench:dispatch", 0, 10 * MS),
             Span("bench:block", 10 * MS, 100 * MS)]
    return ops, spans


def _host():
    return [Span("main/CommonPjRtLoadedExecutable::Execute", 44 * MS,
                 52 * MS),
            Span("futex-default-SDomainT/tpu::System::Execute=>Done", 0,
                 47 * MS),
            Span("main/Wait", 70 * MS, 100 * MS)]


def _recorded():
    with open(os.path.join(FIXTURES, "tiny_chain.hlo.txt")) as f:
        hlo = f.read()
    ops, spans = trace.load(PB, ["c1", "c2", "c3"], [hlo])
    return ops, spans, hlo


def test_stage_unions_per_stage_and_layer():
    r = stages.reduce(*_hand(), STAGE_OF)
    # fusion.1 [0,10) and fusion.2 [5,15) overlap: 15 ms, not 20
    assert r["stage_s"] == {"input_transform": pytest.approx(0.015),
                            "cgemm": pytest.approx(0.015),
                            "output_inverse": pytest.approx(0.010),
                            "direct": pytest.approx(0.010)}
    assert r["layer_stage_s"] == {
        "L1": {"cgemm": pytest.approx(0.015),
               "input_transform": pytest.approx(0.015),
               "output_inverse": pytest.approx(0.010)},
        "L2": {"direct": pytest.approx(0.010)}}
    # copy.5 is L1's but under no stage
    assert r["layer_unstaged_s"] == {"L1": pytest.approx(0.005),
                                     "L2": pytest.approx(0.0)}
    # L1 45 ms + L2 10 ms of layer time, 5 ms of it under no stage
    scope_s = trace.reduce(*_hand())["scope_s"]
    assert stages.staged_share(r, scope_s) == pytest.approx(100 * 50 / 55)


def test_stage_ms_per_step_zero_and_none():
    r = stages.reduce(*_hand(), STAGE_OF)
    assert stages.stage_ms(r, "cgemm", 2) == pytest.approx(7.5)
    assert stages.stage_ms(r, "direct", 2) == pytest.approx(5.0)
    no_direct = stages.reduce(*_hand(), {k: v for k, v in STAGE_OF.items()
                                         if v != "direct"})
    assert stages.stage_ms(no_direct, "direct", 2) == 0.0
    # the window holds ops, none of them under a stage: 0, not None
    unstaged = stages.reduce(*_hand(), {})
    assert stages.stage_ms(unstaged, "cgemm", 2) == 0.0
    assert stages.stage_ms(None, "cgemm", 2) is None
    assert stages.stage_ms(r, "cgemm", 0) is None
    ops, spans = _hand()
    assert stages.reduce(ops, spans[1:], STAGE_OF) is None   # no window
    assert stages.reduce([], spans, STAGE_OF) is None


def test_idle_gap_names_the_host_event_that_overlaps_most():
    r = stages.reduce(*_hand(), STAGE_OF, host=_host())
    # gaps [45,50) ms (Execute overlaps 5, Done 2) and [62,100) (Wait 30);
    # [60,62) is no gap and the 0-length ones are under 1 ms
    assert r["idle_gap_host"] == {
        "top": [["main/Wait", pytest.approx(0.038), 1],
                ["main/CommonPjRtLoadedExecutable::Execute",
                 pytest.approx(0.005), 1]],
        "longest": ["main/Wait", pytest.approx(0.038)]}
    quiet = stages.reduce(*_hand(), STAGE_OF)
    assert quiet["idle_gap_host"]["top"][0][0] == "other"


def test_recorded_trace_has_no_stage_and_names_runtime_threads():
    ops, spans, hlo = _recorded()
    assert stages.stage_map([hlo]) == {}
    host = stages.host_events(PB)
    r = stages.reduce(ops, spans, stages.stage_map([hlo]), host)
    assert r["stage_s"] == dict.fromkeys(stages.STAGES, 0.0)
    assert r["layer_stage_s"] == {}
    assert r["layer_unstaged_s"] == pytest.approx(
        trace.reduce(ops, spans)["scope_s"])
    for stage in stages.STAGES:
        assert stages.stage_ms(r, stage, 29) == 0.0
    names = {h.name for h in host}
    assert "main/CommonPjRtLoadedExecutable::Execute" in names
    assert "futex-default-SDomainT/tpu::System::Execute=>Done" in names
    assert not any(n.split("/", 1)[1].startswith(("$", "bench:"))
                   for n in names)
    assert all(g[1] >= 0.001 for g in r["idle_gap_host"]["top"])


def test_stage_map_reads_stage_components_of_op_names():
    hlo = "\n".join([
        '  %fusion.1 = f32[2] fusion(), metadata={op_name='
        '"jit(fwd)/conv1_2/input_transform/dot_general"}',
        '  ROOT %convolution.3 = f32[2] convolution(), metadata={op_name='
        '"jit(fwd)/conv1_2/cgemm/dot_general"}',
        '  %copy.5 = f32[2] copy(), metadata={op_name="jit(fwd)/conv1_2/'
        'transpose"}'])
    assert stages.stage_map([hlo]) == {"fusion.1": "input_transform",
                                       "convolution.3": "cgemm"}


def test_tool_reading_of_the_recorded_trace(tmp_path):
    d = tmp_path / "trace"
    d.mkdir()
    shutil.copy(PB, d / "tiny_chain.xplane.pb")
    with open(os.path.join(FIXTURES, "tiny_chain.hlo.txt")) as f:
        hlo = f.read()
    ctx = {"trace_files": [str(d / "tiny_chain.xplane.pb"), str(d)],
           "hlo_texts": [hlo], "steps": 29, "images": 58, "window_s": 0.02}
    out = stages_tool.reading(ctx, ["c1", "c2", "c3"],
                              {"c1": "fft-xla", "c2": "direct"})
    assert out["stage_ms"] == dict.fromkeys(stages.STAGES, 0.0)
    assert out["staged_share"] == pytest.approx(0.0)
    assert set(out["unstaged_s"]) == {"c1"}
    assert out["images_per_s"] == pytest.approx(2900.0)
    assert out["scope_s"] == trace.reduce(*trace.load(
        PB, ["c1", "c2", "c3"], [hlo]))["scope_s"]
    ctx["trace_files"] = [str(d)]
    assert stages_tool.reading(ctx, ["c1"], {}) == {"trace": None}
