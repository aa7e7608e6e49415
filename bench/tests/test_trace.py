"""The trace reduction, on hand-made events and on a trace recorded on a
TPU v5e (``fixtures/tiny_chain.xplane.pb``: the tiny chain's closed loop
through the program, traced for 20 ms, with the compiled HLO of its step
in ``fixtures/tiny_chain.hlo.txt``)."""
import os

import pytest

from bench.lib import trace
from bench.lib.trace import Op, Span

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def _spans():
    return [Span("bench:window", 0, 50), Span("bench:dispatch", 0, 22),
            Span("bench:block", 22, 45)]


def test_reduce_by_hand():
    ops = [Op("d0", "fusion.3", 0, 10, "c1"),
           Op("d0", "fusion.4", 5, 20, "c2"),
           Op("d0", "fusion.5", 8, 12, "c2"),       # overlaps fusion.4
           Op("d0", "copy.1", 30, 40, ""),
           Op("d0", "copy.2", 55, 60, "")]           # after the window
    r = trace.reduce(ops, _spans())
    assert r["window_s"] == pytest.approx(50e-9)
    assert r["busy_s"] == pytest.approx(30e-9)      # [0,20) and [30,40)
    assert r["scope_s"] == {"c1": pytest.approx(10e-9),
                            "c2": pytest.approx(15e-9)}
    # gaps [20,30) (mostly under block) and [40,50) (block)
    assert r["idle_gaps"] == [["block", pytest.approx(20e-9)]]
    assert dict(r["device_ops"]) == {"c2/fusion.4": pytest.approx(15e-9),
                                     "c1/fusion.3": pytest.approx(10e-9),
                                     "copy.1": pytest.approx(10e-9),
                                     "c2/fusion.5": pytest.approx(4e-9)}
    assert trace.idle_share(r) == pytest.approx(40.0)


def test_reduce_averages_devices_and_clips_to_the_window():
    ops = [Op("d0", "a", -10, 50, ""), Op("d1", "a", 10, 20, "")]
    r = trace.reduce(ops, _spans())
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((50 + 10) / 2 * 1e-9)


def test_no_window_or_no_device_op_reads_nothing():
    assert trace.reduce([], _spans()) is None
    assert trace.reduce([Op("d0", "a", 0, 1, "")], []) is None
    assert trace.idle_share(None) is None


def test_scope_is_a_path_component():
    scopes = {"conv1_2", "Rconv2.2"}
    assert trace._scope_of("jit(fwd)/conv1_2/dot_general", scopes) == \
        "conv1_2"
    assert trace._scope_of("jit(f)/Rconv2.2/add", scopes) == "Rconv2.2"
    assert trace._scope_of("jit(f)/conv1_22/add", scopes) == ""


def test_scope_map_reads_op_name_metadata():
    hlo = "\n".join([
        '  %fusion.2 = f32[3,16]{1,0} fusion(f32[3,16]{1,0} %slice.8), '
        'kind=kLoop, metadata={op_name="jit(fwd)/c1/conv_general_dilated"}',
        '  ROOT %reduce-window = f32[8]{0} reduce-window(%x), '
        'metadata={op_name="jit(fwd)/pool/reduce_window_max"}',
        '  %copy-start = (f32[8]{0}) copy-start(%p)'])
    assert trace.scope_map([hlo], {"c1", "c2"}) == {"fusion.2": "c1"}


def test_recorded_tpu_trace():
    with open(os.path.join(FIXTURES, "tiny_chain.hlo.txt")) as f:
        hlo = f.read()
    ops, spans = trace.load(os.path.join(FIXTURES, "tiny_chain.xplane.pb"),
                            ["c1", "c2", "c3"], [hlo])
    r = trace.reduce(ops, spans)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"] < 0.03
    assert set(r["scope_s"]) == {"c1", "c2", "c3"}
    assert sum(r["scope_s"].values()) <= r["busy_s"] * 1.0001
    assert {name for name, _ in r["idle_gaps"]} <= {
        "dispatch", "block", "other"}
    # without the HLO, a TPU trace names no scope: its ops carry no
    # op_name of their own
    ops, _ = trace.load(os.path.join(FIXTURES, "tiny_chain.xplane.pb"),
                        ["c1", "c2", "c3"])
    assert not any(o.scope for o in ops)
