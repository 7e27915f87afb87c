"""The trace reducer on a synthetic trace: busy union, idle share,
named-event time, gaps by host span, the parsing of HLO event names."""

import pytest

from benchmark import xplane
from benchmark.xplane import Op, Span, Trace


def make_trace():
    ops = [
        Op(0, "fusion.1", 0.0, 1.0, "fusion", "kOutput"),
        Op(0, "fusion.2", 0.5, 1.5, "fusion", "kLoop"),       # overlaps
        Op(0, "copy.7", 3.0, 4.0, "copy", ""),
        Op(0, "self_attention.3", 4.0, 6.0, "custom-call", ""),
        Op(0, "copy.9", 9.0, 10.0, "copy", ""),
    ]
    spans = [Span("step.dispatch", 1.4, 2.0), Span("loss.fetch", 2.0, 9.5)]
    return Trace(ops, spans)


def test_union_and_gaps():
    assert xplane.union_seconds([(0, 1), (0.5, 1.5), (3, 4)]) == 2.5
    assert xplane.union_seconds([]) == 0.0
    assert xplane.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert xplane.gaps([(0, 5)], 0, 5) == []


def test_busy_idle_and_named_time():
    tr = make_trace()
    assert tr.window == (0.0, 10.0) and tr.window_s == 10.0
    assert tr.busy_s() == pytest.approx(1.5 + 1.0 + 2.0 + 1.0)
    idle_share = 1.0 - tr.busy_s() / tr.window_s
    assert idle_share == pytest.approx(0.45)
    assert tr.seconds_in(lambda op: op.name.startswith("copy")) == 2.0
    assert tr.seconds_in(lambda op: op.opcode == "custom-call"
                         and "self_attention" in op.name) == 2.0
    assert tr.seconds_in(lambda op: op.kind == "kOutput") == 1.0
    assert tr.seconds_in(lambda op: False) == 0.0


def test_top_ops_group_by_stable_name():
    top = dict((name, s) for name, s in make_trace().top_ops(10))
    assert top["copy"] == 2.0
    assert top["custom-call:self_attention"] == 2.0
    assert top["fusion.kOutput:fusion"] == 1.0
    assert len(make_trace().top_ops(2)) == 2


def test_idle_gaps_are_charged_to_the_host_span():
    gaps = make_trace().idle_gaps()
    by_span = {name.split(" (")[0]: seconds for name, seconds in gaps}
    # 1.5-3.0 begins under step.dispatch; 6.0-9.0 under loss.fetch
    assert by_span["step.dispatch"] == pytest.approx(1.5)
    assert by_span["loss.fetch"] == pytest.approx(3.0)
    assert sum(by_span.values()) == pytest.approx(4.5)


def test_two_devices_average():
    ops = [Op(0, "a.1", 0.0, 2.0), Op(1, "a.1", 0.0, 1.0)]
    tr = Trace(ops, [])
    assert tr.devices == [0, 1]
    assert tr.busy_s() == pytest.approx(1.5)


def test_empty_trace_reads_nothing():
    tr = Trace([], [])
    assert tr.busy_s() == 0.0 and tr.top_ops() == [] and tr.idle_gaps() == []


@pytest.mark.parametrize("text,want", [
    ('%self_attention.99 = (bf16[256,1024,64]{2,1,0:T(8,128)(2,1)}, '
     'f32[256,1024,1]{2,1,0:T(8,128)}) custom-call(bf16[256,1024,64]'
     '{2,1,0:T(8,128)(2,1)} %bitcast.3290), custom_call_target="tpu"',
     ("self_attention.99", "custom-call", "")),
    ('%convert_reduce_fusion.101 = (f32[1024,16]{0,1:T(8,128)S(1)}) '
     'fusion(bf16[1024] %x), kind=kOutput, calls=%fused_computation.3',
     ("convert_reduce_fusion.101", "fusion", "kOutput")),
    ('%copy.3583 = s32[2,8,8,128]{3,1,2,0:T(8,128)S(1)} copy(s32[2,8] '
     '%fusion.3834)', ("copy.3583", "copy", "")),
    ("step.dispatch", ("step.dispatch", "", "")),
])
def test_parse_hlo_event_names(text, want):
    assert xplane.parse_hlo(text) == want
