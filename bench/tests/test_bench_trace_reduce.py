"""The trace reduction, on hand-made intervals and on a hand-made
two-chip trace in the profiler's own format with known answers."""
from __future__ import annotations

import pytest

from bench import trace_reduce as tr


def test_union_and_subtract_by_hand():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert tr.subtract([(0, 10)], [[1, 2], [4, 6], [9, 12]]) == [
        (0, 1), (2, 4), (6, 9)]
    assert tr.subtract([(0, 3), (5, 8)], [[2, 6]]) == [(0, 2), (6, 8)]
    assert tr.clip([(0, 5), (6, 9), (20, 30)], 3, 8) == [(3, 5), (6, 8)]
    assert tr.total([(0, 2), (5, 8)]) == 5


# A hand-made two-chip trace with known answers (times in µs from 0),
# its op text on the op's metadata, where a TPU trace keeps it (a fusion
# that reads a kernel's output names it, and is no kernel):
# window 0-100; chip 0 runs a fusion 10-30 and a kernel 25-40 (union
# 10-40), an all-gather 60-70 with a fusion 65-80; chip 1, as a v5e trace
# names its ops (by the instruction's whole HLO text, with the category in
# a stat), a while 0-50 around a fusion 0-40 and a kernel 40-50.
# Host spans: bench.fit 0-55, bench.refresh 55-100.
HAND = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 25000000 duration_ps: 15000000 }
    events { metadata_id: 3 offset_ps: 60000000 duration_ps: 10000000 }
    events { metadata_id: 1 offset_ps: 65000000 duration_ps: 15000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 0 duration_ps: 100000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1"
    stats { metadata_id: 1 str_value: "fusion(%pallas_call.45)" } } }
  event_metadata { key: 2 value { id: 2 name: "jvp__.2"
    stats { metadata_id: 1 str_value: "custom_call_target=\\"tpu_custom_call\\"" } } }
  event_metadata { key: 3 value { id: 3 name: "all-gather.3" } }
  event_metadata { key: 4 value { id: 4 name: "jit_step" } }
  stat_metadata { key: 1 value { id: 1 name: "long_name" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 50000000 }
    events { metadata_id: 1 offset_ps: 0 duration_ps: 40000000 }
    events { metadata_id: 2 offset_ps: 40000000 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.9" } }
  event_metadata { key: 2 value { id: 2
    name: "%transpose_jvp___.8 = f32[8]{0} custom-call(f32[8]{0} %all-gather.2), custom_call_target=\\"tpu_custom_call\\""
    stats { metadata_id: 1 str_value: "custom-call" }
    stats { metadata_id: 2 str_value: "/w/src/repro/core/sharded_softmax.py:185" } } }
  event_metadata { key: 3 value { id: 3
    name: "%while.4 = (f32[8]{0}) while((f32[8]{0}) %tuple.1), body=%body"
    stats { metadata_id: 1 str_value: "while" } } }
  stat_metadata { key: 1 value { id: 1 name: "hlo_category" } }
  stat_metadata { key: 2 value { id: 2 name: "source" } } }
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 55000000 }
    events { metadata_id: 3 offset_ps: 55000000 duration_ps: 45000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.fit" } }
  event_metadata { key: 3 value { id: 3 name: "bench.refresh" } } }
"""


@pytest.fixture(scope="module")
def hand(tmp_path_factory):
    from jax.profiler import ProfileData
    path = tmp_path_factory.mktemp("trace") / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(HAND))
    return tr.reduce(str(path), 2)


def test_hand_trace_busy_is_the_union(hand):
    assert hand.window_s == pytest.approx(100e-6)
    # chip 0: 10-40 and 60-80 = 50 µs; chip 1: 0-50 = 50 µs
    assert hand.busy_by_chip == pytest.approx([50e-6, 50e-6])
    assert hand.busy_s == pytest.approx(50e-6)


def test_hand_trace_gaps_are_named_by_host_span(hand):
    got = sorted((round(s * 1e6, 6), name) for s, name in hand.gaps)
    assert got == sorted([(10.0, "bench.fit"), (20.0, "bench.fit"),
                          (20.0, "bench.refresh"), (50.0, "bench.refresh")])


def test_hand_trace_ops_kernels_and_collectives(hand):
    # 15 µs on chip 0 and 10 on chip 1, over two chips
    assert hand.op_seconds(lambda o: o.is_kernel) == pytest.approx(12.5e-6)
    assert [o.short for o in hand.ops if o.is_kernel] == [
        "jvp__.2", "transpose_jvp___.8"]
    assert [o.short for o in hand.ops if o.is_control] == ["while.4"]
    # the all-gather 60-70 overlaps the fusion from 65: 5 µs exposed
    assert hand.collective_exposed_s == pytest.approx([5e-6, 0.0])
    # the while holds its body's ops and is no op of its own
    assert hand.top_ops(3) == [
        ["fusion.9", pytest.approx(20e-6)],
        ["fusion.1", pytest.approx(17.5e-6)],
        ["jvp__.2", pytest.approx(7.5e-6)]]
    assert dict(hand.top_ops(5))[
        "transpose_jvp___.8 (custom-call, repro/core/sharded_softmax.py:185)"
    ] == pytest.approx(5e-6)


def test_hand_trace_ops_take_their_module_from_the_modules_line(hand):
    assert {o.hlo_module for o in hand.ops if o.chip == 0} == {"jit_step"}
    assert {o.hlo_module for o in hand.ops if o.chip == 1} == {""}


def test_metadata_stats_are_read_from_the_serialized_space(tmp_path):
    from jax.profiler import ProfileData
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(HAND))
    md = tr.metadata_stats(str(path))
    assert set(md) == {"/device:TPU:0", "/device:TPU:1"}
    assert md["/device:TPU:0"]["jvp__.2"] == {
        "long_name": 'custom_call_target="tpu_custom_call"'}
    assert md["/device:TPU:0"]["fusion.1"] == {
        "long_name": "fusion(%pallas_call.45)"}
    assert md["/device:TPU:1"]["fusion.9"] == {}
    assert md["/device:TPU:1"]["%while.4 = (f32[8]{0}) while((f32[8]{0}) "
                               "%tuple.1), body=%body"] == {
        "hlo_category": "while"}
