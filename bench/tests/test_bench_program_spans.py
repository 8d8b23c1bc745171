"""Device idle under the program's own spans, and the compile path's share
of the window, on hand-made traces in the profiler's own format with
known answers for the five readers that use them."""
from __future__ import annotations

import os
from types import SimpleNamespace

import pytest

from bench import run as harness
from bench import trace_reduce
from bench.tests import tiny
from repro.telemetry import SpanEvent

# Two chips, window 0-100 µs. Chip 0 runs ops 10-30 and 50-60 (idle 0-10,
# 30-50, 60-100); chip 1 runs 0-20 and 40-70 (idle 20-40, 70-100).
# Program spans: train.update 5-45 (step 0) and 45-95 (step 1), with a
# train.refresh 55-80 in the second; serve.poll 25-35 (partly over chip
# 0's gap 30-50) and 65-72.
HAND = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 20000000 }
    events { metadata_id: 1 offset_ps: 50000000 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 1 offset_ps: 40000000 duration_ps: 30000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.2" } } }
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 40000000
      stats { metadata_id: 1 int64_value: 0 } }
    events { metadata_id: 2 offset_ps: 45000000 duration_ps: 50000000
      stats { metadata_id: 1 int64_value: 1 } }
    events { metadata_id: 3 offset_ps: 55000000 duration_ps: 25000000 }
    events { metadata_id: 4 offset_ps: 25000000 duration_ps: 10000000 }
    events { metadata_id: 4 offset_ps: 65000000 duration_ps: 7000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "train.update" } }
  event_metadata { key: 3 value { id: 3 name: "train.refresh" } }
  event_metadata { key: 4 value { id: 4 name: "serve.poll" } }
  stat_metadata { key: 1 value { id: 1 name: "step" } } }
"""

# One chip, window 0-100 µs, an op 0-50: the engine polls only while the
# chip is busy (10-30), then a gap 50-100 under no program span.
BUSY_POLL = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 50000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 20000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "serve.poll" } } }
"""

# As a program without host spans of its own writes it: the window only.
NO_SPANS = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 50000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 20000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.poll" } } }
"""


def _run(tmp_path, text, chips, *, updates=2, tracer=None):
    """What a traced run hands the readers, over a hand-made trace."""
    from jax.profiler import ProfileData
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    path = d / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    summary = trace_reduce.reduce(str(path), chips)
    return SimpleNamespace(
        out_dir=str(tmp_path), trace_summary=summary,
        window_s=summary.window_s, tracer=tracer,
        facts={"updates": updates, "window_ns": (1_000, 101_000)})


def _read(metric, run):
    path = os.path.join(tiny.REPO, "bench", "metrics", f"{metric}.py")
    return harness.load_module(
        path, "m_" + metric.replace(".", "_")).read(run)


def test_idle_under_spans_by_hand(tmp_path):
    run = _run(tmp_path, HAND, 2)
    # under the updates, outside the refresh (5-55, 80-95): chip 0 idles
    # 5-10, 30-50, 80-95 (40 µs), chip 1 20-40, 80-95 (35 µs)
    assert _read("train_host_gap_ms", run) == pytest.approx(
        37.5e-6 * 1e3 / 2)
    # under the refresh 55-80: chip 0 60-80, chip 1 70-80
    assert _read("knn_refresh_host_share", run) == pytest.approx(15.0)
    # under the polls: chip 0 30-35 and 65-72, chip 1 25-35 and 70-72
    assert _read("serve_engine_idle_share", run) == pytest.approx(12.0)


def test_spans_over_busy_time_read_zero_and_no_spans_read_nothing(
        tmp_path):
    busy = _run(tmp_path / "busy", BUSY_POLL, 1)
    assert _read("serve_engine_idle_share", busy) == 0.0
    bare = _run(tmp_path / "bare", NO_SPANS, 1)
    for metric in ("train_host_gap_ms", "knn_refresh_host_share",
                   "serve_engine_idle_share"):
        assert _read(metric, bare) is None


def _tracer(*events):
    return SimpleNamespace(events=[SpanEvent(n, s, d, 0, None)
                                   for n, s, d in events])


@pytest.mark.parametrize("metric", ["compile_share.train",
                                    "compile_share.serve"])
def test_compile_share_is_the_union_inside_the_window(tmp_path, metric):
    # window 1,000-101,000 ns: a compile 900-1,900 with a cache load
    # inside it, a trace 1,500-2,500, a lowering 50,000-50,400 and a
    # train span, which is no compile
    run = _run(tmp_path, HAND, 2, tracer=_tracer(
        ("jax.compile", 900, 1_000), ("jax.cache_load", 1_200, 300),
        ("jax.trace", 1_500, 1_000), ("jax.lower", 50_000, 400),
        ("train.step", 1_000, 90_000)))
    run.window_s = 100e-6
    assert _read(metric, run) == pytest.approx(100 * 1_900e-9 / 100e-6)
    run.tracer = _tracer(("jax.compile", 200, 500))
    assert _read(metric, run) == 0.0
    run.tracer = _tracer(("train.step", 1_000, 500))
    assert _read(metric, run) is None
