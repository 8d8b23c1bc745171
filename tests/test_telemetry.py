"""repro.telemetry: span nesting under a fake clock, the disabled
tracer's zero-allocation guarantee, Chrome-trace round-trip, the analytic
comm ledger vs the compiled step's HLO, JSONL sink append semantics, live
spans in the profiler's trace, JAX's compile path as spans, and the spans
of the trainer loop and the serving engine (docs/telemetry.md)."""
import gc
import glob
import json

import numpy as np
import pytest

from repro.telemetry import (NULL_TRACER, CommLedger, MetricsSink, Tracer,
                             train_step_ledger)
from repro.telemetry.tracer import _NullSpan


class FakeClock:
    """Deterministic ns clock: every read advances by ``tick_ns``."""

    def __init__(self, tick_ns: int = 1000):
        self.t = 0
        self.tick_ns = tick_ns

    def __call__(self) -> int:
        self.t += self.tick_ns
        return self.t


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_span_nesting_and_determinism_under_fake_clock():
    tr = Tracer(clock_ns=FakeClock(1000))
    with tr.span("outer"):
        with tr.span("inner", attrs={"k": 1}):
            pass
        with tr.span("inner"):
            pass
    # spans close inner-first; depth recorded at entry
    assert [(e.name, e.depth) for e in tr.events] == [
        ("inner", 1), ("inner", 1), ("outer", 0)]
    # fake clock: enter/exit each consume one 1000ns tick, so every
    # leaf span lasts exactly one tick and the outer one spans all reads
    inner1, inner2, outer = tr.events
    assert inner1.dur_ns == 1000 and inner2.dur_ns == 1000
    assert outer.start_ns == 1000 and outer.dur_ns == 5000
    # a second identical run produces identical events (determinism)
    tr2 = Tracer(clock_ns=FakeClock(1000))
    with tr2.span("outer"):
        with tr2.span("inner", attrs={"k": 1}):
            pass
        with tr2.span("inner"):
            pass
    assert tr2.events == tr.events


def test_span_stats_and_counters():
    tr = Tracer(clock_ns=FakeClock(500))
    for _ in range(3):
        with tr.span("step"):
            pass
    tr.add_span("step", start_ns=10_000, dur_ns=2_000)
    st = tr.span_stats("step")
    assert st["count"] == 4
    assert st["total_s"] == pytest.approx((3 * 500 + 2000) * 1e-9)
    assert tr.span_stats("absent") == {"count": 0, "total_s": 0.0}
    assert tr.count("steps") == 1.0
    assert tr.count("steps", 2.0) == 3.0
    tr.gauge("occupancy", 0.5)
    assert tr.counters["steps"] == 3.0 and tr.gauges["occupancy"] == 0.5


def test_null_tracer_is_zero_alloc_no_op():
    before = _NullSpan.instances
    for _ in range(10_000):
        with NULL_TRACER.span("hot", attrs=None):
            pass
        NULL_TRACER.count("hot.steps")
        NULL_TRACER.gauge("hot.g", 1)
        NULL_TRACER.log_metrics({"x": 1})
    # the module-level singleton is the ONLY instance ever made: the hot
    # loop above allocated zero spans
    assert _NullSpan.instances == before == 1
    assert NULL_TRACER.enabled is False
    assert NULL_TRACER.events == ()
    assert NULL_TRACER.span_stats("hot") == {"count": 0, "total_s": 0.0}
    assert NULL_TRACER.count("hot.steps") == 0.0
    NULL_TRACER.close()  # harmless


# ---------------------------------------------------------------------------
# chrome-trace export
# ---------------------------------------------------------------------------


def test_chrome_trace_round_trip(tmp_path):
    tr = Tracer(clock_ns=FakeClock(1000))
    with tr.span("train.step", attrs={"step": 0}):
        with tr.span("train.data"):
            pass
    tr.count("train.steps")
    tr.gauge("mem.peak_bytes.host_rss", 123)
    path = tr.write_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        loaded = json.load(f)
    assert loaded == tr.chrome_trace()
    events = loaded["traceEvents"]
    assert [e["name"] for e in events] == ["train.data", "train.step"]
    for e in events:
        assert e["ph"] == "X" and e["pid"] == 0 and e["tid"] == 0
    # µs timestamps from the ns clock; attrs + depth ride in args
    assert events[1]["ts"] == 1.0 and events[1]["dur"] == 3.0
    assert events[1]["args"] == {"depth": 0, "step": 0}
    assert events[0]["args"]["depth"] == 1
    assert loaded["counters"] == {"train.steps": 1.0}
    assert loaded["gauges"] == {"mem.peak_bytes.host_rss": 123}
    assert loaded["displayTimeUnit"] == "ms"


# ---------------------------------------------------------------------------
# metrics sink (JSONL)
# ---------------------------------------------------------------------------


def test_metrics_sink_appends_across_reopens(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    with MetricsSink(path) as sink:
        sink.write({"step": 0, "loss": 2.0})
        sink.write({"step": 1, "loss": 1.5})
        assert sink.n_rows == 2
    # a fresh sink on the same path APPENDS (resume semantics), never
    # truncates
    tr = Tracer(metrics_path=path)
    tr.log_metrics({"step": 2, "loss": 1.0})
    tr.close()
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    assert [r["step"] for r in rows] == [0, 1, 2]


# ---------------------------------------------------------------------------
# comm-volume ledger
# ---------------------------------------------------------------------------


def test_ledger_bookkeeping():
    led = CommLedger()
    led.add("all-gather", "x", 100).add("all-reduce", "y", 50, count=2)
    pk = led.per_kind()
    assert pk["all-gather"] == {"bytes": 100.0, "count": 1}
    assert pk["all-reduce"] == {"bytes": 50.0, "count": 2}
    assert pk["total_bytes"] == led.total_bytes() == 150.0
    with pytest.raises(ValueError, match="unknown collective kind"):
        led.add("broadcast", "z", 1)
    with pytest.raises(ValueError, match="extend"):
        train_step_ledger(n_dev=4, rows=8, feat_dim=4, head="mach")
    # compare flags per-kind byte divergence and nothing else
    assert led.compare({"all-gather": {"bytes": 100.0},
                        "all-reduce": {"bytes": 50.0}}) == []
    bad = led.compare({"all-gather": {"bytes": 100.0},
                       "all-reduce": {"bytes": 75.0}})
    assert len(bad) == 1 and bad[0].startswith("all-reduce")
    # a kind only the measurement saw still diverges
    assert led.compare({"all-gather": {"bytes": 100.0},
                        "all-reduce": {"bytes": 50.0},
                        "all-to-all": {"bytes": 7.0}}) != []


@pytest.mark.parametrize("head,backend", [
    ("full", "ref"), ("full", "pallas"),
    ("knn", "ref"), ("knn", "pallas"),
])
def test_ledger_matches_compiled_hlo_mesh4(head, backend):
    """The analytic ledger must match the compiled hybrid train step's
    HLO collective bytes on a 4-device mesh (exact at n_micro=1)."""
    from repro.launch.dryrun import lower_paper_one

    r = lower_paper_one(classes=256, head=head, backend=backend,
                        batch=32, feat_dim=16, n_micro=1, n_dev=4)
    assert r["ledger_divergence"] == [], r["ledger_divergence"]
    assert r["ledger"]["total_bytes"] > 0
    # and the ledger total equals the HLO total within the same rtol
    meas = r["collectives"]["total_bytes"]
    assert meas == pytest.approx(r["ledger"]["total_bytes"], rel=0.02)


def test_ledger_matches_compiled_hlo_micro_pipeline():
    """n_micro > 1 runs the CE completion inside a scan; XLA CSE may
    merge a duplicate pmax, so the model is ~7% high — rtol 10%."""
    from repro.launch.dryrun import lower_paper_one

    r = lower_paper_one(classes=256, head="full", backend="ref",
                        batch=32, feat_dim=16, n_micro=2, n_dev=4)
    assert r["ledger_divergence"] == [], r["ledger_divergence"]


def test_ledger_fe_param_terms():
    """LM-style trunks add the backward reduce-scatter and the dense
    gradient exchange; the feats trunk (fe_param_count=0) charges
    neither."""
    feats = train_step_ledger(n_dev=4, rows=32, feat_dim=16)
    assert "reduce-scatter" not in feats.per_kind()
    lm = train_step_ledger(n_dev=4, rows=32, feat_dim=16,
                           fe_param_count=1000)
    pk = lm.per_kind()
    assert pk["reduce-scatter"]["bytes"] == 32 * 16 * 4 / 4
    labels = {e.label: e.bytes for e in lm.entries}
    assert labels["fe_grad_exchange"] == 4000.0
    with pytest.raises(ValueError, match="divisible"):
        train_step_ledger(n_dev=4, rows=33, feat_dim=16, n_micro=2)


# ---------------------------------------------------------------------------
# spans in the profiler's trace, and JAX's compile path
# ---------------------------------------------------------------------------


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    return [e for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_live_spans_reach_the_profiler_trace(tmp_path):
    import jax

    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("t.outer", {"step": 3, "kind": "x", "rids": [1, 2]}):
            with tr.span("t.inner", {"n": 2}):
                pass
        tr.add_span("t.retro", 0, 10)
    finally:
        jax.profiler.stop_trace()
    by = {e.name: e for e in _host_events(tmp_path)
          if e.name.startswith("t.")}
    # the retroactive span is the tracer's only
    assert set(by) == {"t.outer", "t.inner"}
    outer, inner = by["t.outer"], by["t.inner"]
    # scalar attrs ride as the event's stats; the list stays with the tracer
    assert dict(outer.stats) == {"step": 3, "kind": "x"}
    assert dict(inner.stats) == {"n": 2}
    assert outer.start_ns <= inner.start_ns
    assert inner.start_ns + inner.duration_ns \
        <= outer.start_ns + outer.duration_ns
    assert tr.events[-2].attrs["rids"] == [1, 2]


def test_compile_path_reaches_live_tracers_only():
    import jax
    import jax.numpy as jnp

    from repro.telemetry import tracer as tracer_mod

    tr = Tracer()
    with tr.span("outer"):
        jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()
    got = [e for e in tr.events if e.name.startswith("jax.")]
    assert {"jax.trace", "jax.lower", "jax.compile"} <= {e.name
                                                         for e in got}
    assert tr.counters["jax.compiles"] == sum(e.name == "jax.compile"
                                              for e in got) >= 1
    outer = tr.events[-1]
    for e in got:
        # retroactive spans that end by the time the call returns, at the
        # depth of the span they happened in
        assert e.dur_ns >= 0 and e.depth == 1
        assert e.start_ns + e.dur_ns <= outer.start_ns + outer.dur_ns
    assert any(e.attrs and "lambda" in e.attrs["fun"] for e in got)
    # the disabled tracer is never registered and records nothing; a
    # dropped tracer leaves the live set
    assert NULL_TRACER not in tracer_mod._LIVE
    assert NULL_TRACER.events == ()
    n = len(tracer_mod._LIVE)
    del tr
    gc.collect()
    assert len(tracer_mod._LIVE) == n - 1


# ---------------------------------------------------------------------------
# trainer loop and serving engine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_knn():
    """A tiny paper experiment whose KNN head refreshes every 2 updates."""
    from repro.api import Experiment
    from repro.configs.base import HeadConfig
    from repro.train import hybrid

    return Experiment.from_config(
        system="paper", classes=64, feat_dim=16, batch=16,
        head=HeadConfig(softmax_impl="knn", rebuild_every=2, knn_k=4,
                        knn_kprime=8, active_frac=0.5),
        mesh=hybrid.make_hybrid_mesh(2), log_every=0)


def _children(events, parent):
    """Spans one level below ``parent`` that lie inside it, by start."""
    end = parent.start_ns + parent.dur_ns
    return sorted((e for e in events if e.depth == parent.depth + 1
                   and e.name.startswith(("train.", "serve."))
                   and parent.start_ns <= e.start_ns
                   and e.start_ns + e.dur_ns <= end),
                  key=lambda e: e.start_ns)


def test_trainer_update_spans_hold_their_children_in_order(tiny_knn):
    tr = Tracer()
    tiny_knn.trainer.telemetry = tr
    try:
        t0 = tiny_knn.trainer._t
        tiny_knn.fit(4, use_fccs_batch=False)
    finally:
        tiny_knn.trainer.telemetry = None
    updates = [e for e in tr.events if e.name == "train.update"]
    assert [e.attrs["step"] for e in updates] == list(range(t0, t0 + 4))
    assert all(e.depth == 0 for e in updates)
    for u in updates:
        kids = [e.name for e in _children(tr.events, u)]
        refresh = (u.attrs["step"] + 1) % 2 == 0
        assert kids == ["train.data", "train.step"] + (
            ["train.refresh"] if refresh else []) + ["train.log"]
    refreshes = [e for e in tr.events if e.name == "train.refresh"]
    assert len(refreshes) == 2
    for r in refreshes:
        assert [e.name for e in _children(tr.events, r)] == [
            "train.refresh.build", "train.refresh.fetch",
            "train.refresh.pack", "train.refresh.place"]


def _echo_engine(calls, **kw):
    """An engine whose step answers each query with its own first entry
    (the request id it was made from), and records each batch's ids."""
    from repro.serving import ServingEngine

    def step(q, n):
        calls.append([int(x) for x in q[:n, 0]])
        ids = np.repeat(q[:, :1].astype(np.int64), 2, axis=1)
        return ids, np.zeros((len(q), 2), np.float32)

    return ServingEngine(step, top_k=2, max_batch=4, max_wait_ms=1.0,
                         clock=lambda: 0.0, **kw)


def test_serve_batch_spans_carry_their_request_ids():
    calls = []
    tr = Tracer()
    eng = _echo_engine(calls, telemetry=tr)
    done = []
    for i in range(10):
        eng.submit(np.full(3, i, np.float32), now=i * 4e-4)
        done += eng.poll(now=i * 4e-4)
    done += eng.drain(now=1.0)
    assert sorted(r.rid for r in done) == list(range(10))
    batches = [e for e in tr.events if e.name == "serve.batch"]
    assert [e.attrs["rids"] for e in batches] == calls
    assert [e.attrs["n"] for e in batches] == [len(c) for c in calls]
    assert [e.attrs["batch"] for e in batches] == list(range(len(calls)))
    for b in batches:
        assert [e.name for e in _children(tr.events, b)] == [
            "serve.flush", "serve.compute", "serve.deliver"]
    polls = [e for e in tr.events if e.name == "serve.poll"]
    assert len(polls) == 11
    for b in batches:
        assert any(p.start_ns <= b.start_ns and b.start_ns + b.dur_ns
                   <= p.start_ns + p.dur_ns for p in polls)
    assert not tr.gauges


def test_null_tracer_allocates_no_span_in_trainer_and_engine(tiny_knn):
    from repro.telemetry.tracer import _NullSpan

    assert tiny_knn.trainer.telemetry is None
    before = _NullSpan.instances
    tiny_knn.fit(2, use_fccs_batch=False)        # one update refreshes
    calls = []
    eng = _echo_engine(calls)
    for i in range(6):
        eng.submit(np.full(3, i, np.float32), now=0.0)
        eng.poll(now=0.0)
    eng.drain(now=1.0)
    assert calls and _NullSpan.instances == before == 1
