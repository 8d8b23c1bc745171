"""``repro.telemetry`` — tracing/metrics with comm-volume accounting.

One seam for every layer's observability (docs/telemetry.md):

  * ``Tracer`` — nestable wall-clock spans (``with tr.span("train.step")``)
    over a monotonic ``perf_counter_ns`` clock, each also written into the
    profiler's trace as a ``jax.profiler.TraceAnnotation``; JAX's compile
    path as ``jax.*`` spans; typed counters/gauges, device peak-memory
    watermarks, a JSONL metrics sink, and Chrome-trace (Perfetto) JSON
    export.
  * ``NULL_TRACER`` — the disabled singleton: every hot-path call is a
    constant-time no-op that allocates nothing, so instrumented code pays
    ~nothing when telemetry is off.
  * ``CommLedger`` / ``train_step_ledger`` — the analytic comm-volume
    model: bytes per collective per train step, derived from head config +
    mesh shape, cross-checkable against ``repro.roofline.hlo`` cost
    analysis on the compiled step (tests/test_telemetry.py).

Threaded through ``PaperTrainer``/``ZooExperiment`` fit loops,
``ServingEngine``, ``repro.resilience`` and the launchers
(``--trace-out``/``--metrics-out``).
"""
from repro.telemetry.ledger import (COLLECTIVE_KINDS, Collective, CommLedger,
                                    train_step_ledger)
from repro.telemetry.metrics import MetricsSink
from repro.telemetry.tracer import (NULL_TRACER, NullTracer, SpanEvent,
                                    Tracer, device_peak_memory)

__all__ = [
    "COLLECTIVE_KINDS", "Collective", "CommLedger", "MetricsSink",
    "NULL_TRACER", "NullTracer", "SpanEvent", "Tracer",
    "device_peak_memory", "train_step_ledger",
]
