"""Device idle time under the program's own spans.

A live ``repro.telemetry.Tracer`` writes each of its spans into the
profiler's trace as a host event (``train.update``, ``train.refresh``,
``serve.poll``, ...), on the clock of the device ops. This module reads
those events and the last ``bench.window`` from the run's ``.xplane.pb``,
rebuilds each chip's idle intervals inside the window from the ops that
``bench.trace_reduce`` collected, and gives the idle time that lies under
a set of spans: interval by interval, not by the middles of gaps.

It also gives the time of JAX's compile path inside the window, from the
retroactive ``jax.*`` spans the tracer records (which never reach the
profiler's trace).

A program whose tracer writes no such events (one that predates them) has
nothing here to read: every function then returns None.
"""
from __future__ import annotations

import functools

from bench import trace_reduce

PREFIXES = ("train.", "serve.")
COMPILE = ("jax.trace", "jax.lower", "jax.compile", "jax.cache_load")


@functools.lru_cache(maxsize=1)
def host_spans(path: str):
    """((start, end) of the last ``bench.window``, {name: [(start, end)]}
    of the program's host spans) in the trace at ``path``, in ns."""
    from jax.profiler import ProfileData

    windows, spans = [], {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                iv = (int(e.start_ns), int(e.end_ns))
                if e.name == trace_reduce.WINDOW:
                    windows.append(iv)
                elif e.name.startswith(PREFIXES):
                    spans.setdefault(e.name, []).append(iv)
    if not windows:
        raise ValueError(f"no {trace_reduce.WINDOW} span in {path}")
    return max(windows), spans


def idle_under(run, names, exclude=()) -> float | None:
    """Seconds of device idle in the window that lie under a span named in
    ``names`` and under none named in ``exclude``, averaged over the
    chips; None where the trace holds no span named in ``names``."""
    s = run.trace_summary
    if s is None:
        return None
    (w0, w1), spans = host_spans(
        trace_reduce.latest_xplane(run.out_dir))
    if not any(n in spans for n in names):
        return None

    def cover(ns):
        return trace_reduce.union(trace_reduce.clip(
            [iv for n in ns for iv in spans.get(n, ())], w0, w1))

    under = trace_reduce.subtract(cover(names), cover(exclude))
    chips = max(1, len(s.busy_by_chip))
    total = 0.0
    for chip in range(chips):
        busy = trace_reduce.union(trace_reduce.clip(
            [(o.start, o.end) for o in s.ops if o.chip == chip], w0, w1))
        idle = trace_reduce.subtract([(w0, w1)], busy)
        # idle ∩ under = idle − (idle − under)
        total += (trace_reduce.total(idle) - trace_reduce.total(
            trace_reduce.subtract(idle, under)))
    return total * 1e-9 / chips


def compile_seconds(run) -> float | None:
    """Seconds of the window covered by the tracer's ``jax.*`` compile-path
    spans (their union: a cache load lies inside its backend compile, and
    a trace can hold the traces of the functions it calls); None where the
    tracer recorded no such span at all."""
    if run.tracer is None or "window_ns" not in run.facts:
        return None
    ivs = [(e.start_ns, e.start_ns + e.dur_ns) for e in run.tracer.events
           if e.name in COMPILE]
    if not ivs:
        return None
    t0, t1 = run.facts["window_ns"]
    return trace_reduce.total(trace_reduce.union(
        trace_reduce.clip(ivs, t0, t1))) * 1e-9
