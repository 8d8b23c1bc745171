"""serve_engine_idle_share (%): device idle under the serving engine's
``serve.poll`` spans (the program's host spans in the profiler's trace,
``bench/program_spans.py``) over the traced window: the chip waiting on
the engine's own host work (coalescing, padding, dispatch, the result's
fetch and copies), not on requests that are not yet due."""
from bench import program_spans


def read(run):
    s = run.trace_summary
    if s is None or not s.window_s:
        return None
    idle = program_spans.idle_under(run, ["serve.poll"])
    return None if idle is None else 100.0 * idle / s.window_s
