"""knn_refresh_host_share (%): device idle under the trainer's
``train.refresh`` spans (the program's host spans in the profiler's trace,
``bench/program_spans.py``) over the traced window: the part of the graph
rebuild in which the chip waits on the host (retrace, the graph's fetch,
CSR packing, placement)."""
from bench import program_spans


def read(run):
    s = run.trace_summary
    if s is None or not s.window_s:
        return None
    idle = program_spans.idle_under(run, ["train.refresh"])
    return None if idle is None else 100.0 * idle / s.window_s
