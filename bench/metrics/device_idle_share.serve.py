"""device_idle_share.serve (%): 1 - device busy time (the union of the
ops' intervals, averaged over the chips) over the traced window."""


def read(run):
    s = run.trace_summary
    if s is None or not s.window_s:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
