"""serve_scan_roofline (%): the least time of the window's batches (as
for serve_mfu) over the device time of the serve step programs, which are
all the device ops of a serving window."""
from bench import flops


def read(run):
    f, s = run.facts, run.trace_summary
    if s is None or not f.get("batches") or s.busy_s <= 0:
        return None
    peak = flops.peaks(run.devs[0].device_kind)
    least = sum(flops.bound_s(*flops.scan(n, f["classes"], f["d"]), peak)[0]
                for n in f["batches"])
    return 100.0 * least / s.busy_s
