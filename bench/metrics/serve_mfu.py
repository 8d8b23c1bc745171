"""serve_mfu (%): the least time the chip could take for the window's
batches (per batch the larger of its real queries' scan operations over
the bf16 peak and one read of the class rows over the HBM bandwidth,
``bench/flops.py``), over the window."""
from bench import flops


def read(run):
    f = run.facts
    if not f.get("batches") or not run.window_s:
        return None
    peak = flops.peaks(run.devs[0].device_kind)
    least = sum(flops.bound_s(*flops.scan(n, f["classes"], f["d"]), peak)[0]
                for n in f["batches"])
    return 100.0 * least / run.window_s
