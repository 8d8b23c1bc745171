"""sparse_ce_kernel_roofline (%): the least time of the sparse CE forward
and backward kernels over the window's micro-batches (per micro-batch the
larger of their operations over the bf16 peak and their bytes over the
HBM bandwidth for M active rows, ``bench/flops.sparse_ce_kernels``) over
their device time in the trace: the Pallas kernels of the train step
program (the refresh's kernels run in programs of their own)."""
from bench import flops


def read(run):
    f, s = run.facts, run.trace_summary
    if s is None or not f.get("micro_batches"):
        return None
    t = s.op_seconds(lambda o: o.is_kernel and "step" in o.hlo_module)
    if t <= 0:
        return None
    peak = flops.peaks(run.devs[0].device_kind)
    b = f["micro_batch"] * f["chips"]
    least, _ = flops.bound_s(*flops.sparse_ce_kernels(
        b, f["active_per_chip"], f["d"]), peak)
    return 100.0 * least * f["micro_batches"] / t
