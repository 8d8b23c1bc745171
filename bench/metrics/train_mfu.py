"""train_mfu (%): the head's forward and backward operations per sample
(``bench/flops.py``: 6·V·D for the full head, 6·M·D for KNN's M active
rows) times the traced window's samples per second, over the chips' bf16
peak. The refresh and recomputation are not counted."""
from bench import flops


def read(run):
    f = run.facts
    if "samples" not in f or not run.window_s:
        return None
    per_sample = flops.train_flops_per_sample(
        f["head"], f["classes_per_chip"] * f["chips"], f["d"],
        f["active_per_chip"] * f["chips"])
    peak = flops.peaks(run.devs[0].device_kind)["flops_bf16"]
    return 100.0 * per_sample * f["samples"] / run.window_s / (
        f["chips"] * peak)
