"""train_host_gap_ms (ms): device idle under the trainer loop's
``train.update`` spans and outside its ``train.refresh`` spans (the
program's host spans in the profiler's trace, ``bench/program_spans.py``),
per update in the window: the time the chip waits on the host between one
update's step and the next."""
from bench import program_spans


def read(run):
    updates = run.facts.get("updates")
    if not updates:
        return None
    s = program_spans.idle_under(run, ["train.update"], ["train.refresh"])
    return None if s is None else 1e3 * s / updates
