"""compile_share.serve (%): the window's time covered by the program's JAX
compile-path spans (``jax.trace``, ``jax.lower``, ``jax.compile``,
``jax.cache_load``: their union, ``bench/program_spans.py``) over the
window. Every shape is warmed in set-up, so anything here is a retrace,
a recompile or a compile-cache load inside the measured window."""
from bench import program_spans


def read(run):
    s = program_spans.compile_seconds(run)
    if s is None or not run.window_s:
        return None
    return 100.0 * s / run.window_s
