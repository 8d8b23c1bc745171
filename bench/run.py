"""The chip benchmark of the paper system.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. Everything is found by name:
``BENCHMARK.json`` names the cell's configuration, traffic mix and chips
and the metrics it reports; ``bench/configs/<config>.json`` holds the
configuration, ``bench/traffic/<traffic>.json`` the traffic mix and its
``kind``, ``bench/kinds/<kind>.py`` the runner of that kind of cell, and
``bench/metrics/<metric>.py`` the reader of each per-layer metric.

A run refuses anything but a TPU with at least the cell's chips. It builds
the system through ``Experiment.from_config(system="paper")``, warms the
cell's own shapes (set-up, timed from process start), measures for
``--seconds``, checks what the timed path produced against the plain
references under ``bench/reference/``, and prints one JSON line last on
stdout. With ``--trace 1`` the window runs under the profiler and the line
holds the per-layer metrics, read from the trace, the program's spans and
its counters; otherwise the end-to-end metrics. Every number compared is
printed beside its limit, on the last lines of stderr and under
``checks``, the last key of the line.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class Refused(Exception):
    """The run cannot be made here; no result is printed."""


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.isfile(path):
        raise Refused(f"no file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise Refused(f"no file {path}")
    with open(path) as fh:
        return json.load(fh)


def cell_spec(root: str, workload: str) -> dict:
    """Everything the run needs to know about ``workload``, by name."""
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    traffic = read_json(os.path.join(root, "bench", "traffic",
                                     f"{cell['traffic']}.json"))

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    return {
        "name": workload, "chips": int(cell["chips"]),
        "config": read_json(os.path.join(root, cfg_entry["file"])),
        "traffic": traffic, "kind": traffic["kind"],
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def find_devices(chips: int):
    """The chips JAX reports; a TPU with ``chips`` of them or nothing."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def setup_jax() -> None:
    """Keep every compiled program in the checkout's fixed compile cache
    (``JAX_COMPILATION_CACHE_DIR`` when set), however short its compile,
    so that only a cell's first run in a checkout compiles."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.api.bootstrap import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def load_limits(run) -> dict:
    """The limits of the cell's compared numbers:
    ``bench/cells/<cell>.json``, with the readings they were set from."""
    return read_json(os.path.join(run.root, "bench", "cells",
                                  f"{run.spec['name']}.json"))["limits"]


def peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


class Run:
    """What one run hands to its runner and to the metric readers."""

    def __init__(self, root, spec, seed, seconds, trace, devs):
        self.root, self.spec = root, spec
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devs = devs
        self.config, self.traffic = spec["config"], spec["traffic"]
        self.out_dir = os.path.join(root, "bench", ".out", spec["name"])
        self.t_process = T_PROCESS
        # filled by the runner
        self.window_s = None        # host-clock length of the window
        self.attempted = 0
        self.failed = 0
        self.e2e = {}               # end-to-end metric -> value
        self.facts = {}             # what the readers need (counts, shapes)
        self.tracer = None          # the program's repro.telemetry.Tracer
        self.checks = []            # (name, value, limit), value <= limit
        self.memory_peak = 0
        self.trace_summary = None   # bench.trace_reduce.Summary

    def annotate(self, name: str):
        """A host span in the profiler's trace (a no-op untraced)."""
        import contextlib

        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def span_seconds(self, name: str) -> float:
        """Total seconds of the program's spans ``name`` in the window."""
        t0, t1 = self.facts["window_ns"]
        return sum(e.dur_ns for e in self.tracer.events
                   if e.name == name and e.start_ns >= t0
                   and e.start_ns + e.dur_ns <= t1) * 1e-9


def per_layer_metrics(run: Run) -> dict:
    out = {}
    for m in run.spec["per_layer"]:
        reader = load_module(os.path.join(run.root, "bench", "metrics",
                                          f"{m['name']}.py"),
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(run)
        if value is None:
            print(f"bench: per-layer metric {m['name']} found nothing to "
                  "read in this run and is left out", file=sys.stderr)
        else:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(run: Run, devs) -> dict:
    if run.trace:
        metrics = per_layer_metrics(run)
    else:
        metrics = {m["name"]: {"value": float(run.e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in run.spec["end_to_end"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": run.memory_peak}
    line = {"correct": all(v <= lim for _, v, lim in run.checks),
            "attempted": run.attempted, "failed": run.failed,
            "metrics": metrics, "device": device}
    if run.trace:
        s = run.trace_summary
        device["busy_s"] = s.busy_s
        device["window_s"] = s.window_s
        line["breakdown"] = {"device_ops": s.top_ops(10),
                             "idle_gaps": s.top_gaps(10)}
    line["checks"] = {name: {"value": v, "limit": lim}
                      for name, v, lim in run.checks}
    return line


def main(argv=None, root: str = ROOT) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        spec = cell_spec(root, args.workload)
        if not os.path.isdir(os.path.join(root, "src", "repro")):
            raise Refused(f"no system under test: {root}/src/repro")
        if os.path.join(root, "src") not in sys.path:
            sys.path.insert(0, os.path.join(root, "src"))
        if root not in sys.path:
            sys.path.insert(0, root)
        setup_jax()
        devs = find_devices(spec["chips"])
    except Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr)
        return 2
    run = Run(root, spec, args.seed, args.seconds, bool(args.trace), devs)
    runner = load_module(os.path.join(root, "bench", "kinds",
                                      f"{spec['kind']}.py"),
                         "bench_kind_" + spec["kind"])
    runner.run(run)
    if run.trace:
        from bench import trace_reduce
        run.trace_summary = trace_reduce.reduce(
            trace_reduce.latest_xplane(run.out_dir), len(devs))
    line = result_line(run, devs)
    for name, v, lim in run.checks:
        print(f"check {name}: {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
