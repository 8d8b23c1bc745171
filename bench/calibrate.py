"""Readings that the limits of a cell's compared numbers are set from, in
one process on the chip, at the cell's own sizes:

- ``program``: the timed path against the reference, on every seed;
- ``control``: the reference computed at ``high`` (three bf16 passes)
  put in the program's place, on the first ``--controls`` seeds;
- a planted fault on the same seeds. Training: ``half_batch``, the
  reference with each micro-batch's second half left out and the mean
  taken over the rest (a state left unchanged reads 1 on
  ``update_norm_gap`` by construction and needs no run); a KNN cell's
  ``altered`` graph, each compared list's last neighbour replaced by the
  next id. Serving: ``altered``, each served top-1 id replaced by the
  next id.

A KNN cell's ``graph_gap`` has as its control the exact graph computed at
``high`` in place of the program's.

    python3 bench/calibrate.py --workload full.train.b4096 \\
        --seeds 1,2,3 --controls 3 [--seconds 3]

Prints one JSON line per seed. A serving seed serves a window of
``--seconds`` at the cell's rate, as a run does, and compares its sample.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def train_seed(run, control: bool) -> dict:
    import jax

    from bench.kinds import train
    from repro.telemetry import Tracer

    cfg = run.config
    knn = cfg["head"]["softmax_impl"] == "knn"
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        mesh, data = train.mesh_and_data(run)
        exp = train.build(run, mesh, lambda t, b: data(t), Tracer())
        if knn:
            rows = train.graph_rows(run)
            lists = train.graph_lists(exp, rows)
        got = train.checked_updates(exp, cfg["optimizer"]["weight_decay"])
    del exp, data
    gc.collect()
    want = train.reference(run)
    raw = ("losses", "grad_norm", "update_norm")
    out = {"program": train.gaps(got, want),
           "program_raw": {k: got[k] for k in raw},
           "reference_raw": {k: want[k] for k in raw}}
    if knn:
        out["program"]["graph_gap"] = train.graph_gap(run, rows, lists)
    if control:
        out["control"] = train.gaps(train.reference(run, "high"), want)
        out["half_batch"] = train.gaps(
            train.reference(run, half_batch=True), want)
        if knn:
            out["control"]["graph_gap"] = train.graph_gap(
                run, rows, high_lists(run, rows))
            out["altered"] = {"graph_gap": train.graph_gap(
                run, rows, altered_lists(lists, cfg["classes_per_chip"]))}
    return out


def altered_lists(lists, classes: int) -> list:
    """The program's lists with each last neighbour replaced by the next
    class id: a graph altered where it is produced."""
    out = []
    for nb in lists:
        nb = np.array(nb)
        nb[-1] = (nb[-1] + 1) % classes
        out.append(nb)
    return out


def high_lists(run, rows) -> list:
    """The exact graph's lists of ``rows`` computed at ``high``."""
    import jax

    from bench.reference import softmax_ref
    from bench.traffic.generate import seed31

    cfg = run.config
    with jax.default_matmul_precision("high"):
        w = softmax_ref.init_w(seed31(run.seed), cfg["classes_per_chip"],
                               cfg["d"])
        return list(softmax_ref.knn_rows(w, rows, cfg["head"]["knn_k"]))


def serve_seed(run, control: bool) -> dict:
    import jax

    from bench.kinds import serve
    from bench.traffic import generate as gen
    from repro.telemetry import Tracer

    cfg, trf = run.config, run.traffic
    classes = cfg["classes_per_chip"] * run.spec["chips"]
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        exp, eng = serve.build(run, Tracer())
        due = gen.poisson_arrivals(run.seed, trf["rate"], run.seconds)
        q, _ = gen.queries(run.seed, len(due), classes=classes, d=cfg["d"],
                           noise=trf["noise"])
        eng.warmup(q[0])
        lat, ids, scores, _, _ = serve.serve_window(run, eng, q, due,
                                                    run.seconds)
    del exp, eng
    gc.collect()
    idx = serve.sample(run, np.flatnonzero(np.isfinite(scores).all(axis=1)))
    best, _, of_served = serve.reference(run, q[idx], ids[idx])
    out = {"program": serve.compare(ids[idx], scores[idx], best, of_served,
                                    classes),
           "not_done": int((~np.isfinite(lat)).sum())}
    if control:
        c_best, c_ids, _ = serve.reference(run, q[idx], ids[idx], "high")
        _, _, c_of = serve.reference(run, q[idx], c_ids)
        out["control"] = serve.compare(c_ids, c_best, best, c_of, classes)
        alt = ids[idx].copy()
        alt[:, 0] = (alt[:, 0] + 1) % classes
        _, _, a_of = serve.reference(run, q[idx], alt)
        out["altered"] = serve.compare(alt, scores[idx], best, a_of,
                                       classes)
    return out


def main(argv=None) -> int:
    from bench import run as harness

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--controls", type=int, default=3)
    p.add_argument("--seconds", type=float, default=3.0)
    args = p.parse_args(argv)
    root = harness.ROOT
    spec = harness.cell_spec(root, args.workload)
    sys.path.insert(0, os.path.join(root, "src"))
    harness.setup_jax()
    devs = harness.find_devices(spec["chips"])
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        run = harness.Run(root, spec, seed, args.seconds, False, devs)
        one = train_seed if spec["kind"] == "train" else serve_seed
        out = one(run, i < args.controls)
        print(json.dumps({"seed": seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
