"""A tiny benchmark checkout for the CPU tests: the real ``bench/`` code
with small configurations, traffic mixes and cells, beside the real
``src/``. The harness finds all of it by name, exactly as on the chip."""
from __future__ import annotations

import copy
import io
import json
import os
import shutil
from contextlib import redirect_stderr, redirect_stdout

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)

FULL = {"name": "tiny-full", "classes_per_chip": 1024, "d": 64,
        "matmul_precision": "float32",
        "head": {"softmax_impl": "full", "backend": "pallas",
                 "cosine_scale": 16.0},
        "optimizer": {"name": "sgd", "momentum": 0.9,
                      "weight_decay": 0.0001},
        "fccs": {"eta0": 0.4, "t_warm": 100, "t_ini": 1000000000,
                 "t_final": 1000000001}}
KNN = dict(copy.deepcopy(FULL), name="tiny-knn",
           head={"softmax_impl": "knn", "backend": "pallas",
                 "cosine_scale": 16.0, "knn_k": 16, "knn_kprime": 32,
                 "active_frac": 0.1, "rebuild_every": 3,
                 "knn_pad_random": False})
TRAIN = {"kind": "train", "global_batch": 64, "micro_batch": 32,
         "labels": "uniform", "noise": 0.5}
SERVE = {"kind": "serve", "rate": 100, "arrivals": "poisson",
         "queries": "unique", "noise": 0.5, "top_k": 5, "max_batch": 8,
         "max_wait_ms": 2.0}
TRAIN_LIMITS = {"loss_gap": 1e-4, "grad_norm_gap": 1e-3,
                "update_norm_gap": 1e-3, "grad_max_gap": 5e-7,
                "graph_gap": 1e-6}
SERVE_LIMITS = {"score_gap": 1e-4, "rank_gap": 1e-4}

CELLS = {
    "tiny.train.full": ("tiny-full", "tiny.train", TRAIN_LIMITS),
    "tiny.train.knn": ("tiny-knn", "tiny.train", TRAIN_LIMITS),
    "tiny.serve": ("tiny-full", "tiny.serve", SERVE_LIMITS),
}


def write_json(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        json.dump(obj, fh, indent=1)


def make_root(tmp: str) -> str:
    """A checkout under ``tmp`` holding the tiny cells; returns its root."""
    root = os.path.join(tmp, "checkout")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    for cfg in (FULL, KNN):
        write_json(os.path.join(root, "bench", "configs",
                                f"{cfg['name']}.json"), cfg)
    write_json(os.path.join(root, "bench", "traffic", "tiny.train.json"),
               TRAIN)
    write_json(os.path.join(root, "bench", "traffic", "tiny.serve.json"),
               SERVE)
    bench["configs"] = [
        {"name": c["name"], "source": "tiny", "file":
         f"bench/configs/{c['name']}.json", "reduced": [], "why": "tiny"}
        for c in (FULL, KNN)]
    bench["workloads"] = []
    for name, (cfg, traffic, limits) in CELLS.items():
        bench["workloads"].append({"name": name, "config": cfg,
                                   "traffic": traffic, "chips": 1,
                                   "why": "tiny"})
        write_json(os.path.join(root, "bench", "cells", f"{name}.json"),
                   {"limits": limits})
    real = {w["name"]: w for w in json.load(
        open(os.path.join(REPO, "BENCHMARK.json")))["workloads"]}
    like = {"tiny.train.full": "full.train.b4096",
            "tiny.train.knn": "knn.train.b4096",
            "tiny.serve": "full.serve.poisson"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [t for t, r in like.items()
                              if r in m["workloads"] and r in real]
    write_json(os.path.join(root, "BENCHMARK.json"), bench)
    return root


def run_cell(root: str, cell: str, *, seed: int = 2 ** 33 + 5,
             seconds: float = 1.0, trace: int = 0):
    """Run the harness in this process on the CPU (the look for a TPU and
    the compile cache switched off); returns (rc, last stdout line as a
    dict or None, stderr)."""
    import jax

    from bench import run as harness

    saved = harness.find_devices, harness.setup_jax
    harness.find_devices = lambda chips: jax.devices()[:chips]
    harness.setup_jax = lambda: None
    out, err = io.StringIO(), io.StringIO()
    try:
        with redirect_stdout(out), redirect_stderr(err):
            rc = harness.main(["--workload", cell, "--seed", str(seed),
                               "--seconds", str(seconds), "--trace",
                               str(trace)], root=root)
    finally:
        harness.find_devices, harness.setup_jax = saved
    lines = out.getvalue().strip().splitlines()
    line = json.loads(lines[-1]) if lines and lines[-1].startswith("{") \
        else None
    return rc, line, err.getvalue()

