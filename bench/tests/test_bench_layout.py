"""Everything the harness runs is found by name: each cell's configuration,
traffic mix, runner and limits, and each per-layer metric's reader. A
cell, a configuration or a metric is added as files plus entries in
``BENCHMARK.json``, with no edit to a file that is there. And the harness
refuses to measure anything but a TPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from bench import run as harness
from bench.tests import tiny

REPO = tiny.REPO


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_every_cell_is_found_by_name(cell):
    spec = harness.cell_spec(REPO, cell)
    assert os.path.isfile(os.path.join(REPO, "bench", "kinds",
                                       f"{spec['kind']}.py"))
    limits = json.load(open(os.path.join(REPO, "bench", "cells",
                                         f"{cell}.json")))["limits"]
    assert limits and all(v > 0 for v in limits.values())
    assert spec["config"]["classes_per_chip"] > 0
    assert {m["name"] for m in spec["end_to_end"]} >= {"setup_s"}
    assert spec["per_layer"]


def test_every_metric_has_a_reader():
    for m in _bench()["per_layer"]:
        path = os.path.join(REPO, "bench", "metrics", f"{m['name']}.py")
        mod = harness.load_module(path, "m_" + m["name"].replace(".", "_"))
        assert callable(mod.read)


def test_every_config_file_is_its_own():
    files = [c["file"] for c in _bench()["configs"]]
    assert len(set(files)) == len(files)
    for c in _bench()["configs"]:
        assert json.load(open(os.path.join(REPO, c["file"])))["name"] \
            == c["name"]


def test_a_cell_config_and_metric_added_as_files_only(tmp_path):
    """A throwaway cell on a new configuration and traffic mix, and a new
    per-layer metric, in a copy of the benchmark: no file that was there
    is edited, and the run reports them."""
    root = tiny.make_root(str(tmp_path))
    before = {}
    for dirpath, _, names in os.walk(os.path.join(root, "bench")):
        for n in names:
            p = os.path.join(dirpath, n)
            before[p] = open(p, "rb").read()
    cfg = dict(tiny.FULL, name="tiny-wide", d=128)
    tiny.write_json(os.path.join(root, "bench", "configs",
                                 "tiny-wide.json"), cfg)
    tiny.write_json(os.path.join(root, "bench", "traffic",
                                 "tiny.short.json"),
                    dict(tiny.TRAIN, global_batch=32, micro_batch=32))
    tiny.write_json(os.path.join(root, "bench", "cells",
                                 "tiny.throwaway.json"),
                    {"limits": tiny.TRAIN_LIMITS})
    with open(os.path.join(root, "bench", "metrics",
                           "throwaway_updates.py"), "w") as fh:
        fh.write("def read(run):\n    return run.facts['updates']\n")
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "tiny-wide", "source": "tiny",
                             "file": "bench/configs/tiny-wide.json",
                             "reduced": [], "why": "tiny"})
    bench["workloads"].append({"name": "tiny.throwaway",
                               "config": "tiny-wide",
                               "traffic": "tiny.short", "chips": 1,
                               "why": "tiny"})
    for m in bench["end_to_end"]:
        if "train_samples_per_s" == m["name"]:
            m["workloads"].append("tiny.throwaway")
    bench["per_layer"] = [{"name": "throwaway_updates", "unit": "updates",
                           "better": "higher", "source": "host_clock",
                           "layer": "trainer", "moves":
                           "train_samples_per_s",
                           "workloads": ["tiny.throwaway"]}]
    tiny.write_json(os.path.join(root, "BENCHMARK.json"), bench)
    for p, data in before.items():
        assert open(p, "rb").read() == data
    rc, line, err = tiny.run_cell(root, "tiny.throwaway", seconds=0.5)
    assert rc == 0 and line is not None, err[-3000:]
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    import bench.flops as fl
    real = fl.peaks
    fl.peaks = lambda kind: real("TPU v5 lite")
    try:
        rc, line, err = tiny.run_cell(root, "tiny.throwaway", seconds=0.5,
                                      trace=1)
    finally:
        fl.peaks = real
    assert rc == 0 and line is not None, err[-3000:]
    assert line["metrics"]["throwaway_updates"]["value"] >= 1
    assert line["metrics"]["throwaway_updates"]["unit"] == "updates"


def _run_script(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "full.train.b4096",
         "--seed", "3", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_cpu():
    p = _run_script(REPO)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())


def test_run_refuses_a_checkout_without_the_system(tmp_path):
    import shutil
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    p = _run_script(str(tmp_path))
    assert p.returncode != 0
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
