"""The harness end to end on the CPU at a tiny size, with the look for a
TPU switched off: sound runs come out correct, and a run whose timed path
is broken underneath comes out not correct, once for each fault a cell of
that kind can have."""
from __future__ import annotations

import numpy as np
import pytest

from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def _correct(root, cell, **kw):
    rc, line, err = tiny.run_cell(root, cell, **kw)
    assert rc == 0, err[-3000:]
    assert line is not None, err[-3000:]
    assert list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu"
    return line["correct"], line


@pytest.mark.parametrize("cell", ["tiny.train.full", "tiny.train.knn",
                                  "tiny.serve"])
def test_sound_run_is_correct(root, cell):
    ok, line = _correct(root, cell)
    assert ok, line["checks"]
    assert line["attempted"] > 0
    names = {"tiny.serve": {"serve_p95_ms", "serve_done_per_s", "setup_s"}}
    assert set(line["metrics"]) == names.get(
        cell, {"train_samples_per_s", "setup_s"})


TRAIN_CELLS = ["tiny.train.full", "tiny.train.knn"]


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_state_left_unchanged_is_not_correct(root, monkeypatch, cell):
    from repro.train import hybrid
    monkeypatch.setattr(hybrid, "apply_updates", lambda params, upd: params)
    ok, line = _correct(root, cell)
    assert not ok
    assert line["checks"]["update_norm_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_half_batch_left_out_is_not_correct(root, monkeypatch, cell):
    from repro.train import hybrid
    real = hybrid.microbatched_value_and_grad

    def half(loss_fn, params, inputs, n_micro):
        b = inputs["labels"].shape[0]
        return real(loss_fn, params,
                    {k: v[: b // 2] for k, v in inputs.items()}, n_micro)
    monkeypatch.setattr(hybrid, "microbatched_value_and_grad", half)
    ok, line = _correct(root, cell)
    assert not ok
    assert line["checks"]["grad_norm_gap"]["value"] > 0.05


def test_altered_answer_is_not_correct(root, monkeypatch):
    from repro.serving import engine
    real = engine._paper_step_fn

    def altered(exp, top_k, donate):
        run = real(exp, top_k, donate)

        def step(queries, n_valid):
            ids, scores = run(queries, n_valid)
            ids = ids.copy()
            ids[:, 0] = (ids[:, 0] + 1) % exp.model_cfg.vocab_size
            return ids, scores
        return step
    monkeypatch.setattr(engine, "_paper_step_fn", altered)
    ok, line = _correct(root, "tiny.serve")
    assert not ok
    assert line["checks"]["rank_gap"]["value"] > 1e-3


def test_altered_graph_is_not_correct(root, monkeypatch):
    from bench.kinds import train
    real = train.graph_lists

    def altered(exp, rows):
        lists = [np.array(lst) for lst in real(exp, rows)]
        lists[0][-1] = (lists[0][-1] + 1) % exp.model_cfg.vocab_size
        return lists
    monkeypatch.setattr(train, "graph_lists", altered)
    from bench import run as harness
    real_load = harness.load_module

    def load(path, name):
        if path.endswith("kinds/train.py"):
            return train
        return real_load(path, name)
    monkeypatch.setattr(harness, "load_module", load)
    ok, line = _correct(root, "tiny.train.knn")
    assert not ok
    assert line["checks"]["graph_gap"]["value"] > 1e-3
