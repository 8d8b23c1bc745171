"""The control of the training cells at a size a test run can hold: the
plain reference computed one precision below the configuration's, put in
the program's place, must come out not correct.

On the chip the control is the reference at ``high`` (three bf16 passes,
``bench/calibrate.py``). The CPU computes every float32 product exactly
whatever the precision asked for, so here the lower precision is made by
hand: every normalized operand is rounded to a pair of bf16 numbers, the
16 bits of mantissa that three bf16 passes keep."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from bench.reference import softmax_ref
from bench.tests import tiny


def bf16_pair(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi + (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("cell", ["tiny.train.full", "tiny.train.knn"])
def test_lower_precision_in_the_programs_place_is_not_correct(
        root, monkeypatch, cell):
    from bench.kinds import train

    unit = softmax_ref._unit

    def control(exp, weight_decay):
        run = control.run
        with monkeypatch.context() as m:
            m.setattr(softmax_ref, "_unit", lambda x: bf16_pair(unit(x)))
            m.setattr(softmax_ref, "_full_grad", jax.jit(
                jax.value_and_grad(softmax_ref._full_loss),
                static_argnums=3))
            m.setattr(softmax_ref, "_knn_grad", jax.jit(
                jax.value_and_grad(softmax_ref._knn_loss),
                static_argnums=5))
            return train.reference(run)

    real_build = train.build

    def build(r, mesh, data_fn, tracer):
        control.run = r
        return real_build(r, mesh, data_fn, tracer)

    monkeypatch.setattr(train, "build", build)
    monkeypatch.setattr(train, "checked_updates", control)
    from bench import run as harness
    real_load = harness.load_module

    def load(path, name):
        if path.endswith("kinds/train.py"):
            return train
        return real_load(path, name)
    monkeypatch.setattr(harness, "load_module", load)
    rc, line, err = tiny.run_cell(root, cell)
    assert rc == 0 and line is not None, err[-3000:]
    assert not line["correct"]
    assert line["checks"]["grad_max_gap"]["value"] > \
        line["checks"]["grad_max_gap"]["limit"]
