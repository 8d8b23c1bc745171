"""``bench/flops.py`` against counts worked out by hand at the cells'
shapes: micro-batch 1,024, 390,656 class rows, D = 512, 39,065 active rows
(10% of the shard), serving batches of 64."""
from __future__ import annotations

import pytest

from bench import flops

B, V, D, M = 1024, 390_656, 512, 39_065


def test_ce_kernels_at_the_full_cell():
    # logits 2·B·V·D = 409,632,505,856; two gradient products twice that
    f, nbytes = flops.ce_kernels(B, V, D)
    assert f == 1_228_897_517_568
    # forward reads f (2 MiB) and W (800,063,488 B); backward reads both
    # again and writes dW and df
    assert nbytes == 2_406_481_920


def test_sparse_ce_kernels_at_the_knn_cell():
    f, nbytes = flops.sparse_ce_kernels(B, M, D)
    assert f == 122_887_864_320
    assert nbytes == 246_306_816


def test_scan_of_a_full_serving_batch():
    f, nbytes = flops.scan(64, V, D)
    assert f == 25_602_031_616
    assert nbytes == 800_194_560


def test_per_sample_training_flops():
    assert flops.train_flops_per_sample("full", V, D) == 1_200_095_232
    assert flops.train_flops_per_sample("knn", V, D, M) == 120_007_680


def test_bounds_on_a_v5e():
    peak = flops.peaks("TPU v5 lite")
    t, which = flops.bound_s(*flops.ce_kernels(B, V, D), peak)
    assert which == "compute"
    assert t == pytest.approx(1_228_897_517_568 / 197e12)
    t, which = flops.bound_s(*flops.scan(64, V, D), peak)
    assert which == "memory"
    assert t == pytest.approx(800_194_560 / 819e9)


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("cpu")
    with pytest.raises(KeyError):
        flops.peaks("source")
