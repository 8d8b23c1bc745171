"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing here runs on a chip: the TPU compiler that ships with jaxlib
compiles each kernel with ``interpret=False`` for a ``v5e:2x2`` topology
that is described, not attached, at the paper deployment's per-chip sizes
(D=512, V_loc=390,656 = 100,001,020 / 256 classes padded to a multiple of
the vocab tile). Mosaic refuses here what the chip would refuse: unaligned
blocks, unsupported vector reshapes, lowerings that do not exist, and
kernels that ask for more VMEM than a core has.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and xdist workers
each import every test module.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (ce_softmax, ivf_rerank, knn_dist_topk, sparse_ce,
                           topk_dc)

D = 512
V_LOC = 390_656          # 100,001,020 classes / 256 chips, padded
BATCH = 256              # global training batch at one chip
A_KNN = V_LOC // 10      # knn active set: active_frac = 0.10
SERVE_B = 8              # serving micro-batch
IVF_A = 19 * 782         # nprobe * cap of the default IVF index at V_LOC


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """A spec factory on one described chip, with the persistent cache
    off: an entry compiled for a described chip cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    one = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    yield spec
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_ce_forward_compiles(chip):
    _compile(lambda f, w, y: ce_softmax.ce_forward(f, w, y, interpret=False),
             chip((BATCH, D)), chip((V_LOC, D)), chip((BATCH,), jnp.int32))


def test_ce_backward_compiles(chip):
    _compile(lambda f, w, y, m: ce_softmax.ce_backward(
        f, w, y, m, m, m, interpret=False),
        chip((BATCH, D)), chip((V_LOC, D)), chip((BATCH,), jnp.int32),
        chip((BATCH,)))


@pytest.mark.parametrize("mask_hits", [False, True])
def test_sparse_ce_forward_compiles(chip, mask_hits):
    _compile(lambda f, w, ids, bias, y: sparse_ce.sparse_ce_forward(
        f, w, ids, ids, bias, ids, y, mask_hits=mask_hits, interpret=False),
        chip((BATCH, D)), chip((V_LOC, D)), chip((A_KNN,), jnp.int32),
        chip((A_KNN,)), chip((BATCH,), jnp.int32))


@pytest.mark.parametrize("mask_hits", [False, True])
def test_sparse_ce_backward_compiles(chip, mask_hits):
    _compile(lambda f, w, ids, bias, y, m: sparse_ce.sparse_ce_backward(
        f, w, ids, ids, bias, ids, y, m, m, m, mask_hits=mask_hits,
        interpret=False),
        chip((BATCH, D)), chip((V_LOC, D)), chip((A_KNN,), jnp.int32),
        chip((A_KNN,)), chip((BATCH,), jnp.int32), chip((BATCH,)))


@pytest.mark.parametrize("precision", ["default", "float32"])
def test_dist_topk_compiles(chip, precision):
    """bf16 operands, also under a float32 default matmul precision (which
    chip_smoke.py sets)."""
    with jax.default_matmul_precision(precision):
        _compile(lambda q, k: knn_dist_topk.dist_topk(
            q, k, 32, block_q=256, block_n=512, interpret=False),
            chip((V_LOC, D), jnp.bfloat16), chip((V_LOC, D), jnp.bfloat16))


def test_stage1_topk_compiles(chip):
    n_chunks = SERVE_B * (-(-V_LOC // 2048))
    _compile(lambda x: topk_dc.stage1_topk(x, 5, interpret=False),
             chip((n_chunks, 2048)))


def test_ivf_rerank_compiles(chip):
    _compile(lambda f, w, cand: ivf_rerank.ivf_rerank(
        f, w, cand, 5, interpret=False),
        chip((SERVE_B, D)), chip((V_LOC, D)),
        chip((SERVE_B, IVF_A), jnp.int32))
