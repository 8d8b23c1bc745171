"""Pallas TPU kernel: fused gather + top-k rerank for the IVF serving index.

The IVF serve path (``repro.serving.index``) probes the top-``nprobe``
k-means centroids per query and then scores ONLY the member rows of the
probed clusters. The ref path gathers ``w[cand]`` to a [B, A, D] tensor in
HBM, matmuls to dense [B, A] scores, and runs ``lax.top_k``. This kernel
fuses all three stages, reusing two idioms of this package:

  * the row gather of ``sparse_ce`` (``gather_rows``): each candidate tile's
    ids arrive as an SMEM block, and one DMA per id copies that row of the
    [V_loc, D] shard, which stays in HBM, into a VMEM scratch tile;
  * the slot merge of ``knn_dist_topk`` (``merge_into_slots``): a running
    top-k per query, in VMEM scratch, that admits a tile's scores only while
    one beats the current k-th best.

The grid is (query, candidate-tile); the last tile of a query writes its
slots out sorted. Neither the gathered [A, D] weights nor the [B, A] score
tensor ever reach HBM. Ties break by candidate position, as ``lax.top_k``
over the candidate list does.

Candidate slots of -1 are padding (short clusters); they score -inf, never
enter the top-k, and a row with fewer than k real candidates returns id -1
in the slots left over, matching the ref path bit-for-bit on ids. Wrapped
by ``ops.ivf_rerank``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.knn_dist_topk import (NEG, empty_slot_keys,
                                         merge_into_slots, sorted_slots)
from repro.kernels.sparse_ce import gather_rows


def _rerank_kernel(ids_ref, f_ref, w_hbm, cand_ref, vals_ref, idx_ref,
                   tile, sem, sv, sk, sp, *, ba: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        sv[...] = jnp.full_like(sv, NEG)
        sk[...] = empty_slot_keys(sk.shape)
        sp[...] = jnp.full_like(sp, -1)

    w_t = gather_rows(ids_ref, w_hbm, tile, sem)          # [ba, D]
    s = jax.lax.dot_general(f_ref[...], w_t, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [1, ba]
    cand = cand_ref[...]                                  # [1, ba]; -1 = pad
    s = jnp.where(cand >= 0, s, NEG)
    pos = j * ba + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    sv[...], sk[...], sp[...] = merge_into_slots(s, pos, cand, sv[...],
                                                 sk[...], sp[...])

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        v, _, p = sorted_slots(sv[...], sk[...], sp[...])
        vals_ref[...] = v
        idx_ref[...] = p


def ivf_rerank(f, w, cand, k: int, *, block_a: int = 128, interpret: bool):
    """f [B, D]; w [V_loc, D] (rows gathered in-kernel); cand [B, A] int32
    local row ids with -1 marking empty slots. Returns (vals [B, k] fp32
    descending, ids [B, k] int32 row ids, -1 where a row has fewer than k
    real candidates)."""
    b, d = f.shape
    v = w.shape[0]
    a = cand.shape[1]
    ba = min(block_a, max(8, a))
    pa = (-a) % ba
    cand = cand.astype(jnp.int32)
    if pa:
        cand = jnp.pad(cand, ((0, 0), (0, pa)), constant_values=-1)
    nt = (a + pa) // ba
    # every per-query vector is laid out [B, ..., 1, width] so that one
    # grid step reads one [1, width] block (see sparse_ce._tile_cols)
    cand = cand.reshape(b, nt, 1, ba)
    safe = jnp.clip(cand, 0, v - 1)                       # clip-safe gather
    tile_spec = pl.BlockSpec((None, None, 1, ba), lambda i, j: (i, j, 0, 0))
    row_spec = pl.BlockSpec((None, 1, k), lambda i, j: (i, 0, 0))
    vals, idx = pl.pallas_call(
        functools.partial(_rerank_kernel, ba=ba),
        out_shape=(jax.ShapeDtypeStruct((b, 1, k), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, k), jnp.int32)),
        grid=(b, nt),
        in_specs=[pl.BlockSpec((None, None, 1, ba), lambda i, j: (i, j, 0, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((None, 1, d), lambda i, j: (i, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  tile_spec],
        out_specs=(row_spec, row_spec),
        scratch_shapes=[pltpu.VMEM((ba, 1, d), jnp.float32),
                        pltpu.SemaphoreType.DMA(()),
                        pltpu.VMEM((1, k), jnp.float32),
                        pltpu.VMEM((1, k), jnp.int32),
                        pltpu.VMEM((1, k), jnp.int32)],
        interpret=interpret,
        name="ivf_rerank",
    )(safe, f.astype(jnp.float32).reshape(b, 1, d),
      w.astype(jnp.float32).reshape(v, 1, d), cand)
    return vals[:, 0], idx[:, 0]
