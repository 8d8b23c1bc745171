"""Pallas TPU kernel: fused cosine-score + running top-k' merge — the inner
loop of the distributed KNN graph build (paper §3.2.2).

Per ring hop, each device scores its local rows Q [Nq, D] against the
traveling block K [Nk, D] and merges into a running top-k'. This kernel
fuses the MXU matmul with the merge so the [Nq, Nk] score tile never leaves
VMEM: grid = (q_blocks, n_blocks) with the n dimension innermost; a VMEM
scratch carries each row's k' slots across the n sweep, and the last tile
writes them out sorted.

The merge (``merge_into_slots``) admits a tile's scores one extraction at
a time, and only while some row of the tile still holds a score above its
current k'-th best. Once a row's list has filled, a new tile rarely beats
it, so most tiles take a sweep or two instead of k'. ``ivf_rerank`` reuses
the same merge for its per-query top-k.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -jnp.inf
_IMAX = jnp.iinfo(jnp.int32).max
_IMIN = jnp.iinfo(jnp.int32).min


def empty_slot_keys(shape):
    """Distinct negative keys of the empty slots [R, k]: -1, -2, ..."""
    return -1 - jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def merge_into_slots(s, key, pay, sv, sk, sp):
    """Fold a score tile into per-row top-k slots, with ``lax.top_k``'s
    order: larger score first, and on equal scores the smaller key.

    s, key, pay [R, W]: the tile's scores, their ordering keys (increasing
    along the sweep, so a later tile never wins a tie) and payloads. sv, sk,
    sp [R, k]: the slots (unsorted). A score enters only when it beats the
    row's worst slot strictly; the worst slot is the lowest score with the
    largest key. Every value stays 2-D: Mosaic reduces along lanes into
    [R, 1] columns. Returns the new (sv, sk, sp)."""
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)

    def worst(sv):
        return jnp.min(sv, axis=1, keepdims=True)

    def pending(tmax, smin):
        return jnp.max(jnp.where(tmax > smin, 1, 0)) > 0

    def cond(c):
        return pending(c[4], c[5])

    def body(c):
        s, sv, sk, sp, tmax, smin = c
        first = jnp.min(jnp.where(s == tmax, col, s.shape[1]), axis=1,
                        keepdims=True)
        pick = col == first                               # [R, W] one-hot
        tkey = jnp.max(jnp.where(pick, key, _IMIN), axis=1, keepdims=True)
        tpay = jnp.max(jnp.where(pick, pay, _IMIN), axis=1, keepdims=True)
        at_min = sv == smin
        out_key = jnp.max(jnp.where(at_min, sk, _IMIN), axis=1, keepdims=True)
        put = at_min & (sk == out_key) & (tmax > smin)    # [R, k] one-hot
        sv = jnp.where(put, tmax, sv)
        sk = jnp.where(put, tkey, sk)
        sp = jnp.where(put, tpay, sp)
        s = jnp.where(pick, NEG, s)
        return (s, sv, sk, sp, jnp.max(s, axis=1, keepdims=True), worst(sv))

    c0 = (s, sv, sk, sp, jnp.max(s, axis=1, keepdims=True), worst(sv))
    _, sv, sk, sp, _, _ = jax.lax.while_loop(cond, body, c0)
    return sv, sk, sp


def sorted_slots(sv, sk, sp):
    """The slots in ``lax.top_k`` order (score descending, ties by the
    smaller key): k extraction sweeps over [R, k]. Returns (v, key, pay)."""
    k = sv.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, sv.shape, 1)
    left = jnp.ones(sv.shape, jnp.bool_)
    ov, ok, op = sv, sk, sp
    for i in range(k):                              # k static -> unrolled
        m = jnp.max(jnp.where(left, sv, NEG), axis=1, keepdims=True)
        kmin = jnp.min(jnp.where(left & (sv == m), sk, _IMAX), axis=1,
                       keepdims=True)
        pick = left & (sv == m) & (sk == kmin)
        pay = jnp.max(jnp.where(pick, sp, _IMIN), axis=1, keepdims=True)
        ov = jnp.where(col == i, m, ov)
        ok = jnp.where(col == i, kmin, ok)
        op = jnp.where(col == i, pay, op)
        left = left & ~pick
    return ov, ok, op


def _dist_topk_kernel(q_ref, k_ref, vals_ref, idx_ref, acc_v, acc_i, *,
                      bn: int, n_valid: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_v[...] = jnp.full_like(acc_v, NEG)
        acc_i[...] = empty_slot_keys(acc_i.shape)

    q = q_ref[...]                                # [bq, D]
    kb = k_ref[...]                               # [bn, D]
    # bf16 operands by design (the build's pass 1): an explicit DEFAULT
    # keeps a float32 default_matmul_precision from asking Mosaic for an
    # fp32 contraction of bf16 operands, which it refuses
    scores = jax.lax.dot_general(
        q, kb, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32)       # [bq, bn] MXU
    ids = j * bn + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(ids < n_valid, scores, NEG)  # padded cols never win
    acc_v[...], acc_i[...], _ = merge_into_slots(
        scores, ids, ids, acc_v[...], acc_i[...], acc_i[...])

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        v, i, _ = sorted_slots(acc_v[...], acc_i[...], acc_i[...])
        vals_ref[...] = v
        idx_ref[...] = i


def dist_topk(q: jax.Array, kmat: jax.Array, kprime: int, *,
              block_q: int = 128, block_n: int = 128,
              col_offset: int = 0, interpret: bool):
    """q [Nq, D] x kmat [Nk, D] -> (vals [Nq, k'], ids [Nq, k'] global ids
    offset by col_offset). Blocks shrink to the operands (rounded up to the
    16-row bf16 tile); rows/cols are padded to block multiples."""
    nq, d = q.shape
    nk = kmat.shape[0]
    block_q = min(block_q, -(-nq // 16) * 16)
    block_n = min(block_n, -(-nk // 16) * 16)
    pq, pn = (-nq) % block_q, (-nk) % block_n
    if pq:
        q = jnp.pad(q, ((0, pq), (0, 0)))
    if pn:
        kmat = jnp.pad(kmat, ((0, pn), (0, 0)))  # masked inside the kernel
    nq_p, nk_p = q.shape[0], kmat.shape[0]
    grid = (nq_p // block_q, nk_p // block_n)
    vals, idx = pl.pallas_call(
        functools.partial(_dist_topk_kernel, bn=block_n, n_valid=nk),
        out_shape=(jax.ShapeDtypeStruct((nq_p, kprime), jnp.float32),
                   jax.ShapeDtypeStruct((nq_p, kprime), jnp.int32)),
        grid=grid,
        in_specs=[pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
                  pl.BlockSpec((block_n, d), lambda i, j: (j, 0))],
        out_specs=(pl.BlockSpec((block_q, kprime), lambda i, j: (i, 0)),
                   pl.BlockSpec((block_q, kprime), lambda i, j: (i, 0))),
        scratch_shapes=[pltpu.VMEM((block_q, kprime), jnp.float32),
                        pltpu.VMEM((block_q, kprime), jnp.int32)],
        interpret=interpret,
        name="knn_dist_topk",
    )(q, kmat)
    vals, idx = vals[:nq], idx[:nq]
    real = (idx >= 0) & (idx < nk)
    vals = jnp.where(real, vals, NEG)
    idx = jnp.where(real, idx + col_offset, -1)
    return vals, idx
