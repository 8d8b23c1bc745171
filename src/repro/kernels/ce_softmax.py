"""Pallas TPU kernel: streaming fused softmax cross-entropy over a vocab
shard — the paper's softmax-stage hotspot (§3.2: ">80% of the time is spent
in the softmax stage ... over 10 GB for the output space of the last fc").

Forward: grid sweeps vocab tiles; each tile does an MXU matmul
f [B,D] @ W_tile [bv,D]^T and folds it into online-softmax running stats
(max m, sum z, label logit corr, argmax col) carried in VMEM scratch — the
[B, V_local] logit tensor NEVER exists in HBM (that is the 10 GB the paper
pays). A traced ``limit`` scalar (SMEM) masks columns >= limit, which covers
both Megatron-style vocab padding (n_valid) and the kernel's own block_v
padding in one mechanism. (Candidate-set CE with per-column bias — the
sampled head's -logQ — lives in sparse_ce.py, not here.)

Backward: second sweep recomputes each tile's scores and applies the
caller-provided per-row cotangents (gz for the partition sum, gc for the
label logit):
    dlogits_j = (exp(s_j - m) * gz + onehot_j(label) * gc) * scale
    df += dlogits @ W_tile ; dW_tile = dlogits^T @ f
Parameterizing the backward by (gz, gc) instead of a scalar loss cotangent
lets the SAME kernel serve the single-shard loss (ops.fused_ce: gz = g/z,
gc = -g) and the distributed sharded loss (ops.ce_shard_stats: gz/gc arrive
from autodiff of the cross-shard pmax/psum completion). The per-row max m is
returned as a non-differentiable statistic — its true total derivative
cancels exactly against z's internal rescaling, so ignoring its cotangent is
mathematically exact, not an approximation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -jnp.inf


def _fwd_kernel(lim_ref, f_ref, w_ref, y_ref,
                m_ref, z_ref, corr_ref, amax_ref,
                acc_m, acc_z, acc_c, acc_a, *, bv: int, scale: float):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        acc_m[...] = jnp.full_like(acc_m, NEG)
        acc_z[...] = jnp.zeros_like(acc_z)
        acc_c[...] = jnp.zeros_like(acc_c)
        acc_a[...] = jnp.full_like(acc_a, -1)

    f = f_ref[...]                                    # [B, D]
    w = w_ref[...]                                    # [bv, D]
    s = jax.lax.dot_general(f, w, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    y = y_ref[...]                                    # [B] local label ids
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = col < lim_ref[0]                          # vocab + block padding
    s = jnp.where(valid, s, NEG)
    hit = col == y[:, None]
    # fold the label logit (each label hits exactly one tile)
    acc_c[...] += jnp.sum(jnp.where(hit, s, 0.0), axis=1)

    m_old = acc_m[...]
    tile_m = jnp.max(s, axis=1)                       # NEG if tile all-masked
    tile_a = j * bv + jnp.argmax(s, axis=1).astype(jnp.int32)
    m_new = jnp.maximum(m_old, tile_m)
    acc_a[...] = jnp.where(tile_m > m_old, tile_a, acc_a[...])
    # rescale the running sum to the new max (online softmax); masked columns
    # contribute 0 via the `valid` select, which also discards the NaN from
    # exp(-inf - -inf) on fully-masked rows
    zcorr = jnp.where(jnp.isfinite(m_old), jnp.exp(m_old - m_new), 0.0)
    p = jnp.where(valid, jnp.exp(s - m_new[:, None]), 0.0)
    acc_z[...] = acc_z[...] * zcorr + jnp.sum(p, axis=1)
    acc_m[...] = m_new

    @pl.when(j == pl.num_programs(0) - 1)
    def _flush():
        m_ref[...] = acc_m[...]
        z_ref[...] = acc_z[...]
        corr_ref[...] = acc_c[...]
        amax_ref[...] = acc_a[...]


def ce_forward(f, w, y, *, limit=None, block_v: int = 512,
               scale: float = 1.0, interpret: bool):
    """f [B,D], w [V,D], y [B] local ids (out-of-range = not owned).

    ``limit`` (traced int scalar, default V) masks columns >= limit out of
    the softmax — Megatron vocab padding on the owning shard.
    Returns per-row fp32 (m, z, corr, amax): running max, partition sum
    relative to m, label logit, argmax column (-1 when all columns masked).
    """
    b, d = f.shape
    v = w.shape[0]
    bv = min(block_v, max(8, v))
    pv = (-v) % bv
    if pv:
        w = jnp.pad(w, ((0, pv), (0, 0)))
    vp = w.shape[0]
    if limit is None:
        limit = jnp.asarray(v, jnp.int32)
    lim = jnp.minimum(jnp.asarray(limit, jnp.int32), v).reshape(1)
    # out-of-shard labels must not fold anything: map them to -1 (never
    # matches the col iota)
    y = jnp.where((y >= 0) & (y < v), y, -1)
    m, z, corr, amax = pl.pallas_call(
        functools.partial(_fwd_kernel, bv=bv, scale=scale),
        out_shape=(jax.ShapeDtypeStruct((b,), jnp.float32),
                   jax.ShapeDtypeStruct((b,), jnp.float32),
                   jax.ShapeDtypeStruct((b,), jnp.float32),
                   jax.ShapeDtypeStruct((b,), jnp.int32)),
        grid=(vp // bv,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((b, d), lambda j: (0, 0)),
                  pl.BlockSpec((bv, d), lambda j: (j, 0)),
                  pl.BlockSpec((b,), lambda j: (0,))],
        out_specs=(pl.BlockSpec((b,), lambda j: (0,)),
                   pl.BlockSpec((b,), lambda j: (0,)),
                   pl.BlockSpec((b,), lambda j: (0,)),
                   pl.BlockSpec((b,), lambda j: (0,))),
        scratch_shapes=[pltpu.VMEM((b,), jnp.float32),
                        pltpu.VMEM((b,), jnp.float32),
                        pltpu.VMEM((b,), jnp.float32),
                        pltpu.VMEM((b,), jnp.int32)],
        interpret=interpret,
        name="ce_fwd",
    )(lim, f.astype(jnp.float32), w.astype(jnp.float32), y.astype(jnp.int32))
    return m, z, corr, amax


def _bwd_kernel(lim_ref, f_ref, w_ref, y_ref, m_ref, gz_ref, gc_ref,
                dw_ref, df_ref, acc_df, *, bv: int, scale: float):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        acc_df[...] = jnp.zeros_like(acc_df)

    f = f_ref[...]
    w = w_ref[...]
    s = jax.lax.dot_general(f, w, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    m = m_ref[...]                                    # [B] forward's row max
    gz = gz_ref[...]                                  # [B] dL/dz
    gc = gc_ref[...]                                  # [B] dL/dcorr
    y = y_ref[...]
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = col < lim_ref[0]
    # reshape m before the test: Mosaic cannot reshape an i1 vector
    m = m[:, None]
    p = jnp.where(valid & jnp.isfinite(m),
                  jnp.exp(s - m), 0.0)                # [B, bv] exp rel. to m
    hit = (col == y[:, None]).astype(jnp.float32)
    dl = (p * gz[:, None] + hit * gc[:, None]) * scale
    dw_ref[...] = jax.lax.dot_general(
        dl, f, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # [bv, D]
    acc_df[...] += jax.lax.dot_general(
        dl, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # [B, D]

    @pl.when(j == pl.num_programs(0) - 1)
    def _flush():
        df_ref[...] = acc_df[...]


def ce_backward(f, w, y, m, gz, gc, *, limit=None,
                block_v: int = 512, scale: float = 1.0, interpret: bool):
    """Streamed backward from per-row cotangents.

    m is the forward's per-row running max (residual); gz / gc are the
    cotangents of the forward's z / corr outputs. Returns (df [B,D],
    dw [V,D]) fp32.
    """
    b, d = f.shape
    v = w.shape[0]
    bv = min(block_v, max(8, v))
    pv = (-v) % bv
    if pv:
        w = jnp.pad(w, ((0, pv), (0, 0)))
    vp = w.shape[0]
    if limit is None:
        limit = jnp.asarray(v, jnp.int32)
    lim = jnp.minimum(jnp.asarray(limit, jnp.int32), v).reshape(1)
    y = jnp.where((y >= 0) & (y < v), y, -1)
    dw, df = pl.pallas_call(
        functools.partial(_bwd_kernel, bv=bv, scale=scale),
        out_shape=(jax.ShapeDtypeStruct((vp, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, d), jnp.float32)),
        grid=(vp // bv,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((b, d), lambda j: (0, 0)),
                  pl.BlockSpec((bv, d), lambda j: (j, 0)),
                  pl.BlockSpec((b,), lambda j: (0,)),
                  pl.BlockSpec((b,), lambda j: (0,)),
                  pl.BlockSpec((b,), lambda j: (0,)),
                  pl.BlockSpec((b,), lambda j: (0,))],
        out_specs=(pl.BlockSpec((bv, d), lambda j: (j, 0)),
                   pl.BlockSpec((b, d), lambda j: (0, 0))),
        scratch_shapes=[pltpu.VMEM((b, d), jnp.float32)],
        interpret=interpret,
        name="ce_bwd",
    )(lim, f.astype(jnp.float32), w.astype(jnp.float32), y.astype(jnp.int32),
      m, gz.astype(jnp.float32), gc.astype(jnp.float32))
    return df, dw[:v]
