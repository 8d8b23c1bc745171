"""Pallas TPU kernel: stage 1 of divide-and-conquer top-k (paper Fig. 5).

The paper's DGC bottleneck is selecting top-k from a large flat gradient
tensor. Their fix: split into M chunks, select top-k per chunk in parallel
(this kernel), then top-k over the M*k survivors (tiny — stage 2 in ops.py).
Exact, no sampling.

TPU mapping: the flat tensor is reshaped [M, C]; the grid tiles M into
row-blocks resident in VMEM; per row, k max-extraction sweeps over the lane
dimension (k is small and static, so the sweeps unroll onto the VPU; C is a
multiple of 128 lanes after padding). No HBM round-trip between the k sweeps
— that's the win over k separate jnp.max calls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -jnp.inf


def _stage1_kernel(x_ref, vals_ref, idx_ref, *, k: int):
    x = x_ref[...].astype(jnp.float32)           # [bm, C]
    bm, c = x.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (bm, c), 1)
    for i in range(k):                           # k static -> unrolled sweeps
        m = jnp.max(x, axis=1)                   # [bm]
        am = jnp.argmax(x, axis=1).astype(jnp.int32)
        vals_ref[:, i] = m
        idx_ref[:, i] = am
        x = jnp.where(col == am[:, None], NEG, x)


def stage1_topk(chunks: jax.Array, k: int, *, block_rows: int = 8,
                interpret: bool):
    """chunks: [M, C] -> (vals [M, k] fp32 desc-sorted, idx [M, k] int32)."""
    m, c = chunks.shape
    pad_m = (-m) % block_rows
    if pad_m:
        chunks = jnp.pad(chunks, ((0, pad_m), (0, 0)), constant_values=NEG)
    mp = chunks.shape[0]
    grid = (mp // block_rows,)
    vals, idx = pl.pallas_call(
        functools.partial(_stage1_kernel, k=k),
        out_shape=(jax.ShapeDtypeStruct((mp, k), jnp.float32),
                   jax.ShapeDtypeStruct((mp, k), jnp.int32)),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, c), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((block_rows, k), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, k), lambda i: (i, 0))),
        interpret=interpret,
        name="topk_rows",
    )(chunks)
    return vals[:m], idx[:m]
