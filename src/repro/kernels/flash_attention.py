"""Pallas TPU kernel: fused flash attention (forward).

§Perf pair-1 finding (EXPERIMENTS.md): the pure-JAX flash path is memory-
bound because every [q_block, kv_block] probability tile crosses an XLA
fusion boundary (HBM round-trip) — at prefill_32k that's ~2.3 TB/device of
prob traffic vs 0.8 s of matmul work. The structural fix is this kernel:
the score/prob tile lives ONLY in VMEM; HBM sees q, k, v, o exactly once.

Layout: inputs flattened to [BH, S, Dh]; grid = (BH, q_blocks, kv_blocks)
with the kv dimension innermost; VMEM scratch carries the online-softmax
(m, l, acc) across the kv sweep and the output flushes on the last tile.
Causality lets the sweep skip nothing here (masked tiles still counted) —
block-level skipping is a further ~2x (documented, not implemented).

Off a TPU the kernel runs in the Pallas interpreter (``ops.use_interpreter``)
and is validated against the pure-jnp oracle; the GSPMD dry-run keeps the
jnp path because Pallas cannot lower for TPU on a CPU backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import use_interpreter


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc, m_scr, l_scr, *,
                  bq: int, bkv: int, scale: float, causal: bool,
                  window: int, n_valid: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)

    q = q_ref[0]                                  # [bq, Dh]
    k = k_ref[0]                                  # [bkv, Dh]
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    i = pl.program_id(1)
    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    kpos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    valid = kpos < n_valid
    if causal:
        valid &= kpos <= qpos
    if window > 0:
        valid &= kpos > qpos - window
    s = jnp.where(valid, s, -jnp.inf)

    m_old = m_scr[...][:, 0]                      # [bq]
    m_new = jnp.maximum(m_old, jnp.max(s, axis=1))
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(jnp.where(valid, s - safe_m[:, None], -jnp.inf))  # [bq,bkv]
    corr = jnp.where(jnp.isfinite(m_old), jnp.exp(m_old - safe_m), 0.0)
    l_scr[...] = (l_scr[...][:, 0] * corr + jnp.sum(p, axis=1))[:, None]
    acc[...] = acc[...] * corr[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new[:, None]

    @pl.when(j == pl.num_programs(2) - 1)
    def _flush():
        l = l_scr[...][:, 0]
        o_ref[0] = (acc[...] / jnp.maximum(l, 1e-30)[:, None]).astype(
            o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_kv: int = 128):
    """q: [BH, Sq, Dh]; k, v: [BH, T, Dh] -> [BH, Sq, Dh].

    GQA is handled by the caller repeating/reshaping heads into BH.
    """
    bh, sq, dh = q.shape
    t = k.shape[1]
    scale = 1.0 / (dh ** 0.5)
    pq, pk = (-sq) % block_q, (-t) % block_kv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    sqp, tp = q.shape[1], k.shape[1]
    grid = (bh, sqp // block_q, tp // block_kv)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, bq=block_q, bkv=block_kv,
                          scale=scale, causal=causal, window=window,
                          n_valid=t),
        out_shape=jax.ShapeDtypeStruct((bh, sqp, dh), q.dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
                  pl.BlockSpec((1, block_kv, dh), lambda b, i, j: (b, j, 0)),
                  pl.BlockSpec((1, block_kv, dh), lambda b, i, j: (b, j, 0))],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, dh), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
        interpret=use_interpreter(),
    )(q, k, v)
    return out[:, :sq]
