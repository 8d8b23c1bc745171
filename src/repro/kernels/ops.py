"""jit'd public wrappers around the Pallas kernels.

The kernels compile through Mosaic on a TPU backend and run in the Pallas
interpreter on every other backend (``use_interpreter``), so the CPU tests
check the same kernel bodies the chip runs.

The two CE entry points the heads consume are ``ce_shard_stats`` (dense
vocab-shard sweep) and ``sparse_ce_stats`` (active-class gather + CE). Both
are ``jax.custom_vjp`` over per-row ONLINE-SOFTMAX STATS (m, z, corr, amax)
rather than over a scalar loss: the distributed completion (pmax/psum across
model shards, metrics) is plain jnp in ``core.sharded_softmax``, and its
autodiff delivers the per-row cotangents (gz, gc) that the streaming
backward kernels consume. The running max m is non-differentiable by
construction — its true total derivative cancels exactly against z's
internal rescaling (z is Σ exp(s - m), so z·e^m is m-free), which is why the
backward kernels can ignore its cotangent and still be exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ce_softmax as _ce
from repro.kernels import ivf_rerank as _ir
from repro.kernels import knn_dist_topk as _dk
from repro.kernels import sparse_ce as _sp
from repro.kernels import topk_dc as _dc


def use_interpreter() -> bool:
    """Mosaic compiles the kernels only for a TPU; elsewhere interpret."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# divide-and-conquer top-k (paper Fig. 5)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("k", "chunk", "block_rows"))
def topk_dc(x: jax.Array, k: int, *, chunk: int = 2048, block_rows: int = 8):
    """Exact top-k of a flat tensor via chunked two-stage selection.
    Returns (vals [k] desc, ids [k] int32 into x)."""
    n = x.shape[0]
    if n <= chunk:
        vals, ids = jax.lax.top_k(x.astype(jnp.float32), min(k, n))
        return vals, ids.astype(jnp.int32)
    pad = (-n) % chunk
    xp = jnp.pad(x.astype(jnp.float32), (0, pad), constant_values=-jnp.inf)
    chunks = xp.reshape(-1, chunk)
    kk = min(k, chunk)
    sub_v, sub_i = _dc.stage1_topk(chunks, kk, block_rows=block_rows,
                                   interpret=use_interpreter())  # stage 1
    base = (jnp.arange(chunks.shape[0], dtype=jnp.int32) * chunk)[:, None]
    flat_v = sub_v.reshape(-1)
    flat_i = (sub_i + base).reshape(-1)
    vals, pos = jax.lax.top_k(flat_v, min(k, flat_v.shape[0]))  # stage 2
    return vals, flat_i[pos]


@functools.partial(jax.jit, static_argnames=("k", "chunk", "block_rows"))
def topk_threshold(x_abs: jax.Array, k: int, *, chunk: int = 2048,
                   block_rows: int = 8):
    """k-th largest value (DGC threshold) via the d&c kernel."""
    vals, _ = topk_dc(x_abs, k, chunk=chunk, block_rows=block_rows)
    return vals[-1]


@functools.partial(jax.jit, static_argnames=("k", "chunk", "block_rows"))
def topk_rows(x: jax.Array, k: int, *, chunk: int = 2048,
              block_rows: int = 8):
    """Row-wise exact top-k of x [B, N] via the stage-1 kernel: each row is
    chunked, per-chunk top-k runs in parallel on the kernel, and a tiny
    stage-2 ``lax.top_k`` merges the survivors. Returns (vals [B, k] desc,
    ids [B, k] int32 column indices). Powers the top-k serving path."""
    b, n = x.shape
    kk = min(k, n)
    if n <= chunk:
        vals, ids = _dc.stage1_topk(x, kk, block_rows=block_rows,
                                    interpret=use_interpreter())
        return vals[:, :kk], ids[:, :kk]
    pad = (-n) % chunk
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, pad)),
                 constant_values=-jnp.inf)
    nch = xp.shape[1] // chunk
    chunks = xp.reshape(b * nch, chunk)
    kc = min(kk, chunk)
    sub_v, sub_i = _dc.stage1_topk(chunks, kc, block_rows=block_rows,
                                   interpret=use_interpreter())
    base = (jnp.arange(nch, dtype=jnp.int32) * chunk)[None, :, None]
    flat_v = sub_v.reshape(b, nch * kc)
    flat_i = (sub_i.reshape(b, nch, kc) + base).reshape(b, nch * kc)
    vals, pos = jax.lax.top_k(flat_v, kk)
    return vals, jnp.take_along_axis(flat_i, pos, axis=1)


@functools.partial(jax.jit, static_argnames=("k", "block_a"))
def ivf_rerank(f, w, cand, k: int, *, block_a: int = 128):
    """Fused gather + per-row top-k over IVF candidate lists (the serving
    index's rerank stage). f [B, D]; w [V_loc, D] — candidate rows are
    gathered in-kernel; cand [B, A] int32 local row ids, -1 = empty slot.
    Returns (vals [B, k] fp32 desc, ids [B, k] int32 row ids, -1 when a row
    has fewer than k candidates). Neither the gathered [A, D] weights nor
    the [B, A] scores reach HBM."""
    return _ir.ivf_rerank(f, w, cand, k, block_a=block_a,
                          interpret=use_interpreter())


# ---------------------------------------------------------------------------
# fused distance + top-k' (graph build inner loop)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("kprime", "block_q", "block_n",
                                             "col_offset"))
def dist_topk(q: jax.Array, kmat: jax.Array, kprime: int, *,
              block_q: int = 128, block_n: int = 128, col_offset: int = 0):
    return _dk.dist_topk(q, kmat, kprime, block_q=block_q, block_n=block_n,
                         col_offset=col_offset, interpret=use_interpreter())


# ---------------------------------------------------------------------------
# fused streaming softmax-CE (the paper's softmax stage)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def ce_shard_stats(f, w, y, limit, scale: float = 1.0, block_v: int = 512):
    """Streaming online-softmax stats of f [B,D] against the vocab shard
    w [V,D]: per-row (m, z, corr, amax). y [B] are LOCAL ids (-1 / out of
    range = label not owned by this shard); ``limit`` (traced int scalar)
    masks columns >= limit (Megatron vocab padding). The [B, V] logit tensor
    never materializes; m and amax are non-differentiable statistics."""
    return _ce.ce_forward(f, w, y, limit=limit, scale=scale, block_v=block_v,
                          interpret=use_interpreter())


def _ce_shard_fwd(f, w, y, limit, scale, block_v):
    m, z, corr, amax = _ce.ce_forward(f, w, y, limit=limit, scale=scale,
                                      block_v=block_v,
                                      interpret=use_interpreter())
    return (m, z, corr, amax), (f, w, y, limit, m)


def _ce_shard_bwd(scale, block_v, res, cts):
    f, w, y, limit, m = res
    _, gz, gc, _ = cts          # gm / gamax ignored: exact (see module doc)
    df, dw = _ce.ce_backward(f, w, y, m, gz, gc, limit=limit, scale=scale,
                             block_v=block_v, interpret=use_interpreter())
    return df.astype(f.dtype), dw.astype(w.dtype), None, None


ce_shard_stats.defvjp(_ce_shard_fwd, _ce_shard_bwd)


@functools.partial(jax.jit, static_argnames=("scale", "block_v"))
def fused_ce(f, w, y, scale: float = 1.0, block_v: int = 512):
    """Mean CE of rows whose label is in-shard; [B,V] never materializes.
    f [B,D], w [V,D], y [B] local ids (-1/out-of-range = not owned here).
    Single-shard convenience over ``ce_shard_stats`` (grads flow through its
    custom_vjp)."""
    v = w.shape[0]
    m, z, corr, _ = ce_shard_stats(f, w, y, jnp.asarray(v, jnp.int32),
                                   scale, block_v)
    per = jnp.log(z) + m - corr      # corr is 0 for unowned rows
    return jnp.mean(per)


@functools.partial(jax.jit, static_argnames=("scale", "block_v"))
def fused_ce_stats(f, w, y, *, scale: float = 1.0, block_v: int = 512):
    """(m, z, corr) building blocks for the distributed (sharded) loss."""
    m, z, corr, _ = _ce.ce_forward(f, w, y, scale=scale, block_v=block_v,
                                   interpret=use_interpreter())
    return m, z, corr


# ---------------------------------------------------------------------------
# active-class sparse CE (KNN / selective / sampled candidate sets)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def sparse_ce_stats(f, w, ids, gids, bias, valid, y, scale: float = 1.0,
                    block_a: int = 128, mask_hits: bool = False):
    """Fused gather + streaming CE stats over an active-class set.

    f [B,D]; w [V_loc,D] (full local shard — rows are gathered in-kernel);
    ids [A] local candidate rows; gids [A] global candidate ids; bias [A]
    per-column logit shift (-logQ for sampled, zeros otherwise); valid [A]
    column mask; y [B] GLOBAL labels. ``mask_hits`` drops candidates whose
    gid equals the row label from z (sampled accidental hits) instead of
    folding them into corr (knn / selective label columns).

    Returns per-row fp32 (m, z, corr, amax-col); m / amax non-diff. Only f
    and w receive gradients; dW is a compact [A, D] kernel output
    scatter-added into the shard here."""
    return _sp.sparse_ce_forward(f, w, ids, gids, bias, valid, y,
                                 scale=scale, block_a=block_a,
                                 mask_hits=mask_hits,
                                 interpret=use_interpreter())


def _sparse_ce_fwd(f, w, ids, gids, bias, valid, y, scale, block_a,
                   mask_hits):
    m, z, corr, amax = _sp.sparse_ce_forward(
        f, w, ids, gids, bias, valid, y, scale=scale, block_a=block_a,
        mask_hits=mask_hits, interpret=use_interpreter())
    return (m, z, corr, amax), (f, w, ids, gids, bias, valid, y, m)


def _sparse_ce_bwd(scale, block_a, mask_hits, res, cts):
    f, w, ids, gids, bias, valid, y, m = res
    _, gz, gc, _ = cts          # gm / gamax ignored: exact (see module doc)
    df, dwa = _sp.sparse_ce_backward(
        f, w, ids, gids, bias, valid, y, m, gz, gc, scale=scale,
        block_a=block_a, mask_hits=mask_hits, interpret=use_interpreter())
    safe = jnp.clip(ids.astype(jnp.int32), 0, w.shape[0] - 1)
    dw = jnp.zeros(w.shape, jnp.float32).at[safe].add(dwa)
    return (df.astype(f.dtype), dw.astype(w.dtype), None, None, None, None,
            None)


sparse_ce_stats.defvjp(_sparse_ce_fwd, _sparse_ce_bwd)
