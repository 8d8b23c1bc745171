"""Pallas TPU kernel: active-class sparse softmax cross-entropy — the fused
analogue of dynamic class selection (Zhang et al., AAAI'18) that the KNN and
selective heads run in dense form, and the candidate-set CE of the sampled
head.

Each model shard scores only A active local classes (KNN-graph selection /
LSH buckets / drawn negatives) instead of its full V_local shard. The ref
path gathers ``w[ids]`` to an [A, D] tensor in HBM, matmuls to a dense
[B, A] logit tensor, and lets autodiff scatter the gradient back. This
kernel fuses all three stages:

  forward — grid sweeps tiles of the active-id list; per tile, the [ba, D]
  weight rows are DMA'd one row each from the [V_local, D] shard, which
  stays in HBM, into a VMEM scratch tile (the tile's ids arrive as an SMEM
  block), matmul'd against f [B, D] on the MXU, bias-shifted (the sampled
  head's -logQ), masked, and folded into online-softmax running stats (m,
  z, corr, argmax). Neither the gathered [A, D] weights nor the [B, A]
  logits ever reach HBM.

  per-column masking is computed in-kernel from the GLOBAL candidate ids vs
  each row's global label: ``mask_hits=False`` folds the FIRST label hit
  into corr (knn / selective — the label is a candidate; duplicates from
  random filler collisions count once, matching the ref path's
  ``argmax(hit)``); ``mask_hits=True`` drops every hit from z entirely
  (sampled softmax's accidental-hit correction — the label is scored
  separately by the caller).

  backward — second sweep re-gathers + recomputes each tile's scores and
  applies per-row cotangents (gz, gc) exactly like ce_softmax's backward:
  dlogits = (exp(s - m) * gz + onehot * gc) * scale. dW comes out as the
  compact per-tile [ba, D] product; the wrapper (ops.sparse_ce_stats)
  scatter-adds it into the [V_local, D] shard.

Wrapped by ``ops.sparse_ce_stats`` (jax.custom_vjp).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -jnp.inf


def gather_rows(ids_ref, w_hbm, tile, sem):
    """DMA rows ``w_hbm[ids_ref[0, r]]`` into ``tile[r]`` for every row r
    of the VMEM scratch: start every copy, then wait for each. ``w_hbm`` is
    [V, 1, D] and ``tile`` [ba, 1, D]: a copy may not slice a single row
    out of the tiled minor dimensions, so each row gets a leading index."""
    ba = tile.shape[0]

    def copy(r, row):
        return pltpu.make_async_copy(w_hbm.at[row], tile.at[r], sem)

    def start(r, _):
        copy(r, ids_ref[0, r]).start()
        return 0

    def wait(r, _):
        copy(r, 0).wait()
        return 0

    jax.lax.fori_loop(0, ba, start, 0)
    jax.lax.fori_loop(0, ba, wait, 0)
    return tile[:, 0, :]                              # [ba, D]


def _first_hit(hit, seen):
    """Leftmost hit column per row, and only if no earlier tile hit: the
    ref path's ``argmax(hit)`` counts the label column exactly ONCE even
    when duplicate candidate ids equal the label (random fillers can
    collide), so corr / the backward onehot must too. ``seen`` is [B, 1]."""
    col = jax.lax.broadcasted_iota(jnp.int32, hit.shape, 1)
    first = jnp.min(jnp.where(hit, col, hit.shape[1]), axis=1, keepdims=True)
    return hit & (col == first) & (seen == 0)


def _tile_masks(gids_ref, valid_ref, y_ref):
    """(hit [B, ba], col_ok [1, ba]) of one candidate tile. Vectors are
    reshaped before any comparison: Mosaic cannot reshape an i1 vector."""
    col_ok = valid_ref[...] > 0
    hit = (gids_ref[...] == y_ref[...][:, None]) & col_ok
    return hit, col_ok


def _fwd_kernel(ids_ref, f_ref, w_hbm, gids_ref, bias_ref, valid_ref, y_ref,
                m_ref, z_ref, corr_ref, amax_ref,
                tile, sem, acc_m, acc_z, acc_c, acc_a, acc_seen,
                *, ba: int, scale: float, mask_hits: bool):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        acc_m[...] = jnp.full_like(acc_m, NEG)
        acc_z[...] = jnp.zeros_like(acc_z)
        acc_c[...] = jnp.zeros_like(acc_c)
        acc_a[...] = jnp.full_like(acc_a, -1)
        acc_seen[...] = jnp.zeros_like(acc_seen)

    w_t = gather_rows(ids_ref, w_hbm, tile, sem)     # [ba, D]
    f = f_ref[...]                                    # [B, D]
    s = jax.lax.dot_general(f, w_t, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = s + bias_ref[...]
    hit, col_ok = _tile_masks(gids_ref, valid_ref, y_ref)
    if mask_hits:                                     # sampled: drop dupes
        keep = col_ok & ~hit
    else:                                             # knn/selective: corr
        keep = jnp.broadcast_to(col_ok, s.shape)
        first = _first_hit(hit, acc_seen[...][:, None])
        acc_c[...] += jnp.sum(jnp.where(first, s, 0.0), axis=1)
        acc_seen[...] = jnp.maximum(
            acc_seen[...], jnp.max(hit.astype(jnp.int32), axis=1))
    s = jnp.where(keep, s, NEG)

    m_old = acc_m[...]
    tile_m = jnp.max(s, axis=1)
    tile_a = j * ba + jnp.argmax(s, axis=1).astype(jnp.int32)
    m_new = jnp.maximum(m_old, tile_m)
    acc_a[...] = jnp.where(tile_m > m_old, tile_a, acc_a[...])
    zcorr = jnp.where(jnp.isfinite(m_old), jnp.exp(m_old - m_new), 0.0)
    p = jnp.where(keep, jnp.exp(s - m_new[:, None]), 0.0)
    acc_z[...] = acc_z[...] * zcorr + jnp.sum(p, axis=1)
    acc_m[...] = m_new

    @pl.when(j == pl.num_programs(0) - 1)
    def _flush():
        m_ref[...] = acc_m[...]
        z_ref[...] = acc_z[...]
        corr_ref[...] = acc_c[...]
        amax_ref[...] = acc_a[...]


def _tile_cols(w, ids, gids, bias, valid, ba):
    """Pad the candidate columns to a multiple of ``ba`` and lay each
    per-column vector out as [n_tiles, 1, ba], so that one grid step reads
    one [1, ba] block (ids into SMEM, the rest into VMEM); ``w`` becomes
    [V, 1, D] for the row DMAs."""
    v, d = w.shape
    a = ids.shape[0]
    pa = (-a) % ba
    ids = jnp.clip(ids.astype(jnp.int32), 0, v - 1)
    gids = gids.astype(jnp.int32)
    bias = bias.astype(jnp.float32)
    valid = valid.astype(jnp.int32)
    if pa:
        ids = jnp.pad(ids, (0, pa))                  # clipped-safe row 0
        gids = jnp.pad(gids, (0, pa), constant_values=-1)
        bias = jnp.pad(bias, (0, pa))
        valid = jnp.pad(valid, (0, pa))              # padded cols invalid
    ap = a + pa
    cols = tuple(x.reshape(ap // ba, 1, ba) for x in (ids, gids, bias, valid))
    return (w.astype(jnp.float32).reshape(v, 1, d),) + cols + (ap,)


def _col_specs(ba):
    """BlockSpecs of (ids, w, gids, bias, valid) as laid out by
    ``_tile_cols``."""
    tile = pl.BlockSpec((None, 1, ba), lambda j: (j, 0, 0))
    return [pl.BlockSpec((None, 1, ba), lambda j: (j, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY), tile, tile, tile]


def sparse_ce_forward(f, w, ids, gids, bias, valid, y, *, block_a: int = 128,
                      scale: float = 1.0, mask_hits: bool = False,
                      interpret: bool):
    """f [B,D]; w [V_loc,D]; ids [A] local rows of w; gids [A] global class
    ids of the candidates; bias [A] per-column logit shift; valid [A] col
    mask (int/bool); y [B] global labels. Returns per-row fp32
    (m, z, corr, amax-col)."""
    b, d = f.shape
    ba = min(block_a, max(8, ids.shape[0]))
    w3, ids, gids, bias, valid, ap = _tile_cols(w, ids, gids, bias, valid,
                                                ba)
    ids_spec, w_spec, *col_specs = _col_specs(ba)
    m, z, corr, amax = pl.pallas_call(
        functools.partial(_fwd_kernel, ba=ba, scale=scale,
                          mask_hits=mask_hits),
        out_shape=(jax.ShapeDtypeStruct((b,), jnp.float32),
                   jax.ShapeDtypeStruct((b,), jnp.float32),
                   jax.ShapeDtypeStruct((b,), jnp.float32),
                   jax.ShapeDtypeStruct((b,), jnp.int32)),
        grid=(ap // ba,),
        in_specs=[ids_spec, pl.BlockSpec((b, d), lambda j: (0, 0)), w_spec,
                  *col_specs, pl.BlockSpec((b,), lambda j: (0,))],
        out_specs=(pl.BlockSpec((b,), lambda j: (0,)),
                   pl.BlockSpec((b,), lambda j: (0,)),
                   pl.BlockSpec((b,), lambda j: (0,)),
                   pl.BlockSpec((b,), lambda j: (0,))),
        scratch_shapes=[pltpu.VMEM((ba, 1, d), jnp.float32),
                        pltpu.SemaphoreType.DMA(()),
                        pltpu.VMEM((b,), jnp.float32),
                        pltpu.VMEM((b,), jnp.float32),
                        pltpu.VMEM((b,), jnp.float32),
                        pltpu.VMEM((b,), jnp.int32),
                        pltpu.VMEM((b,), jnp.int32)],
        interpret=interpret,
        name="sparse_ce_fwd",
    )(ids, f.astype(jnp.float32), w3, gids, bias, valid, y.astype(jnp.int32))
    return m, z, corr, amax


def _bwd_kernel(ids_ref, f_ref, w_hbm, gids_ref, bias_ref, valid_ref, y_ref,
                m_ref, gz_ref, gc_ref,
                dwa_ref, df_ref, tile, sem, acc_df, acc_seen,
                *, ba: int, scale: float, mask_hits: bool):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        acc_df[...] = jnp.zeros_like(acc_df)
        acc_seen[...] = jnp.zeros_like(acc_seen)

    w_t = gather_rows(ids_ref, w_hbm, tile, sem)     # [ba, D]
    f = f_ref[...]
    s = jax.lax.dot_general(f, w_t, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = s + bias_ref[...]
    hit, col_ok = _tile_masks(gids_ref, valid_ref, y_ref)
    if mask_hits:
        keep = col_ok & ~hit
        hitf = jnp.zeros_like(s)
    else:
        keep = jnp.broadcast_to(col_ok, s.shape)
        # the corr onehot hits the FIRST label column only, like the forward
        hitf = _first_hit(hit, acc_seen[...][:, None]).astype(jnp.float32)
        acc_seen[...] = jnp.maximum(
            acc_seen[...], jnp.max(hit.astype(jnp.int32), axis=1))

    m = m_ref[...][:, None]
    gz = gz_ref[...]
    gc = gc_ref[...]
    p = jnp.where(keep & jnp.isfinite(m), jnp.exp(s - m), 0.0)
    dl = (p * gz[:, None] + hitf * gc[:, None]) * scale
    dwa_ref[...] = jax.lax.dot_general(
        dl, f, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # [ba, D] compact dW
    acc_df[...] += jax.lax.dot_general(
        dl, w_t, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # [B, D]

    @pl.when(j == pl.num_programs(0) - 1)
    def _flush():
        df_ref[...] = acc_df[...]


def sparse_ce_backward(f, w, ids, gids, bias, valid, y, m, gz, gc, *,
                       block_a: int = 128, scale: float = 1.0,
                       mask_hits: bool = False, interpret: bool):
    """Streamed backward. Returns (df [B,D], dw_act [A,D] per-candidate
    weight grads — scatter-add into [V_loc, D] is the wrapper's job)."""
    b, d = f.shape
    a = ids.shape[0]
    ba = min(block_a, max(8, a))
    w3, ids, gids, bias, valid, ap = _tile_cols(w, ids, gids, bias, valid,
                                                ba)
    ids_spec, w_spec, *col_specs = _col_specs(ba)
    dwa, df = pl.pallas_call(
        functools.partial(_bwd_kernel, ba=ba, scale=scale,
                          mask_hits=mask_hits),
        out_shape=(jax.ShapeDtypeStruct((ap, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, d), jnp.float32)),
        grid=(ap // ba,),
        in_specs=[ids_spec, pl.BlockSpec((b, d), lambda j: (0, 0)), w_spec,
                  *col_specs, pl.BlockSpec((b,), lambda j: (0,)),
                  pl.BlockSpec((b,), lambda j: (0,)),
                  pl.BlockSpec((b,), lambda j: (0,)),
                  pl.BlockSpec((b,), lambda j: (0,))],
        out_specs=(pl.BlockSpec((ba, d), lambda j: (j, 0)),
                   pl.BlockSpec((b, d), lambda j: (0, 0))),
        scratch_shapes=[pltpu.VMEM((ba, 1, d), jnp.float32),
                        pltpu.SemaphoreType.DMA(()),
                        pltpu.VMEM((b, d), jnp.float32),
                        pltpu.VMEM((b,), jnp.int32)],
        interpret=interpret,
        name="sparse_ce_bwd",
    )(ids, f.astype(jnp.float32), w3, gids, bias, valid, y.astype(jnp.int32),
      m, gz.astype(jnp.float32), gc.astype(jnp.float32))
    return df, dwa[:a]
