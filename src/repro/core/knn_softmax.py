"""KNN softmax (paper §3.2): active-class selection + sparse distributed CE.

Per step, each model shard scores only M_local active classes instead of its
full V_local shard. The active set is Algorithm 1, re-expressed with fixed
shapes for TPU:

  1. quick access: capped CSR gather of each local label's neighbor list
     from the *compressed* graph (paper's custom CUDA kernel -> XLA gather);
  2. dedup keeping the best (lowest) graph rank per class (paper's ranking
     score) via lexsort + first-occurrence masking;
  3. top-M_local by rank; underfull slots are padded with pseudo-random
     non-selected classes (paper line 7) or masked out (``pad_random=False``).

Because W is L2-normalized, each label's own class is neighbor 0 of its own
list, so rank-0 entries always win selection — the lossless-inclusion
property the paper relies on. Normalization of X and W (the paper's
"normalization strategy") makes the logits cosine similarities; a fixed
``cosine_scale`` recovers a usable logit range.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.core.sharded_softmax import (_finish_ce, _finish_ce_stats,
                                        _flat_axis_index, _normalize)

BIG_RANK = 1 << 20


def select_active(
    y_loc, offsets, neighbors, *, v_loc, m_local: int, k_cap: int,
    pad_random: bool = True, seed_salt=0, ranks=None,
):
    """Fixed-shape Algorithm 1 on one model shard.

    y_loc: [b] global labels of this device's batch rows.
    offsets: [N+1] CSR row offsets of the local compressed graph.
    neighbors: [nnz_cap] local class ids.
    ranks: [nnz_cap] ORIGINAL neighbor-list positions (Algorithm 1's ranking
    score). If None, the compressed position is used — only safe when every
    shard sees full rows (uncompressed graphs / tests).
    Returns (active_ids [m_local] local ids, valid [m_local] bool).
    """
    b = y_loc.shape[0]
    lens = (offsets[y_loc + 1] - offsets[y_loc]).astype(jnp.int32)  # [b]
    iota = jnp.arange(k_cap, dtype=jnp.int32)
    take = offsets[y_loc][:, None] + iota[None, :]
    safe_take = jnp.clip(take, 0, neighbors.shape[0] - 1)
    cand = neighbors[safe_take]
    in_row = iota[None, :] < jnp.minimum(lens, k_cap)[:, None]
    cand = jnp.where(in_row, cand, -1)                    # [b, k_cap] local ids
    if ranks is not None:
        rank = jnp.where(in_row, ranks[safe_take], BIG_RANK - 1)
    else:
        rank = jnp.broadcast_to(iota[None, :], cand.shape)  # compressed pos

    flat_id = cand.reshape(-1)
    flat_rank = jnp.where(flat_id >= 0, rank.reshape(-1), BIG_RANK)
    # sort by (id, rank); first occurrence per id = best rank
    order = jnp.lexsort((flat_rank, flat_id))
    sid = flat_id[order]
    srank = flat_rank[order]
    first = jnp.concatenate([jnp.array([True]), sid[1:] != sid[:-1]])
    valid = first & (sid >= 0)
    score = jnp.where(valid, BIG_RANK - srank, -1)
    take = min(m_local, score.shape[0])
    top_score, top_pos = jax.lax.top_k(score, take)
    ids = sid[top_pos]
    mask = top_score >= 0
    if take < m_local:  # fewer candidates than budget: pad (paper line 7)
        pad = m_local - take
        ids = jnp.concatenate([ids, jnp.zeros((pad,), ids.dtype)])
        mask = jnp.concatenate([mask, jnp.zeros((pad,), bool)])

    if pad_random:
        # paper line 7: fill with pseudo-random non-chosen classes. Collisions
        # with chosen classes are masked (a collision would double-count a
        # class in Z). Deterministic per (labels, salt) so recompute-in-bwd
        # under remat is stable.
        key = jax.random.fold_in(jax.random.PRNGKey(17), seed_salt)
        key = jax.random.fold_in(key, jnp.sum(y_loc) % (1 << 30))
        fillers = jax.random.randint(key, (m_local,), 0, v_loc, jnp.int32)
        sorted_ids = jnp.sort(jnp.where(mask, ids, -1))
        pos = jnp.searchsorted(sorted_ids, fillers)
        dup = sorted_ids[jnp.clip(pos, 0, m_local - 1)] == fillers
        ids = jnp.where(mask, ids, fillers)
        mask = mask | ~dup
    ids = jnp.where(mask, ids, 0)
    return ids.astype(jnp.int32), mask


def live_slots(m_local: int, b: int, k_cap: int, pad_random: bool) -> int:
    """How many of ``select_active``'s slots can hold an active class, for
    b labels: every slot with filler classes; without them at most one per
    candidate (b * k_cap), and those come first (the rest are padding)."""
    return m_local if pad_random else min(m_local, b * k_cap)


def knn_softmax_local(
    f_loc, y_loc, w_loc, offsets_loc, neighbors_loc, ranks_loc=None, *,
    model_axis: str, batch_axes: Sequence[str], global_batch: int,
    m_local: int, k_cap: int, cosine_scale: float = 16.0,
    pad_random: bool = True, n_valid: int = 0, backend: str = "ref",
    block_a: int = 128, rows=None,
):
    """shard_map body for the KNN-softmax loss (counterpart of
    full_softmax_local). offsets_loc [1, N+1] / neighbors_loc / ranks_loc
    [1, nnz] arrive with the leading model-shard axis from the sharded
    CompressedGraph. ``backend="pallas"`` replaces the dense
    gather-then-softmax (w_loc[ids] -> [b, m_local] logits) with the fused
    active-class sparse-CE kernel (``ops.sparse_ce_rows``): the gather and
    the online softmax run in one streamed sweep and neither the gathered
    weights nor the logit tensor reach HBM. On that backend w_loc holds
    unit rows (the head normalizes the whole shard once per update,
    ``KNNSoftmaxHead.prepare_params``); the ref body gathers raw rows and
    normalizes only those.

    w_loc's gradient is the active rows' gradient scatter-added into the
    shard. ``rows`` (pallas only) is a zero [n, D] operand for the first n
    slots, n at least ``live_slots``, that takes it instead, compact, and
    w_loc gets none: the body then returns (loss, metrics, ids), ids [n]
    the local rows those slots read, v_loc for a masked slot (its gradient
    row is zeros)."""
    offsets = offsets_loc.reshape(-1)
    neighbors = neighbors_loc.reshape(-1)
    ranks = ranks_loc.reshape(-1) if ranks_loc is not None else None
    v_loc = w_loc.shape[0]
    v_start = _flat_axis_index(model_axis) * v_loc

    ids, valid = select_active(
        y_loc, offsets, neighbors, v_loc=v_loc, m_local=m_local,
        k_cap=k_cap, pad_random=pad_random, ranks=ranks)
    if n_valid:  # mask padded vocab rows that slipped in as random fillers
        valid = valid & ((v_start + ids) < n_valid)

    # label position within the active set (owner shard only)
    y_rel = (y_loc - v_start).astype(jnp.int32)
    owned = (y_rel >= 0) & (y_rel < v_loc)
    hit = (ids[None, :] == y_rel[:, None]) & valid[None, :]
    owned = owned & jnp.any(hit, axis=1)  # label must be in the active set

    if backend == "pallas":
        f = _normalize(f_loc).astype(jnp.float32)
        wn = w_loc.astype(jnp.float32)  # unit rows; == gather-then-norm
        gids = v_start + ids
        bias = jnp.zeros((ids.shape[0],), jnp.float32)
        if rows is None:
            m, z, corr, amax = ops.sparse_ce_stats(
                f, wn, ids, gids, bias, valid.astype(jnp.int32), y_loc,
                cosine_scale, block_a, False)
        else:
            n = rows.shape[0]
            if n < live_slots(m_local, y_loc.shape[0], k_cap, pad_random):
                raise ValueError(f"{n} gradient rows miss live slots")
            # the slots past the first n are never live: their gradient
            # rows are zeros, and the step need not add them
            m, z, corr, amax = ops.sparse_ce_rows(
                f, wn, jnp.pad(rows, ((0, m_local - n), (0, 0))), ids, gids,
                bias, valid.astype(jnp.int32), y_loc, cosine_scale, block_a,
                False)
        corr = jnp.where(owned, corr, 0.0)
        pred_gid = jnp.where(amax >= 0, gids[jnp.maximum(amax, 0)], -1)
        loss, metrics = _finish_ce_stats(m, z, corr, pred_gid, y_loc, owned,
                                         model_axis, tuple(batch_axes),
                                         1.0 / global_batch)
    else:
        dt = f_loc.dtype
        f = _normalize(f_loc)
        w_act = _normalize(w_loc[ids])  # [m_local,D]; bwd = scatter-add to W
        logits = jnp.einsum("bd,md->bm", f, w_act.astype(dt),
                            preferred_element_type=jnp.float32) * cosine_scale
        logits = jnp.where(valid[None, :], logits, -1e30)
        pos = jnp.argmax(hit, axis=1).astype(jnp.int32)
        loss, metrics = _finish_ce(logits, pos, owned, model_axis,
                                   tuple(batch_axes), 1.0 / global_batch)
    max_t = model_axis if isinstance(model_axis, tuple) else (model_axis,)
    metrics["active_frac"] = jax.lax.pmean(
        jnp.mean(valid.astype(jnp.float32)), max_t + tuple(batch_axes))
    found = jax.lax.psum(owned.astype(jnp.float32), model_axis)  # [b] 0/1
    metrics["label_recall"] = jax.lax.psum(
        jnp.sum(found), tuple(batch_axes)) / global_batch
    if rows is None:
        return loss, metrics
    return loss, metrics, jnp.where(valid, ids, v_loc)[:rows.shape[0]]


def knn_softmax_ref(features, labels, w, graph, *, m: int,
                    cosine_scale: float = 16.0, pad_random: bool = False):
    """Single-device oracle of the KNN-softmax loss (graph: [N, k] global
    ids). Mirrors the selection semantics with one "shard" owning all of W."""
    n = w.shape[0]
    cand = graph[labels]                       # [b, k]
    rank = jnp.broadcast_to(jnp.arange(graph.shape[1])[None], cand.shape)
    flat_id = cand.reshape(-1)
    flat_rank = rank.reshape(-1)
    order = jnp.lexsort((flat_rank, flat_id))
    sid, srank = flat_id[order], flat_rank[order]
    first = jnp.concatenate([jnp.array([True]), sid[1:] != sid[:-1]])
    score = jnp.where(first, BIG_RANK - srank, -1)
    top_score, top_pos = jax.lax.top_k(score, m)
    ids = jnp.where(top_score >= 0, sid[top_pos], 0)
    maskv = top_score >= 0

    f = features.astype(jnp.float32)
    f = f / (jnp.linalg.norm(f, axis=-1, keepdims=True) + 1e-12)
    wa = w[ids].astype(jnp.float32)
    wa = wa / (jnp.linalg.norm(wa, axis=-1, keepdims=True) + 1e-12)
    logits = f @ wa.T * cosine_scale
    logits = jnp.where(maskv[None, :], logits, -1e30)
    hit = ids[None, :] == labels[:, None]
    pos = jnp.argmax(hit, axis=1)
    corr = jnp.take_along_axis(logits, pos[:, None], axis=1)[:, 0]
    logz = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(logz - corr)
