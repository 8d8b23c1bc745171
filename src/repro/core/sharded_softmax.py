"""Hybrid-parallel distributed softmax cross-entropy (paper §3.1).

The extreme-classification head W [N, D] is split row-wise (by class) across
the ``model`` mesh axis; features arrive batch-sharded over the data axes and
replicated along ``model`` (the all-gather the paper overlaps in §3.3.1 is
what produced that replication). Each device scores its local class shard and
the softmax is completed with two tiny collectives:

    global max  = pmax over "model"   (numerical stability)
    global Z    = psum over "model"   (partition function)
    label logit = psum over "model"   (each class owned by exactly one shard)

The fc gradient stays local to its shard (the paper's key memory/comm win);
only the feature gradient crosses the model axis (inside autodiff of the
einsum) and the scalar loss is averaged over the data axes.

These are *shard_map bodies*: they see local shards and use lax collectives
explicitly, so the paper's communication pattern is visible in the HLO.

Every body takes ``backend="ref" | "pallas"``: ``ref`` is the plain-XLA
einsum path below; ``pallas`` streams the local scoring through the fused
kernels in ``repro.kernels`` (``ops.ce_shard_stats``) so the [B, V_local]
logit tensor never materializes, then completes the softmax with the same
two collectives via ``_finish_ce_stats``. Loss and grads agree to fp32
tolerance (tests/test_backend_parity.py).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.kernels import ops

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# single-device oracle
# ---------------------------------------------------------------------------


def ce_ref(features, labels, w, *, cosine_scale: float = 0.0,
           label_smoothing: float = 0.0):
    """Plain full-softmax cross entropy. features [T,D], labels [T], w [N,D].
    cosine_scale > 0 switches to normalized (cosine) logits — the paper's
    normalization strategy (§3.2.1)."""
    f = features.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    if cosine_scale > 0:
        f = f / (jnp.linalg.norm(f, axis=-1, keepdims=True) + 1e-12)
        wf = wf / (jnp.linalg.norm(wf, axis=-1, keepdims=True) + 1e-12)
    logits = f @ wf.T
    if cosine_scale > 0:
        logits = logits * cosine_scale
    logz = jax.nn.logsumexp(logits, axis=-1)
    corr = jnp.take_along_axis(logits, labels[:, None], axis=1)[:, 0]
    if label_smoothing > 0:
        mean_logit = jnp.mean(logits, axis=-1)
        corr = (1 - label_smoothing) * corr + label_smoothing * mean_logit
    loss = jnp.mean(logz - corr)
    acc = jnp.mean((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))
    return loss, {"accuracy": acc, "logz": jnp.mean(logz)}


# ---------------------------------------------------------------------------
# shard_map body: full softmax
# ---------------------------------------------------------------------------


def _normalize(x):
    xf = x.astype(jnp.float32)
    return (xf / (jnp.linalg.norm(xf, axis=-1, keepdims=True) + 1e-12)).astype(x.dtype)


def _flat_axis_index(axis):
    """Row-major flat index over one axis name or a tuple of axis names
    (vocab sharded over several mesh axes — the paper's 1-D layout where
    every chip is an fc shard)."""
    if isinstance(axis, str):
        return jax.lax.axis_index(axis)
    idx = jnp.zeros((), jnp.int32)
    for a in axis:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def _finish_ce(logits, owned_label_pos, owned, model_axis,
               batch_axes, batch_weight):
    """Shared distributed-CE tail.

    logits: [b, C_local] fp32 (already scaled); owned_label_pos [b] column of
    each sample's label in the local shard (only meaningful where ``owned``);
    owned [b] bool — exactly one device per model group owns each label.
    Returns (loss scalar replicated, metrics dict).
    """
    b = logits.shape[0]
    m_loc = jax.lax.stop_gradient(jnp.max(logits, axis=-1))
    m = jax.lax.pmax(m_loc, model_axis)
    z_loc = jnp.sum(jnp.exp(logits - m[:, None]), axis=-1)
    z = jax.lax.psum(z_loc, model_axis)
    corr_loc = jnp.take_along_axis(
        logits, owned_label_pos[:, None].astype(jnp.int32), axis=1)[:, 0]
    corr_loc = jnp.where(owned, corr_loc, 0.0)
    corr = jax.lax.psum(corr_loc, model_axis)  # [b] label logit
    per_sample = jnp.log(z) + m - corr
    loss = jax.lax.psum(jnp.sum(per_sample) * batch_weight, batch_axes)

    # distributed top-1 accuracy (metrics only — no gradient)
    logits = jax.lax.stop_gradient(logits)
    amax_loc = jnp.argmax(logits, axis=-1)
    vmax_loc = jnp.take_along_axis(logits, amax_loc[:, None], axis=1)[:, 0]
    vmax = jax.lax.pmax(vmax_loc, model_axis)
    is_best = vmax_loc >= vmax  # ties: >=; duplicates across shards unlikely
    pred_here = owned & is_best & (amax_loc == owned_label_pos)
    correct = jax.lax.psum(pred_here.astype(jnp.float32), model_axis) > 0
    acc = jax.lax.psum(jnp.sum(correct.astype(jnp.float32)) * batch_weight,
                       batch_axes)
    logz = jax.lax.pmean(jnp.mean(jnp.log(z) + m), batch_axes)
    return loss, {"accuracy": acc, "logz": logz}


def _finish_ce_stats(m_loc, z_loc, corr_loc, pred_gid, y, owned, model_axis,
                     batch_axes, batch_weight):
    """Distributed-CE tail from per-shard ONLINE-SOFTMAX STATS (the Pallas
    backend's counterpart of ``_finish_ce``, which takes dense logits).

    m_loc/z_loc/corr_loc [b]: each shard's running max, partition sum
    relative to it, and label-logit contribution (0 off the owner shard).
    pred_gid [b]: the shard's best candidate as a GLOBAL class id (-1 when
    the shard scored nothing). Gradients flow through z_loc/corr_loc into
    the streaming backward kernels; m_loc is a non-differentiable statistic
    (ops module doc), so the pmax below needs no explicit stop_gradient —
    its cotangent is discarded exactly.
    """
    m_sg = jax.lax.stop_gradient(m_loc)
    m = jax.lax.pmax(m_sg, model_axis)
    z_resc = jnp.where(jnp.isfinite(m_sg), jnp.exp(m_sg - m), 0.0)
    z = jax.lax.psum(z_loc * z_resc, model_axis)
    corr = jax.lax.psum(corr_loc, model_axis)  # [b] label logit
    per_sample = jnp.log(z) + m - corr
    loss = jax.lax.psum(jnp.sum(per_sample) * batch_weight,
                        tuple(batch_axes))

    # distributed top-1 accuracy (metrics only — no gradient)
    is_best = m_sg >= m  # ties: >=; duplicates across shards unlikely
    pred_here = owned & is_best & (pred_gid == y)
    correct = jax.lax.psum(pred_here.astype(jnp.float32), model_axis) > 0
    acc = jax.lax.psum(jnp.sum(correct.astype(jnp.float32)) * batch_weight,
                       tuple(batch_axes))
    logz = jax.lax.pmean(jnp.mean(jnp.log(z) + m), tuple(batch_axes))
    return loss, {"accuracy": acc, "logz": logz}


def _shard_limit(v_start, v_loc: int, n_valid: int):
    """Valid-column count of this shard (traced): masks Megatron-style vocab
    padding inside the fused kernels. n_valid == 0 means no padding."""
    if not n_valid:
        return jnp.asarray(v_loc, jnp.int32)
    return jnp.clip(n_valid - v_start, 0, v_loc).astype(jnp.int32)


def full_softmax_local(
    f_loc, y_loc, w_loc, *, model_axis: str,
    batch_axes: Sequence[str], global_batch: int, cosine_scale: float = 0.0,
    n_valid: int = 0, backend: str = "ref", block_v: int = 512,
):
    """shard_map body. f_loc [b,D] (replicated along model), y_loc [b] global
    class ids, w_loc [V_loc, D] this device's class shard (row offset derived
    from the device's model-axis index). n_valid > 0 masks padded vocab rows
    (Megatron-style padding) out of the partition function. ``backend``
    routes the [b, V_loc] scoring through XLA (ref) or the streaming fused-CE
    kernel (pallas — the logit tensor never hits HBM). For a cosine head
    (cosine_scale > 0) w_loc holds unit rows: the head normalizes W once
    per update (``FullSoftmaxHead.prepare_params``), and the body
    normalizes only the features."""
    if backend == "pallas":
        f = _normalize(f_loc) if cosine_scale > 0 else f_loc
        scale = cosine_scale if cosine_scale > 0 else 1.0
        v_loc = w_loc.shape[0]
        v_start = _flat_axis_index(model_axis) * v_loc
        pos = (y_loc - v_start).astype(jnp.int32)
        owned = (pos >= 0) & (pos < v_loc)
        y_local = jnp.where(owned, pos, -1)
        limit = _shard_limit(v_start, v_loc, n_valid)
        m, z, corr, amax = ops.ce_shard_stats(
            f.astype(jnp.float32), w_loc.astype(jnp.float32), y_local,
            limit, scale, block_v)
        pred_gid = jnp.where(amax >= 0, v_start + amax, -1)
        return _finish_ce_stats(m, z, corr, pred_gid, y_loc, owned,
                                model_axis, tuple(batch_axes),
                                1.0 / global_batch)
    dt = f_loc.dtype
    f = _normalize(f_loc) if cosine_scale > 0 else f_loc
    logits = jnp.einsum("bd,vd->bv", f, w_loc.astype(dt),
                        preferred_element_type=jnp.float32)
    if cosine_scale > 0:
        logits = logits * cosine_scale
    v_loc = w_loc.shape[0]
    v_start = _flat_axis_index(model_axis) * v_loc
    if n_valid:
        col = v_start + jnp.arange(v_loc)
        logits = jnp.where((col < n_valid)[None, :], logits, NEG_INF)
    pos = (y_loc - v_start).astype(jnp.int32)
    owned = (pos >= 0) & (pos < v_loc)
    pos = jnp.clip(pos, 0, v_loc - 1)
    return _finish_ce(logits, pos, owned, model_axis, tuple(batch_axes),
                      1.0 / global_batch)


def _combine_argmax(vmax, gid, model_axis):
    """One winner per row across model shards: lowest shard index among
    ties. vmax [b] local best value, gid [b] its global class id."""
    gmax = jax.lax.pmax(vmax, model_axis)
    shard_idx = _flat_axis_index(model_axis)
    is_best = vmax >= gmax
    winner_shard = jax.lax.pmin(
        jnp.where(is_best, shard_idx, jnp.iinfo(jnp.int32).max), model_axis)
    mine = is_best & (shard_idx == winner_shard)
    return jax.lax.psum(jnp.where(mine, gid, 0), model_axis).astype(jnp.int32)


def serve_argmax_local(f_loc, w_loc, *, model_axis: str, n_valid: int = 0,
                       block_v: int = 512):
    """Pallas-backend greedy decode: distributed argmax token ids WITHOUT
    materializing the [b, V_loc] logit tensor — the streaming kernel's
    (max, argmax) stats plus one pmax/pmin/psum combine. Counterpart of
    ``serve_logits_local`` (which returns the dense local logits too)."""
    v_loc = w_loc.shape[0]
    v_start = _flat_axis_index(model_axis) * v_loc
    limit = _shard_limit(v_start, v_loc, n_valid)
    b = f_loc.shape[0]
    y_none = jnp.full((b,), -1, jnp.int32)
    m, _, _, amax = ops.ce_shard_stats(
        f_loc.astype(jnp.float32), w_loc.astype(jnp.float32), y_none, limit,
        1.0, block_v)
    gid = v_start + jnp.maximum(amax, 0)
    vmax = jnp.where(amax >= 0, m, -jnp.inf)
    return _combine_argmax(vmax, gid, model_axis), None


def _merge_topk_ring(vals, gids, k: int, model_axis):
    """Merge per-shard local top-k candidates into the global top-k: one
    all-gather over the model axis, then a tiny [b, P*k] ``lax.top_k``.
    Shared by the exact scan (``serve_topk_local``) and the IVF index path
    (``serve_topk_ivf_local``). Returns (vals [b, k] desc, gids [b, k]),
    replicated along the model axis."""
    all_v = jax.lax.all_gather(vals, model_axis, axis=0)   # [P, b, k]
    all_g = jax.lax.all_gather(gids, model_axis, axis=0)
    b = vals.shape[0]
    flat_v = jnp.moveaxis(all_v, 0, 1).reshape(b, -1)      # [b, P*k]
    flat_g = jnp.moveaxis(all_g, 0, 1).reshape(b, -1)
    top_v, pos = jax.lax.top_k(flat_v, k)
    return top_v, jnp.take_along_axis(flat_g, pos, axis=1)


def serve_topk_local(f_loc, w_loc, k: int, *, model_axis: str,
                     n_valid: int = 0, backend: str = "ref",
                     chunk: int = 2048):
    """Top-k retrieval with scores (ROADMAP "serving beyond greedy argmax").

    Each shard scores its class block ([b, V_loc] — serving's product IS the
    scores), selects its local top-k per row (``ref``: lax.top_k; ``pallas``:
    the divide-and-conquer stage-1 kernel via ``ops.topk_rows`` — paper
    Fig. 5 applied to retrieval), then one all-gather over the model axis
    merges the P*k survivors. Returns (vals [b,k] desc, gids [b,k] int32),
    replicated along the model axis.
    """
    logits = jnp.einsum("bd,vd->bv", f_loc, w_loc.astype(f_loc.dtype),
                        preferred_element_type=jnp.float32)
    v_loc = w_loc.shape[0]
    v_start = _flat_axis_index(model_axis) * v_loc
    if n_valid:
        col = v_start + jnp.arange(v_loc)
        logits = jnp.where((col < n_valid)[None, :], logits, NEG_INF)
    kk = min(k, v_loc)
    if backend == "pallas":
        vals, idx = ops.topk_rows(logits, kk, chunk=chunk)
    else:
        vals, idx = jax.lax.top_k(logits, kk)
    gids = v_start + idx.astype(jnp.int32)
    if kk < k:  # more slots than local classes: pad before the merge
        pad = k - kk
        vals = jnp.pad(vals, ((0, 0), (0, pad)), constant_values=-jnp.inf)
        gids = jnp.pad(gids, ((0, 0), (0, pad)), constant_values=-1)
    return _merge_topk_ring(vals, gids, k, model_axis)


def mask_padded_rows(x, n_queries, fill):
    """Serving-tier padding mask: rows >= ``n_queries`` of a fixed-shape
    micro-batch are coalescer padding, not real queries — force them to
    ``fill`` so batch shape never leaks into results. Works for [b] and
    [b, k] outputs; ``n_queries`` may be traced (one jit per bucket shape,
    NOT per occupancy)."""
    b = x.shape[0]
    keep = (jnp.arange(b) < n_queries).reshape((b,) + (1,) * (x.ndim - 1))
    return jnp.where(keep, x, fill)


def serve_topk_batched_local(f_loc, w_loc, k: int, n_queries, *,
                             model_axis: str, n_valid: int = 0,
                             backend: str = "ref", chunk: int = 2048):
    """Multi-query serving entry point (the serving tier's hot path).

    ``f_loc`` is a PADDED micro-batch [b_pad, D] REPLICATED along the model
    axis (the engine feeds every shard the full batch — no ring gather on
    the serve path) with only the first ``n_queries`` rows real. Scoring is
    row-independent, so padding never perturbs real rows; padded rows come
    back as (-inf, -1). Returns (vals [b_pad, k] desc, gids [b_pad, k])."""
    vals, gids = serve_topk_local(f_loc, w_loc, k, model_axis=model_axis,
                                  n_valid=n_valid, backend=backend,
                                  chunk=chunk)
    return (mask_padded_rows(vals, n_queries, -jnp.inf),
            mask_padded_rows(gids, n_queries, -1))


def serve_topk_ivf_local(f_loc, w_loc, cent_loc, members_loc, k: int,
                         nprobe: int, *, model_axis: str,
                         backend: str = "ref", block_a: int = 128):
    """IVF top-k retrieval (sublinear in the class count, ROADMAP "learned
    ANN index"): probe the query's top-``nprobe`` k-means centroids of this
    shard, rerank ONLY the member rows of the probed clusters, then merge
    across shards with the same one-ring all-gather as the exact scan.

    f_loc [b, D] replicated along the model axis; w_loc [V_loc, D] the
    class shard; cent_loc [C, D] unit centroids fit over the shard
    (``repro.serving.index``); members_loc [C, cap] int32 local row ids per
    cluster, -1 padded (every valid class appears in exactly one cluster,
    so ``nprobe == C`` recovers the exact scan). The rerank scores raw
    ``f @ w.T`` dot products — identical to the exact path — over
    A = nprobe * cap candidates instead of V_loc columns (``ref``: gather +
    ``lax.top_k``; ``pallas``: the fused ``ops.ivf_rerank`` kernel). The
    probe always uses the normalized query against the unit centroids
    (cluster membership is directional); cosine heads normalize f/w before
    calling, exactly like the exact serve steps.
    """
    c, cap = members_loc.shape
    v_loc = w_loc.shape[0]
    v_start = _flat_axis_index(model_axis) * v_loc
    f = f_loc.astype(jnp.float32)
    b = f.shape[0]
    fq = _normalize(f)
    n_probe = min(nprobe, c)
    _, probe = jax.lax.top_k(fq @ cent_loc.astype(jnp.float32).T, n_probe)
    cand = jnp.take(members_loc, probe, axis=0).reshape(b, -1)  # [b, A]
    kk = min(k, cand.shape[1])
    if backend == "pallas":
        vals, lids = ops.ivf_rerank(f, w_loc.astype(jnp.float32), cand, kk,
                                    block_a=block_a)
    else:
        safe = jnp.clip(cand, 0, v_loc - 1)
        wc = jnp.take(w_loc.astype(jnp.float32), safe, axis=0)  # [b, A, D]
        s = jnp.einsum("bd,bad->ba", f, wc,
                       preferred_element_type=jnp.float32)
        s = jnp.where(cand >= 0, s, -jnp.inf)
        vals, pos = jax.lax.top_k(s, kk)
        lids = jnp.take_along_axis(cand, pos, axis=1)
    gids = jnp.where(lids >= 0, v_start + lids, -1).astype(jnp.int32)
    vals = jnp.where(lids >= 0, vals, -jnp.inf)
    if kk < k:  # fewer candidates than slots: pad before the merge
        pad = k - kk
        vals = jnp.pad(vals, ((0, 0), (0, pad)), constant_values=-jnp.inf)
        gids = jnp.pad(gids, ((0, 0), (0, pad)), constant_values=-1)
    return _merge_topk_ring(vals, gids, k, model_axis)


def serve_topk_ivf_batched_local(f_loc, w_loc, cent_loc, members_loc, k: int,
                                 nprobe: int, n_queries, *, model_axis: str,
                                 backend: str = "ref", block_a: int = 128):
    """Serving-tier entry for the IVF path: padded micro-batch [b_pad, D]
    with only the first ``n_queries`` rows real (traced — one jit per
    bucket). Scoring is row-independent, so padding never perturbs real
    rows; padded rows come back as (-inf, -1), like the exact path."""
    vals, gids = serve_topk_ivf_local(
        f_loc, w_loc, cent_loc, members_loc, k, nprobe,
        model_axis=model_axis, backend=backend, block_a=block_a)
    return (mask_padded_rows(vals, n_queries, -jnp.inf),
            mask_padded_rows(gids, n_queries, -1))


def serve_logits_local(f_loc, w_loc, *, model_axis: str, n_valid: int = 0):
    """Decode-time local logits [b, V_loc] + distributed argmax token ids.

    Greedy sampling: each shard proposes (best val, global id); combined with
    one pmax + one psum along "model"."""
    logits = jnp.einsum("bd,vd->bv", f_loc, w_loc.astype(f_loc.dtype),
                        preferred_element_type=jnp.float32)
    if n_valid:
        v_loc = w_loc.shape[0]
        col = _flat_axis_index(model_axis) * v_loc + jnp.arange(v_loc)
        logits = jnp.where((col < n_valid)[None, :], logits, NEG_INF)
    amax = jnp.argmax(logits, axis=-1)
    vmax = jnp.take_along_axis(logits, amax[:, None], axis=1)[:, 0]
    v_loc = w_loc.shape[0]
    gid = _flat_axis_index(model_axis) * v_loc + amax.astype(jnp.int32)
    return _combine_argmax(vmax, gid, model_axis), logits
