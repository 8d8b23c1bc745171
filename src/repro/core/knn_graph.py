"""Distributed exact KNN graph over the class weights (paper §3.2.2).

The paper builds an *exact* (linear-search) KNN graph of W_norm because ANN
recall losses translate into accuracy loss. W is row-sharded over "model", so
the build uses a ring: each device's block of W_norm visits every other
device via collective_permute; each hop contributes a [N_loc × N_loc] bf16
matmul (TensorCore in the paper, MXU here) merged into a running top-k'. A
second fp32 pass re-ranks the k' candidates (paper's mixed-precision scheme)
before the final k are kept. Self is always neighbor 0 (W is normalized, so
w_y ranks first in its own list — the property Algorithm 1 relies on).

Graph compression (paper §3.2.3-i): each device keeps, for ALL N rows, only
the neighbor entries that point to classes stored on that device — CSR
(offsets [N+1], values [nnz]) with *local* column ids. ``quick access``
(§3.2.3-ii) becomes a capped CSR gather (see knn_softmax.select_active).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


class CompressedGraph(NamedTuple):
    """Per-model-shard CSR of local neighbors. Leading axis = model shard when
    used as a global (sharded) array. ``ranks`` preserves each entry's
    position in the ORIGINAL (uncompressed) neighbor list — Algorithm 1's
    ranking score. Without it, the first local entry of every row would tie
    at rank 0 with true self-entries and selection could drop labels."""
    offsets: jax.Array    # [P, N+1] int32
    neighbors: jax.Array  # [P, nnz_cap] int32 local ids (pad = -1)
    ranks: jax.Array      # [P, nnz_cap] int32 original positions (pad = -1)


# ---------------------------------------------------------------------------
# reference (single device, fp32, exact)
# ---------------------------------------------------------------------------


def knn_graph_ref(w, k: int):
    """Exact top-k cosine neighbors (self included, ranked first).
    w: [N, D] -> ids [N, k] int32."""
    wn = w.astype(jnp.float32)
    wn = wn / (jnp.linalg.norm(wn, axis=-1, keepdims=True) + 1e-12)
    scores = wn @ wn.T
    _, ids = jax.lax.top_k(scores, k)
    return ids.astype(jnp.int32)


# ---------------------------------------------------------------------------
# distributed ring build (shard_map body over the "model" axis)
# ---------------------------------------------------------------------------


def _merge_topk(best_v, best_i, new_v, new_i, k):
    v = jnp.concatenate([best_v, new_v], axis=1)
    i = jnp.concatenate([best_i, new_i], axis=1)
    top_v, pos = jax.lax.top_k(v, k)
    return top_v, jnp.take_along_axis(i, pos, axis=1)


# Tiles of the ring build at a chip's share of the paper deployment
# (N_loc = 390,656 rows, D = 512), where the untiled pass 1 and pass 2 would
# need 610 GB and 25.6 GB of HBM: pass 1 (ref) scores [ROW_CHUNK, COL_CHUNK]
# f32 tiles (0.5 GB); pass 2 gathers [ROW_CHUNK, k'=32, D] f32 (0.5 GB).
ROW_CHUNK = 8192
COL_CHUNK = 16384
# Pass 1 (ref) sorts only the k' best GROUP-column groups of a tile: a
# lax.top_k on TPU sorts its whole input, and the sort is the cost.
GROUP = 16


def _chunk_rows(x, size: int, fill):
    """[N, ...] -> [ceil(N/size), size, ...], padding with ``fill``."""
    pad = (-x.shape[0]) % size
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1),
                    constant_values=fill)
    return x.reshape((-1, size) + x.shape[1:])


def _merge_tile_topk(bv, bi, scores, cid, kprime: int, group: int):
    """Merge a [R, C] score tile (column ids ``cid`` [C]) into the running
    top-k' (bv, bi) [R, k'], with the result of one ``lax.top_k`` over the
    concatenation. A row's k' best scores of the tile lie in its k' column
    groups with the largest maxima (ties go to the lower group, and groups
    are contiguous columns), so only those groups, in column order, are
    sorted."""
    r, c = scores.shape
    n_groups = c // group
    tiles = scores.reshape(r, n_groups, group)
    _, top = jax.lax.top_k(jnp.max(tiles, axis=2), min(kprime, n_groups))
    top = jnp.sort(top, axis=1)
    cand = jnp.take_along_axis(tiles, top[:, :, None], axis=1)
    ids = jnp.take(cid.reshape(n_groups, group), top, axis=0)
    return _merge_topk(bv, bi, cand.reshape(r, -1), ids.reshape(r, -1),
                       kprime)


def ring_knn_local(w_loc, *, k: int, kprime: int, model_axis: str, n_shards: int,
                   compute_dtype=jnp.bfloat16, backend: str = "ref"):
    """shard_map body: exact KNN of the full W from per-device blocks.

    w_loc: [N_loc, D] local rows. Returns global neighbor ids [N_loc, k].
    Pass 1: bf16 ring scoring into a running top-k'. Pass 2: fp32 re-rank of
    the k' survivors (recomputed against the traveling block).

    ``backend="pallas"`` fuses each hop's score + top-k' through the
    ``kernels.ops.dist_topk`` kernel (the [N_loc, N_loc] score tile stays in
    VMEM); ``ref`` scores [ROW_CHUNK, COL_CHUNK] tiles of the hop and merges
    each into the running top-k' (``_merge_tile_topk``). Pass 2 gathers the
    candidates ``ROW_CHUNK`` rows at a time. Neither pass ever holds an
    [N_loc, N_loc] or [N_loc, k', D] array.
    """
    n_loc, d = w_loc.shape
    wn = w_loc.astype(jnp.float32)
    wn = wn / (jnp.linalg.norm(wn, axis=-1, keepdims=True) + 1e-12)
    w16 = wn.astype(compute_dtype)
    my = jax.lax.axis_index(model_axis)
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    group = min(GROUP, n_loc)
    col_chunk = -(-min(COL_CHUNK, n_loc) // group) * group
    row_chunk = min(ROW_CHUNK, n_loc)

    # ---- pass 1: bf16 scoring, running top-k' ---------------------------
    def hop(step, carry):
        block, bv, bi = carry
        src = (my - step) % n_shards  # owner of the block we hold now
        if backend == "pallas":
            # fused score + per-hop top-k'; the traveling block's local ids
            # are shifted to global AFTER the kernel (src is traced, block
            # geometry is static)
            hv, hi = ops.dist_topk(w16, block, kprime, block_q=256,
                                   block_n=512)
            hi = jnp.where(hi >= 0, hi + src * n_loc, -1)
            bv, bi = _merge_topk(bv, bi, hv, hi, kprime)
        else:
            ids = jnp.arange(n_loc, dtype=jnp.int32) + src * n_loc
            blocks = _chunk_rows(block, col_chunk, 0)
            cids = _chunk_rows(ids, col_chunk, -1)      # -1 = padding

            def rows(xs):
                w_r, v_r, i_r = xs              # [R, D], [R, k'], [R, k']

                def tile(carry, ys):
                    blk, cid = ys               # [C, D], [C]
                    s = jnp.einsum("nd,md->nm", w_r, blk,
                                   precision=jax.lax.Precision.DEFAULT,
                                   preferred_element_type=jnp.float32)
                    s = jnp.where(cid[None, :] >= 0, s, -jnp.inf)
                    return _merge_tile_topk(*carry, s, cid, kprime,
                                            group), None

                return jax.lax.scan(tile, (v_r, i_r), (blocks, cids))[0]

            bv, bi = jax.lax.map(rows, (_chunk_rows(w16, row_chunk, 0),
                                        _chunk_rows(bv, row_chunk, -jnp.inf),
                                        _chunk_rows(bi, row_chunk, -1)))
            bv = bv.reshape(-1, kprime)[:n_loc]
            bi = bi.reshape(-1, kprime)[:n_loc]
        block = jax.lax.ppermute(block, model_axis, perm)
        return block, bv, bi

    def _vary(x):  # mark as device-varying along the ring axis (scan carry)
        return jax.lax.pcast(x, (model_axis,), to="varying")

    with jax.named_scope("knn_pass1"):
        bv0 = _vary(jnp.full((n_loc, kprime), -jnp.inf, jnp.float32))
        bi0 = _vary(jnp.full((n_loc, kprime), -1, jnp.int32))
        _, bv, bi = jax.lax.fori_loop(0, n_shards, hop, (w16, bv0, bi0))

    # ---- pass 2: fp32 re-rank of the k' candidates ----------------------
    def hop32(step, carry):
        block, acc = carry
        src = (my - step) % n_shards
        rel = bi - src * n_loc              # candidate position in this block
        here = (rel >= 0) & (rel < n_loc)

        def rows(xs):
            w_r, rel_r = xs                 # [D], [k']
            cand = block[jnp.clip(rel_r, 0, n_loc - 1)]     # [k', D]
            return jnp.dot(cand, w_r, precision=jax.lax.Precision.HIGHEST)

        s = jax.lax.map(rows, (wn, rel), batch_size=row_chunk)
        acc = jnp.where(here, s, acc)
        block = jax.lax.ppermute(block, model_axis, perm)
        return block, acc

    with jax.named_scope("knn_pass2"):
        acc0 = _vary(jnp.full((n_loc, kprime), -jnp.inf, jnp.float32))
        _, exact = jax.lax.fori_loop(0, n_shards, hop32, (wn, acc0))
        exact = jnp.where(bi >= 0, exact, -jnp.inf)
        _, pos = jax.lax.top_k(exact, k)
    return jnp.take_along_axis(bi, pos, axis=1)


def build_graph_distributed(mesh, w_sharded, *, k: int, kprime: int,
                            model_axis: str = "model", backend: str = "ref"):
    """Run the ring build under shard_map on a W sharded over ``model``.
    Returns the global graph [N, k] (row-sharded the same way)."""
    from jax.sharding import PartitionSpec as P

    n_shards = mesh.shape[model_axis]
    body = functools.partial(ring_knn_local, k=k, kprime=kprime,
                             model_axis=model_axis, n_shards=n_shards,
                             backend=backend)
    fn = jax.shard_map(body, mesh=mesh, in_specs=P(model_axis, None),
                       out_specs=P(model_axis, None), check_vma=False)
    return jax.jit(fn)(w_sharded)


# ---------------------------------------------------------------------------
# compression (paper §3.2.3): host-side CSR build, per model shard
# ---------------------------------------------------------------------------


def compress_graph(graph: np.ndarray, n_shards: int) -> CompressedGraph:
    """graph: [N, k] global neighbor ids (host numpy).

    For shard p, keep only neighbors owned by p (id // n_loc == p), stored as
    LOCAL ids, CSR over all N rows. Shards are padded to a common nnz cap so
    the result is one [P, ...] array shardable over "model".

    This is the paper's per-node graph compression: average storage drops
    from N·k to N·k/P per device.
    """
    graph = np.asarray(graph)
    n, k = graph.shape
    assert n % n_shards == 0, f"N={n} not divisible by shards={n_shards}"
    n_loc = n // n_shards
    owner = graph // n_loc
    local = graph % n_loc
    col = np.broadcast_to(np.arange(k, dtype=np.int32), graph.shape)
    offsets = np.zeros((n_shards, n + 1), np.int32)
    values, rvalues = [], []
    for p in range(n_shards):
        mask = owner == p
        counts = mask.sum(axis=1)
        offsets[p, 1:] = np.cumsum(counts)
        values.append(local[mask].astype(np.int32))
        rvalues.append(col[mask].astype(np.int32))
    nnz_cap = max(int(v.size) for v in values)
    neigh = np.full((n_shards, nnz_cap), -1, np.int32)
    ranks = np.full((n_shards, nnz_cap), -1, np.int32)
    for p, (v, r) in enumerate(zip(values, rvalues)):
        neigh[p, : v.size] = v
        ranks[p, : r.size] = r
    return CompressedGraph(jnp.asarray(offsets), jnp.asarray(neigh),
                           jnp.asarray(ranks))


def graph_storage_bytes(cg: CompressedGraph) -> dict:
    """Storage accounting used by the Table-3-style benchmark."""
    per_shard = cg.neighbors.shape[1] * 4 + cg.offsets.shape[1] * 4
    return {"per_shard_bytes": per_shard,
            "total_bytes": per_shard * cg.offsets.shape[0]}
