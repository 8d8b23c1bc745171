"""Plain float32 ``jax.numpy`` references of the paper system's training
step and top-k retrieval, written from the paper's description (Song et
al., KDD 2020, §3.1-3.4) and independent of ``repro``: nothing here
imports the program or takes anything it made. The class matrix is drawn
again from the seed, the inputs come from the benchmark's generator.

- Cosine-softmax cross entropy: features and class rows L2-normalized,
  logits ``scale * f @ w.T``, loss the batch mean of logsumexp minus the
  label logit.
- KNN softmax (§3.2, Algorithm 1): per micro-batch the active classes are
  the union of the labels' k-nearest-neighbour lists in the exact cosine
  graph of the class rows at the last refresh, ranked by their best
  position in any list, up to M slots. The configuration leaves out line
  7's random filler classes, so the slots past the union stay empty.
- SGD with momentum and weight decay: ``mu = m * mu + (g + wd * w)``,
  ``w -= lr * mu``; the FCCS learning rate warms up linearly over
  ``t_warm`` updates to ``eta0``. A global batch is the mean over its
  micro-batches.

``precision`` is the matmul precision of every product: ``"highest"`` is
the reference, ``"high"`` (three bf16 passes) its control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 1024          # rows of a block: [ROWS, V] float32 logits at a time


def _unit(x):
    x = x.astype(jnp.float32)
    return x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + 1e-12)


def init_w(seed31: int, classes: int, d: int):
    """The class matrix at step 0: ``N(0, 1/d)`` entries drawn from the
    second half of the seed's split (the first is the trunk's)."""
    _, k2 = jax.random.split(jax.random.PRNGKey(seed31))
    return jax.random.normal(k2, (classes, d)) / jnp.sqrt(d)


def lr_at(t: int, fccs: dict) -> float:
    if t < fccs["t_warm"]:
        return fccs["eta0"] * (t + 1) / fccs["t_warm"]
    return fccs["eta0"]


# ---------------------------------------------------------------------------
# full softmax
# ---------------------------------------------------------------------------


def _full_loss(w, f, y, scale):
    logits = scale * (_unit(f) @ _unit(w).T)
    lse = jax.nn.logsumexp(logits, axis=-1)
    return jnp.sum(lse - jnp.take_along_axis(logits, y[:, None], 1)[:, 0])


_full_grad = jax.jit(jax.value_and_grad(_full_loss), static_argnums=3)


def full_loss_and_grad(w, f, y, scale: float):
    """(mean loss, mean gradient [V, D]) over rows, ROWS at a time."""
    n = f.shape[0]
    total, grad = 0.0, jnp.zeros_like(w)
    for s in range(0, n, ROWS):
        l, g = _full_grad(w, f[s:s + ROWS], y[s:s + ROWS], scale)
        total, grad = total + l, grad + g
    return total / n, grad / n


# ---------------------------------------------------------------------------
# KNN softmax
# ---------------------------------------------------------------------------


def top_k(s, k: int):
    """(values, ids) of the k largest entries per row, by k passes of
    argmax: exact, and no sort of the whole row."""
    rows = jnp.arange(s.shape[0])
    vals, ids = [], []
    for _ in range(k):
        i = jnp.argmax(s, axis=1)
        vals.append(s[rows, i])
        ids.append(i.astype(jnp.int32))
        s = s.at[rows, i].set(-jnp.inf)
    return jnp.stack(vals, 1), jnp.stack(ids, 1)


@jax.jit
def _block_neighbors(wn, rows, k_idx):
    return top_k(wn[rows] @ wn.T, k_idx.shape[0])[1]


def knn_rows(w, rows: np.ndarray, k: int) -> np.ndarray:
    """Exact cosine neighbour lists [len(rows), k] (self first)."""
    wn = _unit(w)
    out = []
    kk = jnp.zeros((k,))
    for s in range(0, len(rows), 256):
        out.append(np.asarray(_block_neighbors(
            wn, jnp.asarray(rows[s:s + 256]), kk)))
    return np.concatenate(out)


def active_set(y: np.ndarray, nbrs: dict, *, m: int) -> np.ndarray:
    """Algorithm 1 on one micro-batch's labels ``y``, without fillers:
    every neighbour of a label, by its best position in any list, then by
    id, up to ``m``."""
    lists = np.stack([nbrs[int(v)] for v in y])
    pos = np.broadcast_to(np.arange(lists.shape[1]), lists.shape)
    ids, first = np.unique(lists.reshape(-1), return_inverse=True)
    best = np.full(len(ids), lists.shape[1])
    np.minimum.at(best, first, pos.reshape(-1))
    return ids[np.lexsort((ids, best))][:m].astype(np.int32)


def neighbor_gap(seed31: int, rows: np.ndarray, lists: list, *,
                 classes: int, d: int, k: int,
                 precision: str = "highest") -> float:
    """The widest amount by which the cosine of a listed neighbour of a
    row lies below the row's k-th best cosine in the exact graph of the
    class matrix at step 0, over ``rows`` and their program-made
    ``lists``; 2 (the widest a cosine gap can be) for a list that is not
    k distinct ids in range."""
    with jax.default_matmul_precision(precision):
        wn = _unit(init_w(seed31, classes, d))
        sims = np.concatenate([
            np.asarray(_block_sims(wn, jnp.asarray(rows[s:s + 256])))
            for s in range(0, len(rows), 256)])
    kth = np.partition(sims, -k, axis=1)[:, -k]
    gap = 0.0
    for i, lst in enumerate(lists):
        lst = np.asarray(lst)
        if (len(lst) != k or len(np.unique(lst)) != k
                or lst.min() < 0 or lst.max() >= classes):
            return 2.0
        gap = max(gap, float(np.max(kth[i] - sims[i][lst])))
    return gap


@jax.jit
def _block_sims(wn, rows):
    return wn[rows] @ wn.T


def _knn_loss(w, ids, keep, f, y, scale):
    wa = _unit(w[ids])
    logits = jnp.where(keep[None, :], scale * (_unit(f) @ wa.T), -jnp.inf)
    pos = jnp.argmax((ids[None, :] == y[:, None]) & keep[None, :], axis=1)
    lse = jax.nn.logsumexp(logits, axis=-1)
    return jnp.sum(lse - jnp.take_along_axis(logits, pos[:, None], 1)[:, 0])


_knn_grad = jax.jit(jax.value_and_grad(_knn_loss), static_argnums=5)


# ---------------------------------------------------------------------------
# training: the first steps from the seed
# ---------------------------------------------------------------------------


def train_steps(*, seed: int, n_steps: int, classes: int, d: int,
                batch_fn, micro: int, head: dict, opt: dict, fccs: dict,
                precision: str = "highest", half_batch: bool = False):
    """Run ``n_steps`` updates from the seed. ``batch_fn(t)`` gives step
    t's global batch. ``half_batch`` plants a fault: each micro-batch's
    second half is left out and the mean taken over the rest.

    Returns {"losses": [...], "grad_norm": |g_0| of the class matrix,
    "update_norm": |w_n - w_0|, and on the host "grad": g_0}."""
    from bench.traffic.generate import seed31
    scale = float(head["cosine_scale"])
    knn = head["softmax_impl"] == "knn"
    out = {"losses": []}
    with jax.default_matmul_precision(precision):
        w0 = init_w(seed31(seed), classes, d)
        w, mu = w0, jnp.zeros_like(w0)
        nbrs = {}
        if knn:
            m = max(8, int(classes * head["active_frac"]))
            labels = np.unique(np.concatenate(
                [np.asarray(batch_fn(t)["labels"]) for t in range(n_steps)]))
            lists = knn_rows(w0, labels, head["knn_k"])
            nbrs = dict(zip(labels.tolist(), lists))
        for t in range(n_steps):
            b = batch_fn(t)
            f, y = jnp.asarray(b["features"]), np.asarray(b["labels"])
            n_micro = f.shape[0] // micro
            total, grad = 0.0, jnp.zeros_like(w)
            for i in range(n_micro):
                fm = f[i * micro:(i + 1) * micro]
                ym = y[i * micro:(i + 1) * micro]
                if half_batch:
                    fm, ym = fm[:micro // 2], ym[:micro // 2]
                if knn:
                    ids = active_set(ym, nbrs, m=m)
                    keep = np.arange(m) < len(ids)
                    ids = np.pad(ids, (0, m - len(ids)))
                    l, g = _knn_grad(w, jnp.asarray(ids), jnp.asarray(keep),
                                     fm, jnp.asarray(ym), scale)
                    l, g = l / len(ym), g / len(ym)
                else:
                    l, g = full_loss_and_grad(w, fm, jnp.asarray(ym), scale)
                total, grad = total + l / n_micro, grad + g / n_micro
            out["losses"].append(float(total))
            g = grad + opt["weight_decay"] * w
            if t == 0:
                out["grad_norm"] = float(jnp.linalg.norm(grad))
                out["grad"] = np.asarray(grad)
            mu = opt["momentum"] * mu + g
            w = w - lr_at(t, fccs) * mu
        out["update_norm"] = float(jnp.linalg.norm(w - w0))
    return out


# ---------------------------------------------------------------------------
# retrieval
# ---------------------------------------------------------------------------


@jax.jit
def _block_topk(wn, q, ids, k_idx):
    sc = _unit(q) @ wn.T
    best, best_ids = top_k(sc, k_idx.shape[0])
    return best, best_ids, jnp.take_along_axis(sc, ids, 1)


def topk_scores(seed: int, queries: np.ndarray, served_ids: np.ndarray, *,
                classes: int, d: int, k: int, precision: str = "highest"):
    """For each query [n, D]: the k best cosine scores [n, k] (descending)
    with their ids, and the score of each served id [n, k]."""
    from bench.traffic.generate import seed31
    out = ([], [], [])
    kk = jnp.zeros((k,))
    with jax.default_matmul_precision(precision):
        wn = _unit(init_w(seed31(seed), classes, d))
        for s in range(0, len(queries), 128):
            ids = jnp.asarray(np.clip(served_ids[s:s + 128], 0, classes - 1))
            for acc, x in zip(out, _block_topk(
                    wn, jnp.asarray(queries[s:s + 128]), ids, kk)):
                acc.append(np.asarray(x))
    return tuple(np.concatenate(a) for a in out)
