"""KNN softmax (paper §3.2): exact distributed graph build, compression,
active-class selection (Algorithm 1) invariants, lossless-limit equivalence."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import knn_graph as kg
from repro.core import knn_softmax as ks
from repro.core import sharded_softmax as ss

KSPEC = {"accuracy": P(), "logz": P(), "active_frac": P(),
         "label_recall": P()}


@pytest.fixture(scope="module")
def problem():
    key = jax.random.PRNGKey(0)
    kf, kw, ky = jax.random.split(key, 3)
    N, D, B = 64, 32, 16
    return (jax.random.normal(kf, (B, D)),
            jax.random.normal(kw, (N, D)),
            jax.random.randint(ky, (B,), 0, N))


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("tiles,k,kprime", [
    (None, 8, 16),
    ((8, 5, 2), 8, 16),     # 2 column chunks per hop, row remainder
    ((8, 5, 2), 2, 3),      # 4 groups per chunk: the group prefilter picks 3
])
def test_ring_build_is_exact(mesh2x4, problem, monkeypatch, tiles, k, kprime,
                             backend):
    """Exact with whole-shard tiles and with (COL_CHUNK, ROW_CHUNK, GROUP)
    tiles that split the 16-row shard and leave remainders."""
    if tiles is not None:
        for name, size in zip(("COL_CHUNK", "ROW_CHUNK", "GROUP"), tiles):
            monkeypatch.setattr(kg, name, size)
    _, w, _ = problem
    g_ref = kg.knn_graph_ref(w, k)
    w_sh = jax.device_put(w, NamedSharding(mesh2x4, P("model", None)))
    g = np.asarray(kg.build_graph_distributed(mesh2x4, w_sh, k=k,
                                              kprime=kprime, backend=backend))
    assert (np.sort(g, 1) == np.sort(np.asarray(g_ref), 1)).all()


def test_self_is_first_neighbor(problem):
    """Normalized W: w_y ranks first in its own list — the property
    Algorithm 1's lossless label inclusion relies on."""
    _, w, _ = problem
    g = np.asarray(kg.knn_graph_ref(w, 8))
    assert (g[:, 0] == np.arange(w.shape[0])).all()


def test_compression_roundtrip(problem):
    """CSR per shard contains exactly the local-owned neighbor entries."""
    _, w, _ = problem
    n = w.shape[0]
    g = np.asarray(kg.knn_graph_ref(w, 8))
    cg = kg.compress_graph(g, 4)
    n_loc = n // 4
    for p in range(4):
        offs = np.asarray(cg.offsets[p])
        nbrs = np.asarray(cg.neighbors[p])
        for row in range(n):
            got = sorted(nbrs[offs[row]:offs[row + 1]].tolist())
            want = sorted((g[row][(g[row] // n_loc) == p] % n_loc).tolist())
            assert got == want, (p, row)
    # paper's memory claim: sum of shard storage ~= full graph
    total_entries = sum(int(cg.offsets[p][-1]) for p in range(4))
    assert total_entries == g.size


def _knn_fn(mesh, B, m_local, k_cap, pad_random=False):
    body = functools.partial(
        ks.knn_softmax_local, model_axis="model", batch_axes=("data",),
        global_batch=B, m_local=m_local, k_cap=k_cap, cosine_scale=16.0,
        pad_random=pad_random)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("data", None), P("data"), P("model", None),
                  P("model", None), P("model", None), P("model", None)),
        out_specs=(P(), dict(KSPEC)))


def test_label_recall_is_one(mesh2x4, problem):
    f, w, y = problem
    g = np.asarray(kg.knn_graph_ref(w, 8))
    cg = kg.compress_graph(g, 4)
    fn = _knn_fn(mesh2x4, f.shape[0], m_local=24, k_cap=8)
    with jax.set_mesh(mesh2x4):
        loss, m = jax.jit(fn)(f, y, w, cg.offsets, cg.neighbors, cg.ranks)
    assert float(m["label_recall"]) == 1.0
    assert bool(jnp.isfinite(loss))


def test_all_active_limit_equals_full_softmax(mesh2x4, problem):
    """K = N and M_local = V_local: KNN softmax == full cosine softmax."""
    f, w, y = problem
    n = w.shape[0]
    g = np.asarray(kg.knn_graph_ref(w, n))
    cg = kg.compress_graph(g, 4)
    fn = _knn_fn(mesh2x4, f.shape[0], m_local=n // 4, k_cap=n)
    with jax.set_mesh(mesh2x4):
        loss, m = jax.jit(fn)(f, y, w, cg.offsets, cg.neighbors, cg.ranks)
    loss_ref, _ = ss.ce_ref(f, y, w, cosine_scale=16.0)
    assert abs(float(loss) - float(loss_ref)) < 1e-4


def test_knn_loss_lower_bounds_full(mesh2x4, problem):
    """Fewer active classes -> smaller Z -> loss <= full softmax loss."""
    f, w, y = problem
    g = np.asarray(kg.knn_graph_ref(w, 8))
    cg = kg.compress_graph(g, 4)
    fn = _knn_fn(mesh2x4, f.shape[0], m_local=12, k_cap=8)
    with jax.set_mesh(mesh2x4):
        loss, _ = jax.jit(fn)(f, y, w, cg.offsets, cg.neighbors, cg.ranks)
    loss_full, _ = ss.ce_ref(f, y, w, cosine_scale=16.0)
    assert float(loss) <= float(loss_full) + 1e-5


def test_knn_grads_touch_only_active_rows(mesh2x4, problem):
    f, w, y = problem
    g = np.asarray(kg.knn_graph_ref(w, 4))
    cg = kg.compress_graph(g, 4)
    # loss-only shard_map: old-jax transpose chokes on the symbolic-zero
    # cotangents of the stop-gradient'd metrics outputs
    body = functools.partial(
        ks.knn_softmax_local, model_axis="model", batch_axes=("data",),
        global_batch=f.shape[0], m_local=10, k_cap=4, cosine_scale=16.0,
        pad_random=False)
    fn = jax.shard_map(
        lambda *a: body(*a)[0], mesh=mesh2x4,
        in_specs=(P("data", None), P("data"), P("model", None),
                  P("model", None), P("model", None), P("model", None)),
        out_specs=P())
    with jax.set_mesh(mesh2x4):
        gw = jax.jit(jax.grad(
            lambda w_: fn(f, y, w_, cg.offsets, cg.neighbors, cg.ranks)))(w)
    rows = np.abs(np.asarray(gw)).sum(axis=1)
    n_nonzero = int((rows > 0).sum())
    # bound: m_local per (model shard x data row) = 10 * 4 * 2
    assert 0 < n_nonzero <= 80
    # and far fewer than N rows are touched (the paper's sparse-update win)
    assert n_nonzero < 0.75 * w.shape[0]
