"""Checkpoint roundtrip, optimizers, loss scaling, baselines, HLO analyzer,
microbatch pipeline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as ckpt
from repro.core import baselines as bl
from repro.core.pipeline import microbatched_value_and_grad
from repro.optim import adam, apply_updates, lars, make_optimizer, sgd
from repro.optim.scale import (LossScaleState, dynamic_loss_scale,
                               scaled_grads)
from repro.optim.scale import init_loss_scale
from repro.roofline import hlo


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    from repro.optim.optimizers import OptState
    tree = {"w": jnp.arange(12.0).reshape(3, 4),
            "opt": OptState(step=jnp.asarray(7, jnp.int32),
                            mu={"w": jnp.ones((3, 4)) * 0.5}),
            "meta": [jnp.zeros((2,), jnp.bfloat16)]}
    path = str(tmp_path / "ck")
    ckpt.save(path, tree, step=42)
    assert ckpt.latest_step(path) == 42
    restored, step = ckpt.restore(path, tree)
    assert step == 42
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_multiple_steps(tmp_path):
    path = str(tmp_path / "ck")
    for s in (1, 5, 3):
        ckpt.save(path, {"x": jnp.asarray(float(s))}, step=s)
    assert ckpt.latest_step(path) == 5
    tree, s = ckpt.restore(path, {"x": jnp.asarray(0.0)})
    assert s == 5 and float(tree["x"]) == 5.0


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------


def _quadratic_converges(opt, lr, steps=300):
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"x": jnp.full(3, 0.1)}  # nonzero: LARS trust needs ||w|| > 0
    state = opt.init(params)
    for _ in range(steps):
        g = {"x": 2 * (params["x"] - target)}
        upd, state = opt.update(g, state, params, lr)
        params = apply_updates(params, upd)
    return float(jnp.max(jnp.abs(params["x"] - target)))


@pytest.mark.parametrize("opt,lr", [(sgd(momentum=0.9), 0.05),
                                    (adam(), 0.1),
                                    (lars(trust_coef=0.05,
                                          weight_decay=0.0), 0.05)])
def test_optimizers_converge_quadratic(opt, lr):
    assert _quadratic_converges(opt, lr) < 0.05


def test_make_optimizer_dispatch():
    from repro.configs.base import TrainConfig
    for name in ("sgd", "lars", "adam"):
        make_optimizer(TrainConfig(optimizer=name))
    with pytest.raises(ValueError):
        make_optimizer(TrainConfig(optimizer="bogus"))


# ---------------------------------------------------------------------------
# loss scaling (paper's fp16 recipe)
# ---------------------------------------------------------------------------


def test_scaled_grads_match_unscaled():
    def loss_fn(p, x):
        return jnp.sum(p["w"] * x) ** 2, {}
    p = {"w": jnp.asarray([1.0, 2.0])}
    x = jnp.asarray([0.5, -1.0])
    (_, _), g1, finite = scaled_grads(loss_fn, p, x,
                                      scale=jnp.asarray(1024.0))
    g2 = jax.grad(lambda p_: loss_fn(p_, x)[0])(p)
    assert bool(finite)
    np.testing.assert_allclose(np.asarray(g1["w"]), np.asarray(g2["w"]),
                               rtol=1e-5)


def test_dynamic_scale_shrinks_on_overflow_grows_on_success():
    st = init_loss_scale(1024.0)
    st2, apply = dynamic_loss_scale(st, jnp.asarray(False))
    assert float(st2.scale) == 512.0 and not bool(apply)
    st3 = st
    for _ in range(200):
        st3, _ = dynamic_loss_scale(st3, jnp.asarray(True),
                                    growth_interval=200)
    assert float(st3.scale) >= 2048.0


# ---------------------------------------------------------------------------
# baselines
# ---------------------------------------------------------------------------


def test_selective_includes_labels():
    key = jax.random.PRNGKey(0)
    N, D, B = 128, 32, 16
    w = jax.random.normal(key, (N, D))
    f = jax.random.normal(jax.random.fold_in(key, 1), (B, D))
    y = jax.random.randint(jax.random.fold_in(key, 2), (B,), 0, N)
    tabs = bl.build_lsh_tables(jax.random.fold_in(key, 3), w, 4, 6)
    ids, valid = bl.selective_active(f, y, tabs, m=64, cap=16)
    assert bool(jnp.isin(y, ids[valid]).all())


def test_selective_is_lossy_vs_full():
    """LSH recall < 1: selective active set misses some true neighbors."""
    key = jax.random.PRNGKey(1)
    N, D, B = 256, 32, 8
    w = jax.random.normal(key, (N, D))
    f = jax.random.normal(jax.random.fold_in(key, 1), (B, D))
    y = jax.random.randint(jax.random.fold_in(key, 2), (B,), 0, N)
    tabs = bl.build_lsh_tables(jax.random.fold_in(key, 3), w, 2, 6)
    ids, valid = bl.selective_active(f, y, tabs, m=64, cap=8)
    assert int(valid.sum()) < N  # not all classes recalled


def test_mach_learns_buckets():
    key = jax.random.PRNGKey(2)
    N, D, B = 64, 16, 32
    head = bl.init_mach(key, N, D, n_buckets=16, n_rep=3)
    protos = jax.random.normal(jax.random.fold_in(key, 5), (N, D))
    wh = head.w
    for t in range(150):
        k = jax.random.fold_in(key, t)
        y = jax.random.randint(k, (B,), 0, N)
        f = protos[y] + 0.05 * jax.random.normal(jax.random.fold_in(k, 1),
                                                 (B, D))
        g = jax.grad(lambda w_: bl.mach_loss(bl.MACHHead(head.hashes, w_),
                                             f, y))(wh)
        wh = wh - 0.5 * g
    y = jnp.arange(32)
    f = protos[y]
    pred = bl.mach_predict(bl.MACHHead(head.hashes, wh), f)
    acc = float(jnp.mean((pred == y).astype(jnp.float32)))
    assert acc > 0.5  # learnable but lossy (paper: below full softmax)


# ---------------------------------------------------------------------------
# loop-aware HLO analyzer
# ---------------------------------------------------------------------------


def _cost_analysis(co):
    ca = co.cost_analysis()
    return ca[0] if isinstance(ca, list) else ca  # old jax wraps in a list


def test_hlo_loop_free_matches_cost_analysis():
    def g(x, w):
        return jax.nn.relu(x @ w)
    co = jax.jit(g).lower(
        jax.ShapeDtypeStruct((64, 128), jnp.float32),
        jax.ShapeDtypeStruct((128, 256), jnp.float32)).compile()
    a = hlo.analyze(co.as_text())
    assert a.flops == 2 * 64 * 128 * 256
    assert a.bytes == _cost_analysis(co)["bytes accessed"]


def test_hlo_scan_multiplies_trip_count():
    def g(x):
        def step(c, _):
            return c @ c, None
        y, _ = jax.lax.scan(step, x, None, length=7)
        return y
    co = jax.jit(g).lower(
        jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile()
    a = hlo.analyze(co.as_text())
    assert a.flops == 7 * 2 * 128 ** 3
    # raw cost_analysis counts the body once (the bug we correct); the loop
    # counter contributes a couple of extra scalar flops
    assert _cost_analysis(co)["flops"] < 1.01 * 2 * 128 ** 3


def test_hlo_collectives_in_loops(mesh2x4):
    from jax.sharding import PartitionSpec as P

    def body(x):
        def step(c, _):
            return jax.lax.psum(c @ c, "model"), None
        y, _ = jax.lax.scan(step, x, None, length=5)
        return y
    fn = jax.shard_map(body, mesh=mesh2x4, in_specs=P(None, None),
                       out_specs=P(None, None))
    with jax.set_mesh(mesh2x4):
        co = jax.jit(fn).lower(
            jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    a = hlo.analyze(co.as_text())
    assert a.collectives["all-reduce"]["count"] == 5
    assert a.collectives["all-reduce"]["bytes"] == 5 * 64 * 64 * 4


# ---------------------------------------------------------------------------
# microbatch pipeline
# ---------------------------------------------------------------------------


def test_microbatched_grads_equal_full_batch():
    key = jax.random.PRNGKey(0)
    w = {"w": jax.random.normal(key, (8, 4))}
    x = jax.random.normal(jax.random.fold_in(key, 1), (16, 8))

    def loss_fn(p, inputs):
        return jnp.mean((inputs["x"] @ p["w"]) ** 2), {"m": jnp.zeros(())}

    (l1, _), g1 = microbatched_value_and_grad(loss_fn, w, {"x": x}, 1)
    (l4, _), g4 = microbatched_value_and_grad(loss_fn, w, {"x": x}, 4)
    assert abs(float(l1) - float(l4)) < 1e-6
    np.testing.assert_allclose(np.asarray(g1["w"]), np.asarray(g4["w"]),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# process bootstrap: persistent compilation cache
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """An env-named cache directory is JAX's own business (nothing is set
    in code); without one the cache sits at the fixed <repo>/.jax_cache."""
    import os

    from repro.api import bootstrap
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = bootstrap.enable_compile_cache()
        if env_dir is None:
            assert got == os.path.join(bootstrap.REPO_ROOT, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            assert os.path.isfile(os.path.join(bootstrap.REPO_ROOT,
                                               "chip_smoke.py"))
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
