"""Hybrid-parallel distributed softmax (paper §3.1) vs single-device oracle:
loss, gradients, cosine-normalized variant, vocab padding mask, distributed
greedy argmax."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import sharded_softmax as ss

MSPEC = {"accuracy": P(), "logz": P()}


def _make(mesh, B, cosine=0.0, n_valid=0, loss_only=False):
    """loss_only drops the metrics output — needed when differentiating
    THROUGH the shard_map (old-jax transpose chokes on the symbolic-zero
    cotangents of the stop-gradient'd metrics)."""
    body = functools.partial(ss.full_softmax_local, model_axis="model",
                             batch_axes=("data",), global_batch=B,
                             cosine_scale=cosine, n_valid=n_valid)
    if loss_only:
        return jax.shard_map(lambda f, y, w: body(f, y, w)[0], mesh=mesh,
                             in_specs=(P("data", None), P("data"),
                                       P("model", None)),
                             out_specs=P())
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P("data", None), P("data"),
                                   P("model", None)),
                         out_specs=(P(), dict(MSPEC)))


@pytest.fixture(scope="module")
def problem():
    key = jax.random.PRNGKey(0)
    kf, kw, ky = jax.random.split(key, 3)
    N, D, B = 64, 32, 16
    return (jax.random.normal(kf, (B, D)),
            jax.random.normal(kw, (N, D)),
            jax.random.randint(ky, (B,), 0, N))


@pytest.mark.parametrize("cosine", [0.0, 16.0])
def test_loss_matches_oracle(mesh2x4, problem, cosine):
    f, w, y = problem
    fn = _make(mesh2x4, f.shape[0], cosine)
    # a cosine head hands the body unit rows of W (its prepare_params); the
    # oracle normalizes the raw W itself
    w_in = ss._normalize(w) if cosine else w
    with jax.set_mesh(mesh2x4):
        loss, m = jax.jit(fn)(f, y, w_in)
    loss_ref, m_ref = ss.ce_ref(f, y, w, cosine_scale=cosine)
    assert abs(float(loss) - float(loss_ref)) < 1e-4
    assert abs(float(m["accuracy"]) - float(m_ref["accuracy"])) < 1e-6


def test_grads_match_oracle(mesh2x4, problem):
    f, w, y = problem
    fn = _make(mesh2x4, f.shape[0], loss_only=True)
    with jax.set_mesh(mesh2x4):
        gw = jax.jit(jax.grad(lambda w_: fn(f, y, w_)))(w)
        gf = jax.jit(jax.grad(lambda f_: fn(f_, y, w)))(f)
    gw_ref = jax.grad(lambda w_: ss.ce_ref(f, y, w_)[0])(w)
    gf_ref = jax.grad(lambda f_: ss.ce_ref(f_, y, w)[0])(f)
    assert float(jnp.max(jnp.abs(gw - gw_ref))) < 1e-5
    assert float(jnp.max(jnp.abs(gf - gf_ref))) < 1e-5


def test_fc_gradient_is_local(mesh2x4, problem):
    """The paper's key property: each shard's dW depends only on its own
    rows — rows outside a shard get exactly the oracle's rows (no mixing)."""
    f, w, y = problem
    fn = _make(mesh2x4, f.shape[0], loss_only=True)
    with jax.set_mesh(mesh2x4):
        gw = jax.jit(jax.grad(lambda w_: fn(f, y, w_)))(w)
    gw_ref = jax.grad(lambda w_: ss.ce_ref(f, y, w_)[0])(w)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ref), atol=1e-5)


def test_vocab_padding_masked(mesh2x4):
    """Padded rows must not perturb Z: loss over padded W == loss over W."""
    key = jax.random.PRNGKey(1)
    N, NP, D, B = 60, 64, 32, 16
    f = jax.random.normal(key, (B, D))
    w = jax.random.normal(jax.random.fold_in(key, 1), (N, D))
    y = jax.random.randint(jax.random.fold_in(key, 2), (B,), 0, N)
    wp = jnp.concatenate([w, jnp.full((NP - N, D), 3.0)])  # poison pad rows
    fn = _make(mesh2x4, B, n_valid=N)
    with jax.set_mesh(mesh2x4):
        loss, _ = jax.jit(fn)(f, y, wp)
    loss_ref, _ = ss.ce_ref(f, y, w)
    assert abs(float(loss) - float(loss_ref)) < 1e-4


def test_distributed_greedy_argmax(mesh2x4, problem):
    f, w, y = problem
    body = functools.partial(ss.serve_logits_local, model_axis="model")
    fn = jax.shard_map(body, mesh=mesh2x4,
                       in_specs=(P("data", None), P("model", None)),
                       out_specs=(P("data"), P("data", "model")))
    with jax.set_mesh(mesh2x4):
        tok, logits = jax.jit(fn)(f, w)
    ref = jnp.argmax(f @ w.T, axis=-1)
    assert jnp.array_equal(tok, ref)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(f @ w.T), rtol=1e-5, atol=1e-5)
