"""Hybrid parallel pipelining (paper §3.3.1) + gradient accumulation.

The paper splits each mini-batch into micro-batches so the fc shards can
all-gather micro-batch i's features while the FE computes micro-batch i+1
(and symmetrically in backward). In XLA there are no manual streams: we
express the same structure — per-micro-batch FE -> all-gather -> head -> and
accumulate — as a lax.scan, and the async-collective latency-hiding
scheduler overlaps hops across scan iterations on TPU. The micro-batch split
also cuts peak activation memory exactly as the paper notes.

``grad_accum`` additionally implements FCCS's n× batch enlargement: n scan
steps of micro-grad accumulation per optimizer update, which divides
data-parallel gradient traffic by n.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def split_microbatches(inputs: dict, n_micro: int) -> dict:
    """[B, ...] -> [n_micro, B/n_micro, ...] for every input leaf."""
    def split(x):
        b = x.shape[0]
        assert b % n_micro == 0, f"batch {b} % micro {n_micro} != 0"
        return x.reshape((n_micro, b // n_micro) + x.shape[1:])
    return jax.tree.map(split, inputs)


def _value_and_grad(loss_fn: Callable, params, inputs):
    """``jax.value_and_grad(loss_fn, has_aux=True)(params, inputs)`` as its
    vjp, so the forward and the backward each run under a name of their
    own (``head_fwd``, ``head_bwd``) in the HLO and the device trace."""
    with jax.named_scope("head_fwd"):
        loss, vjp_fn, metrics = jax.vjp(lambda p: loss_fn(p, inputs), params,
                                        has_aux=True)
    with jax.named_scope("head_bwd"):
        grads, = vjp_fn(jnp.ones_like(loss))
    return (loss, metrics), grads


def prepare_once(prepare: Callable, params):
    """Run ``prepare(params)`` once per update, ahead of the micro-batch
    loop, for a transform of the params that is fixed while they are (a
    cosine head's row normalization of W).

    The loop then differentiates the loss against ``prepared`` and
    accumulates that gradient; ``pull_back`` maps the accumulated gradient
    to one against ``params`` in a single vjp: J^T (sum g_i) / n in place of
    sum J^T g_i / n, the same gradient up to float reassociation. The two
    halves run under the names ``head_prepare`` and ``head_prepare_bwd``.
    Returns (prepared, pull_back)."""
    with jax.named_scope("head_prepare"):
        prepared, vjp_fn = jax.vjp(prepare, params)

    def pull_back(grads):
        with jax.named_scope("head_prepare_bwd"):
            return vjp_fn(grads)[0]

    return prepared, pull_back


def microbatched_value_and_grad(
    loss_fn: Callable, params, inputs: dict, n_micro: int,
):
    """Mean loss/grads over n_micro micro-batches via lax.scan.

    loss_fn(params, micro_inputs) -> (loss, metrics). Gradients accumulate in
    fp32 (under the name ``grad_accum``). Metrics are averaged. This is the
    pipelined/accumulated step body: with n_micro=1 it degenerates to the
    paper's Fig. 4(a) baseline.
    """
    if n_micro == 1:
        return _value_and_grad(loss_fn, params, inputs)

    micro = split_microbatches(inputs, n_micro)

    def body(carry, micro_inputs):
        acc_g, acc_l, acc_m = carry
        (loss, metrics), grads = _value_and_grad(loss_fn, params,
                                                 micro_inputs)
        with jax.named_scope("grad_accum"):
            acc_g = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) / n_micro, acc_g,
                grads)
            acc_m = jax.tree.map(lambda a, m: a + m / n_micro, acc_m,
                                 metrics)
        return (acc_g, acc_l + loss / n_micro, acc_m), None

    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    first = jax.tree.map(lambda x: x[0], micro)
    m0 = jax.tree.map(lambda _: jnp.zeros((), jnp.float32),
                      jax.eval_shape(lambda: loss_fn(params, first)[1]))
    (grads, loss, metrics), _ = jax.lax.scan(
        body, (g0, jnp.zeros((), jnp.float32), m0), micro)
    return (loss, metrics), grads
