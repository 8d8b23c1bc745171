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
data-parallel gradient traffic by n. A parameter whose gradient in each
micro-batch is a set of its rows (a ``RowTap``) is accumulated by adding
those rows into its accumulator, in place in the scan's carry.
"""
from __future__ import annotations

import dataclasses
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


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RowTap:
    """Stands in ``params`` for an [n, D] parameter whose gradient in each
    micro-batch is a set of A of its rows (the KNN head's active classes).

    ``rows`` [A, D] is zeros: the loss adds it to the A rows it reads, so
    the loss's gradient against them arrives as ``rows``' cotangent, and
    the parameter itself is not differentiated. With a tap in ``params``
    the loss returns ``(loss, (metrics, ids))``: ``ids`` holds one [A]
    array of row ids per tap, in the order of ``params``' leaves, an id
    outside [0, n) for a slot that writes nothing."""
    rows: jax.Array
    n: int = dataclasses.field(metadata=dict(static=True))


def _is_tap(x) -> bool:
    return isinstance(x, RowTap)


def _accumulate(acc, grads, ids, n_micro: int):
    """``acc + g / n_micro`` per leaf, in float32; a tap's rows are added into
    rows ``ids`` of its accumulator (a repeated id once per slot, an id out
    of range dropped), so no dense gradient is formed."""
    taps = iter(ids)

    def add(a, g):
        if _is_tap(g):
            return a.at[next(taps)].add(
                g.rows.astype(jnp.float32) / n_micro, mode="drop")
        return a + g.astype(jnp.float32) / n_micro

    return jax.tree.map(add, acc, grads)


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

    loss_fn(params, micro_inputs) -> (loss, metrics), or (loss, (metrics,
    ids)) where ``params`` holds ``RowTap``s; a tap's gradient comes back
    dense, [n, D]. Gradients accumulate in fp32 (under the name
    ``grad_accum``). Metrics are averaged. This is the
    pipelined/accumulated step body: with n_micro=1 it degenerates to the
    paper's Fig. 4(a) baseline.
    """
    tapped = any(map(_is_tap, jax.tree.leaves(params, is_leaf=_is_tap)))
    if n_micro == 1 and not tapped:
        return _value_and_grad(loss_fn, params, inputs)

    micro = split_microbatches(inputs, n_micro)

    def body(carry, micro_inputs):
        acc_g, acc_l, acc_m = carry
        (loss, aux), grads = _value_and_grad(loss_fn, params, micro_inputs)
        metrics, ids = aux if tapped else (aux, ())
        with jax.named_scope("grad_accum"):
            acc_g = _accumulate(acc_g, grads, ids, n_micro)
            acc_m = jax.tree.map(lambda a, m: a + m / n_micro, acc_m,
                                 metrics)
        return (acc_g, acc_l + loss / n_micro, acc_m), None

    g0 = jax.tree.map(
        lambda p: jnp.zeros((p.n,) + p.rows.shape[1:] if _is_tap(p)
                            else p.shape, jnp.float32),
        params, is_leaf=_is_tap)
    first = jax.tree.map(lambda x: x[0], micro)
    aux = jax.eval_shape(lambda: loss_fn(params, first)[1])
    m0 = jax.tree.map(lambda _: jnp.zeros((), jnp.float32),
                      aux[0] if tapped else aux)
    (grads, loss, metrics), _ = jax.lax.scan(
        body, (g0, jnp.zeros((), jnp.float32), m0), micro)
    return (loss, metrics), grads
