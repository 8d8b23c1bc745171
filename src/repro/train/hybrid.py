"""The paper's hybrid-parallel trainer (faithful reproduction, §3.1-§3.4).

Layout = the paper's exactly, generalized to a 1-D device ring ("hybrid"
axis over all chips): every device is BOTH a data-parallel FE replica (FE
params replicated; batch sharded over the ring) AND a model-parallel fc
shard (head params sharded over the ring). Per (micro-)batch:

  FE local forward -> all-gather features along the ring -> each device
  scores the whole (micro-)batch against its head shard -> distributed
  softmax (pmax/psum) -> backward; head grads STAY LOCAL; FE grads cross the
  ring once per step — dense psum or DGC top-k sparsified (§3.3.2).

Micro-batching (§3.3.1) runs as a lax.scan whose per-iteration all-gather the
XLA latency-hiding scheduler overlaps with the next iteration's FE compute;
it is also FCCS's gradient-accumulation mechanism (n× batch growth).

The softmax head is a pluggable ``repro.api.SoftmaxHead`` strategy (full /
knn / selective / mach / ...): the head owns its trainable params, its aux
state (graphs, hash tables), the PartitionSpecs that place both on the ring,
and its shard_map loss body. The step builders below are head-agnostic —
no ``use_knn`` booleans, no head-specific branches — and that includes the
compute backend: ``HeadConfig.backend="pallas"`` swaps the head bodies onto
the fused kernels (docs/kernels.md) with zero trainer changes.

Everything is a single shard_map over the full mesh — all collectives
explicit, nothing left to GSPMD — so the HLO *is* the paper's Fig. 2/4.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.api.heads import HeadState, SoftmaxHead, make_head
from repro.configs.base import HeadConfig, ModelConfig, TrainConfig
from repro.core import sparsify as sp
from repro.core.pipeline import (RowTap, microbatched_value_and_grad,
                                 prepare_once)
from repro.models import lm
from repro.optim import apply_updates, make_optimizer

AXIS = "hybrid"


def make_hybrid_mesh(n_dev: Optional[int] = None):
    """The 1-D ring over the first ``n_dev`` devices (default: all)."""
    n = n_dev or len(jax.devices())
    return jax.make_mesh((n,), (AXIS,),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=jax.devices()[:n])


class HybridState(NamedTuple):
    fe_params: dict        # replicated
    head_params: Any       # head-owned trainable pytree, sharded by the head
    head_aux: Any          # head-owned non-trainable pytree (graph/tables)
    opt_state: object
    dgc: Optional[sp.DGCState]   # leaves carry leading [n_dev] axis
    step: jax.Array

    @property
    def w_head(self):
        """The [V, D] class-weight matrix, for heads whose params are one
        array (full/knn/selective/sampled). Deploy/eval code reads this."""
        return self.head_params


def init_state(key, model_cfg: ModelConfig, head_cfg: HeadConfig,
               train_cfg: TrainConfig, n_dev: int, *,
               head: Optional[SoftmaxHead] = None) -> HybridState:
    head = head or make_head(model_cfg, head_cfg)
    k1, k2 = jax.random.split(key)
    fe_params = lm.init_model(k1, model_cfg)
    fe_params.pop("head", None)   # the fc lives separately, sharded
    hs = head.init(k2, n_dev)
    opt = make_optimizer(train_cfg)
    opt_state = opt.init((fe_params, hs.params))
    dgc = None
    if train_cfg.dgc.enabled:
        z = sp.init_dgc_state(fe_params)
        dgc = sp.DGCState(
            u=jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n_dev,) + a.shape), z.u),
            v=jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n_dev,) + a.shape), z.v),
        )
    return HybridState(fe_params, hs.params, hs.aux, opt_state, dgc,
                       jnp.zeros((), jnp.int32))


def refresh_head_state(head: SoftmaxHead, mesh, state: HybridState, *,
                       telemetry=None) -> HybridState:
    """Run the head's periodic work (graph/table rebuild) on the current
    params; no-op for heads without any. ``telemetry`` (a ``Tracer``)
    receives the head's ``train.refresh.*`` spans."""
    hs = head.refresh(mesh, HeadState(state.head_params, state.head_aux),
                      model_axis=AXIS, telemetry=telemetry)
    return state._replace(head_params=hs.params, head_aux=hs.aux)


def state_specs(state: HybridState, head: SoftmaxHead):
    fe_spec = jax.tree.map(lambda _: P(), state.fe_params)
    hp_spec = head.params_spec(AXIS)
    opt_spec = jax.tree.map(lambda _: P(), state.opt_state)
    # opt moments mirror the (fe, head_params) tuple: redo specs for mu/nu
    def moment_spec(tree):
        if tree is None:
            return None
        fe_m = jax.tree.map(lambda _: P(), tree[0])
        return (fe_m, hp_spec)
    opt_spec = type(state.opt_state)(
        step=P(), mu=moment_spec(state.opt_state.mu),
        nu=moment_spec(getattr(state.opt_state, "nu", None)))
    dgc_spec = None
    if state.dgc is not None:
        dgc_spec = sp.DGCState(
            u=jax.tree.map(lambda _: P(AXIS), state.dgc.u),
            v=jax.tree.map(lambda _: P(AXIS), state.dgc.v))
    return HybridState(fe_spec, hp_spec, head.aux_spec(AXIS), opt_spec,
                       dgc_spec, P())


def place_state(state: HybridState, head: SoftmaxHead, mesh) -> HybridState:
    """Put every leaf of ``state`` on ``mesh`` with its ring spec, as the
    train step returns it, so the first step and the later ones share one
    compiled program."""
    from jax.sharding import NamedSharding

    return jax.tree.map(lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
                        state, state_specs(state, head))


def _flat_features_and_labels(model_cfg, fe_params, micro_inputs):
    """Local FE forward -> flat [t_loc, D] features + [t_loc] labels."""
    if model_cfg.family == "feats":
        return (micro_inputs["features"].astype(jnp.dtype(model_cfg.dtype)),
                micro_inputs["labels"], jnp.zeros((), jnp.float32))
    h, aux, _ = lm.backbone(fe_params, model_cfg, micro_inputs)
    d = h.shape[-1]
    f = h.reshape(-1, d)
    labels = micro_inputs["labels"].reshape(-1)
    return f, labels, aux


def _flat_features(model_cfg, fe_params, micro_inputs):
    """Label-free FE forward (serving): flat [t_loc, D] features."""
    if model_cfg.family == "feats":
        return micro_inputs["features"].astype(jnp.dtype(model_cfg.dtype))
    h, _, _ = lm.backbone(fe_params, model_cfg, micro_inputs)
    return h.reshape(-1, h.shape[-1])


def make_train_step(model_cfg: ModelConfig, head_cfg: HeadConfig,
                    train_cfg: TrainConfig, mesh, *, n_micro: int = 1,
                    head: Optional[SoftmaxHead] = None,
                    state_template: HybridState = None):
    """Returns jitted step(state, inputs, lr) -> (state, loss, metrics).

    inputs are GLOBAL arrays batch-sharded over the ring; the head's aux
    state (graph/tables) travels inside ``state`` with head-provided specs.
    """
    head = head or make_head(model_cfg, head_cfg)
    n_dev = mesh.shape[AXIS]
    opt = make_optimizer(train_cfg)
    dcfg = train_cfg.dgc

    def body(fe_params, head_params, head_aux, opt_state, dgc_u, dgc_v,
             inputs_loc, lr, step_no):
        # the head's per-update transform runs on the local shard, once,
        # outside the micro-batch loop (row-local: no collective)
        hp_prepared, pull_back = prepare_once(head.prepare_params,
                                              head_params)

        def loss_fn(params, micro_inputs):
            fe_p, hp = params
            f, y, aux = _flat_features_and_labels(model_cfg, fe_p, micro_inputs)
            # hybrid parallel: gather every replica's features along the ring
            f_all = jax.lax.all_gather(f, AXIS, axis=0, tiled=True)
            y_all = jax.lax.all_gather(y, AXIS, axis=0, tiled=True)
            kw = dict(model_axis=AXIS, batch_axes=(),
                      global_batch=f_all.shape[0], step=step_no)
            if isinstance(hp, RowTap):
                loss, metrics, ids = head.loss_rows(
                    f_all, y_all, hp_prepared, hp.rows, head_aux, **kw)
                return loss + aux, (metrics, (ids,))
            loss, metrics = head.loss_prepared(f_all, y_all, hp, head_aux,
                                               **kw)
            return loss + aux, metrics

        # a head whose class-matrix gradient is a set of rows is
        # differentiated against those rows, which the loop adds into its
        # accumulator; any other against the prepared shard
        micro_rows = inputs_loc["labels"].size // n_micro * n_dev
        grad_rows = head.grad_rows(hp_prepared.shape[0], micro_rows)
        if grad_rows:
            hp = RowTap(jnp.zeros((grad_rows,) + hp_prepared.shape[1:],
                                  hp_prepared.dtype), hp_prepared.shape[0])
        else:
            hp = hp_prepared
        (loss, metrics), (g_fe, g_prepared) = microbatched_value_and_grad(
            loss_fn, (fe_params, hp), inputs_loc, n_micro)
        g_hp = pull_back(g_prepared)

        info = {"wire_bytes": jnp.zeros((), jnp.float32),
                "dense_bytes": jnp.zeros((), jnp.float32)}
        new_u, new_v = dgc_u, dgc_v
        if dcfg.enabled:
            st = sp.DGCState(
                u=jax.tree.map(lambda a: a[0], dgc_u),
                v=jax.tree.map(lambda a: a[0], dgc_v))
            g_fe, st, dinfo = sp.dgc_exchange(
                g_fe, st, dcfg, batch_axes=(AXIS,), n_workers=n_dev)
            info.update(dinfo)
            new_u = jax.tree.map(lambda a: a[None], st.u)
            new_v = jax.tree.map(lambda a: a[None], st.v)
        else:
            g_fe = sp.dense_exchange(g_fe, batch_axes=(AXIS,), n_workers=n_dev)
            info["dense_bytes"] = jnp.asarray(
                sum(leaf.size * 4 for leaf in jax.tree.leaves(g_fe)),
                jnp.float32)
        # head gradient: LOCAL — never crosses devices (paper §3.1 step 6)

        with jax.named_scope("opt_update"):
            updates, opt_state = opt.update((g_fe, g_hp), opt_state,
                                            (fe_params, head_params), lr)
            fe_params, head_params = apply_updates((fe_params, head_params),
                                                   updates)
        metrics = dict(metrics)
        metrics["comm_wire_bytes"] = info.get("wire_bytes", jnp.zeros((), jnp.float32))
        metrics["comm_dense_bytes"] = info["dense_bytes"]
        return fe_params, head_params, opt_state, new_u, new_v, loss, metrics

    tmpl = state_template
    specs = state_specs(tmpl, head)
    dgc_u_spec = specs.dgc.u if specs.dgc is not None else None
    dgc_v_spec = specs.dgc.v if specs.dgc is not None else None
    if tmpl.dgc is None:
        # pass small dummies with replicated spec
        dgc_u_spec = jax.tree.map(lambda _: P(), tmpl.fe_params)
        dgc_v_spec = dgc_u_spec
    metrics_spec = dict(head.metrics_spec())
    metrics_spec["comm_wire_bytes"] = P()
    metrics_spec["comm_dense_bytes"] = P()
    input_spec = jax.tree.map(lambda _: P(AXIS), _input_structure(model_cfg))

    shmapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(specs.fe_params, specs.head_params, specs.head_aux,
                  specs.opt_state, dgc_u_spec, dgc_v_spec, input_spec, P(),
                  P()),
        out_specs=(specs.fe_params, specs.head_params, specs.opt_state,
                   dgc_u_spec, dgc_v_spec, P(), metrics_spec),
        check_vma=False,
    )

    @jax.jit
    def step(state: HybridState, inputs, lr):
        dgc_u = state.dgc.u if state.dgc is not None else state.fe_params
        dgc_v = state.dgc.v if state.dgc is not None else state.fe_params
        fe, hp, opt_state, nu_, nv_, loss, metrics = shmapped(
            state.fe_params, state.head_params, state.head_aux,
            state.opt_state, dgc_u, dgc_v, inputs, lr, state.step)
        dgc = sp.DGCState(u=nu_, v=nv_) if state.dgc is not None else None
        return (HybridState(fe, hp, state.head_aux, opt_state, dgc,
                            state.step + 1),
                loss, metrics)

    return step


def head_prepare_passes(head: SoftmaxHead, head_params) -> int:
    """Whole-shard passes of the head's per-update transform that one train
    step makes: 1 where ``prepare_params`` does work, 0 for the identity."""
    return int(bool(jax.make_jaxpr(head.prepare_params)(head_params).eqns))


def _input_structure(model_cfg: ModelConfig):
    if model_cfg.family == "feats":
        return {"features": 0, "labels": 0}
    if model_cfg.family == "cnn":
        return {"images": 0, "labels": 0}
    if model_cfg.family == "encdec":
        return {"frames": 0, "tokens": 0, "labels": 0}
    return {"tokens": 0, "labels": 0}


# ---------------------------------------------------------------------------
# evaluation / serving
# ---------------------------------------------------------------------------


def _make_deploy_fn(model_cfg, mesh, state_template, head, body, structure):
    """Shared shard_map wiring for the deploy-style eval/serve steps."""
    specs = state_specs(state_template, head)
    input_spec = jax.tree.map(lambda _: P(AXIS), structure)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(specs.fe_params, specs.head_params,
                                 specs.head_aux, input_spec),
                       out_specs=P(), check_vma=False)
    keys = tuple(structure)
    return jax.jit(lambda state, inputs: fn(
        state.fe_params, state.head_params, state.head_aux,
        {k: inputs[k] for k in keys}))


def make_eval_step(model_cfg: ModelConfig, head_cfg: HeadConfig, mesh,
                   state_template: HybridState, *,
                   head: Optional[SoftmaxHead] = None):
    """Distributed top-1 accuracy with the head's own deploy-style
    prediction (nearest class weight for W-heads — paper §4.5 retrieval
    equivalence; hashed-bucket vote for MACH)."""
    head = head or make_head(model_cfg, head_cfg)

    def body(fe_params, head_params, head_aux, inputs_loc):
        f, y, _ = _flat_features_and_labels(model_cfg, fe_params, inputs_loc)
        f_all = jax.lax.all_gather(f, AXIS, axis=0, tiled=True)
        y_all = jax.lax.all_gather(y, AXIS, axis=0, tiled=True)
        pred, _ = head.eval_logits_local(f_all, head_params, head_aux,
                                         model_axis=AXIS)
        return jnp.mean((pred == y_all).astype(jnp.float32))

    return _make_deploy_fn(model_cfg, mesh, state_template, head, body,
                           _input_structure(model_cfg))


def make_serve_step(model_cfg: ModelConfig, head_cfg: HeadConfig, mesh,
                    state_template: HybridState, *,
                    head: Optional[SoftmaxHead] = None):
    """Deploy-style retrieval (§4.5): (state, inputs) -> [b] predicted
    global class ids. Inputs need no "labels" key (any present is ignored);
    pure-inference batches serve directly."""
    head = head or make_head(model_cfg, head_cfg)

    def body(fe_params, head_params, head_aux, inputs_loc):
        f = _flat_features(model_cfg, fe_params, inputs_loc)
        f_all = jax.lax.all_gather(f, AXIS, axis=0, tiled=True)
        pred, _ = head.eval_logits_local(f_all, head_params, head_aux,
                                         model_axis=AXIS)
        return pred.astype(jnp.int32)

    structure = {k: v for k, v in _input_structure(model_cfg).items()
                 if k != "labels"}
    return _make_deploy_fn(model_cfg, mesh, state_template, head, body,
                           structure)


def _serve_query_key(model_cfg: ModelConfig) -> str:
    """The input key a serving-tier query fills (no labels at serve time)."""
    keys = [k for k in _input_structure(model_cfg) if k != "labels"]
    if len(keys) != 1:
        raise NotImplementedError(
            f"serving-tier queries need a single-input trunk; "
            f"{model_cfg.family!r} has inputs {keys}")
    return keys[0]


def _make_batched_deploy_fn(model_cfg, mesh, state_template, head, body,
                            donate: bool):
    """shard_map wiring for the serving tier's batched steps: queries are
    REPLICATED (every shard scores the full padded micro-batch — no ring
    all-gather on the serve path, and no batch-divisibility constraint),
    ``n_queries`` is a traced scalar (one compile per padding bucket, not
    per occupancy), and the padded query buffer is donated when the caller
    is done with it (``donate=True``, the serving engine's default)."""
    specs = state_specs(state_template, head)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(specs.fe_params, specs.head_params,
                                 specs.head_aux, P(), P()),
                       out_specs=P(), check_vma=False)

    def step(state, queries, n_queries):
        return fn(state.fe_params, state.head_params, state.head_aux,
                  queries, n_queries)

    return jax.jit(step, donate_argnums=(1,)) if donate else jax.jit(step)


def make_batched_serve_step(model_cfg: ModelConfig, head_cfg: HeadConfig,
                            mesh, state_template: HybridState, *,
                            head: Optional[SoftmaxHead] = None,
                            donate: bool = True):
    """Serving-tier greedy retrieval over a padded micro-batch.

    (state, queries [b_pad, ...], n_queries []) -> pred [b_pad] int32 with
    padding rows forced to -1. Works for EVERY registry head (the body is
    the head's own ``eval_logits_local`` — hashed-bucket decode included),
    and rows are scored independently, so results for real rows are
    bitwise-identical across padding buckets >= 2 (tests/test_serving.py).
    """
    from repro.core.sharded_softmax import mask_padded_rows

    head = head or make_head(model_cfg, head_cfg)
    key = _serve_query_key(model_cfg)

    def body(fe_params, head_params, head_aux, queries, n_queries):
        f = _flat_features(model_cfg, fe_params, {key: queries})
        pred, _ = head.eval_logits_local(f, head_params, head_aux,
                                         model_axis=AXIS)
        return mask_padded_rows(pred.astype(jnp.int32), n_queries, -1)

    return _make_batched_deploy_fn(model_cfg, mesh, state_template, head,
                                   body, donate)


def make_batched_topk_serve_step(model_cfg: ModelConfig,
                                 head_cfg: HeadConfig, mesh,
                                 state_template: HybridState, top_k: int, *,
                                 head: Optional[SoftmaxHead] = None,
                                 donate: bool = True):
    """Serving-tier top-k retrieval over a padded micro-batch.

    (state, queries [b_pad, ...], n_queries []) -> (vals [b_pad, k] desc,
    gids [b_pad, k]) with padding rows forced to (-inf, -1). W-heads only
    (same contract as ``make_topk_serve_step``); the multi-query body is
    ``core.sharded_softmax.serve_topk_batched_local``."""
    from repro.core.sharded_softmax import (_normalize,
                                            serve_topk_batched_local)

    head = head or make_head(model_cfg, head_cfg)
    if not head.params_are_class_weights:
        raise NotImplementedError(
            f"top-k serving retrieves against the [V, D] class matrix, "
            f"which the {head.name!r} head does not train; use a W-head "
            f"(full/knn/selective/sampled)")
    key = _serve_query_key(model_cfg)

    def body(fe_params, head_params, head_aux, queries, n_queries):
        f = _flat_features(model_cfg, fe_params, {key: queries})
        f = f.astype(jnp.float32)
        w = head_params.astype(jnp.float32)
        if head_cfg.cosine_scale > 0:
            f, w = _normalize(f), _normalize(w)
        return serve_topk_batched_local(
            f, w, top_k, n_queries, model_axis=AXIS, n_valid=head.n_valid,
            backend=head.backend)

    return _make_batched_deploy_fn(model_cfg, mesh, state_template, head,
                                   body, donate)


def make_batched_ivf_topk_serve_step(model_cfg: ModelConfig,
                                     head_cfg: HeadConfig, mesh,
                                     state_template: HybridState,
                                     top_k: int, *, nprobe: int,
                                     head: Optional[SoftmaxHead] = None,
                                     donate: bool = True):
    """Sublinear serving-tier top-k through an ``IVFIndex``.

    (state, centroids [P, C, D], members [P, C, cap], queries [b_pad, ...],
    n_queries []) -> (vals [b_pad, k] desc, gids [b_pad, k]), padding rows
    forced to (-inf, -1). Same contract and shard_map wiring as
    ``make_batched_topk_serve_step``, but each shard probes its own
    ``nprobe`` centroids and reranks only their member rows
    (``serve_topk_ivf_batched_local``; pallas backend = the fused
    ``ops.ivf_rerank`` kernel) instead of scanning the whole [V/n, D]
    shard. W-heads only — the index quantizes the trained class matrix."""
    from repro.core.sharded_softmax import (_normalize,
                                            serve_topk_ivf_batched_local)

    head = head or make_head(model_cfg, head_cfg)
    if not head.params_are_class_weights:
        raise NotImplementedError(
            f"top-k serving retrieves against the [V, D] class matrix, "
            f"which the {head.name!r} head does not train; use a W-head "
            f"(full/knn/selective/sampled)")
    key = _serve_query_key(model_cfg)
    specs = state_specs(state_template, head)

    def body(fe_params, head_params, head_aux, cent, members, queries,
             n_queries):
        f = _flat_features(model_cfg, fe_params, {key: queries})
        f = f.astype(jnp.float32)
        w = head_params.astype(jnp.float32)
        if head_cfg.cosine_scale > 0:
            f, w = _normalize(f), _normalize(w)
        return serve_topk_ivf_batched_local(
            f, w, cent[0], members[0], top_k, nprobe, n_queries,
            model_axis=AXIS, backend=head.backend,
            block_a=head.block_a)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(specs.fe_params, specs.head_params,
                                 specs.head_aux, P(AXIS, None, None),
                                 P(AXIS, None, None), P(), P()),
                       out_specs=P(), check_vma=False)

    def step(state, centroids, members, queries, n_queries):
        return fn(state.fe_params, state.head_params, state.head_aux,
                  centroids, members, queries, n_queries)

    return jax.jit(step, donate_argnums=(3,)) if donate else jax.jit(step)


def make_topk_serve_step(model_cfg: ModelConfig, head_cfg: HeadConfig, mesh,
                         state_template: HybridState, top_k: int, *,
                         head: Optional[SoftmaxHead] = None):
    """Top-k retrieval with scores (ROADMAP "serving beyond greedy argmax"):
    (state, inputs) -> (scores [b, k] desc, global class ids [b, k]).

    W-heads only (the [V, D] retrieval index IS the trained head); each
    shard's local top-k is selected by ``lax.top_k`` (ref backend) or the
    row-wise divide-and-conquer selector ``kernels.ops.topk_rows`` (pallas
    stage-1 kernel), then merged with one all-gather along the ring."""
    from repro.core.sharded_softmax import _normalize, serve_topk_local

    head = head or make_head(model_cfg, head_cfg)
    if not head.params_are_class_weights:
        raise NotImplementedError(
            f"top-k serving retrieves against the [V, D] class matrix, "
            f"which the {head.name!r} head does not train; use a W-head "
            f"(full/knn/selective/sampled)")

    def body(fe_params, head_params, head_aux, inputs_loc):
        f = _flat_features(model_cfg, fe_params, inputs_loc)
        f_all = jax.lax.all_gather(f, AXIS, axis=0, tiled=True)
        f_all = f_all.astype(jnp.float32)
        w = head_params.astype(jnp.float32)
        if head_cfg.cosine_scale > 0:
            f_all, w = _normalize(f_all), _normalize(w)
        return serve_topk_local(
            f_all, w, top_k, model_axis=AXIS, n_valid=head.n_valid,
            backend=head.backend)

    structure = {k: v for k, v in _input_structure(model_cfg).items()
                 if k != "labels"}
    return _make_deploy_fn(model_cfg, mesh, state_template, head, body,
                           structure)
