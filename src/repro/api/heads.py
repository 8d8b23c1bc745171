"""Pluggable softmax-head strategies (the paper's §3.2/§4.1 comparison as an
API).

The KDD'20 paper's core claim is a *comparison* of softmax variants — full,
KNN softmax, selective softmax [Zhang et al., AAAI'18], MACH [Medini et al.,
NeurIPS'19], plus the sampled-softmax [Jean et al., ACL'15] and CSoft
count-min-sketch baselines — trained under identical hybrid-parallel
conditions. This module makes the head a first-class strategy so any head
composes with any trainer and any mesh:

  * ``SoftmaxHead`` — the protocol. A head owns its trainable params AND its
    auxiliary (non-trainable) state as pytrees, provides the
    ``PartitionSpec``s that place both on a mesh, a shard_map-compatible
    loss in two halves (a per-update ``prepare_params`` of the params and
    the ``loss_prepared`` body that takes its result; ``loss_local`` joins
    them), a distributed ``eval_logits_local`` prediction body,
    its metrics spec, and an optional ``refresh`` for periodic work (KNN
    graph rebuilds, LSH table rebuilds).
  * ``HEAD_REGISTRY`` / ``register_head`` / ``make_head`` — the registry
    keyed by ``HeadConfig.softmax_impl``; new heads plug in with
    ``@register_head`` and no trainer changes (see docs/heads.md for the
    authoring guide).

Trainers (``repro.train.hybrid`` faithfully, ``repro.train.gspmd`` for the
zoo) call heads only through this protocol — no ``use_knn`` booleans, no
head-specific branches.

Every head additionally honors ``HeadConfig.backend`` ("ref" | "pallas"):
the strategy threads the choice down into its distributed body, which runs
the softmax-stage hotspot either as plain XLA or through the fused Pallas
kernels (streaming CE for the dense heads, active-class sparse CE for the
selection heads) — see docs/kernels.md. Trainers stay untouched: the
backend is a head concern, selected per-config like the head itself.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import HeadConfig, ModelConfig, effective_vocab
from repro.core import baselines as bl
from repro.core import knn_graph as kg
from repro.core.knn_softmax import knn_softmax_local
from repro.core.sharded_softmax import (_normalize, full_softmax_local,
                                        serve_argmax_local,
                                        serve_logits_local)
from repro.telemetry import NULL_TRACER


class HeadState(NamedTuple):
    """A head's state: ``params`` are trained by the outer optimizer,
    ``aux`` is head-owned non-trainable state (graphs, hash tables, ...)."""
    params: Any
    aux: Any


class SoftmaxHead:
    """Base strategy. Subclasses are stateless objects bound to configs;
    all array state lives in the ``HeadState`` they create."""

    name = "?"
    # True when the head's trainable params ARE the [V, D] class-weight
    # matrix. The zoo (GSPMD) trainer then feeds ``lm.head_weight(params)``
    # (tied embedding or params["head"]) and trains it as part of the model;
    # sketch heads (mach / csoft) set False and the zoo threads
    # ``HeadState.params`` as an extra trainable pytree instead.
    params_are_class_weights = True

    def __init__(self, model_cfg: ModelConfig, head_cfg: HeadConfig):
        self.model_cfg = model_cfg
        self.head_cfg = head_cfg
        self.n_classes = model_cfg.vocab_size
        self.d = model_cfg.d_model
        # padded-vocab masking (Megatron-style): labels < n_valid always
        self.n_valid = (effective_vocab(model_cfg)
                        if model_cfg.real_vocab_size else 0)
        # compute backend for the hot bodies: "ref" (XLA) | "pallas" (fused
        # kernels); the VMEM blocking knobs ride along
        self.backend = head_cfg.backend
        self.block_v = head_cfg.pallas_block_v
        self.block_a = head_cfg.pallas_block_a

    # -- state ------------------------------------------------------------
    def init(self, key, n_dev: int) -> HeadState:
        raise NotImplementedError

    def init_aux(self, key, n_dev: int):
        """Aux-only init, for trainers that own the class weights elsewhere
        (the zoo's W-heads). Default falls back to a full ``init`` and
        discards the params; heads override to avoid the throwaway draw."""
        return self.init(key, n_dev).aux

    def params_spec(self, model_axis):
        """Pytree of PartitionSpecs matching ``state.params``."""
        raise NotImplementedError

    def aux_spec(self, model_axis):
        """Pytree of PartitionSpecs matching ``state.aux``."""
        return ()

    # -- shard_map bodies -------------------------------------------------
    def loss_local(self, f_all, y_all, params, aux, *, model_axis,
                   batch_axes, global_batch: int, step=None):
        """Distributed CE on one device's shard: ``loss_prepared`` on
        ``prepare_params(params)``. ``f_all``/``y_all`` are the
        ring-gathered (global) batch; ``step`` is the replicated training-
        step scalar (for heads with per-step randomness; may be None).
        Returns (loss, metrics). Heads implement the two halves, not this:
        the hybrid trainer calls them apart."""
        return self.loss_prepared(f_all, y_all, self.prepare_params(params),
                                  aux, model_axis=model_axis,
                                  batch_axes=batch_axes,
                                  global_batch=global_batch, step=step)

    def prepare_params(self, params):
        """Per-update transform of the trainable params on one device's
        shard: row-local, and fixed while the params are (a cosine head's
        row normalization of W). The hybrid trainer runs it once per
        update, outside the micro-batch loop, and hands the result to
        ``loss_prepared``. Identity by default."""
        return params

    def loss_prepared(self, f_all, y_all, prepared, aux, *, model_axis,
                      batch_axes, global_batch: int, step=None):
        """The loss body, on ``prepare_params(params)``."""
        raise NotImplementedError

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        if "loss_local" in vars(cls):
            raise TypeError(
                f"{cls.__name__} overrides loss_local; implement "
                "loss_prepared (and prepare_params) instead, which the "
                "hybrid trainer calls apart")

    def eval_logits_local(self, f_all, params, aux, *, model_axis):
        """Deploy-style prediction (§4.5 retrieval equivalence). Returns
        (pred [b] global class ids, local scores)."""
        raise NotImplementedError

    def metrics_spec(self) -> dict:
        return {"accuracy": P(), "logz": P()}

    # -- checkpoint contract ----------------------------------------------
    def state_to_save(self, state: HeadState):
        """Full-state snapshot pytree for the checkpoint layer: the head's
        trainable params AND its aux (KNN graph, LSH tables, CMS hashes /
        bucket weights). Aux is saved, not rebuilt, so a restore resumes
        mid-refresh-interval with the exact tables the killed run was
        using (docs/resilience.md)."""
        return {"params": state.params, "aux": state.aux}

    def state_from_restore(self, tree, mesh, *, model_axis) -> HeadState:
        """Re-place a restored ``state_to_save`` snapshot on ``mesh`` with
        the head's own PartitionSpecs. Shapes may differ from a fresh
        ``init`` (a refreshed KNN graph is denser than the warm-start
        self-graph); only the tree structure must match."""
        def put(subtree, spec):
            if not jax.tree.leaves(subtree):   # e.g. () params on the zoo
                return subtree
            return jax.tree.map(
                lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
                subtree, spec)
        params = put(tree["params"], self.params_spec(model_axis))
        aux = put(tree["aux"], self.aux_spec(model_axis))
        return HeadState(params=params, aux=aux)

    # -- elastic resharding (repro.elastic) -------------------------------
    def reshard_state(self, tree, src, dst):
        """Map a host-side ``state_to_save`` snapshot written on the
        ``src`` mesh geometry onto ``dst`` (both
        ``repro.elastic.MeshGeometry``). Dense [V, D] params are stored as
        GLOBAL rows and pass through; heads whose aux bakes in the ring
        size override with an exact re-pack. Returns
        ``(tree, needs_refresh)`` — the default for aux without a re-pack
        rule re-initializes it shape-correct for the dst ring and asks the
        trainer to run the head's own ``refresh`` path after placement."""
        if src.n_model == dst.n_model or not jax.tree.leaves(tree["aux"]):
            return tree, False
        return dict(tree, aux=self.init_aux(jax.random.PRNGKey(0),
                                            dst.n_model)), True

    def reshard_params_like(self, arr, src, dst):
        """Reshard one optimizer-moment leaf shaped like ``params``.
        Identity for heads whose params are global [V, D] rows; sketch
        heads apply their bucket transfer so moments track params."""
        return arr

    # -- periodic work ----------------------------------------------------
    @property
    def refresh_every(self) -> int:
        """Steps between ``refresh`` calls; 0 = no periodic work."""
        return 0

    def refresh(self, mesh, head_state: HeadState, *, model_axis,
                telemetry=None) -> HeadState:
        """Rebuild aux state from the current params (no-op by default).
        ``telemetry`` (a ``Tracer``) receives the rebuild's
        ``train.refresh.*`` spans."""
        return head_state

    # -- shared helpers ---------------------------------------------------
    def _init_w(self, key, dtype=jnp.float32):
        return (jax.random.normal(key, (self.n_classes, self.d))
                / jnp.sqrt(self.d)).astype(dtype)


HEAD_REGISTRY: dict = {}


def register_head(name: str):
    def deco(cls):
        cls.name = name
        HEAD_REGISTRY[name] = cls
        return cls
    return deco


def make_head(model_cfg: ModelConfig, head_cfg: HeadConfig) -> SoftmaxHead:
    try:
        cls = HEAD_REGISTRY[head_cfg.softmax_impl]
    except KeyError:
        raise ValueError(
            f"unknown softmax_impl {head_cfg.softmax_impl!r}; registered: "
            f"{sorted(HEAD_REGISTRY)}") from None
    return cls(model_cfg, head_cfg)


# ---------------------------------------------------------------------------
# full softmax (paper baseline)
# ---------------------------------------------------------------------------


@register_head("full")
class FullSoftmaxHead(SoftmaxHead):
    """W [V, D] row-sharded; exact distributed softmax (§3.1)."""

    def init(self, key, n_dev: int) -> HeadState:
        return HeadState(params=self._init_w(key), aux=())

    def init_aux(self, key, n_dev: int):
        return ()

    def params_spec(self, model_axis):
        return P(model_axis, None)

    def prepare_params(self, params):
        # a cosine head scores against unit rows of W; W is fixed within an
        # update, so its normalization (and that normalization's gradient)
        # runs once per update instead of once per micro-batch
        return (_normalize(params) if self.head_cfg.cosine_scale > 0
                else params)

    def loss_prepared(self, f_all, y_all, prepared, aux, *, model_axis,
                      batch_axes, global_batch, step=None):
        return full_softmax_local(
            f_all, y_all, prepared, model_axis=model_axis,
            batch_axes=batch_axes, global_batch=global_batch,
            cosine_scale=self.head_cfg.cosine_scale, n_valid=self.n_valid,
            backend=self.backend, block_v=self.block_v)

    def eval_logits_local(self, f_all, params, aux, *, model_axis):
        f = f_all.astype(jnp.float32)
        w = params.astype(jnp.float32)
        if self.head_cfg.cosine_scale > 0:
            # §4.5 retrieval equivalence holds for the normalized objective;
            # raw-trained heads (zoo LM full softmax) decode raw argmax
            f, w = _normalize(f), _normalize(w)
        if self.backend == "pallas":
            # streaming (max, argmax) stats — no [b, V_loc] logits in HBM
            return serve_argmax_local(f, w, model_axis=model_axis,
                                      n_valid=self.n_valid,
                                      block_v=self.block_v)
        return serve_logits_local(f, w, model_axis=model_axis,
                                  n_valid=self.n_valid)


# ---------------------------------------------------------------------------
# KNN softmax (the paper's contribution, §3.2)
# ---------------------------------------------------------------------------


@register_head("knn")
class KNNSoftmaxHead(FullSoftmaxHead):
    """Active classes from the compressed KNN graph of W; ``refresh``
    rebuilds the exact graph on the training devices (§3.2.2)."""

    def init(self, key, n_dev: int) -> HeadState:
        return HeadState(params=self._init_w(key),
                         aux=self.init_aux(key, n_dev))

    def init_aux(self, key, n_dev: int):
        # warm-start graph before the first refresh: self-only neighbor
        # lists (lossless by construction — every label selects itself);
        # needs no weights
        import numpy as np
        self_graph = np.arange(self.n_classes, dtype=np.int32)[:, None]
        cg = kg.compress_graph(self_graph, n_dev)
        return (cg.offsets, cg.neighbors, cg.ranks)

    def aux_spec(self, model_axis):
        return (P(model_axis, None),) * 3

    @property
    def refresh_every(self) -> int:
        return self.head_cfg.rebuild_every

    def refresh(self, mesh, head_state: HeadState, *, model_axis,
                telemetry=None) -> HeadState:
        """Paper §3.2.2: suspend training, ring-build the exact KNN graph of
        the CURRENT class weights, compress per shard (host round-trip for
        CSR packing — an offline step in the paper)."""
        import numpy as np
        tr = telemetry or NULL_TRACER
        n_dev = mesh.shape[model_axis]
        with tr.span("train.refresh.build"):
            graph = kg.build_graph_distributed(
                mesh, head_state.params, k=self.head_cfg.knn_k,
                kprime=self.head_cfg.knn_kprime, model_axis=model_axis,
                backend=self.backend)
        with tr.span("train.refresh.fetch"):
            graph = np.asarray(jax.device_get(graph))
        with tr.span("train.refresh.pack"):
            cg = kg.compress_graph(graph, n_dev)
        with tr.span("train.refresh.place"):
            sh = NamedSharding(mesh, P(model_axis, None))
            aux = tuple(jax.device_put(a, sh)
                        for a in (cg.offsets, cg.neighbors, cg.ranks))
        return HeadState(params=head_state.params, aux=aux)

    def prepare_params(self, params):
        # the pallas body scores against the whole normalized shard, once
        # per update; the ref body normalizes only the rows it gathers
        # (active_frac of the shard) and takes the raw shard
        return _normalize(params) if self.backend == "pallas" else params

    def loss_prepared(self, f_all, y_all, prepared, aux, *, model_axis,
                      batch_axes, global_batch, step=None):
        offsets, neighbors, ranks = aux
        v_loc = prepared.shape[0]
        m_local = max(8, int(v_loc * self.head_cfg.active_frac))
        return knn_softmax_local(
            f_all, y_all, prepared, offsets, neighbors, ranks,
            model_axis=model_axis, batch_axes=batch_axes,
            global_batch=global_batch, m_local=m_local,
            k_cap=self.head_cfg.knn_k,
            cosine_scale=self.head_cfg.cosine_scale,
            pad_random=self.head_cfg.knn_pad_random, n_valid=self.n_valid,
            backend=self.backend, block_a=self.block_a)

    def metrics_spec(self) -> dict:
        return {"accuracy": P(), "logz": P(), "active_frac": P(),
                "label_recall": P()}

    def reshard_state(self, tree, src, dst):
        """Exact CSR re-pack: the per-shard graph compression is
        invertible (``ranks`` keeps original columns), so the restored
        graph — mid-refresh staleness included — is preserved bit-for-bit
        and n->m->n round-trips to the identity."""
        if src.n_model == dst.n_model:
            return tree, False
        from repro.elastic.reshard import repack_knn_aux
        return dict(tree, aux=repack_knn_aux(tree["aux"],
                                             dst.n_model)), False


# ---------------------------------------------------------------------------
# selective softmax [Zhang et al., AAAI'18] — LSH active classes
# ---------------------------------------------------------------------------


@register_head("selective")
class SelectiveSoftmaxHead(FullSoftmaxHead):
    """W [V, D] row-sharded + per-shard LSH tables; ``refresh`` rebuilds the
    tables on the current weights (the baseline's table-refresh cadence)."""

    def _build_tables(self, key, w, n_dev: int):
        return bl.build_sharded_lsh_tables(
            key, w, n_dev, self.head_cfg.selective_n_hash,
            self.head_cfg.selective_n_bits)

    def init(self, key, n_dev: int) -> HeadState:
        kw, kt = jax.random.split(key)
        w = self._init_w(kw)
        planes, offsets, classes = self._build_tables(kt, w, n_dev)
        return HeadState(params=w, aux=(planes, offsets, classes))

    def init_aux(self, key, n_dev: int):
        # shape-correct tables without a throwaway [V, D] weight draw (all
        # classes land in bucket 0); ``refresh`` rebuilds from the real
        # class weights before any training step uses them
        return self._build_tables(
            key, jnp.zeros((self.n_classes, self.d), jnp.float32), n_dev)

    def aux_spec(self, model_axis):
        return (P(), P(model_axis, None, None), P(model_axis, None, None))

    @property
    def refresh_every(self) -> int:
        return self.head_cfg.rebuild_every

    def refresh(self, mesh, head_state: HeadState, *, model_axis,
                telemetry=None) -> HeadState:
        n_dev = mesh.shape[model_axis]
        w = jax.device_get(head_state.params)
        planes, offsets, classes = self._build_tables(
            jax.random.PRNGKey(41), jnp.asarray(w), n_dev)
        sh = NamedSharding(mesh, P(model_axis, None, None))
        aux = (jax.device_put(planes, NamedSharding(mesh, P())),
               jax.device_put(offsets, sh), jax.device_put(classes, sh))
        return HeadState(params=head_state.params, aux=aux)

    def prepare_params(self, params):
        # the LSH body normalizes W itself (its ref path only the gathered
        # rows), so it takes the raw shard
        return params

    def loss_prepared(self, f_all, y_all, params, aux, *, model_axis,
                      batch_axes, global_batch, step=None):
        planes, offsets, classes = aux
        v_loc = params.shape[0]
        m_local = max(8, int(v_loc * self.head_cfg.active_frac))
        return bl.selective_softmax_local(
            f_all, y_all, params, planes, offsets, classes,
            model_axis=model_axis, batch_axes=batch_axes,
            global_batch=global_batch, m_local=m_local,
            cap=self.head_cfg.selective_cap,
            cosine_scale=self.head_cfg.cosine_scale,
            backend=self.backend, block_a=self.block_a)

    def metrics_spec(self) -> dict:
        return {"accuracy": P(), "logz": P(), "active_frac": P(),
                "label_recall": P()}

    def reshard_state(self, tree, src, dst):
        """Exact table re-pack: bucket assignments are a function of the
        replicated planes and the global W rows (mesh-independent), so the
        per-shard CSRs invert to a class->bucket map and re-sort per dst
        shard with the builder's own stable-sort semantics — bitwise what
        ``build_sharded_lsh_tables`` would emit for the same assignment."""
        if src.n_model == dst.n_model:
            return tree, False
        from repro.elastic.reshard import repack_lsh_aux
        return dict(tree, aux=repack_lsh_aux(tree["aux"],
                                             dst.n_model)), False


# ---------------------------------------------------------------------------
# MACH [Medini et al., NeurIPS'19] — R hashed B-way softmaxes
# ---------------------------------------------------------------------------


@register_head("mach")
class MACHSoftmaxHead(SoftmaxHead):
    """R independent bucket heads [R, B, D] with the BUCKET axis sharded
    over the model axis; static class->bucket hash tables replicated."""

    params_are_class_weights = False
    _hash_seed = 0          # universal-hash family seed (csoft uses 1)

    def _n_buckets(self, n_dev: int) -> int:
        # bucket axis must divide the ring
        b = self.head_cfg.mach_b
        return -(-b // n_dev) * n_dev

    def init(self, key, n_dev: int) -> HeadState:
        head = bl.init_mach(key, self.n_classes, self.d,
                            n_buckets=self._n_buckets(n_dev),
                            n_rep=self.head_cfg.mach_r,
                            seed=self._hash_seed)
        return HeadState(params=head.w, aux=(head.hashes,))

    def params_spec(self, model_axis):
        return P(None, model_axis, None)

    def aux_spec(self, model_axis):
        return (P(),)

    def reshard_state(self, tree, src, dst):
        """Keep the stored bucket weights AND hash tables verbatim when
        the stored bucket count still divides the dst ring (the loss reads
        B from the shard shape) — bitwise decode-equivalence. Otherwise
        re-bucket: re-hash classes with the SAME universal family at the
        new modulus and transfer each new bucket the mean of its member
        classes' old bucket weights (the lossy case; docs/resilience.md)."""
        import numpy as np
        w = np.asarray(jax.device_get(tree["params"]))
        if w.shape[1] % dst.n_model == 0:
            return tree, False
        from repro.elastic.reshard import rebucket_sketch
        b_dst = self._n_buckets(dst.n_model)
        h_new = bl.mach_hashes(self.n_classes, b_dst, n_rep=w.shape[0],
                               seed=self._hash_seed)
        w_new = rebucket_sketch(w, tree["aux"][0], h_new, b_dst)
        return dict(tree, params=jnp.asarray(w_new),
                    aux=(jnp.asarray(h_new),)), False

    def reshard_params_like(self, arr, src, dst):
        import numpy as np
        a = np.asarray(jax.device_get(arr))
        if a.ndim != 3 or a.shape[1] % dst.n_model == 0:
            return arr
        from repro.elastic.reshard import rebucket_sketch
        b_dst = self._n_buckets(dst.n_model)
        # both tables recompute deterministically from the family seed, so
        # moments get the identical transfer the params got
        h_old = bl.mach_hashes(self.n_classes, a.shape[1],
                               n_rep=a.shape[0], seed=self._hash_seed)
        h_new = bl.mach_hashes(self.n_classes, b_dst, n_rep=a.shape[0],
                               seed=self._hash_seed)
        return jnp.asarray(rebucket_sketch(a, h_old, h_new, b_dst))

    def loss_prepared(self, f_all, y_all, params, aux, *, model_axis,
                      batch_axes, global_batch, step=None):
        (hashes,) = aux
        return bl.mach_softmax_local(
            f_all, y_all, params, hashes, model_axis=model_axis,
            batch_axes=batch_axes, global_batch=global_batch,
            backend=self.backend, block_v=self.block_v)

    def eval_logits_local(self, f_all, params, aux, *, model_axis):
        (hashes,) = aux
        pred = bl.mach_predict_local(f_all, params, hashes,
                                     model_axis=model_axis)
        return pred, None


# ---------------------------------------------------------------------------
# sampled softmax [Jean et al., ACL'15] — logQ-corrected negative sampling
# ---------------------------------------------------------------------------


@register_head("sampled")
class SampledSoftmaxHead(FullSoftmaxHead):
    """W [V, D] row-sharded; CE over the true label plus a drawn negative
    set with the standard logQ correction.

    ``sampled_dist="uniform"`` draws stratified per-shard negatives without
    replacement — at ``sampled_n >= V`` the loss equals the full softmax
    exactly, and shrinking ``sampled_n`` trades accuracy for compute.
    ``"log_uniform"`` is the classic Zipfian LM sampler (with replacement,
    identical draw on every class shard). Negatives are re-drawn every step
    from (``sampled_seed``, the trainer-threaded ``step``, the batch's
    labels); there is no aux state and no periodic work.

    The train-time ``accuracy`` metric is relative to the candidate set
    (label + drawn negatives), like knn's active-set accuracy — use the
    deploy-style eval for full-vocabulary top-1."""

    def prepare_params(self, params):
        # the sampled body normalizes W itself, so it takes the raw shard
        return params

    def loss_prepared(self, f_all, y_all, params, aux, *, model_axis,
                      batch_axes, global_batch, step=None):
        return bl.sampled_softmax_local(
            f_all, y_all, params, model_axis=model_axis,
            batch_axes=batch_axes, global_batch=global_batch,
            n_samples=self.head_cfg.sampled_n,
            distribution=self.head_cfg.sampled_dist,
            seed=self.head_cfg.sampled_seed,
            cosine_scale=self.head_cfg.cosine_scale, n_valid=self.n_valid,
            step=step, backend=self.backend, block_a=self.block_a)

    def metrics_spec(self) -> dict:
        return {"accuracy": P(), "logz": P(), "sample_frac": P()}


# ---------------------------------------------------------------------------
# CSoft — count-min sketch over class ids (MACH lineage, min-decode)
# ---------------------------------------------------------------------------


@register_head("csoft")
class CSoftSketchHead(MACHSoftmaxHead):
    """Count-min sketch over class ids: R pairwise-independent hash rows of
    B buckets, [R, B, D] with the BUCKET axis sharded over the model axis.

    Training is the sketch's R small softmaxes (exactly MACH's loss,
    inherited) — the two heads differ in their hash family seed and in
    DECODING: csoft takes the min of the row log-probabilities, the
    count-min bound, instead of MACH's mean of probabilities;
    ``csoft_agg="mean"`` selects the geometric-mean variant."""

    _hash_seed = 1

    def _n_buckets(self, n_dev: int) -> int:
        # bucket axis must divide the ring
        b = self.head_cfg.csoft_b
        return -(-b // n_dev) * n_dev

    def init(self, key, n_dev: int) -> HeadState:
        head = bl.init_mach(key, self.n_classes, self.d,
                            n_buckets=self._n_buckets(n_dev),
                            n_rep=self.head_cfg.csoft_r,
                            seed=self._hash_seed)
        return HeadState(params=head.w, aux=(head.hashes,))

    def eval_logits_local(self, f_all, params, aux, *, model_axis):
        (hashes,) = aux
        pred = bl.csoft_predict_local(f_all, params, hashes,
                                      model_axis=model_axis,
                                      agg=self.head_cfg.csoft_agg)
        return pred, None
