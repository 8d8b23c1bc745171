"""FCCS-driven training loop for the paper system (hybrid trainer).

Orchestrates: warm-up LR, continuous batch growth via gradient accumulation
(quantized to powers of two so at most log2(64) step variants compile), the
head's periodic refresh (KNN graph rebuild / LSH table rebuild — training
"suspended", as the paper does at epoch boundaries), periodic checkpoints
and eval.

The softmax head is whatever ``head_cfg.softmax_impl`` names in the
``repro.api`` registry; the trainer never branches on the head kind — it
only honors the head's ``refresh_every`` cadence.

Checkpoints are FULL-state snapshots (docs/resilience.md): FE params, head
params AND head aux (KNN graph / LSH tables / sketch hashes), optimizer
moments, DGC error-feedback buffers, and the data cursor / step counter —
everything a killed run needs for ``restore_checkpoint`` to continue
step-for-step equivalent to an uninterrupted run. The FCCS schedule and
the synthetic data stream are pure functions of the cursor, so saving the
cursor IS saving the schedule state. ``run`` resumes from the cursor, and
``step_hook`` is the fault-injection seam (``repro.resilience``).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro import checkpoint as ckpt_lib
from repro.api.heads import HeadState, make_head
from repro.configs.base import HeadConfig, ModelConfig, TrainConfig
from repro.core import fccs
from repro.core import sparsify as sp
from repro.telemetry import NULL_TRACER
from repro.train import hybrid


def _pow2_quantize(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


@dataclass
class PaperTrainer:
    model_cfg: ModelConfig
    head_cfg: HeadConfig
    train_cfg: TrainConfig
    mesh: object
    data_fn: Callable[[int, int], dict]     # (step, global_batch) -> inputs
    hw_batch: int                           # per-update device-limited batch
    use_knn: bool = False                   # deprecated alias for
                                            # head_cfg.softmax_impl="knn"
    lr_fn: Optional[Callable[[int], float]] = None  # default: FCCS policy
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 0
    ckpt_keep: int = 0                      # 0 = retain every checkpoint
    log_every: int = 10
    seed: int = 0
    history: list = field(default_factory=list)
    telemetry: object = None                # Tracer, or None = NULL_TRACER

    def __post_init__(self):
        if self.use_knn and self.head_cfg.softmax_impl == "full":
            import dataclasses
            self.head_cfg = dataclasses.replace(self.head_cfg,
                                                softmax_impl="knn")
        n_dev = self.mesh.shape[hybrid.AXIS]
        self.n_dev = n_dev
        self.head = make_head(self.model_cfg, self.head_cfg)
        self.state = hybrid.place_state(hybrid.init_state(
            jax.random.PRNGKey(self.seed), self.model_cfg, self.head_cfg,
            self.train_cfg, n_dev, head=self.head), self.head, self.mesh)
        self._steps = {}
        self._t = 0          # data cursor: next step index run() will take
        self.restores = 0    # bumped on every restore (serving-cache probe)
        self.last_reshard = None   # stats dict of the last elastic restore
        # initial refresh: heads with derived aux state (KNN graph, LSH
        # tables) build it from the freshly-initialized weights; a no-op
        # for heads without periodic work.
        self.refresh_head()
        self.head_prepare_passes = hybrid.head_prepare_passes(
            self.head, self.state.head_params)
        self.eval_step = hybrid.make_eval_step(
            self.model_cfg, self.head_cfg, self.mesh, self.state,
            head=self.head)

    def _get_step(self, n_micro: int):
        if n_micro not in self._steps:
            self._steps[n_micro] = hybrid.make_train_step(
                self.model_cfg, self.head_cfg, self.train_cfg, self.mesh,
                n_micro=n_micro, head=self.head, state_template=self.state)
        return self._steps[n_micro]

    def refresh_head(self):
        """Paper §3.2.2: suspend training, rebuild the head's aux state on
        the training devices, resume. Returns the wall-clock spent."""
        tr = self.telemetry or NULL_TRACER
        t0 = time.perf_counter()
        with tr.span("train.refresh"):
            self.state = hybrid.refresh_head_state(self.head, self.mesh,
                                                   self.state, telemetry=tr)
        tr.count("train.refreshes")
        return time.perf_counter() - t0

    # back-compat name (pre-registry API)
    rebuild_graph = refresh_head

    # -- full-state checkpoint / restore ----------------------------------

    def _snapshot(self):
        """The checkpoint pytree: EVERYTHING the step function consumes,
        plus the cursor the outer loop consumes. Same structure every
        save, so any snapshot restores into any fresh trainer of the same
        config (leaf shapes may differ — the checkpoint stores them)."""
        st = self.state
        tree = {
            "fe": st.fe_params,
            "head": self.head.state_to_save(
                HeadState(st.head_params, st.head_aux)),
            "opt": st.opt_state,
            "extra": {"t": jnp.asarray(self._t, jnp.int32),
                      "step": jnp.asarray(st.step, jnp.int32),
                      "seed": jnp.asarray(self.seed, jnp.int32)},
        }
        if st.dgc is not None:
            tree["dgc"] = {"u": st.dgc.u, "v": st.dgc.v}
        return tree

    def geometry(self):
        """This trainer's ``repro.elastic.MeshGeometry`` (the hybrid ring
        is both the model and the data axis)."""
        from repro.elastic import MeshGeometry
        return MeshGeometry(n_model=self.n_dev, n_data=self.n_dev,
                            n_classes=self.model_cfg.vocab_size)

    def save_checkpoint(self) -> str:
        """Atomic full-state snapshot at the current cursor. The mesh
        geometry rides along as checkpoint meta so a restore on a
        different ring is caught up front (or resharded — repro.elastic)."""
        assert self.ckpt_dir, "trainer has no ckpt_dir"
        meta = {"system": "paper", **self.geometry().meta()}
        return ckpt_lib.save(self.ckpt_dir, self._snapshot(), step=self._t,
                             keep=self.ckpt_keep or None, meta=meta)

    def restore_checkpoint(self, step: Optional[int] = None, *,
                           reshard: bool = False) -> int:
        """Refill the FULL trainer state from ``ckpt_dir`` (latest step by
        default) and move the data cursor so the next ``run`` continues the
        killed run step-for-step. ``reshard=True`` accepts a checkpoint
        written on a DIFFERENT ring size and re-shards it onto this one
        (repro.elastic); without it a mesh mismatch raises ``ReshardError``
        before any leaf is decoded. Returns the restored step."""
        assert self.ckpt_dir, "trainer has no ckpt_dir"
        from jax.sharding import NamedSharding

        tr = self.telemetry or NULL_TRACER
        with tr.span("train.restore"):
            return self._restore_checkpoint(step, NamedSharding, tr,
                                            reshard)

    def _restore_checkpoint(self, step, NamedSharding, tr, reshard) -> int:
        from repro import elastic
        dst = self.geometry()
        src = ckpt_lib.validate_restore(self.ckpt_dir, dst, step,
                                        reshard=reshard)
        tree, step = ckpt_lib.restore(self.ckpt_dir, self._snapshot(), step)
        specs = hybrid.state_specs(self.state, self.head)
        mesh = self.mesh

        needs_refresh, plan = False, None
        if src.n_model != dst.n_model:
            t0 = time.perf_counter()
            with tr.span("train.reshard",
                         attrs={"src": src.describe(),
                                "dst": dst.describe()}):
                tree, needs_refresh, led = elastic.reshard_paper_snapshot(
                    tree, self.head, src, dst)
                plan = elastic.plan_reshard(src, dst)
                if not plan.aligned and self.head.params_are_class_weights:
                    # host-staged chunked placement of the dense rows (the
                    # aligned case device_puts gather-free below)
                    tree["head"]["params"] = elastic.place_row_sharded(
                        tree["head"]["params"], mesh, hybrid.AXIS, plan)
            bytes_moved = led.total_bytes()
            tr.count("reshard.bytes_moved", bytes_moved)
            self.last_reshard = {
                "src": src, "dst": dst, "plan": plan.describe(),
                "bytes_moved": bytes_moved, "ledger": led,
                "seconds": time.perf_counter() - t0}

        def put(subtree, spec_tree):
            return jax.tree.map(
                lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
                subtree, spec_tree)

        fe = put(tree["fe"], specs.fe_params)
        hs = self.head.state_from_restore(tree["head"], mesh,
                                          model_axis=hybrid.AXIS)
        opt = put(tree["opt"], specs.opt_state)
        dgc = None
        if self.state.dgc is not None:
            dgc = sp.DGCState(u=put(tree["dgc"]["u"], specs.dgc.u),
                              v=put(tree["dgc"]["v"], specs.dgc.v))
        self.state = hybrid.HybridState(
            fe, hs.params, hs.aux, opt, dgc,
            jnp.asarray(tree["extra"]["step"], jnp.int32))
        self._t = int(tree["extra"]["t"])
        self.restores += 1
        tr.count("train.restores")
        if needs_refresh:
            # the head had aux with no exact re-pack rule: run its own
            # refresh path on the dst mesh (the tentpole's rebuild leg)
            self.refresh_head()
        return step

    # -- the loop ----------------------------------------------------------

    def run(self, total_steps: int, *, use_fccs_batch: bool = True,
            step_hook: Optional[Callable[[int], None]] = None):
        """Run ``total_steps`` MORE steps from the current cursor (0 for a
        fresh trainer; the restored step after ``restore_checkpoint``).
        ``step_hook(t)`` fires before each step — the fault-injection seam
        (``repro.resilience.faults``); whatever it raises propagates after
        any due checkpoint of the previous step was already written."""
        start = self._t
        tr = self.telemetry or NULL_TRACER
        tr.gauge("train.head_prepare_passes", self.head_prepare_passes)
        with jax.set_mesh(self.mesh):
            for t in range(start, start + total_steps):
                with tr.span("train.update", {"step": t}):
                    self._update(t, tr, use_fccs_batch, step_hook)
        tr.record_peak_memory()
        return self.history

    def _update(self, t, tr, use_fccs_batch, step_hook):
        """One iteration of ``run``: the step, then whatever is due."""
        fcfg = self.train_cfg.fccs
        refresh_every = self.head.refresh_every
        if step_hook is not None:
            step_hook(t)
        lr = (self.lr_fn(t) if self.lr_fn is not None
              else fccs.learning_rate(t, fcfg))
        n = (_pow2_quantize(fccs.accum_steps(t, fcfg, self.hw_batch))
             if use_fccs_batch else 1)
        with tr.span("train.data"):
            inputs = self.data_fn(t, self.hw_batch * n)
            step = self._get_step(n)
        with tr.span("train.step"):
            self.state, loss, metrics = step(self.state, inputs, lr)
            if tr.enabled:
                # async dispatch would end the span at launch time; only a
                # live tracer pays for the sync
                jax.block_until_ready(loss)
        tr.count("train.steps")
        self._t = t + 1
        if refresh_every and (t + 1) % refresh_every == 0:
            self.refresh_head()
        if self.ckpt_dir and self.ckpt_every and \
                (t + 1) % self.ckpt_every == 0:
            with tr.span("train.checkpoint"):
                self.save_checkpoint()
            tr.count("train.checkpoints")
        with tr.span("train.log"):
            row = {"step": t, "lr": lr, "batch": self.hw_batch * n,
                   "loss": float(loss), "acc": float(metrics["accuracy"])}
            self.history.append(row)
            tr.log_metrics(row)
            if self.log_every and t % self.log_every == 0:
                print(f"[train] step={t} lr={lr:.4f} B={row['batch']} "
                      f"loss={row['loss']:.4f} acc={row['acc']:.3f}")

    def evaluate(self, eval_inputs) -> float:
        with jax.set_mesh(self.mesh):
            return float(self.eval_step(self.state, eval_inputs))
