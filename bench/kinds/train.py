"""Runner of a training cell.

Set-up builds one ``PaperExperiment`` (the hybrid trainer, its ring and
head) with the benchmark's on-device data feed, and drives it through its
first ``CHECKED`` updates: they compile the step and are the ones compared
with the reference. A head that refreshes then finishes its first cycle in
set-up, so that the window holds whole cycles. The same object then trains
in the window through ``PaperExperiment.fit``, in whole refresh cycles for
a head that refreshes (``rebuild_every`` updates, then the refresh the
trainer runs itself), until ``--seconds`` have passed. The window ends with
the state on the device.

Compared with ``bench/reference/softmax_ref.py`` at the cell's sizes:

- ``loss_gap``: the worst relative gap of the first updates' losses;
- ``grad_norm_gap``: relative gap of the norm of the first gradient of the
  class matrix, as the optimizer got it (its momentum after one update,
  less the weight decay);
- ``update_norm_gap``: relative gap of the norm of the class matrix's
  change over the checked updates;
- ``grad_max_gap``: the widest gap between an entry of the first gradient
  and the reference's, over the reference's widest entry. The gaps of
  norms average rounding away; this reads each class row;
- ``graph_gap`` (a head with a KNN graph): over ``GRAPH_ROWS`` class rows
  drawn from the seed, the widest amount by which a neighbour in the
  graph that the trainer's refresh built from the initial class matrix
  lies below the row's k-th best cosine in the exact graph.
"""
from __future__ import annotations

import gc
import time

import numpy as np

CHECKED = 3
GRAPH_ROWS = 256


def mesh_and_data(r):
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from bench.traffic import generate as gen
    from repro.train import hybrid

    cfg, trf = r.config, r.traffic
    chips = r.spec["chips"]
    mesh = hybrid.make_hybrid_mesh(chips)
    data = gen.train_batch_fn(
        r.seed, classes=cfg["classes_per_chip"] * chips, d=cfg["d"],
        batch=trf["global_batch"], noise=trf["noise"],
        sharding=NamedSharding(mesh, P(hybrid.AXIS)))
    return mesh, data


def build(r, mesh, data_fn, tracer):
    from bench.traffic.generate import seed31
    from repro.api import Experiment
    from repro.configs.base import FCCSConfig, HeadConfig, TrainConfig

    cfg, trf = r.config, r.traffic
    opt = cfg["optimizer"]
    if opt["name"] != "sgd":
        raise ValueError("the training cell checks SGD with momentum")
    b = trf["global_batch"]
    fccs = FCCSConfig(b0=b, b_min=b, b_max=b, **cfg["fccs"])
    train = TrainConfig(optimizer="sgd", momentum=opt["momentum"],
                        weight_decay=opt["weight_decay"], fccs=fccs)
    return Experiment.from_config(
        system="paper", feat_dim=cfg["d"],
        classes=cfg["classes_per_chip"] * r.spec["chips"],
        batch=trf["micro_batch"], head=HeadConfig(**cfg["head"]),
        train=train, mesh=mesh, data_fn=data_fn, log_every=0,
        seed=seed31(r.seed), telemetry=tracer)


def checked_updates(exp, weight_decay: float) -> dict:
    """Drive the first CHECKED updates and read what the reference is
    compared on: their losses, the first gradient as the optimizer got it,
    and the class matrix's change."""
    import jax.numpy as jnp

    w0 = jnp.copy(exp.state.head_params)
    exp.fit(1)
    grad = exp.state.opt_state.mu[1] - weight_decay * w0
    grad_norm = float(jnp.linalg.norm(grad))
    grad = np.asarray(grad)
    exp.fit(CHECKED - 1)
    update_norm = float(jnp.linalg.norm(exp.state.head_params - w0))
    del w0
    return {"losses": [h["loss"] for h in exp.trainer.history[:CHECKED]],
            "grad_norm": grad_norm, "update_norm": update_norm,
            "grad": grad}


def graph_rows(r) -> np.ndarray:
    from bench.traffic.generate import seed31
    rng = np.random.default_rng([seed31(r.seed), 13])
    return np.sort(rng.choice(r.config["classes_per_chip"], GRAPH_ROWS,
                              replace=False))


def graph_lists(exp, rows) -> list:
    """The neighbour lists of ``rows`` in the head's graph (the CSR of one
    chip, whose local ids are the class ids)."""
    offsets, neighbors = (np.asarray(a).reshape(-1)
                          for a in exp.state.head_aux[:2])
    return [neighbors[offsets[i]:offsets[i + 1]] for i in rows]


def graph_gap(r, rows, lists, precision="highest") -> float:
    from bench.reference import softmax_ref
    from bench.traffic.generate import seed31

    cfg = r.config
    return softmax_ref.neighbor_gap(
        seed31(r.seed), rows, lists, classes=cfg["classes_per_chip"],
        d=cfg["d"], k=cfg["head"]["knn_k"], precision=precision)


def reference(r, precision="highest", half_batch=False) -> dict:
    from bench.reference import softmax_ref
    from bench.traffic import generate as gen

    cfg, trf = r.config, r.traffic
    classes = cfg["classes_per_chip"] * r.spec["chips"]
    batch = gen.train_batch_fn(r.seed, classes=classes, d=cfg["d"],
                               batch=trf["global_batch"], noise=trf["noise"])
    return softmax_ref.train_steps(
        seed=r.seed, n_steps=CHECKED, classes=classes, d=cfg["d"],
        batch_fn=batch, micro=trf["micro_batch"], head=cfg["head"],
        opt=cfg["optimizer"], fccs=cfg["fccs"], precision=precision,
        half_batch=half_batch)


def gaps(got: dict, want: dict) -> dict:
    """The numbers compared: relative gaps of program against reference."""
    loss = max(abs(a - b) / abs(b) for a, b in
               zip(got["losses"], want["losses"]))
    return {
        "loss_gap": loss,
        "grad_norm_gap": abs(got["grad_norm"] - want["grad_norm"])
        / want["grad_norm"],
        "update_norm_gap": abs(got["update_norm"] - want["update_norm"])
        / want["update_norm"],
        "grad_max_gap": max_gap(got["grad"], want["grad"]),
    }


def max_gap(got, want) -> float:
    """The widest entry gap over the reference's widest entry."""
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def cycle(exp) -> int:
    """Updates up to and including the next refresh (1 without one)."""
    every = exp.head.refresh_every
    if not every:
        return 1
    return every - exp.trainer._t % every


def run(r) -> None:
    import jax

    from bench.run import load_limits, peak_bytes
    from repro.telemetry import Tracer

    cfg, trf = r.config, r.traffic
    limits = load_limits(r)
    tracer = Tracer()
    r.tracer = tracer
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        mesh, data = mesh_and_data(r)

        def data_fn(t, b):
            with r.annotate("bench.data"):
                return data(t)

        exp = build(r, mesh, data_fn, tracer)
        knn = cfg["head"]["softmax_impl"] == "knn"
        if knn:
            if r.spec["chips"] != 1:
                raise ValueError("the graph is read from one chip's CSR")
            rows = graph_rows(r)
            lists = graph_lists(exp, rows)
        if r.trace:
            refresh = exp.trainer.refresh_head

            def annotated_refresh():
                with r.annotate("bench.refresh"):
                    return refresh()
            exp.trainer.refresh_head = annotated_refresh
        got = checked_updates(exp, cfg["optimizer"]["weight_decay"])
        if exp.head.refresh_every:
            exp.fit(cycle(exp))
        jax.block_until_ready(exp.state.head_params)

        updates = 0
        if r.trace:
            jax.profiler.start_trace(r.out_dir)
        t0_ns = time.perf_counter_ns()
        t0 = time.perf_counter()
        with r.annotate("bench.window"):
            while True:
                n = cycle(exp)
                with r.annotate("bench.fit"):
                    exp.fit(n)
                updates += n
                if time.perf_counter() - t0 >= r.seconds:
                    break
            jax.block_until_ready(exp.state.head_params)
        t1 = time.perf_counter()
        t1_ns = time.perf_counter_ns()
        if r.trace:
            jax.profiler.stop_trace()
    r.window_s = t1 - t0
    samples = updates * trf["global_batch"]
    r.attempted = updates
    r.e2e = {"train_samples_per_s": samples / r.window_s,
             "setup_s": t0 - r.t_process}
    r.facts = {
        "window_ns": (t0_ns, t1_ns), "samples": samples, "updates": updates,
        "micro_batches": updates * (trf["global_batch"]
                                    // trf["micro_batch"]),
        "micro_batch": trf["micro_batch"], "classes_per_chip":
        cfg["classes_per_chip"], "d": cfg["d"], "chips": r.spec["chips"],
        "head": cfg["head"]["softmax_impl"],
        "active_per_chip": max(8, int(cfg["classes_per_chip"]
                                      * cfg["head"].get("active_frac", 0))),
    }
    r.memory_peak = peak_bytes(r.devs)
    del exp, data
    gc.collect()
    want = reference(r)
    for name, value in gaps(got, want).items():
        r.checks.append((name, value, limits[name]))
    if knn:
        r.checks.append(("graph_gap", graph_gap(r, rows, lists),
                         limits["graph_gap"]))
