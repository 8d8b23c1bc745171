"""Bring-up check of the paper system on a TPU.

Drives the hybrid trainer and deploy-style serving through the
``Experiment`` entry point at the paper's feature width (D=512) and one
chip's share of its 100,001,020 classes: 100,001,020 / 256 chips, padded to
a multiple of the 512-row vocab tile, is 390,656 classes per chip. The
class matrix is float32 and random, drawn from a seed.

  phase 0  the devices. Fails unless JAX finds a TPU; never falls back.
  phase 1  training. Heads ``full`` and ``knn``, each under the ``ref``
           (XLA) and ``pallas`` (Mosaic-compiled kernels) backends, 4 steps
           at batch 256 with a KNN graph refresh before step 4. The losses
           must be finite, step 0 must agree across the backends to float32
           tolerance, and the lowered pallas step must hold
           ``tpu_custom_call`` (kernels compiled, not interpreted).
  phase 2  serving. Top-5 requests through the serving engine
           (``exp.serve``), by exact scan and through the IVF index, under
           both backends; the top-1 ids must agree across the backends.

``--four-chips`` runs only the 4-way hybrid ring on a four-chip host:
4 x 390,656 classes, heads ``full`` and ``knn`` for 2 steps, and the full
head's step-0 loss against a plain float32 jax.numpy softmax-CE over the
gathered [V, D] class matrix. Only step 0 is compared: the head gradient's
scale depends on the ring size.

Matmuls run at float32 precision (``jax.default_matmul_precision``), so
both backends compute the float32 model they describe. Everything runs in
this one process, which holds the chips. Timings are printed on earlier
lines for information; any failed check exits non-zero. The last line of
stdout is {"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": ...}}.

Run from the repository root:  python chip_smoke.py [--four-chips]
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

D = 512
CLASSES_PER_CHIP = 390_656
BATCH = 256
STEPS = 4
REBUILD_EVERY = 3        # one knn refresh before step 4, after the initial
SERVE_BATCH = 8
TOP_K = 5
FOUR_CHIP_STEPS = 2


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(what: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {what}")


def losses_agree(a: float, b: float) -> bool:
    """float32 agreement of two mean CE losses over ~10^5..10^6-way
    softmaxes summed in different orders."""
    return abs(a - b) <= 1e-4 + 1e-5 * abs(b)


def devices(n_chips: int):
    """Phase 0: the devices as JAX reports them; a TPU or nothing."""
    import jax

    devs = jax.devices()
    log(f"[phase 0] jax {jax.__version__}; {len(devs)} device(s)")
    for d in devs:
        log(f"[phase 0]   id={d.id} platform={d.platform} "
            f"kind={d.device_kind}")
    if devs[0].platform != "tpu":
        fail(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < n_chips:
        fail(f"{n_chips} chips needed, JAX found {len(devs)}")
    return devs


def build(head: str, backend: str, mesh, classes: int, *,
          rebuild_every: int = 0, telemetry=None):
    from repro.api import Experiment
    from repro.configs.base import HeadConfig

    return Experiment.from_config(
        system="paper", feat_dim=D, classes=classes, batch=BATCH,
        head=HeadConfig(softmax_impl=head, backend=backend,
                        rebuild_every=rebuild_every),
        mesh=mesh, log_every=0, seed=0, telemetry=telemetry)


def span_seconds(tracer, name: str) -> list:
    return [e.dur_ns * 1e-9 for e in tracer.events if e.name == name]


def fit_checked(exp, tr, label: str, steps: int) -> list:
    """Train ``steps`` steps and check them; returns the step losses."""
    hist = exp.fit(steps, use_fccs_batch=False)
    losses = [row["loss"] for row in hist]
    step_s = span_seconds(tr, "train.step")
    refresh_s = span_seconds(tr, "train.refresh")
    log(f"{label}: losses {losses}")
    log(f"{label}: first step (compile + run) {step_s[0]:.2f} s; later "
        f"steps {[round(x, 4) for x in step_s[1:]]} s; refreshes "
        f"{[round(x, 2) for x in refresh_s]} s; process peak HBM bytes "
        f"{tr.record_peak_memory()}")
    if len(losses) != steps or not all(math.isfinite(x) for x in losses):
        fail(f"{label}: non-finite or missing losses {losses}")
    if exp.head.refresh_every and len(refresh_s) < 2:
        fail(f"{label}: no knn refresh ran inside the steps")
    lowered = exp.trainer._get_step(1).lower(
        exp.state, exp.data_fn(0, BATCH), 0.1).as_text()
    kernels = "tpu_custom_call" in lowered
    log(f"{label}: tpu_custom_call in the step: {kernels}")
    if kernels != (exp.head.backend == "pallas"):
        fail(f"{label}: tpu_custom_call present={kernels}")
    return losses


def build_traced(head: str, backend: str, mesh, classes: int,
                 rebuild_every: int):
    from repro.telemetry import Tracer

    tr = Tracer()
    t0 = time.perf_counter()
    exp = build(head, backend, mesh, classes, rebuild_every=rebuild_every,
                telemetry=tr)
    log(f"[{head}/{backend}] built in {time.perf_counter() - t0:.1f} s "
        f"(the head's initial refresh included)")
    return exp, tr


def phase_train(mesh, classes: int) -> None:
    for head in ("full", "knn"):
        step0 = {}
        for backend in ("ref", "pallas"):
            exp, tr = build_traced(head, backend, mesh, classes,
                                   REBUILD_EVERY if head == "knn" else 0)
            step0[backend] = fit_checked(exp, tr, f"[phase 1] "
                                         f"{head}/{backend}", STEPS)[0]
            del exp
            gc.collect()
        ref, pal = step0["ref"], step0["pallas"]
        log(f"[phase 1] {head}: step-0 loss ref {ref!r} pallas {pal!r} "
            f"(diff {abs(ref - pal):.3g})")
        if not losses_agree(pal, ref):
            fail(f"{head}: step-0 loss pallas {pal} != ref {ref}")


def serve_timed(exp, **kw):
    """Answer SERVE_BATCH requests twice; the first call compiles."""
    import numpy as np

    t0 = time.perf_counter()
    exp.serve(top_k=TOP_K, batch=SERVE_BATCH, return_scores=True, **kw)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    ids, scores = exp.serve(top_k=TOP_K, batch=SERVE_BATCH,
                            return_scores=True, **kw)
    again = time.perf_counter() - t0
    return np.asarray(ids), np.asarray(scores), first, again


def phase_serve(mesh, classes: int) -> None:
    import numpy as np

    exps = {b: build("full", b, mesh, classes) for b in ("ref", "pallas")}
    t0 = time.perf_counter()
    index = exps["ref"].ivf_index()
    log(f"[phase 2] IVF fit {time.perf_counter() - t0:.1f} s: "
        f"{index.n_clusters} clusters of cap {index.cap}, nprobe "
        f"{index.nprobe}")
    exps["pallas"].install_ivf_index(index)
    top1 = {}
    for mode in ("exact", "ivf"):
        kw = {"index": "ivf"} if mode == "ivf" else {}
        for backend, exp in exps.items():
            ids, scores, first, again = serve_timed(exp, **kw)
            log(f"[phase 2] {mode}/{backend}: first call (compile + run) "
                f"{first:.2f} s; {SERVE_BATCH} requests {again:.4f} s; "
                f"top-1 {ids[:, 0].tolist()}")
            if ids.shape != (SERVE_BATCH, TOP_K):
                fail(f"{mode}/{backend}: ids shape {ids.shape}")
            if not ((ids >= 0) & (ids < classes)).all():
                fail(f"{mode}/{backend}: ids out of range")
            if not np.isfinite(scores).all():
                fail(f"{mode}/{backend}: non-finite scores")
            if not (np.diff(scores, axis=1) <= 0).all():
                fail(f"{mode}/{backend}: scores not descending")
            top1[mode, backend] = ids[:, 0]
        if not (top1[mode, "ref"] == top1[mode, "pallas"]).all():
            fail(f"{mode}: top-1 ids differ across backends")


def phase_four_chips(mesh, classes: int) -> None:
    """The 4-way hybrid ring, and its step 0 against plain jax.numpy."""
    import jax

    from repro.core.sharded_softmax import ce_ref

    for head in ("full", "knn"):
        exp, tr = build_traced(head, "pallas", mesh, classes, 0)
        if head == "full":
            # the [V, D] gather and its [B, V] logits go to the last chip,
            # which holds no input stream
            dev = jax.devices()[len(mesh.devices.flat) - 1]
            batch = jax.device_put(exp.data_fn(0, BATCH), dev)
            w = jax.device_put(exp.state.head_params, dev)
            want, _ = ce_ref(batch["features"], batch["labels"], w,
                             cosine_scale=exp.head_cfg.cosine_scale)
            want = float(want)
            del w
        losses = fit_checked(exp, tr, f"[4 chips] {head}/pallas",
                             FOUR_CHIP_STEPS)
        if head == "full":
            log(f"[4 chips] full: step-0 loss {losses[0]!r}, float32 "
                f"jax.numpy reference {want!r} (diff "
                f"{abs(losses[0] - want):.3g})")
            if not losses_agree(losses[0], want):
                fail(f"4-chip full step-0 loss {losses[0]} != {want}")
        del exp
        gc.collect()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the 4-way hybrid ring (needs 4 chips)")
    args = p.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail(f"no src/repro next to {__file__}: run from a checkout")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.api.bootstrap import enable_compile_cache
    log(f"[phase 0] compile cache: {enable_compile_cache()}")

    import jax

    from repro.train import hybrid

    n_chips = 4 if args.four_chips else 1
    devs = devices(n_chips)
    mesh = hybrid.make_hybrid_mesh(n_chips)
    classes = CLASSES_PER_CHIP * n_chips
    with jax.default_matmul_precision("float32"):
        if args.four_chips:
            phase_four_chips(mesh, classes)
        else:
            phase_train(mesh, classes)
            phase_serve(mesh, classes)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
