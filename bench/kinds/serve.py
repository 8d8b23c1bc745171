"""Runner of a serving cell.

Set-up builds a ``PaperExperiment`` whose class matrix is drawn from the
seed (no training), its ``ServingEngine`` (exact top-k, no score cache),
every query of the run (unique, made on the device in one call) and their
open-loop Poisson due times, and warms every padding bucket of the engine.

The window submits each query at its due time (``submit(q, now=due)``, so
a stall delays the requests behind it) and polls the engine until
``--seconds`` have passed. A request's latency runs from its due time to
the moment its result is on the host after ``poll``. A request due in the
window and not done when it closes counts in the tail with the time from
its due time to the close, a lower bound, and not as completed; it is
answered after the close and compared like the others. A request fails
only when no answer ever comes.

After the close the rest is drained, and a sample of the completed
requests, drawn from the seed, is compared with the plain float32
reference (``bench/reference/softmax_ref.topk_scores``):

- ``score_gap``: the widest gap between a served score and the reference's
  score of the served id;
- ``rank_gap``: the widest amount by which the reference's score of the
  id served at rank j lies below the reference's j-th best score (2, the
  widest a cosine gap can be, for an id out of range or repeated);
- ``unanswered``: requests that never got an answer, limit 0.
"""
from __future__ import annotations

import gc
import time

import numpy as np

SAMPLE = 1024


def build(r, tracer):
    from bench.traffic.generate import seed31
    from repro.api import Experiment
    from repro.configs.base import HeadConfig, TrainConfig
    from repro.train import hybrid

    cfg, trf = r.config, r.traffic

    def no_training_data(t, b):
        raise RuntimeError("a serving cell does not train")

    exp = Experiment.from_config(
        system="paper", feat_dim=cfg["d"],
        classes=cfg["classes_per_chip"] * r.spec["chips"],
        batch=trf["max_batch"], head=HeadConfig(**cfg["head"]),
        train=TrainConfig(optimizer=cfg["optimizer"]["name"]),
        mesh=hybrid.make_hybrid_mesh(r.spec["chips"]),
        data_fn=no_training_data, log_every=0, seed=seed31(r.seed))
    eng = exp.serving_engine(top_k=trf["top_k"], max_batch=trf["max_batch"],
                             max_wait_ms=trf["max_wait_ms"], cache=None,
                             clock=time.perf_counter, telemetry=tracer)
    return exp, eng


def serve_window(r, eng, queries, due, seconds):
    """Open-loop serving of ``queries`` at ``due`` (s after the start) for
    ``seconds``; returns (latency s [n] (nan: not done in the window),
    ids [n, k], scores [n, k], window_s, the engine's batch count and
    ``serve.queue_wait_s`` counter at the close)."""
    n = len(due)
    k = r.traffic["top_k"]
    lat = np.full(n, np.nan)
    ids = np.full((n, k), -1, np.int64)
    scores = np.full((n, k), np.nan)
    clock = time.perf_counter
    i = 0

    def take(done, t_host, t0):
        for req in done:
            ids[req.rid] = req.ids
            scores[req.rid] = req.scores
            if t_host is not None:
                lat[req.rid] = t_host - (t0 + due[req.rid])

    t0 = clock()
    end = t0 + seconds
    with r.annotate("bench.window"):
        while True:
            now = clock()
            if now >= end:
                break
            while i < n and t0 + due[i] <= now:
                eng.submit(queries[i], now=t0 + due[i])
                i += 1
            with r.annotate("bench.poll"):
                done = eng.poll(now)
            t_host = clock()
            if done:
                take(done, t_host if t_host <= end else None, t0)
                continue
            nxt = min(t0 + due[i] if i < n else end,
                      eng.coalescer.oldest_deadline(default=end), end)
            wait = nxt - clock()
            if wait > 0:
                with r.annotate("bench.wait"):
                    time.sleep(wait)
        t_close = clock()
    at_close = (eng.n_batches,
                eng.telemetry.counters.get("serve.queue_wait_s", 0.0))
    # the rest, for the comparison only: late is late, not wrong
    while i < n:
        eng.submit(queries[i], now=t0 + due[i])
        i += 1
    take(eng.drain(), None, t0)
    return lat, ids, scores, t_close - t0, at_close


def sample(r, done_idx: np.ndarray) -> np.ndarray:
    from bench.traffic.generate import seed31
    rng = np.random.default_rng([seed31(r.seed), 11])
    if len(done_idx) <= SAMPLE:
        return np.sort(done_idx)
    return np.sort(rng.choice(done_idx, SAMPLE, replace=False))


def compare(ids, scores, best, of_served, classes: int) -> dict:
    """The numbers compared, from served (ids, scores) and the reference's
    (best scores, scores of the served ids), all [n, k]."""
    bad = (ids < 0) | (ids >= classes)
    srt = np.sort(ids, axis=1)
    bad |= np.concatenate([np.zeros((len(ids), 1), bool),
                           srt[:, 1:] == srt[:, :-1]], axis=1)
    rank = np.where(bad, 2.0, best - of_served)
    score = np.where(bad, 2.0, np.abs(scores - of_served))
    return {"score_gap": float(np.max(score)),
            "rank_gap": float(max(0.0, np.max(rank)))}


def reference(r, queries, served_ids, precision="highest"):
    from bench.reference import softmax_ref

    cfg = r.config
    return softmax_ref.topk_scores(
        r.seed, queries, served_ids,
        classes=cfg["classes_per_chip"] * r.spec["chips"], d=cfg["d"],
        k=r.traffic["top_k"], precision=precision)


def run(r) -> None:
    import jax

    from bench.run import load_limits, peak_bytes
    from bench.traffic import generate as gen
    from repro.telemetry import Tracer

    cfg, trf = r.config, r.traffic
    limits = load_limits(r)
    classes = cfg["classes_per_chip"] * r.spec["chips"]
    tracer = Tracer()
    r.tracer = tracer
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        exp, eng = build(r, tracer)
        due = gen.poisson_arrivals(r.seed, trf["rate"], r.seconds)
        queries, _ = gen.queries(r.seed, len(due), classes=classes,
                                 d=cfg["d"], noise=trf["noise"])
        eng.warmup(queries[0])
        batches = []
        step_fn = eng.step_fn

        def recorded_step(q, n_valid):
            batches.append(n_valid)
            with r.annotate("bench.serve_step"):
                return step_fn(q, n_valid)
        eng.step_fn = recorded_step
        if r.trace:
            jax.profiler.start_trace(r.out_dir)
        t_open = time.perf_counter()
        t_open_ns = time.perf_counter_ns()
        lat, ids, scores, window_s, (n_batches, wait_s) = serve_window(
            r, eng, queries, due, r.seconds)
        t_close_ns = t_open_ns + int(window_s * 1e9)
        if r.trace:
            jax.profiler.stop_trace()
    done = np.isfinite(lat)
    in_window = batches[:n_batches]
    r.window_s = window_s
    r.attempted = len(due)
    # late is not failed: a request fails when no answer ever comes
    r.failed = int((ids < 0).all(axis=1).sum())
    censored = np.where(done, lat, window_s - due)
    r.e2e = {"serve_p95_ms": float(np.percentile(censored, 95)) * 1e3,
             "serve_done_per_s": float(done.sum()) / window_s,
             "setup_s": t_open - r.t_process}
    r.facts = {"window_ns": (t_open_ns, t_close_ns), "classes": classes,
               "d": cfg["d"], "batches": in_window,
               "batched_requests": sum(in_window),
               "queue_wait_s": wait_s}
    r.memory_peak = peak_bytes(r.devs)
    del exp, eng
    gc.collect()
    idx = sample(r, np.flatnonzero(np.isfinite(scores).all(axis=1)))
    best, _, of_served = reference(r, queries[idx], ids[idx])
    for name, value in compare(ids[idx], scores[idx], best, of_served,
                               classes).items():
        r.checks.append((name, value, limits[name]))
    r.checks.append(("unanswered", r.failed, 0))
