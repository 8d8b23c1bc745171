"""Serving launcher — a thin argparse shim over ``repro.api.Experiment``.

The paper deploys the trained 100M-class fc as a retrieval index (§4.5 —
nearest class weight); ``Experiment.serve`` on the paper system IS that
lookup, executed on the training mesh with whatever head is configured
(hashed-bucket decode for mach/csoft). On the zoo system it is standard batched
token serving: prefill once, then greedy decode steps through the KV/SSM
cache and the sharded-vocab argmax.

``--replay SECONDS`` switches either system onto the ``repro.serving``
tier instead: single feature queries from a bursty Zipfian synthetic
trace are submitted to a ``ServingEngine`` (request coalescing into
padded micro-batches, ``--max-wait-ms`` flush deadline, optional
``--cache N`` LRU score cache) and the run reports p50/p95/p99 latency,
QPS, batch occupancy, and cache hit-rate. The full harness (trajectory
file, cached-vs-uncached sweep) lives in ``benchmarks/serve_replay.py``.

  PYTHONPATH=src python -m repro.launch.serve --devices 8 \
      --arch smollm_135m --reduced --prompt-len 32 --gen 16 --batch 8
  PYTHONPATH=src python -m repro.launch.serve --devices 8 --system paper \
      --classes 4096 --head knn --batch 64
  PYTHONPATH=src python -m repro.launch.serve --devices 8 --system paper \
      --classes 4096 --head full --topk 5 --replay 1.0 --cache 512 \
      --max-wait-ms 2
"""
from __future__ import annotations

import argparse
import sys


def _run_replay(exp, args, feat_dim: int, telemetry=None) -> int:
    """Trace-driven serving through the engine (both systems)."""
    import numpy as np

    from repro.serving import (ScoreCache, TraceConfig, VirtualClock,
                               generate_trace, latency_stats,
                               make_query_pool, replay_trace)

    tcfg = TraceConfig(duration=args.replay)
    times, qids = generate_trace(tcfg)
    pool = make_query_pool(args.classes, feat_dim, tcfg.pool)
    cache = ScoreCache(args.cache) if args.cache else None
    clock = VirtualClock()
    eng = exp.serving_engine(
        top_k=args.topk or None, max_batch=args.batch,
        max_wait_ms=args.max_wait_ms, cache=cache, clock=clock.now,
        index=args.index if args.index != "none" else None,
        nprobe=args.nprobe or None, telemetry=telemetry)
    eng.warmup(pool[0])
    done = replay_trace(eng, clock, times, qids, pool)
    lat = latency_stats(done)
    st = eng.stats()
    span = max(r.t_done for r in done) - min(r.t_submit for r in done)
    print(f"[serve] replayed {lat['n']} requests over {args.replay:.1f}s "
          f"of trace ({args.head} head, top-{args.topk or 1}): "
          f"p50={lat['p50_ms']:.2f}ms p95={lat['p95_ms']:.2f}ms "
          f"p99={lat['p99_ms']:.2f}ms qps={lat['n'] / max(span, 1e-9):.1f}")
    print(f"[serve] batches={st['n_batches']} "
          f"occupancy={st['mean_batch_occupancy']:.2f} "
          f"cache_hit_rate={st['cache_hit_rate']:.2f}")
    pred = done[0].ids
    print("[serve] first result ids:", np.atleast_1d(pred).tolist())
    return 0


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--system", choices=["paper", "zoo"], default="zoo")
    p.add_argument("--devices", type=int, default=0)
    # zoo
    p.add_argument("--arch", default="smollm_135m")
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--gen", type=int, default=16)
    # paper
    p.add_argument("--classes", type=int, default=4096)
    p.add_argument("--feat-dim", type=int, default=64)
    p.add_argument("--head",
                   choices=["full", "knn", "selective", "mach", "sampled",
                            "csoft"],
                   default="full")
    p.add_argument("--topk", type=int, default=0,
                   help="paper system: return the k best classes per query "
                        "with scores (0 = greedy argmax)")
    p.add_argument("--index", choices=["none", "ivf"], default="none",
                   help="top-k serving index: 'ivf' probes nprobe k-means "
                        "centroids per class shard and reranks only their "
                        "member rows (sublinear in the class count)")
    p.add_argument("--nprobe", type=int, default=0,
                   help="--index ivf: centroids probed per shard "
                        "(0 = the index default, max(2, n_clusters/32))")
    # shared
    p.add_argument("--backend", choices=["ref", "pallas"], default="ref",
                   help="head hot-path compute backend")
    p.add_argument("--batch", type=int, default=8)
    # serving tier (repro.serving engine)
    p.add_argument("--replay", type=float, default=0.0, metavar="SECONDS",
                   help="replay a bursty Zipfian synthetic trace of this "
                        "many (virtual) seconds through the serving "
                        "engine instead of a one-shot batch")
    p.add_argument("--cache", type=int, default=0, metavar="N",
                   help="LRU hot-query score-cache capacity for --replay "
                        "(0 = no cache)")
    p.add_argument("--max-wait-ms", type=float, default=2.0,
                   help="coalescer flush deadline: max time a queued query "
                        "waits for batch-mates before a partial "
                        "micro-batch is cut")
    # telemetry (docs/telemetry.md)
    p.add_argument("--trace-out", default="", metavar="PATH",
                   help="write a Chrome-trace/Perfetto JSON of the serving "
                        "spans (open at https://ui.perfetto.dev)")
    p.add_argument("--metrics-out", default="", metavar="PATH",
                   help="append serving metrics as JSONL")
    args = p.parse_args(argv)

    # validate up front: a clear argparse error beats an opaque jit shape
    # failure out of the serving step
    if args.batch <= 0:
        p.error(f"--batch must be a positive query count, got {args.batch}")
    if args.topk < 0:
        p.error(f"--topk must be >= 0, got {args.topk}")
    if args.system == "paper" and args.topk > args.classes:
        p.error(f"--topk {args.topk} exceeds --classes {args.classes}: "
                f"retrieval cannot return more classes than exist")
    if args.index == "ivf" and not args.topk:
        p.error("--index ivf serves top-k retrieval; pass --topk K")
    if args.nprobe < 0:
        p.error(f"--nprobe must be >= 0, got {args.nprobe}")
    if args.nprobe and args.index != "ivf":
        p.error("--nprobe only applies with --index ivf")
    if args.cache < 0:
        p.error(f"--cache must be >= 0, got {args.cache}")
    if args.max_wait_ms < 0:
        p.error(f"--max-wait-ms must be >= 0, got {args.max_wait_ms}")

    from repro.api.bootstrap import enable_compile_cache, ensure_host_devices
    ensure_host_devices(args.devices)
    enable_compile_cache()
    from repro.telemetry import Tracer

    # one tracer for the whole run: the timings printed below are the
    # SAME engine/telemetry spans the benchmarks record (no second
    # hand-rolled perf_counter clock that can disagree on cache hits)
    tr = Tracer(metrics_path=args.metrics_out or None)
    try:
        return _serve(args, tr)
    finally:
        if args.trace_out:
            tr.write_chrome_trace(args.trace_out)
            print(f"[telemetry] trace -> {args.trace_out}")
        tr.close()


def _serve(args, tr) -> int:
    from repro.api import Experiment
    from repro.configs.base import HeadConfig

    def compute_ms() -> float:
        """Engine-measured compute wall-clock (ms) for this run's
        serve.compute spans — what the serving benchmarks also report."""
        return tr.span_stats("serve.compute")["total_s"] * 1e3

    if args.system == "paper":
        exp = Experiment.from_config(
            system="paper", classes=args.classes, feat_dim=args.feat_dim,
            batch=args.batch,
            head=HeadConfig(softmax_impl=args.head, backend=args.backend),
            log_every=0)
        if args.replay > 0:
            return _run_replay(exp, args, args.feat_dim, telemetry=tr)
        if args.topk:
            ids, scores = exp.serve(
                batch=args.batch, top_k=args.topk, return_scores=True,
                index=args.index if args.index != "none" else None,
                nprobe=args.nprobe or None, telemetry=tr)
            via = f" via {args.index}" if args.index != "none" else ""
            print(f"[serve] {args.head}-head top-{args.topk} retrieval over "
                  f"{args.classes} classes ({args.backend}{via}): "
                  f"{ids.shape[0]} queries in {compute_ms():.1f} ms")
            print("[serve] first query ids:   ", ids[0].tolist())
            print("[serve] first query scores:",
                  [round(float(s), 3) for s in scores[0]])
            return 0
        preds = exp.serve(batch=args.batch, telemetry=tr)
        print(f"[serve] {args.head}-head retrieval over {args.classes} "
              f"classes: {preds.shape[0]} queries in {compute_ms():.1f} ms")
        print("[serve] first predictions:", preds[:8].tolist())
        return 0

    exp = Experiment.from_config(system="zoo", arch=args.arch,
                                 reduced=args.reduced, batch=args.batch,
                                 seq=args.prompt_len + args.gen,
                                 head=HeadConfig(softmax_impl=args.head,
                                                 backend=args.backend))
    if args.replay > 0:
        # zoo replay serves FEATURE queries against the model's class
        # matrix (the classifier-as-retrieval path); token decoding stays
        # on the one-shot path below
        args = argparse.Namespace(**{**vars(args),
                                     "classes": exp.model_cfg.vocab_size})
        return _run_replay(exp, args, exp.model_cfg.d_model, telemetry=tr)
    if args.topk:
        # zoo feature retrieval against the model's class matrix (same
        # contract as the paper top-k path; token decoding stays below)
        try:
            ids, scores = exp.serve(
                batch=args.batch, top_k=args.topk, return_scores=True,
                index=args.index if args.index != "none" else None,
                nprobe=args.nprobe or None, telemetry=tr)
        except NotImplementedError as e:
            print(f"[serve] {e}")
            return 0
        via = f" via {args.index}" if args.index != "none" else ""
        print(f"[serve] zoo {args.head}-head top-{args.topk} retrieval over "
              f"{exp.model_cfg.vocab_size} classes ({args.backend}{via}): "
              f"{ids.shape[0]} queries in {compute_ms():.1f} ms")
        print("[serve] first query ids:   ", ids[0].tolist())
        print("[serve] first query scores:",
              [round(float(s), 3) for s in scores[0]])
        return 0
    try:
        gen = exp.serve(prompt_len=args.prompt_len, gen=args.gen,
                        batch=args.batch, telemetry=tr)
    except NotImplementedError as e:
        print(f"[serve] {e}")
        return 0
    prefill_ms = tr.span_stats("serve.prefill")["total_s"] * 1e3
    decode_s = tr.span_stats("serve.decode")["total_s"]
    print(f"[serve] generated {gen.shape} tokens: prefill {prefill_ms:.1f} ms"
          f" + decode {decode_s * 1e3:.1f} ms "
          f"({args.batch * args.gen / max(decode_s, 1e-9):.1f} tok/s)")
    print("[serve] first row:", gen[0].tolist())
    return 0


if __name__ == "__main__":
    sys.exit(main())
