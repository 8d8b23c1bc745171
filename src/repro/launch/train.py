"""Training launcher — a thin argparse shim over ``repro.api.Experiment``.

Two systems behind one entry point:
  * ``--system paper`` — the faithful hybrid-parallel trainer (FE data
    parallel + fc model parallel on a 1-D ring) with ANY registered softmax
    head (``--head full|knn|selective|mach|sampled|csoft``) plus DGC / FCCS
    toggles.
  * ``--system zoo`` — the GSPMD trainer for any assigned architecture
    (``--arch``), tensor/expert parallel on a (data, model) mesh, with the
    same ``--head`` choices routed through the head registry.

On this CPU container use --devices N to get N fake devices (the flag must
be set before jax initializes; ``ensure_host_devices`` handles that).

Examples:
  PYTHONPATH=src python -m repro.launch.train --system paper --devices 8 \
      --classes 4096 --steps 200 --head knn --fccs
  PYTHONPATH=src python -m repro.launch.train --system zoo --devices 8 \
      --arch smollm_135m --reduced --steps 20
"""
from __future__ import annotations

import argparse
import os
import sys


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--system", choices=["paper", "zoo"], default="paper")
    p.add_argument("--devices", type=int, default=0,
                   help="fake host devices (CPU container)")
    # paper system
    p.add_argument("--classes", type=int, default=4096)
    p.add_argument("--feat-dim", type=int, default=64)
    p.add_argument("--head",
                   choices=["full", "knn", "selective", "mach", "sampled",
                            "csoft"],
                   default="full", help="softmax head strategy")
    p.add_argument("--backend", choices=["ref", "pallas"], default="ref",
                   help="head hot-path compute backend (pallas = fused "
                        "kernels, interpret mode on CPU)")
    p.add_argument("--knn", action="store_true",
                   help="back-compat alias for --head knn")
    p.add_argument("--dgc", action="store_true")
    p.add_argument("--fccs", action="store_true")
    p.add_argument("--trunk", choices=["feats", "cnn"], default="feats")
    # zoo system
    p.add_argument("--arch", default="smollm_135m")
    p.add_argument("--reduced", action="store_true")
    # shared
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--lr", type=float, default=2.0)
    p.add_argument("--optimizer", default="sgd")
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--ckpt-every", type=int, default=50,
                   help="full-state snapshot cadence (steps); both systems")
    p.add_argument("--ckpt-keep", type=int, default=None,
                   help="retain only the N newest checkpoints "
                        "(>= 1; omit to keep all)")
    p.add_argument("--resume", nargs="?", const=True, default=False,
                   metavar="CKPT",
                   help="restore the latest checkpoint and run only the "
                        "remaining steps (--steps is the TOTAL). With no "
                        "value, restores from --ckpt-dir; a value names a "
                        "checkpoint directory (or a .msgpack.zst file inside "
                        "one) and implies --ckpt-dir")
    p.add_argument("--resume-reshard", action="store_true",
                   help="allow --resume from a checkpoint written on a "
                        "DIFFERENT mesh shape: re-shards it onto this run's "
                        "--devices mesh (repro.elastic); implies --resume")
    p.add_argument("--trace-out", default="", metavar="PATH",
                   help="write a Chrome-trace/Perfetto JSON of the run's "
                        "telemetry spans (open at https://ui.perfetto.dev)")
    p.add_argument("--metrics-out", default="", metavar="PATH",
                   help="append per-step train metrics as JSONL")
    args = p.parse_args(argv)
    if args.resume_reshard and not args.resume:
        args.resume = True
    if isinstance(args.resume, str):
        # --resume CKPT names the checkpoint to restore from; accept either
        # the directory or one of its .msgpack.zst files
        path = args.resume
        if path.endswith(".msgpack.zst"):
            path = os.path.dirname(path) or "."
        if args.ckpt_dir and args.ckpt_dir != path:
            p.error(f"--resume {args.resume} conflicts with "
                    f"--ckpt-dir {args.ckpt_dir}")
        args.ckpt_dir = path
        args.resume = True
    if args.resume and not args.ckpt_dir:
        p.error("--resume requires --ckpt-dir (or --resume CKPT)")
    if args.ckpt_keep is not None and args.ckpt_keep <= 0:
        p.error("--ckpt-keep must be >= 1 (omit the flag to keep all)")
    if args.ckpt_every < 0:
        p.error("--ckpt-every must be >= 0")
    return args


def main(argv=None):
    args = parse_args(argv)
    from repro.api.bootstrap import enable_compile_cache, ensure_host_devices
    ensure_host_devices(args.devices)
    enable_compile_cache()

    from repro.api import Experiment
    from repro.configs.base import (DGCConfig, FCCSConfig, HeadConfig,
                                    TrainConfig)
    from repro.telemetry import Tracer

    telemetry = None
    if args.trace_out or args.metrics_out:
        telemetry = Tracer(metrics_path=args.metrics_out or None)

    def finish_telemetry():
        if telemetry is None:
            return
        telemetry.record_peak_memory()
        if args.trace_out:
            telemetry.write_chrome_trace(args.trace_out)
            st = telemetry.span_stats("train.step")
            print(f"[telemetry] {st['count']} train.step spans "
                  f"({st['total_s']:.2f}s) -> {args.trace_out}")
        if args.metrics_out:
            print(f"[telemetry] metrics -> {args.metrics_out}")
        telemetry.close()

    resume = "reshard" if args.resume_reshard else bool(args.resume)

    if args.system == "paper":
        # --knn is a back-compat alias; an explicit non-default --head wins
        impl = "knn" if (args.knn and args.head == "full") else args.head
        # sampled_n below the class count so the estimator path (partial
        # draw + logQ correction) is what actually runs, smoke included
        hcfg = HeadConfig(softmax_impl=impl, backend=args.backend, knn_k=16,
                          knn_kprime=32, active_frac=0.1, rebuild_every=100,
                          sampled_n=max(64, args.classes // 4))
        fcfg = FCCSConfig(eta0=args.lr, t_warm=max(1, args.steps // 10),
                          b0=args.batch, b_min=args.batch,
                          b_max=args.batch * 8,
                          t_ini=args.steps // 4, t_final=args.steps)
        tcfg = TrainConfig(optimizer=args.optimizer, fccs=fcfg,
                           dgc=DGCConfig(enabled=args.dgc, sparsity=0.99,
                                         chunk=2048, backend=args.backend))
        exp = Experiment.from_config(
            system="paper", trunk=args.trunk, classes=args.classes,
            feat_dim=args.feat_dim, batch=args.batch, head=hcfg, train=tcfg,
            ckpt_dir=args.ckpt_dir or None, ckpt_every=args.ckpt_every,
            ckpt_keep=args.ckpt_keep or 0)
        exp.fit(args.steps, use_fccs_batch=args.fccs, resume=resume,
                telemetry=telemetry)
        acc = exp.evaluate(eval_batch=args.batch * 4)
        print(f"[train] final eval accuracy: {acc:.4f}")
        finish_telemetry()
        return 0

    impl = "knn" if (args.knn and args.head == "full") else args.head
    exp = Experiment.from_config(
        system="zoo", arch=args.arch, reduced=args.reduced,
        batch=args.batch, seq=args.seq,
        head=HeadConfig(softmax_impl=impl, backend=args.backend, knn_k=16,
                        knn_kprime=32, active_frac=0.1, rebuild_every=100),
        train=TrainConfig(optimizer=args.optimizer),
        ckpt_dir=args.ckpt_dir or None, ckpt_every=args.ckpt_every,
        ckpt_keep=args.ckpt_keep or 0)
    exp.fit(args.steps, lr=args.lr, resume=resume, telemetry=telemetry)
    acc = exp.evaluate()
    print(f"[zoo] final next-token accuracy: {acc:.4f}")
    finish_telemetry()
    return 0


if __name__ == "__main__":
    sys.exit(main())
