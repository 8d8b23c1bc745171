"""Find the knee of a serving cell: the same open-loop window as the
benchmark at each of a list of offered rates, in one process on the chip.

    python3 bench/sweep.py --workload full.serve.poisson \\
        --rates 1000,2000,3000 --seconds 5 --seed 1

Prints one JSON line per rate: offered and completed rate, latency
percentiles, requests not done in the window, mean batch occupancy and
queue wait. The knee is the highest rate whose completed rate keeps up
with the offered one and whose tail does not grow with the run. The
cell's traffic file then takes a fixed rate below it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None) -> int:
    from bench import run as harness

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)
    root = harness.ROOT
    spec = harness.cell_spec(root, args.workload)
    sys.path.insert(0, os.path.join(root, "src"))
    harness.setup_jax()
    devs = harness.find_devices(spec["chips"])
    run = harness.Run(root, spec, args.seed, args.seconds, False, devs)
    import jax
    import numpy as np

    from bench.kinds import serve
    from bench.traffic import generate as gen
    from repro.telemetry import Tracer

    cfg, trf = run.config, run.traffic
    classes = cfg["classes_per_chip"] * spec["chips"]
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        for rate in [float(x) for x in args.rates.split(",")]:
            tracer = Tracer()
            exp, eng = serve.build(run, tracer)
            due = gen.poisson_arrivals(args.seed, rate, args.seconds)
            q, _ = gen.queries(args.seed, len(due), classes=classes,
                               d=cfg["d"], noise=trf["noise"])
            eng.warmup(q[0])
            sizes = []
            step_fn = eng.step_fn

            def recorded(qs, n, step_fn=step_fn, sizes=sizes):
                sizes.append(n)
                return step_fn(qs, n)
            eng.step_fn = recorded
            lat, ids, _, window_s, (n_b, wait_s) = serve.serve_window(
                run, eng, q, due, args.seconds)
            done = np.isfinite(lat)
            cens = np.where(done, lat, window_s - due) * 1e3
            occ = eng.occupancies[:n_b]
            print(json.dumps({
                "rate": rate, "done_per_s": float(done.sum()) / window_s,
                "p50_ms": float(np.percentile(cens, 50)),
                "p95_ms": float(np.percentile(cens, 95)),
                "p99_ms": float(np.percentile(cens, 99)),
                "not_done": int((~done).sum()), "batches": n_b,
                "mean_occupancy": float(np.mean(occ)) if occ else 0.0,
                "queue_wait_ms": 1e3 * wait_s / max(1, sum(sizes[:n_b])),
                "time": time.time()}), flush=True)
            del exp, eng
    return 0


if __name__ == "__main__":
    sys.exit(main())
