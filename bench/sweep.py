#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: offer its traffic at a series
of fixed rates to one live server and report, for each, the rate answered
inside the window, the backlog when the window closed, and the latency tail.

    python bench/sweep.py --workload mnist-serve-poisson --seed 1 \
        --seconds 8 --rates 600 800 1000 1200

The knee is the highest rate whose backlog stays flat; the cell offers a
fixed fraction of it, written into its traffic file.  Not a benchmark run:
it prints one JSON line per rate.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import data  # noqa: E402
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = run.resolve(args.workload)
    run.configure_jax()
    drv = run.load_module(run.BENCH / "drivers" / "serve_open_loop.py")
    from repro.api import ServeSpec, Session
    cfg, tr = cell.config, cell.traffic
    fr = dict(tr["frames"])
    kind, n_pool = fr.pop("kind"), int(fr.pop("pool"))
    pool = data.frames(kind, run.rng(args.seed, 1), n_pool, cfg, **fr)
    spec = ServeSpec(surrogate_kind=cfg["surrogate_kind"],
                     surrogate_alpha=cfg["surrogate_alpha"],
                     **{k: tuple(v) if isinstance(v, list) else v
                        for k, v in tr["serve"].items()})
    sess = Session(run.program_config(cfg), spec,
                   params=run.make_weights(args.seed, cfg))
    live = sess.serve_forever()
    for h in [live.submit(pool[i % n_pool]) for i in range(256)]:
        h.result(timeout=120)
    for i, rate in enumerate(args.rates):
        due = drv.arrivals(run.rng(args.seed, 10 + i),
                           {"process": "poisson", "rate_per_s": rate},
                           args.seconds)
        col = drv.Collector()
        handles = []
        t0 = time.perf_counter()
        for k, d in enumerate(due):
            wait = t0 + d - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            h = live.submit(pool[k % n_pool])
            handles.append(h)
            col.add(k, h)
        t_close = time.perf_counter()
        backlog = sum(1 for h in handles if not h.done())
        answered_in = sum(1 for t in list(col.finished.values())
                          if t <= t_close)
        col.close(120)
        lat = np.asarray([col.finished[k] - (t0 + d)
                          for k, d in enumerate(due) if k in col.finished])
        print(json.dumps({
            "rate_offered": rate, "requests": len(due),
            "answered_per_s": answered_in / (t_close - t0),
            "backlog_at_close": backlog,
            "p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "p95_ms": float(np.percentile(lat, 95)) * 1e3,
            "drain_s": time.perf_counter() - t_close}), flush=True)
    live.shutdown(timeout=120)
    return 0


if __name__ == "__main__":
    sys.exit(main())
