#!/usr/bin/env python3
"""Readings of the control and of planted faults, for setting the limits of
``bench/limits/<cell>.json``.

    python bench/control.py --workload <cell> --seeds 1 2 3

The control is the plain reference put in the program's place and computed
one precision below the configuration's: ``high`` (``Precision.HIGH``, three
bfloat16 passes on a TPU) for float32 at ``highest``.  ``--precisions`` adds
others, such as ``bf16x3`` and ``bf16``, which emulate three passes and one
on any backend.  It is compared with the
reference at the configuration's precision exactly as a run compares the
program: the same inputs from the same seed, the same sample size and the
same numbers (``bench/compare.py``).  For training cells the faults of a
step are planted in the reference too: ``half_batch`` takes the mean over
half of each batch, ``exchange_left_out`` (cells on several chips) over one
chip's share.  A step that returns its state unchanged reads 1 on
``update_gap`` by construction and needs no run.  One JSON line per seed
and reading.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Dict, Iterator, List

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import compare  # noqa: E402
import data  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402


def _serve(cell: run.Cell, seed: int, precisions) -> Iterator[Dict]:
    cfg, tr = cell.config, cell.traffic
    fr = dict(tr["frames"])
    kind, n_pool = fr.pop("kind"), int(fr.pop("pool"))
    pool = data.frames(kind, run.rng(seed, 1), n_pool, cfg, **fr)
    n = int(tr["check"]["sample"])
    pick = run.rng(seed, 3).integers(0, n_pool, n)
    params = run.make_weights(seed, cfg)
    block = int(tr["check"]["block"])
    want, _ = reference.infer(params, pool[pick], cfg,
                              cfg["matmul_precision"], block)
    for p in precisions:
        got, _ = reference.infer(params, pool[pick], cfg, p, block)
        yield {"reading": f"control_{p}", **compare.answers(got, want)}


def _seg(cell: run.Cell, seed: int, precisions) -> Iterator[Dict]:
    cfg, tr = cell.config, cell.traffic
    batch = int(tr["batch"])
    n = int(tr["check"]["batches"])
    frames = data.frames(tr["frames"]["kind"], run.rng(seed, 1),
                         batch * int(tr["frames"]["pool_batches"]), cfg)
    frames = frames[:batch * n]
    params = run.make_weights(seed, cfg)
    want, want_t = reference.infer(params, frames, cfg,
                                   cfg["matmul_precision"], batch)
    firing = len(cfg["conv_channels"]) - (0 if cfg["dense_units"] else 1)
    for p in precisions:
        got, got_t = reference.infer(params, frames, cfg, p, batch)
        worst: Dict[str, float] = {}
        for i in range(n):
            sl = slice(i * batch, (i + 1) * batch)
            for k, v in compare.masks(got[sl], want[sl], got_t[i][:firing],
                                      want_t[i][:firing]).items():
                worst[k] = max(worst.get(k, 0.0), v)
        yield {"reading": f"control_{p}", **worst}


def _train(cell: run.Cell, seed: int, precisions) -> Iterator[Dict]:
    import jax
    cfg, tr = cell.config, cell.traffic
    batch = int(tr["batch"])
    steps = int(tr["check"]["steps"])
    block = int(tr["check"]["block"])
    lr, mom = tr["train"]["lr"], tr["train"].get("momentum", 0.9)
    xs, ys = data.digits(run.rng(seed, 1),
                         batch * int(tr["frames"]["pool_batches"]),
                         tuple(cfg["input_hw"]))
    batches = [(xs[i * batch:(i + 1) * batch], ys[i * batch:(i + 1) * batch])
               for i in range(steps)]
    params = run.make_weights(seed, cfg)
    params0 = jax.tree.map(np.asarray, params)
    prec = cfg["matmul_precision"]
    want = reference.sgd_steps(params, batches, cfg, lr, mom, prec, block)

    def reading(name: str, got: List) -> Dict:
        nums = compare.training([g[0] for g in got], got[0][1], params0,
                                got[-1][2], want)
        return {"reading": name, **nums}

    for p in precisions:
        yield reading(f"control_{p}", reference.sgd_steps(
            params, batches, cfg, lr, mom, p, block))
    shares = {"half_batch": 2}
    chips = int(cell.entry["chips"])
    if chips > 1:
        shares["exchange_left_out"] = chips
    for name, k in shares.items():
        part = [(x[:batch // k], y[:batch // k]) for x, y in batches]
        yield reading(name, reference.sgd_steps(
            params, part, cfg, lr, mom, prec, min(block, batch // k)))


DRIVERS = {"serve_open_loop": _serve, "infer_closed_loop": _seg,
           "train_closed_loop": _train}


def readings(cell: run.Cell, seed: int, precisions=("high",)
             ) -> List[Dict]:
    fn = DRIVERS[cell.traffic["driver"]]
    return [{"seed": seed, **r} for r in fn(cell, seed, precisions)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precisions", nargs="+", default=["high"])
    args = ap.parse_args(argv)
    cell = run.resolve(args.workload)
    run.configure_jax()
    for seed in args.seeds:
        for r in readings(cell, seed, tuple(args.precisions)):
            print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
