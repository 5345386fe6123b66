#!/usr/bin/env python3
"""Readings of the program's numbers over many seeds, in one process, for
setting the lower end of each limit in ``bench/limits/<cell>.json``.

    python bench/calibrate.py --workload <cell> --seconds 2 --seeds 1 2 3

Each seed is a whole run of the cell's driver (its set-up, a short window at
the cell's own load, the comparison with the reference) without the result
line; compiled programs are shared across seeds.  One JSON line per seed
with every number compared.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = run.resolve(args.workload)
    driver = run.load_module(run.BENCH / "drivers"
                             / f"{cell.traffic['driver']}.py")
    for seed in args.seeds:
        r = run.prepare(run.parse_args(
            ["--workload", args.workload, "--seed", str(seed),
             "--seconds", str(args.seconds)]), cell=cell)
        out = driver.drive(r)
        print(json.dumps({"seed": seed,
                          **{c.name: c.value for c in out.checks},
                          **out.end_to_end}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
