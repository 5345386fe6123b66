"""Benchmark harness — one module per paper table/figure (+ kernel micro
benches and the dry-run roofline summary).  Prints ``name,us_per_call,
derived`` CSV as required.

Modes:
  (default)          every section, quick-sized workloads
  --full             every section, full-sized workloads
  --quick            kernel + model-forward section only, and write
                     ``BENCH_kernels.json`` (name -> us_per_call/derived)
                     so successive PRs accumulate a perf trajectory
                     (consumed by scripts/smoke.sh).

A section that raises is reported as a ``<section>/ERROR`` row and the
other sections still run, but the harness then exits non-zero and writes
no artifact.
"""
from __future__ import annotations

import json
import os
import sys

BENCH_JSON = "BENCH_kernels.json"


def _roofline_rows():
    path = "results/dryrun.jsonl"
    if not os.path.exists(path):
        return [{"name": "roofline/missing", "us_per_call": 0.0,
                 "derived": "run launch/dryrun.py --all first"}]
    from repro.launch.roofline import load_rows
    rows = []
    for r in load_rows(path):
        rows.append({
            "name": f"roofline/{r.arch}/{r.shape}/{r.chips}/{r.profile}",
            "us_per_call": r.step_s * 1e6,
            "derived": f"bound={r.bound};frac={r.roofline_fraction:.2f};"
                       f"compute_s={r.compute_s:.3e};collective_s={r.collective_s:.3e}",
        })
    return rows


def main() -> None:
    quick = "--quick" in sys.argv
    full = "--full" in sys.argv
    from repro.runtime.compile_cache import configure_compile_cache
    configure_compile_cache()
    from benchmarks import bench_dist, bench_kernels
    if quick:
        sections = [
            ("kernels", lambda: bench_kernels.run()),
            # sharded train rows: in process on an accelerator host, in a
            # child with 4 fake host devices on a CPU one (the
            # device-count flag must precede jax init)
            ("dist", lambda: bench_dist.rows_for("train", True)),
        ]
    else:
        from benchmarks import (fig6_aprc, fig7_balance, table1_throughput,
                                table2_resources)
        sections = [
            ("fig6", lambda: fig6_aprc.run()),
            ("fig7", lambda: fig7_balance.run(quick=not full)),
            ("table1", lambda: table1_throughput.run(quick=not full)),
            ("table2", lambda: table2_resources.run()),
            ("kernels", lambda: bench_kernels.run()),
            ("dist", lambda: bench_dist.rows_for("train", not full)),
            ("roofline", _roofline_rows),
        ]
    collected, failed = [], []
    print("name,us_per_call,derived")
    for tag, fn in sections:
        try:
            for r in fn():
                collected.append(r)
                print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}",
                      flush=True)
        except Exception as e:  # noqa: BLE001 — report, run the rest, fail
            failed.append(tag)
            print(f"{tag}/ERROR,0.0,{type(e).__name__}:{e}", flush=True)
    if failed:
        print(f"# failed sections: {','.join(failed)}", file=sys.stderr)
        sys.exit(1)
    if quick:
        payload = {r["name"]: {"us_per_call": round(r["us_per_call"], 1),
                               "derived": r["derived"]}
                   for r in collected}
        with open(BENCH_JSON, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {BENCH_JSON} ({len(payload)} entries)", flush=True)


if __name__ == "__main__":
    main()
