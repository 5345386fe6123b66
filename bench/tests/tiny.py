"""Tiny versions of the benchmark's cells, for driving whole runs on the
CPU in tests: the same files and drivers, small sizes, the ``batched``
backend (Pallas interpret mode is a Python loop)."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import run  # noqa: E402

CPU_PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
            "hbm_bytes": 1e9, "source": "test stand-in"}

TINY = {
    "snn-mnist": dict(input_hw=[12, 12], conv_channels=[4, 8],
                      dense_units=[10], timesteps=3),
    "snn-seg": dict(input_hw=[8, 12], conv_channels=[4, 1],
                    dense_units=[], timesteps=3),
}


def resolve(name: str) -> run.Cell:
    """A cell of ``BENCHMARK.json``, or the serving cell, whose entries wait
    in ``data/serving_cell.json`` until its tail can be measured steadily
    (PERF.md): its driver, mix, limits and readers stay under test."""
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    extra = run.load_json(Path(__file__).parent / "data"
                          / "serving_cell.json")
    for key, entries in extra.items():
        bench[key] = bench[key] + entries
    return run.resolve(name, bench=bench)


def tiny_cell(name: str, seconds_trace: float = 0.5,
              data: int = 1) -> run.Cell:
    """The named cell cut to a test's size; ``data`` > 1 trains
    data-parallel over that many devices."""
    cell = copy.deepcopy(resolve(name))
    if data > 1:
        cell.entry["chips"] = data
        cell.traffic["train"]["mesh"] = {"data": data}
    cell.config.update(TINY[cell.entry["config"]])
    tr = cell.traffic
    tr["trace_after_s"], tr["trace_seconds"] = 0.1, seconds_trace
    if tr["driver"] == "serve_open_loop":
        tr["frames"]["pool"] = 64
        tr["arrivals"]["rate_per_s"] = 100
        tr["serve"].update(backend="batched", schedule_mode=None,
                           max_batch=8, buckets=[4, 8])
        tr["warm_requests"] = 8
        tr["check"] = {"sample": 16, "block": 8}
    elif tr["driver"] == "infer_closed_loop":
        tr["execution"]["backend"] = "batched"
        tr["batch"] = 2
        tr["frames"]["pool_batches"] = 3
    elif tr["driver"] == "train_closed_loop":
        tr["train"]["backend"] = "batched"
        tr["batch"] = 8 * data
        tr["frames"]["pool_batches"] = 5
        tr["check"]["block"] = 8
    return cell


def run_tiny(name: str, seed: int = 1, seconds: float = 1.0,
             trace: int = 0, capsys=None, data: int = 1):
    """Run the tiny cell once; returns (exit code, result dict or None)."""
    import json
    configure = run.configure_jax
    run.configure_jax = lambda: None     # no persistent cache for CPU runs
    try:
        code = run.main(["--workload", name, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", str(trace)],
                        require_tpu=False, cell=tiny_cell(name, data=data),
                        peak=CPU_PEAK)
    finally:
        run.configure_jax = configure
    result = None
    if capsys is not None:
        out = capsys.readouterr().out.strip().splitlines()
        result = json.loads(out[-1]) if out else None
    return code, result
