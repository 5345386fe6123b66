"""Whole runs of the harness on the CPU: it refuses to measure without a TPU,
and the rest of a run — inputs, the timed loop, the comparison and the
result line — works on tiny cells."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402
import tiny  # noqa: E402


def test_no_tpu_exits_nonzero_naming_it():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "mnist-train-b64", "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_unknown_device_kind_is_an_error(monkeypatch):
    monkeypatch.setattr(run, "load_json", lambda path: (
        {} if path.name == "peaks.json" else json.loads(path.read_text())))
    args = run.parse_args(["--workload", "mnist-train-b64", "--seed", "1",
                           "--seconds", "1"])
    with pytest.raises(run.BenchError, match="not in bench/peaks.json"):
        run.prepare(args, require_tpu=False,
                    cell=tiny.tiny_cell("mnist-train-b64"))


def test_peak_table_has_the_v5e_row():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    row = peaks["TPU v5 lite"]
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in row["source"]


def test_seeds_beyond_32_bits_give_the_same_inputs():
    import numpy as np
    big = 2 ** 31 + 12345
    cfg = tiny.tiny_cell("mnist-train-b64").config
    a = run.make_weights(big, cfg)["conv"][0]["w"]
    b = run.make_weights(big, cfg)["conv"][0]["w"]
    c = run.make_weights(big + 1, cfg)["conv"][0]["w"]
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.array_equal(run.rng(big, 1).integers(0, 1 << 30, 8),
                          run.rng(big, 1).integers(0, 1 << 30, 8))


def test_compile_cache_is_the_checkouts_and_keeps_every_entry():
    import jax
    run.configure_jax()
    assert jax.config.jax_compilation_cache_dir == str(run.CACHE_DIR)
    assert jax.config.jax_compilation_cache_max_size == -1


def test_stall_note_names_the_longest_step_and_the_host_counters():
    before = run.host_counters()
    assert "invol_switches" in before
    note = run.stall_note([1.1, 1.2, 2.0, 2.1], 1.0, before)
    assert "longest step 0.8000 s ending 1.00 s into the window" in note
    assert "median 0.1000 s" in note
    assert "invol_switches" in note


@pytest.mark.parametrize("cell", ["mnist-train-b64", "seg-stream-b8",
                                  "mnist-serve-poisson"])
def test_tiny_cell_runs_correct(cell, capsys):
    code, result = tiny.run_tiny(cell, seed=2 ** 32 + 9, seconds=0.5,
                                 capsys=capsys)
    assert code == 0
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    names = set(result["metrics"])
    assert "setup_s" in names and len(names) == 2
    for m in result["metrics"].values():
        assert m["value"] > 0


def test_traced_run_without_device_planes_gives_no_result(capsys):
    code, result = tiny.run_tiny("seg-stream-b8", seed=5, seconds=1.0,
                                 trace=1, capsys=capsys)
    # the CPU trace has no TPU plane: no device metric is made up from it
    assert code == 2 and result is None
