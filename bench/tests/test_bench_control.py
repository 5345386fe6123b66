"""The control — the reference put in the program's place one precision
below the configuration's — comes out not correct against each cell's
limits.  On the CPU ``Precision.HIGH`` computes float32, so the test runs the
emulated three-pass bfloat16 (``bf16x3``) at the published widths with a
smaller sample; the chip's readings at the cell's own size are in PERF.md
(``python3 bench/control.py``)."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE))

import control  # noqa: E402
import run  # noqa: E402
import tiny  # noqa: E402


def _fails(cell: run.Cell, reading: dict) -> bool:
    return any(reading[k] > float(v) for k, v in cell.limits.items())


def _small(name: str) -> run.Cell:
    cell = copy.deepcopy(tiny.resolve(name))
    tr = cell.traffic
    if tr["driver"] == "serve_open_loop":
        tr["check"].update(sample=256, block=64)
    elif tr["driver"] == "infer_closed_loop":
        tr["batch"], tr["check"]["batches"] = 2, 2
    else:
        tr["batch"], tr["check"]["block"] = 16, 16
    return cell


@pytest.mark.parametrize("name", ["mnist-serve-poisson", "seg-stream-b8",
                                  "mnist-train-b64"])
def test_emulated_three_pass_control_fails(name):
    cell = _small(name)
    for r in control.readings(cell, 2 ** 32 + 21, ("bf16x3",)):
        if r["reading"].startswith("control"):
            assert _fails(cell, r), r


def test_planted_training_faults_fail():
    cell = _small("mnist-train-b64")
    faults = [r for r in control.readings(cell, 2 ** 32 + 22, ())
              if not r["reading"].startswith("control")]
    assert [r["reading"] for r in faults] == ["half_batch"]
    assert all(_fails(cell, r) for r in faults)
