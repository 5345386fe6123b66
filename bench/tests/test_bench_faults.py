"""A run whose timed path is broken underneath comes out not correct: once
for each fault its cell can have.  The chip is not looked for; the rest of
the run (inputs, the window, the comparison) is the harness's own, on tiny
cells."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(HERE))

import tiny  # noqa: E402


def _broken_run(cell, capsys):
    code, result = tiny.run_tiny(cell, seed=2 ** 33 + 17, seconds=0.5,
                                 capsys=capsys)
    assert code == 0
    return result


def test_step_that_returns_its_state_unchanged(monkeypatch, capsys):
    import jax
    import jax.numpy as jnp
    from repro.api import Session
    orig = Session.train_step

    def frozen(self, x, y):
        params = self.params
        loss = orig(self, x, y)
        self.params = params
        self._mom = jax.tree.map(jnp.zeros_like, params)
        return loss

    monkeypatch.setattr(Session, "train_step", frozen)
    result = _broken_run("mnist-train-b64", capsys)
    assert result["correct"] is False
    assert result["checks"]["update_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out(monkeypatch, capsys):
    from repro.api import Session
    orig = Session.train_step
    monkeypatch.setattr(
        Session, "train_step",
        lambda self, x, y: orig(self, x[:len(x) // 2], y[:len(y) // 2]))
    result = _broken_run("mnist-train-b64", capsys)
    assert result["correct"] is False
    assert result["checks"]["loss_gap"]["value"] > \
        result["checks"]["loss_gap"]["limit"]


def test_served_answer_altered_where_produced(monkeypatch, capsys):
    from repro.serving.engine import ServingEngine
    orig = ServingEngine._finish_request

    def altered(self, r, logits_row):
        row = np.array(logits_row, copy=True)
        row[..., 0] += 0.01
        return orig(self, r, row)

    monkeypatch.setattr(ServingEngine, "_finish_request", altered)
    result = _broken_run("mnist-serve-poisson", capsys)
    assert result["correct"] is False
    assert result["checks"]["rows_off_share"]["value"] == 1.0


def test_mask_altered_where_produced(monkeypatch, capsys):
    from repro.serving.engine import ServingEngine
    orig = ServingEngine.infer

    def altered(self, frames, bucket=None):
        out = orig(self, frames, bucket)
        return out._replace(logits=out.logits + 1e-3)

    monkeypatch.setattr(ServingEngine, "infer", altered)
    result = _broken_run("seg-stream-b8", capsys)
    assert result["correct"] is False


_MESH = """
import json, sys
sys.path.insert(0, {here!r}); sys.path.insert(0, {bench!r})
import tiny
if {broken}:
    from repro.dist.runner import MeshRunner
    orig = MeshRunner.train_step
    # each chip updates from its own rows: the exchange is left out
    MeshRunner.train_step = lambda self, p, m, x, y: orig(
        self, p, m, x[:len(x) // 4], y[:len(y) // 4])
code = tiny.run_tiny("mnist-train-b64", seed=2 ** 32 + 3, seconds=0.5,
                     data=4)[0]
sys.exit(code)
"""


@pytest.mark.parametrize("broken", [False, True])
def test_four_chip_exchange_left_out(broken):
    """The training driver on a data-parallel mesh (the four-chip path a
    later cell will use) catches an update that skips the exchange."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run(
        [sys.executable, "-c", _MESH.format(here=str(HERE),
                                            bench=str(BENCH),
                                            broken=broken)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["device"]["count"] == 4
    assert result["correct"] is (not broken), result["checks"]
