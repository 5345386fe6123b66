"""Closed-loop training: ``Session.train_step`` back to back on batches from a
pool drawn from the seed.

Traffic parameters (``bench/traffic/<mix>.json``):

  frames        {"kind": <bench.data frame kind>, "pool_batches": n}
  batch         global batch of one step
  train         TrainSpec fields: backend, lr, momentum, mesh
  check         {"steps": 3, "block": rows per reference call}

Set-up builds one Session, drives it through its first ``check.steps`` steps
on distinct batches (which compiles and warms the step) and keeps the loss of
each, the optimizer's momentum after the first (the first gradient, since
momentum starts at zero) and the parameters before the first and after the
last.  The same Session then runs the window.  After the window the
reference repeats those steps from the same weights and batches.
"""
from __future__ import annotations

import gc
import time
from typing import List

import numpy as np

import compare
import data
import reference
from run import (Check, Outcome, close_window, make_weights, memory_peak,
                 host_counters, open_window, rng, span, stall_note)


def _host(tree):
    import jax
    return jax.tree.map(lambda a: np.array(a, dtype=np.float32, copy=True),
                        tree)


def drive(run) -> Outcome:
    import jax
    from repro.api import Session, TrainSpec
    cfg = run.cell.config
    tr = run.cell.traffic
    batch = int(tr["batch"])
    n_pool = int(tr["frames"]["pool_batches"])
    g = rng(run.seed, 1)
    xs, ys = data.digits(g, batch * n_pool, tuple(cfg["input_hw"]))
    batches = [(xs[i * batch:(i + 1) * batch], ys[i * batch:(i + 1) * batch])
               for i in range(n_pool)]

    params = make_weights(run.seed, cfg)
    params0 = _host(params)
    spec = TrainSpec(surrogate_kind=cfg["surrogate_kind"],
                     surrogate_alpha=cfg["surrogate_alpha"], **tr["train"])
    sess = Session(run.program_cfg, spec, params=params)
    step = sess.train_step

    n_check = int(tr["check"]["steps"])
    losses: List[float] = []
    grad1 = None
    for i in range(n_check):
        losses.append(float(step(*batches[i])))
        if i == 0:
            grad1 = _host(sess._mom)          # momentum starts at zero
    params_n = _host(sess.params)

    compiles0 = run.stats.compiles
    host0 = host_counters()
    t0 = open_window()
    t_end = t0 + run.seconds
    steps, i = 0, n_check
    bad_loss = 0
    now = t0
    step_ends: List[float] = []
    while now < t_end:
        run.profiler.poll(now - t0)
        x, y = batches[i % n_pool]
        with span("train_step", step=steps):
            loss = step(x, y)
        if not np.isfinite(loss):
            bad_loss += 1
        steps += 1
        i += 1
        now = time.perf_counter()
        step_ends.append(now)
    elapsed = now - t0
    steadiness = stall_note(step_ends, t0, host0)
    run.profiler.stop()
    close_window()
    compiles = run.stats.compiles - compiles0
    peak = memory_peak(run.devices)
    del sess, step, params
    gc.collect()

    ref = reference.sgd_steps(
        jax.tree.map(jax.numpy.asarray, params0),
        [(jax.numpy.asarray(x), jax.numpy.asarray(y))
         for x, y in batches[:n_check]],
        cfg, tr["train"]["lr"], tr["train"].get("momentum", 0.9),
        precision=cfg["matmul_precision"], block=int(tr["check"]["block"]))
    numbers = compare.training(losses, grad1, params0, params_n, ref)
    checks = [Check(k, v, float(run.cell.limits[k]))
              for k, v in numbers.items()]
    fps = steps * batch / elapsed
    return Outcome(
        attempted=steps, failed=bad_loss, window_start=t0,
        end_to_end={"train_fps": fps},
        checks=checks, memory_peak_bytes=peak,
        layer={"steps": steps, "window_s": elapsed, "batch": batch,
               "train_fps": fps, "span": "train_step"},
        notes=[f"train: {steps} steps of {batch} in {elapsed!r} s; "
               f"checked losses {losses!r} vs reference "
               f"{[r[0] for r in ref]!r}", steadiness],
        compiles_in_window=compiles)
