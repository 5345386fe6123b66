"""Closed-loop inference: ``Session.infer`` back to back on batches of frames
from a pool drawn from the seed — a fixed number of streams, each delivering
its next frame when the previous batch is answered.

Traffic parameters (``bench/traffic/<mix>.json``):

  frames      {"kind": <bench.data frame kind>, "pool_batches": n}
  batch       frames per call (one per stream)
  execution   ExecutionSpec fields (backend, ...)
  check       {"batches": calls compared, "block": reference rows per call}

A call counts once its logits and spike counts are on the host.  After the
window a sample of the window's calls, drawn from the seed, is compared with
the plain reference: every mask logit and every layer's spike total.
"""
from __future__ import annotations

import gc
import time

import numpy as np

import compare
import data
import reference
from run import (Check, Outcome, close_window, make_weights, memory_peak,
                 host_counters, open_window, rng, span, stall_note)


def drive(run) -> Outcome:
    from repro.api import ExecutionSpec, Session
    cfg = run.cell.config
    tr = run.cell.traffic
    batch = int(tr["batch"])
    n_pool = int(tr["frames"]["pool_batches"])
    pool = data.frames(tr["frames"]["kind"], rng(run.seed, 1),
                       batch * n_pool, cfg)
    params = make_weights(run.seed, cfg)
    spec = ExecutionSpec(surrogate_kind=cfg["surrogate_kind"],
                         surrogate_alpha=cfg["surrogate_alpha"],
                         **tr["execution"])
    sess = Session(run.program_cfg, spec, params=params)

    def call(x):
        out = sess.infer(x)
        return (np.asarray(out.logits),
                [float(np.asarray(t)) for t in out.spike_totals])

    for i in range(min(2, n_pool)):          # compile and warm
        call(pool[i * batch:(i + 1) * batch])

    outputs = []
    compiles0 = run.stats.compiles
    host0 = host_counters()
    t0 = open_window()
    t_end = t0 + run.seconds
    now, k = t0, 0
    call_ends = []
    while now < t_end:
        run.profiler.poll(now - t0)
        j = k % n_pool
        with span("infer", call=k):
            outputs.append((j, call(pool[j * batch:(j + 1) * batch])))
        k += 1
        now = time.perf_counter()
        call_ends.append(now)
    elapsed = now - t0
    steadiness = stall_note(call_ends, t0, host0)
    run.profiler.stop()
    close_window()
    compiles = run.stats.compiles - compiles0
    peak = memory_peak(run.devices)
    del sess
    gc.collect()

    chk = tr["check"]
    sample = sorted(rng(run.seed, 4).choice(
        len(outputs), size=min(int(chk["batches"]), len(outputs)),
        replace=False))
    frames = np.concatenate([pool[outputs[s][0] * batch:
                                  (outputs[s][0] + 1) * batch]
                             for s in sample])
    want, want_totals = reference.infer(params, frames, cfg,
                                        cfg["matmul_precision"], batch)
    firing = len(cfg["conv_channels"]) - (0 if cfg["dense_units"] else 1)
    worst = {}
    for i, s in enumerate(sample):
        logits, totals = outputs[s][1]
        for name, v in compare.masks(
                logits, want[i * batch:(i + 1) * batch], totals[:firing],
                want_totals[i][:firing]).items():
            worst[name] = max(worst.get(name, 0.0), v)
    checks = [Check(n, v, float(run.cell.limits[n]))
              for n, v in worst.items()]
    fps = k * batch / elapsed
    return Outcome(
        attempted=k, failed=0, window_start=t0,
        end_to_end={"infer_fps": fps}, checks=checks,
        memory_peak_bytes=peak,
        layer={"calls": k, "window_s": elapsed, "batch": batch,
               "infer_fps": fps, "span": "infer"},
        notes=[f"infer: {k} calls of {batch} frames in {elapsed!r} s; "
               f"{len(sample)} calls compared", steadiness],
        compiles_in_window=compiles)
