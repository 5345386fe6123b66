"""Open-loop serving: requests arrive on a schedule drawn from the seed and
are submitted to the live threaded engine (``Session.serve_forever``)
whether or not earlier ones have finished.

Traffic parameters (``bench/traffic/<mix>.json``):

  frames     {"kind": <bench.data frame kind>, "pool": n, ...kind options}
  arrivals   {"process": "poisson", "rate_per_s": r, "schedule_seed": s}:
             one recorded schedule per mix, the same in every run, so that
             the run's seed changes the frames and weights but not when
             requests are due
  serve      ServeSpec fields (backend, schedule_mode, lanes, buckets, ...)
  warm_requests   requests served before the window (set-up)
  check      {"sample": rows compared, "block": reference rows per call}
  drain_s    how long after the window to wait for the last answers
  trace_after_s   when a traced run starts its profiler; it stops when the
             window closes, since stopping stalls the generator

Each request is timed from the moment it was due until its logits are on
the host (a collector thread polls the handles every ``POLL_S``).  Python's
cyclic garbage collector is off inside the window: the generator, the
collector and the engine share one interpreter, and a full collection over
the window's request objects (about 0.1 s) stalls the generator and every
lane at once.  Requests
due inside the window count; one that errs, or has no answer ``drain_s``
after the window closed, counts as failed.
"""
from __future__ import annotations

import gc
import threading
import time
from typing import Dict, List

import numpy as np

import compare
import data
import reference
from run import (Check, Outcome, close_window, make_weights, memory_peak,
                 open_window, rng, span)

POLL_S = 0.0005


def arrivals(g: np.random.Generator, spec: Dict, seconds: float
             ) -> np.ndarray:
    """Due times (seconds from the window's start) inside the window."""
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    rate = float(spec["rate_per_s"])
    n = int(rate * seconds * 1.2) + 64
    due = np.cumsum(g.exponential(1.0 / rate, n))
    while due[-1] < seconds:
        due = np.concatenate([due, due[-1] + np.cumsum(
            g.exponential(1.0 / rate, n))])
    return due[due < seconds]


class Collector:
    """Stamps each handle when it resolves.  One thread polls the
    outstanding handles; ``done()`` is the only call it makes on them."""

    def __init__(self):
        self._lock = threading.Lock()
        self._new: List = []
        self.finished: Dict[int, float] = {}
        self._closed = False
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="bench-collector")
        self._thread.start()

    def add(self, k: int, handle) -> None:
        with self._lock:
            self._new.append((k, handle))

    def _loop(self) -> None:
        pending: List = []
        while True:
            with self._lock:
                pending.extend(self._new)
                self._new = []
                closed = self._closed
            now = time.perf_counter()
            still = []
            for k, h in pending:
                if h.done():
                    self.finished[k] = now
                else:
                    still.append((k, h))
            pending = still
            if closed and not pending:
                return
            time.sleep(POLL_S)

    def close(self, timeout: float) -> bool:
        """Stop taking handles and wait for the outstanding ones; False when
        some had no answer within ``timeout``."""
        with self._lock:
            self._closed = True
        self._thread.join(timeout)
        return not self._thread.is_alive()


def drive(run) -> Outcome:
    from repro.api import ServeSpec, Session
    cfg = run.cell.config
    tr = run.cell.traffic
    fr = dict(tr["frames"])
    kind, n_pool = fr.pop("kind"), int(fr.pop("pool"))
    pool = data.frames(kind, rng(run.seed, 1), n_pool, cfg, **fr)
    due = arrivals(rng(int(tr["arrivals"]["schedule_seed"])), tr["arrivals"],
                   run.seconds)
    pick = rng(run.seed, 3).integers(0, n_pool, len(due))

    params = make_weights(run.seed, cfg)
    spec = ServeSpec(surrogate_kind=cfg["surrogate_kind"],
                     surrogate_alpha=cfg["surrogate_alpha"],
                     trace=run.trace, trace_capacity=1 << 20,
                     **{k: tuple(v) if isinstance(v, list) else v
                        for k, v in tr["serve"].items()})
    sess = Session(run.program_cfg, spec, params=params)
    live = sess.serve_forever()                # compiles every bucket
    warm = [live.submit(pool[i % n_pool])
            for i in range(int(tr.get("warm_requests", 0)))]
    for h in warm:
        h.result(timeout=120)

    collector = Collector()
    handles, submitted_at = [], np.zeros(len(due))
    compiles0 = run.stats.compiles
    t0 = open_window()
    gc.disable()
    for k, d in enumerate(due):
        wait = t0 + d - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        run.profiler.poll(time.perf_counter() - t0)
        submitted_at[k] = time.perf_counter()
        with span("submit", k=k):
            h = live.submit(pool[pick[k]])
        handles.append(h)
        collector.add(k, h)
    t_close = time.perf_counter()
    run.profiler.stop()         # stopping stalls the host: not before here
    drained = collector.close(float(tr.get("drain_s", 60.0)))
    gc.enable()
    close_window()
    compiles = run.stats.compiles - compiles0
    peak = memory_peak(run.devices)
    live.shutdown(timeout=120)
    events = [e.to_dict() for e in live.trace().events()] if run.trace \
        else []

    failed, lat, got = 0, [], {}
    for k, h in enumerate(handles):
        if k not in collector.finished or h.exception(timeout=0) is not None:
            failed += 1
            continue
        lat.append(collector.finished[k] - (t0 + due[k]))
        got[k] = np.asarray(h.result(timeout=0))
    lat = np.asarray(lat)
    late = submitted_at - (t0 + due)
    rid_of = {h.rid: k for k, h in enumerate(handles)}
    del sess, live, handles, warm
    gc.collect()

    chk = tr["check"]
    done = sorted(got)
    sample = sorted(rng(run.seed, 4).choice(
        done, size=min(int(chk["sample"]), len(done)), replace=False)) \
        if done else []
    checks = []
    if sample:
        want, _ = reference.infer(params, pool[pick[sample]], cfg,
                                  cfg["matmul_precision"],
                                  int(chk["block"]))
        numbers = compare.answers(np.stack([got[k] for k in sample]), want)
        checks = [Check(k, v, float(run.cell.limits[k]))
                  for k, v in numbers.items()]
    p95 = float(np.percentile(lat, 95)) * 1e3 if len(lat) else float("nan")
    notes = [
        f"serve: {len(due)} requests due in {run.seconds} s "
        f"({len(due) / run.seconds!r}/s offered), {len(lat)} answered, "
        f"{failed} failed, drained={drained}; latency p50 "
        f"{float(np.percentile(lat, 50)) * 1e3 if len(lat) else 0!r} ms, "
        f"p95 {p95!r} ms over {len(lat)} requests; generator lateness p95 "
        f"{float(np.percentile(late, 95)) * 1e3!r} ms, max "
        f"{float(late.max()) * 1e3 if len(late) else 0!r} ms; "
        f"{len(sample)} answers compared"]
    return Outcome(
        attempted=len(due), failed=failed, window_start=t0,
        end_to_end={"latency_p95_ms": p95},
        checks=checks, memory_peak_bytes=peak,
        layer={"events": events, "rid_of": rid_of,
               "submitted_at": submitted_at, "t0": t0,
               "window_s": t_close - t0,
               "profile_marks": (getattr(run.profiler, "t_start", None),
                                 getattr(run.profiler, "t_stop", None)),
               "buckets": tuple(tr["serve"]["buckets"])},
        notes=notes, compiles_in_window=compiles)
