"""Serving load benchmark: open-loop Poisson arrivals against the
continuous-batching engine (repro.serving).

Three sections, each a ``name,us_per_call,derived`` row family:

  serve/admission/*    CBWS vs FIFO request binning on a skewed synthetic
                       workload (adversarial arrival order) — the measured
                       request-level balance ratio must favor CBWS
  serve/load/*         open-loop Poisson arrivals at several offered loads
                       (fractions of measured capacity): p50/p99 latency,
                       FPS, queue depth, energy/image via the perf model
  serve/throughput/*   engine pipelined throughput vs the old synchronous
                       per-batch-blocking loop at equal batch size
  serve/threaded/*     wall-clock FPS of the worker-thread engine (2 lanes)
                       vs the single-thread virtual-clock engine draining
                       the same skewed burst — real concurrency, measured
                       end to end (compiles excluded via pre-epoch warmup)
  serve/forever/*      live submission (Session.serve_forever + per-request
                       futures, requests submitted WHILE the engine runs)
                       vs the same burst pre-submitted and drained by
                       run() — the live path must not tax throughput/p99
  serve/faults/*       supervised recovery under seeded chaos
                       (runtime.faults): the same burst fault-free vs under
                       a FaultPlan that crashes every lane once mid-epoch
                       plus a submit storm, restart_budget=2 — restarts,
                       time-to-recovery, and post-recovery FPS vs the
                       fault-free baseline
  serve/chunked/*      timestep-chunked continuous batching under a bursty
                       3x-overload Poisson trace (deterministic virtual
                       clock + injected per-timestep service model):
                       served p99 of chunk-boundary rescheduling with
                       mid-flight SLO degrade vs whole-T dispatch, plus a
                       no-SLO burst asserting bit-identical logits between
                       the two engines (the chunk-parity contract)

Engines are constructed exclusively through the ``repro.api`` facade
(``ServeSpec`` -> ``Session``); ``--quick`` shrinks the workload and writes
``BENCH_serving.json`` (same name -> {us_per_call, derived} shape as
BENCH_kernels.json) so every PR leaves a serving-trajectory data point
alongside the kernel one (scripts/smoke.sh runs this).
"""
from __future__ import annotations

import dataclasses
import json
import os
import statistics
import subprocess
import sys
import time

import jax
import numpy as np

BENCH_JSON = "BENCH_serving.json"

# Lane-level (inter-op) parallelism is what the serve/threaded/* section
# measures: it runs in a SUBPROCESS with XLA CPU pinned to one intra-op
# thread, so each serving lane maps onto one execution unit — the
# request-level analogue of the paper's SPE lanes (otherwise XLA's intra-op
# pool absorbs every core and lane threads only contend).  XLA flags are
# frozen at first use, and the other sections' historical numbers are
# tracked unpinned, so the pinning must not leak into this process.
THREADED_XLA_FLAGS = ("--xla_cpu_multi_thread_eigen=false"
                      " intra_op_parallelism_threads=1")


def _skewed_frames(n: int, cfg, sigma: float = 1.2, seed: int = 0):
    """Digit frames with lognormal per-request intensity skew — the
    request-granularity analogue of the paper's Fig. 2b channel skew
    (spike workloads spread over orders of magnitude)."""
    from repro.data.synthetic import mnist_like
    rng = np.random.default_rng(seed)
    imgs, _ = mnist_like(n, seed=seed)
    scale = rng.lognormal(-0.5, sigma, (n, 1, 1, 1))
    return np.clip(imgs * scale, 0.0, 1.0).astype(np.float32)


def _engine(params, cfg, policy, lanes, max_batch, fault_hook=None):
    from repro import api
    spec = api.ServeSpec(backend="batched", num_lanes=lanes,
                         max_batch=max_batch, admission=policy,
                         keep_logits=False)
    return api.Session(cfg, spec, params=params).engine(
        fault_hook=fault_hook)


def admission_rows(params, cfg, quick: bool):
    """(a) CBWS admission vs FIFO binning, measured request-level balance."""
    n = 24 if quick else 96
    lanes, max_batch = 4, 8
    frames = _skewed_frames(n, cfg)
    # adversarial arrival order: heaviest first, so FIFO striping stacks the
    # heavy requests onto the same contiguous micro-batches
    order = np.argsort(-frames.sum(axis=(1, 2, 3)))
    rows, balances = [], {}
    for policy in ("fifo", "cbws"):
        eng = _engine(params, cfg, policy, lanes, max_batch)
        eng.warmup()                   # compiles outside the timed region
        for i in order:
            eng.submit(frames[i], arrival=0.0)
        t0 = time.perf_counter()
        s = eng.run()
        dt = time.perf_counter() - t0
        balances[policy] = s["request_balance"]
        rows.append({
            "name": f"serve/admission/{policy}",
            "us_per_call": dt * 1e6,
            "derived": (f"request_balance={s['request_balance']:.4f};"
                        f"predicted_balance={s['predicted_balance']:.4f};"
                        f"served={s['served']:.0f};rounds={s['rounds']:.0f}"),
        })
    rows.append({
        "name": "serve/admission/gain",
        "us_per_call": 0.0,
        "derived": (f"cbws_over_fifo="
                    f"{balances['cbws'] / max(balances['fifo'], 1e-9):.3f}x;"
                    f"cbws_beats_fifo={balances['cbws'] > balances['fifo']}"),
    })
    return rows


def load_rows(params, cfg, quick: bool):
    """(b) open-loop Poisson sweep: latency/FPS/queue depth/energy."""
    from repro import api
    lanes, max_batch = 2, 8
    n = 32 if quick else 128
    # capacity from a measured full-batch service time
    warm = _skewed_frames(max_batch, cfg, seed=3)
    sess = api.Session(cfg, api.ServeSpec(backend="batched", num_lanes=1),
                       params=params)
    svc = sess.serve(warm, steps=2)["seconds"] / 2
    capacity = lanes * max_batch / svc            # frames/s, all lanes busy
    rows = []
    for rho in ((0.5, 0.9) if quick else (0.3, 0.6, 0.9, 1.2)):
        frames = _skewed_frames(n, cfg, seed=int(rho * 10))
        rng = np.random.default_rng(int(rho * 100))
        arrivals = np.cumsum(rng.exponential(1.0 / (rho * capacity), n))
        eng = _engine(params, cfg, "cbws", lanes, max_batch)
        for f, a in zip(frames, arrivals):
            eng.submit(f, arrival=float(a))
        s = eng.run()
        rows.append({
            "name": f"serve/load/rho{rho}",
            "us_per_call": s["p50_latency_s"] * 1e6,
            "derived": (f"p99_ms={s['p99_latency_s']*1e3:.1f};"
                        f"fps={s['fps']:.1f};"
                        f"mean_queue={s['mean_queue_depth']:.1f};"
                        f"balance={s['request_balance']:.3f};"
                        f"balance_rounds={s['balance_rounds']:.0f};"
                        f"energy_uj_per_image="
                        f"{s.get('energy_j_per_image', 0.0)*1e6:.1f};"
                        f"offered_fps={rho * capacity:.1f}"),
        })
    return rows


def throughput_rows(params, cfg, quick: bool):
    """(c) engine pipelined mode vs the old synchronous per-batch loop,
    equal batch size and backend.  The old loop computed the full
    SNNOutputs and host-synced every batch; the engine serves a logits-only
    executable with deferred syncs (see ServingEngine.infer_pipelined).
    Interleaved pairs + median-of-ratios (the bench_kernels timing
    discipline) to cancel shared-CPU drift."""
    from repro.core import snn_apply

    batch, steps, pairs = (8, 8, 5) if quick else (8, 16, 9)
    frames = _skewed_frames(batch, cfg, seed=7)
    fwd = jax.jit(lambda p, x: snn_apply(p, x, cfg, backend="batched"))
    jax.block_until_ready(fwd(params, frames).logits)        # compile

    def sync_loop():
        """The pre-engine serving loop: full outputs, host-sync per batch."""
        t0 = time.perf_counter()
        for _ in range(steps):
            jax.block_until_ready(fwd(params, frames).logits)
        return time.perf_counter() - t0

    from repro import api
    eng = api.Session(cfg, api.ServeSpec(
        backend="batched", num_lanes=1, max_batch=batch,
        keep_logits=False), params=params).engine()
    eng.infer_pipelined(frames, 1)                           # compile + warm
    t_sync, t_eng, ratios = [], [], []
    for _ in range(pairs):
        s = sync_loop()
        e = eng.infer_pipelined(frames, steps)
        t_sync.append(s)
        t_eng.append(e)
        ratios.append(s / e)
    done = batch * steps
    us_sync = statistics.median(t_sync) * 1e6
    us_eng = statistics.median(t_eng) * 1e6
    ratio = statistics.median(ratios)
    return [
        {"name": "serve/throughput/sync_loop",
         "us_per_call": us_sync,
         "derived": f"fps={done / (us_sync / 1e6):.1f};batch={batch}"},
        {"name": "serve/throughput/engine",
         "us_per_call": us_eng,
         "derived": (f"fps={done / (us_eng / 1e6):.1f};batch={batch};"
                     f"speedup_vs_sync={ratio:.3f}x")},
    ]


def threaded_rows(params, cfg, quick: bool):
    """(d) real concurrency: the worker-thread engine (2 lanes, each owning
    its jit cache) vs the single-thread virtual-clock engine draining the
    same heavy-first skewed burst.  Both walls exclude compilation (explicit
    warmup() for both engines).  Interleaved pairs + median-of-ratios (the
    bench_kernels timing discipline) to cancel shared-CPU drift.  Meant to
    run under THREADED_XLA_FLAGS (see ``threaded_rows_subprocess``)."""
    from repro import api

    lanes, max_batch = 2, 8
    n, pairs = (32, 5) if quick else (96, 7)
    frames = _skewed_frames(n, cfg, seed=11)
    order = np.argsort(-frames.sum(axis=(1, 2, 3)))   # skewed burst: heavy 1st
    buckets = (max_batch,)        # every micro-batch lands on one bucket
    sess = api.Session(cfg, params=params)

    def build(threaded):
        eng = sess.engine(api.ServeSpec(
            backend="batched", num_lanes=lanes, max_batch=max_batch,
            buckets=buckets, threaded=threaded, keep_logits=False))
        for i in order:
            eng.submit(frames[i], arrival=0.0)
        return eng

    def timed_run(eng):
        eng.warmup()                          # compiles outside the wall
        t0 = time.perf_counter()
        s = eng.run()
        return time.perf_counter() - t0, s

    build(True).run()                         # burn in thread/XLA machinery
    walls = {"single": [], "threaded": []}
    ratios, balances = [], []
    for _ in range(pairs):
        w1, _ = timed_run(build(False))
        w2, s2 = timed_run(build(True))
        walls["single"].append(w1)
        walls["threaded"].append(w2)
        ratios.append(w1 / w2)
        balances.append(s2["request_balance"])
    us1 = statistics.median(walls["single"]) * 1e6
    us2 = statistics.median(walls["threaded"]) * 1e6
    ratio = statistics.median(ratios)
    balance = statistics.median(balances)
    return [
        {"name": "serve/threaded/single_thread",
         "us_per_call": us1,
         "derived": f"wall_fps={n / (us1 / 1e6):.1f};lanes={lanes};n={n}"},
        {"name": "serve/threaded/lanes2",
         "us_per_call": us2,
         "derived": (f"wall_fps={n / (us2 / 1e6):.1f};lanes={lanes};n={n};"
                     f"speedup_vs_single_thread={ratio:.3f}x;"
                     f"request_balance={balance:.4f};"
                     f"meets_1p15x={ratio >= 1.15}")},
    ]


def forever_rows(params, cfg, quick: bool):
    """(e) live submission (serve_forever + per-request futures) vs the same
    heavy-first skewed burst pre-submitted and drained by run(), identical
    ServeSpec.  Both walls exclude compilation (serve_forever warms every
    lane cache before its clock epoch; the trace engine warms explicitly).
    A future's logits are spot-checked bitwise against the single-shot
    path.  Meant to run under THREADED_XLA_FLAGS with the threaded
    section."""
    from repro import api

    lanes, max_batch = 2, 8
    n = 32 if quick else 96
    frames = _skewed_frames(n, cfg, seed=13)
    order = np.argsort(-frames.sum(axis=(1, 2, 3)))
    spec = api.ServeSpec(backend="batched", num_lanes=lanes,
                         max_batch=max_batch, buckets=(max_batch,),
                         threaded=True, keep_logits=False)
    sess = api.Session(cfg, spec, params=params)

    # pre-submitted trace: the whole burst is queued before run() starts
    eng = sess.engine()
    for i in order:
        eng.submit(frames[i], arrival=0.0)
    eng.warmup()
    t0 = time.perf_counter()
    s1 = eng.run()
    w1 = time.perf_counter() - t0

    # live: the engine is already running when requests are submitted
    live = sess.serve_forever()               # compiles before the epoch
    t0 = time.perf_counter()
    handles = [live.submit(frames[i]) for i in order]
    results = [h.result(timeout=300.0) for h in handles]
    w2 = time.perf_counter() - t0
    s2 = live.shutdown()
    want = np.asarray(sess.infer(frames[order[0]][None]).logits[0])
    parity = bool(np.array_equal(want, results[0]))
    return [
        {"name": "serve/forever/presubmitted",
         "us_per_call": w1 * 1e6,
         "derived": (f"wall_fps={n / w1:.1f};"
                     f"p99_ms={s1['p99_latency_s']*1e3:.1f};"
                     f"served={s1['served']:.0f};lanes={lanes};n={n}")},
        {"name": "serve/forever/live",
         "us_per_call": w2 * 1e6,
         "derived": (f"wall_fps={n / w2:.1f};"
                     f"p99_ms={s2['p99_latency_s']*1e3:.1f};"
                     f"served={s2['served']:.0f};lanes={lanes};n={n};"
                     f"live_vs_presubmitted={w1 / w2:.3f}x;"
                     f"logits_parity={parity}")},
    ]


def faults_rows(params, cfg, quick: bool):
    """(f) supervised recovery under seeded chaos: the same skewed burst
    drained fault-free, then under a ``FaultPlan`` that crashes every lane
    once mid-epoch and adds a submit storm (``restart_budget=2``).  Derived
    fields surface the recovery story: restarts taken, mean death-to-service
    recovery time, and the FPS of the post-recovery tail (completions
    dispatched after the last restart) against the fault-free baseline —
    the acceptance bar is that tail within ~10% of fault-free.  The plan
    seed is echoed so a regression replays bit-identically.  Meant to run
    under THREADED_XLA_FLAGS with the threaded section."""
    from repro import api

    lanes, max_batch = 2, 8
    n, pairs = (32, 3) if quick else (96, 5)
    frames = _skewed_frames(n, cfg, seed=17)
    # crashes land on each lane's first/second execution so the restarted
    # fleet still has most of the burst ahead of it (the post-recovery tail
    # must span several micro-batches on both lanes to measure a rate)
    plan = api.FaultPlan(seed=2026, crashes=((0, 0), (1, 1)),
                         storms=((0.0, 8),))
    base = api.ServeSpec(backend="batched", num_lanes=lanes,
                         max_batch=max_batch, buckets=(max_batch,),
                         threaded=True, keep_logits=False)
    chaos = dataclasses.replace(base, restart_budget=2,
                                restart_backoff_s=0.005, fault_plan=plan)
    sess = api.Session(cfg, base, params=params)

    def run_once(spec):
        eng = sess.engine(spec)
        for f in frames:
            eng.submit(f, arrival=0.0)
        if spec.fault_plan is not None:
            # storms are driver-level: the plan's burst rides on the trace
            for a in spec.fault_plan.storm_arrivals():
                eng.submit(frames[0], arrival=float(a))
        eng.warmup()
        t0 = time.perf_counter()
        s = eng.run()
        return eng, s, time.perf_counter() - t0

    def fleet_rate(reqs):
        """Frames/s at full fleet utilization: lanes x bucket over the
        *median* micro-batch service time.  Every micro-batch here is the
        same bucket shape, so medians compare directly; makespan- or
        busy-time rates would instead be skewed by how much the two runs'
        batches happened to overlap (a solo batch runs measurably faster
        than two contending ones) and by end-of-run drain."""
        svc = [r.finish - r.start
               for _, r in {(r.lane, r.start): r for r in reqs}.items()]
        if not svc:
            return 0.0
        return lanes * max_batch / statistics.median(svc)

    def tail_rate(eng):
        """Post-recovery tail: requests whose micro-batch was dispatched
        after the last lane restart — the restarted fleet's service rate
        (a cold restart cache would show up here as a recompile stall)."""
        if not eng.metrics.restart_times:
            return 0.0
        t_up = max(eng.metrics.restart_times)
        return fleet_rate([r for r in eng.completed if r.start >= t_up])

    # interleaved pairs + median-of-ratios (the bench_kernels timing
    # discipline): baseline and post-recovery rates drift together under
    # shared-CPU noise, the ratio is what the acceptance bar reads
    walls0, walls1, bases, posts, ratios = [], [], [], [], []
    recov, restarts, watermark, served1 = [], 0.0, 0.0, 0.0
    for _ in range(pairs):
        eng0, s0, w0 = run_once(base)
        eng1, s1, w1 = run_once(chaos)
        b, p = fleet_rate(eng0.completed), tail_rate(eng1)
        walls0.append(w0)
        walls1.append(w1)
        bases.append(b)
        posts.append(p)
        ratios.append(p / max(b, 1e-9))
        recov.append(s1["mean_recovery_s"])
        restarts, watermark = s1["restarts"], s1["queue_watermark"]
        served1 = s1["served"]
    w0 = statistics.median(walls0)
    w1 = statistics.median(walls1)
    base_fps = statistics.median(bases)
    post_fps = statistics.median(posts)
    ratio = statistics.median(ratios)
    s0_served = n
    return [
        {"name": "serve/faults/baseline",
         "us_per_call": w0 * 1e6,
         "derived": (f"wall_fps={n / w0:.1f};fleet_fps={base_fps:.1f};"
                     f"served={s0_served};lanes={lanes};n={n}")},
        {"name": "serve/faults/crash_storm",
         "us_per_call": w1 * 1e6,
         "derived": (f"wall_fps={served1 / w1:.1f};"
                     f"served={served1:.0f};"
                     f"restarts={restarts:.0f};"
                     f"mean_recovery_ms={statistics.median(recov)*1e3:.1f};"
                     f"queue_watermark={watermark:.0f};"
                     f"post_recovery_fleet_fps={post_fps:.1f};"
                     f"post_recovery_over_baseline={ratio:.3f}x;"
                     f"recovered_within_10pct={ratio >= 0.9};"
                     f"plan_seed={plan.seed}")},
    ]


def chunked_rows(params, cfg, quick: bool):
    """(h) timestep-chunked continuous batching (ExecutionSpec
    .chunk_timesteps) under a bursty 3x-overload Poisson trace — the
    tentpole headline: served p99 with chunk-boundary rescheduling +
    mid-flight SLO degrade at or below the whole-T dispatch baseline.

    Fully deterministic: virtual clock, seeded arrivals, and an injected
    3-arg service model ``svc = quantum + unit * timesteps`` (the chunked
    engine pays the dispatch quantum once per *chunk*, so the win has to
    survive realistic per-dispatch overhead).  A separate no-SLO burst
    asserts the chunk-parity contract end to end: chunked and whole-T
    engines produce bit-identical logits per request."""
    from repro import api
    from repro.serving.admission import (layer0_channel_weights,
                                         predict_workload)

    lanes, max_batch = 2, 4
    # long enough that the 3x backlog outgrows the deadline mid-trace (the
    # regime chunk-boundary eviction is for); the quick scale already
    # crosses it at roughly the halfway point
    n = 144 if quick else 288
    T = cfg.timesteps
    chunk = max(1, T // 4)
    svc = 0.004                         # whole-T batch service time
    deadline = 0.012                    # per-request latency contract
    quantum = 0.05 * svc                # fixed per-dispatch overhead
    unit = (svc - quantum) / T          # marginal service per timestep
    frames = _skewed_frames(n, cfg, seed=23)
    cw = layer0_channel_weights(params)
    wmin = min(predict_workload(f, cw, T) for f in frames)
    # deliberately optimistic delay prior (half the conservative rate the
    # SLO tests use): admission keeps requests the drifted model believes
    # will meet their deadline but that actually bust it under the burst —
    # the situation chunk-boundary rescheduling exists for, since expiry
    # checks at boundaries read the clock, not a prediction
    spw = 0.5 * (2.0 * svc / wmin)
    capacity = lanes * max_batch / svc
    arrivals = np.cumsum(
        np.random.default_rng(3).exponential(1.0 / (3.0 * capacity), n))

    def model(lane, wall, tsteps):
        return quantum + unit * tsteps

    sess = api.Session(cfg, params=params)

    def run_once(ct, overload):
        spec = api.ServeSpec(
            backend="batched", num_lanes=lanes, max_batch=max_batch,
            chunk_timesteps=ct, keep_logits=True,
            slo_seconds_per_work=spw, slo_action="degrade")
        eng = sess.engine(spec, service_time_fn=model)
        for f, a in zip(frames, arrivals):
            eng.submit(f, arrival=float(a),
                       deadline_s=deadline if overload else None)
        s = eng.run()
        return eng, s

    # chunk-parity contract, end to end through the engines: no deadlines,
    # so every request runs its full T both ways -> logits must be bit-equal
    e_w, _ = run_once(None, overload=False)
    e_c, _ = run_once(chunk, overload=False)
    lw = {r.rid: np.asarray(r.logits) for r in e_w.completed}
    lc = {r.rid: np.asarray(r.logits) for r in e_c.completed}
    parity = (set(lw) == set(lc)
              and all(np.array_equal(lw[k], lc[k]) for k in lw))
    assert parity, "chunked vs whole-T logits parity violated"

    # headline: bursty 3x overload against a per-request deadline.  Whole-T
    # dispatch cannot shed a request once it is on a lane: requests whose
    # deadline passes mid-service still burn a full T of lane time and
    # their (late) latencies land in the served p99.  The chunked engine
    # re-examines every request at each chunk boundary — expired requests
    # are evicted mid-flight (freeing the backlog) and near-deadline ones
    # are truncated by the mid-flight degrade path
    _, s_w = run_once(None, overload=True)
    e_c, s_c = run_once(chunk, overload=True)
    snap = e_c.snapshot()
    p99_w, p99_c = s_w["p99_latency_s"], s_c["p99_latency_s"]
    return [
        {"name": "serve/chunked/whole_t",
         "us_per_call": p99_w * 1e6,
         "derived": (f"p99_ms={p99_w*1e3:.2f};"
                     f"p50_ms={s_w['p50_latency_s']*1e3:.2f};"
                     f"served={s_w['served']:.0f};"
                     f"deadline_missed={s_w.get('deadline_missed', 0):.0f};"
                     f"degraded={s_w.get('degraded', 0):.0f};"
                     f"lanes={lanes};n={n};T={T}")},
        {"name": "serve/chunked/chunked",
         "us_per_call": p99_c * 1e6,
         "derived": (f"p99_ms={p99_c*1e3:.2f};"
                     f"p50_ms={s_c['p50_latency_s']*1e3:.2f};"
                     f"served={s_c['served']:.0f};"
                     f"deadline_missed={s_c.get('deadline_missed', 0):.0f};"
                     f"degraded={s_c.get('degraded', 0):.0f};"
                     f"mid_degraded={snap.mid_degraded};"
                     f"mid_evicted={snap.mid_evicted};"
                     f"chunks_dispatched={snap.chunks_dispatched};"
                     f"chunk_timesteps={chunk};lanes={lanes};n={n};"
                     f"p99_vs_whole_t={p99_c / max(p99_w, 1e-12):.3f}x;"
                     f"p99_no_worse={p99_c <= p99_w};"
                     f"logits_parity={parity}")},
    ]


def threaded_rows_subprocess(quick: bool):
    """Run the threaded section in its own interpreter with XLA pinned to
    one intra-op thread (flags are frozen at first use, and this process's
    other sections must stay on the default — historically tracked —
    threading config).  CPU hosts only: the child is pinned to the CPU."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " "
                        + THREADED_XLA_FLAGS).strip()
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = (src + os.pathsep + env["PYTHONPATH"]
                         if env.get("PYTHONPATH") else src)
    cmd = [sys.executable, "-m", "benchmarks.serve_load",
           "--section", "threaded"] + (["--quick"] if quick else [])
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          check=True)
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run(quick: bool = True, section: str = "all"):
    from repro.config import get_snn
    from repro.core import init_snn

    cfg = get_snn("snn-mnist")
    params = init_snn(jax.random.PRNGKey(0), cfg)
    if section == "threaded":
        # the whole wall-clock concurrency family (threaded + live
        # serve_forever + chaos recovery) runs under the pinned-XLA
        # subprocess flags
        return (threaded_rows(params, cfg, quick)
                + forever_rows(params, cfg, quick)
                + faults_rows(params, cfg, quick))
    rows = []
    rows += admission_rows(params, cfg, quick)
    rows += load_rows(params, cfg, quick)
    rows += throughput_rows(params, cfg, quick)
    rows += chunked_rows(params, cfg, quick)
    if jax.default_backend() == "cpu":
        rows += threaded_rows_subprocess(quick)
    else:
        # a chip belongs to one process, and this one holds it: the pinned
        # intra-op threading of the child is a CPU-backend concern anyway
        rows += run(quick=quick, section="threaded")
    # sharded serving (repro.dist): lanes pinned to mesh devices — in
    # process on an accelerator host, in a bench_dist child that sees 4
    # fake host devices on a CPU one
    from benchmarks import bench_dist
    rows += bench_dist.rows_for("serve", quick)
    return rows


def main() -> None:
    from repro.runtime.compile_cache import configure_compile_cache
    configure_compile_cache()
    quick = "--quick" in sys.argv
    if "--section" in sys.argv:
        section = sys.argv[sys.argv.index("--section") + 1]
        rows = run(quick=quick, section=section)
        print(json.dumps(rows))            # parsed by the parent process
        return
    rows = run(quick=quick)
    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}", flush=True)
    if quick:
        # only quick mode writes the tracked artifact: full-run numbers use
        # different workload sizes/rates and would break the PR-to-PR diff
        payload = {r["name"]: {"us_per_call": round(r["us_per_call"], 1),
                               "derived": r["derived"]} for r in rows}
        with open(BENCH_JSON, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {BENCH_JSON} ({len(payload)} entries)", flush=True)


if __name__ == "__main__":
    main()
