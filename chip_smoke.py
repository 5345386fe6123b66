#!/usr/bin/env python3
"""Chip smoke test: the paper's two spiking networks, end to end, on a TPU.

    python chip_smoke.py             # one chip: phases (a)-(c)
    python chip_smoke.py --chips 4   # four chips: the mesh phase only

One process drives everything (a chip belongs to one process).  Weights are
random from ``--seed``; the paper's full widths (``repro.configs``) are used.

  (a) serve  — snn-mnist through ``Session(...).engine()`` on the Pallas
      backend with the CBWS kernel schedule, a few hundred requests at
      max_batch 64; logits and spike counts against a ``ref`` Session.
  (b) train  — snn-mnist ``Session.train_step`` on the Pallas backend; the
      loss stays finite, and one batch's gradients agree with ``ref``.
  (c) seg    — snn-seg ``Session.infer`` at 80x160x3, T=16, batch 8, Pallas
      against ``ref``.
  (mesh, ``--chips 4``) — a ``MeshRunner`` train step and sharded inference
      on data=4 against data=1 (bit-parity contract), and serving lanes
      pinned to the 4 devices against one device.

The ``ref`` side runs at ``jax.default_matmul_precision("highest")``: XLA's
default f32 matmul on a TPU rounds through bf16, the kernels' taps run at
full f32 precision.  Parity is checked two ways:

  * exact — weights and frames rounded to multiples of 2**-8 ("dyadic"),
    so every product and partial sum is exact in f32 whatever the
    accumulation order: logits within 1e-6 and spike counts equal, the CPU
    contract unloosened;
  * float — random f32 weights.  The MXU and XLA's convolution sum in
    different orders, so a membrane within rounding of the threshold can
    fire on one side only, and each such flip moves what follows it.  The
    tolerance is the share of outputs that agree within 1e-6 and the
    relative error of each layer's spike total (constants below).  The timings
printed are smoke timings (first-call compile included), not benchmark
numbers.  Any failed phase, or a platform other than ``tpu``, exits
non-zero; only a full pass prints the final JSON line.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Parity of the Pallas path against ``ref``.
LOGIT_ATOL = 1e-6            # an output element agrees within this
DYADIC = 2.0 ** -8           # exact check: weights and frames on this grid
# Float check, set from the first v5e runs: 98.4% of snn-mnist logits (4 of
# 256 rows touched by a flip) and 99.88% of snn-seg mask pixels agreed, and
# spike totals were off by at most 3.5e-6 — bounds with margin:
FLOAT_MIN_AGREE = 0.95       # share of output elements within LOGIT_ATOL
FLOAT_SPIKE_RTOL = 1e-4      # |layer spike total - ref| / ref
GRAD_ATOL, GRAD_RTOL = 5e-5, 5e-4   # |g - g_ref| <= atol + rtol * |g_ref|

SERVE_REQUESTS = 256
SERVE_MAX_BATCH = 64
TRAIN_BATCH = 64
TRAIN_STEPS = 3
SEG_BATCH = 8


class _CompileStats:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.compile_s = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class SmokeFailure(RuntimeError):
    """A phase's output is wrong."""


def _require(ok, msg) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _say(msg: str) -> None:
    print(msg, flush=True)


def _peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def _frames(rng, n, cfg):
    return rng.uniform(0.0, 1.0, (n,) + tuple(cfg.input_hw)
                       + (cfg.input_channels,)).astype(np.float32)


def _dyadic(tree):
    """Round every leaf to a multiple of ``DYADIC``."""
    import jax
    return jax.tree.map(
        lambda a: (np.round(np.asarray(a) / DYADIC) * DYADIC).astype(
            np.float32), tree)


def _params(model, seed, dyadic):
    import jax
    from repro.config import get_snn
    from repro.core import init_snn
    p = init_snn(jax.random.PRNGKey(seed), get_snn(model))
    return _dyadic(p) if dyadic else p


def _ref_infer(model, params, frames, batch):
    """``ref`` logits of all frames, and the spike counts of the first
    batch."""
    import jax
    from repro.api import ExecutionSpec, Session
    sess = Session(model, ExecutionSpec(backend="ref"), params=params)
    logits, counts = [], None
    with jax.default_matmul_precision("highest"):
        for i in range(0, frames.shape[0], batch):
            out = sess.infer(frames[i:i + batch])
            logits.append(np.asarray(out.logits))
            if counts is None:
                counts = [np.asarray(c) for c in out.spike_counts]
    return np.concatenate(logits), counts


def _check_parity(name, exact, got, want, counts, want_counts):
    """Pallas outputs against ``ref``; returns the failures after printing
    every number.  ``exact``: every element within ``LOGIT_ATOL`` and equal
    spike counts; else the float tolerances."""
    _require(got.shape == want.shape, (name, got.shape, want.shape))
    diff = np.abs(got - want)
    agree = float(np.mean(diff <= LOGIT_ATOL))
    _say(f"  {name}: elements within {LOGIT_ATOL!r}: {agree!r}; max |diff| "
         f"{float(diff.max())!r}; mean |diff| {float(diff.mean())!r}")
    bad = []
    if not np.all(np.isfinite(got)):
        bad.append(f"{name}: non-finite logits")
    if agree < (1.0 if exact else FLOAT_MIN_AGREE):
        bad.append(f"{name}: {agree!r} of elements within {LOGIT_ATOL!r}")
    for i, (g, w) in enumerate(zip(counts, want_counts)):
        g, w = np.asarray(g), np.asarray(w)
        rel = abs(float(g.sum()) - float(w.sum())) / max(float(w.sum()), 1.0)
        per_channel = float(np.max(np.abs(g - w)))
        _say(f"  {name} layer {i}: spikes {float(g.sum())!r} vs ref "
             f"{float(w.sum())!r} (rel {rel!r}), max per-channel diff "
             f"{per_channel!r}")
        if (per_channel != 0.0) if exact else (rel > FLOAT_SPIKE_RTOL):
            bad.append(f"{name} layer {i}: spike counts off (rel {rel!r})")
    return bad


def phase_serve(seed: int) -> None:
    from repro.api import ServeSpec, Session
    from repro.config import get_snn
    rng = np.random.default_rng(seed)
    cfg = get_snn("snn-mnist")
    spec = ServeSpec(backend="pallas", schedule_mode="aprc+cbws",
                     max_batch=SERVE_MAX_BATCH, buckets=(16, 32, 64))
    bad = []
    for exact in (True, False):
        tag = "serve " + ("exact" if exact else "float")
        params = _params("snn-mnist", seed, exact)
        frames = _frames(rng, SERVE_REQUESTS, cfg)
        if exact:
            frames = _dyadic(frames)
        sess = Session("snn-mnist", spec, params=params)
        eng = sess.engine()
        rids = [eng.submit(f) for f in frames]
        summary = eng.run()
        by_rid = {r.rid: r.logits for r in eng.completed}
        _require(len(by_rid) == len(rids),
                 f"served {len(by_rid)} of {len(rids)} requests")
        got = np.stack([np.asarray(by_rid[r]) for r in rids])
        _say(f"  {tag}: served {len(rids)} requests; engine fps "
             f"{summary.get('fps')!r}")
        want, want_counts = _ref_infer("snn-mnist", params, frames,
                                       SERVE_MAX_BATCH)
        out = sess.infer(frames[:SERVE_MAX_BATCH])
        bad += _check_parity(tag, exact, got, want, out.spike_counts,
                             want_counts)
        _say(f"  {tag}: argmax agreement "
             f"{float(np.mean(got.argmax(-1) == want.argmax(-1)))!r}; skip "
             f"fractions {[float(s) for s in out.skip_fractions]!r}")
    _require(not bad, bad)


def phase_train(seed: int) -> None:
    import jax
    from repro.api import Session, TrainSpec
    from repro.config import get_snn
    from repro.core.snn_train import make_loss_fn
    rng = np.random.default_rng(seed + 1)
    cfg = get_snn("snn-mnist")
    x = _frames(rng, TRAIN_BATCH, cfg)
    y = rng.integers(0, 10, TRAIN_BATCH).astype(np.int32)
    sess = Session("snn-mnist", TrainSpec(backend="pallas", lr=1e-2),
                   seed=seed)
    params0 = sess.params
    g_p = jax.jit(jax.grad(make_loss_fn(
        cfg, spec=TrainSpec(backend="pallas"))))(params0, x, y)
    with jax.default_matmul_precision("highest"):
        g_r = jax.jit(jax.grad(make_loss_fn(
            cfg, spec=TrainSpec(backend="ref"))))(params0, x, y)
    worst = 0.0
    for path, gp in jax.tree_util.tree_flatten_with_path(g_p)[0]:
        gr = np.asarray(_leaf(g_r, path))
        gp = np.asarray(gp)
        _require(np.all(np.isfinite(gp)), f"non-finite grad at {path}")
        # 1.0 = exactly at the tolerance
        ratio = float(np.max(np.abs(gp - gr)
                             / (GRAD_ATOL + GRAD_RTOL * np.abs(gr))))
        worst = max(worst, ratio)
        _say(f"  grad {jax.tree_util.keystr(path)}: max |diff| "
             f"{float(np.max(np.abs(gp - gr)))!r}, max|ref| "
             f"{float(np.max(np.abs(gr)))!r}, tolerance use {ratio!r}")
    _require(worst <= 1.0, f"gradients exceed the tolerance ({worst!r})")
    losses = [sess.train_step(x, y) for _ in range(TRAIN_STEPS)]
    _say(f"  losses {losses!r}")
    _require(all(math.isfinite(v) for v in losses), "non-finite loss")


def _leaf(tree, path):
    for k in path:
        tree = tree[getattr(k, "key", getattr(k, "idx", None))]
    return tree


def phase_seg(seed: int) -> None:
    from repro.api import ExecutionSpec, Session
    from repro.config import get_snn
    rng = np.random.default_rng(seed + 2)
    cfg = get_snn("snn-seg")
    bad = []
    for exact in (True, False):
        params = _params("snn-seg", seed, exact)
        frames = _frames(rng, SEG_BATCH, cfg)
        if exact:
            frames = _dyadic(frames)
        out = Session("snn-seg", ExecutionSpec(backend="pallas"),
                      params=params).infer(frames)
        want, want_counts = _ref_infer("snn-seg", params, frames, SEG_BATCH)
        bad += _check_parity("seg " + ("exact" if exact else "float"), exact,
                             np.asarray(out.logits), want, out.spike_counts,
                             want_counts)
    _require(not bad, bad)


def phase_mesh(seed: int) -> None:
    """data=4 against data=1 (MeshRunner), and 4 pinned serving lanes
    against one device — bit-parity contract (docs/dist.md)."""
    import jax
    from repro.api import ServeSpec, Session, TrainSpec
    from repro.config import get_snn
    rng = np.random.default_rng(seed + 3)
    cfg = get_snn("snn-mnist")
    x = _frames(rng, TRAIN_BATCH, cfg)
    y = rng.integers(0, 10, TRAIN_BATCH).astype(np.int32)
    sessions = {n: Session("snn-mnist", TrainSpec(backend="pallas",
                                                  mesh={"data": n}),
                           seed=seed) for n in (1, 4)}
    runner = sessions[4]._runner()
    shards = jax.device_put(x, runner._batch_sharding(x.shape))
    placed = sorted((str(s.device), s.data.shape[0])
                    for s in shards.addressable_shards)
    _say(f"  data=4 batch shards (device, rows): {placed!r}")
    _require(len({d for d, _ in placed}) == 4,
             "batch not spread over 4 devices")
    logits = {n: np.asarray(s.infer(x).logits) for n, s in sessions.items()}
    _require(np.array_equal(logits[1], logits[4]),
             "sharded logits differ from one device")
    _say("  MeshRunner infer: data=4 logits bit-identical to data=1")
    losses = {n: s.train_step(x, y) for n, s in sessions.items()}
    _require(losses[1] == losses[4], losses)
    p1 = jax.tree_util.tree_flatten_with_path(sessions[1].params)[0]
    p4 = jax.tree_util.tree_leaves(sessions[4].params)
    differ = {jax.tree_util.keystr(k): float(np.max(np.abs(
        np.asarray(a) - np.asarray(b)))) for (k, a), b in zip(p1, p4)
        if not np.array_equal(np.asarray(a), np.asarray(b))}
    _require(not differ, f"sharded params differ (max |diff|): {differ!r}")
    _say(f"  MeshRunner train step: loss {losses[4]!r}, params bit-identical "
         f"data=4 vs data=1")

    frames = _frames(rng, SERVE_REQUESTS, cfg)
    served = {}
    for mesh in (None, {"data": 4}):
        spec = ServeSpec(backend="pallas", num_lanes=4, max_batch=16,
                         buckets=(16,), mesh=mesh)
        eng = Session("snn-mnist", spec, seed=seed).engine()
        rids = [eng.submit(f) for f in frames]
        eng.run()
        by_rid = {r.rid: r for r in eng.completed}
        served[mesh is not None] = np.stack(
            [np.asarray(by_rid[r].logits) for r in rids])
        if mesh is not None:
            per_dev = {}
            for r in eng.completed:
                dev = str(eng.ecfg.lane_devices[r.lane])
                per_dev[dev] = per_dev.get(dev, 0) + 1
            _say(f"  sharded serving: requests per device {per_dev!r}")
            _require(len(per_dev) == 4, "serving lanes did not use 4 devices")
    _require(np.array_equal(served[False], served[True]),
             "4-device serving logits differ from one device")
    _say("  sharded serving: logits bit-identical to one device")
    for d in jax.devices():
        _say(f"  {d}: peak_bytes_in_use {_peak_bytes(d)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh phase on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.runtime.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    import jax
    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    _say(f"device: {device!r}; compile cache {cache_dir}")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} devices",
              file=sys.stderr)
        return 2
    from repro.kernels import ops
    if ops.default_interpret():
        print("chip_smoke: kernels would run interpreted", file=sys.stderr)
        return 2
    stats = _CompileStats()

    phases = ([("mesh", phase_mesh)] if args.chips == 4 else
              [("serve", phase_serve), ("train", phase_train),
               ("seg", phase_seg)])
    failed = []
    for name, fn in phases:
        c0, h0, t0 = stats.compile_s, stats.cache_hits, time.perf_counter()
        _say(f"[{name}]")
        try:
            fn(args.seed)
            status = "ok"
        except Exception:
            traceback.print_exc()
            failed.append(name)
            status = "FAILED"
        _say(f"[{name}] {status}; smoke timing: wall "
             f"{time.perf_counter() - t0!r} s, backend compile "
             f"{stats.compile_s - c0!r} s, persistent-cache hits "
             f"{stats.cache_hits - h0}; peak_bytes_in_use "
             f"{_peak_bytes(dev)}")
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
