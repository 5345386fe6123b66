"""Sharded serving/training BENCH rows (``repro.dist``).

Rows, one per device count in {1, 2, 4}:

  ``train/sharded/devicesN`` — us per ``MeshRunner`` train_step on an
      N-way data mesh, ``fps`` (frames/s), ``scaling_vs_1dev``, and
      ``grad_parity`` (params bit-equal to the devices1 run after the same
      step sequence — the dist acceptance contract);
  ``serve/sharded/devicesN`` — threaded engine with its lanes pinned
      round-robin over the first N mesh devices (CBWS device placement
      live), ``fps`` from the load trace, and ``logits_parity`` against the
      devices1 run.

The parent harnesses (``benchmarks/run.py`` for train,
``benchmarks/serve_load.py`` for serve) call ``rows_for``.  On an
accelerator host the section runs in the parent's own process over
``jax.devices()`` (device counts 1, 2, 4 up to what is attached): a chip
belongs to one process, and the parent already holds it.  On a CPU host it
re-execs this module via ``rows_subprocess`` with
``repro.dist.host_device_env(4)`` — the fake-device flag only acts before
the first jax import — plus the same intra-op pinning the serve/threaded
section uses (lanes should map onto execution units, not fight XLA's
thread pool).  On a multi-core runner fps rises with the device
count (the CI BENCH gate asserts fps@4 > fps@1); on a single-core container
the sharded rows mostly measure dispatch overhead — see docs/dist.md.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time

import numpy as np

DEVICES = (1, 2, 4)

# same rationale as serve_load.THREADED_XLA_FLAGS: one intra-op thread per
# process so lane/device parallelism is what gets measured
DIST_XLA_FLAGS = ("--xla_cpu_multi_thread_eigen=false"
                  " intra_op_parallelism_threads=1")


def _cfg(quick: bool):
    from repro.config import get_snn
    cfg = get_snn("snn-mnist")
    if quick:
        cfg = dataclasses.replace(cfg, input_hw=(14, 14),
                                  conv_channels=(8, 8), timesteps=4)
    return cfg


def _device_counts():
    """The mesh sizes of ``DEVICES`` this process can build.  A CPU host
    must see all of them (the fake-device child); an accelerator host runs
    what is attached."""
    import jax
    have = jax.device_count()
    if jax.default_backend() == "cpu" and have < max(DEVICES):
        raise RuntimeError(
            f"bench_dist needs {max(DEVICES)} devices but sees {have}; "
            f"run via rows_subprocess / "
            f"repro.dist.host_device_env({max(DEVICES)})")
    return tuple(n for n in DEVICES if n <= have)


def _eq_tree(a, b) -> bool:
    import jax.tree_util as jtu
    return all(np.array_equal(np.asarray(u), np.asarray(v))
               for u, v in zip(jtu.tree_leaves(a), jtu.tree_leaves(b)))


def train_rows(quick: bool):
    """us/step + throughput of the sharded train step per device count,
    with the bit-parity acceptance flag inline."""
    from repro import api
    counts = _device_counts()
    cfg = _cfg(quick)
    batch = 8 if quick else 32
    steps = 3 if quick else 10
    rng = np.random.default_rng(0)
    x = rng.random((batch, *cfg.input_hw, cfg.input_channels),
                   dtype=np.float32)
    y = (np.arange(batch) % 10).astype(np.int32)

    rows, fps1, params1 = [], None, None
    for n in counts:
        sess = api.Session(
            cfg, api.TrainSpec(backend="batched", mesh={"data": n}), seed=0)
        sess.train_step(x, y)              # compile outside the timed region
        t0 = time.perf_counter()
        for _ in range(steps):
            sess.train_step(x, y)
        dt = time.perf_counter() - t0
        fps = steps * batch / dt if dt > 0 else 0.0
        if params1 is None:
            fps1, params1 = fps, sess.params
            parity = True                  # devices1 is the reference
        else:
            parity = _eq_tree(sess.params, params1)
        rows.append({
            "name": f"train/sharded/devices{n}",
            "us_per_call": dt / steps * 1e6,
            "derived": (f"device_count={n};fps={fps:.1f};"
                        f"scaling_vs_1dev={fps / max(fps1, 1e-12):.2f}x;"
                        f"grad_parity={parity};"
                        f"steps={steps};batch={batch}")})
    return rows


def serve_rows(quick: bool):
    """Threaded-engine throughput per device count with lanes pinned to
    mesh devices, plus logits parity against the devices1 run."""
    from repro import api
    counts = _device_counts()
    cfg = _cfg(quick)
    n_req = 32 if quick else 128
    lanes, max_batch = 4, 8
    rng = np.random.default_rng(0)
    frames = rng.random((8, *cfg.input_hw, cfg.input_channels),
                        dtype=np.float32)

    rows, fps1, logits1 = [], None, None
    for n in counts:
        sess = api.Session(
            cfg, api.ServeSpec(backend="batched", mesh={"data": n},
                               num_lanes=lanes, threaded=True,
                               max_batch=max_batch), seed=0)
        eng = sess.engine()
        rids = [eng.submit(frames[i % frames.shape[0]],
                           arrival=float(i) * 1e-3) for i in range(n_req)]
        s = eng.run()
        got = {r.rid: np.asarray(r.logits) for r in eng.completed}
        by_frame = {rid: got[rid] for rid in rids if rid in got}
        if logits1 is None:
            fps1, logits1 = s["fps"], by_frame
            parity = True
        else:
            parity = (set(by_frame) == set(logits1) and all(
                np.array_equal(by_frame[rid], logits1[rid])
                for rid in by_frame))
        snap = eng.snapshot()
        rows.append({
            "name": f"serve/sharded/devices{n}",
            "us_per_call": 1e6 / max(s["fps"], 1e-12),
            "derived": (f"device_count={n};fps={s['fps']:.1f};"
                        f"scaling_vs_1dev={s['fps'] / max(fps1, 1e-12):.2f}x;"
                        f"logits_parity={parity};"
                        f"served={s['served']:.0f};"
                        f"pinned_devices={len(set(snap.lane_devices))};"
                        f"lanes={lanes};n={n_req}")})
    return rows


def run(section: str, quick: bool = True):
    if section == "train":
        return train_rows(quick)
    if section == "serve":
        return serve_rows(quick)
    raise ValueError(f"unknown bench_dist section {section!r}")


def rows_for(section: str, quick: bool):
    """The section's rows, run where the devices are: in this process on an
    accelerator host, in a fake-device CPU child otherwise."""
    import jax
    if jax.default_backend() != "cpu":
        return run(section, quick)
    return rows_subprocess(section, quick)


def rows_subprocess(section: str, quick: bool):
    """Parent end (CPU hosts only): re-exec this module with 4 fake host
    devices + intra-op pinning and parse the JSON row list off the last
    stdout line."""
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    if src not in sys.path:                # parent may run without
        sys.path.insert(0, src)            # PYTHONPATH=src
    from repro.dist.mesh import host_device_env
    env = host_device_env(max(DEVICES), extra_flags=DIST_XLA_FLAGS)
    env["JAX_PLATFORMS"] = "cpu"           # the fake devices are CPU ones
    env["PYTHONPATH"] = (src + os.pathsep + env["PYTHONPATH"]
                         if env.get("PYTHONPATH") else src)
    cmd = [sys.executable, "-m", "benchmarks.bench_dist",
           "--section", section] + (["--quick"] if quick else [])
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          check=True)
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main() -> None:
    from repro.runtime.compile_cache import configure_compile_cache
    configure_compile_cache()
    quick = "--quick" in sys.argv
    if "--section" in sys.argv:
        section = sys.argv[sys.argv.index("--section") + 1]
        rows = run(section, quick=quick)
        print(json.dumps(rows))            # parsed by the parent process
        return
    # standalone: run both sections where the devices are and print CSV
    # (artifact files are owned by run.py / serve_load.py)
    print("name,us_per_call,derived")
    for section in ("train", "serve"):
        for r in rows_for(section, quick):
            print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}",
                  flush=True)


if __name__ == "__main__":
    main()
