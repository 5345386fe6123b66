"""Serving launcher: continuous batched decode against prefix caches, and
SNN frame inference through the selectable kernel backend.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b \
        --batch 4 --prompt-len 64 --new 32
    PYTHONPATH=src python -m repro.launch.serve --snn snn-mnist \
        --backend batched --batch 4 --steps 8
    PYTHONPATH=src python -m repro.launch.serve --snn snn-mnist \
        --engine --lanes 2 --batch 8
    PYTHONPATH=src python -m repro.launch.serve --snn snn-mnist \
        --engine --threaded --lanes 2 --slo-ms 50 --slo-action degrade
    PYTHONPATH=src python -m repro.launch.serve --snn snn-mnist \
        --forever --lanes 2      # live submission + per-request futures

Production path: the same prefill/decode step functions are lowered with the
`serve`/`serve_ep2d` profiles on the pod mesh (see launch/cells.py); here
they run reduced on CPU.  The SNN path runs entirely through the
``repro.api`` facade (docs/api.md): the CLI flags build one validated
``ServeSpec`` (backend / ``--schedule`` kernel schedule / lanes / SLO) and a
``Session`` executes it.  The default is the single-shot path (fixed batch,
per-step sync); ``--engine`` replays a synthetic Poisson trace through the
full continuous-batching loop (FIFO windows, CBWS-balanced micro-batch
lanes, straggler-aware placement), ``--threaded`` promotes the lanes to
real worker threads on the wall clock, ``--forever`` demos live submission
(``Session.serve_forever()`` + per-request futures), and ``--slo-ms`` adds
admission-time latency-budget control (reject or degrade, ``--slo-action``)
— see docs/serving.md.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import get_arch, reduced
from repro.models import transformer
from repro.obs.log import LOG_LEVELS, configure_logging, get_logger

log = get_logger("serve")


def _load_spec_file(path: str):
    """Parse a ``--spec-file`` JSON document into a validated spec via
    ``spec_from_dict`` (the ``kind`` tag dispatches; unknown fields and
    invalid values die loudly at parse time, not inside a jit trace)."""
    import json

    from repro import api
    with open(path) as f:
        d = json.load(f)
    return api.spec_from_dict(d)


def serve_snn(args) -> None:
    import dataclasses as _dc

    from repro import api

    if args.spec_file:
        spec = _load_spec_file(args.spec_file)
        if not isinstance(spec, api.ServeSpec):
            raise SystemExit(
                f"--spec-file {args.spec_file} holds a "
                f"{type(spec).__name__} (kind={spec.KIND!r}); serving needs "
                f"a ServeSpec (kind='serve')")
    else:
        spec = api.ServeSpec(
            backend=args.backend,
            schedule_mode=api.resolve_schedule(args.schedule, args.backend),
            num_lanes=args.lanes, max_batch=args.batch,
            threaded=args.threaded,
            latency_budget_s=(args.slo_ms / 1e3 if args.slo_ms else None),
            slo_action=args.slo_action)
    # robustness knobs layer onto either spec source (explicit flags win)
    overrides = {}
    if args.max_queue is not None:
        overrides["max_queue"] = args.max_queue
    if args.deadline_ms is not None:
        overrides["default_deadline_s"] = args.deadline_ms / 1e3
    if args.trace_out:
        overrides["trace"] = True
    if args.mesh:
        from repro.dist.mesh import parse_mesh
        overrides["mesh"] = parse_mesh(args.mesh)
    if overrides:
        spec = _dc.replace(spec, **overrides)
    sess = api.Session(args.snn, spec)
    cfg = sess.cfg
    frames = np.asarray(jax.random.uniform(
        jax.random.PRNGKey(1),
        (args.batch, *cfg.input_hw, cfg.input_channels)))

    if args.forever:
        # live serving: submissions while the engine runs, per-request
        # futures (Session.serve_forever on the threaded engine)
        n = args.steps * args.batch
        live = sess.serve_forever()
        handles = [live.submit(frames[i % args.batch]) for i in range(n)]
        # live introspection: a consistent MetricsSnapshot taken while
        # requests are still in flight (LiveServer.metrics())
        snap = live.metrics()
        log.info("mid-burst snapshot: served=%d queued=%d in_flight=%d "
                 "lanes=%d/%d", snap.served, snap.queued, snap.in_flight,
                 snap.lanes_alive, snap.lanes_total)
        # exception() instead of result(): with --slo-ms an over-budget
        # submission resolves to SLORejected, which is an outcome to count
        # here, not a crash
        outcomes = [h.exception(timeout=60.0) for h in handles]
        s = live.shutdown()
        _write_trace(args, live.trace())
        log.info(
            "engine[forever] served %.0f frames live (%.1f FPS, backend=%s, "
            "lanes=%d, p50=%.1fms, p99=%.1fms, futures_resolved=%d, "
            "futures_rejected=%d, deadline_missed=%.0f, queue_full=%.0f, "
            "restarts=%.0f)",
            s["served"], s["fps"], spec.backend, spec.num_lanes,
            s["p50_latency_s"] * 1e3, s["p99_latency_s"] * 1e3,
            sum(e is None for e in outcomes),
            sum(e is not None for e in outcomes),
            s["deadline_missed"], s["queue_full"], s["restarts"])
        return

    if args.engine:
        # continuous-batching engine on a synthetic open-loop arrival trace
        eng = sess.engine()
        rng = np.random.default_rng(0)
        n = args.steps * args.batch
        gaps = rng.exponential(1e-3, n)
        for i, arr in enumerate(np.cumsum(gaps)):
            eng.submit(frames[i % args.batch], arrival=float(arr))
        s = eng.run()
        _write_trace(args, eng.trace)
        mode = "threaded" if spec.threaded else "virtual"
        log.info(
            "engine[%s] served %.0f frames in %.0f rounds (%.1f FPS, "
            "backend=%s, lanes=%d, p50=%.1fms, p99=%.1fms, balance=%.3f, "
            "rejected=%.0f, degraded=%.0f)",
            mode, s["served"], s["rounds"], s["fps"], spec.backend,
            spec.num_lanes, s["p50_latency_s"] * 1e3,
            s["p99_latency_s"] * 1e3, s["request_balance"],
            s["rejected"], s["degraded"])
        return

    s = sess.serve(frames, steps=args.steps)
    log.info("served %d frames in %.2fs (%.1f FPS, backend=%s, T=%d, "
             "total_spikes/frame=%.0f)", s["frames"], s["seconds"], s["fps"],
             spec.backend, cfg.timesteps, s["spikes_per_frame"])


def _write_trace(args, trace) -> None:
    """Export the engine's recorded lifecycle trace as Chrome trace-event
    JSON (``--trace-out``; load in Perfetto / chrome://tracing)."""
    if not args.trace_out:
        return
    from repro.obs.export import write_chrome_trace
    n = write_chrome_trace(trace, args.trace_out)
    log.info("wrote %d trace events to %s", n, args.trace_out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--snn", default=None,
                    help="serve an SNN (e.g. snn-mnist) instead of an LM")
    ap.add_argument("--backend", default="batched",
                    choices=("ref", "batched", "pallas"),
                    help="SNN execution backend (see core.snn_model)")
    ap.add_argument("--schedule", default="auto",
                    choices=("auto", "none", "cbws", "aprc+cbws"),
                    help="kernel-level CBWS channel schedule (pallas "
                         "backend only; 'auto' = aprc+cbws on pallas, none "
                         "otherwise — an explicit mode on a non-pallas "
                         "backend is a loud ServeSpec error)")
    ap.add_argument("--steps", type=int, default=8,
                    help="SNN serving iterations")
    ap.add_argument("--engine", action="store_true",
                    help="serve through the continuous-batching engine "
                         "(repro.serving) on a synthetic Poisson trace")
    ap.add_argument("--forever", action="store_true",
                    help="live serving demo: Session.serve_forever() with "
                         "submissions while the engine runs (implies "
                         "threaded lanes)")
    ap.add_argument("--lanes", type=int, default=2,
                    help="engine micro-batch lanes (with --engine)")
    ap.add_argument("--threaded", action="store_true",
                    help="run engine lanes as worker threads on the wall "
                         "clock (with --engine)")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="admission latency budget in ms; over-budget "
                         "requests are rejected/degraded (with --engine)")
    ap.add_argument("--mesh", default="",
                    help="repro.dist mesh string, e.g. 'data=2' or bare "
                         "'2': shards infer/serve over the device mesh and "
                         "pins engine lanes round-robin to mesh devices "
                         "(CPU hosts need XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--slo-action", default="reject",
                    choices=("reject", "degrade"),
                    help="what to do with over-budget requests")
    ap.add_argument("--spec-file", default=None,
                    help="JSON ServeSpec (api.spec_from_dict; kind='serve') "
                         "— replaces the per-flag spec; --max-queue/"
                         "--deadline-ms still layer on top")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded-queue backpressure: live submissions "
                         "beyond this depth fail fast with QueueFull")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="default per-request deadline in ms; requests "
                         "expired in queue fail with DeadlineExceeded")
    ap.add_argument("--trace-out", default=None,
                    help="record engine lifecycle events (ServeSpec.trace) "
                         "and write Chrome trace-event JSON here — load in "
                         "Perfetto (with --engine/--forever)")
    ap.add_argument("--log-level", default="info", choices=LOG_LEVELS,
                    help="stderr log verbosity (repro.obs.log)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new", type=int, default=32)
    ap.add_argument("--full-config", action="store_true")
    args = ap.parse_args()
    configure_logging(args.log_level)
    from repro.runtime.compile_cache import configure_compile_cache
    configure_compile_cache()

    if args.snn:
        serve_snn(args)
        return

    cfg = get_arch(args.arch) if args.full_config else reduced(get_arch(args.arch))
    if cfg.is_encoder_only:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode path")
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    max_len = args.prompt_len + args.new

    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    logits, caches = transformer.prefill(params, cfg, tokens=prompts,
                                         remat=False, max_len=max_len)
    decode = jax.jit(lambda p, c, t, pos: transformer.decode_step(
        p, c, cfg, token=t, pos=pos))
    token = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    t0 = time.time()
    for i in range(args.new - 1):
        logits, caches = decode(params, caches, token,
                                jnp.asarray(args.prompt_len + i))
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    jax.block_until_ready(token)
    n = args.batch * (args.new - 1)
    log.info("served %d tokens in %.2fs", n, time.time() - t0)


if __name__ == "__main__":
    main()
