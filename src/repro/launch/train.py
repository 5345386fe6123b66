"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b \
        --steps 100 --batch 8 --seq 256 [--profile dp_zero1] [--mesh 2x2]
    PYTHONPATH=src python -m repro.launch.train --snn snn-mnist \
        --backend batched --steps 100

On this CPU container it runs reduced configs on a small mesh (or one
device); on a real fleet the same entrypoint runs the full config on the
production mesh — the step function, shardings, checkpointing and the
fault-tolerant loop are identical code paths (launch/cells.py builds them).

The ``--snn`` path trains the paper's spiking networks with surrogate
gradients through the ``repro.api`` facade: the CLI flags build one
validated ``TrainSpec`` (backend / surrogate / lr / timesteps) and a
``Session`` owns the params and the jitted step — the same hot path the
serving launcher deploys, so the trained dataflow is the deployed one
(docs/api.md).
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.checkpoint.checkpointer import Checkpointer
from repro.config import get_arch, reduced
from repro.data.pipeline import Prefetcher
from repro.data.synthetic import token_batches
from repro.models import lm
from repro.obs.log import LOG_LEVELS, configure_logging, get_logger
from repro.runtime.fault_tolerance import LoopConfig, ResilientLoop
from repro.runtime.straggler import StragglerMonitor
from repro.sharding.context import ShardingCtx, make_rules, use_sharding

log = get_logger("train")


def train_snn(args) -> None:
    import json

    from repro import api
    from repro.data.synthetic import mnist_like

    if args.spec_file:
        with open(args.spec_file) as f:
            spec = api.spec_from_dict(json.load(f))
        if not isinstance(spec, api.TrainSpec):
            raise SystemExit(
                f"--spec-file {args.spec_file} holds a "
                f"{type(spec).__name__} (kind={spec.KIND!r}); training "
                f"needs a TrainSpec (kind='train')")
    else:
        spec = api.TrainSpec(
            backend=args.backend, surrogate_kind=args.surrogate, lr=args.lr,
            timesteps=args.timesteps or None)
    if args.mesh:
        import dataclasses as _dc

        from repro.dist.mesh import parse_mesh
        spec = _dc.replace(spec, mesh=parse_mesh(args.mesh))
    sess = api.Session(args.snn, spec)
    t0 = time.perf_counter()
    for i in range(args.steps):
        x, y = mnist_like(args.batch, seed=i)
        loss = sess.train_step(x, y)
        if i % 10 == 0 or i == args.steps - 1:
            log.info("step %5d loss %.4f backend=%s", i, loss, spec.backend)
    dt = time.perf_counter() - t0
    xte, yte = mnist_like(256, seed=10_000)
    acc = sess.evaluate(xte, yte)
    log.info("finished %d SNN steps in %.1fs (backend=%s, "
             "held-out acc %.2f%%)", args.steps, dt, spec.backend, acc * 100)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--snn", default=None,
                    help="train an SNN (e.g. snn-mnist) instead of an LM")
    from repro.core import SNN_BACKENDS, SURROGATE_KINDS

    ap.add_argument("--backend", default="ref", choices=SNN_BACKENDS,
                    help="SNN execution backend to train through "
                         "(core.snn_model.SNN_BACKENDS)")
    ap.add_argument("--surrogate", default="fast_sigmoid",
                    choices=SURROGATE_KINDS,
                    help="SNN surrogate-gradient kind")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--timesteps", type=int, default=0,
                    help="override SNN timesteps (0 = config default)")
    ap.add_argument("--spec-file", default=None,
                    help="JSON TrainSpec (api.spec_from_dict; kind='train') "
                         "— replaces the per-flag SNN spec")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full-config", action="store_true",
                    help="use the full (not reduced) architecture")
    ap.add_argument("--profile", default="tp_fsdp")
    ap.add_argument("--mesh", default="",
                    help="LM: 2x2 => (data=2, model=2).  SNN: a "
                         "repro.dist mesh string, e.g. 'data=4' or bare "
                         "'4' (data-sharded train step on the device "
                         "mesh).  Empty = single device")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--log-level", default="info", choices=LOG_LEVELS,
                    help="stderr log verbosity (repro.obs.log)")
    args = ap.parse_args()
    configure_logging(args.log_level)
    from repro.runtime.compile_cache import configure_compile_cache
    configure_compile_cache()

    if args.snn:
        train_snn(args)
        return
    if not args.arch:
        ap.error("one of --arch / --snn is required")

    cfg = get_arch(args.arch)
    if not args.full_config:
        cfg = reduced(cfg)

    ctx = None
    if args.mesh:
        d, m = (int(v) for v in args.mesh.split("x"))
        mesh = jax.make_mesh((d, m), ("data", "model"))
        ctx = ShardingCtx(mesh, make_rules(args.profile))

    key = jax.random.PRNGKey(0)
    with use_sharding(ctx):
        state = lm.init_train_state(key, cfg)
        step_fn = jax.jit(lm.make_train_step(cfg, total_steps=args.steps))

        batches = Prefetcher(token_batches(cfg.vocab_size, args.batch, args.seq))
        ckpt = Checkpointer(args.ckpt_dir, keep=2)
        monitor = StragglerMonitor(num_hosts=jax.process_count())
        t_last = [time.perf_counter()]

        def on_metrics(step, m):
            now = time.perf_counter()
            monitor.record([now - t_last[0]])
            t_last[0] = now
            if step % 10 == 0:
                log.info("step %5d loss %.4f fleet_balance %.3f",
                         step, float(m["loss"]), monitor.fleet_balance())

        loop = ResilientLoop(step_fn, ckpt, LoopConfig(
            checkpoint_every=args.checkpoint_every, max_steps=args.steps))
        state = loop.run(state, batches, on_metrics=on_metrics)
    log.info("finished %d steps (resumed_from=%s, failures=%d)",
             loop.stats.steps_done, loop.stats.resumed_from,
             len(loop.stats.failures))


if __name__ == "__main__":
    main()
