"""``Session`` — one object that owns params, resolves a spec once, and
caches every jitted executable behind the facade's verbs.

    sess = Session("snn-mnist", TrainSpec(backend="batched", lr=1e-3))
    for x, y in batches:
        loss = sess.train_step(x, y)
    acc = sess.evaluate(xte, yte)
    out = sess.infer(frames)                     # bucketed jit cache
    stats = sess.serve(frames, steps=8)          # single-shot timing
    with sess.serve_forever() as live:           # threaded live engine
        handles = [live.submit(f) for f in frames]
        logits = [h.result(timeout=30) for h in handles]
    # live.summary() -> p50/p99/FPS/balance after shutdown

The spec is resolved exactly once, here: backend / timesteps / surrogate /
schedule names were validated at spec construction, the kernel-level CBWS
schedule (pallas) is built by the engine layer from the resolved mode, and
every entry point hands frames to a Session instead of re-threading
``backend=``/``surrogate_*`` kwargs through five layers.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.specs import ExecutionSpec, ServeSpec, TrainSpec
from repro.config import SNNConfig, get_snn
from repro.obs import spans

__all__ = ["Session", "LiveServer"]


class Session:
    """Owns params + jit caches for one Skydiver model under one spec.

    ``model`` is a registry name (``"snn-mnist"``) or an ``SNNConfig``;
    ``spec`` is any ``ExecutionSpec`` (a ``TrainSpec`` enables
    ``train_step``, a ``ServeSpec`` configures ``engine()`` /
    ``serve_forever()``; the other verbs derive sensible sub-specs from the
    execution fields).  ``params=None`` initializes fresh weights from
    ``seed``.
    """

    def __init__(self, model: Union[str, SNNConfig],
                 spec: Optional[ExecutionSpec] = None, *,
                 params: Optional[Dict] = None, seed: int = 0):
        from repro.core import init_snn
        self.spec = spec if spec is not None else ExecutionSpec()
        if not isinstance(self.spec, ExecutionSpec):
            raise TypeError(
                f"spec must be an ExecutionSpec/TrainSpec/ServeSpec, "
                f"got {type(self.spec).__name__}")
        cfg = model if isinstance(model, SNNConfig) else get_snn(model)
        if self.spec.timesteps is not None:
            cfg = dataclasses.replace(cfg, timesteps=self.spec.timesteps)
        self.cfg = cfg
        self.params = (params if params is not None
                       else init_snn(jax.random.PRNGKey(seed), cfg))
        self._engines: Dict[int, object] = {}    # batch-size -> single-shot
        self._train_step = None
        self._mom = None
        self._eval_fn = None
        self._device_mesh = None                 # repro.dist.DeviceMesh
        self._mesh_runner = None                 # repro.dist.MeshRunner
        self._calls = 0                          # infer calls, for spans
        self._steps = 0                          # train steps, for spans

    # -- mesh plumbing -------------------------------------------------------
    def _device_mesh_for(self, mesh_axes):
        """Resolve a mesh description to a live ``DeviceMesh`` (cached for
        the session's own spec; an override ServeSpec with a different mesh
        gets a fresh resolution)."""
        if self._device_mesh is not None and self._device_mesh.axes == mesh_axes:
            return self._device_mesh
        from repro.dist import DeviceMesh
        dm = DeviceMesh(mesh_axes)
        if self._device_mesh is None:
            self._device_mesh = dm
        return dm

    def _runner(self):
        """The session's ``MeshRunner`` (None when the spec has no mesh):
        the sharded executor infer/train_step/evaluate route through."""
        if self.spec.mesh is None:
            return None
        if self._mesh_runner is None:
            from repro.dist import MeshRunner
            self._mesh_runner = MeshRunner(
                self._device_mesh_for(self.spec.mesh), self.cfg, self.spec)
        return self._mesh_runner

    # -- spec plumbing -------------------------------------------------------
    def _as_serve_spec(self, spec: Optional[ServeSpec] = None) -> ServeSpec:
        """The ServeSpec governing engine construction: an explicit override
        wins, then the session's own spec if it is one, else a default
        ServeSpec carrying the session's execution fields."""
        if spec is not None:
            if spec.timesteps is not None \
                    and spec.timesteps != self.cfg.timesteps:
                raise ValueError(
                    f"override ServeSpec.timesteps={spec.timesteps} "
                    f"conflicts with the session's T={self.cfg.timesteps} "
                    f"(timesteps are resolved once, at Session construction)")
            return spec
        if isinstance(self.spec, ServeSpec):
            return self.spec
        return ServeSpec(**self.spec.execution_fields())

    def _as_train_spec(self) -> TrainSpec:
        if isinstance(self.spec, TrainSpec):
            return self.spec
        # the kernel schedule is serving-only (a deployment-time weight
        # permutation TrainSpec rejects) — derive the training view without
        # it, exactly as evaluate() does
        return TrainSpec(**{**self.spec.execution_fields(),
                            "schedule_mode": None})

    # -- inference / serving -------------------------------------------------
    def _single_shot_engine(self, batch: int):
        """One cached 1-lane engine per batch size (its bucket set is
        extended so any batch has a bucket; compiles are shared per size)."""
        eng = self._engines.get(batch)
        if eng is None:
            from repro.serving.batcher import DEFAULT_BUCKETS, bucket_for
            from repro.serving.engine import ServingEngine
            spec = self._as_serve_spec()
            buckets = (spec.buckets if spec.buckets is not None
                       else DEFAULT_BUCKETS)
            if batch > max(buckets):
                buckets = tuple(buckets) + (int(batch),)
            overrides = dict(
                num_lanes=1, threaded=False, buckets=tuple(buckets),
                max_batch=bucket_for(batch, buckets))
            if spec.mesh is not None:
                overrides["lane_devices"] = \
                    self._device_mesh_for(spec.mesh).lane_devices(1)
            ecfg = spec.to_engine_config(**overrides)
            eng = ServingEngine(self.params, self.cfg, ecfg)
            self._engines[batch] = eng
        return eng

    def infer(self, frames: np.ndarray, *, bucket: Optional[int] = None):
        """One batch through the bucketed jit cache; returns ``SNNOutputs``
        (padded rows sliced off).  Bit-identical to what ``serve`` /
        ``serve_forever`` produce for the same frames — all three share the
        engine's executables.

        ``bucket`` pins the padding bucket (the *canonical bucket*) instead
        of the smallest fit: per-sample convolution makes each row's output
        independent of its batchmates, so two batches of different sizes
        run at one shared bucket produce bit-identical per-row logits —
        the cross-bucket comparison knob the serving parity tests use.

        With a mesh in the spec, the batch axis is sharded over the data
        axis by the session's ``MeshRunner`` — per-row logits stay
        bit-identical to single-device execution (docs/dist.md).

        The call is one ``snn.infer`` span in a running profiler trace; the
        engine opens its phases (``obs.spans``)."""
        self._calls += 1
        with spans.span(spans.INFER, call=self._calls - 1,
                        batch=len(frames)):
            frames = np.asarray(frames, dtype=np.float32)
            n = frames.shape[0]
            if bucket is not None and bucket < n:
                raise ValueError(
                    f"bucket={bucket} cannot hold a batch of {n}")
            runner = self._runner()
            if runner is not None:
                return runner.infer(self.params, frames, pad_to=bucket)
            eng = self._single_shot_engine(n if bucket is None
                                           else max(n, int(bucket)))
            return eng.infer(frames, bucket=bucket)

    def serve(self, frames: np.ndarray, *, steps: int = 1) -> Dict[str, float]:
        """Single-shot serving: ``steps`` iterations of one fixed batch
        (per-step host sync — the historical synchronous-loop semantics);
        returns timing + spike stats."""
        frames = np.asarray(frames, dtype=np.float32)
        eng = self._single_shot_engine(frames.shape[0])
        out = eng.infer(frames)                           # compile + warm
        t0 = time.perf_counter()
        for _ in range(steps):
            out = eng.infer(frames)
        dt = time.perf_counter() - t0
        done = steps * frames.shape[0]
        return {
            "frames": done,
            "seconds": dt,
            "fps": done / dt if dt > 0 else 0.0,
            "spikes_per_frame": sum(float(t) for t in out.spike_totals)
            / frames.shape[0],
            "outputs": out,
        }

    def engine(self, spec: Optional[ServeSpec] = None, **hooks):
        """A fresh continuous-batching ``ServingEngine`` for trace replay
        (``submit`` + ``run``).  ``hooks`` passes engine-internal test knobs
        (``fault_hook``, ``service_time_fn``) through untyped — they are
        callables, not configuration."""
        from repro.serving.engine import ServingEngine
        sspec = self._as_serve_spec(spec)
        if sspec.mesh is not None and "lane_devices" not in hooks:
            hooks["lane_devices"] = self._device_mesh_for(
                sspec.mesh).lane_devices(sspec.num_lanes)
        return ServingEngine(self.params, self.cfg,
                             sspec.to_engine_config(**hooks))

    def serve_forever(self, spec: Optional[ServeSpec] = None) -> "LiveServer":
        """Start a live threaded engine accepting submissions while it runs.

        Returns a ``LiveServer`` (also a context manager): ``submit(frame)``
        -> future-style handle, ``shutdown()`` drains and returns the
        metrics summary.  ``threaded`` is forced on — live submission is
        what worker-thread lanes exist for.
        """
        sspec = self._as_serve_spec(spec)
        if not sspec.threaded:
            sspec = dataclasses.replace(sspec, threaded=True)
        from repro.serving.engine import ServingEngine
        overrides = {}
        if sspec.mesh is not None:
            overrides["lane_devices"] = self._device_mesh_for(
                sspec.mesh).lane_devices(sspec.num_lanes)
        eng = ServingEngine(self.params, self.cfg,
                            sspec.to_engine_config(**overrides))
        return LiveServer(eng.serve_forever())

    # -- training ------------------------------------------------------------
    def train_step(self, x, y) -> float:
        """One surrogate-gradient SGD+momentum step on the session's params
        (spec-selected backend); returns the loss.  The step function jits
        once and is reused; params/momentum live on the session.

        With a mesh, the batch shards over the data axis and the step runs
        through the session's ``MeshRunner`` — per-example gradient rows
        combined canonically on the host, so the updated params are
        bit-identical to single-device training on the same inputs.

        The step is one ``snn.train_step`` span in a running profiler
        trace, with its phases as children (``obs.spans``)."""
        self._steps += 1
        with spans.span(spans.TRAIN_STEP, step=self._steps - 1,
                        batch=len(x)):
            if self._mom is None:
                self._mom = jax.tree.map(jnp.zeros_like, self.params)
            runner = self._runner()
            if runner is not None:
                self.params, self._mom, loss = runner.train_step(
                    self.params, self._mom, x, y)
            else:
                if self._train_step is None:
                    from repro.core.snn_train import make_train_step
                    self._train_step = jax.jit(make_train_step(
                        self.cfg, spec=self._as_train_spec()))
                with spans.span(spans.STAGE, batch=len(x)):
                    x, y = jnp.asarray(x), jnp.asarray(y)
                with spans.span(spans.DISPATCH, batch=len(x)):
                    self.params, self._mom, loss = self._train_step(
                        self.params, self._mom, x, y)
            # compiled executables are params-independent (params are a
            # traced argument): swap the new params into the cached engines
            # in place instead of dropping them, so train/infer interleaves
            # never recompile
            with spans.span(spans.UPDATE_ENGINES,
                            engines=len(self._engines)):
                for eng in self._engines.values():
                    eng.update_params(self.params)
            with spans.span(spans.SYNC, batch=len(x)):
                return float(loss)

    def evaluate(self, x, y) -> float:
        """Classification accuracy through the spec-selected backend (the
        kernel schedule, a serving-time weight permutation, is stripped —
        evaluation runs canonical weights like training does)."""
        runner = self._runner()
        if runner is not None:
            logits = runner.infer(self.params,
                                  np.asarray(x, dtype=np.float32)).logits
            return float((np.argmax(logits, -1) == np.asarray(y)).mean())
        if self._eval_fn is None:
            from repro.core.snn_model import snn_apply
            spec = ExecutionSpec(**{**self.spec.execution_fields(),
                                    "schedule_mode": None})
            self._eval_fn = jax.jit(
                lambda p, xx: snn_apply(p, xx, self.cfg, spec=spec).logits)
        logits = self._eval_fn(self.params, jnp.asarray(x))
        return float((jnp.argmax(logits, -1) == jnp.asarray(y)).mean())


class LiveServer:
    """Client handle for a live (``serve_forever``) engine.

    Context-manager friendly: ``with sess.serve_forever() as live: ...``
    shuts down (draining every queued and in-flight request) on exit.
    """

    def __init__(self, engine):
        self._engine = engine
        self._summary: Optional[Dict[str, float]] = None

    def submit(self, frame: np.ndarray, deadline_s: Optional[float] = None):
        """Submit one frame; returns a ``RequestHandle`` future
        (``result(timeout)`` / ``done()`` / ``exception()`` / ``cancel()``).
        ``deadline_s`` is the request's latency contract (seconds after
        arrival; defaults to the spec's ``default_deadline_s``).  Raises
        ``QueueFull`` fail-fast when the spec's ``max_queue`` is hit."""
        return self._engine.submit_live(frame, deadline_s=deadline_s)

    @property
    def running(self) -> bool:
        return self._engine.live

    def metrics(self):
        """A consistent ``obs.MetricsSnapshot`` of the running engine —
        callable from any thread *while* requests are in flight (each
        subsystem is read under its own lock).  Use ``shutdown()`` /
        ``summary()`` for the terminal numbers."""
        return self._engine.snapshot()

    def trace(self):
        """The engine's ``obs.TraceRecorder`` (empty unless the spec set
        ``trace=True``); export with ``obs.export.write_chrome_trace``."""
        return self._engine.trace

    def shutdown(self, timeout: Optional[float] = None) -> Dict[str, float]:
        """Drain and stop; returns (and caches) the metrics summary."""
        if self._summary is None:
            self._summary = self._engine.shutdown(timeout)
        return self._summary

    def summary(self) -> Dict[str, float]:
        if self._summary is None:
            raise RuntimeError("live server still running — shutdown() first")
        return self._summary

    @property
    def engine(self):
        """The underlying ServingEngine (metrics, completed requests)."""
        return self._engine

    def __enter__(self) -> "LiveServer":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        # on an exception path still drain cleanly, but don't mask the
        # original error with a shutdown re-raise
        try:
            self.shutdown()
        except Exception:
            if exc_type is None:
                raise
