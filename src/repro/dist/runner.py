"""``MeshRunner`` — sharded inference and training over a ``DeviceMesh``.

The execution layer ``Session`` routes through when its spec carries a
``mesh``: the (T,B)-folded batch axis is sharded as the mesh's ``data``
axis (resolved through ``sharding.context.ShardingCtx``'s logical rules +
``sharding.partitioning.replicated``), params stay replicated, and the
jitted executables carry explicit ``in_shardings``/``out_shardings`` so
placement is a compile-time contract rather than a device_put accident.

**Bit-parity contract** (the dist acceptance criterion, tested in
tests/test_dist.py and asserted by the ``*/sharded/*`` BENCH rows):

  * *Logits*: per-sample convolution makes every output row independent of
    its batchmates, so sharding the batch over 1, 2 or 4 devices produces
    bit-identical per-row logits — same property the serving engine's
    canonical buckets already rely on.
  * *Gradients*: a pmean-style batch-loss gradient would NOT be bit-exact
    across device counts (the cross-device reduction reassociates floating
    point).  Instead the runner computes **per-example gradient rows**
    (``core.snn_train.make_grad_rows_fn`` — ``vmap(value_and_grad)`` over
    the batch, rows independent and therefore device-count-invariant) and
    combines them *canonically on the host*: one fixed-order numpy sum and
    the SGD+momentum update in host float32.  Gradients and updated params
    are bit-exact across device counts by construction, not by luck.

The runner is used single-threaded (one ``Session`` verb at a time); it
holds no locks and mutates only its own jit-cache dicts.  Serving-lane
device pinning is separate machinery (``DeviceMesh.lane_devices`` +
``serving.engine.EngineConfig.lane_devices``) — see docs/dist.md.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import numpy as np

from repro.config import SNNConfig
from repro.dist.mesh import DeviceMesh
from repro.sharding import partitioning
from repro.sharding.context import ShardingCtx

__all__ = ["MeshRunner"]


class MeshRunner:
    """Multi-device executor for one model config under one spec.

    ``spec`` is duck-typed like everywhere in core: ``backend`` /
    ``surrogate_*`` select the forward, ``lr`` / ``momentum`` (TrainSpec)
    drive ``train_step``'s host-side update.  ``spec.timesteps`` must
    already be resolved into ``cfg`` (Session does this) and a kernel-level
    CBWS schedule is rejected — mesh execution serves canonical weights
    exactly like ``Session.evaluate`` does.
    """

    def __init__(self, device_mesh: DeviceMesh, cfg: SNNConfig,
                 spec: Optional[object] = None):
        if spec is not None \
                and getattr(spec, "resolved_schedule", lambda: None)() is not None:
            raise ValueError(
                "MeshRunner serves canonical weights: a kernel-level CBWS "
                "schedule_mode (a deployed-weight permutation) is not "
                "supported with a mesh — drop the schedule or the mesh")
        self.dm = device_mesh
        self.cfg = cfg
        self.spec = spec
        self.ctx = ShardingCtx(device_mesh.mesh)
        self._rep = partitioning.replicated(self.ctx)
        # batch-dim divisor: product of the mesh axes the logical "batch"
        # axis resolves to (pod x data under DEFAULT_RULES); inputs are
        # zero-padded up to a multiple so the shard split is always exact
        axes = self.ctx.axes_for("batch")
        self._batch_div = int(np.prod(
            [self.dm.mesh.shape[a] for a in axes])) if axes else 1
        self._infer_fns: Dict[int, object] = {}
        self._grad_fns: Dict[int, object] = {}

    # -- helpers -------------------------------------------------------------
    def _padded(self, n: int) -> int:
        d = self._batch_div
        return -(-n // d) * d

    def _batch_sharding(self, shape: Tuple[int, ...]):
        return self.ctx.sharding(
            ("batch",) + (None,) * (len(shape) - 1), shape)

    def _exec_kwargs(self) -> Dict[str, object]:
        s = self.spec
        kw: Dict[str, object] = {}
        if s is not None:
            for k in ("backend", "surrogate_alpha", "surrogate_kind"):
                if hasattr(s, k):
                    kw[k] = getattr(s, k)
        return kw

    # -- inference -----------------------------------------------------------
    def _batch_pspec(self):
        from jax.sharding import PartitionSpec
        axes = self.ctx.axes_for("batch")
        return PartitionSpec(tuple(axes) if axes else None)

    def _infer_fn(self, m: int, sample_shape: Tuple[int, ...]):
        fn = self._infer_fns.get(m)
        if fn is None:
            from repro.core.snn_model import snn_apply
            kw = self._exec_kwargs()
            cfg = self.cfg
            if kw.get("backend") == "pallas":
                fn = self._manual_infer_fn(kw)
            else:
                bsh = self._batch_sharding((m,) + tuple(sample_shape))
                fn = jax.jit(lambda p, x: snn_apply(p, x, cfg, **kw),
                             in_shardings=(self._rep, bsh),
                             out_shardings=self._rep)
            self._infer_fns[m] = fn
        return fn

    def _manual_infer_fn(self, kw: Dict[str, object]):
        """Batch-sharded forward through ``jax.shard_map``: a Pallas TPU
        kernel is opaque to XLA's SPMD partitioner, which would gather the
        batch and run all of it on every device.  Each device runs its own
        rows, one batch-1 program per row (``lax.map``): on a TPU the XLA
        reductions around the kernels (the readout) pick their order by the
        local batch extent, which changes with the device count.  The
        per-row spike statistics are summed (integer-valued, so exact) and
        psum'd; the skip fractions are averaged and pmean'd."""
        from jax.sharding import PartitionSpec
        from repro.core.snn_model import SNNOutputs, snn_apply
        cfg = self.cfg
        axes = tuple(self.ctx.axes_for("batch"))
        batch = self._batch_pspec()

        def body(p, x):
            rows = jax.lax.map(lambda x1: snn_apply(p, x1[None], cfg, **kw),
                               x)

            def total(ts):
                ts = tuple(t.sum(axis=0) for t in ts)
                return tuple(jax.lax.psum(t, axes) for t in ts) if axes \
                    else ts
            skips = tuple(t.mean(axis=0) for t in rows.skip_fractions)
            return SNNOutputs(
                logits=rows.logits[:, 0],
                spike_counts=total(rows.spike_counts),
                spike_totals=total(rows.spike_totals),
                timestep_counts=total(rows.timestep_counts),
                skip_fractions=tuple(jax.lax.pmean(t, axes) for t in skips)
                if axes else skips)

        rep = PartitionSpec()
        return jax.jit(jax.shard_map(
            body, mesh=self.dm.mesh, in_specs=(rep, batch),
            out_specs=SNNOutputs(logits=batch, spike_counts=rep,
                                 spike_totals=rep, timestep_counts=rep,
                                 skip_fractions=rep),
            check_vma=False))

    def infer(self, params, frames: np.ndarray, *,
              pad_to: Optional[int] = None):
        """One batch, batch axis sharded over the data axis; returns
        ``SNNOutputs`` with pad rows sliced off the logits.  ``pad_to``
        forces a larger pad target (the canonical-bucket knob), rounded up
        to the shard divisor."""
        frames = np.asarray(frames, dtype=np.float32)
        n = frames.shape[0]
        if pad_to is not None and pad_to < n:
            raise ValueError(f"pad_to={pad_to} cannot hold a batch of {n}")
        m = self._padded(n if pad_to is None else int(pad_to))
        if m > n:
            pad = np.zeros((m - n,) + frames.shape[1:], frames.dtype)
            frames = np.concatenate([frames, pad], axis=0)
        out = self._infer_fn(m, frames.shape[1:])(params, frames)
        return out._replace(logits=np.asarray(out.logits)[:n])

    # -- training ------------------------------------------------------------
    def _manual_grads(self) -> bool:
        return self._exec_kwargs().get("backend", "ref") in ("ref", "pallas")

    def _grad_fn(self, m: int, sample_shape: Tuple[int, ...]):
        fn = self._grad_fns.get(m)
        if fn is None:
            from repro.core.snn_train import make_grad_rows_fn
            if self._manual_grads():
                # the "ref" timestep-outer scan trips an XLA SPMD
                # partitioner RET_CHECK (reshape element-count mismatch)
                # when the vmapped per-example grad is auto-partitioned,
                # and a Pallas TPU kernel cannot be partitioned at all (the
                # partitioner would run the whole batch on every device);
                # shard_map partitions the batch manually instead.  The
                # body must be sequential (lax.map of a batch-1 program):
                # a vmapped body's last-ulp arithmetic depends on the
                # *local* batch extent, which varies with device count —
                # the batch-1 body is identical everywhere, keeping rows
                # bit-exact across shardings
                from jax.sharding import PartitionSpec
                rows_fn = make_grad_rows_fn(self.cfg, spec=self.spec,
                                            sequential=True)
                batch = self._batch_pspec()
                rows_fn = jax.shard_map(
                    rows_fn, mesh=self.dm.mesh,
                    in_specs=(PartitionSpec(), batch, batch),
                    out_specs=batch, check_vma=False)
                fn = jax.jit(rows_fn)
            else:
                rows_fn = make_grad_rows_fn(self.cfg, spec=self.spec)
                bx = self._batch_sharding((m,) + tuple(sample_shape))
                by = self._batch_sharding((m,))
                fn = jax.jit(rows_fn, in_shardings=(self._rep, bx, by),
                             out_shardings=self._rep)
            self._grad_fns[m] = fn
        return fn

    def train_step(self, params, mom, x, y):
        """One SGD+momentum step; returns ``(params, mom, loss)`` exactly
        like ``core.snn_train.make_train_step``'s step function.

        Per-example loss/grad rows are computed sharded (each row touches
        only its own example — bit-identical under any data sharding); the
        batch reduction and the optimizer update run on the host in a fixed
        order, so the result is invariant to the device count."""
        x = np.asarray(x, dtype=np.float32)
        y = np.asarray(y)
        n = x.shape[0]
        m = self._padded(n)
        if m > n:
            x = np.concatenate(
                [x, np.zeros((m - n,) + x.shape[1:], x.dtype)], axis=0)
            y = np.concatenate([y, np.zeros((m - n,), y.dtype)], axis=0)
        # the shard_map path runs one row per device per call, so the local
        # program — shapes and buffer layouts included — is the same at
        # every device count: on a TPU, the layout XLA gave a longer row
        # loop's stacked outputs changed the weight-gradient arithmetic
        step = self._batch_div if self._manual_grads() else m
        parts = [self._grad_fn(step, x.shape[1:])(
            params, x[i:i + step], y[i:i + step]) for i in range(0, m, step)]
        loss_rows = np.concatenate([np.asarray(p[0]) for p in parts])[:n]
        grad_rows = jax.tree.map(
            lambda *rows: np.concatenate([np.asarray(r) for r in rows]),
            *[p[1] for p in parts])
        loss = float(loss_rows.mean(dtype=np.float32))
        lr = float(getattr(self.spec, "lr", 1e-3))
        mv = float(getattr(self.spec, "momentum", 0.9))

        def _mean_grad(rows):
            # fixed-order host reduction over the real (unpadded) rows —
            # the canonical combine the parity contract rests on
            r = np.asarray(rows, dtype=np.float32)[:n]
            return (r.sum(axis=0) / np.float32(n)).astype(np.float32)

        g = jax.tree.map(_mean_grad, grad_rows)
        new_mom = jax.tree.map(
            lambda m_, g_: (np.float32(mv) * np.asarray(m_, np.float32)
                            + g_).astype(np.float32), mom, g)
        new_params = jax.tree.map(
            lambda w, m_: (np.asarray(w, np.float32)
                           - np.float32(lr) * m_).astype(np.float32),
            params, new_mom)
        return new_params, new_mom, loss

    # -- serving -------------------------------------------------------------
    def lane_devices(self, num_lanes: int) -> Tuple:
        """Round-robin lane -> device pinning (``DeviceMesh.lane_devices``)
        for ``EngineConfig.lane_devices``."""
        return self.dm.lane_devices(num_lanes)

    def __repr__(self) -> str:
        return f"MeshRunner({self.dm!r}, backend={getattr(self.spec, 'backend', None)!r})"
