"""Public jit'd wrappers for the Pallas kernels.

``interpret`` is resolved here and nowhere else: ``None`` (the default)
means compiled Mosaic when JAX's default backend is a TPU and Pallas
interpret mode otherwise (CPU validation).  The first interpret-mode
resolution logs a warning, so a run that expected the chip cannot drop
to the interpreter unnoticed; the raw kernel entries take ``interpret``
as a required keyword.

All wrappers are **differentiable**: ``spiking_conv`` and
``spiking_conv_lif`` carry ``jax.custom_vjp`` rules (surrogate BPTT for the
fused kernel, transposed-tap conv backward for both — see
kernels/spiking_conv_lif.py), so ``jax.grad`` through the pallas model
backend trains instead of silently returning zeros.  ``bwd`` selects the
backward implementation: ``"pallas"`` (the mirror kernels) or ``"xla"``
(the fallback, default in interpret mode where a Python-interpreted
backward kernel would be pure overhead).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.lif import lif_fused_pallas
from repro.kernels.spiking_conv import (conv_grad_input_pallas,
                                        conv_grad_input_xla,
                                        conv_grad_weights_xla, kernel_name,
                                        skip_table_fraction,
                                        spiking_conv_pallas)
from repro.kernels.spiking_conv_lif import (ConvLIFOpts, _largest_divisor,
                                            spiking_conv_lif_train)

__all__ = ["spiking_conv", "lif_fused", "spiking_conv_lif",
           "spiking_conv_lif_chunked", "skip_table_fraction",
           "default_interpret"]


def default_interpret() -> bool:
    """True (Pallas interpret mode) unless JAX's default backend is a TPU."""
    backend = jax.default_backend()
    if backend != "tpu":
        _warn_interpret(backend)
    return backend != "tpu"


@functools.lru_cache(maxsize=None)
def _warn_interpret(backend: str) -> None:
    # once per backend and process: interpret mode is a validation path
    from repro.obs.log import get_logger
    get_logger("kernels").warning(
        "default backend is %r, not tpu: Pallas kernels run in interpret "
        "mode", backend)


def _default_bwd(interpret: bool) -> str:
    # compiled TPU -> mirror Pallas backward kernels; interpret mode (CPU
    # validation) -> XLA fallback (an interpreted backward kernel is a
    # Python loop, not a performance surface)
    return "xla" if interpret else "pallas"


class _ConvOpts(NamedTuple):
    interpret: bool
    aprc: bool = True
    block_rows: int = 8
    num_groups: int = 4
    bwd: str = "xla"
    layer: Optional[int] = None   # network layer, for the kernels' names


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _spiking_conv_vjp(opts: _ConvOpts, spikes, w, bias):
    return spiking_conv_pallas(
        spikes, w, bias, aprc=opts.aprc, block_rows=opts.block_rows,
        num_groups=opts.num_groups, interpret=opts.interpret,
        name=kernel_name("conv", opts.layer))


def _spiking_conv_fwd(opts, spikes, w, bias):
    return _spiking_conv_vjp(opts, spikes, w, bias), (spikes, w, bias)


def _spiking_conv_bwd(opts, res, g):
    spikes, w, bias = res
    if opts.bwd == "pallas":
        groups = _largest_divisor(w.shape[2], opts.num_groups)
        dx = conv_grad_input_pallas(
            g, w, aprc=opts.aprc, block_rows=opts.block_rows,
            num_groups=groups, interpret=opts.interpret,
            name=kernel_name("conv_grad_input", opts.layer))
    else:
        dx = conv_grad_input_xla(g, w, aprc=opts.aprc)
    dw, db = conv_grad_weights_xla(spikes, g, aprc=opts.aprc, r=w.shape[0])
    return (dx.astype(spikes.dtype), dw.astype(w.dtype), db.astype(bias.dtype))


_spiking_conv_vjp.defvjp(_spiking_conv_fwd, _spiking_conv_bwd)


def spiking_conv(
    spikes: jax.Array, w: jax.Array, bias: jax.Array,
    *, aprc: bool = True, block_rows: int = 8, num_groups: int = 4,
    interpret: Optional[bool] = None, bwd: Optional[str] = None,
    layer: Optional[int] = None,
) -> jax.Array:
    """Spike-driven conv (see kernels.spiking_conv).  Output matches
    ``ref.spiking_conv_ref`` exactly up to float accumulation order.
    Differentiable (transposed-tap backward).  ``layer``, the network
    layer's index, names the kernels ``conv.l<layer>`` and
    ``conv_grad_input.l<layer>`` (``spiking_conv.kernel_name``)."""
    if interpret is None:
        interpret = default_interpret()
    if bwd is None:
        bwd = _default_bwd(interpret)
    opts = _ConvOpts(aprc=aprc, block_rows=block_rows, num_groups=num_groups,
                     interpret=interpret, bwd=bwd, layer=layer)
    return _spiking_conv_vjp(opts, spikes, w, bias)


def lif_fused(
    v: jax.Array, z: jax.Array, v_th: float | jax.Array,
    *, block_rows: int = 8, block_cols: int = 128,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused membrane update + fire + reset over (N, C) tensors.

    Shapes not divisible by the block are handled by padding here (the
    kernel itself requires divisibility)."""
    if interpret is None:
        interpret = default_interpret()
    n, c = v.shape
    pn = -(-n // block_rows) * block_rows
    pc = -(-c // block_cols) * block_cols
    vth_arr = jnp.asarray(v_th, jnp.float32)
    if (pn, pc) != (n, c):
        vp = jnp.zeros((pn, pc), v.dtype).at[:n, :c].set(v)
        zp = jnp.zeros((pn, pc), z.dtype).at[:n, :c].set(z)
        v2, s2 = lif_fused_pallas(vp, zp, vth_arr, block_rows=block_rows,
                                  block_cols=block_cols, interpret=interpret)
        return v2[:n, :c], s2[:n, :c]
    return lif_fused_pallas(v, z, vth_arr, block_rows=block_rows,
                            block_cols=block_cols, interpret=interpret)


def spiking_conv_lif(
    spikes: jax.Array, v0: jax.Array, w: jax.Array, bias: jax.Array,
    *, v_th: float = 1.0, aprc: bool = True, block_rows: int = 8,
    num_groups: int = 4, interpret: Optional[bool] = None,
    surrogate_alpha: float = 10.0, surrogate_kind: str = "fast_sigmoid",
    bwd: Optional[str] = None, spec: Optional[object] = None,
    layer: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused conv+LIF over a whole spike train (see kernels.spiking_conv_lif).

    spikes: (T, B, H, W, Cin);  v0: (B, E, E', Cout).  Returns the output
    spike train and final membrane, matching the composition
    ``ref.spiking_conv_ref`` + ``ref.lif_fused_ref`` scanned over T.

    Differentiable: ``jax.grad`` applies the selectable surrogate
    (``surrogate_kind`` in core.surrogate.SURROGATE_KINDS, scaled by
    ``surrogate_alpha``) through reverse-time BPTT — the same gradient the
    ``backend="ref"`` scan computes.  ``layer``, the network layer's index,
    names its kernels ``conv_lif_fwd.l<layer>``, ``lif_bwd.l<layer>`` and
    ``conv_grad_input.l<layer>`` (``spiking_conv.kernel_name``).

    ``spec`` (a ``repro.api.ExecutionSpec``, duck-typed) overrides the
    surrogate kwargs — the facade threads one validated record all the way
    into the kernel dispatch instead of re-plumbing loose kwargs per layer.
    Spec fields this op cannot apply are loud errors, never silent drops:
    it IS the pallas kernel (``spec.backend`` must be "pallas"), T comes
    from the spike train's leading axis, and a schedule is applied by
    permuting the weights upstream (core.scheduler), not here.
    """
    if spec is not None:
        spec_backend = getattr(spec, "backend", None)
        if spec_backend is not None and spec_backend != "pallas":
            raise ValueError(
                f"spec.backend={spec_backend!r} cannot be applied by "
                f"ops.spiking_conv_lif — this op IS the pallas kernel; "
                f"route backend selection through snn_apply/Session")
        t_spec = getattr(spec, "timesteps", None)
        if t_spec is not None and t_spec != spikes.shape[0]:
            raise ValueError(
                f"spec.timesteps={t_spec} conflicts with the spike train's "
                f"T={spikes.shape[0]} — the kernel runs the train it is "
                f"given; resolve T upstream (repro.api.Session does this)")
        if getattr(spec, "resolved_schedule", lambda: None)() is not None:
            raise ValueError(
                "spec.schedule_mode cannot be applied by ops.spiking_conv_lif"
                " — the CBWS schedule permutes weights upstream "
                "(core.scheduler.permute_conv_params); pass pre-permuted "
                "weights or go through snn_apply with schedule=")
        chunk_t = getattr(spec, "chunk_timesteps", None)
        if chunk_t is not None:
            raise ValueError(
                f"spec.chunk_timesteps={chunk_t} cannot be applied by "
                f"ops.spiking_conv_lif — this op runs the whole train it is "
                f"given; chunk upstream via ops.spiking_conv_lif_chunked or "
                f"core.snn_apply_chunked (the serving engine does this)")
        surrogate_alpha = getattr(spec, "surrogate_alpha", surrogate_alpha)
        surrogate_kind = getattr(spec, "surrogate_kind", surrogate_kind)
    if interpret is None:
        interpret = default_interpret()
    if bwd is None:
        bwd = _default_bwd(interpret)
    opts = ConvLIFOpts(
        v_th=float(v_th), aprc=aprc, block_rows=block_rows,
        num_groups=num_groups, interpret=interpret,
        surrogate_alpha=float(surrogate_alpha),
        surrogate_kind=surrogate_kind, bwd=bwd, layer=layer)
    return spiking_conv_lif_train(opts, spikes, v0, w, bias)


def spiking_conv_lif_chunked(
    spikes: jax.Array, v0: jax.Array, w: jax.Array, bias: jax.Array,
    *, chunk_timesteps: int, v_th: float = 1.0, aprc: bool = True,
    block_rows: int = 8, num_groups: int = 4,
    interpret: Optional[bool] = None, surrogate_alpha: float = 10.0,
    surrogate_kind: str = "fast_sigmoid", bwd: Optional[str] = None,
    layer: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Chunked driver for the fused conv+LIF kernel: run the T-loop in
    segments of ``chunk_timesteps``, threading the membrane between
    segments (``v_final`` of one call is ``v0`` of the next).

    Bit-identical to the single whole-T ``spiking_conv_lif`` call for
    every partition of T: the kernel's T grid axis is strictly sequential
    per element, so a chunk boundary only materializes the carry it would
    have held in VMEM scratch.  Differentiable — each segment
    goes through ``spiking_conv_lif_train``'s custom_vjp and BPTT chains
    across segments through the carried membrane.
    """
    from repro.core.snn_model import chunk_lengths
    s_parts = []
    v = v0
    t0 = 0
    for c in chunk_lengths(spikes.shape[0], chunk_timesteps):
        s, v = spiking_conv_lif(
            spikes[t0:t0 + c], v, w, bias, v_th=v_th, aprc=aprc,
            block_rows=block_rows, num_groups=num_groups,
            interpret=interpret, surrogate_alpha=surrogate_alpha,
            surrogate_kind=surrogate_kind, bwd=bwd, layer=layer)
        s_parts.append(s)
        t0 += c
    return jnp.concatenate(s_parts, axis=0), v


# re-export oracles for test convenience
spiking_conv_ref = ref.spiking_conv_ref
lif_fused_ref = ref.lif_fused_ref
spiking_conv_lif_ref = ref.spiking_conv_lif_ref
