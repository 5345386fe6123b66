"""Fused spiking-conv + LIF kernel (Pallas, TPU target) — forward and VJP.

One kernel runs a whole conv layer for **all T timesteps**: the implicit-GEMM
tap loop of ``spiking_conv.py`` and the LIF integrate/fire/reset of ``lif.py``
are fused, and the timestep walk is the innermost (sequential) grid axis,
so the membrane potential stays in VMEM scratch between steps and never
round-trips through HBM.

Why (memory-traffic model, per layer, T timesteps):

  unfused (seed)             fused (this kernel)
  ------------------------   -------------------------------------------
  dV:  T writes + T reads    never materialized in HBM
  v:   T reads + T writes    1 read (v0) + 1 write (v_T)
  s:   T writes              T writes
  x:   T whole-image reads   T halo-block reads (pl.Element offsets)
       per grid cell

i.e. per element the HBM round trips drop from ~5T to ~T+2 — the fusion of
Sommer et al. (arXiv 2203.12437, accumulate-into-neuron) combined with
FireFly v2's (arXiv 2309.16158) spatiotemporal (T x B) batching.

Grid: ``(B, n_row_blocks, num_groups, T)`` — batch x row-block x CBWS
channel lane x timestep.  Each cell holds one timestep's blocks, so VMEM
use does not grow with T (a whole-T block overflowed VMEM at snn-seg
widths).  The spike-count skip table ``counts[t, b, i]`` (SMEM) covers the
full spatio-temporal workload (paper Fig. 2): a timestep whose receptive
rows carry no spikes skips all R*R matmuls and integrates bias only.
Outputs and membranes use the kernel layout of
``spiking_conv.to_block_layout`` (see that module's doc for why).

Sequencing caveat: the input spike train for all T must be known, so this
kernel runs in the **layer-by-layer** (time-batched) execution order of
``core.snn_model.snn_apply(backend="pallas")``, not the timestep-outer
order.  With ``T=1`` it degenerates to a drop-in fused replacement for
``spiking_conv + lif_fused`` inside a timestep-outer scan
(``core.snn_layers.spiking_conv_step(backend="pallas")``).

Training (``spiking_conv_lif_train``, a ``jax.custom_vjp``): the primal is
the forward-only kernel above; under ``jax.grad`` the fwd rule reruns it
with an extra output — the **pre-reset membrane** ``u_t = v_{t-1} + dV_t``,
exactly the residual the surrogate needs — and the bwd rule runs surrogate
BPTT in the time-batched order:

  1. reverse-time elementwise scan (``lif_bwd_pallas``, whose T grid
     axis walks time backward / XLA fallback):
         lam_t = c_t + (g_s[t] - v_th * c_t) * sg(u_t - v_th)
         c_{t-1} = lam_t,        dv0 = lam_0
     with ``sg`` the selectable surrogate (core.surrogate.surrogate_grad)
     and ``c_{T-1} = g_v`` the final-membrane cotangent.  ``lam_t`` is the
     cotangent of the synaptic current dV_t.
  2. conv backward over the folded (T*B) batch: d(input) via the
     transposed-tap implicit GEMM (``conv_grad_input_pallas`` — the exact
     mirror of the forward tap loop — or the XLA conv fallback), and
     (dw, db) via the tap-loop of folded matmuls.

This is the same gradient the ``backend="ref"``/``"batched"`` surrogate
scans compute, reordered — parity is asserted in tests/test_snn_backends.py.

The BlockSpec contracts at each ``pl.pallas_call`` site (index-map arity
vs grid rank, block rank vs index-map return arity, block dims dividing
the padded shapes, operand/spec counts) are checked statically by
``repro.analysis``'s pallas-consistency rule (docs/analysis.md), which
resolves the named ``seq_spec``/``mem_spec`` assignments and the
``[base] + extra`` list concatenation below (``extra`` is an
``[x] if save_u else []`` conditional) and knows ``pl.Element`` /
``pl.squeezed`` block dims and whole-array SMEM specs — keep spec plumbing
in that resolvable shape.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.surrogate import surrogate_grad
from repro.kernels.spiking_conv import (conv_grad_input_pallas,
                                        conv_grad_input_xla,
                                        conv_grad_weights_xla,
                                        from_block_layout, group_taps,
                                        kernel_name, row_block_counts,
                                        tap_gemm, to_block_layout)

__all__ = ["spiking_conv_lif_pallas", "spiking_conv_lif_fwd_pallas",
           "spiking_conv_lif_train", "ConvLIFOpts", "lif_bwd_pallas",
           "lif_bwd_xla"]


def _make_kernel(r: int, block_rows: int, w_out: int, n_blocks: int,
                 v_th: float, save_u: bool = False):
    def kernel(counts_ref, x_ref, w_ref, b_ref, v0_ref, s_ref, v_ref,
               *rest):
        *maybe_u_ref, v_scr = rest
        b = pl.program_id(0)
        i = pl.program_id(1)
        t = pl.program_id(3)
        n_batch = pl.num_programs(0)
        bias = b_ref[...].astype(jnp.float32)      # (Cout_blk, 1)

        @pl.when(t == 0)
        def _load_v0():
            v_scr[...] = v0_ref[...].astype(jnp.float32)

        def compute():
            # halo block for timestep t: (block_rows+R-1, W_pad, Cin)
            return tap_gemm(x_ref, w_ref, r, block_rows, w_out) + bias

        def skip():
            # spatio-temporal skip: no spikes feed (t, b, i) — bias only
            return jnp.broadcast_to(bias, v_scr.shape)

        count = counts_ref[(t * n_batch + b) * n_blocks + i]
        v = v_scr[...] + jax.lax.cond(count == 0, skip, compute)  # Eq. (1)+(2)
        if save_u:
            # pre-reset membrane: the surrogate's backward residual
            maybe_u_ref[0][...] = v.astype(maybe_u_ref[0].dtype)
        s = (v >= v_th).astype(jnp.float32)        # Eq. (3): fire
        v = v - v_th * s                           # reset by subtraction
        s_ref[...] = s.astype(s_ref.dtype)
        v_scr[...] = v

        @pl.when(t == pl.num_programs(3) - 1)
        def _store_v():
            v_ref[...] = v.astype(v_ref.dtype)

    return kernel


def _fused_call(spikes, v0, w, bias, *, v_th, aprc, block_rows, num_groups,
                interpret, save_u, name):
    T, B, H, W, Cin = spikes.shape
    R, _, _, Cout = w.shape
    assert Cout % num_groups == 0, (Cout, num_groups)
    cout_blk = Cout // num_groups

    if aprc:
        e_h, e_w = H + R - 1, W + R - 1
        pad_lo = R - 1
    else:
        e_h, e_w = H, W
        pad_lo = (R - 1) // 2
    assert v0.shape == (B, e_h, e_w, Cout), (v0.shape, (B, e_h, e_w, Cout))

    n_blocks = -(-e_h // block_rows)                  # ceil
    e_h_pad = n_blocks * block_rows
    h_pad = e_h_pad + R - 1
    w_pad = e_w + R - 1
    halo_rows = block_rows + R - 1

    x = jnp.zeros((T, B, h_pad, w_pad, Cin), spikes.dtype)
    x = jax.lax.dynamic_update_slice(x, spikes, (0, 0, pad_lo, pad_lo, 0))

    # skip table over the full (T, B, row-block) spatio-temporal workload,
    # flat (T * B * n_blocks,) int32, whole in SMEM
    counts = row_block_counts(
        x.reshape(T * B, h_pad, w_pad, Cin), R, block_rows, n_blocks
    ).reshape(-1)

    vp = jnp.zeros((B, e_h_pad, e_w, Cout), v0.dtype)
    vp = jax.lax.dynamic_update_slice(vp, v0, (0, 0, 0, 0))

    # grid (B, row-block, channel group, T): T is the innermost,
    # sequential axis — one timestep's blocks per cell, the membrane
    # carried across it in VMEM scratch.  Outputs and membranes are in
    # spiking_conv.to_block_layout: (G, [T,] B, n_blocks, Cout/G, M)
    m = block_rows * e_w
    seq_spec = pl.BlockSpec(
        (pl.squeezed, pl.squeezed, pl.squeezed, pl.squeezed, cout_blk, m),
        lambda b, i, g, t: (g, t, b, i, 0, 0))
    mem_spec = pl.BlockSpec(
        (pl.squeezed, pl.squeezed, pl.squeezed, cout_blk, m),
        lambda b, i, g, t: (g, b, i, 0, 0))
    # the optional pre-reset membrane output (backward residual) rides as a
    # concatenated extra: both lists stay statically resolvable for the
    # pallas-consistency analysis rule
    extra_specs = [seq_spec] if save_u else []
    extra_shape = [
        jax.ShapeDtypeStruct(
            (num_groups, T, B, n_blocks, cout_blk, m), jnp.float32),
    ] if save_u else []
    out_specs = [seq_spec, mem_spec] + extra_specs
    out_shape = [
        jax.ShapeDtypeStruct(
            (num_groups, T, B, n_blocks, cout_blk, m), spikes.dtype),
        jax.ShapeDtypeStruct(
            (num_groups, B, n_blocks, cout_blk, m), v0.dtype),
    ] + extra_shape

    kernel = _make_kernel(R, block_rows, e_w, n_blocks, float(v_th),
                          save_u=save_u)
    outs = pl.pallas_call(
        kernel,
        name=name,
        grid=(B, n_blocks, num_groups, T),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                  # counts
            # halo input block per (t, b, i): element offsets (pl.Element)
            pl.BlockSpec((pl.squeezed, pl.squeezed, pl.Element(halo_rows),
                          pl.Element(w_pad), pl.Element(Cin)),
                         lambda b, i, g, t: (t, b, i * block_rows, 0, 0)),
            pl.BlockSpec((pl.squeezed, R, R, cout_blk, Cin),
                         lambda b, i, g, t: (g, 0, 0, 0, 0)),
            pl.BlockSpec((pl.squeezed, cout_blk, 1),
                         lambda b, i, g, t: (g, 0, 0)),
            mem_spec,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((cout_blk, m), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(counts, x, group_taps(w, num_groups),
      bias.reshape(num_groups, cout_blk, 1),
      to_block_layout(vp, num_groups, block_rows))
    outs = [from_block_layout(o, e_w) for o in outs]
    if save_u:
        s_out, v_out, u_out = outs
        return s_out[:, :, :e_h], v_out[:, :e_h], u_out[:, :, :e_h]
    s_out, v_out = outs
    return s_out[:, :, :e_h], v_out[:, :e_h]


@functools.partial(
    jax.jit,
    static_argnames=("v_th", "aprc", "block_rows", "num_groups", "interpret",
                     "name"))
def spiking_conv_lif_pallas(
    spikes: jax.Array,       # (T, B, H, W, Cin) binary input train
    v0: jax.Array,           # (B, E_h, E_w, Cout) initial membrane
    w: jax.Array,            # (R, R, Cin, Cout) — CBWS-permuted
    bias: jax.Array,         # (Cout,)
    *,
    v_th: float = 1.0,
    aprc: bool = True,
    block_rows: int = 8,
    num_groups: int = 4,
    interpret: bool,
    name: str | None = None,
):
    """Fused conv+LIF over a spike train (forward only).

    Returns ``(s, v_final)`` with ``s: (T, B, E_h, E_w, Cout)`` the output
    spike train and ``v_final: (B, E_h, E_w, Cout)`` the membrane after the
    last step; ``E = H+R-1`` (APRC) or ``H`` (same-pad).  ``name`` names
    the kernel (``spiking_conv.kernel_name``).
    """
    return _fused_call(spikes, v0, w, bias, v_th=v_th, aprc=aprc,
                       block_rows=block_rows, num_groups=num_groups,
                       interpret=interpret, save_u=False, name=name)


@functools.partial(
    jax.jit,
    static_argnames=("v_th", "aprc", "block_rows", "num_groups", "interpret",
                     "name"))
def spiking_conv_lif_fwd_pallas(
    spikes: jax.Array, v0: jax.Array, w: jax.Array, bias: jax.Array,
    *, v_th: float = 1.0, aprc: bool = True, block_rows: int = 8,
    num_groups: int = 4, interpret: bool, name: str | None = None,
):
    """Forward that additionally emits the **pre-reset membrane** train
    ``u: (T, B, E_h, E_w, Cout) f32`` — the saved residual of the VJP
    (``sg(u - v_th)`` is the surrogate factor of every step).

    Returns ``(s, v_final, u)``.
    """
    return _fused_call(spikes, v0, w, bias, v_th=v_th, aprc=aprc,
                       block_rows=block_rows, num_groups=num_groups,
                       interpret=interpret, save_u=True, name=name)


# ---------------------------------------------------------------------------
# Backward: reverse-time surrogate scan (Pallas kernel + XLA fallback)
# ---------------------------------------------------------------------------


def lif_bwd_xla(u: jax.Array, g_s: jax.Array, g_v: jax.Array, *,
                v_th: float, alpha: float, kind: str):
    """XLA fallback of the reverse-time LIF backward (see module doc).

    u: (T, ...) pre-reset membrane;  g_s: (T, ...) spike-train cotangent;
    g_v: (...) final-membrane cotangent.  Returns (lam: (T, ...), dv0).
    """
    surr = surrogate_grad(u - v_th, alpha, kind)

    def body(c, xs):
        g_s_t, surr_t = xs
        lam = c + (g_s_t - v_th * c) * surr_t
        return lam, lam

    dv0, lam_rev = jax.lax.scan(
        body, g_v.astype(jnp.float32),
        (g_s[::-1].astype(jnp.float32), surr[::-1]))
    return lam_rev[::-1], dv0


def _make_bwd_kernel(v_th: float, alpha: float, kind: str):
    def kernel(u_ref, gs_ref, gv_ref, lam_ref, dv0_ref, c_scr):
        # grid axis 3 walks time backward: cell j holds timestep T-1-j
        j = pl.program_id(3)

        @pl.when(j == 0)
        def _load_gv():
            c_scr[...] = gv_ref[...].astype(jnp.float32)

        c = c_scr[...]
        u = u_ref[...].astype(jnp.float32)
        g_s = gs_ref[...].astype(jnp.float32)
        surr = surrogate_grad(u - v_th, alpha, kind)       # plain jnp
        lam = c + (g_s - v_th * c) * surr
        lam_ref[...] = lam.astype(lam_ref.dtype)
        c_scr[...] = lam

        @pl.when(j == pl.num_programs(3) - 1)
        def _store_dv0():
            dv0_ref[...] = lam.astype(dv0_ref.dtype)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("v_th", "alpha", "kind", "block_rows", "num_groups",
                     "interpret", "name"))
def lif_bwd_pallas(
    u: jax.Array,            # (T, B, E_h, E_w, Cout) pre-reset membrane
    g_s: jax.Array,          # (T, B, E_h, E_w, Cout) spike cotangent
    g_v: jax.Array,          # (B, E_h, E_w, Cout) final-membrane cotangent
    *,
    v_th: float, alpha: float, kind: str,
    block_rows: int = 8, num_groups: int = 4, interpret: bool,
    name: str | None = None,
):
    """Pallas reverse-time LIF backward: the same (B, row-block,
    channel-group) grid as the forward kernel plus an innermost sequential
    T axis walked backward, the running current-cotangent carried across
    it in VMEM scratch.

    Returns ``(lam: (T, B, E_h, E_w, Cout) f32, dv0: (B, E_h, E_w, Cout))``.
    """
    T, B, e_h, e_w, Cout = u.shape
    assert Cout % num_groups == 0, (Cout, num_groups)
    cout_blk = Cout // num_groups
    n_blocks = -(-e_h // block_rows)
    e_h_pad = n_blocks * block_rows

    def pad_rows(a):
        pads = [(0, 0)] * a.ndim
        pads[-3] = (0, e_h_pad - e_h)
        return to_block_layout(jnp.pad(a, pads), num_groups, block_rows)

    up, gsp, gvp = pad_rows(u), pad_rows(g_s), pad_rows(g_v)

    m = block_rows * e_w
    seq_spec = pl.BlockSpec(
        (pl.squeezed, pl.squeezed, pl.squeezed, pl.squeezed, cout_blk, m),
        lambda b, i, g, j: (g, T - 1 - j, b, i, 0, 0))
    mem_spec = pl.BlockSpec(
        (pl.squeezed, pl.squeezed, pl.squeezed, cout_blk, m),
        lambda b, i, g, j: (g, b, i, 0, 0))
    kernel = _make_bwd_kernel(float(v_th), float(alpha), kind)
    lam, dv0 = pl.pallas_call(
        kernel,
        name=name,
        grid=(B, n_blocks, num_groups, T),
        in_specs=[seq_spec, seq_spec, mem_spec],
        out_specs=[seq_spec, mem_spec],
        out_shape=[
            jax.ShapeDtypeStruct(
                (num_groups, T, B, n_blocks, cout_blk, m), jnp.float32),
            jax.ShapeDtypeStruct(
                (num_groups, B, n_blocks, cout_blk, m), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((cout_blk, m), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(up, gsp, gvp)
    return (from_block_layout(lam, e_w)[:, :, :e_h],
            from_block_layout(dv0, e_w)[:, :e_h])


# ---------------------------------------------------------------------------
# The trainable fused op: jax.custom_vjp
# ---------------------------------------------------------------------------


class ConvLIFOpts(NamedTuple):
    """Hashable static config of the trainable fused op (nondiff arg 0).
    ``interpret`` has no default: ``ops`` resolves it in one place."""
    interpret: bool
    v_th: float = 1.0
    aprc: bool = True
    block_rows: int = 8
    num_groups: int = 4
    surrogate_alpha: float = 10.0
    surrogate_kind: str = "fast_sigmoid"
    bwd: str = "xla"         # "pallas" | "xla" backward implementation
    layer: Optional[int] = None   # network layer, for the kernels' names


def _largest_divisor(n: int, cap: int) -> int:
    return max(g for g in range(1, cap + 1) if n % g == 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def spiking_conv_lif_train(opts: ConvLIFOpts, spikes, v0, w, bias):
    """Differentiable fused conv+LIF: forward == ``spiking_conv_lif_pallas``
    (Heaviside spikes), backward == surrogate BPTT (see module doc).

    The primal runs the plain forward kernel — inference pays nothing for
    differentiability; only under ``jax.grad`` does the fwd rule rerun the
    kernel with the pre-reset-membrane output as the saved residual.
    """
    return spiking_conv_lif_pallas(
        spikes, v0, w, bias, v_th=opts.v_th, aprc=opts.aprc,
        block_rows=opts.block_rows, num_groups=opts.num_groups,
        interpret=opts.interpret,
        name=kernel_name("conv_lif_fwd", opts.layer))


def _train_fwd(opts, spikes, v0, w, bias):
    s, v_final, u = spiking_conv_lif_fwd_pallas(
        spikes, v0, w, bias, v_th=opts.v_th, aprc=opts.aprc,
        block_rows=opts.block_rows, num_groups=opts.num_groups,
        interpret=opts.interpret,
        name=kernel_name("conv_lif_fwd", opts.layer))
    return (s, v_final), (spikes, w, bias, u)


def _train_bwd(opts, res, cts):
    spikes, w, bias, u = res
    g_s, g_v = cts
    T, B = spikes.shape[:2]
    R = w.shape[0]

    if opts.bwd == "pallas":
        lam, dv0 = lif_bwd_pallas(
            u, g_s, g_v, v_th=opts.v_th, alpha=opts.surrogate_alpha,
            kind=opts.surrogate_kind, block_rows=opts.block_rows,
            num_groups=opts.num_groups, interpret=opts.interpret,
            name=kernel_name("lif_bwd", opts.layer))
    else:
        lam, dv0 = lif_bwd_xla(
            u, g_s.astype(jnp.float32), g_v.astype(jnp.float32),
            v_th=opts.v_th, alpha=opts.surrogate_alpha,
            kind=opts.surrogate_kind)

    # conv backward over the folded (T*B) spatio-temporal batch
    lam2 = lam.reshape((T * B,) + lam.shape[2:])
    x2 = spikes.reshape((T * B,) + spikes.shape[2:])
    if opts.bwd == "pallas":
        cin_groups = _largest_divisor(w.shape[2], opts.num_groups)
        dx = conv_grad_input_pallas(
            lam2, w, aprc=opts.aprc, block_rows=opts.block_rows,
            num_groups=cin_groups, interpret=opts.interpret,
            name=kernel_name("conv_grad_input", opts.layer))
    else:
        dx = conv_grad_input_xla(lam2, w, aprc=opts.aprc)
    dw, db = conv_grad_weights_xla(x2, lam2, aprc=opts.aprc, r=R)

    return (dx.reshape(spikes.shape).astype(spikes.dtype),
            dv0.astype(g_v.dtype),
            dw.astype(w.dtype), db.astype(bias.dtype))


spiking_conv_lif_train.defvjp(_train_fwd, _train_bwd)
