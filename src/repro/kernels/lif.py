"""Fused LIF update kernel (Pallas, TPU target, VPU-shaped).

Naively, Eq. (1)+(3) is three elementwise HBM round trips
(v+=z; s=v>=th; v-=th*s).  This kernel fuses them into one read of (v, z)
and one write of (v', s) per tile — the memory-bound term drops ~2.5x.

``z`` here is still a materialized synaptic-current tensor; the layer-level
fusion that never writes dV to HBM at all (and keeps ``v`` in VMEM
across all T timesteps) is ``kernels/spiking_conv_lif.py`` — this kernel
remains the building block for timestep-streaming callers and non-conv
layers.  See docs/kernels.md for the memory-traffic model.

Tiles are (block_rows, block_cols) over a 2-D flattened view; block_cols
should be a multiple of 128 (VPU lane width), block_rows a multiple of 8.
The threshold is a scalar read from SMEM.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["lif_fused_kernel", "lif_fused_pallas"]


def lif_fused_kernel(v_ref, z_ref, vth_ref, v_out_ref, s_out_ref):
    v = v_ref[...].astype(jnp.float32)
    z = z_ref[...].astype(jnp.float32)
    v_th = vth_ref[0]
    vf = v + z
    s = (vf >= v_th).astype(jnp.float32)
    v_out_ref[...] = (vf - v_th * s).astype(v_out_ref.dtype)
    s_out_ref[...] = s.astype(s_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def lif_fused_pallas(
    v: jax.Array, z: jax.Array, v_th: jax.Array,
    *, block_rows: int = 8, block_cols: int = 128, interpret: bool,
) -> Tuple[jax.Array, jax.Array]:
    """v, z: (N, C). Returns (v_new, spikes). v_th: () scalar array."""
    n, c = v.shape
    assert n % block_rows == 0 and c % block_cols == 0, (v.shape, block_rows, block_cols)
    grid = (n // block_rows, c // block_cols)
    spec = pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j))
    vth_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        lif_fused_kernel,
        grid=grid,
        in_specs=[spec, spec, vth_spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((n, c), v.dtype),
                   jax.ShapeDtypeStruct((n, c), v.dtype)],
        interpret=interpret,
    )(v, z, jnp.reshape(v_th.astype(jnp.float32), (1,)))
