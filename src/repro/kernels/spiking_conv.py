"""Spike-driven convolution kernel (Pallas, TPU target).

TPU-native adaptation of Skydiver's event-driven SPE array (DESIGN §2/§6):

  * implicit GEMM: for each filter tap (dy, dx), an MXU matmul
        (Cout_group, Cin) @ (rows x W_out, Cin)^T
    accumulates dV — the adder-tree of the paper's SPE cluster becomes the
    MXU systolic reduction over Cin.
  * lane granularity: grid axis 2 walks CBWS-permuted *output-channel
    groups* (the "filter-based SPE clusters"); grid axis 1 walks row blocks
    (the "4 streams" of a SPE, generalized).
  * spatio-temporal skip: an SMEM table ``counts[b, i]`` holds the
    spike population of the input rows feeding row-block i of image b
    (b folds **batch x timestep** — callers running the time-batched layer
    pipeline fold ``(T, B) -> T*B`` so the skip table covers the full
    spatio-temporal workload of paper Fig. 2).  ``pl.when(count == 0)``
    skips the whole tile — the block-granular analogue of the paper's
    per-spike skip.

Memory-traffic model (per grid cell, halo BlockSpec):

  * input block: ``(block_rows + R - 1) x W_pad x Cin`` — only the halo
    rows feeding this output row-block are loaded (``pl.Element`` on every
    block dim: the index map returns element offsets, row-block i starts at
    row ``i * block_rows``), so total input traffic is
    ``~(1 + (R-1)/block_rows) x num_groups`` image reads.
  * weights: one ``(R, R, Cin, Cout/num_groups)`` tap block per cell.
  * output: each dV element is written exactly once.

Weights arrive already CBWS-permuted (see core.scheduler); the kernel sees
only equal-size contiguous channel groups.

TPU (Mosaic) layout rules the specs follow:

  * the channel-group axis is a *leading* array axis of weights, bias and
    outputs, so the last two block dims are always whole array dims —
    Mosaic requires them to be whole or multiples of (8, 128), and a
    ``Cout/G``-wide slice of a ``Cout``-wide last dim is neither;
  * each tap computes dV transposed, ``(Cout/G, rows x W_out)``, and the
    outputs keep that orientation (``to_block_layout``): pixels fill the
    128 lanes, the few channels of a group the sublanes.  A ``Cout/G``-wide
    last dim would pad to 128 lanes in HBM (32x at ``Cout/G = 4``), which
    did not fit snn-seg in a 16 GB v5e;
  * the skip table lives in SMEM (scalar reads by grid position);
  * tap tiles are static slices of the halo block.

The input spike train keeps the canonical (..., W, Cin) layout (Cin pads
to 128 lanes), and block sizing is not tuned; the kernels are also
validated in interpret mode.

The BlockSpec contracts at each ``pl.pallas_call`` site here (index-map
arity vs grid rank, block rank vs index-map return arity, block dims
dividing the padded shapes, operand/spec counts) are checked statically by
``repro.analysis``'s pallas-consistency rule (docs/analysis.md) — keep
grid/spec edits in a shape the checker can resolve (literal tuples, or
names assigned once in the same function).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["spiking_conv_kernel", "spiking_conv_pallas", "row_block_counts",
           "skip_table_fraction", "conv_grad_input_xla",
           "conv_grad_input_pallas", "conv_grad_weights_xla", "conv_pads",
           "to_block_layout", "from_block_layout", "group_taps", "tap_gemm",
           "kernel_name"]


def kernel_name(kind: str, layer: int | None) -> str | None:
    """The stable name of one network layer's kernel, ``<kind>.l<layer>``
    (``conv``, ``conv_lif_fwd``, ``lif_bwd``, ``conv_grad_input``), or None
    without a layer.  It is the ``pallas_call``'s name, and so the name of
    its HLO instruction and of its op in a profiler trace, whatever
    ``jvp`` or ``transpose`` wraps the call."""
    return None if layer is None else f"{kind}.l{layer}"


def conv_pads(r: int, aprc: bool) -> tuple:
    """(pad_lo, pad_hi) of the forward conv; APRC = full, else SAME."""
    if aprc:
        return r - 1, r - 1
    lo = (r - 1) // 2
    return lo, r - 1 - lo


def to_block_layout(a: jax.Array, groups: int, block_rows: int) -> jax.Array:
    """Canonical (..., H_pad, W, C) -> kernel layout
    (G, ..., n_blocks, C/G, block_rows * W).

    Channel group g is leading slice g, and within a row block each channel
    is one lane-major row of ``block_rows * W`` pixels — the orientation of
    ``tap_gemm``'s result, so the last two block dims are whole array dims
    and the pixels, not the few channels of a group, fill the lanes."""
    *lead, hp, w, c = a.shape
    n = len(lead)
    a = a.reshape(tuple(lead) + (hp // block_rows, block_rows, w, groups,
                                 c // groups))
    # (lead..., nb, br, w, G, cb) -> (G, lead..., nb, cb, br, w)
    a = a.transpose((n + 3,) + tuple(range(n)) + (n, n + 4, n + 1, n + 2))
    return a.reshape((groups,) + tuple(lead)
                     + (hp // block_rows, c // groups, block_rows * w))


def from_block_layout(a: jax.Array, w: int) -> jax.Array:
    """Inverse of ``to_block_layout``: -> (..., n_blocks * block_rows, W, C)."""
    g, *lead, nb, cb, m = a.shape
    n = len(lead)
    a = a.reshape((g,) + tuple(lead) + (nb, cb, m // w, w))
    # (G, lead..., nb, cb, br, w) -> (lead..., nb, br, w, G, cb)
    a = a.transpose(tuple(range(1, n + 1)) + (n + 1, n + 3, n + 4, 0, n + 2))
    return a.reshape(tuple(lead) + (nb * (m // w), w, g * cb))


def group_taps(w: jax.Array, groups: int) -> jax.Array:
    """(R, R, Cin, Cout) taps -> per-group transposed taps
    (G, R, R, Cout/G, Cin)."""
    r, _, cin, cout = w.shape
    return w.reshape(r, r, cin, groups, cout // groups).transpose(3, 0, 1, 4, 2)


def tap_gemm(x_ref, w_ref, r: int, block_rows: int, w_out: int):
    """The R*R implicit-GEMM tap loop over one halo block.

    ``x_ref``: (block_rows + R - 1, W_pad, Cin) receptive rows;  ``w_ref``:
    (R, R, C_blk, Cin) transposed taps.  Each tap is a static slice of the
    block and one (C_blk, Cin) x (block_rows * w_out, Cin)^T MXU matmul at
    full f32 precision.  Returns dV as (C_blk, block_rows * w_out) f32."""
    cin = x_ref.shape[-1]
    m = block_rows * w_out
    acc = jnp.zeros((w_ref.shape[-2], m), jnp.float32)
    for dy in range(r):                                # R*R MXU matmuls
        for dx in range(r):
            tile = x_ref[dy:dy + block_rows, dx:dx + w_out, :]
            acc = acc + jax.lax.dot_general(
                w_ref[dy, dx].astype(jnp.float32),
                tile.astype(jnp.float32).reshape(m, cin),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
    return acc


def _make_kernel(r: int, block_rows: int, w_out: int, n_blocks: int):
    def kernel(counts_ref, x_ref, w_ref, b_ref, o_ref):
        b = pl.program_id(0)
        i = pl.program_id(1)
        count = counts_ref[b * n_blocks + i]
        bias = b_ref[...].astype(jnp.float32)          # (Cout_blk, 1)

        @pl.when(count == 0)
        def _skip():
            # no spikes feed this row block: dV is bias only
            o_ref[...] = jnp.broadcast_to(
                bias, o_ref.shape).astype(o_ref.dtype)

        @pl.when(count != 0)
        def _compute():
            # halo block: only the block_rows + R - 1 receptive rows
            out = tap_gemm(x_ref, w_ref, r, block_rows, w_out) + bias
            o_ref[...] = out.astype(o_ref.dtype)

    return kernel


def row_block_counts(spikes_padded: jax.Array, r: int, block_rows: int,
                     n_blocks: int) -> jax.Array:
    """counts[b, i] = #spikes in padded input rows [i*br, i*br + br + r - 1)
    — exactly the receptive rows of output row-block i.

    Counts *nonzero* entries (not the value sum): the first layer feeds the
    analog direct-coded frame through the same kernel, and a value sum < 1
    would truncate to 0 under the int cast and wrongly skip a live block."""
    b = spikes_padded.shape[0]
    row_tot = (spikes_padded != 0).sum(axis=(2, 3))   # (B, H_pad)
    # windowed sum over rows via cumulative sum
    cs = jnp.cumsum(row_tot, axis=1)
    cs = jnp.concatenate([jnp.zeros((b, 1), cs.dtype), cs], axis=1)
    starts = jnp.arange(n_blocks) * block_rows
    ends = jnp.minimum(starts + block_rows + r - 1, row_tot.shape[1])
    win = cs[:, ends] - cs[:, starts]                 # (B, n_blocks)
    return win.astype(jnp.int32)


def skip_table_fraction(spikes: jax.Array, r: int, *, aprc: bool = True,
                        block_rows: int = 8) -> jax.Array:
    """Fraction of the fused kernel's (T, B, row-block) skip-table cells
    that are skipped (zero receptive spikes) — the observable sparsity win
    of the spatio-temporal skip (paper Fig. 2), without running the conv.

    ``spikes`` is the (T, B, H, W, Cin) input train of one fused layer;
    the padding replicates ``spiking_conv_lif._fused_call`` exactly, so
    this counts precisely the cells whose R*R matmuls that kernel elides.
    Traceable (pure jnp) — the time-batched model computes it inline and
    XLA drops it when the caller only consumes logits."""
    t, b, h, w, cin = spikes.shape
    if aprc:
        e_h, e_w = h + r - 1, w + r - 1
        pad_lo = r - 1
    else:
        e_h, e_w = h, w
        pad_lo = (r - 1) // 2
    n_blocks = -(-e_h // block_rows)                  # ceil
    h_pad = n_blocks * block_rows + r - 1
    w_pad = e_w + r - 1
    x = jnp.zeros((t * b, h_pad, w_pad, cin), spikes.dtype)
    x = jax.lax.dynamic_update_slice(
        x, spikes.reshape(t * b, h, w, cin), (0, pad_lo, pad_lo, 0))
    counts = row_block_counts(x, r, block_rows, n_blocks)
    return jnp.mean((counts == 0).astype(jnp.float32))


@functools.partial(
    jax.jit,
    static_argnames=("aprc", "block_rows", "num_groups", "interpret", "name"))
def spiking_conv_pallas(
    spikes: jax.Array,       # (B, H, W, Cin) binary; B may fold T x batch
    w: jax.Array,            # (R, R, Cin, Cout) — CBWS-permuted
    bias: jax.Array,         # (Cout,)
    *,
    aprc: bool = True,
    block_rows: int = 8,
    num_groups: int = 4,
    interpret: bool,
    name: str | None = None,
) -> jax.Array:
    """Returns dV: (B, E_h, E_w, Cout); E = H+R-1 (APRC) or H (same-pad).
    ``name`` names the kernel (``kernel_name``)."""
    B, H, W, Cin = spikes.shape
    R, _, _, Cout = w.shape
    assert Cout % num_groups == 0, (Cout, num_groups)
    cout_blk = Cout // num_groups

    if aprc:
        e_h, e_w = H + R - 1, W + R - 1
        pad_lo = R - 1
    else:
        e_h, e_w = H, W
        pad_lo = (R - 1) // 2

    n_blocks = -(-e_h // block_rows)                  # ceil
    e_h_pad = n_blocks * block_rows
    # rows of padded input required: e_h_pad + R - 1
    h_pad = e_h_pad + R - 1
    w_pad = e_w + R - 1
    x = jnp.zeros((B, h_pad, w_pad, Cin), spikes.dtype)
    x = jax.lax.dynamic_update_slice(x, spikes, (0, pad_lo, pad_lo, 0))

    # flat (B * n_blocks,) int32 skip table, whole in SMEM
    counts = row_block_counts(x, R, block_rows, n_blocks).reshape(-1)
    halo_rows = block_rows + R - 1

    kernel = _make_kernel(R, block_rows, e_w, n_blocks)
    out = pl.pallas_call(
        kernel,
        name=name,
        grid=(B, n_blocks, num_groups),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                  # counts
            # halo input block: element offsets (pl.Element) — row-block i
            # reads exactly its block_rows + R - 1 receptive rows
            pl.BlockSpec((pl.squeezed, pl.Element(halo_rows),
                          pl.Element(w_pad), pl.Element(Cin)),
                         lambda b, i, g: (b, i * block_rows, 0, 0)),
            pl.BlockSpec((pl.squeezed, R, R, cout_blk, Cin),
                         lambda b, i, g: (g, 0, 0, 0, 0)),
            pl.BlockSpec((pl.squeezed, cout_blk, 1),
                         lambda b, i, g: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((pl.squeezed, pl.squeezed, pl.squeezed,
                                cout_blk, block_rows * e_w),
                               lambda b, i, g: (g, b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (num_groups, B, n_blocks, cout_blk, block_rows * e_w),
            spikes.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(counts, x, group_taps(w, num_groups),
      bias.reshape(num_groups, cout_blk, 1))
    return from_block_layout(out, e_w)[:, :e_h]


spiking_conv_kernel = _make_kernel


# ---------------------------------------------------------------------------
# Backward-pass building blocks (consumed by the custom_vjp rules in
# spiking_conv_lif.py / ops.py).
#
# The transpose of the forward conv (pads (lo, hi)) is itself a conv of the
# output-cotangent with the spatially-flipped, channel-transposed taps
#     wt[dy, dx, co, ci] = w[R-1-dy, R-1-dx, ci, co]
# under pads (R-1-lo, R-1-hi): for APRC's full conv that degenerates to a
# VALID conv (no padding at all), for SAME it swaps (lo, hi).
# ---------------------------------------------------------------------------


def _transposed_taps(w: jax.Array) -> jax.Array:
    """(R, R, Cin, Cout) -> flipped (R, R, Cout, Cin) backward taps."""
    return w[::-1, ::-1].transpose(0, 1, 3, 2)


def conv_grad_input_xla(dz: jax.Array, w: jax.Array, *, aprc: bool
                        ) -> jax.Array:
    """dL/d(input spikes) from the dV cotangent — XLA fallback path.

    dz: (N, E_h, E_w, Cout) cotangent of the conv output;
    w:  (R, R, Cin, Cout) forward taps.  Returns (N, H, W, Cin).
    """
    r = w.shape[0]
    lo, hi = conv_pads(r, aprc)
    pad = (r - 1 - lo, r - 1 - hi)
    return jax.lax.conv_general_dilated(
        dz.astype(jnp.float32), _transposed_taps(w).astype(jnp.float32),
        window_strides=(1, 1), padding=(pad, pad),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _make_grad_input_kernel(r: int, block_rows: int, w_out: int):
    """Implicit-GEMM tap loop over the *transposed* taps — same MXU
    structure as the forward kernel, no skip table (the cotangent is
    dense) and no bias."""
    def kernel(g_ref, wt_ref, o_ref):
        # g_ref: (block_rows+R-1, W_pad, Cout);  wt_ref: (R, R, Cin_blk, Cout)
        o_ref[...] = tap_gemm(g_ref, wt_ref, r, block_rows, w_out).astype(
            o_ref.dtype)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("aprc", "block_rows", "num_groups", "interpret",
                              "name"))
def conv_grad_input_pallas(
    dz: jax.Array,           # (N, E_h, E_w, Cout) conv-output cotangent
    w: jax.Array,            # (R, R, Cin, Cout) forward taps
    *,
    aprc: bool = True,
    block_rows: int = 8,
    num_groups: int = 1,     # lanes over Cin (the *output* channels here)
    interpret: bool,
    name: str | None = None,
) -> jax.Array:
    """Pallas transposed-tap backward kernel: dL/d(input), (N, H, W, Cin)."""
    N, e_h, e_w, Cout = dz.shape
    R, _, Cin, _ = w.shape
    assert Cin % num_groups == 0, (Cin, num_groups)
    cin_blk = Cin // num_groups
    lo, hi = conv_pads(R, aprc)
    H, W = e_h + (R - 1) - lo - hi, e_w + (R - 1) - lo - hi
    # backward pads (R-1-lo, R-1-hi); pad rows further up to the row-block
    n_blocks = -(-H // block_rows)
    h_out_pad = n_blocks * block_rows
    h_pad = h_out_pad + R - 1
    w_pad = W + R - 1
    g = jnp.zeros((N, h_pad, w_pad, Cout), jnp.float32)
    g = jax.lax.dynamic_update_slice(
        g, dz.astype(jnp.float32), (0, R - 1 - lo, R - 1 - lo, 0))
    wt = group_taps(_transposed_taps(w).astype(jnp.float32),
                    num_groups)                  # (G, R, R, Cin/G, Cout)
    halo_rows = block_rows + R - 1

    kernel = _make_grad_input_kernel(R, block_rows, W)
    out = pl.pallas_call(
        kernel,
        name=name,
        grid=(N, n_blocks, num_groups),
        in_specs=[
            pl.BlockSpec((pl.squeezed, pl.Element(halo_rows),
                          pl.Element(w_pad), pl.Element(Cout)),
                         lambda b, i, g_: (b, i * block_rows, 0, 0)),
            pl.BlockSpec((pl.squeezed, R, R, cin_blk, Cout),
                         lambda b, i, g_: (g_, 0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((pl.squeezed, pl.squeezed, pl.squeezed,
                                cin_blk, block_rows * W),
                               lambda b, i, g_: (g_, b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (num_groups, N, n_blocks, cin_blk, block_rows * W), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(g, wt)
    return from_block_layout(out, W)[:, :H]


def conv_grad_weights_xla(x: jax.Array, dz: jax.Array, *, aprc: bool,
                          r: int) -> tuple:
    """(dL/dw, dL/db) from the dV cotangent — tap-loop of folded matmuls.

    x: (N, H, W, Cin) forward input;  dz: (N, E_h, E_w, Cout).
    dw[dy,dx,ci,co] = sum_{n,y,x} x_pad[n, y+dy, x+dx, ci] * dz[n, y, x, co]
    — one (Cin, N*E*E') @ (N*E*E', Cout) matmul per tap, the exact
    transpose of the forward implicit GEMM, at full f32 precision like the
    forward taps (XLA's default f32 matmul on a TPU rounds through bf16).
    """
    lo, hi = conv_pads(r, aprc)
    n, e_h, e_w, cout = dz.shape
    cin = x.shape[-1]
    xp = jnp.pad(x.astype(jnp.float32),
                 ((0, 0), (lo, hi), (lo, hi), (0, 0)))
    gz = dz.astype(jnp.float32).reshape(n * e_h * e_w, cout)
    rows = []
    for dy in range(r):
        cols = []
        for dx in range(r):
            tile = jax.lax.dynamic_slice(
                xp, (0, dy, dx, 0), (n, e_h, e_w, cin))
            cols.append(jnp.dot(tile.reshape(n * e_h * e_w, cin).T, gz,
                                precision=jax.lax.Precision.HIGHEST))
        rows.append(jnp.stack(cols))
    dw = jnp.stack(rows)                       # (R, R, Cin, Cout)
    db = dz.astype(jnp.float32).sum(axis=(0, 1, 2))
    return dw, db
