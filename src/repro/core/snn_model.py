"""The paper's two spiking networks, built from ``SNNConfig``.

  classification : 28x28-16c-32c-8c-10   (MNIST, §IV)
  segmentation   : 160x80x3-8C3-16C3-32C3-32C3-16C3-1C3-160x80x1 (MLND-Capstone)

Two execution orders, selected by ``snn_apply(..., backend=...)``:

``backend="ref"`` (timestep-outer, the seed path): ``lax.scan`` over ``T``
timesteps; every conv layer is a spiking LIF layer; the head (dense
classifier / final conv mask) accumulates membrane potential without firing.
Differentiable via the surrogate gradient — this is the training path.

``backend="batched"`` / ``backend="pallas"`` (layer-outer, time-batched):
each layer processes the **whole (T, B) spatio-temporal block** before the
next layer starts (FireFly v2, arXiv 2309.16158).  The convolution is
time-invariant, so it runs once over the folded ``T*B`` batch; only the
cheap elementwise LIF recurrence scans over ``T``.  Direct-coded input is
constant over ``T``, so the first-layer conv is hoisted out of the time loop
entirely — computed once and reused for all ``T`` steps.  ``"batched"``
stays in XLA ops (the fast CPU path); ``"pallas"`` dispatches the fused
``spiking_conv_lif`` kernel per layer (time loop inside the kernel, membrane
in VMEM, (T,B,row-block) spike-skip table; see docs/kernels.md).

All three backends are differentiable with the same selectable surrogate
(``surrogate_kind`` x ``surrogate_alpha``): the time-batched paths
backprop through ``spike_fn`` scans and the fused kernel's ``custom_vjp``
(kernels/spiking_conv_lif.py), and ``jax.grad`` agrees across backends to
float tolerance (tests/test_snn_backends.py) — training can run on the
fast layer-outer hot path.

Both orders compute the same math; outputs agree to float tolerance.  The
scan carry / layer pipeline additionally accumulates per-layer per-channel
**spike counts**, the actual-workload signal consumed by CBWS/balance
evaluation (paper Fig. 2/7).

With APRC on, spatial dims grow by ``R-1`` per conv layer ("full" conv); the
segmentation head center-crops back to the label resolution, which leaves the
workload factorization of Eq. (5) untouched.
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import SNNConfig
from repro.core import snn_layers as L
from repro.core.neuron import LIFState, lif_init
from repro.core.surrogate import spike_fn

__all__ = ["init_snn", "snn_apply", "SNNOutputs", "layer_shapes",
           "SNN_BACKENDS", "ChunkCarry", "ChunkOutputs", "init_chunk_carry",
           "chunk_lengths", "snn_apply_chunk", "snn_apply_chunked",
           "finalize_logits", "skip_cells"]

SNN_BACKENDS = ("ref", "batched", "pallas")


class ChunkCarry(NamedTuple):
    """Per-layer state threaded between timestep chunks.

    Every T-recurrence in the network is strictly sequential per element
    (LIF membranes, the non-firing readout accumulator), so running T in
    segments with this carry reproduces the whole-T execution *bit for
    bit* — the chunk-parity contract the serving engine's continuous
    batching relies on (tests/test_chunk_parity.py).

    ``conv_v``   — membrane per *spiking* conv layer (the segmentation
                   readout conv is non-firing and lives in ``readout_v``);
    ``dense_v``  — membrane per hidden (spiking) dense layer;
    ``readout_v`` — the non-firing readout accumulator: (B, head) for the
                   classifier, the grown-resolution (B, E_h, E_w, Cout)
                   membrane (pre-APRC-crop) for the segmentation head.
    """

    conv_v: Tuple[jax.Array, ...]
    dense_v: Tuple[jax.Array, ...]
    readout_v: jax.Array


class ChunkOutputs(NamedTuple):
    """Per-chunk observability outputs (the SNNOutputs fields that make
    sense for a T-segment; logits only exist once the run finalizes —
    ``finalize_logits`` divides the carried accumulator by the served T)."""

    spike_counts: Tuple[jax.Array, ...]     # per conv layer: (Cout,)
    spike_totals: Tuple[jax.Array, ...]     # per conv layer: scalar
    timestep_counts: Tuple[jax.Array, ...]  # per conv layer: (t_chunk, Cout)
    skip_fractions: Tuple[jax.Array, ...] = ()


def chunk_lengths(t_total: int, chunk_timesteps: int) -> List[int]:
    """Partition ``t_total`` into segments of ``chunk_timesteps`` (the last
    segment carries the remainder)."""
    c = int(chunk_timesteps)
    if c < 1:
        raise ValueError(f"chunk_timesteps must be >= 1, got {chunk_timesteps}")
    if t_total < 1:
        raise ValueError(f"t_total must be >= 1, got {t_total}")
    out: List[int] = []
    rem = int(t_total)
    while rem > 0:
        step = min(c, rem)
        out.append(step)
        rem -= step
    return out


def init_chunk_carry(cfg: SNNConfig, batch: int,
                     dtype=jnp.float32) -> ChunkCarry:
    """The zero carry a fresh request starts from (whole-T execution is
    exactly one chunk started from this)."""
    shapes = layer_shapes(cfg)
    head_dim = cfg.dense_units[-1] if cfg.dense_units else None
    n_spiking = len(shapes) if head_dim is not None else len(shapes) - 1
    conv_v = tuple(jnp.zeros((batch,) + shapes[i], dtype)
                   for i in range(n_spiking))
    dense_v = tuple(jnp.zeros((batch, d), dtype)
                    for d in cfg.dense_units[:-1])
    if head_dim is not None:
        readout_v = jnp.zeros((batch, head_dim), dtype)
    else:
        readout_v = jnp.zeros((batch,) + shapes[-1], dtype)
    return ChunkCarry(conv_v=conv_v, dense_v=dense_v, readout_v=readout_v)


def finalize_logits(readout_v, cfg: SNNConfig, t_total: int):
    """Carried readout accumulator -> logits: APRC center-crop (segmentation
    head) then divide by the served timestep count.  Works on a batch or a
    single row, on jax or numpy arrays — the engine finalizes per-request
    rows host-side and gets bits identical to the jitted whole-T division."""
    v = readout_v
    if not cfg.dense_units and cfg.aprc:
        h0, w0 = cfg.input_hw
        H, W = v.shape[-3], v.shape[-2]
        dh, dw = (H - h0) // 2, (W - w0) // 2
        v = v[..., dh:dh + h0, dw:dw + w0, :]
    return v / t_total


class SNNOutputs(NamedTuple):
    logits: jax.Array            # (B, classes) or (B, H, W, 1) mask logits
    spike_counts: Tuple[jax.Array, ...]   # per conv layer: (Cout,) summed over B,T,HW
    spike_totals: Tuple[jax.Array, ...]   # per conv layer: scalar total spikes
    timestep_counts: Tuple[jax.Array, ...]  # per conv layer: (T, Cout) — temporal profile
    # per pallas-fused conv layer: scalar fraction of (T, B, row-block)
    # skip-table cells skipped (kernels.spiking_conv.skip_table_fraction);
    # empty on backends without skip tables (ref/batched)
    skip_fractions: Tuple[jax.Array, ...] = ()


def layer_shapes(cfg: SNNConfig) -> List[Tuple[int, int, int]]:
    """(H, W, C) after every conv layer (APRC growth accounted)."""
    h, w = cfg.input_hw
    shapes = []
    for cout in cfg.conv_channels:
        h, w = L.conv_out_hw(h, w, cfg.kernel_size, cfg.aprc)
        shapes.append((h, w, cout))
    return shapes


def skip_cells(cfg: SNNConfig, batch: int, timesteps: int, *,
               hoist: bool = True, block_rows: int = 8) -> Tuple[int, ...]:
    """Cells of the skip table, (timestep, batch row, row block), of each
    layer that ``SNNOutputs.skip_fractions`` covers on the pallas backend,
    in its order: every conv layer but a hoisted first one (``hoist``:
    direct-coded frames, not a spike train).  A fraction times its count is
    the number of cells the kernel skipped."""
    r = cfg.kernel_size
    n_conv = len(cfg.conv_channels)
    h_in = [cfg.input_hw[0]] + [h for h, _, _ in layer_shapes(cfg)[:-1]]
    cells = []
    for i in range(n_conv):
        readout = i == n_conv - 1 and not cfg.dense_units
        if hoist and i == 0 and not readout:
            continue
        e_h = h_in[i] + r - 1 if cfg.aprc else h_in[i]
        cells.append(timesteps * batch * -(-e_h // block_rows))
    return tuple(cells)


def init_snn(key: jax.Array, cfg: SNNConfig) -> Dict:
    params: Dict = {"conv": [], "dense": []}
    cin = cfg.input_channels
    keys = jax.random.split(key, len(cfg.conv_channels) + len(cfg.dense_units))
    ki = 0
    for cout in cfg.conv_channels:
        params["conv"].append(L.init_conv(keys[ki], cfg.kernel_size, cin, cout))
        cin, ki = cout, ki + 1
    if cfg.dense_units:
        h, w, c = layer_shapes(cfg)[-1]
        din = h * w * c
        for dout in cfg.dense_units:
            params["dense"].append(L.init_dense(keys[ki], din, dout))
            din, ki = dout, ki + 1
    return params


def snn_apply(params: Dict, frames: jax.Array, cfg: SNNConfig,
              *, surrogate_alpha: float = 10.0,
              surrogate_kind: str = "fast_sigmoid", backend: str = "ref",
              schedule: Optional[Sequence] = None,
              spec: Optional[object] = None) -> SNNOutputs:
    """frames: (B, H, W, Cin) analog input in [0,1] (direct coding) or a
    pre-encoded spike train (T, B, H, W, Cin).

    backend: "ref" (timestep-outer scan, differentiable), "batched"
    (time-batched layer pipeline, XLA ops) or "pallas" (time-batched with
    the fused conv+LIF Pallas kernel).  ``schedule`` (a
    ``core.scheduler.build_schedule`` result, built outside jit) routes the
    pallas backend through CBWS-permuted weights; outputs are reported in
    canonical channel order regardless.

    ``spec`` (a ``repro.api.ExecutionSpec``, duck-typed so core never
    imports the facade) carries backend/surrogate in one validated record
    and overrides the individual kwargs — the facade's single resolution
    point; the loose kwargs remain for the layers beneath it.  Spec fields
    this function cannot apply are loud errors, never silent drops:
    ``spec.timesteps`` must already be resolved into ``cfg`` (Session does
    this), and a ``spec.schedule_mode`` needs the built ``schedule``
    object passed alongside (or go through ``Session``/the engine, which
    build it).
    """
    if frames.shape[-1] != cfg.input_channels:
        # the batched path's single-channel implicit-GEMM conv would
        # silently slice extra channels away; the ref path would raise a
        # conv shape error deep inside the scan — fail loudly here instead
        raise ValueError(
            f"frames carry {frames.shape[-1]} channels but the config "
            f"expects input_channels={cfg.input_channels} "
            f"(frames shape {tuple(frames.shape)})")
    if spec is not None:
        t_spec = getattr(spec, "timesteps", None)
        if t_spec is not None and t_spec != cfg.timesteps:
            raise ValueError(
                f"spec.timesteps={t_spec} conflicts with "
                f"cfg.timesteps={cfg.timesteps}: resolve the spec's T into "
                f"the config first (repro.api.Session does this) — "
                f"snn_apply will not silently pick one")
        mode = getattr(spec, "resolved_schedule", lambda: None)()
        if mode is not None and schedule is None:
            raise ValueError(
                f"spec.schedule_mode={mode!r} but no built schedule was "
                f"passed: snn_apply takes the core.scheduler.build_schedule "
                f"result via schedule= (repro.api.Session/the serving "
                f"engine build it) — the mode alone cannot be applied here")
        backend = spec.backend
        surrogate_alpha = spec.surrogate_alpha
        surrogate_kind = spec.surrogate_kind
        chunk_t = getattr(spec, "chunk_timesteps", None)
        if chunk_t is not None:
            # the chunked driver is bit-identical to whole-T (chunk-parity
            # contract), so routing here keeps Session.infer/eval consistent
            # with what a chunk-scheduling engine serves
            return snn_apply_chunked(
                params, frames, cfg, chunk_timesteps=chunk_t,
                surrogate_alpha=surrogate_alpha,
                surrogate_kind=surrogate_kind, backend=backend,
                schedule=schedule)
    if backend in ("batched", "pallas"):
        return _apply_time_batched(
            params, frames, cfg, surrogate_alpha=surrogate_alpha,
            surrogate_kind=surrogate_kind,
            use_pallas=(backend == "pallas"), schedule=schedule)
    if backend != "ref":
        raise ValueError(
            f"unknown backend {backend!r}; expected one of {SNN_BACKENDS}")
    if frames.ndim == 4:
        z_in = jnp.broadcast_to(frames[None], (cfg.timesteps,) + frames.shape)
    else:
        z_in = frames
    B = z_in.shape[1]
    carry = init_chunk_carry(cfg, B, z_in.dtype)
    counts, t_counts, carry = _apply_ref_chunk(
        params, z_in, cfg, carry, surrogate_alpha=surrogate_alpha,
        surrogate_kind=surrogate_kind)
    return SNNOutputs(
        logits=finalize_logits(carry.readout_v, cfg, cfg.timesteps),
        spike_counts=tuple(counts),
        spike_totals=tuple(c.sum() for c in counts),
        timestep_counts=tuple(t_counts),
    )


def _apply_ref_chunk(params: Dict, z_chunk: jax.Array, cfg: SNNConfig,
                     carry: ChunkCarry, *, surrogate_alpha: float,
                     surrogate_kind: str):
    """One timestep segment of the reference (timestep-outer) path.

    ``z_chunk`` is a (t, B, H, W, Cin) spike-train slice; LIF/readout state
    enters and leaves through ``carry``, so whole-T is the degenerate
    single-chunk call and any chunking of T replays the identical scan.
    Returns (per-layer spike counts for the chunk, per-layer (t, Cout)
    timestep counts, new carry)."""
    B = z_chunk.shape[1]
    n_conv = len(cfg.conv_channels)
    shapes = layer_shapes(cfg)
    head_dim = cfg.dense_units[-1] if cfg.dense_units else None

    conv_states = [LIFState(v=v) for v in carry.conv_v]
    if head_dim is None:
        # segmentation: the non-firing readout conv's membrane is the
        # readout accumulator
        conv_states = conv_states + [LIFState(v=carry.readout_v)]
    dense_states = [LIFState(v=v) for v in carry.dense_v]
    v_readout = carry.readout_v
    counts = [jnp.zeros((c,), jnp.float32) for (_, _, c) in shapes]

    def body(scan_carry, z_t):
        conv_s, dense_s, v_out, cnts = scan_carry
        x = z_t
        new_conv_s, new_cnts, spikes_t = [], [], []
        for i in range(n_conv):
            if i == n_conv - 1 and head_dim is None:
                # segmentation: last conv is the non-firing readout
                z = L.conv2d(x, params["conv"][i]["w"], aprc=cfg.aprc) \
                    + params["conv"][i]["b"]
                v = conv_s[i].v + z
                new_conv_s.append(LIFState(v=v))
                s = (v >= cfg.v_threshold).astype(v.dtype)  # mask spikes (metric only)
                new_cnts.append(cnts[i] + s.sum(axis=(0, 1, 2)))
                spikes_t.append(s.sum(axis=(0, 1, 2)))
                x = v
            else:
                st, s = L.spiking_conv_step(
                    params["conv"][i], conv_s[i], x, aprc=cfg.aprc,
                    v_th=cfg.v_threshold, surrogate_alpha=surrogate_alpha,
                    surrogate_kind=surrogate_kind)
                new_conv_s.append(st)
                new_cnts.append(cnts[i] + s.sum(axis=(0, 1, 2)))
                spikes_t.append(s.sum(axis=(0, 1, 2)))
                x = s
        if head_dim is not None:
            x = x.reshape(B, -1)
            new_dense_s = []
            for j, dp in enumerate(params["dense"][:-1]):
                st, x = L.spiking_dense_step(dp, dense_s[j], x,
                                             v_th=cfg.v_threshold,
                                             surrogate_alpha=surrogate_alpha,
                                             surrogate_kind=surrogate_kind)
                new_dense_s.append(st)
            z = x @ params["dense"][-1]["w"] + params["dense"][-1]["b"]
            v_out = v_out + z
            dense_s = new_dense_s
        else:
            v_out = x  # running readout membrane (already accumulated)
        return (new_conv_s, dense_s, v_out, new_cnts), tuple(spikes_t)

    (conv_states, dense_states, v_out, counts), t_counts = jax.lax.scan(
        body, (conv_states, dense_states, v_readout, counts), z_chunk)

    new_carry = ChunkCarry(
        conv_v=tuple(st.v for st in conv_states[:len(carry.conv_v)]),
        dense_v=tuple(st.v for st in dense_states),
        readout_v=(conv_states[-1].v if head_dim is None else v_out))
    return counts, t_counts, new_carry


def _lif_scan(z_seq: jax.Array, v_th: float, alpha: float,
              kind: str = "fast_sigmoid", v0: Optional[jax.Array] = None,
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """LIF recurrence over a precomputed current train z_seq: (T, B, ...).

    Returns (spike train (T, ...), per-step channel counts (T, C), final
    membrane).  ``v0`` seeds the membrane (chunk carry; None = fresh zeros).

    Two deliberate CPU-perf choices, both measured on the jitted model
    forward: ``lax.scan`` (not unrolling — a T-deep unrolled elementwise
    chain regressed the forward ~30%), and the channel-count reduction
    *inside* the scan body, where it fuses with the spike computation (a
    separate post-hoc reduction over the stacked train forced extra
    materializations and roughly doubled the whole-model time)."""
    def body(v, z):
        v = v + z
        s = spike_fn(v - v_th, alpha, kind)
        return v - v_th * s, (s, s.sum(axis=tuple(range(s.ndim - 1))))

    if v0 is None:
        v0 = jnp.zeros_like(z_seq[0])
    v_fin, (s_seq, cnt) = jax.lax.scan(body, v0, z_seq)
    return s_seq, cnt, v_fin


def _lif_scan_const(z: jax.Array, t: int, v_th: float, alpha: float,
                    kind: str = "fast_sigmoid",
                    v0: Optional[jax.Array] = None,
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """LIF recurrence with a time-constant current (hoisted first layer)."""
    def body(v, _):
        v = v + z
        s = spike_fn(v - v_th, alpha, kind)
        return v - v_th * s, (s, s.sum(axis=tuple(range(s.ndim - 1))))

    if v0 is None:
        v0 = jnp.zeros_like(z)
    v_fin, (s_seq, cnt) = jax.lax.scan(body, v0, None, length=t)
    return s_seq, cnt, v_fin


def _conv_xla(x: jax.Array, p: Dict, aprc: bool) -> jax.Array:
    """Synaptic-current conv, XLA path.  For single-channel input (the
    direct-coded grayscale frame) XLA:CPU's conv is pathologically slow, so
    the R*R-tap implicit GEMM — the same formulation the Pallas kernel
    uses — is dispatched instead (~5x faster, identical math)."""
    w = p["w"]
    r, _, cin, cout = w.shape
    if cin > 1:
        return L.conv2d(x, w, aprc=aprc) + p["b"]
    b_, h, w_in = x.shape[0], x.shape[1], x.shape[2]
    if aprc:
        pad_lo = pad_hi = r - 1                       # full conv
    else:
        pad_lo = (r - 1) // 2                         # SAME
        pad_hi = r - 1 - pad_lo
    e_h = h + pad_lo + pad_hi - r + 1
    e_w = w_in + pad_lo + pad_hi - r + 1
    xp = jnp.pad(x, ((0, 0), (pad_lo, pad_hi), (pad_lo, pad_hi), (0, 0)))
    taps = []
    for dy in range(r):
        for dx in range(r):
            taps.append(jax.lax.dynamic_slice(
                xp, (0, dy, dx, 0), (b_, e_h, e_w, cin)))
    patches = jnp.concatenate(taps, axis=-1)          # (B, E, E', R*R*Cin)
    wm = w.reshape(r * r * cin, cout)
    z = patches.reshape(b_ * e_h * e_w, r * r * cin) @ wm
    return z.reshape(b_, e_h, e_w, cout) + p["b"]


def _conv_folded(x_seq: jax.Array, p: Dict, cfg: SNNConfig,
                 use_pallas: bool, num_groups: int,
                 layer: Optional[int] = None) -> jax.Array:
    """Time-batched synaptic current: fold (T, B) -> T*B and convolve once.

    The fold puts the full spatio-temporal workload on the kernel's batch
    grid axis, so its spike-count skip table covers (T x B x row-blocks).
    ``layer`` names the Pallas kernel (``conv.l<layer>``).
    """
    t, b = x_seq.shape[:2]
    x = x_seq.reshape((t * b,) + x_seq.shape[2:])
    if use_pallas:
        from repro.kernels import ops
        z = ops.spiking_conv(x, p["w"], p["b"], aprc=cfg.aprc,
                             num_groups=num_groups, layer=layer)
    else:
        z = _conv_xla(x, p, cfg.aprc)
    return z.reshape((t, b) + z.shape[1:])


def _kernel_groups(cout: int, cfg: SNNConfig) -> int:
    """Largest lane count <= num_spe_clusters that divides Cout."""
    return max(g for g in range(1, cfg.num_spe_clusters + 1)
               if cout % g == 0)


def _apply_time_batched(params: Dict, frames: jax.Array, cfg: SNNConfig,
                        *, surrogate_alpha: float, surrogate_kind: str,
                        use_pallas: bool,
                        schedule: Optional[Sequence]) -> SNNOutputs:
    """Layer-outer execution: each layer consumes the whole (T, B) block.

    Equivalent math to the timestep-outer scan (backend="ref"), reordered:
      * direct-coded input is constant over T -> the first-layer conv is
        computed ONCE and reused for all T steps (T-fold conv saving);
      * deeper layers convolve the folded (T*B) spike train in one call;
      * only the elementwise LIF recurrence scans over T;
      * the classifier readout is one folded matmul instead of T.

    Whole-T is exactly one chunk of ``_time_batched_chunk`` started from
    the zero carry — that structural identity (plus every T-recurrence
    being a sequential ``lax.scan``) is what makes chunked execution
    bit-identical to this path for any partition of T.
    """
    T = cfg.timesteps
    hoist = frames.ndim == 4
    if hoist:
        B = frames.shape[0]
    else:
        T, B = frames.shape[0], frames.shape[1]
    carry = init_chunk_carry(cfg, B, frames.dtype)
    counts_t, skips, carry = _time_batched_chunk(
        params, frames, cfg, surrogate_alpha=surrogate_alpha,
        surrogate_kind=surrogate_kind, use_pallas=use_pallas,
        schedule=schedule, carry=carry, t_chunk=T)
    return SNNOutputs(
        logits=finalize_logits(carry.readout_v, cfg, cfg.timesteps),
        spike_counts=tuple(c.sum(axis=0) for c in counts_t),
        spike_totals=tuple(c.sum() for c in counts_t),
        timestep_counts=tuple(counts_t),
        skip_fractions=tuple(skips),
    )


def _time_batched_chunk(params: Dict, frames: jax.Array, cfg: SNNConfig,
                        *, surrogate_alpha: float, surrogate_kind: str,
                        use_pallas: bool, schedule: Optional[Sequence],
                        carry: ChunkCarry, t_chunk: int):
    """One timestep segment of the layer-outer pipeline.

    ``frames`` is either the (B, H, W, Cin) direct-coded input (constant
    over T — the hoisted first-layer conv is recomputed per chunk, which is
    deterministic and therefore bit-identical across chunkings) or a
    (t_chunk, B, ...) spike-train slice.  All per-layer LIF membranes and
    the readout accumulator enter/leave via ``carry``; the readout folds
    are sequential ``lax.scan``s (not ``sum``/``cumsum`` tree reductions)
    so every partition of T executes the identical ordered float-add
    sequence.  Returns (per-layer (t_chunk, Cout) counts, per-pallas-layer
    skip fractions, new carry)."""
    T = t_chunk
    hoist = frames.ndim == 4
    B = frames.shape[0] if hoist else frames.shape[1]
    n_conv = len(cfg.conv_channels)
    head_dim = cfg.dense_units[-1] if cfg.dense_units else None
    v_th = cfg.v_threshold

    inv_perms: List[Optional[np.ndarray]] = [None] * n_conv
    if use_pallas and schedule is not None:
        from repro.core.scheduler import permute_conv_params
        params = permute_conv_params(params, list(schedule))
        inv_perms = [np.argsort(s.out_perm) for s in schedule]

    counts_t: List[jax.Array] = []      # per layer (t_chunk, Cout)
    skips: List[jax.Array] = []         # per pallas layer: skip-cell fraction
    new_conv_v: List[jax.Array] = []    # per spiking conv layer: final v
    new_dense_v: List[jax.Array] = []   # per hidden dense layer: final v
    new_readout = carry.readout_v
    x = frames                          # (B,...) analog | (t,B,...) spikes

    def note_skip(train, r):
        # observability: the fused kernel's skip-table sparsity, computed on
        # the same padded train the kernel sees (free when logits-only — XLA
        # drops it with the other unused outputs)
        if use_pallas and train.ndim == 5:
            from repro.kernels import ops
            skips.append(ops.skip_table_fraction(train, r, aprc=cfg.aprc))

    for i in range(n_conv):
        p = params["conv"][i]
        cout = p["w"].shape[-1]
        groups = _kernel_groups(cout, cfg)
        if i == n_conv - 1 and head_dim is None:
            # segmentation: non-firing conv readout — membrane accumulates
            # via a sequential fold (a cumsum could reassociate and break
            # chunk parity)
            if hoist and i == 0:        # degenerate single-layer net
                x = jnp.broadcast_to(x[None], (T,) + x.shape)
                hoist = False
            note_skip(x, p["w"].shape[0])
            z = _conv_folded(x, p, cfg, use_pallas, groups, layer=i)

            def seg_body(v, z_t):
                v = v + z_t
                s = (v >= v_th).astype(z_t.dtype)
                return v, s.sum(axis=(0, 1, 2))

            new_readout, cnt = jax.lax.scan(seg_body, carry.readout_v, z)
        elif hoist and i == 0:
            # direct coding: input constant over T -> conv once, reuse
            if use_pallas:
                from repro.kernels import ops
                z1 = ops.spiking_conv(x, p["w"], p["b"], aprc=cfg.aprc,
                                      num_groups=groups, layer=i)
            else:
                z1 = _conv_xla(x, p, cfg.aprc)
            s, cnt, v_fin = _lif_scan_const(z1, T, v_th, surrogate_alpha,
                                            surrogate_kind,
                                            v0=carry.conv_v[i])
            new_conv_v.append(v_fin)
            x = s
        else:
            if use_pallas:
                from repro.kernels import ops
                note_skip(x, p["w"].shape[0])
                s, v_fin = ops.spiking_conv_lif(
                    x, carry.conv_v[i], p["w"], p["b"], v_th=float(v_th),
                    aprc=cfg.aprc, num_groups=groups,
                    surrogate_alpha=surrogate_alpha,
                    surrogate_kind=surrogate_kind, layer=i)
                cnt = s.sum(axis=(1, 2, 3))
            else:
                z = _conv_folded(x, p, cfg, use_pallas, groups, layer=i)
                s, cnt, v_fin = _lif_scan(z, v_th, surrogate_alpha,
                                          surrogate_kind, v0=carry.conv_v[i])
            new_conv_v.append(v_fin)
            x = s
        if inv_perms[i] is not None:
            cnt = cnt[:, inv_perms[i]]
        counts_t.append(cnt.astype(jnp.float32))

    if head_dim is not None:
        # per-timestep matmuls INSIDE the scans (not one folded
        # (T*B, K) @ W gemm): the gemm's row count is B for every chunk
        # length, so XLA's lowering — which picks shape-dependent
        # accumulation orders for small row counts — cannot round
        # differently across partitions of T
        x = x.reshape(T, B, -1)
        for j, dp in enumerate(params["dense"][:-1]):
            def dense_body(v, x_t, w=dp["w"], b=dp["b"]):
                v = v + (x_t @ w + b)
                s = spike_fn(v - v_th, surrogate_alpha, surrogate_kind)
                return v - v_th * s, s
            v_fin, x = jax.lax.scan(dense_body, carry.dense_v[j], x)
            new_dense_v.append(v_fin)
        dp = params["dense"][-1]
        # readout accumulates, never fires; sequential fold (NOT z.sum
        # (axis=0), whose reduction order need not match a chunked run).
        # The tiny (B, K) @ (K, head) product is written as an explicit
        # broadcast-multiply + K-axis reduce: XLA:CPU picks a different
        # (differently-rounded) dot algorithm for degenerate row counts,
        # so a plain ``@`` would make readout bits depend on the padding
        # bucket — this form lowers to the same per-row K-loop for every
        # (B, t_chunk)
        new_readout, _ = jax.lax.scan(
            lambda acc, x_t, w=dp["w"], b=dp["b"]:
            (acc + ((x_t[:, :, None] * w[None]).sum(axis=1) + b), None),
            carry.readout_v, x)

    return counts_t, skips, ChunkCarry(conv_v=tuple(new_conv_v),
                                       dense_v=tuple(new_dense_v),
                                       readout_v=new_readout)


def snn_apply_chunk(params: Dict, frames: jax.Array, carry: ChunkCarry,
                    cfg: SNNConfig, *, t_chunk: int,
                    surrogate_alpha: float = 10.0,
                    surrogate_kind: str = "fast_sigmoid",
                    backend: str = "batched",
                    schedule: Optional[Sequence] = None,
                    ) -> Tuple[ChunkOutputs, ChunkCarry]:
    """One timestep chunk of the network, any backend.

    ``frames`` is the (B, H, W, Cin) direct-coded input (constant over T)
    or a (t_chunk, B, ...) pre-encoded spike-train slice.  Returns the
    chunk's observability outputs and the updated carry; chain calls over a
    partition of T and the final carry is bit-identical to the whole-T
    run's internal state (``finalize_logits(carry.readout_v, cfg, T)``
    reproduces its logits exactly).  This is the executable the serving
    engine compiles per (bucket, backend, t_chunk) for chunk-boundary
    rescheduling."""
    if backend in ("batched", "pallas"):
        counts_t, skips, carry = _time_batched_chunk(
            params, frames, cfg, surrogate_alpha=surrogate_alpha,
            surrogate_kind=surrogate_kind, use_pallas=(backend == "pallas"),
            schedule=schedule, carry=carry, t_chunk=t_chunk)
    elif backend == "ref":
        if frames.ndim == 4:
            z = jnp.broadcast_to(frames[None], (t_chunk,) + frames.shape)
        else:
            z = frames
        _chunk_totals, counts_t, carry = _apply_ref_chunk(
            params, z, cfg, carry, surrogate_alpha=surrogate_alpha,
            surrogate_kind=surrogate_kind)
        skips = []
    else:
        raise ValueError(
            f"unknown backend {backend!r}; expected one of {SNN_BACKENDS}")
    return ChunkOutputs(
        spike_counts=tuple(c.sum(axis=0) for c in counts_t),
        spike_totals=tuple(c.sum() for c in counts_t),
        timestep_counts=tuple(counts_t),
        skip_fractions=tuple(skips),
    ), carry


def snn_apply_chunked(params: Dict, frames: jax.Array, cfg: SNNConfig,
                      *, chunk_timesteps: int,
                      surrogate_alpha: float = 10.0,
                      surrogate_kind: str = "fast_sigmoid",
                      backend: str = "batched",
                      schedule: Optional[Sequence] = None) -> SNNOutputs:
    """Chunked driver: run T in segments of ``chunk_timesteps`` with the
    membrane/readout state carried between segments.

    Bit-identical logits to the whole-T ``snn_apply`` for every partition
    of T (the chunk-parity contract, tests/test_chunk_parity.py): every
    T-recurrence is a strictly sequential per-element scan, the readouts
    are sequential folds, and the hoisted first-layer conv is
    deterministic, so chunk boundaries change nothing but where the carry
    is materialized.  ``timestep_counts`` are the chunks' counts
    concatenated along T; spike counts/totals are their (integer-exact)
    sums; ``skip_fractions`` is the chunk-length-weighted mean."""
    t_total = cfg.timesteps if frames.ndim == 4 else frames.shape[0]
    B = frames.shape[0] if frames.ndim == 4 else frames.shape[1]
    carry = init_chunk_carry(cfg, B, frames.dtype)
    parts: List[ChunkOutputs] = []
    t_done = 0
    for c in chunk_lengths(t_total, chunk_timesteps):
        xin = frames if frames.ndim == 4 else frames[t_done:t_done + c]
        out, carry = snn_apply_chunk(
            params, xin, carry, cfg, t_chunk=c,
            surrogate_alpha=surrogate_alpha, surrogate_kind=surrogate_kind,
            backend=backend, schedule=schedule)
        parts.append(out)
        t_done += c
    n_layers = len(parts[0].timestep_counts)
    timestep_counts = tuple(
        jnp.concatenate([p.timestep_counts[i] for p in parts], axis=0)
        for i in range(n_layers))
    if parts[0].skip_fractions:
        weights = [t.shape[0] / t_total
                   for t in (p.timestep_counts[0] for p in parts)]
        skip_fractions = tuple(
            sum(w * p.skip_fractions[j] for w, p in zip(weights, parts))
            for j in range(len(parts[0].skip_fractions)))
    else:
        skip_fractions = ()
    return SNNOutputs(
        logits=finalize_logits(carry.readout_v, cfg, cfg.timesteps),
        spike_counts=tuple(c.sum(axis=0) for c in timestep_counts),
        spike_totals=tuple(c.sum() for c in timestep_counts),
        timestep_counts=timestep_counts,
        skip_fractions=skip_fractions,
    )


def skew_channels(params: Dict, sigma: float = 1.0, seed: int = 0) -> Dict:
    """Emulate a trained net's channel skew (paper Fig. 2b: per-channel spike
    counts spread over orders of magnitude).  Random-initialized filters have
    near-uniform magnitudes, so scheduler studies would see no imbalance to
    fix; scaling each output channel by a lognormal factor reproduces the
    operating regime the paper measures (EXPERIMENTS §Repro notes this)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    new_conv = []
    for p in params["conv"]:
        cout = p["w"].shape[-1]
        f = jnp.asarray(rng.lognormal(0.0, sigma, cout), p["w"].dtype)
        new_conv.append({"w": p["w"] * f, "b": p["b"] * f})
    out = dict(params)
    out["conv"] = new_conv
    return out
