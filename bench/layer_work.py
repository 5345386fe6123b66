"""Operations and bytes of one kind of Pallas kernel, layer by layer, from a
configuration's shapes alone (``work.layer_shapes``) — the per-kernel side
of ``work.kernel_work``, counted by the same rules.

Kinds, named as the program names each layer's kernel (``<kind>.l<k>``):

* ``conv_lif_fwd``: the fused conv+LIF forward of every firing layer after
  the first (the first sees the direct-coded frame through the plain
  ``conv`` kernel, a non-firing readout is ``conv`` too).  FLOPs: the dense
  MACs x 2, T times.  Bytes: the input and output spike trains at one bit a
  spike per timestep.  Training's forward also saves the pre-reset
  membrane; that residual is the implementation's, not the work's, so it is
  not counted.
* ``conv_grad_input``: the transposed-tap input gradient of every layer
  after the first, in training.  FLOPs: as ``work.kernel_work`` counts
  them, the dense MACs x 2, T times.  Bytes: the float32 cotangent read and
  the float32 gradient written, T times — less than that no kernel can
  move, since a gradient is not binary.

Both kinds read each layer's float32 taps and bias once per call.
"""
from __future__ import annotations

from typing import Dict, Tuple

import work

KINDS = ("conv_lif_fwd", "conv_grad_input")


def layer_kernels(cfg: Dict, kind: str) -> Dict[int, Tuple[float, float,
                                                           float]]:
    """Layer index -> (FLOPs per frame, bytes per frame, bytes per call) of
    the ``kind`` kernel of each layer that runs one."""
    if kind not in KINDS:
        raise ValueError(f"kind must be one of {KINDS}, got {kind!r}")
    t = cfg["timesteps"]
    r = cfg["kernel_size"]
    layers = work.layer_shapes(cfg)
    last = len(layers) - 1
    out = {}
    for i, l in enumerate(layers):
        if i == 0 or (i == last and not cfg["dense_units"]
                      and kind == "conv_lif_fwd"):
            continue
        in_elems = l["h_in"] * l["w_in"] * l["cin"]
        out_elems = l["h_out"] * l["w_out"] * l["cout"]
        if kind == "conv_lif_fwd":
            nbytes = t * (in_elems + out_elems) / 8
        else:
            nbytes = 4 * t * (in_elems + out_elems)
        out[i] = (2.0 * t * l["macs"], nbytes,
                  4.0 * (r * r * l["cin"] * l["cout"] + l["cout"]))
    return out


def lower_bound_s(cfg: Dict, kind: str, frames: int, calls: int,
                  peak: Dict) -> float:
    """Least seconds the ``kind`` kernels need for ``frames`` frames in
    ``calls`` calls: each layer's kernel at its own roofline, summed."""
    return sum(work.roofline_seconds(flops * frames,
                                     nbytes * frames + per_call * calls,
                                     peak)
               for flops, nbytes, per_call
               in layer_kernels(cfg, kind).values())
