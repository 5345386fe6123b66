"""Operations and bytes of the spiking networks, from a configuration's
shapes alone — the same work whatever implements it.

Counting rules:

* FLOPs: the dense convolution MACs x 2 of every conv layer and timestep,
  plus the dense head's MACs x 2 per timestep (``forward_flops``).  A skip
  table that does less is a gain to find, not work to count.  Training
  counts three forward passes (``train_flops``).
* Pallas lower bound (``kernel_work``): what the kernels on the path must do
  at least.  The first layer sees a direct-coded frame that is constant over
  T, so its convolution counts once; every later layer counts T times.
  Training adds the transposed-tap input gradient of every layer after the
  first (T times).  Bytes: analog input and non-firing readout at 4 bytes
  per element, once; spike trains at 1 bit per spike per timestep; weights
  and biases at 4 bytes (float32, the configuration's precision).
"""
from __future__ import annotations

from typing import Dict, List, Tuple


def layer_shapes(cfg: Dict) -> List[Dict[str, int]]:
    """Per conv layer: input and output H, W, C (APRC full padding grows each
    layer by R-1 pixels; without APRC the size is kept)."""
    h, w = cfg["input_hw"]
    cin = cfg["input_channels"]
    r = cfg["kernel_size"]
    out = []
    for cout in cfg["conv_channels"]:
        ho, wo = (h + r - 1, w + r - 1) if cfg["aprc"] else (h, w)
        out.append(dict(h_in=h, w_in=w, cin=cin, h_out=ho, w_out=wo,
                        cout=cout, macs=ho * wo * r * r * cin * cout))
        h, w, cin = ho, wo, cout
    return out


def conv_flops(cfg: Dict) -> int:
    """Conv FLOPs per frame, every layer at every timestep."""
    return 2 * cfg["timesteps"] * sum(l["macs"] for l in layer_shapes(cfg))


def dense_flops(cfg: Dict) -> int:
    """Dense-head FLOPs per frame, every timestep."""
    if not cfg["dense_units"]:
        return 0
    last = layer_shapes(cfg)[-1]
    din = last["h_out"] * last["w_out"] * last["cout"]
    total = 0
    for dout in cfg["dense_units"]:
        total += din * dout
        din = dout
    return 2 * cfg["timesteps"] * total


def forward_flops(cfg: Dict) -> int:
    """Model FLOPs of one frame's forward pass."""
    return conv_flops(cfg) + dense_flops(cfg)


def train_flops(cfg: Dict) -> int:
    """Model FLOPs of one training frame: forward, input and weight
    gradients."""
    return 3 * forward_flops(cfg)


def _readout_conv(cfg: Dict) -> bool:
    """The segmentation head: the last conv layer integrates without
    firing."""
    return not cfg["dense_units"]


def kernel_work(cfg: Dict, kind: str) -> Tuple[float, float, float]:
    """Lower bound of the Pallas kernels' work: (FLOPs per frame, bytes per
    frame, bytes per call).  ``kind`` is ``"infer"`` or ``"train"``."""
    if kind not in ("infer", "train"):
        raise ValueError(f"kind must be 'infer' or 'train', got {kind!r}")
    t = cfg["timesteps"]
    layers = layer_shapes(cfg)
    flops = 0.0
    nbytes = 0.0
    per_call = 0.0
    n = len(layers)
    for i, l in enumerate(layers):
        reps = 1 if i == 0 else t
        flops += 2 * reps * l["macs"]
        if kind == "train" and i > 0:
            flops += 2 * t * l["macs"]            # transposed-tap input grad
        in_elems = l["h_in"] * l["w_in"] * l["cin"]
        out_elems = l["h_out"] * l["w_out"] * l["cout"]
        nbytes += 4 * in_elems if i == 0 else t * in_elems / 8
        if i == n - 1 and _readout_conv(cfg):
            nbytes += 4 * out_elems
        else:
            nbytes += t * out_elems / 8
        r = cfg["kernel_size"]
        per_call += 4 * (r * r * l["cin"] * l["cout"] + l["cout"])
    return flops, nbytes, per_call


def roofline_seconds(flops: float, nbytes: float, peak: Dict) -> float:
    """The least time the chip could take: the larger of the compute and the
    memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
