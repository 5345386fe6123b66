"""Plain reference of the paper's spiking networks, in ``jax.numpy``.

Imports nothing of the program.  It follows the paper's equations
(Skydiver, arXiv 2203.07516, Eqs. 1-3) in timestep-outer order:

    V(t) = V(t-1) + z(t) - V_th * S(t)      z(t) = W * S_in(t) + b
    S(t) = U(V(t^-) - V_th)

with APRC "full" padding (R-1 zeros on every side, stride 1).  A direct-coded
frame is the input at every timestep.  The classifier flattens the last conv
layer's spikes (NHWC order) into a non-firing dense readout; the segmentation
head's last conv integrates without firing, is centre-cropped back to the
input size and divided by T.  Spike counts of the readout conv count
``V >= V_th`` per timestep (a metric only).  Training differentiates the spike
through the fast-sigmoid surrogate ``1 / (1 + alpha |V - V_th|)^2`` and steps
SGD with momentum.

``precision`` sets every convolution and matrix product: ``"highest"`` is
float32 (``Precision.HIGHEST``); ``"high"`` is ``Precision.HIGH``, the
three-pass bfloat16 a TPU runs for it (a CPU computes it in float32).
``"bf16x3"`` and ``"bf16"`` emulate three passes and one pass by splitting
each operand into bfloat16 parts in the forward pass (the gradient passes
the rounding straight through); they serve the CPU tests, since XLA on a TPU
may drop a float32-bfloat16-float32 round trip as excess precision.  All but
the first are controls that ``correct`` must reject.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "high", "bf16x3", "bf16")
_HIGHEST = jax.lax.Precision.HIGHEST


def _bf16(a):
    """``a`` rounded to bfloat16 in the forward pass, unrounded gradient."""
    return a + jax.lax.stop_gradient(
        a.astype(jnp.bfloat16).astype(jnp.float32) - a)


def _linear(op, x, w, precision: str):
    """``op(x, w, precision)``, bilinear, at the named precision.  Products
    of bfloat16 parts are exact in float32, so the parts run at HIGHEST."""
    if precision == "highest":
        return op(x, w, _HIGHEST)
    if precision == "high":
        return op(x, w, jax.lax.Precision.HIGH)
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}; have {PRECISIONS}")
    xh, wh = _bf16(x), _bf16(w)
    out = op(xh, wh, _HIGHEST)
    if precision == "bf16x3":
        out = out + op(xh, _bf16(w - wh), _HIGHEST) \
            + op(_bf16(x - xh), wh, _HIGHEST)
    return out


def init_params(key: jax.Array, cfg: Dict) -> Dict:
    """He-normal weights and zero biases, as ``{"conv": [{"w", "b"}...],
    "dense": [...]}`` with HWIO conv kernels and (in, out) dense matrices.
    ``key`` draws every weight; ``weights`` (below) keeps the drawn network
    fixed and lets a run's seed permute it."""
    r = cfg["kernel_size"]
    convs = list(cfg["conv_channels"])
    dense = list(cfg["dense_units"])
    keys = jax.random.split(key, len(convs) + len(dense))
    params: Dict = {"conv": [], "dense": []}
    cin = cfg["input_channels"]
    h, w = cfg["input_hw"]
    for i, cout in enumerate(convs):
        fan_in = r * r * cin
        params["conv"].append({
            "w": jax.random.normal(keys[i], (r, r, cin, cout), jnp.float32)
            * jnp.sqrt(2.0 / fan_in),
            "b": jnp.zeros((cout,), jnp.float32)})
        if cfg["aprc"]:
            h, w = h + r - 1, w + r - 1
        cin = cout
    din = h * w * cin
    for j, dout in enumerate(dense):
        params["dense"].append({
            "w": jax.random.normal(keys[len(convs) + j], (din, dout),
                                   jnp.float32) * jnp.sqrt(2.0 / din),
            "b": jnp.zeros((dout,), jnp.float32)})
        din = dout
    return params


def permute_channels(params: Dict, key: jax.Array, cfg: Dict) -> Dict:
    """The same network with the output channels of every hidden conv layer
    permuted (and the next layer's inputs with them): every spike count and
    output is unchanged, while the channels' order — what the CBWS schedule
    balances — follows ``key``.  The classifier's last conv feeds the dense
    head in NHWC order, so its permutation moves the dense rows too; the
    segmentation readout keeps its channel."""
    convs = [dict(p) for p in params["conv"]]
    dense = [dict(p) for p in params["dense"]]
    n = len(convs) if dense else len(convs) - 1
    keys = jax.random.split(key, max(n, 1))
    for i in range(n):
        perm = jax.random.permutation(keys[i], convs[i]["w"].shape[-1])
        convs[i]["w"] = convs[i]["w"][..., perm]
        convs[i]["b"] = convs[i]["b"][perm]
        if i + 1 < len(convs):
            convs[i + 1]["w"] = convs[i + 1]["w"][:, :, perm, :]
        else:
            w = dense[0]["w"]
            c = convs[i]["w"].shape[-1]
            w = w.reshape(-1, c, w.shape[-1])[:, perm, :]
            dense[0]["w"] = w.reshape(-1, w.shape[-1])
    return {"conv": convs, "dense": dense}


def weights(key: jax.Array, cfg: Dict) -> Dict:
    """A run's weights: one He-normal network drawn from a key fixed per
    configuration, its channels permuted by the run's ``key``.  Every seed
    then does the same spiking work on the same inputs, in another channel
    order; drawing the weights themselves from the seed made some networks
    all but silent and changed the work from seed to seed.  (The inputs are
    drawn from the seed.)"""
    base = init_params(jax.random.PRNGKey(0), cfg)
    return permute_channels(base, key, cfg)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _spike(u: jax.Array, alpha: float) -> jax.Array:
    return (u >= 0.0).astype(u.dtype)


def _spike_fwd(u, alpha):
    return _spike(u, alpha), u


def _spike_bwd(alpha, u, g):
    return (g / (1.0 + alpha * jnp.abs(u)) ** 2,)


_spike.defvjp(_spike_fwd, _spike_bwd)


def _conv(x, w, cfg, precision):
    r = w.shape[0]
    if cfg["aprc"]:
        pad = ((r - 1, r - 1), (r - 1, r - 1))
    else:
        lo = (r - 1) // 2
        pad = ((lo, r - 1 - lo), (lo, r - 1 - lo))
    return _linear(lambda a, b, p: jax.lax.conv_general_dilated(
        a, b, (1, 1), pad, dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=p), x, w, precision)


def forward(params: Dict, frames: jax.Array, cfg: Dict,
            precision: str = "highest") -> Tuple[jax.Array, List[jax.Array]]:
    """Logits and per-layer spike totals of a batch of direct-coded frames
    (B, H, W, C)."""
    vth = cfg["v_threshold"]
    alpha = cfg["surrogate_alpha"]
    t_steps = cfg["timesteps"]
    readout_conv = not cfg["dense_units"]
    b = frames.shape[0]
    n_conv = len(params["conv"])

    vs = []
    h, w = frames.shape[1:3]
    grow = cfg["kernel_size"] - 1 if cfg["aprc"] else 0
    for p in params["conv"]:
        h, w = h + grow, w + grow
        vs.append(jnp.zeros((b, h, w, p["w"].shape[-1]), frames.dtype))
    dense_v = [jnp.zeros((b, p["w"].shape[1]), frames.dtype)
               for p in params["dense"]]
    counts = [jnp.zeros((), jnp.float32) for _ in range(n_conv)]

    def step(carry, _):
        vs, dense_v, counts = carry
        x = frames
        new_vs, new_counts = [], []
        for i, p in enumerate(params["conv"]):
            v = vs[i] + _conv(x, p["w"], cfg, precision) + p["b"]
            if readout_conv and i == n_conv - 1:
                s = (v >= vth).astype(v.dtype)
                new_vs.append(v)
            else:
                s = _spike(v - vth, alpha)
                new_vs.append(v - vth * s)
            new_counts.append(counts[i] + jnp.sum(s))
            x = s
        new_dense = []
        if params["dense"]:
            x = x.reshape(b, -1)
            for j, p in enumerate(params["dense"]):
                v = dense_v[j] + _linear(
                    lambda a, b, q: jnp.dot(a, b, precision=q), x, p["w"],
                    precision) + p["b"]
                if j == len(params["dense"]) - 1:
                    new_dense.append(v)
                else:
                    s = _spike(v - vth, alpha)
                    new_dense.append(v - vth * s)
                    x = s
        return (new_vs, new_dense, new_counts), None

    (vs, dense_v, counts), _ = jax.lax.scan(
        step, (vs, dense_v, counts), None, length=t_steps)
    if params["dense"]:
        logits = dense_v[-1] / t_steps
    else:
        v = vs[-1]
        h0, w0 = cfg["input_hw"]
        dh, dw = (v.shape[1] - h0) // 2, (v.shape[2] - w0) // 2
        logits = v[:, dh:dh + h0, dw:dw + w0, :] / t_steps
    return logits, counts


def loss(params: Dict, frames: jax.Array, labels: jax.Array, cfg: Dict,
         precision: str = "highest") -> jax.Array:
    """Mean cross-entropy of the classifier's logits."""
    logits, _ = forward(params, frames, cfg, precision)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def _grad_fn(cfg: Dict, precision: str):
    return jax.jit(jax.value_and_grad(
        lambda p, x, y: loss(p, x, y, cfg, precision) * x.shape[0]))


def grad(fn, params: Dict, frames, labels, block: int = 64):
    """(loss, gradient) of the batch mean, accumulated over blocks of
    ``block`` rows so that a large batch fits; ``fn`` is ``_grad_fn``."""
    n = frames.shape[0]
    total, g = 0.0, None
    for i in range(0, n, block):
        l, gi = fn(params, frames[i:i + block], labels[i:i + block])
        total = total + l
        g = gi if g is None else jax.tree.map(jnp.add, g, gi)
    return total / n, jax.tree.map(lambda a: a / n, g)


def sgd_steps(params: Dict, batches, cfg: Dict, lr: float, momentum: float,
              precision: str = "highest", block: int = 64):
    """SGD with momentum over ``batches`` [(frames, labels), ...] from
    ``params`` and zero momentum.  Returns per step (loss, momentum after
    the step, params after the step) as numpy trees."""
    fn = _grad_fn(cfg, precision)
    mom = jax.tree.map(jnp.zeros_like, params)
    out = []
    for x, y in batches:
        l, g = grad(fn, params, x, y, block)
        mom = jax.tree.map(lambda m, gg: momentum * m + gg, mom, g)
        params = jax.tree.map(lambda w, m: w - lr * m, params, mom)
        out.append((float(l), jax.tree.map(np.asarray, mom),
                    jax.tree.map(np.asarray, params)))
    return out


def infer(params: Dict, frames: np.ndarray, cfg: Dict,
          precision: str = "highest", block: int = 64):
    """Logits (numpy) of ``frames``, computed in blocks of ``block`` rows,
    and each block's per-layer spike totals."""
    fn = jax.jit(lambda p, x: forward(p, x, cfg, precision))
    logits, totals = [], []
    for i in range(0, frames.shape[0], block):
        lg, c = fn(params, jnp.asarray(frames[i:i + block]))
        logits.append(np.asarray(lg))
        totals.append([float(v) for v in c])
    return np.concatenate(logits), totals
