"""Compile every Pallas kernel for a described TPU v5e — no chip needed.

The TPU compiler is installed with JAX, and compiles for a chip that is
described rather than attached, so Mosaic's refusals (block shapes that
are neither whole dims nor (8, 128) multiples, unsupported primitives,
VMEM or HBM overflow) show up here instead of on the chip.  Widths are the
paper's (``snn-mnist`` at the serving batch, ``snn-seg`` at batch 8) with
the CBWS channel-group count at its real size (> 1).  For the fused forward
the groups live inside the cell, not on the grid: each cell's blocks hold
all of them, so the widest layer (snn-seg layer 4, 12 row blocks of 8x170
pixels) proves that those blocks fit v5e VMEM.

The topology is described inside a module fixture — never at import — so
every xdist worker collects the same tests and only the worker running
this file loads the TPU library.  The persistent compilation cache is off
around the compiles (an entry written for a described chip cannot be read
back without one).
"""
import jax
import jax.numpy as jnp
import pytest

from repro.config import get_snn
from repro.core.snn_model import _kernel_groups, layer_shapes
from repro.kernels.lif import lif_fused_pallas
from repro.kernels.spiking_conv import (conv_grad_input_pallas,
                                        spiking_conv_pallas)
from repro.kernels.spiking_conv_lif import (lif_bwd_pallas,
                                            spiking_conv_lif_fwd_pallas,
                                            spiking_conv_lif_pallas)

MNIST_BATCH = 64      # the serving max_batch of chip_smoke.py
SEG_BATCH = 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any describe failure skips
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _layer(model: str, i: int):
    """(T, B, H, W, Cin, Cout, groups) of fused conv layer ``i``."""
    cfg = get_snn(model)
    shapes = layer_shapes(cfg)
    h, w, cin = shapes[i - 1]
    cout = shapes[i][2]
    batch = MNIST_BATCH if model == "snn-mnist" else SEG_BATCH
    return cfg.timesteps, batch, h, w, cin, cout, _kernel_groups(cout, cfg)


def _conv_fwd(sds, model):
    cfg = get_snn(model)
    h, w = cfg.input_hw
    cin, cout = cfg.input_channels, cfg.conv_channels[0]
    b = MNIST_BATCH if model == "snn-mnist" else SEG_BATCH
    g = _kernel_groups(cout, cfg)
    return g, spiking_conv_pallas.lower(
        sds(b, h, w, cin), sds(3, 3, cin, cout), sds(cout), num_groups=g,
        interpret=False)


def _fused(sds, model, i, save_u):
    t, b, h, w, cin, cout, g = _layer(model, i)
    fn = spiking_conv_lif_fwd_pallas if save_u else spiking_conv_lif_pallas
    return g, fn.lower(sds(t, b, h, w, cin), sds(b, h + 2, w + 2, cout),
                       sds(3, 3, cin, cout), sds(cout), num_groups=g,
                       interpret=False)


def _grad_input(sds, model, i):
    t, b, h, w, cin, cout, g = _layer(model, i)
    g_in = max(d for d in range(1, g + 1) if cin % d == 0)
    return g_in, conv_grad_input_pallas.lower(
        sds(t * b, h + 2, w + 2, cout), sds(3, 3, cin, cout),
        num_groups=g_in, interpret=False)


def _lif_bwd(sds, model, i):
    t, b, h, w, _, cout, g = _layer(model, i)
    u = sds(t, b, h + 2, w + 2, cout)
    return g, lif_bwd_pallas.lower(
        u, u, sds(b, h + 2, w + 2, cout), v_th=1.0, alpha=10.0,
        kind="fast_sigmoid", num_groups=g, interpret=False)


def _lif_fused(sds):
    t, b, h, w, _, cout, _ = _layer("snn-mnist", 1)
    n = b * (h + 2) * (w + 2)
    return None, lif_fused_pallas.lower(sds(n, 128), sds(n, 128), sds(),
                                        interpret=False)


CASES = {
    "mnist-conv-forward": lambda s: _conv_fwd(s, "snn-mnist"),
    "mnist-fused-forward": lambda s: _fused(s, "snn-mnist", 1, False),
    "mnist-fused-forward-save-u": lambda s: _fused(s, "snn-mnist", 2, True),
    "mnist-conv-grad-input": lambda s: _grad_input(s, "snn-mnist", 1),
    "mnist-lif-bwd": lambda s: _lif_bwd(s, "snn-mnist", 2),
    "mnist-lif-fused": _lif_fused,
    "seg-fused-forward": lambda s: _fused(s, "snn-seg", 2, False),
    "seg-fused-forward-save-u": lambda s: _fused(s, "snn-seg", 3, True),
    "seg-fused-forward-save-u-l4": lambda s: _fused(s, "snn-seg", 4, True),
    "seg-conv-grad-input": lambda s: _grad_input(s, "snn-seg", 2),
    "seg-lif-bwd": lambda s: _lif_bwd(s, "snn-seg", 3),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    groups, lowered = CASES[case](sds)
    if groups is not None:
        assert groups > 1, f"{case}: CBWS channel groups collapsed to 1"
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


def _pallas_names(compiled) -> set:
    """Names of the compiled program's Pallas custom calls, without XLA's
    uniquifying ``.<n>`` suffix."""
    import re
    out = set()
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = line.strip().split(" = ")[0].lstrip("%")
            out.add(re.sub(r"\.\d+$", "", name))
    return out


def test_model_kernels_carry_stable_layer_names(one_chip, monkeypatch):
    """Forward and train step name each layer's kernels ``<kind>.l<k>``,
    with no ``jvp``/``transpose`` prefix, in inference and in training."""
    import dataclasses

    import numpy as np

    from repro.api import TrainSpec
    from repro.core import init_snn, make_train_step, snn_apply
    from repro.kernels import ops
    monkeypatch.setattr(ops, "default_interpret", lambda: False)

    def sds(a):
        return jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype,
                                    sharding=one_chip)

    for model, chans, dense in (("snn-mnist", (8, 8, 8), True),
                                ("snn-seg", (8, 8, 1), False)):
        cfg = dataclasses.replace(get_snn(model), input_hw=(8, 8),
                                  conv_channels=chans, timesteps=2,
                                  num_spe_clusters=4)
        p = jax.tree.map(sds, init_snn(jax.random.PRNGKey(0), cfg))
        x = jax.ShapeDtypeStruct((2, 8, 8, cfg.input_channels), jnp.float32,
                                 sharding=one_chip)
        fwd = jax.jit(lambda p, x, cfg=cfg: snn_apply(p, x, cfg,
                                                      backend="pallas"))
        last = "conv_lif_fwd.l2" if dense else "conv.l2"
        assert _pallas_names(fwd.lower(p, x).compile()) == {
            "conv.l0", "conv_lif_fwd.l1", last}
        if not dense:
            continue
        step = jax.jit(make_train_step(cfg, spec=TrainSpec(backend="pallas")))
        y = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one_chip)
        assert _pallas_names(step.lower(p, p, x, y).compile()) == {
            "conv.l0", "conv_lif_fwd.l1", "conv_lif_fwd.l2", "lif_bwd.l1",
            "lif_bwd.l2", "conv_grad_input.l1", "conv_grad_input.l2"}
