"""Pallas kernel sweeps vs the pure-jnp oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cbws
from repro.kernels import ops, ref
from repro.kernels.spiking_conv import row_block_counts
from repro.kernels.spiking_conv_lif import (spiking_conv_lif_fwd_pallas,
                                            spiking_conv_lif_pallas)

# Interpret mode runs the grid in a Python loop — keep shapes small so the
# default (non-slow) suite stays fast while covering every structural case.
CONV_CASES = [
    # B, H, W, Cin, Cout, R, aprc, block_rows, groups
    (2, 8, 8, 3, 8, 3, True, 4, 2),
    (1, 12, 12, 1, 16, 3, True, 8, 4),
    (2, 6, 10, 4, 12, 5, True, 4, 3),   # 5x5 taps
    (2, 8, 8, 3, 8, 3, False, 4, 2),
    (1, 7, 9, 2, 6, 3, True, 4, 3),     # ragged rows
    (2, 10, 10, 6, 9, 3, False, 4, 9),  # group = single channel (SPE-like)
]


@pytest.mark.parametrize("case", CONV_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_spiking_conv_matches_ref(case, dtype):
    b, h, w_, cin, cout, r, aprc, br, g = case
    key = jax.random.PRNGKey(hash(case) % 2**31)
    ks = jax.random.split(key, 3)
    spikes = (jax.random.uniform(ks[0], (b, h, w_, cin)) < 0.15).astype(dtype)
    w = (jax.random.normal(ks[1], (r, r, cin, cout)) * 0.2).astype(dtype)
    bias = (jax.random.normal(ks[2], (cout,)) * 0.01).astype(dtype)
    out = ops.spiking_conv(spikes, w, bias, aprc=aprc, block_rows=br,
                           num_groups=g, interpret=True)
    want = ref.spiking_conv_ref(spikes, w, bias, aprc=aprc)
    assert out.shape == want.shape
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_spiking_conv_zero_input_emits_bias():
    """Spatio-temporal skip path: all-zero spikes exercise pl.when(count==0)."""
    spikes = jnp.zeros((2, 8, 8, 3), jnp.float32)
    w = jnp.ones((3, 3, 3, 4), jnp.float32)
    bias = jnp.arange(4, dtype=jnp.float32)
    out = ops.spiking_conv(spikes, w, bias, aprc=True, block_rows=4,
                           num_groups=2, interpret=True)
    want = jnp.broadcast_to(bias, out.shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want))


def test_faint_analog_input_not_skipped():
    """Direct-coded frames are analog: a block whose *value* sum is < 1 must
    still convolve (the skip table counts nonzero entries, it does not sum
    values — a value sum would truncate to 0 under the int32 cast)."""
    spikes = jnp.zeros((1, 8, 8, 1), jnp.float32).at[0, 2, 3, 0].set(0.2)
    w = jnp.ones((3, 3, 1, 4), jnp.float32)
    bias = jnp.zeros((4,), jnp.float32)
    out = ops.spiking_conv(spikes, w, bias, aprc=True, block_rows=4,
                           num_groups=2, interpret=True)
    want = ref.spiking_conv_ref(spikes, w, bias, aprc=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)
    assert float(jnp.abs(out).max()) > 0


def test_row_block_counts_match_manual():
    key = jax.random.PRNGKey(0)
    x = (jax.random.uniform(key, (2, 13, 9, 3)) < 0.3).astype(jnp.float32)
    r, br, nb = 3, 4, 3
    counts = np.asarray(row_block_counts(x, r, br, nb))
    xs = np.asarray(x)
    for b in range(2):
        for i in range(nb):
            lo, hi = i * br, min(i * br + br + r - 1, 13)
            assert counts[b, i] == xs[b, lo:hi].sum()


def test_cbws_permuted_weights_same_result():
    """Kernel + CBWS permutation == reference on unpermuted weights after
    inverse-permuting the output channels (scheduling never changes math)."""
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 3)
    spikes = (jax.random.uniform(ks[0], (2, 8, 8, 4)) < 0.2).astype(jnp.float32)
    w = jax.random.normal(ks[1], (3, 3, 4, 8)) * 0.3
    bias = jax.random.normal(ks[2], (8,)) * 0.1
    mags = np.asarray(jnp.abs(w).sum(axis=(0, 1, 2)))
    perm = cbws.cbws_partition_equal(mags, 4).permutation()
    out_perm = ops.spiking_conv(spikes, w[..., perm], bias[perm],
                                aprc=True, num_groups=4, interpret=True)
    want = ref.spiking_conv_ref(spikes, w, bias, aprc=True)
    np.testing.assert_allclose(np.asarray(out_perm),
                               np.asarray(want[..., perm]), atol=1e-4)


LIF_CASES = [(8, 128), (10, 200), (1, 1), (17, 300), (32, 256)]


@pytest.mark.parametrize("shape", LIF_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lif_fused_matches_ref(shape, dtype):
    key = jax.random.PRNGKey(shape[0])
    v = jax.random.normal(key, shape).astype(dtype)
    z = jax.random.normal(jax.random.PRNGKey(1), shape).astype(dtype)
    v2, s2 = ops.lif_fused(v, z, 1.0, interpret=True)
    vr, sr = ref.lif_fused_ref(v, z, 1.0)
    np.testing.assert_allclose(np.asarray(v2, np.float32),
                               np.asarray(vr, np.float32), atol=1e-2)
    np.testing.assert_allclose(np.asarray(s2, np.float32),
                               np.asarray(sr, np.float32))


def test_lif_fused_threshold_sweep():
    v = jnp.linspace(-2, 2, 64).reshape(8, 8)
    z = jnp.zeros((8, 8))
    for vth in (0.5, 1.0, 2.0):
        v2, s2 = ops.lif_fused(v, z, vth, interpret=True)
        vr, sr = ref.lif_fused_ref(v, z, vth)
        np.testing.assert_allclose(np.asarray(v2), np.asarray(vr), atol=1e-6)
        np.testing.assert_allclose(np.asarray(s2), np.asarray(sr))


# ---------------------------------------------------------------------------
# fused spiking-conv + LIF kernel
# ---------------------------------------------------------------------------

FUSED_CASES = [
    # T, B, H, W, Cin, Cout, R, aprc, block_rows, groups
    (3, 2, 8, 8, 3, 8, 3, True, 4, 2),
    (2, 1, 7, 9, 2, 6, 3, True, 4, 3),    # non-block-divisible rows
    (2, 2, 6, 6, 4, 6, 3, False, 4, 2),   # same-pad (APRC off)
    (2, 2, 8, 8, 4, 8, 3, True, 4, 8),    # the models' 8 CBWS groups, 1 ch
    (2, 1, 9, 8, 4, 16, 3, True, 4, 8),   # 8 groups of 2 channels, ragged
]


def _fused_inputs(case, rate, v0_scale=0.3):
    t, b, h, w_, cin, cout, r, aprc, br, g = case
    key = jax.random.PRNGKey((hash(case) ^ int(rate * 1000)) % 2**31)
    ks = jax.random.split(key, 4)
    spikes = (jax.random.uniform(ks[0], (t, b, h, w_, cin)) < rate
              ).astype(jnp.float32)
    w = jax.random.normal(ks[1], (r, r, cin, cout)) * 0.3
    bias = jax.random.normal(ks[2], (cout,)) * 0.05
    e_h = h + r - 1 if aprc else h
    e_w = w_ + r - 1 if aprc else w_
    v0 = jax.random.normal(ks[3], (b, e_h, e_w, cout)) * v0_scale
    return spikes, v0, w, bias


@pytest.mark.parametrize("rate", [0.02, 0.18, 0.5])
@pytest.mark.parametrize("case", FUSED_CASES)
def test_spiking_conv_lif_matches_composed_ref(case, rate):
    """Fused kernel == ref.spiking_conv_ref + ref.lif_fused_ref scanned
    over T, across spike rates spanning the paper's Fig. 2 regime."""
    _, _, _, _, _, _, r, aprc, br, g = case
    spikes, v0, w, bias = _fused_inputs(case, rate)
    s, v = ops.spiking_conv_lif(spikes, v0, w, bias, v_th=1.0, aprc=aprc,
                                block_rows=br, num_groups=g, interpret=True)
    sr, vr = ref.spiking_conv_lif_ref(spikes, v0, w, bias, v_th=1.0,
                                      aprc=aprc)
    assert s.shape == sr.shape and v.shape == vr.shape
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(v), np.asarray(vr), atol=1e-4)


def test_spiking_conv_lif_zero_train_takes_skip_path():
    """All-zero input exercises the spatio-temporal skip on every (t, b, i)
    cell: dV must be bias-only while the LIF recurrence still advances."""
    t = 3
    spikes = jnp.zeros((t, 2, 8, 8, 3), jnp.float32)
    v0 = jnp.zeros((2, 10, 10, 4), jnp.float32)
    w = jnp.ones((3, 3, 3, 4), jnp.float32)
    bias = jnp.full((4,), 0.4, jnp.float32)
    s, v = ops.spiking_conv_lif(spikes, v0, w, bias, v_th=1.0, aprc=True,
                                block_rows=4, num_groups=2, interpret=True)
    sr, vr = ref.spiking_conv_lif_ref(spikes, v0, w, bias, v_th=1.0,
                                      aprc=True)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr))
    np.testing.assert_allclose(np.asarray(v), np.asarray(vr), atol=1e-6)
    # bias 0.4, threshold 1.0: first spike lands exactly at step 3 (v=1.2)
    assert float(s[:2].sum()) == 0.0 and float(s[2].sum()) > 0.0


def test_spiking_conv_lif_single_step_matches_two_kernel_path():
    """T=1 degenerates to the unfused spiking_conv + lif_fused pair — the
    drop-in contract used by snn_layers.spiking_conv_step(backend='pallas')."""
    case = (1, 2, 8, 8, 3, 8, 3, True, 4, 2)
    spikes, v0, w, bias = _fused_inputs(case, 0.18)
    s, v = ops.spiking_conv_lif(spikes, v0, w, bias, v_th=1.0, aprc=True,
                                block_rows=4, num_groups=2, interpret=True)
    z = ops.spiking_conv(spikes[0], w, bias, aprc=True, block_rows=4,
                         num_groups=2, interpret=True)
    v2, s2 = ops.lif_fused(v0.reshape(-1, v0.shape[-1]),
                           z.reshape(-1, z.shape[-1]), 1.0, interpret=True)
    np.testing.assert_allclose(np.asarray(s[0]),
                               np.asarray(s2.reshape(s[0].shape)), atol=1e-5)
    np.testing.assert_allclose(np.asarray(v),
                               np.asarray(v2.reshape(v.shape)), atol=1e-5)


GROUPS8_CASES = [c for c in FUSED_CASES if c[-1] == 8]


@pytest.mark.parametrize("case", GROUPS8_CASES)
def test_fused_forward_save_u_matches_forward(case):
    """The training forward (saves the pre-reset membrane ``u``) emits the
    inference forward's spikes and final membrane, and ``u`` fires exactly
    where ``s`` does, at the models' 8 CBWS groups."""
    _, _, _, _, _, _, _, aprc, br, g = case
    spikes, v0, w, bias = _fused_inputs(case, 0.18)
    kw = dict(v_th=1.0, aprc=aprc, block_rows=br, num_groups=g,
              interpret=True)
    s, v = spiking_conv_lif_pallas(spikes, v0, w, bias, **kw)
    s2, v2, u = spiking_conv_lif_fwd_pallas(spikes, v0, w, bias, **kw)
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(s))
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(v))
    np.testing.assert_array_equal(np.asarray(u >= 1.0).astype(np.float32),
                                  np.asarray(s))
    # the last step's reset: v_final = u_{T-1} - v_th * s_{T-1}
    np.testing.assert_allclose(np.asarray(v), np.asarray(u[-1] - s[-1]),
                               atol=1e-6)


def _pallas_grids(jaxpr) -> list:
    """Grids of every ``pallas_call`` in ``jaxpr``, nested jaxprs included."""
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(tuple(eqn.params["grid_mapping"].grid))
        for p in eqn.params.values():
            sub = getattr(p, "jaxpr", p)
            if hasattr(sub, "eqns"):
                grids.extend(_pallas_grids(sub))
    return grids


@pytest.mark.parametrize("save_u", [False, True])
def test_fused_forward_grid_has_no_group_axis(save_u):
    """The fused forward's grid is (B, n_blocks, T): every cell computes all
    CBWS channel groups of its (image, row block, timestep)."""
    case = FUSED_CASES[-1]                      # T=2, B=1, 11 rows / 4, G=8
    t, b, h, *_ = case
    spikes, v0, w, bias = _fused_inputs(case, 0.18)
    fn = spiking_conv_lif_fwd_pallas if save_u else spiking_conv_lif_pallas
    jaxpr = jax.make_jaxpr(lambda *a: fn(
        *a, block_rows=4, num_groups=8, interpret=True))(spikes, v0, w, bias)
    n_blocks = -(-(h + 2) // 4)
    assert _pallas_grids(jaxpr.jaxpr) == [(b, n_blocks, t)]
