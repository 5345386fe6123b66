"""``bench/work.py`` reproduces the hand counts of the two networks."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import work  # noqa: E402

CFG = {n: json.loads((BENCH / "configs" / f"{n}.json").read_text())
       for n in ("snn-mnist", "snn-seg")}


@pytest.mark.parametrize("name,conv,dense", [
    ("snn-mnist", 120_185_856, 1_479_680),
    ("snn-seg", 9_393_712_128, 0),
])
def test_forward_flops_match_hand_counts(name, conv, dense):
    cfg = CFG[name]
    assert work.conv_flops(cfg) == conv
    assert work.dense_flops(cfg) == dense
    assert work.forward_flops(cfg) == conv + dense
    assert work.train_flops(cfg) == 3 * (conv + dense)


def test_seg_layers_grow_by_two_pixels():
    shapes = work.layer_shapes(CFG["snn-seg"])
    assert [(l["h_out"], l["w_out"]) for l in shapes][-1] == (92, 172)
    assert [l["cout"] for l in shapes] == [8, 16, 32, 32, 16, 1]


@pytest.mark.parametrize("name", ["snn-mnist", "snn-seg"])
def test_kernel_lower_bound_never_exceeds_the_model_count(name):
    cfg = CFG[name]
    f_inf, b_inf, per_call = work.kernel_work(cfg, "infer")
    f_tr, b_tr, _ = work.kernel_work(cfg, "train")
    # the hoisted first layer counts once, the rest T times
    first = work.layer_shapes(cfg)[0]["macs"]
    assert f_inf == work.conv_flops(cfg) - 2 * (cfg["timesteps"] - 1) * first
    assert f_inf < f_tr < work.train_flops(cfg)
    assert b_inf > 0 and per_call > 0 and b_tr == b_inf


def test_roofline_takes_the_binding_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_seconds(1000.0, 1.0, peak) == 10.0
    assert work.roofline_seconds(1.0, 1000.0, peak) == 100.0
    with pytest.raises(ValueError):
        work.kernel_work(CFG["snn-mnist"], "decode")
