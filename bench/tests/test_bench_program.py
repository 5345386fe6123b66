"""The readers of what the program puts into a trace (``program_trace.py``,
``layer_work.py``): its ``snn.*`` spans split the device's idle time, its
stable kernel names give per-kernel rooflines, and its skip counter gives
the skip share — on hand-made traces with hand counts."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import layer_work  # noqa: E402
import program_trace as pt  # noqa: E402
import run  # noqa: E402
import trace_reduce as tr  # noqa: E402


def _op(name):
    return (f'%{name} = f32[8]{{0}} custom-call(f32[8]{{0}} %p), '
            f'custom_call_target="tpu_custom_call"')


PAD = "%pad.3 = f32[8,16]{1,0:T(8,128)} pad(f32[8,12]{1,0} %x, f32[] %c)"
CFG = {"input_hw": [4, 4], "input_channels": 1, "conv_channels": [2, 2],
       "kernel_size": 3, "aprc": True, "dense_units": [10], "timesteps": 2}
PEAK = {"bf16_flops_per_s": 2e12, "hbm_bytes_per_s": 1e12}


def _trace(program=True):
    # window [0, 100); idle [0, 10), [40, 60), [70, 72), [80, 100)
    device = {0: [(_op("conv_lif_fwd.l1"), 10.0, 20.0), (PAD, 25.0, 15.0),
                  (_op("conv_lif_fwd.l1.3"), 60.0, 10.0),
                  (_op("conv_grad_input.l1"), 72.0, 8.0)]}
    host = [(tr.START_MARK, 0.0, 0.0), (tr.STOP_MARK, 100.0, 0.0),
            ("train_step", 5.0, 40.0), ("train_step", 50.0, 45.0)]
    if program:
        host += [("snn.train_step", 6.0, 38.0), ("snn.stage", 6.0, 6.0),
                 ("snn.dispatch", 12.0, 18.0), ("snn.sync", 30.0, 14.0),
                 ("snn.train_step", 51.0, 43.0), ("snn.stage", 51.0, 4.0),
                 ("snn.dispatch", 55.0, 3.0),
                 ("snn.update_engines", 58.0, 1.0),
                 ("snn.sync", 59.0, 35.0)]
    return tr.from_events(device, host, chips=1)


def _ctx(red):
    return {"trace": red, "span": "train_step", "config": CFG, "batch": 2,
            "peak": PEAK}


def test_program_spans_are_not_the_benchmarks():
    from repro.obs import spans
    assert all(n.startswith(pt.PREFIX) for n in spans.SPAN_NAMES)
    assert not set(spans.SPAN_NAMES) & set(tr.BENCH_SPANS)
    assert set(pt.PHASE_GAP) == set(spans.PHASES)


def test_gaps_split_the_idle_time_exactly():
    red = _trace()
    # [0,10): 6 outside, 4 stage; [40,60): 4 sync, 7 outside, 4 stage,
    # 3 dispatch, 1 update_engines, 1 sync; [70,72): 2 sync; [80,100):
    # 14 sync, 6 outside
    assert pt.idle_split_ns(red) == {"stage": 8.0, "dispatch": 3.0,
                                     "sync": 22.0, "outside": 19.0}
    ctx = _ctx(red)
    assert pt.calls_in_window(ctx) == 2.0
    got = {g: pt.gap_ms_per_call(ctx, g) for g in pt.GAPS}
    assert got == pytest.approx({"stage": 4e-6, "dispatch": 1.5e-6,
                                 "sync": 11e-6, "outside": 9.5e-6})
    idle_ns = (red.window[1] - red.window[0]) - red.busy_s * 1e9
    assert sum(got.values()) == pytest.approx(idle_ns / 1e6 / 2)
    for g in pt.GAPS:
        reader = run.load_module(HERE.parent / "metrics"
                                 / f"{g}_gap_ms.train.py")
        assert reader.read(ctx) == pytest.approx(got[g])


def test_calls_at_the_window_edge_count_by_their_share():
    red = _trace()
    red.spans.append(tr.Span("train_step", 95.0, 105.0, True))
    assert pt.calls_in_window(_ctx(red)) == pytest.approx(2.5)


def test_readers_find_nothing_without_the_programs_spans_and_names():
    ctx = _ctx(_trace(program=False))
    assert all(pt.gap_ms_per_call(ctx, g) is None for g in pt.GAPS)
    unnamed = tr.from_events(
        {0: [(_op("jvp_jit_spiking_conv_lif_fwd_pallas__.2"), 10.0, 20.0)]},
        [(tr.START_MARK, 0.0, 0.0), (tr.STOP_MARK, 100.0, 0.0),
         ("train_step", 5.0, 40.0)], chips=1)
    assert pt.kind_roofline(_ctx(unnamed), "conv_lif_fwd") is None


def test_kernel_names_parse():
    assert pt.kernel_of("conv_lif_fwd.l2.3") == ("conv_lif_fwd", 2)
    assert pt.kernel_of("conv.l0") == ("conv", 0)
    assert pt.kernel_of("conv_grad_input.l12") == ("conv_grad_input", 12)
    assert pt.kernel_of("spiking_conv_lif_pallas.4") is None
    assert pt.kernel_of("transpose_jvp_jit_lif_bwd_pallas___") is None


def test_layer_work_hand_counts():
    # layer 1: 6x6x2 spikes in, 8x8x2 out, 2304 MACs, T=2
    fwd = layer_work.layer_kernels(CFG, "conv_lif_fwd")
    assert fwd == {1: (2 * 2 * 2304, 2 * (72 + 128) / 8, 4 * (36 + 2))}
    grad = layer_work.layer_kernels(CFG, "conv_grad_input")
    assert grad == {1: (2 * 2 * 2304, 4 * 2 * (72 + 128), 4 * (36 + 2))}
    seg = dict(CFG, dense_units=[], conv_channels=[2, 2, 1])
    assert sorted(layer_work.layer_kernels(seg, "conv_lif_fwd")) == [1]
    assert sorted(layer_work.layer_kernels(seg, "conv_grad_input")) == [1, 2]
    with pytest.raises(ValueError):
        layer_work.layer_kernels(CFG, "lif_bwd")


def test_per_kernel_rooflines_match_hand_counts():
    ctx = _ctx(_trace())
    # both steps lie in the window: 4 frames, 2 calls
    fwd_lb = max(4 * 9216 / 2e12, (4 * 50 + 2 * 152) / 1e12)
    assert pt.kind_roofline(ctx, "conv_lif_fwd") == pytest.approx(
        100 * fwd_lb / 30e-9)
    grad_lb = max(4 * 9216 / 2e12, (4 * 1600 + 2 * 152) / 1e12)
    assert pt.kind_roofline(ctx, "conv_grad_input") == pytest.approx(
        100 * grad_lb / 8e-9)
    for name, want in (("conv_lif_roofline.train", fwd_lb / 30e-9),
                       ("grad_input_roofline.train", grad_lb / 8e-9)):
        reader = run.load_module(HERE.parent / "metrics" / f"{name}.py")
        assert reader.read(ctx) == pytest.approx(100 * want)


def test_named_kernels_cover_the_pallas_seconds():
    red = _trace()
    by_kind = pt.kernel_seconds(red, [red.window])
    assert by_kind == pytest.approx({"conv_lif_fwd": 30e-9,
                                     "conv_grad_input": 8e-9})
    assert sum(by_kind.values()) == pytest.approx(red.split_s()[0])


def test_skip_share_reads_the_programs_counter():
    from repro.obs import skip
    skip.counter().reset()
    try:
        assert pt.skip_share() is None
        skip.counter().fold((0.5, 0.25), (8, 24))
        skip.counter().fold((1.0, 0.0), (8, 24))
        reader = run.load_module(HERE.parent / "metrics"
                                 / "skip_share.seg.py")
        assert reader.read({}) == pytest.approx(100 * (4 + 6 + 8) / 64)
    finally:
        skip.counter().reset()
