"""``bench/trace_reduce.py`` and the per-layer readers on known traces: a
hand-made one and a small piece of a recorded TPU v5e trace of the snn-seg
cell (``data/seg_trace_events.json``: 0.9 s from the profiler's start mark
of a ``--trace 1`` run, its device ops with their HLO text cut to 120
characters, and the host events longer than 0.2 ms)."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import readout  # noqa: E402
import run  # noqa: E402
import trace_reduce as tr  # noqa: E402

PALLAS = ('%spiking_conv_lif_pallas.4 = (f32[8,16]{1,0}, f32[8]{0}) '
          'custom-call(f32[8]{0} %p), custom_call_target="tpu_custom_call"')
PAD = "%pad.3 = f32[8,16]{1,0:T(8,128)} pad(f32[8,12]{1,0} %x, f32[] %c)"
ALLOC = ('%custom-call.4 = f32[16,1]{0,1:T(1,128)} custom-call(), '
         'custom_call_target="AllocateBuffer"')


def _hand_made():
    # window [0, 100); ops at [10, 30) pallas, [25, 40) pad, [60, 70) pallas
    device = {0: [(PALLAS, 10.0, 20.0), (PAD, 25.0, 15.0),
                  (PALLAS, 60.0, 10.0)]}
    host = [(tr.START_MARK, 0.0, 0.0), (tr.STOP_MARK, 100.0, 0.0),
            ("infer", 5.0, 40.0), ("infer", 50.0, 30.0),
            ("PjitFunction(f)", 40.0, 20.0)]
    return tr.from_events(device, host, chips=1)


def test_hand_made_busy_idle_and_split():
    red = _hand_made()
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s == pytest.approx(40e-9)      # [10,40) + [60,70)
    pallas, other = red.split_s()
    assert pallas == pytest.approx(30e-9) and other == pytest.approx(15e-9)
    assert red.gaps() == [(0.0, 10.0), (40.0, 60.0), (70.0, 100.0)]
    b = red.breakdown()
    assert b["device_ops"][0] == ["spiking_conv_lif_pallas", 30e-9]
    # the longest gap, [70, 100), has no host span at its middle; the middle
    # of [40, 60) lies under both the runtime event and the second infer
    # span, and the benchmark's own span wins
    assert b["idle_gaps"] == [["no host span", 30e-9], ["infer", 20e-9],
                              ["infer", 10e-9]]
    assert len(red.bench_spans("infer")) == 2


def test_op_kinds():
    assert tr.op_kind(PALLAS) == "custom-call"
    assert tr.op_kind(PAD) == "pad"
    assert tr.is_pallas(PALLAS) and not tr.is_pallas(PAD)
    assert not tr.is_pallas(ALLOC)
    assert tr.short_name(PAD) == "pad.3"


def test_missing_marks_or_devices_are_errors():
    with pytest.raises(ValueError, match="marks"):
        tr.from_events({0: []}, [("infer", 0.0, 1.0)], chips=1)
    with pytest.raises(ValueError, match="devices"):
        tr.from_events({}, [(tr.START_MARK, 0.0, 0.0),
                            (tr.STOP_MARK, 1.0, 0.0)], chips=1)


def _recorded():
    d = json.loads((HERE / "data" / "seg_trace_events.json").read_text())
    return tr.from_events(
        {int(k): [tuple(e) for e in v] for k, v in d["device_ops"].items()},
        [tuple(h) for h in d["host"]], d["chips"])


def test_recorded_seg_trace_known_values():
    red = _recorded()
    assert red.window_s == pytest.approx(0.9)
    assert red.busy_s == pytest.approx(0.83744508, rel=1e-9)
    pallas, other = red.split_s()
    assert pallas == pytest.approx(0.743671211, rel=1e-9)
    assert other == pytest.approx(0.094166994, rel=1e-9)
    assert len(red.bench_spans("infer")) == 2
    b = red.breakdown(3)
    assert [k for k, _ in b["device_ops"]] == [
        "spiking_conv_lif_pallas", "spiking_conv_pallas", "pad"]
    assert all(label == "infer" for label, _ in b["idle_gaps"])


def test_readers_on_the_recorded_trace():
    cell = run.resolve("seg-stream-b8")
    red = _recorded()
    ctx = {"cell": cell, "config": cell.config, "traffic": cell.traffic,
           "peak": json.loads((HERE.parent / "peaks.json").read_text())[
               "TPU v5 lite"], "chips": 1, "trace": red, "batch": 8,
           "span": "infer", "infer_fps": 21.0}
    idle = readout.device_idle(ctx)
    assert idle == pytest.approx(100 * (1 - 0.83744508 / 0.9), rel=1e-6)
    share = readout.xla_share(ctx)
    assert share == pytest.approx(100 * 0.094166994 / 0.837838205, rel=1e-6)
    roof = readout.closed_loop_roofline(ctx, "infer")
    assert 0 < roof < 100
    for name in ("mfu.seg", "kernel_roofline.seg", "xla_share.seg",
                 "device_idle.seg"):
        reader = run.load_module(HERE.parent / "metrics" / f"{name}.py")
        value = reader.read(ctx)
        assert value is not None and 0 < value < 100, name
