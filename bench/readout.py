"""Shared arithmetic of the per-layer readers (``bench/metrics/*.py``).

A reader gets one dict, ``ctx``: the cell, its configuration and traffic,
the peak row, the chip count, the reduced trace (``trace_reduce.Reduced``),
the run's end-to-end values and what the driver handed on (the serving
engine's lifecycle events, the window's call counts).  A reader that finds
nothing to read returns None.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

import work
from trace_reduce import covered


# -- serving lifecycle events -------------------------------------------------
def batches(ctx) -> List[Dict]:
    """The window's micro-batches: {lane, n, rids, t0, t1} on the engine's
    clock, each dispatch paired with the next completion on its lane."""
    rid_of = ctx.get("rid_of", {})     # engine rid -> window index
    open_: Dict[int, List[Dict]] = {}
    out = []
    for e in ctx.get("events", []):
        if e["kind"] == "dispatch":
            open_.setdefault(e["lane"], []).append(
                {"lane": e["lane"], "n": e["n"], "rids": e.get("rids", ()),
                 "t0": e["ts"]})
        elif e["kind"] == "batch_done" and open_.get(e["lane"]):
            b = open_[e["lane"]].pop(0)
            b["t1"] = e["ts"]
            if any(r in rid_of for r in b["rids"]):
                out.append(b)
    return out


def bucket(n: int, buckets) -> int:
    """The padding bucket a micro-batch of ``n`` rows runs at."""
    return min(b for b in buckets if b >= n)


def engine_to_perf(ctx) -> Optional[float]:
    """Offset that maps the engine's clock onto ``time.perf_counter``: the
    median gap between the benchmark's submit stamps and the engine's."""
    rid_of = ctx.get("rid_of", {})     # engine rid -> window index
    sub = ctx.get("submitted_at")
    gaps = [sub[rid_of[e["rid"]]] - e["ts"] for e in ctx.get("events", [])
            if e["kind"] == "submit" and e.get("rid") in rid_of]
    return float(np.median(gaps)) if gaps else None


def perf_to_trace(ctx, t: float) -> Optional[float]:
    """A ``perf_counter`` time on the trace's clock (ns), through the
    profiler's start mark."""
    marks = ctx.get("profile_marks") or (None, None)
    red = ctx.get("trace")
    if marks[0] is None or red is None:
        return None
    return (t - marks[0]) * 1e9 + red.window[0]


# -- work and time ------------------------------------------------------------
def kernel_lower_bound(ctx, frames: int, calls: int, kind: str) -> float:
    """Least seconds the Pallas kernels need for ``frames`` frames in
    ``calls`` calls."""
    flops, nbytes, per_call = work.kernel_work(ctx["config"], kind)
    return work.roofline_seconds(flops * frames,
                                 nbytes * frames + per_call * calls,
                                 ctx["peak"])


def pallas_seconds_in(ctx, spans: List[Tuple[float, float]]) -> float:
    """Pallas op seconds, over the used chips, of ops starting inside any of
    ``spans`` (trace ns)."""
    red = ctx["trace"]
    total = 0.0
    for lo, hi in spans:
        total += sum(o.end - o.start for o in red.ops_in(lo, hi)
                     if o.pallas)
    return total / 1e9


def closed_loop_roofline(ctx, kind: str) -> Optional[float]:
    """Kernel roofline share of the calls whose host span lies wholly
    inside the traced window."""
    red = ctx.get("trace")
    if red is None:
        return None
    spans = [(s.start, s.end) for s in red.bench_spans(ctx["span"])]
    if not spans:
        return None
    t = pallas_seconds_in(ctx, spans)
    if t <= 0:
        return None
    lb = kernel_lower_bound(ctx, ctx["batch"] * len(spans), len(spans),
                            kind)
    return 100.0 * lb / t


def xla_share(ctx) -> Optional[float]:
    red = ctx.get("trace")
    if red is None:
        return None
    pallas, other = red.split_s()
    if pallas + other <= 0:
        return None
    return 100.0 * other / (pallas + other)


def device_idle(ctx) -> Optional[float]:
    red = ctx.get("trace")
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)


def union_seconds(intervals) -> float:
    iv = list(intervals)
    if not iv:
        return 0.0
    return covered(iv, min(a for a, _ in iv), max(b for _, b in iv))
