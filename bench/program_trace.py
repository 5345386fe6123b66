"""What the program itself puts into a traced run, read for the per-layer
metrics: its host spans (``snn.*``), the stable names of its Pallas
kernels (``<kind>.l<layer>``) and its skip-table counter.

A program without them (one older than its spans and names) leaves the
trace without them; each reading is then None, and the metric is left out.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

import layer_work

PREFIX = "snn."
# phase span -> the gap metric its idle time counts under
PHASE_GAP = {"snn.stage": "stage", "snn.dispatch": "dispatch",
             "snn.sync": "sync", "snn.update_engines": "sync"}
GAPS = ("stage", "dispatch", "sync", "outside")

_KERNEL = re.compile(r"^(?P<kind>[a-z_]+)\.l(?P<layer>\d+)(\.\d+)?$")


def calls_in_window(ctx) -> float:
    """The benchmark's calls the traced window holds, each counted by the
    share of its span that lies inside the window."""
    red = ctx["trace"]
    lo, hi = red.window
    total = 0.0
    for s in red.spans:
        if s.bench and s.name == ctx["span"] and s.end > s.start:
            total += max(0.0, min(s.end, hi) - max(s.start, lo)) \
                / (s.end - s.start)
    return total


def idle_split_ns(red) -> Optional[Dict[str, float]]:
    """The idle time of the first used chip in the window, split by the
    innermost program span over each instant: a phase's time under its gap
    (``PHASE_GAP``), everything else — under no phase, or only under a
    call's root — under ``outside``.  None when no program span lies in
    the window."""
    lo, hi = red.window
    prog = [s for s in red.spans if s.name.startswith(PREFIX)
            and s.end > lo and s.start < hi]
    if not prog:
        return None
    out = {g: 0.0 for g in GAPS}
    for a, b in red.gaps():
        over = [s for s in prog if s.start < b and s.end > a]
        cuts = sorted({a, b} | {t for s in over for t in (s.start, s.end)
                                if a < t < b})
        for x, y in zip(cuts, cuts[1:]):
            mid = (x + y) / 2
            under = [s for s in over if s.start <= mid < s.end]
            gap = "outside"
            if under:
                inner = min(under, key=lambda s: s.end - s.start)
                gap = PHASE_GAP.get(inner.name, "outside")
            out[gap] += y - x
    return out


def gap_ms_per_call(ctx, gap: str) -> Optional[float]:
    """Idle ms per call counted under ``gap`` (one of ``GAPS``).  The four
    gaps add up to the window's idle time over its calls."""
    red = ctx.get("trace")
    if red is None:
        return None
    split = idle_split_ns(red)
    calls = calls_in_window(ctx)
    if split is None or calls <= 0:
        return None
    return split[gap] / 1e6 / calls


def kernel_of(op_name: str) -> Optional[Tuple[str, int]]:
    """(kind, layer) of a Pallas op named ``<kind>.l<layer>`` (an XLA
    uniquifying ``.<n>`` suffix allowed), else None."""
    m = _KERNEL.match(op_name)
    return (m.group("kind"), int(m.group("layer"))) if m else None


def kernel_seconds(red, spans: List[Tuple[float, float]]
                   ) -> Dict[str, float]:
    """Device seconds per kernel kind of the named Pallas ops starting
    inside any of ``spans``, over the used chips."""
    out: Dict[str, float] = {}
    for lo, hi in spans:
        for o in red.ops_in(lo, hi):
            k = kernel_of(o.name)
            if o.pallas and k is not None:
                out[k[0]] = out.get(k[0], 0.0) + (o.end - o.start) / 1e9
    return out


def kind_roofline(ctx, kind: str) -> Optional[float]:
    """Least time the ``kind`` kernels need for the calls whose benchmark
    span lies wholly inside the window, over their device time (%)."""
    red = ctx.get("trace")
    if red is None:
        return None
    spans = [(s.start, s.end) for s in red.bench_spans(ctx["span"])]
    t = kernel_seconds(red, spans).get(kind, 0.0)
    if not spans or t <= 0:
        return None
    lb = layer_work.lower_bound_s(ctx["config"], kind,
                                  ctx["batch"] * len(spans), len(spans),
                                  ctx["peak"])
    return 100.0 * lb / t


def skip_share() -> Optional[float]:
    """Skipped over total skip-table cells (%) of the calls the program's
    counter folded in this process: while a profiler trace recorded, the
    calls that lay wholly inside the traced window."""
    try:
        from repro.obs import skip
    except ImportError:
        return None
    reading = skip.counter().read()
    if not reading.calls or not sum(reading.cells):
        return None
    return 100.0 * reading.share
