"""Reduce one profiler trace (``.xplane.pb``) to device busy and idle time,
the Pallas/XLA split, the benchmark's host spans and a breakdown.

The traced window runs from the benchmark's ``bench_profile_start`` host
mark to its ``bench_profile_stop`` mark.  Device time is read from the
``XLA Ops`` line of each ``/device:TPU:<n>`` plane; an op is a Pallas kernel
when it is a ``tpu_custom_call`` (how Mosaic kernels are lowered) or its
name says ``pallas``.  Busy time is the union of a device's op
intervals inside the window, averaged over the chips the cell uses.  Each
idle gap of the first chip is labelled by what the host was doing: the
benchmark's innermost span covering the gap's middle, else the longest
runtime event covering it, else ``"no host span"``.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Tuple

START_MARK = "bench_profile_start"
STOP_MARK = "bench_profile_stop"
# spans the benchmark itself records around its calls into the program
BENCH_SPANS = ("submit", "infer", "train_step")

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


@dataclass
class Op:
    name: str          # short HLO name, e.g. "spiking_conv_lif_pallas.3"
    start: float       # ns
    end: float         # ns
    pallas: bool


@dataclass
class Span:
    name: str
    start: float
    end: float
    bench: bool


@dataclass
class Reduced:
    """What the per-layer readers read from one trace."""
    window: Tuple[float, float]                   # ns, trace clock
    ops: Dict[int, List[Op]]                      # device id -> ops
    spans: List[Span]                             # host spans, all threads
    chips: int
    busy_by_device: Dict[int, float] = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def used(self) -> List[int]:
        return sorted(self.ops)[:self.chips]

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips used."""
        used = self.used
        return sum(self.busy_by_device[d] for d in used) / len(used) / 1e9

    def ops_in(self, lo: float, hi: float) -> List[Op]:
        """Ops of the used chips that start inside [lo, hi)."""
        return [o for d in self.used for o in self.ops[d]
                if lo <= o.start < hi]

    def split_s(self) -> Tuple[float, float]:
        """(Pallas seconds, other seconds) of op time inside the window,
        summed over the used chips."""
        lo, hi = self.window
        pallas = other = 0.0
        for o in self.ops_in(lo, hi):
            d = min(o.end, hi) - o.start
            if o.pallas:
                pallas += d
            else:
                other += d
        return pallas / 1e9, other / 1e9

    def bench_spans(self, name: str) -> List[Span]:
        """The benchmark's spans of ``name`` that lie wholly inside the
        window."""
        lo, hi = self.window
        return [s for s in self.spans
                if s.bench and s.name == name and s.start >= lo
                and s.end <= hi]

    def gaps(self) -> List[Tuple[float, float]]:
        """Idle intervals of the first used chip inside the window."""
        lo, hi = self.window
        return _gaps(_union((o.start, o.end) for o in self.ops[self.used[0]]
                            if o.end > lo and o.start < hi), lo, hi)

    def label(self, t: float) -> str:
        """What the host was doing at ``t``."""
        bench = [s for s in self.spans if s.bench and s.start <= t <= s.end]
        if bench:
            return min(bench, key=lambda s: s.end - s.start).name
        other = [s for s in self.spans
                 if not s.bench and s.start <= t <= s.end]
        if other:
            return max(other, key=lambda s: s.end - s.start).name
        return "no host span"

    def breakdown(self, top: int = 10) -> Dict[str, List]:
        """The device ops that took most time on the used chips (mean per
        chip), and the longest idle gaps of the first chip with the host
        activity under each."""
        lo, hi = self.window
        tot: Dict[str, float] = {}
        for o in self.ops_in(lo, hi):
            key = re.sub(r"\.\d+$", "", o.name)
            tot[key] = tot.get(key, 0.0) + (min(o.end, hi) - o.start)
        n = len(self.used)
        ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[k, v / n / 1e9] for k, v in ops],
                "idle_gaps": [[self.label((a + b) / 2), (b - a) / 1e9]
                              for a, b in gaps]}


def _union(intervals: Iterable[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _gaps(union: List[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    gaps, t = [], lo
    for a, b in union:
        a, b = max(a, lo), min(b, hi)
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def covered(intervals: Iterable[Tuple[float, float]], lo: float,
            hi: float) -> float:
    """Length of the union of ``intervals`` clipped to [lo, hi]."""
    total = 0.0
    for a, b in _union(intervals):
        a, b = max(a, lo), min(b, hi)
        if b > a:
            total += b - a
    return total


def op_kind(hlo: str) -> str:
    """The op kind of an HLO instruction's text ("%x = f32[..] pad(..)")."""
    _, sep, rest = hlo.partition(" = ")
    if not sep:
        return ""
    rest = rest.lstrip()
    if rest.startswith("("):                   # tuple shape
        depth = 0
        for i, c in enumerate(rest):
            depth += c == "("
            depth -= c == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    m = re.match(r"\s*([\w\-]+)\(", rest)
    return m.group(1) if m else ""


def short_name(hlo: str) -> str:
    return hlo.partition(" = ")[0].strip().lstrip("%")


def is_pallas(hlo: str) -> bool:
    return "pallas" in short_name(hlo) or (
        op_kind(hlo) == "custom-call"
        and 'custom_call_target="tpu_custom_call"' in hlo)


def from_events(device_ops: Dict[int, List[Tuple[str, float, float]]],
                host: List[Tuple[str, float, float]], chips: int) -> Reduced:
    """Build the reduction from plain event lists (ns): ``device_ops`` maps
    a device id to (HLO text, start, duration); ``host`` holds (name, start,
    duration) of every host event."""
    marks = {name: start for name, start, _ in host
             if name in (START_MARK, STOP_MARK)}
    if START_MARK not in marks or STOP_MARK not in marks:
        raise ValueError("trace lacks the benchmark's window marks")
    window = (marks[START_MARK], marks[STOP_MARK])
    ops = {d: [Op(short_name(n), s, s + dur, is_pallas(n))
               for n, s, dur in evs] for d, evs in device_ops.items()}
    spans = [Span(n, s, s + dur, n in BENCH_SPANS)
             for n, s, dur in host if dur > 0]
    red = Reduced(window=window, ops=ops, spans=spans, chips=chips)
    if len(ops) < chips:
        raise ValueError(f"trace has {len(ops)} devices, the cell uses "
                         f"{chips}")
    for d, lst in ops.items():
        red.busy_by_device[d] = covered(((o.start, o.end) for o in lst),
                                        *window)
    return red


def reduce(path: Path, chips: int) -> Reduced:
    """Read an ``.xplane.pb`` file and reduce it."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    device_ops: Dict[int, List[Tuple[str, float, float]]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device_ops[int(m.group(1))] = [
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    host.append((e.name, e.start_ns, e.duration_ns))
    return from_events(device_ops, host, chips)
