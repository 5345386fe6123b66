"""The skip-table counter: skipped and total (timestep, batch row, row
block) cells of each fused layer's skip table.

The Pallas forward computes, per layer, the fraction of skip-table cells
whose receptive rows held no spike (``SNNOutputs.skip_fractions``, on the
device).  ``SkipCounter.fold`` takes one call's fractions with each
layer's cell count (``core.snn_model.skip_cells``), starts their copy to
the host and returns without waiting: a later fold or ``read`` picks the
values up once they have arrived, so no call blocks on them.

The process-level counter (``counter()``) is fed by the serving engine's
``_run_batch`` and ``_exec_chunk`` (so by ``Session.infer``) while a
profiler trace records host spans (``obs.spans.tracing()``).  A call
counts when the trace runs at its start and at its end, so a traced window
counts exactly the calls that lie wholly inside it.  With no trace running
the counter is off: a call pays one flag check, and adds no device op and
no transfer.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import List, Sequence, Tuple

import numpy as np

__all__ = ["SkipCounter", "SkipReading", "counter"]


@dataclass(frozen=True)
class SkipReading:
    calls: int                         # calls folded
    skipped: Tuple[float, ...]         # per layer: cells skipped
    cells: Tuple[int, ...]             # per layer: cells in the tables

    @property
    def share(self) -> float:
        """Skipped over total cells, all layers (0 before any call)."""
        total = sum(self.cells)
        return sum(self.skipped) / total if total else 0.0


def _ready(x) -> bool:
    is_ready = getattr(x, "is_ready", None)
    return is_ready() if is_ready is not None else True


class SkipCounter:
    """Thread-safe running sums of skipped and total skip-table cells per
    layer (layer ``i`` is the ``i``-th entry of ``skip_fractions``)."""

    # lock discipline (checked by repro.analysis rule "lock-discipline")
    _GUARDED_BY = {"_pending": "_lock", "_skipped": "_lock",
                   "_cells": "_lock", "_calls": "_lock"}

    def __init__(self):
        self._lock = threading.Lock()
        self._pending: List[Tuple[tuple, tuple]] = []
        self._skipped: List[float] = []
        self._cells: List[int] = []
        self._calls = 0

    def fold(self, fractions: Sequence, cells: Sequence[int]) -> None:
        """One call's per-layer skip fractions (device arrays or floats)
        and the cell count of each layer's table."""
        if len(fractions) != len(cells):
            raise ValueError(f"{len(fractions)} fractions for "
                             f"{len(cells)} layers")
        if not fractions:
            return
        for f in fractions:
            start = getattr(f, "copy_to_host_async", None)
            if start is not None:
                start()
        with self._lock:
            self._pending.append((tuple(fractions), tuple(cells)))
            self._calls += 1
            self._drain(wait=False)

    def _drain(self, wait: bool) -> None:  # lint: holds(_lock)
        """Fold the pending calls whose fractions have reached the host
        (all of them when ``wait``)."""
        keep = []
        for fractions, cells in self._pending:
            if not wait and not all(_ready(f) for f in fractions):
                keep.append((fractions, cells))
                continue
            for i, (f, n) in enumerate(zip(fractions, cells)):
                if i == len(self._cells):
                    self._skipped.append(0.0)
                    self._cells.append(0)
                self._skipped[i] += float(np.asarray(f)) * n
                self._cells[i] += int(n)
        self._pending = keep

    def read(self) -> SkipReading:
        """The sums over every call folded so far (waits for the last
        calls' fractions)."""
        with self._lock:
            self._drain(wait=True)
            return SkipReading(calls=self._calls,
                               skipped=tuple(self._skipped),
                               cells=tuple(self._cells))

    def reset(self) -> None:
        with self._lock:
            self._pending = []
            self._skipped = []
            self._cells = []
            self._calls = 0


_counter = SkipCounter()


def counter() -> SkipCounter:
    """The process-level counter."""
    return _counter
