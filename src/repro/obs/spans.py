"""Program spans on the profiler's clock.

``span(name, **attrs)`` is ``jax.profiler.TraceAnnotation``: while a
profiler trace runs, the span lands in the trace's host plane beside the
device ops, on the same clock, with ``attrs`` as its stats; with no trace
running it costs one annotation object (about 2 us on a CPU core).  The
profiler keeps spans in memory and writes them when the trace stops, so
nothing is buffered here.

Every program span is named ``snn.<phase>``, so that no name can equal a
span the benchmark records around its own calls (``infer``,
``train_step``, ``submit``).  One root per call, its phases as children:

  ``snn.infer``         ``Session.infer``
  ``snn.train_step``    ``Session.train_step``
  ``snn.stage``         host arrays to a padded batch on the device
  ``snn.dispatch``      the jitted call, until it returns
  ``snn.sync``          waiting for the outputs (training: the loss)
  ``snn.update_engines``  training: new params into the cached engines

The serving engine's ``_run_batch`` and ``_exec_chunk`` open the three
phases too, so a serving lane's micro-batches show them without a root.
"""
from __future__ import annotations

import jax

__all__ = ["span", "tracing", "INFER", "TRAIN_STEP", "STAGE", "DISPATCH",
           "SYNC", "UPDATE_ENGINES", "ROOTS", "PHASES", "SPAN_NAMES"]

INFER = "snn.infer"
TRAIN_STEP = "snn.train_step"
STAGE = "snn.stage"
DISPATCH = "snn.dispatch"
SYNC = "snn.sync"
UPDATE_ENGINES = "snn.update_engines"

ROOTS = (INFER, TRAIN_STEP)
PHASES = (STAGE, DISPATCH, SYNC, UPDATE_ENGINES)
SPAN_NAMES = ROOTS + PHASES


def span(name: str, **attrs) -> jax.profiler.TraceAnnotation:
    """A host span in the running profiler trace (a no-op without one)."""
    return jax.profiler.TraceAnnotation(name, **attrs)


def tracing() -> bool:
    """Whether a profiler trace is recording host spans now."""
    return jax.profiler.TraceAnnotation.is_enabled()
