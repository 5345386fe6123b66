"""``repro.obs`` — tracing, live metrics, and structured logging.

The serving engine's observability layer, three seams:

``obs.trace``
    A thread-safe, bounded ring-buffer ``TraceRecorder`` of typed request
    lifecycle events (submit, admit/reject/degrade, window formation, lane
    dispatch start/end, retry, lane death/restart/hang escalation, deadline
    sweep, cancel, drain/shutdown).  Events are stamped on the engine's
    ``Clock``, so a ``VirtualClock`` replay produces byte-identical traces
    and a ``WallClock`` run produces real timestamps.

``obs.export``
    Chrome trace-event JSON (lanes as tracks, requests as flow events
    linking submit -> dispatch -> complete) loadable in Perfetto /
    chrome://tracing, plus a plain-text timeline renderer.

``obs.snapshot``
    ``MetricsSnapshot`` — the point-in-time view ``ServingEngine.snapshot()``
    / ``LiveServer.metrics()`` return *while* ``serve_forever()`` runs.

``obs.log``
    A structured stderr logger with per-subsystem levels for the launchers
    and examples (quiet by default so tests stay silent).

``obs.spans``
    ``span(name, **attrs)`` — the program's host spans (``snn.*``) in the
    ``jax.profiler`` trace, on the device ops' clock: one root per
    ``Session.infer`` / ``train_step`` call, its phases (stage, dispatch,
    sync) as children.

``obs.skip``
    The skip-table counter: skipped and total (t, b, row-block) cells per
    fused layer, folded without a host sync; the process-level counter
    counts while a profiler trace records.

See docs/observability.md.
"""
from repro.obs.log import configure_logging, get_logger
from repro.obs.snapshot import MetricsSnapshot
from repro.obs.trace import TERMINAL_KINDS, TraceEvent, TraceRecorder
from repro.obs.export import chrome_trace, render_timeline, write_chrome_trace
from repro.obs import skip, spans
from repro.obs.spans import span

__all__ = [
    "TraceRecorder", "TraceEvent", "TERMINAL_KINDS",
    "chrome_trace", "write_chrome_trace", "render_timeline",
    "MetricsSnapshot",
    "get_logger", "configure_logging",
    "skip", "spans", "span",
]
