"""Device-idle milliseconds per call of the traced window that lie
under the program's ``snn.sync`` span (waiting for the outputs)
or ``snn.update_engines`` (ms)."""
import program_trace


def read(ctx):
    return program_trace.gap_ms_per_call(ctx, "sync")
