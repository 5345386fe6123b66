"""Device-idle milliseconds per call of the traced window that lie
under the program's ``snn.dispatch`` span (the jitted call, until
it returns) (ms)."""
import program_trace


def read(ctx):
    return program_trace.gap_ms_per_call(ctx, "dispatch")
