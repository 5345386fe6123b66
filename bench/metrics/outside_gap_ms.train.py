"""Device-idle milliseconds per call of the traced window that lie
under no phase span of the program: the benchmark's driver and
the Python between calls (ms)."""
import program_trace


def read(ctx):
    return program_trace.gap_ms_per_call(ctx, "outside")
