"""Device-idle milliseconds per call of the traced window that lie
under the program's ``snn.stage`` span (padding and the copy to
the device, or the batch's ``jnp.asarray``) (ms)."""
import program_trace


def read(ctx):
    return program_trace.gap_ms_per_call(ctx, "stage")
