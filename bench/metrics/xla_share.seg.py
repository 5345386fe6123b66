"""Device time in ops that are not Pallas kernels (pads, layout copies,
skip-table counts, the dense head, XLA weight gradients) over all device op
time in the traced window (%)."""
import readout


def read(ctx):
    return readout.xla_share(ctx)
