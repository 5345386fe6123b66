"""Least time the Pallas kernels need for the calls wholly inside the traced
window over their Pallas op time (%)."""
import readout


def read(ctx):
    return readout.closed_loop_roofline(ctx, "infer")
