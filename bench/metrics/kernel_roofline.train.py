"""Least time the Pallas kernels (forward, LIF backward, transposed-tap
input gradient) need for the steps wholly inside the traced window over
their Pallas op time, summed over the chips used (%)."""
import readout


def read(ctx):
    return readout.closed_loop_roofline(ctx, "train")
