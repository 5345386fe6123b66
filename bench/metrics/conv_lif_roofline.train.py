"""Least time the fused conv+LIF forward kernels of training
(``conv_lif_fwd.l<k>``) need for the steps wholly inside the traced window
over their device time (%)."""
import program_trace


def read(ctx):
    return program_trace.kind_roofline(ctx, "conv_lif_fwd")
