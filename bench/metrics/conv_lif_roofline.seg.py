"""Least time the fused conv+LIF forward kernels (``conv_lif_fwd.l<k>``,
snn-seg's firing layers 1-4, T timesteps each) need for the calls wholly
inside the traced window over their device time (%)."""
import program_trace


def read(ctx):
    return program_trace.kind_roofline(ctx, "conv_lif_fwd")
