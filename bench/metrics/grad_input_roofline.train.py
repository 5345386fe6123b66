"""Least time the transposed-tap input-gradient kernels
(``conv_grad_input.l<k>``) need for the steps wholly inside the traced
window over their device time (%)."""
import program_trace


def read(ctx):
    return program_trace.kind_roofline(ctx, "conv_grad_input")
