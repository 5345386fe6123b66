"""Skipped over total skip-table cells, (timestep, batch row, row block),
of the layers ``skip_fractions`` covers, over the calls wholly inside the
traced window, from the program's skip counter (%)."""
import program_trace


def read(ctx):
    return program_trace.skip_share()
