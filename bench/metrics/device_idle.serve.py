"""Share of the traced window in which no op ran on the device, averaged
over the chips the cell uses (%)."""
import readout


def read(ctx):
    return readout.device_idle(ctx)
