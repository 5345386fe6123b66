"""Mean number of requests in a dispatched micro-batch during the window,
from the serving engine's dispatch events (rows)."""
import numpy as np

import readout


def read(ctx):
    b = readout.batches(ctx)
    return float(np.mean([x["n"] for x in b])) if b else None
