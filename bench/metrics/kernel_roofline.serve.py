"""Least time the Pallas kernels need for the micro-batches that ran wholly
inside the traced window (padded rows included: the kernels compute them)
over the Pallas op time of that window (%).  Batches at the window's edges
add time and no work, so the share errs low."""
import readout


def read(ctx):
    red = ctx.get("trace")
    off = readout.engine_to_perf(ctx)
    if red is None or off is None:
        return None
    lo, hi = red.window
    rows = calls = 0
    for b in readout.batches(ctx):
        t0 = readout.perf_to_trace(ctx, b["t0"] + off)
        t1 = readout.perf_to_trace(ctx, b["t1"] + off)
        if t0 is not None and t0 >= lo and t1 <= hi:
            rows += readout.bucket(b["n"], ctx["buckets"])
            calls += 1
    t = readout.pallas_seconds_in(ctx, [(lo, hi)])
    if not calls or t <= 0:
        return None
    return 100.0 * readout.kernel_lower_bound(ctx, rows, calls, "infer") / t
