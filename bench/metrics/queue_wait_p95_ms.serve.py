"""95th percentile of the wait between a window request's submission and
the dispatch of the micro-batch that carries it, from the serving engine's
lifecycle events (ms)."""
import numpy as np


def read(ctx):
    rid_of = ctx.get("rid_of", {})
    submit, first = {}, {}
    for e in ctx.get("events", []):
        if e["kind"] == "submit" and e.get("rid") in rid_of:
            submit[e["rid"]] = e["ts"]
        elif e["kind"] == "dispatch":
            for r in e.get("rids", ()):
                first.setdefault(r, e["ts"])
    waits = [first[r] - t for r, t in submit.items() if r in first]
    return float(np.percentile(waits, 95)) * 1e3 if waits else None
