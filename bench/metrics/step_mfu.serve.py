"""Model FLOPs of the requests served in the window over the time in which
some lane had a micro-batch out (the union of dispatch -> batch_done
intervals), as a share of the chip's peak (%)."""
import readout
import work


def read(ctx):
    b = readout.batches(ctx)
    busy = readout.union_seconds((x["t0"], x["t1"]) for x in b)
    if busy <= 0:
        return None
    rows = sum(x["n"] for x in b)
    flops = rows * work.forward_flops(ctx["config"])
    return 100.0 * flops / busy / (ctx["chips"]
                                   * ctx["peak"]["bf16_flops_per_s"])
