"""Training frames per second of the traced run times three forward passes'
FLOPs per frame, over the peak of the chips used (%)."""
import work


def read(ctx):
    fps = ctx.get("train_fps")
    if not fps:
        return None
    return 100.0 * fps * work.train_flops(ctx["config"]) / (
        ctx["chips"] * ctx["peak"]["bf16_flops_per_s"])
