"""Frames per second of the traced run times the model FLOPs of a frame,
over the peak of the chips used (%)."""
import work


def read(ctx):
    fps = ctx.get("infer_fps")
    if not fps:
        return None
    return 100.0 * fps * work.forward_flops(ctx["config"]) / (
        ctx["chips"] * ctx["peak"]["bf16_flops_per_s"])
