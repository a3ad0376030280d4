"""Compiled step: the whole step's share of the chip's bf16 peak.  Model
FLOPs per image (the configuration's layer table: 2 per multiply-add,
backward twice the forward, nothing recomputed) times this run's own
images per second per chip, over the peak of the device's kind."""


def read(ctx):
    w = ctx["window"]
    rate = w["images"] / w["seconds"] / ctx["chips"]
    if not rate:
        return None
    return 100.0 * ctx["flops_per_image"] * rate / (
        ctx["peaks"]["bf16_tflops"] * 1e12)
