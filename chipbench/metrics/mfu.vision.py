"""Whole-step share of the chip's peak: images/s of the traced window times
the FLOPs of one image over the kept weights (``work.resnet_image_flops``),
over the bf16 peak."""
from chipbench import readers, work


def read(ctx):
    if not readers.traced(ctx):
        return None
    w = ctx["work"]
    rate = w["batches"] * w["batch"] / ctx["window_s"]
    fl = rate * work.resnet_image_flops(ctx["cell"].config)
    return readers.share_pct(fl, readers.peak_flops(ctx["device_kind"]))
