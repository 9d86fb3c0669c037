"""Whole-step share of the chip's peak: tokens/s of the traced window times
the FLOPs of one token (kept projections, unembedding, attention at the
window's mean cached length; ``work.lm_token_flops``) over the bf16 peak."""
import numpy as np

from chipbench import readers, work


def read(ctx):
    if not readers.traced(ctx):
        return None
    w = ctx["work"]
    steps = w["decode_steps"]
    if not steps:
        return None
    mean_ctx = float(np.mean([np.mean(p) for p in steps]))
    rate = w["tokens"] / ctx["window_s"]
    fl = rate * work.lm_token_flops(ctx["cell"].config, mean_ctx)
    return readers.share_pct(fl, readers.peak_flops(ctx["device_kind"]))
