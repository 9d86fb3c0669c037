"""Whole step's share of the chips' peak: training tokens/s of the traced
window times the FLOPs a token needs (``work_train.train_token_flops``:
three forward passes of the kept projections, the head and causal
attention; remat's recompute and the embedding gather do not count) over
the bf16 peak of all the cell's chips."""
from chipbench import readers, work_train


def read(ctx):
    if not readers.traced(ctx):
        return None
    w = ctx["work"]
    if not w["steps"]:
        return None
    rate = w["tokens"] / ctx["window_s"]
    fl = rate * work_train.train_token_flops(ctx["cell"].config, w["seq_len"])
    return readers.share_pct(fl, w["chips"]
                             * readers.peak_flops(ctx["device_kind"]))
