"""Share of the conv kernels' roofline: the least time of the compressed
convs' column-wise work (kept MACs; bytes of map, ``values``, ``idx`` and
output, ``work.resnet_conv_ops``) over the device time of the Pallas conv kernels.

Kernels are found by their HLO instruction names, which are the names of
the program's jitted kernel wrappers (``kernels/conv_gemm/ops.py``,
``kernels/im2col_pack/ops.py``, ``kernels/colwise_nm/ops.py``)."""
from chipbench import readers, work

KERNELS = ("conv2d_fused", "conv2d_fused_banded", "conv2d_two_kernel",
           "conv2d_two_kernel_pipelined", "im2col_pack", "im2col_then_pack",
           "im2col_only",
           "colwise_nm_matmul_strips", "colwise_nm_matmul_strips_pipelined")

claims = readers.pallas_named(KERNELS)


def read(ctx):
    if not readers.traced(ctx):
        return None
    cfg, w = ctx["cell"].config, ctx["work"]
    least = w["batches"] * readers.least_time_s(
        work.resnet_conv_ops(cfg, w["batch"]), ctx["device_kind"])
    return readers.share_pct(least, ctx["reduced"].op_time_s(claims))
