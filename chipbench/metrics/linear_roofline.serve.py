"""Share of the compressed linears' roofline over the window's decode steps:
the least time of each step's column-wise projections (kept MACs; bytes
of ``x``, ``values``, ``idx`` and ``y``; ``work.lm_decode_linear_ops``)
over the device time of the Pallas column-wise kernels.

Inside the jitted decode step every Pallas kernel's HLO instruction is
named ``closed_call``, so the column-wise kernel is found by its operand
list, as the instruction's ``operand_layout_constraints`` print it (alike in
the compiled module and in the profiler's trace): exactly three operands, a
2-D activation, the kept indices as a 3-D ``[n, 1, block_k]`` int32 array
and the 3-D ``values`` (``kernels/colwise_nm/kernel.py``)."""
from chipbench import readers, work

SIGNATURE = (r"operand_layout_constraints=\{[a-z0-9]+\[\d+,\d+\]\{[^}]*\}, "
             r"s32\[\d+,1,\d+\]\{[^}]*\}, [a-z0-9]+\[\d+,\d+,\d+\]\{[^}]*\}\}")

claims = readers.pallas_matching(SIGNATURE)


def read(ctx):
    if not readers.traced(ctx):
        return None
    cfg, steps = ctx["cell"].config, ctx["work"]["decode_steps"]
    if not steps:
        return None
    least = sum(readers.least_time_s(work.lm_decode_linear_ops(cfg, len(p)),
                                     ctx["device_kind"]) for p in steps)
    return readers.share_pct(least, ctx["reduced"].op_time_s(claims))
