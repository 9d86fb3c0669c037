"""Share of the paged decode attention kernel's roofline over the window's
decode steps: FLOPs and K/V bytes at each sequence's cached length
(``work.paged_decode_attention``), all layers, over the device time of the
Pallas paged-attention kernel.

Inside the jitted decode step every Pallas kernel's HLO instruction is
named ``closed_call``, so the paged kernel is found by its operand list, as
the instruction's ``operand_layout_constraints`` print it (alike in the
compiled module and in the profiler's trace): its scalar-prefetch operands
come first, the 2-D int32 page table and the 1-D int32 lengths
(``kernels/flash_attn/paged.py``)."""
from chipbench import readers, work

SIGNATURE = (r"operand_layout_constraints=\{s32\[\d+,\d+\]\{[^}]*\}, "
             r"s32\[\d+\]\{[^}]*\}, ")

claims = readers.pallas_matching(SIGNATURE)


def read(ctx):
    if not readers.traced(ctx):
        return None
    cfg, steps = ctx["cell"].config, ctx["work"]["decode_steps"]
    if not steps:
        return None
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    layers = cfg["num_hidden_layers"]
    ops = [work.paged_decode_attention(p, h, cfg["num_key_value_heads"], hd)
           for p in steps]
    least = layers * readers.least_time_s(ops, ctx["device_kind"])
    return readers.share_pct(least, ctx["reduced"].op_time_s(claims))
