"""Operations and bytes of the sharded finetune step, counted from shapes.

A training token's work is three times its forward pass (the forward,
and the backward's two products per forward product): the multiply-adds
of every kept projection weight and of the output head, and causal
attention over the positions before it.  Recomputation under remat and the
embedding gather are not work.  The column-wise kernel's work is counted
per executed kernel from the operand shapes its HLO text gives, with
``work.colwise_linear``; ``shard_linears`` gives the per-shard shapes the
tensor-parallel step should show.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from chipbench import work

#: ``x [rows, d_in]``, the kept indices, ``values [n_tiles, k_kept, tile]``
#: among a column-wise kernel's operand layouts
_OPERANDS = re.compile(
    r"operand_layout_constraints=\{([a-z0-9]+)\[(\d+),(\d+)\]\{[^}]*\}, "
    r"s32\[[\d,]+\]\{[^}]*\}, ([a-z0-9]+)\[(\d+),(\d+),(\d+)\]\{[^}]*\}\}")
ITEM = {"bf16": 2, "f16": 2, "f32": 4, "s8": 1, "f8e4m3fn": 1}


def forward_token_flops(cfg: Dict, seq_len: int) -> float:
    """FLOPs of one token's forward pass in a sequence of ``seq_len``:
    every kept projection weight and the head, 2 FLOPs each, and the QK
    and PV products over the ``(seq_len + 1) / 2`` positions a causal
    token attends to on average."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    kept = sum(lin.k_kept * lin.d_out for lin in work.lm_linears(cfg))
    attn = 4 * h * hd * (seq_len + 1) / 2
    return cfg["num_hidden_layers"] * (2 * kept + attn) \
        + 2 * d * cfg["vocab_size"]


def train_token_flops(cfg: Dict, seq_len: int) -> float:
    """FLOPs one token of a training step needs: three forward passes."""
    return 3 * forward_token_flops(cfg, seq_len)


def shard_linears(cfg: Dict) -> List[work.Linear]:
    """The column-parallel compressed projections of one layer as one
    shard of the model axis holds them: whole tiles, ``1 / model`` of the
    columns, every kept row (``o`` and ``down`` are row-parallel, in the
    REDUCE format, and not column-wise kernels)."""
    tp = cfg["mesh"]["model"]
    out = []
    for lin in work.lm_linears(cfg):
        if lin.compressed and lin.name not in ("o", "down"):
            if lin.n_tiles % tp:
                raise ValueError(f"{lin.name}: {lin.n_tiles} tiles over {tp}")
            out.append(work.Linear(lin.name, lin.d_in, lin.d_out // tp,
                                   lin.k_kept, lin.n_tiles // tp, True))
    return out


def shard_linear_ops(cfg: Dict, rows: int) -> List[Tuple[int, int]]:
    """(flops, bytes) of each shard's forward of each column-parallel
    projection over ``rows`` token rows, one entry per layer and
    projection."""
    one = [work.colwise_linear(rows, s.d_in, s.d_out, s.k_kept, s.n_tiles)
           for s in shard_linears(cfg)]
    return one * cfg["num_hidden_layers"]


def kernel_shapes(text: str) -> Optional[Tuple[int, int, int, int, int, int]]:
    """(rows, d_in, n_tiles, k_kept, tile, item) of a column-wise kernel
    from its HLO text, or None where its operands are not ``x``, indices,
    ``values``."""
    m = _OPERANDS.search(text)
    if not m:
        return None
    xt, rows, d_in, vt, n_tiles, k, tile = m.groups()
    return (int(rows), int(d_in), int(n_tiles), int(k), int(tile),
            ITEM[xt])


def kernel_ops(text: str) -> Optional[Tuple[int, int]]:
    """(flops, bytes) of one execution of a column-wise kernel."""
    s = kernel_shapes(text)
    if s is None:
        return None
    rows, d_in, n_tiles, k, tile, item = s
    return work.colwise_linear(rows, d_in, n_tiles * tile, k, n_tiles, item)
