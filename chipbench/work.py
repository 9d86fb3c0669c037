"""Operations and bytes that each operation needs, counted from its shapes.

These are the work of the column-wise N:M operation whatever implements
it: the multiply-adds of the kept weights, and the bytes of the operands
and the result read or written once.  A one-hot gather that an
implementation runs on the matrix unit is not work here, so removing it
cannot lower the count.  Every function returns ``(flops, bytes)``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence

IDX_BYTES = 4  # int32 kept indices


def kept_rows(d_in: int, sparsity: float) -> int:
    """Kept reduction rows of a column-wise layer with one N:M group over
    the whole reduction dimension (the paper's adaptive M)."""
    return min(max(int(round(d_in * (1.0 - sparsity))), 1), d_in)


def prunes(d_in: int, d_out: int, sp: Dict) -> bool:
    """Whether the configuration's sparsity settings prune a layer."""
    return sp["fraction"] > 0 and min(d_in, d_out) >= sp["min_dim"]


def colwise_linear(rows: int, d_in: int, d_out: int, k_kept: int,
                   n_tiles: int, item: int = 2):
    """``y[rows, d_out] = x[rows, kept] @ values``: the kept MACs; bytes of
    ``x``, ``values``, ``idx`` and ``y``."""
    flops = 2 * rows * k_kept * d_out
    bytes_ = (rows * d_in * item + k_kept * d_out * item
              + n_tiles * k_kept * IDX_BYTES + rows * d_out * item)
    return flops, bytes_


def out_size(n: int, k: int, stride: int, pad: int) -> int:
    return (n + 2 * pad - k) // stride + 1


def conv(c: int, b: int, h: int, w: int, o: int, kh: int, kw: int,
         stride: int, pad: int, k_kept: int, n_tiles: int, item: int = 2):
    """Column-wise conv over the GEMM view ``[kh*kw*c, o]``: the kept MACs
    at every output position; bytes of the input map, ``values``, ``idx``
    and the output map."""
    ho, wo = out_size(h, kh, stride, pad), out_size(w, kw, stride, pad)
    flops = 2 * b * ho * wo * k_kept * o
    bytes_ = (c * b * h * w * item + k_kept * o * item
              + n_tiles * k_kept * IDX_BYTES + o * b * ho * wo * item)
    return flops, bytes_


def paged_decode_attention(lengths: Sequence[int], heads: int, kv_heads: int,
                           head_dim: int, item: int = 2):
    """One query per sequence against its cached keys and values plus its
    own new ones (``lengths`` are the cached rows): QK and PV FLOPs; bytes
    of the cached K and V at those lengths, the new K/V, q and the output."""
    ctx = sum(int(n) + 1 for n in lengths)
    flops = 4 * heads * head_dim * ctx
    cached = sum(int(n) for n in lengths)
    b = len(lengths)
    bytes_ = (2 * cached * kv_heads * head_dim * item
              + 2 * b * kv_heads * head_dim * item
              + 2 * b * heads * head_dim * item)
    return flops, bytes_


# ---------------------------------------------------------------------------
# per-model layer lists, from the benchmark's own configuration files
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Linear:
    name: str
    d_in: int
    d_out: int
    k_kept: int      # == d_in for a dense layer
    n_tiles: int
    compressed: bool


def _linear(name, d_in, d_out, sp) -> Linear:
    if prunes(d_in, d_out, sp):
        tile = sp.get("tile") or d_out
        return Linear(name, d_in, d_out, kept_rows(d_in, sp["fraction"]),
                      d_out // tile, True)
    return Linear(name, d_in, d_out, d_in, 1, False)


def lm_linears(cfg: Dict) -> List[Linear]:
    """The projections of one decoder layer (Qwen2 / Llama layout)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    sp = cfg["sparsity"]
    return [_linear("q", d, h * hd, sp), _linear("k", d, kv * hd, sp),
            _linear("v", d, kv * hd, sp), _linear("o", h * hd, d, sp),
            _linear("gate", d, f, sp), _linear("up", d, f, sp),
            _linear("down", f, d, sp)]


def lm_decode_linear_ops(cfg: Dict, rows: int, item: int = 2):
    """(flops, bytes) of each compressed projection of one decode step of
    ``rows`` sequences, one entry per layer and projection."""
    one = [colwise_linear(rows, lin.d_in, lin.d_out, lin.k_kept,
                          lin.n_tiles, item)
           for lin in lm_linears(cfg) if lin.compressed]
    return one * cfg["num_hidden_layers"]


def lm_token_flops(cfg: Dict, context: float) -> float:
    """FLOPs of one generated token: every kept projection weight, the
    unembedding, and attention over ``context`` cached positions."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    per_layer = sum(2 * lin.k_kept * lin.d_out for lin in lm_linears(cfg))
    attn = 4 * h * hd * (context + 1)
    unembed = 2 * d * cfg["vocab_size"]
    return cfg["num_hidden_layers"] * (per_layer + attn) + unembed


@dataclasses.dataclass(frozen=True)
class Conv:
    name: str
    c: int
    h: int
    w: int
    o: int
    k: int
    stride: int
    pad: int
    k_kept: int
    n_tiles: int
    compressed: bool


def resnet_convs(cfg: Dict) -> List[Conv]:
    """Every conv of the ResNet basic-block stack, in execution order."""
    sp = cfg["sparsity"]

    def make(name, c, h, o, k, stride, pad):
        d_in = k * k * c
        if prunes(d_in, o, sp):
            tile = sp.get("tile") or o
            return Conv(name, c, h, h, o, k, stride, pad,
                        kept_rows(d_in, sp["fraction"]), o // tile, True)
        return Conv(name, c, h, h, o, k, stride, pad, d_in, 1, False)

    h = cfg["image_hw"][0]
    out = [make("stem", cfg["c_in"], h, cfg["stem_channels"], 3, 1, 1)]
    c = cfg["stem_channels"]
    i = 0
    for ch, n, st in zip(cfg["stage_channels"], cfg["stage_blocks"],
                         cfg["stage_strides"]):
        for bi in range(n):
            s = st if bi == 0 else 1
            out.append(make(f"blocks[{i}]/conv1", c, h, ch, 3, s, 1))
            ho = out_size(h, 3, s, 1)
            out.append(make(f"blocks[{i}]/conv2", ch, ho, ch, 3, 1, 1))
            if s != 1 or c != ch:
                out.append(make(f"blocks[{i}]/proj", c, h, ch, 1, s, 0))
            c, h, i = ch, ho, i + 1
    return out


def resnet_head(cfg: Dict) -> Linear:
    return _linear("head", cfg["stage_channels"][-1], cfg["num_classes"],
                   cfg["sparsity"])


def resnet_conv_ops(cfg: Dict, batch: int, item: int = 2):
    """(flops, bytes) of each compressed conv of one batch."""
    return [conv(cv.c, batch, cv.h, cv.w, cv.o, cv.k, cv.k, cv.stride,
                 cv.pad, cv.k_kept, cv.n_tiles, item)
            for cv in resnet_convs(cfg) if cv.compressed]


def resnet_image_flops(cfg: Dict) -> float:
    """FLOPs of one image over the kept weights: every conv (the dense stem
    whole) and the classifier head."""
    total = 0
    for cv in resnet_convs(cfg):
        ho = out_size(cv.h, cv.k, cv.stride, cv.pad)
        total += 2 * ho * ho * cv.k_kept * cv.o
    head = resnet_head(cfg)
    return total + 2 * head.k_kept * head.d_out
