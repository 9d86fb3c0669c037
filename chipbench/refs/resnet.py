"""Plain float32 reference of a ResNet basic-block stack (arXiv:1512.03385)
as the benchmark's configuration states it: a 3x3 stride-1 stem, stages of
two 3x3 convs with an identity or 1x1 strided shortcut, ReLU, no batch
norm (folded into the weights at inference), global average pooling and a
linear classifier.

It reads the weights the benchmark made from the seed, expanding each
column-wise compressed conv (``values``, ``idx`` over the GEMM view
``[kh*kw*c, o]``, rows ordered (kh, kw, c)) back to its dense kernel with
its own code, and imports nothing of the program under test.  Inputs and
outputs are CNHW maps, as the program takes them.  ``quant``, when given,
is applied to both operands of every conv and product: the control
computes in a lower precision that way.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from chipbench.refs.qwen2 import dense_from_compressed

F32 = jnp.float32


def _ident(a):
    return a


def conv_kernel_oihw(p: Dict, c: int, o: int, k: int):
    """Dense OIHW kernel of a conv layer, from either storage."""
    if "values" in p:
        w = dense_from_compressed(p["values"], p["idx"], k * k * c)  # [K, O]
        return w.T.reshape(o, k, k, c).transpose(0, 3, 1, 2)
    return p["w"].astype(F32).transpose(0, 3, 1, 2)  # OHWI -> OIHW


def conv(x_nchw, w_oihw, stride, pad, quant):
    return jax.lax.conv_general_dilated(
        quant(x_nchw), quant(w_oihw), (stride, stride),
        [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision="highest")


def forward(params, convs: List, head, x_cnhw,
            quant: Optional[Callable] = None):
    """Logits [B, classes] of CNHW images.  ``convs`` is the benchmark's
    layer list (``work.resnet_convs``); ``head`` its classifier layer."""
    q = quant or _ident
    x = jnp.transpose(x_cnhw.astype(F32), (1, 0, 2, 3))
    by_name = {c.name: c for c in convs}

    def apply(name, p, inp):
        c = by_name[name]
        return conv(inp, conv_kernel_oihw(p, c.c, c.o, c.k), c.stride,
                    c.pad, q)

    y = jax.nn.relu(apply("stem", params["stem"], x))
    for i, blk in enumerate(params["blocks"]):
        z = jax.nn.relu(apply(f"blocks[{i}]/conv1", blk["conv1"], y))
        z = apply(f"blocks[{i}]/conv2", blk["conv2"], z)
        short = apply(f"blocks[{i}]/proj", blk["proj"], y) \
            if "proj" in blk else y
        y = jax.nn.relu(z + short)
    feats = y.mean(axis=(2, 3))
    hp = params["head"]
    w = dense_from_compressed(hp["values"], hp["idx"], head.d_in) \
        if "values" in hp else hp["w"].astype(F32)
    out = jnp.matmul(q(feats), q(w), precision="highest")
    if "b" in hp:
        out = out + hp["b"].astype(F32)
    return out
