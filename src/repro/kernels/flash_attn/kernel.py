"""Pallas TPU flash-attention kernel (beyond-paper; EXPERIMENTS §Perf).

The dry-run showed materialized attention scores are simultaneously the
dominant HBM traffic and the trigger for TB-scale involuntary all-gathers.
The XLA-level chunked attention fixes the collective side; this kernel is the
TPU-native end state: the online-softmax internals (scores, p, m, l, acc)
live entirely in VMEM — HBM traffic is exactly Q + K + V + O.

Grid: (batch*heads, Sq/block_q, Sk/block_k); the KV dimension is the
sequential ("arbitrary") accumulation axis; m/l/acc persist in VMEM scratch
across KV steps. Causal masking via block-offset iota compares.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pltpu_compat import COMPILER_PARAMS as _COMPILER_PARAMS
from repro.kernels.pltpu_compat import ceil_to, dot_f32, kernel_tag

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            n_kv: int, block_q: int, block_k: int, sk: int, causal: bool,
            scale: float, interpret: bool):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]  # [bq, D]
    k = k_ref[0]  # [bk, D]
    v = v_ref[0]  # [bk, D]
    if interpret:  # XLA:CPU has no bf16 dot
        q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    s = dot_f32(q, k.T, interpret) * scale  # [bq, bk]

    i = pl.program_id(1)
    qpos = i * block_q + jax.lax.iota(jnp.int32, block_q)[:, None]
    kpos = j * block_k + jax.lax.iota(jnp.int32, block_k)[None, :]
    mask = kpos < sk  # padded tail
    if causal:
        mask = mask & (kpos <= qpos)
    s = jnp.where(mask, s, NEG)

    m_prev = m_ref[...]  # [bq, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)  # [bq, bk] f32
    alpha = jnp.exp(m_prev - m_new)  # [bq, 1]
    l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
    pv = dot_f32(p.astype(v.dtype), v, interpret)
    acc_ref[...] = alpha * acc_ref[...] + pv
    m_ref[...] = m_new

    @pl.when(j == n_kv - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
    block_q: int = 128, block_k: int = 128, interpret: bool = False,
) -> jax.Array:
    """q: [BH, Sq, D]; k/v: [BH, Sk, D] (GQA expansion handled by ops.py).
    Returns [BH, Sq, D]."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    block_q = min(block_q, ceil_to(sq, 8))
    block_k = min(block_k, ceil_to(sk, 8))
    sq_p, sk_p = ceil_to(sq, block_q), ceil_to(sk, block_k)
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        k = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, 0)))
    grid = (bh, sq_p // block_q, sk_p // block_k)

    out = pl.pallas_call(
        functools.partial(
            _kernel, n_kv=grid[2], block_q=block_q, block_k=block_k, sk=sk,
            causal=causal, scale=scale, interpret=interpret,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        metadata=kernel_tag("flash_attn"),
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq]
