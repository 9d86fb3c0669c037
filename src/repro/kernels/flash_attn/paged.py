"""Ragged paged flash-attention Pallas kernel (serve.kv_pages backend).

The paged KV cache stores a sequence's rows scattered across fixed-size
physical pages; attention must gather them back. The XLA reference
(`paged_attention_ref`) materializes the gather in HBM — ``n_max * ps``
rows per sequence round-trip regardless of the actual length. This kernel
never materializes the gather: the page table is delivered by scalar
prefetch (SMEM), and one grid dimension walks a sequence's pages
sequentially.  The K/V page operands' index maps read the physical page id
from the prefetched table, so Pallas' own pipeline DMAs page j+1 from HBM
while page j's online-softmax update runs. Rows past the sequence's length
(ragged final page, trash-page table padding) are masked with an explicit probability
zeroing, so a fully-masked page contributes exactly nothing.

The current step's not-yet-written K/V ("new" keys) are folded in at the
last page step — same no-write-in-scan contract as ``attn_decode``:
combine(cache rows < len) ++ new keys is identical math to
write-then-attend(len + Sq).

Grid: ``(B, Sq/block_q, n_pages)``; pages are the sequential ("arbitrary")
axis; m/l/acc persist in VMEM scratch across page steps, one slab per KV
head (GQA groups share their KV head's page DMA).  The wrapper lays q out
as ``[B, Sq/block_q, KV, g*block_q, D]`` (group-major rows per KV head), so
the kernel reads each KV head's ``g*block_q`` query rows as one 2-D tile
and never reshapes across the sublane tiling — any group size g works.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pltpu_compat import (
    COMPILER_PARAMS as _COMPILER_PARAMS,
    ceil_to,
    dot_f32,
    kernel_tag,
    should_interpret,
)

NEG = -1e30

#: page_size x block_q geometry grid raced by profile_op (first = default)
DEFAULT_PAGE_SIZE = 16


def _flash_update(m_ref, l_ref, acc_ref, kvh, s, mask, v, interpret):
    """One masked online-softmax accumulation step for KV head ``kvh``.

    The probability matrix is multiplied by ``mask`` (not just score-masked
    with NEG): when every score so far is masked, m stays at NEG and
    ``exp(NEG - NEG) == 1`` would pollute l/acc of *valid* q rows — e.g. the
    page phase of a sequence whose cache is still empty.
    """
    s = jnp.where(mask, s, NEG)
    m_prev = m_ref[kvh]  # [g*bq, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new) * mask.astype(jnp.float32)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[kvh] = alpha * l_ref[kvh] + p.sum(axis=-1, keepdims=True)
    acc_ref[kvh] = alpha * acc_ref[kvh] + dot_f32(p.astype(v.dtype), v,
                                                  interpret)
    m_ref[kvh] = m_new


def _kernel(tbl_ref, len_ref, q_ref, kn_ref, vn_ref, kp_ref, vp_ref, o_ref,
            m_ref, l_ref, acc_ref, *,
            n_pages: int, page_size: int, block_q: int, sn: int, kv: int,
            g: int, scale: float, interpret: bool):
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # kp_ref/vp_ref hold physical page tbl[b, j] (see the index maps).
    # Padded table entries name the trash page — a real, in-range page whose
    # rows the length mask below always kills.
    length = len_ref[b]

    kvpos = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    page_mask = kvpos < length  # [1, ps]; causal is implied: qpos >= length
    for h0 in range(kv):
        qh = q_ref[0, 0, h0]  # [g*bq, D] this KV head's query rows
        kh = kp_ref[0, :, h0, :]  # [ps, D]
        s = dot_f32(qh, kh.T, interpret) * scale  # [g*bq, ps]
        _flash_update(m_ref, l_ref, acc_ref, h0, s, page_mask,
                      vp_ref[0, :, h0, :], interpret)

    @pl.when(j == n_pages - 1)
    def _new_and_flush():
        tpos = jax.lax.broadcasted_iota(jnp.int32, (1, sn), 1)
        # rows are group-major: row r is query i*bq + r % bq
        qrow = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (g * block_q, 1), 0) % block_q
        new_mask = tpos <= qrow
        for h0 in range(kv):
            qh = q_ref[0, 0, h0]
            s = dot_f32(qh, kn_ref[0, :, h0, :].T, interpret) * scale
            _flash_update(m_ref, l_ref, acc_ref, h0, s, new_mask,
                          vn_ref[0, :, h0, :], interpret)
            out = acc_ref[h0] / jnp.maximum(l_ref[h0], 1e-30)
            o_ref[0, 0, h0] = out.astype(o_ref.dtype)


def paged_attention_pallas(
    q: jax.Array, k_new: jax.Array, v_new: jax.Array,
    k_pages: jax.Array, v_pages: jax.Array,
    tables: jax.Array, lengths: jax.Array, *,
    page_size: int, block_q: int = 8, interpret: bool = False,
) -> jax.Array:
    """Ragged paged attention; semantics == :func:`paged_attention_ref`.

    q [B, Sq, H, D]; k_new/v_new [B, Sq, KV, D] (this step's keys, not yet
    written); k_pages/v_pages [P, page_size, KV, D] physical pages; tables
    [B, n_max] int32 (entries past a sequence's mapping must name any
    in-range page — their rows are masked); lengths [B] int32 cache rows
    valid (the step's start position). Requires H % KV == 0.
    """
    b, sq, h, d = q.shape
    kv = k_pages.shape[2]
    if h % kv != 0:
        raise ValueError(f"paged kernel needs H % KV == 0, got {h} % {kv}")
    if k_pages.shape[1] != page_size:
        raise ValueError(
            f"page_size {page_size} != physical page rows {k_pages.shape[1]}")
    g = h // kv
    n_pages = tables.shape[1]
    scale = 1.0 / math.sqrt(d)
    block_q = min(block_q, ceil_to(sq, 8))
    sq_p = ceil_to(sq, block_q)
    if sq_p != sq:
        pad = ((0, 0), (0, sq_p - sq), (0, 0), (0, 0))
        q = jnp.pad(q, pad)
    nq = sq_p // block_q
    # [B, Sq_p, H, D] -> [B, nq, KV, g*bq, D], rows group-major per KV head
    q = (q.reshape(b, nq, block_q, kv, g, d).transpose(0, 1, 3, 4, 2, 5)
         .reshape(b, nq, kv, g * block_q, d))
    grid = (b, nq, n_pages)
    sn = k_new.shape[1]

    def page_map(bb, ii, jj, tbl_ref, len_ref):
        return (tbl_ref[bb, jj], 0, 0, 0)

    out = pl.pallas_call(
        functools.partial(
            _kernel, n_pages=n_pages, page_size=page_size, block_q=block_q,
            sn=sn, kv=kv, g=g, scale=scale, interpret=interpret,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, kv, g * block_q, d),
                             lambda bb, ii, jj, *_: (bb, ii, 0, 0, 0)),
                pl.BlockSpec((1, sn, kv, d),
                             lambda bb, ii, jj, *_: (bb, 0, 0, 0)),
                pl.BlockSpec((1, sn, kv, d),
                             lambda bb, ii, jj, *_: (bb, 0, 0, 0)),
                pl.BlockSpec((1, page_size, kv, d), page_map),
                pl.BlockSpec((1, page_size, kv, d), page_map),
            ],
            out_specs=pl.BlockSpec((1, 1, kv, g * block_q, d),
                                   lambda bb, ii, jj, *_: (bb, ii, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((kv, g * block_q, 1), jnp.float32),
                pltpu.VMEM((kv, g * block_q, 1), jnp.float32),
                pltpu.VMEM((kv, g * block_q, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, nq, kv, g * block_q, d), q.dtype),
        compiler_params=_COMPILER_PARAMS(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        metadata=kernel_tag("paged_attn"),
        interpret=interpret,
    )(jnp.asarray(tables, jnp.int32), jnp.asarray(lengths, jnp.int32),
      q, k_new, v_new, k_pages, v_pages)
    out = (out.reshape(b, nq, kv, g, block_q, d).transpose(0, 1, 4, 2, 3, 5)
           .reshape(b, sq_p, h, d))
    return out[:, :sq]


def paged_attention_ref(q, k_new, v_new, k_pages, v_pages, tables,
                        lengths) -> jax.Array:
    """XLA reference: gather the pages, run the serve combine-attention.

    Materializes the gathered ``[B, n_max * ps, KV, D]`` cache view in HBM
    — correct everywhere (and the CPU/fallback dispatch candidate), but
    bytes-moved scales with the table width, not the actual lengths.
    """
    from repro.models.attention import _cached_attention

    ps = k_pages.shape[1]
    b, n_max = tables.shape
    kv, d = k_pages.shape[2], k_pages.shape[3]
    kc = k_pages[tables].reshape(b, n_max * ps, kv, d)
    vc = v_pages[tables].reshape(b, n_max * ps, kv, d)
    lengths = jnp.asarray(lengths, jnp.int32)
    return _cached_attention(q, k_new, v_new, kc, vc, limit=lengths,
                             causal=True)


def paged_vmem_bytes(page_size: int, kv: int, d: int, block_q: int, h: int,
                     sn: int, in_bytes: int) -> int:
    """Analytic VMEM footprint of one paged-attention grid step."""
    g = h // max(kv, 1)
    pages = 2 * 2 * page_size * kv * d * in_bytes  # k + v double buffers
    qblk = block_q * h * d * in_bytes
    new = 2 * sn * kv * d * in_bytes
    scr = kv * (block_q * g) * (d + 2) * 4  # m, l, acc in f32
    out = block_q * h * d * in_bytes
    return pages + qblk + new + scr + out


def paged_attention(q, k_new, v_new, k_pages, v_pages, tables, lengths, *,
                    page_size: int, impl: str = None) -> jax.Array:
    """Dispatch-resolved paged attention (the serve decode entry point).

    Builds the execution :func:`~repro.dispatch.paged_attn_key` (page size
    pinned — only matching-geometry pallas candidates are feasible) and
    routes to the winning implementation; the XLA gather reference is the
    universal fallback.
    """
    from repro import fault as _fault
    from repro.dispatch import best_impl, current_phase, paged_attn_key, run_guarded

    b, sq, h, d = q.shape
    kv = k_pages.shape[2]
    key = paged_attn_key(
        q_rows=b * sq, n_heads=h, kv_heads=kv, head_dim=d,
        kv_capacity=tables.shape[1] * page_size, page_size=page_size,
        dtype=q.dtype, phase=current_phase())
    spec = best_impl(key, force=impl)

    def _run(s):
        # kernel-specific fault site (probes at trace time, like the kernel
        # failures it stands in for); a hit quarantines the current rung and
        # run_guarded re-resolves — the XLA gather reference is the floor
        _fault.maybe_fail("kernel.paged_attn", impl=s.name, phase=key.phase)
        if s is not None and s.backend == "pallas":
            return paged_attention_pallas(
                q, k_new, v_new, k_pages, v_pages, tables, lengths,
                page_size=page_size, block_q=s.geom("bq", 8),
                interpret=should_interpret())
        return paged_attention_ref(q, k_new, v_new, k_pages, v_pages, tables,
                                   lengths)

    return run_guarded(key, spec, _run)
