"""Ragged paged flash-attention Pallas kernel (serve.kv_pages backend).

The paged KV cache stores a sequence's rows scattered across fixed-size
physical pages; attention must gather them back. The XLA reference
(`paged_attention_ref`) materializes the gather in HBM — ``n_max * ps``
rows per sequence round-trip regardless of the actual length. This kernel
visits only the pages a sequence has filled.

Cache layout: one array per K and V for all layers, ``[L, P, ps, KV*D]``
(P includes the trash page). A page is a ``[ps, KV*D]`` tile: the KV heads
side by side on the lanes, so a 64-wide head dim fills the 128-lane tiling
in pairs instead of padding it. The kernel takes the stacked cache in HBM
with the layer index as a scalar-prefetch operand, so the layer scan hands
it no per-layer slice and nothing is copied.

Grid: ``(B, Sq/block_q)``, one sequence (and query block) per step. Inside
a step a loop walks compute blocks of ``pages_per_block`` pages, up to
``ceil(len / ps)`` pages only: pages wholly past a sequence's length are
neither copied nor multiplied. Each block's pages are copied by async DMAs
from the scalar-prefetched table into one of two VMEM slots; block i+1's
copies (or the next grid step's first block's) run while block i's
online-softmax update does: the shared ``double_buffer_rotate`` walks the
blocks of all grid steps as one chunk stream, so the steps are "arbitrary"
and the count of chunks before a step persists in SMEM scratch.

GQA with any group size g: the wrapper lays each query row out
block-diagonally over the ``KV*D`` lanes (head h's q in its KV head's D
lanes, zeros elsewhere), so one ``[M, KV*D] x [KV*D, rows]`` product scores
every head against its own KV head, and one ``[M, rows] x [rows, KV*D]``
product accumulates; the wrapper keeps each head's own D lanes of the
result. A decode step (``Sq == 1``) is H rows, not padded to a query block.

The current step's not-yet-written K/V ("new" keys) are folded in once, at
the end of the step — same no-write-in-scan contract as ``attn_decode``:
combine(cache rows < len) ++ new keys is identical math to
write-then-attend(len + Sq). Cached rows past the length (the ragged final
page, and stale rows of a slot) are masked in the scores and zeroed in V,
so junk there, NaN included, never reaches the output.
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
    MEM_HBM,
    GuardedCopies,
    ceil_to,
    dot_f32,
    double_buffer_rotate,
    kernel_tag,
    make_async_copy,
    should_interpret,
)

NEG = -1e30


def _dot_nt(a, b, interpret: bool):
    """``a @ b.T`` with float32 accumulation (contract both last dims)."""
    if interpret:
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _online_update(carry, s, v, interpret):
    """One online-softmax accumulation of scores ``s`` [M, n] (masked
    entries already NEG) against values ``v`` [n, F]. Every call sees at
    least one live key per row, so ``m`` is finite afterwards and a masked
    entry's ``exp(NEG - m)`` is exactly 0."""
    m, l, acc = carry
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l = alpha * l + p.sum(axis=-1, keepdims=True)
    acc = alpha * acc + dot_f32(p.astype(v.dtype), v, interpret)
    return m_new, l, acc


def _kernel(tbl_ref, len_ref, layer_ref, q_ref, kn_ref, vn_ref, kp_hbm,
            vp_hbm, o_ref, kbuf, vbuf, sems, chunk_ref, *,
            page_size: int, pages_per_block: int, block_q: int, sn: int,
            scale: float, interpret: bool):
    b = pl.program_id(0)
    i = pl.program_id(1)
    n_b = pl.num_programs(0)
    n_q = pl.num_programs(1)
    ps, ppb = page_size, pages_per_block
    rows = ps * ppb
    n_tbl = tbl_ref.shape[1]
    layer = layer_ref[0]

    def length(bb):
        return jnp.minimum(len_ref[bb], n_tbl * ps)

    def n_blocks(bb):
        return (length(bb) + rows - 1) // rows

    # The chunks the DMA rotation walks are the compute blocks of every
    # grid step in order, so block i+1's copies, or the next step's first
    # block's, stream in behind block i's update.  chunk_ref holds the
    # chunks before this step and the total.
    @pl.when((b == 0) & (i == 0))
    def _count():
        chunk_ref[0] = 0
        chunk_ref[1] = n_q * jax.lax.fori_loop(
            0, n_b, lambda bb, n: n + n_blocks(bb), 0)

    def block_dma(seq, blk, slot):
        """Block ``blk`` of sequence ``seq`` into ``slot``: its pages below
        the length only."""
        seq = jnp.minimum(seq, n_b - 1)
        n_pages = (length(seq) + ps - 1) // ps
        copies = []
        for p in range(ppb):
            j = blk * ppb + p
            page = tbl_ref[seq, jnp.minimum(j, n_tbl - 1)]
            for src, buf, w in ((kp_hbm, kbuf, 0), (vp_hbm, vbuf, 1)):
                copies.append((j < n_pages, make_async_copy(
                    src.at[layer, page], buf.at[slot, pl.ds(p * ps, ps)],
                    sems.at[w, slot])))
        return GuardedCopies(copies)

    n_len = length(b)
    nb = n_blocks(b)
    g0, n_chunks = chunk_ref[0], chunk_ref[1]
    # the sequence whose first block follows this step's last: this one
    # again for its next query block, else the next with any cached rows
    after = jax.lax.while_loop(
        lambda x: (x < n_b) & (n_blocks(jnp.minimum(x, n_b - 1)) == 0),
        lambda x: x + 1, b + 1)
    nxt = jnp.where((i + 1 < n_q) & (nb > 0), b, after)
    q = q_ref[0, 0]  # [M, F]
    m_rows, f = q.shape

    def body(blk, carry):
        g = g0 + blk
        last = blk + 1 == nb

        def dma(slot, gi):
            here = gi == g
            return block_dma(jnp.where(here | ~last, b, nxt),
                             jnp.where(here, blk, jnp.where(last, 0, blk + 1)),
                             slot)

        double_buffer_rotate(dma, g, n_chunks, gate=True)
        slot = g % 2
        pos = blk * rows + jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        s = _dot_nt(q, kbuf[slot], interpret) * scale  # [M, rows]
        s = jnp.where(pos < n_len, s, NEG)
        vpos = blk * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        v = jnp.where(vpos < n_len, vbuf[slot], 0)
        return _online_update(carry, s, v, interpret)

    carry = (jnp.full((m_rows, 1), NEG, jnp.float32),
             jnp.zeros((m_rows, 1), jnp.float32),
             jnp.zeros((m_rows, f), jnp.float32))
    carry = jax.lax.fori_loop(0, nb, body, carry)
    chunk_ref[0] = g0 + nb

    # the step's own keys (zero-padded to 8 rows): query row r is query
    # i*bq + r % bq (head-major rows), and sees new key t iff t <= its
    # position
    tpos = jax.lax.broadcasted_iota(jnp.int32, (1, kn_ref.shape[1]), 1)
    qpos = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (m_rows, 1), 0) % block_q
    s = _dot_nt(q, kn_ref[0], interpret) * scale
    s = jnp.where((tpos <= qpos) & (tpos < sn), s, NEG)
    _, l, acc = _online_update(carry, s, vn_ref[0], interpret)
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)


def paged_attention_pallas(
    q: jax.Array, k_new: jax.Array, v_new: jax.Array,
    k_pages: jax.Array, v_pages: jax.Array,
    tables: jax.Array, lengths: jax.Array, layer=0, *,
    page_size: int, pages_per_block: int = 8, block_q: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Ragged paged attention; semantics == :func:`paged_attention_ref`.

    q [B, Sq, H, D]; k_new/v_new [B, Sq, KV, D] (this step's keys, not yet
    written); k_pages/v_pages [L, P, page_size, KV*D] physical pages of
    every layer; tables [B, n_max] int32 (entries past a sequence's
    mapping are never read); lengths [B] int32 cache rows valid (the step's
    start position); ``layer`` the index into L (an int or a traced int32
    scalar). ``block_q`` query rows per grid step when Sq > 1. Requires
    H % KV == 0.
    """
    b, sq, h, d = q.shape
    kv = k_new.shape[2]
    if h % kv != 0:
        raise ValueError(f"paged kernel needs H % KV == 0, got {h} % {kv}")
    if k_pages.shape[2] != page_size:
        raise ValueError(
            f"page_size {page_size} != physical page rows {k_pages.shape[2]}")
    if k_pages.shape[3] != kv * d:
        raise ValueError(f"page rows of {k_pages.shape[3]} lanes, expected "
                         f"KV*D = {kv * d}")
    g = h // kv
    f = kv * d
    scale = 1.0 / math.sqrt(d)
    bq = 1 if sq == 1 else min(block_q, sq)
    sq_p = ceil_to(sq, bq)
    nq = sq_p // bq
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0)))
    # [B, Sq_p, H, D] -> [B, nq, H*bq, KV*D]: row h*bq + r holds query
    # i*bq + r of head h in its KV head's D lanes, zeros elsewhere
    own = (jnp.arange(h)[:, None] // g == jnp.arange(kv)[None, :])
    q = q.reshape(b, nq, bq, h, d).transpose(0, 1, 3, 2, 4)
    q = jnp.where(own[None, None, :, None, :, None], q[:, :, :, :, None, :],
                  jnp.zeros((), q.dtype))
    m_rows = h * bq
    m_pad = ceil_to(m_rows, 8)
    q = q.reshape(b, nq, m_rows, f)
    if m_pad != m_rows:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, m_pad - m_rows), (0, 0)))
    sn_p = ceil_to(sq, 8)
    k_new, v_new = (jnp.pad(t.reshape(b, sq, f), ((0, 0), (0, sn_p - sq),
                                                  (0, 0)))
                    for t in (k_new, v_new))
    rows = page_size * pages_per_block

    out = pl.pallas_call(
        functools.partial(
            _kernel, page_size=page_size, pages_per_block=pages_per_block,
            block_q=bq, sn=sq, scale=scale, interpret=interpret,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, nq),
            in_specs=[
                pl.BlockSpec((1, 1, m_pad, f),
                             lambda bb, ii, *_: (bb, ii, 0, 0)),
                pl.BlockSpec((1, sn_p, f), lambda bb, ii, *_: (bb, 0, 0)),
                pl.BlockSpec((1, sn_p, f), lambda bb, ii, *_: (bb, 0, 0)),
                pl.BlockSpec(memory_space=MEM_HBM),
                pl.BlockSpec(memory_space=MEM_HBM),
            ],
            out_specs=pl.BlockSpec((1, 1, m_pad, f),
                                   lambda bb, ii, *_: (bb, ii, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, rows, f), k_pages.dtype),
                pltpu.VMEM((2, rows, f), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, nq, m_pad, f), q.dtype),
        compiler_params=_COMPILER_PARAMS(
            # a step prefetches the next step's first block: in order
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        metadata=kernel_tag("paged_attn"),
        interpret=interpret,
    )(jnp.asarray(tables, jnp.int32), jnp.asarray(lengths, jnp.int32),
      jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      q, k_new, v_new, k_pages, v_pages)
    # keep each head's own KV head lanes: [B, nq, H, bq, D]
    out = out[:, :, :m_rows].reshape(b, nq, h, bq, kv, d)
    out = jnp.take_along_axis(
        out, (jnp.arange(h) // g)[None, None, :, None, None, None],
        axis=4)[:, :, :, :, 0]
    out = out.transpose(0, 1, 3, 2, 4).reshape(b, sq_p, h, d)
    return out[:, :sq]


def paged_attention_ref(q, k_new, v_new, k_pages, v_pages, tables,
                        lengths, layer=0) -> jax.Array:
    """XLA reference: gather the pages, run the serve combine-attention.

    Materializes the gathered ``[B, n_max * ps, KV, D]`` cache view in HBM
    — correct everywhere (and the CPU/fallback dispatch candidate), but
    bytes-moved scales with the table width, not the actual lengths.
    """
    from repro.models.attention import _cached_attention

    ps = k_pages.shape[2]
    b, n_max = tables.shape
    kv, d = k_new.shape[2], k_new.shape[3]
    kc = k_pages[layer][tables].reshape(b, n_max * ps, kv, d)
    vc = v_pages[layer][tables].reshape(b, n_max * ps, kv, d)
    lengths = jnp.asarray(lengths, jnp.int32)
    return _cached_attention(q, k_new, v_new, kc, vc, limit=lengths,
                             causal=True)


def paged_vmem_bytes(page_size: int, pages_per_block: int, kv: int, d: int,
                     h: int, in_bytes: int) -> int:
    """Analytic VMEM footprint of one decode grid step: the two K and V
    block slots, and the double-buffered q, new K/V (8 rows) and output
    blocks, with the f32 accumulator."""
    f = kv * d
    m_rows = ceil_to(h, 8)
    slots = 2 * 2 * page_size * pages_per_block * f * in_bytes
    io = 2 * (2 * m_rows + 2 * 8) * f * in_bytes
    acc = m_rows * (f + 2) * 4
    return slots + io + acc


def paged_attention(q, k_new, v_new, k_pages, v_pages, tables, lengths,
                    layer=0, *, page_size: int, impl: str = None) -> jax.Array:
    """Dispatch-resolved paged attention (the serve decode entry point).

    Builds the execution :func:`~repro.dispatch.paged_attn_key` (page size
    pinned — only matching-geometry pallas candidates are feasible) and
    routes to the winning implementation; the XLA gather reference is the
    universal fallback.
    """
    from repro import fault as _fault
    from repro.dispatch import best_impl, current_phase, paged_attn_key, run_guarded

    b, sq, h, d = q.shape
    kv = k_new.shape[2]
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
                q, k_new, v_new, k_pages, v_pages, tables, lengths, layer,
                page_size=page_size, pages_per_block=s.geom("ppb", 8),
                interpret=should_interpret())
        return paged_attention_ref(q, k_new, v_new, k_pages, v_pages, tables,
                                   lengths, layer)

    return run_guarded(key, spec, _run)
