"""Pallas TPU kernel for column-wise N:M sparse matmul (paper Algorithm 1).

TPU adaptation of the RVV micro-kernel:

  RVV                         TPU (this kernel)
  -------------------------   ---------------------------------------------
  T vector-register           float32 VMEM scratch accumulator [block_b, T]
  accumulators
  scalar weight × data         dense [block_b, block_k] × [block_k, T] MXU
  vector vfmacc per kept       matmul per kept-column *chunk* (the gather of
  column                       block_k kept columns happens in VMEM first)
  indexed vector load of the   one-hot MXU gather of the kept columns from
  data-matrix row              the VMEM-resident activation block
  LMUL / vector length         block_k, tile width T (lane multiples of 128)

The kept-column indices are shared by the whole T-wide output tile (the
paper's column-wise constraint), which is exactly what makes the inner step a
*dense* MXU matmul — sparsity is realized as a shorter contraction, not as
masked compute.

Grid: (B/block_b, n_tiles, k_kept/block_k); the last dimension is a sequential
("arbitrary") accumulation dimension, the first two are parallel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pltpu_compat import COMPILER_PARAMS as _COMPILER_PARAMS
from repro.kernels.pltpu_compat import (
    MEM_HBM,
    ceil_to,
    dma_semaphores,
    dot_f32,
    double_buffer_rotate,
    gather_cols,
    gather_rows,
    gather_vmem_bytes,
    kernel_tag,
    make_async_copy,
)


def chunk_kept(values: jax.Array, idx: jax.Array, block_k: int):
    """Split the kept (reduction) axis into ``block_k``-row chunks.

    ``block_k`` is capped at the 8-aligned kept count, and zero-valued
    padding rows fill the last chunk (they gather index 0 but multiply by 0
    weights).  ``idx`` becomes ``[n_tiles * n_kc, 1, block_k]``: one
    lane-vector block per (tile, chunk) grid step (see :func:`idx_spec`) —
    a ``(1, 1, block_k)`` block spans the array's two minor dims, which
    Mosaic accepts for any ``block_k``; a ``(1, block_k)`` block of the 2-D
    ``idx`` does not.  Returns ``(values, idx_chunks, block_k, n_kc)``.
    """
    n_tiles, k_kept, _ = values.shape
    block_k = min(block_k, ceil_to(k_kept, 8))
    k_pad = ceil_to(k_kept, block_k)
    if k_pad != k_kept:
        values = jnp.pad(values, ((0, 0), (0, k_pad - k_kept), (0, 0)))
        idx = jnp.pad(idx, ((0, 0), (0, k_pad - k_kept)))
    n_kc = k_pad // block_k
    return values, idx.reshape(n_tiles * n_kc, 1, block_k), block_k, n_kc


def idx_spec(block_k: int, n_kc: int) -> pl.BlockSpec:
    """BlockSpec of :func:`chunk_kept`'s idx for a ``(rows, tile, k-chunk)``
    grid."""
    return pl.BlockSpec((1, 1, block_k),
                        lambda i, t, kc: (t * n_kc + kc, 0, 0))


def _kernel(x_ref, idx_ref, v_ref, o_ref, acc_ref, *, n_kc: int, out_dtype, interpret: bool):
    kc = pl.program_id(2)

    @pl.when(kc == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ids = idx_ref[0]  # [1, block_k] int32 — kept d_in indices for this chunk
    # In-VMEM gather of the kept columns: the fusion of "im2col/packing" style
    # data movement into the compute kernel — the gathered operand never
    # exists in HBM.
    x_sel = gather_cols(x_ref, ids, interpret).astype(x_ref.dtype)
    acc_ref[...] += dot_f32(x_sel, v_ref[0], interpret)

    @pl.when(kc == n_kc - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def colwise_nm_matmul_pallas(
    x: jax.Array,
    values: jax.Array,
    idx: jax.Array,
    *,
    block_b: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """y[b, t*T:(t+1)*T] = x[b, idx[t]] @ values[t].

    x: [B, d_in]; values: [n_tiles, k_kept, T]; idx: [n_tiles, k_kept].
    Returns [B, n_tiles * T].
    """
    B, d_in = x.shape
    n_tiles, k_kept, tile = values.shape
    assert idx.shape == (n_tiles, k_kept), (idx.shape, values.shape)

    block_b = min(block_b, ceil_to(B, 8))
    b_pad = ceil_to(B, block_b)
    if b_pad != B:
        x = jnp.pad(x, ((0, b_pad - B), (0, 0)))
    values, idx, block_k, n_kc = chunk_kept(values, idx, block_k)

    n_b = b_pad // block_b
    grid = (n_b, n_tiles, n_kc)

    out = pl.pallas_call(
        functools.partial(_kernel, n_kc=n_kc, out_dtype=x.dtype, interpret=interpret),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, d_in), lambda i, t, kc: (i, 0)),
            idx_spec(block_k, n_kc),
            pl.BlockSpec((1, block_k, tile), lambda i, t, kc: (t, kc, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, tile), lambda i, t, kc: (i, t)),
        out_shape=jax.ShapeDtypeStruct((b_pad, n_tiles * tile), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_b, tile), jnp.float32)],
        compiler_params=_COMPILER_PARAMS(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        metadata=kernel_tag("colwise_nm"),
        interpret=interpret,
    )(x, idx, values)
    return out[:B]


# ---------------------------------------------------------------------------
# Strip-major entry: GEMM directly on packed [n_strips, K, V] strips
# ---------------------------------------------------------------------------


def _strips_kernel(x_ref, idx_ref, v_ref, o_ref, acc_ref, *, n_kc: int,
                   out_dtype, interpret: bool):
    kc = pl.program_id(2)

    @pl.when(kc == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ids = idx_ref[0]  # [1, block_k] kept reduction rows for this chunk
    # gather of the kept strip *rows* from the VMEM-resident [K, V] strip —
    # the strips already sit in the paper's packed layout, so no
    # transpose/relayout ever happens in HBM
    x_sel = gather_rows(x_ref.at[0], ids, interpret).astype(x_ref.dtype)
    acc_ref[...] += dot_f32(v_ref[0], x_sel, interpret,
                            trans_a=True)  # [tile, V]

    @pl.when(kc == n_kc - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def colwise_nm_matmul_strips_pallas(
    strips: jax.Array,
    values: jax.Array,
    idx: jax.Array,
    *,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Column-wise sparse GEMM on packed strips: [n_strips, K, V] -> [O, S*V].

    The strip dim is the Pallas batch grid dim, so the un-fused two-kernel
    conv path consumes ``im2col_pack`` output directly — no
    ``transpose(0, 2, 1).reshape`` HBM relayout between the two kernels.
    Output is [n_tiles*tile, n_strips*V] (the conv's [O, P] layout, P padded
    to whole strips); the caller slices off the ragged-strip padding.
    """
    n_strips, d_in, v = strips.shape
    n_tiles, k_kept, tile = values.shape
    assert idx.shape == (n_tiles, k_kept), (idx.shape, values.shape)

    values, idx, block_k, n_kc = chunk_kept(values, idx, block_k)

    grid = (n_strips, n_tiles, n_kc)
    out = pl.pallas_call(
        functools.partial(_strips_kernel, n_kc=n_kc, out_dtype=strips.dtype,
                          interpret=interpret),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, d_in, v), lambda s, t, kc: (s, 0, 0)),
            idx_spec(block_k, n_kc),
            pl.BlockSpec((1, block_k, tile), lambda s, t, kc: (t, kc, 0)),
        ],
        out_specs=pl.BlockSpec((tile, v), lambda s, t, kc: (t, s)),
        out_shape=jax.ShapeDtypeStruct((n_tiles * tile, n_strips * v),
                                       strips.dtype),
        scratch_shapes=[pltpu.VMEM((tile, v), jnp.float32)],
        compiler_params=_COMPILER_PARAMS(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        metadata=kernel_tag("colwise_nm"),
        interpret=interpret,
    )(strips, idx, values)
    return out


def strips_vmem_bytes(d_in: int, v: int, block_k: int, tile: int,
                      in_bytes: int = 2) -> int:
    """Analytic VMEM footprint of one strip-major grid step."""
    strip = d_in * v * in_bytes
    x_sel = block_k * v * in_bytes + gather_vmem_bytes(block_k, v, in_bytes)
    v_blk = block_k * tile * in_bytes
    acc = tile * v * 4
    out = tile * v * in_bytes
    return strip + x_sel + v_blk + acc + out


# ---------------------------------------------------------------------------
# Pipelined strip-major entry: strips stay in HBM, chunks of ``hb`` strips
# are double-buffered into VMEM scratch — the copy of chunk g+1 overlaps the
# GEMM of chunk g, removing the pack->GEMM back-to-back serialization of the
# two-kernel conv plan.
# ---------------------------------------------------------------------------


def _strips_pipelined_kernel(
    x_ref,        # [n_strips, K, V] packed strips, NOT block-mapped (HBM)
    idx_ref,
    v_ref,
    o_ref,
    buf_ref,      # [2*hb, K, V] double-buffered strip-chunk scratch
    sem_ref,      # [2] DMA completion semaphores
    acc_ref,
    *,
    hb: int,
    n_chunks: int,
    n_strips: int,
    n_kc: int,
    out_dtype,
    interpret: bool,
):
    s = pl.program_id(0)
    t = pl.program_id(1)
    kc = pl.program_id(2)
    g = s // hb

    def origin(gi):
        # fixed-size chunks: the final (ragged) chunk re-covers the tail of
        # the previous one instead of reading past the strip array
        return jnp.minimum(gi * hb, n_strips - hb)

    def chunk_dma(slot, gi):
        return make_async_copy(
            x_ref.at[pl.ds(origin(gi), hb)],
            buf_ref.at[pl.ds(slot * hb, hb)],
            sem_ref.at[slot],
        )

    double_buffer_rotate(chunk_dma, g, n_chunks,
                         gate=(s % hb == 0) & (t == 0) & (kc == 0))

    @pl.when(kc == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ids = idx_ref[0]  # [1, block_k]
    x_blk = buf_ref.at[(g % 2) * hb + (s - origin(g))]  # [K, V], VMEM
    x_sel = gather_rows(x_blk, ids, interpret).astype(buf_ref.dtype)
    acc_ref[...] += dot_f32(v_ref[0], x_sel, interpret,
                            trans_a=True)  # [tile, V]

    @pl.when(kc == n_kc - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def colwise_nm_matmul_strips_pipelined_pallas(
    strips: jax.Array,
    values: jax.Array,
    idx: jax.Array,
    *,
    block_k: int = 128,
    hb: int = 2,
    interpret: bool = False,
) -> jax.Array:
    """Double-buffered strip-major sparse GEMM: [n_strips, K, V] -> [O, S*V].

    Same contract as :func:`colwise_nm_matmul_strips_pallas`, but the strips
    array is NOT pipelined block-by-block by Pallas: it stays in HBM and the
    kernel DMAs chunks of ``hb`` strips into a two-slot VMEM scratch, always
    copying chunk g+1 while the GEMM consumes chunk g.
    """
    n_strips, d_in, v = strips.shape
    n_tiles, k_kept, tile = values.shape
    assert idx.shape == (n_tiles, k_kept), (idx.shape, values.shape)

    hb = max(min(hb, n_strips), 1)
    n_chunks = -(-n_strips // hb)

    values, idx, block_k, n_kc = chunk_kept(values, idx, block_k)

    grid = (n_strips, n_tiles, n_kc)
    out = pl.pallas_call(
        functools.partial(
            _strips_pipelined_kernel, hb=hb, n_chunks=n_chunks,
            n_strips=n_strips, n_kc=n_kc, out_dtype=strips.dtype,
            interpret=interpret),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=MEM_HBM),  # strips stay in HBM
            idx_spec(block_k, n_kc),
            pl.BlockSpec((1, block_k, tile), lambda s, t, kc: (t, kc, 0)),
        ],
        out_specs=pl.BlockSpec((tile, v), lambda s, t, kc: (t, s)),
        out_shape=jax.ShapeDtypeStruct((n_tiles * tile, n_strips * v),
                                       strips.dtype),
        scratch_shapes=[
            pltpu.VMEM((2 * hb, d_in, v), strips.dtype),
            dma_semaphores(2),
            pltpu.VMEM((tile, v), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS(
            # strips advance sequentially: the double-buffer rotation assumes
            # chunk g's steps complete before chunk g+1's begin
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        metadata=kernel_tag("colwise_nm"),
        interpret=interpret,
    )(strips, idx, values)
    return out


def pipelined_strips_vmem_bytes(d_in: int, v: int, hb: int, block_k: int,
                                tile: int, in_bytes: int = 2) -> int:
    """Analytic VMEM footprint of one pipelined strip-GEMM grid step: TWO
    chunks of ``hb`` strips (double buffer) plus the gather/weight/acc/out
    tiles of the plain strip-major kernel."""
    chunks = 2 * hb * d_in * v * in_bytes
    x_sel = block_k * v * in_bytes + gather_vmem_bytes(block_k, v, in_bytes)
    v_blk = block_k * tile * in_bytes
    acc = tile * v * 4
    out = tile * v * in_bytes
    return chunks + x_sel + v_blk + acc + out


def vmem_bytes(block_b: int, block_k: int, d_in: int, tile: int, in_bytes: int = 2) -> int:
    """Analytic VMEM footprint of one grid step (for the auto-tuner)."""
    x_blk = block_b * d_in * in_bytes
    x_sel = (block_b * block_k * in_bytes
             + gather_vmem_bytes(block_b, block_k, in_bytes))
    v_blk = block_k * tile * in_bytes
    acc = block_b * tile * 4
    out = block_b * tile * in_bytes
    return x_blk + x_sel + v_blk + acc + out
