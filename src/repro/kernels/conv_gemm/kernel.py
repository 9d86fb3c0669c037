"""Pallas conv megakernel: fused im2col + pack + column-wise N:M sparse GEMM.

The paper's two building blocks (Algorithm 2's fused im2col+packing and
Algorithm 1's column-wise sparse micro-kernel) are here collapsed into ONE
kernel: each packed strip tile is *produced in VMEM* — (kh, kw, c) rows
gathered straight from the CNHW feature map with the same index arithmetic as
``im2col_pack/kernel.py`` — and immediately consumed by the in-VMEM
kept-column gather + dense MXU matmul of ``colwise_nm/kernel.py``.  The patch
matrix / packed strips never exist in HBM:

  two-kernel path   HBM traffic:  write strips, read strips (transposed
                    relayout!), write GEMM output          — 3 round-trips
  this megakernel   HBM traffic:  read feature map, write output — 0 extra

Grid: (n_strips, n_tiles, k_chunks).  At the first (t, kc) step of strip s
the kernel packs the strip's full [Kh*Kw*C, V] patch into VMEM scratch from
an aligned row window of the map (``im2col_pack.kernel.tap_tile``).  Every
step (s, t, kc) then gathers the block_k kept rows of chunk kc for output
tile t from that patch (a one-hot MXU row gather), multiplies by the
[block_k, T] compressed weight chunk, and accumulates into a float32 [T, V]
VMEM scratch.  The output is written directly in [O, P] layout (P padded to
n_strips*V), so the caller's final ``y.T`` relayout disappears as well.
Ragged final strips and out-of-map (kh, kw) taps are masked exactly as in
the standalone pack kernel.
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
    gather_rows,
    gather_vmem_bytes,
    kernel_tag,
    make_async_copy,
)

from repro.kernels.colwise_nm.kernel import chunk_kept, idx_spec
from repro.kernels.im2col_pack.kernel import (
    ROW_ALIGN,
    band_plan,
    first_row,
    pad_rows,
    strip_window,
    tap_tile,
    window_origin,
    window_plan,
)
from repro.kernels.im2col_pack.ref import out_size


def _pack_patch(patch_ref, win, org, s, *, kh, kw, c, interpret, **geo):
    """Write strip ``s``'s full [Kh*Kw*C, v] im2col patch into VMEM scratch
    ``patch_ref`` from the row window ``win`` (origin ``org``): one
    :func:`tap_tile` per kernel tap, rows ordered (kh, kw, c) like the
    compressed weight's reduction dim."""
    for k in range(kh * kw):
        patch_ref[k * c:(k + 1) * c, :] = tap_tile(
            win, org, s, ikh=k // kw, ikw=k % kw, interpret=interpret,
            **geo).astype(patch_ref.dtype)


def _sparse_gemm_step(patch_ref, idx_ref, v_ref, acc_ref, interpret):
    """One Algorithm-1 step: gather this chunk's kept patch rows and
    accumulate ``values[t, chunk].T @ rows`` into the [T, v] accumulator."""
    rows = gather_rows(patch_ref, idx_ref[0], interpret)
    acc_ref[...] += dot_f32(v_ref[0], rows.astype(patch_ref.dtype), interpret,
                            trans_a=True)


def _kernel(
    x_ref,
    idx_ref,
    v_ref,
    o_ref,
    patch_ref,
    acc_ref,
    *,
    kh: int,
    kw: int,
    stride: int,
    pad: int,
    v: int,
    c: int,
    b: int,
    h: int,
    w: int,
    ho: int,
    wo: int,
    win_rows: int,
    bh_pad: int,
    n_kc: int,
    out_dtype,
    interpret: bool,
):
    s = pl.program_id(0)
    t = pl.program_id(1)
    kc = pl.program_id(2)

    @pl.when((t == 0) & (kc == 0))
    def _pack():
        # the packed strip tile is born here, in VMEM, and never touches HBM
        top = first_row(s * v, h=h, ho=ho, wo=wo, stride=stride, pad=pad)
        org = window_origin(top, win_rows=win_rows, bh_pad=bh_pad)
        _pack_patch(patch_ref, x_ref[:, pl.ds(org, win_rows), :], org, s,
                    kh=kh, kw=kw, c=c, stride=stride, pad=pad, b=b, h=h, w=w,
                    ho=ho, wo=wo, v=v, interpret=interpret)

    @pl.when(kc == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _sparse_gemm_step(patch_ref, idx_ref, v_ref, acc_ref, interpret)

    @pl.when(kc == n_kc - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def conv2d_fused_pallas(
    x: jax.Array,
    values: jax.Array,
    idx: jax.Array,
    *,
    kh: int,
    kw: int,
    stride: int = 1,
    pad: int = 0,
    v: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Fused conv: CNHW map -> [O, n_strips*V] sparse-GEMM output.

    x: [C, B, H, W]; values: [n_tiles, k_kept, T]; idx: [n_tiles, k_kept]
    with kept rows indexed in the (kh, kw, c)-flattened reduction dim.
    Columns past B*Ho*Wo are strip padding (zeros); the ops wrapper slices
    them off and reshapes to CNHW.
    """
    c, b, h, w = x.shape
    ho = out_size(h, kh, stride, pad)
    wo = out_size(w, kw, stride, pad)
    n_pos = b * ho * wo
    n_strips = -(-n_pos // v)
    n_tiles, k_kept, tile = values.shape
    assert idx.shape == (n_tiles, k_kept), (idx.shape, values.shape)
    win_rows, bh_pad = strip_window(b=b, h=h, kh=kh, stride=stride, pad=pad,
                                    ho=ho, wo=wo, v=v)

    values, idx, block_k, n_kc = chunk_kept(values, idx, block_k)

    grid = (n_strips, n_tiles, n_kc)
    out = pl.pallas_call(
        functools.partial(
            _kernel, kh=kh, kw=kw, stride=stride, pad=pad, v=v,
            c=c, b=b, h=h, w=w, ho=ho, wo=wo, win_rows=win_rows,
            bh_pad=bh_pad, n_kc=n_kc, out_dtype=x.dtype, interpret=interpret,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((c, bh_pad, w), lambda s, t, kc: (0, 0, 0)),
            idx_spec(block_k, n_kc),
            pl.BlockSpec((1, block_k, tile), lambda s, t, kc: (t, kc, 0)),
        ],
        out_specs=pl.BlockSpec((tile, v), lambda s, t, kc: (t, s)),
        out_shape=jax.ShapeDtypeStruct((n_tiles * tile, n_strips * v), x.dtype),
        scratch_shapes=[pltpu.VMEM((kh * kw * c, v), x.dtype),
                        pltpu.VMEM((tile, v), jnp.float32)],
        compiler_params=_COMPILER_PARAMS(
            # the strip's patch is packed at its (t, kc) == (0, 0) step and
            # reused by every later tile and chunk of that strip
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        metadata=kernel_tag("conv_fused"),
        interpret=interpret,
    )(pad_rows(x, bh_pad), idx, values)
    return out


def _conv_step_vmem_bytes(c, w, win_rows, taps, v, block_k, tile,
                          in_bytes) -> int:
    """VMEM of one megakernel grid step beyond its map storage: the row
    window with its column-select temporaries, the packed patch, the
    kept-row gather, weight chunk, accumulator and output tile."""
    window = c * win_rows * (w * in_bytes + 2 * v * 4)
    patch = taps * c * v * in_bytes
    rows = block_k * v * in_bytes + gather_vmem_bytes(block_k, v, in_bytes)
    v_blk = block_k * tile * in_bytes
    acc = tile * v * 4
    out = tile * v * in_bytes
    return window + patch + rows + v_blk + acc + out


def fused_vmem_bytes(c: int, b: int, h: int, w: int, v: int, block_k: int,
                     tile: int, in_bytes: int = 2, *, kh: int = 1,
                     kw: int = 1, stride: int = 1, pad: int = 0) -> int:
    """Analytic VMEM footprint of one megakernel grid step: the whole
    (row-padded) CNHW map stays resident, double-buffered by the pipeline
    (it is the only input the kernel reads), plus the per-step working set
    of :func:`_conv_step_vmem_bytes`."""
    ho = out_size(h, kh, stride, pad)
    wo = out_size(w, kw, stride, pad)
    win_rows, bh_pad = strip_window(b=b, h=h, kh=kh, stride=stride, pad=pad,
                                    ho=ho, wo=wo, v=v)
    fmap = 2 * c * bh_pad * w * in_bytes
    return fmap + _conv_step_vmem_bytes(c, w, win_rows, kh * kw, v, block_k,
                                        tile, in_bytes)


# ---------------------------------------------------------------------------
# Banded megakernel: H-tiled variant — only a row band of the map is resident
# ---------------------------------------------------------------------------


def _banded_kernel(
    x_ref,        # [C, bh_pad, W'] row/lane-padded feature map, in HBM
    idx_ref,
    v_ref,
    o_ref,
    band_ref,     # [2, C, win_rows, W'] double-buffered row-window scratch
    sem_ref,      # [2] DMA completion semaphores
    patch_ref,
    acc_ref,
    *,
    kh: int,
    kw: int,
    stride: int,
    pad: int,
    v: int,
    hb: int,
    win_rows: int,
    bh_pad: int,
    n_bands: int,
    c: int,
    b: int,
    h: int,
    w: int,
    ho: int,
    wo: int,
    n_kc: int,
    out_dtype,
    interpret: bool,
):
    s = pl.program_id(0)
    t = pl.program_id(1)
    kc = pl.program_id(2)
    g = s // hb

    def origin(gi):
        # the DMA start and wait descriptors must agree exactly: both
        # recompute the band's window origin from its first position
        top = first_row(gi * (hb * v), h=h, ho=ho, wo=wo, stride=stride,
                        pad=pad)
        return window_origin(top, win_rows=win_rows, bh_pad=bh_pad)

    def band_dma(slot, gi):
        return make_async_copy(
            x_ref.at[:, pl.ds(origin(gi), win_rows), :],
            band_ref.at[slot],
            sem_ref.at[slot],
        )

    # Double buffering: at the first grid step of band g, kick off the DMA
    # for band g+1, THEN block on band g's copy — band g+1's rows stream in
    # while the (n_tiles * n_kc * hb-strip) GEMM steps of band g run.
    double_buffer_rotate(band_dma, g, n_bands,
                         gate=(s % hb == 0) & (t == 0) & (kc == 0))

    @pl.when((t == 0) & (kc == 0))
    def _pack():
        # same strip packing as the resident megakernel, from the band's
        # window instead of the resident map
        _pack_patch(patch_ref, band_ref[g % 2], origin(g), s, kh=kh, kw=kw,
                    c=c, stride=stride, pad=pad, b=b, h=h, w=w, ho=ho, wo=wo,
                    v=v, interpret=interpret)

    @pl.when(kc == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _sparse_gemm_step(patch_ref, idx_ref, v_ref, acc_ref, interpret)

    @pl.when(kc == n_kc - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def conv2d_fused_banded_pallas(
    x: jax.Array,
    values: jax.Array,
    idx: jax.Array,
    *,
    kh: int,
    kw: int,
    stride: int = 1,
    pad: int = 0,
    v: int = 128,
    block_k: int = 128,
    hb: int = 2,
    interpret: bool = False,
) -> jax.Array:
    """H-tiled fused conv: like :func:`conv2d_fused_pallas`, but the feature
    map stays in HBM and only a double-buffered row band is VMEM-resident.

    The map is viewed as [C, B*H, W] rows; each band (``hb`` strips) DMAs
    its aligned window of input rows (strip rows + kh-1 halo, see
    :func:`~repro.kernels.im2col_pack.kernel.window_plan`) into one of two
    scratch slots with ``make_async_copy`` while the previous band's
    packing + Algorithm-1 MXU loop runs.  Output layout and semantics are
    identical to the resident megakernel — [O, n_strips*V], strip padding
    sliced off by the ops wrapper.
    """
    c, b, h, w = x.shape
    ho = out_size(h, kh, stride, pad)
    wo = out_size(w, kw, stride, pad)
    n_pos = b * ho * wo
    n_strips = -(-n_pos // v)
    n_tiles, k_kept, tile = values.shape
    assert idx.shape == (n_tiles, k_kept), (idx.shape, values.shape)

    hb = max(min(hb, n_strips), 1)
    n_bands, band_rows = band_plan(b=b, h=h, kh=kh, stride=stride, pad=pad,
                                   ho=ho, wo=wo, v=v, hb=hb)
    win_rows, bh_pad = window_plan(band_rows, b * h)

    values, idx, block_k, n_kc = chunk_kept(values, idx, block_k)

    grid = (n_strips, n_tiles, n_kc)
    out = pl.pallas_call(
        functools.partial(
            _banded_kernel, kh=kh, kw=kw, stride=stride, pad=pad, v=v,
            hb=hb, win_rows=win_rows, bh_pad=bh_pad, n_bands=n_bands,
            c=c, b=b, h=h, w=w, ho=ho, wo=wo, n_kc=n_kc,
            out_dtype=x.dtype, interpret=interpret,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=MEM_HBM),  # map stays in HBM
            idx_spec(block_k, n_kc),
            pl.BlockSpec((1, block_k, tile), lambda s, t, kc: (t, kc, 0)),
        ],
        out_specs=pl.BlockSpec((tile, v), lambda s, t, kc: (t, s)),
        out_shape=jax.ShapeDtypeStruct((n_tiles * tile, n_strips * v), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, c, win_rows, ceil_to(w, 128)), x.dtype),
            dma_semaphores(2),
            pltpu.VMEM((kh * kw * c, v), x.dtype),
            pltpu.VMEM((tile, v), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS(
            # strips advance sequentially: the double-buffer rotation assumes
            # band g's steps complete before band g+1's begin
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        metadata=kernel_tag("conv_fused_banded"),
        interpret=interpret,
    )(pad_rows(x, bh_pad, lanes=128), idx, values)
    return out


def banded_vmem_bytes(c: int, w: int, band_rows: int, v: int, block_k: int,
                      tile: int, in_bytes: int = 2, *, taps: int = 1) -> int:
    """Analytic VMEM footprint of one banded-megakernel grid step: TWO
    aligned row windows (double buffer) of ``band_rows`` needed rows instead
    of the whole map, plus the resident kernel's per-step working set for a
    ``taps``-tap (kh*kw) conv."""
    win_rows = ceil_to(band_rows + ROW_ALIGN - 1, ROW_ALIGN)
    w = ceil_to(w, 128)  # the HBM map's columns are lane-padded
    bands = 2 * c * win_rows * w * in_bytes
    return bands + _conv_step_vmem_bytes(c, w, win_rows, taps, v, block_k,
                                         tile, in_bytes)
