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
the kernel packs the strip's full [Kh*Kw*C, V] patch into VMEM scratch, by
the pack kernel's two packers: for a "same" stride-1 conv, static lane
shifts of the strip's window of the lane-flattened map
(``im2col_pack.kernel.shift_tile``); otherwise the one-hot select from an
aligned row window of the map (``im2col_pack.kernel.tap_tile``).  Every
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
    flatten_lanes,
    lane_plan,
    lane_window,
    pad_rows,
    shift_path,
    shift_tile,
    strip_window,
    tap_tile,
    window_origin,
    window_plan,
)
from repro.kernels.im2col_pack.ref import out_size


def _pack_patch(patch_ref, tile, *, taps, c):
    """Write a strip's full [taps*C, v] im2col patch into VMEM scratch
    ``patch_ref``, ``tile(k)`` giving tap k's [C, v] rows: rows ordered
    (kh, kw, c) like the compressed weight's reduction dim."""
    for k in range(taps):
        patch_ref[k * c:(k + 1) * c, :] = tile(k).astype(patch_ref.dtype)


def _shift_taps(win, s, *, kw, pad, halo, b, h, w, v):
    """``tile(k)`` of :func:`_pack_patch` for the shift packer: tap k's
    rows of strip ``s`` copied from its lane window ``win``."""
    return lambda k: shift_tile(win, s, ikh=k // kw, ikw=k % kw, pad=pad,
                                halo=halo, b=b, h=h, w=w, v=v)


def _select_taps(win, org, s, *, kw, interpret, **geo):
    """``tile(k)`` of :func:`_pack_patch` for the select packer: tap k's
    rows of strip ``s`` gathered from the row window ``win`` whose first
    row is map row ``org``."""
    return lambda k: tap_tile(win, org, s, ikh=k // kw, ikw=k % kw,
                              interpret=interpret, **geo)


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
    halo: int | None,
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
        if halo is not None:
            win = x_ref[:, lane_window(s, v=v, lanes=v + 2 * halo)]
            tile = _shift_taps(win, s, kw=kw, pad=pad, halo=halo, b=b, h=h,
                               w=w, v=v)
        else:
            top = first_row(s * v, h=h, ho=ho, wo=wo, stride=stride, pad=pad)
            org = window_origin(top, win_rows=win_rows, bh_pad=bh_pad)
            tile = _select_taps(x_ref[:, pl.ds(org, win_rows), :], org, s,
                                kw=kw, interpret=interpret, stride=stride,
                                pad=pad, b=b, h=h, w=w, ho=ho, wo=wo, v=v)
        _pack_patch(patch_ref, tile, taps=kh * kw, c=c)

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
    if shift_path(stride=stride, h=h, w=w, ho=ho, wo=wo):
        plan = lane_plan(b=b, h=h, w=w, pad=pad, v=v)
        halo, win_rows, bh_pad = plan.halo, 0, 0
        x_in = flatten_lanes(x, plan)
        x_spec = pl.BlockSpec((c, plan.lanes), lambda s, t, kc: (0, 0))
    else:
        halo = None
        win_rows, bh_pad = strip_window(b=b, h=h, kh=kh, stride=stride,
                                        pad=pad, ho=ho, wo=wo, v=v)
        x_in = pad_rows(x, bh_pad)
        x_spec = pl.BlockSpec((c, bh_pad, w), lambda s, t, kc: (0, 0, 0))

    values, idx, block_k, n_kc = chunk_kept(values, idx, block_k)

    grid = (n_strips, n_tiles, n_kc)
    out = pl.pallas_call(
        functools.partial(
            _kernel, kh=kh, kw=kw, stride=stride, pad=pad, v=v,
            c=c, b=b, h=h, w=w, ho=ho, wo=wo, win_rows=win_rows,
            bh_pad=bh_pad, halo=halo, n_kc=n_kc, out_dtype=x.dtype,
            interpret=interpret,
        ),
        grid=grid,
        in_specs=[
            x_spec,
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
    )(x_in, idx, values)
    return out


def _select_window_bytes(c, w, win_rows, v, in_bytes) -> int:
    """A row window of the map with its column-select temporaries."""
    return c * win_rows * (w * in_bytes + 2 * v * 4)


def _shift_window_bytes(c, halo, v, in_bytes) -> int:
    """A strip's lane window, with one tap's shifted copy and masked tile
    (relaid in 32 bits)."""
    return c * (v + 2 * halo) * in_bytes + 2 * c * v * 4


def _conv_step_vmem_bytes(window, c, taps, v, block_k, tile,
                          in_bytes) -> int:
    """VMEM of one megakernel grid step beyond its map storage: the
    packer's ``window`` bytes, the packed patch, the kept-row gather,
    weight chunk, accumulator and output tile."""
    patch = taps * c * v * in_bytes
    rows = block_k * v * in_bytes + gather_vmem_bytes(block_k, v, in_bytes)
    v_blk = block_k * tile * in_bytes
    acc = tile * v * 4
    out = tile * v * in_bytes
    return window + patch + rows + v_blk + acc + out


def fused_vmem_bytes(c: int, b: int, h: int, w: int, v: int, block_k: int,
                     tile: int, in_bytes: int = 2, *, kh: int = 1,
                     kw: int = 1, stride: int = 1, pad: int = 0) -> int:
    """Analytic VMEM footprint of one megakernel grid step: the whole map
    stays resident, double-buffered by the pipeline (it is the only input
    the kernel reads) — lane-flattened for the shift packer, row-padded
    CNHW for the select packer — plus the per-step working set of
    :func:`_conv_step_vmem_bytes`."""
    ho = out_size(h, kh, stride, pad)
    wo = out_size(w, kw, stride, pad)
    if shift_path(stride=stride, h=h, w=w, ho=ho, wo=wo):
        plan = lane_plan(b=b, h=h, w=w, pad=pad, v=v)
        fmap = 2 * c * plan.lanes * in_bytes
        window = _shift_window_bytes(c, plan.halo, v, in_bytes)
    else:
        win_rows, bh_pad = strip_window(b=b, h=h, kh=kh, stride=stride,
                                        pad=pad, ho=ho, wo=wo, v=v)
        fmap = 2 * c * bh_pad * w * in_bytes
        window = _select_window_bytes(c, w, win_rows, v, in_bytes)
    return fmap + _conv_step_vmem_bytes(window, c, kh * kw, v, block_k,
                                        tile, in_bytes)


# ---------------------------------------------------------------------------
# Banded megakernel: H-tiled variant — only a band of the map is resident
# ---------------------------------------------------------------------------


def _banded_kernel(
    x_ref,        # the map in HBM: [C, lanes] lane-flattened (shift) or
                  # [C, bh_pad, W'] row/lane-padded (select)
    idx_ref,
    v_ref,
    o_ref,
    band_ref,     # [2, C, lanes] or [2, C, win_rows, W'] double-buffered
                  # band-window scratch
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
    halo: int | None,
    win_lanes: int,
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
        if halo is not None:
            src = x_ref.at[:, lane_window(gi * hb, v=v, lanes=win_lanes)]
        else:
            src = x_ref.at[:, pl.ds(origin(gi), win_rows), :]
        return make_async_copy(src, band_ref.at[slot], sem_ref.at[slot])

    # Double buffering: at the first grid step of band g, kick off the DMA
    # for band g+1, THEN block on band g's copy — band g+1's window streams
    # in while the (n_tiles * n_kc * hb-strip) GEMM steps of band g run.
    double_buffer_rotate(band_dma, g, n_bands,
                         gate=(s % hb == 0) & (t == 0) & (kc == 0))

    @pl.when((t == 0) & (kc == 0))
    def _pack():
        # same strip packing as the resident megakernel, from the band's
        # window instead of the resident map
        if halo is not None:
            win = band_ref[g % 2, :, lane_window(s - g * hb, v=v,
                                                 lanes=v + 2 * halo)]
            tile = _shift_taps(win, s, kw=kw, pad=pad, halo=halo, b=b, h=h,
                               w=w, v=v)
        else:
            tile = _select_taps(band_ref[g % 2], origin(g), s, kw=kw,
                                interpret=interpret, stride=stride, pad=pad,
                                b=b, h=h, w=w, ho=ho, wo=wo, v=v)
        _pack_patch(patch_ref, tile, taps=kh * kw, c=c)

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
    map stays in HBM and only a double-buffered band window is
    VMEM-resident.

    A band is ``hb`` strips.  For the shift packer the map is lane-flattened
    and a band's window is its positions plus a lane halo on each side
    (:func:`~repro.kernels.im2col_pack.kernel.lane_plan`); for the select
    packer the map is viewed as [C, B*H, W] rows and a band's window is an
    aligned window of input rows (strip rows + kh-1 halo, see
    :func:`~repro.kernels.im2col_pack.kernel.window_plan`).  Each window is
    DMA'd into one of two scratch slots with ``make_async_copy`` while the
    previous band's packing + Algorithm-1 MXU loop runs.  Output layout and
    semantics are identical to the resident megakernel — [O, n_strips*V],
    strip padding sliced off by the ops wrapper.
    """
    c, b, h, w = x.shape
    ho = out_size(h, kh, stride, pad)
    wo = out_size(w, kw, stride, pad)
    n_pos = b * ho * wo
    n_strips = -(-n_pos // v)
    n_tiles, k_kept, tile = values.shape
    assert idx.shape == (n_tiles, k_kept), (idx.shape, values.shape)

    hb = max(min(hb, n_strips), 1)
    if shift_path(stride=stride, h=h, w=w, ho=ho, wo=wo):
        plan = lane_plan(b=b, h=h, w=w, pad=pad, v=v, hb=hb)
        n_bands, halo, win_lanes = plan.n_bands, plan.halo, plan.win
        win_rows, bh_pad = 0, 0
        x_in = flatten_lanes(x, plan)
        band_window = (c, win_lanes)
    else:
        n_bands, band_rows = band_plan(b=b, h=h, kh=kh, stride=stride,
                                       pad=pad, ho=ho, wo=wo, v=v, hb=hb)
        win_rows, bh_pad = window_plan(band_rows, b * h)
        halo, win_lanes = None, 0
        x_in = pad_rows(x, bh_pad, lanes=128)
        band_window = (c, win_rows, ceil_to(w, 128))

    values, idx, block_k, n_kc = chunk_kept(values, idx, block_k)

    grid = (n_strips, n_tiles, n_kc)
    out = pl.pallas_call(
        functools.partial(
            _banded_kernel, kh=kh, kw=kw, stride=stride, pad=pad, v=v,
            hb=hb, win_rows=win_rows, bh_pad=bh_pad, halo=halo,
            win_lanes=win_lanes, n_bands=n_bands, c=c, b=b, h=h, w=w, ho=ho,
            wo=wo, n_kc=n_kc, out_dtype=x.dtype, interpret=interpret,
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
            pltpu.VMEM((2, *band_window), x.dtype),
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
    )(x_in, idx, values)
    return out


def banded_vmem_bytes(c: int, b: int, h: int, w: int, v: int, block_k: int,
                      tile: int, in_bytes: int = 2, *, kh: int = 1,
                      kw: int = 1, stride: int = 1, pad: int = 0,
                      hb: int = 2) -> int:
    """Analytic VMEM footprint of one banded-megakernel grid step: TWO band
    windows (double buffer) instead of the whole map — lane windows of the
    flattened map for the shift packer, aligned row windows over the
    lane-padded map for the select packer — plus the resident kernel's
    per-step working set."""
    ho = out_size(h, kh, stride, pad)
    wo = out_size(w, kw, stride, pad)
    if shift_path(stride=stride, h=h, w=w, ho=ho, wo=wo):
        plan = lane_plan(b=b, h=h, w=w, pad=pad, v=v, hb=hb)
        bands = 2 * c * plan.win * in_bytes
        window = _shift_window_bytes(c, plan.halo, v, in_bytes)
    else:
        _, band_rows = band_plan(b=b, h=h, kh=kh, stride=stride, pad=pad,
                                 ho=ho, wo=wo, v=v, hb=hb)
        win_rows = ceil_to(band_rows + ROW_ALIGN - 1, ROW_ALIGN)
        w = ceil_to(w, 128)  # the HBM map's columns are lane-padded
        bands = 2 * c * win_rows * w * in_bytes
        window = _select_window_bytes(c, w, win_rows, v, in_bytes)
    return bands + _conv_step_vmem_bytes(window, c, kh * kw, v, block_k,
                                         tile, in_bytes)
