"""Pallas kernel: fused im2col + data packing (paper Algorithm 2, TPU analog).

One pass moves each input element directly from the CNHW feature map into its
packed-strip position; the intermediate patch matrix never exists in HBM.

RVV -> TPU translation:
  - vector length V / LMUL     -> strip width V (lane multiples: 128..1024)
  - dynamic VL trim at the     -> iota-compare masks on the final/ragged strip
    feature-map boundary          (no zero-copy padding regions are touched)
  - scalar loop over (k, c)    -> grid dimensions (strip, k, c-block); each
    with vector strip copies      grid step emits a [c_block, V] strip tile

Grid: (n_strips, Kh*Kw, C_in / c_block).  The source coordinates of a strip
row depend on (kh, kw) but NOT on the channel, so a whole block of channels
shares one gather: step (s, k, cc) emits the strip tile
[s, k*C + cc*c_block : k*C + (cc+1)*c_block, :].

The gather itself is built from what Mosaic lowers (no dynamic vector
gather): the map is viewed as [C, B*H, W] rows, strip s reads a 16-aligned
window of those rows (:func:`window_plan`), and :func:`tap_tile` selects each
output position's (row, column) with two one-hot contractions — an MXU
column select over W, then a masked row reduction over the window.  Both are
exact: every output sums exactly one input element.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.pltpu_compat import COMPILER_PARAMS as _COMPILER_PARAMS
from repro.kernels.pltpu_compat import ceil_to, dot_f32, kernel_tag

from repro.kernels.im2col_pack.ref import out_size

#: row alignment of a VMEM row window: the sublane tile of packed bf16 rows
ROW_ALIGN = 16


def tap_coords(p, *, ikh, ikw, stride, pad, b, h, w, ho, wo,
               band_origin=None, band_rows=None):
    """Source coordinates of flat output positions ``p`` at kernel tap
    (ikh, ikw) — THE im2col index arithmetic, shared by this pack kernel, the
    conv megakernels (``conv_gemm/kernel.py``) and the conv backward's
    transposed-conv scatter (``conv_gemm/ops.py``) so the stride/pad/boundary
    semantics cannot drift between forward and gradient.

    ``p`` is any int32 array of flattened ``(batch, oh, ow)`` output
    positions; ``ikh``/``ikw`` broadcast against it.  Returns
    ``(valid, bc, ihc, iwc)``: the out-of-map / past-the-end mask and
    clamped (always in-bounds) batch/row/col gather coordinates; ``bc``
    keeps ``p``'s shape (positions do not depend on the tap).

    Band mode (``band_origin``/``band_rows`` set): for kernels that keep only
    a row window of the feature map resident, the returned row coordinate is
    *window-local* in the flattened ``(batch*h)`` row space —
    ``bb*h + ih - band_origin``, clamped to ``[0, band_rows)`` — and the
    batch coordinate is dropped (the flattened row subsumes it): returns
    ``(valid, rowc, iwc)``.  ``band_origin`` may be a traced scalar.
    """
    n_pos = b * ho * wo
    bb = p // (ho * wo)
    rem = p % (ho * wo)
    oh = rem // wo
    ow = rem % wo
    ih = oh * stride - pad + ikh
    iw = ow * stride - pad + ikw
    valid = (p < n_pos) & (ih >= 0) & (ih < h) & (iw >= 0) & (iw < w)
    # clamp so the gather itself is always in-bounds; masked after
    if band_origin is not None:
        g = bb * h + ih - band_origin  # window-local flattened (batch*h) row
        return (valid, jnp.clip(g, 0, band_rows - 1), jnp.clip(iw, 0, w - 1))
    return (valid, jnp.clip(bb, 0, b - 1), jnp.clip(ih, 0, h - 1),
            jnp.clip(iw, 0, w - 1))


def strip_tap_coords(s, *, v, ikh, ikw, stride, pad, b, h, w, ho, wo,
                     band_origin=None, band_rows=None):
    """Source coordinates of strip ``s``'s V output positions at kernel tap
    (ikh, ikw): :func:`tap_coords` over the [1, v] lane vector
    ``p = s*v + iota(v)`` — the strip view the Pallas kernels consume.  See
    :func:`tap_coords` for the returned tuple and band mode.
    """
    p = s * v + jax.lax.broadcasted_iota(jnp.int32, (1, v), 1)
    return tap_coords(p, ikh=ikh, ikw=ikw, stride=stride, pad=pad, b=b, h=h,
                      w=w, ho=ho, wo=wo, band_origin=band_origin,
                      band_rows=band_rows)


def first_row(p, *, h, ho, wo, stride, pad):
    """Top flattened (batch*h) input row that output position ``p`` reads at
    tap row 0 (may be negative: the top padding).  Works on ints and traced
    scalars alike."""
    bb = p // (ho * wo)
    oh = (p % (ho * wo)) // wo
    return bb * h + oh * stride - pad


def window_plan(rows: int, bh: int):
    """``(win_rows, bh_pad)`` of a VMEM row window that holds ``rows``
    consecutive input rows starting anywhere: the window origin is aligned
    down to ``ROW_ALIGN`` (Mosaic slices packed rows only at tile
    boundaries), so it carries ``ROW_ALIGN - 1`` rows of slack.  ``bh_pad``
    is the flattened row count the map is zero-padded to, so that every
    aligned window stays in bounds."""
    bh_pad = ceil_to(bh, ROW_ALIGN)
    return min(ceil_to(rows + ROW_ALIGN - 1, ROW_ALIGN), bh_pad), bh_pad


def window_origin(top, *, win_rows: int, bh_pad: int):
    """Aligned origin of the window whose first needed row is ``top``:
    ``top`` rounded down to ``ROW_ALIGN``, clamped so the window ends inside
    the padded map (clamping only moves it up, widening coverage)."""
    org = jnp.maximum(top, 0) // ROW_ALIGN * ROW_ALIGN
    return pl.multiple_of(jnp.minimum(org, bh_pad - win_rows), ROW_ALIGN)


def band_plan(*, b: int, h: int, kh: int, stride: int, pad: int, ho: int,
              wo: int, v: int, hb: int):
    """Static band geometry of the row-window kernels.

    A *band* groups ``hb`` consecutive strips (``hb*v`` output positions).
    In the flattened ``(batch*h)`` input-row space the rows a band's strips
    read are contiguous (consecutive output positions advance monotonically
    through ``bb*h + oh*stride``, including across batch boundaries), so each
    band needs one contiguous row window of roughly
    ``stride * ceil(hb*v / wo) + kh - 1`` rows (the strip rows plus the
    kh-1 halo).  Returns ``(n_bands, band_rows)`` with ``band_rows`` the
    exact maximum over bands (ragged final band included), clamped to the
    full ``b*h``; :func:`window_plan` turns it into an aligned VMEM window.
    """
    n_pos = b * ho * wo
    n_strips = -(-n_pos // v)
    hb = max(min(hb, n_strips), 1)
    n_bands = -(-n_strips // hb)
    bh = b * h
    geo = dict(h=h, ho=ho, wo=wo, stride=stride, pad=pad)

    rows = 1
    for g in range(n_bands):
        p0 = g * hb * v
        p1 = min((g + 1) * hb * v, n_pos) - 1
        r0 = max(first_row(p0, **geo), 0)
        r1 = min(first_row(p1, **geo) + kh - 1, bh - 1)
        rows = max(rows, r1 - r0 + 1)
    return n_bands, min(rows, bh)


def strip_window(*, b, h, kh, stride, pad, ho, wo, v):
    """``(win_rows, bh_pad)`` of the aligned window one strip reads."""
    _, rows = band_plan(b=b, h=h, kh=kh, stride=stride, pad=pad, ho=ho,
                        wo=wo, v=v, hb=1)
    return window_plan(rows, b * h)


def pad_rows(x: jax.Array, bh_pad: int, lanes: int = 1) -> jax.Array:
    """CNHW map -> [C, bh_pad, W']: flattened (batch*h) rows, zero-padded,
    with W zero-padded to a multiple of ``lanes``.  A map that stays in HBM
    for manual DMA needs ``lanes=128``: Mosaic refuses a DMA window narrower
    than the lane tiling of its HBM layout."""
    c, b, h, w = x.shape
    x = x.reshape(c, b * h, w)
    w_pad = ceil_to(w, lanes)
    if (bh_pad, w_pad) != (b * h, w):
        x = jnp.pad(x, ((0, 0), (0, bh_pad - b * h), (0, w_pad - w)))
    return x


def tap_tile(win, org, s, *, ikh, ikw, stride, pad, b, h, w, ho, wo, v,
             interpret: bool):
    """[C, v] f32 im2col rows of tap (ikh, ikw) for strip ``s``, read from
    ``win`` [C, R, W']: flattened input rows ``org .. org+R`` of the map
    (W' >= W when the map's columns are lane-padded).

    Column select: one MXU contraction of the [C*R, W] window with a [W, v]
    one-hot of each position's source column.  Row select: multiply by the
    [R, v] one-hot of each position's window-local source row (zero where
    the tap falls outside the map) and reduce over R.
    """
    c, r, wp = win.shape  # wp >= w: lane padding columns are never read
    valid, rowc, iwc = strip_tap_coords(
        s, v=v, ikh=ikh, ikw=ikw, stride=stride, pad=pad, b=b, h=h, w=w,
        ho=ho, wo=wo, band_origin=org, band_rows=r)
    wsel = jax.lax.broadcasted_iota(jnp.int32, (wp, v), 0) == iwc
    cols = dot_f32(win.reshape(c * r, wp), wsel.astype(win.dtype),
                   interpret).reshape(c, r, v)
    rsel = (jax.lax.broadcasted_iota(jnp.int32, (r, v), 0) == rowc) & valid
    return jnp.sum(cols * rsel.astype(jnp.float32)[None], axis=1)


def _kernel(
    x_ref,
    o_ref,
    *,
    kh: int,
    kw: int,
    stride: int,
    pad: int,
    v: int,
    b: int,
    h: int,
    w: int,
    ho: int,
    wo: int,
    win_rows: int,
    bh_pad: int,
    interpret: bool,
):
    s = pl.program_id(0)
    k = pl.program_id(1)
    top = first_row(s * v, h=h, ho=ho, wo=wo, stride=stride, pad=pad)
    org = window_origin(top, win_rows=win_rows, bh_pad=bh_pad)
    # every channel of the block shares the tap's source coordinates: one
    # gather emits the whole [c_block, v] strip tile
    tile = tap_tile(
        x_ref[:, pl.ds(org, win_rows), :], org, s, ikh=k // kw, ikw=k % kw,
        stride=stride, pad=pad, b=b, h=h, w=w, ho=ho, wo=wo, v=v,
        interpret=interpret)
    o_ref[0] = tile.astype(o_ref.dtype)


def _choose_c_block(c: int, row_bytes: int, cap: int = 32,
                    budget: int = 4 * 2 ** 20) -> int:
    """Largest divisor of C no bigger than ``cap`` whose [c_block, B*H, W]
    input block stays within ``budget`` bytes (grid-coarsening factor)."""
    for cb in range(min(c, cap), 0, -1):
        if c % cb == 0 and (cb * row_bytes <= budget or cb == 1):
            return cb
    return 1


def im2col_pack_pallas(
    x: jax.Array,
    kh: int,
    kw: int,
    stride: int = 1,
    pad: int = 0,
    v: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Fused im2col+pack of a CNHW map -> [n_strips, KhKwC, V] strips."""
    c, b, h, w = x.shape
    ho = out_size(h, kh, stride, pad)
    wo = out_size(w, kw, stride, pad)
    n_pos = b * ho * wo
    n_strips = -(-n_pos // v)
    win_rows, bh_pad = strip_window(b=b, h=h, kh=kh, stride=stride, pad=pad,
                                    ho=ho, wo=wo, v=v)
    c_block = _choose_c_block(c, bh_pad * w * x.dtype.itemsize)
    n_cb = c // c_block

    grid = (n_strips, kh * kw, n_cb)
    out = pl.pallas_call(
        functools.partial(
            _kernel, kh=kh, kw=kw, stride=stride, pad=pad, v=v, b=b, h=h,
            w=w, ho=ho, wo=wo, win_rows=win_rows, bh_pad=bh_pad,
            interpret=interpret,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((c_block, bh_pad, w), lambda s, k, cc: (cc, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, c_block, v), lambda s, k, cc, _n=n_cb: (s, k * _n + cc, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((n_strips, kh * kw * c, v), x.dtype),
        compiler_params=_COMPILER_PARAMS(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        metadata=kernel_tag("im2col_pack"),
        interpret=interpret,
    )(pad_rows(x, bh_pad))
    return out
