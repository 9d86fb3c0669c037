"""GEMM-based convolution in the paper's layouts — the conv plan ladder:

  fused megakernel : im2col + pack + sparse GEMM in ONE Pallas kernel; the
                     packed strips are produced and consumed in VMEM and
                     never exist in HBM (``conv2d_fused``); needs the whole
                     CNHW map VMEM-resident
  banded megakernel: the H-tiled variant (``conv2d_fused_banded``) — only a
                     double-buffered band window of the map is resident, DMA'd
                     per band while the previous band's GEMM runs; covers
                     stem-scale maps and batch > 1
  two-kernel       : fused im2col+pack kernel, then the strip-major sparse
                     GEMM consuming [n_strips, K, V] directly — no transpose
                     relayout between the kernels; ``conv2d_two_kernel_
                     pipelined`` overlaps the GEMM's strip loads with its
                     compute via the same double-buffered DMA scheme
  XLA reference    : pack kernel + gather-einsum GEMM (distribution-friendly)

``conv2d_colwise_sparse`` keeps the historical entry point; with
``use_pallas=None`` (default) it routes through ``repro.dispatch`` and
executes whichever registered conv candidate (including the megakernels and
their geometry variants) the profile DB / heuristic picks.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.formats import ColwiseMeta, meta_for, pack_colwise
from repro.core.pruning import SparsityConfig, colwise_nm_mask
from repro.kernels.colwise_nm.ops import (
    colwise_nm_matmul_strips,
    colwise_nm_matmul_strips_pipelined,
    sparse_grad_dvalues,
    sparse_grad_dxg,
)
from repro.kernels.colwise_nm.ref import colwise_nm_matmul_ref
from repro.kernels.im2col_pack.kernel import (
    lane_plan,
    shift_path,
    tap_coords,
)
from repro.kernels.conv_gemm.kernel import (
    band_plan,
    conv2d_fused_banded_pallas,
    conv2d_fused_pallas,
)
from repro.kernels.im2col_pack.ops import counts_pack_path, im2col_pack
from repro.kernels.im2col_pack.ref import out_size
from repro.kernels.pltpu_compat import should_interpret


def compress_conv_weights(w_ohwi: jax.Array, cfg: SparsityConfig):
    """Prune+compress an OHWI conv kernel column-wise over (kh, kw, c).

    The GEMM weight matrix is [O, Kh*Kw*C]; tiles of T output channels share
    kept (kh, kw, c) positions. Returns (values, idx, meta) for the sparse
    GEMM where the *reduction* dim is Kh*Kw*C.
    """
    o, kh, kw, c = w_ohwi.shape
    wmat = w_ohwi.reshape(o, kh * kw * c).T  # [K, O] = [d_in, d_out]
    meta = meta_for(kh * kw * c, o, cfg)
    mask = colwise_nm_mask(wmat, cfg.sparsity, m=cfg.m, tile=meta.tile)
    values, idx = pack_colwise(wmat, mask, meta)
    return values, idx, meta


@counts_pack_path
@functools.partial(
    jax.jit, static_argnames=("kh", "kw", "stride", "pad", "v", "block_k"))
def conv2d_fused(
    x_cnhw: jax.Array,
    values: jax.Array,
    idx: jax.Array,
    *,
    kh: int,
    kw: int,
    stride: int = 1,
    pad: int = 0,
    v: int = 128,
    block_k: int = 128,
) -> jax.Array:
    """Single-megakernel sparse conv: im2col + pack + sparse GEMM fused.

    The packed strips live only in VMEM (zero intermediate HBM round-trips);
    the output is produced directly in [O, P] layout.  Returns CNHW output
    [O, B, Ho, Wo].
    """
    c, b, h, w = x_cnhw.shape
    ho = out_size(h, kh, stride, pad)
    wo = out_size(w, kw, stride, pad)
    y = conv2d_fused_pallas(
        x_cnhw, values, idx, kh=kh, kw=kw, stride=stride, pad=pad, v=v,
        block_k=block_k, interpret=should_interpret(),
    )  # [O, n_strips*v]
    o = y.shape[0]
    return y[:, : b * ho * wo].reshape(o, b, ho, wo)


@counts_pack_path
@functools.partial(
    jax.jit,
    static_argnames=("kh", "kw", "stride", "pad", "v", "block_k", "hb"))
def conv2d_fused_banded(
    x_cnhw: jax.Array,
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
) -> jax.Array:
    """Banded megakernel conv: the H-tiled fused plan.  Only a double-buffered
    band window (``hb`` strips' input lanes or rows + halo) is VMEM-resident;
    band s+1 is DMA'd while band s's packing+GEMM runs.  Same numerics/layout
    contract as :func:`conv2d_fused`.  Returns CNHW output [O, B, Ho, Wo]."""
    c, b, h, w = x_cnhw.shape
    ho = out_size(h, kh, stride, pad)
    wo = out_size(w, kw, stride, pad)
    y = conv2d_fused_banded_pallas(
        x_cnhw, values, idx, kh=kh, kw=kw, stride=stride, pad=pad, v=v,
        block_k=block_k, hb=hb, interpret=should_interpret(),
    )  # [O, n_strips*v]
    o = y.shape[0]
    return y[:, : b * ho * wo].reshape(o, b, ho, wo)


def conv2d_two_kernel(
    x_cnhw: jax.Array,
    values: jax.Array,
    idx: jax.Array,
    *,
    kh: int,
    kw: int,
    stride: int = 1,
    pad: int = 0,
    v: int = 128,
    block_k: int = 128,
) -> jax.Array:
    """Two-kernel Pallas plan: pack kernel, then strip-major sparse GEMM.

    The GEMM consumes the [n_strips, K, V] strips directly (strip dim as the
    Pallas batch grid dim) — the packed matrix is written and read once, with
    no transpose relayout in between.  Returns CNHW output [O, B, Ho, Wo].
    """
    c, b, h, w = x_cnhw.shape
    ho = out_size(h, kh, stride, pad)
    wo = out_size(w, kw, stride, pad)
    strips = im2col_pack(x_cnhw, kh=kh, kw=kw, stride=stride, pad=pad, v=v)
    y = colwise_nm_matmul_strips(strips, values, idx, block_k=block_k)
    o = y.shape[0]
    return y[:, : b * ho * wo].reshape(o, b, ho, wo)


def conv2d_two_kernel_pipelined(
    x_cnhw: jax.Array,
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
) -> jax.Array:
    """Two-kernel plan with an overlapped strip pipeline: the pack kernel
    writes [n_strips, K, V] strips to HBM, then the *pipelined* strip-major
    GEMM consumes them — chunks of ``hb`` strips are async-copied into a
    double-buffered VMEM scratch so strip s+1 streams in while strip s's
    GEMM runs, instead of the back-to-back block fetch + compute of the
    plain plan.  Returns CNHW output [O, B, Ho, Wo]."""
    c, b, h, w = x_cnhw.shape
    ho = out_size(h, kh, stride, pad)
    wo = out_size(w, kw, stride, pad)
    strips = im2col_pack(x_cnhw, kh=kh, kw=kw, stride=stride, pad=pad, v=v)
    y = colwise_nm_matmul_strips_pipelined(strips, values, idx,
                                           block_k=block_k, hb=hb)
    o = y.shape[0]
    return y[:, : b * ho * wo].reshape(o, b, ho, wo)


def banded_bytes_moved(c: int, b: int, h: int, w: int, kh: int, stride: int,
                       pad: int, ho: int, wo: int, v: int, hb: int,
                       o: int, itemsize: int) -> int:
    """Analytic HBM traffic of the banded megakernel at band depth ``hb``:
    every band DMAs its window once — for the shift packer ``hb*v`` lanes
    plus a lane halo on each side, for the select packer ``band_rows``
    input rows — (halo is re-read by adjacent bands — that is the price of
    banding), and the [O, P] output is written once.  Shallower bands
    re-read more halo; deeper bands amortize it at the cost of
    double-buffer VMEM."""
    n_strips = -(-b * ho * wo // v)
    if shift_path(stride=stride, h=h, w=w, ho=ho, wo=wo):
        plan = lane_plan(b=b, h=h, w=w, pad=pad, v=v, hb=hb)
        band_reads = plan.n_bands * c * plan.win
    else:
        n_bands, band_rows = band_plan(b=b, h=h, kh=kh, stride=stride,
                                       pad=pad, ho=ho, wo=wo, v=v, hb=hb)
        band_reads = n_bands * c * band_rows * w
    out_write = o * n_strips * v
    return (band_reads + out_write) * itemsize


def conv2d_xla_ref(
    x_cnhw: jax.Array,
    values: jax.Array,
    idx: jax.Array,
    *,
    kh: int,
    kw: int,
    stride: int = 1,
    pad: int = 0,
    v: int = 128,
) -> jax.Array:
    """XLA reference plan: pack kernel + gather-einsum GEMM (per-position
    rows, the layout the distribution-friendly linear path uses)."""
    c, b, h, w = x_cnhw.shape
    ho = out_size(h, kh, stride, pad)
    wo = out_size(w, kw, stride, pad)
    n_pos = b * ho * wo
    o = values.shape[0] * values.shape[2]
    strips = im2col_pack(x_cnhw, kh=kh, kw=kw, stride=stride, pad=pad, v=v)
    xt = strips.transpose(0, 2, 1).reshape(-1, kh * kw * c)  # [S*V, K]
    y = colwise_nm_matmul_ref(xt, values, idx)[:n_pos]
    return y.T.reshape(o, b, ho, wo)


# ---------------------------------------------------------------------------
# Differentiable dispatched sparse conv — the conv twin of colwise_nm's VJP
# ---------------------------------------------------------------------------


def _conv_plan_forward(x_cnhw, values, idx, kh, kw, stride, pad, v, impl):
    """Dispatch-resolved forward: exactly what ``conv_apply`` ran before the
    VJP existed — the profiled plan (fused / banded / two-kernel pipelined /
    XLA, any rung) for this conv shape, or the ``impl``-forced candidate."""
    from repro import dispatch as _dispatch

    c, b, h, w = x_cnhw.shape
    n_tiles, k_kept, tile = (int(s) for s in values.shape)
    key = _dispatch.conv_key(
        c, h, w, n_tiles * tile, kh, kw, stride, pad, k_kept, tile,
        v=v, dtype=x_cnhw.dtype, batch=b, phase=_dispatch.current_phase())
    spec = _dispatch.best_impl(key, param_keys=("values", "idx"), force=impl)
    # execution guard: a failing rung is quarantined and the plan re-resolves
    # down the ladder (ultimately the XLA reference) instead of crashing
    return _dispatch.run_guarded(
        key, spec,
        lambda s: s.apply({"values": values, "idx": idx}, x_cnhw,
                          kh=kh, kw=kw, stride=stride, pad=pad, v=v),
        param_keys=("values", "idx"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _conv_sparse(x_cnhw, values, idx, kh, kw, stride, pad, v, impl):
    return _conv_plan_forward(x_cnhw, values, idx, kh, kw, stride, pad, v,
                              impl)


def _conv_fwd(x_cnhw, values, idx, kh, kw, stride, pad, v, impl):
    from repro import dispatch as _dispatch

    # grad tracing re-enters the call site through this rule; dispatch must
    # resolve from the DB / heuristic only — never wall-clock candidates from
    # inside a gradient trace (see dispatch.no_profile_scope)
    with _dispatch.no_profile_scope():
        y = _conv_plan_forward(x_cnhw, values, idx, kh, kw, stride, pad, v,
                               impl)
    return y, (x_cnhw, values, idx)


def _conv_bwd(kh, kw, stride, pad, v, impl, res, dy):
    """Backward of the GEMM-view conv ``y[t*T+f, p] = sum_j values[t, j, f] *
    X_im2col[idx[t, j], p]``, computed without ever materializing the im2col
    matrix: the same :func:`tap_coords` index arithmetic the forward kernels
    gather with is reused to

      * gather the kept im2col rows from the map (``xg``) for ``dvalues``
        (gathered-activation x dy einsum, f32 accumulation), and
      * scatter-add ``dx`` back through the kept (kh, kw, c) taps — the
        transposed-conv scatter, accumulated in f32 (output positions whose
        receptive fields overlap, and tiles sharing a kept row, collide).

    Runs as XLA gather/scatter: the forward is the latency-critical path the
    paper optimizes; this backward appears only in sparse finetuning.
    """
    x, values, idx = res
    c, b, h, w = x.shape
    o, _, ho, wo = dy.shape
    n_pos = b * ho * wo
    n_tiles, k_kept, tile = values.shape
    k_of = idx // c   # [n_tiles, k_kept] kernel-tap index ikh*kw + ikw
    c_of = idx % c    # [n_tiles, k_kept] input channel
    # coordinates with the flattened output position leading: [P, t, k]
    p = jnp.arange(n_pos, dtype=jnp.int32)[:, None, None]
    valid, bc, ihc, iwc = tap_coords(
        p, ikh=(k_of // kw)[None], ikw=(k_of % kw)[None], stride=stride,
        pad=pad, b=b, h=h, w=w, ho=ho, wo=wo)
    fidx = ((c_of[None] * b + bc) * h + ihc) * w + iwc  # [P, t, k] into CNHW
    dy_t = dy.reshape(o, n_pos).T.reshape(n_pos, n_tiles, tile)  # [P, t, f]

    xg = jnp.where(valid, jnp.take(x.reshape(-1), fidx), 0)  # [P, t, k]
    dvalues = sparse_grad_dvalues(xg, dy_t, values.dtype)

    dxg = sparse_grad_dxg(dy_t, values)  # [P, t, k] f32
    dx = (jnp.zeros((c * b * h * w,), jnp.float32)
          .at[fidx.reshape(-1)]
          .add(jnp.where(valid, dxg, 0).reshape(-1))
          .reshape(c, b, h, w).astype(x.dtype))
    return dx, dvalues, None


_conv_sparse.defvjp(_conv_fwd, _conv_bwd)


def conv2d_sparse(
    x_cnhw: jax.Array,
    values: jax.Array,
    idx: jax.Array,
    *,
    kh: int,
    kw: int,
    stride: int = 1,
    pad: int = 0,
    v: int = 128,
    impl: Optional[str] = None,
) -> jax.Array:
    """Differentiable dispatched sparse conv (the conv twin of
    ``colwise_nm``'s custom VJP).

    Forward is the dispatch-resolved execution plan — whichever rung of the
    conv plan ladder the profile DB / heuristic picks for this shape (or the
    ``impl``-forced candidate).  Backward computes ``dx`` via the
    transposed-conv scatter over the kept (kh, kw, c) taps and ``dvalues``
    via the im2col-gather x dy einsum, both f32-accumulated; ``idx`` gets no
    cotangent.  Returns CNHW output [O, B, Ho, Wo].
    """
    return _conv_sparse(x_cnhw, values, idx, kh, kw, stride, pad, v, impl)


def conv2d_colwise_sparse(
    x_cnhw: jax.Array,
    values: jax.Array,
    idx: jax.Array,
    kh: int,
    kw: int,
    stride: int = 1,
    pad: int = 0,
    v: int = 128,
    use_pallas: Optional[bool] = None,
) -> jax.Array:
    """Sparse convolution with dispatched execution plan.

    ``use_pallas=None`` (the default) consults ``repro.dispatch``: the
    registered conv candidates (fused megakernel geometry variants, two-kernel
    strip-major, XLA reference) are resolved per shape from the profile DB /
    platform heuristic, via the differentiable :func:`conv2d_sparse` wrapper.
    ``use_pallas=True`` forces the two-kernel Pallas plan, ``False`` the XLA
    reference plan.  Returns CNHW output [O, B, Ho, Wo].
    """
    if use_pallas is None:
        return conv2d_sparse(x_cnhw, values, idx, kh=kh, kw=kw, stride=stride,
                             pad=pad, v=v)
    if use_pallas:
        return conv2d_two_kernel(x_cnhw, values, idx, kh=kh, kw=kw,
                                 stride=stride, pad=pad, v=v)
    return conv2d_xla_ref(x_cnhw, values, idx, kh=kh, kw=kw,
                          stride=stride, pad=pad, v=v)
