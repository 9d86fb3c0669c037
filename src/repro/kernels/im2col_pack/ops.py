"""Jitted wrappers for fused im2col+packing, plus the un-fused baseline."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.im2col_pack.kernel import im2col_pack_pallas, shift_path
from repro.kernels.im2col_pack.ref import (
    im2col_cnhw,
    im2col_pack_ref,
    out_size,
    pack_strips,
)
from repro.kernels.pltpu_compat import should_interpret
from repro.obs import metrics as _om

_C_PACK_SHIFT = _om.counter("conv.pack_shift")
_C_PACK_SELECT = _om.counter("conv.pack_select")


def counts_pack_path(fn):
    """``fn``, the jitted wrapper of a conv kernel over a CNHW map, raising
    ``conv.pack_shift`` or ``conv.pack_select`` on every call, by the
    packer the kernel builds for that shape.  Jit traces identical calls
    once, yet each call is a kernel of its own in the traced program, so
    the count is taken outside the jit: one per conv the program holds."""

    @functools.wraps(fn)
    def call(x, *args, kh, kw, stride=1, pad=0, **kwargs):
        _, _, h, w = x.shape
        shift = shift_path(stride=stride, h=h, w=w,
                           ho=out_size(h, kh, stride, pad),
                           wo=out_size(w, kw, stride, pad))
        (_C_PACK_SHIFT if shift else _C_PACK_SELECT).inc()
        return fn(x, *args, kh=kh, kw=kw, stride=stride, pad=pad, **kwargs)

    return call


@counts_pack_path
@functools.partial(jax.jit, static_argnames=("kh", "kw", "stride", "pad", "v"))
def im2col_pack(x, *, kh, kw, stride=1, pad=0, v=128):
    """Fused single-pass im2col + packing (the paper's optimization)."""
    return im2col_pack_pallas(
        x, kh, kw, stride=stride, pad=pad, v=v, interpret=should_interpret()
    )


@functools.partial(jax.jit, static_argnames=("kh", "kw", "stride", "pad", "v"))
def im2col_then_pack(x, *, kh, kw, stride=1, pad=0, v=128):
    """Two-pass baseline: materialize the patch matrix, then pack.

    ``optimization_barrier`` pins the intermediate so XLA cannot silently fuse
    the two passes — this is the memory-overhead configuration the paper
    measures against (Fig. 6/8).
    """
    mat = im2col_cnhw(x, kh, kw, stride, pad)
    mat = jax.lax.optimization_barrier(mat)
    return pack_strips(mat, v)


@functools.partial(jax.jit, static_argnames=("kh", "kw", "stride", "pad"))
def im2col_only(x, *, kh, kw, stride=1, pad=0):
    """im2col without packing (paper Fig. 8a's 'no packing' configuration)."""
    return im2col_cnhw(x, kh, kw, stride, pad)


def bytes_moved_fused(c, b, h, w, kh, kw, ho, wo, v, itemsize) -> int:
    """Analytic data movement of the fused pass: each strip element is read
    once from the map and written once to the strip."""
    return 2 * kh * kw * c * (-(-b * ho * wo // v)) * v * itemsize


def bytes_moved_unfused(c, b, h, w, kh, kw, ho, wo, v, itemsize) -> int:
    """Two passes: im2col (read map, write matrix) + pack (read matrix,
    write strips) — double traffic on the patch matrix."""
    mat = kh * kw * c * b * ho * wo
    strips = kh * kw * c * (-(-b * ho * wo // v)) * v
    return (mat + mat + mat + strips) * itemsize
