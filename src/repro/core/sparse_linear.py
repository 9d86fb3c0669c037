"""SparseLinear: one linear-layer abstraction with pluggable execution format.

Formats
-------
  dense            : y = x @ w
  masked           : y = x @ (w * mask)            — training / mask refresh
  compressed_xla   : tiled gather + dense einsum   — pjit-friendly, shards the
                     tile axis over the tensor-parallel mesh axis
  compressed_pallas: the Algorithm-1 micro-kernel  — gather fused in VMEM

Every weight in the model zoo is created through ``linear_init`` and applied
through ``linear_apply`` so the paper's technique is a config switch, not a
code path per model.

Params are returned as ``Boxed(value, logical_spec)`` leaves; ``unbox_tree``
splits them into a value tree and a logical-sharding tree (single source of
truth for distribution).
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import formats
from repro.core.pruning import SparsityConfig, colwise_nm_mask, rowwise_nm_mask


# ---------------------------------------------------------------------------
# Boxed params: value + logical sharding spec in one tree
# ---------------------------------------------------------------------------


class Boxed:
    """A parameter leaf annotated with logical axis names (not a pytree)."""

    __slots__ = ("value", "spec")

    def __init__(self, value, spec: Tuple[Optional[str], ...]):
        self.value = value
        self.spec = spec

    def __repr__(self):
        shape = getattr(self.value, "shape", None)
        return f"Boxed(shape={shape}, spec={self.spec})"


def _is_boxed(x) -> bool:
    return isinstance(x, Boxed)


def unbox_tree(tree):
    """Split a Boxed tree into (values, logical_specs)."""
    values = jax.tree_util.tree_map(lambda b: b.value, tree, is_leaf=_is_boxed)
    specs = jax.tree_util.tree_map(lambda b: b.spec, tree, is_leaf=_is_boxed)
    return values, specs


def box_map(fn, tree):
    return jax.tree_util.tree_map(fn, tree, is_leaf=_is_boxed)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _dense_init(key, d_in, d_out, dtype, scale):
    if scale is None:
        scale = 1.0 / np.sqrt(d_in)
    return jax.random.normal(key, (d_in, d_out), dtype) * jnp.asarray(scale, dtype)


def linear_init(
    key: jax.Array,
    d_in: int,
    d_out: int,
    cfg: SparsityConfig,
    *,
    dtype=jnp.float32,
    use_bias: bool = False,
    in_ax: Optional[str] = "embed",
    out_ax: Optional[str] = "ffn",
    scale: Optional[float] = None,
    mode: str = "concat",
):
    """Create a (possibly pruned) linear layer's params as a Boxed dict.

    mode="reduce" marks layers whose reduction dim is TP-sharded; when the
    SparsityConfig enables shard_local_reduce they get the group-local
    compressed format (values_r/idx_r).
    """
    prune = cfg.applies_to(d_in, d_out)
    params: dict[str, Any] = {}
    if (prune and mode == "reduce" and cfg.shard_local_reduce
            and cfg.format in ("compressed_xla", "compressed_pallas")):
        from repro.core.pruning import choose_group, kept_per_group

        g = choose_group(d_in, cfg.reduce_groups or 4)
        m = d_in // g
        n_per = kept_per_group(m, cfg.sparsity)
        values, idx = formats.init_compressed_reduce(
            key, d_in, d_out, g, n_per, dtype, scale)
        params["values_r"] = Boxed(values, ("reduce_group", None, out_ax))
        params["idx_r"] = Boxed(idx, ("reduce_group", None))
    elif prune and cfg.format in ("compressed_xla", "compressed_pallas"):
        values, idx = formats.init_compressed(key, d_in, d_out, cfg, dtype, scale)
        params["values"] = Boxed(values, ("tile", "kept", None))
        params["idx"] = Boxed(idx, ("tile", None))
    elif prune and cfg.format == "masked":
        w = _dense_init(key, d_in, d_out, dtype, scale)
        if cfg.scheme == "rowwise":
            mask = rowwise_nm_mask(w, cfg.sparsity, m=cfg.m)
        else:
            mask = colwise_nm_mask(w, cfg.sparsity, m=cfg.m, tile=cfg.tile)
        params["w"] = Boxed(w * mask.astype(dtype), (in_ax, out_ax))
        params["mask"] = Boxed(mask, (in_ax, out_ax))
    else:
        params["w"] = Boxed(_dense_init(key, d_in, d_out, dtype, scale), (in_ax, out_ax))
    if use_bias:
        params["b"] = Boxed(jnp.zeros((d_out,), dtype), (out_ax,))
    return params


# ---------------------------------------------------------------------------
# Apply
# ---------------------------------------------------------------------------


def forward_compressed_xla(x: jax.Array, values: jax.Array, idx: jax.Array) -> jax.Array:
    """Tiled gather + dense einsum (the distribution-friendly path).

    x: [..., d_in]; values: [n_tiles, k, T]; idx: [n_tiles, k].
    Per tile t:  y[..., tT:(t+1)T] = x[..., idx[t]] @ values[t]
    With the tile axis sharded over the TP mesh axis every chip gathers its
    own [..., k] operand once and runs a dense local matmul — the paper's
    data-reuse argument lifted to chip granularity.
    """
    n_tiles, k, tile = values.shape
    xg = jnp.take(x, idx, axis=-1)  # [..., n_tiles, k]
    y = jnp.einsum("...tk,tkf->...tf", xg, values)
    return y.reshape(*x.shape[:-1], n_tiles * tile)


def forward_compressed_reduce(x: jax.Array, values: jax.Array, idx: jax.Array) -> jax.Array:
    """Shard-local REDUCE-mode path for layers whose *reduction* dim is
    tensor-parallel-sharded (down-proj, o-proj).

    values: [G, n, d_out]; idx: [G, n] group-local.  x is reshaped to
    [..., G, M] so the gather is a *batched* take_along_axis over the last
    dim — the group (shard) dim stays a batch dim, so GSPMD keeps the gather
    local to each shard and the only collective is the partial-sum
    all-reduce of the small [tokens, d_out] output (exactly the dense
    Megatron down-proj pattern; the dry-run showed the concat-mode gather
    instead all-reduced the full [tokens, k_kept] hidden).
    """
    g, n, d_out = values.shape
    lead = x.shape[:-1]
    m = x.shape[-1] // g
    xg = x.reshape(*lead, g, m)
    from repro.sharding import shd

    xg = shd(xg, *(("act_batch",) + (None,) * (len(lead) - 1) + ("act_ffn", None)))
    idx_b = jnp.broadcast_to(idx, (*lead, g, n))
    sel = jnp.take_along_axis(xg, idx_b, axis=-1)  # [..., G, n] shard-local
    return jnp.einsum("...gn,gnf->...f", sel, values)


def forward_masked(x: jax.Array, w: jax.Array, mask: jax.Array) -> jax.Array:
    return x @ (w * mask.astype(w.dtype))


def _per_shard(fn, x: jax.Array, values: jax.Array, idx: jax.Array):
    """``fn(x, values, idx)`` of a compressed linear, run once per device
    under ``jax.shard_map`` wherever an installed sharding context would
    have GSPMD partition it (GSPMD cannot partition a Mosaic kernel; per
    shard every dispatch candidate sees a single-device operator).

    The tile axis splits over the mesh axes of the ``tile`` rule, so each
    shard owns whole tiles and a contiguous block of output columns; the
    leading batch dim splits over ``act_batch``; everything else, the kept
    dim included, is replicated into the body."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding.api import get_ctx, gspmd_devices, resolve_spec

    if gspmd_devices() <= 1:
        return fn(x, values, idx)
    ctx = get_ctx()
    # one spec, so a mesh axis serves the tile axis or the batch, not both
    t, b = resolve_spec((values.shape[0], x.shape[0]), ("tile", "act_batch"),
                        ctx.rules, ctx.mesh)
    mid = (None,) * (x.ndim - 2)
    return jax.shard_map(
        fn, mesh=ctx.mesh,
        in_specs=(P(b, *mid, None), P(t, None, None), P(t, None)),
        out_specs=P(b, *mid, t), check_vma=False)(x, values, idx)


def linear_apply(params, x: jax.Array, *, prefer_pallas: bool = False,
                 impl: Optional[str] = None) -> jax.Array:
    """Apply a layer created by ``linear_init`` (unboxed params).

    Compressed layers route through ``repro.dispatch``: the implementation
    (gather-einsum XLA vs. fused Pallas micro-kernel) is chosen per operator
    shape from the profile DB / platform heuristic.  ``impl=`` (or the legacy
    ``prefer_pallas`` flag) forces a specific candidate, and
    ``REPRO_DISPATCH=off`` restores the pre-dispatch fixed routing.
    """
    if "values_r" in params:
        y = forward_compressed_reduce(x, params["values_r"], params["idx_r"])
        if "b" in params:
            y = y + params["b"]
        return y
    if "values" in params:
        from repro import dispatch as _dispatch

        if impl is None and prefer_pallas:
            impl = "compressed_pallas"

        def dispatched(x, values, idx):
            p = {"values": values, "idx": idx}
            key = _dispatch.linear_key_from(
                x.shape, values.shape, x.dtype,
                phase=_dispatch.current_phase())
            spec = _dispatch.best_impl(key, param_keys=("values", "idx"),
                                       force=impl)
            # execution guard: a candidate that fails to run (trace-time
            # kernel crash or injected fault) is quarantined and the key
            # re-resolves down the ladder instead of killing the forward
            return _dispatch.run_guarded(key, spec, lambda s: s.apply(p, x),
                                         param_keys=("values", "idx"))

        y = _per_shard(dispatched, x, params["values"], params["idx"])
    elif "mask" in params:
        y = forward_masked(x, params["w"], params["mask"])
    else:
        y = x @ params["w"]
    if "b" in params:
        y = y + params["b"]
    return y


# ---------------------------------------------------------------------------
# Conversions (prune a trained dense layer -> compressed)
# ---------------------------------------------------------------------------


def compress_layer(params, cfg: SparsityConfig):
    """Convert a dense/masked layer param dict into compressed format.

    Scan-stacked weights ([L, ..., d_in, d_out]) are packed per layer via
    vmap — the stacked (values, idx) feed straight back into the layer scan.
    """
    w = params["w"]
    w = w.value if isinstance(w, Boxed) else w
    lead = w.shape[:-2]
    d_in, d_out = w.shape[-2:]
    meta = formats.meta_for(d_in, d_out, cfg)
    mask = params.get("mask")
    if mask is not None and isinstance(mask, Boxed):
        mask = mask.value

    def pack2d(w2, m2):
        if m2 is None:
            if cfg.scheme == "rowwise":
                m2 = rowwise_nm_mask(w2, cfg.sparsity, m=cfg.m)
            else:
                m2 = colwise_nm_mask(w2, cfg.sparsity, m=cfg.m, tile=meta.tile)
        return formats.pack_colwise(w2, m2, meta)

    if lead:
        wf = w.reshape((-1,) + w.shape[-2:])
        mf = mask.reshape((-1,) + w.shape[-2:]) if mask is not None else None
        if mf is None:
            values, idx = jax.vmap(lambda a: pack2d(a, None))(wf)
        else:
            values, idx = jax.vmap(pack2d)(wf, mf)
        values = values.reshape(lead + values.shape[1:])
        idx = idx.reshape(lead + idx.shape[1:])
    else:
        values, idx = pack2d(w, mask)
    out = {"values": values, "idx": idx}
    if "b" in params:
        b = params["b"]
        out["b"] = b.value if isinstance(b, Boxed) else b
    return out


def flops_dense(batch: int, d_in: int, d_out: int) -> int:
    return 2 * batch * d_in * d_out


def flops_compressed(batch: int, meta: formats.ColwiseMeta) -> int:
    return 2 * batch * meta.k_kept * meta.d_out
