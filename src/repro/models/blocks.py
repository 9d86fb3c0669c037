"""Decoder blocks assembled from attention / MLP / MoE / SSM / xLSTM parts,
plus the parameter-stacking helper used for scan-over-layers."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.sparse_linear import Boxed, box_map, linear_apply, linear_init
from repro.models import attention as attn
from repro.models.common import norm_apply, norm_init
from repro.models.mlp import mlp_apply, mlp_init
from repro.models.moe import moe_apply, moe_init
from repro.sharding import shd


def _is_boxed(x):
    return isinstance(x, Boxed)


def stack_init(init_fn, key, n: int):
    """Stack n copies of init_fn's params along a leading 'layers' axis."""
    ks = jax.random.split(key, n)
    proto = init_fn(ks[0])

    def values_only(k):
        return box_map(lambda b: b.value, init_fn(k))

    vals = jax.vmap(values_only)(ks)
    return jax.tree_util.tree_map(
        lambda b, v: Boxed(v, ("layers",) + b.spec), proto, vals, is_leaf=_is_boxed
    )


# ---------------------------------------------------------------------------
# Standard transformer decoder block (attn + mlp/moe)
# ---------------------------------------------------------------------------


def block_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    dtype = jnp.dtype(cfg.param_dtype)
    p = {
        "ln1": norm_init(cfg.d_model, cfg.norm, dtype),
        "attn": attn.attn_init(ks[0], cfg),
        "ln2": norm_init(cfg.d_model, cfg.norm, dtype),
    }
    if cfg.is_moe:
        p["moe"] = moe_init(ks[1], cfg)
    else:
        p["mlp"] = mlp_init(ks[1], cfg)
    return p


def block_apply(params, cfg: ModelConfig, h, *, positions, mrope_positions=None,
                causal=True):
    """Returns (h, aux_loss).

    SP boundary note (EXPERIMENTS §Perf iteration C, refuted hypothesis):
    gathering the bf16 residual *before* the norm cut the f32 boundary
    all-gathers (6.1->5.5s collective) but doubled the memory term — the
    norm then runs on the full gathered sequence and the full-seq residual
    is rematerialized.  Norm-on-sharded-sequence (Megatron-SP order) wins.
    """
    x = norm_apply(params["ln1"], h, cfg.norm)
    x = shd(x, "act_batch", None, "act_embed")  # SP all-gather boundary
    h = h + attn.attn_apply(
        params["attn"], cfg, x, positions=positions,
        mrope_positions=mrope_positions, causal=causal,
    )
    h = shd(h, "act_batch", "act_seq_sp", None)
    x = norm_apply(params["ln2"], h, cfg.norm)
    x = shd(x, "act_batch", None, "act_embed")
    if cfg.is_moe:
        if cfg.moe_impl == "shard_map":
            from repro.models.moe import moe_apply_shard_map

            y, aux = moe_apply_shard_map(params["moe"], cfg, x)
        else:
            y, aux = moe_apply(params["moe"], cfg, x)
    else:
        y, aux = mlp_apply(params["mlp"], cfg, x), jnp.zeros((), jnp.float32)
    h = h + y
    h = shd(h, "act_batch", "act_seq_sp", None)
    return h, aux


def block_decode(params, cfg: ModelConfig, h, layer_cache, *, pos,
                 mrope_positions=None):
    """One-token decode through a transformer block. Returns (h, new_cache)."""
    x = norm_apply(params["ln1"], h, cfg.norm)
    a, new_cache = attn.attn_decode(
        params["attn"], cfg, x, layer_cache, pos=pos, mrope_positions=mrope_positions
    )
    h = h + a
    x = norm_apply(params["ln2"], h, cfg.norm)
    if cfg.is_moe:
        if cfg.moe_impl == "shard_map":
            from repro.models.moe import moe_apply_shard_map

            y, _ = moe_apply_shard_map(params["moe"], cfg, x)
        else:
            y, _ = moe_apply(params["moe"], cfg, x)
    else:
        y = mlp_apply(params["mlp"], cfg, x)
    return h + y, new_cache


def block_prefill_chunk(params, cfg: ModelConfig, h, layer_cache, *, start,
                        mrope_positions=None):
    """Chunked prefill through a transformer block: h [B, C, d] at absolute
    positions [start, start+C) against a preallocated layer cache.
    Returns (h, (k_chunk, v_chunk))."""
    x = norm_apply(params["ln1"], h, cfg.norm)
    a, kv_new = attn.attn_prefill_chunk(
        params["attn"], cfg, x, layer_cache, start=start,
        mrope_positions=mrope_positions,
    )
    h = h + a
    x = norm_apply(params["ln2"], h, cfg.norm)
    if cfg.is_moe:
        if cfg.moe_impl == "shard_map":
            from repro.models.moe import moe_apply_shard_map

            y, _ = moe_apply_shard_map(params["moe"], cfg, x)
        else:
            y, _ = moe_apply(params["moe"], cfg, x)
    else:
        y = mlp_apply(params["mlp"], cfg, x)
    return h + y, kv_new


# ---------------------------------------------------------------------------
# Zamba2 shared attention block (one set of weights reused across the stack)
# ---------------------------------------------------------------------------


def shared_block_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 3)
    dtype = jnp.dtype(cfg.param_dtype)
    return {
        # Zamba concatenates the current hidden with the original embedding;
        # we fuse [2d -> d] before the shared transformer block (see DESIGN).
        "fuse": linear_init(ks[0], 2 * cfg.d_model, cfg.d_model, cfg.sparsity,
                            dtype=dtype, in_ax="embed", out_ax="embed2"),
        "block": block_init(ks[1], cfg),
    }


def shared_block_apply(params, cfg: ModelConfig, h, h0, *, positions):
    x = jnp.concatenate([h, h0], axis=-1)
    x = linear_apply(params["fuse"], x)
    out, _ = block_apply(params["block"], cfg, x, positions=positions)
    return h + out


def shared_block_decode(params, cfg: ModelConfig, h, h0, layer_cache, *, pos):
    x = jnp.concatenate([h, h0], axis=-1)
    x = linear_apply(params["fuse"], x)
    out, new_cache = block_decode(params["block"], cfg, x, layer_cache, pos=pos)
    return h + out, new_cache


def block_paged_decode(params, cfg: ModelConfig, h, cache, *, layer, pos,
                       tables, page_size: int):
    """One-token decode through a transformer block against a paged cache.

    cache (k_pages, v_pages): [L, P, page_size, KV*D], read at ``layer``;
    pos [B]; tables [B, n_max].  Returns (h, (k_new, v_new)) — the caller
    scatters through the page table after the layer scan (same contract as
    block_decode).
    """
    x = norm_apply(params["ln1"], h, cfg.norm)
    a, new_kv = attn.paged_attn_decode(
        params["attn"], cfg, x, cache, layer=layer, pos=pos, tables=tables,
        page_size=page_size,
    )
    h = h + a
    x = norm_apply(params["ln2"], h, cfg.norm)
    if cfg.is_moe:
        if cfg.moe_impl == "shard_map":
            from repro.models.moe import moe_apply_shard_map

            y, _ = moe_apply_shard_map(params["moe"], cfg, x)
        else:
            y, _ = moe_apply(params["moe"], cfg, x)
    else:
        y = mlp_apply(params["mlp"], cfg, x)
    return h + y, new_kv


def block_prefill_packed(params, cfg: ModelConfig, h, *, seq_ids, positions):
    """Packed multi-prompt prefill through a transformer block.

    h [1, T, d] is the concatenated padding-free stream; seq_ids/positions
    [T].  Returns (h, (k [1,T,KV,D], v)); the caller scatters the stream's
    K/V through the page tables after the layer scan.
    """
    x = norm_apply(params["ln1"], h, cfg.norm)
    a, kv_new = attn.attn_prefill_packed(
        params["attn"], cfg, x, seq_ids=seq_ids, positions=positions,
    )
    h = h + a
    x = norm_apply(params["ln2"], h, cfg.norm)
    if cfg.is_moe:
        if cfg.moe_impl == "shard_map":
            from repro.models.moe import moe_apply_shard_map

            y, _ = moe_apply_shard_map(params["moe"], cfg, x)
        else:
            y, _ = moe_apply(params["moe"], cfg, x)
    else:
        y = mlp_apply(params["mlp"], cfg, x)
    return h + y, kv_new
