"""Model facade: one API over all 10 architectures + ShapeDtypeStruct input
specs for every (arch × shape) dry-run cell.

``input_specs`` follows the assignment contract: weak-type-correct,
shardable stand-ins, no device allocation.  Modality frontends are stubs —
whisper receives precomputed frame embeddings, qwen2-vl receives precomputed
patch embeddings + M-RoPE position ids.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeCell
from repro.core.sparse_linear import unbox_tree
from repro.models import encdec as encdec_mod
from repro.models import lm as lm_mod

SDS = jax.ShapeDtypeStruct


# ---------------------------------------------------------------------------
# Init (+ logical specs without materializing params)
# ---------------------------------------------------------------------------


def init_fn(cfg: ModelConfig):
    if cfg.is_encoder_decoder:
        return lambda key: encdec_mod.encdec_init(cfg, key)
    return lambda key: lm_mod.lm_init(cfg, key)


def init_params(cfg: ModelConfig, key):
    """Materialized (values, logical_specs)."""
    return unbox_tree(init_fn(cfg)(key))


def abstract_params(cfg: ModelConfig):
    """(ShapeDtypeStruct tree, logical_specs) with zero allocation — used by
    the dry-run for 72B-scale configs."""
    holder = {}

    def f():
        vals, specs = unbox_tree(init_fn(cfg)(jax.random.PRNGKey(0)))
        holder["specs"] = specs
        return vals

    shapes = jax.eval_shape(f)
    return shapes, holder["specs"]


# ---------------------------------------------------------------------------
# Step functions
# ---------------------------------------------------------------------------


def loss_fn(cfg: ModelConfig):
    if cfg.is_encoder_decoder:
        return lambda params, batch: encdec_mod.encdec_loss(params, cfg, batch)
    return lambda params, batch: lm_mod.loss_fn(params, cfg, batch)


def forward_fn(cfg: ModelConfig):
    if cfg.is_encoder_decoder:
        def f(params, batch):
            enc = encdec_mod.encode(params, cfg, batch["enc_embeds"])
            return encdec_mod.decode_forward(params, cfg, batch["tokens"], enc)
        return f
    return lambda params, batch: lm_mod.lm_forward(params, cfg, batch)[0]


def prefill_fn(cfg: ModelConfig):
    if cfg.is_encoder_decoder:
        return lambda params, batch: encdec_mod.encdec_prefill(
            params, cfg, batch["enc_embeds"], batch["tokens"]
        )
    return lambda params, batch: lm_mod.prefill(params, cfg, batch["tokens"])


def decode_fn(cfg: ModelConfig):
    if cfg.is_encoder_decoder:
        return lambda params, cache, tokens, pos: encdec_mod.encdec_decode_step(
            params, cfg, cache, tokens, pos
        )
    return lambda params, cache, tokens, pos: lm_mod.decode_step(
        params, cfg, cache, tokens, pos
    )


def prefill_chunk_fn(cfg: ModelConfig):
    """Chunked prefill step (continuous batching): processes tokens [B, C] at
    absolute positions [start, start+C) into a preallocated cache.
    Attention-pattern decoder-only families only."""
    if cfg.is_encoder_decoder or cfg.block_pattern != "attn":
        raise NotImplementedError(
            f"chunked prefill requires a decoder-only attention family; "
            f"{cfg.name} has block_pattern={cfg.block_pattern!r}"
            + (" (encoder-decoder)" if cfg.is_encoder_decoder else ""))
    return lambda params, cache, tokens, start, with_logits=True: (
        lm_mod.prefill_chunk(params, cfg, cache, tokens, start, with_logits)
    )


def _require_paged_family(cfg: ModelConfig, what: str):
    if cfg.is_encoder_decoder or cfg.block_pattern != "attn":
        raise NotImplementedError(
            f"{what} requires a decoder-only attention family; "
            f"{cfg.name} has block_pattern={cfg.block_pattern!r}"
            + (" (encoder-decoder)" if cfg.is_encoder_decoder else ""))


def paged_decode_fn(cfg: ModelConfig, page_size: int):
    """Decode step against a paged KV cache (serve.kv_pages tier): tokens
    [B, 1], pos [B], tables [B, n_max]. Attention families only."""
    _require_paged_family(cfg, "paged decode")
    return lambda params, cache, tokens, pos, tables: lm_mod.paged_decode_step(
        params, cfg, cache, tokens, pos, tables, page_size
    )


def prefill_packed_fn(cfg: ModelConfig, page_size: int):
    """Packed padding-free prefill into a paged cache: one concatenated
    [T]-token stream with per-token slot ids/positions."""
    _require_paged_family(cfg, "packed prefill")
    return lambda params, cache, tokens, slot_ids, positions, tables, last_idx: (
        lm_mod.prefill_packed(params, cfg, cache, tokens, slot_ids, positions,
                              tables, last_idx, page_size)
    )


def paged_cache_init_fn(cfg: ModelConfig, n_pages: int, page_size: int):
    """Physical paged cache ([L, n_pages + 1, page_size, KV * D] per leaf;
    the +1 is the trash page)."""
    _require_paged_family(cfg, "paged cache")
    from repro.models import attention as attn_mod

    return lambda: attn_mod.paged_cache_init(
        cfg, n_pages, page_size, cfg.n_layers, jnp.dtype(cfg.dtype))


def cache_init_fn(cfg: ModelConfig, batch: int, max_len: int):
    if cfg.is_encoder_decoder:
        return lambda: encdec_mod.encdec_cache_init(cfg, batch, max_len, cfg.encoder_seq)
    return lambda: lm_mod.cache_init(cfg, batch, max_len)


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int):
    return jax.eval_shape(cache_init_fn(cfg, batch, max_len))


# ---------------------------------------------------------------------------
# Logical specs for activations / batches / caches
# ---------------------------------------------------------------------------


def batch_specs(cfg: ModelConfig, batch: Dict[str, Any]):
    """Logical dim names per batch entry (matched to input_specs output)."""
    names = {
        "tokens": ("act_batch", None),
        "mrope_positions": ("act_batch", None, None),
        "vision_embeds": ("act_batch", None, None),
        "vision_pos": ("act_batch", None),
        "enc_embeds": ("act_batch", None, None),
    }
    return {k: names[k] for k in batch}


def cache_specs(cfg: ModelConfig, cache) -> Any:
    """Logical dim-name tree matching the cache structure."""

    def kv_spec(x):
        return (None, "act_batch", "act_kv_seq", "act_kv_heads", None)

    if cfg.is_encoder_decoder:
        return {k: kv_spec(None) for k in ("k", "v", "xk", "xv")}
    pat = cfg.block_pattern
    if pat == "attn":
        return {"k": kv_spec(None), "v": kv_spec(None)}
    if pat == "xlstm":
        return {
            "mlstm": {
                "C": (None, None, "act_batch", "act_heads", None, None),
                "n": (None, None, "act_batch", "act_heads", None),
                "m": (None, None, "act_batch", "act_heads"),
            },
            "slstm": {
                "c": (None, "act_batch", "act_heads", None),
                "n": (None, "act_batch", "act_heads", None),
                "h": (None, "act_batch", "act_heads", None),
                "m": (None, "act_batch", "act_heads", None),
            },
        }
    if pat == "mamba_shared_attn":
        spec = {
            "mamba": {
                "ssm": (None, None, "act_batch", "act_heads", None, None),
                "conv": (None, None, "act_batch", None, "act_ffn"),
            },
            "shared_kv": {"k": kv_spec(None), "v": kv_spec(None)},
        }
        if isinstance(cache, dict) and "mamba_tail" in cache:
            spec["mamba_tail"] = {
                "ssm": (None, "act_batch", "act_heads", None, None),
                "conv": (None, "act_batch", None, "act_ffn"),
            }
        return spec
    raise ValueError(pat)


# ---------------------------------------------------------------------------
# input_specs: ShapeDtypeStruct stand-ins per (arch × shape)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, cell: ShapeCell) -> Dict[str, Any]:
    """Returns {"kind", "batch" or ("cache","tokens","pos")} of SDS stand-ins."""
    b, s = cell.global_batch, cell.seq_len
    dt = jnp.dtype(cfg.dtype)
    i32 = jnp.int32

    def train_batch(seq):
        batch = {"tokens": SDS((b, seq), i32)}
        if cfg.family == "vlm":
            batch["mrope_positions"] = SDS((b, 3, seq), i32)
            batch["vision_embeds"] = SDS((b, cfg.vision_patches, cfg.d_model), dt)
            batch["vision_pos"] = SDS((b, cfg.vision_patches), i32)
        if cfg.is_encoder_decoder:
            batch["enc_embeds"] = SDS((b, seq, cfg.d_model), dt)
        return batch

    if cell.kind == "train":
        return {"kind": "train", "batch": train_batch(s)}

    if cell.kind == "prefill":
        if cfg.is_encoder_decoder:
            # the 32k lands on the audio/frame axis; decoder prompt is short
            return {
                "kind": "prefill",
                "batch": {
                    "enc_embeds": SDS((b, s, cfg.d_model), dt),
                    "tokens": SDS((b, 128), i32),
                },
            }
        batch = {"tokens": SDS((b, s), i32)}
        if cfg.family == "vlm":
            batch["mrope_positions"] = SDS((b, 3, s), i32)
        return {"kind": "prefill", "batch": batch}

    # decode: one new token vs a cache of length s
    cache = abstract_cache(cfg, b, s)
    return {
        "kind": "decode",
        "cache": cache,
        "tokens": SDS((b, 1), i32),
        "pos": SDS((), i32),
    }
