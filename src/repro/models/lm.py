"""Top-level language models for every family in the zoo.

A single init/apply pair covers:
  - dense / MoE / VLM transformers ("attn" pattern): scan over stacked blocks
  - xLSTM ("xlstm" pattern): scan over superblocks of (slstm_every-1) mLSTM
    blocks followed by one sLSTM block
  - Zamba2 hybrid ("mamba_shared_attn"): scan over superblocks of
    shared_attn_every Mamba2 blocks followed by one application of the
    *shared* attention block (one set of weights, 'layers//every' KV caches)

Training entry point: ``loss_fn``; serving entry points: ``prefill`` and
``decode_step`` (single new token against a KV/state cache).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.sparse_linear import Boxed, box_map, unbox_tree
from repro.models import attention as attn_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.blocks import (
    block_apply,
    block_decode,
    block_init,
    block_paged_decode,
    block_prefill_chunk,
    block_prefill_packed,
    shared_block_apply,
    shared_block_decode,
    shared_block_init,
    stack_init,
)
from repro.models.common import embed_init, embed_lookup, norm_apply, norm_init
from repro.sharding import shd


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def lm_init(cfg: ModelConfig, key) -> Dict[str, Any]:
    ks = jax.random.split(key, 8)
    dtype = jnp.dtype(cfg.param_dtype)
    p: Dict[str, Any] = {
        "embed": embed_init(ks[0], cfg.padded_vocab, cfg.d_model, dtype),
        "final_norm": norm_init(cfg.d_model, cfg.norm, dtype),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = Boxed(
            jax.random.normal(ks[1], (cfg.d_model, cfg.padded_vocab), dtype) * 0.02,
            ("embed", "vocab"),
        )
    pat = cfg.block_pattern
    if pat == "attn":
        p["layers"] = stack_init(lambda k: block_init(k, cfg), ks[2], cfg.n_layers)
    elif pat == "xlstm":
        every = cfg.slstm_every
        assert cfg.n_layers % every == 0, "xlstm: n_layers % slstm_every == 0"
        n_super = cfg.n_layers // every
        p["mlstm"] = stack_init(
            lambda k: stack_init(lambda k2: xlstm_mod.mlstm_init(k2, cfg), k, every - 1),
            ks[2],
            n_super,
        )
        p["slstm"] = stack_init(lambda k: xlstm_mod.slstm_init(k, cfg), ks[3], n_super)
    elif pat == "mamba_shared_attn":
        every = cfg.shared_attn_every
        n_super = cfg.n_layers // every
        rem = cfg.n_layers - n_super * every
        p["mamba"] = stack_init(
            lambda k: stack_init(lambda k2: ssm_mod.mamba_init(k2, cfg), k, every),
            ks[2],
            n_super,
        )
        if rem:
            p["mamba_tail"] = stack_init(lambda k: ssm_mod.mamba_init(k, cfg), ks[4], rem)
        p["shared"] = shared_block_init(ks[3], cfg)
    else:
        raise ValueError(f"unknown block_pattern {pat}")
    return p


def n_shared_applications(cfg: ModelConfig) -> int:
    return cfg.n_layers // cfg.shared_attn_every


# ---------------------------------------------------------------------------
# Forward (training / scoring)
# ---------------------------------------------------------------------------


def _embed_tokens(params, cfg: ModelConfig, batch) -> jax.Array:
    h = embed_lookup(params["embed"], batch["tokens"]).astype(jnp.dtype(cfg.dtype))
    if cfg.family == "vlm" and "vision_embeds" in batch:
        b = h.shape[0]
        ve = batch["vision_embeds"].astype(h.dtype)
        h = h.at[jnp.arange(b)[:, None], batch["vision_pos"]].set(ve)
    return shd(h, "act_batch", "act_seq_sp", None)


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat_policy == "dots"
                  else jax.checkpoint_policies.nothing_saveable)
        return jax.checkpoint(fn, policy=policy)
    return fn


def _compute_params(layer_params, cfg: ModelConfig):
    """Mixed precision: a layer's floating params cast to the compute dtype
    (the cast's gradient lands on the stored ``param_dtype`` leaves)."""
    if cfg.param_dtype == cfg.dtype:
        return layer_params
    dt = jnp.dtype(cfg.dtype)
    return jax.tree_util.tree_map(
        lambda a: a.astype(dt) if jnp.issubdtype(a.dtype, jnp.floating) else a,
        layer_params)


def lm_forward(params, cfg: ModelConfig, batch) -> Tuple[jax.Array, jax.Array]:
    """Returns (logits [B,S,V_padded], aux_loss)."""
    h = _embed_tokens(params, cfg, batch)
    b, s = batch["tokens"].shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    mrope_positions = batch.get("mrope_positions") if cfg.mrope else None
    pat = cfg.block_pattern
    aux = jnp.zeros((), jnp.float32)

    if pat == "attn":
        def body(carry, layer_params):
            hh, = carry
            hh, a = block_apply(_compute_params(layer_params, cfg), cfg, hh,
                                positions=positions,
                                mrope_positions=mrope_positions)
            return (hh,), a

        (h,), auxs = jax.lax.scan(_maybe_remat(body, cfg), (h,), params["layers"])
        aux = auxs.mean()
    elif pat == "xlstm":
        def super_body(carry, sp):
            hh, = carry
            mp, sp_params = sp

            def inner(c2, lp):
                (h2,) = c2
                h2 = h2 + xlstm_mod.mlstm_apply(lp, cfg, h2)
                h2 = shd(h2, "act_batch", "act_seq_sp", None)
                return (h2,), jnp.zeros(())

            (hh,), _ = jax.lax.scan(inner, (hh,), mp)
            hh = hh + xlstm_mod.slstm_apply(sp_params, cfg, hh)
            hh = shd(hh, "act_batch", "act_seq_sp", None)
            return (hh,), jnp.zeros(())

        (h,), _ = jax.lax.scan(
            _maybe_remat(super_body, cfg), (h,), (params["mlstm"], params["slstm"])
        )
    elif pat == "mamba_shared_attn":
        h0 = h

        def super_body(carry, mp):
            hh, = carry

            def inner(c2, lp):
                (h2,) = c2
                h2 = h2 + ssm_mod.mamba_apply(lp, cfg, h2)
                h2 = shd(h2, "act_batch", "act_seq_sp", None)
                return (h2,), jnp.zeros(())

            (hh,), _ = jax.lax.scan(inner, (hh,), mp)
            hh = shared_block_apply(params["shared"], cfg, hh, h0, positions=positions)
            hh = shd(hh, "act_batch", "act_seq_sp", None)
            return (hh,), jnp.zeros(())

        (h,), _ = jax.lax.scan(_maybe_remat(super_body, cfg), (h,), params["mamba"])
        if "mamba_tail" in params:
            def tail(c2, lp):
                (h2,) = c2
                h2 = h2 + ssm_mod.mamba_apply(lp, cfg, h2)
                return (h2,), jnp.zeros(())

            (h,), _ = jax.lax.scan(_maybe_remat(tail, cfg), (h,), params["mamba_tail"])
    else:
        raise ValueError(pat)

    h = norm_apply(params["final_norm"], h, cfg.norm)
    logits = _unembed(params, cfg, h)
    return logits, aux


def _unembed(params, cfg: ModelConfig, h) -> jax.Array:
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", h, params["embed"].astype(h.dtype))
    else:
        logits = h @ params["unembed"].astype(h.dtype)
    return shd(logits, "act_batch", None, "act_vocab")


def loss_fn(params, cfg: ModelConfig, batch, aux_weight: float = 0.01):
    """Next-token cross-entropy (+ MoE load-balance aux)."""
    logits, aux = lm_forward(params, cfg, batch)
    logits = logits[:, :-1].astype(jnp.float32)
    labels = batch["tokens"][:, 1:]
    # padded vocab ids can never appear as labels; mask them out of the
    # softmax so padding does not leak probability mass
    if cfg.padded_vocab != cfg.vocab_size:
        neg = jnp.full((cfg.padded_vocab - cfg.vocab_size,), -1e30, jnp.float32)
        logits = logits.at[..., cfg.vocab_size:].set(neg)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold).mean()
    return nll + aux_weight * aux, {"nll": nll, "aux": aux}


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------


def cache_init(cfg: ModelConfig, batch: int, max_len: int):
    """Family-specific decode cache (all leaves are jnp arrays)."""
    dtype = jnp.dtype(cfg.dtype)
    pat = cfg.block_pattern
    if pat == "attn":
        return attn_mod.cache_init(cfg, batch, max_len, cfg.n_layers, dtype)
    if pat == "xlstm":
        every = cfg.slstm_every
        n_super = cfg.n_layers // every

        def stack(fn, n):
            return jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (n,) + x.shape), fn
            )

        m1 = xlstm_mod.mlstm_cache_init(cfg, batch)
        s1 = xlstm_mod.slstm_cache_init(cfg, batch)
        return {
            "mlstm": jax.tree_util.tree_map(
                lambda x: jnp.zeros((n_super, every - 1) + x.shape, x.dtype), m1
            ),
            "slstm": jax.tree_util.tree_map(
                lambda x: jnp.zeros((n_super,) + x.shape, x.dtype), s1
            ),
        }
    if pat == "mamba_shared_attn":
        every = cfg.shared_attn_every
        n_super = cfg.n_layers // every
        rem = cfg.n_layers - n_super * every
        m1 = ssm_mod.mamba_cache_init(cfg, batch, dtype)
        out = {
            "mamba": jax.tree_util.tree_map(
                lambda x: jnp.zeros((n_super, every) + x.shape, x.dtype), m1
            ),
            "shared_kv": attn_mod.cache_init(cfg, batch, max_len, n_super, dtype),
        }
        if rem:
            out["mamba_tail"] = jax.tree_util.tree_map(
                lambda x: jnp.zeros((rem,) + x.shape, x.dtype), m1
            )
        return out
    raise ValueError(pat)


def decode_step(params, cfg: ModelConfig, cache, tokens: jax.Array, pos: jax.Array):
    """One decode step. tokens [B,1]; pos scalar int32 (current length) or a
    per-sequence [B] int32 vector (continuous batching: slots at mixed
    lengths decode in one step).

    Returns (logits [B,1,V], new_cache).
    """
    h = embed_lookup(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    b = tokens.shape[0]
    pat = cfg.block_pattern
    mrope_positions = None
    if cfg.mrope:
        pos_b = jnp.broadcast_to(jnp.reshape(jnp.asarray(pos, jnp.int32), (-1,)), (b,))
        mrope_positions = jnp.broadcast_to(pos_b.reshape(b, 1, 1), (b, 3, 1))

    if pat == "attn":
        def body(carry, xs):
            hh, = carry
            lp, kc, vc = xs
            hh, (kn, vn) = block_decode(lp, cfg, hh, (kc, vc), pos=pos,
                                        mrope_positions=mrope_positions)
            return (hh,), (kn, vn)

        (h,), (k_news, v_news) = jax.lax.scan(
            body, (h,), (params["layers"], cache["k"], cache["v"])
        )
        k2, v2 = attn_mod.cache_write(cache["k"], cache["v"], k_news, v_news, pos)
        new_cache = {"k": k2, "v": v2}
    elif pat == "xlstm":
        def super_body(carry, xs):
            hh, = carry
            mp, sp_params, mcache, scache = xs

            def inner(c2, xs2):
                (h2,) = c2
                lp, lc = xs2
                dh, nc = xlstm_mod.mlstm_decode(lp, cfg, h2, lc)
                return (h2 + dh,), nc

            (hh,), m_new = jax.lax.scan(inner, (hh,), (mp, mcache))
            dh, s_new = xlstm_mod.slstm_decode(sp_params, cfg, hh, scache)
            return (hh + dh,), (m_new, s_new)

        (h,), (m_new, s_new) = jax.lax.scan(
            super_body, (h,),
            (params["mlstm"], params["slstm"], cache["mlstm"], cache["slstm"]),
        )
        new_cache = {"mlstm": m_new, "slstm": s_new}
    elif pat == "mamba_shared_attn":
        h0 = h

        def super_body(carry, xs):
            hh, = carry
            mp, mcache, kc, vc = xs

            def inner(c2, xs2):
                (h2,) = c2
                lp, lc = xs2
                dh, nc = ssm_mod.mamba_decode(lp, cfg, h2, lc)
                return (h2 + dh,), nc

            (hh,), m_new = jax.lax.scan(inner, (hh,), (mp, mcache))
            hh, (kn, vn) = shared_block_decode(
                params["shared"], cfg, hh, h0, (kc, vc), pos=pos
            )
            return (hh,), (m_new, kn, vn)

        (h,), (m_new, k_news, v_news) = jax.lax.scan(
            super_body, (h,),
            (params["mamba"], cache["mamba"], cache["shared_kv"]["k"],
             cache["shared_kv"]["v"]),
        )
        k2, v2 = attn_mod.cache_write(cache["shared_kv"]["k"], cache["shared_kv"]["v"],
                                      k_news, v_news, pos)
        new_cache = {"mamba": m_new, "shared_kv": {"k": k2, "v": v2}}
        if "mamba_tail" in params:
            def tail(c2, xs2):
                (h2,) = c2
                lp, lc = xs2
                dh, nc = ssm_mod.mamba_decode(lp, cfg, h2, lc)
                return (h2 + dh,), nc

            (h,), t_new = jax.lax.scan(tail, (h,), (params["mamba_tail"], cache["mamba_tail"]))
            new_cache["mamba_tail"] = t_new
    else:
        raise ValueError(pat)

    h = norm_apply(params["final_norm"], h, cfg.norm)
    logits = _unembed(params, cfg, h)
    return logits, new_cache


def prefill(params, cfg: ModelConfig, tokens: jax.Array):
    """Process a prompt, returning (last-token logits, populated cache).

    For attention archs the per-layer K/V come out of the scan as ys; for
    recurrent archs prefill is decode run over the prompt — for the dry-run
    shapes we instead run the chunked parallel forward and only materialize
    the final state, which is what a production prefill would do.
    """
    b, s = tokens.shape
    batch = {"tokens": tokens}
    if cfg.mrope:
        pos3 = jnp.broadcast_to(jnp.arange(s)[None, None, :], (b, 3, s))
        batch["mrope_positions"] = pos3
    pat = cfg.block_pattern
    h = _embed_tokens(params, cfg, batch)
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    if pat == "attn":
        def body(carry, lp):
            hh, = carry
            x = norm_apply(lp["ln1"], hh, cfg.norm)
            q, k, v = attn_mod._qkv(lp["attn"], cfg, x,
                                    positions, batch.get("mrope_positions"))
            if cfg.attn_impl == "chunked" and s > cfg.attn_chunk:
                o = attn_mod.sdpa_gqa_chunked(q, k, v, causal=True,
                                              chunk=cfg.attn_chunk)
            else:
                o = attn_mod.sdpa_gqa(q, k, v, causal=True)
            from repro.core.sparse_linear import linear_apply as _la

            hh = hh + _la(lp["attn"]["o"], o.reshape(b, s, -1))
            x = norm_apply(lp["ln2"], hh, cfg.norm)
            if cfg.is_moe:
                if cfg.moe_impl == "shard_map":
                    from repro.models.moe import moe_apply_shard_map as _moe
                else:
                    from repro.models.moe import moe_apply as _moe

                y, _ = _moe(lp["moe"], cfg, x)
            else:
                from repro.models.mlp import mlp_apply

                y = mlp_apply(lp["mlp"], cfg, x)
            hh = hh + y
            hh = shd(hh, "act_batch", "act_seq_sp", None)
            return (hh,), (k, v)

        (h,), (ks, vs) = jax.lax.scan(_maybe_remat(body, cfg), (h,), params["layers"])
        cache = {"k": ks, "v": vs}  # [L, B, S, KV, D]
    else:
        # recurrent/hybrid prefill: run the parallel forward; dry-run cells
        # exercise decode_step for state-cache serving.
        logits, _ = lm_forward(params, cfg, batch)
        return logits[:, -1:], None

    h = norm_apply(params["final_norm"], h, cfg.norm)
    logits = _unembed(params, cfg, h)
    return logits[:, -1:], cache


def prefill_chunk(params, cfg: ModelConfig, cache, tokens: jax.Array,
                  start: jax.Array, with_logits: bool = True):
    """Prefill one chunk of a prompt into a preallocated cache.

    tokens [B, C] sit at absolute positions [start, start+C); ``cache`` is a
    full-size decode cache ([L, B, S_max, KV, D] per leaf) whose rows < start
    already hold this sequence's earlier chunks.  Returns
    (logits [B, C, V], cache with rows start..start+C written);
    ``with_logits=False`` skips the final-norm + unembed (the vocab-sized
    matmul) and returns (None, cache) — only the chunk containing the last
    prompt token needs logits.

    This is the unit of work the continuous-batching scheduler interleaves
    with decode steps: a long prompt is admitted as ceil(S/C) fixed-shape
    chunk calls (one compiled executable) instead of one [B, S]-shaped
    prefill per distinct prompt length.  Attention-cache families only —
    recurrent/hybrid state caches have no random-access rows to chunk into.
    """
    if cfg.block_pattern != "attn":
        raise NotImplementedError(
            f"prefill_chunk supports attention families only, not "
            f"block_pattern={cfg.block_pattern!r}")
    b, c_len = tokens.shape
    batch = {"tokens": tokens}
    mrope_positions = None
    if cfg.mrope:
        pos1 = start + jnp.arange(c_len, dtype=jnp.int32)
        mrope_positions = jnp.broadcast_to(pos1[None, None, :], (b, 3, c_len))
    h = _embed_tokens(params, cfg, batch)

    def body(carry, xs):
        hh, = carry
        lp, kc, vc = xs
        hh, (kn, vn) = block_prefill_chunk(
            lp, cfg, hh, (kc, vc), start=start,
            mrope_positions=mrope_positions)
        return (hh,), (kn, vn)

    (h,), (k_news, v_news) = jax.lax.scan(
        body, (h,), (params["layers"], cache["k"], cache["v"]))
    k2, v2 = attn_mod.cache_write(cache["k"], cache["v"], k_news, v_news, start)
    if not with_logits:
        return None, {"k": k2, "v": v2}
    h = norm_apply(params["final_norm"], h, cfg.norm)
    logits = _unembed(params, cfg, h)
    return logits, {"k": k2, "v": v2}


def paged_decode_step(params, cfg: ModelConfig, cache, tokens: jax.Array,
                      pos: jax.Array, tables: jax.Array, page_size: int):
    """One decode step against a paged KV cache (serve.kv_pages tier).

    tokens [B, 1]; pos [B] int32 per-slot lengths; tables [B, n_max] int32
    page tables; ``cache`` leaves are [L, P, page_size, KV*D] (P includes
    the trash page). Returns (logits [B, 1, V], new_cache). Same
    no-write-in-scan contract as :func:`decode_step`: the layers' new K/V
    come out as scan ys and ONE page-table scatter commits them.
    Attention-pattern families only.
    """
    if cfg.block_pattern != "attn":
        raise NotImplementedError(
            f"paged_decode_step supports attention families only, not "
            f"block_pattern={cfg.block_pattern!r}")
    h = embed_lookup(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    b = tokens.shape[0]
    pos_b = jnp.broadcast_to(
        jnp.reshape(jnp.asarray(pos, jnp.int32), (-1,)), (b,))

    def body(carry, xs):
        hh, = carry
        lp, li = xs
        # the kernel reads layer li's pages from the whole cache: a per-layer
        # slice would be a copy of the layer's pages every step
        hh, (kn, vn) = block_paged_decode(
            lp, cfg, hh, (cache["k"], cache["v"]), layer=li, pos=pos_b,
            tables=tables, page_size=page_size)
        return (hh,), (kn, vn)

    n_layers = cache["k"].shape[0]
    (h,), (k_news, v_news) = jax.lax.scan(
        body, (h,), (params["layers"], jnp.arange(n_layers, dtype=jnp.int32)))
    # k_news [L, B, 1, KV, D] -> [L, B, KV, D]; one scatter through the
    # tables (inactive slots' rows land on the trash page)
    rows = attn_mod.page_rows(tables, jnp.arange(b, dtype=jnp.int32), pos_b,
                              page_size)
    k2, v2 = attn_mod.paged_cache_write(
        cache["k"], cache["v"], k_news[:, :, 0], v_news[:, :, 0], rows)
    h = norm_apply(params["final_norm"], h, cfg.norm)
    logits = _unembed(params, cfg, h)
    return logits, {"k": k2, "v": v2}


def prefill_packed(params, cfg: ModelConfig, cache, tokens: jax.Array,
                   slot_ids: jax.Array, positions: jax.Array,
                   tables: jax.Array, last_idx: jax.Array, page_size: int):
    """Packed (padding-free) multi-prompt prefill into a paged cache.

    tokens/slot_ids/positions [T] — several prompts concatenated into one
    exact-shape stream (see ``serve.kv_pages.pack_prompts``); tables
    [n_slots, n_max]; last_idx [n_new] stream indices of each prompt's final
    token. Attention is block-diagonal causal over the stream — zero padded
    columns, zero wasted FLOPs — and only the ``n_new`` last-token rows pay
    the unembed matmul. Returns (logits [n_new, 1, V], cache with every
    prompt's K/V scattered through its page table).

    Retraces per distinct total stream length T (the padding-free
    tradeoff); the scheduler admits all same-iteration arrivals in ONE
    stream, so retraces are bounded by distinct admission-batch shapes.
    """
    if cfg.block_pattern != "attn":
        raise NotImplementedError(
            f"prefill_packed supports attention families only, not "
            f"block_pattern={cfg.block_pattern!r}")
    h = embed_lookup(params["embed"], tokens[None, :]).astype(
        jnp.dtype(cfg.dtype))

    def body(carry, xs):
        hh, = carry
        lp, = xs
        hh, (kn, vn) = block_prefill_packed(lp, cfg, hh, seq_ids=slot_ids,
                                            positions=positions)
        return (hh,), (kn, vn)

    (h,), (k_news, v_news) = jax.lax.scan(body, (h,), (params["layers"],))
    # k_news [L, 1, T, KV, D] -> [L, T, KV, D]; one scatter commits the
    # whole stream's K/V through the page tables
    rows = attn_mod.page_rows(tables, slot_ids, positions, page_size)
    k2, v2 = attn_mod.paged_cache_write(
        cache["k"], cache["v"], k_news[:, 0], v_news[:, 0], rows)
    h = norm_apply(params["final_norm"], h, cfg.norm)
    h_last = jnp.take(h[0], last_idx, axis=0)  # [n_new, d]
    logits = _unembed(params, cfg, h_last[:, None, :])
    return logits, {"k": k2, "v": v2}
