"""GQA attention with tensor-parallel head padding, RoPE/M-RoPE, KV cache.

Design notes (distribution):
  - q heads are padded to a multiple of tp; the padded heads' o_proj rows are
    zero so the function is exactly the unpadded one.
  - kv heads are sharded over the model axis only when divisible (the logical
    rules drop the axis otherwise) — for small GQA archs the kv tensors are
    tiny and replication is cheaper than the reshard.
  - GQA is computed with a grouped einsum (q reshaped [B,S,KV,G,D]) so the KV
    tensors are never materialized at H width — essential for 32k/512k decode
    caches.  Only the padded-head case where H % KV != 0 falls back to an
    explicit head-mapped expansion (small archs only).
  - decode attends one query against a [B, S_max, KV, D] cache: O(S) work.
    For long_500k the cache's seq dim carries the 'act_kv_seq' logical axis so
    GSPMD shards it over the otherwise-idle data axis (distributed
    flash-decode); scores at 512k, B=1 are ~64 MB in f32.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.sparse_linear import Boxed, linear_apply, linear_init
from repro.models.common import apply_rope, mrope_cos_sin, rope_cos_sin
from repro.sharding import shd


def attn_init(key, cfg: ModelConfig, *, cross: bool = False):
    """QKV/O projections (each a SparseLinear; o proj is reduce-oriented)."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.padded_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    scfg = cfg.sparsity
    dtype = jnp.dtype(cfg.param_dtype)
    p = {
        "q": linear_init(ks[0], d, h * hd, scfg, dtype=dtype, use_bias=cfg.qkv_bias,
                         in_ax="embed", out_ax="heads_flat"),
        "k": linear_init(ks[1], d, kv * hd, scfg, dtype=dtype, use_bias=cfg.qkv_bias,
                         in_ax="embed", out_ax="kv_flat"),
        "v": linear_init(ks[2], d, kv * hd, scfg, dtype=dtype, use_bias=cfg.qkv_bias,
                         in_ax="embed", out_ax="kv_flat"),
        "o": linear_init(ks[3], h * hd, d, scfg, dtype=dtype,
                         in_ax="heads_flat", out_ax="embed", mode="reduce"),
    }
    if cfg.n_heads != cfg.padded_heads and "w" in p["o"]:
        # zero the padded heads' output rows => exact numerics
        ow = p["o"]["w"]
        w = ow.value.reshape(h, hd, d)
        w = w.at[cfg.n_heads:].set(0.0)
        p["o"]["w"] = Boxed(w.reshape(h * hd, d), ow.spec)
    return p


def _qkv(params, cfg: ModelConfig, x: jax.Array, positions, mrope_positions):
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    h, kv = cfg.padded_heads, cfg.n_kv_heads
    q = linear_apply(params["q"], x).reshape(b, s, h, hd)
    k = linear_apply(params["k"], x).reshape(b, s, kv, hd)
    v = linear_apply(params["v"], x).reshape(b, s, kv, hd)
    if cfg.use_rope:
        if cfg.mrope and mrope_positions is not None:
            cos, sin = mrope_cos_sin(mrope_positions, hd, cfg.rope_theta, cfg.mrope_sections)
        else:
            cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def _expand_kv(k: jax.Array, n_q_heads: int) -> jax.Array:
    """Head-mapped expansion [B,S,KV,D] -> [B,S,H,D]; fallback for H%KV!=0."""
    kvh = k.shape[2]
    if n_q_heads == kvh:
        return k
    mapping = (jnp.arange(n_q_heads) * kvh) // n_q_heads
    return jnp.take(k, mapping, axis=2)


def sdpa_gqa(q, k, v, *, causal: bool, q_offset=0, kv_len=None) -> jax.Array:
    """Scaled dot-product attention with native GQA grouping.

    q: [B, Sq, H, D]; k/v: [B, Sk, KV, D]. Returns [B, Sq, H, D].
    """
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    scale = 1.0 / math.sqrt(d)
    if h % kvh != 0:
        k = _expand_kv(k, h)
        v = _expand_kv(v, h)
        kvh = h
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, d)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32) * scale
    if causal:
        qi = jnp.arange(sq)[:, None] + q_offset
        ki = jnp.arange(sk)[None, :]
        scores = jnp.where(ki <= qi, scores, -1e30)
    if kv_len is not None:
        ki = jnp.arange(sk).reshape(1, 1, 1, 1, sk)
        scores = jnp.where(ki < kv_len.reshape(b, 1, 1, 1, 1), scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", w, v)
    return o.reshape(b, sq, h, d)


def sdpa_gqa_chunked(
    q, k, v, *, causal: bool, q_offset=0, kv_len=None, chunk: int = 512
) -> jax.Array:
    """Blockwise (flash-style) attention: online softmax over KV chunks.

    The [Sq, Sk] score matrix never materializes — the dry-run showed it is
    both the dominant HBM traffic AND the source of TB-scale involuntary
    all-gathers in the backward (GSPMD cannot reshard the giant score tensor
    between the differently-sharded fwd/bwd dots).  Per chunk we expand KV to
    the full (padded) head count, so every tensor stays head-sharded over the
    model axis — no resharding, and the expansion lives only at chunk scale.

    q: [B, Sq, H, D]; k/v: [B, Sk, KV, D]. Returns [B, Sq, H, D].
    """
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    scale = 1.0 / math.sqrt(d)
    chunk = min(chunk, sk)
    n_chunks = -(-sk // chunk)
    pad = n_chunks * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    mapping = (jnp.arange(h) * kvh) // h if h % kvh else None
    kc = k.reshape(b, n_chunks, chunk, kvh, d)
    vc = v.reshape(b, n_chunks, chunk, kvh, d)
    qi = jnp.arange(sq)[:, None] + q_offset  # [Sq,1]
    f32 = jnp.float32

    def body(carry, xs):
        m, l, acc = carry  # [B,H,Sq], [B,H,Sq], [B,Sq,H,D] (f32)
        kx, vx, ci = xs  # [B,chunk,KV,D], [B,chunk,KV,D], scalar chunk idx
        if mapping is not None:
            kx = jnp.take(kx, mapping, axis=2)
            vx = jnp.take(vx, mapping, axis=2)
        elif h != kvh:
            kx = jnp.repeat(kx, h // kvh, axis=2)
            vx = jnp.repeat(vx, h // kvh, axis=2)
        kx = shd(kx, "act_batch", None, "act_heads", None)
        s = jnp.einsum("bqhd,bchd->bhqc", q, kx).astype(f32) * scale
        kpos = ci * chunk + jnp.arange(chunk)[None, :]  # [1,chunk]
        valid = jnp.ones((sq, chunk), bool) if not causal else (kpos <= qi)
        valid = valid & (kpos < sk)
        if kv_len is not None:
            valid = valid[None] & (kpos[None] < kv_len[:, None, None])
            s = jnp.where(valid[:, None], s, -1e30)
        else:
            s = jnp.where(valid[None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])  # [B,H,Sq,chunk] f32
        alpha = jnp.exp(m - m_new)  # [B,H,Sq]
        l_new = alpha * l + p.sum(axis=-1)
        pv = jnp.einsum("bhqc,bchd->bqhd", p.astype(vx.dtype), vx).astype(f32)
        acc_new = acc * alpha.transpose(0, 2, 1)[..., None] + pv
        return (m_new, l_new, acc_new), None

    carry0 = (
        jnp.full((b, h, sq), -1e30, f32),
        jnp.zeros((b, h, sq), f32),
        jnp.zeros((b, sq, h, d), f32),
    )
    xs = (
        jnp.moveaxis(kc, 1, 0),
        jnp.moveaxis(vc, 1, 0),
        jnp.arange(n_chunks),
    )
    # checkpoint the body: backward recomputes per-chunk scores instead of
    # stashing them (the whole point of going blockwise)
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(body), carry0, xs)
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def attn_apply(
    params,
    cfg: ModelConfig,
    x: jax.Array,
    *,
    positions: jax.Array,
    causal: bool = True,
    mrope_positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Full self-attention (training / prefill without cache)."""
    b, s, _ = x.shape
    q, k, v = _qkv(params, cfg, x, positions, mrope_positions)
    q = shd(q, "act_batch", None, "act_heads", None)
    k = shd(k, "act_batch", None, "act_kv_heads", None)
    if cfg.attn_impl == "pallas":
        from repro.kernels.flash_attn import flash_attention

        o = flash_attention(q, k, v, causal=causal)
    elif cfg.attn_impl == "chunked" and s > cfg.attn_chunk:
        o = sdpa_gqa_chunked(q, k, v, causal=causal, chunk=cfg.attn_chunk)
    else:
        o = sdpa_gqa(q, k, v, causal=causal)
    o = o.reshape(b, s, -1)
    return linear_apply(params["o"], o)


# ---------------------------------------------------------------------------
# Cross attention (whisper decoder)
# ---------------------------------------------------------------------------


def cross_attn_apply(params, cfg: ModelConfig, x: jax.Array, enc_kv) -> jax.Array:
    """x [B,Sq,d]; enc_kv = (k, v) precomputed from encoder output (no RoPE)."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = linear_apply(params["q"], x).reshape(b, s, cfg.padded_heads, hd)
    k, v = enc_kv
    o = sdpa_gqa(q, k, v, causal=False).reshape(b, s, -1)
    return linear_apply(params["o"], o)


def cross_kv(params, cfg: ModelConfig, enc_out: jax.Array):
    b, s, _ = enc_out.shape
    hd = cfg.resolved_head_dim
    k = linear_apply(params["k"], enc_out).reshape(b, s, cfg.n_kv_heads, hd)
    v = linear_apply(params["v"], enc_out).reshape(b, s, cfg.n_kv_heads, hd)
    return k, v


# ---------------------------------------------------------------------------
# KV cache (decode)
# ---------------------------------------------------------------------------


def cache_init(cfg: ModelConfig, batch: int, max_len: int, n_layers: int, dtype):
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    shape = (n_layers, batch, max_len, kv, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_spec_names():
    """Logical names per cache dim [L, B, S, KV, D]."""
    return (None, "act_batch", "act_kv_seq", "act_kv_heads", None)


def _cached_attention(q, k_new, v_new, kc, vc, *, limit, causal: bool):
    """softmax over (cache rows < limit[b]) ++ this step's new keys.

    q [B,C,H,D]; k_new/v_new [B,C,KV,D]; kc/vc [B,S_max,KV,D]; limit [B]
    int32.  ``causal`` masks the new keys intra-chunk (j <= i); cache rows
    >= limit may hold stale junk (a freed slot's previous occupant) and are
    always masked.  Shared by one-token decode (C=1, causal irrelevant) and
    chunked prefill.  Returns o [B,C,H,D].
    """
    b, c_len, h, d = q.shape
    kvh = kc.shape[2]
    s_max = kc.shape[1]
    scale = 1.0 / math.sqrt(d)
    f32 = jnp.float32
    qi = jnp.arange(c_len, dtype=jnp.int32)

    if h % kvh == 0:
        g = h // kvh
        qg = q.reshape(b, c_len, kvh, g, d)
        s_c = jnp.einsum("bqkgd,bskd->bkgqs", qg, kc.astype(q.dtype)).astype(f32) * scale
        ki = jnp.arange(s_max).reshape(1, 1, 1, 1, -1)
        s_c = jnp.where(ki < limit.reshape(b, 1, 1, 1, 1), s_c, -1e30)
        s_n = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_new.astype(q.dtype)).astype(f32) * scale
        if causal and c_len > 1:
            mask = (qi[None, :] <= qi[:, None]).reshape(1, 1, 1, c_len, c_len)
            s_n = jnp.where(mask, s_n, -1e30)
        w = jax.nn.softmax(jnp.concatenate([s_c, s_n], axis=-1), axis=-1)
        w = w.astype(q.dtype)
        o = jnp.einsum("bkgqs,bskd->bqkgd", w[..., :s_max], vc.astype(q.dtype))
        o = o + jnp.einsum("bkgqs,bskd->bqkgd", w[..., s_max:],
                           v_new.astype(q.dtype))
        return o.reshape(b, c_len, h, d)

    kx = _expand_kv(kc, h).astype(q.dtype)
    vx = _expand_kv(vc, h).astype(q.dtype)
    s_c = jnp.einsum("bqhd,bshd->bhqs", q, kx).astype(f32) * scale
    ki = jnp.arange(s_max).reshape(1, 1, 1, -1)
    s_c = jnp.where(ki < limit.reshape(b, 1, 1, 1), s_c, -1e30)
    kn = _expand_kv(k_new, h).astype(q.dtype)
    vn = _expand_kv(v_new, h).astype(q.dtype)
    s_n = jnp.einsum("bqhd,bshd->bhqs", q, kn).astype(f32) * scale
    if causal and c_len > 1:
        mask = (qi[None, :] <= qi[:, None]).reshape(1, 1, c_len, c_len)
        s_n = jnp.where(mask, s_n, -1e30)
    w = jax.nn.softmax(jnp.concatenate([s_c, s_n], axis=-1), axis=-1)
    w = w.astype(q.dtype)
    o = jnp.einsum("bhqs,bshd->bqhd", w[..., :s_max], vx)
    o = o + jnp.einsum("bhqs,bshd->bqhd", w[..., s_max:], vn)
    return o


def attn_decode(
    params,
    cfg: ModelConfig,
    x: jax.Array,
    layer_cache: Tuple[jax.Array, jax.Array],
    *,
    pos: jax.Array,
    mrope_positions: Optional[jax.Array] = None,
):
    """One-token decode against a READ-ONLY cache slice.

    x [B, 1, d]; layer_cache (k, v): [B, S_max, KV, D]; pos: scalar int32 OR
    per-sequence [B] int32 (continuous batching: every slot sits at its own
    length).  Returns (out, (k_new [B,1,KV,D], v_new)) — the caller writes the
    new token into the stacked cache with ONE batched dynamic-update-slice
    after the layer scan.  Updating inside the scan made XLA stack a full
    cache copy per layer as scan outputs (2 x 7 TB/chip/token measured on
    qwen2-vl-72b decode_32k; EXPERIMENTS §Perf iteration J).

    Attention = online-softmax combine of (cache positions < pos) with the
    new token at pos — identical math to write-then-attend(pos+1).
    """
    b = x.shape[0]
    pos_b = jnp.broadcast_to(jnp.reshape(jnp.asarray(pos, jnp.int32), (-1,)), (b,))
    q, k_new, v_new = _qkv(params, cfg, x, pos_b[:, None], mrope_positions)
    kc, vc = layer_cache
    o = _cached_attention(q, k_new, v_new, kc, vc, limit=pos_b, causal=False)
    o = o.reshape(b, 1, -1)
    return linear_apply(params["o"], o), (k_new, v_new)


def attn_prefill_chunk(
    params,
    cfg: ModelConfig,
    x: jax.Array,
    layer_cache: Tuple[jax.Array, jax.Array],
    *,
    start: jax.Array,
    mrope_positions: Optional[jax.Array] = None,
):
    """Chunked prefill through one layer against a preallocated cache.

    x [B, C, d] holds tokens at absolute positions [start, start+C);
    layer_cache (k, v): [B, S_max, KV, D] holds this sequence's earlier
    chunks in rows < start.  Attention = softmax over (cache rows < start)
    ++ (causal intra-chunk).  Returns (out, (k_chunk [B,C,KV,D], v_chunk));
    as with decode, the caller commits the chunk's K/V with ONE stacked
    :func:`cache_write` after the layer scan.
    """
    b, c_len = x.shape[:2]
    qi = jnp.arange(c_len, dtype=jnp.int32)
    positions = jnp.broadcast_to(start + qi[None, :], (b, c_len))
    q, k_new, v_new = _qkv(params, cfg, x, positions, mrope_positions)
    kc, vc = layer_cache
    start_b = jnp.broadcast_to(jnp.reshape(jnp.asarray(start, jnp.int32), (-1,)), (b,))
    o = _cached_attention(q, k_new, v_new, kc, vc, limit=start_b, causal=True)
    o = o.reshape(b, c_len, -1)
    return linear_apply(params["o"], o), (k_new, v_new)


def cache_write(cache_k, cache_v, k_news, v_news, pos):
    """One batched in-place write of the step's new K/V into the stacked
    cache. cache_*: [L, B, S, KV, D]; *_news: [L, B, C, KV, D] (C = 1 for
    decode, C = chunk length for chunked prefill).

    ``pos`` is the scalar row where the write starts, or a per-sequence [B]
    vector (continuous batching: every slot writes at its own length; C must
    be 1).  Starts are clamped by dynamic_update_slice semantics, so an idle
    slot parked at its last row can never write out of bounds.
    """
    pos = jnp.asarray(pos, jnp.int32)
    zero = jnp.zeros((), jnp.int32)
    if pos.ndim == 0:
        idx = (zero, zero, pos, zero, zero)
        k2 = jax.lax.dynamic_update_slice(cache_k, k_news.astype(cache_k.dtype), idx)
        v2 = jax.lax.dynamic_update_slice(cache_v, v_news.astype(cache_v.dtype), idx)
        return k2, v2

    def write1(cache, news, p):  # [L, S, KV, D], [L, C, KV, D], scalar
        return jax.lax.dynamic_update_slice(cache, news, (zero, p, zero, zero))

    k2 = jax.vmap(write1, in_axes=(1, 1, 0), out_axes=1)(
        cache_k, k_news.astype(cache_k.dtype), pos)
    v2 = jax.vmap(write1, in_axes=(1, 1, 0), out_axes=1)(
        cache_v, v_news.astype(cache_v.dtype), pos)
    return k2, v2


# ---------------------------------------------------------------------------
# Paged KV cache (repro.serve.kv_pages memory tier)
# ---------------------------------------------------------------------------


def paged_cache_init(cfg: ModelConfig, n_pages: int, page_size: int,
                     n_layers: int, dtype):
    """Physical paged cache: [L, n_pages + 1, page_size, KV * D].

    A page row holds every KV head's D values side by side, so a page is
    one clean ``[page_size, KV*D]`` tile of the TPU's 128-lane layout (the
    paged kernel's own view; see ``kernels/flash_attn/paged.py``). The
    extra page at index ``n_pages`` is the trash page — the write target
    for padded page-table entries (inactive slots, rows past a sequence's
    mapping). It may hold arbitrary junk; reads are always masked by the
    per-sequence length, so nothing ever attends to it.
    """
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    shape = (n_layers, n_pages + 1, page_size, kv * hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def page_rows(tables, seq_idx, pos, page_size: int):
    """Physical flat row index for each (sequence, position) pair.

    tables [n_slots, n_max] int32; seq_idx [N] slot per token; pos [N]
    logical position. Returns [N] int32 indices into the
    ``[P * page_size]``-row flattened view of the paged cache.
    """
    pos = jnp.asarray(pos, jnp.int32)
    page_id = tables[seq_idx, pos // page_size]
    return page_id * page_size + pos % page_size


def paged_cache_write(cache_k, cache_v, k_news, v_news, rows):
    """Scatter the step's new K/V through page-table rows.

    cache_*: [L, P, page_size, KV*D]; *_news: [L, N, KV, D]; rows: [N]
    flat physical row per token (from :func:`page_rows`). Inactive slots'
    rows all alias the trash page — duplicate scatter targets there are
    fine because those rows are never read.
    """
    l, p, ps, f = cache_k.shape
    n = k_news.shape[1]
    # one scatter over the flat [L * P * ps, F] rows: the scattered dim is
    # the major one in the cache's own layout, so XLA relays nothing out
    flat = (jnp.arange(l, dtype=jnp.int32)[:, None] * (p * ps)
            + jnp.asarray(rows, jnp.int32)[None, :]).reshape(-1)

    def write(cache, news):
        out = cache.reshape(l * p * ps, f).at[flat].set(
            news.reshape(l * n, f).astype(cache.dtype))
        return out.reshape(cache.shape)

    return write(cache_k, k_news), write(cache_v, v_news)


def paged_attn_decode(
    params,
    cfg: ModelConfig,
    x: jax.Array,
    cache: Tuple[jax.Array, jax.Array],
    *,
    layer,
    pos: jax.Array,
    tables: jax.Array,
    page_size: int,
):
    """One-token decode against a paged READ-ONLY cache.

    x [B, 1, d]; cache (k_pages, v_pages): [L, P, page_size, KV*D], every
    layer's pages, of which this is layer ``layer`` (an int32 scalar);
    pos [B] int32 per-slot lengths; tables [B, n_max] int32 page tables.
    Same no-write-in-scan contract as :func:`attn_decode` — returns
    (out, (k_new, v_new)) and the caller scatters through the page table
    once after the layer scan.
    """
    b = x.shape[0]
    pos_b = jnp.broadcast_to(jnp.reshape(jnp.asarray(pos, jnp.int32), (-1,)), (b,))
    q, k_new, v_new = _qkv(params, cfg, x, pos_b[:, None], None)
    kc, vc = cache
    from repro.kernels.flash_attn import paged_attention

    o = paged_attention(q, k_new, v_new, kc, vc, tables, pos_b, layer,
                        page_size=page_size)
    o = o.reshape(b, 1, -1)
    return linear_apply(params["o"], o), (k_new, v_new)


def packed_sdpa(q, k, v, *, seq_ids) -> jax.Array:
    """Block-diagonal causal attention over one packed token stream.

    q [1, T, H, D]; k/v [1, T, KV, D]; seq_ids [T] int32 — token t may
    attend to token s iff they share a sequence and s <= t (prompts are
    stream-contiguous with increasing positions, so stream order IS causal
    order). This is the padding-free prefill: no masked-out pad columns,
    zero wasted attention FLOPs.
    """
    b, t, h, d = q.shape
    kvh = k.shape[2]
    scale = 1.0 / math.sqrt(d)
    same = seq_ids[:, None] == seq_ids[None, :]
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    mask = same & causal
    if h % kvh == 0:
        g = h // kvh
        qg = q.reshape(b, t, kvh, g, d)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg,
                       k.astype(q.dtype)).astype(jnp.float32) * scale
        s = jnp.where(mask.reshape(1, 1, 1, t, t), s, -1e30)
        w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        o = jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(q.dtype))
        return o.reshape(b, t, h, d)
    kx = _expand_kv(k, h).astype(q.dtype)
    vx = _expand_kv(v, h).astype(q.dtype)
    s = jnp.einsum("bqhd,bshd->bhqs", q, kx).astype(jnp.float32) * scale
    s = jnp.where(mask.reshape(1, 1, t, t), s, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqs,bshd->bqhd", w, vx)


def attn_prefill_packed(params, cfg: ModelConfig, x: jax.Array, *,
                        seq_ids: jax.Array, positions: jax.Array):
    """Packed multi-prompt prefill through one layer (no cache read).

    x [1, T, d] is the concatenated stream; seq_ids/positions [T].
    Returns (out [1, T, d'], (k [1,T,KV,D], v)) — the caller scatters all
    K/V through the page tables after the layer scan.
    """
    q, k_new, v_new = _qkv(params, cfg, x, positions[None, :], None)
    o = packed_sdpa(q, k_new, v_new, seq_ids=seq_ids)
    t = x.shape[1]
    o = o.reshape(1, t, -1)
    return linear_apply(params["o"], o), (k_new, v_new)
