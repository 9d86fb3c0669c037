"""Mixture-of-Experts layer: top-k routing, capacity-clipped scatter dispatch,
expert-parallel sharding, and per-expert column-wise N:M pruning.

Dispatch is the sort-free scatter formulation: each (token, slot) assignment
computes its position-in-expert by a cumsum over one-hot expert ids, then
tokens are scatter-added into a [E, capacity, d] buffer (dropped tokens are
masked to zero before the scatter, so slot collisions add zeros).  This keeps
every shape static — a requirement for pjit — and lets GSPMD lower the
token->expert movement to an all-to-all over the expert-parallel axis.

The paper's technique applies per expert: every expert FFN matrix is a
SparseLinear; in compressed form the kept-index gather is vmapped over the
expert dimension.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.sparse_linear import (
    Boxed,
    forward_compressed_xla,
    forward_masked,
    linear_init,
)
from repro.sharding import shd


def _stacked_linear_init(key, e: int, d_in: int, d_out: int, cfg: ModelConfig):
    """Init an expert-stacked linear [E, ...] honoring the sparsity config."""
    scfg = cfg.sparsity
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, e)
    base = [linear_init(k, d_in, d_out, scfg, dtype=dtype, in_ax="embed", out_ax="ffn")
            for k in ks[:1]]
    # init one expert to learn the structure, then batch-init all experts with
    # a single vmapped call for speed
    def init_one(k):
        p = linear_init(k, d_in, d_out, scfg, dtype=dtype, in_ax="embed", out_ax="ffn")
        return {kk: v.value for kk, v in p.items()}

    stacked = jax.vmap(init_one)(jnp.stack(ks))
    out = {}
    for kk, spec_src in base[0].items():
        out[kk] = Boxed(stacked[kk], ("expert",) + spec_src.spec)
    return out


def _stacked_linear_apply(params, x: jax.Array) -> jax.Array:
    """x: [E, C, d_in] -> [E, C, d_out] with per-expert weights."""
    if "values" in params:
        return jax.vmap(forward_compressed_xla)(x, params["values"], params["idx"])
    if "mask" in params:
        return jax.vmap(forward_masked)(x, params["w"], params["mask"])
    return jnp.einsum("ecd,edf->ecf", x, params["w"])


def moe_init(key, cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    dtype = jnp.dtype(cfg.param_dtype)
    p = {
        "router": Boxed(
            jax.random.normal(ks[0], (d, e), jnp.float32) * (1.0 / math.sqrt(d)),
            ("embed", "expert"),
        )
    }
    if cfg.mlp_act == "swiglu":
        p["gate"] = _stacked_linear_init(ks[1], e, d, f, cfg)
    p["up"] = _stacked_linear_init(ks[2], e, d, f, cfg)
    p["down"] = _stacked_linear_init(ks[3], e, f, d, cfg)
    return p


def moe_capacity(n_tokens: int, cfg: ModelConfig) -> int:
    c = int(math.ceil(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    return max(8, -(-c // 8) * 8)  # multiple of 8 for clean tiling


def moe_apply_shard_map(params, cfg: ModelConfig, x: jax.Array,
                        router_dtype=jnp.float32):
    """Manual expert-parallel MoE via shard_map (beyond-paper, EXPERIMENTS
    §Perf cell 2 follow-up).

    Key observation: at the MoE input the activations are *replicated over
    the model axis* (they were just all-gathered for the block), so expert
    dispatch needs NO token movement at all — every device routes the full
    local-batch token set, keeps only assignments to ITS expert shard,
    computes them, and the combine is a single psum over 'model'.  This
    replaces GSPMD's f32 full-buffer dispatch all-reduces (~730 GB/chip/step
    on olmoe train_4k) with one [T_loc, d] bf16 reduction per layer.
    """
    from jax.sharding import PartitionSpec as P

    from repro.sharding import get_ctx

    ctx = get_ctx()
    mesh = ctx.mesh if ctx else None
    e, k = cfg.n_experts, cfg.top_k
    if (mesh is None or "model" not in mesh.shape or mesh.shape["model"] == 1
            or e % mesh.shape["model"] != 0):
        return moe_apply(params, cfg, x, router_dtype)
    tp = mesh.shape["model"]
    b, s, d = x.shape
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    batch_spec = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)

    ew_specs = jax.tree_util.tree_map(
        lambda l: P(*(("model",) + (None,) * (l.ndim - 1))),
        {kk: params[kk] for kk in params if kk != "router"},
    )
    in_specs = (P(batch_spec, None, None), P(None, None), ew_specs)
    out_specs = (P(batch_spec, None, None), P())

    def body(x_loc, router, ew):
        bl, sl, _ = x_loc.shape
        t = bl * sl
        xt = x_loc.reshape(t, d)
        midx = jax.lax.axis_index("model")
        e_loc = e // tp
        e_start = midx * e_loc

        logits = jnp.einsum("td,de->te", xt, router.astype(xt.dtype),
                            preferred_element_type=router_dtype)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
        # aux loss: identical on every model-peer (replicated inputs) but
        # per-data-shard tokens differ -> average over the data axes
        me = probs.mean(axis=0)
        ce = jnp.zeros((e,), router_dtype).at[top_i.reshape(-1)].add(1.0) / (t * k)
        aux = e * jnp.sum(me * ce)
        if dp_axes:
            aux = jax.lax.pmean(aux, dp_axes)

        # keep only assignments to MY experts
        ef = top_i.reshape(-1)
        mine = (ef >= e_start) & (ef < e_start + e_loc)
        el = jnp.where(mine, ef - e_start, 0)
        cap = moe_capacity(t, cfg)
        onehot = jax.nn.one_hot(el, e_loc, dtype=jnp.int32) * mine[:, None]
        pos = (jnp.cumsum(onehot, axis=0) - onehot)
        pos = jnp.take_along_axis(pos, el[:, None], axis=1)[:, 0]
        keep = mine & (pos < cap)
        xt_rep = jnp.repeat(xt, k, axis=0)
        contrib = xt_rep * keep[:, None].astype(xt.dtype)
        buf = jnp.zeros((e_loc, cap, d), xt.dtype)
        buf = buf.at[el, jnp.minimum(pos, cap - 1)].add(contrib)

        if cfg.mlp_act == "swiglu":
            h = jax.nn.silu(_stacked_linear_apply(ew["gate"], buf)) * \
                _stacked_linear_apply(ew["up"], buf)
        else:
            h = jnp.square(jax.nn.relu(_stacked_linear_apply(ew["up"], buf)))
        out_buf = _stacked_linear_apply(ew["down"], h)

        gathered = out_buf[el, jnp.minimum(pos, cap - 1)]
        gathered = gathered * keep[:, None].astype(gathered.dtype)
        w = top_p.reshape(-1)[:, None].astype(gathered.dtype)
        y_loc = (gathered * w).reshape(t, k, d).sum(axis=1)
        y = jax.lax.psum(y_loc, "model")  # the ONLY cross-expert collective
        return y.reshape(bl, sl, d), aux

    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    ew = {kk: params[kk] for kk in params if kk != "router"}
    return fn(x, params["router"], ew)


def _dispatch_group(xt, top_i, top_p, e: int, cap: int, k: int):
    """One group's scatter dispatch. xt [Tg,d]; returns (buf [E,cap,d],
    e_flat, pos, keep) — all group-local (no cross-group cumsum)."""
    tg = xt.shape[0]
    e_flat = top_i.reshape(-1)  # [Tg*K]
    onehot = jax.nn.one_hot(e_flat, e, dtype=jnp.int32)
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot
    pos = jnp.take_along_axis(pos_in_e, e_flat[:, None], axis=1)[:, 0]
    keep = pos < cap
    xt_rep = jnp.repeat(xt, k, axis=0)
    contrib = xt_rep * keep[:, None].astype(xt.dtype)
    buf = jnp.zeros((e, cap, xt.shape[1]), xt.dtype)
    buf = buf.at[e_flat, jnp.minimum(pos, cap - 1)].add(contrib)
    return buf, e_flat, pos, keep


def moe_apply(params, cfg: ModelConfig, x: jax.Array, router_dtype=jnp.float32):
    """x: [B, S, d] -> [B, S, d]; returns (y, aux_loss).

    Grouped dispatch (GSPMD/Switch pattern): tokens are split into
    ``cfg.dp`` groups matching the data-parallel shards; routing, cumsum and
    scatter are group-local (no global [T*K, E] cumsum), and the group->expert
    buffer reshard [G(data), E, C, d] -> [G, E(model), C, d] lowers to an
    all-to-all over the expert-parallel axis instead of the full-buffer
    all-reduce the naive scatter produced (measured ~730 GB/chip/step on
    olmoe train_4k; see EXPERIMENTS §Perf).
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    g = max(1, min(cfg.dp, b))
    while b % g != 0:
        g -= 1
    t = b * s
    tg = t // g
    xg = x.reshape(g, tg, d)

    # bf16 operands + f32 accumulation: an f32 *copy* of the activations here
    # costs a [T, d] f32 all-gather in the backward (measured 77 GB/chip/step)
    logits = jnp.einsum(
        "gtd,de->gte", xg, params["router"].astype(xg.dtype),
        preferred_element_type=router_dtype,
    )
    probs = jax.nn.softmax(logits, axis=-1)  # [G, Tg, E]
    top_p, top_i = jax.lax.top_k(probs, k)  # [G, Tg, K]
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # load-balancing auxiliary loss (Switch-style), averaged over groups
    me = probs.mean(axis=1)  # [G, E]
    ce = jax.vmap(
        lambda ti: jnp.zeros((e,), router_dtype).at[ti.reshape(-1)].add(1.0) / (tg * k)
    )(top_i)
    aux = e * jnp.sum(me * ce, axis=-1).mean()

    cap = moe_capacity(tg, cfg)
    buf, e_flat, pos, keep = jax.vmap(
        lambda xx, ti, tp: _dispatch_group(xx, ti, tp, e, cap, k)
    )(xg, top_i, top_p)
    # group-sharded -> expert-sharded: this boundary is the all-to-all
    buf = shd(buf, None, "act_expert", None, None)

    # --- expert FFN (per-expert SparseLinear), batched over groups ---
    apply_e = lambda prm, z: jax.vmap(_stacked_linear_apply, in_axes=(None, 0))(prm, z)
    if cfg.mlp_act == "swiglu":
        h = jax.nn.silu(apply_e(params["gate"], buf)) * apply_e(params["up"], buf)
    else:
        h = jnp.square(jax.nn.relu(apply_e(params["up"], buf)))
    h = shd(h, None, "act_expert", None, None)
    out_buf = apply_e(params["down"], h)  # [G, E, C, d]
    # expert-sharded -> group-sharded: the return all-to-all
    out_buf = shd(out_buf, "act_moe_group", None, None, None)

    def combine(ob, ef, ps, kp, tp):
        gathered = ob[ef, jnp.minimum(ps, cap - 1)]
        gathered = gathered * kp[:, None].astype(ob.dtype)
        w = tp.reshape(-1)[:, None].astype(ob.dtype)
        return (gathered * w).reshape(tg, k, d).sum(axis=1)

    y = jax.vmap(combine)(out_buf, e_flat, pos, keep, top_p)  # [G, Tg, d]
    return y.reshape(b, s, d), aux
