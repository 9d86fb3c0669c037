"""Plain float32 reference of a Qwen2 decoder (arXiv:2407.10671): RMSNorm,
grouped-query attention with q/k/v biases and rotary positions (rotating
the two halves of each head), SwiGLU MLP, tied or untied unembedding.

It reads the weights the benchmark made from the seed, expanding each
column-wise compressed projection (``values``, ``idx``) back to its dense
masked matrix with its own code, and imports nothing of the program under
test.  Every matrix product runs at ``highest`` precision.  ``quant``, when
given, is applied to both operands of every product: the control computes
in a lower precision that way.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def dense_from_compressed(values, idx, d_in: int):
    """``values [n_tiles, k, T]``, ``idx [n_tiles, k]`` -> ``[d_in, n_tiles*T]``:
    kept row ``idx[t, j]`` of tile ``t`` holds ``values[t, j]``."""
    n_tiles, _k, tile = values.shape
    cols = []
    for t in range(n_tiles):
        w = jnp.zeros((d_in, tile), F32).at[idx[t]].set(values[t].astype(F32))
        cols.append(w)
    return jnp.concatenate(cols, axis=1)


def linear_weight(p: Dict, d_in: int):
    if "values" in p:
        return dense_from_compressed(p["values"], p["idx"], d_in)
    return p["w"].astype(F32)


def _ident(a):
    return a


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * scale.astype(F32)


def rope(x, positions, theta):
    """x [S, H, D]; rotates (x[..., :D/2], x[..., D/2:])."""
    d2 = x.shape[-1] // 2
    inv = 1.0 / (theta ** (np.arange(0, 2 * d2, 2, dtype=np.float64)
                           / (2 * d2)))
    ang = positions.astype(F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


class Qwen2Reference:
    """Teacher-forced forward over one token sequence."""

    def __init__(self, cfg: Dict, params, quant: Optional[Callable] = None,
                 q_block: int = 256):
        self.cfg = cfg
        self.d = cfg["hidden_size"]
        self.h = cfg["num_attention_heads"]
        self.kv = cfg["num_key_value_heads"]
        self.hd = cfg.get("head_dim") or self.d // self.h
        self.f = cfg["intermediate_size"]
        self.eps = cfg["rms_norm_eps"]
        self.theta = cfg["rope_theta"]
        self.vocab = cfg["vocab_size"]
        self.quant = quant or _ident
        self.q_block = q_block
        self.params = params
        self._layers = jax.jit(self._dense_layers)
        self._fwd = jax.jit(self._forward)

    # -- weights ----------------------------------------------------------

    def _dense_layers(self, layers):
        """Stacked dense f32 weights of every layer, expanded per layer."""
        d, f, hq = self.d, self.f, self.h * self.hd

        def one(lp):
            a, m = lp["attn"], lp["mlp"]
            return {
                "ln1": lp["ln1"]["scale"].astype(F32),
                "ln2": lp["ln2"]["scale"].astype(F32),
                "wq": linear_weight(a["q"], d), "bq": a["q"]["b"].astype(F32),
                "wk": linear_weight(a["k"], d), "bk": a["k"]["b"].astype(F32),
                "wv": linear_weight(a["v"], d), "bv": a["v"]["b"].astype(F32),
                "wo": linear_weight(a["o"], hq),
                "wg": linear_weight(m["gate"], d),
                "wu": linear_weight(m["up"], d),
                "wd": linear_weight(m["down"], f),
            }

        return jax.lax.map(one, layers)

    # -- forward ----------------------------------------------------------

    def _mm(self, a, b):
        q = self.quant
        return jnp.matmul(q(a), q(b), precision="highest")

    def _attention(self, q, k, v, n_valid):
        """Causal GQA over one sequence; q [S, H, D], k/v [S, KV, D]."""
        s = q.shape[0]
        g = self.h // self.kv
        scale = 1.0 / math.sqrt(self.hd)
        qb = self.q_block
        nb = s // qb
        kpos = jnp.arange(s)
        kk = self.quant(k)
        vv = self.quant(v)

        def block(i):
            qs = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)  # [qb, H, D]
            qs = self.quant(qs).reshape(qb, self.kv, g, self.hd)
            sc = jnp.einsum("qkgd,skd->kgqs", qs, kk,
                            precision="highest") * scale
            qpos = i * qb + jnp.arange(qb)
            mask = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < n_valid)
            sc = jnp.where(mask[None, None], sc, -1e30)
            w = jax.nn.softmax(sc, axis=-1)
            o = jnp.einsum("kgqs,skd->qkgd", self.quant(w), vv,
                           precision="highest")
            return o.reshape(qb, self.h * self.hd)

        out = jax.lax.map(block, jnp.arange(nb))
        return out.reshape(s, self.h * self.hd)

    def _forward(self, dense, embed, final_scale, tokens, n_valid):
        """Final-normed hidden states [S, d] of ``tokens`` (padded to a
        multiple of the query block; rows past ``n_valid`` are ignored)."""
        s = tokens.shape[0]
        pos = jnp.arange(s)
        x = embed[tokens].astype(F32)

        def layer(x, w):
            y = rmsnorm(x, w["ln1"], self.eps)
            q = (self._mm(y, w["wq"]) + w["bq"]).reshape(s, self.h, self.hd)
            k = (self._mm(y, w["wk"]) + w["bk"]).reshape(s, self.kv, self.hd)
            v = (self._mm(y, w["wv"]) + w["bv"]).reshape(s, self.kv, self.hd)
            q, k = rope(q, pos, self.theta), rope(k, pos, self.theta)
            x = x + self._mm(self._attention(q, k, v, n_valid), w["wo"])
            y = rmsnorm(x, w["ln2"], self.eps)
            hmid = jax.nn.silu(self._mm(y, w["wg"])) * self._mm(y, w["wu"])
            return x + self._mm(hmid, w["wd"]), None

        x, _ = jax.lax.scan(layer, x, dense)
        return rmsnorm(x, final_scale, self.eps)

    def hidden(self, tokens: np.ndarray):
        n = len(tokens)
        s = -(-n // self.q_block) * self.q_block
        padded = np.zeros((s,), np.int32)
        padded[:n] = tokens
        p = self.params
        dense = self._layers(p["layers"])
        h = self._fwd(dense, p["embed"], p["final_norm"]["scale"],
                      jnp.asarray(padded), n)
        del dense
        return h

    def unembed_matrix(self):
        p = self.params
        if "unembed" in p:
            return p["unembed"].astype(F32)
        return p["embed"].astype(F32).T


@jax.jit
def _gap(hb, tb, w):
    """Widest gap of tokens ``tb`` below the best logit of rows ``hb``."""
    lg = jnp.matmul(hb, w, precision="highest")
    best = lg.max(-1)
    return (best - jnp.take_along_axis(lg, tb[:, None], 1)[:, 0]).max()


@functools.partial(jax.jit, static_argnums=4)
def _other_gap(hb, ob, w, wq, quant):
    """Widest gap, by the logits of ``hb``, of the tokens that the
    lower-precision rows ``ob`` and weights ``wq`` put first."""
    lg = jnp.matmul(hb, w, precision="highest")
    lo = jnp.matmul(quant(ob), wq, precision="highest")
    pick = lo.argmax(-1)
    return (lg.max(-1) - jnp.take_along_axis(lg, pick[:, None], 1)[:, 0]).max()


def logit_gaps(ref: Qwen2Reference, hidden, positions: np.ndarray,
               served: np.ndarray, *, other: Optional[Qwen2Reference] = None,
               other_hidden=None, block: int = 512) -> Dict[str, float]:
    """Widest gap by which a served token's reference logit lies below the
    reference's best, over ``positions`` (the row whose logits chose
    ``served``).  With ``other`` (a lower-precision reference and its
    hidden states) also the widest gap of the token ``other`` puts first."""
    w = ref.unembed_matrix()[:, :ref.vocab]
    wq = other.quant(other.unembed_matrix()[:, :ref.vocab]) \
        if other is not None else None
    gap = other_gap = 0.0
    n = len(positions)
    for s in range(0, n, block):
        idx = np.zeros((block,), np.int32)
        tok = np.zeros((block,), np.int32)
        m = min(block, n - s)
        idx[:m] = positions[s:s + m]
        tok[:m] = served[s:s + m]
        idx[m:] = idx[0]  # repeat a compared row: adds no new gap
        tok[m:] = tok[0]
        hb = jnp.take(hidden, jnp.asarray(idx), axis=0)
        gap = max(gap, float(_gap(hb, jnp.asarray(tok), w)))
        if other is not None:
            ob = jnp.take(other_hidden, jnp.asarray(idx), axis=0)
            other_gap = max(other_gap, float(_other_gap(hb, ob, w, wq,
                                                        other.quant)))
    out = {"gap": gap}
    if other is not None:
        out["other_gap"] = other_gap
    return out


def fp8_quant(a):
    """float8_e4m3fn with one scale per tensor (the largest magnitude maps
    to the format's largest finite value), back to float32."""
    amax = jnp.max(jnp.abs(a))
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(F32) * s
