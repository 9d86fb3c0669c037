"""Plain float32 reference of masked finetuning steps of a Qwen2 decoder
whose projections are stored column-wise compressed: the next-token loss,
its gradients with respect to every floating parameter (the kept
``values`` of each compressed projection, biases, norms, embedding and
head), and AdamW with clipping by the global norm.

The parameters are the tree the benchmark made from the seed (the
program's layout: ``layers`` stacked over depth, ``attn``/``mlp``
projections).  Column-parallel projections hold ``values [t, k, T]`` and
``idx [t, k]``: kept row ``idx[t, j]`` of column tile ``t`` holds
``values[t, j]``.  Row-parallel ones (``o``, ``down``) hold the REDUCE
format, ``values_r [G, n, d_out]`` and ``idx_r [G, n]``, whose indices are
local to each of the ``G`` groups of ``d_in / G`` consecutive input rows.
Both are expanded to dense matrices here with this module's own code.

It imports nothing of the program under test.  Every product runs at
``highest`` precision.  The gradients come from ``jax.grad`` through a
scan over the layers with each layer rematerialised, so the backward pass
runs layer by layer and only the layers' inputs are kept.  ``quant``,
when given, applies to both operands of every product, and to the
incoming gradient and the saved operand of each product's backward: the
control computes in a lower precision that way.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.refs.qwen2 import F32, fp8_quant, rmsnorm, rope

HIGHEST = jax.lax.Precision.HIGHEST
#: the control's precision, one below bfloat16: float8 e4m3, one scale a
#: tensor
CONTROL_QUANT = fp8_quant


@dataclasses.dataclass(frozen=True)
class AdamW:
    lr: float
    b1: float
    b2: float
    eps: float
    weight_decay: float
    grad_clip: float

    @classmethod
    def from_traffic(cls, tr: Dict) -> "AdamW":
        return cls(**tr["adamw"])


# ---------------------------------------------------------------------------
# compressed formats -> dense
# ---------------------------------------------------------------------------


def expand_tiles(values, idx, d_in: int):
    """``values [t, k, T]``, ``idx [t, k]`` -> ``[t, d_in, T]``."""
    return jax.vmap(lambda v, i: jnp.zeros((d_in, v.shape[-1]), F32)
                    .at[i].set(v.astype(F32)))(values, idx)


def expand_groups(values_r, idx_r, d_in: int):
    """``values_r [G, n, d_out]``, ``idx_r [G, n]`` (group-local) ->
    ``[G, d_in / G, d_out]``: row ``m`` of group ``g`` is input row
    ``g * d_in / G + m``."""
    m = d_in // values_r.shape[0]
    return jax.vmap(lambda v, i: jnp.zeros((m, v.shape[-1]), F32)
                    .at[i].set(v.astype(F32)))(values_r, idx_r)


# ---------------------------------------------------------------------------
# products
# ---------------------------------------------------------------------------


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def quantized_einsum(quant: Callable):
    """``einsum(spec, quant(a), quant(b))`` whose backward quantizes the
    incoming gradient too (every operand of every product, forward and
    backward, in the lower precision)."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def qe(spec, a, b):
        return _einsum(spec, quant(a), quant(b))

    def fwd(spec, a, b):
        qa, qb = quant(a), quant(b)
        return _einsum(spec, qa, qb), (qa, qb)

    def bwd(spec, res, dy):
        qa, qb = res
        _, vjp = jax.vjp(lambda x, y: _einsum(spec, x, y), qa, qb)
        return vjp(quant(dy))

    qe.defvjp(fwd, bwd)
    return qe


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


class Qwen2TrainReference:
    """Loss, gradients and AdamW steps of ``cfg`` in float32."""

    def __init__(self, cfg: Dict, adamw: AdamW,
                 quant: Optional[Callable] = None):
        self.d = cfg["hidden_size"]
        self.h = cfg["num_attention_heads"]
        self.kv = cfg["num_key_value_heads"]
        self.hd = cfg.get("head_dim") or self.d // self.h
        self.f = cfg["intermediate_size"]
        self.eps = cfg["rms_norm_eps"]
        self.theta = cfg["rope_theta"]
        self.hp = adamw
        self.quant = quant
        self.mm = quantized_einsum(quant) if quant else _einsum
        self.step = jax.jit(self._step, donate_argnums=(0, 1, 2))

    # -- forward -------------------------------------------------------------

    def tiled(self, x, p, d_in):
        w = expand_tiles(p["values"], p["idx"], d_in)
        y = self.mm("bsd,tdf->bstf", x, w)
        y = y.reshape(*y.shape[:2], -1)
        return y + p["b"].astype(F32) if "b" in p else y

    def reduce(self, x, p, d_in, group_w):
        """``group_w [G]`` weighs each group's partial sum (all ones: the
        whole product)."""
        w = expand_groups(p["values_r"], p["idx_r"], d_in) \
            * group_w[:, None, None]
        xg = x.reshape(*x.shape[:2], w.shape[0], -1)
        return self.mm("bsgm,gmf->bsf", xg, w)

    def attention(self, q, k, v):
        """Causal grouped-query attention; q [B, S, H, D], k/v [B, S, KV, D]."""
        b, s = q.shape[:2]
        g = self.h // self.kv
        qg = q.reshape(b, s, self.kv, g, self.hd)
        sc = self.mm("bqkgd,bskd->bkgqs", qg, k) / math.sqrt(self.hd)
        causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        sc = jnp.where(causal, sc, -1e30)
        w = jax.nn.softmax(sc, axis=-1)
        o = self.mm("bkgqs,bskd->bqkgd", w, v)
        return o.reshape(b, s, self.h * self.hd)

    def layer(self, x, lp, group_w):
        b, s, _ = x.shape
        pos = jnp.arange(s)
        a, m = lp["attn"], lp["mlp"]
        y = rmsnorm(x, lp["ln1"]["scale"], self.eps)
        q = self.tiled(y, a["q"], self.d).reshape(b, s, self.h, self.hd)
        k = self.tiled(y, a["k"], self.d).reshape(b, s, self.kv, self.hd)
        v = self.tiled(y, a["v"], self.d).reshape(b, s, self.kv, self.hd)
        q, k = rope(q, pos, self.theta), rope(k, pos, self.theta)
        x = x + self.reduce(self.attention(q, k, v), a["o"],
                            self.h * self.hd, group_w)
        y = rmsnorm(x, lp["ln2"]["scale"], self.eps)
        hmid = jax.nn.silu(self.tiled(y, m["gate"], self.d)) \
            * self.tiled(y, m["up"], self.d)
        return x + self.reduce(hmid, m["down"], self.f, group_w)

    def _loss(self, params, tokens, row_w, group_w):
        """Mean next-token cross-entropy of each row, weighed by ``row_w``
        (all ``1/B``: the batch's mean)."""
        x = params["embed"].astype(F32)[tokens]

        def body(x, lp):
            return self.layer(x, lp, group_w), None

        x, _ = jax.lax.scan(jax.checkpoint(body), x, params["layers"])
        hid = rmsnorm(x, params["final_norm"]["scale"], self.eps)
        logits = self.mm("bsd,dv->bsv", hid[:, :-1], params["unembed"])
        labels = tokens[:, 1:]
        gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        nll = jax.nn.logsumexp(logits, axis=-1) - gold
        return (nll.mean(-1) * row_w).sum()

    # -- optimizer -----------------------------------------------------------

    def _step(self, params, m, v, tokens, row_w, group_w, step):
        """One step (``step`` counts from 1): the loss and its gradients,
        then AdamW: clip by the global norm, moments, bias correction,
        decoupled weight decay on every floating leaf.  Returns the new
        parameters and moments, the loss, the gradient's norm before
        clipping and the per-leaf norms of the clipped gradient."""
        loss, grads = jax.value_and_grad(self._loss, allow_int=True)(
            params, tokens, row_w, group_w)
        hp = self.hp
        fl = [g for g, p in zip(jax.tree.leaves(grads), jax.tree.leaves(params))
              if is_float(p)]
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in fl))
        scale = jnp.minimum(1.0, hp.grad_clip / jnp.maximum(gnorm, 1e-9))
        t = step.astype(F32)
        b1c, b2c = 1.0 - hp.b1 ** t, 1.0 - hp.b2 ** t

        def upd(p, g, m_, v_):
            if not is_float(p):
                return p, m_, v_, g
            g = g.astype(F32) * scale
            m2 = hp.b1 * m_ + (1 - hp.b1) * g
            v2 = hp.b2 * v_ + (1 - hp.b2) * g * g
            new = p - hp.lr * ((m2 / b1c) / (jnp.sqrt(v2 / b2c) + hp.eps)
                               + hp.weight_decay * p)
            return new, m2, v2, g

        out = jax.tree.map(upd, params, grads, m, v)
        treedef = jax.tree.structure(params)
        cols = [treedef.unflatten(c) for c in zip(*treedef.flatten_up_to(out))]
        return cols[0], cols[1], cols[2], loss, gnorm, leaf_norms(cols[3])


def is_float(a) -> bool:
    return jnp.issubdtype(a.dtype, jnp.floating)


# ---------------------------------------------------------------------------
# per-leaf norms: the numbers compared
# ---------------------------------------------------------------------------


def leaf_names(tree) -> List[str]:
    """One name per floating leaf, one per layer for the stacked
    ``layers`` leaves: the order of ``leaf_norms``."""
    names = []
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if not is_float(a):
            continue
        p = jax.tree_util.keystr(path)
        if p.startswith("['layers']"):
            names += [f"{p}[{i}]" for i in range(a.shape[0])]
        else:
            names.append(p)
    return names


def leaf_norms(tree):
    """L2 norm of every floating leaf, each layer of a stacked leaf apart,
    as one vector in the order of ``leaf_names``."""
    out = []
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if not is_float(a):
            continue
        a = a.astype(F32)
        if jax.tree_util.keystr(path).startswith("['layers']"):
            out.append(jnp.sqrt(jnp.sum(jnp.square(a),
                                        axis=tuple(range(1, a.ndim)))))
        else:
            out.append(jnp.sqrt(jnp.sum(jnp.square(a)))[None])
    return jnp.concatenate(out)


def moments(params):
    """Zero moments, laid out as ``params`` on the devices (a placeholder
    for an integer leaf, which does not train)."""
    return jax.tree.map(
        lambda a: jnp.zeros(a.shape, F32, device=a.sharding) if is_float(a)
        else jnp.zeros((1,), jnp.int8), params)


def diff_norms(new, old):
    return leaf_norms(jax.tree.map(
        lambda a, b: a - b if is_float(a) else a, new, old))


# ---------------------------------------------------------------------------
# readings of a few steps
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Readings:
    """What the first steps of a run give, on the program's side or the
    reference's: the loss of each step, the first gradient's global norm
    before clipping, the per-leaf norms of the first gradient as the
    optimizer applied it (clipped) and of the parameters' change over the
    steps."""

    losses: List[float]
    grad_norm: float
    grad_leaf: np.ndarray
    change_leaf: np.ndarray


def run_steps(ref: Qwen2TrainReference, params, batches: Sequence, remake,
              *, row_w=None, group_w=None) -> Readings:
    """``len(batches)`` AdamW steps of ``ref`` from ``params`` (which it
    consumes).  ``remake()`` makes the starting parameters again, for the
    change.  ``row_w`` and ``group_w`` default to the plain step."""
    b = batches[0].shape[0]
    row_w = jnp.full((b,), 1.0 / b, F32) if row_w is None else row_w
    first = jax.tree.leaves(params["layers"]["attn"]["o"])[0]
    group_w = jnp.ones((first.shape[1],), F32) if group_w is None else group_w
    m, v = moments(params), moments(params)
    losses, gnorm0, g0 = [], None, None
    for i, tokens in enumerate(batches):
        params, m, v, loss, gnorm, gl = ref.step(params, m, v, tokens, row_w,
                                                 group_w, jnp.int32(i + 1))
        losses.append(float(loss))
        if i == 0:
            gnorm0, g0 = float(gnorm), np.asarray(gl)
    del m, v
    change = np.asarray(jax.jit(diff_norms)(params, remake()))
    return Readings(losses, gnorm0, g0, change)


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

#: leaves whose reference gradient is under this share of the median
#: leaf's move under Adam by round-off alone (a key's bias under softmax)
#: and are left out of the change
STILL_LEAF = 1e-3


def compare(got: Readings, want: Readings, names: Sequence[str]) -> Dict:
    """The numbers compared, each a relative gap, with the leaf that set
    the two per-leaf ones."""
    losses = [abs(a - b) / abs(b) for a, b in zip(got.losses, want.losses)]
    gmed = float(np.median(want.grad_leaf))
    g_gap = np.abs(got.grad_leaf - want.grad_leaf) \
        / np.maximum(want.grad_leaf, gmed)
    moved = want.grad_leaf >= STILL_LEAF * gmed
    cmed = float(np.median(want.change_leaf[moved]))
    c_gap = np.where(moved, np.abs(got.change_leaf - want.change_leaf)
                     / np.maximum(want.change_leaf, cmed), 0.0)
    gi, ci = int(np.argmax(g_gap)), int(np.argmax(c_gap))
    return {
        "loss_rel_err": max(losses),
        "grad_norm_rel_err": abs(got.grad_norm - want.grad_norm)
        / want.grad_norm,
        "grad_leaf_gap": float(g_gap[gi]),
        "change_leaf_gap": float(c_gap[ci]),
        "worst": {"grad_leaf_gap": names[gi], "change_leaf_gap": names[ci]},
        "still_leaves": [n for n, k in zip(names, moved) if not k],
    }
