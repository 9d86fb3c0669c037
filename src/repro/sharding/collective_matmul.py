"""Ring collective matmul: overlap the tensor-parallel all-gather with the
matmul it feeds (beyond-paper distributed optimization, DESIGN §5).

Standard TP computes ``y = all_gather(x) @ W_shard`` — the gather must finish
before the MXU starts.  The ring formulation keeps x sharded, multiplies the
resident shard while ppermute-ing the next shard around the ring, so
communication hides behind compute (Wang et al., "Overlap communication with
dependent computation", and the classic Cannon/SUMMA trick):

  for step in 0..n-1:
      y += x_shard @ W[block owned at this step]
      x_shard <- ppermute(x_shard)

Used inside shard_map; numerically identical to the gather-then-matmul path
(tests/test_collective_matmul.py runs it on 8 emulated devices).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def ring_allgather_matmul_local(x_shard: jax.Array, w_full: jax.Array,
                                axis_name: str) -> jax.Array:
    """Per-device body. x_shard: [B, d_in/n]; w_full: [d_in, d_out] (this
    device's full copy of its W — here W replicated for clarity; the block
    actually used rotates with the ring step). Returns [B, d_out] = x @ W.
    """
    n = jax.lax.psum(1, axis_name)
    me = jax.lax.axis_index(axis_name)
    chunk = x_shard.shape[-1]

    def body(step, carry):
        acc, xs = carry
        # shard arriving at step k originated at device (me + k) mod n and
        # holds x columns [(me+k)%n * chunk : ...]
        src = (me + step) % n
        w_blk = jax.lax.dynamic_slice_in_dim(w_full, src * chunk, chunk, axis=0)
        acc = acc + xs @ w_blk
        xs = jax.lax.ppermute(
            xs, axis_name, perm=[(i, (i - 1) % n) for i in range(n)]
        )
        return acc, xs

    acc0 = jnp.zeros((x_shard.shape[0], w_full.shape[1]), x_shard.dtype)
    acc, _ = jax.lax.fori_loop(0, n, body, (acc0, x_shard))
    return acc


def ring_allgather_matmul(x: jax.Array, w: jax.Array, mesh: Mesh,
                          axis: str = "model") -> jax.Array:
    """y = x @ w with x's feature dim sharded over `axis`, overlapping the
    gather with partial matmuls. x: [B, d_in]; w: [d_in, d_out]."""
    fn = jax.shard_map(
        functools.partial(ring_allgather_matmul_local, axis_name=axis),
        mesh=mesh,
        in_specs=(P(None, axis), P(None, None)),
        out_specs=P(None, None),
        check_vma=False,
    )
    return fn(x, w)
