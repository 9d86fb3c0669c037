"""Logical-axis sharding: one rules table maps logical dimension names to
mesh axes; resolution checks divisibility per concrete dim so every arch in
the zoo (including awkward head counts) compiles on every mesh.

Model code never mentions mesh axes — it annotates logical names via ``shd``;
param trees carry logical specs in their Boxed leaves.  The launcher installs
a ``ShardingCtx``; with no context installed everything is a no-op (CPU unit
tests see single-device JAX).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# Default logical->mesh rules. 'pod' appears only in the multi-pod mesh; axes
# missing from the mesh are dropped at resolution time.
RULES: Dict[str, Tuple[str, ...]] = {
    # --- parameters ---
    "embed": ("data",),          # FSDP: shard the replicated-capable dim over data
    "ffn": ("model",),           # tensor parallel
    "heads": ("model",),
    "kv_heads": ("model",),
    "heads_flat": ("model",),    # flattened H*head_dim projection output
    "kv_flat": ("model",),
    "embed2": (),                # aux embed-sized dims (e.g. zamba fuse output)
    "head_dim": (),
    "vocab": ("model",),
    "expert": ("model",),        # expert parallel
    "tile": ("model",),          # compressed colwise-N:M tile axis == TP axis
    "kept": ("data",),           # FSDP the kept-index dim of compressed values
    "reduce_group": ("model",),  # shard-local reduce-mode group dim == TP axis
    "layers": (),
    # --- activations ---
    "act_batch": ("pod", "data"),
    "act_seq_sp": ("model",),    # Megatron-style sequence parallelism between blocks
    "act_embed": (),
    "act_heads": ("model",),
    "act_kv_heads": ("model",),
    "act_ffn": ("model",),
    "act_expert": ("model",),
    "act_moe_group": ("pod", "data"),  # MoE dispatch group dim == DP shards
    "act_kv_seq": ("data",),     # long-context decode: shard the KV seq dim
    "act_vocab": ("model",),
}


@dataclasses.dataclass
class ShardingCtx:
    mesh: Mesh
    rules: Dict[str, Tuple[str, ...]] = dataclasses.field(default_factory=lambda: dict(RULES))


_CURRENT: Optional[ShardingCtx] = None


def set_ctx(ctx: Optional[ShardingCtx]) -> None:
    global _CURRENT
    _CURRENT = ctx


def get_ctx() -> Optional[ShardingCtx]:
    return _CURRENT


def gspmd_devices() -> int:
    """Number of devices GSPMD would partition an op traced here over: the
    installed context's mesh, less the axes a ``jax.shard_map`` body has made
    manual (1 with no context installed, and inside a body manual over every
    axis, where the op is per-shard)."""
    ctx = _CURRENT
    if ctx is None:
        return 1
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    return math.prod(size for ax, size in ctx.mesh.shape.items()
                     if ax not in manual)


@contextlib.contextmanager
def use_ctx(ctx: Optional[ShardingCtx]):
    prev = get_ctx()
    set_ctx(ctx)
    try:
        yield
    finally:
        set_ctx(prev)


def resolve_spec(
    shape: Sequence[int],
    names: Sequence[Optional[str]],
    rules: Dict[str, Tuple[str, ...]],
    mesh: Mesh,
) -> P:
    """Map logical dim names to a PartitionSpec, keeping only mesh axes that
    exist and divide the dim (axes are applied left-to-right greedily)."""
    assert len(shape) == len(names), (shape, names)
    parts = []
    used: set = set()  # a mesh axis may appear at most once in a spec
    for dim, name in zip(shape, names):
        chosen: list[str] = []
        if name is not None:
            prod = 1
            for ax in rules.get(name, ()):
                if ax not in mesh.shape or ax in used:
                    continue
                size = mesh.shape[ax]
                if dim % (prod * size) == 0:
                    chosen.append(ax)
                    used.add(ax)
                    prod *= size
        parts.append(tuple(chosen) if len(chosen) > 1 else (chosen[0] if chosen else None))
    # trailing Nones can be dropped but keeping them is fine
    return P(*parts)


def shd(x: jax.Array, *names: Optional[str]) -> jax.Array:
    """Constrain an activation's sharding by logical dim names (no-op without
    an installed context)."""
    ctx = _CURRENT
    if ctx is None or x is None:
        return x
    spec = resolve_spec(x.shape, names, ctx.rules, ctx.mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


logical_constraint = shd


def specs_to_shardings(spec_tree, shape_tree, mesh: Mesh, rules=None):
    """Resolve a tree of logical specs (+ matching shapes) to NamedShardings."""
    rules = rules or RULES

    def one(spec, arr):
        shape = arr.shape if hasattr(arr, "shape") else arr
        return NamedSharding(mesh, resolve_spec(shape, spec, rules, mesh))

    return jax.tree_util.tree_map(one, spec_tree, shape_tree, is_leaf=lambda s: isinstance(s, tuple))
