"""Weights and inputs made on the device from the seed, in one jitted call
each, in the type they are served in.

The program's parameter tree gives the shapes (its ``abstract`` form); the
values are the benchmark's own: every compressed layer keeps a random
sorted set of reduction rows per column tile (the column-wise N:M support)
with normal values scaled to keep activations near unit size.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def path_str(path) -> str:
    parts = []
    for p in path:
        for attr in ("key", "idx", "name"):
            if hasattr(p, attr):
                parts.append(str(getattr(p, attr)))
                break
    return "/".join(parts)


def kept_support(key, lead: tuple, d_in: int, k_kept: int):
    """Sorted random ``k_kept`` of ``range(d_in)`` for every leading index."""
    u = jax.random.uniform(key, lead + (d_in,))
    return jnp.sort(jnp.argsort(u, axis=-1)[..., :k_kept], axis=-1) \
        .astype(jnp.int32)


def make_params(abstract, key, *, d_in_of: Callable[[str], int],
                scale_of: Callable[[str, tuple], float],
                geom_of: Callable[[str], tuple] = None):
    """Fill every leaf of ``abstract`` (a tree of ShapeDtypeStruct) by its
    role, told by the leaf's own name:

    - ``values`` and ``w``: normal, times ``scale_of(path, shape)``;
    - ``idx``: a random sorted support of ``d_in_of(path)`` rows;
    - ``b``: normal times 0.1; ``scale`` (norms): 1 + normal times 0.1;
    - ``embed`` / ``unembed``: normal times 0.02;
    - ``conv_geom``: ``geom_of(path)``.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    keys = jax.random.split(key, len(flat))
    out = []
    for (path, sds), k in zip(flat, keys):
        p = path_str(path)
        leaf = p.rsplit("/", 1)[-1]
        shape, dtype = sds.shape, sds.dtype
        if leaf in ("values", "w"):
            v = jax.random.normal(k, shape, jnp.float32) * scale_of(p, shape)
        elif leaf == "idx":
            v = kept_support(k, shape[:-1], d_in_of(p), shape[-1])
        elif leaf == "b":
            v = 0.1 * jax.random.normal(k, shape, jnp.float32)
        elif leaf == "scale":
            v = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        elif leaf in ("embed", "unembed"):
            v = 0.02 * jax.random.normal(k, shape, jnp.float32)
        elif leaf == "conv_geom":
            v = jnp.asarray(geom_of(p), jnp.int32)
        else:
            raise ValueError(f"no rule for parameter {p}")
        out.append(v.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def check_same_tree(made, abstract) -> None:
    """The made tree has the program's structure, shapes and dtypes."""
    a = jax.tree_util.tree_structure(made)
    b = jax.tree_util.tree_structure(abstract)
    if a != b:
        raise ValueError(f"parameter tree differs from the program's:\n"
                         f"{a}\nvs\n{b}")
    for x, y in zip(jax.tree.leaves(made), jax.tree.leaves(abstract)):
        if x.shape != y.shape or x.dtype != y.dtype:
            raise ValueError(f"leaf {x.shape}/{x.dtype} vs {y.shape}/{y.dtype}")

