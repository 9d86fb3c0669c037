"""Operator registry: the candidate implementations behind each logical op.

AITemplate keeps, per operator, a list of generated kernels plus a profiler
that races them on the target; TensorRT-LLM hides per-phase implementations
behind one operator facade.  This registry is the analogous single
registration point for this repo: a logical op (``linear``, ``conv``) maps to
a list of :class:`ImplSpec` candidates, each declaring

  * ``requires``   — which param-dict keys it can execute from (a compressed
    layer can only run compressed candidates; a dense layer only dense ones),
  * ``feasible``   — a static predicate over the :class:`OpKey` (VMEM budget,
    divisibility, backend availability) returning (ok, reason),
  * ``vmem_bytes`` — analytic footprint used for tie-breaks and fallbacks,
  * ``apply``      — how to execute the layer's params on an input,
  * ``make_bench`` — how to synthesize a self-contained benchmark closure for
    the profiler (operands built from the key alone, no real params needed),
  * ``geometry``   — the execution-geometry knobs (block sizes, strip width)
    this variant is pinned to.

Execution geometry lives IN the candidate space: a Pallas kernel registers
one candidate per point of its geometry grid (``compressed_pallas`` plus
``compressed_pallas@bb256_bk128`` …, ``fused_sparse_pallas`` plus
``fused_sparse_pallas@v256_bk128`` …), each with its own VMEM predicate, so a
single ``profile_op`` pass picks implementation AND geometry jointly and
bakes both into one profile-DB record.  This replaced the seed's separate
``Tuner`` tier (tile × block_b × block_k), which survives only as a
deprecated compatibility shim.

New kernels/backends register here once and every call site that consults
``repro.dispatch.best_impl`` picks them up — no per-call-site if/else chains.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

VMEM_BYTES = 16 * 2 ** 20  # ~16 MB usable per TPU core (paper §3.3 analog)


def bucket_batch(n: int) -> int:
    """Round a leading-dim size up to a power of two (min 8) so the profile
    DB is keyed by a bounded family of batch buckets, not every exact size."""
    b = 8
    while b < n:
        b *= 2
    return b


def bucket_dim(n: int) -> int:
    """Power-of-two bucket for the reduction dim of linear keys.  Both the
    trace-time call site (which knows the exact d_in from the activation) and
    the build-time params scan (which can only bound d_in by max kept index)
    land in the same bucket, so their DB tokens agree."""
    return bucket_batch(n)


@dataclasses.dataclass(frozen=True)
class OpKey:
    """Hashable identity of one operator instance (static shapes only)."""

    op: str          # "linear" | "conv"
    batch: int       # bucketed leading-dim rows (GEMM) / output positions (conv)
    d_in: int        # reduction dim (linear) / kh*kw*c (conv)
    d_out: int
    k_kept: int      # kept reduction indices per tile (== d_in when dense)
    tile: int        # output-feature tile width sharing one index set
    dtype: str = "f32"
    extra: Tuple[Tuple[str, int], ...] = ()
    # serving-phase tag ("prefill" | "decode"); "" = phase-agnostic.  The same
    # layer weights see [B*S]-row operands during prefill and [B]-row operands
    # during decode, and the profiled winner differs between the two shapes
    # (TensorRT-LLM-style per-phase operator specialization), so phase-tagged
    # keys get distinct profile-DB entries.  Untagged keys keep the exact
    # pre-phase token format, so existing DBs stay valid.
    phase: str = ""

    @property
    def token(self) -> str:
        """Stable string key for the profile DB."""
        base = (f"{self.op}|b{self.batch}|i{self.d_in}|o{self.d_out}"
                f"|k{self.k_kept}|t{self.tile}|{self.dtype}")
        for k, v in self.extra:
            base += f"|{k}{v}"
        if self.phase:
            base += f"|ph:{self.phase}"
        return base

    def get(self, name: str, default: int = 0) -> int:
        for k, v in self.extra:
            if k == name:
                return v
        return default


def _dtype_tag(dtype) -> str:
    import numpy as np

    try:
        name = np.dtype(dtype).name  # accepts instances, classes, strings
    except TypeError:
        name = str(dtype)
    return {"float32": "f32", "bfloat16": "bf16", "float16": "f16"}.get(
        name, name)


def _mesh_extra() -> Tuple[Tuple[str, int], ...]:
    """``(("mesh", n),)`` for an op that GSPMD would partition over ``n > 1``
    devices, else ``()`` (single-device tokens keep their format).  GSPMD
    does not partition Mosaic kernels, so the Pallas predicates refuse such
    keys, and the profile DB keeps them apart.  An op inside a
    ``jax.shard_map`` body manual over every mesh axis is per-shard and
    keys like a single device: the compressed linear runs so under a mesh
    (``core.sparse_linear.linear_apply``)."""
    from repro.sharding.api import gspmd_devices

    n = gspmd_devices()
    return (("mesh", n),) if n > 1 else ()


def linear_key(batch: int, d_in: int, d_out: int, k_kept: int, tile: int,
               dtype="float32", phase: str = "") -> OpKey:
    return OpKey(op="linear", batch=bucket_batch(batch), d_in=bucket_dim(d_in),
                 d_out=d_out, k_kept=k_kept, tile=tile, dtype=_dtype_tag(dtype),
                 extra=_mesh_extra(), phase=phase)


def linear_key_from(x_shape: Sequence[int], values_shape: Sequence[int],
                    dtype="float32", phase: str = "") -> OpKey:
    """OpKey from an activation shape and a compressed values shape.

    ``values_shape`` may carry scan/stacked leading dims; only the trailing
    [n_tiles, k_kept, tile] matter for dispatch.
    """
    n_tiles, k_kept, tile = values_shape[-3:]
    rows = 1
    for s in x_shape[:-1]:
        rows *= int(s)
    return linear_key(max(rows, 1), int(x_shape[-1]), int(n_tiles * tile),
                      int(k_kept), int(tile), dtype, phase=phase)


def conv_key(c: int, h: int, w: int, o: int, kh: int, kw: int, stride: int,
             pad: int, k_kept: int, tile: int, v: int = 128,
             dtype="float32", batch: int = 1, phase: str = "") -> OpKey:
    """OpKey for a conv operator instance.  ``phase`` mirrors ``linear_key``:
    a conv traced inside ``dispatch.phase_scope`` gets a phase-tagged token
    (and hence its own profile-DB entry) instead of silently profiling
    phase-agnostic."""
    n_pos_h = (h + 2 * pad - kh) // stride + 1
    n_pos_w = (w + 2 * pad - kw) // stride + 1
    return OpKey(
        op="conv", batch=bucket_batch(max(batch * n_pos_h * n_pos_w, 1)),
        d_in=kh * kw * c, d_out=o, k_kept=k_kept, tile=tile,
        dtype=_dtype_tag(dtype),
        extra=(("b", batch), ("c", c), ("h", h), ("w", w), ("kh", kh),
               ("kw", kw), ("s", stride), ("p", pad), ("v", v))
        + _mesh_extra(),
        phase=phase,
    )


@dataclasses.dataclass(frozen=True)
class ImplSpec:
    """One candidate implementation of a logical op.

    A Pallas kernel family registers one ImplSpec per execution-geometry
    point (``geometry`` carries the block sizes / strip width the variant is
    pinned to; the default-geometry variant keeps the bare family name, the
    rest get an ``@k1v1_k2v2`` suffix via :func:`geometry_name`), so the
    profiler selects implementation and geometry in one pass.
    """

    name: str
    op: str
    backend: str                       # "xla" | "pallas"
    requires: frozenset                # param keys it executes from
    priority: int                      # heuristic rank (lower preferred)
    feasible: Callable[[OpKey], Tuple[bool, str]]
    vmem_bytes: Callable[[OpKey], int]
    apply: Optional[Callable] = None   # (params, x, **op_args) -> y
    make_bench: Optional[Callable] = None  # key -> zero-arg timed closure
    geometry: Tuple[Tuple[str, int], ...] = ()

    def geom(self, name: str, default: int = 0) -> int:
        for k, v in self.geometry:
            if k == name:
                return v
        return default

    def __repr__(self):
        return f"ImplSpec({self.op}:{self.name}, backend={self.backend})"


def geometry_name(base: str, geometry: Tuple[Tuple[str, int], ...],
                  default: Tuple[Tuple[str, int], ...]) -> str:
    """Candidate name for one geometry point: the default geometry keeps the
    bare family name (profile-DB/force back-compat), others get a suffix like
    ``base@bb256_bk128``."""
    if geometry == default:
        return base
    return base + "@" + "_".join(f"{k}{v}" for k, v in geometry)


class OperatorRegistry:
    def __init__(self):
        self._impls: Dict[str, Dict[str, ImplSpec]] = {}
        self.generation = 0  # bumped on register(); invalidates dispatch memos

    def register(self, spec: ImplSpec) -> ImplSpec:
        self._impls.setdefault(spec.op, {})[spec.name] = spec
        self.generation += 1
        return spec

    def ops(self) -> List[str]:
        return sorted(self._impls)

    def get(self, op: str, name: str) -> ImplSpec:
        try:
            return self._impls[op][name]
        except KeyError:
            known = sorted(self._impls.get(op, {}))
            raise KeyError(
                f"no impl {name!r} registered for op {op!r}; known: {known}"
            ) from None

    def candidates(self, op: str, *, param_keys=None) -> List[ImplSpec]:
        """All candidates for an op, optionally filtered to those executable
        from a given param-dict key set.

        Only *most-specific* matches are kept: a candidate whose ``requires``
        is a strict subset of another executable candidate's is dropped, so
        e.g. ``dense`` (requires {w}) can never be selected for a masked
        layer ({w, mask}) and silently ignore the mask.
        """
        specs = list(self._impls.get(op, {}).values())
        if param_keys is not None:
            pk = frozenset(param_keys)
            specs = [s for s in specs if s.requires <= pk]
            specs = [s for s in specs
                     if not any(s.requires < o.requires for o in specs)]
        return specs

    def feasible(self, key: OpKey, *, param_keys=None) -> List[ImplSpec]:
        return [s for s in self.candidates(key.op, param_keys=param_keys)
                if s.feasible(key)[0]]


REGISTRY = OperatorRegistry()


# ---------------------------------------------------------------------------
# Built-in linear candidates
# ---------------------------------------------------------------------------


def _always(key: OpKey) -> Tuple[bool, str]:
    return True, "ok"


def _no_vmem(key: OpKey) -> int:
    return 0


# Per-op geometry grids.  Each point becomes one registered candidate; the
# first entry is the default geometry and keeps the bare family name.
LINEAR_GEOMETRY = (
    (("bb", 128), ("bk", 128)),
    (("bb", 256), ("bk", 128)),
    (("bb", 128), ("bk", 64)),
)
FUSED_CONV_GEOMETRY = (
    (("v", 128), ("bk", 128)),
    (("v", 256), ("bk", 128)),
    (("v", 128), ("bk", 64)),
)
# Banded/pipelined conv plans: strip width x block_k x band depth ``hb``
# (strips per double-buffered DMA — row band for the banded megakernel,
# strip chunk for the pipelined two-kernel GEMM).  Shallow bands minimize
# VMEM, deep bands amortize DMA issue overhead; the profiler picks.
BANDED_CONV_GEOMETRY = (
    (("v", 128), ("bk", 128), ("hb", 2)),
    (("v", 256), ("bk", 128), ("hb", 2)),
    (("v", 128), ("bk", 128), ("hb", 4)),
    (("v", 128), ("bk", 64), ("hb", 1)),
)


def _key_itemsize(key: OpKey) -> int:
    """Operand byte width from the key's dtype tag (f32 maps under-count VMEM
    2x if assumed bf16 — load-bearing for the whole-map-resident megakernel)."""
    return 4 if key.dtype == "f32" else 2


def _unpartitioned(key: OpKey) -> Tuple[bool, str]:
    """Pallas candidates run only where no GSPMD partitioning is asked of
    them (the compiler: "Mosaic kernels cannot be automatically
    partitioned")."""
    n = key.get("mesh", 1)
    if n > 1:
        return False, (f"Mosaic kernels cannot be automatically partitioned "
                       f"(traced under a {n}-device sharded mesh)")
    return True, "ok"


def _tile_ok(key: OpKey) -> Tuple[bool, str]:
    ok, reason = _unpartitioned(key)
    if not ok:
        return ok, reason
    if key.d_out % key.tile != 0:
        return False, f"d_out={key.d_out} not divisible by tile={key.tile}"
    if key.tile % 8 != 0:
        return False, f"tile={key.tile} not a multiple of 8 (sublane)"
    return True, "ok"


def _pallas_vmem_for(block_b: int, block_k: int):
    def vm(key: OpKey) -> int:
        from repro.kernels.colwise_nm.kernel import vmem_bytes

        return vmem_bytes(min(block_b, key.batch), min(block_k, key.k_kept),
                          key.d_in, min(key.tile, 512),
                          in_bytes=_key_itemsize(key))

    return vm


def _pallas_feasible_for(block_b: int, block_k: int):
    vm_fn = _pallas_vmem_for(block_b, block_k)

    def feasible(key: OpKey) -> Tuple[bool, str]:
        ok, reason = _tile_ok(key)
        if not ok:
            return ok, reason
        vm = vm_fn(key)
        if vm > VMEM_BYTES:
            return False, f"VMEM {vm} > budget {VMEM_BYTES}"
        return True, "ok"

    return feasible


# default-geometry predicates (shared by the strip-major conv candidate)
_pallas_feasible = _pallas_feasible_for(128, 128)
_pallas_vmem = _pallas_vmem_for(128, 128)


def _jnp_dtype(tag: str):
    import jax.numpy as jnp

    return {"f32": jnp.float32, "bf16": jnp.bfloat16,
            "f16": jnp.float16}.get(tag, jnp.float32)


def _rand(shape, seed=0, dtype_tag: str = "f32"):
    import jax

    x = jax.random.normal(jax.random.PRNGKey(seed), shape)
    return x.astype(_jnp_dtype(dtype_tag))


def _synth_compressed(key: OpKey):
    """Strided synthetic (values, idx) matching the key's geometry/dtype."""
    import jax.numpy as jnp

    n_tiles = key.d_out // key.tile
    values = _rand((n_tiles, key.k_kept, key.tile), seed=1,
                   dtype_tag=key.dtype) / (key.k_kept ** 0.5)
    values = values.astype(_jnp_dtype(key.dtype))
    stride = max(key.d_in // key.k_kept, 1)
    idx1 = (jnp.arange(key.k_kept, dtype=jnp.int32) * stride) % key.d_in
    idx = jnp.broadcast_to(jnp.sort(idx1)[None, :], (n_tiles, key.k_kept))
    return values, jnp.asarray(idx, jnp.int32)


def _bench_linear_xla(key: OpKey):
    import jax

    from repro.core.sparse_linear import forward_compressed_xla

    x = _rand((key.batch, key.d_in), dtype_tag=key.dtype)
    values, idx = _synth_compressed(key)
    f = jax.jit(lambda x: forward_compressed_xla(x, values, idx))
    return lambda: f(x)


def _bench_linear_pallas(key: OpKey, block_b: int = 128, block_k: int = 128):
    import jax

    from repro.kernels.colwise_nm import ops as cops

    x = _rand((key.batch, key.d_in), dtype_tag=key.dtype)
    values, idx = _synth_compressed(key)
    # jitted like every other candidate's closure: profiling must compare
    # steady-state (traced) execution, not eager per-op dispatch overhead
    f = jax.jit(lambda x: cops.colwise_nm_matmul(x, values, idx,
                                                 block_b=block_b,
                                                 block_k=block_k))
    return lambda: f(x)


def _bench_linear_dense(key: OpKey):
    import jax

    x = _rand((key.batch, key.d_in), dtype_tag=key.dtype)
    w = _rand((key.d_in, key.d_out), seed=2, dtype_tag=key.dtype) / (key.d_in ** 0.5)
    f = jax.jit(lambda x: x @ w)
    return lambda: f(x)


def _apply_linear_xla(params, x):
    from repro.core.sparse_linear import forward_compressed_xla

    return forward_compressed_xla(x, params["values"], params["idx"])


def _apply_linear_pallas(params, x, block_b: int = 128, block_k: int = 128):
    from repro.kernels.colwise_nm import ops as cops

    return cops.colwise_nm_matmul(x, params["values"], params["idx"],
                                  block_b=block_b, block_k=block_k)


def _apply_linear_masked(params, x):
    from repro.core.sparse_linear import forward_masked

    return forward_masked(x, params["w"], params["mask"])


def _apply_linear_dense(params, x):
    return x @ params["w"]


REGISTRY.register(ImplSpec(
    name="compressed_xla", op="linear", backend="xla",
    requires=frozenset({"values", "idx"}), priority=10,
    feasible=_always, vmem_bytes=_no_vmem,
    apply=_apply_linear_xla, make_bench=_bench_linear_xla,
))

# one candidate per geometry point — profile_op races them all, so a single
# profiling pass picks implementation AND block geometry jointly
for _geom in LINEAR_GEOMETRY:
    _bb, _bk = dict(_geom)["bb"], dict(_geom)["bk"]
    REGISTRY.register(ImplSpec(
        name=geometry_name("compressed_pallas", _geom, LINEAR_GEOMETRY[0]),
        op="linear", backend="pallas",
        requires=frozenset({"values", "idx"}), priority=10,
        feasible=_pallas_feasible_for(_bb, _bk),
        vmem_bytes=_pallas_vmem_for(_bb, _bk),
        apply=functools.partial(_apply_linear_pallas, block_b=_bb, block_k=_bk),
        make_bench=functools.partial(_bench_linear_pallas, block_b=_bb,
                                     block_k=_bk),
        geometry=_geom,
    ))

REGISTRY.register(ImplSpec(
    name="masked", op="linear", backend="xla",
    requires=frozenset({"w", "mask"}), priority=20,
    feasible=_always, vmem_bytes=_no_vmem,
    apply=_apply_linear_masked, make_bench=_bench_linear_dense,
))

REGISTRY.register(ImplSpec(
    name="dense", op="linear", backend="xla",
    requires=frozenset({"w"}), priority=30,
    feasible=_always, vmem_bytes=_no_vmem,
    apply=_apply_linear_dense, make_bench=_bench_linear_dense,
))


# ---------------------------------------------------------------------------
# Built-in conv candidates (GEMM view: [P, KhKwC] x [KhKwC, O])
# ---------------------------------------------------------------------------


def _synth_conv_input(key: OpKey):
    c, h, w = key.get("c"), key.get("h"), key.get("w", key.get("h"))
    b = max(key.get("b", 1), 1)
    return _rand((c, b, h, w), seed=3, dtype_tag=key.dtype)


def _conv_args(key: OpKey):
    return dict(kh=key.get("kh"), kw=key.get("kw"), stride=key.get("s", 1),
                pad=key.get("p", 0), v=key.get("v", 128))


def _bench_conv_dense(key: OpKey):
    import jax

    from repro.kernels.conv_gemm.ref import conv2d_cnhw_ref

    x = _synth_conv_input(key)
    a = _conv_args(key)
    wt = _rand((key.d_out, a["kh"], a["kw"], key.get("c")), seed=4,
                dtype_tag=key.dtype)
    f = jax.jit(lambda x: conv2d_cnhw_ref(x, wt, stride=a["stride"], pad=a["pad"]))
    return lambda: f(x)


def _bench_conv_im2col_dense(key: OpKey):
    import jax
    import jax.numpy as jnp

    from repro.kernels.im2col_pack.ops import im2col_then_pack

    x = _synth_conv_input(key)
    a = _conv_args(key)
    w = _rand((key.d_in, key.d_out), seed=5, dtype_tag=key.dtype) / (key.d_in ** 0.5)

    @jax.jit
    def f(x):
        strips = im2col_then_pack(x, kh=a["kh"], kw=a["kw"], stride=a["stride"],
                                  pad=a["pad"], v=a["v"])
        xt = strips.transpose(0, 2, 1).reshape(-1, key.d_in)
        return xt @ w

    return lambda: f(x)


def _apply_conv_xla(params, x, *, kh, kw, stride=1, pad=0, v=128):
    from repro.kernels.conv_gemm.ops import conv2d_xla_ref

    return conv2d_xla_ref(x, params["values"], params["idx"], kh=kh, kw=kw,
                          stride=stride, pad=pad, v=v)


def _apply_conv_two_kernel(params, x, *, kh, kw, stride=1, pad=0, v=128):
    from repro.kernels.conv_gemm.ops import conv2d_two_kernel

    return conv2d_two_kernel(x, params["values"], params["idx"], kh=kh, kw=kw,
                             stride=stride, pad=pad, v=v)


def _apply_conv_fused(params, x, *, kh, kw, stride=1, pad=0, v=128,
                      geom_v=128, geom_bk=128):
    # the megakernel's strips never exist in HBM, so its strip width is pure
    # execution geometry — it uses the profiled geom_v, not the caller's v
    from repro.kernels.conv_gemm.ops import conv2d_fused

    return conv2d_fused(x, params["values"], params["idx"], kh=kh, kw=kw,
                        stride=stride, pad=pad, v=geom_v, block_k=geom_bk)


def _bench_conv(key: OpKey, apply_fn):
    import jax

    x = _synth_conv_input(key)
    a = _conv_args(key)
    values, idx = _synth_compressed(key)
    params = {"values": values, "idx": idx}
    f = jax.jit(lambda x: apply_fn(params, x, **a))
    return lambda: f(x)


def _strips_vmem(key: OpKey) -> int:
    from repro.kernels.colwise_nm.kernel import strips_vmem_bytes

    return strips_vmem_bytes(key.d_in, key.get("v", 128),
                             min(128, key.k_kept), min(key.tile, 512),
                             in_bytes=_key_itemsize(key))


def _strips_feasible(key: OpKey) -> Tuple[bool, str]:
    ok, reason = _tile_ok(key)
    if not ok:
        return ok, reason
    vm = _strips_vmem(key)
    if vm > VMEM_BYTES:
        return False, f"VMEM {vm} > budget {VMEM_BYTES}"
    return True, "ok"


def _fused_vmem_for(geom_v: int, geom_bk: int):
    def vm(key: OpKey) -> int:
        from repro.kernels.conv_gemm.kernel import fused_vmem_bytes

        return fused_vmem_bytes(
            key.get("c"), max(key.get("b", 1), 1), key.get("h"),
            key.get("w", key.get("h")), geom_v, min(geom_bk, key.k_kept),
            min(key.tile, 512), in_bytes=_key_itemsize(key),
            kh=key.get("kh"), kw=key.get("kw"), stride=key.get("s", 1),
            pad=key.get("p", 0))

    return vm


def _fused_feasible_for(geom_v: int, geom_bk: int):
    vm_fn = _fused_vmem_for(geom_v, geom_bk)

    def feasible(key: OpKey) -> Tuple[bool, str]:
        ok, reason = _tile_ok(key)
        if not ok:
            return ok, reason
        if key.get("c") <= 0 or key.get("h") <= 0:
            return False, "conv geometry (c, h, w) missing from key extras"
        vm = vm_fn(key)  # the whole CNHW feature map must sit in VMEM
        if vm > VMEM_BYTES:
            return False, f"VMEM {vm} > budget {VMEM_BYTES}"
        return True, "ok"

    return feasible


REGISTRY.register(ImplSpec(
    name="dense_conv", op="conv", backend="xla",
    requires=frozenset({"w"}), priority=30,
    feasible=_always, vmem_bytes=_no_vmem,
    make_bench=_bench_conv_dense,
))

REGISTRY.register(ImplSpec(
    name="im2col_dense_gemm", op="conv", backend="xla",
    requires=frozenset({"w"}), priority=20,
    feasible=_always, vmem_bytes=_no_vmem,
    make_bench=_bench_conv_im2col_dense,
))

REGISTRY.register(ImplSpec(
    name="im2col_sparse_xla", op="conv", backend="xla",
    requires=frozenset({"values", "idx"}), priority=10,
    feasible=_always, vmem_bytes=_no_vmem,
    apply=_apply_conv_xla,
    make_bench=lambda key: _bench_conv(key, _apply_conv_xla),
))

# two-kernel Pallas plan: pack kernel + strip-major GEMM (no HBM relayout)
REGISTRY.register(ImplSpec(
    name="im2col_sparse_pallas", op="conv", backend="pallas",
    requires=frozenset({"values", "idx"}), priority=10,
    feasible=_strips_feasible, vmem_bytes=_strips_vmem,
    apply=_apply_conv_two_kernel,
    make_bench=lambda key: _bench_conv(key, _apply_conv_two_kernel),
))

def _apply_conv_banded(params, x, *, kh, kw, stride=1, pad=0, v=128,
                       geom_v=128, geom_bk=128, geom_hb=2):
    # like the resident megakernel, the banded kernel's strips never exist in
    # HBM — strip width and band depth are pure execution geometry
    from repro.kernels.conv_gemm.ops import conv2d_fused_banded

    return conv2d_fused_banded(x, params["values"], params["idx"], kh=kh,
                               kw=kw, stride=stride, pad=pad, v=geom_v,
                               block_k=geom_bk, hb=geom_hb)


def _apply_conv_pipelined(params, x, *, kh, kw, stride=1, pad=0, v=128,
                          geom_v=128, geom_bk=128, geom_hb=2):
    # the pipelined plan writes and reads its own strips, so the profiled
    # strip width applies to both kernels of the pair
    from repro.kernels.conv_gemm.ops import conv2d_two_kernel_pipelined

    return conv2d_two_kernel_pipelined(x, params["values"], params["idx"],
                                       kh=kh, kw=kw, stride=stride, pad=pad,
                                       v=geom_v, block_k=geom_bk, hb=geom_hb)


def _conv_hw(key: OpKey):
    """(c, b, h, w, ho, wo) of a conv key (ho/wo recomputed from extras)."""
    from repro.kernels.im2col_pack.ref import out_size

    c, h = key.get("c"), key.get("h")
    w = key.get("w", h)
    b = max(key.get("b", 1), 1)
    ho = out_size(h, key.get("kh"), key.get("s", 1), key.get("p", 0))
    wo = out_size(w, key.get("kw"), key.get("s", 1), key.get("p", 0))
    return c, b, h, w, ho, wo


def _banded_vmem_for(geom_v: int, geom_bk: int, geom_hb: int):
    def vm(key: OpKey) -> int:
        from repro.kernels.conv_gemm.kernel import band_plan, banded_vmem_bytes

        c, b, h, w, ho, wo = _conv_hw(key)
        _, band_rows = band_plan(b=b, h=h, kh=key.get("kh"),
                                 stride=key.get("s", 1), pad=key.get("p", 0),
                                 ho=ho, wo=wo, v=geom_v, hb=geom_hb)
        return banded_vmem_bytes(c, w, band_rows, geom_v,
                                 min(geom_bk, key.k_kept), min(key.tile, 512),
                                 in_bytes=_key_itemsize(key),
                                 taps=key.get("kh") * key.get("kw"))

    return vm


def _dma_conv_feasible_for(vm_fn):
    """Predicate factory shared by the manual-DMA conv plans: tile shape,
    conv extras present, and the double-buffered footprint within budget."""

    def feasible(key: OpKey) -> Tuple[bool, str]:
        ok, reason = _tile_ok(key)
        if not ok:
            return ok, reason
        if key.get("c") <= 0 or key.get("h") <= 0:
            return False, "conv geometry (c, h, w) missing from key extras"
        vm = vm_fn(key)
        if vm > VMEM_BYTES:
            return False, f"VMEM {vm} > budget {VMEM_BYTES}"
        return True, "ok"

    return feasible


def _pipelined_vmem_for(geom_v: int, geom_bk: int, geom_hb: int):
    def vm(key: OpKey) -> int:
        from repro.kernels.colwise_nm.kernel import pipelined_strips_vmem_bytes

        return pipelined_strips_vmem_bytes(
            key.d_in, geom_v, geom_hb, min(geom_bk, key.k_kept),
            min(key.tile, 512), in_bytes=_key_itemsize(key))

    return vm


# fused megakernel: one geometry-pinned candidate per (strip width, block_k)
for _geom in FUSED_CONV_GEOMETRY:
    _gv, _gbk = dict(_geom)["v"], dict(_geom)["bk"]
    _apply = functools.partial(_apply_conv_fused, geom_v=_gv, geom_bk=_gbk)
    REGISTRY.register(ImplSpec(
        name=geometry_name("fused_sparse_pallas", _geom,
                           FUSED_CONV_GEOMETRY[0]),
        op="conv", backend="pallas",
        requires=frozenset({"values", "idx"}), priority=5,
        feasible=_fused_feasible_for(_gv, _gbk),
        vmem_bytes=_fused_vmem_for(_gv, _gbk),
        apply=_apply,
        make_bench=functools.partial(_bench_conv, apply_fn=_apply),
        geometry=_geom,
    ))

# The banded megakernel and pipelined two-kernel plans: the next rungs of the
# conv plan ladder (VMEM-resident -> banded -> pipelined two-kernel -> XLA;
# see docs/kernels.md).  Both are geometry-parameterized over strip width x
# block_k x band depth, with dtype-aware predicates that account for the
# DOUBLE buffers their DMA pipelines keep resident.
for _family, _apply_fn, _vm_for, _prio in (
        ("fused_banded_pallas", _apply_conv_banded, _banded_vmem_for, 6),
        ("two_kernel_pipelined", _apply_conv_pipelined, _pipelined_vmem_for,
         8)):
    for _geom in BANDED_CONV_GEOMETRY:
        _gv, _gbk, _ghb = (dict(_geom)["v"], dict(_geom)["bk"],
                           dict(_geom)["hb"])
        _apply = functools.partial(_apply_fn, geom_v=_gv, geom_bk=_gbk,
                                   geom_hb=_ghb)
        _vm = _vm_for(_gv, _gbk, _ghb)
        REGISTRY.register(ImplSpec(
            name=geometry_name(_family, _geom, BANDED_CONV_GEOMETRY[0]),
            op="conv", backend="pallas",
            requires=frozenset({"values", "idx"}), priority=_prio,
            feasible=_dma_conv_feasible_for(_vm),
            vmem_bytes=_vm,
            apply=_apply,
            make_bench=functools.partial(_bench_conv, apply_fn=_apply),
            geometry=_geom,
        ))


# ---------------------------------------------------------------------------
# Paged attention (the serve.kv_pages memory tier): page_size x
# pages_per_block geometry ladder.  Page size is a *cache layout* decision,
# so it has two key flavors: a planning key (no "ps" extra) races every
# geometry in profile_op — that's how choose_page_size picks the layout
# before the cache is allocated — and an execution key (pinned "ps") where
# only matching-layout pallas candidates plus the gather reference remain
# feasible.  Without a profile the heuristic takes the lowest priority, so
# the ladder's order is the chip's: a TPU v5e sweep at the Qwen2-0.5B decode
# shape (64 sequences, 24 layers, mean 213 cached rows) put a step's kernel
# time at 2.77, 3.12, 3.33 and 4.50 ms for these, first to last.  Fewer,
# larger copies and blocks win; each page size keeps its best block, so a
# cache pinned to 8, 16 or 32 still has a Pallas candidate.
# ---------------------------------------------------------------------------

PAGED_ATTN_GEOMETRY = (
    (("ps", 32), ("ppb", 8)),
    (("ps", 32), ("ppb", 4)),
    (("ps", 16), ("ppb", 16)),
    (("ps", 8), ("ppb", 32)),
)

DEFAULT_PAGE_SIZE = dict(PAGED_ATTN_GEOMETRY[0])["ps"]


def paged_attn_key(q_rows: int, n_heads: int, kv_heads: int, head_dim: int,
                   kv_capacity: int, page_size: int = 0, dtype="float32",
                   phase: str = "") -> OpKey:
    """OpKey for one paged-attention instance.

    ``page_size == 0`` builds the planning flavor; nonzero pins the physical
    layout. ``kv_capacity`` (table width x page size) is bucketed like batch
    so the DB is keyed by a bounded family of cache capacities.
    """
    extra = (("hd", head_dim), ("kvcap", bucket_batch(max(kv_capacity, 1))))
    if page_size:
        extra += (("ps", page_size),)
    extra += _mesh_extra()
    return OpKey(op="paged_attn", batch=bucket_batch(max(q_rows, 1)),
                 d_in=head_dim, d_out=n_heads * head_dim, k_kept=kv_heads,
                 tile=8, dtype=_dtype_tag(dtype), extra=extra, phase=phase)


def _paged_vmem_for(geom_ps: int, geom_ppb: int):
    def vm(key: OpKey) -> int:
        from repro.kernels.flash_attn.paged import paged_vmem_bytes

        hd, kv = key.get("hd", key.d_in), max(key.k_kept, 1)
        h = key.d_out // max(hd, 1)
        return paged_vmem_bytes(geom_ps, geom_ppb, kv, hd, h,
                                in_bytes=_key_itemsize(key))

    return vm


def _paged_feasible_for(geom_ps: int, geom_ppb: int):
    def feasible(key: OpKey) -> Tuple[bool, str]:
        ok, reason = _unpartitioned(key)
        if not ok:
            return ok, reason
        hd, kv = key.get("hd"), key.k_kept
        if hd <= 0 or kv <= 0:
            return False, "paged geometry (hd, kv) missing from key extras"
        h = key.d_out // hd
        if h % kv != 0:
            return False, f"H={h} not divisible by KV={kv} (head-map GQA)"
        pinned = key.get("ps", 0)
        if pinned and pinned != geom_ps:
            return False, f"cache layout pinned to page size {pinned}"
        vm = _paged_vmem_for(geom_ps, geom_ppb)(key)
        if vm > VMEM_BYTES:
            return False, f"VMEM {vm} > budget {VMEM_BYTES}"
        return True, "ok"

    return feasible


def _synth_paged(key: OpKey, ps: int):
    """Deterministic decode-shaped operands for a paged-attention bench
    (one layer of the ``[L, P, ps, KV*D]`` cache)."""
    import numpy as np

    hd, kv = key.get("hd"), key.k_kept
    h = key.d_out // hd
    b = key.batch
    kvcap = key.get("kvcap", 128)
    n_max = -(-kvcap // ps)
    p = b * n_max
    q = _rand((b, 1, h, hd), 1, key.dtype)
    kn = _rand((b, 1, kv, hd), 2, key.dtype)
    vn = _rand((b, 1, kv, hd), 3, key.dtype)
    kp = _rand((1, p + 1, ps, kv * hd), 4, key.dtype)
    vp = _rand((1, p + 1, ps, kv * hd), 5, key.dtype)
    tables = np.arange(p, dtype=np.int32).reshape(b, n_max)
    # three-quarter-full caches: the ragged-final-page case is the hot one
    lengths = np.full((b,), max(kvcap * 3 // 4, 1), np.int32)
    return q, kn, vn, kp, vp, tables, lengths


def _bench_paged_ref(key: OpKey):
    import jax

    from repro.kernels.flash_attn.paged import paged_attention_ref

    ps = key.get("ps", 0) or DEFAULT_PAGE_SIZE
    q, kn, vn, kp, vp, tables, lengths = _synth_paged(key, ps)
    f = jax.jit(lambda q: paged_attention_ref(q, kn, vn, kp, vp, tables,
                                              lengths))
    return lambda: f(q)


def _bench_paged_pallas(key: OpKey, geom_ps: int, geom_ppb: int):
    import jax

    from repro.kernels.flash_attn.paged import paged_attention_pallas
    from repro.kernels.pltpu_compat import should_interpret

    # the candidate's OWN page size, not the key's: a planning key races
    # every geometry's physical layout against the others
    q, kn, vn, kp, vp, tables, lengths = _synth_paged(key, geom_ps)
    interp = should_interpret()
    f = jax.jit(lambda q: paged_attention_pallas(
        q, kn, vn, kp, vp, tables, lengths, page_size=geom_ps,
        pages_per_block=geom_ppb, interpret=interp))
    return lambda: f(q)


REGISTRY.register(ImplSpec(
    name="paged_attn_ref", op="paged_attn", backend="xla",
    requires=frozenset(), priority=10,
    feasible=_always, vmem_bytes=_no_vmem,
    make_bench=_bench_paged_ref,
))

# every candidate's name carries its geometry (no bare default), so the
# decision set-up reports names the page size and block
for _rank, _geom in enumerate(PAGED_ATTN_GEOMETRY):
    _gps, _gppb = dict(_geom)["ps"], dict(_geom)["ppb"]
    REGISTRY.register(ImplSpec(
        name=geometry_name("paged_attn_pallas", _geom, ()),
        op="paged_attn", backend="pallas",
        requires=frozenset(), priority=5 + _rank,
        feasible=_paged_feasible_for(_gps, _gppb),
        vmem_bytes=_paged_vmem_for(_gps, _gppb),
        make_bench=functools.partial(_bench_paged_pallas, geom_ps=_gps,
                                     geom_ppb=_gppb),
        geometry=_geom,
    ))


def choose_page_size(n_heads: int, kv_heads: int, head_dim: int,
                     kv_capacity: int, *, q_rows: int = 8, dtype="float32",
                     phase: str = "decode", db=None,
                     profile: bool = False) -> int:
    """Pick the KV page size for a serving config (the cache-layout plan).

    Resolves the unpinned planning key: with ``profile=True`` (or a warm
    DB), profile_op has raced every ``PAGED_ATTN_GEOMETRY`` page size for
    this shape and the winner's layout is returned; otherwise the heuristic
    rung decides (DEFAULT_PAGE_SIZE when the gather reference wins).
    """
    from repro.dispatch.dispatch import best_impl, ensure_profiled
    from repro.dispatch.profiler import TuningError

    key = paged_attn_key(q_rows, n_heads, kv_heads, head_dim, kv_capacity,
                         page_size=0, dtype=dtype, phase=phase)
    if profile:
        try:
            ensure_profiled(key, db=db)
        except TuningError:
            pass
    spec = best_impl(key, db=db)
    ps = spec.geom("ps", 0) if spec is not None else 0
    return ps or DEFAULT_PAGE_SIZE
