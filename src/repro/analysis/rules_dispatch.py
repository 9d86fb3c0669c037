"""Dispatch-predicate consistency (DP3xx): VMEM predicates vs. kernels.

PR 3 shipped the canonical bug this family exists for: a VMEM-feasibility
predicate that assumed bf16 operands under-counted the resident footprint
2x for f32 keys, so the dispatcher admitted a megakernel whose whole-map
scratch could not fit.  These rules recompute each registered pallas
candidate's footprint **independently** — straight from the kernel modules'
analytic ``*_vmem_bytes`` functions (which are derived from the literal
``scratch_shapes``/``BlockSpec`` the kernels allocate), with the byte width
taken from the probe key's dtype and both halves of every double buffer
counted — and compare against what the registry's ``vmem_bytes``/
``feasible`` claim, over a grid of representative OpKeys x dtypes.

These are *project* rules: they import the live registry, so they only run
when the analyzed tree contains the real ``src/repro`` package.
"""
from __future__ import annotations

from typing import Iterable, List, Optional, Tuple

from repro.analysis.engine import Context, Rule, register

_REGISTRY_PATH = "src/repro/dispatch/registry.py"


def _itemsize(key) -> int:
    # the independent statement of the dtype law; if the registry's
    # _key_itemsize ever regresses to a constant, this disagrees and fires
    return 4 if key.dtype == "f32" else 2


def probe_keys(R) -> List:
    """Representative OpKeys per op: small/large x f32/bf16, plus one
    deliberately over-budget shape per family so the feasible() rejection
    boundary is exercised too."""
    keys = []
    for dt in ("float32", "bfloat16"):
        keys.append(R.linear_key(8, 512, 512, 128, 128, dt))
        keys.append(R.linear_key(256, 2048, 1024, 256, 128, dt))
        keys.append(R.conv_key(16, 28, 28, 128, 3, 3, 1, 1, 72, 128,
                               v=128, dtype=dt, batch=1))
        keys.append(R.conv_key(32, 56, 56, 256, 3, 3, 1, 1, 144, 128,
                               v=128, dtype=dt, batch=4))
        keys.append(R.paged_attn_key(8, 8, 2, 64, 256, page_size=0, dtype=dt))
        keys.append(R.paged_attn_key(8, 8, 2, 64, 256, page_size=16,
                                     dtype=dt))
    # over-budget probes: the whole-map megakernel cannot hold a stem-scale
    # f32 map, and no block geometry holds a 2M-wide reduction
    keys.append(R.linear_key(512, 1 << 21, 512, 128, 128, "float32"))
    keys.append(R.conv_key(64, 224, 224, 128, 7, 7, 2, 3, 288, 128,
                           v=128, dtype="float32", batch=8))
    return keys


def recompute_vmem(spec, key) -> Optional[int]:
    """The kernel-side footprint for ``spec`` at ``key``: the analytic
    byte-count colocated with each kernel's scratch allocation, evaluated
    with a locally derived (dtype-aware) element size.  None for families
    with no VMEM-resident kernel (xla) or unknown families."""
    from repro.kernels.colwise_nm import kernel as ck
    from repro.kernels.conv_gemm import kernel as gk
    from repro.kernels.flash_attn import paged as pk
    from repro.kernels.im2col_pack.ref import out_size

    family = spec.name.split("@")[0]
    geom = dict(spec.geometry)
    ib = _itemsize(key)
    tile = min(key.tile, 512)
    if family == "compressed_pallas":
        return ck.vmem_bytes(min(geom.get("bb", 128), key.batch),
                             min(geom.get("bk", 128), key.k_kept),
                             key.d_in, tile, in_bytes=ib)
    if family == "im2col_sparse_pallas":
        return ck.strips_vmem_bytes(key.d_in, key.get("v", 128),
                                    min(128, key.k_kept), tile, in_bytes=ib)
    if family == "fused_sparse_pallas":
        return gk.fused_vmem_bytes(
            key.get("c"), max(key.get("b", 1), 1), key.get("h"),
            key.get("w", key.get("h")), geom["v"],
            min(geom["bk"], key.k_kept), tile, in_bytes=ib,
            kh=key.get("kh"), kw=key.get("kw"), stride=key.get("s", 1),
            pad=key.get("p", 0))
    if family == "fused_banded_pallas":
        c, h = key.get("c"), key.get("h")
        w = key.get("w", h)
        b = max(key.get("b", 1), 1)
        ho = out_size(h, key.get("kh"), key.get("s", 1), key.get("p", 0))
        wo = out_size(w, key.get("kw"), key.get("s", 1), key.get("p", 0))
        _, band_rows = band_rows_for(gk, b, h, key, ho, wo, geom)
        return gk.banded_vmem_bytes(c, w, band_rows, geom["v"],
                                    min(geom["bk"], key.k_kept), tile,
                                    in_bytes=ib,
                                    taps=key.get("kh") * key.get("kw"))
    if family == "two_kernel_pipelined":
        return ck.pipelined_strips_vmem_bytes(
            key.d_in, geom["v"], geom["hb"], min(geom["bk"], key.k_kept),
            tile, in_bytes=ib)
    if family == "paged_attn_pallas":
        hd = key.get("hd", key.d_in)
        kv = max(key.k_kept, 1)
        h = key.d_out // max(hd, 1)
        return pk.paged_vmem_bytes(geom["ps"], geom["ppb"], kv, hd, h,
                                   in_bytes=ib)
    return None


def band_rows_for(gk, b, h, key, ho, wo, geom) -> Tuple[int, int]:
    return gk.band_plan(b=b, h=h, kh=key.get("kh"), stride=key.get("s", 1),
                        pad=key.get("p", 0), ho=ho, wo=wo, v=geom["v"],
                        hb=geom["hb"])


def _audit_pairs(ctx: Context):
    if ctx.root is None or not (ctx.root / _REGISTRY_PATH).is_file():
        return None, ()
    from repro.dispatch import registry as R

    pairs = []
    for key in probe_keys(R):
        for spec in R.REGISTRY.candidates(key.op):
            if spec.backend != "pallas":
                continue
            expected = recompute_vmem(spec, key)
            if expected is None:
                continue
            pairs.append((spec, key, expected))
    return R, pairs


@register
class VmemPredicateUnderCount(Rule):
    """DP301: a registered candidate's ``vmem_bytes(key)`` claims less than
    the kernel-side analytic footprint for that key — the PR 3 bug class
    (dtype-unaware or single-halved accounting) as a CI failure."""

    id = "DP301"
    title = "VMEM predicate under-counts the kernel's footprint"

    def check_project(self, ctx: Context) -> Iterable:
        R, pairs = _audit_pairs(ctx)
        if R is None:
            return
        for spec, key, expected in pairs:
            declared = spec.vmem_bytes(key)
            if declared < expected:
                yield self.finding(
                    _REGISTRY_PATH, 1,
                    f"{spec.op}:{spec.name} vmem_bytes({key.token}) = "
                    f"{declared} under-counts the kernel footprint "
                    f"{expected} (dtype {key.dtype}; check per-operand byte "
                    f"width and both double-buffer halves)",
                    anchor=f"{spec.op}:{spec.name}:{key.dtype}")


@register
class FeasibleAdmitsOverBudget(Rule):
    """DP302: ``feasible(key)`` accepts a key whose kernel-side footprint
    exceeds the VMEM budget — the dispatcher would admit a kernel that
    cannot fit, failing at compile/run time instead of falling down the
    plan ladder."""

    id = "DP302"
    title = "feasibility predicate admits an over-budget kernel"

    def check_project(self, ctx: Context) -> Iterable:
        R, pairs = _audit_pairs(ctx)
        if R is None:
            return
        for spec, key, expected in pairs:
            if expected > R.VMEM_BYTES and spec.feasible(key)[0]:
                yield self.finding(
                    _REGISTRY_PATH, 1,
                    f"{spec.op}:{spec.name} feasible({key.token}) admits a "
                    f"kernel footprint of {expected} bytes against a "
                    f"{R.VMEM_BYTES}-byte budget",
                    anchor=f"{spec.op}:{spec.name}:{key.dtype}:budget")
