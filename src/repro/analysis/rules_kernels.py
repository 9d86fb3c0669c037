"""Pallas kernel lints (PK1xx): the manual-DMA and VMEM conventions.

The kernels under ``src/repro/kernels/`` share a hand-rolled protocol
(``pltpu_compat``): async copies are created by ``make_async_copy`` and
driven by the two-slot ``double_buffer_rotate`` helper, HBM-resident
operands are declared ``BlockSpec(memory_space=ANY)`` and touched only
through windowed ``ref.at[...]`` DMA descriptors, and MXU contractions go
through ``dot_f32`` so interpret mode (XLA:CPU, no bf16 dot) keeps working.
These rules pin the protocol with pure AST checks — a kernel that starts a
DMA it never waits on, or indexes an ANY operand as if it were in VMEM,
fails CI instead of failing on hardware.

All rules key off names imported from ``pltpu_compat``, so the compat shim
itself (which *defines* the helpers and legitimately calls ``.start()`` /
``.wait()`` inside ``double_buffer_rotate``) is exempt by construction.
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Dict, Iterable, List, Optional, Tuple

from repro.analysis.engine import Context, Rule, register

_COMPAT_SUFFIX = "pltpu_compat"


def compat_aliases(tree: ast.Module) -> Dict[str, str]:
    """Local names bound by ``from ...pltpu_compat import X [as Y]``."""
    out: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module \
                and node.module.endswith(_COMPAT_SUFFIX):
            for a in node.names:
                out[a.asname or a.name] = a.name
    return out


def _call_name(node: ast.Call) -> Optional[str]:
    if isinstance(node.func, ast.Name):
        return node.func.id
    if isinstance(node.func, ast.Attribute):
        return node.func.attr
    return None


def _kwarg(call: ast.Call, name: str) -> Optional[ast.expr]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _const_or(node: Optional[ast.expr], default: int) -> int:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return node.value
    return default


@dataclasses.dataclass
class PallasModel:
    """One ``pallas_call`` invocation resolved against its kernel function."""

    call: ast.Call
    kernel: Optional[ast.FunctionDef]
    in_specs: List[ast.expr]
    n_out: int
    scratch: List[ast.expr]
    n_prefetch: int

    def params(self) -> List[str]:
        if self.kernel is None:
            return []
        args = self.kernel.args
        return [a.arg for a in (*args.posonlyargs, *args.args)]

    def any_operand_params(self) -> List[str]:
        """Kernel param names bound to ``BlockSpec(memory_space=...)``
        (un-blocked, HBM/ANY-resident) inputs."""
        params = self.params()
        out = []
        for i, spec in enumerate(self.in_specs):
            if isinstance(spec, ast.Call) and _call_name(spec) == "BlockSpec" \
                    and _kwarg(spec, "memory_space") is not None:
                j = self.n_prefetch + i
                if j < len(params):
                    out.append(params[j])
        return out

    def scratch_expr_for(self, name: str) -> Optional[ast.expr]:
        """The scratch_shapes entry backing kernel param ``name``."""
        params = self.params()
        if name not in params:
            return None
        idx = params.index(name) - (self.n_prefetch + len(self.in_specs)
                                    + self.n_out)
        if 0 <= idx < len(self.scratch):
            return self.scratch[idx]
        return None


def _resolve_kernel_fn(tree: ast.Module, arg: ast.expr) \
        -> Optional[ast.FunctionDef]:
    name = None
    if isinstance(arg, ast.Name):
        name = arg.id
    elif isinstance(arg, ast.Call) and _call_name(arg) == "partial" \
            and arg.args and isinstance(arg.args[0], ast.Name):
        name = arg.args[0].id
    if name is None:
        return None
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name == name:
            return node
    return None


def pallas_models(tree: ast.Module) -> List[PallasModel]:
    models = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and _call_name(node) == "pallas_call"):
            continue
        spec_src: ast.Call = node
        n_prefetch = 0
        grid_spec = _kwarg(node, "grid_spec")
        if isinstance(grid_spec, ast.Call):
            # PrefetchScalarGridSpec(num_scalar_prefetch=K, in_specs=...):
            # scalar-prefetch operands shift every kernel param right by K
            spec_src = grid_spec
            n_prefetch = _const_or(_kwarg(grid_spec, "num_scalar_prefetch"), 0)
        in_specs = _kwarg(spec_src, "in_specs")
        out_specs = _kwarg(spec_src, "out_specs")
        scratch = _kwarg(spec_src, "scratch_shapes")
        models.append(PallasModel(
            call=node,
            kernel=_resolve_kernel_fn(tree, node.args[0]) if node.args
            else None,
            in_specs=list(in_specs.elts)
            if isinstance(in_specs, (ast.List, ast.Tuple)) else [],
            n_out=len(out_specs.elts)
            if isinstance(out_specs, (ast.List, ast.Tuple))
            else (1 if out_specs is not None else 1),
            scratch=list(scratch.elts)
            if isinstance(scratch, (ast.List, ast.Tuple)) else [],
            n_prefetch=n_prefetch,
        ))
    return models


def _top_level_functions(tree: ast.Module) -> List[ast.FunctionDef]:
    return [n for n in tree.body if isinstance(n, ast.FunctionDef)]


def _calls_to(fn: ast.AST, names: Iterable[str]) -> List[ast.Call]:
    wanted = set(names)
    out = []
    for node in ast.walk(fn):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id in wanted:
            out.append(node)
    return out


def _method_calls(fn: ast.AST, attr: str) -> List[ast.Call]:
    out = []
    for node in ast.walk(fn):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute) \
                and node.func.attr == attr:
            out.append(node)
    return out


def _aliases_of(compat: Dict[str, str], original: str) -> List[str]:
    return [local for local, orig in compat.items() if orig == original]


@register
class UnpairedAsyncCopy(Rule):
    """PK101: a ``make_async_copy`` descriptor must be driven to completion —
    either a direct ``.start()``/``.wait()`` pair or (preferred) the shared
    ``double_buffer_rotate`` protocol.  A start with no wait deadlocks or
    races on hardware; a descriptor that is never started is dead code."""

    id = "PK101"
    title = "make_async_copy without a matching wait"

    def check_module(self, ctx: Context, path: str, tree: ast.Module):
        compat = compat_aliases(tree)
        mac = _aliases_of(compat, "make_async_copy")
        rot = _aliases_of(compat, "double_buffer_rotate")
        if not mac:
            return
        for fn in _top_level_functions(tree):
            mac_calls = _calls_to(fn, mac)
            if not mac_calls:
                continue
            starts = _method_calls(fn, "start")
            waits = _method_calls(fn, "wait")
            rotates = _calls_to(fn, rot) if rot else []
            if rotates and not starts and not waits:
                continue  # the shared rotation protocol drives the DMA
            if starts and waits:
                continue  # manually paired; PK102 judges the style
            line = mac_calls[0].lineno
            what = "started but never waited" if starts else (
                "waited but never started" if waits
                else "neither started nor handed to double_buffer_rotate")
            yield self.finding(
                path, line,
                f"async copy in {fn.name}() is {what}; drive it with "
                f"pltpu_compat.double_buffer_rotate or a .start()/.wait() "
                f"pair on every path",
                anchor=fn.name)


@register
class RawSlotRotation(Rule):
    """PK102: double-buffer slot sequencing belongs to the one shared
    ``double_buffer_rotate`` helper.  Hand-rolled ``.start()``/``.wait()``
    arithmetic re-implements the warmup/prefetch/drain protocol per kernel,
    which is exactly how slot-index bugs (wait on the buffer being filled)
    get written."""

    id = "PK102"
    title = "manual DMA slot rotation instead of double_buffer_rotate"

    def check_module(self, ctx: Context, path: str, tree: ast.Module):
        compat = compat_aliases(tree)
        mac = _aliases_of(compat, "make_async_copy")
        if not mac:
            return
        for fn in _top_level_functions(tree):
            if not _calls_to(fn, mac):
                continue
            starts = _method_calls(fn, "start")
            waits = _method_calls(fn, "wait")
            if starts and waits:
                yield self.finding(
                    path, starts[0].lineno,
                    f"{fn.name}() sequences DMA slots with raw "
                    f".start()/.wait() calls; use "
                    f"pltpu_compat.double_buffer_rotate so warmup/prefetch/"
                    f"drain share one audited protocol",
                    anchor=fn.name)


@register
class AnyOperandDirectIndex(Rule):
    """PK103: a ``BlockSpec(memory_space=ANY)`` operand is HBM-resident —
    the kernel body may only carve DMA windows with ``ref.at[...]``, never
    read it with a direct subscript (which compiles to a per-element HBM
    access or fails late on hardware)."""

    id = "PK103"
    title = "ANY-memory operand indexed without an explicit copy"

    def check_module(self, ctx: Context, path: str, tree: ast.Module):
        for model in pallas_models(tree):
            if model.kernel is None:
                continue
            any_params = set(model.any_operand_params())
            if not any_params:
                continue
            for node in ast.walk(model.kernel):
                if isinstance(node, ast.Subscript) \
                        and isinstance(node.value, ast.Name) \
                        and node.value.id in any_params:
                    yield self.finding(
                        path, node.lineno,
                        f"{model.kernel.name}() indexes ANY-memory operand "
                        f"{node.value.id!r} directly; copy a window into "
                        f"VMEM scratch first ({node.value.id}.at[...] + "
                        f"make_async_copy)",
                        anchor=f"{model.kernel.name}.{node.value.id}")


@register
class BareDotInKernel(Rule):
    """PK104: kernel-body contractions must go through
    ``pltpu_compat.dot_f32`` (which casts to f32 under interpret mode —
    XLA:CPU has no bf16 dot), not bare ``jnp.dot``.  A bare dot works on
    TPU and then breaks every CPU test/profile run in interpret mode."""

    id = "PK104"
    title = "bare jnp.dot in a pallas kernel body"

    def check_module(self, ctx: Context, path: str, tree: ast.Module):
        for model in pallas_models(tree):
            if model.kernel is None:
                continue
            for node in ast.walk(model.kernel):
                if isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute) \
                        and node.func.attr in ("dot", "dot_general"):
                    yield self.finding(
                        path, node.lineno,
                        f"{model.kernel.name}() calls a bare "
                        f"{ast.unparse(node.func)}; route the contraction "
                        f"through pltpu_compat.dot_f32 so interpret mode "
                        f"(CPU tests, profiling) keeps working",
                        anchor=model.kernel.name)


def _leading_dim_doubled(shape: ast.expr) -> bool:
    """True when a VMEM scratch shape's leading dim carries two DMA halves:
    a literal ``2`` or a ``2 * x`` / ``x * 2`` product."""
    if not isinstance(shape, (ast.Tuple, ast.List)) or not shape.elts:
        return False
    lead = shape.elts[0]
    if isinstance(lead, ast.Constant):
        return lead.value == 2
    if isinstance(lead, ast.BinOp) and isinstance(lead.op, ast.Mult):
        for side in (lead.left, lead.right):
            if isinstance(side, ast.Constant) and side.value == 2:
                return True
    return False


@register
class SingleBufferedDmaScratch(Rule):
    """PK105: the VMEM scratch a ``make_async_copy`` lands in must hold BOTH
    double-buffer halves (leading dim ``2`` or ``2*hb``).  A single-slot
    scratch silently serializes the pipeline — or worse, the prefetch
    overwrites the half still being consumed."""

    id = "PK105"
    title = "DMA destination scratch is not double-buffered"

    def check_module(self, ctx: Context, path: str, tree: ast.Module):
        compat = compat_aliases(tree)
        mac = set(_aliases_of(compat, "make_async_copy"))
        if not mac:
            return
        for model in pallas_models(tree):
            if model.kernel is None:
                continue
            for call in _calls_to(model.kernel, mac):
                if len(call.args) < 2:
                    continue
                dst = _base_ref_name(call.args[1])
                if dst is None:
                    continue
                scratch = model.scratch_expr_for(dst)
                if scratch is None or not (
                        isinstance(scratch, ast.Call)
                        and _call_name(scratch) == "VMEM"):
                    continue
                shape = scratch.args[0] if scratch.args else None
                if shape is not None and not _leading_dim_doubled(shape):
                    yield self.finding(
                        path, call.lineno,
                        f"{model.kernel.name}() DMAs into scratch "
                        f"{dst!r} whose leading dim is not a 2x double "
                        f"buffer; allocate (2, ...) or (2*hb, ...) so "
                        f"prefetch can overlap compute",
                        anchor=f"{model.kernel.name}.{dst}")


def _base_ref_name(node: ast.expr) -> Optional[str]:
    """``buf.at[i]`` / ``buf.at[...]`` / ``buf`` -> ``"buf"``."""
    if isinstance(node, ast.Subscript):
        node = node.value
    if isinstance(node, ast.Attribute) and node.attr == "at":
        node = node.value
    if isinstance(node, ast.Name):
        return node.id
    return None
