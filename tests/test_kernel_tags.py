"""Every Pallas kernel names its family: each ``pallas_call`` under
``src/repro/kernels/`` passes ``metadata=kernel_tag("<family>")`` with a
family of ``pltpu_compat.KERNEL_FAMILIES`` (read from the source, so a
kernel added without its tag fails here)."""
import ast
from pathlib import Path

import pytest

from repro.kernels.pltpu_compat import KERNEL_FAMILIES, kernel_tag

KERNELS = Path(__file__).resolve().parent.parent / "src" / "repro" / "kernels"


def _call_sites():
    """(id, call node) of every ``pallas_call(...)`` in the kernel sources,
    named by file and enclosing function."""
    sites = []
    for path in sorted(KERNELS.rglob("*.py")):
        tree = ast.parse(path.read_text())
        for fn in ast.walk(tree):
            if not isinstance(fn, ast.FunctionDef):
                continue
            for node in ast.walk(fn):
                if isinstance(node, ast.Call) and (
                        getattr(node.func, "attr", None) == "pallas_call"
                        or getattr(node.func, "id", None) == "pallas_call"):
                    rel = path.relative_to(KERNELS).as_posix()
                    sites.append((f"{rel}::{fn.name}", node))
    return sites


SITES = _call_sites()


def _family(node):
    """The family literal of a call's ``metadata=kernel_tag("...")``."""
    for kw in node.keywords:
        if kw.arg == "metadata":
            v = kw.value
            if (isinstance(v, ast.Call)
                    and getattr(v.func, "id", None) == "kernel_tag"
                    and len(v.args) == 1
                    and isinstance(v.args[0], ast.Constant)):
                return v.args[0].value
            return None
    return None


@pytest.mark.parametrize("node", [n for _, n in SITES],
                         ids=[i for i, _ in SITES])
def test_pallas_call_passes_its_family_tag(node):
    assert _family(node) in KERNEL_FAMILIES


def test_every_family_tags_a_kernel():
    assert sorted({_family(n) for _, n in SITES}) == sorted(KERNEL_FAMILIES)


def test_kernel_tag_refuses_an_unknown_family():
    assert kernel_tag("paged_attn") == {"kernel": "paged_attn"}
    with pytest.raises(ValueError, match="KERNEL_FAMILIES"):
        kernel_tag("closed_call")
