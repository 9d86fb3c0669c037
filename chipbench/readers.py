"""Arithmetic the per-layer metric readers share.  Each reader in
``metrics/`` takes the context of a traced run and returns a number, or
``None`` where it finds nothing to read.  A reader of a kernel family also
names a predicate ``claims(op)`` that picks that family's Pallas kernels
out of the trace; ``claim_report`` checks that the readers of a cell
account for its kernels."""
from __future__ import annotations

import re
from typing import Callable, Dict, Iterable, List, Optional, Sequence

from chipbench import peaks


def traced(ctx) -> bool:
    """The run traced at least one device (a CPU test run does not)."""
    red = ctx["reduced"]
    return red is not None and red.n_devices > 0


def pallas_named(names: Sequence[str]) -> Callable:
    """Predicate on a traced op: a Pallas kernel whose HLO instruction name
    is one of ``names`` (numeric suffix aside)."""
    pat = re.compile(r"^%(" + "|".join(map(re.escape, names)) + r")(\.\d+)?$")
    return lambda o: o.is_pallas and bool(pat.match(o.name))


def pallas_matching(signature: str) -> Callable:
    """Predicate on a traced op: a Pallas kernel whose HLO text matches the
    regular expression ``signature`` (its operand list)."""
    pat = re.compile(signature)
    return lambda o: o.is_pallas and bool(pat.search(o.text))


class UnclaimedKernels(Exception):
    """A kernel reader read nothing while Pallas kernels that no reader
    recognises ran: a kernel was most likely renamed or its operands
    reordered, and the reader's roofline would go silent unseen."""


def claim_report(reduced, claims: Dict[str, Callable],
                 read: Iterable[str]) -> List[str]:
    """Check that the kernel readers of a cell account for its Pallas
    kernels, and describe each kernel with the reader that claims it.

    ``claims`` maps each kernel reader's metric to its predicate; ``read``
    names the metrics that read a number.  Raises ``UnclaimedKernels``
    where two readers claim one kernel, or where a reader read nothing
    while some Pallas kernel was claimed by none.  A reader whose kernel
    is off the path reads nothing and leaves no kernel unclaimed: that is
    no fault."""
    per: Dict[str, list] = {}
    for o in reduced.ops:
        if not o.is_pallas:
            continue
        owners = [m for m, c in sorted(claims.items()) if c(o)]
        if len(owners) > 1:
            raise UnclaimedKernels(f"{o.name} is claimed by {owners}")
        base = re.sub(r"\.\d+$", "", o.name)
        key = (base, owners[0] if owners else None)
        a = per.setdefault(key, [0, 0.0, o.text])
        a[0] += 1
        a[1] += o.dur_ns * 1e-9
    lines = [f"pallas kernel: {base} runs {n} device_s {sec!r} claimed by "
             f"{owner or 'none'} e.g. {text[:400]}"
             for (base, owner), (n, sec, text) in sorted(
                 per.items(), key=lambda kv: (kv[0][0], kv[0][1] or ""))]
    unclaimed = sorted(b for b, owner in per if owner is None)
    silent = sorted(set(claims) - set(read))
    if silent and unclaimed:
        raise UnclaimedKernels(
            f"kernel readers {silent} read nothing while Pallas kernels "
            f"{unclaimed} ran unclaimed\n" + "\n".join(lines))
    return lines


def share_pct(least_s: float, spent_s: float) -> Optional[float]:
    """Least time over time spent, in percent; nothing where no time was
    spent or no work counted."""
    if spent_s <= 0 or least_s <= 0:
        return None
    return 100.0 * least_s / spent_s


def least_time_s(ops, device_kind: str) -> float:
    """Summed least time of ``ops``, a list of (flops, bytes), each op
    bound by its own roofline."""
    pk = peaks.peaks_for(device_kind)
    return sum(peaks.least_time_s(f, b, pk) for f, b in ops)


def peak_flops(device_kind: str) -> float:
    return peaks.peaks_for(device_kind).flops


def idle_pct(ctx) -> Optional[float]:
    red, win = ctx["reduced"], ctx["window_s"]
    if not traced(ctx) or win <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s() / win)
