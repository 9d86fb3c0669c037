"""Reduce a profiler trace (``.xplane.pb``) to device time per operation,
busy and idle time, collective intervals and the longest idle gaps.

Read with ``jax.profiler.ProfileData``.  On a TPU the device planes are
``/device:TPU:<n>``: line ``XLA Ops`` holds one event per HLO instruction
executed, named by the instruction's HLO text
(``%name = type opcode(operands), ...``); line ``XLA Modules`` holds one
event per program run.  Host planes hold the benchmark's own
``jax.profiler.TraceAnnotation`` spans.  The host and device clocks of one
trace can disagree by about a millisecond, so an idle gap is named by the
device programs around it, not by the host span it falls in.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


@dataclasses.dataclass
class Op:
    """One executed HLO instruction on one device."""

    device: int
    name: str       # instruction name, ``%fusion.12``
    opcode: str     # ``fusion``, ``custom-call``, ``all-reduce-start`` ...
    text: str       # the whole HLO text
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def is_pallas(self) -> bool:
        return 'custom_call_target="tpu_custom_call"' in self.text

    @property
    def is_collective(self) -> bool:
        return self.opcode.replace("-start", "").replace("-done", "") \
            in COLLECTIVES


@dataclasses.dataclass
class Reduced:
    ops: List[Op]
    modules: List[Tuple[int, str, float, float]]  # device, name, start, end
    annotations: List[Tuple[str, float, float]]   # host spans: name, start, end
    n_devices: int

    # -- device time ---------------------------------------------------------

    def busy_s(self) -> float:
        """Union of the intervals in which an operation ran, per device,
        averaged over the devices."""
        if not self.n_devices:
            return 0.0
        total = 0.0
        for d in range(self.n_devices):
            total += _union_ns([(o.start_ns, o.end_ns) for o in self.ops
                                if o.device == d])
        return total / self.n_devices * 1e-9

    def op_time_s(self, pred) -> float:
        """Summed device time of the ops that ``pred`` accepts, per device,
        averaged over the devices."""
        if not self.n_devices:
            return 0.0
        return sum(o.dur_ns for o in self.ops if pred(o)) \
            / self.n_devices * 1e-9

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` operations that took most device time, by instruction
        name and opcode, in seconds per device."""
        acc: Dict[str, float] = collections.Counter()
        for o in self.ops:
            acc[f"{o.name} {o.opcode}"] += o.dur_ns
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / max(self.n_devices, 1) * 1e-9] for k, v in top]

    def pallas_kernels(self) -> Dict[str, Tuple[int, float, str]]:
        """Pallas kernel instruction name (numeric suffix dropped) ->
        (executions, device seconds over all devices, one HLO text)."""
        acc: Dict[str, list] = {}
        for o in self.ops:
            if o.is_pallas:
                base = re.sub(r"\.\d+$", "", o.name)
                a = acc.setdefault(base, [0, 0.0, o.text])
                a[0] += 1
                a[1] += o.dur_ns * 1e-9
        return {k: (v[0], v[1], v[2]) for k, v in acc.items()}

    # -- collectives -----------------------------------------------------------

    def exposed_collective_s(self) -> float:
        """Time in which a collective ran on a device and no other
        operation did, per device, averaged over the devices."""
        if not self.n_devices:
            return 0.0
        total = 0.0
        for d in range(self.n_devices):
            mine = [o for o in self.ops if o.device == d]
            coll = [(o.start_ns, o.end_ns) for o in mine if o.is_collective]
            comp = [(o.start_ns, o.end_ns) for o in mine
                    if not o.is_collective]
            total += _union_ns(coll) - _overlap_ns(coll, comp)
        return total / self.n_devices * 1e-9

    # -- idle gaps -------------------------------------------------------------

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The ``n`` longest idle gaps on device 0 between its first and
        last operation, each named by the programs that ran before and after
        it, in seconds."""
        mods = sorted((m for m in self.modules if m[0] == 0),
                      key=lambda m: m[2])
        spans = _merged([(o.start_ns, o.end_ns) for o in self.ops
                         if o.device == 0])
        gaps = []
        for (s0, e0), (s1, _e1) in zip(spans, spans[1:]):
            if s1 > e0:
                gaps.append((s1 - e0, e0, s1))
        gaps.sort(key=lambda g: -g[0])
        out = []
        for dur, a, b in gaps[:n]:
            before = _module_at(mods, a, before=True)
            after = _module_at(mods, b, before=False)
            out.append([f"after {before} before {after}", dur * 1e-9])
        return out


def _module_at(mods, t, *, before: bool) -> str:
    best = None
    for _d, name, s, e in mods:
        if before and s <= t:
            best = name
        elif not before and s >= t:
            return _short(name)
    return _short(best) if best else "-"


def _short(name: Optional[str]) -> str:
    return re.sub(r"\(\d+\)$", "", name or "-")


def _merged(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _union_ns(iv) -> float:
    return sum(e - s for s, e in _merged(iv))


def _overlap_ns(a, b) -> float:
    """Length of the intersection of the unions of ``a`` and ``b``."""
    ma, mb = _merged(a), _merged(b)
    i = j = 0
    tot = 0.0
    while i < len(ma) and j < len(mb):
        s = max(ma[i][0], mb[j][0])
        e = min(ma[i][1], mb[j][1])
        if e > s:
            tot += e - s
        if ma[i][1] < mb[j][1]:
            i += 1
        else:
            j += 1
    return tot


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def reduce_file(path: str, annotation_prefix: str = "chipbench.") -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: List[Op] = []
    modules = []
    annotations = []
    devices = set()
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = int(m.group(1))
            devices.add(dev)
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        text = ev.name
                        name = text.split(" = ", 1)[0]
                        om = _OPCODE.search(text.split(" = ", 1)[-1])
                        ops.append(Op(dev, name, om.group(1) if om else "?",
                                      text, ev.start_ns, ev.duration_ns))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        modules.append((dev, ev.name, ev.start_ns,
                                        ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(annotation_prefix):
                        annotations.append((ev.name, ev.start_ns,
                                            ev.start_ns + ev.duration_ns))
    # devices are renumbered densely so averages divide by the chips traced
    order = {d: i for i, d in enumerate(sorted(devices))}
    for o in ops:
        o.device = order[o.device]
    modules = [(order[d], n, s, e) for d, n, s, e in modules]
    return Reduced(ops=ops, modules=modules, annotations=annotations,
                   n_devices=len(devices))


def reduce_dir(log_dir: str) -> Reduced:
    return reduce_file(find_xplane(log_dir))
