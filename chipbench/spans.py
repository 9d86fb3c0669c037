"""The program's own spans in a profiler trace, and the device idle time
they hold.

With a profiler session active, every ``repro.obs`` span is also a
``jax.profiler.TraceAnnotation``, so the program's spans (``serve.*``,
``engine.*``, ``dispatch.*``) land in the trace's host plane, on the
profiler's clock, beside the device ops.  The host also records each
program it enqueues on the device (``DoEnqueueProgram``), with the run id
that the device's ``XLA Modules`` event of that run carries.

``trace_reduce.reduce_file`` keeps only the benchmark's own annotations;
``keep_program_spans`` makes it also keep the program's spans, in
``Reduced.spans``, and each run's enqueue and start, in
``Reduced.launches``.  Every other field of ``Reduced`` is left as
``reduce_file`` made it.  The two scheduler readers call it when they are
loaded, so that the benchmark's run reads the spans of its trace; the
tests undo it after each test (``chipbench/tests/conftest.py``).

The device and host clocks of one trace disagree by up to a millisecond.
A device program cannot start before the host enqueued it, so the most
negative (program start - enqueue) over the window's runs bounds the skew;
it is applied to the device ops before idle time is attributed to spans.

    python chipbench/spans.py TRACE.xplane.pb

prints the skew, the device idle time per innermost ``serve.*`` span, the
long idle gaps with the spans that held them, and the long iterations.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import re
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

PREFIXES = ("serve.", "engine.", "dispatch.")
WAIT = "serve.wait"
ITER = "serve.iter"
ENQUEUE = "DoEnqueueProgram"

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class HostEvent:
    """One program span in the host plane."""

    name: str
    thread: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass(frozen=True)
class Launch:
    """One run of a device program: when the host enqueued it and when it
    started on the first device, each on its own clock."""

    run_id: int
    host_ns: float
    device_ns: float


def _run_id(ev) -> Optional[int]:
    for key, value in ev.stats:
        if key == "run_id":
            return int(value)
    return None


def read_program_events(path: str) -> Tuple[List[HostEvent], List[Launch]]:
    """(program spans, launches) of an ``.xplane.pb``: the host planes'
    spans, and each run of the first device that the host's enqueue of the
    same run id can be paired with."""
    from jax.profiler import ProfileData

    spans: List[HostEvent] = []
    enqueued: Dict[int, float] = {}
    started: Dict[int, Dict[int, float]] = {}
    for plane in ProfileData.from_file(path).planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            runs = started.setdefault(int(m.group(1)), {})
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        rid = _run_id(ev)
                        if rid is not None:
                            runs[rid] = ev.start_ns
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIXES):
                        spans.append(HostEvent(ev.name, line.name,
                                               ev.start_ns,
                                               ev.start_ns + ev.duration_ns))
                    elif ev.name == ENQUEUE:
                        rid = _run_id(ev)
                        if rid is not None:
                            enqueued[rid] = min(ev.start_ns,
                                                enqueued.get(rid, ev.start_ns))
    first = started[min(started)] if started else {}
    launches = [Launch(rid, enqueued[rid], t)
                for rid, t in sorted(first.items()) if rid in enqueued]
    return spans, launches


def keep_program_spans() -> None:
    """Make ``trace_reduce.reduce_file`` also read the program's spans and
    the launches of the same file into ``Reduced.spans`` and
    ``Reduced.launches``.  Idempotent; the readers that need the spans call
    it when they are loaded, before the run."""
    from chipbench import trace_reduce

    if getattr(trace_reduce.reduce_file, "keeps_program_spans", False):
        return
    reduce_file = trace_reduce.reduce_file

    @functools.wraps(reduce_file)
    def with_spans(path, *args, **kwargs):
        red = reduce_file(path, *args, **kwargs)
        red.spans, red.launches = read_program_events(path)
        return red

    with_spans.keeps_program_spans = True
    trace_reduce.reduce_file = with_spans


def plain_reduce_file():
    """``trace_reduce.reduce_file`` without the wrapper of
    ``keep_program_spans``."""
    from chipbench import trace_reduce

    fn = trace_reduce.reduce_file
    while getattr(fn, "keeps_program_spans", False):
        fn = fn.__wrapped__
    return fn


def program_spans(red) -> List[HostEvent]:
    return list(getattr(red, "spans", None) or []) if red is not None else []


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def _merged(iv) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _intersect(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Intersection of two sorted lists of disjoint intervals."""
    out = []
    i = j = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length(iv) -> float:
    return sum(e - s for s, e in iv)


def _innermost(spans: Sequence[HostEvent]) -> List[Tuple[float, float, str]]:
    """Disjoint segments of one thread's properly nested spans, each
    labelled by the innermost span open over it."""
    out = []
    stack: List[Tuple[float, str]] = []
    t = 0.0
    for sp in sorted(spans, key=lambda x: (x.start_ns, -x.end_ns)):
        while stack and stack[-1][0] <= sp.start_ns:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end
        if stack and sp.start_ns > t:
            out.append((t, sp.start_ns, stack[-1][1]))
        stack.append((sp.end_ns, sp.name))
        t = sp.start_ns
    while stack:
        end, name = stack.pop()
        if end > t:
            out.append((t, end, name))
            t = end
    return out


def serve_segments(spans: Sequence[HostEvent]
                   ) -> List[Tuple[float, float, str]]:
    """Innermost ``serve.*`` span over time, on every thread that has one;
    spans of other prefixes (a retrace inside a dispatch) count as the
    ``serve.*`` span around them."""
    by_thread: Dict[str, list] = collections.defaultdict(list)
    for sp in spans:
        if sp.name.startswith("serve."):
            by_thread[sp.thread].append(sp)
    return sorted(seg for th in by_thread.values() for seg in _innermost(th))


# ---------------------------------------------------------------------------
# clock skew and device idle time
# ---------------------------------------------------------------------------


def _short(module: str) -> str:
    return re.sub(r"\(\d+\)$", "", module)


def clock_skew(red) -> Tuple[float, int]:
    """(skew in ns, runs it rests on): the most negative (start on the
    device - enqueue on the host) over the runs of the first device, or 0
    where none is negative.  Runs enqueued while the device is busy start
    late and bound nothing; a run enqueued on an idle device bounds the
    skew within its launch latency."""
    diffs = [ln.device_ns - ln.host_ns
             for ln in getattr(red, "launches", None) or []]
    if not diffs:
        return 0.0, 0
    return min(0.0, min(diffs)), len(diffs)


def device_idle(red, skew_ns: float, lo: float, hi: float) -> List[Interval]:
    """Intervals of [lo, hi] in which no op ran on device 0, on the host's
    clock (device times minus ``skew_ns``)."""
    busy = _merged((o.start_ns - skew_ns, o.end_ns - skew_ns)
                   for o in red.ops if o.device == 0)
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class IdleSplit:
    """Device idle time of a traced window split by the innermost
    ``serve.*`` span over it (``None``: outside every span);
    ``wait_tail_ns`` is the part of ``serve.wait``'s that follows the
    device's last op in each wait."""

    skew_ns: float
    runs: int
    idle: List[Interval]
    segments: List[Tuple[float, float, str]]
    by_span: Dict[Optional[str], float]
    wait_tail_ns: float

    def counted_ns(self) -> float:
        """Idle time the loop's host work causes: inside a ``serve.*`` span
        other than ``serve.wait``, and in each ``serve.wait`` after the
        device's last op there, while the host learns that the step is
        done and reads its tokens back."""
        return self.wait_tail_ns + sum(
            v for k, v in self.by_span.items() if k is not None and k != WAIT)


def _tail(idle: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of the idle interval that holds ``hi``, clipped to
    [lo, hi]: the idle from the last op before ``hi`` on."""
    i = bisect.bisect_left(idle, (hi,)) - 1
    if i < 0 or idle[i][1] < hi:
        return 0.0
    return hi - max(lo, idle[i][0])


def split_idle(red) -> Optional[IdleSplit]:
    """The device idle of a reduced trace attributed to the program's
    ``serve.*`` spans, or ``None`` where the trace holds none."""
    spans = program_spans(red)
    segs = serve_segments(spans)
    if not segs or not red.n_devices:
        return None
    skew, runs = clock_skew(red)
    dev = [(o.start_ns - skew, o.end_ns - skew) for o in red.ops
           if o.device == 0]
    lo = min([segs[0][0]] + [s for s, _ in dev])
    hi = max([max(e for _, e, _ in segs)] + [e for _, e in dev])
    idle = device_idle(red, skew, lo, hi)
    by_span: Dict[Optional[str], float] = collections.Counter()
    inside = 0.0
    for name in {n for _, _, n in segs}:
        mine = _merged((s, e) for s, e, n in segs if n == name)
        by_span[name] = _length(_intersect(idle, mine))
        inside += by_span[name]
    by_span[None] = _length(idle) - inside
    tail = sum(_tail(idle, s, e) for s, e, n in segs if n == WAIT)
    return IdleSplit(skew, runs, idle, segs, dict(by_span), tail)


# ---------------------------------------------------------------------------
# the scheduler's metrics
# ---------------------------------------------------------------------------


def iteration_host_ns(spans: Sequence[HostEvent]) -> List[float]:
    """Per decode iteration (a ``serve.iter`` holding a ``serve.wait``),
    its duration less the time of the ``serve.wait`` spans inside it: the
    host's own share of the iteration."""
    waits: Dict[str, List[HostEvent]] = collections.defaultdict(list)
    for sp in spans:
        if sp.name == WAIT:
            waits[sp.thread].append(sp)
    for w in waits.values():
        w.sort(key=lambda x: x.start_ns)
    starts = {th: [w.start_ns for w in ws] for th, ws in waits.items()}
    out = []
    for it in spans:
        if it.name != ITER:
            continue
        ws = waits.get(it.thread, [])
        i = bisect.bisect_left(starts.get(it.thread, []), it.start_ns)
        waited, n = 0.0, 0
        while i < len(ws) and ws[i].end_ns <= it.end_ns:
            waited += ws[i].end_ns - ws[i].start_ns
            n += 1
            i += 1
        if n:
            out.append(it.end_ns - it.start_ns - waited)
    return out


def sched_host_ms(red) -> Optional[float]:
    """Median host time per decode iteration, in ms."""
    host = iteration_host_ns(program_spans(red))
    return statistics.median(host) * 1e-6 if host else None


def sched_idle_pct(red, window_s: float) -> Optional[float]:
    """Device idle the loop's host work causes (``IdleSplit.counted_ns``),
    in percent of the window."""
    if red is None or window_s <= 0:
        return None
    split = split_idle(red)
    if split is None:
        return None
    return 100.0 * split.counted_ns() * 1e-9 / window_s


# ---------------------------------------------------------------------------
# report of one kept trace
# ---------------------------------------------------------------------------


def report(red, stall_ms: float = 50.0, long_iter_ms: float = 70.0) -> Dict:
    """What the spans show in one trace: skew, idle per span, the idle
    gaps over ``stall_ms`` with the spans that held them, and the
    iterations over ``long_iter_ms`` with their children's durations."""
    split = split_idle(red)
    if split is None:
        return {"spans": 0}
    spans = program_spans(red)
    gaps = []
    for s, e in split.idle:
        if e - s >= stall_ms * 1e6:
            held = collections.Counter()
            for a, b, n in split.segments:
                ov = min(b, e) - max(a, s)
                if ov > 0:
                    held[n] += ov * 1e-6
            held[None] = (e - s) * 1e-6 - sum(held.values())
            gaps.append({"start_ms": s * 1e-6, "ms": (e - s) * 1e-6,
                         "held_ms": {str(k): v for k, v in held.items()}})
    iters = []
    for it in spans:
        if it.name == ITER and it.end_ns - it.start_ns >= long_iter_ms * 1e6:
            kids = collections.Counter()
            for sp in spans:
                if (sp.thread == it.thread and sp is not it
                        and sp.start_ns >= it.start_ns
                        and sp.end_ns <= it.end_ns):
                    kids[sp.name] += (sp.end_ns - sp.start_ns) * 1e-6
            iters.append({"start_ms": it.start_ns * 1e-6,
                          "ms": (it.end_ns - it.start_ns) * 1e-6,
                          "spans_ms": dict(kids)})
    host = iteration_host_ns(spans)
    durations: Dict[str, List[float]] = collections.defaultdict(list)
    for sp in spans:
        durations[sp.name].append((sp.end_ns - sp.start_ns) * 1e-6)
    return {
        "spans": len(spans),
        "skew_ms": split.skew_ns * 1e-6, "skew_runs": split.runs,
        "idle_ms": _length(split.idle) * 1e-6,
        "idle_ms_by_span": {str(k): v * 1e-6
                            for k, v in sorted(split.by_span.items(),
                                               key=lambda kv: -kv[1])},
        "wait_tail_ms": split.wait_tail_ns * 1e-6,
        "counted_idle_ms": split.counted_ns() * 1e-6,
        "iterations": len(host),
        "host_ms_per_iteration": ([statistics.median(host) * 1e-6,
                                   min(host) * 1e-6, max(host) * 1e-6]
                                  if host else None),
        "span_ms_median": {k: statistics.median(v)
                           for k, v in sorted(durations.items())},
        "stalls": gaps,
        "long_iterations": iters,
    }


def gap_end_modules(red, n: int = 10) -> List[Dict]:
    """The ``n`` longest idle gaps on device 0 (device clock, as
    ``Reduced.idle_gaps`` finds them), each with the device program whose
    run holds the op that ends the gap."""
    mods = [m for m in red.modules if m[0] == 0]
    busy = _merged((o.start_ns, o.end_ns) for o in red.ops if o.device == 0)
    gaps = sorted(((b[0] - a[1], b[0]) for a, b in zip(busy, busy[1:])
                   if b[0] > a[1]), reverse=True)[:n]
    out = []
    for dur, end in gaps:
        holding = [_short(name) for _d, name, s, e in mods if s <= end <= e]
        out.append({"ms": dur * 1e-6, "ends_in": holding[-1] if holding
                    else "-"})
    return out


def main(argv=None) -> int:
    import argparse
    import json
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from chipbench import trace_reduce

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help=".xplane.pb of a traced run")
    args = ap.parse_args(argv)
    keep_program_spans()
    red = trace_reduce.reduce_file(args.trace)
    rep = report(red)
    rep["idle_gaps"] = red.idle_gaps(10)
    rep["gap_end_modules"] = gap_end_modules(red)
    print(json.dumps(rep, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
