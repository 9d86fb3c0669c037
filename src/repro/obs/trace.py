"""Structured span/instant-event tracer with Chrome-trace-event export.

Design constraints (the reason this module exists instead of printf):

  * **Compiled out by default.** Every instrumentation point first asks
    :func:`enabled`; when observability is off (the default) ``span()``
    returns a shared no-op context manager and ``instant()`` returns without
    allocating, so the serving hot loop pays one module-global bool read per
    probe.  ``REPRO_OBS=on`` (or :func:`set_enabled`) turns recording on.
  * **Bounded memory.** Events land in a thread-safe ring buffer
    (``REPRO_OBS_RING`` entries, default 65536).  Overflow drops the *oldest*
    events and counts the drops — a long-running server can leave tracing on
    without unbounded growth.
  * **Ambient nesting.** A contextvar stack (the same ambient-scope pattern
    as ``dispatch.phase_scope``) tracks the open-span path, so events carry
    their nesting depth/parent without threading a span object through call
    signatures; spans close correctly under exceptions (``finally``).
  * **Profiler bridge.** While a ``jax.profiler`` session is collecting
    host events, every span also opens a ``jax.profiler.TraceAnnotation``
    of its name and args, whether or not recording is on, so the program's
    spans land in the profiler's host plane on the same clock as the device
    ops.  With no session active a span pays one ``is_enabled`` check.
    Instants stay ring-only.
  * **Standard export.** :func:`dump_chrome_trace` writes the Chrome
    trace-event JSON format (``{"traceEvents": [...]}``) loadable in
    Perfetto / ``chrome://tracing``; spans are B/E duration-event pairs,
    instants are ``ph="i"`` events.  ``REPRO_OBS_TRACE=<path>`` dumps
    automatically at interpreter exit.

See ``docs/observability.md`` for the event schema and env-var reference.
"""
from __future__ import annotations

import atexit
import contextvars
import json
import os
import tempfile
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation as _Annotation

__all__ = [
    "enabled", "set_enabled", "configure", "span", "instant", "events",
    "reset", "dropped_events", "dump_chrome_trace", "current_stack", "now_us",
]

DEFAULT_RING = 65536

# process-relative clock origin: Chrome trace ts are microseconds from an
# arbitrary epoch, so perf_counter (monotonic, high-resolution) is the right
# source; anchoring at import keeps the numbers small and diff-friendly
_T0 = time.perf_counter()


def now_us() -> float:
    """Microseconds since module import (monotonic)."""
    return (time.perf_counter() - _T0) * 1e6


def _env_enabled() -> bool:
    from repro import env as _env

    return bool(_env.get("REPRO_OBS"))


def _env_ring() -> int:
    from repro import env as _env

    return max(int(_env.get("REPRO_OBS_RING")), 1)


# module-global fast path: instrumentation points read one bool
_ENABLED: bool = _env_enabled()

# is a profiler session collecting host events? (one TraceMe level check)
_profiling = _Annotation.is_enabled


def enabled() -> bool:
    """Is event recording on?  The single gate every probe checks first."""
    return _ENABLED


def set_enabled(value: Optional[bool]) -> None:
    """Force recording on/off; ``None`` re-reads ``REPRO_OBS`` from the
    environment (tests toggling the env var mid-process)."""
    global _ENABLED
    _ENABLED = _env_enabled() if value is None else bool(value)


class _RingBuffer:
    """Thread-safe bounded event store; overflow drops oldest, counts drops."""

    def __init__(self, capacity: int):
        self._lock = threading.Lock()
        self._buf: deque = deque(maxlen=capacity)
        self.dropped = 0

    @property
    def capacity(self) -> int:
        return self._buf.maxlen or 0

    def append(self, event: Dict) -> None:
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self.dropped += 1
            self._buf.append(event)

    def snapshot(self) -> List[Dict]:
        with self._lock:
            return list(self._buf)

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self.dropped = 0


_RING = _RingBuffer(_env_ring())

# open-span name path of the current (logical) thread of execution; a tuple
# so each set() is an immutable snapshot (async/generator-safe)
_STACK: contextvars.ContextVar = contextvars.ContextVar("obs_span_stack",
                                                        default=())


def configure(capacity: Optional[int] = None) -> None:
    """Replace the ring buffer (tests sizing overflow behaviour).  ``None``
    re-reads ``REPRO_OBS_RING``."""
    global _RING
    _RING = _RingBuffer(_env_ring() if capacity is None else max(capacity, 1))


def current_stack() -> tuple:
    """Names of the spans currently open in this execution context."""
    return _STACK.get()


def _event(ph: str, name: str, cat: str, args: Optional[Dict] = None,
           ts: Optional[float] = None) -> Dict:
    ev = {
        "name": name,
        "cat": cat,
        "ph": ph,
        "ts": now_us() if ts is None else ts,
        "pid": os.getpid(),
        "tid": threading.get_ident(),
    }
    if args:
        ev["args"] = args
    return ev


class _Span:
    """Recording span: emits a B event on enter, an E event on exit (also on
    exceptions), and maintains the ambient nesting stack; also annotates the
    profiler's timeline while a session is active."""

    __slots__ = ("name", "cat", "args", "_token", "_extra", "_ann")

    def __init__(self, name: str, cat: str, args: Dict):
        self.name = name
        self.cat = cat
        self.args = args
        self._token = None
        self._extra: Dict = {}
        self._ann = None

    def set(self, **kwargs) -> "_Span":
        """Attach result args known only at span end (merged into E)."""
        self._extra.update(kwargs)
        if self._ann is not None:
            self._ann.set_metadata(**kwargs)
        return self

    def __enter__(self) -> "_Span":
        stack = _STACK.get()
        args = dict(self.args)
        args["depth"] = len(stack)
        self._token = _STACK.set(stack + (self.name,))
        _RING.append(_event("B", self.name, self.cat, args))
        if _profiling():
            self._ann = _Annotation(self.name, **self.args)
            self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        if self._token is not None:
            _STACK.reset(self._token)
        args = dict(self._extra)
        if exc is not None:
            args["error"] = f"{exc_type.__name__}: {exc}"
        _RING.append(_event("E", self.name, self.cat, args or None))
        return False  # never swallow


class _ProfilerSpan:
    """Span handed out while recording is off but a profiler session is
    active: a ``TraceAnnotation`` only, nothing in the ring buffer."""

    __slots__ = ("_ann",)

    def __init__(self, name: str, args: Dict):
        self._ann = _Annotation(name, **args)

    def set(self, **kwargs) -> "_ProfilerSpan":
        self._ann.set_metadata(**kwargs)
        return self

    def __enter__(self) -> "_ProfilerSpan":
        self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._ann.__exit__(exc_type, exc, tb)
        return False


class _NullSpan:
    """No-op span handed out while recording is off and no profiler session
    is active (one shared instance)."""

    __slots__ = ()

    def set(self, **kwargs) -> "_NullSpan":
        return self

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


_NULL_SPAN = _NullSpan()


def span(name: str, cat: str = "repro", **args):
    """Context manager recording a B/E duration pair around its body.

    With recording off it records nothing; while a ``jax.profiler``
    session is active it still annotates the profiler's timeline, and
    otherwise returns a shared no-op object and allocates nothing.
    ``with span("dispatch.resolve", token=...) as s: ...;
    s.set(impl=...)`` attaches end-of-span result args.
    """
    if _ENABLED:
        return _Span(name, cat, args)
    if _profiling():
        return _ProfilerSpan(name, args)
    return _NULL_SPAN


def instant(name: str, cat: str = "repro", **args) -> None:
    """Record a point-in-time event (Chrome ``ph="i"``, thread scope)."""
    if not _ENABLED:
        return
    ev = _event("i", name, cat, args or None)
    ev["s"] = "t"
    _RING.append(ev)


def events() -> List[Dict]:
    """Snapshot of the ring buffer (oldest first)."""
    return _RING.snapshot()


def dropped_events() -> int:
    """Events lost to ring overflow since the last :func:`reset`."""
    return _RING.dropped


def reset() -> None:
    """Clear the ring buffer and the drop counter."""
    _RING.clear()


def dump_chrome_trace(path, metadata: Optional[Dict] = None) -> int:
    """Write the buffered events as a Chrome trace-event JSON file.

    The file is the object form (``{"traceEvents": [...]}``) so Perfetto /
    ``chrome://tracing`` load it directly; ``metadata`` (e.g. a metrics
    snapshot) lands under ``otherData``.  Atomic write (temp + rename) so a
    crash mid-dump never leaves a torn file.  Returns the event count.
    """
    evs = _RING.snapshot()
    payload = {
        "traceEvents": evs,
        "displayTimeUnit": "ms",
        "otherData": dict(metadata or {}, dropped_events=_RING.dropped),
    }
    path = os.fspath(path)
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, prefix=os.path.basename(path),
                               suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(payload, f, indent=1, default=str)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise
    return len(evs)


def _atexit_dump() -> None:
    from repro import env as _env

    path = _env.get("REPRO_OBS_TRACE")
    if path and _RING.snapshot():
        try:
            dump_chrome_trace(path)
        except OSError:
            pass  # exiting anyway; never mask the real exit


atexit.register(_atexit_dump)
