"""Fault-tolerance machinery: preemption handling, step watchdog, straggler
log.

At 1000+ nodes the relevant failure modes are (a) preemption (SIGTERM with a
grace window), (b) hung collectives / dead hosts (steps stop completing),
(c) stragglers (steps complete but slowly on some hosts).  The trainer wires
these as:
  - PreemptionGuard: SIGTERM/SIGINT -> request a final checkpoint + clean exit
  - StepWatchdog: a daemon thread that aborts the process (so the cluster
    scheduler restarts it from the last checkpoint) if no step completes
    within `timeout_s` — the restart-from-checkpoint path IS the recovery
    mechanism for hung collectives
  - StragglerMonitor: per-step durations; steps slower than `factor` x the
    rolling median are logged (on real fleets this feeds host-quarantine)
"""
from __future__ import annotations

import os
import signal
import threading
import time
from collections import deque
from typing import Callable, Deque, List, Optional


class PreemptionGuard:
    def __init__(self):
        self.requested = False
        self._orig = {}

    def install(self):
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                self._orig[sig] = signal.signal(sig, self._handler)
            except ValueError:
                pass  # non-main thread (tests)
        return self

    def _handler(self, signum, frame):
        self.requested = True

    def uninstall(self):
        for sig, h in self._orig.items():
            signal.signal(sig, h)


class StepWatchdog:
    """Aborts the process if no heartbeat arrives within timeout_s.

    The default abort is not a bare ``os._exit``: it first emits a
    ``fault.watchdog`` obs instant and, when a trace sink is armed
    (``REPRO_OBS_TRACE``), dumps the trace ring — ``os._exit`` skips atexit
    handlers, so without the explicit dump a hung run's trace (the one
    artifact that says *where* it hung) would be lost.
    """

    def __init__(self, timeout_s: float = 1800.0, abort: Optional[Callable] = None):
        self.timeout_s = timeout_s
        self._last = time.monotonic()
        self._stop = threading.Event()
        self._abort = abort or self._default_abort
        self._thread: Optional[threading.Thread] = None
        self.fired = False

    def _default_abort(self):
        from repro import env as _env
        from repro.obs import trace as _ot

        _ot.instant("fault.watchdog", timeout_s=self.timeout_s)
        path = _env.get("REPRO_OBS_TRACE")
        if path:
            try:
                _ot.dump_chrome_trace(path)
            except OSError:
                pass  # aborting anyway; never mask the exit
        os._exit(42)

    def start(self):
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        return self

    @property
    def started(self) -> bool:
        return self._thread is not None

    def beat(self):
        self._last = time.monotonic()

    def stop(self):
        self._stop.set()

    def _run(self):
        while not self._stop.wait(min(self.timeout_s / 4, 5.0)):
            if time.monotonic() - self._last > self.timeout_s:
                self.fired = True
                self._abort()
                return


class StragglerMonitor:
    def __init__(self, window: int = 50, factor: float = 2.0):
        self.durations: Deque[float] = deque(maxlen=window)
        self.factor = factor
        self.events: List[dict] = []

    def record(self, step: int, duration_s: float) -> bool:
        """Returns True if this step was a straggler."""
        is_straggler = False
        if len(self.durations) >= 8:
            med = sorted(self.durations)[len(self.durations) // 2]
            if duration_s > self.factor * med:
                is_straggler = True
                self.events.append(
                    {"step": step, "duration_s": duration_s, "median_s": med}
                )
        self.durations.append(duration_s)
        return is_straggler

    @property
    def median(self) -> float:
        if not self.durations:
            return 0.0
        return sorted(self.durations)[len(self.durations) // 2]
