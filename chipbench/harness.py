"""What every cell shares: finding its files by name, the device check, the
measured window, the compile counter, the program's dispatch report and
the result line."""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import shutil
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

#: lowering of a jaxpr to a program: one per compile or compile-cache read
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class Refused(Exception):
    """The run cannot measure this cell here (no chip, missing files)."""


def load_module(path: Path):
    """Import a file of the benchmark by its path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise Refused(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> Any:
    if not path.is_file():
        raise Refused(f"missing {path}")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files resolved."""

    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    driver: Any
    metric_readers: Dict[str, Any]

    @classmethod
    def resolve(cls, name: str, bench: Optional[Dict] = None) -> "Cell":
        bench = bench if bench is not None else read_json(ROOT / "BENCHMARK.json")
        by_name = {w["name"]: w for w in bench["workloads"]}
        if name not in by_name:
            raise Refused(f"no workload {name!r} in BENCHMARK.json")
        w = by_name[name]
        configs = {c["name"]: c for c in bench["configs"]}
        config = read_json(ROOT / configs[w["config"]]["file"])
        traffic = read_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
        limits = read_json(BENCH_DIR / "limits" / f"{name}.json")
        e2e = [m for m in bench["end_to_end"]
               if name in m.get("workloads", [name])]
        e2e_names = {m["name"] for m in e2e}
        per_layer = [m for m in bench["per_layer"]
                     if name in m.get("workloads", [name])
                     and m["moves"] in e2e_names]
        driver = load_module(BENCH_DIR / "drivers" / f"{traffic['driver']}.py")
        readers = {m["name"]: load_module(BENCH_DIR / "metrics" /
                                          f"{m['name']}.py")
                   for m in per_layer}
        return cls(name=name, chips=int(w["chips"]), config=config,
                   traffic=traffic, limits=limits, end_to_end=e2e,
                   per_layer=per_layer, driver=driver,
                   metric_readers=readers)


# ---------------------------------------------------------------------------
# seeds
# ---------------------------------------------------------------------------


def seed_words(seed: int):
    """Two 32-bit words from a seed of any size (the driver's seeds pass
    2**31), for ``jax.random.wrap_key_data``."""
    import numpy as np

    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


def jax_key(seed: int, salt: int = 0):
    import jax
    import jax.numpy as jnp

    return jax.random.wrap_key_data(
        jnp.asarray(seed_words(seed * 1000003 + salt)), impl="threefry2x32")


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------


class CompileCounter:
    """Counts lowerings (compiles and compile-cache reads) by the time
    they happened."""

    def __init__(self):
        import jax

        self.times: List[float] = []

        def listener(event, _duration, **_kw):
            if event == LOWER_EVENT:
                self.times.append(time.perf_counter())

        jax.monitoring.register_event_duration_secs_listener(listener)

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t < t1)


class Window:
    """The measured window of one run.

    ``start()`` ends set-up: it reports what dispatch chose (``close_setup``)
    and, with tracing on, starts the profiler (host annotations only, no
    Python tracer).  ``unit(name)`` marks one unit of work (a batch, a
    scheduler iteration, a step) in the trace.  ``stop()`` ends the window;
    the caller has waited for the device first."""

    def __init__(self, seconds: float, trace: bool, report):
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.report = report
        self.t0 = self.t1 = None
        self.log_dir: Optional[str] = None
        self._ann = None

    def start(self) -> float:
        close_setup(self.report)
        if self.trace:
            import jax

            self.log_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.t0 = time.perf_counter()
        return self.t0

    def expired(self, now: Optional[float] = None) -> bool:
        return (now or time.perf_counter()) - self.t0 >= self.seconds

    def unit(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(f"chipbench.{name}")

    def begin_unit(self, name: str) -> None:
        """Open a unit span that ``end_unit`` closes (for loops that only
        offer a callback between units)."""
        if self.trace:
            self.end_unit()
            self._ann = self.unit(name)
            self._ann.__enter__()

    def end_unit(self) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    def stop(self, t1: Optional[float] = None) -> float:
        self.t1 = t1 or time.perf_counter()
        self.end_unit()
        if self.trace:
            import jax

            jax.profiler.stop_trace()
        return self.t1

    @property
    def seconds_measured(self) -> float:
        return self.t1 - self.t0

    def reduce(self, keep: Optional[str] = None):
        """The reduced trace; ``keep`` names a file to copy it to first."""
        from chipbench import trace_reduce

        try:
            path = trace_reduce.find_xplane(self.log_dir)
            if keep:
                shutil.copyfile(path, keep)
            return trace_reduce.reduce_file(path)
        finally:
            shutil.rmtree(self.log_dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# the program's own report: dispatch decisions, counters, memory
# ---------------------------------------------------------------------------


def dispatch_report() -> List[str]:
    """One line per (op, phase) with the impls dispatch resolved, from the
    program's ``dispatch.decision`` events."""
    from repro.obs import trace as ot

    seen: Dict[tuple, set] = {}
    for ev in ot.events():
        a = ev.get("args", {})
        if ev.get("name") == "dispatch.decision" and a.get("source") != "legacy":
            seen.setdefault((a.get("op"), a.get("phase") or "-"), set()).add(
                f"{a.get('impl')} [{a.get('backend')}, {a.get('source')}]")
    return [f"dispatch: op={op} phase={phase} impl={', '.join(sorted(v))}"
            for (op, phase), v in sorted(seen.items())]


def counter_report() -> List[str]:
    from repro.obs import metrics as om

    return [f"counter: {n} = {om.counter(n).value:g}"
            for n in ("dispatch.quarantine", "dispatch.execute_retries")]


def open_setup() -> None:
    """Turn the program's event recording on for set-up: dispatch resolves
    an impl, and quarantines one, while a step is traced, and both the
    ``dispatch.decision`` events and the quarantine and retry counters
    record only while recording is on."""
    from repro import obs

    obs.set_enabled(True)


def close_setup(report) -> None:
    """End of set-up: report the impls dispatch chose and its counters,
    then turn the program's event recording off, so that the window runs
    the program as it runs by default."""
    from repro import obs

    report(dispatch_report())
    report(counter_report())
    obs.set_enabled(False)


def quarantine_report() -> List[str]:
    """The program's quarantine list after the window.  It is state, kept
    whether recording is on or not, so it also shows an impl quarantined
    inside the window (where a step would have had to be traced anew)."""
    from repro import dispatch

    q = sorted(dispatch.quarantined())
    return [f"dispatch: quarantined after the window: "
            f"{', '.join(f'{op}:{impl}' for op, impl in q) or 'none'}"]


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


# ---------------------------------------------------------------------------
# outcome and result line
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Compared:
    """One number of the correctness comparison beside its limit; it
    passes while ``value <= limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back after its window and its check."""

    window: Window
    end_to_end: Dict[str, float]
    work: Dict[str, Any]           # counts the per-layer readers use
    compared: List[Compared]
    attempted: int
    failed: int
    memory_peak_bytes: int
    reduced: Any = None            # trace_reduce.Reduced of the traced run
    control: Dict[str, float] = dataclasses.field(default_factory=dict)


def emit(lines: List[str]) -> None:
    for line in lines:
        print(line, flush=True)
