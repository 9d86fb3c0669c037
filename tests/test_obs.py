"""Observability layer (`repro.obs`): span nesting under exceptions,
ring-buffer overflow semantics, histogram percentile correctness vs numpy,
zero-overhead-when-off guarantees (no events + bit-identical dispatch),
metric registry lifecycle, cross-process trace-file schema validation, and
the profiler bridge (spans in a ``jax.profiler`` trace's host plane)."""
import contextlib
import glob
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro import dispatch, obs
from repro.dispatch import ProfileDB
from repro.obs import metrics, trace
from repro.obs.validate import TraceValidationError, validate_chrome_trace

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def obs_on():
    """Recording on with a clean ring + registry; restores env-derived state
    (and the env-sized ring) afterwards."""
    trace.set_enabled(True)
    obs.reset()
    yield
    trace.set_enabled(None)
    obs.reset()
    trace.configure(None)


# ---------------------------------------------------------------------------
# Spans & nesting
# ---------------------------------------------------------------------------


class TestSpans:
    def test_nesting_closes_under_exceptions(self, obs_on):
        with pytest.raises(ValueError, match="boom"):
            with trace.span("outer", x=1):
                assert trace.current_stack() == ("outer",)
                with trace.span("inner"):
                    assert trace.current_stack() == ("outer", "inner")
                    raise ValueError("boom")
        # the stack unwound and every B got its E, innermost first
        assert trace.current_stack() == ()
        evs = trace.events()
        assert [(e["ph"], e["name"]) for e in evs] == [
            ("B", "outer"), ("B", "inner"), ("E", "inner"), ("E", "outer")]
        # both E events carry the error; the B events carry depth + open args
        assert evs[2]["args"]["error"] == "ValueError: boom"
        assert evs[3]["args"]["error"] == "ValueError: boom"
        assert evs[0]["args"] == {"x": 1, "depth": 0}
        assert evs[1]["args"]["depth"] == 1
        # and the resulting stream passes the schema validator
        stats = validate_chrome_trace({"traceEvents": evs})
        assert stats["spans"] == 2

    def test_set_attaches_end_args(self, obs_on):
        with trace.span("work") as sp:
            sp.set(result=7)
        end = trace.events()[-1]
        assert end["ph"] == "E" and end["args"] == {"result": 7}

    def test_instant_records_thread_scope(self, obs_on):
        trace.instant("tick", n=3)
        (ev,) = trace.events()
        assert ev["ph"] == "i" and ev["s"] == "t" and ev["args"] == {"n": 3}

    def test_ring_overflow_keeps_newest(self, obs_on):
        trace.configure(capacity=8)
        for i in range(20):
            trace.instant("tick", i=i)
        evs = trace.events()
        assert len(evs) == 8
        assert [e["args"]["i"] for e in evs] == list(range(12, 20))
        assert trace.dropped_events() == 12
        trace.reset()
        assert trace.events() == [] and trace.dropped_events() == 0


# ---------------------------------------------------------------------------
# Histograms vs numpy
# ---------------------------------------------------------------------------


class TestHistogram:
    def test_percentiles_bound_numpy_nearest_rank(self, obs_on):
        rng = np.random.default_rng(0)
        data = rng.lognormal(mean=-7.0, sigma=2.0, size=5000)
        h = metrics.histogram("t.lat")
        for v in data:
            h.observe(v)
        data.sort()
        for p in (50, 90, 99):
            true = data[max(int(np.ceil(p / 100 * len(data))), 1) - 1]
            est = h.percentile(p)
            # upper bucket edge: bounds the nearest-rank value from above,
            # off by at most one bucket ratio (factor 2)
            assert true <= est <= true * 2.0 + 1e-12, (p, true, est)
        assert h.percentile(100) == pytest.approx(data[-1])
        s = h.summary()
        assert s["count"] == 5000
        assert s["min"] == pytest.approx(data[0])
        assert s["sum"] == pytest.approx(data.sum())

    def test_empty_and_bad_p(self, obs_on):
        h = metrics.histogram("t.empty")
        assert h.percentile(50) == 0.0
        assert h.summary()["count"] == 0
        h.observe(1.0)
        with pytest.raises(ValueError, match="outside"):
            h.percentile(101)

    def test_registry_kind_mismatch_raises(self, obs_on):
        metrics.counter("t.kind")
        with pytest.raises(TypeError):
            metrics.gauge("t.kind")

    def test_reset_zeroes_cached_references_in_place(self, obs_on):
        c = metrics.counter("t.cached")
        c.inc(5)
        metrics.reset()
        assert c.value == 0
        c.inc(2)
        assert metrics.snapshot()["counters"]["t.cached"] == 2


# ---------------------------------------------------------------------------
# Zero overhead when off
# ---------------------------------------------------------------------------


class TestDisabled:
    def test_no_events_no_metrics_when_off(self):
        trace.set_enabled(False)
        obs.reset()
        try:
            with trace.span("hot", x=1) as sp:
                sp.set(y=2)
                trace.instant("tick")
            metrics.counter("off.c").inc(3)
            metrics.gauge("off.g").set(4)
            metrics.histogram("off.h").observe(0.5)
            assert trace.events() == []
            snap = metrics.snapshot()
            assert snap["counters"]["off.c"] == 0
            assert snap["gauges"]["off.g"] == 0
            assert snap["histograms"]["off.h"]["count"] == 0
        finally:
            trace.set_enabled(None)
            obs.reset()

    def test_null_span_is_shared_singleton(self):
        trace.set_enabled(False)
        try:
            assert trace.span("a") is trace.span("b")
        finally:
            trace.set_enabled(None)

    def test_dispatch_resolution_bit_identical(self, tmp_path):
        """Turning obs on must not change which impl dispatch picks."""
        key = dispatch.linear_key(batch=8, d_in=64, d_out=64, k_kept=32,
                                  tile=16)
        db = ProfileDB(path=str(tmp_path / "db.json"))
        try:
            trace.set_enabled(False)
            dispatch.set_db(db)  # clears the memo
            off = dispatch.best_impl(key)
            trace.set_enabled(True)
            dispatch.set_db(db)
            on = dispatch.best_impl(key)
        finally:
            trace.set_enabled(None)
            dispatch.set_db(None)
            obs.reset()
        assert off is on or (off.name == on.name
                             and off.geometry == on.geometry)

    def test_dispatch_emits_decision_when_on(self, obs_on, tmp_path):
        key = dispatch.linear_key(batch=8, d_in=64, d_out=64, k_kept=32,
                                  tile=16)
        try:
            dispatch.set_db(ProfileDB(path=str(tmp_path / "db.json")))
            spec = dispatch.best_impl(key)
        finally:
            dispatch.set_db(None)
        dec = [e for e in trace.events() if e["name"] == "dispatch.decision"]
        assert len(dec) == 1
        args = dec[0]["args"]
        assert args["impl"] == spec.name
        assert args["token"] == key.token
        assert args["source"] in ("forced", "legacy", "degraded", "db",
                                  "profiled", "heuristic")
        assert "geometry" in args


# ---------------------------------------------------------------------------
# Trace export & validation
# ---------------------------------------------------------------------------


class TestExport:
    def test_dump_and_validate_roundtrip(self, obs_on, tmp_path):
        with trace.span("a"):
            with trace.span("b"):
                trace.instant("tick")
        path = tmp_path / "t.json"
        n = trace.dump_chrome_trace(path, metadata={"metrics": obs.snapshot()})
        assert n == 5
        stats = validate_chrome_trace(str(path))
        assert stats == {"events": 5, "spans": 2, "instants": 1, "lanes": 1}
        payload = json.loads(path.read_text())
        assert payload["otherData"]["dropped_events"] == 0
        assert "metrics" in payload["otherData"]

    def test_validator_rejects_unbalanced(self):
        evs = [{"name": "a", "ph": "B", "ts": 1.0, "pid": 1, "tid": 1}]
        with pytest.raises(TraceValidationError, match="open"):
            validate_chrome_trace({"traceEvents": evs})
        with pytest.raises(TraceValidationError, match="empty"):
            validate_chrome_trace({"traceEvents": []})

    def test_validator_rejects_nonmonotonic(self):
        evs = [
            {"name": "a", "ph": "i", "ts": 2.0, "pid": 1, "tid": 1},
            {"name": "b", "ph": "i", "ts": 1.0, "pid": 1, "tid": 1},
        ]
        with pytest.raises(TraceValidationError, match="backwards"):
            validate_chrome_trace({"traceEvents": evs})

    def test_cross_process_atexit_trace(self, tmp_path):
        """REPRO_OBS + REPRO_OBS_TRACE make a plain process emit a valid
        trace file at interpreter exit with no explicit dump call."""
        out = tmp_path / "proc.json"
        code = (
            "from repro.obs import trace\n"
            "with trace.span('outer', job='x'):\n"
            "    with trace.span('inner'):\n"
            "        trace.instant('tick', n=1)\n"
        )
        env = dict(os.environ, REPRO_OBS="1", REPRO_OBS_TRACE=str(out),
                   PYTHONPATH=str(REPO / "src"))
        subprocess.run([sys.executable, "-c", code], check=True, env=env,
                       cwd=str(REPO), timeout=120)
        stats = validate_chrome_trace(str(out))
        assert stats["spans"] == 2 and stats["instants"] == 1
        names = [e["name"]
                 for e in json.loads(out.read_text())["traceEvents"]]
        assert names == ["outer", "inner", "tick", "inner", "outer"]


# ---------------------------------------------------------------------------
# Profiler bridge
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def profiled(log_dir):
    """A ``jax.profiler`` session collecting host events, as the chip
    benchmark's traced window runs one."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def host_spans(log_dir, prefixes):
    """(name, start_ns, end_ns, stats) of the host-plane events of the one
    ``.xplane.pb`` under ``log_dir`` whose names start with ``prefixes``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(prefixes):
                        out.append((ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns,
                                    dict(ev.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


class TestProfilerBridge:
    def test_spans_reach_the_profiler_with_recording_off(self, tmp_path):
        trace.set_enabled(False)
        obs.reset()
        try:
            with profiled(tmp_path):
                with trace.span("bridge.outer", n=3) as sp:
                    with trace.span("bridge.inner"):
                        trace.instant("bridge.tick")
                    sp.set(done=1)
            assert trace.events() == []  # the ring stays empty
        finally:
            trace.set_enabled(None)
        (outer, inner) = host_spans(tmp_path, ("bridge.",))
        assert (outer[0], inner[0]) == ("bridge.outer", "bridge.inner")
        assert outer[1] <= inner[1] and inner[2] <= outer[2]
        assert outer[3] == {"n": 3, "done": 1}

    def test_recording_spans_also_reach_the_profiler(self, obs_on, tmp_path):
        with profiled(tmp_path):
            with trace.span("bridge.rec", k="v"):
                pass
        assert [e["name"] for e in trace.events()] == ["bridge.rec"] * 2
        ((name, _s, _e, stats),) = host_spans(tmp_path, ("bridge.",))
        assert name == "bridge.rec" and stats == {"k": "v"}
