"""The program's spans in a trace: the clock skew, device idle per span and
the two scheduler metrics on a hand-made trace, the reduction's fields left
as they were on the recorded ResNet trace, and the readers on a traced run
of the tiny serving cell on the CPU."""
import dataclasses
from pathlib import Path

import pytest

from chipbench import harness, spans
from chipbench import trace_reduce as tr
from chipbench.tests import tiny

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
MS = 1e6  # ns
STEP = ("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %a), kind=kLoop")


def _span(name, a, b, thread="python"):
    return spans.HostEvent(name, thread, a * MS, b * MS)


def _hand_made(skew_ms: float):
    """One decode iteration from 0 to 10 ms and an admission-only one from
    10.2 to 10.8 ms.  The step is launched at 2.5 ms and starts at once;
    it runs until 7.5 ms, and another program runs from 11 to 12 ms.  The
    device clock reads ``skew_ms`` early."""
    host = [
        _span("serve.iter", 0, 10), _span("serve.sweep", 0, 1),
        _span("serve.decode", 1, 8), _span("serve.tables", 1, 2),
        _span("serve.dispatch", 2, 3), _span("serve.wait", 3, 8),
        _span("serve.advance", 8, 10),
        _span("serve.iter", 10.2, 10.8), _span("serve.sweep", 10.3, 10.4),
        _span("engine.trace", 2.6, 2.7),
    ]
    d = skew_ms
    # the step is enqueued on an idle device; the other program is
    # enqueued at 3 ms behind it and starts late, bounding nothing
    launches = [spans.Launch(1, 2.5 * MS, (2.5 - d) * MS),
                spans.Launch(2, 3.0 * MS, (11 - d) * MS)]
    ops = [tr.Op(0, "%fusion.1", "fusion", STEP, (2.5 - d) * MS, 5 * MS),
           tr.Op(0, "%fusion.1", "fusion", STEP, (11 - d) * MS, 1 * MS)]
    mods = [(0, "jit_step(7)", (2.5 - d) * MS, (7.5 - d) * MS),
            (0, "jit_other(8)", (11 - d) * MS, (12 - d) * MS)]
    red = tr.Reduced(ops=ops, modules=mods, annotations=[], n_devices=1)
    red.spans, red.launches = host, launches
    return red


@pytest.mark.parametrize("skew_ms", [0.0, 1.0])
def test_idle_is_split_by_span_and_the_skew_corrected(skew_ms):
    red = _hand_made(skew_ms)
    skew, runs = spans.clock_skew(red)
    assert skew == pytest.approx(-skew_ms * MS) and runs == 2
    split = spans.split_idle(red)
    by = {k: v / MS for k, v in split.by_span.items()}
    assert by == pytest.approx({
        "serve.sweep": 1.0 + 0.1, "serve.tables": 1.0,
        "serve.dispatch": 0.5, "serve.wait": 0.5, "serve.advance": 2.0,
        "serve.iter": 0.5, None: 0.4})
    # of serve.wait's idle only the tail after the step's last op counts
    # (all of it here); idle outside every span does not count
    assert split.wait_tail_ns / MS == pytest.approx(0.5)
    assert split.counted_ns() / MS == pytest.approx(5.6)
    assert spans.sched_idle_pct(red, 12e-3) == pytest.approx(
        100 * 5.6 / 12)


def test_only_the_tail_of_the_wait_counts():
    """Idle inside ``serve.wait`` before the step's last op (here a 0.5 ms
    gap between two of its programs) is the device's own; the idle after
    it, until the host has the tokens, is the loop's."""
    red = _hand_made(0.0)
    step, other = red.ops
    red.ops = [dataclasses.replace(step, dur_ns=2.5 * MS),
               dataclasses.replace(step, start_ns=5.5 * MS, dur_ns=2 * MS),
               other]
    split = spans.split_idle(red)
    assert split.by_span["serve.wait"] / MS == pytest.approx(1.0)
    assert split.wait_tail_ns / MS == pytest.approx(0.5)
    assert split.counted_ns() / MS == pytest.approx(5.6)
    # a wait that ends while the device still runs has no tail
    red.ops[1] = dataclasses.replace(step, start_ns=5.5 * MS, dur_ns=3 * MS)
    assert spans.split_idle(red).wait_tail_ns == 0


def test_an_uncorrected_skew_would_move_idle_into_the_wait():
    """Without the correction, the 1 ms skew would put 1 ms of idle into
    the wait and take it from the tables: the reading would differ."""
    red = _hand_made(1.0)
    red.launches = []  # no runs paired: no correction
    assert spans.clock_skew(red) == (0.0, 0)
    by = spans.split_idle(red).by_span
    assert by["serve.wait"] / MS == pytest.approx(1.5)
    assert by["serve.tables"] / MS == pytest.approx(0.5)


def test_a_device_running_late_is_no_skew():
    """Runs that start after their enqueue bound nothing below zero."""
    red = _hand_made(-1.0)  # the device clock reads 1 ms late
    assert spans.clock_skew(red) == (0.0, 2)


def test_host_time_per_decode_iteration():
    red = _hand_made(0.0)
    # 10 ms less 5 ms of wait; the admission-only iteration has no wait
    assert spans.iteration_host_ns(red.spans) == [pytest.approx(5 * MS)]
    assert spans.sched_host_ms(red) == pytest.approx(5.0)


def test_innermost_segments_follow_the_nesting():
    segs = spans.serve_segments(_hand_made(0.0).spans)
    assert [(a / MS, b / MS, n) for a, b, n in segs[:7]] == [
        (0, 1, "serve.sweep"), (1, 2, "serve.tables"),
        (2, 3, "serve.dispatch"), (3, 8, "serve.wait"),
        (8, 10, "serve.advance"),
        (10.2, 10.3, "serve.iter"), (10.3, 10.4, "serve.sweep")]


@pytest.mark.parametrize("metric", ["sched_host_ms.serve",
                                    "sched_idle.serve"])
def test_readers_read_nothing_without_program_spans(metric):
    reader = harness.load_module(BENCH / "metrics" / f"{metric}.py")
    red = _hand_made(0.0)
    red.spans = []
    assert reader.read({"reduced": red, "window_s": 0.012}) is None
    assert reader.read({"reduced": None, "window_s": 0.012}) is None
    plain = tr.Reduced(ops=red.ops, modules=red.modules, annotations=[],
                       n_devices=1)  # a trace the parent's reducer made
    assert reader.read({"reduced": plain, "window_s": 0.012}) is None


@pytest.mark.parametrize("metric,value", [("sched_host_ms.serve", 5.0),
                                          ("sched_idle.serve", 560 / 12)])
def test_readers_on_the_hand_made_trace(metric, value):
    reader = harness.load_module(BENCH / "metrics" / f"{metric}.py")
    assert getattr(tr.reduce_file, "keeps_program_spans", False)
    ctx = {"reduced": _hand_made(1.0), "window_s": 0.012}
    assert reader.read(ctx) == pytest.approx(value)


def test_a_reader_leaves_the_reduction_plain_for_the_next_test():
    """The tests before this one loaded both scheduler readers, which wrap
    ``trace_reduce.reduce_file``; ``conftest.py`` undoes that after each
    test, so ``test_trace_reduce.py`` and the rest see the plain one."""
    assert not getattr(tr.reduce_file, "keeps_program_spans", False)
    assert tr.reduce_file is spans.plain_reduce_file()


@pytest.mark.parametrize("trace", ["resnet18_b32_v5e",
                                   "qwen2_gen256_v5e_short"])
def test_reduction_keeps_its_fields_on_the_recorded_traces(trace):
    """With the program's spans kept, every field the reduction had reads
    as before, so every existing per-layer metric does too; the ResNet
    trace, recorded before the spans existed, holds launches but no
    program spans, and the scheduler readers read nothing there."""
    path = str(DATA / f"{trace}.xplane.pb")
    spans.keep_program_spans()
    spans.keep_program_spans()  # idempotent
    red = tr.reduce_file(path)
    assert not hasattr(tr.reduce_file.__wrapped__, "keeps_program_spans")
    plain = tr.reduce_file.__wrapped__(path)
    assert (red.ops, red.modules, red.annotations, red.n_devices) == (
        plain.ops, plain.modules, plain.annotations, plain.n_devices)
    assert red.launches
    if trace.startswith("resnet"):
        assert red.spans == []
        assert spans.split_idle(red) is None
        assert spans.sched_host_ms(red) is None


def test_recorded_serving_trace():
    """Five decode steps of the serving cell recorded on a v5e chip
    (``data/qwen2_gen256_v5e_short.xplane.pb``): every run pairs with its
    enqueue, the device clock reads early, the spans nest as the scheduler
    opens them, and nearly all device idle falls inside a span."""
    spans.keep_program_spans()
    red = tr.reduce_file(str(DATA / "qwen2_gen256_v5e_short.xplane.pb"))
    assert len(red.launches) == len(red.modules) == 70
    skew, runs = spans.clock_skew(red)
    assert -1e6 < skew < 0 and runs == 70
    names = [s.name for s in sorted(red.spans, key=lambda x: x.start_ns)]
    assert names[:7] == ["serve.iter", "serve.sweep", "serve.decode",
                         "serve.tables", "serve.dispatch", "serve.wait",
                         "serve.advance"]
    assert len(spans.iteration_host_ns(red.spans)) == 5
    split = spans.split_idle(red)
    idle = sum(split.by_span.values())
    assert split.by_span[None] < 0.01 * idle
    assert 0 < split.counted_ns() < idle
    # each step's idle gap ends inside the decode step's own program
    gaps = spans.gap_end_modules(red, 4)
    assert {g["ends_in"] for g in gaps} == {"jit__lambda"}


def test_traced_tiny_serving_run_reads_the_host_time():
    """``--trace 1`` on the CPU: the program's spans reach the reduced
    trace, so the host time per iteration reads; with no device plane
    there is no device idle to split."""
    import types

    import jax

    from chipbench import run

    name = "qwen2-0.5b-c50.gen256"
    cfg, trf = tiny.tiny_qwen2()
    trf.update(warmup_iterations=3)
    cell = tiny.cell(name, cfg, trf, tiny.limits(name))
    args = types.SimpleNamespace(seed=2**31 + 3, seconds=0.3, trace=1,
                                 control=False)
    res = run.run_cell(cell, args, jax.devices()[:1])
    assert res["correct"], res["compared"]
    assert res["metrics"]["sched_host_ms.serve"]["value"] > 0
    assert "sched_idle.serve" not in res["metrics"]


def test_recorded_serving_trace_kernels_are_tagged_and_claimed():
    """Every Pallas kernel of the recorded serving trace carries its family
    tag, and the serve readers still claim each one."""
    import re

    from chipbench import readers

    red = tr.reduce_file(str(DATA / "qwen2_gen256_v5e_short.xplane.pb"))
    tag = re.compile(r'kernel_metadata=\{\s*"kernel":\s*"(\w+)"')
    kernels = [o for o in red.ops if o.is_pallas]
    assert {tag.search(o.text).group(1) for o in kernels} == {
        "colwise_nm", "paged_attn"}
    claims = {m: harness.load_module(BENCH / "metrics" / f"{m}.py").claims
              for m in ("linear_roofline.serve", "paged_attn_roofline.serve")}
    lines = readers.claim_report(red, claims, list(claims))
    assert lines and not [ln for ln in lines if "claimed by none" in ln]
