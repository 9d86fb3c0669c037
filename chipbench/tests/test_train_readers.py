"""The sharded finetune cell's readers on one step recorded on a v5e 2x2
(``data/qwen2_7b_ft4_v5e_short.xplane.pb``): collectives and idle read
over four devices from the leaf operations, the linear reader claims
exactly the kernels tagged ``colwise_nm``, and no kernel goes unclaimed;
and on a hand-made trace whose collective and idle gap lie inside a
layer scan's ``while``.

The trace is the second of the three steps of ``run.py --workload
qwen2-7b-l8-c50.ft4 --seconds 3 --trace 1 --keep-trace`` at batches of
4 x 512 tokens, cut to stay
small: on each chip its ``XLA Modules`` run and the ``XLA Ops`` inside
it, without the ``Async XLA Ops`` line, the stats of events and of their
metadata, and the host's metadata plane, none of which the reduction
reads; names of ops other than Pallas kernels end at their opcode."""
import types
from pathlib import Path

import pytest

from chipbench import harness, readers, trace_reduce, work_train
from chipbench.tests import tiny

DATA = Path(__file__).resolve().parent / "data"
BENCH = DATA.parent.parent
TRACE = DATA / "qwen2_7b_ft4_v5e_short.xplane.pb"
CONFIG = tiny.load("configs/qwen2-7b-l8-c50.json")
#: tokens a step in the recorded trace
TOKENS = 4 * 512


def _reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py")


def _leaf_ops(red):
    return harness.load_module(
        BENCH / "drivers" / "train_sharded.py").leaf_ops(red)


@pytest.fixture(scope="module")
def ctx():
    red = _leaf_ops(trace_reduce.reduce_file(str(TRACE)))
    (_, _, start, end), = [m for m in red.modules if m[0] == 0]
    return {"reduced": red, "window_s": (end - start) * 1e-9,
            "device_kind": "TPU v5 lite",
            "cell": types.SimpleNamespace(config=CONFIG),
            "work": {"steps": 1, "tokens": TOKENS, "seq_len": 512,
                     "chips": 4}}


def test_four_devices_busy_idle_and_collectives(ctx):
    red = ctx["reduced"]
    assert red.n_devices == 4
    idle = _reader("device_idle.train").read(ctx)
    assert 0 <= idle < 100
    assert idle == pytest.approx(100 * (1 - red.busy_s() / ctx["window_s"]))
    assert not any(o.opcode == "while" for o in red.ops)
    exposed = _reader("collective_exposed.train").read(ctx)
    coll = sum(o.dur_ns for o in red.ops if o.is_collective) \
        / red.n_devices * 1e-9
    assert 0 < exposed <= 100 * coll / ctx["window_s"] + 1e-9
    # the step's collectives run inside its layer scans: the sync ones
    # and the waits on permutes are exposed, not hidden by the scan
    assert exposed > 0.5 * 100 * coll / ctx["window_s"]


def _nested_trace():
    """Two devices, one step: a ``while`` over 0-100 us holds a fusion
    (0-40), an all-reduce (40-60) and a fusion (70-100); the module runs
    0-100 on each device."""
    def op(dev, name, opcode, start, dur):
        return trace_reduce.Op(dev, f"%{name}", opcode,
                               f"%{name} = f32[8] {opcode}(f32[8] %p)",
                               start * 1e3, dur * 1e3)

    ops = [o for d in (0, 1) for o in (
        op(d, "while.1", "while", 0, 100), op(d, "fusion.1", "fusion", 0, 40),
        op(d, "all-reduce.1", "all-reduce", 40, 20),
        op(d, "fusion.2", "fusion", 70, 30))]
    return trace_reduce.Reduced(
        ops=ops, modules=[(d, "jit_step", 0.0, 100e3) for d in (0, 1)],
        annotations=[], n_devices=2)


def test_collective_and_gap_inside_a_loop_are_seen():
    """Read with the loop's event, the collective and the gap are covered;
    read from the leaf operations, the collective is 20% of the window
    and the gap 10%."""
    whole = _nested_trace()
    assert whole.exposed_collective_s() == 0
    assert whole.busy_s() == pytest.approx(100e-6)
    red = _leaf_ops(whole)
    ctx = {"reduced": red, "window_s": 100e-6}
    assert _reader("collective_exposed.train").read(ctx) \
        == pytest.approx(20.0)
    assert _reader("device_idle.train").read(ctx) == pytest.approx(10.0)
    assert red.idle_gaps(1)[0][1] == pytest.approx(10e-6)


def test_linear_reader_claims_exactly_the_tagged_kernels(ctx):
    red = ctx["reduced"]
    r = _reader("linear_roofline.train")
    tagged = {o.name for o in red.ops
              if o.is_pallas and '"kernel":"colwise_nm"' in o.text}
    claimed = {o.name for o in red.ops if r.claims(o)}
    assert tagged and claimed == tagged
    # every Pallas kernel of the step is a column-wise one
    assert {o.name for o in red.ops if o.is_pallas} == tagged
    # each is one shard's forward of a column-parallel projection
    want = {(TOKENS, s.d_in, s.n_tiles, s.k_kept, s.d_out // s.n_tiles, 2)
            for s in work_train.shard_linears(CONFIG)}
    got = {work_train.kernel_shapes(o.text) for o in red.ops if r.claims(o)}
    assert got == want
    share = r.read(ctx)
    assert 0 < share <= 100


def test_claim_report_is_clean(ctx):
    red = ctx["reduced"]
    r = _reader("linear_roofline.train")
    lines = readers.claim_report(red, {"linear_roofline.train": r.claims},
                                 ["linear_roofline.train"])
    assert lines and not any("claimed by none" in x for x in lines)


def test_mfu_reads_under_the_peak(ctx):
    mfu = _reader("mfu.train").read(ctx)
    assert 0 < mfu < 100
