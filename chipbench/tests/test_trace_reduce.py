"""The trace reduction: interval arithmetic on a hand-made trace, and the
whole reduction on a short trace of the ResNet cell recorded on a v5e
chip (``data/resnet18_b32_v5e.xplane.pb``)."""
from pathlib import Path

import pytest

from chipbench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
ALL_REDUCE = "%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %p), to_apply=%add"
FUSION = "%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %a), kind=kLoop"
KERNEL = ('%conv2d_fused.2 = bf16[8]{0} custom-call(bf16[8]{0} %a), '
          'custom_call_target="tpu_custom_call"')


def _op(text, start, dur, device=0):
    name = text.split(" = ")[0]
    opcode = tr._OPCODE.search(text.split(" = ", 1)[1]).group(1)
    return tr.Op(device, name, opcode, text, start, dur)


def _hand_made():
    ops = [_op(FUSION, 0, 100), _op(KERNEL, 50, 100),      # busy 0..150
           _op(ALL_REDUCE, 140, 60),                       # exposed 150..200
           _op(FUSION, 300, 100),                          # gap 200..300
           _op(FUSION, 0, 400, device=1)]
    mods = [(0, "jit_a(1)", 0, 200), (0, "jit_b(2)", 300, 400)]
    return tr.Reduced(ops=ops, modules=mods, annotations=[], n_devices=2)


def test_interval_arithmetic():
    red = _hand_made()
    assert red.busy_s() == pytest.approx((300 + 400) / 2 * 1e-9)
    assert red.exposed_collective_s() == pytest.approx(50 / 2 * 1e-9)
    assert red.op_time_s(lambda o: o.is_pallas) == pytest.approx(50e-9)
    assert red.pallas_kernels() == {
        "%conv2d_fused": (1, pytest.approx(1e-7), KERNEL)}
    gaps = red.idle_gaps()
    assert gaps == [["after jit_a before jit_b", pytest.approx(100e-9)]]
    top = red.top_ops(2)
    assert top[0][0] == "%fusion.3 fusion"
    assert top[0][1] == pytest.approx((100 + 100 + 400) / 2 * 1e-9)


def test_opcodes():
    assert _op(ALL_REDUCE, 0, 1).is_collective
    assert _op(KERNEL, 0, 1).is_pallas and not _op(FUSION, 0, 1).is_pallas
    start = ("%all-gather-start = (bf16[4]{0}, bf16[16]{0}) "
             "all-gather-start(bf16[4]{0} %x), dimensions={0}")
    assert _op(start, 0, 1).opcode == "all-gather-start"
    assert _op(start, 0, 1).is_collective


def test_recorded_chip_trace():
    path = DATA / "resnet18_b32_v5e.xplane.pb"
    red = tr.reduce_file(str(path))
    assert red.n_devices == 1
    busy = red.busy_s()
    assert 0 < busy
    kernels = red.pallas_kernels()
    assert kernels and all(n > 0 and s > 0 for n, s, _t in kernels.values())
    assert sum(s for _n, s, _t in kernels.values()) <= busy * 1.0001
    assert len(red.top_ops(10)) == 10
    assert any(a[0] == "chipbench.vision.batch" for a in red.annotations)
    for name, sec in red.idle_gaps(10):
        assert name.startswith("after ") and sec > 0


def _reader(name):
    from chipbench import harness

    return harness.load_module(DATA.parent.parent / "metrics" / f"{name}.py")


def test_kernel_readers_claim_the_recorded_kernels():
    """On the recorded ResNet trace the conv reader claims every conv
    kernel and leaves only the head's linear, and reads a share under
    100%; a reader whose kernels were renamed reads nothing and fails the
    run, since kernels it would have claimed ran unclaimed."""
    from chipbench import readers

    red = tr.reduce_file(str(DATA / "resnet18_b32_v5e.xplane.pb"))
    conv = _reader("conv_roofline.vision")
    lines = readers.claim_report(red, {"conv": conv.claims}, {"conv"})
    unclaimed = [ln for ln in lines if "claimed by none" in ln]
    assert len(unclaimed) == 1 and unclaimed[0].startswith(
        "pallas kernel: %_lambda_ ")
    assert 0 < red.op_time_s(conv.claims) <= red.busy_s()
    renamed = readers.pallas_named(["conv2d_renamed"])
    with pytest.raises(readers.UnclaimedKernels, match="conv2d_renamed|read "
                       "nothing"):
        readers.claim_report(red, {"conv": renamed}, set())


def test_claims_off_the_path_and_overlap():
    """A reader whose kernel is off the path reads nothing and leaves no
    kernel unclaimed: no fault.  Two readers claiming one kernel is one."""
    from chipbench import readers

    red = tr.Reduced(ops=[_op(KERNEL, 0, 10)], modules=[], annotations=[],
                     n_devices=1)
    mine = readers.pallas_named(["conv2d_fused"])
    gone = readers.pallas_named(["colwise_nm_matmul_strips"])
    lines = readers.claim_report(red, {"a": mine, "b": gone}, {"a"})
    assert lines == [f"pallas kernel: %conv2d_fused runs 1 device_s 1e-08 "
                     f"claimed by a e.g. {KERNEL}"]
    with pytest.raises(readers.UnclaimedKernels, match="claimed by"):
        readers.claim_report(red, {"a": mine, "c": mine}, {"a", "c"})
