"""The rule that sets the sharded finetune cell's limits from its chip
readings (``tools/train_limits.place_limits``)."""
import pytest

from chipbench import harness
from chipbench.tests.tiny import BENCH

COMPARED = ("loss_rel_err", "grad_norm_rel_err", "grad_leaf_gap",
            "change_leaf_gap")


@pytest.fixture(scope="module")
def tool():
    return harness.load_module(BENCH / "tools" / "train_limits.py")


def _readings(program, control, **faults):
    def row(vals):
        return dict(zip(COMPARED, vals))
    return {"program": {1: row(program), 2: row([v / 2 for v in program])},
            "control": {3: row(control)},
            "faults": {f: {4: row(v)} for f, v in faults.items()}}


def test_limit_lies_between_the_readings_nearer_the_upper(tool):
    out = _readings([8.6e-5, 2.3e-3, 2.5e-3, 1.65e-3],
                    [1.63e-4, 5.4e-3, 9.7e-3, 9.2e-3],
                    half_batch=[2.8e-3, 0.38, 0.049, 0.29],
                    exchange_left_out=[2.55e-3, 7.5e-3, 0.108, 0.50])
    placed = tool.place_limits(out, COMPARED)
    lim, src = placed["limits"], placed["from"]
    # the control reads under 3x the program's loss: the exchange sets it
    assert src["loss_rel_err"]["upper_from"] == "exchange_left_out"
    assert src["grad_norm_rel_err"]["upper_from"] == "half_batch"
    assert src["grad_leaf_gap"]["upper_from"] == "control"
    for k in COMPARED:
        lo, up = src[k]["lower"], src[k]["upper"]
        assert lo < lim[k] < up
        assert lim[k] / lo > up / lim[k]
        assert lim[k] == float(f"{lim[k]:.2g}")
    assert lim["loss_rel_err"] == 6.5e-4


def test_a_number_with_no_upper_reading_is_refused(tool):
    out = _readings([1e-3] * 4, [2e-3, 2e-3, 0.1, 0.1])
    with pytest.raises(ValueError, match="loss_rel_err: no upper"):
        tool.place_limits(out, COMPARED)


def test_a_fault_that_fails_no_limit_is_refused(tool):
    out = _readings([1e-4, 1e-3, 1e-3, 1e-3], [1e-2, 1e-1, 1e-1, 1e-1],
                    stale_batch=[1e-4, 0.0, 0.0, 1e-3])
    with pytest.raises(ValueError, match="stale_batch seed 4 fails no"):
        tool.place_limits(out, COMPARED)


def test_leaves_that_a_zeroed_gradient_would_pass(tool):
    out = {"leaf_zeroed": {7: {"grad_gaps": [0.5, 1e-3, 1e-3],
                               "change_gaps": [0.5, 0.2, 1e-3]}}}
    lim = {"grad_leaf_gap": 6e-3, "change_leaf_gap": 5e-3}
    assert tool.leaf_faults_caught(out, lim) == {7: [2]}
