"""The sharded finetune cell's comparison at a size the CPU holds, on four
virtual devices (``train_cases``, in a process of its own): the program
passes it, the fp8 control fails it (by hand from ``--control``'s
readings, and through the harness's own ``correct`` in the program's
place), each fault planted in the timed path is not correct, and the
reference's expansion of the compressed formats reproduces the program's
linears."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench.tests import train_cases

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def cases(tmp_path_factory):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu", REPRO_DISPATCH_PROFILE="0",
               REPRO_DISPATCH_DB=str(tmp_path_factory.mktemp("db")
                                     / "profile.json"))
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.tests.train_cases"], cwd=ROOT,
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_program_passes_and_control_fails(cases):
    res = cases["program"]
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0
    assert any(res["control"][k] > c["limit"]
               for k, c in res["compared"].items()), res["control"]


@pytest.mark.parametrize("fault", sorted(train_cases.FAULTS))
def test_fault_is_not_correct(cases, fault):
    res = cases[fault]
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("linear", ["down", "gate", "q"])
def test_reference_expansion_reproduces_the_program(cases, linear):
    """The REDUCE format (``down``) and the tiled one (``gate``, ``q``), in
    float32 on one device: the program's linear and the reference's dense
    expansion agree to rounding."""
    assert cases["expansion"][linear] < 1e-5
