"""``chip_smoke.py`` off the chip: it refuses a CPU-only JAX at once, and its
phases run end to end at small sizes with the kernels in interpret mode (the
serve phase on the CPU's XLA dispatch candidates)."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro import dispatch, obs
from repro.dispatch.profiler import ProfileDB

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
import chip_smoke as cs  # noqa: E402


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "'cpu'" in out.stderr
    assert '"ok"' not in out.stdout
    assert "== " not in out.stdout  # refused before any phase built anything


@pytest.fixture
def fresh_dispatch(tmp_path):
    """Private profile DB (clears dispatch memos) and a zeroed obs layer,
    restored afterwards."""
    dispatch.set_db(ProfileDB(str(tmp_path / "db.json")))
    obs.reset()
    obs.set_enabled(True)
    yield
    obs.set_enabled(None)
    obs.reset()
    dispatch.set_db(None)


def test_linear_kernel_phase_interpret():
    chk = cs.Checks()
    cs.linear_kernels(chk, rows=8, interpret=True)
    assert not chk.failed


def test_conv_kernel_phase_interpret(fresh_dispatch):
    chk = cs.Checks()
    cs.conv_kernels(chk, layers=[("s4.c2-narrow", 32, 7, 32, 3, 1, 1),
                                    ("stem-narrow", 16, 12, 16, 3, 2, 2)],
                    interpret=True, expect_backend="xla")
    assert not chk.failed


def test_paged_kernel_phase_interpret():
    chk = cs.Checks()
    cs.paged_kernel(chk, batch=2, interpret=True)
    assert not chk.failed


def test_serve_phase_smoke_widths(fresh_dispatch):
    chk = cs.Checks()
    cs.serve_phase(chk, smoke=True, n_requests=4, prompt_lens=(8, 24),
                   new_tokens=(4, 8), page_size=8, expect_backend="xla")
    assert not chk.failed
