"""Rules of the benchmark's contract that the shape test leaves out: an
end-to-end metric other than ``setup_s`` names the cells that report it,
and at most half of the cells (rounded down, at least one) take four
chips."""
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("m", [m for m in BENCH["end_to_end"]
                               if m["name"] != "setup_s"],
                         ids=lambda m: m["name"])
def test_end_to_end_metric_lists_its_cells(m):
    cells = {w["name"] for w in BENCH["workloads"]}
    assert m.get("workloads") and set(m["workloads"]) <= cells


def test_four_chip_cells_at_most_half():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2)
