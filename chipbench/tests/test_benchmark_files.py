"""Every cell of BENCHMARK.json resolves, by name, to its configuration,
traffic, driver, limits and metric readers, and the file keeps to the
shape the benchmark's contract gives it."""
import json
import re
from pathlib import Path

import pytest

from chipbench import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_resolves(w):
    cell = harness.Cell.resolve(w["name"], BENCH)
    assert cell.driver.run
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(cell.metric_readers[m["name"]].read)
    for name, limit in cell.limits.items():
        assert NAME.match(name) and limit > 0


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_moves_is_reported_wherever_the_metric_is(m):
    e2e = {x["name"]: x for x in BENCH["end_to_end"]}
    assert m["moves"] in e2e
    cells = m.get("workloads", [w["name"] for w in BENCH["workloads"]])
    target = e2e[m["moves"]]
    for c in cells:
        assert c in target.get("workloads", [c]), (m["name"], c)


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        assert (ROOT / c["file"]).is_file()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len({x["name"] for x in BENCH["end_to_end"] + BENCH["per_layer"]}) \
        == len(BENCH["end_to_end"]) + len(BENCH["per_layer"])
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert "bound" not in m
    cells = {w["name"] for w in BENCH["workloads"]}
    for c in cells:  # every cell: setup_s, one more end-to-end, one per-layer
        mine = [m for m in BENCH["end_to_end"] if c in m.get("workloads", [c])]
        assert len(mine) >= 2
        assert any(c in m.get("workloads", [c]) for m in BENCH["per_layer"])
    assert len(json.dumps(BENCH)) < 64 * 1024
