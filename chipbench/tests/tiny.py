"""Tiny cells for the CPU tests: the real drivers, metric readers and
references at sizes the interpreter can run in seconds."""
from __future__ import annotations

import copy
import json
from pathlib import Path

from chipbench import harness

BENCH = Path(__file__).resolve().parents[1]


def load(rel: str):
    return json.loads((BENCH / rel).read_text())


def tiny_resnet():
    cfg = load("configs/resnet18-c50.json")
    cfg.update(stem_channels=16, stage_channels=[16, 32], stage_blocks=[1, 1],
               stage_strides=[1, 2], image_hw=[8, 8], num_classes=10)
    cfg["sparsity"] = dict(cfg["sparsity"], min_dim=16)
    tr = load("traffic/b32.json")
    tr.update(batch=2, distinct_batches=2, queue_depth=2, check_batches=2)
    return cfg, tr


def tiny_qwen2():
    cfg = load("configs/qwen2-0.5b-c50.json")
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=2, num_hidden_layers=2, vocab_size=256)
    cfg["sparsity"] = dict(cfg["sparsity"], min_dim=64)
    tr = load("traffic/gen256.json")
    tr.update(slots=8, requests=12, prompt_tokens=[3, 9],
              output_tokens=450, warmup_iterations=40,
              check_requests=8)
    return cfg, tr


def cell(name: str, cfg, tr, limits, *, per_layer=True):
    bench = load("../BENCHMARK.json")
    w = next(x for x in bench["workloads"] if x["name"] == name)
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    pl = [m for m in bench["per_layer"]
          if name in m.get("workloads", [name]) and m["moves"] in names]
    return harness.Cell(
        name=name, chips=w["chips"], config=copy.deepcopy(cfg),
        traffic=copy.deepcopy(tr), limits=limits, end_to_end=e2e,
        per_layer=pl if per_layer else [],
        driver=harness.load_module(BENCH / "drivers" / f"{tr['driver']}.py"),
        metric_readers={m["name"]: harness.load_module(
            BENCH / "metrics" / f"{m['name']}.py") for m in pl})


def run_tiny(cell_, *, seed=2**31 + 7, seconds=1.0, control=False):
    """One run of a tiny cell on the CPU, the device check skipped."""
    import types

    import jax

    from chipbench import run

    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0,
                                 control=control)
    return run.run_cell(cell_, args, jax.devices()[:1])


def limits(name: str):
    return load(f"limits/{name}.json")
