#!/usr/bin/env python3
"""Readings that the sharded finetune cell's limits are set from, in one
process on the cell's chips (its set-up is long): the program's numbers
on many seeds, the fp8 control's on some, and those of faults planted in
the reference put in the program's place.

    python3 chipbench/tools/train_limits.py --seeds 11 12 ... \\
        --control-seeds 21 22 ... --fault-seeds 31 32 ... --out FILE \\
        [--limits chipbench/limits/qwen2-7b-l8-c50.ft4.json]

Each seed's program readings come from the cell's own compiled step
through its first steps, at the cell's sizes, against the float32
reference; the control is the reference with float8 e4m3 operands; the
faults are the reference with half of the batch left out (the mean over
the rest), with the REDUCE layers' exchange left out (group 0's partial
sum alone, as chip 0 would hold it), and with the second step fed the
first step's batch again.  A state left unchanged reads 1 by the
measure, and is not run.  Nor is one leaf's gradient zeroed: its reading
follows from the reference's own (``leaf_zeroed``), and each seed prints
the weakest such fault with the smallest leaf-to-median ratios.  Writes
every reading as JSON to ``--out`` and, with ``--limits``, the limits
that ``place_limits`` sets from them (exit 4, nothing written, where the
readings do not separate).
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]

NAME = "qwen2-7b-l8-c50.ft4"


def leaf_zeroed(ref, want, init_norms, hp, steps: int) -> dict:
    """Reading of each leaf's gradient zeroed in the program, the weakest
    over the leaves.  Such a leaf's first moment stays 0, so its gradient
    as applied reads 0, and AdamW moves it by the weight decay alone:
    ``|p| (1 - (1 - lr wd)^steps)``.  Each gap is measured as
    ``ref.compare`` measures it."""
    import numpy as np

    g, c = want.grad_leaf, want.change_leaf
    gmed = float(np.median(g))
    moved = g >= ref.STILL_LEAF * gmed
    cmed = float(np.median(c[moved]))
    decay = init_norms * (1.0 - (1.0 - hp.lr * hp.weight_decay) ** steps)
    g_gap = g / np.maximum(g, gmed)
    c_gap = np.where(moved, np.abs(decay - c) / np.maximum(c, cmed), 0.0)
    reading = np.maximum(g_gap, c_gap)
    i, gi = int(np.argmin(reading)), int(np.argmin(g / gmed))
    ci = int(np.argmin(np.where(moved, c / cmed, np.inf)))
    return {"reading": float(reading[i]), "leaf": i,
            "grad_gap": float(g_gap[i]), "change_gap": float(c_gap[i]),
            "least_grad_ratio": float(g[gi] / gmed), "least_grad_leaf": gi,
            "least_change_ratio": float(c[ci] / cmed),
            "least_change_leaf": ci,
            "grad_gaps": g_gap.tolist(), "change_gaps": c_gap.tolist()}


#: numbers that a state left unchanged reads 1 on (its first moment and its
#: change are 0), with no run
UNCHANGED_READS_1 = ("grad_leaf_gap", "change_leaf_gap")


def _round_down(x: float) -> float:
    e = math.floor(math.log10(x)) - 1
    return float(f"{math.floor(x / 10 ** e)}e{e}")


def place_limits(out: dict, compared) -> dict:
    """Each number's limit from the readings: the lower reading ``L`` is
    the largest of the program's; the upper ``U`` the least of the
    control's where it is 3 L or more and of each fault's where it is
    10 L or more (a state left unchanged reads 1 on ``UNCHANGED_READS_1``).
    The limit is ``L^0.4 U^0.6`` rounded down to two digits: more room
    above the lower than below the upper.  Raises ``ValueError`` where a
    number has no upper reading, or where the control or a fault, on
    some seed, fails none of the limits."""
    limits, why = {}, {}
    for k in compared:
        lo = max(r[k] for r in out["program"].values())
        ups = {}
        ctl = [r[k] for r in out["control"].values()]
        if ctl and min(ctl) >= 3 * lo:
            ups["control"] = min(ctl)
        for f, by_seed in out["faults"].items():
            vals = [r[k] for r in by_seed.values()]
            if vals and min(vals) >= 10 * lo:
                ups[f] = min(vals)
        if k in UNCHANGED_READS_1:
            ups["state_unchanged"] = 1.0
        if not ups:
            raise ValueError(f"{k}: no upper reading (lower {lo!r})")
        up = min(ups.values())
        limits[k] = _round_down(lo ** 0.4 * up ** 0.6)
        why[k] = {"lower": lo, "upper": up,
                  "upper_from": min(ups, key=ups.get)}
    parts = [("control", out["control"])] + list(out["faults"].items())
    for part, by_seed in parts:
        for seed, r in by_seed.items():
            if not any(r[k] > limits[k] for k in compared):
                raise ValueError(f"{part} seed {seed} fails no limit: {r}")
    return {"limits": limits, "from": why}


def leaf_faults_caught(out: dict, limits: dict) -> dict:
    """Per seed, the leaves whose gradient zeroed alone would pass every
    limit (``leaf_zeroed``), by index."""
    return {seed: [i for i, (g, c) in enumerate(zip(z["grad_gaps"],
                                                     z["change_gaps"]))
                   if g <= limits["grad_leaf_gap"]
                   and c <= limits["change_leaf_gap"]]
            for seed, z in out["leaf_zeroed"].items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", required=True)
    ap.add_argument("--limits", default=None, metavar="FILE")
    args = ap.parse_args(argv)

    from chipbench import harness, run

    cell = harness.Cell.resolve(NAME)
    run.setup_program_env()
    devices = run.check_device(cell)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    drv, cfg, tr = cell.driver, cell.config, cell.traffic
    ref = drv.ref
    prog = drv.Program(cfg, tr, devices)
    plain = drv.reference(cfg, tr)
    low = drv.reference(cfg, tr, ref.CONTROL_QUANT)
    b = tr["batch"]
    faults = {
        "half_batch": {"row_w": jnp.asarray([1.0 / (b // 2)] * (b // 2)
                                            + [0.0] * (b - b // 2))},
        "exchange_left_out": {"group_w": jnp.asarray(
            [1.0] + [0.0] * (cfg["mesh"]["model"] - 1))},
        "stale_batch": {"batch_order": [0, 0] + list(
            range(2, tr["setup_steps"]))},
    }
    out = {"program": {}, "control": {}, "faults": {f: {} for f in faults},
           "leaf_zeroed": {}}
    norms = jax.jit(ref.leaf_norms)
    hp = ref.AdamW.from_traffic(tr)
    seeds = sorted(set(args.seeds) | set(args.control_seeds)
                   | set(args.fault_seeds), key=lambda s: (
                       s not in args.seeds, s))
    for seed in seeds:
        t0 = time.perf_counter()
        key = harness.jax_key(seed, 0)
        batches = prog.make_batches(harness.jax_key(seed, 1))
        want = None

        def reading(label, got):
            c = ref.compare(got, want, prog.names)
            print(f"{label} seed {seed}: " + " ".join(
                f"{k} {c[k]!r}" for k in drv.COMPARED)
                + f" worst {c['worst']}", flush=True)
            return {k: c[k] for k in drv.COMPARED}

        want = drv.reference_readings(plain, tr, prog, key, batches)
        z = leaf_zeroed(ref, want, np.asarray(norms(prog.make_params(key))),
                        hp, tr["setup_steps"])
        for k in ("leaf", "least_grad_leaf", "least_change_leaf"):
            z[k] = prog.names[z[k]]
        out["leaf_zeroed"][seed] = z
        print("leaf_zeroed seed {}: {}".format(seed, {
            k: v for k, v in z.items() if not k.endswith("_gaps")}),
            flush=True)
        if seed in args.seeds:
            params, opt, got = prog.first_steps(key, batches)
            del params, opt
            out["program"][seed] = reading("program", got)
            out["program"][seed]["losses"] = got.losses
            out["program"][seed]["reference_losses"] = want.losses
        if seed in args.control_seeds:
            got = drv.reference_readings(low, tr, prog, key, batches)
            out["control"][seed] = reading("control", got)
        if seed in args.fault_seeds:
            for name, fault in faults.items():
                got = drv.reference_readings(plain, tr, prog, key, batches,
                                             **dict(fault))
                out["faults"][name][seed] = reading(name, got)
        print(f"seed {seed}: {time.perf_counter() - t0:.1f} s", flush=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
    for part, by_seed in [("program", out["program"]),
                          ("control", out["control"])] + [
            (f, v) for f, v in out["faults"].items()]:
        for k in drv.COMPARED:
            vals = [r[k] for r in by_seed.values()]
            if vals:
                print(f"summary {part} {k}: min {min(vals)!r} "
                      f"max {max(vals)!r} n {len(vals)}", flush=True)
    for k in ("reading", "grad_gap", "change_gap", "least_grad_ratio",
              "least_change_ratio"):
        vals = [r[k] for r in out["leaf_zeroed"].values()]
        if vals:
            print(f"summary leaf_zeroed {k}: min {min(vals)!r} "
                  f"max {max(vals)!r} n {len(vals)}", flush=True)
    if not args.limits:
        return 0
    try:
        placed = place_limits(out, drv.COMPARED)
    except ValueError as e:
        print(f"limits: not placed: {e}", flush=True)
        return 4
    missed = leaf_faults_caught(out, placed["limits"])
    print(f"limits: {json.dumps(placed)}", flush=True)
    print("limits: leaves whose zeroed gradient passes every limit: "
          + json.dumps({s: [prog.names[i] for i in v]
                        for s, v in missed.items()}), flush=True)
    Path(args.limits).write_text(json.dumps(placed["limits"]) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
