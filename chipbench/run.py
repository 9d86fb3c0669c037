#!/usr/bin/env python3
"""Run one cell of the chip benchmark.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1> [--control] [--keep-trace FILE]

One process, one cell, on the chips of the machine it is started on.  The
cell, its configuration, traffic mix, driver and per-layer metric readers
are found by name from ``BENCHMARK.json``.  Set-up (weights made on the
device from the seed, every shape the window uses compiled) is timed as
``setup_s``; then the window measures for ``--seconds``; then what the
window produced is compared with the plain reference under ``refs/``.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  ``--control`` also computes the reference in the next precision
down over the same inputs and prints its reading of each compared number
(used to set the limits; the benchmark's own runs never pass it).

The last line of standard output is one JSON object; the numbers compared
and their limits are also the last lines of standard error.  The run exits
non-zero with no result line when it finds no TPU, fewer chips than the
cell asks for, a device kind without published peaks, or a missing file;
and, with ``--trace 1``, when a kernel reader reads nothing while Pallas
kernels that no reader recognises ran (``readers.claim_report``).
"""
from __future__ import annotations

import time

PROC_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(ROOT))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--keep-trace", default=None, metavar="FILE",
                    help="with --trace 1, also copy the .xplane.pb here")
    return ap.parse_args(argv)


def setup_program_env() -> None:
    """The program under test comes from ``src/``; its dispatch profile DB
    is pinned inside the benchmark's directory with profiling on a miss
    left off, so decisions do not depend on the tree it runs in."""
    from chipbench.harness import Refused

    if not (ROOT / "src" / "repro").is_dir():
        raise Refused(f"no program under test at {ROOT / 'src' / 'repro'}")
    sys.path.insert(0, str(ROOT / "src"))
    os.environ["REPRO_DISPATCH_DB"] = str(BENCH_DIR / "dispatch_profile.json")
    os.environ["REPRO_DISPATCH_PROFILE"] = "0"


def check_device(cell):
    """The chips the cell asks for, or ``Refused``."""
    import jax

    from chipbench import peaks
    from chipbench.harness import Refused

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise Refused(f"needs a TPU, found platform {dev.platform!r}")
    try:
        peaks.peaks_for(dev.device_kind)
    except KeyError as e:
        raise Refused(str(e)) from None
    if len(devices) < cell.chips:
        raise Refused(f"cell {cell.name} needs {cell.chips} chips, found "
                      f"{len(devices)}")
    return devices[:cell.chips]


def run_cell(cell, args, devices, *, proc_start: float = PROC_START) -> dict:
    """Drive the cell and build the result object (no device check here:
    tests call this on the CPU)."""
    from chipbench import harness, readers
    from repro.launch.compile_cache import enable_compile_cache

    harness.emit([f"compile cache: {enable_compile_cache()}"])
    harness.open_setup()
    compiles = harness.CompileCounter()
    out = cell.driver.run(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), control=args.control,
                          devices=devices, compiles=compiles,
                          report=harness.emit,
                          keep_trace=getattr(args, "keep_trace", None))
    win = out.window
    harness.emit(harness.quarantine_report())
    harness.emit([f"memory: peak_bytes_in_use {out.memory_peak_bytes}"])
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    metrics = {}
    result = {}
    if args.trace:
        red = out.reduced
        ctx = {"cell": cell, "reduced": red, "work": out.work,
               "window_s": win.seconds_measured,
               "compiles_in_window": compiles.between(win.t0, win.t1),
               "device_kind": dev.device_kind}
        for m in cell.per_layer:
            v = cell.metric_readers[m["name"]].read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        harness.emit(readers.claim_report(
            red, {n: r.claims for n, r in cell.metric_readers.items()
                  if hasattr(r, "claims")}, metrics))
        device["busy_s"] = red.busy_s()
        device["window_s"] = win.seconds_measured
        result["breakdown"] = {"device_ops": red.top_ops(10),
                               "idle_gaps": red.idle_gaps(10)}
    else:
        metrics["setup_s"] = {"value": win.t0 - proc_start, "unit": "s"}
        for m in cell.end_to_end:
            if m["name"] in out.end_to_end:
                metrics[m["name"]] = {"value": out.end_to_end[m["name"]],
                                      "unit": m["unit"]}
    compared = {c.name: {"value": c.value, "limit": c.limit}
                for c in out.compared}
    head = {"correct": all(c.ok for c in out.compared) and out.failed == 0,
            "attempted": out.attempted, "failed": out.failed,
            "metrics": metrics, "device": device}
    head.update(result)
    if out.control:
        head["control"] = out.control
    head["compared"] = compared
    return head


def main(argv=None) -> int:
    args = parse(argv)
    from chipbench import harness

    try:
        cell = harness.Cell.resolve(args.workload)
        setup_program_env()
        devices = check_device(cell)
    except (harness.Refused, ImportError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    from chipbench.readers import UnclaimedKernels

    try:
        res = run_cell(cell, args, devices)
    except UnclaimedKernels as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    for name, c in res["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
