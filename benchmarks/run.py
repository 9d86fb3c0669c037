"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Mapping to the paper:
  bench_conv_layers  -> Fig. 5 (+ Fig. 10): dense vs conventional N:M vs
                        column-wise N:M per conv layer
  bench_fusion       -> Fig. 6/7/8: fused im2col+packing
  bench_blockwidth   -> Fig. 9: LMUL sweep (strip/tile width analogs)
  bench_accuracy     -> Table 1: pruning-pattern accuracy (proxy task)
  bench_e2e          -> Table 2 / Fig. 11: end-to-end throughput vs sparsity
  bench_layout       -> Fig. 12: CNHW vs NHWC
  bench_roofline     -> assignment §Roofline from the dry-run artifacts
  bench_dispatch     -> §3.3: dispatched vs fixed-backend operator selection
  bench_conv_fused   -> fused conv megakernel vs two-kernel/XLA plans
  bench_serve_scheduler -> continuous-batching scheduler vs static engine

``--quick`` runs a smoke subset (conv layers + dispatch, 3 iters) fast
enough for CI / pre-commit, so dispatch-latency regressions are caught
locally; ``--only NAME`` runs a single module.
"""
from __future__ import annotations

import argparse
import inspect
import sys
import traceback


def _modules():
    import types

    from benchmarks import (
        bench_accuracy,
        bench_blockwidth,
        bench_conv_fused,
        bench_conv_layers,
        bench_dispatch,
        bench_e2e,
        bench_fusion,
        bench_layout,
        bench_roofline,
        bench_serve_scheduler,
    )

    return [
        ("fig5_conv_layers", bench_conv_layers),
        ("conv_fused", bench_conv_fused),
        ("fig6_8_fusion", bench_fusion),
        ("fig9_blockwidth", bench_blockwidth),
        ("table1_accuracy", bench_accuracy),
        # conv cell of the accuracy protocol (dense -> prune -> finetune
        # through the sparse-conv backward -> compressed inference); its own
        # entry so --quick can run it without the full LM Table-1 sweep
        ("conv_accuracy", types.SimpleNamespace(run=bench_accuracy.run_conv)),
        # crash-safe training row: checkpoint overhead % + the asserted-zero
        # accuracy delta of an interrupted-then-resumed finetune
        ("train_resume",
         types.SimpleNamespace(run=bench_accuracy.run_train_resume)),
        ("table2_fig11_e2e", bench_e2e),
        ("fig12_layout", bench_layout),
        ("roofline", bench_roofline),
        ("dispatch", bench_dispatch),
        ("serve_scheduler", bench_serve_scheduler),
    ]


QUICK = {"fig5_conv_layers", "dispatch", "conv_accuracy"}
QUICK_ITERS = 3  # median of 3: the middle sample, robust to one outlier


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="smoke subset with few iterations (CI mode)")
    ap.add_argument("--only", default=None, metavar="NAME",
                    help="run a single benchmark module by name")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable the obs layer and write a Perfetto-loadable "
                         "Chrome trace of the whole run to PATH")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.trace:
        from repro import obs

        obs.set_enabled(True)

    modules = _modules()
    if args.only:
        modules = [(n, m) for n, m in modules if n == args.only]
        if not modules:
            sys.exit(f"unknown benchmark {args.only!r}; known: "
                     f"{[n for n, _ in _modules()]}")
    elif args.quick:
        modules = [(n, m) for n, m in modules if n in QUICK]

    print("name,us_per_call,derived")
    failures = 0
    for name, mod in modules:
        try:
            # --quick shrinks iterations, but only for modules whose run()
            # takes an iters knob (e2e/accuracy/roofline parameterize
            # differently)
            quick_ok = args.quick and "iters" in inspect.signature(mod.run).parameters
            lines = mod.run(iters=QUICK_ITERS) if quick_ok else mod.run()
            for line in lines:
                print(line)
            sys.stdout.flush()
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name}.ERROR,0.0,{traceback.format_exc(limit=1).splitlines()[-1]}")
    if args.trace:
        from benchmarks.report import metrics_table
        from repro import obs

        n = obs.dump_chrome_trace(args.trace,
                                  metadata={"metrics": obs.snapshot()})
        print(f"# trace: wrote {n} events to {args.trace}", file=sys.stderr)
        for line in metrics_table(obs.snapshot()):
            print(line, file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
