"""Serving launcher: batched generation with the column-wise N:M engine.

Static batch (pads every request to the slowest sequence):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
        --batch 4 --new-tokens 32 --sparsity 0.5

Continuous batching (slot-based in-flight admission over a synthetic
mixed-length request trace).  Bare ``--trace`` prints the admit/retire event
log; ``--trace out.json`` additionally turns on the observability layer and
writes a Chrome-trace-event file (dispatch decisions, scheduler iteration
spans, per-request TTFT/TPOT metrics) loadable in Perfetto:

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
        --continuous --requests 12 --slots 4 --trace out.json

``--paged`` switches the continuous scheduler onto the paged-KV memory tier
(``repro.serve.kv_pages``): block-granular admission, packed padding-free
prefill, and page-occupancy gauges in the summary (and, as
``sched.page_stats``, in the ``--trace`` file's metadata).
``--page-size`` pins the page size; omitted, dispatch races the registered
page-size geometries for the serving shape:

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
        --continuous --paged --page-size 8 --requests 12 --slots 4

Robustness knobs (``docs/robustness.md``): ``--deadline-s`` stamps every
trace request with a deadline, ``--faults SPEC`` installs the seeded
fault-injection plan (``repro.fault`` grammar, e.g.
``page_pool.alloc:n=2,scheduler.iter:iter=3``), ``--alloc grow`` switches the
paged tier to grow-on-demand allocation with preemption-restore, and SIGTERM
(or Ctrl-C) drains gracefully: admissions stop, in-flight requests finish,
queued ones flush as cancelled.  A scheduler-iteration watchdog
(``--watchdog-s``) aborts a wedged serve loop:

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
        --continuous --paged --alloc grow --deadline-s 30 \
        --faults 'page_pool.alloc:p=0.05' --requests 12 --slots 4
"""
from __future__ import annotations

import argparse
from typing import Optional

import jax
import numpy as np

from repro import fault as rfault
from repro import obs
from repro.configs import get_config, smoke_config
from repro.core.pruning import SparsityConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import registry as reg
from repro.serve import (
    STATUSES,
    Engine,
    Scheduler,
    ServeConfig,
    latency_percentiles,
    synthetic_trace,
)
from repro.train.fault import PreemptionGuard, StepWatchdog


def build_engine(args, *, dtype=None) -> Engine:
    """Engine for ``args`` (``arch``, ``sparsity``, ``smoke``,
    ``new_tokens``, ``temperature``): random weights from seed 0, linears
    column-wise compressed at ``sparsity``.  ``dtype`` sets both the compute
    and the parameter dtype, as a deployment serves them (default: the
    config's own)."""
    scfg = SparsityConfig(sparsity=args.sparsity, m=None, tile=None,
                          format="compressed_xla" if args.sparsity > 0 else "dense",
                          min_dim=64 if args.smoke else 512)
    cfg = (smoke_config(args.arch) if args.smoke else get_config(args.arch)).with_(
        sparsity=scfg)
    if dtype:
        cfg = cfg.with_(dtype=dtype, param_dtype=dtype)
    params, _ = reg.init_params(cfg, jax.random.PRNGKey(0))
    return Engine(cfg, params, ServeConfig(max_new_tokens=args.new_tokens,
                                           temperature=args.temperature))


def watchdog_heartbeat(dog: StepWatchdog):
    """Heartbeat that arms ``dog`` at the first completed scheduler
    iteration.  That iteration compiles the full-width prefill and decode
    steps, which can take longer than a wedged-step window; later
    iterations recompile only for a new packed-prefill length."""
    def beat():
        if not dog.started:
            dog.start()
        dog.beat()

    return beat


def run_static(args) -> None:
    eng = build_engine(args)
    cfg = eng.cfg
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (args.batch, args.prompt_len)).astype(np.int32)
    eng.generate(prompts)  # compile
    res = eng.generate(prompts)
    print(f"arch={cfg.name} sparse={args.sparsity} batch={args.batch}")
    print(f"prefill {res['prefill_s']*1e3:.1f} ms; decode {res['decode_tok_s']:.1f} tok/s")
    for i, row in enumerate(res["tokens"][:2]):
        print(f"  seq{i}: {row[:16].tolist()}")


def run_continuous(args) -> Scheduler:
    if args.requests < 1:
        raise SystemExit("--continuous needs --requests >= 1")
    eng = build_engine(args)
    cfg = eng.cfg
    trace = synthetic_trace(
        args.requests, seed=0, vocab=cfg.vocab_size,
        prompt_lens=(max(args.prompt_len // 4, 1), args.prompt_len),
        new_tokens=(max(args.new_tokens // 4, 1), args.new_tokens))
    if args.deadline_s is not None:
        for r in trace:
            r.deadline_s = args.deadline_s
    sched = Scheduler(eng, n_slots=args.slots, prefill_chunk=args.prefill_chunk,
                      paged=args.paged, page_size=args.page_size,
                      kv_budget_rows=args.kv_budget_rows, alloc=args.alloc)
    log = print if args.trace == "" else None
    # SIGTERM/SIGINT -> graceful drain (finish in-flight, flush the queue);
    # the watchdog aborts the process if no scheduler iteration completes
    # inside the window (wedged decode step / hung runtime)
    guard = PreemptionGuard().install()
    dog = StepWatchdog(timeout_s=args.watchdog_s)
    try:
        completions = sched.run(trace, log_fn=log,
                                should_drain=lambda: guard.requested,
                                heartbeat=watchdog_heartbeat(dog))
    finally:
        dog.stop()
        guard.uninstall()
    stats = sched.stats
    p50, p99 = latency_percentiles(completions)
    mode = f"paged(page_size={sched.page_size},alloc={args.alloc})" \
        if args.paged else "contiguous"
    print(f"arch={cfg.name} sparse={args.sparsity} continuous kv={mode} "
          f"slots={args.slots} requests={len(completions)}")
    by_status = " ".join(
        f"{s}={int(stats[f'retired_{s}'])}" for s in STATUSES
        if stats[f"retired_{s}"])
    print(f"status: {by_status or 'none'}; "
          f"preemptions {int(stats['preemptions'])}, "
          f"iter faults {int(stats['iter_faults'])}"
          + (" [drained]" if guard.requested else ""))
    print(f"decode {stats['decode_tok_s']:.1f} tok/s "
          f"({stats['generated_tokens']} tokens, "
          f"{stats['decode_steps']} steps); "
          f"latency p50 {p50*1e3:.1f} ms p99 {p99*1e3:.1f} ms")
    print(f"ttft p50 {stats['ttft_p50_s']*1e3:.1f} ms "
          f"p99 {stats['ttft_p99_s']*1e3:.1f} ms; "
          f"tpot p50 {stats['tpot_p50_s']*1e3:.2f} ms "
          f"p99 {stats['tpot_p99_s']*1e3:.2f} ms")
    if args.paged:
        ps = sched.page_stats
        print(f"pages peak {int(ps['pages_peak'])} "
              f"(hwm {int(ps['kv_rows_hwm'])} KV rows), "
              f"occupancy {int(ps['pages_active'])} active / "
              f"{int(ps['pages_free'])} free, "
              f"fragmentation {ps['page_fragmentation']:.2f}")
    for c in completions[:2]:
        print(f"  uid={c.uid}: {c.tokens[:16].tolist()}")
    return sched


def _finish_trace(path: str, sched: Optional[Scheduler] = None) -> None:
    """Dump the trace; a continuous run's metadata also carries the
    scheduler's own stats and page occupancy."""
    meta = {"metrics": obs.snapshot()}
    if sched is not None:
        meta["sched.stats"] = sched.stats
        meta["sched.page_stats"] = sched.page_stats
    n = obs.dump_chrome_trace(path, metadata=meta)
    print(f"trace: wrote {n} events to {path} (load in ui.perfetto.dev)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--sparsity", type=float, default=0.5)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--continuous", action="store_true",
                    help="slot-based continuous batching over a synthetic "
                         "mixed-length request trace")
    ap.add_argument("--requests", type=int, default=12,
                    help="trace size for --continuous")
    ap.add_argument("--slots", type=int, default=4,
                    help="KV slot count (decode batch width) for --continuous")
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--paged", action="store_true",
                    help="page the KV cache (serve.kv_pages) and prefill "
                         "admitted prompts as one packed padding-free "
                         "stream; --continuous only")
    ap.add_argument("--page-size", type=int, default=None,
                    help="KV rows per page; default lets "
                         "dispatch.choose_page_size race the registered "
                         "page-size geometries for this serving shape")
    ap.add_argument("--kv-budget-rows", type=int, default=None,
                    help="total physical KV rows for the paged pool "
                         "(default: slots * max_len)")
    ap.add_argument("--alloc", choices=("reserve", "grow"), default="reserve",
                    help="paged allocation policy: reserve prompt+budget up "
                         "front, or grow on demand with preemption-restore "
                         "on exhaustion")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline (seconds from submission) "
                         "stamped onto every trace request; expiry retires "
                         "with status=timeout")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="seeded fault-injection plan, repro.fault grammar "
                         "(e.g. 'page_pool.alloc:n=2,kernel.paged_attn:"
                         "iter=0')")
    ap.add_argument("--faults-seed", type=int, default=0,
                    help="seed for probabilistic (p=) fault rules")
    ap.add_argument("--watchdog-s", type=float, default=300.0,
                    help="scheduler-iteration watchdog: abort the process "
                         "if no iteration completes within this window")
    ap.add_argument("--trace", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="bare: print per-request admit/retire events; "
                         "with PATH: enable the obs layer and write a "
                         "Perfetto-loadable Chrome trace to PATH")
    args = ap.parse_args()
    enable_compile_cache()
    if args.paged and not args.continuous:
        raise SystemExit("--paged requires --continuous (the static engine "
                         "uses the contiguous per-batch cache)")
    if (args.alloc != "reserve" or args.deadline_s is not None) \
            and not args.continuous:
        raise SystemExit("--alloc/--deadline-s require --continuous")
    trace_path = args.trace if args.trace else None
    if trace_path:
        obs.set_enabled(True)
    if args.faults:
        rfault.install(args.faults, seed=args.faults_seed)
    sched = None
    try:
        if args.continuous:
            sched = run_continuous(args)
        else:
            run_static(args)
    finally:
        if args.faults:
            print(f"faults: fired {dict(rfault.plan().fired)} "
                  f"of probes {dict(rfault.plan().probes)}")
            rfault.uninstall()
        if trace_path:
            _finish_trace(trace_path, sched)


if __name__ == "__main__":
    main()
