"""Conv execution-plan ladder race (perf trajectory artifact).

Races, per ResNet-shaped conv layer at 50% column-wise sparsity:

  fused       — the im2col+pack+sparse-GEMM megakernel (strips live in VMEM,
                zero intermediate HBM round-trips); skipped where its
                whole-map-resident VMEM predicate fails (stem-scale, batch>1)
  banded      — the H-tiled megakernel: double-buffered DMA row bands keep
                only ``stride*V rows + kh-1 halo`` resident; the rung that
                covers the shapes fused cannot
  two_kernel  — pack kernel + strip-major sparse GEMM (strips written/read
                once, no transpose relayout)
  pipelined   — two-kernel with the overlapped strip pipeline: strip chunk
                s+1 is async-copied while the GEMM consumes chunk s
  transposed  — the pre-megakernel two-kernel path: pack kernel, then
                ``transpose(0,2,1).reshape`` relayout feeding the row-major
                GEMM (three patch-matrix HBM round-trips)
  xla         — pack kernel + gather-einsum reference GEMM

Also reports the analytic bytes moved around the packing stage
(``im2col_pack.ops.bytes_moved_*``) and — for the banded plan — the analytic
band-DMA traffic per band depth (``conv_gemm.ops.banded_bytes_moved`` over
hb in {1, 2, 4}: shallow bands re-read more halo rows, deep bands amortize
it), so measured orderings can be checked against the data-movement model.
``--json`` appends to ``BENCH_conv.json`` — the repo's conv perf-trajectory
artifact keeps prior runs under ``history`` so the trajectory across PRs is
recorded, not overwritten.  ``--quick`` runs the two deepest layers with 3
iters (CI smoke; interpret-mode Pallas on CPU is the slow part).
"""
from __future__ import annotations

import argparse
import json
import subprocess
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from benchmarks.timing import row, time_fn
from repro import dispatch
from repro.core import SparsityConfig
from repro.dispatch import REGISTRY, env_fingerprint
from repro.obs import metrics as _om
from repro.obs import trace as _ot
from repro.kernels.conv_gemm.ops import (
    banded_bytes_moved,
    compress_conv_weights,
    conv2d_fused,
    conv2d_fused_banded,
    conv2d_two_kernel,
    conv2d_two_kernel_pipelined,
    conv2d_xla_ref,
)
from repro.kernels.colwise_nm.ops import colwise_nm_matmul
from repro.kernels.im2col_pack.ops import (
    bytes_moved_fused,
    bytes_moved_unfused,
    im2col_pack,
)
from repro.kernels.im2col_pack.ref import out_size

SPARSITY = 0.5
V = 128
BAND_HB = 2  # band depth the banded/pipelined plans run at (default geometry)

# ResNet-50 stages; the deeper layers are the exact paper shapes (H capped so
# CPU interpret-mode Pallas stays affordable).  ``stem.b8`` and ``s2.c2.b4``
# are the banded tier's reason to exist: stem-scale spatial extent and
# batch > 1 blow the whole-map-resident megakernel's VMEM predicate, so
# before this tier those shapes always fell back to the two-kernel plan.
#          name       c    h    o    k  stride batch
LAYERS = [
    ("s2.c2", 128, 28, 128, 3, 1, 1),
    ("s3.c2", 256, 14, 256, 3, 1, 1),
    ("s4.c2", 512, 7, 512, 3, 1, 1),
    ("s2.c2.b4", 128, 28, 128, 3, 1, 4),
    ("stem.b8", 64, 112, 64, 3, 2, 8),
]
QUICK_LAYERS = ("s3.c2", "s4.c2")


def _transposed(x, values, idx, *, kh, kw, stride, pad, v):
    """The pre-megakernel plan: pack, relayout through HBM, row-major GEMM."""
    c, b, h, w = x.shape
    ho = out_size(h, kh, stride, pad)
    wo = out_size(w, kw, stride, pad)
    o = values.shape[0] * values.shape[2]
    strips = im2col_pack(x, kh=kh, kw=kw, stride=stride, pad=pad, v=v)
    xt = strips.transpose(0, 2, 1).reshape(-1, kh * kw * c)
    y = colwise_nm_matmul(xt, values, idx)[: b * ho * wo]
    return y.T.reshape(o, b, ho, wo)


def _banded(x, values, idx, *, kh, kw, stride, pad, v):
    return conv2d_fused_banded(x, values, idx, kh=kh, kw=kw, stride=stride,
                               pad=pad, v=v, hb=BAND_HB)


def _pipelined(x, values, idx, *, kh, kw, stride, pad, v):
    return conv2d_two_kernel_pipelined(x, values, idx, kh=kh, kw=kw,
                                       stride=stride, pad=pad, v=v, hb=BAND_HB)


# (name, fn, needs_fused_feasible): plans gated on the VMEM-resident
# predicate only run where a real TPU could run them
PLANS = [
    ("fused", conv2d_fused, True),
    ("banded", _banded, False),
    ("two_kernel", conv2d_two_kernel, False),
    ("pipelined", _pipelined, False),
    ("transposed", _transposed, False),
    ("xla", conv2d_xla_ref, False),
]


def _problem(c, h, o, k, stride, batch):
    x = jax.random.normal(jax.random.PRNGKey(0), (c, batch, h, h))
    wt = jax.random.normal(jax.random.PRNGKey(1), (o, k, k, c)) / jnp.sqrt(
        float(k * k * c))
    cfg = SparsityConfig(SPARSITY, m=None, tile=None, format="compressed_pallas")
    values, idx, meta = compress_conv_weights(wt, cfg)
    return x, values, idx, meta


def measure(iters: int = 5, quick: bool = False):
    """Time every plan per layer; returns {layer: {plan: us, ...}}."""
    layers = [l for l in LAYERS if not quick or l[0] in QUICK_LAYERS]
    results = {}
    for name, c, h, o, k, stride, batch in layers:
        pad = k // 2 if k > 1 else 0
        x, values, idx, meta = _problem(c, h, o, k, stride, batch)
        ho = out_size(h, k, stride, pad)
        key = dispatch.conv_key(c, h, h, o, k, k, stride, pad,
                                meta.k_kept, meta.tile, v=V, batch=batch)
        fused_ok, fused_why = REGISTRY.get(
            "conv", "fused_sparse_pallas").feasible(key)
        entry = {
            "shape": {"c": c, "h": h, "o": o, "k": k, "stride": stride,
                      "batch": batch, "tile": meta.tile,
                      "k_kept": meta.k_kept},
            "fused_feasible": bool(fused_ok),
            "fused_feasible_reason": fused_why,
        }
        for plan, fn, needs_fused in PLANS:
            if needs_fused and not fused_ok:
                continue  # a real TPU could not run this plan on this shape
            f = jax.jit(lambda x, fn=fn: fn(
                x, values, idx, kh=k, kw=k, stride=stride, pad=pad, v=V))
            entry[plan] = time_fn(f, x, iters=iters, warmup=1,
                                  name=f"conv_fused.{name}.{plan}")
        if "fused" in entry:
            entry["fused_speedup_vs_two_kernel"] = (
                entry["two_kernel"] / entry["fused"])
            entry["fused_speedup_vs_transposed"] = (
                entry["transposed"] / entry["fused"])
        for plan in ("banded", "pipelined"):
            if plan in entry:
                entry[f"{plan}_speedup_vs_two_kernel"] = (
                    entry["two_kernel"] / entry[plan])
        entry["bytes_moved_fused"] = bytes_moved_fused(
            c, batch, h, h, k, k, ho, ho, V, 4)
        entry["bytes_moved_unfused"] = bytes_moved_unfused(
            c, batch, h, h, k, k, ho, ho, V, 4)
        # band-DMA traffic vs band depth: the data-movement model behind the
        # hb tunable (shallow bands re-read halo rows; deep bands cost VMEM)
        entry["bytes_moved_banded"] = {
            str(hb): banded_bytes_moved(c, batch, h, h, k, stride, pad,
                                        ho, ho, V, hb, o, 4)
            for hb in (1, 2, 4)
        }
        # analytic data-movement counters on the obs registry (no-ops while
        # REPRO_OBS is off): a trace of a bench run carries the model-side
        # bytes next to the measured wall times
        _om.counter("bench.conv.bytes_moved_fused").inc(
            entry["bytes_moved_fused"])
        _om.counter("bench.conv.bytes_moved_unfused").inc(
            entry["bytes_moved_unfused"])
        _ot.instant("bench.conv.bytes_moved", layer=name,
                    fused=entry["bytes_moved_fused"],
                    unfused=entry["bytes_moved_unfused"],
                    banded_hb2=entry["bytes_moved_banded"]["2"])
        results[name] = entry
    return results


def run(iters: int = 5, quick: bool = False):
    out = []
    for name, entry in measure(iters=iters, quick=quick).items():
        sh = entry["shape"]
        for plan, _fn, _nf in PLANS:
            if plan not in entry:
                continue
            out.append(row(f"conv_fused.{name}.{plan}", entry[plan],
                           f"C={sh['c']} H={sh['h']} O={sh['o']} "
                           f"k={sh['k']} B={sh['batch']}"))
        speed = " ".join(
            f"{p}_vs_two_kernel={entry[f'{p}_speedup_vs_two_kernel']:.2f}x"
            for p in ("fused", "banded")
            if f"{p}_speedup_vs_two_kernel" in entry)
        out.append(row(
            f"conv_fused.{name}.speedup", 0.0,
            speed + " bytes_fused/unfused="
            f"{entry['bytes_moved_fused'] / entry['bytes_moved_unfused']:.2f}"
        ))
    return out


HISTORY_CAP = 20  # trajectory points kept; beyond this, oldest runs drop


def _git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=Path(__file__).resolve().parent, capture_output=True,
            text=True, timeout=5, check=True).stdout.strip() or "unknown"
    except Exception:  # noqa: BLE001 — not a git checkout / git missing
        return "unknown"


def _write_json(results, iters, quick=False):
    """Append this run to BENCH_conv.json.  A FULL run becomes the new
    top-level payload (back-compat with readers of the PR-3 schema) and the
    previous top-level run is pushed onto ``history`` — the perf trajectory
    across PRs, capped at :data:`HISTORY_CAP` entries so the artifact cannot
    grow without bound.  Every run is stamped with the dispatch-layer
    environment fingerprint and the git revision, so trajectory points from
    different machines/commits are distinguishable instead of silently
    comparable.  A ``--quick`` run (the CI smoke) only refreshes the
    ``smoke`` section of the existing payload: it proves the plans still run
    without replacing a real trajectory point with 2-layer/3-iter noise or
    growing ``history`` on every CI invocation."""
    path = Path(__file__).resolve().parent.parent / "BENCH_conv.json"
    old = None
    if path.exists():
        try:
            old = json.loads(path.read_text())
        except json.JSONDecodeError:
            old = None
        if not isinstance(old, dict):
            old = None
    run = {
        "backend": jax.default_backend(),
        "sparsity": SPARSITY,
        "v": V,
        "band_hb": BAND_HB,
        "iters": iters,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "git_rev": _git_rev(),
        "fingerprint": env_fingerprint(),
        "layers": results,
    }
    if quick and old is not None and "layers" in old:
        old["smoke"] = run
        payload = old
        note = "refreshed smoke section"
    else:
        history = []
        if old is not None:
            history = old.pop("history", [])
            old.pop("smoke", None)
            history.append(old)
        history = history[-HISTORY_CAP:]
        payload = dict(run, history=history)
        note = f"{len(history)} prior run(s) kept in history"
    path.write_text(json.dumps(payload, indent=1))
    print(f"wrote {path} ({note})")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", action="store_true",
                    help="append to BENCH_conv.json (perf trajectory artifact)")
    ap.add_argument("--quick", action="store_true",
                    help="two deepest layers, 3 iters (CI smoke)")
    ap.add_argument("--iters", type=int, default=None)
    args = ap.parse_args(argv)

    iters = args.iters if args.iters is not None else (3 if args.quick else 5)
    results = measure(iters=iters, quick=args.quick)
    for name, entry in results.items():
        for plan, _fn, _nf in PLANS:
            if plan in entry:
                print(row(f"conv_fused.{name}.{plan}", entry[plan]))
        print(row(f"conv_fused.{name}.speedup", 0.0, " ".join(
            f"{p}_vs_two_kernel={entry[f'{p}_speedup_vs_two_kernel']:.2f}x"
            for p in ("banded", "pipelined")
            if f"{p}_speedup_vs_two_kernel" in entry)))
    if args.json:
        _write_json(results, iters, quick=args.quick)


if __name__ == "__main__":
    main()
