"""Generate EXPERIMENTS.md §Dry-run and §Roofline tables from the dry-run
JSON artifacts.

MODEL_FLOPS is recomputed live from the configs (the stored value predates an
active-param accounting fix), and the derived ratios are refreshed from the
stored per-chip terms.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Optional

from repro.configs import SHAPES, get_config
from repro.roofline.analysis import (
    DRYRUN_DEVICE_KIND,
    model_flops_for,
    peaks_for,
)

ART = Path("artifacts/dryrun")


def refresh_roofline(rec: Dict) -> Dict:
    """Recompute model_flops-derived fields from the live config."""
    r = rec.get("roofline")
    if not r:
        return rec
    cfg = get_config(rec["arch"])
    cell = SHAPES[rec["shape"]]
    mf = model_flops_for(cfg, cell, rec.get("sparsity", 0.0))
    r["model_flops"] = mf
    total = r["flops_per_chip"] * r["chips"]
    r["useful_flops_ratio"] = mf / total if total else 0.0
    t_bound = max(r["t_compute_s"], r["t_memory_s"], r["t_collective_s"])
    ideal = mf / r["chips"] / peaks_for(DRYRUN_DEVICE_KIND).flops
    r["roofline_fraction"] = ideal / t_bound if t_bound else 0.0
    return rec

ARCH_ORDER = [
    "olmoe-1b-7b", "moonshot-v1-16b-a3b", "smollm-360m", "qwen2-0.5b",
    "qwen2-7b", "nemotron-4-15b", "xlstm-350m", "qwen2-vl-72b",
    "whisper-small", "zamba2-7b",
]
SHAPE_ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def load(mesh: str, sparsity: int, tag: str = "") -> Dict[str, Dict]:
    out = {}
    for p in ART.glob(f"*__{mesh}__s{sparsity}{tag}.json"):
        if ".err" in p.name:
            continue
        rec = json.loads(p.read_text())
        out[(rec["arch"], rec["shape"])] = refresh_roofline(rec)
    return out


def fmt_s(x: Optional[float]) -> str:
    if x is None:
        return "—"
    if x >= 1:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x*1e3:.1f}ms"
    return f"{x*1e6:.0f}µs"


def fmt_b(x: Optional[float]) -> str:
    if x is None:
        return "—"
    for unit, div in [("TB", 1e12), ("GB", 1e9), ("MB", 1e6), ("KB", 1e3)]:
        if x >= div:
            return f"{x/div:.2f}{unit}"
    return f"{x:.0f}B"


def roofline_table(recs: Dict, caption: str) -> List[str]:
    lines = [
        f"\n### {caption}\n",
        "| arch | shape | t_compute | t_memory | t_collective | bottleneck | "
        "MODEL_FLOPS/HLO | roofline frac | one-line fix |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    fixes = {
        "compute": "shrink HLO FLOPs: higher sparsity realization, drop remat recompute",
        "memory": "cut HBM traffic: fuse gathers into matmuls, wider fusion, bf16 master",
        "collective": "reshard: shard-local gathers for reduce-dim sparse layers, overlap",
    }
    for arch in ARCH_ORDER:
        for shape in SHAPE_ORDER:
            rec = recs.get((arch, shape))
            if rec is None:
                continue
            if "skipped" in rec:
                lines.append(f"| {arch} | {shape} | — | — | — | skipped | — | — | {rec['skipped'][:60]} |")
                continue
            if "roofline" not in rec:
                lines.append(f"| {arch} | {shape} | ERR | | | | | | {rec.get('error','')[:60]} |")
                continue
            r = rec["roofline"]
            lines.append(
                f"| {arch} | {shape} | {fmt_s(r['t_compute_s'])} | {fmt_s(r['t_memory_s'])} "
                f"| {fmt_s(r['t_collective_s'])} | **{r['bottleneck']}** "
                f"| {r['useful_flops_ratio']:.3f} | {r['roofline_fraction']:.3f} "
                f"| {fixes[r['bottleneck']]} |"
            )
    return lines


def dryrun_table(recs: Dict, caption: str) -> List[str]:
    lines = [
        f"\n### {caption}\n",
        "| arch | shape | HLO FLOPs/chip | HBM bytes/chip | collective bytes/chip | "
        "top collectives | compile |",
        "|---|---|---|---|---|---|---|",
    ]
    for arch in ARCH_ORDER:
        for shape in SHAPE_ORDER:
            rec = recs.get((arch, shape))
            if rec is None or "skipped" in rec or "roofline" not in rec:
                continue
            r = rec["roofline"]
            coll = rec.get("collectives", {}).get("bytes", {})
            top = sorted(coll.items(), key=lambda kv: -kv[1])[:2]
            tops = ", ".join(f"{k}:{fmt_b(v)}" for k, v in top) or "none"
            lines.append(
                f"| {arch} | {shape} | {r['flops_per_chip']:.2e} | "
                f"{fmt_b(r['hlo_bytes_per_chip'])} | {fmt_b(r['collective_bytes_per_chip'])} "
                f"| {tops} | {rec.get('compile_seconds', 0):.0f}s |"
            )
    return lines


def compare_table(base: Dict, opt: Dict, caption: str) -> List[str]:
    lines = [
        f"\n### {caption}\n",
        "| arch | shape | bound (base) | bound (opt) | speedup | bottleneck base→opt | frac base→opt |",
        "|---|---|---|---|---|---|---|",
    ]
    for arch in ARCH_ORDER:
        for shape in SHAPE_ORDER:
            rb, ro = base.get((arch, shape)), opt.get((arch, shape))
            if not rb or not ro or "roofline" not in rb or "roofline" not in ro:
                continue
            b, o = rb["roofline"], ro["roofline"]
            tb = max(b["t_compute_s"], b["t_memory_s"], b["t_collective_s"])
            to = max(o["t_compute_s"], o["t_memory_s"], o["t_collective_s"])
            lines.append(
                f"| {arch} | {shape} | {fmt_s(tb)} | {fmt_s(to)} | **{tb/to:.2f}×** "
                f"| {b['bottleneck']}→{o['bottleneck']} "
                f"| {b['roofline_fraction']:.3f}→{o['roofline_fraction']:.3f} |"
            )
    return lines


def deployed_table(base: Dict, opt: Dict, caption: str) -> List[str]:
    """Per-cell best-of selection — the §3.3 tuner's profile-and-pick applied
    at configuration granularity. Feasibility guard: a config whose
    memory_analysis temps exceed 16 GB/chip cannot deploy regardless of its
    roofline bound (naive 32k prefill)."""
    HBM = 16e9
    lines = [
        f"\n### {caption}\n",
        "| arch | shape | deployed config | bound | temp GB/chip |",
        "|---|---|---|---|---|",
    ]
    for arch in ARCH_ORDER:
        for shape in SHAPE_ORDER:
            cands = []
            for name, rec in (("paper-faithful", base.get((arch, shape))),
                              ("optimized", opt.get((arch, shape)))):
                if not rec or "roofline" not in rec:
                    continue
                r = rec["roofline"]
                t = max(r["t_compute_s"], r["t_memory_s"], r["t_collective_s"])
                temp = (rec.get("memory_analysis") or {}).get("temp_size_in_bytes") or 0
                feasible = float(temp or 0) <= HBM
                cands.append((not feasible, t, name, temp))
            if not cands:
                continue
            cands.sort()
            infeas, t, name, temp = cands[0]
            note = "" if not infeas else " ⚠ exceeds HBM"
            lines.append(
                f"| {arch} | {shape} | {name}{note} | {fmt_s(t)} | "
                f"{float(temp or 0)/1e9:.1f} |"
            )
    return lines


def metrics_table(snapshot: Dict, caption: str = "Obs metrics") -> List[str]:
    """Render a ``repro.obs`` metrics snapshot ({counters, gauges,
    histograms}) as a markdown table; histograms show count + p50/p99."""
    lines = [f"\n### {caption}\n",
             "| metric | kind | value |", "|---|---|---|"]
    for name in sorted(snapshot.get("counters", {})):
        lines.append(f"| {name} | counter | {snapshot['counters'][name]:g} |")
    for name in sorted(snapshot.get("gauges", {})):
        lines.append(f"| {name} | gauge | {snapshot['gauges'][name]:g} |")
    for name in sorted(snapshot.get("histograms", {})):
        h = snapshot["histograms"][name]
        lines.append(f"| {name} | histogram | n={h['count']} "
                     f"p50={h['p50']:.4g} p99={h['p99']:.4g} |")
    return lines


def conv_trajectory_table(path: Path = Path("BENCH_conv.json")) -> List[str]:
    """Render the conv perf-trajectory artifact: one row per recorded run
    (history oldest-first, current run last) with per-layer fused/two_kernel
    timings, stamped with timestamp + git rev + backend fingerprint."""
    if not path.exists():
        return []
    try:
        cur = json.loads(path.read_text())
    except json.JSONDecodeError:
        return []
    if not isinstance(cur, dict) or "layers" not in cur:
        return []
    runs = [r for r in cur.get("history", []) if isinstance(r, dict)] + [cur]
    lines = [
        "\n### Conv plan trajectory (BENCH_conv.json)\n",
        "| timestamp | git rev | backend | layer | fused µs | banded µs | "
        "two_kernel µs | xla µs |",
        "|---|---|---|---|---|---|---|---|",
    ]

    def us(entry, plan):
        v = entry.get(plan)
        return f"{v:.0f}" if isinstance(v, (int, float)) else "—"

    for r in runs:
        ts = r.get("timestamp", "?")
        rev = r.get("git_rev", "?")
        backend = r.get("backend", "?")
        for layer, entry in sorted(r.get("layers", {}).items()):
            lines.append(
                f"| {ts} | {rev} | {backend} | {layer} "
                f"| {us(entry, 'fused')} | {us(entry, 'banded')} "
                f"| {us(entry, 'two_kernel')} | {us(entry, 'xla')} |")
    return lines


def main():
    sp = load("pod16x16", 50)
    mp = load("pod2x16x16", 50)
    dense = load("pod16x16", 0)
    opt = load("pod16x16", 50, tag="_opt")
    out = ["<!-- AUTOGENERATED by benchmarks/report.py — do not hand-edit tables -->"]
    out += dryrun_table(sp, "Dry-run, single pod (16×16), column-wise N:M 50% (paper-faithful)")
    out += roofline_table(sp, "Roofline, single pod (16×16), sparse 50% (paper-faithful baseline)")
    if opt:
        out += roofline_table(opt, "Roofline, single pod, sparse 50% OPTIMIZED "
                                   "(chunked attention + shard-local reduce + grouped MoE + decode restructure)")
        out += compare_table(sp, opt, "Baseline → optimized, per-cell step-time bound")
        out += deployed_table(sp, opt, "Deployed configuration per cell "
                                       "(tuner-style best-of, HBM-feasibility-guarded)")
    if dense:
        out += roofline_table(dense, "Roofline, single pod (16×16), dense baseline")
    if mp:
        out += dryrun_table(mp, "Dry-run, multi-pod (2×16×16) — proves the pod axis shards")
    out += conv_trajectory_table()
    print("\n".join(out))


if __name__ == "__main__":
    main()
