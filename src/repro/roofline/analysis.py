"""Roofline analysis from dry-run compiled artifacts.

Three terms per (arch × shape × mesh), against the peaks of the target chip
(:data:`DEVICE_PEAKS`, keyed by ``jax.Device.device_kind``; the dry-run
targets TPU v5e):
  compute    = HLO_FLOPs_per_chip / peak_FLOP/s
  memory     = HLO_bytes_per_chip / HBM_bw
  collective = collective_bytes_per_chip / link_bw

cost_analysis() is computed on the post-SPMD per-device module, so flops /
bytes are already per-chip.  Collective bytes are NOT in cost_analysis —
they are parsed from the optimized HLO text: for every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute we take
max(input bytes, output bytes) as the wire-traffic proxy.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    flops: float         # dense bf16 FLOP/s per chip
    hbm_bw: float        # HBM bytes/s per chip
    link_bw: float       # bytes/s per inter-chip (ICI) link


#: Published per-chip peaks by ``device_kind``.  TPU v5e (Google Cloud
#: documentation, "TPU v5e"): 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s
#: of inter-chip interconnect over four links (50 GB/s each).
DEVICE_PEAKS: Dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(flops=197e12, hbm_bw=819e9, link_bw=50e9),
}

#: the chip the dry-run's per-chip roofline models
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks_for(device_kind: str) -> DevicePeaks:
    """Peaks of ``device_kind``; a kind without published peaks here is an
    error, never a silent default."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       f"known: {sorted(DEVICE_PEAKS)}") from None


_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f32": 4, "s32": 4, "u32": 4,
    "f64": 8, "s64": 8, "u64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def shape_bytes(shape_str: str) -> int:
    """'bf16[8,128]{1,0}' -> byte count. Tuple shapes handled by the caller."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclasses.dataclass
class CollectiveStats:
    counts: Dict[str, int]
    bytes_by_kind: Dict[str, int]

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum wire bytes of every collective op in an (optimized) HLO module.

    Loop bodies are counted once (an under-estimate when collectives sit in a
    scanned layer body — the per-layer trip count multiplier is applied by the
    caller when known via `loop_multipliers`).
    """
    counts: Dict[str, int] = {}
    by: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+([\w\-]+)", s)
        if not m:
            continue
        out_shape, op = m.groups()
        base_op = op.rstrip("-start").rstrip("-done") if op.endswith(("-start", "-done")) else op
        if base_op not in _COLLECTIVES:
            continue
        if op.endswith("-done"):
            continue  # counted at -start
        out_b = shape_bytes(out_shape)
        # operand bytes: parse shapes inside the argument list
        args = s[s.find("(") :]
        in_b = shape_bytes(args)
        traffic = max(in_b, out_b)
        counts[base_op] = counts.get(base_op, 0) + 1
        by[base_op] = by.get(base_op, 0) + traffic
    return CollectiveStats(counts=counts, bytes_by_kind=by)


def count_while_trip(hlo_text: str) -> List[int]:
    """Best-effort trip counts of while loops (from known_trip_count)."""
    return [int(x) for x in re.findall(r'known_trip_count=\{?"?n"?[=:](\d+)', hlo_text)]


@dataclasses.dataclass
class Roofline:
    flops: float                 # per chip
    hlo_bytes: float             # per chip
    collective_bytes: float      # per chip
    model_flops: float           # 6*N*D global
    chips: int
    device_kind: str             # key of DEVICE_PEAKS

    @property
    def peaks(self) -> DevicePeaks:
        return peaks_for(self.device_kind)

    @property
    def t_compute(self) -> float:
        return self.flops / self.peaks.flops

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / self.peaks.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / self.peaks.link_bw

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> float:
        total = self.flops * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the step's bound time spent at the compute roofline if
        only MODEL_FLOPS were executed — the 'score' we hillclimb."""
        ideal = self.model_flops / self.chips / self.peaks.flops
        return ideal / self.t_bound if self.t_bound else 0.0

    def to_dict(self) -> Dict:
        return {
            "flops_per_chip": self.flops,
            "hlo_bytes_per_chip": self.hlo_bytes,
            "collective_bytes_per_chip": self.collective_bytes,
            "model_flops": self.model_flops,
            "chips": self.chips,
            "device_kind": self.device_kind,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def model_flops_for(cfg, cell, sparsity: float = 0.0) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE); D = tokens processed.

    For decode cells D = global_batch (one token each); the attention
    KV-read work is memory-side and not part of the 6ND convention.
    Sparsity scales the prunable fraction of N (embeddings excluded).
    """
    n_active = cfg.active_param_count()
    emb = cfg.padded_vocab * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    body = max(n_active - emb, 0)
    n_eff = emb + body * (1.0 - sparsity)
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * n_eff * tokens
    if cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * n_eff * tokens
    return 2.0 * n_eff * cell.global_batch  # decode: one token per sequence
