import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init). Everything below is ordinary.

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import LONG_CONTEXT_ARCHS, SHAPES, get_config, list_archs  # noqa: E402
from repro.core.pruning import SparsityConfig  # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_dp, mesh_tp  # noqa: E402
from repro.launch import steps as steps_mod  # noqa: E402
from repro.models import registry as reg  # noqa: E402
from repro.optim import AdamWConfig, adamw_init  # noqa: E402
from repro.roofline.analysis import (  # noqa: E402
    DRYRUN_DEVICE_KIND,
    Roofline,
    model_flops_for,
)
from repro.roofline.hlo_analyzer import analyze_hlo, xla_cost_analysis  # noqa: E402
from repro.sharding import RULES, ShardingCtx, use_ctx  # noqa: E402


def cell_skipped(arch: str, shape: str) -> str:
    if shape == "long_500k" and arch not in LONG_CONTEXT_ARCHS:
        return "long_500k needs sub-quadratic attention; skipped for pure full-attention archs (DESIGN.md §6)"
    return ""


# per-cell microbatch counts for the big training cells (activation memory)
MICROBATCH = {
    ("qwen2-vl-72b", "train_4k"): 8,
    ("nemotron-4-15b", "train_4k"): 4,
    ("qwen2-7b", "train_4k"): 4,
    ("zamba2-7b", "train_4k"): 4,
    ("moonshot-v1-16b-a3b", "train_4k"): 2,
}


def build_cfg(arch: str, sparsity: float, fmt: str, mesh, attn: str = "naive",
              local_reduce: bool = False, remat_policy: str = "nothing",
              attn_chunk: int = 512, moe_impl: str = "auto") -> "ModelConfig":
    cfg = get_config(arch)
    scfg = SparsityConfig(
        sparsity=sparsity,
        m=None,               # adaptive M = full reduction dim (paper §3.1)
        tile=None,            # tile = d_out / tp (DESIGN §4)
        format=fmt if sparsity > 0 else "dense",
        min_dim=512,
        shard_local_reduce=local_reduce,
        reduce_groups=mesh_tp(mesh),
    )
    return cfg.with_(
        dtype="bfloat16",
        param_dtype="bfloat16",
        remat=True,
        tp=mesh_tp(mesh),
        dp=mesh_dp(mesh),
        sparsity=scfg,
        attn_impl=attn,
        remat_policy=remat_policy,
        attn_chunk=attn_chunk,
        moe_impl=moe_impl,
    )


def lower_cell(arch: str, shape: str, mesh, sparsity: float, fmt: str, attn: str = "naive",
               local_reduce: bool = False, remat_policy: str = "nothing",
               attn_chunk: int = 512, moe_impl: str = "auto"):
    """Lower + compile one (arch, shape) cell on the given mesh."""
    cfg = build_cfg(arch, sparsity, fmt, mesh, attn, local_reduce, remat_policy, attn_chunk, moe_impl)
    cell = SHAPES[shape]
    spec = reg.input_specs(cfg, cell)
    param_shapes, param_specs = reg.abstract_params(cfg)

    ctx = ShardingCtx(mesh=mesh)
    with use_ctx(ctx), mesh:
        if spec["kind"] == "train":
            mb = MICROBATCH.get((arch, shape), 1)
            step = steps_mod.make_train_step(cfg, AdamWConfig(), microbatches=mb)
            opt_shapes = jax.eval_shape(adamw_init, param_shapes)
            in_sh, out_sh = steps_mod.train_shardings(
                cfg, mesh, param_shapes, param_specs, spec["batch"]
            )
            lowered = jax.jit(
                step, in_shardings=in_sh, out_shardings=out_sh, donate_argnums=(0, 1)
            ).lower(param_shapes, opt_shapes, spec["batch"])
        elif spec["kind"] == "prefill":
            step = steps_mod.make_prefill_step(cfg)
            in_sh = steps_mod.serve_shardings(cfg, mesh, param_shapes, param_specs, spec)
            lowered = jax.jit(step, in_shardings=in_sh).lower(param_shapes, spec["batch"])
        else:
            step = steps_mod.make_decode_step(cfg)
            # batch-1 long-context cells need the explicit seq-sharded cache
            # (distributed flash-decode); bigger batches do best with GSPMD's
            # own partial-axis KV layout (EXPERIMENTS §Perf iteration K)
            auto = spec["tokens"].shape[0] > 1
            in_sh, cache_sh = steps_mod.serve_shardings(
                cfg, mesh, param_shapes, param_specs, spec, cache_auto=auto
            )
            lowered = jax.jit(
                step,
                in_shardings=in_sh,
                out_shardings=(None, cache_sh),
                donate_argnums=(1,),
            ).lower(param_shapes, spec["cache"], spec["tokens"], spec["pos"])
        compiled = lowered.compile()
    return cfg, cell, lowered, compiled


def analyze(cfg, cell, lowered, compiled, mesh, sparsity: float):
    chips = mesh.devices.size
    cost = xla_cost_analysis(compiled)
    flops = float(cost.get("flops", 0.0))
    hbm_bytes = float(cost.get("bytes accessed", 0.0))
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_size_in_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_in_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_in_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_in_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception:
        mem_d = {}
    hlo = compiled.as_text()
    # loop-aware per-chip accounting (XLA's cost_analysis counts while bodies
    # once; the analyzer multiplies by known trip counts)
    acc = analyze_hlo(hlo)
    rl = Roofline(
        flops=acc["flops"],
        hlo_bytes=acc["bytes"],
        collective_bytes=acc["collective_bytes"],
        model_flops=model_flops_for(cfg, cell, sparsity),
        chips=chips,
        device_kind=DRYRUN_DEVICE_KIND,
    )
    return {
        "memory_analysis": mem_d,
        "cost_analysis_raw": {"flops": flops, "bytes_accessed": hbm_bytes},
        "collectives": {
            "counts": acc["collective_counts"],
            "bytes": acc["collective_by_kind"],
        },
        "roofline": rl.to_dict(),
        "hlo_size_chars": len(hlo),
    }


def run_cell(arch, shape, multi_pod, sparsity, fmt, out_dir: Path, tag="", attn="naive",
             local_reduce=False, remat_policy="nothing", attn_chunk=512, moe_impl="auto"):
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    name = f"{arch}__{shape}__{mesh_name}__s{int(sparsity*100)}{tag}"
    out_path = out_dir / f"{name}.json"
    if out_path.exists():
        print(f"[skip-cached] {name}")
        return True
    skip = cell_skipped(arch, shape)
    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_name,
        "sparsity": sparsity, "format": fmt if sparsity > 0 else "dense",
    }
    if skip:
        rec["skipped"] = skip
        out_path.write_text(json.dumps(rec, indent=1))
        print(f"[skipped] {name}: {skip}")
        return True
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        cfg, cell, lowered, compiled = lower_cell(arch, shape, mesh, sparsity, fmt, attn, local_reduce, remat_policy, attn_chunk, moe_impl)
        rec.update(analyze(cfg, cell, lowered, compiled, mesh, sparsity))
        rec["compile_seconds"] = time.time() - t0
        out_path.write_text(json.dumps(rec, indent=1))
        rl = rec["roofline"]
        print(
            f"[ok] {name}: bottleneck={rl['bottleneck']} "
            f"tc={rl['t_compute_s']:.4f}s tm={rl['t_memory_s']:.4f}s "
            f"tcoll={rl['t_collective_s']:.4f}s frac={rl['roofline_fraction']:.3f} "
            f"({rec['compile_seconds']:.0f}s compile)"
        )
        return True
    except Exception as e:  # noqa: BLE001
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        rec["compile_seconds"] = time.time() - t0
        out_path.with_suffix(".err.json").write_text(json.dumps(rec, indent=1))
        print(f"[FAIL] {name}: {rec['error'][:300]}")
        return False


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run driver")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--sparsity", type=float, default=0.5)
    ap.add_argument("--format", default="compressed_xla")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--attn", default="naive", choices=["naive", "chunked"])
    ap.add_argument("--local-reduce", action="store_true")
    ap.add_argument("--remat-policy", default="nothing", choices=["nothing", "dots"])
    ap.add_argument("--attn-chunk", type=int, default=512)
    ap.add_argument("--moe", default="auto", choices=["auto", "shard_map"])
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    archs = list_archs() if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                ok = run_cell(arch, shape, mp, args.sparsity, args.format, out_dir,
                              tag=args.tag, attn=args.attn, local_reduce=args.local_reduce,
                              remat_policy=args.remat_policy, attn_chunk=args.attn_chunk,
                              moe_impl=args.moe)
                n_fail += 0 if ok else 1
    print(f"done; failures={n_fail}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
