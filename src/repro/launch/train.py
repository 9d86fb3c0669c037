"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --steps 100 \
        --sparsity 0.5 --ckpt-dir /tmp/ckpt [--mesh host|single|multi] [--smoke]

On the host (default) this trains the reduced config for real; with
--mesh single/multi it installs the production mesh + shardings (on real TPU
hardware that is the deployment path; on this CPU container use
repro.launch.dryrun to validate compilation instead).
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import get_config, smoke_config
from repro.core.pruning import SparsityConfig
from repro.data import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh, mesh_tp
from repro.optim import AdamWConfig
from repro.sharding import ShardingCtx, use_ctx
from repro.train import TrainConfig, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--sparsity", type=float, default=0.5)
    ap.add_argument("--format", default="compressed_xla")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh", choices=["host", "single", "multi"], default="host")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-friendly)")
    args = ap.parse_args()
    enable_compile_cache()

    scfg = SparsityConfig(sparsity=args.sparsity, m=None, tile=None,
                          format=args.format if args.sparsity > 0 else "dense",
                          min_dim=64 if args.smoke else 512)
    cfg = (smoke_config(args.arch) if args.smoke else get_config(args.arch))
    mesh = {"host": make_host_mesh,
            "single": lambda: make_production_mesh(multi_pod=False),
            "multi": lambda: make_production_mesh(multi_pod=True)}[args.mesh]()
    cfg = cfg.with_(sparsity=scfg, tp=mesh_tp(mesh))

    data = DataConfig(vocab_size=cfg.vocab_size, batch=args.batch,
                      seq_len=args.seq, seed=0)
    tcfg = TrainConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                       ckpt_every=args.ckpt_every, log_every=10,
                       microbatches=args.microbatches)
    ctx = ShardingCtx(mesh=mesh) if args.mesh != "host" else None
    with use_ctx(ctx), mesh:
        tr = Trainer(cfg, data, AdamWConfig(lr=args.lr), tcfg)
        out = tr.run()
    for h in out["history"]:
        print(f"step {h['step']:>6}  loss {h['loss']:.4f}  "
              f"gnorm {h.get('grad_norm', 0):.2f}  {h['sec_per_step']*1e3:.0f} ms")
    if out["preempted"]:
        print("preempted — final checkpoint written; restart to resume")


if __name__ == "__main__":
    main()
