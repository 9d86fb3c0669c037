#!/usr/bin/env python3
"""On-chip smoke test: the main serving path and the kernels it stands on.

    python chip_smoke.py            # one TPU chip: kernels, then serving
    python chip_smoke.py --chips 4  # four chips: sharded sparse train step

One process drives the chip; nothing here starts a child that needs it.
Phases run in order and any failure exits non-zero:

1. device  -- refuse at once unless JAX's first device is a TPU.
2. kernels -- every Pallas kernel of the serve and conv paths, compiled
   (``interpret=False``) on real arrays against its ``ref.py`` oracle:
   the column-wise N:M linear at the qwen2-0.5b widths, the ResNet-50 stage
   convs through dispatched ``conv_apply`` and each Pallas conv plan, the
   fused im2col+pack kernel, and qwen2-0.5b paged decode attention.
3. serve   -- qwen2-0.5b at its published widths in bfloat16, linears 50%
   column-wise compressed, random weights from a fixed seed, driven by the
   paged continuous scheduler over a seeded synthetic trace.  Every request
   must retire ``ok``; request 0's prefill and first-decode logits must
   agree with the same model on the XLA reference ops; no dispatch
   candidate may be quarantined or retried; linear, conv and paged_attn
   must resolve to Pallas candidates.

With ``--chips 4`` only the sharded train step runs: qwen2-7b at its
published widths cut to 8 layers, on a (data=1, model=4) mesh with the
shard-local REDUCE format.  Its loss must be finite and fall over 3 steps
on a fixed batch; at step 0 its loss and last-position logits must match a
bfloat16 forward of the same parameters on device 0 alone; the compressed
linears, run per shard, must resolve to Pallas candidates.

The last line of standard output is one JSON object with the device as JAX
reports it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# ResNet-50 stage convs (benchmarks/bench_conv_fused.py LAYERS):
#      name        c    h    o   k  stride batch
CONV_LAYERS = [
    ("s2.c2", 128, 28, 128, 3, 1, 1),
    ("s3.c2", 256, 14, 256, 3, 1, 1),
    ("s4.c2", 512, 7, 512, 3, 1, 1),
    ("s2.c2.b4", 128, 28, 128, 3, 1, 4),
    ("stem.b8", 64, 112, 64, 3, 2, 8),
]
SPARSITY = 0.5

# Tolerances: largest |kernel - oracle| over largest |oracle|.  bf16 outputs
# round at 2^-8 relative; the f32-accumulated reductions add little on top.
TOL_LINEAR = 1e-2
TOL_CONV = 2e-2
TOL_PACK = 0.0  # the pack kernel only moves elements: exact
TOL_PAGED = 2e-2
# 24 bf16 layers: the Pallas and XLA reference ops round their bf16 outputs
# after differently ordered f32 reductions, and the differences compound
TOL_LOGITS = 5e-2
# the sharded step-0 loss against one device's forward: about 1e-4 on four
# v5e chips.  The loss of a random model sits near ln(vocab) whatever its
# forward, so the logits are compared as well (TOL_LOGITS)
TOL_TRAIN_LOSS = 1e-3


def rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


class Checks:
    """Collects named checks; every failure is reported, then fails the run."""

    def __init__(self):
        self.failed = []

    def check(self, name: str, ok: bool, detail: str = "") -> None:
        print(f"  [{'ok' if ok else 'FAIL'}] {name}{': ' + detail if detail else ''}",
              flush=True)
        if not ok:
            self.failed.append(name)

    def err(self, name: str, err: float, tol: float) -> None:
        self.check(name, err <= tol, f"max rel err {err:.3e} (tol {tol:.0e})")


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _compressed_linear(key, d_in, d_out, dtype):
    import jax

    from repro.core.formats import meta_for, pack_colwise
    from repro.core.pruning import SparsityConfig, colwise_nm_mask

    w = jax.random.normal(key, (d_in, d_out)) / d_in ** 0.5
    meta = meta_for(d_in, d_out, SparsityConfig(SPARSITY, m=None, tile=None))
    values, idx = pack_colwise(w, colwise_nm_mask(w, SPARSITY, m=None,
                                                  tile=meta.tile), meta)
    return values.astype(dtype), idx


def linear_kernels(chk: Checks, rows: int = 128,
                   interpret: bool = False) -> None:
    """Column-wise N:M linear at the qwen2-0.5b projection widths."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.kernels.colwise_nm.kernel import colwise_nm_matmul_pallas
    from repro.kernels.colwise_nm.ref import colwise_nm_matmul_ref

    cfg = get_config("qwen2-0.5b")
    d, hd = cfg.d_model, cfg.resolved_head_dim
    shapes = [("q/o", d, cfg.n_heads * hd), ("k/v", d, cfg.n_kv_heads * hd),
              ("gate/up", d, cfg.d_ff), ("down", cfg.d_ff, d)]
    key = jax.random.PRNGKey(0)
    for name, d_in, d_out in shapes:
        key, kw, kx = jax.random.split(key, 3)
        values, idx = _compressed_linear(kw, d_in, d_out, jnp.bfloat16)
        x = jax.random.normal(kx, (rows, d_in)).astype(jnp.bfloat16)
        y = jax.jit(lambda x, v, i: colwise_nm_matmul_pallas(
            x, v, i, interpret=interpret))(x, values, idx)
        want = colwise_nm_matmul_ref(x.astype(jnp.float32),
                                     values.astype(jnp.float32), idx)
        chk.err(f"colwise_nm {name} [{rows}x{d_in}] -> {d_out} "
                f"(tile {values.shape[2]})", rel_err(y, want), TOL_LINEAR)


def conv_kernels(chk: Checks, layers=CONV_LAYERS,
                 interpret: bool = False,
                 expect_backend: str = "pallas") -> None:
    """ResNet stage convs: dispatched ``conv_apply`` plus every Pallas conv
    plan that fits, and the pack kernel, against the dense-masked XLA conv."""
    import jax
    import jax.numpy as jnp

    from repro import dispatch
    from repro.core import conv_apply
    from repro.core.formats import unpack_colwise
    from repro.core.pruning import SparsityConfig
    from repro.kernels.conv_gemm import compress_conv_weights, conv2d_cnhw_ref
    from repro.kernels.im2col_pack.kernel import im2col_pack_pallas
    from repro.kernels.im2col_pack.ref import im2col_pack_ref

    scfg = SparsityConfig(SPARSITY, m=None, tile=None,
                          format="compressed_pallas")
    key = jax.random.PRNGKey(1)
    for name, c, h, o, k, stride, batch in layers:
        key, kx, kw = jax.random.split(key, 3)
        pad = k // 2
        x = jax.random.normal(kx, (c, batch, h, h)).astype(jnp.bfloat16)
        wt = jax.random.normal(kw, (o, k, k, c)) / (k * k * c) ** 0.5
        values, idx, meta = compress_conv_weights(wt, scfg)
        values = values.astype(jnp.bfloat16)
        w_masked = unpack_colwise(values.astype(jnp.float32), idx, meta)
        w_ohwi = w_masked.T.reshape(o, k, k, c)
        want = conv2d_cnhw_ref(x.astype(jnp.float32), w_ohwi, stride=stride,
                               pad=pad)
        params = {"values": values, "idx": idx}
        geo = dict(kh=k, kw=k, stride=stride, pad=pad)

        key_ = dispatch.conv_key(c, h, h, o, k, k, stride, pad,
                                 values.shape[1], values.shape[2],
                                 dtype=x.dtype, batch=batch)
        plans = [None, "fused_sparse_pallas", "fused_banded_pallas",
                 "two_kernel_pipelined", "im2col_sparse_pallas"]
        for impl in plans:
            if impl is not None and not dispatch.REGISTRY.get(
                    "conv", impl).feasible(key_)[0]:
                print(f"  [skip] conv {name} {impl}: VMEM-infeasible here",
                      flush=True)
                continue
            label = impl
            if impl is None:
                spec = dispatch.best_impl(key_, param_keys=("values", "idx"))
                label = f"dispatched -> {spec.name}"
                chk.check(f"conv {name} resolved to {expect_backend}",
                          spec.backend == expect_backend, spec.backend)
            y = jax.jit(lambda x, p, impl=impl: conv_apply(
                p, x, impl=impl, **geo))(x, params)
            chk.err(f"conv {name} {label}", rel_err(y, want), TOL_CONV)
        strips = jax.jit(lambda x: im2col_pack_pallas(
            x, k, k, stride=stride, pad=pad, interpret=interpret))(x)
        chk.err(f"im2col_pack {name}",
                rel_err(strips, im2col_pack_ref(x, k, k, stride, pad)),
                TOL_PACK)


def paged_kernel(chk: Checks, batch: int = 8,
                 interpret: bool = False) -> None:
    """qwen2-0.5b paged decode attention over shuffled, ragged page tables."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.kernels.flash_attn.paged import (
        paged_attention_pallas,
        paged_attention_ref,
    )

    cfg = get_config("qwen2-0.5b")
    h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ps, n_max = 16, 24
    rng = np.random.default_rng(0)
    n_pages = batch * n_max + 1
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    q = jax.random.normal(ks[0], (batch, 1, h, d)).astype(jnp.bfloat16)
    kn = jax.random.normal(ks[1], (batch, 1, kv, d)).astype(jnp.bfloat16)
    vn = jax.random.normal(ks[2], (batch, 1, kv, d)).astype(jnp.bfloat16)
    # two layers of the [L, P, ps, KV*D] cache; the kernel reads layer 1
    kp = jax.random.normal(ks[3], (2, n_pages, ps, kv * d)).astype(
        jnp.bfloat16)
    vp = jax.random.normal(ks[4], (2, n_pages, ps, kv * d)).astype(
        jnp.bfloat16)
    tables = rng.permutation(n_pages - 1)[:batch * n_max].reshape(
        batch, n_max).astype(np.int32)
    lengths = rng.integers(0, n_max * ps, batch).astype(np.int32)
    lengths[0] = 0  # empty cache: only the new key is attended
    y = jax.jit(lambda *a: paged_attention_pallas(
        *a, 1, page_size=ps, interpret=interpret))(q, kn, vn, kp, vp, tables,
                                                   lengths)
    f32 = [t.astype(jnp.float32) for t in (q, kn, vn, kp, vp)]
    want = paged_attention_ref(*f32, tables, lengths, 1)
    chk.err(f"paged_attention B={batch} H={h} KV={kv} D={d} ps={ps}",
            rel_err(y, want), TOL_PAGED)


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def first_logits(engine, prompt, page_size: int, next_token=None):
    """Prefill logits of ``prompt`` alone, then the logits of one paged
    decode step on ``next_token`` (default: the prefill's greedy token)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.models import registry as reg
    from repro.serve.kv_pages import PagePool, pack_prompts

    n_pages = -(-(len(prompt) + 1) // page_size)
    pages = PagePool(n_pages, page_size)
    pages.alloc(0, len(prompt) + 1)
    tables = pages.table_array(1, n_pages)
    cache = reg.paged_cache_init_fn(engine.cfg, n_pages, page_size)()
    logits, cache = engine.packed_prefill_step(
        cache, pack_prompts([prompt], [0]), tables, page_size=page_size)
    logits = np.asarray(logits[:, -1].astype(jnp.float32))
    if next_token is None:
        next_token = int(logits[0, :engine.cfg.vocab_size].argmax())
    dlogits, _ = engine.paged_decode_step(
        cache, np.array([[next_token]], np.int32),
        np.array([len(prompt)], np.int32), tables, page_size=page_size)
    return logits, np.asarray(dlogits[:, -1].astype(jnp.float32)), next_token


def serve_phase(chk: Checks, *, arch: str = "qwen2-0.5b",
                smoke: bool = False, n_requests: int = 8,
                prompt_lens=(32, 256), new_tokens=(16, 32),
                page_size: int = 16, expect_backend: str = "pallas") -> None:
    """Paged continuous serving of ``arch`` in bfloat16 over a seeded trace,
    checked against the XLA reference ops and the dispatch counters (which
    count only while ``repro.obs`` is enabled)."""
    import jax

    from repro.launch.serve import build_engine, watchdog_heartbeat
    from repro.obs import metrics as om
    from repro.obs import trace as ot
    from repro.serve import Scheduler, synthetic_trace
    from repro.train.fault import StepWatchdog

    args = argparse.Namespace(arch=arch, sparsity=SPARSITY, smoke=smoke,
                              new_tokens=new_tokens[1], temperature=0.0)
    t0 = time.perf_counter()
    eng = build_engine(args, dtype="bfloat16")
    cfg = eng.cfg
    print(f"  model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, dtype {cfg.dtype}, "
          f"{SPARSITY:.0%} column-wise; built in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    trace = synthetic_trace(n_requests, seed=0, vocab=cfg.vocab_size,
                            prompt_lens=prompt_lens, new_tokens=new_tokens)

    # request 0 through the dispatched ops, then the XLA reference ops
    t0 = time.perf_counter()
    logits, dlogits, tok = first_logits(eng, trace[0].prompt, page_size)
    os.environ["REPRO_DISPATCH"] = "off"  # legacy routing: XLA references
    try:
        ref_eng = type(eng)(cfg, eng.params, eng.scfg)
        ref_logits, ref_dlogits, _ = first_logits(ref_eng, trace[0].prompt,
                                                  page_size, next_token=tok)
    finally:
        del os.environ["REPRO_DISPATCH"]
    del ref_eng
    print(f"  request 0 (prompt {len(trace[0].prompt)}) vs XLA reference "
          f"ops: {time.perf_counter() - t0:.1f} s incl. compiles", flush=True)
    chk.err("prefill logits vs compressed_xla + paged_attn_ref",
            rel_err(logits, ref_logits), TOL_LOGITS)
    chk.err("first decode logits vs compressed_xla + paged_attn_ref",
            rel_err(dlogits, ref_dlogits), TOL_LOGITS)

    sched = Scheduler(eng, n_slots=n_requests, paged=True,
                      page_size=page_size)
    dog = StepWatchdog(timeout_s=600.0)
    t0 = time.perf_counter()
    try:
        completions = sched.run(trace, heartbeat=watchdog_heartbeat(dog))
    finally:
        dog.stop()
    wall = time.perf_counter() - t0
    stats = sched.stats
    statuses = sorted({c.status for c in completions})
    chk.check(f"{len(trace)} requests served", len(completions) == len(trace)
              and statuses == ["ok"],
              f"statuses {statuses}, {int(stats['generated_tokens'])} "
              f"tokens in {wall:.1f} s incl. compiles")
    print(f"  one-chip smoke decode rate (information only, not a "
          f"benchmark): {stats['decode_tok_s']:.1f} tok/s over "
          f"{int(stats['decode_steps'])} steps", flush=True)

    decisions = {}
    for ev in ot.events():
        a = ev.get("args", {})
        # "legacy" decisions are the XLA reference engine's, not the server's
        if ev.get("name") == "dispatch.decision" and a.get("source") != "legacy":
            decisions.setdefault((a.get("op"), a.get("phase") or "-"),
                                 set()).add((a.get("impl"), a.get("backend")))
    for (op, phase), impls in sorted(decisions.items()):
        print(f"  resolved {op:<10} phase={phase:<8} "
              f"{', '.join(sorted(i for i, _ in impls))}", flush=True)
    for op in ("linear", "paged_attn"):
        backends = {b for (o, _), s in decisions.items() if o == op
                    for _, b in s}
        chk.check(f"{op} resolved to {expect_backend}",
                  backends == {expect_backend}, f"backends {sorted(backends)}")
    # counted since obs was enabled: by main, from the first kernel on
    for name in ("dispatch.quarantine", "dispatch.execute_retries"):
        n = om.counter(name).value
        chk.check(f"{name} == 0", n == 0, f"{n:g}")
    jax.clear_caches()


# ---------------------------------------------------------------------------
# four chips: sharded sparse train step
# ---------------------------------------------------------------------------


def train_phase(chk: Checks, cfg, devices, *, batch: int, seq: int,
                steps: int = 3, lr: float = 1e-3,
                expect_backend: str = "pallas") -> None:
    """``steps`` sharded train steps of ``cfg`` on a (data=1, model=N) mesh
    over ``devices``, against a bfloat16 forward on ``devices[0]`` alone.
    The compressed linears run per shard (``jax.shard_map``); their
    dispatch decisions are read from the obs trace, which must be on."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import steps as steps_mod
    from repro.launch.mesh import make_mesh
    from repro.models import registry as reg
    from repro.obs import metrics as om
    from repro.obs import trace as ot
    from repro.optim import AdamWConfig, adamw_init
    from repro.sharding import ShardingCtx, use_ctx

    mesh = make_mesh((1, len(devices)), ("data", "model"), devices=devices)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                cfg.vocab_size)
    batch_ = {"tokens": tokens}
    shapes, specs = reg.abstract_params(cfg)
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    print(f"  model {cfg.name} cut to {cfg.n_layers} layers: "
          f"{n_params / 1e9:.2f} B parameters ({cfg.param_dtype}), "
          f"mesh data=1 x model={len(devices)}", flush=True)
    fwd, lossf = reg.forward_fn(cfg), reg.loss_fn(cfg)

    def last_logits(p, b):  # [batch, vocab]: every position feeds the last
        return fwd(p, b)[:, -1].astype(jnp.float32)

    with use_ctx(ShardingCtx(mesh=mesh)), mesh:
        (p_sh, o_sh, b_sh), out_sh = steps_mod.train_shardings(
            cfg, mesh, shapes, specs, batch_)
        t0 = time.perf_counter()
        params = jax.jit(lambda k: reg.init_params(cfg, k)[0],
                         out_shardings=p_sh)(jax.random.PRNGKey(0))
        jax.block_until_ready(params)
        print(f"  sharded init {time.perf_counter() - t0:.1f} s", flush=True)

    # reference: bf16 copy of the same parameters, one device, no mesh
    # (made before the optimizer state exists, so device 0 holds both)
    one = jax.sharding.SingleDeviceSharding(devices[0])
    to_bf16 = jax.jit(lambda p: jax.tree.map(
        lambda a: a.astype(jnp.bfloat16)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, p))
    ref_params = jax.device_put(to_bf16(params), one)
    ref_batch = jax.device_put(batch_, one)
    ref_loss = float(jax.jit(lambda p, b: lossf(p, b)[0])(ref_params,
                                                          ref_batch))
    ref_logits = np.asarray(jax.jit(last_logits)(ref_params, ref_batch))
    del ref_params

    with use_ctx(ShardingCtx(mesh=mesh)), mesh:
        batch_sh = jax.device_put(batch_, b_sh)
        logits = np.asarray(jax.jit(last_logits)(params, batch_sh))
        opt = jax.jit(adamw_init, out_shardings=o_sh)(params)
        step = jax.jit(steps_mod.make_train_step(cfg, AdamWConfig(lr=lr)),
                       in_shardings=(p_sh, o_sh, b_sh), out_shardings=out_sh,
                       donate_argnums=(0, 1))
        losses = []
        for i in range(steps):
            t0 = time.perf_counter()
            params, opt, m = step(params, opt, batch_sh)
            losses.append(float(m["loss"]))
            print(f"  step {i}: loss {losses[-1]:.5f} "
                  f"({time.perf_counter() - t0:.1f} s)", flush=True)
    chk.check("loss finite", bool(np.isfinite(losses).all()), f"{losses}")
    chk.check("loss falls on a fixed batch", losses[-1] < losses[0],
              f"{losses[0]:.5f} -> {losses[-1]:.5f}")
    err = abs(losses[0] - ref_loss) / abs(ref_loss)
    chk.check("step-0 loss vs bf16 forward on device 0", err <= TOL_TRAIN_LOSS,
              f"{losses[0]:.5f} vs {ref_loss:.5f}, rel err {err:.2e} "
              f"(tol {TOL_TRAIN_LOSS:.0e})")
    chk.err("last-position logits vs bf16 forward on device 0",
            rel_err(logits, ref_logits), TOL_LOGITS)
    # a per-shard key equal to a single-device one resolves from the memo
    # and logs no decision, so every linear decision of the phase is read
    linear = [ev["args"] for ev in ot.events()
              if ev.get("name") == "dispatch.decision"
              and ev["args"].get("op") == "linear"]
    impls = sorted({a["impl"] for a in linear})
    backends = sorted({a["backend"] for a in linear})
    print(f"  resolved linear: {', '.join(impls)}", flush=True)
    chk.check(f"linear resolved to {expect_backend}, per shard",
              backends == [expect_backend]
              and not any("mesh" in a["token"] for a in linear),
              f"backends {backends}, {len(linear)} decisions")
    n = om.counter("dispatch.quarantine").value
    chk.check("dispatch.quarantine == 0", n == 0, f"{n:g}")


def train_config(base=None, min_dim: int = 512, chips: int = 4,
                 tile: int = 128):
    """``base`` (default: qwen2-7b cut to 8 layers) set up for the sharded
    train step: 50% column-wise with the shard-local REDUCE format over
    ``chips`` model shards, bfloat16 compute over float32 parameters.
    ``tile``-wide column tiles give every column-parallel projection a tile
    count divisible by ``chips``, so its values shard over the model axis
    (one d_out-wide tile could only be replicated)."""
    from repro.configs import get_config
    from repro.core.pruning import SparsityConfig

    base = base or get_config("qwen2-7b").with_(n_layers=8)
    scfg = SparsityConfig(SPARSITY, m=None, tile=tile, format="compressed_xla",
                          min_dim=min_dim, shard_local_reduce=True,
                          reduce_groups=chips)
    return base.with_(sparsity=scfg, tp=chips, dp=1, dtype="bfloat16",
                      param_dtype="float32", remat=True)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded train step on four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    os.environ.setdefault("REPRO_DISPATCH_DB",
                          str(ROOT / ".repro_cache" / "dispatch_profile.json"))
    from repro import obs
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    obs.set_enabled(True)  # dispatch decisions + quarantine/retry counters
    chk = Checks()
    t_start = time.perf_counter()

    def phase(name, fn, *a, **kw):
        t0 = time.perf_counter()
        print(f"== {name} ==", flush=True)
        fn(chk, *a, **kw)
        print(f"   {name}: {time.perf_counter() - t0:.1f} s", flush=True)

    if args.chips == 4:
        phase("sharded train step", train_phase, train_config(), devices[:4],
              batch=4, seq=256)
    else:
        phase("kernels: column-wise linear", linear_kernels)
        phase("kernels: ResNet convs", conv_kernels)
        phase("kernels: paged attention", paged_kernel)
        phase("serve", serve_phase)
    print(f"total {time.perf_counter() - t_start:.1f} s", flush=True)
    if chk.failed:
        print(f"chip_smoke: {len(chk.failed)} check(s) failed: {chk.failed}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
