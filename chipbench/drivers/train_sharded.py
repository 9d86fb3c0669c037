"""Driver ``train_sharded``: the program's own sharded train step
(``launch/steps.make_train_step`` with ``train_shardings``, jitted with
the parameters and optimizer state donated) on a (data, model) mesh over
the cell's chips.

Set-up makes the weights on the device from the seed, in one jitted call
laid out as the step takes them, the optimizer state, and
``distinct_batches`` batches of uniform random token ids; it then drives
the compiled step through its first ``setup_steps`` steps on batches
0, 1, 2, ..., reading what the reference will be held to: each step's
loss, the first gradient's norm (the step's metric), the per-leaf norms
of the first gradient as the optimizer applied it (its first moment over
``1 - b1``) and, after the last of them, the per-leaf norms of the
parameters' change.  The window then goes on with the same parameters and
state, one batch per step in turn, dispatching whole steps at most
``max_in_flight`` ahead of the device until ``--seconds`` have passed, and
waits for the last.  ``train_tokens_per_s`` is the tokens of the window's
steps over the time from the first dispatch to the last step's end.

After the window the program's state is freed and the plain float32
reference (``refs/qwen2_train.py``) follows the first steps from the same
weights and batches, made again from the seed.

A traced run hands the readers the trace's leaf operations only
(``leaf_ops``): the layer scans are ``while`` loops, and the event of a
loop spans every operation of its body, so it would hide each idle gap
and each collective inside the scans.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness, weights
from chipbench.refs import qwen2_train as ref

COMPARED = ("loss_rel_err", "grad_norm_rel_err", "grad_leaf_gap",
            "change_leaf_gap")
#: control-flow instructions: each one's event in the trace's ``XLA Ops``
#: line spans the operations of the computations it runs
CONTROL_FLOW = ("while", "conditional", "call")


def leaf_ops(reduced):
    """``reduced`` without its control-flow events, so that busy time,
    idle gaps, the collectives' exposure and the top operations are read
    from the operations that did the work."""
    return dataclasses.replace(reduced, ops=[
        o for o in reduced.ops if o.opcode not in CONTROL_FLOW])


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for the benchmark's configuration."""
    from repro.configs import get_config
    from repro.core.pruning import SparsityConfig

    sp, mesh = cfg["sparsity"], cfg["mesh"]
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    if cfg["rms_norm_eps"] != 1e-6:
        raise ValueError("the program's RMSNorm uses eps 1e-6")
    return get_config(cfg["program_arch"]).with_(
        n_layers=cfg["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg.get("head_dim") or d // h, qkv_bias=True,
        rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["dtype"], param_dtype=cfg["param_dtype"],
        remat=cfg["remat"], tp=mesh["model"], dp=mesh["data"],
        sparsity=SparsityConfig(
            sparsity=sp["fraction"], m=sp["m"], tile=sp["tile"],
            format=sp["format"], min_dim=sp["min_dim"],
            shard_local_reduce=sp["shard_local_reduce"],
            reduce_groups=sp["reduce_groups"]))


def make_params(cfg: dict, abstract, key):
    """Every leaf of the program's parameter tree, by its role:
    ``values`` normal over the square root of the kept rows, ``values_r``
    over that of all groups' kept rows; ``idx`` a random sorted half of the
    input rows per tile, ``idx_r`` of the group's own rows; biases, norms,
    embedding and head as ``weights.make_params`` makes them."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq = cfg["num_attention_heads"] * (cfg.get("head_dim")
                                       or d // cfg["num_attention_heads"])
    d_in = {"q": d, "k": d, "v": d, "o": hq, "gate": d, "up": d, "down": f}
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    keys = jax.random.split(key, len(flat))
    out = []
    for (path, sds), k in zip(flat, keys):
        p = weights.path_str(path)
        parts = p.split("/")
        leaf, shape = parts[-1], sds.shape
        if leaf == "values":
            v = jax.random.normal(k, shape, jnp.float32) / math.sqrt(shape[-2])
        elif leaf == "values_r":
            v = jax.random.normal(k, shape, jnp.float32) \
                / math.sqrt(shape[-3] * shape[-2])
        elif leaf == "idx":
            v = weights.kept_support(k, shape[:-1], d_in[parts[-2]], shape[-1])
        elif leaf == "idx_r":
            v = weights.kept_support(k, shape[:-1],
                                     d_in[parts[-2]] // shape[-2], shape[-1])
        else:
            one = weights.make_params({leaf: sds}, k, d_in_of=None,
                                      scale_of=None)
            v = one[leaf]
        out.append(v.astype(sds.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


class Program:
    """The program's compiled step on the cell's mesh, with the benchmark's
    makers of its inputs laid out as the step takes them."""

    def __init__(self, cfg: dict, tr: dict, devices):
        from repro.launch import steps
        from repro.launch.mesh import make_mesh
        from repro.models import registry as reg
        from repro.optim import AdamWConfig, adamw_init
        from repro.sharding import ShardingCtx

        self.cfg, self.tr = cfg, tr
        self.pcfg = program_config(cfg)
        mesh = cfg["mesh"]
        if mesh["data"] * mesh["model"] != len(devices):
            raise ValueError(f"mesh {mesh} needs {mesh['data'] * mesh['model']}"
                             f" devices, given {len(devices)}")
        self.mesh = make_mesh((mesh["data"], mesh["model"]),
                              ("data", "model"), devices=devices)
        self.ctx = ShardingCtx(mesh=self.mesh)
        self.abstract, specs = reg.abstract_params(self.pcfg)
        b, s = tr["batch"], tr["seq_len"]
        batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        with self.scope():
            (p_sh, o_sh, b_sh), out_sh = steps.train_shardings(
                self.pcfg, self.mesh, self.abstract, specs, batch)
        self.make_params = jax.jit(
            lambda k: make_params(cfg, self.abstract, k), out_shardings=p_sh)
        self.init_opt = jax.jit(adamw_init, out_shardings=o_sh)
        n = tr["distinct_batches"]
        vocab = cfg["vocab_size"]
        self.make_batches = jax.jit(
            lambda k: tuple(jax.random.randint(kk, (b, s), 0, vocab, jnp.int32)
                            for kk in jax.random.split(k, n)),
            out_shardings=(b_sh["tokens"],) * n)
        self.adamw = AdamWConfig(**tr["adamw"])
        self.step = jax.jit(steps.make_train_step(self.pcfg, self.adamw),
                            in_shardings=(p_sh, o_sh, b_sh),
                            out_shardings=out_sh, donate_argnums=(0, 1))
        self.grad_leaf = jax.jit(
            lambda m: ref.leaf_norms(m) / (1.0 - self.adamw.b1))
        self.change_leaf = jax.jit(
            lambda p, k: ref.diff_norms(p, make_params(cfg, self.abstract, k)))
        self.names = ref.leaf_names(self.abstract)

    def scope(self):
        """The sharding context the program's layers read while tracing."""
        from repro.sharding import use_ctx

        stack = contextlib.ExitStack()
        stack.enter_context(use_ctx(self.ctx))
        stack.enter_context(self.mesh)
        return stack

    def run_step(self, params, opt, tokens):
        with self.scope():
            return self.step(params, opt, {"tokens": tokens})

    def first_steps(self, key, batches):
        """The weights made from ``key``, the optimizer state, and the
        first ``setup_steps`` steps: (params, opt, Readings)."""
        params = self.make_params(key)
        opt = self.init_opt(params)
        losses = []
        gnorm = g_leaf = None
        for i in range(self.tr["setup_steps"]):
            params, opt, m = self.run_step(params, opt, batches[i])
            losses.append(float(m["loss"]))
            if i == 0:
                gnorm = float(m["grad_norm"])
                g_leaf = np.asarray(self.grad_leaf(opt["m"]))
        change = np.asarray(self.change_leaf(params, key))
        return params, opt, ref.Readings(losses, gnorm, g_leaf, change)


def reference(cfg, tr, quant=None) -> ref.Qwen2TrainReference:
    """The plain reference of the cell or, with ``quant``, its control."""
    return ref.Qwen2TrainReference(cfg, ref.AdamW.from_traffic(tr),
                                   quant=quant)


def reference_readings(model, tr, prog: Program, key, batches,
                       **fault) -> ref.Readings:
    """``model``'s readings of the first steps from the weights made from
    ``key``.  ``fault`` passes ``row_w`` / ``group_w`` to the reference's
    step, or ``batch_order``: the batches the steps take, by index."""
    order = fault.pop("batch_order", range(tr["setup_steps"]))
    return ref.run_steps(model, prog.make_params(key),
                         [batches[i] for i in order],
                         lambda: prog.make_params(key), **fault)


def compared(got, want, names, report, label="check"):
    c = ref.compare(got, want, names)
    report([f"{label}: losses {got.losses} reference {want.losses}",
            f"{label}: grad_norm {got.grad_norm!r} reference "
            f"{want.grad_norm!r}",
            f"{label}: worst leaves {c['worst']}; left out of the change "
            f"(reference gradient under {ref.STILL_LEAF} of the median): "
            f"{len(c['still_leaves'])} {c['still_leaves'][:8]}"])
    return c


def run(cell, *, seed, seconds, trace, control, devices, compiles, report,
        keep_trace=None):
    cfg, tr = cell.config, cell.traffic
    prog = Program(cfg, tr, devices)
    key_p, key_b = harness.jax_key(seed, 0), harness.jax_key(seed, 1)
    batches = prog.make_batches(key_b)
    params, opt, got = prog.first_steps(key_p, batches)
    report([f"train: {len(prog.names)} floating leaves, set-up losses "
            f"{got.losses}"])

    win = harness.Window(seconds, trace, report)
    nb, per_step = len(batches), tr["batch"] * tr["seq_len"]
    i = tr["setup_steps"]
    losses, pending = [], collections.deque()
    t0 = win.start()
    while True:
        with win.unit("train.step"):
            params, opt, m = prog.run_step(params, opt, batches[i % nb])
        i += 1
        losses.append(m["loss"])
        pending.append(m["loss"])
        if len(pending) >= tr["max_in_flight"]:
            pending.popleft().block_until_ready()
        if win.expired():
            break
    jax.block_until_ready(list(pending))
    win.stop()
    steps = len(losses)
    lowered = compiles.between(win.t0, win.t1)
    mem = harness.memory_peak_bytes(devices)
    reduced = leaf_ops(win.reduce(keep_trace)) if trace else None
    window_losses = [float(x) for x in losses]
    del params, opt, m, losses, pending
    report([f"train: window {win.seconds_measured:.3f} s, {steps} steps, "
            f"{lowered} lowerings, losses {window_losses[0]!r} .. "
            f"{window_losses[-1]!r}"])

    t_check = time.perf_counter()
    want = reference_readings(reference(cfg, tr), tr, prog, key_p, batches)
    c = compared(got, want, prog.names, report)
    ctl = {}
    if control:
        low = reference_readings(reference(cfg, tr, ref.CONTROL_QUANT), tr, prog,
                                 key_p, batches)
        cc = compared(low, want, prog.names, report, label="control")
        ctl = {k: cc[k] for k in COMPARED}
    report([f"check: {time.perf_counter() - t_check:.1f} s"])
    return harness.Outcome(
        window=win,
        end_to_end={"train_tokens_per_s": steps * per_step
                    / win.seconds_measured},
        work={"steps": steps, "tokens": steps * per_step,
              "seq_len": tr["seq_len"], "chips": len(devices)},
        compared=[harness.Compared(k, c[k], cell.limits[k]) for k in COMPARED],
        attempted=steps,
        failed=sum(1 for x in window_losses if not math.isfinite(x)),
        memory_peak_bytes=mem, reduced=reduced, control=ctl)
