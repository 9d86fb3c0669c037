"""The sharded finetune cell at a size the CPU holds, on four virtual
devices: the real driver, reference and comparison, with the program as
it is, with the fp8 control in the program's place, and with each fault
planted in the timed path.  Run as a module in a process of its own (the device count is set
before JAX starts); prints one JSON object, each case's result by name.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python -m chipbench.tests.train_cases [case ...]
"""
from __future__ import annotations

import contextlib
import json
import sys
import types
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

NAME = "qwen2-7b-l8-c50.ft4"
#: the tiny cell's own limits, where the committed ones come from the
#: chip.  Its program reads about 6e-5, 1.2e-3, 1.8e-3 and 0.016 (its small
#: leaves make the change noisy), its fp8 control 1.2e-3, 4.7e-3, 0.031 and
#: 0.019: the control fails the loss and the first gradient's leaves
TINY_LIMITS = {"loss_rel_err": 4e-4, "grad_norm_rel_err": 3e-3,
               "grad_leaf_gap": 0.01, "change_leaf_gap": 0.1}


def tiny_train():
    from chipbench.tests import tiny

    cfg = tiny.load("configs/qwen2-7b-l8-c50.json")
    cfg.update(hidden_size=128, intermediate_size=256, num_attention_heads=4,
               num_key_value_heads=2, num_hidden_layers=2, vocab_size=512)
    cfg["sparsity"] = dict(cfg["sparsity"], tile=16, min_dim=64)
    tr = tiny.load("traffic/ft4.json")
    tr.update(batch=4, seq_len=32, distinct_batches=4)
    return cfg, tr


#: the plain reference's readings of the one seed every case runs: the
#: faults change the program, not what it is held to
_REFERENCE = {}


def run_case(setup=None, *, control=False, seed=2**31 + 11):
    import jax

    from chipbench import run
    from chipbench.tests import tiny

    cfg, tr = tiny_train()
    cell = tiny.cell(NAME, cfg, tr, TINY_LIMITS, per_layer=False)
    drv = cell.driver
    orig = drv.reference_readings

    def reference_readings(model, *a, **fault):
        if model.quant or fault:
            return orig(model, *a, **fault)
        if "plain" not in _REFERENCE:
            _REFERENCE["plain"] = orig(model, *a)
        return _REFERENCE["plain"]

    with contextlib.ExitStack() as stack:
        patch(stack, drv, "reference_readings", reference_readings)
        if setup:
            setup(stack, cell)
        args = types.SimpleNamespace(seed=seed, seconds=0.3, trace=0,
                                     control=control)
        res = run.run_cell(cell, args, jax.devices()[:4])
    return {k: res[k] for k in ("correct", "attempted", "failed", "compared",
                                "metrics")} | {"control": res.get("control")}


def patch(stack, obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    stack.callback(setattr, obj, name, old)
    return old


# -- faults planted in the timed path ------------------------------------------


def state_unchanged(stack, cell):
    """The step computes and returns its metrics but hands back the
    parameters and optimizer state it was given."""
    from repro.launch import steps

    def make(cfg, opt_cfg, *a, **kw):
        real = orig(cfg, opt_cfg, *a, **kw)

        def step(params, opt, batch):
            return params, opt, real(params, opt, batch)[2]
        return step

    orig = patch(stack, steps, "make_train_step", make)


def half_batch(stack, cell):
    """The loss is the mean over the first half of the batch's rows."""
    from repro.models import registry

    def loss_fn(cfg):
        real = orig(cfg)
        return lambda p, b: real(p, {"tokens": b["tokens"][:b["tokens"]
                                                           .shape[0] // 2]})

    orig = patch(stack, registry, "loss_fn", loss_fn)


def exchange_left_out(stack, cell):
    """The REDUCE layers keep each chip's partial sum: no all-reduce."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core import sparse_linear
    from repro.sharding.api import get_ctx

    def partial(x, values, idx):
        def local(x, v, i):
            ib = jnp.broadcast_to(i[0], (*x.shape[:-1], i.shape[-1]))
            sel = jnp.take_along_axis(x, ib, axis=-1)
            return jnp.einsum("...n,nf->...f", sel, v[0])

        lead = (None,) * (x.ndim - 1)
        return jax.shard_map(
            local, mesh=get_ctx().mesh,
            in_specs=(P(*lead, "model"), P("model"), P("model")),
            out_specs=P(), check_vma=False)(x, values, idx)

    patch(stack, sparse_linear, "forward_compressed_reduce", partial)


def stale_batch(stack, cell):
    """The second step is fed the first step's batch again."""
    prog = cell.driver.Program
    seen = []

    def run_step(self, params, opt, tokens):
        seen.append(tokens)
        if len(seen) == 2:
            tokens = seen[0]
        return orig(self, params, opt, tokens)

    orig = patch(stack, prog, "run_step", run_step)


def _grads_altered(stack, alter):
    from repro.launch import steps

    def update(params, grads, state, cfg, *a, **kw):
        return orig(params, alter(grads), state, cfg, *a, **kw)

    orig = patch(stack, steps, "adamw_update", update)


def shard_grad_zeroed(stack, cell):
    """The gate projection's gradient is zero on the first model shard's
    tiles."""
    def alter(g):
        v = g["layers"]["mlp"]["gate"]["values"]
        q = v.shape[1] // 4
        g["layers"]["mlp"]["gate"]["values"] = v.at[:, :q].set(0)
        return g

    _grads_altered(stack, alter)


def head_grad_doubled(stack, cell):
    def alter(g):
        g["unembed"] = 2 * g["unembed"]
        return g

    _grads_altered(stack, alter)


def _layer0_grad_zeroed(stack, *path):
    def alter(g):
        node = g["layers"]
        for k in path[:-1]:
            node = node[k]
        node[path[-1]] = node[path[-1]].at[0].set(0)
        return g

    _grads_altered(stack, alter)


def key_bias_grad_zeroed(stack, cell):
    """The first layer's key bias, the smallest leaf, gets no gradient:
    a fault confined to a leaf far under the median."""
    _layer0_grad_zeroed(stack, "attn", "k", "b")


def norm_grad_zeroed(stack, cell):
    """The first layer's second RMSNorm scale gets no gradient."""
    _layer0_grad_zeroed(stack, "ln2", "scale")


def fp8_control(stack, cell):
    """The control in the program's place: the readings the harness
    compares are the fp8 reference's, from the same weights and batches."""
    drv = cell.driver
    prog = drv.Program

    def first_steps(self, key, batches):
        params, opt, _ = orig(self, key, batches)
        low = drv.reference(self.cfg, self.tr, drv.ref.CONTROL_QUANT)
        return params, opt, drv.reference_readings(low, self.tr, self, key,
                                                   batches)

    orig = patch(stack, prog, "first_steps", first_steps)


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch,
                                  exchange_left_out, stale_batch,
                                  shard_grad_zeroed, head_grad_doubled,
                                  key_bias_grad_zeroed, norm_grad_zeroed,
                                  fp8_control)}


# -- the reference's expansion against the program's layers -------------------


def expansion():
    """Largest relative gap between the program's compressed linears (the
    REDUCE format and the tiled one, float32, on one device) and the
    reference's dense expansion of the same weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import harness
    from chipbench.refs import qwen2_train as ref
    from repro.core.sparse_linear import linear_apply

    cfg, tr = tiny_train()
    drv = harness.load_module(BENCH / "drivers" / "train_sharded.py")
    prog = drv.Program(cfg, tr, jax.devices()[:4])
    params = jax.device_get(prog.make_params(jax.random.PRNGKey(3)))
    layer = jax.tree.map(lambda a: jnp.asarray(a[0]), params["layers"])
    model = ref.Qwen2TrainReference(cfg, ref.AdamW.from_traffic(tr))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 8, 256), jnp.float32)
    gaps = {}
    for name, d_in in (("down", 256), ("gate", 128), ("q", 128)):
        p = layer["mlp" if name in ("down", "gate") else "attn"][name]
        xi = x[..., :d_in]
        got = linear_apply(p, xi)
        want = (model.reduce(xi, p, d_in, jnp.ones((4,), jnp.float32))
                if "values_r" in p else model.tiled(xi, p, d_in))
        gaps[name] = float(np.abs(np.asarray(got) - np.asarray(want)).max()
                           / np.abs(np.asarray(want)).max())
    return gaps


def main(argv):
    import os

    import jax

    os.environ.setdefault("REPRO_DISPATCH_PROFILE", "0")
    if len(jax.devices()) < 4:
        raise SystemExit("needs four devices: set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=4")
    cases = argv or ["program", "expansion", *FAULTS]
    out = {}
    for c in cases:
        if c == "program":
            out[c] = run_case(control=True)
        elif c == "expansion":
            out[c] = expansion()
        else:
            out[c] = run_case(FAULTS[c])
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1:])
