"""Driver ``vision_batch``: batches of images run back to back through the
program's jitted ``vision_apply``.

Set-up makes the weights and a pool of distinct input batches on the
device from the seed, and compiles and runs the forward once.  The window
enqueues batches, keeping at most ``queue_depth`` in flight, until
``--seconds`` have passed, then waits for the last.  A reservoir sample of
the window's outputs, drawn from the seed, is compared with the plain
reference after the window.
"""
from __future__ import annotations

import collections
import math

import numpy as np

from chipbench import harness, weights, work
from chipbench.refs import resnet as ref
from chipbench.refs.qwen2 import fp8_quant


def program_config(cfg: dict):
    """The program's ``VisionConfig`` for the benchmark's configuration."""
    from repro.configs.base import VisionConfig
    from repro.core.pruning import SparsityConfig

    sp = cfg["sparsity"]
    return VisionConfig(
        name=cfg["name"], c_in=cfg["c_in"], stem_channels=cfg["stem_channels"],
        stage_channels=tuple(cfg["stage_channels"]),
        stage_blocks=tuple(cfg["stage_blocks"]),
        stage_strides=tuple(cfg["stage_strides"]),
        image_hw=tuple(cfg["image_hw"]), num_classes=cfg["num_classes"],
        strip_v=cfg["strip_v"], dtype=cfg["dtype"],
        sparsity=SparsityConfig(sparsity=sp["fraction"], m=sp["m"],
                                tile=sp["tile"], min_dim=sp["min_dim"],
                                format=sp["format"]))


def make_params(cfg: dict, vcfg, key):
    import jax

    from repro.core.sparse_linear import unbox_tree
    from repro.models.vision import vision_init

    abstract = jax.eval_shape(
        lambda: unbox_tree(vision_init(vcfg, jax.random.PRNGKey(0)))[0])
    convs = {c.name: c for c in work.resnet_convs(cfg)}
    head = work.resnet_head(cfg)

    def layer(p):  # "blocks/3/conv1/idx" -> "blocks[3]/conv1"
        parts = p.split("/")[:-1]
        if parts[0] == "blocks":
            return f"blocks[{parts[1]}]/{parts[2]}"
        return parts[0]

    def d_in_of(p):
        name = layer(p)
        if name == "head":
            return head.d_in
        c = convs[name]
        return c.k * c.k * c.c

    def scale_of(p, shape):
        if layer(p) == "head":
            return 1.0 / math.sqrt(shape[-2])
        fan_in = shape[-2] if p.endswith("values") else math.prod(shape[1:])
        return math.sqrt(2.0 / fan_in)  # He: ReLU halves the variance

    def geom_of(p):
        c = convs[layer(p)]
        return (c.k, c.k, c.c)

    made = jax.jit(lambda k: weights.make_params(
        abstract, k, d_in_of=d_in_of, scale_of=scale_of, geom_of=geom_of))(key)
    weights.check_same_tree(made, abstract)
    return made


def run(cell, *, seed, seconds, trace, control, devices, compiles, report,
        keep_trace=None):
    import jax
    import jax.numpy as jnp

    from repro.models import vision as V

    cfg, tr = cell.config, cell.traffic
    vcfg = program_config(cfg)
    b, pool = tr["batch"], tr["distinct_batches"]
    h, w = cfg["image_hw"]
    with jax.default_device(devices[0]):
        params = make_params(cfg, vcfg, harness.jax_key(seed, 0))
        xs = jax.jit(lambda k: tuple(jax.random.normal(
            k, (pool, cfg["c_in"], b, h, w), jnp.float32).astype(
                jnp.dtype(cfg["dtype"]))))(harness.jax_key(seed, 1))
    fwd = jax.jit(lambda p, x: V.vision_apply(p, vcfg, x))
    jax.block_until_ready(fwd(params, xs[0]))
    jax.block_until_ready(fwd(params, xs[1 % pool]))

    rng = np.random.default_rng(harness.seed_words(seed))
    keep_n = tr["check_batches"]
    kept = {}  # batch index -> output (a reservoir sample of the window)
    inflight = collections.deque()
    win = harness.Window(seconds, trace, report)
    win.start()
    i = 0
    while True:
        with win.unit("vision.batch"):
            y = fwd(params, xs[i % pool])
        if i < keep_n:
            kept[i] = y
        else:
            j = int(rng.integers(0, i + 1))
            if j < keep_n:
                kept.pop(sorted(kept)[j])
                kept[i] = y
        inflight.append(y)
        if len(inflight) > tr["queue_depth"]:
            inflight.popleft().block_until_ready()
        i += 1
        if win.expired():
            break
    jax.block_until_ready(y)
    win.stop()
    n_batches = i
    mem = harness.memory_peak_bytes(devices)
    reduced = win.reduce(keep_trace) if trace else None
    images_per_s = n_batches * b / win.seconds_measured

    # the check: every sampled output against the float32 reference
    convs, head = work.resnet_convs(cfg), work.resnet_head(cfg)
    ref_fwd = jax.jit(lambda p, x: ref.forward(p, convs, head, x))
    ctl_fwd = jax.jit(lambda p, x: ref.forward(p, convs, head, x,
                                               quant=fp8_quant))
    err = ctl_err = 0.0
    for idx, y in sorted(kept.items()):
        want = ref_fwd(params, xs[idx % pool])
        err = max(err, _rel_err(y, want))
        if control:
            ctl_err = max(ctl_err, _rel_err(ctl_fwd(params, xs[idx % pool]),
                                            want))
    return harness.Outcome(
        window=win,
        end_to_end={"images_per_s": images_per_s},
        work={"batches": n_batches, "batch": b},
        compared=[harness.Compared("logits_rel_err", err,
                                   cell.limits["logits_rel_err"])],
        attempted=n_batches * b, failed=0, memory_peak_bytes=mem,
        reduced=reduced,
        control={"logits_rel_err": ctl_err} if control else {})


def _rel_err(got, want) -> float:
    """Per image: largest |got - want| over largest |want|; the largest
    over the batch."""
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    if not np.isfinite(g).all():
        return float("inf")
    num = np.abs(g - w).max(axis=-1)
    den = np.maximum(np.abs(w).max(axis=-1), 1e-30)
    return float((num / den).max())
