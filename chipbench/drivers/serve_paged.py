"""Driver ``serve_paged``: the program's paged continuous-batching
``Scheduler`` over a queue of requests, all queued at the start.

Set-up makes the weights on the device from the seed, builds the
``Engine`` and ``Scheduler``, and runs the scheduler's first
``warmup_iterations`` iterations: the first admits a wave of requests in
one packed prefill, and every decode shape is compiled.  The window then
runs from the scheduler's heartbeat of that iteration until ``--seconds``
have passed; at its close every request is cancelled, so the scheduler
retires them with the tokens served so far.

Traffic (``traffic/<name>.json``): ``slots``, ``requests``,
``prompt_tokens`` as an inclusive range and ``output_tokens``, the budget
of every request.  Each wave of ``slots`` requests has the same set of
prompt lengths, evenly spaced over the range; the seed only orders them
and draws the prompt tokens, so every seed runs the same shapes.  With one
budget for all, a wave retires in one iteration and the next wave admits
in one packed prefill of the same length as the first, which set-up
compiled: a decode fast enough to finish waves inside the window compiles
nothing there.  Sampling is greedy, the KV cache paged with the page size
dispatch chooses.
"""
from __future__ import annotations

import math
import time

import numpy as np

from chipbench import harness, weights
from chipbench.refs import qwen2 as ref


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for the benchmark's configuration."""
    from repro.configs import get_config
    from repro.core.pruning import SparsityConfig

    sp = cfg["sparsity"]
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
        dtype=cfg["dtype"], param_dtype=cfg["dtype"],
        sparsity=SparsityConfig(sparsity=sp["fraction"], m=sp["m"],
                                tile=sp["tile"], format=sp["format"],
                                min_dim=sp["min_dim"]))


def make_params(cfg: dict, pcfg, key):
    import jax

    from repro.models import registry as reg

    abstract, _ = reg.abstract_params(pcfg)
    d = cfg["hidden_size"]
    hq = pcfg.padded_heads * pcfg.resolved_head_dim
    d_in = {"q": d, "k": d, "v": d, "o": hq, "gate": d, "up": d,
            "down": cfg["intermediate_size"]}

    def d_in_of(p):
        return d_in[p.split("/")[-2]]

    def scale_of(p, shape):
        return 1.0 / math.sqrt(shape[-2])  # kept rows (values) or d_in (w)

    made = jax.jit(lambda k: weights.make_params(
        abstract, k, d_in_of=d_in_of, scale_of=scale_of))(key)
    weights.check_same_tree(made, abstract)
    return made


def make_requests(tr: dict, seed: int, vocab: int):
    from repro.serve import Request

    rng = np.random.default_rng(harness.seed_words(seed))
    n, slots = tr["requests"], tr["slots"]
    lo, hi = tr["prompt_tokens"]
    lens = np.rint(np.linspace(lo, hi, slots)).astype(int)
    reqs = []
    for wave in range(-(-n // slots)):
        pl = rng.permutation(lens)
        for j in range(min(slots, n - wave * slots)):
            reqs.append(Request(
                uid=len(reqs),
                prompt=rng.integers(0, vocab, (int(pl[j]),)).astype(np.int32),
                max_new_tokens=int(tr["output_tokens"])))
    return reqs


def weighted_percentile(values, weights_, q: float) -> float:
    """Smallest value with at least ``q`` percent of the weight at or
    below it."""
    order = np.argsort(values)
    v = np.asarray(values, float)[order]
    w = np.asarray(weights_, float)[order]
    c = np.cumsum(w)
    return float(v[np.searchsorted(c, q / 100.0 * c[-1])])


def run(cell, *, seed, seconds, trace, control, devices, compiles, report,
        keep_trace=None):
    import jax

    from repro.serve import Engine, Scheduler, ServeConfig

    cfg, tr = cell.config, cell.traffic
    pcfg = program_config(cfg)
    with jax.default_device(devices[0]):
        params = make_params(cfg, pcfg, harness.jax_key(seed, 0))
    jax.block_until_ready(params)
    reqs = make_requests(tr, seed, cfg["vocab_size"])
    engine = Engine(pcfg, params, ServeConfig(
        max_new_tokens=tr["output_tokens"], temperature=0.0))
    sched = Scheduler(engine, n_slots=tr["slots"], paged=True,
                      alloc=tr["alloc"])

    # what each decode step was given: its rows and their cached lengths
    steps = []
    decode = engine.paged_decode_step

    def observed_decode(cache, tokens, pos, tables, *, page_size):
        steps.append(np.asarray(pos).copy())
        return decode(cache, tokens, pos, tables, page_size=page_size)

    engine.paged_decode_step = observed_decode

    gen = sched.metrics.counter("generated_tokens")
    win = harness.Window(seconds, trace, report)
    st = {"it": 0, "open": False, "closed": False, "beats": [],
          "step0": 0, "step1": 0}

    def heartbeat():
        now = time.perf_counter()
        it = st["it"]
        st["it"] += 1
        if it == tr["warmup_iterations"]:
            report([f"serve: page_size {sched.page_size}, "
                    f"{len(steps)} warm-up decode steps"])
            win.start()
            now = win.t0
            st["open"] = True
            st["step0"] = len(steps)
        if st["open"]:
            st["beats"].append((now, gen.value))
            if win.expired(now):
                win.stop(now)
                st["pages"] = sched.page_stats
                st["open"], st["closed"] = False, True
                st["step1"] = len(steps)
                for r in reqs:
                    sched.cancel(r.uid)
            else:
                win.begin_unit("serve.iter")

    completions = sched.run(reqs, heartbeat=heartbeat)
    if not st["closed"]:
        raise RuntimeError("the queue ran dry before the window closed: "
                           "the traffic is too short for --seconds")
    mem = harness.memory_peak_bytes(devices)
    reduced = win.reduce(keep_trace) if trace else None
    del sched, engine

    beats = st["beats"]
    t = np.array([b[0] for b in beats])
    g = np.array([b[1] for b in beats], float)
    gaps, toks = np.diff(t), np.diff(g)
    tokens = g[-1] - g[0]
    out_tok_s = tokens / win.seconds_measured
    itl_p95_ms = weighted_percentile(gaps[toks > 0], toks[toks > 0], 95) * 1e3
    win_steps = steps[st["step0"]:st["step1"]]
    report([f"serve: window {win.seconds_measured:.3f} s, "
            f"{len(win_steps)} decode steps, {int(tokens)} tokens"])
    if win_steps:
        last = win_steps[-1]
        filled = int((last[last > 0] + 1).sum())
        row_bytes = (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
                     * (cfg.get("head_dim") or cfg["hidden_size"]
                        // cfg["num_attention_heads"]) * 2)
        pg = st["pages"]
        report([f"kv: {filled} rows filled at the window's close "
                f"({filled * row_bytes} bytes), "
                f"{int(pg['pages_active'] * pg['page_size'])} rows reserved, "
                f"{int((pg['pages_active'] + pg['pages_free']) * pg['page_size'])}"
                f" rows in the pool"])

    served = [c for c in completions if c.n_generated > 0]
    bad = [c for c in completions if c.status not in ("ok", "cancelled")]
    prompts = {r.uid: r.prompt for r in reqs}
    t_check = time.perf_counter()
    gap, ctl_gap = check(cfg, params, served, prompts, seed,
                         tr["check_requests"], control, report)
    report([f"check: {time.perf_counter() - t_check:.1f} s"])
    return harness.Outcome(
        window=win,
        end_to_end={"output_tokens_per_s": out_tok_s,
                    "itl_p95_ms": itl_p95_ms},
        work={"decode_steps": win_steps, "tokens": tokens},
        compared=[harness.Compared("logit_gap", gap,
                                   cell.limits["logit_gap"])],
        attempted=len(served), failed=len(bad), memory_peak_bytes=mem,
        reduced=reduced,
        control={"logit_gap": ctl_gap} if control else {})


def check(cfg, params, served, prompts, seed, n_check, control, report):
    """Widest logit gap of the served tokens of a sample of requests drawn
    from the seed, the one with the longest sequence among them, against
    the float32 reference (and, with ``control``, the gap of the tokens an
    fp8 reference would choose)."""
    rng = np.random.default_rng(harness.seed_words(seed + 1))
    longest = max(range(len(served)),
                  key=lambda i: served[i].prompt_len + served[i].n_generated)
    rest = [i for i in range(len(served)) if i != longest]
    pick = [longest] + list(rng.choice(rest, size=min(n_check - 1, len(rest)),
                                       replace=False))
    reference = ref.Qwen2Reference(cfg, params)
    lower = ref.Qwen2Reference(cfg, params, quant=ref.fp8_quant) \
        if control else None
    gap = ctl_gap = 0.0
    for i in pick:
        c = served[i]
        prompt = prompts[c.uid]
        seq = np.concatenate([prompt, c.tokens]).astype(np.int32)
        # token k was chosen by the logits at position prompt_len - 1 + k
        pos = np.arange(c.n_generated) + len(prompt) - 1
        hid = reference.hidden(seq)
        kw = {}
        if control:
            kw = {"other": lower, "other_hidden": lower.hidden(seq)}
        r = ref.logit_gaps(reference, hid, pos, c.tokens, **kw)
        gap = max(gap, r["gap"])
        ctl_gap = max(ctl_gap, r.get("other_gap", 0.0))
        report([f"check: request {c.uid} prompt {len(prompt)} served "
                f"{c.n_generated} gap {r['gap']!r}"
                + (f" control gap {r['other_gap']!r}" if control else "")])
    return gap, ctl_gap
