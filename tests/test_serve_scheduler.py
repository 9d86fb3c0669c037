"""Continuous-batching scheduler: equivalence vs the static engine, slot-pool
invariants, chunked prefill, per-phase dispatch plans, and the EOS fixes."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import dispatch
from repro.configs import smoke_config
from repro.core.pruning import SparsityConfig
from repro.core.sparse_linear import linear_init, unbox_tree
from repro.dispatch import ProfileDB
from repro.models import registry as reg
from repro.serve import (
    STATUSES,
    Engine,
    Request,
    Scheduler,
    ServeConfig,
    SlotError,
    SlotPool,
    synthetic_trace,
)

REPO = Path(__file__).resolve().parent.parent


def _smoke_cfg(arch="smollm-360m", sparsity=0.5):
    scfg = SparsityConfig(sparsity=sparsity, m=None, tile=None,
                          format="compressed_xla", min_dim=64)
    return smoke_config(arch).with_(sparsity=scfg)


@pytest.fixture(scope="module")
def engine():
    cfg = _smoke_cfg()
    params, _ = reg.init_params(cfg, jax.random.PRNGKey(0))
    return Engine(cfg, params, ServeConfig(max_new_tokens=8))


# ---------------------------------------------------------------------------
# Scheduler vs static engine (greedy equivalence, per request)
# ---------------------------------------------------------------------------


class TestSchedulerEquivalence:
    def test_mixed_length_batch_matches_static_engine(self, engine):
        trace = synthetic_trace(6, seed=3, vocab=engine.cfg.vocab_size,
                                prompt_lens=(3, 14), new_tokens=(2, 8))
        sched = Scheduler(engine, n_slots=3, prefill_chunk=4)
        completions = {c.uid: c for c in sched.run(trace)}
        assert sorted(completions) == [r.uid for r in trace]
        for req in trace:
            engine.scfg.max_new_tokens = req.max_new_tokens
            ref = engine.generate(req.prompt[None, :])
            got = completions[req.uid]
            np.testing.assert_array_equal(
                got.tokens, ref["tokens"][0],
                err_msg=f"uid={req.uid} prompt_len={len(req.prompt)}")

    def test_streaming_yields_before_trace_ends(self, engine):
        """run_iter retires short requests while long ones still decode."""
        engine.scfg.max_new_tokens = 8
        reqs = [Request(uid=0, prompt=np.arange(4, dtype=np.int32) + 1,
                        max_new_tokens=8),
                Request(uid=1, prompt=np.arange(3, dtype=np.int32) + 1,
                        max_new_tokens=2)]
        sched = Scheduler(engine, n_slots=2, prefill_chunk=4)
        first = next(iter(sched.run_iter(reqs)))
        assert first.uid == 1  # the small budget retires first

    def test_padded_final_chunk_grows_cache_not_corrupts(self, engine):
        """prompt=9 with chunk=8 pads the final chunk to rows [8, 16); the
        auto-sized cache must hold the padded write (a clamped
        dynamic_update_slice would silently shift back over real rows)."""
        rng = np.random.default_rng(11)
        req = Request(uid=0, max_new_tokens=3,
                      prompt=rng.integers(0, engine.cfg.vocab_size,
                                          (9,)).astype(np.int32))
        sched = Scheduler(engine, n_slots=1, prefill_chunk=8)
        comp = sched.run([req])[0]
        engine.scfg.max_new_tokens = req.max_new_tokens
        ref = engine.generate(req.prompt[None, :])
        np.testing.assert_array_equal(comp.tokens, ref["tokens"][0])

    def test_explicit_max_len_too_small_for_chunk_padding_raises(self, engine):
        req = Request(uid=0, prompt=np.arange(9, dtype=np.int32) + 1,
                      max_new_tokens=2)
        sched = Scheduler(engine, n_slots=1, max_len=11, prefill_chunk=8)
        with pytest.raises(ValueError, match="pads the longest prompt"):
            sched.run([req])

    def test_rejects_recurrent_families(self):
        cfg = _smoke_cfg("xlstm-350m", sparsity=0.0)
        params, _ = reg.init_params(cfg, jax.random.PRNGKey(0))
        eng = Engine(cfg, params)
        with pytest.raises(ValueError, match="attention family"):
            Scheduler(eng)


# ---------------------------------------------------------------------------
# Chunked prefill primitive
# ---------------------------------------------------------------------------


class TestChunkedPrefill:
    def test_matches_full_prefill(self, engine):
        cfg = engine.cfg
        b, s, max_len, c_w = 2, 11, 24, 4
        toks = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (b, s)).astype(np.int32)
        logits_full, cache_full = engine._prefill(
            engine.params, {"tokens": jnp.asarray(toks)})
        cache = reg.cache_init_fn(cfg, b, max_len)()
        for start in range(0, s, c_w):
            chunk = toks[:, start:start + c_w]
            if chunk.shape[1] < c_w:
                chunk = np.pad(chunk, ((0, 0), (0, c_w - chunk.shape[1])))
            logits, cache = engine.prefill_chunk_step(cache, chunk, start)
        last = logits[:, (s - 1) % c_w]
        np.testing.assert_allclose(np.asarray(last),
                                   np.asarray(logits_full[:, -1]),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(cache["k"][:, :, :s]),
                                   np.asarray(cache_full["k"]),
                                   rtol=1e-4, atol=1e-5)

    def test_decode_accepts_position_vector(self, engine):
        """Scalar pos and an equal [B] vector produce identical steps."""
        cfg = engine.cfg
        b, s, max_len = 2, 6, 12
        toks = np.random.default_rng(1).integers(
            0, cfg.vocab_size, (b, s)).astype(np.int32)
        _, cache = engine.prefill_step(toks, max_len)
        tok = jnp.asarray([[5], [7]], jnp.int32)
        l1, c1 = reg.decode_fn(cfg)(engine.params, dict(cache), tok,
                                    jnp.asarray(s, jnp.int32))
        l2, c2 = reg.decode_fn(cfg)(engine.params, dict(cache), tok,
                                    jnp.full((b,), s, jnp.int32))
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
        np.testing.assert_array_equal(np.asarray(c1["k"]), np.asarray(c2["k"]))


# ---------------------------------------------------------------------------
# Slot pool invariants
# ---------------------------------------------------------------------------


class TestSlotPool:
    def test_no_leak_no_double_assign_random_order(self):
        rng = np.random.default_rng(0)
        pool = SlotPool(n_slots=5, max_len=64)
        held = []
        for _ in range(500):
            if held and (pool.n_free == 0 or rng.random() < 0.5):
                idx = held.pop(rng.integers(len(held)))
                pool.free(idx)
            else:
                slot = pool.alloc(request_id=int(rng.integers(1000)))
                assert slot.index not in held
                held.append(slot.index)
            pool.check_invariants()
            assert pool.n_free + pool.n_active == pool.n_slots
        for idx in held:
            pool.free(idx)
        assert pool.n_free == pool.n_slots

    def test_double_free_and_exhaustion_raise(self):
        pool = SlotPool(n_slots=1, max_len=8)
        slot = pool.alloc(request_id=0)
        with pytest.raises(SlotError, match="no free slots"):
            pool.alloc(request_id=1)
        pool.free(slot.index)
        with pytest.raises(SlotError, match="inactive"):
            pool.free(slot.index)

    def test_advance_bounds_checked(self):
        pool = SlotPool(n_slots=1, max_len=4)
        slot = pool.alloc(request_id=0)
        pool.advance(slot.index, by=4)
        with pytest.raises(SlotError, match="exceeds"):
            pool.advance(slot.index)

    def test_pool_drains_clean_after_run(self, engine):
        trace = synthetic_trace(5, seed=7, vocab=engine.cfg.vocab_size,
                                prompt_lens=(2, 8), new_tokens=(1, 4))
        sched = Scheduler(engine, n_slots=2, prefill_chunk=4)
        comps = sched.run(trace)
        assert len(comps) == len(trace)
        assert sched.stats["generated_tokens"] == sum(
            c.n_generated for c in comps)


# ---------------------------------------------------------------------------
# Per-phase dispatch
# ---------------------------------------------------------------------------


PLAN_SNIPPET = r"""
import json, sys
import jax
from repro import dispatch
from repro.core.pruning import SparsityConfig
from repro.core.sparse_linear import linear_init, unbox_tree
from repro.dispatch import ProfileDB

dispatch.set_db(ProfileDB(path=sys.argv[1], autosave=False))
cfg = SparsityConfig(sparsity=0.5, format="compressed_xla", min_dim=8, tile=16)
vals, _ = unbox_tree(linear_init(jax.random.PRNGKey(0), 64, 64, cfg))
plan = dispatch.plan_params({"l": vals},
                            phase_hints={"prefill": 1024, "decode": 8})
print(json.dumps(plan, sort_keys=True))
"""


class TestPerPhaseDispatch:
    @pytest.fixture()
    def db(self, tmp_path):
        db = ProfileDB(path=str(tmp_path / "db.json"), autosave=False)
        prev = dispatch.get_db()
        dispatch.set_db(db)
        yield db
        dispatch.set_db(prev)

    def test_phase_tokens_distinct(self):
        k_pre = dispatch.linear_key(1024, 64, 64, 8, 16, phase="prefill")
        k_dec = dispatch.linear_key(8, 64, 64, 8, 16, phase="decode")
        assert "|ph:prefill" in k_pre.token and "|ph:decode" in k_dec.token
        assert k_pre.token != k_dec.token
        # untagged keys keep the exact pre-phase token format
        assert "|ph:" not in dispatch.linear_key(8, 64, 64, 8, 16).token

    def test_plan_params_phase_hints(self, db):
        cfg = SparsityConfig(sparsity=0.5, format="compressed_xla",
                             min_dim=8, tile=16)
        vals, _ = unbox_tree(linear_init(jax.random.PRNGKey(0), 64, 64, cfg))
        plan = dispatch.plan_params(
            {"l": vals}, phase_hints={"prefill": 1024, "decode": 8})
        phases = sorted(t.split("|ph:")[-1] for t in plan if "|ph:" in t)
        assert phases == ["decode", "prefill"]

    def test_profiled_phases_land_in_db(self, db):
        cfg = SparsityConfig(sparsity=0.5, format="compressed_xla",
                             min_dim=8, tile=16)
        vals, _ = unbox_tree(linear_init(jax.random.PRNGKey(0), 64, 64, cfg))
        dispatch.plan_params({"l": vals}, profile=True,
                             phase_hints={"prefill": 64, "decode": 8})
        tokens = list(db._entries)
        assert any("|ph:prefill" in t for t in tokens)
        assert any("|ph:decode" in t for t in tokens)

    def test_engine_plans_both_phases(self, db, engine):
        plan = dispatch.plan_params(
            engine.params, phase_hints={"prefill": 8 * 128, "decode": 8})
        assert any("|ph:prefill" in t for t in plan)
        assert any("|ph:decode" in t for t in plan)
        assert set(plan) <= set(dispatch.plan_params(
            engine.params, phase_hints={"prefill": 8 * 128, "decode": 8}))

    def test_scheduler_plan_matches_trace_geometry(self, engine):
        """The scheduler re-plans with its real shapes: prefill keys bucket
        by the chunk width, decode keys by the slot count — the engine's
        static-path hints would never match the scheduler's traces."""
        from repro.dispatch import bucket_batch

        sched = Scheduler(engine, n_slots=3, prefill_chunk=4)
        pre = [t for t in sched.dispatch_plan if "|ph:prefill" in t]
        dec = [t for t in sched.dispatch_plan if "|ph:decode" in t]
        assert pre and dec
        assert all(f"|b{bucket_batch(4)}|" in t for t in pre)
        assert all(f"|b{bucket_batch(3)}|" in t for t in dec)
        # merged into the engine's plan so both consumers see one view
        assert set(sched.dispatch_plan) <= set(engine.dispatch_plan)

    def test_phase_scope_tags_linear_impl_keys(self):
        with dispatch.phase_scope("decode"):
            assert dispatch.current_phase() == "decode"
            with dispatch.phase_scope("prefill"):
                assert dispatch.current_phase() == "prefill"
            assert dispatch.current_phase() == "decode"
        assert dispatch.current_phase() == ""

    def test_plan_deterministic_across_processes(self, tmp_path):
        env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
        outs = []
        for i in range(2):
            r = subprocess.run(
                [sys.executable, "-c", PLAN_SNIPPET,
                 str(tmp_path / f"db{i}.json")],
                capture_output=True, text=True, timeout=600, env=env,
                cwd=REPO)
            assert r.returncode == 0, r.stderr[-2000:]
            outs.append(json.loads(r.stdout))
        assert outs[0] == outs[1]
        assert any("|ph:prefill" in t for t in outs[0])


# ---------------------------------------------------------------------------
# Engine satellite fixes (shared default config, EOS masking)
# ---------------------------------------------------------------------------


class TestEngineFixes:
    def test_serve_config_not_shared_across_engines(self, engine):
        cfg = engine.cfg
        e2 = Engine(cfg, engine.params)
        e3 = Engine(cfg, engine.params)
        e2.scfg.max_new_tokens = 99
        assert e3.scfg.max_new_tokens != 99
        assert e2.scfg is not e3.scfg

    def test_eos_masks_tail_and_reports_gen_lens(self, engine):
        prompts = np.random.default_rng(5).integers(
            0, engine.cfg.vocab_size, (2, 6)).astype(np.int32)
        engine.scfg.max_new_tokens = 6
        engine.scfg.eos_id = None
        free = engine.generate(prompts)
        assert np.all(free["gen_lens"] == free["tokens"].shape[1])
        # re-run with eos_id set to a token the free run actually emits
        eos = int(free["tokens"][0, 2])
        engine.scfg.eos_id = eos
        res = engine.generate(prompts)
        engine.scfg.eos_id = None
        toks, lens = res["tokens"], res["gen_lens"]
        for b in range(toks.shape[0]):
            n = int(lens[b])
            hit = np.nonzero(toks[b] == eos)[0]
            if hit.size and hit[0] < toks.shape[1] - 1:
                # everything after the first EOS is masked to EOS
                assert np.all(toks[b, hit[0]:] == eos)
                assert n == hit[0] + 1
            else:
                assert n == toks.shape[1]
        # greedy prefix up to EOS matches the unconstrained run
        n0 = int(lens[0])
        np.testing.assert_array_equal(toks[0, :n0], free["tokens"][0, :n0])


# ---------------------------------------------------------------------------
# Stats lifecycle (obs-backed derived view)
# ---------------------------------------------------------------------------


STAT_KEYS = {
    "decode_steps", "decode_s", "total_s", "generated_tokens", "requests",
    "completed_requests", "decode_tok_s", "ttft_p50_s", "ttft_p99_s",
    "tpot_p50_s", "tpot_p99_s", "latency_p50_s", "latency_p99_s",
    "preemptions", "iter_faults",
} | {f"retired_{s}" for s in STATUSES}


class TestStatsLifecycle:
    def test_full_key_set_before_first_run(self, engine):
        """A fresh Scheduler reports the complete all-zeros key set — not the
        pre-obs empty dict that KeyError'd consumers before run()."""
        sched = Scheduler(engine, n_slots=2, prefill_chunk=4)
        stats = sched.stats
        assert set(stats) == STAT_KEYS
        assert all(v == 0 for v in stats.values())

    def test_consistent_during_partial_run_iter(self, engine):
        """stats read mid-generator reflects the work done so far with the
        same key set, and keeps counting to the final totals."""
        engine.scfg.max_new_tokens = 8
        trace = synthetic_trace(5, seed=7, vocab=engine.cfg.vocab_size,
                                prompt_lens=(3, 10), new_tokens=(2, 8))
        sched = Scheduler(engine, n_slots=2, prefill_chunk=4)
        gen = sched.run_iter(trace)
        first = next(gen)
        mid = sched.stats
        assert set(mid) == STAT_KEYS
        assert mid["requests"] == 5
        assert mid["completed_requests"] >= 1
        assert mid["generated_tokens"] >= first.n_generated
        assert mid["decode_s"] > 0 and mid["decode_tok_s"] > 0
        rest = list(gen)
        end = sched.stats
        assert end["completed_requests"] == 5
        assert end["generated_tokens"] == first.n_generated + sum(
            c.n_generated for c in rest)
        assert end["generated_tokens"] >= mid["generated_tokens"]
        assert end["latency_p50_s"] > 0 and end["tpot_p50_s"] >= 0

    def test_rerun_resets_counters(self, engine):
        engine.scfg.max_new_tokens = 4
        trace = synthetic_trace(3, seed=2, vocab=engine.cfg.vocab_size,
                                prompt_lens=(3, 8), new_tokens=(2, 4))
        sched = Scheduler(engine, n_slots=2, prefill_chunk=4)
        sched.run(trace)
        a = sched.stats
        sched.run(trace)
        b = sched.stats
        assert a["completed_requests"] == b["completed_requests"] == 3
        assert b["generated_tokens"] == a["generated_tokens"]  # not 2x


# ---------------------------------------------------------------------------
# Host spans in a profiler trace
# ---------------------------------------------------------------------------


DECODE_CHILDREN = {True: ["serve.tables", "serve.dispatch", "serve.wait"],
                   False: ["serve.dispatch", "serve.wait"]}


def _profiled_spans(log_dir, run):
    """Run ``run()`` under a profiler session collecting host events (as
    the chip benchmark's traced window does) and return the ``serve.*``
    spans of the trace's host plane as (name, start_ns, end_ns)."""
    import glob

    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events
                        if ev.name.startswith("serve.")]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _inside(outer, spans):
    """The spans of ``spans`` that lie within ``outer``, in start order."""
    return [s for s in spans if s is not outer
            and outer[1] <= s[1] and s[2] <= outer[2]]


class TestProfilerSpans:
    @pytest.mark.parametrize("paged", [True, False],
                             ids=["paged", "contiguous"])
    def test_decode_iteration_spans_nest(self, engine, paged, tmp_path):
        """Each decode iteration writes serve.iter > serve.decode >
        {tables (paged), dispatch, wait} and serve.advance after it into
        the profiler's host plane with recording off, properly nested."""
        from repro import obs

        engine.scfg.max_new_tokens = 4
        trace = synthetic_trace(3, seed=5, vocab=engine.cfg.vocab_size,
                                prompt_lens=(3, 6), new_tokens=(2, 4))
        sched = Scheduler(engine, n_slots=2, prefill_chunk=4, paged=paged,
                          page_size=4 if paged else None)
        obs.set_enabled(False)
        try:
            spans = _profiled_spans(tmp_path, lambda: sched.run(trace))
            assert obs.events() == []
        finally:
            obs.set_enabled(None)
        iters = [s for s in spans if s[0] == "serve.iter"]
        decodes = [s for s in spans if s[0] == "serve.decode"]
        assert len(decodes) == sched.stats["decode_steps"] > 0
        for it in iters:
            kids = _inside(it, spans)
            assert kids[0][0] == "serve.sweep"
            for a, b in zip(kids, kids[1:]):  # nested or disjoint
                assert b[1] >= a[2] or b[2] <= a[2]
        for dec in decodes:
            (it,) = [s for s in iters if s[1] <= dec[1] and dec[2] <= s[2]]
            children = _inside(dec, spans)
            assert [c[0] for c in children] == DECODE_CHILDREN[paged]
            for a, b in zip(children, children[1:]):
                assert a[2] <= b[1]
            after = [s for s in _inside(it, spans) if s[1] >= dec[2]]
            assert after and after[0][0] == "serve.advance"
