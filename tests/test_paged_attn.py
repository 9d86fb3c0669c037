"""Ragged paged flash-attention kernel (kernels/flash_attn/paged.py) vs the
XLA gather reference, the dispatch geometry tier (PAGED_ATTN_GEOMETRY page
sizes, pinned execution keys), and frozen-DB cross-process determinism."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import dispatch
from repro.dispatch import (
    DEFAULT_PAGE_SIZE,
    PAGED_ATTN_GEOMETRY,
    REGISTRY,
    ProfileDB,
    choose_page_size,
    paged_attn_key,
)
from repro.kernels.flash_attn import (
    paged_attention,
    paged_attention_pallas,
    paged_attention_ref,
)

REPO = Path(__file__).resolve().parent.parent

def _problem(b=3, sq=1, h=4, kv=2, d=16, n_pages=4, page_size=8,
             lengths=None, seed=0, dtype=jnp.float32, shuffle=False):
    """Random q/new-KV/pages + per-sequence tables.  ``lengths[i]`` rows of
    sequence i's cache are valid; table entries past its mapping point at
    the trash page (last physical page), which holds garbage — exactly the
    serving layout PagePool.table_array produces."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    p_total = b * n_pages + 1  # + trash page
    q = jax.random.normal(keys[0], (b, sq, h, d), dtype)
    k_new = jax.random.normal(keys[1], (b, sq, kv, d), dtype)
    v_new = jax.random.normal(keys[2], (b, sq, kv, d), dtype)
    k_pages = jax.random.normal(keys[3], (p_total, page_size, kv, d), dtype)
    v_pages = jax.random.normal(keys[4], (p_total, page_size, kv, d), dtype)
    pages = np.arange(b * n_pages)
    if shuffle:
        np.random.default_rng(seed).shuffle(pages)
    tables = pages.reshape(b, n_pages).astype(np.int32)
    if lengths is None:
        lengths = [n_pages * page_size] * b
    lengths = np.asarray(lengths, np.int32)
    trash = p_total - 1
    for i in range(b):
        used = -(-int(lengths[i]) // page_size) if lengths[i] else 0
        tables[i, used:] = trash
    return q, k_new, v_new, k_pages, v_pages, jnp.asarray(tables), \
        jnp.asarray(lengths)


def _assert_close(got, want, dtype=jnp.float32):
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


class TestPagedKernelVsRef:
    def test_decode_step_full_pages(self):
        prob = _problem(sq=1)
        ref = paged_attention_ref(*prob)
        got = paged_attention_pallas(*prob, page_size=8, interpret=True)
        _assert_close(got, ref)

    def test_ragged_lengths_including_zero(self):
        """Lengths that end mid-page, on a page boundary, and at zero (a
        fresh sequence whose cache phase must contribute nothing)."""
        prob = _problem(b=3, sq=1, lengths=[13, 16, 0])
        ref = paged_attention_ref(*prob)
        got = paged_attention_pallas(*prob, page_size=8, interpret=True)
        _assert_close(got, ref)

    def test_multirow_q_block_strides_page_boundary(self):
        """sq > block_q exercises the i (q-block) grid dim; lengths chosen
        so pages are full, partial, and empty across the batch."""
        prob = _problem(b=2, sq=12, n_pages=3, page_size=8,
                        lengths=[24, 9])
        ref = paged_attention_ref(*prob)
        got = paged_attention_pallas(*prob, page_size=8, block_q=8,
                                     interpret=True)
        _assert_close(got, ref)

    def test_shuffled_page_tables(self):
        """Physical page order is arbitrary — only the table defines the
        logical sequence."""
        prob = _problem(b=3, sq=4, lengths=[17, 32, 5], shuffle=True)
        ref = paged_attention_ref(*prob)
        got = paged_attention_pallas(*prob, page_size=8, interpret=True)
        _assert_close(got, ref)

    def test_bf16(self):
        prob = _problem(b=2, sq=4, lengths=[11, 26], dtype=jnp.bfloat16)
        ref = paged_attention_ref(*prob)
        got = paged_attention_pallas(*prob, page_size=8, interpret=True)
        _assert_close(got, ref, dtype=jnp.bfloat16)

    def test_page_size_mismatch_raises(self):
        prob = _problem()
        with pytest.raises(ValueError, match="page_size"):
            paged_attention_pallas(*prob, page_size=16, interpret=True)

    def test_gqa_group_mismatch_raises(self):
        q, k_new, v_new, kp, vp, tables, lengths = _problem(h=3, kv=2)
        with pytest.raises(ValueError, match="H % KV"):
            paged_attention_pallas(q, k_new, v_new, kp, vp, tables, lengths,
                                   page_size=8, interpret=True)


class TestPagedDispatch:
    def test_geometry_candidates_registered(self):
        names = {s.name for s in REGISTRY.candidates("paged_attn")}
        assert "paged_attn_ref" in names
        assert "paged_attn_pallas" in names  # default ps16_bq8 geometry
        # one candidate per registered geometry
        assert len(names) == 1 + len(PAGED_ATTN_GEOMETRY)

    def test_pinned_key_restricts_to_matching_page_size(self):
        key = paged_attn_key(q_rows=8, n_heads=4, kv_heads=2, head_dim=16,
                             kv_capacity=64, page_size=8)
        feas = {s.name for s in REGISTRY.candidates("paged_attn")
                if s.feasible(key)[0]}
        assert "paged_attn_ref" in feas  # universal fallback
        for name in feas - {"paged_attn_ref"}:
            assert "ps8" in name, f"{name} feasible under a ps=8 pin"

    def test_planning_key_admits_every_geometry(self):
        key = paged_attn_key(q_rows=8, n_heads=4, kv_heads=2, head_dim=16,
                             kv_capacity=64)  # no page-size pin
        feas = {s.name for s in REGISTRY.candidates("paged_attn")
                if s.feasible(key)[0]}
        assert len(feas) == 1 + len(PAGED_ATTN_GEOMETRY)

    def test_choose_page_size_returns_registered_geometry(self):
        ps = choose_page_size(4, 2, 16, 64, q_rows=8)
        registered = {dict(g)["ps"] for g in PAGED_ATTN_GEOMETRY}
        assert ps in registered or ps == DEFAULT_PAGE_SIZE

    def test_wrapper_matches_forced_ref(self):
        prob = _problem(b=2, sq=1, lengths=[13, 7])
        ref = paged_attention(*prob, page_size=8, impl="paged_attn_ref")
        got = paged_attention(*prob, page_size=8)
        _assert_close(got, ref)

    def test_cross_process_frozen_db_determinism(self, tmp_path):
        """A frozen profile DB pins the same paged-attention geometry in
        fresh processes (same property test_dispatch proves for linear)."""
        db = ProfileDB(path=str(tmp_path / "profile.json"))
        dispatch.set_db(db)
        try:
            key = paged_attn_key(q_rows=8, n_heads=4, kv_heads=2,
                                 head_dim=16, kv_capacity=64, page_size=16,
                                 phase="decode")
            db.put(key.token, {"impl": "paged_attn_pallas", "wall_us": 1.0})
        finally:
            dispatch.set_db(None)
        snippet = (
            "from repro import dispatch\n"
            "key = dispatch.paged_attn_key(q_rows=8, n_heads=4, kv_heads=2,"
            " head_dim=16, kv_capacity=64, page_size=16, phase='decode')\n"
            "print(dispatch.best_impl(key).name)\n"
        )
        env = dict(os.environ,
                   PYTHONPATH=os.path.join(REPO, "src"),
                   REPRO_DISPATCH_DB=str(db.path))
        outs = []
        for _ in range(2):
            r = subprocess.run([sys.executable, "-c", snippet], env=env,
                               capture_output=True, text=True, timeout=300)
            assert r.returncode == 0, r.stderr
            outs.append(r.stdout.strip())
        assert outs == ["paged_attn_pallas", "paged_attn_pallas"]
