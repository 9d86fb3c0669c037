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

N_LAYERS = 2


def _problem(b=3, sq=1, h=4, kv=2, d=16, n_pages=4, page_size=8,
             lengths=None, seed=0, dtype=jnp.float32, shuffle=False,
             dead=None):
    """Random q/new-KV, a two-layer ``[L, P, ps, KV*D]`` page cache, and
    per-sequence tables.  ``lengths[i]`` rows of sequence i's cache are
    valid; table entries past its mapping point at the trash page (last
    physical page), which holds garbage — exactly the serving layout
    PagePool.table_array produces.  ``dead`` (a value) fills every page
    wholly past each sequence's length, the trash page, and the rows past
    the length in each ragged final page."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    p_total = b * n_pages + 1  # + trash page
    q = jax.random.normal(keys[0], (b, sq, h, d), dtype)
    k_new = jax.random.normal(keys[1], (b, sq, kv, d), dtype)
    v_new = jax.random.normal(keys[2], (b, sq, kv, d), dtype)
    cache = (N_LAYERS, p_total, page_size, kv * d)
    k_pages = np.array(jax.random.normal(keys[3], cache, jnp.float32))
    v_pages = np.array(jax.random.normal(keys[4], cache, jnp.float32))
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
        if dead is not None:
            for pages_ in (k_pages, v_pages):
                pages_[:, tables[i, used:]] = dead
                if lengths[i] % page_size:
                    pages_[:, tables[i, used - 1],
                           lengths[i] % page_size:] = dead
        tables[i, used:] = trash
    if dead is not None:
        k_pages[:, trash] = dead
        v_pages[:, trash] = dead
    return q, k_new, v_new, jnp.asarray(k_pages, dtype), \
        jnp.asarray(v_pages, dtype), jnp.asarray(tables), \
        jnp.asarray(lengths)


def _assert_close(got, want, dtype=jnp.float32):
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def _kernel(prob, layer=1, **kw):
    kw.setdefault("page_size", 8)
    return paged_attention_pallas(*prob, layer, interpret=True, **kw)


# lengths against page size 8 and blocks of 2 pages (16 rows), 4-page
# tables: empty, ps - 1, ps, a block boundary -/+ 1, the full table width
EDGE_LENGTHS = {
    "empty": 0, "ps-1": 7, "ps": 8, "block-1": 15, "block": 16,
    "block+1": 17, "full": 32,
}


class TestPagedKernelVsRef:
    def test_decode_step_full_pages(self):
        prob = _problem(sq=1)
        ref = paged_attention_ref(*prob, 1)
        got = _kernel(prob)
        _assert_close(got, ref)

    def test_ragged_lengths_including_zero(self):
        """Lengths that end mid-page, on a page boundary, and at zero (a
        fresh sequence whose cache phase must contribute nothing)."""
        prob = _problem(b=3, sq=1, lengths=[13, 16, 0])
        ref = paged_attention_ref(*prob, 1)
        got = _kernel(prob)
        _assert_close(got, ref)

    @pytest.mark.parametrize("ppb", [1, 2, 4])
    @pytest.mark.parametrize("edge", sorted(EDGE_LENGTHS))
    def test_edge_lengths(self, edge, ppb):
        """Each edge length in the middle of a batch of other lengths, so
        the cross-step prefetch also runs into and out of it."""
        n = EDGE_LENGTHS[edge]
        prob = _problem(b=3, sq=1, lengths=[21, n, 9], shuffle=True)
        _assert_close(_kernel(prob, pages_per_block=ppb),
                      paged_attention_ref(*prob, 1))

    def test_multirow_q_block_strides_page_boundary(self):
        """sq > block_q exercises the i (q-block) grid dim; lengths chosen
        so pages are full, partial, and empty across the batch."""
        prob = _problem(b=2, sq=12, n_pages=3, page_size=8,
                        lengths=[24, 9])
        ref = paged_attention_ref(*prob, 1)
        got = _kernel(prob, block_q=8)
        _assert_close(got, ref)

    def test_multirow_q_across_a_block_boundary(self):
        """Sq > 1 whose cached lengths end just before, on and just after a
        compute block's edge (2 pages of 8)."""
        prob = _problem(b=3, sq=5, lengths=[15, 16, 17], shuffle=True)
        _assert_close(_kernel(prob, pages_per_block=2, block_q=4),
                      paged_attention_ref(*prob, 1))

    @pytest.mark.parametrize("h,kv,d", [(14, 2, 64), (16, 4, 128)],
                             ids=["g7_d64", "g4_d128"])
    def test_gqa_groups(self, h, kv, d):
        """Qwen2-0.5B's group of 7 at head dim 64, and a group of 4 at head
        dim 128."""
        prob = _problem(b=2, sq=1, h=h, kv=kv, d=d, lengths=[19, 30])
        _assert_close(_kernel(prob, pages_per_block=2),
                      paged_attention_ref(*prob, 1))

    def test_pages_past_the_length_never_enter_the_math(self):
        """NaN in every page wholly past each length, in the trash page and
        in the rows past the length of each ragged final page: the output
        stays finite and equals the reference over clean pages."""
        kw = dict(b=4, sq=1, lengths=[13, 0, 16, 27], shuffle=True)
        got = _kernel(_problem(dead=np.nan, **kw), pages_per_block=2)
        assert np.isfinite(np.asarray(got)).all()
        _assert_close(got, paged_attention_ref(*_problem(**kw), 1))

    def test_shuffled_page_tables(self):
        """Physical page order is arbitrary — only the table defines the
        logical sequence."""
        prob = _problem(b=3, sq=4, lengths=[17, 32, 5], shuffle=True)
        ref = paged_attention_ref(*prob, 1)
        got = _kernel(prob)
        _assert_close(got, ref)

    def test_bf16(self):
        prob = _problem(b=2, sq=4, lengths=[11, 26], dtype=jnp.bfloat16)
        ref = paged_attention_ref(*prob, 1)
        got = _kernel(prob)
        _assert_close(got, ref, dtype=jnp.bfloat16)

    def test_page_size_mismatch_raises(self):
        prob = _problem()
        with pytest.raises(ValueError, match="page_size"):
            _kernel(prob, page_size=16)

    def test_gqa_group_mismatch_raises(self):
        q, k_new, v_new, kp, vp, tables, lengths = _problem(h=3, kv=2)
        with pytest.raises(ValueError, match="H % KV"):
            paged_attention_pallas(q, k_new, v_new, kp, vp, tables, lengths,
                                   page_size=8, interpret=True)


class TestPagedDispatch:
    def test_geometry_candidates_registered(self):
        names = {s.name for s in REGISTRY.candidates("paged_attn")}
        assert "paged_attn_ref" in names
        # one candidate per registered geometry, each named for it
        assert len(names) == 1 + len(PAGED_ATTN_GEOMETRY)
        for g in PAGED_ATTN_GEOMETRY:
            ps, ppb = dict(g)["ps"], dict(g)["ppb"]
            assert f"paged_attn_pallas@ps{ps}_ppb{ppb}" in names

    def test_pinned_key_restricts_to_matching_page_size(self):
        key = paged_attn_key(q_rows=8, n_heads=4, kv_heads=2, head_dim=16,
                             kv_capacity=64, page_size=8)
        feas = {s.name for s in REGISTRY.candidates("paged_attn")
                if s.feasible(key)[0]}
        assert "paged_attn_ref" in feas  # universal fallback
        for name in feas - {"paged_attn_ref"}:
            assert "@ps8_" in name, f"{name} feasible under a ps=8 pin"

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
        fresh processes (same property test_dispatch proves for linear):
        here the ladder's last rung, which the heuristic would not pick."""
        geom = dict(PAGED_ATTN_GEOMETRY[-1])
        name = f"paged_attn_pallas@ps{geom['ps']}_ppb{geom['ppb']}"
        db = ProfileDB(path=str(tmp_path / "profile.json"))
        dispatch.set_db(db)
        try:
            key = paged_attn_key(q_rows=8, n_heads=4, kv_heads=2,
                                 head_dim=16, kv_capacity=64,
                                 page_size=geom["ps"], phase="decode")
            db.put(key.token, {"impl": name, "wall_us": 1.0})
        finally:
            dispatch.set_db(None)
        snippet = (
            "from repro import dispatch\n"
            "key = dispatch.paged_attn_key(q_rows=8, n_heads=4, kv_heads=2,"
            f" head_dim=16, kv_capacity=64, page_size={geom['ps']},"
            " phase='decode')\n"
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
        assert outs == [name, name]
