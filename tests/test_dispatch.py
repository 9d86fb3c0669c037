"""Tests for the operator dispatch & profiling subsystem (`repro.dispatch`):
registry feasibility filtering, profile-DB round-trip + fingerprint/version
invalidation + atomic writes, deterministic selection from a frozen DB
(including across processes), numerical equivalence of every registered
linear candidate, escape hatches, and the absorbed Tuner's fixes."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import dispatch
from repro.core import (
    SparsityConfig,
    colwise_nm_mask,
    linear_apply,
    linear_init,
    meta_for,
    pack_colwise,
    unbox_tree,
)
from repro.dispatch import (
    REGISTRY,
    OpKey,
    ProfileDB,
    SCHEMA_VERSION,
    Tuner,
    TuningError,
    linear_key,
    profile_op,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def db(tmp_path):
    d = ProfileDB(path=str(tmp_path / "profile.json"))
    dispatch.set_db(d)
    yield d
    dispatch.set_db(None)


def _small_key():
    return linear_key(batch=8, d_in=64, d_out=64, k_kept=32, tile=16)


# ---------------------------------------------------------------------------
# Registry & feasibility
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_linear_candidates_registered(self):
        names = {s.name for s in REGISTRY.candidates("linear")}
        assert {"dense", "masked", "compressed_xla", "compressed_pallas"} <= names

    def test_conv_candidates_registered(self):
        names = {s.name for s in REGISTRY.candidates("conv")}
        assert {"dense_conv", "im2col_dense_gemm", "im2col_sparse_xla",
                "im2col_sparse_pallas", "fused_sparse_pallas"} <= names

    def test_geometry_variants_registered(self):
        # block geometry lives in the candidate space: one candidate per
        # geometry grid point, default geometry keeping the bare family name
        linear = {s.name for s in REGISTRY.candidates("linear")}
        assert "compressed_pallas" in linear
        assert any(n.startswith("compressed_pallas@") for n in linear)
        conv = {s.name for s in REGISTRY.candidates("conv")}
        assert any(n.startswith("fused_sparse_pallas@") for n in conv)
        for s in REGISTRY.candidates("linear"):
            if s.name.startswith("compressed_pallas"):
                assert s.geom("bb") > 0 and s.geom("bk") > 0

    def test_param_keys_filter(self):
        # a compressed layer can only execute compressed candidates; the
        # pallas family contributes one candidate per geometry point
        names = {s.name for s in
                 REGISTRY.candidates("linear", param_keys=("values", "idx"))}
        assert {n.split("@")[0] for n in names} == {
            "compressed_xla", "compressed_pallas"}
        assert "compressed_pallas" in names

    def test_masked_layer_never_resolves_dense(self):
        # dense (requires {w}) is a strict-subset match for {w, mask} but
        # would silently drop the mask; the most-specific rule must hide it
        names = {s.name for s in
                 REGISTRY.candidates("linear", param_keys=("w", "mask"))}
        assert names == {"masked"}
        names = {s.name for s in REGISTRY.candidates("linear", param_keys=("w",))}
        assert names == {"dense"}

    def test_vmem_infeasibility_filters_pallas(self):
        huge = linear_key(batch=512, d_in=1 << 22, d_out=2048, k_kept=1 << 21,
                          tile=512)
        feas = {s.name for s in
                REGISTRY.feasible(huge, param_keys=("values", "idx"))}
        assert "compressed_pallas" not in feas
        assert "compressed_xla" in feas
        spec = REGISTRY.get("linear", "compressed_pallas")
        ok, reason = spec.feasible(huge)
        assert not ok and "VMEM" in reason

    def test_divisibility_infeasibility(self):
        odd = OpKey(op="linear", batch=8, d_in=64, d_out=60, k_kept=30, tile=7)
        ok, reason = REGISTRY.get("linear", "compressed_pallas").feasible(odd)
        assert not ok

    def test_sharded_mesh_keys_refuse_pallas(self):
        # GSPMD cannot partition a Mosaic kernel: an op traced under a
        # multi-device sharding context gets a mesh-tagged key on which every
        # Pallas candidate is infeasible; single-device tokens are unchanged,
        # and so are keys traced inside a shard_map body manual over every
        # mesh axis (there the op is per-shard)
        import types

        from jax.sharding import PartitionSpec as P

        from repro.launch.mesh import make_mesh
        from repro.sharding import ShardingCtx, use_ctx

        plain = linear_key(batch=8, d_in=512, d_out=512, k_kept=256, tile=128)
        ctx = ShardingCtx(mesh=types.SimpleNamespace(
            shape={"data": 1, "model": 4}))
        in_body = []

        def body(x):
            in_body.append(linear_key(batch=8, d_in=512, d_out=512,
                                      k_kept=256, tile=128))
            return x

        with use_ctx(ctx):
            sharded = linear_key(batch=8, d_in=512, d_out=512, k_kept=256,
                                 tile=128)
            conv = dispatch.conv_key(16, 8, 8, 16, 3, 3, 1, 1, 72, 16)
            paged = dispatch.paged_attn_key(8, 8, 2, 64, 256, page_size=16)
            jax.jit(jax.shard_map(
                body, mesh=make_mesh((1, 1), ("data", "model")),
                in_specs=P(), out_specs=P()))(jnp.zeros(8))
        assert "mesh" not in plain.token and sharded.get("mesh") == 4
        assert in_body[0].token == plain.token
        for key, op in ((sharded, "linear"), (conv, "conv"),
                        (paged, "paged_attn")):
            for spec in REGISTRY.candidates(op):
                ok, reason = spec.feasible(key)
                if spec.backend == "pallas":
                    assert not ok and "partitioned" in reason, spec.name
        assert REGISTRY.get("linear", "compressed_pallas").feasible(plain)[0]

    def test_infeasible_key_still_dispatches(self, db):
        # every predicate failing degrades to smallest-footprint, not a crash
        odd = OpKey(op="linear", batch=8, d_in=64, d_out=60, k_kept=30, tile=7)
        spec = dispatch.best_impl(odd, param_keys=("values", "idx"))
        assert spec.name == "compressed_xla"


# ---------------------------------------------------------------------------
# Profile DB persistence
# ---------------------------------------------------------------------------


class TestProfileDB:
    def test_roundtrip(self, tmp_path):
        p = str(tmp_path / "db.json")
        d1 = ProfileDB(path=p)
        d1.put("k1", {"impl": "compressed_xla", "wall_us": 1.0})
        d2 = ProfileDB(path=p)
        assert d2.get("k1") == {"impl": "compressed_xla", "wall_us": 1.0}
        assert not d2.invalidated

    def test_atomic_write_leaves_no_temp(self, tmp_path):
        p = tmp_path / "db.json"
        d = ProfileDB(path=str(p))
        for i in range(5):
            d.put(f"k{i}", {"impl": "x", "wall_us": float(i)})
        leftovers = [f for f in tmp_path.iterdir() if f.name != "db.json"]
        assert leftovers == []
        json.loads(p.read_text())  # parseable, never torn

    def test_schema_version_mismatch_invalidates(self, tmp_path):
        p = tmp_path / "db.json"
        d = ProfileDB(path=str(p))
        d.put("k1", {"impl": "x"})
        data = json.loads(p.read_text())
        data["version"] = SCHEMA_VERSION - 1
        p.write_text(json.dumps(data))
        d2 = ProfileDB(path=str(p))
        assert d2.invalidated and len(d2) == 0

    def test_fingerprint_mismatch_invalidates(self, tmp_path):
        p = tmp_path / "db.json"
        d = ProfileDB(path=str(p))
        d.put("k1", {"impl": "x"})
        data = json.loads(p.read_text())
        data["fingerprint"]["backend"] = "not-a-real-backend"
        p.write_text(json.dumps(data))
        d2 = ProfileDB(path=str(p))
        assert d2.invalidated and len(d2) == 0

    def test_seed_era_bare_dict_invalidated(self, tmp_path):
        # the seed wrote {key: record} with no version envelope
        p = tmp_path / "tuning_cache.json"
        p.write_text(json.dumps({"b64_i256_o256_s50": {"tile": 64}}))
        d = ProfileDB(path=str(p))
        assert d.invalidated and len(d) == 0

    def test_lru_caps_entries(self, tmp_path):
        d = ProfileDB(path=str(tmp_path / "db.json"), max_entries=3,
                      autosave=False)
        for i in range(6):
            d.put(f"k{i}", {"impl": "x"}, save=False)
        assert len(d) == 3 and d.get("k5") is not None and d.get("k0") is None


# ---------------------------------------------------------------------------
# Selection: frozen DB determinism, overrides, escape hatches
# ---------------------------------------------------------------------------


class TestSelection:
    def test_frozen_db_overrides_heuristic(self, db):
        key = _small_key()
        # CPU heuristic would pick compressed_xla; a frozen profile saying
        # pallas won must be honoured verbatim
        db.put(key.token, {"impl": "compressed_pallas", "wall_us": 1.0})
        spec = dispatch.best_impl(key, param_keys=("values", "idx"))
        assert spec.name == "compressed_pallas"

    def test_selection_deterministic(self, db):
        key = _small_key()
        db.put(key.token, {"impl": "compressed_pallas", "wall_us": 1.0})
        names = {dispatch.best_impl(key, param_keys=("values", "idx")).name
                 for _ in range(10)}
        assert names == {"compressed_pallas"}

    def test_profile_then_select_consistent(self, db):
        key = _small_key()
        rec = profile_op(key, db, param_keys=("values", "idx"), iters=2)
        assert rec["impl"] in rec["all"]
        assert dispatch.best_impl(key, param_keys=("values", "idx")).name == rec["impl"]

    def test_env_off_restores_legacy_routing(self, db, monkeypatch):
        key = _small_key()
        db.put(key.token, {"impl": "compressed_pallas", "wall_us": 1.0})
        monkeypatch.setenv("REPRO_DISPATCH", "off")
        spec = dispatch.best_impl(key, param_keys=("values", "idx"))
        assert spec.name == "compressed_xla"

    def test_explicit_force_wins_even_when_off(self, db, monkeypatch):
        monkeypatch.setenv("REPRO_DISPATCH", "off")
        spec = dispatch.best_impl(_small_key(), param_keys=("values", "idx"),
                                  force="compressed_pallas")
        assert spec.name == "compressed_pallas"

    def test_env_force(self, db, monkeypatch):
        monkeypatch.setenv("REPRO_DISPATCH_FORCE", "compressed_pallas")
        spec = dispatch.best_impl(_small_key(), param_keys=("values", "idx"))
        assert spec.name == "compressed_pallas"

    def test_unknown_force_raises(self, db):
        with pytest.raises(KeyError):
            dispatch.best_impl(_small_key(), force="no_such_impl")

    def test_explicit_force_incompatible_params_raises(self, db):
        # 'dense' is registered but requires {"w"}; explicitly forcing it for
        # a compressed layer is a caller bug, not something to paper over
        with pytest.raises(KeyError, match="requires"):
            dispatch.best_impl(_small_key(), param_keys=("values", "idx"),
                               force="dense")

    def test_env_force_incompatible_params_ignored(self, db, monkeypatch):
        # the process-wide override skips layers it cannot execute
        monkeypatch.setenv("REPRO_DISPATCH_FORCE", "dense")
        spec = dispatch.best_impl(_small_key(), param_keys=("values", "idx"))
        assert spec.name == "compressed_xla"

    def test_new_registration_invalidates_memo(self, db):
        import dataclasses

        key = _small_key()
        first = dispatch.best_impl(key, param_keys=("values", "idx"))
        assert first.name == "compressed_xla"
        spec = REGISTRY.get("linear", "compressed_xla")
        try:
            # re-register under a new name with priority that beats the memo'd
            # winner: best_impl must see it without any manual cache clearing
            REGISTRY.register(dataclasses.replace(spec, name="compressed_xla2",
                                                  priority=1))
            assert dispatch.best_impl(
                key, param_keys=("values", "idx")).name == "compressed_xla2"
        finally:
            del REGISTRY._impls["linear"]["compressed_xla2"]
            REGISTRY.generation += 1

    def test_cross_process_determinism(self, tmp_path, db):
        """A frozen profile DB reproduces identical selections in fresh
        processes (the AITemplate 'bake the winner in' property)."""
        key = _small_key()
        db.put(key.token, {"impl": "compressed_pallas", "wall_us": 1.0})
        snippet = (
            "from repro import dispatch\n"
            f"key = dispatch.linear_key(batch=8, d_in=64, d_out=64, k_kept=32, tile=16)\n"
            "print(dispatch.best_impl(key, param_keys=('values','idx')).name)\n"
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
        assert outs == ["compressed_pallas", "compressed_pallas"]


# ---------------------------------------------------------------------------
# Numerical equivalence of every registered linear candidate
# ---------------------------------------------------------------------------


class TestLinearEquivalence:
    def _problem(self, d_in=64, d_out=64, batch=4, sparsity=0.5, tile=16):
        w = jax.random.normal(jax.random.PRNGKey(0), (d_in, d_out)) / (d_in ** 0.5)
        x = jax.random.normal(jax.random.PRNGKey(1), (batch, d_in))
        cfg = SparsityConfig(sparsity, m=None, tile=tile, format="compressed_xla")
        meta = meta_for(d_in, d_out, cfg)
        mask = colwise_nm_mask(w, sparsity, tile=meta.tile)
        values, idx = pack_colwise(w, mask, meta)
        return x, w, mask, values, idx

    def test_every_candidate_matches_dense_reference(self):
        x, w, mask, values, idx = self._problem()
        refs = {
            frozenset({"w"}): np.asarray(x @ w),
            frozenset({"w", "mask"}): np.asarray(x @ (w * mask)),
            frozenset({"values", "idx"}): np.asarray(x @ (w * mask)),
        }
        params_by_req = {
            frozenset({"w"}): {"w": w},
            frozenset({"w", "mask"}): {"w": w, "mask": mask},
            frozenset({"values", "idx"}): {"values": values, "idx": idx},
        }
        checked = 0
        for spec in REGISTRY.candidates("linear"):
            assert spec.apply is not None, f"{spec.name} has no apply"
            y = spec.apply(params_by_req[spec.requires], x)
            np.testing.assert_allclose(
                np.asarray(y), refs[spec.requires], rtol=1e-4, atol=1e-4,
                err_msg=f"candidate {spec.name} diverges from dense reference")
            checked += 1
        assert checked >= 4

    def test_linear_apply_executes_db_selection(self, db, monkeypatch):
        # route linear_apply's compressed branch through a counting pallas
        # impl pinned by the profile DB — proves the dispatch layer, not a
        # hardcoded branch, picks the kernel
        x, w, mask, values, idx = self._problem()
        key = dispatch.linear_key_from(x.shape, values.shape)
        db.put(key.token, {"impl": "compressed_pallas", "wall_us": 1.0})
        calls = []
        spec = REGISTRY.get("linear", "compressed_pallas")
        counting = dataclasses.replace(
            spec, apply=lambda p, xx: (calls.append(1),
                                       spec.apply(p, xx))[1])
        monkeypatch.setitem(REGISTRY._impls["linear"], "compressed_pallas",
                            counting)
        y = linear_apply({"values": values, "idx": idx}, x)
        assert calls, "profile-DB winner was not executed"
        np.testing.assert_allclose(np.asarray(y), np.asarray(x @ (w * mask)),
                                   rtol=1e-4, atol=1e-4)

    def test_linear_apply_off_switch(self, db, monkeypatch):
        x, w, mask, values, idx = self._problem()
        monkeypatch.setenv("REPRO_DISPATCH", "off")
        y = linear_apply({"values": values, "idx": idx}, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x @ (w * mask)),
                                   rtol=1e-4, atol=1e-4)

    def test_linear_apply_under_jit(self, db):
        x, w, mask, values, idx = self._problem()
        f = jax.jit(lambda x: linear_apply({"values": values, "idx": idx}, x))
        np.testing.assert_allclose(np.asarray(f(x)),
                                   np.asarray(x @ (w * mask)),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Build-time plan (serve Engine integration)
# ---------------------------------------------------------------------------


class TestPlanParams:
    def test_plan_finds_compressed_layers(self, db):
        cfg = SparsityConfig(sparsity=0.5, format="compressed_xla",
                             min_dim=8, tile=16)
        params = linear_init(jax.random.PRNGKey(0), 64, 64, cfg)
        vals, _ = unbox_tree(params)
        tree = {"blocks": [{"mlp": vals}], "head": {"w": jnp.zeros((4, 4))}}
        plan = dispatch.plan_params(tree, batch_hint=8)
        assert len(plan) == 1
        (token, impl), = plan.items()
        assert token.startswith("linear|") and impl in (
            "compressed_xla", "compressed_pallas")

    def test_plan_respects_frozen_db(self, db):
        cfg = SparsityConfig(sparsity=0.5, format="compressed_xla",
                             min_dim=8, tile=16)
        vals, _ = unbox_tree(linear_init(jax.random.PRNGKey(0), 64, 64, cfg))
        token = next(iter(dispatch.plan_params({"l": vals}, batch_hint=8)))
        db.put(token, {"impl": "compressed_pallas", "wall_us": 1.0})
        plan = dispatch.plan_params({"l": vals}, batch_hint=8)
        assert plan[token] == "compressed_pallas"


# ---------------------------------------------------------------------------
# Absorbed Tuner: crash fix, profile=False fallback, stale-cache invalidation
# ---------------------------------------------------------------------------


class TestTunerFixes:
    def test_all_infeasible_raises_named_error(self, tmp_path):
        t = Tuner(cache_path=str(tmp_path / "c.json"))
        with pytest.raises(TuningError, match=r"d_in=10000000"):
            t.tune(batch=1, d_in=10_000_000, d_out=512, profile=False)

    def test_profile_disabled_falls_back_to_smallest_vmem(self, tmp_path):
        from repro.dispatch import enumerate_candidates

        t = Tuner(cache_path=str(tmp_path / "c.json"))
        r = t.tune(batch=8, d_in=256, d_out=256, profile=False)
        feas = [c for c in enumerate_candidates(256, 256) if c.feasible]
        assert r["vmem_bytes"] == min(c.vmem_bytes for c in feas)
        assert r["wall_us"] is None  # nothing was wall-clocked

    def test_stale_seed_cache_not_reused(self, tmp_path):
        p = tmp_path / "tuning_cache.json"
        stale = {"b8_i256_o256_s50": {"tile": 999, "block_b": 1, "block_k": 1,
                                      "wall_us": 0.1, "vmem_bytes": 1}}
        p.write_text(json.dumps(stale))
        t = Tuner(cache_path=str(p))
        assert len(t.db) == 0  # versionless seed cache dropped
        r = t.tune(batch=8, d_in=256, d_out=256, profile=False)
        assert r["tile"] != 999

    def test_tuner_persists_versioned_format(self, tmp_path):
        p = tmp_path / "c.json"
        t = Tuner(cache_path=str(p))
        t.tune(batch=8, d_in=256, d_out=256, profile=False)
        data = json.loads(p.read_text())
        assert data["version"] == SCHEMA_VERSION
        assert "fingerprint" in data and "entries" in data
