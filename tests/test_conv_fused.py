"""Conv megakernel + conv dispatch tests: fused-vs-reference equivalence
across stride/pad/ragged/dtype, strip-major GEMM equivalence, geometry
candidates in the dispatch space (frozen-DB cross-process determinism,
extending the test_dispatch.py pattern), and the conv layer abstraction
(conv_init/conv_apply) routing through the registry with real params."""
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
    compress_conv_layer,
    conv_apply,
    conv_init,
    unbox_tree,
)
from repro.dispatch import REGISTRY, ProfileDB
from repro.kernels.colwise_nm import (
    colwise_nm_matmul_ref,
    colwise_nm_matmul_strips,
)
from repro.kernels.conv_gemm import (
    compress_conv_weights,
    conv2d_cnhw_ref,
    conv2d_colwise_sparse,
    conv2d_fused,
    conv2d_two_kernel,
    fused_vmem_bytes,
)
from repro.kernels.im2col_pack import im2col_pack_ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOL = {jnp.float32: dict(rtol=1e-4, atol=1e-4),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


@pytest.fixture
def db(tmp_path):
    d = ProfileDB(path=str(tmp_path / "profile.json"))
    dispatch.set_db(d)
    yield d
    dispatch.set_db(None)


def _sparse_conv_problem(c, b, h, w, o, k, sparsity=0.5, tile=8,
                         dtype=jnp.float32):
    x = jax.random.normal(jax.random.PRNGKey(c * h + w), (c, b, h, w), dtype)
    wt = jax.random.normal(jax.random.PRNGKey(o + k), (o, k, k, c), dtype)
    cfg = SparsityConfig(sparsity=sparsity, m=None, tile=tile,
                         format="compressed_pallas")
    values, idx, meta = compress_conv_weights(wt, cfg)
    # masked dense conv is the oracle
    wmat = wt.reshape(o, -1).T
    mask = colwise_nm_mask(wmat, sparsity, m=None, tile=meta.tile)
    wt_masked = (wmat * mask).T.reshape(o, k, k, c).astype(dtype)
    return x, values, idx, wt_masked


class TestFusedMegakernel:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "c,b,h,w,o,k,stride,pad,v",
        [
            (8, 2, 10, 10, 16, 3, 1, 1, 16),
            (8, 1, 10, 10, 16, 3, 2, 1, 16),    # strided
            (5, 2, 9, 7, 8, 3, 1, 0, 8),        # no pad, non-square
            (4, 1, 8, 8, 16, 1, 2, 0, 32),      # 1x1 strided
            (3, 1, 7, 7, 8, 3, 2, 1, 128),      # ragged final strip (P < V)
            (6, 2, 11, 11, 8, 3, 1, 1, 32),     # ragged: P % V != 0
        ],
    )
    def test_fused_matches_reference_conv(self, dtype, c, b, h, w, o, k,
                                          stride, pad, v):
        x, values, idx, wt_masked = _sparse_conv_problem(
            c, b, h, w, o, k, dtype=dtype)
        y = conv2d_fused(x, values, idx, kh=k, kw=k, stride=stride, pad=pad,
                         v=v)
        y_ref = conv2d_cnhw_ref(x, wt_masked, stride=stride, pad=pad)
        np.testing.assert_allclose(
            np.asarray(y, np.float32), np.asarray(y_ref, np.float32),
            **TOL[dtype])

    def test_fused_block_k_chunking(self):
        # k_kept not divisible by block_k: zero-padded chunks must not leak
        x, values, idx, wt_masked = _sparse_conv_problem(8, 1, 9, 9, 16, 3)
        assert values.shape[1] % 8 != 0 or values.shape[1] > 8
        y = conv2d_fused(x, values, idx, kh=3, kw=3, stride=1, pad=1, v=16,
                         block_k=8)
        y_ref = conv2d_cnhw_ref(x, wt_masked, stride=1, pad=1)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_strip_major_matches_row_major_gemm(self):
        x, values, idx, _ = _sparse_conv_problem(4, 2, 8, 8, 16, 3)
        strips = im2col_pack_ref(x, 3, 3, 1, 1, 16)  # [S, K, V]
        y = colwise_nm_matmul_strips(strips, values, idx)  # [O, S*V]
        xt = np.asarray(strips).transpose(0, 2, 1).reshape(-1, strips.shape[1])
        y_ref = colwise_nm_matmul_ref(jnp.asarray(xt), values, idx).T
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-5)

    def test_two_kernel_matches_fused(self):
        x, values, idx, _ = _sparse_conv_problem(6, 2, 11, 11, 8, 3)
        a = dict(kh=3, kw=3, stride=1, pad=1, v=32)
        y1 = conv2d_fused(x, values, idx, **a)
        y2 = conv2d_two_kernel(x, values, idx, **a)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=1e-5, atol=1e-5)


class TestConvDispatch:
    def test_fused_candidates_have_geometry_and_vmem(self):
        specs = [s for s in REGISTRY.candidates("conv")
                 if s.name.startswith("fused_sparse_pallas")]
        assert len(specs) >= 2
        for s in specs:
            assert s.geom("v") > 0 and s.geom("bk") > 0
            assert s.apply is not None and s.make_bench is not None

    def test_fused_infeasible_when_map_exceeds_vmem(self):
        # the megakernel keeps the whole CNHW map in VMEM; a big map must
        # fail its predicate while the two-kernel plan stays available
        key = dispatch.conv_key(512, 224, 224, 512, 3, 3, 1, 1, k_kept=2304,
                                tile=128, batch=8)
        spec = REGISTRY.get("conv", "fused_sparse_pallas")
        ok, reason = spec.feasible(key)
        assert not ok and "VMEM" in reason
        assert fused_vmem_bytes(512, 8, 224, 224, 128, 128, 128) > \
            dispatch.VMEM_BYTES

    def test_conv_key_phase_parity_with_linear_key(self):
        # the conv_key parity fix: phase-tagged conv tokens, untagged format
        # unchanged (existing DBs stay valid)
        plain = dispatch.conv_key(8, 10, 10, 16, 3, 3, 1, 1, 36, 8)
        tagged = dispatch.conv_key(8, 10, 10, 16, 3, 3, 1, 1, 36, 8,
                                   phase="prefill")
        assert tagged.token == plain.token + "|ph:prefill"
        with dispatch.phase_scope("decode"):
            assert dispatch.current_phase() == "decode"

    def test_frozen_db_picks_fused_geometry_variant(self, db):
        x, values, idx, wt_masked = _sparse_conv_problem(8, 2, 10, 10, 16, 3)
        key = dispatch.conv_key(8, 10, 10, 16, 3, 3, 1, 1,
                                values.shape[1], values.shape[2], v=16,
                                batch=2)
        name = [s.name for s in REGISTRY.candidates("conv")
                if s.name.startswith("fused_sparse_pallas@")][0]
        db.put(key.token, {"impl": name, "wall_us": 1.0})
        spec = dispatch.best_impl(key, param_keys=("values", "idx"))
        assert spec.name == name and spec.geometry
        y = conv2d_colwise_sparse(x, values, idx, kh=3, kw=3, stride=1,
                                  pad=1, v=16)
        y_ref = conv2d_cnhw_ref(x, wt_masked, stride=1, pad=1)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_geometry_selection_cross_process_deterministic(self, db):
        """A frozen DB naming a geometry variant reproduces the identical
        impl+geometry selection in fresh processes (impl and geometry are
        one record — the joint-selection property)."""
        key = dispatch.conv_key(8, 10, 10, 16, 3, 3, 1, 1, 36, 8, batch=2)
        name = [s.name for s in REGISTRY.candidates("conv")
                if s.name.startswith("fused_sparse_pallas@")][0]
        db.put(key.token, {"impl": name, "wall_us": 1.0})
        snippet = (
            "from repro import dispatch\n"
            "key = dispatch.conv_key(8, 10, 10, 16, 3, 3, 1, 1, 36, 8, batch=2)\n"
            "s = dispatch.best_impl(key, param_keys=('values','idx'))\n"
            "print(s.name, dict(s.geometry)['v'], dict(s.geometry)['bk'])\n"
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
        spec = REGISTRY.get("conv", name)
        want = f"{name} {spec.geom('v')} {spec.geom('bk')}"
        assert outs == [want, want]


class TestConvLayerAbstraction:
    CFG = SparsityConfig(sparsity=0.5, m=None, tile=8, min_dim=8,
                         format="compressed_pallas")

    def test_conv_init_compressed_params(self):
        params = conv_init(jax.random.PRNGKey(0), 8, 16, 3, 3, self.CFG)
        vals, specs = unbox_tree(params)
        # conv_geom is the op discriminator dispatch.plan_params keys on
        assert set(vals) == {"values", "idx", "conv_geom"}
        assert [int(v) for v in vals["conv_geom"]] == [3, 3, 8]
        n_tiles, k_kept, tile = vals["values"].shape
        assert n_tiles * tile == 16 and vals["idx"].shape == (n_tiles, k_kept)

    def test_conv_apply_round_trip_through_registry(self, db):
        # conv_apply must execute the profile-DB winner with real params
        params, _ = unbox_tree(conv_init(jax.random.PRNGKey(0), 8, 16, 3, 3,
                                         self.CFG))
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 2, 10, 10))
        key = dispatch.conv_key(8, 10, 10, 16, 3, 3, 1, 1,
                                params["values"].shape[1], 8, batch=2)
        db.put(key.token, {"impl": "fused_sparse_pallas", "wall_us": 1.0})
        y = conv_apply(params, x, kh=3, kw=3, stride=1, pad=1)
        # oracle: decompress and run the lax conv
        from repro.core import ColwiseMeta, unpack_colwise

        meta = ColwiseMeta(d_in=72, d_out=16, tile=8, m=72,
                           n=params["values"].shape[1])
        wmat = unpack_colwise(params["values"], params["idx"], meta)
        wt = wmat.T.reshape(16, 3, 3, 8)
        y_ref = conv2d_cnhw_ref(x, wt, stride=1, pad=1)
        assert y.shape == y_ref.shape == (16, 2, 10, 10)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_conv_apply_forced_impl_and_equivalence(self, db):
        params, _ = unbox_tree(conv_init(jax.random.PRNGKey(2), 8, 16, 3, 3,
                                         self.CFG))
        x = jax.random.normal(jax.random.PRNGKey(3), (8, 1, 9, 9))
        ys = [np.asarray(conv_apply(params, x, kh=3, kw=3, pad=1, impl=name))
              for name in ("fused_sparse_pallas", "im2col_sparse_pallas",
                           "im2col_sparse_xla")]
        np.testing.assert_allclose(ys[0], ys[1], rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(ys[0], ys[2], rtol=1e-4, atol=1e-4)

    def test_conv_init_masked_format(self):
        # masked parity with linear_init: weights actually pruned, mask kept
        cfg = SparsityConfig(sparsity=0.5, m=None, tile=8, min_dim=8,
                             format="masked")
        params, _ = unbox_tree(conv_init(jax.random.PRNGKey(10), 8, 16, 3, 3,
                                         cfg))
        assert set(params) == {"w", "mask"}
        zero_frac = float((params["w"] == 0).mean())
        assert abs(zero_frac - 0.5) < 0.05
        x = jax.random.normal(jax.random.PRNGKey(11), (8, 1, 8, 8))
        y = conv_apply(params, x, kh=3, kw=3, pad=1)
        y_ref = conv2d_cnhw_ref(
            x, params["w"] * params["mask"].astype(params["w"].dtype),
            stride=1, pad=1)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-5)

    def test_vmem_predicate_is_dtype_aware(self):
        # the same map geometry can be feasible in bf16 but not f32
        spec = REGISTRY.get("conv", "fused_sparse_pallas")
        kw = dict(kh=3, kw=3, stride=1, pad=1, k_kept=1152, tile=128)
        f32 = dispatch.conv_key(256, 64, 64, 256, kw["kh"], kw["kw"],
                                kw["stride"], kw["pad"], kw["k_kept"],
                                kw["tile"], dtype="float32")
        bf16 = dispatch.conv_key(256, 64, 64, 256, kw["kh"], kw["kw"],
                                 kw["stride"], kw["pad"], kw["k_kept"],
                                 kw["tile"], dtype="bfloat16")
        assert spec.vmem_bytes(f32) > spec.vmem_bytes(bf16)
        assert not spec.feasible(f32)[0] and spec.feasible(bf16)[0]

    def test_conv_dense_and_bias(self):
        cfg = SparsityConfig()  # disabled -> dense
        params, _ = unbox_tree(conv_init(jax.random.PRNGKey(4), 4, 8, 3, 3,
                                         cfg, use_bias=True))
        assert set(params) == {"w", "b"}
        x = jax.random.normal(jax.random.PRNGKey(5), (4, 2, 8, 8))
        y = conv_apply(params, x, kh=3, kw=3, pad=1)
        y_ref = conv2d_cnhw_ref(x, params["w"], stride=1, pad=1) + \
            params["b"][:, None, None, None]
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-5)

    def test_compress_conv_layer_matches_masked_dense(self, db):
        cfg = SparsityConfig()
        dense, _ = unbox_tree(conv_init(jax.random.PRNGKey(6), 8, 16, 3, 3,
                                        cfg))
        # compress_conv_layer returns Boxed leaves (same contract as
        # conv_init); apply consumes the unboxed values
        comp, _ = unbox_tree(compress_conv_layer(dense, 3, 3, self.CFG))
        x = jax.random.normal(jax.random.PRNGKey(7), (8, 1, 8, 8))
        y = conv_apply(comp, x, kh=3, kw=3, pad=1)
        wmat = dense["w"].reshape(16, -1).T
        mask = colwise_nm_mask(wmat, 0.5, m=None, tile=8)
        wt_masked = (wmat * mask).T.reshape(16, 3, 3, 8)
        y_ref = conv2d_cnhw_ref(x, wt_masked, stride=1, pad=1)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_conv_apply_inside_phase_scope(self, db):
        # a conv traced in a phase scope resolves a phase-tagged token; pin
        # different winners per phase and check both execute
        params, _ = unbox_tree(conv_init(jax.random.PRNGKey(8), 8, 16, 3, 3,
                                         self.CFG))
        x = jax.random.normal(jax.random.PRNGKey(9), (8, 1, 9, 9))
        base = dispatch.conv_key(8, 9, 9, 16, 3, 3, 1, 1,
                                 params["values"].shape[1], 8, batch=1)
        db.put(base.token + "|ph:prefill",
               {"impl": "fused_sparse_pallas", "wall_us": 1.0})
        with dispatch.phase_scope("prefill"):
            y = conv_apply(params, x, kh=3, kw=3, pad=1)
        y_ref = conv_apply(params, x, kh=3, kw=3, pad=1,
                           impl="im2col_sparse_xla")
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)
