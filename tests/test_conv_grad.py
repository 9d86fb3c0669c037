"""Differentiable sparse-conv path tests: the conv custom VJP against dense
autodiff across every conv plan rung (fused / banded / two-kernel pipelined /
plain / XLA, incl. stride-2, padding, ragged strips and forced rungs), the
f32-accumulated linear backward (bf16 params, 3-D/4-D duplicate scatter),
the Boxed ``compress_conv_layer`` round trip, masked-finetune hooks, and the
resnet-tiny sparse train step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import dispatch
from repro.configs import get_vision_config
from repro.core import (
    SparsityConfig,
    apply_conv_mask,
    colwise_nm_mask,
    compress_conv_layer,
    compress_conv_tree,
    conv_apply,
    conv_colwise_nm_mask,
    conv_init,
    mask_project_tree,
    prune_conv_tree,
    refresh_conv_mask,
    unbox_tree,
)
from repro.core.pruning import mask_is_colwise
from repro.dispatch import ProfileDB
from repro.kernels.colwise_nm import colwise_nm_matmul, sparse_grad_dvalues
from repro.kernels.conv_gemm import (
    compress_conv_weights,
    conv2d_cnhw_ref,
    conv2d_sparse,
)
from repro.models import vision


@pytest.fixture
def db(tmp_path):
    d = ProfileDB(path=str(tmp_path / "profile.json"))
    dispatch.set_db(d)
    yield d
    dispatch.set_db(None)


# every rung of the conv plan ladder (docs/kernels.md); the DMA rungs need an
# async-copy-capable pallas build, same gate as their dispatch predicates
RUNGS = [
    "fused_sparse_pallas",
    "fused_banded_pallas",
    "two_kernel_pipelined",
    "im2col_sparse_pallas",
    "im2col_sparse_xla",
]


def _conv_problem(c, b, h, w, o, k, stride, pad, dtype=jnp.float32, seed=0):
    """(x, values, idx, masked dense OHWI oracle, cotangent) for one conv."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (c, b, h, w), dtype)
    wt = jax.random.normal(jax.random.PRNGKey(seed + 1), (o, k, k, c),
                           jnp.float32)
    cfg = SparsityConfig(sparsity=0.5, m=None, tile=8,
                         format="compressed_pallas")
    values, idx, meta = compress_conv_weights(wt, cfg)
    wmat = wt.reshape(o, -1).T
    mask = colwise_nm_mask(wmat, 0.5, m=None, tile=meta.tile)
    wm = ((wmat * mask).T.reshape(o, k, k, c)).astype(dtype)
    y_ref = conv2d_cnhw_ref(x, wm, stride=stride, pad=pad)
    cot = jax.random.normal(jax.random.PRNGKey(seed + 2), y_ref.shape, dtype)
    return x, values.astype(dtype), idx, wm, cot


def _dense_ref_grads(x, wm, stride, pad, cot):
    """(dx, dW_ohwi) of the dense masked oracle under the same cotangent."""
    def loss(x, wm):
        return jnp.sum(conv2d_cnhw_ref(x, wm, stride=stride, pad=pad)
                       .astype(jnp.float32) * cot.astype(jnp.float32))

    return jax.grad(loss, argnums=(0, 1))(x, wm)


def _dvalues_ref(dw_ohwi, idx, tile):
    """Gather the dense oracle's weight grad at the kept packed positions."""
    o = dw_ohwi.shape[0]
    dwmat = np.asarray(dw_ohwi, np.float32).reshape(o, -1).T  # [K, O]
    n_tiles = idx.shape[0]
    return np.stack([dwmat[np.asarray(idx)[t], t * tile:(t + 1) * tile]
                     for t in range(n_tiles)])


class TestConvVJPLadder:
    """jax.grad through conv2d_sparse matches dense autodiff on every rung."""

    @pytest.mark.parametrize("impl", RUNGS)
    @pytest.mark.parametrize(
        "c,b,h,w,o,k,stride,pad",
        [
            (8, 2, 10, 10, 16, 3, 1, 1),   # multi-batch, padded
            (8, 1, 10, 10, 16, 3, 2, 1),   # stride 2
            (5, 2, 9, 7, 8, 3, 1, 0),      # no pad, non-square
            (6, 2, 11, 11, 8, 3, 1, 1),    # ragged: P % V != 0
        ],
    )
    def test_grad_matches_dense_reference(self, db, impl, c, b, h, w, o, k,
                                          stride, pad):
        x, values, idx, wm, cot = _conv_problem(c, b, h, w, o, k, stride, pad)

        def loss(x, values):
            y = conv2d_sparse(x, values, idx, kh=k, kw=k, stride=stride,
                              pad=pad, v=16, impl=impl)
            return jnp.sum(y * cot)

        dx, dv = jax.grad(loss, argnums=(0, 1))(x, values)
        dx_ref, dw_ref = _dense_ref_grads(x, wm, stride, pad, cot)
        np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(
            np.asarray(dv), _dvalues_ref(dw_ref, idx, values.shape[2]),
            rtol=1e-4, atol=1e-4)

    def test_value_and_grad_through_conv_apply(self, db):
        # the layer-level entry point (compressed conv_init params) is
        # differentiable end to end, gradients land on values only
        cfg = SparsityConfig(sparsity=0.5, m=None, tile=8, min_dim=8,
                             format="compressed_pallas")
        params, _ = unbox_tree(conv_init(jax.random.PRNGKey(0), 8, 16, 3, 3,
                                         cfg, use_bias=True))
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 2, 9, 9))

        def loss(p):
            return jnp.sum(conv_apply(p, x, kh=3, kw=3, pad=1) ** 2)

        val, g = jax.value_and_grad(loss, allow_int=True)(params)
        assert np.isfinite(float(val))
        assert np.isfinite(np.asarray(g["values"], np.float32)).all()
        assert np.isfinite(np.asarray(g["b"], np.float32)).all()
        assert g["idx"].dtype == jax.dtypes.float0  # no cotangent for idx

    def test_env_forced_rung_grad(self, db, monkeypatch):
        # REPRO_DISPATCH_FORCE pins the forward rung; the backward must still
        # be the shared VJP and match the dense reference
        monkeypatch.setenv("REPRO_DISPATCH_FORCE", "fused_banded_pallas")
        x, values, idx, wm, cot = _conv_problem(8, 2, 10, 10, 16, 3, 1, 1)

        def loss(x, values):
            y = conv2d_sparse(x, values, idx, kh=3, kw=3, stride=1, pad=1,
                              v=16)
            return jnp.sum(y * cot)

        dx, dv = jax.grad(loss, argnums=(0, 1))(x, values)
        dx_ref, dw_ref = _dense_ref_grads(x, wm, 1, 1, cot)
        np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(
            np.asarray(dv), _dvalues_ref(dw_ref, idx, values.shape[2]),
            rtol=1e-4, atol=1e-4)

    def test_grad_tracing_never_profiles(self, db, monkeypatch):
        # REPRO_DISPATCH_PROFILE=1 profiles on a DB miss at *forward* trace
        # time, but a gradient trace resolves through no_profile_scope: the
        # DB must stay empty after jax.grad
        monkeypatch.setenv("REPRO_DISPATCH_PROFILE", "1")
        x, values, idx, _wm, cot = _conv_problem(8, 1, 8, 8, 16, 3, 1, 1)

        def loss(x):
            y = conv2d_sparse(x, values, idx, kh=3, kw=3, stride=1, pad=1,
                              v=16)
            return jnp.sum(y * cot)

        jax.grad(loss)(x)
        assert not [t for t in db.tokens() if t.startswith("conv|")]


class TestLinearBackwardPrecision:
    """The f32-accumulation fixes in colwise_nm's _bwd."""

    def _linear_problem(self, batch_shape, d_in, d_out, tile, seed=0):
        w = jax.random.normal(jax.random.PRNGKey(seed), (d_in, d_out))
        mask = colwise_nm_mask(w, 0.5, m=None, tile=tile)
        from repro.core.formats import meta_for, pack_colwise

        cfg = SparsityConfig(sparsity=0.5, m=None, tile=tile,
                             format="compressed_pallas")
        values, idx = pack_colwise(w, mask, meta_for(d_in, d_out, cfg))
        x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                              (*batch_shape, d_in))
        cot = jax.random.normal(jax.random.PRNGKey(seed + 2),
                                (*batch_shape, d_out))
        return x, values, idx, (w * mask), cot

    def test_bf16_grads_match_f32_reference(self):
        # bf16 params used to accumulate the grad einsums in bf16; with
        # preferred_element_type=f32 the bf16 grads track the f32 oracle to
        # input-rounding accuracy over a 256-term reduction
        x, values, idx, wm, cot = self._linear_problem((64,), 512, 64, 8)

        def loss(x, values):
            return jnp.sum(colwise_nm_matmul(x, values, idx)
                           .astype(jnp.float32) * cot)

        dx16, dv16 = jax.grad(loss, argnums=(0, 1))(
            x.astype(jnp.bfloat16), values.astype(jnp.bfloat16))
        assert dx16.dtype == jnp.bfloat16 and dv16.dtype == jnp.bfloat16
        dx32, dw32 = jax.grad(
            lambda x, wm: jnp.sum((x @ wm) * cot), argnums=(0, 1))(x, wm)
        dv32 = _dvalues_ref(
            np.asarray(dw32).T.reshape(64, 1, 1, 512), idx, 8)
        scale_x = np.abs(np.asarray(dx32)).max()
        scale_v = np.abs(dv32).max()
        np.testing.assert_allclose(np.asarray(dx16, np.float32),
                                   np.asarray(dx32), rtol=3e-2,
                                   atol=3e-2 * scale_x)
        np.testing.assert_allclose(np.asarray(dv16, np.float32), dv32,
                                   rtol=3e-2, atol=3e-2 * scale_v)

    @pytest.mark.parametrize("batch_shape", [(6,), (2, 3), (2, 2, 3)])
    def test_dx_matches_dense_reference_nd(self, batch_shape):
        # leading batch dims are collapsed by colwise_nm_matmul before the
        # VJP; the duplicate scatter (tiles sharing kept d_in indices) must
        # still reproduce dense autodiff for 2-D/3-D/4-D inputs
        x, values, idx, wm, cot = self._linear_problem(batch_shape, 64, 32, 8)
        assert len(np.unique(np.asarray(idx))) < idx.size  # cross-tile dups

        def loss(x, values):
            return jnp.sum(colwise_nm_matmul(x, values, idx) * cot)

        dx, dv = jax.grad(loss, argnums=(0, 1))(x, values)
        dx_ref, dw_ref = jax.grad(
            lambda x, wm: jnp.sum((x @ wm) * cot), argnums=(0, 1))(x, wm)
        np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(
            np.asarray(dv),
            _dvalues_ref(np.asarray(dw_ref).T.reshape(32, 1, 1, 64), idx, 8),
            rtol=1e-4, atol=1e-4)

    def test_shared_dvalues_helper_accumulates_f32(self):
        xg = jnp.ones((4, 2, 8), jnp.bfloat16)
        dy = jnp.ones((4, 2, 8), jnp.bfloat16)
        out = sparse_grad_dvalues(xg, dy, jnp.bfloat16)
        assert out.dtype == jnp.bfloat16
        # 4-row reduction of ones is exact; f16-range overflow guard
        np.testing.assert_array_equal(np.asarray(out, np.float32), 4.0)


class TestCompressConvLayerBoxed:
    CFG = SparsityConfig(sparsity=0.5, m=None, tile=8, min_dim=8,
                         format="compressed_pallas")

    def test_boxed_structure_matches_conv_init(self):
        # post-hoc compression must emit the exact Boxed structure conv_init
        # emits for a born-sparse layer: same keys, same logical axes
        dense = conv_init(jax.random.PRNGKey(0), 8, 16, 3, 3,
                          SparsityConfig(), use_bias=True)
        comp = compress_conv_layer(dense, 3, 3, self.CFG)
        born = conv_init(jax.random.PRNGKey(1), 8, 16, 3, 3, self.CFG,
                         use_bias=True)
        assert set(comp) == set(born)
        for key in born:
            assert type(comp[key]).__name__ == "Boxed", key
            assert comp[key].spec == born[key].spec, key
            assert comp[key].value.shape == born[key].value.shape, key
            assert comp[key].value.dtype == born[key].value.dtype, key

    def test_compress_plan_params_round_trip(self, db):
        # the boxed compressed tree round-trips through plan_params exactly
        # like conv_init output: the conv_geom discriminator survives and the
        # planned token equals the one conv_apply resolves at trace time
        dense = conv_init(jax.random.PRNGKey(0), 8, 16, 3, 3,
                          SparsityConfig())
        comp = compress_conv_layer(dense, 3, 3, self.CFG)
        plan = dispatch.plan_params(
            {"layer": comp},
            conv_hints={"": dict(h=8, w=8, batch=2, stride=1, pad=1, v=128)})
        vals, _ = unbox_tree(comp)
        n_tiles, k_kept, tile = vals["values"].shape
        want = dispatch.conv_key(8, 8, 8, 16, 3, 3, 1, 1, k_kept, tile,
                                 v=128, batch=2).token
        assert list(plan) == [want]

    def test_compress_uses_stored_mask(self):
        # masked finetuning moves weights off their magnitude ordering; the
        # stored mask (not a recomputed one) must pin the packed support so
        # compressed inference equals the masked forward exactly
        mcfg = self.CFG.with_(format="masked", min_dim=8)
        params = conv_init(jax.random.PRNGKey(0), 8, 16, 3, 3, mcfg)
        vals, _ = unbox_tree(params)
        # drive kept weights toward zero: a recomputed magnitude mask would
        # select a different support
        shrunk = {**params, "w": type(params["w"])(
            vals["w"] * 1e-3, params["w"].spec)}
        comp, _ = unbox_tree(compress_conv_layer(shrunk, 3, 3, self.CFG))
        x = jax.random.normal(jax.random.PRNGKey(2), (8, 1, 8, 8))
        y = conv_apply(comp, x, kh=3, kw=3, pad=1, impl="im2col_sparse_xla")
        sv, _ = unbox_tree(shrunk)
        y_ref = conv2d_cnhw_ref(x, sv["w"] * sv["mask"].astype(sv["w"].dtype),
                                stride=1, pad=1)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-6)


class TestMaskedFinetuneHooks:
    MCFG = SparsityConfig(sparsity=0.5, m=None, tile=8, min_dim=8,
                          format="masked")

    def test_masked_conv_grad_confined_to_support(self):
        params, _ = unbox_tree(conv_init(jax.random.PRNGKey(0), 8, 16, 3, 3,
                                         self.MCFG))
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 2, 8, 8))
        g = jax.grad(lambda p: jnp.sum(conv_apply(p, x, kh=3, kw=3, pad=1)),
                     allow_int=True)(params)
        off = ~np.asarray(params["mask"])
        assert np.all(np.asarray(g["w"])[off] == 0)

    def test_apply_conv_mask_projects(self):
        params, _ = unbox_tree(conv_init(jax.random.PRNGKey(0), 8, 16, 3, 3,
                                         self.MCFG))
        drifted = {**params, "w": params["w"] + 1.0}  # resurrects pruned taps
        proj = apply_conv_mask(drifted)
        off = ~np.asarray(params["mask"])
        assert np.all(np.asarray(proj["w"])[off] == 0)
        on = ~off
        np.testing.assert_allclose(np.asarray(proj["w"])[on],
                                   np.asarray(drifted["w"])[on])

    def test_refresh_conv_mask_tracks_weights(self):
        params, _ = unbox_tree(conv_init(jax.random.PRNGKey(0), 8, 16, 3, 3,
                                         self.MCFG))
        # hand the layer new weights whose importance ordering differs
        new_w = jax.random.normal(jax.random.PRNGKey(7),
                                  params["w"].shape)
        refreshed = refresh_conv_mask({**params, "w": new_w}, self.MCFG)
        want = conv_colwise_nm_mask(new_w, 0.5, m=None, tile=8)
        np.testing.assert_array_equal(np.asarray(refreshed["mask"]),
                                      np.asarray(want))
        gemm_mask = np.asarray(want).reshape(16, -1).T
        assert mask_is_colwise(gemm_mask, 8)
        np.testing.assert_allclose(
            np.asarray(refreshed["w"]),
            np.asarray(new_w * want.astype(new_w.dtype)))

    def test_prune_conv_tree_then_project(self):
        cfg = get_vision_config("resnet-tiny")
        from repro.core import DENSE

        params, _ = unbox_tree(
            vision.vision_init(cfg.with_(sparsity=DENSE),
                               jax.random.PRNGKey(0)))
        pruned = prune_conv_tree(params, self.MCFG.with_(min_dim=16))
        # at least the stage convs got masks; stem (c_in=3 -> d_in=27) never
        assert "mask" not in pruned["stem"]
        assert any("mask" in blk[k] for blk in pruned["blocks"]
                   for k in ("conv1", "conv2") if isinstance(blk[k], dict))
        drift = jax.tree_util.tree_map(lambda p: p + 0.5, pruned)
        proj = mask_project_tree(drift)
        for blk_d, blk_p in zip(drift["blocks"], proj["blocks"]):
            for k in blk_d:
                if isinstance(blk_d[k], dict) and "mask" in blk_d[k]:
                    off = ~np.asarray(blk_d[k]["mask"], bool)
                    assert np.all(np.asarray(blk_p[k]["w"])[off] == 0)


    def test_compress_conv_tree_matches_masked_forward(self, db):
        # the full protocol's last step: prune -> compress_conv_tree; the
        # compressed model must reproduce the masked forward (stored masks
        # pin the packed support) and keep dense layers (stem, head) intact
        cfg = get_vision_config("resnet-tiny")
        from repro.core import DENSE

        params, _ = unbox_tree(
            vision.vision_init(cfg.with_(sparsity=DENSE),
                               jax.random.PRNGKey(0)))
        pruned = prune_conv_tree(params, self.MCFG.with_(min_dim=16))
        comp = compress_conv_tree(
            pruned, self.MCFG.with_(min_dim=16, format="compressed_pallas"))
        assert "w" in comp["stem"] and "w" in comp["head"]  # left dense
        assert any("values" in blk[k] for blk in comp["blocks"]
                   for k in ("conv1", "conv2") if isinstance(blk[k], dict))
        x, _ = vision.synth_batch(cfg, jax.random.PRNGKey(1), 2)
        y_masked = vision.vision_apply(pruned, cfg, x)
        y_comp = vision.vision_apply(comp, cfg, x)
        np.testing.assert_allclose(np.asarray(y_comp), np.asarray(y_masked),
                                   rtol=1e-4, atol=1e-4)


class TestVisionTrainStep:
    def test_train_smoke_reduces_loss(self, db):
        losses = vision.train_smoke(steps=2, verbose=False)
        assert losses[-1] < losses[0]
        assert all(np.isfinite(losses))

    def test_masked_finetune_keeps_support(self, db):
        cfg = get_vision_config("resnet-tiny")
        mcfg = cfg.with_(sparsity=cfg.sparsity.with_(format="masked"))
        params, _ = unbox_tree(vision.vision_init(mcfg, jax.random.PRNGKey(0)))
        x, labels = vision.synth_batch(cfg, jax.random.PRNGKey(1), 4)
        mom = vision.sgd_init(params)
        step = jax.jit(lambda p, m, x, y: vision.train_step(p, m, mcfg, x, y))
        before = [np.asarray(l["mask"], bool)
                  for blk in params["blocks"]
                  for l in blk.values()
                  if isinstance(l, dict) and "mask" in l]
        assert before  # masked layers exist
        params2, mom, loss = step(params, mom, x, labels)
        after = [l for blk in params2["blocks"] for l in blk.values()
                 if isinstance(l, dict) and "mask" in l]
        assert np.isfinite(float(loss))
        for mask, layer in zip(before, after):
            assert np.all(np.asarray(layer["w"])[~mask] == 0)
