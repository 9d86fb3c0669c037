"""Banded conv execution tier tests: the H-tiled megakernel (double-buffered
DMA row bands), the pipelined two-kernel strip GEMM, the four-rung conv plan
ladder in the dispatch registry, conv-aware ``plan_params`` (op
discriminator), and the resnet-tiny vision config exercising the conv
dispatch path end-to-end."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import dispatch
from repro.configs import get_vision_config
from repro.core import (
    SparsityConfig,
    colwise_nm_mask,
    compress_conv_layer,
    conv_apply,
    conv_init,
    linear_init,
    unbox_tree,
)
from repro.dispatch import REGISTRY, ProfileDB
from repro.kernels.colwise_nm import (
    colwise_nm_matmul_strips,
    colwise_nm_matmul_strips_pipelined,
)
from repro.kernels.conv_gemm import (
    band_plan,
    banded_vmem_bytes,
    compress_conv_weights,
    conv2d_cnhw_ref,
    conv2d_fused,
    conv2d_fused_banded,
    conv2d_two_kernel,
    conv2d_two_kernel_pipelined,
)
from repro.kernels.conv_gemm.kernel import (
    _conv_step_vmem_bytes,
    _select_window_bytes,
    _shift_window_bytes,
)
from repro.kernels.im2col_pack import im2col_pack_ref, out_size
from repro.kernels.im2col_pack.kernel import window_plan
from repro.kernels.pltpu_compat import ceil_to
from repro.models import vision

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
    wmat = wt.reshape(o, -1).T
    mask = colwise_nm_mask(wmat, sparsity, m=None, tile=meta.tile)
    wt_masked = (wmat * mask).T.reshape(o, k, k, c).astype(dtype)
    return x, values, idx, wt_masked


class TestBandedMegakernel:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "c,b,h,w,o,k,stride,pad,v,hb",
        [
            (8, 2, 10, 10, 16, 3, 1, 1, 16, 1),   # halo crosses every band
            (8, 2, 10, 10, 16, 3, 1, 1, 16, 2),
            (8, 1, 12, 12, 16, 3, 2, 1, 16, 2),   # stride>1 band origins
            (5, 2, 9, 7, 8, 3, 1, 0, 8, 2),       # no pad, non-square
            (3, 1, 7, 7, 8, 3, 2, 1, 128, 2),     # single ragged strip
            (6, 2, 11, 11, 8, 3, 1, 1, 32, 4),    # ragged final band, deep
            (4, 3, 8, 8, 16, 1, 2, 0, 32, 2),     # 1x1 strided, batch 3
        ],
    )
    def test_banded_matches_reference_conv(self, dtype, c, b, h, w, o, k,
                                           stride, pad, v, hb):
        x, values, idx, wt_masked = _sparse_conv_problem(
            c, b, h, w, o, k, dtype=dtype)
        y = conv2d_fused_banded(x, values, idx, kh=k, kw=k, stride=stride,
                                pad=pad, v=v, hb=hb)
        y_ref = conv2d_cnhw_ref(x, wt_masked, stride=stride, pad=pad)
        np.testing.assert_allclose(
            np.asarray(y, np.float32), np.asarray(y_ref, np.float32),
            **TOL[dtype])

    def test_banded_matches_fused_when_both_run(self):
        x, values, idx, _ = _sparse_conv_problem(8, 2, 10, 10, 16, 3)
        a = dict(kh=3, kw=3, stride=1, pad=1, v=16)
        y_f = conv2d_fused(x, values, idx, **a)
        y_b = conv2d_fused_banded(x, values, idx, hb=2, **a)
        np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_b),
                                   rtol=1e-5, atol=1e-5)

    def test_bands_are_genuinely_partial(self):
        # the correctness sweep must not silently degenerate to whole-map
        # bands: this geometry keeps under a third of the rows resident, and
        # adjacent bands share halo rows (the band-boundary case)
        b, h, k, stride, pad, v, hb = 2, 10, 3, 1, 1, 16, 1
        ho = wo = out_size(h, k, stride, pad)
        n_bands, rows = band_plan(b=b, h=h, kh=k, stride=stride, pad=pad,
                                  ho=ho, wo=wo, v=v, hb=hb)
        assert rows < b * h // 3
        assert n_bands > 3

    def test_band_plan_covers_every_strip(self):
        # coverage invariant: each band's fixed-size row window contains all
        # valid input rows of its strips — exact re-derivation per strip
        for (b, h, wo_w, k, stride, pad, v, hb) in [
                (2, 10, 10, 3, 1, 1, 16, 1), (1, 12, 12, 3, 2, 1, 16, 3),
                (3, 8, 8, 1, 2, 0, 32, 2), (2, 11, 11, 3, 1, 1, 32, 4)]:
            ho = out_size(h, k, stride, pad)
            wo = out_size(wo_w, k, stride, pad)
            n_pos = b * ho * wo
            n_strips = -(-n_pos // v)
            hb_eff = max(min(hb, n_strips), 1)
            n_bands, rows = band_plan(b=b, h=h, kh=k, stride=stride, pad=pad,
                                      ho=ho, wo=wo, v=v, hb=hb)
            assert n_bands == -(-n_strips // hb_eff)
            def first_row(p):
                bb, rem = divmod(p, ho * wo)
                return bb * h + (rem // wo) * stride - pad

            for g in range(n_bands):
                p0 = g * hb_eff * v
                p1 = min((g + 1) * hb_eff * v, n_pos) - 1
                origin = min(max(first_row(p0), 0), b * h - rows)
                # every in-bounds tap row of every position in the band must
                # fall inside the fixed-size window (first_row is monotonic
                # in p, so checking all positions is cheap and exhaustive)
                for p in range(p0, p1 + 1):
                    bb, rem = divmod(p, ho * wo)
                    for tap in range(k):
                        local = (rem // wo) * stride - pad + tap
                        if 0 <= local < h:
                            r = bb * h + local
                            assert origin <= r < origin + rows, (g, p, tap)

    def test_banded_block_k_chunking(self):
        x, values, idx, wt_masked = _sparse_conv_problem(8, 1, 9, 9, 16, 3)
        y = conv2d_fused_banded(x, values, idx, kh=3, kw=3, stride=1, pad=1,
                                v=16, block_k=8, hb=2)
        y_ref = conv2d_cnhw_ref(x, wt_masked, stride=1, pad=1)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)


class TestPipelinedStripGemm:
    @pytest.mark.parametrize("hb", [1, 2, 3, 100])  # 100 > n_strips: clamped
    def test_pipelined_matches_plain_strips(self, hb):
        x, values, idx, _ = _sparse_conv_problem(4, 2, 8, 8, 16, 3)
        strips = im2col_pack_ref(x, 3, 3, 1, 1, 16)  # [S, K, V]
        y_plain = colwise_nm_matmul_strips(strips, values, idx)
        y_pipe = colwise_nm_matmul_strips_pipelined(strips, values, idx,
                                                    hb=hb)
        np.testing.assert_allclose(np.asarray(y_pipe), np.asarray(y_plain),
                                   rtol=1e-5, atol=1e-5)

    def test_pipelined_two_kernel_matches_reference(self):
        x, values, idx, wt_masked = _sparse_conv_problem(6, 2, 11, 11, 8, 3)
        y = conv2d_two_kernel_pipelined(x, values, idx, kh=3, kw=3, stride=1,
                                        pad=1, v=32, hb=2)
        y_ref = conv2d_cnhw_ref(x, wt_masked, stride=1, pad=1)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_pipelined_matches_two_kernel_ragged_final_chunk(self):
        # n_strips odd with hb=2: the final chunk re-covers the previous
        # chunk's tail instead of reading out of bounds
        x, values, idx, _ = _sparse_conv_problem(5, 1, 10, 10, 8, 3)
        a = dict(kh=3, kw=3, stride=1, pad=1, v=16)
        n_pos = 10 * 10
        assert (-(-n_pos // 16)) % 2 == 1
        y1 = conv2d_two_kernel(x, values, idx, **a)
        y2 = conv2d_two_kernel_pipelined(x, values, idx, hb=2, **a)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=1e-5, atol=1e-5)


class TestPlanLadder:
    """The four rungs (VMEM-resident -> banded -> pipelined -> XLA) separate
    by their feasibility predicates, and the platform heuristic walks them in
    order as shapes grow."""

    # (key kwargs) per rung: tiny / stem-scale / wide-row / huge
    KEYS = {
        "resident": dict(c=8, h=10, w=10, o=16, kh=3, kw=3, stride=1, pad=1,
                         k_kept=36, tile=8, batch=2),
        "banded": dict(c=64, h=112, w=112, o=64, kh=3, kw=3, stride=2, pad=1,
                       k_kept=288, tile=64, batch=8),
        "pipelined": dict(c=512, h=64, w=2048, o=128, kh=3, kw=3, stride=1,
                          pad=1, k_kept=2304, tile=128, batch=1),
        "xla": dict(c=4096, h=512, w=512, o=128, kh=3, kw=3, stride=1, pad=1,
                    k_kept=18432, tile=128, batch=1),
    }
    FAMILY = {
        "resident": "fused_sparse_pallas",
        "banded": "fused_banded_pallas",
        "pipelined": "two_kernel_pipelined",
        "xla": "im2col_sparse_xla",
    }

    @staticmethod
    def _key(kw):
        return dispatch.conv_key(kw["c"], kw["h"], kw["w"], kw["o"], kw["kh"],
                                 kw["kw"], kw["stride"], kw["pad"],
                                 kw["k_kept"], kw["tile"], batch=kw["batch"])

    def test_predicates_separate_the_rungs(self):
        resident = REGISTRY.get("conv", "fused_sparse_pallas")
        banded = REGISTRY.get("conv", "fused_banded_pallas")
        key_b = self._key(self.KEYS["banded"])
        assert not resident.feasible(key_b)[0]
        assert banded.feasible(key_b)[0]
        key_p = self._key(self.KEYS["pipelined"])
        assert not any(
            s.feasible(key_p)[0] for s in REGISTRY.candidates("conv")
            if s.name.startswith("fused_"))
        assert any(
            s.feasible(key_p)[0] for s in REGISTRY.candidates("conv")
            if s.name.startswith("two_kernel_pipelined"))
        key_x = self._key(self.KEYS["xla"])
        feas = [s.name for s in
                REGISTRY.feasible(key_x, param_keys=("values", "idx"))]
        assert feas == ["im2col_sparse_xla"]

    def test_heuristic_walks_the_ladder(self, db, monkeypatch):
        # the pallas rungs are ahead of XLA only on the matching platform
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        for rung, kw in self.KEYS.items():
            spec = dispatch.best_impl(self._key(kw),
                                      param_keys=("values", "idx"))
            assert spec.name.startswith(self.FAMILY[rung]), (rung, spec.name)

    def test_profiled_db_pins_each_rung(self, db):
        # a profiled winner per rung shape: the frozen-DB selection returns
        # each rung's candidate (and its geometry) for its shape
        for rung, kw in self.KEYS.items():
            key = self._key(kw)
            name = self.FAMILY[rung]
            if rung == "banded":
                name += "@v256_bk128_hb2"  # a non-default banded geometry
            if rung == "pipelined":
                name += "@v128_bk64_hb1"
            db.put(key.token, {"impl": name, "wall_us": 1.0})
            spec = dispatch.best_impl(key, param_keys=("values", "idx"))
            assert spec.name == name, (rung, spec.name)
            if rung in ("banded", "pipelined"):
                assert spec.geom("hb") > 0

    def test_banded_vmem_predicate_is_dtype_aware_of_double_buffer(self):
        # the same band geometry is feasible in bf16 but not f32, and the
        # analytic model counts BOTH band buffers of the double buffer.  A
        # strided conv: its bands are row windows (the select packer)
        spec = REGISTRY.get("conv", "fused_banded_pallas")
        hb = spec.geom("hb")
        # w chosen so hb*v does not divide wo: bands cross an output-row
        # boundary and the window carries the full stride+halo row count
        kw = dict(c=64, h=640, w=600, o=256, k_kept=288, tile=128)
        f32 = dispatch.conv_key(kw["c"], kw["h"], kw["w"], kw["o"], 3, 3, 2,
                                1, kw["k_kept"], kw["tile"], dtype="float32")
        bf16 = dispatch.conv_key(kw["c"], kw["h"], kw["w"], kw["o"], 3, 3, 2,
                                 1, kw["k_kept"], kw["tile"],
                                 dtype="bfloat16")
        assert spec.vmem_bytes(f32) > spec.vmem_bytes(bf16)
        assert not spec.feasible(f32)[0] and spec.feasible(bf16)[0]
        ho = out_size(kw["h"], 3, 2, 1)
        wo = out_size(kw["w"], 3, 2, 1)
        v = spec.geom("v")
        _, rows = band_plan(b=1, h=kw["h"], kh=3, stride=2, pad=1, ho=ho,
                            wo=wo, v=v, hb=hb)
        # one band buffer as the kernel allocates it: an aligned row window
        # over the lane-padded map
        win_rows, _ = window_plan(rows, kw["h"])
        w_pad = ceil_to(kw["w"], 128)
        one_band = kw["c"] * win_rows * w_pad * 4
        assert spec.vmem_bytes(f32) >= 2 * one_band
        # beyond the per-step working set it counts exactly two buffers
        window = _select_window_bytes(kw["c"], w_pad, win_rows, v, 4)
        step = _conv_step_vmem_bytes(window, kw["c"], 9, v,
                                     min(spec.geom("bk"), kw["k_kept"]),
                                     kw["tile"], 4)
        assert spec.vmem_bytes(f32) - step == 2 * one_band

    def test_banded_vmem_predicate_counts_both_lane_windows(self):
        # a "same" stride-1 conv's bands are lane windows of the flattened
        # map (the shift packer): the predicate is dtype-aware and counts
        # both windows of the double buffer, each hb strips plus a lane halo
        # on each side wide
        spec = REGISTRY.get("conv", "fused_banded_pallas")
        v, hb = spec.geom("v"), spec.geom("hb")
        kw = dict(c=512, h=8, w=1000, o=256, k_kept=288, tile=128)
        f32, bf16 = (dispatch.conv_key(kw["c"], kw["h"], kw["w"], kw["o"], 3,
                                       3, 1, 1, kw["k_kept"], kw["tile"],
                                       dtype=dt)
                     for dt in ("float32", "bfloat16"))
        assert not spec.feasible(f32)[0] and spec.feasible(bf16)[0]
        halo = ceil_to(kw["w"] + 1, 128)  # pad*w + pad lanes, tile-aligned
        one_band = kw["c"] * (hb * v + 2 * halo) * 4
        window = _shift_window_bytes(kw["c"], halo, v, 4)
        step = _conv_step_vmem_bytes(window, kw["c"], 9, v,
                                     min(spec.geom("bk"), kw["k_kept"]),
                                     kw["tile"], 4)
        assert spec.vmem_bytes(f32) - step == 2 * one_band

    def test_banded_geometry_cross_process_deterministic(self, db):
        """A frozen DB naming a banded geometry variant reproduces the
        identical impl+geometry (incl. band depth) in fresh processes."""
        kw = self.KEYS["banded"]
        key = self._key(kw)
        name = "fused_banded_pallas@v256_bk128_hb2"
        db.put(key.token, {"impl": name, "wall_us": 1.0})
        snippet = (
            "from repro import dispatch\n"
            f"key = dispatch.conv_key({kw['c']}, {kw['h']}, {kw['w']}, "
            f"{kw['o']}, 3, 3, {kw['stride']}, {kw['pad']}, {kw['k_kept']}, "
            f"{kw['tile']}, batch={kw['batch']})\n"
            "s = dispatch.best_impl(key, param_keys=('values','idx'))\n"
            "print(s.name, s.geom('v'), s.geom('bk'), s.geom('hb'))\n"
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
        assert outs == [f"{name} 256 128 2"] * 2

    def test_forced_banded_and_pipelined_execute(self, db):
        # REPRO_DISPATCH_FORCE-style forcing by name runs the DMA plans with
        # real params through the conv layer abstraction
        cfg = SparsityConfig(sparsity=0.5, m=None, tile=8, min_dim=8,
                             format="compressed_pallas")
        params, _ = unbox_tree(conv_init(jax.random.PRNGKey(2), 8, 16, 3, 3,
                                         cfg))
        x = jax.random.normal(jax.random.PRNGKey(3), (8, 2, 9, 9))
        ys = [np.asarray(conv_apply(params, x, kh=3, kw=3, pad=1, impl=name))
              for name in ("fused_banded_pallas", "two_kernel_pipelined",
                           "im2col_sparse_xla")]
        np.testing.assert_allclose(ys[0], ys[2], rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(ys[1], ys[2], rtol=1e-4, atol=1e-4)


class TestConvAwarePlanParams:
    CFG = SparsityConfig(sparsity=0.5, m=None, tile=8, min_dim=8,
                         format="compressed_pallas")

    def _tree(self):
        return {
            "blk": conv_init(jax.random.PRNGKey(0), 8, 16, 3, 3, self.CFG),
            "head": linear_init(jax.random.PRNGKey(1), 128, 256,
                                self.CFG.with_(min_dim=128)),
        }

    def test_discriminator_separates_ops(self):
        ops = {p: op for p, op, _ in dispatch.iter_op_layers(self._tree())}
        assert ops == {"blk": "conv", "head": "linear"}
        info = next(i for _, op, i in dispatch.iter_op_layers(self._tree())
                    if op == "conv")
        assert (info["kh"], info["kw"], info["c_in"]) == (3, 3, 8)

    def test_iter_compressed_layers_back_compat(self):
        # the legacy generator still yields BOTH kinds (3-tuples)
        out = list(dispatch.iter_compressed_layers(self._tree()))
        assert {p for p, _v, _i in out} == {"blk", "head"}

    def test_conv_layers_planned_under_conv_tokens(self, db):
        plan = dispatch.plan_params(
            self._tree(), batch_hint=8,
            conv_hints={"": {"h": 10, "w": 10, "batch": 2, "stride": 1,
                             "pad": 1, "v": 16}})
        want = dispatch.conv_key(8, 10, 10, 16, 3, 3, 1, 1, 36, 8, v=16,
                                 batch=2).token
        assert want in plan
        # exactly one conv token and one linear token; nothing misfiled
        assert sum(t.startswith("conv|") for t in plan) == 1
        assert sum(t.startswith("linear|") for t in plan) == 1

    def test_conv_without_hint_is_skipped_not_misfiled(self, db):
        plan = dispatch.plan_params(self._tree(), batch_hint=8)
        assert not any(t.startswith("conv|") for t in plan)
        assert sum(t.startswith("linear|") for t in plan) == 1

    def test_longest_hint_key_wins(self, db):
        tree = {"a": {"blk": conv_init(jax.random.PRNGKey(0), 8, 16, 3, 3,
                                       self.CFG)}}
        plan = dispatch.plan_params(
            tree,
            conv_hints={"": {"h": 8, "batch": 1},
                        "a/blk": {"h": 12, "batch": 1, "pad": 1}})
        assert any("|h12|" in t for t in plan), list(plan)

    def test_scan_stacked_conv_geom(self):
        # a lax.scan-stacked conv layer carries an [L, 3] marker; the scan
        # reads layer 0's statics instead of crashing
        p, _ = unbox_tree(conv_init(jax.random.PRNGKey(0), 8, 16, 3, 3,
                                    self.CFG))
        stacked = {k: np.stack([np.asarray(v)] * 4) for k, v in p.items()}
        (path, op, info), = dispatch.iter_op_layers({"scan": stacked})
        assert op == "conv"
        assert (info["kh"], info["kw"], info["c_in"]) == (3, 3, 8)

    def test_compress_conv_layer_carries_discriminator(self):
        dense, _ = unbox_tree(conv_init(jax.random.PRNGKey(6), 8, 16, 3, 3,
                                        SparsityConfig()))
        comp = compress_conv_layer(dense, 3, 3, self.CFG)
        assert [int(v) for v in comp["conv_geom"].value] == [3, 3, 8]
        ops = [op for _, op, _ in dispatch.iter_op_layers({"l": comp})]
        assert ops == ["conv"]


class TestVisionConfig:
    def test_resnet_tiny_forward(self):
        cfg = get_vision_config("resnet-tiny")
        params, specs = unbox_tree(vision.vision_init(cfg,
                                                      jax.random.PRNGKey(0)))
        x = jax.random.normal(jax.random.PRNGKey(1),
                              (cfg.c_in, 2, *cfg.image_hw))
        logits = vision.vision_apply(params, cfg, x)
        assert logits.shape == (2, cfg.num_classes)
        assert np.isfinite(np.asarray(logits)).all()

    def test_pruned_convs_present_and_stem_dense(self):
        cfg = get_vision_config("resnet-tiny")
        params, _ = unbox_tree(vision.vision_init(cfg, jax.random.PRNGKey(0)))
        assert "w" in params["stem"]  # 3-channel stem left dense (paper)
        conv_paths = [p for p, op, _ in dispatch.iter_op_layers(params)
                      if op == "conv"]
        assert len(conv_paths) >= 4  # both stages' 3x3s are pruned

    def test_plan_matches_trace_time_conv_tokens(self, db):
        # end-to-end: every conv token the traced forward resolves was
        # pre-planned by plan_params(conv_hints=vision.conv_hints(cfg))
        cfg = get_vision_config("resnet-tiny")
        params, _ = unbox_tree(vision.vision_init(cfg, jax.random.PRNGKey(0)))
        plan = dispatch.plan_params(params, batch_hint=2,
                                    conv_hints=vision.conv_hints(cfg, batch=2))
        seen = []
        orig = dispatch.best_impl

        def spy(key, **kw):
            seen.append(key.token)
            return orig(key, **kw)

        dispatch.best_impl = spy
        try:
            x = jax.random.normal(jax.random.PRNGKey(1),
                                  (cfg.c_in, 2, *cfg.image_hw))
            vision.vision_apply(params, cfg, x)
        finally:
            dispatch.best_impl = orig
        trace_conv = {t for t in seen if t.startswith("conv|")}
        assert trace_conv and trace_conv <= set(plan)

    def test_forward_matches_forced_xla_plan(self, db):
        # the dispatched forward equals the forced XLA-reference-plan forward
        cfg = get_vision_config("resnet-tiny")
        params, _ = unbox_tree(vision.vision_init(cfg, jax.random.PRNGKey(4)))
        x = jax.random.normal(jax.random.PRNGKey(5),
                              (cfg.c_in, 1, *cfg.image_hw))
        y = vision.vision_apply(params, cfg, x)
        y_ref = vision.vision_apply(params, cfg, x, impl="im2col_sparse_xla")
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_banded_plan_through_vision_model(self, db):
        # force the DMA megakernel through a whole vision forward
        cfg = get_vision_config("resnet-tiny")
        params, _ = unbox_tree(vision.vision_init(cfg, jax.random.PRNGKey(6)))
        x = jax.random.normal(jax.random.PRNGKey(7),
                              (cfg.c_in, 1, *cfg.image_hw))
        y = vision.vision_apply(params, cfg, x, impl="fused_banded_pallas")
        y_ref = vision.vision_apply(params, cfg, x, impl="im2col_sparse_xla")
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)
