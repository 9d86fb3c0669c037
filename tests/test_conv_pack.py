"""The conv kernels' two im2col packers, against the plain references.

A stride-1 conv whose output grid is its input grid packs by shifted copies
of the lane-flattened map; every other conv keeps the one-hot select.  Each
case runs one kernel in interpret mode in bfloat16 and compares bit for bit:
the packed strips against ``im2col_pack/ref.py``, the megakernels' conv
output against ``conv_gemm/ref.py`` on small-integer data, whose products
and sums are exact in any order.  The ``conv.pack_shift`` and
``conv.pack_select`` counters say which packer the kernel built."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import dispatch, obs
from repro.configs.base import VisionConfig
from repro.core import SparsityConfig, colwise_nm_mask, unbox_tree
from repro.dispatch import ProfileDB
from repro.kernels.conv_gemm import (
    compress_conv_weights,
    conv2d_cnhw_ref,
    conv2d_fused,
    conv2d_fused_banded,
)
from repro.kernels.im2col_pack import im2col_pack, im2col_pack_ref
from repro.kernels.im2col_pack.kernel import lane_plan, tap_coords
from repro.obs import metrics as om
from repro.models import vision

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (c, b, h = w, k, stride, pad, packer): B = 3 so that taps cross batch
# boundaries; no B*H*W here is a multiple of the 128-wide strip
CASES = {
    "w56_k3_s1_p1": (8, 3, 56, 3, 1, 1, "shift"),
    "w28_k3_s1_p1": (16, 3, 28, 3, 1, 1, "shift"),
    "w28_k3_s2_p1": (8, 3, 28, 3, 2, 1, "select"),
    "w14_k1_s1_p0": (16, 3, 14, 1, 1, 0, "shift"),
    "w14_k1_s2_p0": (16, 3, 14, 1, 2, 0, "select"),
    "w7_k3_s1_p1": (16, 3, 7, 3, 1, 1, "shift"),
    "w7_k3_s1_p0": (8, 3, 7, 3, 1, 0, "select"),
}
KERNELS = ("pack", "fused", "banded")
V = 128


@pytest.fixture
def counting():
    """The global metrics registry zeroed and recording for one test."""
    was = obs.enabled()
    obs.reset()
    obs.set_enabled(True)
    yield lambda: (om.counter("conv.pack_shift").value,
                   om.counter("conv.pack_select").value)
    obs.set_enabled(was)
    obs.reset()


def _ints(key, shape):
    """bfloat16 integers in [-2, 2]."""
    return jax.random.randint(key, shape, -2, 3).astype(jnp.bfloat16)


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("kernel", KERNELS)
def test_pack_and_conv_exact(counting, kernel, case):
    c, b, hw, k, stride, pad, packer = CASES[case]
    x = _ints(jax.random.PRNGKey(c * hw + k), (c, b, hw, hw))
    geo = dict(kh=k, kw=k, stride=stride, pad=pad)
    if kernel == "pack":
        got = im2col_pack(x, v=V, **geo)
        want = im2col_pack_ref(x, k, k, stride, pad, V)
    else:
        o = 16
        wt = _ints(jax.random.PRNGKey(o + k), (o, k, k, c))
        cfg = SparsityConfig(sparsity=0.5, m=None, tile=8,
                             format="compressed_pallas")
        values, idx, meta = compress_conv_weights(wt, cfg)
        wmat = wt.reshape(o, -1).T
        mask = colwise_nm_mask(wmat, 0.5, m=None, tile=meta.tile)
        wt_kept = (wmat * mask).T.reshape(o, k, k, c).astype(wt.dtype)
        run = conv2d_fused if kernel == "fused" else conv2d_fused_banded
        got = run(x, values, idx, v=V, **geo)
        want = conv2d_cnhw_ref(x, wt_kept, stride=stride, pad=pad)
    assert got.dtype == want.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    assert counting() == ((1, 0) if packer == "shift" else (0, 1))


@pytest.mark.parametrize("b,hw,pad,v,hb", [(3, 7, 1, 128, 1),
                                            (2, 10, 1, 16, 2),
                                            (3, 5, 2, 8, 3),
                                            (1, 9, 0, 16, 1)])
def test_lane_plan_covers_every_tap(b, hw, pad, v, hb):
    """Every in-map source of every tap of every position lies in its
    band's lane window, at the lane the shift packer reads, and the padded
    map holds every band's window."""
    k = 2 * pad + 1
    plan = lane_plan(b=b, h=hw, w=hw, pad=pad, v=v, hb=hb)
    n_pos = b * hw * hw
    n_strips = -(-n_pos // v)
    hb = min(hb, n_strips)
    assert plan.n_bands == -(-n_strips // hb)
    assert plan.n_bands * hb * v + 2 * plan.halo == plan.lanes
    p = np.arange(n_strips * v)
    for ikh in range(k):
        for ikw in range(k):
            valid, bc, ihc, iwc = (np.asarray(a) for a in tap_coords(
                p, ikh=ikh, ikw=ikw, stride=1, pad=pad, b=b, h=hw, w=hw,
                ho=hw, wo=hw))
            # the padded map's lane of the source, as the packer reads it
            lane = p + plan.halo + (ikh - pad) * hw + ikw - pad
            first = (p // v // hb) * hb * v  # lane 0 of the band's window
            ok = (lane >= first) & (lane < first + plan.win)
            assert ok[valid].all()
            flat = (bc * hw + ihc) * hw + iwc
            assert (lane[valid] - plan.halo == flat[valid]).all()


def _resnet18_c50() -> VisionConfig:
    """The benchmark's ``resnet18-c50`` configuration as the program's
    ``VisionConfig``."""
    path = os.path.join(REPO, "chipbench", "configs", "resnet18-c50.json")
    with open(path) as f:
        cfg = json.load(f)
    sp = cfg["sparsity"]
    return VisionConfig(
        name=cfg["name"], c_in=cfg["c_in"],
        stem_channels=cfg["stem_channels"],
        stage_channels=tuple(cfg["stage_channels"]),
        stage_blocks=tuple(cfg["stage_blocks"]),
        stage_strides=tuple(cfg["stage_strides"]),
        image_hw=tuple(cfg["image_hw"]), num_classes=cfg["num_classes"],
        strip_v=cfg["strip_v"], dtype=cfg["dtype"],
        sparsity=SparsityConfig(sparsity=sp["fraction"], m=sp["m"],
                                tile=sp["tile"], min_dim=sp["min_dim"],
                                format=sp["format"]))


def test_resnet18_counts_pack_paths(counting, tmp_path):
    """One trace of ResNet-18's forward builds 19 compressed convs: the 13
    "same" stride-1 3x3 convs pack by shifted copies, the three 3x3/2 convs
    and the three 1x1/2 projections by the one-hot select (the stem is
    dense)."""
    cfg = _resnet18_c50()
    params = jax.eval_shape(
        lambda: unbox_tree(vision.vision_init(cfg, jax.random.PRNGKey(0)))[0])
    h, w = cfg.image_hw
    x = jax.ShapeDtypeStruct((cfg.c_in, 2, h, w), jnp.bfloat16)
    dispatch.set_db(ProfileDB(path=str(tmp_path / "profile.json")))
    try:
        jax.make_jaxpr(lambda p, x: vision.vision_apply(p, cfg, x))(params, x)
    finally:
        dispatch.set_db(None)
    assert counting() == (13, 6)
