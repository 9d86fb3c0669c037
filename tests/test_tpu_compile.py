"""Compile the main path's Pallas kernels for a described TPU v5e chip.

No chip is attached: the TPU compiler lowers each kernel at real widths for a
described ``v5e:2x2`` topology (``jax.experimental.topologies``) and refuses
what the chip would refuse — unaligned blocks, unsupported vector shapes and
gathers, VMEM overflows — which interpret mode cannot see.  Nothing runs, so
these tests say nothing about results or speed.

The topology is described only inside the module fixture, once a test of
this file runs: the TPU library admits one loader per process.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.colwise_nm.kernel import (
    colwise_nm_matmul_pallas,
    colwise_nm_matmul_strips_pallas,
    colwise_nm_matmul_strips_pipelined_pallas,
)
from repro.kernels.conv_gemm.kernel import (
    conv2d_fused_banded_pallas,
    conv2d_fused_pallas,
)
from repro.dispatch import PAGED_ATTN_GEOMETRY
from repro.kernels.flash_attn.kernel import flash_attention_pallas
from repro.kernels.flash_attn.paged import paged_attention_pallas
from repro.kernels.im2col_pack.kernel import im2col_pack_pallas

BF16 = jnp.bfloat16
I32 = jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e chip, with the persistent compile
    cache off (entries compiled for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # the Pallas kernel is in the program
    return text


# qwen2-0.5b column-wise 50% linears: (rows, d_in, n_tiles, k_kept, tile)
LINEAR = {
    "mlp_tile128_prefill": (128, 896, 38, 448, 128),
    "gate_tile_dout_decode": (8, 896, 1, 448, 4864),
    "gate_tile_dout_prefill": (256, 896, 1, 448, 4864),
    "down_tile_dout_prefill": (256, 4864, 1, 2432, 896),
    "kv_tile_dout_decode": (8, 896, 1, 448, 128),
}


@pytest.mark.parametrize("case", sorted(LINEAR))
def test_colwise_nm_compiles(one_chip, case):
    rows, d_in, n_tiles, k, tile = LINEAR[case]
    _compile(one_chip, colwise_nm_matmul_pallas,
             ((rows, d_in), BF16), ((n_tiles, k, tile), BF16),
             ((n_tiles, k), I32))


@pytest.mark.parametrize("kernel", [colwise_nm_matmul_strips_pallas,
                                    colwise_nm_matmul_strips_pipelined_pallas],
                         ids=["strips", "strips_pipelined"])
def test_colwise_nm_strips_compile(one_chip, kernel):
    # ResNet s3.c2 at 50%: 2 strips of [2304, 128], one 256-wide tile
    _compile(one_chip, kernel, ((2, 2304, 128), BF16), ((1, 1152, 256), BF16),
             ((1, 1152), I32))


# ResNet-50 stage convs: (c, h, o, k, stride, batch)
CONV = {
    "s2.c2": (128, 28, 128, 3, 1, 1),
    "s3.c2": (256, 14, 256, 3, 1, 1),
    "s4.c2": (512, 7, 512, 3, 1, 1),
    "s2.c2.b4": (128, 28, 128, 3, 1, 4),
    "stem.b8": (64, 112, 64, 3, 2, 8),
}


@pytest.mark.parametrize("layer", ["s3.c2", "stem.b8"])
def test_im2col_pack_compiles(one_chip, layer):
    c, h, _, k, stride, b = CONV[layer]
    _compile(one_chip, lambda x: im2col_pack_pallas(x, k, k, stride, k // 2),
             ((c, b, h, h), BF16))


def _conv_shapes(layer):
    c, h, o, k, stride, b = CONV[layer]
    kept = k * k * c // 2
    return (k, stride), [((c, b, h, h), BF16), ((1, kept, o), BF16),
                         ((1, kept), I32)]


@pytest.mark.parametrize("layer", ["s2.c2", "s3.c2", "s4.c2"])
def test_conv2d_fused_compiles(one_chip, layer):
    (k, stride), shapes = _conv_shapes(layer)
    _compile(one_chip, lambda x, v, i: conv2d_fused_pallas(
        x, v, i, kh=k, kw=k, stride=stride, pad=k // 2), *shapes)


@pytest.mark.parametrize("layer", ["s3.c2", "s2.c2.b4", "stem.b8"])
def test_conv2d_fused_banded_compiles(one_chip, layer):
    (k, stride), shapes = _conv_shapes(layer)
    _compile(one_chip, lambda x, v, i: conv2d_fused_banded_pallas(
        x, v, i, kh=k, kw=k, stride=stride, pad=k // 2), *shapes)


# paged attention: (batch, Sq, heads, kv_heads, head_dim, page_size,
# pages_per_block, block_q, table rows); "gen256" is the benchmark's decode
# step at the default geometry
_PS, _PPB = (dict(PAGED_ATTN_GEOMETRY[0])[k] for k in ("ps", "ppb"))
PAGED = {
    "qwen2-0.5b": (8, 1, 14, 2, 64, 16, 8, 8, 256),
    "qwen2-7b": (8, 1, 28, 4, 128, 16, 8, 8, 256),
    "qwen2-0.5b_ps32_bq16": (8, 40, 14, 2, 64, 32, 4, 16, 256),
    "qwen2-0.5b_gen256": (64, 1, 14, 2, 64, _PS, _PPB, 8, 384),
}


@pytest.mark.parametrize("case", sorted(PAGED))
def test_paged_attention_compiles(one_chip, case):
    b, sq, h, kv, d, ps, ppb, bq, rows = PAGED[case]
    n_max = rows // ps
    pages = (24, b * n_max + 1, ps, kv * d)
    _compile(one_chip, lambda *a: paged_attention_pallas(
        *a, page_size=ps, pages_per_block=ppb, block_q=bq),
        ((b, sq, h, d), BF16), ((b, sq, kv, d), BF16), ((b, sq, kv, d), BF16),
        (pages, BF16), (pages, BF16), ((b, n_max), I32), ((b,), I32),
        ((), I32))


def test_flash_attention_compiles(one_chip):
    _compile(one_chip, flash_attention_pallas, ((14, 512, 64), BF16),
             ((14, 512, 64), BF16), ((14, 512, 64), BF16))


# one small case per kernel family (``pltpu_compat.KERNEL_FAMILIES``)
TAGGED = {
    "colwise_nm": (colwise_nm_matmul_pallas,
                   [((8, 896), BF16), ((1, 448, 128), BF16), ((1, 448), I32)]),
    "im2col_pack": (lambda x: im2col_pack_pallas(x, 3, 3, 1, 1),
                    [((256, 1, 14, 14), BF16)]),
    "conv_fused": (lambda x, v, i: conv2d_fused_pallas(
        x, v, i, kh=3, kw=3, stride=1, pad=1), _conv_shapes("s3.c2")[1]),
    "conv_fused_banded": (lambda x, v, i: conv2d_fused_banded_pallas(
        x, v, i, kh=3, kw=3, stride=1, pad=1), _conv_shapes("s3.c2")[1]),
    "paged_attn": (lambda *a: paged_attention_pallas(
        *a, page_size=16, pages_per_block=8),
        [((8, 1, 14, 64), BF16), ((8, 1, 2, 64), BF16),
         ((8, 1, 2, 64), BF16), ((2, 129, 16, 128), BF16),
         ((2, 129, 16, 128), BF16), ((8, 16), I32), ((8,), I32),
         ((), I32)]),
    "flash_attn": (flash_attention_pallas,
                   [((14, 512, 64), BF16)] * 3),
}


@pytest.mark.parametrize("family", sorted(TAGGED))
def test_compiled_kernel_carries_its_family_tag(one_chip, family):
    """The compiled custom call carries the ``pallas_call`` metadata as
    ``frontend_attributes={kernel_metadata=...}``: the stable name a
    profiler trace shows for the kernel."""
    fn, shapes = TAGGED[family]
    text = _compile(one_chip, fn, *shapes)
    tags = re.findall(r'kernel_metadata=\{\s*"kernel":\s*"(\w+)"', text)
    assert tags and set(tags) == {family}
