"""Operations and bytes from shapes, against hand counts at the
qwen2-0.5b, ResNet-18 and qwen2-7b shapes; the peaks table."""
import pytest

from chipbench import peaks, work
from chipbench.tests import tiny

QWEN = tiny.load("configs/qwen2-0.5b-c50.json")
RESNET = tiny.load("configs/resnet18-c50.json")
# qwen2-7b at its published widths, as the sharded finetune runs it
QWEN7 = dict(QWEN, hidden_size=3584, intermediate_size=18944,
             num_attention_heads=28, num_key_value_heads=4,
             num_hidden_layers=8, vocab_size=152064,
             sparsity=dict(QWEN["sparsity"], tile=128))


def test_qwen2_0_5b_linears():
    lins = {l.name: l for l in work.lm_linears(QWEN)}
    # min_dim 512 keeps the 896 -> 128 k/v projections dense
    assert not lins["k"].compressed and not lins["v"].compressed
    assert lins["q"].k_kept == 448 and lins["q"].n_tiles == 1
    assert lins["down"].k_kept == 2432
    ops = work.lm_decode_linear_ops(QWEN, 64)
    assert len(ops) == 5 * 24
    # q: 2*64*448*896 FLOPs; x 64*896*2, values 448*896*2, idx 448*4,
    # y 64*896*2 bytes
    assert ops[0] == (51380224, 114688 + 802816 + 1792 + 114688)


def test_qwen2_token_flops():
    # per layer: q 448*896 + k,v 896*128 + o 448*896 + gate,up 448*4864
    # + down 2432*896 kept weights, 2 FLOPs each; attention 4*14*64*(n+1);
    # unembed 2*896*151936
    per_layer = 2 * (448 * 896 * 2 + 896 * 128 * 2 + 448 * 4864 * 2
                     + 2432 * 896)
    want = 24 * (per_layer + 4 * 14 * 64 * 101) + 2 * 896 * 151936
    assert work.lm_token_flops(QWEN, 100) == want


def test_qwen2_7b_tiled_linears():
    lins = {l.name: l for l in work.lm_linears(QWEN7)}
    assert lins["q"].n_tiles == 3584 // 128 and lins["q"].k_kept == 1792
    assert lins["down"].k_kept == 9472 and lins["down"].n_tiles == 28
    f, b = work.colwise_linear(2048, 3584, 18944, 1792, 148)
    assert f == 2 * 2048 * 1792 * 18944
    assert b == (2048 * 3584 + 1792 * 18944 + 2048 * 18944) * 2 \
        + 148 * 1792 * 4


def test_paged_attention_counts():
    f, b = work.paged_decode_attention([0, 10], heads=14, kv_heads=2,
                                       head_dim=64)
    assert f == 4 * 14 * 64 * (1 + 11)
    assert b == 2 * 10 * 2 * 64 * 2 + 2 * 2 * 2 * 64 * 2 + 2 * 2 * 14 * 64 * 2


def test_resnet18_layers():
    convs = work.resnet_convs(RESNET)
    assert len(convs) == 1 + 8 * 2 + 3  # stem, 16 convs, 3 projections
    assert [c.h for c in convs if c.name.endswith("conv2")] == \
        [56, 56, 28, 28, 14, 14, 7, 7]
    assert not convs[0].compressed and all(c.compressed for c in convs[1:])
    ops = work.resnet_conv_ops(RESNET, 32)
    # blocks[0]/conv1: 64 -> 64, 3x3 at 56x56; kept 288 of 576 rows
    c = convs[1]
    assert (c.c, c.o, c.k_kept) == (64, 64, 288)
    assert ops[0][0] == 2 * 32 * 56 * 56 * 288 * 64
    assert ops[0][1] == (64 * 32 * 56 * 56 * 2 + 288 * 64 * 2 + 288 * 4
                         + 64 * 32 * 56 * 56 * 2)
    # ResNet-18's dense convs do about 1.8 GMAC at these maps; half kept
    dense_macs = sum(cv.k * cv.k * cv.c * cv.o * work.out_size(
        cv.h, cv.k, cv.stride, cv.pad) ** 2 for cv in convs)
    assert 1.7e9 < dense_macs < 1.9e9
    assert 0.45 < work.resnet_image_flops(RESNET) / (2 * dense_macs) < 0.55


def test_peaks_table():
    pk = peaks.peaks_for("TPU v5 lite")
    assert pk.flops == 197e12 and pk.hbm_bw == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
    assert peaks.least_time_s(197e12, 0, pk) == 1.0
    assert peaks.least_time_s(0, 819e9, pk) == 1.0
