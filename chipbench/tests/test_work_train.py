"""The sharded finetune step's operations from shapes, against hand counts
at the 8-layer qwen2-7b shapes of ``configs/qwen2-7b-l8-c50.json``."""
from chipbench import work_train
from chipbench.tests import tiny

QWEN7 = tiny.load("configs/qwen2-7b-l8-c50.json")
KERNEL = ('%closed_call.7 = bf16[2048,896]{1,0:T(8,128)(2,1)} custom-call('
          'bf16[2048,3584]{1,0} %x, s32[98,1,128]{2,1,0} %i, '
          'bf16[7,1792,128]{2,1,0} %v), custom_call_target="tpu_custom_call",'
          ' operand_layout_constraints={bf16[2048,3584]{1,0}, '
          's32[98,1,128]{2,1,0}, bf16[7,1792,128]{2,1,0}}, '
          'frontend_attributes={kernel_metadata={\n"kernel":"colwise_nm"\n}}')


def test_shard_linears():
    """Each of 4 shards holds whole 128-wide tiles of q, k, v, gate and up
    and every kept row; o and down are row-parallel (REDUCE)."""
    got = {s.name: (s.d_in, s.d_out, s.k_kept, s.n_tiles)
           for s in work_train.shard_linears(QWEN7)}
    assert got == {"q": (3584, 896, 1792, 7), "k": (3584, 128, 1792, 1),
                   "v": (3584, 128, 1792, 1), "gate": (3584, 4736, 1792, 37),
                   "up": (3584, 4736, 1792, 37)}


def test_shard_linear_ops():
    ops = work_train.shard_linear_ops(QWEN7, 2048)
    assert len(ops) == 5 * 8
    # q: 2*2048*1792*896 FLOPs; x 2048*3584, values 1792*896 and y
    # 2048*896 bf16, 7 tiles of 1792 int32 indices
    assert ops[0] == (2 * 2048 * 1792 * 896,
                      (2048 * 3584 + 1792 * 896 + 2048 * 896) * 2
                      + 7 * 1792 * 4)
    assert ops[3][0] == 2 * 2048 * 1792 * 4736


def test_train_token_flops():
    # kept weights a layer: q and o 1792*3584, k and v 1792*512, gate and
    # up 1792*18944, down 9472*3584; 2 FLOPs each; causal attention
    # 4*28*128 FLOPs a position over (512+1)/2 positions; the head
    # 2*3584*152064; a training token is three forward passes
    kept = 1792 * 3584 * 2 + 1792 * 512 * 2 + 1792 * 18944 * 2 + 9472 * 3584
    fwd = 8 * (2 * kept + 4 * 28 * 128 * 513 / 2) + 2 * 3584 * 152064
    assert work_train.forward_token_flops(QWEN7, 512) == fwd
    assert work_train.train_token_flops(QWEN7, 512) == 3 * fwd
    assert 8.9e9 < 3 * fwd < 9.0e9


def test_kernel_ops_from_hlo_text():
    assert work_train.kernel_shapes(KERNEL) == (2048, 3584, 7, 1792, 128, 2)
    assert work_train.kernel_ops(KERNEL) == work_train.shard_linear_ops(
        QWEN7, 2048)[0]
    assert work_train.kernel_ops(KERNEL.replace("s32[98,1,128]{2,1,0}, ",
                                                "")) is None
