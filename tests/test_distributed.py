"""Multi-device execution tests (8 emulated host devices via subprocess —
the main test process must keep seeing 1 device per the assignment).

Covers: ring collective matmul numerics, a real sharded sparse train step
(pjit EXECUTION, not just compile), and cross-'pod' gradient compression
inside shard_map.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


class TestDistributed:
    def test_ring_collective_matmul(self):
        out = run_with_devices("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_mesh
            from repro.sharding.collective_matmul import ring_allgather_matmul
            mesh = make_mesh((8,), ("model",))
            x = jax.random.normal(jax.random.PRNGKey(0), (16, 64))
            w = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
            with mesh:
                y = ring_allgather_matmul(x, w, mesh, axis="model")
            np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                                       rtol=2e-5, atol=2e-5)
            print("RING_OK")
        """)
        assert "RING_OK" in out

    def test_sharded_sparse_train_step_executes(self):
        """One REAL train step of a compressed sparse model on a 2x4 mesh —
        validates the whole sharded path executes, not just compiles."""
        out = run_with_devices("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs import smoke_config
            from repro.core.pruning import SparsityConfig
            from repro.launch import steps as steps_mod
            from repro.launch.mesh import make_mesh, mesh_tp
            from repro.models import registry as reg
            from repro.optim import AdamWConfig, adamw_init
            from repro.sharding import ShardingCtx, use_ctx

            mesh = make_mesh((2, 4), ("data", "model"))
            scfg = SparsityConfig(0.5, m=None, tile=None, format="compressed_xla",
                                  min_dim=32, shard_local_reduce=True, reduce_groups=4)
            cfg = smoke_config("qwen2-7b").with_(
                n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                d_ff=128, vocab_size=256, sparsity=scfg, tp=4, dp=2,
                attn_impl="chunked", attn_chunk=8)
            with use_ctx(ShardingCtx(mesh=mesh)), mesh:
                params, specs = reg.init_params(cfg, jax.random.PRNGKey(0))
                opt = adamw_init(params)
                step = steps_mod.make_train_step(cfg, AdamWConfig(lr=1e-3))
                in_sh, out_sh = steps_mod.train_shardings(
                    cfg, mesh, params, specs, {"tokens": jnp.ones((8, 32), jnp.int32)})
                f = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                            donate_argnums=(0, 1))
                batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                                       (8, 32), 0, 256)}
                p2, o2, m = f(params, opt, batch)
                loss = float(m["loss"])
                assert np.isfinite(loss), loss
                p3, o3, m2 = f(p2, o2, batch)
                assert float(m2["loss"]) < loss  # same batch twice -> improves
            print("SHARDED_STEP_OK", loss)
        """)
        assert "SHARDED_STEP_OK" in out

    def test_crosspod_compressed_psum(self):
        out = run_with_devices("""
            import functools
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import PartitionSpec as P
            from repro.launch.mesh import make_mesh
            from repro.optim.grad_compress import crosspod_psum_compressed
            mesh = make_mesh((4, 2), ("pod", "data"))
            g = jax.random.normal(jax.random.PRNGKey(0), (4, 256))
            e = jnp.zeros((4, 256))

            f = jax.shard_map(
                functools.partial(crosspod_psum_compressed, axis="pod"),
                mesh=mesh, in_specs=(P("pod", None), P("pod", None)),
                out_specs=(P("pod", None), P("pod", None)), check_vma=False)
            with mesh:
                reduced, err = f(g, e)
            # every pod-shard of `reduced` equals the true sum up to int8 error
            true = np.asarray(g).reshape(4, 1, 256).sum(axis=0)
            got = np.asarray(reduced).reshape(4, 1, 256)
            scale = np.abs(np.asarray(g)).max() / 127 * 4
            for i in range(4):
                np.testing.assert_allclose(got[i], true, atol=4 * scale)
            print("COMPRESS_OK")
        """)
        assert "COMPRESS_OK" in out


def test_shard_map_moe_matches_auto():
    """Manual shard_map MoE == GSPMD-auto MoE when capacity is ample
    (identical routing, no drops)."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import smoke_config
        from repro.models.moe import moe_apply, moe_apply_shard_map, moe_init
        from repro.core.sparse_linear import unbox_tree
        from repro.launch.mesh import make_mesh
        from repro.sharding import ShardingCtx, use_ctx

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = smoke_config("olmoe-1b-7b").with_(
            d_model=64, d_ff=96, n_experts=8, top_k=2, capacity_factor=8.0,
            tp=4, dp=2, moe_impl="shard_map")
        params, _ = unbox_tree(moe_init(jax.random.PRNGKey(0), cfg))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64))
        with use_ctx(ShardingCtx(mesh=mesh)), mesh:
            y_manual, aux_m = jax.jit(
                lambda p, xx: moe_apply_shard_map(p, cfg, xx))(params, x)
            y_auto, aux_a = jax.jit(
                lambda p, xx: moe_apply(p, cfg, xx))(params, x)
        np.testing.assert_allclose(np.asarray(y_manual), np.asarray(y_auto),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(float(aux_m), float(aux_a), rtol=1e-3)
        print("MOE_MANUAL_OK")
    """)
    assert "MOE_MANUAL_OK" in out


_TRAIN_PHASE = """
    import os, sys
    sys.path.insert(0, {repo!r})
    if {force!r}:
        os.environ["REPRO_DISPATCH_FORCE"] = {force!r}
    import jax
    import chip_smoke as cs
    from repro import obs
    from repro.configs import smoke_config

    obs.set_enabled(True)
    cfg = cs.train_config(
        smoke_config("qwen2-7b").with_(
            n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
            d_ff=128, vocab_size=256),
        min_dim=32, tile=16)
    chk = cs.Checks()
    cs.train_phase(chk, cfg, jax.devices()[:4], batch=4, seq=32,
                   expect_backend={backend!r})
    assert not chk.failed, chk.failed
    print("TRAIN_PHASE_OK")
"""


@pytest.mark.parametrize("force,backend", [
    ("", "xla"),                    # dispatched as on the CPU
    ("compressed_pallas", "pallas"),  # interpret mode, inside shard_map
])
def test_chip_smoke_train_phase_rehearsal(force, backend):
    """``chip_smoke.py --chips 4`` at smoke widths on 4 virtual CPU devices:
    the sharded REDUCE-format train step on a (data=1, model=4) mesh runs
    3 steps, its loss is finite and falls, and step 0's loss and logits
    match a bf16 forward of the same parameters on device 0 alone.  The
    compressed linears run per shard in ``jax.shard_map``, where the forced
    Pallas candidate's key carries no mesh and stays feasible."""
    out = run_with_devices(_TRAIN_PHASE.format(repo=str(REPO), force=force,
                                               backend=backend), n=4)
    assert "TRAIN_PHASE_OK" in out
