"""Validate the loop-aware HLO analyzer against XLA's own cost analysis on
loop-free graphs, and against hand-computed trip-count math on scans."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.hlo_analyzer import HloCost, analyze_hlo, xla_cost_analysis


def compiled_text(f, *args):
    c = jax.jit(f).lower(*args).compile()
    return c, c.as_text()


class TestHloAnalyzer:
    def test_plain_matmul_flops(self):
        x = jnp.zeros((128, 256), jnp.float32)
        w = jnp.zeros((256, 64), jnp.float32)
        c, txt = compiled_text(lambda a, b: a @ b, x, w)
        got = analyze_hlo(txt)
        expect = 2 * 128 * 256 * 64
        assert got["flops"] == pytest.approx(expect, rel=0.01)
        # agrees with XLA's own count on a loop-free graph
        assert got["flops"] == pytest.approx(xla_cost_analysis(c)["flops"], rel=0.05)

    def test_batched_dot(self):
        x = jnp.zeros((4, 32, 16))
        w = jnp.zeros((4, 16, 8))
        _, txt = compiled_text(lambda a, b: jnp.einsum("bik,bkj->bij", a, b), x, w)
        got = analyze_hlo(txt)
        assert got["flops"] == pytest.approx(2 * 4 * 32 * 16 * 8, rel=0.01)

    def test_scan_multiplies_trip_count(self):
        x = jnp.zeros((64, 64))
        w = jnp.zeros((64, 64))

        def f(x, w):
            def body(c, _):
                return jnp.tanh(c @ w), None
            out, _ = jax.lax.scan(body, x, None, length=7)
            return out

        c, txt = compiled_text(f, x, w)
        got = analyze_hlo(txt)
        per_iter = 2 * 64 * 64 * 64
        assert got["flops"] >= 7 * per_iter
        assert got["flops"] < 7 * per_iter * 1.5  # elementwise slack
        # XLA undercounts — that's the bug this module exists to fix
        assert xla_cost_analysis(c)["flops"] < 2 * per_iter

    def test_nested_scan(self):
        def f(x, w):
            def outer(c, _):
                def inner(c2, _):
                    return c2 @ w, None
                c2, _ = jax.lax.scan(inner, c, None, length=3)
                return c2, None
            out, _ = jax.lax.scan(outer, x, None, length=5)
            return out

        x = jnp.zeros((32, 32))
        w = jnp.zeros((32, 32))
        _, txt = compiled_text(f, x, w)
        got = analyze_hlo(txt)
        per = 2 * 32 * 32 * 32
        assert got["flops"] >= 15 * per
        assert got["flops"] < 15 * per * 1.5

    def test_bytes_positive_and_fusion_boundary(self):
        x = jnp.zeros((1024, 1024))
        _, txt = compiled_text(lambda a: jnp.tanh(a) * 2 + 1, x)
        got = analyze_hlo(txt)
        # boundary traffic should be ~ read + write of the array, not 4 passes
        nbytes = 1024 * 1024 * 4
        assert nbytes * 1.5 <= got["bytes"] <= nbytes * 6


def test_roofline_peaks_keyed_by_device_kind():
    from repro.roofline.analysis import DEVICE_PEAKS, Roofline, peaks_for

    v5e = peaks_for("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    rl = Roofline(flops=197e12, hlo_bytes=819e9, collective_bytes=0.0,
                  model_flops=197e12, chips=1, device_kind="TPU v5 lite")
    assert rl.t_compute == pytest.approx(1.0)
    assert rl.t_memory == pytest.approx(1.0)
    with pytest.raises(KeyError, match="no peaks recorded"):
        peaks_for("TPU v9 imaginary")
    assert "TPU v9 imaginary" not in DEVICE_PEAKS
