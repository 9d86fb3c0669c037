"""The ResNet cell's comparison at a size the CPU holds: the program passes
it, the fp8 control fails it, and so does each fault planted in the timed
path (an answer altered where it is produced; half of the batch left out)."""
import jax.numpy as jnp
import pytest

from chipbench.tests import tiny

NAME = "resnet18-c50.b32"


def _cell():
    cfg, tr = tiny.tiny_resnet()
    return tiny.cell(NAME, cfg, tr, tiny.limits(NAME), per_layer=False)


def test_program_passes_and_control_fails():
    res = tiny.run_tiny(_cell(), control=True)
    lim = res["compared"]["logits_rel_err"]["limit"]
    assert res["correct"], res["compared"]
    assert res["control"]["logits_rel_err"] > lim
    assert res["metrics"]["images_per_s"]["value"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0


def _altered(orig):
    def apply(params, cfg, x, **kw):
        y = orig(params, cfg, x, **kw)
        return y.at[0, 0].add(10.0 * jnp.abs(y).max())
    return apply


def _half_batch(orig):
    def apply(params, cfg, x, **kw):
        b = x.shape[1]
        y = orig(params, cfg, x[:, :b // 2], **kw)
        return jnp.concatenate([y, jnp.zeros_like(y)], axis=0)
    return apply


@pytest.mark.parametrize("fault", [_altered, _half_batch],
                         ids=["answer_altered", "half_batch_left_out"])
def test_fault_is_not_correct(monkeypatch, fault):
    from repro.models import vision

    monkeypatch.setattr(vision, "vision_apply", fault(vision.vision_apply))
    res = tiny.run_tiny(_cell(), seconds=0.5)
    assert not res["correct"], res["compared"]


def test_traced_run_builds_its_result_line():
    """``--trace 1`` path end to end on the CPU: per-layer readers, device
    busy time and the breakdown (the CPU trace has no TPU plane, so the
    device readers find nothing and stay silent)."""
    import types

    import jax

    from chipbench import run

    args = types.SimpleNamespace(seed=5, seconds=0.3, trace=1, control=False)
    cell = tiny.cell(NAME, *tiny.tiny_resnet(), tiny.limits(NAME))
    res = run.run_cell(cell, args, jax.devices()[:1])
    assert res["correct"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert res["device"]["window_s"] > 0
    assert "conv_roofline.vision" not in res["metrics"]
    assert "setup_s" not in res["metrics"]
    assert list(res)[-1] == "compared"


def test_program_recording_is_off_in_the_window(monkeypatch, capsys):
    """Set-up records the program's dispatch events (reported on earlier
    lines); the window runs with the program's event recording off, as
    the program runs by default."""
    from repro import obs
    from repro.models import vision

    seen = []
    orig = vision.vision_apply

    def apply(*a, **kw):
        seen.append(obs.enabled())
        return orig(*a, **kw)

    monkeypatch.setattr(vision, "vision_apply", apply)
    res = tiny.run_tiny(_cell(), seconds=0.3)
    out = capsys.readouterr().out
    assert res["correct"]
    assert seen[0] is True and not obs.enabled()
    assert "dispatch: op=conv" in out
    assert "counter: dispatch.quarantine = 0" in out
    assert "dispatch: quarantined after the window: none" in out
