"""The serving cell's comparison at a size the CPU holds: the program
passes it, the fp8 control fails it, and so does each fault planted in the
timed path (a token altered where it is sampled; a step that leaves its
KV state unchanged)."""
import pytest

from chipbench.tests import tiny

NAME = "qwen2-0.5b-c50.gen256"
#: the tiny cell's own limit: its program reads about 0.006 and its fp8
#: control about 0.08, where the committed limit is set from the readings
#: at the cell's size on the chip (program up to 0.045, control from 0.5)
TINY_LIMITS = {"logit_gap": 0.06}


def _cell():
    cfg, tr = tiny.tiny_qwen2()
    return tiny.cell(NAME, cfg, tr, TINY_LIMITS, per_layer=False)


def test_program_passes_and_control_fails():
    res = tiny.run_tiny(_cell(), control=True)
    lim = res["compared"]["logit_gap"]["limit"]
    assert res["correct"], res["compared"]
    assert res["control"]["logit_gap"] > lim
    m = res["metrics"]
    assert m["output_tokens_per_s"]["value"] > 0 and m["itl_p95_ms"]["value"] > 0


def _token_altered(monkeypatch):
    from repro.serve.engine import Engine

    orig = Engine.sample
    calls = {"n": 0}

    def sample(self, logits, key):
        tok = orig(self, logits, key)
        calls["n"] += 1
        if calls["n"] == 20:  # one token of each request, mid-generation
            tok = (tok + 1) % self.cfg.vocab_size
        return tok

    monkeypatch.setattr(Engine, "sample", sample)


def _state_unchanged(monkeypatch):
    from repro.models import attention

    monkeypatch.setattr(attention, "paged_cache_write",
                        lambda ck, cv, kn, vn, rows: (ck, cv))


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    res = tiny.run_tiny(_cell(), seconds=0.5)
    assert not res["correct"], res["compared"]


def test_waves_turn_over_without_compiling():
    """With one budget for every request, waves that retire inside the
    window admit the next wave in a packed prefill that set-up compiled:
    nothing is lowered inside the window."""
    import types

    import jax

    from chipbench import run

    cfg, tr = tiny.tiny_qwen2()
    tr.update(requests=8 * 60, output_tokens=5, warmup_iterations=3)
    cell = tiny.cell(NAME, cfg, tr, tiny.limits(NAME))
    args = types.SimpleNamespace(seed=11, seconds=1.0, trace=1, control=False)
    res = run.run_cell(cell, args, jax.devices()[:1])
    assert res["correct"], res["compared"]
    assert res["attempted"] > tr["slots"]  # a wave turned over in the window
    assert res["metrics"]["compiles_in_window.serve"]["value"] == 0
