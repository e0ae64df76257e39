"""The chat cell's ``correct``: a sound run passes; the control (the
reference with fp8 weights in the program's place) and planted faults
fail.  Runs the whole driver on the CPU with a tiny decoder of the
served layout."""

import numpy as np
import pytest

from conftest import run_small


def test_sound_run_is_correct(small_decode_cell):
    line, ctx = run_small(small_decode_cell)
    assert line["correct"], line["checks"]
    assert ctx.details["served_tokens"] > 0


def test_control_is_not_correct(small_decode_cell):
    sound, _ = run_small(small_decode_cell)
    control, ctx = run_small(small_decode_cell, control=True)
    gap = sound["checks"]["served_token_gap"]["value"]
    assert ctx.details["control_gap"] >= 3 * gap
    assert not control["correct"]


def _unchanged_state(monkeypatch):
    from repro.serve import engine as eng
    orig = eng.ServeEngine.__init__

    def init(self, *a, **kw):
        orig(self, *a, **kw)
        decode = self._decode
        self._decode = lambda p, cache, b: (decode(p, cache, b)[0], cache)
    monkeypatch.setattr(eng.ServeEngine, "__init__", init)


def _half_batch(monkeypatch):
    from repro.serve import engine as eng
    orig = eng.ServeEngine.__init__

    def init(self, *a, **kw):
        orig(self, *a, **kw)
        decode = self._decode

        def half(p, cache, b):
            logits, cache = decode(p, cache, b)
            h = logits.shape[0] // 2
            return logits.at[h:].set(logits[:h].mean(0)), cache
        self._decode = half
    monkeypatch.setattr(eng.ServeEngine, "__init__", init)


def _altered_token(monkeypatch):
    from repro.serve import engine as eng
    orig = eng.ServeEngine.step

    def step(self):
        emitted = orig(self)
        for req, _tok in emitted:
            if len(req.out) == 3:
                req.out[-1] = (req.out[-1] + 1) % self.cfg.vocab_size
        return emitted
    monkeypatch.setattr(eng.ServeEngine, "step", step)


@pytest.mark.parametrize("plant", [_unchanged_state, _half_batch,
                                   _altered_token],
                         ids=["state-unchanged", "half-the-batch",
                              "token-altered"])
def test_planted_faults_are_not_correct(monkeypatch, small_decode_cell,
                                        plant):
    plant(monkeypatch)
    line, _ = run_small(small_decode_cell)
    assert not line["correct"]
    assert line["checks"]["served_token_gap"]["value"] > \
        line["checks"]["served_token_gap"]["limit"]
