"""The engine cells' ``correct``: a sound run passes; the control (the
reference's answer for a neighbouring machine point in the program's
place) and planted faults fail.  Runs the whole driver on the CPU at the
kernels' reduced sizes."""

import numpy as np
import pytest

from conftest import run_small


def test_sound_run_is_correct(small_engine_cell):
    line, ctx = run_small(small_engine_cell)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1
    assert len(ctx.details["points"]) == len(
        small_engine_cell.traffic["kernels"])


def test_control_is_not_correct(small_engine_cell):
    line, _ = run_small(small_engine_cell, control=True)
    assert not line["correct"]
    assert line["checks"]["exact_mismatches"]["value"] > 0


def _stale(orig):
    prev = {}

    def run(self, sweep):
        res = orig(self, sweep)
        out, prev["res"] = prev.get("res", res), res
        return out
    return run


def _half(orig):
    def run(self, sweep):
        res = orig(self, sweep)
        h = len(sweep.kernels) // 2
        for k, v in res.data.items():
            if v.dtype != bool:
                v[h:] = v[:h].mean(axis=0).astype(v.dtype)
        return res
    return run


def _altered(orig):
    def run(self, sweep):
        res = orig(self, sweep)
        res.data["cycles"] += np.ones_like(res.data["cycles"])
        return res
    return run


@pytest.mark.parametrize("fault", [_stale, _half, _altered],
                         ids=["state-unchanged", "half-the-batch",
                              "answer-altered"])
def test_planted_faults_are_not_correct(monkeypatch, small_engine_cell,
                                        fault):
    from repro import api
    monkeypatch.setattr(api.Session, "run", fault(api.Session.run))
    line, _ = run_small(small_engine_cell)
    assert not line["correct"]
