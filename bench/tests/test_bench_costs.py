"""Closed-form work of a decode step against the configuration's sizes."""

import json

from conftest import BENCH
from harness import costs

CFG = json.loads((BENCH / "configs" / "phi3-mini-3.8b.json").read_text())


def test_decode_counts_match_the_configuration():
    cfg = CFG
    d, L, ff, v = 3072, 32, 8192, 32064
    layer = 4 * d * d + 3 * d * ff
    assert costs.matmul_params(cfg) == L * layer + d * v
    assert costs.params(cfg) == L * layer + 2 * d * v + (2 * L + 1) * d
    # The published model's parameter count (3.82 B).
    assert costs.params(cfg) == 3_821_079_552
    assert costs.flops_per_token(cfg) == 2 * (L * layer + d * v)
    kv = 2 * L * 4 * 1024 * 32 * 96 * 2
    assert costs.kv_bytes(cfg, 4, 1024) == kv == 1_610_612_736
    assert costs.decode_step_bytes(cfg, 4, 1024) == \
        2 * (L * layer + d * v) + 4 * (2 * L + 1) * d + kv


def test_counts_agree_with_the_programs_own_parameter_count():
    from repro.configs import get
    cfg = CFG
    assert costs.params(cfg) == get("phi3-mini-3.8b").param_count() + \
        (2 * cfg["num_hidden_layers"] + 1) * cfg["hidden_size"]
