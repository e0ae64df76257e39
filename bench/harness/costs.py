"""Closed-form work of one decode step of a dense decoder, from the sizes
in the configuration's file (Hugging Face key names).

The counts are what the algorithm needs, so a share of a peak built on
them cannot pass 100 %:

* bytes: every layer weight and the output head once per step (the
  embedding table is gathered, a few rows, and is left out), plus the
  whole key/value cache the step attends over, at ``slots`` x
  ``max_len`` positions;
* FLOPs: 2 per multiply-add of every weight matrix except the embedding
  table, per token fed; attention's own products are left out.
"""

from __future__ import annotations


def sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return dict(d=d, layers=cfg["num_hidden_layers"], heads=h,
                kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg.get("head_dim") or d // h,
                ff=cfg["intermediate_size"], vocab=cfg["vocab_size"])


def matmul_params(cfg: dict) -> int:
    """Weights of every matrix multiply: attention, MLP and output head."""
    s = sizes(cfg)
    d, hd = s["d"], s["head_dim"]
    attn = d * s["heads"] * hd * 2 + d * s["kv_heads"] * hd * 2
    mlp = 3 * d * s["ff"]
    return s["layers"] * (attn + mlp) + d * s["vocab"]


def params(cfg: dict) -> int:
    """Every parameter: matrices, embedding table and norm scales."""
    s = sizes(cfg)
    norms = (2 * s["layers"] + 1) * s["d"]
    return matmul_params(cfg) + s["vocab"] * s["d"] + norms


def kv_bytes(cfg: dict, slots: int, max_len: int, bytes_per: int = 2) -> int:
    s = sizes(cfg)
    return (2 * s["layers"] * slots * max_len * s["kv_heads"]
            * s["head_dim"] * bytes_per)


def decode_step_bytes(cfg: dict, slots: int, max_len: int,
                      bytes_per: int = 2) -> int:
    s = sizes(cfg)
    norms = (2 * s["layers"] + 1) * s["d"] * 4          # float32 scales
    return (matmul_params(cfg) * bytes_per + norms
            + kv_bytes(cfg, slots, max_len, bytes_per))


def flops_per_token(cfg: dict) -> int:
    return 2 * matmul_params(cfg)
