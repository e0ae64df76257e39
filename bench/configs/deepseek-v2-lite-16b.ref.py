"""Plain reference of DeepSeek-V2-Lite (the configuration beside this file).

Written from the published architecture (DeepSeek-V2, arXiv:2405.04434,
and the model's ``config.json`` and modelling code), not from the program.
A decoder layer is RMSNorm -> multi-head latent attention (MLA) ->
residual -> RMSNorm -> MLP -> residual; the first
``first_k_dense_replace`` layers have a dense SwiGLU MLP, every later one
a mixture of experts.

* MLA without a query compression (``q_lora_rank`` null): ``q = x Wq``
  splits per head into ``nope`` and ``rope`` parts; ``x Wkv_a`` gives the
  latent ``c_kv`` (RMS-normalised) and one rope key shared by all heads;
  ``c_kv Wkv_b`` decompresses per head into ``k_nope`` and ``v``; rotary
  positions (YaRN, ``rope_scaling``) on the rope parts; softmax scale
  ``q_head_dim ** -0.5 * mscale ** 2`` with YaRN's ``mscale_all_dim``.
  Here MLA runs in its naive decompressed form, and a decode step appends
  the new token's latent and rope key to the cache and attends over it.
* MoE: softmax router scores, the top ``num_experts_per_tok`` experts
  weighted by their scores without renormalising (``norm_topk_prob``
  false) times ``routed_scaling_factor``, SwiGLU experts, plus the shared
  experts as one SwiGLU MLP of ``n_shared_experts * moe_intermediate_size``.

Departures: the weights are random (drawn here from a key); rotary
embedding is rotate-half without the published code's interleave of the
rope dimensions, a fixed permutation of ``Wq``'s and ``Wkv_a``'s rope
columns that random weights absorb.  Everything runs in float32 at
``highest`` matmul precision.

Also here:

* ``routing_loads``: the fixed routing draw of a lowered decode step;
* ``lowered_summary``: the kernels, units and instances that a lowering
  of one decode step into RVV tile programs must consist of, from the
  published sizes and the routing draw.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def sizes(cfg: dict) -> dict:
    return dict(
        d=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
        heads=cfg["num_attention_heads"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], latent=cfg["kv_lora_rank"],
        vdim=cfg["v_head_dim"], ff=cfg["intermediate_size"],
        moe_ff=cfg["moe_intermediate_size"], experts=cfg["n_routed_experts"],
        shared=cfg["n_shared_experts"], top_k=cfg["num_experts_per_tok"],
        dense=cfg["first_k_dense_replace"], vocab=cfg["vocab_size"],
        eps=cfg["rms_norm_eps"], theta=cfg["rope_theta"],
        scaling=cfg["rope_scaling"],
        routed_scale=cfg["routed_scaling_factor"])


# ---------------------------------------------------------------------------
# The lowered decode step.
# ---------------------------------------------------------------------------


def routing_loads(cfg: dict) -> list:
    """Tokens per routed expert in every MoE layer of the decode step the
    configuration's ``decode`` block states.  One generator,
    ``numpy.random.default_rng(routing_seed)``, serves the layers in
    order: a permutation ranks the experts, popularity is ``1 / (rank +
    1) ** routing_zipf`` normalised, and each token in turn draws its
    ``num_experts_per_tok`` distinct experts with those weights."""
    s, dec = sizes(cfg), cfg["decode"]
    rng = np.random.default_rng(dec["routing_seed"])
    out = []
    for _ in range(s["layers"] - s["dense"]):
        rank = rng.permutation(s["experts"])
        weights = 1.0 / (rank + 1.0) ** dec["routing_zipf"]
        weights /= weights.sum()
        loads = np.zeros(s["experts"], np.int64)
        for _ in range(dec["batch"]):
            loads[rng.choice(s["experts"], s["top_k"], replace=False,
                             p=weights)] += 1
        out.append(loads)
    return out


def lowered_layers(cfg: dict) -> list:
    """(kernel name, instances) of every matrix multiply and attention of
    one decode step: ``dgemm`` at the step's rows, ``expert`` for shared
    and routed experts (a routed expert at the tokens routed to it, none
    if it gets none), ``mla`` once per layer; the embedding is a gather,
    norms and activations are not lowered."""
    s, dec = sizes(cfg), cfg["decode"]
    b, d, L, H = dec["batch"], s["d"], s["layers"], s["heads"]

    def mm(kind, m, k, n, count):
        return (f"net:{kind}:{m}x{k}x{n}", count)

    mla = (f"net:mla:b{b}:h{H}:n{s['nope']}:r{s['rope']}:l{s['latent']}"
           f":v{s['vdim']}:c{dec['context']}")
    out = [mm("dgemm", b, d, H * (s["nope"] + s["rope"]), L),
           mm("dgemm", b, d, s["latent"], L),
           mm("dgemm", b, d, s["rope"], L),
           (mla, L),
           mm("dgemm", b, H * s["vdim"], d, L),
           mm("dgemm", b, d, s["ff"], 2 * s["dense"]),
           mm("dgemm", b, s["ff"], d, s["dense"]),
           mm("dgemm", b, d, s["vocab"], 1)]
    n_moe = L - s["dense"]
    shared = s["shared"] * s["moe_ff"]
    out += [mm("dgemm", b, d, s["experts"], n_moe),
            mm("expert", b, d, shared, 2 * n_moe),
            mm("expert", b, shared, d, n_moe)]
    for loads in routing_loads(cfg):
        for m in loads[loads > 0]:
            out += [mm("expert", int(m), d, s["moe_ff"], 2),
                    mm("expert", int(m), s["moe_ff"], d, 1)]
    return out


def lowered_summary(cfg: dict) -> dict:
    """Kernels (one per distinct kind and shape), units and instances."""
    units: dict = {}
    for name, count in lowered_layers(cfg):
        units[name] = units.get(name, 0) + count
    return dict(kernels=sorted(units), units=len(units),
                instances=sum(units.values()))


# ---------------------------------------------------------------------------
# One decoder layer's decode step in float32.
# ---------------------------------------------------------------------------


def init_layer(cfg: dict, key, moe: bool = True) -> dict:
    """Random float32 weights of one decoder layer, ~N(0, 1/fan_in);
    norm scales 1 + N(0, 0.1^2)."""
    s = sizes(cfg)
    d, H, dn, dr, r, dv = (s["d"], s["heads"], s["nope"], s["rope"],
                           s["latent"], s["vdim"])
    shapes = {"attn_norm": (d,), "wq": (d, H * (dn + dr)),
              "wkv_a": (d, r + dr), "kv_norm": (r,),
              "wkv_b": (r, H * (dn + dv)), "wo": (H * dv, d),
              "mlp_norm": (d,)}
    if moe:
        e, f = s["experts"], s["moe_ff"]
        fs = s["shared"] * f
        shapes.update(router=(d, e), wg=(e, d, f), wi=(e, d, f),
                      wd=(e, f, d), shared_wg=(d, fs), shared_wi=(d, fs),
                      shared_wd=(fs, d))
    else:
        shapes.update(wg=(d, s["ff"]), wi=(d, s["ff"]), wd=(s["ff"], d))
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        z = jax.random.normal(k, shape, jnp.float32)
        out[name] = (1.0 + 0.1 * z if len(shape) == 1
                     else z / np.sqrt(shape[-2]))
    return out


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _yarn(dim: int, theta: float, rs: dict | None):
    """Inverse frequencies and cos/sin scale of YaRN rotary embedding."""
    pos = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / theta ** pos
    if not rs:
        return extra, 1.0
    factor = rs["factor"]
    inter = extra / factor
    orig = rs["original_max_position_embeddings"]

    def corr(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp                     # 1: extrapolate (high freqs)
    inv = inter * (1 - keep) + extra * keep
    return inv, (_yarn_mscale(factor, rs["mscale"])
                 / _yarn_mscale(factor, rs["mscale_all_dim"]))


def _rope(x, positions, s):
    """Rotate-half rotary embedding of x (..., rope) at ``positions``
    (broadcast against x's leading dims)."""
    inv, msc = _yarn(s["rope"], s["theta"], s["scaling"])
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(
        inv, jnp.float32)
    cos, sin = jnp.cos(ang) * msc, jnp.sin(ang) * msc
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    cos = jnp.concatenate([cos, cos], -1)
    sin = jnp.concatenate([sin, sin], -1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def softmax_scale(cfg: dict) -> float:
    s = sizes(cfg)
    scale = (s["nope"] + s["rope"]) ** -0.5
    rs = s["scaling"]
    if rs:
        scale *= _yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def up_projections(p: dict, cfg: dict):
    """(W_UK, W_UV) per head, (heads, latent, nope) and (heads, latent,
    vdim), cut from ``Wkv_b``."""
    s = sizes(cfg)
    w = p["wkv_b"].reshape(s["latent"], s["heads"], s["nope"] + s["vdim"])
    return (w[..., : s["nope"]].transpose(1, 0, 2),
            w[..., s["nope"]:].transpose(1, 0, 2))


def mla_decode(p: dict, cfg: dict, x, c_kv, k_rope, pos):
    """MLA of one new token per sequence, decompressed.

    x (B, d) the normalised hidden state at position ``pos``; c_kv (B, T,
    latent) and k_rope (B, T, rope) the cache of positions 0..T-1.
    Returns the output (B, d) and, for comparison, the per-head outputs
    before ``Wo`` (B, H, vdim), the queries (B, H, nope), (B, H, rope)
    and the cache with this token appended."""
    s = sizes(cfg)
    b = x.shape[0]
    H, dn, dr, r = s["heads"], s["nope"], s["rope"], s["latent"]
    with jax.default_matmul_precision("highest"):
        q = (x @ p["wq"]).reshape(b, H, dn + dr)
        q_nope = q[..., :dn]
        q_rope = _rope(q[..., dn:], jnp.full((b, 1), pos), s)
        kv = x @ p["wkv_a"]
        c_new = _rms(kv[:, :r], p["kv_norm"], s["eps"])
        kr_new = _rope(kv[:, r:], jnp.full((b,), pos), s)
        c_kv = jnp.concatenate([c_kv, c_new[:, None]], 1)
        k_rope = jnp.concatenate([k_rope, kr_new[:, None]], 1)
        w_uk, w_uv = up_projections(p, cfg)
        k_nope = jnp.einsum("btl,hln->bthn", c_kv, w_uk)
        v = jnp.einsum("btl,hlv->bthv", c_kv, w_uv)
        logits = (jnp.einsum("bhn,bthn->bht", q_nope, k_nope)
                  + jnp.einsum("bhr,btr->bht", q_rope, k_rope))
        probs = jax.nn.softmax(logits * softmax_scale(cfg), axis=-1)
        heads = jnp.einsum("bht,bthv->bhv", probs, v)
        out = heads.reshape(b, -1) @ p["wo"]
    return out, dict(heads=heads, q_nope=q_nope, q_rope=q_rope,
                     c_kv=c_kv, k_rope=k_rope)


def _swiglu(x, wg, wi, wd):
    return (jax.nn.silu(x @ wg) * (x @ wi)) @ wd


def moe(p: dict, cfg: dict, x):
    """Mixture of experts of x (B, d): returns the output (B, d) and its
    parts: ``routed`` (B, d), ``shared`` (B, d), the chosen experts (B,
    top_k) and their weights (B, top_k)."""
    s = sizes(cfg)
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.softmax(x @ p["router"], axis=-1)
        weight, chosen = jax.lax.top_k(scores, s["top_k"])
        weight = weight * s["routed_scale"]
        every = jnp.stack([_swiglu(x, p["wg"][e], p["wi"][e], p["wd"][e])
                           for e in range(s["experts"])], 1)   # (B, E, d)
        picked = jnp.take_along_axis(every, chosen[..., None], 1)
        routed = jnp.sum(picked * weight[..., None], 1)
        shared = _swiglu(x, p["shared_wg"], p["shared_wi"], p["shared_wd"])
    return routed + shared, dict(routed=routed, shared=shared,
                                 chosen=chosen, weight=weight)


def decoder_layer(p: dict, cfg: dict, x, c_kv, k_rope, pos,
                  moe_layer: bool = True):
    """One decoder layer's decode step: (output (B, d), parts)."""
    s = sizes(cfg)
    a, parts = mla_decode(p, cfg, _rms(x, p["attn_norm"], s["eps"]), c_kv,
                          k_rope, pos)
    h = x + a
    hn = _rms(h, p["mlp_norm"], s["eps"])
    if moe_layer:
        f, parts["moe"] = moe(p, cfg, hn)
    else:
        with jax.default_matmul_precision("highest"):
            f = _swiglu(hn, p["wg"], p["wi"], p["wd"])
    parts["mlp_in"] = hn
    return h + f, parts
