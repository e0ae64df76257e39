"""Plain reference of Phi-3-mini (the configuration beside this file).

Written from the published architecture, not from the program: a decoder
of ``num_hidden_layers`` blocks, each RMSNorm -> multi-head attention with
rotary positions (rotate-half, base ``rope_theta``) and a causal mask ->
residual -> RMSNorm -> SwiGLU MLP (``silu(x Wg) * (x Wi)``, then ``Wo``)
-> residual; a final RMSNorm and an untied output head.  Departures from
the published model: the weights are random (drawn here from the seed),
and the norm epsilon is the one the configuration file states.

Everything runs in float32 at ``highest`` matmul precision, one layer at a
time, so the reference fits beside the bf16 weights on one chip.

Also here:

* ``make_weights``: the benchmark's own weights, drawn on the device in
  one jitted call from the seed, in bf16 (norm scales in f32), in the
  pytree layout the served program takes;
* ``served_gaps``: for every served token, how far its reference logit
  lies below the reference's best; with ``control`` also the same gap for
  the token that the reference with fp8 (e4m3) weights puts first;
* ``lowered_summary``: the layers a lowering of this model into RVV tile
  programs must consist of.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def sizes(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, layers=cfg["num_hidden_layers"], heads=h,
                kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg.get("head_dim") or d // h,
                ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                eps=cfg["rms_norm_eps"], theta=cfg["rope_theta"])


def key_for(seed: int):
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    key = jax.random.key(seed % 2**32)
    return jax.random.fold_in(key, (seed >> 32) % 2**31)


def weight_shapes(cfg: dict) -> dict:
    s = sizes(cfg)
    d, L, hd = s["d"], s["layers"], s["head_dim"]
    q, kv, ff, v = s["heads"] * hd, s["kv_heads"] * hd, s["ff"], s["vocab"]
    bf, f32 = jnp.bfloat16, jnp.float32
    return {
        "embed": ((v, d), bf, None),
        "final_norm": {"scale": ((d,), f32, "norm")},
        "lm_head": ((d, v), bf, d),
        "blocks": {
            "norm1": {"scale": ((L, d), f32, "norm")},
            "mixer": {"wq": ((L, d, q), bf, d), "wk": ((L, d, kv), bf, d),
                      "wv": ((L, d, kv), bf, d), "wo": ((L, q, d), bf, q)},
            "norm2": {"scale": ((L, d), f32, "norm")},
            "ffn": {"wi": ((L, d, ff), bf, d), "wg": ((L, d, ff), bf, d),
                    "wo": ((L, ff, d), bf, ff)},
        },
    }


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def make_weights(cfg: dict, seed: int) -> dict:
    """Weights from the seed, drawn on the device in one jitted call.
    Matrices ~ N(0, 1/fan_in), embedding ~ N(0, 0.02^2), norm scales
    1 + N(0, 0.1^2)."""
    shapes = weight_shapes(cfg)
    leaves, tree = jax.tree.flatten(shapes, is_leaf=_is_leaf)

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, dtype, fan) in zip(keys, leaves):
            z = jax.random.normal(k, shape, jnp.float32)
            if fan == "norm":
                w = 1.0 + 0.1 * z
            elif fan is None:
                w = 0.02 * z
            else:
                w = z / np.sqrt(fan)
            out.append(w.astype(dtype))
        return jax.tree.unflatten(tree, out)

    return draw(key_for(seed))


# ---------------------------------------------------------------------------
# Forward in float32.
# ---------------------------------------------------------------------------


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    """Rotate-half rotary embedding; x: (B, S, H, D)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions[:, :, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _fp8(w):
    """Round a weight matrix to float8 e4m3 with one scale per output
    column (the control's precision)."""
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (w / scale).astype(jnp.float8_e4m3fn)
    return q.astype(jnp.float32) * scale


def _w(w, control):
    w = w.astype(jnp.float32)
    return _fp8(w) if control else w


@functools.partial(jax.jit, static_argnames=("s", "control"))
def _layer(x, p, positions, *, s, control):
    b, n, _ = x.shape
    hd = s["head_dim"]
    h = _rms(x, p["norm1"]["scale"], s["eps"])
    m = p["mixer"]
    q = (h @ _w(m["wq"], control)).reshape(b, n, s["heads"], hd)
    k = (h @ _w(m["wk"], control)).reshape(b, n, s["kv_heads"], hd)
    v = (h @ _w(m["wv"], control)).reshape(b, n, s["kv_heads"], hd)
    q, k = _rope(q, positions, s["theta"]), _rope(k, positions, s["theta"])
    rep = s["heads"] // s["kv_heads"]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    att = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((n, n), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, n, -1)
    x = x + o @ _w(m["wo"], control)
    h = _rms(x, p["norm2"]["scale"], s["eps"])
    f = p["ffn"]
    g = jax.nn.silu(h @ _w(f["wg"], control)) * (h @ _w(f["wi"], control))
    return x + g @ _w(f["wo"], control)


@functools.partial(jax.jit, static_argnames=("s", "control"))
def _logits(x, norm, head, *, s, control):
    return _rms(x, norm, s["eps"]) @ _w(head, control)


@jax.jit
def _gaps(ref, served, other):
    """Reference best minus the reference logit of ``served`` (and of the
    control's first choice ``other``'s argmax), per position."""
    best = ref.max(-1)
    at = jnp.take_along_axis(ref, jnp.maximum(served, 0)[..., None],
                             -1)[..., 0]
    gap = jnp.where(served >= 0, best - at, 0.0)
    if other is None:
        return gap, None
    pick = other.argmax(-1)
    at_c = jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
    return gap, jnp.where(served >= 0, best - at_c, 0.0)


def forward(params, cfg, tokens, *, control=False):
    """Logits (B, S, V) of a teacher-forced forward pass in float32."""
    s = sizes(cfg)
    key = tuple(sorted(s.items()))
    s = dict(key)
    frozen = _Frozen(key)
    tokens = jnp.asarray(tokens, jnp.int32)
    b, n = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (b, n))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        for i in range(s["layers"]):
            p = jax.tree.map(lambda w: w[i], params["blocks"])
            x = _layer(x, p, positions, s=frozen, control=control)
        return _logits(x, params["final_norm"]["scale"], params["lm_head"],
                       s=frozen, control=control)


class _Frozen(dict):
    """Hashable view of the size dict (a static jit argument)."""

    def __init__(self, items):
        super().__init__(items)
        self._key = items

    def __hash__(self):
        return hash(self._key)


def served_gaps(params, cfg, requests, pad_to, control=False) -> dict:
    """Per served token, the reference's best logit minus its logit for
    the served token; ``requests`` is a list of (prompt, served tokens).

    Returns ``{"gap": [per request array], "control_gap": [...] or None}``.
    """
    b = len(requests)
    tokens = np.zeros((b, pad_to), np.int32)
    served = np.full((b, pad_to), -1, np.int32)
    for i, (prompt, out) in enumerate(requests):
        seq = list(prompt) + list(out[:-1])
        if len(seq) > pad_to:
            raise ValueError(f"sequence of {len(seq)} exceeds {pad_to}")
        tokens[i, : len(seq)] = seq
        p = len(prompt) - 1
        served[i, p: p + len(out)] = out
    ref = forward(params, cfg, tokens)
    other = forward(params, cfg, tokens, control=True) if control else None
    gap, gap_c = _gaps(ref, jnp.asarray(served), other)
    gap = np.asarray(gap)
    gap_c = None if gap_c is None else np.asarray(gap_c)
    out = {"gap": [], "control_gap": [] if control else None}
    for i, (prompt, toks) in enumerate(requests):
        p = len(prompt) - 1
        out["gap"].append(gap[i, p: p + len(toks)])
        if control:
            out["control_gap"].append(gap_c[i, p: p + len(toks)])
    return out


# ---------------------------------------------------------------------------
# The model as RVV tile programs.
# ---------------------------------------------------------------------------


def lowered_layers(cfg: dict) -> list:
    """(kind, shape, instances per token block) of every matrix multiply
    and attention of the model; the embedding is a gather, not a GEMM."""
    s = sizes(cfg)
    d, L, hd = s["d"], s["layers"], s["head_dim"]
    q, kv = s["heads"] * hd, s["kv_heads"] * hd
    return [("gemm", (d, q), L), ("gemm", (d, kv), L), ("gemm", (d, kv), L),
            ("gemm", (q, d), L), ("gemm", (d, s["ff"]), L),
            ("gemm", (d, s["ff"]), L), ("gemm", (s["ff"], d), L),
            ("gemm", (d, s["vocab"]), 1), ("attn", (s["heads"], hd), L)]


def lowered_summary(cfg: dict) -> dict:
    """Kernels (one per distinct layer shape), units and instances."""
    units: dict = {}
    for kind, shape, count in lowered_layers(cfg):
        name = (f"net:gemm:{shape[0]}x{shape[1]}" if kind == "gemm"
                else f"net:attn:{shape[0]}h{shape[1]}")
        units[name] = units.get(name, 0) + count
    return dict(kernels=sorted(units), units=len(units),
                instances=sum(units.values()))
