"""Attention blocks: GQA/MQA (RoPE, M-RoPE, qk-norm, sliding window, cross)
and DeepSeek-V2 MLA (compressed latent KV).

Three entry modes share weights:
  * ``train/prefill``: full-sequence attention (optionally via the Pallas
    flash kernel when ``impl='pallas'`` — TPU target; ``xla`` path is used
    for dry-run lowering and CPU tests).
  * ``decode``: single-token step against a (possibly dispersed) KV cache.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import numpy as np_  # noqa: F401

from repro.models import common
from repro.models.common import shard

NEG_INF = -1e30


def init_attention(key, cfg, dtype) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 6)
    p = {
        "wq": common.dense_init(ks[0], (d, nq * hd), dtype),
        "wk": common.dense_init(ks[1], (d, nkv * hd), dtype),
        "wv": common.dense_init(ks[2], (d, nkv * hd), dtype),
        "wo": common.dense_init(ks[3], (nq * hd, d), dtype),
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((nq * hd,), dtype)
        p["bk"] = jnp.zeros((nkv * hd,), dtype)
        p["bv"] = jnp.zeros((nkv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = common.init_rmsnorm(hd)
        p["k_norm"] = common.init_rmsnorm(hd)
    return p


def _project_qkv(p, cfg, x, positions, kv_input=None,
                 expand_kv: bool = False):
    """Returns q: (B,S,Hq,D), k/v: (B,Skv,Hkv,D) (rope applied).

    ``expand_kv`` (train/prefill): GQA KV heads are expanded to the full
    query head count *in the weight view* (repeat over the group axis;
    backprop sums group gradients, preserving GQA semantics exactly).  With
    fewer KV heads than the tensor-parallel axis (e.g. qwen3's 8 kv-heads on
    a 16-way model axis) the un-expanded KV activations cannot shard and XLA
    inserts a full activation all-gather per layer (~9.3 GB/layer measured
    on qwen3 train_4k) — expansion keeps every attention tensor head-sharded
    at ~3% extra projection FLOPs (see EXPERIMENTS.md §Perf, hypothesis H1).
    """
    b, s, _ = x.shape
    d, hd = cfg.d_model, cfg.head_dim
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    kv_src = x if kv_input is None else kv_input
    skv = kv_src.shape[1]
    groups = hq // hkv
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    mesh = common.get_mesh()
    tp = mesh.shape.get(common.MODEL, 1) if mesh is not None else 1
    # Expand only as far as divisibility requires (e.g. kv8 on a 16-way
    # model axis -> 16 heads, not the full 32): halves the extra projection
    # FLOPs of the naive full expansion (§Perf H5).
    rep = 1
    if expand_kv and groups > 1 and hkv % tp != 0:
        # smallest group-divisor expansion that makes heads tp-divisible;
        # if none exists (e.g. 28 or 10 total heads on a 16-way axis) fall
        # back to no expansion — those archs shard on feature dims instead.
        rep = next((r for r in range(1, groups + 1)
                    if groups % r == 0 and (hkv * r) % tp == 0), 1)
    if rep > 1:
        wk = jnp.repeat(p["wk"].reshape(d, hkv, hd), rep, axis=1)
        wv = jnp.repeat(p["wv"].reshape(d, hkv, hd), rep, axis=1)
        wk = shard(wk, common.FSDP, common.MODEL, None)
        wv = shard(wv, common.FSDP, common.MODEL, None)
        k = jnp.einsum("bsd,dhe->bshe", kv_src, wk).reshape(b, skv, -1)
        v = jnp.einsum("bsd,dhe->bshe", kv_src, wv).reshape(b, skv, -1)
        hkv_eff = hkv * rep
        if cfg.attn_bias:
            k = k + jnp.repeat(p["bk"].reshape(hkv, hd), rep, 0).reshape(-1)
            v = v + jnp.repeat(p["bv"].reshape(hkv, hd), rep, 0).reshape(-1)
    else:
        k = jnp.einsum("bsd,dh->bsh", kv_src, p["wk"])
        v = jnp.einsum("bsd,dh->bsh", kv_src, p["wv"])
        hkv_eff = hkv
        if cfg.attn_bias:
            k, v = k + p["bk"], v + p["bv"]
    if cfg.attn_bias:
        q = q + p["bq"]
    q = q.reshape(b, s, hq, hd)
    k = k.reshape(b, skv, hkv_eff, hd)
    v = v.reshape(b, skv, hkv_eff, hd)
    q = shard(q, common.BATCH, None, common.MODEL, None)
    k = shard(k, common.BATCH, None, common.MODEL, None)
    v = shard(v, common.BATCH, None, common.MODEL, None)
    if cfg.qk_norm:
        q = common.rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = common.rmsnorm(p["k_norm"], k, cfg.norm_eps)
    if cfg.positional == "rope" and kv_input is None:
        q = common.apply_rope(q, positions, cfg.rope_theta)
        k = common.apply_rope(k, positions, cfg.rope_theta)
    elif cfg.positional == "mrope" and kv_input is None:
        q = common.apply_mrope(q, positions, cfg.rope_theta)
        k = common.apply_mrope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, *, causal, window, q_offset=0):
    """XLA attention path. q: (B,Sq,Hq,D), k/v: (B,Sk,Hkv,D)."""
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    groups = hq // hkv
    qg = q.reshape(b, sq, hkv, groups, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * (d ** -0.5)
    qpos = q_offset + jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= qpos - kpos < window
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, hq, d).astype(q.dtype)


def attention(p, cfg, x, positions, *, causal=True, kv_input=None,
              impl="xla"):
    """Full-sequence attention (train / prefill). Returns (out, kv)."""
    q, k, v = _project_qkv(p, cfg, x, positions, kv_input, expand_kv=True)
    window = cfg.sliding_window
    if impl == "pallas":
        from repro.kernels import ops
        assert window is None and kv_input is None
        out = ops.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal,
            interpret=False).transpose(0, 2, 1, 3)
    else:
        out = _sdpa(q, k, v, causal=causal and kv_input is None,
                    window=window)
    b, s = x.shape[:2]
    out = jnp.einsum("bsh,hd->bsd",
                     out.reshape(b, s, cfg.num_heads * cfg.head_dim),
                     p["wo"])
    return shard(out, common.BATCH, None, None), (k, v)


def decode_attention(p, cfg, x, positions, cache_k, cache_v, cache_len):
    """One-token decode. x: (B,1,d); cache_k/v: (B,S_max,Hkv,D) with KV
    sharded (batch, seq->model).  Returns (out, new_k, new_v)."""
    q, k, v = _project_qkv(p, cfg, x, positions)
    # Flash-decode sharding (EXPERIMENTS.md §Perf H2): the per-token q/k/v
    # are tiny — replicate them over the model axis so attention against the
    # *sequence-sharded* cache is a local partial-softmax plus small
    # all-reduces, instead of re-gathering the multi-GB cache every layer.
    q = shard(q, common.BATCH, None, None, None)
    k = shard(k, common.BATCH, None, None, None)
    v = shard(v, common.BATCH, None, None, None)
    # For M-RoPE, positions is (3,B,1); the temporal component drives the
    # cache slot and causal validity.
    tpos = positions[0] if positions.ndim == 3 else positions
    b, _, hkv, d = k.shape
    smax = cache_k.shape[1]
    if cfg.sliding_window is not None and smax <= cfg.sliding_window:
        slot = tpos[:, 0] % smax                      # ring buffer
    else:
        slot = jnp.minimum(tpos[:, 0], smax - 1)
    oh = jax.nn.one_hot(slot, smax, dtype=k.dtype)    # (B, Smax)
    new_k = cache_k * (1 - oh[:, :, None, None]) + oh[:, :, None, None] * k
    new_v = cache_v * (1 - oh[:, :, None, None]) + oh[:, :, None, None] * v
    new_k = shard(new_k, common.BATCH, common.MODEL, None, None)
    new_v = shard(new_v, common.BATCH, common.MODEL, None, None)

    groups = cfg.num_heads // hkv
    qg = q.reshape(b, hkv, groups, d)                 # (B,Hkv,G,D) (Sq=1)
    logits = jnp.einsum("bhgd,bshd->bhgs", qg.astype(jnp.float32),
                        new_k.astype(jnp.float32)) * (d ** -0.5)
    kpos = jnp.arange(smax)[None, :]
    valid = kpos <= tpos[:, :1]                       # causal up to current
    if cfg.sliding_window is not None:
        if smax <= cfg.sliding_window:
            # Ring buffer: every written slot is in-window; once the ring has
            # wrapped, all slots are valid.
            valid = valid | (tpos[:, :1] >= smax)
        else:
            valid &= tpos[:, :1] - kpos < cfg.sliding_window
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", probs, new_v.astype(jnp.float32))
    out = out.reshape(b, 1, cfg.num_heads * cfg.head_dim).astype(x.dtype)
    out = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    return shard(out, common.BATCH, None, None), new_k, new_v


def decode_cross_attention(p, cfg, x, enc_k, enc_v):
    """Cross-attention for enc-dec decode: enc K/V precomputed at prefill."""
    b = x.shape[0]
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    if cfg.attn_bias:
        q = q + p["bq"]
    q = q.reshape(b, 1, cfg.num_heads, cfg.head_dim)
    out = _sdpa(q, enc_k, enc_v, causal=False, window=None)
    out = jnp.einsum("bsh,hd->bsd",
                     out.reshape(b, 1, cfg.num_heads * cfg.head_dim),
                     p["wo"])
    return shard(out, common.BATCH, None, None)


# ---------------------------------------------------------------------------
# DeepSeek-V2 Multi-head Latent Attention (MLA).
# ---------------------------------------------------------------------------


def init_mla(key, cfg, dtype) -> dict:
    d = cfg.d_model
    h, dn, dr, dv = (cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.v_head_dim)
    r = cfg.kv_lora_rank
    ks = jax.random.split(key, 6)
    return {
        "wq": common.dense_init(ks[0], (d, h * (dn + dr)), dtype),
        "wdkv": common.dense_init(ks[1], (d, r), dtype),        # compress
        "wkr": common.dense_init(ks[2], (d, dr), dtype),        # shared rope k
        "wuk": common.dense_init(ks[3], (r, h * dn), dtype),    # expand k
        "wuv": common.dense_init(ks[4], (r, h * dv), dtype),    # expand v
        "wo": common.dense_init(ks[5], (h * dv, d), dtype),
        "kv_norm": common.init_rmsnorm(r),
    }


def mla_attention(p, cfg, x, positions, *, causal=True):
    """Full-sequence MLA. Cache payload = (c_kv, k_rope): the paper-relevant
    point is that the latent (r + dr per token) is what a serving system
    stores — a compressed 'architectural register' the cVRF analogy caches.
    Returns (out, (c_kv, k_rope))."""
    b, s, _ = x.shape
    h = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = common.apply_rope(q_rope, positions, cfg.rope_theta)

    c_kv = common.rmsnorm(p["kv_norm"],
                          jnp.einsum("bsd,dr->bsr", x, p["wdkv"]),
                          cfg.norm_eps)                       # (B,S,r)
    k_rope = common.apply_rope(
        jnp.einsum("bsd,dr->bsr", x, p["wkr"])[:, :, None, :],
        positions, cfg.rope_theta)                            # (B,S,1,dr)
    k_nope = jnp.einsum("bsr,rh->bsh", c_kv,
                        p["wuk"]).reshape(b, s, h, dn)
    v = jnp.einsum("bsr,rh->bsh", c_kv, p["wuv"]).reshape(b, s, h, dv)

    scale = (dn + dr) ** -0.5
    logits = (jnp.einsum("bqhd,bkhd->bhqk", q_nope.astype(jnp.float32),
                         k_nope.astype(jnp.float32))
              + jnp.einsum("bqhd,bkod->bhqk", q_rope.astype(jnp.float32),
                           jnp.broadcast_to(
                               k_rope, (b, s, 1, dr)).astype(jnp.float32))
              ) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, -1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    out = out.reshape(b, s, h * dv).astype(x.dtype)
    out = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    return shard(out, common.BATCH, None, None), (c_kv, k_rope[:, :, 0, :])


def mla_decode(p, cfg, x, positions, cache_c, cache_kr, cache_len):
    """One-token MLA decode against the compressed latent cache.
    cache_c: (B,Smax,r); cache_kr: (B,Smax,dr)."""
    b = x.shape[0]
    h = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    smax = cache_c.shape[1]
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(b, 1, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = common.apply_rope(q_rope, positions, cfg.rope_theta)

    c_new = common.rmsnorm(p["kv_norm"],
                           jnp.einsum("bsd,dr->bsr", x, p["wdkv"]),
                           cfg.norm_eps)[:, 0]                # (B,r)
    kr_new = common.apply_rope(
        jnp.einsum("bsd,dr->bsr", x, p["wkr"])[:, :, None, :],
        positions, cfg.rope_theta)[:, 0, 0]                   # (B,dr)
    slot = jnp.minimum(positions[:, 0], smax - 1)
    oh = jax.nn.one_hot(slot, smax, dtype=cache_c.dtype)
    cache_c = cache_c * (1 - oh[..., None]) + oh[..., None] * c_new[:, None]
    cache_kr = (cache_kr * (1 - oh[..., None])
                + oh[..., None] * kr_new[:, None])
    cache_c = shard(cache_c, common.BATCH, common.MODEL, None)
    cache_kr = shard(cache_kr, common.BATCH, common.MODEL, None)

    # Absorbed attention: q_nope projected into latent space once.
    wuk = p["wuk"].reshape(cfg.kv_lora_rank, h, dn)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0].astype(jnp.float32),
                       wuk.astype(jnp.float32))               # (B,h,r)
    scale = (dn + dr) ** -0.5
    logits = (jnp.einsum("bhr,bsr->bhs", q_lat,
                         cache_c.astype(jnp.float32))
              + jnp.einsum("bhd,bsd->bhs",
                           q_rope[:, 0].astype(jnp.float32),
                           cache_kr.astype(jnp.float32))) * scale
    valid = jnp.arange(smax)[None] <= positions[:, :1]
    logits = jnp.where(valid[:, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, -1)
    ctx = jnp.einsum("bhs,bsr->bhr", probs,
                     cache_c.astype(jnp.float32))             # (B,h,r)
    wuv = p["wuv"].reshape(cfg.kv_lora_rank, h, dv)
    out = jnp.einsum("bhr,rhd->bhd", ctx, wuv.astype(jnp.float32))
    out = out.reshape(b, 1, h * dv).astype(x.dtype)
    out = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    return shard(out, common.BATCH, None, None), cache_c, cache_kr
