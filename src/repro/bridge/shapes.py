"""Layer-shape extraction: configs registry -> concrete LayerOp lists.

The bridge walks a model from :mod:`repro.configs.registry` and asks the
existing :mod:`repro.models` init functions — via ``jax.eval_shape``, so no
parameter memory is ever allocated — for every weight's concrete shape.
Each 2-D weight ``(K, N)`` becomes a GEMM op; each 3-D per-expert weight
``(E, K, N)`` becomes a GEMM op counted once per *active* expert
(``moe_top_k``); attention, Mamba-scan and RG-LRU blocks additionally emit
one dynamic op (``attn`` / ``scan``) for the part of the layer that is not
a weight GEMM.  Non-GEMM parameters (1-D vectors, the SSM ``a_log`` decay
table, the depthwise ``conv_w``) are skipped explicitly.

The workload unit is a **token block** of :data:`TOKEN_BLOCK` tokens: every
GEMM processes TOKEN_BLOCK rows, attention covers a TOKEN_BLOCK-long
context, and recurrences run TOKEN_BLOCK steps.  Lowered tiles cover a
fixed sub-problem; the ratio real-work / tile-work is the op's macro
factor (see :mod:`repro.bridge.lower`).

:func:`decode_ops` lowers the other phase: one decode step of ``batch``
sequences, each ``context`` positions into its cache.  Every GEMM then has
the step's tokens as its rows (``dgemm``), routed experts have the tokens
a seeded routing draw gives them (``expert``), and latent attention (MLA)
reads the compressed cache with its up-projections absorbed (``mla``).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.models.attention import init_attention, init_mla
from repro.models.mlp import init_mlp, init_moe
from repro.models.rglru import init_rglru
from repro.models.ssm import init_mamba

#: Tokens processed per workload unit (GEMM M rows, attention context
#: length, recurrence steps).
TOKEN_BLOCK = 128

#: Parameters that are 2-D but not GEMM weights: the SSM decay table and
#: the depthwise conv kernel (its work is a scan-shaped stencil, covered by
#: the layer's scan op), plus anything 1-D.
_SKIP_NAMES = frozenset({"a_log", "conv_w"})

_WHISPER_MELS = 80        # audio frontend: log-mel bins, conv kernel 3
_VISION_PATCH = 3 * 14 * 14   # vision frontend: RGB 14x14 patch embedding


@dataclasses.dataclass(frozen=True)
class LayerOp:
    """One lowered unit of network work.

    ``kind``: ``gemm`` (shape ``(K, N)``: x(M,K) @ W(K,N)), ``attn`` (shape
    ``(heads, head_dim)``) or ``scan`` (shape ``(width,)``: elementwise
    recurrence over ``width`` channels).  ``count`` is how many instances
    the whole network runs per token block (layers x multiplicity).
    Decode-phase kinds (:func:`decode_ops`) carry their rows in the shape
    and count instances per decode step: ``dgemm`` and ``expert`` ``(M, K,
    N)``, ``mla`` ``(batch, heads, nope, rope, latent, vdim, context)``.
    """

    kind: str
    label: str
    shape: tuple
    count: int

    @property
    def signature(self) -> tuple:
        """Dedup key: kind + concrete dims.  Label-free on purpose — two
        layers with the same shape lower to the same program."""
        return (self.kind,) + tuple(self.shape)

    @property
    def work(self) -> int:
        """Scalar work per instance (MACs for gemm/attn, element updates
        for scan) at the TOKEN_BLOCK workload unit."""
        if self.kind == "gemm":
            k, n = self.shape
            return TOKEN_BLOCK * k * n
        if self.kind == "attn":
            heads, hd = self.shape
            return 2 * TOKEN_BLOCK * TOKEN_BLOCK * hd * heads
        if self.kind in ("dgemm", "expert"):
            m, k, n = self.shape
            return m * k * n
        if self.kind == "mla":
            return mla_macs(*self.shape)
        (width,) = self.shape
        return TOKEN_BLOCK * width


def mla_macs(batch, heads, nope, rope, latent, vdim, context) -> int:
    """MACs of one absorbed-MLA decode step: per sequence and head, the
    query absorbed into the latent space (nope x latent), scores against
    the latent and rope cache (context x (latent + rope)), the weighted
    latent sum (context x latent) and the value up-projection (latent x
    vdim)."""
    return batch * heads * (nope * latent + context * (latent + rope)
                            + context * latent + latent * vdim)


def _weight_shapes(init_fn, *args, **kwargs) -> list[tuple[str, tuple]]:
    """(name, shape) per weight of an init function, via ``jax.eval_shape``
    (shape inference only — no arrays are materialised)."""
    tree = jax.eval_shape(
        lambda key: init_fn(key, *args, jnp.float32, **kwargs),
        jax.random.PRNGKey(0))
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        name = str(getattr(path[-1], "key", path[-1]))
        out.append((name, tuple(leaf.shape)))
    return out


def _gemm_ops(prefix: str, weights, count: int, top_k: int) -> list[LayerOp]:
    ops = []
    for name, shape in weights:
        if name in _SKIP_NAMES or len(shape) < 2:
            continue
        if len(shape) == 2:
            k, n = shape
            mult = 1
        elif len(shape) == 3:           # per-expert (E, K, N): top_k active
            _, k, n = shape
            mult = max(1, top_k)
        else:
            continue
        ops.append(LayerOp("gemm", f"{prefix}/{name}", (int(k), int(n)),
                           count * mult))
    return ops


def _head_geometry(cfg) -> tuple[int, int]:
    """(heads, qk head dim) — for MLA the decompressed per-head QK width."""
    hd = cfg.head_dim or cfg.d_model // cfg.num_heads
    if cfg.mla:
        hd = cfg.qk_nope_dim + cfg.qk_rope_dim
    return cfg.num_heads, hd


@functools.lru_cache(maxsize=None)
def model_ops(model: str) -> tuple[LayerOp, ...]:
    """All LayerOps of registry model ``model``, with network-level counts.

    Block composition mirrors :meth:`ArchConfig.param_count`: dense /
    MoE MLPs, (ML)A attention, Mamba blocks, hybrid attention/RG-LRU
    interleave (layer i is attention iff ``i % 3 == 2``), Whisper
    encoder-decoder (decoder layers carry self- plus cross-attention), the
    modality frontend as an im2col GEMM, and the LM head.
    """
    cfg = registry.get(model)
    d, l = cfg.d_model, cfg.num_layers
    ops: list[LayerOp] = []

    # ---- attention / recurrence block mix ------------------------------
    if cfg.ssm:
        ops += _gemm_ops("ssm", _weight_shapes(init_mamba, cfg), l, 0)
        din = cfg.ssm_expand * d
        ops.append(LayerOp("scan", "ssm_scan", (din * cfg.ssm_state,), l))
        n_mlp = 0                        # Mamba blocks subsume the MLP
    elif cfg.hybrid:
        n_att = sum(1 for i in range(l) if i % 3 == 2)
        n_rec = l - n_att
        ops += _gemm_ops("attn", _weight_shapes(init_attention, cfg),
                         n_att, 0)
        ops.append(LayerOp("attn", "attention", _head_geometry(cfg), n_att))
        ops += _gemm_ops("rglru", _weight_shapes(init_rglru, cfg), n_rec, 0)
        ops.append(LayerOp("scan", "rglru_scan", (cfg.lru_width or d,),
                           n_rec))
        n_mlp = l
    else:
        init_a = init_mla if cfg.mla else init_attention
        n_att = l + (cfg.num_encoder_layers + l if cfg.encoder_decoder
                     else 0)            # decoder self + cross, encoder self
        ops += _gemm_ops("attn", _weight_shapes(init_a, cfg), n_att, 0)
        ops.append(LayerOp("attn", "attention", _head_geometry(cfg), n_att))
        n_mlp = l + (cfg.num_encoder_layers if cfg.encoder_decoder else 0)

    # ---- MLP / MoE blocks ---------------------------------------------
    if n_mlp:
        if cfg.moe:
            n_dense = cfg.first_dense_layers
            n_moe = n_mlp - n_dense
            if n_dense:
                ops += _gemm_ops(
                    "mlp", _weight_shapes(init_mlp, d, cfg.d_ff,
                                          kind=cfg.mlp_kind), n_dense, 0)
            ops += _gemm_ops("moe", _weight_shapes(init_moe, cfg), n_moe,
                             cfg.moe_top_k)
        else:
            ops += _gemm_ops(
                "mlp", _weight_shapes(init_mlp, d, cfg.d_ff,
                                      kind=cfg.mlp_kind), n_mlp, 0)

    # ---- frontend + LM head -------------------------------------------
    if cfg.frontend == "audio":          # two k=3 conv1d layers, im2col
        ops.append(LayerOp("gemm", "frontend/conv1", (_WHISPER_MELS * 3, d),
                           1))
        ops.append(LayerOp("gemm", "frontend/conv2", (d * 3, d), 1))
    elif cfg.frontend == "vision":       # patch embedding, im2col
        ops.append(LayerOp("gemm", "frontend/patch", (_VISION_PATCH, d), 1))
    ops.append(LayerOp("gemm", "lm_head", (d, cfg.vocab_size), 1))
    return tuple(ops)


# ---------------------------------------------------------------------------
# Decode phase.
# ---------------------------------------------------------------------------


def routing_loads(num_experts: int, top_k: int, tokens: int, layers: int,
                  zipf: float, seed: int) -> list[np.ndarray]:
    """Tokens each routed expert receives, per MoE layer, under a seeded
    Zipf-skewed expert popularity.

    One generator ``numpy.random.default_rng(seed)`` serves every layer
    in order.  Per layer: ``rank = rng.permutation(num_experts)`` ranks
    the experts, expert ``e`` has popularity ``1 / (rank[e] + 1) ** zipf``
    (normalised), and each token in turn picks ``top_k`` distinct experts
    by ``rng.choice(num_experts, top_k, replace=False, p=popularity)``.
    The draw depends on its arguments alone, never on a run's seed, so
    every sweep lowers the same work.  Each layer's loads add up to
    ``tokens * top_k``.
    """
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(layers):
        rank = rng.permutation(num_experts)
        pop = 1.0 / (rank + 1.0) ** zipf
        pop /= pop.sum()
        picks = [rng.choice(num_experts, top_k, replace=False, p=pop)
                 for _ in range(tokens)]
        out.append(np.bincount(np.concatenate(picks),
                               minlength=num_experts))
    return out


@functools.lru_cache(maxsize=None)
def decode_ops(model: str, batch: int, context: int, zipf: float,
               seed: int) -> tuple[LayerOp, ...]:
    """LayerOps of one decode step of registry model ``model``: ``batch``
    sequences, one new token each, ``context`` positions cached.

    Latent attention (``cfg.mla``) only: its projections at M = batch
    (``wq``, ``wdkv``, ``wkr``, ``wo``; the key/value up-projection is
    absorbed, not run) and one ``mla`` op over the latent cache per layer.
    The MLP of each dense layer, the router, the shared experts (one MLP
    of ``num_shared_experts`` x ``moe_d_ff``) and the LM head run at M =
    batch; each routed expert runs at the tokens :func:`routing_loads`
    gives it, and an expert that gets none emits nothing.
    """
    cfg = registry.get(model)
    if not cfg.mla:
        raise ValueError(
            f"decode lowering needs latent attention (MLA); {model!r} has "
            f"{cfg.num_heads}-head attention, whose decode tile does not "
            f"exist yet")
    d, l, b = cfg.d_model, cfg.num_layers, batch
    h, dn, dr = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    r, dv = cfg.kv_lora_rank, cfg.v_head_dim
    ops = [LayerOp("dgemm", "attn/wq", (b, d, h * (dn + dr)), l),
           LayerOp("dgemm", "attn/wdkv", (b, d, r), l),
           LayerOp("dgemm", "attn/wkr", (b, d, dr), l),
           LayerOp("mla", "attention", (b, h, dn, dr, r, dv, context), l),
           LayerOp("dgemm", "attn/wo", (b, h * dv, d), l)]
    n_dense = cfg.first_dense_layers if cfg.moe else l
    if n_dense:
        ops += [LayerOp("dgemm", "mlp/wg", (b, d, cfg.d_ff), n_dense),
                LayerOp("dgemm", "mlp/wi", (b, d, cfg.d_ff), n_dense),
                LayerOp("dgemm", "mlp/wo", (b, cfg.d_ff, d), n_dense)]
    n_moe = l - n_dense
    if n_moe:
        ff = cfg.moe_d_ff
        ops.append(LayerOp("dgemm", "moe/router", (b, d, cfg.num_experts),
                           n_moe))
        if cfg.num_shared_experts:
            sff = ff * cfg.num_shared_experts
            ops += [LayerOp("expert", "moe/shared/wg", (b, d, sff), n_moe),
                    LayerOp("expert", "moe/shared/wi", (b, d, sff), n_moe),
                    LayerOp("expert", "moe/shared/wo", (b, sff, d), n_moe)]
        loads = routing_loads(cfg.num_experts, cfg.moe_top_k, b, n_moe,
                              zipf, seed)
        for i, per_expert in enumerate(loads):
            for e in np.flatnonzero(per_expert):
                m = int(per_expert[e])
                tag = f"moe/routed/l{i}e{e}"
                ops += [LayerOp("expert", f"{tag}/wg", (m, d, ff), 1),
                        LayerOp("expert", f"{tag}/wi", (m, d, ff), 1),
                        LayerOp("expert", f"{tag}/wo", (m, ff, d), 1)]
    ops.append(LayerOp("dgemm", "lm_head", (b, d, cfg.vocab_size), 1))
    return tuple(ops)
