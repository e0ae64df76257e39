"""Decode-phase lowering: the new tile families against the plain
reference, the routing draw, fold certification, and the prefill lowering
pinned.

The plain reference is the configuration's own
(``bench/configs/deepseek-v2-lite-16b.ref.py``): one DeepSeek-V2 decoder
layer's decode step in float32, MLA decompressed, sharing no code with
``repro.bridge`` or ``repro.models``.  The tiles run in the functional
interpreter on seeded weights at a small size of the same layout.
"""

import copy
import hashlib
import importlib.util
import json
import pathlib

import jax
import numpy as np
import pytest

from repro import api, bridge, obs
from repro.bridge import lower, network, shapes
from repro.configs import registry
from repro.core import folding, interpreter
from repro.rvv import common

ROOT = pathlib.Path(__file__).resolve().parents[1]
CFG = json.loads((ROOT / "bench" / "configs" /
                  "deepseek-v2-lite-16b.json").read_text())
SPEC = "deepseek-v2-lite-16b:decode:batch=8:context=4096:zipf=1.0:seed=0"
GEOMETRIES = [(64, 2), (256, 2)]          # the cell's 4 KB and 16 KB L1s
ROW_FIELDS = ("op", "vd", "vs1", "vs2", "addr", "imm", "cost_override")


@pytest.fixture(scope="module")
def ref():
    path = ROOT / "bench" / "configs" / "deepseek-v2-lite-16b.ref.py"
    spec = importlib.util.spec_from_file_location("dsv2_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def small_cfg():
    """The published layout at a size the interpreter runs in a second."""
    cfg = copy.deepcopy(CFG)
    cfg.update(hidden_size=64, num_attention_heads=4, qk_nope_head_dim=16,
               qk_rope_head_dim=8, kv_lora_rank=32, v_head_dim=16,
               moe_intermediate_size=16, intermediate_size=48,
               n_routed_experts=8, num_experts_per_tok=2,
               num_hidden_layers=3, vocab_size=128)
    return cfg


def _run(built):
    """Interpret a tile program; its f64 mirror must agree first."""
    mem = interpreter.run(built.program).memory
    common.check(built, mem)
    return mem


# ---------------------------------------------------------------------------
# The tiles against the plain reference.
# ---------------------------------------------------------------------------


def test_absorbed_mla_tile_matches_decompressed_reference(ref, small_cfg):
    """The tile never forms k_nope or v: it absorbs W_UK into the query
    and projects the weighted latent sum through W_UV.  The reference
    decompresses the cache and runs ordinary attention."""
    b, t = 2, 15                            # 15 cached + the new token
    k = jax.random.split(jax.random.key(3), 4)
    p = ref.init_layer(small_cfg, k[0])
    s = ref.sizes(small_cfg)
    x = np.asarray(jax.random.normal(k[1], (b, s["d"])))
    c_kv = np.asarray(jax.random.normal(k[2], (b, t, s["latent"])))
    k_rope = np.asarray(jax.random.normal(k[3], (b, t, s["rope"])))
    _, parts = ref.mla_decode(p, small_cfg, x, c_kv, k_rope, pos=t)
    w_uk, w_uv = ref.up_projections(p, small_cfg)
    built = lower.mla_program(
        *(np.asarray(parts[n], np.float32)
          for n in ("q_nope", "q_rope", "c_kv", "k_rope")),
        np.asarray(w_uk, np.float32), np.asarray(w_uv, np.float32),
        scale=ref.softmax_scale(small_cfg))
    mem = _run(built)
    h, dv = s["heads"], s["vdim"]
    got = built.program.buffer_view(mem, "O").reshape(b, -1)[:, : h * dv]
    want = np.asarray(parts["heads"]).reshape(b, -1)
    # The tile's exp is (1 + x/4096)**4096: relative error ~x**2/8192 on a
    # probability; the scores here span 8.4, so under 1 % on the smallest
    # and far less on the largest.  Measured: 5.6e-4 worst absolute error
    # on outputs up to 1.6.  The same tile on bf16-rounded inputs is off
    # by 1.1e-2 and fails.
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4)


def _tile_matmul(rows, w, width=8):
    """rows (m, k) @ w (k, n) through the skinny GEMM tile, one tile per
    ``width``-column block of w."""
    k, n = w.shape
    blocks = np.ascontiguousarray(
        w.reshape(k, n // width, width).transpose(1, 0, 2), np.float32)
    built = lower.dgemm_program(np.asarray(rows, np.float32), blocks)
    mem = _run(built)
    m = rows.shape[0]
    c = built.program.buffer_view(mem, "C").reshape(n // width, -1)
    return np.concatenate([c[i, : m * width].reshape(m, width)
                           for i in range(n // width)], 1)


def test_skinny_expert_tiles_match_the_routed_reference(ref, small_cfg):
    """Each routed expert runs its gate, up and down projections as skinny
    GEMM tiles at M = the tokens routed to it; weighted by the router and
    summed, they give the layer's routed part."""
    b = 8
    k = jax.random.split(jax.random.key(5), 2)
    p = ref.init_layer(small_cfg, k[0])
    x = np.asarray(jax.random.normal(k[1], (b, small_cfg["hidden_size"])))
    _, parts = ref.moe(p, small_cfg, x)
    chosen = np.asarray(parts["chosen"])
    weight = np.asarray(parts["weight"])
    routed = np.zeros_like(x, dtype=np.float64)
    loads = []
    for e in range(small_cfg["n_routed_experts"]):
        tok, slot = np.nonzero(chosen == e)
        if not len(tok):
            continue
        loads.append(len(tok))
        rows = x[tok]
        g = _tile_matmul(rows, np.asarray(p["wg"][e]))
        u = _tile_matmul(rows, np.asarray(p["wi"][e]))
        act = (g / (1 + np.exp(-g.astype(np.float64))) * u).astype(
            np.float32)
        out = _tile_matmul(act, np.asarray(p["wd"][e]))
        routed[tok] += weight[tok, slot][:, None] * out
    assert max(loads) > min(loads)          # uneven rows, as routed
    # float32 products summed in another order than XLA's: ~1e-6.
    np.testing.assert_allclose(routed, np.asarray(parts["routed"]),
                               rtol=1e-4, atol=1e-5)


def test_reference_layer_adds_up(ref, small_cfg):
    """The reference's decode step is its attention plus its MLP parts:
    a MoE layer's output is shared + routed on top of the residual."""
    b, t = 3, 7
    k = jax.random.split(jax.random.key(9), 4)
    p = ref.init_layer(small_cfg, k[0])
    s = ref.sizes(small_cfg)
    x = jax.random.normal(k[1], (b, s["d"]))
    c_kv = jax.random.normal(k[2], (b, t, s["latent"]))
    k_rope = jax.random.normal(k[3], (b, t, s["rope"]))
    out, parts = ref.decoder_layer(p, small_cfg, x, c_kv, k_rope, pos=t)
    assert out.shape == (b, s["d"]) and parts["c_kv"].shape[1] == t + 1
    a, _ = ref.mla_decode(p, small_cfg, ref._rms(x, p["attn_norm"], s["eps"]),
                          c_kv, k_rope, pos=t)
    m = parts["moe"]
    np.testing.assert_allclose(out, x + a + m["routed"] + m["shared"],
                               rtol=1e-5, atol=1e-5)
    dense = ref.init_layer(small_cfg, k[0], moe=False)
    out_d, _ = ref.decoder_layer(dense, small_cfg, x, c_kv, k_rope, pos=t,
                                 moe_layer=False)
    assert np.isfinite(np.asarray(out_d)).all()


# ---------------------------------------------------------------------------
# The routing draw.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2**40 + 7])
def test_routing_draw_is_fixed_and_places_every_assignment(seed):
    a = shapes.routing_loads(64, 6, 8, 26, 1.0, seed)
    b = shapes.routing_loads(64, 6, 8, 26, 1.0, seed)
    assert len(a) == 26
    assert all((x == y).all() for x, y in zip(a, b))
    assert all(x.sum() == 48 and x.max() <= 8 for x in a)
    other = shapes.routing_loads(64, 6, 8, 26, 1.0, seed + 1)
    assert any((x != y).any() for x, y in zip(a, other))


def test_routing_draw_is_skewed():
    """Zipf 1.0: the busiest expert of a layer takes several tokens while
    most experts get none; with no skew the loads even out."""
    skewed = shapes.routing_loads(64, 6, 8, 26, 1.0, 0)
    flat = shapes.routing_loads(64, 6, 8, 26, 0.0, 0)
    assert np.mean([x.max() for x in skewed]) > np.mean([x.max()
                                                         for x in flat])
    assert np.mean([(x == 0).sum() for x in skewed]) > 30


def test_program_and_reference_draw_alike(ref):
    want = ref.routing_loads(CFG)
    got = shapes.routing_loads(64, 6, 8, 26, 1.0, 0)
    assert all((x == y).all() for x, y in zip(got, want))


# ---------------------------------------------------------------------------
# The decode lowering.
# ---------------------------------------------------------------------------


def test_decode_lowering_matches_the_reference_summary(ref):
    net = bridge.lower_network(SPEC)
    s = net.summary()
    assert dict(kernels=sorted(s["kernels"]), units=s["units"],
                instances=s["instances"]) == ref.lowered_summary(CFG)
    assert net.model == SPEC
    kinds = {u.kind for u in net.units}
    assert kinds == {"dgemm", "expert", "mla"}
    # published widths in every kernel name; M = 8 outside routed experts
    assert "net:mla:b8:h16:n128:r64:l512:v128:c4096" in net.kernels
    assert {"net:dgemm:8x2048x3072", "net:dgemm:8x2048x512",
            "net:dgemm:8x2048x64", "net:dgemm:8x2048x2048",
            "net:dgemm:8x2048x10944", "net:dgemm:8x10944x2048",
            "net:dgemm:8x2048x102400", "net:expert:8x2048x2816",
            "net:expert:8x2816x2048"} <= set(net.kernels)
    routed = [u for u in net.units if u.kind == "expert"
              and u.shape[1:] in ((2048, 1408), (1408, 2048))]
    loads = shapes.routing_loads(64, 6, 8, 26, 1.0, 0)
    want = {m: sum(int((x == m).sum()) for x in loads)
            for m in range(1, 9)}
    for u in routed:
        per = 2 if u.shape[1] == 2048 else 1
        assert u.count == per * want[u.shape[0]], u.kernel
    assert not any(k.startswith(("net:gemm:", "net:attn:"))
                   for k in net.kernels)


def test_decode_macro_factors_cover_the_real_work():
    net = bridge.lower_network(SPEC)
    ops = shapes.decode_ops("deepseek-v2-lite-16b", 8, 4096, 1.0, 0)
    real = sum(o.work * o.count for o in ops)
    tile = 0.0
    for u in net.units:
        p = u.params
        if u.kind == "mla":
            w = shapes.mla_macs(*(p[k] for k in lower.MLA_CAPS))
        else:
            w = p["tiles"] * p["m"] * p["k"] * p["n"]
        tile += w * u.scale
    assert tile == pytest.approx(real)


def test_decode_span_counts_what_it_emitted():
    obs.reset()
    with obs.recording():
        bridge.lower_network(SPEC)
    (sp,) = [s for s in obs.spans() if s.name == "bridge.lower"]
    obs.reset()
    a = sp.attrs
    assert a["model"] == SPEC and a["mla_ops"] == 27
    assert a["dgemm_ops"] == 27 * 4 + 3 + 26 + 1
    loads = shapes.routing_loads(64, 6, 8, 26, 1.0, 0)
    active = sum(int((x > 0).sum()) for x in loads)
    assert a["expert_ops"] == 26 * 3 + 3 * active
    assert a["expert_tokens_max"] == max(int(x.max()) for x in loads)
    assert a["expert_tokens_min"] == 1


@pytest.mark.parametrize("spec,error", [
    ("deepseek-v2-lite-16b:prefill", "unknown phase"),
    ("deepseek-v2-lite-16b:decode:beam=4", "bad option"),
    ("deepseek-v2-lite-16b:decode:batch", "bad option"),
    ("deepseek-v2-lite-16b:decode:batch=8:context=512", "missing options"),
    ("phi3-mini-3.8b:decode:batch=8:context=512:zipf=1.0:seed=0",
     "needs latent attention"),
])
def test_bad_decode_specs_are_refused(spec, error):
    with pytest.raises(ValueError, match=error):
        bridge.lower_network(spec)


def test_decode_spec_options_parse():
    assert network.parse_spec("granite-8b") == ("granite-8b", None)
    spec = "deepseek-v2-lite-16b:decode:seed=3:context=512:zipf=0.5:batch=8"
    model, opts = network.parse_spec(spec)
    assert model == "deepseek-v2-lite-16b"
    assert opts == dict(batch=8, context=512, zipf=0.5, seed=3)
    assert type(opts["zipf"]) is float and type(opts["batch"]) is int
    net = bridge.lower_network(spec)
    assert "net:mla:b8:h16:n128:r64:l512:v128:c512" in net.kernels


def test_decode_network_runs_through_the_session():
    """``Sweep(network=(<decode spec>,))`` on the normal path: every point
    folds certified, and ``session.prepare`` records each kernel family's
    prepared rows."""
    sweep = api.Sweep(network=(SPEC,), capacity=(8,), mem_latency=(20,),
                      l1_geometry=((64, 2),))
    ses = api.Session()
    obs.reset()
    with obs.recording():
        res = ses.run(sweep)
    spans = obs.spans()
    obs.reset()
    assert res.data["fold_exact"].all()
    assert res.meta["networks"][0]["model"] == SPEC
    (prep,) = [s for s in spans if s.name == "session.prepare"]
    m = sweep.machine_sweep(sweep.l1_geometry[0])
    rows = {k: ses.prepared(k, machine=m).num_rows for k in sweep.kernels}
    assert prep.attrs["rows"] == sum(rows.values())
    for fam in ("net:mla", "net:expert", "net:dgemm"):
        assert prep.attrs[f"rows:{fam}"] == sum(
            v for k, v in rows.items() if k.startswith(fam + ":"))
    share = (prep.attrs["rows:net:mla"] + prep.attrs["rows:net:expert"]) \
        / prep.attrs["rows"]
    assert share > 0.5


# ---------------------------------------------------------------------------
# Fold certification and the unrolled emission.
# ---------------------------------------------------------------------------

DECODE_TILES = {
    "dgemm-m1": (lower.build_dgemm, dict(tiles=8, m=1, k=64, n=32)),
    "dgemm-m5": (lower.build_dgemm, dict(tiles=8, m=5, k=64, n=32)),
    "dgemm-m8": (lower.build_dgemm, dict(tiles=8, m=8, k=64, n=32)),
    "mla": (lower.build_mla, dict(lower.MLA_CAPS)),
}


@pytest.mark.parametrize("geometry", GEOMETRIES, ids=["4KB", "16KB"])
@pytest.mark.parametrize("tile", sorted(DECODE_TILES))
def test_decode_tiles_certify(tile, geometry):
    build, params = DECODE_TILES[tile]
    plan = folding.plan(build(**params).program,
                        warm_lines=folding.warm_lines_for(*geometry))
    assert plan is not None and plan.certifiable
    assert plan.kept_fraction < 0.8


def _assert_same_rows(a, b):
    for f in ROW_FIELDS:
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f),
                                      err_msg=f)
    np.testing.assert_array_equal(a.memory, b.memory)


@pytest.mark.parametrize("tiles,m,k,n", [(1, 1, 1, 8), (2, 3, 5, 16),
                                         (3, 8, 7, 8), (4, 2, 33, 24)])
def test_dgemm_repeat_equals_unrolled(tiles, m, k, n):
    rolled = lower.build_dgemm(tiles=tiles, m=m, k=k, n=n)
    flat = lower.build_dgemm(tiles=tiles, m=m, k=k, n=n, unroll=True)
    _assert_same_rows(rolled.program, flat.program)
    assert not flat.program.repeats
    if max(tiles, k, n // 8) > 1:          # count-1 loops drop out
        assert rolled.program.repeats


@pytest.mark.parametrize("params", [
    dict(seqs=1, heads=1, nope=1, rope=8, latent=8, vdim=8, ctx=8),
    dict(seqs=2, heads=3, nope=5, rope=8, latent=16, vdim=8, ctx=16),
    dict(seqs=3, heads=16, nope=2, rope=16, latent=24, vdim=16, ctx=8),
])
def test_mla_repeat_equals_unrolled(params):
    rolled = lower.build_mla(**params)
    flat = lower.build_mla(**params, unroll=True)
    _assert_same_rows(rolled.program, flat.program)
    assert not flat.program.repeats and rolled.program.repeats
    _run(rolled)


def test_mla_tile_keeps_one_score_register_per_head():
    """A latent row loaded once serves every head: the score phase holds
    one accumulator per head live, v4..v(3+heads)."""
    prog = lower.build_mla(seqs=1, heads=16).program
    used = set(prog.active_vregs().tolist())
    assert set(range(4, 20)) <= used


# ---------------------------------------------------------------------------
# The prefill lowering of every registry model, pinned.
# ---------------------------------------------------------------------------

# (units, instances, digest of the unit records, digest of the built tile
# programs, kernels): the lowering as it stood before the decode phase.
PREFILL = {
    "command-r-plus-104b": (6, 513, "6d0cba86d2bc7dc4", "cb0bb3b6215e0a9a", [
        "net:attn:96h128", "net:gemm:12288x1024", "net:gemm:12288x12288",
        "net:gemm:12288x256000", "net:gemm:12288x33792",
        "net:gemm:33792x12288"]),
    "deepseek-v2-lite-16b": (13, 765, "47d736a5a5f37116", "899393cc98189745", [
        "net:attn:16h192", "net:gemm:10944x2048", "net:gemm:1408x2048",
        "net:gemm:2048x102400", "net:gemm:2048x10944", "net:gemm:2048x1408",
        "net:gemm:2048x2048", "net:gemm:2048x2816", "net:gemm:2048x3072",
        "net:gemm:2048x512", "net:gemm:2048x64", "net:gemm:2816x2048",
        "net:gemm:512x2048"]),
    "falcon-mamba-7b": (6, 385, "e369da977815ceaf", "9cffb815c22e7640", [
        "net:gemm:256x8192", "net:gemm:4096x65024", "net:gemm:4096x8192",
        "net:gemm:8192x288", "net:gemm:8192x4096", "net:scan:131072"]),
    "granite-8b": (6, 289, "246b2dfdc1bc92c8", "cb0bb3b6215e0a9a", [
        "net:attn:32h128", "net:gemm:14336x4096", "net:gemm:4096x1024",
        "net:gemm:4096x14336", "net:gemm:4096x4096", "net:gemm:4096x49152"]),
    "phi3-mini-3.8b": (5, 257, "94de61359293095d", "72b29ad19f04492f", [
        "net:attn:32h96", "net:gemm:3072x3072", "net:gemm:3072x32064",
        "net:gemm:3072x8192", "net:gemm:8192x3072"]),
    "phi3.5-moe-42b-a6.6b": (7, 385, "06830f209d607e4d", "67808d6769e88880", [
        "net:attn:32h128", "net:gemm:4096x1024", "net:gemm:4096x16",
        "net:gemm:4096x32064", "net:gemm:4096x4096", "net:gemm:4096x6400",
        "net:gemm:6400x4096"]),
    "qwen2-vl-7b": (7, 226, "2f9c0fe0a74a5c1b", "eb98a7dc9324f864", [
        "net:attn:28h128", "net:gemm:18944x3584", "net:gemm:3584x152064",
        "net:gemm:3584x18944", "net:gemm:3584x3584", "net:gemm:3584x512",
        "net:gemm:588x3584"]),
    "qwen3-8b": (6, 289, "74dd8611a0980922", "cb0bb3b6215e0a9a", [
        "net:attn:32h128", "net:gemm:12288x4096", "net:gemm:4096x1024",
        "net:gemm:4096x12288", "net:gemm:4096x151936", "net:gemm:4096x4096"]),
    "recurrentgemma-2b": (7, 201, "48c271f13ab7910b", "c82d81cf7489141a", [
        "net:attn:10h256", "net:gemm:2560x256", "net:gemm:2560x2560",
        "net:gemm:2560x256000", "net:gemm:2560x7680", "net:gemm:7680x2560",
        "net:scan:2560"]),
    "whisper-large-v3": (7, 611, "ae27b07f55617f2c", "eb98a7dc9324f864", [
        "net:attn:20h64", "net:gemm:1280x1280", "net:gemm:1280x5120",
        "net:gemm:1280x51866", "net:gemm:240x1280", "net:gemm:3840x1280",
        "net:gemm:5120x1280"]),
}


def test_every_registry_model_is_pinned():
    assert sorted(PREFILL) == sorted(registry.ARCHS)


@pytest.mark.parametrize("model", sorted(PREFILL))
def test_prefill_lowering_is_unchanged(model):
    units, instances, unit_digest, program_digest, kernels = PREFILL[model]
    net = bridge.lower_network(model)
    s = net.summary()
    assert (s["units"], s["instances"], s["kernels"]) == (units, instances,
                                                          kernels)
    recs = [[u.kernel, u.kind, list(u.labels), list(u.shape), u.count,
             repr(u.macro_factor), sorted(u.params.items())]
            for u in net.units]
    assert hashlib.sha256(json.dumps(recs).encode()).hexdigest()[:16] \
        == unit_digest
    h = hashlib.sha256()
    for u in net.units:
        bench = common.get_benchmark(u.kernel)
        p = bench.build(**bench.paper_params).program
        for f in ROW_FIELDS + ("memory",):
            h.update(np.ascontiguousarray(getattr(p, f)).tobytes())
        h.update(repr(p.repeats).encode())
    assert h.hexdigest()[:16] == program_digest
