"""Pallas kernels: shape/dtype sweeps in interpret mode vs the pure-jnp
oracles (assignment requirement: per-kernel allclose against ref.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispersed_gemm, flash_attention, ops, ref, traffic


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("s,d,causal,dtype", [
    (128, 64, False, jnp.float32),
    (128, 64, True, jnp.float32),
    (256, 128, True, jnp.float32),
    (128, 64, True, jnp.bfloat16),
    (256, 64, False, jnp.bfloat16),
])
def test_flash_attention_allclose(s, d, causal, dtype):
    k = jax.random.split(jax.random.PRNGKey(s + d), 3)
    q = _rand(k[0], (1, 2, s, d), dtype)
    kk = _rand(k[1], (1, 2, s, d), dtype)
    v = _rand(k[2], (1, 2, s, d), dtype)
    out = flash_attention.flash_attention(
        q, kk, v, causal=causal, block_q=64, block_k=64, interpret=True)
    want = ref.attention_ref(q, kk, v, causal=causal)
    tol = 2.5e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_gqa_and_cross_lengths():
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(k[0], (2, 8, 128, 64), jnp.float32)
    kk = _rand(k[1], (2, 2, 256, 64), jnp.float32)
    v = _rand(k[2], (2, 2, 256, 64), jnp.float32)
    out = ops.flash_attention(q, kk, v, block_q=64, block_k=64,
                              interpret=True)
    want = ref.attention_ref(q, jnp.repeat(kk, 4, 1), jnp.repeat(v, 4, 1))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("m,k,n,w,dtype", [
    (256, 512, 128, 1, jnp.float32),
    (256, 512, 128, 2, jnp.float32),
    (512, 256, 256, 4, jnp.float32),
    (256, 512, 128, 2, jnp.bfloat16),
])
def test_gemm_grouped_allclose(m, k, n, w, dtype):
    a = _rand(jax.random.PRNGKey(m), (m, k), dtype)
    b = _rand(jax.random.PRNGKey(n), (k, n), dtype)
    got = dispersed_gemm.matmul_grouped(a, b, block_m=128, block_k=256,
                                        working_set=w, interpret=True)
    want = ref.matmul_ref(a, b)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("m,k,n,dtype", [
    (256, 512, 128, jnp.float32),
    (128, 1024, 128, jnp.float32),
    (512, 1024, 256, jnp.bfloat16),
])
def test_gemm_dispersed_allclose(m, k, n, dtype):
    """With nm > 1 m-tiles and nk > 1 k-steps each accumulator tile is
    revisited once per k step with the other m-tiles in between, so every
    revisit must read back what the previous k step spilled to HBM.  The
    interpreter reads revisited blocks back either way; only a run on the
    chip (``chip_smoke.py`` phase (c)) tells the aliased accumulator from
    an output-block revisit."""
    a = _rand(jax.random.PRNGKey(1), (m, k), dtype)
    b = _rand(jax.random.PRNGKey(2), (k, n), dtype)
    got = dispersed_gemm.matmul_dispersed(a, b, block_m=128, block_k=256,
                                          interpret=True)
    want = ref.matmul_ref(a, b)
    tol = 6e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_vmem_footprint_sizes_the_blocks():
    """The scoped-VMEM request is the double-buffered pipeline blocks plus
    the f32 accumulator and one f32 block product, term by term."""
    m, n, k, bm, bk = 512, 8192, 3072, 128, 512
    g = dispersed_gemm.vmem_footprint("grouped", m, n, k, block_m=bm,
                                      block_k=bk, working_set=4)
    assert g == {"a": 2 * bm * bk * 2, "b": 2 * bk * n * 2,
                 "c": 2 * bm * n * 2, "acc": 4 * bm * n * 4,
                 "product": bm * n * 4}
    d = dispersed_gemm.vmem_footprint("dispersed", m, n, k, block_m=bm,
                                      block_k=bk)
    assert d == {"a": 2 * bm * bk * 2, "b": 2 * bk * n * 2,
                 "acc": bm * n * 4, "product": bm * n * 4}
    assert sum(g.values()) < dispersed_gemm.VMEM_CAP_BYTES


def test_gemm_refuses_shapes_over_the_vmem_budget():
    """A full-N B panel too wide for VMEM is refused by name, before the
    chip's compiler would run out of scoped VMEM."""
    a = jax.ShapeDtypeStruct((512, 4096), jnp.bfloat16)
    b = jax.ShapeDtypeStruct((4096, 65536), jnp.bfloat16)
    for fn in (dispersed_gemm.matmul_grouped,
               dispersed_gemm.matmul_dispersed):
        with pytest.raises(ValueError, match="VMEM footprint .* b="):
            jax.eval_shape(fn, a, b)


def test_gemm_grouped_bitwise_independent_of_working_set():
    """The architectural result must not depend on the physical working
    set (the paper's core invariant): the grouped kernel accumulates the
    K reduction in the same f32 order for every W, so the outputs are
    bit-identical, not just allclose."""
    a = _rand(jax.random.PRNGKey(3), (256, 512), jnp.float32)
    b = _rand(jax.random.PRNGKey(4), (512, 128), jnp.float32)
    outs = [np.asarray(dispersed_gemm.matmul_grouped(
        a, b, block_m=64, block_k=128, working_set=w, interpret=True))
        for w in (1, 2, 4)]
    for other in outs[1:]:
        np.testing.assert_array_equal(outs[0], other)


def test_traffic_model_monotone_in_working_set():
    prev = None
    for w in (1, 2, 4, 8, 16, 32):
        t = dispersed_gemm.hbm_traffic_model(4096, 4096, 4096, block_m=128,
                                             block_k=512, working_set=w)
        assert t["grouped"] >= t["ideal"]
        if w >= 4:
            # with a reasonable working set, caching beats HBM round-trips
            assert t["dispersed"] >= t["grouped"]
        if prev is not None:
            assert t["grouped"] <= prev       # more regs => less traffic
        prev = t["grouped"]


def test_traffic_model_closed_forms_pinned():
    """The exact byte counts, term by term — pins the dispersed-B fix
    (B streams once: k*n input-width bytes, no dead nk factor) and the
    f32-width accumulator spill/fill term."""
    m, n, k, bm, bk, bpe = 256, 128, 512, 64, 128, 2
    nm, nk = m // bm, k // bk
    t = dispersed_gemm.hbm_traffic_model(m, n, k, block_m=bm, block_k=bk,
                                         working_set=2, bytes_per_el=bpe)
    assert t["grouped"] == (m * k + (nm // 2) * k * n + m * n) * bpe
    assert t["dispersed"] == (m * k + k * n) * bpe + 2 * m * n * nk * 4
    assert t["ideal"] == (m * k + k * n + m * n) * bpe
    assert t["vmem_acc_bytes"] == 2 * bm * n * 4


def test_traffic_model_rejects_what_the_kernel_rejects():
    """Model legality == kernel legality: a working_set that does not
    divide the m-tile count used to be silently floor-divided into an
    undercounted ``groups``; both sides now raise the same ValueError."""
    a = _rand(jax.random.PRNGKey(5), (256, 512), jnp.float32)
    b = _rand(jax.random.PRNGKey(6), (512, 128), jnp.float32)
    with pytest.raises(ValueError, match="working_set"):
        dispersed_gemm.hbm_traffic_model(256, 128, 512, block_m=64,
                                         block_k=128, working_set=3)
    with pytest.raises(ValueError, match="working_set"):
        dispersed_gemm.matmul_grouped(a, b, block_m=64, block_k=128,
                                      working_set=3, interpret=True)
    with pytest.raises(ValueError, match="working_set"):
        dispersed_gemm.hbm_traffic_model(256, 128, 512, block_m=64,
                                         block_k=128, working_set=0)


@pytest.mark.parametrize("w", [1, 2, 4])
def test_counted_traffic_matches_model_grouped(w):
    kw = dict(block_m=64, block_k=128, working_set=w, bytes_per_el=2)
    model = dispersed_gemm.hbm_traffic_model(256, 128, 512, **kw)
    counted = traffic.count(
        dispersed_gemm.grouped_schedule(256, 128, 512, **kw))
    assert counted["total"] == model["grouped"]


def test_counted_traffic_matches_model_dispersed_and_flash():
    model = dispersed_gemm.hbm_traffic_model(
        256, 128, 512, block_m=64, block_k=128, working_set=1)
    counted = traffic.count(dispersed_gemm.dispersed_schedule(
        256, 128, 512, block_m=64, block_k=128))
    assert counted["total"] == model["dispersed"]
    fm = flash_attention.hbm_traffic_model(
        2, 2, 256, 256, 64, block_q=64, block_k=64)
    fc = traffic.count(flash_attention.flash_schedule(
        2, 2, 256, 256, 64, block_q=64, block_k=64))
    assert fc["total"] == fm["flash"]
    assert fm["flash"] >= fm["ideal"]
    assert fm["materialized"] >= fm["flash"]   # fusing beats spilling S


def test_traffic_model_int8_bytes_per_el():
    """The int8 roofline point's byte accounting: operands at one byte per
    element (bytes_per_el=1) while the dispersed accumulator spill/fill
    stays f32-wide; counted == closed form for all three schedules."""
    m, n, k, bm, bk = 256, 128, 512, 64, 128
    nm, nk = m // bm, k // bk
    kw = dict(block_m=bm, block_k=bk, working_set=2, bytes_per_el=1)
    t = dispersed_gemm.hbm_traffic_model(m, n, k, **kw)
    assert t["grouped"] == m * k + (nm // 2) * k * n + m * n
    assert t["dispersed"] == (m * k + k * n) + 2 * m * n * nk * 4
    assert traffic.count(dispersed_gemm.grouped_schedule(
        m, n, k, **kw))["total"] == t["grouped"]
    assert traffic.count(dispersed_gemm.dispersed_schedule(
        m, n, k, block_m=bm, block_k=bk,
        bytes_per_el=1))["total"] == t["dispersed"]
    fm = flash_attention.hbm_traffic_model(
        1, 2, 128, 128, 64, block_q=64, block_k=64, bytes_per_el=1)
    fc = traffic.count(flash_attention.flash_schedule(
        1, 2, 128, 128, 64, block_q=64, block_k=64, bytes_per_el=1))
    assert fc["total"] == fm["flash"]


def test_kernel_shape_errors_name_the_dimension():
    a = _rand(jax.random.PRNGKey(7), (200, 512), jnp.float32)
    b = _rand(jax.random.PRNGKey(8), (512, 128), jnp.float32)
    with pytest.raises(ValueError, match="m=200"):
        dispersed_gemm.matmul_grouped(a, b, block_m=128, block_k=128,
                                      interpret=True)
    with pytest.raises(ValueError, match="m=200"):
        dispersed_gemm.matmul_dispersed(a, b, block_m=128, block_k=128,
                                        interpret=True)
    bad_b = _rand(jax.random.PRNGKey(9), (256, 128), jnp.float32)
    with pytest.raises(ValueError, match="k=512"):
        dispersed_gemm.matmul_grouped(a[:128], bad_b, interpret=True)
    q = _rand(jax.random.PRNGKey(10), (1, 2, 200, 64), jnp.float32)
    with pytest.raises(ValueError, match="sq=200"):
        flash_attention.flash_attention(q, q, q, block_q=128, block_k=128,
                                        interpret=True)
    with pytest.raises(ValueError, match="multiple"):
        ops.flash_attention(q, q[:, :1][:, [0, 0, 0]], q[:, :3],
                            interpret=True)


@pytest.mark.parametrize("rows,d,dtype", [
    (256, 512, jnp.float32), (128, 1024, jnp.bfloat16),
])
def test_rmsnorm_kernel_allclose(rows, d, dtype):
    from repro.kernels import rmsnorm as rn
    from repro.models import common as mc
    x = _rand(jax.random.PRNGKey(7), (2, rows // 2, d), dtype)
    scale = 1.0 + 0.1 * _rand(jax.random.PRNGKey(8), (d,), jnp.float32)
    got = rn.rmsnorm(x, scale, block_rows=64, interpret=True)
    want = mc.rmsnorm({"scale": scale}, x)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
