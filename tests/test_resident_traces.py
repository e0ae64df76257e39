"""Trace inputs stay on the device across sweeps (``ResidentTraces``).

A session packs each bucket group's traces on the host and copies them to
the device once; a later sweep of the same kernels at a new machine point
copies only its configuration and machine parameters, and its counters
are those of a fresh session.  Each case runs through the XLA scan and
through the Pallas kernel in the interpreter.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, obs, rvv
from repro.core import scan_tpu, simulator

KERNELS = ("gemv", "dropout", "pathfinder")


@pytest.fixture(params=["xla", "pallas"])
def impl(request, monkeypatch):
    if request.param == "pallas":
        monkeypatch.setattr(simulator, "scan_impl", lambda *a: "pallas")
        monkeypatch.setattr(simulator, "_run_grid_pallas", functools.partial(
            simulator._run_grid_pallas, interpret=True))
    return request.param


def _sweep(mem_latency):
    return api.Sweep(kernels=KERNELS, capacity=(3, 8),
                     mem_latency=(mem_latency,), kernel_params="reduced")


def _params_bytes(impl, configs, machines):
    """What one engine call copies besides its traces: the (8, C * M)
    int32 lane block, or the config (int32, int32, bool) and machine
    (3 x int32) arrays."""
    if impl == "pallas":
        return 8 * 4 * configs * machines
    return configs * (4 + 4 + 1) + machines * 3 * 4


def _run_recorded(ses, sweep):
    obs.reset()
    with obs.recording():
        res = ses.run(sweep)
    dispatches = [s for s in obs.spans() if s.name == "engine.dispatch"]
    obs.reset()
    return res, dispatches


@pytest.mark.parametrize("fused", [False, True])
def test_a_second_sweep_finds_every_trace_resident(impl, fused):
    """Sweep 2 of the same kernels at a new memory latency copies no
    trace, only each call's parameters, and its counters are those of a
    fresh session at that latency, bit for bit."""
    ses = api.Session(batch_programs=fused)
    _, first = _run_recorded(ses, _sweep(5))
    assert first and not any(s.attrs["resident"] for s in first)
    assert all(s.attrs["upload_bytes"] > _params_bytes(impl, 2, 1)
               for s in first)
    got, second = _run_recorded(ses, _sweep(9))
    assert len(second) == len(first)
    assert all(s.attrs["resident"] for s in second)
    assert all(s.attrs["impl"] == impl for s in second)
    assert [s.attrs["upload_bytes"] for s in second] == \
        [_params_bytes(impl, 2, 1)] * len(second)
    want = api.Session(batch_programs=fused).run(_sweep(9))
    assert sorted(got.keys()) == sorted(want.keys())
    for k in want.keys():
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def test_reset_empties_the_map(impl):
    ses = api.Session()
    ses.run(_sweep(5))
    assert len(ses._resident) == len(KERNELS)
    assert ses._resident.nbytes > 0
    ses.reset()
    assert len(ses._resident) == 0 and ses._resident.nbytes == 0
    _, dispatches = _run_recorded(ses, _sweep(9))
    assert not any(s.attrs["resident"] for s in dispatches)


def _prepared(name, fold=False):
    b = rvv.BENCHMARKS[name]
    return simulator.prepare(b.build(**b.reduced_params).program, fold=fold)


def test_the_map_evicts_the_least_recently_used_entry(impl):
    """Past its budget the map drops the entry used longest ago, and
    keeps the one just used again."""
    dev = jax.devices()[0]
    a, b, c = ([_prepared(n)] for n in KERNELS)
    size = {id(g): simulator.ResidentTraces().fetch(g, impl, dev)[1]
            for g in (a, b, c)}
    budget = max(size[id(a)] + size[id(b)], size[id(a)] + size[id(c)])
    res = simulator.ResidentTraces()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(simulator, "_resident_budget", lambda device: budget)
        for g in (a, b, a, c):
            res.fetch(g, impl, dev)
        assert len(res) == 2 and res.nbytes == size[id(a)] + size[id(c)]
        assert res.fetch(a, impl, dev)[1] == 0
        assert res.fetch(c, impl, dev)[1] == 0
        assert res.fetch(b, impl, dev)[1] == size[id(b)]


def test_an_over_budget_map_still_returns_the_counters(impl, monkeypatch):
    """A budget smaller than any entry keeps only the group dispatched
    last: every dispatch of the next sweep copies its traces again, and
    the counters are those of a session that kept them all."""
    want = api.Session().run(_sweep(9))
    ses = api.Session()
    monkeypatch.setattr(simulator, "_resident_budget", lambda device: 1)
    ses.run(_sweep(5))
    assert len(ses._resident) == 1
    got, dispatches = _run_recorded(ses, _sweep(9))
    assert not any(s.attrs["resident"] for s in dispatches)
    for k in want.keys():
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


@jax.jit
def _device_sums(arrays, weight):
    """The trace-only counters as the device sums them, in int32."""
    rv, _, vdw, vdr, _, _, _, mv, _, mw, cost = arrays[:11]
    i32 = lambda x: x.astype(jnp.int32)
    per_row = jnp.stack([
        cost, i32(rv[..., 0]) + i32(rv[..., 1]) + i32(rv[..., 2] & vdr),
        i32(rv[..., 2] & vdw),
        i32(mv[..., 0] & ~mw[..., 0]) + i32(mv[..., 1] & ~mw[..., 1]),
        i32(mv[..., 0] & mw[..., 0]) + i32(mv[..., 1] & mw[..., 1])], -1)
    return jnp.sum(per_row * weight[..., None], axis=1)


@pytest.mark.parametrize("scale", [1, 2**20])
def test_host_packing_matches_the_stacked_arrays(scale):
    """The host-packed row words are ``_pack_rows`` of the padded stack,
    and the host's trace-only sums are the device's int32 sums bit for
    bit; at ``scale`` 2**20 the weighted sums wrap past 2**31."""
    preps = [_prepared("dropout", fold=True), _prepared("gemv"),
             _prepared("flashattention2")]
    preps = [dataclasses.replace(p, weight=p.weight * scale,
                                 wa=p.wa * scale, wb=p.wb * scale)
             for p in preps]
    arrays, _, _ = simulator._stack(preps)
    bucket = arrays[0].shape[1]
    rows, sums = scan_tpu.pack([simulator._columns(p) for p in preps],
                               simulator._FILLS, bucket)
    np.testing.assert_array_equal(
        rows, scan_tpu._pack_rows(arrays).reshape(-1))
    assert rows.dtype == sums.dtype == np.int32
    for a, w in enumerate(arrays[-3:]):
        want = np.asarray(_device_sums(tuple(map(jnp.asarray, arrays)),
                                       jnp.asarray(w)))
        np.testing.assert_array_equal(sums[a], want)
        np.testing.assert_array_equal(
            scan_tpu._trace_counters(arrays, w), want)
    if scale > 1:                   # the weighted cost sums did wrap
        exact = (arrays[10].astype(np.int64) * arrays[12]).sum(axis=1)
        assert np.abs(exact).max() > 2**31
