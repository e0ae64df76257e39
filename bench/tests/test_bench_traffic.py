"""Traffic is a function of the seed, and every seed does the same work."""

import numpy as np
import pytest

from harness import spec

SEEDS = [0, 1, 7, 42, 2**31 - 1, 2**31 + 5, 2**33 + 3, 123456789, 99, 5,
         3000000001, 17]


def _sweeps(cell, seed):
    from conftest import BENCH  # noqa: F401  (sys.path set-up)
    drv = spec.driver(cell)
    return drv.Sweeps(cell.traffic, seed)


@pytest.mark.parametrize("name", ["table2.capacity-sweep",
                                  "phi3-mini.rvv-network"])
def test_engine_traffic_is_deterministic_and_never_repeats_a_point(name):
    cell = spec.cell(name)
    a, b = _sweeps(cell, 2**33 + 3), _sweeps(cell, 2**33 + 3)
    assert [a.machine(j) for j in range(len(a))] == \
        [b.machine(j) for j in range(len(b))]
    points = [tuple(sorted(a.machine(j).items())) for j in range(len(a))]
    assert len(set(points)) == len(points)
    assert a.values != _sweeps(cell, 2**33 + 4).values


@pytest.mark.parametrize("name", ["table2.capacity-sweep",
                                  "phi3-mini.rvv-network"])
def test_engine_work_per_sweep_is_the_same_for_every_seed(name):
    """Every seed's sweeps have the same static shape, so the same plan of
    scan buckets; and refinement, the only work a traced latency could
    add, never depends on the latency a seed draws: a kernel above the
    session's refine limit is never refined, and every kernel below it
    carries the same fold certificate at every latency of the pool."""
    from repro import api

    cell = spec.cell(name)
    static = set()
    for seed in SEEDS:
        sw = _sweeps(cell, seed)
        for j in range(3):
            s = sw.make(j)
            static.add((s.kernels, s.capacity, s.policy, s.l1_geometry,
                        s.kernel_params, s.cores))
    assert len(static) == 1
    (kernels, caps, pols, geos, params, _cores), = static

    session = api.Session(batch_programs=False)
    small = [k for k in kernels
             if session.built(k, params).program.num_instructions
             <= session.refine_max_rows]
    m = cell.traffic["machine"]
    probe = api.Sweep(kernels=small, capacity=caps, policy=pols,
                      l1_geometry=geos, kernel_params=params,
                      **{m["vary"]: tuple(m["values"])})
    res = session.run(probe)
    fold = res.data["fold_exact"]
    lat_axis = [a.name for a in res.axes].index(m["vary"])
    per_kernel = np.moveaxis(fold, lat_axis, -1)
    assert (per_kernel == per_kernel[..., :1]).all()


def test_decode_traffic_is_deterministic_and_seed_only_draws_tokens(
        small_decode_cell):
    drv = spec.driver(small_decode_cell)
    v = small_decode_cell.config["vocab_size"]
    a = drv.Requests(small_decode_cell.traffic, v, 2**33 + 3)
    b = drv.Requests(small_decode_cell.traffic, v, 2**33 + 3)
    c = drv.Requests(small_decode_cell.traffic, v, 11)
    n = len(small_decode_cell.traffic["schedule"])
    for i in (0, 1, n - 1, n, 3 * n + 2):
        assert a[i].prompt == b[i].prompt
        assert len(a[i].prompt) == len(c[i].prompt)
        assert a[i].max_new_tokens == c[i].max_new_tokens
    assert a[0].prompt != c[0].prompt
    assert all(1 <= t < v for t in a[5].prompt)
