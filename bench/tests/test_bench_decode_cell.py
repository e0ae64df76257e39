"""The DeepSeek-V2-Lite decode cell: its pool, its lowered network against
the configuration's reference, the reader of its per-layer metric, and one
whole run of ``engine_sweep`` on the CPU at a cut grid."""

import copy
import types

import pytest

from conftest import run_small
from harness import spec

CELL = "deepseek-v2-lite.rvv-decode"


def test_the_pool_holds_128_distinct_latencies():
    """127 window sweeps (sweep 0 is set-up), none at a repeated point,
    in an order each seed draws."""
    cell = spec.cell(CELL)
    m = cell.traffic["machine"]
    assert m["vary"] == "mem_latency"
    assert sorted(m["values"]) == list(range(1, 129))
    drv = spec.driver(cell)
    a, b = drv.Sweeps(cell.traffic, 2**33 + 3), drv.Sweeps(cell.traffic, 5)
    assert len(a) == 128
    assert len({a.machine(j)["mem_latency"] for j in range(len(a))}) == 128
    assert a.values != b.values


def test_the_lowered_network_is_the_references():
    """What ``engine_sweep`` compares every sweep: the lowering of the
    traffic's network spec against the configuration reference's layer
    list."""
    from repro import bridge
    cell = spec.cell(CELL)
    (net_spec,) = cell.traffic["network"]
    net = bridge.lower_network(net_spec).summary()
    want = spec.config_reference(cell).lowered_summary(cell.config)
    assert dict(kernels=sorted(net["kernels"]), units=net["units"],
                instances=net["instances"]) == want
    # the spec states the configuration's decode step
    dec = cell.config["decode"]
    assert net_spec == (f"{cell.config['name']}:decode:batch={dec['batch']}"
                        f":context={dec['context']}"
                        f":zipf={dec['routing_zipf']}"
                        f":seed={dec['routing_seed']}")


def _span(name, id, parent=None, **attrs):
    return types.SimpleNamespace(name=name, start_ns=0, end_ns=1, id=id,
                                 parent=parent, attrs=attrs)


def test_the_row_share_reader_reads_a_synthetic_window():
    """Two traced sweeps, two L1 geometries each: the MLA and expert rows
    of every ``session.prepare`` span over the rows ``engine_sweep``
    counted."""
    reader = spec.metric_reader("engine.mla_moe_row_share")
    prepares = [_span("session.prepare", i, rows=1000,
                      **{"rows:net:mla": 500, "rows:net:expert": 200,
                         "rows:net:dgemm": 300})
                for i in range(4)]
    spans = [_span("session.run", 10)] + prepares
    rec = dict(program_spans=spans, counts=dict(rows=4000))
    assert reader.read(rec) == pytest.approx(70.0)
    # a program whose spans lack the row counters, or that has no spans,
    # reads nothing
    bare = [_span("session.prepare", 0, hits=1, misses=0)]
    assert reader.read(dict(program_spans=bare,
                            counts=dict(rows=4000))) is None
    assert reader.read(dict(program_spans=[],
                            counts=dict(rows=4000))) is None
    # no MLA or expert kernel in the sweeps: 0 %
    other = [_span("session.prepare", 0, rows=10, **{"rows:gemv": 10})]
    assert reader.read(dict(program_spans=other,
                            counts=dict(rows=10))) == 0.0


@pytest.fixture
def cut_cell():
    """The cell at two capacities, the 4 KB L1 and a pool of three
    latencies: the whole decode network, one window sweep on the CPU."""
    cell = copy.deepcopy(spec.cell(CELL))
    cell.traffic.update(capacity=[3, 32], l1_kb=[4])
    cell.traffic["machine"]["values"] = [3, 9, 27]
    return cell


def test_a_run_on_the_cpu_is_correct(cut_cell):
    line, ctx = run_small(cut_cell, seed=2**33 + 3, seconds=0)
    assert line["correct"], line["checks"]
    checks = line["checks"]
    assert checks["exact_mismatches"]["value"] == 0
    assert checks["network_mismatches"]["value"] == 0
    assert checks["window_compiles"]["value"] == 0
    assert all(p["certified"] for p in ctx.details["points"])
    assert len(ctx.details["points"]) == 26
