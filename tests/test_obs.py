"""Spans (``repro.obs``): off by default, nested while recording, on the
profiler's clock, and placed at the engine's layer boundaries."""

import pathlib

import pytest

from repro import api, obs


@pytest.fixture(autouse=True)
def clean_spans():
    obs.reset()
    yield
    obs.reset()


def by_name(spans, name):
    return [s for s in spans if s.name == name]


def test_nothing_is_recorded_when_off():
    with obs.span("a", x=1) as sp:
        sp.set(y=2)
        assert not sp
    assert obs.spans() == []


def test_recording_keeps_nested_spans_with_their_parents():
    with obs.recording():
        with obs.span("outer", kind="o") as outer:
            assert outer
            with obs.span("inner") as inner:
                inner.set(rows=3, done=True)
            with obs.span("second"):
                pass
        with obs.span("root2"):
            pass
    assert not obs.span("after")
    got = {s.name: s for s in obs.spans()}
    assert [s.name for s in obs.spans()] == ["outer", "inner", "second",
                                             "root2"]
    assert got["outer"].parent is None and got["root2"].parent is None
    assert got["inner"].parent == got["outer"].id == outer.rec.id
    assert got["second"].parent == got["outer"].id
    assert got["inner"].attrs == dict(rows=3, done=True)
    assert got["outer"].attrs == dict(kind="o")
    for s in obs.spans():
        assert 0 <= s.start_ns <= s.end_ns and s.wall_ns >= 0
    assert got["outer"].start_ns <= got["inner"].start_ns
    assert got["inner"].end_ns <= got["second"].start_ns
    assert got["second"].end_ns <= got["outer"].end_ns
    obs.reset()
    assert obs.spans() == []


def test_spans_record_under_the_profiler_on_its_clock(tmp_path):
    """While a profiler trace runs, spans are recorded without
    ``recording()``, and each one's ``repro:`` twin in the ``.xplane.pb``
    (offsets from the trace's ``profile_start_time``) lies within 1 ms of
    the recorded stamps."""
    import time

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(3):
            with obs.span(f"phase{i}"):
                with obs.span("inner"):
                    time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    recorded = obs.spans()
    assert len(recorded) == 6
    path = sorted(pathlib.Path(tmp_path).rglob("*.xplane.pb"))[-1]
    data = ProfileData.from_file(str(path))
    start = None
    events = []
    for plane in data.planes:
        for key, value in plane.stats:
            if key == "profile_start_time":
                start = int(value)
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(obs.PREFIX):
                    events.append((ev.name[len(obs.PREFIX):],
                                   int(ev.start_ns), int(ev.duration_ns)))
    assert start is not None
    events.sort(key=lambda e: e[1])
    assert [e[0] for e in events] == [s.name for s in recorded]
    for s, (_, off, dur) in zip(recorded, events):
        assert abs(start + off - s.start_ns) < 1_000_000
        assert abs(start + off + dur - s.end_ns) < 1_000_000


def _check_run(spans, meta, *, fused, geometries):
    roots = [s for s in spans if s.parent is None]
    assert [s.name for s in roots] == ["session.run"]
    run = roots[0]
    assert run.attrs["points"] == meta["points"]
    assert run.attrs["geometries"] == geometries
    assert len(by_name(spans, "session.prepare")) == geometries
    assert len(by_name(spans, "session.assemble")) == 1
    dispatch = by_name(spans, "engine.dispatch")
    assert sum(s.attrs["dispatches"] for s in dispatch) == \
        meta["dispatches"]
    assert all(s.attrs["engine"] == "core" for s in dispatch)
    refine_ids = {s.id for s in by_name(spans, "session.refine")}
    planned = [s for s in dispatch if s.parent not in refine_ids]
    want = [e["bucket"] * (1 if fused else len(e["kernels"]))
            for e in meta["plan"]]
    assert sum(s.attrs["steps"] for s in planned) == sum(want)
    assert len(planned) == len(meta["plan"])
    assert len(spans) <= 5 + 3 * meta["dispatches"] + geometries
    for s in spans:
        assert run.start_ns <= s.start_ns <= s.end_ns <= run.end_ns


@pytest.mark.parametrize("fused", [False, True])
def test_session_run_spans(fused):
    ses = api.Session(batch_programs=fused)
    sweep = api.Sweep(kernels=("gemv", "dropout", "somier"),
                      capacity=(3, 8), mem_latency=(1, 5),
                      l1_geometry=(api.L1Geometry(256, 2),
                                   api.L1Geometry(128, 2)),
                      kernel_params="reduced")
    with obs.recording():
        res = ses.run(sweep)
    spans = obs.spans()
    _check_run(spans, res.meta, fused=fused, geometries=2)
    prep = by_name(spans, "session.prepare")
    assert [s.attrs["misses"] for s in prep] == [3, 3]
    stacks = by_name(spans, "engine.stack")
    assert len(stacks) == res.meta["dispatches"]
    assert all(s.attrs["bytes"] > 0 for s in stacks)
    for s in by_name(spans, "engine.dispatch"):
        assert s.attrs["lanes"] == s.attrs["programs"] * 2 * 2
    # A second run of the same sweep prepares nothing and compiles nothing.
    obs.reset()
    with obs.recording():
        res = ses.run(sweep)
    spans = obs.spans()
    _check_run(spans, res.meta, fused=fused, geometries=2)
    assert [s.attrs["hits"] for s in by_name(spans, "session.prepare")] \
        == [3, 3]
    assert not any(s.attrs["compiled"]
                   for s in by_name(spans, "engine.dispatch"))


@pytest.mark.parametrize("fused", [False, True])
def test_refinement_dispatches_nest_in_the_refine_span(fused):
    params = {"n": 8192, "scale": 0.5}
    ses = api.Session(batch_programs=fused)
    # A folded trace whose certificate cannot hold is re-simulated whole.
    ses.prepared("dropout", params=params).certifiable = False
    with obs.recording():
        res = ses.run(api.Sweep(kernels=("dropout",), capacity=(3,),
                                kernel_params=params))
    spans = obs.spans()
    _check_run(spans, res.meta, fused=fused, geometries=1)
    (refine,) = by_name(spans, "session.refine")
    assert refine.attrs["programs"] == 1
    inner = [s for s in by_name(spans, "engine.dispatch")
             if s.parent == refine.id]
    assert [s.attrs["rows"] for s in inner] == [6144]
    assert by_name(spans, "session.prepare")[0].attrs["hits"] == 1


def test_cluster_dispatch_span():
    ses = api.Session(batch_programs=True)
    with obs.recording():
        res = ses.run(api.Sweep(kernels=("gemv",), capacity=(3,),
                                cores=(2,), kernel_params="reduced"))
    (d,) = by_name(obs.spans(), "engine.dispatch")
    assert d.attrs["engine"] == "cluster"
    assert d.attrs["dispatches"] == res.meta["dispatches"] == 1
    assert d.attrs["steps"] == res.meta["plan"][0]["bucket"]


def test_network_sweep_records_one_lowering():
    with obs.recording():
        sweep = api.Sweep(network=("phi3-mini-3.8b",), capacity=(3,))
    (lower,) = obs.spans()
    assert lower.name == "bridge.lower" and lower.parent is None
    assert lower.attrs["model"] == "phi3-mini-3.8b"
    assert lower.attrs["kernels"] == len(sweep.kernels)
    assert lower.attrs["ops"] > 0
