"""Per-layer metrics from the program's own spans (``repro.obs``).

The program records its spans in memory while the profiler runs, so after
a ``--trace 1`` run this process holds those of the traced sweeps:
``recorded()`` returns them.  Each metric below takes a list of span
records (``name``, ``start_ns``, ``end_ns``, ``id``, ``parent``,
``attrs``) and returns None when there is nothing to read, as from a
program that has no spans.  None of them reads the profiler trace.

- ``dispatch_us_per_step``: ``engine.dispatch`` spans run from the call to
  the counters on the host, so their walls hold the device's work; those
  that compiled nothing, over the bucket rows they scanned, in us.
- ``run_host_ms``: the host self-time of a sweep, ``session.run`` wall
  minus the walls of the ``engine.dispatch`` spans under it, mean over
  sweeps, in ms.
- ``lower_ms``: mean wall of ``bridge.lower`` (one model lowered), in ms.
"""

from __future__ import annotations


def recorded() -> list:
    """The spans the program recorded in this process; [] when the
    program has no ``repro.obs``."""
    try:
        from repro import obs
    except ImportError:
        return []
    return obs.spans()


def _wall(s) -> int:
    return s.end_ns - s.start_ns


def dispatch_us_per_step(spans) -> float | None:
    warm = [s for s in spans
            if s.name == "engine.dispatch" and not s.attrs.get("compiled")]
    steps = sum(s.attrs["steps"] for s in warm)
    if not steps:
        return None
    return sum(_wall(s) for s in warm) / steps / 1e3


def run_host_ms(spans) -> float | None:
    runs = {s.id: s for s in spans if s.name == "session.run"}
    if not runs:
        return None
    parent = {s.id: s.parent for s in spans}
    device = dict.fromkeys(runs, 0)
    for s in spans:
        if s.name != "engine.dispatch":
            continue
        up = s.parent
        while up is not None and up not in runs:
            up = parent.get(up)
        if up is not None:
            device[up] += _wall(s)
    return sum(_wall(r) - device[i] for i, r in runs.items()) \
        / len(runs) / 1e6


def lower_ms(spans) -> float | None:
    walls = [_wall(s) for s in spans if s.name == "bridge.lower"]
    if not walls:
        return None
    return sum(walls) / len(walls) / 1e6
