"""Profiler trace -> device busy time, idle gaps and top device ops.

``capture`` runs the JAX profiler around a block.  ``read`` takes the
newest ``.xplane.pb`` below a directory apart into plain lists: per device
the intervals of every operation event, and the host spans that the
benchmark annotated (``bench:<name>``).  The reduction below works on
those lists alone, so it is tested on synthetic traces.

Busy time is the union of all operation intervals on a device, never the
sum and never a subset of ops, so an idle share cannot fall below 0 and a
busy share cannot pass 100 %.
"""

from __future__ import annotations

import contextlib
import dataclasses
import pathlib

import numpy as np

from harness.spans import PREFIX

# Lines of a device plane that are summaries of other lines, not ops.
_SUMMARY_LINES = ("Steps", "XLA Modules", "Framework Name Scope",
                  "Framework Ops", "Source code", "XLA TraceMe")
# A plane with more op events than this (a scan traces every op of every
# iteration) is read from its module line instead: one event per
# executable run, covering its ops.
MAX_OP_EVENTS = 2_000_000


@dataclasses.dataclass
class Trace:
    devices: dict            # plane -> [(line, start_ns, dur_ns, name)]
    spans: list              # (name, start_ns, end_ns) of bench: spans
    line_names: dict         # plane name -> line names, for inspection

    def window(self, name: str = "window") -> tuple[int, int]:
        """The first span of this name: the traced window."""
        for n, a, b in self.spans:
            if n == name:
                return a, b
        raise KeyError(f"no span {name!r} in the trace")


@contextlib.contextmanager
def capture(log_dir: pathlib.Path):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _events(lines, keep, cap=None):
    """(line, start_ns, duration_ns, name) of the kept lines' events;
    None once more than ``cap``."""
    out = []
    for ln in lines:
        if keep(ln.name):
            for ev in ln.events:
                out.append((ln.name, ev.start_ns, ev.duration_ns, ev.name))
                if cap is not None and len(out) > cap:
                    return None
    return out


def read(log_dir: pathlib.Path) -> Trace:
    from jax.profiler import ProfileData
    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(str(files[-1]))
    devices, spans, line_names = {}, [], {}
    for plane in data.planes:
        lines = list(plane.lines)
        line_names[plane.name] = [ln.name for ln in lines]
        if plane.name.startswith("/device:"):
            events = _events(lines, lambda n: n not in _SUMMARY_LINES,
                             MAX_OP_EVENTS)
            if events is None:
                events = _events(lines, lambda n: n == "XLA Modules")
            if events:
                devices[plane.name] = events
        else:
            for ln in lines:
                for ev in ln.events:
                    if ev.name.startswith(PREFIX):
                        spans.append((ev.name[len(PREFIX):], ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    spans.sort(key=lambda s: s[1])
    return Trace(devices, spans, line_names)


# ---------------------------------------------------------------------------
# Reduction (pure functions of interval lists).
# ---------------------------------------------------------------------------


def union(starts, ends) -> np.ndarray:
    """Merge intervals; returns an (n, 2) array of disjoint intervals."""
    starts = np.asarray(starts, np.float64)
    ends = np.asarray(ends, np.float64)
    if starts.size == 0:
        return np.zeros((0, 2))
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.r_[first[1:] - 1, s.size - 1]
    return np.stack([s[first], reach[last]], axis=1)


def clip(merged: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if merged.size == 0:
        return merged
    c = np.stack([np.maximum(merged[:, 0], lo),
                  np.minimum(merged[:, 1], hi)], axis=1)
    return c[c[:, 1] > c[:, 0]]


def busy(merged: np.ndarray, lo: float, hi: float) -> float:
    """Nanoseconds inside [lo, hi) covered by the merged intervals."""
    c = clip(merged, lo, hi)
    return float((c[:, 1] - c[:, 0]).sum()) if c.size else 0.0


def gaps(merged: np.ndarray, lo: float, hi: float) -> list:
    """Idle (start, end) intervals inside [lo, hi)."""
    c = clip(merged, lo, hi)
    edges = np.r_[lo, c.ravel(), hi].reshape(-1, 2) if c.size \
        else np.asarray([[lo, hi]])
    return [(float(a), float(b)) for a, b in edges if b > a]


def name_gap(gap: tuple, spans: list) -> str:
    """The innermost host span open at the gap's midpoint."""
    mid = (gap[0] + gap[1]) / 2
    best = None
    for name, a, b in spans:
        if a <= mid < b and (best is None or a >= best[1]):
            best = (name, a)
    return best[0] if best else "outside spans"


def idle_gaps(merged: np.ndarray, lo: float, hi: float, spans: list,
              top: int = 10) -> list:
    """The longest idle gaps, each named by the host span open in it:
    ``[[name, seconds], ...]``."""
    gs = sorted(gaps(merged, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return [[name_gap(g, spans), (g[1] - g[0]) / 1e9] for g in gs]


def top_ops(starts, ends, names, lo: float, hi: float,
            top: int = 10) -> list:
    """Device operations by total time inside [lo, hi):
    ``[[name, seconds], ...]``."""
    s = np.maximum(np.asarray(starts), lo)
    e = np.minimum(np.asarray(ends), hi)
    dur = np.clip(e - s, 0, None)
    total: dict[str, float] = {}
    for n, d in zip(names, dur):
        if d > 0:
            total[n] = total.get(n, 0.0) + float(d)
    best = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return [[n, d / 1e9] for n, d in best]


def span_busy(merged: np.ndarray, spans: list, name: str) -> list:
    """(wall_ns, device_busy_ns) for every span of this name."""
    return [(b - a, busy(merged, a, b)) for n, a, b in spans if n == name]


@dataclasses.dataclass
class Reduced:
    """What the metric readers see of a traced window."""

    window_ns: float
    busy_ns: float           # averaged over the devices used
    merged: np.ndarray       # first device's merged busy intervals
    spans: list              # bench spans inside the window
    device_ops: list
    idle_gaps: list


def _intervals(events):
    starts = np.asarray([e[1] for e in events], np.float64)
    return starts, starts + np.asarray([e[2] for e in events], np.float64)


def reduce(tr: Trace, n_devices: int = 1) -> Reduced:
    """Busy time from every event of every device line; the top ops from
    the ``XLA Ops`` line where the device has one."""
    lo, hi = tr.window()
    planes = sorted(tr.devices)[:max(1, n_devices)]
    if not planes:
        raise ValueError("the trace holds no device operations")
    merged = [union(*_intervals(tr.devices[p])) for p in planes]
    busy_ns = float(np.mean([busy(m, lo, hi) for m in merged]))
    spans = [s for s in tr.spans if s[2] > lo and s[1] < hi]
    events = tr.devices[planes[0]]
    ops = [e for e in events if e[0] == "XLA Ops"] or events
    starts, ends = _intervals(ops)
    names = [e[3] for e in ops]
    return Reduced(
        window_ns=hi - lo, busy_ns=busy_ns, merged=merged[0], spans=spans,
        device_ops=top_ops(starts, ends, names, lo, hi),
        idle_gaps=idle_gaps(merged[0], lo, hi,
                            [s for s in spans if s[0] != "window"]))
