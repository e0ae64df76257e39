"""One front door for the paper's design-space sweeps.

Every result in the paper — Fig 4's capacity sweep, Table 3's speedups, the
memory-system ablation — is a point grid over the same named axes:

  ``kernel``        benchmark name from the :mod:`repro.rvv` registry
  ``capacity``      physical registers in the compact VRF
  ``policy``        replacement policy (int constant or ``"fifo"``-style name)
  ``alloc_no_fetch``  beyond-paper write-allocate optimisation
  ``l1_geometry``   static L1 shape (:class:`L1Geometry`) — sizes the L1
                    state arrays, so each value is its own compiled engine
  ``cores``         static cluster-size axis (N lockstep dispersion cores
                    behind a shared L2, :mod:`repro.cluster`) — like the
                    geometry, N sizes the engine state, so each value is
                    its own compiled engine; present only when requested
  ``mem_latency`` / ``l1_hit_cycles`` / ``uop_hit_cycles``
                    traced machine-latency axes (never recompile)

A :class:`Sweep` declares values for those axes; a :class:`Session` executes
it.  ``Session.run`` plans the execution: points are grouped into one fused
engine call per (program-shape bucket, L1 geometry) — the static geometry
axis becomes an orchestrated outer loop inside the planner instead of a
hand-rolled loop in user code — and the traced latency grid rides inside
each dispatch.  The result is a :class:`SweepResult` with labeled axes,
per-point counters and per-point ``fold_exact`` certificates, plus
``to_rows()`` / ``select()`` / ``value()`` accessors so suites never do
index arithmetic on raw (P, C, M) arrays again — and the metric algebra
(``derive`` / ``normalize`` / ``pareto``, evaluated by the
:mod:`repro.metrics` registry) so they never hand-roll derived
quantities either.

The Session owns every cache the old module-global benchmark layer held:
built kernels, prepared (expanded + folded) traces, the fold/refine policy,
and compile/dispatch accounting (``compile_count()`` — the probe the
planner tests pin).  Two Sessions share nothing except XLA's process-level
executable cache, which is keyed only on shapes and static geometry.

Legacy entry points (``simulator.simulate_sweep``, the benchmark layer's
``prepared_for(max_events=...)`` truncation) are deprecation shims routed
through this module — see ``docs/api.md`` for the migration table.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from repro import obs
from repro.core import folding, policies, simulator
from repro.core.simulator import (DEFAULT_MACHINE, MachineSweep,
                                  SweepConfig)

__all__ = [
    "L1Geometry", "ConfigPoint", "Axis", "Sweep", "SweepResult", "Session",
    "default_session", "reset_default_session", "sweep_program",
    "REFINE_MAX_ROWS",
]

# A folded trace whose steadiness check fails is re-simulated in full when
# the full trace is affordable; bigger traces keep the (flagged) fold.
REFINE_MAX_ROWS = 400_000


# ---------------------------------------------------------------------------
# Axis value types.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class L1Geometry:
    """Static L1 data-cache shape: ``sets`` x ``ways`` lines of 32 bytes.

    These two fields size the engine's L1 state arrays, so every distinct
    geometry is a separate compiled executable — which is exactly why the
    planner treats this axis as its outer loop rather than a traced one.
    """

    sets: int = 256
    ways: int = 2

    LINE_BYTES = 32

    @classmethod
    def from_kbytes(cls, kbytes: int, ways: int = 2) -> "L1Geometry":
        return cls(kbytes * 1024 // cls.LINE_BYTES // ways, ways)

    @property
    def kbytes(self) -> int:
        return self.sets * self.ways * self.LINE_BYTES // 1024

    def __str__(self) -> str:
        return f"{self.kbytes}KB/{self.ways}w"


@dataclasses.dataclass(frozen=True)
class ConfigPoint:
    """One zipped (capacity, policy, alloc_no_fetch) configuration point,
    for irregular grids the product axes cannot express (e.g. the policy
    headroom study's per-capacity FIFO+no-fetch extra column)."""

    capacity: int
    policy: int = policies.FIFO
    alloc_no_fetch: bool = False


_POLICY_BY_NAME = {v: k for k, v in policies.POLICY_NAMES.items()}


def _policy_id(p) -> int:
    if isinstance(p, str):
        try:
            return _POLICY_BY_NAME[p.lower()]
        except KeyError:
            raise ValueError(
                f"unknown policy {p!r}; available: "
                f"{', '.join(sorted(_POLICY_BY_NAME))}") from None
    return int(p)


def _as_geometry(g) -> L1Geometry:
    if isinstance(g, L1Geometry):
        return g
    if isinstance(g, tuple) and len(g) == 2:
        return L1Geometry(int(g[0]), int(g[1]))
    raise TypeError(
        f"l1_geometry values must be L1Geometry or (sets, ways) tuples, "
        f"got {g!r}")


def _as_config_point(c) -> ConfigPoint:
    if isinstance(c, ConfigPoint):
        return ConfigPoint(int(c.capacity), _policy_id(c.policy),
                           bool(c.alloc_no_fetch))
    if isinstance(c, dict):
        return _as_config_point(ConfigPoint(**c))
    if isinstance(c, (tuple, list)) and 1 <= len(c) <= 3:
        return _as_config_point(ConfigPoint(*c))
    raise TypeError(
        f"config_points entries must be ConfigPoint / (capacity, policy, "
        f"alloc_no_fetch) tuples / dicts, got {c!r}")


def _as_tuple(v) -> tuple:
    if isinstance(v, (str, bytes)):
        return (v,)
    try:
        return tuple(v)
    except TypeError:
        return (v,)


@dataclasses.dataclass(frozen=True)
class Axis:
    """One labeled sweep axis: a name and its ordered point values."""

    name: str
    values: tuple

    def __len__(self) -> int:
        return len(self.values)

    def indices(self, want) -> list[int]:
        """Positions of the requested value(s), normalised per axis type.
        Lists/sets/arrays always multi-select; tuples multi-select too,
        except on the ``config``/``l1_geometry`` axes where a tuple is one
        point."""
        multi = (list, set, np.ndarray)
        if self.name not in ("config", "l1_geometry"):
            multi += (tuple,)
        wants = list(want) if isinstance(want, multi) else [want]
        norm = {"policy": _policy_id, "l1_geometry": _as_geometry,
                "config": _as_config_point}.get(self.name, lambda v: v)
        idx = []
        for w in wants:
            w = norm(w)
            hits = [i for i, v in enumerate(self.values) if v == w]
            if not hits:
                raise ValueError(
                    f"axis {self.name!r} has no point {w!r}; values: "
                    f"{list(self.values)}")
            idx.extend(hits)
        return idx


# ---------------------------------------------------------------------------
# The declarative sweep spec.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Sweep:
    """A declarative design-space sweep over named axes.

    The config axes (``capacity`` x ``policy`` x ``alloc_no_fetch``) and the
    machine-latency axes (``mem_latency`` x ``l1_hit_cycles`` x
    ``uop_hit_cycles``) form full cartesian products; ``config_points``
    replaces the three config axes with one zipped ``config`` axis for
    irregular grids.  ``l1_geometry`` is the static outer axis the planner
    orchestrates (one engine build per geometry).

    ``kernel_params`` selects the build size: ``"paper"`` (default),
    ``"reduced"``, or a dict of build kwargs applied to every kernel.
    ``fold=None`` defers to the Session's fold policy.  ``max_events`` is
    the legacy truncation budget (forces ``fold`` off) — kept as an explicit
    escape hatch for smoke runs; prefer folding.

    ``network`` names models from :mod:`repro.configs.registry` (a bare
    name lowers a prefill token block, ``"<model>:decode:batch=B:..."``
    one decode step, :func:`repro.bridge.network.parse_spec`): each is
    lowered through :mod:`repro.bridge` (layer shapes -> deduplicated
    ``net:*`` kernels, registered on first use) and the union of lowered
    kernels joins the ``kernel`` axis — so one ``Sweep(network=(...,))``
    plans a whole model mix as a single planned run.  The lowered
    per-layer records ride on the result's ``meta["networks"]``;
    :func:`repro.bridge.network_report` folds per-kernel counters back
    into per-model totals.

    ``cores`` turns the sweep into a **cluster** sweep
    (:mod:`repro.cluster`): each value N runs every point on N lockstep
    dispersion cores behind the shared memory system described by
    ``cluster`` (a :class:`repro.cluster.ClusterConfig` template whose
    ``n_cores`` is overridden per axis point; ``None`` means no shared
    L2, one memory channel).  Like ``l1_geometry``, ``cores`` is static —
    the planner compiles one engine per (bucket, geometry, cores) group —
    and the result grid gains a ``cores`` axis (after ``l1_geometry``)
    plus the cluster counters (``contention_stalls``, ``l2_hits``,
    ``l2_misses``, ``core_cycles_min/max/sum``); ``cycles`` becomes the
    cluster makespan.  Single-core sweeps (``cores=(1,)`` and no
    ``cluster``) are untouched — no ``cores`` axis, no cluster counters.
    """

    kernels: tuple[str, ...] = ()
    capacity: tuple[int, ...] = (8,)
    policy: tuple[int, ...] = (policies.FIFO,)
    alloc_no_fetch: tuple[bool, ...] = (False,)
    config_points: tuple[ConfigPoint, ...] | None = None
    mem_latency: tuple[int, ...] = (DEFAULT_MACHINE.mem_latency,)
    l1_hit_cycles: tuple[int, ...] = (DEFAULT_MACHINE.l1_hit_cycles,)
    uop_hit_cycles: tuple[int, ...] = (DEFAULT_MACHINE.uop_hit_cycles,)
    l1_geometry: tuple[L1Geometry, ...] = (
        L1Geometry(DEFAULT_MACHINE.l1_sets, DEFAULT_MACHINE.l1_ways),)
    kernel_params: str | dict = "paper"
    fold: bool | None = None
    max_events: int | None = None
    network: tuple[str, ...] = ()
    cores: tuple[int, ...] = (1,)
    cluster: object | None = None     # repro.cluster.ClusterConfig template

    def __post_init__(self):
        fix = object.__setattr__
        fix(self, "network",
            tuple(_as_tuple(self.network)) if self.network else ())
        kernels = list(_as_tuple(self.kernels))
        lowered = ()
        if self.network:
            from repro.bridge import lower_network
            lowered = tuple(lower_network(m) for m in self.network)
            for net in lowered:
                kernels += [k for k in net.kernels if k not in kernels]
        fix(self, "_lowered", lowered)    # companion record, not a field
        fix(self, "kernels", tuple(kernels))
        if not self.kernels:
            raise ValueError("Sweep needs at least one kernel name")
        fix(self, "capacity", tuple(int(c) for c in _as_tuple(self.capacity)))
        fix(self, "policy",
            tuple(_policy_id(p) for p in _as_tuple(self.policy)))
        fix(self, "alloc_no_fetch",
            tuple(bool(a) for a in _as_tuple(self.alloc_no_fetch)))
        if self.config_points is not None:
            fix(self, "config_points",
                tuple(_as_config_point(c)
                      for c in _as_tuple(self.config_points)))
        fix(self, "mem_latency",
            tuple(int(m) for m in _as_tuple(self.mem_latency)))
        fix(self, "l1_hit_cycles",
            tuple(int(m) for m in _as_tuple(self.l1_hit_cycles)))
        fix(self, "uop_hit_cycles",
            tuple(int(m) for m in _as_tuple(self.uop_hit_cycles)))
        fix(self, "l1_geometry",
            tuple(_as_geometry(g) for g in _as_tuple(self.l1_geometry)))
        fix(self, "cores", tuple(int(n) for n in _as_tuple(self.cores)))
        if any(n < 1 for n in self.cores):
            raise ValueError(f"cores values must be >= 1, got {self.cores}")
        if self.cluster is not None:
            from repro.cluster import ClusterConfig
            if not isinstance(self.cluster, ClusterConfig):
                raise TypeError(
                    f"cluster must be a repro.cluster.ClusterConfig, "
                    f"got {self.cluster!r}")

    @property
    def is_cluster(self) -> bool:
        """True when this sweep runs the cluster engine (a non-trivial
        ``cores`` axis or an explicit shared-memory ``cluster`` template)."""
        return self.cores != (1,) or self.cluster is not None

    def cluster_config(self, n_cores: int):
        """The :class:`repro.cluster.ClusterConfig` for one ``cores`` point:
        the ``cluster`` template with its ``n_cores`` overridden (default
        template: no shared L2, one memory channel)."""
        from repro.cluster import ClusterConfig
        base = self.cluster if self.cluster is not None else ClusterConfig()
        return dataclasses.replace(base, n_cores=int(n_cores))

    # -- derived engine inputs -------------------------------------------

    def config(self) -> SweepConfig:
        """The flattened (C,) config axis the engine vmaps over."""
        if self.config_points is not None:
            return SweepConfig(
                np.asarray([c.capacity for c in self.config_points],
                           np.int32),
                np.asarray([c.policy for c in self.config_points], np.int32),
                np.asarray([c.alloc_no_fetch for c in self.config_points],
                           bool))
        return SweepConfig.product(self.capacity, self.policy,
                                   self.alloc_no_fetch)

    def machine_sweep(self, geometry: L1Geometry) -> MachineSweep:
        """The traced (M,) latency grid bound to one static geometry."""
        return MachineSweep.product(
            self.mem_latency, self.l1_hit_cycles, self.uop_hit_cycles,
            l1_sets=geometry.sets, l1_ways=geometry.ways)

    def axes(self) -> tuple[Axis, ...]:
        """The labeled result axes, in canonical (row-major) order."""
        if self.config_points is not None:
            cfg_axes = (Axis("config", self.config_points),)
        else:
            cfg_axes = (Axis("capacity", self.capacity),
                        Axis("policy", self.policy),
                        Axis("alloc_no_fetch", self.alloc_no_fetch))
        core_axes = (Axis("cores", self.cores),) if self.is_cluster else ()
        return ((Axis("kernel", self.kernels),) + cfg_axes
                + (Axis("l1_geometry", self.l1_geometry),) + core_axes
                + (Axis("mem_latency", self.mem_latency),
                   Axis("l1_hit_cycles", self.l1_hit_cycles),
                   Axis("uop_hit_cycles", self.uop_hit_cycles)))


# ---------------------------------------------------------------------------
# The labeled result grid.
# ---------------------------------------------------------------------------


_CONFIG_FIELDS = ("capacity", "policy", "alloc_no_fetch")
# Row-field name -> L1Geometry attribute, shared with repro.metrics'
# axis_grid so label expansion and metric grids can never disagree.
_GEOMETRY_FIELDS = {"l1_sets": "sets", "l1_ways": "ways", "l1_kb": "kbytes"}


@dataclasses.dataclass
class SweepResult:
    """Counter grids over labeled axes (see :meth:`Sweep.axes` for order).

    ``data`` maps counter name -> ndarray shaped like the axes; alongside
    the raw :data:`simulator.COUNTER_NAMES` it carries ``hit_rate``,
    ``event_scale`` and the per-point ``fold_exact`` certificate.
    ``fold_exact`` certifies the periodic-fold extrapolation only — it is
    vacuously True for unfolded points, including ``max_events``-truncated
    smoke runs, whose scaled-prefix approximation is flagged by
    ``event_scale > 1`` instead.  ``meta`` records the execution plan:
    dispatch groups, compile/dispatch counts and point totals.
    """

    axes: tuple[Axis, ...]
    data: dict[str, np.ndarray]
    meta: dict = dataclasses.field(default_factory=dict)

    @property
    def shape(self) -> tuple[int, ...]:
        return tuple(len(a) for a in self.axes)

    @classmethod
    def from_table(cls, axes: dict, rows: list[dict], values=None,
                   meta: dict | None = None) -> "SweepResult":
        """Assemble a labeled grid from flat result rows.

        ``axes`` is an ordered {name: values} mapping; every row must carry
        each axis name (its value locating the row on the grid) plus the
        measured fields.  ``values`` names the fields to grid (default:
        every non-axis key of the first row).  Missing grid points read
        NaN.  This is how non-simulator sweeps (e.g. the serving SLO
        benchmark) ride the same ``select``/``pareto``/``derive`` surface
        as the cVRF grids.
        """
        ax = tuple(Axis(n, tuple(_as_tuple(v))) for n, v in axes.items())
        if not rows:
            raise ValueError("from_table needs at least one row")
        names = [a.name for a in ax]
        if values is None:
            values = [k for k in rows[0] if k not in names]
        shape = tuple(len(a) for a in ax)
        data = {k: np.full(shape, np.nan) for k in values}
        lookup = [{v: i for i, v in enumerate(a.values)} for a in ax]
        for row in rows:
            try:
                idx = tuple(lk[row[a.name]]
                            for a, lk in zip(ax, lookup))
            except KeyError as e:
                raise ValueError(
                    f"row {row!r} has no grid point for axis value "
                    f"{e.args[0]!r}") from None
            for k in values:
                data[k][idx] = float(row[k])
        return cls(ax, data, meta if meta is not None else {})

    def keys(self):
        return self.data.keys()

    def __getitem__(self, counter: str) -> np.ndarray:
        return self.data[counter]

    def axis(self, name: str) -> Axis:
        for a in self.axes:
            if a.name == name:
                return a
        raise KeyError(f"no axis {name!r}; axes: "
                       f"{[a.name for a in self.axes]}")

    # -- accessors --------------------------------------------------------

    def _resolve(self, key, want) -> tuple[int, list[int]]:
        names = [a.name for a in self.axes]
        if key in names:
            ai = names.index(key)
            return ai, self.axes[ai].indices(want)
        if key in _CONFIG_FIELDS and "config" in names:
            ai = names.index("config")
            axis = self.axes[ai]
            wants = list(want) if isinstance(
                want, (list, tuple, set, np.ndarray)) else [want]
            if key == "policy":
                wants = [_policy_id(w) for w in wants]
            idx = [i for i, c in enumerate(axis.values)
                   if getattr(c, key) in wants]
            if not idx:
                raise ValueError(
                    f"no config point with {key}={want!r}; points: "
                    f"{list(axis.values)}")
            return ai, idx
        raise KeyError(f"unknown axis {key!r}; axes: {names}")

    def select(self, **sel) -> "SweepResult":
        """Filter axes by value (scalar keeps a length-1 axis; a list keeps
        the listed points).  With a zipped ``config`` axis, ``capacity`` /
        ``policy`` / ``alloc_no_fetch`` filter by field.  Views share the
        sweep's ``meta``, so ``derive`` on any view records into the same
        execution history entry."""
        r = self
        for key, want in sel.items():
            ai, idx = r._resolve(key, want)       # against the narrowed axes
            axes = list(r.axes)
            axes[ai] = Axis(axes[ai].name,
                            tuple(axes[ai].values[i] for i in idx))
            r = SweepResult(
                tuple(axes),
                {k: np.take(v, idx, axis=ai) for k, v in r.data.items()},
                self.meta)
        return r

    def value(self, counter: str, **sel):
        """The single scalar at a fully determined point."""
        r = self.select(**sel) if sel else self
        arr = r.data[counter]
        if arr.size != 1:
            raise ValueError(
                f"selection leaves {arr.size} points for {counter!r} "
                f"(shape {r.shape}); pin every multi-valued axis")
        return arr.reshape(())[()].item()

    def array(self, counter: str, **sel) -> np.ndarray:
        """Counter values for a selection, singleton axes squeezed away."""
        r = self.select(**sel) if sel else self
        return np.squeeze(r.data[counter])

    def to_grid(self, **sel) -> dict[str, np.ndarray]:
        """The legacy (P, C, M) engine view — kernels x flattened configs x
        flattened machine-latency points — for one L1 geometry (select a
        geometry first when the sweep has several).  This is the shape
        :func:`repro.core.costmodel.check_machine_affine` consumes."""
        r = self.select(**sel) if sel else self
        geo = r.axis("l1_geometry")
        if len(geo) != 1:
            raise ValueError(
                "to_grid needs a single L1 geometry; select one of "
                f"{list(geo.values)} first")
        p = len(r.axes[0])
        m = math.prod(len(r.axis(n)) for n in
                      ("mem_latency", "l1_hit_cycles", "uop_hit_cycles"))
        c = math.prod(len(a) for a in r.axes) // (p * m)
        return {k: np.ascontiguousarray(v).reshape(p, c, m)
                for k, v in r.data.items()}

    def _labels(self, idx) -> dict:
        """Axis labels of one grid point, expanded to scalar fields."""
        row = {}
        for a, i in zip(self.axes, idx):
            v = a.values[i]
            if a.name == "config":
                row.update(capacity=v.capacity, policy=v.policy,
                           alloc_no_fetch=v.alloc_no_fetch)
                row["policy_name"] = policies.POLICY_NAMES[v.policy]
            elif a.name == "policy":
                row["policy"] = v
                row["policy_name"] = policies.POLICY_NAMES[v]
            elif a.name == "l1_geometry":
                row["l1_geometry"] = str(v)
                row.update({f: getattr(v, attr)
                            for f, attr in _GEOMETRY_FIELDS.items()})
            else:
                row[a.name] = v
        return row

    def to_rows(self, counters=None) -> list[dict]:
        """One dict per grid point: every axis label (config points and
        geometries expanded into scalar fields) plus the counters."""
        counters = list(counters) if counters is not None \
            else list(self.data)
        rows = []
        for idx in np.ndindex(*self.shape):
            row = self._labels(idx)
            for k in counters:
                row[k] = self.data[k][idx].item()
            rows.append(row)
        return rows

    def quantile(self, q: float, over: str) -> "SweepResult":
        """Collapse the ``over`` axis to its q-th percentile (0..100),
        counter by counter — e.g. ``result.quantile(99, over="seed")``
        turns a per-seed grid into a p99 grid.  The collapsed axis is
        removed from the result."""
        names = [a.name for a in self.axes]
        if over not in names:
            raise KeyError(f"no axis {over!r}; axes: {names}")
        ai = names.index(over)
        axes = tuple(a for a in self.axes if a.name != over)
        data = {k: np.percentile(v, q, axis=ai)
                for k, v in self.data.items()}
        return SweepResult(axes, data, self.meta)

    # -- the metric algebra (repro.metrics evaluates; this owns the axes) --

    def _baseline_view(self, baseline: dict) -> "SweepResult":
        """The baseline-aligned view of this grid, broadcastable against
        it: every product axis named in ``baseline`` is pinned to exactly
        one point (kept as a length-1 axis); on a zipped ``config`` axis,
        ``capacity``/``policy``/``alloc_no_fetch`` keys pin *fields* and
        each config point is aligned to the point sharing its remaining
        fields (e.g. ``baseline=dict(policy="fifo")`` maps every (cap,
        pol) point to (cap, FIFO))."""
        if not isinstance(baseline, dict) or not baseline:
            raise TypeError("baseline must be a non-empty dict of axis "
                            "selections, e.g. dict(capacity=32)")
        names = [a.name for a in self.axes]
        r = self
        pins = {}
        for key, want in baseline.items():
            if key in names:
                r = r.select(**{key: want})
                if len(r.axis(key)) != 1:
                    raise ValueError(
                        f"baseline {key}={want!r} selects "
                        f"{len(r.axis(key))} points; pin exactly one")
            elif key in _CONFIG_FIELDS and "config" in names:
                pins[key] = _policy_id(want) if key == "policy" else want
            else:
                raise KeyError(
                    f"unknown baseline axis {key!r}; axes: {names}")
        if pins:
            ai = names.index("config")
            pts = r.axis("config").values
            first = {}
            for j, c in enumerate(pts):
                first.setdefault((c.capacity, c.policy, c.alloc_no_fetch),
                                 j)
            idx = []
            for c in pts:
                tgt = tuple(pins.get(f, getattr(c, f))
                            for f in _CONFIG_FIELDS)
                if tgt not in first:
                    raise ValueError(
                        f"no baseline config point "
                        f"{dict(zip(_CONFIG_FIELDS, tgt))} to align "
                        f"{c} against")
                idx.append(first[tgt])
            axes = list(r.axes)
            axes[ai] = Axis("config", tuple(pts[j] for j in idx))
            r = SweepResult(
                tuple(axes),
                {k: np.take(v, idx, axis=ai) for k, v in r.data.items()},
                self.meta)
        return r

    def derive(self, metric, baseline: dict | None = None,
               out: str | None = None, **params) -> "SweepResult":
        """Evaluate a registered :mod:`repro.metrics` metric over the whole
        grid and return a new result carrying it as an extra labeled
        counter (under ``out`` or the metric's name).  Relational metrics
        require ``baseline=`` (an axis-selection dict); extra keyword
        arguments are metric parameters.  Sub-metrics the evaluation pulls
        in via ``ctx.counter`` ride along in the returned data.  Deriving
        is pure counter algebra — it never compiles or dispatches."""
        from repro import metrics as _metrics
        m = _metrics.get(metric)
        r = SweepResult(self.axes, dict(self.data), self.meta)
        arr = _metrics.evaluate(r, m, baseline=baseline, params=params)
        r.data[out or m.name] = np.broadcast_to(
            np.asarray(arr), self.shape).copy()
        record = dict(metric=m.name, kind=m.kind, out=out or m.name)
        if baseline is not None:
            record["baseline"] = {k: str(v) for k, v in baseline.items()}
        if params:
            record["params"] = {k: str(v) for k, v in params.items()}
        derived = self.meta.setdefault("derived", [])
        if record not in derived:
            derived.append(record)
        return r

    def normalize(self, counter: str, baseline: dict) -> "SweepResult":
        """Return a copy with ``counter`` divided by its value at the
        ``baseline`` selection (broadcast; the baseline points read 1.0).
        Other counters are untouched."""
        base = self._baseline_view(baseline)
        r = SweepResult(self.axes, dict(self.data), self.meta)
        r.data[counter] = self.data[counter] / base.data[counter]
        return r

    def pareto(self, x: str | None = None, y: str | None = None,
               axes: list | tuple | None = None, maximize: tuple = (),
               **sel) -> list[dict]:
        """The maximal (non-dominated) front over N objectives across every
        point of the (optionally ``select``-narrowed) grid.

        Objectives come either as the classic two-objective sugar
        ``pareto(x, y)`` or as ``pareto(axes=["area", "cycles",
        "energy"])`` — the two forms are exclusive and ``pareto(x, y)``
        is exactly ``pareto(axes=[x, y])``.  Every objective is minimized
        unless named in ``maximize``; objectives may be counters or
        registered non-relational metrics (derived on demand).  A point is
        dominated when some other point is no worse on every objective and
        strictly better on at least one; exact ties on all objectives keep
        both points (so duplicates survive, as in the original
        two-objective implementation).

        Dominance is resolved with a lexicographic sort + incremental
        front (only lexicographically earlier points can dominate, and any
        dominator is itself dominated only by earlier front members), so
        the scan is one vectorized comparison per point against the
        growing front instead of the old all-pairs Python loop.

        Returns the non-dominated points as label rows (axis labels
        expanded, plus the objective values), sorted ascending by the
        tuple of raw objective values (for two objectives: ascending
        ``x``, then ``y`` — the original ordering).
        """
        if axes is None:
            if x is None or y is None:
                raise TypeError(
                    "pareto needs either positional x and y or "
                    "axes=[obj1, obj2, ...]")
            objectives = [x, y]
        else:
            if x is not None or y is not None:
                raise TypeError("pass either (x, y) or axes=, not both")
            objectives = list(axes)
        if len(objectives) < 2:
            raise ValueError(
                f"pareto needs at least 2 objectives, got {objectives!r}")
        if isinstance(maximize, str):
            maximize = (maximize,)
        unknown = sorted(set(maximize) - set(objectives))
        if unknown:
            raise ValueError(
                f"maximize names {unknown} are not objectives "
                f"{objectives}")
        r = self.select(**sel) if sel else self
        for m in objectives:
            if m not in r.data:
                r = r.derive(m)
        vals = np.stack([np.asarray(r.data[m], np.float64).ravel()
                         for m in objectives])          # (N_obj, K) raw
        signs = np.array([-1.0 if m in maximize else 1.0
                          for m in objectives])
        obj = vals * signs[:, None]                     # minimize all
        npts = obj.shape[1]
        # lexsort's last key is primary -> sort by obj0, then obj1, ...
        order = np.lexsort(obj[::-1])
        fv = np.empty((npts, len(objectives)))
        nf = 0
        front = []
        for k in order:
            p = obj[:, k]
            if nf:
                le = (fv[:nf] <= p).all(axis=1)
                lt = (fv[:nf] < p).any(axis=1)
                if bool(np.any(le & lt)):
                    continue
            fv[nf] = p
            nf += 1
            front.append(int(k))
        rows = []
        for k in front:
            idx = tuple(int(v) for v in np.unravel_index(k, r.shape))
            row = r._labels(idx)
            for oi, m in enumerate(objectives):
                row[m] = vals[oi, k].item()
            rows.append(row)
        rows.sort(key=lambda rr: tuple(rr[m] for m in objectives))
        return rows


# ---------------------------------------------------------------------------
# The session: cache owner + execution planner.
# ---------------------------------------------------------------------------


def _family_rows(preps: dict) -> dict:
    """``session.prepare`` span counters: the prepared scan rows of every
    kernel family (a ``net:<kind>:...`` kernel's ``net:<kind>``, any other
    kernel's own name) as ``rows:<family>``, and their sum as ``rows``."""
    out: dict = {}
    for name, prep in preps.items():
        key = "rows:" + ":".join(name.split(":")[:2])
        out[key] = out.get(key, 0) + prep.num_rows
    out["rows"] = sum(out.values())
    return out


class Session:
    """Owns every sweep-side cache and executes :class:`Sweep` specs.

    * *built* kernels, keyed (name, build params);
    * *prepared* traces (expanded + folded / truncated), keyed (name,
      params, fold, max_events, fold warm-up — a function of the static L1
      geometry only);
    * the fold / refine policy (``refine`` transparently re-simulates
      uncertified folds without folding when the full trace is affordable);
    * compile / dispatch accounting for every engine call it issued
      (``compile_count()`` — one compile per (shape bucket, L1 geometry)).

    Compiled executables live in XLA's process-level jit cache (keyed only
    on shapes and static geometry), so Sessions never recompile each
    other's buckets — but they share no Python state: two Sessions build
    and prepare independently, and dropping one frees its traces.

    ``batch_programs=None`` picks the backend default: per-program
    dispatches on CPU (vmapped lanes execute serially there, and per-trace
    padding stays small), one fused dispatch per planner group elsewhere.
    """

    def __init__(self, fold: bool = True, refine: bool = True,
                 refine_max_rows: int = REFINE_MAX_ROWS,
                 batch_programs: bool | None = None):
        self.fold = fold
        self.refine = refine
        self.refine_max_rows = refine_max_rows
        if batch_programs is None:
            import jax
            batch_programs = jax.default_backend() != "cpu"
        self.batch_programs = batch_programs
        self.history: list[dict] = []
        self._built: dict = {}
        self._prepared: dict = {}
        self._compiles = 0
        self._dispatches = 0

    # -- caches -----------------------------------------------------------

    @staticmethod
    def _build_params(bench, params):
        if params == "paper":
            return dict(bench.paper_params)
        if params == "reduced":
            return dict(bench.reduced_params)
        if isinstance(params, dict):
            return dict(params)
        raise ValueError(
            f"kernel_params must be 'paper', 'reduced' or a dict of build "
            f"kwargs, got {params!r}")

    def built(self, name: str, params: str | dict = "paper"):
        """Build (and cache) one benchmark kernel at the requested size."""
        from repro import rvv
        bench = rvv.get_benchmark(name)
        kw = self._build_params(bench, params)
        key = (name, tuple(sorted(kw.items())))
        if key not in self._built:
            self._built[key] = bench.build(**kw)
        return self._built[key]

    def prepared(self, name: str, fold: bool | None = None,
                 max_events: int | None = None,
                 machine=DEFAULT_MACHINE,
                 params: str | dict = "paper") -> simulator.PreparedTrace:
        """Expanded (+folded / truncated) trace per benchmark, cached.

        The fold warm-up is a function of the static L1 geometry only
        (``machine.l1_sets`` / ``l1_ways``), so it is part of the cache key;
        the traced latency values never are.
        """
        from repro import rvv
        if fold is None:
            fold = self.fold
        if max_events is not None:
            fold = False                  # truncation is the legacy mode
        warm = folding.warm_lines_for(machine.l1_sets, machine.l1_ways)
        kw = self._build_params(rvv.get_benchmark(name), params)
        # Unfolded preparations never read the warm-up, so they are shared
        # across L1 geometries instead of duplicated per geometry.
        key = (name, tuple(sorted(kw.items())), fold, max_events,
               warm if fold else None)
        if key not in self._prepared:
            self._prepared[key] = simulator.prepare(
                self.built(name, params).program, fold=fold,
                max_events=max_events, warm_lines=warm)
        return self._prepared[key]

    def reset(self) -> None:
        """Drop every cache and counter (the jit cache is XLA's, not ours)."""
        self._built.clear()
        self._prepared.clear()
        self.history.clear()
        self._compiles = 0
        self._dispatches = 0

    # -- accounting -------------------------------------------------------

    def compile_count(self) -> int:
        """Engine compiles this session triggered (one per new (shape
        bucket, L1 geometry) signature)."""
        return self._compiles

    def dispatch_count(self) -> int:
        """Engine dispatches this session issued."""
        return self._dispatches

    def _simulate(self, preps, config, machine):
        c0, d0 = simulator.compile_count(), simulator.dispatch_count()
        out = simulator.simulate_grid(preps, config, machine,
                                      batch_programs=self.batch_programs)
        self._compiles += simulator.compile_count() - c0
        self._dispatches += simulator.dispatch_count() - d0
        return out

    def _simulate_cluster(self, preps, config, machine, cluster):
        """Cluster-engine grid call with the same compile/dispatch
        accounting as :meth:`_simulate` (the cluster engine increments the
        simulator-module counters, so one probe covers both engines)."""
        from repro.cluster import simulate_cluster_grid
        c0, d0 = simulator.compile_count(), simulator.dispatch_count()
        out = simulate_cluster_grid(preps, config, machine, cluster,
                                    batch_programs=self.batch_programs)
        self._compiles += simulator.compile_count() - c0
        self._dispatches += simulator.dispatch_count() - d0
        return out

    def _refine(self, names, out, config, machine, params) -> None:
        """Re-simulate, in place, every program whose fold certificate
        failed at any grid point and whose full trace is affordable."""
        if "fold_exact" not in out:
            return
        with obs.span("session.refine") as sp:
            redone = 0
            for pi, name in enumerate(names):
                if out["fold_exact"][pi].all():
                    continue
                rows = self.built(name, params).program.num_instructions
                if rows > self.refine_max_rows:
                    continue
                sub = self._simulate(
                    [self.prepared(name, fold=False, machine=machine,
                                   params=params)], config, machine)
                for k in out:
                    out[k][pi] = sub[k][0] if k != "fold_exact" else True
                redone += 1
            sp.set(programs=redone)

    def _refine_cluster(self, names, out, config, machine, sweep) -> None:
        """Cluster analogue of :meth:`_refine`: re-simulate, unfolded and
        per failing ``cores`` point, every program whose cluster fold
        certificate failed (the shared L2 can break a period alignment
        that holds single-core, so certificates are per (kernel, cores))."""
        if "fold_exact" not in out:
            return
        with obs.span("session.refine") as sp:
            redone = 0
            for pi, name in enumerate(names):
                if out["fold_exact"][pi].all():
                    continue
                rows = self.built(
                    name, sweep.kernel_params).program.num_instructions
                if rows > self.refine_max_rows:
                    continue
                prep = self.prepared(name, fold=False, machine=machine,
                                     params=sweep.kernel_params)
                for ki, n in enumerate(sweep.cores):
                    if out["fold_exact"][pi, ki].all():
                        continue
                    sub = self._simulate_cluster(
                        [prep], config, machine, sweep.cluster_config(n))
                    for k in out:
                        out[k][pi, ki] = sub[k][0] if k != "fold_exact" \
                            else True
                redone += 1
            sp.set(programs=redone)

    # -- execution --------------------------------------------------------

    def grid(self, names, config: SweepConfig, machine=DEFAULT_MACHINE,
             fold: bool | None = None, max_events: int | None = None,
             refine: bool | None = None,
             params: str | dict = "paper") -> dict[str, np.ndarray]:
        """The legacy-shaped sweep call: P named kernels x a flat (C,)
        config axis (x M machine points when ``machine`` is a
        :class:`MachineSweep`), returning raw counter arrays.  Prefer
        :meth:`run` with a declarative :class:`Sweep`; this is the engine
        room it and the ``benchmarks.common`` shim share.
        """
        if fold is None:
            fold = self.fold
        if refine is None:
            refine = self.refine
        names = list(names)
        preps = [self.prepared(n, fold=fold, max_events=max_events,
                               machine=machine, params=params)
                 for n in names]
        out = self._simulate(preps, config, machine)
        if fold and refine:
            self._refine(names, out, config, machine, params)
        return out

    def run(self, sweep: Sweep) -> SweepResult:
        """Execute a declarative sweep.

        Planning: for each L1 geometry (static — its own engine build) the
        kernels are grouped by padded shape bucket and each (bucket,
        geometry) group is issued as one engine call — a single fused
        dispatch when ``batch_programs`` is on, per-program dispatches
        sharing the group's one compiled executable otherwise.  The traced
        latency grid rides inside every dispatch; uncertified folds are
        refined per geometry exactly as :meth:`grid` does.

        Cluster sweeps (:attr:`Sweep.is_cluster`) add the static ``cores``
        axis to the plan loop: one cluster-engine call per (bucket,
        geometry, cores) group — each a plan entry carrying ``cores`` —
        and the result grid gains the cluster counters with ``cycles`` as
        the cluster makespan.
        """
        fold = self.fold if sweep.fold is None else sweep.fold
        if sweep.max_events is not None:
            fold = False
        c0, d0 = self._compiles, self._dispatches
        axes = sweep.axes()
        points = int(np.prod([len(a) for a in axes]))
        with obs.span("session.run", kernels=len(sweep.kernels),
                      points=points, geometries=len(sweep.l1_geometry)):
            plan: list[dict] = []
            per_geo = [self._run_geometry(sweep, geo, fold, plan)
                       for geo in sweep.l1_geometry]
            with obs.span("session.assemble"):
                data = self._assemble(sweep, per_geo)
                meta = dict(
                    plan=plan,
                    compiles=self._compiles - c0,
                    dispatches=self._dispatches - d0,
                    points=points,
                    axes={a.name: [str(v) if a.name in ("l1_geometry",
                                                        "config")
                                   else v for v in a.values] for a in axes},
                    kernel_params=(sweep.kernel_params
                                   if isinstance(sweep.kernel_params, str)
                                   else dict(sweep.kernel_params)),
                    fold=fold,
                )
                if sweep.is_cluster:
                    cl0 = sweep.cluster_config(1)
                    meta["cluster"] = dict(
                        cores=list(sweep.cores), l2_sets=cl0.l2_sets,
                        l2_ways=cl0.l2_ways, mem_channels=cl0.mem_channels,
                        l2_hit_cycles=cl0.l2_hit_cycles,
                        l2_bytes=cl0.l2_bytes)
                lowered = getattr(sweep, "_lowered", ())
                if lowered:
                    meta["networks"] = [net.summary() for net in lowered]
        self.history.append(meta)
        return SweepResult(axes, data, meta)

    def _run_geometry(self, sweep: Sweep, geo: L1Geometry, fold: bool,
                      plan: list) -> dict[str, np.ndarray]:
        """One geometry of :meth:`run`: prepare, dispatch each bucket
        group (appending its entries to ``plan``), refine.  Returns the
        (P, C, M) — (P, K, C, M) for a cluster sweep — counter arrays."""
        names = list(sweep.kernels)
        config = sweep.config()
        cluster_mode = sweep.is_cluster
        machines = sweep.machine_sweep(geo)
        with obs.span("session.prepare") as sp:
            n0 = len(self._prepared)
            preps = {n: self.prepared(n, fold=fold,
                                      max_events=sweep.max_events,
                                      machine=machines,
                                      params=sweep.kernel_params)
                     for n in names}
            misses = len(self._prepared) - n0
            sp.set(hits=len(names) - misses, misses=misses)
            if sp:
                sp.set(**_family_rows(preps))
        groups: dict[int, list[str]] = {}
        for n in names:
            bucket = simulator._bucket(preps[n].num_rows)
            groups.setdefault(bucket, []).append(n)
        parts: dict[str, dict[str, np.ndarray]] = {}
        for bucket in sorted(groups):
            group = groups[bucket]
            group_preps = [preps[n] for n in group]
            if cluster_mode:
                subs = []
                for ncores in sweep.cores:
                    subs.append(self._simulate_cluster(
                        group_preps, config, machines,
                        sweep.cluster_config(ncores)))
                    plan.append(dict(
                        l1_geometry=str(geo), bucket=bucket,
                        cores=ncores, kernels=list(group),
                        fused=bool(self.batch_programs)))
                for gi, n in enumerate(group):
                    parts[n] = {k: np.stack([s[k][gi] for s in subs])
                                for k in subs[0]}        # (K, C, M)
            else:
                sub = self._simulate(group_preps, config, machines)
                plan.append(dict(l1_geometry=str(geo), bucket=bucket,
                                 kernels=list(group),
                                 fused=bool(self.batch_programs)))
                for gi, n in enumerate(group):
                    parts[n] = {k: v[gi] for k, v in sub.items()}
        shape_cm = parts[names[0]]["cycles"].shape  # (C, M) / (K, C, M)
        for n in names:                  # normalise across buckets
            parts[n].setdefault(
                "fold_exact", np.ones(shape_cm, bool))
        geo_out = {k: np.stack([parts[n][k] for n in names])
                   for k in parts[names[0]]}
        if fold and self.refine:
            if cluster_mode:
                self._refine_cluster(names, geo_out, config, machines,
                                     sweep)
            else:
                self._refine(names, geo_out, config, machines,
                             sweep.kernel_params)
        return geo_out

    @staticmethod
    def _assemble(sweep: Sweep, per_geo: list) -> dict[str, np.ndarray]:
        """Stack the per-geometry grids into the result's canonical axis
        order: kernel, config axes, geometry (, cores), machine axes."""
        if sweep.config_points is not None:
            cshape = (len(sweep.config_points),)
        else:
            cshape = (len(sweep.capacity), len(sweep.policy),
                      len(sweep.alloc_no_fetch))
        mshape = (len(sweep.mem_latency), len(sweep.l1_hit_cycles),
                  len(sweep.uop_hit_cycles))
        data = {}
        for k in per_geo[0]:
            if sweep.is_cluster:
                # (G, P, K, C, M) -> geometry and cores move to their
                # canonical slots after the config axes.
                stacked = np.stack([g[k] for g in per_geo])
                g, p, kc = stacked.shape[:3]
                stacked = stacked.reshape((g, p, kc) + cshape + mshape)
                data[k] = np.moveaxis(
                    stacked, (0, 2),
                    (1 + len(cshape), 2 + len(cshape)))
            else:
                stacked = np.stack([g[k] for g in per_geo])  # (G, P, C, M)
                g, p = stacked.shape[:2]
                stacked = stacked.reshape((g, p) + cshape + mshape)
                # geometry moves to its canonical slot: after the config
                # axes.
                data[k] = np.moveaxis(stacked, 0, 1 + len(cshape))
        return data


# ---------------------------------------------------------------------------
# Process-default session + the raw-program front door.
# ---------------------------------------------------------------------------


_DEFAULT_SESSION: Session | None = None


def default_session() -> Session:
    """The process-default Session the benchmark layer shares."""
    global _DEFAULT_SESSION
    if _DEFAULT_SESSION is None:
        _DEFAULT_SESSION = Session()
    return _DEFAULT_SESSION


def reset_default_session() -> Session:
    """Replace the process-default Session with a fresh one (tests use the
    ``fresh_default_session`` pytest fixture, which restores the old one)."""
    global _DEFAULT_SESSION
    _DEFAULT_SESSION = Session()
    return _DEFAULT_SESSION


def sweep_program(program_or_events, config: SweepConfig,
                  machine=DEFAULT_MACHINE, fold: bool = False,
                  max_events: int | None = None) -> dict[str, np.ndarray]:
    """Sweep one raw Program / EventStream / PreparedTrace over a flat
    config axis — the front door for traces that are not registered
    kernels (the deprecated ``simulator.simulate_sweep`` delegates here).
    Returns (C,)-shaped counter arrays, (C, M)-shaped under a
    :class:`MachineSweep`."""
    prep = simulator.prepare(program_or_events, fold=fold,
                             max_events=max_events, machine=machine)
    out = simulator.simulate_grid([prep], config, machine)
    return {k: v[0] for k, v in out.items()}
