"""Network-level driver: lower a registry model into registered kernels.

``lower_network(model)`` walks the model's LayerOps (:mod:`.shapes`),
deduplicates them by shape signature, registers one benchmark per unique
signature through the ordinary ``@register_benchmark`` registry (domain
``"network"``, idempotent via ``exist_ok``), and returns a
:class:`LoweredNetwork` mapping every layer instance onto its kernel with
a count and macro factor.  Because the registered kernels are plain
benchmarks, a whole model becomes one ``Sweep(kernels=net.kernels, ...)``
— or, via the ``network`` axis on :class:`repro.api.Sweep`, just
``Sweep(network=("granite-8b", ...))``.

``network_report`` folds per-kernel sweep results back into per-model
totals: each unit's counters scale by ``count * macro_factor`` (tile
programs cover a fixed sub-problem; the macro factor is real-work /
tile-work, see :mod:`.lower`).

A network spec names a model and, optionally, its phase: ``"granite-8b"``
is a prefill token block (:func:`.shapes.model_ops`);
``"deepseek-v2-lite-16b:decode:batch=8:context=4096:zipf=1.0:seed=0"`` is
one decode step (:func:`.shapes.decode_ops`), every option of
:data:`DECODE_OPTIONS` stated.
"""

from __future__ import annotations

import dataclasses

from repro import obs
from repro.bridge import lower, shapes
from repro.rvv import common as rvv_common


@dataclasses.dataclass(frozen=True)
class NetworkUnit:
    """One deduplicated layer group of a lowered network."""

    kernel: str           # registered benchmark name (net:<kind>:<shape>)
    kind: str             # gemm | attn | scan | dgemm | expert | mla
    labels: tuple         # layer labels merged into this unit
    shape: tuple          # real layer shape (signature dims)
    count: int            # instances across the network
    macro_factor: float   # real work / tile work, per instance
    params: dict          # tile build kwargs

    @property
    def scale(self) -> float:
        """Counter multiplier taking one tile run to network-level work."""
        return self.count * self.macro_factor


@dataclasses.dataclass(frozen=True)
class LoweredNetwork:
    model: str
    units: tuple

    @property
    def kernels(self) -> tuple:
        """Sorted unique kernel names (the Sweep kernel axis)."""
        return tuple(sorted({u.kernel for u in self.units}))

    @property
    def num_instances(self) -> int:
        return sum(u.count for u in self.units)

    def summary(self) -> dict:
        """JSON-friendly description (lands in ``Session.run`` meta)."""
        return dict(model=self.model, kernels=list(self.kernels),
                    units=len(self.units), instances=self.num_instances)


def _register(name: str, kind: str, kwargs: dict, op) -> None:
    rvv_common.register_benchmark(
        name, domain="network", paper_params=dict(kwargs),
        reduced_params=dict(kwargs),
        table2=f"bridge-lowered {kind} {'x'.join(map(str, op.shape))}",
        scalar_cost=lower.cost_for(kind), exist_ok=True,
    )(lower.builder_for(kind))


#: Options of a decode spec, ``<model>:decode:<key>=<value>...``, each
#: required, and their types: the sequences in the step, the positions
#: each has cached, and the routing draw's Zipf exponent and seed
#: (:func:`.shapes.routing_loads`).
DECODE_OPTIONS = dict(batch=int, context=int, zipf=float, seed=int)


def parse_spec(spec: str) -> tuple[str, dict | None]:
    """(model, decode options), or (model, None) for a prefill spec."""
    model, sep, rest = spec.partition(":")
    if not sep:
        return model, None
    phase, *opts = rest.split(":")
    if phase != "decode":
        raise ValueError(f"network spec {spec!r}: unknown phase {phase!r} "
                         f"(only 'decode' may follow the model)")
    params = {}
    for opt in opts:
        key, eq, value = opt.partition("=")
        if not eq or key not in DECODE_OPTIONS:
            raise ValueError(f"network spec {spec!r}: bad option {opt!r}; "
                             f"options are {sorted(DECODE_OPTIONS)}")
        params[key] = DECODE_OPTIONS[key](value)
    missing = sorted(DECODE_OPTIONS.keys() - params.keys())
    if missing:
        raise ValueError(f"network spec {spec!r}: missing options "
                         f"{missing}")
    return model, params


def _decode_counts(layer_ops) -> dict:
    """``bridge.lower`` span counters of a decode lowering: instances of
    each decode kind, and the tokens of the busiest and idlest active
    routed expert."""
    out = {f"{kind}_ops": sum(o.count for o in layer_ops if o.kind == kind)
           for kind in ("dgemm", "expert", "mla")}
    routed = [o.shape[0] for o in layer_ops
              if o.label.startswith("moe/routed/")]
    if routed:
        out.update(expert_tokens_max=max(routed),
                   expert_tokens_min=min(routed))
    return out


def lower_network(spec: str) -> LoweredNetwork:
    """Lower the network ``spec`` names (a registry model, optionally with
    ``:decode`` options, see :func:`parse_spec`); idempotent (re-lowering
    a model, or lowering two models sharing a layer shape, reuses
    registered kernels).
    """
    with obs.span("bridge.lower", model=spec) as sp:
        model, decode = parse_spec(spec)
        layer_ops = (shapes.model_ops(model) if decode is None
                     else shapes.decode_ops(model, **decode))
        groups: dict[tuple, list] = {}
        for op in layer_ops:
            groups.setdefault(op.signature, []).append(op)
        units = []
        for sig, ops in sorted(groups.items(), key=lambda kv: repr(kv[0])):
            name, kwargs, macro = lower.tile_for(ops[0])
            _register(name, ops[0].kind, kwargs, ops[0])
            units.append(NetworkUnit(
                kernel=name, kind=ops[0].kind,
                labels=tuple(o.label for o in ops), shape=tuple(sig[1:]),
                count=sum(o.count for o in ops), macro_factor=macro,
                params=dict(kwargs)))
        net = LoweredNetwork(model=spec, units=tuple(units))
        sp.set(kernels=len(net.kernels), ops=len(layer_ops))
        if decode is not None and sp:
            sp.set(**_decode_counts(layer_ops))
    return net


def network_report(result, lowered, metrics=("scaled_cycles",),
                   capacity_bytes_per_reg: int = 32) -> list[dict]:
    """Per-model totals from a per-kernel sweep result.

    ``result``: a ``SweepResult`` whose first axis is ``kernel`` and whose
    data contains every name in ``metrics`` (``derive`` them first).
    ``lowered``: a LoweredNetwork or list thereof; every unit's kernel
    must be on the result's kernel axis.  One row per (model, non-kernel
    grid point): the point's axis labels, the model's cVRF footprint
    (capacity x 32 B vector registers), and ``<metric>_total`` — the
    count x macro-factor weighted sum of the metric over the model's
    units (tile counters scaled back to network-level work).
    """
    import numpy as np

    if isinstance(lowered, LoweredNetwork):
        lowered = [lowered]
    kaxis = result.axis("kernel")
    if result.axes[0].name != "kernel":
        raise ValueError("network_report expects kernel as the first axis")
    ki_for = {n: i for i, n in enumerate(kaxis.values)}
    rows = []
    other = result.axes[1:]
    for idx in np.ndindex(*(len(a) for a in other)):
        labels = result._labels((0,) + idx)
        labels.pop("kernel", None)
        for net in lowered:
            row = dict(model=net.model, **labels)
            row["kernels"] = len(net.kernels)
            row["instances"] = net.num_instances
            if "capacity" in row:
                row["footprint_bytes"] = (int(row["capacity"])
                                          * capacity_bytes_per_reg)
            for m in metrics:
                vals = result.data[m]
                total = 0.0
                for u in net.units:
                    total += float(vals[(ki_for[u.kernel],) + idx]) * u.scale
                row[f"{m}_total"] = total
            rows.append(row)
    return rows
