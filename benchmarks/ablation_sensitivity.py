"""Ablation (beyond-paper): is the paper's "8 registers suffice" conclusion
robust to the memory system?  Sweeps main-memory latency (Table 1 gives a
1-5 cycle range; we extend to 10) and L1D capacity, and reports the cVRF-8
performance (normalised to the full VRF under the SAME machine).

If dispersion relied on a fast memory system, slow memories would break it;
the result shows the conclusion is latency-robust because spill/fill
traffic is tiny and L1-resident.

Sweep shape: ONE declarative ``repro.api.Sweep`` covers the whole study —
``l1_geometry`` is a first-class axis, so the static L1 capacities that
used to need a hand-rolled outer loop are planned by the Session (one
engine build per geometry), while the memory latencies ride the traced
machine axes inside each dispatch (zero recompiles across latency values).
The per-point affine cross-check (``costmodel.check_machine_affine``)
certifies the traced grid against the analytic machine model on every run.
"""

from __future__ import annotations

from benchmarks import common
from repro import api
from repro.core import costmodel, simulator

APPS = ("pathfinder", "gemv", "dropout", "flashattention2")
MEM_LATENCIES = (1, 3, 5, 10)
L1_KBYTES = (4, 16)
GEOMETRIES = tuple(api.L1Geometry.from_kbytes(kb) for kb in L1_KBYTES)


def machine_grid(l1_kb: int) -> simulator.MachineSweep:
    """The traced latency axis for one (static) L1 capacity."""
    return simulator.MachineSweep.make(
        MEM_LATENCIES, l1_sets=l1_kb * 1024 // 32 // 2)


def run(max_events=None, fold=True, check_affine=True,
        session=None) -> list[dict]:
    ses = session or api.default_session()
    res = ses.run(api.Sweep(kernels=APPS, capacity=[8, 32],
                            mem_latency=MEM_LATENCIES,
                            l1_geometry=GEOMETRIES,
                            fold=fold, max_events=max_events))
    if check_affine:
        for l1_kb in L1_KBYTES:
            costmodel.check_machine_affine(
                res.to_grid(l1_geometry=api.L1Geometry.from_kbytes(l1_kb)),
                machine_grid(l1_kb))
    rows = []
    for l1_kb in L1_KBYTES:
        geo = api.L1Geometry.from_kbytes(l1_kb)
        for mem_lat in MEM_LATENCIES:
            for name in APPS:
                pt = dict(kernel=name, mem_latency=mem_lat, l1_geometry=geo)
                rows.append(dict(
                    name=f"{name}_mem{mem_lat}_l1_{l1_kb}k",
                    kernel=name, mem_latency=mem_lat, l1_kb=l1_kb,
                    cycles=res.value("cycles", capacity=8, **pt),
                    perf_cvrf8=round(res.value("cycles", capacity=32, **pt)
                                     / res.value("cycles", capacity=8, **pt),
                                     4),
                    hit_rate=round(res.value("hit_rate", capacity=8, **pt),
                                   4),
                ))
    return rows


def main():
    rows = run()
    common.emit(rows, ["name", "perf_cvrf8", "hit_rate"])
    return rows


if __name__ == "__main__":
    main()
