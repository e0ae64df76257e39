"""Fig 4: (a) performance of cVRF sizes 3..16 normalised to the full VRF and
(b) cVRF hit rates, for every benchmark application (FIFO, as the paper).

One declarative sweep: all applications x all capacities through
``repro.api`` — the Session plans one fused engine call per program-shape
bucket (folded traces, exact for steady-state kernels).  The normalised
performance column is the ``speedup`` metric against the full-VRF
baseline.
"""

from __future__ import annotations

from benchmarks import common
from repro import api, rvv

CAPS = list(range(3, 17))


def run(names=None, max_events=None, fold=True, session=None) -> list[dict]:
    names = list(names or rvv.BENCHMARKS)
    ses = session or api.default_session()
    res = ses.run(api.Sweep(kernels=names, capacity=CAPS + [32],
                            fold=fold, max_events=max_events))
    r = res.derive("speedup", baseline=dict(capacity=32))
    rows = []
    for name in names:
        for cap in CAPS:
            pt = dict(kernel=name, capacity=cap)
            rows.append(dict(
                name=name, capacity=cap,
                norm_perf=round(r.value("speedup", **pt), 4),
                hit_rate=round(r.value("hit_rate", **pt), 4),
                spills=r.value("spills", **pt),
                fills=r.value("fills", **pt),
                fold_exact=r.value("fold_exact", **pt),
            ))
    return rows


def main(names=None, max_events=None):
    rows = run(names=names, max_events=max_events)
    common.emit(rows, ["name", "capacity", "norm_perf",
                       "hit_rate", "spills", "fills", "fold_exact"])
    return rows


if __name__ == "__main__":
    main()
