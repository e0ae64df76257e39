"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix.  The configuration's file is the ``file`` of its ``configs`` entry;
the traffic mix is ``bench/workloads/<traffic>.json`` and names the
driver, ``bench/drivers/<driver>.py``, that generates it.  A per-layer
metric ``m`` is read by ``bench/metrics/<m>.py``.  Adding a cell, a
configuration or a metric therefore means adding files and entries; no
existing file changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict                 # the configuration's file, as run
    traffic_name: str
    traffic: dict                # the traffic mix's file
    end_to_end: list             # BENCHMARK.json entries this cell reports
    per_layer: list
    root: pathlib.Path

    @property
    def driver(self) -> str:
        return self.traffic["driver"]


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")
    cfg = next(c for c in bench["configs"] if c["name"] == entry["config"])
    traffic_file = root / "bench" / "workloads" / f"{entry['traffic']}.json"
    return Cell(
        name=name, chips=int(entry["chips"]), config_name=cfg["name"],
        config=json.loads((root / cfg["file"]).read_text()),
        traffic_name=entry["traffic"],
        traffic=json.loads(traffic_file.read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=root)


def load_module(path: pathlib.Path, name: str | None = None):
    """Import a file by path (file names may hold ``-`` and ``.``)."""
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + path.stem.replace("-", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(c: Cell):
    return load_module(c.root / "bench" / "drivers" / f"{c.driver}.py")


def metric_reader(name: str, root: pathlib.Path = ROOT):
    return load_module(root / "bench" / "metrics" / f"{name}.py")


def config_reference(c: Cell):
    """The configuration's plain reference, named by its file."""
    return load_module(c.root / "bench" / c.config["reference"])
