"""The benchmark's data: ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by name:

- ``configs[].file``: the configuration (``bench/configs/<name>.json``):
  the dataset module (``bench/datasets/<dataset>.py``), its size, the
  ``IndexSpec`` fields it is built with, and the reference module
  (``bench/references/<reference>.py``) that answers for it;
- ``bench/traffic/<traffic>.json``: a traffic mix, the parameters of the
  driver its ``driver`` key names;
- ``bench/drivers/<driver>.py``: a driver, its class ``Driver`` (what
  it offers: ``bench/loadgen.py``);
- ``bench/metrics/<metric>.py``: one per-layer reader, ``read(ctx)``,
  returning the metric's value or ``None`` where it finds nothing.

A later change adds a configuration, mix or metric by adding files and
entries; nothing here names one.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json``, resolved to its files."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list          # the metric entries this cell reports
    per_layer: list           # (entry, reader) pairs this cell reports
    driver: type              # the traffic's driver class


def load_benchmark(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _load(root: Path, kind: str, name: str):
    """The module ``bench/<kind>/<name>.py`` under ``root``, loaded by
    path (names may hold dots)."""
    path = Path(root) / "bench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no {kind} file {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: Path, name: str):
    """``read`` of ``bench/metrics/<name>.py``."""
    return _load(root, "metrics", name).read


def load_driver(root: Path, name: str):
    """``Driver`` of ``bench/drivers/<name>.py``."""
    return _load(root, "drivers", name).Driver


def resolve(bench: dict, name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic and readers."""
    root = Path(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(root / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    layer = [(m, load_reader(root, m["name"]))
             for m in bench["per_layer"] if _reports(m, name)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, layer,
                load_driver(root, traffic["driver"]))


def dataset(config: dict):
    """The configuration's dataset module (``make(n, seed)``)."""
    return importlib.import_module(f"bench.datasets.{config['dataset']}")


def reference(config: dict):
    """The configuration's reference module (``build(strings, scores,
    rules)``)."""
    return importlib.import_module(f"bench.references.{config['reference']}")


def peaks(kind: str, root: Path = ROOT) -> dict:
    """The published peaks of a device kind; an unknown kind is an
    error."""
    with open(Path(root) / "bench" / "peaks.json") as f:
        table = json.load(f)
    if kind not in table["kinds"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"(known: {sorted(table['kinds'])})")
    return table["kinds"][kind]


class CompileCounter:
    """Host-clock times of every program JAX traced or compiled (or
    loaded from its persistent cache) in this process."""

    #: JAX's monitoring events for tracing a program and for building
    #: its executable (compiled, or loaded from the persistent cache)
    TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
    BUILD_EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.traced: list[float] = []
        self.built: list[float] = []
        names = {self.TRACE_EVENT: self.traced, self.BUILD_EVENT: self.built}

        def listen(event, duration, **kw):
            if event in names:
                names[event].append(time.perf_counter())

        jax.monitoring.register_event_duration_secs_listener(listen)

    def between(self, t0: float, t1: float) -> tuple[int, int]:
        """(programs traced, programs compiled or loaded) in [t0, t1]."""
        return (sum(t0 <= t <= t1 for t in self.traced),
                sum(t0 <= t <= t1 for t in self.built))
