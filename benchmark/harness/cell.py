"""Finding a cell's parts by name.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, named after it in ``BENCHMARK.json``
or in the file that names it:

- configuration ``<c>``: ``benchmark/configs/<c>.json``, which names its
  frozen policy set (a file beside it), its inventory generator
  (``benchmark/generators/<g>.py``: ``make(seed, spec)``, and
  ``churn(...)`` where a mix churns) and its plain reference
  (``benchmark/reference/<r>.py``: ``make(policy, inventory, control)``
  and the ``LIMITS`` that decide ``correct``);
- traffic mix ``<t>``: ``benchmark/traffic/<t>.json``, parameters, which
  name the driver that runs them (``benchmark/drivers/<d>.py``: a
  ``Driver`` with ``setup``, ``window``, ``close`` and ``check``);
- per-layer metric ``<m>``: ``benchmark/metrics/<m>.py``, a reader with
  ``read(record) -> float | None``;
- peaks: ``benchmark/peaks.json``, keyed by ``device_kind``.

A later cell, mix, driver, generator, reference, configuration or
metric is new files and entries; no file here changes.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class CellError(Exception):
    """The cell cannot be run as asked."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise CellError(f"missing {path}") from e


def load_module(bench_dir: str, kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, imported from its file."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    if not os.path.exists(path):
        raise CellError(f"no {kind} {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads``, with its configuration, mix and
    metrics resolved."""

    def __init__(self, bench: dict, name: str, root: str = ROOT):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise CellError(f"no workload {name!r} in BENCHMARK.json "
                            f"(have {sorted(cells)})")
        w = cells[name]
        self.name = name
        self.chips = int(w["chips"])
        self.bench_dir = os.path.join(root, "benchmark")
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = configs[w["config"]]
        self.config = _load_json(os.path.join(root,
                                              self.config_entry["file"]))
        config_dir = os.path.dirname(os.path.join(
            root, self.config_entry["file"]))
        self.policy = _load_json(os.path.join(config_dir,
                                              self.config["policy"]))
        self.traffic_name = w["traffic"]
        self.mix = _load_json(os.path.join(self.bench_dir, "traffic",
                                           f"{w['traffic']}.json"))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]
        self.generator = self.module(
            "generators", self.config["inventory"]["generator"])
        self.reference = self.module("reference", self.config["reference"])

    def module(self, kind: str, name: str):
        return load_module(self.bench_dir, kind, name)

    def driver(self, seed: int, say):
        """The mix's driver, set for one run."""
        return self.module("drivers", self.mix["driver"]).Driver(
            self, seed, say)

    def reader(self, metric: str):
        """The ``read`` function of ``benchmark/metrics/<metric>.py``."""
        return self.module("metrics", metric).read


def load(name: str, root: str = ROOT) -> Cell:
    return Cell(_load_json(os.path.join(root, "BENCHMARK.json")), name, root)


def peaks(device_kind: str, root: str = ROOT) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, not a default."""
    table = _load_json(os.path.join(root, "benchmark", "peaks.json"))
    if device_kind not in table["devices"]:
        raise CellError(f"device kind {device_kind!r} is not in "
                        f"benchmark/peaks.json")
    return table["devices"][device_kind]
