"""BENCHMARK.json, and the harness finding every part of a cell by name."""

import json
import os
import re
import shutil

import pytest
from bench_helpers import BENCH_DIR, ROOT
from harness import cell as cellmod

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))


def test_names_units_and_entry_keys(bench):
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in bench[k]}) == len(bench[k])
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("workload", ["large-cluster.full_sweep",
                                      "large-cluster.churn_audit"])
def test_cell_found_by_name(workload):
    c = cellmod.load(workload)
    assert c.chips == 1
    assert c.config["name"] == c.config_entry["name"]
    assert c.policy["templates"] and c.policy["constraints"]
    assert callable(c.generator.make) and c.reference.LIMITS
    assert callable(c.module("drivers", c.mix["driver"]).Driver)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert callable(c.reader(m["name"]))
        # each metric the cell lists reports an end-to-end metric it has
        assert m["moves"] in names


def test_unknown_cell_and_device_kind_are_errors():
    with pytest.raises(cellmod.CellError, match="no workload"):
        cellmod.load("no.such_cell")
    with pytest.raises(cellmod.CellError, match="not in benchmark/peaks"):
        cellmod.peaks("TPU v99")
    assert cellmod.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


GENERATOR = '''
def make(seed, spec):
    return [{"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": f"p{i}", "namespace": "a"},
             "spec": {"containers": []}} for i in range(spec["n"])]
'''

DRIVER = '''
from harness.record import Record


class Driver:
    def __init__(self, cell, seed, say):
        self.cell, self.seed = cell, seed

    def setup(self, seconds):
        self.objs = self.cell.generator.make(self.seed,
                                             self.cell.config["inventory"])

    def window(self, seconds):
        rec = Record()
        rec.cycles = rec.attempted = len(self.objs)
        self.metrics = {"objects_per_s": rec.cycles / seconds}
        return rec

    def close(self):
        pass

    def check(self, reference, control=False):
        return {"objects_compared": len(self.objs)}
'''


def test_a_new_cell_needs_no_edit_to_an_existing_file(bench, tmp_path):
    """A configuration with a generator of its own, a mix run by a driver
    of its own, a metric and a cell, added as new files and new entries
    only: the harness finds and runs them, and no file it already has
    changed."""
    root = tmp_path / "repo"
    shutil.copytree(BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    b = root / "benchmark"
    (b / "generators" / "empty_pods.py").write_text(GENERATOR)
    (b / "drivers" / "count.py").write_text(DRIVER)
    (b / "configs" / "tiny.json").write_text(json.dumps({
        "name": "tiny", "policy": "large-cluster-policy.json",
        "reference": "pods", "inventory": {"generator": "empty_pods",
                                           "n": 7}}))
    (b / "traffic" / "count.json").write_text(json.dumps({"driver":
                                                          "count"}))
    (b / "metrics" / "cycles.count.py").write_text(
        "def read(rec):\n    return float(rec.cycles)\n")
    new = dict(bench)
    new["configs"] = bench["configs"] + [
        {"name": "tiny", "source": "https://example.org/tiny",
         "file": "benchmark/configs/tiny.json", "reduced": [],
         "why": "a test configuration"}]
    new["workloads"] = bench["workloads"] + [
        {"name": "tiny.count", "config": "tiny", "traffic": "count",
         "chips": 1, "why": "a test cell"}]
    new["end_to_end"] = bench["end_to_end"] + [
        {"name": "objects_per_s", "unit": "1/s", "better": "higher",
         "bound": 0.05, "source": "host_clock", "workloads": ["tiny.count"]}]
    new["per_layer"] = bench["per_layer"] + [
        {"name": "cycles.count", "unit": "count", "better": "higher",
         "source": "host_clock", "layer": "harness",
         "moves": "objects_per_s", "workloads": ["tiny.count"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    c = cellmod.load("tiny.count", root=str(root))
    assert [m["name"] for m in c.per_layer] == ["cycles.count"]
    assert {m["name"] for m in c.end_to_end} == {"objects_per_s", "setup_s"}
    t = c.driver(5, print)
    t.setup(1.0)
    rec = t.window(1.0)
    assert c.reader("cycles.count")(rec) == 7.0
    assert t.metrics == {"objects_per_s": 7.0}
    from harness import checks
    assert checks.correct(t.check(c.reference), c.reference.LIMITS)
    assert all(p.read_bytes() == b for p, b in before.items())
