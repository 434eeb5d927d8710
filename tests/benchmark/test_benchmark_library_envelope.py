"""The library-envelope configuration on the CPU at a small size: its
generator, its plain reference (equal to ``LocalDriver`` for all 48
templates), and its cell end to end, with the device path forced (a
small inventory would otherwise stay on the scalar oracle,
``SMALL_WORKLOAD_EVALS``).  ``correct`` has to come out false for the
reference's control and for a stale first cycle, a dropped message and
an altered one."""

import collections
import copy
import dataclasses
import json
import os

import pytest
from bench_helpers import BENCH_DIR, Args, CpuDevice, cpu_op_lines, shrink
from harness import cell as cellmod
from harness import checks

import run
from gatekeeper_tpu.client.client import Client

CELL = "library-envelope.full_sweep"
N = 1500                # Pods; about 2,500 objects of 12 kinds


@pytest.fixture(scope="module")
def device_path():
    from gatekeeper_tpu.engine import jax_driver
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax_driver, "SMALL_WORKLOAD_EVALS", 0)
        yield


def _config():
    with open(os.path.join(BENCH_DIR, "configs",
                           "library-envelope-150k.json")) as f:
        return json.load(f)


def _module(kind, name):
    return cellmod.load_module(BENCH_DIR, kind, name)


def test_configuration_counts():
    """~239,000 objects of 12 kinds, 150,000 Pods, 48 templates x 1
    constraint; the 13 pod-security constraints exempt privileged
    namespaces."""
    cfg = _config()
    spec = cfg["inventory"]
    cnt = _module("generators", "envelope").scaled(spec)
    pods = sum(r * c for r, c in zip(spec["deployments"]["replicas"],
                                     cnt["deployments"]))
    deps = sum(cnt["deployments"])
    total = pods + 2 * deps + sum(v for k, v in cnt.items()
                                  if k != "deployments")
    assert (pods, deps, total) == (150_000, 15_000, 239_000)
    p = spec["containers_per_pod"]
    assert abs(sum(v * w for v, w in zip(p["values"], p["p"])) - 2.0) < 1e-9
    policy = cellmod.load(CELL).policy
    kinds = [c["kind"] for c in policy["constraints"]]
    assert len(policy["templates"]) == len(set(kinds)) == len(kinds) == 48
    assert "K8sDenyAll" not in kinds
    assert all(c["spec"]["match"]["kinds"] for c in policy["constraints"])
    assert sum("namespaceSelector" in c["spec"]["match"]
               for c in policy["constraints"]) == 13
    assert cfg["reduced"] == []


def test_generator_seed_renames_only():
    """Two seeds: the same objects in the same cache-key order, only the
    names differ."""
    spec = dict(_config()["inventory"], n=N)
    gen = _module("generators", "envelope")
    key = _module("reference", "library").cache_key
    a, b = (sorted(gen.make(s, spec), key=key) for s in (3, 2**33 + 5))
    assert len(a) == len(b) > 2000
    assert [o["kind"] for o in a] == [o["kind"] for o in b]
    assert [o.get("spec") for o in a if o["kind"] == "Pod"] == \
        [o.get("spec") for o in b if o["kind"] == "Pod"]
    assert [o["metadata"]["name"] for o in a] != \
        [o["metadata"]["name"] for o in b]
    kinds = collections.Counter(o["kind"] for o in a)
    assert len(kinds) == 12 and kinds["Pod"] == 1615
    per_ns = collections.Counter(o["metadata"]["namespace"] for o in a
                                 if o["kind"] == "Pod")
    assert max(per_ns.values()) <= spec["namespace_pod_cap"]


def _entry(r):
    meta = r.resource["metadata"]
    return (r.constraint["metadata"]["name"], meta.get("namespace"),
            meta["name"], r.msg)


def test_reference_equals_local_driver_for_every_template():
    """Every violating field drawn at 30 %, so that each of the 48
    templates reports: the reference's uncapped audit equals the scalar
    oracle's, entry for entry."""
    from gatekeeper_tpu.client.client import Backend
    from gatekeeper_tpu.client.local_driver import LocalDriver
    from gatekeeper_tpu.target.k8s import K8sValidationTarget
    spec = dict(_config()["inventory"], n=N)
    spec["violation_p"] = {k: max(v, 0.3)
                           for k, v in spec["violation_p"].items()}
    objs = _module("generators", "envelope").make(11, spec)
    policy = cellmod.load(CELL).policy
    want = _module("reference", "library").make(policy, objs).audit(10**9)
    c = Backend(LocalDriver()).new_client([K8sValidationTarget()])
    for doc in policy["templates"]:
        c.add_template(doc)
    for doc in policy["constraints"]:
        c.add_constraint(doc)
    c.add_data_batch(objs)
    got = [_entry(r) for r in c.audit().results()]
    assert collections.Counter(got) == collections.Counter(want)
    assert {e[0] for e in want} == {c["metadata"]["name"]
                                    for c in policy["constraints"]}


def test_cell_end_to_end_through_run(device_path):
    out = run.run(Args(CELL, trace=1),
                  device_check=lambda chips, peaks: CpuDevice(),
                  adjust=lambda cell: shrink(cell, N),
                  device_line=cpu_op_lines)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["audit_missing"]["value"] == 0
    assert out["checks"]["audit_extra"]["value"] == 0
    assert out["checks"]["cycles_compared"]["value"] == out["attempted"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(m) == {"device.idle_share.audit", "kernels.sweep_device_ms",
                      "prep.host_prep_ms", "format.format_ms",
                      "executor.compiles_in_window",
                      "dispatch.scalar_templates", "format.scalar_ms"}
    # every template on the device: nothing formatted on the oracle
    assert m["dispatch.scalar_templates"] == 0
    assert m["format.scalar_ms"] == 0
    assert m["executor.compiles_in_window"] == 0


class _Results:
    def __init__(self, results):
        self._r = results

    def results(self):
        return self._r


@pytest.fixture(scope="module")
def envelope_cell(device_path):
    """The cell set up at a small size, every quantity violation within
    1 % of its boundary (so that the bfloat16 control shows at this
    size), then one Pod changed: the first Pod of an enforced namespace
    now shares the host network.  ``stale`` is the answer before the
    change."""
    cell = cellmod.load(CELL)
    shrink(cell, N)
    cell.config["inventory"]["near_p"] = 1.0
    t = cell.driver(2**31 + 7, lambda msg: None)
    t.setup(1.0)
    stale = t.client.audit(limit_per_constraint=20, full=True).results()
    ns_ok = {o["metadata"]["name"] for o in t.objs
             if o["kind"] == "Namespace" and o["metadata"]["labels"].get(
                 "pod-security.kubernetes.io/enforce") != "privileged"}
    i = min((k for k, o in enumerate(t.objs) if o["kind"] == "Pod"
             and o["metadata"]["namespace"] in ns_ok),
            key=lambda k: t.objs[k]["metadata"]["namespace"])
    pod = copy.deepcopy(t.objs[i])
    pod["spec"]["hostNetwork"] = True
    t.client.add_data(pod)
    t.objs[i] = pod
    yield cell, t, stale
    t.close()


def _stale_first(stale):
    """The window's first cycle answers as before the last write."""
    def fault(orig):
        calls = []

        def audit(self, *a, **k):
            res = orig(self, *a, **k)
            calls.append(1)
            return _Results(stale) if len(calls) == 1 else res
        return audit
    return fault


def _dropped(orig):
    def audit(self, *a, **k):
        return _Results(list(orig(self, *a, **k).results())[1:])
    return audit


def _altered(orig):
    def audit(self, *a, **k):
        res = list(orig(self, *a, **k).results())
        res[-1] = dataclasses.replace(res[-1], msg=res[-1].msg + ".")
        return _Results(res)
    return audit


@pytest.mark.parametrize("fault", [None, "control", "stale", _dropped,
                                   _altered])
def test_correct_only_for_the_sound_run(envelope_cell, monkeypatch, fault):
    cell, t, stale = envelope_cell
    if fault == "stale":
        fault = _stale_first(stale)
    if callable(fault):
        monkeypatch.setattr(Client, "audit", fault(Client.audit))
    t.window(1.0)
    monkeypatch.undo()
    numbers = t.check(cell.reference, control=fault == "control")
    assert numbers["cycles_compared"] > 0
    ok = checks.correct(numbers, cell.reference.LIMITS)
    assert ok is (fault is None), numbers
