"""Forced-full-sweep audit (QueryOpts.full / Client.audit(full=True)):
parity with the scalar oracle and the memoized path, cache
invalidation, pipeline phase metering, and the inline dispatch that a
mesh spanning processes takes.

VERDICT §weak #4 context: the steady-state audit number is delta/memo
replay.  audit(full=True) drops the mask/bindings/format memoization
for the sweep so "full sweep" and "memoized steady" are two separately
metered numbers — and a forced-full sweep must still return results
bit-identical to both the oracle and the memoized path on unchanged
data.
"""

import random

from gatekeeper_tpu.client.client import Backend
from gatekeeper_tpu.client.local_driver import LocalDriver
from gatekeeper_tpu.engine import jax_driver as jd_mod
from gatekeeper_tpu.engine.jax_driver import JaxDriver
from gatekeeper_tpu.library import (LIBRARY, all_docs, constraint_doc,
                                    template_doc)
from gatekeeper_tpu.library.workload import make_mixed
from gatekeeper_tpu.target.k8s import K8sValidationTarget, TARGET_NAME


def _fill_library(client, resources):
    for tdoc, cdoc in all_docs():
        client.add_template(tdoc)
        client.add_constraint(cdoc)
    for r in resources:
        client.add_data(r)


def _keys(results):
    return [(r.msg, r.constraint["metadata"]["name"],
             (r.review or {}).get("name")) for r in results]


# a small device-friendly workload: three lowerable kinds, few compiles
def _small_device_client(rng, n=150, n_con=3):
    labels = ["l0", "l1", "l2", "l3", "l4"]
    resources = []
    for i in range(n):
        resources.append({
            "apiVersion": "v1", "kind": "Namespace",
            "metadata": {"name": f"ns-{i:04d}",
                         "labels": {k: "v" for k in labels
                                    if rng.random() < 0.4}}})
    params = [rng.sample(labels, k=2) for _ in range(n_con)]
    jd = JaxDriver()
    c = Backend(jd).new_client([K8sValidationTarget()])
    c.add_template(template_doc("K8sRequiredLabels",
                                LIBRARY["K8sRequiredLabels"][0]))
    for j, p in enumerate(params):
        c.add_constraint(constraint_doc(
            "K8sRequiredLabels", f"labels-{j}", {"labels": p}))
    c.add_data_batch(resources)
    return jd, c, resources, params


def test_full_sweep_parity_library_under_churn():
    """audit(full=True) is bit-identical to the scalar oracle and to the
    memoized path on unchanged data, across the lowerable library
    templates — before and after churn."""
    rng = random.Random(5)
    resources = make_mixed(rng, 120)

    jd = JaxDriver()
    cj = Backend(jd).new_client([K8sValidationTarget()])
    _fill_library(cj, resources)

    memo1 = _keys(cj.audit().results())         # builds the memo layers
    memo2 = _keys(cj.audit().results())         # memoized replay
    full1 = _keys(cj.audit(full=True).results())
    memo3 = _keys(cj.audit().results())         # memo rebuilt post-full
    assert memo1 == memo2 == full1 == memo3
    assert len(full1) > 50

    ld = LocalDriver()
    cl = Backend(ld).new_client([K8sValidationTarget()])
    _fill_library(cl, resources)
    assert _keys(cl.audit().results()) == full1

    # churn: relabel a third of the rows, then full-vs-oracle again
    churn = random.Random(7)
    for r in churn.sample(resources, len(resources) // 3):
        md = r.setdefault("metadata", {})
        md["labels"] = {k: "v" for k in ["l0", "owner", "team"]
                        if churn.random() < 0.5}
        cj.add_data(r)
        cl.add_data(r)
    full_churned = _keys(cj.audit(full=True).results())
    assert full_churned == _keys(cl.audit().results())
    assert _keys(cj.audit().results()) == full_churned


def test_full_sweep_device_path_parity_and_phases(monkeypatch):
    """Device-forced forced-full sweep: identical results to the
    memoized device path and the oracle, a genuinely re-built cache
    layer, and per-phase pipeline timings recorded."""
    monkeypatch.setattr(jd_mod, "SMALL_WORKLOAD_EVALS", 0)
    # the bindings_cache memo layer belongs to the legacy device sweep;
    # paged kinds are served from the VerdictLedger instead
    monkeypatch.setenv("GATEKEEPER_PAGES", "off")
    rng = random.Random(3)
    jd, c, resources, params = _small_device_client(rng)

    memo = _keys(c.audit().results())
    st = jd.state[TARGET_NAME]
    assert st.bindings_cache            # memo layer exists
    cache_before = st.bindings_cache

    full = _keys(c.audit(full=True).results())
    assert full == memo
    # the memo layer was REBOUND and rebuilt, not reused
    assert jd.state[TARGET_NAME].bindings_cache is not cache_before
    assert jd.state[TARGET_NAME].bindings_cache

    phases = jd.last_sweep_phases
    assert phases["full"] is True
    for k in ("host_prep_s", "h2d_s", "device_s", "pipeline_wall_s",
              "overlap_fraction", "h2d_bytes"):
        assert k in phases
    assert phases["host_prep_s"] > 0
    assert phases["device_s"] > 0       # the device path genuinely ran
    assert phases["h2d_bytes"] > 0      # uploads genuinely re-staged
    assert 0.0 <= phases["overlap_fraction"] <= 1.0

    snap = jd.metrics.snapshot()
    assert snap["full_sweeps"] >= 1
    assert "full_sweep_overlap_fraction" in snap

    # a plain (memoized) sweep records no phase breakdown — only the
    # full flag and the Stage-5/-6 selective-invalidation,
    # plan-driven-sharding, and continuous-enforcement stanzas
    c.audit()
    assert jd.last_sweep_phases["full"] is False
    assert set(jd.last_sweep_phases) <= {"full", "footprint", "shard", "dispatch",
                                         "pages", "devpages"}

    # oracle parity for the same workload
    ld = LocalDriver()
    cl = Backend(ld).new_client([K8sValidationTarget()])
    cl.add_template(template_doc("K8sRequiredLabels",
                                 LIBRARY["K8sRequiredLabels"][0]))
    for j, p in enumerate(params):
        cl.add_constraint(constraint_doc(
            "K8sRequiredLabels", f"labels-{j}", {"labels": p}))
    cl.add_data_batch(resources)
    assert _keys(cl.audit().results()) == full


def test_full_sweep_serial_mode_matches_pipelined(monkeypatch):
    """The inline dispatch a mesh spanning processes takes (each kind
    dispatched from the sweep thread, in kind order) changes only
    scheduling, never results: the forced full sweep equals the pooled
    one."""
    from gatekeeper_tpu.engine import veval

    monkeypatch.setattr(jd_mod, "SMALL_WORKLOAD_EVALS", 0)
    rng = random.Random(9)
    jd, c, _resources, _params = _small_device_client(rng)

    pooled = _keys(c.audit(full=True).results())
    assert pooled

    spans = []

    def spans_processes(mesh):
        spans.append(mesh)
        return True

    monkeypatch.setattr(veval, "mesh_spans_processes", spans_processes)
    inline = _keys(c.audit(full=True).results())
    assert spans, "the sweep never asked whether its mesh spans processes"
    assert inline == pooled
    phases = jd.last_sweep_phases
    assert phases["full"] is True
    assert phases["device_s"] > 0


def test_audit_manager_full_report():
    """AuditManager.audit_once(full=True) carries the per-phase sweep
    metrics into the report the status writer (and an operator) reads."""
    from gatekeeper_tpu.audit.manager import AuditManager
    from gatekeeper_tpu.cluster.fake import FakeCluster
    from gatekeeper_tpu.controllers.constrainttemplate import TEMPLATE_GVK
    from gatekeeper_tpu.controllers.registry import add_to_manager
    from tests.test_audit_manager import template_crd_obj
    from tests.test_control_plane import (NS_GVK, constraint_obj, ns_obj,
                                          template_obj)

    cluster = FakeCluster()
    cluster.register_kind(TEMPLATE_GVK, "constrainttemplates")
    cluster.register_kind(NS_GVK, "namespaces")
    driver = JaxDriver()
    client = Backend(driver).new_client([K8sValidationTarget()])
    plane = add_to_manager(cluster, client)
    cluster.create(template_crd_obj())
    cluster.create(template_obj())
    plane.run_until_idle()
    cluster.create(constraint_obj())
    plane.run_until_idle()
    for i in range(10):
        obj = ns_obj(f"ns{i:03d}", {"gatekeeper": "on"} if i % 2 else None)
        cluster.create(obj)
        client.add_data(obj)

    am = AuditManager(cluster, client, sleep=lambda _s: None)
    memo_report = am.audit_once()
    assert memo_report["full"] is False
    assert "host_prep_s" not in memo_report

    report = am.audit_once(full=True)
    assert report["skipped"] is False
    assert report["full"] is True
    for k in ("host_prep_s", "h2d_s", "device_s", "overlap_fraction"):
        assert k in report, k
    assert report["violations"] == memo_report["violations"]
