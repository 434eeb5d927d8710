"""The tracer on the profiler's timeline.

While a ``jax.profiler`` session records, a capped audit's spans land in
its trace as host events under their own names, the kind as a stat;
every span the sweep opens is a direct child of ``audit.sweep``;
compiles and store batches are root spans; the device programs carry
role names; and ``GATEKEEPER_TRACE=off`` emits neither spans nor
profiler events.
"""

import collections
import glob
import random

import jax
import pytest

from gatekeeper_tpu.client.client import Backend
from gatekeeper_tpu.engine import jax_driver as jd_mod
from gatekeeper_tpu.library import make_mixed
from gatekeeper_tpu.library.templates import (LIBRARY, constraint_doc,
                                              template_doc)
from gatekeeper_tpu.obs import trace as trace_mod
from gatekeeper_tpu.target.k8s import K8sValidationTarget

KINDS = ("K8sAllowedRepos", "K8sDisallowedTags", "K8sRequiredLabels")
ROLES = {"gk_mask", "gk_topk", "gk_reduce", "gk_delta", "gk_devdelta"}
SWEEP_CHILDREN = {"audit.order", "kind.host_prep", "kind.h2d", "kind.block",
                  "device.dispatch", "kind.fetch", "kind.format"}
PROGRAM_SPANS = ("audit.", "kind.", "device.", "executor.", "store.",
                 "external.", "dedup.")


@pytest.fixture
def tracer(monkeypatch):
    """A fresh process tracer: no other test's spans in the ring."""
    tr = trace_mod.Tracer(ring=4096)
    monkeypatch.setattr(trace_mod, "_tracer", tr)
    return tr


@pytest.fixture
def driver(monkeypatch):
    """Three library kinds over 300 objects, on the device path."""
    monkeypatch.setattr(jd_mod, "SMALL_WORKLOAD_EVALS", 0)
    return jd_mod.JaxDriver()


@pytest.fixture
def client(driver):
    c = Backend(driver).new_client([K8sValidationTarget()])
    for kind in KINDS:
        rego, params = LIBRARY[kind]
        c.add_template(template_doc(kind, rego))
        c.add_constraint(constraint_doc(kind, kind.lower() + "-1", params))
    c.add_data_batch(make_mixed(random.Random(7), 300))
    return c


def _capped_audit(c, full=True):
    with jax.profiler.TraceAnnotation("bench.audit"):
        return c.audit(limit_per_constraint=20, full=full).results()


def _host_events(tmp_path, fn) -> dict:
    """Event name -> the stats of each such event on the host plane of a
    profiler trace recorded around ``fn()``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    from jax.profiler import ProfileData
    events = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events[e.name].append(dict(e.stats))
    return events


def test_sweep_spans_land_on_the_profiler_timeline(tracer, client,
                                                   tmp_path):
    client.audit(limit_per_constraint=20, full=True)   # compiles first
    events = _host_events(tmp_path, lambda: _capped_audit(client))
    for name in ("bench.audit", "audit.sweep", "audit.order",
                 "kind.host_prep", "kind.h2d", "kind.block", "kind.fetch",
                 "kind.format"):
        assert name in events, name
    # the kind rides as a stat, so one event name serves every kind
    assert {s["kind"] for s in events["kind.format"]} == set(KINDS)
    assert {s["mode"] for s in events["kind.format"]} == {"topk"}
    # device programs by role (the sharded twins here: the tests' CPU
    # has eight devices), none left anonymous
    assert any(f"jit({r})" in n for r in ROLES for n in events)
    assert not any("jit(raw)" in n or "jit_raw" in n for n in events)


def test_trace_off_emits_no_spans_and_no_profiler_events(
        monkeypatch, client, tmp_path):
    monkeypatch.setenv("GATEKEEPER_TRACE", "off")
    tr = trace_mod.Tracer()
    assert tr.enabled is False
    monkeypatch.setattr(trace_mod, "_tracer", tr)
    client.audit(limit_per_constraint=20, full=True)
    events = _host_events(tmp_path, lambda: _capped_audit(client))
    assert "bench.audit" in events          # the profiler did record
    assert [n for n in events if n.startswith(PROGRAM_SPANS)] == []
    assert tr.export()["traceEvents"] == []


def test_sweep_spans_are_direct_children_with_their_counts(tracer,
                                                          client):
    client.audit(limit_per_constraint=20, full=True)
    client.add_data_batch(make_mixed(random.Random(9), 5))
    client.audit(limit_per_constraint=20)          # memoized, churned
    evs = tracer.export()["traceEvents"]
    sweeps = [e for e in evs if e["name"] == "audit.sweep"]
    assert [e["args"]["full"] for e in sweeps] == [True, False]
    ids = {e["args"]["span_id"] for e in sweeps}
    # executor.wait only where a dispatch was still running
    children = [e for e in evs
                if e["name"] in SWEEP_CHILDREN | {"executor.wait"}]
    assert {e["name"] for e in children} >= SWEEP_CHILDREN
    assert all(e["args"]["parent_span_id"] in ids for e in children)
    roots = [e for e in evs
             if e["name"] in ("executor.compile", "store.put_batch")]
    assert {e["name"] for e in roots} == {"executor.compile",
                                          "store.put_batch"}
    assert all("parent_span_id" not in e["args"] for e in roots)

    def args(name):
        return [e["args"] for e in evs if e["name"] == name]

    assert {a["role"] for a in args("executor.compile")} <= ROLES
    assert args("store.put_batch")[-1]["rows"] == 5
    assert [a["rows"] for a in args("audit.order")] == [300, 305]
    assert args("audit.order")[1]["resorted"] is True    # creates
    # the five created keys merge into the first sort's order
    assert [a["merged"] for a in args("audit.order")] == [0, 5]
    assert all(a["h2d_bytes"] > 0 for a in args("kind.h2d"))
    assert sum(a["h2d_bytes"] for a in args("device.dispatch")) > 0
    assert sum(a["rows"] for a in args("kind.format")) > 0
    assert len(args("kind.fetch")) == 2 * len(KINDS)


def test_memoized_sweep_reuses_the_order_of_unchanged_keys(tracer, client):
    client.audit(limit_per_constraint=20)
    client.audit(limit_per_constraint=20)
    order = [e["args"] for e in tracer.export()["traceEvents"]
             if e["name"] == "audit.order"]
    assert [a["resorted"] for a in order] == [True, False]
    assert [a["merged"] for a in order] == [0, 0]


def test_a_churned_sweep_merges_its_changed_keys(tracer, client):
    client.audit(limit_per_constraint=20)
    client.add_data_batch(make_mixed(random.Random(9), 5))
    client.audit(limit_per_constraint=20)
    client.audit(limit_per_constraint=20)
    order = [e["args"] for e in tracer.export()["traceEvents"]
             if e["name"] == "audit.order"]
    assert [a["resorted"] for a in order] == [True, True, False]
    assert [a["merged"] for a in order] == [0, 5, 0]


def test_wait_timers_time_the_fetch(tracer, driver, client):
    client.audit(limit_per_constraint=20, full=True)
    fetch_s = sum(e["dur"] for e in tracer.export()["traceEvents"]
                  if e["name"] == "kind.fetch") / 1e6
    dispatch_wait = driver.metrics.timer("audit_dispatch_wait")
    device_wait = driver.metrics.timer("device_wait")
    assert dispatch_wait.count == 1 and device_wait.count == len(KINDS)
    # the same regions as the spans, each timed on its own clock
    assert dispatch_wait.total == pytest.approx(fetch_s, abs=1e-3)
    assert device_wait.total == pytest.approx(dispatch_wait.total, abs=1e-4)
    assert driver.metrics.timer("audit_format").total >= 0


class TestBeginEnd:
    def test_begin_does_not_enter_the_context(self):
        tr = trace_mod.Tracer(ring=16)
        with tr.span("root") as root:
            sp = tr.begin("region", kind="K")
            assert tr.current() == (root.trace_id, root.span_id)
            with tr.span("inner") as inner:
                pass
            tr.end(sp)
        assert sp.parent_id == root.span_id
        assert inner.parent_id == root.span_id
        assert sp.dur_us is not None and sp.args == {"kind": "K"}

    def test_root_ignores_the_active_span(self):
        tr = trace_mod.Tracer(ring=16)
        with tr.span("outer") as outer:
            with tr.span("compile", root=True) as sp:
                assert tr.current() == (sp.trace_id, sp.span_id)
        assert sp.parent_id == 0 and sp.trace_id != outer.trace_id

    def test_no_profiler_event_without_a_session(self):
        tr = trace_mod.Tracer(ring=16)
        with tr.span("quiet") as sp:
            assert sp._tm is None

    def test_profiler_event_while_a_session_records(self, tmp_path):
        tr = trace_mod.Tracer(ring=16)

        def spans():
            with tr.span("loud", kind="K", n=3, full=True,
                         skipped=[1]) as sp:
                assert sp._tm is not None
            assert sp._tm is None

        events = _host_events(tmp_path, spans)
        assert events["loud"] == [{"kind": "K", "n": 3, "full": True}]

    def test_disabled_begin_and_end_are_noops(self):
        tr = trace_mod.Tracer(ring=16)
        tr.enabled = False
        sp = tr.begin("ghost")
        assert sp is None
        tr.end(sp)
        assert tr.export()["traceEvents"] == []


def test_sweep_span_counts_device_and_scalar_templates(tracer, client):
    import os

    import yaml
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for sub in ("templates/k8sagilelabels.yaml",
                "constraints/owner-must-be-provided.yaml"):
        with open(os.path.join(root, "demo", "agilebank", sub)) as f:
            doc = yaml.safe_load(f)
        if doc["kind"] == "K8sAgileLabels":     # every kind, as Pods are
            del doc["spec"]["match"]
        (client.add_template if sub.startswith("templates")
         else client.add_constraint)(doc)
    client.audit(limit_per_constraint=20, full=True)
    evs = tracer.export()["traceEvents"]
    sweep, = [e["args"] for e in evs if e["name"] == "audit.sweep"]
    assert (sweep["templates_device"], sweep["templates_scalar"]) \
        == (len(KINDS), 1)
    modes = {e["args"]["kind"]: e["args"]["mode"] for e in evs
             if e["name"] == "kind.format"}
    assert modes["K8sAgileLabels"] == "scalar"
    assert {modes[k] for k in KINDS} == {"topk"}


def test_host_prep_spans_count_table_values_by_path(tracer, client):
    client.audit(limit_per_constraint=20, full=True)
    client.audit(limit_per_constraint=20, full=True)
    evs = tracer.export()["traceEvents"]
    preps = [e["args"] for e in evs if e["name"] == "kind.host_prep"]
    assert all("table_values_batched" in a and "table_values_host" in a
               for a in preps)
    by_kind = collections.defaultdict(list)
    for a in preps:
        by_kind[a["kind"]].append((a["table_values_batched"],
                                   a["table_values_host"]))
    # each full sweep rebuilds the image tables, all on the batched path
    # (startswith / endswith / contains of the image); labels have none
    for kind in ("K8sAllowedRepos", "K8sDisallowedTags"):
        assert len(by_kind[kind]) == 2
        assert all(b > 0 and h == 0 for b, h in by_kind[kind]), kind
    assert by_kind["K8sRequiredLabels"] == [(0, 0), (0, 0)]
