"""Observability tests: span tracer, Chrome trace export, per-template
device-time attribution, the degradation flight recorder, and the
/metrics + /debug/trace endpoints under concurrent admission load.

The device_lost acceptance test pins this PR's headline guarantee: a
mid-sweep backend loss must leave behind a flight dump holding the
supervisor transition AND the in-flight sweep's span tree.
"""

import json
import logging
import os
import random
import threading
import time
import urllib.request

import pytest

from gatekeeper_tpu.obs.flightrecorder import (FlightRecorder,
                                               get_flight_recorder)
from gatekeeper_tpu.obs.trace import Tracer, get_tracer
from gatekeeper_tpu.utils import device_probe
from gatekeeper_tpu.utils.metrics import Metrics, sanitize_name

TARGET = "admission.k8s.gatekeeper.sh"


@pytest.fixture
def clean_backend(monkeypatch):
    """Fresh probe verdict + supervisor + fault harness (mirrors
    tests/test_resilience.py — the obs hooks must observe the same
    transitions those tests drive)."""
    monkeypatch.setenv("GATEKEEPER_SUPERVISOR_REPROBE", "0")
    for var in ("GATEKEEPER_FAULT", "GATEKEEPER_SNAPSHOT_DIR",
                "GATEKEEPER_PROBE_TEST_HANG", "GATEKEEPER_PROBE_TEST_FAIL"):
        monkeypatch.delenv(var, raising=False)
    device_probe.reset_for_tests()
    yield
    device_probe.reset_for_tests()


def _mk_client(n=24, seed=7):
    from gatekeeper_tpu.client.client import Backend
    from gatekeeper_tpu.engine import jax_driver as jd_mod
    from gatekeeper_tpu.library import make_mixed
    from gatekeeper_tpu.library.templates import (LIBRARY, constraint_doc,
                                                  template_doc)
    from gatekeeper_tpu.target.k8s import K8sValidationTarget

    jd = jd_mod.JaxDriver()
    client = Backend(jd).new_client([K8sValidationTarget()])
    for kind in ("K8sRequiredLabels", "K8sAllowedRepos", "K8sDisallowedTags"):
        rego, params = LIBRARY[kind]
        client.add_template(template_doc(kind, rego))
        client.add_constraint(constraint_doc(kind, kind.lower() + "-1",
                                             params))
    client.add_data_batch(make_mixed(random.Random(seed), n))
    return jd, client


def _audit(jd, full=True):
    from gatekeeper_tpu.client.interface import QueryOpts
    results, _trace = jd.query_audit(TARGET, QueryOpts(full=full))
    return results


# ----------------------------------------------------------------------
# tracer


class TestTracer:
    def test_nesting_inherits_trace_and_parent(self):
        tr = Tracer(ring=64)
        with tr.span("outer", cat="test") as outer:
            assert tr.current() == (outer.trace_id, outer.span_id)
            with tr.span("inner", cat="test") as inner:
                assert inner.trace_id == outer.trace_id
                assert inner.parent_id == outer.span_id
        assert tr.current() is None
        evs = tr.export()["traceEvents"]
        assert {e["name"] for e in evs} == {"outer", "inner"}
        by_name = {e["name"]: e for e in evs}
        assert by_name["inner"]["args"]["parent_span_id"] == \
            by_name["outer"]["args"]["span_id"]
        # ph "X" complete events with µs ts/dur — the Chrome contract
        for e in evs:
            assert e["ph"] == "X" and e["dur"] >= 0 and "pid" in e

    def test_sibling_roots_get_distinct_traces(self):
        tr = Tracer(ring=64)
        with tr.span("a") as a:
            pass
        with tr.span("b") as b:
            pass
        assert a.trace_id != b.trace_id
        only_a = tr.export(trace_id=a.trace_id)["traceEvents"]
        assert [e["name"] for e in only_a] == ["a"]

    def test_explicit_parent_crosses_threads(self):
        tr = Tracer(ring=64)
        seen = {}
        with tr.span("root") as root:
            ctx = tr.current()

            def worker():
                # context vars do not flow into a foreign thread: the
                # explicit parent handoff is what links the spans
                assert tr.current() is None
                with tr.span("child", parent=ctx) as sp:
                    seen["child"] = sp

            t = threading.Thread(target=worker)
            t.start()
            t.join()
        assert seen["child"].trace_id == root.trace_id
        assert seen["child"].parent_id == root.span_id

    def test_add_complete_records_measured_region(self):
        tr = Tracer(ring=64)
        t0 = time.perf_counter()
        t1 = t0 + 0.25
        with tr.span("root") as root:
            tr.add_complete("measured", "device", t0, t1, kind="K")
        ev = [e for e in tr.export()["traceEvents"]
              if e["name"] == "measured"][0]
        assert abs(ev["dur"] - 250_000) < 1_000     # 0.25s in µs
        assert ev["args"]["kind"] == "K"
        assert ev["args"]["trace_id"] == root.trace_id

    def test_ring_is_bounded(self):
        tr = Tracer(ring=8)
        for i in range(50):
            with tr.span(f"s{i}"):
                pass
        evs = tr.export()["traceEvents"]
        assert len(evs) == 8
        assert evs[-1]["name"] == "s49"

    def test_disabled_is_noop(self):
        tr = Tracer(ring=8)
        tr.enabled = False
        with tr.span("ghost") as sp:
            assert sp is None
            assert tr.current() is None
        tr.add_complete("ghost2", "host", 0.0, 1.0)
        assert tr.export()["traceEvents"] == []

    def test_open_spans_export_incomplete(self):
        tr = Tracer(ring=8)
        cm = tr.span("inflight", cat="audit")
        cm.__enter__()
        try:
            evs = tr.export()["traceEvents"]
            assert len(evs) == 1
            assert evs[0]["args"]["incomplete"] is True
            assert evs[0]["dur"] >= 0
        finally:
            cm.__exit__(None, None, None)
        evs = tr.export()["traceEvents"]
        assert "incomplete" not in evs[0]["args"]

    def test_export_json_round_trips(self):
        tr = Tracer(ring=8)
        with tr.span("x", note="hi"):
            pass
        doc = json.loads(tr.export_json())
        assert doc["displayTimeUnit"] == "ms"
        assert doc["traceEvents"][0]["args"]["note"] == "hi"


class TestLogTraceContext:
    def test_log_lines_carry_trace_id(self):
        from gatekeeper_tpu.utils.log import logger
        records = []

        class Capture(logging.Handler):
            def emit(self, record):
                records.append(record)

        h = Capture()
        root = logging.getLogger("gatekeeper_tpu")
        root.addHandler(h)
        try:
            log = logger("obs.test")
            tr = get_tracer()
            with tr.span("logspan") as sp:
                log.info("inside", foo=1)
            log.info("outside", foo=2)
        finally:
            root.removeHandler(h)
        inside = [r for r in records if r.getMessage() == "inside"][0]
        assert inside.kv["trace"] == sp.trace_id
        assert inside.kv["span"] == sp.span_id
        assert inside.kv["foo"] == 1                # explicit kv intact
        outside = [r for r in records if r.getMessage() == "outside"][0]
        assert "trace" not in outside.kv


# ----------------------------------------------------------------------
# metrics registry


class TestMetrics:
    def test_sanitize_name(self):
        assert sanitize_name("ok_name2") == "ok_name2"
        assert sanitize_name("bad-name.x") == "bad_name_x"
        assert sanitize_name("9starts_digit") == "_9starts_digit"

    def test_zero_mean_survives_snapshot(self):
        # regression: `if t.mean` treated a legitimate 0.0 mean as
        # missing; snapshot must distinguish 0.0 from no-observations
        m = Metrics()
        m.timer("instant_seconds").observe(0.0)
        snap = m.snapshot()
        assert snap["instant_seconds"]["mean_seconds"] == 0.0
        assert snap["instant_seconds"]["count"] == 1

    def test_labels_and_help_exposition(self):
        m = Metrics()
        m.gauge("template_device_seconds",
                help="per-template attributed device seconds",
                template="K8sRequiredLabels").set(0.25)
        m.gauge("template_device_seconds",
                template="K8sAllowedRepos").set(0.75)
        m.counter("bad-name!").inc()
        text = m.render_prometheus(prefix="gatekeeper")
        assert ("# HELP gatekeeper_template_device_seconds "
                "per-template attributed device seconds") in text
        assert "# TYPE gatekeeper_template_device_seconds gauge" in text
        assert ('gatekeeper_template_device_seconds'
                '{template="K8sRequiredLabels"} 0.25') in text
        assert ('gatekeeper_template_device_seconds'
                '{template="K8sAllowedRepos"} 0.75') in text
        assert "gatekeeper_bad_name_ 1" in text
        # every exposed series name obeys the Prometheus charset
        for line in text.splitlines():
            if not line or line.startswith("#"):
                continue
            name = line.split("{")[0].split(" ")[0]
            assert sanitize_name(name) == name, line

    def test_histogram_exposition(self):
        m = Metrics()
        t = m.timer("admission_seconds")
        for s in (0.0002, 0.003, 0.003, 7.0, 42.0):
            t.observe(s)
        text = m.render_prometheus(prefix="g")
        assert "# TYPE g_admission_seconds histogram" in text
        assert 'g_admission_seconds_bucket{le="0.00025"} 1' in text
        assert 'g_admission_seconds_bucket{le="0.005"} 3' in text
        assert 'g_admission_seconds_bucket{le="10"} 4' in text
        assert 'g_admission_seconds_bucket{le="+Inf"} 5' in text
        assert "g_admission_seconds_count 5" in text
        assert "g_admission_seconds_sum 49.006200" in text
        # cumulative monotonicity across the whole ladder
        accs = [int(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
                if ln.startswith("g_admission_seconds_bucket")]
        assert accs == sorted(accs)

    def test_snapshot_keys_carry_labels(self):
        m = Metrics()
        m.counter("denials", kind="K8sRequiredLabels").inc(3)
        snap = m.snapshot()
        assert snap['denials{kind="K8sRequiredLabels"}'] == 3


# ----------------------------------------------------------------------
# attribution


class TestAttribution:
    def test_unit_fallback_weights_sum_exactly(self):
        from gatekeeper_tpu.analysis import costmodel
        from gatekeeper_tpu.obs.attribution import attribute_sweep
        costmodel.reset_calibration()
        # bogus lowered objects: every estimate fails -> unit weights
        entries = [("A", object(), 2), ("B", object(), 3),
                   ("C", object(), 1)]
        out = attribute_sweep(entries, device_s=0.9, n_rows=100)
        rows = out["templates"]
        assert [r["template"] for r in rows] == ["A", "B", "C"]
        total = sum(r["device_seconds"] for r in rows)
        assert abs(total - 0.9) < 1e-9      # sums to measured exactly
        assert all(abs(r["share"] - 1 / 3) < 1e-6 for r in rows)
        # the apportioned seconds fed calibration
        assert costmodel.calibration_info()["samples"] == 3

    def test_full_sweep_attribution_sums_to_device_time(self, clean_backend):
        from gatekeeper_tpu.analysis import costmodel
        costmodel.reset_calibration()
        jd, _client = _mk_client(n=24)
        assert _audit(jd), "workload must produce violations"
        phases = jd.last_sweep_phases
        att = phases.get("attribution")
        assert att is not None, f"no attribution on a full sweep: {phases}"
        total = sum(r["device_seconds"] for r in att["templates"])
        assert att["device_s"] > 0
        assert abs(total - att["device_s"]) / att["device_s"] < 0.01
        kinds = {r["template"] for r in att["templates"]}
        assert kinds == {"K8sRequiredLabels", "K8sAllowedRepos",
                         "K8sDisallowedTags"}
        # per-kind measured dispatch seconds anchor the drift report
        assert any(r["measured_seconds"] for r in att["templates"])
        assert costmodel.calibration_info()["samples"] >= 3
        # the labelled gauges reached the driver's registry
        text = jd.metrics.render_prometheus()
        assert ('gatekeeper_template_device_seconds'
                '{template="K8sRequiredLabels"}') in text
        # memoized follow-up sweeps keep the lean phases dict (plus the
        # Stage-5/-6 selective-invalidation and sharding stanzas)
        _audit(jd, full=False)
        assert jd.last_sweep_phases["full"] is False
        assert set(jd.last_sweep_phases) <= {"full", "footprint", "shard", "dispatch",
                                             "pages", "devpages"}


# ----------------------------------------------------------------------
# flight recorder


class TestFlightRecorder:
    def test_ring_is_bounded(self):
        rec = FlightRecorder(ring=8)
        for i in range(40):
            rec.record("tick", i=i)
        evs = rec.snapshot()
        assert len(rec) == 8
        assert [e["i"] for e in evs] == list(range(32, 40))

    def test_record_attaches_active_trace(self):
        rec = FlightRecorder(ring=8)
        with get_tracer().span("flightspan") as sp:
            rec.record("probe_result", ok=True)
        assert rec.snapshot()[-1]["trace"] == sp.trace_id

    def test_dump_structure_and_prune(self, monkeypatch, tmp_path):
        monkeypatch.setenv("GATEKEEPER_FLIGHT_DIR", str(tmp_path))
        monkeypatch.setenv("GATEKEEPER_FLIGHT_KEEP", "3")
        rec = FlightRecorder(ring=16)
        rec.record("breaker_flip", frm="closed", to="open")
        paths = [rec.dump(f"test:{i}") for i in range(5)]
        assert all(paths)
        files = sorted(os.listdir(tmp_path))
        assert len(files) == 3              # pruned to the newest keep
        with open(tmp_path / files[-1]) as f:
            doc = json.load(f)
        assert doc["reason"] == "test:4"
        assert doc["pid"] == os.getpid()
        assert doc["events"][-1]["type"] == "breaker_flip"
        assert "traceEvents" in doc["trace"]

    def test_dump_failure_returns_none(self, monkeypatch, tmp_path):
        bad = tmp_path / "file-not-dir"
        bad.write_text("x")
        monkeypatch.setenv("GATEKEEPER_FLIGHT_DIR", str(bad))
        assert FlightRecorder(ring=4).dump("nope") is None


# ----------------------------------------------------------------------
# acceptance: device_lost leaves evidence behind


class TestDeviceLostFlightDump:
    def test_dump_holds_transition_and_sweep_span(
            self, clean_backend, monkeypatch, tmp_path):
        monkeypatch.setenv("GATEKEEPER_FLIGHT_DIR", str(tmp_path))
        monkeypatch.setenv("GATEKEEPER_FAULT", "device_lost")
        get_tracer().reset()
        jd, _client = _mk_client(n=24)
        got = _audit(jd)
        assert got, "faulted sweep must still complete"
        from gatekeeper_tpu.resilience import supervisor as sup_mod
        assert jd.supervisor.state == sup_mod.DEGRADED

        dumps = sorted(tmp_path.glob("flight-*.json"))
        assert dumps, "device_lost produced no flight dump"
        # the fault trip dumps first, the supervisor demotion second;
        # the acceptance contract is on the union of what survived
        transitions, sweep_spans = [], []
        for p in dumps:
            doc = json.loads(p.read_text())
            transitions += [e for e in doc["events"]
                            if e["type"] == "supervisor_transition"]
            sweep_spans += [e for e in doc["trace"]["traceEvents"]
                            if e["name"] == "audit.sweep"]
        assert any(t["to"] == sup_mod.DEGRADED and
                   "device_lost" in t["reason"] for t in transitions), \
            transitions
        assert any(e for e in sweep_spans), \
            "no audit.sweep span in any flight dump"
        # the demotion fired mid-sweep: the span tree was captured
        # while the sweep was still open
        assert any(e["args"].get("incomplete") for e in sweep_spans)
        # fault trip landed on the ring too
        all_events = [e for p in dumps
                      for e in json.loads(p.read_text())["events"]]
        assert any(e["type"] == "fault_trip" and e["fault"] == "device_lost"
                   for e in all_events)


# ----------------------------------------------------------------------
# endpoints under concurrent admission load


class TestEndpointsUnderLoad:
    def test_metrics_and_trace_serve_during_admission(self):
        from gatekeeper_tpu.client.client import Backend
        from gatekeeper_tpu.client.local_driver import LocalDriver
        from gatekeeper_tpu.target.k8s import K8sValidationTarget
        from gatekeeper_tpu.webhook.policy import ValidationHandler
        from gatekeeper_tpu.webhook.server import WebhookServer
        from tests.test_control_plane import (constraint_obj, ns_obj,
                                              template_obj)

        client = Backend(LocalDriver()).new_client([K8sValidationTarget()])
        client.add_template(template_obj())
        client.add_constraint(constraint_obj())
        server = WebhookServer(ValidationHandler(client), port=0)
        server.start()
        stop = threading.Event()
        errors: list = []
        try:
            base = f"http://127.0.0.1:{server.port}"

            def admit(i):
                obj = ns_obj(f"ns-{i}",
                             {"gatekeeper": "on"} if i % 2 else None)
                body = {"apiVersion": "admission.k8s.io/v1beta1",
                        "kind": "AdmissionReview",
                        "request": {
                            "uid": f"u{i}", "operation": "CREATE",
                            "kind": {"group": "", "version": "v1",
                                     "kind": "Namespace"},
                            "name": obj["metadata"]["name"],
                            "userInfo": {"username": "t", "groups": []},
                            "object": obj}}
                req = urllib.request.Request(
                    f"{base}/v1/admit", data=json.dumps(body).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=10) as resp:
                    json.loads(resp.read())

            admitted = threading.Event()

            def hammer(tid):
                i = 0
                try:
                    while not stop.is_set():
                        admit(tid * 1000 + i)
                        admitted.set()
                        i += 1
                except Exception as e:      # noqa: BLE001
                    errors.append(e)

            threads = [threading.Thread(target=hammer, args=(t,))
                       for t in range(3)]
            for t in threads:
                t.start()
            # the histogram exists once the first admission completed
            assert admitted.wait(30), errors
            deadline = time.monotonic() + 2.0
            reads = 0
            while time.monotonic() < deadline:
                # /metrics: every line is a comment or `series value` —
                # a torn exposition (half-written family) fails this
                with urllib.request.urlopen(f"{base}/metrics",
                                            timeout=10) as resp:
                    text = resp.read().decode()
                for line in text.splitlines():
                    if not line or line.startswith("#"):
                        continue
                    name, _, value = line.rpartition(" ")
                    assert name and value, f"torn exposition line: {line!r}"
                    float(value)
                assert "gatekeeper_admission_seconds_bucket" in text
                # /debug/trace: valid Chrome trace JSON at any moment
                with urllib.request.urlopen(f"{base}/debug/trace",
                                            timeout=10) as resp:
                    assert resp.headers["Content-Type"].startswith(
                        "application/json")
                    doc = json.loads(resp.read())
                assert isinstance(doc["traceEvents"], list)
                reads += 1
            stop.set()
            for t in threads:
                t.join(10)
            assert not errors, errors
            assert reads >= 3
            # admission spans actually flowed into the trace export
            names = {e["name"] for e in doc["traceEvents"]}
            assert "admission.request" in names
        finally:
            stop.set()
            server.stop()


# ----------------------------------------------------------------------
# probe --trace artifact (in-process)


@pytest.mark.slow
class TestProbeTrace:
    def test_run_trace_artifact(self, monkeypatch, tmp_path, clean_backend):
        from gatekeeper_tpu.client import probe
        monkeypatch.setenv("GATEKEEPER_TRACE_PROBE_N", "40")
        out = tmp_path / "trace.json"
        rc = probe.run_trace(str(out))
        assert rc == 0
        doc = json.loads(out.read_text())
        evs = doc["traceEvents"]
        assert evs and all(e["ph"] == "X" for e in evs)
        assert any(e["name"] == "audit.sweep" for e in evs)
        gt = doc["gatekeeperTrace"]
        att = gt["attribution"]
        total = sum(r["device_seconds"] for r in att["templates"])
        assert abs(total - gt["device_s"]) / gt["device_s"] < 0.01
