"""What-if engine tests (ROADMAP item 5): shadow policy sets,
historical replay, multi-cluster batched audit, and the admission
corpus hygiene the replay path depends on.

The parity contracts here are bit-exact, not approximate:

- a ShadowSession sweep's candidate half must equal a standalone
  install of the candidate set over the same store, tuple for tuple;
- replaying a recorded admission stream under the same policy set must
  reproduce every recorded verdict;
- the stacked fleet sweep must match a per-cluster audit loop.
"""

import os
import random

import pytest

from gatekeeper_tpu.client.client import Backend
from gatekeeper_tpu.engine.jax_driver import JaxDriver
from gatekeeper_tpu.library import all_docs, make_mixed
from gatekeeper_tpu.target.k8s import K8sValidationTarget
from gatekeeper_tpu.whatif import (ShadowSession, fleet_audit,
                                   fleet_loop_oracle, make_cluster,
                                   normalize_results, replay_admissions,
                                   replay_snapshot,
                                   standalone_candidate_verdicts,
                                   verdict_digest)

N_TEMPLATES = 8          # library prefix: enough diversity, fast compile
N_ROWS = 120


def _policy_subset(n=N_TEMPLATES):
    pairs = all_docs()[:n]
    return [t for t, _c in pairs], [c for _t, c in pairs]


def _mk_client(templates, constraints, n_rows=N_ROWS, seed=7):
    driver = JaxDriver()
    handler = K8sValidationTarget()
    client = Backend(driver).new_client([handler])
    for d in templates:
        client.add_template(d)
    for d in constraints:
        client.add_constraint(d)
    client.add_data_batch(make_mixed(random.Random(seed), n_rows))
    return driver, handler, client


@pytest.fixture(scope="module")
def live():
    templates, constraints = _policy_subset()
    driver, handler, client = _mk_client(templates, constraints)
    state = driver._state(handler.name).table.snapshot_state()
    baseline = normalize_results(
        client.audit(limit_per_constraint=20, full=True).results())
    return {"templates": templates, "constraints": constraints,
            "driver": driver, "handler": handler, "client": client,
            "state": state, "baseline": baseline}


# ---------------------------------------------------------------------------
# shadow installs


class TestShadow:
    def test_candidate_parity_is_bit_identical(self, live):
        """The acceptance contract: one sweep over live ∪ shadow, the
        shadow half bit-identical to installing the candidate alone."""
        cand = [dict(c) for c in live["constraints"][1:]]   # drop one
        sess = ShadowSession(live["client"], tag="v2")
        sess.stage(live["templates"], cand)
        try:
            rep = sess.sweep(limit_per_constraint=20)
        finally:
            sess.unstage()
        oracle = standalone_candidate_verdicts(
            live["templates"], cand, live["state"], 20)
        assert rep.shadow == oracle
        assert rep.shadow_digest == verdict_digest(oracle)

    def test_live_half_unchanged_and_diff_shape(self, live):
        """Staging must not perturb the live verdicts, and the diff
        must be exactly the dropped constraint's violations."""
        dropped = live["constraints"][0]
        cand = [dict(c) for c in live["constraints"][1:]]
        with ShadowSession(live["client"], tag="diff") as sess:
            sess.stage(live["templates"], cand)
            rep = sess.sweep(limit_per_constraint=20)
        assert rep.live == live["baseline"]
        assert rep.added == []
        dropped_name = dropped["metadata"]["name"]
        assert all(v[1] == dropped_name for v in rep.cleared)
        cleared_in_live = [v for v in live["baseline"]
                          if v[1] == dropped_name]
        assert [v[:-1] for v in rep.cleared] == \
            [v[:-1] for v in cleared_in_live]
        assert rep.by_constraint.get(dropped_name, {}).get("cleared") == \
            len(rep.cleared)

    def test_unstage_restores_live_set(self, live):
        cand = [dict(c) for c in live["constraints"]]
        sess = ShadowSession(live["client"], tag="undo")
        sess.stage(live["templates"], cand)
        sess.unstage()
        after = normalize_results(
            live["client"].audit(limit_per_constraint=20,
                                 full=True).results())
        assert after == live["baseline"]

    def test_cross_version_dedup_sharing(self, live):
        """The PR-5 dedup digests ignore kind names, so a staged twin
        of the live set must share conjunct groups ACROSS versions."""
        if live["driver"].scalar_only or \
                os.environ.get("GATEKEEPER_DEDUP") == "off":
            pytest.skip("dedup plan needs the device sweep")
        with ShadowSession(live["client"], tag="twin") as sess:
            sess.stage(live["templates"],
                       [dict(c) for c in live["constraints"]])
            rep = sess.sweep(limit_per_constraint=20)
        assert rep.dedup["groups_cross_version"] > 0
        assert rep.dedup["sites_cross_version"] >= \
            2 * rep.dedup["groups_cross_version"]

    def test_twin_dispatch_sharing_at_device_scale(self, live, monkeypatch):
        """Above SMALL_WORKLOAD_EVALS an unchanged shadow twin must
        alias the live kind's device dispatch (jax_driver._twin_future)
        instead of re-running it, and sharing must stay bit-identical
        to the GATEKEEPER_WHATIF_SHARE=off oracle."""
        if live["driver"].scalar_only:
            pytest.skip("twin sharing needs the device sweep")
        templates, constraints = _policy_subset(3)
        driver, handler, client = _mk_client(
            templates, constraints, n_rows=20_000)
        sess = ShadowSession(client, tag="twin")
        sess.stage(templates, [dict(c) for c in constraints])
        try:
            rep = sess.sweep(limit_per_constraint=20)
            stats = driver.last_sweep_phases.get("whatif") or {}
            assert stats.get("twin_shared_kinds", 0) >= 1
            monkeypatch.setenv("GATEKEEPER_WHATIF_SHARE", "off")
            rep_off = sess.sweep(limit_per_constraint=20)
            assert driver.last_sweep_phases.get("whatif") is None
        finally:
            sess.unstage()
        assert rep.shadow == rep_off.shadow
        assert rep.live == rep_off.live
        assert rep.shadow_digest == rep_off.shadow_digest

    def test_stage_failure_unwinds(self, live):
        bad = {"kind": "NoSuchTemplateKind", "metadata": {"name": "x"},
               "spec": {}}
        sess = ShadowSession(live["client"], tag="boom")
        with pytest.raises(Exception):
            sess.stage(live["templates"], [bad])
        assert sess._templates == [] and sess._constraints == []
        after = normalize_results(
            live["client"].audit(limit_per_constraint=20,
                                 full=True).results())
        assert after == live["baseline"]

    def test_shadow_kind_helpers(self):
        from gatekeeper_tpu.analysis.policyset import (is_shadow_kind,
                                                       shadow_kind,
                                                       split_shadow_kind)
        sk = shadow_kind("K8sFoo", "v2")
        assert is_shadow_kind(sk) and not is_shadow_kind("K8sFoo")
        assert split_shadow_kind(sk) == ("K8sFoo", "v2")
        assert split_shadow_kind("K8sFoo") == ("K8sFoo", None)
        with pytest.raises(ValueError):
            shadow_kind(sk, "v3")            # no double-staging
        with pytest.raises(ValueError):
            shadow_kind("K8sFoo", "bad tag")  # tag charset


# ---------------------------------------------------------------------------
# historical replay


class TestReplay:
    def test_snapshot_replay_parity(self, live):
        rep = replay_snapshot(live["templates"], live["constraints"],
                              live["state"], 20)
        assert rep.verdicts == live["baseline"]
        assert rep.digest == verdict_digest(live["baseline"])
        assert rep.n_resources > 0

    def test_snapshot_replay_under_candidate_set(self, live):
        cand = [dict(c) for c in live["constraints"][1:]]
        rep = replay_snapshot(live["templates"], cand, live["state"], 20)
        oracle = standalone_candidate_verdicts(
            live["templates"], cand, live["state"], 20)
        assert rep.verdicts == oracle

    def test_load_historical_store_round_trip(self, live, monkeypatch,
                                              tmp_path):
        """Write a store snapshot under one root, then read it back as
        a HISTORICAL root while the live env points elsewhere."""
        from gatekeeper_tpu.resilience import snapshot as snap
        from gatekeeper_tpu.whatif import load_historical_store
        root = str(tmp_path / "snaps")
        monkeypatch.setenv("GATEKEEPER_SNAPSHOT_DIR", root)
        assert snap.save_store(live["handler"].name, live["state"])
        monkeypatch.delenv("GATEKEEPER_SNAPSHOT_DIR")
        assert load_historical_store(live["handler"].name) is None
        got = load_historical_store(live["handler"].name, root=root)
        assert got is not None
        rep = replay_snapshot(live["templates"], live["constraints"],
                              got, 20)
        assert rep.verdicts == live["baseline"]

    def test_admission_stream_replay_is_exact(self, live, monkeypatch,
                                              tmp_path):
        """Record a webhook admission stream into the corpus, replay it
        through the same policy set, and demand exact reproduction."""
        from gatekeeper_tpu.obs import flightrecorder as fr
        from gatekeeper_tpu.webhook.policy import ValidationHandler
        monkeypatch.setenv("GATEKEEPER_FLIGHT_DIR", str(tmp_path))
        monkeypatch.setenv("GATEKEEPER_FLIGHT_ADMISSION", "1")
        monkeypatch.setattr(fr, "_recorder", None)

        handler = ValidationHandler(live["client"])
        reviews = make_mixed(random.Random(11), 12)
        for obj in reviews:
            handler.handle({
                "uid": "u", "operation": "CREATE",
                "kind": {"group": "", "version": "v1",
                         "kind": obj.get("kind", "")},
                "name": (obj.get("metadata") or {}).get("name", ""),
                "userInfo": {"username": "alice", "groups": []},
                "object": obj})
        events = fr.load_admission_corpus(str(tmp_path))
        assert len(events) == len(reviews)
        srep = replay_admissions(events, live["client"])
        assert srep.exact, srep.mismatches[:2]
        assert srep.replayed == len(reviews)
        assert srep.matched == len(reviews)

    def test_truncated_events_are_skipped(self, live):
        events = [{"request": {"object": {"__truncated__": True}},
                   "allowed": True, "verdicts": []}]
        srep = replay_admissions(events, live["client"])
        assert srep.skipped_oversize == 1       # distinct from errors
        assert srep.skipped == 0 and srep.replayed == 0
        assert not srep.exact                   # nothing replayed
        from gatekeeper_tpu.whatif import replay_admissions_batched
        brep = replay_admissions_batched(events, live["client"])
        assert brep.skipped_oversize == 1 and brep.skipped == 0


# ---------------------------------------------------------------------------
# multi-cluster batched audit


class TestFleet:
    def test_stacked_matches_loop_oracle(self):
        """The fleet acceptance contract: heterogeneous stores, one
        stacked sweep, bit-identical to the per-cluster loop."""
        templates, constraints = _policy_subset()
        clusters = [
            make_cluster(f"c{i}", templates, constraints,
                         objs=make_mixed(random.Random(100 + i), 60 + 30 * i))
            for i in range(3)]
        rep = fleet_audit(clusters, 20)
        verdicts, digests, _wall = fleet_loop_oracle(clusters, 20)
        assert rep.digests == digests
        assert rep.verdicts == verdicts
        assert rep.n_clusters == 3
        if not clusters[0].driver.scalar_only:
            assert rep.kinds_stacked, rep.kinds_replicated
            assert rep.device_dispatches == len(rep.kinds_stacked)

    def test_four_cluster_stack_matches_loop_oracle(self):
        """Four equal-sized clusters stack into one sweep whose verdicts
        equal the per-cluster loop's."""
        templates, constraints = _policy_subset()
        clusters = [
            make_cluster(f"c{i}", templates, constraints,
                         objs=make_mixed(random.Random(100 + i), 50))
            for i in range(4)]
        rep = fleet_audit(clusters, 20)
        verdicts, digests, _wall = fleet_loop_oracle(clusters, 20)
        assert rep.n_clusters == 4
        assert rep.digests == digests
        assert rep.verdicts == verdicts

    def test_cluster_from_store_state(self, live):
        """A cluster seeded from a store snapshot audits identically to
        the client the snapshot came from."""
        templates, constraints = live["templates"], live["constraints"]
        cl = make_cluster("snap", templates, constraints,
                          store_state=live["state"])
        verdicts, _d, _w = fleet_loop_oracle([cl], 20)
        assert verdicts[0] == live["baseline"]

    def test_policy_set_mismatch_rejected(self):
        templates, constraints = _policy_subset(3)
        a = make_cluster("a", templates, constraints, objs=[])
        b = make_cluster("b", templates[:2], constraints[:2], objs=[])
        with pytest.raises(ValueError, match="share one policy set"):
            fleet_audit([a, b])

    def test_empty_fleet_rejected(self):
        with pytest.raises(ValueError):
            fleet_audit([])


# ---------------------------------------------------------------------------
# corpus hygiene (satellite: redact -> cap -> persist)


class TestCorpusHygiene:
    def test_managed_fields_stripped_and_secrets_redacted(self):
        from gatekeeper_tpu.obs.flightrecorder import (REDACTED,
                                                       redact_payload)
        obj = {"apiVersion": "v1", "kind": "Pod",
               "metadata": {"name": "p", "managedFields": [{"x": 1}],
                            "labels": {"a": "b"}},
               "spec": {"password": "hunter2",
                        "env": [{"name": "API_KEY", "apiKey": "k"}],
                        "image": "nginx"}}
        red = redact_payload(obj)
        assert "managedFields" not in red["metadata"]
        assert red["metadata"]["labels"] == {"a": "b"}
        assert red["spec"]["password"] == REDACTED
        assert red["spec"]["env"][0]["apiKey"] == REDACTED
        assert red["spec"]["image"] == "nginx"
        assert obj["spec"]["password"] == "hunter2"     # input untouched

    def test_secret_kind_values_blanket_redacted(self):
        from gatekeeper_tpu.obs.flightrecorder import (REDACTED,
                                                       redact_payload)
        sec = {"kind": "Secret", "metadata": {"name": "s"},
               "data": {"anything": "dmFsdWU="},
               "stringData": {"note": "plaintext"}}
        red = redact_payload(sec)
        assert red["data"]["anything"] == REDACTED
        assert red["stringData"]["note"] == REDACTED
        assert red["metadata"]["name"] == "s"

    def test_payload_cap_truncates_to_envelope(self, monkeypatch):
        from gatekeeper_tpu.obs.flightrecorder import (cap_payload,
                                                       payload_byte_cap)
        monkeypatch.setenv("GATEKEEPER_FLIGHT_PAYLOAD_BYTES", "200")
        assert payload_byte_cap() == 200
        big = {"apiVersion": "v1", "kind": "ConfigMap",
               "metadata": {"name": "big", "namespace": "ns",
                            "labels": {"l": "v"}},
               "data": {"blob": "x" * 1000}}
        capped = cap_payload(big)
        assert capped["__truncated__"] is True
        assert capped["__bytes__"] > 200
        assert capped["metadata"]["name"] == "big"
        assert "data" not in capped
        small = {"kind": "ConfigMap", "metadata": {"name": "s"}}
        assert cap_payload(small) == small

    def test_corpus_segments_pruned_by_keep(self, monkeypatch, tmp_path):
        """The capture log rotates at the segment cap and prunes sealed
        segments down to GATEKEEPER_CAPTURE_KEEP."""
        from gatekeeper_tpu.obs.flightrecorder import FlightRecorder
        monkeypatch.setenv("GATEKEEPER_FLIGHT_DIR", str(tmp_path))
        monkeypatch.setenv("GATEKEEPER_FLIGHT_ADMISSION", "1")
        monkeypatch.setenv("GATEKEEPER_CAPTURE_SEGMENT_BYTES", "4096")
        monkeypatch.setenv("GATEKEEPER_CAPTURE_KEEP", "2")
        rec = FlightRecorder(ring=8)
        for i in range(200):
            rec.record_admission(
                {"operation": "CREATE", "kind": {"kind": "Pod"},
                 "object": {"metadata": {"name": f"p{i}",
                                         "labels": {"pad": "x" * 64}}}},
                True)
        log = rec._capture_log()
        assert log.flush()
        st = rec.capture_stats()
        assert st["dropped"] == 0 and st["written"] == 200
        assert st["rotations"] >= 2             # cap actually rotated
        files = [f for f in os.listdir(tmp_path / "capture")
                 if f.endswith(".seg")]
        assert 0 < len(files) <= 2              # pruned to keep
        log.close()

    def test_record_admission_persists_verdict_fields(self, monkeypatch,
                                                      tmp_path):
        from gatekeeper_tpu.client.types import Result
        from gatekeeper_tpu.obs import flightrecorder as fr
        monkeypatch.setenv("GATEKEEPER_FLIGHT_DIR", str(tmp_path))
        monkeypatch.setenv("GATEKEEPER_FLIGHT_ADMISSION", "1")
        rec = fr.FlightRecorder(ring=8)
        r = Result(msg="no", constraint={"kind": "K8sFoo",
                                        "metadata": {"name": "c1"}},
                   enforcement_action="warn")
        rec.record_admission(
            {"operation": "CREATE", "kind": {"kind": "Pod"},
             "object": {"metadata": {"name": "p"},
                        "spec": {"token": "t"}}},
            True, verdicts=[r], warnings=["[warn by c1] no"])
        events = fr.load_admission_corpus(str(tmp_path))
        assert len(events) == 1
        ev = events[0]
        assert ev["allowed"] is True
        assert ev["verdicts"] == [{"kind": "K8sFoo", "name": "c1",
                                   "action": "warn", "msg": "no"}]
        assert ev["warnings"] == ["[warn by c1] no"]
        assert ev["request"]["object"]["spec"]["token"] == fr.REDACTED
