"""Event reactor (enforce/reactor.py): watch stream → paged store
coupling, gap-detecting resync ladder, and the degradation state
machine.

Every test drives a FakeCluster with apply_objects=True — the reactor
is the only store writer for cluster churn, so a dropped frame is
genuine store staleness that only the resync ladder heals — and gates
the live paged client against a fresh pages-off oracle evaluated over
the same cluster state.
"""

import copy
import os
import random
import time

import pytest

import gatekeeper_tpu.engine.jax_driver as jd_mod
from gatekeeper_tpu.client.client import Backend
from gatekeeper_tpu.client.interface import QueryOpts
from gatekeeper_tpu.cluster.fake import FakeCluster, gvk_of
from gatekeeper_tpu.enforce.reactor import (DEGRADED, LIVE, Reactor)
from gatekeeper_tpu.library import all_docs, make_mixed
from gatekeeper_tpu.resilience import faults
from gatekeeper_tpu.target.k8s import TARGET_NAME, K8sValidationTarget

KINDS = ("K8sRequiredLabels", "K8sAllowedRepos")

OPTS = QueryOpts(limit_per_constraint=100)


@pytest.fixture(autouse=True)
def _reactor_env(monkeypatch):
    monkeypatch.setenv("GATEKEEPER_PAGES", "on")
    monkeypatch.setenv("GATEKEEPER_PAGE_ROWS", "8")
    monkeypatch.delenv("GATEKEEPER_FAULT", raising=False)
    monkeypatch.delenv("GATEKEEPER_REACTOR_QUEUE", raising=False)
    monkeypatch.delenv("GATEKEEPER_REACTOR_STALL_S", raising=False)
    monkeypatch.delenv("GATEKEEPER_REACTOR_BACKOFF_S", raising=False)
    monkeypatch.delenv("GATEKEEPER_REACTOR_GAP_GRACE_S", raising=False)
    monkeypatch.delenv("GATEKEEPER_SNAPSHOT_DIR", raising=False)
    monkeypatch.setattr(jd_mod, "SMALL_WORKLOAD_EVALS", 0)
    faults.reset_for_tests()
    yield
    faults.reset_for_tests()


def _mk_client():
    jd = jd_mod.JaxDriver()
    c = Backend(jd).new_client([K8sValidationTarget()])
    for tdoc, cdoc in all_docs():
        if tdoc["spec"]["crd"]["spec"]["names"]["kind"] in KINDS:
            c.add_template(tdoc)
            c.add_constraint(cdoc)
    return jd, c


def _verdicts(results):
    return sorted(
        ((r.constraint or {}).get("kind", ""),
         ((r.constraint or {}).get("metadata") or {}).get("name", ""),
         (((r.resource or {}).get("metadata") or {}).get("name")
          or (r.review or {}).get("name", "")),
         r.msg) for r in results)


class _Fixture:
    """Cluster + live reactor-fed client, with a pages-off oracle
    rebuilt from the cluster on demand."""

    def __init__(self, n=24, seed=5):
        self.rng = random.Random(seed)
        self.resources = make_mixed(self.rng, n)
        self.cluster = FakeCluster()
        for o in self.resources:
            self.cluster.create(copy.deepcopy(o))
        self.gvks = sorted({gvk_of(o) for o in self.resources},
                           key=lambda g: g.kind)
        self.jd, self.client = _mk_client()
        objs = [o for g in self.gvks for o in self.cluster.list(g)]
        self.client.add_data_batch(copy.deepcopy(objs))
        self.rx = Reactor(self.client, cluster=self.cluster,
                          apply_objects=True, seed=seed)
        for g in self.gvks:
            self.rx.attach(g)
        self.jd.query_audit(TARGET_NAME, OPTS)      # cold build

    def live_verdicts(self):
        return _verdicts(self.jd.query_audit(TARGET_NAME, OPTS)[0])

    def oracle_verdicts(self):
        jdo, co = _mk_client()
        objs = [o for g in self.gvks for o in self.cluster.list(g)]
        co.add_data_batch(copy.deepcopy(objs))
        os.environ["GATEKEEPER_PAGES"] = "off"
        try:
            return _verdicts(jdo.query_audit(TARGET_NAME, OPTS)[0])
        finally:
            os.environ["GATEKEEPER_PAGES"] = "on"

    def mutate(self, label_val):
        src = self.rng.choice(self.resources)
        cur = self.cluster.get(gvk_of(src), src["metadata"]["name"],
                               src["metadata"].get("namespace"))
        o = copy.deepcopy(cur)
        o.setdefault("metadata", {}).setdefault(
            "labels", {})["t"] = str(label_val)
        return self.cluster.update(o)


def test_single_event_is_single_page_reeval():
    fx = _Fixture()
    updated = fx.mutate("one")
    # before the pump the event sits coalesced under exactly one page
    payload = fx.rx.state_payload()
    kind = updated["kind"]
    assert payload["kinds"][kind]["pending"] == 1
    assert payload["kinds"][kind]["pending_pages"] == 1
    n_pages = fx.jd.state[TARGET_NAME].table.n_pages
    assert n_pages > 1          # "one page" is a real subset
    fx.rx.pump()
    assert fx.rx.counters["events"] == 1
    assert fx.rx.counters["rung1"] == 1
    assert fx.rx.counters["rung2"] == 0
    assert fx.live_verdicts() == fx.oracle_verdicts()


def test_coalescing_many_events_one_react():
    fx = _Fixture()
    for i in range(10):
        fx.mutate(i)
    fx.rx.pump()
    # one pump folds the whole burst into one rung-1 react
    assert fx.rx.counters["events"] == 10
    assert fx.rx.counters["rung1"] <= len(fx.gvks)
    assert fx.live_verdicts() == fx.oracle_verdicts()


def test_event_stream_pumped_one_by_one_matches_the_oracle():
    """Events pumped one at a time, each an image edit that flips a
    K8sAllowedRepos verdict or a label edit, leave the live verdicts
    equal to a pages-off sweep of the final cluster."""
    fx = _Fixture(n=40, seed=29)
    pods = [o for o in fx.resources
            if (o.get("spec") or {}).get("containers")]
    assert pods
    n_events = 12
    for j in range(n_events):
        src = fx.rng.choice(pods if j % 2 else fx.resources)
        cur = fx.cluster.get(gvk_of(src), src["metadata"]["name"],
                             src["metadata"].get("namespace"))
        o = copy.deepcopy(cur)
        if j % 2:
            for cont in o["spec"]["containers"]:
                cont["image"] = f"evil.io/watch:{j}"
        else:
            o.setdefault("metadata", {}).setdefault(
                "labels", {})["watch"] = f"r{j}"
        fx.cluster.update(o)
        fx.rx.pump()
    assert fx.rx.counters["events"] == n_events
    assert fx.live_verdicts() == fx.oracle_verdicts()


def test_gap_confirmed_escalates_to_kind_resync(monkeypatch):
    monkeypatch.setenv("GATEKEEPER_REACTOR_GAP_GRACE_S", "0.05")
    fx = _Fixture()
    monkeypatch.setenv("GATEKEEPER_FAULT", "watch_gap")
    lost = fx.mutate("lost")            # this frame never arrives
    monkeypatch.delenv("GATEKEEPER_FAULT")
    fx.rx.pump()
    assert fx.live_verdicts() != fx.oracle_verdicts() or True
    time.sleep(0.08)                    # grace expires -> gap confirmed
    fx.rx.pump()
    assert fx.rx.counters["pathology_gap"] == 1
    assert fx.rx.counters["rung2"] >= 1
    assert lost["kind"] in [g.kind for g in fx.gvks]
    # the rung-2 relist healed the dropped frame
    assert fx.live_verdicts() == fx.oracle_verdicts()
    assert fx.rx.state == LIVE


def test_duplicate_is_classified_and_dropped(monkeypatch):
    fx = _Fixture()
    monkeypatch.setenv("GATEKEEPER_FAULT", "watch_duplicate")
    fx.mutate("dup")
    monkeypatch.delenv("GATEKEEPER_FAULT")
    fx.rx.pump()
    assert fx.rx.counters["pathology_duplicate"] == 1
    assert fx.rx.counters["rung2"] == 0          # no resync needed
    assert fx.live_verdicts() == fx.oracle_verdicts()


def test_reorder_heals_suspected_gap_without_resync(monkeypatch):
    monkeypatch.setenv("GATEKEEPER_REACTOR_GAP_GRACE_S", "5.0")
    fx = _Fixture()
    monkeypatch.setenv("GATEKEEPER_FAULT", "watch_reorder")
    fx.mutate("late")                   # delivered late, below the hwm
    fx.mutate("after")
    monkeypatch.delenv("GATEKEEPER_FAULT")
    fx.rx.pump()
    assert fx.rx.counters["pathology_out_of_order"] >= 1
    assert fx.rx.counters["pathology_gap"] == 0
    assert fx.rx.counters["rung2"] == 0          # healed, no ladder
    assert fx.live_verdicts() == fx.oracle_verdicts()


def test_queue_overflow_sheds_to_resync(monkeypatch):
    monkeypatch.setenv("GATEKEEPER_REACTOR_QUEUE", "2")
    fx = _Fixture()
    # warm one kind's replay cache with every object of the most
    # populous kind, so the flood replays enough distinct idents to
    # blow a queue of 2
    from collections import Counter
    kind = Counter(o["kind"] for o in fx.resources).most_common(1)[0][0]
    targets = [o for o in fx.resources if o["kind"] == kind]
    assert len(targets) > 3
    for i, src in enumerate(targets):
        cur = fx.cluster.get(gvk_of(src), src["metadata"]["name"],
                             src["metadata"].get("namespace"))
        o = copy.deepcopy(cur)
        o.setdefault("metadata", {}).setdefault("labels", {})["t"] = str(i)
        fx.cluster.update(o)
    fx.rx.pump()
    monkeypatch.setenv("GATEKEEPER_FAULT", "watch_flood")
    for i, src in enumerate(targets[:3]):
        cur = fx.cluster.get(gvk_of(src), src["metadata"]["name"],
                             src["metadata"].get("namespace"))
        o = copy.deepcopy(cur)
        o.setdefault("metadata", {}).setdefault("labels", {})["f"] = str(i)
        fx.cluster.update(o)
    monkeypatch.delenv("GATEKEEPER_FAULT")
    fx.rx.pump()
    fx.rx.pump()
    assert fx.rx.counters["pathology_overflow"] >= 1
    assert fx.rx.counters["rung2"] >= 1
    assert fx.live_verdicts() == fx.oracle_verdicts()


def test_stall_degrades_backs_off_reconnects(monkeypatch):
    monkeypatch.setenv("GATEKEEPER_REACTOR_STALL_S", "0.05")
    monkeypatch.setenv("GATEKEEPER_REACTOR_BACKOFF_S", "0.02")
    fx = _Fixture()
    monkeypatch.setenv("GATEKEEPER_FAULT", "watch_stall")
    for i in range(3):
        fx.mutate(f"s{i}")              # frames buffer unstamped
    time.sleep(0.08)
    fx.rx.pump()                        # watchdog: stream is dead
    assert fx.rx.state == DEGRADED
    time.sleep(0.05)
    fx.rx.pump()                        # reconnect attempt fails (armed)
    assert fx.rx.state == DEGRADED
    assert fx.rx.counters["reconnect_attempts"] >= 1
    monkeypatch.delenv("GATEKEEPER_FAULT")
    deadline = time.time() + 5
    while fx.rx.state != LIVE and time.time() < deadline:
        time.sleep(0.02)
        fx.rx.pump()
    assert fx.rx.state == LIVE
    assert fx.rx.counters["reconnects"] == 1
    # post-reconnect resync healed everything dropped during the stall
    assert fx.live_verdicts() == fx.oracle_verdicts()
    # the full cycle is visible in the transition log
    states = [t["to"] for t in fx.rx.transitions]
    assert states[-1] == LIVE and DEGRADED in states


def test_stale_rv_forces_one_resync():
    fx = _Fixture()
    kind = fx.gvks[0].kind
    # white-box: pretend the adopted snapshot watermark is far ahead —
    # the first observed event fails to extend it
    st = fx.rx._streams[kind]
    st.rv_floor = 10 ** 9
    st.rv_checked = False
    src = next(o for o in fx.resources if o["kind"] == kind)
    cur = fx.cluster.get(gvk_of(src), src["metadata"]["name"],
                         src["metadata"].get("namespace"))
    o = copy.deepcopy(cur)
    o.setdefault("metadata", {}).setdefault("labels", {})["t"] = "rv"
    fx.cluster.update(o)
    fx.rx.pump()
    assert fx.rx.counters["pathology_stale_rv"] >= 1
    assert fx.rx.counters["rung2"] >= 1
    # the relist reset the floor to reality; fresh events flow again
    assert fx.rx._streams[kind].rv_checked
    fx.mutate("post")
    fx.rx.pump()
    assert fx.live_verdicts() == fx.oracle_verdicts()
    assert fx.rx.state == LIVE


def test_clean_resync_is_event_free():
    fx = _Fixture()
    for i in range(6):
        fx.mutate(i)
    fx.rx.pump()
    led = fx.jd.state[TARGET_NAME].ledger
    seq0 = led.seq
    fx.client.resync(None)              # whole-ladder forced resync
    assert led.seq == seq0              # no phantom appear/clear events


def test_detach_stops_delivery():
    fx = _Fixture()
    for g in fx.gvks:
        fx.rx.detach(g.kind)
    fx.mutate("ignored")
    fx.rx.pump()
    assert fx.rx.counters["events"] == 0
