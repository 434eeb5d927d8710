"""Device-resident paged store (GATEKEEPER_DEVPAGES, enforce/devpages).

Covers the in-jit verdict delta stream's exact equality with the diff
of consecutive full oracle sweeps under randomized churn — including
freed-slot reuse by a DIFFERENT resource identity (the PR-14
clear-before-appear pairing must survive row->slot indirection) — the
device-lowered cross-row inventory join (K8sUniqueIngressHost becomes
page-eligible; deleting one duplicate clears the OTHER row's verdict
with no host re-eval of that row), the pg snapshot tier's device
pagemap geometry (warm restart adopts it with zero ledger rebuilds),
the per-kind widen scoping of dirty-log overflow (a kind whose
observable kinds are disjoint from the dropped half's skips the widen),
and H2D accounting (churn sweeps move row-sized records, not the
store).
"""

import collections
import copy
import os
import random

import pytest

from gatekeeper_tpu.analysis import footprint
from gatekeeper_tpu.client.client import Backend
from gatekeeper_tpu.client.interface import QueryOpts
from gatekeeper_tpu.library import all_docs, make_mixed
from gatekeeper_tpu.store import table as table_mod
from gatekeeper_tpu.target.k8s import K8sValidationTarget, TARGET_NAME


@pytest.fixture(autouse=True)
def _devpages_env(monkeypatch):
    monkeypatch.setattr(footprint, "_memo", {})
    monkeypatch.setattr(footprint, "cross_row", {})
    monkeypatch.setattr(footprint, "violations", {})
    monkeypatch.setattr(footprint, "analyses_run", 0)
    monkeypatch.setenv("GATEKEEPER_DEVPAGES", "on")
    monkeypatch.delenv("GATEKEEPER_PAGES", raising=False)
    monkeypatch.delenv("GATEKEEPER_PAGE_ROWS", raising=False)
    monkeypatch.delenv("GATEKEEPER_SNAPSHOT_DIR", raising=False)
    yield


def _mk_client(jd_mod, kinds):
    jd = jd_mod.JaxDriver()
    c = Backend(jd).new_client([K8sValidationTarget()])
    for tdoc, cdoc in all_docs():
        if tdoc["spec"]["crd"]["spec"]["names"]["kind"] in kinds:
            c.add_template(tdoc)
            c.add_constraint(cdoc)
    return jd, c


def _sweep(jd, opts, pages: bool, devpages: bool = True):
    os.environ["GATEKEEPER_PAGES"] = "on" if pages else "off"
    os.environ["GATEKEEPER_DEVPAGES"] = "on" if devpages else "off"
    try:
        return jd.query_audit(TARGET_NAME, opts)[0]
    finally:
        os.environ.pop("GATEKEEPER_PAGES", None)
        os.environ["GATEKEEPER_DEVPAGES"] = "on"


def _verdicts(results):
    out = []
    for r in results:
        obj = (r.review or {}).get("object") or {}
        out.append(
            ((r.constraint or {}).get("kind", ""),
             ((r.constraint or {}).get("metadata") or {}).get("name", ""),
             (obj.get("metadata") or {}).get("name", ""),
             r.msg))
    return sorted(out)


def _vcounter(results):
    out = collections.Counter()
    for r in results:
        kind = (r.constraint or {}).get("kind", "")
        cname = ((r.constraint or {}).get("metadata") or {}).get(
            "name", "")
        obj = (r.review or {}).get("object") or {}
        md = obj.get("metadata") or {}
        ns, name = md.get("namespace"), md.get("name")
        ref = f"{ns}/{name}" if ns else str(name)
        out[(kind, cname, ref, r.msg)] += 1
    return out


def _ingress(name: str, host: str, ns: str = "default") -> dict:
    return {"apiVersion": "extensions/v1beta1", "kind": "Ingress",
            "metadata": {"name": name, "namespace": ns},
            "spec": {"host": host, "rules": [{"host": host}],
                     "tls": [{"secretName": "tls"}]}}


class TestDeltaStream:
    KINDS = ("K8sRequiredLabels", "K8sAllowedRepos", "K8sBlockNodePort")

    def _drivers(self, monkeypatch, n=60, seed=5):
        import gatekeeper_tpu.engine.jax_driver as jd_mod
        monkeypatch.setattr(jd_mod, "SMALL_WORKLOAD_EVALS", 0)
        monkeypatch.setenv("GATEKEEPER_PAGE_ROWS", "16")
        resources = make_mixed(random.Random(seed), n)
        jd_p, cp = _mk_client(jd_mod, self.KINDS)
        jd_o, co = _mk_client(jd_mod, self.KINDS)
        for c in (cp, co):
            c.add_data_batch(copy.deepcopy(resources))
        return resources, jd_p, cp, jd_o, co

    def test_injit_deltas_equal_oracle_diff_with_slot_reuse(
            self, monkeypatch):
        """4 rounds of randomized churn where deletes free slots and
        later inserts of DIFFERENT identities reuse them: the in-jit
        delta stream's ledger events must equal the Counter-diff of
        consecutive full oracle sweeps exactly."""
        resources, jd_p, cp, jd_o, co = self._drivers(monkeypatch)
        opts = QueryOpts(limit_per_constraint=10_000)
        rng = random.Random(13)
        pods = [o for o in resources
                if (o.get("spec") or {}).get("containers")]
        rounds = []
        # 1: verdict flips
        batch = []
        for o in rng.sample(pods, 3):
            o = copy.deepcopy(o)
            o["spec"]["containers"][0]["image"] = "evil.io/devpages:1"
            batch.append(("upsert", o))
        rounds.append(batch)
        # 2: delete violating rows (frees their slots)
        doomed = rng.sample(resources, 4)
        rounds.append([("remove", copy.deepcopy(o)) for o in doomed])
        # 3: fresh inserts — different identities land in freed slots
        rounds.append([("upsert", o)
                       for o in make_mixed(random.Random(99), 4)])
        # 4: noise + another flip
        batch = [("upsert", copy.deepcopy(o)) for o in
                 (dict(o, metadata={**o.get("metadata", {}),
                                    "labels": {}})
                  for o in rng.sample(resources, 2))]
        rounds.append(batch)

        prev = collections.Counter()
        last_seq = 0
        dev_sweeps = 0
        for rnd in [[]] + rounds:
            for op, obj in rnd:
                for c in (cp, co):
                    o = copy.deepcopy(obj)
                    (c.add_data if op == "upsert" else c.remove_data)(o)
            _sweep(jd_p, opts, pages=True, devpages=True)
            cur = _vcounter(_sweep(jd_o, opts, pages=False,
                                   devpages=False))
            dvp = dict(jd_p.last_sweep_phases.get("devpages") or {})
            dev_sweeps += 1 if dvp.get("kinds_device") else 0
            led = jd_p._state(TARGET_NAME).ledger
            assert led is not None
            evs = [e for e in led.events if e["seq"] > last_seq]
            last_seq = led.seq
            appears = collections.Counter(
                (e["kind"], e["constraint"], e["resource"], e["msg"])
                for e in evs if e["op"] == "appear")
            clears = collections.Counter(
                (e["kind"], e["constraint"], e["resource"], e["msg"])
                for e in evs if e["op"] == "clear")
            assert appears == cur - prev
            assert clears == prev - cur
            prev = cur
        assert led.total_violations() == sum(prev.values())
        # the device path actually carried churn sweeps (cold sweep
        # builds the resident mask; later ones ride deltas)
        assert dev_sweeps >= len(rounds)

    def test_churn_h2d_is_row_sized(self, monkeypatch):
        """One churned row moves row-sized records, not the store: the
        devpages churn sweep's H2D accounting must come in well under
        the cold resident build.  n=240 so the store dwarfs the fixed
        per-scatter floor (indices pad to 8-row buckets)."""
        resources, jd_p, cp, _jd_o, _co = self._drivers(monkeypatch,
                                                        n=240)
        opts = QueryOpts(limit_per_constraint=20)
        _sweep(jd_p, opts, pages=True)                   # cold build
        cold = dict(jd_p.last_sweep_phases.get("devpages") or {})
        assert cold.get("kinds_device", 0) > 0
        assert cold.get("h2d_bytes", 0) > 0
        o = copy.deepcopy(resources[7])
        o.setdefault("metadata", {})["labels"] = {}
        cp.add_data(o)
        _sweep(jd_p, opts, pages=True)
        churn = dict(jd_p.last_sweep_phases.get("devpages") or {})
        assert churn.get("kinds_device", 0) > 0
        assert churn["h2d_bytes"] * 5 < cold["h2d_bytes"]


    def test_churn_h2d_is_a_tenth_of_the_restage_oracle(self,
                                                         monkeypatch):
        """One churned row on the device-resident store moves at least
        ten times fewer H2D bytes than the re-stage oracle (pages,
        footprints and the incremental binding build off), with equal
        verdicts.  A first churn round is paid before the measured one:
        it may rebuild a bucket sized in the cold sweep."""
        import gatekeeper_tpu.engine.jax_driver as jd_mod
        monkeypatch.setattr(jd_mod, "SMALL_WORKLOAD_EVALS", 0)
        monkeypatch.setenv("GATEKEEPER_PAGE_ROWS", "16")
        resources = make_mixed(random.Random(17), 240)
        pods = [o for o in resources
                if (o.get("spec") or {}).get("containers")]
        opts = QueryOpts(limit_per_constraint=20)

        def leg(flag):
            for var in ("GATEKEEPER_DEVPAGES", "GATEKEEPER_PAGES",
                        "GATEKEEPER_FOOTPRINT", "GATEKEEPER_BINDING_DELTA"):
                monkeypatch.setenv(var, flag)
            jd, c = _mk_client(jd_mod, self.KINDS)
            c.add_data_batch(copy.deepcopy(resources))
            jd.query_audit(TARGET_NAME, opts)
            ex = jd.executor
            for i, image in enumerate(("warm.io/devpages:steady",
                                       "evil.io/devpages:1")):
                o = copy.deepcopy(pods[i])
                for cont in o["spec"]["containers"]:
                    cont["image"] = image
                c.add_data(o)
                h2d0 = ex.h2d_bytes + ex.h2d_scatter_bytes
                results = jd.query_audit(TARGET_NAME, opts)[0]
            return (_verdicts(results),
                    ex.h2d_bytes + ex.h2d_scatter_bytes - h2d0)

        v_oracle, oracle_h2d = leg("off")
        v_dev, dev_h2d = leg("on")
        assert v_dev == v_oracle
        assert 0 < dev_h2d * 10 <= oracle_h2d


class TestCrossRowInventoryJoin:
    KINDS = ("K8sUniqueIngressHost",)

    def _drivers(self, monkeypatch):
        import gatekeeper_tpu.engine.jax_driver as jd_mod
        monkeypatch.setattr(jd_mod, "SMALL_WORKLOAD_EVALS", 0)
        monkeypatch.setenv("GATEKEEPER_PAGE_ROWS", "8")
        jd_p, cp = _mk_client(jd_mod, self.KINDS)
        jd_o, co = _mk_client(jd_mod, self.KINDS)
        objs = [_ingress("ing-a", "dup.example.com"),
                _ingress("ing-b", "dup.example.com"),
                _ingress("ing-c", "solo.example.com")]
        objs += make_mixed(random.Random(3), 20)
        for c in (cp, co):
            c.add_data_batch(copy.deepcopy(objs))
        return objs, jd_p, cp, jd_o, co

    def test_join_is_device_resident_and_clears_cross_row(
            self, monkeypatch):
        objs, jd_p, cp, jd_o, co = self._drivers(monkeypatch)
        opts = QueryOpts(limit_per_constraint=100)
        got = _verdicts(_sweep(jd_p, opts, pages=True))
        want = _verdicts(_sweep(jd_o, opts, pages=False,
                                devpages=False))
        assert got == want
        assert any("duplicate ingress host" in v[3] for v in got)
        st = jd_p._state(TARGET_NAME)
        # the cross-row kind took the DEVICE paged path, not fallback
        assert jd_p._pages_ineligible(
            st, "K8sUniqueIngressHost",
            st.templates["K8sUniqueIngressHost"]) is None
        dvp = dict(jd_p.last_sweep_phases.get("devpages") or {})
        assert dvp.get("kinds_device", 0) >= 1
        assert dvp.get("inv_joins_device", 0) >= 1
        # delete ing-b: ing-a's duplicate verdict must CLEAR even
        # though ing-a's own row never churned (cross-row '-' delta)
        for c in (cp, co):
            c.remove_data(_ingress("ing-b", "dup.example.com"))
        got = _verdicts(_sweep(jd_p, opts, pages=True))
        want = _verdicts(_sweep(jd_o, opts, pages=False,
                                devpages=False))
        assert got == want
        assert not any("duplicate ingress host" in v[3] for v in got)
        # re-insert under a DIFFERENT name into the freed slot: both
        # duplicates must re-appear through the device join
        for c in (cp, co):
            c.add_data(_ingress("ing-d", "dup.example.com"))
        got = _verdicts(_sweep(jd_p, opts, pages=True))
        want = _verdicts(_sweep(jd_o, opts, pages=False,
                                devpages=False))
        assert got == want
        dup = [v for v in got if "duplicate ingress host" in v[3]]
        assert {v[2] for v in dup} == {"ing-a", "ing-d"}


class TestGeometrySnapshot:
    KINDS = ("K8sRequiredLabels", "K8sAllowedRepos")

    def test_warm_restart_adopts_device_pagemap(self, monkeypatch,
                                                tmp_path):
        import gatekeeper_tpu.engine.jax_driver as jd_mod
        monkeypatch.setenv("GATEKEEPER_SNAPSHOT_DIR", str(tmp_path))
        monkeypatch.setattr(jd_mod, "SMALL_WORKLOAD_EVALS", 0)
        resources = make_mixed(random.Random(3), 50)
        opts = QueryOpts(limit_per_constraint=20)
        jd_cold, c_cold = _mk_client(jd_mod, self.KINDS)
        c_cold.add_data_batch(copy.deepcopy(resources))
        cold = _verdicts(_sweep(jd_cold, opts, pages=True))
        assert dict(jd_cold.last_sweep_phases["devpages"]).get(
            "kinds_device", 0) > 0
        os.environ["GATEKEEPER_PAGES"] = "on"
        try:
            assert jd_cold.save_store_snapshot(TARGET_NAME)
            jd_warm, _c_warm = _mk_client(jd_mod, self.KINDS)
            assert jd_warm.restore_store_snapshot(TARGET_NAME) is True
        finally:
            os.environ.pop("GATEKEEPER_PAGES", None)
        warm = _verdicts(_sweep(jd_warm, opts, pages=True))
        assert warm == cold
        pg = dict(jd_warm.last_sweep_phases.get("pages") or {})
        dvp = dict(jd_warm.last_sweep_phases.get("devpages") or {})
        # the ledger was adopted (0 rebuilds, 0 re-emitted events) AND
        # the device pagemap geometry came from the snapshot
        assert pg["ledger_full_builds"] == 0
        assert pg["events"] == 0
        assert dvp.get("geometry_adopted", 0) > 0
        st = jd_warm._state(TARGET_NAME)
        assert any(getattr(kp, "geometry_adopted", False)
                   for kp in st.devpages.values())


class TestWidenByKind:
    KINDS = ("K8sAllowedRepos", "K8sHttpsOnly")
    # the library constraints carry no spec.match (wildcard — every
    # kind observes everything); pin each to its natural kind so the
    # widen marker's kind-scoping has something to scope by
    MATCH = {"K8sAllowedRepos": ["Pod"], "K8sHttpsOnly": ["Ingress"]}

    def _mk_scoped_client(self, jd_mod):
        jd = jd_mod.JaxDriver()
        c = Backend(jd).new_client([K8sValidationTarget()])
        for tdoc, cdoc in all_docs():
            kind = tdoc["spec"]["crd"]["spec"]["names"]["kind"]
            if kind in self.KINDS:
                cdoc = copy.deepcopy(cdoc)
                cdoc.setdefault("spec", {})["match"] = {
                    "kinds": [{"apiGroups": ["*"],
                               "kinds": self.MATCH[kind]}]}
                c.add_template(tdoc)
                c.add_constraint(cdoc)
        return jd, c

    def test_widen_scoped_to_churned_kinds(self, monkeypatch):
        """Dirty-log overflow where only Pods churned: the
        Ingress-observing kind must skip the widen marker outright
        (host-paged path — the device path never consults the log)."""
        import gatekeeper_tpu.engine.jax_driver as jd_mod
        monkeypatch.setattr(jd_mod, "SMALL_WORKLOAD_EVALS", 0)
        monkeypatch.setattr(table_mod, "PATH_LOG_CAP", 8)
        monkeypatch.setenv("GATEKEEPER_PAGE_ROWS", "16")
        monkeypatch.setenv("GATEKEEPER_DEVPAGES", "off")
        resources = make_mixed(random.Random(5), 40)
        jd_p, cp = self._mk_scoped_client(jd_mod)
        jd_o, co = self._mk_scoped_client(jd_mod)
        for c in (cp, co):
            c.add_data_batch(copy.deepcopy(resources))
        opts = QueryOpts(limit_per_constraint=50)
        _sweep(jd_p, opts, pages=True, devpages=False)
        _sweep(jd_o, opts, pages=False, devpages=False)
        # clear the insert-era log so the overflowed window is
        # all-Pod, then churn only pods past the cap
        st = jd_p._state(TARGET_NAME)
        sto = jd_o._state(TARGET_NAME)
        st.table.compact()
        sto.table.compact()
        _sweep(jd_p, opts, pages=True, devpages=False)  # absorb remap
        _sweep(jd_o, opts, pages=False, devpages=False)
        pods = [o for o in resources
                if (o.get("spec") or {}).get("containers")]
        for i in range(20):
            o = copy.deepcopy(pods[i % len(pods)])
            o.setdefault("metadata", {}).setdefault(
                "annotations", {})["widen"] = str(i)
            for c in (cp, co):
                c.add_data(copy.deepcopy(o))
        assert st.table.dirtylog_overflows > 0
        got = _verdicts(_sweep(jd_p, opts, pages=True, devpages=False))
        want = _verdicts(_sweep(jd_o, opts, pages=False,
                                devpages=False))
        assert got == want
        pg = dict(jd_p.last_sweep_phases.get("pages") or {})
        # only the Pod-observing kind pays the widen; the
        # Ingress-only kind skips the marker (kind-disjoint)
        assert pg["widen_fallbacks"] == 1


class TestObservableKinds:
    def test_union_and_wildcards(self):
        from gatekeeper_tpu.engine.jax_driver import JaxDriver

        class _C:
            vectorized = None

        def con(kinds_field):
            return {"spec": {"match": {"kinds": kinds_field}}}

        f = JaxDriver._observable_kinds
        assert f(_C(), [con([{"kinds": ["Pod"]}]),
                        con([{"kinds": ["Ingress", "Service"]}])]) == \
            frozenset({"Pod", "Ingress", "Service"})
        # "*" in kinds, non-list kinds field, absent kinds: wildcard
        assert f(_C(), [con([{"kinds": ["*"]}])]) is None
        assert f(_C(), [con("Pod")]) is None
        assert f(_C(), [{"spec": {"match": {}}}]) is None
