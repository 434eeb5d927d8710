"""Continuous enforcement: row pages, the dirty-page log, and the
delta-maintained VerdictLedger (enforce/ledger.py).

Covers the store's per-page dirty bits (upsert/delete/aliased
re-upsert exactness, tail-page geometry), the paged sweep's
bit-identical parity with the GATEKEEPER_PAGES=off oracle under seeded
churn, the ledger event stream's exact equality with the diff of
consecutive full sweeps (ordered, no duplicates, no silent drops), the
dirty-log overflow widen marker (degrade to full-kind for exactly the
overflowed interval, counted), and the pagemap snapshot tier (a warm
restart adopts the ledger instead of paying a cold full build — the
PR 7-11 cold>0 / warm==0 convention).
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
from gatekeeper_tpu.store.table import ResourceMeta, ResourceTable
from gatekeeper_tpu.target.k8s import K8sValidationTarget, TARGET_NAME


@pytest.fixture(autouse=True)
def _reset_pages_state(monkeypatch):
    """Footprint analyzer state is process-global and feeds page
    eligibility — isolate every test, and keep the paged path opt-in
    per test via the env seam."""
    monkeypatch.setattr(footprint, "_memo", {})
    monkeypatch.setattr(footprint, "cross_row", {})
    monkeypatch.setattr(footprint, "violations", {})
    monkeypatch.setattr(footprint, "analyses_run", 0)
    monkeypatch.delenv("GATEKEEPER_PAGES", raising=False)
    monkeypatch.delenv("GATEKEEPER_PAGE_ROWS", raising=False)
    monkeypatch.delenv("GATEKEEPER_FOOTPRINT", raising=False)
    monkeypatch.delenv("GATEKEEPER_SNAPSHOT_DIR", raising=False)
    yield


def _meta(name: str, ns: str = "default") -> ResourceMeta:
    return ResourceMeta("v1", "Pod", name, ns)


def _verdicts(results):
    return sorted(
        ((r.constraint or {}).get("kind", ""),
         ((r.constraint or {}).get("metadata") or {}).get("name", ""),
         ((r.resource or {}).get("metadata") or {}).get("name", ""),
         r.msg)
        for r in results)


# ---------------------------------------------------------------------------
# the store's page dimension


class TestPageDirtyBits:
    def _table(self, monkeypatch, page_rows=4, n=10):
        monkeypatch.setenv("GATEKEEPER_PAGE_ROWS", str(page_rows))
        t = ResourceTable()
        for i in range(n):
            t.upsert(f"p{i}", {"kind": "Pod", "spec": {"a": i}},
                     _meta(f"p{i}"))
        return t

    def test_geometry(self, monkeypatch):
        t = self._table(monkeypatch, page_rows=4, n=10)
        assert t.page_rows == 4
        assert t.n_pages == 3                   # tail page half-empty
        assert t.page_of(0) == 0 and t.page_of(9) == 2

    def test_upsert_dirties_exactly_its_page(self, monkeypatch):
        t = self._table(monkeypatch)
        g = t.generation
        row = t.lookup("p5")
        t.upsert("p5", {"kind": "Pod", "spec": {"a": 99}}, _meta("p5"))
        assert t.dirty_pages_since(g) == frozenset({t.page_of(row)})
        # window starting at the new generation is empty
        assert t.dirty_pages_since(t.generation) == frozenset()

    def test_delete_dirties_its_page(self, monkeypatch):
        t = self._table(monkeypatch)
        g = t.generation
        row = t.lookup("p2")
        assert t.remove("p2")
        assert t.dirty_pages_since(g) == frozenset({t.page_of(row)})

    def test_insert_dirties_its_page(self, monkeypatch):
        t = self._table(monkeypatch)
        g = t.generation
        row = t.upsert("p-new", {"kind": "Pod", "spec": {}},
                       _meta("p-new"))
        assert t.dirty_pages_since(g) == frozenset({t.page_of(row)})

    def test_aliased_reupsert_page_exact(self, monkeypatch):
        # mutating the STORED reference and re-upserting it widens the
        # path set to the wildcard root (no pre-image to diff) — but
        # the page bit stays exact: only that row's page is dirty
        t = self._table(monkeypatch)
        g = t.generation
        row = t.lookup("p1")
        obj = t.object_at(row)
        obj["spec"]["a"] = 123
        t.upsert("p1", obj, _meta("p1"))
        assert ("*",) in t.dirty_paths_since(g)
        assert t.dirty_pages_since(g) == frozenset({t.page_of(row)})

    def test_entries_in_write_order_with_pages(self, monkeypatch):
        t = self._table(monkeypatch)
        g = t.generation
        t.upsert("p0", {"kind": "Pod", "spec": {"a": -1}}, _meta("p0"))
        t.upsert("p9", {"kind": "Pod", "spec": {"a": -2}}, _meta("p9"))
        entries = t.dirty_page_entries_since(g)
        assert [pages for _g, _p, pages, _k in entries] == \
            [frozenset({0}), frozenset({2})]
        assert all(p == frozenset({("spec", "a")})
                   for _g, p, _pg, _k in entries)
        assert all(k == frozenset({"Pod"})
                   for _g, _p, _pg, k in entries)

    def test_compact_floors_the_page_log(self, monkeypatch):
        t = self._table(monkeypatch)
        g = t.generation
        t.remove("p3")
        t.compact()
        assert t.dirty_pages_since(g) is None   # row ids reassigned

    def test_overflow_leaves_widen_marker(self, monkeypatch):
        monkeypatch.setattr(table_mod, "PATH_LOG_CAP", 8)
        t = self._table(monkeypatch)
        g = t.generation
        for i in range(20):                     # spill the log
            t.upsert("p1", {"kind": "Pod", "spec": {"a": i}}, _meta("p1"))
        assert t.dirtylog_overflows > 0
        # a window spanning the marker loses PATH attribution but
        # keeps the dropped half's page/kind unions exact — consumers
        # re-eval those pages (for matching kinds) instead of the world
        assert t.dirty_paths_since(g) is None
        spanning = t.dirty_pages_since(g)
        assert spanning is not None
        assert t.page_of(t.lookup("p1")) in spanning
        entries = t.dirty_page_entries_since(g)
        widens = [e for e in entries if e[1] is None]
        assert widens and all(k == frozenset({"Pod"})
                              for _g, _p, _pg, k in widens)
        # ...but a window after it is exact again
        g2 = t.generation
        t.upsert("p6", {"kind": "Pod", "spec": {"a": 0}}, _meta("p6"))
        assert t.dirty_pages_since(g2) == frozenset({t.page_of(
            t.lookup("p6"))})


# ---------------------------------------------------------------------------
# paged sweep: oracle parity + the ledger event stream


def _mk_client(jd_mod, kinds):
    jd = jd_mod.JaxDriver()
    c = Backend(jd).new_client([K8sValidationTarget()])
    for tdoc, cdoc in all_docs():
        if tdoc["spec"]["crd"]["spec"]["names"]["kind"] in kinds:
            c.add_template(tdoc)
            c.add_constraint(cdoc)
    return jd, c


def _sweep(jd, opts, pages: bool):
    os.environ["GATEKEEPER_PAGES"] = "on" if pages else "off"
    try:
        return jd.query_audit(TARGET_NAME, opts)[0]
    finally:
        os.environ.pop("GATEKEEPER_PAGES", None)


def _vcounter(results):
    """Full-sweep violations as the (kind, constraint, resource, msg)
    multiset the ledger's event stream is diffed against."""
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


class TestPagedSweep:
    # all three are row-local with empty provider sets — every kind is
    # page-eligible, so the event stream must account for EVERY change
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

    def _churn_rounds(self, resources, rng):
        """Seeded churn batches: (op, obj) lists applied identically to
        the paged and oracle clients — metadata noise, verdict-flipping
        edits, deletes, restores, fresh inserts."""
        pods = [o for o in resources
                if (o.get("spec") or {}).get("containers")]
        rounds = []
        # 1: annotation noise (outside every installed read-set)
        batch = []
        for o in rng.sample(resources, 4):
            o = copy.deepcopy(o)
            o.setdefault("metadata", {}).setdefault(
                "annotations", {})["pages-test"] = "noise"
            batch.append(("upsert", o))
        rounds.append(batch)
        # 2: verdict-flipping edits — bad image, stripped labels
        flipped = rng.sample(pods, 2)
        batch = []
        for o in flipped:
            o = copy.deepcopy(o)
            o["spec"]["containers"][0]["image"] = "evil.io/pages:1"
            batch.append(("upsert", o))
        for o in rng.sample(resources, 2):
            o = copy.deepcopy(o)
            o.setdefault("metadata", {})["labels"] = {}
            batch.append(("upsert", o))
        rounds.append(batch)
        # 3: deletes (violating rows must emit clears) + fresh inserts
        batch = [("remove", copy.deepcopy(o))
                 for o in rng.sample(resources, 3)]
        batch += [("upsert", o) for o in make_mixed(random.Random(77), 5)]
        rounds.append(batch)
        # 4: restore the flipped pods to their original verdicts
        rounds.append([("upsert", copy.deepcopy(o)) for o in flipped])
        return rounds

    def test_oracle_parity_under_churn(self, monkeypatch):
        resources, jd_p, cp, jd_o, co = self._drivers(monkeypatch)
        opts = QueryOpts(limit_per_constraint=20)
        rng = random.Random(9)
        for rnd in [[]] + self._churn_rounds(resources, rng):
            for op, obj in rnd:
                for c in (cp, co):
                    o = copy.deepcopy(obj)
                    (c.add_data if op == "upsert" else c.remove_data)(o)
            got = _verdicts(_sweep(jd_p, opts, pages=True))
            want = _verdicts(_sweep(jd_o, opts, pages=False))
            assert got == want
        pg = dict(jd_p.last_sweep_phases.get("pages") or {})
        assert pg.get("enabled") is True
        assert pg.get("kinds_paged") == len(self.KINDS)
        assert pg.get("kinds_fallback") == 0

    def test_one_row_churn_reevaluates_under_five_percent(self,
                                                           monkeypatch):
        """One verdict-flipping image edit: the paged sweep equals the
        kind-granular one (pages off) and the full oracle (pages and
        footprints off), and re-evaluates under 5 % of the
        row-evaluation space."""
        import gatekeeper_tpu.engine.jax_driver as jd_mod
        monkeypatch.setattr(jd_mod, "SMALL_WORKLOAD_EVALS", 0)
        monkeypatch.setenv("GATEKEEPER_PAGE_ROWS", "16")
        n = 400
        resources = make_mixed(random.Random(13), n)
        edit = copy.deepcopy(next(
            o for o in resources[n // 2:]
            if (o.get("spec") or {}).get("containers")))
        for cont in edit["spec"]["containers"]:
            cont["image"] = "evil.io/pages:1"
        opts = QueryOpts(limit_per_constraint=20)
        got = {}
        for leg, pages, fp in (("oracle", "off", "off"),
                               ("kind", "off", "on"),
                               ("paged", "on", "on")):
            monkeypatch.setenv("GATEKEEPER_PAGES", pages)
            monkeypatch.setenv("GATEKEEPER_FOOTPRINT", fp)
            jd, c = _mk_client(jd_mod, self.KINDS)
            c.add_data_batch(copy.deepcopy(resources))
            jd.query_audit(TARGET_NAME, opts)
            c.add_data(copy.deepcopy(edit))
            got[leg] = _verdicts(jd.query_audit(TARGET_NAME, opts)[0])
        assert got["paged"] == got["kind"] == got["oracle"]
        pg = dict(jd.last_sweep_phases.get("pages") or {})
        assert pg["kinds_paged"] == len(self.KINDS)
        rows_frac = pg["rows_reevaluated"] / (n * pg["kinds_paged"])
        assert 0 < rows_frac < 0.05
        assert pg["evaluations_saved"] > 0

    def test_metadata_noise_skips_pages(self, monkeypatch):
        resources, jd_p, cp, _jd_o, _co = self._drivers(monkeypatch)
        opts = QueryOpts(limit_per_constraint=20)
        _sweep(jd_p, opts, pages=True)          # cold build
        o = copy.deepcopy(resources[3])
        o.setdefault("metadata", {}).setdefault(
            "annotations", {})["pages-test"] = "noise"
        cp.add_data(o)
        _sweep(jd_p, opts, pages=True)
        pg = dict(jd_p.last_sweep_phases.get("pages") or {})
        # an annotation edit intersects no installed read-set: zero
        # pages re-evaluated, everything skipped, the saving counted
        assert pg["pages_evaluated"] == 0
        assert pg["pages_skipped"] > 0
        assert pg["evaluations_saved"] > 0
        assert pg["events"] == 0

    def test_event_stream_equals_full_sweep_diff(self, monkeypatch):
        resources, jd_p, cp, jd_o, co = self._drivers(monkeypatch)
        # cap high enough that no per-constraint limit binds: the diff
        # of consecutive FULL result sets is then the exact oracle for
        # the ledger's delta stream
        opts = QueryOpts(limit_per_constraint=10_000)
        rng = random.Random(9)
        prev = collections.Counter()
        last_seq = 0
        for rnd in [[]] + self._churn_rounds(resources, rng):
            for op, obj in rnd:
                for c in (cp, co):
                    o = copy.deepcopy(obj)
                    (c.add_data if op == "upsert" else c.remove_data)(o)
            _sweep(jd_p, opts, pages=True)
            cur = _vcounter(_sweep(jd_o, opts, pages=False))
            led = jd_p._state(TARGET_NAME).ledger
            assert led is not None
            evs = [e for e in led.events if e["seq"] > last_seq]
            # ordered: strictly increasing seq, no duplicates
            assert [e["seq"] for e in evs] == sorted(
                {e["seq"] for e in evs})
            last_seq = led.seq
            appears = collections.Counter(
                (e["kind"], e["constraint"], e["resource"], e["msg"])
                for e in evs if e["op"] == "appear")
            clears = collections.Counter(
                (e["kind"], e["constraint"], e["resource"], e["msg"])
                for e in evs if e["op"] == "clear")
            # exactly the diff of consecutive full sweeps — nothing
            # extra, nothing silently dropped
            assert appears == cur - prev
            assert clears == prev - cur
            prev = cur
        # the ledger's resident set equals the final full sweep
        assert led.total_violations() == sum(prev.values())

    def test_tail_page_padding_parity(self, monkeypatch):
        # 60 rows at 16 rows/page: the tail page maps 4 slots past
        # n_rows; churn a row inside it and verify the padded page
        # evaluates without phantom verdicts
        resources, jd_p, cp, jd_o, co = self._drivers(monkeypatch)
        opts = QueryOpts(limit_per_constraint=20)
        _sweep(jd_p, opts, pages=True)
        _sweep(jd_o, opts, pages=False)
        st = jd_p._state(TARGET_NAME)
        assert st.table.n_rows % st.table.page_rows != 0
        tail_row = st.table.n_rows - 1
        key = None
        for k, row in st.table._rows.items():
            if row == tail_row:
                key = k
                break
        assert key is not None
        obj = copy.deepcopy(st.table.object_at(tail_row))
        obj.setdefault("metadata", {})["labels"] = {}
        for c in (cp, co):
            c.add_data(copy.deepcopy(obj))
        got = _verdicts(_sweep(jd_p, opts, pages=True))
        want = _verdicts(_sweep(jd_o, opts, pages=False))
        assert got == want
        pg = dict(jd_p.last_sweep_phases.get("pages") or {})
        dvp = dict(jd_p.last_sweep_phases.get("devpages") or {})
        if dvp.get("kinds_device"):
            # device-resident path: rows ride fixed slot arrays — no
            # host page padding happens, the delta kernel covers it
            assert pg["rows_reevaluated"] >= 0
        else:
            assert pg["rows_padded"] > 0


# ---------------------------------------------------------------------------
# overflow widen: degrade to full-kind for exactly the overflowed window


class TestOverflowWiden:
    KINDS = ("K8sRequiredLabels", "K8sAllowedRepos")

    def test_widen_falls_back_and_recovers(self, monkeypatch):
        import gatekeeper_tpu.engine.jax_driver as jd_mod
        monkeypatch.setattr(jd_mod, "SMALL_WORKLOAD_EVALS", 0)
        monkeypatch.setattr(table_mod, "PATH_LOG_CAP", 8)
        monkeypatch.setenv("GATEKEEPER_PAGE_ROWS", "16")
        resources = make_mixed(random.Random(5), 40)
        jd_p, cp = _mk_client(jd_mod, self.KINDS)
        jd_o, co = _mk_client(jd_mod, self.KINDS)
        for c in (cp, co):
            c.add_data_batch(copy.deepcopy(resources))
        opts = QueryOpts(limit_per_constraint=20)
        _sweep(jd_p, opts, pages=True)
        _sweep(jd_o, opts, pages=False)
        # 20 single-object churn events overflow the capped log: the
        # sweep window spans the widen marker
        for i in range(20):
            o = copy.deepcopy(resources[i % len(resources)])
            o.setdefault("metadata", {}).setdefault(
                "annotations", {})["widen"] = str(i)
            for c in (cp, co):
                c.add_data(copy.deepcopy(o))
        st = jd_p._state(TARGET_NAME)
        assert st.table.dirtylog_overflows > 0
        got = _verdicts(_sweep(jd_p, opts, pages=True))
        want = _verdicts(_sweep(jd_o, opts, pages=False))
        assert got == want                      # parity through the widen
        pg = dict(jd_p.last_sweep_phases.get("pages") or {})
        dvp = dict(jd_p.last_sweep_phases.get("devpages") or {})
        if dvp.get("kinds_device"):
            # the device path's dirty set is _ver-exact (log-free):
            # a path-log widen never forces it anywhere
            assert pg["widen_fallbacks"] == 0
        else:
            assert pg["widen_fallbacks"] == len(self.KINDS)
        # the next (small) churn is back on the exact paged path
        o = copy.deepcopy(resources[0])
        o.setdefault("metadata", {}).setdefault(
            "annotations", {})["widen"] = "post"
        for c in (cp, co):
            c.add_data(copy.deepcopy(o))
        got = _verdicts(_sweep(jd_p, opts, pages=True))
        want = _verdicts(_sweep(jd_o, opts, pages=False))
        assert got == want
        pg = dict(jd_p.last_sweep_phases.get("pages") or {})
        assert pg["widen_fallbacks"] == 0
        assert pg["pages_skipped"] > 0


# ---------------------------------------------------------------------------
# warm restart: the pagemap snapshot tier


class TestPagemapSnapshot:
    KINDS = ("K8sRequiredLabels", "K8sAllowedRepos")

    def test_warm_restart_adopts_ledger(self, monkeypatch, tmp_path):
        import gatekeeper_tpu.engine.jax_driver as jd_mod
        monkeypatch.setenv("GATEKEEPER_SNAPSHOT_DIR", str(tmp_path))
        monkeypatch.setattr(jd_mod, "SMALL_WORKLOAD_EVALS", 0)
        resources = make_mixed(random.Random(3), 50)
        opts = QueryOpts(limit_per_constraint=20)

        jd_cold, c_cold = _mk_client(jd_mod, self.KINDS)
        c_cold.add_data_batch(copy.deepcopy(resources))
        cold = _verdicts(_sweep(jd_cold, opts, pages=True))
        pg = dict(jd_cold.last_sweep_phases.get("pages") or {})
        assert pg["ledger_full_builds"] > 0     # cold: every kind built
        os.environ["GATEKEEPER_PAGES"] = "on"
        try:
            assert jd_cold.save_store_snapshot(TARGET_NAME)

            # "restarted process": fresh driver, same snapshot dir —
            # restore the store, adopt the pagemap, zero full builds
            jd_warm, _c_warm = _mk_client(jd_mod, self.KINDS)
            assert jd_warm.restore_store_snapshot(TARGET_NAME) is True
            assert jd_warm._state(TARGET_NAME).ledger_restored
        finally:
            os.environ.pop("GATEKEEPER_PAGES", None)
        warm = _verdicts(_sweep(jd_warm, opts, pages=True))
        assert warm == cold                     # bit-identical verdicts
        pg = dict(jd_warm.last_sweep_phases.get("pages") or {})
        assert pg["ledger_full_builds"] == 0    # adopted, not rebuilt
        assert pg["events"] == 0                # and nothing re-emitted

    def test_constraint_drift_rejects_adoption(self, monkeypatch,
                                               tmp_path):
        import gatekeeper_tpu.engine.jax_driver as jd_mod
        monkeypatch.setenv("GATEKEEPER_SNAPSHOT_DIR", str(tmp_path))
        monkeypatch.setattr(jd_mod, "SMALL_WORKLOAD_EVALS", 0)
        resources = make_mixed(random.Random(3), 50)
        opts = QueryOpts(limit_per_constraint=20)
        jd_cold, c_cold = _mk_client(jd_mod, self.KINDS)
        c_cold.add_data_batch(copy.deepcopy(resources))
        _sweep(jd_cold, opts, pages=True)
        os.environ["GATEKEEPER_PAGES"] = "on"
        try:
            assert jd_cold.save_store_snapshot(TARGET_NAME)
            jd_warm, c_warm = _mk_client(jd_mod, self.KINDS)
            assert jd_warm.restore_store_snapshot(TARGET_NAME) is True
        finally:
            os.environ.pop("GATEKEEPER_PAGES", None)
        # drift the constraint set in the restarted process: adoption
        # must be refused (content digest mismatch) and the ledger
        # rebuilt cold — never served from stale verdicts
        for tdoc, cdoc in all_docs():
            kind = tdoc["spec"]["crd"]["spec"]["names"]["kind"]
            if kind == "K8sRequiredLabels":
                drifted = copy.deepcopy(cdoc)
                drifted["spec"]["parameters"]["labels"] = ["pages-drift"]
                c_warm.add_constraint(drifted)
        jd_oracle, c_oracle = _mk_client(jd_mod, self.KINDS)
        c_oracle.add_data_batch(copy.deepcopy(resources))
        for tdoc, cdoc in all_docs():
            kind = tdoc["spec"]["crd"]["spec"]["names"]["kind"]
            if kind == "K8sRequiredLabels":
                drifted = copy.deepcopy(cdoc)
                drifted["spec"]["parameters"]["labels"] = ["pages-drift"]
                c_oracle.add_constraint(drifted)
        warm = _verdicts(_sweep(jd_warm, opts, pages=True))
        want = _verdicts(_sweep(jd_oracle, opts, pages=False))
        assert warm == want
        pg = dict(jd_warm.last_sweep_phases.get("pages") or {})
        assert pg["ledger_full_builds"] > 0     # drift forced a rebuild


class TestWatchWatermark:
    """Satellite: the pg snapshot tier records the watch
    resourceVersion watermark it was built at; on restart the reactor
    seeds each kind's RV floor from it.  A clean restart replays
    nothing; a watermark the live stream cannot extend means the
    adopted state is from another watch epoch, and the kind gets one
    forced resync."""

    KINDS = ("K8sRequiredLabels", "K8sAllowedRepos")

    def _cluster_fixture(self, jd_mod, n=24, seed=11):
        from gatekeeper_tpu.cluster.fake import FakeCluster, gvk_of
        resources = make_mixed(random.Random(seed), n)
        cluster = FakeCluster()
        for o in resources:
            cluster.create(copy.deepcopy(o))
        gvks = sorted({gvk_of(o) for o in resources}, key=lambda g: g.kind)
        jd, c = _mk_client(jd_mod, self.KINDS)
        objs = [o for g in gvks for o in cluster.list(g)]
        c.add_data_batch(copy.deepcopy(objs))
        return cluster, gvks, resources, jd, c

    def test_clean_warm_restart_replays_nothing(self, monkeypatch,
                                                tmp_path):
        import gatekeeper_tpu.engine.jax_driver as jd_mod
        from gatekeeper_tpu.cluster.fake import gvk_of
        from gatekeeper_tpu.enforce.reactor import LIVE, Reactor
        monkeypatch.setenv("GATEKEEPER_SNAPSHOT_DIR", str(tmp_path))
        monkeypatch.setattr(jd_mod, "SMALL_WORKLOAD_EVALS", 0)
        opts = QueryOpts(limit_per_constraint=20)
        cluster, gvks, resources, jd, c = self._cluster_fixture(jd_mod)
        _sweep(jd, opts, pages=True)
        os.environ["GATEKEEPER_PAGES"] = "on"
        try:
            assert jd.save_store_snapshot(TARGET_NAME)

            jd2, c2 = _mk_client(jd_mod, self.KINDS)
            assert jd2.restore_store_snapshot(TARGET_NAME) is True
            # the watermark survived the round-trip: floors seed > 0
            assert any(jd2.ledger_rv(TARGET_NAME, g.kind) > 0
                       for g in gvks)
            rx = Reactor(c2, cluster=cluster, apply_objects=True)
            for g in gvks:
                rx.attach(g)
            # adoption sweep: nothing changed, nothing replayed
            warm = _verdicts(_sweep(jd2, opts, pages=True))
            led = jd2._state(TARGET_NAME).ledger
            assert led.seq == 0              # zero spurious events
            # live stream extends the watermark: no stale-RV resync
            src = resources[0]
            cur = cluster.get(gvk_of(src), src["metadata"]["name"],
                              src["metadata"].get("namespace"))
            o = copy.deepcopy(cur)
            o.setdefault("metadata", {}).setdefault(
                "labels", {})["wm"] = "x"
            cluster.update(o)
            rx.pump()
            assert rx.counters["pathology_stale_rv"] == 0
            assert rx.counters["rung2"] == 0
            assert rx.state == LIVE
        finally:
            os.environ.pop("GATEKEEPER_PAGES", None)
        assert warm == _verdicts(_sweep(jd, opts, pages=True))

    def test_stale_watermark_forces_kind_resync(self, monkeypatch,
                                                tmp_path):
        import gatekeeper_tpu.engine.jax_driver as jd_mod
        from gatekeeper_tpu.cluster.fake import FakeCluster, gvk_of
        from gatekeeper_tpu.enforce.reactor import LIVE, Reactor
        monkeypatch.setenv("GATEKEEPER_SNAPSHOT_DIR", str(tmp_path))
        monkeypatch.setattr(jd_mod, "SMALL_WORKLOAD_EVALS", 0)
        opts = QueryOpts(limit_per_constraint=20)
        cluster_a, gvks, resources, jd, c = self._cluster_fixture(jd_mod)
        # pump cluster A's RVs well past what a fresh cluster restarts
        # at, so the snapshot watermark is unambiguously ahead
        for _round in range(3):
            for src in resources:
                cur = cluster_a.get(gvk_of(src), src["metadata"]["name"],
                                    src["metadata"].get("namespace"))
                o = copy.deepcopy(cur)
                o.setdefault("metadata", {}).setdefault(
                    "labels", {})["bump"] = str(_round)
                cluster_a.update(o)
        objs = [o for g in gvks for o in cluster_a.list(g)]
        c.add_data_batch(copy.deepcopy(objs))
        _sweep(jd, opts, pages=True)
        os.environ["GATEKEEPER_PAGES"] = "on"
        try:
            assert jd.save_store_snapshot(TARGET_NAME)

            # "restart" against a DIFFERENT watch epoch: a fresh
            # cluster whose RVs restart from 1, with diverged objects
            cluster_b = FakeCluster()
            for o in resources:
                o2 = copy.deepcopy(o)
                o2.setdefault("metadata", {}).setdefault(
                    "labels", {})["epoch"] = "b"
                cluster_b.create(o2)
            jd2, c2 = _mk_client(jd_mod, self.KINDS)
            assert jd2.restore_store_snapshot(TARGET_NAME) is True
            rx = Reactor(c2, cluster=cluster_b, apply_objects=True)
            for g in gvks:
                rx.attach(g)
            # first observed event per kind fails to extend the
            # watermark -> one forced resync each, relisted from B
            for g in gvks:
                src = next(o for o in resources if o["kind"] == g.kind)
                cur = cluster_b.get(g, src["metadata"]["name"],
                                    src["metadata"].get("namespace"))
                o = copy.deepcopy(cur)
                o.setdefault("metadata", {}).setdefault(
                    "labels", {})["poke"] = "1"
                cluster_b.update(o)
            rx.pump()
            assert rx.counters["pathology_stale_rv"] >= len(gvks)
            assert (rx.counters["rung2"] + rx.counters["rung3"]) >= 1
            assert rx.state == LIVE
            got = _verdicts(_sweep(jd2, opts, pages=True))
        finally:
            os.environ.pop("GATEKEEPER_PAGES", None)
        # the store converged to cluster B, not the adopted snapshot
        jd_o, c_o = _mk_client(jd_mod, self.KINDS)
        c_o.add_data_batch(
            copy.deepcopy([o for g in gvks for o in cluster_b.list(g)]))
        assert got == _verdicts(_sweep(jd_o, opts, pages=False))
