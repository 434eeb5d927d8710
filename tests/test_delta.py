"""Delta-maintained bindings: ``ir/prep.update_bindings`` must be
indistinguishable from a full ``build_bindings`` rebuild under churn
(the oracle-twin rule) — exercised across every lowerable library
template so all binding kinds (r/e cols, tables, ptables, csets, memb,
ekm, keyed_vals) take the incremental path.
"""

import random

import numpy as np
import pytest

from gatekeeper_tpu.api.templates import compile_target_rego
from gatekeeper_tpu.engine.veval import ProgramExecutor
from gatekeeper_tpu.ir.lower import CannotLower, lower_template
from gatekeeper_tpu.ir.prep import (TABLE_VALUES, build_bindings,
                                    update_bindings)
from gatekeeper_tpu.library.templates import LIBRARY, all_docs
from gatekeeper_tpu.library.workload import make_mixed
from gatekeeper_tpu.store.table import ResourceMeta, ResourceTable


def _fill(table, objs, start=0):
    for i, o in enumerate(objs, start=start):
        meta = ResourceMeta(
            api_version=o.get("apiVersion", "v1"),
            kind=o.get("kind", "Pod"),
            name=o.get("metadata", {}).get("name", f"r{i}"),
            namespace=o.get("metadata", {}).get("namespace"))
        table.upsert(f"k{i:05d}", o, meta)


def _lowered_library():
    out = []
    for kind, (rego, _params) in sorted(LIBRARY.items()):
        ct = compile_target_rego(kind, "admission.k8s.gatekeeper.sh", rego)
        try:
            out.append((kind, lower_template(ct.module, ct.interp)))
        except CannotLower:
            pass
    return out


def _constraints_for(kind):
    return [c for t, c in all_docs() if t["spec"]["crd"]["spec"]["names"]["kind"] == kind]


class TestUpdateBindingsParity:

    def _compare(self, prog, spec, b_delta, b_fresh, kind):
        # exact equality for everything whose layout is deterministic;
        # ptables may permute dense value slots between delta and fresh
        pt_names = {p.name for p in spec.ptables}
        for nm, fresh in b_fresh.arrays.items():
            base = nm.split(".")[0]
            if base in pt_names:
                continue
            got = b_delta.arrays[nm]
            assert got.shape == fresh.shape, (kind, nm)
            np.testing.assert_array_equal(got, fresh, err_msg=f"{kind} {nm}")
        # ptables (and everything else) compared via the real contract:
        # identical violation masks
        ex = ProgramExecutor()
        m1 = ex.run(prog, b_delta)
        m2 = ex.run(prog, b_fresh)
        np.testing.assert_array_equal(m1, m2, err_msg=f"{kind} mask")

    def test_library_churn_parity(self):
        rng = random.Random(11)
        table = ResourceTable()
        objs = make_mixed(rng, 150)
        _fill(table, objs)
        lowered = _lowered_library()
        assert len(lowered) >= 25
        cases = []
        for kind, lp in lowered:
            cons = _constraints_for(kind)
            assert cons, kind
            cases.append((kind, lp, cons,
                          build_bindings(lp.spec, table, cons)))
        for round_ in range(3):
            # churn: updates (some new strings/images), adds, deletes
            upd = make_mixed(rng, 10)
            for i, o in zip(rng.sample(range(150), 10), upd):
                if round_ == 2:
                    # inject brand-new strings to force table/ptable
                    # delta slots
                    o.setdefault("metadata", {}).setdefault(
                        "labels", {})[f"fresh{round_}{i}"] = f"val{i}"
                _fill(table, [o], start=i)
            _fill(table, make_mixed(rng, 2), start=1000 + round_ * 2)
            table.remove(f"k{rng.randrange(150):05d}")
            nxt = []
            for kind, lp, cons, prev in cases:
                b = update_bindings(lp.spec, table, cons, prev)
                if b is None:
                    # legal (bucket outgrown); rebuild and continue
                    b = build_bindings(lp.spec, table, cons)
                else:
                    fresh = build_bindings(lp.spec, table, cons)
                    self._compare(lp.program, lp.spec, b, fresh, kind)
                nxt.append((kind, lp, cons, b))
            cases = nxt

    @pytest.mark.parametrize("kind", [
        "K8sAllowedRepos", "K8sDisallowLatestTag", "K8sDisallowedRepos",
        "K8sDisallowedTags"])
    def test_new_image_churn_tables_equal_a_fresh_build(self, kind):
        """A churn batch of images never seen before goes through
        update_bindings' table and ptable sections (the batched builtin
        path); every table then reads as a fresh build's does."""
        rng = random.Random(23)
        table = ResourceTable()

        def pod(i, image):
            return {"kind": "Pod", "apiVersion": "v1",
                    "metadata": {"name": f"p{i}", "namespace": "ns"},
                    "spec": {"containers": [{"name": "c", "image": image}]}}

        # 100 distinct images: table buckets with room for the new ones
        _fill(table, [pod(i, f"{('gcr.io', 'docker.io', 'quay.io')[i % 3]}"
                             f"/app{i % 100}"
                             f"{('', ':latest', ':v1', ':v2')[i % 4]}")
                      for i in range(100)])
        lp = dict(_lowered_library())[kind]
        cons = _constraints_for(kind) + [
            {"kind": kind, "metadata": {"name": "wide"},
             "spec": {"parameters": {"repos": ["gcr.io/", "", "quay.io/"],
                                     "tags": ["latest", "", "v2"]}}}]
        prev = build_bindings(lp.spec, table, cons)
        images = ["quay.io/new:latest", "quay.io/new", "gcr.io/x:v2",
                  "docker.io/y:", "ünï:v2", "", "gcr.io/", 5, None]
        for i, img in zip(rng.sample(range(100), len(images)), images):
            _fill(table, [pod(i, img)], start=i)
        before = TABLE_VALUES.batched
        b = update_bindings(lp.spec, table, cons, prev)
        assert b is not None
        # the new images alone, on the batched path
        assert 0 < TABLE_VALUES.batched - before < len(images) * 8
        fresh = build_bindings(lp.spec, table, cons)

        def live(src):
            # ids the rows hold now: the delta keeps the evaluations of
            # replaced values too, which no row reads
            ids = np.unique(fresh.arrays[src])
            return ids[ids >= 0]

        for tr in lp.spec.tables:
            ids = live(tr.src)
            for suffix in (".ok", ".v"):
                np.testing.assert_array_equal(
                    b.arrays[tr.name + suffix][ids],
                    fresh.arrays[tr.name + suffix][ids],
                    err_msg=f"{kind} {tr.name}{suffix}")
        # ptable value slots may be permuted: compare each value's column
        for pt in lp.spec.ptables:
            ids = live(pt.src)
            for suffix in (".any", ".all"):
                got = b.arrays[pt.name + suffix][
                    :, b.arrays[pt.name + ".vmap"][ids]]
                want = fresh.arrays[pt.name + suffix][
                    :, fresh.arrays[pt.name + ".vmap"][ids]]
                np.testing.assert_array_equal(
                    got, want, err_msg=f"{kind} {pt.name}{suffix}")
        ex = ProgramExecutor()
        np.testing.assert_array_equal(ex.run(lp.program, b),
                                      ex.run(lp.program, fresh))

    def test_recycle_ping_pong_parity(self):
        """update_bindings with `recycle` (the ping-pong write-buffer
        path) must produce the same arrays as a fresh full rebuild, and
        alternate buffer identities across updates."""
        rng = random.Random(17)
        table = ResourceTable()
        _fill(table, make_mixed(rng, 120))
        for kind, lp in _lowered_library()[:10]:
            cons = _constraints_for(kind)
            b0 = build_bindings(lp.spec, table, cons)
            chain = [b0]
            for round_ in range(4):
                upd = make_mixed(rng, 6)
                for i, o in zip(rng.sample(range(120), 6), upd):
                    _fill(table, [o], start=i)
                recycle = chain[-2] if len(chain) >= 2 else None
                b = update_bindings(lp.spec, table, cons, chain[-1],
                                    recycle=recycle)
                if b is None:
                    b = build_bindings(lp.spec, table, cons)
                else:
                    fresh = build_bindings(lp.spec, table, cons)
                    self._compare(lp.program, lp.spec, b, fresh, kind)
                    if recycle is not None:
                        # recycled buffers must never alias prev's
                        for nm, arr in b.arrays.items():
                            assert arr is not chain[-1].arrays.get(nm) \
                                or nm not in b.base_dirty, (kind, nm)
                chain.append(b)

    def test_update_declines_on_remap(self):
        table = ResourceTable()
        _fill(table, make_mixed(random.Random(1), 80))
        kind, lp = _lowered_library()[0]
        cons = _constraints_for(kind)
        prev = build_bindings(lp.spec, table, cons)
        table.wipe()
        _fill(table, make_mixed(random.Random(2), 10))
        assert update_bindings(lp.spec, table, cons, prev) is None

    def test_update_declines_on_growth_past_bucket(self):
        table = ResourceTable()
        _fill(table, make_mixed(random.Random(3), 60))
        kind, lp = _lowered_library()[0]
        cons = _constraints_for(kind)
        prev = build_bindings(lp.spec, table, cons)
        # grow past the r_pad bucket
        _fill(table, make_mixed(random.Random(4), prev.r_pad), start=500)
        assert update_bindings(lp.spec, table, cons, prev) is None

    def test_base_dirty_covers_changes(self):
        """Every array that differs from the base must either carry a
        base_dirty entry covering the differing rows (r-axis delta) or
        be a fresh full array — the device-cache contract."""
        from gatekeeper_tpu.ir.prep import binding_axes
        rng = random.Random(5)
        table = ResourceTable()
        _fill(table, make_mixed(rng, 100))
        for kind, lp in _lowered_library()[:8]:
            cons = _constraints_for(kind)
            prev = build_bindings(lp.spec, table, cons)
            upd = make_mixed(rng, 5)
            rows = rng.sample(range(100), 5)
            for i, o in zip(rows, upd):
                _fill(table, [o], start=i)
            b = update_bindings(lp.spec, table, cons, prev)
            assert b is not None
            assert b.base is prev
            for nm, arr in b.arrays.items():
                old = prev.arrays[nm]
                if arr is old:
                    np.testing.assert_array_equal(arr, old)
                    continue
                if nm in b.base_dirty:
                    axes = binding_axes(nm)
                    ax = axes.index("r")
                    mask = np.ones(arr.shape[ax], dtype=bool)
                    mask[b.base_dirty[nm]] = False
                    sl = [slice(None)] * arr.ndim
                    sl[ax] = mask
                    np.testing.assert_array_equal(
                        arr[tuple(sl)], old[tuple(sl)],
                        err_msg=f"{kind} {nm}: differs outside base_dirty")


class TestDriverChurnParity:
    """End-to-end: the JaxDriver's delta-maintained audit (bindings +
    match mask + device scatter) must match the scalar LocalDriver
    exactly across churn rounds — including mixed update/add/delete
    churn and Namespace-object churn (which forces full mask rebuilds)."""

    def _clients(self):
        from gatekeeper_tpu.client.client import Backend
        from gatekeeper_tpu.client.local_driver import LocalDriver
        from gatekeeper_tpu.engine.jax_driver import JaxDriver
        from gatekeeper_tpu.target.k8s import K8sValidationTarget
        return (Backend(LocalDriver()).new_client([K8sValidationTarget()]),
                Backend(JaxDriver()).new_client([K8sValidationTarget()]))

    @staticmethod
    def _results(client, limit=None):
        resp = client.audit(limit_per_constraint=limit)
        return sorted(
            (r.msg, r.constraint["metadata"]["name"],
             (r.review or {}).get("name") if isinstance(r.review, dict) else None)
            for r in resp.results())

    @staticmethod
    def _assert_capped_prefix(local, jx, limit):
        """The capped device subset must be, per constraint, a prefix of
        the scalar driver's full sorted-cache-key order (the LocalDriver
        ignores the cap by design — the audit manager truncates)."""
        from gatekeeper_tpu.client.interface import QueryOpts
        key = lambda r: (r.msg, r.constraint["metadata"]["name"],
                         (r.review or {}).get("name")
                         if isinstance(r.review, dict) else None)
        lraw = local.driver.query_audit("admission.k8s.gatekeeper.sh")[0]
        jcap = jx.driver.query_audit(
            "admission.k8s.gatekeeper.sh",
            QueryOpts(limit_per_constraint=limit))[0]
        by_full: dict = {}
        for r in lraw:
            by_full.setdefault(key(r)[1], []).append(key(r))
        by_cap: dict = {}
        for r in jcap:
            by_cap.setdefault(key(r)[1], []).append(key(r))
        for name, rs in by_cap.items():
            assert rs == by_full[name][: len(rs)], name

    def test_library_driver_churn_parity(self):
        rng = random.Random(23)
        local, jx = self._clients()
        docs = all_docs()
        for t, c in docs:
            for cl in (local, jx):
                cl.add_template(t)
                cl.add_constraint(c)
        objs = make_mixed(rng, 120)
        # namespaces so namespaceSelector/ns matching has cached targets
        for ns in ("default", "prod", "dev"):
            objs.append({"apiVersion": "v1", "kind": "Namespace",
                         "metadata": {"name": ns,
                                      "labels": {"env": ns}}})
        for o in objs:
            local.add_data(o)
            jx.add_data(o)
        assert self._results(local) == self._results(jx)
        self._assert_capped_prefix(local, jx, 3)
        deltas0 = jx.driver.metrics.counter("bindings_delta_updates").value
        for round_ in range(4):
            upd = make_mixed(rng, 8)
            for o in upd:
                # updates reuse existing names -> same cache path keys
                o["metadata"]["name"] = f"pod{rng.randrange(120)}"
                o["kind"] = "Pod"
                o["apiVersion"] = "v1"
                local.add_data(o)
                jx.add_data(o)
            if round_ == 1:
                # add + delete: key-set churn (order caches rebuild) AND
                # tombstones through the driver delta (mask/alive/scatter
                # over dead rows)
                new = make_mixed(rng, 3)
                for o in new:
                    local.add_data(o)
                    jx.add_data(o)
                for o in objs[:2]:
                    local.remove_data(o)
                    jx.remove_data(o)
            if round_ == 2:
                # Namespace churn: delta mask must be bypassed
                ns = {"apiVersion": "v1", "kind": "Namespace",
                      "metadata": {"name": "prod",
                                   "labels": {"env": "changed"}}}
                local.add_data(ns)
                jx.add_data(ns)
            assert self._results(local) == self._results(jx), f"round {round_}"
            self._assert_capped_prefix(local, jx, 3)
        deltas = jx.driver.metrics.counter("bindings_delta_updates").value
        assert deltas > deltas0, "delta path never engaged"

    def test_binding_delta_off_is_bit_identical_oracle(self, monkeypatch):
        """GATEKEEPER_BINDING_DELTA=off rebuilds bindings whole every
        generation — the delta chain's off-switch oracle (and the
        re-stage comparator the devpages_churn bench measures H2D
        against).  Verdicts must match the delta-on driver exactly
        across churn, and the delta counter must stay at zero."""
        rng = random.Random(31)
        _, jx_on = self._clients()
        monkeypatch.setenv("GATEKEEPER_BINDING_DELTA", "off")
        _, jx_off = self._clients()
        for t, c in all_docs():
            for cl in (jx_on, jx_off):
                cl.add_template(t)
                cl.add_constraint(c)
        objs = make_mixed(rng, 60)
        for o in objs:
            jx_on.add_data(o)
            jx_off.add_data(o)
        monkeypatch.delenv("GATEKEEPER_BINDING_DELTA")
        monkeypatch.setenv("GATEKEEPER_BINDING_DELTA", "on")
        r_on = self._results(jx_on)
        monkeypatch.setenv("GATEKEEPER_BINDING_DELTA", "off")
        assert r_on == self._results(jx_off)
        for round_ in range(2):
            upd = make_mixed(rng, 5)
            for o in upd:
                o["metadata"]["name"] = f"pod{rng.randrange(60)}"
                o["kind"] = "Pod"
                o["apiVersion"] = "v1"
                jx_on.add_data(o)
                jx_off.add_data(o)
            monkeypatch.setenv("GATEKEEPER_BINDING_DELTA", "on")
            r_on = self._results(jx_on)
            monkeypatch.setenv("GATEKEEPER_BINDING_DELTA", "off")
            assert r_on == self._results(jx_off), f"round {round_}"
        assert jx_off.driver.metrics.counter(
            "bindings_delta_updates").value == 0
        assert jx_on.driver.metrics.counter(
            "bindings_delta_updates").value > 0


class TestDeviceBatchReview:
    """query_review_batch's [C, B] device pass must match per-review
    query_review exactly — including namespaceSelector resolution
    against the real cached namespaces, autoreject, DELETE/UPDATE
    operations (the $meta-operation guard), and unlowerable kinds."""

    def test_batch_review_parity(self, monkeypatch):
        from gatekeeper_tpu.client.client import Backend
        from gatekeeper_tpu.client.interface import QueryOpts
        from gatekeeper_tpu.engine import jax_driver as jd_mod
        from gatekeeper_tpu.engine.jax_driver import JaxDriver
        from gatekeeper_tpu.target.k8s import TARGET_NAME, K8sValidationTarget

        monkeypatch.setattr(jd_mod, "SMALL_WORKLOAD_EVALS", 1)
        monkeypatch.setattr(jd_mod, "REVIEW_BATCH_MIN_EVALS", 1)
        rng = random.Random(31)
        jx = Backend(JaxDriver()).new_client([K8sValidationTarget()])
        for t, c in all_docs():
            jx.add_template(t)
            jx.add_constraint(c)
        # cached namespaces (one matching ns-selector-ish labels)
        for ns, labels in (("default", {"env": "prod"}), ("dev", {})):
            jx.add_data({"apiVersion": "v1", "kind": "Namespace",
                         "metadata": {"name": ns, "labels": labels}})
        objs = make_mixed(rng, 40)
        reviews = []
        for i, o in enumerate(objs):
            op = ["CREATE", "UPDATE", "DELETE"][i % 3]
            reviews.append({
                "kind": {"group": "", "version": "v1",
                         "kind": o.get("kind", "Pod")},
                "name": o["metadata"]["name"],
                "namespace": o["metadata"].get("namespace",
                                               ["default", "nowhere"][i % 2]),
                "operation": op, "object": o})
        drv = jx.driver
        batched = drv.query_review_batch(TARGET_NAME, reviews, QueryOpts())
        single = [drv.query_review(TARGET_NAME, r, QueryOpts())
                  for r in reviews]
        assert drv.metrics.counter("review_batches_device").value == 1
        for i, ((br, _), (sr, _)) in enumerate(zip(batched, single)):
            bk = [(r.msg, r.constraint["metadata"]["name"]) for r in br]
            sk = [(r.msg, r.constraint["metadata"]["name"]) for r in sr]
            assert bk == sk, f"review {i}: {bk} != {sk}"
        assert any(len(br) > 0 for br, _ in batched)


class TestInventoryJoinLowering:
    """K8sUniqueIngressHost (data.inventory duplicate join) on the
    device path: parity with the scalar oracle, including churn where an
    upsert ELSEWHERE flips this row's duplicate verdict (the cross-row
    diff in update_bindings)."""

    def _clients(self):
        from gatekeeper_tpu.client.client import Backend
        from gatekeeper_tpu.client.local_driver import LocalDriver
        from gatekeeper_tpu.engine.jax_driver import JaxDriver
        from gatekeeper_tpu.target.k8s import K8sValidationTarget
        return (Backend(LocalDriver()).new_client([K8sValidationTarget()]),
                Backend(JaxDriver()).new_client([K8sValidationTarget()]))

    @staticmethod
    def _res(client):
        return sorted((r.msg, r.constraint["metadata"]["name"],
                       (r.review or {}).get("name"))
                      for r in client.audit().results())

    def _ing(self, name, ns, host):
        return {"apiVersion": "extensions/v1beta1", "kind": "Ingress",
                "metadata": {"name": name, "namespace": ns},
                "spec": {"host": host}}

    def test_unique_ingress_host_parity_and_churn(self):
        from gatekeeper_tpu.library.templates import (LIBRARY,
                                                      constraint_doc,
                                                      template_doc)
        local, jx = self._clients()
        for c in (local, jx):
            c.add_template(template_doc(
                "K8sUniqueIngressHost", LIBRARY["K8sUniqueIngressHost"][0]))
            c.add_constraint(constraint_doc("K8sUniqueIngressHost", "uniq"))
        st = jx.driver.state["admission.k8s.gatekeeper.sh"]
        assert st.templates["K8sUniqueIngressHost"].vectorized is not None, \
            "K8sUniqueIngressHost must lower (33/33)"
        objs = [self._ing("a", "ns1", "x.com"),
                self._ing("b", "ns2", "x.com"),       # dup of a
                self._ing("c", "ns1", "y.com"),       # unique
                self._ing("c2", "ns3", "z.com"),      # unique
                # same name, same host, different ns: NOT a violation
                # (the guard excludes same-name entries)
                self._ing("d", "ns1", "w.com"),
                self._ing("d", "ns2", "w.com"),
                # a pod with a spec.host colliding with an ingress: the
                # join matches any review object with that host
                {"apiVersion": "v1", "kind": "Pod",
                 "metadata": {"name": "p", "namespace": "ns1"},
                 "spec": {"host": "x.com", "containers": []}}]
        for o in objs:
            local.add_data(o)
            jx.add_data(o)
        r_l, r_j = self._res(local), self._res(jx)
        assert r_l == r_j
        names = {n for _, _, n in r_l}
        assert names == {"a", "b", "p"}, names
        # churn: change c's host to x.com — a/b/p unchanged but c JOINS;
        # then change b away — c/p still violate via a... etc.
        upd = self._ing("c", "ns1", "x.com")
        local.add_data(upd)
        jx.add_data(upd)
        r_l, r_j = self._res(local), self._res(jx)
        assert r_l == r_j
        assert {n for _, _, n in r_j} == {"a", "b", "c", "p"}
        # removal flips OTHER rows' verdicts (cross-row delta)
        rm = self._ing("a", "ns1", "x.com")
        local.remove_data(rm)
        jx.remove_data(rm)
        r_l, r_j = self._res(local), self._res(jx)
        assert r_l == r_j
        assert {n for _, _, n in r_j} == {"b", "c", "p"}
        # collapse: no Ingress holds x.com anymore — the pod's join
        # finds nothing (its own row is not an Ingress), no violations
        for o in (self._ing("b", "ns2", "x.com"), self._ing("c", "ns1", "x.com")):
            local.remove_data(o)
            jx.remove_data(o)
        r_l, r_j = self._res(local), self._res(jx)
        assert r_l == r_j
        assert not r_j, r_j


class TestBatchReviewInventoryGuard:
    def test_batch_review_inventory_join_sound(self, monkeypatch):
        """An admission batch must not gate inventory-join kinds with a
        mini-table join (the batch can't see the real inventory): a new
        Ingress duplicating a CACHED host must be flagged even when no
        other review in the batch shares the host."""
        from gatekeeper_tpu.client.client import Backend
        from gatekeeper_tpu.client.interface import QueryOpts
        from gatekeeper_tpu.engine import jax_driver as jd_mod
        from gatekeeper_tpu.engine.jax_driver import JaxDriver
        from gatekeeper_tpu.library.templates import (LIBRARY,
                                                      constraint_doc,
                                                      template_doc)
        from gatekeeper_tpu.target.k8s import TARGET_NAME, K8sValidationTarget
        monkeypatch.setattr(jd_mod, "SMALL_WORKLOAD_EVALS", 1)
        monkeypatch.setattr(jd_mod, "REVIEW_BATCH_MIN_EVALS", 1)
        jx = Backend(JaxDriver()).new_client([K8sValidationTarget()])
        jx.add_template(template_doc(
            "K8sUniqueIngressHost", LIBRARY["K8sUniqueIngressHost"][0]))
        jx.add_constraint(constraint_doc("K8sUniqueIngressHost", "uniq"))
        jx.add_data({"apiVersion": "extensions/v1beta1", "kind": "Ingress",
                     "metadata": {"name": "existing", "namespace": "ns1"},
                     "spec": {"host": "x.com"}})
        new = {"apiVersion": "extensions/v1beta1", "kind": "Ingress",
               "metadata": {"name": "incoming", "namespace": "ns2"},
               "spec": {"host": "x.com"}}
        reviews = [{"kind": {"group": "extensions", "version": "v1beta1",
                             "kind": "Ingress"},
                    "name": "incoming", "namespace": "ns2",
                    "operation": "CREATE", "object": new}]
        out = jx.driver.query_review_batch(TARGET_NAME, reviews, QueryOpts())
        msgs = [r.msg for r in out[0][0]]
        assert any("duplicate ingress host" in m for m in msgs), msgs
