"""Stage-6 sharding certifier: static partition plans, simulated-mesh
validation, snapshot persistence, plan-gated sharded sweeps.

Covers the abstract interpreter's plan shape (row-local templates are
shard-eligible with the serving collectives, padding constraints, and
per-binding H2D layout; the inventory-join template is ineligible with
the footprint's reason), the 2-shard simulated-mesh validator (honest
plans validate; the GATEKEEPER_SHARDPLAN_TEST_BREAK seam is caught, and
under strict mode the broken kind pins to the replicated path WITHOUT
failing the install), snapshot persistence in the "sp" tier (warm
process re-runs zero analyses; stale versions are ignored), the
reconciler's ``shard_ineligible`` status warning (present exactly once,
surviving re-reconcile), and the plan-driven GATEKEEPER_SHARDS=2 sweep's
bit-identical parity with the unsharded oracle.
"""

import copy
import os
import random

import pytest

from gatekeeper_tpu.analysis import footprint, shardplan
from gatekeeper_tpu.api.templates import compile_target_rego
from gatekeeper_tpu.client.client import Backend
from gatekeeper_tpu.client.interface import QueryOpts
from gatekeeper_tpu.engine.jax_driver import JaxDriver
from gatekeeper_tpu.ir.lower import lower_template
from gatekeeper_tpu.library import all_docs, make_mixed
from gatekeeper_tpu.target.k8s import K8sValidationTarget, TARGET_NAME

AGILE_LABELS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "demo", "agilebank", "templates", "k8sagilelabels.yaml")


@pytest.fixture(autouse=True)
def _reset_shardplan_state(monkeypatch):
    """Analyzer state is process-global (memo, registries, counter) —
    isolate every test.  The footprint registries reset too: the
    ineligibility reason prefers footprint.locality_for."""
    monkeypatch.setattr(shardplan, "_memo", {})
    monkeypatch.setattr(shardplan, "plans", {})
    monkeypatch.setattr(shardplan, "ineligible", {})
    monkeypatch.setattr(shardplan, "violations", {})
    monkeypatch.setattr(shardplan, "analyses_run", 0)
    monkeypatch.setattr(footprint, "_memo", {})
    monkeypatch.setattr(footprint, "cross_row", {})
    monkeypatch.setattr(footprint, "violations", {})
    monkeypatch.delenv("GATEKEEPER_SHARDPLAN", raising=False)
    monkeypatch.delenv("GATEKEEPER_SHARDPLAN_TEST_BREAK", raising=False)
    monkeypatch.delenv("GATEKEEPER_SHARDS", raising=False)
    monkeypatch.delenv("GATEKEEPER_SNAPSHOT_DIR", raising=False)
    yield


def _library(kind: str):
    for tdoc, cdoc in all_docs():
        k = tdoc["spec"]["crd"]["spec"]["names"]["kind"]
        if k != kind:
            continue
        tt = tdoc["spec"]["targets"][0]
        compiled = compile_target_rego(kind, tt["target"], tt["rego"])
        return compiled, lower_template(compiled.module,
                                        compiled.interp), cdoc
    raise LookupError(kind)


# ---------------------------------------------------------------------------
# the abstract interpreter: plan shape


class TestAnalyzer:
    def test_row_local_template_is_eligible(self):
        _c, lowered, _ = _library("K8sRequiredLabels")
        plan = shardplan.analyze("K8sRequiredLabels", lowered)
        assert plan.eligible
        assert plan.version == shardplan.SHARDPLAN_VERSION
        # the serving reduction: one all_reduce over the per-shard
        # counts, an all_gather each for the capped top-k rows/scores
        assert ("all_reduce", "r", "violation_counts") in plan.collectives
        gathers = [c for c in plan.collectives if c[0] == "all_gather"]
        assert {c[2] for c in gathers} == {"topk_rows", "topk_scores"}
        # pad-to-multiple-of-shard-count constraints
        assert "r_pad % r_shards == 0" in plan.padding
        assert "c_pad % c_shards == 0" in plan.padding
        # per-shard H2D layout: framework bindings partition as prepped
        layout = dict(plan.layout)
        assert layout["__match__"] == ("c", "r")
        assert layout["__alive__"] == ("r",)
        assert layout["__cvalid__"] == ("c",)
        # every reachable node carries an abstract state
        assert plan.node_shardings
        states = {s for _i, s in plan.node_shardings}
        assert states <= {shardplan.SHARDED, shardplan.REPLICATED}
        assert shardplan.SHARDED in states

    def test_inventory_join_is_ineligible(self):
        _c, lowered, _ = _library("K8sUniqueIngressHost")
        plan = shardplan.analyze("K8sUniqueIngressHost", lowered)
        assert not plan.eligible
        assert "inventory join" in plan.reason
        assert plan.node_shardings == ()
        assert plan.collectives == ()

    def test_ineligible_reason_prefers_footprint_registry(self):
        compiled, lowered, cdoc = _library("K8sUniqueIngressHost")
        footprint.certify("K8sUniqueIngressHost", compiled, lowered,
                          [cdoc])
        want = footprint.locality_for("K8sUniqueIngressHost")
        assert want is not None
        plan = shardplan.analyze("K8sUniqueIngressHost", lowered)
        assert plan.reason == want

    def test_library_keeps_forty_templates_shard_eligible(self):
        eligible = 0
        for tdoc, _cdoc in all_docs():
            kind = tdoc["spec"]["crd"]["spec"]["names"]["kind"]
            _c, lowered, _ = _library(kind)
            eligible += shardplan.analyze(kind, lowered).eligible
        assert eligible >= 40

    def test_digest_pins_program_and_spec(self):
        _c, lowered, _ = _library("K8sRequiredLabels")
        _c2, lowered2, _ = _library("K8sAllowedRepos")
        assert shardplan.shardplan_digest(lowered) \
            == shardplan.shardplan_digest(lowered)
        assert shardplan.shardplan_digest(lowered) \
            != shardplan.shardplan_digest(lowered2)


# ---------------------------------------------------------------------------
# simulated-mesh validation + the TEST_BREAK fault seam


class TestValidation:
    def test_honest_plan_validates_at_2_shards(self):
        compiled, lowered, cdoc = _library("K8sRequiredLabels")
        plan = shardplan.analyze("K8sRequiredLabels", lowered)
        plan2, found = shardplan.validate_plan(
            "K8sRequiredLabels", compiled, lowered, plan, [cdoc])
        assert found == []
        assert plan2.validated
        assert plan2.shards_validated == 2

    def test_broken_plan_caught(self, monkeypatch):
        monkeypatch.setenv("GATEKEEPER_SHARDPLAN_TEST_BREAK",
                           "K8sRequiredLabels")
        compiled, lowered, cdoc = _library("K8sRequiredLabels")
        plan = shardplan.analyze("K8sRequiredLabels", lowered)
        plan2, found = shardplan.validate_plan(
            "K8sRequiredLabels", compiled, lowered, plan, [cdoc])
        assert found, "validator missed a deliberately broken plan"
        assert not plan2.validated
        assert all(v.kind == "K8sRequiredLabels" for v in found)
        assert "mask mismatch" in found[0].note

    def test_strict_break_pins_replicated_never_fails_install(
            self, monkeypatch):
        monkeypatch.setenv("GATEKEEPER_SHARDPLAN", "strict")
        monkeypatch.setenv("GATEKEEPER_SHARDPLAN_TEST_BREAK",
                           "K8sAllowedRepos")
        for tdoc, _cdoc in all_docs():
            if tdoc["spec"]["crd"]["spec"]["names"]["kind"] \
                    == "K8sAllowedRepos":
                break
        jd = JaxDriver()
        c = Backend(jd).new_client([K8sValidationTarget()])
        c.add_template(tdoc)        # must NOT raise (unlike footprint)
        st = jd._state(TARGET_NAME)
        assert st.shardplans.get("K8sAllowedRepos") is None
        assert shardplan.violations_for("K8sAllowedRepos")

    def test_strict_honest_install_validates(self, monkeypatch):
        monkeypatch.setenv("GATEKEEPER_SHARDPLAN", "strict")
        for tdoc, _cdoc in all_docs():
            if tdoc["spec"]["crd"]["spec"]["names"]["kind"] \
                    == "K8sRequiredLabels":
                break
        jd = JaxDriver()
        c = Backend(jd).new_client([K8sValidationTarget()])
        c.add_template(tdoc)
        st = jd._state(TARGET_NAME)
        plan = st.shardplans.get("K8sRequiredLabels")
        assert plan is not None and plan.eligible and plan.validated


# ---------------------------------------------------------------------------
# engine wiring: modes, scalar fallbacks, snapshot persistence


class TestEngine:
    def test_mode_off_skips_analysis(self, monkeypatch):
        monkeypatch.setenv("GATEKEEPER_SHARDPLAN", "off")
        for tdoc, _cdoc in all_docs():
            if tdoc["spec"]["crd"]["spec"]["names"]["kind"] \
                    == "K8sRequiredLabels":
                break
        jd = JaxDriver()
        c = Backend(jd).new_client([K8sValidationTarget()])
        c.add_template(tdoc)
        st = jd._state(TARGET_NAME)
        assert st.shardplans.get("K8sRequiredLabels") is None
        assert shardplan.analyses_run == 0

    def test_cannot_lower_has_no_plan(self):
        import yaml
        jd = JaxDriver()
        c = Backend(jd).new_client([K8sValidationTarget()])
        # scalar fallback: sprintf with mixed dependencies
        with open(AGILE_LABELS) as f:
            c.add_template(yaml.safe_load(f))
        st = jd._state(TARGET_NAME)
        assert st.templates["K8sAgileLabels"].vectorized is None
        assert st.shardplans.get("K8sAgileLabels") is None

    def test_ineligible_plan_is_stored(self):
        for tdoc, _cdoc in all_docs():
            if tdoc["spec"]["crd"]["spec"]["names"]["kind"] \
                    == "K8sUniqueIngressHost":
                break
        jd = JaxDriver()
        c = Backend(jd).new_client([K8sValidationTarget()])
        c.add_template(tdoc)
        st = jd._state(TARGET_NAME)
        plan = st.shardplans.get("K8sUniqueIngressHost")
        # the sweep reads plan.eligible: ineligible plans ARE stored
        assert plan is not None and not plan.eligible
        assert shardplan.ineligible_for("K8sUniqueIngressHost") \
            == plan.reason

    def test_snapshot_roundtrip_zero_warm_analyses(self, monkeypatch,
                                                   tmp_path):
        monkeypatch.setenv("GATEKEEPER_SNAPSHOT_DIR", str(tmp_path))
        compiled, lowered, cdoc = _library("K8sRequiredLabels")
        plan = shardplan.certify("K8sRequiredLabels", compiled, lowered,
                                 [cdoc])
        assert shardplan.analyses_run == 1
        # a "restarted process": fresh memo, same snapshot dir
        monkeypatch.setattr(shardplan, "_memo", {})
        plan2 = shardplan.certify("K8sRequiredLabels", compiled, lowered,
                                  [cdoc])
        assert shardplan.analyses_run == 1      # loaded, not re-analyzed
        assert plan2.digest == plan.digest
        assert plan2.collectives == plan.collectives
        assert shardplan.plan_for("K8sRequiredLabels") is plan2

    def test_version_mismatch_reanalyzes(self, monkeypatch, tmp_path):
        import dataclasses
        from gatekeeper_tpu.resilience import snapshot as snap
        monkeypatch.setenv("GATEKEEPER_SNAPSHOT_DIR", str(tmp_path))
        compiled, lowered, cdoc = _library("K8sRequiredLabels")
        plan = shardplan.certify("K8sRequiredLabels", compiled, lowered,
                                 [cdoc])
        stale = dataclasses.replace(plan, version="sp-0")
        snap.save_shardplan(plan.digest, stale)
        monkeypatch.setattr(shardplan, "_memo", {})
        shardplan.certify("K8sRequiredLabels", compiled, lowered, [cdoc])
        assert shardplan.analyses_run == 2      # stale tier ignored


# ---------------------------------------------------------------------------
# reconciler status: cross_row_dependency + shard_ineligible, no dupes


class TestReconcilerStatus:
    def test_both_warnings_once_and_survive_re_reconcile(self):
        from gatekeeper_tpu.api.config import GVK
        from gatekeeper_tpu.cluster.fake import FakeCluster
        from gatekeeper_tpu.controllers.config import CONFIG_GVK
        from gatekeeper_tpu.controllers.constrainttemplate import \
            TEMPLATE_GVK
        from gatekeeper_tpu.controllers.registry import add_to_manager
        from gatekeeper_tpu.utils.ha_status import get_ha_status
        from tests.test_control_plane import make_client

        cluster = FakeCluster()
        cluster.register_kind(TEMPLATE_GVK, "constrainttemplates")
        cluster.register_kind(CONFIG_GVK, "configs")
        cluster.register_kind(GVK("", "v1", "Namespace"), "namespaces")
        plane = add_to_manager(cluster, make_client(JaxDriver()))
        for tdoc, _cdoc in all_docs():
            if tdoc["spec"]["crd"]["spec"]["names"]["kind"] \
                    == "K8sUniqueIngressHost":
                break
        cluster.create(copy.deepcopy(tdoc))
        plane.run_until_idle()

        def warning_codes():
            tmpl = cluster.get(TEMPLATE_GVK, "k8suniqueingresshost")
            ws = get_ha_status(tmpl).get("warnings") or []
            return tmpl, ws, [w["code"] for w in ws]

        tmpl, ws, codes = warning_codes()
        assert codes.count("cross_row_dependency") == 1
        assert codes.count("shard_ineligible") == 1
        sharded = next(w for w in ws if w["code"] == "shard_ineligible")
        # the shard warning carries the footprint's reason verbatim
        assert "inventory join" in sharded["message"]
        assert "replicated path" in sharded["message"]

        # re-reconcile (spec-less touch): warnings must not accumulate
        touched = copy.deepcopy(tmpl)
        touched["metadata"].setdefault("labels", {})["touch"] = "1"
        cluster.update(touched)
        plane.run_until_idle()
        _tmpl, _ws, codes2 = warning_codes()
        assert codes2.count("cross_row_dependency") == 1
        assert codes2.count("shard_ineligible") == 1


# ---------------------------------------------------------------------------
# plan-driven sharded sweep: oracle parity under GATEKEEPER_SHARDS


def _verdicts(results):
    return sorted(
        ((r.constraint or {}).get("kind", ""),
         ((r.constraint or {}).get("metadata") or {}).get("name", ""),
         ((r.resource or {}).get("metadata") or {}).get("name", ""),
         r.msg)
        for r in results)


class TestShardedSweep:
    KINDS = ("K8sRequiredLabels", "K8sAllowedRepos",
             "K8sUniqueIngressHost")

    def _run(self, n_shards, monkeypatch, n=60):
        import gatekeeper_tpu.engine.jax_driver as jd_mod
        monkeypatch.setenv("GATEKEEPER_SHARDS", str(n_shards))
        monkeypatch.setattr(jd_mod, "SMALL_WORKLOAD_EVALS", 0)
        resources = make_mixed(random.Random(11), n)
        jd = jd_mod.JaxDriver()
        c = Backend(jd).new_client([K8sValidationTarget()])
        for tdoc, cdoc in all_docs():
            kind = tdoc["spec"]["crd"]["spec"]["names"]["kind"]
            if kind in self.KINDS:
                c.add_template(tdoc)
                c.add_constraint(cdoc)
        c.add_data_batch(resources)
        opts = QueryOpts(limit_per_constraint=20, full=True)
        results, _ = jd.query_audit(TARGET_NAME, opts)
        stanza = dict(jd.last_sweep_phases.get("shard") or {})
        return _verdicts(results), stanza

    def test_two_shard_parity_with_unsharded_oracle(self, monkeypatch):
        v_oracle, st_oracle = self._run(1, monkeypatch)
        v_sharded, st_sharded = self._run(2, monkeypatch)
        assert v_sharded == v_oracle        # bit-identical verdicts
        assert st_oracle.get("enabled") is False
        assert st_sharded.get("enabled") is True
        assert st_sharded.get("shards") == 2
        assert st_sharded.get("plan_gated") is True
        # the row-local kinds shard; the inventory-join kind pins
        assert st_sharded.get("kinds_sharded", 0) >= 1
        assert st_sharded.get("kinds_replicated", 0) >= 1
        assert st_sharded.get("per_shard_evals", 0) > 0
        assert st_sharded.get("collectives", 0) > 0

    def test_four_shard_parity_with_unsharded_oracle(self, monkeypatch):
        v_oracle, _st_oracle = self._run(1, monkeypatch)
        v_sharded, st_sharded = self._run(4, monkeypatch)
        assert v_sharded == v_oracle
        assert st_sharded.get("shards") == 4
        assert st_sharded.get("kinds_sharded", 0) >= 1
        assert st_sharded.get("collectives", 0) > 0

    def test_off_mode_gating_disabled(self, monkeypatch):
        monkeypatch.setenv("GATEKEEPER_SHARDPLAN", "off")
        v_off, st_off = self._run(2, monkeypatch)
        v_oracle, _ = self._run(1, monkeypatch)
        # GATEKEEPER_SHARDPLAN=off is the oracle: everything shards as
        # before this stage, still bit-identically
        assert st_off.get("plan_gated") is False
        assert v_off == v_oracle
