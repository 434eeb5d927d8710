"""Stage-5 dependency analysis: column read-set footprints,
row-locality certificates, and footprint-driven selective invalidation.

Covers the abstract interpreter's read-set exactness (library basics,
aliased columns claimed once per source, provider-table reads,
CannotLower fallbacks carrying no footprint), cross-row detection
(inventory-join templates), the perturbation validator (honest
footprints survive; the GATEKEEPER_FOOTPRINT_TEST_NARROW seam is
caught, and under strict mode the narrowed install fails with
VetError), the store's dirty-path log, snapshot persistence (warm
process re-runs zero analyses), and the sweep-time selective
invalidation's bit-identical parity with the GATEKEEPER_FOOTPRINT=off
oracle under churn.
"""

import copy
import os
import random

import pytest

from gatekeeper_tpu.analysis import footprint
from gatekeeper_tpu.api.templates import compile_target_rego
from gatekeeper_tpu.client.client import Backend
from gatekeeper_tpu.client.interface import QueryOpts
from gatekeeper_tpu.engine.jax_driver import JaxDriver
from gatekeeper_tpu.ir.lower import lower_template
from gatekeeper_tpu.library import all_docs, make_mixed
from gatekeeper_tpu.store.table import ResourceMeta, ResourceTable
from gatekeeper_tpu.target.k8s import K8sValidationTarget, TARGET_NAME

AGILE_LABELS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "demo", "agilebank", "templates", "k8sagilelabels.yaml")


@pytest.fixture(autouse=True)
def _reset_footprint_state(monkeypatch):
    """Analyzer state is process-global (memo, registries, counter) —
    isolate every test."""
    monkeypatch.setattr(footprint, "_memo", {})
    monkeypatch.setattr(footprint, "cross_row", {})
    monkeypatch.setattr(footprint, "violations", {})
    monkeypatch.setattr(footprint, "analyses_run", 0)
    monkeypatch.delenv("GATEKEEPER_FOOTPRINT", raising=False)
    monkeypatch.delenv("GATEKEEPER_FOOTPRINT_TEST_NARROW", raising=False)
    monkeypatch.delenv("GATEKEEPER_SNAPSHOT_DIR", raising=False)
    # this file measures the legacy full-kind sweep's selective
    # invalidation (kind skips); the paged path supersedes it with page
    # bits and has its own oracle gates in test_pages.py
    monkeypatch.setenv("GATEKEEPER_PAGES", "off")
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


def _paths(fp):
    return {c.path for c in fp.columns}


def _sens(fp, path):
    return {c.sensitivity for c in fp.columns if c.path == path}


# ---------------------------------------------------------------------------
# read-set exactness


class TestReadSets:
    def test_required_labels_reads_only_labels(self):
        compiled, lowered, _ = _library("K8sRequiredLabels")
        fp = footprint.analyze("K8sRequiredLabels", lowered)
        assert fp.row_local
        assert _paths(fp) == {("metadata", "labels")}
        assert _sens(fp, ("metadata", "labels")) == {"equality"}
        assert fp.providers == ()

    def test_allowed_repos_reads_container_images(self):
        compiled, lowered, _ = _library("K8sAllowedRepos")
        fp = footprint.analyze("K8sAllowedRepos", lowered)
        assert fp.row_local
        assert ("spec", "containers", "*", "image") in _paths(fp)
        # nothing outside spec.containers / initContainers is claimed
        for p in _paths(fp):
            assert p[0] == "spec", p

    def test_sensitivity_classes(self):
        # hostPort is ordered-compared -> range, not equality
        _c, lowered, _ = _library("K8sHostPorts")
        fp = footprint.analyze("K8sHostPorts", lowered)
        ranged = {c.path for c in fp.columns if c.sensitivity == "range"}
        assert any("hostPort" in p for p in ranged), fp.columns
        # a regex-table template reads its column as string-regex
        _c, lowered, _ = _library("K8sImageDigests")
        fp2 = footprint.analyze("K8sImageDigests", lowered)
        assert any(c.sensitivity == "string-regex" for c in fp2.columns), \
            fp2.columns

    def test_existence_only_reads(self):
        _c, lowered, _ = _library("K8sRequiredProbes")
        fp = footprint.analyze("K8sRequiredProbes", lowered)
        assert all(c.sensitivity == "existence" for c in fp.columns), \
            fp.columns

    def test_aliased_columns_claimed_once_per_source(self):
        # the same scalar column feeding several conjuncts appears once
        for kind in ("K8sRequiredLabels", "K8sAllowedRepos",
                     "K8sStorageClass"):
            _c, lowered, _ = _library(kind)
            fp = footprint.analyze(kind, lowered)
            seen = [(c.path, c.source) for c in fp.columns]
            assert len(seen) == len(set(seen)), (kind, fp.columns)

    def test_provider_table_reads_recorded(self):
        rego = """package extfp
violation[{"msg": msg}] {
  image := input.review.object.spec.image
  verdict := object.get(external_data({"provider": "sig-prov", "keys": [image]}), ["responses", image], "missing")
  verdict == "invalid"
  msg := sprintf("image %v rejected: %v", [image, verdict])
}
"""
        compiled = compile_target_rego("K8sExtFp",
                                       "admission.k8s.gatekeeper.sh", rego)
        lowered = lower_template(compiled.module, compiled.interp)
        fp = footprint.analyze("K8sExtFp", lowered)
        assert fp.providers == ("sig-prov",)
        assert ("spec", "image") in _paths(fp)

    def test_digest_pins_program_and_spec(self):
        _c, lowered, _ = _library("K8sRequiredLabels")
        _c2, lowered2, _ = _library("K8sAllowedRepos")
        assert footprint.footprint_digest(lowered) \
            == footprint.footprint_digest(lowered)
        assert footprint.footprint_digest(lowered) \
            != footprint.footprint_digest(lowered2)


# ---------------------------------------------------------------------------
# row locality


class TestRowLocality:
    def test_inventory_join_is_cross_row(self):
        compiled, lowered, _ = _library("K8sUniqueIngressHost")
        fp = footprint.analyze("K8sUniqueIngressHost", lowered)
        assert not fp.row_local
        assert fp.cross_row_reasons
        assert any("inventory join" in r for r in fp.cross_row_reasons)
        # the joined column is claimed under the inventory source
        assert any(c.source.startswith("inventory:")
                   for c in fp.columns), fp.columns

    def test_library_is_mostly_row_local(self):
        kinds = ["K8sRequiredLabels", "K8sAllowedRepos",
                 "K8sContainerLimits", "K8sBlockNodePort",
                 "K8sDisallowedTags"]
        for kind in kinds:
            _c, lowered, _ = _library(kind)
            assert footprint.analyze(kind, lowered).row_local, kind

    def test_locality_registry(self):
        compiled, lowered, cdoc = _library("K8sUniqueIngressHost")
        footprint.certify("K8sUniqueIngressHost", compiled, lowered,
                          [cdoc])
        assert footprint.locality_for("K8sUniqueIngressHost") is not None
        _c2, low2, cd2 = _library("K8sRequiredLabels")
        footprint.certify("K8sRequiredLabels", _c2, low2, [cd2])
        assert footprint.locality_for("K8sRequiredLabels") is None


# ---------------------------------------------------------------------------
# paths_intersect + the store's dirty-path log


class TestPaths:
    def test_paths_intersect(self):
        pi = footprint.paths_intersect
        assert pi(("spec", "host"), ("spec", "host"))
        assert pi(("spec",), ("spec", "host"))          # write above
        assert pi(("spec", "host", "x"), ("spec", "host"))  # write below
        assert pi(("spec", "containers", "*", "image"),
                  ("spec", "containers", "0", "image"))
        assert not pi(("spec", "host"), ("spec", "port"))
        assert not pi(("metadata", "labels"), ("spec", "host"))

    def _table(self):
        t = ResourceTable()
        meta = ResourceMeta("v1", "Pod", "p1", "default")
        t.upsert("p1", {"kind": "Pod", "spec": {"a": 1, "b": [1, 2]},
                        "metadata": {"labels": {"x": "1"}}}, meta)
        return t, meta

    def test_dirty_paths_replace_upsert(self):
        t, meta = self._table()
        g = t.generation
        t.upsert("p1", {"kind": "Pod", "spec": {"a": 2, "b": [1, 2]},
                        "metadata": {"labels": {"x": "1"}}}, meta)
        changed = t.dirty_paths_since(g)
        assert changed == frozenset({("spec", "a")})
        # window starting at the new generation is empty
        assert t.dirty_paths_since(t.generation) == frozenset()

    def test_dirty_paths_meta_change(self):
        t, _meta = self._table()
        g = t.generation
        t.upsert("p1", {"kind": "Pod", "spec": {"a": 1, "b": [1, 2]},
                        "metadata": {"labels": {"x": "1"}}},
                 ResourceMeta("v1", "Pod", "p1", "prod"))
        assert ("$meta",) in t.dirty_paths_since(g)

    def test_dirty_paths_floor_after_wipe(self):
        t, meta = self._table()
        g = t.generation
        t.wipe()
        assert t.dirty_paths_since(g) is None   # window predates the log

    def test_dirty_paths_list_change(self):
        t, meta = self._table()
        g = t.generation
        t.upsert("p1", {"kind": "Pod", "spec": {"a": 1, "b": [1, 2, 3]},
                        "metadata": {"labels": {"x": "1"}}}, meta)
        assert ("spec", "b") in t.dirty_paths_since(g)


# ---------------------------------------------------------------------------
# perturbation validation + the NARROW fault seam


class TestValidation:
    def test_honest_footprint_validates(self):
        compiled, lowered, cdoc = _library("K8sRequiredLabels")
        fp = footprint.analyze("K8sRequiredLabels", lowered)
        found = footprint.validate_footprint(
            "K8sRequiredLabels", compiled, lowered, fp, [cdoc])
        assert found == []

    def test_narrowed_footprint_caught(self, monkeypatch):
        monkeypatch.setenv("GATEKEEPER_FOOTPRINT_TEST_NARROW",
                           "K8sRequiredLabels")
        compiled, lowered, cdoc = _library("K8sRequiredLabels")
        fp = footprint.analyze("K8sRequiredLabels", lowered)
        narrowed = footprint.maybe_narrowed("K8sRequiredLabels", fp)
        assert len(narrowed.columns) < len(fp.columns)
        found = footprint.validate_footprint(
            "K8sRequiredLabels", compiled, lowered, narrowed, [cdoc])
        assert found, "validator missed a deliberately narrowed footprint"
        assert all(v.kind == "K8sRequiredLabels" for v in found)

    def test_certify_strict_records_violations(self, monkeypatch):
        monkeypatch.setenv("GATEKEEPER_FOOTPRINT", "strict")
        monkeypatch.setenv("GATEKEEPER_FOOTPRINT_TEST_NARROW",
                           "K8sAllowedRepos")
        compiled, lowered, cdoc = _library("K8sAllowedRepos")
        fp = footprint.certify("K8sAllowedRepos", compiled, lowered,
                               [cdoc])
        assert not fp.validated
        assert footprint.violations_for("K8sAllowedRepos")

    def test_strict_install_fails_on_violation(self, monkeypatch):
        from gatekeeper_tpu.errors import VetError
        monkeypatch.setenv("GATEKEEPER_FOOTPRINT", "strict")
        monkeypatch.setenv("GATEKEEPER_FOOTPRINT_TEST_NARROW",
                           "K8sRequiredLabels")
        for tdoc, cdoc in all_docs():
            if tdoc["spec"]["crd"]["spec"]["names"]["kind"] \
                    == "K8sRequiredLabels":
                break
        jd = JaxDriver()
        c = Backend(jd).new_client([K8sValidationTarget()])
        # template install validates against the parameterless default
        # doc, where a narrowed K8sRequiredLabels footprint is vacuously
        # consistent (the template never fires) — it must survive
        c.add_template(tdoc)
        # the first real parameter doc is a new operating point: the
        # constraint install re-validates and catches the narrow
        with pytest.raises(VetError, match="footprint_violation|verdict"):
            c.add_constraint(cdoc)
        st = jd._state(TARGET_NAME)
        assert st.footprints.get("K8sRequiredLabels") is None

    def test_strict_honest_install_succeeds(self, monkeypatch):
        monkeypatch.setenv("GATEKEEPER_FOOTPRINT", "strict")
        for tdoc, cdoc in all_docs():
            if tdoc["spec"]["crd"]["spec"]["names"]["kind"] \
                    == "K8sRequiredLabels":
                break
        jd = JaxDriver()
        c = Backend(jd).new_client([K8sValidationTarget()])
        c.add_template(tdoc)
        st = jd._state(TARGET_NAME)
        fp = st.footprints.get("K8sRequiredLabels")
        assert fp is not None and fp.validated


# ---------------------------------------------------------------------------
# engine wiring: scalar fallbacks, snapshot persistence


class TestEngine:
    def test_cannot_lower_has_no_footprint(self):
        from tests.test_jax_driver import template_doc
        import yaml
        jd = JaxDriver()
        c = Backend(jd).new_client([K8sValidationTarget()])
        # scalar fallback: sprintf with mixed dependencies
        with open(AGILE_LABELS) as f:
            c.add_template(yaml.safe_load(f))
        st = jd._state(TARGET_NAME)
        assert st.templates["K8sAgileLabels"].vectorized is None
        assert st.footprints.get("K8sAgileLabels") is None

    def test_mode_off_skips_analysis(self, monkeypatch):
        monkeypatch.setenv("GATEKEEPER_FOOTPRINT", "off")
        for tdoc, _cdoc in all_docs():
            if tdoc["spec"]["crd"]["spec"]["names"]["kind"] \
                    == "K8sRequiredLabels":
                break
        jd = JaxDriver()
        c = Backend(jd).new_client([K8sValidationTarget()])
        c.add_template(tdoc)
        st = jd._state(TARGET_NAME)
        assert st.footprints.get("K8sRequiredLabels") is None
        assert footprint.analyses_run == 0

    def test_snapshot_roundtrip_zero_warm_analyses(self, monkeypatch,
                                                   tmp_path):
        monkeypatch.setenv("GATEKEEPER_SNAPSHOT_DIR", str(tmp_path))
        compiled, lowered, cdoc = _library("K8sRequiredLabels")
        fp = footprint.certify("K8sRequiredLabels", compiled, lowered,
                               [cdoc])
        assert footprint.analyses_run == 1
        # a "restarted process": fresh memo, same snapshot dir
        monkeypatch.setattr(footprint, "_memo", {})
        fp2 = footprint.certify("K8sRequiredLabels", compiled, lowered,
                                [cdoc])
        assert footprint.analyses_run == 1      # loaded, not re-analyzed
        assert fp2.digest == fp.digest
        assert _paths(fp2) == _paths(fp)

    def test_version_mismatch_reanalyzes(self, monkeypatch, tmp_path):
        import dataclasses
        from gatekeeper_tpu.resilience import snapshot as snap
        monkeypatch.setenv("GATEKEEPER_SNAPSHOT_DIR", str(tmp_path))
        compiled, lowered, cdoc = _library("K8sRequiredLabels")
        fp = footprint.certify("K8sRequiredLabels", compiled, lowered,
                               [cdoc])
        stale = dataclasses.replace(fp, version="fp-0")
        snap.save_footprint(fp.digest, stale)
        monkeypatch.setattr(footprint, "_memo", {})
        footprint.certify("K8sRequiredLabels", compiled, lowered, [cdoc])
        assert footprint.analyses_run == 2      # stale tier ignored


# ---------------------------------------------------------------------------
# selective invalidation: oracle parity under churn


def _verdicts(results):
    return sorted(
        ((r.constraint or {}).get("kind", ""),
         ((r.constraint or {}).get("metadata") or {}).get("name", ""),
         ((r.resource or {}).get("metadata") or {}).get("name", ""),
         r.msg)
        for r in results)


class TestSelectiveInvalidation:
    KINDS = ("K8sRequiredLabels", "K8sAllowedRepos", "K8sBlockNodePort")

    def _run(self, fp_mode, monkeypatch, n=80):
        import gatekeeper_tpu.engine.jax_driver as jd_mod
        monkeypatch.setenv("GATEKEEPER_FOOTPRINT", fp_mode)
        monkeypatch.setattr(jd_mod, "SMALL_WORKLOAD_EVALS", 0)
        resources = make_mixed(random.Random(3), n)
        jd = jd_mod.JaxDriver()
        c = Backend(jd).new_client([K8sValidationTarget()])
        for tdoc, cdoc in all_docs():
            kind = tdoc["spec"]["crd"]["spec"]["names"]["kind"]
            if kind in self.KINDS:
                c.add_template(tdoc)
                c.add_constraint(cdoc)
        c.add_data_batch(resources)
        opts = QueryOpts(limit_per_constraint=20)
        jd.query_audit(TARGET_NAME, QueryOpts(limit_per_constraint=20,
                                              full=True))
        jd.query_audit(TARGET_NAME, opts)       # steady state
        # churn with FRESH objects (a real watch decodes a new dict per
        # event; re-upserting the mutated stored reference would hit
        # the store's aliasing guard and dirty the wildcard root)
        # annotation-only noise on a handful of rows — outside every
        # installed template's read-set
        for i in (0, 3, 7):
            o = copy.deepcopy(resources[i])
            o.setdefault("metadata", {}).setdefault(
                "annotations", {})["fp-test"] = f"r{i}"
            c.add_data(o)
        results, _ = jd.query_audit(TARGET_NAME, opts)
        stanza = dict(jd.last_sweep_phases.get("footprint") or {})
        # an image edit lands inside K8sAllowedRepos' read-set (and
        # only its): just that kind re-sweeps
        idx = next(i for i, r in enumerate(resources)
                   if (r.get("spec") or {}).get("containers"))
        o = copy.deepcopy(resources[idx])
        o["spec"]["containers"][0]["image"] = "evil.io/fp-test:1"
        c.add_data(o)
        results2, _ = jd.query_audit(TARGET_NAME, opts)
        stanza2 = dict(jd.last_sweep_phases.get("footprint") or {})
        return (_verdicts(results), _verdicts(results2), stanza, stanza2)

    def test_oracle_parity_and_skips(self, monkeypatch):
        v_on, v2_on, stanza, stanza2 = self._run("on", monkeypatch)
        v_off, v2_off, off_stanza, _ = self._run("off", monkeypatch)
        assert v_on == v_off        # bit-identical to the oracle
        assert v2_on == v2_off
        assert stanza.get("enabled") is True
        assert off_stanza.get("enabled") is False
        if stanza.get("kinds_skipped") is not None:
            # annotation churn: every row-local kind skipped
            assert stanza["kinds_skipped"] == len(self.KINDS)
            assert stanza["evaluations_saved"] > 0
            # image churn: K8sAllowedRepos re-swept, the others not
            assert stanza2["kinds_skipped"] == len(self.KINDS) - 1
