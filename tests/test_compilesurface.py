"""Stage-7 compile-surface certifier: static signature enumeration,
snapshot persistence, AOT precompilation, and the retrace sentinel.

Covers the abstract interpreter's certificate shape (row-local
templates compose finite pad-geometry ladders; the deployment caps
bound every input-driven axis; an unmappable binding rejects the
surface as unbounded), the pad-geometry generator table itself
(ir/prep.bucket_ladder + binding_dim_classes), snapshot persistence in
the "cs" tier (a warm process re-runs zero analyses; the AOT geometry
stamp suppresses the startup compile storm), the certified review
rungs the micro-batcher shrinks along, the batcher's rung-ladder
deadline shrink, and the dispatch-time sentinel: an uncertified
signature (an oversized review batch under a shrunk row cap) is
counted + flight-recorded and served in warn mode, refused with
UncertifiedRetrace under strict, while the certified steady sweep
dispatches with the counter at zero.
"""

import random
import threading
import time

import pytest

from gatekeeper_tpu.analysis import compilesurface
from gatekeeper_tpu.api.templates import compile_target_rego
from gatekeeper_tpu.client.client import Backend
from gatekeeper_tpu.client.interface import QueryOpts
from gatekeeper_tpu.engine import jax_driver as jd_mod
from gatekeeper_tpu.engine.jax_driver import JaxDriver
from gatekeeper_tpu.ir import prep
from gatekeeper_tpu.ir.lower import lower_template
from gatekeeper_tpu.library import all_docs, make_mixed
from gatekeeper_tpu.target.k8s import K8sValidationTarget, TARGET_NAME


@pytest.fixture(autouse=True)
def _reset_compilesurface_state(monkeypatch):
    """Certifier state is process-global (memo, registries, counters) —
    isolate every test."""
    monkeypatch.setattr(compilesurface, "_memo", {})
    monkeypatch.setattr(compilesurface, "surfaces", {})
    monkeypatch.setattr(compilesurface, "unbounded", {})
    monkeypatch.setattr(compilesurface, "_registry", {})
    monkeypatch.setattr(compilesurface, "analyses_run", 0)
    monkeypatch.setattr(compilesurface, "precompiles_run", 0)
    monkeypatch.setattr(compilesurface, "uncertified_total", 0)
    for var in ("GATEKEEPER_COMPILE_SURFACE",
                "GATEKEEPER_CS_TEST_UNBOUNDED",
                "GATEKEEPER_CS_MAX_ROWS", "GATEKEEPER_CS_MAX_CONSTRAINTS",
                "GATEKEEPER_CS_MAX_TABLE", "GATEKEEPER_CS_MAX_ELEMS",
                "GATEKEEPER_SNAPSHOT_DIR", "GATEKEEPER_SHARDS"):
        monkeypatch.delenv(var, raising=False)
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


def _docs(kinds):
    by_kind = {t["spec"]["crd"]["spec"]["names"]["kind"]: (t, c)
               for t, c in all_docs()}
    return [by_kind[k] for k in kinds]


# ---------------------------------------------------------------------------
# the pad-geometry generator table (ir/prep.py)


class TestGenerators:
    def test_bucket_ladder_is_the_pow2_ladder(self):
        assert prep.bucket_ladder(8, 64) == (8, 16, 32, 64)
        assert prep.bucket_ladder(4, 4) == (4,)
        # minimum above the cap: empty ladder (the analyzer rejects)
        assert prep.bucket_ladder(8, 4) == ()
        # non-pow2 minimum rounds up to the next rung
        assert prep.bucket_ladder(5, 32) == (8, 16, 32)

    def test_framework_bindings_classify(self):
        assert prep.binding_dim_classes("__match__") == ("c", "r")
        assert prep.binding_dim_classes("__alive__") == ("r",)
        assert prep.binding_dim_classes("__cvalid__") == ("c",)
        assert prep.binding_dim_classes("__pagetable__") == ("r",)

    def test_request_bindings_classify(self):
        assert prep.binding_dim_classes("r:spec.replicas.v") == ("r",)
        assert prep.binding_dim_classes("t0") == ("t",)
        assert prep.binding_dim_classes("dfa0.trans") == ("static", "static")
        assert prep.binding_dim_classes("dfa0.xv") == ("t",)
        assert prep.binding_dim_classes("__strbytes__") == ("t", "static")
        assert prep.binding_dim_classes("cs0.vmap") == ("t",)

    def test_unknown_binding_raises(self):
        with pytest.raises(ValueError):
            prep.binding_dim_classes("__no_such_binding__")

    def test_every_ladder_value_is_a_reachable_pad(self):
        # soundness spot-check: bucket() output always lands on a rung
        for n in (1, 7, 8, 9, 100, 1000):
            assert prep.bucket(n) in prep.bucket_ladder(8, 1 << 22)


# ---------------------------------------------------------------------------
# the abstract interpreter: certificate shape


class TestAnalyzer:
    def test_row_local_template_is_bounded(self):
        _c, lowered, _ = _library("K8sRequiredLabels")
        cert = compilesurface.analyze("K8sRequiredLabels", lowered)
        assert cert.bounded
        assert cert.version == compilesurface.CS_VERSION
        assert cert.n_signatures > 0
        classes = {cls for cls, _lo, _cap, _n in cert.axes}
        assert classes <= {"r", "c", "t", "e"}
        assert "r" in classes and "c" in classes
        # a resource axis brings the devpages delta-width variants
        assert cert.delta_rungs > 0
        # every enumerated binding carries its per-dim generators
        names = {n for n, _cls in cert.bindings}
        assert {"__alive__", "__match__", "__rank__"} <= names

    def test_library_keeps_forty_five_templates_certified(self):
        certs = []
        for tdoc, _cdoc in all_docs():
            kind = tdoc["spec"]["crd"]["spec"]["names"]["kind"]
            _c, lowered, _ = _library(kind)
            certs.append(compilesurface.analyze(kind, lowered))
        assert sum(c.bounded and not c.scalar_pin for c in certs) >= 45
        assert not [c for c in certs if not c.bounded]

    def test_caps_bound_the_signature_count(self, monkeypatch):
        _c, lowered, _ = _library("K8sRequiredLabels")
        full = compilesurface.analyze("K8sRequiredLabels", lowered)
        monkeypatch.setenv("GATEKEEPER_CS_MAX_ROWS", "64")
        shrunk = compilesurface.analyze("K8sRequiredLabels", lowered)
        assert shrunk.bounded
        assert shrunk.n_signatures < full.n_signatures
        r_axis = {cls: n for cls, _lo, _cap, n in shrunk.axes}
        assert r_axis["r"] == 4          # 8, 16, 32, 64

    def test_cap_below_pad_minimum_is_unbounded(self, monkeypatch):
        monkeypatch.setenv("GATEKEEPER_CS_MAX_ROWS", "4")
        _c, lowered, _ = _library("K8sRequiredLabels")
        cert = compilesurface.analyze("K8sRequiredLabels", lowered)
        assert not cert.bounded
        assert "compile_surface_unbounded" in (cert.reason or "")

    def test_digest_pins_program_and_caps(self, monkeypatch):
        _c, lowered, _ = _library("K8sRequiredLabels")
        _c2, lowered2, _ = _library("K8sAllowedRepos")
        assert compilesurface.surface_digest(lowered) \
            == compilesurface.surface_digest(lowered)
        assert compilesurface.surface_digest(lowered) \
            != compilesurface.surface_digest(lowered2)
        before = compilesurface.surface_digest(lowered)
        monkeypatch.setenv("GATEKEEPER_CS_MAX_ROWS", "64")
        assert compilesurface.surface_digest(lowered) != before

    def test_scalar_surface_is_a_pin(self):
        cert = compilesurface.scalar_surface("K8sAgileLabels")
        assert cert.bounded and cert.scalar_pin
        assert cert.n_signatures == 0

    def test_seeded_unbounded_seam(self, monkeypatch):
        monkeypatch.setenv("GATEKEEPER_CS_TEST_UNBOUNDED",
                           "K8sRequiredLabels")
        _c, lowered, _ = _library("K8sRequiredLabels")
        cert = compilesurface.analyze("K8sRequiredLabels", lowered)
        assert not cert.bounded


# ---------------------------------------------------------------------------
# memo + snapshot persistence ("cs" tier)


class TestPersistence:
    def test_memo_runs_one_analysis(self):
        compiled, lowered, _ = _library("K8sRequiredLabels")
        a = compilesurface.certify("K8sRequiredLabels", compiled, lowered)
        b = compilesurface.certify("K8sRequiredLabels", compiled, lowered)
        assert a == b
        assert compilesurface.analyses_run == 1
        assert compilesurface.surface_for("K8sRequiredLabels") == a

    def test_snapshot_roundtrip_warm_zero_analyses(self, tmp_path,
                                                   monkeypatch):
        monkeypatch.setenv("GATEKEEPER_SNAPSHOT_DIR", str(tmp_path))
        compiled, lowered, _ = _library("K8sRequiredLabels")
        cold = compilesurface.certify("K8sRequiredLabels", compiled,
                                      lowered)
        assert compilesurface.analyses_run == 1
        # simulate a restart: wipe the in-process memo, keep the tier
        monkeypatch.setattr(compilesurface, "_memo", {})
        monkeypatch.setattr(compilesurface, "analyses_run", 0)
        warm = compilesurface.certify("K8sRequiredLabels", compiled,
                                      lowered)
        assert warm == cold
        assert compilesurface.analyses_run == 0

    def test_unbounded_certs_are_not_persisted(self, tmp_path,
                                               monkeypatch):
        monkeypatch.setenv("GATEKEEPER_SNAPSHOT_DIR", str(tmp_path))
        monkeypatch.setenv("GATEKEEPER_CS_TEST_UNBOUNDED",
                           "K8sRequiredLabels")
        compiled, lowered, _ = _library("K8sRequiredLabels")
        cert = compilesurface.certify("K8sRequiredLabels", compiled,
                                      lowered)
        assert not cert.bounded
        # the honest re-analysis must not find a poisoned cache entry
        monkeypatch.delenv("GATEKEEPER_CS_TEST_UNBOUNDED")
        monkeypatch.setattr(compilesurface, "_memo", {})
        honest = compilesurface.certify("K8sRequiredLabels", compiled,
                                        lowered)
        assert honest.bounded


# ---------------------------------------------------------------------------
# driver integration: install-time certification, AOT precompile,
# certified review rungs


def _driver(kinds, n_rows=40, seed=3):
    jd = JaxDriver()
    client = Backend(jd).new_client([K8sValidationTarget()])
    for tdoc, cdoc in _docs(kinds):
        client.add_template(tdoc)
        client.add_constraint(cdoc)
    client.add_data_batch(make_mixed(random.Random(seed), n_rows))
    return jd, client


KINDS = ["K8sRequiredLabels", "K8sAllowedRepos", "K8sContainerLimits"]


class TestDriver:
    def test_install_publishes_certificates(self):
        jd, _client = _driver(KINDS)
        if jd.scalar_only:
            pytest.skip("device backend unavailable")
        st = jd.state[TARGET_NAME]
        assert set(st.compilesurfaces) == set(KINDS)
        for cert in st.compilesurfaces.values():
            assert cert.bounded and not cert.scalar_pin

    def test_prepare_audit_precompiles_then_warm_skips(self, tmp_path,
                                                       monkeypatch):
        monkeypatch.setenv("GATEKEEPER_SNAPSHOT_DIR", str(tmp_path))
        # the conftest 8-device virtual CPU mesh would route through the
        # sharded executor, whose prewarm path owns its own compile
        # amortisation — AOT precompile is the unsharded oracle's seam
        monkeypatch.setenv("GATEKEEPER_SHARDS", "1")
        jd, _client = _driver(KINDS)
        if jd.scalar_only:
            pytest.skip("device backend unavailable")
        jd.prepare_audit(TARGET_NAME)
        cold = compilesurface.precompiles_run
        assert cold == len(KINDS)
        # same geometry in a fresh driver: the cs-tier stamp suppresses
        # the AOT storm entirely
        jd2, _client2 = _driver(KINDS)
        jd2.prepare_audit(TARGET_NAME)
        assert compilesurface.precompiles_run == cold

    def test_certified_review_rungs_follow_the_ladder(self, monkeypatch):
        monkeypatch.setenv("GATEKEEPER_CS_MAX_ROWS", "4096")
        jd, client = _driver(KINDS)
        if jd.scalar_only:
            pytest.skip("device backend unavailable")
        assert jd.certified_review_rungs(TARGET_NAME, 64) \
            == [1, 8, 16, 32, 64]
        assert client.certified_review_rungs(64) == [1, 8, 16, 32, 64]
        # uncapped: the whole ladder up to the row cap
        assert jd.certified_review_rungs(TARGET_NAME)[-1] == 4096

    def test_rungs_are_none_when_surface_unbounded(self, monkeypatch):
        monkeypatch.setenv("GATEKEEPER_CS_TEST_UNBOUNDED",
                           "K8sAllowedRepos")
        jd, client = _driver(KINDS)
        if jd.scalar_only:
            pytest.skip("device backend unavailable")
        assert jd.certified_review_rungs(TARGET_NAME, 64) is None
        assert client.certified_review_rungs(64) is None

    def test_rungs_are_none_when_stage_off(self, monkeypatch):
        monkeypatch.setenv("GATEKEEPER_COMPILE_SURFACE", "off")
        jd, _client = _driver(KINDS)
        if jd.scalar_only:
            pytest.skip("device backend unavailable")
        assert jd.certified_review_rungs(TARGET_NAME, 64) is None


# ---------------------------------------------------------------------------
# the dispatch-time retrace sentinel


class TestSentinel:
    def _seeded_uncertified(self, monkeypatch, mode):
        """A 200-review batch under GATEKEEPER_CS_MAX_ROWS=64 pads its
        review mini-table to 256 rows — a signature provably outside
        every installed certificate."""
        monkeypatch.setenv("GATEKEEPER_CS_MAX_ROWS", "64")
        monkeypatch.setenv("GATEKEEPER_COMPILE_SURFACE", mode)
        monkeypatch.setattr(jd_mod, "REVIEW_BATCH_MIN_EVALS", 1)
        jd, client = _driver(KINDS)
        reviews = [{"object": o, "operation": "CREATE"}
                   for o in make_mixed(random.Random(7), 200)]
        return jd, client, reviews

    def test_warn_counts_records_and_serves(self, monkeypatch):
        from gatekeeper_tpu.obs import flightrecorder as fr
        jd, client, reviews = self._seeded_uncertified(monkeypatch,
                                                       "warn")
        if jd.scalar_only:
            pytest.skip("device backend unavailable")
        rec = fr.FlightRecorder(ring=256)
        monkeypatch.setattr(fr, "_recorder", rec)
        results = jd.query_review_batch(TARGET_NAME, reviews,
                                        QueryOpts())
        assert len(results) == len(reviews)     # served, not refused
        assert jd.executor.retrace_uncertified > 0
        assert jd.metrics.counter("retrace_uncertified_total").value \
            == jd.executor.retrace_uncertified
        assert compilesurface.uncertified_total \
            == jd.executor.retrace_uncertified
        evs = [e for e in rec.snapshot()
               if e["type"] == "retrace_uncertified"]
        assert evs and evs[0]["mode"] == "warn"

    def test_strict_refuses_the_dispatch(self, monkeypatch):
        jd, client, reviews = self._seeded_uncertified(monkeypatch,
                                                       "strict")
        if jd.scalar_only:
            pytest.skip("device backend unavailable")
        with pytest.raises(compilesurface.UncertifiedRetrace):
            jd.query_review_batch(TARGET_NAME, reviews, QueryOpts())
        assert jd.executor.retrace_uncertified > 0

    def test_certified_sweep_is_clean_under_strict(self, monkeypatch):
        monkeypatch.setenv("GATEKEEPER_COMPILE_SURFACE", "strict")
        jd, client = _driver(KINDS)
        if jd.scalar_only:
            pytest.skip("device backend unavailable")
        jd.prepare_audit(TARGET_NAME)
        results, _trace = jd.query_audit(TARGET_NAME, QueryOpts(full=True))
        jd.query_audit(TARGET_NAME, QueryOpts(full=True))
        assert results
        assert jd.executor.retrace_uncertified == 0

    def test_dispatch_membership_function(self, monkeypatch):
        import numpy as np
        jd, _client = _driver(KINDS)
        if jd.scalar_only:
            pytest.skip("device backend unavailable")
        st = jd.state[TARGET_NAME]
        kind = KINDS[0]
        program = st.templates[kind].vectorized.program
        b = jd._kind_bindings(st, kind, st.templates[kind],
                              st.constraints[kind])
        assert compilesurface.dispatch_certified(program, b.arrays)
        # an off-ladder (non-pow2) row axis is outside the surface
        bad = dict(b.arrays)
        bad["__alive__"] = np.ones(7, dtype=bool)
        assert not compilesurface.dispatch_certified(program, bad)
        # an unregistered program makes no membership claim
        monkeypatch.setattr(compilesurface, "_registry", {})
        assert compilesurface.dispatch_certified(program, bad)


# ---------------------------------------------------------------------------
# the micro-batcher's certified rung shrink


class _FakePending:
    def __init__(self, deadline):
        self.request = {}
        self.ctx = None
        self.deadline = deadline
        self.withdrawn = False
        self.error = None
        self.response = None
        self.event = threading.Event()


class TestBatcherRungs:
    def _batcher(self, rungs):
        from gatekeeper_tpu.webhook.batcher import MicroBatcher
        # predictor: anything past 16 reviews blows the budget
        return MicroBatcher(
            evaluate_batch=lambda reqs: [None] * len(reqs),
            max_batch=64,
            predict_seconds=lambda n: 10.0 if n > 16 else 0.001,
            certified_rungs=(lambda: rungs) if rungs is not None
            else None)

    def test_shrink_steps_down_the_certified_ladder(self):
        mb = self._batcher([1, 8, 16, 32])
        take = [_FakePending(time.monotonic() + 1.0) for _ in range(20)]
        keep = mb._fit_to_deadline(take)
        # 20 -> 16 (the next rung down), NOT 20 -> 10 (blind halving)
        assert len(keep) == 16
        assert mb.depth() == 4          # the tail re-queued
        snap = mb.metrics.snapshot()
        assert snap.get("admission_batch_rung_shrinks") == 1
        assert snap.get("admission_batch_deadline_shrinks") == 1

    def test_without_certificates_shrink_halves(self):
        mb = self._batcher(None)
        take = [_FakePending(time.monotonic() + 1.0) for _ in range(20)]
        keep = mb._fit_to_deadline(take)
        assert len(keep) == 10          # 20 -> 10: the blind fallback
        assert "admission_batch_rung_shrinks" not in mb.metrics.snapshot()

    def test_no_rung_below_collapses_to_singleton(self):
        mb = self._batcher([32, 64])
        # predictor never fits: walk to the floor
        mb.predict_seconds = lambda n: 10.0
        take = [_FakePending(time.monotonic() + 1.0) for _ in range(20)]
        keep = mb._fit_to_deadline(take)
        assert len(keep) == 1
        assert mb.depth() == 19

    def test_broken_rung_provider_is_advisory(self):
        def boom():
            raise RuntimeError("provider down")
        from gatekeeper_tpu.webhook.batcher import MicroBatcher
        mb = MicroBatcher(
            evaluate_batch=lambda reqs: [None] * len(reqs),
            predict_seconds=lambda n: 10.0 if n > 16 else 0.001,
            certified_rungs=boom)
        take = [_FakePending(time.monotonic() + 1.0) for _ in range(20)]
        keep = mb._fit_to_deadline(take)
        assert len(keep) == 10          # falls back to halving
