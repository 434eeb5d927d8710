"""Stage-8 memory-surface certifier: static peak-HBM accounting, the
install-time budget gate, and certified residency/spill planning.

Covers the over-approximation contract itself (the certificate's
per-array byte claims must dominate the bytes ir/prep.build_bindings
actually materializes, checked on a table-heavy, a regex-DFA, and a
cross-row inventory-join template — and the deliberately unsound
GATEKEEPER_MEMSURFACE_TEST_UNDER seam must FAIL that check), the
install-time budget gate (warn counts ``hbm_budget_exceeded`` and
serves, strict rejects the install with a VetError, off skips
certification entirely), snapshot persistence in the "ms" tier (a warm
process re-runs zero analyses; a stale-version tier entry is
re-analyzed, not trusted), the certificate-driven devpages
ResidencyPlanner (spill/restore round-trips are bit-identical to the
always-resident mask, freed slots are reused in place when the working
set shifts, and a forced-tiny-budget churn run reproduces the
unbudgeted oracle's verdicts exactly), the driver's consumer seams
(memsurface_review_cap truncating the certified review-rung ladder,
memsurface_sweep_order's largest/smallest weave), the micro-batcher's
budget-capped batch formation, and the static cost-model prior that
un-no-ops deadline shrinking through the uncalibrated window.
"""

import copy
import random
import threading
import time

import numpy as np
import pytest

from gatekeeper_tpu.analysis import costmodel, memsurface
from gatekeeper_tpu.analysis.transval import _world_state
from gatekeeper_tpu.api.templates import compile_target_rego
from gatekeeper_tpu.client.client import Backend
from gatekeeper_tpu.client.interface import QueryOpts
from gatekeeper_tpu.engine import jax_driver as jd_mod
from gatekeeper_tpu.engine.jax_driver import JaxDriver
from gatekeeper_tpu.errors import VetError
from gatekeeper_tpu.ir import prep
from gatekeeper_tpu.ir.lower import lower_template
from gatekeeper_tpu.library import all_docs, make_mixed
from gatekeeper_tpu.target.k8s import K8sValidationTarget, TARGET_NAME


@pytest.fixture(autouse=True)
def _reset_memsurface_state(monkeypatch):
    """Certifier state is process-global (memo, registries, counters) —
    isolate every test."""
    monkeypatch.setattr(memsurface, "_memo", {})
    monkeypatch.setattr(memsurface, "surfaces", {})
    monkeypatch.setattr(memsurface, "over_budget", {})
    monkeypatch.setattr(memsurface, "analyses_run", 0)
    for var in ("GATEKEEPER_HBM_BUDGET", "GATEKEEPER_HBM_BUDGET_BYTES",
                "GATEKEEPER_MEMSURFACE_TEST_UNDER",
                "GATEKEEPER_MS_MAX_ROWS", "GATEKEEPER_MS_MAX_CONSTRAINTS",
                "GATEKEEPER_MS_MAX_TABLE", "GATEKEEPER_MS_MAX_ELEMS",
                "GATEKEEPER_MS_PROBE_N",
                "GATEKEEPER_DEVPAGES_BUDGET_BYTES",
                "GATEKEEPER_DEVPAGES", "GATEKEEPER_PAGES",
                "GATEKEEPER_PAGE_ROWS", "GATEKEEPER_COST_PRIOR_UPS",
                "GATEKEEPER_SNAPSHOT_DIR"):
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


def _driver(kinds, n_rows=40, seed=3):
    jd = JaxDriver()
    client = Backend(jd).new_client([K8sValidationTarget()])
    for tdoc, cdoc in _docs(kinds):
        client.add_template(tdoc)
        client.add_constraint(cdoc)
    client.add_data_batch(make_mixed(random.Random(seed), n_rows))
    return jd, client


KINDS = ["K8sRequiredLabels", "K8sAllowedRepos", "K8sContainerLimits"]


# ---------------------------------------------------------------------------
# the over-approximation contract: claimed bytes dominate built bytes


def _under_claims(cert, bindings) -> list[str]:
    """The probe's validation core: per modeled base name, the
    certificate's itemsize x worst-shape claim must dominate the bytes
    build_bindings actually materialized."""
    suffixes = (".v", ".p", ".B", ".bitmap")
    model_item: dict[str, int] = {}
    for name, _dcls, itemsize in cert.bindings:
        model_item[name] = max(model_item.get(name, 0), itemsize)
    grouped: dict[str, list] = {}
    for aname, arr in bindings.arrays.items():
        mname = aname
        if mname not in model_item:
            for suf in suffixes:
                if aname.endswith(suf) and aname[:-len(suf)] in model_item:
                    mname = aname[:-len(suf)]
                    break
        grouped.setdefault(mname, []).append(arr)
    under = []
    for mname, arrs in sorted(grouped.items()):
        built = sum(int(a.nbytes) for a in arrs)
        if mname not in model_item:
            under.append(f"{mname} unmodeled ({built} B built)")
            continue
        claimed = model_item[mname] * max(
            int(np.prod(a.shape)) for a in arrs)
        if claimed < built:
            under.append(f"{mname} claims {claimed} B < {built} B")
    return under


class TestOverApprox:
    def _check(self, kind, n=48, seed=11):
        compiled, lowered, cdoc = _library(kind)
        assert lowered is not None
        cert = memsurface.analyze(kind, lowered)
        assert cert.bounded
        assert cert.version == memsurface.MS_VERSION
        st, _rows, _handler = _world_state(
            make_mixed(random.Random(seed), n))
        bindings = prep.build_bindings(lowered.spec, st.table, [cdoc])
        return cert, _under_claims(cert, bindings)

    def test_table_heavy_template_dominates(self):
        cert, under = self._check("K8sContainerLimits")
        assert under == []
        # host-table companions are in the model, not just masks
        names = {n for n, _d, _i in cert.bindings}
        assert any(".ok" in n or ".v" not in n for n in names)

    def test_dfa_template_dominates(self):
        cert, under = self._check("K8sImageDigests")
        assert under == []
        assert any(n.startswith("dfa") for n, _d, _i in cert.bindings)

    def test_cross_row_join_template_dominates(self):
        cert, under = self._check("K8sUniqueIngressHost")
        assert under == []
        assert cert.has_r      # devpages terms apply to row-axis kinds

    def test_swept_claims_are_within_three_times_the_built_bytes(
            self, monkeypatch):
        """Summed over a swept driver's kinds, the resident claims at
        the geometry the sweep built cover the bytes of the built
        arrays and stay within three times them."""
        monkeypatch.setattr(jd_mod, "SMALL_WORKLOAD_EVALS", 0)
        jd, _client = _driver(KINDS, n_rows=200)
        jd.query_audit(TARGET_NAME, QueryOpts(limit_per_constraint=20,
                                              full=True))
        st = jd.state[TARGET_NAME]
        t_pad = prep.interner_bucket(len(st.table.interner))
        claimed = built = 0
        for kind in KINDS:
            b = st.bindings_cache[kind][1]
            dims = {"c": b.c_pad, "r": b.r_pad, "t": t_pad}
            if b.e_pads:
                dims["e"] = max(b.e_pads.values())
            claimed += st.memsurfaces[kind].resident_bytes(
                dims, shapes={k: a.shape for k, a in b.arrays.items()})
            built += sum(int(a.nbytes) for a in b.arrays.values())
        assert 0 < built <= claimed <= 3 * built

    def test_seeded_underclaim_fails_the_check(self, monkeypatch):
        monkeypatch.setenv("GATEKEEPER_MEMSURFACE_TEST_UNDER",
                           "K8sContainerLimits")
        cert, under = self._check("K8sContainerLimits")
        assert under                        # the harness catches it
        assert "under-claimed" in (cert.reason or "")

    def test_claim_is_monotone_in_geometry(self):
        _c, lowered, _d = _library("K8sRequiredLabels")
        cert = memsurface.analyze("K8sRequiredLabels", lowered)
        small = cert.resident_bytes({"c": 8, "r": 64})
        big = cert.resident_bytes({"c": 16, "r": 4096})
        assert 0 < small < big
        assert cert.peak_bytes({"c": 8, "r": 64}) \
            >= cert.resident_bytes({"c": 8, "r": 64})

    def test_scalar_pin_claims_nothing(self):
        cert = memsurface.scalar_surface("K8sAgileLabels")
        assert cert.scalar_pin and cert.bounded
        assert cert.peak_bytes() == 0
        assert memsurface.budget_reason(cert) is None

    def test_budget_reason_fires_under_tiny_budget(self, monkeypatch):
        _c, lowered, _d = _library("K8sRequiredLabels")
        cert = memsurface.analyze("K8sRequiredLabels", lowered)
        assert memsurface.budget_reason(cert) is None
        monkeypatch.setenv("GATEKEEPER_HBM_BUDGET_BYTES", "1024")
        reason = memsurface.budget_reason(cert)
        assert reason is not None
        assert reason.startswith("hbm_budget_exceeded")


# ---------------------------------------------------------------------------
# the install-time budget gate (driver integration)


class TestBudgetGate:
    def test_warn_counts_breaches_and_serves(self, monkeypatch):
        monkeypatch.setenv("GATEKEEPER_HBM_BUDGET", "warn")
        monkeypatch.setenv("GATEKEEPER_HBM_BUDGET_BYTES", "4096")
        jd, _client = _driver(KINDS)
        if jd.scalar_only:
            pytest.skip("device backend unavailable")
        st = jd.state[TARGET_NAME]
        assert set(st.memsurfaces) == set(KINDS)
        assert jd.metrics.counter("hbm_budget_exceeded").value \
            >= len(KINDS)
        assert memsurface.over_budget
        for reason in memsurface.over_budget.values():
            assert "hbm_budget_exceeded" in reason
        # warn serves: the sweep still runs against every template
        results, _trace = jd.query_audit(TARGET_NAME,
                                         QueryOpts(full=True))
        assert results

    def test_strict_rejects_the_install(self, monkeypatch):
        monkeypatch.setenv("GATEKEEPER_HBM_BUDGET", "strict")
        monkeypatch.setenv("GATEKEEPER_HBM_BUDGET_BYTES", "4096")
        jd = JaxDriver()
        if jd.scalar_only:
            pytest.skip("device backend unavailable")
        client = Backend(jd).new_client([K8sValidationTarget()])
        tdoc, _cdoc = _docs(["K8sRequiredLabels"])[0]
        with pytest.raises(VetError, match="hbm_budget_exceeded"):
            client.add_template(tdoc)
        st = jd.state[TARGET_NAME]
        assert st.memsurfaces.get("K8sRequiredLabels") is None

    def test_strict_within_budget_installs(self, monkeypatch):
        monkeypatch.setenv("GATEKEEPER_HBM_BUDGET", "strict")
        jd, _client = _driver(KINDS)     # default 16 GiB budget
        if jd.scalar_only:
            pytest.skip("device backend unavailable")
        st = jd.state[TARGET_NAME]
        assert set(st.memsurfaces) == set(KINDS)
        assert not memsurface.over_budget

    def test_off_skips_certification(self, monkeypatch):
        monkeypatch.setenv("GATEKEEPER_HBM_BUDGET", "off")
        jd, _client = _driver(KINDS)
        if jd.scalar_only:
            pytest.skip("device backend unavailable")
        st = jd.state[TARGET_NAME]
        assert not st.memsurfaces
        assert memsurface.analyses_run == 0


# ---------------------------------------------------------------------------
# memo + snapshot persistence ("ms" tier)


class TestPersistence:
    def test_memo_runs_one_analysis(self):
        compiled, lowered, _ = _library("K8sRequiredLabels")
        a = memsurface.certify("K8sRequiredLabels", compiled, lowered)
        b = memsurface.certify("K8sRequiredLabels", compiled, lowered)
        assert a == b
        assert memsurface.analyses_run == 1
        assert memsurface.surface_for("K8sRequiredLabels") == a

    def test_snapshot_roundtrip_warm_zero_analyses(self, tmp_path,
                                                   monkeypatch):
        monkeypatch.setenv("GATEKEEPER_SNAPSHOT_DIR", str(tmp_path))
        compiled, lowered, _ = _library("K8sRequiredLabels")
        cold = memsurface.certify("K8sRequiredLabels", compiled, lowered)
        assert memsurface.analyses_run == 1
        # simulate a restart: wipe the in-process memo, keep the tier
        monkeypatch.setattr(memsurface, "_memo", {})
        monkeypatch.setattr(memsurface, "analyses_run", 0)
        warm = memsurface.certify("K8sRequiredLabels", compiled, lowered)
        assert warm == cold
        assert memsurface.analyses_run == 0

    def test_version_mismatch_reanalyzes(self, tmp_path, monkeypatch):
        import dataclasses

        from gatekeeper_tpu.resilience import snapshot as snap
        monkeypatch.setenv("GATEKEEPER_SNAPSHOT_DIR", str(tmp_path))
        compiled, lowered, _ = _library("K8sRequiredLabels")
        cold = memsurface.certify("K8sRequiredLabels", compiled, lowered)
        # poison the tier with a prior-version certificate: an analyzer
        # fix must re-run, never trust a stale claim
        digest = memsurface.surface_digest(lowered)
        snap.save_memsurface(
            digest, dataclasses.replace(cold, version="ms-0"))
        monkeypatch.setattr(memsurface, "_memo", {})
        monkeypatch.setattr(memsurface, "analyses_run", 0)
        warm = memsurface.certify("K8sRequiredLabels", compiled, lowered)
        assert memsurface.analyses_run == 1
        assert warm.version == memsurface.MS_VERSION

    def test_seam_bypasses_memo_and_snapshot(self, tmp_path,
                                             monkeypatch):
        monkeypatch.setenv("GATEKEEPER_SNAPSHOT_DIR", str(tmp_path))
        compiled, lowered, _ = _library("K8sRequiredLabels")
        honest = memsurface.certify("K8sRequiredLabels", compiled,
                                    lowered)
        assert memsurface.analyses_run == 1
        monkeypatch.setenv("GATEKEEPER_MEMSURFACE_TEST_UNDER",
                           "K8sRequiredLabels")
        seeded = memsurface.certify("K8sRequiredLabels", compiled,
                                    lowered)
        # the unsound certificate reached the caller (not a cached
        # honest one) and did NOT poison memo or tier
        assert "under-claimed" in (seeded.reason or "")
        assert memsurface.analyses_run == 2
        monkeypatch.delenv("GATEKEEPER_MEMSURFACE_TEST_UNDER")
        again = memsurface.certify("K8sRequiredLabels", compiled,
                                   lowered)
        assert again == honest
        assert memsurface.analyses_run == 2


# ---------------------------------------------------------------------------
# certificate-driven devpages residency planning (the spill ladder)


class _Ex:
    """Stub of the executor's host->device row-scatter seam."""

    @staticmethod
    def _scatter_rows(name, full, host, rows, accum, axis=1):
        import jax.numpy as jnp
        return full.at[:, rows].set(jnp.asarray(host[:, rows]))


class TestResidencyPlanner:
    def _planner(self, budget=256, c_pad=8, r_pad=128, page_rows=16):
        from gatekeeper_tpu.enforce.devpages import ResidencyPlanner
        return ResidencyPlanner(budget, c_pad, r_pad, page_rows)

    def test_spill_restore_roundtrip_is_bit_identical(self):
        import jax.numpy as jnp
        p = self._planner()
        assert p.active and p.n_slots < p.n_pages
        rng = np.random.RandomState(7)
        host = rng.rand(8, 128) < 0.1
        p.touch(range(p.n_pages))
        p.store(jnp.asarray(host))
        assert p.spills == p.n_pages - p.n_slots
        full = np.asarray(p.expand(_Ex()))
        assert np.array_equal(full, host)
        assert p.restores > 0

    def test_all_zero_pages_restore_for_free(self):
        import jax.numpy as jnp
        p = self._planner()
        host = np.zeros((8, 128), dtype=bool)
        host[3, 5] = True               # one bit, in a hot page
        p.touch([0])
        p.store(jnp.asarray(host))
        full = np.asarray(p.expand(_Ex()))
        assert np.array_equal(full, host)
        # every spilled page is all-zero: zero restore scatters
        assert p.restores == 0

    def test_freed_slots_are_reused_on_working_set_shift(self):
        import jax.numpy as jnp
        p = self._planner()
        rng = np.random.RandomState(8)
        host1 = rng.rand(8, 128) < 0.1
        p.touch(range(p.n_pages))       # hot set = highest pages
        p.store(jnp.asarray(host1))
        spills1 = p.spills
        hot1 = set(p.slot_of)
        # shift the working set to the lowest pages: the leavers' slots
        # must be reused in place, never grown past n_slots
        host2 = rng.rand(8, 128) < 0.1
        p.touch(range(p.n_slots))
        p.store(jnp.asarray(host2))
        assert set(p.slot_of) == set(range(p.n_slots)) != hot1
        assert set(p.slot_of.values()) <= set(range(p.n_slots))
        assert len(p.free) + len(p.slot_of) == p.n_slots
        assert p.spills > spills1       # the leavers spilled
        full = np.asarray(p.expand(_Ex()))
        assert np.array_equal(full, host2)

    def test_inactive_when_claim_fits_budget(self):
        p = self._planner(budget=1 << 30)
        assert not p.active


class TestResidencySweepParity:
    """Forced-tiny-budget churn run vs the always-resident oracle:
    verdicts must be bit-identical every round while pages actually
    spill and restore."""

    KINDS = ("K8sRequiredLabels", "K8sAllowedRepos", "K8sBlockNodePort")

    def _mk_client(self, kinds):
        jd = JaxDriver()
        c = Backend(jd).new_client([K8sValidationTarget()])
        for tdoc, cdoc in all_docs():
            if tdoc["spec"]["crd"]["spec"]["names"]["kind"] in kinds:
                c.add_template(tdoc)
                c.add_constraint(cdoc)
        return jd, c

    @staticmethod
    def _verdicts(results):
        out = []
        for r in results:
            obj = (r.review or {}).get("object") or {}
            out.append(
                ((r.constraint or {}).get("kind", ""),
                 ((r.constraint or {}).get("metadata") or {}).get(
                     "name", ""),
                 (obj.get("metadata") or {}).get("name", ""), r.msg))
        return sorted(out)

    def _leg(self, monkeypatch, resources, churn_rounds, budget):
        if budget is None:
            monkeypatch.delenv("GATEKEEPER_DEVPAGES_BUDGET_BYTES",
                               raising=False)
        else:
            monkeypatch.setenv("GATEKEEPER_DEVPAGES_BUDGET_BYTES",
                               str(budget))
        jd, c = self._mk_client(self.KINDS)
        if jd.scalar_only:
            pytest.skip("device backend unavailable")
        c.add_data_batch(copy.deepcopy(resources))
        opts = QueryOpts(limit_per_constraint=10_000)
        rounds = [self._verdicts(jd.query_audit(TARGET_NAME, opts)[0])]
        for batch in churn_rounds:
            for o in batch:
                c.add_data(copy.deepcopy(o))
            rounds.append(
                self._verdicts(jd.query_audit(TARGET_NAME, opts)[0]))
        st = jd._state(TARGET_NAME)
        planners = [kp.resident for kp in st.devpages.values()
                    if getattr(kp, "resident", None) is not None
                    and kp.resident.active]
        return (rounds, sum(p.spills for p in planners),
                sum(p.restores for p in planners), planners)

    def test_tiny_budget_matches_oracle_with_spills(self, monkeypatch):
        monkeypatch.setattr(jd_mod, "SMALL_WORKLOAD_EVALS", 0)
        monkeypatch.setenv("GATEKEEPER_DEVPAGES", "on")
        monkeypatch.setenv("GATEKEEPER_PAGES", "on")
        monkeypatch.setenv("GATEKEEPER_PAGE_ROWS", "16")
        resources = make_mixed(random.Random(5), 60)
        pods = [o for o in resources
                if (o.get("spec") or {}).get("containers")]
        rng = random.Random(17)
        churn_rounds = []
        for j in range(3):
            batch = []
            for o in rng.sample(pods, 3):
                o = copy.deepcopy(o)
                o["spec"]["containers"][0]["image"] = \
                    f"evil.io/memsurface:{j}"
                batch.append(o)
            churn_rounds.append(batch)
        want, o_spills, _o_restores, o_planners = self._leg(
            monkeypatch, resources, churn_rounds, None)
        got, spills, restores, planners = self._leg(
            monkeypatch, resources, churn_rounds, 256)
        assert got == want                   # bit-identical verdicts
        assert not o_planners and o_spills == 0
        assert planners and spills > 0 and restores > 0


# ---------------------------------------------------------------------------
# driver consumer seams: review-rung cap + sweep-order weave


class TestDriverConsumers:
    def test_review_cap_truncates_the_rung_ladder(self, monkeypatch):
        jd, _client = _driver(KINDS)
        if jd.scalar_only:
            pytest.skip("device backend unavailable")
        assert jd.certified_review_rungs(TARGET_NAME, 64) \
            == [1, 8, 16, 32, 64]
        # a budget the installed set alone exhausts: only singleton
        # review dispatches are certified to fit
        monkeypatch.setenv("GATEKEEPER_HBM_BUDGET_BYTES", "100000")
        assert jd.certified_review_rungs(TARGET_NAME, 64) == [1]
        # stage off: no memory cap, the Stage-7 ladder stands
        monkeypatch.setenv("GATEKEEPER_HBM_BUDGET", "off")
        assert jd.certified_review_rungs(TARGET_NAME, 64) \
            == [1, 8, 16, 32, 64]

    def test_sweep_order_weaves_largest_smallest(self, monkeypatch):
        jd, _client = _driver(KINDS)
        if jd.scalar_only:
            pytest.skip("device backend unavailable")
        st = jd.state[TARGET_NAME]
        order = jd.memsurface_sweep_order(st, list(KINDS))
        assert sorted(order) == sorted(KINDS)
        peaks = {k: st.memsurfaces[k].peak_bytes() for k in KINDS}
        assert peaks[order[0]] == max(peaks.values())
        assert peaks[order[1]] == min(peaks.values())
        assert jd.metrics.counter("memsurface_sweep_reorders").value == 1
        # off: deterministic sorted order, no counter
        monkeypatch.setenv("GATEKEEPER_HBM_BUDGET", "off")
        assert jd.memsurface_sweep_order(st, list(KINDS)) \
            == sorted(KINDS)
        assert jd.metrics.counter("memsurface_sweep_reorders").value == 1

    def test_sweep_order_sorted_below_three_kinds(self):
        jd, _client = _driver(KINDS[:2])
        if jd.scalar_only:
            pytest.skip("device backend unavailable")
        st = jd.state[TARGET_NAME]
        assert jd.memsurface_sweep_order(st, list(KINDS[:2])) \
            == sorted(KINDS[:2])


# ---------------------------------------------------------------------------
# the micro-batcher's budget-capped batch formation


class _FakePending:
    def __init__(self, deadline):
        self.request = {}
        self.ctx = None
        self.deadline = deadline
        self.withdrawn = False
        self.error = None
        self.response = None
        self.event = threading.Event()


class TestBatcherBudgetCap:
    def _batcher(self, rungs):
        from gatekeeper_tpu.webhook.batcher import MicroBatcher
        return MicroBatcher(
            evaluate_batch=lambda reqs: [None] * len(reqs),
            max_batch=64,
            certified_rungs=(lambda: rungs) if rungs is not None
            else None)

    def test_formation_caps_at_top_certified_rung(self):
        mb = self._batcher([1, 8])
        mb._queue = [_FakePending(time.monotonic() + 5.0)
                     for _ in range(20)]
        take = mb._take_batch(time.monotonic())
        assert len(take) == 8           # the budget-fitted rung
        assert mb.depth() == 12         # the tail stays queued
        assert mb.metrics.snapshot().get(
            "admission_batch_budget_caps") == 1

    def test_no_cap_without_certificates(self):
        mb = self._batcher(None)
        mb._queue = [_FakePending(time.monotonic() + 5.0)
                     for _ in range(20)]
        assert len(mb._take_batch(time.monotonic())) == 20
        assert "admission_batch_budget_caps" not in \
            mb.metrics.snapshot()

    def test_no_counter_when_queue_fits_the_rung(self):
        mb = self._batcher([1, 8])
        mb._queue = [_FakePending(time.monotonic() + 5.0)
                     for _ in range(5)]
        assert len(mb._take_batch(time.monotonic())) == 5
        assert "admission_batch_budget_caps" not in \
            mb.metrics.snapshot()


# ---------------------------------------------------------------------------
# the static cost-model prior (deadline shrinking pre-calibration)


class TestCostPrior:
    def test_prior_seeds_the_uncalibrated_window(self):
        costmodel.reset_calibration()
        assert costmodel.current_scale() == 0.0
        assert costmodel.prior_scale() > 0.0
        assert costmodel.effective_scale() \
            == pytest.approx(costmodel.prior_scale())

    def test_prior_env_override_and_disable(self, monkeypatch):
        costmodel.reset_calibration()
        monkeypatch.setenv("GATEKEEPER_COST_PRIOR_UPS", "1e6")
        assert costmodel.prior_scale() == pytest.approx(1e-6)
        monkeypatch.setenv("GATEKEEPER_COST_PRIOR_UPS", "0")
        assert costmodel.prior_scale() == 0.0
        assert costmodel.effective_scale() == 0.0

    def test_fitted_scale_wins_over_prior(self):
        costmodel.reset_calibration()
        try:
            costmodel.record_sample(1e6, 2.0)
            assert costmodel.effective_scale() \
                == pytest.approx(costmodel.current_scale())
            assert costmodel.current_scale() != costmodel.prior_scale()
        finally:
            costmodel.reset_calibration()

    def test_uncalibrated_predictor_has_an_opinion(self):
        jd, _client = _driver(KINDS)
        if jd.scalar_only:
            pytest.skip("device backend unavailable")
        costmodel.reset_calibration()
        pred = jd.predict_review_batch_seconds(TARGET_NAME, 16)
        assert pred is not None and pred > 0.0

    def test_deadline_shrink_no_longer_noops_uncalibrated(self):
        """The regression the prior fixes: an uncalibrated predictor
        used to return None, so _fit_to_deadline passed a
        deadline-doomed batch through untouched."""
        from gatekeeper_tpu.webhook.batcher import MicroBatcher
        jd, _client = _driver(KINDS)
        if jd.scalar_only:
            pytest.skip("device backend unavailable")
        costmodel.reset_calibration()
        mb = MicroBatcher(
            evaluate_batch=lambda reqs: [None] * len(reqs),
            max_batch=64,
            predict_seconds=lambda n: jd.predict_review_batch_seconds(
                TARGET_NAME, n))
        # a deadline the prior-priced batch provably cannot make
        take = [_FakePending(time.monotonic() + 1e-9)
                for _ in range(20)]
        keep = mb._fit_to_deadline(take)
        assert len(keep) < 20
