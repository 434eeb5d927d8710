"""Backend supervisor + warm-restart snapshot unit tests.

Deterministic by construction: the supervisor's background re-probe
loop is disabled (``GATEKEEPER_SUPERVISOR_REPROBE=0``) and transitions
are driven by hand via ``reprobe_now()`` with a monkeypatched device
check — except the one test whose subject IS the background loop,
which runs it with a tiny backoff.  Snapshot persistence activates
only inside tests that point ``GATEKEEPER_SNAPSHOT_DIR`` at a
tmp_path, so the rest of the suite stays hermetic.
"""

import json
import os
import random
import subprocess
import sys
import time

import pytest

from gatekeeper_tpu.resilience import snapshot as snap
from gatekeeper_tpu.resilience import supervisor as sup_mod
from gatekeeper_tpu.resilience.smoke import _verdict_digest
from gatekeeper_tpu.utils import device_probe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TARGET = "admission.k8s.gatekeeper.sh"


@pytest.fixture
def clean_backend(monkeypatch):
    """Fresh probe verdict + supervisor + fault harness, auto-reprobe
    off (tests drive transitions by hand), snapshots off."""
    monkeypatch.setenv("GATEKEEPER_SUPERVISOR_REPROBE", "0")
    for var in ("GATEKEEPER_FAULT", "GATEKEEPER_SNAPSHOT_DIR",
                "GATEKEEPER_PROBE_TEST_HANG", "GATEKEEPER_PROBE_TEST_FAIL"):
        monkeypatch.delenv(var, raising=False)
    device_probe.reset_for_tests()
    yield
    device_probe.reset_for_tests()


def _mk_client(jd=None, n=24, seed=7, with_data=True):
    """A small 3-template policy set over a deterministic inventory."""
    from gatekeeper_tpu.client.client import Backend
    from gatekeeper_tpu.engine import jax_driver as jd_mod
    from gatekeeper_tpu.library import make_mixed
    from gatekeeper_tpu.library.templates import (LIBRARY, constraint_doc,
                                                  template_doc)
    from gatekeeper_tpu.target.k8s import K8sValidationTarget

    jd = jd or jd_mod.JaxDriver()
    client = Backend(jd).new_client([K8sValidationTarget()])
    for kind in ("K8sRequiredLabels", "K8sAllowedRepos", "K8sDisallowedTags"):
        rego, params = LIBRARY[kind]
        client.add_template(template_doc(kind, rego))
        client.add_constraint(constraint_doc(kind, kind.lower() + "-1", params))
    if with_data:
        client.add_data_batch(make_mixed(random.Random(seed), n))
    return jd, client


def _audit(jd):
    from gatekeeper_tpu.client.interface import QueryOpts
    from gatekeeper_tpu.target.k8s import TARGET_NAME
    results, _trace = jd.query_audit(TARGET_NAME, QueryOpts(full=True))
    return results


# ----------------------------------------------------------------------
# supervisor state machine


def test_supervisor_demote_reprobe_recover(clean_backend, monkeypatch):
    s = sup_mod.get_supervisor()
    assert s.state == sup_mod.HEALTHY
    assert s.use_device()

    s.report_failure("transient backend error")
    assert s.state == sup_mod.DEGRADED
    assert not s.use_device()
    st = s.status()
    assert st["reason"] == "transient backend error"
    assert st["backend"] == "cpu-fallback"
    # the demotion keeps the probe verdict (and child env) coherent
    assert not device_probe.probe_devices().ok
    assert device_probe.child_env({})["JAX_PLATFORMS"] == "cpu"

    # a failed re-probe lands back in degraded, not healthy
    monkeypatch.setattr(s, "_device_check",
                        lambda t: (False, 0, "", "still down"))
    assert s.reprobe_now() is False
    assert s.state == sup_mod.DEGRADED
    assert s.status()["reprobe_attempts"] == 1
    assert s.metrics.counter("backend_reprobe_failures").value == 1

    # a succeeding re-probe restores healthy and the probe verdict
    monkeypatch.setattr(s, "_device_check", lambda t: (True, 8, "cpu", ""))
    assert s.reprobe_now() is True
    assert s.state == sup_mod.HEALTHY
    assert s.use_device()
    assert s.metrics.counter("backend_recoveries").value == 1
    assert device_probe.probe_devices().ok


def test_poisoned_is_terminal(clean_backend, monkeypatch):
    device_probe.probe_devices()            # seed healthy
    device_probe.mark_unavailable("hung mid-dispatch")
    s = sup_mod.get_supervisor()
    assert s.state == sup_mod.POISONED
    # poisoned never re-probes: the hung thread may hold jax's init lock
    monkeypatch.setattr(
        s, "_device_check",
        lambda t: pytest.fail("poisoned supervisor must not re-probe"))
    assert s.reprobe_now() is False
    assert s.state == sup_mod.POISONED
    s.report_failure("later flake")         # cannot un-poison either
    assert s.state == sup_mod.POISONED
    res = device_probe.probe_devices()
    assert not res.ok and res.poisoned
    # reprobe() (bench's retry primitive) returns a poisoned verdict
    # as-is instead of re-entering backend init
    assert device_probe.reprobe().poisoned
    assert device_probe.child_env({})["JAX_PLATFORMS"] == "cpu"


def test_background_reprobe_loop_with_backoff(monkeypatch):
    monkeypatch.setenv("GATEKEEPER_SUPERVISOR_REPROBE", "1")
    monkeypatch.setenv("GATEKEEPER_SUPERVISOR_BACKOFF_S", "0.05")
    monkeypatch.delenv("GATEKEEPER_FAULT", raising=False)
    device_probe.reset_for_tests()
    try:
        s = sup_mod.get_supervisor()
        assert s.state == sup_mod.HEALTHY
        calls = []

        def check(timeout_s):
            calls.append(timeout_s)
            if len(calls) < 3:
                return (False, 0, "", "still down")
            return (True, 8, "cpu", "")

        monkeypatch.setattr(s, "_device_check", check)
        s.report_failure("transient flake")
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and s.state != sup_mod.HEALTHY:
            time.sleep(0.02)
        assert s.state == sup_mod.HEALTHY, \
            f"loop never recovered: {s.status()}"
        assert len(calls) >= 3              # two failures, then recovery
        assert s.metrics.counter("backend_recoveries").value == 1
    finally:
        device_probe.reset_for_tests()


def test_probe_retry_primitive_recovers_transient_failure(
        clean_backend, monkeypatch):
    """bench._probe_with_retry is built on reprobe(): a non-poisoned
    failed verdict is dropped and re-probed; once the transient flake
    clears, the retry finds the backend."""
    monkeypatch.setenv("GATEKEEPER_PROBE_TEST_FAIL", "1")
    res = device_probe.probe_devices()
    assert not res.ok and not res.poisoned
    assert not device_probe.reprobe().ok    # still failing: fails again
    monkeypatch.delenv("GATEKEEPER_PROBE_TEST_FAIL")
    res = device_probe.reprobe()            # flake cleared: retry succeeds
    assert res.ok and res.n_devices == 8


# ----------------------------------------------------------------------
# device_lost mid-sweep


def test_device_lost_mid_sweep_completes_then_recovers(
        clean_backend, monkeypatch):
    # oracle: the same workload on a healthy backend
    jd0, _ = _mk_client(seed=7)
    want = _audit(jd0)
    assert want, "workload must produce violations"
    want_digest = _verdict_digest(want)

    # faulted run: the backend dies after the first kind dispatches
    device_probe.reset_for_tests()
    monkeypatch.setenv("GATEKEEPER_FAULT", "device_lost")
    jd, _ = _mk_client(seed=7)
    got = _audit(jd)
    s = jd.supervisor
    assert s.state == sup_mod.DEGRADED
    assert "device_lost" in s.reason
    assert jd.scalar_only                   # property re-consults per dispatch
    # the sweep still completed, with verdicts identical to the oracle
    assert _verdict_digest(got) == want_digest

    # re-probe brings the backend home and re-jits the driver
    monkeypatch.delenv("GATEKEEPER_FAULT")
    monkeypatch.setattr(s, "_device_check", lambda t: (True, 8, "cpu", ""))
    assert s.reprobe_now() is True
    assert s.state == sup_mod.HEALTHY
    assert not jd.scalar_only
    assert jd.metrics.counter("backend_rejits").value == 1
    assert _verdict_digest(_audit(jd)) == want_digest


# ----------------------------------------------------------------------
# snapshot persistence


def test_snapshot_corruption_never_crashes(clean_backend, monkeypatch,
                                           tmp_path):
    monkeypatch.setenv("GATEKEEPER_SNAPSHOT_DIR", str(tmp_path))
    payload = ({"x": 1, "y": [1, 2, 3]}, True)
    assert snap.save_template_module("K", TARGET, "src", payload)
    key = f"mod:{snap.template_digest('K', TARGET, 'src')}"
    path = snap._entry_path("mod", key)
    with open(path, "rb") as f:
        raw = f.read()

    hit = snap.load_template_module("K", TARGET, "src")
    assert hit is not None and hit[0] == payload

    corruptions = [
        ("truncated", raw[:-3]),
        ("bad magic", raw.replace(snap.MAGIC.encode(), b"not-a-snapshot", 1)),
        ("version skew", raw.replace(
            f'"version": {snap.VERSION}'.encode(), b'"version": 9999', 1)),
        ("payload bitflip", raw[:-1] + bytes([raw[-1] ^ 0xFF])),
        ("garbage", b"\x00\x01 not even a header"),
    ]
    for why, bad in corruptions:
        with open(path, "wb") as f:
            f.write(bad)
        before = snap.stats.snapshot()
        assert snap.load_template_module("K", TARGET, "src") is None, why
        delta = snap.stats.delta_since(before)
        assert delta["corrupt_discarded"] == 1, why
        assert delta["mod_misses"] == 1 and delta["mod_hits"] == 0, why
        # the bad entry is deleted so the cold rebuild can re-save it
        assert not os.path.exists(path), why
        assert snap.save_template_module("K", TARGET, "src", payload), why
    hit = snap.load_template_module("K", TARGET, "src")
    assert hit is not None and hit[0] == payload


def test_snapshot_corrupt_fault_is_one_shot(clean_backend, monkeypatch,
                                            tmp_path):
    monkeypatch.setenv("GATEKEEPER_SNAPSHOT_DIR", str(tmp_path))
    assert snap.save_dedup_plan("abc", {"plan": 1})
    monkeypatch.setenv("GATEKEEPER_FAULT", "snapshot_corrupt")
    before = snap.stats.snapshot()
    assert snap.load_dedup_plan("abc") is None      # injected corruption
    assert snap.stats.delta_since(before)["corrupt_discarded"] == 1
    # one-shot: after the single injected failure, the rebuilt entry
    # loads fine even with the fault still armed in the env
    assert snap.save_dedup_plan("abc", {"plan": 2})
    hit = snap.load_dedup_plan("abc")
    assert hit is not None and hit[0] == {"plan": 2}


@pytest.mark.parametrize("pages", ["off", "on"])
def test_warm_restart_in_process_skips_lowering(clean_backend, monkeypatch,
                                                tmp_path, pages):
    monkeypatch.setenv("GATEKEEPER_SNAPSHOT_DIR", str(tmp_path))
    monkeypatch.setenv("GATEKEEPER_PAGES", pages)
    from gatekeeper_tpu.engine import jax_driver as jd_mod
    from gatekeeper_tpu.target.k8s import TARGET_NAME

    jd_cold, _ = _mk_client(seed=3)
    cold_digest = _verdict_digest(_audit(jd_cold))
    assert jd_cold.save_store_snapshot(TARGET_NAME)

    base = snap.stats.snapshot()

    def boom(*a, **k):
        raise AssertionError("warm path must not re-lower Rego")

    monkeypatch.setattr(jd_mod, "lower_template", boom)
    jd_warm, _ = _mk_client(with_data=False)        # would raise if lowering
    assert jd_warm.restore_store_snapshot(TARGET_NAME) is True
    assert jd_warm.prepare_audit(TARGET_NAME) is True
    warm_digest = _verdict_digest(_audit(jd_warm))

    assert warm_digest == cold_digest               # bit-identical verdicts
    delta = snap.stats.delta_since(base)
    hits, misses = snap.tier_counts(delta)
    assert misses == 0, delta
    assert delta["ir_hits"] == 3                    # one per template
    assert delta["mod_hits"] == 3                   # parse+vet skipped too
    assert delta["store_hits"] == 1
    assert delta["plan_hits"] == 1
    # the pagemap tier exists only for paged deployments
    assert delta.get("pg_hits", 0) == (pages == "on")
    assert hits == 8 + (pages == "on")

    # repeat prepare_audit is satisfied from the in-memory memo, not
    # another disk read (monkeypatched loader would fail the call)
    monkeypatch.setattr(
        snap, "load_dedup_plan",
        lambda *a, **k: pytest.fail("memo must satisfy repeat prepare_audit"))
    assert jd_warm.prepare_audit(TARGET_NAME) is True


def test_snapshot_disabled_is_inert(clean_backend):
    assert not snap.enabled()
    assert snap.load_template_ir("K", TARGET, "src") is None
    assert snap.save_template_ir("K", TARGET, "src", None) is False
    assert snap.load_store(TARGET) is None
    assert snap.snapshot_dir() is None


# ----------------------------------------------------------------------
# probe --health


def _run_health(env_extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "GATEKEEPER_SUPERVISOR_REPROBE": "0", **env_extra}
    for var in ("GATEKEEPER_FAULT", "GATEKEEPER_SNAPSHOT_DIR",
                "GATEKEEPER_PROBE_TEST_HANG"):
        env.pop(var, None)
    out = subprocess.run(
        [sys.executable, "-m", "gatekeeper_tpu.client.probe", "--health"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=180)
    doc = json.loads(out.stdout.strip().splitlines()[0])
    return out, doc


def test_probe_health_healthy_and_degraded():
    out, doc = _run_health({})
    assert out.returncode == 0, out.stderr[-2000:]
    assert doc["state"] == "healthy"
    assert "HEALTH OK" in out.stdout
    assert "restart_persistent_cache_hits" in doc
    assert doc["last_probe_at"] is not None

    out, doc = _run_health({"GATEKEEPER_PROBE_TEST_FAIL": "1"})
    assert out.returncode == 2, out.stderr[-2000:]
    assert doc["state"] == "degraded"
    assert doc["backend"] == "cpu-fallback"
    assert doc["reason"]
    assert "HEALTH FAIL" in out.stdout
