"""Bounded device bring-up (utils/device_probe).

Failure mode guarded: a backend that neither succeeds nor raises —
backend init blocks forever, which would hang driver construction, the
engine worker, the demos, and the bench.  The
reference's driver constructs unconditionally
(vendor/.../drivers/local/local.go:28-48); these tests pin the same
always-available posture for the jax driver: under a simulated hung
backend (GATEKEEPER_PROBE_TEST_HANG=1) the full client stack must stay
correct, served by the scalar oracle, within a bounded wall clock.
"""

import os
import subprocess
import sys
import textwrap
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_probe_ok_on_cpu():
    from gatekeeper_tpu.utils.device_probe import probe_devices
    res = probe_devices()   # conftest pinned jax to the 8-device cpu mesh
    assert res.ok and not res.poisoned
    assert res.platform == "cpu"
    assert res.n_devices == 8
    assert res.backend_label == "cpu"


def test_hung_backend_serves_scalar_within_deadline():
    """End-to-end in a fresh process: probe hangs -> driver constructs
    anyway, reviews + audits produce correct results on the scalar
    path, children get pinned to cpu — all in bounded time."""
    code = textwrap.dedent("""
        import sys
        sys.path.insert(0, %r)
        from gatekeeper_tpu.utils.device_probe import probe_devices, child_env
        res = probe_devices()
        assert res.poisoned and not res.ok, res
        assert res.backend_label == "cpu-fallback", res
        import os
        assert os.environ["JAX_PLATFORMS"] == "cpu"
        assert child_env()["JAX_PLATFORMS"] == "cpu"
        assert "GATEKEEPER_PROBE_TEST_HANG" not in child_env()

        from gatekeeper_tpu.client.client import Backend
        from gatekeeper_tpu.engine.jax_driver import JaxDriver
        from gatekeeper_tpu.target.k8s import K8sValidationTarget
        d = JaxDriver()
        assert d.scalar_only
        c = Backend(d).new_client([K8sValidationTarget()])
        tpl = {
            "apiVersion": "templates.gatekeeper.sh/v1alpha1",
            "kind": "ConstraintTemplate",
            "metadata": {"name": "k8sdenyall"},
            "spec": {
                "crd": {"spec": {"names": {"kind": "K8sDenyAll"}}},
                "targets": [{
                    "target": "admission.k8s.gatekeeper.sh",
                    "rego": 'package x\\n'
                            'violation[{"msg": "DENIED", "details": {}}]'
                            ' { 1 == 1 }',
                }],
            },
        }
        c.add_template(tpl)
        c.add_constraint({"apiVersion": "constraints.gatekeeper.sh/v1alpha1",
                          "kind": "K8sDenyAll",
                          "metadata": {"name": "deny-everything"},
                          "spec": {}})
        ns = {"apiVersion": "v1", "kind": "Namespace",
              "metadata": {"name": "prod"}}
        c.add_data(ns)
        review = {"kind": {"group": "", "version": "v1",
                           "kind": "Namespace"},
                  "name": "prod", "operation": "CREATE", "object": ns}
        resp = c.review(review)
        results = resp.by_target["admission.k8s.gatekeeper.sh"].results
        assert [r.msg for r in results] == ["DENIED"], results
        audit = c.audit()
        aresults = audit.by_target["admission.k8s.gatekeeper.sh"].results
        assert [r.msg for r in aresults] == ["DENIED"], aresults
        print("SCALAR-FALLBACK-OK")
    """ % REPO)
    env = {**os.environ,
           "GATEKEEPER_PROBE_TEST_HANG": "1",
           "GATEKEEPER_DEVICE_PROBE_TIMEOUT_S": "2"}
    t0 = time.monotonic()
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    wall = time.monotonic() - t0
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "SCALAR-FALLBACK-OK" in out.stdout
    # 2s probe + imports + scalar eval; far under the old infinite hang
    assert wall < 90, f"fallback path took {wall:.0f}s"


def test_mark_unavailable_demotes_future_drivers():
    """The bench downgrades the process verdict when an
    EXECUTION hangs after a successful probe — drivers constructed
    after mark_unavailable() must serve scalar-only, and children must
    be pinned to cpu."""
    code = textwrap.dedent("""
        import sys
        sys.path.insert(0, %r)
        from gatekeeper_tpu.utils.device_probe import (
            child_env, mark_unavailable, probe_devices)
        res = probe_devices()        # healthy cpu probe first
        assert res.ok, res
        mark_unavailable("simulated mid-run hang")
        res2 = probe_devices()
        assert not res2.ok and "simulated" in res2.reason, res2
        assert child_env()["JAX_PLATFORMS"] == "cpu"
        from gatekeeper_tpu.engine.jax_driver import JaxDriver
        assert JaxDriver().scalar_only
        print("DEMOTED-OK")
    """ % REPO)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "DEMOTED-OK" in out.stdout


def test_worker_starts_with_hung_backend():
    """The engine worker must come up and serve when the backend probe
    hangs."""
    from gatekeeper_tpu.client.replica_pool import ReplicaPool
    from gatekeeper_tpu.client.client import Backend
    from gatekeeper_tpu.target.k8s import K8sValidationTarget
    env = {"GATEKEEPER_PROBE_TEST_HANG": "1",
           "GATEKEEPER_DEVICE_PROBE_TIMEOUT_S": "2",
           "JAX_PLATFORMS": ""}   # let the (hanging) probe decide
    with ReplicaPool.spawn_workers(1, timeout=120, env=env) as pool:
        c = Backend(pool).new_client([K8sValidationTarget()])
        assert c.review({"kind": {"group": "", "version": "v1",
                                  "kind": "Namespace"},
                         "name": "x", "operation": "CREATE",
                         "object": {"apiVersion": "v1", "kind": "Namespace",
                                    "metadata": {"name": "x"}}}) is not None


def test_children_of_an_accelerator_holder_are_pinned_to_cpu(monkeypatch):
    """One process per chip: a process that brought up a TPU pins every
    child it spawns to the CPU, over whatever platform the caller asked
    for — ReplicaPool's admission workers included."""
    from gatekeeper_tpu.client import replica_pool
    from gatekeeper_tpu.utils import device_probe
    assert not device_probe.holds_accelerator()     # conftest: cpu
    monkeypatch.setattr(device_probe, "_RESULT", device_probe.ProbeResult(
        True, 1, "tpu", False, "1 tpu device(s)"))
    assert device_probe.holds_accelerator()
    assert device_probe.child_env({"JAX_PLATFORMS": "tpu"})[
        "JAX_PLATFORMS"] == "cpu"
    seen = []

    def fake_popen(*args, env=None, **kw):
        seen.append(env)
        raise RuntimeError("stop after the spawn")
    monkeypatch.setattr(replica_pool.subprocess, "Popen", fake_popen)
    import pytest
    with pytest.raises(RuntimeError, match="stop after the spawn"):
        replica_pool.ReplicaPool.spawn_workers(
            1, env={"JAX_PLATFORMS": "tpu"})
    assert seen[0]["JAX_PLATFORMS"] == "cpu"
