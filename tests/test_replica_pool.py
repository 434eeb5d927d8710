"""ReplicaPool: broadcast mutations + distributed reviews must be
indistinguishable from a single driver (reference HA model: every pod
holds full state, the Service spreads admission — ha_status.go,
deploy/gatekeeper.yaml StatefulSet)."""

import pytest

from gatekeeper_tpu.client.client import Backend
from gatekeeper_tpu.client.local_driver import LocalDriver
from gatekeeper_tpu.client.remote_driver import EngineWorker, RemoteDriver
from gatekeeper_tpu.client.replica_pool import ReplicaPool
from gatekeeper_tpu.library import constraint_doc, template_doc
from gatekeeper_tpu.library.templates import LIBRARY
from gatekeeper_tpu.target.k8s import K8sValidationTarget, TARGET_NAME


def _ns(name, labels):
    return {"apiVersion": "v1", "kind": "Namespace",
            "metadata": {"name": name, "labels": labels}}


def _setup(client):
    client.add_template(template_doc("K8sRequiredLabels",
                                     LIBRARY["K8sRequiredLabels"][0]))
    client.add_constraint(constraint_doc("K8sRequiredLabels", "need-owner",
                                         {"labels": ["owner"]}))
    client.add_data_batch([_ns("good", {"owner": "me"}), _ns("bad-a", {}),
                           _ns("bad-b", {})])


def _audit_names(client):
    resp = client.audit(limit_per_constraint=20)
    return sorted((r.resource or {}).get("metadata", {}).get("name")
                  for r in resp.by_target[TARGET_NAME].results)


@pytest.fixture()
def pool2():
    workers = [EngineWorker(LocalDriver()), EngineWorker(LocalDriver())]
    for w in workers:
        w.start()
    pool = ReplicaPool([RemoteDriver(w.url) for w in workers])
    yield pool
    for w in workers:
        w.stop()


class TestReplicaPool:
    def test_matches_single_driver(self, pool2):
        ref = Backend(LocalDriver()).new_client([K8sValidationTarget()])
        _setup(ref)
        c = Backend(pool2).new_client([K8sValidationTarget()])
        _setup(c)
        assert _audit_names(c) == _audit_names(ref) == ["bad-a", "bad-b"]

    def test_reviews_consistent_across_replicas(self, pool2):
        c = Backend(pool2).new_client([K8sValidationTarget()])
        _setup(c)
        req = {"kind": {"group": "", "version": "v1", "kind": "Namespace"},
               "name": "n", "operation": "CREATE", "object": _ns("n", {})}
        # round-robin: consecutive reviews land on different replicas
        # and must return identical verdicts
        outs = [c.review(req).by_target[TARGET_NAME].results
                for _ in range(4)]
        assert all(len(o) == 1 for o in outs)
        msgs = {o[0].msg for o in outs}
        assert len(msgs) == 1

    def test_mutations_reach_every_replica(self, pool2):
        c = Backend(pool2).new_client([K8sValidationTarget()])
        _setup(c)
        # remove one object; BOTH replicas must stop reporting it
        # (probe each replica directly, bypassing round-robin)
        c.remove_data(_ns("bad-b", {}))
        for d in pool2.drivers:
            results, _ = d.query_audit(TARGET_NAME, None)
            names = sorted((r.review or {}).get("name") for r in results)
            assert names == ["bad-a"], names

    def test_wipe_broadcasts(self, pool2):
        from gatekeeper_tpu.client.targets import WipeData
        c = Backend(pool2).new_client([K8sValidationTarget()])
        _setup(c)
        c.remove_data(WipeData())
        for d in pool2.drivers:
            results, _ = d.query_audit(TARGET_NAME, None)
            assert results == []


class TestEviction:
    def test_failed_replica_leaves_rotation(self, pool2):
        c = Backend(pool2).new_client([K8sValidationTarget()])
        _setup(c)
        # kill replica 1's worker out from under the pool: the next
        # broadcast must evict it (queries would otherwise round-robin
        # onto half-updated state) and survivors stay consistent
        import pytest as _pytest
        from gatekeeper_tpu.errors import ClientError
        victim = pool2.drivers[1]
        victim.url = "http://127.0.0.1:1"        # unroutable
        victim._host, victim._port = "127.0.0.1", 1
        victim._local.__dict__.clear()
        with _pytest.raises(ClientError, match="evicted"):
            c.add_data(_ns("late", {}))
        assert len(pool2.drivers) == 1
        # the surviving replica took the mutation and keeps serving
        assert "late" in _audit_names(c)
        req = {"kind": {"group": "", "version": "v1", "kind": "Namespace"},
               "name": "q", "operation": "CREATE", "object": _ns("q", {})}
        assert len(c.review(req).by_target[TARGET_NAME].results) == 1


class TestQueryFailover:
    def test_review_fails_over_to_survivor(self, pool2):
        c = Backend(pool2).new_client([K8sValidationTarget()])
        _setup(c)
        victim = pool2.drivers[0]
        victim.url = "http://127.0.0.1:1"
        victim._host, victim._port = "127.0.0.1", 1
        victim._local.__dict__.clear()
        req = {"kind": {"group": "", "version": "v1", "kind": "Namespace"},
               "name": "n", "operation": "CREATE", "object": _ns("n", {})}
        # several rounds: whichever replica round-robin picks first, the
        # dead one gets evicted and every review still answers
        for _ in range(4):
            assert len(c.review(req).by_target[TARGET_NAME].results) == 1
        assert len(pool2.drivers) == 1

    def test_all_dead_raises(self, pool2):
        from gatekeeper_tpu.errors import ClientError
        c = Backend(pool2).new_client([K8sValidationTarget()])
        _setup(c)
        for victim in list(pool2.drivers):
            victim.url = "http://127.0.0.1:1"
            victim._host, victim._port = "127.0.0.1", 1
            victim._local.__dict__.clear()
        req = {"kind": {"group": "", "version": "v1", "kind": "Namespace"},
               "name": "n", "operation": "CREATE", "object": _ns("n", {})}
        with pytest.raises(ClientError, match="all replicas failed"):
            c.review(req)


class TestSpawnWorkers:
    def test_subprocess_worker_end_to_end(self):
        # pin the worker subprocess to CPU: the scenario needs no
        # accelerator, and the worker must not depend on one
        with ReplicaPool.spawn_workers(
                1, timeout=120, env={"JAX_PLATFORMS": "cpu"}) as pool:
            c = Backend(pool).new_client([K8sValidationTarget()])
            _setup(c)
            assert _audit_names(c) == ["bad-a", "bad-b"]
            req = {"kind": {"group": "", "version": "v1",
                            "kind": "Namespace"},
                   "name": "x", "operation": "CREATE",
                   "object": _ns("x", {"owner": "me"})}
            assert c.review(req).by_target[TARGET_NAME].results == []
