"""The plain reference against the program's scalar engine
(``LocalDriver``) at small sizes, its control, the generator, and the
frozen policy set against the program's own."""

import collections
import json
import os
import random

import numpy as np
import pytest
from bench_helpers import BENCH_DIR
from harness import cell as cellmod

from gatekeeper_tpu.client.client import Backend
from gatekeeper_tpu.client.local_driver import LocalDriver
from gatekeeper_tpu.target.k8s import K8sValidationTarget

CELL = cellmod.load("large-cluster.full_sweep")
pods, ref = CELL.generator, CELL.reference


def spec(n):
    return dict(CELL.config["inventory"], n=n)


def local_client(pol, objs):
    c = Backend(LocalDriver()).new_client([K8sValidationTarget()])
    for t in pol["templates"]:
        c.add_template(t)
    for x in pol["constraints"]:
        c.add_constraint(x)
    c.add_data_batch(objs)
    return c


def capped(results, objs, limit):
    """LocalDriver's uncapped audit, capped as Gatekeeper's audit caps:
    per constraint, objects in cache-key order, all messages of each,
    until at least ``limit`` are out."""
    rank = {(o["metadata"]["namespace"], o["metadata"]["name"]):
            ref.cache_key(o) for o in objs}
    by = collections.defaultdict(list)
    for r in results:
        m = r.resource["metadata"]
        by[r.constraint["metadata"]["name"]].append(
            (r.constraint["metadata"]["name"], m["namespace"], m["name"],
             r.msg))
    out = []
    for rows in by.values():
        rows.sort(key=lambda k: rank[(k[1], k[2])])
        emitted, cur = 0, None
        for k in rows:
            if (k[1], k[2]) != cur:
                if emitted >= limit:
                    break
                cur = (k[1], k[2])
            out.append(k)
            emitted += 1
    return collections.Counter(out)


def test_reference_equals_local_driver_with_churn():
    # every seventh constraint: all three kinds, at a size the scalar
    # engine audits in seconds
    pol = dict(CELL.policy, constraints=CELL.policy["constraints"][::7])
    objs = pods.make(11, spec(600))
    r = ref.make(pol, objs)
    client = local_client(pol, objs)
    mix = {"fraction": 0.05, "update": 0.9, "create": 0.05}
    for ups, dels in [([], [])] + pods.churn(11, objs, 2, mix, spec(600)):
        client.add_data_batch(ups)
        for d in dels:
            client.remove_data(d)
        r.apply(ups, dels)
        live = {x.resource["metadata"]["name"]: x.resource
                for x in client.audit().results()}
        want = capped(client.audit().results(), list(live.values()), 20)
        assert collections.Counter(map(tuple, r.audit(20))) == want


def test_control_rounds_to_bfloat16():
    mi = 2**20
    got = ref.as_bfloat16(np.array([2049 * mi, 2056 * mi, 2064 * mi,
                                    513 * mi, 2003.0, 1500.0]))
    assert got.tolist() == [2048 * mi, 2048 * mi, 2064 * mi, 512 * mi,
                            2000.0, 1504.0]
    assert ref.exact(2049 * mi) == 2049 * mi


def test_control_differs_where_quantities_decide():
    """The bfloat16 control drops violations just above a maximum, and
    only those: every entry it loses or gains is a K8sContainerLimits
    quantity message."""
    objs = pods.make(12, spec(400))
    want = collections.Counter(ref.make(CELL.policy, objs).audit(20))
    got = collections.Counter(ref.make(CELL.policy, objs, True).audit(20))
    diff = (want - got) + (got - want)
    assert diff
    kinds = {c["metadata"]["name"]: c["kind"]
             for c in CELL.policy["constraints"]}
    assert {kinds[k[0]] for k in diff} <= {"K8sContainerLimits",
                                           "K8sRequiredLabels",
                                           "K8sAllowedRepos"}
    assert any("limit <" in k[3] for k in diff)


def test_frozen_policy_is_the_programs_and_still_lowers():
    """The data file holds what the program's library generates today,
    and every template installs and lowers to the device."""
    from gatekeeper_tpu.engine.jax_driver import JaxDriver
    from gatekeeper_tpu.library.workload import north_star_docs
    t, c = north_star_docs(random.Random(43), 67)
    assert CELL.policy == {"templates": t, "constraints": c}
    jd = JaxDriver()
    client = Backend(jd).new_client([K8sValidationTarget()])
    for doc in CELL.policy["templates"]:
        client.add_template(doc)
    st = next(iter(jd.state.values()))
    assert all(v.vectorized is not None for v in st.templates.values())


@pytest.mark.parametrize("seed", [0, -5, 2**31 + 99, 2**40])
def test_generator_is_seeded(seed):
    assert pods.make(seed, spec(50)) == pods.make(seed, spec(50))
    assert pods.make(seed, spec(50)) != pods.make(seed + 1, spec(50))


def test_generator_quantities():
    """Every seed holds the same Pods in the same cache-key order under
    other names; about a fifth of the limits lie within 1% above a
    policy maximum, and each is exact in float32."""
    a, b = pods.make(1, spec(2000)), pods.make(2, spec(2000))
    assert [p["spec"] for p in a] == [p["spec"] for p in b]
    assert [p["metadata"]["labels"] for p in a] == \
        [p["metadata"]["labels"] for p in b]
    assert [p["metadata"]["name"] for p in a] != \
        [p["metadata"]["name"] for p in b]
    for ps in (a, b):
        keys = [ref.cache_key(p) for p in ps]
        assert sorted(range(len(ps)), key=keys.__getitem__) == \
            sorted(range(len(ps)), key=lambda i: (f"ns{i % 50}", i))
    cpu = [ref.cpu_milli(c["resources"]["limits"]["cpu"])
           for p in a for c in p["spec"]["containers"]]
    mem = [ref.mem_bytes(c["resources"]["limits"]["memory"]) / 2**20
           for p in a for c in p["spec"]["containers"]]
    near = np.mean([any(t < v <= t * 1.01 for t in (500, 1000, 2000))
                    for v in cpu])
    assert 0.1 < near < 0.25
    assert 0.1 < np.mean([any(t < v <= t * 1.01 for t in (512, 2048))
                          for v in mem]) < 0.25
    v = np.array(cpu + [m * 2**20 for m in mem])
    assert (v.astype(np.float32).astype(np.float64) == v).all()


def test_config_file_matches_its_entry():
    with open(os.path.join(BENCH_DIR, "configs",
                           "large-cluster-150k.json")) as f:
        cfg = json.load(f)
    assert cfg["source"] == CELL.config_entry["source"]
    assert cfg["reduced"] == CELL.config_entry["reduced"] == []
