"""The audit and admission path, end to end, on one TPU chip.

One process drives the main path through
``Backend(JaxDriver()).new_client([K8sValidationTarget()])`` at the
north-star size of BASELINE.json, and checks every verdict against the
scalar oracle (``LocalDriver``):

  audit      1,000,000 Pods x 201 constraints (K8sRequiredLabels,
             K8sAllowedRepos, K8sContainerLimits x 67): ingest, one cold
             capped audit, 3 steady audits, one sweep after a 1 % churn
             (half of it on the rows the cold audit reported).  Every
             capped result is confirmed by the oracle, the churned
             capped audit equals a fresh driver's over the final
             inventory, and on a seeded 5,000-Pod subsample the uncapped
             verdicts of a device-forced JaxDriver equal the oracle's.
  library    every library template over 100,000 mixed resources; every
             kind must dispatch on the device, and a
             seeded 5,000-resource subsample must match the oracle.
  admission  the demo/agilebank AdmissionReviews POSTed to a live
             WebhookServer, then one review_batch of 1,024 reviews
             against the 201 north-star constraints (the device path).

The oracle's own evaluations run in CPU-pinned worker processes, in
parallel with the device phases; they never open the chip.  The lines
before the last are a smoke record, not benchmark numbers.  The last line
is ``{"ok": true, "device": {...}}``, printed only when the platform is
TPU and every check passed.

``--chips 4`` runs only the north-star audit on the (c, r) mesh over
every visible device, against the same audit on one chip
(GATEKEEPER_SHARDS=1) in the same process, and prints each device's
memory.
"""

from __future__ import annotations

import argparse
import copy
import hashlib
import json
import os
import random
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
# BASELINE.json's north star and library configs
N_PODS, PER_KIND, LIBRARY_N = 1_000_000, 67, 100_000
SUBSAMPLE, BATCH, CAP = 5_000, 1_024, 20
ORACLE_WORKERS = 6
AGILEBANK = os.path.join(HERE, "demo", "agilebank")
# the deny message each bad agilebank resource must produce at the
# webhook; duplicate-service.yaml is denied only once the good payments
# Service is in the inventory
EXPECTED_DENY = {
    "duplicate-service.yaml":
        "[denied by unique-service-selector] same selector as Service "
        "<payments> in namespace <production>",
    "namespace-no-owner.yaml":
        "[denied by all-must-have-owner] All namespaces must have an "
        "`owner` label that points to your company username",
    "pod-limits-too-high.yaml":
        "[denied by container-must-have-limits] container <app> cpu limit "
        "<4> is higher than the maximum allowed of <200m>\n"
        "[denied by container-must-have-limits] container <app> memory "
        "limit <16Gi> is higher than the maximum allowed of <1Gi>",
    "pod-no-limits.yaml":
        "[denied by container-must-have-limits] container <app> has no "
        "resource limits",
    "pod-wrong-repo.yaml":
        "[denied by prod-repo-is-approved] container <app> has an invalid "
        "image repo <docker.io/somebody/sketchy:latest>",
}


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# -- verdicts ---------------------------------------------------------------

def verdicts(results) -> list[tuple]:
    """Sorted (constraint kind, constraint name, resource name, msg)."""
    return sorted(
        ((r.constraint or {}).get("kind", ""),
         ((r.constraint or {}).get("metadata") or {}).get("name", ""),
         ((r.resource or {}).get("metadata") or {}).get("name", ""),
         r.msg)
        for r in results)


def digest(vs: list[tuple]) -> str:
    return hashlib.sha256(repr(sorted(vs)).encode()).hexdigest()[:16]


def new_client(driver, templates, constraints, objs=()):
    from gatekeeper_tpu.client.client import Backend
    from gatekeeper_tpu.target.k8s import K8sValidationTarget
    client = Backend(driver).new_client([K8sValidationTarget()])
    for doc in templates:
        client.add_template(doc)
    for doc in constraints:
        client.add_constraint(doc)
    if objs:
        client.add_data_batch(list(objs))
    return client


def audit_results(client, limit: int | None = None) -> list:
    return client.audit(limit_per_constraint=limit).results()


def review_envelope(obj: dict) -> dict:
    meta = obj.get("metadata") or {}
    api = obj.get("apiVersion", "v1")
    group, _, version = api.rpartition("/")
    return {"uid": "smoke",
            "kind": {"group": group, "version": version,
                     "kind": obj.get("kind", "")},
            "name": meta.get("name", ""), "namespace": meta.get("namespace"),
            "operation": "CREATE", "object": obj,
            "userInfo": {"username": "smoke"}}


# -- the oracle, in CPU-pinned worker processes -----------------------------

def _oracle_init() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")


def oracle_audit(templates, constraints, objs) -> list[tuple]:
    """LocalDriver's uncapped audit verdicts over `objs`."""
    from gatekeeper_tpu.client.local_driver import LocalDriver
    return verdicts(audit_results(
        new_client(LocalDriver(), templates, constraints, objs)))


def oracle_reviews(templates, constraints, objs) -> list[tuple]:
    """LocalDriver's admission verdicts, one review per object."""
    from gatekeeper_tpu.client.local_driver import LocalDriver
    client = new_client(LocalDriver(), templates, constraints)
    return review_verdicts(
        [client.review(review_envelope(o)).results() for o in objs])


def review_verdicts(per_review: list) -> list[tuple]:
    """Sorted (review index, constraint kind, constraint name, msg)."""
    return sorted(
        (i, (r.constraint or {}).get("kind", ""),
         ((r.constraint or {}).get("metadata") or {}).get("name", ""),
         r.msg)
        for i, results in enumerate(per_review) for r in results)


class Oracle:
    """LocalDriver evaluations run in spawned worker processes pinned to
    CPU, so they overlap the device phases.  ``None`` workers run them
    inline (tests)."""

    def __init__(self, workers: int | None):
        self.pool = None
        if workers:
            import concurrent.futures
            import multiprocessing
            self.pool = concurrent.futures.ProcessPoolExecutor(
                workers, mp_context=multiprocessing.get_context("spawn"),
                initializer=_oracle_init)

    def submit(self, fn, *args):
        if self.pool is not None:
            return self.pool.submit(fn, *args)
        import concurrent.futures
        f = concurrent.futures.Future()
        f.set_result(fn(*args))
        return f

    def audit(self, templates, constraints, objs, chunks: int = 1):
        """A zero-argument callable that returns the oracle's audit
        verdicts over `objs`, computed as `chunks` jobs (only for
        row-local templates)."""
        step = max(-(-len(objs) // chunks), 1)
        parts = [self.submit(oracle_audit, templates, constraints,
                             objs[i:i + step])
                 for i in range(0, len(objs), step)]
        return lambda: sorted(v for p in parts for v in p.result())

    def close(self) -> None:
        if self.pool is not None:
            self.pool.shutdown(wait=True, cancel_futures=True)


# -- driver health -------------------------------------------------------------

def assert_healthy(jd, where: str) -> None:
    from gatekeeper_tpu.resilience.supervisor import HEALTHY
    check(not jd.scalar_only, f"{where}: JaxDriver went scalar-only")
    sup = jd.supervisor
    check(sup.state == HEALTHY, f"{where}: supervisor left healthy "
          f"({sup.state}: {sup.reason})")
    check(sup.metrics.counter("backend_degradations").value == 0,
          f"{where}: supervisor degraded during the run")


def device_memory() -> list[dict]:
    import jax
    out = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        out.append({"device": d.id,
                    "bytes_in_use": st.get("bytes_in_use"),
                    "peak_bytes_in_use": st.get("peak_bytes_in_use")})
    return out


def cache_stats() -> dict:
    from gatekeeper_tpu.utils.compile_cache import persistent_cache_stats
    return persistent_cache_stats().snapshot()


def device_audit(templates, constraints, objs, where: str):
    """(uncapped results, dispatch stanza) of a fresh JaxDriver over
    `objs`, with the device path forced at subsample size (as
    tests/conftest.py does)."""
    from gatekeeper_tpu.engine import jax_driver as jd_mod
    small = jd_mod.SMALL_WORKLOAD_EVALS
    jd_mod.SMALL_WORKLOAD_EVALS = 0
    try:
        jd = jd_mod.JaxDriver()
        res = audit_results(new_client(jd, templates, constraints,
                                       copy.deepcopy(objs)))
        assert_healthy(jd, where)
        return res, jd.last_sweep_phases["dispatch"]
    finally:
        jd_mod.SMALL_WORKLOAD_EVALS = small


def compare(where: str, got: list[tuple], want: list[tuple]) -> dict:
    """Digest record of device verdicts `got` against the oracle's."""
    dev_d, ref_d = digest(got), digest(want)
    check(dev_d == ref_d, f"{where}: digest {dev_d} != oracle {ref_d}")
    return {"verdicts": len(got), "digest": dev_d, "oracle_digest": ref_d}


# -- phases -------------------------------------------------------------------

def north_star_inventory(n: int, per_kind: int, seed: int):
    """(templates, constraints, resources): the BASELINE.json north star
    from library/workload.py's generators."""
    from gatekeeper_tpu.library.workload import make_pods, north_star_docs
    resources = make_pods(n, random.Random(seed))
    templates, constraints = north_star_docs(random.Random(seed + 1),
                                             per_kind)
    return templates, constraints, resources


def churn_edits(resources: list, head: list[int], frac: float,
                seed: int) -> dict:
    """{index: edited copy} for `frac` of the rows, labels redrawn.  Up
    to half of them are `head`, the rows the capped audit reported: a
    delta that fails to set or clear a verdict shows in the capped
    output only there.  The rest are drawn at random."""
    rng = random.Random(seed)
    budget = max(int(len(resources) * frac), 1)
    picked = sorted(head)[:max(budget // 2, 1)]
    rest = sorted(set(range(len(resources))) - set(picked))
    picked += rng.sample(rest, min(budget - len(picked), len(rest)))
    churned = {}
    for i in picked:
        o = copy.deepcopy(resources[i])
        o["metadata"]["labels"] = {f"l{j}": "v" for j in range(10)
                                   if rng.random() < 0.35}
        churned[i] = o
    return churned


def phase_audit(n: int, per_kind: int, sub_n: int, seed: int,
                oracle: Oracle, steady_reps: int = 3,
                churn_frac: float = 0.01) -> dict:
    from gatekeeper_tpu.engine.jax_driver import JaxDriver
    t0 = time.perf_counter()
    templates, constraints, resources = north_star_inventory(
        n, per_kind, seed)
    sub = [resources[i] for i in
           sorted(random.Random(seed + 3).sample(range(n), min(sub_n, n)))]
    # the three north-star templates are row-local: the oracle's audit
    # splits over disjoint row chunks
    sub_oracle = oracle.audit(templates, constraints, sub, chunks=8)
    out = {"n_resources": n, "n_constraints": len(constraints),
           "generate_s": time.perf_counter() - t0}

    jd = JaxDriver()
    t0 = time.perf_counter()
    client = new_client(jd, templates, constraints, resources)
    out["ingest_s"] = time.perf_counter() - t0
    say(f"audit: {n} resources ingested in {out['ingest_s']:.1f}s")
    c0 = jd.executor.compiles
    t0 = time.perf_counter()
    cold = audit_results(client, CAP)
    out["cold_s"] = time.perf_counter() - t0
    out["cold_compiles"] = jd.executor.compiles - c0
    assert_healthy(jd, "cold audit")
    say(f"audit: cold capped audit {out['cold_s']:.1f}s")
    disp = jd.last_sweep_phases["dispatch"]
    check(len(disp["device"]) == len(templates) and not disp["scalar"],
          f"north-star kinds not all on the device: {disp}")
    steady = []
    c0 = jd.executor.compiles
    for _ in range(steady_reps):
        t0 = time.perf_counter()
        res = audit_results(client, CAP)
        steady.append(time.perf_counter() - t0)
        check(verdicts(res) == verdicts(cold),
              "steady audit differs from the cold audit")
    out["steady_s"] = steady
    out["steady_compiles"] = jd.executor.compiles - c0
    index = {o["metadata"]["name"]: i for i, o in enumerate(resources)}
    churned = churn_edits(
        resources, [index[name] for name in
                    {r.resource["metadata"]["name"] for r in cold}],
        churn_frac, seed + 2)
    final = list(resources)
    for i, o in churned.items():
        final[i] = o
    t0 = time.perf_counter()
    client.add_data_batch(list(churned.values()))
    out["churn_upsert_s"] = time.perf_counter() - t0
    out["churn_rows"] = len(churned)
    t0 = time.perf_counter()
    capped = audit_results(client, CAP)
    out["churn_sweep_s"] = time.perf_counter() - t0
    assert_healthy(jd, "churn sweep")
    out["capped_results"] = len(capped)
    check(len(capped) > 0, "the capped audit found no violation")
    hit = {r.constraint["metadata"]["name"] for r in capped}
    check(len(hit) == len(constraints),
          f"{len(constraints) - len(hit)} constraints have no capped result")

    # every capped result must be the oracle's verdict on its resource
    by_name = {r.resource["metadata"]["name"]: r.resource for r in capped}
    confirm = oracle.audit(templates, constraints, list(by_name.values()))

    # the churned client's delta path against a fresh driver that
    # ingested the final inventory: the same capped verdicts
    t0 = time.perf_counter()
    fresh = JaxDriver()
    fresh_capped = audit_results(
        new_client(fresh, templates, constraints, final), CAP)
    out["fresh_final_s"] = time.perf_counter() - t0
    assert_healthy(fresh, "fresh final-state audit")
    check(verdicts(fresh_capped) == verdicts(capped),
          "churned capped audit differs from a fresh driver's audit of "
          "the final inventory")
    out["churn_digest"] = digest(verdicts(capped))
    out["churn_capped_changed"] = len(set(verdicts(cold))
                                      ^ set(verdicts(capped)))
    check(out["churn_capped_changed"] > 0,
          "the churn left the capped audit unchanged: nothing to check")
    del fresh, fresh_capped

    say(f"audit: churn sweep {out['churn_sweep_s']:.2f}s; "
        f"subsample of {len(sub)} next")
    sub_res, disp = device_audit(templates, constraints, sub, "subsample")
    check(len(disp["device"]) == len(templates),
          f"subsample kinds not all on the device: {disp}")
    oracle_set = set(confirm())
    missing = [v for v in verdicts(capped) if v not in oracle_set]
    check(not missing, f"{len(missing)} capped results not confirmed by "
          f"the oracle, e.g. {missing[:3]}")
    out["subsample"] = {"n": len(sub), **compare(
        "audit subsample", verdicts(sub_res), sub_oracle())}
    out["client"], out["driver"] = client, jd
    out["templates"], out["constraints"] = templates, constraints
    out["resources"] = final
    return out


def phase_library(n: int, sub_n: int, seed: int, oracle: Oracle) -> dict:
    from gatekeeper_tpu.engine.jax_driver import JaxDriver
    from gatekeeper_tpu.library import all_docs, make_mixed
    docs = all_docs()
    templates = [t for t, _ in docs]
    constraints = [c for _, c in docs]
    resources = make_mixed(random.Random(seed), n)
    sub = [resources[i] for i in
           sorted(random.Random(seed + 1).sample(range(n), min(sub_n, n)))]
    # inventory joins make some templates cross-row: one oracle job
    sub_oracle = oracle.audit(templates, constraints, sub)
    out = {"n_resources": n, "n_templates": len(templates)}
    jd = JaxDriver()
    t0 = time.perf_counter()
    client = new_client(jd, templates, constraints, resources)
    out["ingest_s"] = time.perf_counter() - t0
    c0 = jd.executor.compiles
    t0 = time.perf_counter()
    audit_results(client, CAP)
    out["cold_s"] = time.perf_counter() - t0
    out["cold_compiles"] = jd.executor.compiles - c0
    assert_healthy(jd, "library audit")
    disp = jd.last_sweep_phases["dispatch"]
    st = next(iter(jd.state.values()))
    lowered = sorted(k for k, t in st.templates.items()
                     if t.vectorized is not None)
    f32 = sorted(k for k, why in disp["scalar"].items() if why == "f32 pin")
    out["device_kinds"] = len(disp["device"])
    out["scalar_kinds"] = disp["scalar"]
    check(sorted(disp["device"] + f32) == lowered,
          f"device-lowered kinds that did not dispatch on the device: "
          f"{sorted(set(lowered) - set(disp['device']))}")
    bad = {k: why for k, why in disp["scalar"].items() if why != "f32 pin"}
    check(not bad, f"library kinds on the scalar oracle: {bad}")
    t0 = time.perf_counter()
    audit_results(client, CAP)
    out["steady_s"] = time.perf_counter() - t0
    say(f"library: cold {out['cold_s']:.1f}s; subsample of {len(sub)} next")
    del client, jd, st

    sub_res, _ = device_audit(templates, constraints, sub,
                              "library subsample")
    out["subsample"] = {"n": len(sub), **compare(
        "library subsample", verdicts(sub_res), sub_oracle())}
    return out


def _post(port: int, review: dict) -> dict:
    import urllib.request
    body = {"apiVersion": "admission.k8s.io/v1", "kind": "AdmissionReview",
            "request": review}
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/admit", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read())["response"]


def _load_dir(name: str) -> list[tuple[str, dict]]:
    import yaml
    d = os.path.join(AGILEBANK, name)
    out = []
    for f in sorted(os.listdir(d)):
        with open(os.path.join(d, f)) as fh:
            out.append((f, yaml.safe_load(fh)))
    return out


def phase_admission(north: dict, batch: int, seed: int,
                    oracle: Oracle) -> dict:
    """`north` is phase_audit's output: its client holds the 201
    constraints the review batch runs against."""
    from gatekeeper_tpu.engine import jax_driver as jd_mod
    from gatekeeper_tpu.engine.jax_driver import JaxDriver
    from gatekeeper_tpu.webhook.policy import ValidationHandler
    from gatekeeper_tpu.webhook.server import WebhookServer
    out = {}
    pods = random.Random(seed).sample(north["resources"],
                                      min(batch, len(north["resources"])))
    pods = copy.deepcopy(pods)
    batch_oracle = oracle.submit(oracle_reviews, north["templates"],
                                 north["constraints"], pods)

    jd = JaxDriver()
    client = new_client(jd, [d for _, d in _load_dir("templates")],
                        [d for _, d in _load_dir("constraints")])
    server = WebhookServer(ValidationHandler(client), port=0)
    server.start()
    try:
        good = _load_dir("good_resources")
        for name, obj in good:
            resp = _post(server.port, review_envelope(obj))
            check(resp["allowed"] is True,
                  f"good {name} was not admitted: {resp.get('status')}")
            client.add_data(obj)
        denied = 0
        for name, obj in _load_dir("bad_resources"):
            resp = _post(server.port, review_envelope(obj))
            check(resp["allowed"] is False
                  and resp["status"]["code"] == 403,
                  f"bad {name} was not denied with 403: {resp}")
            check(resp["status"]["message"] == EXPECTED_DENY[name],
                  f"bad {name}: {resp['status']['message']!r} is not "
                  f"{EXPECTED_DENY[name]!r}")
            denied += 1
        out["webhook_denied"], out["webhook_admitted"] = denied, len(good)
    finally:
        server.stop()
    assert_healthy(jd, "webhook")

    client, jd = north["client"], north["driver"]
    pairs = len(pods) * len(north["constraints"])
    check(pairs >= jd_mod.REVIEW_BATCH_MIN_EVALS,
          f"review batch of {pairs} pairs stays below "
          f"REVIEW_BATCH_MIN_EVALS={jd_mod.REVIEW_BATCH_MIN_EVALS}")
    d0 = jd.executor.dispatches
    b0 = jd.metrics.counter("review_batches_device").value
    t0 = time.perf_counter()
    resps = client.review_batch([review_envelope(o) for o in pods])
    out["review_batch_s"] = time.perf_counter() - t0
    out["review_batch_dispatches"] = jd.executor.dispatches - d0
    check(jd.metrics.counter("review_batches_device").value == b0 + 1
          and out["review_batch_dispatches"] > 0,
          "review_batch did not take the device path")
    assert_healthy(jd, "review batch")
    out["review_batch"] = {"reviews": len(pods), "pairs": pairs, **compare(
        "review batch", review_verdicts([r.results() for r in resps]),
        batch_oracle.result())}
    return out


def phase_mesh(n: int, per_kind: int, seed: int) -> dict:
    """The north-star capped audit on the (c, r) mesh over every visible
    device, then on one chip (GATEKEEPER_SHARDS=1), same process."""
    import gc

    from gatekeeper_tpu.engine.jax_driver import JaxDriver
    templates, constraints, resources = north_star_inventory(
        n, per_kind, seed)
    out = {"n_resources": n, "n_constraints": len(constraints)}
    for label, shards in (("mesh", None), ("one_chip", "1")):
        if shards is None:
            os.environ.pop("GATEKEEPER_SHARDS", None)
        else:
            os.environ["GATEKEEPER_SHARDS"] = shards
        jd = JaxDriver()
        if shards is None:
            check(jd.executor.mesh is not None, "no mesh was built")
            out["mesh_shape"] = dict(jd.executor.mesh.shape)
        client = new_client(jd, templates, constraints, resources)
        t0 = time.perf_counter()
        res = audit_results(client, CAP)
        cold = time.perf_counter() - t0
        shard = jd.last_sweep_phases.get("shard")
        t0 = time.perf_counter()
        again = audit_results(client, CAP)
        steady = time.perf_counter() - t0
        assert_healthy(jd, label)
        check(verdicts(again) == verdicts(res), f"{label}: steady differs")
        rec = {"cold_s": cold, "steady_s": steady,
               "capped_results": len(res), "digest": digest(verdicts(res)),
               "shard": shard,
               "memory": device_memory()}
        out[label] = rec
        say(f"{label}: {json.dumps(rec)}")
        del client, jd, res, again
        gc.collect()
    check(out["mesh"]["shard"]["kinds_sharded"] == len(templates),
          f"not every kind ran sharded: {out['mesh']['shard']}")
    check(out["mesh"]["digest"] == out["one_chip"]["digest"],
          "sharded and one-chip digests differ")
    return out


# -- entry point --------------------------------------------------------------

def _versions() -> dict:
    from importlib import metadata

    import jax
    out = {"jax": jax.__version__}
    try:
        out["libtpu"] = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        out["libtpu"] = None
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=42)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    if args.chips == 1:
        os.environ["GATEKEEPER_SHARDS"] = "1"

    import jax
    from gatekeeper_tpu import native
    from gatekeeper_tpu.utils.device_probe import probe_devices
    t_start = time.perf_counter()
    res = probe_devices()
    check(res.ok, f"device probe failed: {res.reason}")
    devs = jax.devices()
    dev = devs[0]
    check(dev.platform == "tpu", f"platform is {dev.platform!r}, not tpu")
    check(len(devs) >= args.chips,
          f"{len(devs)} device(s) visible, --chips {args.chips} asked")
    count = 1 if args.chips == 1 else len(devs)
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"visible={len(devs)} used={count} {_versions()}")
    say(f"native ingest extension loaded: {native.available}")

    from gatekeeper_tpu.utils.compile_cache import enable_persistent_cache
    say(f"compile cache: {enable_persistent_cache() or 'off'}")
    c0 = cache_stats()

    if args.chips == 4:
        rec = phase_mesh(N_PODS, PER_KIND, args.seed)
        say(f"mesh {rec['mesh_shape']}: sharded digest "
            f"{rec['mesh']['digest']} == one-chip digest "
            f"{rec['one_chip']['digest']}")
    else:
        oracle = Oracle(ORACLE_WORKERS)
        try:
            ns = phase_audit(N_PODS, PER_KIND, SUBSAMPLE, args.seed, oracle)
            say("audit: " + json.dumps(
                {k: v for k, v in ns.items() if k not in
                 ("client", "driver", "templates", "constraints",
                  "resources")}))
            say(f"memory after audit: {device_memory()}")
            adm = phase_admission(ns, BATCH, args.seed, oracle)
            say("admission: " + json.dumps(adm))
            del ns
            import gc
            gc.collect()
            lib = phase_library(LIBRARY_N, SUBSAMPLE, args.seed + 10,
                                oracle)
            say("library: " + json.dumps(lib))
            say(f"library: {lib['device_kinds']} kinds dispatched on the "
                f"device, {len(lib['scalar_kinds'])} stayed scalar: "
                f"{lib['scalar_kinds']}")
        finally:
            oracle.close()
    say(f"memory: {device_memory()}")
    c1 = cache_stats()
    say(f"persistent compile cache: hits={c1['hits'] - c0['hits']} "
        f"misses={c1['misses'] - c0['misses']} "
        f"requests={c1['requests'] - c0['requests']}")
    say(f"wall: {time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
