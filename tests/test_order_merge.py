"""The audit row order across key changes.

The driver keeps the sorted key array and merges the keys the table
logged as inserted or removed into it, instead of sorting every key
again.  After any sequence of creates,
deletes, re-creates, pure updates and reused free rows the merged order,
its row -> position map and the rank array equal a fresh sort's; a wipe,
a compact, a log past its bound and another table object each fall back
to a full sort.
"""

import random

import numpy as np
import pytest

from gatekeeper_tpu.client.client import Backend
from gatekeeper_tpu.client.interface import QueryOpts
from gatekeeper_tpu.client.local_driver import LocalDriver
from gatekeeper_tpu.engine import jax_driver as jd_mod
from gatekeeper_tpu.library.workload import make_pods, north_star_docs
from gatekeeper_tpu.obs import trace as trace_mod
from gatekeeper_tpu.store import table as table_mod
from gatekeeper_tpu.store.table import ResourceMeta, ResourceTable
from gatekeeper_tpu.target.k8s import K8sValidationTarget

TARGET = "admission.k8s.gatekeeper.sh"


def _key(ns: int, pod: int, name: str = "") -> str:
    return f"namespace/ns-{ns:02d}/v1/Pod/{name or f'pod{pod:08d}'}"


def _put(table: ResourceTable, key: str, tag: int = 0) -> int:
    name = key.rsplit("/", 1)[1]
    return table.upsert(key, {"metadata": {"name": name}, "tag": tag},
                        ResourceMeta("v1", "Pod", name, "ns"))


@pytest.fixture
def driver():
    return jd_mod.JaxDriver()


def _fresh(driver, table):
    """Order, row -> position and rank of a full sort of ``table``."""
    st = jd_mod.JaxTargetState()
    st.table = table
    ordered, row_order = driver._ensure_order(st)
    return ordered, row_order, driver._row_rank(st, row_order)


def _assert_fresh(driver, st):
    ordered, row_order = driver._ensure_order(st)
    rank = driver._row_rank(st, row_order)
    f_ordered, f_row_order, f_rank = _fresh(driver, st.table)
    assert ordered == f_ordered
    assert isinstance(row_order, jd_mod._RowOrder)
    assert len(row_order) == len(f_row_order)
    np.testing.assert_array_equal(row_order._pos, f_row_order._pos)
    np.testing.assert_array_equal(rank, f_rank)
    return st.order_cache.merged


def _table(n: int = 200) -> tuple[ResourceTable, list[str]]:
    t = ResourceTable()
    keys = [_key(i % 7, i) for i in range(n)]
    for k in keys:
        _put(t, k)
    return t, keys


def test_key_log_reports_inserted_and_removed_keys():
    t, keys = _table(10)
    kgen = t.key_generation
    assert t.keys_changed_since(kgen) == set()
    _put(t, keys[0], tag=1)                 # a pure update logs nothing
    assert t.key_generation == kgen
    t.remove(keys[1])
    _put(t, _key(0, 99))
    assert t.keys_changed_since(kgen) == {keys[1], _key(0, 99)}
    assert t.keys_changed_since(kgen + 1) == {_key(0, 99)}
    assert t.keys_changed_since(t.key_generation + 1) is None


@pytest.mark.parametrize("reset", ["wipe", "compact"])
def test_key_log_does_not_span_a_wipe_or_compact(reset):
    t, keys = _table(10)
    kgen = t.key_generation
    t.remove(keys[0])
    getattr(t, reset)()
    assert t.keys_changed_since(kgen) is None
    assert t.keys_changed_since(t.key_generation) == set()


def test_key_log_drops_its_older_half_past_the_bound(monkeypatch):
    monkeypatch.setattr(table_mod, "KEY_LOG_MIN", 16)
    t, keys = _table(10)
    kgen = t.key_generation
    for i in range(17):
        _put(t, _key(1, 100 + i))
    assert t.keys_changed_since(kgen) is None
    recent = t.key_generation - 5
    assert t.keys_changed_since(recent) == {_key(1, 100 + i)
                                            for i in range(12, 17)}


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_merged_order_equals_a_fresh_sort_after_every_step(
        driver, seed):
    rng = random.Random(seed)
    t, keys = _table()
    live = set(keys)
    gone: list[str] = []
    st = jd_mod.JaxTargetState()
    st.table = t
    assert _assert_fresh(driver, st) == 0           # the first sort
    merged_steps = 0
    for step in range(30):
        before, cache = t.key_generation, st.order_cache
        op = rng.choice(["create", "delete", "recreate", "update",
                         "mixed", "long"])
        if op in ("create", "mixed"):
            # free rows left by earlier deletes are reused first
            for _ in range(rng.randint(1, 6)):
                _put(t, _key(rng.randrange(9), rng.randrange(10**6)))
        if op in ("delete", "mixed"):
            for k in rng.sample(sorted(live), rng.randint(1, 6)):
                t.remove(k)
                gone.append(k)
        if op == "recreate":
            # the same key at another row, and an old key back
            k = rng.choice(sorted(live))
            t.remove(k)
            _put(t, _key(0, 10**7 + step))          # takes k's free row
            _put(t, k)
            if gone:
                _put(t, gone.pop())
        if op == "update":
            for k in rng.sample(sorted(live), 5):
                _put(t, k, tag=step)
        if op == "long":
            # longer than any key the cached array was sized for
            _put(t, _key(3, 0, name="pod-" + "x" * (40 + step)))
        live = {k for k, _ in t.rows_items()}
        changed = t.keys_changed_since(before)
        merged = _assert_fresh(driver, st)
        if op == "update":
            assert st.order_cache is cache
        else:
            assert merged == len(changed) > 0
            merged_steps += 1
    assert merged_steps > 0


def test_a_key_created_and_deleted_between_orders_is_ignored(driver):
    t, keys = _table(50)
    st = jd_mod.JaxTargetState()
    st.table = t
    _assert_fresh(driver, st)
    _put(t, _key(1, 777))
    t.remove(_key(1, 777))
    t.remove(keys[3])
    assert _assert_fresh(driver, st) == 2


def test_a_cache_hit_keeps_the_order_object(driver):
    t, _keys = _table(50)
    st = jd_mod.JaxTargetState()
    st.table = t
    first = driver._ensure_order(st)
    _put(t, _key(0, 0), tag=9)              # pure update: same key set
    again = driver._ensure_order(st)
    assert again[0] is first[0] and again[1] is first[1]


@pytest.mark.parametrize("fallback", ["wipe", "compact", "overflow",
                                      "table"])
def test_each_fallback_sorts_from_scratch(driver, monkeypatch, fallback):
    monkeypatch.setattr(table_mod, "KEY_LOG_MIN", 16)
    t, keys = _table(60)
    st = jd_mod.JaxTargetState()
    st.table = t
    _assert_fresh(driver, st)
    t.remove(keys[0])
    if fallback == "wipe":
        t.wipe()
        for k in keys[:30]:
            _put(t, k)
    elif fallback == "compact":
        t.compact()
    elif fallback == "overflow":
        for i in range(40):
            _put(t, _key(2, 5000 + i))
    else:
        other, _ = _table(60)
        st.table = other
    assert _assert_fresh(driver, st) == 0


def test_small_tables_keep_the_dict_order(driver):
    """A table of a few keys takes the same mergeable order as a large
    one: a ``_RowOrder`` equal to a fresh sort, before and after a
    delete merged into it."""
    t, keys = _table(5)
    st = jd_mod.JaxTargetState()
    st.table = t
    assert _assert_fresh(driver, st) == 0
    t.remove(keys[0])
    assert _assert_fresh(driver, st) == 1
    ordered, _row_order = driver._ensure_order(st)
    assert ordered == [r for _, r in sorted(t.rows_items())]


# -- audit level ------------------------------------------------------

LIMIT = 20


def _capped_expectation(results: list, limit: int) -> dict:
    """Per constraint, the full cache-key-ordered list cut as the cap
    cuts it: whole resources until at least ``limit`` results are out."""
    by_c: dict = {}
    for r in results:
        by_c.setdefault(r.constraint["metadata"]["name"], []).append(r)
    out = {}
    for name, rs in by_c.items():
        kept, n = [], 0
        for r in rs:
            if n >= limit and r.review["name"] != kept[-1][1]:
                break
            kept.append((r.msg, r.review["name"]))
            n += 1
        out[name] = kept
    return out


def _by_constraint(results: list) -> dict:
    out: dict = {}
    for r in results:
        out.setdefault(r.constraint["metadata"]["name"], []).append(
            (r.msg, r.review["name"]))
    return out


def test_memoized_capped_audit_under_churn_equals_the_oracle(monkeypatch):
    monkeypatch.setattr(jd_mod, "SMALL_WORKLOAD_EVALS", 0)
    tracer = trace_mod.Tracer(ring=4096)
    monkeypatch.setattr(trace_mod, "_tracer", tracer)
    rng = random.Random(25)
    jx = Backend(jd_mod.JaxDriver()).new_client([K8sValidationTarget()])
    local = Backend(LocalDriver()).new_client([K8sValidationTarget()])
    templates, constraints = north_star_docs(rng, 1)
    pods = make_pods(2000, rng)
    for c in (jx, local):
        for t in templates:
            c.add_template(t)
        for con in constraints:
            c.add_constraint(con)
        c.add_data_batch(pods)
    live = {p["metadata"]["name"]: p for p in pods}
    for cycle in range(4):
        if cycle:
            names = rng.sample(sorted(live), 30)
            upd = [dict(live[n], metadata=dict(
                live[n]["metadata"], labels={"l1": "v"})) for n in names[:20]]
            new = make_pods(15, rng)
            for p, n in zip(new, rng.sample(range(2000), 15)):
                # new names land between existing keys
                p["metadata"]["name"] = f"pod{n:07d}x{cycle}"
                p["metadata"]["namespace"] = f"ns{n % 50}"
            for c in (jx, local):
                c.add_data_batch(upd + new)
                for n in names[20:]:
                    c.remove_data(live[n])
            for p in upd + new:
                live[p["metadata"]["name"]] = p
            for n in names[20:]:
                live.pop(n)
        got = jx.driver.query_audit(
            TARGET, QueryOpts(limit_per_constraint=LIMIT))[0]
        want = local.driver.query_audit(TARGET)[0]
        assert _by_constraint(got) == _capped_expectation(want, LIMIT), \
            f"cycle {cycle}"
    order = [e["args"] for e in tracer.export()["traceEvents"]
             if e["name"] == "audit.order"]
    assert [a["resorted"] for a in order] == [True] * 4
    assert order[0]["merged"] == 0                  # the first full sort
    assert all(a["merged"] == 25 for a in order[1:]), order
