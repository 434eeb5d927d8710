"""Regression tests for the round-1 advisor findings (ADVICE.md).

Each test reproduces a soundness bug the advisor demonstrated and
asserts the fixed behavior: the two drivers must agree (either because
the device path is now exact, or because the lowerer correctly refuses
and falls back to the scalar oracle)."""

import numpy as np
import pytest

from gatekeeper_tpu.client.client import Backend
from gatekeeper_tpu.client.interface import QueryOpts
from gatekeeper_tpu.client.local_driver import LocalDriver
from gatekeeper_tpu.engine.jax_driver import JaxDriver
from gatekeeper_tpu.target.k8s import K8sValidationTarget
from tests.test_jax_driver import constraint_doc, template_doc


def _pair():
    local = Backend(LocalDriver()).new_client([K8sValidationTarget()])
    jx = Backend(JaxDriver()).new_client([K8sValidationTarget()])
    return local, jx


def _key(r):
    return (r.msg, (r.constraint.get("metadata") or {}).get("name"),
            (r.resource or {}).get("metadata", {}).get("name"))


def _audit_keys(client):
    return [_key(r) for r in client.audit().results()]


NEGATED_FN = """package negatedfn
f(obj) = v { v := obj.enabled }
violation[{"msg": msg}] {
  not f(input.review.object.spec)
  msg := "not enabled"
}
"""


def test_negated_inlined_function_head_value():
    """ADVICE high #1: `not f(x)` where f has a computed head value must
    not silently under-approximate on device (f fires-as-true even when
    v would be false; negation flips over- into under-approximation).
    The lowerer must refuse (scalar fallback) and both drivers agree."""
    local, jx = _pair()
    for c in (local, jx):
        c.add_template(template_doc("NegFn", NEGATED_FN))
        c.add_constraint(constraint_doc("NegFn", "nf"))
        c.add_data({"apiVersion": "v1", "kind": "Namespace",
                    "metadata": {"name": "a"}, "spec": {"enabled": False}})
        c.add_data({"apiVersion": "v1", "kind": "Namespace",
                    "metadata": {"name": "b"}, "spec": {}})
        c.add_data({"apiVersion": "v1", "kind": "Namespace",
                    "metadata": {"name": "c"}, "spec": {"enabled": True}})
    lk, jk = _audit_keys(local), _audit_keys(jx)
    # enabled:false -> f(x)=false -> not f(x) fires; absent -> undefined
    # -> fires; enabled:true -> no violation
    assert len(lk) == 2
    assert lk == jk


LAUNDERED_FN = """package launderedfn
f(obj) = v { v := obj.enabled }
g(b) { b }
violation[{"msg": msg}] {
  x := f(input.review.object.spec)
  not g(x)
  msg := "not enabled"
}
"""


def test_negated_function_laundered_through_wrapper():
    """Exactness must propagate through env vars into wrapper
    functions: `x := f(...); not g(x)` is the same under-approximation
    as `not f(...)` one level removed."""
    local, jx = _pair()
    for c in (local, jx):
        c.add_template(template_doc("LaunderFn", LAUNDERED_FN))
        c.add_constraint(constraint_doc("LaunderFn", "lf"))
        c.add_data({"apiVersion": "v1", "kind": "Namespace",
                    "metadata": {"name": "a"}, "spec": {"enabled": False}})
        c.add_data({"apiVersion": "v1", "kind": "Namespace",
                    "metadata": {"name": "c"}, "spec": {"enabled": True}})
    lk, jk = _audit_keys(local), _audit_keys(jx)
    # x := f(spec) requires f defined: enabled:false -> x=false -> g(x)
    # undefined -> not g(x) fires.  enabled:true -> g fires -> no viol.
    assert len(lk) == 1
    assert lk == jk


NEGATED_TRUE_FN = """package negtruefn
is_special(obj) { obj.tier == "special" }
violation[{"msg": msg}] {
  not is_special(input.review.object.spec)
  msg := "not special"
}
"""


def test_negated_boolean_function_still_lowers():
    """Functions without computed head values (fire == true) are exact:
    negation must still lower to device and agree with the oracle."""
    local, jx = _pair()
    for c in (local, jx):
        c.add_template(template_doc("NegTrue", NEGATED_TRUE_FN))
        c.add_constraint(constraint_doc("NegTrue", "nt"))
        c.add_data({"apiVersion": "v1", "kind": "Namespace",
                    "metadata": {"name": "a"}, "spec": {"tier": "special"}})
        c.add_data({"apiVersion": "v1", "kind": "Namespace",
                    "metadata": {"name": "b"}, "spec": {"tier": "basic"}})
    st = jx.driver._state("admission.k8s.gatekeeper.sh")
    assert st.templates["NegTrue"].vectorized is not None, \
        "exact negated function should not force scalar fallback"
    lk, jk = _audit_keys(local), _audit_keys(jx)
    assert len(lk) == 1
    assert lk == jk


COMPOUND_EQ = """package compoundeq
violation[{"msg": msg}] {
  input.review.object.spec.sel == input.constraint.spec.parameters.sel
  msg := "selector collision"
}
"""


def test_compound_equality_fires_on_device():
    """ADVICE high #2: equality between compound (list/object) values
    must fire on device — compounds now intern a canonical serialization
    in the encoded-value namespace."""
    local, jx = _pair()
    for c in (local, jx):
        c.add_template(template_doc("CompoundEq", COMPOUND_EQ))
        c.add_constraint(constraint_doc("CompoundEq", "ce",
                                        {"sel": ["a", "b"]}))
        c.add_data({"apiVersion": "v1", "kind": "Namespace",
                    "metadata": {"name": "hit"}, "spec": {"sel": ["a", "b"]}})
        c.add_data({"apiVersion": "v1", "kind": "Namespace",
                    "metadata": {"name": "order"}, "spec": {"sel": ["b", "a"]}})
        c.add_data({"apiVersion": "v1", "kind": "Namespace",
                    "metadata": {"name": "miss"}, "spec": {"sel": ["a"]}})
        c.add_data({"apiVersion": "v1", "kind": "Namespace",
                    "metadata": {"name": "scalar"}, "spec": {"sel": "a"}})
    st = jx.driver._state("admission.k8s.gatekeeper.sh")
    assert st.templates["CompoundEq"].vectorized is not None
    lk, jk = _audit_keys(local), _audit_keys(jx)
    assert len(lk) == 1 and lk[0][2] == "hit"
    assert lk == jk


COMPOUND_OBJ_EQ = """package compoundobjeq
violation[{"msg": "m"}] {
  input.review.object.spec.cfg == input.constraint.spec.parameters.cfg
}
"""


def test_compound_object_equality_key_order_independent():
    """Object equality ignores key order (canonical serialization)."""
    local, jx = _pair()
    for c in (local, jx):
        c.add_template(template_doc("CfgEq", COMPOUND_OBJ_EQ))
        c.add_constraint(constraint_doc("CfgEq", "ce",
                                        {"cfg": {"x": 1, "y": [2, 3]}}))
        c.add_data({"apiVersion": "v1", "kind": "Namespace",
                    "metadata": {"name": "hit"},
                    "spec": {"cfg": {"y": [2, 3], "x": 1}}})
        c.add_data({"apiVersion": "v1", "kind": "Namespace",
                    "metadata": {"name": "miss"},
                    "spec": {"cfg": {"x": 1, "y": [3, 2]}}})
    lk, jk = _audit_keys(local), _audit_keys(jx)
    assert len(lk) == 1 and lk[0][2] == "hit"
    assert lk == jk


def test_topk_limit_exceeds_padded_rows():
    """ADVICE medium #1: a capped audit whose limit exceeds the padded
    resource count must not crash lax.top_k (k is clamped)."""
    _, jx = _pair()
    jx.add_template(template_doc("CompoundEq", COMPOUND_EQ))
    jx.add_constraint(constraint_doc("CompoundEq", "ce", {"sel": ["a"]}))
    for i in range(5):
        jx.add_data({"apiVersion": "v1", "kind": "Namespace",
                     "metadata": {"name": f"n{i}"}, "spec": {"sel": ["a"]}})
    res = jx.driver.query_audit("admission.k8s.gatekeeper.sh",
                                QueryOpts(limit_per_constraint=20))[0]
    assert len(res) == 5


def test_sharded_audit_with_constraint_only_literal():
    """ADVICE medium #2: cb<N> per-constraint bool bindings must shard
    over 'c' (a template with a constraint-only literal on a c-sharded
    mesh used to crash with a broadcast TypeError)."""
    from gatekeeper_tpu.api.templates import compile_target_rego
    from gatekeeper_tpu.engine.veval import ProgramExecutor
    from gatekeeper_tpu.ir.lower import lower_template
    from gatekeeper_tpu.ir.prep import build_bindings
    from gatekeeper_tpu.parallel.sharding import make_mesh, run_sharded_audit
    from gatekeeper_tpu.store.table import ResourceMeta, ResourceTable

    rego = """package cbl
violation[{"msg": "m"}] {
  input.constraint.spec.parameters.enforce == true
  input.review.object.spec.replicas > 3
}
"""
    compiled = compile_target_rego("CBL", "admission.k8s.gatekeeper.sh", rego)
    lowered = lower_template(compiled.module, compiled.interp)
    assert any(cv.name.startswith("cb") for cv in lowered.spec.cvals), \
        "expected a constraint-only (cb) binding in this template"
    table = ResourceTable()
    for i in range(16):
        table.upsert(f"cluster/v1/Deployment/d{i}",
                     {"apiVersion": "v1", "kind": "Deployment",
                      "metadata": {"name": f"d{i}"},
                      "spec": {"replicas": i}},
                     ResourceMeta("v1", "Deployment", f"d{i}", None))
    constraints = [
        {"kind": "CBL", "metadata": {"name": f"c{j}"},
         "spec": {"parameters": {"enforce": j % 2 == 0}}}
        for j in range(4)
    ]
    bindings = build_bindings(lowered.spec, table, constraints)
    mesh = make_mesh(8)
    counts, rows, valid = run_sharded_audit(lowered.program, bindings, mesh, k=5)
    ref, _, _ = ProgramExecutor().run_topk(lowered.program, bindings, 5)
    assert counts.tolist() == ref.tolist()
    assert counts.tolist() == [12, 0, 12, 0]


def test_capped_subset_matches_scalar_after_churn():
    """ADVICE low: after deletes/re-inserts the device cap must pick the
    same subset as the scalar driver (rank-ordered top-k, not raw row
    index)."""
    local, jx = _pair()
    for c in (local, jx):
        c.add_template(template_doc("CompoundEq", COMPOUND_EQ))
        c.add_constraint(constraint_doc("CompoundEq", "ce", {"sel": ["a"]}))
        for i in range(12):
            c.add_data({"apiVersion": "v1", "kind": "Namespace",
                        "metadata": {"name": f"n{i:02d}"},
                        "spec": {"sel": ["a"]}})
        # churn: delete early rows, re-add with names sorting first —
        # their table rows are recycled out of cache-key order
        for i in range(4):
            c.remove_data({"apiVersion": "v1", "kind": "Namespace",
                           "metadata": {"name": f"n{i:02d}"}})
        for i in range(4):
            c.add_data({"apiVersion": "v1", "kind": "Namespace",
                        "metadata": {"name": f"a{i:02d}"},
                        "spec": {"sel": ["a"]}})
    # the scalar driver applies no cap (the reference caps in the audit
    # manager, manager.go:161-199); the capped device subset must equal
    # the first-k of the scalar driver's (sorted-cache-key) order
    lres = local.driver.query_audit("admission.k8s.gatekeeper.sh")[0]
    jres = jx.driver.query_audit("admission.k8s.gatekeeper.sh",
                                 QueryOpts(limit_per_constraint=5))[0]
    assert len(lres) == 12 and len(jres) == 5
    assert [_key(r) for r in lres[:5]] == [_key(r) for r in jres]

    # the sharded path with the same rank must pick the same subset
    st = jx.driver._state("admission.k8s.gatekeeper.sh")
    compiled = st.templates["CompoundEq"]
    from gatekeeper_tpu.ir.prep import build_bindings
    from gatekeeper_tpu.parallel.sharding import make_mesh, run_sharded_audit
    constraints = jx.driver._kind_constraints(st, "CompoundEq")
    bindings = build_bindings(compiled.vectorized.spec, st.table, constraints)
    _ordered_rows, row_order = jx.driver._ensure_order(st)
    rank = jx.driver._row_rank(st, row_order)
    mesh = make_mesh(8)
    counts, rows, valid = run_sharded_audit(
        compiled.vectorized.program, bindings, mesh, k=5, rank=rank)
    sharded_names = [
        st.table.meta_at(int(r)).name
        for r, v in zip(rows[0], valid[0]) if v]
    scalar_first5 = [(r.review or {}).get("object", {})["metadata"]["name"]
                     for r in lres[:5]]
    assert sorted(sharded_names) == sorted(scalar_first5)


NEG_PARAM_PRED = """package negparam
violation[{"msg": msg}] {
  p := input.constraint.spec.parameters.pats[_]
  container := input.review.object.spec.containers[_]
  not startswith(container.image, p)
  msg := sprintf("container %v misses pattern %v", [container.name, p])
}
"""

ARRAY_MEMBER_REF = """package arrmember
violation[{"msg": msg}] {
  allowed := input.constraint.spec.parameters.allowed
  container := input.review.object.spec.containers[_]
  not allowed[container.image]
  msg := sprintf("image %v not allowed", [container.image])
}
"""

SET_MEMBER_REF = """package setmember
violation[{"msg": msg}] {
  allowed := {a | a := input.constraint.spec.parameters.allowed[_]}
  container := input.review.object.spec.containers[_]
  not allowed[container.image]
  msg := sprintf("image %v not in set", [container.image])
}
"""


def _pod(i, image):
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": f"p{i:03d}", "namespace": "d"},
            "spec": {"containers": [{"name": "c", "image": image}]}}


def _audit_msgs(client):
    return sorted(r.msg for r in client.audit().results())


def test_negated_param_pred_exists_not_semantics():
    """`not pred(leaf, p)` with p := params[_] fires when SOME param
    fails the predicate (the not applies per binding), not when none
    succeed.  Device path must match the oracle."""
    local, jx = _pair()
    for c in (local, jx):
        c.add_template(template_doc("NegParam", NEG_PARAM_PRED))
        c.add_constraint(constraint_doc("NegParam", "np",
                                        {"pats": ["a", "b"]}))
        c.add_data(_pod(0, "a-image"))   # fails p="b" -> violates
        c.add_data(_pod(1, "b-image"))   # fails p="a" -> violates
    st = jx.driver.state["admission.k8s.gatekeeper.sh"]
    assert st.templates["NegParam"].vectorized is not None
    l, j = _audit_msgs(local), _audit_msgs(jx)
    assert l == j
    assert len(l) == 2  # both pods fail at least one pattern


def test_array_member_ref_is_index_access():
    """`allowed[x]` on an ARRAY is index access (undefined for string
    keys), not membership; both drivers must agree."""
    local, jx = _pair()
    for c in (local, jx):
        c.add_template(template_doc("ArrMember", ARRAY_MEMBER_REF))
        c.add_constraint(constraint_doc("ArrMember", "am",
                                        {"allowed": ["good"]}))
        c.add_data(_pod(0, "good"))
        c.add_data(_pod(1, "bad"))
    st = jx.driver.state["admission.k8s.gatekeeper.sh"]
    l, j = _audit_msgs(local), _audit_msgs(jx)
    assert l == j
    assert len(l) == 2  # array["good"] is undefined -> both violate


def test_set_member_ref_is_membership():
    local, jx = _pair()
    for c in (local, jx):
        c.add_template(template_doc("SetMember", SET_MEMBER_REF))
        c.add_constraint(constraint_doc("SetMember", "sm",
                                        {"allowed": ["good"]}))
        c.add_data(_pod(0, "good"))
        c.add_data(_pod(1, "bad"))
    st = jx.driver.state["admission.k8s.gatekeeper.sh"]
    assert st.templates["SetMember"].vectorized is not None
    l, j = _audit_msgs(local), _audit_msgs(jx)
    assert l == j
    assert l == ["image bad not in set"]


EMBEDDED_NEG_PRED = """package embneg
violation[{"msg": msg}] {
  container := input.review.object.spec.containers[_]
  not startswith(container.image, input.constraint.spec.parameters.pats[_])
  msg := sprintf("image %v matches no pattern", [container.image])
}
"""


def test_negated_embedded_iteration_is_not_exists():
    """`not pred(x, params[_])` (iteration INSIDE the negation) is
    negation-as-failure over the local existential: fires only when NO
    param satisfies.  Contrast test_negated_param_pred_exists_not_semantics
    where p is generator-bound outside the not.  The raw device mask
    must be exact, not merely over-approximating."""
    local, jx = _pair()
    for c in (local, jx):
        c.add_template(template_doc("EmbNeg", EMBEDDED_NEG_PRED))
        c.add_constraint(constraint_doc("EmbNeg", "en", {"pats": ["a", "b"]}))
        c.add_data(_pod(0, "a-image"))   # matches "a" -> no violation
        c.add_data(_pod(1, "c-image"))   # matches none -> violates
    st = jx.driver.state["admission.k8s.gatekeeper.sh"]
    assert st.templates["EmbNeg"].vectorized is not None
    l, j = _audit_msgs(local), _audit_msgs(jx)
    assert l == j == ["image c-image matches no pattern"]
    # raw mask exactness (counts feed run_sharded_audit / status totals)
    from gatekeeper_tpu.engine.veval import ProgramExecutor
    compiled = st.templates["EmbNeg"]
    cons = jx.driver._kind_constraints(st, "EmbNeg")
    b = jx.driver._kind_bindings(st, "EmbNeg", compiled, cons)
    mask = ProgramExecutor().run(compiled.vectorized.program, b)
    assert mask.sum() == 1


INLINED_PROBE_NEG = """package inlp
f(c, probe) { not c[probe] }
violation[{"msg": msg}] {
  container := input.review.object.spec.containers[_]
  probe := input.constraint.spec.parameters.probes[_]
  not f(container, probe)
  msg := sprintf("has %v on %v", [probe, container.name])
}
"""

CVALID_AFTER_PROBE = """package cvp
violation[{"msg": msg}] {
  container := input.review.object.spec.containers[_]
  probe := input.constraint.spec.parameters.probes[_]
  not container[probe]
  probe != "startupProbe"
  msg := sprintf("missing %v", [probe])
}
"""


def test_elem_key_missing_not_renegated_through_inline():
    """`not f(container, probe)` with f wrapping `not c[probe]`: the
    double negation must not under-approximate (template falls back)."""
    local, jx = _pair()
    for c in (local, jx):
        c.add_template(template_doc("InlP", INLINED_PROBE_NEG))
        c.add_constraint(constraint_doc("InlP", "i", {"probes": ["a", "b"]}))
        c.add_data({"apiVersion": "v1", "kind": "Pod",
                    "metadata": {"name": "p", "namespace": "d"},
                    "spec": {"containers": [{"name": "c1", "b": {"x": 1}}]}})
    l = sorted(r.msg for r in local.audit().results())
    j = sorted(r.msg for r in jx.audit().results())
    assert l == j == ["has b on c1"]


def test_constraint_literal_on_generator_var_falls_back_cleanly():
    """A constraint-only literal referencing the generator-bound probe
    must reject at lower time (scalar fallback), not crash the sweep."""
    local, jx = _pair()
    for c in (local, jx):
        c.add_template(template_doc("Cvp", CVALID_AFTER_PROBE))
        c.add_constraint(constraint_doc(
            "Cvp", "c", {"probes": ["livenessProbe", "startupProbe"]}))
        c.add_data({"apiVersion": "v1", "kind": "Pod",
                    "metadata": {"name": "p", "namespace": "d"},
                    "spec": {"containers": [{"name": "c1"}]}})
    l = sorted(r.msg for r in local.audit().results())
    j = sorted(r.msg for r in jx.audit().results())
    assert l == j == ["missing livenessProbe"]


POSITIVE_INLINED_PROBE = """package posp
lacks(c, probe) { not c[probe] }
violation[{"msg": msg}] {
  container := input.review.object.spec.containers[_]
  probe := input.constraint.spec.parameters.probes[_]
  lacks(container, probe)
  msg := sprintf("lacks %v on %v", [probe, container.name])
}
"""


def test_positive_inlined_probe_stays_vectorized():
    """A POSITIVE inlined wrapper around `not c[probe]` keeps the
    device path (only re-negation must fall back)."""
    local, jx = _pair()
    for c in (local, jx):
        c.add_template(template_doc("PosP", POSITIVE_INLINED_PROBE))
        c.add_constraint(constraint_doc("PosP", "p", {"probes": ["a", "b"]}))
        c.add_data({"apiVersion": "v1", "kind": "Pod",
                    "metadata": {"name": "p", "namespace": "d"},
                    "spec": {"containers": [{"name": "c1", "b": {"x": 1}}]}})
    st = jx.driver.state["admission.k8s.gatekeeper.sh"]
    assert st.templates["PosP"].vectorized is not None
    l = sorted(r.msg for r in local.audit().results())
    j = sorted(r.msg for r in jx.audit().results())
    assert l == j == ["lacks a on c1"]
