"""Lowerer tests: lowered programs must agree with the scalar oracle on
the violation decision for every (constraint, resource) pair.

The contract is one-sided in general (the device mask may over-
approximate; host re-evaluation formats only real violations), but for
the fully-lowerable templates below the masks should be exact.
"""

import numpy as np
import pytest

from gatekeeper_tpu.api.templates import compile_target_rego
from gatekeeper_tpu.engine.veval import ProgramExecutor
from gatekeeper_tpu.ir.lower import CannotLower, lower_template
from gatekeeper_tpu.ir.prep import build_bindings
from gatekeeper_tpu.rego.values import Obj, freeze
from gatekeeper_tpu.store.table import ResourceMeta, ResourceTable

REQUIRED_LABELS = """package k8srequiredlabels
violation[{"msg": msg, "details": {"missing_labels": missing}}] {
  provided := {label | input.review.object.metadata.labels[label]}
  required := {label | label := input.constraint.spec.parameters.labels[_]}
  missing := required - provided
  count(missing) > 0
  msg := sprintf("you must provide labels: %v", [missing])
}
"""

ALLOWED_REPOS = """package k8sallowedrepos
violation[{"msg": msg}] {
  container := input.review.object.spec.containers[_]
  satisfied := [good | repo = input.constraint.spec.parameters.repos[_] ; good = startswith(container.image, repo)]
  not any(satisfied)
  msg := sprintf("container <%v> has an invalid image repo <%v>", [container.name, container.image])
}
"""

CONTAINER_LIMITS = """package k8scontainerlimits
missing(obj, field) = true { not obj[field] }
missing(obj, field) = true { obj[field] == "" }

canonify_cpu(orig) = new { is_number(orig); new := orig * 1000 }
canonify_cpu(orig) = new {
  not is_number(orig)
  endswith(orig, "m")
  new := to_number(replace(orig, "m", ""))
}
canonify_cpu(orig) = new {
  not is_number(orig)
  not endswith(orig, "m")
  re_match("^[0-9]+$", orig)
  new := to_number(orig) * 1000
}

violation[{"msg": msg}] {
  container := input.review.object.spec.containers[_]
  not container.resources
  msg := sprintf("container <%v> has no resource limits", [container.name])
}
violation[{"msg": msg}] {
  container := input.review.object.spec.containers[_]
  not container.resources.limits
  msg := sprintf("container <%v> has no resource limits", [container.name])
}
violation[{"msg": msg}] {
  container := input.review.object.spec.containers[_]
  missing(container.resources.limits, "cpu")
  msg := sprintf("container <%v> has no cpu limit", [container.name])
}
violation[{"msg": msg}] {
  container := input.review.object.spec.containers[_]
  cpu_orig := container.resources.limits.cpu
  not canonify_cpu(cpu_orig)
  msg := sprintf("container <%v> cpu limit <%v> could not be parsed", [container.name, cpu_orig])
}
violation[{"msg": msg}] {
  container := input.review.object.spec.containers[_]
  cpu_orig := container.resources.limits.cpu
  cpu := canonify_cpu(cpu_orig)
  max_cpu_orig := input.constraint.spec.parameters.cpu
  max_cpu := canonify_cpu(max_cpu_orig)
  cpu > max_cpu
  msg := sprintf("container <%v> cpu limit is too high", [container.name])
}
"""


def _mk_table(objs):
    t = ResourceTable()
    for i, o in enumerate(objs):
        meta = ResourceMeta(api_version=o.get("apiVersion", "v1"),
                            kind=o.get("kind", "Pod"),
                            name=o.get("metadata", {}).get("name", f"r{i}"),
                            namespace=o.get("metadata", {}).get("namespace"))
        t.upsert(f"k{i}", o, meta)
    return t


def _review(meta, obj):
    r = {"kind": {"group": meta.group, "version": meta.version, "kind": meta.kind},
         "name": meta.name, "operation": "CREATE", "object": obj}
    if meta.namespace is not None:
        r["namespace"] = meta.namespace
    return r


def oracle_mask(compiled, constraints, table):
    n = table.n_rows
    mask = np.zeros((len(constraints), n), dtype=bool)
    for r in range(n):
        meta = table.meta_at(r)
        obj = table.object_at(r)
        if meta is None:
            continue
        review = freeze(_review(meta, obj))
        for ci, c in enumerate(constraints):
            inp = Obj({"review": review, "constraint": freeze(c)})
            res = compiled.interp.query_set("violation", inp, None)
            mask[ci, r] = len(res) > 0
    return mask


def device_mask(compiled, constraints, table, exact=True):
    lowered = lower_template(compiled.module, compiled.interp)
    b = build_bindings(lowered.spec, table, constraints)
    return ProgramExecutor().run(lowered.program, b)


def check(rego, kind, constraints, objs, exact=True):
    compiled = compile_target_rego(kind, "k8s", rego)
    table = _mk_table(objs)
    dev = device_mask(compiled, constraints, table)
    orc = oracle_mask(compiled, constraints, table)
    if exact:
        assert dev.tolist() == orc.tolist(), \
            f"device:\n{dev}\noracle:\n{orc}"
    else:  # over-approximation allowed, under-approximation never
        assert (orc & ~dev).sum() == 0


def test_required_labels_lowering():
    objs = [
        {"kind": "Namespace", "metadata": {"name": "a", "labels": {"gk": "x", "o": "y"}}},
        {"kind": "Namespace", "metadata": {"name": "b", "labels": {"other": "y"}}},
        {"kind": "Namespace", "metadata": {"name": "c"}},
        {"kind": "Namespace", "metadata": {"name": "d", "labels": {"gk": "z"}}},
    ]
    cons = [
        {"kind": "K8sRequiredLabels", "metadata": {"name": "gk"},
         "spec": {"parameters": {"labels": ["gk"]}}},
        {"kind": "K8sRequiredLabels", "metadata": {"name": "both"},
         "spec": {"parameters": {"labels": ["gk", "o"]}}},
        {"kind": "K8sRequiredLabels", "metadata": {"name": "none"},
         "spec": {"parameters": {"labels": []}}},
        {"kind": "K8sRequiredLabels", "metadata": {"name": "unset"},
         "spec": {"parameters": {}}},
    ]
    check(REQUIRED_LABELS, "K8sRequiredLabels", cons, objs)


def test_allowed_repos_lowering():
    objs = [
        {"metadata": {"name": "p1"},
         "spec": {"containers": [{"name": "a", "image": "gcr.io/org/app:1"}]}},
        {"metadata": {"name": "p2"},
         "spec": {"containers": [{"name": "a", "image": "gcr.io/x"},
                                 {"name": "b", "image": "docker.io/evil"}]}},
        {"metadata": {"name": "p3"}, "spec": {"containers": []}},
        {"metadata": {"name": "p4"}},  # no spec at all
        {"metadata": {"name": "p5"},
         "spec": {"containers": [{"name": "noimg"}]}},
    ]
    cons = [
        {"kind": "K8sAllowedRepos", "metadata": {"name": "gcr"},
         "spec": {"parameters": {"repos": ["gcr.io/"]}}},
        {"kind": "K8sAllowedRepos", "metadata": {"name": "both"},
         "spec": {"parameters": {"repos": ["gcr.io/", "docker.io/"]}}},
        {"kind": "K8sAllowedRepos", "metadata": {"name": "emptylist"},
         "spec": {"parameters": {"repos": []}}},
    ]
    check(ALLOWED_REPOS, "K8sAllowedRepos", cons, objs)


def test_container_limits_lowering():
    objs = [
        {"metadata": {"name": "ok"},
         "spec": {"containers": [
             {"name": "a", "resources": {"limits": {"cpu": "100m", "memory": "1Gi"}}}]}},
        {"metadata": {"name": "no-resources"},
         "spec": {"containers": [{"name": "a"}]}},
        {"metadata": {"name": "no-limits"},
         "spec": {"containers": [{"name": "a", "resources": {}}]}},
        {"metadata": {"name": "no-cpu"},
         "spec": {"containers": [{"name": "a", "resources": {"limits": {"memory": "1Gi"}}}]}},
        {"metadata": {"name": "empty-cpu"},
         "spec": {"containers": [{"name": "a", "resources": {"limits": {"cpu": ""}}}]}},
        {"metadata": {"name": "bad-cpu"},
         "spec": {"containers": [{"name": "a", "resources": {"limits": {"cpu": "wat"}}}]}},
        {"metadata": {"name": "big-cpu"},
         "spec": {"containers": [{"name": "a", "resources": {"limits": {"cpu": "4"}}}]}},
        {"metadata": {"name": "num-cpu"},
         "spec": {"containers": [{"name": "a", "resources": {"limits": {"cpu": 2}}}]}},
    ]
    cons = [
        {"kind": "K8sContainerLimits", "metadata": {"name": "max1"},
         "spec": {"parameters": {"cpu": "1"}}},
        {"kind": "K8sContainerLimits", "metadata": {"name": "max3000m"},
         "spec": {"parameters": {"cpu": "3000m"}}},
    ]
    check(CONTAINER_LIMITS, "K8sContainerLimits", cons, objs)


def test_unlowerable_falls_back():
    rego = """package p
violation[{"msg": "m"}] {
  other := data.inventory.cluster["v1"]["Service"][_]
  other.spec.x == input.review.object.spec.x
}
"""
    compiled = compile_target_rego("P", "k8s", rego)
    with pytest.raises(CannotLower):
        lower_template(compiled.module, compiled.interp)


NESTED_ENV = """package nested
violation[{"msg": msg}] {
  container := input.review.object.spec.containers[_]
  env := container.env[_]
  re_match("(?i)(secret|token)", env.name)
  env.value
  msg := sprintf("container <%v> env <%v>", [container.name, env.name])
}
"""

NESTED_CAPS = """package nestedcaps
violation[{"msg": msg}] {
  container := input.review.object.spec.containers[_]
  cap := container.securityContext.capabilities.add[_]
  bad := input.constraint.spec.parameters.disallowed[_]
  cap == bad
  msg := sprintf("cap %v", [cap])
}
"""


class TestNestedElementAxes:
    def _pair(self):
        from gatekeeper_tpu.client.client import Backend
        from gatekeeper_tpu.client.local_driver import LocalDriver
        from gatekeeper_tpu.engine.jax_driver import JaxDriver
        from gatekeeper_tpu.target.k8s import K8sValidationTarget
        return (Backend(LocalDriver()).new_client([K8sValidationTarget()]),
                Backend(JaxDriver()).new_client([K8sValidationTarget()]))

    def _tdoc(self, kind, rego):
        return {"apiVersion": "templates.gatekeeper.sh/v1alpha1",
                "kind": "ConstraintTemplate", "metadata": {"name": kind.lower()},
                "spec": {"crd": {"spec": {"names": {"kind": kind}}},
                         "targets": [{"target": "admission.k8s.gatekeeper.sh",
                                      "rego": rego}]}}

    def _cdoc(self, kind, name, params=None):
        return {"apiVersion": "constraints.gatekeeper.sh/v1alpha1",
                "kind": kind, "metadata": {"name": name},
                "spec": ({"parameters": params} if params else {})}

    def test_nested_env_parity_and_lowered(self):
        local, jx = self._pair()
        pods = [
            # multiple containers x multiple envs, hits in different spots
            {"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": "p1", "namespace": "d"},
             "spec": {"containers": [
                 {"name": "a", "env": [{"name": "API_TOKEN", "value": "x"},
                                        {"name": "HOME", "value": "/"}]},
                 {"name": "b", "env": [{"name": "MY_SECRET", "value": "y"}]}]}},
            # env without value (valueFrom) must not fire the truthy conjunct
            {"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": "p2", "namespace": "d"},
             "spec": {"containers": [
                 {"name": "c", "env": [{"name": "TOKEN_X",
                                        "valueFrom": {"secretKeyRef": {}}}]}]}},
            # empty env / missing env / non-dict containers entries
            {"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": "p3", "namespace": "d"},
             "spec": {"containers": [{"name": "d", "env": []}, {"name": "e"}]}},
            {"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": "p4", "namespace": "d"},
             "spec": {"containers": "notalist"}},
        ]
        for c in (local, jx):
            c.add_template(self._tdoc("NestedEnv", NESTED_ENV))
            c.add_constraint(self._cdoc("NestedEnv", "ne"))
            for p in pods:
                c.add_data(p)
        st = jx.driver.state["admission.k8s.gatekeeper.sh"]
        assert st.templates["NestedEnv"].vectorized is not None
        lmsg = sorted(r.msg for r in local.audit().results())
        jmsg = sorted(r.msg for r in jx.audit().results())
        assert lmsg == jmsg
        assert lmsg == ["container <a> env <API_TOKEN>",
                        "container <b> env <MY_SECRET>"]

    def test_nested_caps_membership_parity(self):
        local, jx = self._pair()
        pods = [{"apiVersion": "v1", "kind": "Pod",
                 "metadata": {"name": f"p{i}", "namespace": "d"},
                 "spec": {"containers": [
                     {"name": "a", "securityContext": {"capabilities":
                         {"add": caps}}}]}}
                for i, caps in enumerate([["SYS_ADMIN"], ["CHOWN"],
                                          ["NET_ADMIN", "SYS_ADMIN"], []])]
        for c in (local, jx):
            c.add_template(self._tdoc("NestedCaps", NESTED_CAPS))
            c.add_constraint(self._cdoc("NestedCaps", "nc",
                                        {"disallowed": ["SYS_ADMIN", "NET_ADMIN"]}))
            for p in pods:
                c.add_data(p)
        st = jx.driver.state["admission.k8s.gatekeeper.sh"]
        assert st.templates["NestedCaps"].vectorized is not None
        lres = sorted((r.msg, (r.review or {}).get("name"))
                      for r in local.audit().results())
        jres = sorted((r.msg, (r.review or {}).get("name"))
                      for r in jx.audit().results())
        assert lres == jres
        assert len(lres) == 3  # p0: SYS_ADMIN; p2: NET_ADMIN + SYS_ADMIN

    def test_nested_axis_independent_of_rule_order(self):
        """A rule touching the parent axis must not knock a sibling
        nested-axis rule (or the whole template) off the device path."""
        from gatekeeper_tpu.api.templates import compile_target_rego
        from gatekeeper_tpu.ir.lower import lower_template
        both_orders = [
            """package t
violation[{"msg": "img"}] {
  container := input.review.object.spec.containers[_]
  container.image == "bad"
}
violation[{"msg": msg}] {
  container := input.review.object.spec.containers[_]
  env := container.env[_]
  env.name == "SECRET"
  msg := "env"
}
""",
            """package t
violation[{"msg": msg}] {
  container := input.review.object.spec.containers[_]
  env := container.env[_]
  env.name == "SECRET"
  msg := "env"
}
violation[{"msg": "img"}] {
  container := input.review.object.spec.containers[_]
  container.image == "bad"
}
""",
        ]
        for rego in both_orders:
            ct = compile_target_rego("T", "admission.k8s.gatekeeper.sh", rego)
            lp = lower_template(ct.module, ct.interp)
            assert lp.n_rules_lowered == 2, rego

    def test_keyed_label_lookup_parity(self):
        """labels[key] with a constraint-param key: per-constraint
        dynamic dict lookup must match the oracle, including absent
        keys, non-string values, false values, and missing dicts."""
        rego = """package vlv
violation[{"msg": msg}] {
  key := input.constraint.spec.parameters.key
  value := input.review.object.metadata.labels[key]
  allowed := {v | v := input.constraint.spec.parameters.allowed[_]}
  not allowed[value]
  msg := sprintf("label <%v> value <%v> not allowed", [key, value])
}
"""
        local, jx = self._pair()
        objs = [
            {"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": "a", "namespace": "d",
                          "labels": {"env": "prod", "tier": "web"}},
             "spec": {"containers": []}},
            {"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": "b", "namespace": "d",
                          "labels": {"env": "weird"}},
             "spec": {"containers": []}},
            {"apiVersion": "v1", "kind": "Pod",   # key absent
             "metadata": {"name": "c", "namespace": "d", "labels": {"x": "y"}},
             "spec": {"containers": []}},
            {"apiVersion": "v1", "kind": "Pod",   # no labels dict
             "metadata": {"name": "d", "namespace": "d"},
             "spec": {"containers": []}},
            {"apiVersion": "v1", "kind": "Pod",   # false label value
             "metadata": {"name": "e", "namespace": "d",
                          "labels": {"env": False}},
             "spec": {"containers": []}},
        ]
        for c in (local, jx):
            c.add_template(self._tdoc("Vlv", rego))
            c.add_constraint(self._cdoc("Vlv", "env-check",
                                        {"key": "env",
                                         "allowed": ["prod", "dev"]}))
            c.add_constraint(self._cdoc("Vlv", "tier-check",
                                        {"key": "tier", "allowed": ["web"]}))
            for o in objs:
                c.add_data(o)
        st = jx.driver.state["admission.k8s.gatekeeper.sh"]
        assert st.templates["Vlv"].vectorized is not None
        l = sorted((r.msg, r.constraint["metadata"]["name"])
                   for r in local.audit().results())
        j = sorted((r.msg, r.constraint["metadata"]["name"])
                   for r in jx.audit().results())
        assert l == j
        # b: env "weird" not allowed; e: env False -> not in allowed set
        assert ("label <env> value <weird> not allowed", "env-check") in l
        assert len([x for x in l if x[1] == "env-check"]) == 2

    def test_keyed_lookup_sharded(self):
        """keyed_val bindings shard correctly over the (c, r) mesh."""
        from gatekeeper_tpu.engine.veval import ProgramExecutor
        from gatekeeper_tpu.ir.prep import build_bindings
        from gatekeeper_tpu.parallel.sharding import make_mesh, run_sharded_audit
        from gatekeeper_tpu.api.templates import compile_target_rego
        from gatekeeper_tpu.ir.lower import lower_template
        rego = """package vlv
violation[{"msg": "bad"}] {
  key := input.constraint.spec.parameters.key
  value := input.review.object.metadata.labels[key]
  value != input.constraint.spec.parameters.want
}
"""
        objs = [{"kind": "Pod",
                 "metadata": {"name": f"p{i:03d}",
                              "labels": {"env": ["prod", "dev", "x"][i % 3]}}}
                for i in range(40)]
        table = _mk_table(objs)
        cons = [{"kind": "Vlv", "metadata": {"name": "c0"},
                 "spec": {"parameters": {"key": "env", "want": "prod"}}},
                {"kind": "Vlv", "metadata": {"name": "c1"},
                 "spec": {"parameters": {"key": "env", "want": "dev"}}}]
        ct = compile_target_rego("Vlv", "k8s", rego)
        lp = lower_template(ct.module, ct.interp)
        b = build_bindings(lp.spec, table, cons)
        c1, _, _ = ProgramExecutor().run_topk(lp.program, b, 5)
        c8, _, _ = run_sharded_audit(lp.program, b, make_mesh(8), k=5)
        assert c1.tolist() == c8.tolist()
        assert c1[0] > 0

    def test_keyed_int_index_into_array(self):
        """Integer constraint keys index arrays (oracle _walk_ref tuple
        semantics) — must not silently drop violations."""
        rego = """package idx
violation[{"msg": msg}] {
  i := input.constraint.spec.parameters.i
  val := input.review.object.spec.args[i]
  val == "forbidden"
  msg := sprintf("arg %v is forbidden", [i])
}
"""
        local, jx = self._pair()
        for c in (local, jx):
            c.add_template(self._tdoc("Idx", rego))
            c.add_constraint(self._cdoc("Idx", "i1", {"i": 1}))
            c.add_data({"apiVersion": "v1", "kind": "Pod",
                        "metadata": {"name": "p", "namespace": "d"},
                        "spec": {"args": ["x", "forbidden", "y"],
                                 "containers": []}})
            c.add_data({"apiVersion": "v1", "kind": "Pod",
                        "metadata": {"name": "q", "namespace": "d"},
                        "spec": {"args": ["forbidden"], "containers": []}})
        l = sorted(r.msg for r in local.audit().results())
        j = sorted(r.msg for r in jx.audit().results())
        assert l == j == ["arg 1 is forbidden"]

    def test_keyed_lookup_interner_boundary_no_alias(self):
        """Values first interned by the keyed fill must not alias onto
        cset members when the interner crosses a bucket boundary."""
        rego = """package vlv
violation[{"msg": msg}] {
  key := input.constraint.spec.parameters.key
  value := input.review.object.metadata.labels[key]
  allowed := {v | v := input.constraint.spec.parameters.allowed[_]}
  not allowed[value]
  msg := sprintf("bad %v", [value])
}
"""
        for pad in range(24):   # sweep interner sizes across a boundary
            local, jx = self._pair()
            allowed = [f"ok-{i}" for i in range(7)]
            for c in (local, jx):
                c.add_template(self._tdoc("Vlv", rego))
                c.add_constraint(self._cdoc("Vlv", "k",
                                            {"key": "env", "allowed": allowed}))
                for i in range(pad):   # vary interner fill
                    c.add_data({"apiVersion": "v1", "kind": "Namespace",
                                "metadata": {"name": f"fill-{i:02d}"}})
                for i in range(6):
                    c.add_data({"apiVersion": "v1", "kind": "Pod",
                                "metadata": {"name": f"p{i}", "namespace": "d",
                                             "labels": {"env": f"new-{i}"}},
                                "spec": {"containers": []}})
            l = sorted(r.msg for r in local.audit().results())
            j = sorted(r.msg for r in jx.audit().results())
            assert l == j, f"pad={pad}: {l} != {j}"
            assert len([m for m in l if m.startswith("bad new-")]) == 6

    def test_required_probes_elem_key_missing(self):
        """`not container[probe]` with probe := params[_]: fires per
        (container, missing probe); false values count as missing
        (statement truthiness); non-dict elements lack every key."""
        rego = """package probes
violation[{"msg": msg}] {
  container := input.review.object.spec.containers[_]
  probe := input.constraint.spec.parameters.probes[_]
  not container[probe]
  msg := sprintf("container <%v> has no <%v>", [container.name, probe])
}
"""
        local, jx = self._pair()
        pods = [
            {"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": "a", "namespace": "d"},
             "spec": {"containers": [
                 {"name": "full", "livenessProbe": {"httpGet": {}},
                  "readinessProbe": {"httpGet": {}}},
                 {"name": "half", "livenessProbe": {"httpGet": {}}},
                 {"name": "none"}]}},
            {"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": "b", "namespace": "d"},
             "spec": {"containers": [
                 {"name": "falsy", "livenessProbe": False,
                  "readinessProbe": {"x": 1}}]}},
        ]
        for c in (local, jx):
            c.add_template(self._tdoc("Probes", rego))
            c.add_constraint(self._cdoc("Probes", "p",
                                        {"probes": ["livenessProbe",
                                                    "readinessProbe"]}))
            for p in pods:
                c.add_data(p)
        st = jx.driver.state["admission.k8s.gatekeeper.sh"]
        assert st.templates["Probes"].vectorized is not None
        l = sorted(r.msg for r in local.audit().results())
        j = sorted(r.msg for r in jx.audit().results())
        assert l == j
        assert "container <half> has no <readinessProbe>" in l
        assert "container <falsy> has no <livenessProbe>" in l  # false = fails
        assert "container <none> has no <livenessProbe>" in l
        assert not any("full" in m for m in l)

    def test_elem_key_missing_on_array_elements(self):
        """coll[key] semantics per element type: array elements honor
        int-index probes exactly (no over-approximation)."""
        rego = """package arrp
violation[{"msg": msg}] {
  item := input.review.object.spec.items[_]
  idx := input.constraint.spec.parameters.idxs[_]
  not item[idx]
  msg := sprintf("missing %v", [idx])
}
"""
        local, jx = self._pair()
        for c in (local, jx):
            c.add_template(self._tdoc("ArrP", rego))
            c.add_constraint(self._cdoc("ArrP", "a", {"idxs": [0, 2]}))
            c.add_data({"apiVersion": "v1", "kind": "Pod",
                        "metadata": {"name": "p", "namespace": "d"},
                        "spec": {"items": [["a", "b", "c"], ["x"]],
                                 "containers": []}})
        st = jx.driver.state["admission.k8s.gatekeeper.sh"]
        assert st.templates["ArrP"].vectorized is not None
        l = sorted(r.msg for r in local.audit().results())
        j = sorted(r.msg for r in jx.audit().results())
        assert l == j == ["missing 2"]   # ["x"] lacks index 2; index 0 ok


# K8sRequiredResources' shape: a constraint-bound key under a static path
# of the element (``not container.resources.limits[field]``)
_RR_CONTAINERS = [
    {"name": "none"},
    {"name": "empty", "resources": {}},
    {"name": "null", "resources": {"limits": None, "requests": None}},
    {"name": "lists", "resources": {"limits": ["cpu"], "requests": [0]}},
    {"name": "str", "resources": "cpu"},
    {"name": "falsy", "resources": {"limits": {"cpu": False, "memory": 0},
                                    "requests": {"cpu": "", "memory": None}}},
    {"name": "full", "resources": {
        "limits": {"cpu": "1", "memory": "1Gi"},
        "requests": {"cpu": "500m", "memory": "1Gi"}}},
    {"name": "cpu", "resources": {"limits": {"cpu": "1"},
                                  "requests": {"cpu": "1"}}},
]


def _rr_pods():
    pods = [{"apiVersion": "v1", "kind": "Pod",
             "metadata": {"name": f"p-{c['name']}", "namespace": "d"},
             "spec": {"containers": [c]}} for c in _RR_CONTAINERS]
    pods.append({"apiVersion": "v1", "kind": "Pod",
                 "metadata": {"name": "p-all", "namespace": "d"},
                 "spec": {"containers": _RR_CONTAINERS}})
    return pods


class TestKeyedPathUnderElement:
    _pair = TestNestedElementAxes._pair
    _tdoc = TestNestedElementAxes._tdoc
    _cdoc = TestNestedElementAxes._cdoc

    def _audit_pair(self, params_by_name, pods, churn=()):
        from gatekeeper_tpu.library import LIBRARY
        local, jx = self._pair()
        for c in (local, jx):
            c.add_template(self._tdoc("K8sRequiredResources",
                                      LIBRARY["K8sRequiredResources"][0]))
            for name, params in params_by_name.items():
                c.add_constraint(self._cdoc("K8sRequiredResources", name,
                                            params))
            for p in pods:
                c.add_data(p)
        st = jx.driver.state["admission.k8s.gatekeeper.sh"]
        assert st.templates["K8sRequiredResources"].vectorized is not None

        def results(c, **kw):
            return sorted((r.constraint["metadata"]["name"],
                           (r.review or {}).get("name"), r.msg)
                          for r in c.audit(**kw).results())
        want = results(local)
        assert results(jx, full=True) == want
        assert jx.driver.last_sweep_phases["dispatch"]["device"] \
            == ["K8sRequiredResources"]
        for p in churn:          # the delta path refills the dirty rows
            for c in (local, jx):
                c.add_data(p)
        if churn:
            want = results(local)
            assert results(jx) == want
        return want

    @pytest.mark.parametrize("limits,requests", [
        ([], []), (["cpu"], []), ([], ["memory"]),
        (["cpu", "memory"], ["cpu"]), (None, ["cpu", "memory"])])
    def test_key_lists_parity(self, limits, requests):
        params = {"requests": requests}
        if limits is not None:
            params["limits"] = limits
        got = self._audit_pair({"rr": params}, _rr_pods())
        fired = len(limits or []) + len(requests)
        assert bool(got) == bool(fired)
        # keys present with any value but false pass the statement
        assert not any(n in ("p-full",) and f"limits.{k}" in m
                       for _, n, m in got for k in ("cpu", "memory"))

    def test_device_mask_equals_oracle(self):
        from gatekeeper_tpu.library import LIBRARY
        check(LIBRARY["K8sRequiredResources"][0], "K8sRequiredResources",
              [{"spec": {"parameters": {"limits": ["cpu", "memory"],
                                        "requests": ["cpu"]}}},
               {"spec": {"parameters": {"limits": [], "requests": []}}},
               {"spec": {"parameters": {"limits": ["memory"]}}}],
              _rr_pods())

    def test_two_constraints_different_lists_and_churn(self):
        fixed = {"apiVersion": "v1", "kind": "Pod",
                 "metadata": {"name": "p-none", "namespace": "d"},
                 "spec": {"containers": [_RR_CONTAINERS[6]]}}
        broken = {"apiVersion": "v1", "kind": "Pod",
                  "metadata": {"name": "p-full", "namespace": "d"},
                  "spec": {"containers": [{"name": "full", "resources": {
                      "limits": {"memory": "1Gi"}}}]}}
        got = self._audit_pair(
            {"rr-a": {"limits": ["cpu"], "requests": []},
             "rr-b": {"limits": ["memory", "ephemeral-storage"],
                      "requests": ["cpu"]}},
            _rr_pods(), churn=[fixed, broken])
        assert ("rr-a", "p-full",
                "container <full> has no resources.limits.cpu") in got
        assert ("rr-b", "p-full",
                "container <full> has no resources.requests.cpu") in got
        assert [(c, m) for c, n, m in got if n == "p-none"] == [
            ("rr-b", "container <full> has no "
                     "resources.limits.ephemeral-storage")]
