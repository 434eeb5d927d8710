"""Host lookup tables whose leaf term is one pure builtin call
(``ir/prep.LeafCall``): the batched build must give the same ``ok``,
``vals``, ``.vmap``, ``.any`` and ``.all`` arrays as the per-value
interpreter walk of the same term, for every library template with a
host table, and the recogniser must accept exactly that shape."""

import dataclasses
import functools

import numpy as np
import pytest

from gatekeeper_tpu.api.templates import compile_target_rego
from gatekeeper_tpu.ir import prep
from gatekeeper_tpu.ir.encode import decode_value
from gatekeeper_tpu.ir.lower import Lowerer, lower_template
from gatekeeper_tpu.ir.prep import build_bindings
from gatekeeper_tpu.library.templates import LIBRARY, constraint_doc
from gatekeeper_tpu.rego import builtins as bi
from gatekeeper_tpu.rego.ast_nodes import (ArrayTerm, Call, Ref, Scalar,
                                           Var)
from gatekeeper_tpu.store.table import ResourceMeta, ResourceTable

# library templates with a host table or ptable, and whether one of
# them is a single pure builtin call of the leaf
TABLE_TEMPLATES = {
    "K8sAllowedRepos": True,
    "K8sBlockWildcardIngress": True,
    "K8sContainerLimits": False,        # canonify_cpu(leaf): template fn
    "K8sContainerRatios": False,
    "K8sContainerRequests": False,
    "K8sDisallowLatestTag": True,
    "K8sDisallowedRepos": True,
    "K8sDisallowedTags": True,
    "K8sHostFilesystem": True,
    "K8sMemoryRequestEqualsLimit": False,
    "K8sNoEnvVarSecrets": True,
}

VALUES = ["", "nginx", "nginx:latest", "gcr.io/app:1.2",
          "docker.io/library/nginx:latest", ":", "latest", "a:b:c",
          "/var/log/pods", "/etc", "*.example.com", "ünïcødé:ταγ", "Ω",
          "100m", "2Gi", "1.5", "PASSWORD", "my_secret_token",
          0, 7, 1.5, -2, True, False, None, {"a": "b"}, ["x"]]

EXTRA_PARAMS = ["", ":", "latest", "ünï", "/var", "gcr.io/", 5]


@functools.lru_cache(maxsize=None)
def _lowered(kind):
    ct = compile_target_rego(kind, "k8s", LIBRARY[kind][0])
    return lower_template(ct.module, ct.interp)


def _per_value(spec):
    """The same spec with every table on the per-value walk."""
    return dataclasses.replace(
        spec,
        tables=tuple(dataclasses.replace(t, leaf_call=None)
                     for t in spec.tables),
        ptables=tuple(dataclasses.replace(t, leaf_call=None)
                      for t in spec.ptables))


def _nest(base, rel, value):
    """An object whose ``base`` axis holds one element with ``value`` at
    ``rel`` (a ``*`` in ``base`` steps into a list's element)."""
    elem = value
    for k in reversed(rel):
        elem = {k: elem}
    cur = [elem]
    for k in reversed(base):
        cur = [cur] if k == "*" else {k: cur}
    return cur


def _table(spec, values):
    cols = {c.name: c for c in spec.e_cols}
    srcs = sorted({t.src for t in spec.tables + spec.ptables})
    t = ResourceTable()
    i = 0
    for src in srcs:
        col = cols[src]
        for v in values:
            obj = _nest(col.base, col.rel, v)
            obj["metadata"] = {"name": f"o{i}", "namespace": "ns"}
            t.upsert(f"k{i}", obj, ResourceMeta(api_version="v1",
                                                kind="Pod", name=f"o{i}",
                                                namespace="ns"))
            i += 1
    return t


def _constraints(kind):
    params = LIBRARY[kind][1]
    wide = {k: (v + EXTRA_PARAMS if isinstance(v, list) else v)
            for k, v in params.items()}
    return [constraint_doc(kind, "sample", params),
            constraint_doc(kind, "wide", wide),
            constraint_doc(kind, "none", {})]


def _assert_same(b1, b2):
    assert b1.arrays.keys() == b2.arrays.keys()
    for name, a in b1.arrays.items():
        other = b2.arrays[name]
        assert a.dtype == other.dtype and a.shape == other.shape, name
        np.testing.assert_array_equal(a, other, err_msg=name)
    assert b1.f32_unsafe == b2.f32_unsafe


def test_table_templates_are_every_library_template_with_a_table():
    have = {}
    for kind in LIBRARY:
        try:
            spec = _lowered(kind).spec
        except Exception:  # noqa: BLE001 - not lowered: no tables
            continue
        reqs = spec.tables + spec.ptables
        if reqs:
            have[kind] = any(r.leaf_call is not None for r in reqs)
    assert have == TABLE_TEMPLATES


@pytest.mark.parametrize("kind", sorted(TABLE_TEMPLATES))
def test_batched_tables_equal_the_per_value_walk(kind):
    spec = _lowered(kind).spec
    cons = _constraints(kind)
    b0, h0 = prep.TABLE_VALUES.batched, prep.TABLE_VALUES.host
    batched = build_bindings(spec, _table(spec, VALUES), cons)
    b1 = prep.TABLE_VALUES.batched
    walked = build_bindings(_per_value(spec), _table(spec, VALUES), cons)
    assert (b1 > b0) is TABLE_TEMPLATES[kind]
    assert prep.TABLE_VALUES.batched == b1     # the walk counts as host
    assert prep.TABLE_VALUES.host > h0
    _assert_same(batched, walked)


def test_batched_ptable_counts_every_parameter_and_value():
    spec = _lowered("K8sDisallowedTags").spec
    pt, = spec.ptables
    vals = ["a:latest", "b:1", "c"]
    table = _table(spec, vals)
    b0 = prep.TABLE_VALUES.batched
    b = build_bindings(spec, table,
                       [constraint_doc("K8sDisallowedTags", "t",
                                       {"tags": ["latest", "1"]})])
    # the unary table's three values and the ptable's 2 x 3
    assert prep.TABLE_VALUES.batched - b0 == 3 + 6
    vmap = b.arrays[pt.name + ".vmap"]
    anyv = b.arrays[pt.name + ".any"]
    got = {decode_value(table.interner.string(int(uid))):
           bool(anyv[0, vmap[uid]])
           for uid in np.unique(b.arrays[pt.src]) if uid >= 0}
    assert got == {"a:latest": True, "b:1": True, "c": False}


# ---- the recogniser


def _lowerer():
    """A lowerer over a template that defines the function ``f``."""
    ct = compile_target_rego("X", "k8s", "package x\nf(a) = a\n"
                             "violation[{\"msg\": m}] { m := f(\"a\") }\n")
    return Lowerer(ct.module, ct.interp)


LEAF = Var("__leaf0__")


def test_concat_over_the_parameter_is_recognised():
    pt, = _lowered("K8sDisallowedTags").spec.ptables
    lc = pt.leaf_call
    assert lc is not None
    assert lc.fn is bi.REGISTRY[("endswith",)] and lc.pos == 0
    assert lc.args("latest") == [(":latest",)]


@pytest.mark.parametrize("term", [
    Call(("startswith",), (LEAF, Scalar("gcr.io/"))),
    Call(("re_match",), (Scalar("^a"), LEAF)),
    Call(("endswith",), (LEAF, Call(("concat",), (
        Scalar(":"), ArrayTerm((Scalar(""), Var("tag"))))))),
    Call(("startswith",), (LEAF, Ref(Var("tag"), (Scalar("prefix"),)))),
], ids=["const", "leaf_second", "concat_param", "param_ref"])
def test_recognised(term):
    lc = _lowerer()._leaf_call(term, "tag")
    assert lc is not None
    assert lc.fn is bi.REGISTRY[term.name]
    assert term.args[lc.pos] == LEAF


@pytest.mark.parametrize("term", [
    Call(("startswith",), (LEAF, LEAF)),
    Call(("concat",), (Scalar(":"), ArrayTerm((LEAF, LEAF)))),
    Call(("startswith",), (Call(("lower",), (LEAF,)), Scalar("a"))),
    Call(("startswith",), (LEAF, Call(("lower",), (LEAF,)))),
    Call(("f",), (LEAF,)),
    Call(("startswith",), (LEAF, Call(("f",), (Scalar("a"),)))),
    Call(("io", "jwt", "decode_verify"), (LEAF, Scalar("k"))),
    Call(("startswith",), (LEAF, Call(("time", "now_ns"), ()))),
    Call(("no", "such_builtin"), (LEAF,)),
    Ref(LEAF, (Scalar("a"),)),
], ids=["leaf_twice", "leaf_nested_twice", "leaf_nested",
        "leaf_in_other_arg", "user_function", "user_function_in_arg",
        "impure", "impure_in_arg", "unknown", "not_a_call"])
def test_rejected(term):
    assert _lowerer()._leaf_call(term, "tag") is None


def test_user_function_table_keeps_the_per_value_walk():
    t0 = _lowered("K8sContainerLimits").spec.tables[0]
    assert t0.leaf_call is None
    # the walk through the template's function still answers
    assert t0.fn("100m") == 100


def _walk(lowerer, term, value, param, truth):
    """The per-value interpreter walk of ``term``, as a table's fn."""
    from gatekeeper_tpu.rego.interp import UNDEFINED
    from gatekeeper_tpu.rego.values import freeze
    env = {"__leaf0__": freeze(value), "tag": freeze(param)}
    ctx = lowerer.interp._ctx(UNDEFINED, None, None)
    for v, _ in lowerer.interp._eval_term(ctx, term, env):
        if not truth:
            return v
        if v is not False:
            return True
    return None


ANY = Var("$0")


@pytest.mark.parametrize("term,truth", [
    (Call(("startswith",), (LEAF, Ref(Call(("split",), (
        Var("tag"), Scalar(","))), (ANY,)))), True),
    (Call(("endswith",), (LEAF, Ref(ArrayTerm((
        Scalar(":latest"), Scalar(":v1"))), (ANY,)))), True),
    (Call(("trim_prefix",), (LEAF, Ref(ArrayTerm((
        Scalar("gcr.io/"), Scalar("docker.io/"))), (ANY,)))), False),
    (Call(("indexof",), (LEAF, Ref(Call(("split",), (
        Var("tag"), Scalar(","))), (ANY,)))), False),
], ids=["any_prefix", "any_suffix", "first_trim", "first_index"])
def test_several_argument_tuples_answer_as_the_walk(term, truth):
    lw = _lowerer()
    lc = lw._leaf_call(term, "tag")
    assert lc is not None
    values = ["a1", "b:v1", "gcr.io/x:latest", "docker.io/y", "", "ü,b",
              5, None, True]
    param = "a,b,gcr"
    assert len(lc.args(param)) > 1
    from gatekeeper_tpu.rego.values import freeze
    got = prep._leaf_call_each(lc, [freeze(v) for v in values], param,
                               truth)
    want = [prep._eval_host(_walk, lw, term, v, param, truth)
            for v in values]
    assert got == want
