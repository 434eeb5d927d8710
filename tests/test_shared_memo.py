"""Per-review shared memo for review-pure comprehensions
(rego/closures._memoize_review_pure): one evaluation per review across
the constraint loop, never shared where results could differ — and for
pure user-function calls (interp._call_function)."""

import json
import pathlib
import random

import pytest

from gatekeeper_tpu.rego import parse_module
from gatekeeper_tpu.rego.interp import Interpreter
from gatekeeper_tpu.rego.values import Obj, freeze

MOD = """package t

violation[{"msg": m}] {
	provided := {l | input.review.object.metadata.labels[l]}
	required := {l | l := input.constraint.spec.parameters.labels[_]}
	missing := required - provided
	count(missing) > 0
	m := sprintf("missing: %v", [missing])
}
"""


def _input(labels, required):
    return Obj({"review": freeze({"object": {"metadata":
                                             {"labels": labels}}}),
                "constraint": freeze({"spec": {"parameters":
                                               {"labels": required}}})})


def test_shared_memo_caches_review_pure_only():
    interp = Interpreter(parse_module(MOD))
    shared: dict = {}
    # same review, two different constraints: the provided-comprehension
    # is cached; required/missing still differ per constraint
    out1 = interp.query_set("violation", _input({"a": "1"}, ["a", "b"]),
                            shared_memo=shared)
    assert len(shared) == 1          # exactly the review-pure entry
    out2 = interp.query_set("violation", _input({"a": "1"}, ["c"]),
                            shared_memo=shared)
    assert len(shared) == 1          # reused, not regrown
    assert [str(v["msg"]) for v in out1] == ['missing: {"b"}']
    assert [str(v["msg"]) for v in out2] == ['missing: {"c"}']


def test_results_match_unshared():
    interp = Interpreter(parse_module(MOD))
    shared: dict = {}
    for labels, req in ((({"a": "1", "b": "2"}), ["a", "b"]),
                        (({"x": "1"}), ["a"]),
                        ({}, ["z"])):
        with_memo = interp.query_set("violation", _input(labels, req),
                                     shared_memo=shared)
        without = interp.query_set("violation", _input(labels, req))
        assert with_memo == without


def test_constraint_reading_comprehension_not_shared():
    interp = Interpreter(parse_module(MOD))
    from gatekeeper_tpu.rego.ast_nodes import Comprehension
    comp = [t for r in interp.module.rules for lit in r.body
            for t in [lit.expr.rhs] if isinstance(t, Comprehension)]
    shareable = [interp._closures._review_shareable(c) for c in comp]
    # provided (review-pure) shareable; required (reads constraint) not
    assert shareable.count(None) == 1
    assert sum(1 for s in shareable if s is not None) == 1


def test_whole_input_binding_not_shared():
    """A comprehension that binds the WHOLE input document (`i :=
    input`) can reach input.constraint through the binding — it must
    never be classified review-pure (round-5 review finding, was
    serving constraint A's result to constraint B)."""
    mod = """package t
violation[{"msg": m}] {
	vals := {v | i := input; v := i.constraint.spec.parameters.labels[_]}
	m := sprintf("vals: %v", [vals])
}
"""
    interp = Interpreter(parse_module(mod))
    shared: dict = {}

    def q(labels):
        doc = Obj({"review": freeze({"object": {}}),
                   "constraint": freeze({"spec": {"parameters":
                                                  {"labels": labels}}})})
        return [str(v["msg"]) for v in
                interp.query_set("violation", doc, shared_memo=shared)]

    assert q(["a"]) == ['vals: {"a"}']
    assert q(["b"]) == ['vals: {"b"}']


def test_impure_builtins_never_shared():
    """A comprehension that LOOKS review-pure but calls an impure
    builtin (clock, trace, signature verification that consults the
    clock for exp/nbf) must never be memoized across a review — the
    central builtins.IMPURE_BUILTINS set is the single gate."""
    from gatekeeper_tpu.rego import builtins as bi
    from gatekeeper_tpu.rego.ast_nodes import Comprehension

    assert ("trace",) in bi.IMPURE_BUILTINS
    assert ("time", "now_ns") in bi.IMPURE_BUILTINS
    assert ("io", "jwt", "decode_verify") in bi.IMPURE_BUILTINS

    mod = """package t
violation[{"msg": "x"}] {
	toks := {t | raw := input.review.object.toks[_];
	             t := io.jwt.decode_verify(raw, {"secret": "s"})}
	count(toks) > 0
}
"""
    interp = Interpreter(parse_module(mod))
    comp = [t for r in interp.module.rules for lit in r.body
            for t in [lit.expr.rhs] if isinstance(t, Comprehension)]
    assert comp, "expected a comprehension in the test rule"
    # every one is refused: the jwt verification consults the clock
    assert all(interp._closures._review_shareable(c) is None
               for c in comp)


def test_with_override_bypasses_shared():
    mod = """package t
p := {l | input.review.object.metadata.labels[l]}
violation[{"msg": "x"}] {
	q := p with input as {"review": {"object": {"metadata": {"labels": {"zz": 1}}}}}
	q["zz"]
}
"""
    interp = Interpreter(parse_module(mod))
    shared: dict = {}
    out = interp.query_set("violation", _input({"a": "1"}, []),
                           shared_memo=shared)
    assert len(out) == 1    # the with-override saw zz, not the cached {a}


# ---------------------------------------------------------------------------
# pure user-function calls (interp._call_function): one evaluation per
# (function, typed scalar arguments) per review


def _fn_input(v, params=None):
    return Obj({"review": freeze({"object": {"v": v}}),
                "constraint": freeze({"spec": {"parameters": params or {}}})})


IMPURE_FNS = """package t

lim(x) = y { y := input.constraint.spec.parameters.n + x }
inv(x) = y { y := data.inventory.k[x] }
clk(x) = y { time.now_ns() > 0; y := x }
wrap(x) = y { y := lim(x) }
whole(x) = y { i := input; y := i.constraint.spec.parameters.n * x }
dbl(x) = y { y := x * 2 }
usedbl(x) = y { y := dbl(x) + 1 }
violation[{"msg": m}] {
	v := input.review.object.v
	m := sprintf("%v %v %v", [wrap(v), whole(v), usedbl(v)])
}
inv_msg[{"msg": m}] {
	m := sprintf("%v", [inv(input.review.object.v)])
}
"""


def test_functions_that_read_input_data_or_the_clock_are_not_pure():
    interp = Interpreter(parse_module(IMPURE_FNS))
    assert interp._pure_fns == {"dbl", "usedbl"}


def test_impure_functions_answer_per_constraint():
    interp = Interpreter(parse_module(IMPURE_FNS))
    shared: dict = {}
    review = freeze({"object": {"v": 3}})

    def q(n, memo):
        doc = Obj({"review": review, "constraint": freeze(
            {"spec": {"parameters": {"n": n}}})})
        return [str(v["msg"]) for v in
                interp.query_set("violation", doc, shared_memo=memo)]

    # two constraints on one review: lim/wrap/whole read the constraint
    assert q(1, shared) == ["4 3 7"] == q(1, None)
    assert q(100, shared) == ["103 300 7"] == q(100, None)
    # only usedbl and dbl went through the memo: the second constraint's
    # usedbl(3) is served whole, so dbl is not even called again
    assert (interp.fn_memo_misses, interp.fn_memo_hits) == (2, 1)
    # a data-reading function sees each query's own document
    for k, want in (({"3": "a"}), "a"), (({"3": "b"}), "b"):
        got = interp.query_set("inv_msg", _fn_input("3"),
                               {"inventory": {"k": k}}, shared_memo=shared)
        assert [str(v["msg"]) for v in got] == [want]


def test_call_cycle_is_not_pure():
    mod = """package t
f(x) = y { x > 0; y := g(x - 1) }
else = y { y := 0 }
g(x) = y { y := f(x) }
h(x) = y { y := x }
violation[{"msg": m}] { m := sprintf("%v", [f(input.review.object.v)]) }
"""
    interp = Interpreter(parse_module(mod))
    assert interp._pure_fns == {"h"}
    assert [str(v["msg"]) for v in interp.query_set(
        "violation", _fn_input(3), shared_memo={})] == ["0"]


CANON = r"""package t

canon(orig) = new { is_number(orig); new := orig * 1000 }
else = new {
	endswith(orig, "m")
	new := to_number(replace(orig, "m", ""))
}
else = new {
	re_match("^[0-9]+(\\.[0-9]+)?$", orig)
	new := to_number(orig) * 1000
}
else = new { is_boolean(orig); new := "bool" }
else = new { is_null(orig); new := "null" }
violation[{"msg": m}] {
	m := sprintf("%v", [canon(input.review.object.v)])
}
"""

# the same value under different types takes a different clause; the
# memo must never let one serve another (in Python 1 == 1.0 == True)
CANON_ARGS = ("1", 1, 1.0, True, "500m", None, 1.5, "1.5", False, 0)


def test_else_chain_same_with_and_without_memo_per_type():
    interp = Interpreter(parse_module(CANON))
    shared: dict = {}
    got, want = [], []
    for _ in range(2):
        for v in CANON_ARGS:
            got.append(interp.query_set("violation", _fn_input(v),
                                        shared_memo=shared))
            want.append(interp.query_set("violation", _fn_input(v)))
    assert got == want
    msgs = [str(r[0]["msg"]) for r in want[:len(CANON_ARGS)]]
    assert msgs == ["1000", "1000", "1000", "bool", "500", "null", "1500",
                    "1500", "bool", "0"]
    # 1.0 freezes to the int 1: nine distinct (type, value) keys, each
    # run once; the second pass is served whole
    assert interp.fn_memo_misses == 9
    assert interp.fn_memo_hits == len(CANON_ARGS) + 1


def test_two_argument_calls_key_on_every_argument():
    mod = """package t
f(x, y) = z { is_boolean(y); z := concat("", [x, "b"]) }
else = z { z := sprintf("%v%v", [x, y]) }
violation[{"msg": m}] {
	a := input.review.object.v
	m := concat(",", [f(a, 1), f(a, true), f("a", 1), f("c", 1)])
}
"""
    interp = Interpreter(parse_module(mod))
    out = interp.query_set("violation", _fn_input("a"), shared_memo={})
    assert [str(v["msg"]) for v in out] == ["a1,ab,a1,c1"]
    assert (interp.fn_memo_hits, interp.fn_memo_misses) == (1, 3)


def test_composite_arguments_are_not_memoized():
    interp = Interpreter(parse_module(CANON.replace(
        "input.review.object.v", "[input.review.object.v]")))
    shared: dict = {}
    for _ in range(2):
        interp.query_set("violation", _fn_input("1"), shared_memo=shared)
    assert (interp.fn_memo_hits, interp.fn_memo_misses) == (0, 0)


def test_with_override_and_step_tracer_bypass_function_memo():
    from gatekeeper_tpu.rego.trace import StepTracer
    mod = CANON + """
under_with[{"msg": m}] {
	v := input.review.object.v
	c := canon(v) with input as {"x": 1}
	m := sprintf("%v", [c])
}
"""
    interp = Interpreter(parse_module(mod))
    shared: dict = {}
    for _ in range(2):
        assert [str(v["msg"]) for v in interp.query_set(
            "under_with", _fn_input("2"), shared_memo=shared)] == ["2000"]
    assert (interp.fn_memo_hits, interp.fn_memo_misses) == (0, 0)
    interp.query_set("violation", _fn_input("2"), shared_memo=shared)
    assert (interp.fn_memo_hits, interp.fn_memo_misses) == (0, 1)
    step = StepTracer()
    out = interp.query_set("violation", _fn_input("2"), step_tracer=step,
                           shared_memo=shared)
    assert [str(v["msg"]) for v in out] == ["2000"]
    assert (interp.fn_memo_hits, interp.fn_memo_misses) == (0, 1)
    # the tracer saw the call itself
    assert any(e.op == "Enter" and e.node == "canon" for e in step.events)


def test_exception_inside_function_is_not_cached():
    from gatekeeper_tpu.errors import ConflictError
    mod = """package t
f(x) = 1 { x == "a" }
f(x) = 2 { x == "a" }
f(x) = 3 { x == "b" }
violation[{"msg": m}] { m := sprintf("%v", [f(input.review.object.v)]) }
"""
    interp = Interpreter(parse_module(mod))
    shared: dict = {}
    for _ in range(2):
        with pytest.raises(ConflictError):
            interp.query_set("violation", _fn_input("a"), shared_memo=shared)
    assert interp.fn_memo_misses == 2 and interp.fn_memo_hits == 0
    assert not any(isinstance(k, tuple) and "a" in k for k in shared)
    for _ in range(2):
        assert [str(v["msg"]) for v in interp.query_set(
            "violation", _fn_input("b"), shared_memo=shared)] == ["3"]
    assert (interp.fn_memo_hits, interp.fn_memo_misses) == (1, 3)


def test_container_limits_runs_each_canonify_call_once_per_review():
    import collections
    from gatekeeper_tpu.library import LIBRARY
    interp = Interpreter(parse_module(LIBRARY["K8sContainerLimits"][0]))
    assert interp._pure_fns == {"canonify_cpu", "canonify_mem"}
    runs = collections.Counter()
    apply = interp._apply_function

    def counting(ctx, name, argvals):
        runs[(name,) + argvals] += 1
        return apply(ctx, name, argvals)

    interp._apply_function = counting
    review = freeze({"object": {"spec": {"containers": [
        {"name": n, "resources": {"limits": {"cpu": cpu, "memory": mem}}}
        for n, cpu, mem in (("a", "4", "4Gi"), ("b", "1500m", "1.5Gi"),
                            ("c", "250m", "128Mi"))]}}})
    params = [{"cpu": cpu, "memory": mem} for cpu in ("500m", "1", "2")
              for mem in ("512Mi", "2Gi")] * 2
    shared: dict = {}
    with_memo = [interp.query_set("violation", Obj(
        {"review": review, "constraint": freeze({"spec": {"parameters": p}})}),
        shared_memo=shared) for p in params]
    assert runs and set(runs.values()) == {1}
    # three containers' cpu and memory, three cpu and two memory maxima
    assert len(runs) == 3 + 3 + 3 + 2
    assert interp.fn_memo_misses == len(runs)
    assert interp.fn_memo_hits > 10 * len(runs)
    without = [interp.query_set("violation", Obj(
        {"review": review, "constraint": freeze({"spec": {"parameters": p}})}))
        for p in params]
    assert with_memo == without
    assert sum(map(len, without)) > len(params)


# ---------------------------------------------------------------------------
# the benchmark's policy set on the device path: a capped forced-full
# audit equals the scalar driver's, and the format spans count the memo

POLICY = pathlib.Path(__file__).resolve().parents[1] / "benchmark" / \
    "configs" / "large-cluster-policy.json"
LIMIT = 20


def _capped(results: list, limit: int) -> dict:
    """Per constraint, the cache-key-ordered results cut as the cap cuts
    them: whole resources until at least ``limit`` results are out."""
    by_c: dict = {}
    for r in results:
        by_c.setdefault(r.constraint["metadata"]["name"], []).append(
            (r.msg, r.review["name"]))
    if limit is None:
        return by_c
    out = {}
    for name, rs in by_c.items():
        kept = []
        for msg, res in rs:
            if len(kept) >= limit and res != kept[-1][1]:
                break
            kept.append((msg, res))
        out[name] = kept
    return out


@pytest.fixture(scope="module")
def policy_audits():
    """(capped device audit, capped scalar audit, kind.format span args)
    of the benchmark's 201-constraint policy over 300 Pods whose limits
    are drawn from the configuration's own quantity values."""
    from gatekeeper_tpu.client.client import Backend
    from gatekeeper_tpu.client.interface import QueryOpts
    from gatekeeper_tpu.client.local_driver import LocalDriver
    from gatekeeper_tpu.engine import jax_driver as jd_mod
    from gatekeeper_tpu.library.workload import make_pods
    from gatekeeper_tpu.obs import trace as trace_mod
    from gatekeeper_tpu.target.k8s import K8sValidationTarget, TARGET_NAME

    policy = json.loads(POLICY.read_text())
    inv = json.loads((POLICY.parent / "large-cluster-150k.json")
                     .read_text())["inventory"]
    rng = random.Random(1618)
    pods = make_pods(300, rng)
    for p in pods:
        for c in p["spec"]["containers"]:
            c["resources"]["limits"] = {
                "cpu": rng.choice(inv["cpu"]["values"]),
                "memory": rng.choice(inv["memory"]["values"])}
    mp = pytest.MonkeyPatch()
    mp.setattr(jd_mod, "SMALL_WORKLOAD_EVALS", 0)
    tracer = trace_mod.Tracer(ring=4096)
    mp.setattr(trace_mod, "_tracer", tracer)
    try:
        clients = [Backend(d).new_client([K8sValidationTarget()])
                   for d in (jd_mod.JaxDriver(), LocalDriver())]
        for c in clients:
            for t in policy["templates"]:
                c.add_template(t)
            for con in policy["constraints"]:
                c.add_constraint(con)
            c.add_data_batch(pods)
        jx, local = clients
        jx.driver.query_audit(TARGET_NAME, QueryOpts(
            limit_per_constraint=LIMIT, full=True))
        got = jx.driver.query_audit(TARGET_NAME, QueryOpts(
            limit_per_constraint=LIMIT, full=True))[0]
        want = local.driver.query_audit(TARGET_NAME)[0]
        spans = [e["args"] for e in tracer.export()["traceEvents"]
                 if e["name"] == "kind.format"]
    finally:
        mp.undo()
    return got, want, spans


def test_policy_capped_full_audit_equals_the_scalar_driver(policy_audits):
    got, want, _ = policy_audits
    assert _capped(got, None) == _capped(want, LIMIT)
    assert len(got) > 1000


def test_policy_format_spans_carry_function_memo_counts(policy_audits):
    _, _, spans = policy_audits
    # two forced-full sweeps, three kinds each
    assert len(spans) == 6
    limits = [a for a in spans if a["kind"] == "K8sContainerLimits"]
    assert all(a["mode"] == "topk" for a in spans)
    # a full sweep formats every capped pair again, and a row flagged by
    # many constraints canonifies each of its quantities once
    assert all(a["fn_memo_hits"] > a["fn_memo_misses"] > 0 for a in limits)
    assert all(a["fn_memo_hits"] == a["fn_memo_misses"] == 0
               for a in spans if a["kind"] != "K8sContainerLimits")
