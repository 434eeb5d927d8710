"""ops/regex_dfa: batched regex via byte DFA — parity with Python re
(the scalar engine's semantics, rego/builtins.py `re_match` =
unanchored search like Go's regexp.MatchString, topdown/regex.go),
plus the prep-table integration at forced-low thresholds.
"""

import random
import re
import string

import numpy as np
import pytest

from gatekeeper_tpu.ops import regex_dfa
from gatekeeper_tpu.ops.regex_dfa import (
    compile_dfa, match_packed, match_packed_device, match_strings,
    pack_strings)

LIBRARY_PATTERNS = [
    "@sha256:[a-f0-9]{64}$",
    "^[0-9]+(\\.[0-9]+)?$",
    ":latest$",
    "^[a-zA-Z]+.agilebank.demo$",
    "^gcr\\.io/",
]

GENERATED_PATTERNS = [
    "abc", "a+b*c?", "(foo|bar)[0-9]{2}", "^x(y|z)+$", "te?st",
    "[^0-9]+$", "hello$", "^$", "a{2,4}b", "(ab)+c", "[a-f]{3}",
    "x.y", "\\d+\\.\\d+", "\\w+@\\w+", "\\s", "v[0-9]+(-rc[0-9]+)?$",
]

# a global (?i) lives on the parse state, not in the token list
ICASE_PATTERNS = [
    "(?i)(secret|token)", "(?i)^[a-z]+$", "(?i)[^k]x", "(?i)Te?ST",
    "(?i)[A-C0-9]{2}", "(?i)LATEST$",
]


def _corpus(rng, n=400):
    out = ["", "123", "1.5", "x1.5", "abc", "xabcz", "latest",
           "img:latest", "gcr.io/app", "xgcr.io/", "foo12", "bar99x",
           "te st", "tst", "test", "@sha256:" + "a" * 64,
           "x@sha256:" + "b" * 64 + "y", "jane.agilebank.demo",
           "aaab", "aaaaab", "ababc", "v12-rc3", "v12", "a@b", "x y"]
    pool = string.ascii_letters + string.digits + ":./@-_ $^"
    out += ["".join(rng.choice(pool) for _ in range(rng.randrange(24)))
            for _ in range(n)]
    return out


class TestParityWithRe:
    @pytest.mark.parametrize("pattern",
                             LIBRARY_PATTERNS + GENERATED_PATTERNS
                             + ICASE_PATTERNS)
    def test_search_parity(self, pattern):
        rng = random.Random(hash(pattern) & 0xffff)
        strs = _corpus(rng)
        dfa = compile_dfa(pattern)
        assert dfa is not None, f"library-class pattern must compile: {pattern}"
        got = match_strings(dfa, strs)
        rx = re.compile(pattern)
        want = np.array([rx.search(x) is not None for x in strs])
        mism = [x for x, g, w in zip(strs, got, want) if bool(g) != w]
        assert not mism, (pattern, mism[:5])

    def test_device_twin_matches_numpy(self):
        dfa = compile_dfa("(foo|bar)[0-9]{2}$")
        packed, ok = pack_strings(_corpus(random.Random(3)))
        assert ok.all()
        np.testing.assert_array_equal(
            np.asarray(match_packed_device(dfa, packed)),
            match_packed(dfa, packed))

    def test_non_ascii_falls_back_exactly(self):
        dfa = compile_dfa("caf")
        strs = ["café", "cafe", "caféx", "name"]
        got = match_strings(dfa, strs)
        want = [re.search("caf", x) is not None for x in strs]
        assert [bool(g) for g in got] == want

    def test_empty_patterns_match_everything(self):
        # empty sequence ("" / "^" / empty alternative) matches every
        # string under search semantics
        for pat in ("", "^", "a|"):
            dfa = compile_dfa(pat)
            assert dfa is not None, pat
            got = match_strings(dfa, ["", "x", "abc"])
            assert all(bool(g) for g in got), (pat, got)

    def test_overlong_strings_take_host_path(self):
        import gatekeeper_tpu.ops.regex_dfa as rd
        dfa = compile_dfa("big$")
        s2 = ["x" * (rd.MAX_PACK_LEN + 50) + "big", "big", "nope"]
        got = match_strings(dfa, s2)
        assert [bool(g) for g in got] == [True, True, False]

    def test_unsupported_returns_none(self):
        assert compile_dfa(r"(a)\1") is None          # backreference
        assert compile_dfa(r"(?=a)b") is None         # lookahead
        assert compile_dfa("a" * 600) is None         # state blowup
        assert compile_dfa("(?m)^a") is None          # multiline anchors
        assert compile_dfa("(?s)a.b") is None         # dot matches newline
        assert compile_dfa("(?i)\u017f") is None      # folds to ASCII s


class TestPrepIntegration:
    def test_high_cardinality_table_parity(self, monkeypatch):
        """K8sImageDigests over many UNIQUE images: the DFA table route
        (forced on via threshold=1) must agree with the scalar oracle —
        including a non-ASCII image that the packer rejects."""
        monkeypatch.setattr(regex_dfa, "TABLE_MIN_UNIQUES", 1)
        from gatekeeper_tpu.client.client import Backend
        from gatekeeper_tpu.client.local_driver import LocalDriver
        from gatekeeper_tpu.engine.jax_driver import JaxDriver
        from gatekeeper_tpu.library import constraint_doc, template_doc
        from gatekeeper_tpu.library.templates import LIBRARY
        from gatekeeper_tpu.target.k8s import K8sValidationTarget

        rng = random.Random(5)
        objs = []
        for i in range(300):
            if i % 3 == 0:
                img = f"gcr.io/org/app{i}@sha256:" + "".join(
                    rng.choice("0123456789abcdef") for _ in range(64))
            elif i % 3 == 1:
                img = f"gcr.io/org/app{i}:v{i}"
            else:
                img = f"quay.io/café/app{i}:latest"       # non-ASCII
            objs.append({"apiVersion": "v1", "kind": "Pod",
                         "metadata": {"name": f"p{i:04d}", "namespace": "d"},
                         "spec": {"containers": [{"name": "c", "image": img}]}})

        res = {}
        for nm, drv in (("jax", JaxDriver()), ("local", LocalDriver())):
            c = Backend(drv).new_client([K8sValidationTarget()])
            c.add_template(template_doc("K8sImageDigests",
                                        LIBRARY["K8sImageDigests"][0]))
            c.add_constraint(constraint_doc("K8sImageDigests", "digests",
                                            LIBRARY["K8sImageDigests"][1]))
            for o in objs:
                c.add_data(o)
            got, _ = drv.query_audit("admission.k8s.gatekeeper.sh")
            res[nm] = sorted((r.review or {}).get("name", "") for r in got)
        assert res["jax"] == res["local"]
        assert len(res["jax"]) == 200          # all non-digest images
