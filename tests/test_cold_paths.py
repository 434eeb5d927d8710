"""Cold-start path semantics: bulk ingest, numpy row ordering, and
narrow device transfers must be behavior-preserving optimizations.

The benchmark (benchmark/) measures their speed; these tests pin the
*equivalences* the optimizations rely on (batch AddData == looped
AddData, _RowOrder == dict ordering, int8/int16 narrow transfer ==
int32 upload)."""

import numpy as np
import pytest

from gatekeeper_tpu.client.client import Backend
from gatekeeper_tpu.client.interface import QueryOpts
from gatekeeper_tpu.client.targets import WipeData
from gatekeeper_tpu.engine.jax_driver import JaxDriver, _RowOrder
from gatekeeper_tpu.engine.veval import ProgramExecutor, _widen_args
from gatekeeper_tpu.library import constraint_doc, template_doc
from gatekeeper_tpu.library.templates import LIBRARY
from gatekeeper_tpu.target.k8s import K8sValidationTarget, TARGET_NAME


def _ns(name, labels):
    return {"apiVersion": "v1", "kind": "Namespace",
            "metadata": {"name": name, "labels": labels}}


def _client():
    d = JaxDriver()
    c = Backend(d).new_client([K8sValidationTarget()])
    c.add_template(template_doc("K8sRequiredLabels",
                                LIBRARY["K8sRequiredLabels"][0]))
    c.add_constraint(constraint_doc("K8sRequiredLabels", "need-owner",
                                    {"labels": ["owner"]}))
    return d, c


def _audit(_d, c):
    resp = c.audit(limit_per_constraint=20)
    return [(r.msg, (r.resource or {}).get("metadata", {}).get("name"))
            for r in resp.by_target[TARGET_NAME].results]


class TestAddDataBatch:
    def test_matches_looped_add_data(self):
        objs = [_ns(f"n{i:03d}", {"owner": "x"} if i % 3 else {})
                for i in range(40)]
        d1, c1 = _client()
        for o in objs:
            c1.add_data(o)
        d2, c2 = _client()
        resp = c2.add_data_batch(objs)
        assert resp.handled[TARGET_NAME]
        assert _audit(d1, c1) == _audit(d2, c2)

    def test_wipe_data_inside_batch(self):
        d, c = _client()
        c.add_data(_ns("stale", {}))
        c.add_data_batch([WipeData(), _ns("fresh", {})])
        names = [n for _m, n in _audit(d, c)]
        assert names == ["fresh"]

    def test_objects_queued_before_wipe_are_wiped(self):
        # looped semantics: objA lands, wipe removes it, objB survives
        d, c = _client()
        c.add_data_batch([_ns("a", {}), WipeData(), _ns("b", {})])
        assert [n for _m, n in _audit(d, c)] == ["b"]

    def test_unhandled_objects_skipped(self):
        d, c = _client()
        c.add_data_batch([_ns("good", {}), "not-an-object", 42])
        assert [n for _m, n in _audit(d, c)] == ["good"]


class TestRowOrder:
    def test_matches_dict_semantics(self):
        rng = np.random.default_rng(7)
        rows = rng.permutation(200)[:120]          # gaps + shuffle
        ro = _RowOrder(np.asarray(rows, dtype=np.int64))
        want = {int(r): i for i, r in enumerate(rows)}
        assert len(ro) == len(want)
        for r in range(220):
            assert (r in ro) == (r in want)
            if r in want:
                assert ro[r] == want[r]
            else:
                with pytest.raises(KeyError):
                    ro[r]

    def test_empty(self):
        ro = _RowOrder(np.zeros((0,), dtype=np.int64))
        assert len(ro) == 0 and 0 not in ro


class TestNarrowTransfer:
    def test_narrow_roundtrip_preserves_values(self):
        ex = ProgramExecutor()
        host = np.full((1 << 17,), -1, dtype=np.int32)
        host[::3] = np.arange(len(host[::3]), dtype=np.int32) % 100
        dev = ex._put("r:x", host, sharded=False)
        assert str(dev.dtype) == "int8"            # ids fit int8
        (widened,) = _widen_args((dev,))
        np.testing.assert_array_equal(np.asarray(widened), host)

    def test_wide_values_stay_int32(self):
        ex = ProgramExecutor()
        host = np.arange(1 << 17, dtype=np.int32)   # exceeds int16
        dev = ex._put("r:x", host, sharded=False)
        assert str(dev.dtype) == "int32"

    def test_scatter_widens_on_overflow(self):
        ex = ProgramExecutor()
        host = np.zeros((1 << 17,), dtype=np.int32)
        dev = ex._put("r:x", host, sharded=False)
        assert str(dev.dtype) == "int8"
        # churn introduces ids beyond the narrow range: the delta
        # scatter must re-upload rather than wrap around
        host2 = host.copy()
        rows = np.arange(64, dtype=np.int64)
        host2[rows] = 70_000
        out = ex._scatter_rows("r:x", dev, host2, rows, sharded=False)
        np.testing.assert_array_equal(
            np.asarray(_widen_args((out,))[0]), host2)

    def test_scatter_narrow_in_range(self):
        ex = ProgramExecutor()
        host = np.zeros((1 << 17,), dtype=np.int32)
        dev = ex._put("r:x", host, sharded=False)
        host2 = host.copy()
        rows = np.arange(8, dtype=np.int64)
        host2[rows] = 5
        out = ex._scatter_rows("r:x", dev, host2, rows, sharded=False)
        assert str(out.dtype) == "int8"
        np.testing.assert_array_equal(
            np.asarray(_widen_args((out,))[0]), host2)
