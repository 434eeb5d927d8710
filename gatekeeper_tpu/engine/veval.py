"""JAX evaluation of vectorized programs.

This is the replacement for the reference's hot loop — OPA's recursive
tree-walking evaluator (opa/topdown/eval.go:156, ``eval/evalExpr/
biunify``) which runs the whole audit cross-product single-threaded
inside one query (regolib/src.go:38-52).  Here the same semantics run as
one jitted tensor expression over the padded ``[n_constraints,
n_resources(, n_elements)]`` lattice: gathers from host-built tables,
integer/float compares, boolean algebra, and masked reductions.  XLA
fuses the whole thing into a handful of kernels; no per-document Python
or per-rule dispatch survives on the hot path.

Tri-state evaluation: each node yields ``(defined, value)``; a rule
fires where every conjunct is defined and truthy (only ``false`` and
undefined fail — rego/interp.py mirrors this exactly).  The element
axis, when present, is reduced existentially under its presence mask.

Executables are cached by (program structure, shape bucket): growing
inventories re-enter the same bucket sizes and never recompile — unlike
the reference, which recompiles every module on any PutModule
(drivers/local/local.go:65-93).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from gatekeeper_tpu.ir.prep import _STR_PREFIX, Bindings, binding_axes
from gatekeeper_tpu.ir.program import Node, Program, RuleSpec

_3D = (1, 1, 1)

# -- background-compile thread registry ------------------------------
# Threads that can hold an XLA compile in flight (tier upgrades, delta
# prewarms, audit warmups) are joined before interpreter teardown via
# one atexit drain; see ProgramExecutor.spawn_bg.
_BG_LOCK = __import__("threading").Lock()
_BG_THREADS: list = []
_bg_drain_registered = False

# Collective (shard_map) executables must be launched one at a time,
# held to completion: two in-flight programs with cross-device
# collectives can interleave on the per-device execution threads so
# that one program's all-reduce rendezvous never assembles all its
# participants — XLA's rendezvous watchdog then *kills the process*
# (rendezvous.cc "Exiting to ensure a consistent program state";
# observed as the round-3 `Fatal Python error: Aborted` in concurrent
# dispatch).  Same discipline as NCCL's "issue collectives in a
# consistent order" rule.  PROCESS-wide, not per-executor: two
# executors over the same devices (the driver's and a test's) are the
# same hazard.  Single-device executables are unaffected (their async
# fetches overlap freely).
#
# SCOPE: this (unfair) lock only serializes launches WITHIN one
# process.  On a mesh spanning processes (jax.distributed), each
# process's threads could still acquire their local lock in different
# orders and launch cross-host collective programs in different orders
# — the same rendezvous deadlock, now across DCN.  Multi-host meshes
# therefore require single-flight, deterministically ORDERED dispatch:
# the driver detects a spanning mesh (mesh_spans_processes) and
# dispatches collective kinds serially in sorted-kind order from the
# sweep thread (engine/jax_driver.query_audit), which every process
# reproduces identically.
_COLLECTIVE_EXEC_LOCK = __import__("threading").Lock()


def mesh_spans_processes(mesh) -> bool:
    """True when the mesh includes devices of other processes — the
    cross-host collective-ordering discipline then applies."""
    if mesh is None:
        return False
    import jax
    pid = jax.process_index()
    return any(d.process_index != pid for d in mesh.devices.flat)

_EXECUTORS = __import__("weakref").WeakSet()


def quiesce_upgrades(timeout: float = 120.0) -> bool:
    """Block until every live executor's upgrade queue and in-flight
    compiles drain (or timeout).  Benchmarks call this between
    configs so one phase's background recompiles never contaminate the
    next phase's measurement on a small host."""
    import time as _time
    deadline = _time.monotonic() + timeout
    while _time.monotonic() < deadline:
        busy = False
        for ex in list(_EXECUTORS):
            with ex._lock:
                if ex._upgrade_q or ex._compile_inflight \
                        or getattr(ex, "_upgrade_busy", 0):
                    busy = True
                    break
        if not busy:
            return True
        _time.sleep(0.1)
    return False


def _register_bg_drain() -> None:
    global _bg_drain_registered
    if _bg_drain_registered:
        return
    _bg_drain_registered = True
    import atexit
    import time as _time

    def _drain():
        ProgramExecutor._shutdown.set()
        deadline = _time.monotonic() + 120
        with _BG_LOCK:
            threads = list(_BG_THREADS)
        for t in threads:
            t.join(timeout=max(0.0, deadline - _time.monotonic()))
    atexit.register(_drain)


class _LazyTwoTier:
    """Deferred two-tier jit: traces/compiles on first call (shapes come
    from the live arguments), serving the fast-compiled executable while
    the executor's background worker swaps in the full-effort twin.
    Retraces per distinct input signature like jax.jit would (narrow-
    transferred columns may arrive int8/int16/int32)."""

    def __init__(self, executor, raw, fast: bool = True, name=None,
                 upgrade=True):
        import threading as _threading
        self._ex = executor
        self._raw = raw
        self._fast = fast
        self._name = name      # stable marker-key base (upgraded-keys)
        self._upgrade = upgrade
        self._fns: dict[tuple, Any] = {}
        self._lock = _threading.Lock()
        self._inflight: dict[tuple, Any] = {}   # sig -> Event

    def _get_or_build(self, sig, lower):
        """Single-flight per signature: a prewarm and the first real
        call must not compile the same executable twice (the compile
        service serializes — a duplicate doubles cold latency)."""
        import threading as _threading
        while True:
            with self._lock:
                fn = self._fns.get(sig)
                if fn is not None:
                    return fn
                ev = self._inflight.get(sig)
                if ev is None:
                    ev = _threading.Event()
                    self._inflight[sig] = ev
                    break
            ev.wait()
        try:
            from gatekeeper_tpu.obs.trace import get_tracer
            role = self._raw.__name__
            with get_tracer().span("executor.compile", cat="compile",
                                   root=True, role=role, upgrade=False):
                lowered = lower()

                def install(full, _sig=sig):
                    self._fns[_sig] = full

                if self._fast:
                    fn = self._ex._compile_two_tier(
                        lowered, install, role,
                        marker_key=(self._name, sig)
                        if self._name is not None else None,
                        upgrade=self._upgrade)
                else:
                    fn = lowered.compile()
            with self._lock:
                self._fns[sig] = fn
            return fn
        finally:
            with self._lock:
                self._inflight.pop(sig, None)
            ev.set()

    def __call__(self, *args):
        sig = tuple((tuple(a.shape), str(a.dtype)) for a in args)
        fn = self._fns.get(sig)
        if fn is None:
            fn = self._get_or_build(
                sig, lambda: jax.jit(self._raw).lower(*args))
        return fn(*args)

    def prewarm(self, *examples) -> None:
        """Compile for the given jax.ShapeDtypeStruct signature ahead of
        the first call (cold audits overlap this with host prep)."""
        sig = tuple((tuple(a.shape), str(a.dtype)) for a in examples)
        if sig not in self._fns:
            self._get_or_build(
                sig, lambda ex=tuple(examples):
                jax.jit(self._raw).lower(*ex))


def _widen_args(args: tuple) -> tuple:
    """Upcast narrow-transferred id columns (_put ships int8/int16 to
    cut host->device bytes) back to int32 *inside* the jitted program —
    the cast fuses into the first consumer kernel, costing no extra
    dispatch or transfer."""
    return tuple(a.astype(jnp.int32)
                 if a.dtype in (jnp.int8, jnp.int16) else a
                 for a in args)


def _fires(dv: tuple[jax.Array, jax.Array]) -> jax.Array:
    """defined & truthy; only False and undefined fail in Rego."""
    d, v = dv
    if v.dtype == jnp.bool_:
        return d & v
    return d


def _to3(a: jax.Array, axes: str) -> jax.Array:
    """Reshape a bound array into the canonical [C, R, E] lattice."""
    if axes == "c":
        return a.reshape(a.shape[0], 1, 1)
    if axes == "r":
        return a.reshape(1, a.shape[0], 1)
    if axes == "e":
        return a.reshape(1, a.shape[0], a.shape[1])
    raise ValueError(axes)


def _dfa_device_table(arrays: dict, dname: str) -> jax.Array:
    """Per-interned-id regex verdicts [t_pad] bool, computed on device:
    a ``lax.scan`` of gathers runs the bound [S, 256] transition table
    over the interner's packed byte matrix (prefix bytes skipped — val
    columns hold encoded strings, ir/encode).  One trailing TERM step
    after the scan keeps ``$`` exact for strings that fill the row
    width (mirrors pack_strings' [U, L+1] terminator column).  Ids the
    byte rows cannot represent exactly take the host-oracle fallback
    ``.xv`` — never an approximation."""
    # asarray: eager callers (transval, explain, delta slices) hand in
    # numpy arrays, and numpy's fancy indexing would call __array__ on
    # the scan tracer; inside jit these are no-ops on device arrays
    trans = jnp.asarray(arrays[dname + ".trans"])
    accept = jnp.asarray(arrays[dname + ".accept"])
    payload = jnp.asarray(
        arrays["__strbytes__"])[:, len(_STR_PREFIX):].astype(jnp.int32)

    def step(state, col):
        return trans[state, col], None

    init = jnp.zeros((payload.shape[0],), dtype=jnp.int32)
    state, _ = jax.lax.scan(step, init, payload.T)
    hit = accept[trans[state, 0]]
    return jnp.where(jnp.asarray(arrays["__strdfaok__"]), hit,
                     jnp.asarray(arrays[dname + ".xv"]))


def _with_dfa_tables(program: Program,
                     d: dict[str, jax.Array]) -> dict[str, jax.Array]:
    """Precompute every ``dfa_match`` verdict table once per evaluation
    (into a COPY of the arrays dict, keyed ``<name>.devtab``): the
    chunked mask/top-k paths would otherwise re-run the byte scan in
    every lax.scan chunk body."""
    names = sorted({n.meta[0] for n in program.nodes
                    if n.op == "dfa_match"})
    if not names:
        return d
    d = dict(d)
    for nm in names:
        if nm + ".devtab" not in d:
            d[nm + ".devtab"] = _dfa_device_table(d, nm)
    return d


class _Evaluator:
    def __init__(self, program: Program, arrays: dict[str, jax.Array]):
        self.p = program
        self.arrays = arrays
        self.cache: dict[int, tuple[jax.Array, jax.Array]] = {}
        self.dfa_memo: dict[str, jax.Array] = {}

    def _dfa_devtab(self, dname: str) -> jax.Array:
        tab = self.arrays.get(dname + ".devtab")
        if tab is None:                  # eager paths (transval,
            tab = self.dfa_memo.get(dname)   # explain, delta slices)
            if tab is None:
                tab = _dfa_device_table(self.arrays, dname)
                self.dfa_memo[dname] = tab
        return tab

    def node(self, i: int) -> tuple[jax.Array, jax.Array]:
        hit = self.cache.get(i)
        if hit is None:
            hit = self._eval(self.p.nodes[i])
            self.cache[i] = hit
        return hit

    def _eval(self, n: Node) -> tuple[jax.Array, jax.Array]:
        op = n.op
        if op == "const":
            value, dtype = n.meta
            v = jnp.asarray(value, dtype=dtype)
            return jnp.ones(_3D, dtype=bool), v.reshape(_3D)
        if op == "input":
            name, kind = n.meta
            axes = {"r": "r", "e": "e", "c": "c"}[kind[0]]
            if kind.endswith("_num"):
                v = _to3(self.arrays[name + ".v"], axes)
                d = _to3(self.arrays[name + ".p"], axes)
                return d, v
            if kind.endswith("_id"):
                v = _to3(self.arrays[name], axes)
                return v >= 0, v
            v = _to3(self.arrays[name], axes)  # bool
            return jnp.ones_like(v), v
        if op == "table":
            (tname,) = n.meta
            d_i, idx = self.node(n.args[0])
            ci = jnp.clip(idx, 0, None)
            ok = self.arrays[tname + ".ok"][ci]
            val = self.arrays[tname + ".v"][ci]
            return d_i & ok, val
        if op == "dfa_match":
            # in-program regex: one gather into the per-id verdict
            # table.  Verdict doubles as the defined bit exactly like
            # the bool-table route (`ok` encodes defined AND truthy).
            (dname,) = n.meta
            d_i, idx = self.node(n.args[0])
            v = self._dfa_devtab(dname)[jnp.clip(idx, 0, None)]
            return d_i & v, v
        if op in ("ptable_any", "ptable_all"):
            # pre-combined per-constraint table (ir/prep.py): one gather,
            # no [C, K, R, E] per-param axis on device
            tname, _ = n.meta
            d_i, idx = self.node(n.args[0])
            vmap = self.arrays[tname + ".vmap"]            # [T] -> dense u
            tbl = self.arrays[tname + (".any" if op == "ptable_any" else ".all")]
            sentinel = tbl.shape[1] - 1
            in_rng = (idx >= 0) & (idx < vmap.shape[0])
            u = jnp.where(in_rng, vmap[jnp.clip(idx, 0, vmap.shape[0] - 1)],
                          sentinel)
            v = tbl[:, u[0]]                               # [C, R, E]
            return d_i & jnp.ones_like(v), v
        if op == "keyed_val":
            # per-constraint dynamic-key dict lookup (ir/prep.KeyedValReq):
            # value id of dict[key_c] per (constraint, row); undefined
            # where the constraint's key or the row's entry is absent
            (name,) = n.meta
            kv = self.arrays[name + ".kv"]                 # [K, R] int32
            sel = self.arrays[name + ".sel"]               # [C] int32
            picked = kv[jnp.clip(sel, 0, None)]            # [C, R]
            v = picked[:, :, None]                         # [C, R, 1]
            d = (sel >= 0)[:, None, None] & (v >= 0)
            return d, v
        if op == "cmp":
            (cop,) = n.meta
            da, va = self.node(n.args[0])
            db, vb = self.node(n.args[1])
            d = da & db
            if cop == "==":
                v = va == vb
            elif cop == "!=":
                v = va != vb
            elif cop == "<":
                v = va < vb
            elif cop == "<=":
                v = va <= vb
            elif cop == ">":
                v = va > vb
            else:
                v = va >= vb
            return d, v
        if op == "and":
            a = _fires(self.node(n.args[0]))
            b = _fires(self.node(n.args[1]))
            return jnp.ones_like(a & b), a & b
        if op == "or":
            a = _fires(self.node(n.args[0]))
            b = _fires(self.node(n.args[1]))
            return jnp.ones_like(a | b), a | b
        if op == "not":
            a = _fires(self.node(n.args[0]))
            return jnp.ones_like(a), ~a
        if op == "in_cset":
            (cname,) = n.meta
            d_i, idx = self.node(n.args[0])
            # idx is [1, R, E] (shared leaf) or [C, R, E] (per-constraint,
            # e.g. a keyed_val lookup)
            vmap = self.arrays[cname + ".vmap"]            # [T] -> dense u
            bitmap = self.arrays[cname + ".bitmap"]        # [C, U]
            sentinel = bitmap.shape[1] - 1
            in_rng = (idx >= 0) & (idx < vmap.shape[0])
            u = jnp.where(in_rng, vmap[jnp.clip(idx, 0, vmap.shape[0] - 1)],
                          sentinel)
            if u.shape[0] == 1:
                v = bitmap[:, u[0]]                        # [C, R, E]
            else:
                c, r, e = u.shape
                v = jnp.take_along_axis(bitmap, u.reshape(c, r * e),
                                        axis=1).reshape(c, r, e)
            return d_i & jnp.ones_like(v), v
        if op in ("cset_not_subset_memb", "cset_subset_memb"):
            # required-keys subset test as a bf16 matmul on the MXU:
            # miss[c, r] = |{l : B[c, l] & ~memb[l, r]}| — exact in f32
            # accumulation (0/1 operands, L < 2^24)
            cname, mname = n.meta
            memb = self.arrays[mname]                      # [L, R]
            B = self.arrays[cname + ".B"]                  # [C, L]
            # bf16 feeds the MXU natively; CPU (tests) lacks bf16 dot
            mm = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
            miss = jax.lax.dot_general(
                B.astype(mm), (~memb).astype(mm),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # [C, R]
            if op == "cset_not_subset_memb":
                v = (miss > 0.5)[:, :, None]
            else:
                v = (miss < 0.5)[:, :, None]
            return jnp.ones_like(v), v
        if op == "elem_keys_missing":
            # ∃ required key (per constraint) absent/false in the element
            # dict: B [C, K] x ~ekm [K, R, E] as a matmul over the small
            # K axis (same MXU trick as the label-subset ops)
            cname, ekname = n.meta
            ekm = self.arrays[ekname]                      # [K, R, E] bool
            B = self.arrays[cname + ".B"]                  # [C, K]
            k, r, e = ekm.shape
            mm = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
            miss = jax.lax.dot_general(
                B.astype(mm), (~ekm).reshape(k, r * e).astype(mm),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # [C, R*E]
            v = (miss > 0.5).reshape(B.shape[0], r, e)
            return jnp.ones_like(v), v
        if op in ("any_e", "all_e", "count_e"):
            (axis,) = n.meta
            pres = self.arrays[f"__elem__:{axis}"][None]   # [1, R, E]
            a = _fires(self.node(n.args[0]))
            if op == "any_e":
                v = jnp.any(a & pres, axis=2, keepdims=True)
                return jnp.ones_like(v), v
            if op == "all_e":
                v = jnp.all(a | ~pres, axis=2, keepdims=True)
                return jnp.ones_like(v), v
            v = jnp.sum((a & pres).astype(jnp.float32), axis=2, keepdims=True)
            return jnp.ones(v.shape, dtype=bool), v
        if op == "arith":
            (aop,) = n.meta
            da, va = self.node(n.args[0])
            db, vb = self.node(n.args[1])
            d = da & db
            if aop == "+":
                v = va + vb
            elif aop == "-":
                v = va - vb
            elif aop == "*":
                v = va * vb
            else:
                d = d & (vb != 0)
                v = va / jnp.where(vb == 0, 1.0, vb)
            return d, v
        raise ValueError(f"unknown IR op {op!r}")


def _eval_program(program: Program, arrays: dict[str, jax.Array]) -> jax.Array:
    """-> violation mask [C, R] bool (padded).  An optional "__match__"
    input (the vectorized constraint match mask, engine/match.py) gates
    the result on device."""
    ev = _Evaluator(program, arrays)
    alive = arrays["__alive__"][None, :, None]
    cvalid = arrays["__cvalid__"][:, None, None]
    viol = None
    for rule in program.rules:
        total = None
        for ci in rule.conjuncts:
            f = _fires(ev.node(ci))
            total = f if total is None else total & f
        if total is None:
            total = jnp.ones(_3D, dtype=bool)
        total = total & alive & cvalid
        if rule.elem_axis is not None:
            pres = arrays[f"__elem__:{rule.elem_axis}"][None]
            fired = jnp.any(total & pres, axis=2)
        else:
            # broadcast may still carry E=1; reduce it
            fired = jnp.any(total, axis=2)
        viol = fired if viol is None else viol | fired
    c_pad = arrays["__cvalid__"].shape[0]
    r_pad = arrays["__alive__"].shape[0]
    if viol is None:
        viol = jnp.zeros((c_pad, r_pad), dtype=bool)
    else:
        viol = jnp.broadcast_to(viol, (c_pad, r_pad))
    match = arrays.get("__match__")
    if match is not None:
        viol = viol & match
    return viol


R_CHUNK = 1 << 15
"""Rows per device evaluation chunk.  Above this, the [C, R(, E)]
intermediates are produced chunk-by-chunk under a ``lax.scan`` so peak
HBM stays bounded regardless of inventory size (SURVEY §7 step 9);
top-k and counts merge across chunks on device.  Tuned on v5e at
1M x 201: 2^15 keeps per-chunk intermediates VMEM-friendly (0.45s
steady vs 0.64s at 2^16 and 0.9s at 2^17)."""


def _r_axis(name: str) -> int | None:
    """Which dim of a bound array is the resource axis (None if none).
    Derived from the shared prep naming convention (ir/prep.binding_axes,
    also the source of truth for multi-chip sharding); unknown binding
    names raise there rather than silently skipping the chunk slice."""
    axes = binding_axes(name)
    return axes.index("r") if "r" in axes else None


def _slice_r(name: str, arr: jax.Array, off, rc: int) -> jax.Array:
    ax = _r_axis(name)
    if ax is None:
        return arr
    return jax.lax.dynamic_slice_in_dim(arr, off, rc, axis=ax)


def _n_chunks(r_pad: int) -> int:
    if r_pad <= R_CHUNK or r_pad % R_CHUNK != 0:
        return 1
    return r_pad // R_CHUNK


def _eval_mask(program: Program, d: dict[str, jax.Array]) -> jax.Array:
    """Full violation mask [C, R], chunked over R when large."""
    d = _with_dfa_tables(program, d)
    r_pad = d["__alive__"].shape[0]
    c_pad = d["__cvalid__"].shape[0]
    nc = _n_chunks(r_pad)
    if nc == 1:
        return _eval_program(program, d)
    rc = r_pad // nc

    def body(_, i):
        dd = {nm: _slice_r(nm, a, i * rc, rc) for nm, a in d.items()}
        return None, _eval_program(program, dd)

    _, ys = jax.lax.scan(body, None, jnp.arange(nc))   # [nc, C, rc]
    return jnp.moveaxis(ys, 0, 1).reshape(c_pad, r_pad)


def _inv_join_mask(src: jax.Array, inv: jax.Array, sel: jax.Array,
                   names: jax.Array, exclude_same_name: bool) -> jax.Array:
    """Device twin of ir/prep.build_inv_join — the duplicate-detection
    inventory join (K8sUniqueIngressHost) as an on-device
    segment-reduce: sort the selected inventory values once, then
    per-row occurrence counts are two ``searchsorted`` gathers
    (``right - left``), with the same-name exclusion counted by a
    merged lexsort over (value, name) pairs — int64 pair keys are NOT
    available (default jax is 32-bit; jnp.int64 silently truncates,
    ``1 << 32`` becomes 0).  All shapes are static ([r_pad]), so this
    fuses into the violation-mask program — the join stops being a
    host-computed bool column and becomes part of the jitted sweep,
    which is what makes the cross-row kind devpages-eligible.

    ``sel`` is the inventory-side row filter (alive & joined-kind
    [& namespaced]); ``src``/``inv``/``names`` are int32 id columns
    with MISSING = -1.  Mirrors the host builder bit-for-bit: missing
    names still participate on the inventory side (encoded as
    ``value*big - 1``), the review side counts own-pairs only for
    present src AND name ids."""
    sentinel = jnp.int32(np.iinfo(np.int32).max)
    invsel = sel & (inv >= 0)
    sh = jnp.sort(jnp.where(invsel, inv, sentinel))
    left = jnp.searchsorted(sh, src, side="left")
    right = jnp.searchsorted(sh, src, side="right")
    total = jnp.where(src >= 0, right - left, 0)
    if not exclude_same_name:
        return total > 0
    # own-pair counts: merge the inventory pairs with the review
    # queries into one lexsort keyed (value, name, flag) — the flag
    # axis breaks ties, deciding whether equal inventory pairs sort
    # before the query (right bound) or after it (left bound), so the
    # exclusive inventory prefix-count at each query's sorted position
    # IS the bound and own = right - left.  Counting this way needs no
    # composite integer key, so it survives 32-bit jax.
    n = src.shape[0]
    inm = jnp.where(invsel, names, sentinel)
    iv = jnp.where(invsel, inv, sentinel)
    comb_v = jnp.concatenate([iv, jnp.where(src >= 0, src, sentinel)])
    comb_n = jnp.concatenate([inm, names])
    is_q = jnp.concatenate([jnp.zeros((n,), bool), jnp.ones((n,), bool)])

    def _bound(q_first: bool) -> jax.Array:
        flag = jnp.where(is_q == q_first, 0, 1)
        order = jnp.lexsort((flag, comb_n, comb_v))
        inv_sorted = ~is_q[order]
        cum_excl = jnp.cumsum(inv_sorted.astype(jnp.int32)) \
            - inv_sorted.astype(jnp.int32)
        qpos = jnp.where(order >= n, order - n, 0)
        contrib = jnp.where(order >= n, cum_excl, 0)
        return jnp.zeros((n,), jnp.int32).at[qpos].add(contrib)

    own = jnp.where((src >= 0) & (names >= 0),
                    _bound(False) - _bound(True), 0)
    return (total - own) > 0


def _eval_topk(program: Program, d: dict[str, jax.Array], k: int,
               score_base: int | None = None):
    """Violation top-k, chunked over R: per-chunk lax.top_k merged into
    a running [C, k] best set, counts summed across chunks.  Returns
    (counts [C], rows [C, k], scores [C, k]) — a positive score marks a
    valid entry.  Scores are ``score_base - rank`` so they stay
    comparable across chunks AND across shards: inside shard_map pass
    the GLOBAL r_pad as score_base (the sharded ``__rank__`` carries
    global ranks that can exceed the local slice length)."""
    d = _with_dfa_tables(program, d)
    r_pad = d["__alive__"].shape[0]
    c_pad = d["__cvalid__"].shape[0]
    base_score = score_base if score_base is not None else r_pad
    nc = _n_chunks(r_pad)
    if nc == 1:
        viol = _eval_program(program, d)
        return topk_reduce(viol, k, d.get("__rank__"),
                           score_base=base_score, return_scores=True)
    rc = r_pad // nc
    k_out = min(k, r_pad)
    k_eff = min(k_out, rc)

    def body(carry, i):
        off = i * rc
        dd = {nm: _slice_r(nm, a, off, rc) for nm, a in d.items()}
        viol = _eval_program(program, dd)              # [C, rc]
        cnt = jnp.sum(viol, axis=1, dtype=jnp.int32)
        rank = dd.get("__rank__")
        if rank is None:
            rank = off + jnp.arange(rc, dtype=jnp.int32)
        score = jnp.where(viol, base_score - rank[None, :], 0)
        vals, rows = jax.lax.top_k(score, k_eff)
        rows = rows + off
        bs, br, bc = carry
        ms, mi = jax.lax.top_k(jnp.concatenate([bs, vals], axis=1), k_out)
        mr = jnp.take_along_axis(jnp.concatenate([br, rows], axis=1), mi, axis=1)
        return (ms, mr, bc + cnt), None

    init = (jnp.zeros((c_pad, k_out), jnp.int32),
            jnp.zeros((c_pad, k_out), jnp.int32),
            jnp.zeros((c_pad,), jnp.int32))
    (vals, rows, counts), _ = jax.lax.scan(body, init, jnp.arange(nc))
    if k_out < k:
        vals = jnp.pad(vals, ((0, 0), (0, k - k_out)))
        rows = jnp.pad(rows, ((0, 0), (0, k - k_out)))
    return counts, rows, vals


def pad_rank(rank: np.ndarray, r_pad: int) -> np.ndarray:
    """Pad a [n_rows] rank array to [r_pad].  The fill must stay within
    [live-rank, r_pad) so padded rows can never outscore live ones in
    the ``r_pad - rank`` top-k score (shared by the single-device and
    sharded capped paths)."""
    pr = np.full((r_pad,), r_pad - 1, dtype=np.int32)
    pr[: rank.shape[0]] = rank
    return pr


def topk_reduce(viol: jax.Array, k: int, rank: jax.Array | None = None,
                score_base: int | None = None, return_scores: bool = False):
    """First-k violating resource rows per constraint, on device.

    Returns (counts [C] int32, rows [C, k] int32, valid [C, k] bool).
    Implements the audit manager's per-constraint violation cap
    (reference manager.go:35,161-199) as a device reduction so the host
    never materializes the full mask.

    `rank` ([r_pad] int32, lower = earlier) orders the capped subset;
    the driver passes the sorted-cache-key rank so the capped device
    subset matches the scalar driver's cap order exactly (after
    deletes/re-inserts, raw row index and cache-key order diverge).
    Default: raw row order.  k is clamped to r_pad (lax.top_k requires
    k <= axis size; callers may cap at 20 with fewer padded rows) and
    the outputs are padded back to width k."""
    c_pad, r_pad = viol.shape
    base_score = score_base if score_base is not None else r_pad
    k_eff = min(k, r_pad)
    counts = jnp.sum(viol, axis=1, dtype=jnp.int32)
    if rank is None:
        rank = jnp.arange(r_pad, dtype=jnp.int32)
    score = jnp.where(viol, base_score - rank, 0)
    vals, rows = jax.lax.top_k(score, k_eff)
    if k_eff < k:
        vals = jnp.pad(vals, ((0, 0), (0, k - k_eff)))
        rows = jnp.pad(rows, ((0, 0), (0, k - k_eff)))
    if return_scores:
        return counts, rows, vals
    return counts, rows, vals > 0


def explain(program: Program, bindings: Bindings, ci: int, row: int,
            match: np.ndarray | None = None) -> str:
    """Mask dump for one (constraint, resource) pair: every IR node's
    (defined, value) on the [1, 1(, E)] slice, plus each rule's conjunct
    verdicts and the match-mask gate — the device-path analogue of the
    scalar tracer (SURVEY §5 tracing).  Runs the real evaluator on
    sliced bindings, so what it prints is exactly what the device
    computes."""
    matched = True if match is None else bool(match[ci, row])
    sliced: dict[str, jax.Array] = {}
    for nm, arr in bindings.arrays.items():
        axes = binding_axes(nm)
        a = arr
        for d, ax in enumerate(axes):
            if ax == "r":
                a = np.take(a, [row], axis=d)
            elif ax == "c":
                a = np.take(a, [ci], axis=d)
        sliced[nm] = jnp.asarray(a)
    ev = _Evaluator(program, sliced)
    lines = [f"explain constraint={ci} row={row}"]
    for i, n in enumerate(program.nodes):
        try:
            d, v = ev.node(i)
        except KeyError as e:
            lines.append(f"  n{i:<3} {n.op:<22} <missing binding {e}>")
            continue
        dv = np.asarray(d).ravel()
        vv = np.asarray(v).ravel()
        show = vv if vv.size <= 8 else vv[:8]
        lines.append(f"  n{i:<3} {n.op:<22} meta={n.meta!r} "
                     f"defined={bool(dv.all())} value={show.tolist()}")
    for ri, rule in enumerate(program.rules):
        # elementwise AND of conjuncts, reduced exactly like
        # _eval_program (existential over the presence-masked E axis)
        total = None
        verdicts = []
        for cix in rule.conjuncts:
            f = _fires(ev.node(cix))
            verdicts.append(f"n{cix}={np.asarray(f).ravel().astype(int).tolist()}")
            total = f if total is None else total & f
        if total is None:
            fired = True
        else:
            total = total & sliced["__alive__"][None, :, None] \
                & sliced["__cvalid__"][:, None, None]
            if rule.elem_axis is not None:
                pres = sliced[f"__elem__:{rule.elem_axis}"][None]
                fired = bool(np.asarray(jnp.any(total & pres)))
            else:
                fired = bool(np.asarray(jnp.any(total)))
        fired = fired and matched
        lines.append(f"  rule{ri} axis={rule.elem_axis or '-'} "
                     f"conjuncts[{' '.join(verdicts)}] -> "
                     f"{'FIRES' if fired else 'no'}")
    lines.insert(1, f"  match gate: "
                    f"{'matched' if matched else 'NOT matched (constraint match criteria exclude this resource)'}")
    return "\n".join(lines)


class PendingMask:
    """In-flight full violation mask (see run_async)."""

    def __init__(self, mask, n_constraints: int, n_resources: int):
        self._mask = mask
        self._nc = n_constraints
        self._nr = n_resources

    def block(self) -> "PendingMask":
        """Wait until the device result exists (NOT until it is on the
        host — the D2H copy stays async).  The full-sweep pipeline uses
        this to meter per-kind device occupancy without forcing the
        host fetch into the measured stage."""
        jax.block_until_ready(self._mask)
        return self

    def get(self) -> np.ndarray:
        return np.asarray(self._mask)[: self._nc, : self._nr]


class PendingTopK:
    """In-flight packed top-k result (see run_topk_async)."""

    def __init__(self, packed, n_constraints: int, k: int):
        self._packed = packed
        self._nc = n_constraints
        self._k = k

    def block(self) -> "PendingTopK":
        """See PendingMask.block."""
        jax.block_until_ready(self._packed)
        return self

    def get(self):
        p = np.asarray(self._packed)[: self._nc]
        counts = p[:, 0]
        rows = p[:, 1: 1 + self._k]
        valid = p[:, 1 + self._k:].astype(bool)
        return counts, rows, valid


class ProgramExecutor:
    """Jit-cache wrapper: one compiled executable per (program, bucket).
    Executables also persist across processes via JAX's on-disk
    compilation cache (utils/compile_cache) — a restart re-traces but
    skips the multi-second XLA compile per (template, bucket)."""

    def __init__(self, mesh=None):
        from gatekeeper_tpu.utils.compile_cache import (
            enable_persistent_cache, persistent_cache_stats)
        enable_persistent_cache()
        # process-wide persistent (on-disk) cache hit/miss counters —
        # distinct from the in-process counters below
        self.persistent_stats = persistent_cache_stats()
        self._cache: dict[tuple, Any] = {}
        self._lock = __import__("threading").Lock()   # dispatch runs threaded
        self._trace_lock = __import__("threading").Lock()
        # see _COLLECTIVE_EXEC_LOCK below — per-process, because the
        # hazard is per device set, not per executor instance
        self._collective_lock = _COLLECTIVE_EXEC_LOCK
        _EXECUTORS.add(self)
        # set by the driver around a sweep: background upgrade compiles
        # defer while a sweep is in flight (GIL-bound retraces would
        # slow the sweep's host phases)
        self.sweep_active = __import__("threading").Event()
        self._compile_inflight: dict[tuple, Any] = {}  # key -> Event
        self.compiles = 0      # executable-cache misses (trace+compile)
        self.cache_hits = 0    # executable-cache hits
        self.dispatches = 0    # device evaluations launched (run_async,
        #                        run_topk_async, eval_mask_delta)
        self.trace_seconds = 0.0    # cumulative jit-trace (GIL-bound)
        self.compile_seconds = 0.0  # cumulative XLA compile (parallel)
        self.upgrades = 0      # background full-opt recompiles landed
        # H2D accounting: bytes staged to device through _put (whole
        # arrays) and _scatter_rows (row-sized update records), split
        # so the devpages stanza can show churn shipping records
        # instead of columns.  Plain int adds under the GIL — read by
        # the driver per sweep as (h2d_bytes, h2d_scatter_bytes,
        # h2d_scatter_rows) deltas.
        self.h2d_bytes = 0
        self.h2d_scatter_bytes = 0
        self.h2d_scatter_rows = 0
        # the same bytes per thread (dirty-row indices of the delta
        # path included): a span takes the difference around one call,
        # which the executor-wide counters cannot give while the
        # dispatch pool uploads for several kinds at once
        self._h2d_local = __import__("threading").local()
        self._upgrade_q: list = []
        self._upgrade_thread = None
        # Stage-7 retrace sentinel (analysis/compilesurface.py): the
        # driver installs a guard(program, arrays, delta_k) -> bool
        # consulted ONLY on a jit cache miss.  An uncertified signature
        # bumps retrace_uncertified; under strict mode the dispatch is
        # refused (UncertifiedRetrace) instead of compiled mid-traffic.
        self.surface_guard = None
        self.retrace_uncertified = 0
        # multi-chip: a (c, r) jax.sharding.Mesh — bindings device_put
        # with NamedShardings per ir/prep.binding_axes, executables built
        # via shard_map (parallel/sharding.py).  None = single device.
        self.mesh = mesh

    def reset_for_recovery(self) -> None:
        """Drop in-process compiled executables after a backend
        recovery (resilience/supervisor): cached jitted fns hold the
        dead backend's client, so the next dispatch must re-trace and
        re-jit onto the recovered one.  The on-disk persistent cache
        and the pending upgrade queue survive — only live handles are
        dropped."""
        with self._lock:
            self._cache.clear()
            self._upgrade_q.clear()

    # ------------------------------------------------------------------
    # two-tier compilation
    #
    # XLA-for-TPU compile time is dominated by execution-time
    # optimization passes; `exec_time_optimization_effort=-1` compiles
    # ~4x faster with near-identical generated code for these
    # gather/compare/reduce programs.  Cold starts serve the
    # fast-compiled executable immediately and a single background
    # worker re-compiles at default effort and swaps it in — steady
    # state always converges to the fully optimized binary, and the
    # upgrade queue is deferred so it never competes with the cold
    # flurry for the (serialized) compile service.

    FAST_OPTS = {"exec_time_optimization_effort": -1.0}
    UPGRADE_DELAY_S = 3.0   # quiesce horizon after a cold flurry —
    #                         short, so upgrades land between sweeps
    #                         instead of smearing into later work
    #                         (sweep_active gates them off live sweeps)
    _shutdown = __import__("threading").Event()

    @staticmethod
    def spawn_bg(target, name: str):
        """Start a background thread that may issue XLA compiles, and
        register it for the process-exit drain.  A compile (an RPC to
        the serialized compile service, or a C++ call into XLA) in
        flight while the interpreter finalizes aborts the whole process
        — C++ statics destruct under the thread and `terminate` fires
        with an unrethrowable exception.  Every compile-capable thread
        must therefore be joined before Python teardown: daemon threads
        that merely *exist* at exit are exactly the crash."""
        import threading as _threading
        t = _threading.Thread(target=target, name=name, daemon=True)
        with _BG_LOCK:
            _BG_THREADS[:] = [x for x in _BG_THREADS if x.is_alive()]
            _BG_THREADS.append(t)
            _register_bg_drain()
        t.start()
        return t

    def _compile_two_tier(self, lowered, install, role: str,
                          marker_key=None, upgrade=True):
        """Compile `lowered` fast; schedule the full-effort twin and
        hand it to `install(full_fn)` when ready.  Falls back to a
        single default-effort compile when the option is unsupported
        (non-TPU backends) or fast compilation fails.

        When a previous process already upgraded this executable (the
        persistent cache holds the full-effort twin — recorded in the
        upgraded-keys marker), compile at full effort directly: the
        restart then pays ONE cache load instead of a fast-tier load
        PLUS a background recompile that steals GIL/compile-service
        time from the first sweeps."""
        import os
        import time as _time
        from gatekeeper_tpu.utils.compile_cache import is_upgraded, key_hash
        if os.environ.get("GATEKEEPER_NO_FAST_COMPILE") == "1":
            return lowered.compile()
        h = key_hash(marker_key) if marker_key is not None else None
        if upgrade and h is not None and is_upgraded(h):
            try:
                return lowered.compile()
            except Exception:
                pass          # fall through to the two-tier path
        try:
            fast = lowered.compile(compiler_options=dict(self.FAST_OPTS))
        except Exception:
            return lowered.compile()
        if not upgrade:
            # fast-FINAL: gather/compare/reduce mask programs compile
            # ~4x faster at exec_time_optimization_effort=-1 with
            # near-identical generated code (measured round 3) — the
            # full-effort twin buys nothing, and the background
            # recompile it would queue steals GIL/compile-service time
            # from live sweeps.  Only scan/top_k-bearing executables
            # (the shared reduce, sharded top-k twins) need full effort.
            return fast
        with self._lock:
            self._upgrade_q.append((_time.perf_counter(), lowered, install,
                                    h, role))
            if self._upgrade_thread is None or \
                    not self._upgrade_thread.is_alive():
                self._upgrade_thread = self.spawn_bg(
                    self._upgrade_loop, "xla-upgrade")
        return fast

    def _upgrade_loop(self):
        import time as _time
        from gatekeeper_tpu.obs.trace import get_tracer
        from gatekeeper_tpu.utils.compile_cache import mark_upgraded
        while not self._shutdown.is_set():
            with self._lock:
                if not self._upgrade_q:
                    self._upgrade_thread = None
                    return
                # quiesce-based deferral: wait until the whole cold
                # flurry stopped enqueueing, so upgrades never compete
                # with first-serve compiles for the serialized service
                newest = max(e[0] for e in self._upgrade_q)
                _, lowered, install, h, role = self._upgrade_q[0]
            wait = newest + self.UPGRADE_DELAY_S - _time.perf_counter()
            if wait > 0 or self.sweep_active.is_set():
                # never trace/compile under a live sweep — the jit
                # retrace is GIL-bound and measurably slows the sweep's
                # host phases on small hosts
                if self._shutdown.wait(min(max(wait, 0.2), 1.0)):
                    return
                continue
            with self._lock:
                self._upgrade_q.pop(0)
                # visible to quiesce_upgrades: the compile below runs
                # outside the lock and must still count as in-flight
                self._upgrade_busy = getattr(self, "_upgrade_busy", 0) + 1
            try:
                with get_tracer().span("executor.compile", cat="compile",
                                       root=True, role=role, upgrade=True):
                    full = lowered.compile()
                install(full)
                self.upgrades += 1
                if h is not None:
                    mark_upgraded(h)
            except Exception:
                pass   # the fast executable stays in service
            finally:
                with self._lock:
                    self._upgrade_busy -= 1
        with self._lock:
            self._upgrade_thread = None

    def _sharding_of(self, name: str):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from gatekeeper_tpu.ir.prep import binding_axes
        return NamedSharding(self.mesh, P(*binding_axes(name)))

    def _mesh_divides(self, arrays: dict) -> bool:
        """Every c/r-sharded dim must divide by its mesh axis (always
        true for power-of-two buckets >= the mesh axis; tiny toy shapes
        fall back to single-device execution)."""
        from gatekeeper_tpu.ir.prep import binding_axes
        cs, rs = self.mesh.shape["c"], self.mesh.shape["r"]
        for nm, a in arrays.items():
            for d, ax in enumerate(binding_axes(nm)):
                if ax == "c" and a.shape[d] % cs:
                    return False
                if ax == "r" and a.shape[d] % rs:
                    return False
        return True

    def _sharded_for(self, bindings: Bindings) -> bool:
        """Whether this bindings set executes on the mesh (memoized per
        (executor, Bindings) — different executors may carry different
        meshes, e.g. the driver's vs a test's)."""
        d = bindings.__dict__.setdefault("_sharded_by", {})
        hit = d.get(id(self))
        if hit is None:
            hit = d[id(self)] = self.mesh is not None and \
                self._mesh_divides(bindings.arrays)
        return hit

    def set_sharding_allowed(self, bindings: Bindings,
                             allowed: bool) -> None:
        """Pre-seed the per-(executor, Bindings) sharding decision: the
        Stage-6 plan gate.  ``allowed=False`` pins this bindings set to
        the replicated (single-device) path even on a mesh; ``True``
        defers to the usual mesh-divisibility check.  Must run before
        the first ``_sharded_for`` for the pin to take effect."""
        d = bindings.__dict__.setdefault("_sharded_by", {})
        d[id(self)] = bool(allowed) and self.mesh is not None and \
            self._mesh_divides(bindings.arrays)

    def _staged(self, nbytes: int) -> None:
        tl = self._h2d_local
        tl.n = getattr(tl, "n", 0) + nbytes

    def thread_h2d_bytes(self) -> int:
        """Bytes the calling thread has staged to the device so far."""
        return getattr(self._h2d_local, "n", 0)

    def _put(self, name: str, host: np.ndarray, sharded: bool) -> jax.Array:
        if sharded:
            self._note_put(host)
            return jax.device_put(host, self._sharding_of(name))
        import os
        if host.dtype == np.int32 and host.size >= (1 << 16) and \
                os.environ.get("GATEKEEPER_NO_NARROW") != "1":
            # narrow-transfer: id columns usually fit int8/int16 (the
            # interner holds few distinct strings relative to rows);
            # ship the narrow form and widen on device — fewer
            # host->device bytes for the cost of an on-device cast
            lo = int(host.min()) if host.size else 0
            hi = int(host.max()) if host.size else 0
            for dt in (np.int8, np.int16):
                info = np.iinfo(dt)
                if info.min <= lo and hi <= info.max:
                    # stays narrow on device; executables upcast at
                    # entry (_widen_args) so the cast fuses away.  The
                    # executable cache keys on dtype, so a column later
                    # outgrowing the narrow range simply compiles the
                    # int32 twin once.
                    narrow = host.astype(dt)
                    self._note_put(narrow)
                    return jax.device_put(narrow)
        self._note_put(host)
        return jax.device_put(host)

    def _note_put(self, shipped: np.ndarray) -> None:
        self.h2d_bytes += int(shipped.nbytes)
        self._staged(int(shipped.nbytes))

    def _scatter_rows(self, name: str, dev: jax.Array, host: np.ndarray,
                      rows: np.ndarray, sharded: bool,
                      axis: int | None = None) -> jax.Array:
        """Device-side delta: replace `rows` along the resource axis of
        the cached device array (or an explicit `axis` — id-axis for
        interner-indexed append-only tables) with the new host values.
        Ships O(|dirty|) bytes instead of the whole column, so churned
        steady-state sweeps do not re-pay full column uploads."""
        from gatekeeper_tpu.ir.prep import bucket
        ax = _r_axis(name) if axis is None else axis
        # pad the dirty set to a power-of-two bucket (repeat the first
        # row; duplicate scatter of identical values is a no-op) so the
        # scatter kernel compiles once per bucket, not once per sweep
        b = bucket(max(len(rows), 1), minimum=8)
        rows = np.concatenate(
            [rows, np.full((b - len(rows),), rows[0] if len(rows) else 0,
                           dtype=rows.dtype)])
        idx = [slice(None)] * host.ndim
        idx[ax] = rows
        vals = np.ascontiguousarray(host[tuple(idx)])
        if dev.dtype != vals.dtype:
            # narrow-transferred column (_put): scatter narrow when the
            # new values still fit, else re-upload whole (the rare event
            # of the interner outgrowing the narrow range)
            info = np.iinfo(dev.dtype) if np.issubdtype(dev.dtype, np.integer) \
                else None
            if info is not None and len(vals) and \
                    info.min <= vals.min() and vals.max() <= info.max:
                vals = vals.astype(dev.dtype)
            else:
                return self._put(name, host, sharded)
        self.h2d_scatter_bytes += int(vals.nbytes) + int(rows.nbytes)
        self.h2d_scatter_rows += int(len(rows))
        self._staged(int(vals.nbytes) + int(rows.nbytes))
        out = dev.at[tuple(idx)].set(jax.device_put(vals))
        if sharded:
            # scatter output placement follows XLA's choice; pin it back
            # to the canonical named sharding (no-op when already there)
            out = jax.device_put(out, self._sharding_of(name))
        return out

    def _migrate(self, bindings: Bindings, depth: int = 0) -> dict:
        """Per-name device cache for `bindings`, seeded from its delta
        base when present: unchanged arrays keep their device copies,
        r-axis-dirty arrays are scatter-updated on device, and only
        genuinely new arrays are uploaded whole."""
        caches = bindings.__dict__.setdefault("_device_caches", {})
        cache = caches.get(id(self))
        if cache is not None:
            return cache
        # snapshot the lineage once: a concurrent reader may sever the
        # chain (bindings.base = None) while we migrate — racing readers
        # compute identical caches, and setdefault below keeps whichever
        # landed first instead of clobbering a populated cache with an
        # empty one (RWLock contract: reader-side fills must be benign)
        base = bindings.base
        base_dirty = bindings.base_dirty
        append_rows = getattr(bindings, "base_append_rows", None) or {}
        arrays = bindings.arrays
        cache = {}
        if base is not None and depth < 8:
            sharded = self._sharded_for(bindings)
            base_cache = self._migrate(base, depth + 1)
            for name, (href, dev) in base_cache.items():
                cur = arrays.get(name)
                if cur is None:
                    continue
                if cur is href:
                    cache[name] = (href, dev)
                elif cur.shape != dev.shape \
                        or href is not base.arrays.get(name):
                    continue
                elif name in base_dirty:
                    cache[name] = (cur, self._scatter_rows(
                        name, dev, cur, base_dirty[name], sharded))
                elif name in append_rows and len(append_rows[name]):
                    # append-only interner-indexed array: only the
                    # newly interned id rows differ from the device
                    # copy — scatter them along axis 0 instead of
                    # re-uploading the whole (padded) table
                    cache[name] = (cur, self._scatter_rows(
                        name, dev, cur, append_rows[name], sharded,
                        axis=0))
        cache = caches.setdefault(id(self), cache)
        bindings.base = None          # sever the chain; keep memory flat
        bindings.base_dirty = {}
        bindings.base_append_rows = {}
        return cache

    def _arrays(self, bindings: Bindings, match: np.ndarray | None,
                rank: np.ndarray | None = None):
        """Device-resident view of the bindings, memoized per array name
        on the Bindings instance (identity-keyed): steady-state audits
        re-run the executable without re-uploading columns, and
        delta-derived bindings (update_bindings) migrate the previous
        generation's device arrays via on-device row scatter."""
        cache = self._migrate(bindings)
        sharded = self._sharded_for(bindings)
        arrays: dict[str, jax.Array] = {}
        for name, host in bindings.arrays.items():
            hit = cache.get(name)
            if hit is None or hit[0] is not host:
                cache[name] = hit = (host, self._put(name, host, sharded))
            arrays[name] = hit[1]
        if match is not None and "__match__" not in bindings.arrays:
            hit = cache.get("__match__")
            if hit is None or hit[0] is not match:
                padded = np.zeros((bindings.c_pad, bindings.r_pad), dtype=bool)
                padded[: match.shape[0], : match.shape[1]] = match
                cache["__match__"] = hit = (
                    match, self._put("__match__", padded, sharded))
            arrays["__match__"] = hit[1]
        if rank is not None and "__rank__" not in bindings.arrays:
            hit = cache.get("__rank__")
            if hit is None or hit[0] is not rank:
                cache["__rank__"] = hit = (
                    rank, self._put("__rank__", pad_rank(rank, bindings.r_pad),
                                    sharded))
            arrays["__rank__"] = hit[1]
        return arrays

    def stage_uploads(self, bindings: Bindings) -> None:
        """H2D staging as its own pipeline stage: enqueue every binding
        array upload now (device_put is asynchronous — the transfers for
        kind N+1 then overlap kind N's device compute), so the later
        dispatch's _arrays call hits the per-bindings device cache and
        launches against already-resident buffers.  Fresh full-sweep
        bindings double-buffer naturally: each kind owns its own device
        arrays, so staging the next kind never touches the buffers the
        current kind is computing on.  Donation is deliberately NOT used
        even where shapes repeat across kinds: the identity-keyed device
        cache keeps buffers alive across sweeps (the memoized steady
        path depends on that), and a donated buffer would be invalidated
        under the cache's feet."""
        self._arrays(bindings, None, None)

    def eval_mask_delta(self, program: Program, bindings: Bindings,
                        match: np.ndarray | None, old_mask: jax.Array,
                        page_table: jax.Array, k: int,
                        ij_specs: tuple = (),
                        ij_arrays: dict | None = None):
        """Violation mask AND its delta against the previous resident
        mask in ONE jitted call — the devpages sweep kernel.

        Evaluates the program over the bindings' device-resident arrays
        (plus optional in-jit inventory-join columns, computed by
        :func:`_inv_join_mask` from ``ij_arrays`` input records and
        injected under their join binding names), gathers through the
        on-device page table (row -> slot indirection), XORs against
        ``old_mask``, and compacts the changed bits to a fixed-width
        (flat index, sign) stream via ``jnp.nonzero(size=k)``.

        Returns ``(new_mask, idx, signs, count, row_any)``: the new
        mask STAYS ON DEVICE (the caller keeps it resident for the next
        delta), ``idx`` [k] int32 flat indices into [c_pad * r_pad]
        (-1 = fill), ``signs`` [k] bool (True = appeared), ``count``
        the true changed-bit count (> k means the stream overflowed —
        the caller must fall back to a host re-diff), and ``row_any``
        [r_pad] bool = any constraint violates the row (the host
        confirm set for dirty rows) — all but the mask as host numpy.

        H2D here is only what ``_arrays`` stages: unchanged device
        copies are reused, churned rows arrive as row-sized scatter
        records (``_scatter_rows``), so transfer bytes scale with
        churn, never with pages x row width."""
        self.dispatches += 1
        arrays = self._arrays(bindings, match)
        if ij_arrays:
            arrays = {**arrays, **ij_arrays}
        names = tuple(sorted(arrays))
        ij_sig = tuple((nm, bool(ex)) for nm, ex in ij_specs)
        key = ("devdelta", program.cache_key(), k, ij_sig, R_CHUNK,
               tuple((nm,) + tuple(arrays[nm].shape)
                     + (str(arrays[nm].dtype),) for nm in names))
        with self._lock:
            fn = self._cache.get(key)
        if fn is None:
            self._guard_miss(program, arrays, delta_k=k)

            def gk_devdelta(args: tuple, old: jax.Array, pt: jax.Array):
                args = _widen_args(args)
                d = dict(zip(names, args))
                for nm, ex in ij_sig:
                    d[nm] = _inv_join_mask(
                        d[f"r:ij.{nm}.src"], d[f"r:ij.{nm}.inv"],
                        d[f"r:ij.{nm}.sel"], d[f"r:ij.{nm}.names"], ex)
                new = _eval_mask(program, d)
                new = jnp.take(new, pt, axis=1)     # slot indirection
                diff = new ^ old
                flat = diff.ravel()
                idx = jnp.nonzero(flat, size=k, fill_value=-1)[0]
                idx = idx.astype(jnp.int32)
                signs = jnp.take(new.ravel(), jnp.clip(idx, 0, None))
                count = jnp.sum(flat, dtype=jnp.int32)
                return new, idx, signs, count, jnp.any(new, axis=0)
            with self._trace_lock:
                fn = jax.jit(gk_devdelta)
            with self._lock:
                fn = self._cache.setdefault(key, fn)
                self.compiles += 1
            # jit compiles on its first call: that call is the compile
            from gatekeeper_tpu.obs.trace import get_tracer
            compiling = get_tracer().span("executor.compile", cat="compile",
                                          root=True, role="gk_devdelta",
                                          upgrade=False)
        else:
            with self._lock:
                self.cache_hits += 1
            compiling = contextlib.nullcontext()
        args = tuple(arrays[nm] for nm in names)
        with compiling:
            new_mask, idx, signs, count, row_any = fn(args, old_mask,
                                                      page_table)
        return (new_mask, np.asarray(idx), np.asarray(signs),
                int(count), np.asarray(row_any))

    def _guard_miss(self, program, arrays, delta_k: int | None = None):
        """Stage-7 sentinel, called on a jit cache miss before tracing.
        warn mode counts + records (the driver's guard does both) and
        lets the lazy recompile proceed; strict mode refuses the
        dispatch — a signature outside the certificate compiled
        mid-traffic is exactly the retrace storm the CompileSurface
        rules out."""
        guard = self.surface_guard
        if guard is None:
            return
        try:
            ok = guard(program, arrays, delta_k)
        except Exception:   # noqa: BLE001 — the sentinel must never
            return          # take a legitimate dispatch down
        if ok:
            return
        with self._lock:
            self.retrace_uncertified += 1
        from gatekeeper_tpu.analysis import compilesurface as _cs
        if _cs.mode() == "strict":
            shapes = {nm: tuple(int(d) for d in arrays[nm].shape)
                      for nm in sorted(arrays)}
            raise _cs.UncertifiedRetrace(
                f"dispatch signature outside the certified compile "
                f"surface (strict mode refuses the retrace): "
                f"shapes={shapes}, delta_k={delta_k}")

    def _compiled(self, program: Program, arrays: dict, topk: int | None,
                  sharded: bool = False):
        """Callable for (program, shape bucket).  Tracing/lowering is
        pure Python and GIL-bound — running it from the dispatch thread
        pool just thrashes the GIL (measured 4-5x slower than serial) —
        so it is serialized under `_trace_lock`; the XLA compile
        (`lowered.compile()`, C++ — releases the GIL and hits the
        persistent on-disk cache) runs outside it, which is what the
        thread pool actually parallelizes on a cold start.

        With `sharded`, the executable is the shard_map multi-chip twin
        (parallel/sharding.py) over the executor's mesh — same packed
        output shapes, counts/top-k merged across shards via XLA
        collectives (psum / all_gather over ICI)."""
        names = tuple(sorted(arrays))
        mesh_key = tuple(self.mesh.shape.items()) if sharded else None
        key = (program.cache_key(), topk, R_CHUNK, mesh_key,
               tuple((nm,) + tuple(arrays[nm].shape)
                     + (str(arrays[nm].dtype),) for nm in names))
        with self._lock:
            fn = self._cache.get(key)
            if fn is not None:
                self.cache_hits += 1
        if fn is None:
            self._guard_miss(program, arrays)
            # single-flight per key: concurrent misses (dispatch pool)
            # must not compile the same executable twice — the compile
            # service serializes, so a duplicate doubles cold latency
            import threading as _threading
            while fn is None:
                with self._lock:
                    fn = self._cache.get(key)
                    if fn is not None:
                        self.cache_hits += 1
                        return fn, names
                    ev = self._compile_inflight.get(key)
                    if ev is None:
                        ev = _threading.Event()
                        self._compile_inflight[key] = ev
                        break
                ev.wait()
            try:
                fn = self._compile_locked(program, arrays, topk, sharded,
                                          names, key)
            finally:
                with self._lock:
                    self._compile_inflight.pop(key, None)
                ev.set()
        return fn, names

    def _compile_locked(self, program: Program, arrays: dict,
                        topk: int | None, sharded: bool,
                        names: tuple, key: tuple):
        if sharded:
            from jax.sharding import PartitionSpec as P
            from gatekeeper_tpu.ir.prep import binding_axes
            from gatekeeper_tpu.parallel.sharding import (
                make_sharded_mask_fn, make_sharded_topk_packed)
            specs = {nm: P(*binding_axes(nm)) for nm in names}
            r_pad = arrays["__alive__"].shape[0]
            if topk is None:
                raw = make_sharded_mask_fn(program, names, specs,
                                           self.mesh)
            else:
                raw = make_sharded_topk_packed(program, names, specs,
                                               self.mesh, topk, r_pad)
        elif topk is None:
            def gk_mask(args: tuple):
                args = _widen_args(args)
                return _eval_mask(program, dict(zip(names, args)))
            raw = gk_mask
        else:
            def gk_topk(args: tuple):
                args = _widen_args(args)
                counts, rows, scores = _eval_topk(
                    program, dict(zip(names, args)), topk)
                valid = (scores > 0).astype(jnp.int32)
                return jnp.concatenate(
                    [counts[:, None], rows, valid], axis=1)  # [C, 1+2k]
            raw = gk_topk
        example = tuple(
            jax.ShapeDtypeStruct(arrays[nm].shape, arrays[nm].dtype,
                                 sharding=arrays[nm].sharding
                                 if sharded else None)
            for nm in names)
        import time as _time
        from gatekeeper_tpu.obs.trace import get_tracer
        with get_tracer().span("executor.compile", cat="compile", root=True,
                               role=raw.__name__, upgrade=False):
            with self._trace_lock:
                # tracing is GIL-bound; keep it serial (the pool would
                # thrash), while compiles below run concurrently
                _t0 = _time.perf_counter()
                lowered = jax.jit(raw).lower(example)
                self.trace_seconds += _time.perf_counter() - _t0
            _t0 = _time.perf_counter()

            def install(full, _key=key):
                with self._lock:
                    self._cache[_key] = full

            fn = self._compile_two_tier(
                lowered, install, raw.__name__, marker_key=key,
                upgrade=(sharded or topk is not None))
            self.compile_seconds += _time.perf_counter() - _t0
        with self._lock:
            self._cache[key] = fn
            self.compiles += 1
        return fn

    # ------------------------------------------------------------------
    # persistent device violation masks
    #
    # The full [C, R] violation mask of each (program, bindings lineage)
    # lives ON DEVICE across sweeps.  Sweeps over unchanged bindings
    # skip evaluation entirely (reduce-only over the stored mask);
    # churned sweeps evaluate just the dirty-row slice [C, |dirty|(,E)]
    # and scatter it in — device work becomes O(|dirty| + one reduction
    # pass) instead of O(C x R) per sweep.  Sound because every binding
    # value at a row depends only on that row (tables gain entries only
    # for ids introduced by dirty rows), which is the same row-locality
    # update_bindings relies on.  Multi-chip meshes keep the full
    # re-evaluation path (scatter of global dirty indices into sharded
    # arrays does not decompose per-shard with static shapes).

    def prewarm_reduce(self, k: int, c_pad: int, r_pad: int,
                       with_rank: bool = True) -> None:
        """Compile the shared top-k reduce executable for the audit
        shape bucket before any kind's mask is ready — on a cold start
        its (serialized) XLA compile then overlaps host binding prep
        instead of serializing after the last mask evaluation."""
        fn = self._reduce_fn(k, (c_pad, r_pad), (r_pad,) if with_rank
                             else None)
        if isinstance(fn, _LazyTwoTier):
            ex = [jax.ShapeDtypeStruct((c_pad, r_pad), jnp.bool_)]
            if with_rank:
                ex.append(jax.ShapeDtypeStruct((r_pad,), jnp.int32))
            fn.prewarm(*ex)

    def prewarm_audit_exec(self, program: Program, bindings: Bindings,
                           k: int | None = None,
                           with_match: bool = False) -> None:
        """Compile (or reload from the persistent cache) the audit
        executables for `bindings`' shape bucket ahead of the first
        sweep — from a background thread at ingest time, so the
        multi-second compile (or the persistent-cache reload on a warm
        cache) overlaps host work instead of
        serializing inside the first audit."""
        if self.mesh is not None or self._sharded_for(bindings):
            return       # collective twins compile on dispatch
        arrays = dict(bindings.arrays)
        if k is not None and "__rank__" not in arrays:
            # the capped audit always installs a rank gate; mirror the
            # dispatch-time name set or the cache key won't match
            arrays["__rank__"] = np.empty((bindings.r_pad,), np.int32)
        if with_match and "__match__" not in arrays:
            # kinds whose constraints carry match criteria get a
            # "__match__" gate installed at dispatch (_install_gates);
            # without this placeholder the prewarm compiles under a
            # name-set the first sweep never requests — a wasted
            # compile-service round AND the real compile still lands on
            # the cold sweep (round-4 advisor finding)
            arrays["__match__"] = np.empty(
                (bindings.c_pad, bindings.r_pad), np.bool_)
        self._compiled(program, arrays, None, False)
        if k is not None:
            self.prewarm_reduce(k, bindings.c_pad, bindings.r_pad)

    def prewarm_deltas(self, program: Program, bindings: Bindings,
                       buckets: tuple = (8, 1 << 10, 1 << 14)) -> None:
        """Compile the churn-delta executables for a ladder of dirty-row
        buckets ahead of the first churned sweep.  Called from a
        background thread right after a sweep: the compiles hide inside
        the audit interval instead of adding multiple seconds to the
        first sweep after data churn."""
        if self.mesh is not None or self._sharded_for(bindings):
            return
        cache = bindings.__dict__.get("_device_caches", {}).get(id(self))
        if not cache:
            return
        arrays = {nm: dev for nm, (_h, dev) in cache.items()}
        names = tuple(sorted(arrays))
        viol_sd = jax.ShapeDtypeStruct((bindings.c_pad, bindings.r_pad),
                                       jnp.bool_)
        arg_sds = [jax.ShapeDtypeStruct(arrays[nm].shape, arrays[nm].dtype)
                   for nm in names]
        for b in buckets:
            if self._shutdown.is_set():
                return
            fn = self._delta_fn(program, names, b)
            if isinstance(fn, _LazyTwoTier):
                fn.prewarm(viol_sd,
                           jax.ShapeDtypeStruct((b,), jnp.int32), *arg_sds)

    def _viol_key(self, program: Program) -> tuple:
        return (id(self), program.cache_key())

    def _viol_plan(self, program: Program, bindings: Bindings,
                   arrays: dict, base, base_dirty,
                   append_only=frozenset()) -> tuple:
        """('reduce', viol) | ('delta', viol_old, rows) | ('full',).
        `base`/`base_dirty`/`append_only` must be captured BEFORE
        _arrays (migration severs the chain)."""
        key = self._viol_key(program)
        vm = bindings.__dict__.setdefault("_viol_masks", {})
        hit = vm.get(key)
        if hit is not None:
            sig, viol = hit
            if all(arrays.get(nm) is dev for nm, dev in sig.items()) \
                    and len(sig) == len(arrays):
                return ("reduce", viol)
        if base is not None and base_dirty:
            bhit = base.__dict__.get("_viol_masks", {}).get(key)
            if bhit is not None:
                bsig, bviol = bhit
                ok = len(bsig) == len(arrays)
                for nm, dev in arrays.items():
                    if not ok:
                        break
                    if bsig.get(nm) is dev:
                        continue
                    if nm not in base_dirty and nm not in append_only:
                        ok = False      # changed outside the dirty rows
                for nm in base_dirty:
                    if nm not in arrays:
                        ok = False
                if ok:
                    rows = np.unique(np.concatenate(
                        [np.asarray(r) for r in base_dirty.values()])) \
                        if base_dirty else np.zeros((0,), np.int64)
                    return ("delta", bviol, rows)
        return ("full",)

    def _store_viol(self, program: Program, bindings: Bindings,
                    arrays: dict, viol) -> None:
        bindings.__dict__.setdefault("_viol_masks", {})[
            self._viol_key(program)] = (dict(arrays), viol)

    def _reduce_fn(self, k: int, shape, rank_shape):
        """(viol [C, R], rank [R]?) -> packed [C, 1+2k] int32.  Chunked
        over R exactly like _eval_topk — a full-width lax.top_k at
        [C, 1M] blows past v5e scoped VMEM and runs ~10x slower."""
        key = ("reduce", k, shape, rank_shape, R_CHUNK)
        fn = self._cache.get(key)
        if fn is None:
            def pack(counts, rows, scores):
                return jnp.concatenate(
                    [counts[:, None], rows,
                     (scores > 0).astype(jnp.int32)], axis=1)

            def reduce_chunked(viol, rnk):
                c_pad, r_pad = viol.shape
                nc = _n_chunks(r_pad)
                if nc == 1:
                    return pack(*topk_reduce(viol, k, rnk,
                                             return_scores=True))
                rc = r_pad // nc
                k_out = min(k, r_pad)
                k_eff = min(k_out, rc)

                def body(carry, i):
                    off = i * rc
                    v = jax.lax.dynamic_slice_in_dim(viol, off, rc, 1)
                    if rnk is None:
                        rk = off + jnp.arange(rc, dtype=jnp.int32)
                    else:
                        rk = jax.lax.dynamic_slice_in_dim(rnk, off, rc, 0)
                    cnt = jnp.sum(v, axis=1, dtype=jnp.int32)
                    score = jnp.where(v, r_pad - rk[None, :], 0)
                    vals, rows = jax.lax.top_k(score, k_eff)
                    rows = rows + off
                    bs, br, bc = carry
                    ms, mi = jax.lax.top_k(
                        jnp.concatenate([bs, vals], axis=1), k_out)
                    mr = jnp.take_along_axis(
                        jnp.concatenate([br, rows], axis=1), mi, axis=1)
                    return (ms, mr, bc + cnt), None

                init = (jnp.zeros((c_pad, k_out), jnp.int32),
                        jnp.zeros((c_pad, k_out), jnp.int32),
                        jnp.zeros((c_pad,), jnp.int32))
                (vals, rows, counts), _ = jax.lax.scan(
                    body, init, jnp.arange(nc))
                if k_out < k:
                    vals = jnp.pad(vals, ((0, 0), (0, k - k_out)))
                    rows = jnp.pad(rows, ((0, 0), (0, k - k_out)))
                return pack(counts, rows, vals)

            if rank_shape is not None:
                def gk_reduce(viol, rnk):
                    return reduce_chunked(viol, rnk)
            else:
                def gk_reduce(viol):
                    return reduce_chunked(viol, None)
            # exec-critical and shared across kinds: always compile at
            # full effort (prewarm_reduce overlaps it with host prep);
            # a fast-compiled scan/top_k runs several times slower
            fn = _LazyTwoTier(self, gk_reduce, fast=False)
            self._cache[key] = fn
        return fn

    def _delta_fn(self, program: Program, names: tuple, d_bucket: int):
        """(viol_old, dirty [d_bucket], *arrays) -> viol_new: evaluate
        the program on the dirty-row gather of every r-axis array and
        scatter the result into the stored mask."""
        key = ("deltav", program.cache_key(), names, d_bucket)
        fn = self._cache.get(key)
        if fn is None:
            def gk_delta(viol_old, dirty, *args):
                args = _widen_args(args)
                full = dict(zip(names, args))
                sliced = {}
                for nm, a in full.items():
                    ax = _r_axis(nm)
                    if ax is None:
                        sliced[nm] = a
                    else:
                        sliced[nm] = jnp.take(a, dirty, axis=ax)
                sub = _eval_program(program, sliced)      # [C, d_bucket]
                return viol_old.at[:, dirty].set(sub)
            # two-tier WITH upgrade: the dirty-row scatter (at[].set)
            # belongs to the scan/top_k class that executes several
            # times slower at low optimization effort (churn sweep
            # 0.58s -> 3.8s measured when left fast-final)
            fn = _LazyTwoTier(self, gk_delta, name=key)
            self._cache[key] = fn
        return fn

    def _viol_mask_dev(self, program: Program, bindings: Bindings,
                       arrays: dict, base, base_dirty,
                       append_only=frozenset()):
        """Device [C, R] violation mask, maintained incrementally."""
        from gatekeeper_tpu.ir.prep import bucket
        plan = self._viol_plan(program, bindings, arrays, base, base_dirty,
                               append_only)
        names = tuple(sorted(arrays))
        if plan[0] == "reduce":
            return plan[1]
        if plan[0] == "delta":
            _, viol_old, rows = plan
            b = bucket(max(len(rows), 1), minimum=8)
            rows = np.concatenate(
                [rows, np.full((b - len(rows),),
                               rows[0] if len(rows) else 0,
                               dtype=np.int64)]).astype(np.int32)
            # int32 keeps the call signature identical to the
            # prewarm_deltas examples (x64-off device_put would narrow
            # int64 anyway; being explicit keeps the cache warm even if
            # that config changes)
            self._staged(int(rows.nbytes))
            viol = self._delta_fn(program, names, b)(
                viol_old, jax.device_put(rows),
                *(arrays[nm] for nm in names))
        else:
            fn, names = self._compiled(program, arrays, None, False)
            viol = fn(tuple(arrays[nm] for nm in names))
        self._store_viol(program, bindings, arrays, viol)
        return viol

    def run_async(self, program: Program, bindings: Bindings,
                  match: np.ndarray | None = None,
                  rank: np.ndarray | None = None) -> "PendingMask":
        """Dispatch a full-mask evaluation without blocking; .get()
        yields the violation mask trimmed to [n_constraints,
        n_resources].  Like run_topk_async, the host copy starts
        eagerly so per-kind fetch round-trips overlap."""
        self.dispatches += 1
        base, base_dirty = bindings.base, bindings.base_dirty
        append_only = bindings.base_append_only
        arrays = self._arrays(bindings, match, rank)
        if self._sharded_for(bindings):
            fn, names = self._compiled(program, arrays, None, True)
            with self._collective_lock:
                mask = fn(tuple(arrays[nm] for nm in names))
                jax.block_until_ready(mask)
        else:
            mask = self._viol_mask_dev(program, bindings, arrays,
                                       base, base_dirty, append_only)
        try:
            mask.copy_to_host_async()
        except AttributeError:
            pass
        return PendingMask(mask, bindings.n_constraints, bindings.n_resources)

    def run(self, program: Program, bindings: Bindings,
            match: np.ndarray | None = None,
            rank: np.ndarray | None = None) -> np.ndarray:
        """Evaluate; returns the violation mask trimmed to live shape
        [n_constraints, n_resources].  `rank` is unused by the full-mask
        evaluation but participates in the device-array cache key — a
        caller alternating run_topk/run on the same bindings (the capped
        audit's under-fill fallback) must pass the same rank instance to
        keep the single-slot device cache hot."""
        return self.run_async(program, bindings, match, rank).get()

    def run_topk_async(self, program: Program, bindings: Bindings, k: int,
                       match: np.ndarray | None = None,
                       rank: np.ndarray | None = None) -> "PendingTopK":
        """Dispatch evaluate + device top-k without blocking; returns a
        PendingTopK whose .get() yields (counts [C], rows [C, k],
        valid [C, k]) trimmed to the live constraint count.

        The three outputs are packed into ONE [C, 1+2k] int32 array on
        device and the host copy is started eagerly, so one audit sweep
        pays one device-to-host fetch per kind — all overlapping —
        instead of three serialized fetches per kind.

        Single-device, the evaluation rides the persistent violation
        mask (see _viol_mask_dev): unchanged bindings reduce-only,
        churned bindings re-evaluate just the dirty rows."""
        self.dispatches += 1
        base, base_dirty = bindings.base, bindings.base_dirty
        append_only = bindings.base_append_only
        arrays = self._arrays(bindings, match, rank)
        if self._sharded_for(bindings):
            fn, names = self._compiled(program, arrays, k, True)
            with self._collective_lock:
                packed = fn(tuple(arrays[nm] for nm in names))
                jax.block_until_ready(packed)
        else:
            viol = self._viol_mask_dev(program, bindings, arrays,
                                       base, base_dirty, append_only)
            rnk = arrays.get("__rank__")
            rfn = self._reduce_fn(k, tuple(viol.shape),
                                  tuple(rnk.shape) if rnk is not None
                                  else None)
            packed = rfn(viol, rnk) if rnk is not None else rfn(viol)
        try:
            packed.copy_to_host_async()
        except AttributeError:
            pass
        return PendingTopK(packed, bindings.n_constraints, k)

    def run_topk(self, program: Program, bindings: Bindings, k: int,
                 match: np.ndarray | None = None,
                 rank: np.ndarray | None = None):
        """Blocking convenience wrapper around run_topk_async."""
        return self.run_topk_async(program, bindings, k, match, rank).get()
