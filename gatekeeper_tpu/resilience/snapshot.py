"""Warm-restart persistence: lowered IR, dedup plans, store snapshots.

The XLA compilation cache (utils/compile_cache.py) only persists the
*executable* tier, and only on non-cpu backends — which is exactly why
``restart_persistent_cache_hits`` sat at 0: on the cpu platform the
XLA tier is disabled by default (deserialized cpu AOT executables
crash under concurrent dispatch), and the artifacts that dominate cold
start — Rego lowering, IR verification, dedup planning, store
replication — were never persisted at all.  This module persists those
tiers, on every backend, alongside the XLA cache:

- **template IR**: the full ``LoweredProgram`` per template, keyed by
  a digest of (kind, target, Rego source).  A ``None`` payload is a
  negative certificate — the template is known scalar-only
  (CannotLower), so the restarted pod skips the lowering *attempt*
  too.
- **parsed module**: the template's parsed + vetted Rego AST, keyed
  like the IR — a warm client skips parse, hygiene checks, and the
  stage-1 vet (all deterministic in the source, which keys the entry).
- **dedup plan**: the whole-policy-set cross-template predicate dedup
  plan, keyed by the digest of the installed set.
- **store snapshot**: the columnar store's rows + interned string
  table as plain data (``ResourceTable.snapshot_state()``).

Activation is explicit: snapshots read/write only when
``GATEKEEPER_SNAPSHOT_DIR`` is set (the ci restart smoke and the
manager set it; unit tests stay hermetic by default).

**Why a custom pickler.**  A ``LoweredProgram``'s PrepSpec carries
*local* functions (TableReq.fn / PTableReq.fn / CSetReq.fn close over
the Lowerer and AST terms), which stdlib pickle rejects.  The pickler
below serializes such functions as (marshalled code object, defining
module, closure cell contents) and rebuilds them with
``types.FunctionType`` against the live module globals.  Marshalled
code is CPython-bytecode-version specific, so entries are keyed by
``host_fingerprint()`` + the exact Python version + a format version,
every file is length- and sha256-checked, and *any* load failure —
truncation, version skew, unpickle error — deletes the entry and falls
back to a cold rebuild.  Corruption can cost a re-lower; it can never
crash startup or poison a verdict.
"""

from __future__ import annotations

import hashlib
import importlib
import io
import json
import marshal
import os
import pickle
import sys
import threading
import types

from gatekeeper_tpu.utils.log import logger

_log = logger("snapshot")

MAGIC = "gatekeeper-tpu-snapshot"
VERSION = 1


# ----------------------------------------------------------------------
# stats (feeds restart_report and bench restart counters)

class SnapshotStats:
    _FIELDS = ("ir_hits", "ir_misses", "mod_hits", "mod_misses",
               "plan_hits", "plan_misses",
               "store_hits", "store_misses",
               "cert_hits", "cert_misses",
               "fp_hits", "fp_misses",
               "sp_hits", "sp_misses",
               "pg_hits", "pg_misses",
               "dfa_hits", "dfa_misses",
               "ro_hits", "ro_misses",
               "cs_hits", "cs_misses",
               "ms_hits", "ms_misses", "corrupt_discarded",
               "saves", "save_errors")

    def __init__(self):
        self._lock = threading.Lock()
        for f in self._FIELDS:
            setattr(self, f, 0)

    def bump(self, field: str, by: int = 1) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + by)

    def snapshot(self) -> dict:
        with self._lock:
            return {f: getattr(self, f) for f in self._FIELDS}

    def delta_since(self, snap: dict) -> dict:
        cur = self.snapshot()
        return {f: cur[f] - snap.get(f, 0) for f in self._FIELDS}


stats = SnapshotStats()


# ----------------------------------------------------------------------
# closure-aware pickling

def _rebuild_fn(code_b: bytes, module: str, name: str, defaults,
                kwdefaults, cells):
    code = marshal.loads(code_b)
    g = importlib.import_module(module).__dict__
    closure = None
    if cells is not None:
        closure = tuple(types.CellType(v) for v in cells)
    fn = types.FunctionType(code, g, name, defaults, closure)
    if kwdefaults:
        fn.__kwdefaults__ = dict(kwdefaults)
    return fn


def _rebuild_lock(kind: str):
    if kind == "rlock":
        return threading.RLock()
    if kind == "event":
        return threading.Event()
    if kind == "condition":
        return threading.Condition()
    return threading.Lock()


_LOCK_T = type(threading.Lock())
_RLOCK_T = type(threading.RLock())


class _Pickler(pickle.Pickler):
    """stdlib pickle + reducers for the artifacts a LoweredProgram
    actually carries: local functions/lambdas (by marshalled code),
    synchronization primitives (rebuilt fresh), and modules (by
    name)."""

    def reducer_override(self, obj):
        if isinstance(obj, types.FunctionType):
            qual = getattr(obj, "__qualname__", "")
            if "<locals>" in qual or obj.__name__ == "<lambda>":
                cells = None
                if obj.__closure__:
                    # cell_contents raises ValueError on an empty cell
                    # (never observed in lowered IR; a PicklingError
                    # here just skips the save)
                    cells = tuple(c.cell_contents for c in obj.__closure__)
                return (_rebuild_fn,
                        (marshal.dumps(obj.__code__),
                         obj.__module__ or "builtins", obj.__name__,
                         obj.__defaults__, obj.__kwdefaults__, cells))
            return NotImplemented
        if isinstance(obj, _LOCK_T):
            return (_rebuild_lock, ("lock",))
        if isinstance(obj, _RLOCK_T):
            return (_rebuild_lock, ("rlock",))
        if isinstance(obj, threading.Event):
            return (_rebuild_lock, ("event",))
        if isinstance(obj, threading.Condition):
            return (_rebuild_lock, ("condition",))
        if isinstance(obj, types.ModuleType):
            return (importlib.import_module, (obj.__name__,))
        return NotImplemented


def dumps(obj) -> bytes:
    buf = io.BytesIO()
    _Pickler(buf, protocol=4).dump(obj)
    return buf.getvalue()


# ----------------------------------------------------------------------
# the on-disk store

def enabled() -> bool:
    return bool(os.environ.get("GATEKEEPER_SNAPSHOT_DIR"))


def _python_tag() -> str:
    return f"cpython-{sys.version_info[0]}.{sys.version_info[1]}"


def snapshot_dir(create: bool = False, root: str | None = None) -> str | None:
    """Per-(host, python, format-version) subdirectory — marshalled
    code never crosses an interpreter or format boundary.  ``root``
    overrides the env var (historical-snapshot reads, whatif/replay.py)."""
    root = root or os.environ.get("GATEKEEPER_SNAPSHOT_DIR")
    if not root:
        return None
    from gatekeeper_tpu.utils.compile_cache import host_fingerprint
    d = os.path.join(root,
                     f"{host_fingerprint()}-{_python_tag()}-v{VERSION}")
    if create:
        os.makedirs(d, exist_ok=True)
    return d


def _entry_path(category: str, key: str,
                root: str | None = None) -> str | None:
    d = snapshot_dir(root=root)
    if d is None:
        return None
    h = hashlib.sha256(key.encode()).hexdigest()[:24]
    return os.path.join(d, f"{category}-{h}.snap")


def _discard(path: str, why: str) -> None:
    stats.bump("corrupt_discarded")
    _log.warning("discarding snapshot entry; will rebuild",
                 path=os.path.basename(path), why=why)
    try:
        os.remove(path)
    except OSError:
        pass


def _write_entry(category: str, key: str, payload: bytes) -> bool:
    path = _entry_path(category, key)
    if path is None:
        return False
    try:
        snapshot_dir(create=True)
        header = json.dumps({
            "magic": MAGIC, "version": VERSION, "python": _python_tag(),
            "key": key, "sha256": hashlib.sha256(payload).hexdigest(),
            "len": len(payload),
        }).encode()
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(header + b"\n" + payload)
        os.replace(tmp, path)   # atomic: readers see old-or-new, never torn
        stats.bump("saves")
        return True
    except Exception as e:   # noqa: BLE001 — persistence is best-effort
        stats.bump("save_errors")
        _log.warning("snapshot save failed", category=category, error=e)
        return False


def _read_entry(category: str, key: str, root: str | None = None):
    """Returns the unpickled payload in a 1-tuple, or None on miss.
    Any validation or unpickle failure deletes the entry (rebuild on
    the cold path) — corruption must never crash startup."""
    path = _entry_path(category, key, root=root)
    if path is None or not os.path.exists(path):
        return None
    from gatekeeper_tpu.resilience import faults
    if faults.take("snapshot_corrupt"):
        _discard(path, "fault injection: snapshot_corrupt")
        return None
    try:
        with open(path, "rb") as f:
            raw = f.read()
        nl = raw.index(b"\n")
        hdr = json.loads(raw[:nl])
        payload = raw[nl + 1:]
        if hdr.get("magic") != MAGIC:
            _discard(path, "bad magic")
            return None
        if hdr.get("version") != VERSION or hdr.get("python") != _python_tag():
            _discard(path, "version mismatch")
            return None
        if hdr.get("key") != key:
            _discard(path, "key mismatch")
            return None
        if hdr.get("len") != len(payload):
            _discard(path, "truncated")
            return None
        if hdr.get("sha256") != hashlib.sha256(payload).hexdigest():
            _discard(path, "checksum mismatch")
            return None
        return (pickle.loads(payload),)
    except Exception as e:   # noqa: BLE001 — any failure => cold rebuild
        _discard(path, f"load error: {e}")
        return None


# ----------------------------------------------------------------------
# typed entry points

def template_digest(kind: str, target: str, source: str) -> str:
    # GATEKEEPER_DFA changes what lower() emits (dfa_match nodes vs host
    # lookup tables), so IR entries must never cross flag modes — fold
    # the mode into the digest rather than the VERSION so flipping the
    # flag back and forth reuses both snapshot populations.
    from gatekeeper_tpu.ops.regex_dfa import dfa_enabled
    mode = "dfa" if dfa_enabled() else "nodfa"
    h = hashlib.sha256(
        f"{kind}\x00{target}\x00{source}\x00v{VERSION}\x00{mode}".encode())
    return h.hexdigest()[:24]


def load_template_ir(kind: str, target: str, source: str):
    """None = miss.  A 1-tuple hit carries the LoweredProgram, or None
    when the saved outcome was CannotLower (skip the attempt too)."""
    if not enabled():
        return None
    key = f"ir:{template_digest(kind, target, source)}"
    got = _read_entry("ir", key)
    stats.bump("ir_hits" if got is not None else "ir_misses")
    return got


def save_template_ir(kind: str, target: str, source: str, lowered) -> bool:
    if not enabled():
        return False
    key = f"ir:{template_digest(kind, target, source)}"
    try:
        payload = dumps(lowered)
    except Exception as e:   # noqa: BLE001 — an unpicklable program
        stats.bump("save_errors")   # just stays cold-start-only
        _log.warning("lowered IR not snapshottable", kind=kind, error=e)
        return False
    return _write_entry("ir", key, payload)


def load_template_module(kind: str, target: str, source: str):
    """None = miss.  A 1-tuple hit carries ``(Module, uses_inventory)``
    — the parsed + hygiene-checked + vetted AST.  Entries are written
    only after the stage-1 vet passes, so a hit certifies the source as
    vetted; the Interpreter is rebuilt fresh from the Module (its side
    tables are id()-keyed and must never cross a process boundary)."""
    if not enabled():
        return None
    key = f"mod:{template_digest(kind, target, source)}"
    got = _read_entry("mod", key)
    stats.bump("mod_hits" if got is not None else "mod_misses")
    return got


def save_template_module(kind: str, target: str, source: str,
                         module_and_flags) -> bool:
    if not enabled():
        return False
    key = f"mod:{template_digest(kind, target, source)}"
    try:
        payload = dumps(module_and_flags)
    except Exception as e:   # noqa: BLE001
        stats.bump("save_errors")
        _log.warning("parsed module not snapshottable", kind=kind, error=e)
        return False
    return _write_entry("mod", key, payload)


def policyset_digest(parts: list[str]) -> str:
    h = hashlib.sha256()
    for p in sorted(parts):
        h.update(p.encode())
        h.update(b"\x00")
    return h.hexdigest()[:24]


def load_dedup_plan(digest: str):
    if not enabled():
        return None
    got = _read_entry("plan", f"plan:{digest}")
    stats.bump("plan_hits" if got is not None else "plan_misses")
    return got


def save_dedup_plan(digest: str, plan) -> bool:
    if not enabled():
        return False
    try:
        payload = dumps(plan)
    except Exception as e:   # noqa: BLE001
        stats.bump("save_errors")
        _log.warning("dedup plan not snapshottable", error=e)
        return False
    return _write_entry("plan", f"plan:{digest}", payload)


def load_cert(digest: str):
    """Fifth tier: translation-validation certificates, keyed by the
    transval certificate digest (program cache_key + constraint docs +
    budget + validator version).  A warm restart that reuses the
    snapshotted lowered IR also reuses its certificate, so it re-runs
    zero validations (analysis/transval.certify)."""
    if not enabled():
        return None
    got = _read_entry("cert", f"cert:{digest}")
    stats.bump("cert_hits" if got is not None else "cert_misses")
    return got


def save_cert(digest: str, cert) -> bool:
    if not enabled():
        return False
    try:
        payload = dumps(cert)
    except Exception as e:   # noqa: BLE001
        stats.bump("save_errors")
        _log.warning("certificate not snapshottable", error=e)
        return False
    return _write_entry("cert", f"cert:{digest}", payload)


def load_footprint(digest: str):
    """Sixth tier: Stage-5 dependency footprints, keyed by the
    footprint digest (program cache_key + prep-spec signature +
    analyzer version).  A warm restart that reuses the snapshotted
    lowered IR also reuses its footprint, so it re-runs zero
    dependency analyses (analysis/footprint.certify)."""
    if not enabled():
        return None
    got = _read_entry("fp", f"fp:{digest}")
    stats.bump("fp_hits" if got is not None else "fp_misses")
    return got


def save_footprint(digest: str, fp) -> bool:
    if not enabled():
        return False
    try:
        payload = dumps(fp)
    except Exception as e:   # noqa: BLE001
        stats.bump("save_errors")
        _log.warning("footprint not snapshottable", error=e)
        return False
    return _write_entry("fp", f"fp:{digest}", payload)


def load_shardplan(digest: str):
    """Seventh tier: Stage-6 partition plans, keyed by the shardplan
    digest (program cache_key + prep-spec signature + analyzer
    version).  A warm restart that reuses the snapshotted lowered IR
    also reuses its partition plan, so it re-runs zero sharding
    analyses (analysis/shardplan.certify)."""
    if not enabled():
        return None
    got = _read_entry("sp", f"sp:{digest}")
    stats.bump("sp_hits" if got is not None else "sp_misses")
    return got


def save_shardplan(digest: str, plan) -> bool:
    if not enabled():
        return False
    try:
        payload = dumps(plan)
    except Exception as e:   # noqa: BLE001
        stats.bump("save_errors")
        _log.warning("shardplan not snapshottable", error=e)
        return False
    return _write_entry("sp", f"sp:{digest}", payload)


def load_compilesurface(digest: str):
    """Tenth tier: Stage-7 compile-surface certificates
    (analysis/compilesurface.py), keyed by program cache_key +
    pad-geometry version + ladder caps — plus the AOT-precompile
    geometry stamps JaxDriver.precompile writes under ``aot:`` keys.
    A warm restart reuses both: zero surface analyses AND zero AOT
    executable compiles at startup (smoke's ``compile_surfaces`` /
    ``aot_precompiles`` == 0 warm)."""
    if not enabled():
        return None
    got = _read_entry("cs", f"cs:{digest}")
    stats.bump("cs_hits" if got is not None else "cs_misses")
    return got


def save_compilesurface(digest: str, cert) -> bool:
    if not enabled():
        return False
    try:
        payload = dumps(cert)
    except Exception as e:   # noqa: BLE001
        stats.bump("save_errors")
        _log.warning("compile surface not snapshottable", error=e)
        return False
    return _write_entry("cs", f"cs:{digest}", payload)


def load_memsurface(digest: str):
    """Eleventh tier: Stage-8 memory-surface certificates
    (analysis/memsurface.py), keyed by program cache_key +
    pad-geometry version + MS deployment caps.  A warm restart
    re-runs zero peak-HBM analyses (smoke's ``memsurfaces`` == 0
    warm); a caps or geometry change invalidates by key mismatch."""
    if not enabled():
        return None
    got = _read_entry("ms", f"ms:{digest}")
    stats.bump("ms_hits" if got is not None else "ms_misses")
    return got


def save_memsurface(digest: str, cert) -> bool:
    if not enabled():
        return False
    try:
        payload = dumps(cert)
    except Exception as e:   # noqa: BLE001
        stats.bump("save_errors")
        _log.warning("memory surface not snapshottable", error=e)
        return False
    return _write_entry("ms", f"ms:{digest}", payload)


def load_dfa(digest: str):
    """Eighth tier: compiled regex byte-DFA tables (ops/regex_dfa),
    keyed by the pattern + DFA_VERSION digest.  A warm restart that
    reuses the snapshotted lowered IR also reuses its DFA tables, so
    it compiles zero automata (smoke's ``dfa_compiles`` == 0 warm).
    A hit may carry None — a negative certificate for a pattern known
    to fall outside the supported subset (skip the compile attempt)."""
    if not enabled():
        return None
    got = _read_entry("dfa", f"dfa:{digest}")
    stats.bump("dfa_hits" if got is not None else "dfa_misses")
    return got


def save_dfa(digest: str, dfa) -> bool:
    if not enabled():
        return False
    try:
        payload = dumps(dfa)
    except Exception as e:   # noqa: BLE001
        stats.bump("save_errors")
        _log.warning("dfa table not snapshottable", error=e)
        return False
    return _write_entry("dfa", f"dfa:{digest}", payload)


def load_store(target: str, root: str | None = None):
    """Load the store tier.  With ``root``, read from that snapshot
    root explicitly (a *historical* snapshot directory, independent of
    GATEKEEPER_SNAPSHOT_DIR) — the replay path's time machine."""
    if root is None and not enabled():
        return None
    got = _read_entry("store", f"store:{target}", root=root)
    stats.bump("store_hits" if got is not None else "store_misses")
    return got


def save_store(target: str, state) -> bool:
    if not enabled():
        return False
    try:
        payload = dumps(state)
    except Exception as e:   # noqa: BLE001
        stats.bump("save_errors")
        _log.warning("store snapshot failed to serialize", error=e)
        return False
    return _write_entry("store", f"store:{target}", payload)


def load_pagemap(target: str, root: str | None = None):
    """Load the pagemap tier: the VerdictLedger's per-kind confirmed
    violation sets, saved alongside the store tier so a warm restart
    adopts its verdicts (revalidated per kind by constraint digest +
    row count) instead of paying a cold full build."""
    if root is None and not enabled():
        return None
    got = _read_entry("pg", f"pg:{target}", root=root)
    stats.bump("pg_hits" if got is not None else "pg_misses")
    return got


def save_pagemap(target: str, payload_obj) -> bool:
    if not enabled():
        return False
    try:
        payload = dumps(payload_obj)
    except Exception as e:   # noqa: BLE001
        stats.bump("save_errors")
        _log.warning("pagemap snapshot failed to serialize", error=e)
        return False
    return _write_entry("pg", f"pg:{target}", payload)


def load_rollout(name: str, root: str | None = None):
    """Ninth tier: promotion-rollout state (rollout/controller.py),
    keyed by rollout name.  A warm restart resumes an in-flight
    promotion at the same rung — state machine position, installed
    enforcement rung, and the prior-doc set a rollback would restore."""
    if root is None and not enabled():
        return None
    got = _read_entry("ro", f"ro:{name}", root=root)
    stats.bump("ro_hits" if got is not None else "ro_misses")
    return got


def save_rollout(name: str, payload_obj) -> bool:
    if not enabled():
        return False
    try:
        payload = dumps(payload_obj)
    except Exception as e:   # noqa: BLE001
        stats.bump("save_errors")
        _log.warning("rollout state not snapshottable", error=e)
        return False
    return _write_entry("ro", f"ro:{name}", payload)


# ----------------------------------------------------------------------
# the combined restart counter (the keying-bug fix)

def tier_counts(s: dict) -> tuple[int, int]:
    """(hits, misses) summed across every snapshot tier of a stats dict
    (works on both ``stats.snapshot()`` absolutes and ``delta_since``
    deltas)."""
    hits = (s["ir_hits"] + s["mod_hits"] + s["plan_hits"]
            + s["store_hits"] + s.get("cert_hits", 0)
            + s.get("fp_hits", 0) + s.get("sp_hits", 0)
            + s.get("pg_hits", 0) + s.get("dfa_hits", 0)
            + s.get("ro_hits", 0) + s.get("cs_hits", 0)
            + s.get("ms_hits", 0))
    misses = (s["ir_misses"] + s["mod_misses"] + s["plan_misses"]
              + s["store_misses"] + s.get("cert_misses", 0)
              + s.get("fp_misses", 0) + s.get("sp_misses", 0)
              + s.get("pg_misses", 0) + s.get("dfa_misses", 0)
              + s.get("ro_misses", 0) + s.get("cs_misses", 0)
              + s.get("ms_misses", 0))
    return hits, misses


def restart_report() -> dict:
    """One number that actually reflects warm-restart reuse.

    The old bench counter read only the XLA event listener — on the
    cpu platform that tier is off by default, so the counter was
    structurally 0.  The fixed counter sums every persistence tier:
    XLA executable hits (when that tier is on) + lowered-IR hits +
    dedup-plan hits + store-snapshot hits.
    """
    from gatekeeper_tpu.utils.compile_cache import persistent_cache_stats
    x = persistent_cache_stats().snapshot()
    s = stats.snapshot()
    t_hits, t_misses = tier_counts(s)
    hits = x.get("hits", 0) + t_hits
    misses = x.get("misses", 0) + t_misses
    return {
        "restart_persistent_cache_hits": hits,
        "restart_persistent_cache_misses": misses,
        "xla": x,
        "snapshot": s,
    }
