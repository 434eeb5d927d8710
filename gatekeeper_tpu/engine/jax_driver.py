"""The ``jax`` driver: vectorized device engine with scalar fallback.

This plugs the TPU pipeline into the same Driver seam the reference
exposes for engines (vendor/.../drivers/interface.go:21-33 — the local
OPA driver and the remote HTTP driver are the two reference
implementations; this is the third kind the seam was designed for).

Audit dataflow (replacing the single-threaded topdown cross-product,
reference client.go:584-607 + regolib/src.go:38-52):

  1. per template kind: lowered program + bindings (columns, host
     tables, per-constraint tensors) — cached by (table generation,
     constraint-set version), so steady-state audits re-run only the
     jitted executable;
  2. device: violation mask [n_constraints, n_resources], ANDed with
     the vectorized match mask (engine/match.py);
  3. host: only the violating pairs are re-evaluated with the scalar
     oracle to produce exact messages/details (the device mask may
     over-approximate; over-approximated pairs simply format to
     nothing).  With a per-constraint limit (the audit manager's cap,
     reference manager.go:35) the host formats at most
     limit x n_constraints pairs regardless of inventory size.

Templates outside the lowerable subset run on the scalar oracle
restricted to match-mask candidates — same results, no silent behavior
split (SURVEY §7 hard-part 6).  data.inventory joins in the
duplicate-detection shape DO lower (ir/lower.py `_try_inventory_join`);
the per-template bucket is pinned in library/lowering_buckets.json.

The review path delegates to the scalar engine: single-review latency
is interpreter-bound and the reference's semantics (autoreject,
matching, tracing) are already exact there.  Micro-batched admission
rides the audit kernels via webhook batching (pkg webhook).
"""

from __future__ import annotations

import bisect
import dataclasses
import os
from typing import Any

import numpy as np

from gatekeeper_tpu.api.templates import CompiledTemplate
from gatekeeper_tpu.client.interface import QueryOpts
from gatekeeper_tpu.client.local_driver import (LocalDriver, TargetState,
                                                locked, locked_read)
from gatekeeper_tpu.client.types import Result, enforcement_action_of
from gatekeeper_tpu.engine.veval import ProgramExecutor
from gatekeeper_tpu.errors import ExternalDataError
from gatekeeper_tpu.ir.lower import CannotLower, lower_template
from gatekeeper_tpu.ir.prep import build_bindings
from gatekeeper_tpu.rego.values import freeze
from gatekeeper_tpu.utils.metrics import Metrics


class _TrivialMatch:
    """Sentinel mask: every alive row matches every constraint (no
    spec.match anywhere).  Indexable like the real mask so host-side
    candidate checks stay uniform."""

    def __getitem__(self, _idx):
        return True

    def __bool__(self):
        return True


TRIVIAL_MATCH = _TrivialMatch()


class _RowOrder:
    """Dict-like row -> sorted-key position, backed by an inverse
    permutation array (building a 1M-entry Python dict is a measurable
    cold-start tax; formatting only ever probes a capped handful)."""

    __slots__ = ("_pos", "_n")

    def __init__(self, ordered_rows: np.ndarray):
        n = int(ordered_rows.max()) + 1 if len(ordered_rows) else 0
        self._pos = np.full((n,), -1, dtype=np.int64)
        self._pos[ordered_rows] = np.arange(len(ordered_rows), dtype=np.int64)
        self._n = len(ordered_rows)

    def __len__(self) -> int:
        return self._n

    def __contains__(self, row) -> bool:
        return 0 <= row < len(self._pos) and self._pos[row] >= 0

    def __getitem__(self, row) -> int:
        p = self._pos[row] if 0 <= row < len(self._pos) else -1
        if p < 0:
            raise KeyError(row)
        return int(p)


@dataclasses.dataclass
class _OrderCache:
    """The audit row order of one table at one (remap_generation,
    key_generation).  ``keys`` holds the sorted keys (a list of the
    table's own strings) and ``rows`` their rows, for the next merge;
    ``merged`` counts the changed keys merged in to build it (0 for a
    full sort)."""
    table: Any
    remap: int
    kgen: int
    ordered_rows: list
    row_order: _RowOrder
    keys: list
    rows: np.ndarray
    merged: int = 0

    def holds(self, table) -> bool:
        return (self.table is table
                and self.remap == table.remap_generation
                and self.kgen == table.key_generation)


def _merge_sorted_keys(keys: list, ordered_rows: list, rows: np.ndarray,
                       changed, lookup) -> tuple[list, list, np.ndarray]:
    """``keys`` (sorted), ``ordered_rows`` and ``rows`` with the
    ``changed`` keys applied: each is dropped where ``keys`` holds it
    and inserted with its current row where ``lookup`` finds it now, so
    a delete-then-recreate, a reused free row and a key created and
    deleted in between all come out as a fresh sort would give them.
    One splice of each sequence, whatever the number of keys."""
    k_out: list = []
    o_out: list = []
    r_out: list = []
    prev = 0
    for k in sorted(changed):
        i = bisect.bisect_left(keys, k)
        row = lookup(k)
        held = i < len(keys) and keys[i] == k
        if not held and row is None:
            continue
        k_out += keys[prev:i]
        o_out += ordered_rows[prev:i]
        r_out.append(rows[prev:i])
        prev = i + 1 if held else i
        if row is not None:
            k_out.append(k)
            o_out.append(row)
            r_out.append(np.array([row], dtype=np.int64))
    k_out += keys[prev:]
    o_out += ordered_rows[prev:]
    r_out.append(rows[prev:])
    return k_out, o_out, np.concatenate(r_out)


SMALL_WORKLOAD_EVALS = 20_000
"""Below this many (resource, constraint) pairs per kind, the kind runs
on the scalar engine: a device dispatch plus fetch has a fixed cost that
only amortizes over enough work.  The scalar path produces identical
results (it is the oracle), so routing is purely a latency decision.
The value predates any local-chip measurement (PERF.md, open
questions)."""

MIRROR_EAGER_MIN_ROWS = 50_000
"""Batch ingests at/above this row count eagerly materialize the
columnar mirror (element axes + per-kind bindings) and kick background
executable prewarms — the first audit after a restart then spends its
wall on dispatch + fetch, not on prep the store could have done at
write time (deliberately NOT test-overridable via
SMALL_WORKLOAD_EVALS: tiny test ingests must stay cheap)."""

def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


REVIEW_BATCH_MIN_EVALS = _env_int(
    "GATEKEEPER_REVIEW_BATCH_MIN_EVALS", 200_000)
"""Below this many (review, constraint) pairs, a coalesced admission
batch stays on the scalar engine.

The value has not been measured on a chip: with the webhook's default
--max-batch 64 (and 200 constraints = 12.8k evals) admission never
routes to the device.  The planned benchmark cell of device review
batches at the large-cluster inventory (ROADMAP Queue 2 item 9) is the
measurement to derive it from; GATEKEEPER_REVIEW_BATCH_MIN_EVALS moves
it until then.  See README "Device-batched admission"."""

DEFAULT_PREWARM_CAP = 20
"""Cap assumed for prewarmed audit executables — the audit manager's
per-constraint violation cap (reference pkg/audit/manager.go:35)."""

class _ResolvedHandle:
    """Pre-resolved stand-in for a PendingTopK/PendingMask handle:
    selective invalidation replays a payload captured on a previous
    sweep instead of dispatching, and the format path only ever calls
    ``.get()``/``.block()``."""

    def __init__(self, payload):
        self._payload = payload

    def get(self):
        return self._payload

    def block(self):
        return self


SWEEP_CACHE_MAX_BYTES = 64 * 1024 * 1024
"""Per-kind payload cap for the selective-invalidation sweep cache —
top-k payloads are tiny; uncapped [C, R] masks at cluster scale are
not worth holding for a maybe-reuse."""


class JaxTargetState(TargetState):
    def __init__(self):
        super().__init__()
        self.con_version: dict[str, int] = {}      # kind -> bump on change
        self.bindings_cache: dict[str, tuple] = {}  # kind -> (cache key, b)
        self.bindings_retired: dict[str, tuple] = {}  # kind -> (ver, old b)
        self.mask_cache: dict[str, tuple] = {}
        # kind -> the padded mask currently installed as a bindings
        # __match__ array: that buffer may still be referenced by host
        # formatting and by the device cache, so the mask ping-pong must
        # never overwrite it in place
        self.installed_match: dict[str, object] = {}
        self.rank_cache: tuple | None = None       # (generation, rank arr)
        self.order_cache: _OrderCache | None = None
        self.fmt_cache: dict[str, tuple] = {}      # kind -> (con_ver, {(cname,row): (ver, results)})
        self.match_engine = None
        # kind -> Stage-5 dependency footprint (analysis/footprint.py)
        self.footprints: dict[str, object] = {}
        # kind -> Stage-6 partition plan (analysis/shardplan.py)
        self.shardplans: dict[str, object] = {}
        # kind -> Stage-7 compile-surface certificate
        # (analysis/compilesurface.py)
        self.compilesurfaces: dict[str, object] = {}
        # kind -> Stage-8 memory-surface certificate
        # (analysis/memsurface.py)
        self.memsurfaces: dict[str, object] = {}
        # kind -> last device sweep payload + guards, for
        # footprint-driven selective invalidation (_selective_reuse)
        self.sweep_cache: dict[str, dict] = {}
        # continuous enforcement (enforce/ledger.py): the target's
        # VerdictLedger (created on the first paged sweep), a restored
        # pagemap snapshot awaiting per-kind adoption, and the overflow
        # counter watermark already exported to metrics
        self.ledger = None
        self.ledger_restored: dict | None = None
        self.dirtylog_overflows_seen = 0
        # device-resident paged store (enforce/devpages.py): per-kind
        # KindPages (resident mask + page table + inv-join inputs),
        # and snapshot geometry awaiting adoption on warm restart
        self.devpages: dict[str, object] = {}
        self.devpages_geom: dict | None = None
        # dedup shared-conjunct columns carried across full sweeps:
        # digest -> (gen, remap, shape, col) — churn re-evaluates only
        # the dirty-row slice (policyset.eval_shared_host rows=...)
        self.dedup_shared_cache: dict = {}
        # kind -> why its template has no lowered program (CannotLower
        # message or IR-verifier refusal); reported per sweep in the
        # "dispatch" stanza
        self.scalar_pins: dict[str, str] = {}

    def bump(self, kind: str) -> None:
        self.con_version[kind] = self.con_version.get(kind, 0) + 1


class JaxDriver(LocalDriver):
    """Driver with device-evaluated audit; construction mirrors
    local.New (drivers/local/local.go:28) with tracing default."""

    def __init__(self, tracing: bool = False):
        super().__init__(tracing=tracing)
        mesh = None
        self.metrics = Metrics()
        # bounded bring-up (utils/device_probe): a backend that errors
        # OR hangs must not block construction — the reference's driver
        # always constructs (drivers/local/local.go:28-48).  A failed
        # probe is an error, logged and counted (device_probe_failures):
        # scalar_only (a property over the backend supervisor) then
        # routes every evaluation through the scalar oracle, which never
        # touches jax.  Each dispatch re-consults the supervisor, and a
        # recovery re-jits through _on_backend_recovered.
        from gatekeeper_tpu.utils.device_probe import probe_devices
        from gatekeeper_tpu.resilience.supervisor import get_supervisor
        res = probe_devices()
        self.supervisor = get_supervisor()
        if not res.ok:
            from gatekeeper_tpu.utils.log import logger
            logger("engine").error(
                "device backend unavailable; scalar-only engine",
                reason=res.reason)
            self.metrics.counter("device_probe_failures").inc()
        else:
            # GATEKEEPER_SHARDS selects the mesh: 0/unset keeps the
            # legacy all-device mesh when multiple devices exist; 1
            # forces the unsharded oracle (no mesh even multi-device);
            # N >= 2 builds the Stage-6 row-only simulated mesh the
            # partition plans are certified against.
            n_shards = _env_int("GATEKEEPER_SHARDS", 0)
            if n_shards >= 2:
                from gatekeeper_tpu.parallel.sharding import make_sim_mesh
                mesh = make_sim_mesh(n_shards)
            elif n_shards == 0 and res.n_devices > 1:
                from gatekeeper_tpu.parallel.sharding import make_mesh
                mesh = make_mesh()      # a real failure here should raise
        self.supervisor.add_recovery_listener(self, "_on_backend_recovered")
        self.executor = ProgramExecutor(mesh=mesh)
        # Stage-7 retrace sentinel: consulted by the executor ONLY on a
        # jit cache miss; a signature outside the installed
        # CompileSurface certificate is counted + flight-recorded here
        # (strict-mode refusal happens at the executor seam)
        self.executor.surface_guard = self._surface_guard
        # serializes reader-side cache fills (bindings/mask delta prep):
        # racing audit readers would otherwise interleave interner
        # appends and column/cache mutations across different kinds —
        # NOT the identical computation the RWLock benign-race argument
        # assumes.  Execution and host formatting stay concurrent.
        import threading as _threading
        self._prep_lock = _threading.Lock()
        # predict_review_batch_seconds memo: (n, #templates, #cons) ->
        # summed cost units (scale applied fresh each call)
        self._predict_cache: dict[tuple, float] = {}
        # one-shot background churn-delta prewarm after the first sweep
        # (shape changes later recompile lazily on the sweep, as before)
        self._delta_warmed = False
        # cross-template dedup plan memo: target -> (policyset digest,
        # plan).  The digest is a pure function of the installed set, so
        # template/constraint churn invalidates by key mismatch — no
        # staleness window.  prepare_audit fills it at startup; the
        # sweep consults it before building.
        self._dedup_plan_memo: dict = {}
        # per-phase breakdown of the most recent audit sweep (the audit
        # manager copies host_prep_s/h2d_s/device_s/overlap_fraction
        # into its sweep report; phase timings are only measured on
        # forced-full sweeps — {"full": False} otherwise)
        self.last_sweep_phases: dict = {}

    # ------------------------------------------------------------------

    @property
    def scalar_only(self) -> bool:
        """Is the device path unavailable *right now*?  A property, not
        a construction-time bool: serving paths re-consult the backend
        supervisor per dispatch, so a mid-sweep degradation routes the
        remaining kinds through the scalar oracle and a recovery routes
        later sweeps back onto the device."""
        return not self.supervisor.use_device()

    def _on_backend_recovered(self) -> None:
        """Recovery listener: compiled executables (and uploaded
        buffers, via the bindings they hang off) may reference the dead
        backend's client — drop them so the next dispatch re-jits onto
        the recovered backend.  The XLA persistent cache and the warm
        IR snapshots make that re-jit cheap."""
        try:
            with self._prep_lock:
                self.executor.reset_for_recovery()
                for st in self.state.values():
                    st.bindings_cache.clear()
                    st.bindings_retired.clear()
                    st.mask_cache.clear()
                    st.installed_match.clear()
                    st.rank_cache = None
                    st.order_cache = None
                self._dedup_plan_memo.clear()
            self.metrics.counter("backend_rejits").inc()
        except Exception as e:   # noqa: BLE001 — recovery cleanup must
            from gatekeeper_tpu.utils.log import logger   # never throw
            logger("engine").warning("post-recovery re-jit reset failed",
                                     error=e)

    def init(self, targets) -> None:
        self.targets = dict(targets)
        for name in targets:
            self.state.setdefault(name, JaxTargetState())

    @locked
    def save_store_snapshot(self, target: str) -> bool:
        """Persist the target's columnar store (rows + interned string
        table) for warm restart.  No-op unless GATEKEEPER_SNAPSHOT_DIR
        is set."""
        from gatekeeper_tpu.resilience import snapshot as _snap
        if not _snap.enabled():
            return False
        st = self._state(target)
        ok = _snap.save_store(target, st.table.snapshot_state())
        if ok and isinstance(st, JaxTargetState):
            from gatekeeper_tpu.enforce.ledger import pages_mode as _pg
            if st.ledger is not None and st.ledger.entries:
                # companion pagemap tier: the ledger's confirmed
                # verdicts ride the same snapshot so a warm restart
                # adopts them (per kind, revalidated by constraint
                # digest + row count) instead of paying a cold build.
                # Each kind is stamped with the watch RV watermark the
                # verdicts were built at: the reactor forces one kind
                # resync if its first observed event does not extend it
                payload = st.ledger.snapshot_payload()
                wm = st.table.rv_watermark()
                # ledger entries are keyed by constraint kind while the
                # store watermark is keyed by resource kind, so each
                # entry gets the global epoch (RVs are cluster-global)
                # and the per-resource-kind map rides along under a
                # reserved key for the reactor's per-stream floors
                wm_max = max(wm.values(), default=0)
                for kind, p in payload.items():
                    p["rv"] = max(int(p.get("rv", 0) or 0), wm_max)
                    # device-pagemap geometry rides the pg tier: a warm
                    # restart adopting the verdicts also adopts the
                    # paged layout (slot capacity, page shape, free
                    # list) so the first device sweep rebuilds nothing
                    kp = st.devpages.get(kind)
                    if kp is not None and getattr(kp, "slots", 0):
                        p["devpages"] = kp.geometry()
                if wm:
                    payload["__rv__"] = dict(wm)
                _snap.save_pagemap(target, payload)
            elif _pg():
                # pages-on deployment snapshotted before the first
                # sweep built a ledger: persist an empty pagemap so the
                # companion-tier restore is a hit with zero adoptions,
                # not a spurious tier miss
                _snap.save_pagemap(target, {})
        return ok

    @locked
    def restore_store_snapshot(self, target: str) -> bool:
        """Warm restart: rebuild the target's columnar store from the
        on-disk snapshot instead of replaying the full inventory.
        Only valid on a fresh (empty) store; returns False on miss,
        disabled persistence, or a non-empty table."""
        from gatekeeper_tpu.resilience import snapshot as _snap
        st = self._state(target)
        if len(st.table) > 0:
            return False
        hit = _snap.load_store(target)
        if hit is None:
            return False
        st.table.restore_state(hit[0])
        if isinstance(st, JaxTargetState):
            from gatekeeper_tpu.enforce.ledger import pages_mode as _pg
            # the pagemap tier only exists for paged deployments — with
            # pages off the ledger is never consulted, so don't charge
            # a tier miss against the warm-restart counters
            if _pg():
                hitpg = _snap.load_pagemap(target)
                st.ledger_restored = hitpg[0] if hitpg is not None \
                    else None
        return True

    @locked
    def adopt_store(self, target: str, state: dict) -> None:
        """Swap the target's columnar store for a fresh table built
        from a ``snapshot_state()`` payload — the
        load-snapshot-as-secondary-store path (whatif/replay.py).
        Unlike restore_store_snapshot this is valid on a non-empty
        driver: every table-derived cache layer is dropped, because the
        new table's generation counters restart and would otherwise
        collide with cached keys from the old table."""
        from gatekeeper_tpu.store.table import ResourceTable
        st = self._state(target)
        st.table = ResourceTable.from_state(state)
        st._inv_cache = None
        if isinstance(st, JaxTargetState):
            st.bindings_cache = {}
            st.bindings_retired = {}
            st.mask_cache = {}
            st.installed_match = {}
            st.rank_cache = None
            st.order_cache = None
            st.fmt_cache = {}
            st.match_engine = None
            st.sweep_cache = {}
            # the ledger's row ids and generation guards are meaningless
            # against the swapped table (its counters restart)
            st.ledger = None
            st.ledger_restored = None
            st.dirtylog_overflows_seen = 0
            for kind in list(st.templates):
                st.bump(kind)

    @locked
    def put_template(self, target: str, kind: str, compiled: CompiledTemplate) -> None:
        pin = None
        if compiled.vectorized is None:
            from gatekeeper_tpu.resilience import snapshot as _snap
            hit = _snap.load_template_ir(kind, target, compiled.source)
            if hit is not None:
                # warm restart: lowering AND stage-2 verification are
                # skipped — the snapshot stores the verified outcome
                # (possibly None: a known-scalar-only certificate)
                compiled.vectorized = hit[0]
                pin = "scalar pin (snapshot)"
                self.metrics.counter("template_ir_snapshot_hits").inc()
            else:
                try:
                    compiled.vectorized = lower_template(
                        compiled.module, compiled.interp)
                except CannotLower as e:
                    compiled.vectorized = None  # scalar fallback
                    pin = f"CannotLower: {e}"
                if compiled.vectorized is not None:
                    compiled.vectorized = self._verify_lowered(
                        kind, compiled.vectorized)
                    if compiled.vectorized is None:
                        pin = "IR verification failed"
                _snap.save_template_ir(kind, target, compiled.source,
                                       compiled.vectorized)
            # stage 4 runs on BOTH paths: the cert snapshot tier (not
            # the IR tier) is what makes the warm restart skip it
            if compiled.vectorized is not None:
                compiled.vectorized = self._certify_lowered(kind, compiled)
        st = self._state(target)
        # stage 5 (dependency footprint) also runs on both paths — the
        # fp snapshot tier keeps warm restarts at zero re-analyses
        if isinstance(st, JaxTargetState):
            if compiled.vectorized is None:
                st.scalar_pins[kind] = pin or "not lowered"
            else:
                st.scalar_pins.pop(kind, None)
            fp = None
            if compiled.vectorized is not None:
                fp = self._footprint_lowered(kind, compiled)
            if fp is not None:
                st.footprints[kind] = fp
            else:
                st.footprints.pop(kind, None)
            # stage 6 (partition plan): certifies HOW the lowered
            # program shards along the resource axis; the sp snapshot
            # tier keeps warm restarts at zero re-analyses
            sp = None
            if compiled.vectorized is not None:
                sp = self._shardplan_lowered(kind, compiled)
            if sp is not None:
                st.shardplans[kind] = sp
            else:
                st.shardplans.pop(kind, None)
            # stage 7 (compile surface): certifies the finite signature
            # set the jitted programs can be entered with; the cs
            # snapshot tier keeps warm restarts at zero re-analyses.
            # Scalar pins get the trivial empty-surface certificate.
            cs_cert = self._compilesurface_lowered(kind, compiled)
            if cs_cert is not None:
                st.compilesurfaces[kind] = cs_cert
            else:
                st.compilesurfaces.pop(kind, None)
            # stage 8 (memory surface): certifies the conservative
            # peak-HBM bytes of every certified signature; the ms
            # snapshot tier keeps warm restarts at zero re-analyses.
            # Strict mode rejects installs whose worst-signature peak
            # exceeds the budget (hbm_budget_exceeded).
            ms_cert = self._memsurface_lowered(kind, compiled)
            if ms_cert is not None:
                st.memsurfaces[kind] = ms_cert
            else:
                st.memsurfaces.pop(kind, None)
            st.sweep_cache.pop(kind, None)
        st.templates[kind] = compiled
        st.bump(kind)

    def _footprint_lowered(self, kind: str, compiled: CompiledTemplate):
        """Stage-5 dependency analysis (analysis/footprint.py) behind
        GATEKEEPER_FOOTPRINT=off|on|strict.  on: compute the read-set /
        row-locality footprint (enables selective invalidation); strict:
        additionally perturbation-validate it and FAIL the install on
        any violation — a violation means the analysis itself is wrong,
        and serving selective sweeps from a wrong read-set would skip
        real re-evaluations."""
        from gatekeeper_tpu.analysis import footprint
        if footprint.mode() == "off":
            return None
        try:
            fp = footprint.certify(kind, compiled, compiled.vectorized)
        except Exception as e:   # noqa: BLE001 — analysis must not take
            # template install down with it; no footprint just means no
            # selective reuse for this kind
            from gatekeeper_tpu.utils.log import logger
            logger("engine.jax_driver").warning(
                "footprint analysis errored", kind=kind, err=str(e))
            self.metrics.counter("footprint_errors").inc()
            return None
        bad = footprint.violations_for(kind)
        if bad:
            self.metrics.counter("footprint_violations").inc(len(bad))
            if footprint.mode() == "strict":
                from gatekeeper_tpu.analysis.diagnostics import Diagnostic
                from gatekeeper_tpu.errors import VetError
                raise VetError([Diagnostic(code="footprint_violation",
                                           severity="error",
                                           message=v.format())
                                for v in bad])
            return None
        if not fp.row_local:
            self.metrics.counter("footprint_cross_row").inc()
        return fp

    def _shardplan_lowered(self, kind: str, compiled: CompiledTemplate):
        """Stage-6 partition-plan certification (analysis/shardplan.py)
        behind GATEKEEPER_SHARDPLAN=off|warn|strict.  Unlike the other
        stages this one NEVER fails an install: a missing/invalid plan
        only pins the kind to the replicated path (sharding is a
        performance contract, not a semantic one — the replicated path
        is always correct).  strict: the plan is executed on a 2-shard
        simulated mesh at install; any divergence is recorded and the
        kind pins replicated.  Ineligible plans (cross-row templates)
        ARE returned — the sweep reads plan.eligible."""
        from gatekeeper_tpu.analysis import shardplan
        if shardplan.mode() == "off":
            return None
        try:
            plan = shardplan.certify(kind, compiled, compiled.vectorized)
        except Exception as e:   # noqa: BLE001 — analysis must not take
            # template install down with it; no plan just means the
            # kind stays on the replicated path
            from gatekeeper_tpu.utils.log import logger
            logger("engine.jax_driver").warning(
                "shardplan analysis errored", kind=kind, err=str(e))
            self.metrics.counter("shardplan_errors").inc()
            return None
        bad = shardplan.violations_for(kind)
        if bad:
            self.metrics.counter("shardplan_violations").inc(len(bad))
            from gatekeeper_tpu.utils.log import logger
            for v in bad:
                logger("engine.jax_driver").warning(
                    "shardplan invalid; kind pinned to replicated path",
                    kind=kind, note=v.note)
            return None
        if not plan.eligible:
            self.metrics.counter("shardplan_ineligible").inc()
        return plan

    def _compilesurface_lowered(self, kind: str,
                                compiled: CompiledTemplate):
        """Stage-7 compile-surface certification
        (analysis/compilesurface.py) behind
        GATEKEEPER_COMPILE_SURFACE=off|warn|strict.  Like stage 6 this
        NEVER fails an install: an unbounded (or errored) surface only
        excludes the kind from AOT precompilation and retrace gating —
        it keeps serving through the lazy-recompile path, which is
        always correct."""
        from gatekeeper_tpu.analysis import compilesurface
        if compilesurface.mode() == "off":
            return None
        if compiled.vectorized is None:
            return compilesurface.scalar_surface(kind)
        try:
            cert = compilesurface.certify(kind, compiled,
                                          compiled.vectorized)
        except Exception as e:   # noqa: BLE001 — analysis must not take
            # template install down with it; no certificate just means
            # no AOT prewarm or retrace gating for this kind
            from gatekeeper_tpu.utils.log import logger
            logger("engine.jax_driver").warning(
                "compile-surface analysis errored", kind=kind,
                err=str(e))
            self.metrics.counter("compilesurface_errors").inc()
            return None
        if not cert.bounded:
            self.metrics.counter("compile_surface_unbounded").inc()
            from gatekeeper_tpu.utils.log import logger
            logger("engine.jax_driver").warning(
                "compile surface unbounded; kind excluded from AOT "
                "precompile and retrace gating", kind=kind,
                reason=cert.reason)
        return cert

    def _memsurface_lowered(self, kind: str, compiled: CompiledTemplate):
        """Stage-8 memory-surface certification (analysis/memsurface.py)
        behind GATEKEEPER_HBM_BUDGET=off|warn|strict.  warn (default):
        certify the conservative peak-HBM bytes and count budget
        breaches but serve anyway; strict: a template whose
        worst-signature peak exceeds GATEKEEPER_HBM_BUDGET_BYTES fails
        the install with ``hbm_budget_exceeded`` — the reconciler
        expands the VetError into status.byPod[].errors."""
        from gatekeeper_tpu.analysis import memsurface
        if memsurface.mode() == "off":
            return None
        if compiled.vectorized is None:
            return memsurface.scalar_surface(kind)
        try:
            cert = memsurface.certify(kind, compiled, compiled.vectorized)
        except Exception as e:   # noqa: BLE001 — analysis must not take
            # template install down with it; no certificate just means
            # no budget gating or residency planning for this kind
            from gatekeeper_tpu.utils.log import logger
            logger("engine.jax_driver").warning(
                "memory-surface analysis errored", kind=kind, err=str(e))
            self.metrics.counter("memsurface_errors").inc()
            return None
        reason = memsurface.budget_reason(cert)
        if reason is not None:
            self.metrics.counter("hbm_budget_exceeded").inc()
            from gatekeeper_tpu.utils.log import logger
            logger("engine.jax_driver").warning(
                "memory surface exceeds HBM budget", kind=kind,
                reason=reason)
            if memsurface.mode() == "strict":
                from gatekeeper_tpu.analysis.diagnostics import Diagnostic
                from gatekeeper_tpu.errors import VetError
                raise VetError([Diagnostic(code="hbm_budget_exceeded",
                                           severity="error",
                                           message=f"{kind}: {reason}")])
        return cert

    def _surface_guard(self, program, arrays,
                       delta_k: int | None = None) -> bool:
        """Executor cache-miss hook: True when the dispatch signature
        is inside the installed certificate (or the program is
        unguarded).  An uncertified signature is counted and
        flight-recorded; the executor decides refusal (strict) vs the
        lazy-recompile fallback (warn)."""
        from gatekeeper_tpu.analysis import compilesurface
        try:
            ok = compilesurface.dispatch_certified(program, arrays,
                                                   delta_k=delta_k)
        except Exception:   # noqa: BLE001 — the sentinel must never
            return True     # take a legitimate dispatch down
        if ok:
            return True
        compilesurface.uncertified_total += 1
        self.metrics.counter("retrace_uncertified_total").inc()
        try:
            from gatekeeper_tpu.obs.flightrecorder import record_event
            record_event(
                "retrace_uncertified",
                shapes={nm: tuple(int(d) for d in arrays[nm].shape)
                        for nm in sorted(arrays)},
                delta_k=delta_k, mode=compilesurface.mode())
        except Exception:   # noqa: BLE001
            pass
        return False

    def _certify_lowered(self, kind: str, compiled: CompiledTemplate):
        """Stage-4 translation validation (analysis/transval.py) behind
        GATEKEEPER_TRANSVAL=off|warn|strict.  strict: a counterexample
        pins the template to the scalar oracle exactly like CannotLower
        (and the reconciler surfaces `translation_unvalidated`); warn:
        log and serve on device anyway.  Certificates are memoized
        in-process and through the cert snapshot tier, so warm restarts
        run zero validations."""
        from gatekeeper_tpu.analysis import transval
        tv_mode = transval.mode()
        if tv_mode not in ("warn", "strict"):
            return compiled.vectorized
        lowered = transval.maybe_miscompiled(kind, compiled.vectorized)
        try:
            result = transval.certify(kind, compiled, lowered)
        except Exception as e:   # noqa: BLE001 — validation must not
            # take template install down with it; an inconclusive run
            # certifies nothing, so strict mode still pins
            from gatekeeper_tpu.utils.log import logger
            logger("engine.jax_driver").warning(
                "translation validation errored", kind=kind, err=str(e))
            self.metrics.counter("transval_errors").inc()
            return None if tv_mode == "strict" else compiled.vectorized
        if isinstance(result, transval.Certificate):
            self.metrics.counter("transval_certified").inc()
            return compiled.vectorized
        self.metrics.counter("transval_counterexamples").inc()
        from gatekeeper_tpu.utils.log import logger
        logger("engine.jax_driver").warning(
            "translation validation found a counterexample",
            kind=kind, note=result.note, expected=result.expected,
            actual=result.actual, mode=tv_mode)
        if tv_mode == "strict":
            return None   # scalar pin — identical to CannotLower
        return compiled.vectorized

    @staticmethod
    def _verify_lowered(kind: str, lowered):
        """Stage-2 IR verification (analysis/ir_verifier.py) on every
        program before it can reach jit.  Structural checks only — the
        engine has no provider registry in scope.  A malformed program
        falls back to the scalar oracle (identical semantics, no device
        path) unless GATEKEEPER_IR_VERIFY=strict, which raises instead;
        GATEKEEPER_IR_VERIFY=off skips the pass."""
        import os
        mode = os.environ.get("GATEKEEPER_IR_VERIFY", "fallback")
        if mode == "off":
            return lowered
        from gatekeeper_tpu.analysis import verify_program
        from gatekeeper_tpu.analysis.diagnostics import format_all
        diags = verify_program(lowered, providers=None, file=kind)
        if not diags:
            return lowered
        if mode == "strict":
            from gatekeeper_tpu.errors import VetError
            raise VetError(diags)
        import logging
        logging.getLogger(__name__).warning(
            "IR verification failed for %s; falling back to the scalar "
            "oracle:\n%s", kind, format_all(diags))
        return None

    @locked
    def delete_template(self, target: str, kind: str) -> None:
        super().delete_template(target, kind)
        st = self._state(target)
        st.bump(kind)

    @locked
    def put_constraint(self, target: str, kind: str, name: str, constraint: dict) -> None:
        self._footprint_constraint(target, kind, constraint)
        super().put_constraint(target, kind, name, constraint)
        self._state(target).bump(kind)

    def _footprint_constraint(self, target: str, kind: str,
                              constraint: dict) -> None:
        """Strict-mode footprint re-validation at constraint install.

        The footprint claims to cover EVERY constraint of the kind, but
        install order puts templates before constraints, so the
        template-install validation ran against the empty default
        parameter document — under which many templates never fire and
        the perturbation check is vacuous.  The first real parameter
        document is a new operating point: re-certify against it (the
        memo/snapshot make the honest case free) and reject the
        constraint if the claimed read-set fails — a wrong read-set
        would make selective sweeps skip real re-evaluations."""
        from gatekeeper_tpu.analysis import footprint
        if footprint.mode() != "strict":
            return
        st = self._state(target)
        if not isinstance(st, JaxTargetState):
            return
        compiled = st.templates.get(kind)
        if compiled is None or compiled.vectorized is None:
            return
        try:
            fp = footprint.certify(kind, compiled, compiled.vectorized,
                                   constraints=[constraint])
        except Exception as e:   # noqa: BLE001 — analysis failure only
            # disables selective reuse for the kind, never the install
            from gatekeeper_tpu.utils.log import logger
            logger("engine.jax_driver").warning(
                "footprint re-validation errored", kind=kind, err=str(e))
            self.metrics.counter("footprint_errors").inc()
            st.footprints.pop(kind, None)
            st.sweep_cache.pop(kind, None)
            return
        bad = footprint.violations_for(kind)
        if bad:
            self.metrics.counter("footprint_violations").inc(len(bad))
            st.footprints.pop(kind, None)
            st.sweep_cache.pop(kind, None)
            from gatekeeper_tpu.analysis.diagnostics import Diagnostic
            from gatekeeper_tpu.errors import VetError
            raise VetError([Diagnostic(code="footprint_violation",
                                       severity="error",
                                       message=v.format())
                            for v in bad])
        st.footprints[kind] = fp

    @locked
    def delete_constraint(self, target: str, kind: str, name: str) -> None:
        super().delete_constraint(target, kind, name)
        self._state(target).bump(kind)

    # ------------------------------------------------------------------

    def _match_engine(self, st: JaxTargetState, target: str):
        if st.match_engine is None:
            st.match_engine = self.targets[target].make_match_engine(st.table)
        return st.match_engine

    def _kind_constraints(self, st: TargetState, kind: str) -> list[dict]:
        return [st.constraints[kind][n] for n in sorted(st.constraints.get(kind, {}))]

    def _kind_mask(self, st: JaxTargetState, target: str, kind: str,
                   constraints: list[dict]):
        """(mask [C, n_rows] view, dirty rows | None, padded).  The mask
        is kept in its padded [c_pad, r_pad] form (the device layout) and
        delta-maintained under churn: one copy + dirty-column writes per
        generation instead of full re-matching + re-padding.  Delta is
        bypassed when a Namespace object changed (namespaceSelector
        results of unchanged rows may shift, table.namespaces_dirty_since)
        or rows were remapped."""
        from gatekeeper_tpu.ir.prep import audit_pads
        from gatekeeper_tpu.store.table import delta_worthwhile
        engine = self._match_engine(st, target)
        if engine is None:
            return None, None, None
        if all(not (c.get("spec") or {}).get("match") for c in constraints):
            # no constraint carries match criteria: every alive resource
            # matches (kinds default to wildcard, target.go:147-173).
            # TRIVIAL sentinel: the device gates on __alive__ alone and
            # no [C, R] mask is built or shipped — at 1M rows the mask
            # upload dominates cold start through a thin transport.
            return TRIVIAL_MATCH, None, None
        table = st.table
        gen, remap = table.generation, table.remap_generation
        conver = self.con_version_of(st, kind)
        n = table.n_rows
        n_con = len(constraints)
        r_pad, c_pad = audit_pads(n, n_con)
        hit = st.mask_cache.get(kind)
        if hit is not None and hit[0] == (gen, conver):
            padded = hit[2]
            return padded[:n_con, :n], None, padded
        if hit is not None and hit[1] == (conver, remap) \
                and hit[2].shape == (c_pad, r_pad):
            prev_gen = hit[0][0]
            old = hit[3]            # retired (gen, padded) or None
            # ping-pong: overwrite the retired buffer (two updates old)
            # at the rows dirty since ITS generation — O(|dirty|) writes
            # instead of an O(c_pad*r_pad) copy.  Requires (a) no
            # Namespace churn since the buffer's generation
            # (namespaceSelector results of untouched rows would be
            # stale in it) and (b) the buffer not being the one
            # currently installed in the bindings arrays (host/device
            # references must see immutable content).
            if old is not None and old[1].shape == (c_pad, r_pad) \
                    and old[1] is not hit[2] \
                    and old[1] is not st.installed_match.get(kind) \
                    and not table.namespaces_dirty_since(old[0]):
                target, since = old[1], min(old[0], prev_gen)
            elif not table.namespaces_dirty_since(prev_gen):
                target, since = None, prev_gen     # copy-on-write path
            else:
                target = since = -1                # full rebuild
            if since != -1:
                rows = table.dirty_rows_since(since)
                if delta_worthwhile(len(rows), n):
                    sub, rows = engine.mask_rows_since(constraints, since) \
                        if len(rows) else (None, rows)
                    if target is None:
                        target = hit[2].copy()
                    if len(rows):
                        # flat scatter: one 1-D fancy write beats the
                        # 2-D cross-product indexing at [C, 10k] scale
                        flat = (np.arange(n_con, dtype=np.int64)[:, None]
                                * target.shape[1] + rows[None, :]).ravel()
                        target.ravel()[flat] = sub.ravel()
                    base_rows = rows if since == prev_gen else \
                        table.dirty_rows_since(prev_gen)
                    st.mask_cache[kind] = ((gen, conver), (conver, remap),
                                           target, (prev_gen, hit[2]))
                    # the delta is only meaningful relative to hit[2]:
                    # the device-sync consumer must verify ITS base is
                    # that exact buffer (scalar-sweep interludes advance
                    # the mask without advancing the device)
                    return target[:n_con, :n], (hit[2], base_rows), target
        padded = np.zeros((c_pad, r_pad), dtype=bool)
        padded[:n_con, :n] = engine.mask(constraints)
        st.mask_cache[kind] = ((gen, conver), (conver, remap), padded, None)
        return padded[:n_con, :n], None, padded

    @staticmethod
    def _binding_delta_on() -> bool:
        """GATEKEEPER_BINDING_DELTA: the incremental update_bindings
        chain (O(dirty) host work + row-sized device scatters).  ``off``
        rebuilds bindings whole on every store generation — the
        bit-identical oracle for the delta chain, and the re-stage
        comparator tests/test_devpages.py measures H2D against."""
        import os
        return os.environ.get(
            "GATEKEEPER_BINDING_DELTA", "on").lower() not in ("off", "0")

    def _kind_bindings(self, st: JaxTargetState, kind: str,
                       compiled: CompiledTemplate, constraints: list[dict]):
        """Per-kind bindings with incremental churn updates.  Retired
        bindings (two updates old) are recycled as write buffers
        (ping-pong): the driver hands out only the newest bindings per
        kind and device arrays are immutable snapshots, so overwriting
        the retired generation's numpy buffers is safe — and it turns
        per-sweep full-array copies into O(|dirty|) writes."""
        from gatekeeper_tpu.ir.prep import update_bindings
        key = (st.table.generation, self.con_version_of(st, kind))
        hit = st.bindings_cache.get(kind)
        if hit is not None and hit[0] == key:
            return hit[1]
        if hit is not None and hit[0][1] == key[1] \
                and self._binding_delta_on():
            retired = st.bindings_retired.get(kind)
            recycle = retired[1] if retired is not None \
                and retired[0] == key[1] else None
            b = update_bindings(compiled.vectorized.spec, st.table,
                                constraints, hit[1], recycle=recycle)
            if b is not None:
                # carry the gate-source identities so unchanged gates
                # keep their device copies through the delta chain
                for attr in ("_match_src", "_rank_src"):
                    if attr in hit[1].__dict__:
                        b.__dict__[attr] = hit[1].__dict__[attr]
                self.metrics.counter("bindings_delta_updates").inc()
                st.bindings_retired[kind] = (key[1], hit[1])
                st.bindings_cache[kind] = (key, b)
                return b
        bindings = build_bindings(compiled.vectorized.spec, st.table, constraints)
        self.metrics.counter("bindings_full_builds").inc()
        st.bindings_retired.pop(kind, None)
        st.bindings_cache[kind] = (key, bindings)
        return bindings

    def _selective_reuse(self, st: JaxTargetState, kind: str,
                         compiled: CompiledTemplate,
                         constraints: list[dict], limit):
        """Footprint-driven selective invalidation: return the cached
        sweep entry + bindings when this kind's verdicts provably
        cannot have changed since they were captured — no dirty column
        path (store.table.dirty_paths_since) intersects the template's
        validated read-set (footprint object paths + the constraint
        match criteria paths), the key set / row ids / constraint set
        are unchanged, and the template is row-local with no external
        providers or inventory reads (their inputs live outside the
        table's column diff).  Caller holds ``_prep_lock``."""
        ent = st.sweep_cache.get(kind)
        if ent is None:
            return None
        fp = st.footprints.get(kind)
        if fp is None or not fp.row_local or fp.providers \
                or compiled.uses_inventory:
            return None
        table = st.table
        conver = self.con_version_of(st, kind)
        if ent["conver"] != conver or ent["limit"] != limit \
                or ent["kgen"] != table.key_generation \
                or ent["remap"] != table.remap_generation \
                or ent["n_rows"] != table.n_rows:
            return None
        if table.generation != ent["gen"]:
            if table.namespaces_dirty_since(ent["gen"]):
                return None
            changed = table.dirty_paths_since(ent["gen"])
            if changed is None:     # window predates the path log
                return None
            from gatekeeper_tpu.analysis.footprint import (MATCH_PATHS,
                                                           paths_intersect)
            read = set(fp.object_paths()) | set(MATCH_PATHS)
            for c in changed:
                if any(paths_intersect(c, r) for r in read):
                    return None
        hitb = st.bindings_cache.get(kind)
        if hitb is None or hitb[1] is not ent["bindings"]:
            return None
        # refresh the cache key to the current generation: the dirty
        # columns provably don't feed this kind, so its bindings are
        # already current.  Safe for later delta chains — a future
        # update_bindings derives its dirty window from the bindings'
        # own delta_state, not from this key.
        st.bindings_cache[kind] = ((table.generation, conver), hitb[1])
        ent["gen"] = table.generation
        self.metrics.counter("footprint_kind_sweeps_skipped").inc()
        return ent, hitb[1]

    def _capture_sweep(self, st: JaxTargetState, kind: str,
                       compiled: CompiledTemplate, mode: str, spec,
                       payload, limit) -> None:
        """Store one kind's resolved device payload + reuse guards so a
        later churn sweep whose dirty columns miss this kind's read-set
        can replay it (_selective_reuse).  Only row-local templates
        without provider/inventory reads are eligible — everything the
        payload depends on is then visible to the table's column
        diff."""
        fp = st.footprints.get(kind)
        if fp is None or not fp.row_local or fp.providers \
                or compiled.uses_inventory:
            return
        parts = payload if isinstance(payload, tuple) else (payload,)
        try:
            nbytes = sum(int(getattr(a, "nbytes", 0)) for a in parts)
        except Exception:   # noqa: BLE001 — exotic payload: don't cache
            return
        if nbytes > SWEEP_CACHE_MAX_BYTES:
            return
        table = st.table
        with self._prep_lock:
            st.sweep_cache[kind] = {
                "mode": mode, "payload": payload, "prog": spec[4],
                "bindings": spec[5], "mask": spec[6],
                "gen": table.generation, "kgen": table.key_generation,
                "remap": table.remap_generation, "n_rows": table.n_rows,
                "conver": self.con_version_of(st, kind), "limit": limit,
            }

    def _devpages_active(self, compiled: CompiledTemplate) -> bool:
        """Device-resident pages are usable for this template right
        now: GATEKEEPER_DEVPAGES on, a lowered program to evaluate, and
        a live device backend (scalar-only degradation keeps every kind
        on the host-paged oracle)."""
        from gatekeeper_tpu.enforce.devpages import devpages_mode
        return devpages_mode() and compiled.vectorized is not None \
            and not self.scalar_only

    @staticmethod
    def _inv_join_only(fp, compiled: CompiledTemplate) -> bool:
        """True when a template is cross-row SOLELY through lowered
        inventory joins (spec.inv_joins) — the one cross-row shape the
        devpages delta kernel evaluates in-jit (_inv_join_mask), which
        is what makes e.g. K8sUniqueIngressHost page-eligible.  Any
        other cross-row reason (or an inventory read the lowering did
        not capture as a join) keeps the kind ineligible."""
        if compiled.vectorized is None:
            return False
        if not getattr(compiled.vectorized.spec, "inv_joins", ()):
            return False
        reasons = tuple(getattr(fp, "cross_row_reasons", ()) or ())
        return bool(reasons) and all(
            r.startswith("inventory join") for r in reasons)

    def _pages_ineligible(self, st: JaxTargetState, kind: str,
                          compiled: CompiledTemplate) -> str | None:
        """None when the kind can serve from the VerdictLedger, else
        the fallback reason.  Same gates as footprint selective reuse:
        only a row-local template with no provider/inventory reads has
        verdicts that per-page re-evaluation can maintain exactly.
        Under GATEKEEPER_DEVPAGES one relaxation: a kind whose only
        cross-row dependency is a lowered inventory join is admitted —
        the in-jit join sees the whole table every delta sweep, so page
        locality is not assumed (and on devpages fallback such a kind
        takes a full rebuild, never the host page loop)."""
        if compiled.vectorized is None:
            return "scalar-pin"
        fp = st.footprints.get(kind)
        if fp is None:
            return "no-footprint"
        dev_ij = self._devpages_active(compiled) \
            and self._inv_join_only(fp, compiled)
        if not fp.row_local and not dev_ij:
            return "cross-row"
        if fp.providers:
            return "external-providers"
        if compiled.uses_inventory and not dev_ij:
            return "inventory-read"
        return None

    @staticmethod
    def _observable_kinds(compiled: CompiledTemplate,
                          constraints: list[dict]) -> frozenset | None:
        """Resource kinds whose churn can change this template kind's
        verdicts: the union of every constraint's ``spec.match.kinds``
        plus the kinds its inventory joins read.  None = wildcard
        (some constraint matches every kind — cannot scope).  Drives
        the per-kind widen scoping: a dirty-log widen marker whose
        churned-kind union is disjoint from this set is skippable."""
        out: set[str] = set()
        if compiled.vectorized is not None:
            for ij in getattr(compiled.vectorized.spec, "inv_joins", ()):
                out.add(ij.kind)
        for c in constraints:
            match = (c.get("spec") or {}).get("match") or {}
            kl = match.get("kinds")
            if not isinstance(kl, list):
                return None         # absent/malformed kinds: wildcard
            for ks in kl:
                knames = (ks or {}).get("kinds") or []
                if "*" in knames:
                    return None
                out.update(k for k in knames if isinstance(k, str))
        return frozenset(out)

    def _devpages_reject(self, dv: dict, kind: str, reason: str) -> None:
        """Record one kind falling back from the device-resident path
        (stats + flight recorder + labeled counter)."""
        dv["kinds_fallback"] += 1
        dv["fallback_reasons"][kind] = reason
        self.metrics.counter("devpages_fallbacks", kind=kind).inc()
        from gatekeeper_tpu.obs.flightrecorder import record_event
        record_event("devpages_fallback", kind=kind, reason=reason)

    def _devpaged_kind(self, st, target, handler, compiled, constraints,
                       kind, led, ent, conver, rcache, pg, dv,
                       refresh_only: bool = False) -> bool:
        """One kind's sweep on the device-resident paged store.

        The kind's columns stay resident as fixed-geometry page arrays
        (the bindings delta chain scatters row-sized records to dirty
        slots — veval._scatter_rows), inventory-join input records ride
        the same discipline, and ONE jitted call (eval_mask_delta)
        computes the violation mask, gathers it through the on-device
        page table and returns the compact appear/clear delta stream
        against the previous resident mask.  Consumption preserves the
        ledger's exact-event contract:

          * dirty rows with any candidate bit, and every ``+`` delta
            row, re-confirm through the exact scalar path
            (_ledger_apply_row) — messages stay oracle-identical;
          * dirty rows with NO candidate bit are direct full-row clears
            (mask bit 0 = definitely no violation — sound by the
            over-approximation contract);
          * ``-`` deltas on non-dirty rows (cross-row inventory-join
            flips) drop just that constraint's verdicts — same
            identity, so no phantom clear+appear pair.

        The resident mask deliberately excludes ``__match__``: every
        match input is row-local (a flip dirties its own row, which the
        confirm covers; namespaceSelector churn rebuilt upstream), so
        the [C, R] match matrix never rides H2D on churn.

        Returns True when the ledger was brought current (or, with
        ``refresh_only``, the resident state rebuilt after a host full
        build); False = caller falls back.  Raising is also a fallback
        — the caller drops the kind's device state and recovers."""
        from gatekeeper_tpu.enforce import devpages as _dvp
        import jax.numpy as jnp
        table = st.table
        ex = self.executor
        kp = st.devpages.get(kind)
        if kp is None:
            kp = _dvp.KindPages(kind=kind)
            if st.devpages_geom:
                geom = st.devpages_geom.pop(kind, None)
                if isinstance(geom, dict) and kp.adopt_geometry(geom):
                    dv["geometry_adopted"] += 1
            st.devpages[kind] = kp
        h2d0 = ex.h2d_bytes
        sc0, sr0 = ex.h2d_scatter_bytes, ex.h2d_scatter_rows
        try:
            bindings = self._kind_bindings(st, kind, compiled, constraints)
        except ExternalDataError:
            self._devpages_reject(dv, kind, "external-data-failure")
            return False
        if bindings.f32_unsafe:
            self._devpages_reject(dv, kind, "f32-unsafe")
            return False
        r_pad, c_pad = bindings.r_pad, bindings.c_pad
        # inventory-join device input records (r:ij.<join>.*): cold
        # upload once, then row-sized scatters of just the changed
        # entries — rebound per update, never mutated in place
        spec = compiled.vectorized.spec
        ij_specs_raw = tuple(getattr(spec, "inv_joins", ()))
        ij_dev: dict = {}
        for req in ij_specs_raw:
            host = _dvp.build_inv_join_inputs(req, table, r_pad)
            for nm, arr in host.items():
                prev_h = kp.ij_host.get(nm)
                prev_d = kp.ij_dev.get(nm)
                if prev_h is None or prev_d is None \
                        or prev_h.shape != arr.shape:
                    dev = ex._put(nm, arr, False)
                elif np.array_equal(prev_h, arr):
                    dev = prev_d
                else:
                    changed = np.nonzero(prev_h != arr)[0]
                    from gatekeeper_tpu.analysis.costmodel import \
                        scatter_worthwhile
                    if scatter_worthwhile(len(changed), arr.shape[0]):
                        dev = ex._scatter_rows(nm, prev_d, arr, changed,
                                               False)
                    else:
                        dev = ex._put(nm, arr, False)
                kp.ij_host = {**kp.ij_host, nm: arr}
                kp.ij_dev = {**kp.ij_dev, nm: dev}
                ij_dev[nm] = dev
        if ij_specs_raw:
            dv["inv_joins_device"] += len(ij_specs_raw)
        # on-device page table: row -> slot indirection ([r_pad] int32,
        # identity while row ids are stable); rebuilt — rebound, not
        # mutated — on remap or slot-capacity change
        if kp.page_table is None or kp.slots != r_pad \
                or kp.remap != table.remap_generation:
            kp.page_table = ex._put(
                "__pagetable__", np.arange(r_pad, dtype=np.int32), False)
            kp.slots = r_pad
            kp.page_rows = table.page_rows
            kp.n_pages = table.n_pages
        kp.free = tuple(table.free_slots())
        # Stage-8 residency planning: under a devpages budget whose
        # certified claim the full resident mask exceeds, the mask
        # lives split across a hot device slot buffer and a host spill
        # mirror (enforce/devpages.ResidencyPlanner) and is
        # reconstructed bit-identically here before the delta sweep
        planner = kp.resident
        budget = _dvp.residency_budget_bytes()
        if budget is None:
            planner = kp.resident = None
        elif planner is None \
                or not planner.compatible(c_pad, r_pad, table.page_rows):
            planner = _dvp.ResidencyPlanner(
                budget, c_pad, r_pad, table.page_rows,
                cert=st.memsurfaces.get(kind))
            kp.resident = planner
        planner_holds = planner is not None \
            and planner.holds(c_pad, r_pad)
        _rs_sp0 = planner.spills if planner is not None else 0
        _rs_rs0 = planner.restores if planner is not None else 0
        have_mask = planner_holds or (
            kp.mask is not None
            and tuple(kp.mask.shape) == (c_pad, r_pad))
        mask_valid = (have_mask and kp.gen == ent.gen
                      and kp.remap == table.remap_generation
                      and kp.conver == conver
                      and kp.c_pad == c_pad and kp.slots == r_pad)
        if refresh_only or not mask_valid:
            # allocs-ok: cold rebuild after geometry/generation change
            old_mask = jnp.zeros((c_pad, r_pad), dtype=bool)
        elif planner_holds:
            old_mask = planner.expand(ex)
        else:
            old_mask = kp.mask
        ij_sig = tuple((req.name, bool(req.exclude_same_name))
                       for req in ij_specs_raw)
        k = max(kp.k, _dvp.DELTA_K_MIN)
        dirty = table.dirty_rows_since(ent.gen) \
            if not refresh_only else np.empty((0,), dtype=np.int64)
        new_mask, idx, signs, count, row_any = ex.eval_mask_delta(
            compiled.vectorized.program, bindings, None, old_mask,
            kp.page_table, k, ij_sig, ij_dev)
        if count > k and not refresh_only:
            # compact stream overflowed the compiled width: one
            # recompile at the next bucket, then re-dispatch
            dv["delta_overflows"] += 1
            k = _dvp.delta_bucket(count) * _dvp.DELTA_K_LADDER
            if k > (c_pad * r_pad):
                k = c_pad * r_pad
            kp.k = k
            new_mask, idx, signs, count, row_any = ex.eval_mask_delta(
                compiled.vectorized.program, bindings, None, old_mask,
                kp.page_table, k, ij_sig, ij_dev)
            if count > k:
                self._devpages_reject(dv, kind, "delta-overflow")
                return False
        if not refresh_only:
            n_rows = table.n_rows
            cnames = [(c.get("metadata") or {}).get("name", "")
                      for c in constraints]
            valid = int(min(count, k))
            plus_rows: set[int] = set()
            plus_bits: set[tuple[int, int]] = set()
            minus_by_row: dict[int, list[str]] = {}
            for i in range(valid):
                flat = int(idx[i])
                if flat < 0:
                    continue
                ci, row = flat // r_pad, flat % r_pad
                if ci >= len(cnames) or row >= n_rows:
                    continue    # padded constraint/row space
                if bool(signs[i]):
                    plus_rows.add(row)
                    plus_bits.add((ci, row))
                else:
                    minus_by_row.setdefault(row, []).append(cnames[ci])
            dirty_set = set(int(r) for r in dirty)
            confirm = {r for r in dirty_set if bool(row_any[r])} \
                | plus_rows
            n_evals = 0
            involved = sorted(dirty_set | plus_rows | set(minus_by_row))
            for row in involved:
                if row in confirm:
                    n_evals += self._ledger_apply_row(
                        st, target, handler, compiled, constraints,
                        kind, led, rcache, row, pg)
                elif row in dirty_set:
                    # no candidate bit anywhere on a dirty row: the
                    # device proved no constraint can violate — direct
                    # full-row clear, no scalar eval
                    meta = table.meta_at(row)
                    ident = () if meta is None \
                        else (meta.namespace, meta.name)
                    pg["events"] += len(led.set_row(kind, row, ident, {}))
                    dv["direct_clears"] += 1
                else:
                    # '-' delta on a clean row: that constraint's bit
                    # went definitely-no-violation — drop exactly its
                    # verdicts, same identity (no clear+appear pair)
                    old = ent.rows.get(row)
                    if old is None:
                        continue
                    ident, by_c = old
                    drop = set(minus_by_row[row])
                    new_by_c = {cn: rs for cn, rs in by_c.items()
                                if cn not in drop}
                    if len(new_by_c) != len(by_c):
                        pg["events"] += len(
                            led.set_row(kind, row, ident, new_by_c))
                        dv["direct_clears"] += 1
            if not mask_valid and ent.rows:
                # reconcile sweep (restart/resize/toggle): the previous
                # resident mask is unknown, so '-' deltas don't exist —
                # prune stale ledger verdicts by the new mask's bits
                # instead (vs zeros, every 1-bit is in the '+' stream)
                cset = set(cnames)
                cidx = {cn: i for i, cn in enumerate(cnames)}
                for row, (ident, by_c) in list(ent.rows.items()):
                    if row in confirm or row in dirty_set:
                        continue
                    new_by_c = {cn: rs for cn, rs in by_c.items()
                                if cn in cset
                                and (cidx[cn], row) in plus_bits}
                    if len(new_by_c) != len(by_c):
                        pg["events"] += len(
                            led.set_row(kind, row, ident, new_by_c))
                        dv["direct_clears"] += 1
            dv["delta_events"] += int(count)
            dv["scatter_rows"] += ex.h2d_scatter_rows - sr0
            dv["rows_confirmed"] += len(confirm)
            pg["rows_reevaluated"] += len(confirm)
            pg["evaluations_saved"] += \
                max(0, n_rows - len(confirm)) * len(constraints)
            pg["pages_skipped"] += max(
                0, table.n_pages
                - len({r // table.page_rows for r in involved}))
        else:
            dv["mask_builds"] += 1
        if planner is not None and planner.active:
            # LRU bump the pages this sweep actually touched, then
            # split the fresh mask across the slot buffer and the
            # host spill mirror — the full-size device array is
            # released (the certified resident claim is what stays)
            if not refresh_only:
                planner.touch({r // planner.page_rows
                               for r in involved})
            planner.store(new_mask)
            kp.mask = None
            dv["resident_spills"] += planner.spills - _rs_sp0
            dv["resident_restores"] += planner.restores - _rs_rs0
            dv["resident_pages_device"] += len(planner.slot_of)
        else:
            kp.mask = new_mask
        kp.gen = table.generation
        kp.remap = table.remap_generation
        kp.conver = conver
        kp.c_pad = c_pad
        kp.n_pages = table.n_pages
        kp.page_rows = table.page_rows
        dv["kinds_device"] += 1
        dv["h2d_bytes"] += (ex.h2d_bytes - h2d0) \
            + (ex.h2d_scatter_bytes - sc0)
        dv["h2d_scatter_bytes"] += ex.h2d_scatter_bytes - sc0
        return True

    def _paged_kind(self, st, target, handler, compiled, constraints,
                    ordered_rows, row_order, kind, limit, tagged, rcache,
                    pg, dirty_pages_out, dv=None) -> None:
        """Serve one kind from the VerdictLedger, first applying the
        deltas for every page dirtied since the entry's generation.
        Rows re-evaluate through the exact scalar path (match + oracle
        + fmt memo), so the ledger holds exactly the confirmed
        violating rows; capped output walks them in rank order —
        bit-identical to the full path's top-k + refill emission."""
        from gatekeeper_tpu.analysis.footprint import (MATCH_PATHS,
                                                       paths_intersect)
        from gatekeeper_tpu.enforce.ledger import (VerdictLedger,
                                                   constraints_digest)
        table = st.table
        if st.ledger is None:
            st.ledger = VerdictLedger(target)
        led = st.ledger
        ent = led.entry(kind)
        conver = self.con_version_of(st, kind)
        condigest = constraints_digest(constraints)
        if ent.gen < 0 and st.ledger_restored:
            # warm restart: adopt the snapshot's verdicts when the
            # constraint set (by content) and row space still match the
            # restored table — a hit means zero cold full builds
            payload = st.ledger_restored.pop(kind, None)
            if payload is not None and led.adopt(kind, payload, condigest,
                                                 table, conver):
                ent = led.entry(kind)
                geom = payload.get("devpages") \
                    if isinstance(payload, dict) else None
                if isinstance(geom, dict):
                    if dv is not None:
                        # adopt the device-pagemap geometry now: a
                        # clean warm restart may have nothing dirty, so
                        # the first devpages sweep (which would pop a
                        # stash) can be arbitrarily far away
                        from gatekeeper_tpu.enforce import \
                            devpages as _dvp_mod
                        kp = st.devpages.get(kind)
                        if kp is None:
                            kp = _dvp_mod.KindPages(kind=kind)
                            st.devpages[kind] = kp
                        if kp.adopt_geometry(geom):
                            dv["geometry_adopted"] += 1
                    else:
                        # devpages off this sweep: stash for the first
                        # devpages sweep to adopt instead of deriving
                        # the paged layout cold
                        if st.devpages_geom is None:
                            st.devpages_geom = {}
                        st.devpages_geom[kind] = geom
        rebuild = None
        if ent.gen < 0:
            rebuild = "cold"
        elif ent.conver != conver or ent.condigest != condigest:
            rebuild = "constraints-changed"
        elif ent.remap != table.remap_generation:
            rebuild = "rows-remapped"
        elif table.namespaces_dirty_since(ent.gen):
            # namespace label edits shift namespaceSelector matching of
            # OTHER rows — page locality doesn't hold
            rebuild = "namespace-churn"
        entries = None
        if rebuild is None and table.generation != ent.gen:
            entries = table.dirty_page_entries_since(ent.gen)
            if entries is None:
                # window predates the log floor: the dirty PAGES are
                # unattributable, but the row space itself is intact
                # (a shrink would have bumped remap_generation and been
                # caught above), so rebuild the kind page-by-page
                # through the normal delta path below — every page
                # re-evaluates, warming the review cache incrementally
                # and clearing dead rows via their own page's re-eval —
                # instead of one monolithic whole-kind build.  (A cap-
                # overflow widen no longer lands here: the log keeps a
                # paths=None marker carrying the dropped half's exact
                # page/kind unions, scoped per kind in the loop below.)
                pg["widen_fallbacks"] += 1
                self.metrics.counter("widen_fallbacks", kind=kind).inc()
                entries = [(table.generation, None,
                            frozenset(range(table.n_pages)), None)]
        dev_done = False
        if rebuild is None and entries and dv is not None \
                and self._devpages_active(compiled):
            # device-resident delta path: scatter-update the resident
            # columns, compute mask + delta in one jitted call, consume
            # the compact stream.  Falls back to the host page loop on
            # any failure — except for cross-row (inventory-join)
            # kinds, whose verdicts the page loop cannot maintain
            # (page locality is exactly what the device delta waived),
            # so those take one full rebuild instead.
            try:
                dev_done = self._devpaged_kind(
                    st, target, handler, compiled, constraints, kind,
                    led, ent, conver, rcache, pg, dv)
            except Exception as e:  # noqa: BLE001 — devpages is the
                st.devpages.pop(kind, None)         # gated experiment
                self._devpages_reject(dv, kind, f"error: {e!r}")
                dev_done = False
            if not dev_done:
                fp = st.footprints.get(kind)
                if fp is None or not fp.row_local:
                    rebuild = "devpages-fallback"
        n_evals = 0
        if dev_done:
            pass        # ledger brought current on the device path
        elif rebuild is not None:
            # full build: clear rows that died since (sorted — the
            # canonical event order puts dead-row clears first), then
            # every live row in rank order
            for row in sorted(ent.rows):
                if row >= table.n_rows or table.meta_at(row) is None:
                    pg["events"] += len(led.set_row(kind, row, (), {}))
            for row in ordered_rows:
                n_evals += self._ledger_apply_row(
                    st, target, handler, compiled, constraints, kind, led,
                    rcache, row, pg)
            ent.full_builds += 1
            pg["full_builds"] += 1
            pg["pages_evaluated"] += table.n_pages
            pg["rows_reevaluated"] += len(ordered_rows)
            if dv is not None and self._devpages_active(compiled):
                # refresh the device-resident mask after a host full
                # build so the NEXT sweep deltas instead of reconciling
                try:
                    self._devpaged_kind(
                        st, target, handler, compiled, constraints,
                        kind, led, ent, conver, rcache, pg, dv,
                        refresh_only=True)
                except Exception:   # noqa: BLE001 — refresh is advisory
                    st.devpages.pop(kind, None)
        elif entries:
            fp = st.footprints[kind]
            read = set(fp.object_paths()) | set(MATCH_PATHS)
            obs_kinds = self._observable_kinds(compiled, constraints)
            kgen_changed = ent.kgen != table.key_generation
            pages: set[int] = set()
            for _g, paths, pgs, ekinds in entries:
                if paths is None:
                    # cap-overflow widen marker: its paths are
                    # unattributable (treat as every path), but its
                    # resource-kind union is exact — a template whose
                    # observable kinds (match criteria + inventory
                    # joins) are disjoint skips the dropped half
                    # outright instead of re-evaluating its pages
                    if ekinds is not None and obs_kinds is not None \
                            and not (obs_kinds & ekinds):
                        continue
                    pg["widen_fallbacks"] += 1
                    self.metrics.counter("widen_fallbacks",
                                         kind=kind).inc()
                    pages |= pgs
                    continue
                # page filtering by read-set intersection is only exact
                # for pure replaces: a bulk entry mixing inserts (empty
                # paths) with non-intersecting edits can't attribute
                # pages, so key-set churn includes every touched page
                if kgen_changed or not paths or any(
                        paths_intersect(p, r) for p in paths
                        for r in read):
                    pages |= pgs
            R = table.page_rows
            n_rows = table.n_rows
            rows_seen = 0
            for p in sorted(pages):
                start, end = p * R, (p + 1) * R
                if start >= n_rows:
                    continue    # stale page beyond the row space
                pg["rows_padded"] += max(0, end - n_rows)
                for row in range(start, min(end, n_rows)):
                    n_evals += self._ledger_apply_row(
                        st, target, handler, compiled, constraints, kind,
                        led, rcache, row, pg)
                    rows_seen += 1
            dirty_pages_out |= pages
            pg["pages_evaluated"] += len(pages)
            pg["pages_skipped"] += max(0, table.n_pages - len(pages))
            pg["rows_reevaluated"] += rows_seen
            pg["evaluations_saved"] += \
                max(0, len(ordered_rows) - rows_seen) * len(constraints)
        else:
            # generation unchanged (or every entry already applied):
            # the ledger is current — pure serve
            pg["pages_skipped"] += table.n_pages
            pg["evaluations_saved"] += len(ordered_rows) * len(constraints)
        ent.gen = table.generation
        ent.kgen = table.key_generation
        ent.remap = table.remap_generation
        ent.n_rows = table.n_rows
        ent.conver = conver
        ent.condigest = condigest
        if tagged is not None:
            # sweep caller: emit capped results.  The reactor passes
            # None — it maintains verdicts between sweeps; formatting
            # happens when the next audit serves from the ledger.
            self._ledger_serve(ent, constraints, row_order, kind, limit,
                               tagged)

    def _ledger_apply_row(self, st, target, handler, compiled, constraints,
                          kind, led, rcache, row, pg) -> int:
        """Re-evaluate one row against the kind's constraints through
        the exact scalar path and replace its ledger verdicts, emitting
        the delta events.  Returns evaluations performed."""
        table = st.table
        meta = table.meta_at(row)
        if meta is None:
            pg["events"] += len(led.set_row(kind, row, (), {}))
            return 0
        pair = self._row_review(st, handler, row, rcache)
        if pair is None:
            pg["events"] += len(led.set_row(kind, row, (), {}))
            return 0
        review, frozen, shared = pair
        by_c: dict[str, list] = {}
        n_evals = 0
        for c in constraints:
            if not any(True for _ in handler.matching_constraints(
                    review, [c], table)):
                continue
            n_evals += 1
            results = self._pair_results(st, target, kind, compiled, c,
                                         row, review, frozen, None, shared)
            if results:
                by_c[(c.get("metadata") or {}).get("name", "")] = results
        pg["events"] += len(led.set_row(kind, row,
                                        (meta.namespace, meta.name), by_c))
        return n_evals

    def _ledger_serve(self, ent, constraints, row_order, kind, limit,
                      tagged) -> None:
        """Emit capped results from the ledger's confirmed rows.  Rank
        order + whole-row emission with the cap checked at the top of
        the loop reproduces _format_topk/_scalar_kind exactly (top-k by
        rank plus full-mask refill IS "walk confirmed rows in rank
        order until the result count reaches the cap")."""
        for c in constraints:
            cname = (c.get("metadata") or {}).get("name", "")
            rows = [row for row, (_ident, by_c) in ent.rows.items()
                    if cname in by_c and row in row_order]
            rows.sort(key=row_order.__getitem__)
            emitted = 0
            for row in rows:
                if limit is not None and emitted >= limit:
                    break
                results = ent.rows[row][1][cname]
                for r in results:
                    # fresh copies: downstream sets .resource and owns
                    # result.metadata; the ledger's canon stays pristine
                    tagged.append(((row_order[row], kind, cname),
                                   dataclasses.replace(
                                       r, metadata=dict(r.metadata))))
                emitted += len(results)

    # ------------------------------------------------------------------
    # continuous-enforcement entry points (enforce/reactor.py)

    def react_kind(self, target: str,
                   kind: str | None = None) -> dict | None:
        """Rung 1 of the reactor's resync ladder: fold the store's
        dirty pages into the VerdictLedger for one kind (every eligible
        kind when None) with no sweep in between — the single-event →
        single-page re-eval path.  Serving is skipped (verdicts are
        *maintained*; the next audit formats from the updated ledger).
        Returns the paged accounting dict, or None when pages are off
        or nothing was eligible."""
        from gatekeeper_tpu.enforce.ledger import pages_mode
        if not pages_mode():
            return None
        st = self._state(target)
        if not isinstance(st, JaxTargetState):
            return None
        handler = self.targets[target]
        pg = {"pages_evaluated": 0, "pages_skipped": 0, "rows_padded": 0,
              "rows_reevaluated": 0, "evaluations_saved": 0,
              "widen_fallbacks": 0, "full_builds": 0, "events": 0}
        dirty: set[int] = set()
        from gatekeeper_tpu.enforce.devpages import (
            devpages_mode as _dv_mode, fresh_stats as _dv_fresh)
        dv = _dv_fresh() if (_dv_mode() and not self.scalar_only) else None
        reacted = 0
        with self._prep_lock:
            ordered_rows, row_order = self._ensure_order(st)
            kinds = [kind] if kind is not None else sorted(st.templates)
            rcache: dict[int, tuple] = {}
            for k in kinds:
                compiled = st.templates.get(k)
                if compiled is None:
                    continue
                constraints = self._kind_constraints(st, k)
                if not constraints:
                    continue
                if self._pages_ineligible(st, k, compiled) is not None:
                    continue
                self._paged_kind(st, target, handler, compiled,
                                 constraints, ordered_rows, row_order, k,
                                 None, None, rcache, pg, dirty, dv)
                reacted += 1
        if reacted == 0:
            return None
        pg["kinds"] = reacted
        pg["dirty_pages"] = len(dirty)
        if dv is not None:
            pg["devpages"] = dv
        m = self.metrics
        m.counter("reactor_reacts_total").inc()
        if st.ledger is not None:
            m.gauge("ledger_violations").set(
                st.ledger.total_violations())
        return pg

    def resync_kind(self, target: str,
                    kind: str | None = None) -> dict | None:
        """Rungs 2/3: force a whole-kind rebuild that DIFF-APPLIES
        against the existing ledger rows — the entry is marked cold but
        keeps its verdicts, so a clean resync emits zero events and a
        divergent one emits exactly the true appear/clear delta, never
        a drop-and-replay phantom storm.  Pending snapshot adoptions
        for the kind are discarded: a resync exists precisely because
        adopted state is suspect."""
        from gatekeeper_tpu.enforce.ledger import pages_mode
        if not pages_mode():
            return None
        st = self._state(target)
        if not isinstance(st, JaxTargetState):
            return None
        with self._prep_lock:
            led = st.ledger
            kinds = [kind] if kind is not None else sorted(st.templates)
            for k in kinds:
                if st.ledger_restored:
                    st.ledger_restored.pop(k, None)
                if led is not None:
                    ent = led.entries.get(k)
                    if ent is not None:
                        ent.gen = -1
        # _prep_lock released: react_kind re-acquires it (plain Lock,
        # not reentrant)
        out = self.react_kind(target, kind)
        self.metrics.counter("reactor_resyncs_total").inc()
        return out

    @locked_read
    def devpages_report(self, target: str) -> dict:
        """Per-kind device-residency eligibility for ``probe --pages``:
        kind -> None (device-resident eligible) or the blocking
        reason.  Reflects the live gates — with GATEKEEPER_DEVPAGES
        off the cross-row relaxation is off too, so an inventory-join
        kind reports its host-path reason."""
        from gatekeeper_tpu.enforce.devpages import devpages_mode
        st = self._state(target)
        out: dict[str, str | None] = {}
        if not isinstance(st, JaxTargetState):
            return out
        on = devpages_mode()
        for kind in sorted(st.templates):
            compiled = st.templates[kind]
            reason = self._pages_ineligible(st, kind, compiled)
            if reason is None:
                if not on:
                    reason = "devpages-off"
                elif self.scalar_only:
                    reason = "scalar-only"
                elif compiled.vectorized is None:
                    reason = "not-vectorized"
            out[kind] = reason
        return out

    @locked_read
    def page_of_object(self, target: str, obj: Any) -> int | None:
        """Row page an event object lands in — the reactor's
        coalescing hint.  None when unhandled or not resident."""
        handler = self.targets.get(target)
        if handler is None:
            return None
        try:
            key, _meta, _doc = handler.process_data(obj)
        except Exception:   # noqa: BLE001 — unhandled/malformed event
            return None
        st = self._state(target)
        row = st.table.lookup(key)
        return None if row is None else st.table.page_of(row)

    @locked_read
    def kind_residents(self, target: str, api_version: str,
                       kind: str) -> list[str]:
        """Store keys of every resident row of (apiVersion, kind) — the
        deletion scan for a rung-2 relist (Client.sync_kind)."""
        st = self._state(target)
        table = st.table
        out: list[str] = []
        for key, row in list(table.rows_items()):
            meta = table.meta_at(row)
            if meta is not None and meta.kind == kind \
                    and meta.api_version == api_version:
                out.append(key)
        return out

    def ledger_rv(self, target: str, kind: str) -> int:
        """The kind's adopted/live RV watermark (0 = none recorded) —
        seeds the reactor's first-event staleness check on restart."""
        st = self._state(target)
        if not isinstance(st, JaxTargetState):
            return 0
        led = st.ledger
        if led is not None:
            ent = led.entries.get(kind)
            if ent is not None and ent.rv:
                return int(ent.rv)
        if st.ledger_restored:
            # resource-kind floors from the snapshot's watermark map
            # (reactor streams are keyed by resource kind; the ledger
            # entries below are keyed by constraint kind)
            wm = st.ledger_restored.get("__rv__")
            if isinstance(wm, dict) and kind in wm:
                try:
                    return int(wm[kind] or 0)
                except (TypeError, ValueError):
                    return 0
            payload = st.ledger_restored.get(kind)
            if isinstance(payload, dict):
                try:
                    return int(payload.get("rv", 0) or 0)
                except (TypeError, ValueError):
                    return 0
        return 0

    def _ensure_order(self, st):
        """Sorted-cache-key row order (matches the scalar driver), cached
        per table, remap_generation and key_generation; pure updates
        never re-order.  A key change the table's key log still covers
        (``keys_changed_since``) is merged into the cached sorted keys;
        otherwise, after a wipe or compact, past the log's bound or on
        another table, the keys are sorted from scratch.  Both give the
        same order."""
        table = st.table
        cache = st.order_cache
        if cache is not None and cache.holds(table):
            return cache.ordered_rows, cache.row_order
        remap, kgen = table.remap_generation, table.key_generation
        changed = None
        if cache is not None and cache.table is table \
                and cache.remap == remap:
            changed = table.keys_changed_since(cache.kgen)
        if changed is not None:
            keys, ordered_rows, rows = _merge_sorted_keys(
                cache.keys, cache.ordered_rows, cache.rows, changed,
                table.lookup)
        else:
            # numpy lexicographic sort of the key strings: ~4s of
            # Python tuple-sort at 1M rows becomes ~0.5s
            items = list(table.rows_items())
            key_list = [k for k, _ in items]
            order = np.argsort(np.array(key_list), kind="stable")
            rows = np.fromiter((r for _, r in items),
                               dtype=np.int64, count=len(items))[order]
            keys = [key_list[i] for i in order.tolist()]
            ordered_rows = rows.tolist()
        built = _OrderCache(table, remap, kgen, ordered_rows,
                            _RowOrder(rows), keys, rows,
                            len(changed) if changed is not None else 0)
        st.order_cache = built
        return built.ordered_rows, built.row_order

    def _prefetch_axes(self, st) -> None:
        """Union-prefetch the element-axis extractions: kinds sharing
        an axis (spec.containers for most of the library) pay ONE
        full-table walk, not one per kind — per-kind build_bindings
        then slices the table's superset cache."""
        axis_union: dict[tuple, set] = {}
        for kind in st.templates:
            lowered = st.templates[kind].vectorized
            if lowered is None or not self._kind_constraints(st, kind):
                continue
            abase = dict(lowered.spec.axes)
            for axis, base in lowered.spec.axes:
                axis_union.setdefault(base, set())
            for ec in lowered.spec.e_cols:
                axis_union[abase[ec.axis]].add((ec.rel, ec.mode))
        for base, rels in axis_union.items():
            # only when nothing is cached yet (first build): on churn,
            # delta sweeps never need a full re-extraction (the dirty
            # rows re-extract inside update_bindings), and a full
            # rebuild's first elem_arrays call re-walks the union once
            # by itself (prefetch_elem_arrays carries coverage)
            if base not in st.table._elem_cache:
                st.table.prefetch_elem_arrays(base, sorted(rels))

    def _ext_specs(self, st) -> list[tuple[str, tuple[str, ...]]]:
        """(provider, review-column path) pairs across the target's
        lowered kinds — the sweep-level key-collection scan of the
        two-phase external-data design."""
        specs: list[tuple[str, tuple[str, ...]]] = []
        for kind in sorted(st.templates):
            lowered = st.templates[kind].vectorized
            if lowered is None or not self._kind_constraints(st, kind):
                continue
            for tr in lowered.spec.tables:
                if not tr.ext_providers or not tr.src.startswith("r:val:"):
                    # e-col-keyed lookups are rare; the build-time
                    # prefetch hook still batches them per table build
                    continue
                path = tuple(tr.src[len("r:val:"):].split("."))
                for provider in tr.ext_providers:
                    specs.append((provider, path))
        return specs

    def _prefetch_external(self, st) -> dict | None:
        """Bulk-warm every (provider, distinct key) pair the sweep's
        external-data tables will gather — one batched round per
        provider, overlapped with host prep (the caller submits this to
        the sweep pool; single-flight in the provider cache dedupes
        against the build-time hook racing it).  Returns stats for the
        audit report, or None when there is nothing to do."""
        from gatekeeper_tpu.externaldata.runtime import get_runtime
        rt = get_runtime()
        if rt is None:
            return None
        specs = self._ext_specs(st)
        if not specs:
            return None
        import time as _time
        from gatekeeper_tpu.ir.encode import decode_value
        from gatekeeper_tpu.store.columns import ColSpec
        t0 = _time.perf_counter()
        interner = st.table.interner
        by_provider: dict[str, dict] = {}
        for provider, path in specs:
            want = by_provider.setdefault(provider, {})
            ids = np.unique(st.table.column(ColSpec(path, "val")).ids)
            for uid in ids[ids >= 0].tolist():
                v = decode_value(interner.string(uid))
                if isinstance(v, str):
                    want[v] = True
        n_keys = 0
        for provider, want in by_provider.items():
            n_keys += len(want)
            if want:
                rt.prefetch(provider, list(want))
        return {"providers": len(by_provider), "keys": n_keys,
                "prefetch_s": round(_time.perf_counter() - t0, 6)}

    @staticmethod
    def _external_sweep_stats(ext_fut) -> dict | None:
        """Sweep-report payload: the overlapped bulk-warm's numbers plus
        every provider's breaker state / cache hit ratio / fetch
        timings.  None when no runtime or no provider is configured."""
        from gatekeeper_tpu.externaldata.runtime import get_runtime
        rt = get_runtime()
        if rt is None or not rt.provider_names():
            return None
        bulk = None
        if ext_fut is not None and ext_fut.done():
            try:
                bulk = ext_fut.result()
            except Exception:   # noqa: BLE001 — report-only path
                bulk = None
        out: dict = {"providers": rt.stats()}
        if bulk is not None:
            out["bulk_prefetch"] = bulk
        return out

    @locked_read
    def prefetch_external_for_reviews(self, target: str,
                                      reviews: list[dict]) -> None:
        """Batched external-data warm for an admission micro-batch: one
        fetch round per provider covering every key any review in the
        batch will look up.  Wired ahead of MicroBatcher evaluation so
        fetch latency is paid once per batch — including batches small
        enough to fall back to per-review scalar queries, which would
        otherwise fetch key-by-key."""
        from gatekeeper_tpu.externaldata.runtime import get_runtime
        rt = get_runtime()
        if rt is None:
            return
        st = self._state(target)
        if not isinstance(st, JaxTargetState):
            return
        specs = self._ext_specs(st)
        if not specs:
            return
        from gatekeeper_tpu.store.columns import iter_path
        by_provider: dict[str, dict] = {}
        for provider, path in specs:
            want = by_provider.setdefault(provider, {})
            for rv in reviews:
                obj = rv.get("object") if isinstance(rv, dict) else None
                if not isinstance(obj, dict):
                    continue
                for v in iter_path(obj, path):
                    if isinstance(v, str):
                        want[v] = True
        for provider, want in by_provider.items():
            if want:
                rt.prefetch(provider, list(want))

    @locked
    def put_data_batch(self, target: str, entries) -> None:
        from gatekeeper_tpu.obs.trace import get_tracer
        with get_tracer().span("store.put_batch", cat="store", root=True,
                               rows=len(entries)):
            # the parent method is itself @locked and the RW lock is not
            # reentrant — call its unwrapped body under OUR writer hold
            LocalDriver.put_data_batch.__wrapped__(self, target, entries)
            st = self._state(target)
            # keyed to the BATCH size, not the table size: a steady
            # stream of small watch batches on a large table must not
            # re-run mirror prep under the writer lock on every write
            if isinstance(st, JaxTargetState) \
                    and len(entries) >= MIRROR_EAGER_MIN_ROWS:
                self._materialize_mirror(st)

    def _materialize_mirror(self, st) -> None:
        """Eagerly build what the first audit would otherwise build
        lazily: the shared element-axis extraction and each kind's
        bindings (a columnar store maintains its mirror on write — the
        reference's informer caches do the same on the watch path).
        Executable compiles/reloads are then kicked on a background
        thread: they release the GIL (XLA compile / persistent-cache
        load), so by the time the first sweep dispatches,
        its executables are compiled or in flight."""
        import time as _time
        _t0 = _time.perf_counter()
        self._prefetch_axes(st)
        warm: list[tuple] = []
        with self._prep_lock:
            for kind in sorted(st.templates):
                compiled = st.templates[kind]
                cons = self._kind_constraints(st, kind)
                if compiled.vectorized is None or not cons:
                    continue
                if self.scalar_only or \
                        st.table.n_rows * len(cons) < SMALL_WORKLOAD_EVALS:
                    continue
                bindings = self._kind_bindings(st, kind, compiled, cons)
                # mirror the dispatch-time gate set: kinds with match
                # criteria get a __match__ binding at _install_gates
                with_match = any((c.get("spec") or {}).get("match")
                                 for c in cons)
                warm.append((compiled.vectorized.program, bindings,
                             with_match))
        # the sorted row order + rank gate are table-derived too
        _, row_order = self._ensure_order(st)
        self._row_rank(st, row_order)
        self.metrics.timer("mirror_materialize").observe(
            _time.perf_counter() - _t0)
        if warm and self.executor.mesh is None:
            from gatekeeper_tpu.engine.veval import ProgramExecutor

            def _warm_one(prog, bindings, with_match):
                if self.executor._shutdown.is_set():
                    return
                try:
                    self.executor.prewarm_audit_exec(
                        prog, bindings, DEFAULT_PREWARM_CAP,
                        with_match=with_match)
                    # upload the binding arrays while the GIL is free —
                    # the first dispatch then reuses the per-bindings
                    # device cache instead of paying the host-to-
                    # device transfer inside the sweep
                    self.executor._arrays(bindings, None, None)
                except Exception:
                    pass        # warmup is best-effort
            # a few worker threads over a shared queue: ONE sequential
            # warm thread serializes a 40-kind library's compiles in
            # front of the first audit's single-flight waits (measured
            # 87s library cold), while one-thread-per-kind thrashes the
            # GIL with 40 concurrent traces.  Compile requests overlap
            # ~1.4x through the serialized service; loads more.
            q = list(warm)
            qlock = __import__("threading").Lock()

            def _drain_q():
                while True:
                    with qlock:
                        if not q:
                            return
                        prog, bindings, with_match = q.pop(0)
                    _warm_one(prog, bindings, with_match)
            for _ in range(min(4, len(warm))):
                ProgramExecutor.spawn_bg(_drain_q, "ingest-prewarm")

    def _install_gates(self, st, kind: str, bindings,
                       mask: np.ndarray | None,
                       mask_delta: tuple | None,
                       rank: np.ndarray | None,
                       padded: np.ndarray | None = None) -> None:
        """Attach the padded match mask and rank as regular bindings
        arrays ("__match__", "__rank__") so they ride the same per-name
        device cache + scatter-update path as the columns (the executor
        then needs no separate match/rank plumbing, and the sharded
        path shards them by their declared axes).  `padded` is the
        mask's canonical padded form from _kind_mask, installed without
        any copy; `mask_delta` = (base_buffer, rows) states which buffer
        the dirty rows are relative to — a device scatter is recorded
        ONLY when the bindings' inherited __match__ IS that buffer
        (scalar-sweep interludes advance the mask cache without touching
        the device; syncing the wrong frame would under-approximate)."""
        # NOTE: bindings.arrays / base_dirty are REBOUND (never mutated
        # in place): concurrent readers (RWLock shares queries) may be
        # iterating the old dicts — racing installs produce identical
        # dicts and last-write-wins is benign, mid-iteration mutation
        # would not be.
        d = bindings.__dict__
        if mask is TRIVIAL_MATCH:
            if "__match__" in bindings.arrays:
                # constraints lost their match criteria: drop the stale
                # gate (alive-only gating is exact now)
                bindings.arrays = {k: v for k, v in bindings.arrays.items()
                                   if k != "__match__"}
                d.pop("_match_src", None)
            st.installed_match.pop(kind, None)
            mask = None
        if mask is not None and bindings.arrays.get("__match__") is not padded \
                and d.get("_match_src") is not mask:
            if padded is None or \
                    padded.shape != (bindings.c_pad, bindings.r_pad):
                padded = np.zeros((bindings.c_pad, bindings.r_pad),
                                  dtype=bool)
                padded[: mask.shape[0], : mask.shape[1]] = mask
            old = bindings.arrays.get("__match__")
            bindings.arrays = {**bindings.arrays, "__match__": padded}
            d["_match_src"] = mask
            st.installed_match[kind] = padded
            if bindings.base is not None and mask_delta is not None \
                    and old is not None and old is mask_delta[0] \
                    and old.shape == padded.shape:
                bindings.base_dirty = {**bindings.base_dirty,
                                       "__match__": mask_delta[1]}
        if rank is not None and d.get("_rank_src") is not rank:
            from gatekeeper_tpu.engine.veval import pad_rank
            bindings.arrays = {**bindings.arrays,
                               "__rank__": pad_rank(rank, bindings.r_pad)}
            d["_rank_src"] = rank

    def _audit_dedup_plan(self, st, target: str):
        """The cross-template predicate dedup plan for the currently
        installed set, or None.  Caller holds ``_prep_lock``.  The plan
        is a pure function of the installed set — it is memoized by the
        set digest (churn invalidates by key mismatch) and persisted to
        the warm-restart snapshot tier, so a restarted pod loads it
        instead of re-running the whole-policy-set analysis."""
        try:
            from gatekeeper_tpu.analysis.policyset import build_dedup_plan
            dkinds = {}
            for k in st.templates:
                cons = self._kind_constraints(st, k)
                if st.templates[k].vectorized is not None and cons:
                    dkinds[k] = (st.templates[k].vectorized, cons)
            if not dkinds:
                return None
            import json as _json
            from gatekeeper_tpu.resilience import snapshot as _snap
            parts = [
                f"{k}|"
                f"{_snap.template_digest(k, target, st.templates[k].source)}|"
                + _json.dumps(cons, sort_keys=True, default=str)
                for k, (_, cons) in dkinds.items()]
            pdigest = _snap.policyset_digest(parts)
            memo = self._dedup_plan_memo.get(target)
            if memo is not None and memo[0] == pdigest:
                return memo[1]
            hit = _snap.load_dedup_plan(pdigest)
            if hit is not None:
                plan = hit[0]
            else:
                plan = build_dedup_plan(dkinds)
                _snap.save_dedup_plan(pdigest, plan)
            self._dedup_plan_memo[target] = (pdigest, plan)
            # plan changed: drop cross-sweep shared columns whose
            # digest is no longer in the live group set
            live = set(plan.groups)
            for d in list(st.dedup_shared_cache):
                if d not in live:
                    del st.dedup_shared_cache[d]
            return plan
        except Exception:
            # dedup is an optimization; the original programs are
            # always a valid fallback
            return None

    @locked_read
    def prepare_audit(self, target: str) -> bool:
        """Pre-build the serving structures a full audit sweep needs —
        today the cross-template dedup plan — so a (re)started pod pays
        that cost at startup, before declaring itself ready, instead of
        inside its first sweep.  Warm restarts load the plan from the
        snapshot tier; cold starts run the analysis here.  Returns True
        when a plan is ready (False: scalar-only, dedup off, or nothing
        lowered — the sweep then runs without a plan, as always)."""
        st = self.state.get(target)
        if st is None or self.scalar_only:
            return False
        # Stage-7: AOT-compile the certified signatures of the current
        # geometry before declaring ready (warm restarts skip via the
        # cs-tier geometry stamp — zero startup compiles)
        self._precompile(st, target)
        if os.environ.get("GATEKEEPER_DEDUP", "on") == "off":
            return False
        with self._prep_lock:
            return self._audit_dedup_plan(st, target) is not None

    @locked_read
    def precompile(self, target: str) -> int:
        """AOT-lower and compile every Stage-7-certified signature of
        the target's current geometry (the install/warm-restart seam,
        also reached through :meth:`prepare_audit`).  Returns the
        number of AOT compiles issued — 0 on a warm restart whose
        geometry stamp is already in the cs snapshot tier."""
        st = self.state.get(target)
        if st is None:
            return 0
        return self._precompile(st, target)

    def _precompile(self, st, target: str) -> int:
        from gatekeeper_tpu.analysis import compilesurface
        if compilesurface.mode() == "off" or self.scalar_only \
                or not isinstance(st, JaxTargetState) \
                or self.executor.mesh is not None:
            return 0
        entries: list[tuple] = []
        with self._prep_lock:
            for kind in sorted(st.templates):
                compiled = st.templates[kind]
                cert = st.compilesurfaces.get(kind)
                cons = self._kind_constraints(st, kind)
                if compiled.vectorized is None or not cons:
                    continue
                if cert is None or not cert.bounded \
                        or getattr(cert, "scalar_pin", False):
                    continue
                try:
                    bindings = self._kind_bindings(st, kind, compiled,
                                                   cons)
                except Exception:   # noqa: BLE001 — prewarm is an
                    continue        # optimization, never a gate
                # mirror the dispatch-time gate set (_install_gates):
                # kinds with match criteria get a __match__ binding
                with_match = any((c.get("spec") or {}).get("match")
                                 for c in cons)
                entries.append((kind, cert.digest,
                                compiled.vectorized.program, bindings,
                                with_match))
        if not entries:
            return 0
        import hashlib as _hashlib
        from gatekeeper_tpu.resilience import snapshot as _snap
        geom = sorted((kind, dg, b.c_pad, b.r_pad, wm)
                      for kind, dg, _p, b, wm in entries)
        stamp = _hashlib.sha256(repr(geom).encode()).hexdigest()
        if _snap.load_compilesurface(f"aot:{target}:{stamp}") is not None:
            # warm restart at the same certified geometry: zero AOT
            # compiles here — first dispatches reload their executables
            # through the persistent compile cache instead of paying a
            # startup compile storm
            return 0
        n = 0
        for _kind, _dg, prog, bindings, wm in entries:
            try:
                self.executor.prewarm_audit_exec(
                    prog, bindings, DEFAULT_PREWARM_CAP, with_match=wm)
                compilesurface.precompiles_run += 1
                n += 1
            except Exception:   # noqa: BLE001 — best-effort
                continue
        self.metrics.counter("compile_surface_precompiles").inc(n)
        _snap.save_compilesurface(f"aot:{target}:{stamp}",
                                  {"target": target, "n": n})
        return n

    def certified_review_rungs(self, target: str,
                               max_n: int | None = None
                               ) -> list[int] | None:
        """Batch sizes whose padded review signature is inside the
        Stage-7 certified surface — the rungs the micro-batcher's
        ``_fit_to_deadline`` may shrink along.  Review mini-tables pad
        to ``bucket(B)`` (minimum 8), so the rungs are 1 plus the
        power-of-two ladder up to the rows cap.  None when the stage is
        off, nothing is certified yet, or any installed template's
        surface is unbounded (the batcher then falls back to blind
        halving)."""
        from gatekeeper_tpu.analysis import compilesurface
        from gatekeeper_tpu.ir import prep as _prep
        if compilesurface.mode() == "off":
            return None
        st = self.state.get(target)
        if not isinstance(st, JaxTargetState):
            return None
        certs = [st.compilesurfaces.get(k) for k in st.templates]
        have = [c for c in certs if c is not None]
        if not have or any(not c.bounded for c in have):
            return None
        rungs = [1] + list(_prep.bucket_ladder(
            8, compilesurface._cap("r")))
        if max_n is not None:
            rungs = [r for r in rungs if r <= max_n] or [1]
        cap = self.memsurface_review_cap(target)
        if cap is not None:
            rungs = [r for r in rungs if r <= cap] or [1]
        return rungs

    @locked_read
    def memsurface_review_cap(self, target: str) -> int | None:
        """Stage-8 consumer 2: the largest certified review-batch rung
        whose worst per-kind dispatch footprint fits the HBM budget
        left after the installed set's certified resident arrays.  A
        review batch pads its mini-table to ``bucket(B)`` rows and
        dispatches one kind at a time, so the in-flight claim is the
        max (not sum) over installed kinds of the peak at that row
        geometry.  None when the stage is off or nothing is certified
        (the batcher then caps only by the Stage-7 rung ladder)."""
        from gatekeeper_tpu.analysis import memsurface
        from gatekeeper_tpu.ir import prep as _prep
        if memsurface.mode() == "off":
            return None
        st = self.state.get(target)
        if not isinstance(st, JaxTargetState):
            return None
        certs = [c for c in st.memsurfaces.values()
                 if isinstance(c, memsurface.MemorySurface)
                 and not c.scalar_pin]
        if not certs:
            return None
        remaining = memsurface.budget_bytes() - sum(
            c.resident_bytes(memsurface.cap_dims()) for c in certs)
        if remaining <= 0:
            return 1
        rungs = [1] + list(_prep.bucket_ladder(
            8, memsurface._cap("r")))
        best = 1
        for rung in rungs:
            dims = memsurface.cap_dims()
            dims["r"] = _prep.bucket(max(rung, 1))
            claim = max(c.peak_bytes(dims, devpages=False)
                        for c in certs)
            if claim <= remaining:
                best = rung
            else:
                break
        return best

    def memsurface_sweep_order(self, st, kinds: list[str]) -> list[str]:
        """Stage-8 consumer 3: order full-sweep kind dispatch so
        concurrent in-flight footprints stay under budget.  JAX
        dispatch is async — while kind i's program drains, kind i+1's
        uploads and intermediates are already materializing, so the
        transient claim of *adjacent* kinds coexists.  Weaving the
        certified-peak order (largest, smallest, second-largest, ...)
        minimizes the worst adjacent-pair sum without changing the
        result: phase-2 formatting re-sorts tagged results into a
        total order, so any dispatch permutation is parity-safe on
        the full path.  Falls back to sorted order when the stage is
        off or any kind lacks a certificate (determinism over
        cleverness)."""
        from gatekeeper_tpu.analysis import memsurface
        base = sorted(kinds)
        if memsurface.mode() == "off" or len(base) < 3:
            return base
        peaks = {}
        for k in base:
            cert = st.memsurfaces.get(k)
            if not isinstance(cert, memsurface.MemorySurface):
                return base
            peaks[k] = 0 if cert.scalar_pin else cert.peak_bytes()
        ranked = sorted(base, key=lambda k: (-peaks[k], k))
        woven: list[str] = []
        lo, hi = 0, len(ranked) - 1
        while lo <= hi:
            woven.append(ranked[lo])
            if lo != hi:
                woven.append(ranked[hi])
            lo += 1
            hi -= 1
        self.metrics.counter("memsurface_sweep_reorders").inc()
        return woven

    def _shared_col(self, st, plan, kind: str, digest: str, bindings):
        """One shared conjunct's host column, page-partitioned ACROSS
        sweeps: a geometry-stable cache hit re-evaluates only the rows
        the store dirtied since the cached generation and splices them
        into a COPY (the previous sweep's bindings may still reference
        the cached array).  Sound because shared subtrees are
        row-local by construction (_SHAREABLE_OPS: own columns +
        digest-stable interner tables; the interner is append-only, so
        an unchanged row's ids resolve identically) — a changed row is
        always in dirty_rows_since.  Eviction is the key itself: a
        constraint-set change changes the digest, a remap or resize
        misses the guards."""
        from gatekeeper_tpu.analysis.policyset import eval_shared_host
        g = plan.groups[digest]
        member = g.members[kind]
        table = st.table
        want_shape = (bindings.r_pad, bindings.e_pads.get(g.axis)) \
            if g.ekind == "e" else (bindings.r_pad,)
        hit = st.dedup_shared_cache.get(digest)
        if hit is not None:
            c_gen, c_remap, c_col = hit
            if c_remap == table.remap_generation \
                    and c_col.shape == want_shape:
                if c_gen == table.generation:
                    return c_col
                dirty = table.dirty_rows_since(c_gen)
                if len(dirty) <= max(64, table.n_rows // 4):
                    sub = eval_shared_host(
                        plan.originals[kind], member.node_idx,
                        bindings.arrays, g.ekind, rows=dirty)
                    col = c_col.copy()
                    col[dirty] = sub
                    st.dedup_shared_cache[digest] = (
                        table.generation, table.remap_generation, col)
                    self.metrics.counter(
                        "dedup_shared_delta_evals").inc()
                    return col
        col = eval_shared_host(plan.originals[kind], member.node_idx,
                               bindings.arrays, g.ekind)
        st.dedup_shared_cache[digest] = (
            table.generation, table.remap_generation, col)
        return col

    def _apply_dedup(self, st, plan, kind: str, bindings,
                     shared_cols: dict, applied: dict):
        """Swap one kind's program for its dedup rewrite
        (analysis/policyset.py), injecting the shared predicate columns
        as plain bool bindings.  The column for a digest is computed
        ONCE per sweep — on the host, from the first member kind's
        bound arrays (the numpy twin of the device evaluator) — and
        handed to every member; member kinds bind identical arrays for
        identical canonical inputs (same inventory, same interner, same
        row bucket), which the shape guard re-checks per kind.  Across
        sweeps the per-digest column is cached and churn re-evals only
        dirty rows (_shared_col), so shared-conjunct host-eval is
        O(dirty), not O(rows/sweep).  Any mismatch or twin failure
        keeps the kind on its original program.  Returns the rewritten
        Program or None."""
        add: dict = {}
        try:
            for digest in plan.kind_digests[kind]:
                g = plan.groups[digest]
                col = shared_cols.get(digest)
                if col is None:
                    col = self._shared_col(st, plan, kind, digest,
                                           bindings)
                    shared_cols[digest] = col
                if g.ekind == "e":
                    if col.shape != (bindings.r_pad,
                                     bindings.e_pads.get(g.axis)):
                        return None
                elif col.shape != (bindings.r_pad,):
                    return None
                add[g.binding] = col
        except Exception:
            return None     # dedup is an optimization, never a failure
        # rebind, never mutate (see the _install_gates NOTE): readers
        # may hold the previous arrays dict
        bindings.arrays = {**bindings.arrays, **add}
        for digest in plan.kind_digests[kind]:
            applied[digest] = applied.get(digest, 0) \
                + plan.groups[digest].members[kind].sites
        return plan.rewritten[kind]

    @staticmethod
    def _twin_bindings_equal(a, b) -> bool:
        """True when two kinds' bound arrays are bit-identical — same
        names, shapes, dtypes, contents.  Shared dedup columns are the
        same objects in both dicts, so identity short-circuits the
        common case; everything else pays one host memcmp."""
        if a is None or b is None:
            return False
        if a.c_pad != b.c_pad or a.r_pad != b.r_pad:
            return False
        if set(a.arrays) != set(b.arrays):
            return False
        for name, x in a.arrays.items():
            y = b.arrays[name]
            if x is y:
                continue
            try:
                xa, ya = np.asarray(x), np.asarray(y)
            except Exception:
                return False
            if xa.shape != ya.shape or xa.dtype != ya.dtype \
                    or not np.array_equal(xa, ya):
                return False
        return True

    def _twin_future(self, twin_src: dict, mode: str, kind: str,
                     prog, bindings, specs: list, futures: list):
        """Whole-kind dispatch sharing for what-if (shadow) sweeps.

        A shadow install stages the candidate set's kinds beside the
        live set under mangled names (analysis/policyset.shadow_kind).
        For every template the candidate did NOT change, the shadow
        twin lowers to the same program (cache keys match — kind names
        never reach the IR) over bit-identical bound arrays, so its
        device dispatch would recompute the live kind's payload
        exactly.  This seam detects that case after gate install and
        dedup rewrite, and aliases the shadow kind to the live twin's
        in-flight future instead of dispatching — the combined
        live+shadow sweep then pays device time only for kinds the
        candidate actually changed.  Handles resolve idempotently
        (PendingTopK/PendingMask.get is a pure D2H read), so both
        slots format from the one payload.  Each alias gets a fresh
        chained Future: phase 2 keys its completion map by future
        object, and a shared object would collapse two slots into one.

        Live (unmangled) kinds register; shadow kinds return a chained
        Future when their twin matches, else None (normal dispatch).
        Any comparison failure falls back to dispatching — sharing is
        an optimization, never a correctness dependency."""
        from gatekeeper_tpu.analysis.policyset import split_shadow_kind
        base, tag = split_shadow_kind(kind)
        if tag is None:
            twin_src[(kind, mode)] = len(futures)
            return None
        si = twin_src.get((base, mode))
        if si is None:
            return None
        src_fut = futures[si]
        if src_fut is None:
            return None
        s_prog, s_bind = specs[si][4], specs[si][5]
        try:
            if s_prog is None or s_prog.cache_key() != prog.cache_key():
                return None
        except Exception:
            return None
        if not self._twin_bindings_equal(s_bind, bindings):
            return None
        import concurrent.futures
        self.metrics.counter("whatif_twin_dispatches_shared").inc()
        out: "concurrent.futures.Future" = concurrent.futures.Future()

        def _chain(src, out=out):
            exc = src.exception()
            if exc is not None:
                out.set_exception(exc)
            else:
                out.set_result(src.result())

        src_fut.add_done_callback(_chain)
        return out

    # ------------------------------------------------------------------

    @locked_read
    def query_audit(self, target: str,
                    opts: QueryOpts | None = None) -> tuple[list[Result], str | None]:
        import time as _time
        _t0 = _time.perf_counter()
        st = self._state(target)
        if not isinstance(st, JaxTargetState):
            return super().query_audit(target, opts)
        handler = self.targets[target]
        tracing = opts.tracing if opts is not None else self.default_tracing
        limit = opts.limit_per_constraint if opts is not None else None
        full = opts.full if opts is not None else False
        trace: list | None = [] if tracing else None

        if full:
            # Forced full sweep: drop every layer of sweep memoization
            # for this target.  Rebind (never .clear()) so concurrent
            # readers keep the dicts they already hold.  Fresh Bindings
            # built after this carry no per-executor device caches and
            # no persistent violation masks, so host prep, H2D upload,
            # and device evaluation all genuinely re-run; fmt_cache goes
            # too, so every violating pair re-formats through the scalar
            # oracle.  rank/order caches stay — they derive from the
            # table (row keys), not from any evaluation.
            with self._prep_lock:
                st.mask_cache = {}
                st.bindings_cache = {}
                st.bindings_retired = {}
                st.installed_match = {}
                st.fmt_cache = {}

        m = self.metrics
        # sweep root span, entered manually so the 300-line pipeline
        # body below keeps its indentation; closed in the finally.
        # Child spans on pool threads parent via _sweep_ctx (context
        # vars don't flow into pre-existing worker threads).
        from gatekeeper_tpu.obs.trace import get_tracer as _get_tracer
        _tracer = _get_tracer()
        _sweep_cm = _tracer.span("audit.sweep", cat="audit", target=target,
                                 full=full)
        _sweep_sp = _sweep_cm.__enter__()
        _sweep_ctx = _tracer.current()
        self.executor.sweep_active.set()
        try:
            # row ordering matches the scalar driver (sorted cache keys)
            # so both drivers return identical result lists; the order
            # is keyed on key_generation — pure updates never re-order,
            # a create or delete merges its key in (``merged`` keys) or
            # re-sorts
            resorted = st.order_cache is None or \
                not st.order_cache.holds(st.table)
            with _tracer.span("audit.order", cat="prep", parent=_sweep_ctx,
                              resorted=resorted) as _osp:
                ordered_rows, row_order = self._ensure_order(st)
                rank = self._row_rank(st, row_order)
                if _osp is not None:
                    _osp.args["merged"] = \
                        st.order_cache.merged if resorted else 0
            for _sp in (_sweep_sp, _osp):
                if _sp is not None:
                    _sp.args["rows"] = len(ordered_rows)

            # phase 1: dispatch every kind's device evaluation without
            # blocking — one packed-fetch round-trip per kind, all in
            # flight at once (run_topk_async; the latency of fetch N
            # overlaps the execution of fetch N+1).  Dispatches run on a
            # thread pool so first-time jit traces / XLA compiles of
            # different kinds overlap (a 30-template library would
            # otherwise pay its compiles serially on a cold start).
            import threading as _threading
            # full-sweep pipeline phase accumulators: host_prep on the
            # sweep thread, h2d/device on whichever pool worker runs the
            # kind (hence the lock).  Their SUM exceeding the pipeline
            # wall is the overlap the pipeline buys.
            ph = {"host_prep_s": 0.0, "h2d_s": 0.0, "device_s": 0.0,
                  "h2d_bytes": 0}
            # per-kind measured device block seconds (full sweeps) —
            # ground truth for the attribution drift report
            per_kind_dev: dict[str, float] = {}
            ph_lock = _threading.Lock()

            def _launch(mode, prog, bindings):
                if mode == "topk":
                    return self.executor.run_topk_async(prog, bindings, limit)
                return self.executor.run_async(prog, bindings)

            def dispatch(spec):
                mode, kind, _, _, prog, bindings, mask = spec
                # match/rank gates ride bindings.arrays (_install_gates)
                if mode not in ("topk", "mask"):
                    return None
                ex = self.executor
                if not full:
                    with _tracer.span("device.dispatch", cat="device",
                                      parent=_sweep_ctx, kind=kind,
                                      mode=mode) as dsp:
                        b0 = ex.thread_h2d_bytes()
                        h = _launch(mode, prog, bindings)
                        if dsp is not None:
                            dsp.args["h2d_bytes"] = \
                                ex.thread_h2d_bytes() - b0
                        return h
                # full sweep: meter the two device-side pipeline stages
                # where they run (concurrently across kinds).
                # stage_uploads enqueues this kind's H2D transfers as
                # its own stage — the _arrays call inside run_*_async
                # then hits the device cache — and block() rides until
                # the result is device-resident, so kind.block is host
                # wall time until the device holds the result, not the
                # host fetch (the D2H copy stays async and is collected
                # in phase 2, under kind.fetch).
                t0 = _time.perf_counter()
                with _tracer.span("kind.h2d", cat="h2d", parent=_sweep_ctx,
                                  kind=kind) as hsp:
                    b0 = ex.thread_h2d_bytes()
                    ex.stage_uploads(bindings)
                    staged = ex.thread_h2d_bytes() - b0
                    if hsp is not None:
                        hsp.args["h2d_bytes"] = staged
                t1 = _time.perf_counter()
                with _tracer.span("kind.block", cat="device",
                                  parent=_sweep_ctx, kind=kind, mode=mode):
                    h = _launch(mode, prog, bindings).block()
                t2 = _time.perf_counter()
                with ph_lock:
                    ph["h2d_s"] += t1 - t0
                    ph["device_s"] += t2 - t1
                    ph["h2d_bytes"] += staged
                    per_kind_dev[kind] = \
                        per_kind_dev.get(kind, 0.0) + (t2 - t1)
                return h

            # (t0, span, table evaluations so far) of the host-prep
            # region open on the sweep thread
            from gatekeeper_tpu.ir.prep import TABLE_VALUES as _tv
            _prep = None

            def _prep_begin(kind):
                nonlocal _prep
                _prep = (_time.perf_counter(), _tracer.begin(
                    "kind.host_prep", cat="host_prep", parent=_sweep_ctx,
                    kind=kind), _tv.batched, _tv.host)

            def _prep_done():
                # close it: meter it into the pipeline phase sum, record
                # the kind's table evaluations by path, end the span
                nonlocal _prep
                t0, sp, b0, h0 = _prep
                _prep = None
                if sp is not None:
                    sp.args["table_values_batched"] = _tv.batched - b0
                    sp.args["table_values_host"] = _tv.host - h0
                _tracer.end(sp)
                ph["host_prep_s"] += _time.perf_counter() - t0

            # prep + dispatch interleaved: each kind's device step is
            # submitted the moment its bindings are ready, so kind N's
            # device execution (and any cold compile, on the pool) overlaps
            # kind N+1's host prep — on churned sweeps the host delta work
            # hides most of the device time instead of serializing before it
            import concurrent.futures
            pool = concurrent.futures.ThreadPoolExecutor(max_workers=8)
            specs: list[tuple] = []
            futures: list = []
            # bulk external-data warm, overlapped with host prep: by the
            # time a kind's build loop asks for a key it is a cache hit
            # (or a single-flight wait on this very fetch)
            def _ext_prefetch():
                with _tracer.span("external.prefetch", cat="external",
                                  parent=_sweep_ctx):
                    return self._prefetch_external(st)
            ext_fut = pool.submit(_ext_prefetch)
            # cross-host collective ordering: on a mesh spanning
            # processes, collective launches must happen in the SAME
            # order on every process (see veval._COLLECTIVE_EXEC_LOCK
            # scope note).  The kind loop below is sorted, so inline
            # dispatch from this one thread is deterministic; the
            # threaded pool (whose completion order is not) stays for
            # single-process meshes where only mutual exclusion matters.
            from gatekeeper_tpu.engine.veval import mesh_spans_processes
            ordered_dispatch = mesh_spans_processes(self.executor.mesh)
            if limit is not None and not self.scalar_only \
                    and self.executor.mesh is None:
                # the shared top-k reduce executable's shape bucket is known
                # before any prep — compile it concurrently with host prep
                # (its XLA compile is the longest pole of a cold audit)
                from gatekeeper_tpu.ir.prep import audit_pads
                n_rows = st.table.n_rows
                pads = set()
                for kind in st.templates:
                    n_con = len(st.constraints.get(kind, {}))
                    if not n_con or n_rows * n_con < SMALL_WORKLOAD_EVALS:
                        continue
                    pads.add(audit_pads(n_rows, n_con))
                # dedupe by bucket: kinds overwhelmingly share one shape,
                # and duplicate submissions would park pool workers on the
                # single-flight wait, starving the dispatch futures
                for r_pad, c_pad in pads:
                    pool.submit(self.executor.prewarm_reduce, limit, c_pad,
                                r_pad)
            # cross-template predicate dedup (analysis/policyset.py):
            # full sweeps only — the plan is a pure function of the
            # installed set, rebuilt each time (milliseconds), so there
            # is no cached plan to go stale under template churn.
            # GATEKEEPER_DEDUP=off is the parity oracle's kill switch.
            dedup_plan = None
            dedup_shared_cols: dict = {}
            dedup_applied: dict = {}
            dedup_host_s = 0.0
            # footprint-driven selective invalidation (analysis/
            # footprint.py): a non-full sweep replays a kind's cached
            # device payload when no dirty column path intersects its
            # validated read-set (_selective_reuse).
            # GATEKEEPER_FOOTPRINT=off is the bit-identical oracle.
            from gatekeeper_tpu.analysis.footprint import mode as _fp_mode
            fp_enabled = not self.scalar_only and _fp_mode() != "off"
            fp_skipped: list[str] = []
            fp_saved = 0
            # continuous enforcement (enforce/): eligible kinds skip
            # the per-kind device sweep entirely — only dirty pages ×
            # affected constraints re-evaluate, and capped results are
            # served from the VerdictLedger's confirmed violation set.
            # GATEKEEPER_PAGES=off is the bit-identical oracle (the
            # legacy path below, including footprint selective reuse).
            from gatekeeper_tpu.enforce.ledger import pages_mode as _pg_mode
            pg_on = _pg_mode()
            pg_kinds: list[str] = []
            pg_fallback: dict[str, str] = {}
            pg_stats = {"pages_evaluated": 0, "pages_skipped": 0,
                        "rows_padded": 0, "rows_reevaluated": 0,
                        "evaluations_saved": 0, "widen_fallbacks": 0,
                        "full_builds": 0, "events": 0}
            pg_dirty_pages: set[int] = set()
            from gatekeeper_tpu.enforce.devpages import (
                devpages_mode as _dv_mode, fresh_stats as _dv_fresh)
            dv_on = pg_on and _dv_mode() and not self.scalar_only
            dv_stats = _dv_fresh() if dv_on else None
            # what-if twin sharing (whatif/shadow.py): when shadow
            # kinds are staged, an unchanged twin aliases the live
            # kind's dispatch instead of re-running it on device.
            # GATEKEEPER_WHATIF_SHARE=off is the parity oracle.
            _twin_src: dict | None = None
            twin_shared: list[str] = []
            if full and not self.scalar_only and \
                    os.environ.get("GATEKEEPER_WHATIF_SHARE", "on") != "off":
                from gatekeeper_tpu.analysis.policyset import is_shadow_kind
                if any(is_shadow_kind(k) for k in st.templates):
                    _twin_src = {}
            # Stage-6 plan gating (analysis/shardplan.py): on a mesh,
            # a kind's bindings shard only when its partition plan
            # certifies eligibility; uncertified/ineligible kinds pin
            # to the replicated (single-device) path.
            # GATEKEEPER_SHARDPLAN=off is the oracle: everything
            # shards exactly as before this stage.
            from gatekeeper_tpu.analysis.shardplan import mode as _sp_mode
            sp_gate = self.executor.mesh is not None and \
                _sp_mode() != "off"
            sp_sharded: list[str] = []
            sp_replicated: list[str] = []
            sp_evals = 0
            sp_collectives = 0
            # kind -> why it ran on the scalar oracle this sweep
            scalar_why: dict[str, str] = {}
            _t_pipe = _time.perf_counter()
            try:
                with self._prep_lock:
                    _prep_begin("__axes_and_plan__")
                    self._prefetch_axes(st)
                    if full and not self.scalar_only and \
                            os.environ.get("GATEKEEPER_DEDUP", "on") != "off":
                        dedup_plan = self._audit_dedup_plan(st, target)
                    _prep_done()
                    _sweep_kinds = sorted(st.templates)
                    if full and trace is None and not self.scalar_only:
                        # Stage-8 consumer 3: dispatch order packs
                        # adjacent in-flight footprints under budget;
                        # parity-safe here because phase 2 re-sorts
                        # tagged results into a total order (pages/
                        # ledger kinds only occur when not full)
                        _sweep_kinds = self.memsurface_sweep_order(
                            st, _sweep_kinds)
                    for _kind_i, kind in enumerate(_sweep_kinds):
                        # fault injection: kill the backend mid-sweep
                        # (after the first kind when there are several)
                        # — the scalar_only property re-consults the
                        # supervisor below, so the remaining kinds
                        # route through the scalar oracle and the
                        # sweep completes with correct verdicts
                        if _kind_i > 0 or len(_sweep_kinds) == 1:
                            from gatekeeper_tpu.resilience import \
                                faults as _faults
                            if _faults.take("device_lost"):
                                self.supervisor.report_failure(
                                    "fault injection: device_lost "
                                    "mid-sweep")
                        compiled = st.templates[kind]
                        constraints = self._kind_constraints(st, kind)
                        if not constraints:
                            continue
                        _prep_begin(kind)
                        if pg_on and not full and trace is None:
                            reason = self._pages_ineligible(st, kind,
                                                            compiled)
                            if reason is None:
                                # no device dispatch: the paged serve
                                # runs in phase 2 on the sweep thread
                                # (futures=None kinds format first, in
                                # sorted-kind order — deterministic
                                # ledger event order)
                                spec = ("pages", kind, compiled,
                                        constraints, None, None, None)
                                _prep_done()
                                futures.append(None)
                                specs.append(spec)
                                pg_kinds.append(kind)
                                continue
                            pg_fallback[kind] = reason
                        if fp_enabled and not full and trace is None:
                            reuse = self._selective_reuse(
                                st, kind, compiled, constraints, limit)
                            if reuse is not None:
                                ent, bindings = reuse
                                spec = (ent["mode"], kind, compiled,
                                        constraints, ent["prog"], bindings,
                                        ent["mask"])
                                _prep_done()
                                f = concurrent.futures.Future()
                                f.set_result(_ResolvedHandle(ent["payload"]))
                                futures.append(f)
                                specs.append(spec)
                                fp_skipped.append(kind)
                                fp_saved += len(ordered_rows) \
                                    * len(constraints)
                                continue
                        mask, mask_dirty, padded = self._kind_mask(
                            st, target, kind, constraints)
                        small = self.scalar_only or \
                            len(ordered_rows) * len(constraints) \
                            < SMALL_WORKLOAD_EVALS
                        if compiled.vectorized is not None and mask is not None \
                                and not small:
                            try:
                                bindings = self._kind_bindings(
                                    st, kind, compiled, constraints)
                            except ExternalDataError:
                                # failurePolicy Fail during this kind's
                                # table build: contained per kind — its
                                # violations are unknown this sweep, every
                                # other template is unaffected
                                self.metrics.counter(
                                    "external_data_kind_failures").inc()
                                _prep_done()
                                continue
                            if bindings.f32_unsafe:
                                # some bound numeric value does not survive a
                                # float32 round-trip (|v| past 2^24): device
                                # ordering compares could silently mis-order,
                                # so this kind runs on the scalar oracle
                                # (ir/lower.py "known deviations" guard)
                                self.metrics.counter(
                                    "f32_unsafe_scalar_fallbacks").inc()
                                scalar_why[kind] = "f32 pin"
                                spec = ("scalar", kind, compiled, constraints,
                                        None, None, mask)
                                _prep_done()
                                futures.append(None)
                                specs.append(spec)
                                continue
                            if self.executor.mesh is not None:
                                plan = st.shardplans.get(kind)
                                if sp_gate:
                                    self.executor.set_sharding_allowed(
                                        bindings,
                                        plan is not None and
                                        getattr(plan, "eligible", False))
                                if self.executor._sharded_for(bindings):
                                    sp_sharded.append(kind)
                                    _ms = self.executor.mesh.shape
                                    sp_evals += bindings.c_pad * \
                                        bindings.r_pad // \
                                        (_ms["c"] * _ms["r"])
                                    if plan is not None:
                                        sp_collectives += len(
                                            getattr(plan, "collectives",
                                                    ()))
                                else:
                                    sp_replicated.append(kind)
                            self._install_gates(st, kind, bindings, mask,
                                                mask_dirty, rank, padded)
                            prog = compiled.vectorized.program
                            if dedup_plan is not None and \
                                    kind in dedup_plan.rewritten:
                                _t_dd = _time.perf_counter()
                                with _tracer.span("dedup.host_eval",
                                                  cat="dedup",
                                                  parent=_sweep_ctx,
                                                  kind=kind):
                                    prog2 = self._apply_dedup(
                                        st, dedup_plan, kind, bindings,
                                        dedup_shared_cols, dedup_applied)
                                if prog2 is not None:
                                    prog = prog2
                                dedup_host_s += _time.perf_counter() - _t_dd
                            mode = "topk" if limit is not None else "mask"
                            spec = (mode, kind, compiled, constraints, prog,
                                    bindings, mask)
                            _prep_done()
                            if _twin_src is not None:
                                tf = self._twin_future(
                                    _twin_src, mode, kind, prog, bindings,
                                    specs, futures)
                                if tf is not None:
                                    twin_shared.append(kind)
                                    futures.append(tf)
                                    specs.append(spec)
                                    continue
                            if ordered_dispatch:
                                f = concurrent.futures.Future()
                                try:
                                    f.set_result(dispatch(spec))
                                except Exception as e:  # noqa: BLE001
                                    f.set_exception(e)
                                futures.append(f)
                            else:
                                futures.append(pool.submit(dispatch, spec))
                        else:
                            # unlowerable template — or a workload too small
                            # to amortize a device dispatch round-trip
                            if compiled.vectorized is None:
                                scalar_why[kind] = st.scalar_pins.get(
                                    kind, "not lowered")
                            elif self.scalar_only:
                                scalar_why[kind] = "backend unavailable"
                            elif mask is None:
                                scalar_why[kind] = "no match engine"
                            else:
                                scalar_why[kind] = "below SMALL_WORKLOAD_EVALS"
                            spec = ("scalar", kind, compiled, constraints, None,
                                    None, mask)
                            _prep_done()
                            futures.append(None)
                        specs.append(spec)

                # phase 2: resolve handles and host-format per kind.  The
                # tag key (row rank, kind, constraint name) is a total
                # order, so the tagged sort below restores output order no
                # matter which kind formats first — which lets a pipelined
                # sweep format each kind the moment its handle completes,
                # overlapping host formatting of finished kinds with
                # device compute of kinds still in flight.  Tracing is
                # append-order-sensitive, so it keeps sorted-kind order.
                # One (review, frozen) per violating row for the whole
                # sweep — rows recur across kinds/constraints, and
                # freeze() is a deep walk.
                rcache: dict[int, tuple] = {}
                tagged: list[tuple[tuple, Result]] = []
                fmt_s = 0.0
                fetch_s = 0.0

                misses = m.counter("format_memo_misses")

                def _format_kind(spec, handle):
                    # kind.format: the fetch, then the capped rows
                    # formatted; ``rows`` counts the (constraint, row)
                    # pairs the scalar oracle re-evaluated (format-memo
                    # misses; the sweep thread is the one formatting),
                    # ``fn_memo_hits``/``fn_memo_misses`` the pure
                    # function calls the per-review memo served / ran
                    nonlocal fmt_s
                    n0 = misses.value
                    h0, f0 = spec[2].fn_memo_counts()
                    _tf = _time.perf_counter()
                    with _tracer.span("kind.format", cat="format",
                                      parent=_sweep_ctx, kind=spec[1],
                                      mode=spec[0]) as fsp:
                        _format_payload(spec, handle)
                        if fsp is not None:
                            h1, f1 = spec[2].fn_memo_counts()
                            fsp.args["rows"] = misses.value - n0
                            fsp.args["fn_memo_hits"] = h1 - h0
                            fsp.args["fn_memo_misses"] = f1 - f0
                    fmt_s += _time.perf_counter() - _tf

                def _format_payload(spec, handle):
                    nonlocal fetch_s
                    mode, kind, compiled, constraints, prog, bindings, \
                        mask = spec
                    # resolve the device payload once: the format path
                    # reads it through a pre-resolved handle, and a
                    # fresh (non-replayed) payload is captured for
                    # footprint-driven reuse on later churn sweeps
                    payload = None
                    fresh = not isinstance(handle, _ResolvedHandle)
                    if handle is not None and mode in ("topk", "mask"):
                        _tw = _time.perf_counter()
                        with _tracer.span("kind.fetch", cat="device",
                                          parent=_sweep_ctx, kind=kind):
                            payload = handle.get()
                        _wait = _time.perf_counter() - _tw
                        m.timer("device_wait").observe(_wait)
                        fetch_s += _wait
                        handle = _ResolvedHandle(payload)
                    try:
                        if mode == "pages":
                            # the sweep formats pages-mode kinds outside
                            # _prep_lock; the devpages path fills the
                            # reader-side caches (_kind_bindings) the
                            # lock serializes against the reactor —
                            # take it here (react_kind's own _paged_kind
                            # call already holds it; plain Lock, so it
                            # must not be re-acquired deeper down)
                            with self._prep_lock:
                                self._paged_kind(st, target, handler,
                                                 compiled, constraints,
                                                 ordered_rows, row_order,
                                                 kind, limit, tagged,
                                                 rcache, pg_stats,
                                                 pg_dirty_pages, dv_stats)
                        elif mode == "topk":
                            self._format_topk(st, target, handler, compiled,
                                              constraints, prog, bindings,
                                              mask, rank, row_order, kind,
                                              limit, trace, tagged, handle,
                                              rcache)
                        elif mode == "mask":
                            self._format_pairs(st, target, handler, compiled,
                                               constraints, payload,
                                               row_order, kind, limit, trace,
                                               tagged, rcache)
                        else:
                            self._scalar_kind(st, target, handler, compiled,
                                              constraints, mask, ordered_rows,
                                              row_order, kind, limit, trace,
                                              tagged, rcache)
                    except ExternalDataError:
                        # scalar-oracle re-check hit a Fail-policy
                        # provider failure: same per-kind containment as
                        # the prep loop
                        m.counter("external_data_kind_failures").inc()
                    else:
                        if fp_enabled and fresh and payload is not None:
                            self._capture_sweep(st, kind, compiled, mode,
                                                spec, payload, limit)

                def _finished(fs):
                    # those of ``fs`` done, else the first to finish: the
                    # sweep thread waiting on the dispatch pool (the
                    # executor's uploads and launches, and on a full
                    # sweep the device)
                    done = {f for f in fs if f.done()}
                    if done:
                        return done
                    with _tracer.span("executor.wait", cat="device",
                                      parent=_sweep_ctx):
                        return concurrent.futures.wait(
                            fs, return_when=concurrent.futures
                            .FIRST_COMPLETED).done

                if trace is None:
                    fut_idx = {f: i for i, f in enumerate(futures)
                               if f is not None}
                    for i, f in enumerate(futures):
                        if f is None:   # scalar kinds: nothing to wait on
                            _format_kind(specs[i], None)
                    pending = set(fut_idx)
                    while pending:
                        done = _finished(pending)
                        pending -= done
                        for f in done:
                            _format_kind(specs[fut_idx[f]], f.result())
                else:
                    for sp, f in zip(specs, futures):
                        if f is not None:
                            _finished({f})
                        _format_kind(sp,
                                     f.result() if f is not None else None)
                # the wait for device results, and host format without it
                m.timer("audit_dispatch_wait").observe(fetch_s)
                m.timer("audit_format").observe(fmt_s - fetch_s)
                ph["format_s"] = fmt_s
                pipeline_wall = _time.perf_counter() - _t_pipe
            finally:
                pool.shutdown(wait=False)
                if _prep is not None:       # a kind's prep raised
                    _tracer.end(_prep[1])
            tagged.sort(key=lambda kv: kv[0])
            # warm the churn-delta executables in the background: the first
            # sweep after data churn otherwise pays one serialized XLA
            # compile per kind (multiple seconds) right on the sweep
            if limit is not None and not self.scalar_only \
                    and self.executor.mesh is None:
                warm = [(sp[4], sp[5]) for sp in specs if sp[0] == "topk"]
                if warm and not self._delta_warmed:
                    self._delta_warmed = True

                    def _warm(items=warm):
                        for prog, bindings in items:
                            if self.executor._shutdown.is_set():
                                return
                            try:
                                self.executor.prewarm_deltas(prog, bindings)
                            except Exception:
                                pass    # warmup is best-effort
                    # spawn_bg (not a bare daemon thread): a compile in
                    # flight at interpreter teardown aborts the process
                    self.executor.spawn_bg(_warm, "delta-warmup")
            m = self.metrics
            m.counter("audit_sweeps").inc()
            m.counter("audit_results").inc(len(tagged))
            m.timer("audit_sweep_wall").observe(_time.perf_counter() - _t0)
            m.gauge("audit_resources").set(len(ordered_rows))
            if full:
                # overlap_fraction: how much of the summed stage time
                # the pipeline hid — 0 means strictly serial stages,
                # (sum - wall)/sum > 0 means uploads/compute of some
                # kinds ran under other kinds' host prep.  Honest by
                # construction: every term is measured where the work
                # actually ran, and a serial run shows ~0.
                sum_ph = ph["host_prep_s"] + ph["h2d_s"] + \
                    ph["device_s"] + ph.get("format_s", 0.0)
                overlap = max(0.0, (sum_ph - pipeline_wall) / sum_ph) \
                    if sum_ph > 0 else 0.0
                self.last_sweep_phases = {
                    "full": True,
                    "host_prep_s": round(ph["host_prep_s"], 6),
                    "h2d_s": round(ph["h2d_s"], 6),
                    "device_s": round(ph["device_s"], 6),
                    "format_s": round(ph.get("format_s", 0.0), 6),
                    "h2d_bytes": int(ph["h2d_bytes"]),
                    "pipeline_wall_s": round(pipeline_wall, 6),
                    "overlap_fraction": round(overlap, 4),
                }
                # per-template attribution of the measured device time
                # (obs/attribution.py): CostVector units apportion the
                # total, the per-kind timed dispatch blocks anchor the
                # drift report, and the samples recalibrate the cost
                # model's seconds-per-unit scale
                _dev_entries = [
                    (sp[1], sp[2].vectorized, len(sp[3]))
                    for sp in specs
                    if sp[0] in ("topk", "mask")
                    and sp[2].vectorized is not None]
                if _dev_entries and ph["device_s"] > 0:
                    from gatekeeper_tpu.obs.attribution import \
                        attribute_sweep
                    self.last_sweep_phases["attribution"] = \
                        attribute_sweep(_dev_entries, ph["device_s"],
                                        len(ordered_rows),
                                        measured=per_kind_dev, metrics=m)
                ext = self._external_sweep_stats(ext_fut)
                if ext is not None:
                    self.last_sweep_phases["external"] = ext
                if dedup_plan is not None:
                    n_res = len(ordered_rows)
                    saved = sum(max(0, c - 1) * n_res
                                for c in dedup_applied.values())
                    shared_n = sum(1 for c in dedup_applied.values()
                                   if c >= 2)
                    self.last_sweep_phases["dedup"] = {
                        "enabled": True,
                        "groups": len(dedup_plan.groups),
                        "subprograms_shared": shared_n,
                        "evaluations_saved": int(saved),
                        "host_eval_s": round(dedup_host_s, 6),
                    }
                    m.counter("dedup_shared_subprograms").inc(shared_n)
                    m.counter("dedup_evaluations_saved").inc(saved)
                else:
                    self.last_sweep_phases["dedup"] = {"enabled": False}
                if _twin_src is not None:
                    self.last_sweep_phases["whatif"] = {
                        "twin_shared_kinds": len(twin_shared),
                        "twin_dispatched_kinds": sum(
                            1 for s in specs
                            if s[0] in ("topk", "mask")) - len(twin_shared),
                    }
                m.counter("full_sweeps").inc()
                m.timer("full_sweep_host_prep").observe(ph["host_prep_s"])
                m.timer("full_sweep_h2d").observe(ph["h2d_s"])
                m.timer("full_sweep_device").observe(ph["device_s"])
                m.timer("full_sweep_format").observe(ph.get("format_s", 0.0))
                m.gauge("full_sweep_h2d_bytes").set(float(ph["h2d_bytes"]))
                m.gauge("full_sweep_overlap_fraction").set(overlap)
            else:
                self.last_sweep_phases = {"full": False}
                ext = self._external_sweep_stats(ext_fut)
                if ext is not None:
                    self.last_sweep_phases["external"] = ext
            # selective-invalidation stanza (both sweep shapes): how
            # many kinds replayed a cached payload vs ran, and the
            # (constraint x row) evaluations that skipping saved
            self.last_sweep_phases["footprint"] = {
                "enabled": fp_enabled,
                "kinds_skipped": len(fp_skipped),
                "kinds_evaluated": len(specs) - len(fp_skipped),
                "evaluations_saved": int(fp_saved),
            }
            if fp_saved:
                m.counter("footprint_evaluations_saved").inc(fp_saved)
            # dispatch stanza (both sweep shapes): the kinds whose
            # program ran on the device this sweep, and why each kind
            # that did not ran on the scalar oracle (replayed and paged
            # kinds are counted by the footprint and pages stanzas)
            disp = self.last_sweep_phases["dispatch"] = {
                "device": sorted(
                    sp[1] for sp in specs if sp[0] in ("topk", "mask")
                    and sp[1] not in fp_skipped
                    and sp[1] not in twin_shared),
                "scalar": dict(sorted(scalar_why.items())),
            }
            if _sweep_sp is not None:
                _sweep_sp.args["templates_device"] = len(disp["device"])
                _sweep_sp.args["templates_scalar"] = len(disp["scalar"])
            # plan-driven sharding stanza (both sweep shapes): mesh
            # size, which kinds ran sharded vs pinned replicated, the
            # per-shard evaluation slice and the collective count the
            # consumed plans declared
            _mesh = self.executor.mesh
            self.last_sweep_phases["shard"] = {
                "enabled": _mesh is not None,
                "shards": int(_mesh.devices.size) if _mesh is not None
                else 0,
                "plan_gated": sp_gate,
                "kinds_sharded": len(sp_sharded),
                "kinds_replicated": len(sp_replicated),
                "per_shard_evals": int(sp_evals),
                "collectives": int(sp_collectives),
            }
            # continuous-enforcement stanza (both sweep shapes): which
            # kinds served from the ledger vs fell back (with reasons),
            # page-level work accounting, and the delta events emitted
            _led = st.ledger if isinstance(st, JaxTargetState) else None
            self.last_sweep_phases["pages"] = {
                "enabled": pg_on,
                "page_rows": st.table.page_rows,
                "n_pages": st.table.n_pages,
                "kinds_paged": len(pg_kinds),
                "kinds_fallback": len(pg_fallback),
                "fallback_reasons": dict(pg_fallback),
                "pages_evaluated": int(pg_stats["pages_evaluated"]),
                "pages_skipped": int(pg_stats["pages_skipped"]),
                "rows_padded": int(pg_stats["rows_padded"]),
                "rows_reevaluated": int(pg_stats["rows_reevaluated"]),
                "evaluations_saved": int(pg_stats["evaluations_saved"]),
                "widen_fallbacks": int(pg_stats["widen_fallbacks"]),
                "ledger_full_builds": int(pg_stats["full_builds"]),
                "ledger_violations": _led.total_violations()
                if _led is not None else 0,
                "events": int(pg_stats["events"]),
            }
            self.last_sweep_phases["devpages"] = {"enabled": dv_on} \
                if dv_stats is None else {"enabled": True, **dv_stats}
            if dv_stats is not None:
                m.counter("store_h2d_bytes_total").inc(
                    int(dv_stats["h2d_bytes"]))
                m.gauge("devpages_scatter_rows").set(
                    float(dv_stats["scatter_rows"]))
                m.gauge("devpages_delta_events").set(
                    float(dv_stats["delta_events"]))
            m.gauge("store_pages_total").set(float(st.table.n_pages))
            if pg_kinds:
                m.gauge("store_pages_dirty").set(float(len(pg_dirty_pages)))
            if _led is not None:
                m.gauge("ledger_violations").set(
                    float(_led.total_violations()))
            _ov = st.table.dirtylog_overflows
            if _ov > st.dirtylog_overflows_seen:
                m.counter("store_dirtylog_overflow_total").inc(
                    _ov - st.dirtylog_overflows_seen)
                st.dirtylog_overflows_seen = _ov
            if _sweep_sp is not None:
                _sweep_sp.args["results"] = len(tagged)
            from gatekeeper_tpu.obs.flightrecorder import \
                record_event as _record_event
            _record_event("sweep", full=full, results=len(tagged),
                          wall_s=_time.perf_counter() - _t0,
                          device_s=(ph["device_s"] if full else None),
                          scalar_only=self.scalar_only)
            return [r for _, r in tagged], ("\n".join(trace) if trace is not None else None)
        finally:
            # ALWAYS cleared — a dispatch error leaving this set
            # would defer background upgrades forever
            self.executor.sweep_active.clear()
            _sweep_cm.__exit__(None, None, None)

    @locked_read
    def query_review_batch(self, target: str, reviews: list[dict],
                           opts: QueryOpts | None = None) -> list[tuple]:
        """Admission micro-batch as one [C, B] device pass per template
        kind (SURVEY §7 step 7).

        The B review objects become a throwaway mini resource table
        (own interner — admission strings must not grow the inventory
        interner); lowered programs and a ns-over-approximated match
        mask produce candidate (constraint, review) pairs on device, and
        only candidates are re-evaluated exactly on host (autoreject,
        namespaceSelector against the REAL cached namespaces, scalar
        oracle) — the same over-approximate-then-verify contract as the
        audit path, so results match per-review query_review exactly.

        Small batches (or tracing, which must observe evaluation) fall
        back to per-review scalar queries — below SMALL_WORKLOAD_EVALS
        pairs a device dispatch round-trip costs more than it saves."""
        st = self._state(target)
        handler = self.targets[target]
        tracing = opts.tracing if opts is not None else self.default_tracing
        shed = (opts.shed_actions if opts is not None else None) or None
        constraints_all = list(st.all_constraints())
        if shed:
            # brownout (overload.py): shed-action constraints excluded
            # before any evaluation — device mask, host verify, all of it
            constraints_all = [c for c in constraints_all
                               if enforcement_action_of(c) not in shed]
        B = len(reviews)
        if tracing or self.scalar_only or not isinstance(st, JaxTargetState) \
                or not B or \
                B * len(constraints_all) < REVIEW_BATCH_MIN_EVALS:
            return [self.query_review(target, r, opts) for r in reviews]

        import time as _time

        from gatekeeper_tpu.engine.match import MatchEngine
        from gatekeeper_tpu.store.table import ResourceMeta, ResourceTable
        _t_batch = _time.perf_counter()
        mt = ResourceTable()
        for i, rv in enumerate(reviews):
            k = rv.get("kind") or {}
            g, v = k.get("group", ""), k.get("version", "")
            api = f"{g}/{v}" if g else (v or "v1")
            obj = rv.get("object")
            mt.upsert(f"r{i:06d}", obj if isinstance(obj, dict) else {},
                      ResourceMeta(api_version=api, kind=k.get("kind", ""),
                                   name=str(rv.get("name", "")),
                                   namespace=rv.get("namespace")))
        mini = MatchEngine(mt)

        plans: list[tuple] = []
        for kind in sorted(st.templates):
            compiled = st.templates[kind]
            cons = self._kind_constraints(st, kind)
            if shed:
                cons = [c for c in cons
                        if enforcement_action_of(c) not in shed]
            if not cons:
                continue
            cmask = mini.mask(cons, overapprox_ns=True)
            lowered = compiled.vectorized
            # the audit review's operation is always CREATE, so $meta
            # operation columns would mis-encode UPDATE/DELETE reviews —
            # under-approximation risk; those kinds stay on the mask gate
            uses_op = lowered is not None and any(
                rc.path[:1] == ("$meta",) and rc.path[1:] == ("operation",)
                for rc in lowered.spec.r_cols)
            ops_ok = all(r.get("operation", "CREATE") == "CREATE"
                         for r in reviews) if uses_op else True
            # inventory-join columns built over the mini table would see
            # only the batch's reviews, not the real inventory — an
            # under-approximating gate (dropped violations).  Those
            # kinds keep the match-only gate + exact host evaluation.
            if lowered is not None and lowered.spec.inv_joins:
                lowered = None
            if lowered is not None and ops_ok:
                bindings = build_bindings(lowered.spec, mt, cons)
                if bindings.f32_unsafe:
                    # float32 round-trip-unsafe numerics: the device
                    # gate could under-approximate (mis-ordered compare
                    # drops a real violation) — keep the match-only gate
                    plans.append((kind, compiled, cons, cmask, None))
                    continue
                h = self.executor.run_async(lowered.program, bindings,
                                            match=cmask)
                plans.append((kind, compiled, cons, cmask, h))
            else:
                plans.append((kind, compiled, cons, cmask, None))
        gates = [(kind, compiled, cons, (h.get() if h is not None else cmask))
                 for kind, compiled, cons, cmask, h in plans]

        ns_sel_cons = [c for c in constraints_all
                       if ((c.get("spec") or {}).get("match") or {})
                       .get("namespaceSelector") is not None]
        out: list[tuple] = []
        for i, rv in enumerate(reviews):
            results: list[Result] = []
            if ns_sel_cons:
                for c, msg, details in handler.autoreject_review(
                        rv, ns_sel_cons, st.table):
                    results.append(Result(msg=msg,
                                          metadata={"details": details},
                                          constraint=c, review=rv,
                                          enforcement_action=
                                          enforcement_action_of(c)))
            frozen = freeze(rv)
            for kind, compiled, cons, gate in gates:
                for ci, c in enumerate(cons):
                    if not gate[ci, i]:
                        continue
                    # exact matching (incl. namespaceSelector against
                    # the real inventory) before the exact evaluation
                    if not any(True for _ in handler.matching_constraints(
                            rv, [c], st.table)):
                        continue
                    results.extend(self._eval_pair(st, target, compiled, rv,
                                                   frozen, c, None))
            out.append((results, None))
        m = self.metrics
        m.counter("review_batches_device").inc()
        m.counter("reviews_device").inc(B)
        from gatekeeper_tpu.obs.trace import get_tracer as _get_tracer
        _get_tracer().add_complete(
            "admission.device_batch", cat="device", t0=_t_batch,
            t1=_time.perf_counter(), n_reviews=B, kinds=len(gates))
        return out

    @locked_read
    def predict_review_batch_seconds(self, target: str,
                                     n_reviews: int) -> float | None:
        """Cost-model-predicted wall seconds for a review batch of size
        ``n_reviews`` against the installed constraint set — the PR-5
        static cost vector priced by the PR-9 calibrated seconds-per-unit
        scale, seeded with the static prior while uncalibrated
        (costmodel.effective_scale) so deadline-aware batch shrinking
        has an opinion from the very first batch.  None only when there
        is nothing to predict (no reviews, prior disabled and no
        samples) — callers must treat None as "no opinion", never as
        zero."""
        from gatekeeper_tpu.analysis import costmodel
        scale = costmodel.effective_scale()
        if scale <= 0.0 or n_reviews <= 0:
            return None
        st = self._state(target)
        key = (n_reviews, len(st.templates),
               sum(len(v) for v in st.constraints.values()))
        cached = self._predict_cache.get(key)
        units = cached
        if units is None:
            units = 0.0
            for kind in st.templates:
                compiled = st.templates[kind]
                lowered = compiled.vectorized
                if lowered is None:
                    continue
                n_cons = len(st.constraints.get(kind, {}))
                if not n_cons:
                    continue
                units += costmodel.estimate(
                    lowered, n_reviews, n_cons).units()
            if len(self._predict_cache) > 64:
                self._predict_cache.clear()
            self._predict_cache[key] = units
        return costmodel.predict_seconds(units, scale)

    @locked_read
    def explain_pair(self, target: str, kind: str, constraint_name: str,
                     resource_key: str) -> str:
        """Device-path mask dump for one (constraint, resource) pair:
        every IR node's value on that slice plus rule verdicts (the
        tracing equivalent for the vectorized engine, SURVEY §5), with
        the scalar oracle's verdict appended for cross-checking."""
        from gatekeeper_tpu.engine.veval import explain
        st = self._state(target)
        compiled = st.templates.get(kind)
        if compiled is None:
            return f"no template {kind!r}"
        row = st.table.lookup(resource_key)
        if row is None:
            return f"no resource {resource_key!r}"
        constraints = self._kind_constraints(st, kind)
        names = [(c.get("metadata") or {}).get("name") for c in constraints]
        if constraint_name not in names:
            return f"no constraint {constraint_name!r} of kind {kind!r}"
        ci = names.index(constraint_name)
        if compiled.vectorized is None:
            return f"template {kind!r} runs on the scalar engine (not lowered)"
        if self.scalar_only:
            return ("device backend unavailable (scalar-only engine); "
                    "use tracing on the scalar oracle instead")
        with self._prep_lock:
            bindings = self._kind_bindings(st, kind, compiled, constraints)
            mask, _, _ = self._kind_mask(st, target, kind, constraints)
        out = explain(compiled.vectorized.program, bindings, ci, row,
                      match=mask if mask is not TRIVIAL_MATCH else None)
        handler = self.targets[target]
        meta = st.table.meta_at(row)
        review = handler.make_review(meta, st.table.object_at(row))
        matched = any(True for _ in handler.matching_constraints(
            review, [constraints[ci]], st.table))
        oracle = list(self._eval_pair(st, target, compiled, review,
                                      freeze(review), constraints[ci], None)) \
            if matched else []
        return out + f"\n  oracle: {len(oracle)} violation(s)" + "".join(
            f"\n    msg={r.msg!r}" for r in oracle)

    def _pair_results(self, st, target, kind, compiled, c, row, review,
                      frozen, trace, shared=None) -> list:
        """Memoized per-pair formatting.  Steady-state sweeps re-visit
        the same capped (constraint, row) pairs against unchanged rows —
        the oracle re-evaluation is skipped when neither the row (its
        table version) nor the kind's constraint set changed.  Inventory
        -reading templates key on the whole table generation instead
        (their results can depend on any row); tracing bypasses the
        cache (the tracer must observe the evaluation)."""
        if trace is not None:
            return list(self._eval_pair(st, target, compiled, review, frozen,
                                        c, trace))
        con_ver = self.con_version_of(st, kind)
        hit = st.fmt_cache.get(kind)
        if hit is None or hit[0] != con_ver:
            hit = (con_ver, {})
            st.fmt_cache[kind] = hit
        entries = hit[1]
        ver = st.table.generation if compiled.uses_inventory \
            else st.table.version_at(row)
        cname = (c.get("metadata") or {}).get("name", "")
        key = (cname, row)
        ent = entries.get(key)
        if ent is None or ent[0] != ver:
            self.metrics.counter("format_memo_misses").inc()
            results = list(self._eval_pair(st, target, compiled, review,
                                           frozen, c, trace, shared))
            if len(entries) > 65536:     # bound growth across churn
                entries.clear()
            entries[key] = ent = (ver, results)
        else:
            self.metrics.counter("format_memo_hits").inc()
        # fresh copies (own metadata dict too): downstream sets
        # .resource and owns result.metadata — the cached canonical list
        # must stay pristine.  (metadata["details"] values are still
        # shared; they are produced once by thaw() and treated
        # read-only everywhere.)
        return [dataclasses.replace(r, metadata=dict(r.metadata))
                for r in ent[1]]

    @staticmethod
    def con_version_of(st, kind: str) -> int:
        return st.con_version.get(kind, 0)

    def _row_review(self, st, handler, row, rcache):
        """(review, frozen_review, shared_memo) for a table row, cached
        per sweep; None if the row is dead.  The third element is the
        per-review shared memo (rego/closures._memoize_review_pure):
        a violating row is formatted against every constraint that
        flagged it, and its review-pure comprehensions evaluate once per
        row instead of once per (row, constraint) — the memo entries are
        keyed by closure id and verify the frozen review's identity, so
        one dict is safe across kinds (the scalar driver's audit shares
        it the same way)."""
        hit = rcache.get(row)
        if hit is None:
            meta = st.table.meta_at(row)
            if meta is None:
                return None
            review = handler.make_review(meta, st.table.object_at(row))
            hit = (review, freeze(review), {})
            rcache[row] = hit
        return hit

    def _format_pairs(self, st, target, handler, compiled, constraints,
                      cand: np.ndarray, row_order, kind, limit, trace, tagged,
                      rcache):
        """Host-format violating (constraint, resource) pairs via the
        scalar oracle; over-approximated pairs yield no results."""
        for ci, c in enumerate(constraints):
            rows = np.nonzero(cand[ci])[0]
            # visit rows in the scalar driver's order for identical output
            rows = sorted((r for r in rows.tolist() if r in row_order),
                          key=row_order.__getitem__)
            emitted = 0
            for row in rows:
                if limit is not None and emitted >= limit:
                    break
                pair = self._row_review(st, handler, row, rcache)
                if pair is None:
                    continue
                review, frozen, shared = pair
                results = self._pair_results(st, target, kind, compiled, c,
                                             row, review, frozen, trace,
                                             shared)
                for r in results:
                    tagged.append(((row_order[row], kind,
                                    (c.get("metadata") or {}).get("name", "")), r))
                emitted += len(results)

    def _row_rank(self, st: JaxTargetState,
                  row_order: _RowOrder) -> np.ndarray:
        """[n_rows] int32: row -> sorted-cache-key rank.  The device
        top-k scores by this rank so the capped subset matches the
        scalar driver's cap order (not raw table row order, which
        diverges after deletes/re-inserts).  Keyed on key_generation —
        pure updates reuse one array instance (device cache stays hot)."""
        kgen = st.table.key_generation
        if st.rank_cache is not None and st.rank_cache[0] == kgen:
            return st.rank_cache[1]
        n = st.table.n_rows
        rank = np.full((n,), n - 1, dtype=np.int32)
        m = min(len(row_order._pos), n)
        pos = row_order._pos[:m]
        valid = pos >= 0
        rank[:m][valid] = pos[valid].astype(np.int32)
        st.rank_cache = (kgen, rank)
        return rank

    def _format_topk(self, st, target, handler, compiled, constraints,
                     prog, bindings, mask, rank, row_order, kind, limit,
                     trace, tagged, handle, rcache):

        """Capped audit: device finds the first-k candidate rows per
        constraint (in scalar cap order, via rank); the host formats
        only those.  If over-approximated pairs leave the cap
        under-filled while more candidates exist, fall back to the full
        mask for that constraint."""
        import time as _time
        if handle is None:
            handle = self.executor.run_topk_async(prog, bindings, limit)
        counts, rows, valid = handle.get()
        _tf = _time.perf_counter()
        full_cand = None
        for ci, c in enumerate(constraints):
            sel = [int(r) for r, v in zip(rows[ci], valid[ci]) if v]
            sel = sorted((r for r in sel if r in row_order),
                         key=row_order.__getitem__)
            emitted = self._emit_rows(st, target, handler, compiled, c, sel,
                                      row_order, kind, limit, trace, tagged,
                                      rcache)
            if emitted < limit and int(counts[ci]) > len(sel):
                if full_cand is None:
                    full_cand = self.executor.run(prog, bindings)
                sel_set = set(sel)
                rest = sorted((ri for ri in map(int, np.nonzero(full_cand[ci])[0])
                               if ri in row_order and ri not in sel_set),
                              key=row_order.__getitem__)
                self._emit_rows(st, target, handler, compiled, c, rest,
                                row_order, kind, limit - emitted, trace, tagged,
                                rcache)
        self.metrics.timer("host_format").observe(_time.perf_counter() - _tf)

    def _emit_rows(self, st, target, handler, compiled, c, rows, row_order,
                   kind, limit, trace, tagged, rcache) -> int:
        emitted = 0
        for row in rows:
            if limit is not None and emitted >= limit:
                break
            pair = self._row_review(st, handler, row, rcache)
            if pair is None:
                continue
            review, frozen, shared = pair
            results = self._pair_results(st, target, kind, compiled, c, row,
                                         review, frozen, trace, shared)
            for r in results:
                tagged.append(((row_order[row], kind,
                                (c.get("metadata") or {}).get("name", "")), r))
            emitted += len(results)
        return emitted

    def _scalar_kind(self, st, target, handler, compiled, constraints,
                     mask, ordered_rows, row_order, kind, limit, trace, tagged,
                     rcache):
        """Scalar fallback for unlowerable templates, restricted to
        match-mask candidates when a vector matcher exists."""
        emitted = {ci: 0 for ci in range(len(constraints))}
        for row in ordered_rows:
            if limit is not None and all(e >= limit for e in emitted.values()):
                break            # every constraint capped: stop scanning
            if st.table.meta_at(row) is None:
                continue
            pair = None
            for ci, c in enumerate(constraints):
                if limit is not None and emitted[ci] >= limit:
                    continue
                if mask is not None:
                    if not mask[ci, row]:
                        continue
                else:
                    if pair is None:
                        pair = self._row_review(st, handler, row, rcache)
                    if not any(True for _ in handler.matching_constraints(
                            pair[0], [c], st.table)):
                        continue
                if pair is None:
                    pair = self._row_review(st, handler, row, rcache)
                review, frozen, shared = pair
                results = self._pair_results(st, target, kind, compiled, c,
                                             row, review, frozen, trace,
                                             shared)
                for r in results:
                    tagged.append(((row_order[row], kind,
                                    (c.get("metadata") or {}).get("name", "")), r))
                emitted[ci] += len(results)
