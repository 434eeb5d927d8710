"""Benchmark: all BASELINE.md configs on the device engine.

Headline (the ONE stdout JSON line): the north-star full audit matrix —
N resources x C constraints (default 1M x 201), steady-state capped
audit (per-constraint violation cap 20, the reference audit manager's
default, pkg/audit/manager.go:35) — in constraint-evals/sec, with
`vs_baseline` the speedup over the scalar CPU oracle (the
reference-semantics engine standing in for OPA's single-threaded
topdown audit, measured on a subsample and extrapolated linearly).

Also measured (stderr, and embedded in the `detail` field):
- demo/basic:    K8sRequiredLabels over 1k Namespaces (both engines)
- allowed repos: K8sAllowedRepos allowlist over 10k Pods (both engines)
- library:       full 40-template library x 100k mixed resources
- full sweep:    forced full re-evaluation (QueryOpts.full) pipelined
                 vs serial no-overlap vs memoized steady, with
                 per-phase host_prep/h2d/device timings
- regex-heavy:   image-digest / tag / wildcard-host templates x 100k
- selector-heavy: namespaceSelector matching at 100k namespaces
- admission:     AdmissionReview replay through the webhook handler with
                 micro-batching, p50/p99 latency
- cold start:    first-audit-complete time (batch ingest eagerly
                 materializes the mirror + prewarms executables;
                 persistent XLA cache + upgraded-keys markers make
                 restarts reload instead of recompile)
- regex-hicard:  500k unique strings through the batched byte-DFA
                 (ops/regex_dfa) vs the per-unique host re loop
- open-loop:     fixed-rate admission replay, honest p99 at 1k/2k/4k rps
- device-batch:  query_review_batch crossover vs the scalar engine

Resilience contract (round-4 postmortem: one hung backend probe ran
the driver into its kill timeout and erased every config's numbers —
BENCH_r04 rc=124, parsed=null):

- backend bring-up is bounded (utils/device_probe); with a backend that
  never answers the whole bench runs on the scalar/CPU path at shrunk sizes, flagged
  ``"backend": "cpu-fallback"``;
- a tiny device canary runs FIRST and sets a provisional headline —
  a number of record exists within the first minutes;
- every phase has a wall-clock budget enforced by a watchdog thread:
  a phase that hangs (device op that never completes) gets the headline
  JSON printed from whatever is already measured, then the process
  exits — partial detail is fine, a dead capture is not;
- ``detail`` is flushed to BENCH_partial.json as each phase completes.

Env knobs: GATEKEEPER_BENCH_N (north-star N), GATEKEEPER_BENCH_C
(constraints per kind), GATEKEEPER_BENCH_QUICK=1 (shrink everything),
GATEKEEPER_BENCH_BUDGET_S (global wall budget, default 1500 — chosen
to fire before the driver's external kill timeout).
"""

from __future__ import annotations

import copy
import hashlib
import json
import math
import os
import random
import shutil
import statistics
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from gatekeeper_tpu.client.client import Backend
from gatekeeper_tpu.client.interface import QueryOpts
from gatekeeper_tpu.client.local_driver import LocalDriver
from gatekeeper_tpu.engine.jax_driver import JaxDriver
from gatekeeper_tpu.library import all_docs, constraint_doc, make_mixed, template_doc
from gatekeeper_tpu.library.templates import LIBRARY
from gatekeeper_tpu.library.workload import make_pods as make_resources
from gatekeeper_tpu.library.workload import north_star_docs
from gatekeeper_tpu.target.k8s import K8sValidationTarget, TARGET_NAME
from gatekeeper_tpu.utils.device_probe import probe_devices

QUICK = os.environ.get("GATEKEEPER_BENCH_QUICK") == "1"
N = int(os.environ.get("GATEKEEPER_BENCH_N", 100_000 if QUICK else 1_000_000))
C_PER_KIND = int(os.environ.get("GATEKEEPER_BENCH_C", 67))
BASELINE_N = int(os.environ.get("GATEKEEPER_BENCH_BASELINE_N", 2_000))
CAP = 20
HBM_PEAK_GBPS = 819.0   # TPU v5e HBM bandwidth peak (public spec)

# set by main() after the bounded probe / canary: the device backend is
# unusable, so phases run scalar-only at sizes the scalar oracle can
# finish inside the budget
FALLBACK = False


def sized(full: int, fallback: int, quick: int | None = None) -> int:
    """Workload size for the current mode."""
    if FALLBACK:
        return fallback
    if QUICK and quick is not None:
        return quick
    return full

REQUIRED_LABELS = LIBRARY["K8sRequiredLabels"][0]
ALLOWED_REPOS = LIBRARY["K8sAllowedRepos"][0]
CONTAINER_LIMITS = LIBRARY["K8sContainerLimits"][0]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# headline + phase harness

DETAIL: dict = {}
HEADLINE: dict = {"metric": "audit_constraint_evals_per_sec", "value": 0.0,
                  "unit": "evals/s", "vs_baseline": 0.0, "detail": DETAIL}
_T0 = time.monotonic()
_EMIT_LOCK = threading.Lock()
_EMITTED = False
_PARTIAL_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_partial.json")
# Default chosen to sit INSIDE the driver's own kill timeout (the r4
# capture was externally killed ≥26 min in, rc=124): the watchdog must
# always fire first, because only it prints the headline on a breach.
GLOBAL_BUDGET_S = float(os.environ.get("GATEKEEPER_BENCH_BUDGET_S", "1500"))

# watchdog state: (phase name, absolute deadline)
_PHASE = {"name": None, "deadline": None}
_PHASE_LOCK = threading.Lock()


_ABANDONED_THREADS: set = set()     # phase threads left behind at timeout


def set_headline(value: float, vs_baseline: float,
                 provisional: bool = False) -> None:
    """Record the number of record the moment it exists — and surface
    it on stderr immediately, so even a capture that dies later still
    shows it in the tail."""
    if threading.current_thread() in _ABANDONED_THREADS:
        return      # a revived zombie phase must not overwrite the record
    HEADLINE["value"] = round(value, 1)
    HEADLINE["vs_baseline"] = round(vs_baseline, 2)
    if provisional:
        HEADLINE["provisional"] = True
    else:
        HEADLINE.pop("provisional", None)
    log(f"[headline]{' (provisional)' if provisional else ''} "
        + json.dumps({k: v for k, v in HEADLINE.items() if k != "detail"}))
    flush_partial()


def flush_partial() -> None:
    """Write everything measured so far to BENCH_partial.json (atomic)."""
    try:
        tmp = _PARTIAL_PATH + ".tmp"
        with open(tmp, "w") as f:
            json.dump(HEADLINE, f)
        os.replace(tmp, _PARTIAL_PATH)
    except Exception:   # noqa: BLE001 — includes mid-dump dict mutation
        pass


def _slim_headline() -> dict:
    """The stdout headline WITHOUT the full detail tree: metric, value,
    backend, and one-line north-star / full-sweep summaries.  Kept
    ≤1,750 chars by contract — the capture windows that consume the
    bench keep only a stdout tail (ci.sh parses the trailing 2,000
    bytes; the round-5 number of record was erased by exactly such a
    window).  Everything measured stays in BENCH_partial.json."""
    slim = {k: v for k, v in HEADLINE.items() if k != "detail"}
    slim["backend"] = DETAIL.get("backend")
    slim["detail_file"] = "BENCH_partial.json"
    ns = DETAIL.get("north_star")
    if isinstance(ns, dict):
        slim["north_star"] = {k: ns.get(k) for k in
                              ("n_resources", "n_constraints",
                               "steady_seconds")
                              if ns.get(k) is not None}
    fs = DETAIL.get("full_sweep")
    if isinstance(fs, dict):
        slim["full_sweep"] = {k: fs.get(k) for k in
                              ("memoized_steady_seconds",
                               "pipelined_full_seconds", "pipeline_speedup")
                              if fs.get(k) is not None}
    to = DETAIL.get("trace_overhead")
    if isinstance(to, dict):
        slim["trace_overhead"] = {k: to.get(k) for k in
                                  ("overhead_fraction", "within_budget")
                                  if to.get(k) is not None}
    xd = DETAIL.get("external_data")
    if isinstance(xd, dict):
        slim["external_data"] = {k: xd.get(k) for k in
                                 ("baseline_seconds", "cold_seconds",
                                  "warm_seconds", "warm_overhead_fraction")
                                 if xd.get(k) is not None}
    an = DETAIL.get("analysis")
    if isinstance(an, dict):
        slim["analysis"] = {k: an.get(k) for k in
                            ("policyset_wall_seconds",
                             "subprograms_shared", "evaluations_saved",
                             "dedup_parity")
                            if an.get(k) is not None}
    cs = DETAIL.get("churn_selective")
    if isinstance(cs, dict):
        slim["churn_selective"] = {k: cs.get(k) for k in
                                   ("kinds_skipped", "evaluations_saved",
                                    "parity")
                                   if cs.get(k) is not None}
    pc = DETAIL.get("paged_churn")
    if isinstance(pc, dict):
        slim["paged_churn"] = {k: pc.get(k) for k in
                               ("parity", "rows_frac",
                                "evaluations_saved")
                               if pc.get(k) is not None}
    dc = DETAIL.get("devpages_churn")
    if isinstance(dc, dict):
        slim["devpages_churn"] = {k: dc.get(k) for k in
                                  ("parity", "h2d_reduction")
                                  if dc.get(k) is not None}
    wl = DETAIL.get("watch_latency")
    if isinstance(wl, dict):
        slim["watch_latency"] = {k: wl.get(k) for k in
                                 ("parity", "p50_ms", "p99_ms")
                                 if wl.get(k) is not None}
    # headline budget: only ci-asserted keys ride in slim stanzas from
    # here on — everything measured stays in BENCH_partial.json
    tv = DETAIL.get("transval")
    if isinstance(tv, dict):
        slim["transval"] = {k: tv.get(k) for k in
                            ("templates_certified", "counterexamples")
                            if tv.get(k) is not None}
    sh = DETAIL.get("shard_sim")
    if isinstance(sh, dict):
        ss = {k: sh.get(k) for k in ("parity", "parity_digest")
              if sh.get(k) is not None}
        s2 = sh.get("shards_2")
        if isinstance(s2, dict):
            ss["kinds_sharded"] = s2.get("kinds_sharded")
            ss["collectives"] = s2.get("collectives")
        slim["shard_sim"] = ss
    sw = DETAIL.get("shadow_sweep")
    if isinstance(sw, dict):
        slim["shadow_sweep"] = {k: sw.get(k) for k in
                                ("ratio", "within_budget", "parity",
                                 "parity_digest")
                                if sw.get(k) is not None}
    rp = DETAIL.get("replay")
    if isinstance(rp, dict):
        slim["replay"] = {k: rp.get(k) for k in
                          ("parity", "stream_match")
                          if rp.get(k) is not None}
    fs2 = DETAIL.get("fleet_stack")
    if isinstance(fs2, dict):
        slim["fleet_stack"] = {k: fs2.get(k) for k in
                               ("clusters", "parity", "kinds_stacked")
                               if fs2.get(k) is not None}
    pm = DETAIL.get("promotion")
    if isinstance(pm, dict):
        pr = {k: pm.get(k) for k in
              ("replay_speedup", "parity", "final_rung",
               "fleet_graduated")
              if pm.get(k) is not None}
        if pm.get("parity_digest"):
            pr["digest"] = pm["parity_digest"]
        if pr:
            slim["promotion"] = pr
    cf = DETAIL.get("compile_surface")
    if isinstance(cf, dict):
        cfs = {k: cf.get(k) for k in ("certified", "ok")
               if cf.get(k) is not None}
        if cf.get("uncertified_retraces") is not None:
            cfs["uncertified"] = cf["uncertified_retraces"]
        slim["compile_surface"] = cfs
    msf = DETAIL.get("mem_surface")
    if isinstance(msf, dict):
        slim["mem_surface"] = {k: msf.get(k) for k in
                               ("ratio", "within_band", "spill_parity",
                                "ok")
                               if msf.get(k) is not None}
    rx = DETAIL.get("regex_high_cardinality")
    rh = DETAIL.get("regex_heavy")
    if isinstance(rx, dict) or isinstance(rh, dict):
        rg = {}
        if isinstance(rx, dict):
            for k in ("n_unique", "in_jit_vs_host_loop"):
                if rx.get(k) is not None:
                    rg[k] = rx[k]
        if isinstance(rh, dict):
            for k in ("dfa_parity", "parity_digest"):
                if rh.get(k) is not None:
                    rg[k] = rh[k]
        if rg:
            slim["regex"] = rg
    ov = DETAIL.get("overload")
    if isinstance(ov, dict):
        so = {k: ov.get(k) for k in ("shed_total", "max_rung",
                                     "within_budget")
              if ov.get(k) is not None}
        for tag in ("1x", "2x"):
            leg = ov.get(f"open_loop_{tag}")
            if isinstance(leg, dict):
                so[f"p99_{tag}_ms"] = leg.get("p99_ms")
        slim["overload"] = so
    if DETAIL.get("aborted"):
        slim["aborted"] = DETAIL["aborted"]
    return slim


def emit_headline() -> None:
    """Print THE one stdout JSON line (exactly once, from any thread) —
    the SLIM headline (≤1,750 chars; full detail goes to
    BENCH_partial.json via flush_partial, never to stdout).  The
    watchdog calls this while a phase thread may be mutating DETAIL —
    serialization must survive the race (and _EMITTED only latches
    after a successful print, so a failed attempt does not suppress
    the headline forever)."""
    global _EMITTED
    with _EMIT_LOCK:
        if _EMITTED:
            return
        HEADLINE["wall_seconds"] = round(time.monotonic() - _T0, 1)
        line = None
        for _ in range(3):
            try:
                line = json.dumps(_slim_headline())
                break
            except RuntimeError:        # dict mutated mid-dump; retry
                time.sleep(0.05)
        if line is None or len(line) > 1750:    # belt and braces: the
            # headline must fit the 2,000-byte tail window whole
            line = json.dumps({k: HEADLINE.get(k) for k in
                               ("metric", "value", "unit", "vs_baseline",
                                "provisional", "wall_seconds")
                               if k in HEADLINE})
        print(line, flush=True)
        _EMITTED = True
        flush_partial()


def _watchdog() -> None:
    """Emit-and-exit when a phase (or the whole run) blows its budget.
    A hung device op cannot be interrupted from Python — the only safe
    recovery that still produces a number of record is to print the
    headline from what is already measured and leave."""
    global_deadline = _T0 + GLOBAL_BUDGET_S
    while True:
        time.sleep(1.0)
        now = time.monotonic()
        with _PHASE_LOCK:
            name, deadline = _PHASE["name"], _PHASE["deadline"]
        breach = None
        if now > global_deadline:
            breach = f"global budget {GLOBAL_BUDGET_S:.0f}s exceeded"
        elif name is not None and deadline is not None and now > deadline:
            breach = f"phase {name!r} exceeded its budget"
        if breach:
            log(f"[watchdog] {breach}; emitting headline and exiting")
            try:
                DETAIL.setdefault("phases", {}).setdefault(
                    name or "<none>", {})["timed_out"] = True
                DETAIL["aborted"] = breach
                emit_headline()
                sys.stdout.flush()
                sys.stderr.flush()
            finally:
                # the exit must fire even if emit races; a degraded
                # backend still reports nonzero from this path
                rc = 3 if HEADLINE.get("backend_degraded") else 0
                if rc:
                    _flight_dump("bench:watchdog-degraded")
                os._exit(rc)


def _flight_dump(reason: str) -> None:
    """Dump the flight ring on a degraded (rc-3) bench exit so the
    capture artifact keeps the last sweeps/probe results/supervisor
    transitions that led to the demotion.  Best-effort by design."""
    try:
        from gatekeeper_tpu.obs.flightrecorder import get_flight_recorder
        path = get_flight_recorder().dump(reason)
        if path:
            log(f"[bench] flight ring dumped to {path}")
    except Exception:   # noqa: BLE001 — never mask the exit code
        pass


_LEAKED_PHASES: list[str] = []


def run_phase(name: str, fn, budget_s: float) -> None:
    """Run one bench phase on a worker thread, joined with the phase's
    wall-clock budget.  A phase that raises is recorded and skipped —
    later phases still run.  A phase that HANGS (device op that never
    completes) is abandoned: its daemon thread is leaked, the run
    demotes to scalar fallback, and later phases still produce numbers
    (fallback phases never touch the device, so the leaked thread
    cannot contend with them).  A phase that would not fit in the
    remaining global budget is skipped outright."""
    global FALLBACK
    phases = DETAIL.setdefault("phases", {})
    left = (_T0 + GLOBAL_BUDGET_S) - time.monotonic()
    if left < min(60.0, budget_s * 0.25):
        phases[name] = {"skipped": f"only {left:.0f}s of global budget left"}
        log(f"[{name}] skipped ({left:.0f}s of global budget left)")
        return
    hang_hook = os.environ.get("GATEKEEPER_BENCH_TEST_HANG_PHASE") == name
    if hang_hook:
        budget_s = min(budget_s, 10.0)  # the test shouldn't wait long
    budget_s = min(budget_s, max(left, 60.0))
    with _PHASE_LOCK:
        _PHASE["name"] = name
        # the watchdog backstops the join below (+grace), and still
        # guards the global budget
        _PHASE["deadline"] = time.monotonic() + budget_s + 30.0
    t0 = time.monotonic()
    rec = phases.setdefault(name, {})

    def _body():
        # phase fns write top-level detail keys; stage them in a
        # private dict so a thread abandoned at timeout cannot later
        # wake up and clobber results recorded after it (e.g. the
        # fallback re-measure of the same phase)
        local: dict = {}
        try:
            if hang_hook:
                time.sleep(3600)    # test hook: simulated hung device op
            fn(local)
            if threading.current_thread() in _ABANDONED_THREADS:
                return
            DETAIL.update(local)
            rec["ok"] = True
        except Exception as e:  # noqa: BLE001 — a phase must not kill the run
            if threading.current_thread() in _ABANDONED_THREADS:
                return
            rec["ok"] = False
            rec["error"] = f"{type(e).__name__}: {e}"
            log(f"[{name}] FAILED: {type(e).__name__}: {e}")

    t = threading.Thread(target=_body, name=f"phase-{name}", daemon=True)
    t.start()
    t.join(budget_s)
    if t.is_alive():
        _ABANDONED_THREADS.add(t)
        rec["ok"] = False
        rec["timed_out"] = True
        _LEAKED_PHASES.append(name)
        log(f"[{name}] TIMED OUT after {budget_s:.0f}s; abandoning the "
            f"phase thread")
        if not FALLBACK:
            FALLBACK = True
            DETAIL["backend"] = "cpu-fallback"
            _mark_degraded(f"phase {name!r} hung on the device; "
                           f"demoted to scalar")
            # one-way process-wide demotion: drivers constructed by
            # later phases (incl. the north-star fallback re-measure)
            # must see scalar_only=True, or their >20k-eval kinds
            # would route straight back to the hung device
            from gatekeeper_tpu.utils import device_probe
            device_probe.mark_unavailable(
                "device execution hung mid-bench; demoted to scalar")
            log("[bench] demoting to FALLBACK sizing: the device path "
                "hangs mid-execution")
    rec["wall_seconds"] = round(time.monotonic() - t0, 1)
    rec["backend"] = "cpu-fallback" if FALLBACK else \
        probe_devices().backend_label
    with _PHASE_LOCK:
        _PHASE["name"] = None
        _PHASE["deadline"] = None
    flush_partial()


def setup_north_star(driver, resources, rng):
    client = Backend(driver).new_client([K8sValidationTarget()])
    templates, constraints = north_star_docs(rng, C_PER_KIND)
    for doc in templates:
        client.add_template(doc)
    for doc in constraints:
        client.add_constraint(doc)
    client.add_data_batch(resources)
    return client


def timed_audit(driver, reps=3, cap=CAP):
    """(best_seconds, first_seconds, n_results): best-of-reps is the
    memoized steady state; the first rep re-formats after whatever state
    the caller left (still executable/bindings-warm)."""
    best = float("inf")
    first = None
    n_results = 0
    for _ in range(reps):
        t0 = time.perf_counter()
        results, _ = driver.query_audit(TARGET_NAME,
                                        QueryOpts(limit_per_constraint=cap))
        dt = time.perf_counter() - t0
        if first is None:
            first = dt
        best = min(best, dt)
        n_results = len(results)
    return best, first, n_results


def bench_north_star(detail):
    rng = random.Random(42)
    n = sized(N, 1_000)
    n_constraints = 3 * C_PER_KIND
    log(f"[north-star] building {n} resources x {n_constraints} constraints")
    resources = make_resources(n, rng)

    jd = JaxDriver()
    t0 = time.perf_counter()
    client = setup_north_star(jd, resources, random.Random(7))
    ingest_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    jd.query_audit(TARGET_NAME, QueryOpts(limit_per_constraint=CAP))
    cold_s = time.perf_counter() - t0
    snap0 = jd.metrics.snapshot()
    t_best, _t_first, n_results = timed_audit(jd)
    snap = jd.metrics.snapshot()
    evals = n * n_constraints
    # number of record, the moment it exists: vs_baseline provisionally
    # against the round-3-measured scalar-oracle rate (~5.8k evals/s on
    # this host) until the oracle subsample below replaces it
    set_headline(evals / t_best, (evals / 5800.0) / t_best, provisional=True)

    # churn: upsert 1% of rows (label/image edits on existing names),
    # then sweep — delta-maintained columns/bindings/masks must keep the
    # sweep near steady state instead of re-paying full prep
    from gatekeeper_tpu.engine.veval import quiesce_upgrades
    quiesce_upgrades()      # cold-flurry upgrades must not bleed in
    churn_rng = random.Random(1234)
    n_churn = max(n // 100, 1)
    churn_times = []
    for _rep in range(1 if FALLBACK else 3):
        t0 = time.perf_counter()
        for i in churn_rng.sample(range(n), n_churn):
            o = resources[i]
            o["metadata"]["labels"] = {
                k: "v" for k in [f"l{j}" for j in range(10)]
                if churn_rng.random() < 0.35}
            client.add_data(o)
        t_upsert = time.perf_counter() - t0
        t0 = time.perf_counter()
        _res, _ = jd.query_audit(TARGET_NAME,
                                 QueryOpts(limit_per_constraint=CAP))
        churn_times.append(time.perf_counter() - t0)
        log(f"[north-star] churn rep: upsert {n_churn} rows {t_upsert:.2f}s,"
            f" sweep {churn_times[-1]:.2f}s")
    churn_s = min(churn_times)

    def delta_mean(key):
        a, b = snap0.get(key, {}), snap.get(key, {})
        n = (b.get("count") or 0) - (a.get("count") or 0)
        tot = (b.get("total_seconds") or 0) - (a.get("total_seconds") or 0)
        return tot / n if n else 0.0

    dev = {"mean_seconds": delta_mean("device_wait")}
    fmt = {"mean_seconds": delta_mean("host_format")}
    log(f"[north-star] ingest {ingest_s:.1f}s | first audit (cold) {cold_s:.1f}s"
        f" | steady {t_best*1e3:.0f}ms ({n_results} capped results)"
        f" | 1%-churn sweep {churn_s*1e3:.0f}ms")
    log(f"[north-star] breakdown: device-wait mean "
        f"{(dev.get('mean_seconds') or 0)*1e3:.0f}ms/kind, host-format mean "
        f"{(fmt.get('mean_seconds') or 0)*1e3:.0f}ms/kind | format-memo "
        f"{snap.get('format_memo_hits', 0)} hits / "
        f"{snap.get('format_memo_misses', 0)} misses | "
        f"executables: {jd.executor.compiles} compiled, "
        f"{jd.executor.cache_hits} cache hits")

    # roofline context: host-side bytes of every array the steady sweep
    # reads on device (binding columns, element tables, per-constraint
    # tensors, match/rank gates).  A lower bound on HBM traffic per
    # sweep (XLA materializes intermediates on top), so pct_of_peak is
    # an upper bound on how close the sweep is to the bandwidth floor.
    roofline = None
    if not FALLBACK:
        st = jd.state[TARGET_NAME]
        kind_bytes = {}
        b = None
        for kind, (_key, b) in st.bindings_cache.items():
            kind_bytes[kind] = b.nbytes()
        gates = sum(int(getattr(m, "nbytes", 0))
                    for m in st.installed_match.values())
        if st.rank_cache is not None:
            gates += int(st.rank_cache[1].nbytes)
        total_bytes = sum(kind_bytes.values()) + gates
        achieved_gbps = total_bytes / t_best / 1e9
        roofline = {
            "bytes_touched_per_sweep": total_bytes,
            "bytes_by_kind": kind_bytes,
            "gate_bytes": gates,
            "achieved_gbps": round(achieved_gbps, 4),
            "hbm_peak_gbps": HBM_PEAK_GBPS,
            "pct_of_hbm_peak": round(100 * achieved_gbps / HBM_PEAK_GBPS, 4),
            "note": "host-side array bytes (lower bound on device "
                    "traffic).  pct_of_hbm_peak far below 100 means the "
                    "steady sweep is LATENCY-bound (fixed dispatch + "
                    "fetch round-trips, see device_wait_mean_s), not "
                    "bandwidth-bound: the relevant floor is per-kind "
                    "RTT, and more HBM streaming headroom remains for "
                    "larger inventories at the same sweep latency",
        }
        log(f"[north-star] roofline: {total_bytes/1e9:.3f} GB/sweep -> "
            f"{achieved_gbps:.1f} GB/s achieved = "
            f"{100*achieved_gbps/HBM_PEAK_GBPS:.1f}% of v5e HBM peak "
            f"({HBM_PEAK_GBPS:.0f} GB/s)")
        # st/b pin the old driver's whole target state (1M-row table,
        # binding columns, masks) — release before the restart
        # measurement below frees the driver (same hazard bench_library
        # handles with its own `del c, st`)
        del st, b

    # restart: a fresh driver in the same environment — state rebuilt
    # from scratch (the reference rebuilds from watches on every
    # restart too) but the persistent XLA cache skips the compiles.
    # Meaningless in fallback mode (nothing compiles).
    import gc
    restart_ingest_s = restart_audit_s = None
    pc = {"hits": 0, "misses": 0}
    sn_hits = sn_misses = 0
    if not FALLBACK:
        from gatekeeper_tpu.resilience import snapshot as _snap
        del client
        jd_old, jd = jd, None
        del jd_old
        gc.collect()
        quiesce_upgrades()  # measure the restart, not leftover compiles
        jd2 = JaxDriver()
        pc_snap = jd2.executor.persistent_stats.snapshot()
        sn_snap = _snap.stats.snapshot()
        t0 = time.perf_counter()
        client2 = setup_north_star(jd2, resources, random.Random(7))
        restart_ingest_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        jd2.query_audit(TARGET_NAME, QueryOpts(limit_per_constraint=CAP))
        restart_audit_s = time.perf_counter() - t0
        pc = jd2.executor.persistent_stats.delta_since(pc_snap)
        # the restart counter sums EVERY persistence tier (this was the
        # keying bug: XLA is the only tier that existed, and it is off
        # on cpu — so the counter sat at 0 even when the restart reused
        # plenty): XLA executables + snapshotted modules/IR/plans/store
        sn_hits, sn_misses = _snap.tier_counts(
            _snap.stats.delta_since(sn_snap))
        log(f"[north-star] restart: ingest {restart_ingest_s:.1f}s, first "
            f"audit {restart_audit_s:.1f}s (persistent XLA cache: "
            f"{pc['hits']} hits / {pc['misses']} writes; snapshots: "
            f"{sn_hits} hits / {sn_misses} misses; executor: "
            f"{jd2.executor.compiles} compiles)")
        del client2, jd2
        gc.collect()

    # CPU oracle baseline on a subsample, linearly extrapolated
    ld = LocalDriver()
    sub = resources[:min(BASELINE_N, n)]
    setup_north_star(ld, sub, random.Random(7))
    t0 = time.perf_counter()
    ld.query_audit(TARGET_NAME, QueryOpts())
    t_cpu_sub = time.perf_counter() - t0
    t_cpu = t_cpu_sub * (n / max(len(sub), 1))
    log(f"[north-star] cpu oracle: {t_cpu_sub:.2f}s for {len(sub)} -> "
        f"extrapolated {t_cpu:.1f}s for {n}")
    detail["north_star"] = {
        "n_resources": n, "n_constraints": n_constraints,
        "steady_seconds": round(t_best, 4), "cold_seconds": round(cold_s, 2),
        "ingest_seconds": round(ingest_s, 2),
        "churn_1pct_sweep_seconds": round(churn_s, 4),
        "restart_ingest_seconds": restart_ingest_s and round(restart_ingest_s, 2),
        "restart_first_audit_seconds": restart_audit_s and round(restart_audit_s, 2),
        "restart_persistent_cache_hits": pc["hits"] + sn_hits,
        "restart_persistent_cache_misses": pc["misses"] + sn_misses,
        "restart_xla_hits": pc["hits"],
        "restart_snapshot_hits": sn_hits,
        "device_wait_mean_s": dev.get("mean_seconds"),
        "host_format_mean_s": fmt.get("mean_seconds"),
        "capped_results": n_results,
        "roofline": roofline,
        "cpu_oracle_extrapolated_seconds": round(t_cpu, 2)}
    set_headline(evals / t_best, t_cpu / t_best)


def bench_two_engines(detail, key, resources, templates, constraints,
                      oracle_n=None):
    out = {}
    for nm, drv in (("jax", JaxDriver()), ("local", LocalDriver())):
        c = Backend(drv).new_client([K8sValidationTarget()])
        for t in templates:
            c.add_template(t)
        for cd in constraints:
            c.add_constraint(cd)
        sub = resources if nm == "jax" or oracle_n is None else resources[:oracle_n]
        c.add_data_batch(sub)
        drv.query_audit(TARGET_NAME, QueryOpts(limit_per_constraint=CAP))
        best, _first, n_res = timed_audit(drv)
        scale = len(resources) / max(len(sub), 1)
        out[nm] = {"seconds": round(best * scale, 4),
                   "evals_per_sec": round(len(resources) * len(constraints) /
                                          (best * scale), 1),
                   "extrapolated": scale != 1.0}
        if nm == "jax":
            out["results"] = n_res
    log(f"[{key}] jax {out['jax']['seconds']*1e3:.0f}ms "
        f"({out['jax']['evals_per_sec']:.0f} evals/s) vs cpu oracle "
        f"{out['local']['seconds']*1e3:.0f}ms "
        f"({out['local']['evals_per_sec']:.0f} evals/s)")
    detail[key] = out


def bench_demo_basic(detail):
    rng = random.Random(3)
    nss = [{"apiVersion": "v1", "kind": "Namespace",
            "metadata": {"name": f"ns-{i:04d}",
                         "labels": ({"owner": "me"} if rng.random() < 0.5 else {})}}
           for i in range(1_000)]
    bench_two_engines(
        detail, "demo_basic_1k_namespaces", nss,
        [template_doc("K8sRequiredLabels", REQUIRED_LABELS)],
        [constraint_doc("K8sRequiredLabels", "ns-must-have-owner",
                        {"labels": ["owner"]})])


def bench_allowed_repos(detail):
    rng = random.Random(4)
    pods = make_resources(10_000, rng)
    bench_two_engines(
        detail, "allowed_repos_10k_pods", pods,
        [template_doc("K8sAllowedRepos", ALLOWED_REPOS)],
        [constraint_doc("K8sAllowedRepos", "gcr-only", {"repos": ["gcr.io/"]})])


def bench_library(detail):
    n = sized(100_000, 2_000, 10_000)
    log(f"[library] building {n} mixed resources x {len(LIBRARY)} templates")
    rng = random.Random(5)
    resources = make_mixed(rng, n)
    jd = JaxDriver()
    c = Backend(jd).new_client([K8sValidationTarget()])
    for tdoc, cdoc in all_docs():
        c.add_template(tdoc)
        c.add_constraint(cdoc)
    t0 = time.perf_counter()
    c.add_data_batch(resources)
    ingest_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    jd.query_audit(TARGET_NAME, QueryOpts(limit_per_constraint=CAP))
    cold_s = time.perf_counter() - t0
    best, _first, n_res = timed_audit(jd)
    st = jd.state[TARGET_NAME]
    lowered = sum(1 for t in st.templates.values() if t.vectorized is not None)
    # restart: the cold number above is one serialized compile-service
    # round per template and is paid once per cluster lifetime — a
    # process restart reloads all executables from the persistent cache.
    # Nothing compiles in fallback mode, so nothing to measure there.
    restart_ingest_s = restart_audit_s = None
    pc = {"hits": 0}
    sn_hits = sn_misses = 0
    import gc as _gc
    if not FALLBACK:
        from gatekeeper_tpu.engine.veval import quiesce_upgrades
        from gatekeeper_tpu.resilience import snapshot as _snap
        quiesce_upgrades()
        del c, st             # st pins the old driver's target state
        jd_old, jd = jd, None
        del jd_old
        _gc.collect()
        jd2 = JaxDriver()
        pc_snap = jd2.executor.persistent_stats.snapshot()
        sn_snap = _snap.stats.snapshot()
        c2 = Backend(jd2).new_client([K8sValidationTarget()])
        for tdoc, cdoc in all_docs():
            c2.add_template(tdoc)
            c2.add_constraint(cdoc)
        t0 = time.perf_counter()
        c2.add_data_batch(resources)
        restart_ingest_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        jd2.query_audit(TARGET_NAME, QueryOpts(limit_per_constraint=CAP))
        restart_audit_s = time.perf_counter() - t0
        pc = jd2.executor.persistent_stats.delta_since(pc_snap)
        sn_hits, sn_misses = _snap.tier_counts(
            _snap.stats.delta_since(sn_snap))
        log(f"[library] restart: ingest {restart_ingest_s:.1f}s, first audit "
            f"{restart_audit_s:.1f}s (persistent XLA cache: {pc['hits']} hits"
            f" / {pc['misses']} writes / {pc['requests']} requests; "
            f"snapshots: {sn_hits} hits / {sn_misses} misses)")
        del c2, jd2           # release before the CPU-oracle phase
        _gc.collect()
    # oracle on a subsample
    ld = LocalDriver()
    cl = Backend(ld).new_client([K8sValidationTarget()])
    for tdoc, cdoc in all_docs():
        cl.add_template(tdoc)
        cl.add_constraint(cdoc)
    sub = resources[:1000]
    for r in sub:
        cl.add_data(r)
    t0 = time.perf_counter()
    ld.query_audit(TARGET_NAME, QueryOpts())
    t_cpu = (time.perf_counter() - t0) * (n / len(sub))
    log(f"[library] {len(LIBRARY)} templates ({lowered} device-lowered) x {n}:"
        f" steady {best*1e3:.0f}ms ({n_res} capped results), cold {cold_s:.1f}s,"
        f" cpu oracle ~{t_cpu:.1f}s")
    detail[f"library_{n}"] = {
        "n_resources": n, "n_templates": len(LIBRARY),
        "device_lowered": lowered, "steady_seconds": round(best, 4),
        "cold_seconds": round(cold_s, 2), "ingest_seconds": round(ingest_s, 2),
        "restart_ingest_seconds": restart_ingest_s and round(restart_ingest_s, 2),
        "restart_first_audit_seconds": restart_audit_s and round(restart_audit_s, 2),
        "restart_persistent_cache_hits": pc["hits"] + sn_hits,
        "restart_xla_hits": pc["hits"],
        "restart_snapshot_hits": sn_hits,
        "capped_results": n_res,
        "cpu_oracle_extrapolated_seconds": round(t_cpu, 2)}


def bench_full_sweep(detail):
    """Forced full re-evaluation (QueryOpts.full) vs the memoized steady
    sweep, both backends — and pipelined vs the serial no-overlap
    forced-full baseline (FULL_SWEEP_SERIAL).  VERDICT §weak #4: the
    steady number is delta/memo replay, so a forced-full sweep is the
    number a cache-cold audit actually costs; it is reported with the
    driver's per-phase breakdown (host_prep_s / h2d_s / device_s /
    overlap_fraction) so the overlap claim is measured, not asserted."""
    n = 2_000   # the library_2000 scale, device path forced below — a
    #             forced-full sweep is host-prep-bound, so bigger N only
    #             stretches the wall without changing the overlap story
    log(f"[full-sweep] building {n} mixed resources x {len(LIBRARY)} "
        f"templates")
    rng = random.Random(6)
    resources = make_mixed(rng, n)
    jd = JaxDriver()
    c = Backend(jd).new_client([K8sValidationTarget()])
    for tdoc, cdoc in all_docs():
        c.add_template(tdoc)
        c.add_constraint(cdoc)
    c.add_data_batch(resources)
    from gatekeeper_tpu.engine import jax_driver as jd_mod
    saved = jd_mod.SMALL_WORKLOAD_EVALS
    if not FALLBACK:
        jd_mod.SMALL_WORKLOAD_EVALS = 0     # force the device path
    full_opts = QueryOpts(limit_per_constraint=CAP, full=True)
    try:
        # warm once (compiles), then the two memoized-steady reps; the
        # warm sweep kicks off background delta-prewarm compiles — drain
        # them so the timed reps measure the pipeline, not compile theft
        from gatekeeper_tpu.engine.veval import quiesce_upgrades
        jd.query_audit(TARGET_NAME, full_opts)
        quiesce_upgrades()
        steady_best, _f, _nres = timed_audit(jd)
        # tracer overhead on the memoized steady sweep — the cheapest
        # sweep shape, where the tracer's fixed per-span cost looms
        # largest (~150 spans/sweep).  The GATED number is direct
        # accounting: spans recorded by one sweep × measured per-span
        # cost, each factor individually stable.  Differencing two
        # ~130ms wall-clocks cannot resolve a ~1.3ms effect on a
        # shared CPU host (observed jitter is heavy-tailed, ±100ms
        # swings), so the interleaved traced/untraced paired-median
        # comparison is reported as corroboration but not gated.
        # Budget: <2%.  ci.sh gates within_budget from the headline.
        import statistics
        from gatekeeper_tpu.obs.trace import get_tracer
        _tracer = get_tracer()
        _saved_tracing = _tracer.enabled
        plain_opts = QueryOpts(limit_per_constraint=CAP)

        def _one_rep():
            t0 = time.perf_counter()
            jd.query_audit(TARGET_NAME, plain_opts)
            return time.perf_counter() - t0

        try:
            _tracer.enabled = True
            _tracer.reset()
            _one_rep()
            n_spans = len(_tracer.export()["traceEvents"])
            t0 = time.perf_counter()
            for _ in range(2000):
                with _tracer.span("overhead_probe", cat="bench"):
                    pass
            per_span_s = (time.perf_counter() - t0) / 2000
            _tracer.reset()     # drop the probe spans from the ring
            pairs = []
            for _ in range(5):
                _tracer.enabled = True
                t = _one_rep()
                _tracer.enabled = False
                pairs.append((t, _one_rep()))
        finally:
            _tracer.enabled = _saved_tracing
        med_traced = statistics.median(p[0] for p in pairs)
        med_untraced = statistics.median(p[1] for p in pairs)
        delta = statistics.median(p[0] - p[1] for p in pairs)
        overhead = (n_spans * per_span_s / med_untraced
                    if med_untraced else 0.0)
        detail["trace_overhead"] = {
            "spans_per_sweep": n_spans,
            "per_span_seconds": round(per_span_s, 9),
            "steady_traced_seconds": round(med_traced, 5),
            "steady_untraced_seconds": round(med_untraced, 5),
            "median_paired_delta_seconds": round(delta, 5),
            "overhead_fraction": round(overhead, 4),
            "within_budget": bool(overhead < 0.02),
        }
        log(f"[full-sweep] tracer overhead {overhead:.2%} "
            f"({n_spans} spans x {per_span_s*1e6:.1f}us on a "
            f"{med_untraced*1e3:.1f}ms sweep; paired-median delta "
            f"{delta*1e3:+.1f}ms corroborates)")
        # pipelined forced-full
        pipe_times = []
        n_res_full = 0
        for _ in range(5):
            t0 = time.perf_counter()
            results, _ = jd.query_audit(TARGET_NAME, full_opts)
            pipe_times.append(time.perf_counter() - t0)
            n_res_full = len(results)
        pipe_best = min(pipe_times)
        phases = dict(jd.last_sweep_phases)
        # serial no-overlap forced-full baseline: same workload, each
        # kind's prep -> upload -> execute completes before the next
        saved_serial = jd_mod.FULL_SWEEP_SERIAL
        jd_mod.FULL_SWEEP_SERIAL = True
        try:
            serial_times = []
            for _ in range(5):
                t0 = time.perf_counter()
                jd.query_audit(TARGET_NAME, full_opts)
                serial_times.append(time.perf_counter() - t0)
        finally:
            jd_mod.FULL_SWEEP_SERIAL = saved_serial
        serial_best = min(serial_times)
    finally:
        jd_mod.SMALL_WORKLOAD_EVALS = saved
    del c, jd
    # the scalar oracle is full-by-construction: its plain audit IS the
    # forced-full number for the other backend
    ld = LocalDriver()
    cl = Backend(ld).new_client([K8sValidationTarget()])
    for tdoc, cdoc in all_docs():
        cl.add_template(tdoc)
        cl.add_constraint(cdoc)
    cl.add_data_batch(resources)
    t0 = time.perf_counter()
    ld.query_audit(TARGET_NAME, QueryOpts(limit_per_constraint=CAP))
    oracle_s = time.perf_counter() - t0
    speedup = serial_best / pipe_best if pipe_best else 0.0
    detail["full_sweep"] = {
        "n_resources": n, "n_templates": len(LIBRARY),
        "memoized_steady_seconds": round(steady_best, 4),
        "pipelined_full_seconds": round(pipe_best, 4),
        "serial_full_seconds": round(serial_best, 4),
        "pipeline_speedup": round(speedup, 2),
        "full_vs_steady": round(pipe_best / steady_best, 1)
        if steady_best else None,
        "cpu_oracle_full_seconds": round(oracle_s, 4),
        "results": n_res_full,
        **{k: phases.get(k) for k in
           ("host_prep_s", "h2d_s", "device_s", "format_s", "h2d_bytes",
            "pipeline_wall_s", "overlap_fraction")},
    }
    log(f"[full-sweep] memoized steady {steady_best*1e3:.0f}ms | "
        f"forced-full pipelined {pipe_best*1e3:.0f}ms vs serial "
        f"{serial_best*1e3:.0f}ms ({speedup:.2f}x) | overlap "
        f"{phases.get('overlap_fraction', 0):.0%} (host_prep "
        f"{phases.get('host_prep_s', 0)*1e3:.0f}ms, h2d "
        f"{phases.get('h2d_s', 0)*1e3:.0f}ms, device "
        f"{phases.get('device_s', 0)*1e3:.0f}ms) | cpu oracle full "
        f"{oracle_s*1e3:.0f}ms")


EXT_SIG_REGO = """package k8sextsig
violation[{"msg": msg}] {
  image := input.review.object.spec.image
  verdict := object.get(external_data({"provider": "bench-sig", "keys": [image]}), ["responses", image], "missing")
  verdict == "invalid"
  msg := sprintf("image %v rejected: %v", [image, verdict])
}
"""


def bench_external_data(detail):
    """The external_data two-phase path at the library_2000 scale:
    cold fetch (empty cache — the sweep pays one batched provider round)
    vs warm cache (every key fresh) vs the no-provider baseline (same
    workload and library, no external template).  The acceptance metric
    is warm-cache overhead vs baseline; the two-phase design's claim is
    that a warm sweep adds only the per-unique-key host gather, so the
    overhead must stay under 10%."""
    from gatekeeper_tpu.api.externaldata import Provider
    from gatekeeper_tpu.externaldata.fake import (FakeProvider, clear_fakes,
                                                  register_fake)
    from gatekeeper_tpu.externaldata.runtime import (ExternalDataRuntime,
                                                     set_runtime)
    from gatekeeper_tpu.engine.veval import quiesce_upgrades

    n = sized(2_000, 600, 600)
    n_keys = 256
    latency_s = 0.02    # simulated provider round-trip (paid once, cold)
    rng = random.Random(11)
    resources = make_mixed(rng, n)
    images = [f"registry.example/app{i}:v1" for i in range(n_keys)]
    n_pods = 0
    for r in resources:
        if r.get("kind") == "Pod":
            r["spec"]["image"] = rng.choice(images)
            n_pods += 1
    log(f"[external-data] {n} resources ({n_pods} pods, {n_keys} distinct "
        f"keys), provider latency {latency_s*1e3:.0f}ms")
    from gatekeeper_tpu.engine import jax_driver as jd_mod
    saved = jd_mod.SMALL_WORKLOAD_EVALS
    if not FALLBACK:
        jd_mod.SMALL_WORKLOAD_EVALS = 0
    full_opts = QueryOpts(limit_per_constraint=CAP, full=True)

    def build(with_ext):
        jd = JaxDriver()
        c = Backend(jd).new_client([K8sValidationTarget()])
        for tdoc, cdoc in all_docs():
            c.add_template(tdoc)
            c.add_constraint(cdoc)
        if with_ext:
            c.add_template(template_doc("K8sExtSig", EXT_SIG_REGO))
            c.add_constraint(constraint_doc(
                "K8sExtSig", "bench-sig-check",
                match={"kinds": [{"apiGroups": [""], "kinds": ["Pod"]}]}))
        c.add_data_batch(resources)
        return jd, c

    def best_full(jd, reps=3):
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            results, _ = jd.query_audit(TARGET_NAME, full_opts)
            times.append(time.perf_counter() - t0)
        return min(times), len(results)

    prev_rt = None
    rt = ExternalDataRuntime()
    try:
        # no-provider baseline
        jd, c = build(with_ext=False)
        jd.query_audit(TARGET_NAME, full_opts)      # compile warm
        quiesce_upgrades()
        baseline_s, _nb = best_full(jd)
        del c, jd

        prev_rt = set_runtime(rt)
        data = {img: ("invalid" if i % 10 == 0 else "valid")
                for i, img in enumerate(images)}
        fake = register_fake("bench-sig", FakeProvider(data,
                                                       latency_s=latency_s))
        provider = Provider(name="bench-sig", url="fake://bench-sig",
                            failure_policy="Ignore", retries=0,
                            cache_ttl_s=600.0)
        rt.register(provider)
        jd, c = build(with_ext=True)
        jd.query_audit(TARGET_NAME, full_opts)      # compile warm (+fetch)
        quiesce_upgrades()
        rt.register(provider)       # re-register: drops cache -> cold
        calls_before = fake.calls
        t0 = time.perf_counter()
        results, _ = jd.query_audit(TARGET_NAME, full_opts)
        cold_s = time.perf_counter() - t0
        cold_batches = fake.calls - calls_before
        warm_s, n_ext = best_full(jd)
        del c, jd
    finally:
        jd_mod.SMALL_WORKLOAD_EVALS = saved
        set_runtime(prev_rt)
        clear_fakes()

    overhead = (warm_s / baseline_s - 1.0) if baseline_s else 0.0
    detail["external_data"] = {
        "n_resources": n, "n_pods": n_pods, "n_keys": n_keys,
        "provider_latency_s": latency_s,
        "baseline_seconds": round(baseline_s, 4),
        "cold_seconds": round(cold_s, 4),
        "warm_seconds": round(warm_s, 4),
        "warm_overhead_fraction": round(overhead, 4),
        "cold_fetch_batches": cold_batches,
        "results_with_ext": n_ext,
        "provider_stats": rt.stats().get("bench-sig"),
    }
    log(f"[external-data] baseline {baseline_s*1e3:.0f}ms | cold "
        f"{cold_s*1e3:.0f}ms ({cold_batches} batched round(s)) | warm "
        f"{warm_s*1e3:.0f}ms ({overhead:+.1%} vs baseline)")


def bench_analysis(detail):
    """Stage-3 whole-policy-set analysis: (a) the static pass — lower +
    IR-verify + cost/shadowing/dedup analysis over the full built-in
    library — must stay milliseconds-cheap (it runs at install time,
    inside reconcile); (b) the cross-template predicate dedup at the
    library_2000 scale, with the deduped sweep's verdicts checked
    bit-for-bit against a GATEKEEPER_DEDUP=off oracle sweep."""
    from gatekeeper_tpu.analysis.ir_verifier import verify_program
    from gatekeeper_tpu.analysis.policyset import analyze_policy_set
    from gatekeeper_tpu.client.probe import _library_entries

    # (a) static-pass wall over the library
    t0 = time.perf_counter()
    entries = _library_entries()
    for _kind, lowered, _cons in entries:
        if lowered is not None:
            verify_program(lowered)
    report = analyze_policy_set(entries)
    static_wall = time.perf_counter() - t0
    shared = report["shared_subprograms"]

    # (b) dedup parity + savings at library_2000
    n = sized(BASELINE_N, 500, 2_000)
    log(f"[analysis] static pass {static_wall*1e3:.0f}ms "
        f"({len(shared)} shared group(s)); dedup parity at n={n}")
    rng = random.Random(6)
    resources = make_mixed(rng, n)
    from gatekeeper_tpu.engine import jax_driver as jd_mod
    full_opts = QueryOpts(limit_per_constraint=CAP, full=True)

    def sweep(dedup: str):
        prev = os.environ.get("GATEKEEPER_DEDUP")
        os.environ["GATEKEEPER_DEDUP"] = dedup
        saved = jd_mod.SMALL_WORKLOAD_EVALS
        try:
            if not FALLBACK:
                jd_mod.SMALL_WORKLOAD_EVALS = 0
            jd = JaxDriver()
            c = Backend(jd).new_client([K8sValidationTarget()])
            for tdoc, cdoc in all_docs():
                c.add_template(tdoc)
                c.add_constraint(cdoc)
            c.add_data_batch(resources)
            jd.query_audit(TARGET_NAME, full_opts)    # warm/compile
            t0 = time.perf_counter()
            results, _ = jd.query_audit(TARGET_NAME, full_opts)
            wall = time.perf_counter() - t0
            verdicts = sorted(
                ((r.constraint or {}).get("kind", ""),
                 ((r.constraint or {}).get("metadata") or {}).get("name", ""),
                 ((r.resource or {}).get("metadata") or {}).get("name", ""),
                 r.msg)
                for r in results)
            return verdicts, wall, dict(jd.last_sweep_phases.get("dedup")
                                        or {})
        finally:
            jd_mod.SMALL_WORKLOAD_EVALS = saved
            if prev is None:
                os.environ.pop("GATEKEEPER_DEDUP", None)
            else:
                os.environ["GATEKEEPER_DEDUP"] = prev

    v_oracle, oracle_s, _ = sweep("off")
    v_dedup, dedup_s, stanza = sweep("on")
    parity = v_oracle == v_dedup
    detail["analysis"] = {
        "n_resources": n,
        "policyset_wall_seconds": round(static_wall, 4),
        "shared_groups": len(shared),
        "subprograms_shared": stanza.get("subprograms_shared", 0),
        "evaluations_saved": stanza.get("evaluations_saved", 0),
        "dedup_parity": parity,
        "dedup_full_seconds": round(dedup_s, 4),
        "nodedup_full_seconds": round(oracle_s, 4),
        "dedup_host_eval_s": stanza.get("host_eval_s"),
        "findings": len(report["findings"]),
    }
    if not parity:
        raise AssertionError(
            f"dedup verdict mismatch: oracle={len(v_oracle)} "
            f"dedup={len(v_dedup)}")
    log(f"[analysis] dedup sweep {dedup_s*1e3:.0f}ms vs no-dedup "
        f"{oracle_s*1e3:.0f}ms | {stanza.get('subprograms_shared', 0)} "
        f"shared subprogram(s), {stanza.get('evaluations_saved', 0)} "
        f"evaluations saved | parity={parity}")


def bench_churn_selective(detail):
    """Stage-5 footprint-driven selective invalidation at library
    scale: install the full library, ingest a mixed inventory, run to
    steady state, churn 1% of rows (half annotation-only noise that no
    library template reads, half image edits that several do), then
    re-sweep with footprints on vs the GATEKEEPER_FOOTPRINT=off oracle.
    Verdicts must be bit-identical; the selective sweep reports how
    many kind-sweeps it skipped and the constraint-evaluations saved
    (jax_driver's ``footprint`` phase stanza)."""
    import copy
    from gatekeeper_tpu.engine import jax_driver as jd_mod

    n = sized(BASELINE_N, 400, 1_000)
    n_churn = max(n // 100, 1)
    log(f"[churn-selective] n={n}, churn={n_churn} rows, "
        "footprints on vs off")
    rng = random.Random(11)
    resources = make_mixed(rng, n)
    opts = QueryOpts(limit_per_constraint=CAP)
    full_opts = QueryOpts(limit_per_constraint=CAP, full=True)

    def run(fp_mode: str):
        prev = os.environ.get("GATEKEEPER_FOOTPRINT")
        os.environ["GATEKEEPER_FOOTPRINT"] = fp_mode
        saved = jd_mod.SMALL_WORKLOAD_EVALS
        try:
            if not FALLBACK:
                jd_mod.SMALL_WORKLOAD_EVALS = 0
            work = copy.deepcopy(resources)     # churn mutates rows
            jd = JaxDriver()
            c = Backend(jd).new_client([K8sValidationTarget()])
            for tdoc, cdoc in all_docs():
                c.add_template(tdoc)
                c.add_constraint(cdoc)
            c.add_data_batch(work)
            jd.query_audit(TARGET_NAME, full_opts)      # compile warm
            jd.query_audit(TARGET_NAME, opts)           # steady state
            churn_rng = random.Random(77)
            for j, i in enumerate(churn_rng.sample(range(n), n_churn)):
                # fresh object per event (a real watch decodes a new
                # dict each time); re-upserting the mutated stored
                # reference trips the store's aliasing guard and
                # dirties the wildcard root, disabling all skips
                o = copy.deepcopy(work[i])
                if j % 2 == 0:
                    # annotation-only edit: outside every library
                    # template's read-set — the selective sweep must
                    # skip the whole library for these rows
                    o.setdefault("metadata", {}).setdefault(
                        "annotations", {})["bench-churn"] = f"r{j}"
                else:
                    # image edit: inside the repos/tags/digest
                    # templates' read-sets — those kinds must re-sweep
                    for cont in (o.get("spec") or {}).get(
                            "containers") or []:
                        cont["image"] = f"evil.io/churn:{j}"
                c.add_data(o)
            t0 = time.perf_counter()
            results, _ = jd.query_audit(TARGET_NAME, opts)
            wall = time.perf_counter() - t0
            verdicts = sorted(
                ((r.constraint or {}).get("kind", ""),
                 ((r.constraint or {}).get("metadata") or {}).get("name", ""),
                 ((r.resource or {}).get("metadata") or {}).get("name", ""),
                 r.msg)
                for r in results)
            stanza = dict(jd.last_sweep_phases.get("footprint") or {})
            return verdicts, wall, stanza
        finally:
            jd_mod.SMALL_WORKLOAD_EVALS = saved
            if prev is None:
                os.environ.pop("GATEKEEPER_FOOTPRINT", None)
            else:
                os.environ["GATEKEEPER_FOOTPRINT"] = prev

    v_oracle, oracle_s, _ = run("off")
    v_sel, sel_s, stanza = run("on")
    parity = v_oracle == v_sel
    digest = hashlib.sha256(repr(v_sel).encode()).hexdigest()[:16]
    detail["churn_selective"] = {
        "n_resources": n,
        "churn_rows": n_churn,
        "kinds_skipped": stanza.get("kinds_skipped", 0),
        "kinds_evaluated": stanza.get("kinds_evaluated", 0),
        "evaluations_saved": stanza.get("evaluations_saved", 0),
        "parity": parity,
        "parity_digest": digest,
        "selective_seconds": round(sel_s, 4),
        "oracle_seconds": round(oracle_s, 4),
    }
    log(f"[churn-selective] selective sweep {sel_s*1e3:.0f}ms vs oracle "
        f"{oracle_s*1e3:.0f}ms | skipped {stanza.get('kinds_skipped', 0)}"
        f"/{stanza.get('kinds_skipped', 0) + stanza.get('kinds_evaluated', 0)}"
        f" kind-sweeps, {stanza.get('evaluations_saved', 0)} evaluations "
        f"saved | parity={parity} digest={digest}")
    if not parity:
        raise AssertionError(
            f"selective-invalidation verdict mismatch: "
            f"oracle={len(v_oracle)} selective={len(v_sel)}")


def bench_paged_churn(detail):
    """Continuous enforcement at library scale: the row-paged sweep
    (GATEKEEPER_PAGES=on, enforce/ledger.py) vs the PR-10
    kind-granular selective sweep vs the pages-off/footprint-off full
    oracle, at 0.1% and 1% churn.  Verdicts must be bit-identical
    across all three configs; the paged run additionally reports the
    page-level work accounting (rows re-evaluated as a fraction of the
    row-evaluation space, constraint-evaluations saved, delta events)
    from jax_driver's ``pages`` phase stanza.  The acceptance floor —
    <5% of row-evaluations at 0.1% churn — is gated in ci.sh off this
    detail row."""
    import copy
    from gatekeeper_tpu.engine import jax_driver as jd_mod

    n = sized(BASELINE_N, 400, 1_000)
    log(f"[paged-churn] n={n}, paged vs kind-granular vs full oracle")
    rng = random.Random(13)
    resources = make_mixed(rng, n)
    opts = QueryOpts(limit_per_constraint=CAP)
    full_opts = QueryOpts(limit_per_constraint=CAP, full=True)

    def run(pages: str, fp_mode: str, n_churn: int, image_only: bool):
        prev_pg = os.environ.get("GATEKEEPER_PAGES")
        prev_fp = os.environ.get("GATEKEEPER_FOOTPRINT")
        os.environ["GATEKEEPER_PAGES"] = pages
        os.environ["GATEKEEPER_FOOTPRINT"] = fp_mode
        saved = jd_mod.SMALL_WORKLOAD_EVALS
        try:
            if not FALLBACK:
                jd_mod.SMALL_WORKLOAD_EVALS = 0
            work = copy.deepcopy(resources)     # churn mutates rows
            jd = JaxDriver()
            c = Backend(jd).new_client([K8sValidationTarget()])
            for tdoc, cdoc in all_docs():
                c.add_template(tdoc)
                c.add_constraint(cdoc)
            c.add_data_batch(work)
            jd.query_audit(TARGET_NAME, full_opts)      # compile warm
            jd.query_audit(TARGET_NAME, opts)           # ledger built
            churn_rng = random.Random(99)
            pod_idx = [i for i, o in enumerate(work)
                       if (o.get("spec") or {}).get("containers")]
            for j in range(n_churn):
                # fresh object per event — a real watch decodes a new
                # dict each time (re-upserting the stored reference
                # trips the aliasing guard and widens the path set)
                if image_only or j % 2:
                    # verdict-flipping edit inside the image templates'
                    # read-sets (sampled from container-bearing rows so
                    # the edit lands): those kinds re-evaluate ONE page
                    # and the ledger emits the msg delta
                    o = copy.deepcopy(work[churn_rng.choice(pod_idx)])
                    for cont in o["spec"]["containers"]:
                        cont["image"] = f"evil.io/paged:{j}"
                else:
                    # annotation noise outside every read-set
                    o = copy.deepcopy(work[churn_rng.randrange(n)])
                    o.setdefault("metadata", {}).setdefault(
                        "annotations", {})["bench-paged"] = f"r{j}"
                c.add_data(o)
            t0 = time.perf_counter()
            results, _ = jd.query_audit(TARGET_NAME, opts)
            wall = time.perf_counter() - t0
            verdicts = sorted(
                ((r.constraint or {}).get("kind", ""),
                 ((r.constraint or {}).get("metadata") or {}).get(
                     "name", ""),
                 ((r.resource or {}).get("metadata") or {}).get(
                     "name", ""),
                 r.msg)
                for r in results)
            stanza = dict(jd.last_sweep_phases.get("pages") or {})
            return verdicts, wall, stanza
        finally:
            jd_mod.SMALL_WORKLOAD_EVALS = saved
            for key, prev in (("GATEKEEPER_PAGES", prev_pg),
                              ("GATEKEEPER_FOOTPRINT", prev_fp)):
                if prev is None:
                    os.environ.pop(key, None)
                else:
                    os.environ[key] = prev

    out = {"n_resources": n}
    for label, n_churn, image_only in (
            ("churn_0p1", max(n // 1000, 1), True),
            ("churn_1p0", max(n // 100, 1), False)):
        v_or, or_s, _ = run("off", "off", n_churn, image_only)
        v_kind, kind_s, _ = run("off", "on", n_churn, image_only)
        v_pg, pg_s, stanza = run("on", "on", n_churn, image_only)
        parity = v_or == v_kind == v_pg
        digest = hashlib.sha256(repr(v_pg).encode()).hexdigest()[:16]
        kinds_paged = stanza.get("kinds_paged", 0) or 1
        rows_frac = (stanza.get("rows_reevaluated", 0)
                     / float(n * kinds_paged))
        out[label] = {
            "churn_rows": n_churn,
            "parity": parity,
            "parity_digest": digest,
            "kinds_paged": kinds_paged,
            "kinds_fallback": stanza.get("kinds_fallback", 0),
            "pages_evaluated": stanza.get("pages_evaluated", 0),
            "pages_skipped": stanza.get("pages_skipped", 0),
            "rows_reevaluated": stanza.get("rows_reevaluated", 0),
            "rows_frac": round(rows_frac, 5),
            "evaluations_saved": stanza.get("evaluations_saved", 0),
            "events": stanza.get("events", 0),
            "paged_seconds": round(pg_s, 4),
            "kind_granular_seconds": round(kind_s, 4),
            "oracle_seconds": round(or_s, 4),
            "paged_vs_oracle_ratio": round(pg_s / or_s, 3)
            if or_s else None,
        }
        log(f"[paged-churn] {label}: {n_churn} row(s) churned | paged "
            f"{pg_s*1e3:.0f}ms vs kind {kind_s*1e3:.0f}ms vs oracle "
            f"{or_s*1e3:.0f}ms | rows_frac={rows_frac:.4f} "
            f"saved={stanza.get('evaluations_saved', 0)} "
            f"events={stanza.get('events', 0)} | parity={parity} "
            f"digest={digest}")
        if not parity:
            raise AssertionError(
                f"paged-churn verdict mismatch at {label}: "
                f"oracle={len(v_or)} kind={len(v_kind)} paged={len(v_pg)}")
    # the headline/gate keys: the 0.1%-churn leg carries the O(dirty)
    # claim of record
    out["parity"] = out["churn_0p1"]["parity"] \
        and out["churn_1p0"]["parity"]
    out["parity_digest"] = out["churn_0p1"]["parity_digest"]
    out["rows_frac"] = out["churn_0p1"]["rows_frac"]
    out["evaluations_saved"] = out["churn_0p1"]["evaluations_saved"]
    out["page_rows"] = stanza.get("page_rows")
    detail["paged_churn"] = out


def bench_devpages_churn(detail):
    """Device-resident page table (GATEKEEPER_DEVPAGES=on,
    enforce/devpages.py) vs the host-paged sweep vs the pages-off full
    oracle, at 0.1% and 1% churn.  Verdicts must be bit-identical
    across all three configs; the claim of record is the H2D byte
    count of the steady-state churn sweep — the device-resident store
    moves row-sized scatter records (churned rows x read-set columns)
    while the re-stage oracle re-uploads every bound array, so total
    H2D at 0.1% churn must come in >=10x under the oracle figure.
    The comparator legs run with GATEKEEPER_BINDING_DELTA=off: the
    incremental binding chain landed in the same PR as the device
    store and would otherwise ride along in every leg, hiding the
    re-stage cost this row exists to measure.  The host-paged leg is
    reported, not gated — its dirty-page staging is already
    page-slice-granular, so at sub-page churn its H2D is small and
    does not represent the full-re-stage behavior the claim of record
    is measured against.  One warm churn round runs before the timed
    leg: the first churn after a cold build pays a one-time bucket
    rebuild for kinds whose interner-indexed arrays were sized early
    in the cold sweep, and that is not the steady-state cost.  Capped
    at n=2000: the CPU-backed CI container cannot carry the
    north-star shape through a jitted sweep inside the watchdog
    budget."""
    import copy
    from gatekeeper_tpu.engine import jax_driver as jd_mod

    n = sized(2_000, 400, 1_000)
    log(f"[devpages-churn] n={n}, device-paged vs host-paged vs oracle")
    rng = random.Random(17)
    resources = make_mixed(rng, n)
    opts = QueryOpts(limit_per_constraint=CAP)
    full_opts = QueryOpts(limit_per_constraint=CAP, full=True)

    def run(devpages: str, pages: str, fp_mode: str, delta: str,
            n_churn: int):
        env_keys = ("GATEKEEPER_DEVPAGES", "GATEKEEPER_PAGES",
                    "GATEKEEPER_FOOTPRINT", "GATEKEEPER_BINDING_DELTA")
        prev_env = {k: os.environ.get(k) for k in env_keys}
        os.environ["GATEKEEPER_DEVPAGES"] = devpages
        os.environ["GATEKEEPER_PAGES"] = pages
        os.environ["GATEKEEPER_FOOTPRINT"] = fp_mode
        os.environ["GATEKEEPER_BINDING_DELTA"] = delta
        saved = jd_mod.SMALL_WORKLOAD_EVALS
        try:
            if not FALLBACK:
                jd_mod.SMALL_WORKLOAD_EVALS = 0
            work = copy.deepcopy(resources)
            jd = JaxDriver()
            c = Backend(jd).new_client([K8sValidationTarget()])
            for tdoc, cdoc in all_docs():
                c.add_template(tdoc)
                c.add_constraint(cdoc)
            c.add_data_batch(work)
            jd.query_audit(TARGET_NAME, full_opts)      # compile warm
            jd.query_audit(TARGET_NAME, opts)           # resident build
            churn_rng = random.Random(99)
            pod_idx = [i for i, o in enumerate(work)
                       if (o.get("spec") or {}).get("containers")]
            # warm churn round: kinds whose interner-indexed buckets
            # were sized early in the cold sweep rebuild exactly once
            # on the first post-cold churn; pay that here so the timed
            # sweep below measures the steady-state delta path
            warm = copy.deepcopy(work[pod_idx[0]])
            for cont in warm["spec"]["containers"]:
                cont["image"] = "warm.io/devpages:steady"
            c.add_data(warm)
            jd.query_audit(TARGET_NAME, opts)
            for j in range(n_churn):
                o = copy.deepcopy(work[churn_rng.choice(pod_idx)])
                for cont in o["spec"]["containers"]:
                    cont["image"] = f"evil.io/devpages:{j}"
                c.add_data(o)
            ex = jd.executor
            h2d0 = ex.h2d_bytes + ex.h2d_scatter_bytes
            t0 = time.perf_counter()
            results, _ = jd.query_audit(TARGET_NAME, opts)
            wall = time.perf_counter() - t0
            h2d = (ex.h2d_bytes + ex.h2d_scatter_bytes) - h2d0
            verdicts = sorted(
                ((r.constraint or {}).get("kind", ""),
                 ((r.constraint or {}).get("metadata") or {}).get(
                     "name", ""),
                 ((r.resource or {}).get("metadata") or {}).get(
                     "name", ""),
                 r.msg)
                for r in results)
            stanza = dict(jd.last_sweep_phases.get("devpages") or {})
            return verdicts, wall, h2d, stanza
        finally:
            jd_mod.SMALL_WORKLOAD_EVALS = saved
            for key, prev in prev_env.items():
                if prev is None:
                    os.environ.pop(key, None)
                else:
                    os.environ[key] = prev

    out = {"n_resources": n}
    for label, n_churn in (("churn_0p1", max(n // 1000, 1)),
                           ("churn_1p0", max(n // 100, 1))):
        v_or, or_s, or_h2d, _ = run("off", "off", "off", "off", n_churn)
        v_host, host_s, host_h2d, _ = run("off", "on", "on", "off",
                                          n_churn)
        v_dev, dev_s, dev_h2d, stanza = run("on", "on", "on", "on",
                                            n_churn)
        parity = v_or == v_host == v_dev
        digest = hashlib.sha256(repr(v_dev).encode()).hexdigest()[:16]
        reduction = round(or_h2d / dev_h2d, 2) if dev_h2d else None
        out[label] = {
            "churn_rows": n_churn,
            "parity": parity,
            "parity_digest": digest,
            "kinds_device": stanza.get("kinds_device", 0),
            "kinds_fallback": stanza.get("kinds_fallback", 0),
            "scatter_rows": stanza.get("scatter_rows", 0),
            "delta_events": stanza.get("delta_events", 0),
            "rows_confirmed": stanza.get("rows_confirmed", 0),
            "direct_clears": stanza.get("direct_clears", 0),
            "inv_joins_device": stanza.get("inv_joins_device", 0),
            "devpages_h2d_bytes": dev_h2d,
            "host_paged_h2d_bytes": host_h2d,
            "oracle_h2d_bytes": or_h2d,
            "h2d_reduction": reduction,
            "devpages_seconds": round(dev_s, 4),
            "host_paged_seconds": round(host_s, 4),
            "oracle_seconds": round(or_s, 4),
        }
        log(f"[devpages-churn] {label}: {n_churn} row(s) churned | "
            f"H2D dev {dev_h2d}B vs host {host_h2d}B vs oracle "
            f"{or_h2d}B ({reduction}x under re-stage oracle) | "
            f"kinds_device={stanza.get('kinds_device', 0)} "
            f"scatter_rows={stanza.get('scatter_rows', 0)} "
            f"delta_events={stanza.get('delta_events', 0)} | "
            f"parity={parity} digest={digest}")
        if not parity:
            raise AssertionError(
                f"devpages-churn verdict mismatch at {label}: "
                f"oracle={len(v_or)} host={len(v_host)} dev={len(v_dev)}")
    # gate keys: the 0.1%-churn leg carries the H2D-proportional-to-
    # churn claim of record
    out["parity"] = out["churn_0p1"]["parity"] \
        and out["churn_1p0"]["parity"]
    out["parity_digest"] = out["churn_0p1"]["parity_digest"]
    out["h2d_reduction"] = out["churn_0p1"]["h2d_reduction"]
    out["kinds_device"] = out["churn_0p1"]["kinds_device"]
    detail["devpages_churn"] = out


def bench_watch_latency(detail):
    """Event→verdict latency of the continuous-enforcement reactor: a
    FakeCluster mutation flows watch event → page-granular re-eval →
    ledger delta inside one pump, timed per event (p50/p99), against
    the wall a fixed-interval auditor would pay — one full pages-off
    oracle sweep over the same final state.  The live verdicts after
    the whole event stream must be bit-identical to that oracle; the
    parity digest rides the headline and is gated in ci.sh."""
    import copy
    from gatekeeper_tpu.cluster.fake import FakeCluster, gvk_of
    from gatekeeper_tpu.enforce.reactor import Reactor
    from gatekeeper_tpu.engine import jax_driver as jd_mod

    n = sized(BASELINE_N, 300, 800)
    n_events = sized(100, 40, 60)
    log(f"[watch-latency] n={n}, {n_events} events, reactor vs sweep")
    rng = random.Random(29)
    resources = make_mixed(rng, n)
    opts = QueryOpts(limit_per_constraint=CAP)
    full_opts = QueryOpts(limit_per_constraint=CAP, full=True)

    def mk_client():
        jd = JaxDriver()
        c = Backend(jd).new_client([K8sValidationTarget()])
        for tdoc, cdoc in all_docs():
            c.add_template(tdoc)
            c.add_constraint(cdoc)
        return jd, c

    def verdicts_of(results):
        return sorted(
            ((r.constraint or {}).get("kind", ""),
             ((r.constraint or {}).get("metadata") or {}).get("name", ""),
             (((r.resource or {}).get("metadata") or {}).get("name")
              or (r.review or {}).get("name", "")),
             r.msg) for r in results)

    prev_pg = os.environ.get("GATEKEEPER_PAGES")
    os.environ["GATEKEEPER_PAGES"] = "on"
    saved = jd_mod.SMALL_WORKLOAD_EVALS
    try:
        if not FALLBACK:
            jd_mod.SMALL_WORKLOAD_EVALS = 0
        cluster = FakeCluster()
        for o in resources:
            cluster.create(copy.deepcopy(o))
        gvks = sorted({gvk_of(o) for o in resources},
                      key=lambda g: g.kind)
        jd, c = mk_client()
        c.add_data_batch(
            copy.deepcopy([o for g in gvks for o in cluster.list(g)]))
        rx = Reactor(c, cluster=cluster, apply_objects=True, seed=29)
        for g in gvks:
            rx.attach(g)
        jd.query_audit(TARGET_NAME, full_opts)      # compile warm
        jd.query_audit(TARGET_NAME, opts)           # ledger built
        churn_rng = random.Random(31)
        pods = [o for o in resources
                if (o.get("spec") or {}).get("containers")]
        lat = []
        for j in range(n_events):
            src = churn_rng.choice(pods) if j % 2 else \
                churn_rng.choice(resources)
            cur = cluster.get(gvk_of(src), src["metadata"]["name"],
                              src["metadata"].get("namespace"))
            o = copy.deepcopy(cur)
            if j % 2 and (o.get("spec") or {}).get("containers"):
                # verdict-flipping edit inside the image read-sets
                for cont in o["spec"]["containers"]:
                    cont["image"] = f"evil.io/watch:{j}"
            else:
                o.setdefault("metadata", {}).setdefault(
                    "labels", {})["bench-watch"] = f"r{j}"
            t0 = time.perf_counter()
            cluster.update(o)
            rx.pump()                   # event → page re-eval → delta
            lat.append(time.perf_counter() - t0)
        assert rx.counters["events"] >= n_events
        live = verdicts_of(jd.query_audit(TARGET_NAME, opts)[0])
        # the fixed-interval baseline: one full pages-off sweep over
        # the same final cluster state (what every audit tick costs
        # when there is no event→page coupling)
        jdo, co = mk_client()
        co.add_data_batch(
            copy.deepcopy([o for g in gvks for o in cluster.list(g)]))
        os.environ["GATEKEEPER_PAGES"] = "off"
        try:
            jdo.query_audit(TARGET_NAME, full_opts)     # compile warm
            t0 = time.perf_counter()
            oracle = verdicts_of(jdo.query_audit(TARGET_NAME, opts)[0])
            sweep_s = time.perf_counter() - t0
        finally:
            os.environ["GATEKEEPER_PAGES"] = "on"
        parity = live == oracle
        digest = hashlib.sha256(repr(live).encode()).hexdigest()[:16]
        lat.sort()
        p50 = lat[len(lat) // 2]
        p99 = lat[min(len(lat) - 1, int(len(lat) * 0.99))]
        out = {
            "n_resources": n,
            "events": len(lat),
            "p50_ms": round(p50 * 1e3, 3),
            "p99_ms": round(p99 * 1e3, 3),
            "sweep_oracle_ms": round(sweep_s * 1e3, 3),
            "p50_vs_sweep_ratio": round(p50 / sweep_s, 4)
            if sweep_s else None,
            "coalesced_pages": rx.counters.get("coalesced_pages", 0),
            "parity": parity,
            "parity_digest": digest,
        }
        log(f"[watch-latency] p50={p50*1e3:.2f}ms p99={p99*1e3:.2f}ms "
            f"vs sweep {sweep_s*1e3:.0f}ms | events={len(lat)} | "
            f"parity={parity} digest={digest}")
        if not parity:
            raise AssertionError(
                f"watch-latency verdict mismatch: live={len(live)} "
                f"oracle={len(oracle)}")
        detail["watch_latency"] = out
    finally:
        jd_mod.SMALL_WORKLOAD_EVALS = saved
        if prev_pg is None:
            os.environ.pop("GATEKEEPER_PAGES", None)
        else:
            os.environ["GATEKEEPER_PAGES"] = prev_pg


_SHARD_SIM_CHILD = r"""
import copy, hashlib, json, os, random, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
try:
    jax.config.update("jax_num_cpu_devices", 4)
except Exception:
    pass    # XLA_FLAGS fallback came in via the environment
sys.path.insert(0, os.environ["SHARD_SIM_REPO"])
from gatekeeper_tpu.engine import jax_driver as jd_mod
jd_mod.SMALL_WORKLOAD_EVALS = 0
from gatekeeper_tpu.client.client import Backend
from gatekeeper_tpu.client.interface import QueryOpts
from gatekeeper_tpu.library import all_docs, make_mixed
from gatekeeper_tpu.target.k8s import K8sValidationTarget, TARGET_NAME

n = int(os.environ["SHARD_SIM_N"])
resources = make_mixed(random.Random(17), n)
opts = QueryOpts(limit_per_constraint=20, full=True)

def digest_of(results):
    verdicts = sorted(
        ((r.constraint or {}).get("kind", ""),
         ((r.constraint or {}).get("metadata") or {}).get("name", ""),
         ((r.resource or {}).get("metadata") or {}).get("name", ""),
         r.msg)
        for r in results)
    return hashlib.sha256(repr(verdicts).encode()).hexdigest()[:16]

out = {"n": n}
for ns in (1, 2, 4):
    os.environ["GATEKEEPER_SHARDS"] = str(ns)
    jd = jd_mod.JaxDriver()
    c = Backend(jd).new_client([K8sValidationTarget()])
    for tdoc, cdoc in all_docs():
        c.add_template(tdoc)
        c.add_constraint(cdoc)
    c.add_data_batch(copy.deepcopy(resources))
    jd.query_audit(TARGET_NAME, opts)           # compile warm
    t0 = time.perf_counter()
    results, _ = jd.query_audit(TARGET_NAME, opts)
    wall = time.perf_counter() - t0
    out[str(ns)] = {"digest": digest_of(results),
                    "n_results": len(results),
                    "wall_seconds": round(wall, 4),
                    "stanza": jd.last_sweep_phases.get("shard") or {}}
print(json.dumps(out))
"""


def bench_shard_sim(detail):
    """Stage-6 plan-driven simulated-mesh sweep at library scale: the
    full library over a mixed inventory on 2- and 4-shard simulated
    CPU meshes (GATEKEEPER_SHARDS=N) vs the unsharded oracle
    (GATEKEEPER_SHARDS=1), in ONE subprocess pinned to 4 CPU devices
    (the device count is frozen at first backend use, so the parent
    process cannot host this).  A PARITY row per the ROADMAP caveat —
    simulated shards on cpu measure correctness and collective
    plumbing, not device speed.  Verdicts must be bit-identical
    (sha256 digest) across all three sweeps."""
    import subprocess

    from gatekeeper_tpu.utils.device_probe import child_env

    n = sized(BASELINE_N, 400, 1_000)
    log(f"[shard-sim] n={n}, shards 2 and 4 vs unsharded oracle "
        "(subprocess, 4 cpu devices)")
    repo = os.path.dirname(os.path.abspath(__file__))
    env = child_env(dict(os.environ))
    env["JAX_PLATFORMS"] = "cpu"
    env["SHARD_SIM_REPO"] = repo
    env["SHARD_SIM_N"] = str(n)
    env.pop("GATEKEEPER_SHARDS", None)
    flags = env.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4").strip()
    proc = subprocess.run(
        [sys.executable, "-c", _SHARD_SIM_CHILD], env=env, cwd=repo,
        capture_output=True, text=True, timeout=280)
    if proc.returncode != 0:
        raise AssertionError(
            f"shard_sim child failed rc={proc.returncode}: "
            f"{proc.stderr[-800:]}")
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    oracle = data["1"]["digest"]
    row = {"n_resources": data["n"], "oracle_digest": oracle,
           "oracle_seconds": data["1"]["wall_seconds"]}
    parity = True
    for ns in ("2", "4"):
        d = data[ns]
        stanza = d["stanza"]
        ok = d["digest"] == oracle
        parity = parity and ok
        row[f"shards_{ns}"] = {
            "parity": ok,
            "digest": d["digest"],
            "wall_seconds": d["wall_seconds"],
            "mesh_shards": stanza.get("shards", 0),
            "kinds_sharded": stanza.get("kinds_sharded", 0),
            "kinds_replicated": stanza.get("kinds_replicated", 0),
            "per_shard_evals": stanza.get("per_shard_evals", 0),
            "collectives": stanza.get("collectives", 0),
        }
        log(f"[shard-sim] {ns} shards: parity={ok} "
            f"digest={d['digest']} "
            f"sharded={stanza.get('kinds_sharded', 0)} "
            f"replicated={stanza.get('kinds_replicated', 0)} "
            f"per_shard_evals={stanza.get('per_shard_evals', 0)} "
            f"collectives={stanza.get('collectives', 0)}")
    row["parity"] = parity
    row["parity_digest"] = oracle
    detail["shard_sim"] = row
    if not parity:
        raise AssertionError(
            f"shard_sim parity mismatch vs oracle {oracle}: "
            + ", ".join(f"{ns}={data[ns]['digest']}" for ns in ("2", "4")))


def bench_whatif(detail):
    """What-if engine rows (ROADMAP item 5), one phase, three rows:

    - ``shadow_sweep``: stage a library-scale candidate set beside the
      live one and audit BOTH in one sweep; the acceptance gate is the
      combined wall at < 1.5x a single-set sweep (damped) with the
      candidate half bit-identical (sha256) to a standalone install;
    - ``replay``: re-audit the live store snapshot in a fresh driver —
      digest parity with the live sweep — plus a recorded admission
      stream replayed exactly;
    - ``fleet_stack``: 4 clusters stacked along a leading cluster axis,
      one vmapped mega-sweep, bit-identical to the per-cluster loop
      oracle.  In-process: the vmap needs one device, no subprocess."""
    from gatekeeper_tpu.whatif import (ShadowSession, fleet_audit,
                                       fleet_loop_oracle, make_cluster,
                                       normalize_results, replay_admissions,
                                       replay_snapshot,
                                       standalone_candidate_verdicts,
                                       verdict_digest)

    # quick mode keeps the full 20k rows: below SMALL_WORKLOAD_EVALS the
    # sweep routes to the scalar oracle and the <1.5x combined-wall gate
    # would be measuring the wrong engine
    n = sized(20_000, 1_000, 20_000)
    log(f"[whatif] n={n}, library shadow sweep / replay / 4-cluster stack")
    templates = [t for t, _c in all_docs()]
    constraints = [c for _t, c in all_docs()]
    jd = JaxDriver()
    handler = K8sValidationTarget()
    c = Backend(jd).new_client([handler])
    for tdoc, cdoc in all_docs():
        c.add_template(tdoc)
        c.add_constraint(cdoc)
    c.add_data_batch(make_mixed(random.Random(7), n))
    state = jd._state(TARGET_NAME).table.snapshot_state()

    # single-set wall (warm best-of-2) and the live verdict baseline
    c.audit(limit_per_constraint=CAP, full=True)
    single_s = math.inf
    for _ in range(2):
        t0 = time.perf_counter()
        resp = c.audit(limit_per_constraint=CAP, full=True)
        single_s = min(single_s, time.perf_counter() - t0)
    baseline = normalize_results(resp.results())
    live_digest = verdict_digest(baseline)

    # --- shadow_sweep ---------------------------------------------------
    candidate = constraints[1:]
    with ShadowSession(c, tag="candidate") as sess:
        sess.stage(templates, candidate)
        sess.sweep(limit_per_constraint=CAP)         # compile/warm
        t0 = time.perf_counter()
        rep = sess.sweep(limit_per_constraint=CAP)
        combined_s = time.perf_counter() - t0
    oracle = standalone_candidate_verdicts(templates, candidate, state, CAP)
    parity = rep.shadow == oracle and rep.live == baseline
    within = combined_s <= single_s * 1.5 + 0.25
    twin = (jd.last_sweep_phases.get("whatif") or {})
    detail["shadow_sweep"] = {
        "n_resources": n,
        "single_set_seconds": round(single_s, 3),
        "combined_seconds": round(combined_s, 3),
        "ratio": round(combined_s / single_s, 3) if single_s else None,
        "within_budget": within,
        "parity": parity,
        "parity_digest": rep.shadow_digest,
        "added": len(rep.added), "cleared": len(rep.cleared),
        "twin_shared_kinds": twin.get("twin_shared_kinds", 0),
        "dedup_groups_cross_version": rep.dedup["groups_cross_version"],
        "dedup_sites_cross_version": rep.dedup["sites_cross_version"],
    }
    log(f"[whatif] shadow: single {single_s:.2f}s combined "
        f"{combined_s:.2f}s ({combined_s / max(single_s, 1e-9):.2f}x) "
        f"parity={parity} twin_shared={twin.get('twin_shared_kinds', 0)} "
        f"shared_groups={rep.dedup['groups_cross_version']}")

    # --- replay ---------------------------------------------------------
    rrep = replay_snapshot(templates, constraints, state, CAP)
    snap_parity = rrep.verdicts == baseline
    stream_match = None
    saved_env = {k: os.environ.get(k) for k in
                 ("GATEKEEPER_FLIGHT_DIR", "GATEKEEPER_FLIGHT_ADMISSION")}
    corpus_dir = tempfile.mkdtemp(prefix="gk-whatif-corpus-")
    try:
        from gatekeeper_tpu.obs import flightrecorder as fr
        from gatekeeper_tpu.webhook.policy import ValidationHandler
        os.environ["GATEKEEPER_FLIGHT_DIR"] = corpus_dir
        os.environ["GATEKEEPER_FLIGHT_ADMISSION"] = "1"
        wh = ValidationHandler(c)
        rec = fr.FlightRecorder(ring=64)
        saved_rec, fr._recorder = fr._recorder, rec
        try:
            for obj in make_mixed(random.Random(11), 32):
                wh.handle({"uid": "u", "operation": "CREATE",
                           "kind": {"group": "", "version": "v1",
                                    "kind": obj.get("kind", "")},
                           "userInfo": {"username": "bench", "groups": []},
                           "object": obj})
        finally:
            fr._recorder = saved_rec
        events = fr.load_admission_corpus(corpus_dir)
        srep = replay_admissions(events, c)
        stream_match = srep.exact
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        shutil.rmtree(corpus_dir, ignore_errors=True)
    detail["replay"] = {
        "n_resources": n,
        "wall_seconds": round(rrep.wall_s, 3),
        "parity": snap_parity,
        "parity_digest": rrep.digest,
        "live_digest": live_digest,
        "stream_replayed": srep.replayed,
        "stream_match": stream_match,
    }
    log(f"[whatif] replay: snapshot parity={snap_parity} "
        f"({rrep.wall_s:.2f}s), stream {srep.replayed} events "
        f"exact={stream_match}")
    del wh, resp, c, jd
    import gc as _gc
    _gc.collect()

    # --- fleet_stack ----------------------------------------------------
    n_clusters = 4
    per = max(n // (n_clusters * 2), 50)
    fleet = [make_cluster(f"c{i}", templates, constraints,
                          objs=make_mixed(random.Random(100 + i), per))
             for i in range(n_clusters)]
    fleet_audit(fleet, CAP)                          # compile/warm
    t0 = time.perf_counter()
    frep = fleet_audit(fleet, CAP)
    stacked_s = time.perf_counter() - t0
    _v, digests, loop_s = fleet_loop_oracle(fleet, CAP)
    fparity = frep.digests == digests
    detail["fleet_stack"] = {
        "clusters": n_clusters,
        "rows_per_cluster": per,
        "parity": fparity,
        "digests": frep.digests,
        "kinds_stacked": len(frep.kinds_stacked),
        "kinds_replicated": len(frep.kinds_replicated),
        "device_dispatches": frep.device_dispatches,
        "stacked_seconds": round(stacked_s, 3),
        "loop_seconds": round(loop_s, 3),
    }
    log(f"[whatif] fleet: {n_clusters}x{per} rows parity={fparity} "
        f"stacked {stacked_s:.2f}s vs loop {loop_s:.2f}s "
        f"({len(frep.kinds_stacked)} stacked / "
        f"{len(frep.kinds_replicated)} replicated kinds)")
    if not parity:
        raise AssertionError(
            f"shadow parity mismatch: sweep {rep.shadow_digest} vs "
            f"standalone {verdict_digest(oracle)}")
    if not snap_parity:
        raise AssertionError(
            f"replay parity mismatch: {rrep.digest} vs {live_digest}")
    if not fparity:
        raise AssertionError(
            f"fleet parity mismatch: {frep.digests} vs {digests}")


def bench_promotion(detail):
    """Policy promotion pipeline rows (ROADMAP item 5, PR 18):

    - ``replay_speedup``: the shadow→replayed evidence gate's batched
      corpus replay (``client.review_batch``, the device micro-batch
      seam forced eligible) vs the scalar per-event oracle — gate ≥3x,
      with the sha256 stream digests bit-identical on both paths.
      Measured on the regime the micro-batcher exists for: a
      constraint-dense policy set (200 constraints, the
      admission_device_batch shape) over a recorded-ALLOWED corpus —
      the promotion gate's own precondition — where the device mask
      over-approximation gates nearly every (constraint, review) pair
      out and host re-verify collapses; a violator-heavy corpus makes
      both paths re-verify everything and measures nothing;
    - ``promote``: end-to-end PromotionController run candidate→deny
      over a mixed recorded corpus on the full library client (wall +
      replay-gate evidence);
    - ``fleet``: 4-cluster ``graduate_fleet`` map-reduce promotion wall.

    Deliberately sized ≤2k rows: the gates are RATIOS and digests, not
    absolute walls, so the row also validates on the CPU fallback —
    the north-star-sized phases are where absolute numbers live."""
    import gatekeeper_tpu.engine.jax_driver as jd_mod
    from gatekeeper_tpu.obs import flightrecorder as fr
    from gatekeeper_tpu.rollout import PromotionController, graduate_fleet
    from gatekeeper_tpu.webhook.policy import ValidationHandler
    from gatekeeper_tpu.whatif import make_cluster
    from gatekeeper_tpu.whatif.replay import (replay_admissions,
                                              replay_admissions_batched)

    n = sized(2_000, 400, 2_000)
    log(f"[promotion] n={n}, replay gate / controller / 4-cluster fleet")
    templates = [t for t, _c in all_docs()]
    constraints = [c for _t, c in all_docs()]
    candidate = constraints[1:]

    def _record(client, objs, directory):
        """Record ``objs`` through the webhook handler into the durable
        capture log at ``directory`` (the same store probe --rollout
        health-checks) and return the decoded corpus."""
        os.environ["GATEKEEPER_FLIGHT_DIR"] = directory
        os.environ["GATEKEEPER_FLIGHT_ADMISSION"] = "1"
        wh = ValidationHandler(client)
        rec = fr.FlightRecorder(ring=64)
        saved_rec, fr._recorder = fr._recorder, rec
        try:
            for obj in objs:
                wh.handle({"uid": "u", "operation": "CREATE",
                           "kind": {"group": "", "version": "v1",
                                    "kind": obj.get("kind", "")},
                           "userInfo": {"username": "bench", "groups": []},
                           "object": obj})
        finally:
            fr._recorder = saved_rec
            try:
                if rec._capture is not None:
                    rec._capture.close()
            except Exception:   # noqa: BLE001
                pass
        return fr.load_admission_corpus(directory)

    saved_env = {k: os.environ.get(k) for k in
                 ("GATEKEEPER_FLIGHT_DIR", "GATEKEEPER_FLIGHT_ADMISSION",
                  "GATEKEEPER_SNAPSHOT_DIR")}
    work = tempfile.mkdtemp(prefix="gk-promotion-")
    saved_thresh = jd_mod.REVIEW_BATCH_MIN_EVALS
    try:
        os.environ["GATEKEEPER_SNAPSHOT_DIR"] = os.path.join(work, "snaps")

        # --- replay_speedup: the evidence-gate hot path ----------------
        # (device-path measurement: with a dead backend review_batch
        # routes to the scalar loop and the ratio measures nothing —
        # skip it like admission_device_batch does)
        if FALLBACK:
            revents, srep = [], None
            s_s = b_s = speedup = parity = None
            log("[promotion] replay gate skipped "
                "(device backend unavailable)")
        else:
            rng = random.Random(5)
            rjd = JaxDriver()
            rc = Backend(rjd).new_client([K8sValidationTarget()])
            rc.add_template(template_doc("K8sRequiredLabels",
                                         REQUIRED_LABELS))
            rc.add_template(template_doc("K8sAllowedRepos", ALLOWED_REPOS))
            for j in range(100):
                rc.add_constraint(constraint_doc(
                    "K8sRequiredLabels", f"lab-{j:03d}",
                    {"labels": rng.sample([f"l{x}" for x in range(10)],
                                          k=2)}))
                rc.add_constraint(constraint_doc(
                    "K8sAllowedRepos", f"rep-{j:03d}",
                    {"repos": ["gcr.io/",
                               rng.choice(["docker.io/", "quay.io/",
                                           "ghcr.io/"])]}))
            rc.add_data_batch(make_mixed(random.Random(29), min(n, 500)))
            clean = [{"apiVersion": "v1", "kind": "Pod",
                      "metadata": {"name": f"clean-{i:03d}",
                                   "namespace": "default",
                                   "labels": {f"l{x}": "v"
                                              for x in range(10)}},
                      "spec": {"containers": [
                          {"name": "app",
                           "image": f"gcr.io/proj/app:{i}"}]}}
                     for i in range(64)]
            revents = _record(rc, clean, os.path.join(work, "replay")) * 8
            jd_mod.REVIEW_BATCH_MIN_EVALS = 1   # force the [B, C] pass
            brep = replay_admissions_batched(revents, rc,
                                             batch_size=len(revents))
            b_s = math.inf
            for _ in range(2):
                t0 = time.perf_counter()
                brep = replay_admissions_batched(revents, rc,
                                                 batch_size=len(revents))
                b_s = min(b_s, time.perf_counter() - t0)
            srep = replay_admissions(revents, rc)                  # warm
            s_s = math.inf
            for _ in range(2):
                t0 = time.perf_counter()
                srep = replay_admissions(revents, rc)
                s_s = min(s_s, time.perf_counter() - t0)
            speedup = s_s / max(b_s, 1e-9)
            parity = (srep.digest == brep.digest
                      and srep.replayed == brep.replayed)
            log(f"[promotion] replay: {len(revents)} events scalar "
                f"{s_s:.3f}s batched {b_s:.3f}s ({speedup:.1f}x) "
                f"parity={parity} digest={srep.digest}")
            del rc, rjd

        # --- promote: candidate → deny on the library client -----------
        jd = JaxDriver()
        c = Backend(jd).new_client([K8sValidationTarget()])
        for tdoc, cdoc in all_docs():
            c.add_template(tdoc)
            c.add_constraint(cdoc)
        c.add_data_batch(make_mixed(random.Random(29), n))
        events = _record(c, make_mixed(random.Random(31), 48),
                         os.path.join(work, "promo")) * 4
        ctrl = PromotionController(c, templates, candidate,
                                   name="bench", events=events,
                                   limit_per_constraint=CAP)
        t0 = time.perf_counter()
        final = ctrl.run(target_rung="deny")
        promote_s = time.perf_counter() - t0
        gate = ctrl.evidence.get("replay_gate", {})
        rungs = [h["to"] for h in ctrl.history]
        log(f"[promotion] promote: {' -> '.join(rungs)} in {promote_s:.2f}s "
            f"({gate.get('unexpected_denials', '?')} unexpected denials)")
    finally:
        jd_mod.REVIEW_BATCH_MIN_EVALS = saved_thresh
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        shutil.rmtree(work, ignore_errors=True)
    del ctrl, c, jd
    import gc as _gc
    _gc.collect()

    # --- fleet: 4-cluster map-reduce graduation ------------------------
    n_clusters = 4
    per = max(n // (n_clusters * 2), 50)
    fleet = [make_cluster(f"p{i}", templates, constraints,
                          objs=make_mixed(random.Random(200 + i), per))
             for i in range(n_clusters)]
    graduate_fleet(fleet, templates, candidate,
                   limit_per_constraint=CAP, block_size=2)   # compile/warm
    frep = graduate_fleet(fleet, templates, candidate,
                          limit_per_constraint=CAP, block_size=2)
    log(f"[promotion] {frep.headline()}")

    detail["promotion"] = {
        "n_resources": n,
        "replay_events": len(revents),
        "promo_events": len(events),
        "scalar_seconds": s_s if s_s is None else round(s_s, 3),
        "batched_seconds": b_s if b_s is None else round(b_s, 3),
        "replay_speedup": speedup if speedup is None
        else round(speedup, 2),
        "parity": parity,
        "parity_digest": srep.digest if srep is not None else None,
        "final_rung": final,
        "rungs": rungs,
        "unexpected_denials": gate.get("unexpected_denials"),
        "promote_wall_s": round(promote_s, 3),
        "fleet_clusters": n_clusters,
        "fleet_rows_per_cluster": per,
        "fleet_graduated": frep.graduated,
        "fleet_blocks": frep.n_blocks,
        "fleet_dispatches": frep.device_dispatches,
        "fleet_wall_s": round(frep.wall_s, 3),
    }
    if parity is False:
        raise AssertionError(
            f"promotion replay parity mismatch: scalar {srep.digest} vs "
            f"batched {brep.digest}")
    if final != "deny" or gate.get("unexpected_denials") != 0:
        raise AssertionError(
            f"promotion did not graduate cleanly: final={final} "
            f"gate={gate}")
    if frep.graduated != n_clusters:
        raise AssertionError(f"fleet graduation incomplete: "
                             f"{frep.headline()}")
    if speedup is not None and speedup < 3.0:
        raise AssertionError(
            f"batched replay speedup {speedup:.2f}x below the 3x gate "
            f"(scalar {s_s:.3f}s vs batched {b_s:.3f}s)")


def bench_transval(detail):
    """Stage-4 translation validation at library scale: certify every
    device-lowered built-in template against the interpreter on its
    bounded small-model universe.  The whole library must certify
    (0 counterexamples) and the pass must stay well inside the 60s
    budget ci.sh gives the certify stage — it runs at install time."""
    from gatekeeper_tpu.analysis import transval
    from gatekeeper_tpu.api.templates import compile_target_rego
    from gatekeeper_tpu.ir.lower import CannotLower, lower_template
    from gatekeeper_tpu.library import all_docs

    t0 = time.perf_counter()
    n_cert = n_pin = n_ce = models = 0
    for tdoc, cdoc in all_docs():
        kind = ((tdoc.get("spec") or {}).get("crd") or {}) \
            .get("spec", {}).get("names", {}).get("kind") \
            or tdoc.get("metadata", {}).get("name", "?")
        tt = ((tdoc.get("spec") or {}).get("targets") or [{}])[0]
        compiled = compile_target_rego(
            kind, tt.get("target") or "", tt.get("rego") or "")
        try:
            lowered = lower_template(compiled.module, compiled.interp)
        except CannotLower:
            n_pin += 1
            continue
        res = transval.validate_template(kind, compiled, lowered, [cdoc])
        if isinstance(res, transval.Certificate):
            n_cert += 1
            models += res.models_checked
        else:
            n_ce += 1
    wall = time.perf_counter() - t0
    detail["transval"] = {
        "certify_wall_seconds": round(wall, 3),
        "templates_certified": n_cert,
        "templates_pinned": n_pin,
        "counterexamples": n_ce,
        "models_checked": models,
    }
    log(f"[transval] {n_cert} certified, {n_pin} pinned, {n_ce} "
        f"counterexample(s), {models} models in {wall*1e3:.0f}ms")
    if n_ce:
        raise AssertionError(
            f"{n_ce} library template(s) failed translation validation")


def bench_selector_heavy(detail):
    """namespaceSelector-heavy matching at 100k namespaces: the
    namespace-axis selector evaluation is the cost center (VERDICT r2
    weak #5 — previously scalar per-namespace)."""
    n_ns = sized(100_000, 2_000, 2_000)
    rng = random.Random(8)
    resources = []
    for i in range(n_ns):
        labels = {"team": rng.choice(["a", "b", "c", "d"]),
                  "stage": rng.choice(["dev", "prod"])}
        if rng.random() < 0.5:
            labels["owner"] = f"u{rng.randrange(64)}"
        resources.append({"apiVersion": "v1", "kind": "Namespace",
                          "metadata": {"name": f"ns-{i:06d}",
                                       "labels": labels}})
    for i in range(n_ns // 4):                    # pods spread across ns
        resources.append({
            "apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": f"p-{i:06d}",
                         "namespace": f"ns-{rng.randrange(n_ns):06d}",
                         "labels": {"app": rng.choice(["x", "y"])}},
            "spec": {"containers": [{"name": "c",
                                     "image": "gcr.io/app:latest"}]}})
    constraints = []
    for j in range(8):
        constraints.append(constraint_doc(
            "K8sRequiredLabels", f"sel-{j}", {"labels": ["owner"]},
            match={"namespaceSelector": {
                "matchExpressions": [
                    {"key": "team", "operator": "In",
                     "values": [rng.choice(["a", "b", "c", "d"])]},
                    {"key": "stage", "operator":
                        rng.choice(["Exists", "DoesNotExist"])}]}}))
    bench_two_engines(
        detail, f"selector_heavy_{n_ns}_namespaces", resources,
        [template_doc("K8sRequiredLabels", REQUIRED_LABELS)],
        constraints, oracle_n=2_000)


def bench_compile_surface(detail):
    """Stage-7 compile-surface certification row: full library install
    under ``GATEKEEPER_COMPILE_SURFACE=strict``, certificate coverage
    + AOT precompile at prepare_audit, then a full sweep and memoized
    steady sweeps whose every jit dispatch must stay inside the
    certified surface (``uncertified_retraces == 0`` is the gate).

    Deliberately sized ≤2k rows and NEVER at north-star N: the gates
    here are coverage counts and a zero counter, not a wall — and the
    20000x201 matrix hangs the CPU watchdog on fallback containers."""
    from gatekeeper_tpu.analysis import compilesurface as cs_mod

    n = sized(2_000, 400, 2_000)
    log(f"[compile_surface] n={n}, strict certification + steady sweep")
    saved_mode = os.environ.get("GATEKEEPER_COMPILE_SURFACE")
    os.environ["GATEKEEPER_COMPILE_SURFACE"] = "strict"
    try:
        pre0 = cs_mod.precompiles_run
        jd = JaxDriver()
        client = Backend(jd).new_client([K8sValidationTarget()])
        for tdoc, cdoc in all_docs():
            client.add_template(tdoc)
            client.add_constraint(cdoc)
        client.add_data_batch(make_mixed(random.Random(11), n))
        t0 = time.perf_counter()
        jd.prepare_audit(TARGET_NAME)       # certify + AOT precompile
        prepare_s = time.perf_counter() - t0
        st = jd.state[TARGET_NAME]
        certs = getattr(st, "compilesurfaces", {})
        certified = sum(1 for c in certs.values()
                        if c.bounded and not c.scalar_pin)
        pinned = sum(1 for c in certs.values() if c.scalar_pin)
        n_unbounded = sum(1 for c in certs.values() if not c.bounded)
        t0 = time.perf_counter()
        results, _ = jd.query_audit(TARGET_NAME, QueryOpts(full=True))
        full_s = time.perf_counter() - t0
        steady: list[float] = []
        for _ in range(3):
            t0 = time.perf_counter()
            jd.query_audit(TARGET_NAME, QueryOpts(full=True))
            steady.append(time.perf_counter() - t0)
        uncertified = getattr(jd.executor, "retrace_uncertified", 0)
        row = {
            "n_resources": n,
            "templates": len(certs),
            "certified": certified,
            "pinned": pinned,
            "unbounded": n_unbounded,
            "signatures_certified": sum(
                c.n_signatures for c in certs.values() if c.bounded),
            "aot_precompiles": cs_mod.precompiles_run - pre0,
            "uncertified_retraces": uncertified,
            "prepare_seconds": round(prepare_s, 3),
            "full_seconds": round(full_s, 3),
            "steady_seconds": round(min(steady), 4) if steady else None,
            "n_results": len(results),
            # scalar-only fallback pins everything: coverage is vacuous
            # there, so the gate only binds on a device-capable run
            "ok": (uncertified == 0 and n_unbounded == 0
                   and (certified >= 45 or FALLBACK)),
        }
        detail["compile_surface"] = row
        log(f"[compile_surface] {certified} certified, {pinned} pinned, "
            f"{n_unbounded} unbounded, "
            f"{row['aot_precompiles']} AOT precompile(s), "
            f"uncertified_retraces={uncertified}")
    finally:
        if saved_mode is None:
            os.environ.pop("GATEKEEPER_COMPILE_SURFACE", None)
        else:
            os.environ["GATEKEEPER_COMPILE_SURFACE"] = saved_mode


def bench_mem_surface(detail):
    """Stage-8 memory-surface row: the certified peak-HBM claims
    validated against the live-buffer high-water a real library sweep
    actually reaches (``jax.live_arrays`` byte census), plus the
    certificate-driven devpages residency planner's spill/restore path
    proven bit-identical to the always-resident oracle under a forced
    tiny ``GATEKEEPER_DEVPAGES_BUDGET_BYTES``.

    The contract is one-sided over-approximation: the predicted
    resident claim at the deployment's actual pad geometry must be >=
    the measured array census (an analyzer that under-predicts is
    broken) while staying within 3x (an analyzer that over-predicts
    unboundedly certifies nothing useful); the full peak claim — which
    additionally bounds the XLA-fused SSA transients and devpages
    staging the census cannot observe — rides beside it, >= by
    construction.  Sized <=2k rows and NEVER at north-star N: the gates are
    a ratio band and a parity digest, not a wall — and the 20000x201
    matrix hangs the CPU watchdog on fallback containers."""
    import copy

    import jax

    from gatekeeper_tpu.analysis import memsurface as ms_mod
    from gatekeeper_tpu.engine import jax_driver as jd_mod
    from gatekeeper_tpu.ir.prep import audit_pads, interner_bucket

    n = sized(2_000, 400, 1_000)
    log(f"[mem_surface] n={n}, predicted-vs-measured + spill parity")
    rng = random.Random(23)
    resources = make_mixed(rng, n)
    opts = QueryOpts(limit_per_constraint=CAP)
    full_opts = QueryOpts(limit_per_constraint=CAP, full=True)
    env_keys = ("GATEKEEPER_HBM_BUDGET", "GATEKEEPER_DEVPAGES",
                "GATEKEEPER_PAGES", "GATEKEEPER_FOOTPRINT",
                "GATEKEEPER_DEVPAGES_BUDGET_BYTES")
    prev_env = {k: os.environ.get(k) for k in env_keys}

    def _live() -> int:
        return sum(int(a.nbytes) for a in jax.live_arrays())

    def _restore_env():
        for key, prev in prev_env.items():
            if prev is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = prev

    # ---- leg 1: predicted peak vs measured live-buffer high-water
    os.environ["GATEKEEPER_HBM_BUDGET"] = "strict"
    saved_swe = jd_mod.SMALL_WORKLOAD_EVALS
    try:
        if not FALLBACK:
            # the small-workload heuristic would route this n to the
            # scalar oracle — no device arrays, nothing to measure
            jd_mod.SMALL_WORKLOAD_EVALS = 0
        base_live = _live()
        jd = JaxDriver()
        client = Backend(jd).new_client([K8sValidationTarget()])
        for tdoc, cdoc in all_docs():
            client.add_template(tdoc)
            client.add_constraint(cdoc)
        client.add_data_batch(copy.deepcopy(resources))
        high = 0
        jd.prepare_audit(TARGET_NAME)
        high = max(high, _live() - base_live)
        results, _ = jd.query_audit(TARGET_NAME, full_opts)
        high = max(high, _live() - base_live)
        jd.query_audit(TARGET_NAME, opts)
        high = max(high, _live() - base_live)
        st = jd.state[TARGET_NAME]
        certs = {k: c for k, c in getattr(st, "memsurfaces", {}).items()
                 if not c.scalar_pin}
        # the deployment's actual pad geometry: one constraint per
        # library kind, the shared inventory r/t buckets, the e cap
        r_pad, c_pad = audit_pads(n, 1)
        dims = {"c": c_pad, "r": r_pad,
                "t": interner_bucket(len(st.table.interner))}
        predicted = sum(c.peak_bytes(dims) for c in certs.values())
        # the census sees live *arrays* — the resident set.  SSA
        # transients are XLA-fused (never materialized as trackable
        # buffers) and the devpages staging terms only exist with the
        # device store on, so the band compares the resident claim,
        # evaluated per kind at the geometry the sweep actually built
        # (bindings_cache holds each kind's real Bindings); the full
        # peak (resident + transient + devpages) is reported beside it
        # and is >= by construction.
        resident = 0
        for kind, cert in certs.items():
            hit = st.bindings_cache.get(kind)
            b = hit[1] if hit is not None else None
            if b is None:
                resident += cert.resident_bytes(dims)
                continue
            kd = dict(dims, c=b.c_pad, r=b.r_pad)
            if b.e_pads:
                kd["e"] = max(b.e_pads.values())
            resident += cert.resident_bytes(
                kd, shapes={k: a.shape for k, a in b.arrays.items()})
        ratio = round(resident / high, 2) if high > 0 else None
    finally:
        jd_mod.SMALL_WORKLOAD_EVALS = saved_swe
        _restore_env()

    # ---- leg 2: spill ladder vs always-resident oracle (bit parity)
    def spill_leg(budget: int | None):
        os.environ["GATEKEEPER_DEVPAGES"] = "on"
        os.environ["GATEKEEPER_PAGES"] = "on"
        os.environ["GATEKEEPER_FOOTPRINT"] = "on"
        if budget is None:
            os.environ.pop("GATEKEEPER_DEVPAGES_BUDGET_BYTES", None)
        else:
            os.environ["GATEKEEPER_DEVPAGES_BUDGET_BYTES"] = str(budget)
        saved = jd_mod.SMALL_WORKLOAD_EVALS
        try:
            if not FALLBACK:
                jd_mod.SMALL_WORKLOAD_EVALS = 0
            work = copy.deepcopy(resources)
            jd2 = JaxDriver()
            c2 = Backend(jd2).new_client([K8sValidationTarget()])
            for tdoc, cdoc in all_docs():
                c2.add_template(tdoc)
                c2.add_constraint(cdoc)
            c2.add_data_batch(work)
            jd2.query_audit(TARGET_NAME, full_opts)     # compile warm
            jd2.query_audit(TARGET_NAME, opts)          # resident build
            churn_rng = random.Random(41)
            pod_idx = [i for i, o in enumerate(work)
                       if (o.get("spec") or {}).get("containers")]
            spills = restores = 0
            for j in range(3):
                o = copy.deepcopy(work[churn_rng.choice(pod_idx)])
                for cont in o["spec"]["containers"]:
                    cont["image"] = f"evil.io/memsurface:{j}"
                c2.add_data(o)
                jd2.query_audit(TARGET_NAME, opts)
                dv = jd2.last_sweep_phases.get("devpages") or {}
                spills += dv.get("resident_spills", 0)
                restores += dv.get("resident_restores", 0)
            results2, _ = jd2.query_audit(TARGET_NAME, full_opts)
            return _verdict_digest(results2), spills, restores
        finally:
            jd_mod.SMALL_WORKLOAD_EVALS = saved
            _restore_env()

    d_oracle, _sp0, _rs0 = spill_leg(None)
    d_tiny, spills, restores = spill_leg(8192)
    parity = d_oracle == d_tiny

    row = {
        "n_resources": n,
        "templates_certified": len(certs),
        "predicted_peak_bytes": int(predicted),
        "predicted_resident_bytes": int(resident),
        "measured_high_water_bytes": int(high),
        "ratio": ratio,
        # scalar-only fallback keeps no device arrays live: the band
        # is vacuous there, like compile_surface's coverage gate
        "within_band": bool(ratio is not None
                            and 1.0 <= ratio <= 3.0) or FALLBACK,
        "spill_parity": parity,
        "spill_parity_digest": d_tiny,
        "resident_spills": spills,
        "resident_restores": restores,
        "analyses_run": ms_mod.analyses_run,
        "n_results": len(results),
        "ok": bool(parity and (ratio is None or 1.0 <= ratio <= 3.0
                               or FALLBACK)),
    }
    detail["mem_surface"] = row
    log(f"[mem_surface] peak {predicted / (1 << 20):.1f} MiB, resident "
        f"{resident / (1 << 20):.1f} MiB vs measured "
        f"{high / (1 << 20):.1f} MiB (ratio {ratio}); spill parity "
        f"{parity} ({spills} spill(s), {restores} restore(s))")


def _verdict_digest(results) -> str:
    """Order-independent digest of a full audit result set (same shape
    as resilience/smoke.py's) — the bit-identity oracle the regex rows
    report."""
    items = sorted(
        ((r.constraint or {}).get("kind", ""),
         ((r.constraint or {}).get("metadata") or {}).get("name", ""),
         (r.resource or {}).get("kind", ""),
         str(((r.resource or {}).get("metadata") or {}).get("namespace")),
         ((r.resource or {}).get("metadata") or {}).get("name", ""),
         r.msg)
        for r in results)
    return hashlib.sha256(repr(items).encode()).hexdigest()[:16]


def bench_regex_heavy(detail):
    n = sized(100_000, 2_000, 10_000)
    rng = random.Random(6)
    resources = make_resources(n, rng)
    kinds = ["K8sImageDigests", "K8sDisallowedTags", "K8sNoEnvVarSecrets"]
    templates = [template_doc(k, LIBRARY[k][0]) for k in kinds]
    constraints = [constraint_doc(k, k.lower(), LIBRARY[k][1]) for k in kinds]
    bench_two_engines(detail, f"regex_heavy_{n}", resources, templates,
                      constraints, oracle_n=2_000)
    # in-jit dfa_match vs GATEKEEPER_DFA=off lookup-table parity: the
    # same jax sweep with the DFA lowering disabled is the graduation
    # oracle — both legs must produce a bit-identical verdict digest
    row = dict(detail.get(f"regex_heavy_{n}") or {})
    # the parity legs run even in scalar fallback (smaller subset, like
    # every other parity row) — the digest is the gate, not the wall
    sub = resources[:min(n, 2_000 if FALLBACK else 4_000)]
    digests = {}
    for mode in ("on", "off"):
        prev = os.environ.get("GATEKEEPER_DFA")
        os.environ["GATEKEEPER_DFA"] = mode
        try:
            drv = JaxDriver()
            c = Backend(drv).new_client([K8sValidationTarget()])
            for t in templates:
                c.add_template(t)
            for cd in constraints:
                c.add_constraint(cd)
            c.add_data_batch(sub)
            got, _ = drv.query_audit(TARGET_NAME, QueryOpts(full=True))
            digests[mode] = _verdict_digest(got)
        finally:
            if prev is None:
                os.environ.pop("GATEKEEPER_DFA", None)
            else:
                os.environ["GATEKEEPER_DFA"] = prev
    row["dfa_parity"] = digests["on"] == digests["off"]
    row["parity_digest"] = digests["on"]
    log(f"[regex_heavy] dfa parity {row['dfa_parity']} "
        f"(digest {digests['on']} vs off-oracle {digests['off']})")
    detail["regex_heavy"] = row


def bench_admission_open_loop(detail, handler, reqs):
    """Open-loop (fixed-rate) admission replay: requests fire on a
    schedule regardless of completion, so reported latency includes
    honest queueing delay at that arrival rate — unlike the closed
    32-thread loop below, which measures saturation queueing only
    (round-3 VERDICT weak #3)."""
    import threading

    out = {}
    for rate in (1000, 2000, 4000):
        n = min(len(reqs), max(2000, rate * 3))
        interval = 1.0 / rate
        lat: list[float] = []
        lock = threading.Lock()
        it = iter(range(n))
        start = time.perf_counter() + 0.05

        def worker():
            while True:
                with lock:
                    i = next(it, None)
                if i is None:
                    return
                sched = start + i * interval
                now = time.perf_counter()
                if sched > now:
                    time.sleep(sched - now)
                handler.handle(reqs[i % len(reqs)])
                done = time.perf_counter()
                with lock:
                    lat.append(done - sched)

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - start
        lat.sort()
        p50 = statistics.median(lat)
        p99 = lat[int(0.99 * len(lat))]
        achieved = n / wall
        saturated = achieved < rate * 0.9
        log(f"[admission-open-loop] {rate} rps target: p50 {p50*1e3:.2f}ms "
            f"p99 {p99*1e3:.2f}ms, achieved {achieved:.0f} rps"
            f"{' (SATURATED)' if saturated else ''}")
        out[str(rate)] = {"p50_ms": round(p50 * 1e3, 3),
                          "p99_ms": round(p99 * 1e3, 3),
                          "achieved_rps": round(achieved, 1),
                          "saturated": saturated}
        if saturated:
            break    # higher rates only measure deeper saturation
    detail["admission_open_loop"] = out


def bench_overload(detail):
    """Graceful degradation under admission overload: open-loop replay
    at 1x and 2x the measured saturation rate against the FULL overload
    stack (bounded queue + deadline propagation + brownout ladder).
    The contract is not "stay fast" — an overloaded webhook cannot —
    but "degrade, don't collapse": deny verdicts keep flowing (shed or
    429'd requests are explicit, never silent admits) and the deny-path
    p99 at 2x stays under 5x the healthy (1x) p99.  ci.sh gates
    ``within_budget`` from the headline."""
    import threading
    from gatekeeper_tpu.webhook.batcher import MicroBatcher
    from gatekeeper_tpu.webhook.overload import OverloadController
    from gatekeeper_tpu.webhook.policy import ValidationHandler

    jd = JaxDriver()
    c = Backend(jd).new_client([K8sValidationTarget()])
    c.add_template(template_doc("K8sRequiredLabels", REQUIRED_LABELS))
    c.add_constraint(constraint_doc("K8sRequiredLabels", "need-l1",
                                    {"labels": ["l1"]}))
    batcher = MicroBatcher(None, max_batch=32, max_wait=0.002,
                           capacity=128, submit_timeout=1.0,
                           predict_seconds=c.predict_review_seconds)
    overload = OverloadController(batcher.depth, batcher.capacity)
    batcher.evaluate_batch = lambda reqs: c.review_batch(
        reqs, shed_actions=overload.shed_actions() or None)
    handler = ValidationHandler(c, batcher=batcher, overload=overload,
                                batch_mode="always")
    batcher.start()

    rng = random.Random(21)
    objs = make_resources(256, rng)
    reqs = []
    for i, o in enumerate(objs):
        reqs.append({"uid": f"o{i}", "kind": {"group": "", "version": "v1",
                                              "kind": "Pod"},
                     "name": o["metadata"]["name"],
                     "namespace": o["metadata"]["namespace"],
                     "operation": "CREATE", "object": o,
                     "userInfo": {"username": "bench"}})
    handler.handle(reqs[0])     # warm (compiles on the batched path)

    # closed-loop burst to find the saturation rate for THIS stack
    t0 = time.perf_counter()
    n_probe = 1_000 if not FALLBACK else 300
    import concurrent.futures
    with concurrent.futures.ThreadPoolExecutor(max_workers=16) as ex:
        list(ex.map(lambda i: handler.handle(reqs[i % len(reqs)]),
                    range(n_probe)))
    sat_rps = max(n_probe / (time.perf_counter() - t0), 50.0)
    log(f"[overload] measured saturation ~{sat_rps:.0f} rps")

    def open_loop(rate, duration_s=6.0):
        n = int(rate * duration_s)
        interval = 1.0 / rate
        lat: list[float] = []
        codes: dict = {}
        lock = threading.Lock()
        it = iter(range(n))
        start = time.perf_counter() + 0.05

        def worker():
            while True:
                with lock:
                    i = next(it, None)
                if i is None:
                    return
                sched = start + i * interval
                now = time.perf_counter()
                if sched > now:
                    time.sleep(sched - now)
                resp = handler.handle(reqs[i % len(reqs)],
                                      deadline=time.monotonic() + 0.5)
                done = time.perf_counter()
                code = (resp.get("status") or {}).get("code", 200)
                with lock:
                    lat.append(done - sched)
                    codes[code] = codes.get(code, 0) + 1

        # enough client concurrency to sustain the arrival rate even
        # with requests blocking up to the deadline — a thread-starved
        # client would measure its own backlog, not the server's
        n_workers = max(32, min(512, int(rate * 0.15)))
        threads = [threading.Thread(target=worker)
                   for _ in range(n_workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        lat.sort()
        return {"p50_ms": round(statistics.median(lat) * 1e3, 3),
                "p99_ms": round(lat[int(0.99 * len(lat))] * 1e3, 3),
                "denied_403": codes.get(403, 0),
                "rejected_429": codes.get(429, 0),
                "timeouts_504": codes.get(504, 0),
                "n": n}

    one_x = open_loop(sat_rps)
    two_x = open_loop(sat_rps * 2.0)
    batcher.stop()
    shed_total = sum(
        v for k, v in overload.metrics.snapshot().items()
        if k.startswith("admission_shed_total"))
    shed_total += sum(
        v for k, v in batcher.metrics.snapshot().items()
        if k.startswith("admission_shed_total"))
    within = bool(two_x["p99_ms"] < 5.0 * max(one_x["p99_ms"], 1e-3))
    detail["overload"] = {
        "saturation_rps": round(sat_rps, 1),
        "open_loop_1x": one_x, "open_loop_2x": two_x,
        "shed_total": shed_total,
        "max_rung": overload.max_rung,
        "within_budget": within,
    }
    log(f"[overload] 1x p99 {one_x['p99_ms']:.1f}ms | 2x p99 "
        f"{two_x['p99_ms']:.1f}ms (429s {two_x['rejected_429']}, shed "
        f"{shed_total}, max rung {overload.max_rung}) | "
        f"within_budget={within}")


def bench_admission_device_batch(detail):
    """Device-batched admission (query_review_batch, jax_driver.py) vs
    the scalar per-review engine at a realistic constraint count: find
    the batch-size crossover that justifies routing a coalesced batch
    to the device (round-3 VERDICT weak #4 — the batch path existed
    but its crossover was never measured)."""
    from gatekeeper_tpu.engine import jax_driver as jd_mod

    if FALLBACK:
        detail["admission_device_batch"] = {
            "skipped": "device backend unavailable"}
        return
    rng = random.Random(11)
    jd = JaxDriver()
    c = Backend(jd).new_client([K8sValidationTarget()])
    c.add_template(template_doc("K8sRequiredLabels", REQUIRED_LABELS))
    c.add_template(template_doc("K8sAllowedRepos", ALLOWED_REPOS))
    for j in range(100):
        c.add_constraint(constraint_doc(
            "K8sRequiredLabels", f"lab-{j:03d}",
            {"labels": rng.sample([f"l{x}" for x in range(10)], k=2)}))
        c.add_constraint(constraint_doc(
            "K8sAllowedRepos", f"rep-{j:03d}",
            {"repos": rng.sample(["gcr.io/", "docker.io/", "quay.io/",
                                  "ghcr.io/"], k=2)}))
    objs = make_resources(4096, rng)
    reviews = []
    for i, o in enumerate(objs):
        reviews.append({"uid": f"u{i}", "kind": {"group": "", "version": "v1",
                                                 "kind": "Pod"},
                        "name": o["metadata"]["name"],
                        "namespace": o["metadata"]["namespace"],
                        "operation": "CREATE", "object": o,
                        "userInfo": {"username": "bench"}})
    n_cons = 200

    # scalar ceiling: single-thread per-review loop
    for r in reviews[:8]:
        jd.query_review(TARGET_NAME, r)          # closure warm
    n_scalar = 512 if QUICK else 1024
    t0 = time.perf_counter()
    for r in reviews[:n_scalar]:
        jd.query_review(TARGET_NAME, r)
    scalar_rps = n_scalar / (time.perf_counter() - t0)

    out = {"n_constraints": n_cons,
           "scalar_single_thread_rps": round(scalar_rps, 1), "batched": {}}
    crossover = None
    # zero BOTH routing thresholds: with only SMALL_WORKLOAD_EVALS
    # zeroed, sub-threshold batches silently fell back to the scalar
    # loop inside query_review_batch and the "measured crossover" was
    # the REVIEW_BATCH_MIN_EVALS threshold echoing itself (round-4
    # advisor finding) — every batch size below must actually run the
    # device path to make the threshold derivation non-circular
    saved = (jd_mod.SMALL_WORKLOAD_EVALS, jd_mod.REVIEW_BATCH_MIN_EVALS)
    jd_mod.SMALL_WORKLOAD_EVALS = 0
    jd_mod.REVIEW_BATCH_MIN_EVALS = 0
    try:
        for B in (64, 256, 1024, 4096):
            batch = reviews[:B]
            jd.query_review_batch(TARGET_NAME, batch)       # compile warm
            reps = 2 if B >= 1024 else 4
            t0 = time.perf_counter()
            for _ in range(reps):
                jd.query_review_batch(TARGET_NAME, batch)
            rps = B * reps / (time.perf_counter() - t0)
            out["batched"][str(B)] = round(rps, 1)
            log(f"[admission-device-batch] B={B}: {rps:.0f} reviews/s "
                f"(scalar single-thread {scalar_rps:.0f}/s)")
            if crossover is None and rps > scalar_rps:
                crossover = B
    finally:
        jd_mod.SMALL_WORKLOAD_EVALS, jd_mod.REVIEW_BATCH_MIN_EVALS = saved
    out["crossover_batch"] = crossover
    out["crossover_evals"] = crossover and crossover * n_cons
    out["shipped_threshold_evals"] = jd_mod.REVIEW_BATCH_MIN_EVALS
    out["threshold_engages_at_default_webhook_batch"] = (
        crossover is not None and
        jd_mod.REVIEW_BATCH_MIN_EVALS <= 64 * n_cons)
    log(f"[admission-device-batch] crossover batch size: {crossover} "
        f"({out['crossover_evals']} evals; shipped threshold "
        f"{jd_mod.REVIEW_BATCH_MIN_EVALS} evals)")
    detail["admission_device_batch"] = out


def bench_regex_high_cardinality(detail):
    """Regex table build at exploding unique-string cardinality: the
    per-unique host re.search loop vs the batched byte-DFA engine
    (ops/regex_dfa, numpy and device twins) — records where each route
    wins (round-3 VERDICT #10)."""
    from gatekeeper_tpu.ir.lower import Lowerer
    from gatekeeper_tpu.ir.prep import build_bindings
    from gatekeeper_tpu.ops import regex_dfa
    from gatekeeper_tpu.rego import parse_module
    from gatekeeper_tpu.rego.interp import Interpreter
    from gatekeeper_tpu.store.table import ResourceMeta, ResourceTable

    n = sized(500_000, 20_000, 50_000)
    rng = random.Random(17)

    def _lower(mode):
        prev = os.environ.get("GATEKEEPER_DFA")
        os.environ["GATEKEEPER_DFA"] = mode
        try:
            interp = Interpreter(parse_module(LIBRARY["K8sImageDigests"][0]))
            return Lowerer(interp.module, interp).lower()
        finally:
            if prev is None:
                os.environ.pop("GATEKEEPER_DFA", None)
            else:
                os.environ["GATEKEEPER_DFA"] = prev

    # table lowering (regex as a per-unique lookup table) for the three
    # host build routes; dfa_match lowering for the in-jit route, whose
    # bindings carry only the packed bytes + transition constants
    lowered = _lower("off")
    lowered_jit = _lower("on")
    table = ResourceTable()
    hexd = "0123456789abcdef"
    log(f"[regex-hicard] building {n} unique image strings")
    for i in range(n):
        if i % 2:
            img = f"gcr.io/org/app{i}@sha256:" + "".join(
                rng.choice(hexd) for _ in range(64))
        else:
            img = f"gcr.io/org/app{i}:v{i}"
        table.upsert(f"d/p{i}", {
            "apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": f"p{i}", "namespace": "d"},
            "spec": {"containers": [{"name": "c", "image": img}]}},
            ResourceMeta("v1", "Pod", f"p{i}", "d"))
    cons = [{"kind": "K8sImageDigests", "metadata": {"name": "digests"},
             "spec": {"parameters": LIBRARY["K8sImageDigests"][1]}}]
    big = 1 << 60
    out = {"n_unique": n}
    saved = (regex_dfa.TABLE_MIN_UNIQUES, regex_dfa.TABLE_DEVICE_MIN_UNIQUES)
    try:
        modes = [("host_re_loop", lowered.spec, big, big),
                 ("dfa_numpy", lowered.spec, 1, big)]
        if not FALLBACK:
            modes.append(("dfa_device", lowered.spec, 1, 1))
        # in_jit: per-churn binding cost of the dfa_match route — the
        # match itself runs as gathers inside the jitted sweep, so the
        # rebuilt bindings are just the packed bytes + per-dfa fallback
        # vector (no per-unique host re.search, no table)
        modes.append(("in_jit", lowered_jit.spec, big, big))
        for mode, spec, t_min, d_min in modes:
            regex_dfa.TABLE_MIN_UNIQUES = t_min
            regex_dfa.TABLE_DEVICE_MIN_UNIQUES = d_min
            times = []
            for _ in range(2):
                t0 = time.perf_counter()
                build_bindings(spec, table, cons)
                times.append(time.perf_counter() - t0)
            out[mode + "_seconds"] = round(min(times), 3)
            log(f"[regex-hicard] {mode}: {min(times):.3f}s "
                f"(bindings build incl. table)")
    finally:
        regex_dfa.TABLE_MIN_UNIQUES, \
            regex_dfa.TABLE_DEVICE_MIN_UNIQUES = saved
    hs, js = out.get("host_re_loop_seconds"), out.get("in_jit_seconds")
    if hs and js:
        out["in_jit_vs_host_loop"] = round(hs / max(js, 1e-9), 1)
        log(f"[regex-hicard] in-jit DFA {out['in_jit_vs_host_loop']}x "
            f"faster than host re loop at {n} uniques")
    detail["regex_high_cardinality"] = out


def bench_admission_replay(detail):
    """AdmissionReview stream through the webhook ValidationHandler with
    micro-batching (BASELINE.md final config)."""
    from gatekeeper_tpu.webhook.batcher import MicroBatcher
    from gatekeeper_tpu.webhook.policy import ValidationHandler
    import concurrent.futures

    jd = JaxDriver()
    c = Backend(jd).new_client([K8sValidationTarget()])
    c.add_template(template_doc("K8sRequiredLabels", REQUIRED_LABELS))
    c.add_template(template_doc("K8sAllowedRepos", ALLOWED_REPOS))
    c.add_constraint(constraint_doc("K8sRequiredLabels", "need-l1", {"labels": ["l1"]}))
    c.add_constraint(constraint_doc("K8sAllowedRepos", "gcr", {"repos": ["gcr.io/"]}))
    handler = ValidationHandler(c)
    batcher = MicroBatcher(lambda reqs: c.review_batch(reqs),
                           max_batch=64, max_wait=0.002)
    handler.batcher = batcher
    batcher.start()

    n_reviews = sized(20_000, 5_000, 2_000)
    rng = random.Random(9)
    objs = make_resources(512, rng)
    reqs = []
    for i in range(n_reviews):
        o = objs[i % len(objs)]
        reqs.append({"uid": f"u{i}", "kind": {"group": "", "version": "v1",
                                              "kind": "Pod"},
                     "name": o["metadata"]["name"],
                     "namespace": o["metadata"]["namespace"],
                     "operation": "CREATE", "object": o,
                     "userInfo": {"username": "bench"}})
    handler.handle(reqs[0])  # warm
    lat: list[float] = []
    lock = __import__("threading").Lock()
    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(max_workers=32) as ex:
        def one(r):
            s = time.perf_counter()
            resp = handler.handle(r)
            with lock:
                lat.append(time.perf_counter() - s)
            return resp
        list(ex.map(one, reqs))
    wall = time.perf_counter() - t0
    batcher.stop()
    lat.sort()
    p50 = statistics.median(lat)
    p99 = lat[int(0.99 * len(lat))]
    rps = n_reviews / wall
    log(f"[admission] {n_reviews} reviews micro-batched: p50 {p50*1e3:.2f}ms"
        f" p99 {p99*1e3:.2f}ms, {rps:.0f} reviews/s")
    detail["admission_replay"] = {
        "n_reviews": n_reviews, "p50_ms": round(p50 * 1e3, 3),
        "p99_ms": round(p99 * 1e3, 3), "reviews_per_sec": round(rps, 1)}

    # honest tail latency: fixed-rate (open-loop) replay
    bench_admission_open_loop(detail, handler, reqs)

    # replicated serving: N engine-worker processes behind a ReplicaPool
    # (the reference's webhook-pod-replica model on one host) — scalar
    # admission evaluation escapes the GIL.  Pointless without cores to
    # run them on: time-slicing one core only adds RPC overhead.
    default_workers = min(3, (os.cpu_count() or 1) - 1)
    n_workers = int(os.environ.get("GATEKEEPER_BENCH_REPLICAS",
                                   str(default_workers)))
    if n_workers > 0:
        from gatekeeper_tpu.client.replica_pool import ReplicaPool
        try:
            pool = ReplicaPool.spawn_workers(n_workers, timeout=180)
        except Exception as e:
            log(f"[admission] replica spawn failed ({e}); skipping")
            return
        try:
            cp = Backend(pool).new_client([K8sValidationTarget()])
            cp.add_template(template_doc("K8sRequiredLabels", REQUIRED_LABELS))
            cp.add_template(template_doc("K8sAllowedRepos", ALLOWED_REPOS))
            cp.add_constraint(constraint_doc("K8sRequiredLabels", "need-l1",
                                             {"labels": ["l1"]}))
            cp.add_constraint(constraint_doc("K8sAllowedRepos", "gcr",
                                             {"repos": ["gcr.io/"]}))
            rhandler = ValidationHandler(cp)
            rhandler.handle(reqs[0])  # warm every replica
            for r in reqs[1:n_workers]:
                rhandler.handle(r)
            rlat: list[float] = []
            t0 = time.perf_counter()
            with concurrent.futures.ThreadPoolExecutor(max_workers=32) as ex:
                def one_r(r):
                    s = time.perf_counter()
                    resp = rhandler.handle(r)
                    with lock:
                        rlat.append(time.perf_counter() - s)
                    return resp
                list(ex.map(one_r, reqs))
            rwall = time.perf_counter() - t0
        finally:
            pool.close()
        rlat.sort()
        rp50 = statistics.median(rlat)
        rp99 = rlat[int(0.99 * len(rlat))]
        rrps = n_reviews / rwall
        log(f"[admission] {n_reviews} reviews over {n_workers} worker "
            f"processes: p50 {rp50*1e3:.2f}ms p99 {rp99*1e3:.2f}ms, "
            f"{rrps:.0f} reviews/s")
        detail["admission_replay"]["replicated"] = {
            "workers": n_workers, "p50_ms": round(rp50 * 1e3, 3),
            "p99_ms": round(rp99 * 1e3, 3),
            "reviews_per_sec": round(rrps, 1)}


def bench_canary(detail):
    """Tiny end-to-end device run, FIRST: proves the device actually
    executes + fetches (the probe only proves backend init), warms the
    compiler, and sets a provisional headline so a
    number of record exists minutes in.  A canary failure demotes the
    whole run to fallback sizing."""
    global FALLBACK
    if FALLBACK:
        detail["canary"] = {"skipped": "probe already failed"}
        return
    rng = random.Random(99)
    n = 2_000
    resources = make_resources(n, rng)
    jd = JaxDriver()
    client = Backend(jd).new_client([K8sValidationTarget()])
    client.add_template(template_doc("K8sRequiredLabels", REQUIRED_LABELS))
    for j in range(4):
        client.add_constraint(constraint_doc(
            "K8sRequiredLabels", f"canary-{j}",
            {"labels": [f"l{j}", f"l{j+1}"]}))
    client.add_data_batch(resources)
    from gatekeeper_tpu.engine import jax_driver as jd_mod
    saved = jd_mod.SMALL_WORKLOAD_EVALS
    jd_mod.SMALL_WORKLOAD_EVALS = 0     # force the device path
    try:
        t0 = time.perf_counter()
        jd.query_audit(TARGET_NAME, QueryOpts(limit_per_constraint=CAP))
        cold = time.perf_counter() - t0
        best, _first, _nres = timed_audit(jd, reps=2)
    finally:
        jd_mod.SMALL_WORKLOAD_EVALS = saved
    evals = n * 4
    detail["canary"] = {"n_resources": n, "n_constraints": 4,
                        "cold_seconds": round(cold, 2),
                        "steady_seconds": round(best, 4)}
    log(f"[canary] device path live: cold {cold:.1f}s, steady "
        f"{best*1e3:.0f}ms at {n}x4")
    # provisional number of record (the real north star overwrites it);
    # vs_baseline against the round-3-measured scalar rate
    set_headline(evals / best, (evals / 5800.0) / best, provisional=True)


def _probe_with_retry(attempts: int = 3, backoff_s: float = 2.0):
    """The bench must not silently measure the scalar fallback: a failed
    first probe is retried with backoff (transient backend errors
    resolve in seconds), and only after `attempts` failures does the run proceed
    degraded — marked ``backend_degraded`` in the headline and a nonzero
    exit.  A poisoned verdict (hung probe thread pinned in jax init)
    never recovers in-process, so retrying it would just burn budget."""
    from gatekeeper_tpu.utils import device_probe
    res = probe_devices()
    delay = backoff_s
    for attempt in range(2, attempts + 1):
        if res.ok or res.poisoned:
            return res
        log(f"[bench] device probe failed ({res.reason}); retry "
            f"{attempt}/{attempts} in {delay:.0f}s")
        time.sleep(delay)
        delay *= 2
        res = device_probe.reprobe()
    return res


def _mark_degraded(reason: str) -> None:
    """Latch the loud-failure contract: `backend_degraded: true` rides
    in the stdout headline (slim copies every top-level key) and the
    process exits nonzero."""
    HEADLINE["backend_degraded"] = True
    DETAIL["backend_degraded_reason"] = reason


def main():
    global FALLBACK
    from gatekeeper_tpu.engine.veval import quiesce_upgrades
    from gatekeeper_tpu.utils.compile_cache import cache_root
    # warm-restart persistence is on by default for the bench (the unit
    # suite stays hermetic: only the bench, ci restart-smoke, and
    # cmd/manager set the snapshot dir) — the restart phases below
    # measure real snapshot reuse, not just the XLA tier
    os.environ.setdefault("GATEKEEPER_SNAPSHOT_DIR",
                          os.path.join(cache_root(), "snapshots"))
    threading.Thread(target=_watchdog, name="bench-watchdog",
                     daemon=True).start()
    res = _probe_with_retry()
    FALLBACK = not res.ok
    DETAIL["backend"] = res.backend_label
    DETAIL["backend_probe"] = res.reason
    log(f"[bench] backend: {res.backend_label} ({res.reason}); "
        f"global budget {GLOBAL_BUDGET_S:.0f}s")
    if FALLBACK:
        _mark_degraded(f"device probe failed after retries: {res.reason}")
        log("[bench] FALLBACK MODE: scalar-only at shrunk sizes")

    run_phase("canary", bench_canary, 300)
    if DETAIL.get("phases", {}).get("canary", {}).get("ok") is False \
            and not FALLBACK:
        # the backend answered the probe but cannot execute — demote,
        # process-wide, so every later driver constructs scalar-only
        FALLBACK = True
        DETAIL["backend"] = "cpu-fallback"
        _mark_degraded("device canary failed; demoted to scalar")
        from gatekeeper_tpu.utils import device_probe
        device_probe.mark_unavailable(
            "device canary failed; demoted to scalar")
        log("[bench] canary failed; demoting to FALLBACK sizing")
    run_phase("north_star", bench_north_star, 1100)
    if DETAIL["phases"].get("north_star", {}).get("timed_out"):
        # the device run hung mid-execution (run_phase demoted us to
        # fallback): re-measure at fallback sizing so the capture still
        # carries a REAL north-star number, not a provisional canary
        run_phase("north_star_fallback", bench_north_star, 400)
    quiesce_upgrades()
    run_phase("demo_basic", bench_demo_basic, 240)
    run_phase("allowed_repos", bench_allowed_repos, 240)
    quiesce_upgrades()
    run_phase("library", bench_library, 700)
    quiesce_upgrades()
    run_phase("full_sweep", bench_full_sweep, 400)
    quiesce_upgrades()
    run_phase("external_data", bench_external_data, 300)
    quiesce_upgrades()
    run_phase("analysis", bench_analysis, 300)
    quiesce_upgrades()
    run_phase("churn_selective", bench_churn_selective, 300)
    quiesce_upgrades()
    run_phase("paged_churn", bench_paged_churn, 420)

    run_phase("devpages_churn", bench_devpages_churn, 420)

    run_phase("watch_latency", bench_watch_latency, 300)
    quiesce_upgrades()
    run_phase("transval", bench_transval, 240)
    quiesce_upgrades()
    run_phase("shard_sim", bench_shard_sim, 300)
    quiesce_upgrades()
    run_phase("whatif", bench_whatif, 400)
    quiesce_upgrades()
    run_phase("promotion", bench_promotion, 300)
    quiesce_upgrades()
    run_phase("compile_surface", bench_compile_surface, 300)

    run_phase("mem_surface", bench_mem_surface, 300)
    quiesce_upgrades()
    run_phase("regex_heavy", bench_regex_heavy, 300)
    run_phase("selector_heavy", bench_selector_heavy, 300)
    run_phase("regex_high_cardinality", bench_regex_high_cardinality, 400)
    quiesce_upgrades()
    run_phase("admission_replay", bench_admission_replay, 600)
    run_phase("admission_device_batch", bench_admission_device_batch, 400)
    run_phase("overload", bench_overload, 240)
    emit_headline()
    # fail loudly on a degraded run: the artifact says backend_degraded
    # AND the process exit code says it — a capture harness that only
    # checks rc cannot mistake a scalar-fallback run for a device run
    rc = 3 if HEADLINE.get("backend_degraded") else 0
    if rc:
        log("[bench] exiting nonzero: backend degraded "
            f"({DETAIL.get('backend_degraded_reason')})")
        _flight_dump("bench:degraded")
    if _LEAKED_PHASES:
        # abandoned phase threads are stuck inside C calls (a hung
        # device op); normal interpreter teardown under them can abort
        # AFTER the headline is out — exit hard instead
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(rc)
    sys.exit(rc)


if __name__ == "__main__":
    main()
