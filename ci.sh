#!/usr/bin/env bash
# CI entry point (reference analogue: Travis building Dockerfile_ci and
# running `make test`).  Runs lint, the library certifiers and probes,
# the full suite on the virtual 8-device CPU mesh, the restart smoke
# and the chaos soak.
set -euo pipefail
cd "$(dirname "$0")"

echo "== lint =="
python -m compileall -q gatekeeper_tpu
# Stage-1/2/3 static analysis over every library template: any
# error-severity finding fails the build, and with --strict any warning
# not pinned as a known scalar-fallback (library/lowering_buckets.json)
# fails it too — the library must stay warning-clean
JAX_PLATFORMS=cpu python -m gatekeeper_tpu.client.probe --lint --strict --library | tail -1
# host-sync self-lint: no block_until_ready / np.asarray / time.time
# inside kernel-side (jitted) functions of the engine or the IR layer
python -m gatekeeper_tpu.analysis.selflint gatekeeper_tpu/engine gatekeeper_tpu/ir
# lock-discipline self-lint: no blocking calls (provider fetch,
# time.sleep, future .result()) while holding a *_lock in host
# control-plane code
python -m gatekeeper_tpu.analysis.selflint --locks gatekeeper_tpu/watch gatekeeper_tpu/controllers gatekeeper_tpu/externaldata
# lock-order self-lint: the lock-acquisition graph (lexical nesting +
# calls made while holding a lock) must stay acyclic, or two threads
# taking the same pair in opposite order can deadlock; enforce/ brings
# the reactor's _rx_lock into the graph (client → driver → reactor
# must stay one-directional)
python -m gatekeeper_tpu.analysis.selflint --lockorder gatekeeper_tpu/engine gatekeeper_tpu/watch gatekeeper_tpu/externaldata gatekeeper_tpu/enforce
# rebind-only self-lint: Bindings.arrays / base_dirty (and the
# device-resident mask / page-table / inventory-join handles of
# enforce/devpages.py) are shared with the sweep cache and in-flight
# futures — engine and enforce code must rebind a fresh dict/handle,
# never mutate in place
python -m gatekeeper_tpu.analysis.selflint --rebind gatekeeper_tpu/engine gatekeeper_tpu/enforce
# retrace-hazard self-lint (the static twin of the Stage-7 compile-
# surface certificate): no per-call jit construction, no host-value
# jnp.asarray baking, no shape-dependent branching inside kernel-side
# functions — any of these dispatches signatures the certifier cannot
# enumerate
python -m gatekeeper_tpu.analysis.selflint --retrace gatekeeper_tpu/engine gatekeeper_tpu/ir gatekeeper_tpu/enforce gatekeeper_tpu/ops
# alloc-discipline self-lint (the static twin of the Stage-8 memory-
# surface certificate): no fresh device-buffer construction
# (jnp.zeros/ones/full/empty/arange, device_put of freshly built host
# values) in steady-state serve paths — buffers are built in
# build/rebuild seams and reused; anything else needs an explicit
# `# allocs-ok: <reason>` waiver
python -m gatekeeper_tpu.analysis.selflint --allocs gatekeeper_tpu/engine gatekeeper_tpu/enforce gatekeeper_tpu/webhook gatekeeper_tpu/client

echo "== certify (translation validation over the library) =="
# Stage-4 translation validation: bounded-model Rego<->IR equivalence
# over every library template.  Every device-lowered template must
# certify (0 counterexamples); the whole stage gets a 60s cpu budget.
CERT=$(JAX_PLATFORMS=cpu timeout -k 10 60 \
       python -m gatekeeper_tpu.client.probe --certify --library | tail -5)
echo "$CERT"
echo "$CERT" | grep -q " 0 counterexample(s)" \
  || { echo "certify stage found counterexamples" >&2; exit 1; }
echo "$CERT" | grep -Eq "[1-9][0-9]* certified" \
  || { echo "certify stage certified nothing" >&2; exit 1; }

echo "== footprint (Stage-5 dependency analysis over the library) =="
# Stage-5 column read-set footprints + perturbation validation: every
# device-lowered template's claimed read-set must survive perturbation
# of unclaimed columns bit-identically (0 violations).  rc=1 is the
# expected warning tier (the library's one cross-row template); rc=2
# (a violation) fails the build.
FP_RC=0
FP=$(JAX_PLATFORMS=cpu timeout -k 10 120 \
     python -m gatekeeper_tpu.client.probe --footprint --library \
     | tail -3) || FP_RC=$?
echo "$FP"
[ "$FP_RC" -le 1 ] \
  || { echo "footprint stage failed (rc=$FP_RC)" >&2; exit 1; }
echo "$FP" | grep -q " 0 violation(s)" \
  || { echo "footprint stage found violations" >&2; exit 1; }
echo "$FP" | grep -Eq "[1-9][0-9]* row-local" \
  || { echo "footprint stage analyzed nothing" >&2; exit 1; }

echo "== shardplan (Stage-6 partition plans over the library) =="
# Stage-6 sharding certifier: every device-lowered template gets a
# resource-axis partition plan (collectives + padding + per-shard
# layout) validated on a 2-shard simulated mesh against the unsharded
# oracle.  rc=1 is the expected warning tier (the cross-row template
# plus the scalar pin); rc=2 (a parity violation) fails the build, and
# the library must keep >= 40 of its templates shard-eligible.
SP_RC=0
SP=$(JAX_PLATFORMS=cpu GATEKEEPER_SHARDPLAN=strict timeout -k 10 240 \
     python -m gatekeeper_tpu.client.probe --shardplan --library \
     | tail -3) || SP_RC=$?
echo "$SP"
[ "$SP_RC" -le 1 ] \
  || { echo "shardplan stage failed (rc=$SP_RC)" >&2; exit 1; }
echo "$SP" | grep -q " 0 violation(s)" \
  || { echo "shardplan stage found violations" >&2; exit 1; }
echo "$SP" | grep -Eq "(4[0-9]|[5-9][0-9]|[0-9]{3,}) shard-eligible" \
  || { echo "shardplan stage certified < 40 shard-eligible" >&2; exit 1; }

echo "== compilesurface (Stage-7 compile-surface certificates) =="
# Stage-7 compile-surface certifier: every device-lowered template's
# reachable jit-signature set must be statically finite under the
# deployment caps (pad-geometry ladders composed into a certificate).
# rc=1 is the expected warning tier (the scalar pin); rc=2 (an
# unbounded surface or analyzer error) fails the build, and the
# library must keep >= 45 of its 49 templates fully certified with 0
# unbounded.
CSF_RC=0
CSF=$(JAX_PLATFORMS=cpu GATEKEEPER_COMPILE_SURFACE=strict timeout -k 10 240 \
      python -m gatekeeper_tpu.client.probe --compilesurface --library \
      | tail -3) || CSF_RC=$?
echo "$CSF"
[ "$CSF_RC" -le 1 ] \
  || { echo "compilesurface stage failed (rc=$CSF_RC)" >&2; exit 1; }
echo "$CSF" | grep -q " 0 unbounded" \
  || { echo "compilesurface stage found unbounded surfaces" >&2; exit 1; }
echo "$CSF" | grep -Eq "(4[5-9]|[5-9][0-9]|[0-9]{3,}) certified" \
  || { echo "compilesurface stage certified < 45 templates" >&2; exit 1; }

echo "== memsurface (Stage-8 memory-surface certificates) =="
# Stage-8 memory-surface certifier: every device-lowered template's
# conservative peak-HBM claim must fit the installed budget, and the
# claims are validated (not trusted) against the bytes actually
# materialized at a small world — an under-claiming certificate is an
# error.  rc=1 is the expected warning tier (the scalar pin); rc=2 (a
# budget violation, under-claim, or analyzer error) fails the build,
# and the library must keep >= 45 of its 49 templates certified.
MS_RC=0
MS=$(JAX_PLATFORMS=cpu GATEKEEPER_HBM_BUDGET=strict timeout -k 10 240 \
     python -m gatekeeper_tpu.client.probe --memsurface --library \
     | tail -3) || MS_RC=$?
echo "$MS"
[ "$MS_RC" -le 1 ] \
  || { echo "memsurface stage failed (rc=$MS_RC)" >&2; exit 1; }
echo "$MS" | grep -q " 0 over budget" \
  || { echo "memsurface stage found budget violations" >&2; exit 1; }
echo "$MS" | grep -q " 0 under-claimed" \
  || { echo "memsurface stage found under-claiming certificates" >&2; exit 1; }
echo "$MS" | grep -Eq "(4[5-9]|[5-9][0-9]|[0-9]{3,}) certified" \
  || { echo "memsurface stage certified < 45 templates" >&2; exit 1; }

echo "== whatif (shadow / replay / fleet parity probe) =="
# What-if engine self-check: a shadow (live ∪ candidate) sweep must be
# bit-identical to a standalone candidate install, snapshot replay must
# reproduce the live digest, and a 2-cluster stacked sweep must match
# the per-cluster loop oracle.  rc=1 is the warning tier (scalar
# fallback — parity still holds); rc=2 (any parity break) fails the
# build.
WI_RC=0
WI=$(JAX_PLATFORMS=cpu timeout -k 10 180 \
     python -m gatekeeper_tpu.client.probe --whatif | tail -3) || WI_RC=$?
echo "$WI"
[ "$WI_RC" -le 1 ] \
  || { echo "whatif stage failed (rc=$WI_RC)" >&2; exit 1; }
echo "$WI" | grep -q " 0 parity failure(s)" \
  || { echo "whatif stage found parity failures" >&2; exit 1; }

echo "== rollout (policy promotion pipeline probe) =="
# Promotion pipeline self-check (rollout/): a seeded candidate must
# graduate candidate → shadow → replayed → dryrun → warn → deny on
# recorded evidence alone — capture-log health (0 drops / torn tails /
# write errors), batched-replay digest parity with the scalar oracle,
# zero unexpected denials — and the 4-cluster graduation plan must
# land with every cluster graduated.  rc=1 is the warning tier (scalar
# fallback — the evidence gates still hold); rc=2 fails the build.
RO_RC=0
RO=$(JAX_PLATFORMS=cpu timeout -k 10 240 \
     python -m gatekeeper_tpu.client.probe --rollout | tail -12) || RO_RC=$?
echo "$RO"
[ "$RO_RC" -le 1 ] \
  || { echo "rollout stage failed (rc=$RO_RC)" >&2; exit 1; }
echo "$RO" | grep -q "0 unexpected denial(s)" \
  || { echo "rollout stage saw unexpected denials" >&2; exit 1; }
echo "$RO" | grep -q " 0 gate failure(s)" \
  || { echo "rollout stage reported gate failures" >&2; exit 1; }
echo "$RO" | grep -Eq "fleet: [0-9]+/[0-9]+ graduated, 0 blocked" \
  || { echo "rollout stage fleet plan incomplete" >&2; exit 1; }

echo "== devpages (device-resident page table, library parity) =="
# Device-resident paged store (GATEKEEPER_DEVPAGES=on,
# enforce/devpages.py): per-kind device residency over the library with
# verdicts bit-identical to the pages-off oracle.  rc=1 is the warning
# tier (the scalar-pinned template falls back host-side); rc=2 (a
# parity failure) fails the build.
DP_RC=0
DP=$(JAX_PLATFORMS=cpu GATEKEEPER_DEVPAGES=on timeout -k 10 240 \
     python -m gatekeeper_tpu.client.probe --pages --library \
     | tail -3) || DP_RC=$?
echo "$DP"
[ "$DP_RC" -le 1 ] \
  || { echo "devpages stage failed (rc=$DP_RC)" >&2; exit 1; }
echo "$DP" | grep -q " 0 parity failure(s)" \
  || { echo "devpages stage found parity failures" >&2; exit 1; }
echo "$DP" | grep -Eq "(4[0-9]|[5-9][0-9]|[0-9]{3,})/[0-9]+ kind\(s\) paged" \
  || { echo "devpages stage paged < 40 kinds" >&2; exit 1; }

echo "== tests (virtual 8-device CPU mesh) =="
python -m pytest tests/ -q

echo "== engine self-validation (client/probe.py) =="
JAX_PLATFORMS=cpu python -m gatekeeper_tpu.client.probe | tail -1

# Soak cadence: `make soak` (GATEKEEPER_SOAK=1 long fuzz/race sweeps)
# runs nightly and before any release image — opt-in here via SOAK=1
# so the per-commit path stays fast.
if [ "${SOAK:-0}" = "1" ]; then
  echo "== soak (long fuzz + race sweeps) =="
  GATEKEEPER_SOAK=1 python -m pytest tests/test_soak.py -q
fi

echo "== obs (sweep trace capture + schema validation) =="
# capture a full-sweep trace via the probe and validate the Chrome
# trace-event schema (Perfetto-loadable) plus the attribution contract:
# per-template device seconds must sum to the measured device time
TRACE=$(mktemp /tmp/gatekeeper-trace-XXXX.json)
JAX_PLATFORMS=cpu GATEKEEPER_TRACE_PROBE_N=200 timeout -k 10 120 \
  python -m gatekeeper_tpu.client.probe --trace --out "$TRACE"
TRACE="$TRACE" python - <<'EOF'
import json, os
t = json.load(open(os.environ["TRACE"]))
evs = t["traceEvents"]
assert evs, "empty traceEvents"
for e in evs:
    assert e["ph"] == "X" and "name" in e and "ts" in e and "dur" in e \
        and "pid" in e and "tid" in e, f"malformed trace event: {e}"
names = {e["name"] for e in evs}
assert "audit.sweep" in names, f"no audit.sweep span: {sorted(names)[:20]}"
gt = t["gatekeeperTrace"]
attr = gt.get("attribution")
if attr:     # device path only; scalar-only runs carry no attribution
    total = sum(r["device_seconds"] for r in attr["templates"])
    dev = gt["device_s"]
    assert dev > 0 and abs(total - dev) / dev < 0.01, \
        f"attribution sum {total} vs measured device_s {dev}"
    print(f"obs ok: {len(evs)} events, {len(attr['templates'])} "
          f"templates attributed, sum within 1% of device_s")
else:
    print(f"obs ok (scalar-only): {len(evs)} events, no attribution")
EOF
rm -f "$TRACE"

echo "== restart smoke (warm-restart persistence) =="
# cold run in a fresh snapshot dir, then a warm run in a FRESH PROCESS
# against the same dir: the warm process must skip all Rego lowering,
# restore the store, report snapshot hits, produce bit-identical
# verdicts, and start up in under half the cold wall-clock
SNAPDIR=$(mktemp -d)
COLD=$(JAX_PLATFORMS=cpu GATEKEEPER_SNAPSHOT_DIR="$SNAPDIR" \
       GATEKEEPER_SMOKE_N=200 python -m gatekeeper_tpu.resilience.smoke)
WARM=$(JAX_PLATFORMS=cpu GATEKEEPER_SNAPSHOT_DIR="$SNAPDIR" \
       GATEKEEPER_SMOKE_N=200 python -m gatekeeper_tpu.resilience.smoke)
rm -rf "$SNAPDIR"
COLD="$COLD" WARM="$WARM" python - <<'EOF'
import json, os
cold = json.loads(os.environ["COLD"])
warm = json.loads(os.environ["WARM"])
assert warm["restart_persistent_cache_hits"] > 0, \
    f"warm run reused nothing: {warm}"
assert warm["lowerings"] == 0, f"warm run re-lowered Rego: {warm}"
assert warm["validations"] == 0, \
    f"warm run re-ran translation validation: {warm}"
assert cold["validations"] > 0, \
    f"cold run never validated (transval off?): {cold}"
assert warm["footprints"] == 0, \
    f"warm run re-ran Stage-5 dependency analysis: {warm}"
assert cold["footprints"] > 0, \
    f"cold run never analyzed footprints (footprint off?): {cold}"
assert warm["shardplans"] == 0, \
    f"warm run re-ran Stage-6 partition-plan analysis: {warm}"
assert cold["shardplans"] > 0, \
    f"cold run never planned shards (shardplan off?): {cold}"
assert warm["store_restored"] is True, f"store not restored: {warm}"
assert warm["verdict_digest"] == cold["verdict_digest"], \
    f"verdicts diverged: cold {cold['verdict_digest']} " \
    f"warm {warm['verdict_digest']}"
assert warm["startup_seconds"] < 0.5 * cold["startup_seconds"], \
    f"warm startup {warm['startup_seconds']}s not < 50% of " \
    f"cold {cold['startup_seconds']}s"
assert cold["dfa_compiles"] > 0, \
    f"cold run never compiled a regex DFA (dfa lowering off?): {cold}"
assert warm["dfa_compiles"] == 0, \
    f"warm run recompiled DFAs instead of loading the dfa " \
    f"snapshot tier: {warm}"
assert cold["compile_surfaces"] > 0, \
    f"cold run never certified a compile surface (stage-7 off?): {cold}"
assert warm["compile_surfaces"] == 0, \
    f"warm run re-ran Stage-7 compile-surface analysis: {warm}"
assert cold["aot_precompiles"] > 0, \
    f"cold run never AOT-precompiled the certified surface: {cold}"
assert warm["aot_precompiles"] == 0, \
    f"warm run repeated the startup AOT compile storm instead of " \
    f"honoring the cs-tier geometry stamp: {warm}"
assert cold["memsurfaces"] > 0, \
    f"cold run never certified a memory surface (stage-8 off?): {cold}"
assert warm["memsurfaces"] == 0, \
    f"warm run re-ran Stage-8 memory-surface analysis: {warm}"
print(f"restart smoke ok: startup cold {cold['startup_seconds']}s -> "
      f"warm {warm['startup_seconds']}s; "
      f"{warm['restart_persistent_cache_hits']} snapshot hits, "
      f"0 re-lowerings, 0 DFA recompiles, 0 warm AOT compiles, "
      f"verdict digest {warm['verdict_digest']}")
EOF

echo "== chaos (seeded 30s soak, admission + audit under faults) =="
# Seeded schedule-driven chaos soak (resilience/chaos.py): sustained
# concurrent admission + audit + watch-churn load with
# GATEKEEPER_PAGES=on while probe_hang / device_lost /
# snapshot_corrupt / slow_provider / queue_storm and the watch-class
# faults (watch_stall / watch_gap / watch_duplicate / watch_reorder /
# watch_flood) fire on a schedule that is a pure function of the seed.
# Invariants: no deadlock, deny verdicts bit-identical to the scalar
# oracle or explicitly rejected (never silently admitted), p99
# bounded, queue depth <= its bound, supervisor recovers + re-jits,
# the ledger delta stream stays exact (mirror == state == pages-off
# oracle at every checkpoint), forced resyncs emit zero phantom
# events, and the reactor returns to live.  rc=1 is the warning tier
# (e.g. a quiet run where brownout never engaged); rc=2 (any
# invariant violation) fails the build.  The last line is the
# headline — grep it from the trailing window like the bench gate.
CH_RC=0
CH=$(JAX_PLATFORMS=cpu GATEKEEPER_SUPERVISOR_BACKOFF_S=0.5 \
     GATEKEEPER_COMPILE_SURFACE=strict \
     timeout -k 10 300 \
     python -m gatekeeper_tpu.resilience.chaos --seed 7 --duration 30 \
     | tail -3) || CH_RC=$?
echo "$CH"
[ "$CH_RC" -le 1 ] \
  || { echo "chaos soak failed (rc=$CH_RC)" >&2; exit 1; }
echo "$CH" | grep -q " 0 invariant violation(s)" \
  || { echo "chaos soak reported invariant violations" >&2; exit 1; }
echo "$CH" | grep -Eq "completed=[1-9][0-9]*" \
  || { echo "chaos soak completed no admissions" >&2; exit 1; }
echo "$CH" | grep -Eq "watch_ev=[1-9][0-9]*" \
  || { echo "chaos soak delivered no watch events" >&2; exit 1; }
echo "$CH" | grep -Eq "ledger_checks=[1-9][0-9]*" \
  || { echo "chaos soak ran no ledger checkpoints" >&2; exit 1; }
echo "$CH" | grep -q "uncertified_retraces=0 " \
  || { echo "chaos soak dispatched outside the compile surface" >&2; exit 1; }

echo "CI PASS"
