"""The bench must produce a parsed number of record unconditionally
(round-4 postmortem: one hung backend probe erased every config's
numbers — BENCH_r04 rc=124, parsed=null) — and a degraded run must FAIL
LOUDLY: `backend_degraded: true` rides in the stdout headline and the
process exits nonzero (rc=3), so a capture harness that only checks the
exit code cannot mistake a scalar-fallback run for a device run.  These
tests run bench.py as the driver does (a subprocess, stdout captured)
under the two failure modes and require both halves of that contract.
The full detail tree is read from BENCH_partial.json (the stdout line
is the slim headline by contract — it must fit a 2,000-byte tail
window)."""

import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(env_extra: dict, timeout: int, expect_rc: int = 0):
    env = {**os.environ, **env_extra}
    t0 = time.monotonic()
    out = subprocess.run([sys.executable, "bench.py"], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=timeout)
    wall = time.monotonic() - t0
    assert out.returncode == expect_rc, \
        f"rc={out.returncode} (wanted {expect_rc}): {out.stderr[-3000:]}"
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, f"bench must print ONE stdout line: {lines}"
    headline = json.loads(lines[0])
    with open(os.path.join(REPO, "BENCH_partial.json")) as f:
        detail = json.load(f).get("detail", {})
    return headline, detail, wall, out.stderr


@pytest.mark.slow
def test_hung_backend_yields_parsed_fallback_capture():
    headline, detail, wall, _err = _run({
        "GATEKEEPER_PROBE_TEST_HANG": "1",      # blackholed backend
        "GATEKEEPER_DEVICE_PROBE_TIMEOUT_S": "2",
        "GATEKEEPER_BENCH_BUDGET_S": "600",
    }, timeout=700, expect_rc=3)
    # the loud-failure contract: degraded is visible in the slim stdout
    # headline AND the nonzero exit (asserted by expect_rc above)
    assert headline["backend_degraded"] is True
    assert detail["backend"] == "cpu-fallback"
    assert headline["value"] > 0                # a real number of record
    assert detail["north_star"]["steady_seconds"] > 0
    assert detail["phases"]["north_star"]["ok"]
    # the device-batch phase cannot run without a device: recorded as
    # an explicit skip, not silence
    assert detail["admission_device_batch"]["skipped"]
    assert wall < 400, f"fallback capture took {wall:.0f}s"


@pytest.mark.slow
def test_hung_phase_is_abandoned_and_the_run_continues():
    """A phase that hangs mid-run (device op that never completes)
    must not erase the already-measured headline NOR the rest of the
    run: the phase thread is abandoned at its budget, the run demotes
    to fallback sizing, and later phases still produce numbers."""
    headline, detail, wall, err = _run({
        "GATEKEEPER_PROBE_TEST_HANG": "1",
        "GATEKEEPER_DEVICE_PROBE_TIMEOUT_S": "2",
        "GATEKEEPER_BENCH_TEST_HANG_PHASE": "library",
        "GATEKEEPER_BENCH_BUDGET_S": "600",
    }, timeout=700, expect_rc=3)
    assert "TIMED OUT" in err
    assert headline["backend_degraded"] is True
    lib = detail["phases"]["library"]
    assert lib["timed_out"] and lib["ok"] is False
    # the north star ran BEFORE the hang: its number survives
    assert headline["value"] > 0
    assert detail["north_star"]["steady_seconds"] > 0
    # phases AFTER the hang still ran
    assert detail["phases"]["regex_heavy"]["ok"]
    assert detail["phases"]["admission_replay"]["ok"]


@pytest.mark.slow
def test_probe_retry_then_degraded_exit():
    """A probe that fails (without poisoning jax) is retried with
    backoff before the bench gives up; the run then proceeds degraded
    with the nonzero-exit + backend_degraded contract.  TEST_FAIL (not
    TEST_HANG) so the verdict is non-poisoned: bench retry skips
    poisoned verdicts by design (re-entering a hung backend init would
    just burn budget)."""
    headline, detail, _wall, err = _run({
        "GATEKEEPER_PROBE_TEST_FAIL": "1",      # transient init error
        "GATEKEEPER_DEVICE_PROBE_TIMEOUT_S": "5",
        "GATEKEEPER_BENCH_BUDGET_S": "600",
    }, timeout=700, expect_rc=3)
    # all three attempts ran before the bench gave up
    assert "retry 2/3" in err and "retry 3/3" in err
    assert headline["backend_degraded"] is True
    assert "probe failed after retries" in detail["backend_degraded_reason"]
