"""Bounded jax backend bring-up: probe once, under a deadline.

The reference's in-process driver can never hang on construction
(vendor/.../drivers/local/local.go:28-48 — it allocates maps and
returns).  A jax backend can fail at bring-up in two ways.  An *error*
is easy (jax.devices() raises).  A backend that never answers is worse:
init neither succeeds nor fails, and an unbounded first jax.devices()
hangs driver construction, the engine worker and every caller behind
them.

This module is the single choke point.  ``probe_devices()`` runs the
first ``jax.devices()`` of the process on a daemon thread and waits at
most ``GATEKEEPER_DEVICE_PROBE_TIMEOUT_S`` (default 45 s):

  * success   -> zero added cost (that init had to happen anyway; the
                 result is simply observed from a thread);
  * error     -> no devices.  JaxDriver logs the verdict at error level
                 and counts it (``device_probe_failures``); evaluation
                 runs on the scalar oracle;
  * timeout   -> the probe thread is still parked inside backend init
                 and very likely holds jax's backend-init lock, so ANY
                 later jax dispatch from this process could block too.
                 The process is marked *poisoned*: callers must route
                 every evaluation through the scalar oracle (pure
                 Python/numpy — the oracle never touches jax, exactly
                 like the reference's topdown engine) and must pin
                 ``JAX_PLATFORMS=cpu`` into any child process they
                 spawn so children don't walk into the same backend.

A process that runs on the chip must not lean on either fallback:
chip_smoke.py fails on any probe verdict that is not ok.

The verdict is cached process-wide: the decision is per-process by
nature (a jax backend initializes once).

Test hook: ``GATEKEEPER_PROBE_TEST_HANG=1`` makes the probe thread
sleep forever instead of calling jax — simulating a backend that never
answers.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import threading
import time

DEFAULT_TIMEOUT_S = 45.0


@dataclasses.dataclass(frozen=True)
class ProbeResult:
    ok: bool                # devices answered within the deadline
    n_devices: int
    platform: str           # "tpu" / "cpu" / ... ("" when not ok)
    poisoned: bool          # probe timed out: jax unusable in-process
    reason: str             # human-readable, logged once

    @property
    def backend_label(self) -> str:
        """For bench/metrics artifacts: what actually serves evals."""
        if self.ok:
            return self.platform
        return "cpu-fallback"


_RESULT: ProbeResult | None = None
_LOCK = threading.Lock()


def _fault_probe_hang() -> bool:
    try:
        from gatekeeper_tpu.resilience import faults
        return faults.active("probe_hang")
    except Exception:   # noqa: BLE001 — probing must never depend on
        return False    # the fault harness importing cleanly


def _timeout_s() -> float:
    try:
        return float(os.environ.get(
            "GATEKEEPER_DEVICE_PROBE_TIMEOUT_S", DEFAULT_TIMEOUT_S))
    except ValueError:
        return DEFAULT_TIMEOUT_S


def probe_devices(timeout_s: float | None = None) -> ProbeResult:
    """Probe the jax backend once, bounded.  Thread-safe; cached."""
    global _RESULT
    if _RESULT is not None:
        return _RESULT
    with _LOCK:
        if _RESULT is not None:
            return _RESULT
        _RESULT = _probe_locked(
            _timeout_s() if timeout_s is None else timeout_s)
        return _RESULT


def _probe_locked(timeout_s: float) -> ProbeResult:
    if timeout_s <= 0:
        # probe disabled: trust the environment (callers inline the
        # historical unbounded behavior — jax.devices() direct)
        try:
            import jax
            devs = jax.devices()
            return ProbeResult(True, len(devs), devs[0].platform, False,
                               "probe disabled; direct device init")
        except RuntimeError as e:
            return ProbeResult(False, 0, "", False,
                               f"backend init failed: {e}")

    box: dict = {}

    def _init():
        try:
            if (os.environ.get("GATEKEEPER_PROBE_TEST_HANG") == "1"
                    or _fault_probe_hang()):
                time.sleep(3600)    # simulated unanswering backend
            if os.environ.get("GATEKEEPER_PROBE_TEST_FAIL") == "1":
                # simulated transient init error: fails WITHOUT
                # poisoning, so reprobe()/bench retry loops engage
                raise RuntimeError("simulated transient backend "
                                   "init failure (test hook)")
            import jax
            # jax.config is authoritative over the env var: mirror the
            # env in before first device contact when config is unset
            # or the env pins cpu (a cpu-pinned child must never reach
            # an accelerator's backend init).  A non-cpu env var never
            # overrides an explicit in-process pin (the test conftest's
            # cpu config stays authoritative).
            plats = os.environ.get("JAX_PLATFORMS")
            cur = getattr(jax.config, "jax_platforms", None)
            if plats and plats != cur and (not cur or plats == "cpu"):
                jax.config.update("jax_platforms", plats)
            devs = jax.devices()
            box["devs"] = (len(devs), devs[0].platform)
        except BaseException as e:   # noqa: BLE001 — report, don't die
            box["err"] = e

    t = threading.Thread(target=_init, name="device-probe", daemon=True)
    start = time.perf_counter()
    t.start()
    t.join(timeout_s)
    took = time.perf_counter() - start
    if t.is_alive():
        # Poisoned: the hung thread may hold jax's backend-init lock.
        # Children we spawn must not walk into the same dead plugin.
        os.environ["JAX_PLATFORMS"] = "cpu"
        return ProbeResult(
            False, 0, "", True,
            f"jax backend init did not answer within {timeout_s:.0f}s; "
            "serving from the scalar/CPU path (set "
            "GATEKEEPER_DEVICE_PROBE_TIMEOUT_S to adjust)")
    if "err" in box:
        return ProbeResult(False, 0, "", False,
                           f"backend init failed after {took:.1f}s: "
                           f"{box['err']}")
    n, platform = box["devs"]
    return ProbeResult(True, n, platform, False,
                       f"{n} {platform} device(s) in {took:.1f}s")


def mark_unavailable(reason: str) -> None:
    """Downgrade the process-wide verdict after the fact: an execution
    (not the probe) discovered the backend hangs or died.  Every driver
    constructed from now on serves scalar-only, and children get pinned
    to cpu via child_env().  One-way: a hung backend does not come back
    for this process (its in-flight op is still stuck) — this routes to
    the backend supervisor as a *poisoned* (terminal) failure.  For a
    recoverable degradation, call
    ``resilience.supervisor.get_supervisor().report_failure(reason)``
    instead: that path re-probes with backoff and can return to
    healthy."""
    from gatekeeper_tpu.resilience.supervisor import get_supervisor
    get_supervisor().report_failure(reason, poisoned=True)


def _install_result(res: ProbeResult) -> None:
    """Supervisor-owned verdict transitions (degrade/recover) land
    here so probe_devices()/child_env() stay coherent with supervisor
    state.  Not for general use."""
    global _RESULT
    with _LOCK:
        _RESULT = res
    try:
        from gatekeeper_tpu.obs.flightrecorder import record_event
        record_event("probe_result", ok=res.ok, platform=res.platform,
                     n_devices=res.n_devices, poisoned=res.poisoned,
                     reason=res.reason)
    except Exception:   # noqa: BLE001 — observability is best-effort
        pass


def reprobe(timeout_s: float | None = None) -> ProbeResult:
    """Drop a *non-poisoned* failed verdict and probe again (bench's
    bounded retry loop).  An ok or poisoned verdict is returned as-is:
    success needs no retry, and a poisoned process must never re-enter
    backend init — the hung thread may still hold jax's init lock."""
    global _RESULT
    with _LOCK:
        r = _RESULT
        if r is not None and (r.ok or r.poisoned):
            return r
        _RESULT = None
    return probe_devices(timeout_s)


def reset_for_tests() -> None:
    """Drop the cached verdict (tests only — a real process's verdict
    is immutable because a jax backend initializes once).  Also drops
    the backend supervisor singleton, which is seeded from it."""
    global _RESULT
    with _LOCK:
        _RESULT = None
    try:
        from gatekeeper_tpu.resilience import faults, supervisor
        supervisor.reset_for_tests()
        faults.reset_for_tests()
    except Exception:   # noqa: BLE001 — reset must stay usable even if
        pass            # the resilience package is mid-import


def holds_accelerator() -> bool:
    """Has this process brought up a non-cpu jax backend?  Such a
    process holds its chips until it exits, so a child that opens them
    would fail or hang."""
    r = _RESULT
    if r is not None and r.ok and r.platform != "cpu":
        return True
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return False
    return jax.default_backend() != "cpu"


def child_env(base: dict | None = None) -> dict:
    """Environment for child processes we spawn.  The child is pinned
    to CPU when this process holds an accelerator (one process per
    chip) or fell back from one (so the child does not spend its own
    probe timeout rediscovering the failure)."""
    env = dict(os.environ if base is None else base)
    r = _RESULT
    if (r is not None and not r.ok) or holds_accelerator():
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("GATEKEEPER_PROBE_TEST_HANG", None)
        env.pop("GATEKEEPER_PROBE_TEST_FAIL", None)
    return env
