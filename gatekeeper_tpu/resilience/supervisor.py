"""Backend supervisor: a state machine over the jax device backend.

Replaces the one-shot, one-way ``device_probe.mark_unavailable``
demotion with supervised transitions::

    healthy ──failure──> degraded ──re-probe──> recovering ──ok──> healthy
                             ^                       │
                             └───────fail────────────┘
    (any)  ──poisoned failure──> poisoned            (terminal)

Serving paths consult ``use_device()`` per dispatch (JaxDriver's
``scalar_only`` is a property over it), so a mid-sweep demotion routes
the *remaining* kinds through the scalar oracle while the sweep still
completes with correct verdicts — SURVEY §5's "device failure =>
recompile/retry on CPU fallback", but now with a road back.

Re-probes are *bounded* (a tiny device op on a daemon thread with a
join deadline — never an unbounded jax call from the supervisor) and
run with exponential backoff from a background thread.  ``poisoned``
is terminal: a probe that timed out may still hold jax's backend-init
lock, so re-entering jax from this process is never safe (this
preserves the old ``mark_unavailable`` contract, which now routes here
with ``poisoned=True``).

On the degraded->healthy edge, registered recovery listeners fire
(drivers drop compiled-fn caches and re-jit onto the recovered
backend; the audit manager re-warms; controllers re-reconcile
templates).  State, reason, and transition counts are exported through
``utils.metrics`` and surfaced by ``probe --health`` and the webhook's
``/healthz``.
"""

from __future__ import annotations

import os
import threading
import time
import weakref
from typing import Callable

from gatekeeper_tpu.utils.log import logger
from gatekeeper_tpu.utils.metrics import Metrics

_log = logger("supervisor")

HEALTHY = "healthy"
DEGRADED = "degraded"
RECOVERING = "recovering"
POISONED = "poisoned"

# stable numeric encoding for the state gauge (dashboards alert on >0)
STATE_CODE = {HEALTHY: 0, RECOVERING: 1, DEGRADED: 2, POISONED: 3}

DEFAULT_BACKOFF_S = 2.0
BACKOFF_FACTOR = 2.0
BACKOFF_CAP_S = 60.0
DEFAULT_REPROBE_TIMEOUT_S = 10.0


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


class BackendSupervisor:
    """Process-wide singleton (``get_supervisor()``); the verdict is
    per-process by nature, like the probe result it supersedes."""

    def __init__(self, metrics: Metrics | None = None):
        self.metrics = metrics or Metrics()
        self._lock = threading.RLock()
        self._state = HEALTHY
        self._reason = ""
        self._since = time.time()
        self._last_probe_at: float | None = None
        self._last_ok_at: float | None = None
        self._reprobe_attempts = 0
        self._platform = ""
        self._n_devices = 0
        # recovery listeners: weakly-held (owner, method-name) pairs so
        # short-lived drivers don't accumulate in the singleton, plus
        # strong plain callables for process-lifetime hooks.
        self._weak_listeners: list[tuple[weakref.ref, str]] = []
        self._listeners: list[Callable[[], None]] = []
        self._reprobe_thread: threading.Thread | None = None
        self._stop_evt = threading.Event()
        self._seeded = False
        self._seed_lock = threading.Lock()
        self._gauge_state()

    # ------------------------------------------------------------------
    # seeding from the initial probe verdict

    def _ensure_seeded(self) -> None:
        if self._seeded:
            return
        with self._seed_lock:
            if self._seeded:
                return
            # probe outside self._lock (first contact can take ~45s);
            # concurrent readers block here, exactly as they blocked on
            # probe_devices() before the supervisor existed
            from gatekeeper_tpu.utils import device_probe
            res = device_probe.probe_devices()
            with self._lock:
                if res.ok:
                    self._state = HEALTHY
                    self._platform = res.platform
                    self._n_devices = res.n_devices
                    self._last_ok_at = time.time()
                else:
                    self._state = POISONED if res.poisoned else DEGRADED
                    self.metrics.counter("backend_degradations").inc()
                self._reason = res.reason
                self._last_probe_at = time.time()
                self._gauge_state()
            self._seeded = True
        if not res.ok and not res.poisoned:
            self._maybe_start_reprobe_loop()

    # ------------------------------------------------------------------
    # read side (hot path: lock-free state read)

    @property
    def state(self) -> str:
        self._ensure_seeded()
        return self._state

    @property
    def reason(self) -> str:
        self._ensure_seeded()
        return self._reason

    def use_device(self) -> bool:
        """May callers dispatch onto the jax device path right now?
        Consulted per dispatch (driver ``scalar_only`` property), so it
        must stay cheap: one attribute read after the first call."""
        if not self._seeded:
            self._ensure_seeded()
        return self._state == HEALTHY

    def status(self) -> dict:
        self._ensure_seeded()
        with self._lock:
            return {
                "state": self._state,
                "reason": self._reason,
                "since": self._since,
                "last_probe_at": self._last_probe_at,
                "last_ok_at": self._last_ok_at,
                "reprobe_attempts": self._reprobe_attempts,
                "platform": self._platform,
                "n_devices": self._n_devices,
                "backend": (self._platform if self._state == HEALTHY
                            else "cpu-fallback"),
            }

    # ------------------------------------------------------------------
    # transitions

    def report_failure(self, reason: str, poisoned: bool = False) -> None:
        """An execution (or the probe) discovered the backend is gone.
        ``poisoned=True`` is terminal — a hung jax op may hold the
        backend-init lock, so this process must never re-enter jax on
        the device path (the old ``mark_unavailable`` contract)."""
        self._ensure_seeded()
        with self._lock:
            if self._state == POISONED:
                return
            target = POISONED if poisoned else DEGRADED
            if self._state == target and not poisoned:
                self._reason = reason
                return
            prev = self._state
            self._state = target
            self._reason = reason
            self._since = time.time()
            self.metrics.counter("backend_degradations").inc()
            self._gauge_state()
        _log.warning("backend degraded", state=target, reason=reason)
        # flight recorder: the degradation edge is THE moment the last
        # minute of evidence matters — record the transition, then dump
        # the ring (outside the lock; dump does file I/O)
        self._flight_transition(prev, target, reason, dump=True)
        self._pin_children_to_cpu()
        if not poisoned:
            self._maybe_start_reprobe_loop()

    @staticmethod
    def _flight_transition(prev: str, new: str, reason: str,
                           dump: bool = False) -> None:
        """Record a supervisor transition in the flight recorder and
        optionally dump the ring.  Best-effort: observability must
        never alter supervisor behavior."""
        try:
            from gatekeeper_tpu.obs.flightrecorder import \
                get_flight_recorder
            rec = get_flight_recorder()
            rec.record("supervisor_transition", frm=prev, to=new,
                       reason=reason)
            if dump:
                rec.dump(f"supervisor:{new}")
        except Exception:   # noqa: BLE001
            pass

    def reprobe_now(self, timeout_s: float | None = None) -> bool:
        """Synchronous bounded re-probe; True iff the backend is (or
        becomes) healthy.  Poisoned processes never re-probe."""
        self._ensure_seeded()
        with self._lock:
            if self._state == POISONED:
                return False
            if self._state == HEALTHY:
                return True
            prev = self._state
            self._state = RECOVERING
            self._gauge_state()
        self._flight_transition(prev, RECOVERING, "re-probe")
        if timeout_s is None:
            timeout_s = _env_float("GATEKEEPER_SUPERVISOR_REPROBE_TIMEOUT_S",
                                   DEFAULT_REPROBE_TIMEOUT_S)
        ok, n, platform, err = self._device_check(timeout_s)
        now = time.time()
        with self._lock:
            self._last_probe_at = now
            self._reprobe_attempts += 1
            if ok:
                self._state = HEALTHY
                self._reason = f"recovered: {n} {platform} device(s)"
                self._since = now
                self._last_ok_at = now
                self._platform = platform
                self._n_devices = n
                self.metrics.counter("backend_recoveries").inc()
            else:
                self._state = DEGRADED
                self.metrics.counter("backend_reprobe_failures").inc()
                if err:
                    self._reason = f"{self._reason} (re-probe: {err})" \
                        if "(re-probe:" not in self._reason else self._reason
            self._gauge_state()
        self._flight_transition(
            RECOVERING, HEALTHY if ok else DEGRADED,
            self._reason if ok else "re-probe failed")
        if ok:
            _log.info("backend recovered", platform=platform, n_devices=n)
            self._install_probe_result(True, n, platform)
            self._fire_recovery()
        return ok

    def _device_check(self, timeout_s: float):
        """Run one tiny jax device op on a daemon thread with a join
        deadline.  Returns (ok, n_devices, platform, err)."""
        from gatekeeper_tpu.resilience import faults
        box: dict = {}

        def _check():
            try:
                if (faults.active("probe_hang")
                        or os.environ.get("GATEKEEPER_PROBE_TEST_HANG") == "1"):
                    time.sleep(3600)    # simulated unanswering backend
                import jax
                import jax.numpy as jnp
                devs = jax.devices()
                # an actual dispatch, not just device enumeration: a
                # half-dead backend can enumerate but not execute
                jnp.add(jnp.int32(1), jnp.int32(1)).block_until_ready()
                box["devs"] = (len(devs), devs[0].platform)
            except BaseException as e:   # noqa: BLE001 — report, don't die
                box["err"] = e

        t = threading.Thread(target=_check, name="backend-reprobe",
                             daemon=True)
        t.start()
        t.join(timeout_s)
        if t.is_alive():
            return False, 0, "", f"re-probe hung past {timeout_s:.0f}s"
        if "err" in box:
            return False, 0, "", str(box["err"])
        n, platform = box["devs"]
        return True, n, platform, ""

    # ------------------------------------------------------------------
    # background re-probe loop (exponential backoff)

    def _maybe_start_reprobe_loop(self) -> None:
        if os.environ.get("GATEKEEPER_SUPERVISOR_REPROBE", "1") == "0":
            return
        with self._lock:
            if (self._reprobe_thread is not None
                    and self._reprobe_thread.is_alive()):
                return
            self._stop_evt.clear()
            self._reprobe_thread = threading.Thread(
                target=self._reprobe_loop, name="backend-reprobe-loop",
                daemon=True)
            self._reprobe_thread.start()

    def _reprobe_loop(self) -> None:
        delay = _env_float("GATEKEEPER_SUPERVISOR_BACKOFF_S",
                           DEFAULT_BACKOFF_S)
        while True:
            if self._stop_evt.wait(delay):
                return
            with self._lock:
                st = self._state
            if st in (HEALTHY, POISONED):
                return
            if self.reprobe_now():
                return
            delay = min(delay * BACKOFF_FACTOR, BACKOFF_CAP_S)

    # ------------------------------------------------------------------
    # recovery listeners

    def on_recovery(self, fn: Callable[[], None]) -> None:
        """Register a process-lifetime recovery hook (strong ref)."""
        with self._lock:
            self._listeners.append(fn)

    def add_recovery_listener(self, owner: object, method: str) -> None:
        """Register ``getattr(owner, method)()`` to run on recovery.
        The owner is held weakly: short-lived drivers (tests construct
        hundreds) don't leak into the process singleton."""
        with self._lock:
            self._weak_listeners.append((weakref.ref(owner), method))

    def _fire_recovery(self) -> None:
        with self._lock:
            weak = list(self._weak_listeners)
            strong = list(self._listeners)
        live: list[tuple[weakref.ref, str]] = []
        for ref, method in weak:
            owner = ref()
            if owner is None:
                continue
            live.append((ref, method))
            try:
                getattr(owner, method)()
            except Exception as e:   # noqa: BLE001 — a listener must not
                _log.warning("recovery listener failed",   # break recovery
                             listener=method, error=e)
        with self._lock:
            self._weak_listeners = live
        for fn in strong:
            try:
                fn()
            except Exception as e:   # noqa: BLE001
                _log.warning("recovery listener failed",
                             listener=getattr(fn, "__name__", "fn"), error=e)

    # ------------------------------------------------------------------
    # plumbing

    def _gauge_state(self) -> None:
        self.metrics.gauge("backend_supervisor_state").set(
            STATE_CODE.get(self._state, -1))

    def _pin_children_to_cpu(self) -> None:
        """Keep ``device_probe.child_env`` coherent with supervisor
        state: while degraded, children must not walk into the same
        failed backend (and the probe verdict they'd inherit agrees)."""
        from gatekeeper_tpu.utils import device_probe
        with self._lock:
            poisoned = self._state == POISONED
            reason = self._reason
        device_probe._install_result(device_probe.ProbeResult(
            False, 0, "", poisoned, reason))
        os.environ["JAX_PLATFORMS"] = "cpu"

    def _install_probe_result(self, ok: bool, n: int, platform: str) -> None:
        from gatekeeper_tpu.utils import device_probe
        device_probe._install_result(device_probe.ProbeResult(
            ok, n, platform, False, self._reason))
        # drop our cpu pin only if the recovered platform is not cpu
        # (a cpu-pinned process that recovered cpu stays pinned)
        if ok and platform != "cpu" \
                and os.environ.get("JAX_PLATFORMS") == "cpu":
            os.environ.pop("JAX_PLATFORMS", None)

    def stop(self) -> None:
        self._stop_evt.set()
        t = self._reprobe_thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=5)


    def peek_state(self) -> str:
        """State WITHOUT triggering the seed probe — for hot-path
        readers (the overload ladder runs on every admission request)
        that must never pay the ~45s first-contact device probe.  An
        unseeded supervisor reads as healthy: the ladder only wants
        degradation signals that some dispatch already discovered."""
        return self._state if self._seeded else HEALTHY


_SUP: BackendSupervisor | None = None
_SUP_LOCK = threading.Lock()


def get_supervisor() -> BackendSupervisor:
    global _SUP
    if _SUP is not None:
        return _SUP
    with _SUP_LOCK:
        if _SUP is None:
            _SUP = BackendSupervisor()
        return _SUP


def peek_state() -> str:
    """Module-level hot-path read: current supervisor state without
    creating the singleton or triggering its seed probe.  The overload
    ladder calls this per admission request."""
    sup = _SUP
    return sup.peek_state() if sup is not None else HEALTHY


def reset_for_tests() -> None:
    """Drop the singleton (tests only; pairs with
    ``device_probe.reset_for_tests``, which calls this)."""
    global _SUP
    with _SUP_LOCK:
        sup, _SUP = _SUP, None
    if sup is not None:
        sup.stop()
