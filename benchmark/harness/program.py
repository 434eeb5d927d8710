"""The system under test: the program's public entry points and counters,
as the drivers use them."""

from __future__ import annotations

import contextlib
import gc


def annotate(name: str):
    """A region of the host's timeline in the profiler's trace."""
    import jax
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def no_gc():
    """Millions of fresh dicts: no cyclic-GC passes while making them."""
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()


def new_client(policy: dict, objs: list[dict]):
    """The program's default client over the JAX driver, with the policy
    installed and ``objs`` ingested."""
    from gatekeeper_tpu.client.client import Backend
    from gatekeeper_tpu.engine.jax_driver import JaxDriver
    from gatekeeper_tpu.target.k8s import K8sValidationTarget
    driver = JaxDriver()
    client = Backend(driver).new_client([K8sValidationTarget()])
    for doc in policy["templates"]:
        client.add_template(doc)
    for doc in policy["constraints"]:
        client.add_constraint(doc)
    client.add_data_batch(objs)
    return client, driver


def settle() -> None:
    """Wait for the program's background compiles (eager-mirror and
    delta prewarms, tier upgrades) so that none lands in the window."""
    from gatekeeper_tpu.engine import veval
    for _ in range(3):
        veval.quiesce_upgrades(timeout=600)
        with veval._BG_LOCK:
            threads = [t for t in veval._BG_THREADS if t.is_alive()]
        for t in threads:
            t.join(timeout=600)
        if not threads:
            break
    veval.quiesce_upgrades(timeout=600)


def compile_state(driver) -> str:
    """The executor's compile counters and the persistent cache's, for
    the set-up's log lines."""
    from gatekeeper_tpu.utils.compile_cache import persistent_cache_stats
    ex = driver.executor
    pc = persistent_cache_stats().snapshot()
    return (f"compiles {ex.compiles}, upgrades {ex.upgrades}, compile "
            f"{ex.compile_seconds:.3f}s, cache hits {pc['hits']} misses "
            f"{pc['misses']} requests {pc['requests']}")
