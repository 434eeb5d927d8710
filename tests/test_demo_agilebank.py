"""demo/agilebank must stay green: it is the end-to-end acceptance
scenario (multi-policy admission, inventory join, audit catch-up)."""

import os
import subprocess
import sys


def test_agilebank_demo_passes():
    # pin the child to CPU: a fresh subprocess would otherwise bring up
    # whatever accelerator the environment names, which this scenario
    # does not need (and which another process may hold)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, "demo/agilebank/demo.py"],
        capture_output=True, text=True, timeout=300, cwd="/root/repo",
        env=env)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "DEMO PASS" in out.stdout
    assert out.stdout.count("DENIED (403)") == 4
