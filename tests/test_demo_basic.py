"""demo/basic must stay green: it is the reference's basic walkthrough
(sync -> policy -> good/bad fixtures -> synchronous rejection of
malformed gatekeeper resources -> audit)."""

import os
import subprocess
import sys


def test_basic_demo_passes():
    # pin the child to CPU (see test_demo_agilebank.py)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, "demo/basic/demo.py"],
        capture_output=True, text=True, timeout=300, cwd="/root/repo",
        env=env)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "DEMO PASS" in out.stdout
    # two policy denials + three malformed-resource rejections
    assert out.stdout.count("Error from server (Forbidden)") == 5
    assert "already taken by namespace" in out.stdout      # inventory join
    assert "- name: no-label" in out.stdout                # audit catch-up
