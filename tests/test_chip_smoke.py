"""chip_smoke.py's phases at a tiny size on the CPU (the parity logic the
chip run relies on), and its refusal to report success without a TPU."""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture
def engine(monkeypatch):
    """The smoke's engine settings (the defaults, one chip); tiny
    batches still take the device path (conftest zeroes
    SMALL_WORKLOAD_EVALS the same way)."""
    from gatekeeper_tpu.engine import jax_driver
    monkeypatch.delenv("GATEKEEPER_PAGES", raising=False)
    monkeypatch.setenv("GATEKEEPER_SHARDS", "1")
    monkeypatch.setattr(jax_driver, "REVIEW_BATCH_MIN_EVALS", 1)
    return chip_smoke.Oracle(None)


@pytest.fixture
def north(engine):
    return chip_smoke.phase_audit(300, 3, 120, 42, engine, steady_reps=2,
                                  churn_frac=0.05)


def test_audit_phase_matches_oracle(north):
    assert north["n_constraints"] == 9
    sub = north["subsample"]
    assert sub["digest"] == sub["oracle_digest"] and sub["verdicts"] > 0
    assert north["capped_results"] > 0 and north["churn_rows"] == 15
    assert north["churn_capped_changed"] > 0


def test_default_audit_runs_on_the_device_not_the_ledger(north):
    """By default no kind is served from the host-paged ledger, whose
    cold entry confirms every row on the scalar oracle."""
    st = next(iter(north["driver"].state.values()))
    assert st.ledger is None
    disp = north["driver"].last_sweep_phases["dispatch"]
    assert len(disp["device"]) == 3 and not disp["scalar"]


def test_admission_phase_matches_oracle(north, engine):
    out = chip_smoke.phase_admission(north, 40, 42, engine)
    assert out["webhook_denied"] == len(chip_smoke.EXPECTED_DENY)
    assert out["webhook_admitted"] == 3
    rb = out["review_batch"]
    assert rb["digest"] == rb["oracle_digest"] and rb["verdicts"] > 0
    assert out["review_batch_dispatches"] == 3


def test_library_phase_dispatches_every_lowered_kind(engine):
    out = chip_smoke.phase_library(400, 150, 52, engine)
    assert out["device_kinds"] == out["n_templates"]
    assert out["scalar_kinds"] == {}
    sub = out["subsample"]
    assert sub["digest"] == sub["oracle_digest"]


def test_lost_churn_update_fails_the_phase(engine, monkeypatch):
    """A churn edit that never reaches the churned client (as a delta
    that drops a row would) must fail the fresh-driver comparison."""
    edits = chip_smoke.churn_edits

    class Lossy(dict):
        def values(self):           # what the client upserts
            return list(super().values())[1:]

    monkeypatch.setattr(chip_smoke, "churn_edits",
                        lambda *a: Lossy(edits(*a)))
    with pytest.raises(chip_smoke.SmokeFailure, match="fresh driver"):
        chip_smoke.phase_audit(300, 3, 60, 42, engine, steady_reps=1,
                               churn_frac=0.05)


def test_digest_mismatch_fails_the_phase(engine, monkeypatch):
    monkeypatch.setattr(chip_smoke, "oracle_audit",
                        lambda *a: [("K8sX", "c", "r", "planted")])
    with pytest.raises(chip_smoke.SmokeFailure, match="digest"):
        chip_smoke.phase_library(200, 50, 52, engine)


def test_mesh_phase_matches_one_chip(engine, monkeypatch):
    """conftest's 8 virtual devices: the (c, r) = (2, 4) mesh against the
    unsharded driver, same process."""
    out = chip_smoke.phase_mesh(512, 2, 7)
    assert out["mesh_shape"] == {"c": 2, "r": 4}
    assert out["mesh"]["digest"] == out["one_chip"]["digest"]
    assert len(out["mesh"]["memory"]) == 8


@pytest.mark.parametrize("alone", [False, True],
                         ids=["cpu-platform", "script-alone"])
def test_no_ok_line_without_a_tpu(tmp_path, alone):
    """Under JAX_PLATFORMS=cpu, and copied into a directory that holds
    nothing else of the repo, the script exits nonzero and never prints
    the ok line."""
    script = os.path.join(REPO, "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
