"""The check that decides ``correct`` in the HPCG cell, at a size a test run
holds, on the CPU: a sound run passes; the control (the plain reference
BiCGStab in bfloat16, the precision below the configuration's float32) and
a CG step that leaves its iterate unchanged fail."""

import time

import jax
import jax.numpy as jnp
import pytest

from bench import control, harness
from repro.core.solvers import cg as solver_cg

CELL = "hpcg-384-1chip"
MESH = [32, 32, 32]


def tiny() -> harness.Cell:
    cell = harness.load_cell(CELL)
    cell.traffic = dict(cell.traffic, mesh=MESH)
    return cell


@pytest.fixture(autouse=True)
def cpu_stands_in(monkeypatch):
    monkeypatch.setattr(harness, "devices_for", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "peak_of", lambda kind: {})


def run(seed: int = 3141592653589) -> dict:
    return harness.run_cell(tiny(), seed=seed, seconds=0.3, trace=False,
                            t_start=time.perf_counter(), log=lambda m: None)


def test_sound_run_is_correct():
    out = run()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 4
    assert out["checks"]["operator_gap"]["value"] == 0.0
    assert out["checks"]["unconverged_solves"]["value"] == 0
    assert set(out["metrics"]) == {"solve_s", "setup_s"}   # the CPU keeps no memory stats


def test_bf16_control_fails():
    cell = tiny()
    assert cell.config["control"] == {"storage": "bfloat16", "compute": "bfloat16"}
    for reading in control.readings(cell, False, None, [0, 1]):
        assert not harness.passes(reading["checks"]), reading
        assert reading["checks"]["residual_answer"]["value"] > \
            reading["checks"]["residual_answer"]["limit"], reading


def test_fault_state_unchanged(monkeypatch):
    real = solver_cg.run_krylov

    def frozen(step, init, **kw):
        # every step leaves x, r, p as they were and reports convergence
        def idle(carry):
            i, *state, res2, conv, brk = carry
            return (i + 1, *state, res2, jnp.ones_like(conv), brk)
        return real(idle, init, **kw)

    monkeypatch.setattr(solver_cg, "run_krylov", frozen)
    out = run()
    assert not out["correct"] and out["failed"] == 0, out["checks"]
