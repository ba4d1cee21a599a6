"""The check that decides ``correct``, at sizes a test run holds, on the CPU.

A sound run passes.  The control (the plain reference BiCGStab in the
precision below the configuration's, put in the program's place) fails,
and so does a whole run with the timed path broken underneath in each of
the ways a solve can break: a step that leaves its state unchanged, half
of the answer left out, the halo exchange between chips left out, part of
the operator left out, and the answer altered where it is produced.  The
harness's look for a chip and its peak is skipped: the CPU stands in.
"""

import time

import jax
import jax.numpy as jnp
import pytest

from bench import control, faults, harness
from repro.core import bicgstab
from repro.core.solvers import bicgstab as solver_bicgstab

TINY = {  # cell -> (mesh, fabric) at test size
    "star7-608-1chip": ([16, 16, 32], [1, 1]),
    "star25-504-1chip": ([24, 24, 32], [1, 1]),
    "star7-cs1-2x2": ([32, 32, 32], [2, 2]),
}


def tiny(name: str) -> harness.Cell:
    cell = harness.load_cell(name)
    mesh, fabric = TINY[name]
    cell.traffic = dict(cell.traffic, mesh=mesh, fabric=fabric)
    return cell


@pytest.fixture(autouse=True)
def cpu_stands_in(monkeypatch):
    monkeypatch.setattr(harness, "devices_for", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "peak_of", lambda kind: {})


def run(name: str, seed: int = 12345678901) -> dict:
    return harness.run_cell(tiny(name), seed=seed, seconds=0.3, trace=False,
                            t_start=time.perf_counter(), log=lambda m: None)


@pytest.mark.parametrize("name", sorted(TINY))
def test_sound_run_is_correct(name):
    out = run(name)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"solve_s", "setup_s"}   # the CPU keeps no memory stats
    assert out["checks"]["operator_gap"]["value"] == 0.0
    split = tiny(name).traffic["fabric"] != [1, 1]
    assert ("seam_residual_last" in out["checks"]) == split


@pytest.mark.parametrize("name", ["star7-608-1chip", "star25-504-1chip"])
def test_control_fails(name):
    cell = tiny(name)
    for reading in control.readings(cell, False, None, [0, 1, 2, 3]):
        assert not harness.passes(reading["checks"]), reading
        assert reading["checks"]["operator_gap"]["value"] > 0, reading


def test_program_readings_pass():
    cell = tiny("star7-608-1chip")
    for r in control.readings(cell, True, None, []):
        assert harness.passes(r["checks"]), r


def test_fault_readings_fail(monkeypatch):
    cell = tiny("star25-504-1chip")
    got = list(control.readings(cell, False, "truncate", [], set_attr=monkeypatch.setattr))
    assert [r["who"] for r in got] == ["fault:truncate"] * 4
    assert not any(harness.passes(r["checks"]) for r in got)


def _with_answer(change):
    """``solve_distributed`` whose returned solution is ``change(x)``."""
    real = bicgstab.solve_distributed

    def broken(*args, **kw):
        res = real(*args, **kw)
        res.x = change(res.x)
        return res
    return broken


def test_fault_state_unchanged(monkeypatch):
    real = solver_bicgstab.run_krylov

    def frozen(step, init, **kw):
        # every step leaves x, r, p as they were and reports convergence
        def idle(carry):
            i, *state, res2, conv, brk = carry
            return (i + 1, *state, res2, jnp.ones_like(conv), brk)
        return real(idle, init, **kw)

    monkeypatch.setattr(solver_bicgstab, "run_krylov", frozen)
    out = run("star7-608-1chip")
    assert not out["correct"] and out["failed"] == 0, out["checks"]


def test_fault_half_the_answer_left_out(monkeypatch):
    def half(x):
        return x.at[: x.shape[0] // 2].set(0)
    monkeypatch.setattr(bicgstab, "solve_distributed", _with_answer(half))
    assert not run("star7-608-1chip")["correct"]


def test_fault_answer_altered(monkeypatch):
    monkeypatch.setattr(bicgstab, "solve_distributed",
                        _with_answer(lambda x: (x * 0.875).astype(x.dtype)))
    assert not run("star25-504-1chip")["correct"]


def test_fault_exchange_left_out(monkeypatch):
    faults.exchange(monkeypatch.setattr)
    out = run("star7-cs1-2x2")
    assert not out["correct"] and out["failed"] == 0, out["checks"]
    seam = out["checks"]["seam_residual_last"]
    assert seam["value"] > seam["limit"], out["checks"]


def test_fault_operator_truncated(monkeypatch):
    faults.truncate(monkeypatch.setattr)
    out = run("star25-504-1chip")
    assert not out["correct"] and out["failed"] == 0, out["checks"]
    gap = out["checks"]["operator_gap"]
    assert gap["value"] > gap["limit"], out["checks"]


def test_no_accelerator_prints_no_result(monkeypatch, capsys):
    from bench import run as bench_run

    monkeypatch.undo()      # the harness's own look for a chip
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    rc = bench_run.main(["--workload", "star7-608-1chip", "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""
