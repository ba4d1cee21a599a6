"""CFD application driver: SIMPLE through the pluggable solver stack.

    PYTHONPATH=src python -m repro.launch.cfd --scenario cavity --backend spmd --precond jacobi
    PYTHONPATH=src python -m repro.launch.cfd --scenario cavity --raw-coeffs --precond jacobi
    PYTHONPATH=src python -m repro.launch.cfd --scenario channel --dt 0.05 --steps 40 \\
        --checkpoint-dir /tmp/cfd_ckpt
    PYTHONPATH=src python -m repro.launch.cfd --p-solver pipelined_bicgstab --schedule overlap

Steady mode runs the lid-driven cavity (or channel) SIMPLE loop to
convergence and, for the Re=100 cavity, verifies the Ghia et al. (1982)
centerline structure.  Transient mode (``--dt --steps``) marches implicit-
Euler time steps with under-relaxed outer loops per step; with
``--checkpoint-dir`` the run is fault-tolerant and resumable (restart from
the latest checkpoint is automatic and bit-deterministic).

``--solver/--backend/--precond/--policy`` select the same registry entries
as ``launch/solve.py`` — the application consumes the stack, it does not
reimplement it.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.apps.cfd import (
    CFDConfig, SolverOptions, TransientConfig, centerline_u, run_transient,
    solve_steady, to_staggered,
)
from repro.core import precision
from repro.core.comm import SCHEDULES
from repro.core.precond import PRECONDS
from repro.core.solvers import SOLVERS
from repro.launch import enable_compile_cache
from repro.launch.mesh import make_mesh_for_devices


def ghia_check(u_stag) -> tuple[bool, str]:
    """Qualitative Ghia et al. Re=100 centerline structure (coarse-grid band,
    same acceptance band as tests/test_cfd.py)."""
    cl = np.asarray(centerline_u(u_stag))
    checks = [
        ("return-flow strength -0.30 < min < -0.10", -0.30 < cl.min() < -0.10),
        ("return flow near mid-height", 0.25 < cl.argmin() / len(cl) < 0.75),
        ("lid-adjacent cells dragged (u > 0.4)", cl[-1] > 0.4),
        ("near-stationary bottom (|u| < 0.1)", abs(cl[0]) < 0.1),
    ]
    ok = all(passed for _, passed in checks)
    lines = [f"  [{'ok' if passed else 'FAIL'}] {name}" for name, passed in checks]
    return ok, "\n".join(lines)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="cavity", choices=["cavity", "channel"])
    ap.add_argument("--n", type=int, default=32, help="cells per side")
    ap.add_argument("--re", type=float, default=100.0, help="Reynolds number")
    ap.add_argument("--u-in", type=float, default=1.0, help="channel inflow velocity")
    ap.add_argument("--solver", default="bicgstab", choices=sorted(SOLVERS))
    ap.add_argument("--p-solver", default=None, choices=sorted(SOLVERS),
                    help="route the pressure-correction solve through a "
                         "different solver (e.g. pipelined_bicgstab: 1 "
                         "AllReduce per inner iteration); default: --solver")
    ap.add_argument("--backend", default="spmd",
                    choices=["reference", "spmd"],
                    help="operator backend for the inner solves (spmd runs "
                         "the whole SIMPLE iteration inside shard_map)")
    ap.add_argument("--schedule", default="overlap", choices=sorted(SCHEDULES),
                    help="halo communication schedule for the inner-solve "
                         "SpMVs (overlap is bit-identical to blocking)")
    ap.add_argument("--precond", default="none", choices=sorted(PRECONDS))
    ap.add_argument("--cheb-degree", type=int, default=3)
    ap.add_argument("--policy", default="f32", choices=sorted(precision.POLICIES))
    ap.add_argument("--raw-coeffs", action="store_true",
                    help="hand the solver the raw aP-diagonal rows instead of "
                         "pre-normalized unit-diagonal ones (makes --precond "
                         "jacobi do real registry work)")
    ap.add_argument("--outer", type=int, default=400,
                    help="steady outer-iteration cap (or per-step cap, see --dt)")
    ap.add_argument("--tol", type=float, default=5e-6, help="continuity tolerance")
    ap.add_argument("--dt", type=float, default=None,
                    help="time-step size: switches to the transient driver")
    ap.add_argument("--steps", type=int, default=50, help="transient time steps")
    ap.add_argument("--outers-per-step", type=int, default=20)
    ap.add_argument("--checkpoint-dir", default=None,
                    help="transient only: checkpointed fault-tolerant march "
                         "(resumes automatically from the latest checkpoint)")
    ap.add_argument("--no-check", action="store_true",
                    help="skip the Ghia centerline acceptance check")
    ap.add_argument("--obs", action="store_true",
                    help="observability: spans + metrics + a run bundle "
                         "results/runs/<run_id>/ (see docs/observability.md)")
    ap.add_argument("--profile", action="store_true",
                    help="wrap the run in jax.profiler.trace (implies --obs)")
    ap.add_argument("--run-dir", default=None,
                    help="bundle directory override (implies --obs)")
    args = ap.parse_args()
    enable_compile_cache()

    args.obs = args.obs or args.profile or args.run_dir is not None
    run_ctx = None
    if args.obs:
        from repro.obs import manifest as obs_manifest
        from repro.obs import trace as obs_trace

        obs_trace.enable(sync=True)
        run_ctx = obs_manifest.start_run(
            "cfd", config=vars(args), run_dir=args.run_dir,
            profile=args.profile)
    try:
        _cfd(args)
    finally:
        if run_ctx is not None:
            from repro.obs import manifest as obs_manifest

            obs_manifest.finish_run(run_ctx)
            print(f"run bundle: {run_ctx.run_dir}")


def _cfd(args) -> None:
    if args.policy == "f64":
        jax.config.update("jax_enable_x64", True)
    pol = precision.get_policy(args.policy)
    cfg = CFDConfig(n=args.n, reynolds=args.re, scenario=args.scenario,
                    u_in=args.u_in, outer_iters=args.outer, tol=args.tol,
                    policy=pol)
    opts = SolverOptions(solver=args.solver, backend=args.backend,
                         precond=args.precond, cheb_degree=args.cheb_degree,
                         normalize=not args.raw_coeffs,
                         schedule=args.schedule, p_solver=args.p_solver)
    mesh = make_mesh_for_devices() if args.backend != "reference" else None
    fab = dict(mesh.shape) if mesh is not None else {"local": 1}
    print(f"SIMPLE {args.scenario} n={args.n} Re={args.re:g} on fabric {fab} "
          f"solver={args.solver} p_solver={opts.pressure_solver} "
          f"backend={args.backend} schedule={args.schedule} "
          f"precond={args.precond} policy={pol.name} "
          f"rows={'raw' if args.raw_coeffs else 'unit-diagonal'}")
    if args.precond == "jacobi" and not args.raw_coeffs:
        print("note: unit-diagonal rows make jacobi the identity (the paper's "
              "pre-normalization); use --raw-coeffs for real Jacobi work")

    t0 = time.time()
    if args.dt is not None:
        tcfg = TransientConfig(dt=args.dt, n_steps=args.steps,
                               outers_per_step=args.outers_per_step)
        (u, v, p), metrics = run_transient(cfg, tcfg, opts, mesh,
                                           checkpoint_dir=args.checkpoint_dir)
        dt_wall = time.time() - t0
        last = metrics[-1] if metrics else {}
        print(f"{len(metrics)} steps of dt={args.dt:g} in {dt_wall:.1f}s "
              f"({dt_wall / max(len(metrics), 1) * 1e3:.0f} ms/step); "
              f"final continuity residual {last.get('continuity', float('nan')):.3e}")
    else:
        u, v, p, hist = solve_steady(cfg, opts, mesh)
        dt_wall = time.time() - t0
        print(f"outer iterations: {len(hist)} (continuity {hist[0]:.2e} -> "
              f"{hist[-1]:.2e}) in {dt_wall:.1f}s")
        if hist[-1] >= cfg.tol:
            print("WARNING: did not reach --tol within --outer iterations")

    u_stag, _v_stag = to_staggered(u, v)
    if args.scenario == "cavity":
        cl = np.asarray(centerline_u(u_stag))
        print(f"centerline u: min={cl.min():.3f} (Ghia Re=100 fine-grid "
              f"reference ~ -0.21; first-order upwind on {args.n}^2 is diffusive)")
        if not args.no_check and args.dt is None and 90 <= args.re <= 110:
            ok, report = ghia_check(u_stag)
            print("Ghia Re=100 centerline check:")
            print(report)
            if not ok:
                raise SystemExit(1)
    else:
        h = 1.0 / args.n
        outflux = float(u[-1, :].sum() * h)
        mid = np.asarray(u[args.n // 2, :])
        print(f"channel: outlet flux {outflux:.4f} (inflow {args.u_in:g}), "
              f"mid-channel profile center/wall = "
              f"{mid[args.n // 2]:.3f}/{mid[0]:.3f}")


if __name__ == "__main__":
    main()
