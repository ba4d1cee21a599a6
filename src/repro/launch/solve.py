"""Stencil-solver driver: the paper's experiment through one entry point,
for the whole stencil family and the full solver x backend x
preconditioner matrix.

    PYTHONPATH=src python -m repro.launch.solve --mesh 48 48 32 --policy bf16_mixed
    PYTHONPATH=src python -m repro.launch.solve --mesh 608 608 608 --backend pallas
    PYTHONPATH=src python -m repro.launch.solve --stencil star25 --mesh 24 24 16
    PYTHONPATH=src python -m repro.launch.solve --solver cg --problem poisson
    PYTHONPATH=src python -m repro.launch.solve --precond chebyshev --problem poisson
    PYTHONPATH=src python -m repro.launch.solve --stencil box27 --problem poisson \
        --solver cg --precond mg --mesh 32 32 32 --policy f32      # HPCG's solve
    PYTHONPATH=src python -m repro.launch.solve --solver pipelined_bicgstab --schedule overlap
    PYTHONPATH=src python -m repro.launch.solve --backend pallas --autotune --mesh 16 16 8

Builds a diagonally-dominant system with the requested stencil shape
(``star7`` is the paper's 7-point MFIX class; ``star25`` the high-order
seismic shape of Jacquelin et al.; ``box27`` the full-neighborhood cube)
directly in the solve's sharding, solves it with the selected Krylov
solver on a mesh of the first ``--devices`` devices — through the SPMD
halo path or the Pallas fused-kernel backend, optionally
right-preconditioned — checks the true residual on the device, and reports
iterations, residuals, compile and warm-call seconds and peak device
memory.  :func:`main` returns the same numbers (and, under ``"x"``, the
solution array) as a dict, so a caller can drive the solve in-process.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.core import bicgstab, precision, stencil
from repro.core.comm import SCHEDULES
from repro.core.halo import FabricAxes, global_apply
from repro.core.operator import BACKENDS
from repro.core.precond import PRECONDS, PrecondConfig
from repro.core.solvers import SOLVERS
from repro.launch import enable_compile_cache
from repro.launch.mesh import make_mesh_for_devices

PROBLEMS = ("convdiff", "random", "poisson", "heterogeneous", "seismic")


def default_problem(solver: str, spec: stencil.StencilSpec) -> str:
    """The shape-appropriate problem when ``--problem`` is not given."""
    if solver in ("cg", "pipelined_cg"):
        return "poisson"          # CG wants a symmetric operator
    if spec == stencil.STAR7:
        return "convdiff"
    return "seismic" if spec.pattern == "star" else "random"


def make_coeffs(problem: str, spec: stencil.StencilSpec, shape) -> stencil.StencilCoeffs:
    """The f32 coefficients of the requested (problem, spec) pair."""
    key = jax.random.PRNGKey(0)
    if problem == "random":
        return stencil.random_nonsymmetric(key, shape, spec=spec)
    if problem == "poisson":
        return stencil.poisson(shape, spec=spec)
    if problem == "heterogeneous":
        return stencil.heterogeneous_poisson(key, shape, spec=spec)
    if problem == "seismic":
        if spec.pattern != "star":
            raise SystemExit("--problem seismic needs a star stencil")
        return stencil.high_order_star(shape, spec.radius)
    if problem == "convdiff":
        if spec != stencil.STAR7:
            raise SystemExit("--problem convdiff is the 7-point MFIX class; "
                             "use seismic/random/poisson for other stencils")
        return stencil.convection_diffusion(shape)
    raise SystemExit(f"unknown problem {problem!r}")


def build_problem(problem: str, spec: stencil.StencilSpec, shape, *,
                  dtype, nrhs: int = 1, mesh=None):
    """``(coeffs, x_true, b)`` of the manufactured system, in ``dtype``.

    ``b = A x_true`` is formed from the f32 coefficients and the stored
    ``x_true``, then rounded to ``dtype``.  With a ``mesh`` every array is
    built directly in the solve's ``NamedSharding`` — each device computes
    its own block, and no global array ever lands on one device.  The
    values equal the unsharded build's.
    """
    nb = 1 if nrhs > 1 else 0      # nrhs == 1 stays unbatched (bitwise)
    xshape = (nrhs,) * nb + tuple(shape)

    def make():
        cf = make_coeffs(problem, spec, shape)
        x_true = jax.random.normal(jax.random.PRNGKey(1), xshape,
                                   jnp.float32).astype(dtype)
        b = stencil.rhs_for_solution(cf, x_true).astype(dtype)
        return cf.astype(dtype), x_true, b

    if mesh is None:
        return jax.jit(make)()
    fabric = FabricAxes.from_mesh(mesh)
    csh = NamedSharding(mesh, fabric.spec(len(shape)))
    vsh = NamedSharding(mesh, fabric.spec(len(shape), n_batch=nb))
    # csh is a prefix: it shards every diagonal of the coefficient pytree
    return jax.jit(make, out_shardings=(csh, vsh, vsh))()


def true_residual(mesh, coeffs, x, b) -> jax.Array:
    """``||b - A x|| / ||b||`` (per RHS) in f32, on the device, in the
    solve's sharding: the stored operator applied through the halo path
    with f32 products, then fabric-wide norms."""
    def rel(cf, xx, bb):
        r = bb.astype(jnp.float32) - global_apply(
            mesh, cf, xx, policy=precision.F32)
        axes = tuple(range(r.ndim - cf.ndim, r.ndim))
        bf = bb.astype(jnp.float32)
        return (jnp.sqrt(jnp.sum(r * r, axes))
                / jnp.sqrt(jnp.sum(bf * bf, axes)))

    return jax.jit(rel)(coeffs, x, b)


def peak_bytes(devices) -> list[int]:
    """Peak bytes in use on each device since the process started; empty
    where the backend keeps no statistics, as the CPU does."""
    stats = [d.memory_stats() for d in devices]
    return [int(s["peak_bytes_in_use"]) for s in stats if s]


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", type=int, nargs=3, default=[48, 48, 32],
                    metavar=("X", "Y", "Z"))
    ap.add_argument("--devices", type=int, default=None,
                    help="solve on the first N devices (default: all)")
    ap.add_argument("--stencil", default="star7", choices=sorted(stencil.SPECS),
                    help="stencil shape: star7 (paper), star13, star25 "
                         "(seismic RTM), box27")
    ap.add_argument("--solver", default="bicgstab", choices=sorted(SOLVERS),
                    help="Krylov solver (bicgstab: the paper's; cg: symmetric; "
                         "pipelined_*: single-reduction variants, 1 fused "
                         "AllReduce/iter)")
    ap.add_argument("--backend", default="spmd", choices=sorted(BACKENDS),
                    help="SpMV backend: spmd (halo local_apply), pallas "
                         "(fused kernels + 3 AllReduces/iter), reference")
    ap.add_argument("--schedule", default="overlap", choices=sorted(SCHEDULES),
                    help="communication schedule: overlap hides the halo "
                         "ppermutes under the interior apply (bit-identical "
                         "to blocking)")
    ap.add_argument("--precond", default="none", choices=sorted(PRECONDS),
                    help="preconditioner (local — the collective schedule "
                         "is unchanged; cg applies it as the textbook PCG; "
                         "mg is HPCG's V-cycle, one device only)")
    ap.add_argument("--cheb-degree", type=int, default=3,
                    help="Chebyshev polynomial degree (extra local SpMVs "
                         "per apply, no extra AllReduces)")
    ap.add_argument("--policy", default="bf16_mixed",
                    choices=sorted(precision.POLICIES))
    ap.add_argument("--tol", type=float, default=1e-6)
    ap.add_argument("--maxiter", type=int, default=200)
    ap.add_argument("--problem", default=None, choices=list(PROBLEMS),
                    help="default: convdiff for star7, seismic for deeper "
                         "stars, random for box, poisson for --solver cg; "
                         "heterogeneous is the raw variable-diagonal case "
                         "where --precond jacobi does real work")
    ap.add_argument("--autotune", action="store_true",
                    help="sweep the Pallas kernel tuning space for this "
                         "cell if the tuning cache has no entry, then "
                         "solve with the tuned shapes (cache path: "
                         "REPRO_TUNING_CACHE or results/tuning_cache.json)")
    ap.add_argument("--nrhs", type=int, default=1,
                    help="number of right-hand sides solved as one block "
                         "(batched) Krylov solve: halo slabs of all RHS "
                         "ride each ppermute and every sync point is one "
                         "AllReduce of stacked [k, B] scalars")
    ap.add_argument("--refine", action="store_true",
                    help="iterative refinement to f32 accuracy")
    ap.add_argument("--paper-separate-reductions", action="store_true",
                    help="paper-faithful: one AllReduce per dot product")
    ap.add_argument("--obs", action="store_true",
                    help="observability: spans + metrics + a run bundle "
                         "results/runs/<run_id>/{manifest.json,events.jsonl,"
                         "trace.json} (trace.json loads in Perfetto)")
    ap.add_argument("--profile", action="store_true",
                    help="wrap the solve in jax.profiler.trace into "
                         "<run_dir>/jax_profile (implies --obs)")
    ap.add_argument("--run-dir", default=None,
                    help="bundle directory override (implies --obs; "
                         "default results/runs/<run_id>)")
    args = ap.parse_args(argv)
    if args.nrhs < 1:
        ap.error("--nrhs must be >= 1")
    args.obs = args.obs or args.profile or args.run_dir is not None
    return args


def main(argv=None) -> dict:
    """Run one solve as the command line describes; return its summary."""
    args = parse_args(argv)
    enable_compile_cache()
    run_ctx = None
    if args.obs:
        from repro.obs import manifest as obs_manifest
        from repro.obs import trace as obs_trace

        obs_trace.enable(sync=True)
        run_ctx = obs_manifest.start_run(
            "solve", config=vars(args), run_dir=args.run_dir,
            profile=args.profile)
    try:
        return _solve(args)
    finally:
        if run_ctx is not None:
            from repro.obs import manifest as obs_manifest

            obs_manifest.finish_run(run_ctx)
            print(f"run bundle: {run_ctx.run_dir}")


def _fmt(v) -> str:
    a = np.asarray(v)
    return (f"{float(a):.3e}" if a.ndim == 0
            else "[" + ", ".join(f"{x:.3e}" for x in a.reshape(-1)) + "]")


def _solve(args) -> dict:
    if args.policy == "f64":
        # get_policy("f64") refuses to hand out a policy that would silently
        # degrade; the CLI owns process startup, so it can just enable x64.
        jax.config.update("jax_enable_x64", True)
    shape = tuple(args.mesh)
    spec = stencil.get_spec(args.stencil)
    pol = precision.get_policy(args.policy)
    mesh = make_mesh_for_devices(args.devices)
    devices = list(mesh.devices.flat)
    dev0 = devices[0]
    kind = dev0.device_kind
    problem = args.problem or default_problem(args.solver, spec)
    print(f"problem {problem}/{spec.name} (radius {spec.radius}, "
          f"{spec.n_points} points) {shape} on fabric {dict(mesh.shape)} "
          f"of {len(devices)} x {kind} solver={args.solver} "
          f"backend={args.backend} schedule={args.schedule} "
          f"precond={args.precond} policy={pol.name}")

    if args.autotune:
        # tune the per-shard kernel cell the pallas backend will look up:
        # the local block shape under this fabric, in the storage dtype
        from repro.core import tuning

        fabric = FabricAxes.from_mesh(mesh)
        local = (shape[0] // fabric.nx, shape[1] // fabric.ny,
                 shape[2] // fabric.nz)
        rec = tuning.ensure_tuned(spec, pol.storage, local)
        hit = "cache hit" if rec["cache_hit"] else "swept"
        print(f"autotune[{rec['key']}]: {hit}, config={rec['config']}"
              + ("" if rec["cache_hit"] else
                 f", speedup vs default {rec['speedup_vs_default']:.2f}x"))

    t0 = time.perf_counter()
    # refinement is the f32-accuracy path: its outer residuals need the f32
    # system; a plain solve gets its data in the storage dtype
    dtype = jnp.float32 if args.refine else pol.storage
    cf, x_true, b = build_problem(problem, spec, shape, dtype=dtype,
                                  nrhs=args.nrhs, mesh=mesh)
    jax.block_until_ready(b)
    build_s = time.perf_counter() - t0
    summary = {
        "problem": problem, "stencil": spec.name, "shape": list(shape),
        "solver": args.solver, "backend": args.backend,
        "schedule": args.schedule, "precond": args.precond,
        "policy": pol.name, "nrhs": args.nrhs,
        "platform": dev0.platform, "device_kind": kind,
        "device_count": len(devices), "fabric": dict(mesh.shape),
        "build_s": build_s,
    }

    if args.refine:
        if args.nrhs > 1:
            raise SystemExit("--refine is single-RHS; drop --nrhs")
        if (args.solver, args.backend, args.precond) != ("bicgstab", "spmd", "none"):
            raise SystemExit(
                "--refine drives its own inner bicgstab/spmd solves and does "
                "not honor --solver/--backend/--precond; drop those flags")
        t0 = time.perf_counter()
        x, rels = bicgstab.solve_refined(cf, b, mesh=mesh, inner_policy=pol)
        jax.block_until_ready(x)
        dt = time.perf_counter() - t0
        print("refinement true-residual trajectory:",
              [f"{r:.2e}" for r in np.asarray(rels)])
        err = float(jnp.abs(x - x_true.astype(jnp.float32)).max())
        print(f"max err vs manufactured solution: {err:.3e}  ({dt:.2f}s)")
        summary.update(refine_residuals=np.asarray(rels).tolist(),
                       max_err=err, wall_s=dt)
        return summary

    from repro.core.solvers.common import emit_solve_metrics
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace

    pconf = PrecondConfig(name=args.precond, degree=args.cheb_degree)
    solve_kwargs = dict(
        tol=args.tol, maxiter=args.maxiter, policy=pol, solver=args.solver,
        backend=args.backend, precond=pconf, schedule=args.schedule,
        fused_reductions=not args.paper_separate_reductions)
    labels = dict(solver=args.solver, backend=args.backend,
                  schedule=args.schedule, nrhs=args.nrhs, problem=problem,
                  policy=pol.name)
    solve = jax.jit(lambda c, v: bicgstab.solve_distributed(
        mesh, c, v, **solve_kwargs))
    t0 = time.perf_counter()
    with obs_trace.span("solve.compile", **labels):
        lowered = solve.lower(cf, b)
        compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    # the first call pays one-time set-up; the warm second call is the
    # solve's time
    t0 = time.perf_counter()
    res = jax.block_until_ready(compiled(cf, b))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    with obs_trace.span("solve.krylov", **labels) as sp:
        res = compiled(cf, b)
        sp.block(res.x)
    jax.block_until_ready(res.x)
    warm_s = time.perf_counter() - t0
    emit_solve_metrics(res, wall_s=warm_s, **labels)
    if obs_trace.is_enabled():
        # collective counts of this exact solve program — the events.jsonl
        # ground truth the tests check
        counts = obs_metrics.record_collectives(lowered.as_text(), **labels)
        print(f"collectives (whole solve HLO): "
              f"allreduce={counts['allreduce_total']} "
              f"ppermute={counts['ppermute_total']}")

    true_rel = np.asarray(true_residual(mesh, cf, res.x, b))
    iters = np.asarray(res.iterations)
    rec_rel = np.asarray(res.rel_residual)
    n_iter = int(iters.max())
    peaks = peak_bytes(devices)
    summary.update(
        iterations=iters.tolist(), converged=np.asarray(res.converged).tolist(),
        recurrence_rel_residual=rec_rel.tolist(),
        true_rel_residual=true_rel.tolist(),
        compile_s=compile_s, first_call_s=first_s, warm_s=warm_s,
        tpu_custom_calls=compiled.as_text().count("tpu_custom_call"),
        peak_bytes_per_device=peaks,
        x=res.x)      # the solution itself, still on the device(s)

    print(f"iterations: {iters.tolist() if iters.ndim else int(iters)}  "
          f"converged: {np.asarray(res.converged).tolist()}")
    print(f"recurrence rel-residual: {_fmt(rec_rel)}")
    print(f"true rel-residual (f32, on device): {_fmt(true_rel)}")
    print(f"build {build_s:.2f}s, compile {compile_s:.2f}s, first call "
          f"{first_s:.2f}s, warm call "
          f"{warm_s:.3f}s ({warm_s / max(n_iter, 1) * 1e3:.2f} ms/iter on "
          f"{len(devices)} x {kind})")
    if peaks:
        print(f"peak bytes in use per device (this process so far): {peaks}")
    return summary


if __name__ == "__main__":
    main()
