#!/usr/bin/env python3
"""The readings that the correctness limits are set from, in one process.

    python3 bench/control.py --workload star7-cs1-2x2 --program --fault exchange --control 0 1 2

With ``--program`` the program's solve (the compiled program the window
drives, at the cell's sizes) answers every right-hand side of the cell's
set and is checked as a run checks it.  ``--fault <name>`` then plants that
fault of ``bench/faults.py`` in the program and reads it the same way.
For each index after ``--control`` the control answers: the plain
reference BiCGStab put in the program's place, storing its fields and
vectors in the precision below the configuration's (``control`` in the
configuration file), with the reference SpMV in that precision as its
operator.  The operator probe of right-hand side ``k`` is drawn from seed
``k``.  One JSON line per solve; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def control_answer(config: dict, problem, k: int):
    """The control's solution of right-hand side ``k``, and its iterations."""
    import jax.numpy as jnp

    from bench import reference

    c = config["control"]
    x, it = reference.bicgstab(problem.fields, problem.bs[k], tol=config["tol"],
                               maxiter=config["maxiter"], storage=jnp.dtype(c["storage"]),
                               compute=jnp.dtype(c["compute"]))
    return x, int(it)


def control_apply(config: dict, problem):
    """The control's SpMV: the reference in the control's precision."""
    import jax
    import jax.numpy as jnp

    from bench import reference

    c = config["control"]
    storage, compute = jnp.dtype(c["storage"]), jnp.dtype(c["compute"])
    # stored apart, as the control's solve keeps them: fused into the SpMV,
    # the TPU's compiler drops the round trip through the lower precision
    low = jax.jit(lambda fields: {o: f.astype(storage) for o, f in fields.items()})(
        problem.fields)

    @jax.jit
    def apply(low, v):
        return reference.apply(low, v.astype(storage), compute).astype(v.dtype)

    return functools.partial(apply, low)


def readings(cell, program: bool, fault: str | None, control_ks, *, set_attr=setattr):
    """Yield one dict per solve: who solved which right-hand side, and the
    checks.  ``set_attr`` plants the fault."""
    import jax

    from bench import faults, harness
    from repro.launch.mesh import make_mesh_for_devices

    harness.devices_for(cell.chips)
    mesh = make_mesh_for_devices(cell.chips)
    config = cell.config
    problem = harness.build_problem(config, cell.traffic, harness.solve_sharding(mesh))
    cf = harness.program_coeffs(problem.fields)
    runs = ([("program", None)] if program else []) + ([(f"fault:{fault}", fault)] if fault else [])
    for who, name in runs:
        if name:
            faults.FAULTS[name](set_attr)
        compiled = harness.solve_fn(config, mesh).lower(cf, problem.bs[0]).compile()
        apply = functools.partial(harness.operator_fn(config, mesh), cf)
        for k, b in enumerate(problem.bs):
            res = jax.block_until_ready(compiled(cf, b))
            ok = bool(res.converged) and not bool(res.breakdown)
            gap = harness.operator_gap(problem, apply, k)
            yield {"who": who, "rhs": k, "iterations": int(res.iterations),
                   "checks": harness.checks(config, problem, {"answer": (k, res.x)},
                                            int(not ok), gap)}
            del res
        del compiled
    apply = control_apply(config, problem)
    for k in control_ks:
        x, it = control_answer(config, problem, k)
        yield {"who": "control", "rhs": k, "iterations": it,
               "checks": harness.checks(config, problem, {"answer": (k, x)}, 0,
                                        harness.operator_gap(problem, apply, k))}
        del x


def main(argv=None) -> int:
    from bench import faults

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    ap.add_argument("--control", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    from bench import harness

    harness.enable_compile_cache()
    cell = harness.load_cell(args.workload)
    try:
        for r in readings(cell, args.program, args.fault, args.control):
            r["passes"] = harness.passes(r["checks"])
            print(json.dumps(dict(r, workload=cell.name)), flush=True)
    except harness.NoAccelerator as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
