#!/usr/bin/env python3
"""Chip smoke test: the paper's BiCGStab stencil solve on TPU, through
the normal entry point (``repro.launch.solve.main``), in this one process.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the distributed solve on a 2x2 mesh
    python chip_smoke.py --hpcg       # one chip: HPCG's MG-PCG at 384^3

One chip: ``joule_600`` (608^3, star7 convection-diffusion, bf16_mixed,
BiCGStab, overlap schedule) on the ``spmd`` and ``pallas`` backends; one
Pallas stencil SpMV at 608^3 under the solve's own tiles, in bf16 and
f32, against the plain jnp stencil on the chip; the spmd backend's
``spmv_stream`` SpMV against the jnp apply it replaces at every element
(star7 608^3, star25 504x504x352); then both
backends at 32x32x128 f32 against ``solve_ref`` run on the host CPU.
Four chips: ``cs1_paper`` (608x608x1536) on a 2x2 mesh with both
backends, ``joule_370`` on the 2x2 mesh against one chip, and both
backends at 64x64x256 f32 on the 2x2 mesh against ``solve_ref``.
HPCG: the 27-point CG solve with the multigrid V-cycle at 384^3 f32
through the entry point; then the timed path's ``symgs`` sweeps and one
V-cycle against ``bench/reference_mg.py`` at 384^3, in f32 (within
``MG_RTOL``) and in bf16_mixed (outside it).

Informative lines first; the last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed check, or a process that finds no TPU, exits nonzero before
printing it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

# bf16 unit roundoff (8 significant bits): the storage precision of the
# bf16_mixed policy
U_BF16 = 2.0 ** -8
# A bf16-stored iterate cannot push the true residual below ~u * ||A||
# ||x|| / ||b||, while the recurrence residual keeps falling: the two may
# part by this much (32 u = 0.125) in bf16_mixed before the solve is wrong.
BF16_RESIDUAL_GAP = 32 * U_BF16
# In bf16_mixed the backends round differently (the Pallas kernels round
# every stencil term to bf16, the XLA path fuses a chain and rounds once)
# and BiCGStab's path to a tolerance is sensitive to rounding: at
# 32x32x128 (interpret mode) spmd took 24 iterations to 1e-3 and pallas 30.
# Iteration counts may differ by half; true residuals by the gap above.
ITER_RTOL_BF16 = 0.5
# f32 oracle comparison (tol 1e-6): relative solution error, and iteration
# counts within 10 % of the CPU's
F32_SOLUTION_RTOL = 1e-4
ITER_RTOL_F32 = 0.10
# One SpMV against the f32 jnp stencil on the same operands: elementwise
# within one rounding of the output dtype plus f32 reassociation slack,
# 2^-19 of the sum of the terms' magnitudes (7 terms, 2 orders of
# summation, u_f32 = 2^-24 each).
F32_SLACK = 2.0 ** -19
OUTPUT_ROUNDING = {"bfloat16": U_BF16, "float32": 0.0}
# The V-cycle and its sweeps against the plain reference, max error over
# the largest value: both sum each row's 26 terms in f32 in different
# orders and Gauss-Seidel contracts errors, 2.4e-7 measured on the CPU
# (tests/test_mg.py); 1e-5 leaves 40 times that, and bf16 storage misses
# it by 2000 times.
MG_RTOL = 1e-5


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(f"[{'ok' if ok else 'FAIL'}] {what}", flush=True)
    if not ok:
        raise SmokeFailure(what)


def solve(*argv: str) -> dict:
    from repro.launch import solve as launch_solve

    print(f"--- solve {' '.join(argv)}", flush=True)
    return launch_solve.main(list(argv))


def rel_err(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def check_solve(s: dict, devices: int) -> None:
    name = f"{s['backend']} {tuple(s['shape'])} on {devices}"
    check(s["platform"] == "tpu" and s["device_count"] == devices,
          f"{name}: ran on {s['device_count']} x {s['device_kind']} "
          f"({s['platform']})")
    rec = s["recurrence_rel_residual"]
    true = s["true_rel_residual"]
    check(true <= rec + BF16_RESIDUAL_GAP and true < 0.1,
          f"{name}: true rel-residual {true:.3e} within {BF16_RESIDUAL_GAP:.3e} "
          f"of the recurrence's {rec:.3e}, and well below ||b||")
    if s["backend"] == "pallas":
        check(s["tpu_custom_calls"] > 0,
              f"{name}: compiled program holds {s['tpu_custom_calls']} "
              f"tpu_custom_call kernels (interpret off)")
    print(f"    iterations {s['iterations']}, compile {s['compile_s']:.1f}s, "
          f"first call {s['first_call_s']:.2f}s, warm {s['warm_s']:.3f}s, "
          f"process peak bytes per device {s['peak_bytes_per_device']}",
          flush=True)


def check_agree(a: dict, b: dict, what: str) -> None:
    ia, ib = a["iterations"], b["iterations"]
    check(abs(ia - ib) <= ITER_RTOL_BF16 * max(ia, ib),
          f"{what}: iterations {ia} vs {ib} within {ITER_RTOL_BF16:.0%}")
    ra, rb = a["true_rel_residual"], b["true_rel_residual"]
    check(abs(ra - rb) <= BF16_RESIDUAL_GAP,
          f"{what}: true rel-residuals {ra:.3e} vs {rb:.3e} within "
          f"{BF16_RESIDUAL_GAP:.3e}")


def cell_args(name: str) -> tuple[str, ...]:
    """``--mesh``/``--policy`` of a paper cell (``configs/stencil_cs1``)."""
    from repro.configs.stencil_cs1 import STENCIL_CELLS

    cell = STENCIL_CELLS[name]
    return ("--mesh", *map(str, cell.mesh_shape), "--policy", cell.policy)


def f32_oracle(shape: tuple[int, int, int], devices: int) -> None:
    """Both backends on ``devices`` chips against ``solve_ref`` run on the
    host CPU, in this process, on the same small f32 system: an explicit
    oracle, not a fallback."""
    import jax

    from repro.core import bicgstab, precision, stencil
    from repro.launch.solve import build_problem

    with jax.default_device(jax.devices("cpu")[0]):
        cf, _, b = build_problem("convdiff", stencil.STAR7, shape,
                                 dtype=jax.numpy.float32)
        ref = bicgstab.solve_ref(cf, b, tol=1e-6, maxiter=500,
                                 policy=precision.F32)
        x_ref = jax.device_get(ref.x)
    it_ref = int(ref.iterations)
    check(bool(ref.converged), f"CPU oracle {shape} converged in {it_ref} "
          f"iterations")
    name = "x".join(map(str, shape))
    for backend in ("spmd", "pallas"):
        s = solve("--mesh", *map(str, shape), "--policy", "f32",
                  "--devices", str(devices), "--problem", "convdiff",
                  "--maxiter", "500", "--tol", "1e-6", "--backend", backend)
        err = rel_err(jax.device_get(s.pop("x")), x_ref)
        it = s["iterations"]
        check(s["device_count"] == devices and bool(s["converged"])
              and err <= F32_SOLUTION_RTOL
              and abs(it - it_ref) <= ITER_RTOL_F32 * it_ref,
              f"{name} f32 {backend} on {devices} x {s['device_kind']} "
              f"(fabric {s['fabric']}): solution within {err:.2e} of the CPU "
              f"oracle (<= {F32_SOLUTION_RTOL:.0e}), {it} vs {it_ref} "
              f"iterations (within {ITER_RTOL_F32:.0%})")


def spmv_check(shape: tuple[int, int, int], dtype) -> None:
    """One Pallas stencil SpMV on the chip under the tile the solve takes
    at ``shape``, against the plain jnp stencil in f32 on the same random
    operands.  With f32 accumulation every element must lie within one
    rounding of the output dtype of the reference; with the bf16_mixed
    policy's own arithmetic (``compute`` = bf16) the error of both
    backends' SpMV is reported."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.core import precision, stencil, tuning
    from repro.core.halo import global_apply
    from repro.kernels.stencil_nd import stencil_apply, stencil_nd_ref
    from repro.kernels.stencil_nd.kernel import chunk_rows
    from repro.launch.mesh import make_mesh_for_devices

    spec = stencil.STAR7
    f32 = jnp.float32

    @functools.partial(jax.jit, static_argnums=0)
    def operands(key):
        keys = jax.random.split(jax.random.PRNGKey(key), spec.n_offsets + 1)
        cf = stencil.StencilCoeffs({
            n: jax.random.uniform(k, shape, f32, -0.15, 0.15).astype(dtype)
            for n, k in zip(spec.names, keys)})
        return cf, jax.random.normal(keys[-1], shape, f32).astype(dtype)

    @jax.jit
    def error(u, cf, x, rounding):
        # max over elements of |u - ref| beyond its bound, and the
        # norm-relative error
        xf = x.astype(f32)
        cl = [cf.diags[n].astype(f32) for n in spec.names]
        ref = stencil_nd_ref(xf, cl, spec.offsets)
        mag = stencil_nd_ref(jnp.abs(xf), [jnp.abs(c) for c in cl],
                             spec.offsets)
        d = jnp.abs(u.astype(f32) - ref)
        excess = jnp.max(d - rounding * jnp.abs(ref) - F32_SLACK * mag)
        return excess, jnp.sqrt(jnp.sum(d * d) / jnp.sum(ref * ref))

    dname = jnp.dtype(dtype).name
    config, source = tuning.lookup_config(spec, dtype, shape)
    bxc, byc, zc = config.tile
    nq = byc // chunk_rows(byc, zc)
    check(shape[0] // bxc > 1 and nq > 1,
          f"{dname} {shape}: the solve's tile {config.tile} ({source}) runs "
          f"{shape[0] // bxc} x-slab grid steps of {nq} row chunks per plane")
    cf, x = operands(3)
    u = stencil_apply(cf, x, accum_dtype=f32)
    excess, rel = (float(a) for a in error(u, cf, x, OUTPUT_ROUNDING[dname]))
    check(excess <= 0.0,
          f"{dname} {shape} pallas SpMV (f32 accumulation) within one "
          f"{dname} rounding of the f32 jnp stencil at every element "
          f"(norm-relative error {rel:.3e})")
    if dtype != jnp.bfloat16:
        return
    # the solve's own arithmetic in bf16_mixed, both backends
    pol = precision.MIXED
    mesh = make_mesh_for_devices(1)
    rels = {
        "pallas": error(stencil_apply(cf, x, accum_dtype=pol.compute),
                        cf, x, 0.0)[1],
        "spmd": error(jax.jit(lambda c, v: global_apply(mesh, c, v,
                                                        policy=pol))(cf, x),
                      cf, x, 0.0)[1]}
    for backend, r in rels.items():
        r = float(r)
        check(r <= 8 * U_BF16,
              f"{shape} bf16_mixed {backend} SpMV: norm-relative error "
              f"{r:.3e} against the f32 stencil (<= 8 u = {8 * U_BF16:.3e})")


def stream_check(specname: str, shape: tuple[int, int, int]) -> None:
    """The spmd backend's SpMV kernel (``spmv_stream``) on the chip against
    the jnp interior apply it replaces, both in bf16_mixed as the solve
    runs them: equal at every element."""
    import jax
    import jax.numpy as jnp

    from repro.core import precision, stencil
    from repro.core.halo import interior_apply
    from repro.kernels.stencil_nd.stream import stream_interior_apply

    spec = stencil.get_spec(specname)
    pol = precision.MIXED
    f32 = jnp.float32

    @jax.jit
    def operands(key):
        keys = jax.random.split(key, spec.n_offsets + 1)
        cf = stencil.StencilCoeffs({
            n: jax.random.uniform(k, shape, f32, -0.15, 0.15).astype(pol.storage)
            for n, k in zip(spec.names, keys)})
        return cf, jax.random.normal(keys[-1], shape, f32).astype(pol.storage)

    cf, x = operands(jax.random.PRNGKey(5))
    out = {"xla": jax.jit(lambda c, v: interior_apply(c, v, policy=pol))(cf, x),
           "stream": jax.jit(lambda c, v: stream_interior_apply(
               c, v, policy=pol))(cf, x)}
    d = jnp.abs(out["stream"].astype(f32) - out["xla"].astype(f32))
    n_diff = int(jnp.sum(out["stream"] != out["xla"]))
    check(n_diff == 0,
          f"{specname} {shape}: spmv_stream equals the jnp/XLA interior "
          f"apply at every element ({n_diff} differ, max |diff| "
          f"{float(jnp.max(d)):.3e})")


def mg_check(shape: tuple[int, int, int]) -> None:
    """The timed path's ``symgs`` sweeps and one V-cycle on HPCG's fields
    and random vectors against ``bench/reference_mg.py``, in f32 and with
    the program in bf16_mixed."""
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, HERE)
    from bench import reference_mg
    from repro.core import operator, precision, stencil
    from repro.core.multigrid import BACKWARD, FORWARD, build_levels, colours, vcycle
    from repro.kernels import resolve_interpret
    from repro.kernels.stencil_nd.symgs import symgs_sweep

    offsets = stencil.BOX27.offsets
    fields = jax.jit(lambda: {o: jnp.full(shape, -1.0 / 26, jnp.float32)
                              for o in offsets})()
    cf = stencil.StencilCoeffs({stencil.offset_name(o): f for o, f in fields.items()})
    r, x = (jax.random.normal(jax.random.PRNGKey(k), shape, jnp.float32) for k in (1, 2))

    def rel(got, want):
        return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))
                     / jnp.max(jnp.abs(want)))

    for sweep, name in ((FORWARD, "forward"), (BACKWARD, "backward")):
        got = jax.jit(lambda r, x, f: symgs_sweep(
            r, x, [f[o] for o in offsets], offsets, first=sweep[0],
            inplane=sweep[1], interpret=resolve_interpret()))(r, x, fields)
        want = reference_mg.apply_sweep(fields, r, x, tuple(colours(sweep)))
        err = rel(got, want)
        del got, want
        check(err <= MG_RTOL, f"{shape} symgs {name} sweep against the reference: "
              f"max relative error {err:.3e} (<= {MG_RTOL:.0e})")
    want = reference_mg.apply_vcycle(fields, r)
    for pol in (precision.F32, precision.MIXED):
        def cycle(c, v):
            op = operator.make_operator("spmd", c.astype(pol.storage),
                                        policy=pol, schedule="overlap")
            return vcycle(build_levels(op), v.astype(pol.storage))
        err = rel(jax.jit(cycle)(cf, r), want)
        ok = err <= MG_RTOL if pol is precision.F32 else err > MG_RTOL
        check(ok, f"{shape} V-cycle in {pol.name} against the f32 reference: max "
              f"relative error {err:.3e} ({'<=' if pol is precision.F32 else '>'} "
              f"{MG_RTOL:.0e})")


def hpcg() -> None:
    s = solve("--stencil", "box27", "--problem", "poisson", "--solver", "cg",
              "--precond", "mg", "--mesh", "384", "384", "384", "--policy", "f32",
              "--devices", "1", "--tol", "1e-6", "--maxiter", "500")
    s.pop("x")
    check_solve(s, 1)
    check(bool(s["converged"]) and s["true_rel_residual"] < 1e-5,
          f"HPCG 384^3: converged in {s['iterations']} iterations, true "
          f"rel-residual {s['true_rel_residual']:.3e}")
    mg_check((384, 384, 384))


def one_chip() -> None:
    import jax.numpy as jnp

    from repro.kernels import resolve_interpret

    check(not resolve_interpret(), "Pallas kernels compile (interpret off)")
    joule = (*cell_args("joule_600"), "--devices", "1", "--problem",
             "convdiff", "--solver", "bicgstab", "--schedule", "overlap",
             "--maxiter", "300", "--tol", "1e-3")
    runs = {}
    for backend in ("spmd", "pallas"):
        s = solve(*joule, "--backend", backend)
        s.pop("x")
        check_solve(s, 1)
        runs[backend] = s
    check_agree(runs["spmd"], runs["pallas"], "joule_600 pallas vs spmd")
    for dtype in (jnp.bfloat16, jnp.float32):
        spmv_check((608, 608, 608), dtype)
    stream_check("star7", (608, 608, 608))
    stream_check("star25", (504, 504, 352))
    f32_oracle((32, 32, 128), 1)


def four_chips() -> None:
    import jax

    if len(jax.devices()) < 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    cs1 = (*cell_args("cs1_paper"), "--devices", "4", "--problem", "convdiff",
           "--schedule", "overlap", "--maxiter", "100", "--tol", "1e-3")
    runs = {}
    for backend in ("spmd", "pallas"):
        s = solve(*cs1, "--backend", backend)
        s.pop("x")
        check_solve(s, 4)
        peaks = s["peak_bytes_per_device"]
        check(len(peaks) == 4 and min(peaks) > 0.5 * max(peaks),
              f"cs1_paper {backend}: memory spread over the 4 chips "
              f"(peak bytes {peaks})")
        runs[backend] = s
    check_agree(runs["spmd"], runs["pallas"], "cs1_paper pallas vs spmd")

    joule = (*cell_args("joule_370"), "--problem", "convdiff", "--backend",
             "spmd", "--maxiter", "300", "--tol", "1e-3")
    four = solve(*joule, "--devices", "4")
    x4 = jax.device_get(four.pop("x"))
    check_solve(four, 4)
    one = solve(*joule, "--devices", "1")
    x1 = jax.device_get(one.pop("x"))
    check_solve(one, 1)
    check_agree(four, one, "joule_370 2x2 vs one chip")
    err = rel_err(x4, x1)
    check(err <= BF16_RESIDUAL_GAP,
          f"joule_370: 2x2 and one-chip solutions within {err:.3e} "
          f"(<= {BF16_RESIDUAL_GAP:.3e})")
    # the halo exchange and AllReduces at f32, where a dropped or misrouted
    # halo plane cannot hide under bf16 rounding
    f32_oracle((64, 64, 256), 4)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the one-chip phases; 4: only the 2x2 phases")
    ap.add_argument("--hpcg", action="store_true",
                    help="only HPCG's MG-PCG phases, on one chip")
    args = ap.parse_args()
    try:
        import jax

        from repro.launch import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repo's code ({e})", file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (default device: {dev.platform})",
              file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    try:
        if args.hpcg:
            hpcg()
        else:
            four_chips() if args.chips == 4 else one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
