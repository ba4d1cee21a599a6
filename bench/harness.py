"""One run of one benchmark cell: set-up, the measured window, the check
against the plain reference, and the metrics.

Everything that belongs to one configuration, traffic mix, metric or
solver is a file found by its name in ``BENCHMARK.json``:

* ``bench/configs/<config>.json`` and ``.py``: the configuration and the
  generator of its coefficient fields;
* ``bench/traffic/<traffic>.json``: the mesh, the fabric and the loop;
* ``bench/metrics/<metric>.py``: ``read(run)`` gives the metric, or None
  where the run holds nothing to read;
* ``bench/counts/<solver>.py``: ``least_words_per_point(n_offsets)``.

From the program the benchmark takes the system under test, the solve
``repro.core.bicgstab.solve_distributed`` on a mesh from
``repro.launch.mesh.make_mesh_for_devices``, exactly as
``repro.launch.solve`` calls it.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import itertools
import json
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: the persistent compilation cache, at a fixed path inside the checkout
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
#: how long the traced solves run before the measured window
TRACE_SECONDS = 2.0


class NoAccelerator(RuntimeError):
    pass


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module, found by its file name."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list      # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, benchmark: dict | None = None) -> Cell:
    bm = benchmark or load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {c["name"]: c for c in bm["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; have {sorted(cells)}")
    c = cells[name]
    conf = {x["name"]: x for x in bm["configs"]}[c["config"]]
    return Cell(
        name=name, chips=c["chips"],
        config=load_json(os.path.join(ROOT, conf["file"])),
        traffic=load_json(os.path.join(BENCH, "traffic", c["traffic"] + ".json")),
        end_to_end=[m for m in bm["end_to_end"] if applies(m, name)],
        per_layer=[m for m in bm["per_layer"] if applies(m, name)])


def enable_compile_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices_for(chips: int) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoAccelerator("JAX found no accelerator")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} devices, JAX found {len(devs)}")
    return devs[:chips]


def peak_of(kind: str) -> dict:
    peaks = load_json(os.path.join(BENCH, "peaks.json"))
    if kind not in peaks:
        raise NoAccelerator(f"no peak for device kind {kind!r} in bench/peaks.json")
    return peaks[kind]


# ---------------------------------------------------------------------------
# the problem, made on the device
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Problem:
    fields: dict        # offset -> coefficient field, storage dtype, solve's sharding
    bs: list            # the right-hand sides, storage dtype, solve's sharding
    sharding: object
    radius: int         # of the stencil
    seams: tuple        # (axis, planes per chip) of every axis the fabric splits


def build_problem(config: dict, traffic: dict, sharding) -> Problem:
    """The coefficient fields of the configuration's ``case_seed`` and the
    traffic's set of right-hand sides ``b_k = A x_k``.

    The generator's modes and the keys of the ``x_k`` are drawn on the host
    and passed as arguments, so every case runs the same compiled programs.
    Each field is computed elementwise and written in its storage dtype;
    each ``b_k`` is formed in float32 from the stored fields and a normal
    ``x_k`` stored in the same dtype."""
    import jax
    import jax.numpy as jnp

    from bench import reference

    gen = load_module("configs", config["name"])
    shape = tuple(traffic["mesh"])
    storage = jnp.dtype(config["storage"])
    params = gen.draw(config, np.random.default_rng(config["case_seed"]))
    offsets = gen.offsets(config)

    @functools.partial(jax.jit, out_shardings=sharding)
    def make_fields(params):
        cf = gen.coefficients(config, params, shape)
        return {o: cf[o].astype(storage) for o in offsets}

    @functools.partial(jax.jit, out_shardings=sharding)
    def make_rhs(fields, key):
        x = jax.random.normal(key, shape, jnp.float32).astype(storage)
        return reference.apply(fields, x).astype(storage)

    fields = make_fields(params)
    rhs = traffic["rhs"]
    bs = [make_rhs(fields, jax.random.fold_in(jax.random.PRNGKey(rhs["seed"]), k))
          for k in range(rhs["count"])]
    jax.block_until_ready(bs)
    seams = tuple((axis, shape[axis] // n) for axis, n in enumerate(traffic["fabric"]) if n > 1)
    return Problem(fields, bs, sharding, max(max(map(abs, o)) for o in offsets), seams)


def solve_fn(config: dict, mesh):
    """The system under test: the solve as ``repro.launch.solve`` jits it."""
    import jax

    from repro.core import bicgstab, precision

    policy = precision.get_policy(config["policy"])
    if policy.storage.name != config["storage"]:
        raise SystemExit(f"policy {policy.name} stores {policy.storage.name}, "
                         f"the configuration states {config['storage']}")
    kw = dict(tol=config["tol"], maxiter=config["maxiter"], policy=policy,
              solver=config["solver"], backend=config["backend"],
              precond=config["precond"], schedule=config["schedule"])
    return jax.jit(lambda c, v: bicgstab.solve_distributed(mesh, c, v, **kw))


def operator_fn(config: dict, mesh):
    """The program's SpMV, built as the solve builds its operator: ``(coeffs,
    v) -> A v`` on the solve's sharding."""
    import jax

    from repro.core import comm, precision
    from repro.core.halo import FabricAxes
    from repro.core.operator import make_operator

    policy = precision.get_policy(config["policy"])
    fabric = FabricAxes.from_mesh(mesh)
    spec = fabric.spec(3)
    schedule = comm.get_schedule(config["schedule"])

    def local(cf, v):
        return make_operator(config["backend"], cf.astype(policy.storage), fabric,
                             policy=policy, schedule=schedule).apply(v)

    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(spec, spec), out_specs=spec,
                                 check_vma=False))


def program_coeffs(fields: dict):
    from repro.core.stencil import StencilCoeffs, offset_name

    return StencilCoeffs({offset_name(o): f for o, f in fields.items()})


def solve_sharding(mesh):
    from jax.sharding import NamedSharding

    from repro.core.halo import FabricAxes

    return NamedSharding(mesh, FabricAxes.from_mesh(mesh).spec(3))


# ---------------------------------------------------------------------------
# the check against the plain reference
# ---------------------------------------------------------------------------

def operator_gap(problem: Problem, apply, seed: int) -> float:
    """An SpMV ``apply`` against the reference on a comb of unit points
    spaced ``2 r + 1`` apart, shifted as the seed draws.  Each row of the
    answer holds one stored coefficient or the unit diagonal, so a sound
    SpMV reads exactly 0, and one that drops or changes a coupling reads its
    share of the off-diagonal part, however small its weight in a solve."""
    import jax
    import jax.numpy as jnp

    from bench import reference

    spacing = 2 * problem.radius + 1
    shift = np.random.default_rng((seed, 1)).integers(0, spacing, 3)
    b = problem.bs[0]
    make = jax.jit(functools.partial(reference.comb, b.shape, spacing, dtype=b.dtype),
                   out_shardings=problem.sharding)
    c = make(jnp.asarray(shift, jnp.int32))
    return reference.operator_gap(problem.fields, c, apply(c))


def checks(config: dict, problem: Problem, answers: dict, unconverged: int,
           gap: float) -> dict:
    """Every number compared, with its limit.  ``answers`` maps a name to
    ``(k, x)``, a solution of right-hand side ``k``; each is judged by the
    reference's relative residual against the stored system, over all rows
    and over the rows next to a seam between chips.  ``gap`` is the SpMV's
    ``operator_gap``."""
    from bench import reference

    limits = config["limits"]
    out = {}
    for name, (k, x) in answers.items():
        whole, seam = reference.true_residual(problem.fields, x, problem.bs[k], problem.seams)
        out[f"residual_{name}"] = {"value": whole, "limit": limits["true_residual"]}
        if seam is not None:
            out[f"seam_residual_{name}"] = {"value": seam, "limit": limits["seam_residual"]}
    out["operator_gap"] = {"value": gap, "limit": limits["operator_gap"]}
    out["unconverged_solves"] = {"value": unconverged, "limit": 0}
    return out


def passes(checks: dict) -> bool:
    # a NaN compares False and so fails
    return all(c["value"] <= c["limit"] for c in checks.values())


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    setup_s: float
    compile_s: float
    window_s: float = 0.0
    solves: int = 0
    iterations: list = dataclasses.field(default_factory=list)
    peak_bytes: int = 0
    trace: object = None            # devtrace.Reduced of the traced solves
    traced_iterations: int = 0
    least_bytes_per_iter: float = 0.0
    peak: dict | None = None


def _solve_once(compiled, cf, b):
    """One closed-loop solve: dispatch, wait, read its counters."""
    import jax
    from jax.profiler import TraceAnnotation

    with TraceAnnotation("bench.dispatch"):
        res = compiled(cf, b)
    with TraceAnnotation("bench.wait"):
        jax.block_until_ready(res)
    with TraceAnnotation("bench.read"):
        it, conv, brk = jax.device_get((res.iterations, res.converged, res.breakdown))
    return res, int(it), bool(conv) and not bool(brk)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool, t_start: float,
             log=print) -> dict:
    import jax

    from bench import devtrace
    from repro.launch.mesh import make_mesh_for_devices

    devices = devices_for(cell.chips)
    kind = devices[0].device_kind
    peak = peak_of(kind)
    mesh = make_mesh_for_devices(cell.chips)
    fabric = list(mesh.devices.shape)
    if fabric != cell.traffic["fabric"]:
        raise SystemExit(f"mesh {fabric} is not the traffic's fabric {cell.traffic['fabric']}")
    if cell.traffic["nrhs"] != 1:
        raise SystemExit("the harness solves one right-hand side at a time (nrhs 1)")
    shape = tuple(cell.traffic["mesh"])
    config = cell.config
    problem = build_problem(config, cell.traffic, solve_sharding(mesh))
    cf = program_coeffs(problem.fields)
    # every seed solves the same set of right-hand sides, in its own order
    rng = np.random.default_rng(seed)
    order = [int(k) for k in rng.permutation(len(problem.bs))]
    t0 = time.perf_counter()
    compiled = solve_fn(config, mesh).lower(cf, problem.bs[0]).compile()
    compile_s = time.perf_counter() - t0
    kept = (order[0], _solve_once(compiled, cf, problem.bs[order[0]])[0])   # warm-up
    run = Run(setup_s=time.perf_counter() - t_start, compile_s=compile_s, peak=peak)
    log(f"set-up {run.setup_s:.3f} s (compile {compile_s:.3f} s)")

    count = load_module("counts", config["solver"])
    pts_per_chip = math.prod(shape) // cell.chips
    run.least_bytes_per_iter = (count.least_words_per_point(len(problem.fields))
                                * problem.bs[0].dtype.itemsize * pts_per_chip)

    unconverged = failed = 0        # traced solves and window solves
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            jax.profiler.start_trace(trace_dir)
            t0 = time.perf_counter()
            for j in itertools.count():
                res, it, ok = _solve_once(compiled, cf, problem.bs[order[j % len(order)]])
                run.traced_iterations += it
                unconverged += not ok
                del res
                if time.perf_counter() - t0 >= TRACE_SECONDS:
                    break
            jax.profiler.stop_trace()
            run.trace = devtrace.reduce(devtrace.load(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # the measured window: a closed loop of whole solves, over whole passes
    # through the set.  A reservoir of one keeps a solution drawn from the
    # seed for the check; exactly one earlier solution is alive during every
    # solve, so the memory peak is the same whichever is kept.
    t0 = time.perf_counter()
    res = None
    took = []
    for j in itertools.count():
        del res
        k = order[j % len(order)]
        t1 = time.perf_counter()
        res, it, ok = _solve_once(compiled, cf, problem.bs[k])
        took.append(time.perf_counter() - t1)
        run.solves += 1
        run.iterations.append(it)
        failed += not ok
        if rng.random() * run.solves < 1.0:
            kept = (k, res)
        if time.perf_counter() - t0 >= seconds and run.solves % len(order) == 0:
            break
    run.window_s = time.perf_counter() - t0
    stats = [d.memory_stats() for d in devices]
    run.peak_bytes = max((s or {}).get("peak_bytes_in_use", 0) for s in stats)
    log(f"window {run.window_s:.3f} s, {run.solves} solves, iterations "
        f"{run.iterations[:len(order)]} in the order {order}; seconds per solve "
        f"{[round(t, 4) for t in took]}")

    del compiled
    answers = {"sampled": (kept[0], kept[1].x), "last": (k, res.x)}
    del kept, res
    gap = operator_gap(problem, functools.partial(operator_fn(config, mesh), cf), seed)
    found = checks(config, problem, answers, unconverged + failed, gap)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = load_module("metrics", m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": run.peak_bytes}
    out = {"correct": passes(found), "attempted": run.solves, "failed": failed,
           "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": run.trace.device_ops,
                            "idle_gaps": run.trace.idle_gaps}
    out["checks"] = found
    return out


def report(out: dict) -> None:
    """The checks as the last lines on stderr, then the result line on stdout."""
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r}) "
              f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    out = dict(out, checks={k: {"value": _finite(c["value"]), "limit": c["limit"]}
                            for k, c in out["checks"].items()})
    print(json.dumps(out), flush=True)


def _finite(v):
    """A check's reading as JSON: a number, or its name where it is none."""
    return v if math.isfinite(v) else repr(v)
