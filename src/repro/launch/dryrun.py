import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=" + os.environ.get(
    "REPRO_DRYRUN_DEVICES", "512")

"""Multi-pod dry-run: lower + compile every (architecture x shape x mesh)
cell against the production mesh and extract the roofline terms.

This file MUST set XLA_FLAGS before any jax import (jax locks the device
count at first init), which is why the docstring sits below the os.environ
lines.  Do not import this module from tests — run it as a script:

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2_1_5b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun

Per cell it records: memory_analysis (fits-per-chip proof), cost_analysis
(per-chip HLO flops/bytes), the collective schedule parsed from the compiled
HLO (op x shape x replica-group), and the three roofline terms of
EXPERIMENTS.md §Roofline.
"""

import argparse
import dataclasses
import json
import math
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.configs.stencil_cs1 import STENCIL_CELLS
from repro.core import bicgstab, precision
from repro.core.halo import FabricAxes
from repro.core.perfmodel import HBM_BW, PEAK_FLOPS  # one v5e chip
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.models.transformer import ArchConfig

LINK_BW = 50e9               # bytes/s per ICI link

LM_SHAPES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


# ---------------------------------------------------------------------------
# Collective parsing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[^\]]*\][^\s]*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_ITOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
_PAIRS_RE = re.compile(r"source_target_pairs=\{(.*?)\}\}?,")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, n_devices: int) -> int:
    m = _GROUPS_ITOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return n_devices


def parse_collectives(hlo_text: str, n_devices: int) -> dict:
    """Per-chip collective traffic from the (per-device SPMD) compiled HLO.

    bytes_raw  = sum of output-shape bytes (the assignment's "operand sizes").
    bytes_link = ring-model bytes that actually cross a link per chip:
      all-reduce 2(g-1)/g, all-gather/reduce-scatter/all-to-all (g-1)/g,
      collective-permute 1x.
    """
    ops = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None or "-done" in line:
            continue
        type_str, kind = m.group(1), m.group(2)
        nbytes = _shape_bytes(type_str)
        g = _group_size(line, n_devices)
        if kind == "all-reduce":
            factor = 2 * (g - 1) / max(g, 1)
        elif kind == "collective-permute":
            factor = 1.0
        else:
            factor = (g - 1) / max(g, 1)
        ops.append({"op": kind, "bytes": nbytes, "group": g,
                    "link_bytes": nbytes * factor})
    agg: dict = {}
    for o in ops:
        a = agg.setdefault(o["op"], {"count": 0, "bytes": 0, "link_bytes": 0.0})
        a["count"] += 1
        a["bytes"] += o["bytes"]
        a["link_bytes"] += o["link_bytes"]
    return {
        "by_op": agg,
        "total_bytes": sum(o["bytes"] for o in ops),
        "total_link_bytes": sum(o["link_bytes"] for o in ops),
        "n_collectives": len(ops),
    }


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------

def _mem_dict(mem) -> dict:
    return {k: getattr(mem, k) for k in (
        "argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes",
        "alias_size_in_bytes", "generated_code_size_in_bytes")}


def analyze(compiled, mesh, *, model_flops: float | None = None,
            steps_per_unit: float = 1.0) -> dict:
    n_dev = math.prod(mesh.devices.shape)
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    coll = parse_collectives(compiled.as_text(), n_dev)
    t_comp = flops / PEAK_FLOPS
    t_mem = bytes_acc / HBM_BW
    t_coll = coll["total_link_bytes"] / LINK_BW
    dominant = max(("compute", t_comp), ("memory", t_mem), ("collective", t_coll),
                   key=lambda kv: kv[1])[0]
    out = {
        "n_devices": n_dev,
        "per_chip_flops": flops,
        "per_chip_bytes": bytes_acc,
        "collectives": coll,
        "t_compute_s": t_comp,
        "t_memory_s": t_mem,
        "t_collective_s": t_coll,
        "t_bound_s": max(t_comp, t_mem, t_coll),
        "dominant": dominant,
        "memory_analysis": _mem_dict(compiled.memory_analysis()),
    }
    if model_flops is not None:
        hlo_global = flops * n_dev
        out["model_flops_global"] = model_flops
        out["useful_flops_ratio"] = model_flops / hlo_global if hlo_global else 0.0
        out["mfu_bound"] = (model_flops / n_dev / PEAK_FLOPS) / max(
            out["t_bound_s"], 1e-30) / steps_per_unit
    return out


def _compile_step(cfg: ArchConfig, shape, mesh):
    """Lower+compile the cell's step under the ambient mesh."""
    params = M.abstract_params(cfg, mesh)
    batch = M.input_specs(cfg, shape, mesh)
    with jax.sharding.set_mesh(mesh):
        if shape.kind == "train":
            opt = M.abstract_opt_state(cfg, mesh)
            step = M.make_train_step(cfg)
            out_sh = M.out_shardings_for_train(cfg, mesh)
            lowered = jax.jit(step, donate_argnums=(0, 1),
                              out_shardings=out_sh).lower(params, opt, batch)
        elif shape.kind == "prefill":
            caches = M.abstract_caches(cfg, shape, mesh)
            step = M.make_prefill_step(cfg, shape)
            lowered = jax.jit(step, donate_argnums=(2,)).lower(params, batch, caches)
        else:
            caches = M.abstract_caches(cfg, shape, mesh)
            step = M.make_serve_step(cfg)
            lowered = jax.jit(step, donate_argnums=(2,)).lower(params, batch, caches)
        return lowered.compile()


def _cost_vector(compiled, mesh) -> dict:
    n_dev = math.prod(mesh.devices.shape)
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):  # pre-0.4.30 jax: one dict per device
        cost = cost[0] if cost else {}
    coll = parse_collectives(compiled.as_text(), n_dev)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll_bytes": float(coll["total_bytes"]),
        "coll_link_bytes": float(coll["total_link_bytes"]),
        "n_collectives": coll["n_collectives"],
    }


def _extrapolate(c1: dict, c2: dict, n_periods: int) -> dict:
    """total = probe1 + (P-1) * (probe2 - probe1): exact for a periodic stack."""
    out = {}
    for k in c1:
        out[k] = c1[k] + (n_periods - 1) * (c2[k] - c1[k])
    return out


def lower_lm_cell(arch: str, shape_name: str, multi_pod: bool,
                  cfg: ArchConfig | None = None, *, probes: bool = True) -> dict:
    cfg = cfg or get_config(arch)
    shape = M.SHAPES[shape_name]
    ok, reason = M.cell_is_supported(cfg, shape)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16", "kind": shape.kind}
    if not ok:
        rec["status"] = "skipped"
        rec["skip_reason"] = reason
        return rec

    from repro.models.param import rule_overrides
    with rule_overrides(dict(cfg.rules)):
        return _lower_lm_cell_inner(arch, shape_name, multi_pod, cfg, shape,
                                    rec, probes)


def _lower_lm_cell_inner(arch, shape_name, multi_pod, cfg, shape, rec, probes):
    mesh = make_production_mesh(multi_pod=multi_pod)

    # (A) full-depth scanned compile: the sharding/memory proof
    t0 = time.time()
    compiled = _compile_step(cfg, shape, mesh)
    rec["lower_compile_s"] = time.time() - t0
    rec["memory_analysis"] = _mem_dict(compiled.memory_analysis())
    rec["scan_cost_raw"] = _cost_vector(compiled, mesh)

    # (B) unrolled 1-/2-period cost probes: exact per-period extrapolation
    # (XLA cost analysis counts loop bodies once; see model.probe_config)
    n_dev = math.prod(mesh.devices.shape)
    has_rwkv = any(s.kind == "rwkv" for s in cfg.period)
    if probes and has_rwkv and shape.kind != "decode":
        # RWKV cost is affine in seq_len => bilinear (depth x T) probes keep
        # the chunk loop tiny enough to unroll exactly.
        Ta, Tb = 2 * cfg.rwkv_chunk, 4 * cfg.rwkv_chunk
        t0 = time.time()

        def cv(k, T):
            sh = dataclasses.replace(shape, seq_len=T)
            return _cost_vector(_compile_step(M.probe_config(cfg, k, T), sh, mesh), mesh)

        c1a, c2a, c1b, c2b = cv(1, Ta), cv(2, Ta), cv(1, Tb), cv(2, Tb)
        rec["probe_compile_s"] = time.time() - t0
        T = shape.seq_len
        cost = {}
        for key in c1a:
            b_a, b_b = c2a[key] - c1a[key], c2b[key] - c1b[key]
            a_a, a_b = c1a[key] - b_a, c1b[key] - b_b
            b_T = b_a + (b_b - b_a) * (T - Ta) / (Tb - Ta)
            a_T = a_a + (a_b - a_a) * (T - Ta) / (Tb - Ta)
            cost[key] = a_T + cfg.n_periods * b_T
        rec["probe_mode"] = "bilinear_depth_x_seq"
        rec["probe1_cost"], rec["probe2_cost"] = c1a, c2b
    elif probes:
        t0 = time.time()
        c1 = _cost_vector(_compile_step(M.probe_config(cfg, 1, shape.seq_len),
                                        shape, mesh), mesh)
        c2 = _cost_vector(_compile_step(M.probe_config(cfg, 2, shape.seq_len),
                                        shape, mesh), mesh)
        rec["probe_compile_s"] = time.time() - t0
        cost = _extrapolate(c1, c2, cfg.n_periods)
        rec["probe_mode"] = "depth"
        rec["probe1_cost"], rec["probe2_cost"] = c1, c2
    else:
        cost = rec["scan_cost_raw"]

    t_comp = cost["flops"] / PEAK_FLOPS
    t_mem = cost["bytes"] / HBM_BW
    t_coll = cost["coll_link_bytes"] / LINK_BW
    n = M.n_params(cfg)
    n_act = M.n_active_params(cfg)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    model_flops = mult * n_act * tokens
    hlo_global = cost["flops"] * n_dev

    rec.update({
        "n_devices": n_dev,
        "per_chip_flops": cost["flops"],
        "per_chip_bytes": cost["bytes"],
        "coll_bytes": cost["coll_bytes"],
        "coll_link_bytes": cost["coll_link_bytes"],
        "n_collectives": cost["n_collectives"],
        "t_compute_s": t_comp,
        "t_memory_s": t_mem,
        "t_collective_s": t_coll,
        "t_bound_s": max(t_comp, t_mem, t_coll),
        "dominant": max(("compute", t_comp), ("memory", t_mem),
                        ("collective", t_coll), key=lambda kv: kv[1])[0],
        "model_flops_global": model_flops,
        "useful_flops_ratio": model_flops / hlo_global if hlo_global else 0.0,
        "n_params": n,
        "n_active_params": n_act,
        "tokens_per_step": tokens,
    })
    from repro.launch.roofline_model import lm_cell_memory_estimate
    est = lm_cell_memory_estimate(cfg, shape, mesh)
    rec.update(est)
    rec["t_memory_est_s"] = est["est_hbm_traffic_bytes"] / HBM_BW
    rec["t_bound_est_s"] = max(t_comp, rec["t_memory_est_s"], t_coll)
    rec["dominant_est"] = max(
        ("compute", t_comp), ("memory", rec["t_memory_est_s"]),
        ("collective", t_coll), key=lambda kv: kv[1])[0]
    rec["roofline_fraction"] = (model_flops / n_dev / PEAK_FLOPS) / max(
        rec["t_bound_s"], 1e-30)
    rec["roofline_fraction_est"] = (model_flops / n_dev / PEAK_FLOPS) / max(
        rec["t_bound_est_s"], 1e-30)
    rec["status"] = "ok"
    return rec


def _compile_stencil(cell, mesh, policy, *, fused, schedule):
    fabric = FabricAxes.from_mesh(mesh)
    X, Y, Z = cell.mesh_shape
    spec = fabric.spec(3)
    sh = NamedSharding(mesh, spec)
    rep = NamedSharding(mesh, P())
    vec = jax.ShapeDtypeStruct((X, Y, Z), policy.storage, sharding=sh)
    scl = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    from repro.core.stencil import StencilCoeffs, DIAGS_3D
    cf = StencilCoeffs({n: vec for n in DIAGS_3D})
    it = bicgstab.make_iteration_fn(mesh, policy=policy, fused_reductions=fused,
                                    schedule=schedule)
    lowered = jax.jit(it, donate_argnums=(1, 2, 3)).lower(cf, vec, vec, vec, vec, scl)
    return lowered.compile()


def lower_stencil_cell(cell_name: str, multi_pod: bool, *, fused: bool = True,
                       schedule: str = "overlap", policy_name: str | None = None) -> dict:
    """Stencil BiCGStab iteration roofline.

    Two compiles: the requested policy (usually bf16_mixed — proves the
    16-bit program partitions and fits) and an f32 twin used for FLOP
    counting.  On the CPU backend, bf16 math lowers through explicit
    converts that HloCostAnalysis counts as flops (a ~19x artifact absent
    on TPU, where bf16 is native); the f32 twin counts the same real
    arithmetic without converts (measured ratio vs the paper's 44
    ops/meshpoint: 1.11).  Bytes for the 16-bit policy are the f32 bytes
    scaled by the storage-width ratio — identical op schedule, half-width
    words — and halo collective-permute traffic scales the same way.
    """
    cell = STENCIL_CELLS[cell_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    policy = precision.get_policy(policy_name or cell.policy)
    X, Y, Z = cell.mesh_shape
    rec = {"arch": f"stencil_{cell_name}", "shape": "bicgstab_iter",
           "mesh": "2x16x16" if multi_pod else "16x16", "kind": "solver",
           "fused_reductions": fused, "schedule": schedule,
           "policy": policy.name}
    n_dev = math.prod(mesh.devices.shape)

    t0 = time.time()
    compiled = _compile_stencil(cell, mesh, policy, fused=fused, schedule=schedule)
    rec["lower_compile_s"] = time.time() - t0
    rec["memory_analysis"] = _mem_dict(compiled.memory_analysis())
    rec["policy_cost_raw"] = _cost_vector(compiled, mesh)

    if policy.storage != jnp.float32:
        c32 = _compile_stencil(cell, mesh, precision.F32, fused=fused,
                               schedule=schedule)
        cost32 = _cost_vector(c32, mesh)
        ratio = jnp.dtype(policy.storage).itemsize / 4.0
        cost = {
            "flops": cost32["flops"],
            "bytes": cost32["bytes"] * ratio,
            "coll_bytes": cost32["coll_bytes"] * ratio,
            "coll_link_bytes": cost32["coll_link_bytes"] * ratio,
            "n_collectives": cost32["n_collectives"],
        }
        rec["f32_cost_raw"] = cost32
    else:
        cost = rec["policy_cost_raw"]

    npts = X * Y * Z
    model_flops = 44.0 * npts          # paper Table I: 44 ops/meshpoint/iter
    t_comp = cost["flops"] / PEAK_FLOPS
    t_mem = cost["bytes"] / HBM_BW
    t_coll = cost["coll_link_bytes"] / LINK_BW
    from repro.launch.roofline_model import stencil_cell_memory_estimate
    pods = 2 if multi_pod else 1
    est = stencil_cell_memory_estimate(
        cell.mesh_shape, (16, 16, pods),
        itemsize=jnp.dtype(policy.storage).itemsize)
    rec.update({
        "n_devices": n_dev,
        "per_chip_flops": cost["flops"],
        "per_chip_bytes": cost["bytes"],
        "coll_link_bytes": cost["coll_link_bytes"],
        "n_collectives": cost["n_collectives"],
        "t_compute_s": t_comp,
        "t_memory_s": t_mem,
        "t_collective_s": t_coll,
        "t_memory_est_s": est["est_hbm_traffic_bytes"] / HBM_BW,
        "t_bound_s": max(t_comp, t_mem, t_coll),
        "dominant": max(("compute", t_comp), ("memory", t_mem),
                        ("collective", t_coll), key=lambda kv: kv[1])[0],
        "model_flops_global": model_flops,
        "useful_flops_ratio": model_flops / (cost["flops"] * n_dev),
        "meshpoints": npts,
        "paper_iter_us_cs1": 28.1 if cell_name == "cs1_paper" else None,
        **est,
    })
    rec["roofline_fraction"] = (model_flops / n_dev / PEAK_FLOPS) / max(
        rec["t_bound_s"], 1e-30)
    rec["status"] = "ok"
    return rec


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def run_cells(cells, out_dir: str) -> list[dict]:
    os.makedirs(out_dir, exist_ok=True)
    results = []
    for kind, name, shape, multi_pod in cells:
        tag = f"{name}__{shape}__{'pod2' if multi_pod else 'pod1'}"
        path = os.path.join(out_dir, tag + ".json")
        if os.path.exists(path):
            with open(path) as f:
                rec = json.load(f)
            if rec.get("status") in ("ok", "skipped"):
                print(f"[cached] {tag}: {rec.get('status')}")
                results.append(rec)
                continue
        print(f"[lower ] {tag} ...", flush=True)
        try:
            if kind == "lm":
                rec = lower_lm_cell(name, shape, multi_pod)
            else:
                rec = lower_stencil_cell(name, multi_pod)
        except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
            rec = {"arch": name, "shape": shape,
                   "mesh": "2x16x16" if multi_pod else "16x16",
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
        with open(path, "w") as f:
            json.dump(rec, f, indent=2)
        status = rec.get("status")
        extra = ""
        if status == "ok":
            extra = (f" dominant={rec['dominant']}"
                     f" t_bound={rec['t_bound_s']:.3e}s"
                     f" compile={rec.get('lower_compile_s', 0):.0f}s")
        print(f"[done  ] {tag}: {status}{extra}", flush=True)
        results.append(rec)
    return results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", help="arch id or stencil cell (stencil:<name>)")
    ap.add_argument("--shape", help="shape name (LM archs)", default=None)
    ap.add_argument("--mesh", choices=["single", "pod", "both"], default="both")
    ap.add_argument("--all", action="store_true", help="run every assigned cell")
    ap.add_argument("--stencil-only", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    pods = {"single": [False], "pod": [True], "both": [False, True]}[args.mesh]
    cells: list = []
    if args.all or args.stencil_only:
        if not args.stencil_only:
            for arch in ARCH_IDS:
                for shape in LM_SHAPES:
                    for mp in pods:
                        cells.append(("lm", arch, shape, mp))
        for cell in ("cs1_paper", "joule_600", "joule_370"):
            for mp in pods:
                cells.append(("stencil", cell, "bicgstab_iter", mp))
    elif args.arch and args.arch.startswith("stencil:"):
        for mp in pods:
            cells.append(("stencil", args.arch.split(":", 1)[1], "bicgstab_iter", mp))
    elif args.arch:
        shapes = [args.shape] if args.shape else LM_SHAPES
        for shape in shapes:
            for mp in pods:
                cells.append(("lm", args.arch, shape, mp))
    else:
        ap.error("pass --arch or --all")

    results = run_cells(cells, args.out)
    n_ok = sum(r.get("status") == "ok" for r in results)
    n_skip = sum(r.get("status") == "skipped" for r in results)
    n_err = sum(r.get("status") == "error" for r in results)
    print(f"\n=== dry-run: {n_ok} ok, {n_skip} skipped, {n_err} errors "
          f"of {len(results)} cells ===")
    if n_err:
        for r in results:
            if r.get("status") == "error":
                print(" ERROR:", r["arch"], r["shape"], r["mesh"], "-", r["error"])
        raise SystemExit(1)


if __name__ == "__main__":
    main()
