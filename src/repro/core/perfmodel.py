"""Analytic performance model for the distributed BiCGStab iteration
(paper §V's model, re-derived for the TPU roofline).

The paper validates a simple model: iteration time = compute at the vector
unit rate + communication at the fabric rate, with the AllReduce adding a
diameter-bound latency.  On TPU the same three terms are:

  t_compute    = 44 flops/pt * pts_per_chip / peak
  t_memory     = words/pt * itemsize * pts_per_chip / HBM_bw
                 (words/pt = 42: 2 SpMV sweeps reading 6 diagonals + iterate
                  + writing result, 6 AXPY r/w sweeps, 4 dot reads — §IV's
                  10-vector working set traffic)
  t_collective = halo faces (4 or 6 per SpMV, 2 SpMV) / link_bw
                 + n_reductions * allreduce_latency(mesh)

and the iteration is bound by max(compute, memory) + collective (halos can
overlap interior compute under ``schedule="overlap"``; the blocking
reductions cannot — the paper's explicit design choice, §IV-3).

Communication-schedule extension: the model is parameterized over the
solver's collective structure (:data:`SOLVER_COMMS`) and the halo schedule
(``blocking`` exposes the full halo time; ``overlap`` only the fraction the
interior cannot hide).  The pipelined solvers trade 2 (CG) or 3 (BiCGStab)
reduction latencies per iteration for one, at the price of extra memory
sweeps — :func:`predict_crossover` locates the fabric size where that
trade wins, which ``benchmarks/allreduce_model.py`` and
``benchmarks/comm_overlap.py`` report against measured schedules.
"""

from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class DevicePeak:
    """Published per-chip peaks of one accelerator, with their source."""

    flops_per_s: float           # bf16 dense FLOP/s
    hbm_bytes_per_s: float       # HBM bandwidth
    source: str


#: The one table of device peaks, keyed by ``jax.Device.device_kind``.  A
#: device that is not here (the CPU among them) has no peak: its roofline
#: share is "not measured", never a share of some other chip's peak.
PEAKS = {
    "TPU v5 lite": DevicePeak(
        flops_per_s=197e12, hbm_bytes_per_s=819e9,
        source='Google Cloud documentation, "TPU v5e" (per chip: 197 '
               'TFLOP/s bf16, 16 GB HBM at 819 GB/s)'),
}

#: the chip the analytic model below predicts for (one v5e).
MODEL_CHIP = PEAKS["TPU v5 lite"]
PEAK_FLOPS = MODEL_CHIP.flops_per_s
HBM_BW = MODEL_CHIP.hbm_bytes_per_s
LINK_BW = 50e9
HOP_LATENCY_S = 1e-6          # per-hop ICI latency (~us class)
FLOPS_PER_PT = 44.0
WORDS_PER_PT = 42.0


@dataclasses.dataclass(frozen=True)
class SolverComm:
    """Per-iteration communication/traffic structure of a registered solver.

    ``words_per_pt`` follows the §IV accounting style: SpMV sweeps read the
    coefficient diagonals + iterate and write the result (8 words each for
    star7), each AXPY-class update reads/writes 3 words, each dot reads 2.
    """

    n_spmv: int                  # SpMVs (= halo exchanges) per iteration
    reductions_fused: int        # AllReduces per iteration, fused schedule
    reductions_separate: int     # ... one psum per dot (paper-faithful)
    words_per_pt: float          # HBM words per meshpoint per iteration


#: solver name (core.solvers.SOLVERS) -> its collective structure.
SOLVER_COMMS = {
    # 2 SpMV (16) + 6 AXPY (18) + 4 dot reads (8) = 42 (§IV's 10-vector set)
    "bicgstab": SolverComm(2, 3, 5, 42.0),
    # 2 SpMV (16) + 9 AXPY (27) + 12 dot reads (24) = 67: the memory price
    # of the single-reduction reformulation (carried A-images z, t)
    "pipelined_bicgstab": SolverComm(2, 1, 12, 67.0),
    # 1 SpMV (8) + 3 AXPY (9) + 2 dot reads (4) = 21
    "cg": SolverComm(1, 2, 3, 21.0),
    # 1 SpMV (8) + 6 AXPY (18) + 2 dot reads (4) = 30 (Ghysels-Vanroose
    # z/s/p recurrence triple)
    "pipelined_cg": SolverComm(1, 1, 2, 30.0),
}


def allreduce_latency(px: int, py: int, pz: int = 1) -> float:
    """Latency-optimal AllReduce on a (px, py[, pz]) torus: ~2x diameter hops
    (reduce + broadcast), the paper's Fig. 6 scheme."""
    diameter = (px // 2) + (py // 2) + (pz // 2)
    return 2.0 * diameter * HOP_LATENCY_S


def iteration_time_model(mesh_shape, chips: int, *, itemsize: int = 2,
                         fused_reductions: bool = True,
                         fused_sweeps: bool = False,
                         solver: str = "bicgstab",
                         schedule: str = "overlap",
                         pods: int = 1) -> dict:
    """Predicted Krylov iteration time for an X*Y*Z mesh on `chips` chips.

    ``solver`` selects the per-iteration collective structure from
    :data:`SOLVER_COMMS`; ``schedule`` chooses whether the halo transfers
    hide under the interior apply (``overlap``) or serialize before it
    (``blocking``).  ``fused_sweeps`` models the Pallas fused-iteration
    kernels (BiCGStab words/pt 42 -> 28: SpMV+dot and AXPY+dot single
    passes, see kernels/fused_iter).
    """
    comm = SOLVER_COMMS[solver]
    X, Y, Z = mesh_shape
    per_pod = chips // pods
    px = py = int(math.sqrt(per_pod))
    pts_chip = X * Y * Z / chips
    words = comm.words_per_pt
    if fused_sweeps and solver == "bicgstab":
        words = 28.0

    t_comp = FLOPS_PER_PT * pts_chip / PEAK_FLOPS
    t_mem = words * itemsize * pts_chip / HBM_BW

    # halos: n_spmv x 4 faces of (block_y*Z or block_x*Z) + pod Z-faces
    bx, by = X / px, Y / py
    face_words = 2 * ((bx + by) * (Z / pods)) * 2  # both directions, per spmv
    if pods > 1:
        face_words += 2 * (bx * by) * 2
    t_halo = comm.n_spmv * face_words * itemsize / LINK_BW
    n_red = comm.reductions_fused if fused_reductions else comm.reductions_separate
    t_red = n_red * allreduce_latency(px, py, pods)

    t_interior = max(t_comp, t_mem)
    if schedule == "overlap":
        # halos hide under the interior apply; only the excess is exposed
        t_halo_exposed = max(0.0, t_halo - t_interior)
    elif schedule == "blocking":
        t_halo_exposed = t_halo
    else:
        raise KeyError(f"unknown schedule {schedule!r}; "
                       f"have ['blocking', 'overlap']")
    t_iter = t_interior + t_red + t_halo_exposed
    return {
        "t_compute_s": t_comp,
        "t_memory_s": t_mem,
        "t_halo_s": t_halo,
        "t_halo_exposed_s": t_halo_exposed,
        "t_reduce_s": t_red,
        "t_iter_s": t_iter,
        "n_reductions": n_red,
        "bound": "memory" if t_mem >= t_comp else "compute",
    }


def predict_crossover(mesh_shape, base: dict, alt: dict,
                      chip_counts=(4, 16, 64, 256, 1024, 4096, 16384, 65536),
                      **common) -> dict:
    """First fabric size where model config ``alt`` beats ``base``.

    ``base``/``alt`` are keyword overrides for :func:`iteration_time_model`
    (e.g. ``{"solver": "bicgstab"}`` vs ``{"solver": "pipelined_bicgstab"}``
    or ``{"schedule": "blocking"}`` vs ``{"schedule": "overlap"}``); the
    scan reports both predicted iteration times per chip count and the
    smallest count where the alternative is faster — the schedule-choice
    guidance ``benchmarks/comm_overlap.py`` publishes.
    """
    rows = []
    crossover = None
    for chips in chip_counts:
        t_base = iteration_time_model(mesh_shape, chips, **common, **base)
        t_alt = iteration_time_model(mesh_shape, chips, **common, **alt)
        rows.append({"chips": chips,
                     "t_base_s": t_base["t_iter_s"],
                     "t_alt_s": t_alt["t_iter_s"]})
        if crossover is None and t_alt["t_iter_s"] < t_base["t_iter_s"]:
            crossover = chips
    return {"base": base, "alt": alt, "mesh_shape": list(mesh_shape),
            "rows": rows, "crossover_chips": crossover}


def mfix_timesteps_per_second(mesh_shape, chips: int, *,
                              simple_iters: int = 15,
                              mom_solver_iters: int = 5,
                              cont_solver_iters: int = 20) -> float:
    """Paper §VI-A projection: SIMPLE wall time from the iteration model +
    Table II's matrix-forming cost (~2 us per Z-meshpoint per timestep on
    CS-1; here scaled by the memory roofline of forming ~7-point systems)."""
    solve_iters = simple_iters * (3 * mom_solver_iters + cont_solver_iters)
    t_iter = iteration_time_model(mesh_shape, chips)["t_iter_s"]
    # forming: Table II total 165-364 cycles/pt -> ~60 memory words/pt
    X, Y, Z = mesh_shape
    t_form = simple_iters * 4 * 60 * 2 * (X * Y * Z / chips) / HBM_BW
    return 1.0 / (solve_iters * t_iter + t_form)
