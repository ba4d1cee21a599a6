"""Persistent kernel-tuning cache: sweep once, cache winners, look up forever.

The paper's headline number — ~1/3 of machine peak on the 7-point BiCGStab
solve — comes from hand-shaping the per-PE compute to the fabric.  The
Pallas stencil kernels (``kernels/stencil_nd``) instead used one fixed
block shape for every {StencilSpec x dtype x local shape}; Jacquelin et
al.'s scaling study shows block-shape choice dominates achieved bandwidth
for the wide star operators.  This module is the production answer, the
same shape as an inference stack's kernel autotuner:

* :class:`KernelConfig` — one point of the kernel's tuning space: the
  ``(bx, by)`` x/y tile, the Z-split chunk ``zc``, and whether the
  boundary-ring patch of the overlap schedule is *fused* into the interior
  kernel's pass (one launch) or kept as separate patch launches.
* :class:`TuningCache` — a JSON-persisted map from a registry-style key
  ``"{device}/{spec}/{dtype}/{XxYxZ}"`` to the winning config plus the
  sweep record that chose it.  The device part keeps a config swept in
  one place (the CPU's interpreter, a TPU generation) from serving
  another.  Default path ``results/tuning_cache.json``; overridden (or
  disabled) by the ``REPRO_TUNING_CACHE`` env var.
* :func:`lookup_config` — the one call sites use: returns the cached
  winner when a valid entry exists, else the deterministic pre-tuning
  default (``kernels/stencil_nd.ops.default_tile``), so an empty or absent
  cache reproduces the untuned behaviour bit-for-bit.
* :func:`autotune_cell` / :func:`measure_config` — the hypothesis->measure
  sweep primitives ``launch.solve --autotune`` calls inline for its own
  cell.

Kernel imports are deferred inside functions: ``kernels/stencil_nd`` looks
configs up here, so a module-level import would cycle.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import os
import time
import warnings

import jax
import jax.numpy as jnp

from repro.core.perfmodel import MODEL_CHIP
from repro.core.stencil import StencilSpec

#: default persistence path, relative to the working directory (the repo
#: root in CI and the benchmarks); ``REPRO_TUNING_CACHE`` overrides it.
DEFAULT_CACHE_PATH = os.path.join("results", "tuning_cache.json")

#: ``REPRO_TUNING_CACHE`` values that disable cache lookup entirely.
_DISABLED = ("", "0", "off", "none", "false", "no")

#: peak memory bandwidth (bytes/s) the sweep's roofline fractions are
#: quoted against: the modeled chip of ``core/perfmodel.PEAKS``.
PEAK_BYTES_PER_S = MODEL_CHIP.hbm_bytes_per_s


def _dtype_name(dtype) -> str:
    return jnp.dtype(dtype).name


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """One point of the stencil kernel's tuning space.

    ``block`` is the (bx, by) x/y tile of the grid; ``zc`` the Z-split
    chunk; ``fuse_ring`` folds the overlap schedule's boundary-ring patch
    into the interior kernel's pass.
    """

    block: tuple[int, int]
    zc: int
    fuse_ring: bool = False

    def to_json(self) -> dict:
        return {"block": list(self.block), "zc": self.zc,
                "fuse_ring": self.fuse_ring}

    @classmethod
    def from_json(cls, d: dict) -> "KernelConfig":
        return cls(block=tuple(d["block"]), zc=int(d["zc"]),
                   fuse_ring=bool(d.get("fuse_ring", False)))

    @property
    def tile(self) -> tuple[int, int, int]:
        return tuple(self.block) + (self.zc,)

    def valid_for(self, shape: tuple[int, int, int]) -> bool:
        """Whether the kernel compiles this tile on ``shape``
        (``kernels/stencil_nd.kernel.clamp_tile`` leaves it unchanged)."""
        from repro.kernels.stencil_nd.kernel import clamp_tile

        return clamp_tile(self.tile, tuple(shape)) == self.tile


def device_key(device_kind: str | None = None) -> str:
    """The device part of a cache key: ``jax.devices()[0].device_kind``
    lower-cased with spaces as underscores (``tpu_v5_lite``, ``cpu``)."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    return device_kind.strip().lower().replace(" ", "_")


def cache_key(spec: StencilSpec, dtype, shape: tuple[int, ...],
              device_kind: str | None = None) -> str:
    """Registry-style cache key: ``tpu_v5_lite/star7/bfloat16/608x608x608``.

    It names the device the sweep ran on and the *problem cell* (shape
    contract x dtype x local block), never the code revision; re-sweep
    (``autotune_cell(..., force=True)``) after a kernel change.  ``device_kind``
    defaults to the first visible device's.
    """
    dims = "x".join(str(int(s)) for s in shape)
    return f"{device_key(device_kind)}/{spec.name}/{_dtype_name(dtype)}/{dims}"


def validate_config(config: KernelConfig, shape: tuple[int, int, int], *,
                    warn: bool = True, context: str = "") -> KernelConfig:
    """Clamp ``config`` to a tile the kernel compiles for ``shape``.

    Returns the config unchanged when it is valid; otherwise the nearest
    valid tile (``kernels/stencil_nd.kernel.clamp_tile``), with a warning
    that names both: the trace-time guard against a tile Mosaic would
    refuse — e.g. the paper's unpadded 600 x 595 tiles, where a requested
    64-plane x slab degrades to 60.
    """
    from repro.kernels.stencil_nd.kernel import clamp_tile

    tile = clamp_tile(config.tile, tuple(shape))
    if tile == config.tile:
        return config
    fixed = dataclasses.replace(config, block=tile[:2], zc=tile[2])
    if warn:
        warnings.warn(
            f"stencil kernel tile {config.tile} is not a valid tile of the "
            f"local block {shape}{context}; falling back to the nearest "
            f"valid tile {fixed.tile}",
            stacklevel=3)
    return fixed


def default_config(spec: StencilSpec, dtype,
                   shape: tuple[int, int, int]) -> KernelConfig:
    """The deterministic pre-tuning default: the VMEM-budgeted
    ``default_tile`` (whole Y and Z, the deepest x slab that fits), split
    ring epilogue — so a missing cache changes nothing."""
    from repro.kernels.stencil_nd.ops import default_tile

    bx, by, zc = default_tile(tuple(shape), jnp.dtype(dtype).itemsize,
                              radius=spec.radius, n_coeffs=spec.n_offsets)
    return KernelConfig(block=(bx, by), zc=zc, fuse_ring=False)


# ---------------------------------------------------------------------------
# The persistent cache
# ---------------------------------------------------------------------------

class TuningCache:
    """A {cache_key -> sweep record} map persisted as one JSON file.

    Each entry holds the winning ``config`` plus the measurement record
    that chose it (candidate timings, default timing, roofline fractions),
    so the cache file doubles as the sweep's results artifact.
    """

    def __init__(self, path: str | None, entries: dict | None = None):
        self.path = path
        self.entries: dict[str, dict] = dict(entries or {})

    @classmethod
    def load(cls, path: str) -> "TuningCache":
        """Load from ``path``; a missing or unreadable file is an empty
        cache (deterministic defaults), never an error."""
        try:
            with open(path) as f:
                raw = json.load(f)
            entries = raw.get("entries", {}) if isinstance(raw, dict) else {}
        except (OSError, ValueError):
            entries = {}
        return cls(path, entries)

    def save(self, path: str | None = None) -> str:
        path = path or self.path or DEFAULT_CACHE_PATH
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        payload = {
            "format": "repro.tuning_cache.v1",
            "generated_by": "repro.core.tuning",
            "peak_bytes_per_s": PEAK_BYTES_PER_S,
            "entries": {k: self.entries[k] for k in sorted(self.entries)},
        }
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        self.path = path
        return path

    def get(self, key: str) -> KernelConfig | None:
        entry = self.entries.get(key)
        if entry is None:
            return None
        try:
            return KernelConfig.from_json(entry["config"])
        except (KeyError, TypeError, ValueError):
            return None

    def put(self, key: str, config: KernelConfig, record: dict | None = None):
        self.entries[key] = {"config": config.to_json(), **(record or {})}

    def __contains__(self, key: str) -> bool:
        return key in self.entries

    def __len__(self) -> int:
        return len(self.entries)


def resolve_cache_path() -> str | None:
    """The active cache path: ``REPRO_TUNING_CACHE`` (a path, or one of
    ``0/off/none`` to disable lookup) falling back to the default."""
    env = os.environ.get("REPRO_TUNING_CACHE")
    if env is None:
        return DEFAULT_CACHE_PATH
    if env.strip().lower() in _DISABLED:
        return None
    return env


# (path -> (mtime, cache)) memo so trace-time lookups don't re-read the
# file per call; a saved cache bumps the mtime and is picked up again.
_LOADED: dict[str, tuple[float, TuningCache]] = {}


def get_cache(path: str | None = None) -> TuningCache | None:
    """The active :class:`TuningCache`, or None when lookup is disabled."""
    path = path if path is not None else resolve_cache_path()
    if path is None:
        return None
    try:
        mtime = os.path.getmtime(path)
    except OSError:
        mtime = -1.0
    hit = _LOADED.get(path)
    if hit is not None and hit[0] == mtime:
        return hit[1]
    cache = TuningCache.load(path)
    _LOADED[path] = (mtime, cache)
    return cache


def lookup_config(spec: StencilSpec, dtype, shape: tuple[int, int, int], *,
                  cache: TuningCache | None = None,
                  ) -> tuple[KernelConfig, str]:
    """The call every kernel wrapper makes: ``(config, source)``.

    ``shape`` may carry leading batch axes (a many-RHS apply); only the
    trailing mesh dims key the lookup — a cell tuned at ``(bx, by, Z)``
    serves every batch size, since the kernel's per-step working set is
    one RHS's tile either way.

    ``source`` is ``"cache"`` for a valid tuned entry, ``"default"`` when
    the cache is disabled/missing/has no entry, and ``"stale"`` when an
    entry exists but names a tile that is not valid for ``shape`` (the
    deterministic default is used, with a warning) — so tests and CI can
    assert lookups do not silently regress to defaults.
    """
    from repro.obs import metrics as obs_metrics

    shape = tuple(shape)[-3:]
    cache = cache if cache is not None else get_cache()
    key = cache_key(spec, dtype, shape)
    if cache is not None:
        tuned = cache.get(key)
        if tuned is not None:
            if tuned.valid_for(shape):
                obs_metrics.counter("tuning.lookup.cache").inc()
                return tuned, "cache"
            warnings.warn(
                f"tuning-cache entry {key!r} names tile "
                f"{tuned.tile} which is not valid for the "
                f"local block {shape} (stale entry?); using the default "
                f"config — re-sweep with tuning.autotune_cell(force=True)",
                stacklevel=2)
            obs_metrics.counter("tuning.lookup.stale").inc()
            return default_config(spec, dtype, shape), "stale"
    obs_metrics.counter("tuning.lookup.default").inc()
    return default_config(spec, dtype, shape), "default"


# ---------------------------------------------------------------------------
# The sweep primitives (hypothesis -> measure, hillclimb-style)
# ---------------------------------------------------------------------------

def candidate_configs(spec: StencilSpec, dtype,
                      shape: tuple[int, int, int], *,
                      smoke: bool = False) -> list[KernelConfig]:
    """The sweep's hypothesis set for one cell, deduplicated and valid.

    Axes: x/y tiles and Z-split chunks (the default's, the whole axis,
    and halves/quarters, each clamped to a valid extent) and ring fusion;
    tiles whose working set exceeds the VMEM budget are left out.  The
    deterministic default is always candidate 0 so the sweep's "before"
    column is measured under the same harness as every hypothesis.
    """
    from repro.kernels.stencil_nd.kernel import clamp_tile, tile_bytes
    from repro.kernels.stencil_nd.ops import VMEM_BUDGET_BYTES

    shape = tuple(shape)
    base = default_config(spec, dtype, shape)
    divs = (1, 2) if smoke else (1, 2, 4)
    tiles = [clamp_tile(tuple(n // d for n in shape), shape) for d in divs]
    axes = [sorted({base.tile[i]} | {t[i] for t in tiles}, reverse=True)
            for i in range(3)]
    itemsize = jnp.dtype(dtype).itemsize
    cands = [base]
    for tile in itertools.product(*axes):
        if tile_bytes(tile, shape, itemsize, radius=spec.radius,
                      n_tiled=spec.n_offsets + 1) > VMEM_BUDGET_BYTES:
            continue
        for fuse in (False, True):
            c = KernelConfig(block=tile[:2], zc=tile[2], fuse_ring=fuse)
            if c != base:
                cands.append(c)
    return cands


def _cell_problem(spec: StencilSpec, dtype, shape: tuple[int, int, int]):
    """Deterministic coefficients + iterate for timing one cell."""
    from repro.core import stencil

    cf = stencil.random_nonsymmetric(jax.random.PRNGKey(0), shape,
                                     dtype=dtype, spec=spec)
    v = jax.random.normal(jax.random.PRNGKey(1), shape,
                          jnp.float32).astype(dtype)
    return cf, v


def synthetic_exchange(v: jax.Array, spec: StencilSpec, fabric):
    """A collective-free stand-in for an issued depth-r halo exchange.

    Mimics ``gather_halo``'s layout exactly: the padded interior is ``v``
    bit-for-bit, the halo slabs of every *split* fabric axis carry values
    (random, standing in for a neighbor's face), and unsplit-axis halos
    stay zero (the global Dirichlet boundary).  That layout is what the
    fused-vs-split bitwise identity rests on — a non-ring cell must read
    the same (zero) unsplit-axis halo in both forms.
    """
    from repro.core import comm

    r = spec.radius
    vp = jnp.pad(v, r)
    key = jax.random.PRNGKey(2)
    for axis, name, n in fabric.split_info(v.ndim):
        if name is None or n == 1:
            continue
        for side in (slice(0, r), slice(vp.shape[axis] - r, None)):
            reg = tuple(side if i == axis else slice(None)
                        for i in range(v.ndim))
            key, sub = jax.random.split(key)
            vp = vp.at[reg].set(
                jax.random.normal(sub, vp[reg].shape,
                                  jnp.float32).astype(vp.dtype))
    return comm.HaloExchange(padded=vp, radius=r, shape=v.shape)


def spmv_bytes(spec: StencilSpec, dtype, shape: tuple[int, int, int]) -> int:
    """HBM traffic of one fused SpMV pass: each coefficient diagonal read
    once, v read once, u written once (the kernel's streaming contract)."""
    n = 1
    for s in shape:
        n *= int(s)
    return (spec.n_offsets + 2) * n * jnp.dtype(dtype).itemsize


def measure_config(spec: StencilSpec, dtype, shape: tuple[int, int, int],
                   config: KernelConfig, *, repeats: int = 3,
                   interpret: bool | None = None) -> float:
    """Median wall seconds of one kernel apply under ``config``.

    ``fuse_ring=False`` times the overlap schedule's split form — the
    interior kernel plus the per-region boundary-ring patch launches;
    ``fuse_ring=True`` the fused form — one pass over the exchanged block.
    Both are timed against the same synthetic exchanged halo (no
    collectives; the schedule's compute cost is what differs).
    """
    from repro.core import comm
    from repro.core.halo import FabricAxes
    from repro.kernels.stencil_nd.ops import ring_patch_apply, tile_apply

    cf, v = _cell_problem(spec, dtype, shape)
    cf_list = [cf.diags[n] for n in spec.names]
    r = spec.radius
    # synthetic in-flight exchange: halo slabs filled, no ppermutes
    fabric = FabricAxes(nx=2, ny=2)   # shape-only: both x/y axes "split"
    exchange = synthetic_exchange(v, spec, fabric)
    vp = exchange.padded

    if config.fuse_ring:
        def apply_once(vpad):
            return tile_apply(vpad, cf_list, spec, config,
                              interpret=interpret)
        fn = jax.jit(apply_once)
        args = (vp,)
    else:
        def apply_once(vv, vpad):
            u = tile_apply(jnp.pad(vv, r), cf_list, spec, config,
                           interpret=interpret)
            ex = comm.HaloExchange(padded=vpad, radius=r, shape=vv.shape)
            return ring_patch_apply(ex, cf_list, spec, config, u, fabric,
                                    interpret=interpret)
        fn = jax.jit(apply_once)
        args = (v, vp)

    jax.block_until_ready(fn(*args))          # compile + warm
    times = []
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def autotune_cell(spec: StencilSpec, dtype, shape: tuple[int, int, int], *,
                  cache: TuningCache | None = None, force: bool = False,
                  smoke: bool = False, repeats: int = 3,
                  interpret: bool | None = None, save: bool = True) -> dict:
    """Sweep one {spec x dtype x shape} cell and persist the winner.

    A valid cached entry short-circuits the sweep (``cache_hit`` True,
    identical winner) unless ``force``.  Returns the cell record: winner
    config, per-candidate timings, the fixed-default baseline, and the
    roofline fraction before/after (bytes moved per :func:`spmv_bytes`
    against :data:`PEAK_BYTES_PER_S`).
    """
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace

    cache = cache if cache is not None else get_cache()
    if cache is None:
        cache = TuningCache(resolve_cache_path() or DEFAULT_CACHE_PATH)
    key = cache_key(spec, dtype, shape)
    cached = cache.get(key)
    if cached is not None and not force and cached.valid_for(shape):
        obs_metrics.counter("tuning.sweep.cache_hit").inc()
        rec = dict(cache.entries[key])
        rec.update(key=key, cache_hit=True)
        return rec

    obs_metrics.counter("tuning.sweep.runs").inc()
    cands = candidate_configs(spec, dtype, shape, smoke=smoke)
    bytes_moved = spmv_bytes(spec, dtype, shape)
    swept = []
    with obs_trace.span("tuning.autotune_cell", key=key,
                        n_candidates=len(cands)):
        for cfg in cands:
            t = measure_config(spec, dtype, shape, cfg, repeats=repeats,
                               interpret=interpret)
            swept.append({"config": cfg.to_json(), "seconds": t,
                          "roofline_frac": bytes_moved / t / PEAK_BYTES_PER_S})
    default_s = swept[0]["seconds"]           # candidate 0 is the default
    best = min(swept, key=lambda s: s["seconds"])
    winner = KernelConfig.from_json(best["config"])
    record = {
        "key": key, "cache_hit": False,
        "shape": list(shape), "spec": spec.name,
        "dtype": _dtype_name(dtype),
        "default_config": cands[0].to_json(),
        "default_seconds": default_s,
        "best_seconds": best["seconds"],
        "speedup_vs_default": default_s / best["seconds"],
        "roofline_frac_default": bytes_moved / default_s / PEAK_BYTES_PER_S,
        "roofline_frac_tuned": best["roofline_frac"],
        "spmv_bytes": bytes_moved,
        "n_candidates": len(swept),
        "swept": swept,
    }
    cache.put(key, winner, record)
    if save:
        cache.save()
    obs_metrics.event("autotune_sweep", key=key,
                      best_seconds=best["seconds"],
                      speedup_vs_default=record["speedup_vs_default"],
                      roofline_frac_tuned=best["roofline_frac"])
    rec = dict(cache.entries[key])
    rec.update(key=key, cache_hit=False)
    return rec


def ensure_tuned(spec: StencilSpec, dtype, shape: tuple[int, int, int], *,
                 smoke: bool = True, interpret: bool | None = None) -> dict:
    """``launch.solve --autotune``'s entry: sweep the cell only when no
    valid cache entry exists, then return the entry (a pure lookup hit on
    every later run)."""
    return autotune_cell(spec, dtype, shape, smoke=smoke,
                         interpret=interpret)
