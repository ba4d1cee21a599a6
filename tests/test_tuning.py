"""Tuning-cache tests: round-trip persistence, key stability, fallback
semantics, tile-divisibility clamping, and the fused boundary-ring
epilogue's bitwise identity + launch accounting."""

import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import comm, stencil, tuning
from repro.core.halo import FabricAxes
from repro.kernels.stencil_nd.fused import fused_ring_apply
from repro.kernels.stencil_nd.kernel import traced_call_count
from repro.kernels.stencil_nd.ops import ring_patch_apply, tile_apply


def _cell(specname, dtype, shape, seed=0):
    spec = stencil.get_spec(specname)
    cf = stencil.random_nonsymmetric(jax.random.PRNGKey(seed), shape,
                                     dtype=dtype, spec=spec)
    v = jax.random.normal(jax.random.PRNGKey(seed + 1), shape,
                          jnp.float32).astype(dtype)
    return spec, [cf.diags[n] for n in spec.names], v


# ---------------------------------------------------------------------------
# Cache mechanics
# ---------------------------------------------------------------------------

def test_cache_key_is_stable():
    # the literal format is the contract: cache files outlive code revisions
    assert tuning.cache_key(stencil.STAR7, jnp.float32, (48, 48, 32),
                            device_kind="cpu") == "cpu/star7/float32/48x48x32"
    assert tuning.cache_key(stencil.get_spec("box27"), jnp.bfloat16,
                            (16, 8, 4), device_kind="TPU v5 lite") \
        == "tpu_v5_lite/box27/bfloat16/16x8x4"
    # the device part defaults to the device this process computes on, so
    # a CPU-interpret sweep never serves a chip (and vice versa)
    assert tuning.cache_key(stencil.STAR7, jnp.float32, (8, 8, 8)) \
        == f"{tuning.device_key(jax.devices()[0].device_kind)}/star7/float32/8x8x8"


def test_cache_round_trip(tmp_path):
    path = str(tmp_path / "cache.json")
    cache = tuning.TuningCache(path)
    cfg = tuning.KernelConfig(block=(8, 4), zc=16, fuse_ring=True)
    cache.put("star7/float32/16x8x32", cfg, {"best_seconds": 1e-3})
    cache.save()

    loaded = tuning.TuningCache.load(path)
    assert len(loaded) == 1
    assert loaded.get("star7/float32/16x8x32") == cfg
    assert loaded.entries["star7/float32/16x8x32"]["best_seconds"] == 1e-3
    with open(path) as f:
        assert json.load(f)["format"] == "repro.tuning_cache.v1"


def test_cache_load_missing_or_corrupt_is_empty(tmp_path):
    assert len(tuning.TuningCache.load(str(tmp_path / "nope.json"))) == 0
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert len(tuning.TuningCache.load(str(bad))) == 0


def test_lookup_defaults_without_cache():
    cfg, src = tuning.lookup_config(stencil.STAR7, jnp.float32, (12, 10, 8),
                                    cache=tuning.TuningCache(None))
    assert src == "default"
    assert cfg == tuning.default_config(stencil.STAR7, jnp.float32,
                                        (12, 10, 8))
    assert cfg.block == (12, 10) and not cfg.fuse_ring  # pre-tuning behavior


def test_lookup_hits_cache_and_rejects_stale():
    cache = tuning.TuningCache(None)
    tuned = tuning.KernelConfig(block=(6, 16), zc=128, fuse_ring=True)
    cache.put(tuning.cache_key(stencil.STAR7, jnp.float32, (12, 32, 256)),
              tuned)
    cfg, src = tuning.lookup_config(stencil.STAR7, jnp.float32, (12, 32, 256),
                                    cache=cache)
    assert (cfg, src) == (tuned, "cache")

    # same entry against a shape its tile no longer divides -> default + warn
    cache.put(tuning.cache_key(stencil.STAR7, jnp.float32, (13, 32, 256)),
              tuned)
    with pytest.warns(UserWarning, match="stale"):
        cfg, src = tuning.lookup_config(stencil.STAR7, jnp.float32,
                                        (13, 32, 256), cache=cache)
    assert src == "stale"
    assert cfg == tuning.default_config(stencil.STAR7, jnp.float32,
                                        (13, 32, 256))

    # an entry whose tile divides but that Mosaic would refuse (a split Y
    # axis cut off the 16-row tile) is stale too
    cache.put(tuning.cache_key(stencil.STAR7, jnp.float32, (12, 10, 8)),
              tuning.KernelConfig(block=(6, 5), zc=8))
    with pytest.warns(UserWarning, match="stale"):
        _, src = tuning.lookup_config(stencil.STAR7, jnp.float32,
                                      (12, 10, 8), cache=cache)
    assert src == "stale"


def test_lookup_ignores_batch_dim():
    """A batched (B, bx, by, Z) apply must hit the cell tuned at the mesh
    shape: only the trailing mesh dims key the lookup (the kernel's
    per-step working set is one RHS's tile either way)."""
    cache = tuning.TuningCache(None)
    tuned = tuning.KernelConfig(block=(60, 595), zc=96, fuse_ring=True)
    cache.put(tuning.cache_key(stencil.STAR7, jnp.float32, (600, 595, 96)),
              tuned)
    for shape in ((600, 595, 96), (8, 600, 595, 96), (2, 8, 600, 595, 96)):
        cfg, src = tuning.lookup_config(stencil.STAR7, jnp.float32, shape,
                                        cache=cache)
        assert (cfg, src) == (tuned, "cache"), shape
    # and an untuned batched shape still falls through to default
    _, src = tuning.lookup_config(stencil.STAR7, jnp.float32,
                                  (8, 12, 10, 8), cache=cache)
    assert src == "default"


def test_env_var_disables_lookup(monkeypatch):
    monkeypatch.setenv("REPRO_TUNING_CACHE", "off")
    assert tuning.resolve_cache_path() is None
    assert tuning.get_cache() is None
    _, src = tuning.lookup_config(stencil.STAR7, jnp.float32, (8, 8, 8))
    assert src == "default"


def test_env_var_points_lookup_at_file(tmp_path, monkeypatch):
    path = str(tmp_path / "cache.json")
    cache = tuning.TuningCache(path)
    tuned = tuning.KernelConfig(block=(4, 8), zc=8, fuse_ring=True)
    cache.put(tuning.cache_key(stencil.STAR7, jnp.float32, (8, 8, 8)), tuned)
    cache.save()
    monkeypatch.setenv("REPRO_TUNING_CACHE", path)
    cfg, src = tuning.lookup_config(stencil.STAR7, jnp.float32, (8, 8, 8))
    assert (cfg, src) == (tuned, "cache")


# ---------------------------------------------------------------------------
# Divisibility validation (satellite bugfix)
# ---------------------------------------------------------------------------

def test_nearest_divisor_paper_tiles():
    # the paper's unpadded 600 x 595 local tiles: a 64-ish request must
    # land on real divisors, not crash in pallas_call
    from repro.kernels.stencil_nd.kernel import clamp_tile

    # x takes any divisor; a split Y (Z) must be a multiple of 16 (128)
    assert clamp_tile((64, 64, 64), (600, 595, 96)) == (60, 595, 96)
    assert clamp_tile((64, 64, 4), (595, 7, 13)) == (35, 7, 13)
    assert clamp_tile((4, 40, 300), (13, 64, 512)) == (1, 32, 256)
    # a valid tile comes back unchanged
    assert clamp_tile((4, 608, 608), (608, 608, 608)) == (4, 608, 608)


def test_validate_config_clamps_and_warns():
    cfg = tuning.KernelConfig(block=(64, 64), zc=64)
    with pytest.warns(UserWarning, match="nearest valid tile"):
        fixed = tuning.validate_config(cfg, (600, 595, 96))
    # x takes any divisor; no multiple of 16 divides 595 and no multiple of
    # 128 divides 96, so Y and Z stay whole (what Mosaic accepts)
    assert fixed.block == (60, 595) and fixed.zc == 96
    assert fixed.valid_for((600, 595, 96))
    with pytest.warns(UserWarning, match="nearest valid tile"):
        aligned = tuning.validate_config(cfg, (608, 608, 1536))
    assert aligned.block == (38, 32) and aligned.zc == 1536
    assert aligned.valid_for((608, 608, 1536))
    # an already-valid config passes through untouched, no warning
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert tuning.validate_config(fixed, (600, 595, 96)) is fixed


def test_kernel_clamps_bad_tile_at_trace_time():
    """An odd-shaped block with a non-dividing requested tile must fall back
    (with a warning) and still match the untiled result bitwise."""
    spec, cl, v = _cell("star7", jnp.float32, (6, 10, 8))
    vp = jnp.pad(v, spec.radius)
    good = tuning.KernelConfig(block=(6, 10), zc=8)
    bad = tuning.KernelConfig(block=(4, 4), zc=3)   # divides nothing here
    u_ref = tile_apply(vp, cl, spec, good, interpret=True)
    with pytest.warns(UserWarning, match="nearest valid tile"):
        u_bad = tile_apply(vp, cl, spec, bad, interpret=True)
    np.testing.assert_allclose(np.asarray(u_ref), np.asarray(u_bad),
                               rtol=0, atol=0)


@pytest.mark.parametrize("specname", ["star7", "box27"])
def test_xy_tiling_bitwise_equivalence(specname):
    """Any valid (bx, by, zc) tiling is bitwise identical to the full-block
    pass (per-element canonical-order accumulation is tile-independent)."""
    spec, cl, v = _cell(specname, jnp.float32, (8, 32, 256))
    vp = jnp.pad(v, spec.radius)
    base = tile_apply(vp, cl, spec,
                      tuning.KernelConfig(block=(8, 32), zc=256),
                      interpret=True)
    for blk, zc in (((4, 32), 256), ((8, 16), 128), ((2, 16), 128),
                    ((1, 32), 128)):
        u = tile_apply(vp, cl, spec, tuning.KernelConfig(block=blk, zc=zc),
                       interpret=True)
        np.testing.assert_allclose(np.asarray(base), np.asarray(u),
                                   rtol=0, atol=0)


# ---------------------------------------------------------------------------
# Fused boundary-ring epilogue (tentpole)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("specname", ["star7", "star25", "box27"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_ring_bitwise_identical_to_split(specname, dtype):
    """The overlap schedule's two forms — interior kernel + per-region ring
    patches vs one fused pass over the exchanged block — must agree
    bitwise, for every spec depth and in reduced precision."""
    shape = (8, 8, 8) if specname == "star25" else (6, 8, 8)
    spec, cl, v = _cell(specname, dtype, shape)
    fabric = FabricAxes(nx=2, ny=2)
    config = tuning.KernelConfig(block=shape[:2], zc=shape[2])
    exchange = tuning.synthetic_exchange(v, spec, fabric)

    u_fused = fused_ring_apply(exchange, cl, spec, config, interpret=True)
    u_int = tile_apply(jnp.pad(v, spec.radius), cl, spec, config,
                       interpret=True)
    u_split = ring_patch_apply(exchange, cl, spec, config, u_int, fabric,
                               interpret=True)
    assert u_fused.dtype == u_split.dtype == v.dtype
    np.testing.assert_allclose(np.asarray(u_fused, np.float32),
                               np.asarray(u_split, np.float32),
                               rtol=0, atol=0)


def test_fused_ring_single_launch_vs_split():
    """Launch accounting: the fused form traces exactly 1 pallas_call; the
    split form 1 (interior) + one per boundary region."""
    spec, cl, v = _cell("star7", jnp.float32, (6, 8, 8))
    fabric = FabricAxes(nx=2, ny=2)
    config = tuning.KernelConfig(block=(6, 8), zc=8)
    exchange = tuning.synthetic_exchange(v, spec, fabric)
    n_regions = len(comm.boundary_regions(v.shape, fabric, spec.radius))
    assert n_regions == 4   # both x faces + both y faces on a 2x2 fabric

    c0 = traced_call_count()
    fused_ring_apply(exchange, cl, spec, config, interpret=True)
    assert traced_call_count() - c0 == 1

    c1 = traced_call_count()
    u = tile_apply(jnp.pad(v, spec.radius), cl, spec, config, interpret=True)
    ring_patch_apply(exchange, cl, spec, config, u, fabric, interpret=True)
    assert traced_call_count() - c1 == 1 + n_regions


def test_operator_fuse_ring_override_matches():
    """pallas_local_apply under the overlap schedule: fuse_ring True/False
    and the cache-resolved default all agree bitwise on a 1x1 fabric."""
    from repro.core.precision import F32
    from repro.kernels.stencil_nd import pallas_local_apply

    shape = (6, 8, 8)
    spec = stencil.STAR7
    cf = stencil.random_nonsymmetric(jax.random.PRNGKey(0), shape, spec=spec)
    cfu = stencil.StencilCoeffs(cf.diags)
    v = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
    outs = [pallas_local_apply(cfu, v, FabricAxes(), policy=F32,
                               schedule="overlap", interpret=True,
                               fuse_ring=f) for f in (None, False, True)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=0, atol=0)


# ---------------------------------------------------------------------------
# The sweep itself
# ---------------------------------------------------------------------------

def test_autotune_cell_sweeps_then_hits(tmp_path):
    cache = tuning.TuningCache(str(tmp_path / "cache.json"))
    spec = stencil.STAR7
    rec = tuning.autotune_cell(spec, jnp.float32, (8, 8, 8), cache=cache,
                               smoke=True, repeats=1, interpret=True)
    assert not rec["cache_hit"]
    assert rec["n_candidates"] >= 2
    assert rec["speedup_vs_default"] >= 1.0   # default is candidate 0
    assert rec["roofline_frac_tuned"] > 0

    # second call: pure cache hit, identical winner, no re-sweep
    rec2 = tuning.autotune_cell(spec, jnp.float32, (8, 8, 8), cache=cache,
                                smoke=True, repeats=1, interpret=True)
    assert rec2["cache_hit"]
    assert rec2["config"] == rec["config"]

    # and the persisted file serves lookups
    loaded = tuning.TuningCache.load(str(tmp_path / "cache.json"))
    cfg, src = tuning.lookup_config(spec, jnp.float32, (8, 8, 8),
                                    cache=loaded)
    assert src == "cache"
    assert cfg.to_json() == rec["config"]


def test_candidate_configs_default_first_and_valid():
    spec = stencil.get_spec("star25")
    shape = (12, 10, 16)
    cands = tuning.candidate_configs(spec, jnp.float32, shape)
    assert cands[0] == tuning.default_config(spec, jnp.float32, shape)
    assert len(cands) == len(set(cands))      # deduplicated
    assert all(c.valid_for(shape) for c in cands)
    assert any(c.fuse_ring for c in cands)    # the epilogue axis is swept


def test_synthetic_exchange_layout():
    """Interior == v bitwise; only split-axis halos carry values (the
    invariant the fused-vs-split identity rests on)."""
    spec = stencil.STAR7
    v = jax.random.normal(jax.random.PRNGKey(0), (6, 8, 8), jnp.float32)
    ex = tuning.synthetic_exchange(v, spec, FabricAxes(nx=2, ny=2))
    r = spec.radius
    inner = tuple(slice(r, -r) for _ in range(3))
    np.testing.assert_array_equal(np.asarray(ex.padded[inner]),
                                  np.asarray(v))
    assert np.any(np.asarray(ex.padded[:r, r:-r, r:-r]))    # x halo filled
    # the unsplit z axis: its halo (away from x/y slab corners) stays zero
    assert not np.any(np.asarray(ex.padded[r:-r, r:-r, :r]))
