"""Unit tests for the dry-run/roofline machinery (parsers, extrapolation,
probe configs, analytic memory model).  The launcher itself needs 512 fake
devices and is exercised by the sweep (results/dryrun) + a subprocess test."""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_dryrun_module():
    """Import dryrun WITHOUT triggering its XLA_FLAGS (already-initialized
    jax in this process ignores the env var, so importing is safe)."""
    spec = importlib.util.spec_from_file_location(
        "dryrun_under_test", os.path.join(REPO, "src/repro/launch/dryrun.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def dr():
    return _load_dryrun_module()


HLO_SAMPLE = """
  %ar0 = f32[128]{0} all-reduce(f32[128]{0} %x), channel_id=1, replica_groups=[16,16]<=[256], to_apply=%add
  %ag = bf16[4,256]{1,0} all-gather(bf16[4,16]{1,0} %y), channel_id=2, replica_groups=[16,16]<=[256], dimensions={1}
  %cp = bf16[1,38,1536]{2,1,0} collective-permute(%z), channel_id=3, source_target_pairs={{0,16},{1,17}}
  %ars = (f32[2]{0}, f32[4]{0}) all-reduce(%a, %b), channel_id=4, replica_groups={{0,1,2,3}}, to_apply=%add
  %start = f32[64]{0} all-reduce-start(f32[64]{0} %w), channel_id=5, replica_groups=[2,128]<=[256]
  %done = f32[64]{0} all-reduce-done(f32[64]{0} %start)
"""


def test_parse_collectives_ops_and_bytes(dr):
    out = dr.parse_collectives(HLO_SAMPLE, 256)
    by = out["by_op"]
    assert by["all-reduce"]["count"] == 3          # ar0, tuple ars, start (not done)
    assert by["all-gather"]["count"] == 1
    assert by["collective-permute"]["count"] == 1
    # tuple all-reduce bytes = 2*4 + 4*4
    assert by["all-reduce"]["bytes"] == 128 * 4 + (2 * 4 + 4 * 4) + 64 * 4
    assert by["all-gather"]["bytes"] == 4 * 256 * 2
    assert by["collective-permute"]["bytes"] == 38 * 1536 * 2


def test_parse_collectives_ring_factors(dr):
    out = dr.parse_collectives(
        "%ar = f32[100]{0} all-reduce(%x), replica_groups=[1,4]<=[4], to_apply=%a\n", 4)
    # group=4 => 2*(4-1)/4 = 1.5x
    assert abs(out["total_link_bytes"] - 400 * 1.5) < 1e-6
    out = dr.parse_collectives(
        "%cp = f32[100]{0} collective-permute(%x), source_target_pairs={{0,1}}\n", 4)
    assert out["total_link_bytes"] == 400.0        # permute: 1x


def test_group_size_formats(dr):
    assert dr._group_size("replica_groups=[16,16]<=[256]", 256) == 16
    assert dr._group_size("replica_groups={{0,1,2,3,4,5,6,7}}", 256) == 8
    assert dr._group_size("no groups here", 256) == 256


def test_extrapolation_is_exact_for_affine(dr):
    c1 = {"flops": 10.0, "bytes": 7.0}
    c2 = {"flops": 14.0, "bytes": 9.0}
    out = dr._extrapolate(c1, c2, 10)
    assert out["flops"] == 10 + 9 * 4 and out["bytes"] == 7 + 9 * 2


def test_probe_config_shapes():
    from repro.configs import get_config
    from repro.models.model import probe_config
    cfg = get_config("gemma3_12b")
    p1 = probe_config(cfg, 1, 32768)
    assert p1.n_layers == len(cfg.period) == 6
    assert p1.unroll and p1.inner_unroll and not p1.remat
    assert p1.attn_block == 8192
    p2 = probe_config(cfg, 2, 4096)
    assert p2.n_layers == 12


def test_lm_memory_estimate_orders_of_magnitude():
    from repro.configs import get_config
    from repro.launch.roofline_model import lm_cell_memory_estimate
    from repro.models.model import SHAPES

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = get_config("qwen2_1_5b")
    est = lm_cell_memory_estimate(cfg, SHAPES["smoke_decode"], mesh)
    # single fake device, smoke decode: params dominate; 1.5B * 2B ~ 3.1GB
    assert 2.5e9 < est["est_params_bytes"] < 4.5e9
    assert est["est_hbm_traffic_bytes"] >= est["est_params_bytes"]


def test_sweep_artifacts_complete_and_clean():
    """The committed dry-run sweep must cover all 86 cells with 0 errors:
    40 LM cells x 2 meshes + 3 stencil cells x 2 meshes."""
    d = os.path.join(REPO, "results/dryrun")
    if not os.path.isdir(d):
        pytest.skip("dry-run sweep not present")
    cells = [json.load(open(os.path.join(d, f)))
             for f in os.listdir(d) if f.endswith(".json")]
    assert len(cells) >= 86
    assert sum(c.get("status") == "error" for c in cells) == 0
    ok = [c for c in cells if c.get("status") == "ok"]
    skipped = [c for c in cells if c.get("status") == "skipped"]
    assert len(skipped) == 16      # 8 full-attention archs x long_500k x 2 meshes
    for c in ok:
        assert c["t_bound_s"] > 0
        assert c["dominant"] in ("compute", "memory", "collective")
        # multi-pod proof: every ok cell exists in both mesh variants unless skipped
    meshes = {(c["arch"], c["shape"]): set() for c in ok}
    for c in ok:
        meshes[(c["arch"], c["shape"])].add(c["mesh"])
    for key, ms in meshes.items():
        assert ms == {"16x16", "2x16x16"}, (key, ms)


def test_production_mesh_shapes(subproc):
    subproc("""
        from repro.launch.mesh import make_production_mesh, fabric_shape
        m1 = make_production_mesh()
        assert dict(m1.shape) == {"data": 16, "model": 16}
        assert fabric_shape(m1) == (1, 16, 16)
        m2 = make_production_mesh(multi_pod=True)
        assert dict(m2.shape) == {"pod": 2, "data": 16, "model": 16}
        assert fabric_shape(m2) == (2, 16, 16)
        print("OK")
    """, n_devices=512)


def test_mesh_helpers_single_device():
    from repro.launch.mesh import make_mesh_for_devices, fabric_shape
    m = make_mesh_for_devices(1)
    assert dict(m.shape) == {"data": 1, "model": 1}
    assert fabric_shape(m) == (1, 1, 1)
