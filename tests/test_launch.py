"""The solve entry point as a library call, its problem built in the solve's
sharding, and the chip smoke script's refusal to run off the chip."""

import os
import subprocess
import sys

import jax
import pytest

from repro.launch import solve as launch_solve

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_compile_cache():
    """``main`` turns the persistent compile cache on for its process;
    give the test worker its own setting back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


def test_main_returns_summary_in_process(restore_compile_cache):
    s = launch_solve.main(["--mesh", "8", "8", "16", "--policy", "f32",
                           "--devices", "1", "--maxiter", "100"])
    assert s["platform"] == jax.devices()[0].platform
    assert s["device_count"] == 1 and s["shape"] == [8, 8, 16]
    assert s["converged"] and 0 < s["iterations"] < 100
    # the on-device true residual agrees with the recurrence at f32
    assert s["true_rel_residual"] < 1e-5
    assert abs(s["true_rel_residual"] - s["recurrence_rel_residual"]) < 1e-5
    assert s["compile_s"] > 0 and s["warm_s"] > 0
    assert s["x"].shape == (8, 8, 16)
    # no rate from the host's clock against a peak: the benchmark reads the trace
    assert "roofline_fraction" not in s and "model_gb_per_s" not in s


def test_cell_sets_mesh_and_policy():
    """chip_smoke.py takes each paper cell's mesh and policy from
    configs/stencil_cs1.py and passes them through the launcher's flags."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    argv = chip_smoke.cell_args("joule_600")
    assert argv == ("--mesh", "608", "608", "608", "--policy", "bf16_mixed")
    args = launch_solve.parse_args(list(argv))
    assert args.mesh == [608, 608, 608] and args.policy == "bf16_mixed"


def test_build_problem_sharded_matches_global(subproc):
    """Coefficients, x_true and b built in a 2x2 mesh's NamedSharding hold
    the same values as the unsharded build, and live on all four devices."""
    subproc("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import stencil
        from repro.launch.mesh import make_mesh_for_devices
        from repro.launch.solve import build_problem
        mesh = make_mesh_for_devices(4)
        for problem, spec in (("random", stencil.STAR7),
                              ("heterogeneous", stencil.BOX27)):
            for nrhs in (1, 3):
                glob = build_problem(problem, spec, (8, 12, 16),
                                     dtype=jnp.bfloat16, nrhs=nrhs)
                shard = build_problem(problem, spec, (8, 12, 16),
                                      dtype=jnp.bfloat16, nrhs=nrhs, mesh=mesh)
                for g, s in zip(jax.tree.leaves(glob), jax.tree.leaves(shard)):
                    assert s.dtype == jnp.bfloat16
                    assert len(s.sharding.device_set) == 4, s.sharding
                    assert s.addressable_shards[0].data.shape[-3:] == (4, 6, 16)
                    np.testing.assert_array_equal(np.asarray(g, np.float32),
                                                  np.asarray(s, np.float32))
        print("OK")
    """, n_devices=4)


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr


@pytest.mark.parametrize("profile_fails", ["start", "stop"])
def test_profiler_failure_raises(tmp_path, monkeypatch, profile_fails):
    """--profile must never exit 0 without a trace."""
    from repro.obs import manifest

    class Broken:
        def __enter__(self):
            if profile_fails == "start":
                raise RuntimeError("profiler would not start")

        def __exit__(self, *exc):
            raise RuntimeError("profiler would not stop")

    monkeypatch.setattr(jax.profiler, "trace", lambda *a, **k: Broken())
    with pytest.raises(RuntimeError, match="profiler"):
        ctx = manifest.start_run("t", run_dir=str(tmp_path / "run"),
                                 profile=True)
        manifest.finish_run(ctx)
