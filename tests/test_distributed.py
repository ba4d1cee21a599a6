"""Distributed (shard_map) solver tests — run in subprocesses with 8 fake
devices so the main pytest process keeps a single CpuDevice."""

import pytest

pytestmark = pytest.mark.slow   # every test here spawns 8-device subprocesses


def test_distributed_apply_matches_ref(subproc):
    subproc("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import stencil
        from repro.core.halo import global_apply
        from repro.launch.mesh import make_mesh_for_devices
        mesh = make_mesh_for_devices(8)
        shape = (8, 8, 6)
        cf = stencil.random_nonsymmetric(jax.random.PRNGKey(0), shape)
        v = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
        u_ref = stencil.apply_ref(cf, v)
        for schedule in ("overlap", "blocking"):
            u = global_apply(mesh, cf, v, schedule=schedule)
            np.testing.assert_allclose(np.asarray(u), np.asarray(u_ref), rtol=1e-5, atol=1e-5)
        print('OK')
    """)


def test_distributed_solve_matches_ref(subproc):
    subproc("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import stencil, bicgstab, precision
        from repro.launch.mesh import make_mesh_for_devices
        mesh = make_mesh_for_devices(8)
        shape = (8, 8, 6)
        cf = stencil.random_nonsymmetric(jax.random.PRNGKey(0), shape)
        x_true = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
        b = stencil.rhs_for_solution(cf, x_true)
        for fused in (True, False):
            res = bicgstab.solve_distributed(mesh, cf, b, tol=1e-8, maxiter=300,
                                             policy=precision.F32, fused_reductions=fused)
            assert bool(res.converged) and not bool(res.breakdown)
            np.testing.assert_allclose(np.asarray(res.x), np.asarray(x_true),
                                       rtol=2e-4, atol=2e-4)
        print('OK')
    """)


def test_multipod_z_split_solve(subproc):
    """3-axis mesh: pod axis slabs Z with its own halo exchange."""
    subproc("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import stencil, bicgstab, precision
        from repro.launch.mesh import make_mesh_for_devices
        mesh = make_mesh_for_devices(8, pods=2)   # (pod=2, data=2, model=2)
        shape = (4, 4, 8)
        cf = stencil.random_nonsymmetric(jax.random.PRNGKey(0), shape)
        x_true = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
        b = stencil.rhs_for_solution(cf, x_true)
        res = bicgstab.solve_distributed(mesh, cf, b, tol=1e-8, maxiter=300,
                                         policy=precision.F32)
        assert bool(res.converged)
        np.testing.assert_allclose(np.asarray(res.x), np.asarray(x_true),
                                   rtol=2e-4, atol=2e-4)
        print('OK')
    """)


def test_depth_r_halo_apply_matches_ref(subproc):
    """Acceptance: the SPMD depth-r halo path (star13 r=2, star25 r=4, box27
    corners) agrees with the single-device reference on an 8-device mesh."""
    subproc("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import stencil
        from repro.core.halo import global_apply
        from repro.launch.mesh import make_mesh_for_devices
        mesh = make_mesh_for_devices(8)    # 2 x 4 fabric
        shape = (8, 16, 6)                 # local blocks (4, 4, 6) >= radius 4
        for name in ("star13", "star25", "box27"):
            spec = stencil.get_spec(name)
            cf = stencil.random_nonsymmetric(jax.random.PRNGKey(0), shape, spec=spec)
            v = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
            u_ref = stencil.apply_ref(cf, v)
            for schedule in ("overlap", "blocking"):
                u = global_apply(mesh, cf, v, schedule=schedule)
                np.testing.assert_allclose(np.asarray(u), np.asarray(u_ref),
                                           rtol=1e-5, atol=1e-5, err_msg=name)
        print('OK')
    """)


def test_depth_r_halo_multipod_z_split(subproc):
    """Depth-2 and corner halos across a 3-axis mesh (pod axis slabs Z)."""
    subproc("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import stencil
        from repro.core.halo import global_apply
        from repro.launch.mesh import make_mesh_for_devices
        mesh = make_mesh_for_devices(8, pods=2)
        shape = (4, 4, 8)
        for name in ("star13", "box27"):
            spec = stencil.get_spec(name)
            cf = stencil.random_nonsymmetric(jax.random.PRNGKey(0), shape, spec=spec)
            v = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
            u_ref = stencil.apply_ref(cf, v)
            u = global_apply(mesh, cf, v)
            np.testing.assert_allclose(np.asarray(u), np.asarray(u_ref),
                                       rtol=1e-5, atol=1e-5, err_msg=name)
        print('OK')
    """)


def test_distributed_solve_star25_and_box27(subproc):
    subproc("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import stencil, bicgstab, precision
        from repro.launch.mesh import make_mesh_for_devices
        mesh = make_mesh_for_devices(4)    # 2 x 2 fabric
        shape = (8, 8, 6)
        for spec, gen in ((stencil.STAR25, lambda: stencil.high_order_star(shape, 4)),
                          (stencil.BOX27, lambda: stencil.random_nonsymmetric(
                               jax.random.PRNGKey(0), shape, spec=stencil.BOX27))):
            cf = gen()
            x_true = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
            b = stencil.rhs_for_solution(cf, x_true)
            res = bicgstab.solve_distributed(mesh, cf, b, tol=1e-8, maxiter=300,
                                             policy=precision.F32)
            assert bool(res.converged), spec.name
            np.testing.assert_allclose(np.asarray(res.x), np.asarray(x_true),
                                       rtol=2e-4, atol=2e-4, err_msg=spec.name)
        print('OK')
    """, n_devices=4)


def test_halo_depth_exceeding_block_raises(subproc):
    """radius > local block extent must fail loudly, not corrupt."""
    subproc("""
        import jax, jax.numpy as jnp
        from repro.core import stencil
        from repro.core.halo import global_apply
        from repro.launch.mesh import make_mesh_for_devices
        mesh = make_mesh_for_devices(8)    # 2 x 4: y blocks of 2 < radius 4
        cf = stencil.random_nonsymmetric(jax.random.PRNGKey(0), (8, 8, 6),
                                         spec=stencil.STAR25)
        v = jnp.ones((8, 8, 6), jnp.float32)
        try:
            global_apply(mesh, cf, v)
        except ValueError as e:
            assert 'halo depth' in str(e), e
            print('OK')
        else:
            raise SystemExit('expected ValueError')
    """)


def test_fused_reductions_reduce_allreduce_count(subproc):
    """Beyond-paper claim: 3 fused vs 5 separate AllReduces per iteration."""
    subproc("""
        import jax, jax.numpy as jnp
        from repro.core import stencil, bicgstab, precision
        from repro.launch.mesh import make_mesh_for_devices
        mesh = make_mesh_for_devices(8)
        cf = stencil.random_nonsymmetric(jax.random.PRNGKey(0), (8, 8, 4))
        b = jnp.ones((8, 8, 4), jnp.float32)
        def n_allreduce(fused):
            f = lambda c, bb: bicgstab.solve_distributed(
                mesh, c, bb, maxiter=10, policy=precision.F32, fused_reductions=fused)
            return jax.jit(f).lower(cf, b).as_text().count('all_reduce')
        nf, ns = n_allreduce(True), n_allreduce(False)
        assert nf < ns, (nf, ns)
        print('OK', nf, ns)
    """)


def test_distributed_mixed_precision(subproc):
    subproc("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import stencil, bicgstab, precision
        from repro.launch.mesh import make_mesh_for_devices
        mesh = make_mesh_for_devices(8)
        shape = (8, 8, 6)
        cf = stencil.random_nonsymmetric(jax.random.PRNGKey(0), shape)
        x_true = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
        b = stencil.rhs_for_solution(cf, x_true)
        res = bicgstab.solve_distributed(mesh, cf, b.astype(jnp.bfloat16),
                                         tol=1e-8, maxiter=300, policy=precision.MIXED)
        err = np.abs(np.asarray(res.x, np.float32) - np.asarray(x_true)).max()
        assert err < 0.1, err   # bf16 plateau accuracy
        print('OK')
    """)
