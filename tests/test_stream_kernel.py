"""The plane-streaming SpMV kernel (kernels/stencil_nd/stream.py) and the
spmd backend's choice of it.

The kernel must equal ``core.halo.interior_apply`` with f32 compute, bit
for bit.  Operands hold bf16 values (also when stored as f32), so every
product is exact in f32 and the comparison does not depend on whether a
compiler fuses a multiply and an add; the sums still run in the order each
side chose.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import halo, operator, precision, stencil
from repro.core.halo import FabricAxes
from repro.kernels.stencil_nd.stream import spmv_stream, stream_interior_apply
from repro.obs import metrics

# ragged blocks: rows and lanes not multiples of the (16, 128) bf16 tile;
# the combs put a unit point on every face
SHAPES = {"star7": (10, 20, 36), "star25": (12, 24, 40), "box27": (9, 20, 36)}


def _bf16_values(key, shape, dtype, scale=1.0):
    x = scale * jax.random.normal(key, shape, jnp.float32)
    return x.astype(jnp.bfloat16).astype(dtype)


def _comb(shape, spacing, high):
    """``bench.reference.comb``'s probe: unit points ``spacing`` apart, on
    the low faces (shift 0) or on the high faces."""
    shift = [(n - 1) % spacing if high else 0 for n in shape]
    hit = np.ones(shape, bool)
    for axis, n in enumerate(shape):
        i = np.arange(n).reshape([-1 if a == axis else 1
                                  for a in range(len(shape))])
        hit = hit & ((i - shift[axis]) % spacing == 0)
    return hit


def _operands(spec, shape, dtype, probe):
    keys = jax.random.split(jax.random.PRNGKey(7), spec.n_offsets + 2)
    cf = {n: _bf16_values(k, shape, dtype, 0.2)
          for n, k in zip(spec.names, keys)}
    diag = (_bf16_values(keys[-2], shape, dtype) if probe == "random_diag"
            else None)
    if probe.startswith("comb"):
        v = jnp.asarray(_comb(shape, 2 * spec.radius + 1,
                              probe == "comb_high"), dtype)
    else:
        v = _bf16_values(keys[-1], shape, dtype)
    return stencil.StencilCoeffs(cf, diag=diag), v


@pytest.mark.parametrize("probe", ["random", "random_diag", "comb_low",
                                   "comb_high"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("specname", ["star7", "star25", "box27"])
def test_stream_equals_interior_apply(specname, dtype, probe):
    spec = stencil.get_spec(specname)
    shape = SHAPES[specname]
    coeffs, v = _operands(spec, shape, dtype, probe)
    f32_compute = precision.Policy("f32_compute", jnp.dtype(dtype),
                                   jnp.dtype(jnp.float32),
                                   jnp.dtype(jnp.float32))
    want = halo.interior_apply(coeffs, v, policy=f32_compute).astype(dtype)
    got = stream_interior_apply(coeffs, v, policy=f32_compute)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert metrics.snapshot()["counters"][
        "kernels.stencil_stream.traced_calls"] == 1


def test_stream_refuses_offsets_off_the_axes():
    """Off the axes it takes the radius-1 box's corners only."""
    v = jnp.zeros((4, 8, 8), jnp.float32)
    with pytest.raises(ValueError, match="box offsets of radius 1"):
        spmv_stream(v, [v], ((2, 1, 0),), interpret=True)


def _kernel_jaxpr(specname, shape, dtype) -> str:
    """The text of the jaxpr inside ``spmv_stream``'s pallas_call: what
    Mosaic lowers."""
    spec = stencil.get_spec(specname)
    v = jax.ShapeDtypeStruct(shape, dtype)
    closed = jax.make_jaxpr(lambda v, *c: spmv_stream(v, list(c), spec.offsets))(
        v, *[v] * spec.n_offsets)

    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn
            for p in eqn.params.values():
                sub = getattr(p, "jaxpr", None)
                if sub is not None:
                    found = find(getattr(sub, "jaxpr", sub))
                    if found is not None:
                        return found
        return None

    return str(find(closed.jaxpr).params["jaxpr"])


# sha256 of _kernel_jaxpr before the stream kernel took box specs
STAR_KERNELS = {
    ("star7", (608, 608, 608), "bfloat16"):
        "c815401089e2d19e263489e00fe9c39173ea1a391c064441b445de7b591f5e7e",
    ("star7", (608, 608, 608), "float32"):
        "c3f24b7314cce10ee7dc57bbd1613edbab321da4427154e9d1f84e93b2f0bb8e",
    ("star25", (504, 504, 352), "bfloat16"):
        "50238eb1db622da7716145a30947cb1f76253db6a0aa18c949a8f64d646e158e",
    ("star25", (504, 504, 352), "float32"):
        "f0bcf9d2775d6a972cced7f3bb6f5d3fb3b7818f3d750a57c9d08aaf571f1f97",
}


@pytest.mark.parametrize("specname,shape,dtype", sorted(STAR_KERNELS))
def test_star_kernels_lower_as_before(specname, shape, dtype):
    """The box's corner terms leave the star kernels' text as it was."""
    import hashlib

    text = _kernel_jaxpr(specname, shape, jnp.dtype(dtype))
    assert hashlib.sha256(text.encode()).hexdigest() == \
        STAR_KERNELS[(specname, shape, dtype)]


@pytest.mark.parametrize("specname,operand_ndim,dtype,platform,takes", [
    ("star7", 3, jnp.bfloat16, "tpu", True),
    ("star25", 3, jnp.bfloat16, "tpu", True),
    ("star13", 3, jnp.float32, "tpu", True),
    ("star7", 3, jnp.bfloat16, "cpu", False),
    ("box27", 3, jnp.bfloat16, "tpu", True),
    ("box27", 4, jnp.float32, "tpu", False),      # leading batch axis
    ("star7", 4, jnp.bfloat16, "tpu", False),     # leading batch axis
    ("star25", 4, jnp.float32, "tpu", False),
    ("star7", 3, jnp.float64, "tpu", False),
])
def test_stream_selection(specname, operand_ndim, dtype, platform, takes):
    spec = stencil.get_spec(specname)
    assert operator.stream_applies(spec, operand_ndim, dtype,
                                   platform) is takes


def _spmd_apply(spec, shape, batch=()):
    coeffs, v = _operands(spec, shape, jnp.float32, "random")
    v = jnp.broadcast_to(v, batch + shape)
    op = operator.spmd_operator(coeffs, FabricAxes(), policy=precision.F32,
                                schedule="overlap")
    return op.apply(v), coeffs, v


def test_spmd_operator_keeps_jnp_on_cpu():
    _spmd_apply(stencil.STAR7, SHAPES["star7"])
    counters = metrics.snapshot()["counters"]
    assert counters["operator.spmv_interior.xla"] == 1
    assert "operator.spmv_interior.stream" not in counters
    assert "kernels.stencil_stream.traced_calls" not in counters


@pytest.mark.parametrize("specname,batch,path", [
    ("star7", (), "stream"),
    ("star25", (), "stream"),
    ("box27", (), "stream"),
    ("box27", (2,), "xla"),
    ("star7", (2,), "xla"),
])
def test_spmd_operator_selects_on_tpu(monkeypatch, specname, batch, path):
    """With the platform read as a TPU (and kernels interpreted, as there is
    no chip here), star and radius-1 box specs without a batch axis take
    the stream kernel and the rest keep the jnp apply; both answer as the
    reference."""
    from repro.kernels.stencil_nd import stream

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(stream, "resolve_interpret", lambda i=None: True)
    spec = stencil.get_spec(specname)
    u, coeffs, v = _spmd_apply(spec, (6, 8, 12), batch)
    counters = metrics.snapshot()["counters"]
    assert counters[f"operator.spmv_interior.{path}"] == 1
    assert len([k for k in counters
                if k.startswith("operator.spmv_interior.")]) == 1
    np.testing.assert_allclose(np.asarray(u),
                               np.asarray(stencil.apply_ref(coeffs, v)),
                               rtol=1e-6, atol=1e-6)


def _reassociation_tol(x, u, n):
    """How far two f32 sums of the ``n`` products ``x u`` may lie apart in
    their order alone: a few ``sqrt(n)`` f32 epsilons of ``sum |x u|``
    (``n`` epsilons at worst)."""
    scale = float(jnp.sum(jnp.abs(x.astype(jnp.float32) * u.astype(jnp.float32))))
    return 4 * math.sqrt(n) * float(jnp.finfo(jnp.float32).eps) * scale


@pytest.mark.parametrize("ring", ["whole", "ring_left_out"])
@pytest.mark.parametrize("policy", [precision.MIXED, precision.F32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("specname", ["star7", "star25", "box27"])
def test_stream_dots_equal_policy_dot(specname, policy, ring):
    """The dot epilogue's partials are ``Policy.dot`` of the stored output
    with itself, with the input and with a third vector, over the points
    it is asked for; its ``u`` is the plain kernel's, bit for bit."""
    from repro.kernels.stencil_nd.stream import stream_interior_partials

    spec = stencil.get_spec(specname)
    shape = SHAPES[specname]
    dtype = policy.storage
    coeffs, v = _operands(spec, shape, dtype, "random_diag")
    w = _bf16_values(jax.random.PRNGKey(11), shape, dtype)
    depth = (spec.radius,) * 3 if ring == "ring_left_out" else (0, 0, 0)
    u, partials = stream_interior_partials(coeffs, v, w, ("u", "v", "w"),
                                           policy=policy, ring=depth)
    np.testing.assert_array_equal(
        np.asarray(u), np.asarray(stream_interior_apply(coeffs, v, policy=policy)))
    inner = tuple(slice(d, n - d) for d, n in zip(depth, shape))
    n = math.prod(s.stop - s.start for s in inner)
    for x, got in zip((u, v, w), partials):
        want = policy.dot(x[inner], u[inner])
        assert abs(float(got) - float(want)) <= _reassociation_tol(
            x[inner], u[inner], n), (float(got), float(want))


def test_stream_dots_take_w_exactly_when_named():
    v = jnp.zeros((4, 8, 8), jnp.float32)
    with pytest.raises(ValueError, match="w exactly"):
        spmv_stream(v, [v], ((1, 0, 0),), dots=("v",), w=v, interpret=True)
    with pytest.raises(ValueError, match="w exactly"):
        spmv_stream(v, [v], ((1, 0, 0),), dots=("w",), interpret=True)


def _as_tpu(monkeypatch):
    from repro.kernels.stencil_nd import stream

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(stream, "resolve_interpret", lambda i=None: True)


@pytest.mark.parametrize("policy,path", [
    (precision.MIXED, "stream"),
    (precision.F32, "stream"),
    (precision.BF16_PURE, "xla"),      # bf16 sums: not the kernel's f32 ones
])
def test_spmd_apply_dots_on_tpu(monkeypatch, policy, path):
    """On a 1x1 fabric with the platform read as a TPU, the spmd
    SpMV-with-dots takes the epilogue where its products are the policy's,
    and gives ``apply`` and the separate dots either way."""
    _as_tpu(monkeypatch)
    spec = stencil.STAR7
    shape = (6, 8, 12)
    coeffs, v = _operands(spec, shape, policy.storage, "random")
    w = _bf16_values(jax.random.PRNGKey(3), shape, policy.storage)
    op = operator.make_operator("spmd", coeffs, FabricAxes(), policy=policy,
                                schedule="overlap")
    u, dots = op.apply_dots(v, w, ("w", "v", "u"))
    counters = metrics.snapshot()["counters"]
    assert counters[f"operator.spmv_dots.{path}"] == 1
    assert len([k for k in counters if k.startswith("operator.spmv_dots.")]) == 1
    want_u = op.apply(v)
    np.testing.assert_array_equal(np.asarray(u), np.asarray(want_u))
    want = op.dots([(w, want_u), (v, want_u), (want_u, want_u)], policy)
    np.testing.assert_allclose(np.asarray(dots), np.asarray(want), rtol=2e-5)


@pytest.mark.parametrize("backend", ["spmd", "reference"])
def test_wrapped_operator_dots_fall_back(monkeypatch, backend):
    """An operator whose SpMV was swapped (a right preconditioner's
    ``A M^-1``) has no SpMV-with-dots of its own: ``apply_dots`` is its
    ``apply`` and then the dots, counted under no stream path."""
    _as_tpu(monkeypatch)
    shape = (6, 8, 12)
    coeffs, v = _operands(stencil.STAR7, shape, jnp.float32, "random")
    op = operator.make_operator(backend, coeffs, FabricAxes(),
                                policy=precision.F32, schedule="overlap")
    wrapped = op.with_apply(lambda x: op.apply(2 * x))
    assert wrapped.apply_partials is None
    u, (vu, uu) = wrapped.apply_dots(v, None, ("v", "u"))
    assert "operator.spmv_dots.stream" not in metrics.snapshot()["counters"]
    np.testing.assert_array_equal(np.asarray(u), np.asarray(op.apply(2 * v)))
    np.testing.assert_array_equal(
        np.asarray([vu, uu]),
        np.asarray(op.dots([(v, u), (u, u)], precision.F32)))


def test_spmd_apply_dots_on_split_fabric(subproc):
    """On a 2x2 fabric under the overlap schedule the kernel leaves the
    boundary ring out and the operator adds it from the patched output:
    the reduced dots are ``apply`` and the separate dots', for the star
    radii 1 and 4 (a ring as deep as half the block) and the box's
    corners, in bf16 and f32."""
    subproc(f"""
        import math
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import operator, precision, stencil
        from repro.core.halo import FabricAxes
        from repro.kernels.stencil_nd import stream
        from repro.launch.mesh import make_mesh_for_devices
        from repro.obs import metrics

        jax.default_backend = lambda: "tpu"
        stream.resolve_interpret = lambda i=None: True
        mesh = make_mesh_for_devices(4)
        fabric = FabricAxes.from_mesh(mesh)
        assert (fabric.nx, fabric.ny) == (2, 2)
        spec_v = fabric.spec(3)
        for specname, shape in (("star7", (12, 16, 36)), ("star25", (16, 16, 40)),
                                ("box27", (12, 16, 36))):
            spec = stencil.get_spec(specname)
            for pol in (precision.MIXED, precision.F32):
                keys = jax.random.split(jax.random.PRNGKey(5), 2)
                cf = stencil.random_nonsymmetric(keys[0], shape, pol.storage,
                                                 spec=spec)
                v, w = (jax.random.normal(k, shape).astype(pol.storage)
                        for k in jax.random.split(keys[1]))

                def both(c, v, w):
                    op = operator.make_operator("spmd", c, fabric, policy=pol,
                                                schedule="overlap")
                    u, got = op.apply_dots(v, w, ("w", "v", "u"))
                    want_u = op.apply(v)
                    want = op.dots([(w, want_u), (v, want_u),
                                    (want_u, want_u)], pol)
                    return u, want_u, got, want

                u, want_u, got, want = jax.jit(jax.shard_map(
                    both, mesh=mesh, in_specs=(spec_v, spec_v, spec_v),
                    out_specs=(spec_v, spec_v, jax.sharding.PartitionSpec(),
                               jax.sharding.PartitionSpec()),
                    check_vma=False))(cf, v, w)
                np.testing.assert_array_equal(np.asarray(u), np.asarray(want_u))
                uf = np.asarray(u, np.float32)
                for x, g, h in zip((w, v, u), np.asarray(got), np.asarray(want)):
                    scale = np.abs(np.asarray(x, np.float32) * uf).sum()
                    tol = 4 * math.sqrt(uf.size) * np.finfo(np.float32).eps * scale
                    assert abs(g - h) <= tol, (specname, pol.name, g, h, tol)
        assert metrics.snapshot()["counters"]["operator.spmv_dots.stream"] == 6
        print("OK")
    """, n_devices=4)


def test_stream_dots_solve_matches_xla_dots(subproc):
    """BiCGStab and CG on a 2x2 fabric, the platform read as a TPU: with the
    sync points' dots in the stream kernel the solve follows the trajectory
    of the same solve with the dots in passes of their own (the epilogue
    refused), in iterations and final residual, and keeps 3 (BiCGStab) and
    2 (CG) AllReduces an iteration."""
    subproc(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import bicgstab, operator, precision, stencil
        from repro.kernels.stencil_nd import stream
        from repro.launch.mesh import make_mesh_for_devices
        from repro.obs import metrics

        jax.default_backend = lambda: "tpu"
        stream.resolve_interpret = lambda i=None: True
        mesh = make_mesh_for_devices(4)
        shape = (12, 16, 36)
        x_true = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
        systems = {{
            "bicgstab": stencil.random_nonsymmetric(jax.random.PRNGKey(0), shape),
            "cg": stencil.poisson(shape),
        }}
        exact = operator.stream_dots_apply
        for solver, cf in systems.items():
            b = stencil.rhs_for_solution(cf, x_true)
            runs = {{}}
            for path in ("stream", "xla"):
                operator.stream_dots_apply = (exact if path == "stream"
                                              else lambda *a: False)
                metrics.reset()
                f = jax.jit(lambda c, bb: bicgstab.solve_distributed(
                    mesh, c, bb, tol=1e-5, maxiter=200, policy=precision.F32,
                    solver=solver, schedule="overlap"))
                text = f.lower(cf, b).as_text()
                n_ar = text.count("all_reduce") + text.count("all-reduce")
                assert n_ar == 1 + {{"bicgstab": 3, "cg": 2}}[solver], (solver, n_ar)
                assert metrics.snapshot()["counters"][
                    f"operator.spmv_dots.{{path}}"] >= 1, (solver, path)
                runs[path] = f(cf, b)
            s, x = runs["stream"], runs["xla"]
            assert bool(s.converged) and bool(x.converged), solver
            assert int(s.iterations) == int(x.iterations), (
                solver, int(s.iterations), int(x.iterations))
            np.testing.assert_allclose(float(s.rel_residual),
                                       float(x.rel_residual), rtol=1e-2)
            np.testing.assert_allclose(np.asarray(s.x), np.asarray(x.x),
                                       rtol=1e-4, atol=1e-4)
        print("OK")
    """, n_devices=4)
