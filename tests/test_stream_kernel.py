"""The plane-streaming SpMV kernel (kernels/stencil_nd/stream.py) and the
spmd backend's choice of it.

The kernel must equal ``core.halo.interior_apply`` with f32 compute, bit
for bit.  Operands hold bf16 values (also when stored as f32), so every
product is exact in f32 and the comparison does not depend on whether a
compiler fuses a multiply and an add; the sums still run in the order each
side chose.
"""

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
