"""Ahead-of-time compiles of the main path's kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: a described
``v5e:2x2`` topology lets Mosaic accept or refuse each kernel at the local
shapes of the paper's cells — what interpret mode cannot show (tile
alignment, VMEM limits, unsupported lowerings).  Nothing runs; a compile
that passes is not a chip run.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file.  Keep every such compile in this one file.
"""

import math
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.core import bicgstab, precision, stencil, tuning
from repro.kernels import fused_iter
from repro.kernels.stencil_nd import tile_apply
from repro.kernels.stencil_nd.stream import spmv_stream

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import scopes  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A described-device compile cannot be read back without the chip, so
    keep these compiles out of any persistent compilation cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the kernel is there
    return compiled


# local blocks: joule_600 on one chip, cs1_paper (608x608x1536) per chip of
# a 2x2 mesh, and a lane-aligned star25 block
STENCIL_CELLS = [
    ("star7", (608, 608, 608), jnp.bfloat16),
    ("star7", (608, 608, 608), jnp.float32),
    ("star7", (304, 304, 1536), jnp.bfloat16),
    ("star7", (304, 304, 1536), jnp.float32),
    ("star25", (256, 256, 512), jnp.bfloat16),
]


@pytest.mark.parametrize("specname,shape,dtype", STENCIL_CELLS)
def test_stencil_kernel_compiles_for_v5e(one_chip, specname, shape, dtype):
    spec = stencil.get_spec(specname)
    r = spec.radius
    config = tuning.default_config(spec, dtype, shape)
    assert config.valid_for(shape)
    vp = jax.ShapeDtypeStruct(tuple(s + 2 * r for s in shape), dtype,
                              sharding=one_chip)
    cfs = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)] * spec.n_offsets
    compiled = _compile(
        lambda v, *c: tile_apply(v, list(c), spec, config, interpret=False),
        vp, *cfs)
    # the arguments (window + diagonals) are the kernel's whole HBM footprint
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


# the benchmark cells' local blocks: star7 608^3 on one chip, star25
# 504x504x352 on one chip, star7 304x304x1536 per chip of the 2x2 mesh
STREAM_CELLS = [
    ("star7", (608, 608, 608)),
    ("star25", (504, 504, 352)),
    ("star7", (304, 304, 1536)),
]


@pytest.mark.parametrize("specname,shape", STREAM_CELLS)
def test_spmv_stream_compiles_for_v5e(one_chip, specname, shape):
    spec = stencil.get_spec(specname)
    arr = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    compiled = _compile(
        lambda v, *c: spmv_stream(v, list(c), spec.offsets),
        arr, *[arr] * spec.n_offsets)
    assert scopes.pallas_kernels(compiled.as_text()) == ["spmv_stream"]


def test_overlap_ring_slabs_compile_for_v5e(one_chip):
    """The split overlap epilogue's depth-1 ring slabs of a 2x2 cs1_paper
    shard, (1, 304, 1536) and (304, 1, 1536), under their default tiles."""
    spec = stencil.STAR7
    for shape in ((1, 304, 1536), (304, 1, 1536)):
        config = tuning.default_config(spec, jnp.bfloat16, shape)
        vp = jax.ShapeDtypeStruct(tuple(s + 2 for s in shape), jnp.bfloat16,
                                  sharding=one_chip)
        cfs = [jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                    sharding=one_chip)] * 6
        _compile(lambda v, *c: tile_apply(v, list(c), spec, config,
                                          interpret=False), vp, *cfs)


@pytest.mark.parametrize("batch", [0, 8])
@pytest.mark.parametrize("op", ["update_q_dots", "update_xr_dots",
                                "update_p", "dot_mixed"])
def test_fused_iter_kernel_compiles_for_v5e(one_chip, op, batch):
    mesh = (608, 608, 608) if not batch else (64, 64, 608)
    shape = ((batch,) if batch else ()) + mesh
    vec = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    sca = jax.ShapeDtypeStruct((batch,) if batch else (), jnp.float32,
                               sharding=one_chip)
    n_scalars, n_vecs = {"update_q_dots": (1, 3), "update_xr_dots": (2, 5),
                         "update_p": (2, 3), "dot_mixed": (0, 2)}[op]
    fn = getattr(fused_iter, op)
    _compile(lambda *a: fn(*a, interpret=False, batched=bool(batch)),
             *([sca] * n_scalars + [vec] * n_vecs))


@pytest.mark.parametrize("backend", ["spmd", "pallas"])
def test_solve_layers_named_for_v5e(one_chip, backend):
    """The star7 solve on one chip, compiled by the TPU compiler: the layer
    rules of ``bench/scopes.py`` claim every vector-sized instruction of
    its loop in the TPU's own fusions (the ones a chip trace names), and
    each Pallas kernel's custom call carries its ``name=``."""
    shape = (128, 128, 608)
    mesh = Mesh([[one_chip.device_set.pop()]], ("data", "model"))
    arr = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    cf = stencil.StencilCoeffs({n: arr for n in stencil.STAR7.names})
    pol = precision.get_policy("bf16_mixed")
    hlo = jax.jit(lambda c, v: bicgstab.solve_distributed(
        mesh, c, v, tol=1e-3, maxiter=50, policy=pol, backend=backend,
        schedule="overlap", interpret=False)).lower(cf, arr).compile().as_text()
    assert scopes.unscoped_vectors(hlo, math.prod(shape)) == []
    layers = scopes.layer_map(hlo)
    assert {"spmv", "update", "dots", "setup"} <= set(layers.values())
    kernels = set(scopes.pallas_kernels(hlo))
    assert kernels == ({"stencil_star7", "dot_partial", "update_q_dots",
                        "update_xr_dots", "update_p"} if backend == "pallas" else set())
    if backend == "pallas":
        # the stencil kernels of the loop are the SpMV (the one of x0 is set-up)
        stencils = {layers[k] for k in layers if k.startswith("stencil_star7")}
        assert stencils == {"spmv", "setup"}


@pytest.mark.parametrize("specname", ["star7", "star25"])
def test_spmd_solve_streams_spmv_for_v5e(one_chip, monkeypatch, specname):
    """The spmd solve as a TPU process traces it (the platform read as
    ``tpu``): its SpMVs are ``spmv_stream`` kernels, in the ``spmv`` layer
    in the loop, and nothing else is a custom call."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    spec = stencil.get_spec(specname)
    shape = (64, 64, 608)
    mesh = Mesh([[one_chip.device_set.pop()]], ("data", "model"))
    arr = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    cf = stencil.StencilCoeffs({n: arr for n in spec.names})
    pol = precision.get_policy("bf16_mixed")
    hlo = jax.jit(lambda c, v: bicgstab.solve_distributed(
        mesh, c, v, tol=1e-3, maxiter=50, policy=pol, backend="spmd",
        schedule="overlap")).lower(cf, arr).compile().as_text()
    assert set(scopes.pallas_kernels(hlo)) == {"spmv_stream"}
    assert scopes.unscoped_vectors(hlo, math.prod(shape)) == []
    layers = scopes.layer_map(hlo)
    assert {layers[k] for k in layers if k.startswith("spmv_stream")} \
        == {"spmv", "setup"}


# HPCG's 384^3 block and its coarse levels (core/multigrid, 4 levels)
HPCG_LEVELS = [(384, 384, 384), (192, 192, 192), (96, 96, 96), (48, 48, 48)]


@pytest.mark.parametrize("shape", HPCG_LEVELS, ids=str)
def test_symgs_and_box_stream_compile_for_v5e(one_chip, shape):
    from repro.core.multigrid import BACKWARD, FORWARD
    from repro.kernels.stencil_nd.symgs import symgs_sweep

    offsets = stencil.BOX27.offsets
    arr = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    fields = [arr] * len(offsets)
    for first, inplane in (FORWARD, BACKWARD):
        compiled = _compile(lambda r, x, *c: symgs_sweep(
            r, x, list(c), offsets, first=first, inplane=inplane), arr, arr, *fields)
        assert scopes.pallas_kernels(compiled.as_text()) == ["symgs"]
    _compile(lambda r, *c: symgs_sweep(r, None, list(c), offsets, first=1,
                                       inplane=FORWARD[1]), arr, *fields)
    compiled = _compile(lambda v, *c: spmv_stream(v, list(c), offsets), arr, *fields)
    assert scopes.pallas_kernels(compiled.as_text()) == ["spmv_stream"]


def test_hpcg_solve_compiles_for_v5e(one_chip, monkeypatch):
    """The HPCG cell's MG-PCG solve at 384^3 as a TPU process traces it:
    its kernels are ``symgs`` and ``spmv_stream``, every vector-sized loop
    instruction has a layer, and it fits a v5e's 16 GB."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shape = HPCG_LEVELS[0]
    mesh = Mesh([[one_chip.device_set.pop()]], ("data", "model"))
    arr = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    cf = stencil.StencilCoeffs({n: arr for n in stencil.BOX27.names})
    compiled = jax.jit(lambda c, v: bicgstab.solve_distributed(
        mesh, c, v, tol=1e-6, maxiter=500, policy=precision.F32, solver="cg",
        precond="mg", backend="spmd", schedule="overlap")).lower(cf, arr).compile()
    hlo = compiled.as_text()
    assert set(scopes.pallas_kernels(hlo)) == {"symgs", "spmv_stream"}
    assert scopes.unscoped_vectors(hlo, math.prod(shape)) == []
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14e9
