"""The solve names its layers inside the compiled program.

Every registered solver, on both distributed backends and under both halo
schedules, is compiled as the benchmark compiles it (``jit`` of
``solve_distributed``), and the benchmark's own reading of the HLO
(``bench/scopes.py``) must find: no vector-sized instruction of the
solver's loop outside a layer, the expected number of SpMV applies in the
loop body, and every Pallas kernel under its ``name=``.  The v5e compiles
of the same checks live in ``tests/test_tpu_compile.py``.
"""

import math
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.core import bicgstab, precision, stencil
from repro.launch.mesh import make_mesh_for_devices

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from bench import scopes  # noqa: E402

SHAPE = (16, 16, 128)
#: SpMV applies in one iteration of each solver
SPMVS = {"bicgstab": 2, "pipelined_bicgstab": 2, "cg": 1, "pipelined_cg": 1}
#: the ``name=`` of every Pallas kernel a star7 solve can launch
KERNELS = {"stencil_star7", "update_q_dots", "update_xr_dots", "update_p", "dot_partial"}


def solve(mesh, solver, backend, schedule, **kw):
    pol = precision.get_policy("bf16_mixed")
    return jax.jit(lambda c, v: bicgstab.solve_distributed(
        mesh, c, v, tol=1e-3, maxiter=50, policy=pol, solver=solver,
        backend=backend, schedule=schedule, **kw))


def abstract(shape, sharding=None):
    arr = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)
    return stencil.StencilCoeffs({n: arr for n in stencil.STAR7.names}), arr


def equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs it holds, in order."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                if hasattr(sub, "consts") and hasattr(sub, "jaxpr"):
                    yield from equations(sub.jaxpr)
                elif hasattr(sub, "eqns"):
                    yield from equations(sub)


def spmv_applies(jaxpr) -> int:
    """Runs of consecutive ``spmv`` equations in the solver loop's body."""
    (loop,) = [e for e in equations(jaxpr) if e.primitive.name == "while"]
    runs, inside = 0, False
    for eqn in loop.params["body_jaxpr"].jaxpr.eqns:
        now = "spmv" in str(eqn.source_info.name_stack)
        runs += now and not inside
        inside = now
    return runs


@pytest.mark.parametrize("schedule", ["blocking", "overlap"])
@pytest.mark.parametrize("backend", ["spmd", "pallas"])
@pytest.mark.parametrize("solver", sorted(SPMVS))
def test_layers_named_in_compiled_solve(solver, backend, schedule):
    cf, b = abstract(SHAPE)
    traced = solve(make_mesh_for_devices(1), solver, backend, schedule).trace(cf, b)
    hlo = traced.lower().compile().as_text()
    assert scopes.unscoped_vectors(hlo, math.prod(SHAPE)) == []
    layers = set(scopes.layer_map(hlo).values())
    assert {"spmv", "dots", "setup"} <= layers
    assert spmv_applies(traced.jaxpr.jaxpr) == SPMVS[solver]
    kernels = [e.params["name"] for e in equations(traced.jaxpr.jaxpr)
               if e.primitive.name == "pallas_call"]
    assert set(kernels) <= KERNELS
    assert bool(kernels) == (backend == "pallas")


def test_scope_names_are_the_layers():
    from repro.obs import trace

    assert set(trace.SCOPES) == set(scopes.RANK)
    with pytest.raises(ValueError):
        trace.scope("spvm")


@pytest.mark.parametrize("backend", ["spmd", "pallas"])
def test_layers_named_across_chips(subproc, backend):
    """On a 2x2 fabric the halo exchange is ``halo`` (its ppermutes too)
    and every AllReduce of the loop is ``dots``."""
    subproc(f"""
        import collections, math, sys
        sys.path.insert(0, {REPO!r})
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding
        from bench import scopes
        from repro.core import bicgstab, precision, stencil
        from repro.core.halo import FabricAxes
        from repro.launch.mesh import make_mesh_for_devices

        mesh = make_mesh_for_devices(4)
        sh = NamedSharding(mesh, FabricAxes.from_mesh(mesh).spec(3))
        arr = jax.ShapeDtypeStruct((32, 32, 128), jnp.bfloat16, sharding=sh)
        cf = stencil.StencilCoeffs({{n: arr for n in stencil.STAR7.names}})
        pol = precision.get_policy("bf16_mixed")
        for schedule in ("blocking", "overlap"):
            f = jax.jit(lambda c, v: bicgstab.solve_distributed(
                mesh, c, v, tol=1e-3, maxiter=50, policy=pol,
                backend={backend!r}, schedule=schedule))
            hlo = f.lower(cf, arr).compile().as_text()
            assert scopes.unscoped_vectors(hlo, 16 * 16 * 128) == [], schedule
            comps, _ = scopes.parse_hlo(hlo)
            layers = scopes.layer_map(hlo)
            by_op = collections.defaultdict(set)
            for instrs in comps.values():
                for i in instrs:
                    if layers.get(i.name) not in (None, "setup"):
                        by_op[i.opcode].add(layers[i.name])
            assert by_op["collective-permute"] | by_op["collective-permute-start"] == {{"halo"}}, by_op
            assert by_op["all-reduce"] | by_op["all-reduce-start"] == {{"dots"}}, by_op
        print("OK")
    """, n_devices=4)
