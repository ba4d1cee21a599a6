"""Parameter declaration + logical-axis sharding (MaxText-style rules).

Each parameter is declared once with *logical* axes; `mesh_rules` maps the
logical names onto physical mesh axes, dropping any mapping that does not
divide evenly (replicate instead).  That single degradation rule absorbs all
the per-arch irregularities (whisper's 20 heads on a 16-way model axis,
qwen2-moe's 60 experts, batch-1 long-context decode, ...), which is what
lets one sharding policy serve 10 architectures x 4 shapes.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]            # logical axis names, len == len(shape)
    init: str = "normal"                    # normal | zeros | ones | embed
    scale: float | None = None              # override fan-in scaling
    dtype: jnp.dtype = jnp.bfloat16

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


# Logical axis -> preferred mesh axis (or tuple). None = always replicated.
DEFAULT_RULES: dict[str, str | tuple[str, ...] | None] = {
    "batch": ("pod", "data"),
    "vocab": "model",
    "heads": "model",
    "heads_flat": "model",    # flattened (H*Dh) projections (RWKV, Mamba d_inner)
    "kv_heads": "model",
    "head_dim": None,
    "ff": "model",
    "experts": "model",
    "expert_ff": "model",
    "moe_groups": ("pod", "data"),   # MoE token groups follow the batch axes
    "d_model": None,
    "seq": None,
    "seq_act": "model",       # Megatron-SP: layer-boundary activations and the
                              # remat stash shard the sequence over the model
                              # axis; GSPMD inserts the AG/RS around attn/mlp
    "kv_seq": "model",        # decode shapes: flash-decode sequence sharding
    "conv": None,
    "state": None,
    "layers": None,           # stacked-period leading axis
}


# Cell-scoped sharding-rule overrides (e.g. long_500k decode: batch=1 leaves
# the data axes idle, so weights/KV re-shard over ("model","data")).  Set via
# `with rule_overrides({...}):` around both spec construction AND tracing so
# `constrain` sees the same rules.
_RULE_OVERRIDES: contextvars.ContextVar[dict] = contextvars.ContextVar(
    "repro_rule_overrides", default={})


@contextlib.contextmanager
def rule_overrides(rules: dict):
    tok = _RULE_OVERRIDES.set({**_RULE_OVERRIDES.get(), **rules})
    try:
        yield
    finally:
        _RULE_OVERRIDES.reset(tok)


def _mesh_axis_sizes(mesh) -> dict[str, int]:
    return dict(mesh.shape)  # works for Mesh and AbstractMesh


def constrain(x: jax.Array, axes: tuple[str | None, ...], rules: dict | None = None):
    """Constrain an activation's sharding by logical axes, if a mesh is ambient.

    Outside ``jax.sharding.set_mesh`` (smoke tests, single device) this is a
    no-op, so model code stays mesh-agnostic.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return x
    return jax.lax.with_sharding_constraint(
        x, physical_spec(x.shape, axes, mesh, rules))


def physical_spec(shape: tuple[int, ...], axes: tuple[str | None, ...], mesh,
                  rules: dict | None = None) -> P:
    """Logical axes -> PartitionSpec, dropping non-dividing mappings."""
    rules = {**DEFAULT_RULES, **_RULE_OVERRIDES.get(), **(rules or {})}
    sizes = _mesh_axis_sizes(mesh)
    used: set[str] = set()
    out = []
    for dim, ax in zip(shape, axes):
        phys = rules.get(ax) if ax is not None else None
        if phys is None:
            out.append(None)
            continue
        cand = (phys,) if isinstance(phys, str) else tuple(phys)
        cand = tuple(a for a in cand if a in sizes and a not in used)
        total = math.prod(sizes[a] for a in cand) if cand else 1
        if cand and dim % total == 0:
            out.append(cand if len(cand) > 1 else cand[0])
            used.update(cand)
        else:
            # try shrinking a multi-axis mapping from the left (e.g. batch on
            # ("pod","data") where only "data" divides)
            placed = None
            for i in range(1, len(cand)):
                sub = cand[i:]
                t = math.prod(sizes[a] for a in sub)
                if dim % t == 0:
                    placed = sub if len(sub) > 1 else sub[0]
                    used.update(sub)
                    break
            out.append(placed)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def sharding_tree(defs, mesh, rules: dict | None = None):
    """ParamDef tree -> NamedSharding tree."""
    return jax.tree.map(
        lambda d: NamedSharding(mesh, physical_spec(d.shape, d.axes, mesh, rules)),
        defs, is_leaf=lambda x: isinstance(x, ParamDef),
    )


def zero1_spec(shape: tuple[int, ...], axes: tuple[str | None, ...], mesh,
               rules: dict | None = None) -> P:
    """ZeRO-1: the parameter's spec plus the batch axes spread over the
    largest still-unsharded dividing dimension.  Used for optimizer moments
    (and implicitly gradients, which GSPMD then reduce-scatters): f32 Adam
    state is 4x the bf16 params — without this it dominates the footprint
    (EXPERIMENTS §Dry-run)."""
    base = physical_spec(shape, axes, mesh, rules)
    entries = list(base) + [None] * (len(shape) - len(base))
    sizes = _mesh_axis_sizes(mesh)
    used = {a for e in entries if e is not None
            for a in (e if isinstance(e, tuple) else (e,))}
    free = [a for a in ("data", "pod") if a in sizes and a not in used]
    if free:
        extra = math.prod(sizes[a] for a in free)
        for i in sorted(range(len(shape)), key=lambda i: -shape[i]):
            if entries[i] is None and shape[i] % extra == 0:
                entries[i] = tuple(free) if len(free) > 1 else free[0]
                break
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def zero1_sharding_tree(defs, mesh, rules: dict | None = None):
    return jax.tree.map(
        lambda d: NamedSharding(mesh, zero1_spec(d.shape, d.axes, mesh, rules)),
        defs, is_leaf=lambda x: isinstance(x, ParamDef),
    )


def abstract_tree(defs, mesh=None, rules: dict | None = None):
    """ParamDef tree -> ShapeDtypeStruct tree (with shardings when mesh given)."""
    def mk(d: ParamDef):
        sh = None
        if mesh is not None:
            sh = NamedSharding(mesh, physical_spec(d.shape, d.axes, mesh, rules))
        return jax.ShapeDtypeStruct(d.shape, d.dtype, sharding=sh)
    return jax.tree.map(mk, defs, is_leaf=lambda x: isinstance(x, ParamDef))


def init_tree(defs, key: jax.Array):
    """ParamDef tree -> real parameter arrays (smoke/test scale only)."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(key, len(leaves))

    def mk(d: ParamDef, k):
        if d.init == "zeros":
            return jnp.zeros(d.shape, d.dtype)
        if d.init == "ones":
            return jnp.ones(d.shape, d.dtype)
        if d.init == "embed":
            # unit-variance rows scaled by 1/sqrt(d) so tied logits start O(1)
            s = 1.0 / math.sqrt(d.shape[-1])
            return (s * jax.random.normal(k, d.shape, jnp.float32)).astype(d.dtype)
        fan_in = d.shape[0] if len(d.shape) == 1 else math.prod(d.shape[:-1])
        scale = d.scale if d.scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
        return (scale * jax.random.normal(k, d.shape, jnp.float32)).astype(d.dtype)

    return treedef.unflatten([mk(d, k) for d, k in zip(leaves, keys)])


def count_params(defs) -> int:
    leaves = jax.tree.leaves(defs, is_leaf=lambda x: isinstance(x, ParamDef))
    return sum(math.prod(d.shape) for d in leaves)


def stack_defs(defs, n: int):
    """Prepend a ``layers`` axis of length n to every ParamDef in the tree."""
    return jax.tree.map(
        lambda d: ParamDef((n,) + d.shape, ("layers",) + d.axes, d.init, d.scale, d.dtype),
        defs, is_leaf=lambda x: isinstance(x, ParamDef),
    )
