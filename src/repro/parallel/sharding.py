"""Logical-axis sharding substrate (MaxText-style rules).

Every parameter is created as a :class:`Tagged` leaf carrying its logical axis
names; :func:`split_tree` separates the value tree (fed to jit) from the axes
tree (turned into ``NamedSharding``s via :data:`DEFAULT_RULES`).  Activation
sharding is asserted with :func:`constrain`, which is a no-op unless a mesh
context has been installed (so single-device smoke tests run untouched code).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axis (or tuple of mesh axes). Axes absent from the
# active mesh are dropped at resolution time, so one rule table serves the
# single-pod (data, model) and multi-pod (pod, data, model) meshes.
DEFAULT_RULES = {
    "batch": ("pod", "data"),
    "worker": "model",        # FedOCS worker axis == TP shard axis
    "heads": "model",
    "kv_heads": "model",
    "experts": "model",
    "vocab": "model",
    "ff": "model",
    "embed": None,
    "ff_local": None,
    "seq": None,
    "kv_seq": "data",         # sequence-parallel KV cache (long-context decode)
    "layers": None,
    "conv": None,
    "state": None,
    "fsdp": ("pod", "data"),  # ZeRO axis for optimizer state / master weights
    None: None,
}


class Tagged:
    """A parameter value bundled with its logical axis names.

    Registered as a pytree node so inits can be ``vmap``-ed to build stacked
    per-layer parameters (the aux data — axes — must then be identical across
    the mapped instances, which holds by construction).  Rank may temporarily
    disagree with ``axes`` inside such transforms; :func:`retag_stacked`
    prepends the ``layers`` axis afterwards.
    """

    __slots__ = ("value", "axes")

    def __init__(self, value: Any, axes: Sequence[Optional[str]]):
        self.value = value
        self.axes = tuple(axes)

    def __repr__(self):
        shape = getattr(self.value, "shape", None)
        return f"Tagged(shape={shape}, axes={self.axes})"


def _tagged_flatten(t: Tagged):
    return (t.value,), t.axes


def _tagged_unflatten(axes, children):
    return Tagged(children[0], axes)


jax.tree_util.register_pytree_node(Tagged, _tagged_flatten, _tagged_unflatten)


def retag_stacked(tree, lead_axis: str = "layers"):
    """Prepend a leading logical axis to every Tagged leaf (post-vmap init)."""
    return jax.tree.map(
        lambda t: Tagged(t.value, (lead_axis,) + t.axes), tree,
        is_leaf=_is_tagged)


def _is_tagged(x) -> bool:
    return isinstance(x, Tagged)


def split_tree(tree):
    """tagged tree -> (value tree, axes tree) with identical structure."""
    values = jax.tree.map(lambda t: t.value, tree, is_leaf=_is_tagged)
    axes = jax.tree.map(lambda t: t.axes, tree, is_leaf=_is_tagged)
    return values, axes


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """Device mesh over the first ``prod(shape)`` devices with ``Auto`` axes.

    JAX 0.9 builds ``Explicit`` axes by default, under which an eager or
    un-meshed ``shard_map`` refuses arguments placed on one device; every
    mesh of this repo is an ``Auto`` mesh so GSPMD places them.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def shard_map(fn, mesh: Mesh, in_specs, out_specs):
    """The repo's one ``jax.shard_map`` call site (replication check off:
    the callers psum/all_gather explicitly)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def mesh_axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def resolve_axes(logical_axes: Sequence[Optional[str]], mesh: Mesh,
                 rules: dict = DEFAULT_RULES) -> P:
    """logical axis names -> PartitionSpec valid on `mesh`."""
    names = set(mesh.axis_names)
    spec = []
    for ax in logical_axes:
        mapped = rules.get(ax, None)
        if mapped is None:
            spec.append(None)
            continue
        if isinstance(mapped, str):
            mapped = (mapped,)
        present = tuple(m for m in mapped if m in names)
        if not present:
            spec.append(None)
        elif len(present) == 1:
            spec.append(present[0])
        else:
            spec.append(present)
    return P(*spec)


def sharding_for(logical_axes, mesh: Mesh, rules: dict = DEFAULT_RULES
                 ) -> NamedSharding:
    return NamedSharding(mesh, resolve_axes(logical_axes, mesh, rules))


def sharding_for_shape(logical_axes, shape, mesh: Mesh,
                       rules: dict = DEFAULT_RULES) -> NamedSharding:
    """Like :func:`sharding_for`, but drops (replicates) any axis whose
    dimension is not divisible by its mesh extent — required for jit
    *argument* shardings (e.g. 36 attention heads or a 122753 vocab over a
    16-way axis; GSPMD pads internal values but arguments must be even)."""
    sizes = mesh_axis_sizes(mesh)
    base = resolve_axes(logical_axes, mesh, rules)
    spec = []
    for entry, dim in zip(tuple(base), tuple(shape)):
        if entry is None:
            spec.append(None)
            continue
        names = (entry,) if isinstance(entry, str) else tuple(entry)
        ways = 1
        for nm in names:
            ways *= sizes[nm]
        spec.append(entry if dim % ways == 0 else None)
    return NamedSharding(mesh, P(*spec))


def tree_shardings_for_values(axes_tree, values_tree, mesh: Mesh,
                              rules: dict = DEFAULT_RULES):
    """Per-leaf shape-aware shardings (axes_tree zipped with value shapes)."""
    def is_axes_leaf(x):
        return isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x)

    return jax.tree.map(
        lambda ax, v: sharding_for_shape(ax, v.shape, mesh, rules),
        axes_tree, values_tree, is_leaf=is_axes_leaf)


def tree_shardings(axes_tree, mesh: Mesh, rules: dict = DEFAULT_RULES):
    return jax.tree.map(
        lambda axes: sharding_for(axes, mesh, rules),
        axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x),
    )


# ---------------------------------------------------------------------------
# activation constraints — thread-local mesh context
# ---------------------------------------------------------------------------

class _MeshCtx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: dict = DEFAULT_RULES


_CTX = _MeshCtx()


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], rules: dict = DEFAULT_RULES):
    """Install a mesh for activation constraints (and jax's global mesh)."""
    prev_mesh, prev_rules = _CTX.mesh, _CTX.rules
    _CTX.mesh, _CTX.rules = mesh, rules
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules = prev_mesh, prev_rules


def active_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def constrain(x: jax.Array, logical_axes: Sequence[Optional[str]]) -> jax.Array:
    """Assert activation sharding; no-op when no mesh context is installed."""
    mesh = _CTX.mesh
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, sharding_for(logical_axes, mesh, _CTX.rules))


# ---------------------------------------------------------------------------
# ZeRO-1 optimizer-state sharding: add the fsdp axis to the largest
# unsharded-and-divisible dimension of each parameter.
# ---------------------------------------------------------------------------

def _resolves_unsharded(ax, mesh_names, rules) -> bool:
    """True if this logical axis maps to no axis of the active mesh."""
    mapped = rules.get(ax, None)
    if mapped is None:
        return True
    if isinstance(mapped, str):
        mapped = (mapped,)
    return not any(m in mesh_names for m in mapped)


def zero_axes(axes: Tuple[Optional[str], ...], shape: Tuple[int, ...],
              fsdp_size: int, mesh_names=(), rules: dict = DEFAULT_RULES
              ) -> Tuple[Optional[str], ...]:
    """Add the fsdp axis to the largest *effectively unsharded* divisible dim
    (an axis like 'embed'/'ff_local' resolves to None and is eligible)."""
    if fsdp_size <= 1 or "fsdp" in axes:   # idempotent: never double-apply
        return axes
    best, best_dim = None, 0
    for i, (ax, dim) in enumerate(zip(axes, shape)):
        if (_resolves_unsharded(ax, mesh_names, rules)
                and dim % fsdp_size == 0 and dim > best_dim):
            best, best_dim = i, dim
    if best is None:
        return axes
    out = list(axes)
    out[best] = "fsdp"
    return tuple(out)


def zero_axes_tree(axes_tree, values_tree, mesh: Mesh,
                   rules: dict = DEFAULT_RULES):
    """Per-leaf ZeRO axes given actual shapes (values may be ShapeDtypeStructs)."""
    sizes = mesh_axis_sizes(mesh)
    names = set(mesh.axis_names)
    fsdp_axes = rules.get("fsdp", ())
    if isinstance(fsdp_axes, str):
        fsdp_axes = (fsdp_axes,)
    fsdp_size = int(np.prod([sizes[a] for a in fsdp_axes if a in sizes])) \
        if fsdp_axes else 1

    def one(axes, val):
        return zero_axes(axes, val.shape, fsdp_size, names, rules)

    return jax.tree.map(
        one, axes_tree, values_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x),
    )
