"""Logical-axis sharding: models annotate tensors with *logical* names; the
active mesh maps them to physical axes.

Rules (DESIGN §6): batch-like dims spread over ("pod", "data"); tensor /
expert / vocab / embedding-row / candidate dims over "model". A mesh without
a "pod" axis (single pod) simply drops it. Axes not in the rules replicate.

Models call `constrain(x, "batch", None, "heads", None)` and stay mesh-
agnostic; launchers activate a mesh with `use_mesh(mesh)`.
"""

from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

LOGICAL_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "edges": ("pod", "data", "model"),   # GNN full-graph edge lists
    "heads": ("model",),
    "kv": ("model",),
    "mlp": ("model",),
    "expert": ("model",),
    "vocab": ("model",),
    "rows": ("model",),     # embedding-table rows
    "cand": ("model",),     # retrieval candidates
    "seq": ("model",),      # sequence parallelism (long-context)
    "fsdp": ("data",),      # ZeRO-3-style weight sharding over the dp axis
                            # (weights re-gathered per scan step)
    "nodes": ("pod", "data", "model"),  # GNN node dim for full-graph MLPs
}

_state = threading.local()


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Activate `mesh` for constrain()/sharding() and XLA lowering."""
    prev = getattr(_state, "mesh", None)
    _state.mesh = mesh
    try:
        with mesh:
            yield mesh
    finally:
        _state.mesh = prev


@contextlib.contextmanager
def exclude_axes(*axes: str):
    """Drop physical axes from rule resolution — used inside shard_map
    islands where those axes are manual (e.g. 'pod' inside the pipeline-
    parallel island: 'batch' must map to ('data',) only there)."""
    prev = getattr(_state, "excluded", frozenset())
    _state.excluded = prev | set(axes)
    try:
        yield
    finally:
        _state.excluded = prev


def current_mesh() -> Mesh | None:
    return getattr(_state, "mesh", None)


def spec_for(logical: tuple, mesh: Mesh) -> P:
    names = set(mesh.axis_names) - getattr(_state, "excluded", frozenset())
    parts = []
    for ax in logical:
        if ax is None:
            parts.append(None)
            continue
        rule = LOGICAL_RULES.get(ax, ())
        phys = tuple(a for a in rule if a in names)
        parts.append(phys if phys else None)
    return P(*parts)


def sharding_for(logical: tuple, mesh: Mesh | None = None) -> NamedSharding:
    mesh = mesh or current_mesh()
    return NamedSharding(mesh, spec_for(logical, mesh))


def constrain(x: jax.Array, *logical):
    """with_sharding_constraint under the active mesh (no-op without one).

    Uses the *context* abstract mesh when tracing inside a shard_map island
    (its manual axes differ from the registered mesh; excluded axes are
    already dropped from the spec by exclude_axes)."""
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return x
    assert len(logical) == x.ndim, (logical, x.shape)
    spec = spec_for(logical, mesh)
    abstract = jax.sharding.get_abstract_mesh()
    target = abstract if (abstract is not None
                          and getattr(abstract, "shape_tuple", None)) else mesh
    return jax.lax.with_sharding_constraint(x, NamedSharding(target, spec))


def tree_shardings(axes_tree, mesh: Mesh | None = None):
    """Map a pytree of logical-axes tuples to NamedShardings."""
    mesh = mesh or current_mesh()
    return jax.tree.map(
        lambda ax: sharding_for(ax, mesh), axes_tree,
        is_leaf=lambda x: isinstance(x, tuple))


def dp_axes(mesh: Mesh | None = None) -> tuple[str, ...]:
    mesh = mesh or current_mesh()
    excluded = getattr(_state, "excluded", frozenset())
    return tuple(a for a in ("pod", "data")
                 if a in mesh.axis_names and a not in excluded)


def dp_size(mesh: Mesh | None = None) -> int:
    mesh = mesh or current_mesh()
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = 1
    for a in dp_axes(mesh):
        out *= shape[a]
    return out


def model_size(mesh: Mesh | None = None) -> int:
    mesh = mesh or current_mesh()
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    return shape.get("model", 1)
