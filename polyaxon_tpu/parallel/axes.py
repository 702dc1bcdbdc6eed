"""Logical-axis sharding: name tensor dimensions, map names to mesh axes.

The TPU-native replacement for the reference's per-framework rendezvous
recipes (``polypod/{tensorflow,pytorch,horovod,mxnet}.py`` — which only ever
expressed *data* parallelism as env vars): every parameter and activation
carries a tuple of *logical* axis names (``("embed", "mlp")``), and a
parallelism strategy is nothing but a mapping from logical names to mesh
axes (``{"mlp": "tensor"}``).  XLA then inserts the collectives.  This is
the idiomatic jax/pjit design (same shape as t5x/flax logical partitioning,
re-implemented here without those deps) and is what lets one model
definition serve ddp/fsdp/tp/pp/sp/ep unchanged.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Optional, Sequence, Tuple, Union

from polyaxon_tpu.exceptions import RuntimeLayerError

#: logical axis name -> mesh axis (str), tuple of mesh axes, or None (replicate)
AxisRules = Mapping[str, Union[str, Tuple[str, ...], None]]

LogicalAxes = Tuple[str, ...]


def logical_to_spec(axes: Sequence[str], rules: AxisRules, mesh_axes=None):
    """Turn one tensor's logical axes into a ``PartitionSpec``.

    ``mesh_axes`` (the mesh's axis->size map) is optional; when given, rules
    that point at axes absent from the mesh degrade to replication — so one
    template works on smaller meshes (e.g. tp rules on a mesh with no
    ``tensor`` axis).
    """
    from jax.sharding import PartitionSpec

    entries = []
    used: set = set()
    for name in axes:
        target = rules.get(name) if name is not None else None
        if target is None:
            entries.append(None)
            continue
        parts = (target,) if isinstance(target, str) else tuple(target)
        if mesh_axes is not None:
            parts = tuple(p for p in parts if p in mesh_axes)
        parts = tuple(p for p in parts if p not in used)
        used.update(parts)
        if not parts:
            entries.append(None)
        elif len(parts) == 1:
            entries.append(parts[0])
        else:
            entries.append(parts)
    while entries and entries[-1] is None:
        entries.pop()
    return PartitionSpec(*entries)


def tree_specs(axes_tree: Any, rules: AxisRules, mesh_axes=None):
    """Map :func:`logical_to_spec` over a pytree of logical-axes tuples."""
    import jax

    return jax.tree.map(
        lambda axes: logical_to_spec(axes, rules, mesh_axes),
        axes_tree,
        # A leaf is one tensor's logical-axes tuple; entries may be None
        # (explicitly-replicated dims).
        is_leaf=lambda x: isinstance(x, tuple)
        and all(e is None or isinstance(e, str) for e in x),
    )


def tree_shardings(mesh, spec_tree: Any):
    """PartitionSpec pytree -> NamedSharding pytree for ``mesh``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        spec_tree,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )


def with_logical_constraint(
    x, axes: Sequence[str], rules: AxisRules, mesh=None
):
    """``lax.with_sharding_constraint`` by logical names (inside jit).

    No-op outside a mesh context — model code stays runnable single-device.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty:
            return x
    spec = logical_to_spec(axes, rules, dict(getattr(mesh, "shape", {}) or {}))
    if getattr(mesh, "_any_axis_manual", False):  # inside shard_map
        return x
    if isinstance(mesh, jax.sharding.Mesh):
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)


def validate_rules(rules: AxisRules, mesh_axes: Dict[str, int]) -> None:
    """Reject rules that reference axes the mesh doesn't have (strict mode)."""
    for logical, target in rules.items():
        if target is None:
            continue
        parts = (target,) if isinstance(target, str) else target
        missing = [p for p in parts if p not in mesh_axes]
        if missing:
            raise RuntimeLayerError(
                f"Rule {logical!r} -> {target!r} references mesh axes {missing} "
                f"not present in {list(mesh_axes)}"
            )
