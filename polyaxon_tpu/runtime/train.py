"""Generic sharded training loop: strategy template → jitted train step.

The runtime core the reference delegates to user containers (SURVEY §2.8):
given a mesh, a strategy template, and a loss function, build the fully
sharded (init, step) pair.  Param/optimizer placement comes from the
template's logical rules; batch placement from its batch spec; everything
else XLA propagates.  The step is one compiled program — gradient, update,
metric — with donated state so params update in place in HBM.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

from polyaxon_tpu.exceptions import RuntimeLayerError
from polyaxon_tpu.parallel.axes import tree_shardings, tree_specs
from polyaxon_tpu.parallel.templates import StrategyTemplate


def _validate_param_shapes(init_fn, param_specs, mesh_axes) -> None:
    """Every sharded param dim must divide by its mesh axes — checked up
    front so a config/mesh mismatch (e.g. 2 GQA KV heads tensor-sharded
    4 ways) reads as a one-line config error naming the parameter, not a
    pjit internals traceback out of jit_init."""
    import jax
    from jax.sharding import PartitionSpec

    abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    flat_shapes, _ = jax.tree.flatten(abstract)
    flat_specs, _ = jax.tree.flatten(
        param_specs, is_leaf=lambda x: isinstance(x, PartitionSpec)
    )
    paths = [
        "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        for path, _ in jax.tree.flatten_with_path(abstract)[0]
    ]
    for name, leaf, spec in zip(paths, flat_shapes, flat_specs):
        for dim, entry in zip(leaf.shape, spec):
            if entry is None:
                continue
            axes = (entry,) if isinstance(entry, str) else entry
            size = 1
            for a in axes:
                size *= mesh_axes.get(a, 1)
            if size > 1 and dim % size:
                raise RuntimeLayerError(
                    f"Parameter {name!r} dim of size {dim} cannot shard over "
                    f"mesh axes {axes} (total {size}) — adjust the model "
                    f"config or the mesh (e.g. GQA kv heads vs tensor "
                    f"parallelism)"
                )


@dataclass
class TrainStep:
    """A compiled sharded train step plus its placement helpers."""

    step: Callable  # (params, opt_state, batch, rng) -> (params, opt_state, metrics)
    init: Callable  # (rng) -> (params, opt_state)
    param_shardings: Any
    batch_sharding: Any
    mesh: Any

    def place_batch(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        import jax

        return jax.tree.map(
            lambda x: jax.device_put(x, self.batch_sharding), batch
        )


def build_train_step(
    *,
    loss_fn: Callable,
    init_fn: Callable,
    axes_tree: Any,
    optimizer: Any,
    mesh,
    template: StrategyTemplate,
    extra_metrics: Optional[Callable] = None,
) -> TrainStep:
    """Wire a loss/init pair into a sharded, jitted training step.

    ``loss_fn(params, batch) -> scalar`` and ``init_fn(rng) -> params`` are
    closures over the model config; ``axes_tree`` names every param's
    logical axes (same tree structure as params).
    """
    import jax
    from jax.sharding import NamedSharding

    mesh_axes = dict(mesh.shape)
    param_specs = tree_specs(axes_tree, template.rules, mesh_axes)
    param_shardings = tree_shardings(mesh, param_specs)
    batch_sharding = NamedSharding(mesh, template.batch_spec())

    _validate_param_shapes(init_fn, param_specs, mesh_axes)
    jit_init = jax.jit(init_fn, out_shardings=param_shardings)

    def _opt_state_shardings(params):
        """Shardings for the optimizer state: any sub-tree that mirrors the
        param tree (optax's mu/nu/trace) gets the param shardings leaf for
        leaf; everything else (step counts, empty states) replicates.

        ``jax.jit(optimizer.init)`` alone gets this wrong in both
        directions — leaves with no data dependence on params (the count)
        land on device 0, and without out_shardings nothing forces mu/nu
        onto the params' placement."""
        from jax.sharding import PartitionSpec

        replicated = NamedSharding(mesh, PartitionSpec())
        param_treedef = jax.tree.structure(params)

        def rec(node):
            if jax.tree.structure(node) == param_treedef:
                return param_shardings
            if hasattr(node, "_fields"):  # optax's namedtuple states
                return type(node)(*(rec(c) for c in node))
            if isinstance(node, (tuple, list)):
                return type(node)(rec(c) for c in node)
            return replicated

        abstract = jax.eval_shape(optimizer.init, params)
        return rec(abstract)

    def init(rng):
        params = jit_init(rng)
        opt_state = jax.jit(
            optimizer.init, out_shardings=_opt_state_shardings(params)
        )(params)
        return params, opt_state

    def _step(params, opt_state, batch, rng):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: p + u, params, updates)
        gnorm = jax.tree.reduce(
            lambda a, b: a + b,
            jax.tree.map(lambda g: (g.astype("float32") ** 2).sum(), grads),
        ) ** 0.5
        metrics = {"loss": loss, "grad_norm": gnorm}
        if extra_metrics is not None:
            metrics.update(extra_metrics(params, batch))
        return params, opt_state, metrics

    step = jax.jit(_step, donate_argnums=(0, 1))
    return TrainStep(
        step=step,
        init=init,
        param_shardings=param_shardings,
        batch_sharding=batch_sharding,
        mesh=mesh,
    )
