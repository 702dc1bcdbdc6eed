"""Worker entrypoint of the training cells: ``lm_train``'s step under a window.

Runs inside the gang the ``Orchestrator`` spawned (spec -> compiler -> spawner
-> ``runtime.worker`` -> here), and builds the step exactly as
``builtins/trainers.py:lm_train`` does, from the program's own parts:
``TransformerConfig``, ``init_params``, ``loss_fn``, ``param_axes``,
``template_for``, ``build_train_step``, ``optax.adamw(lr)``, ``aot_compile``,
the same seed, the same feed (one batch of ids from ``default_rng(seed)``).
``lm_train`` itself cannot be held to a window or asked for its state: it
logs a loss every tenth step and returns nothing (PERF.md, Open questions).

Set-up builds ONE object, the compiled step with its state, drives it through
its first steps, reads from it what ``correct`` compares (each step's loss,
the first gradient's per-leaf norm out of AdamW's first moment, the per-leaf
norm of the parameters' change), and hands that same object to the window.
After the window: the device's peak memory is read, the state is freed, and
the plain reference follows the same steps on the same chip.
"""

from __future__ import annotations

import json
import time
from pathlib import Path

B1 = 0.9  # optax.adamw's first-moment decay: mu_1 = (1 - B1) * g_1


def _adam_mu(opt_state):
    for part in opt_state:
        if hasattr(part, "mu"):
            return part.mu
    raise RuntimeError("no AdamW first moment in the optimizer state")


def main(ctx) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmark.harness.manifest import load_module
    from benchmark.reference import compare_train, train_steps
    from polyaxon_tpu.models import TransformerConfig, init_params, loss_fn, param_axes
    from polyaxon_tpu.parallel import template_for
    from polyaxon_tpu.runtime.compilecache import aot_compile
    from polyaxon_tpu.runtime.train import build_train_step
    from polyaxon_tpu.tracking.ledger import compile_cache_telemetry, install_compile_hooks

    install_compile_hooks()
    devices = jax.devices()
    t_chip = time.time()
    bench = json.loads(Path(ctx.get_param("bench_job")).read_text())
    seconds = float(bench["seconds"])
    batch_size, seq = int(ctx.get_param("batch")), int(ctx.get_param("seq"))
    lr = float(ctx.get_param("lr"))
    cfg_fields = {
        f: int(ctx.get_param(f))
        for f in ("vocab_size", "d_model", "n_layers", "n_heads", "head_dim",
                  "d_ff", "n_kv_heads", "ce_chunk")
        if ctx.get_param(f) is not None
    }
    for f in ("attention_impl", "remat_policy"):
        if ctx.get_param(f) is not None:
            cfg_fields[f] = str(ctx.get_param(f))
    if ctx.get_param("remat") is not None:
        cfg_fields["remat"] = str(ctx.get_param("remat")).lower() not in (
            "0", "false", "no", "off", "")
    cfg = TransformerConfig(max_seq=seq, **cfg_fields)
    mesh = ctx.mesh
    if mesh is None:
        from polyaxon_tpu.runtime.mesh import build_mesh

        mesh = build_mesh({"data": jax.device_count()})
    template = template_for(ctx.strategy, dict(mesh.shape), ctx.strategy_options)
    ts = build_train_step(
        loss_fn=lambda p, b: loss_fn(p, b, cfg, template=template, mesh=mesh),
        init_fn=lambda k: init_params(k, cfg),
        axes_tree=param_axes(cfg),
        optimizer=optax.adamw(lr),
        mesh=mesh,
        template=template,
    )
    key = jax.random.PRNGKey(ctx.seed or 0)
    params, opt_state = ts.init(key)
    tokens = np.random.default_rng(ctx.seed or 0).integers(
        0, cfg.vocab_size, (batch_size, seq + 1))
    fault = bench.get("fault")  # planted by the tests and the limits' readings only
    fed = tokens[: batch_size // 2] if fault == "half_batch" else tokens
    batch = ts.place_batch({"tokens": jnp.asarray(fed[:, :-1]),
                            "targets": jnp.asarray(fed[:, 1:])})
    step_fn, aot_s = aot_compile(ts.step, params, opt_state, batch, key)
    hlo = step_fn.as_text()

    def step(params, opt_state):
        return step_fn(params, opt_state, batch, key)

    if fault == "state_unchanged":
        def step(params, opt_state):  # noqa: F811 - the planted fault takes the step's place
            _, _, metrics = step_fn(jax.tree.map(jnp.copy, params),
                                    jax.tree.map(jnp.copy, opt_state), batch, key)
            return params, opt_state, metrics

    # -- set-up: the first steps, through the window's own call and feed -------------
    warm = int(bench["warm_steps"])
    program = {"losses": []}
    for i in range(warm):
        params, opt_state, metrics = step(params, opt_state)
        program["losses"].append(float(metrics["loss"]))
        if i == 0:
            program["grad_norms"] = {
                k: v / (1.0 - B1)
                for k, v in train_steps.leaf_norms(_adam_mu(opt_state)).items()}
    # The change of each leaf since the seed's draw, one leaf at a time (the
    # program's own init, jitted down to that leaf), before the next step
    # donates the buffers.
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    program["change_norms"] = {}
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        first = jax.jit(lambda k, path=path: _at(init_params(k, cfg), path))(key)
        program["change_norms"][name] = float(
            jnp.sqrt(jnp.sum(jnp.square(leaf - first))))
        del first
    misses_setup = compile_cache_telemetry()[1]

    # -- the window: opens on a step boundary, closes on the last one inside it ------
    trace_root = trace_dir = bench.get("trace_dir")
    trace_from, trace_steps = 2, int(bench.get("trace_steps", 3))
    tracing = False
    t_open = time.time()
    ends = []
    t_trace = [None, None]
    while True:
        n = len(ends)
        if trace_dir and n == trace_from and not tracing:
            jax.profiler.start_trace(trace_dir)
            tracing, t_trace[0] = True, time.time()
        params, opt_state, metrics = step(params, opt_state)
        float(metrics["loss"])  # the step is done when its loss is on the host
        now = time.time()
        if tracing and n + 1 >= trace_from + trace_steps:
            jax.profiler.stop_trace()
            tracing, trace_dir, t_trace[1] = False, None, time.time()
        if now - t_open > seconds:
            break
        ends.append(now)
    if tracing:
        jax.profiler.stop_trace()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())
    misses_total = compile_cache_telemetry()[1]
    result = {
        "device": {"platform": devices[0].platform, "kind": devices[0].device_kind,
                   "count": len(devices), "memory_peak_bytes": peak},
        "t_chip": t_chip, "t_open": t_open, "step_ends": ends, "aot_compile_s": aot_s,
        "tokens_per_step": batch_size * seq, "batch": batch_size, "seq": seq,
        "n_params": cfg.n_params,
        "compile_cache_misses_setup": misses_setup,
        "compile_cache_misses_window": misses_total - misses_setup,
        "mosaic_kernels": sorted({k for k in ("flash_fwd", "flash_dq", "flash_dkv") if k in hlo}),
        "collectives": sum(hlo.count(c + "(") + hlo.count(c + "-start(") for c in (
            "all-reduce", "all-gather", "reduce-scatter", "collective-permute", "all-to-all")),
        "trace_span": t_trace,
        "program": program,
    }
    out = Path(bench["result_file"])
    out.write_text(json.dumps(result))

    # -- free the program's state, then the plain reference on the same chip ---------
    del params, opt_state, batch, step_fn, metrics
    ref = load_module(Path(bench["reference_file"]))
    t0 = time.time()
    reference = train_steps.follow(
        ref, bench["config"], int(ctx.seed or 0), tokens, int(bench["reference_steps"]), lr)
    result["reference"] = reference
    result["compared"] = compare_train.compare(program, reference)
    if bench.get("control_mode"):
        # The control: the reference in the precision below, put in the program's
        # place and held to the same limits.
        control = train_steps.follow(
            ref, bench["config"], int(ctx.seed or 0), tokens,
            int(bench["reference_steps"]), lr, mode=bench["control_mode"])
        result["program_compared"] = result["compared"]
        result["compared"] = compare_train.compare(control, reference)
    result["reference_s"] = time.time() - t0
    if trace_root and devices[0].platform != "cpu":  # a CPU run has no device trace
        from benchmark.trace import reduce as trace_reduce

        events = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_root))
        result["trace"] = trace_reduce.reduce(events)
    out.write_text(json.dumps(result))


def _at(tree, path):
    for k in path:
        tree = tree[getattr(k, "key", k)]
    return tree
