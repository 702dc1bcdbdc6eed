"""Built-in entrypoints: quick-start trainers + test probes.

Parity: the reference's quick-start workloads (MNIST/CIFAR polyaxonfiles in
its docs/tutorials) — here as in-process jax entrypoints any spec can point
at (``run: {entrypoint: polyaxon_tpu.builtins.trainers:<name>}``).  The
probe entrypoints (`failing`, `sleepy`, `flaky_once`) exist for the
platform's own failure-handling tests, like the reference's fixture specs.
"""

from __future__ import annotations

import collections
import functools
import json
import re
import time

from polyaxon_tpu.stats import get_stats
from polyaxon_tpu.tracking import Context
from polyaxon_tpu.tracking.flightrec import get_progress
from polyaxon_tpu.tracking.trace import get_tracer


def _percentile_metrics(run_stats, key: str, out_prefix: str) -> dict:
    """Histogram percentiles for ``key`` as flat metric fields."""
    summary = run_stats.summaries().get(key)
    if not summary or not summary["count"]:
        return {}
    return {
        f"{out_prefix}_p50": summary["p50"],
        f"{out_prefix}_p95": summary["p95"],
        f"{out_prefix}_p99": summary["p99"],
    }


def noop(ctx: Context) -> None:
    """Smallest possible run: report one metric."""
    ctx.log_text("noop trainer running")
    ctx.log_metrics(step=0, done=1.0)


def failing(ctx: Context) -> None:
    """Always fails (failure-path probe)."""
    raise RuntimeError("intentional failure")


def sleepy(ctx: Context) -> None:
    """Sleeps `seconds` (stop/zombie probe)."""
    time.sleep(float(ctx.get_param("seconds", 30.0)))


def flaky_once(ctx: Context) -> None:
    """Fails on the first gang attempt, succeeds after restart.

    Uses a marker file in outputs/ (which survives a gang restart) to
    remember the first attempt.
    """
    marker = ctx.outputs_path / f"attempt_p{ctx.process_id}"
    if not marker.exists():
        marker.write_text("1")
        raise RuntimeError("flaky first attempt")
    ctx.log_metrics(recovered=1.0)


def stalling(ctx: Context) -> None:
    """Beats the progress beacon, then one process goes silent
    (stall/straggler-detection probe).

    Every process beats ``warm_steps`` steps ``beat_interval`` apart, then
    the ``stall_process`` victim (-1 = all of them) sleeps ``stall_s``
    without beating while its peers advance ``peer_steps`` more — which is
    what distinguishes a gang-wide *stall* (everyone silent, heartbeats
    fresh) from a *straggler* (one host falling behind the gang median).

    ``recover_steps`` > 0 makes the victim resume beating after the sleep
    (``recover_interval`` apart) — a stall that *clears* while the gang is
    still running, which is what the alert engine's firing → resolved
    transition needs to be tested against honestly.
    """
    progress = get_progress()
    warm = int(ctx.get_param("warm_steps", 5))
    interval = float(ctx.get_param("beat_interval", 0.02))
    for i in range(warm):
        progress.beat(step=i)
        time.sleep(interval)
    victim = int(ctx.get_param("stall_process", -1))
    recover = int(ctx.get_param("recover_steps", 0))
    if victim in (-1, ctx.process_id):
        time.sleep(float(ctx.get_param("stall_s", 2.0)))
        recover_interval = float(ctx.get_param("recover_interval", interval))
        for i in range(warm, warm + recover):
            progress.beat(step=i)
            time.sleep(recover_interval)
    else:
        for i in range(warm, warm + int(ctx.get_param("peer_steps", 100))):
            progress.beat(step=i)
            time.sleep(interval)
    ctx.log_metrics(step=warm, done=1.0)


def resume_counter(ctx: Context) -> None:
    """Counts resume attempts via a checkpoint file (artifact-store probe).

    Each attempt reads the counter from checkpoints/ (which clone/resume
    restores — from the local run dir or the artifact store), increments
    it, and reports it; outputs/ gets a marker file so output shipping is
    observable too.
    """
    state = ctx.checkpoints_path / "counter.txt"
    n = int(state.read_text()) if state.exists() else 0
    state.write_text(str(n + 1))
    (ctx.outputs_path / f"attempt_{n + 1}.marker").write_text("ok")
    ctx.log_metrics(step=n + 1, counter=float(n + 1))
    ctx.log_text(f"resume_counter attempt {n + 1}")


def _fault_injection(ctx: Context):
    """Per-step fault injector for the declared chaos params, or None.

    ``preempt_step``/``preempt_process``/``preempt_signal`` kill a worker
    mid-loop with REAL process death (SIGKILL, or SIGTERM then SIGKILL
    after ``preempt_grace_s`` — the preemption-notice shape), once per run:
    an outputs marker survives the restart so the resumed attempt trains
    through.  ``stall_at_step``/``stall_s``/``stall_process`` silence a
    worker's progress beats mid-loop (heartbeats keep flowing) to trip the
    stall/straggler detectors against a live train loop.
    """
    preempt_step = int(ctx.get_param("preempt_step", -1))
    stall_at = int(ctx.get_param("stall_at_step", -1))
    stall_s = float(ctx.get_param("stall_s", 0.0))
    if preempt_step < 0 and (stall_at < 0 or stall_s <= 0):
        return None
    preempt_process = int(ctx.get_param("preempt_process", 0))
    preempt_signal = str(ctx.get_param("preempt_signal", "kill"))
    preempt_grace_s = float(ctx.get_param("preempt_grace_s", 0.5))
    stall_process = int(ctx.get_param("stall_process", -1))

    def on_step(step: int) -> None:
        import os
        import signal as _signal

        if step == stall_at and stall_s > 0 and stall_process in (-1, ctx.process_id):
            ctx.log_text(f"injecting {stall_s:.1f}s stall at step {step}")
            time.sleep(stall_s)
        if step == preempt_step and preempt_process in (-1, ctx.process_id):
            marker = None
            if ctx.outputs_path is not None:
                marker = ctx.outputs_path / f"preempted_p{ctx.process_id}"
                if marker.exists():
                    return
                marker.write_text(str(step))
            ctx.log_text(
                f"injecting preemption at step {step} (signal={preempt_signal})"
            )
            if preempt_signal == "term":
                os.kill(os.getpid(), _signal.SIGTERM)
                time.sleep(max(preempt_grace_s, 0.0))
            os.kill(os.getpid(), _signal.SIGKILL)

    return on_step


def _should_measure_flops(ctx: Context, backend: str) -> bool:
    """Whether to take per-step FLOPs from XLA's cost analysis of the
    AOT-compiled step instead of the analytic estimate.

    ``auto`` measures only on CPU (the e2e path exercises cost analysis
    there) and trusts the analytic estimate on TPU, where the analysis
    counts remat's recomputed FLOPs and nothing for the Mosaic kernels —
    not the model FLOPs an MFU is defined over.
    ``flops_probe: measure|analytic`` overrides."""
    mode = str(ctx.get_param("flops_probe", "auto"))
    if mode == "measure":
        return True
    if mode == "analytic":
        return False
    return backend == "cpu"


def _train_image_classifier(
    ctx: Context,
    *,
    label: str,
    loss_fn,
    accuracy_fn,
    init_fn,
    axes_tree,
    optimizer,
    flops_per_example: float = 0.0,
) -> None:
    """Shared image-classifier train loop (cnn_train / vit_train).

    Two data paths, one loop:

    - ``dataset: <name>`` — a store-registered dataset (see
      ``runtime/datasets.py``): host-sharded mmap shard reading, per-epoch
      shuffles, uint8 on the wire, and a position-exact resume (the data
      stream fast-forwards to the restored step).  ``cifar10-train`` after
      ``register_cifar10`` is the reference's CIFAR-10 guide
      (``docs/guides/training-cifar10.md``).
    - no dataset — synthetic class-conditional images (deterministic from
      the seed), isolating compute+collectives from IO for benchmarks.

    The hot loop is OVERLAPPED (see ``docs/pipeline.md``): host-side row
    gathers run ``prefetch`` batches ahead on ``prefetch_workers`` threads,
    the next batch's device placement is dispatched before the current
    step is consumed, checkpoint saves are async, and loss logging drains
    on a background thread — the device never waits on the host for any of
    them.  ``prefetch: 0`` restores the fully synchronous loop
    (byte-identical data stream; the A/B baseline).
    """
    import itertools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from polyaxon_tpu.parallel import template_for
    from polyaxon_tpu.runtime.data import global_batch_from_host_data
    from polyaxon_tpu.runtime.pipeline import MetricsDrain, TrainPipeline
    from polyaxon_tpu.runtime.train import build_train_step
    from polyaxon_tpu.tracking.ledger import get_ledger
    from polyaxon_tpu.tracking.profiling import StepClock, StepProfiler

    # Arm the utilization ledger first: model build, jit init, and data
    # setup all belong to this run's wall clock.
    led = get_ledger().start(source="train")

    steps = int(ctx.get_param("steps", 20))
    batch_size = int(ctx.get_param("batch", 64))
    image_size = int(ctx.get_param("image_size", 32))
    n_classes = int(ctx.get_param("classes", 10))
    dataset = ctx.get_param("dataset")
    save_every = int(ctx.get_param("save_every", 0))
    prefetch = int(ctx.get_param("prefetch", 2))
    prefetch_workers = int(ctx.get_param("prefetch_workers", 2))

    mesh = ctx.mesh
    if mesh is None:
        from polyaxon_tpu.runtime.mesh import build_mesh

        mesh = build_mesh({"data": jax.device_count()})
    template = template_for(ctx.strategy, dict(mesh.shape), ctx.strategy_options)

    ts = build_train_step(
        loss_fn=lambda p, b: loss_fn(p, b, template, mesh),
        init_fn=init_fn,
        axes_tree=axes_tree,
        optimizer=optimizer,
        mesh=mesh,
        template=template,
    )
    key = jax.random.PRNGKey(ctx.seed or 0)
    params, opt_state = ts.init(key)

    # Checkpoint/resume (same contract as lm_train): restore whatever the
    # checkpoints/ dir holds — a resumed clone inherits the original's.
    start_step = 0
    ckpt = None
    if save_every > 0 and ctx.checkpoints_path is not None:
        from polyaxon_tpu.runtime.checkpoint import CheckpointManager

        ckpt = CheckpointManager(ctx.checkpoints_path, save_interval_steps=save_every)
        restored = ckpt.restore(params, opt_state)
        if restored is not None:
            params, opt_state = restored["params"], restored["opt_state"]
            start_step = restored["step"] + 1
            ctx.log_text(f"restored checkpoint at step {restored['step']}")

    if dataset is not None:
        from polyaxon_tpu.runtime.datasets import DatasetReader

        reader = DatasetReader(
            ctx.data_path,
            str(dataset),
            global_batch=batch_size,
            seed=ctx.seed or 0,
            num_processes=ctx.num_processes,
            process_id=ctx.process_id,
        )

        def place(local):
            return global_batch_from_host_data(
                {
                    "images": local["images"],
                    "labels": local["labels"].astype(np.int32),
                },
                ts.batch_sharding,
            )

        # Host prefetch over gather thunks + device prefetch onto the
        # step's batch sharding; each host prefetches only its own rows.
        pipe = TrainPipeline(
            reader.batch_tasks(start_step),
            place,
            prefetch=prefetch,
            workers=prefetch_workers,
        )
    else:
        # Synthetic class-conditional images (the fixture dataset's exact
        # recipe — shared helper so benchmark and fixture never diverge).
        from polyaxon_tpu.runtime.datasets import synthetic_class_images

        rng = np.random.default_rng(ctx.seed or 0)
        images, labels = synthetic_class_images(
            rng, batch_size, image_size, n_classes
        )
        fixed = ts.place_batch(
            {"images": jnp.asarray(images), "labels": jnp.asarray(labels)}
        )
        pipe = TrainPipeline(
            itertools.repeat(fixed), prefetch=0, tasks=False
        )

    acc_fn = jax.jit(lambda p, b: accuracy_fn(p, b, template, mesh))
    profiler = StepProfiler(
        ctx.outputs_path or ".",
        start_step=int(ctx.get_param("profile_start", -1)),
        num_steps=int(ctx.get_param("profile_steps", 0)),
    )
    # On-demand capture (control-plane `profile` commands): same per-step
    # hook as the launch-time profiler, armed only when a command arrives.
    from polyaxon_tpu.tracking.capture import get_capture_agent

    capture = get_capture_agent()
    ckpt_now = None
    if ckpt is not None:
        from polyaxon_tpu.runtime.checkpoint import CheckpointNowService

        ckpt_now = CheckpointNowService(ckpt, capture)
    inject = _fault_injection(ctx)
    drain = MetricsDrain(lambda step, vals: ctx.log_metrics(step=step, **vals))
    clock = StepClock()
    tracer = get_tracer()
    run_stats = get_stats()
    progress = get_progress()
    metrics = None
    batch = None
    # FLOPs denominator for live MFU: XLA cost analysis of the compiled
    # step where cheap (see _should_measure_flops — probed in-loop, once
    # the first real batch exists), analytic conv/attention estimate
    # otherwise.
    measure_flops = _should_measure_flops(ctx, jax.default_backend())
    led.set_flops_per_step(flops_per_example * batch_size)
    data_wait_accounted = 0.0
    from polyaxon_tpu.runtime.compilecache import aot_compile

    # Peek the first batch BEFORE the loop: it feeds the FLOPs probe and
    # the AOT compile of the step, so both land in the ledger's pre-loop
    # bucket (mark_loop_start below) instead of inside the first step's
    # measured wall — and with the persistent cache armed, a warm
    # restart loads the executable from disk.  step_fn is the compiled
    # executable; calling the jitted ts.step afterwards would compile a
    # second time.  The peeked batch is consumed at start_step, so the
    # data stream is position-identical.
    warm_batch = None
    step_fn, aot_s = ts.step, 0.0
    if steps > start_step:
        warm_batch = next(pipe)
        dwait = pipe.pop_data_wait_s()
        run_stats.timing("train.data_wait_s", dwait)
        led.account("data_wait_s", dwait)
        data_wait_accounted += dwait
        with tracer.span("train.aot_compile"):
            step_fn, aot_s = aot_compile(
                ts.step, params, opt_state, warm_batch, key
            )
        capture.register_executable("train_step", step_fn)
        if measure_flops:
            from polyaxon_tpu.tracking.ledger import executable_flops

            led.set_flops_per_step(
                executable_flops(step_fn) or flops_per_example * batch_size
            )
    first_step_s = None
    t0 = time.time()
    clock.start()
    led.mark_loop_start()
    try:
        with tracer.span("train.loop", steps=steps - start_step):
            for i in range(start_step, steps):
                profiler.on_step(i)
                capture.on_step(i)
                if inject is not None:
                    inject(i)
                with tracer.span("train.step", sample=tracer.hot_sample, step=i):
                    if warm_batch is not None:
                        batch, warm_batch = warm_batch, None
                    else:
                        batch = next(pipe)
                    params, opt_state, metrics = step_fn(
                        params, opt_state, batch, key
                    )
                if ctx.is_leader and (i % 10 == 0 or i == steps - 1):
                    # Push the device array; the drain thread does the host
                    # read — no sync in the dispatch path.
                    drain.push(i, {"loss": metrics["loss"]})
                if ckpt is not None:
                    ckpt.save(i, params, opt_state)
                if ckpt_now is not None:
                    ckpt_now.maybe_save(i, params, opt_state)
                step_dt = clock.tick()
                if step_dt is not None:
                    run_stats.timing("train.step_wall_s", step_dt)
                    if first_step_s is None:
                        # Cold-start honesty metric: AOT compile (or its
                        # cache load) + the first step's dispatch wall.
                        first_step_s = aot_s + step_dt
                dwait = pipe.pop_data_wait_s()
                run_stats.timing("train.data_wait_s", dwait)
                led.account("data_wait_s", dwait)
                data_wait_accounted += dwait
                led.step(step_dt, tokens=batch_size)
                led.maybe_flush()
                # Feed the stall watchdog (tracking/flightrec.py): a beat
                # per step keeps the adaptive deadline honest.
                progress.beat(step=i)
        # Fence BEFORE timing: with async dispatch, steps are still
        # executing when the loop exits — an unfenced clock read would
        # overstate throughput.
        jax.block_until_ready(params)
        dt = time.time() - t0
    finally:
        profiler.close()
        pipe.close()
        drain.close()
        if ckpt is not None:
            ckpt.wait_until_finished()
            ckpt.close()
    # Ledger finalization (every process — the gang roll-up sums hosts):
    # residual data waits not popped in-loop, checkpoint write blocks,
    # the drain backlog paid at close.  A crashed run skips this; the
    # worker's exit flush ships whatever was accounted by then.
    led.account("data_wait_s", max(0.0, pipe.data_wait_s - data_wait_accounted))
    if ckpt is not None:
        led.account("ckpt_block_s", ckpt.save_block_s)
    led.account("metric_drain_s", drain.close_wait_s)
    led.flush(final=True)
    steps_run = steps - start_step
    if steps_run <= 0 or batch is None:
        if ctx.is_leader:
            ctx.log_text(f"{label}: nothing to do (checkpoint already at end)")
        return
    # Every process must join the (global-array) accuracy computation —
    # leader-only dispatch would deadlock multi-host gangs.
    acc = float(acc_fn(params, batch))
    if ctx.is_leader:
        ips = steps_run * batch_size / dt
        clock.add("data_wait_s", pipe.data_wait_s)
        if ckpt is not None:
            clock.add("ckpt_block_s", ckpt.save_block_s)
            run_stats.timing("train.ckpt_block_s", ckpt.save_block_s)
        stats = clock.summary()  # per-step means
        stats.update(_percentile_metrics(run_stats, "train.step_wall_s", "step_wall_s"))
        ctx.log_metrics(
            step=steps,
            accuracy=acc,
            images_per_s=ips,
            aot_compile_s=aot_s,
            first_step_s=first_step_s or aot_s,
            **stats,
        )
        ctx.log_text(
            f"{label} done: {steps} steps, strategy={template.name}, "
            f"loss {float(metrics['loss']):.4f}, acc {acc:.3f}, {ips:.0f} img/s "
            f"(data wait {pipe.data_wait_s * 1e3 / steps_run:.1f} ms/step, "
            f"prefetch={'off' if prefetch <= 0 else prefetch})"
        )


def cnn_train(ctx: Context) -> None:
    """Train the CNN image classifier (the CIFAR-10 quick-start shape).

    Params: steps, batch (global), image_size, classes, lr, channels,
    dataset, save_every — data/checkpoint contracts in
    :func:`_train_image_classifier`.
    """
    import optax

    from polyaxon_tpu.models import cnn
    from polyaxon_tpu.tracking.ledger import conv_classifier_flops_per_image

    cfg = cnn.CNNConfig(
        image_size=int(ctx.get_param("image_size", 32)),
        n_classes=int(ctx.get_param("classes", 10)),
        channels=tuple(ctx.get_param("channels", (64, 128, 256))),
    )

    def normalized(fn):
        # uint8 rides the host→HBM wire (4x smaller than f32); normalize
        # on device where it fuses into the first conv.
        def wrapped(p, b, template, mesh):
            images = b["images"].astype(cfg.dtype) / 255.0 - 0.5
            return fn(p, {**b, "images": images}, cfg)

        return wrapped

    _train_image_classifier(
        ctx,
        label="cnn_train",
        loss_fn=normalized(cnn.loss_fn),
        accuracy_fn=normalized(cnn.accuracy),
        init_fn=lambda k: cnn.init_params(k, cfg),
        axes_tree=cnn.param_axes(cfg),
        optimizer=optax.adamw(float(ctx.get_param("lr", 1e-3))),
        flops_per_example=conv_classifier_flops_per_image(
            cfg.image_size,
            cfg.in_channels,
            cfg.channels,
            cfg.dense_dim,
            cfg.n_classes,
        ),
    )


def vit_train(ctx: Context) -> None:
    """Train the Vision Transformer image classifier.

    The ViT family exercises attention/MLP templates (tp/fsdp) the conv
    net cannot.  Params: steps, batch, image_size, patch_size, classes,
    lr, d_model, n_layers, n_heads, head_dim, d_ff, dataset, save_every —
    data/checkpoint contracts in :func:`_train_image_classifier`.
    """
    import jax.numpy as jnp
    import optax

    from polyaxon_tpu.models import vit
    from polyaxon_tpu.tracking.ledger import transformer_flops_per_token

    d_model = int(ctx.get_param("d_model", 192))
    n_heads = int(ctx.get_param("n_heads", 6))
    cfg = vit.ViTConfig(
        image_size=int(ctx.get_param("image_size", 32)),
        patch_size=int(ctx.get_param("patch_size", 4)),
        n_classes=int(ctx.get_param("classes", 10)),
        d_model=d_model,
        n_layers=int(ctx.get_param("n_layers", 6)),
        n_heads=n_heads,
        head_dim=int(ctx.get_param("head_dim", max(8, d_model // n_heads))),
        d_ff=int(ctx.get_param("d_ff", 4 * d_model)),
    )
    _train_image_classifier(
        ctx,
        label="vit_train",
        loss_fn=lambda p, b, template, mesh: vit.loss_fn(
            p, b, cfg, template=template, mesh=mesh
        ),
        accuracy_fn=lambda p, b, template, mesh: vit.accuracy(
            p, b, cfg, template=template, mesh=mesh
        ),
        init_fn=lambda k: vit.init_params(k, cfg),
        axes_tree=vit.param_axes(cfg),
        optimizer=optax.adamw(
            float(ctx.get_param("lr", 1e-3)), mu_dtype=jnp.bfloat16
        ),
        # A ViT image is a num_patches-token transformer sequence.
        flops_per_example=transformer_flops_per_token(
            cfg.n_params,
            cfg.n_layers,
            cfg.n_heads,
            cfg.head_dim,
            cfg.num_patches,
        )
        * cfg.num_patches,
    )


def lm_generate(ctx: Context) -> None:
    """Autoregressive generation from the flagship LM (the serving story).

    Params: ``target`` (run uuid whose checkpoint to load — typically an
    ``lm_train`` run with ``save_every``; omitted = fresh random weights,
    useful as a pure decode benchmark), ``prompt_len``, ``max_new_tokens``,
    ``batch``, ``temperature``, plus the model-shape params of ``lm_train``
    (must match the checkpointed config when ``target`` is set).  Reports
    ``decode_tokens_per_s`` and logs a sample of the generated ids.
    """
    import numpy as np
    import jax
    import jax.numpy as jnp

    from polyaxon_tpu.models import TransformerConfig, decode, init_params

    cfg_fields = {
        f: int(ctx.get_param(f))
        for f in (
            "vocab_size", "d_model", "n_layers", "n_heads",
            "head_dim", "d_ff", "n_kv_heads", "n_experts",
        )
        if ctx.get_param(f) is not None
    }
    seq = int(ctx.get_param("seq", 256))
    cfg = TransformerConfig(max_seq=seq, **cfg_fields)
    batch = int(ctx.get_param("batch", 1))
    prompt_len = int(ctx.get_param("prompt_len", 16))
    max_new = int(ctx.get_param("max_new_tokens", 64))
    temperature = float(ctx.get_param("temperature", 0.0))

    key = jax.random.PRNGKey(ctx.seed or 0)
    params = init_params(key, cfg)

    target = ctx.get_param("target")
    if target is not None:
        from polyaxon_tpu.runtime.checkpoint import CheckpointManager

        runs_root = ctx.runs_root
        ckpt_dir = runs_root / str(target) / "checkpoints"
        ckpt = CheckpointManager(ckpt_dir)
        try:
            # Weights-only restore: no optimizer template, no optimizer IO.
            restored = ckpt.restore_params(params)
        except ValueError:
            # Pre-round-4 checkpoint layout: needs a full-state template.
            import optax

            restored = ckpt.restore(params, optax.adamw(1e-3).init(params))
        ckpt.close()
        if restored is None:
            raise RuntimeError(f"No checkpoint under {ckpt_dir}")
        params = restored["params"]
        ctx.log_text(f"restored weights from run {target} step {restored['step']}")

    # int8 weight-only decode (see decode.quantize_weights): +51% measured
    # on the bandwidth-bound per-token loop.
    qweights = None
    if str(ctx.get_param("quantize", "") or "") == "int8":
        qweights = decode.quantize_weights(params)
        ctx.log_text("lm_generate: int8 weight-only decode enabled")

    rng = np.random.default_rng(ctx.seed or 0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, prompt_len)))
    gen = jax.jit(
        lambda p, prompt, key, qw: decode.generate(
            p, prompt, cfg, max_new_tokens=max_new,
            temperature=temperature, rng=key, qweights=qw,
        )
    )
    pre = jax.jit(
        lambda p, prompt: decode.prefill(
            p, prompt, decode.init_cache(cfg, batch, prompt_len + max_new), cfg
        )[0]
    )
    # Host reads are the timing barriers. Prefill is timed separately so
    # the decode rate isn't diluted by the O(T^2) prompt pass.
    out = gen(params, prompt, key, qweights)
    np.asarray(out[0, 0])
    np.asarray(pre(params, prompt)[0, 0])
    p0 = time.time()
    np.asarray(pre(params, prompt)[0, 0])
    prefill_s = time.time() - p0
    t0 = time.time()
    out = gen(params, prompt, key, qweights)
    first = np.asarray(out[0, :16])
    total_s = time.time() - t0
    tps = batch * max_new / max(total_s - prefill_s, 1e-9)
    if ctx.is_leader:
        ctx.log_metrics(
            decode_tokens_per_s=tps,
            prefill_s=prefill_s,
            generated=batch * max_new,
        )
        ctx.log_text(
            f"lm_generate done: {batch}x{max_new} tokens at {tps:.0f} tok/s "
            f"decode (prefill {prefill_s*1e3:.0f} ms); sample: {first.tolist()}"
        )


def metric_probe(ctx: Context) -> None:
    """Report a deterministic metric of the hyperparams (hpsearch probe).

    score = -(lr - 0.7)^2  (max at lr=0.7); loss = (lr - 0.3)^2 (min at 0.3).
    Sweeps over this trainer exercise the full search loop in milliseconds.
    """
    lr = float(ctx.get_param("lr", 0.0))
    ctx.log_metrics(
        step=int(ctx.get_param("epochs", 1)),
        score=-((lr - 0.7) ** 2),
        loss=(lr - 0.3) ** 2,
    )


def _placement_report(step_fn, params, tokens) -> dict:
    """Where the step actually runs, for the run log: the backend, each
    local device's bytes in use, which slice of the most-sharded
    parameter and of the batch each device holds, and — from the compiled
    step's HLO — the collectives it holds and the Mosaic kernels it calls
    (none = attention went dense or was interpreted).  Code that has only
    met virtual devices can put everything on the first one; this is
    where that shows."""
    import jax

    def shards(arr):
        return {
            str(s.device.id): str(s.index) for s in arr.addressable_shards
        }

    flat = jax.tree.flatten_with_path(params)[0]
    path, widest = max(
        flat, key=lambda kv: len({str(s.index) for s in kv[1].addressable_shards})
    )
    hlo = step_fn.as_text()
    return {
        "backend": jax.default_backend(),
        "bytes_in_use": {
            str(d.id): int((d.memory_stats() or {}).get("bytes_in_use", 0))
            for d in jax.local_devices()
        },
        "param": jax.tree_util.keystr(path),
        "param_shards": shards(widest),
        "batch_shards": shards(tokens),
        "collectives": dict(
            collections.Counter(
                re.findall(
                    r"\b(all-reduce|all-gather|reduce-scatter|"
                    r"collective-permute|all-to-all)(?:-start)?\(",
                    hlo,
                )
            )
        ),
        "mosaic_calls": hlo.count('custom_call_target="tpu_custom_call"'),
        "mosaic_kernels": sorted(
            set(re.findall(r"flash_(?:fwd|dq|dkv)", hlo))
        ),
    }


def lm_train(ctx: Context) -> None:
    """Train the flagship transformer LM under the spec's strategy.

    The quick-start "CIFAR-10 distributed" equivalent for this framework
    (BASELINE.md north-star): one entrypoint that honors whatever mesh +
    parallelism template the topology declares.  Data is a synthetic
    next-token stream (deterministic from the seed) so the benchmark
    isolates compute + collectives from IO.

    Params: steps, batch, seq, lr, and any TransformerConfig field
    (d_model, n_layers, n_heads, head_dim, d_ff, vocab_size, n_experts,
    n_kv_heads, ce_chunk, attention_impl, remat, remat_policy).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from polyaxon_tpu.models import (
        TransformerConfig,
        init_params,
        loss_fn,
        param_axes,
    )
    from polyaxon_tpu.parallel import template_for
    from polyaxon_tpu.runtime.train import build_train_step
    from polyaxon_tpu.tracking.ledger import (
        get_ledger,
        transformer_flops_per_token,
    )

    led = get_ledger().start(source="train")
    steps = int(ctx.get_param("steps", 10))
    batch_size = int(ctx.get_param("batch", 8))
    seq = int(ctx.get_param("seq", 128))
    lr = float(ctx.get_param("lr", 3e-4))
    cfg_fields = {
        f: int(ctx.get_param(f))
        for f in (
            "vocab_size", "d_model", "n_layers", "n_heads",
            "head_dim", "d_ff", "n_experts", "n_kv_heads", "ce_chunk",
        )
        if ctx.get_param(f) is not None
    }
    for f in ("attention_impl", "remat_policy"):
        if ctx.get_param(f) is not None:
            cfg_fields[f] = str(ctx.get_param(f))
    if ctx.get_param("remat") is not None:
        cfg_fields["remat"] = str(ctx.get_param("remat")).lower() not in (
            "0", "false", "no", "off", "",
        )
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

    # Checkpoint/resume: restore whatever the checkpoints/ dir holds (a
    # resumed clone inherits the original's checkpoints), save every
    # `save_every` steps.
    save_every = int(ctx.get_param("save_every", 0))
    start_step = 0
    ckpt = None
    if save_every > 0 and ctx.checkpoints_path is not None:
        from polyaxon_tpu.runtime.checkpoint import CheckpointManager

        ckpt = CheckpointManager(ctx.checkpoints_path, save_interval_steps=save_every)
        restored = ckpt.restore(params, opt_state)
        if restored is not None:
            params, opt_state = restored["params"], restored["opt_state"]
            start_step = restored["step"] + 1
            ctx.log_text(f"restored checkpoint at step {restored['step']}")

    rng = np.random.default_rng(ctx.seed or 0)
    tokens = rng.integers(0, cfg.vocab_size, (batch_size, seq + 1))
    batch = ts.place_batch(
        {
            "tokens": jnp.asarray(tokens[:, :-1]),
            "targets": jnp.asarray(tokens[:, 1:]),
        }
    )

    from polyaxon_tpu.runtime.pipeline import MetricsDrain
    from polyaxon_tpu.tracking.profiling import StepClock, StepProfiler

    profiler = StepProfiler(
        ctx.outputs_path or ".",
        start_step=int(ctx.get_param("profile_start", -1)),
        num_steps=int(ctx.get_param("profile_steps", 0)),
    )
    # On-demand capture (control-plane `profile` commands): same per-step
    # hook as the launch-time profiler, armed only when a command arrives.
    from polyaxon_tpu.tracking.capture import get_capture_agent

    capture = get_capture_agent()
    # Remediation's checkpoint-now lands on the bus thread but must save
    # from the loop thread (donated buffers) — the service bridges them.
    ckpt_now = None
    if ckpt is not None:
        from polyaxon_tpu.runtime.checkpoint import CheckpointNowService

        ckpt_now = CheckpointNowService(ckpt, capture)
    inject = _fault_injection(ctx)
    # Metrics leave the loop as device arrays; a drain thread does the
    # host reads — even logging steps no longer serialize dispatch.
    drain = MetricsDrain(lambda step, vals: ctx.log_metrics(step=step, **vals))
    clock = StepClock()

    tracer = get_tracer()
    run_stats = get_stats()
    progress = get_progress()
    metrics = None
    # FLOPs denominator for live MFU: XLA cost analysis of the compiled
    # step on CPU (see _should_measure_flops), else the analytic
    # 6N + attention accounting bench.py uses.
    analytic = transformer_flops_per_token(
        cfg.n_params, cfg.n_layers, cfg.n_heads, cfg.head_dim, seq
    ) * (batch_size * seq)
    from polyaxon_tpu.runtime.compilecache import aot_compile
    from polyaxon_tpu.tracking.ledger import executable_flops

    # AOT-compile the step BEFORE the loop (and before the FLOPs probe,
    # which rides the compiled executable for free): the compile lands
    # in the ledger's pre-loop bucket (mark_loop_start below), and with
    # the persistent cache armed a warm restart loads the executable
    # from disk instead of compiling — aot_s IS the cold-start cost.
    # step_fn is the compiled executable — calling the jitted ts.step
    # afterwards would compile a second time.
    with tracer.span("train.aot_compile"):
        step_fn, aot_s = aot_compile(ts.step, params, opt_state, batch, key)
    capture.register_executable("train_step", step_fn)
    ctx.log_text(
        "lm_train placement: "
        + json.dumps(_placement_report(step_fn, params, batch["tokens"]))
    )
    measured = (
        executable_flops(step_fn)
        if _should_measure_flops(ctx, jax.default_backend())
        else None
    )
    led.set_flops_per_step(measured or analytic)
    first_step_s = None
    t0 = time.time()
    clock.start()
    led.mark_loop_start()
    try:
        with tracer.span("train.loop", steps=steps - start_step):
            for i in range(start_step, steps):
                profiler.on_step(i)
                capture.on_step(i)
                if inject is not None:
                    inject(i)
                with tracer.span("train.step", sample=tracer.hot_sample, step=i):
                    params, opt_state, metrics = step_fn(
                        params, opt_state, batch, key
                    )
                if ctx.is_leader and (i % 10 == 0 or i == steps - 1):
                    drain.push(
                        i,
                        {"loss": metrics["loss"], "grad_norm": metrics["grad_norm"]},
                    )
                if ckpt is not None:
                    ckpt.save(i, params, opt_state)  # async; fenced at close
                if ckpt_now is not None:
                    ckpt_now.maybe_save(i, params, opt_state)
                step_dt = clock.tick()
                if step_dt is not None:
                    run_stats.timing("train.step_wall_s", step_dt)
                    if first_step_s is None:
                        # Cold-start honesty metric: AOT compile (or its
                        # cache load) + the first step's dispatch wall.
                        first_step_s = aot_s + step_dt
                led.step(step_dt, tokens=batch_size * seq)
                led.maybe_flush()
                progress.beat(step=i)
        jax.block_until_ready(params)
        dt = time.time() - t0
    finally:
        profiler.close()
        drain.close()
        if ckpt is not None:
            ckpt.wait_until_finished()
            ckpt.close()
    # Ledger finalization (every process — the gang roll-up sums hosts).
    if ckpt is not None:
        led.account("ckpt_block_s", ckpt.save_block_s)
    led.account("metric_drain_s", drain.close_wait_s)
    led.flush(final=True)
    steps_run = steps - start_step
    if steps_run <= 0:
        if ctx.is_leader:
            ctx.log_text("lm_train: nothing to do (checkpoint already at end)")
        return
    loss = float(metrics["loss"]) if metrics is not None else None
    if ctx.is_leader:
        tps = steps_run * batch_size * seq / dt
        if ckpt is not None:
            clock.add("ckpt_block_s", ckpt.save_block_s)
            run_stats.timing("train.ckpt_block_s", ckpt.save_block_s)
        stats = clock.summary()
        stats.update(_percentile_metrics(run_stats, "train.step_wall_s", "step_wall_s"))
        ctx.log_metrics(
            step=steps,
            tokens_per_s=tps,
            aot_compile_s=aot_s,
            first_step_s=first_step_s or aot_s,
            **stats,
        )
        ctx.log_text(
            f"lm_train done: {steps} steps, strategy={template.name}, "
            f"final loss {loss:.4f}, {tps:.0f} tokens/s "
            f"(aot compile {aot_s:.2f}s)"
        )


def synthetic_regression(ctx: Context) -> None:
    """A real (tiny) distributed training loop: pjit linear regression.

    Exercises the full TPU-native path — mesh, NamedSharding, jit train
    step, metric reporting — at a size that runs in milliseconds on the
    virtual CPU mesh.  Params: lr, steps, batch, dim.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    lr = float(ctx.get_param("lr", 0.1))
    steps = int(ctx.get_param("steps", 20))
    batch = int(ctx.get_param("batch", 64))
    dim = int(ctx.get_param("dim", 8))
    seed = ctx.seed if ctx.seed is not None else 0

    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=(dim, 1)).astype(np.float32)
    x = rng.normal(size=(batch, dim)).astype(np.float32)
    y = x @ w_true + 0.01 * rng.normal(size=(batch, 1)).astype(np.float32)

    params = {"w": jnp.zeros((dim, 1), jnp.float32)}
    opt = optax.sgd(lr)
    opt_state = opt.init(params)

    mesh = ctx.mesh
    if mesh is not None:
        data_axes = tuple(n for n in mesh.axis_names if n in ("data", "fsdp", "replica"))
        batch_sharding = NamedSharding(mesh, P(data_axes if data_axes else None))
        x = jax.device_put(x, batch_sharding)
        y = jax.device_put(y, batch_sharding)

    # params/opt_state are rebound from the result every step — donate
    # them so XLA updates in place instead of copying both pytrees per
    # call (x/y are reused across steps and must NOT be donated).
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, x, y):
        def loss_fn(p):
            pred = x @ p["w"]
            return jnp.mean((pred - y) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    loss = None
    for i in range(steps):
        params, opt_state, loss = step(params, opt_state, x, y)
        if ctx.is_leader and (i % 5 == 0 or i == steps - 1):
            ctx.log_metrics(step=i, loss=float(loss))
    ctx.log_text(f"final loss {float(loss):.6f}")
