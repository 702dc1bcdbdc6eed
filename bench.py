"""Single-chip training benchmark: flagship transformer LM on the real TPU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
The headline number is model FLOP/s utilization (MFU) of a bf16 train step
sized for one chip.  The reference publishes no training numbers
(BASELINE.md: "published": {}), so vs_baseline compares against the last
recorded run of THIS benchmark (BENCH_BASELINE.json, written on first run)
— i.e. the bar is "don't regress, then beat yourself".
"""

from __future__ import annotations

import json
import time
from pathlib import Path

# bf16 peak FLOP/s per chip by device kind (dense MXU) — single source of
# truth lives in the platform's utilization ledger so bench MFU and the
# in-product MFU can never disagree about the denominator.  A TPU kind
# missing from the table is an error there, not a default.
from polyaxon_tpu.tracking.ledger import (  # noqa: E402
    PEAK_FLOPS,
    peak_flops_per_chip,
)


def main() -> None:
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
    from polyaxon_tpu.runtime.mesh import build_mesh
    from polyaxon_tpu.runtime.train import build_train_step

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    # Sized to exercise the MXU on one chip; tiny fallback for CPU smoke.
    if on_tpu:
        # Shape picked by measurement on v5e: d=2048/L=8 amortizes
        # non-matmul overhead; batch 20 is the r5 sweet spot (0.566 vs
        # 16:0.560, 18:0.564, 22:0.559, 24/32 spill/OOM); the save_attn
        # remat policy keeps the attention output across the bwd
        # recompute — full sweep in bench-notes. auto attention resolves
        # to the in-house flash kernel (1024-edge tiles), which beats XLA
        # dense at every measured T since the round-4 block sweep.
        cfg = TransformerConfig(
            vocab_size=32768,
            d_model=2048,
            n_layers=8,
            n_heads=32,
            head_dim=64,
            d_ff=8192,
            max_seq=1024,
            remat=True,
            remat_policy="save_attn",
        )
        batch_size, seq, steps, warmup = 20, 1024, 20, 3
    else:
        cfg = TransformerConfig(
            vocab_size=256,
            d_model=64,
            n_layers=2,
            n_heads=4,
            head_dim=16,
            d_ff=128,
            max_seq=64,
            dtype=jnp.float32,
        )
        batch_size, seq, steps, warmup = 4, 64, 5, 1

    mesh_axes = {"data": jax.local_device_count()}
    mesh = build_mesh(mesh_axes)
    template = template_for("ddp", mesh_axes)
    # bf16 first moment: halves adam-mu HBM traffic in the update step —
    # measured +2.6% MFU on v5e (0.529 → 0.543); loss curve unchanged at
    # bench scale (docs/bench-notes.md).
    optimizer = optax.adamw(3e-4, mu_dtype=jnp.bfloat16)
    ts = build_train_step(
        loss_fn=lambda p, b: loss_fn(p, b, cfg, template=template, mesh=mesh),
        init_fn=lambda k: init_params(k, cfg),
        axes_tree=param_axes(cfg),
        optimizer=optimizer,
        mesh=mesh,
        template=template,
    )
    key = jax.random.PRNGKey(0)
    params, opt_state = ts.init(key)
    rng = np.random.default_rng(0)
    tok = rng.integers(0, cfg.vocab_size, (batch_size, seq + 1))
    batch = ts.place_batch(
        {"tokens": jnp.asarray(tok[:, :-1]), "targets": jnp.asarray(tok[:, 1:])}
    )

    # Sync via a host read of the loss: a device->host copy of the last
    # step's output is a barrier for the whole timed window.
    for _ in range(warmup):
        params, opt_state, metrics = ts.step(params, opt_state, batch, key)
    float(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, metrics = ts.step(params, opt_state, batch, key)
    float(metrics["loss"])
    dt = time.perf_counter() - t0

    # Tracing overhead: the instrumented hot loop (per-step sampled span +
    # StepClock tick + histogram observe — exactly what the built-in
    # trainers add) vs the bare loop, same jitted step.  Gates the
    # observability layer's acceptance bar: tracing at default sampling
    # must cost <1% of step wall on the real device.
    trace_overhead_pct = None
    trace_overhead_ok = None
    try:
        from polyaxon_tpu.stats import MemoryStats
        from polyaxon_tpu.tracking.flightrec import Progress
        from polyaxon_tpu.tracking.profiling import StepClock
        from polyaxon_tpu.tracking.trace import get_tracer

        tracer = get_tracer()
        treg = MemoryStats()
        beacon = Progress()
        n_tr = min(steps, 10)

        # ts.step donates (params, opt_state), so every loop consumes the
        # state it is given and returns the live replacement.  The
        # instrumented side mirrors the built-in trainers exactly: span +
        # StepClock tick + histogram observe + stall-beacon beat, so the
        # watchdog's per-step cost is charged against the same budget.
        def _overhead_loop(n: int, instrumented: bool, p, o):
            clock = StepClock()
            clock.start()
            t0 = time.perf_counter()
            m = None
            for i in range(n):
                if instrumented:
                    with tracer.span("train:step", sample=tracer.hot_sample):
                        p, o, m = ts.step(p, o, batch, key)
                    d = clock.tick()
                    if d is not None:
                        treg.timing("train.step_wall_s", d)
                    beacon.beat(step=i)
                else:
                    p, o, m = ts.step(p, o, batch, key)
            float(m["loss"])
            return time.perf_counter() - t0, p, o

        _, params, opt_state = _overhead_loop(2, True, params, opt_state)
        plain = float("inf")
        instr = float("inf")
        for _ in range(3):
            d, params, opt_state = _overhead_loop(n_tr, False, params, opt_state)
            plain = min(plain, d)
        for _ in range(3):
            d, params, opt_state = _overhead_loop(n_tr, True, params, opt_state)
            instr = min(instr, d)
        trace_overhead_pct = max(0.0, (instr - plain) / plain * 100.0)
        # CPU-smoke steps are ~ms each, so scheduler noise dominates the
        # delta; the 1% bar is enforced where it means something (TPU).
        trace_budget_pct = 1.0 if on_tpu else 25.0
        trace_overhead_ok = trace_overhead_pct < trace_budget_pct
        if not trace_overhead_ok:
            import sys

            print(
                f"bench: trace_overhead_pct={trace_overhead_pct:.2f} exceeds "
                f"the {trace_budget_pct}% budget — tracing is taxing the "
                "hot loop",
                file=sys.stderr,
            )
    except Exception:
        import sys
        import traceback

        traceback.print_exc(file=sys.stderr)

    steps_per_s = steps / dt
    tokens_per_s = steps_per_s * batch_size * seq
    # Train-step FLOPs: 6*N per token (fwd+bwd matmuls) + attention scores
    # 12*L*H*hd*T per token (fwd+bwd, causal halves then doubles back).
    n_params = cfg.n_params
    flops_per_token = 6 * n_params + 12 * cfg.n_layers * cfg.n_heads * cfg.head_dim * seq
    model_flops_per_s = tokens_per_s * flops_per_token
    peak = (
        peak_flops_per_chip(dev.platform, dev.device_kind)
        * jax.local_device_count()
    )
    mfu = model_flops_per_s / peak if on_tpu else 0.0

    # Second metric: LONG-CONTEXT capability+throughput. T=8192 is past the
    # dense path's memory wall on one v5e chip (dense OOMs at 25.7G); the
    # pallas flash kernel's O(T) memory makes the config runnable at all.
    final_loss = float(metrics["loss"])
    longctx = None
    if on_tpu:
        try:
            # Free the headline model's HBM first (params+adam ≈ 8G; the
            # long-context model needs the same again).
            del params, opt_state, batch, metrics
            import gc

            gc.collect()
            lcfg = cfg.scaled(max_seq=8192, attention_impl="flash")
            lts = build_train_step(
                loss_fn=lambda p, b: loss_fn(p, b, lcfg, template=template, mesh=mesh),
                init_fn=lambda k: init_params(k, lcfg),
                axes_tree=param_axes(lcfg),
                optimizer=optax.adamw(3e-4),
                mesh=mesh,
                template=template,
            )
            lparams, lopt = lts.init(key)
            ltok = rng.integers(0, lcfg.vocab_size, (2, 8192 + 1))
            lbatch = lts.place_batch(
                {"tokens": jnp.asarray(ltok[:, :-1]), "targets": jnp.asarray(ltok[:, 1:])}
            )
            for _ in range(2):
                lparams, lopt, lm = lts.step(lparams, lopt, lbatch, key)
            float(lm["loss"])
            lt0 = time.perf_counter()
            for _ in range(6):
                lparams, lopt, lm = lts.step(lparams, lopt, lbatch, key)
            float(lm["loss"])
            ldt = time.perf_counter() - lt0
            ltps = 6 * 2 * 8192 / ldt
            lfpt = 6 * lcfg.n_params + 12 * lcfg.n_layers * lcfg.n_heads * lcfg.head_dim * 8192
            longctx = {
                "tokens_per_s": round(ltps),
                "mfu": round(ltps * lfpt / peak, 4),
            }
            del lparams, lopt, lbatch
            gc.collect()
            # Capability stretch: T=16384 through the ring path on one
            # device (sp_ring, n=1 — the flash block kernel over the full
            # sequence inside the ring body). 2x the old context ceiling.
            rcfg = cfg.scaled(max_seq=16384, attention_impl="flash")
            rmesh_axes = {"sequence": 1}
            rmesh = build_mesh(rmesh_axes)
            rtmpl = template_for("sp_ring", rmesh_axes)
            rts = build_train_step(
                loss_fn=lambda p, b: loss_fn(p, b, rcfg, template=rtmpl, mesh=rmesh),
                init_fn=lambda k: init_params(k, rcfg),
                axes_tree=param_axes(rcfg),
                optimizer=optimizer,
                mesh=rmesh,
                template=rtmpl,
            )
            rparams, ropt = rts.init(key)
            rtok = rng.integers(0, rcfg.vocab_size, (1, 16384 + 1))
            rbatch = rts.place_batch(
                {"tokens": jnp.asarray(rtok[:, :-1]), "targets": jnp.asarray(rtok[:, 1:])}
            )
            for _ in range(2):
                rparams, ropt, rm = rts.step(rparams, ropt, rbatch, key)
            float(rm["loss"])
            rt0 = time.perf_counter()
            for _ in range(4):
                rparams, ropt, rm = rts.step(rparams, ropt, rbatch, key)
            float(rm["loss"])
            rdt = time.perf_counter() - rt0
            rtps = 4 * 16384 / rdt
            rfpt = 6 * rcfg.n_params + 12 * rcfg.n_layers * rcfg.n_heads * rcfg.head_dim * 16384
            # Honest label (r4 weak #4): this is the ring PATH exercised on
            # ONE chip ({sequence: 1} mesh) — a capability-stretch metric
            # (2x the dense context ceiling), not multi-device ring perf.
            longctx["t16384_single_chip_tokens_per_s"] = round(rtps)
            longctx["t16384_single_chip_mfu"] = round(rtps * rfpt / peak, 4)
            del rparams, ropt, rbatch
        except Exception:
            # null in the output = degraded gracefully, but the reason must
            # be visible (a flash-path regression is not an OOM).
            import sys
            import traceback

            traceback.print_exc(file=sys.stderr)

    # North-star #2 (BASELINE.md): hpsearch trials/hour — a real sweep
    # through the orchestrator (create → waves → iterate), workers as
    # subprocess gangs. Orchestration throughput, not model compute.
    # 16 trials / concurrency 4 (up from 6/2 in r≤4): one monitor tick no
    # longer moves the number double digits.
    trials_per_hour = None
    try:
        import tempfile

        from polyaxon_tpu.orchestrator import Orchestrator

        n_trials = 16
        orch = Orchestrator(
            tempfile.mkdtemp(), monitor_interval=0.05, heartbeat_interval=1.0
        )
        try:
            t0 = time.perf_counter()
            group = orch.submit(
                {
                    "kind": "group",
                    "run": {
                        "entrypoint": "polyaxon_tpu.builtins.trainers:metric_probe"
                    },
                    "environment": {
                        "topology": {
                            "accelerator": "cpu-1",
                            "num_devices": 1,
                            "num_hosts": 1,
                        }
                    },
                    "hptuning": {
                        "matrix": {"lr": {"uniform": [0, 1]}},
                        "concurrency": 4,
                        "random_search": {"n_experiments": n_trials, "seed": 0},
                    },
                }
            )
            done = orch.wait(group.id, timeout=300)
            sweep_dt = time.perf_counter() - t0
            if done.status == "succeeded":
                trials_per_hour = n_trials / sweep_dt * 3600
        finally:
            orch.stop()
    except Exception:
        pass

    # Stall-detection latency: a CPU-smoke gang whose train loop goes
    # silent mid-run (builtins stalling probe), measured through the REAL
    # path — worker beacon → progress report line → watcher ingest →
    # gang detector → anomaly row.  stall_detect_s is (anomaly row
    # created_at − last progress beat), i.e. injection→detection; the
    # budget is the detector threshold plus ingest/poll slack.
    stall_detect_s = None
    stall_detect_ok = None
    alert_fire_latency_s = None
    alert_fire_ok = None
    alert_tick_us = None
    alert_tick_overhead_ok = None
    try:
        import os
        import sys
        import tempfile

        from polyaxon_tpu.orchestrator import Orchestrator

        stall_after_s = 0.6
        knobs = {
            "POLYAXON_TPU_STALL_AFTER_S": str(stall_after_s),
            "POLYAXON_TPU_PROGRESS_INTERVAL_S": "0.05",
            "POLYAXON_TPU_WATCHDOG_INTERVAL_S": "0.05",
            "POLYAXON_TPU_WATCHDOG_FLOOR_S": "0.6",
            "POLYAXON_TPU_WATCHDOG_CEILING_S": "2.0",
            "POLYAXON_TPU_ALERT_INTERVAL_S": "0.05",
        }
        saved_env = {k: os.environ.get(k) for k in knobs}
        os.environ.update(knobs)
        orch = Orchestrator(
            tempfile.mkdtemp(), monitor_interval=0.05, heartbeat_interval=0.2
        )
        try:
            run = orch.submit(
                {
                    "kind": "experiment",
                    "run": {
                        "entrypoint": "polyaxon_tpu.builtins.trainers:stalling"
                    },
                    "declarations": {
                        "warm_steps": 10,
                        "beat_interval": 0.02,
                        "stall_s": 3.0,
                    },
                    "environment": {
                        "topology": {
                            "accelerator": "cpu-1",
                            "num_devices": 1,
                            "num_hosts": 1,
                        }
                    },
                }
            )
            orch.wait(run.id, timeout=120)
            stalls = orch.registry.get_anomalies(run.id, kind="stall")
            prog = orch.registry.get_progress(run.id)
            beats = [r["at"] for r in prog if r.get("at")]
            if stalls and beats:
                # First stall row from either detector (worker watchdog or
                # gang-level), whichever landed first.
                stall_detect_s = stalls[0]["created_at"] - max(
                    b for b in beats if b <= stalls[0]["created_at"]
                )
            # Alert-fire latency rides the same run: injection (last beat)
            # → detector → rule engine tick → FIRING row's fired_at.  The
            # run_stalled row is resolved at teardown but keeps fired_at.
            alerts = orch.registry.get_alerts(run.id, rule="run_stalled")
            if alerts and alerts[0]["fired_at"] and beats:
                fired_at = alerts[0]["fired_at"]
                before = [b for b in beats if b <= fired_at]
                if before:
                    alert_fire_latency_s = fired_at - max(before)
        finally:
            orch.stop()
            for k, v in saved_env.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        if stall_detect_s is not None:
            # Threshold + generous poll/ingest slack; must also fire while
            # the 3s stall is still in progress (else detection is moot).
            stall_detect_ok = 0.0 < stall_detect_s < stall_after_s + 2.5
            if not stall_detect_ok:
                print(
                    f"bench: stall_detect_s={stall_detect_s:.2f} outside "
                    f"budget ({stall_after_s} + 2.5s slack) — stall "
                    "detection is too slow",
                    file=sys.stderr,
                )
        else:
            print(
                "bench: stalling gang produced no stall anomaly row",
                file=sys.stderr,
            )
        if alert_fire_latency_s is not None:
            # Detection budget plus one engine tick of slack: the rule
            # engine rides the detector, it must not add seconds on top.
            alert_fire_ok = 0.0 < alert_fire_latency_s < stall_after_s + 3.0
            if not alert_fire_ok:
                print(
                    f"bench: alert_fire_latency_s={alert_fire_latency_s:.2f} "
                    f"outside budget ({stall_after_s} + 3.0s slack) — the "
                    "alert engine lags its detector",
                    file=sys.stderr,
                )
        else:
            print(
                "bench: stalling gang produced no firing run_stalled alert",
                file=sys.stderr,
            )

        # Idle-tick overhead: one full catalog evaluation over a healthy
        # run (no open alerts) must stay in microsecond territory — it
        # rides every monitor tick for every live gang forever.
        import pathlib

        from polyaxon_tpu.db.registry import RunRegistry
        from polyaxon_tpu.monitor.alerts import AlertEngine
        from polyaxon_tpu.stats.backends import MemoryStats

        idle_reg = RunRegistry(
            pathlib.Path(tempfile.mkdtemp()) / "bench-alerts.db"
        )
        try:
            idle_run = idle_reg.create_run(
                {
                    "kind": "experiment",
                    "run": {"entrypoint": "noop:main"},
                    "environment": {
                        "topology": {"accelerator": "cpu", "num_devices": 1}
                    },
                }
            )
            idle_engine = AlertEngine(
                idle_reg, stats=MemoryStats(), interval_s=0
            )
            idle_engine.evaluate(idle_run.id)  # warm sqlite/caches
            n_ticks = 200
            t0 = time.perf_counter()
            for _ in range(n_ticks):
                idle_engine.evaluate(idle_run.id)
            alert_tick_us = (time.perf_counter() - t0) / n_ticks * 1e6
        finally:
            idle_reg.close()
        alert_tick_overhead_ok = alert_tick_us < 5000.0
        if not alert_tick_overhead_ok:
            print(
                f"bench: alert_tick_us={alert_tick_us:.1f} over the 5ms "
                "budget — rule evaluation is taxing the monitor loop",
                file=sys.stderr,
            )
    except Exception:
        import sys
        import traceback

        traceback.print_exc(file=sys.stderr)

    # On-demand profiling round trip, measured through the REAL path:
    # request_profile → command file in the worker mailbox → heartbeat
    # poll → windowed jax trace in the live train loop → capture report
    # line → watcher ingest → COMPLETE command row.  The budget covers
    # one heartbeat of delivery latency, the 3-step window, and ingest
    # slack.  Alongside it, the idle cost of the bus itself: a mailbox
    # poll with nothing queued must be microseconds — it rides every
    # worker heartbeat forever.
    profile_roundtrip_s = None
    profile_roundtrip_ok = None
    idle_bus_poll_us = None
    idle_bus_overhead_ok = None
    try:
        import sys
        import tempfile

        from polyaxon_tpu.db.registry import CommandStatus
        from polyaxon_tpu.orchestrator import Orchestrator
        from polyaxon_tpu.tracking.capture import CaptureAgent

        # Idle-bus overhead first (no gang needed): poll an empty mailbox
        # the way the Reporter heartbeat does.
        import pathlib

        idle_dir = pathlib.Path(tempfile.mkdtemp()) / "proc0"
        idle_dir.mkdir(parents=True)
        idle_agent = CaptureAgent().configure(
            reporter=None, mailbox=idle_dir, profiles_root=None, process_id=0
        )
        n_polls = 2000
        t0 = time.perf_counter()
        for _ in range(n_polls):
            idle_agent.poll()
        idle_bus_poll_us = (time.perf_counter() - t0) / n_polls * 1e6
        idle_bus_overhead_ok = idle_bus_poll_us < 500.0
        if not idle_bus_overhead_ok:
            print(
                f"bench: idle_bus_poll_us={idle_bus_poll_us:.1f} over the "
                "500us budget — the command mailbox is taxing every "
                "worker heartbeat",
                file=sys.stderr,
            )

        orch = Orchestrator(
            tempfile.mkdtemp(), monitor_interval=0.05, heartbeat_interval=0.2
        )
        try:
            run = orch.submit(
                {
                    "kind": "experiment",
                    "run": {
                        "entrypoint": "polyaxon_tpu.builtins.trainers:lm_train"
                    },
                    "declarations": {
                        "steps": 4000,
                        "batch": 4,
                        "seq": 64,
                        "vocab_size": 256,
                        "d_model": 64,
                        "n_layers": 2,
                        "n_heads": 4,
                        "head_dim": 16,
                        "d_ff": 128,
                    },
                    "environment": {
                        "topology": {
                            "accelerator": "cpu-1",
                            "num_devices": 1,
                            "num_hosts": 1,
                        }
                    },
                }
            )
            deadline = time.time() + 240
            stepping = False
            while time.time() < deadline:
                orch.pump(0.05)
                r = orch.registry.get_run(run.id)
                if r.is_done:
                    break
                prog = orch.registry.get_progress(run.id)
                if r.status == "running" and prog and prog[0]["step"] >= 1:
                    stepping = True
                    break
            if stepping:
                t0 = time.perf_counter()
                cmd = orch.request_profile(run.id, num_steps=3)
                deadline = time.time() + 60
                while time.time() < deadline:
                    orch.pump(0.05)
                    row = orch.registry.get_command(cmd["uuid"])
                    if row["status"] in CommandStatus.TERMINAL:
                        break
                if row["status"] == CommandStatus.COMPLETE:
                    caps = orch.registry.get_captures(
                        run.id, capture_id=cmd["capture_id"]
                    )
                    if caps and caps[0]["artifacts"]:
                        profile_roundtrip_s = time.perf_counter() - t0
                orch.stop_run(run.id)
                orch.wait(run.id, timeout=120)
        finally:
            orch.stop()
        if profile_roundtrip_s is not None:
            # heartbeat delivery (0.2s) + 3-step window + ingest slack.
            profile_roundtrip_ok = 0.0 < profile_roundtrip_s < 10.0
            if not profile_roundtrip_ok:
                print(
                    f"bench: profile_roundtrip_s={profile_roundtrip_s:.2f} "
                    "over the 10s budget — on-demand capture is too slow "
                    "to be an incident tool",
                    file=sys.stderr,
                )
        else:
            print(
                "bench: profile round trip produced no completed capture",
                file=sys.stderr,
            )
    except Exception:
        import sys
        import traceback

        traceback.print_exc(file=sys.stderr)

    # Ledger ground-truth check: run an lm_train smoke gang through the
    # REAL platform path (worker ledger → report line → watcher ingest →
    # goodput roll-up) and compare the platform's MFU against this
    # benchmark's own out-of-band computation for the same run (reported
    # tokens/s × analytic FLOPs/token ÷ the shared peak table).  Budget-
    # asserted like trace_overhead_pct, so the in-product number can
    # never silently drift from the benchmark's accounting.  The two
    # measure slightly different windows (the ledger's wall clock
    # includes model build + compile; reported tokens/s is loop-only), so
    # the budget is absolute-error with compile-amortization slack.
    reported_mfu_abs_err = None
    reported_mfu_ok = None
    first_step_s_cold = None
    first_step_s_warm = None
    first_step_warm_ok = None
    warm_cache_hits = None
    try:
        import sys
        import tempfile

        from polyaxon_tpu.monitor.watcher import goodput_status
        from polyaxon_tpu.orchestrator import Orchestrator

        orch = Orchestrator(
            tempfile.mkdtemp(), monitor_interval=0.05, heartbeat_interval=0.2
        )
        try:
            smoke_spec = {
                "kind": "experiment",
                "run": {
                    "entrypoint": "polyaxon_tpu.builtins.trainers:lm_train"
                },
                "declarations": {
                    "steps": 30,
                    "batch": 4,
                    "seq": 64,
                    "vocab_size": 256,
                    "d_model": 64,
                    "n_layers": 2,
                    "n_heads": 4,
                    "head_dim": 16,
                    "d_ff": 128,
                },
                "environment": {
                    "topology": {
                        "accelerator": "cpu-1",
                        "num_devices": 1,
                        "num_hosts": 1,
                    },
                    # The A/B needs a cache that starts EMPTY: the spec
                    # places both gangs' compile cache in a fresh dir
                    # (the program itself never moves it).
                    "env_vars": {
                        "JAX_COMPILATION_CACHE_DIR": tempfile.mkdtemp()
                    },
                },
            }
            run = orch.submit(smoke_spec)
            orch.wait(run.id, timeout=300)
            g = goodput_status(orch.registry, run.id)
            last = orch.registry.get_run(run.id).last_metric or {}
            # Cold/warm A/B on the SAME store layout: the first gang
            # compiled fresh and wrote the persistent compile cache; a
            # second, identical gang is a NEW worker process that should
            # load its step executable from disk instead of compiling.
            # first_step_s (AOT compile/cache-load + first step wall) is
            # the cold-start metric; the warm run must be materially
            # below the cold one and its ledger must show cache hits.
            run2 = orch.submit(smoke_spec)
            orch.wait(run2.id, timeout=300)
            g2 = goodput_status(orch.registry, run2.id)
            last2 = orch.registry.get_run(run2.id).last_metric or {}
        finally:
            orch.stop()
        first_step_s_cold = last.get("first_step_s")
        first_step_s_warm = last2.get("first_step_s")
        warm_cache_hits = g2.get("compile_cache_hits")
        if first_step_s_cold and first_step_s_warm:
            # Budget: the warm restart must recoup a real fraction of the
            # cold compile bill (cache load + dispatch isn't free, so not
            # ~0 — but well under a fresh compile).
            first_step_warm_ok = (
                first_step_s_warm < 0.8 * first_step_s_cold
                and (warm_cache_hits or 0) > 0
            )
            if not first_step_warm_ok:
                print(
                    f"bench: warm first_step_s={first_step_s_warm:.3f} "
                    f"(cache hits={warm_cache_hits}) did not materially "
                    f"beat cold first_step_s={first_step_s_cold:.3f} — "
                    "the persistent compile cache is not being reused "
                    "across worker processes",
                    file=sys.stderr,
                )
        if g["rows"] and g["wall_s"] > 0 and last.get("tokens_per_s"):
            # The smoke gang runs on `accelerator: cpu`, which has no
            # peak (the ledger's own MFU is 0.0 there): both sides are
            # divided by the same reference rate so the check exercises
            # the FLOP accounting and the error reads in MFU units.
            smoke_peak = PEAK_FLOPS["TPU v5 lite"] * max(1, g["devices"])
            platform_mfu = g["mfu"] or g["flops"] / (g["wall_s"] * smoke_peak)
            smoke_cfg = TransformerConfig(
                vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                head_dim=16, d_ff=128, max_seq=64,
            )
            smoke_fpt = (
                6 * smoke_cfg.n_params
                + 12 * smoke_cfg.n_layers * smoke_cfg.n_heads
                * smoke_cfg.head_dim * 64
            )
            bench_mfu = last["tokens_per_s"] * smoke_fpt / smoke_peak
            reported_mfu_abs_err = abs(platform_mfu - bench_mfu)
            mfu_budget = 0.15 if on_tpu else 0.05
            reported_mfu_ok = reported_mfu_abs_err <= mfu_budget
            if not reported_mfu_ok:
                print(
                    f"bench: reported_mfu_abs_err={reported_mfu_abs_err:.4f} "
                    f"exceeds the {mfu_budget} budget — the platform ledger "
                    "disagrees with the benchmark's MFU accounting",
                    file=sys.stderr,
                )
        else:
            print(
                "bench: lm_train smoke gang produced no usable ledger "
                f"roll-up (rows={g['rows']}, wall={g['wall_s']:.2f}, "
                f"tokens_per_s={last.get('tokens_per_s')})",
                file=sys.stderr,
            )
    except Exception:
        import sys
        import traceback

        traceback.print_exc(file=sys.stderr)

    # Goodput under injected failures: the remediation loop's reason to
    # exist, A/B'd through the REAL platform path.  Run A declares
    # save_every + restart_policy and gets SIGKILLed mid-loop; the
    # scheduler must auto-resume it from the latest complete async
    # checkpoint.  Run B is the no-remediation baseline (engine off, no
    # checkpoints): the blind restart re-pays every step.  The ratio is
    # step-accounted (useful steps / executed steps) so it is
    # deterministic under CI timing noise; recovery_s (failure decision →
    # back to RUNNING) carries the wall-clock side separately.
    run_goodput_ratio = None
    run_goodput_ok = None
    run_goodput_ratio_norestart = None
    recovery_s = None
    try:
        import os
        import sys
        import tempfile

        from polyaxon_tpu.db.registry import RemediationStatus
        from polyaxon_tpu.lifecycles import StatusOptions
        from polyaxon_tpu.orchestrator import Orchestrator

        fail_steps, fail_preempt = 24, 12

        def failure_spec(save_every):
            decls = {
                "steps": fail_steps,
                "preempt_step": fail_preempt,
                "batch": 4,
                "seq": 16,
                "vocab_size": 64,
                "d_model": 32,
                "n_layers": 1,
                "n_heads": 2,
                "head_dim": 16,
                "d_ff": 64,
            }
            if save_every:
                decls["save_every"] = save_every
            return {
                "kind": "experiment",
                "run": {
                    "entrypoint": "polyaxon_tpu.builtins.trainers:lm_train"
                },
                "declarations": decls,
                "environment": {
                    "topology": {
                        "accelerator": "cpu-1",
                        "num_devices": 1,
                        "num_hosts": 1,
                    },
                    "restart_policy": {
                        "max_restarts": 1,
                        "backoff_seconds": 0.1,
                    },
                },
            }

        saved_rem_env = os.environ.get("POLYAXON_TPU_REMEDIATION_ENABLED")
        orch = Orchestrator(
            tempfile.mkdtemp(), monitor_interval=0.05, heartbeat_interval=0.2
        )
        try:
            os.environ["POLYAXON_TPU_REMEDIATION_ENABLED"] = "1"
            run_a = orch.submit(failure_spec(save_every=1))
            done_a = orch.wait(run_a.id, timeout=300)
            if done_a.status == StatusOptions.SUCCEEDED:
                rows = [
                    r
                    for r in orch.registry.get_remediations(
                        run_a.id, action="resume"
                    )
                    if r["status"] == RemediationStatus.SUCCEEDED
                ]
                from_step = (
                    rows[0]["attrs"].get("from_step") if rows else None
                )
                # Attempt 1 executed steps [0, preempt); attempt 2
                # resumed at from_step+1 and executed the rest.
                executed = fail_preempt + fail_steps
                if from_step is not None:
                    executed -= int(from_step) + 1
                run_goodput_ratio = fail_steps / max(1, executed)
                history = orch.registry.get_statuses(run_a.id)
                warn_ts = next(
                    (
                        s["created_at"]
                        for s in history
                        if s["status"] == StatusOptions.WARNING
                    ),
                    None,
                )
                if warn_ts is not None:
                    back = [
                        s["created_at"]
                        for s in history
                        if s["status"] == StatusOptions.RUNNING
                        and s["created_at"] > warn_ts
                    ]
                    if back:
                        recovery_s = back[0] - warn_ts
            else:
                print(
                    "bench: remediated run under injected failure did not "
                    f"complete (status={done_a.status})",
                    file=sys.stderr,
                )
            # Baseline: engine off, nothing to resume from — the restart
            # re-executes the whole schedule.
            os.environ["POLYAXON_TPU_REMEDIATION_ENABLED"] = "0"
            run_b = orch.submit(failure_spec(save_every=0))
            done_b = orch.wait(run_b.id, timeout=300)
            if done_b.status == StatusOptions.SUCCEEDED:
                run_goodput_ratio_norestart = fail_steps / (
                    fail_preempt + fail_steps
                )
        finally:
            orch.stop()
            if saved_rem_env is None:
                os.environ.pop("POLYAXON_TPU_REMEDIATION_ENABLED", None)
            else:
                os.environ["POLYAXON_TPU_REMEDIATION_ENABLED"] = saved_rem_env
        if run_goodput_ratio is not None:
            run_goodput_ok = run_goodput_ratio >= 0.5
            if not run_goodput_ok:
                print(
                    f"bench: run_goodput_ratio={run_goodput_ratio:.2f} under "
                    "the 0.5 floor — auto-resume is re-paying too much work "
                    "after an injected failure",
                    file=sys.stderr,
                )
    except Exception:
        import sys
        import traceback

        traceback.print_exc(file=sys.stderr)

    # Serving: the continuous-batching engine under CONCURRENT load vs the
    # same requests one-at-a-time through generate().  Decode is
    # memory-bound, so a batched slot step costs about what a B=1 step
    # does — the engine turns that slack into throughput.  Both sides are
    # warmed first (per prompt-length bucket) so this measures steady
    # state, not compilation.
    serving = None
    serving_ready_s = None
    try:
        from polyaxon_tpu.models import decode as decode_mod
        from polyaxon_tpu.serving import ServingEngine

        if on_tpu:
            scfg = TransformerConfig(
                vocab_size=32768,
                d_model=1024,
                n_layers=8,
                n_heads=16,
                head_dim=64,
                d_ff=4096,
                max_seq=1024,
            )
            n_req, max_new, slots = 16, 64, 8
        else:
            scfg = TransformerConfig(
                vocab_size=256,
                d_model=64,
                n_layers=2,
                n_heads=4,
                head_dim=16,
                d_ff=128,
                max_seq=128,
                dtype=jnp.float32,
            )
            n_req, max_new, slots = 8, 24, 4
        sparams = init_params(jax.random.PRNGKey(1), scfg)
        lengths = [6, 10, 14]
        prompts = [
            [int(x) for x in rng.integers(0, scfg.vocab_size, lengths[i % 3])]
            for i in range(n_req)
        ]
        # Offline reference: generate() jitted whole — the full decode
        # scan fused in one device call, no streaming, no admission.  An
        # upper bound the serving loop (which must return to the host
        # every step to stream tokens and admit work) does not get to
        # match; reported for context, not gated.
        import functools

        gen = jax.jit(
            functools.partial(
                decode_mod.generate, cfg=scfg, max_new_tokens=max_new
            )
        )
        for t in lengths:
            np.asarray(gen(sparams, jnp.asarray([[1] * t])))
        t0 = time.perf_counter()
        for p in prompts:
            np.asarray(gen(sparams, jnp.asarray([p])))
        offline_dt = time.perf_counter() - t0
        # Serving comparison — same regime both sides (per-step host loop,
        # streaming, admission): the SAME engine serving the SAME list
        # one-request-at-a-time vs all-at-once.  The delta is what
        # continuous batching itself buys.
        eng = ServingEngine(sparams, scfg, slots=slots, max_len=scfg.max_seq)
        t0 = time.perf_counter()
        eng.start()
        # Readiness gate: start() warms the whole bucket family in the
        # scheduler thread; ready means the first request compiles
        # nothing.  With the persistent cache primed by an earlier
        # process this is a disk load, not a compile.
        eng.wait_ready(timeout=600)
        serving_ready_s = time.perf_counter() - t0
        try:
            for t in lengths:
                eng.submit([1] * t, 2).wait(timeout=600)
            t0 = time.perf_counter()
            for p in prompts:
                eng.submit(p, max_new).wait(timeout=600)
            seq_dt = time.perf_counter() - t0
            t0 = time.perf_counter()
            reqs = [eng.submit(p, max_new) for p in prompts]
            for r in reqs:
                r.wait(timeout=600)
            conc_dt = time.perf_counter() - t0
        finally:
            eng.stop()
        # Fully-quantized serving: int8 weights (the PR 6 streaming path)
        # AND an int8 KV pool (rows + per-row scales) — same prompts,
        # same concurrent burst.  Decode is HBM-bandwidth-bound, so on
        # real hardware the int8 stream is the throughput story; on the
        # CPU smoke this is a correctness/steady-state check and the pool
        # byte ratio is the claim that transfers.
        qweights = decode_mod.quantize_weights(sparams)
        eng8 = ServingEngine(
            sparams, scfg, slots=slots, max_len=scfg.max_seq,
            qweights=qweights, kv_quantize="int8",
        ).start()
        eng8.wait_ready(timeout=600)
        try:
            for t in lengths:
                eng8.submit([1] * t, 2).wait(timeout=600)
            t0 = time.perf_counter()
            reqs = [eng8.submit(p, max_new) for p in prompts]
            for r in reqs:
                r.wait(timeout=600)
            conc8_dt = time.perf_counter() - t0
            int8_steady = eng8.stats()["steady_state_compiles"]
        finally:
            eng8.stop()
        total = n_req * max_new
        serving = {
            "tokens_per_s": round(total / conc_dt),
            "sequential_tokens_per_s": round(total / seq_dt),
            "speedup": round(seq_dt / conc_dt, 2),
            "offline_generate_tokens_per_s": round(total / offline_dt),
            "tokens_per_s_int8": round(total / conc8_dt),
            "int8_vs_f32": round(conc_dt / conc8_dt, 2),
            "kv_pool_bytes": eng.kv_pool_bytes,
            "kv_pool_bytes_int8": eng8.kv_pool_bytes,
            "kv_pool_ratio": round(eng8.kv_pool_bytes / eng.kv_pool_bytes, 3),
            "int8_steady_state_compiles": int8_steady,
            "n_requests": n_req,
            "slots": slots,
            "ready_s": round(serving_ready_s, 3),
        }
    except Exception:
        import sys
        import traceback

        traceback.print_exc(file=sys.stderr)

    # Serving under LOAD: the same engine driven by Poisson arrivals at a
    # calibrated offered rate, chunked prefill vs full-prompt prefill.
    # The instant-burst number above can't see head-of-line blocking: a
    # short prompt that ARRIVES while a long prompt's monolithic prefill
    # is on the device waits the whole thing out; with chunked prefill it
    # waits at most one chunk (chunk boundaries are preemption points for
    # the shortest-remaining-first prefill queue).  Both sides get the
    # IDENTICAL arrival schedule (same seed), warmed pad buckets, and the
    # prefix cache off so the second run can't ride the first's KV.
    # The headline TTFT percentiles are over the SHORT (interactive)
    # class: chunking deliberately trades the long prompt's own TTFT for
    # everyone else's, so all-requests percentiles at small n are just
    # the slowest long both ways (bench-notes.md has the methodology).
    serving_loaded = None
    try:
        from polyaxon_tpu.serving import ServingEngine
        from polyaxon_tpu.serving.loadgen import poisson_load

        if on_tpu:
            lcfg, lparams = scfg, sparams
            long_len, short_len = 768, 16
            lmax_new, lchunk, n_loaded, lslots = 32, 128, 24, 8
        else:
            # The tiny smoke config's prefill is microseconds — too fast
            # for arrival overlap to be measurable above timer noise — so
            # the loaded A/B uses a config whose full-prompt prefill costs
            # real milliseconds on CPU.
            lcfg = TransformerConfig(
                vocab_size=256,
                d_model=256,
                n_layers=2,
                n_heads=4,
                head_dim=64,
                d_ff=1024,
                max_seq=512,
                dtype=jnp.float32,
            )
            lparams = init_params(jax.random.PRNGKey(2), lcfg)
            # 8 slots so admission never bottlenecks (a long request holds
            # its slot for its whole prefill; the A/B should measure
            # prefill head-of-line blocking, not slot scarcity).
            long_len, short_len = 480, 8
            lmax_new, lchunk, n_loaded, lslots = 4, 128, 24, 8
        loaded_prompts = [
            [
                int(x)
                for x in rng.integers(
                    0,
                    lcfg.vocab_size,
                    long_len if i % 3 == 0 else short_len,
                )
            ]
            for i in range(n_loaded)
        ]

        def loaded_run(prefill_chunk, rate_rps=None):
            eng = ServingEngine(
                lparams,
                lcfg,
                slots=lslots,
                max_len=lcfg.max_seq,
                prefill_chunk=prefill_chunk,
                prefix_cache=False,
            ).start()
            try:
                # Warm every prefill pad bucket + the decode step.
                for t in (long_len, short_len):
                    eng.submit([1] * t, 2).wait(timeout=600)
                if rate_rps is None:
                    # Calibrate the offered rate once, from this side's
                    # measured sequential service time.  This mix is
                    # PREFILL-bound (prefill is serialized on the device
                    # regardless of slot count), so capacity is ~1/svc,
                    # not slots/svc; offer 60% of it — genuinely loaded,
                    # but queues drain, so TTFT measures head-of-line
                    # blocking rather than raw queueing backlog.
                    t0 = time.perf_counter()
                    for p in loaded_prompts[:3]:
                        eng.submit(p, lmax_new).wait(timeout=600)
                    svc = (time.perf_counter() - t0) / 3
                    rate_rps = 0.6 / svc
                res = poisson_load(
                    eng,
                    loaded_prompts,
                    lmax_new,
                    rate_rps=rate_rps,
                    seed=17,
                )
            finally:
                eng.stop()
            return res, rate_rps

        full_res, lrate = loaded_run(None)
        chunked_res, _ = loaded_run(lchunk, rate_rps=lrate)

        from polyaxon_tpu.serving.loadgen import _pct

        def short_pct(res, q):
            vals = sorted(
                t
                for i, t in enumerate(res["ttft_s"])
                if i % 3 != 0 and t is not None
            )
            return _pct(vals, q)

        def long_mean(res):
            vals = [
                t
                for i, t in enumerate(res["ttft_s"])
                if i % 3 == 0 and t is not None
            ]
            return round(float(np.mean(vals)), 6) if vals else 0.0

        c_p99, f_p99 = short_pct(chunked_res, 99), short_pct(full_res, 99)
        serving_loaded = {
            "ttft_p99_s": c_p99,
            "ttft_p50_s": short_pct(chunked_res, 50),
            "tokens_per_s_loaded": chunked_res["tokens_per_s"],
            "full_prefill_ttft_p99_s": f_p99,
            "full_prefill_ttft_p50_s": short_pct(full_res, 50),
            "full_prefill_tokens_per_s": full_res["tokens_per_s"],
            "ttft_p99_speedup": (
                round(f_p99 / c_p99, 2) if c_p99 > 0 else None
            ),
            # The other side of the trade, reported so it can't hide:
            # the long prompts' own TTFT, which chunking makes WORSE.
            "long_ttft_mean_s": long_mean(chunked_res),
            "full_prefill_long_ttft_mean_s": long_mean(full_res),
            "all_ttft_p99_s": chunked_res["ttft_p99_s"],
            "full_prefill_all_ttft_p99_s": full_res["ttft_p99_s"],
            "offered_rps": round(lrate, 2),
            "prefill_chunk": lchunk,
            "n_requests": n_loaded,
            "completed": [chunked_res["completed"], full_res["completed"]],
            "errors": [chunked_res["errors"], full_res["errors"]],
        }
    except Exception:
        import sys
        import traceback

        traceback.print_exc(file=sys.stderr)

    # Fixed-HBM A/B: the int8 KV pool's CAPACITY claim under load.  Same
    # mix, same Poisson schedule, but the pool is now the binding
    # resource: the f32 side gets ~1.5 long-request spans of blocks, so
    # two longs in flight contend (park, or shed on true deadlock); the
    # int8 side gets the SAME byte budget, which at (d+4) vs 4d bytes
    # per head-row holds >2x the blocks (``decode.kv_block_bytes`` is
    # the sizing primitive, test-pinned to the real leaf nbytes).
    # Completions / tokens-per-s / parks at equal HBM are the honest
    # comparison — this is "double the live batch at a fixed memory
    # budget" measured rather than asserted.
    serving_int8_kv = None
    try:
        if serving_loaded is None:
            raise RuntimeError(
                "loaded serving section did not run; skipping fixed-HBM A/B"
            )
        from polyaxon_tpu.models import decode as decode_mod

        ab_bs = 16
        span = -(-(long_len + lmax_new) // ab_bs)  # blocks one long spans
        kv_blocks_f32 = 1 + span + span // 2
        budget = kv_blocks_f32 * decode_mod.kv_block_bytes(lcfg, ab_bs)
        kv_blocks_int8 = int(
            budget // decode_mod.kv_block_bytes(lcfg, ab_bs, "int8")
        )

        def fixed_hbm_run(num_blocks, kv_quantize):
            eng = ServingEngine(
                lparams, lcfg, slots=lslots, max_len=lcfg.max_seq,
                block_size=ab_bs, num_blocks=num_blocks,
                prefill_chunk=lchunk, prefix_cache=False,
                kv_quantize=kv_quantize,
            ).start()
            try:
                for t in (long_len, short_len):
                    eng.submit([1] * t, 2).wait(timeout=600)
                res = poisson_load(
                    eng, loaded_prompts, lmax_new, rate_rps=lrate, seed=23
                )
                res["block_parks"] = eng.stats()["block_parks"]
                res["kv_pool_bytes"] = eng.kv_pool_bytes
            finally:
                eng.stop()
            return res

        ab_f32 = fixed_hbm_run(kv_blocks_f32, None)
        ab_int8 = fixed_hbm_run(kv_blocks_int8, "int8")
        serving_int8_kv = {  # [f32 pool, int8 pool] at equal pool bytes
            "kv_blocks": [kv_blocks_f32, kv_blocks_int8],
            "pool_bytes": [
                ab_f32["kv_pool_bytes"], ab_int8["kv_pool_bytes"]
            ],
            "tokens_per_s": [
                ab_f32["tokens_per_s"], ab_int8["tokens_per_s"]
            ],
            "completed": [ab_f32["completed"], ab_int8["completed"]],
            "errors": [ab_f32["errors"], ab_int8["errors"]],
            "block_parks": [ab_f32["block_parks"], ab_int8["block_parks"]],
            "ttft_p99_s": [ab_f32["ttft_p99_s"], ab_int8["ttft_p99_s"]],
            "offered_rps": round(lrate, 2),
            "n_requests": n_loaded,
        }
    except Exception:
        import sys
        import traceback

        traceback.print_exc(file=sys.stderr)

    # Speculative-decoding A/B: templated (high n-gram self-overlap)
    # traffic through the same engine twice — spec off, then on — on the
    # identical Poisson schedule, offered above both arms' capacity so
    # tokens/s measures service capacity rather than the offered rate.
    # Decode-heavy on purpose (short prompts, LONG generations):
    # speculation buys nothing during prefill, and on a random-init
    # model the drafter's acceptance comes from greedy continuations
    # settling into short cycles ~100 tokens in, so the generation must
    # run long enough for the predictable tail to dominate — bench-notes
    # has the full methodology and why that mechanism is the honest CPU
    # stand-in for real templated traffic.  Greedy only (the engine's
    # spec scope), prefix cache off so arm 2 can't ride arm 1's KV,
    # warmup=True so the K-bucketed verify family compiles BEFORE the
    # clock starts — the same zero-steady-state-compiles discipline the
    # engine tests pin.  Gates on the tokens/s ratio AND unchanged
    # completion/error accounting: a speedup that drops requests is a
    # bug, not a win.
    serving_spec_decode = None
    try:
        from polyaxon_tpu.serving import ServingEngine
        from polyaxon_tpu.serving.loadgen import (
            poisson_load,
            templated_prompts,
        )

        # Small vocab + seed-0 params: the combination whose greedy
        # continuations reliably reach short cycles within the window.
        spec_cfg = TransformerConfig(
            vocab_size=64,
            d_model=64,
            n_layers=2,
            n_heads=4,
            head_dim=16,
            d_ff=256,
            max_seq=512,
            dtype=jnp.float32,
        )
        spec_params = init_params(jax.random.PRNGKey(0), spec_cfg)
        spec_max_new, spec_k, spec_slots = 448, 4, 4
        spec_prompts = templated_prompts(16, spec_cfg.vocab_size, seed=11)

        def spec_run(spec_on, rate_rps=None):
            eng = ServingEngine(
                spec_params, spec_cfg, slots=spec_slots,
                max_len=spec_cfg.max_seq, prefill_chunk=128,
                prefix_cache=False, warmup=True,
                spec_decode=spec_on, spec_k=spec_k, spec_min_ngram=2,
            ).start()
            try:
                if not eng.wait_ready(timeout=600):
                    raise RuntimeError("spec A/B engine warmup timed out")
                if rate_rps is None:
                    # Calibrate once, on THIS (spec-off) side: single-
                    # stream service time svc makes slots/svc the batch
                    # capacity ceiling; offer 2x that so both arms stay
                    # saturated and the makespan is service-bound.
                    t0 = time.perf_counter()
                    for p in spec_prompts[:3]:
                        eng.submit(p, spec_max_new).wait(timeout=600)
                    svc = (time.perf_counter() - t0) / 3
                    rate_rps = 2.0 * spec_slots / svc
                res = poisson_load(
                    eng, spec_prompts, spec_max_new,
                    rate_rps=rate_rps, seed=29,
                )
                s = eng.stats()
                res["spec_accept_rate"] = s["spec_accept_rate"]
                res["steady_state_compiles"] = s["steady_state_compiles"]
            finally:
                eng.stop()
            return res, rate_rps

        spec_off, spec_rate = spec_run(False)
        spec_on, _ = spec_run(True, rate_rps=spec_rate)
        spec_speedup = (
            round(spec_on["tokens_per_s"] / spec_off["tokens_per_s"], 3)
            if spec_off["tokens_per_s"] > 0
            else None
        )
        accounting_ok = (
            spec_on["completed"] == spec_off["completed"]
            and spec_on["errors"] == spec_off["errors"] == 0
        )
        serving_spec_decode = {  # [spec off, spec on]
            "tokens_per_s": [
                spec_off["tokens_per_s"], spec_on["tokens_per_s"]
            ],
            "speedup": spec_speedup,
            "speedup_ok": (
                spec_speedup is not None and spec_speedup >= 1.5
            ),
            "accounting_ok": accounting_ok,
            "completed": [spec_off["completed"], spec_on["completed"]],
            "errors": [spec_off["errors"], spec_on["errors"]],
            "spec_accept_rate": spec_on["spec_accept_rate"],
            "steady_state_compiles": [
                spec_off["steady_state_compiles"],
                spec_on["steady_state_compiles"],
            ],
            "spec_k": spec_k,
            "max_new_tokens": spec_max_new,
            "offered_rps": round(spec_rate, 2),
            "n_requests": len(spec_prompts),
        }
        if not (serving_spec_decode["speedup_ok"] and accounting_ok):
            import sys

            print(
                f"bench: serving_spec_decode gate failed: {serving_spec_decode}",
                file=sys.stderr,
            )
    except Exception:
        import sys
        import traceback

        traceback.print_exc(file=sys.stderr)

    # Hierarchical KV A/B: the host offload tier's AVAILABILITY claim at
    # fixed HBM.  Same mix and seeded Poisson schedule as the loaded
    # section, but the pool is ONE long span + one block — any two longs
    # in flight (or a long mid-prefill beside a parked decoder) want ~2x
    # the pool.  Offload OFF, a parked sequence sits on its blocks and
    # contention resolves by deadlock-shedding; offload ON, parking
    # SPILLS the blocks to pinned host memory, so the same schedule
    # absorbs with zero sheds — oversubscription now costs restore
    # latency (the bounded-TTFT number) instead of availability.
    # warmup=True so the export/import fns compile before the clock
    # starts: the gate includes steady_state_compiles == 0 WITH
    # spill/restore active.
    serving_kv_offload = None
    try:
        if serving_loaded is None:
            raise RuntimeError(
                "loaded serving section did not run; skipping KV offload A/B"
            )
        ob_bs = 16
        ospan = -(-(long_len + lmax_new) // ob_bs)  # blocks one long spans
        kv_blocks_sub = 1 + ospan + 1  # trash + one long span + one block
        # 2x the loaded section's calibrated rate: the pool-contention
        # window (two longs in flight) must open RELIABLY, not by
        # arrival luck — at 0.6 utilization the off arm can dodge it.
        orate = 2.0 * lrate

        def offload_run(kv_offload):
            eng = ServingEngine(
                lparams, lcfg, slots=lslots, max_len=lcfg.max_seq,
                block_size=ob_bs, num_blocks=kv_blocks_sub,
                prefill_chunk=lchunk, prefix_cache=False, warmup=True,
                kv_offload=kv_offload,
            ).start()
            try:
                if not eng.wait_ready(timeout=600):
                    raise RuntimeError("KV offload A/B warmup timed out")
                res = poisson_load(
                    eng, loaded_prompts, lmax_new, rate_rps=orate, seed=23
                )
                s = eng.stats()
                for k in (
                    "block_parks",
                    "host_spilled_blocks_total",
                    "host_restored_blocks_total",
                    "steady_state_compiles",
                ):
                    res[k] = s[k]
            finally:
                eng.stop()
            return res

        kv_off = offload_run(False)
        kv_on = offload_run(True)
        usable = kv_blocks_sub - 1
        serving_kv_offload = {  # [offload off, offload on]
            "kv_blocks": kv_blocks_sub,
            "long_span_blocks": ospan,
            "oversubscription_x": round(2 * ospan / usable, 2),
            "completed": [kv_off["completed"], kv_on["completed"]],
            "sheds": [kv_off["sheds"], kv_on["sheds"]],
            "errors": [kv_off["errors"], kv_on["errors"]],
            "block_parks": [
                kv_off["block_parks"], kv_on["block_parks"]
            ],
            "spilled_blocks": [
                kv_off["host_spilled_blocks_total"],
                kv_on["host_spilled_blocks_total"],
            ],
            "restored_blocks": [
                kv_off["host_restored_blocks_total"],
                kv_on["host_restored_blocks_total"],
            ],
            "ttft_p99_s": [kv_off["ttft_p99_s"], kv_on["ttft_p99_s"]],
            "tokens_per_s": [
                kv_off["tokens_per_s"], kv_on["tokens_per_s"]
            ],
            "steady_state_compiles": [
                kv_off["steady_state_compiles"],
                kv_on["steady_state_compiles"],
            ],
            "zero_sheds_ok": (
                kv_on["sheds"] == 0
                and kv_on["errors"] == 0
                and kv_on["completed"] == n_loaded
            ),
            "spill_active_ok": (
                kv_on["host_spilled_blocks_total"] > 0
                and kv_on["steady_state_compiles"] == 0
            ),
            "offered_rps": round(orate, 2),
            "n_requests": n_loaded,
        }
        if not (
            serving_kv_offload["zero_sheds_ok"]
            and serving_kv_offload["spill_active_ok"]
        ):
            import sys

            print(
                f"bench: serving_kv_offload gate failed: {serving_kv_offload}",
                file=sys.stderr,
            )
    except Exception:
        import sys
        import traceback

        traceback.print_exc(file=sys.stderr)

    # Warm replica boot: the persistent prefix store's TTFT claim.  An
    # incumbent engine serves the shared prefixes once and persists its
    # hot prefix blocks on stop; a COLD and a WARM replacement then face
    # the IDENTICAL seeded schedule of prefix+tail traffic.  The warm
    # replica preloaded the prefixes during warmup, so its first
    # requests skip the long prefix prefill — exactly the chaos
    # scale-up scenario (the autoscaler's replacement boots while the
    # fleet is most loaded).  Greedy parity probes pin that the warm KV
    # is the SAME KV: outputs warm vs cold must be token-identical.
    serving_warm_boot = None
    try:
        import tempfile

        if serving_loaded is None:
            raise RuntimeError(
                "loaded serving section did not run; skipping warm-boot A/B"
            )
        wb_bs = 16
        n_pref, pref_len, tail_len, wb_max_new = 2, 240, 8, 4
        wrng = np.random.default_rng(47)
        wb_prefixes = [
            [int(x) for x in wrng.integers(0, lcfg.vocab_size, pref_len)]
            for _ in range(n_pref)
        ]
        wb_prompts = [
            wb_prefixes[i % n_pref]
            + [int(x) for x in wrng.integers(0, lcfg.vocab_size, tail_len)]
            for i in range(12)
        ]
        wb_probe = wb_prefixes[0] + [3, 1, 4, 1, 5, 9, 2, 6]
        wb_blocks = 96  # preload budget (96-1)//2 = 47 >= the 30 stored

        def wb_engine(persist_dir):
            return ServingEngine(
                lparams, lcfg, slots=lslots, max_len=lcfg.max_seq,
                block_size=wb_bs, num_blocks=wb_blocks,
                prefill_chunk=lchunk, prefix_cache=True, warmup=True,
                kv_persist_dir=persist_dir, kv_persist_sig="bench",
                kv_persist_blocks=48,
            )

        with tempfile.TemporaryDirectory() as wb_dir:
            # Incumbent: compute + persist the shared prefixes.
            inc = wb_engine(wb_dir).start()
            try:
                if not inc.wait_ready(timeout=600):
                    raise RuntimeError("warm-boot incumbent warmup timed out")
                t0 = time.perf_counter()
                for pref in wb_prefixes:
                    inc.submit(list(pref), wb_max_new).wait(timeout=600)
                wb_svc = (time.perf_counter() - t0) / n_pref
            finally:
                inc.stop()  # final persist happens here
            wb_rate = 0.6 / wb_svc

            def replacement_run(persist_dir):
                eng = wb_engine(persist_dir).start()
                try:
                    if not eng.wait_ready(timeout=600):
                        raise RuntimeError("warm-boot arm warmup timed out")
                    preloaded = eng.stats()["kv_preloaded_blocks"]
                    res = poisson_load(
                        eng, wb_prompts, wb_max_new,
                        rate_rps=wb_rate, seed=31,
                    )
                    res["kv_preloaded_blocks"] = preloaded
                    res["prefix_cache_hit_rate"] = eng.stats()[
                        "prefix_cache_hit_rate"
                    ]
                    res["probe_tokens"] = eng.submit(
                        list(wb_probe), wb_max_new
                    ).wait(timeout=600)
                finally:
                    eng.stop()
                return res

            cold = replacement_run(None)
            warm = replacement_run(wb_dir)
        token_identical = cold["probe_tokens"] == warm["probe_tokens"]
        serving_warm_boot = {  # [cold boot, warm boot]
            "kv_preloaded_blocks": [
                cold["kv_preloaded_blocks"], warm["kv_preloaded_blocks"]
            ],
            "first_requests_ttft_p99_s": [
                cold["ttft_p99_s"], warm["ttft_p99_s"]
            ],
            "first_requests_ttft_mean_s": [
                cold["ttft_mean_s"], warm["ttft_mean_s"]
            ],
            "prefix_cache_hit_rate": [
                cold["prefix_cache_hit_rate"], warm["prefix_cache_hit_rate"]
            ],
            "completed": [cold["completed"], warm["completed"]],
            "errors": [cold["errors"], warm["errors"]],
            "ttft_p99_speedup": (
                round(cold["ttft_p99_s"] / warm["ttft_p99_s"], 2)
                if warm["ttft_p99_s"] > 0
                else None
            ),
            "token_identical": token_identical,
            "warm_boot_ok": (
                token_identical
                and warm["kv_preloaded_blocks"] > 0
                and cold["kv_preloaded_blocks"] == 0
                and warm["ttft_p99_s"] < cold["ttft_p99_s"]
            ),
            "offered_rps": round(wb_rate, 2),
            "n_requests": len(wb_prompts),
        }
        if not serving_warm_boot["warm_boot_ok"]:
            import sys

            print(
                f"bench: serving_warm_boot gate failed: {serving_warm_boot}",
                file=sys.stderr,
            )
    except Exception:
        import sys
        import traceback

        traceback.print_exc(file=sys.stderr)

    # Training input pipeline: the overlapped hot loop (host prefetch +
    # device prefetch + async metrics, runtime/pipeline.py) vs the same
    # loop fully synchronous, on a dataset-backed image-classifier config.
    # On CPU smoke the fixture lives in page cache and the box may have 1
    # core, so the data path has no REAL latency for overlap to hide —
    # `io_delay_ms` injects a simulated per-batch storage RTT (sleep, no
    # CPU) on the shared thunk stream, applied identically to BOTH sides.
    # On TPU the delay is 0: gather + H2D genuinely overlap device compute.
    train_images = None
    try:
        import tempfile

        from polyaxon_tpu.models import cnn
        from polyaxon_tpu.runtime.data import global_batch_from_host_data
        from polyaxon_tpu.runtime.datasets import (
            DatasetReader,
            make_image_fixture,
        )
        from polyaxon_tpu.runtime.pipeline import MetricsDrain, TrainPipeline

        if on_tpu:
            t_batch, t_img, t_ch = 256, 64, (64, 128, 256)
            t_steps, t_warm, t_examples, io_delay_ms = 40, 5, 8192, 0.0
        else:
            t_batch, t_img, t_ch = 128, 16, (8, 16)
            t_steps, t_warm, t_examples, io_delay_ms = 24, 3, 2048, 15.0
        t_dir = tempfile.mkdtemp()
        make_image_fixture(
            t_dir, "bench-images",
            num_examples=t_examples, image_size=t_img, shards=4, seed=0,
        )
        t_cfg = cnn.CNNConfig(
            image_size=t_img, n_classes=10, channels=t_ch
        )

        def t_loss(p, b):
            images = b["images"].astype(t_cfg.dtype) / 255.0 - 0.5
            return cnn.loss_fn(p, {**b, "images": images}, t_cfg)

        t_ts = build_train_step(
            loss_fn=t_loss,
            init_fn=lambda k: cnn.init_params(k, t_cfg),
            axes_tree=cnn.param_axes(t_cfg),
            optimizer=optax.adamw(1e-3),
            mesh=mesh,
            template=template,
        )

        def t_place(local):
            return global_batch_from_host_data(
                {
                    "images": local["images"],
                    "labels": local["labels"].astype(np.int32),
                },
                t_ts.batch_sharding,
            )

        def t_source(reader):
            for task in reader.batch_tasks(0):
                yield (
                    lambda t=task: (time.sleep(io_delay_ms / 1e3), t())[1]
                    if io_delay_ms
                    else t()
                )

        def t_run(overlap: bool):
            t_params, t_opt = t_ts.init(jax.random.PRNGKey(0))
            reader = DatasetReader(
                t_dir, "bench-images", global_batch=t_batch, seed=0
            )
            pipe = TrainPipeline(
                t_source(reader), t_place,
                prefetch=3 if overlap else 0, workers=2,
            )
            drain = MetricsDrain(lambda s, v: None) if overlap else None
            m = None
            try:
                for _ in range(t_warm):
                    b = next(pipe)
                    t_params, t_opt, m = t_ts.step(t_params, t_opt, b, None)
                jax.block_until_ready(t_params)
                wait0 = pipe.data_wait_s
                t0 = time.perf_counter()
                for i in range(t_steps):
                    b = next(pipe)
                    t_params, t_opt, m = t_ts.step(t_params, t_opt, b, None)
                    # Logging convention per side: the sync loop pays the
                    # host read inline (the old trainers' shape), the
                    # overlapped loop pushes the device array to the drain.
                    if i % 10 == 0:
                        if overlap:
                            drain.push(i, {"loss": m["loss"]})
                        else:
                            float(m["loss"])
                jax.block_until_ready(t_params)  # fence BEFORE timing
                dt = time.perf_counter() - t0
            finally:
                pipe.close()
                if drain is not None:
                    drain.close()
            ips = t_steps * t_batch / dt
            wait_ms = (pipe.data_wait_s - wait0) / t_steps * 1e3
            return ips, wait_ms

        off_ips, off_wait = t_run(False)
        on_ips, on_wait = t_run(True)
        train_images = {
            "images_per_s": round(on_ips),
            "sync_images_per_s": round(off_ips),
            "speedup": round(on_ips / off_ips, 2),
            "data_wait_ms_per_step": round(on_wait, 2),
            "sync_data_wait_ms_per_step": round(off_wait, 2),
            "batch": t_batch,
            "io_delay_ms": io_delay_ms,
        }
    except Exception:
        import sys
        import traceback

        traceback.print_exc(file=sys.stderr)

    # Fleet serving: N real replica subprocesses behind the FleetRouter.
    # Two claims, measured not asserted: (1) aggregate decode throughput
    # scales with replicas (same seeded burst offered to N=1 and N=2 —
    # the router's least-loaded spread is what's under test), and
    # (2) SIGKILLing a replica mid-load loses NOTHING: every request
    # completes (failover) or gets exactly one typed error — zero hangs,
    # zero silent drops — and TTFT recovers once the dead replica is
    # ejected.  The same fleet serves both N=2 arms (throughput first,
    # then the destructive failover arm), so the bench pays 2 boots.
    serving_fleet = None
    serving_fleet_failover = None
    try:
        import os
        import tempfile
        import threading
        from http.server import ThreadingHTTPServer

        from polyaxon_tpu.serving.fleet import LocalServingFleet
        from polyaxon_tpu.serving.loadgen import (
            http_poisson_load,
            shared_prefix_prompts,
        )
        from polyaxon_tpu.serving.router import FleetRouter, make_router_handler

        fmodel = {
            "vocab_size": 64, "d_model": 32, "n_layers": 2,
            "n_heads": 4, "head_dim": 8, "d_ff": 64,
        }
        fl_n_req, fl_max_new = (48, 24) if on_tpu else (24, 16)
        fl_prompts = shared_prefix_prompts(
            fl_n_req, fmodel["vocab_size"],
            prefix_len=8, suffix_len=8, groups=4, seed=11,
        )

        def fleet_warm(fl):
            # One request straight at EVERY replica (bypassing the
            # router) before the timed run: concurrent cold compiles
            # otherwise thrash the host and both arms measure XLA's
            # compile queue instead of the router's spread.
            import urllib.request

            for wname in list(fl._procs):
                wrep = fl.router.replica(wname)
                wbody = json.dumps(
                    {
                        "prompts": [fl_prompts[0]],
                        "max_new_tokens": fl_max_new * 2,
                    }
                ).encode()
                wreq = urllib.request.Request(
                    wrep.base_url + "/generate",
                    data=wbody,
                    headers={"Content-Type": "application/json"},
                )
                with urllib.request.urlopen(wreq, timeout=300) as wr:
                    wr.read()

        def fleet_up(n):
            # Occupancy shedding is OFF for the throughput arms: a shed
            # under the deliberate burst would let the N=1 arm drop work
            # and fake a flat scaleup.  The failover arm re-enables it.
            router = FleetRouter(
                probe_interval_s=0.2, probe_timeout_s=1.0,
                request_timeout_s=300.0, retry_limit=2,
                eject_failures=2, eject_backoff_s=0.5,
                shed_occupancy=1e9,
            )
            fl = LocalServingFleet(
                Path(tempfile.mkdtemp()), fmodel,
                replicas=n, seq=64, slots=4, seed=0, router=router,
                env={"POLYAXON_TPU_SERVING_WARMUP": "0"},
            )
            fl.start()
            if not fl.wait_ready(timeout_s=180):
                fl.stop()
                raise RuntimeError(f"{n}-replica fleet never became ready")
            handler = make_router_handler(router, {"fleet_name": "bench"})
            front = ThreadingHTTPServer(("127.0.0.1", 0), handler)
            threading.Thread(target=front.serve_forever, daemon=True).start()
            url = f"http://127.0.0.1:{front.server_address[1]}"
            return fl, front, url

        def fleet_down(fl, front):
            front.shutdown()
            front.server_close()
            fl.stop()

        # Arm A: single replica, seeded burst (rate >> capacity, so wall
        # is service-bound, not schedule-bound — the only regime where
        # replica count can show up in tokens/s at all).
        fl1, front1, url1 = fleet_up(1)
        try:
            fleet_warm(fl1)
            res1 = http_poisson_load(
                url1, fl_prompts, fl_max_new,
                rate_rps=200.0, seed=11, timeout_s=300.0,
            )
        finally:
            fleet_down(fl1, front1)

        # Arm B: two replicas, byte-identical prompt set and schedule.
        fl2, front2, url2 = fleet_up(2)
        try:
            fleet_warm(fl2)
            res2 = http_poisson_load(
                url2, fl_prompts, fl_max_new,
                rate_rps=200.0, seed=11, timeout_s=300.0,
            )
            scaleup = (
                round(res2["tokens_per_s"] / res1["tokens_per_s"], 3)
                if res1["tokens_per_s"] > 0 else None
            )
            # The >1.5x claim needs cores for the second replica to run
            # ON — two CPU-bound processes can't beat one core.  On a
            # starved smoke box the gate degrades to no-collapse (the
            # router must not serialize the fleet below a lone replica's
            # floor); multi-core CI and TPU hosts enforce the real bar.
            fl_cores = os.cpu_count() or 1
            fl_gate = 1.5 if fl_cores >= 3 else 0.5
            serving_fleet = {  # [N=1, N=2] on the same offered burst
                "tokens_per_s": [res1["tokens_per_s"], res2["tokens_per_s"]],
                "scaleup": scaleup,
                "scaleup_gate": fl_gate,
                "scaleup_ok": scaleup is not None and scaleup > fl_gate,
                "cores": fl_cores,
                "completed": [res1["completed"], res2["completed"]],
                "hangs": [res1["hangs"], res2["hangs"]],
                "ttft_p99_s": [res1["ttft_p99_s"], res2["ttft_p99_s"]],
                "n_requests": fl_n_req,
                "max_new_tokens": fl_max_new,
            }

            # Arm C (same fleet, now warm): SIGKILL one replica mid-load.
            # Longer decodes keep requests in flight at the kill point.
            victim = next(iter(fl2._procs))
            resf = http_poisson_load(
                url2, fl_prompts, fl_max_new * 2,
                rate_rps=200.0, seed=13, timeout_s=300.0,
                kill_at_s={victim: max(0.5, res2["wall_s"] * 0.3)},
                fleet=fl2,
            )
            accounted = resf["completed"] + resf["sheds"] + resf["errors"]
            # TTFT of the tail third — sent after the kill landed — shows
            # whether routing recovered or late requests starved.
            tail = [
                t for t in resf["ttft_s"][-(fl_n_req // 3):] if t is not None
            ]
            rc = fl2.router.stats()["counters"]
            serving_fleet_failover = {
                "n_requests": resf["n_requests"],
                "completed": resf["completed"],
                "sheds": resf["sheds"],
                "typed_errors": resf["errors"],
                "failures": resf["failures"],
                "hangs": resf["hangs"],
                # The contract: every request accounted for, none hung.
                "zero_lost": (
                    accounted == resf["n_requests"]
                    and resf["hangs"] == 0
                    and resf["failures"] == 0
                ),
                "ttft_p99_s": resf["ttft_p99_s"],
                "tail_ttft_p99_s": (
                    round(max(tail), 6) if tail else None
                ),
                "tail_completed": len(tail),
                "router_failovers": rc["failovers"],
                "router_retries": rc["retries"],
                "router_ejections": rc["ejections"],
                "kill_at_s": round(max(0.5, res2["wall_s"] * 0.3), 3),
            }
        finally:
            fleet_down(fl2, front2)
    except Exception:
        import sys
        import traceback

        traceback.print_exc(file=sys.stderr)

    # Autoscale under chaos: the availability proof for the PR 15
    # closed loop.  A 1-replica fleet with the FleetAutoscaler attached
    # faces a seeded chaos schedule — an overload ramp (sustained sheds
    # → scale-up), a mid-ramp SIGKILL of a serving replica (reap →
    # capacity repair), a same-rate recovery phase, then an idle tail
    # (drain back to min).  Claims: (1) ZERO lost requests — every
    # request completes or ends typed, no hangs — across all of it;
    # (2) the recovery-phase shed+error fraction drops well below the
    # overload phase's once the autoscaler restores capacity; (3) the
    # fleet returns to min_replicas with the autoscaler idle.
    serving_autoscale_chaos = None
    try:
        import tempfile
        import threading
        from http.server import ThreadingHTTPServer

        from polyaxon_tpu.serving.fleet import LocalServingFleet
        from polyaxon_tpu.serving.loadgen import ChaosEvent, chaos_poisson_load
        from polyaxon_tpu.serving.router import FleetRouter, make_router_handler

        acmodel = {
            "vocab_size": 64, "d_model": 16, "n_layers": 1,
            "n_heads": 2, "head_dim": 8, "d_ff": 32,
        }
        ac_router = FleetRouter(
            probe_interval_s=0.1, probe_timeout_s=1.0,
            request_timeout_s=120.0, retry_limit=2,
            eject_failures=2, eject_backoff_s=0.5,
            shed_occupancy=0.8,
        )
        ac_fleet = LocalServingFleet(
            Path(tempfile.mkdtemp()), acmodel,
            replicas=1, seq=64, slots=2, seed=0, router=ac_router,
            env={"POLYAXON_TPU_SERVING_WARMUP": "0"},
        )
        ac_fleet.start()
        try:
            if not ac_fleet.wait_ready(timeout_s=180):
                raise RuntimeError("autoscale-chaos fleet never became ready")
            ac_scaler = ac_fleet.attach_autoscaler(
                enabled=True, shed_rate=0.25, idle_occupancy=0.3,
                min_replicas=1, max_replicas=2,
                up_hold_s=1.0, down_hold_s=1.0,
                up_cooldown_s=1.0, down_cooldown_s=2.0,
                budget=8,
            )
            ac_handler = make_router_handler(
                ac_router, {"fleet_name": "autoscale-chaos"}
            )
            ac_front = ThreadingHTTPServer(("127.0.0.1", 0), ac_handler)
            threading.Thread(
                target=ac_front.serve_forever, daemon=True
            ).start()
            ac_url = f"http://127.0.0.1:{ac_front.server_address[1]}"
            try:
                ac_res = chaos_poisson_load(
                    ac_url,
                    [[i % 60, (i + 7) % 60, (i + 21) % 60, (i + 33) % 60]
                     for i in range(12)],
                    8,
                    phases=[
                        (6.0, 8.0),   # overload ramp on 1 replica
                        (20.0, 8.0),  # sustain: scale-up + kill repair
                        (8.0, 8.0),   # recovery: capacity restored
                        (8.0, 0.0),   # idle tail: drain back to min
                    ],
                    seed=17,
                    events=[ChaosEvent(3.0, "kill")],  # mid-ramp SIGKILL
                    fleet=ac_fleet,
                    pump=ac_fleet.poll,
                    pump_interval_s=0.05,
                    timeout_s=300.0,
                )
                # Drain-down may still be in flight when the load tail
                # ends — keep pumping the control loop until it settles.
                settle_deadline = time.time() + 90.0
                while time.time() < settle_deadline:
                    ac_fleet.poll()
                    if (
                        ac_router.stats()["n_ready"] == 1
                        and len(ac_fleet._procs) == 1
                        and ac_scaler.status()["state"] == "idle"
                    ):
                        break
                    time.sleep(0.05)
                accounted = (
                    ac_res["completed"] + ac_res["sheds"]
                    + ac_res["errors"] + ac_res["failures"]
                )
                overload = ac_res["by_phase"][0]
                recovery = ac_res["by_phase"][2]
                shed_frac = lambda p: (  # noqa: E731
                    (p["sheds"] + p["errors"]) / p["n"] if p["n"] else None
                )
                st = ac_scaler.status()
                serving_autoscale_chaos = {
                    "n_requests": ac_res["n_requests"],
                    "completed": ac_res["completed"],
                    "sheds": ac_res["sheds"],
                    "typed_errors": ac_res["errors"],
                    "failures": ac_res["failures"],
                    "hangs": ac_res["hangs"],
                    # The contract: every request accounted for, none
                    # hung — through scale-up, SIGKILL, and drain-down.
                    "zero_lost": (
                        accounted == ac_res["n_requests"]
                        and ac_res["hangs"] == 0
                        and ac_res["failures"] == 0
                    ),
                    "by_phase": ac_res["by_phase"],
                    "overload_shed_frac": shed_frac(overload),
                    "recovered_shed_frac": shed_frac(recovery),
                    "shed_recovered": (
                        shed_frac(recovery) is not None
                        and shed_frac(recovery) < 0.3
                    ),
                    "decisions_spent": ac_scaler.decisions_spent,
                    "back_to_min": (
                        ac_router.stats()["n_ready"] == 1
                        and len(ac_fleet._procs) == 1
                        and st["state"] == "idle"
                        and st["target_replicas"] == 1
                    ),
                    "last_decision": st["last_decision"],
                }
            finally:
                ac_front.shutdown()
                ac_front.server_close()
        finally:
            ac_fleet.stop()
    except Exception:
        import sys
        import traceback

        traceback.print_exc(file=sys.stderr)

    # Request-tracing overhead gate + waterfall completeness: the same
    # saturated engine drives an A/B with per-request distributed
    # tracing on vs off (root span, phase spans, waterfall build,
    # exemplar ring).  Throughput-based on purpose — a fixed-rate
    # Poisson arm's wall clock is set by the schedule, which would hide
    # the overhead being measured.  The traced arm also checks the
    # waterfall contract: interval-based phases must sum to the
    # client-observed request latency (within 10%), which hot-sampled
    # per-step spans cannot break by construction.
    serving_trace_overhead_pct = None
    serving_trace_overhead_ok = None
    serving_waterfall_err_pct = None
    serving_waterfall_ok = None
    try:
        from polyaxon_tpu.serving import ServingEngine as _TrEngine
        from polyaxon_tpu.tracking.trace import TraceContext, new_trace_id

        tr_max_new = 16
        tr_prompts = [
            [int(x) for x in rng.integers(0, scfg.vocab_size, 24)]
            for _ in range(16)
        ]

        def trace_run(traced):
            eng = _TrEngine(
                sparams, scfg, slots=4, max_len=scfg.max_seq,
                prefix_cache=False,
            ).start()
            try:
                eng.trace_requests = traced
                eng.submit([1] * 24, 2).wait(timeout=600)  # warm buckets
                t0 = time.perf_counter()
                pending = []
                for p in tr_prompts:
                    pending.append(
                        (
                            eng.submit(
                                p,
                                tr_max_new,
                                trace=(
                                    TraceContext(new_trace_id())
                                    if traced
                                    else None
                                ),
                            ),
                            time.perf_counter(),
                        )
                    )
                errs = []
                for r, ts in pending:
                    r.wait(timeout=600)
                    lat = time.perf_counter() - ts
                    summary = r.trace_summary
                    if summary is not None and lat > 0:
                        phase_sum = sum(summary["waterfall"].values())
                        errs.append(abs(phase_sum - lat) / lat * 100.0)
                wall = time.perf_counter() - t0
            finally:
                eng.stop()
            return wall, errs

        # Interleaved reps; min-wall per arm shrugs off scheduler noise.
        walls = {True: [], False: []}
        wf_errs = []
        for _ in range(2):
            for traced in (False, True):
                wall, errs = trace_run(traced)
                walls[traced].append(wall)
                if traced:
                    wf_errs.extend(errs)
        off, on = min(walls[False]), min(walls[True])
        serving_trace_overhead_pct = max(0.0, (on - off) / off * 100.0)
        serving_trace_budget_pct = 3.0 if on_tpu else 25.0
        serving_trace_overhead_ok = (
            serving_trace_overhead_pct < serving_trace_budget_pct
        )
        if not serving_trace_overhead_ok:
            import sys

            print(
                f"bench: serving_trace_overhead_pct="
                f"{serving_trace_overhead_pct:.2f} exceeds the "
                f"{serving_trace_budget_pct}% budget — request tracing "
                f"is taxing the serving engine",
                file=sys.stderr,
            )
        if wf_errs:
            serving_waterfall_err_pct = max(wf_errs)
            serving_waterfall_ok = serving_waterfall_err_pct <= 10.0
            if not serving_waterfall_ok:
                import sys

                print(
                    f"bench: waterfall phases off by "
                    f"{serving_waterfall_err_pct:.1f}% from "
                    f"client-observed latency (> 10%) — the phase "
                    f"intervals no longer partition the request",
                    file=sys.stderr,
                )
    except Exception:
        import sys
        import traceback

        traceback.print_exc(file=sys.stderr)

    baseline_path = Path(__file__).parent / "BENCH_BASELINE.json"
    vs_baseline = 1.0
    longctx_vs_baseline = None
    hpsearch_vs_baseline = None
    serving_vs_baseline = None
    serving_int8_vs_baseline = None
    serving_loaded_vs_baseline = None
    serving_spec_vs_baseline = None
    serving_fleet_vs_baseline = None
    train_images_vs_baseline = None
    if on_tpu:
        base = json.loads(baseline_path.read_text()) if baseline_path.exists() else {}
        if base.get("tokens_per_s"):
            vs_baseline = tokens_per_s / base["tokens_per_s"]
        else:
            base["tokens_per_s"], base["mfu"] = tokens_per_s, mfu
        # The long-context metric is baselined too (round-3 weak #5: a
        # flash regression must not ship silently behind the headline).
        if longctx is not None:
            if base.get("longctx_tokens_per_s"):
                longctx_vs_baseline = round(
                    longctx["tokens_per_s"] / base["longctx_tokens_per_s"], 3
                )
            else:
                base["longctx_tokens_per_s"] = longctx["tokens_per_s"]
        # hpsearch trials/hour gates too (r4 weak #2: a 13.5% regression
        # shipped silently because only tokens/s and longctx were gated).
        if trials_per_hour is not None:
            if base.get("hpsearch_trials_per_hour"):
                hpsearch_vs_baseline = round(
                    trials_per_hour / base["hpsearch_trials_per_hour"], 3
                )
            else:
                base["hpsearch_trials_per_hour"] = round(trials_per_hour)
        # Serving throughput gates like the rest: a scheduler or slot-step
        # regression must not hide behind an unchanged training headline.
        if serving is not None:
            if base.get("serving_tokens_per_s"):
                serving_vs_baseline = round(
                    serving["tokens_per_s"] / base["serving_tokens_per_s"], 3
                )
            else:
                base["serving_tokens_per_s"] = serving["tokens_per_s"]
        # The quantized serving path gates on its own baseline — an int8
        # dequant-fusion regression must not hide behind the f32 number.
        if serving is not None and serving.get("tokens_per_s_int8"):
            if base.get("serving_tokens_per_s_int8"):
                serving_int8_vs_baseline = round(
                    serving["tokens_per_s_int8"]
                    / base["serving_tokens_per_s_int8"],
                    3,
                )
            else:
                base["serving_tokens_per_s_int8"] = serving[
                    "tokens_per_s_int8"
                ]
        # Loaded serving throughput gates separately — paging/prefill
        # regressions show up here before the instant-burst number moves.
        if serving_loaded is not None:
            if base.get("serving_tokens_per_s_loaded"):
                serving_loaded_vs_baseline = round(
                    serving_loaded["tokens_per_s_loaded"]
                    / base["serving_tokens_per_s_loaded"],
                    3,
                )
            else:
                base["serving_tokens_per_s_loaded"] = serving_loaded[
                    "tokens_per_s_loaded"
                ]
        # The speculative arm gates on its own baseline: a drafter or
        # verify-kernel regression must not hide behind the unchanged
        # non-speculative loaded number.
        if serving_spec_decode is not None:
            if base.get("serving_spec_tokens_per_s"):
                serving_spec_vs_baseline = round(
                    serving_spec_decode["tokens_per_s"][1]
                    / base["serving_spec_tokens_per_s"],
                    3,
                )
            else:
                base["serving_spec_tokens_per_s"] = serving_spec_decode[
                    "tokens_per_s"
                ][1]
        # Fleet aggregate throughput gates on the N=2 arm — a router or
        # balancing regression shows up here even when the single-engine
        # serving numbers are unchanged.
        if serving_fleet is not None:
            if base.get("serving_fleet_tokens_per_s"):
                serving_fleet_vs_baseline = round(
                    serving_fleet["tokens_per_s"][1]
                    / base["serving_fleet_tokens_per_s"],
                    3,
                )
            else:
                base["serving_fleet_tokens_per_s"] = serving_fleet[
                    "tokens_per_s"
                ][1]
        # The overlapped train input path gates like serving: a prefetch
        # or async-checkpoint regression must not hide behind an unchanged
        # (synthetic-data) training headline.
        if train_images is not None:
            if base.get("train_images_per_s"):
                train_images_vs_baseline = round(
                    train_images["images_per_s"] / base["train_images_per_s"],
                    3,
                )
            else:
                base["train_images_per_s"] = train_images["images_per_s"]
        baseline_path.write_text(json.dumps(base))

    # Control-plane saturation: the flight instruments under load.  A
    # ~1000-run registry, 8 fake gangs streaming report lines, and a
    # concurrent API hammer run simultaneously while one gang stalls
    # mid-flight — gating on watcher ingest-lag p99 (is the tail keeping
    # up with the writers), stall→alert fire latency beyond the
    # configured threshold (does detection survive saturation), and API
    # read p99 under full ingest.  The idle-tick measure is the
    # instrumentation overhead floor, held to the same 5ms budget as
    # alert_tick_us.
    controlplane_saturation = None
    cp_watcher_lag_p99_ok = None
    cp_alert_fire_ok = None
    cp_api_p99_ok = None
    cp_idle_tick_us = None
    cp_idle_tick_ok = None
    try:
        import sys
        import tempfile

        from polyaxon_tpu.monitor.cploadgen import (
            measure_idle_tick_us,
            run_saturation,
        )

        controlplane_saturation = run_saturation(
            tempfile.mkdtemp(),
            n_registry_runs=1000,
            n_gangs=8,
            procs_per_gang=2,
            duration_s=6.0,
            write_hz=20.0,
            api_concurrency=4,
            stall_after_s=0.75,
            monitor_interval_s=0.05,
        )
        cp_lag_p99 = controlplane_saturation["watcher_ingest_lag_p99_s"]
        cp_fire_s = controlplane_saturation["alert_fire_latency_s"]
        cp_api_p99 = controlplane_saturation["api_p99_s"]
        # Budgets: ingest lag tracks the write cadence (50ms monitor tick
        # + 50ms writer period ≪ 1s), the stall alert must fire within 2s
        # of first becoming fireable, and API reads must stay interactive
        # while every gang's reports drain through the same process.
        cp_watcher_lag_p99_ok = cp_lag_p99 is not None and cp_lag_p99 < 1.0
        cp_alert_fire_ok = cp_fire_s is not None and cp_fire_s < 2.0
        cp_api_p99_ok = cp_api_p99 is not None and cp_api_p99 < 0.25
        if not cp_watcher_lag_p99_ok:
            print(
                f"bench: watcher_ingest_lag_p99_s={cp_lag_p99} over the 1s "
                "budget — the watcher tail is not keeping up with ingest",
                file=sys.stderr,
            )
        if not cp_alert_fire_ok:
            print(
                f"bench: cp alert_fire_latency_s={cp_fire_s} over the 2s "
                "budget — stall detection degrades under saturation",
                file=sys.stderr,
            )
        if not cp_api_p99_ok:
            print(
                f"bench: api_p99_s={cp_api_p99} over the 250ms budget — "
                "API reads degrade under concurrent ingest",
                file=sys.stderr,
            )
        if controlplane_saturation.get("api_errors"):
            print(
                f"bench: {controlplane_saturation['api_errors']} API errors "
                "during the saturation hammer",
                file=sys.stderr,
            )
        cp_idle_tick_us = measure_idle_tick_us(tempfile.mkdtemp(), iters=200)
        cp_idle_tick_ok = cp_idle_tick_us < 5000.0
        if not cp_idle_tick_ok:
            print(
                f"bench: cp_idle_tick_us={cp_idle_tick_us:.1f} over the 5ms "
                "budget — tick instrumentation costs too much when idle",
                file=sys.stderr,
            )
    except Exception:
        import sys
        import traceback

        traceback.print_exc(file=sys.stderr)

    # Metric-history scrape overhead: the scrape phase rides the monitor
    # tick, so it gets a share-of-tick budget (amortised at the
    # production scrape:tick cadence ratio) — and the query API must
    # stay interactive against a populated registry while scrapes and
    # report ingest run concurrently.
    metrics_scrape_overhead = None
    scrape_share_ok = None
    metrics_query_p99_ok = None
    try:
        import sys
        import tempfile

        from polyaxon_tpu.monitor.cploadgen import run_scrape_overhead

        metrics_scrape_overhead = run_scrape_overhead(
            tempfile.mkdtemp(),
            n_registry_runs=1000,
            n_replicas=16,
            n_gangs=4,
            duration_s=4.0,
            monitor_interval_s=0.05,
            api_duration_s=2.0,
            api_concurrency=2,
        )
        scrape_share = metrics_scrape_overhead["scrape_share"]
        query_p99 = metrics_scrape_overhead["query_p99_s"]
        scrape_share_ok = scrape_share is not None and scrape_share < 0.10
        metrics_query_p99_ok = query_p99 is not None and query_p99 < 0.1
        if not scrape_share_ok:
            print(
                f"bench: scrape_share={scrape_share} over the 10% budget — "
                "the metric scrape phase is taxing the monitor tick",
                file=sys.stderr,
            )
        if not metrics_query_p99_ok:
            print(
                f"bench: metrics query_p99_s={query_p99} over the 100ms "
                "budget on a 1000-run registry",
                file=sys.stderr,
            )
        if metrics_scrape_overhead.get("query_errors"):
            print(
                f"bench: {metrics_scrape_overhead['query_errors']} metric "
                "query errors during the overhead hammer",
                file=sys.stderr,
            )
    except Exception:
        import sys
        import traceback

        traceback.print_exc(file=sys.stderr)

    # graft-lint full-package runtime: the static pass rides every CI
    # invocation (`make lint` is in the gate), so it gets a wall-clock
    # budget like every other tick path — a rule that grows a quadratic
    # project index fails here, not in everyone's pre-push loop.
    analysis_runtime_s = None
    analysis_runtime_ok = None
    try:
        import sys

        from polyaxon_tpu.analysis import run_analysis

        t0 = time.perf_counter()
        run_analysis()
        analysis_runtime_s = time.perf_counter() - t0
        analysis_runtime_ok = analysis_runtime_s < 10.0
        if not analysis_runtime_ok:
            print(
                f"bench: analysis_runtime_s={analysis_runtime_s:.2f} over "
                "the 10s budget — graft-lint is too slow for CI",
                file=sys.stderr,
            )
    except Exception:
        import sys
        import traceback

        traceback.print_exc(file=sys.stderr)

    print(
        json.dumps(
            {
                "metric": "lm_train_single_chip_mfu",
                "value": round(mfu, 4),
                "unit": "mfu",
                "vs_baseline": round(vs_baseline, 3),
                "tokens_per_s": round(tokens_per_s),
                "steps_per_s": round(steps_per_s, 3),
                "final_loss": round(final_loss, 4),
                "device": dev.device_kind,
                "n_params": n_params,
                "hpsearch_trials_per_hour": (
                    round(trials_per_hour) if trials_per_hour else None
                ),
                "hpsearch_vs_baseline": hpsearch_vs_baseline,
                "longctx_flash_t8192": longctx,
                "longctx_vs_baseline": longctx_vs_baseline,
                "serving_tokens_per_s": serving,
                "serving_vs_baseline": serving_vs_baseline,
                "serving_tokens_per_s_int8": (
                    serving.get("tokens_per_s_int8") if serving else None
                ),
                "serving_int8_vs_baseline": serving_int8_vs_baseline,
                "serving_int8_kv": serving_int8_kv,
                "serving_ttft_p99_s": (
                    serving_loaded["ttft_p99_s"] if serving_loaded else None
                ),
                "serving_tokens_per_s_loaded": (
                    serving_loaded["tokens_per_s_loaded"]
                    if serving_loaded
                    else None
                ),
                "serving_loaded": serving_loaded,
                "serving_loaded_vs_baseline": serving_loaded_vs_baseline,
                "serving_spec_decode": serving_spec_decode,
                "serving_spec_vs_baseline": serving_spec_vs_baseline,
                "serving_kv_offload": serving_kv_offload,
                "serving_warm_boot": serving_warm_boot,
                "serving_fleet_tokens_per_s": serving_fleet,
                "serving_fleet_vs_baseline": serving_fleet_vs_baseline,
                "serving_fleet_failover": serving_fleet_failover,
                "serving_autoscale_under_chaos": serving_autoscale_chaos,
                "train_images_per_s": train_images,
                "train_images_vs_baseline": train_images_vs_baseline,
                "trace_overhead_pct": (
                    round(trace_overhead_pct, 2)
                    if trace_overhead_pct is not None
                    else None
                ),
                "trace_overhead_ok": trace_overhead_ok,
                "serving_trace_overhead_pct": (
                    round(serving_trace_overhead_pct, 2)
                    if serving_trace_overhead_pct is not None
                    else None
                ),
                "serving_trace_overhead_ok": serving_trace_overhead_ok,
                "serving_waterfall_err_pct": (
                    round(serving_waterfall_err_pct, 2)
                    if serving_waterfall_err_pct is not None
                    else None
                ),
                "serving_waterfall_ok": serving_waterfall_ok,
                "stall_detect_s": (
                    round(stall_detect_s, 2)
                    if stall_detect_s is not None
                    else None
                ),
                "stall_detect_ok": stall_detect_ok,
                "alert_fire_latency_s": (
                    round(alert_fire_latency_s, 2)
                    if alert_fire_latency_s is not None
                    else None
                ),
                "alert_fire_ok": alert_fire_ok,
                "alert_tick_us": (
                    round(alert_tick_us, 1)
                    if alert_tick_us is not None
                    else None
                ),
                "alert_tick_overhead_ok": alert_tick_overhead_ok,
                "profile_roundtrip_s": (
                    round(profile_roundtrip_s, 2)
                    if profile_roundtrip_s is not None
                    else None
                ),
                "profile_roundtrip_ok": profile_roundtrip_ok,
                "idle_bus_poll_us": (
                    round(idle_bus_poll_us, 1)
                    if idle_bus_poll_us is not None
                    else None
                ),
                "idle_bus_overhead_ok": idle_bus_overhead_ok,
                "reported_mfu_abs_err": (
                    round(reported_mfu_abs_err, 5)
                    if reported_mfu_abs_err is not None
                    else None
                ),
                "reported_mfu_ok": reported_mfu_ok,
                "first_step_s_cold": (
                    round(first_step_s_cold, 3)
                    if first_step_s_cold is not None
                    else None
                ),
                "first_step_s_warm": (
                    round(first_step_s_warm, 3)
                    if first_step_s_warm is not None
                    else None
                ),
                "first_step_warm_ok": first_step_warm_ok,
                "compile_cache_hits_warm": warm_cache_hits,
                "run_goodput_ratio": (
                    round(run_goodput_ratio, 3)
                    if run_goodput_ratio is not None
                    else None
                ),
                "run_goodput_ok": run_goodput_ok,
                "run_goodput_ratio_norestart": (
                    round(run_goodput_ratio_norestart, 3)
                    if run_goodput_ratio_norestart is not None
                    else None
                ),
                "recovery_s": (
                    round(recovery_s, 2) if recovery_s is not None else None
                ),
                "serving_ready_s": (
                    round(serving_ready_s, 3)
                    if serving_ready_s is not None
                    else None
                ),
                "controlplane_saturation": controlplane_saturation,
                "cp_watcher_lag_p99_ok": cp_watcher_lag_p99_ok,
                "cp_alert_fire_ok": cp_alert_fire_ok,
                "cp_api_p99_ok": cp_api_p99_ok,
                "cp_idle_tick_us": (
                    round(cp_idle_tick_us, 1)
                    if cp_idle_tick_us is not None
                    else None
                ),
                "cp_idle_tick_ok": cp_idle_tick_ok,
                "metrics_scrape_overhead": metrics_scrape_overhead,
                "scrape_share_ok": scrape_share_ok,
                "metrics_query_p99_ok": metrics_query_p99_ok,
                "analysis_runtime_s": (
                    round(analysis_runtime_s, 3)
                    if analysis_runtime_s is not None
                    else None
                ),
                "analysis_runtime_ok": analysis_runtime_ok,
            }
        )
    )


if __name__ == "__main__":
    main()
