"""Built-in service entrypoints: tensorboard + outputs file server.

Parity: reference plugin deployments — ``polypod/tensorboard.py:32`` (a
tensorboard pod over an experiment's outputs) and ``polypod/notebook.py:35``.
TPU-native framing: services are ordinary gangs whose entrypoint serves
until the platform stops them; the serving port is allocated at dispatch
and arrives as ``ctx.get_param("service_port")`` (also exported as
``POLYAXON_TPU_SERVICE_PORT``), and the run's ``service_url`` is recorded
in the registry.

Target resolution: services usually visualize ANOTHER run's outputs — the
``target`` param is that run's uuid; the shared store layout makes its
``outputs/`` reachable from this gang's host.
"""

from __future__ import annotations

import subprocess
import sys
import time
from pathlib import Path
from polyaxon_tpu.tracking import Context


def _target_outputs(ctx: Context) -> Path:
    """The outputs dir to serve: the `target` run's, or our own."""
    target = ctx.get_param("target")
    if ctx.get_param("logdir"):
        return Path(str(ctx.get_param("logdir")))
    own_outputs = ctx.outputs_path
    if target is None:
        return own_outputs
    # The worker hands us the layout's runs/ root; a target run's outputs
    # live beside ours on the shared layout.
    runs_root = ctx.runs_root or own_outputs.parent.parent
    return runs_root / str(target) / "outputs"


def _service_port(ctx: Context) -> int:
    port = ctx.get_param("service_port") or ctx.get_param("port")
    if not port:
        raise RuntimeError(
            "No service port allocated — submit this entrypoint under a "
            "service kind (notebook/tensorboard) so dispatch assigns one"
        )
    return int(port)


def tensorboard(ctx: Context) -> None:
    """Serve tensorboard over a run's outputs until stopped.

    Params: ``target`` (run uuid whose outputs to visualize; default: this
    run's own outputs), ``logdir`` (explicit path override), ``host``
    (bind address, default 0.0.0.0 so the URL is reachable off-host).
    """
    import os

    logdir = _target_outputs(ctx)
    port = _service_port(ctx)
    host = str(ctx.get_param("host", "0.0.0.0"))
    ctx.log_text(f"tensorboard serving {logdir} on {host}:{port}")
    # A subprocess (not the program API) so the gang's TERM→KILL escalation
    # tears it down exactly like any workload; --load_fast=false keeps the
    # data-loading path version-robust.  When the environment has no real
    # pkg_resources (setuptools >= 82 removed it; tensorboard 2.20 still
    # imports it), the _compat dir supplies a scoped shim — prepended only
    # for THIS subprocess, and never when the real module exists.
    env = dict(os.environ)
    import importlib.util

    if importlib.util.find_spec("pkg_resources") is None:
        compat_dir = str(Path(__file__).resolve().parents[1] / "_compat")
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (compat_dir, env.get("PYTHONPATH")) if p
        )
    rc = subprocess.call(
        [
            sys.executable,
            "-m",
            "tensorboard.main",
            "--logdir",
            str(logdir),
            "--host",
            host,
            "--port",
            str(port),
            "--load_fast",
            "false",
        ],
        env=env,
    )
    if rc != 0:
        raise RuntimeError(f"tensorboard exited {rc}")


def jupyter(ctx: Context) -> None:
    """Serve JupyterLab until stopped — the default ``kind: notebook``
    entrypoint (reference ran real Jupyter: ``polypod/notebook.py:35``).

    Params: ``notebook_dir`` (default: this run's outputs — writable, so
    notebooks persist as artifacts), ``target`` (work in another run's
    outputs instead), ``host`` (bind address, default 0.0.0.0), ``token``
    (access token; default: a fresh random one), ``jupyter_bin`` (explicit
    server executable — tests point this at a stub so the service plumbing
    is verifiable without jupyter installed).

    The token is generated worker-side and published by appending
    ``?token=...`` to the dispatch-recorded service URL through the report
    channel — the control plane never has to know it in advance.
    """
    import os
    import secrets

    port = _service_port(ctx)
    host = str(ctx.get_param("host", "0.0.0.0"))
    if ctx.get_param("notebook_dir"):
        root = Path(str(ctx.get_param("notebook_dir")))
    else:
        root = _target_outputs(ctx)
    root.mkdir(parents=True, exist_ok=True)
    token = str(ctx.get_param("token") or secrets.token_hex(16))

    jupyter_bin = ctx.get_param("jupyter_bin")
    if jupyter_bin:
        argv = [str(jupyter_bin)]
    else:
        import importlib.util

        if importlib.util.find_spec("jupyterlab") is not None:
            argv = [sys.executable, "-m", "jupyterlab"]
        elif importlib.util.find_spec("jupyter_server") is not None:
            # Same --ServerApp flags; serves the classic file/API surface
            # when only the server core is installed.
            argv = [sys.executable, "-m", "jupyter_server"]
        else:
            raise RuntimeError(
                "jupyter is not installed on this worker — install jupyterlab "
                "or pass a jupyter_bin param"
            )
    argv += [
        f"--ServerApp.ip={host}",
        f"--ServerApp.port={port}",
        f"--ServerApp.token={token}",
        f"--ServerApp.root_dir={root}",
        "--ServerApp.port_retries=0",
        "--ServerApp.allow_remote_access=True",
        "--no-browser",
    ]
    if ctx.is_leader:
        ctx.report_service(query=f"token={token}")
    ctx.log_text(f"jupyter serving {root} on {host}:{port}")
    rc = subprocess.call(argv, env=dict(os.environ))
    if rc != 0:
        raise RuntimeError(f"jupyter exited {rc}")


def _make_lm_handler(engine, cfg, meta: dict, log=lambda line: None):
    """HTTP handler class over a :class:`ServingEngine` (factored out of
    ``lm_server`` so tests can drive the exact production handler against
    a bare engine, no platform Context required)."""
    import json as json_mod
    from http.server import BaseHTTPRequestHandler

    from polyaxon_tpu.serving.engine import EngineDrainingError
    from polyaxon_tpu.tracking.trace import (
        TraceContext,
        extract,
        get_tracer,
        new_trace_id,
    )

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):  # route into run logs, not stderr
            log("lm_server: " + fmt % args)

        def _json(self, code, payload, headers=None):
            body = json_mod.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _error(self, code, kind, message, headers=None):
            # Machine-readable errors: routers and loadgen dispatch on
            # error.kind (429 "shed" is load signal, 503 "draining" is
            # lifecycle, connection drop is a fault) — string matching
            # on messages is not an API.
            return self._json(
                code, {"error": {"kind": kind, "message": message}}, headers
            )

        def do_GET(self):
            if self.path == "/v1/stats":
                payload = engine.stats()
                latency = engine.latency_summaries()
                if latency:
                    payload["latency"] = latency
                return self._json(200, payload)
            if self.path.startswith("/v1/trace/"):
                # Raw spans for one trace from this process's ring
                # buffer — the router merges these fleet-wide.  An empty
                # list is a valid answer (expired or never sampled).
                trace_id = self.path[len("/v1/trace/"):]
                spans = [
                    s
                    for s in get_tracer().spans()
                    if s.get("trace_id") == trace_id
                ]
                return self._json(200, {"trace_id": trace_id, "spans": spans})
            if self.path == "/metrics":
                from polyaxon_tpu.stats.metrics import (
                    PROMETHEUS_CONTENT_TYPE,
                    render_prometheus,
                    render_standard_gauges,
                )

                snapshot_fn = getattr(engine.stats_registry, "snapshot", None)
                if snapshot_fn is None:
                    text = "# engine stats backend keeps no in-process registry\n"
                else:
                    try:
                        snap = snapshot_fn(include_timings=False)
                    except TypeError:  # duck-typed stand-in without the kwarg
                        snap = snapshot_fn()
                    text = render_prometheus(
                        snap, labels={"component": "lm_server"}
                    )
                text += render_standard_gauges(labels={"component": "lm_server"})
                body = text.encode("utf-8")
                self.send_response(200)
                self.send_header("Content-Type", PROMETHEUS_CONTENT_TYPE)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                return self.wfile.write(body)
            if self.path not in ("/healthz", "/"):
                return self._error(404, "not_found", "not found")
            stats = engine.stats()
            failed = stats["state"] == "failed"
            self._json(
                503 if failed else 200,
                {
                    "ok": not failed,
                    "model": {
                        "n_params": cfg.n_params,
                        "vocab_size": cfg.vocab_size,
                        "max_seq": cfg.max_seq,
                        "n_kv_heads": cfg.kv_heads,
                    },
                    # "warming" until the start()-time warmup has
                    # pre-compiled the whole bucket family; LBs should
                    # gate traffic on state == "ready".  "failed" (503)
                    # when the warmup raised — the process is on its way
                    # out with a non-zero exit.
                    "state": stats["state"],
                    "start_error": stats.get("start_error"),
                    "engine": {
                        "slots": stats["slots"],
                        "slots_active": stats["slots_active"],
                        "queue_depth": stats["queue_depth"],
                        "warmup": stats["warmup"],
                    },
                    **meta,
                },
            )

        def do_POST(self):
            if self.path == "/v1/cancel":
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    req = json_mod.loads(self.rfile.read(n) or b"{}")
                    rid = int(req["request_id"])
                except (KeyError, ValueError, TypeError) as e:
                    return self._error(400, "bad_request", str(e))
                return self._json(200, {"cancelled": engine.cancel(rid)})
            if self.path != "/generate":
                return self._error(404, "not_found", "not found")
            try:
                n = int(self.headers.get("Content-Length", 0))
                req = json_mod.loads(self.rfile.read(n) or b"{}")
                prompts = req["prompts"]
                max_new = int(
                    req.get("max_new_tokens", meta.get("default_max_new", 64))
                )
                temperature = float(req.get("temperature", 0.0))
                if not prompts or not isinstance(prompts[0], list):
                    raise ValueError("prompts must be a list of id lists")
            except (KeyError, ValueError, TypeError) as e:
                return self._error(400, "bad_request", str(e))
            # Join the caller's trace (router hop) or mint a fresh one
            # for direct clients; a malformed traceparent extracts to
            # None and degrades to a fresh trace, never an error.
            tctx = extract(self.headers)
            if tctx is None and getattr(engine, "trace_requests", False):
                tctx = TraceContext(new_trace_id())
            if tctx is not None and not tctx.sampled:
                tctx = None
            if tctx is None:
                return self._generate(prompts, max_new, temperature, None)
            with get_tracer().span(
                "serving.generate",
                sample=1.0,
                trace_id=tctx.trace_id,
                parent_id=tctx.span_id or None,
                prompts=len(prompts),
            ) as sp:
                return self._generate(
                    prompts, max_new, temperature, tctx.child(sp.span_id)
                )

        def _generate(self, prompts, max_new, temperature, tctx):
            try:
                # Mixed lengths are fine now — each prompt is its own
                # request; the engine batches them at the decode-step
                # level.  Validation happens in submit() per prompt.
                t0 = time.time()
                # The trace kwarg rides only when a context exists, so
                # duck-typed engine stand-ins keep working untraced.
                reqs = [
                    engine.submit(p, max_new, temperature, trace=tctx)
                    if tctx is not None
                    else engine.submit(p, max_new, temperature)
                    for p in prompts
                ]
            except EngineDrainingError as e:
                retry_after = str(int(meta.get("retry_after_s", 1)))
                return self._error(
                    503, "draining", str(e), {"Retry-After": retry_after}
                )
            except (KeyError, ValueError, TypeError) as e:
                return self._error(400, "bad_request", str(e))
            try:
                timeout_s = float(meta.get("request_timeout_s", 600))
                tokens = [r.wait(timeout=timeout_s) for r in reqs]
            except (RuntimeError, TimeoutError) as e:
                # The client is about to get an error and walk away:
                # release every still-running sibling's slot, KV blocks,
                # and prefix refs instead of decoding to max_new_tokens
                # for nobody.
                for r in reqs:
                    if not r.done.is_set():
                        engine.cancel(r.id)
                kinds = {r.error_kind for r in reqs if r.error_kind}
                if "shed" in kinds:
                    # Deadlock-shed: the pool cannot fit this working
                    # set RIGHT NOW.  429 + Retry-After tells the client
                    # to back off, not to count a fault.
                    retry_after = str(int(meta.get("retry_after_s", 1)))
                    return self._error(
                        429, "shed", str(e), {"Retry-After": retry_after}
                    )
                if isinstance(e, TimeoutError):
                    return self._error(503, "timeout", str(e))
                kind = next(iter(kinds)) if kinds else "engine_error"
                return self._error(503, kind, str(e))
            dt = time.time() - t0
            total = sum(len(t) for t in tokens)
            ttfts = [
                round(r.first_token_at - t0, 6)
                if r.first_token_at is not None
                else None
                for r in reqs
            ]
            payload = {
                "tokens": tokens,
                "decode_tokens_per_s": round(total / max(dt, 1e-9), 1),
                "ttft_s": ttfts,
            }
            if tctx is not None:
                # Per-request latency waterfalls ride the response so
                # clients (loadgen) see where the time went without a
                # second round-trip.
                payload["trace"] = {
                    "trace_id": tctx.trace_id,
                    "waterfalls": [
                        r.trace_summary
                        for r in reqs
                        if r.trace_summary is not None
                    ],
                }
            self._json(200, payload)

    return Handler


def serve_engine(server, engine) -> None:
    """``server.serve_forever()`` for an engine-backed HTTP server, except
    that a failed engine start ends it: the readiness gate never opens,
    the server shuts down, and the caller gets the start error — so the
    process exits non-zero instead of sitting in ``warming`` forever."""
    import threading

    def _watch():
        if not engine.wait_ready() and engine.start_error is not None:
            server.shutdown()

    threading.Thread(target=_watch, name="engine-start-watch", daemon=True).start()
    try:
        server.serve_forever()
    finally:
        engine.stop()
    if engine.start_error is not None:
        raise RuntimeError(
            f"serving engine failed to start: {engine.start_error}"
        )


def _truthy(value) -> bool:
    return str(value).lower() not in ("0", "false", "no", "off", "")


def lm_server(ctx: Context) -> None:
    """LM inference endpoint: the default ``kind: service`` entrypoint.

    A CONTINUOUS-BATCHING server (polyaxon_tpu/serving/engine.py) over a
    PAGED KV cache: one ref-counted block pool, per-request block tables,
    shared-prefix reuse (system prompts map to the same blocks,
    copy-on-write at divergence), chunked prefill interleaved with
    decode, and one jitted decode step advancing every in-flight request
    a token per iteration.  Concurrent connections feed the engine queue
    through a threaded front-end and block only on their own completion —
    a long generation (or a long PROMPT) never head-of-line-blocks a
    short one.  Routes:

    - ``POST /generate`` ``{"prompts": [[ids…]…], "max_new_tokens": N,
      "temperature": t}`` → ``{"tokens": [[ids…]…], "decode_tokens_per_s"}``
      (prompts may have DIFFERENT lengths — each is its own engine
      request; the KV cache stores UNEXPANDED GQA heads).  A request that
      times out server-side is CANCELLED (its slot and blocks free
      immediately) before the 503 goes out.
    - ``POST /v1/cancel`` ``{"request_id": N}`` → ``{"cancelled": bool}``
      — release an in-flight or queued request's slot, KV blocks, and
      prefix-cache references immediately.
    - ``GET /healthz`` → model/checkpoint metadata + engine occupancy +
      readiness ``state`` (``"warming"`` until the start()-time warmup
      has pre-compiled the decode step and every prefill bucket,
      ``"ready"`` after).
    - ``GET /v1/stats`` → queue depth, slot occupancy, tokens/s, block
      pool occupancy, prefix-cache hit rate, prefill backlog, latency
      percentiles (queue wait / TTFT / per-token decode).
    - ``GET /metrics`` → Prometheus text exposition of the same
      histograms plus the paging gauges (see docs/observability.md).

    Params: ``target`` (run uuid whose ``checkpoints/`` to serve — omit
    for fresh random weights, a load-testing double), the model-shape
    params of ``lm_train`` (must match the checkpoint), ``seq`` (max
    prompt+generation length per request), ``slots`` (concurrent
    sequences the batch holds), ``block_size`` (tokens per KV block),
    ``kv_blocks`` (pool size override — size below slots×seq to
    overcommit on prefix sharing), ``kv_quantize`` (``int8`` stores the
    KV pool quantized with per-row scales — <0.3× the pool HBM, so a
    fixed byte budget holds >2× the blocks; composes with ``quantize``),
    ``prefill_chunk`` (prompt tokens
    inserted per scheduler iteration; 0/unset = whole-prompt),
    ``prefix_cache`` (share identical prompt prefixes, default on),
    ``request_timeout_s`` (server-side wait budget per /generate),
    ``max_new_tokens`` (server default when a request omits it),
    ``eos_id`` (retire a slot early on this token), ``host``,
    ``quantize`` (``int8`` weight-only decode), ``spec_decode`` /
    ``spec_k`` / ``spec_min_ngram`` (speculative decoding: self-drafted
    multi-token steps for greedy requests — see docs/serving.md),
    ``kv_offload`` / ``kv_offload_blocks`` (pinned-host KV tier: parked
    sequences spill blocks to host instead of holding the pool, cold
    prefixes demote instead of evicting), ``kv_persist`` /
    ``kv_persist_dir`` (persist hot prefix blocks to the shared store's
    ``kv_cache/`` dir so replacement/scale-up replicas boot
    prefix-warm; ``kv_persist: true`` defaults the dir from the store
    layout).  The decode step's shapes depend only on (slots, pool
    size) — steady-state serving never recompiles.

    A model with linear-attention layers (``models/hybrid.py``) is
    declared by ``layer_types`` (a list or comma-separated string, one
    ``linear_attention`` / ``full_attention`` a layer), the sizes
    ``linear_num_key_heads`` / ``linear_num_value_heads`` /
    ``linear_key_head_dim`` / ``linear_value_head_dim`` /
    ``linear_conv_kernel_dim``, ``linear_allow_neg_eigval`` and ``rope``
    (``0``: the full layers apply no rotary embedding).  Such a model
    keeps a float32 recurrent state per slot beside the KV pool;
    ``state_snapshot_every`` (tokens, a multiple of ``block_size``) and
    ``state_snapshots`` (places in the device store) size the snapshots
    a prefix hit resumes from — one costs the state of every linear
    layer, and a hit is cut back to the newest one (docs/serving.md).
    It refuses ``spec_decode``, ``kv_offload``, ``kv_persist`` and a
    multi-chip mesh with a ``RecurrentStateError`` naming the option.

    ``rope_theta`` is the rotary base of any model (default 10000).

    A latent-attention model (``models/latent_moe.py``) is declared by
    ``kv_lora_rank`` with ``q_lora_rank``, ``qk_nope_head_dim``,
    ``qk_rope_head_dim`` and ``v_head_dim`` under the published configs'
    names, and by ``layer_types`` naming each layer's MLP: ``dense_mlp``
    (``d_ff`` wide) or ``expert_mlp``.  The expert layers take
    ``n_routed_experts`` (the router's width), ``num_experts_per_tok``,
    ``moe_intermediate_size``, ``n_shared_experts``,
    ``routed_scaling_factor``, and the chip's share of a layer's experts:
    ``experts_held`` from ``expert_offset`` on (default: all).  The pool
    then holds one latent row a token a layer (``kv_row_bytes`` in
    ``/v1/stats``, beside ``moe_rows_routed`` / ``moe_rows_held`` /
    ``moe_rows_busiest`` / ``moe_experts_hit`` / ``moe_call_shapes``).  It
    refuses ``spec_decode`` and a multi-chip mesh with a ``LatentStackError``
    naming the option.

    A model of window and full attention layers (``models/window_moe.py``) is
    declared by ``mlp_layer_types`` (``dense`` / ``sparse``, a layer each)
    beside ``layer_types`` (``full_attention`` / ``sliding_attention``), with
    ``sliding_window``, ``sliding_n_heads`` (query heads of the window layers;
    ``n_heads`` are the full layers'), ``head_gate`` (one sigmoid gate a
    head), the rotary forms ``rope_theta`` / ``partial_rotary_factor`` /
    ``rope_yarn_factor`` / ``rope_yarn_original_max`` / ``rope_yarn_beta_fast``
    / ``rope_yarn_beta_slow`` / ``rope_attention_factor`` (full layers) and
    ``sliding_rope_theta`` (window layers), and the expert sizes above (the
    router a softmax, no selection bias).  Its window layers keep a ring of
    ``sliding_window`` K and V rows a slot beside the pool, snapshotted for
    prefix hits as the hybrid model's state is (``state_snapshot_every``,
    ``state_snapshots``); ``/v1/stats`` carries ``window_pairs`` and
    ``window_chunk_calls``.  It refuses ``spec_decode``, ``kv_offload``,
    ``kv_persist`` and a multi-chip mesh with a ``WindowStackError`` naming
    the option.
    """
    import jax

    from polyaxon_tpu import stats as stats_backends
    from polyaxon_tpu.models import TransformerConfig, decode, init_params
    from polyaxon_tpu.serving import ServingEngine

    cfg_fields = {
        f: int(ctx.get_param(f))
        for f in (
            "vocab_size", "d_model", "n_layers", "n_heads",
            "head_dim", "d_ff", "n_kv_heads", "n_experts",
        )
        if ctx.get_param(f) is not None
    }
    seq = int(ctx.get_param("seq", 512))
    # A layer pattern selects the hybrid stack (models/hybrid.py): which
    # layers are gated-delta-rule ("linear_attention") and which full
    # attention, the linear layers' sizes under the published configs'
    # names, and ``rope: 0`` for full layers without rotary embedding.
    # (With ``kv_lora_rank``, below, the pattern names the latent stack's
    # MLPs instead.)
    layer_types = ctx.get_param("layer_types")
    if layer_types is not None:
        if isinstance(layer_types, str):
            layer_types = [t.strip() for t in layer_types.split(",") if t.strip()]
        cfg_fields["layer_types"] = tuple(str(t) for t in layer_types)
        for f in (
            "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim",
        ):
            if ctx.get_param(f) is not None:
                cfg_fields[f] = int(ctx.get_param(f))
        cfg_fields["linear_allow_neg_eigval"] = _truthy(
            ctx.get_param("linear_allow_neg_eigval", False)
        )
        if not _truthy(ctx.get_param("rope", True)):
            cfg_fields["rope_theta"] = None
    if ctx.get_param("rope_theta") is not None:
        cfg_fields["rope_theta"] = float(ctx.get_param("rope_theta"))
    # Latent attention and routed experts (models/latent_moe.py), sized under
    # the published configs' names; ``layer_types`` above names each layer's
    # MLP, ``experts_held`` / ``expert_offset`` the chip's share of a layer.
    for f in (
        "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "n_routed_experts", "num_experts_per_tok",
        "moe_intermediate_size", "n_shared_experts", "experts_held",
        "expert_offset",
    ):
        if ctx.get_param(f) is not None:
            cfg_fields[f] = int(ctx.get_param(f))
    if ctx.get_param("routed_scaling_factor") is not None:
        cfg_fields["routed_scaling_factor"] = float(
            ctx.get_param("routed_scaling_factor")
        )
    # Window and full attention mixed (models/window_moe.py): the second list
    # names each layer's MLP and selects the stack; heads, the gate and the
    # rotary forms by layer kind.
    mlp_layer_types = ctx.get_param("mlp_layer_types")
    if mlp_layer_types is not None:
        if isinstance(mlp_layer_types, str):
            mlp_layer_types = [t.strip() for t in mlp_layer_types.split(",") if t.strip()]
        cfg_fields["mlp_layer_types"] = tuple(str(t) for t in mlp_layer_types)
        cfg_fields["head_gate"] = _truthy(ctx.get_param("head_gate", False))
    for f, kind in (
        ("sliding_window", int), ("sliding_n_heads", int),
        ("rope_yarn_original_max", int), ("partial_rotary_factor", float),
        ("rope_yarn_factor", float), ("rope_yarn_beta_fast", float),
        ("rope_yarn_beta_slow", float), ("rope_attention_factor", float),
        ("sliding_rope_theta", float),
    ):
        if ctx.get_param(f) is not None:
            cfg_fields[f] = kind(ctx.get_param(f))
    cfg = TransformerConfig(max_seq=seq, **cfg_fields)
    params = init_params(jax.random.PRNGKey(ctx.seed or 0), cfg)

    # Multi-chip serving: shard the weights over the gang's mesh per the
    # topology's strategy (tp shards heads over the tensor axis; GSPMD
    # propagates through the decode scan so the KV cache lands
    # heads-sharded too). Single-device keeps plain jit.  SINGLE-PROCESS
    # only: a sharded decode is a collective program every process must
    # enter, but only the process that receives the HTTP request would —
    # a multi-host sharded /generate would wedge in the collective.
    # Multi-host service gangs therefore keep the pre-mesh behavior:
    # each host serves an independent local replica.
    mesh = ctx.mesh if ctx.num_processes == 1 else None
    if ctx.num_processes > 1:
        ctx.log_text(
            "lm_server: multi-host gang — serving an independent replica "
            "per host (sharded decode needs a single-process mesh)"
        )
    template = None
    param_shardings = None
    if mesh is not None and mesh.size > 1:
        if cfg.stack != "uniform":  # hybrid, latent, window: typed, by name
            from polyaxon_tpu.models.transformer import stack_module

            raise stack_module(cfg).refusal("mesh")
        from polyaxon_tpu.models.decode import decode_param_shardings
        from polyaxon_tpu.parallel import template_for

        template = template_for(
            ctx.strategy, dict(mesh.shape), ctx.strategy_options
        )
        param_shardings = decode_param_shardings(
            cfg, mesh, template, params=params
        )
        params = jax.device_put(params, param_shardings)

    step = None
    target = ctx.get_param("target")
    if target is not None:
        from polyaxon_tpu.runtime.checkpoint import CheckpointManager

        ckpt_dir = (ctx.runs_root or ctx.outputs_path.parent.parent) / str(
            target
        ) / "checkpoints"
        ckpt = CheckpointManager(ckpt_dir)
        # The (possibly sharded) init params are the restore template —
        # orbax restores each leaf onto its sharding, so a checkpoint
        # written under a training mesh reshards onto the serving mesh.
        restored = ckpt.restore_params(params)
        ckpt.close()
        if restored is None:
            raise RuntimeError(f"No checkpoint under {ckpt_dir}")
        params, step = restored["params"], restored["step"]
        del restored  # ``params`` is now the float32 tree's only name
        ctx.log_text(f"lm_server: restored run {target} step {step}")

    # int8 weight-only decode (param ``quantize: int8``): the per-token
    # loop streams int8 weights (+51% measured decode throughput on the
    # bench model).  Composes with a sharded mesh: the (q, scale) pairs
    # shard like the weights they replaced, so each chip streams only
    # its shard's int8 bytes.
    qweights = None
    qweights_shardings = None
    if str(ctx.get_param("quantize", "") or "") == "int8":
        qweights = decode.quantize_weights(params)
        if template is not None:
            qweights_shardings = decode.quantized_weight_shardings(
                cfg, mesh, template, qweights
            )
            qweights = jax.device_put(qweights, qweights_shardings)
        ctx.log_text("lm_server: int8 weight-only decode enabled")

    # The chip holds what the programs read: ``decode.serving_params``'s
    # tree, rounded HERE one leaf at a time, each float32 leaf released as
    # soon as it is cast.  The high-water mark at load is then the float32
    # tree plus one leaf; cast by the engine, both trees would stand under
    # its pool for a moment (13.3 GB against 10.3 for the 4-layer
    # Olmo-Hybrid cell, PERF.md PR 30).  The engine's own call finds nothing
    # left to do.  ``qweights`` were made from the float32 weights above.
    want = jax.tree.leaves(
        jax.eval_shape(lambda p: decode.serving_params(p, cfg), params)
    )
    leaves, treedef = jax.tree.flatten(params)
    del params
    for i, w in enumerate(want):
        if leaves[i].dtype != w.dtype:
            # waited for, so the float32 leaf is gone before the next cast
            leaves[i] = jax.block_until_ready(leaves[i].astype(w.dtype))
    params = jax.tree.unflatten(treedef, leaves)

    port = _service_port(ctx)
    host = str(ctx.get_param("host", "0.0.0.0"))

    # Label this process's request spans so a fleet's merged trace puts
    # every replica on its own named track (the worker entrypoint set
    # sink/process_id already; the label rides on top).
    from polyaxon_tpu.tracking.trace import get_tracer

    get_tracer().configure(
        process=(
            f"lm_server-{ctx.run_uuid[:8]}" if ctx.run_uuid
            else f"lm_server-{port}"
        )
    )
    eos_id = ctx.get_param("eos_id")
    kv_blocks = ctx.get_param("kv_blocks")
    prefill_chunk = int(ctx.get_param("prefill_chunk", 0) or 0)
    kv_quantize = str(ctx.get_param("kv_quantize", "") or "") or None
    if kv_quantize:
        ctx.log_text(f"lm_server: kv_quantize={kv_quantize} KV pool enabled")
    spec_decode = ctx.get_param("spec_decode")
    spec_decode = (
        None
        if spec_decode is None
        else str(spec_decode).lower() not in ("0", "false", "no", "")
    )
    spec_k = ctx.get_param("spec_k")
    spec_min_ngram = ctx.get_param("spec_min_ngram")
    if spec_decode:
        ctx.log_text(
            f"lm_server: speculative decoding enabled "
            f"(spec_k={spec_k}, spec_min_ngram={spec_min_ngram})"
        )
    kv_offload = ctx.get_param("kv_offload")
    kv_offload = (
        None
        if kv_offload is None
        else str(kv_offload).lower() not in ("0", "false", "no", "")
    )
    kv_offload_blocks = ctx.get_param("kv_offload_blocks")
    kv_persist_dir = ctx.get_param("kv_persist_dir")
    if kv_persist_dir is None and str(
        ctx.get_param("kv_persist", "") or ""
    ).lower() in ("1", "true", "yes"):
        # Default the persist dir from the shared store layout: runs/
        # sits under the layout base, and kv_cache/ beside it (see
        # StoreLayout.kv_cache_dir) — every replica of a fleet lands on
        # the same store, which is what makes warm boot work.
        runs_root = ctx.runs_root or ctx.outputs_path.parent.parent
        kv_persist_dir = runs_root.parent / "kv_cache"
    # Weight identity for the persisted KV fingerprint: prefix blocks
    # are only reusable under the exact weights (and weight-quantize
    # mode) that produced them.
    kv_persist_sig = (
        f"ckpt:{target}:{step}" if target is not None
        else f"random:{ctx.seed or 0}"
    ) + (":wq-int8" if qweights is not None else "")
    if kv_offload:
        ctx.log_text("lm_server: host KV offload tier enabled")
    if kv_persist_dir:
        ctx.log_text(f"lm_server: prefix KV persistence at {kv_persist_dir}")
    engine = ServingEngine(
        params,
        cfg,
        slots=int(ctx.get_param("slots", 4)),
        max_len=seq,
        block_size=int(ctx.get_param("block_size", 16)),
        num_blocks=int(kv_blocks) if kv_blocks is not None else None,
        prefill_chunk=prefill_chunk if prefill_chunk > 0 else None,
        prefix_cache=str(ctx.get_param("prefix_cache", "1")).lower()
        not in ("0", "false", "no"),
        qweights=qweights,
        kv_quantize=kv_quantize,
        mesh=mesh if template is not None else None,
        eos_id=int(eos_id) if eos_id is not None else None,
        seed=ctx.seed or 0,
        spec_decode=spec_decode,
        spec_k=int(spec_k) if spec_k is not None else None,
        spec_min_ngram=(
            int(spec_min_ngram) if spec_min_ngram is not None else None
        ),
        kv_offload=kv_offload,
        kv_offload_blocks=(
            int(kv_offload_blocks) if kv_offload_blocks is not None else None
        ),
        kv_persist_dir=str(kv_persist_dir) if kv_persist_dir else None,
        kv_persist_sig=kv_persist_sig,
        # 0 / unset = the engine's defaults (docs/serving.md)
        state_snapshot_every=int(ctx.get_param("state_snapshot_every", 0) or 0),
        state_snapshots=int(ctx.get_param("state_snapshots", 0) or 0),
        # The process-wide registry: /metrics then also exports anything
        # else this worker records (pipeline waits, task timings).
        stats=stats_backends.get_stats(),
    ).start()

    from http.server import ThreadingHTTPServer

    # Control-plane drain: the fleet layer (or an operator) sends a
    # `drain` bus command before replacing this replica.  The handler
    # only flips the engine's admission flag (no I/O, no sleeps) —
    # new /generate calls get a typed 503 "draining" while in-flight
    # requests run to completion.
    from polyaxon_tpu.tracking.capture import get_capture_agent

    capture = get_capture_agent()

    def _on_drain(cmd):
        engine.drain()
        ctx.log_text("lm_server: drain command — no new admissions")
        capture.command_event(
            str(cmd.get("uuid") or ""), "complete", message="engine draining"
        )

    capture.register_handler("drain", _on_drain)

    meta = {
        "checkpoint_step": step,
        "target": target,
        "default_max_new": int(ctx.get_param("max_new_tokens", 64)),
        "request_timeout_s": float(ctx.get_param("request_timeout_s", 600)),
    }
    handler = _make_lm_handler(engine, cfg, meta, log=ctx.log_text)
    server = ThreadingHTTPServer((host, port), handler)
    ctx.log_text(
        f"lm_server: {cfg.n_params/1e6:.0f}M params, {engine.slots} slots "
        f"on {host}:{port}"
        + (f" (checkpoint step {step})" if step is not None else " (random init)")
    )
    serve_engine(server, engine)


def output_server(ctx: Context) -> None:
    """Serve a run's outputs dir over plain HTTP until stopped.

    The dependency-free notebook-kind analogue (and the test double for
    service plumbing): directory listing + file download for ``target``'s
    outputs.  Params: ``target``, ``logdir``, ``host`` (default 0.0.0.0 —
    the advertised service_url names the gang host, so the listener is
    network-visible; pass host: 127.0.0.1 for loopback-only).
    """
    import functools
    from http.server import SimpleHTTPRequestHandler, ThreadingHTTPServer

    root = _target_outputs(ctx)
    port = _service_port(ctx)
    # 0.0.0.0 so the advertised service_url (which names the gang host, not
    # loopback) is reachable on remote pools too.
    host = str(ctx.get_param("host", "0.0.0.0"))
    handler = functools.partial(SimpleHTTPRequestHandler, directory=str(root))
    server = ThreadingHTTPServer((host, port), handler)
    ctx.log_text(f"output_server serving {root} on {host}:{port}")
    server.serve_forever()
