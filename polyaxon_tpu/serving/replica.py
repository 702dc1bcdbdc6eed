"""Standalone ``lm_server`` replica: the fleet's subprocess entrypoint.

``python -m polyaxon_tpu.serving.replica <spec.json>`` boots one
engine + the production HTTP handler (``_make_lm_handler``) with no
platform Context — the process-level unit
:class:`~polyaxon_tpu.serving.fleet.LocalServingFleet` provisions via
``spawner.transport.LocalExecTransport`` so fault injection (SIGKILL /
SIGSTOP) hits a real OS process, not a thread.

The spec is plain JSON::

    {
      "host": "127.0.0.1", "port": 8301, "seed": 0,
      "model": {"vocab_size": 64, "d_model": 32, ...},  # TransformerConfig ints
      "seq": 48, "slots": 4, "block_size": 16,
      "kv_blocks": null, "prefill_chunk": 0,
      "kv_offload": false, "kv_offload_blocks": 0,
      "kv_persist_dir": null, "kv_persist_sig": "",
      "max_new_tokens": 64, "request_timeout_s": 600.0,
      "retry_after_s": 1.0
    }

Random-init weights only (the fleet bench/test double); checkpointed
fleets go through the control-plane path (``orchestrator`` +
``builtins.services.lm_server``), which this entry deliberately does
not duplicate.
"""

from __future__ import annotations

import json
import sys


def serve(spec: dict) -> None:
    # Heavy imports stay inside serve() so `--help`-style failures and
    # spec parse errors don't pay for jax.
    import os

    # Same persistent compile cache the gang workers arm (before the jax
    # import, so jax reads it from the environment): a replacement or
    # scale-up replica warms from disk instead of compiling cold.
    from polyaxon_tpu.runtime.compilecache import enable_compile_cache

    enable_compile_cache()

    import jax

    from polyaxon_tpu.builtins.services import _make_lm_handler, serve_engine
    from polyaxon_tpu.models import TransformerConfig, init_params
    from polyaxon_tpu.serving import ServingEngine
    from polyaxon_tpu.tracking.trace import get_tracer

    # Label this process's spans with the replica name: span ids become
    # globally unique across the fleet and the router's merged trace
    # export gives each replica its own named Perfetto track.
    name = str(spec.get("name") or f"replica-{spec.get('port', 0)}")
    get_tracer().configure(process=name, process_id=os.getpid())

    model = {k: int(v) for k, v in (spec.get("model") or {}).items()}
    seq = int(spec.get("seq", 128))
    cfg = TransformerConfig(max_seq=seq, **model)
    params = init_params(jax.random.PRNGKey(int(spec.get("seed", 0))), cfg)

    kv_blocks = spec.get("kv_blocks")
    prefill_chunk = int(spec.get("prefill_chunk", 0) or 0)
    spec_decode = spec.get("spec_decode")
    spec_k = spec.get("spec_k")
    spec_min_ngram = spec.get("spec_min_ngram")
    kv_offload = spec.get("kv_offload")
    kv_offload_blocks = spec.get("kv_offload_blocks")
    kv_persist_dir = spec.get("kv_persist_dir")
    engine = ServingEngine(
        params,
        cfg,
        slots=int(spec.get("slots", 4)),
        max_len=seq,
        block_size=int(spec.get("block_size", 16)),
        num_blocks=int(kv_blocks) if kv_blocks is not None else None,
        prefill_chunk=prefill_chunk if prefill_chunk > 0 else None,
        seed=int(spec.get("seed", 0)),
        spec_decode=bool(spec_decode) if spec_decode is not None else None,
        spec_k=int(spec_k) if spec_k is not None else None,
        spec_min_ngram=(
            int(spec_min_ngram) if spec_min_ngram is not None else None
        ),
        kv_offload=bool(kv_offload) if kv_offload is not None else None,
        kv_offload_blocks=(
            int(kv_offload_blocks) if kv_offload_blocks is not None else None
        ),
        kv_persist_dir=str(kv_persist_dir) if kv_persist_dir else None,
        kv_persist_sig=str(spec.get("kv_persist_sig", "")),
    )
    del params  # the engine reads its own cfg.dtype tree; free the float32 one
    engine.start()

    meta = {
        "checkpoint_step": None,
        "target": None,
        "default_max_new": int(spec.get("max_new_tokens", 64)),
        "request_timeout_s": float(spec.get("request_timeout_s", 600.0)),
        "retry_after_s": float(spec.get("retry_after_s", 1.0)),
    }
    from http.server import ThreadingHTTPServer

    handler = _make_lm_handler(engine, cfg, meta)
    host = str(spec.get("host", "127.0.0.1"))
    port = int(spec["port"])
    server = ThreadingHTTPServer((host, port), handler)
    print(f"replica: serving on {host}:{port} with {jax.devices()}", flush=True)
    serve_engine(server, engine)


def main(argv) -> int:
    if len(argv) != 1:
        print("usage: python -m polyaxon_tpu.serving.replica <spec.json>")
        return 2
    with open(argv[0]) as f:
        spec = json.load(f)
    serve(spec)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
