"""A serving cell: an ``lm_server`` gang spawned by the ``Orchestrator`` and
driven over ``POST /generate`` from this process (which never imports jax).

Set-up = boot, weights from the seed, warm-up of the cell's shapes, pre-roll.
Then the window; then the window's requests are followed to their end; then
the gang is stopped and a child process on the freed chip runs the plain
reference over a sample of the served requests and reduces the trace.
"""

from __future__ import annotations

import http.client
import json
import shutil
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional
from urllib.parse import urlparse

import numpy as np

from .gang import Gang, get_json

MODEL_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "num_key_value_heads": "n_kv_heads",
}


def model_declarations(config: Dict[str, Any]) -> Dict[str, Any]:
    return {ours: config[theirs] for theirs, ours in MODEL_KEYS.items()}


class Request:
    """One ``/generate`` call and what the client saw of it."""

    __slots__ = ("spec", "body", "sent", "read", "ttft_s", "tokens", "error")

    def __init__(self, spec: Dict[str, Any]) -> None:
        self.spec = spec
        self.body = json.dumps({
            "prompts": [spec["prompt"]], "max_new_tokens": spec["max_new"],
            "temperature": spec["temperature"],
        }).encode()
        self.sent: Optional[float] = None
        self.read: Optional[float] = None
        self.ttft_s: Optional[float] = None
        self.tokens: Optional[List[int]] = None
        self.error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.error is None and self.tokens is not None

    def ttft_ms(self) -> Optional[float]:
        """The server's own time to the first token: a closed-loop client sends
        when its last reply is read, so nothing is ever due earlier than sent."""
        return self.ttft_s * 1e3 if self.ok and self.ttft_s is not None else None


def call(url: str, req: Request, timeout: float) -> None:
    """Send one request and record the typed outcome on it."""
    u = urlparse(url)
    req.sent = time.time()
    try:
        conn = http.client.HTTPConnection(u.hostname, u.port, timeout=timeout)
        try:
            conn.request("POST", "/generate", body=req.body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            payload = json.loads(resp.read() or b"{}")
            req.read = time.time()
        finally:
            conn.close()
        if resp.status != 200:
            kind = (payload.get("error") or {}).get("kind", "http")
            req.error = f"{resp.status}:{kind}"
            return
        tokens = payload["tokens"][0]
        if len(tokens) != req.spec["max_new"]:
            req.error = f"token_count:{len(tokens)}!={req.spec['max_new']}"
            return
        req.tokens = [int(t) for t in tokens]
        req.ttft_s = payload["ttft_s"][0]
    except Exception as e:  # noqa: BLE001 - a typed failure of this request
        req.read = time.time()
        req.error = f"{type(e).__name__}:{e}"


def closed_loop(gang: Gang, url: str, requests: List[Request], seconds: float,
                traffic: Dict[str, Any], on_open, on_close, on_followed) -> Dict[str, Any]:
    """``clients`` callers, each sending its next request when the last reply is
    read; all pull from the one list.  The window opens once every client has
    completed one request.  The clients keep pulling after it closes until the
    requests sent inside it have all been read and ``on_followed`` has run."""
    n_clients = int(traffic["clients"])
    timeout = float(traffic["follow_s"]) + 60.0
    lock = threading.Lock()
    state = {"next": 0, "in_flight": 0, "max_in_flight": 0, "stop": False}
    first_done = [False] * n_clients

    def client(i: int) -> None:
        while True:
            with lock:
                if state["stop"] or state["next"] >= len(requests):
                    return
                r = requests[state["next"]]
                state["next"] += 1
                state["in_flight"] += 1
                state["max_in_flight"] = max(state["max_in_flight"], state["in_flight"])
            call(url, r, timeout)
            with lock:
                state["in_flight"] -= 1
            first_done[i] = True

    threads = [threading.Thread(target=client, args=(i,), daemon=True,
                                name=f"bench-client-{i}") for i in range(n_clients)]
    for t in threads:
        t.start()
    limit = time.time() + 300.0
    while not all(first_done):
        gang.pump(0.05)
        gang.check_alive("during the pre-roll")
        if time.time() > limit or not any(t.is_alive() for t in threads):
            raise RuntimeError("pre-roll did not complete one request per client")
    base = time.time()
    on_open()
    while time.time() < base + seconds:
        gang.check_alive("during the run")
        gang.pump(0.05)
    on_close()
    end = base + seconds
    deadline = end + float(traffic["follow_s"])

    def sent_in_window() -> List[Request]:
        return [r for r in requests if r.sent is not None and base <= r.sent < end]

    while time.time() < deadline and not all(r.read is not None for r in sent_in_window()):
        gang.pump(0.05)
    on_followed()
    with lock:
        state["stop"] = True
    while any(t.is_alive() for t in threads) and time.time() < deadline:
        gang.pump(0.05)
    measured = sent_in_window()
    return {"base": base, "measured": measured, "window_s": seconds,
            "max_in_flight": state["max_in_flight"],
            "sent_in_order": [r for r in requests if r.sent is not None],
            "read_in_window": [r for r in requests
                               if r.ok and r.read is not None and base <= r.read < end]}


DRIVERS = {"closed_loop": closed_loop}


def run(cell, args, root, generator, t_start: float) -> Dict[str, Any]:
    config, traffic = cell.config, cell.traffic
    toy = bool(args.cpu_toy)
    model_seed = int(args.seed) % 2147483647
    engine = dict(config["engine"])
    spec = {
        "kind": "service",
        "declarations": {**model_declarations(config), **engine},
        "environment": {
            "seed": model_seed,
            "topology": {"accelerator": "cpu-1" if toy else f"v5e-{cell.chips}"},
        },
    }
    requests = [Request(s) for s in generator.schedule(
        traffic, int(args.seed), float(args.seconds), int(config["vocab_size"]))]
    gang = Gang(toy=toy, chips=cell.chips)
    marks: Dict[str, Any] = {"t_start": t_start}
    try:
        gang.submit(spec, cell.name)
        url = gang.wait_ready(900.0)
        marks["t_ready"] = time.time()
        health = get_json(f"{url}/healthz")
        state: Dict[str, Any] = {"capture_dir": None}

        def on_open() -> None:
            marks["t_open"] = time.time()
            marks["stats_open"] = get_json(f"{url}/v1/stats")

        def on_close() -> None:
            marks["stats_close"] = get_json(f"{url}/v1/stats")
            marks["t_close"] = time.time()

        def on_followed() -> None:
            """The traced window: taken once the window's requests are all read
            and with the load still on, because the program's capture stalls
            the engine's thread while it writes the trace out (PERF.md)."""
            marks["t_followed"] = time.time()
            if not int(args.trace):
                return
            capture = gang.profile(int(traffic.get("trace_steps", 40)),
                                   float(traffic.get("trace_seconds", 8.0)))
            state["capture_dir"] = gang.capture_dir(capture)
            limit = time.time() + 90.0
            while not (state["capture_dir"] / "manifest.json").exists() and time.time() < limit:
                gang.check_alive("during the capture")
                gang.pump(0.1)

        driven = DRIVERS[generator.DRIVER](
            gang, url, requests, float(args.seconds), traffic,
            on_open, on_close, on_followed)
        capture_dir = state["capture_dir"]
        # One more sample of the device's peak before the gang goes.
        time.sleep(2.5)
        gang.pump(0.3)
        gang.stop()
        for _ in range(5):
            gang.pump(0.2)
        out = {
            "marks": marks, "driven": driven, "health": health,
            "ledger": gang.last_ledger_row(),
            "memory_peak_bytes": gang.hbm_peak_bytes(),
            "capture_dir": None, "model_seed": model_seed,
        }
        if capture_dir is not None and capture_dir.exists():
            # Keep the trace past the gang's state directory.
            keep = tempfile.mkdtemp(prefix="bench_trace_")
            shutil.copytree(capture_dir, keep, dirs_exist_ok=True)
            out["capture_dir"] = keep
        return out
    finally:
        gang.close()


def sample_for_reference(measured: List[Request], n: int, seed: int) -> List[Dict[str, Any]]:
    """A sample of the window's finished requests, drawn from the seed, with the
    longest in it: whole groups of requests that share a prefix (a document's
    questions), in the seed's order after the longest's group, until ``n``
    requests are in."""
    groups: Dict[Any, List[Request]] = {}
    for i, r in enumerate(measured):
        if r.ok:
            groups.setdefault(r.spec.get("document", f"alone-{i}"), []).append(r)
    if not groups:
        return []
    size = lambda r: len(r.spec["prompt"]) + len(r.tokens)  # noqa: E731
    keys = list(groups)
    longest = max(keys, key=lambda k: max(size(r) for r in groups[k]))
    rest = [k for k in keys if k != longest]
    order = [longest] + [rest[i] for i in np.random.default_rng(seed).permutation(len(rest))]
    out, count = [], 0
    for k in order:
        if count >= n:
            break
        reqs = groups[k]
        shared = min(int(r.spec.get("shared_tokens", 0)) for r in reqs) if len(reqs) > 1 else 0
        out.append({"shared": reqs[0].spec["prompt"][:shared],
                    "requests": [{"prompt": r.spec["prompt"], "tokens": r.tokens} for r in reqs]})
        count += len(reqs)
    return out


def admitted_between(before: Dict[str, Any], after: Dict[str, Any],
                     sent_in_order: List[Request]) -> Dict[str, int]:
    """Prompt tokens, and those of them served from cached blocks, over the SAME
    requests: the ones the engine admitted between the two ``/v1/stats``
    snapshots.  It looks a prompt up in the prefix cache as it admits it, counts
    an admission in ``queue_wait_s`` and admits in the order of arrival, so they
    are that slice of the requests in the order sent."""
    first, last = (int(((s.get("latency") or {}).get("queue_wait_s") or {}).get("count", 0))
                   for s in (before, after))
    hits = after.get("prefix_cache_hits", 0) - before.get("prefix_cache_hits", 0)
    return {"prompt_tokens": sum(len(r.spec["prompt"]) for r in sent_in_order[first:last]),
            "cached_tokens": hits * int(after.get("block_size", 0))}


def finish(cell, args, raw: Dict[str, Any], manifest, peaks, root):
    from pathlib import Path

    from . import finish as fin

    config, traffic = cell.config, cell.traffic
    toy = bool(args.cpu_toy)
    marks, driven = raw["marks"], raw["driven"]
    measured: List[Request] = driven["measured"]
    window_s = float(driven["window_s"])
    records = [{
        "prompt_tokens": len(r.spec["prompt"]), "max_new": r.spec["max_new"],
        "output_tokens": len(r.tokens) if r.ok else 0, "ok": r.ok, "error": r.error,
        "ttft_ms": r.ttft_ms(),
    } for r in measured]
    failed = sum(1 for r in records if not r["ok"])
    if failed:
        print(f"failed requests: {sorted({r['error'] for r in records if r['error']})[:5]}",
              file=sys.stderr)

    work = Path(tempfile.mkdtemp(prefix="bench_post_"))
    try:
        want = config["correct"]
        if args.fault == "altered_token":
            # Planted by the tests only: where it is produced, a token of every
            # fourth reply becomes another.
            for r in [r for r in measured if r.ok][::4]:
                r.tokens[len(r.tokens) // 2] = (r.tokens[len(r.tokens) // 2] + 1) % int(
                    config["vocab_size"])
        job = {
            "work_dir": str(work), "reference_file": config["reference"],
            "config": {k: v for k, v in config.items() if not isinstance(v, dict)},
            "model_seed": raw["model_seed"],
            "groups": sample_for_reference(measured, int(want["sample_requests"]), int(args.seed)),
            "pad_tokens_to": want["pad_tokens_to"], "pad_rows_to": want["pad_rows_to"],
            "capture_dir": raw["capture_dir"],
        }
        post = fin.run_post(root, job, toy)
    finally:
        shutil.rmtree(work, ignore_errors=True)
        if raw.get("capture_dir"):
            shutil.rmtree(raw["capture_dir"], ignore_errors=True)
    device = dict(post["device"])
    peak = fin.peak_for(device, peaks, toy, cell.chips)
    device["memory_peak_bytes"] = raw["memory_peak_bytes"] or 0

    tokens_out = sum(len(r.tokens) for r in driven["read_in_window"])
    end_to_end = {
        "serve_tokens_per_s": tokens_out / window_s,
        "setup_s": marks["t_open"] - marks["t_start"],
    }
    serve_ctx = {
        "stats_open": marks["stats_open"], "stats_close": marks["stats_close"],
        "window_s": window_s, "measured": records,
        **admitted_between(marks["stats_open"], marks["stats_close"], driven["sent_in_order"]),
    }
    if serve_ctx["prompt_tokens"]:
        serve_ctx["hit_share"] = serve_ctx["cached_tokens"] / serve_ctx["prompt_tokens"]
    ledger_attrs = (raw.get("ledger") or {}).get("attrs") or {}
    run_ctx = {
        "config": config, "traffic": traffic, "peak": peak, "chips": cell.chips,
        "setup": {
            "boot_to_chip_s": marks["t_ready"] - marks["t_start"] - float(
                raw["health"]["engine"]["warmup"]["ready_s"]),
            "compile_cache_misses": ledger_attrs.get("compile_cache_misses"),
            "warmup_ready_s": raw["health"]["engine"]["warmup"]["ready_s"],
        },
        "serve": serve_ctx, "trace": post.get("trace"),
    }
    served = post.get("served") or {}
    print(f"served {json.dumps(served)} reference_s {post.get('reference_s')}", file=sys.stderr)
    compared = {name: {"value": served.get(name), "limit": float(limit)}
                for name, limit in want["limits"].items()}
    steady = marks["stats_close"].get("steady_state_compiles", 0) == 0
    return fin.compose(
        cell, args, manifest, device=device, attempted=len(records), failed=failed,
        end_to_end=end_to_end, run_ctx=run_ctx, compared=compared,
        trace=post.get("trace"), sound=(failed == 0 and bool(records) and steady))
