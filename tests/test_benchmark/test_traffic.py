"""Generators are pure functions of (traffic file, seed, seconds); the
population does not change with the seed; the clients' arithmetic."""

import collections
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

import pytest

from benchmark.generators import closed_sessions, strata, train_stream
from benchmark.harness import drive_lm_server as serve
from benchmark.harness.stats import percentile, spread

ROOT = Path(__file__).resolve().parents[2]
MIXES = {p.stem: json.loads(p.read_text()) for p in (ROOT / "benchmark/traffic").glob("*.json")}
GENERATORS = {"closed_sessions": closed_sessions, "train_stream": train_stream}


@pytest.mark.parametrize("name", sorted(MIXES))
def test_same_seed_same_schedule_and_other_seed_same_population(name):
    mix = MIXES[name]
    gen = GENERATORS[mix["kind"]]
    a, b, c = (gen.schedule(mix, s, 45.0, 1000) for s in (2**31 + 11, 2**31 + 11, 7))
    assert a == b
    if mix["kind"] == "train_stream":
        return
    assert a != c

    if mix["kind"] == "closed_sessions":
        # the seed draws the token ids; the sizes and their order are the mix's own, so
        # the head of the list that a window reaches is the same work whatever the seed
        sizes = lambda reqs: [(r["document"], len(r["prompt"]), r["max_new"]) for r in reqs]  # noqa: E731
        assert sizes(a) == sizes(c)
        assert all(x["prompt"] != y["prompt"] for x, y in zip(a, c))
        other = gen.schedule({**mix, "order_seed": mix["order_seed"] + 1}, 7, 45.0, 1000)
        assert sizes(other) != sizes(c)
        assert sorted(r["max_new"] for r in other) == sorted(r["max_new"] for r in c)
        # whole groups of documents hold the same lengths whatever the order
        group = mix["document_group"]
        lens = lambda reqs: [n for _, n in sorted({r["document"]: r["shared_tokens"] for r in reqs}.items())]  # noqa: E731
        for g in range(0, mix["documents"], group):
            assert sorted(lens(other)[g:g + group]) == sorted(lens(c)[g:g + group])


def test_stratified_lengths_are_quantiles_in_the_generators_order():
    import numpy as np

    dist = {"dist": "log_uniform", "min": 32, "max": 1024}
    a = strata.lengths(dist, 300, np.random.default_rng(1))
    b = strata.lengths(dist, 300, np.random.default_rng(2))
    assert sorted(a) == sorted(b) and a != b
    assert min(a) >= 32 and max(a) <= 1024 and 270 < sum(a) / 300 < 300


def test_closed_list_keeps_other_documents_between_two_questions_on_one():
    mix = MIXES["docqa-closed8"]
    reqs = closed_sessions.schedule(mix, 5, 45.0, 1000)
    assert len(reqs) == mix["documents"] * mix["questions_per_document"]
    last = {}
    gaps = []
    for i, r in enumerate(reqs):
        if r["document"] in last:
            gaps.append(i - last[r["document"]] - 1)
        last[r["document"]] = i
    body = gaps[: len(gaps) * 3 // 4]  # the list's tail runs out of other documents
    assert min(body) >= mix["documents_in_rotation"] - 2
    assert all(len(r["prompt"]) >= mix["document_tokens"]["min"] for r in reqs)
    assert len({r["document"] for r in reqs}) == mix["documents"]


def test_failures_rank_above_every_success():
    assert percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, None], 90) == 9
    assert percentile([1, 2, 3, 4, 5, 6, 7, 8, None, None], 90) == float("inf")
    assert percentile([1, 2, 3, 4, 5, 6, 7, 8, None, None], 90, missing=60000.0) == 60000.0
    assert percentile(list(range(1, 301)), 90) == 270
    assert abs(spread([10, 10.1, 10.2, 9.9, 10.0, 10.3]) - 0.0248) < 1e-3


class _SlowServer:
    """A stand-in for /generate that takes ``delay`` seconds and counts how many
    calls are in flight."""

    def __init__(self, delay):
        outer = self
        self.in_flight = self.max_in_flight = 0
        self.lock = threading.Lock()

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_POST(self):
                n = int(self.headers["Content-Length"])
                req = json.loads(self.rfile.read(n))
                with outer.lock:
                    outer.in_flight += 1
                    outer.max_in_flight = max(outer.max_in_flight, outer.in_flight)
                time.sleep(delay)
                with outer.lock:
                    outer.in_flight -= 1
                body = json.dumps({"tokens": [[1] * req["max_new_tokens"]], "ttft_s": [delay / 2]}).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.url = f"http://127.0.0.1:{self.server.server_address[1]}"
        threading.Thread(target=self.server.serve_forever, daemon=True).start()

    def close(self):
        self.server.shutdown()
        self.server.server_close()


class _NoGang:
    def pump(self, wait=0.0):
        time.sleep(min(wait, 0.01))

    def check_alive(self, what):
        pass


def _requests(n, phase="window", gap=0.02):
    return [serve.Request({"phase": phase, "due_s": i * gap, "prompt": [1, 2, 3], "max_new": 4,
                           "temperature": 0.0}) for i in range(n)]


def test_closed_loop_never_has_more_than_its_clients_in_flight():
    srv = _SlowServer(0.02)
    try:
        reqs = _requests(40, phase="list")
        out = serve.closed_loop(_NoGang(), srv.url, reqs, 0.3,
                                {"clients": 3, "follow_s": 5.0},
                                lambda: None, lambda: None, lambda: None)
        assert srv.max_in_flight <= 3 and out["max_in_flight"] <= 3
        assert out["measured"] and all(r.ok for r in out["measured"])
        assert all(r.ttft_ms() == pytest.approx(10.0) for r in out["measured"])
        assert [r.spec for r in out["sent_in_order"]][: len(out["measured"])] == [
            r.spec for r in reqs][: len(out["measured"])]
        assert set(map(id, out["read_in_window"])) <= set(map(id, out["sent_in_order"]))
    finally:
        srv.close()


def test_a_short_or_failed_reply_is_a_typed_failure():
    srv = _SlowServer(0.0)
    try:
        r = serve.Request({"phase": "window", "due_s": 0, "prompt": [1], "max_new": 4, "temperature": 0.0})
        r.spec["max_new"] = 5  # the server will send 4 tokens: body was built with 4
        serve.call(srv.url, r, 5.0)
        assert not r.ok and r.error.startswith("token_count")
        bad = serve.Request({"phase": "window", "due_s": 0, "prompt": [1], "max_new": 4, "temperature": 0.0})
        serve.call("http://127.0.0.1:9", bad, 1.0)
        assert not bad.ok and bad.ttft_ms() is None
    finally:
        srv.close()


def test_every_question_says_how_much_of_its_prompt_its_document_is():
    reqs = closed_sessions.schedule(MIXES["docqa-closed8"], 9, 45.0, 1000)
    by_doc = collections.defaultdict(list)
    for r in reqs:
        by_doc[r["document"]].append(r)
    for group in by_doc.values():
        shared = group[0]["shared_tokens"]
        assert all(r["shared_tokens"] == shared and shared % 16 == 0 for r in group)
        assert all(r["prompt"][:shared] == group[0]["prompt"][:shared] for r in group)
        assert all(32 <= len(r["prompt"]) - shared <= 128 for r in group)


def _done(document, prompt, tokens, shared):
    r = serve.Request({"phase": "list", "document": document, "shared_tokens": shared,
                       "prompt": prompt, "max_new": len(tokens), "temperature": 0.0})
    r.tokens = tokens
    return r


def test_the_sample_is_whole_documents_from_the_seed_with_the_longest_request_first():
    docs = {d: list(range(d * 100, d * 100 + 16 + d)) for d in range(6)}
    measured = [_done(d, docs[d] + [7, q], [1, 2, 3], len(docs[d])) for q in range(3) for d in range(6)]
    failed = _done(5, docs[5] + [9] * 50, [1], len(docs[5]))
    failed.error = "503:timeout"
    a, b, c = (serve.sample_for_reference(measured + [failed], 7, seed) for seed in (1, 1, 2))
    assert a == b and a != c
    assert a[0]["shared"] == docs[5] and c[0]["shared"] == docs[5]  # the longest finished request
    assert [len(g["requests"]) for g in a] == [3, 3, 3]  # whole groups until 7 requests are in
    assert all(r["prompt"][: len(g["shared"])] == g["shared"] for g in a for r in g["requests"])
    assert len({tuple(g["shared"]) for g in a}) == 3
    alone = serve.sample_for_reference([_done(0, [1, 2, 3], [4], 2)], 5, 0)
    assert alone == [{"shared": [], "requests": [{"prompt": [1, 2, 3], "tokens": [4]}]}]
    assert serve.sample_for_reference([failed], 5, 0) == []


def test_hits_and_prompt_tokens_are_counted_over_the_requests_admitted_between_the_snapshots():
    sent = [_done(i, [0] * (100 + i), [1], 0) for i in range(10)]

    def stats(admitted, hits):
        return {"latency": {"queue_wait_s": {"count": float(admitted)}},
                "prefix_cache_hits": hits, "block_size": 16}

    got = serve.admitted_between(stats(2, 10), stats(7, 25), sent)
    assert got == {"prompt_tokens": sum(100 + i for i in range(2, 7)), "cached_tokens": 15 * 16}
    assert serve.admitted_between({}, stats(3, 4), sent) == {
        "prompt_tokens": 100 + 101 + 102, "cached_tokens": 64}
