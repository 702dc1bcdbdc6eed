"""Known-good fixture: the real hot-path shapes; zero findings expected.

Mirrors the package's idioms: ``lax.scan`` over pool pytrees, donated
jit rebinds, registry writes under the lock, non-blocking beat hooks,
catalogued knobs, timeouts everywhere.
"""

import threading
import urllib.request

import jax
import jax.numpy as jnp
from jax import lax


# -- pure traced functions (decode.py shape) ---------------------------------

def decode_loop(pool, tokens):
    def body(carry, tok):
        pool, step = carry
        new = jnp.take(pool, tok, axis=0)
        return (pool, step + 1), new

    return lax.scan(body, (pool, 0), tokens)


fn = jax.jit(decode_loop)


# -- donated rebinds (train.py / engine.py shape) ----------------------------

def train_step(params, opt_state, batch):
    return params, opt_state


step = jax.jit(train_step, donate_argnums=(0, 1))


def loop(params, opt_state, batch):
    params, opt_state = step(params, opt_state, batch)
    return params, opt_state


# -- registry writes under the lock (db/registry.py shape) -------------------

class GoodRegistry:
    def __init__(self, conn):
        self._lock = threading.Lock()
        self._db = conn

    def write(self, run_id):
        with self._lock, self._db as conn:
            conn.execute("UPDATE runs SET x = 1 WHERE id = ?", (run_id,))

    def _delete_tree_locked(self, run_id):
        # *_locked convention: caller holds self._lock
        self._db.execute("DELETE FROM runs WHERE id = ?", (run_id,))

    def read(self, run_id):
        return self._db.execute(
            "SELECT * FROM runs WHERE id = ?", (run_id,)
        ).fetchone()


# -- non-blocking tick paths (capture.py shape) ------------------------------

class QuietAgent:
    def poll(self):
        return list(self._pending())

    def _pending(self):
        return ()


def wire(reporter):
    agent = QuietAgent()
    reporter.add_beat_hook(agent.poll)


# -- catalogued knobs + bounded network I/O ----------------------------------

KNOWN = "POLYAXON_TPU_WATCHDOG_K"
FAMILY_MEMBER = "POLYAXON_TPU_ALERT_GOODPUT_LOW_FLOOR"
WILDCARD_MENTION = "tune via POLYAXON_TPU_REMEDIATION_* knobs"


def notify(url, payload):
    return urllib.request.urlopen(url, data=payload, timeout=5.0)


# -- GL007: bounded metric labels ---------------------------------------------

def labeled_key(name, **labels):  # stand-in for stats.metrics.labeled_key
    return name


_CODE_CLASSES = {2: "2xx", 4: "4xx", 5: "5xx"}


def export_good_labels(stats, run_id, method, code):
    # Plain variables and catalogued keys: the runtime series cap is the
    # backstop for value cardinality; no interpolation at the call site.
    stats.gauge(labeled_key("queue_depth_ok", run=run_id), 1.0)
    stats.incr(
        labeled_key(
            "api_request_ok",
            method=method,
            code=_CODE_CLASSES.get(code // 100, "other"),
        )
    )
    stats.incr(labeled_key("plain_counter_ok"))


# -- GL008: span-name hygiene -------------------------------------------------

def trace_good_spans(tracer, match, step):
    # Catalogued literal name; the variable part rides as an attribute.
    with tracer.span("train.step", step=step):
        pass
    # re.Match.span() / .span(group) — not tracer calls, must not flag.
    match.span()
    match.span(1)


class EngineLikeForwarders:
    """The serving engine's forwarding-wrapper shape: the name parameter
    passes through verbatim, so the literal check applies at call sites."""

    def __init__(self, tracer):
        self._tracer = tracer

    def _trace_span(self, req, name, start, duration, **attrs):
        self._tracer.record_span(name, start=start, duration=duration, **attrs)

    def _trace_hot(self, req, name, start, duration, **attrs):
        self._trace_span(req, name, start, duration, **attrs)

    def prefill(self, req, t0, dt):
        self._trace_span(req, "serving.prefill.chunk", t0, dt)
        self._trace_hot(req, "serving.decode.step", t0, dt)
