"""Central ``POLYAXON_TPU_*`` env-knob catalog + typed accessors.

Every process-level env knob the platform reads lives here: name, type,
default, and one line of doc.  Before this module the ~40 knobs were
scattered across 18 files, each with its own ad-hoc ``_env_float``
helper — and a typo'd knob name silently no-oped forever.  Now:

- call sites read through the typed accessors (:func:`knob_bool` /
  :func:`knob_int` / :func:`knob_float` / :func:`knob_str`), which
  raise ``KeyError`` on a name the catalog doesn't know — a typo fails
  loudly at import/construction time instead of silently returning the
  hardcoded default;
- graft-lint rule **GL005** (``polyaxon_tpu/analysis``) closes the loop
  statically: every ``POLYAXON_TPU_*`` string literal in the package
  must resolve to a catalog entry, and every catalog entry must be
  referenced somewhere — no dead knobs, no phantom knobs;
- :func:`reference_table` renders the catalog as the markdown knob
  table in ``docs/observability.md`` (kept in sync by
  ``tests/test_analysis/test_knobs.py``).

Two kinds of entry:

- plain knobs — one env var, one default (the common case);
- *families* (``prefix=True``) — a declared prefix with dynamic
  suffixes, e.g. ``POLYAXON_TPU_ALERT_<RULE>_<PARAM>``; read through
  the ``family_*`` accessors which validate the prefix is declared.

This module imports nothing from the package (stdlib only) so every
layer — including pre-jax worker boot — can use it without cycles.
The cluster-editable *option* store (``conf/options.py``) is a separate
namespace: options are DB-backed and resolve DB → env → default; knobs
are env-only process configuration.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

__all__ = [
    "Knob",
    "KNOBS",
    "FAMILIES",
    "knob_bool",
    "knob_int",
    "knob_float",
    "knob_str",
    "knob_default",
    "family_prefix",
    "family_value",
    "family_float",
    "reference_table",
]

#: Values (lowercased) that read as False for bool knobs.  An *empty*
#: string also reads as False — matching the historical call sites
#: (``POLYAXON_TPU_SERVING_WARMUP=""`` disables warmup).
_FALSY = ("0", "false", "off", "no", "")


@dataclass(frozen=True)
class Knob:
    name: str
    kind: str  # "bool" | "int" | "float" | "str"
    default: Any
    doc: str
    group: str = "misc"
    #: True = a declared prefix family with dynamic suffixes
    #: (``POLYAXON_TPU_ALERT_<RULE>_<PARAM>``), not a single env var.
    prefix: bool = False


_ALL: List[Knob] = [
    # -- gang rendezvous contract (spawner-written, worker-read) -----------
    Knob("POLYAXON_TPU_RUN_ID", "int", None,
         "run id of the gang this process belongs to", "gang-env"),
    Knob("POLYAXON_TPU_RUN_UUID", "str", None, "run uuid", "gang-env"),
    Knob("POLYAXON_TPU_RUN_DIR", "str", None,
         "the run's store directory", "gang-env"),
    Knob("POLYAXON_TPU_SPEC_PATH", "str", None,
         "path to the materialized run spec", "gang-env"),
    Knob("POLYAXON_TPU_PROCESS_ID", "int", None,
         "this process's gang rank", "gang-env"),
    Knob("POLYAXON_TPU_NUM_PROCESSES", "int", None,
         "gang size (hosts)", "gang-env"),
    Knob("POLYAXON_TPU_COORDINATOR", "str", "",
         "jax.distributed coordinator address ('' = single-host)",
         "gang-env"),
    Knob("POLYAXON_TPU_DEVICES_PER_HOST", "int", 1,
         "local device count per host", "gang-env"),
    Knob("POLYAXON_TPU_ACCELERATOR", "str", "cpu",
         "accelerator backend (cpu/tpu)", "gang-env"),
    Knob("POLYAXON_TPU_MESH", "str", "{}",
         "JSON mesh axes ({axis: size})", "gang-env"),
    Knob("POLYAXON_TPU_MESH_DCN", "str", "{}",
         "JSON subset of mesh axes spanning slices (DCN)", "gang-env"),
    Knob("POLYAXON_TPU_STRATEGY", "str", "ddp",
         "parallelism strategy template name", "gang-env"),
    Knob("POLYAXON_TPU_STRATEGY_OPTIONS", "str", "{}",
         "JSON strategy options", "gang-env"),
    Knob("POLYAXON_TPU_HEARTBEAT_INTERVAL", "float", 5.0,
         "reporter heartbeat cadence (s)", "gang-env"),
    Knob("POLYAXON_TPU_SEED", "int", None,
         "deterministic seed ('' = unseeded)", "gang-env"),
    Knob("POLYAXON_TPU_DATA_DIR", "str", "",
         "store layout's shared data/ dir (registered datasets)",
         "gang-env"),
    Knob("POLYAXON_TPU_SERVICE_PORT", "str", "",
         "dispatch-time allocated port for kind:service gangs",
         "gang-env"),
    # -- tracing / ledger ---------------------------------------------------
    Knob("POLYAXON_TPU_TRACE_SAMPLE", "float", 1.0,
         "span sampling rate for normal spans", "tracing"),
    Knob("POLYAXON_TPU_TRACE_HOT_SAMPLE", "float", 0.05,
         "span sampling rate for hot-path spans", "tracing"),
    Knob("POLYAXON_TPU_LEDGER_INTERVAL_S", "float", 30.0,
         "min spacing of cumulative utilization-ledger rows", "tracing"),
    Knob("POLYAXON_TPU_TRACE_REQUESTS", "bool", True,
         "request-scoped distributed tracing across router → replica → "
         "engine (waterfalls, /v1/trace exports, exemplars)", "tracing"),
    Knob("POLYAXON_TPU_TRACE_EXEMPLARS", "int", 5,
         "slowest fully-traced requests kept per exemplar window "
         "(0 = exemplars off)", "tracing"),
    Knob("POLYAXON_TPU_TRACE_EXEMPLAR_WINDOW_S", "float", 300.0,
         "sliding window for the slow-request exemplar ring (s)",
         "tracing"),
    # -- stall watchdog (worker side) --------------------------------------
    Knob("POLYAXON_TPU_WATCHDOG_K", "float", 8.0,
         "stall deadline = k x rolling median step dt", "watchdog"),
    Knob("POLYAXON_TPU_WATCHDOG_FLOOR_S", "float", 30.0,
         "stall deadline lower clamp (s)", "watchdog"),
    Knob("POLYAXON_TPU_WATCHDOG_CEILING_S", "float", 600.0,
         "stall deadline upper clamp, and the deadline before any dt "
         "sample exists (s)", "watchdog"),
    Knob("POLYAXON_TPU_WATCHDOG_INTERVAL_S", "float", 1.0,
         "watchdog poll period (s); <= 0 disables the thread", "watchdog"),
    Knob("POLYAXON_TPU_PROGRESS_INTERVAL_S", "float", 2.0,
         "min spacing of typed progress report lines (s)", "watchdog"),
    # -- gang watcher / anomaly detection (control plane) ------------------
    Knob("POLYAXON_TPU_WATCHER_POLL_BYTES", "int", 4 * 1024 * 1024,
         "per-poll read budget per process report file", "watcher"),
    Knob("POLYAXON_TPU_STALL_AFTER_S", "float", 60.0,
         "gang declared stalled when the newest beat is older than this "
         "but heartbeats stay fresh", "watcher"),
    Knob("POLYAXON_TPU_STRAGGLER_LAG_STEPS", "float", 50.0,
         "host straggler threshold vs the gang median step", "watcher"),
    Knob("POLYAXON_TPU_STALL_HEARTBEAT_FRESH_S", "float", 30.0,
         "heartbeat freshness window for the stall predicate", "watcher"),
    # -- alert engine -------------------------------------------------------
    Knob("POLYAXON_TPU_ALERT_INTERVAL_S", "float", 1.0,
         "per-run alert rule evaluation throttle (s)", "alerts"),
    Knob("POLYAXON_TPU_ALERT_", "float", None,
         "per-rule parameter family: POLYAXON_TPU_ALERT_<RULE>_<PARAM> "
         "(e.g. _GOODPUT_LOW_FLOOR) and _<RULE>_ENABLED", "alerts",
         prefix=True),
    # -- remediation engine -------------------------------------------------
    Knob("POLYAXON_TPU_REMEDIATION_ENABLED", "bool", True,
         "remediation master switch (off = legacy blind restart)",
         "remediation"),
    Knob("POLYAXON_TPU_REMEDIATION_BUDGET", "int", 16,
         "max non-skipped remediation actions per run", "remediation"),
    Knob("POLYAXON_TPU_REMEDIATION_BACKOFF_BASE_S", "str", "",
         "relaunch backoff base seconds ('' = the plan's "
         "backoff_seconds)", "remediation"),
    Knob("POLYAXON_TPU_REMEDIATION_BACKOFF_MAX_S", "float", 300.0,
         "relaunch backoff cap (s)", "remediation"),
    Knob("POLYAXON_TPU_REMEDIATION_CHECKPOINT_ALERTS", "str", "run_stalled",
         "comma-separated alert rules whose firing edge triggers "
         "checkpoint-now", "remediation"),
    Knob("POLYAXON_TPU_REMEDIATION_EVICT", "bool", False,
         "opt-in straggler eviction + elastic gang re-form", "remediation"),
    Knob("POLYAXON_TPU_REMEDIATION_COMMAND_TIMEOUT_S", "float", 30.0,
         "how long an issued command may stay unresolved before the "
         "action fails", "remediation"),
    Knob("POLYAXON_TPU_REMEDIATION_DRAIN_ALERTS", "str",
         "serving_ttft_p99,heartbeat_stale",
         "comma-separated alert rules whose firing edge triggers "
         "drain+replace on a serving-fleet replica", "remediation"),
    # -- serving ------------------------------------------------------------
    Knob("POLYAXON_TPU_SERVING_WARMUP", "bool", True,
         "pre-compile the whole serving fn family behind the readiness "
         "gate before traffic", "serving"),
    Knob("POLYAXON_TPU_SERVING_SPEC_DECODE", "bool", False,
         "speculative decoding: self-draft multi-token steps on the "
         "paged engine (greedy requests only)", "serving"),
    Knob("POLYAXON_TPU_SERVING_SPEC_K", "int", 4,
         "max drafted tokens per lane per verify step", "serving"),
    Knob("POLYAXON_TPU_SERVING_SPEC_MIN_NGRAM", "int", 2,
         "n-gram length the prompt-lookup drafter matches against the "
         "request's own context", "serving"),
    Knob("POLYAXON_TPU_SERVING_STATS_WINDOW_S", "float", 60.0,
         "trailing window (s) for the *_window variants of /v1/stats "
         "lifetime ratios (prefix_cache_hit_rate_window, "
         "spec_accept_rate_window)", "serving"),
    # -- hierarchical KV (host offload tier + persistent prefix store) -----
    Knob("POLYAXON_TPU_KV_OFFLOAD", "bool", False,
         "host-memory KV tier: parked sequences spill their private "
         "blocks to host and cold prefixes demote instead of evicting",
         "kv-tier"),
    Knob("POLYAXON_TPU_KV_OFFLOAD_BLOCKS", "int", 0,
         "host-tier budget for DEMOTED prefix blocks (0 = unbounded; "
         "parked-sequence spills are pinned and never count)", "kv-tier"),
    Knob("POLYAXON_TPU_KV_PERSIST_DIR", "str", "",
         "prefix-store directory ('' = persistence off); normally the "
         "store layout's kv_cache/ dir so every replica shares it",
         "kv-tier"),
    Knob("POLYAXON_TPU_KV_PERSIST_BLOCKS", "int", 64,
         "max prefix blocks per persisted snapshot (hottest-first with "
         "chain closure)", "kv-tier"),
    Knob("POLYAXON_TPU_KV_PERSIST_INTERVAL_S", "float", 60.0,
         "min spacing of idle-time prefix-store snapshots (stop() "
         "always writes a final one)", "kv-tier"),
    # -- fleet router (control-plane request routing) ----------------------
    Knob("POLYAXON_TPU_ROUTER_PROBE_INTERVAL_S", "float", 1.0,
         "health/stats probe cadence per replica (s)", "router"),
    Knob("POLYAXON_TPU_ROUTER_PROBE_TIMEOUT_S", "float", 2.0,
         "per-probe HTTP timeout (s)", "router"),
    Knob("POLYAXON_TPU_ROUTER_REQUEST_TIMEOUT_S", "float", 600.0,
         "proxied /generate timeout per attempt (s)", "router"),
    Knob("POLYAXON_TPU_ROUTER_SHED_OCCUPANCY", "float", 0.95,
         "fleet-mean occupancy ceiling; at/above it new requests are "
         "shed with a typed 429 + Retry-After", "router"),
    Knob("POLYAXON_TPU_ROUTER_RETRY_AFTER_S", "float", 1.0,
         "Retry-After seconds advertised on shed (429) responses",
         "router"),
    Knob("POLYAXON_TPU_ROUTER_RETRY_LIMIT", "int", 2,
         "max failover retries per request on connection error/replica "
         "death (admission is idempotent before the first token)",
         "router"),
    Knob("POLYAXON_TPU_ROUTER_EJECT_FAILURES", "int", 2,
         "consecutive probe/request failures before a replica is "
         "ejected from the rotation", "router"),
    Knob("POLYAXON_TPU_ROUTER_EJECT_BACKOFF_S", "float", 1.0,
         "first re-admission probe delay after ejection (s); doubles "
         "per consecutive failed re-admission", "router"),
    Knob("POLYAXON_TPU_ROUTER_EJECT_BACKOFF_MAX_S", "float", 30.0,
         "re-admission backoff cap (s)", "router"),
    Knob("POLYAXON_TPU_ROUTER_AFFINITY_TOKENS", "int", 16,
         "prompt-prefix length hashed for replica affinity (0 = no "
         "affinity, pure least-loaded)", "router"),
    Knob("POLYAXON_TPU_ROUTER_AFFINITY_SLACK", "float", 0.25,
         "base load excess (affine minus least-loaded, per-slot) the "
         "affine replica may carry before affinity yields", "router"),
    Knob("POLYAXON_TPU_ROUTER_AFFINITY_HIT_SLACK", "float", 0.75,
         "extra affinity slack earned per unit of the affine replica's "
         "prefix_cache_hit_rate (warm caches justify routing into a "
         "busier replica)", "router"),
    # -- serving fleet (replica gang lifecycle) ----------------------------
    Knob("POLYAXON_TPU_FLEET_REPLICAS", "int", 2,
         "default replica count for a serving fleet", "fleet"),
    Knob("POLYAXON_TPU_FLEET_DRAIN_DEADLINE_S", "float", 30.0,
         "max time a draining replica may hold in-flight requests "
         "before it is replaced anyway", "fleet"),
    Knob("POLYAXON_TPU_FLEET_READY_TIMEOUT_S", "float", 120.0,
         "how long a replacement replica may take to reach ready "
         "before the drain/replace action fails", "fleet"),
    # -- fleet autoscaler (shed/occupancy-driven N resizing) ---------------
    Knob("POLYAXON_TPU_AUTOSCALER_ENABLED", "bool", True,
         "fleet autoscaler master switch (an attached autoscaler still "
         "tracks signals when off, but never resizes)", "autoscaler"),
    Knob("POLYAXON_TPU_AUTOSCALER_SHED_RATE", "float", 0.05,
         "windowed shed fraction (sheds/requests per tick) at/above "
         "which sustained overload triggers scale-up", "autoscaler"),
    Knob("POLYAXON_TPU_AUTOSCALER_IDLE_OCCUPANCY", "float", 0.1,
         "fleet-mean occupancy floor; sustained occupancy below it "
         "(with zero sheds) triggers drain-down", "autoscaler"),
    Knob("POLYAXON_TPU_AUTOSCALER_MIN_REPLICAS", "int", 1,
         "scale-down floor — the fleet never drains below this",
         "autoscaler"),
    Knob("POLYAXON_TPU_AUTOSCALER_MAX_REPLICAS", "int", 4,
         "scale-up ceiling", "autoscaler"),
    Knob("POLYAXON_TPU_AUTOSCALER_UP_HOLD_S", "float", 5.0,
         "hysteresis: the shed signal must hold this long before a "
         "scale-up fires", "autoscaler"),
    Knob("POLYAXON_TPU_AUTOSCALER_DOWN_HOLD_S", "float", 30.0,
         "hysteresis: the idle signal must hold this long before a "
         "drain-down fires", "autoscaler"),
    Knob("POLYAXON_TPU_AUTOSCALER_UP_COOLDOWN_S", "float", 15.0,
         "min spacing between scale-up decisions", "autoscaler"),
    Knob("POLYAXON_TPU_AUTOSCALER_DOWN_COOLDOWN_S", "float", 60.0,
         "min spacing between scale-down decisions; a completed "
         "scale-UP also re-arms it (flap suppression)", "autoscaler"),
    Knob("POLYAXON_TPU_AUTOSCALER_BUDGET", "int", 0,
         "hard cap on autoscaler decisions per fleet (0 = inherit "
         "POLYAXON_TPU_REMEDIATION_BUDGET)", "autoscaler"),
    # -- worker / monitoring ------------------------------------------------
    Knob("POLYAXON_TPU_RESOURCE_INTERVAL", "float", 10.0,
         "host/device resource sampler cadence (s)", "worker"),
    # -- control-plane self-telemetry --------------------------------------
    Knob("POLYAXON_TPU_METRICS_MAX_SERIES", "int", 1024,
         "per-metric cap on distinct label sets in MemoryStats; overflow "
         "folds into one {...=\"other\"} series (+ one warning)",
         "cp-telemetry"),
    Knob("POLYAXON_TPU_RETENTION_SWEEP_ROWS", "int", 20000,
         "per-tick row budget for the registry retention sweep (one "
         "transaction per tick; leftovers age out on later ticks)",
         "cp-telemetry"),
    Knob("POLYAXON_TPU_WS_TAIL_MAX_BATCH", "int", 500,
         "max rows a WS tail sends per poll; the remainder is deferred "
         "to the next poll and exported as ws_tail_backlog_rows",
         "cp-telemetry"),
    # -- metric history (in-process TSDB + scrape phase) -------------------
    Knob("POLYAXON_TPU_TSDB_ENABLED", "bool", True,
         "metric-history master switch: the monitor tick's scrape phase, "
         "the registry metric_samples write-behind, and the query API",
         "tsdb"),
    Knob("POLYAXON_TPU_TSDB_SCRAPE_INTERVAL_S", "float", 5.0,
         "scrape cadence (s) — the phase runs every monitor tick but "
         "only samples when due, so tick rate doesn't multiply cost",
         "tsdb"),
    Knob("POLYAXON_TPU_TSDB_RAW_POINTS", "int", 720,
         "raw ring length per series (at the default 5s cadence: 1h)",
         "tsdb"),
    Knob("POLYAXON_TPU_TSDB_ROLLUP_POINTS", "int", 360,
         "rollup ring length per series per stage (10s stage: 1h; "
         "1m stage: 6h of min/max/sum/count buckets)", "tsdb"),
    Knob("POLYAXON_TPU_TSDB_MAX_SERIES", "int", 2048,
         "per-base-name cap on distinct label sets in the MetricStore; "
         "overflow folds into one {...=\"other\"} series", "tsdb"),
    Knob("POLYAXON_TPU_TSDB_FLUSH_ROWS", "int", 512,
         "max metric_samples rows flushed to the registry per scrape "
         "(write-behind batch size)", "tsdb"),
    Knob("POLYAXON_TPU_TSDB_PENDING_MAX", "int", 8192,
         "bound on samples queued for the registry flush; overflow "
         "drops the oldest (in-memory history is unaffected)", "tsdb"),
    Knob("POLYAXON_TPU_TSDB_QUERY_MAX_POINTS", "int", 2000,
         "max points one /api/v1/metrics/query response returns "
         "(the newest win)", "tsdb"),
    Knob("POLYAXON_TPU_BASELINE_ALPHA", "float", 0.3,
         "EWMA weight for folding a completed run's summary series into "
         "its (project, kind) regression baseline", "tsdb"),
    # -- control plane / CLI ------------------------------------------------
    Knob("POLYAXON_TPU_HOME", "str", "~/.polyaxon_tpu",
         "platform state dir for the local CLI and tooling state",
         "control-plane"),
    Knob("POLYAXON_TPU_AUTH_TOKEN", "str", "",
         "API bearer token ('' = auth off locally)", "control-plane"),
    Knob("POLYAXON_TPU_SECRET_KEY", "str", "",
         "Fernet key for secret-option encryption at rest ('' = "
         "per-deployment keyfile)", "control-plane"),
    Knob("POLYAXON_TPU_WEBHOOK_URL", "str", "",
         "legacy env fallback for the notifier.webhook_url option",
         "control-plane"),
    Knob("POLYAXON_TPU_WEBHOOK_KIND", "str", "",
         "legacy env fallback for the notifier.webhook_kind option",
         "control-plane"),
    # -- static analysis (graft-lint) --------------------------------------
    Knob("POLYAXON_TPU_LINT_STATE", "str", "",
         "graft-lint state-file path override ('' = "
         "<POLYAXON_TPU_HOME>/analysis/last_run.json)", "analysis"),
    Knob("POLYAXON_TPU_LINT_STALE_S", "float", 7 * 86400.0,
         "age after which the /status probe calls the last graft-lint "
         "run stale", "analysis"),
    # -- option-store root prefix ------------------------------------------
    # conf/options.py builds option env vars as POLYAXON_TPU_ + the
    # dotted option key; the bare prefix is a declared family so GL005
    # can account for the builder's literal.
    Knob("POLYAXON_TPU_", "str", None,
         "root prefix family: cluster options resolve env overrides as "
         "POLYAXON_TPU_<OPTION_KEY> (see conf/options.py)", "options",
         prefix=True),
]

KNOBS: Dict[str, Knob] = {k.name: k for k in _ALL}
FAMILIES: Dict[str, Knob] = {k.name: k for k in _ALL if k.prefix}


def _knob(name: str) -> Knob:
    try:
        knob = KNOBS[name]
    except KeyError:
        raise KeyError(
            f"Unknown knob {name!r} — declare it in conf/knobs.py "
            "(graft-lint GL005 enforces the catalog)"
        ) from None
    if knob.prefix:
        raise KeyError(
            f"{name!r} is a prefix family — read it through the "
            "family_* accessors"
        )
    return knob


def knob_default(name: str) -> Any:
    """The catalog default for ``name`` (single source of truth for
    call sites that also expose the value as a module constant)."""
    return _knob(name).default


def knob_str(name: str, default: Optional[str] = None) -> str:
    knob = _knob(name)
    raw = os.environ.get(name)
    if raw is None:
        return default if default is not None else knob.default
    return raw


def knob_bool(name: str, default: Optional[bool] = None) -> bool:
    knob = _knob(name)
    raw = os.environ.get(name)
    if raw is None:
        return default if default is not None else bool(knob.default)
    return raw.strip().lower() not in _FALSY


def knob_int(name: str, default: Optional[int] = None) -> int:
    knob = _knob(name)
    fallback = default if default is not None else knob.default
    raw = os.environ.get(name)
    if raw is None:
        return fallback
    try:
        return int(float(raw))
    except (TypeError, ValueError):
        return fallback


def knob_float(name: str, default: Optional[float] = None) -> float:
    knob = _knob(name)
    fallback = default if default is not None else knob.default
    raw = os.environ.get(name)
    if raw is None:
        return fallback
    try:
        return float(raw)
    except (TypeError, ValueError):
        return fallback


# -- prefix families ---------------------------------------------------------

def family_prefix(prefix: str) -> str:
    """Validate ``prefix`` is a declared family and return it (call
    sites build dynamic names as ``family_prefix(P) + suffix``)."""
    if prefix not in FAMILIES:
        raise KeyError(
            f"Unknown knob family {prefix!r} — declare it (prefix=True) "
            "in conf/knobs.py"
        )
    return prefix


def family_value(prefix: str, suffix: str) -> Optional[str]:
    """Raw env read of a dynamic family member (None when unset)."""
    return os.environ.get(family_prefix(prefix) + suffix)


def family_float(prefix: str, suffix: str, default: float) -> float:
    raw = family_value(prefix, suffix)
    if raw is None:
        return default
    try:
        return float(raw)
    except (TypeError, ValueError):
        return default


# -- documentation -----------------------------------------------------------

def reference_table() -> str:
    """The catalog as a grouped markdown table (the knob reference in
    ``docs/observability.md`` is generated from this)."""
    lines = [
        "| Knob | Type | Default | What it does |",
        "| --- | --- | --- | --- |",
    ]
    for knob in _ALL:
        name = f"`{knob.name}<...>`" if knob.prefix else f"`{knob.name}`"
        default = "—" if knob.default is None else f"`{knob.default}`"
        kind = f"{knob.kind} family" if knob.prefix else knob.kind
        lines.append(f"| {name} | {kind} | {default} | {knob.doc} |")
    return "\n".join(lines)
