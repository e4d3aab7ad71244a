"""Engine (deploy) server — serves a trained engine on :8000.

Reference: core/.../workflow/CreateServer.scala: MasterActor supervises a
ServerActor; POST /queries.json is the hot path; GET / is the status page;
/reload hot-swaps the latest engine instance; /stop shuts down; plugins
observe query/result pairs; optional feedback loop self-logs prediction
events.

TPU-native: the deployment holds device-resident models with warmed-up
compiled executables (ALSModel.warm_up), so the per-query Python work is
JSON parse → host gather → one device dispatch → one host fetch.
"""

from __future__ import annotations

import asyncio
import collections
import concurrent.futures
import contextvars
import copy
import datetime as _dt
import hmac
import json
import logging
import math
import os
import threading
from typing import Any, Optional

import time as _time

from aiohttp import web

from ..common import deadline, envknobs, faultinject, telemetry
from ..common.resilience import retry_after_jitter
from ..controller.engine import Engine
from ..data.storage.datamap import DataMap
from ..data.storage.event import Event
from ..data.storage.registry import Storage
from .context import WorkflowContext
from .core_workflow import load_deployment
from .plugins import EngineServerPluginContext

log = logging.getLogger("pio.engineserver")


def _env_int(name: str, default: int) -> int:
    """Tolerant integer knob: unset/unparsable degrades to the default
    (a typo'd env var must not crash a deploy). Float spellings like
    ``"1e3"`` are accepted. One shared implementation: common/envknobs."""
    return envknobs.env_int(name, default, float_ok=True)


# query-cache telemetry is process-wide monotonic (counters survive a
# server object being rebuilt in-process, like the fold-in counters)
_M_CACHE_HITS = telemetry.registry().counter(
    "pio_query_cache_hits_total",
    "Queries answered from the served-result cache without a model "
    "dispatch").labels()
_M_CACHE_MISSES = telemetry.registry().counter(
    "pio_query_cache_misses_total",
    "Cache-armed queries that had to run a model dispatch (entry "
    "absent, expired, or invalidated)").labels()
_M_CACHE_INVALIDATIONS = telemetry.registry().counter(
    "pio_query_cache_invalidations_total",
    "Query-cache invalidation events by trigger: foldin = targeted "
    "per-user eviction from an increment's freshness footprint; swap "
    "= full flush on any other model swap; rollback = full flush "
    "when a rollback restores the previous model", ("reason",))


class QueryResultCache:
    """Per-user served-result cache (``PIO_QUERY_CACHE_SIZE`` > 0 arms
    it). Keyed on (user, canonical query fingerprint, app): a
    byte-identical repeat of a query within the TTL is answered without
    touching the model — at a zipfian user mix the hot heads collapse
    onto cache hits and the sharded million-item dispatch only runs for
    the tail. The app component keeps tenants' entries disjoint
    (multi-tenant serving shares ONE cache across every resident app).

    Freshness contract (docs/serving.md "Million-item catalogs"):

    - a fold-in increment going live evicts exactly the users its
      freshness footprint names (the ``users`` list online.py writes
      into ``runtime_conf["foldin"]``) — a fold-in touching a user's
      rows MUST invalidate that user, and does;
    - any other swap (retrain, operator reload, an increment without
      an attributable footprint or of a different lineage) flushes
      everything;
    - a rollback flushes everything — the restored model must never
      answer with results the rolled-back model computed;
    - the TTL bounds staleness against serve-time event-log reads
      (e.g. the e-commerce seen-items filter) that no swap observes.

    Entries store a deep copy and hits return a deep copy: results
    flow through after_query plugins that may mutate them in place.
    Thread-safe (its own lock): lookups run on the event loop while
    swap invalidation arrives from reload worker threads."""

    def __init__(self, max_entries: int, ttl_s: float):
        self.max_entries = int(max_entries)
        self.ttl_s = float(ttl_s)
        self._lock = threading.Lock()
        # key → (expires_monotonic, result); insertion order doubles
        # as LRU order (move_to_end on hit)
        self._entries: collections.OrderedDict = collections.OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.invalidated_entries = 0
        self.invalidations = 0
        # bumped by every invalidation: an in-flight dispatch that
        # started before a swap must not re-insert its (stale) result
        # after the invalidation ran — put() drops generation-mismatched
        # inserts, so "zero stale serves" holds without a lock spanning
        # the whole dispatch
        self.generation = 0

    @staticmethod
    def key_for(query, app: Optional[str] = None) -> tuple:
        """(user-or-None, canonical JSON fingerprint, app-or-None). The
        fingerprint is computed on the post-``before_query`` plugin
        form, so two spellings a plugin canonicalizes share one entry.
        The app component is the tenant-isolation dimension: without
        it, two apps' identical (user, query) pairs would SHARE an
        entry — tenant B served tenant A's cached result, and tenant
        A's fold-in invalidation leaving B's stale alias behind. The
        server passes its tenant's app on every lookup/insert; app=None
        (a bare single-tenant deploy, pre-multi-tenant callers) is its
        own namespace and never collides with a named app's."""
        user = query.get("user") if isinstance(query, dict) else None
        fp = json.dumps(query, sort_keys=True, separators=(",", ":"),
                        default=str)
        return (None if user is None else str(user), fp,
                None if app is None else str(app))

    def get(self, key: tuple):
        now = _time.monotonic()
        with self._lock:
            ent = self._entries.get(key)
            if ent is not None and ent[0] > now:
                self._entries.move_to_end(key)
                self.hits += 1
                _M_CACHE_HITS.inc()
                return copy.deepcopy(ent[1])
            if ent is not None:
                del self._entries[key]  # expired
            self.misses += 1
        _M_CACHE_MISSES.inc()
        return None

    def put(self, key: tuple, result, generation: Optional[int] = None
            ) -> None:
        entry = (_time.monotonic() + self.ttl_s, copy.deepcopy(result))
        with self._lock:
            if generation is not None and generation != self.generation:
                return  # an invalidation ran mid-dispatch: result stale
            self._entries[key] = entry
            self._entries.move_to_end(key)
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)
                self.evictions += 1

    def invalidate_users(self, users, app: Optional[str] = None) -> int:
        """Targeted eviction: drop every entry keyed to one of
        ``users``. Userless entries (similarity queries) survive — a
        fold-in re-solves only user rows against fixed item-side
        state, which userless queries score exclusively. With ``app``,
        only that tenant's entries are touched: tenant A's fold-in
        footprint naming user "u1" must not evict (or miss) app B's
        "u1", who is a different person under a different model."""
        users = {str(u) for u in users}
        app = None if app is None else str(app)
        with self._lock:
            doomed = [k for k in self._entries
                      if k[0] in users and (app is None or k[2] == app)]
            for k in doomed:
                del self._entries[k]
            self.invalidated_entries += len(doomed)
            self.invalidations += 1
            self.generation += 1
        _M_CACHE_INVALIDATIONS.labels("foldin").inc()
        return len(doomed)

    def flush_app(self, app: str, reason: str) -> int:
        """Drop every entry of ONE tenant (its rollback / unfootprinted
        swap); every other tenant's entries — and their hit rates —
        survive untouched."""
        app = str(app)
        with self._lock:
            doomed = [k for k in self._entries if k[2] == app]
            for k in doomed:
                del self._entries[k]
            self.invalidated_entries += len(doomed)
            self.invalidations += 1
            self.generation += 1
        _M_CACHE_INVALIDATIONS.labels(reason).inc()
        return len(doomed)

    def flush(self, reason: str) -> int:
        with self._lock:
            n = len(self._entries)
            self._entries.clear()
            self.invalidated_entries += n
            self.invalidations += 1
            self.generation += 1
        _M_CACHE_INVALIDATIONS.labels(reason).inc()
        return n

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "entries": len(self._entries),
                "maxEntries": self.max_entries,
                "ttlMs": round(self.ttl_s * 1e3, 3),
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "invalidations": self.invalidations,
                "invalidatedEntries": self.invalidated_entries,
            }


class AdmissionShed(Exception):
    """The admission gate refused this query (queue full or server
    draining). Maps to HTTP 503 + jittered ``Retry-After`` — the query
    never started, so a retry elsewhere/later is safe and cheap."""

    def __init__(self, message: str, retry_after_base: float, reason: str):
        super().__init__(message)
        self.retry_after_base = retry_after_base
        self.reason = reason


class SwapValidationError(RuntimeError):
    """The validation gate refused to put a (re)loaded model live
    (nan_guard hit, warm-up failed, or the golden-query smoke predict
    raised). The last-good deployment keeps serving; the reload/refresh
    caller decides whether to pin the refused instance."""

    def __init__(self, instance_id: str, reason: str):
        super().__init__(
            f"engine instance {instance_id} failed swap validation: "
            f"{reason}")
        self.instance_id = instance_id
        self.reason = reason


class EngineServer:
    def __init__(
        self,
        engine: Engine,
        engine_factory_name: str = "",
        engine_variant: str = "default",
        instance_id: Optional[str] = None,
        storage: Optional[Storage] = None,
        feedback: bool = False,
        feedback_app_name: Optional[str] = None,
        plugins: Optional[EngineServerPluginContext] = None,
        batch_window_ms: float = 0.0,
        max_batch: int = 64,
        query_conc: Optional[int] = None,
        query_max_pending: Optional[int] = None,
        query_deadline_ms: Optional[float] = None,
        drain_deadline_ms: Optional[float] = None,
        swap_validate: Optional[bool] = None,
        swap_watch_ms: Optional[float] = None,
        swap_max_error_rate: Optional[float] = None,
        model_refresh_ms: Optional[float] = None,
        foldin_ms: Optional[float] = None,
        fleet_replica: Optional[int] = None,
        fleet_replicas: Optional[int] = None,
        fleet_sync_ms: Optional[float] = None,
        quality_sample: Optional[float] = None,
        query_cache_size: Optional[int] = None,
        query_cache_ttl_ms: Optional[float] = None,
        tenant_max_resident: Optional[int] = None,
        tenant_max_pending: Optional[int] = None,
    ):
        # start the PIO_FAULT_SPEC at-mode offset clock at "server
        # constructing", not "first query": soak timelines schedule
        # faults relative to process start (no-op when chaos is off)
        faultinject.arm()
        self.engine = engine
        self.engine_factory_name = engine_factory_name
        self.engine_variant = engine_variant
        self.requested_instance_id = instance_id
        self.storage = storage or Storage.instance()
        self.feedback = feedback
        self.feedback_app_name = feedback_app_name
        self.plugins = plugins or EngineServerPluginContext()
        # Micro-batching window (0 = off): queries arriving within
        # batch_window_ms are coalesced into ONE vectorized
        # Deployment.batch_query dispatch. At high QPS the per-query
        # path serializes one device dispatch per request; batching
        # trades ≤ window ms of added latency for an order of magnitude
        # in throughput (SURVEY.md §2.9 serving-concurrency row / §7
        # hard part 1 "may need batching window at high QPS").
        self.batch_window_ms = float(batch_window_ms)
        # Cap: ops.topk pads pow2 only up to 256 (larger batches are the
        # bulk eval/batchpredict regime where padding wastes matmul), so
        # windows beyond that would compile per exact batch size.
        self.max_batch = min(int(max_batch), 256)
        self._batch_queue = None
        self._batch_task = None
        self.start_time = _dt.datetime.now(_dt.timezone.utc)
        self._lock = threading.Lock()
        self._query_count = 0
        self._init_overload_state(query_conc, query_max_pending,
                                  query_deadline_ms, drain_deadline_ms,
                                  swap_validate, swap_watch_ms,
                                  swap_max_error_rate, model_refresh_ms,
                                  fleet_replica, fleet_replicas,
                                  fleet_sync_ms, foldin_ms,
                                  quality_sample,
                                  query_cache_size=query_cache_size,
                                  query_cache_ttl_ms=query_cache_ttl_ms,
                                  tenant_max_resident=tenant_max_resident,
                                  tenant_max_pending=tenant_max_pending)
        # Probe marker secret: synthetic startup-probe traffic is
        # excluded from queryCount/feedback, so the marker must not be
        # spoofable — an external client sending a bare "X-Pio-Probe: 1"
        # would silently bypass the accounting. Per-process random token,
        # never exposed via any endpoint; only probe_and_record (same
        # process) knows it.
        import secrets

        self._probe_token = secrets.token_hex(16)
        # degraded mode: serving continues on the last-good model after a
        # failed reload / feedback outage; /status and /readyz surface it
        self._degraded_reason: Optional[str] = None
        self._dropped_feedback = 0
        # per-algorithm warm-up compile accounting (instance families,
        # exported via the registry collector below; gauges because a
        # reload re-measures the new instance's compiles from scratch —
        # _load rebuilds them so a reload to a different variant drops
        # the dead instance's algorithm labels)
        self._m_compile_count, self._m_compile_seconds = \
            self._new_compile_families()
        telemetry.registry().register_collector(
            "engineserver", self._collect_metrics)
        self.deployment = None
        self.instance = None
        if self.fleet_mode and instance_id is None:
            self._fleet_bootstrap_load()
        else:
            self._load(instance_id)
        if self.tenant_max_resident > 0:
            from . import multitenant

            self._tenants = multitenant.TenantMux(
                self, self.tenant_max_resident, self.tenant_max_pending)

        self.app = web.Application(
            middlewares=[telemetry.trace_middleware()])
        self.app.cleanup_ctx.append(telemetry.loop_monitor("engine"))
        self.app.add_routes(
            [
                web.get("/", self.handle_status),
                web.get("/status", self.handle_status),
                web.get("/metrics", self.handle_metrics),
                web.get("/healthz", self.handle_healthz),
                web.get("/readyz", self.handle_readyz),
                web.post("/queries.json", self.handle_query),
                web.get("/reload", self.handle_reload),
                web.post("/reload", self.handle_reload),
                web.get("/rollback", self.handle_rollback),
                web.post("/rollback", self.handle_rollback),
                web.get("/stop", self.handle_stop),
                web.post("/stop", self.handle_stop),
                web.get("/plugins.json", self.handle_plugins),
            ]
        )
        if self.batch_window_ms > 0:
            self.app.on_startup.append(self._start_batcher)
            self.app.on_cleanup.append(self._stop_batcher)
        self.app.on_startup.append(self._start_refresher)
        self.app.on_cleanup.append(self._stop_refresher)
        self.app.on_startup.append(self._start_foldin)
        self.app.on_cleanup.append(self._stop_foldin)
        self.app.on_startup.append(self._start_quality)
        self.app.on_cleanup.append(self._stop_quality)
        self.app.on_startup.append(self._start_fleet)
        self.app.on_cleanup.append(self._stop_fleet)
        self.app.on_startup.append(self._start_heartbeat)
        self.app.on_cleanup.append(self._stop_heartbeat)
        self.app.on_cleanup.append(self._shutdown_executor)

    def _init_overload_state(self, query_conc=None, query_max_pending=None,
                             query_deadline_ms=None,
                             drain_deadline_ms=None, swap_validate=None,
                             swap_watch_ms=None, swap_max_error_rate=None,
                             model_refresh_ms=None, fleet_replica=None,
                             fleet_replicas=None,
                             fleet_sync_ms=None, foldin_ms=None,
                             quality_sample=None, query_cache_size=None,
                             query_cache_ttl_ms=None,
                             tenant_max_resident=None,
                             tenant_max_pending=None) -> None:
        """Admission control: the query path gets a DEDICATED bounded
        executor (query_conc workers) plus a bounded waiting budget
        (query_max_pending); offered load beyond conc+pending is shed
        with 503 + jittered Retry-After instead of queueing without
        limit in the default executor. Args override the PIO_QUERY_*
        env knobs; see docs/operations.md "Serving: overload safety".
        (Separate from __init__ so harness code building a skeleton
        server via __new__ — tools/big_catalog_demo.py — can arm the
        gate without the storage-backed load.)"""
        self.query_conc = max(1, int(
            query_conc if query_conc is not None
            else _env_int("PIO_QUERY_CONC",
                          min(32, (os.cpu_count() or 4) + 4))))
        self.query_max_pending = max(0, int(
            query_max_pending if query_max_pending is not None
            else _env_int("PIO_QUERY_MAX_PENDING", 128)))
        # Deadline budget per query (0 = unbounded); the X-Pio-Deadline-Ms
        # request header overrides per request. Exceeded → 504.
        self.query_deadline_ms = float(
            query_deadline_ms if query_deadline_ms is not None
            else _env_int("PIO_QUERY_DEADLINE_MS", 30_000))
        # Ceiling on what the client header may loosen the budget TO
        # (0 = uncapped). Without it a client could grant itself an
        # effectively unbounded budget and park unkillable workers on a
        # hung model — defeating the operator's overload protection.
        self.query_deadline_max_ms = max(0.0, float(
            _env_int("PIO_QUERY_DEADLINE_MAX_MS", 600_000)))
        # Graceful-drain budget for SIGTERM / /stop.
        self.drain_deadline_ms = max(0.0, float(
            drain_deadline_ms if drain_deadline_ms is not None
            else _env_int("PIO_DRAIN_DEADLINE_MS", 10_000)))
        self._query_executor = concurrent.futures.ThreadPoolExecutor(
            max_workers=self.query_conc, thread_name_prefix="pio-query")
        self._adm_lock = threading.Lock()   # pending count is touched
        self._adm_pending = 0               # from loop AND worker threads
        self._adm_peak = 0
        self._shed_count = 0
        self._deadline_count = 0
        self._orphaned = 0
        self._draining = False
        self._drain_stragglers = 0
        self._reload_lock = asyncio.Lock()
        self._reload_conflicts = 0
        # -- model lifecycle (docs/operations.md "Model lifecycle") ----
        # Validation gate: before any (re)loaded model goes live, run
        # nan_guard over its arrays, require warm-up success, and smoke-
        # predict the golden query. Gate failure → stay on last-good.
        self.swap_validate = (
            bool(swap_validate) if swap_validate is not None
            else envknobs.env_flag("PIO_SWAP_VALIDATE", True))
        # Post-swap watch: for this long after a hot swap, query
        # failures are counted against the NEW model (and hedged onto
        # the retained previous one); past the error-rate threshold the
        # swap is rolled back automatically and the bad instance pinned.
        self.swap_watch_ms = max(0.0, float(
            swap_watch_ms if swap_watch_ms is not None
            else _env_int("PIO_SWAP_WATCH_MS", 60_000)))
        self.swap_max_error_rate = float(
            swap_max_error_rate if swap_max_error_rate is not None
            else envknobs.env_float("PIO_SWAP_MAX_ERROR_RATE", 0.5,
                                    lo=0.0, hi=1.0))
        # Continuous refresh (ROADMAP item 4): poll for newer COMPLETED
        # instances and hot-swap them through the validated gate.
        # 0 = off (the default: reloads stay operator-driven).
        self.model_refresh_ms = max(0.0, float(
            model_refresh_ms if model_refresh_ms is not None
            else _env_int("PIO_MODEL_REFRESH_MS", 0)))
        # Streaming online fold-in (ROADMAP item 2; docs/operations.md
        # "Online learning"): tail the deployed app's event log and
        # fold new events into the live model continuously, publishing
        # each increment through the same gate/watch/pin path as a
        # retrain. 0 = off; `pio deploy --online-foldin` arms it.
        self.foldin_ms = max(0.0, float(
            foldin_ms if foldin_ms is not None
            else _env_int("PIO_FOLDIN_MS", 0)))
        self._foldin_task = None
        # loop-confined (the _watch idiom): the runner ticks single-
        # flight off-thread, and /status reads the last view snapshot
        self._foldin_runner = None
        self._foldin_view: Optional[dict] = None
        # Continuous quality evaluation (ROADMAP item 1's guardrail;
        # docs/operations.md "Continuous quality evaluation"): sample a
        # slice of live queries, shadow-replay them on the retained
        # last-good deployment, grade BOTH against held-out next events
        # tailed from the app's log partitions, and feed a significant
        # canary-vs-last-good regression into the SAME rollback path as
        # an error-rate breach (reason "quality"). 0 = off; `pio deploy
        # --quality-eval` arms it.
        self.quality_sample = min(1.0, max(0.0, float(
            quality_sample if quality_sample is not None
            else envknobs.env_float("PIO_QUALITY_SAMPLE", 0.0,
                                    lo=0.0, hi=1.0))))
        self.quality_k = max(1, _env_int("PIO_QUALITY_K", 10))
        self.quality_min_samples = max(1, _env_int(
            "PIO_QUALITY_MIN_SAMPLES", 20))
        self.quality_max_drop = envknobs.env_float(
            "PIO_QUALITY_MAX_DROP", 0.2, lo=0.0)
        # labels are the user's NEXT events, so the quality watch
        # usually outlives the error watch; 0 = inherit the error
        # watch's window
        self.quality_watch_ms = max(0.0, float(
            _env_int("PIO_QUALITY_WATCH_MS", 0))) or self.swap_watch_ms
        self.quality_resolve_ms = max(0.0, float(
            _env_int("PIO_QUALITY_RESOLVE_MS", 2000)))
        self.quality_ms = max(50.0, float(
            _env_int("PIO_QUALITY_MS", 500)))
        # Served-result cache (0 = off, the default): identical
        # queries within the TTL are answered without a model dispatch.
        # Freshness is invalidation-driven (fold-in footprint / swap /
        # rollback — see QueryResultCache); the TTL only bounds
        # staleness the model lifecycle can't observe.
        self.query_cache_size = max(0, int(
            query_cache_size if query_cache_size is not None
            else _env_int("PIO_QUERY_CACHE_SIZE", 0)))
        self.query_cache_ttl_ms = max(0.0, float(
            query_cache_ttl_ms if query_cache_ttl_ms is not None
            else _env_int("PIO_QUERY_CACHE_TTL_MS", 10_000)))
        self._query_cache = (
            QueryResultCache(self.query_cache_size,
                             self.query_cache_ttl_ms / 1e3)
            if self.query_cache_size > 0 and self.query_cache_ttl_ms > 0
            else None)
        # Multi-tenant serving (docs/operations.md "Multi-tenant
        # serving"): > 0 arms the tenant multiplexer — requests routed
        # by access key / X-Pio-App to an LRU cache of that many
        # resident per-app deployments, each tenant with its own
        # lifecycle/fold-in/admission state. 0 = off (single-tenant,
        # the default); `pio deploy --multitenant` arms it.
        self.tenant_max_resident = max(0, int(
            tenant_max_resident if tenant_max_resident is not None
            else _env_int("PIO_TENANT_MAX_RESIDENT", 0)))
        # One tenant's in-flight + queued budget, deliberately below
        # the process cap so a hot app sheds while cold apps serve.
        self.tenant_max_pending = max(1, int(
            tenant_max_pending if tenant_max_pending is not None
            else _env_int("PIO_TENANT_MAX_PENDING", 32)))
        # built in __init__ once storage + the default load are up
        # (skeleton servers built via __new__ stay single-tenant)
        self._tenants = None
        self._quality_task = None
        # loop-confined (the _watch idiom): offer() appends from the
        # request path, the loop ticks single-flight off-thread, and
        # /status reads the last view snapshot
        self._quality_runner = None
        self._quality_view: Optional[dict] = None
        self._quality_watch = None   # active post-swap quality watch
        self._previous = None            # (deployment, instance) resident
        self._pinned: dict[str, str] = {}  # instance id → pin reason
        # pins mid-application (store-walk rollback in flight): honored
        # by this replica's own walks but NOT published to the fleet —
        # the coordinator merges pins irreversibly, so a provisional
        # pin that fails to apply must never leak into the directive
        self._pins_provisional: set = set()
        self._watch = None               # active post-swap watch window
        self._rollbacks: dict[str, int] = {}   # reason → count
        self._swap_count = 0
        self._validate_failures = 0
        self._refresh_swaps = 0
        self._refresh_task = None
        # fleet wiring rides along so __new__-built harness skeletons
        # (tools/big_catalog_demo.py) arm everything with ONE call
        self._init_fleet_state(fleet_replica, fleet_replicas,
                               fleet_sync_ms)

    def _init_fleet_state(self, fleet_replica=None, fleet_replicas=None,
                          fleet_sync_ms=None) -> None:
        """Replica-fleet wiring (docs/operations.md "Serving fleet").

        A fleet replica (``PIO_FLEET_REPLICA`` >= 0, set by the fleet
        supervisor) does not chase the newest COMPLETED instance on its
        own: the fleet coordinator (workflow/fleet.py) stages rollouts
        through a store-mediated directive record, and this replica's
        sync loop applies directives — each swap still passing this
        replica's OWN validation gate — and publishes a status row the
        coordinator (and `pio status --engine-url`) aggregates."""
        self.fleet_replica = int(
            fleet_replica if fleet_replica is not None
            else envknobs.env_int("PIO_FLEET_REPLICA", -1))
        self.fleet_replicas = max(0, int(
            fleet_replicas if fleet_replicas is not None
            else envknobs.env_int("PIO_FLEET_REPLICAS", 0, lo=0)))
        self.fleet_sync_ms = max(50.0, float(
            fleet_sync_ms if fleet_sync_ms is not None
            else _env_int("PIO_FLEET_SYNC_MS", 1000)))
        self.fleet_mode = self.fleet_replica >= 0
        # loop-confined cache of the last directive + peer rows (the
        # _watch idiom): /status and the divergence gauge read the
        # reference atomically, never the store
        self._fleet_view: Optional[dict] = None
        self._fleet_task = None
        self._hb_task = None
        # why the operator's refresh knob "did nothing": surfaced on
        # /status as refreshMs: "disabled(fleet)" instead of silently
        # reporting 0 — a replica chasing the newest instance on its
        # own would race the coordinator's staged canary
        self._refresh_disabled: Optional[str] = None
        if self.fleet_mode and self.model_refresh_ms > 0:
            log.warning(
                "fleet mode: PIO_MODEL_REFRESH_MS=%.0f refused — the "
                "fleet coordinator owns refresh (staged canary); "
                "/status reports refreshMs: disabled(fleet)",
                self.model_refresh_ms)
            self._refresh_disabled = "fleet"
            self.model_refresh_ms = 0.0

    def _fleet_group(self) -> str:
        from . import model_artifact

        # PIO_FLEET_APP (set by the fleet front when the tenant mux is
        # armed) scopes the directive record to the DEFAULT app so the
        # coordinator and every replica agree on the same group name
        app = envknobs.env_str("PIO_FLEET_APP", "")
        return model_artifact.fleet_group(self.engine_factory_name,
                                          self.engine_variant,
                                          app or None)

    @staticmethod
    def _new_compile_families():
        return (telemetry.GaugeFamily(
                    "pio_engine_compile_count",
                    "Warm-up compilations performed for the live engine "
                    "instance, per algorithm", ("algorithm",)),
                telemetry.GaugeFamily(
                    "pio_engine_compile_seconds",
                    "Warm-up compilation wall seconds for the live engine "
                    "instance, per algorithm", ("algorithm",)))

    # -- lifecycle --------------------------------------------------------
    def _load(self, instance_id: Optional[str],
              skip_if_current: bool = False, on_reject=None) -> bool:
        """(Re)load a deployment; returns True when a deployment was
        published, False when skip_if_current short-circuited.

        At INITIAL deploy (nothing serving yet) a validation-refused
        newest instance is pinned and the walk retries older COMPLETED
        instances — the same recovery the integrity walk-back gives a
        corrupt blob, because there is no last-good model to stay on.
        Once something IS serving, a validation failure raises so the
        caller keeps the last-good deployment (and decides about
        pinning)."""
        while True:
            try:
                return self._load_once(instance_id, skip_if_current,
                                       on_reject)
            except SwapValidationError as e:
                with self._lock:
                    has_current = self.deployment is not None
                if instance_id is not None or has_current:
                    raise
                with self._lock:
                    self._validate_failures += 1
                    self._pinned[e.instance_id] = "validate"
                log.warning(
                    "initial deploy: %s; pinning it and walking back to "
                    "an older COMPLETED instance", e)

    def _load_once(self, instance_id: Optional[str],
                   skip_if_current: bool = False, on_reject=None) -> bool:
        ctx = WorkflowContext(storage=self.storage)
        # snapshot under the lock: this runs on a worker thread while
        # the event loop may be pinning concurrently (error-rate
        # rollback is not serialized by the reload lock)
        with self._lock:
            pinned = tuple(self._pinned) if instance_id is None else ()
        deployment, instance, _ = load_deployment(
            self.engine,
            instance_id,
            ctx,
            engine_factory_name=self.engine_factory_name,
            engine_variant=self.engine_variant,
            # latest-completed mode never re-picks a pinned (rolled
            # back / validation-refused) instance; an explicit id is
            # the operator overriding the pin on purpose
            exclude_ids=pinned,
            on_reject=on_reject,
        )
        with self._lock:
            current = self.instance
        if (skip_if_current and current is not None
                and instance.id == current.id):
            # refresh poll raced a walk-back onto the live instance:
            # nothing newer is deployable, keep serving as-is
            log.info("refresh: no newer deployable instance than %s",
                     current.id)
            return False
        # Fresh compile families for this instance: the collector reads
        # the attributes live, so swapping them drops labels that only
        # existed on the previous variant (nothing merges stale rows)
        m_count, m_seconds = self._new_compile_families()
        warmup_errors: list[str] = []
        # Warm up every model that supports it (compile + device
        # placement); wall time per algorithm feeds the compile gauges —
        # on a cold deploy this is almost entirely XLA compilation, the
        # number an operator needs when a reload suddenly takes 30 s.
        for (algo_name, _algo), model in zip(deployment.algo_list,
                                             deployment.models):
            warm = getattr(model, "warm_up", None)
            if callable(warm):
                label = algo_name or type(model).__name__
                t0 = _time.perf_counter()
                try:
                    warm()
                except Exception as e:  # noqa: BLE001 - gate decides below
                    log.exception("model warm-up failed")
                    warmup_errors.append(f"{label}: {e}")
                else:
                    m_count.labels(label).set(1)
                    m_seconds.labels(label).set(
                        _time.perf_counter() - t0)
        if self.batch_window_ms > 0:
            # Pre-compile every power-of-two batch shape the micro-batch
            # path can produce — a cold shape showed ~1.5s p99 through a
            # remote compile service, which would otherwise surface as
            # p99 spikes on live traffic. Models opt in by providing an
            # example_query() the batch path can execute.
            example = self._find_example_query(deployment)
            if example is not None:
                # up to the next pow2 ≥ max_batch: a live window of
                # max_batch queries pads to that shape
                top = 1 << max(self.max_batch - 1, 0).bit_length()
                b = 1
                n_shapes = 0
                t0 = _time.perf_counter()
                while b <= top:
                    try:
                        deployment.batch_query([dict(example)] * b)
                    except Exception as e:  # noqa: BLE001 - gate below
                        log.exception("batch warm-up failed at size %d", b)
                        warmup_errors.append(f"batch[{b}]: {e}")
                        break
                    n_shapes += 1
                    b *= 2
                m_count.labels("batch").set(n_shapes)
                m_seconds.labels("batch").set(
                    _time.perf_counter() - t0)
        # Validation gate — this deployment goes live only past it. A
        # failure leaves the compile gauges and the served deployment
        # exactly as they were (the caller keeps the last-good model).
        if self.swap_validate and warmup_errors:
            raise SwapValidationError(
                instance.id, "warm-up failed: " + "; ".join(warmup_errors))
        self._validate_swap(deployment, instance)
        self._m_compile_count, self._m_compile_seconds = m_count, m_seconds
        with self._lock:
            prev_dep, prev_inst = self.deployment, self.instance
            swapped = (prev_inst is not None
                       and prev_inst.id != instance.id)
            if swapped:
                # Keep exactly ONE previous deployment resident (warm,
                # device buffers intact): /rollback and the post-swap
                # error-rate watch swap back to it instantly, with no
                # storage round trip and no recompile.
                self._previous = (prev_dep, prev_inst)
                self._swap_count += 1
            self.deployment = deployment
            self.instance = instance
            if swapped and self.swap_watch_ms > 0:
                self._watch = {
                    "until": _time.monotonic() + self.swap_watch_ms / 1e3,
                    "total": 0, "errors": 0, "instance": instance.id,
                }
            if (swapped and self.quality_sample > 0
                    and self.quality_watch_ms > 0):
                # quality watch rides every swap alongside the error
                # watch: while it is open, a canary-vs-last-good NDCG
                # breach from the shadow scorer rolls this swap back
                self._quality_watch = {
                    "until": (_time.monotonic()
                              + self.quality_watch_ms / 1e3),
                    "instance": instance.id,
                }
        if swapped and self._query_cache is not None:
            # freshness-correct cache across the swap: an increment
            # whose fold-in marker proves it descends from what we were
            # serving AND names the users it touched evicts exactly
            # those users; anything else flushes the whole cache
            users = self._foldin_footprint(instance, prev_inst)
            capp = self._cache_app()
            if users is None:
                # an unfootprinted swap invalidates the DEFAULT app's
                # entries; with the mux armed other tenants' entries
                # are theirs (their own lifecycles invalidate them)
                n = (self._query_cache.flush("swap") if capp is None
                     else self._query_cache.flush_app(capp, "swap"))
                log.info("query cache: flushed %d entrie(s) on swap "
                         "to %s", n, instance.id)
            else:
                n = self._query_cache.invalidate_users(users, app=capp)
                log.info("query cache: fold-in %s evicted %d entrie(s) "
                         "for %d touched user(s)", instance.id, n,
                         len(users))
        log.info("deployed engine instance %s", instance.id)
        return True

    @staticmethod
    def _foldin_footprint(instance, prev_inst) -> Optional[list]:
        """The incoming instance's targeted-invalidation user list, or
        None when only a full flush is safe. Targeted eviction needs
        BOTH halves of the marker online.py writes: ``users`` (the rows
        the increment chain re-solved) and ``bases`` containing the
        instance this server was actually serving — an increment of
        some other lineage changed an unknown amount of state."""
        try:
            raw = (instance.runtime_conf or {}).get("foldin")
            if not raw or prev_inst is None:
                return None
            doc = json.loads(raw) if isinstance(raw, str) else raw
            users = doc.get("users")
            bases = doc.get("bases")
            if not isinstance(users, list):
                return None
            if not isinstance(bases, list) or prev_inst.id not in bases:
                return None
            return users
        except Exception:  # noqa: BLE001 — on any doubt, full flush
            return None

    def _validate_swap(self, deployment, instance) -> None:
        """Swap gate (PIO_SWAP_VALIDATE, default on): nan_guard over
        every model's arrays plus a smoke predict on the golden query
        (instance runtime_conf["golden_query"] → $PIO_GOLDEN_QUERY →
        the models' example_query protocol). The ``swap.validate``
        fault point lets the chaos harness fail the gate
        deterministically. Any failure raises
        :class:`SwapValidationError` — the model never goes live."""
        if not self.swap_validate:
            return
        from ..common.nan_guard import check_finite

        try:
            faultinject.fault_point("swap.validate")
            for (algo_name, _algo), model in zip(deployment.algo_list,
                                                 deployment.models):
                check_finite(
                    model, f"swap.validate[{algo_name or 'default'}]")
            golden = self._golden_query(instance, deployment)
            if golden is not None:
                # Drive the DASE stages directly instead of
                # Deployment.query: synthetic gate traffic must not
                # consume chaos fault-point budgets (query.*) nor
                # pollute the per-query stage histograms.
                q = deployment.serving.supplement(dict(golden))
                predictions = [
                    algo.predict(model, q)
                    for (_n, algo), model in zip(deployment.algo_list,
                                                 deployment.models)
                ]
                deployment.serving.serve(q, predictions)
            else:
                log.debug("swap validation: no golden query available; "
                          "skipping smoke predict")
        except Exception as e:  # noqa: BLE001 - any failure refuses the swap
            raise SwapValidationError(instance.id, str(e)) from e

    def _golden_query(self, instance, deployment) -> Optional[dict]:
        """The smoke-predict query: a known-good query stored on the
        instance row (runtime_conf["golden_query"]), the operator's
        $PIO_GOLDEN_QUERY, or the models' example_query() opt-in."""
        raw = ((instance.runtime_conf or {}).get("golden_query")
               or envknobs.env_str("PIO_GOLDEN_QUERY", "", lower=False))
        if raw:
            try:
                doc = json.loads(raw)
                if isinstance(doc, dict):
                    return doc
                log.warning("golden_query is not a JSON object; "
                            "falling back to example_query")
            except json.JSONDecodeError:
                log.warning("golden_query is not valid JSON; falling "
                            "back to example_query")
        return self._find_example_query(deployment)

    @staticmethod
    def _find_example_query(deployment) -> Optional[dict]:
        """First model offering a non-None example_query() (the warm-up /
        probe opt-in protocol)."""
        for model in deployment.models:
            ex = getattr(model, "example_query", None)
            if callable(ex):
                example = ex()
                if example is not None:
                    return example
        return None

    # -- handlers ---------------------------------------------------------
    async def handle_status(self, request: web.Request) -> web.Response:
        """Reference: CreateServer status page — JSON here."""
        from ..parallel.mesh import device_report

        with self._lock:
            instance = self.instance
        out = {
            "status": "alive",
            "engineInstanceId": instance.id if instance else None,
            "engineFactory": self.engine_factory_name,
            "engineVariant": self.engine_variant,
            "startTime": self.start_time.isoformat(),
            "queryCount": self._query_count,
            # which device answers (platform / deviceKind / deviceCount
            # as JAX reports them): a benchmark or smoke reads this to
            # prove the server is not on a CPU it fell back to
            **device_report(),
            "plugins": self.plugins.plugin_names(),
            # resilience surface: serving on a stale model after a failed
            # reload (degraded=true), and feedback events dropped because
            # the event store write failed (counter — ops alert on growth)
            "degraded": self._degraded_reason is not None,
            "degradedReason": self._degraded_reason,
            "droppedFeedback": self._dropped_feedback,
            # overload surface: the operator's no-scrape view of the
            # admission gate (`pio status --engine-url` prints this)
            "overload": self.overload_snapshot(),
            # model-lifecycle surface: previous/pinned instances,
            # rollback + swap-validation counters, refresh config
            "lifecycle": self.lifecycle_snapshot(),
        }
        if self.foldin_ms > 0:
            # online fold-in surface: cursor LSN, freshness lag,
            # publish/rollback history (`pio status --engine-url`
            # prints the freshness-lag line off this). lagSeconds is
            # recomputed at READ time from the last caught-up anchor:
            # the view snapshot freezes while a tick is WEDGED (hung
            # storage), and serving its stale lag would disarm the
            # staleness warn-marker in exactly that case
            fv = self._foldin_view
            if fv and fv.get("caughtUpAt"):
                fv = {**fv, "lagSeconds": round(
                    max(0.0, _time.time() - fv["caughtUpAt"]), 3)}
            out["foldin"] = fv or {
                "enabled": True, "ms": self.foldin_ms,
                "producer": (not self.fleet_mode
                             or self.fleet_replica == 0),
                "events": 0, "publishes": 0, "lagSeconds": None,
            }
        if self._query_cache is not None:
            # served-result cache surface: occupancy, hit/miss and
            # invalidation accounting (`pio status --engine-url` and
            # the soak scorecard's freshness assertion read this)
            out["queryCache"] = self._query_cache.snapshot()
        if self._tenants is not None:
            # multi-tenant surface: LRU occupancy/evictions plus one
            # row per tenant — residency, pins, watch, shed/rollback
            # counters, fold-in cursor lag (`pio status --engine-url`
            # prints the per-tenant table off this)
            out["tenants"] = self._tenants.snapshot()
        if self.quality_sample > 0:
            # continuous-quality surface: sampling/scoring counters,
            # windowed live metrics, last-good deltas, holdout cursor
            # (`pio status --engine-url` prints the quality line off
            # this)
            qw = self._quality_watch
            out["quality"] = {
                **(self._quality_view or {
                    "enabled": True, "sample": self.quality_sample,
                    "sampled": 0, "scored": 0}),
                "watchMs": self.quality_watch_ms,
                "watch": ({"instance": qw["instance"],
                           "remainingMs": round(max(
                               0.0, (qw["until"] - _time.monotonic())
                               * 1e3), 1)}
                          if qw is not None else None),
            }
        if self.fleet_mode:
            # store-fed fleet aggregation, cached by the sync loop (no
            # storage I/O on the status path): directive state, every
            # peer's status row, and a divergence flag — `pio status
            # --engine-url` against the front lands on ANY replica and
            # still sees the whole fleet
            out["fleet"] = self._fleet_view or {
                "group": self._fleet_group(),
                "replica": self.fleet_replica,
                "replicas": self.fleet_replicas,
                "directive": None, "peers": [], "divergence": False,
            }
        # measured serving-latency decomposition, when a probe ran
        # (pio deploy --probe-latency persists it to the instance row)
        probe = (instance.runtime_conf.get("probe_latency")
                 if instance is not None else None)
        if probe:
            try:
                out["probeLatency"] = json.loads(probe)
            except (TypeError, json.JSONDecodeError):
                pass
        return web.json_response(out)

    def _collect_metrics(self):
        """Render-time families owned by THIS server instance."""
        qc = telemetry.GaugeFamily(
            "pio_engine_query_count",
            "Queries served by the live engine server (excludes "
            "synthetic startup probes)")
        qc.labels().set(self._query_count)
        dropped = telemetry.GaugeFamily(
            "pio_engine_dropped_feedback_total",
            "Feedback self-log events dropped by event-store failures")
        dropped.labels().set(self._dropped_feedback)
        ov = self.overload_snapshot()
        fams = [self._m_compile_count, self._m_compile_seconds, qc,
                dropped]
        for name, help_, value in (
            ("pio_engine_query_pending",
             "Accepted queries currently queued or running in the "
             "admission-gated executor", ov["pending"]),
            ("pio_engine_query_pending_limit",
             "Admission cap: PIO_QUERY_CONC + PIO_QUERY_MAX_PENDING",
             ov["pendingLimit"]),
            ("pio_engine_query_pending_peak",
             "High-water mark of accepted in-flight + queued queries",
             ov["peakPending"]),
            ("pio_engine_query_shed_total",
             "Queries refused 503 at admission (queue full or "
             "draining)", ov["shed"]),
            ("pio_engine_query_deadline_exceeded_total",
             "Queries answered 504 because their deadline budget ran "
             "out", ov["deadlineExceeded"]),
            ("pio_engine_query_orphaned_total",
             "Deadline-exceeded queries whose worker thread was still "
             "running at 504 time (freed at the next spend-point)",
             ov["orphaned"]),
            ("pio_engine_draining",
             "1 while the server drains for shutdown (readyz answers "
             "503)", 1 if ov["draining"] else 0),
            ("pio_engine_drain_stragglers",
             "Accepted queries still unfinished when the drain "
             "deadline expired", ov["drainStragglers"]),
        ):
            fam = telemetry.GaugeFamily(name, help_)
            fam.labels().set(value)
            fams.append(fam)
        lc = self.lifecycle_snapshot()
        rb = telemetry.GaugeFamily(
            "pio_engine_rollbacks_total",
            "Deployment rollbacks to the retained previous model, by "
            "reason (error-rate = automatic post-swap watch, quality = "
            "shadow-scorer breach, manual = /rollback)", ("reason",))
        # always expose the automatic-rollback rows so dashboards can
        # alert on their first increment, plus any reasons already seen
        for reason in sorted({"error-rate", "quality",
                              *lc["rollbacks"]}):
            rb.labels(reason).set(lc["rollbacks"].get(reason, 0))
        fams.append(rb)
        for name, help_, value in (
            ("pio_engine_model_swaps_total",
             "Hot swaps to a different engine instance since start "
             "(reload, explicit target, or refresh)", lc["swaps"]),
            ("pio_engine_swap_validate_failures_total",
             "Reload/refresh attempts refused by the swap validation "
             "gate (nan_guard, warm-up, golden-query smoke predict)",
             lc["validateFailures"]),
            ("pio_engine_pinned_instances",
             "Engine instances pinned against redeployment (rolled "
             "back or validation-refused)", len(lc["pinned"])),
            ("pio_engine_model_refresh_swaps_total",
             "Hot swaps performed by the continuous-refresh loop",
             lc["refreshSwaps"]),
        ):
            fam = telemetry.GaugeFamily(name, help_)
            fam.labels().set(value)
            fams.append(fam)
        if self.fleet_mode:
            view = self._fleet_view
            div = telemetry.GaugeFamily(
                "pio_fleet_divergence",
                "1 while this replica's cached peer view shows the "
                "fleet serving more than one engine instance (mixed "
                "brain; converges within PIO_FLEET_SYNC_MS)")
            div.labels().set(
                1 if (view and view.get("divergence")) else 0)
            fams.append(div)
        return fams

    async def handle_metrics(self, request: web.Request) -> web.Response:
        """Prometheus text exposition: query stage histograms, compile
        gauges, storage transport + breaker families — the engine
        server's share of the process-wide registry."""
        return web.Response(text=telemetry.render_all(),
                            content_type="text/plain")

    async def handle_healthz(self, request: web.Request) -> web.Response:
        """Liveness: the process serves HTTP (mirrors the storage
        server's /health). Restart-worthy failures never answer at all."""
        return web.json_response({"status": "alive"})

    async def handle_readyz(self, request: web.Request) -> web.Response:
        """Readiness: a model is loaded AND no storage circuit breaker
        is open; not-ready answers 503 so load balancers rotate this
        replica out. The degraded flag (serving the last-good model
        after a failed reload) is deliberately NOT part of readiness —
        a degraded replica still answers queries correctly and draining
        it would trade a stale-but-valid model for no capacity; it is
        surfaced here and on /status as telemetry only.

        A DRAINING server is not-ready by design — SIGTERM / /stop flip
        this to 503 FIRST so load balancers rotate the replica out
        while the in-flight queries finish."""
        with self._lock:
            loaded = self.deployment is not None
        open_breakers = [
            b["name"] for b in self._storage_breakers()
            if b.get("state") == "open"
        ]
        with self._adm_lock:
            draining = self._draining
        ready = loaded and not open_breakers and not draining
        out = {
            "ready": ready,
            "modelLoaded": loaded,
            "degraded": self._degraded_reason is not None,
            "draining": draining,
            "openBreakers": open_breakers,
        }
        return web.json_response(out, status=200 if ready else 503)

    # -- admission control / deadlines / drain ----------------------------
    def overload_snapshot(self) -> dict:
        """Shed/deadline/drain counters for /status and `pio status`."""
        with self._adm_lock:
            pending, peak = self._adm_pending, self._adm_peak
            shed, deadline_exceeded = self._shed_count, self._deadline_count
            orphaned, draining = self._orphaned, self._draining
            stragglers = self._drain_stragglers
        return {
            "conc": self.query_conc,
            "pending": pending,
            "pendingLimit": self.query_conc + self.query_max_pending,
            "peakPending": peak,
            "shed": shed,
            "deadlineExceeded": deadline_exceeded,
            "orphaned": orphaned,
            "deadlineMsDefault": self.query_deadline_ms,
            "draining": draining,
            "drainDeadlineMs": self.drain_deadline_ms,
            "drainStragglers": stragglers,
            "reloadConflicts": self._reload_conflicts,
        }

    def _request_deadline(self, request: web.Request) \
            -> Optional[deadline.Deadline]:
        """Per-request budget: X-Pio-Deadline-Ms header, else the
        server default (0 = unbounded). The header may tighten freely
        and loosen only up to PIO_QUERY_DEADLINE_MAX_MS — a malformed,
        non-positive or non-finite header falls back to the default, so
        no client can grant itself an unbounded budget (only the
        operator's default may disable the deadline)."""
        budget_ms = self.query_deadline_ms
        raw = request.headers.get("X-Pio-Deadline-Ms")
        if raw:
            try:
                hdr = float(raw)
            except ValueError:
                hdr = float("nan")
            if math.isfinite(hdr) and hdr > 0:
                budget_ms = hdr
                if self.query_deadline_max_ms > 0:
                    budget_ms = min(budget_ms, self.query_deadline_max_ms)
        if budget_ms <= 0:
            return None
        return deadline.Deadline(budget_ms)

    def _admit(self) -> int:
        """Take one admission slot or refuse; returns the admitted
        queries now pending, this one included. A slot covers the query
        from acceptance until its compute FINISHES — including workers
        that overran their deadline after the client got its 504
        (threads can't be killed), so orphaned work keeps counting
        against the cap and the executor stays bounded."""
        with self._adm_lock:
            if self._draining:
                raise AdmissionShed(
                    "server is draining for shutdown", 1.0, "draining")
            cap = self.query_conc + self.query_max_pending
            if self._adm_pending >= cap:
                raise AdmissionShed(
                    f"query admission queue full ({self._adm_pending}"
                    f"/{cap})", 1.0, "full")
            self._adm_pending += 1
            if self._adm_pending > self._adm_peak:
                self._adm_peak = self._adm_pending
            return self._adm_pending

    def _release_slot(self, fut=None) -> None:
        """Admission-slot release; done-callback on both asyncio and
        concurrent futures (the latter runs on a worker thread). Also
        retrieves the future's exception: an orphaned worker failing
        AFTER its client got 504 must be accounted, not warned about
        as a never-retrieved exception."""
        if fut is not None and not fut.cancelled():
            exc = fut.exception()
            if exc is not None and not isinstance(
                    exc, deadline.DeadlineExceeded):
                log.debug("orphaned/abandoned query failed: %s", exc)
        with self._adm_lock:
            self._adm_pending -= 1

    def _run_admitted_query(self, deployment, query, admitted_ns=0,
                            pending=0):
        """Executor-thread entry. Closes ``query.admit_wait`` (admission
        to a worker picking the query up; ``pending`` = admitted queries
        at admission, this one included), then re-checks the budget: a
        query that spent its whole deadline WAITING in the executor
        queue frees the worker immediately instead of computing an
        answer nobody is waiting for."""
        if admitted_ns:
            telemetry.add_span("query.admit_wait", admitted_ns,
                               _time.perf_counter_ns(), pending=pending)
        dl = deadline.current()
        if dl is not None:
            dl.check("executor pickup")
        return deployment.query(query)

    async def _dispatch_query(self, deployment, query, dl,
                              direct: bool = False):
        """The admission gate — the ONLY way a handler may hand a query
        to compute (guard-tested; a direct ``asyncio.to_thread(
        deployment.query, ...)`` would bypass the bounded executor,
        the shed path and the deadline budget).

        ``direct=True`` skips the micro-batch queue: the batch worker
        always dispatches against the LIVE deployment, so callers that
        must run on a SPECIFIC one (the watch window's hedge onto the
        retained previous model) go straight to the executor.

        Raises :class:`AdmissionShed` (→ 503) or
        :class:`deadline.DeadlineExceeded` (→ 504)."""
        if dl is not None:
            dl.check("admission")
        pending = self._admit()
        admitted_ns = telemetry.timer_start()
        slot_owned_by_future = False
        try:
            timeout = dl.remaining() if dl is not None else None
            if self._batch_queue is not None and not direct:
                fut = asyncio.get_running_loop().create_future()
                fut.add_done_callback(self._release_slot)
                slot_owned_by_future = True
                await self._batch_queue.put((query, fut))
                try:
                    return await asyncio.wait_for(fut, timeout)
                except asyncio.TimeoutError:
                    # wait_for cancelled fut; the batch worker's
                    # fut.done() check skips delivering to it
                    raise deadline.DeadlineExceeded(
                        dl.budget_ms, dl.overrun_ms(),
                        "batch queue") from None
            # deadline rides the copied context into the worker thread
            # (same mechanism that carries the open span and the trace)
            with deadline.running(dl):
                ctx = contextvars.copy_context()
            cfut = self._query_executor.submit(
                ctx.run, self._run_admitted_query, deployment, query,
                admitted_ns, pending)
            cfut.add_done_callback(self._release_slot)
            slot_owned_by_future = True
            afut = asyncio.wrap_future(cfut)
            # the shield below can leave afut unawaited (504 path):
            # consume its result/exception so nothing warns
            afut.add_done_callback(
                lambda f: None if f.cancelled() else f.exception())
            try:
                return await asyncio.wait_for(asyncio.shield(afut),
                                              timeout)
            except asyncio.TimeoutError:
                if cfut.cancel():
                    # still queued: the model never saw this query —
                    # the stage matters to the post-swap watch, which
                    # must not blame the canary for queue starvation
                    stage = "queued"
                else:
                    # already running: the thread can't be killed; it
                    # frees itself at the next deadline spend-point
                    # (stage boundary / storage egress) and releases
                    # its admission slot then — clean overrun, the
                    # executor stays bounded
                    with self._adm_lock:
                        self._orphaned += 1
                    stage = "await"
                raise deadline.DeadlineExceeded(
                    dl.budget_ms, dl.overrun_ms(), stage) from None
        finally:
            if not slot_owned_by_future:
                self._release_slot()

    def _storage_breakers(self) -> list[dict]:
        try:
            return [b for states in
                    self.storage.breaker_states().values() for b in states]
        except Exception:  # noqa: BLE001 - readiness must never crash
            log.exception("breaker state collection failed")
            return []

    async def _shutdown_executor(self, app) -> None:
        """App cleanup: release the bounded executor's idle workers
        (don't wait — orphaned threads free themselves at their next
        deadline spend-point; finalize_shutdown owns the hard stop)."""
        self._query_executor.shutdown(wait=False, cancel_futures=True)

    # -- micro-batching ---------------------------------------------------
    async def _start_batcher(self, app) -> None:
        self._batch_queue = asyncio.Queue()
        self._batch_task = asyncio.get_running_loop().create_task(
            self._batch_worker())

    async def _stop_batcher(self, app) -> None:
        # stop accepting, cancel the worker, and fail any stranded
        # queries cleanly instead of leaving their handlers awaiting
        # futures that will never resolve
        queue, self._batch_queue = self._batch_queue, None
        if self._batch_task is not None:
            self._batch_task.cancel()
            self._batch_task = None
        if queue is not None:
            while not queue.empty():
                _, fut = queue.get_nowait()
                if not fut.done():
                    fut.set_exception(
                        RuntimeError("engine server shutting down"))

    async def _batch_worker(self) -> None:
        """Coalesce queued queries: wait for the first, gather more until
        the window closes (or max_batch), one vectorized dispatch. On
        cancellation (server shutdown) the IN-FLIGHT batch's futures are
        failed too — _stop_batcher only sees items still queued."""
        try:
            await self._batch_worker_loop()
        except asyncio.CancelledError:
            for _, fut in getattr(self, "_inflight_batch", []):
                if not fut.done():
                    fut.set_exception(
                        RuntimeError("engine server shutting down"))
            raise

    async def _batch_worker_loop(self) -> None:
        loop = asyncio.get_running_loop()
        window = self.batch_window_ms / 1000.0
        while True:
            self._inflight_batch = []
            batch = self._inflight_batch
            batch.append(await self._batch_queue.get())
            deadline = loop.time() + window
            while len(batch) < self.max_batch:
                timeout = deadline - loop.time()
                if timeout <= 0:
                    break
                try:
                    batch.append(await asyncio.wait_for(
                        self._batch_queue.get(), timeout))
                except asyncio.TimeoutError:
                    break
            # Drop entries whose future already settled — a deadline
            # timeout cancels the future but leaves the (query, fut)
            # pair queued; computing it would burn a batch slot on an
            # answer nobody is waiting for (in-place: the cancellation
            # handler aliases this list as _inflight_batch).
            batch[:] = [(q, f) for q, f in batch if not f.done()]
            if not batch:
                continue
            with self._lock:
                deployment = self.deployment
            queries = [q for q, _ in batch]
            try:
                results = await asyncio.to_thread(
                    deployment.batch_query, queries)
            except Exception:  # noqa: BLE001
                # One bad query (e.g. missing field) must not poison its
                # batchmates: degrade to per-query processing so each
                # request gets ITS OWN result or error, exactly like the
                # unbatched path.
                def _one_by_one():
                    out = []
                    for q in queries:
                        try:
                            out.append((True, deployment.query(q)))
                        except Exception as qe:  # noqa: BLE001
                            out.append((False, qe))
                    return out

                for (_, fut), (ok, res) in zip(
                        batch, await asyncio.to_thread(_one_by_one)):
                    if fut.done():
                        continue
                    if ok:
                        fut.set_result(res)
                    else:
                        fut.set_exception(res)
                continue
            for (_, fut), res in zip(batch, results):
                if not fut.done():
                    fut.set_result(res)

    async def handle_query(self, request: web.Request) -> web.Response:
        try:
            query = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"message": "invalid JSON body"}, status=400)
        if self._tenants is not None:
            routed = await self._route_tenant_query(request, query)
            if routed is not None:
                return routed
        with self._lock:
            deployment = self.deployment
        if deployment is None:
            # jittered Retry-After, like every other shed: a constant
            # (or absent) value would synchronize every honouring SDK
            # into one retry wave against the still-empty server
            return web.json_response(
                {"message": "no model deployed"}, status=503,
                headers={"Retry-After": str(retry_after_jitter(2.0))})
        dl = self._request_deadline(request)
        # Plugin hooks run OUTSIDE the watch-window accounting below: a
        # plugin raising on particular client input is not evidence
        # against a freshly-swapped model, and hedging past a failed
        # before_query would serve the untransformed query.
        try:
            query = self.plugins.before_query(query)
        except KeyError as e:
            return web.json_response(
                {"message": f"missing query field {e.args[0]!r}"}, status=400
            )
        except Exception as e:  # noqa: BLE001
            log.exception("before_query plugin failed")
            return web.json_response({"message": str(e)}, status=500)
        cache = self._query_cache
        ckey = None
        cgen = 0
        if cache is not None and "X-Pio-Probe" not in request.headers:
            # probe traffic bypasses the cache BOTH ways: the latency
            # probe must measure the real dispatch path, and synthetic
            # queries must not pollute hit/miss accounting. The key is
            # the post-plugin query — see QueryResultCache.key_for.
            ckey = QueryResultCache.key_for(query, self._cache_app())
            cgen = cache.generation
            cached = cache.get(ckey)
            if cached is not None:
                return await self._finish_query(request, query, cached)
        try:
            result = await self._dispatch_query(deployment, query, dl)
            if self._watch is not None and self._is_live(deployment):
                self._note_watch(ok=True)
            if (self._quality_runner is not None
                    and self._is_live(deployment)):
                # shadow-scorer sampling: one RNG draw on the hot path;
                # sampled queries cost one ranking extraction + an
                # atomic deque append (scored off-loop by the quality
                # tick, never here)
                self._quality_runner.offer(query, result)
            if ckey is not None:
                # only CLEAN dispatch results are cached — the hedged
                # path below (watch-window failure answered by the
                # retained last-good model) never inserts, so a cache
                # hit is always the live model's own answer; the
                # generation guard drops the insert if a swap
                # invalidated mid-dispatch
                cache.put(ckey, result, cgen)
        except AdmissionShed as e:
            with self._adm_lock:
                self._shed_count += 1
            return web.json_response(
                {"message": f"query shed: {e}"}, status=503,
                headers={"Retry-After":
                         str(retry_after_jitter(e.retry_after_base))})
        except deadline.DeadlineExceeded as e:
            # accepted but out of time: 504, NOT 503 — work started, a
            # blind client retry may duplicate load, so the two cases
            # stay distinguishable
            with self._adm_lock:
                self._deadline_count += 1
            # A pathologically SLOW new model is a rollback trigger
            # too: overruns whose stage shows compute was running count
            # against the watch window (no hedge — the budget is
            # spent). Queue-side stages are overload, not the model,
            # and an overrun on a PRE-swap deployment still in flight
            # is not evidence against the model that replaced it.
            if (self._watch is not None
                    and e.stage not in ("admission", "executor pickup",
                                        "batch queue", "queued")
                    and self._is_live(deployment)
                    and self._note_watch(ok=False)):
                self._rollback_to_previous("error-rate")
            return web.json_response({"message": str(e)}, status=504)
        except KeyError as e:
            return web.json_response(
                {"message": f"missing query field {e.args[0]!r}"}, status=400
            )
        except Exception as e:  # noqa: BLE001 - surfaced as HTTP 500 w/ message
            log.exception("query failed")
            # Inside a post-swap watch window: count the failure against
            # the NEW model (rolling back past the error-rate threshold)
            # and hedge this query onto the retained last-good model so
            # the client still gets its answer. The hedge's OWN
            # overload/deadline outcomes keep their 503/504 verdicts —
            # before this mapping they fell into the bare return below
            # as the canary's raw 500 (the 1-in-~12 seed-5 soak red).
            try:
                hedged = await self._watched_failure(deployment, query,
                                                     dl)
            except AdmissionShed as e2:
                with self._adm_lock:
                    self._shed_count += 1
                return web.json_response(
                    {"message": f"query shed: {e2}"}, status=503,
                    headers={"Retry-After":
                             str(retry_after_jitter(
                                 e2.retry_after_base))})
            except deadline.DeadlineExceeded as e2:
                with self._adm_lock:
                    self._deadline_count += 1
                return web.json_response({"message": str(e2)},
                                         status=504)
            if hedged is None:
                return web.json_response({"message": str(e)}, status=500)
            result = hedged
        return await self._finish_query(request, query, result)

    # -- multi-tenant routing (docs/operations.md "Multi-tenant
    # serving"; the mux itself lives in workflow/multitenant.py) -------

    def _default_app_name(self) -> str:
        """The app the process's default deployment serves — anonymous
        requests and this app's keyed requests share the classic
        single-tenant path (and its cache/lifecycle/fold-in state)."""
        from . import model_artifact

        with self._lock:
            inst = self.instance
        name = (model_artifact.instance_app_name(inst)
                if inst is not None else "")
        return name or (self.feedback_app_name or "")

    def _cache_app(self) -> Optional[str]:
        """Cache-key app component for the DEFAULT query path: None
        while single-tenant (the pre-multi-tenant key shape), the
        default app's name once the mux is armed — the default tenant's
        entries must be app-scoped like everyone else's, or an
        anonymous hit could alias a named tenant's miss."""
        if self._tenants is None:
            return None
        return self._default_app_name() or None

    def _tenant_cache_invalidate(self, app: str,
                                 users=None) -> None:
        """Mux callback: invalidate ONE tenant's served-result cache
        entries — by fold-in freshness footprint when attributable,
        else the whole tenant. Never the neighbors: that asymmetry is
        the reason cache keys carry the app component at all."""
        cache = self._query_cache
        if cache is None:
            return
        if users:
            n = cache.invalidate_users(users, app=app)
        else:
            n = cache.flush_app(app, "tenant")
        if n:
            log.info("tenant %r: invalidated %d cached result(s)",
                     app, n)

    async def _route_tenant_query(self, request: web.Request, query):
        """Route a query to its tenant, or return None for the classic
        default path (anonymous requests and the default app's own
        key). A BAD credential is 401/404 — never a silent fallthrough
        that would serve the default app's model under another
        tenant's key."""
        from . import multitenant

        mux = self._tenants
        try:
            app = mux.resolve_app(request)
        except multitenant.UnknownTenant as e:
            return web.json_response({"message": str(e)}, status=401)
        if app is None or app == self._default_app_name():
            return None
        dl = self._request_deadline(request)
        # same contract as the default path: plugin hooks run OUTSIDE
        # the per-tenant watch accounting
        try:
            query = self.plugins.before_query(query)
        except KeyError as e:
            return web.json_response(
                {"message": f"missing query field {e.args[0]!r}"},
                status=400)
        except Exception as e:  # noqa: BLE001
            log.exception("before_query plugin failed")
            return web.json_response({"message": str(e)}, status=500)
        try:
            state = mux.admit(app)
        except multitenant.UnknownTenant as e:
            return web.json_response({"message": str(e)}, status=404)
        except AdmissionShed as e:
            # the TENANT's budget refused (its own counter); the
            # process-wide gate still guards the dispatch below
            return web.json_response(
                {"message": f"query shed: {e}"}, status=503,
                headers={"Retry-After":
                         str(retry_after_jitter(e.retry_after_base))})
        try:
            # admit→release brackets the whole query: the refcount it
            # holds is what "eviction never drops a tenant mid-query"
            # means mechanically
            return await self._tenant_query(request, state, query, dl)
        finally:
            mux.release(state)

    async def _tenant_query(self, request: web.Request, state, query,
                            dl) -> web.Response:
        """One admitted tenant query: lazy load, app-scoped cache,
        dispatch through the PROCESS admission gate, per-tenant watch
        accounting with the rollback-and-answer hedge."""
        mux = self._tenants
        try:
            await asyncio.to_thread(mux.ensure_loaded, state)
        except Exception as e:  # noqa: BLE001 — nothing deployable for
            # THIS app (never trained / every instance pinned): the
            # tenant is unavailable, the process is healthy → 503
            log.warning("tenant %r load failed: %s", state.name, e)
            return web.json_response(
                {"message": f"tenant {state.name!r}: {e}"}, status=503,
                headers={"Retry-After": str(retry_after_jitter(2.0))})
        cache = self._query_cache
        ckey = None
        cgen = 0
        if cache is not None and "X-Pio-Probe" not in request.headers:
            ckey = QueryResultCache.key_for(query, state.name)
            cgen = cache.generation
            cached = cache.get(ckey)
            if cached is not None:
                return await self._finish_query(request, query, cached)
        deployment = state.deployment
        try:
            result = await self._dispatch_query(deployment, query, dl)
            mux.note_result(state, ok=True)
            if ckey is not None:
                cache.put(ckey, result, cgen)
        except AdmissionShed as e:
            with self._adm_lock:
                self._shed_count += 1
            return web.json_response(
                {"message": f"query shed: {e}"}, status=503,
                headers={"Retry-After":
                         str(retry_after_jitter(e.retry_after_base))})
        except deadline.DeadlineExceeded as e:
            with self._adm_lock:
                self._deadline_count += 1
            # compute-stage overruns count against the tenant's OWN
            # watch (same stage taxonomy as the default path)
            if (e.stage not in ("admission", "executor pickup",
                                "batch queue", "queued")
                    and mux.note_result(state, ok=False)):
                await asyncio.to_thread(mux.rollback_tenant, state,
                                        "error-rate")
            return web.json_response({"message": str(e)}, status=504)
        except KeyError as e:
            return web.json_response(
                {"message": f"missing query field {e.args[0]!r}"},
                status=400)
        except Exception as e:  # noqa: BLE001 — per-tenant watch+hedge
            log.exception("tenant %r query failed", state.name)
            restored = None
            if mux.note_result(state, ok=False):
                # watch breach: pin + roll back THIS tenant alone
                restored = await asyncio.to_thread(
                    mux.rollback_tenant, state, "error-rate")
            if restored is not None:
                # the tenant analogue of the watch hedge: answer the
                # triggering query on the restored deployment
                try:
                    result = await self._dispatch_query(
                        restored, query, dl, direct=True)
                except Exception:  # noqa: BLE001 — original verdict
                    return web.json_response({"message": str(e)},
                                             status=500)
                return await self._finish_query(request, query, result)
            return web.json_response({"message": str(e)}, status=500)
        return await self._finish_query(request, query, result)

    async def _finish_query(self, request: web.Request, query,
                            result) -> web.Response:
        """Shared response tail for dispatched AND cache-hit results:
        after_query plugin, probe-marker accounting bypass, query
        count, feedback self-log. A cache hit goes through the same
        plugin + feedback path as a dispatch — only the model call is
        skipped."""
        try:
            result = self.plugins.after_query(query, result)
        except KeyError as e:
            return web.json_response(
                {"message": f"missing query field {e.args[0]!r}"}, status=400
            )
        except Exception as e:  # noqa: BLE001
            log.exception("after_query plugin failed")
            return web.json_response({"message": str(e)}, status=500)
        probe = request.headers.get("X-Pio-Probe")
        # bytes comparison: compare_digest raises TypeError on non-ASCII
        # str input, which a hostile header could use to 500 the request
        # AFTER the query already executed
        if probe and hmac.compare_digest(
                probe.encode("utf-8", "surrogateescape"),
                self._probe_token.encode()):
            # synthetic startup-probe traffic: excluded from queryCount
            # and the feedback self-log; REAL queries arriving during the
            # probe window are unaffected (the marker is per-request).
            # The marker only counts when it carries this process's
            # random token — external clients can't forge the bypass.
            return web.json_response(result)
        self._query_count += 1
        if self.feedback:
            # sync DAO write runs in the default executor, never on the
            # loop. The future must not be fire-and-forget: a failing
            # event store would otherwise drop feedback events with the
            # exception swallowed by the orphaned future — the
            # done-callback logs every failure and counts it into the
            # droppedFeedback counter on /status.
            fut = asyncio.get_running_loop().run_in_executor(
                None, self._log_feedback, query, result
            )
            fut.add_done_callback(self._feedback_done)
        return web.json_response(result)

    def _feedback_done(self, fut: "asyncio.Future") -> None:
        if fut.cancelled():
            self._dropped_feedback += 1
            return
        exc = fut.exception()
        if exc is not None:
            self._dropped_feedback += 1
            log.error("feedback logging failed (dropped=%d): %s",
                      self._dropped_feedback, exc)

    def _log_feedback(self, query: Any, result: Any) -> None:
        """Self-log the prediction as a "predict" event (reference:
        CreateServer feedback loop → event server). Raises on failure —
        the done-callback owns logging and the dropped counter."""
        app_name = self.feedback_app_name
        if not app_name:
            return
        app = self.storage.get_meta_data_apps().get_by_name(app_name)
        if app is None:
            return
        self.storage.get_l_events().insert(
            Event(
                event="predict",
                entity_type="pio_pr",  # server-generated: prefix allowed internally
                entity_id=str(query.get("user", "")) if isinstance(query, dict) else "",
                properties=DataMap({"query": query, "result": result}),
            ),
            app.id,
        )

    # -- startup latency probe (reference: CreateServer hot path;
    # BASELINE.json north star #2 asks for a MEASURED full-path p50) ----
    def probe_and_record(self, base_url: str, n: int = 60) -> Optional[dict]:
        """Measure the full-path query latency decomposition against the
        LIVE server (real HTTP through loopback) and persist it to the
        EngineInstance row (runtime_conf["probe_latency"]). Components:
        http_full (wire-to-wire), predict (host gather + device dispatch
        + on-chip + download), bare device dispatch RTT (a no-op
        executable's round trip), json parse. http − predict =
        server/HTTP overhead;
        predict − rtt ≈ on-chip + result transfer."""
        import http.client
        import ssl
        import time
        import urllib.parse

        with self._lock:
            deployment, instance = self.deployment, self.instance
        example = self._find_example_query(deployment)
        if example is None:
            log.warning(
                "probe-latency: no deployed model provides example_query(); "
                "skipping")
            return None
        body = json.dumps(example).encode()
        # Loopback self-probe: the server's own cert won't verify for
        # 127.0.0.1 (hostname-scoped / self-signed), and verification
        # adds nothing when we ARE the server.
        tls_ctx = None
        parsed = urllib.parse.urlsplit(base_url)
        if parsed.scheme == "https":
            tls_ctx = ssl.create_default_context()
            tls_ctx.check_hostname = False
            tls_ctx.verify_mode = ssl.CERT_NONE

        # ONE keep-alive connection reused across every sample: the p50
        # must measure steady-state request latency, not a per-request
        # TCP (+TLS) handshake — real serving clients hold persistent
        # connections, and the handshake share was the dominant term of
        # the old per-request-urlopen numbers at sub-ms predict times.
        conn_box: list = [None]

        def connect():
            if parsed.scheme == "https":
                return http.client.HTTPSConnection(
                    parsed.hostname, parsed.port, timeout=60,
                    context=tls_ctx)
            return http.client.HTTPConnection(
                parsed.hostname, parsed.port, timeout=60)

        def post():
            for attempt in (0, 1):
                if conn_box[0] is None:
                    conn_box[0] = connect()
                conn = conn_box[0]
                try:
                    conn.request(
                        "POST", "/queries.json", body=body,
                        headers={"Content-Type": "application/json",
                                 "X-Pio-Probe": self._probe_token})
                    conn.getresponse().read()
                    return
                except (http.client.HTTPException, OSError):
                    # server dropped the idle connection: reconnect and
                    # retry the sample once
                    conn.close()
                    conn_box[0] = None
                    if attempt:
                        raise

        def pct(a, p):
            a = sorted(a)
            return a[min(len(a) - 1, round(p / 100 * (len(a) - 1)))]

        for _ in range(5):  # warm the keep-alive connection + executables
            post()
        http_ms = []
        for _ in range(n):
            t0 = time.perf_counter()
            post()
            http_ms.append((time.perf_counter() - t0) * 1e3)
        if conn_box[0] is not None:
            conn_box[0].close()
        parse_ms, predict_ms = [], []
        for _ in range(n):
            t0 = time.perf_counter()
            q = json.loads(body)
            parse_ms.append((time.perf_counter() - t0) * 1e3)
            t0 = time.perf_counter()
            deployment.query(q)
            predict_ms.append((time.perf_counter() - t0) * 1e3)
        rtt_ms = []
        try:
            import jax
            import numpy as _np

            noop = jax.jit(lambda v: v + 1)
            x = jax.device_put(_np.zeros(8, _np.float32))
            jax.device_get(noop(x))  # compile
            for _ in range(n):
                t0 = time.perf_counter()
                jax.device_get(noop(x))
                rtt_ms.append((time.perf_counter() - t0) * 1e3)
        except Exception:  # noqa: BLE001 - probe must not kill serving
            log.exception("probe-latency: device RTT probe failed")

        from ..parallel.mesh import device_report

        dev = device_report()
        result = {
            "n": n,
            "attachment": f"{dev['platform']}:{dev['deviceKind']}",
            "http_p50_ms": round(pct(http_ms, 50), 3),
            "http_p99_ms": round(pct(http_ms, 99), 3),
            "predict_p50_ms": round(pct(predict_ms, 50), 3),
            "predict_p99_ms": round(pct(predict_ms, 99), 3),
            "dispatch_rtt_p50_ms": round(pct(rtt_ms, 50), 3) if rtt_ms else None,
            "parse_p50_ms": round(pct(parse_ms, 50), 4),
        }
        result["overhead_p50_ms"] = round(
            max(result["http_p50_ms"] - result["predict_p50_ms"], 0.0), 3)
        if rtt_ms:
            result["onchip_plus_transfer_p50_ms"] = round(
                max(result["predict_p50_ms"] - result["dispatch_rtt_p50_ms"],
                    0.0), 3)
        print(f"[probe] full-path p50={result['http_p50_ms']}ms "
              f"p99={result['http_p99_ms']}ms over {n} queries "
              f"({result['attachment']})")
        print(f"[probe]   predict (gather+dispatch+on-chip+fetch) "
              f"p50={result['predict_p50_ms']}ms")
        if rtt_ms:
            print(f"[probe]   bare device dispatch RTT "
                  f"p50={result['dispatch_rtt_p50_ms']}ms → on-chip+transfer "
                  f"≈ {result['onchip_plus_transfer_p50_ms']}ms")
        print(f"[probe]   http+queue overhead p50="
              f"{result['overhead_p50_ms']}ms, json parse "
              f"p50={result['parse_p50_ms']}ms")
        try:
            import dataclasses as _dc

            instances = self.storage.get_meta_data_engine_instances()
            fresh = instances.get(instance.id) or instance
            updated = _dc.replace(
                fresh,
                runtime_conf={**fresh.runtime_conf,
                              "probe_latency": json.dumps(result)})
            instances.update(updated)
            with self._lock:
                # keep the live status page in sync with the stored row
                if self.instance is not None and self.instance.id == updated.id:
                    self.instance = updated
        except Exception:  # noqa: BLE001 - persistence is best-effort
            log.exception("probe-latency: persisting to instance row failed")
        return result

    # -- post-swap watch + rollback ---------------------------------------
    def lifecycle_snapshot(self) -> dict:
        """Model-lifecycle state for /status and `pio status
        --engine-url`: current/previous instance, pins, rollback and
        validation counters, refresh/watch config."""
        from . import model_artifact

        with self._lock:
            cur, prev = self.instance, self._previous
            pinned = dict(self._pinned)
            rollbacks = dict(self._rollbacks)
            swaps = self._swap_count
            validate_failures = self._validate_failures
            refresh_swaps = self._refresh_swaps
        w = self._watch
        return {
            "instance": cur.id if cur else None,
            "previous": prev[1].id if prev else None,
            # process-wide: every model blob the verifying loader
            # refused in this process, by failure kind
            "integrityFailures": model_artifact.integrity_failure_counts(),
            "pinned": pinned,
            "rollbacks": rollbacks,
            "swaps": swaps,
            "validateFailures": validate_failures,
            "validate": self.swap_validate,
            # "disabled(fleet)" when the operator's knob was refused
            # (the coordinator owns refresh) — a bare 0 here looked
            # exactly like "never configured" and hid the reason
            "refreshMs": (f"disabled({self._refresh_disabled})"
                          if self._refresh_disabled
                          else self.model_refresh_ms),
            "refreshSwaps": refresh_swaps,
            "watchMs": self.swap_watch_ms,
            "maxErrorRate": self.swap_max_error_rate,
            "watch": ({"total": w["total"], "errors": w["errors"]}
                      if w is not None else None),
        }

    def _is_live(self, deployment) -> bool:
        """Whether ``deployment`` is the one currently published — watch
        accounting must ignore outcomes of queries dispatched to a
        PRE-swap deployment that were still in flight when the swap
        landed."""
        with self._lock:
            return self.deployment is deployment

    def _note_watch(self, ok: bool) -> bool:
        """Record one query outcome against the post-swap watch window
        (loop context only). Returns True when the error rate tripped
        the rollback threshold — at least 2 failures AND a failure
        fraction above PIO_SWAP_MAX_ERROR_RATE, so one flaky query
        can't roll back a healthy model."""
        w = self._watch
        if w is None:
            return False
        with self._lock:
            cur = self.instance
        if cur is None or w["instance"] != cur.id:
            # a newer swap/rollback superseded this window — but only
            # clear OUR snapshot: a concurrent _load (worker thread) may
            # have already installed the NEW swap's watch, which must
            # not be disarmed by a query that raced the swap
            if self._watch is w:
                self._watch = None
            return False
        if _time.monotonic() > w["until"]:
            log.info("post-swap watch for %s closed clean (%d queries, "
                     "%d errors)", w["instance"], w["total"], w["errors"])
            if self._watch is w:
                self._watch = None
            return False
        w["total"] += 1
        if not ok:
            w["errors"] += 1
            if (w["errors"] >= 2
                    and w["errors"] / w["total"] > self.swap_max_error_rate):
                return True
        return False

    def _rollback_to_previous(self, reason: str) -> Optional[str]:
        """Instant swap back to the resident previous deployment (no
        storage round trip, no recompile — it stayed warm). The bad
        instance is PINNED so neither the latest-completed walk nor the
        refresh loop re-picks it; its blob is never deleted. Returns
        the restored instance id, or None when no previous deployment
        is resident."""
        with self._lock:
            if self._previous is None:
                return None
            bad_inst = self.instance
            self.deployment, self.instance = self._previous
            self._previous = None
            restored = self.instance
        self._watch = None
        # the bad instance's quality watch dies with it — the restored
        # model is the last-good baseline, not a canary
        self._quality_watch = None
        if self._query_cache is not None:
            # every cached result was computed by the model we just
            # rolled away from; the restored model must answer fresh
            n = self._query_cache.flush("rollback")
            log.info("query cache: flushed %d entrie(s) on rollback", n)
        with self._lock:
            # setdefault: a fleet-directed rollback arrives AFTER the
            # coordinator already recorded the real pin reason (e.g.
            # error-rate from the canary) — "fleet" must not clobber it
            self._pinned.setdefault(bad_inst.id, reason)
            self._rollbacks[reason] = self._rollbacks.get(reason, 0) + 1
        self._degraded_reason = (
            f"rolled back from {bad_inst.id} to {restored.id} ({reason}) "
            f"at {_dt.datetime.now(_dt.timezone.utc).isoformat()}; "
            f"{bad_inst.id} pinned until an operator reloads it "
            "explicitly")
        try:
            from . import online

            # a poisoned fold-in rolling back counts on ITS family too,
            # so operators can tell bad increments from bad retrains
            if online.is_foldin_instance(bad_inst):
                online.note_rollback(reason)
        except Exception:  # noqa: BLE001 — accounting must not block it
            pass
        log.warning("automatic rollback (%s): %s → %s; %s pinned",
                    reason, bad_inst.id, restored.id, bad_inst.id)
        return restored.id

    async def _watched_failure(self, deployment, query, dl):
        """A query failed on a deployment inside its post-swap watch
        window: hedge it onto the last-good deployment, and — only when
        last-good SUCCEEDS on the same query (differential diagnosis:
        a query that fails on both models is the query's problem, not
        the canary's) — count the failure against the new model,
        rolling back past the error-rate threshold. Either way the
        client gets the hedged answer instead of the canary's 500.
        Returns the hedged result, or None (caller answers the
        original error). Overload/deadline failures of the HEDGE
        dispatch itself (:class:`AdmissionShed`,
        :class:`deadline.DeadlineExceeded`) PROPAGATE — they are the
        server's state, not the canary's, so the caller must answer
        503/504, never convert them into the canary's raw 500 (the
        soak's seed-5 leak), and they never count against the watch."""
        w = self._watch
        with self._lock:
            live_dep = self.deployment
            prev = self._previous
            cur = self.instance
        if w is None:
            # No watch — but if the deployment this query failed on is
            # no longer the live one, a rollback (which clears the
            # watch) or a swap landed while the query was in flight:
            # its failure is stale evidence, and the client deserves
            # the LIVE model's answer, not the retired model's 500.
            # This is the post-rollback straggler leg of the seed-5
            # soak's raw-500 leak.
            if live_dep is not None and live_dep is not deployment:
                try:
                    return await self._dispatch_query(live_dep, query,
                                                      dl, direct=True)
                except (AdmissionShed, deadline.DeadlineExceeded):
                    raise
                except Exception:  # noqa: BLE001 - original error stands
                    log.exception("retry on live model failed")
                    return None
            return None
        # prune an expired or superseded window BEFORE hedging: outside
        # the watch the client must get the live model's real error,
        # not a silent answer from a long-superseded previous model
        if cur is None or w["instance"] != cur.id:
            if self._watch is w:     # superseded by a newer swap
                self._watch = None
            return None
        if _time.monotonic() > w["until"]:
            log.info("post-swap watch for %s closed clean (%d queries, "
                     "%d errors)", w["instance"], w["total"], w["errors"])
            if self._watch is w:
                self._watch = None
            return None
        if live_dep is not deployment:
            # a concurrent query already rolled back: serve the restored
            try:
                return await self._dispatch_query(live_dep, query, dl,
                                                  direct=True)
            except (AdmissionShed, deadline.DeadlineExceeded):
                raise   # server state, not the canary's error — 503/504
            except Exception:  # noqa: BLE001 - original error stands
                log.exception("retry on restored model failed")
                return None
        if prev is None:
            return None
        try:
            # direct=True: the micro-batch queue would dispatch against
            # the LIVE (canary) deployment, defeating the hedge
            result = await self._dispatch_query(prev[0], query, dl,
                                                direct=True)
        except (AdmissionShed, deadline.DeadlineExceeded):
            # the hedge ran out of budget/capacity: NOT evidence against
            # either model — surface the overload verdict (503/504)
            raise
        except Exception:  # noqa: BLE001 - query fails on BOTH models
            log.exception("hedged retry on last-good model failed too; "
                          "not counting against the new model")
            return None
        if self._note_watch(ok=False):
            self._rollback_to_previous("error-rate")
        return result

    async def handle_rollback(self, request: web.Request) -> web.Response:
        """Operator rollback to the retained previous deployment
        (`pio models rollback --engine-url` / `pio deploy --rollback`).
        Instant — the previous model stayed resident — and pins the
        rolled-back instance so refresh/reload-latest won't re-pick
        it."""
        if self._reload_lock.locked():
            self._reload_conflicts += 1
            return web.json_response(
                {"message": "reload in progress; retry shortly"},
                status=409)
        async with self._reload_lock:
            restored = self._rollback_to_previous("manual")
            if restored is None and self.fleet_mode:
                restored = await self._fleet_rollback_via_store()
        if restored is None:
            return web.json_response(
                {"message": "no previous deployment resident to roll "
                            "back to"}, status=409)
        if self.fleet_mode:
            # propagate NOW instead of waiting for the next tick: the
            # pin lands in this replica's status row, the coordinator
            # picks it up on its next poll, and the whole fleet
            # converges on last-good within the sync bound
            t = asyncio.get_running_loop().create_task(self._fleet_sync())
            t.add_done_callback(
                lambda f: None if f.cancelled() else f.exception())
        return web.json_response(
            {"message": "Rolled back", "engineInstanceId": restored,
             **({"fleet": True} if self.fleet_mode else {})})

    # -- continuous refresh ------------------------------------------------
    async def _start_refresher(self, app) -> None:
        if self.model_refresh_ms > 0:
            self._refresh_task = asyncio.get_running_loop().create_task(
                self._refresh_loop())

    async def _stop_refresher(self, app) -> None:
        task, self._refresh_task = self._refresh_task, None
        if task is not None:
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass

    async def _refresh_loop(self) -> None:
        """Continuous model refresh (PIO_MODEL_REFRESH_MS > 0): poll
        for a newer COMPLETED instance and hot-swap it through the SAME
        validated gate as /reload. A validation failure pins the
        candidate (it will fail again — NaN models don't heal) and
        stays on last-good; a poll/storage error is logged and retried
        next tick. The loop must never die."""
        log.info("model refresh loop armed (every %.0f ms)",
                 self.model_refresh_ms)
        while True:
            await asyncio.sleep(self.model_refresh_ms / 1000.0)
            try:
                await self._refresh_once()
            except asyncio.CancelledError:
                raise
            except Exception:  # noqa: BLE001 - poll errors never kill it
                log.exception("model refresh poll failed; retrying next "
                              "tick")

    async def _refresh_once(self) -> None:
        candidate = await asyncio.to_thread(self._newer_candidate)
        if candidate is None:
            return
        log.info("refresh: newer COMPLETED instance %s; validating "
                 "hot swap", candidate.id)
        if await self._publish_once("refresh") == "swapped":
            with self._lock:
                self._refresh_swaps += 1

    async def _publish_once(self, source: str) -> str:
        """THE publish-through-gate entry point — the ONE place a newer
        COMPLETED instance becomes the served deployment outside an
        operator /reload: validated load of the newest deployable
        instance (skip-if-current), gate-refusal pin + degraded mode,
        integrity-rejection pins, post-swap watch armed by the swap
        itself. Shared by the continuous-refresh loop and the online
        fold-in publisher (docs/operations.md "Online learning") so the
        two paths cannot drift — duplicating the gate/watch/pin
        sequence is exactly how they would. Returns "swapped" |
        "current" | "busy" | "refused" | "error"."""
        if self._reload_lock.locked():
            return "busy"
        async with self._reload_lock:
            rejected: list[tuple[str, str]] = []
            result = "current"
            try:
                swapped = await asyncio.to_thread(
                    self._load, None, True,
                    lambda iid, kind: rejected.append((iid, kind)))
            except SwapValidationError as e:
                with self._lock:
                    self._validate_failures += 1
                    self._pinned[e.instance_id] = "validate"
                self._degraded_reason = (
                    f"{source}: {e}; serving last-good model "
                    f"({e.instance_id} pinned)")
                log.warning("%s swap refused: %s", source, e)
                # a refused FOLD-IN increment counts on its family no
                # matter which caller's gate caught it — the refresh
                # loop can win the reload-lock race for an increment
                # the fold-in tick committed a moment earlier
                await asyncio.to_thread(self._count_foldin_refusal,
                                        e.instance_id)
                result = "refused"
            except Exception as e:  # noqa: BLE001 - stay on last-good
                self._degraded_reason = (
                    f"{source} reload failed at "
                    f"{_dt.datetime.now(_dt.timezone.utc).isoformat()}: "
                    f"{e}; serving last-good model")
                log.exception("%s reload failed; continuing on "
                              "last-good model", source)
                result = "error"
            else:
                if swapped:
                    result = "swapped"
                # the load SUCCEEDED — whether it swapped or confirmed
                # the live instance is still the newest deployable, a
                # degraded reason from an earlier transient failure no
                # longer describes reality
                self._degraded_reason = None
            # pin integrity-rejected candidates: a corrupt blob won't
            # heal, and without the pin every poll would re-walk (and
            # re-count) the same corpse
            for iid, kind in rejected:
                with self._lock:
                    self._pinned.setdefault(iid, f"integrity:{kind}")
                log.warning("%s: pinned undeployable instance %s "
                            "(%s)", source, iid, kind)
            return result

    def _count_foldin_refusal(self, instance_id: str) -> None:
        """Worker-thread classification of a gate-refused instance:
        increments pio_foldin_rollbacks_total{validate} when the row
        carries the fold-in provenance marker. Best-effort — metric
        accounting must never fail a publish path."""
        try:
            from . import online

            row = self.storage.get_meta_data_engine_instances().get(
                instance_id)
            if row is not None and online.is_foldin_instance(row):
                online.note_rollback("validate")
        except Exception:  # noqa: BLE001 — accounting only
            log.debug("fold-in refusal classification failed",
                      exc_info=True)

    # -- streaming online fold-in (docs/operations.md "Online learning") --
    async def _start_foldin(self, app) -> None:
        if self.foldin_ms <= 0:
            return
        if self.fleet_mode and self.fleet_replica != 0:
            # ONE producer per fleet: replica 0 commits increments and
            # the coordinator canaries them to everyone (this replica
            # included) — N replicas each folding the same events would
            # race N duplicate instance rows into the store
            log.info("fold-in: replica %d stands by — replica 0 is the "
                     "fleet's fold-in producer", self.fleet_replica)
            return
        from . import online

        runner = self._foldin_runner = online.FoldInRunner(
            self.storage, self.engine_factory_name, self.engine_variant,
            interval_ms=self.foldin_ms)
        with self._lock:
            instance = self.instance
        if instance is not None:
            # arm the cursor BEFORE the listen port opens: without a
            # persisted cursor the tailer anchors at the log end, and
            # anchoring on the first tick instead would skip events
            # that land in the start→first-tick window
            try:
                await asyncio.to_thread(runner.arm, instance)
            except Exception:  # noqa: BLE001 — first tick retries
                log.exception("fold-in arm failed; first tick retries")
        self._foldin_view = {**runner.view(), "producer": True}
        self._foldin_task = asyncio.get_running_loop().create_task(
            self._foldin_loop())

    async def _stop_foldin(self, app) -> None:
        task, self._foldin_task = self._foldin_task, None
        if task is not None:
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass

    async def _foldin_loop(self) -> None:
        """Online fold-in (PIO_FOLDIN_MS > 0): tail the app's event
        log, fold new events into a copy of the live models, commit the
        increment as a new COMPLETED instance, and publish it through
        the SAME gate as a retrain (fleet mode: leave publication to
        the coordinator's staged canary). A failed tick is logged and
        retried — the loop must never die, and the freshness-lag gauge
        keeps growing until a tick lands."""
        log.info("online fold-in loop armed (every %.0f ms%s)",
                 self.foldin_ms,
                 ", fleet producer" if self.fleet_mode else "")
        while True:
            await asyncio.sleep(self.foldin_ms / 1000.0)
            try:
                await self._foldin_once()
            except asyncio.CancelledError:
                raise
            except Exception:  # noqa: BLE001 - tick errors never kill it
                log.exception("fold-in tick failed; retrying next tick")

    async def _foldin_once(self) -> None:
        from . import online

        if self._tenants is not None:
            # per-tenant fold-in rides the same clock: each resident
            # tenant's runner reads its OWN durable cursor row and its
            # increments publish through that tenant's gate + watch;
            # per-tenant failures are contained inside the tick
            await asyncio.to_thread(self._tenants.foldin_tick)
        with self._lock:
            deployment, instance = self.deployment, self.instance
            pinned = tuple(self._pinned)
        if deployment is None or instance is None:
            return
        runner = self._foldin_runner
        if runner is None:
            runner = self._foldin_runner = online.FoldInRunner(
                self.storage, self.engine_factory_name,
                self.engine_variant, interval_ms=self.foldin_ms)
        try:
            view = await asyncio.to_thread(runner.run_once, deployment,
                                           instance, pinned)
        finally:
            self._foldin_view = {**runner.view(), "producer": True}
        produced = view.get("instance")
        if self.fleet_mode:
            if produced:
                # the coordinator discovers the new COMPLETED row on
                # its next tick and stages it as a CANARY; publishing
                # locally would bypass the staged rollout (and be
                # reverted by the next directive sync anyway)
                log.info("fold-in: instance %s committed; awaiting the "
                         "fleet coordinator's canary staging", produced)
            return
        if not produced and not view.get("pendingInstance"):
            return
        # produced this tick OR still pending from an earlier one (a
        # busy gate / failed cursor persist must not strand a committed
        # increment until the next event happens to arrive)
        # gate refusals are classified + counted inside _publish_once
        # (via the provenance marker), so refusals caught by the
        # refresh loop's racing publish land on the same family
        await self._publish_once("foldin")
        self._foldin_view = {**runner.view(), "producer": True}

    # -- continuous quality evaluation (docs/operations.md
    # "Continuous quality evaluation") ------------------------------------
    async def _start_quality(self, app) -> None:
        if self.quality_sample <= 0:
            return
        from . import quality

        self._quality_runner = quality.QualityShadow(
            self.storage, sample=self.quality_sample,
            k=self.quality_k, min_samples=self.quality_min_samples,
            max_drop=self.quality_max_drop,
            resolve_ms=self.quality_resolve_ms)
        self._quality_view = self._quality_runner.view()
        self._quality_task = asyncio.get_running_loop().create_task(
            self._quality_loop())

    async def _stop_quality(self, app) -> None:
        task, self._quality_task = self._quality_task, None
        if task is not None:
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass

    async def _quality_loop(self) -> None:
        """Shadow scoring (PIO_QUALITY_SAMPLE > 0): replay sampled live
        queries against the retained last-good deployment, grade both
        against held-out next events tailed from the app's log
        partitions, and roll a quality-watch breach back through the
        SAME path as an error-rate breach (reason "quality"). A failed
        tick is logged and retried — the loop must never die."""
        log.info("quality shadow loop armed (sample %.3f, every %.0f "
                 "ms, watch %.0f ms, min %d samples, max ndcg drop "
                 "%.3f)", self.quality_sample, self.quality_ms,
                 self.quality_watch_ms, self.quality_min_samples,
                 self.quality_max_drop)
        while True:
            await asyncio.sleep(self.quality_ms / 1000.0)
            try:
                await self._quality_once()
            except asyncio.CancelledError:
                raise
            except Exception:  # noqa: BLE001 - tick errors never kill it
                log.exception("quality tick failed; retrying next tick")

    async def _quality_once(self) -> None:
        runner = self._quality_runner
        if runner is None:
            return
        with self._lock:
            deployment, instance = self.deployment, self.instance
            prev = self._previous
        if deployment is None or instance is None:
            return
        qw = self._quality_watch
        if qw is not None and (instance.id != qw["instance"]
                               or _time.monotonic() > qw["until"]):
            # superseded by a newer swap/rollback, or closed clean —
            # only clear OUR snapshot (the _note_watch idiom): a
            # concurrent _load may have armed the NEW swap's watch
            if self._quality_watch is qw:
                if instance.id == qw["instance"]:
                    log.info("quality watch for %s closed clean",
                             qw["instance"])
                self._quality_watch = None
            qw = None
        prev_dep = prev[0] if prev is not None else None
        try:
            view = await asyncio.to_thread(runner.run_once, deployment,
                                           instance, prev_dep)
        finally:
            self._quality_view = runner.view()
        if not view.get("breach") or qw is None:
            return
        with self._lock:
            live = self.instance
        if (self._quality_watch is qw and live is not None
                and live.id == qw["instance"]):
            self._quality_watch = None
            restored = self._rollback_to_previous("quality")
            if restored:
                log.warning(
                    "quality watch breach on %s (ndcg drop %.4f > "
                    "%.4f over %d graded samples): rolled back to %s",
                    qw["instance"], view["deltas"].get("ndcg", 0.0),
                    self.quality_max_drop,
                    view.get("live", {}).get("n", 0), restored)

    def _newer_candidate(self):
        """Worker-thread poll: the newest non-pinned COMPLETED instance
        strictly newer than the live one, or None when up to date (the
        shared definition in model_artifact — the fleet coordinator's
        rollout staging must agree with this poll about "newer")."""
        from . import model_artifact

        with self._lock:
            cur = self.instance
            pinned = set(self._pinned)
        # with the tenant mux armed the DEFAULT path refreshes within
        # its own app only — a tenant's fold-in increment is newer but
        # must never hot-swap in as the default deployment
        app = self._cache_app() if self._tenants is not None else None
        return model_artifact.newer_completed_instance(
            self.storage.get_meta_data_engine_instances(),
            self.engine_factory_name, self.engine_variant, cur,
            exclude=pinned, app_name=app)

    # -- replica fleet (store-mediated staged rollout) ---------------------
    def _fleet_bootstrap_load(self) -> None:
        """Initial load of a fleet replica: honor the fleet record
        BEFORE touching the instance walk — a replica relaunched after
        a fleet rollback must come up on the directed last-good
        instance with the fleet's pins applied, not on the newest
        COMPLETED row (which may be exactly the poisoned artifact the
        fleet just rolled back)."""
        from . import model_artifact

        row_id = model_artifact.fleet_row_id(self._fleet_group())
        directive = model_artifact.read_fleet_doc(self.storage, row_id)
        if directive is None:
            # the coordinator re-commits the directive every sync tick,
            # and on backends whose Models.insert is DELETE-then-INSERT
            # (pg/mysql) a read can land in the gap and see the row
            # absent — one short retry separates "no directive yet"
            # from that window, because booting onto the newest
            # COMPLETED row here may be exactly the poisoned artifact
            # the fleet just rolled back
            _time.sleep(0.05)
            directive = model_artifact.read_fleet_doc(
                self.storage, row_id)
        directive = directive or {}
        with self._lock:
            for iid, reason in (directive.get("pinned") or {}).items():
                self._pinned.setdefault(iid, reason)
            pinned = set(self._pinned)
        want = directive.get("instance")
        if want and want not in pinned:
            try:
                self._load(want)
                return
            except Exception:  # noqa: BLE001 - degrade to the walk
                log.warning(
                    "fleet directive instance %s not deployable at "
                    "startup; walking back to latest", want,
                    exc_info=True)
        self._load(None)

    async def _start_fleet(self, app) -> None:
        if self.fleet_mode:
            self._fleet_task = asyncio.get_running_loop().create_task(
                self._fleet_loop())

    async def _stop_fleet(self, app) -> None:
        task, self._fleet_task = self._fleet_task, None
        if task is not None:
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass

    async def _fleet_loop(self) -> None:
        """Fleet sync (PIO_FLEET_SYNC_MS): apply coordinator directives
        and publish this replica's status row. Never dies — a storage
        flake is logged and retried next tick."""
        log.info("fleet sync loop armed (replica %d, every %.0f ms)",
                 self.fleet_replica, self.fleet_sync_ms)
        while True:
            try:
                await self._fleet_sync()
            except asyncio.CancelledError:
                raise
            except Exception:  # noqa: BLE001 - poll errors never kill it
                log.exception("fleet sync failed; retrying next tick")
            await asyncio.sleep(self.fleet_sync_ms / 1000.0)

    async def _fleet_sync(self) -> None:
        from . import model_artifact

        directive = await asyncio.to_thread(
            model_artifact.read_fleet_doc, self.storage,
            model_artifact.fleet_row_id(self._fleet_group())) or {}
        with self._lock:
            # fleet pins propagate to every replica: a restarting
            # refresh/reload on this replica must never re-pick an
            # instance any peer rolled back (mixed-brain prevention)
            for iid, reason in (directive.get("pinned") or {}).items():
                self._pinned.setdefault(iid, reason)
            pinned = set(self._pinned)
            cur = self.instance
        want = directive.get("instance")
        if (directive.get("state") == "canary"
                and directive.get("canaryReplica") == self.fleet_replica
                and directive.get("target")):
            # staged rollout: ONLY the canary replica swaps to the
            # target; everyone else holds the directed instance until
            # the coordinator promotes a clean watch window
            want = directive.get("target")
        if (want and want not in pinned
                and (cur is None or want != cur.id)
                and not self._reload_lock.locked()):
            async with self._reload_lock:
                # recheck under the lock: a concurrent sync (manual-
                # rollback fast path) may have applied this directive
                # while we queued — re-applying would pay a full
                # storage reload for nothing
                with self._lock:
                    cur = self.instance
                if cur is None or want != cur.id:
                    await self._fleet_apply(want)
        await asyncio.to_thread(self._fleet_publish, directive)

    async def _fleet_apply(self, want: str) -> None:
        """Apply one directive target through this replica's own gate.
        A directed rollback whose target is the still-resident previous
        deployment swaps back instantly (no storage round trip); other
        targets take the full verified + validated load. Failures pin
        (validate/integrity) or degrade (transient) — the coordinator
        sees the pin in the next status row and propagates."""
        from . import model_artifact

        with self._lock:
            prev = self._previous
        if prev is not None and prev[1].id == want:
            self._rollback_to_previous("fleet")
            return
        try:
            await asyncio.to_thread(self._load, want)
        except SwapValidationError as e:
            with self._lock:
                self._validate_failures += 1
                self._pinned.setdefault(e.instance_id, "validate")
            self._degraded_reason = (
                f"fleet: {e}; serving last-good model "
                f"({e.instance_id} pinned)")
            log.warning("fleet swap refused by gate: %s", e)
        except model_artifact.ModelIntegrityError as e:
            with self._lock:
                self._pinned.setdefault(e.instance_id,
                                        f"integrity:{e.kind}")
            self._degraded_reason = (
                f"fleet: directed instance {e.instance_id} failed "
                f"integrity ({e.kind}); serving last-good model")
            log.warning("fleet swap refused by integrity: %s", e)
        except Exception as e:  # noqa: BLE001 - transient: retry next tick
            self._degraded_reason = (
                f"fleet reload failed at "
                f"{_dt.datetime.now(_dt.timezone.utc).isoformat()}: {e}; "
                "serving last-good model")
            log.exception("fleet swap failed; continuing on last-good")
        else:
            self._degraded_reason = None

    def _fleet_publish(self, directive: dict) -> None:
        """Worker-thread half of the sync: write this replica's status
        row (single writer: us) and refresh the cached peer view that
        /status and the divergence gauge read."""
        from . import model_artifact

        with self._lock:
            cur, prev = self.instance, self._previous
            pinned = {i: r for i, r in self._pinned.items()
                      if i not in self._pins_provisional}
            rollbacks = dict(self._rollbacks)
        with self._adm_lock:
            draining = self._draining
        w = self._watch
        qw = self._quality_watch
        # the coordinator treats the quality watch EXACTLY like the
        # error watch: a canary promotes only once BOTH windows close
        # clean (a ranking-degrading canary must not be promoted while
        # its labels are still arriving)
        watch_done = ((w is None or cur is None
                       or w.get("instance") != cur.id
                       or _time.monotonic() > w["until"])
                      and (qw is None or cur is None
                           or qw.get("instance") != cur.id
                           or _time.monotonic() > qw["until"]))
        group = self._fleet_group()
        status = {
            "replica": self.fleet_replica,
            "pid": os.getpid(),
            "instance": cur.id if cur else None,
            "previous": prev[1].id if prev else None,
            "pinned": pinned,
            "rollbacks": rollbacks,
            "draining": draining,
            "watchDone": watch_done,
            "epochSeen": directive.get("epoch", 0),
            "updatedAt": _time.time(),
        }
        model_artifact.write_fleet_doc(
            self.storage, model_artifact.fleet_row_id(
                group, self.fleet_replica), status)
        peers = directive.get("peers")
        if peers is None:
            # no coordinator peer snapshot yet (coordinator not started,
            # or a pre-snapshot directive): fall back to reading each
            # peer row directly
            peers = []
            for i in range(max(self.fleet_replicas,
                               self.fleet_replica + 1)):
                doc = model_artifact.read_fleet_doc(
                    self.storage, model_artifact.fleet_row_id(group, i))
                if doc is not None:
                    peers.append(doc)
        else:
            # the coordinator aggregates every status row each tick and
            # ships the snapshot inside the directive — consuming it
            # costs each replica ONE store read per tick instead of N
            # (O(N) fleet-wide, not O(N^2)); substitute our own
            # just-written row so this replica's /status never lags
            # itself by a coordinator tick
            peers = [p for p in peers
                     if p.get("replica") != self.fleet_replica]
            peers.append(status)
            peers.sort(key=lambda p: p.get("replica") or 0)
        serving = {p.get("instance") for p in peers if p.get("instance")}
        self._fleet_view = {
            "group": group,
            "replica": self.fleet_replica,
            "replicas": self.fleet_replicas,
            "syncMs": self.fleet_sync_ms,
            "directive": {k: directive.get(k) for k in
                          ("state", "instance", "target",
                           "canaryReplica", "lastGood", "epoch",
                           "pinned")},
            "peers": peers,
            "divergence": len(serving) > 1,
        }

    async def _fleet_rollback_via_store(self) -> Optional[str]:
        """Fleet rollback on a replica with NO resident previous
        deployment (it was relaunched and booted straight onto the
        current instance): the front's round-robin must not make
        `pio models rollback --engine-url <front>` nondeterministic, so
        pin the current instance and walk back through the store
        instead. Caller holds the reload lock. Returns the restored
        instance id, or None (pin reverted) when nothing older is
        deployable."""
        with self._lock:
            cur = self.instance
        if cur is None:
            return None
        with self._lock:
            # provisional until the walk-back lands: a concurrent
            # _fleet_publish tick during the (slow) storage walk must
            # not ship this pin to the coordinator — pins merge into
            # the directive irreversibly, and if no older instance is
            # deployable we pop the pin and keep serving cur. Only a
            # pin WE insert is provisional/poppable: a pre-existing pin
            # (e.g. merged from the directive while this replica still
            # serves it) is real and must neither vanish from published
            # status rows during the walk nor be deleted on failure
            inserted = cur.id not in self._pinned
            if inserted:
                self._pinned[cur.id] = "manual"
                self._pins_provisional.add(cur.id)
        try:
            await asyncio.to_thread(self._load, None)
        except Exception:  # noqa: BLE001 - nothing older deployable
            if inserted:
                with self._lock:
                    self._pinned.pop(cur.id, None)
                    self._pins_provisional.discard(cur.id)
            log.exception("fleet rollback: no older deployable "
                          "instance; keeping %s live", cur.id)
            return None
        # the reload retained the PINNED instance as "previous" and
        # opened a watch on the restored one — both wrong for a
        # rollback (the hedge/swap-back target must never be the model
        # we just pinned); drop them
        with self._lock:
            self._pins_provisional.discard(cur.id)
            self._previous = None
            self._rollbacks["manual"] = \
                self._rollbacks.get("manual", 0) + 1
            restored = self.instance
        self._watch = None
        log.warning("fleet rollback via store: %s pinned, restored %s",
                    cur.id, restored.id)
        return restored.id

    async def _start_heartbeat(self, app) -> None:
        if envknobs.env_str("PIO_WORKER_HEARTBEAT_FILE", "",
                            lower=False):
            self._hb_task = asyncio.get_running_loop().create_task(
                self._heartbeat_loop())

    async def _stop_heartbeat(self, app) -> None:
        task, self._hb_task = self._hb_task, None
        if task is not None:
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass

    async def _heartbeat_loop(self) -> None:
        """Supervised-replica liveness (the event-server pattern):
        touch the heartbeat file so a wedged event loop — not just a
        dead process — is detected and this replica relaunched. The
        touch is disk I/O, shipped off-loop."""
        from ..parallel import supervisor

        interval = max(0.05, envknobs.env_ms(
            "PIO_WORKER_HEARTBEAT_MS", 1000.0, lo_ms=20.0) / 2.0)
        while True:
            await asyncio.to_thread(supervisor.beat)
            await asyncio.sleep(interval)

    async def handle_reload(self, request: web.Request) -> web.Response:
        """Hot-swap to the latest completed instance (reference: /reload →
        MasterActor ! ReloadServer), or — with ``?instance=<id>`` — to an
        EXPLICIT engine instance (operator rollback/pin-override to a
        known-good version; the target is verified and validated like
        any other swap, and un-pinned on success). A failed reload NEVER
        takes down serving: the last-good model stays live and the
        server enters degraded mode (visible on /status and /readyz)
        until a reload succeeds.

        Serialized: two concurrent /reload calls race `_load` (two
        warm-ups, interleaved compile-gauge swaps, last-writer-wins on
        the deployment) — the loser gets 409 and retries once the
        winner finishes."""
        target = request.query.get("instance") or None
        if self.fleet_mode:
            # a reload through the front would land on ONE replica and
            # be silently reverted by the next directive sync — refuse
            # loudly instead of pretending: rollouts are staged by the
            # coordinator (retrain → canary → promote), rollbacks via
            # POST /rollback (fleet-wide)
            return web.json_response(
                {"message": "fleet mode: model rollout is coordinator-"
                            "driven — retrain to stage a canary, POST "
                            "/rollback for a fleet rollback",
                 "engineInstanceId":
                     self.instance.id if self.instance else None},
                status=409)
        if self._reload_lock.locked():
            self._reload_conflicts += 1
            return web.json_response(
                {"message": "reload already in progress",
                 "engineInstanceId":
                     self.instance.id if self.instance else None},
                status=409)
        async with self._reload_lock:
            try:
                await asyncio.to_thread(self._load, target)
            except Exception as e:  # noqa: BLE001
                if isinstance(e, SwapValidationError):
                    with self._lock:
                        self._validate_failures += 1
                self._degraded_reason = (
                    f"reload failed at "
                    f"{_dt.datetime.now(_dt.timezone.utc).isoformat()}: {e}; "
                    "serving last-good model")
                log.exception("reload failed; continuing on last-good model")
                return web.json_response(
                    {"message": str(e), "degraded": True,
                     "engineInstanceId":
                         self.instance.id if self.instance else None},
                    status=500)
            if target:
                # the operator explicitly chose (and the gate passed)
                # this version — a standing pin no longer applies
                with self._lock:
                    self._pinned.pop(target, None)
        self._degraded_reason = None
        return web.json_response(
            {"message": "Reloaded", "engineInstanceId": self.instance.id}
        )

    # -- graceful drain ----------------------------------------------------
    async def drain_then_stop(self, stopper=None) -> None:
        """SIGTERM / /stop sequence: flip /readyz to 503 FIRST (load
        balancers rotate this replica out and new arrivals shed 503 at
        admission), wait for every ACCEPTED in-flight query up to
        PIO_DRAIN_DEADLINE_MS, then stop — stragglers past the budget
        are failed by shutdown (batch-queue cleanup + connection
        close) rather than holding the process open."""
        with self._adm_lock:
            if self._draining:
                return      # second SIGTERM / /stop: first drain owns it
            self._draining = True
        log.info("draining: readyz → 503, waiting for in-flight queries "
                 "(budget %.0f ms)", self.drain_deadline_ms)
        if stopper is None:
            stopper = self.app.get("stopper")
        await asyncio.sleep(0.05)   # let the triggering response flush
        t_end = _time.monotonic() + self.drain_deadline_ms / 1000.0
        while _time.monotonic() < t_end:
            with self._adm_lock:
                pending = self._adm_pending
            if pending == 0:
                break
            await asyncio.sleep(0.02)
        with self._adm_lock:
            stragglers = self._adm_pending
        if stragglers:
            with self._adm_lock:
                self._drain_stragglers = stragglers
            log.warning("drain deadline (%.0f ms) expired with %d "
                        "query(ies) unfinished; failing them",
                        self.drain_deadline_ms, stragglers)
        else:
            log.info("drain complete: all accepted queries answered")
        if stopper is not None:
            stopper()

    def finalize_shutdown(self, grace: float = 2.0) -> None:
        """After the event loop exits. Worker threads can't be killed,
        so: cancel everything still queued, give RUNNING orphans a
        short grace, then hard-exit rather than letting a hung model
        call block interpreter shutdown forever (the SIGKILL-after-
        drain contract a supervisor would apply, applied to
        ourselves)."""
        self._query_executor.shutdown(wait=False, cancel_futures=True)
        t_end = _time.monotonic() + grace
        while _time.monotonic() < t_end:
            with self._adm_lock:
                if self._adm_pending <= 0:
                    return
            _time.sleep(0.02)
        with self._adm_lock:
            left = self._adm_pending
        log.warning("%d query worker(s) still running after shutdown "
                    "grace; exiting anyway", left)
        os._exit(0)

    async def handle_stop(self, request: web.Request) -> web.Response:
        if self.fleet_mode:
            # through the front this lands on ONE replica, which would
            # drain and exit cleanly — and a clean exit is NOT
            # relaunched by the supervisor, so `pio undeploy` against a
            # fleet would silently shrink it by one replica while
            # reporting success. Refuse loudly: the fleet stops as a
            # unit (SIGTERM to the `pio deploy --replicas` front
            # process drains every replica)
            return web.json_response(
                {"message": "fleet mode: a single-replica stop would "
                            "silently shrink the fleet — stop the "
                            "whole fleet by terminating the `pio "
                            "deploy --replicas` front process "
                            "(SIGTERM)"},
                status=409)
        log.info("stop requested")
        with self._adm_lock:
            draining = self._draining
        if draining:
            return web.json_response({"message": "Already draining."})
        asyncio.get_running_loop().create_task(
            self.drain_then_stop(request.app["stopper"]))
        return web.json_response({"message": "Shutting down."})

    async def handle_plugins(self, request: web.Request) -> web.Response:
        return web.json_response({"plugins": self.plugins.plugin_names()})


def run_engine_server(server: EngineServer, host: str = "0.0.0.0",
                      port: int = 8000, probe_latency: bool = False):
    """Blocking entry point (reference: CreateServer.main)."""
    loop = asyncio.new_event_loop()
    stop_event = asyncio.Event()
    server.app["stopper"] = stop_event.set

    async def main():
        from ..common import ssl_context_from_env

        tls = ssl_context_from_env()
        # short shutdown_timeout: stragglers already got the full drain
        # window; aiohttp's default 60 s grace would triple-wait them
        runner = web.AppRunner(server.app, shutdown_timeout=5.0)
        await runner.setup()
        site = web.TCPSite(runner, host, port, ssl_context=tls)
        await site.start()
        log.info("Engine Server listening on %s:%d", host, port)
        # SIGTERM/SIGINT → graceful drain (readyz 503 first, in-flight
        # queries answered, then exit) — what a rolling restart sends
        import signal as _signal

        rloop = asyncio.get_running_loop()

        def _on_term(signame: str) -> None:
            log.info("%s received: graceful drain", signame)
            rloop.create_task(server.drain_then_stop(stop_event.set))

        for signame in ("SIGTERM", "SIGINT"):
            try:
                rloop.add_signal_handler(
                    getattr(_signal, signame), _on_term, signame)
            except (NotImplementedError, RuntimeError, AttributeError):
                pass    # platform without unix signal support
        if probe_latency:
            scheme = "https" if tls else "http"
            try:
                await asyncio.to_thread(
                    server.probe_and_record, f"{scheme}://127.0.0.1:{port}")
            except Exception:  # noqa: BLE001 - diagnostics must not kill serving
                log.exception("startup latency probe failed; serving anyway")
        await stop_event.wait()
        await runner.cleanup()

    loop.run_until_complete(main())
    server.finalize_shutdown()
