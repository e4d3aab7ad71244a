"""Event Server — the REST ingestion API on :7070.

Reference: data/.../data/api/EventServer.scala (spray-can service). Wire
compatibility targets the documented PredictionIO API so existing SDKs
work unchanged:

  POST   /events.json?accessKey=K[&channel=C]        → 201 {"eventId": id}
  GET    /events/<id>.json?accessKey=K               → 200 event JSON
  DELETE /events/<id>.json?accessKey=K               → 200 {"message": ...}
  GET    /events.json?accessKey=K&<filters>          → 200 [event JSON...]
  POST   /batch/events.json?accessKey=K              → 200 [per-event status]
  GET    /                                           → {"status": "alive"}
  GET    /stats.json?accessKey=K                     → ingestion counters (--stats)
  POST   /webhooks/<connector>.json?accessKey=K      → 3rd-party adapters

Auth: accessKey query param or Authorization header (basic user = key),
checked against the AccessKeys DAO; per-key event whitelists enforced
(reference: Common.withAccessKey / KeyAuthentication).

The aiohttp handlers call synchronous storage DAOs via the default thread
executor, preserving the reference's async-server/sync-store split.
"""

from __future__ import annotations

import asyncio
import base64
import datetime as _dt
import json
import logging
import os
import time
from typing import Optional

from aiohttp import web

from ...common import envknobs, faultinject, ssl_context_from_env, telemetry
from ...common.resilience import CircuitOpenError, retry_after_jitter
from ...workflow.plugins import EventServerPluginContext
from ..storage.base import AccessKey
from ..storage.event import Event, EventValidationError, parse_event_time
from ..storage.registry import Storage
from ..webhooks import get_connector
from . import ingest_wal
from .ingest_buffer import (ForbiddenEventError, IngestBuffer, IngestConfig,
                            IngestOverloadError, parse_single_event)
from .stats import Stats

log = logging.getLogger("pio.eventserver")

MAX_BATCH_SIZE = 50  # reference: /batch/events.json limit


def _json_error(status: int, message: str) -> web.Response:
    return web.json_response({"message": message}, status=status)


class EventServer:
    def __init__(
        self,
        storage: Optional[Storage] = None,
        enable_stats: bool = False,
        plugins: Optional[EventServerPluginContext] = None,
    ):
        # start the PIO_FAULT_SPEC at-mode offset clock at "server
        # constructing" so soak timelines schedule faults relative to
        # worker start (no-op when chaos is off)
        faultinject.arm()
        self.storage = storage or Storage.instance()
        self.stats = Stats() if enable_stats else None
        self.plugins = plugins or EventServerPluginContext()
        # access-key TTL cache: auth otherwise costs one executor hop +
        # one metadata lookup PER ingested event — the single-POST hot
        # path. Key revocation/whitelist edits take effect within the
        # TTL; PIO_ACCESSKEY_CACHE_SECS=0 restores per-request lookups.
        self._key_ttl = envknobs.env_float(
            "PIO_ACCESSKEY_CACHE_SECS", 5.0, lo=0.0)
        self._key_cache: dict = {}  # key -> (expires_monotonic, AccessKey)
        # load-shed accounting: requests refused because the storage
        # backend's circuit breaker is open or the ingest buffer is full
        # (reported on GET /)
        self._shed_count = 0
        # partitioned event log (data/api/event_log.py): a multi-worker
        # deployment gives each worker PIO_EVENT_PARTITION=i. Claim the
        # partition lease FIRST — before WAL replay, before serving —
        # so everything this process ever writes (replay included) runs
        # under fenced ownership. A held lease raises and the worker
        # exits: the supervisor's backoff retries until the previous
        # owner is gone.
        self.lease = None
        part = envknobs.env_str("PIO_EVENT_PARTITION", "")
        if part.isdigit():
            from . import event_log

            le = self.storage.get_l_events()
            log_dir = getattr(le, "_dir", None)
            if log_dir is not None:
                self.lease = event_log.claim_partition(log_dir, int(part))
            else:
                log.warning(
                    "PIO_EVENT_PARTITION=%s but the event store is not a "
                    "JSONL log; partition fencing disabled", part)
        # crash durability (PIO_WAL=1): BEFORE serving, replay any
        # uncommitted write-ahead-log records a previous process left
        # behind (kill -9 mid-group), deduped by event_id against what
        # did land. A dead backing store is logged, not fatal — the
        # server comes up shedding (breaker) and the operator can
        # `pio wal replay` once storage is back.
        wal_config = ingest_wal.WalConfig.from_env()
        wal = None
        if wal_config.enabled:
            try:
                recovered = ingest_wal.recover(
                    self.storage, wal_config, stats=self.stats,
                    plugins=self.plugins)
                if recovered["replayed"] or recovered["deduped"]:
                    log.info("WAL recovery replayed %d event(s), "
                             "deduped %d", recovered["replayed"],
                             recovered["deduped"])
            except Exception:  # noqa: BLE001 — serve; operator replays
                log.exception("WAL recovery failed; uncommitted records "
                              "remain until `pio wal replay` succeeds")
            wal = ingest_wal.IngestWal(wal_config)
        # write-behind group commit: every write handler feeds this
        # buffer; the flusher coalesces concurrent requests into one
        # insert_batch/append per (app, channel) group. The partition
        # lease rides along: its epoch is verified before every write
        # group, so a fenced worker cannot land a byte.
        self.ingest = IngestBuffer(self.storage, self.stats, self.plugins,
                                   IngestConfig.from_env(), wal=wal,
                                   lease=self.lease)
        # background compaction (PIO_COMPACT_INTERVAL_MS > 0): rewrite
        # this worker's own log shards into columnar snapshots so train
        # scans skip the JSON re-parse; scrub once at startup.
        self._compact_interval = envknobs.env_float(
            "PIO_COMPACT_INTERVAL_MS", 0.0, lo=0.0) / 1000.0
        self._compact_min_bytes = envknobs.env_int(
            "PIO_COMPACT_MIN_BYTES", 1 << 20, lo=0)
        self._bg_tasks: list = []
        # telemetry: per-instance stats counters join the process-wide
        # registry exposition via a collector (replaced per instance —
        # the LIVE server's counters are what /metrics shows)
        telemetry.registry().register_collector(
            "eventserver", self._collect_metrics)
        self.app = web.Application(
            client_max_size=16 * 1024 * 1024,
            middlewares=[self._shed_middleware,
                         telemetry.trace_middleware()])
        self.app.cleanup_ctx.append(telemetry.loop_monitor("event"))
        self.app.on_startup.append(self._start_background)
        self.app.on_shutdown.append(self._drain_ingest)
        self.app.add_routes(
            [
                web.get("/", self.handle_root),
                web.get("/metrics", self.handle_metrics),
                web.post("/events.json", self.handle_create),
                web.get("/events.json", self.handle_find),
                web.get("/events/{event_id}.json", self.handle_get),
                web.delete("/events/{event_id}.json", self.handle_delete),
                web.post("/batch/events.json", self.handle_batch),
                web.get("/stats.json", self.handle_stats),
                web.post("/webhooks/{connector}.json", self.handle_webhook),
            ]
        )

    # -- load shedding -----------------------------------------------------
    @web.middleware
    async def _shed_middleware(self, request: web.Request, handler):
        """Backend breaker open → shed with 503 + Retry-After.

        Hammering a dead store with one blocking DAO call per request
        would tie up the executor for the full timeout each time; the
        breaker fails those calls fast and this middleware converts the
        refusal into the HTTP backpressure contract (SDKs honour
        Retry-After), instead of a misleading per-request 500."""
        try:
            return await handler(request)
        except CircuitOpenError as e:
            self._shed_count += 1
            return web.json_response(
                {"message": "event store temporarily unavailable "
                            f"({e.breaker_name}); retry later"},
                status=503,
                # full-jittered: a constant value would synchronize every
                # honouring SDK into one retry wave (thundering herd)
                headers={"Retry-After":
                         str(retry_after_jitter(e.retry_after))},
            )
        except IngestOverloadError as e:
            # the write-behind buffer hit its in-flight cap (or is
            # draining for shutdown): same backpressure contract
            self._shed_count += 1
            return web.json_response(
                {"message": str(e)},
                status=503,
                headers={"Retry-After":
                         str(retry_after_jitter(e.retry_after))},
            )

    # -- background tasks (worker heartbeat, compaction) -------------------
    async def _start_background(self, app) -> None:
        if envknobs.env_str("PIO_WORKER_HEARTBEAT_FILE", "", lower=False):
            self._bg_tasks.append(
                asyncio.get_running_loop().create_task(
                    self._heartbeat_loop()))
        if self._compact_interval > 0:
            self._bg_tasks.append(
                asyncio.get_running_loop().create_task(
                    self._compact_loop()))

    async def _heartbeat_loop(self) -> None:
        """Supervised worker liveness: touch the heartbeat file so a
        wedged event loop (not just a dead process) is detected and the
        worker relaunched (parallel/supervisor.py, worker scope)."""
        from ...parallel import supervisor

        # env_ms returns SECONDS; beat at half the configured period
        interval = max(0.05, envknobs.env_ms(
            "PIO_WORKER_HEARTBEAT_MS", 1000.0, lo_ms=20.0) / 2.0)
        while True:
            # beat() touches the heartbeat file — disk I/O that must
            # stall a worker thread, not the accept loop (a cold or
            # contended volume turning a liveness beat into a server
            # freeze would be the detector CAUSING the disease)
            await asyncio.to_thread(supervisor.beat)
            await asyncio.sleep(interval)

    async def _compact_loop(self) -> None:
        from . import event_log

        le = self.storage.get_l_events()
        log_dir = getattr(le, "_dir", None)
        if log_dir is None:
            return
        # startup scrub: corrupt snapshots are quarantined NOW, not on
        # the first unlucky scan
        report = await asyncio.to_thread(event_log.scrub_log_dir, log_dir)
        if report["quarantined"]:
            log.warning("event-log scrub quarantined %d snapshot(s)",
                        report["quarantined"])
        part = self.lease.partition if self.lease is not None else None
        own_suffix = f".p{part}.jsonl" if part is not None else ".jsonl"
        while True:
            await asyncio.sleep(self._compact_interval)
            try:
                # the directory listing is disk I/O too — a cold or
                # contended volume must stall a worker thread, not the
                # accept loop
                names = await asyncio.to_thread(os.listdir, log_dir)
                for name in sorted(names):
                    if not name.endswith(own_suffix):
                        continue
                    await asyncio.to_thread(
                        event_log.compact_log,
                        os.path.join(log_dir, name),
                        self._compact_min_bytes)
                    # retention rides the compaction cadence: with
                    # PIO_EVENT_RETENTION set this tombstones fully-
                    # expired generations; without it, only the
                    # convergence sweep runs (finishing a crashed
                    # earlier retire pass)
                    await asyncio.to_thread(
                        event_log.retire_expired,
                        os.path.join(log_dir, name))
            except Exception:  # noqa: BLE001 — compaction must not die
                log.exception("background compaction pass failed")

    async def _drain_ingest(self, app) -> None:
        """Shutdown: drain the buffer, then ALWAYS release the cached
        file handles (JSONL append handles, WAL segments) — a drain
        that raises must not leak open fds or keep a WAL segment from
        a clean last fsync."""
        for t in self._bg_tasks:
            t.cancel()
        try:
            await self.ingest.drain()
        finally:
            try:
                close = getattr(self.storage.get_l_events(), "close", None)
                if close is not None:
                    await asyncio.to_thread(close)
            except Exception:  # noqa: BLE001 — best-effort on shutdown
                log.exception("event store close failed on shutdown")
            if self.ingest.wal is not None:
                try:
                    self.ingest.wal.close()
                except Exception:  # noqa: BLE001 — best-effort on shutdown
                    log.exception("WAL close failed on shutdown")
            if self.lease is not None:
                self.lease.release()

    # -- auth -------------------------------------------------------------
    def _access_key_str(self, request: web.Request) -> Optional[str]:
        key = request.query.get("accessKey")
        if key:
            return key
        auth = request.headers.get("Authorization", "")
        if auth.startswith("Basic "):
            try:
                decoded = base64.b64decode(auth[6:]).decode()
                return decoded.split(":", 1)[0]
            except Exception:
                return None
        return None

    async def _authorize(self, request: web.Request) -> AccessKey:
        key = self._access_key_str(request)
        if not key:
            raise web.HTTPUnauthorized(
                text=json.dumps({"message": "Missing accessKey."}),
                content_type="application/json",
            )
        if self._key_ttl > 0:
            hit = self._key_cache.get(key)
            if hit is not None and hit[0] > time.monotonic():
                access_key = hit[1]
            else:
                access_key = await asyncio.to_thread(
                    self.storage.get_meta_data_access_keys().get, key
                )
                # negative results are cached too (same TTL): a flood of
                # bad keys must not turn into a storage-lookup flood
                self._key_cache[key] = (
                    time.monotonic() + self._key_ttl, access_key)
                if len(self._key_cache) > 10_000:
                    # drop EXPIRED entries of either sign (fresh
                    # negatives must survive — they ARE the flood
                    # shield); if everything is fresh, drop oldest by
                    # expiry so the bound holds without O(n) rebuilds
                    # on every subsequent miss
                    now = time.monotonic()
                    fresh = {k: v for k, v in self._key_cache.items()
                             if v[0] > now}
                    if len(fresh) > 10_000:
                        keep = sorted(fresh.items(),
                                      key=lambda kv: kv[1][0])[-5_000:]
                        fresh = dict(keep)
                    self._key_cache = fresh
        else:
            access_key = await asyncio.to_thread(
                self.storage.get_meta_data_access_keys().get, key
            )
        if access_key is None:
            raise web.HTTPUnauthorized(
                text=json.dumps({"message": "Invalid accessKey."}),
                content_type="application/json",
            )
        return access_key

    async def _channel_id(
        self, request: web.Request, access_key: AccessKey
    ) -> Optional[int]:
        name = request.query.get("channel")
        if not name:
            return None
        channels = await asyncio.to_thread(
            self.storage.get_meta_data_channels().get_by_appid, access_key.appid
        )
        for c in channels:
            if c.name == name:
                return c.id
        raise web.HTTPBadRequest(
            text=json.dumps({"message": f"Invalid channel {name!r}."}),
            content_type="application/json",
        )

    def _check_event_allowed(self, access_key: AccessKey, event_name: str) -> None:
        if access_key.events and event_name not in access_key.events:
            raise web.HTTPForbidden(
                text=json.dumps(
                    {"message": f"event {event_name!r} is not allowed for this access key"}
                ),
                content_type="application/json",
            )

    # -- handlers ---------------------------------------------------------
    async def handle_root(self, request: web.Request) -> web.Response:
        out = {"status": "alive"}
        if self.lease is not None:
            out["partition"] = self.lease.partition
        if self._shed_count:
            out["shedRequests"] = self._shed_count
        snap = self.ingest.snapshot()
        if (snap["groupsCommitted"] or snap["pending"]
                or snap["droppedEvents"] or "wal" in snap):
            out["ingest"] = snap
        return web.json_response(out)

    def _collect_metrics(self):
        """Render-time families owned by THIS server instance."""
        if self.stats is not None:
            return [self.stats.family]
        return []

    async def handle_metrics(self, request: web.Request) -> web.Response:
        """Prometheus text exposition of the process registry: ingest
        histograms/counters, storage transport latency + breaker state,
        and (with --stats) the per-app event counters. Unauthenticated
        like GET / — scrapers don't carry access keys."""
        return web.Response(text=telemetry.render_all(),
                            content_type="text/plain")

    async def handle_create(self, request: web.Request) -> web.Response:
        access_key = await self._authorize(request)
        channel_id = await self._channel_id(request, access_key)
        raw = await request.read()
        # per-request ack-mode override (X-Pio-Ack: enqueue|commit):
        # both paths exist on the buffer regardless of the configured
        # default, carry the same WAL durability-before-ack contract,
        # and the soak's mixed flood interleaves them in one run
        ack = request.headers.get("X-Pio-Ack", "").lower()
        if ack and ack not in ("enqueue", "commit"):
            return _json_error(
                400, "X-Pio-Ack must be 'enqueue' or 'commit'")
        ack_enqueue = (ack == "enqueue") if ack \
            else self.ingest.ack_on_enqueue
        if ack_enqueue:
            # fire-and-forget ack: validate inline (same canonical path
            # the group commit uses, so the modes cannot drift) so
            # 400/403 are still real, then respond once queued
            try:
                event, body = parse_single_event(
                    raw, access_key.events or ())
            except EventValidationError as e:
                self._record(access_key.appid, getattr(e, "body", None), 400)
                return _json_error(400, str(e))
            except ForbiddenEventError as e:
                return _json_error(403, str(e))
            event_id = await self.ingest.enqueue_event(
                event, body, access_key, channel_id)
            return web.json_response({"eventId": event_id}, status=201)
        # default (ack=commit): the raw body rides the write-behind
        # buffer as-is — validation, id assignment, stats and plugin
        # dispatch all happen inside the group commit, which encodes
        # whole runs through the native codec's batch path
        try:
            event_id = await self.ingest.ingest_raw(
                raw, access_key, channel_id)
        except EventValidationError as e:
            return _json_error(400, str(e))
        except ForbiddenEventError as e:
            return _json_error(403, str(e))
        except (CircuitOpenError, IngestOverloadError):
            raise  # the shed middleware owns the 503 contract
        except Exception as e:  # noqa: BLE001 — storage fault, per request
            return _json_error(500, f"event store error: {e}")
        return web.json_response({"eventId": event_id}, status=201)

    async def handle_batch(self, request: web.Request) -> web.Response:
        access_key = await self._authorize(request)
        channel_id = await self._channel_id(request, access_key)
        raw = await request.read()
        fast = self._try_native_batch(raw, access_key, channel_id)
        if fast is not None:
            ids, lines = fast
            # pre-encoded canonical lines ride the same buffer as single
            # POSTs: concurrent batch requests group-commit together
            try:
                await self.ingest.ingest_lines(
                    lines, ids, access_key, channel_id)
            except (CircuitOpenError, IngestOverloadError):
                raise  # the shed middleware owns the 503 contract
            except Exception as e:  # noqa: BLE001 — storage fault
                # same per-item shape the python path returns: the whole
                # entry commits atomically, so every item failed together
                return web.json_response(
                    [{"status": 500, "message": f"event store error: {e}"}
                     for _ in ids])
            return web.json_response(
                [{"status": 201, "eventId": eid} for eid in ids])
        try:
            body = json.loads(raw)
        except (json.JSONDecodeError, UnicodeDecodeError):
            return _json_error(400, "invalid JSON body")
        if not isinstance(body, list):
            return _json_error(400, "batch body must be a JSON array")
        if len(body) > MAX_BATCH_SIZE:
            return _json_error(
                400, f"Batch request must have less than or equal to {MAX_BATCH_SIZE} events"
            )
        # Validate every item first (failures stay per-item, matching
        # the reference's independent-items semantics), then persist all
        # valid events through ONE buffer submission: the group commit
        # coalesces them — and whatever else is queued — into a single
        # storage call instead of 50 round-trips.
        results: list[Optional[dict]] = [None] * len(body)
        valid: list[tuple[int, Event, object]] = []
        for pos, obj in enumerate(body):
            try:
                if isinstance(obj, dict):
                    obj = dict(obj)
                    obj.pop("creationTime", None)
                event = Event.from_json(obj)
                self._check_event_allowed(access_key, event.event)
                valid.append((pos, event, obj))
            except (EventValidationError, web.HTTPForbidden) as e:
                message = str(e) if isinstance(e, EventValidationError) else "forbidden"
                results[pos] = {"status": 400, "message": message}
                self._record(access_key.appid, obj, 400)
        if valid:
            # one atomic buffer entry for the whole request: either every
            # valid item commits (201s below) or none did (the raised
            # error — a retry cannot duplicate a partial prefix)
            try:
                event_ids = await self.ingest.ingest_events(
                    [(event, obj if isinstance(obj, dict) else None)
                     for _, event, obj in valid],
                    access_key, channel_id)
            except (CircuitOpenError, IngestOverloadError):
                raise  # whole-request shed, PR 1 contract
            except Exception as e:  # noqa: BLE001 — storage fault
                for pos, _event, _obj in valid:
                    results[pos] = {"status": 500,
                                    "message": f"event store error: {e}"}
                return web.json_response(results)
            for (pos, _event, _obj), eid in zip(valid, event_ids,
                                                strict=True):
                results[pos] = {"status": 201, "eventId": eid}
        return web.json_response(results)

    async def handle_get(self, request: web.Request) -> web.Response:
        access_key = await self._authorize(request)
        channel_id = await self._channel_id(request, access_key)
        event = await asyncio.to_thread(
            self.storage.get_l_events().get,
            request.match_info["event_id"],
            access_key.appid,
            channel_id,
        )
        if event is None:
            return _json_error(404, "Event not found.")
        return web.json_response(event.to_json())

    async def handle_delete(self, request: web.Request) -> web.Response:
        access_key = await self._authorize(request)
        channel_id = await self._channel_id(request, access_key)
        found = await asyncio.to_thread(
            self.storage.get_l_events().delete,
            request.match_info["event_id"],
            access_key.appid,
            channel_id,
        )
        if not found:
            return _json_error(404, "Event not found.")
        return web.json_response({"message": "Found"})

    async def handle_find(self, request: web.Request) -> web.Response:
        access_key = await self._authorize(request)
        channel_id = await self._channel_id(request, access_key)
        q = request.query

        def parse_time(name):
            v = q.get(name)
            return parse_event_time(v) if v else None

        try:
            start_time = parse_time("startTime")
            until_time = parse_time("untilTime")
        except EventValidationError as e:
            return _json_error(400, str(e))
        try:
            limit = int(q.get("limit", 20))
        except ValueError:
            return _json_error(400, "limit must be an integer")
        if limit > 500 or limit == 0:
            limit = 500  # reference caps scans
        event_names = q.getall("event") if "event" in q else None
        events = await asyncio.to_thread(
            lambda: list(
                self.storage.get_l_events().find(
                    access_key.appid,
                    channel_id=channel_id,
                    start_time=start_time,
                    until_time=until_time,
                    entity_type=q.get("entityType"),
                    entity_id=q.get("entityId"),
                    event_names=event_names,
                    target_entity_type=q.get("targetEntityType"),
                    target_entity_id=q.get("targetEntityId"),
                    limit=None if limit < 0 else limit,
                    reversed_order=q.get("reversed", "false") == "true",
                )
            )
        )
        return web.json_response([e.to_json() for e in events])

    async def handle_stats(self, request: web.Request) -> web.Response:
        access_key = await self._authorize(request)
        if self.stats is None:
            return _json_error(
                404, "To see stats, launch Event Server with --stats argument."
            )
        return web.json_response(self.stats.to_json(access_key.appid))

    async def handle_webhook(self, request: web.Request) -> web.Response:
        access_key = await self._authorize(request)
        channel_id = await self._channel_id(request, access_key)
        name = request.match_info["connector"]
        connector = get_connector(name)
        if connector is None:
            return _json_error(404, f"webhook connector {name!r} not found")
        if request.content_type == "application/x-www-form-urlencoded":
            payload = dict(await request.post())
        else:
            try:
                payload = await request.json()
            except json.JSONDecodeError:
                return _json_error(400, "invalid JSON body")
        try:
            event_json = connector.to_event_json(payload)
            event = Event.from_json(event_json)
            self._check_event_allowed(access_key, event.event)
        except EventValidationError as e:
            return _json_error(400, str(e))
        # webhooks feed the same write-behind buffer as direct POSTs
        if self.ingest.ack_on_enqueue:
            event_id = await self.ingest.enqueue_event(
                event, event_json, access_key, channel_id)
            return web.json_response({"eventId": event_id}, status=201)
        try:
            event_id = await self.ingest.ingest_event(
                event, event_json, access_key, channel_id)
        except (CircuitOpenError, IngestOverloadError):
            raise
        except Exception as e:  # noqa: BLE001 — storage fault, per request
            return _json_error(500, f"event store error: {e}")
        return web.json_response({"eventId": event_id}, status=201)

    def _try_native_batch(self, raw: bytes, access_key, channel_id):
        """Native ingest fast path (reference ★ hot path: EventServer →
        validate → store Put, here one C pass over the raw body). Only
        taken when NOTHING needs per-event Python: no per-key event
        whitelist, stats off, no event plugins, and an event store that
        accepts pre-serialized canonical lines (the JSONL log). Returns
        (ids, lines) or None → caller runs the Python path (which also
        owns every error message)."""
        if (access_key.events
                or self.stats is not None
                or self.plugins.plugins
                or not hasattr(self.storage.get_l_events(),
                               "insert_canonical_lines")):
            return None
        try:
            from ...native import NativeUnavailable, ingest_batch

            from ..storage.event import _utcnow, format_event_time

            return ingest_batch(
                raw, MAX_BATCH_SIZE, format_event_time(_utcnow()))
        except NativeUnavailable:
            return None
        except Exception:  # noqa: BLE001 - fast path must never 500 a request
            log.exception("native batch ingest failed; using python path")
            return None

    def _record(self, app_id: int, body, status: int) -> None:
        if status < 400 and isinstance(body, dict):
            self.plugins.on_event(body)
        if self.stats is None:
            return
        name = body.get("event", "?") if isinstance(body, dict) else "?"
        etype = body.get("entityType", "?") if isinstance(body, dict) else "?"
        self.stats.record(app_id, name, etype, status)


def run_event_server(
    host: str = "0.0.0.0",
    port: int = 7070,
    storage: Optional[Storage] = None,
    enable_stats: bool = False,
) -> None:
    """Blocking entry point (reference: EventServer.createEventServer)."""
    server = EventServer(storage, enable_stats)
    log.info("Event Server listening on %s:%d", host, port)
    web.run_app(
        server.app, host=host, port=port, print=None,
        ssl_context=ssl_context_from_env(),
    )
