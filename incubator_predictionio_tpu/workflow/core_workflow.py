"""CoreWorkflow — run a training job and persist its results.

Reference: core/.../workflow/{CoreWorkflow,CreateWorkflow}.scala: stamp an
EngineInstance row RUNNING → COMPLETED, run engine.train, serialize models
into the Models DAO (or let PersistentModel models save themselves).
No spark-submit: the whole thing is one in-process call (SURVEY.md §7
design stance).
"""

from __future__ import annotations

import contextlib
import dataclasses
import datetime as _dt
import json
import logging
import os
import pickle
import socket
from typing import Any, Optional

from ..common import envknobs, telemetry
from ..controller.engine import Engine, EngineParams
from ..controller.persistent_model import PersistentModel
from ..data.storage.base import EngineInstance
from ..data.storage.event import new_event_id
from . import model_artifact
from .context import WorkflowContext
from .workflow_params import WorkflowParams

log = logging.getLogger("pio.workflow")


def _utcnow():
    return _dt.datetime.now(_dt.timezone.utc)


def serialize_models(algo_list, models: list[Any]) -> bytes:
    """Device pytrees → host → pickle (reference: Engine.makeSerializableModels
    + java serialization into the Models DAO). PersistentModel entries are
    replaced by a marker — they saved themselves."""
    prepared = []
    for (name, algo), model in zip(algo_list, models):
        if isinstance(model, PersistentModel):
            prepared.append({"__persistent__": type(model).__module__ + "." + type(model).__qualname__})
        else:
            prepared.append(algo.prepare_model_for_persistence(model))
    return pickle.dumps(prepared, protocol=pickle.HIGHEST_PROTOCOL)


def deserialize_models(blob: bytes, algo_list, instance_id: str, ctx) -> list[Any]:
    import importlib

    stored = pickle.loads(blob)
    out = []
    for (name, algo), item in zip(algo_list, stored):
        if isinstance(item, dict) and "__persistent__" in item:
            dotted = item["__persistent__"]
            module_name, _, cls_name = dotted.rpartition(".")
            cls = getattr(importlib.import_module(module_name), cls_name)
            out.append(cls.load(instance_id, ctx))
        else:
            out.append(item)
    return out


def _train_with_stale_checkpoint_fallback(engine, engine_params, ctx, wp,
                                          cm=contextlib.nullcontext):
    """engine.train with the --resume stale-snapshot fallback: a
    CheckpointIncompatibleError (data/rank changed) discards the
    checkpoints and retrains from scratch — otherwise every future
    --resume re-selects the same instance and fails the same way. The
    ONE implementation for the gang leader and followers: the
    fingerprint check is deterministic across the gang, so every
    process takes (or skips) this branch at the same point and the
    collectives stay aligned. ``cm`` wraps each attempt (the leader's
    profiler trace)."""
    from .checkpoint import CheckpointHook, CheckpointIncompatibleError

    try:
        with cm():
            return engine.train(ctx, engine_params, wp)
    except CheckpointIncompatibleError as e:
        if ctx.checkpoint_hook is None or not wp.resume:
            raise
        # Shared dir — rmtree tolerates gang peers racing the delete.
        log.warning(
            "--resume: %s; discarding stale checkpoints and training "
            "from scratch", e,
        )
        root = ctx.checkpoint_hook
        root.delete_all()
        ctx.checkpoint_hook = CheckpointHook(
            root.directory, every_n=root.every_n,
            max_to_keep=root.max_to_keep,
        )
        ctx.workflow_params = dataclasses.replace(wp, resume=False)
        try:
            with cm():
                return engine.train(ctx, engine_params, ctx.workflow_params)
        finally:
            ctx.workflow_params = wp


def _run_train_follower(engine, engine_params, ctx, wp, gang_id: str) -> str:
    """Gang processes 1..N-1: participate in every training collective
    (and the checkpoint barriers) under the supervisor-pinned instance
    id, but leave ALL metadata/model persistence to the leader — the
    factors are replicated, so the leader's copy is the gang's copy."""
    from .checkpoint import CheckpointHook, instance_checkpoint_dir

    ctx.engine_instance_id = gang_id
    if wp.resume:
        prior = ctx.get_storage().get_meta_data_engine_instances().get(
            gang_id)
        if prior is not None and prior.status == "COMPLETED":
            # Mirror of the leader's already-COMPLETED exit: on a
            # relaunch that raced the finish line, every process must
            # skip training or the ones that don't would wait forever
            # in the first collective.
            log.info("gang follower: EngineInstance %s already "
                     "COMPLETED; nothing to do", gang_id)
            return gang_id
    if wp.checkpoint_every > 0 or wp.resume:
        ctx.checkpoint_hook = CheckpointHook(
            instance_checkpoint_dir(gang_id), every_n=wp.checkpoint_every)
    try:
        _train_with_stale_checkpoint_fallback(engine, engine_params, ctx, wp)
    finally:
        if ctx.checkpoint_hook is not None:
            ctx.checkpoint_hook.close()
            ctx.checkpoint_hook = None
    log.info("gang follower %s: train stage complete",
             envknobs.env_str("PIO_PROCESS_ID", "?"))
    return gang_id


def _capture_foldin_anchor(storage, ctx):
    """(app_id, LogCursor) at the current event-log end, or None when
    fold-in structurally cannot apply (non-JSONL store, no app).
    Best-effort: training must never fail over its online-learning
    bookkeeping."""
    try:
        from ..data.api.log_tail import LogTailer

        le = storage.get_l_events()
        events_dir = getattr(le, "events_dir", None)
        if not events_dir or not ctx.app_name:
            return None
        app = storage.get_meta_data_apps().get_by_name(ctx.app_name)
        if app is None:
            return None
        return app.id, LogTailer(events_dir, app.id).end_cursor()
    except Exception:  # noqa: BLE001 — bookkeeping only
        return None


def _persist_foldin_anchor(storage, anchor, ctx, engine_factory_name,
                           engine_variant) -> None:
    """Seed the fold-in cursor row from a completed train — ONLY when
    none exists yet: a live fold-in producer owns an existing row
    (single-writer), and rewinding it under a running tailer would
    re-fold everything since its last tick for nothing."""
    if anchor is None:
        return
    try:
        import time as _time

        app_id, cursor = anchor
        group = model_artifact.fleet_group(engine_factory_name,
                                           engine_variant)
        row_id = model_artifact.foldin_row_id(group, app_id)
        if model_artifact.read_fleet_doc(storage, row_id) is not None:
            return
        model_artifact.write_fleet_doc(storage, row_id, {
            "cursor": cursor.to_json(),
            "group": group,
            "appId": app_id,
            "app": ctx.app_name,
            "intervalMs": 0.0,
            "updatedAt": _time.time(),
            "caughtUpAt": None,
            "events": 0,
            "publishes": 0,
            "anchor": "train",
        })
        log.info("fold-in cursor anchored at this train's read "
                 "position (LSN %d) for app %r", cursor.total(),
                 ctx.app_name)
    except Exception:  # noqa: BLE001 — bookkeeping only
        log.debug("could not persist the fold-in train anchor",
                  exc_info=True)


def run_train(
    engine: Engine,
    engine_params: EngineParams,
    ctx: Optional[WorkflowContext] = None,
    workflow_params: Optional[WorkflowParams] = None,
    engine_factory_name: str = "",
    engine_variant: str = "default",
) -> str:
    """Run the training workflow; returns the engine-instance id.

    Call stack parity with SURVEY.md §3.1: Console→train lands here, then
    Engine.train → DataSource.read_training → Preparator.prepare →
    Algorithm.train (pjit'd hot loop) → model persistence.
    """
    ctx = ctx or WorkflowContext()
    wp = workflow_params or WorkflowParams()
    ctx.workflow_params = wp
    # Resolve the streaming-input config ONCE per run (pins the env
    # snapshot for every stage of this train) and record it — whether a
    # train streamed or single-shot must be readable from its log.
    pl = ctx.get_input_pipeline()
    log.info(
        "input pipeline: mode=%s chunk_rows=%d chunk_docs=%d depth=%d "
        "workers=%d", pl.mode, pl.chunk_rows, pl.chunk_docs, pl.depth,
        pl.workers)
    from ..parallel import supervisor as gang

    # Gang runs (parallel/supervisor.py): the supervisor pins ONE
    # engine-instance id for the whole gang so every process agrees on
    # the checkpoint directory and a relaunch resumes the same row.
    # Only process 0 (the leader) touches metadata/model storage;
    # followers train — every collective needs them — and discard.
    gang_id = os.environ.get(gang.ENV_GANG_INSTANCE_ID) or None
    follower = bool(
        gang_id) and envknobs.env_str("PIO_PROCESS_ID", "0") != "0"
    if follower:
        return _run_train_follower(engine, engine_params, ctx, wp, gang_id)
    storage = ctx.get_storage()
    instances = storage.get_meta_data_engine_instances()

    instance = EngineInstance(
        id=new_event_id(),
        status="RUNNING",
        start_time=_utcnow(),
        end_time=None,
        engine_id=engine_factory_name or "engine",
        engine_version="1",
        engine_variant=engine_variant,
        engine_factory=engine_factory_name,
        batch=wp.batch,
        # pid/host let `--resume` distinguish a SIGKILL'd RUNNING row from a
        # train that is genuinely still alive on this machine.
        env={"appName": ctx.app_name, "pid": str(os.getpid()),
             "host": socket.gethostname()},
        data_source_params=json.dumps(dict(engine_params.data_source_params)),
        preparator_params=json.dumps(dict(engine_params.preparator_params)),
        algorithms_params=json.dumps(
            [{"name": n, "params": dict(p)} for n, p in engine_params.algorithm_params_list]
        ),
        serving_params=json.dumps(dict(engine_params.serving_params)),
    )
    if gang_id:
        # Supervisor-pinned id: the row and checkpoint dir are shared
        # by every gang attempt, so resume discovery is a direct get —
        # a relaunch must never pick up some OTHER interrupted run.
        from .checkpoint import instance_checkpoint_dir

        instance = EngineInstance(**{**instance.__dict__, "id": gang_id})
        prior = instances.get(gang_id) if wp.resume else None
        if prior is not None and prior.status == "COMPLETED":
            # A relaunch can race the finish line: the leader persisted
            # and stamped COMPLETED while a wedged follower got the gang
            # killed. The job is DONE — retraining it would flip the row
            # back to RUNNING and duplicate the Model insert. Every gang
            # process takes this same exit (followers check the shared
            # row), so nobody is left alone in a collective.
            log.info("gang resume: EngineInstance %s is already "
                     "COMPLETED; nothing to do", gang_id)
            return gang_id
        if (prior is not None
                and prior.algorithms_params != instance.algorithms_params):
            # Same guard as the discovery path below: resuming under
            # changed hyperparameters would blend them — drop the stale
            # snapshots and train this gang id from scratch.
            from .checkpoint import CheckpointHook

            log.warning(
                "gang --resume: instance %s has different algorithm "
                "params; discarding its checkpoints and training from "
                "scratch", gang_id)
            CheckpointHook(instance_checkpoint_dir(gang_id)).delete_all()
            prior = None
        if (prior is not None and prior.status != "COMPLETED"
                and os.path.isdir(instance_checkpoint_dir(gang_id))):
            instance = EngineInstance(
                **{**instance.__dict__, "start_time": prior.start_time})
            instances.update(instance)
            log.info("gang resume: continuing EngineInstance %s", gang_id)
        elif instances.get(gang_id) is not None:
            # The row exists but isn't resumable (no snapshots landed
            # before the relaunch): retake it fresh — an insert here
            # would be a duplicate key on strict backends.
            instances.update(instance)
        else:
            instances.insert(instance)
        instance_id = gang_id
    elif wp.resume:
        from .checkpoint import find_resumable_instance

        prior = find_resumable_instance(
            storage, engine_factory_name or "engine", "1", engine_variant,
            data_source_params=instance.data_source_params,
            preparator_params=instance.preparator_params,
        )
        if prior is not None and prior.algorithms_params != instance.algorithms_params:
            # Same data, changed hyperparameters — resuming would blend
            # them and falsify provenance. The superseded snapshots are
            # useless under the new params: drop them and retire the row so
            # a `--resume` months from now can't restore stale factors.
            log.warning(
                "--resume: interrupted instance %s has different algorithm "
                "params than the current engine.json; discarding its "
                "checkpoints and training from scratch",
                prior.id,
            )
            from .checkpoint import CheckpointHook, instance_checkpoint_dir

            CheckpointHook(instance_checkpoint_dir(prior.id)).delete_all()
            if prior.status == "RUNNING":
                instances.update(prior.with_status("ABORTED", _utcnow()))
            prior = None
        if prior is not None:
            # Continue the interrupted run under its own instance id so the
            # checkpoint directory and metadata row line up.
            instance = EngineInstance(**{**instance.__dict__, "id": prior.id,
                                         "start_time": prior.start_time})
            instances.update(instance)
            instance_id = prior.id
            log.info("resuming interrupted EngineInstance %s", instance_id)
        else:
            log.info("--resume requested but no resumable instance found; "
                     "training from scratch")
            instance_id = instances.insert(instance)
    else:
        instance_id = instances.insert(instance)
    ctx.engine_instance_id = instance_id
    log.info("EngineInstance %s RUNNING", instance_id)
    # Online-learning anchor (docs/operations.md "Online learning"):
    # capture the event log's position BEFORE the training read so the
    # fold-in tailer's FIRST arm resumes from what this train covers —
    # without it, events ingested between train and `pio deploy
    # --online-foldin` startup fall into neither the trained model nor
    # the tail. Captured here (pre-read) so the error direction is
    # at-least-once: an event racing the read may be both trained AND
    # folded, never silently dropped.
    foldin_anchor = _capture_foldin_anchor(storage, ctx)

    if wp.checkpoint_every > 0 or wp.resume:
        from .checkpoint import CheckpointHook, instance_checkpoint_dir

        ctx.checkpoint_hook = CheckpointHook(
            instance_checkpoint_dir(instance_id), every_n=wp.checkpoint_every
        )

    def _profile_cm():
        if wp.profile_dir:
            # Device-level trace of the whole DASE train (XLA ops, HBM,
            # collectives) — the TPU answer to the Spark web UI the
            # reference leaned on (SURVEY.md §5.1). View with xprof/
            # tensorboard pointed at the directory.
            import jax

            return jax.profiler.trace(wp.profile_dir)
        return contextlib.nullcontext()

    try:
        # the train's root span: every dase.* / als.* / xla.compile span
        # beneath it shares its trace id, the engine-instance id; at close
        # it says how long the collector held the train up
        with telemetry.span("train.run", trace_id=instance_id,
                            instance=instance_id,
                            factory=engine_factory_name) as root, \
                telemetry.gc_share(root):
            models = _train_with_stale_checkpoint_fallback(
                engine, engine_params, ctx, wp, cm=_profile_cm)
            gang.beat()
            if wp.stop_after_read or wp.stop_after_prepare:
                instances.update(instance.with_status("ABORTED", _utcnow()))
                if ctx.checkpoint_hook is not None:
                    ctx.checkpoint_hook.close()
                    ctx.checkpoint_hook = None
                return instance_id

            # Persistence has no natural beat points, and at scale the
            # device_get + pickle + storage insert can outlast the stall
            # threshold — a background beat keeps the supervisor from
            # gang-killing a job whose training already succeeded.
            with gang.beat_while():
                _, _, algo_list, _ = engine.make_components(engine_params)
                persistent = sum(
                    1 for (_, algo), model in zip(algo_list, models)
                    if isinstance(model, PersistentModel)
                    and model.save(instance_id, algo.params))
                # The models and then the blob are dropped where their
                # last use is timed, not when this function returns: at
                # 2.4 GB each their release is a third of a second that
                # would otherwise fall after train.run closed, and the
                # models no longer sit beside two copies of the blob.
                with telemetry.span("dase.serialize"):
                    blob = serialize_models(algo_list, models)
                    del models
                # Checksummed artifact via the single verifying-writer path
                # (workflow/model_artifact.py). The Model row MUST land
                # before the COMPLETED stamp below: a crash in between
                # leaves a RUNNING row (never deployed) instead of a
                # COMPLETED row without a model — and the verifying loader
                # skips the latter anyway, for rows written by older code.
                n_bytes = len(blob)
                with telemetry.span("dase.persist", bytes=n_bytes):
                    sha = model_artifact.write_model(storage, instance_id,
                                                     blob)
                    del blob
                log.info(
                    "models persisted: %d bytes pickled (sha256 %s), "
                    "%d self-persisted",
                    n_bytes, sha[:12], persistent,
                )
                done = EngineInstance(
                    **{**instance.__dict__, "id": instance_id}
                ).with_status("COMPLETED", _utcnow())
                instances.update(done)
                if ctx.checkpoint_hook is not None:
                    ctx.checkpoint_hook.delete_all()  # superseded by the model
                    ctx.checkpoint_hook = None
            _persist_foldin_anchor(storage, foldin_anchor, ctx,
                                   engine_factory_name, engine_variant)
            log.info("EngineInstance %s COMPLETED", instance_id)
            return instance_id
    except Exception:
        # Best-effort ABORTED stamp: when the failure IS the storage
        # backend (dead store, open breaker), this second write fails
        # too — it must never mask the original training error, and the
        # row heals later (`--resume` liveness-checks RUNNING rows by
        # pid/host, so an unstamped row is still recoverable).
        try:
            instances.update(
                EngineInstance(
                    **{**instance.__dict__, "id": instance_id}
                ).with_status("ABORTED", _utcnow())
            )
        except Exception:  # noqa: BLE001 - the original error wins
            log.exception(
                "could not stamp EngineInstance %s ABORTED (storage "
                "unavailable?); surfacing the original failure", instance_id)
        if ctx.checkpoint_hook is not None:
            ctx.checkpoint_hook.close()  # keep snapshots for --resume
            ctx.checkpoint_hook = None
        raise


def load_deployment(
    engine: Engine,
    instance_id: Optional[str],
    ctx: Optional[WorkflowContext] = None,
    engine_factory_name: str = "",
    engine_variant: str = "default",
    exclude_ids=(),
    on_reject=None,
    app_name: Optional[str] = None,
):
    """Load a trained instance for serving (reference: CreateServer /
    MasterActor prepareDeployment). instance_id None → latest
    *deployable* COMPLETED: every candidate's stored model is verified
    (checksum/size/format via workflow/model_artifact.py) and a corrupt,
    missing or unpicklable artifact makes the loader WALK BACK to the
    next-older COMPLETED instance instead of crashing — the bad blob is
    counted (`pio_model_integrity_failures_total{kind}`) and kept on
    disk for forensics, never deleted. ``exclude_ids`` skips instances
    the caller has pinned (a rolled-back deployment must not be
    re-picked); ``on_reject(instance_id, kind)`` is called per skipped
    instance so callers (the refresh loop) can pin them instead of
    re-walking the same corpse every poll. An EXPLICIT instance_id
    never walks back: the operator asked for that version, so a
    failure surfaces as an error. ``app_name`` confines the candidate
    walk to ONE app's instances (the instances namespace is per
    factory/variant, not per app — multi-tenant serving interleaves
    every app's rows in one completed list)."""
    ctx = ctx or WorkflowContext()
    storage = ctx.get_storage()
    instances = storage.get_meta_data_engine_instances()
    excluded = set(exclude_ids or ())
    if instance_id is None:
        candidates = instances.get_completed(
            engine_factory_name or "engine", "1", engine_variant
        )
        if app_name is not None:
            candidates = [
                c for c in candidates
                if model_artifact.instance_app_name(c) == app_name]
        if not candidates:
            raise RuntimeError(
                "No COMPLETED engine instance found"
                + (f" for app {app_name!r}" if app_name else "")
                + "; run `pio train` first"
            )
        candidates = [c for c in candidates if c.id not in excluded]
        if not candidates:
            raise RuntimeError(
                "Every COMPLETED engine instance "
                + (f"for app {app_name!r} " if app_name else "")
                + "is pinned (rolled back "
                "or failed validation); train a fresh instance or reload "
                "one explicitly")
    else:
        instance = instances.get(instance_id)
        if instance is None:
            raise RuntimeError(f"Engine instance {instance_id} not found")
        candidates = [instance]

    rejected: list[str] = []
    caller_app_name = ctx.app_name
    for instance in candidates:
        try:
            payload = model_artifact.read_model(storage, instance.id)
        except model_artifact.ModelIntegrityError as e:
            if instance_id is not None:
                raise
            rejected.append(f"{instance.id} ({e.kind})")
            if on_reject is not None:
                on_reject(instance.id, e.kind)
            log.warning("%s; walking back to an older COMPLETED instance",
                        e)
            continue
        engine_params = EngineParams(
            data_source_params=json.loads(instance.data_source_params),
            preparator_params=json.loads(instance.preparator_params),
            algorithm_params_list=[
                (a["name"], a["params"])
                for a in json.loads(instance.algorithms_params)
            ],
            serving_params=json.loads(instance.serving_params),
        )
        ctx.engine_instance_id = instance.id
        # derive from THIS candidate, not whatever a previously rejected
        # candidate left behind — each walk iteration binds its own app
        if not caller_app_name:
            ctx.app_name = instance.env.get("appName", "")
        _, _, algo_list, _ = engine.make_components(engine_params)
        try:
            models = deserialize_models(payload, algo_list, instance.id, ctx)
        except Exception as e:  # noqa: BLE001 - checksummed yet unloadable
            if instance_id is not None:
                raise
            ctx.app_name = caller_app_name
            model_artifact.count_integrity_failure("deserialize")
            rejected.append(f"{instance.id} (deserialize)")
            if on_reject is not None:
                on_reject(instance.id, "deserialize")
            log.warning(
                "model for engine instance %s verified but failed to "
                "deserialize (%s); walking back to an older COMPLETED "
                "instance", instance.id, e)
            continue
        deployment = engine.prepare_deployment(ctx, engine_params, models)
        if rejected:
            log.warning(
                "deployed %s after skipping %d undeployable instance(s): "
                "%s", instance.id, len(rejected), ", ".join(rejected))
        return deployment, instance, engine_params
    raise RuntimeError(
        "No deployable COMPLETED engine instance: all candidates "
        f"rejected ({', '.join(rejected)}); blobs kept for forensics — "
        "`pio models verify` to inspect, `pio train` to replace")
