"""`pio build/train/deploy/undeploy/batchpredict` (reference:
tools/.../commands/Engine.scala + RunWorkflow/RunServer; no spark-submit —
the workflow runs in-process, SURVEY.md §7)."""

from __future__ import annotations

import argparse
import json
import os
import sys

from ...data.storage.registry import Storage
from ...workflow.context import WorkflowContext, enable_compilation_cache
from ...workflow.json_extractor import engine_and_params_from_json, load_engine_json
from ...workflow.workflow_params import WorkflowParams
from . import verb


def _load_engine(ns):
    engine_json_path = os.path.join(ns.engine_dir, "engine.json")
    engine_json = load_engine_json(engine_json_path, getattr(ns, "variant", None))
    engine, params, factory = engine_and_params_from_json(engine_json, ns.engine_dir)
    variant = engine_json.get("id", "default")
    return engine, params, factory, variant, engine_json


def _common_args(p: argparse.ArgumentParser):
    p.add_argument("--engine-dir", default=".", help="template directory (with engine.json)")
    p.add_argument("--variant", default=None, help="engine.json variant suffix")


@verb("build", "validate the engine template (no compilation needed)")
def build_cmd(args: list[str]) -> int:
    p = argparse.ArgumentParser(prog="pio build")
    _common_args(p)
    ns = p.parse_args(args)
    try:
        engine, params, factory, variant, _ = _load_engine(ns)
    except Exception as e:  # noqa: BLE001
        print(f"[error] engine build failed: {e}", file=sys.stderr)
        return 1
    n_algos = len(params.algorithm_params_list) or 1
    print(f"[info] Engine {factory} (variant {variant}) is ready: "
          f"{n_algos} algorithm(s) configured. No compilation needed.")
    return 0


def _placement_default() -> str:
    from ...workflow.placement import device_mode_from_env

    return device_mode_from_env("auto")


@verb("train", "run the training workflow")
def train_cmd(args: list[str]) -> int:
    p = argparse.ArgumentParser(prog="pio train")
    _common_args(p)
    p.add_argument("--batch", default="")
    p.add_argument("--skip-sanity-check", action="store_true")
    p.add_argument("--stop-after-read", action="store_true")
    p.add_argument("--stop-after-prepare", action="store_true")
    p.add_argument("--checkpoint-every", type=int, default=0, metavar="N",
                   help="snapshot algorithm state every N iterations (orbax)")
    p.add_argument("--resume", action="store_true",
                   help="continue the most recent interrupted run from its "
                        "last checkpoint")
    p.add_argument("--profile-dir", default="",
                   help="write a jax.profiler trace of the train stage here")
    p.add_argument("--nan-guard", action="store_true",
                   help="fail fast with stage/iteration attribution when a "
                        "stage produces NaN/Inf (SURVEY §5.2 sanitizer tier; "
                        "iterative trainers dispatch per-iteration)")
    p.add_argument("--device", choices=("tpu", "cpu", "auto"), default=None,
                   help="where to train: auto (default) prices "
                        "accelerator-vs-CPU per algorithm with measured "
                        "link/host rates and picks the faster; tpu/cpu "
                        "force one side (PIO_TRAIN_DEVICE sets the default)")
    p.add_argument("--num-workers", type=int, default=None, metavar="N",
                   help="train as a supervised gang of N worker processes "
                        "(liveness + heartbeat monitoring, automatic "
                        "checkpoint gang-restart; default $PIO_NUM_WORKERS, "
                        "else 1 = in-process)")
    p.add_argument("--feed", choices=("partition", "merged"), default=None,
                   help="training data plane: 'partition' = each gang "
                        "worker reads only its event-log partitions "
                        "(colseg snapshot scans, id maps allgathered; "
                        "the gang default), 'merged' = every worker "
                        "reads the merged view (the pre-partition-feed "
                        "behavior; default $PIO_TRAIN_FEED, else "
                        "'partition' for gangs / 'merged' in-process)")
    p.add_argument("--window", default=None, metavar="DUR",
                   help="train on events from the last DUR only "
                        "(90d/12h/30m/45s): windowed reads skip whole "
                        "sealed log generations by their manifest "
                        "event-time bounds without decoding them "
                        "(default $PIO_TRAIN_WINDOW)")
    ns = p.parse_args(args)

    from ...common import envknobs

    num_workers = (ns.num_workers if ns.num_workers is not None
                   else envknobs.env_int("PIO_NUM_WORKERS", 1, lo=1))
    supervised_worker = envknobs.env_flag("PIO_GANG_WORKER", False)
    if ns.feed:
        # explicit flag wins over env, for this process AND (via
        # inherited env) every gang worker it spawns
        os.environ["PIO_TRAIN_FEED"] = ns.feed
    if ns.window:
        from ...common import train_window

        dur = train_window.parse_duration_us(ns.window)
        if dur is None:
            print(f"[error] --window {ns.window!r}: expected a duration "
                  "like 90d, 12h, 30m, or 45s", file=sys.stderr)
            return 1
        os.environ["PIO_TRAIN_WINDOW"] = ns.window
        # Resolve the duration to an absolute bound ONCE here so every
        # gang worker inherits the identical microsecond cut instead of
        # re-anchoring at its own clock.
        os.environ.setdefault("PIO_TRAIN_WINDOW_START_US",
                              str(train_window.now_us() - dur))
    if num_workers > 1 and not supervised_worker:
        # gang default: the partitioned event log IS the training data
        # plane (workflow/train_feed.py); merged stays one flag away
        os.environ.setdefault("PIO_TRAIN_FEED", "partition")
        return _train_supervised(args, ns, num_workers)
    from ...parallel.distributed import initialize_distributed

    initialize_distributed()  # no-op without PIO_COORDINATOR_ADDRESS
    if supervised_worker:
        # Gang worker: SIGTERM means "checkpoint at the next sweep
        # boundary and exit". Installed AFTER distributed init — jax's
        # coordination service registers XLA's preemption-sync SIGTERM
        # handler during initialize, and the drain semantics must win
        # the sigaction. (No heartbeat yet — the first beat comes from
        # the training loop, after work completes; the supervisor's
        # init grace covers init + compile.)
        from ...parallel.supervisor import install_worker_signal_handlers

        install_worker_signal_handlers()
    from ...workflow.core_workflow import run_train

    # before the engine module is imported: a jit that ran at its import
    # would latch the process's compile cache off (workflow/context.py)
    enable_compilation_cache()
    engine, params, factory, variant, engine_json = _load_engine(ns)
    app_name = (
        dict(params.data_source_params).get("app_name")
        or dict(params.data_source_params).get("appName", "")
    )
    ctx = WorkflowContext(app_name=app_name, storage=Storage.instance())
    wp = WorkflowParams(
        batch=ns.batch,
        skip_sanity_check=ns.skip_sanity_check,
        stop_after_read=ns.stop_after_read,
        stop_after_prepare=ns.stop_after_prepare,
        checkpoint_every=ns.checkpoint_every,
        resume=ns.resume,
        profile_dir=ns.profile_dir,
        nan_guard=ns.nan_guard,
        device=ns.device or _placement_default(),
    )
    import time as _time

    t0 = _time.perf_counter()
    try:
        instance_id = run_train(
            engine, params, ctx, wp,
            engine_factory_name=factory, engine_variant=variant,
        )
    except Exception as e:  # noqa: BLE001 - drain is not a failure
        from ...parallel.supervisor import (DRAIN_EXIT_CODE,
                                            GangDrainRequested)

        if isinstance(e, GangDrainRequested):
            print(f"[info] Drained at step {e.step}; checkpoint kept — "
                  "resume with `pio train --resume`.")
            return DRAIN_EXIT_CODE  # the supervisor treats this as a
            #                         drain outcome, never a failure
        raise
    train_s = _time.perf_counter() - t0
    from ...parallel.mesh import device_report

    # the configured mesh's devices, so the line proves where it trained
    dev = device_report(ctx.get_mesh().devices.flat)
    print(f"[info] Training completed in {train_s:.2f}s on "
          f"platform={dev['platform']} deviceKind={dev['deviceKind']!r} "
          f"deviceCount={dev['deviceCount']}. "
          f"Engine instance ID: {instance_id}")
    return 0


def _strip_num_workers(args: list[str]) -> list[str]:
    """Worker argv = the train argv minus the gang flag (a worker that
    re-spawned a gang would fork-bomb; belt to the PIO_GANG_WORKER
    suspenders)."""
    out, skip = [], False
    for tok in args:
        if skip:
            skip = False
            continue
        if tok == "--num-workers":
            skip = True
            continue
        if tok.startswith("--num-workers="):
            continue
        out.append(tok)
    return out


def _train_supervised(args: list[str], ns, num_workers: int) -> int:
    """Run `pio train` as a supervised gang (parallel/supervisor.py):
    N copies of this exact command, coordinator/process-id wiring from
    the supervisor, automatic checkpoint gang-restart on worker death
    or heartbeat stall, clean drain on SIGTERM."""
    from ...data.storage.event import new_event_id
    from ...parallel.supervisor import (COMPLETED, DRAINED, GangConfig,
                                        Supervisor)

    if ns.checkpoint_every <= 0:
        print("[warn] gang training without --checkpoint-every: a "
              "restart retrains from scratch instead of resuming "
              "mid-run", file=sys.stderr)
    gang_id = None
    if ns.resume:
        # A fresh supervisor invocation must pin the INTERRUPTED run's
        # instance id, or the gang leader would look up a brand-new id
        # and quietly train from scratch.
        from ...workflow.checkpoint import find_resumable_instance

        engine, params, factory, variant, _ = _load_engine(ns)
        prior = find_resumable_instance(
            Storage.instance(), factory or "engine", "1", variant,
            data_source_params=json.dumps(dict(params.data_source_params)),
            preparator_params=json.dumps(dict(params.preparator_params)),
        )
        if prior is not None:
            gang_id = prior.id
            print(f"[info] --resume: continuing interrupted instance "
                  f"{gang_id}")
        else:
            print("[info] --resume requested but no resumable instance "
                  "found; training from scratch")
    gang_id = gang_id or new_event_id()
    worker_argv = [sys.executable, "-m",
                   "incubator_predictionio_tpu.tools.console", "train",
                   *_strip_num_workers(args)]
    sup = Supervisor(worker_argv, num_workers,
                     config=GangConfig.from_env(num_workers),
                     gang_instance_id=gang_id)
    sup.install_signal_handlers()
    print(f"[info] Gang training: {num_workers} workers, instance "
          f"{gang_id}, run dir {sup.run_dir}")
    outcome = sup.run()
    if outcome == COMPLETED:
        print(f"[info] Gang training completed "
              f"({sup.restarts} restart(s)). Engine instance ID: {gang_id}")
        return 0
    if outcome == DRAINED:
        print("[info] Gang drained cleanly; resume with "
              "`pio train --num-workers "
              f"{num_workers} --resume` (instance {gang_id}).")
        return 0
    print(f"[error] Gang training failed after {sup.restarts} restart(s); "
          f"see worker logs under {sup.run_dir}", file=sys.stderr)
    return 1


@verb("deploy", "serve the trained engine over HTTP")
def deploy_cmd(args: list[str]) -> int:
    p = argparse.ArgumentParser(prog="pio deploy")
    _common_args(p)
    p.add_argument("--ip", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--engine-instance-id", default=None)
    p.add_argument("--feedback", action="store_true")
    p.add_argument("--batch-window-ms", type=float, default=0.0,
                   help="coalesce queries arriving within this window into "
                        "one vectorized dispatch (0 = off; raises "
                        "throughput at high QPS for <= window added "
                        "latency)")
    p.add_argument("--max-batch", type=int, default=64)
    p.add_argument("--probe-latency", action="store_true",
                   help="at startup, measure and print the full-path "
                        "query p50/p99 decomposition (HTTP / predict / "
                        "device RTT / parse) against this attachment and "
                        "persist it to the EngineInstance row")
    p.add_argument("--query-conc", type=int, default=None,
                   help="bounded query executor width (default "
                        "$PIO_QUERY_CONC, else cpu+4 capped at 32)")
    p.add_argument("--query-max-pending", type=int, default=None,
                   help="admission queue depth beyond --query-conc; "
                        "excess load sheds 503 + jittered Retry-After "
                        "(default $PIO_QUERY_MAX_PENDING, else 128)")
    p.add_argument("--query-deadline-ms", type=float, default=None,
                   help="per-query deadline budget; exceeded → 504 "
                        "(X-Pio-Deadline-Ms overrides per request; 0 "
                        "disables; default $PIO_QUERY_DEADLINE_MS, "
                        "else 30000)")
    p.add_argument("--drain-deadline-ms", type=float, default=None,
                   help="graceful-drain budget on SIGTERM or /stop "
                        "(default $PIO_DRAIN_DEADLINE_MS, else 10000)")
    p.add_argument("--model-refresh-ms", type=float, default=None,
                   help="poll for newer COMPLETED instances and hot-swap "
                        "them through the validated gate every N ms "
                        "(default $PIO_MODEL_REFRESH_MS, else 0 = off)")
    p.add_argument("--online-foldin", action="store_true",
                   help="streaming online learning: tail the app's "
                        "event log and fold new events into the served "
                        "model continuously, publishing each increment "
                        "through the same validation gate + watch "
                        "window as a retrain (interval $PIO_FOLDIN_MS, "
                        "default 1000; with --replicas, replica 0 "
                        "produces and the coordinator stages canaries)")
    p.add_argument("--quality-eval", action="store_true",
                   help="continuous quality evaluation: shadow-score a "
                        "sampled slice of live queries against held-out "
                        "next events tailed from the app's log, and roll "
                        "a significant canary-vs-last-good ranking "
                        "regression back through the same watch/pin "
                        "path as an error-rate breach (sample rate "
                        "$PIO_QUALITY_SAMPLE, default 0.01 with this "
                        "flag; thresholds via PIO_QUALITY_*)")
    p.add_argument("--rollback", action="store_true",
                   help="don't deploy: tell the engine server already "
                        "running at --ip/--port to roll back to its "
                        "previous deployment, then exit (against a "
                        "fleet front this is a FLEET rollback — the "
                        "pin propagates to every replica)")
    p.add_argument("--multitenant", action="store_true",
                   help="serve EVERY registered app from this process: "
                        "queries route by access key (accessKey param / "
                        "X-Pio-Access-Key) or app name (X-Pio-App) to a "
                        "per-app model cache holding "
                        "$PIO_TENANT_MAX_RESIDENT (default 8) resident "
                        "deployments (LRU; lazy load on first query), "
                        "each tenant with its own validation gate, "
                        "watch/rollback/pin lifecycle, fold-in cursor "
                        "and admission budget ($PIO_TENANT_MAX_PENDING)")
    p.add_argument("--replicas", default=None, metavar="N|auto",
                   help="serve as a fleet of N supervised engine-server "
                        "processes behind an L4 splice front with a "
                        "staged canary rollout (default "
                        "$PIO_QUERY_REPLICAS, else 0 = single process). "
                        "'auto' arms elastic mode: the fleet starts at "
                        "$PIO_FLEET_MIN_REPLICAS and sizes itself "
                        "within [$PIO_FLEET_MIN_REPLICAS, "
                        "$PIO_FLEET_MAX_REPLICAS] from live shed/queue "
                        "telemetry (workflow/elastic.py)")
    p.add_argument("--replica-worker", action="store_true",
                   help=argparse.SUPPRESS)  # internal: fleet replica
    ns = p.parse_args(args)
    if ns.rollback:
        from ...common import ssl_context_from_env
        from .models import rollback_via_url

        # same TLS detection the server itself deploys with; loopback
        # https skips verification (self-signed / hostname-scoped cert)
        scheme = "https" if ssl_context_from_env() else "http"
        host = "127.0.0.1" if ns.ip in ("0.0.0.0", "::") else ns.ip
        return rollback_via_url(f"{scheme}://{host}:{ns.port}",
                                insecure=True)
    from ...common import envknobs

    if ns.replica_worker:
        return _deploy_replica_worker(ns)
    raw = (str(ns.replicas) if ns.replicas is not None
           else envknobs.env_str("PIO_QUERY_REPLICAS", "0"))
    elastic = raw.strip().lower() == "auto"
    if elastic:
        replicas = 0  # run_fleet starts at the operator floor
    else:
        try:
            replicas = max(0, int(raw))
        except ValueError:
            print(f"[error] --replicas expects an integer or 'auto', "
                  f"got {raw!r}", file=sys.stderr)
            return 1
    if replicas >= 1 or elastic:
        return _deploy_fleet(args, ns, replicas, elastic)
    from ...workflow.create_server import run_engine_server

    server = _build_engine_server(ns)
    print(f"[info] Engine is deployed and running. Listening on {ns.ip}:{ns.port}")
    run_engine_server(server, ns.ip, ns.port,
                      probe_latency=ns.probe_latency)
    return 0


def _build_engine_server(ns):
    """ONE EngineServer construction for the single-process deploy and
    the fleet replica worker: a serving knob added here reaches both
    paths (two hand-synced kwarg blocks had already drifted once).
    `model_refresh_ms` is safe to pass in fleet mode — the replica
    zeroes it itself (the coordinator owns refresh), and
    `--online-foldin` reaches every replica too (only replica 0
    produces; the rest stand by as failover producers)."""
    from ...common import envknobs
    from ...workflow.create_server import EngineServer

    enable_compilation_cache()  # before the engine module import
    engine, params, factory, variant, _ = _load_engine(ns)
    app_name = dict(params.data_source_params).get("app_name") or dict(
        params.data_source_params
    ).get("appName", "")
    # --online-foldin arms the loop at $PIO_FOLDIN_MS (default 1000);
    # without the flag the env knob alone can still arm it
    foldin_ms = (float(envknobs.env_int("PIO_FOLDIN_MS", 1000, lo=1))
                 if getattr(ns, "online_foldin", False) else None)
    # --quality-eval arms the shadow scorer at $PIO_QUALITY_SAMPLE
    # (default 1% with the flag); same pattern — the env knob alone can
    # still arm it
    quality_sample = (envknobs.env_float("PIO_QUALITY_SAMPLE", 0.01,
                                         lo=0.0, hi=1.0)
                      if getattr(ns, "quality_eval", False) else None)
    # --multitenant arms the mux at $PIO_TENANT_MAX_RESIDENT (default 8
    # resident deployments); same pattern — the env knob alone can
    # still arm it
    tenant_max_resident = (
        envknobs.env_int("PIO_TENANT_MAX_RESIDENT", 8, lo=1)
        if getattr(ns, "multitenant", False) else None)
    return EngineServer(
        engine,
        engine_factory_name=factory,
        engine_variant=variant,
        instance_id=ns.engine_instance_id,
        feedback=ns.feedback,
        feedback_app_name=app_name,
        batch_window_ms=ns.batch_window_ms,
        max_batch=ns.max_batch,
        query_conc=ns.query_conc,
        query_max_pending=ns.query_max_pending,
        query_deadline_ms=ns.query_deadline_ms,
        drain_deadline_ms=ns.drain_deadline_ms,
        model_refresh_ms=ns.model_refresh_ms,
        foldin_ms=foldin_ms,
        quality_sample=quality_sample,
        tenant_max_resident=tenant_max_resident,
    )


def _strip_replicas(args: list[str]) -> list[str]:
    """Replica worker argv = the deploy argv minus the fleet flag (a
    replica that re-spawned a fleet would fork-bomb; belt to the
    --replica-worker suspenders — the PR 7 --num-workers pattern)."""
    out, skip = [], False
    for tok in args:
        if skip:
            skip = False
            continue
        if tok == "--replicas":
            skip = True
            continue
        if tok.startswith("--replicas="):
            continue
        out.append(tok)
    return out


def _deploy_fleet(args: list[str], ns, replicas: int,
                  elastic: bool = False) -> int:
    """`pio deploy --replicas N` front: the fleet coordinator + splice
    front (workflow/fleet.py) supervising N `--replica-worker` copies
    of this exact command. The front never imports the engine module
    (factory/variant names come straight from engine.json), so it stays
    light while the replicas carry the models."""
    from ...common import envknobs, ssl_context_from_env
    from ...workflow.fleet import run_fleet

    if ssl_context_from_env() is not None:
        # the splice front is plaintext L4: TLS-serving replicas would
        # fail every plaintext /readyz probe (readiness routing never
        # engages) and the front's /healthz first-bytes peek cannot see
        # inside a TLS ClientHello — a silently ops-blind fleet. Refuse
        # with the deployment that works instead.
        print("[error] --replicas does not support PIO_SSL_CERTFILE/"
              "PIO_SSL_KEYFILE: the splice front and its readiness "
              "probes are plaintext L4. Terminate TLS at a proxy in "
              "front of the fleet and unset the PIO_SSL_* knobs here.",
              file=sys.stderr)
        return 1
    engine_json_path = os.path.join(ns.engine_dir, "engine.json")
    engine_json = load_engine_json(engine_json_path,
                                   getattr(ns, "variant", None))
    factory = engine_json.get("engineFactory", "engine")
    variant = engine_json.get("id", "default")
    worker_argv = [sys.executable, "-m",
                   "incubator_predictionio_tpu.tools.console", "deploy",
                   "--replica-worker", *_strip_replicas(args)]
    if ns.probe_latency:
        print("[warn] --probe-latency is ignored with --replicas: the "
              "probe measures ONE process's hot path and would race "
              "N replicas writing the same instance row; probe a "
              "single-process deploy instead", file=sys.stderr)
    if ns.engine_instance_id:
        print("[warn] --engine-instance-id only seeds the replicas' "
              "FIRST load with --replicas: the fleet coordinator owns "
              "rollout and will stage (and, if healthy, promote) the "
              "newest COMPLETED instance on its next tick. To hold the "
              "fleet on an older version, roll back to it (`pio models "
              "rollback --engine-url <front>`) so the newer instance "
              "is pinned", file=sys.stderr)
    if elastic:
        print(f"[info] Engine fleet: elastic replicas behind "
              f"{ns.ip}:{ns.port} (autoscaler armed; bounds from "
              "PIO_FLEET_MIN/MAX_REPLICAS, staged canary rollout, "
              "front /healthz aggregates liveness + scaler state)")
    else:
        print(f"[info] Engine fleet: {replicas} replica(s) behind "
              f"{ns.ip}:{ns.port} (staged canary rollout; front "
              "/healthz aggregates liveness)")
    # with the tenant mux armed, every replica serves N apps but the
    # fleet COORDINATOR stages rollouts for the default app only: an
    # unconfined candidate walk would promote some tenant's fold-in
    # increment fleet-wide as the default deployment
    fleet_app = ""
    if (getattr(ns, "multitenant", False)
            or envknobs.env_int("PIO_TENANT_MAX_RESIDENT", 0, lo=0) > 0):
        ds = (engine_json.get("datasource") or {}).get("params") or {}
        fleet_app = ds.get("appName") or ds.get("app_name") or ""
    return run_fleet(worker_argv, replicas, ns.ip, ns.port,
                     engine_factory_name=factory,
                     engine_variant=variant, app_name=fleet_app,
                     elastic=elastic)


def _deploy_replica_worker(ns) -> int:
    """One supervised fleet replica: identity/port arrive via the
    supervisor environment; the front owns --ip/--port. The
    ``fleet.spawn`` fault point fires BEFORE the engine loads, so
    spawn-window chaos (PIO_FLEET_WORKER_FAULT_SPEC) kills the replica
    where the supervisor's relaunch machinery must catch it."""
    from ...workflow.create_server import run_engine_server
    from ...workflow.fleet import replica_worker_entry

    port = replica_worker_entry()
    if port <= 0:
        return 1
    server = _build_engine_server(ns)
    run_engine_server(server, "127.0.0.1", port)
    return 0


@verb("undeploy", "stop a running engine server")
def undeploy_cmd(args: list[str]) -> int:
    p = argparse.ArgumentParser(prog="pio undeploy")
    p.add_argument("--ip", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000)
    ns = p.parse_args(args)
    import requests

    try:
        r = requests.post(f"http://{ns.ip}:{ns.port}/stop", timeout=10)
        msg = r.json().get("message", r.status_code)
        if r.status_code >= 400:
            # e.g. a fleet replica refusing a single-replica stop
            print(f"[error] {msg}", file=sys.stderr)
            return 1
        print(f"[info] {msg}")
        return 0
    except Exception as e:  # noqa: BLE001
        print(f"[error] {e}", file=sys.stderr)
        return 1


@verb("batchpredict", "bulk scoring: queries JSONL in, predictions JSONL out")
def batchpredict_cmd(args: list[str]) -> int:
    """Reference: tools/.../commands/BatchPredict.scala (0.13+)."""
    p = argparse.ArgumentParser(prog="pio batchpredict")
    _common_args(p)
    p.add_argument("--input", required=True)
    p.add_argument("--output", required=True)
    p.add_argument("--engine-instance-id", default=None)
    p.add_argument("--query-partitions", type=int, default=None, help="ignored (single process)")
    ns = p.parse_args(args)
    from ...workflow.core_workflow import load_deployment

    engine, params, factory, variant, _ = _load_engine(ns)
    ctx = WorkflowContext(storage=Storage.instance())
    deployment, _, _ = load_deployment(
        engine, ns.engine_instance_id, ctx,
        engine_factory_name=factory, engine_variant=variant,
    )
    queries = []
    with open(ns.input) as f:
        for line in f:
            line = line.strip()
            if line:
                queries.append(json.loads(line))
    # Vectorized sweep through each algorithm's batch_predict when there is
    # exactly one algorithm; otherwise per-query through serving.
    if len(deployment.algo_list) == 1:
        _, algo = deployment.algo_list[0]
        supplemented = [deployment.serving.supplement(q) for q in queries]
        preds = algo.batch_predict(deployment.models[0], supplemented)
        results = [
            deployment.serving.serve(q, [pr]) for q, pr in zip(supplemented, preds)
        ]
    else:
        results = [deployment.query(q) for q in queries]
    with open(ns.output, "w") as f:
        for q, r in zip(queries, results):
            f.write(json.dumps({"query": q, "prediction": r}) + "\n")
    print(f"[info] Batch predict completed: {len(results)} predictions → {ns.output}")
    return 0
