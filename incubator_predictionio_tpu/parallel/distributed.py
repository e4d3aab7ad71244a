"""Multi-host initialization — the control-plane analog of Spark's
driver/executor RPC (reference dependency; SURVEY.md §2.10).

Single-host: no-op. Multi-host: `jax.distributed.initialize` connects every
host to the coordination service over DCN; afterwards jax.devices() spans
the pod and the same mesh/pjit code runs unchanged (single-controller SPMD
per host — the workflow binary is simply launched once per host, the way
the reference launches one executor JVM per node).

Failure semantics (the gang supervisor depends on these): a worker that
cannot REACH its coordinator must error within ``PIO_COORDINATOR_TIMEOUT_MS``
instead of retrying forever, and a worker whose coordinator DIES mid-run
must notice within ``PIO_DIST_HEARTBEAT_MS × PIO_DIST_MAX_MISSING_HEARTBEATS``
(the coordination-service health check, which also tears down the
remaining processes when any peer is declared dead) — so a dead gang
member surfaces as a worker error the supervisor can act on rather than
an infinite hang in the next collective.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

import jax

from ..common import envknobs

log = logging.getLogger("pio.distributed")


def process_count() -> int:
    return jax.process_count()


def is_multi_host() -> bool:
    return jax.process_count() > 1


def resolve_distributed_timeouts() -> dict:
    """Resolved connection/health-check knobs (seconds, jax's unit).

    - ``PIO_COORDINATOR_TIMEOUT_MS`` — how long a process retries the
      initial coordinator connection before erroring (jax
      ``initialization_timeout``; default 300 s). Floored at 1 s —
      jax takes whole seconds.
    - ``PIO_DIST_HEARTBEAT_MS`` — coordination-service heartbeat
      interval (default 10 s, floor 1 s).
    - ``PIO_DIST_MAX_MISSING_HEARTBEATS`` — missed beats before a
      process is declared dead and the job torn down (default 10).
      jax takes their product (``heartbeat_timeout_seconds``).

    Malformed or absent values fall back to the jax defaults (a typo'd
    knob must not take down a training job at init).
    """
    init_s = envknobs.env_ms("PIO_COORDINATOR_TIMEOUT_MS", 300_000.0,
                             lo_ms=1000.0)
    hb_s = envknobs.env_ms("PIO_DIST_HEARTBEAT_MS", 10_000.0, lo_ms=1000.0)
    missing = envknobs.env_int("PIO_DIST_MAX_MISSING_HEARTBEATS", 10, lo=2)
    return {
        "initialization_timeout": max(1, int(round(init_s))),
        "heartbeat_interval": max(1, int(round(hb_s))),
        "max_missing_heartbeats": missing,
    }


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Initialize JAX multi-controller runtime from args or PIO_* env vars
    (PIO_COORDINATOR_ADDRESS, PIO_NUM_PROCESSES, PIO_PROCESS_ID). Safe to
    call when unset → single-process mode. Timeout/health-check knobs:
    :func:`resolve_distributed_timeouts`."""
    coordinator_address = (
        coordinator_address
        or envknobs.env_str("PIO_COORDINATOR_ADDRESS", "", lower=False))
    if not coordinator_address:
        log.debug("single-process mode (no PIO_COORDINATOR_ADDRESS)")
        return
    # identity knobs parse STRICTLY (int() raises on garbage AND on a
    # set-but-empty value): a gang worker whose rank/world-size env is
    # garbled must crash loudly at startup — any tolerant fallback to
    # rank 0 / world 1 would collide with the real leader or hang its
    # peers' collectives instead
    num_processes = num_processes or int(
        os.environ.get("PIO_NUM_PROCESSES", "1"))  # pio-lint: disable=knob-envknobs -- identity knob: strict crash beats tolerant world=1
    process_id = (process_id if process_id is not None
                  else int(os.environ.get("PIO_PROCESS_ID", "0")))  # pio-lint: disable=knob-envknobs -- identity knob: strict crash beats tolerant rank=0
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        # The CPU PJRT client ships WITHOUT cross-process collectives by
        # default ("Multiprocess computations aren't implemented on the
        # CPU backend") — select the gloo TCP implementation before the
        # backend initializes. TPU/GPU pods use their own interconnect
        # collectives and never read this flag.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    t = resolve_distributed_timeouts()
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        initialization_timeout=t["initialization_timeout"],
        # jax takes the product: a peer silent for this long is dead
        heartbeat_timeout_seconds=(t["heartbeat_interval"]
                                   * t["max_missing_heartbeats"]),
    )
    log.info(
        "jax.distributed initialized: process %d/%d, %d global devices",
        process_id, num_processes, len(jax.devices()),
    )
