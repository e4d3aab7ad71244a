"""WorkflowContext — what `ctx` means inside DASE components.

The reference passes a SparkContext through every DASE call
(reference: core/.../workflow/WorkflowContext.scala). The TPU-native
context carries the device mesh + storage registry + app binding instead:
everything a component needs to read events and place arrays.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Any, Optional

from ..data.storage.registry import Storage
from ..workflow.workflow_params import WorkflowParams

log = logging.getLogger("pio.workflow")

_cache_enabled = False

#: Where compiled executables are kept when JAX_COMPILATION_CACHE_DIR is
#: unset: ONE fixed path inside the checkout, so every `pio` process —
#: whatever its PIO_FS_BASEDIR — finds what the last one compiled (a
#: directory that moves between runs never hits). Git-ignored.
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache() -> None:
    """Persistent XLA compilation cache, so a fresh `pio train`/`pio
    deploy` process loads the executables an earlier one compiled
    instead of re-paying the compile.

    Where JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and this
    function sets nothing. Otherwise the cache lives at
    DEFAULT_COMPILATION_CACHE_DIR. Must run before the process's first
    compile: JAX latches "no cache" for the life of the process at the
    first compile that finds no directory, so the compiling verbs call
    this before they import an engine module, and WorkflowContext calls
    it again for library users who never pass through the CLI.
    PIO_COMPILATION_CACHE=0 opts out; sub-second compiles are skipped
    by JAX's default jax_persistent_cache_min_compile_time_secs=1.

    The same once-per-process moment hands jax to common/telemetry.py
    (which never imports it): open spans get a profiler annotation, and
    every backend compile becomes an ``xla.compile`` span and counts in
    ``pio_xla_*`` on ``/metrics``, cache or no cache.
    """
    global _cache_enabled
    if _cache_enabled:
        return
    _cache_enabled = True
    from ..common import envknobs, telemetry

    try:
        import jax

        telemetry.install_xla_hooks(jax)
    except Exception:  # noqa: BLE001 - telemetry must not stop a train
        log.warning("compiles will not show as spans or on /metrics",
                    exc_info=True)
    if not envknobs.env_flag("PIO_COMPILATION_CACHE", True):
        return
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    try:
        import jax

        os.makedirs(DEFAULT_COMPILATION_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILATION_CACHE_DIR)
    except Exception:  # noqa: BLE001 - the cache is an optimization; a
        # train must not die for it, but a dead cache must be visible
        log.warning("persistent compilation cache NOT enabled (every "
                    "process will pay the full compile)", exc_info=True)


@dataclasses.dataclass
class WorkflowContext:
    app_name: str = ""
    channel_name: Optional[str] = None
    storage: Optional[Storage] = None
    mesh: Any = None  # jax.sharding.Mesh; lazily built to keep import light
    workflow_params: WorkflowParams = dataclasses.field(default_factory=WorkflowParams)
    engine_instance_id: Optional[str] = None
    # workflow.checkpoint.CheckpointHook when `pio train --checkpoint-every`
    # / `--resume` is active; algorithms with iterative loops snapshot
    # through it (see ops/als.py train_als).
    checkpoint_hook: Any = None
    # workflow.input_pipeline.PipelineConfig — resolved lazily from
    # WorkflowParams + the PIO_PIPELINE_* envs (get_input_pipeline);
    # algorithms pass it to the streaming trainers.
    input_pipeline: Any = None

    def __post_init__(self):
        enable_compilation_cache()

    def get_storage(self) -> Storage:
        return self.storage or Storage.instance()

    def get_mesh(self):
        if self.mesh is None:
            from ..parallel.mesh import default_mesh

            self.mesh = default_mesh()
        return self.mesh

    def get_input_pipeline(self):
        """Resolved streaming-input config: WorkflowParams fields win
        over the PIO_PIPELINE_* envs, envs over built-in defaults."""
        if self.input_pipeline is None:
            import dataclasses as _dc

            from .input_pipeline import PipelineConfig

            wp = self.workflow_params
            cfg = PipelineConfig.from_env(mode=wp.pipeline or None)
            over = {}
            if wp.pipeline_chunk > 0:
                over["chunk_rows"] = wp.pipeline_chunk
            if wp.pipeline_depth > 0:
                over["depth"] = wp.pipeline_depth
            if wp.pipeline_workers > 0:
                over["workers"] = wp.pipeline_workers
            self.input_pipeline = _dc.replace(cfg, **over) if over else cfg
        return self.input_pipeline
