"""Cost-based device placement for training (`pio train --device`).

Reference contract: tools/.../tools/Runner.scala — "run where configured
to be fastest" (the reference delegates the choice to deploy-time Spark
configuration). TPU-native version: the choice is MEASURED, per workload,
at train time. Where the host→device link is slow against host RAM, a
single-pass, transfer-bound train (NB sufficient stats, TF-IDF featurize)
can lose to the host CPU — dispatching it to the accelerator anyway is
"run where configured", not "run where fastest".

Model: an algorithm describes its workload as a StageModel (bytes that
must reach the device, number of algorithmic passes over them there,
bytes the CPU path would stream instead); this module prices both
placements with rates MEASURED ONCE per process (a timed device_put for
the link, a timed numpy pass for host bandwidth) and picks the cheaper,
logged and overridable (--device=tpu|cpu|auto).
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Optional

from ..common import envknobs

log = logging.getLogger("pio.placement")

#: Sustained on-device bandwidth assumed for pass pricing, per
#: ``device_kind`` — deliberately conservative: the decision is dominated
#: by the measured link rate, this term only keeps many-pass workloads
#: (ALS, CCO) priced sub-linearly on device. A device that is not in the
#: table is an error, not a default (see :func:`_device_pass_bps`).
_DEVICE_PASS_BPS = {"TPU v5 lite": 200e9}
_PROBE_BYTES = 8 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class StageModel:
    """What a train stage would move and touch, in bytes.

    bytes_to_device: one-time upload the accelerator path needs.
    device_passes:  algorithmic passes over those bytes on device.
    host_bytes:     bytes the CPU path streams instead (usually the same
                    data, possibly wider/narrower).
    cpu_passes:     passes over host_bytes on the CPU path.
    """

    bytes_to_device: int
    device_passes: float = 1.0
    host_bytes: Optional[int] = None
    cpu_passes: float = 1.0

    @property
    def effective_host_bytes(self) -> int:
        return self.bytes_to_device if self.host_bytes is None else self.host_bytes


_rates: dict = {}


def _measured_put_bps() -> float:
    """Host→default-device transfer rate, measured once per process
    (8 MB put + block). A failure here is a bug on a machine with a
    device and propagates: pricing a broken link at some pessimal rate
    would silently send every auto-placed stage to the host."""
    if "put" not in _rates:
        import jax
        import jax.numpy as jnp
        import numpy as np

        dev = jax.devices()[0]
        # One trivial executable runs first, so the rate is the one a
        # train sees (every real train runs executables before and
        # between its uploads).
        jax.block_until_ready(
            jax.jit(lambda v: v + 1)(jnp.zeros(8, jnp.float32)))
        buf = np.empty(_PROBE_BYTES, np.uint8)
        # warm BOTH the put path and the x[:1] barrier executable — a
        # first-time slice compile inside the timed window would bill a
        # compile to the link rate
        warm = jax.device_put(buf, dev)
        _ = jax.device_get(warm[:1])
        t0 = time.perf_counter()
        x = jax.device_put(buf, dev)
        # completion barrier: a readback that depends on the put
        _ = jax.device_get(x[:1])
        dt = max(time.perf_counter() - t0, 1e-6)
        _rates["put"] = _PROBE_BYTES / dt
    return _rates["put"]


def _device_pass_bps() -> float:
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in _DEVICE_PASS_BPS:
        raise ValueError(
            f"--device=auto cannot price device_kind {kind!r}: no "
            f"on-device pass rate is recorded for it (known: "
            f"{sorted(_DEVICE_PASS_BPS)}); pass --device=tpu or "
            "--device=cpu")
    return _DEVICE_PASS_BPS[kind]


def _measured_cpu_bps() -> float:
    """Host streaming rate, measured once (one numpy reduction pass)."""
    if "cpu" not in _rates:
        import numpy as np

        buf = np.empty(_PROBE_BYTES // 4, np.float32)
        buf.sum()  # touch/fault pages
        t0 = time.perf_counter()
        buf.sum()
        dt = max(time.perf_counter() - t0, 1e-6)
        _rates["cpu"] = _PROBE_BYTES / dt
    return _rates["cpu"]


def _default_is_cpu() -> bool:
    import jax

    return jax.devices()[0].platform == "cpu"


def validate_device_mode(mode: str) -> str:
    if mode not in ("tpu", "cpu", "auto"):
        raise ValueError(f"--device={mode!r}: expected tpu|cpu|auto")
    return mode


def require_tpu(mesh):
    """``--device=tpu`` means a TPU. On a machine where JAX fell back to
    the CPU, training there without a word would pass for a chip run."""
    platform = mesh.devices.flat[0].platform
    if platform != "tpu":
        raise RuntimeError(
            f"--device=tpu, but the configured mesh is on platform "
            f"{platform!r} ({mesh.devices.flat[0].device_kind}): JAX "
            "found no TPU. Use --device=cpu or --device=auto to train "
            "here.")
    return mesh


def choose(model: Optional[StageModel], mode: str, stage: str = "") -> str:
    """"cpu" or "device" for this stage. mode: tpu|cpu|auto."""
    validate_device_mode(mode)
    if mode == "tpu":
        return "device"
    if mode == "cpu":
        return "cpu"
    if model is None or _default_is_cpu():
        return "device"  # nothing to compare (or default IS the cpu)
    put = _measured_put_bps()
    cpu = _measured_cpu_bps()
    t_dev = (model.bytes_to_device / put
             + model.device_passes * model.bytes_to_device
             / _device_pass_bps())
    t_cpu = model.cpu_passes * model.effective_host_bytes / cpu
    pick = "device" if t_dev <= t_cpu else "cpu"
    log.info(
        "placement%s: %s (est device %.3fs [link %.0f MB/s] vs cpu %.3fs "
        "[%.1f GB/s], %.1f MB to move)",
        f" {stage}" if stage else "", pick, t_dev, put / 1e6, t_cpu,
        cpu / 1e9, model.bytes_to_device / 1e6)
    return pick


def cpu_mesh():
    """1-D mesh over the host CPU devices (the forced/auto-CPU target)."""
    import jax

    from ..parallel.mesh import mesh_from_devices

    return mesh_from_devices(devices=jax.devices("cpu"))


def mesh_for_stage(ctx, model: Optional[StageModel], mode: str, stage: str):
    """The mesh an algorithm should train on under the given --device
    mode. Multi-process runs always use the configured mesh — every
    process must join the same collectives, so per-stage re-placement
    would wedge the job."""
    import jax

    validate_device_mode(mode)
    if mode == "tpu":
        return require_tpu(ctx.get_mesh())
    if jax.process_count() > 1:
        # NOT silent: the user asked for cpu/auto but multi-process
        # collectives require every process on the configured mesh
        log.warning(
            "placement%s: --device=%s ignored in a %d-process run — "
            "all processes must join the configured mesh's collectives",
            f" {stage}" if stage else "", mode, jax.process_count())
        return ctx.get_mesh()
    if choose(model, mode, stage) == "cpu":
        return cpu_mesh()
    return ctx.get_mesh()


def device_mode_from_env(default: str = "auto") -> str:
    """PIO_TRAIN_DEVICE env tier (engine.json/CLI win over it). An
    invalid env value warns and falls back — a typo must not surface as
    a mid-training crash minutes later."""
    v = envknobs.env_str("PIO_TRAIN_DEVICE", default) or default
    try:
        return validate_device_mode(v)
    except ValueError:
        log.warning("PIO_TRAIN_DEVICE=%r is not tpu|cpu|auto; using %r",
                    v, default)
        return default
