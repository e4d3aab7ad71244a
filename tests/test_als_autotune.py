"""Auto-resolution of ALS compute knobs + template param plumbing.

Round-1 gap: the bench harness set its
knobs by hand while the template exposed neither, so a real `pio train`
at ml20m diverged from the benched configuration. These tests pin:
(a) the "auto" knobs resolve deterministically from the mesh platform,
(b) engine.json spellings reach ALSParams, (c) the DASE path trains with
pure template defaults."""

import numpy as np

import jax

from incubator_predictionio_tpu.ops.als import (
    ALSParams,
    _AUTO_ENTRIES_PER_STEP,
    _resolve_params,
    train_als,
)
from incubator_predictionio_tpu.parallel.mesh import mesh_from_devices


def test_auto_resolves_dtype_from_mesh_platform():
    mesh = mesh_from_devices(devices=jax.devices("cpu")[:2])
    p, entries = _resolve_params(mesh, ALSParams(rank=8))
    assert p.compute_dtype == "float32"  # cpu mesh
    assert entries == _AUTO_ENTRIES_PER_STEP


def test_chunk_tiles_scales_entries_per_step():
    """chunkTiles keeps its engine.json meaning: tiles × blockLen
    gathered entries per device step."""
    mesh = mesh_from_devices(devices=jax.devices("cpu")[:2])
    p, entries = _resolve_params(
        mesh, ALSParams(rank=8, block_len=16, chunk_tiles=128))
    assert entries == 128 * 16


def test_explicit_knobs_pass_through_unchanged():
    mesh = mesh_from_devices(devices=jax.devices("cpu")[:2])
    p0 = ALSParams(rank=8, compute_dtype="bfloat16", chunk_tiles=7)
    p, _ = _resolve_params(mesh, p0)
    assert p.compute_dtype == "bfloat16"
    assert p.chunk_tiles == 7


def test_auto_defaults_train_end_to_end():
    """train_als with pure defaults (auto dtype, auto chunking) works."""
    rng = np.random.default_rng(2)
    u = rng.integers(0, 30, 400).astype(np.int32)
    i = rng.integers(0, 20, 400).astype(np.int32)
    r = rng.random(400).astype(np.float32)
    mesh = mesh_from_devices(devices=jax.devices("cpu")[:4])
    out = train_als(u, i, r, 30, 20,
                    ALSParams(rank=8, num_iterations=2),
                    mesh=mesh)
    assert np.isfinite(out.user_factors).all()


def test_template_json_spellings_reach_als_params():
    """engine.json camelCase params flow through doer() to ALSParams."""
    from incubator_predictionio_tpu.controller.base import doer
    from incubator_predictionio_tpu.models.recommendation import ALSAlgorithm

    algo = doer(ALSAlgorithm, {
        "rank": 16, "numIterations": 7, "lambda": 0.2,
        "implicitPrefs": True, "alpha": 3.0, "lambdaScaling": "nratings",
        "blockLen": 16, "computeDtype": "float32", "chunkTiles": 128,
    })
    ap = ALSAlgorithm.als_params(algo.params)
    assert ap.rank == 16
    assert ap.num_iterations == 7
    assert ap.reg == 0.2
    assert ap.implicit_prefs is True
    assert ap.alpha == 3.0
    assert ap.lambda_scaling == "nratings"
    assert ap.block_len == 16
    assert ap.compute_dtype == "float32"
    assert ap.chunk_tiles == 128


def test_template_defaults_are_auto():
    from incubator_predictionio_tpu.controller.base import doer
    from incubator_predictionio_tpu.models.recommendation import ALSAlgorithm

    algo = doer(ALSAlgorithm, {"rank": 8, "numIterations": 2})
    ap = ALSAlgorithm.als_params(algo.params)
    assert ap.compute_dtype == "auto"
    assert ap.chunk_tiles == -1


def test_phase_spans_through_train_als():
    """The plain path — the only one — records its phases as spans
    (upload, the loop with its compile beneath it), which is what
    bench.py reads, and a second call returns the same factors."""
    import time

    from incubator_predictionio_tpu.common import telemetry
    from incubator_predictionio_tpu.ops.als import train_phase_seconds
    from incubator_predictionio_tpu.workflow.context import (
        enable_compilation_cache,
    )

    enable_compilation_cache()  # hands jax to telemetry: xla.compile spans
    rng = np.random.default_rng(4)
    u = rng.integers(0, 25, 300).astype(np.int32)
    i = rng.integers(0, 15, 300).astype(np.int32)
    r = rng.random(300).astype(np.float32)
    mesh = mesh_from_devices(devices=jax.devices("cpu")[:4])
    p = ALSParams(rank=5, num_iterations=3)  # a rank no other test compiles
    since_ns = time.perf_counter_ns()
    first = train_als(u, i, r, 25, 15, p, mesh=mesh)
    spans = [s for s in telemetry.spans_snapshot() if s.t0_ns >= since_ns]
    names = [s.name for s in spans]
    for name in ("als.layout", "als.init", "als.pack", "als.upload",
                 "als.loop", "als.readback"):
        assert names.count(name) == 1, (name, names)
    loop = next(s for s in spans if s.name == "als.loop")
    compiles = [s for s in spans if s.name == "xla.compile"
                and s.parent_id == loop.span_id]
    assert compiles and all(s.trace_id == loop.trace_id for s in compiles)
    t = train_phase_seconds(since_ns)
    assert set(t) == {"upload_seconds", "compile_seconds",
                      "device_train_seconds"}
    assert t["compile_seconds"] > 0 and t["device_train_seconds"] > 0
    again_ns = time.perf_counter_ns()
    second = train_als(u, i, r, 25, 15, p, mesh=mesh)
    assert train_phase_seconds(again_ns)["compile_seconds"] == 0
    np.testing.assert_array_equal(first.user_factors, second.user_factors)
