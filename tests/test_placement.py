"""Cost-based device placement (`pio train --device`, VERDICT r4 next #2):
the measured stage model must route transfer-bound trains to the host CPU
when the link is slow, keep iterative dense trains on the accelerator,
and honor forced modes."""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from incubator_predictionio_tpu.workflow import placement  # noqa: E402
from incubator_predictionio_tpu.workflow.placement import (  # noqa: E402
    StageModel,
    choose,
    mesh_for_stage,
)


def _as_accelerator(monkeypatch, put_bps):
    """Pretend the default platform is an accelerator of a known kind
    behind a link of ``put_bps``, with a 10 GB/s host."""
    monkeypatch.setattr(placement, "_rates", {"put": put_bps, "cpu": 10e9})
    monkeypatch.setattr(placement, "_default_is_cpu", lambda: False)
    monkeypatch.setitem(placement._DEVICE_PASS_BPS,
                        jax.devices()[0].device_kind, 200e9)


@pytest.fixture()
def slow_link_rates(monkeypatch):
    _as_accelerator(monkeypatch, 35e6)


def test_forced_modes_ignore_model(slow_link_rates):
    big = StageModel(bytes_to_device=10**9)
    assert choose(big, "tpu") == "device"
    assert choose(None, "cpu") == "cpu"
    with pytest.raises(ValueError):
        choose(big, "fastest")


def test_auto_routes_transfer_bound_to_cpu(slow_link_rates):
    # one pass over 40 MB through a 35 MB/s link vs a GB/s host: CPU
    nb = StageModel(bytes_to_device=40 * 2**20, device_passes=1)
    assert choose(nb, "auto", "algorithm[naive]") == "cpu"
    # no stage model (ALS/CCO) → accelerator-pinned
    assert choose(None, "auto") == "device"


def test_auto_flips_with_a_fast_link(monkeypatch):
    _as_accelerator(monkeypatch, 20e9)
    nb = StageModel(bytes_to_device=40 * 2**20, device_passes=1)
    assert choose(nb, "auto") == "device"  # the fast link wins


def test_auto_refuses_to_price_an_unknown_device_kind(monkeypatch):
    """The assumed on-device pass rate is recorded per device_kind; a
    kind the table does not know is an error, never a default."""
    monkeypatch.setattr(placement, "_rates", {"put": 20e9, "cpu": 10e9})
    monkeypatch.setattr(placement, "_default_is_cpu", lambda: False)
    assert jax.devices()[0].device_kind not in placement._DEVICE_PASS_BPS
    with pytest.raises(ValueError, match="cannot price device_kind"):
        choose(StageModel(bytes_to_device=40 * 2**20), "auto")


def test_link_probe_failure_propagates(monkeypatch):
    """A failing put probe is a bug, not a 1 B/s link that silently
    sends every auto-placed stage to the host."""
    monkeypatch.setattr(placement, "_rates", {})

    def boom(*a, **k):
        raise RuntimeError("device lost")

    monkeypatch.setattr(jax, "device_put", boom)
    with pytest.raises(RuntimeError, match="device lost"):
        placement._measured_put_bps()
    assert "put" not in placement._rates


def test_auto_on_cpu_default_is_noop():
    # tests run with the CPU platform as default: nothing to price
    assert choose(StageModel(bytes_to_device=10**9), "auto") == "device"


def test_measured_probes_return_sane_rates():
    placement._rates.clear()
    put = placement._measured_put_bps()
    cpu = placement._measured_cpu_bps()
    assert put > 1e6 and cpu > 1e8  # MB/s-class at minimum on any host


def test_engine_train_swaps_and_restores_mesh(memory_storage, monkeypatch):
    """--device=cpu: the stage trains on the CPU mesh and the context
    mesh is restored afterwards (placement must not leak)."""
    from incubator_predictionio_tpu.controller import (
        Algorithm, DataSource, Engine, EngineParams,
    )
    from incubator_predictionio_tpu.workflow.context import WorkflowContext
    from incubator_predictionio_tpu.workflow.workflow_params import (
        WorkflowParams,
    )

    seen = {}

    class DS(DataSource):
        def read_training(self, ctx):
            return {"x": np.ones(4, np.float32)}

    class Algo(Algorithm):
        def stage_model(self, pd):
            return StageModel(bytes_to_device=16)

        def train(self, ctx, pd):
            seen["mesh"] = ctx.get_mesh()
            return {"w": np.ones(1, np.float32)}

        def predict(self, model, q):
            return {}

    from incubator_predictionio_tpu.parallel.mesh import mesh_from_devices

    engine = Engine(DS, algorithm_class_map={"a": Algo})
    # distinct sentinel: a (4,2) mesh — jax interns meshes, so on a CPU
    # host the placement CPU mesh would be IDENTICAL to the 1-D default
    sentinel_mesh = mesh_from_devices(shape=(4, 2), axis_names=("d", "m"))
    ctx = WorkflowContext(storage=memory_storage, mesh=sentinel_mesh)
    engine.train(ctx, EngineParams(algorithm_params_list=[("a", {})]),
                 WorkflowParams(device="cpu"))
    assert seen["mesh"] is not sentinel_mesh
    assert {d.platform for d in seen["mesh"].devices.flat} == {"cpu"}
    assert ctx.mesh is sentinel_mesh  # restored

    # forced tpu mode on a mesh that is not a TPU: an error, not a
    # silent CPU train that passes for a chip run
    seen.clear()
    with pytest.raises(RuntimeError, match="--device=tpu.*platform 'cpu'"):
        engine.train(ctx, EngineParams(algorithm_params_list=[("a", {})]),
                     WorkflowParams(device="tpu"))
    assert not seen and ctx.mesh is sentinel_mesh


def test_template_algorithms_expose_stage_models():
    from incubator_predictionio_tpu.models.classification import (
        LogisticRegressionAlgorithm, NaiveBayesAlgorithm, PreparedData,
    )
    from incubator_predictionio_tpu.models.recommendation import ALSAlgorithm

    pd = PreparedData(
        features=np.ones((100, 8), np.float32),
        labels=np.zeros(100, np.int32),
        attribute_names=["a"] * 8,
        label_values=np.array([0, 1]),
    )
    from incubator_predictionio_tpu.controller.base import doer

    # all-ones features ride the lossless uint8 wire → 1 byte/element
    nb = doer(NaiveBayesAlgorithm, {}).stage_model(pd)
    assert nb.bytes_to_device == 100 * 8 * 1 and nb.device_passes == 1
    lr = doer(LogisticRegressionAlgorithm, {"max_iters": 7}).stage_model(pd)
    assert lr.device_passes == 7
    # f32-only features price the full width
    pd_f32 = dataclasses.replace(
        pd, features=pd.features + np.float32(0.123456))
    assert doer(NaiveBayesAlgorithm, {}).stage_model(
        pd_f32).bytes_to_device == 100 * 8 * 4
    # iterative dense trainer: accelerator-pinned by design
    assert doer(ALSAlgorithm, {}).stage_model(object()) is None


def test_eval_sweeps_apply_placement(memory_storage):
    """Engine.eval trains many candidates — each one must get the same
    cost-based placement Engine.train applies (a mis-placed
    transfer-bound stage would cost once PER candidate)."""
    from incubator_predictionio_tpu.controller import (
        Algorithm, DataSource, Engine, EngineParams,
    )
    from incubator_predictionio_tpu.parallel.mesh import mesh_from_devices
    from incubator_predictionio_tpu.workflow.context import WorkflowContext
    from incubator_predictionio_tpu.workflow.workflow_params import (
        WorkflowParams,
    )

    meshes = []

    class DS(DataSource):
        def read_training(self, ctx):
            return {"x": np.ones(4, np.float32)}

        def read_eval(self, ctx):
            td = self.read_training(ctx)
            return [(td, None, [({"q": 1}, {"a": 1})])]

    class Algo(Algorithm):
        def stage_model(self, pd):
            return StageModel(bytes_to_device=16)

        def train(self, ctx, pd):
            meshes.append(ctx.get_mesh())
            return {}

        def predict(self, model, q):
            return {"p": 0}

    engine = Engine(DS, algorithm_class_map={"a": Algo})
    sentinel = mesh_from_devices(shape=(4, 2), axis_names=("d", "m"))
    ctx = WorkflowContext(storage=memory_storage, mesh=sentinel)
    ctx.workflow_params = WorkflowParams(device="cpu")
    engine.eval(ctx, EngineParams(algorithm_params_list=[("a", {})]))
    assert meshes and meshes[-1] is not sentinel
    assert {d.platform for d in meshes[-1].devices.flat} == {"cpu"}
    assert ctx.mesh is sentinel  # restored after the fold


def test_text_lr_stage_model_reflects_iterations():
    """TextLR must NOT inherit NB's single-pass pricing (it runs
    max_iters L-BFGS passes over the dense matrix)."""
    from incubator_predictionio_tpu.controller.base import doer
    from incubator_predictionio_tpu.models.text_classification import (
        PreparedData, TextLRAlgorithm, TextNBAlgorithm,
    )
    from incubator_predictionio_tpu.ops.tfidf import TfIdfVectorizer

    vec = TfIdfVectorizer(n_features=64)
    vec.fit_tf_coo(["a b c", "b c d"])
    pd = PreparedData(None, np.zeros(2, np.int32), np.array(["x", "y"]),
                      vec, features_are_tf=True,
                      coo=vec.fit_tf_coo(["a b c", "b c d"]))
    lr = doer(TextLRAlgorithm, {"max_iters": 50}).stage_model(pd)
    assert lr.device_passes == 50 and lr.cpu_passes == 500
    assert lr.bytes_to_device == 2 * 64 * 4  # the dense f32 matrix
    nb = doer(TextNBAlgorithm, {}).stage_model(pd)
    assert nb.device_passes == 1
