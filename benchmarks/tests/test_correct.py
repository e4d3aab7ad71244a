"""``correct`` has to come out false where it should: for the control (the
reference in the nearest precision below the configuration's) and for each
fault a cell can have, planted under the harness with the timed path broken.
These drive the whole of a run at the rehearsal size on the CPU
(``--rehearse`` skips the look for a chip and nothing else)."""

import json

import numpy as np
import pytest

import run as bench

RETRAIN, SERVE = "retrain-electronics-r128", "serve-catalog9m-steady"


def run_cell(capsys, cell, seed=123):
    rc = bench.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     "1", "--trace", "0", "--rehearse"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device", "compared"}
    assert list(line)[-1] == "compared"
    return line


def rehearsal(cell):
    """(configuration, traffic, its deployment file) at the rehearsal size."""
    _cell, cfg, traffic = bench.load_cell(cell, rehearse=True)
    return cfg, traffic, bench.load_module("deployments", cfg["deployment"])


def test_sound_runs_are_correct(capsys):
    for cell in (RETRAIN, SERVE):
        line = run_cell(capsys, cell)
        assert line["correct"] is True, line["compared"]
        assert all(v["value"] <= v["limit"] for v in line["compared"].values())


def test_retrain_control_in_lower_precision_is_not_correct():
    cfg, traffic, deployment = rehearsal(RETRAIN)
    got = deployment.control(traffic["kind"], cfg, traffic, 5)
    lim = cfg["limits"]
    low = got["control_lower_precision"]
    assert any(low[k] > lim[k] for k in lim), low
    for fault in ("fault_half_ratings", "fault_one_sweep_short"):
        assert any(got[fault][k] > lim[k] for k in lim), (fault, got[fault])


def test_serve_control_in_lower_precision_is_not_correct():
    cfg, traffic, deployment = rehearsal(SERVE)
    got = deployment.control(traffic["kind"], cfg, traffic, 5)
    lim = cfg["limits"]
    low = got["control_lower_precision"]
    assert low["rank_gap"] > lim["rank_gap"] or \
        low["score_gap"] > lim["score_gap"], low
    assert got["fault_one_item_altered"]["rank_gap"] > lim["rank_gap"]


def _patched_train_als(monkeypatch, change):
    from incubator_predictionio_tpu.models import recommendation

    real = recommendation.train_als

    def broken(u, i, r, **kw):
        return change(real, u, i, r, kw)

    monkeypatch.setattr(recommendation, "train_als", broken)


def test_fault_state_returned_unchanged(capsys, monkeypatch):
    from incubator_predictionio_tpu.ops.als import ALSFactors

    def unchanged(real, u, i, r, kw):
        k = kw["params"].rank
        rng = np.random.default_rng(kw["params"].seed)
        x = (rng.standard_normal((kw["n_users"], k)) / np.sqrt(k))
        y = (rng.standard_normal((kw["n_items"], k)) / np.sqrt(k))
        return ALSFactors(x.astype(np.float32), y.astype(np.float32),
                          kw["n_users"], kw["n_items"])

    _patched_train_als(monkeypatch, unchanged)
    assert run_cell(capsys, RETRAIN)["correct"] is False


def test_fault_half_of_the_ratings_left_out(capsys, monkeypatch):
    _patched_train_als(
        monkeypatch, lambda real, u, i, r, kw: real(u[::2], i[::2], r[::2],
                                                    **kw))
    assert run_cell(capsys, RETRAIN)["correct"] is False


def test_fault_factors_altered_where_they_are_produced(capsys, monkeypatch):
    def scaled(real, u, i, r, kw):
        f = real(u, i, r, **kw)
        f.item_factors = f.item_factors * np.float32(1.2)
        return f

    _patched_train_als(monkeypatch, scaled)
    line = run_cell(capsys, RETRAIN)
    assert line["correct"] is False
    over = {k for k, v in line["compared"].items() if v["value"] > v["limit"]}
    assert over == {"item_fro"}


def test_fault_served_item_altered_where_it_is_produced(capsys, monkeypatch):
    from incubator_predictionio_tpu.models.recommendation import ALSModel

    real = ALSModel.recommend_products

    def altered(self, user, num):
        out = real(self, user, num)
        if out:
            item, score = out[-1]
            out[-1] = (str((int(item) + 1) % len(self.items)), score)
        return out

    monkeypatch.setattr(ALSModel, "recommend_products", altered)
    line = run_cell(capsys, SERVE)
    assert line["correct"] is False
    assert line["compared"]["rank_gap"]["value"] > \
        line["compared"]["rank_gap"]["limit"]


def test_no_chip_exits_nonzero_and_prints_no_result(capsys):
    rc = bench.main(["--workload", RETRAIN, "--seed", "1", "--seconds", "1",
                     "--trace", "0"])
    assert rc == bench.EXIT_NO_CHIP
    assert capsys.readouterr().out.strip() == ""
