"""The one-chip cluster's cell at toy size on the CPU: ``decide-under-round``
end to end through ``run.run_cell`` with a trace, every metric the cell
declares present, and the controls that have to come out as not correct.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_decide_under_round.py -q
"""

from __future__ import annotations

import dataclasses
import json
import os

import numpy as np
import pytest

from benchmarks.generators import open_loop_decisions as old
from benchmarks.generators import decide_under_round as rud
from benchmarks.harness import cells, reference
from benchmarks.tests import toy
from benchmarks.tests.test_benchmark import _run, compile_cache  # noqa: F401

CELL = "decide-under-round"
MIX = "rounds-60chunk-decide-half-knee"
TOY_MIX = {
    **{k: v for k, v in toy.TOY_TRAIN.items() if k not in ("follow_steps", "trace_from_s")},
    **{k: v for k, v in toy.TOY_SERVE.items() if k not in ("trace_from_s", "trace_seconds")},
    "horizon_seconds": 120.0,
}


@pytest.fixture()
def root(tmp_path):
    """``toy.make_root`` and the new mix shrunk beside the others."""
    root = toy.make_root(tmp_path)
    toy._edit(os.path.join(root, "benchmarks", "traffic", f"{MIX}.json"), **TOY_MIX)
    return root


def _checks(capsys) -> dict:
    """``check <name>: <value> limit <limit> <verdict>`` lines of a run."""
    out = {}
    for line in capsys.readouterr().out.splitlines():
        if line.startswith("check "):
            name, _, rest = line.removeprefix("check ").partition(": ")
            out[name] = rest.endswith(" ok")
    return out


def test_the_cell_is_declared_as_the_issue_names_it():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == ("one-chip-cluster", MIX, 1)
    assert len(entry["why"]) <= 200
    cell = cells.load_cell(CELL)
    assert cell.traffic["kind"] == "decide_under_round"
    assert cell.params["rate_per_s"] > 0
    # the upload of rounds-60chunk and the swarm of decide-poisson-0.8, but for the rate
    for mix, skip in (("rounds-60chunk", ("kind", "why", "trace_from_s")), ("decide-poisson-0.8", ("kind", "why", "share_of_knee", "warmup_seconds", "trace_from_s", "trace_seconds"))):
        with open(os.path.join(cells.BENCH_DIR, "traffic", f"{mix}.json")) as f:
            for key, value in json.load(f).items():
                if key not in skip:
                    assert cell.traffic[key] == value, key
    # the groups the issue says are another configuration's, word for word
    with open(os.path.join(cells.BENCH_DIR, "configs", "resident-ml.json")) as f:
        resident = json.load(f)
    with open(os.path.join(cells.BENCH_DIR, "configs", "default-ml.json")) as f:
        default = json.load(f)
    cfg = cell.config
    assert cfg["trainer"] == resident["trainer"] and cfg["scale"] == resident["scale"]
    assert cfg["scheduler"] == default["scheduler"] and cfg["reduced"] == resident["reduced"]
    assert cfg["assumed"][:3] == resident["assumed"]
    assert cfg["guarantees"][:4] == resident["guarantees"] + default["guarantees"][:2]
    assert {k: v for k, v in cfg["limits"].items() if k not in ("rank_gap", "mlp_score_gap")} == resident["limits"]
    assert len(cfg["source"]) <= 200 and cfg["source"] == next(
        c for c in bench["configs"] if c["name"] == "one-chip-cluster"
    )["source"]
    assert [m["name"] for m in cell.end_to_end] == ["train_records_per_s", "setup_s"]


def test_versions_in_force_leave_out_what_an_install_touches():
    start = np.array([0.0, 1.0, 2.9, 3.5, 4.2, 9.0])
    end = start + 0.2
    installs = [(3.0, 4.0, 2), (8.0, 8.5, 3)]
    got = rud.versions_in_force(start, end, installs, at_open=1)
    # 2.9-3.1 and 3.5-3.7 touch the first install; 4.2 starts after it
    assert got.tolist() == [1, 1, 0, 0, 2, 3]


def test_colocated_cell_rehearsal(root, tmp_path, capsys):
    """End to end with a trace: both comparisons hold, nothing compiles in
    the window, every per-layer metric of the cell is in the line."""
    out = _run(root, CELL, tmp_path, seconds=0.5, trace=True)
    checks = _checks(capsys)
    assert out["correct"] is True, (out, checks)
    # on a loaded CPU a decision may outlast the service's grace beside a
    # round's compile: that is a failed operation, not a wrong one
    assert out["attempted"] > 10 and out["failed"] <= 0.05 * out["attempted"], out
    want = {
        "mlp_fit_s", "gnn_fit_s", "gru_fit_s",
        "decision_latency_us_p50", "decision_latency_us_p99", "decision_service_us_max", "decisions_below_serving_share",
        "process_pause_us_max", "mlp_feed_slice_us", "mlp_epoch_slice_us",
        "score_pack_us", "score_h2d_us", "score_forward_us", "score_d2h_us", "score_unpack_us", "rtt_gather_us",
        "evaluate_us_mean", "serving_wait_us_mean", "serving_batch_rows",
    }
    # device_idle_share.shared needs a TPU plane; a CPU trace has none
    assert want <= set(out["metrics"]) <= want | {"device_idle_share.shared"}
    assert 0.0 <= out["metrics"]["decisions_below_serving_share"]["value"] <= 5.0
    assert all(out["metrics"][m]["value"] > 0 for m in want - {"decisions_below_serving_share"})
    assert {"rank_gap", "mlp_rows_sampled_mismatch", "mlp_versions_update_gap", "mlp_versions_score_gap", "gnn_end_loss_gap", "gru_update_gap",
            "installs_failed", "compiles_in_window"} <= set(checks)
    untraced = _run(root, CELL, tmp_path, seed=2**31 + 11, seconds=0.5)
    assert untraced["correct"] is True
    assert set(untraced["metrics"]) == {"train_records_per_s", "setup_s"}


def test_fp8_in_the_served_models_place_is_not_correct(root, tmp_path, monkeypatch, capsys):
    """The control: the fp8 reference ranks in the program's place."""
    real = old.judge
    monkeypatch.setattr(
        old, "judge", lambda desc, w, cfg, picks, returned_of: real(desc, w, cfg, picks, lambda n: None, "fp8")
    )
    out = _run(root, CELL, tmp_path, seconds=0.5)
    checks = _checks(capsys)
    assert out["correct"] is False and checks["rank_gap"] is False
    assert all(ok for name, ok in checks.items() if name != "rank_gap")


def test_a_reversed_ranking_is_not_correct(root, tmp_path, monkeypatch, capsys):
    from dragonfly2_tpu.scheduler.scheduling import Scheduling

    real = Scheduling.find_candidate_parents

    def reversed_(self, child, *a, **kw):
        parents, found = real(self, child, *a, **kw)
        return list(reversed(parents)), found

    monkeypatch.setattr(Scheduling, "find_candidate_parents", reversed_)
    out = _run(root, CELL, tmp_path, seconds=0.5)
    assert out["correct"] is False and _checks(capsys)["rank_gap"] is False


def test_a_resident_fit_on_half_its_pairs_is_not_correct(root, tmp_path, monkeypatch, capsys):
    from dragonfly2_tpu.trainer import training as training_mod

    real = training_mod.train_mlp
    monkeypatch.setattr(
        training_mod, "train_mlp",
        lambda x, y, **kw: real(x[: x.shape[0] // 2], y[: y.shape[0] // 2], **kw),
    )
    out = _run(root, CELL, tmp_path, seconds=0.5)
    checks = _checks(capsys)
    # the replay of the whole upload no longer meets what was fitted: not
    # the last round's, and not any version a decision was held to
    versions = {k: ok for k, ok in checks.items() if k.startswith("mlp_versions_")}
    last = {k: ok for k, ok in checks.items() if k.startswith("mlp_") and k not in versions}
    assert out["correct"] is False and not all(last.values()) and not all(versions.values()), checks
    assert checks["rank_gap"] is True  # the decisions were ranked by what was served


def test_a_version_off_the_replay_or_a_decision_held_to_the_wrong_version_is_not_correct(root, tmp_path, monkeypatch, capsys):
    """The warm-up round's fit starts from another seed, so the version in
    force when the window opens is not what the replay trains, and differs
    from those the window's rounds register. No check of the last round's
    fit sees it; the hold of every version does. The decisions are still
    ranked as served: each is held to the version that ranked it. Control:
    every decision is held to the newest version, and the ranking fails."""
    from dragonfly2_tpu.trainer import training as training_mod

    def first_fit_differs():
        real, calls = training_mod.train_mlp, []

        def fit(x, y, config=None, **kw):
            calls.append(1)
            if len(calls) == 1:
                config = dataclasses.replace(config, seed=5)
            return real(x, y, config=config, **kw)

        monkeypatch.setattr(training_mod, "train_mlp", fit)

    versions = {"mlp_versions_loss_path_gap", "mlp_versions_update_gap", "mlp_versions_holdout_mse_gap", "mlp_versions_score_gap"}
    first_fit_differs()
    out = _run(root, CELL, tmp_path, seconds=0.5)
    checks = _checks(capsys)
    assert versions <= set(checks)
    assert out["correct"] is False and not all(checks[name] for name in versions)
    assert all(ok for name, ok in checks.items() if name not in versions), checks
    monkeypatch.undo()
    first_fit_differs()
    newest = lambda start, end, installs, at_open: np.full(len(start), installs[-1][2])  # noqa: E731
    monkeypatch.setattr(rud, "versions_in_force", newest)
    out = _run(root, CELL, tmp_path, seconds=0.5)
    checks = _checks(capsys)
    assert out["correct"] is False and checks["rank_gap"] is False
    assert all(ok for name, ok in checks.items() if name not in versions | {"rank_gap"})


def test_a_version_that_scores_the_swarm_another_way_is_not_correct(root, tmp_path, monkeypatch, capsys):
    """Every fit registers its head reading the hidden units in reverse:
    the version is served and the decisions are ranked by it, but over
    the decisions' candidate rows it no longer scores as the replay's
    weights do."""
    from dragonfly2_tpu.trainer import training as training_mod

    real = training_mod.train_mlp

    def head_reversed(x, y, **kw):
        out = real(x, y, **kw)
        head = out.params["layers"][-1]
        head["w"] = head["w"][::-1]
        return out

    monkeypatch.setattr(training_mod, "train_mlp", head_reversed)
    out = _run(root, CELL, tmp_path, seconds=0.5)
    checks = _checks(capsys)
    assert out["correct"] is False and checks["mlp_versions_score_gap"] is False, checks
    assert checks["rank_gap"] is True  # ranked by what was served


def test_score_gap_is_zero_for_the_same_scorer_and_wide_for_another():
    rng = np.random.default_rng(0)
    f = reference.MLP_FEATURE_DIM
    w = {"layers": [{"w": rng.normal(size=(f, 8)).astype(np.float32), "b": np.zeros(8, np.float32)},
                    {"w": rng.normal(size=(8, 1)).astype(np.float32), "b": np.ones(1, np.float32)}]}
    shifted = {"layers": [w["layers"][0], {"w": w["layers"][1]["w"], "b": 5 * w["layers"][1]["b"]}]}
    other = {"layers": [w["layers"][0], {"w": w["layers"][1]["w"][::-1], "b": w["layers"][1]["b"]}]}
    x = rng.normal(size=(500, f)).astype(np.float32)
    assert rud.score_gap(w, w, x) == 0.0
    assert rud.score_gap(shifted, w, x) < 1e-6  # an offset ranks nothing differently
    assert rud.score_gap(other, w, x) > 0.5
    assert 0.0 < rud.score_gap(w, w, x, "fp8") < 0.2
    assert rud.score_gap(w, w, x[:0]) == float("inf")


def test_the_registry_answers_the_refresher_in_the_managers_words():
    from dragonfly2_tpu.trainer.serving import deserialize_params_auto

    import manager_pb2

    reg = rud.Registry()
    assert list(reg.ListModels(manager_pb2.ListModelsRequest(scheduler_cluster_id=1)).models) == []
    w = {"layers": [{"w": np.ones((reference.MLP_FEATURE_DIM, 1), np.float32), "b": np.zeros(1, np.float32)}]}
    reg.create_model("m", "gnn", "ip", "h", {"x": np.zeros(1)}, {})
    reg.create_model("m", "mlp", "ip", "h", w, {"mse": 1.0})
    reg.create_model("m", "mlp", "ip", "h", {"layers": [{"w": 2 * w["layers"][0]["w"], "b": w["layers"][0]["b"]}]}, {})
    (m,) = reg.ListModels(manager_pb2.ListModelsRequest(scheduler_cluster_id=1)).models
    assert (m.type, m.version, m.state) == ("mlp", 2, "active")
    got = deserialize_params_auto(reg.GetModelWeights(manager_pb2.GetModelRequest(model_id="m", version=1)).weights)
    assert np.array_equal(got["layers"][0]["w"], w["layers"][0]["w"])
    assert sorted(t for t, _, _ in reg.round) == ["gnn", "mlp", "mlp"]


def _as_on_the_chip(monkeypatch, root):
    import jax

    from benchmarks.harness import device

    load = cells.load_cell
    monkeypatch.setattr(cells, "load_cell", lambda name: load(name, root=root))
    monkeypatch.setattr(device, "require_chips", lambda chips: jax.devices()[:1])


def test_the_sweep_beside_a_round_prints_a_line_a_window(root, monkeypatch, capsys):
    import sys

    from benchmarks.tools import sweep_beside

    _as_on_the_chip(monkeypatch, root)
    monkeypatch.setattr(
        sys, "argv",
        ["sweep_beside.py", "--workload", CELL, "--rates", "40@5,40,80", "--seconds", "1", "--windows", "2",
         "--at-round-start", "1", "--held-ms", "0"],
    )
    assert sweep_beside.main() == 0
    printed = capsys.readouterr().out.splitlines()
    lines = [json.loads(l) for l in printed if l.startswith("{")]
    windows = [l for l in lines if "rate" in l]
    assert [(w["rate"], w["switch_interval_ms"]) for w in windows] == [(40.0, 5.0)] * 2 + [(40.0, 0.5)] * 2 + [(80.0, 0.5)] * 2
    assert all(w["service_max_us"] > 0 and w["held_max_us"] >= 0 and w["trip_max_us"] >= 0 for w in windows)
    # both witnesses speak (at a threshold of 0 every turn is a wait), the first with the rounds' stack
    held = [json.loads(l.removeprefix("held ")) for l in printed if l.startswith("held ")]
    assert held and any("bench.rounds" in h["stacks"] for h in held) and all(h["round_s"] >= 0 for h in held)
    assert any(l.startswith("trip ") for l in printed)
    assert all(w["errors"] == 0 and w["lost"] == 0 and w["below_serving"] == 0 for w in windows)
    assert len(lines[-1]["round_walls_s"]) >= 5  # rounds ran beside the windows, one began before each
    assert all(w["round_phase_s"] < 0.5 for w in windows)
    assert set(lines[-1]["sustained"]) == {"40.0", "80.0"} and lines[-1]["knee_per_s"] in (0.0, 40.0, 80.0)
    assert sweep_beside.knee({40.0: True, 80.0: True, 100.0: False, 120.0: True}) == 80.0
    assert sweep_beside.knee({40.0: False, 80.0: True}) == 0.0


def test_the_served_control_reads_trained_weights(root, monkeypatch, capsys):
    import sys

    from benchmarks.tools import readings_served

    _as_on_the_chip(monkeypatch, root)
    monkeypatch.setattr(sys, "argv", ["readings_served.py", "--workload", CELL, "--seeds", "3"])
    assert readings_served.main() == 0
    (line,) = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert line["reference_in_its_own_place"]["rank_gap"] == 0.0
    assert line["control_fp8"]["rank_gap"] > 0
    assert line["score_gap_fp8_fit"] > 0 and line["score_gap_fp8_forward"] > 0 and line["candidate_rows"] > 0
    assert line["loss"][-1] < 1.0
