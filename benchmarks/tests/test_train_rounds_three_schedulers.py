"""``train-rounds-three-schedulers`` at toy size on the CPU: three
schedulers' uploads, one trainer (ISSUE 45; first asked as ISSUE 44). A toy chip that holds two
rounds is handed in (the CPU states no limit), the cell runs end to end
against its plain reference, and the three controls come out as not
correct, each by the checks it is meant for (a fourth, of the merge's
weights, runs uneven uploads). The three metrics of the
admission and the merge that are held back from ``BENCHMARK.json``
(``held_back_round_metrics.json`` says why) are read from a root that
declares them.

    env -u XLA_FLAGS JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_train_rounds_three_schedulers.py -q

On the chip by hand, in the same command as the run (the copy on the
machine is edited, the repo's file is not):

    chiprun -- bash -c "python3 -c \\"from benchmarks.tests import test_train_rounds_three_schedulers as t; \\
        t.declare_held_back('.')\\"; python3 benchmarks/run.py --workload train-rounds-three-schedulers ... --trace 1"
"""

from __future__ import annotations

import json
import os
import threading

import pytest

from benchmarks.generators import rounds_by_scheduler as gen
from benchmarks.harness import admission as plain
from benchmarks.harness import cells
from benchmarks.tests import toy
from benchmarks.tests.test_benchmark import _run, compile_cache  # noqa: F401
from benchmarks.tests.test_decide_under_round import _checks

CELL, SIBLING = "train-rounds-three-schedulers", "train-round-resident"
CONFIG, MIX, SIBLING_MIX = "three-scheduler-cluster", "cadences-3x60chunk", "rounds-60chunk"
HELD_BACK = os.path.join(cells.BENCH_DIR, "held_back_round_metrics.json")
HELD_BACK_METRICS = {"round_wait_s", "rounds_waited_share", "merge_us"}
CELL_METRICS = ["mlp_fit_s", "gnn_fit_s", "gru_fit_s", "device_idle_share.train"]
TOY_MIX = {
    "chunks_per_upload": 2, "body_records": 256, "body_repeats_per_chunk": 8, "hosts": 64,
    "trace_from_s": 0.1, "trace_seconds": 0.3, "stream_end_offsets_s": [0.0, 0.05, 0.1],
}
PER_HOST = [
    "mlp_pairs_gap", "mlp_rows_mismatch", "mlp_loss_path_gap", "mlp_update_gap", "mlp_holdout_mse_gap",
    "gnn_graph_gap", "gnn_loss_path_gap", "gnn_update_gap", "gnn_end_loss_gap",
    "gru_sequences_gap", "gru_loss_path_gap", "gru_update_gap",
]
CHECKS = {
    "compiles_in_window", "cadences_failed", "feed_off_chip", "versions_gap", "rounds_admitted_beyond_limit",
    "admission_order_gap", "mlp_rows_sampled_mismatch", "merged_gap", "mlp_holdout_mse_gap.merged",
    *(f"{name}.s{k}" for name in PER_HOST for k in range(3)),
}


def declare_held_back(root: str) -> None:
    """The held-back entries put at the end of ``per_layer`` in
    ``root``'s ``BENCHMARK.json``: a toy root, or a chip call's copy of
    the repo."""
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    with open(HELD_BACK) as f:
        bench["per_layer"] += json.load(f)["per_layer"]
    with open(path, "w") as f:
        json.dump(bench, f, indent=2)


def toy_chip(monkeypatch, rounds_held: float) -> int:
    """A device limit under which ``rounds_held`` toy rounds fit, handed
    to the trainer and to the reference alike (the CPU states none)."""
    from dragonfly2_tpu.trainer import training as training_mod

    stated = cells.load_cell(CELL).config["admission"]
    limit = stated["reserve_bytes"] + int(rounds_held * plain.round_bytes(10_000, stated))
    monkeypatch.setattr(training_mod, "_device_bytes_limit", lambda mesh: limit)
    monkeypatch.setattr(gen, "device_bytes_limit", lambda devices: limit)
    return limit


@pytest.fixture()
def root(tmp_path, monkeypatch):
    root = toy.make_root(tmp_path)
    toy._edit(os.path.join(root, "benchmarks", "traffic", f"{MIX}.json"), **TOY_MIX)
    toy_chip(monkeypatch, 2.5)
    return root


def _notes(out: str) -> dict:
    return json.loads(next(line for line in out.splitlines() if line.startswith("notes: ")).removeprefix("notes: "))


def _run_checked(root, tmp_path, capsys, **kw):
    out = _run(root, CELL, tmp_path, **kw)
    printed = capsys.readouterr().out
    checks = {}
    for line in printed.splitlines():
        if line.startswith("check "):
            name, _, rest = line.removeprefix("check ").partition(": ")
            checks[name] = rest.endswith(" ok")
    return out, checks, _notes(printed)


def test_the_cell_is_declared_as_the_issue_names_it():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, MIX, 1) and len(entry["why"]) <= 200
    assert bench["workloads"][-1] is entry and bench["configs"][-1]["name"] == CONFIG  # at the end of their lists
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 0 and len(bench["workloads"]) == 6
    declared = bench["configs"][-1]
    assert len(declared["source"]) <= 200 and declared["reduced"] == ["hosts", "mlp_epochs"]
    for part in ("deploy/kubernetes/manifests.yaml", "storage.go:141-148", "service_v1.go:87,155-159", "constants.go:196-200", "BASELINE.json configs[4]"):
        assert part in declared["source"], part
    cell, sibling = cells.load_cell(CELL), cells.load_cell(SIBLING)
    # the cell's own metric set: the sibling's four and both end-to-end metrics, each list ending with the cell
    assert [m["name"] for m in cell.end_to_end] == ["train_records_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == CELL_METRICS == [m["name"] for m in sibling.per_layer]
    for m in bench["per_layer"] + bench["end_to_end"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL
    # no per_layer entry was added: the list ends as the churn cell's test pins it
    assert [m["name"] for m in bench["per_layer"]][-4:] == ["gnn_reembed_us", "topology_flush_us", "topology_leave_us", "decisions_unknown_host_share"]


def test_the_configuration_is_the_siblings_by_three_schedulers():
    cell, sibling = cells.load_cell(CELL), cells.load_cell(SIBLING)
    cfg, one = cell.config, sibling.config
    assert cfg["trainer"] == one["trainer"]  # word for word; no width cut
    assert {k: v for k, v in cfg["limits"].items() if k != "merged_gap"} == one["limits"] and cfg["limits"]["merged_gap"] == 1e-6
    assert cfg["schedulers"] == 3 and cfg["cluster"]["hosts"] == 3 * one["scale"]["hosts"]
    # each scheduler's fleet and week are the sibling's, word for word; a cadence is three such weeks
    assert cfg["scale"] == one["scale"] and cfg["scale"]["upload_chunks_per_round"] == 60
    assert cfg["cluster"]["records_per_cadence"] == 3 * one["scale"]["records_per_round"] == 41_287_680
    assert cfg["cluster"]["pairs_per_cadence"] == 3 * one["scale"]["pairs_per_round"] == 165_150_720
    assert set(cfg["reduced"]) == {"hosts", "mlp_epochs"}
    assert cfg["merge"]["holdout_kept"] == {"share_of_holdout": 1 / 64, "at_least_rows": 1024}
    assert cfg["assumed"][:3] == one["assumed"] and len(cfg["assumed"]) == 5
    assert [g[:3] for g in cfg["guarantees"]] == ["(a)", "(b)", "(c)", "(d)", "(e)", "(f)"]
    assert cfg["source"] == next(c for c in json.load(open(os.path.join(cells.ROOT, "BENCHMARK.json")))["configs"] if c["name"] == CONFIG)["source"]
    # the mix is the sibling's upload, once a scheduler
    mix, alone = cell.traffic, sibling.traffic
    assert mix["kind"] == "rounds_by_scheduler" and mix["schedulers"] == 3
    assert mix["chunks_per_upload"] == cfg["scale"]["upload_chunks_per_round"] == alone["chunks"]
    assert cfg["scale"]["records_per_round"] == mix["chunks_per_upload"] * mix["body_repeats_per_chunk"] * mix["body_records"]
    for key in ("body_records", "body_repeats_per_chunk", "hosts", "probe_fan_out", "probe_rounds", "trace_from_s", "trace_seconds"):
        assert mix[key] == alone[key], key
    assert mix["stream_end_offsets_s"] == [0.0, 0.3, 0.6]


def test_the_stated_admission_is_the_trainers_and_holds_two_weeks_not_three():
    """The reference's plain arithmetic, the configuration's numbers and
    the trainer's constants say one thing; on a v5e's 16 GiB (whatever of
    it the runtime states as its limit) two weeks' rounds fit the budget
    and three do not."""
    from dragonfly2_tpu.trainer import train as train_mod
    from dragonfly2_tpu.trainer import training as training_mod

    stated = cells.load_cell(CELL).config["admission"]
    assert stated["reserve_bytes"] == training_mod.ROUND_RESERVE_BYTES
    assert stated["small_fits_bytes"] == training_mod.ROUND_SMALL_FITS_BYTES
    assert stated["slice_bytes"] == train_mod.FEED_SLICE_BYTES
    week = 55_050_240
    for pairs in (0, 1, 6, 7, 10_000, 838_860, 838_861, week):
        mlp = train_mod.resident_fit_bytes(pairs, (19,), ()) if pairs else 0
        want = training_mod.ROUND_SMALL_FITS_BYTES + mlp
        got = plain.round_bytes(pairs, stated) if pairs else stated["small_fits_bytes"]
        assert got == want, pairs
    one = plain.round_bytes(week, stated)
    assert one == 5_320_482_816 and "5,320,482,816" in stated["why"]
    assert plain.round_bytes(cells.load_cell(CELL).config["scale"]["pairs_per_round"], stated) == one
    for limit in (15 << 30, 16_909_336_064, 16 << 30):  # a v5e: what the runtime states (my chip run, PR 45; the refused PR 44's builder read the same) and about it
        room = plain.budget(limit, stated)
        assert 2 * one <= room < 3 * one, limit  # whole weeks: two and a wait
    assert "14,761,852,416" in stated["why"] and plain.budget(16_909_336_064, stated) == 14_761_852_416
    # the plain replay of the expected cadence: two at once, the third at the first return
    rounds = [(0, 0.0, 10.0, one), (1, 0.3, 10.5, one), (2, 0.6, 17.0, one)]
    assert plain.replay(rounds, plain.budget(16 << 30, stated)) == [(0, False), (1, False), (2, True)]
    assert plain.replay(rounds, None) == [(0, False), (1, False), (2, False)]
    assert plain.replay([(0, 0.0, 5.0, 9 * one), (1, 0.1, 9.0, one)], plain.budget(16 << 30, stated)) == [(0, False), (1, True)]


def test_the_held_back_metrics_read_what_the_program_declares():
    from dragonfly2_tpu.trainer import metrics as M  # noqa: F401  (declares the phases and the counter)
    from dragonfly2_tpu.utils import profiling
    from dragonfly2_tpu.utils.metrics import default_registry

    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(HELD_BACK) as f:
        held = json.load(f)
    assert set(held) == {"why", "per_layer"} and {m["name"] for m in held["per_layer"]} == HELD_BACK_METRICS
    M.ROUND_ADMISSION_TOTAL.labels("waited").inc(0)
    exposed = default_registry.expose()
    for m in held["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}, m
        assert (m["workloads"], m["moves"], m["better"]) == ([CELL], "train_records_per_s", "lower")
        assert m["name"] not in {have["name"] for have in bench["per_layer"]}
        assert m["layer"] in {have["layer"] for have in bench["per_layer"]}
        with open(os.path.join(cells.BENCH_DIR, "layer_metrics", m["name"] + ".json")) as f:
            decl = json.load(f)
        assert set(decl) == {"reader"}
        reader = decl["reader"]
        if reader["kind"] == "prof_phase":
            assert reader["phase"] in profiling._phases and m["source"] == "program_span"
            assert reader["scale"] == (1e6 if m["unit"] == "us" else 1.0)
        else:
            assert reader["kind"] == "prom_series" and reader["series"] in exposed and m["source"] == "program_counter"


def test_three_schedulers_rehearsal(root, tmp_path, capsys):
    """End to end as ``BENCHMARK.json`` declares the cell: every check
    printed beside its limit and held, two rounds at once and the third
    waiting in every cadence, nine versions and one merged, the traced
    line the cell's four metrics (the untraced line's two end-to-end
    metrics are read in the controls' runs below)."""
    out, checks, notes = _run_checked(root, tmp_path, capsys, seconds=0.5, trace=True)
    assert out["correct"] is True, [k for k, ok in checks.items() if not ok]
    assert set(checks) == CHECKS, (sorted(CHECKS - set(checks)), sorted(set(checks) - CHECKS))
    assert out["attempted"] >= 1 and out["failed"] == 0
    order = notes["stream_end_order"]
    assert sorted(order) == [0, 1, 2]
    one = notes["reserved_bytes"][0]
    for cadence in [notes["warm_up"], *notes["by_cadence"]]:
        assert [(k, result) for k, result, _, _ in cadence["admitted"]] == [(order[0], "at_once"), (order[1], "at_once"), (order[2], "waited")]
        assert cadence["admitted"][2][2] > 0 and cadence["wall_s"] >= max(a[3] for a in cadence["admitted"])
        assert cadence["most_side_by_side"] == [2, 2 * one]  # by the program's own gauges: never three
    assert notes["replayed"] == [[[order[0], "at_once"], [order[1], "at_once"], [order[2], "waited"]]] * (1 + notes["cadences"])
    assert notes["budget_bytes"] == notes["bytes_limit"] - (2 << 30) and len(set(notes["reserved_bytes"])) == 1
    got = set(out["metrics"]) - {"train_records_per_s", "setup_s"}
    assert set(CELL_METRICS) - {"device_idle_share.train"} <= got <= set(CELL_METRICS)


def test_the_held_back_metrics_in_a_traced_line(root, tmp_path, capsys):
    declare_held_back(root)
    out, checks, notes = _run_checked(root, tmp_path, capsys, seed=2**31 + 45, seconds=0.3, trace=True)  # a seed past 32 signed bits
    assert out["correct"] is True, [k for k, ok in checks.items() if not ok]
    got = set(out["metrics"]) - {"train_records_per_s", "setup_s"}
    assert HELD_BACK_METRICS <= got <= set(CELL_METRICS) | HELD_BACK_METRICS
    value = lambda name: out["metrics"][name]["value"]  # noqa: E731
    # one round in three waits, the mean wait is a third of the third round's
    assert value("rounds_waited_share") == pytest.approx(100 / 3)
    waits = [a[2] for c in notes["by_cadence"] for a in c["admitted"]]
    assert value("round_wait_s") == pytest.approx(sum(waits) / len(waits), abs=0.01) and value("merge_us") > 0


def test_fp8_replay_in_the_programs_place_fails_the_mlp_gaps(root):
    """The control of the three MLP limits, as in the sibling: the replay
    of one scheduler's own upload in fp8 against the float32 one, at the
    cell's own limits (not the toy's)."""
    from benchmarks.harness import reference, reference_fits, synth

    cell = cells.load_cell(CELL, root=root)
    cfg, limits = cell.config["trainer"]["mlp"], cells.load_cell(CELL).config["limits"]
    x, y = reference.record_pairs(synth.download_records(256, gen.scheduler_seed(5, 1, 3)))
    kw = dict(hidden=tuple(cfg["hidden_dims"]), epochs=cfg["epochs"], batch=cfg["batch_size"])
    sound, control = reference_fits.fit_mlp(x, y, 16, **kw), reference_fits.fit_mlp(x, y, 16, precision="fp8", **kw)
    held = reference_fits.mlp_holdout_mse(x, y, 16, sound["params"])
    held_fp8 = reference_fits.mlp_holdout_mse(x, y, 16, sound["params"], precision="fp8")
    assert any([
        reference.path_gap(control["history"], sound["history"]) > limits["mlp_loss_path_gap"],
        reference_fits.update_gap(control["params"], sound) > limits["mlp_update_gap"],
        abs(held_fp8 - held) / held > limits["mlp_holdout_mse_gap"],
    ])


def exchange_uploads(stages: list, i: int, j: int) -> list:
    """The control: schedulers ``i`` and ``j`` hold each other's uploads
    on the trainer's disk, while the reference keeps who sent what."""
    for a, b in zip(stages[i].files, stages[j].files):
        for suffix in ("", ".staged"):
            pa, pb, tmp = str(a) + suffix, str(b) + suffix, str(a) + suffix + ".swap"
            os.rename(pa, tmp)
            os.rename(pb, pa)
            os.rename(tmp, pb)
    return stages


def test_two_uploads_exchanged_fail_those_hosts_gaps_alone(root, tmp_path, monkeypatch, capsys):
    real = gen.stage_all
    monkeypatch.setattr(gen, "stage_all", lambda *a: exchange_uploads(real(*a), 0, 1))
    out, checks, _ = _run_checked(root, tmp_path, capsys, seed=12, seconds=0.3)
    assert out["correct"] is False and out["failed"] == 0
    failed = {k for k, ok in checks.items() if not ok}
    assert {"mlp_rows_mismatch.s0", "mlp_rows_mismatch.s1", "mlp_rows_sampled_mismatch", "gnn_graph_gap.s0", "gru_sequences_gap.s1"} <= failed
    # the two hosts' own gaps, the rows sampled over all hosts, and the merged model's error, which is
    # held against those hosts' replays; the third host, the merge itself and the admission hold
    others = {k for k in failed if not k.endswith((".s0", ".s1"))}
    assert others <= {"mlp_rows_sampled_mismatch", "mlp_holdout_mse_gap.merged"}, sorted(others)
    assert set(out["metrics"]) == {"train_records_per_s", "setup_s"}  # an untraced line: the two end-to-end metrics alone


def admit_all(self, a) -> None:
    """The control: the rule without its bytes. Whoever arrives runs,
    in arrival order, whatever the rounds running hold."""
    from dragonfly2_tpu.trainer import metrics as M

    with self._cond:
        while not a.admitted:
            if self._waiting[0] is a:
                self._waiting.popleft()
                self._admit(a)
                self._cond.notify_all()
                break
            self._cond.wait()
    M.ROUND_ADMISSION_TOTAL.labels(a.result).inc()


def test_admission_switched_off_is_not_correct(root, tmp_path, monkeypatch, capsys):
    from dragonfly2_tpu.trainer import training as training_mod

    monkeypatch.setattr(training_mod.RoundAdmission, "wait", admit_all)
    out, checks, notes = _run_checked(root, tmp_path, capsys, seed=13, seconds=0.3)
    assert out["correct"] is False and out["failed"] == 0
    assert {k for k, ok in checks.items() if not ok} == {"rounds_admitted_beyond_limit"}
    assert set(out["metrics"]) == {"train_records_per_s", "setup_s"} and out["metrics"]["train_records_per_s"]["value"] > 0
    assert all(c["most_side_by_side"][0] == 3 and c["most_side_by_side"][1] > notes["budget_bytes"] for c in notes["by_cadence"])  # three at once


def plain_mean(trees, weights=None):
    """The control: the merge forgets its weights."""
    from dragonfly2_tpu.parallel.fedavg import fedavg_trees

    return fedavg_trees(trees)


def test_a_merge_that_forgets_its_weights_fails_the_merged_gap_alone(root, tmp_path, monkeypatch, capsys):
    """Uneven uploads (two chunks, two, one): everything holds scheduler
    by scheduler at its own size, and a plain mean in the pair-weighted
    mean's place is seen, by ``merged_gap`` and nothing else (the error
    registered with the merged version is that version's own)."""
    from dragonfly2_tpu.trainer import federation as federation_mod

    toy._edit(os.path.join(root, "benchmarks", "traffic", f"{MIX}.json"), chunks_per_upload=[2, 2, 1])
    monkeypatch.setattr(federation_mod, "fedavg_trees", plain_mean)
    out, checks, notes = _run_checked(root, tmp_path, capsys, seed=14, seconds=0.3)
    assert out["correct"] is False and out["failed"] == 0
    assert {k for k, ok in checks.items() if not ok} == {"merged_gap"}
    assert len(set(notes["reserved_bytes"])) == 2  # reckoned each at its own size


def test_a_trainer_without_the_fork_is_refused_before_staging(root, tmp_path, monkeypatch):
    """The parent commit's trainer: the cell says so and exits, soon,
    and before a trainer is built: a ``TrainerServer`` starts the native
    library's build on a thread of its own, and an exit beside it left
    that ``make`` running after the run (the driver's check of PR 44)."""
    from dragonfly2_tpu.trainer import server as server_mod
    from dragonfly2_tpu.trainer.service import TrainerService

    def built(self, config):
        raise AssertionError("a trainer was built before the refusal")

    monkeypatch.delattr(TrainerService, "fit_after_stream")
    monkeypatch.setattr(server_mod.TrainerServer, "__init__", built)
    alive = {t.ident for t in threading.enumerate()}
    with pytest.raises(SystemExit, match="fit_after_stream"):
        _run(root, CELL, tmp_path, seconds=0.3)
    assert {t.ident for t in threading.enumerate()} <= alive
