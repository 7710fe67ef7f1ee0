"""``hack/failed_decisions.py`` at toy size on the CPU: the tool from
outside that says where in the round a run's failed and longest
decisions began (ISSUE 43; PERF.md §7).

The child's part (``one_run``: ``benchmarks/run.py``'s own ``main``,
watched) runs once, in a process of its own on one host device as the
benchmark's tests do (``tests/benchmark_harness.py``), over the toy
``decide-under-round`` of ``benchmarks/tests``; the parent's part (a
process a seed, the lines it keeps) is read with the process replaced.
"""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "hack"))

import failed_decisions  # noqa: E402

DRIVER = """
import os, sys
sys.path.insert(0, os.getcwd())
sys.path.insert(0, os.path.join(os.getcwd(), "hack"))
import jax
from benchmarks.harness import cells, device
from benchmarks.tests import toy
from benchmarks.tests.test_decide_under_round import MIX, TOY_MIX
root = toy.make_root(sys.argv[1])
toy._edit(os.path.join(root, "benchmarks", "traffic", f"{MIX}.json"), **TOY_MIX)
load = cells.load_cell
cells.load_cell = lambda name: load(name, root=root)
device.require_chips = lambda chips: jax.devices()[:1]
device.peaks_for = lambda kind: {}
import failed_decisions
sys.argv = ["run.py", "--workload", "decide-under-round", "--seed", "7", "--seconds", "1.0", "--trace", "0"]
sys.exit(failed_decisions.one_run())
"""


STRETCHES = {"walk", "assemble", "fit_shared", "fit_alone", "idle"}


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = re.sub(r"--xla_force_host_platform_device_count=\d+", "", env.get("XLA_FLAGS", "")).strip()
    proc = subprocess.run(
        [sys.executable, "-c", DRIVER, str(tmp_path_factory.mktemp("toy"))],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.splitlines()
    told = json.loads(next(l for l in lines if l.startswith("failed decisions: ")).split(": ", 1)[1])
    phases = json.loads(next(l for l in lines if l.startswith("phases: ")).split(": ", 1)[1])
    result = json.loads(next(l for l in lines if l.startswith('{"correct"')))
    notes = json.loads(next(l for l in lines if l.startswith("notes: ")).split(": ", 1)[1])
    return {"told": told, "phases": phases, "result": result, "notes": notes, "stdout": proc.stdout}


def test_the_runs_own_lines_stand_and_the_failed_are_those_not_done(run):
    """A toy run on a quiet machine fails nothing; on a crowded one a
    decision may pass the window and grace: either way the tool's count
    is the generator's, and every failed decision is listed (ten at
    most) with where it began."""
    told, result = run["told"], run["result"]
    assert told["decisions"] == run["notes"]["decisions"] and told["answered"] >= told["done"]
    not_done = told["decisions"] - told["done"]
    assert len(told["failed"]) + told["failed_more"] == not_done <= result["failed"]
    assert told["answered"] - told["done"] == run["notes"]["answered_late"]
    assert all(what in ("late", "lost", "no answer") and stretch in STRETCHES for *_, stretch, what in told["failed"])
    assert told["rounds"] == run["notes"]["rounds"] and told["round_walls_s"] == pytest.approx(run["notes"]["round_walls_s"], abs=0.05)


def test_the_longest_decisions_are_the_generators_and_say_where_they_began(run):
    """The five longest from their own start, as the generator's own
    ``slowest_at_round_s_took_s`` has them, each with the second of the
    window, the round, the second of the round and the stretch."""
    longest, slowest = run["told"]["longest"], run["notes"]["slowest_at_round_s_took_s"]
    assert len(longest) == 5 and [row[0] for row in longest] == sorted((row[0] for row in longest), reverse=True)
    assert [round(row[0], 3) for row in longest] == pytest.approx([took for _, took in slowest], abs=0.002)
    assert [row[3] for row in longest] == pytest.approx([at for at, _ in slowest], abs=0.02)
    for took, at_window, at_round, in_round, stretch, what in longest:
        assert took > 0 and at_window >= 0 and at_round >= 0 and 0 <= in_round <= at_window + 0.1
        assert stretch in STRETCHES and what in ("answered", "late")
    assert run["told"]["service_ms_max"] == pytest.approx(longest[0][0] * 1e3, abs=0.06)
    assert run["told"]["latency_ms"]["p50"] == pytest.approx(run["notes"]["latency_us"]["50"] / 1e3, abs=0.006)


def test_the_heartbeat_beats_through_the_window_and_names_its_longest_stop(run):
    beat = run["told"]["heartbeat"]
    window_s = run["notes"]["decision_window_s"]
    assert 0.3 * window_s / failed_decisions.BEAT_S <= beat["beats"] <= 1.05 * window_s / failed_decisions.BEAT_S + 50
    stop, at_window, at_round, in_round, stretch = beat["longest_stop"]
    assert 0 <= stop < 1.0 and 0 <= at_window <= window_s + 1.0 and stretch in STRETCHES
    assert beat["late_50ms"] >= (1 if stop >= 0.05 else 0)
    # a second thread maps, writes and unmaps a page of its own a beat: the longest, placed the same way
    took, at_window, at_round, in_round, stretch = beat["longest_fresh_page"]
    assert 0 < took < 1.0 and 0 <= at_window <= window_s + 1.0 and stretch in STRETCHES


def test_the_phases_are_the_windows_and_the_librarys_walk_is_among_them(run):
    from dragonfly2_tpu.schema import native

    phases, rounds = run["phases"], run["told"]["rounds"]
    assert phases["round"][0] == phases["mlp_load"][0] == phases["mlp_load_walk"][0] == rounds
    assert phases.get("mlp_load_walk_native", [0, 0.0])[0] == (rounds if native.available() else 0)
    assert phases.get("mlp_load_walk_native", [0, 0.0])[1] <= phases["mlp_load_walk"][1] <= phases["mlp_load"][1]
    assert phases["find_parents"][0] == run["told"]["decisions"]
    # the GRU leg's tail read through the library, told once a fit inside its load
    assert phases["gru_load"][0] == rounds and phases.get("gru_load_native", [0, 0.0])[0] == (rounds if native.available() else 0)
    assert phases.get("gru_load_native", [0, 0.0])[1] <= phases["gru_load"][1]


def test_the_parent_runs_a_process_a_seed_in_the_tree_and_keeps_the_lines(run, tmp_path, monkeypatch, capsys):
    calls = []

    def process(cmd, cwd, capture_output, text):
        calls.append((cmd, cwd))
        return subprocess.CompletedProcess(cmd, 0, run["stdout"], "")

    monkeypatch.setattr(failed_decisions.subprocess, "run", process)
    monkeypatch.setattr(sys, "argv", [
        "failed_decisions.py", "--workload", "decide-under-round", "--seeds", "2147520001", "2147520002",
        "--tree", str(tmp_path), "--label", "P", "--out", str(tmp_path / "out"),
    ])
    assert failed_decisions.main() == 0
    assert [cwd for _, cwd in calls] == [str(tmp_path)] * 2
    for (cmd, _), seed in zip(calls, ("2147520001", "2147520002")):
        assert cmd[1] == os.path.abspath(failed_decisions.__file__) and "--one" in cmd
        assert cmd[cmd.index("--seed") + 1] == seed and cmd[cmd.index("--seconds") + 1] == "51" and cmd[cmd.index("--trace") + 1] == "0"
    out = capsys.readouterr().out.splitlines()
    heads = [json.loads(l[3:]) for l in out if l.startswith("== ")]
    assert [h["seed"] for h in heads] == [2147520001, 2147520002] and all(h["side"] == "P" and h["rc"] == 0 for h in heads)
    assert all(h["correct"] is run["result"]["correct"] and h["failed"] == run["result"]["failed"] and h["train_records_per_s"] > 0 for h in heads)
    assert all(h["answered_late"] == run["notes"]["answered_late"] for h in heads)
    assert sum(l.startswith("   failed decisions: ") for l in out) == 2 and sum(l.startswith("   phases: ") for l in out) == 2
    assert sorted(os.listdir(tmp_path / "out")) == [f"decide-under-round.P.{s}.{e}" for s in (2147520001, 2147520002) for e in ("err", "log")]
