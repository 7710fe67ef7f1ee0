"""``decide-own-rate-under-round`` at toy size on the CPU: the one-chip
cluster's cell at the deployment's own rate (ISSUE 40), and the metrics
of the round's stretches and a decision's service by stretch that are
held back from ``BENCHMARK.json`` (``held_back_phase_metrics.json`` says
why), read from the program's own phases in a root that declares them.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_decide_own_rate_under_round.py -q

On the chip by hand, in the same command as the run (the copy on the
machine is edited, the repo's file is not):

    chiprun -- bash -c "python3 -c \"from benchmarks.tests import test_decide_own_rate_under_round as t; \\
        t.declare_held_back('.')\"; python3 benchmarks/run.py --workload decide-own-rate-under-round ... --trace 1"
"""

from __future__ import annotations

import json
import os

import pytest

from benchmarks.harness import cells
from benchmarks.tests import toy
from benchmarks.tests.test_benchmark import _run, compile_cache  # noqa: F401
from benchmarks.tests.test_decide_under_round import TOY_MIX, _checks

CELL, SIBLING = "decide-own-rate-under-round", "decide-under-round"
MIX, SIBLING_MIX = "rounds-60chunk-decide-own-rate", "rounds-60chunk-decide-half-knee"
HELD_BACK = os.path.join(cells.BENCH_DIR, "held_back_phase_metrics.json")
# the held-back metrics: the round's stretches, a decision's service from
# inside and by stretch (no mean for `idle`: rounds run back to back, a
# window holds one to eight such decisions), a full collection
HELD_BACK_METRICS = {
    "mlp_load_s", "mlp_load_walk_s", "mlp_load_assemble_s", "mlp_table_put_s", "mlp_load_span_us",
    "mlp_epoch_dispatch_s", "gru_load_s", "gru_epoch_dispatch_s", "find_parents_us", "filter_parents_us",
    *(f"find_parents_us_beside_{s}" for s in ("walk", "assemble", "fit_shared", "fit_alone")), "gc_full_us",
}
# a toy round's stretches are fractions of a second: a stretch in which no
# decision began has no mean, and no full collection need fall into a window
MAYBE = {m for m in HELD_BACK_METRICS if "_beside_" in m} | {"gc_full_us", "device_idle_share.shared"}


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


@pytest.fixture()
def root(tmp_path):
    root = toy.make_root(tmp_path)
    toy._edit(os.path.join(root, "benchmarks", "traffic", f"{MIX}.json"), **TOY_MIX)
    # the deployment's own 23/s gives a toy window of a second or two a few
    # dozen decisions and not every stretch one: the rehearsal asks ten times
    # as often (the chip's cell runs at the file's rate)
    toy._edit(os.path.join(root, "benchmarks", "cells", f"{CELL}.json"), rate_per_s=230.0)
    return root


def test_the_cell_is_its_sibling_but_for_the_rate():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    sibling = next(w for w in bench["workloads"] if w["name"] == SIBLING)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (sibling["config"], MIX, 1) and len(entry["why"]) <= 200
    cell, other = cells.load_cell(CELL), cells.load_cell(SIBLING)
    assert cell.params == {"rate_per_s": 23.0} and cell.config == other.config
    # the configuration's own mean: a week's records over a week's seconds
    assert round(cell.config["scale"]["records_per_round"] / (7 * 24 * 3600)) == cell.params["rate_per_s"]
    # the mix key for key, the rate's origin in the share's place
    assert [k for k in cell.traffic] == [k if k != "share_of_knee" else "rate_source" for k in other.traffic]
    assert {k: v for k, v in cell.traffic.items() if k not in ("why", "rate_source")} == {
        k: v for k, v in other.traffic.items() if k not in ("why", "share_of_knee")
    }
    assert cell.traffic["why"] != other.traffic["why"] and "13,762,560" in cell.traffic["rate_source"]
    # what the sibling reports, entry for entry, and nothing else
    assert [m["name"] for m in cell.end_to_end] == ["train_records_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == [m["name"] for m in other.per_layer]


def test_the_held_back_metrics_read_phases_the_program_declares():
    """Each entry as ``BENCHMARK.json`` would take it (the keys of a
    ``per_layer`` entry, the new cell alone, a name of its own), with a
    ``prof_phase`` reader of a phase that importing the program declares."""
    from dragonfly2_tpu.colocated import server  # noqa: F401  (the scheduler's, the trainer's and the process's phases)
    from dragonfly2_tpu.utils import profiling

    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(HELD_BACK) as f:
        held = json.load(f)
    assert set(held) == {"why", "per_layer"} and {m["name"] for m in held["per_layer"]} == HELD_BACK_METRICS
    layers = {m["layer"] for m in bench["per_layer"]}
    for m in held["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}, m
        assert (m["workloads"], m["moves"], m["source"], m["better"]) == ([CELL], "train_records_per_s", "program_span", "lower")
        assert m["name"] not in {have["name"] for have in bench["per_layer"]} and len(m["name"]) <= 64
        assert m["layer"] in layers or m["name"] == "gc_full_us", m["layer"]
        with open(os.path.join(cells.BENCH_DIR, "layer_metrics", m["name"] + ".json")) as f:
            reader = json.load(f)["reader"]
        assert reader["kind"] == "prof_phase" and reader["phase"] in profiling._phases, reader
        assert reader.get("scale", 1.0) == (1e6 if m["unit"] == "us" else 1.0) and m["unit"] in ("s", "us")


def test_own_rate_cell_rehearsal(root, tmp_path, capsys):
    """End to end as ``BENCHMARK.json`` declares the cell: the sibling's
    comparisons hold, the traced line holds the sibling's metrics and no
    other, the untraced line the two end-to-end metrics alone."""
    out = _run(root, CELL, tmp_path, seconds=0.5, trace=True)
    checks = _checks(capsys)
    assert out["correct"] is True, [k for k, ok in checks.items() if not ok]
    assert out["attempted"] > 10 and out["failed"] <= 0.05 * out["attempted"], out
    sibling = {m["name"] for m in cells.load_cell(SIBLING).per_layer}
    got = set(out["metrics"]) - {"train_records_per_s", "setup_s"}
    assert sibling - MAYBE <= got <= sibling, (sorted(sibling - got), sorted(got - sibling))
    untraced = _run(root, CELL, tmp_path, seed=2**31 + 11, seconds=0.5)
    assert untraced["correct"] is True
    assert set(untraced["metrics"]) == {"train_records_per_s", "setup_s"}


def test_the_held_back_metrics_in_a_traced_line(root, tmp_path, capsys):
    """In a root that declares them the traced line holds every one
    (but a stretch's mean in which no decision began, and a collection's
    when none ran) beside the sibling's, and the stretches add up."""
    declare_held_back(root)
    out = _run(root, CELL, tmp_path, seed=2**31 + 12, seconds=0.5, trace=True)
    checks = _checks(capsys)
    assert out["correct"] is True, [k for k, ok in checks.items() if not ok]
    want = {m["name"] for m in cells.load_cell(SIBLING).per_layer} | HELD_BACK_METRICS
    got = set(out["metrics"]) - {"train_records_per_s", "setup_s"}
    assert want - MAYBE <= got <= want, (sorted(want - MAYBE - got), sorted(got - want))
    assert len(got & {m for m in HELD_BACK_METRICS if "_beside_" in m}) >= 2, sorted(got)
    value = lambda name: out["metrics"][name]["value"]  # noqa: E731
    assert all(value(m) > 0 for m in HELD_BACK_METRICS - MAYBE)
    # the load holds its two stretches (and the order's start, which at toy size is as long as they);
    # the rules and the ranking are parts of a decision
    assert value("mlp_load_walk_s") + value("mlp_load_assemble_s") <= value("mlp_load_s")
    assert value("filter_parents_us") < value("find_parents_us")
    assert value("evaluate_us_mean") < value("find_parents_us")
