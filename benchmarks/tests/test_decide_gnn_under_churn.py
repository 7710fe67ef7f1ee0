"""``decide-gnn-under-churn`` at toy size on the CPU: the cell end to end
through ``run.run_cell`` with a trace, the four new metrics and every new
limit in its result, each control in the program's place failing its own
limit, and the membership replay against a log written by hand.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_decide_gnn_under_churn.py -q
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from benchmarks.generators import decide_gnn_under_churn as guc
from benchmarks.harness import cells, membership
from benchmarks.tests import toy
from benchmarks.tests.test_benchmark import _run, compile_cache  # noqa: F401
from benchmarks.tests.test_decide_under_round import TOY_MIX, _checks

CELL = "decide-gnn-under-churn"
MIX = "rounds-60chunk-decide-gnn-churn-half-knee"
CONFIG = "autoscaled-cluster-gnn"
NEW_METRICS = {"gnn_reembed_us", "topology_flush_us", "topology_leave_us", "decisions_unknown_host_share"}
NEW_LIMITS = {
    "parents_on_departed_hosts", "below_serving_unexplained", "rank_gap_mlp", "gnn_row_counts_gap",
    "reembeds_missed", "reembeds_idle", "engine_hosts_gap", "capacity_rung_not_crossed", "compiles_in_window",
}
TOY_FLEET = {
    "fitted_hosts": TOY_MIX["hosts"], "late_hosts": 30, "live_hosts_at_setup": TOY_MIX["hosts"] + 30,
    # 126 hosts and five more a second net: the fleet passes 128 in the window's second second and stays under the 192 at
    # which the engine would compile the rung above 256 ahead, on its own thread, inside the toy's window
    "joins_per_s": 6.0, "leaves_per_s": 1.0, "join_tasks": 2, "events_horizon_seconds": 60.0,
    # 1,560 edges at set-up, past three quarters of the 2,048 they are padded to: the kernels for 4,096 edges
    # are compiled in set-up too, where ten probes a join would have the engine compile them ahead mid-window
    "probe_rounds": 3,
}


def _sound_but(checks: dict, *failing) -> bool:
    """Every check reads ok but ``failing`` (and, on a loaded CPU, a
    decision that outlasted the service's grace beside a compile: the
    rehearsals bound their share)."""
    return all(ok for name, ok in checks.items() if name not in (*failing, "below_serving_unexplained"))


@pytest.fixture()
def root(tmp_path):
    """``toy.make_root`` with this cell's mix, fleet and capacity rung shrunk."""
    root = toy.make_root(tmp_path)
    toy._edit(os.path.join(root, "benchmarks", "traffic", f"{MIX}.json"), **TOY_MIX, **TOY_FLEET)
    toy._edit(
        os.path.join(root, "benchmarks", "configs", f"{CONFIG}.json"),
        **{
            "scale.hosts": TOY_FLEET["fitted_hosts"], "scale.live_hosts_at_setup": TOY_FLEET["live_hosts_at_setup"],
            "served_gnn.node_capacity": 128, "refresher.interval_s": 1.0, "limits.rank_gap_mlp": 0.001,
            # the toy's sound readings are 0-0.0006 over 36 runs (the model computes in bfloat16: two candidates
            # whose float32 costs lie 0.0005 apart come back in the other order), once over 0.001; its fp8
            # control reads 0.008-0.013
            "limits.rank_gap": 0.003,
        },
    )
    return root


def _notes(printed: str) -> dict:
    return json.loads(next(l for l in printed.splitlines() if l.startswith("notes: ")).removeprefix("notes: "))


def test_churn_cell_rehearsal(root, tmp_path, capsys):
    """End to end with a trace: hosts join and leave beside rounds and
    decisions, the refresher's own loop installs and re-embeds, the fleet
    crosses its capacity rung and nothing compiles in the window."""
    out = _run(root, CELL, tmp_path, seconds=0.5, trace=True)
    printed = capsys.readouterr().out
    checks = {
        l.removeprefix("check ").partition(": ")[0]: l.endswith(" ok") for l in printed.splitlines() if l.startswith("check ")
    }
    assert _sound_but(checks) and out["correct"] is checks["below_serving_unexplained"], [k for k, ok in checks.items() if not ok]
    assert NEW_LIMITS <= set(checks), NEW_LIMITS - set(checks)
    assert NEW_METRICS <= set(out["metrics"]) and all(out["metrics"][m]["value"] > 0 for m in NEW_METRICS - {"decisions_unknown_host_share"})
    notes = _notes(printed)
    assert notes["events"]["joins"] > 0 and notes["events"]["leaves"] > 0 and notes["embeds_in_window"] >= 1
    assert notes["capacity_nodes_engine_gnn"]["at_open"] == [128, 128] and notes["capacity_nodes_engine_gnn"]["at_close"] == [256, 256]
    assert notes["embed_rows"][0]["default"] == TOY_FLEET["late_hosts"] and notes["embed_rows"][0]["placed"] == TOY_FLEET["fitted_hosts"]
    assert out["attempted"] > 10 and out["failed"] <= 0.05 * out["attempted"], out


def test_the_cell_is_declared_as_the_issue_names_it():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, MIX, 1) and len(entry["why"]) <= 200
    cell = cells.load_cell(CELL)
    assert cell.traffic["kind"] == "decide_gnn_under_churn" and cell.params["rate_per_s"] > 0
    # every parameter of the frozen fleet's mix, and the fleet's own
    with open(os.path.join(cells.BENCH_DIR, "traffic", "rounds-60chunk-decide-gnn-half-knee.json")) as f:
        frozen = json.load(f)
    assert {k: v for k, v in frozen.items() if k not in ("kind", "why")} == {
        k: cell.traffic[k] for k in frozen if k not in ("kind", "why")
    }
    t = cell.traffic
    assert (t["fitted_hosts"], t["late_hosts"], t["live_hosts_at_setup"], t["joins_per_s"], t["leaves_per_s"]) == (1040, 920, 1960, 4.0, 1.0)
    # the sibling deployment word for word, and what this one adds
    with open(os.path.join(cells.BENCH_DIR, "configs", "one-chip-cluster-gnn.json")) as f:
        sibling = json.load(f)
    cfg = cell.config
    for group in ("trainer", "scheduler", "interpreter", "reduced", "served_model", "architecture"):
        assert cfg[group] == sibling[group], group
    assert cfg["assumed"][: len(sibling["assumed"])] == sibling["assumed"]
    assert {k: v for k, v in cfg["limits"].items() if k != "rank_gap_mlp"} == sibling["limits"] and cfg["limits"]["rank_gap_mlp"] == 0.06
    assert {k: cfg["served_gnn"][k] for k in sibling["served_gnn"] if k != "graph_orders"} == {
        k: v for k, v in sibling["served_gnn"].items() if k != "graph_orders"
    }
    assert {k: cfg["scale"][k] for k in sibling["scale"]} == sibling["scale"] and cfg["scale"]["live_hosts_at_setup"] == 1960
    assert cfg["refresher"]["interval_s"] == 5.0 and len(cfg["guarantees"]) == 9
    declared = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert len(cfg["source"]) <= 200 and cfg["source"] == declared["source"] and declared["reduced"] == ["hosts", "mlp_epochs"]
    assert [m["name"] for m in cell.end_to_end] == ["train_records_per_s", "setup_s"]
    # the cell reports what decide-under-round reports, and its own four (the swap's four are pinned to
    # decide-gnn-under-round by that cell's own test; the notes line has those steps)
    names = {m["name"] for m in cell.per_layer}
    assert names == {m["name"] for m in cells.load_cell("decide-under-round").per_layer} | NEW_METRICS
    assert all(m["workloads"] == [CELL] for m in bench["per_layer"] if m["name"] in NEW_METRICS)
    assert [m["name"] for m in bench["per_layer"]][-4:] == ["gnn_reembed_us", "topology_flush_us", "topology_leave_us", "decisions_unknown_host_share"]


def test_rows_placed_by_position_are_not_correct(root, tmp_path, monkeypatch, capsys):
    """The control: the scorer joins the fitted table to the live graph
    row by row. On a fleet with late and departed hosts it cannot even
    count them."""
    from dragonfly2_tpu.trainer import serving

    def by_position(params, node_ids):
        params = {k: v for k, v in params.items() if k != "node_ids"}
        embed = np.zeros((len(node_ids), params["node_embed"].shape[1]), np.float32)
        n = min(len(node_ids), params["node_embed"].shape[0])
        embed[:n] = np.asarray(params["node_embed"])[:n]
        return {**params, "node_embed": embed}, {"placed": len(node_ids), "default": 0, "dropped": 0}

    monkeypatch.setattr(serving, "place_node_rows", by_position)
    out = _run(root, CELL, tmp_path, seconds=0.5)
    checks = _checks(capsys)
    assert out["correct"] is False and not checks["gnn_rows_misplaced"] and not checks["rank_gap"] and not checks["gnn_row_counts_gap"], checks
    assert _sound_but(checks, "gnn_rows_misplaced", "rank_gap", "gnn_row_counts_gap"), [k for k, ok in checks.items() if not ok]


def test_a_leave_the_engine_ignores_is_not_correct(root, tmp_path, monkeypatch, capsys):
    """The control: ``LeaveHost`` reaches the host manager and not the
    engine, which goes on answering for the departed host."""
    from dragonfly2_tpu.topology.engine import TopologyEngine

    monkeypatch.setattr(TopologyEngine, "delete_host", lambda self, host_id: None)
    out = _run(root, CELL, tmp_path, seconds=0.5)
    checks = _checks(capsys)
    assert out["correct"] is False and not checks["engine_hosts_gap"], checks
    # its slots are never given back either (the arrays outgrow what set-up compiled), its edges stay
    # in every export's choice of freshest targets, and its estimates in every rtt join
    assert _sound_but(checks, "engine_hosts_gap", "compiles_in_window", "gnn_row_counts_gap", "rank_gap_mlp", "rank_gap"), [k for k, ok in checks.items() if not ok]


def test_the_reembed_switched_off_is_not_correct(root, tmp_path, monkeypatch, capsys):
    """The control: the loaded version is never embedded again, so the
    served graph is the one the install read until the next version."""
    from dragonfly2_tpu.scheduler.model_refresher import ModelRefresher

    monkeypatch.setattr(ModelRefresher, "_reembed_gnn", lambda self, key: False)
    out = _run(root, CELL, tmp_path, seconds=0.5)
    checks = _checks(capsys)
    assert out["correct"] is False and not checks["reembeds_missed"], checks
    assert _sound_but(checks, "reembeds_missed", "capacity_rung_not_crossed"), [k for k, ok in checks.items() if not ok]


def test_fp8_reference_in_the_programs_place_fails_rank_gap(root, tmp_path, monkeypatch, capsys):
    """The control: the fp8 reference ranks in the program's place, for
    the GraphSAGE-ranked and the MLP-ranked decisions alike."""
    real = guc.judge
    monkeypatch.setattr(
        guc, "judge", lambda desc, cfg, picks, returned_of, costs_of: real(desc, cfg, picks, lambda n: None, costs_of, "fp8")
    )
    out = _run(root, CELL, tmp_path, seconds=0.5)
    checks = _checks(capsys)
    # (rank_gap_mlp fails with it whenever the window's few MLP-ranked decisions hold a close call)
    assert out["correct"] is False and not checks["rank_gap"], checks
    assert _sound_but(checks, "rank_gap", "rank_gap_mlp"), [k for k, ok in checks.items() if not ok]


def test_the_membership_replay_against_a_log_written_by_hand():
    log = [
        ["announce", 0, 0, "a"], ["announce", 0, 0, "b"], ["announce", 0, 0, "c"], ["announce", 0, 0, "d"],
        ["adopt", 0, 0, "a", "b", 10e6, 1.0], ["adopt", 0, 0, "b", "c", 20e6, 1.0], ["adopt", 0, 0, "c", "d", 5e6, 1.0],
        ["flush", 0, 0, 0],
        ["export", 1, 2],
        ["announce", 3, 3, "e"],
        ["probe", 3, 3, "e", "a", 4e6, 2.0], ["probe", 3, 3, "b", "e", 6e6, 2.0],
        ["leave", 4, 5, "c"],
        ["flush", 6, 7, 2],
        ["export", 6, 8],
        ["announce", 9, 9, "f"], ["probe", 9, 9, "f", "b", 3e6, 3.0], ["flush", 10, 11, 1],
    ]
    r = membership.Replay(landmarks=2, iters=3, dests_per_source=1)
    for entry in log[:9]:
        r.apply(entry)
    assert list(r.live) == ["a", "b", "c", "d"] and r.slot == {"a": 0, "b": 1, "c": 2, "d": 3}
    assert r.exports[0] == [("a", [("b", 10e6, 1.0)]), ("b", [("c", 20e6, 1.0)]), ("c", [("d", 5e6, 1.0)])]
    assert membership.nodes_of(r.exports[0]) == ["a", "b", "c", "d"]
    # landmarks b and c (degree 2, the lower slots): a to d through them, 10 + 20 + 5 ms
    assert r.affinity("a", "d") == pytest.approx(np.log1p(35.0) / 10.0, rel=1e-6)
    assert r.affinity("a", "b") == pytest.approx(np.log1p(10.0) / 10.0, rel=1e-6) and r.affinity("a", "a") == 0.0
    for entry in log[9:13]:
        r.apply(entry)
    # e has announced and its probes wait: the engine does not know it; c is gone at once, with its edges
    assert r.affinity("e", "a") == 0.0 and r.affinity("b", "c") == 0.0 and "c" not in r.live and r.left_at["c"] == 5
    assert ("b", "c") not in r.edges and ("c", "d") not in r.edges and r.engine_hosts() == {"a", "b", "d"}
    for entry in log[13:15]:
        r.apply(entry)
    # c's slot was still held back when e was interned, and is free after that flush
    assert r.slot["e"] == 4 and r.free == [2] and r.faults == []
    assert r.exports[1] == [("a", [("b", 10e6, 1.0)]), ("e", [("a", 4e6, 2.0)]), ("b", [("e", 6e6, 2.0)])]
    assert membership.row_counts(r.exports[1], ["a", "b", "c", "d"]) == {"placed": 2, "default": 1, "dropped": 2}
    assert r.affinity("e", "b") == pytest.approx(np.log1p(6.0) / 10.0, rel=1e-6) and r.affinity("d", "a") == 0.0  # d is cut off
    for entry in log[15:]:
        r.apply(entry)
    assert r.slot["f"] == 2  # the next host to join takes the departed host's slot
    records = membership.records_of(r.exports[1], lambda hid: type("H", (), {"type": "normal", "network": None}))
    assert [(rec.host.id, [d.id for d in rec.dest_hosts], rec.dest_hosts[0].probes.average_rtt) for rec in records][1] == ("e", ["a"], 4000000)
    # guarantee 4: the poll after the leave and the flush embedded; one that did not is missed; one on a still graph is idle
    assert membership.polls_held([(5.5, 8.5, "reembed")], log) == (0, 0)
    assert membership.polls_held([(5.5, 8.5, None)], log[:14]) == (1, 0)
    assert membership.polls_held([(2.5, 2.9, "reembed")], log) == (0, 1) and membership.polls_held([(2.5, 2.9, "install")], log) == (0, 0)
    assert membership.Replay().faults == [] and [r2 := membership.Replay(), r2.apply(["probe", 0, 0, "a", "b", 1e6, 1.0]), r2.apply(["flush", 0, 0, 0])] and r2.faults


def test_the_sweep_beside_a_round_runs_under_host_events(root, monkeypatch, capsys):
    import sys

    from benchmarks.tests.test_decide_under_round import _as_on_the_chip
    from benchmarks.tools import sweep_beside_churn

    _as_on_the_chip(monkeypatch, root)
    monkeypatch.setattr(sweep_beside_churn, "HORIZON_S", 90.0)  # the sweep's own is for a dozen rates
    monkeypatch.setattr(
        sys, "argv",
        ["sweep_beside_churn.py", "--workload", CELL, "--rates", "40", "--seconds", "1", "--windows", "2",
         "--at-round-start", "1", "--held-ms", "1000"],
    )
    assert sweep_beside_churn.main() == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    windows = [l for l in lines if "rate" in l]
    assert len(windows) == 2 and all(w["errors"] == 0 and w["lost"] == 0 for w in windows)
    last = lines[-1]
    assert last["served_kind"] == "gnn" and last["events_done"] > 0 and not last["event_errors"]
    assert last["steps"]["delete_host"]["count"] > 0 and last["steps"]["flush"]["count"] > 0
