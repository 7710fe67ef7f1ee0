"""Tier-1's hold on the `decide-gnn-under-round` cell
(`benchmarks/tests/test_decide_gnn_under_round.py`): each case by its own
id, the module run once (`tests/benchmark_harness.py`)."""

import pytest

import benchmark_harness as harness

MODULE = "test_decide_gnn_under_round"
CASES = [
    "test_the_cell_is_declared_as_the_issue_names_it",
    "test_gnn_cell_rehearsal",
    "test_rows_placed_by_position_are_not_correct",
    "test_fp8_gnn_reference_in_the_programs_place_fails_rank_gap",
    "test_a_failed_install_is_counted_and_fails_the_run",
    "test_placed_weights_and_rows_misplaced_read_ids_not_positions",
    "test_the_registry_activates_the_newest_graphsage_version",
    "test_the_sweep_beside_a_round_runs_on_the_graphsage_rung",
    "test_the_served_control_reads_trained_graphsage_weights",
    "test_the_swap_holds_tool_times_the_lock_the_interpreter_and_the_join",
]


@pytest.fixture(scope="module")
def report(tmp_path_factory):
    return harness.run_module(MODULE, tmp_path_factory.mktemp(MODULE))


@pytest.mark.parametrize("case", CASES)
def test_benchmark_harness_case(report, case):
    harness.assert_passed(report, case)


def test_benchmark_harness_ids(report):
    harness.assert_ids(report, CASES)
