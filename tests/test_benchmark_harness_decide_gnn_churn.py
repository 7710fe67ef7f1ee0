"""Tier-1's hold on the `decide-gnn-under-churn` cell
(`benchmarks/tests/test_decide_gnn_under_churn.py`): each case by its own
id, the module run once (`tests/benchmark_harness.py`)."""

import pytest

import benchmark_harness as harness

MODULE = "test_decide_gnn_under_churn"
CASES = [
    "test_churn_cell_rehearsal",
    "test_the_cell_is_declared_as_the_issue_names_it",
    "test_rows_placed_by_position_are_not_correct",
    "test_a_leave_the_engine_ignores_is_not_correct",
    "test_the_reembed_switched_off_is_not_correct",
    "test_fp8_reference_in_the_programs_place_fails_rank_gap",
    "test_the_membership_replay_against_a_log_written_by_hand",
    "test_the_sweep_beside_a_round_runs_under_host_events",
]


@pytest.fixture(scope="module")
def report(tmp_path_factory):
    return harness.run_module(MODULE, tmp_path_factory.mktemp(MODULE))


@pytest.mark.parametrize("case", CASES)
def test_benchmark_harness_case(report, case):
    harness.assert_passed(report, case)


def test_benchmark_harness_ids(report):
    harness.assert_ids(report, CASES)
