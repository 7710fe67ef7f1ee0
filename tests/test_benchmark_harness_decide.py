"""Tier-1's hold on the `decide-under-round` cell
(`benchmarks/tests/test_decide_under_round.py`): each case by its own
id, the module run once (`tests/benchmark_harness.py`)."""

import pytest

import benchmark_harness as harness

MODULE = "test_decide_under_round"
CASES = [
    "test_the_cell_is_declared_as_the_issue_names_it",
    "test_versions_in_force_leave_out_what_an_install_touches",
    "test_colocated_cell_rehearsal",
    "test_fp8_in_the_served_models_place_is_not_correct",
    "test_a_reversed_ranking_is_not_correct",
    "test_a_resident_fit_on_half_its_pairs_is_not_correct",
    "test_a_version_off_the_replay_or_a_decision_held_to_the_wrong_version_is_not_correct",
    "test_a_version_that_scores_the_swarm_another_way_is_not_correct",
    "test_score_gap_is_zero_for_the_same_scorer_and_wide_for_another",
    "test_the_registry_answers_the_refresher_in_the_managers_words",
    "test_the_sweep_beside_a_round_prints_a_line_a_window",
    "test_the_served_control_reads_trained_weights",
]


@pytest.fixture(scope="module")
def report(tmp_path_factory):
    return harness.run_module(MODULE, tmp_path_factory.mktemp(MODULE))


@pytest.mark.parametrize("case", CASES)
def test_benchmark_harness_case(report, case):
    harness.assert_passed(report, case)


def test_benchmark_harness_ids(report):
    harness.assert_ids(report, CASES)
