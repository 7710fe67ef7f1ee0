"""Tier-1's hold on the `train-rounds-three-schedulers` cell
(`benchmarks/tests/test_train_rounds_three_schedulers.py`): each case by
its own id, the module run once (`tests/benchmark_harness.py`)."""

import pytest

import benchmark_harness as harness

MODULE = "test_train_rounds_three_schedulers"
CASES = [
    "test_the_cell_is_declared_as_the_issue_names_it",
    "test_the_configuration_is_the_siblings_by_three_schedulers",
    "test_the_stated_admission_is_the_trainers_and_holds_two_weeks_not_three",
    "test_the_held_back_metrics_read_what_the_program_declares",
    "test_three_schedulers_rehearsal",
    "test_the_held_back_metrics_in_a_traced_line",
    "test_fp8_replay_in_the_programs_place_fails_the_mlp_gaps",
    "test_two_uploads_exchanged_fail_those_hosts_gaps_alone",
    "test_admission_switched_off_is_not_correct",
    "test_a_merge_that_forgets_its_weights_fails_the_merged_gap_alone",
    "test_a_trainer_without_the_fork_is_refused_before_staging",
]


@pytest.fixture(scope="module")
def report(tmp_path_factory):
    return harness.run_module(MODULE, tmp_path_factory.mktemp(MODULE))


@pytest.mark.parametrize("case", CASES)
def test_benchmark_harness_case(report, case):
    harness.assert_passed(report, case)


def test_benchmark_harness_ids(report):
    harness.assert_ids(report, CASES)
