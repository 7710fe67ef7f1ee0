"""Tier-1's hold on the round's phase metrics
(`benchmarks/tests/test_round_phase_metrics.py`): each case by its own
id, the module run once (`tests/benchmark_harness.py`)."""

import pytest

import benchmark_harness as harness

MODULE = "test_round_phase_metrics"
CASES = [
    "test_a_resident_round_feeds_the_phase_the_reader_reads[gru_epoch_wait_s]",
    "test_a_resident_round_feeds_the_phase_the_reader_reads[gru_load_s]",
    "test_a_resident_round_feeds_the_phase_the_reader_reads[mlp_epoch_dispatch_s]",
    "test_a_resident_round_feeds_the_phase_the_reader_reads[mlp_epoch_wait_s]",
    "test_a_resident_round_feeds_the_phase_the_reader_reads[mlp_feed_s]",
    "test_a_resident_round_feeds_the_phase_the_reader_reads[mlp_gather_s]",
    "test_a_resident_round_feeds_the_phase_the_reader_reads[mlp_holdout_s]",
    "test_a_resident_round_feeds_the_phase_the_reader_reads[mlp_load_s]",
    "test_a_resident_round_feeds_the_phase_the_reader_reads[mlp_register_s]",
    "test_a_resident_round_feeds_the_phase_the_reader_reads[mlp_split_s]",
    "test_the_mlp_legs_phases_are_its_fit",
    "test_the_phases_are_the_programs",
    "test_a_phase_never_entered_reads_nothing",
    "test_the_phases_tool_prints_the_windows_split",
]


@pytest.fixture(scope="module")
def report(tmp_path_factory):
    return harness.run_module(MODULE, tmp_path_factory.mktemp(MODULE))


@pytest.mark.parametrize("case", CASES)
def test_benchmark_harness_case(report, case):
    harness.assert_passed(report, case)


def test_benchmark_harness_ids(report):
    harness.assert_ids(report, CASES)
