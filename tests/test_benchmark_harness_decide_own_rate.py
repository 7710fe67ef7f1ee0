"""Tier-1's hold on the `decide-own-rate-under-round` cell
(`benchmarks/tests/test_decide_own_rate_under_round.py`): each case by
its own id, the module run once (`tests/benchmark_harness.py`)."""

import pytest

import benchmark_harness as harness

MODULE = "test_decide_own_rate_under_round"
CASES = [
    "test_the_cell_is_its_sibling_but_for_the_rate",
    "test_the_held_back_metrics_read_phases_the_program_declares",
    "test_own_rate_cell_rehearsal",
    "test_the_held_back_metrics_in_a_traced_line",
]


@pytest.fixture(scope="module")
def report(tmp_path_factory):
    return harness.run_module(MODULE, tmp_path_factory.mktemp(MODULE))


@pytest.mark.parametrize("case", CASES)
def test_benchmark_harness_case(report, case):
    harness.assert_passed(report, case)


def test_benchmark_harness_ids(report):
    harness.assert_ids(report, CASES)
