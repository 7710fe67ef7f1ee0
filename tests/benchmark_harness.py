"""Runs one module of `benchmarks/tests/` in a process of its own and reads
its report, for `tests/test_benchmark_harness*.py`.

Why a process: those tests drive the benchmark's generators at toy size
through the program's own names (`taps.spy(training_mod, "train_mlp")`,
`colocated.server.settle`, `wire.encode_train_block`, ...), so a rename
in the package that the benchmark would only meet on the chip turns them
red here. They build a one-chip cell, and `generators/rounds.py` refuses
a fit mesh that does not span the cell's chips; `tests/conftest.py`
forces eight host devices on this process, so they run beside it with
one (ROADMAP D9: once a toy cell's trainer is built on `ctx.devices`,
the three files re-export the cases and this helper goes).
"""

from __future__ import annotations

import os
import re
import subprocess
import sys
import xml.etree.ElementTree as ET

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TIME_LIMIT_S = 300


def run_module(module: str, out_dir) -> dict[str, str | None]:
    """`benchmarks/tests/<module>.py`, run once from the repo root on one
    host device: case id -> None when it passed, else what it reported."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "", env.get("XLA_FLAGS", "")
    ).strip()
    xml = os.path.join(str(out_dir), "report.xml")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", f"benchmarks/tests/{module}.py", "-q",
         "-p", "no:cacheprovider", "-p", "no:randomly", f"--junitxml={xml}"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=TIME_LIMIT_S,
    )
    assert os.path.exists(xml), (
        f"{module} wrote no report (exit {proc.returncode}):\n"
        f"{proc.stdout[-4000:]}\n{proc.stderr[-4000:]}"
    )
    report: dict[str, str | None] = {}
    for case in ET.parse(xml).iter("testcase"):
        faults = [
            f"{f.tag}: {f.get('message', '')}\n{f.text or ''}"
            for f in case if f.tag in ("failure", "error", "skipped")
        ]
        report[case.get("name")] = "\n".join(faults) or None
    return report


# The asserts carry their own messages: pytest rewrites asserts in test
# modules only, and this is not one.


def assert_passed(report: dict[str, str | None], case: str) -> None:
    assert case in report, f"{case} is not in the report: {sorted(report)}"
    assert report[case] is None, report[case]


def assert_ids(report: dict[str, str | None], cases: list[str]) -> None:
    """The module ran exactly the cases its file lists: a `benchmark` PR
    that adds, renames or drops a case edits that file's `CASES`."""
    unlisted = sorted(set(report) - set(cases))
    not_run = sorted(set(cases) - set(report))
    assert not unlisted and not not_run, (
        f"ran but not in CASES: {unlisted}; in CASES but not run: {not_run}"
    )
