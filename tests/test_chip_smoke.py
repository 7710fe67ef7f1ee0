"""chip_smoke.py's wiring, at toy size on the CPU backend: the same
servers and the same phases the chip run takes, so a renamed hook or a
changed signature fails here and not on a chip call. What the chip run
is for — the TPU compiler, device placement at real sizes — this cannot
show."""

import json
import os
import subprocess
import sys
from dataclasses import replace

import pytest

import chip_smoke
from dragonfly2_tpu.utils import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOY = chip_smoke.Size(
    download_bytes=0,
    unique_records=512,
    hosts=24,
    probe_rounds=2,
    daemons=4,
    tasks=2,
    demand_tasks=16,
)


class ToyPath(chip_smoke.MainPath):
    """512 records are no 128 MiB upload: reach the streamed fit (the
    path the chip run takes) by lowering the trainer's threshold, with a
    batch small enough that the toy stream has steps to learn over."""

    def bring_up(self):
        super().bring_up()
        cfg = self.trainer.training.config
        cfg.streaming_threshold_bytes = 0
        cfg.mlp = replace(cfg.mlp, batch_size=256)


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    path = ToyPath(
        TOY, 0, str(tmp_path_factory.mktemp("chip-smoke")), chip_smoke.Report()
    )
    try:
        path.train_round()
        yield path
    finally:
        path.tear_down()


def test_main_path_passes_every_check_at_toy_size(trained):
    trained.serve_round()
    assert trained.report.failed == []
    assert set(trained.fits) == {"mlp", "gnn", "gru"}
    # the conftest's eight host devices: the fit mesh found them by itself
    assert dict(trained.trainer.training.mesh.shape) == {"dp": 8}
    assert set(trained.report.phases) >= {"train", "serve_mlp", "serve_gnn"}


def test_a_failing_served_score_fails_the_smoke(trained):
    """The evaluator's ladder keeps scheduling through a serving failure
    by design; the smoke must not read that as a healthy run."""
    faults.configure("scheduler.serving_score=error")
    try:
        trained.serve_decisions("faulted")
    finally:
        faults.clear()
    assert "faulted: no serving fallback, no serving error" in trained.report.failed
    assert "faulted: decisions scored by the served model" in trained.report.failed


def test_result_line_is_exactly_ok_and_device():
    """The driver parses the last stdout line and refuses any other key."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = chip_smoke.result_line(True, device)
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": device}


def test_script_entry_refuses_a_cpu_backend():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode not in (0, None)
    assert proc.stdout == ""  # no result line, nothing that reads as one
    assert "needs an accelerator" in proc.stderr
