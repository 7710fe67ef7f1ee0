"""The resident round's dfprof phases at toy size on the CPU, read the way
a per-layer metric would read them: ``taps.phase_counts`` before the
run, ``taps.phase_delta`` after it, the ``prof_phase`` reader over the
difference. The ``rounds`` generator hands no ``prof_phase`` bundle yet
(PERF.md, Open questions), so the bundle is taken here, around the
generator, with the harness's own taps and nothing else.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

from __future__ import annotations

import json
import sys

import pytest

from benchmarks.harness import taps
from benchmarks.harness.layer_readers import read_metric
from benchmarks.tests import toy
from benchmarks.tests.test_benchmark import _run, compile_cache  # noqa: F401

# the metrics a `benchmark` PR can declare, and the phase each would read
PHASE_METRICS = {
    "mlp_load_s": "trainer.mlp_load",
    "mlp_split_s": "trainer.mlp_split",
    "mlp_gather_s": "trainer.mlp_gather",
    "mlp_feed_s": "trainer.mlp_feed",
    "mlp_epoch_dispatch_s": "trainer.mlp_epoch_dispatch",
    "mlp_epoch_wait_s": "trainer.mlp_epoch_wait",
    "mlp_holdout_s": "trainer.mlp_holdout",
    "mlp_register_s": "trainer.mlp_register",
    "gru_load_s": "trainer.gru_load",
    "gru_epoch_wait_s": "trainer.gru_epoch_wait",
}
NEVER = "trainer.test_never_entered"
BEFORE = {"mlp_fit_s", "gnn_fit_s", "gru_fit_s", "device_idle_share.train"}


def _decl(name: str, phase: str) -> dict:
    return {"name": name, "unit": "s", "reader": {"kind": "prof_phase", "phase": phase}}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced toy run (a warm-up round and the window's rounds) and
    what its phases moved by, as a generator's ``probes`` would hold it."""
    tmp = tmp_path_factory.mktemp("phases")
    names = [*PHASE_METRICS.values(), NEVER]
    before = taps.phase_counts(names)
    out = _run(toy.make_root(tmp), "train-round-resident", tmp, seconds=0.5, trace=True)
    assert out["correct"] is True, out
    return out, {"prof_phase": taps.phase_delta(before, taps.phase_counts(names))}


@pytest.mark.parametrize("name", sorted(PHASE_METRICS))
def test_a_resident_round_feeds_the_phase_the_reader_reads(traced, name):
    _, probes = traced
    value = read_metric(_decl(name, PHASE_METRICS[name]), probes)
    assert value is not None and value > 0


def test_the_mlp_legs_phases_are_its_fit(traced):
    """Seconds an entry times the toy's one epoch a round: the eight are
    the leg (the warm-up round's compile lies in its epoch dispatch, so
    the mean over all rounds may pass the window's median fit)."""
    out, probes = traced
    total = sum(
        read_metric(_decl(n, p), probes) for n, p in PHASE_METRICS.items() if n.startswith("mlp_")
    )
    assert total > 0.5 * out["metrics"]["mlp_fit_s"]["value"]
    rounds = probes["prof_phase"]["trainer.mlp_load"]["count"]
    assert rounds >= 2  # the warm-up round and at least one in the window
    for stage in ("split", "gather", "feed", "epoch_dispatch", "epoch_wait", "holdout", "register"):
        assert probes["prof_phase"][f"trainer.mlp_{stage}"]["count"] == rounds


def test_the_phases_are_the_programs():
    from dragonfly2_tpu.trainer import metrics as M

    declared = {ph.name for leg in (M.PH_MLP, M.PH_GNN, M.PH_GRU) for ph in vars(leg).values()}
    assert set(PHASE_METRICS.values()) <= declared


def test_a_phase_never_entered_reads_nothing(traced):
    """What a commit that lacks a phase gives: a count of 0, the reader
    returns None, the line would leave the metric out; nothing raises.
    So does a run whose generator hands no ``prof_phase`` bundle, which
    is every run of the ``rounds`` generator as it stands."""
    out, probes = traced
    assert read_metric(_decl("never_s", NEVER), probes) is None
    assert read_metric(_decl("mlp_load_s", PHASE_METRICS["mlp_load_s"]), {}) is None
    assert set(out["metrics"]) == BEFORE


def test_the_phases_tool_prints_the_windows_split(tmp_path, monkeypatch, capsys):
    """``tools/phases.py`` at toy size: the run's own result line, then
    the phases that moved between the window's opening and its close."""
    import jax

    from benchmarks.harness import cells, device
    from benchmarks.tools import phases

    root, load = toy.make_root(tmp_path), cells.load_cell
    monkeypatch.setattr(cells, "load_cell", lambda name: load(name, root=root))
    monkeypatch.setattr(device, "require_chips", lambda chips: jax.devices()[:1])
    monkeypatch.setattr(device, "peaks_for", lambda kind: {})
    monkeypatch.setattr(
        sys, "argv", ["phases.py", "--workload", "train-round-resident", "--seed", "7", "--seconds", "0.5"]
    )
    assert phases.main() == 0
    *_, result, split = capsys.readouterr().out.strip().splitlines()
    assert json.loads(result)["correct"] is True
    moved = json.loads(split.removeprefix("phases over the window: "))
    assert set(PHASE_METRICS.values()) <= set(moved)
    # the window's rounds only: the warm-up round's entries are not in it
    rounds = moved["trainer.mlp_load"]["count"]
    assert moved["trainer.gru_epoch_wait"]["count"] == 10 * rounds
    assert all(d["total_s"] > 0 and d["s_per_entry"] > 0 for d in moved.values())
