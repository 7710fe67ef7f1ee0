"""The benchmark's own tests: the CPU rehearsal of both generators at toy
size, the controls that have to come out as not correct, and the
harness's arithmetic. Not part of tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

from __future__ import annotations

import json
import math
import os
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.normpath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.generators import open_loop_decisions as old  # noqa: E402
from benchmarks.harness import (  # noqa: E402
    cells, layer_readers, reference, reference_fits, swarm, synth, trace_reduce,
)
from benchmarks.tests import toy  # noqa: E402


@pytest.fixture()
def root(tmp_path):
    return toy.make_root(tmp_path)


@pytest.fixture(autouse=True, scope="module")
def compile_cache(tmp_path_factory):
    """A run keeps JAX's persistent cache on; so does the rehearsal, or a
    fit that builds its jit wrappers anew each round would count as
    compiling inside the window."""
    import jax

    old_dir = jax.config.jax_compilation_cache_dir
    old_floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir", str(tmp_path_factory.mktemp("jaxcache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    yield
    jax.config.update("jax_compilation_cache_dir", old_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old_floor)


def _run(root, name, tmp_path, seed=7, seconds=1.0, trace=False):
    import jax

    cell = cells.load_cell(name, root=root)
    return bench_run.run_cell(cell, seed, seconds, trace, jax.devices()[:1], str(tmp_path / "work"))


# -- BENCHMARK.json and the files it names ---------------------------------


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cell_names = {w["name"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        cell = cells.load_cell(w["name"])
        assert cell.traffic["kind"] in ("rounds", "open_loop_decisions")
        assert len(w["why"]) <= 200
        assert [m for m in cell.end_to_end if m["name"] != "setup_s"]
        assert cell.per_layer
    for m in bench["per_layer"]:
        with open(os.path.join(ROOT, "benchmarks", "layer_metrics", m["name"] + ".json")) as f:
            decl = json.load(f)
        assert set(decl) == {"reader"}  # everything else is said once, in BENCHMARK.json
        assert decl["reader"]["kind"] in layer_readers.READERS
        moved = e2e[m["moves"]]
        for name in m["workloads"]:
            assert name in cell_names
            assert "workloads" not in moved or name in moved["workloads"]


# -- generators: the same seed, the same work ------------------------------


def test_same_seed_same_inputs_other_seed_other_inputs():
    from dragonfly2_tpu.schema import wire

    def body(seed):
        recs = synth.download_records(64, seed)
        return wire.encode_train_block(recs)

    assert body(3) == body(3)
    assert body(3) != body(4)
    with open(os.path.join(cells.BENCH_DIR, "traffic", "decide-poisson-0.8.json")) as f:
        traffic = json.load(f)
    a = swarm.arrivals(traffic, 3, 500.0, 2.0)
    b = swarm.arrivals(traffic, 3, 500.0, 2.0)
    c = swarm.arrivals(traffic, 4, 500.0, 2.0)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0]) and not np.array_equal(a[1], c[1])
    # every seed gets the same amount of work: as many arrivals, the same
    # split over tasks, the same number of parents a task
    assert len(a[0]) == len(c[0]) == 1000
    assert np.array_equal(np.bincount(a[1], minlength=256), np.bincount(c[1], minlength=256))
    small = dict(traffic, hosts=96, tasks=12)
    da, db = swarm.describe(small, 3), swarm.describe(small, 4)
    assert [len(t["peers"]) for t in da["tasks"]] == [len(t["peers"]) for t in db["tasks"]]
    assert [p["state"] for t in da["tasks"] for p in t["peers"]] == [
        p["state"] for t in db["tasks"] for p in t["peers"]
    ]
    assert da["hosts"][5].cpu.percent != db["hosts"][5].cpu.percent


def test_probe_graph_has_a_fixed_edge_count():
    for seed in (1, 2, 2**31 + 5):
        edges = synth.probe_edges(128, seed)
        assert len({(s, t) for s, t, _ in edges}) == 128 * 10
        assert all(s != t for s, t, _ in edges)


# -- the open-loop clock ---------------------------------------------------


def test_a_stalled_worker_charges_the_decisions_behind_it():
    class Stalls:
        def __init__(self):
            self.n = 0
            self.lock = threading.Lock()

        def find_candidate_parents(self, child):
            with self.lock:
                self.n += 1
                first = self.n == 1
            time.sleep(0.3 if first else 0.001)
            return [type("P", (), {"id": "p"})()], True

    due = np.array([0.0, 0.01, 0.02, 0.03])
    w = old.Window(Stalls(), [[object()]], due, np.zeros(4, int), np.zeros(4, int), workers=1)
    w.run(drain_s=2.0)
    lat = w.end - (w.t0 + w.due)
    # one worker, stalled 0.3 s on the first: the three behind it were
    # due during the stall and are charged for it
    assert lat[0] >= 0.3
    assert all(lat[1:] >= 0.25)
    wait = w.start - (w.t0 + w.due)
    assert all(wait[1:] >= 0.25) and not w.slept[1:].any()


# -- the trace reduction ---------------------------------------------------


def test_trace_reduction_on_a_toy_trace():
    E = trace_reduce.Event
    dev0 = [E("fusion.1", 0, 100), E("fusion.1", 150, 250), E("copy", 240, 300)]
    dev1 = [E("fusion.1", 0, 50)]
    host = [E("decode", 100, 150), E("bench.window", 0, 1000), E("short", 10, 20)]
    out = trace_reduce.reduce_planes([dev0, dev1], host)
    assert out["busy_s"] == pytest.approx((250 + 50) / 2 / 1e9)
    assert out["window_s"] == pytest.approx(300 / 1e9)
    assert out["device_ops"][0][0] == "fusion.1"
    assert out["idle_gaps"][0] == ["decode", pytest.approx(50 / 1e9)]
    assert trace_reduce.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    from collections import namedtuple

    X = namedtuple("X", "name start_ns duration_ns")
    line = [X("%w = (f32[]) while(%a)", 0, 100), X("%f = f32[] fusion(%b)", 10, 20), X("%g = f32[] fusion(%b)", 90, 30), X("%c = f32[] copy(%b)", 200, 5)]
    kept = trace_reduce.outermost(line, {})
    assert [(e.name, e.start_ns, e.end_ns) for e in kept] == [("%w while", 0, 100), ("%g fusion", 90, 120), ("%c copy", 200, 205)]
    assert trace_reduce.gaps([(1, 2), (4, 5)], 0, 6)[0] in ((2, 4),)


def test_trace_reduction_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.toy"):
        jax.block_until_ready(jnp.ones((64, 64)) @ jnp.ones((64, 64)))
    jax.profiler.stop_trace()
    planes, host = trace_reduce.load_xplane(str(tmp_path))
    assert any(ev.name == "bench.toy" for ev in host)
    assert planes == []  # no TPU plane in a CPU trace: nothing is busy
    assert trace_reduce.reduce_planes(planes, host)["busy_s"] == 0.0


# -- the references --------------------------------------------------------


def test_rtt_reference_agrees_with_the_engine():
    from dragonfly2_tpu.topology import TopologyConfig, TopologyEngine

    hosts = synth.fleet(96, 5)
    edges = synth.probe_edges(96, 5)
    engine = TopologyEngine(TopologyConfig(backend="jax"))
    old.fill_topology(engine, {"hosts": hosts, "edges": edges})
    ref = reference.RttReference(96, edges)
    rng = np.random.default_rng(0)
    pairs = rng.integers(0, 96, size=(200, 2))
    got = engine.rtt_affinity_pairs([hosts[a].id for a, _ in pairs], [hosts[b].id for _, b in pairs])
    want = [ref.affinity(int(a), int(b)) for a, b in pairs]
    assert np.allclose(got, want, atol=1e-5)
    assert len(set(np.round(want, 4))) > 50


def test_record_pairs_agree_with_the_encoded_block():
    from dragonfly2_tpu.schema import wire

    recs = synth.download_records(64, 9)
    _, cols, _ = wire.decode_block(wire.encode_train_block(recs))
    x, y = reference.record_pairs(recs)
    assert np.allclose(cols["pairs.features"], x, atol=1e-6)
    assert np.allclose(cols["pairs.labels"], y, atol=1e-6)


def test_graph_and_sequences_agree_with_the_programs():
    from dragonfly2_tpu.schema.columnar import records_to_columns
    from dragonfly2_tpu.schema.features import build_probe_graph, extract_piece_sequences

    topo = synth.topology_records(synth.fleet(64, 3), synth.probe_edges(64, 3, 5, 2))
    g, want = build_probe_graph(records_to_columns(topo), max_degree=16), reference.probe_graph(topo, 16)
    assert reference_fits.mismatches(
        (g.node_features, want["features"]), (g.edge_src, want["src"]), (g.edge_dst, want["dst"]),
        (g.edge_rtt_log_ms, want["rtt_log"]), (g.neighbors, want["neighbors"]),
        (g.neighbor_mask, want["mask"]),
    ) == 0
    recs = synth.download_records(64, 3)
    got = extract_piece_sequences(records_to_columns(recs))
    seqs, labels, lengths = reference_fits.piece_sequences(recs)
    assert reference_fits.mismatches((got.sequences, seqs), (got.labels, labels), (got.lengths, lengths)) == 0
    assert reference_fits.mismatches((seqs, seqs[:-1])) == seqs.size
    tail = reference_fits.newest(np.arange(10), 3, 12)
    assert tail.tolist() == [8, 9, *range(10)]


def test_rank_gap():
    costs = {"a": 1.0, "b": 2.0, "c": 3.0, "d": 4.0, "e": 5.0}
    assert reference.rank_gap(["a", "b", "c", "d"], costs, True) == 0.0
    assert reference.rank_gap(["b", "a", "c", "d"], costs, True) == 1.0
    assert reference.rank_gap(["a", "b", "c", "e"], costs, True) == 1.0
    assert reference.rank_gap(["a", "b", "c", "e"], costs, False) == 0.0
    assert math.isinf(reference.rank_gap(["a", "z"], costs, True))


# -- the rehearsal: both generators end to end, and a cell added as files --


def test_train_round_rehearsal(root, tmp_path, monkeypatch):
    toy.force_streaming(monkeypatch)
    out = _run(root, "train-round", tmp_path, seconds=0.5)
    assert out["correct"] is True, out
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"train_records_per_s", "setup_s"}
    assert out["metrics"]["train_records_per_s"]["value"] > 0
    traced = _run(root, "train-round", tmp_path, seconds=0.5, trace=True)
    assert {"mlp_fit_s", "gnn_fit_s", "gru_fit_s", "ingest_decode_wait_share"} <= set(traced["metrics"])
    assert "busy_s" in traced["device"] and "breakdown" in traced


def test_resident_round_rehearsal(root, tmp_path):
    """The cell BENCHMARK.json holds: streaming off, so the MLP fit keeps
    every pair of the upload on the device."""
    out = _run(root, "train-round-resident", tmp_path, seconds=0.5)
    assert out["correct"] is True, out
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"train_records_per_s", "setup_s"}
    traced = _run(root, "train-round-resident", tmp_path, seconds=0.5, trace=True)
    assert set(traced["metrics"]) == {"mlp_fit_s", "gnn_fit_s", "gru_fit_s", "device_idle_share.train"}
    assert "busy_s" in traced["device"] and "breakdown" in traced


def test_decide_steady_rehearsal(root, tmp_path):
    out = _run(root, "decide-steady", tmp_path, seconds=1.0)
    assert out["correct"] is True, out
    assert out["failed"] == 0 and out["attempted"] == 150
    assert set(out["metrics"]) == {
        "decisions_per_s", "decision_p50_us", "setup_s",
    }
    traced = _run(root, "decide-steady", tmp_path, seconds=1.0, trace=True)
    assert {
        "queue_wait_us_p50", "serving_batch_rows", "serving_batches_per_s",
        "evaluate_us_mean", "serving_wait_us_mean", "generator_lateness_us_p99",
    } <= set(traced["metrics"])


def test_decide_steady_gnn_rehearsal(root, tmp_path):
    out = _run(root, "decide-steady-gnn", tmp_path, seconds=1.0)
    assert out["correct"] is True, out
    assert out["failed"] == 0 and out["attempted"] == 150


def test_a_cell_is_three_new_files_and_one_entry(root, tmp_path):
    bdir = os.path.join(root, "benchmarks")
    with open(os.path.join(bdir, "traffic", "decide-poisson-0.8.json")) as f:
        mix = json.load(f)
    mix["zipf_s"] = 0.5
    with open(os.path.join(bdir, "traffic", "decide-flat.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bdir, "cells", "decide-flat.json"), "w") as f:
        json.dump({"rate_per_s": 100.0}, f)
    metric = {
        "name": "serving_rows_per_s", "layer": "scoring scheduler/serving.py", "unit": "rows/s",
        "better": "higher", "moves": "decisions_per_s", "workloads": ["decide-flat"],
        "source": "program_counter",
        "reader": {"kind": "serving_snapshot", "num": "rows_scored", "den": "window_s"},
    }
    with open(os.path.join(bdir, "layer_metrics", "serving_rows_per_s.json"), "w") as f:
        json.dump({"reader": metric["reader"]}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append(
        {"name": "decide-flat", "config": "default-ml", "traffic": "decide-flat", "chips": 1, "why": "flat popularity"}
    )
    bench["per_layer"].append({k: v for k, v in metric.items() if k != "reader"})
    for m in bench["end_to_end"]:
        if "workloads" in m and "decide-steady" in m["workloads"]:
            m["workloads"].append("decide-flat")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    out = _run(root, "decide-flat", tmp_path, seconds=1.0, trace=True)
    assert out["correct"] is True
    assert out["metrics"]["serving_rows_per_s"]["value"] > 0
    assert "serving_batch_rows" not in out["metrics"]  # it does not list the new cell


# -- the controls: each has to come out as not correct ---------------------


def test_fp8_reference_in_the_programs_place_fails_rank_gap(root):
    cell = cells.load_cell("decide-steady", root=root)
    desc = swarm.describe(cell.traffic, 11)
    weights = synth.mlp_weights(11, [19, 128, 128, 1])
    picks = [(k, c) for k in range(len(desc["tasks"])) for c in range(2)]
    sound = old.judge(desc, weights, cell.config, picks, lambda n: None, "float32")
    control = old.judge(desc, weights, cell.config, picks, lambda n: None, "fp8")
    assert sound["rank_gap"] == 0.0 and sound["illegal"] == 0 and sound["wrong_count"] == 0
    assert control["rank_gap"] > 3 * 0.02  # far over what bf16 serving shows


def test_fp8_gnn_reference_in_the_programs_place_fails_rank_gap(root):
    cell = cells.load_cell("decide-steady-gnn", root=root)
    desc = swarm.describe(cell.traffic, 11)
    weights = synth.gnn_weights(11, len(desc["hosts"]))
    picks = [(k, c) for k in range(len(desc["tasks"])) for c in range(2)]
    sound = old.judge(desc, weights, cell.config, picks, lambda n: None, "float32")
    control = old.judge(desc, weights, cell.config, picks, lambda n: None, "fp8")
    assert sound["rank_gap"] == 0.0 and sound["illegal"] == 0 and sound["wrong_count"] == 0
    assert control["rank_gap"] > 3 * 0.02


def test_gnn_reference_agrees_with_the_served_scorer(root):
    from dragonfly2_tpu.schema.columnar import records_to_columns
    from dragonfly2_tpu.schema.features import build_probe_graph
    from dragonfly2_tpu.trainer.serving import GNNScorer

    cell = cells.load_cell("decide-steady-gnn", root=root)
    desc = swarm.describe(cell.traffic, 5)
    graph = build_probe_graph(records_to_columns(desc["topology_records"]), max_degree=16)
    weights = synth.gnn_weights(5, graph.num_nodes)
    index = {h.id: i for i, h in enumerate(desc["hosts"])}
    ref = reference.GnnReference(desc["topology_records"], index, weights)
    rng = np.random.default_rng(0)
    a, b = rng.integers(0, len(index), 200), rng.integers(0, len(index), 200)
    got = GNNScorer(weights, graph).predict_rtt_log_ms(
        [desc["hosts"][i].id for i in a], [desc["hosts"][i].id for i in b]
    )
    want = ref.costs(list(a), list(b))
    assert np.abs(got - want).max() < 0.03  # the scorer's matmuls are bfloat16
    assert want.std() > 0.1


def test_a_reversed_ranking_is_not_correct(root, tmp_path, monkeypatch):
    from dragonfly2_tpu.scheduler.scheduling import Scheduling

    real = Scheduling.find_candidate_parents

    def reversed_order(self, peer, blocklist=None):
        parents, found = real(self, peer, blocklist)
        return parents[::-1], found

    monkeypatch.setattr(Scheduling, "find_candidate_parents", reversed_order)
    out = _run(root, "decide-steady", tmp_path, seconds=1.0)
    assert out["correct"] is False


def test_a_round_that_leaves_out_a_pass_is_not_correct(root, tmp_path, monkeypatch):
    from dragonfly2_tpu.trainer import ingest

    toy.force_streaming(monkeypatch)
    real = ingest.stream_train_mlp

    def one_pass(*args, **kwargs):
        kwargs["passes"] = 1
        return real(*args, **kwargs)

    monkeypatch.setattr(ingest, "stream_train_mlp", one_pass)
    out = _run(root, "train-round", tmp_path, seconds=0.5)
    assert out["correct"] is False


def test_a_resident_fit_that_hands_back_its_start_is_not_correct(root, tmp_path, monkeypatch):
    """The timed path broken underneath: an epoch that returns its state
    unchanged still registers a version with a finite loss."""
    from dragonfly2_tpu.trainer import train

    real = train.make_epoch_fn

    def no_step(loss_fn, optimizer):
        epoch = real(loss_fn, optimizer)

        def unchanged(params, opt_state, batches):
            import jax

            if len(batches) != 2:  # the GraphSAGE and GRU fits feed three arrays
                return epoch(params, opt_state, batches)
            keep = jax.tree_util.tree_map(lambda a: a + 0, (params, opt_state))
            _, _, loss = epoch(params, opt_state, batches)
            return (*keep, loss)

        return unchanged

    monkeypatch.setattr(train, "make_epoch_fn", no_step)
    out = _run(root, "train-round-resident", tmp_path, seconds=0.5)
    assert out["correct"] is False and out["failed"] == 0


def test_a_resident_fit_on_half_its_pairs_is_not_correct(root, tmp_path, monkeypatch):
    from dragonfly2_tpu.trainer import training

    real = training.train_mlp

    def half(features, labels, **kwargs):
        n = features.shape[0] // 2
        return real(features[:n], labels[:n], **kwargs)

    monkeypatch.setattr(training, "train_mlp", half)
    out = _run(root, "train-round-resident", tmp_path, seconds=0.5)
    assert out["correct"] is False and out["failed"] == 0


def test_fp8_mlp_replay_in_the_programs_place_fails_its_gaps(root):
    cell = cells.load_cell("train-round-resident", root=root)
    cfg = cell.config["trainer"]["mlp"]
    with open(os.path.join(cells.BENCH_DIR, "configs", "resident-ml.json")) as f:
        limits = json.load(f)["limits"]  # the cell's own, not the toy's
    x, y = reference.record_pairs(synth.download_records(256, 5))
    kw = dict(hidden=tuple(cfg["hidden_dims"]), epochs=cfg["epochs"], batch=cfg["batch_size"])
    sound = reference_fits.fit_mlp(x, y, 16, **kw)
    control = reference_fits.fit_mlp(x, y, 16, precision="fp8", **kw)
    assert reference_fits.update_gap(sound["params"], sound) == 0.0
    assert reference_fits.update_gap(sound["start"], sound) == 1.0  # a fit that hands back its start
    held = reference_fits.mlp_holdout_mse(x, y, 16, sound["params"])
    held_fp8 = reference_fits.mlp_holdout_mse(x, y, 16, sound["params"], precision="fp8")
    failed = [
        reference.path_gap(control["history"], sound["history"]) > limits["mlp_loss_path_gap"],
        reference_fits.update_gap(control["params"], sound) > limits["mlp_update_gap"],
        abs(held_fp8 - held) / held > limits["mlp_holdout_mse_gap"],
    ]
    assert any(failed), failed


@pytest.mark.parametrize("model", ["gnn", "gru"])
def test_a_fit_on_half_of_what_it_was_handed_is_not_correct(root, tmp_path, monkeypatch, model):
    """Half the edges, or half the sequences, fitted inside the fit: the
    round still registers three versions with falling losses."""
    from dragonfly2_tpu.trainer import train, training

    toy.force_streaming(monkeypatch)
    if model == "gnn":
        # the cell's own fleet: several steps an epoch, as the cell has
        # (on one step an epoch, half the edges only change which edges)
        toy._edit(os.path.join(root, "benchmarks", "traffic", "rounds-4chunk.json"), hosts=1040)
        real = training.train_gnn

        def half(graph, **kwargs):
            import dataclasses

            n = len(graph.edge_src) // 2
            cut = dataclasses.replace(
                graph, edge_src=graph.edge_src[:n], edge_dst=graph.edge_dst[:n],
                edge_rtt_log_ms=graph.edge_rtt_log_ms[:n],
            )
            return real(cut, **kwargs)

        monkeypatch.setattr(training, "train_gnn", half)
    else:
        real = train.train_gru

        def half(sequences, labels, lengths=None, **kwargs):
            n = sequences.shape[0] // 2
            return real(sequences[:n], labels[:n], lengths=lengths[:n], **kwargs)

        monkeypatch.setattr(train, "train_gru", half)
    out = _run(root, "train-round", tmp_path, seconds=0.5)
    assert out["correct"] is False and out["failed"] == 0


@pytest.mark.parametrize("model", ["gnn", "gru"])
def test_fp8_replay_in_the_programs_place_fails_its_gaps(root, model):
    cell = cells.load_cell("train-round", root=root)
    cfg, limits = cell.config["trainer"][model], cell.config["limits"]
    if model == "gnn":
        topo = synth.topology_records(synth.fleet(64, 5), synth.probe_edges(64, 5, 5, 2))
        kw = dict(hidden=tuple(cfg["hidden_dims"]), epochs=cfg["epochs"], batch=cfg["batch_size"])
        fit, args = reference_fits.fit_gnn, (reference.probe_graph(topo, cfg["max_degree"]),)
    else:
        kw = dict(hidden=cfg["hidden_dims"][0], epochs=cfg["epochs"], batch=cfg["batch_size"])
        fit, args = reference_fits.fit_gru, reference_fits.piece_sequences(synth.download_records(256, 5))
    sound, control = fit(*args, **kw), fit(*args, precision="fp8", **kw)
    assert reference.path_gap(sound["history"], sound["history"]) == 0.0
    assert reference_fits.update_gap(sound["params"], sound) == 0.0
    follow = slice(*cfg["follow_epochs"])
    assert reference.path_gap(control["history"][follow], sound["history"][follow]) > limits[f"{model}_loss_path_gap"]
    assert reference_fits.update_gap(control["params"], sound) > limits[f"{model}_update_gap"]
    assert reference_fits.update_gap(sound["start"], sound) == 1.0  # a fit that hands back its start
    if model == "gnn":
        rows = sound["last_epoch_rows"]
        at_end = reference_fits.gnn_loss_at(args[0], sound["params"], rows)
        # no trajectory between a fit's last epoch and the loss at its end
        assert abs(sound["history"][-1] - at_end) / at_end < 1e-3


def test_fp8_reference_fit_is_worse_than_the_float32_fit():
    recs = synth.download_records(256, 2)
    x, y = reference.record_pairs(recs)
    kw = dict(seed=2, steps=60, batch=256, hidden=(128, 128), learning_rate=3e-3, weight_decay=1e-4)
    sound = reference.mse(reference.fit_mlp(x, y, **kw), x, y)
    control = reference.mse(reference.fit_mlp(x, y, precision="fp8", **kw), x, y)
    assert math.isfinite(sound) and control > sound
