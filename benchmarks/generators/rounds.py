"""Training rounds, back to back: the operator's path.

Set-up builds the trainer as the ``TrainerServer`` binary does (without
``serve()``), stages one scheduler's upload with the storage calls the
Train-stream handler makes, and runs one untimed round that compiles
every fit. The window then runs whole rounds — ``Training.train()``:
MLP (streamed, or resident where the configuration turns streaming
off), GraphSAGE and GRU fits concurrently and three ``create_model``
calls — until ``--seconds`` of round walls have accumulated. A closed loop by nature: a trainer runs one round at a time
for a scheduler. Between rounds the consumed upload is put back by a
hard link, outside the timed walls.
"""

from __future__ import annotations

import dataclasses
import math
import os
import time

import numpy as np

from benchmarks.harness import reference, reference_fits, synth, taps

IP, HOSTNAME = "10.0.0.1", "bench-scheduler"


class Registry:
    """The slice of the manager the trainer calls: keeps what the last
    round registered."""

    def __init__(self):
        self.round: list = []

    def create_model(self, model_id, model_type, ip, hostname, params, evaluation):
        self.round.append((model_type, params, dict(evaluation)))


def check_config(training, cfg: dict) -> None:
    """The configuration file states what is run: refuse to measure if
    the server's own defaults have drifted from it."""
    c = training.config
    have = {
        "mlp.hidden_dims": list(c.mlp.hidden_dims),
        "mlp.batch_size": c.mlp.batch_size,
        "mlp.learning_rate": c.mlp.learning_rate,
        "mlp.weight_decay": c.mlp.weight_decay,
        "mlp.epochs": c.mlp.epochs,
        "mlp.streaming": c.streaming,
        "mlp.streaming_passes": c.streaming_passes,
        "mlp.streaming_workers": c.streaming_workers,
        "mlp.streaming_steps_per_call": c.streaming_steps_per_call,
        "gnn.hidden_dims": list(c.gnn.hidden_dims),
        "gnn.epochs": c.gnn.epochs,
        "gnn.max_degree": c.gnn_max_degree,
        "gnn.batch_size": c.gnn.batch_size,
        "gnn.learning_rate": c.gnn.learning_rate,
        "gnn.weight_decay": c.gnn.weight_decay,
        "gru.hidden_dims": list(c.gru_config.hidden_dims),
        "gru.batch_size": c.gru_config.batch_size,
        "gru.epochs": c.gru_config.epochs,
        "gru.learning_rate": c.gru_config.learning_rate,
        "gru.weight_decay": c.gru_config.weight_decay,
        "gru.max_sequences": c.gru_max_sequences,
    }
    for key, value in have.items():
        group, _, name = key.partition(".")
        want = cfg["trainer"][group][name]
        if want != value:
            raise SystemExit(f"configuration drift: {key} is {value!r}, file says {want!r}")


def decreased(history) -> bool:
    """Finite throughout, and lower over the last tenth than the first
    (a history of one epoch has only to be finite: its replay holds it)."""
    h = np.asarray(history, np.float64)
    if h.size < 1 or not np.isfinite(h).all():
        return False
    if h.size == 1:
        return True
    k = max(1, h.size // 10)
    return float(h[-k:].mean()) < float(h[:k].mean())


class Stage:
    """One scheduler's upload in trainer storage, and its re-staging."""

    def __init__(self, storage, host_id: str, traffic: dict, seed: int):
        from dragonfly2_tpu.schema import wire

        self.storage, self.host_id = storage, host_id
        self.records = synth.download_records(traffic["body_records"], seed)
        rpb = self.block_records = wire.BLOCK_RECORDS
        group = b"".join(
            wire.encode_train_block(self.records[i : i + rpb])
            for i in range(0, len(self.records), rpb)
        )
        chunk = group * traffic["body_repeats_per_chunk"]
        for _ in range(traffic["chunks"]):
            storage.append_download_blocks(host_id, chunk)
        self.chunk_bytes = len(chunk)
        self.records_per_round = (
            len(self.records) * traffic["body_repeats_per_chunk"] * traffic["chunks"]
        )
        hosts = synth.fleet(traffic["hosts"], seed)
        self.edges = synth.probe_edges(
            traffic["hosts"], seed, traffic["probe_fan_out"], traffic["probe_rounds"]
        )
        topo = self.topology = synth.topology_records(hosts, self.edges)
        for i in range(0, len(topo), rpb):
            storage.append_network_topology_blocks(
                host_id, wire.encode_topology_block(topo[i : i + rpb])
            )
        storage.mark_download_round(host_id)
        self.files = [
            storage.download_blocks_path(host_id),
            storage.network_topology_blocks_path(host_id),
        ]
        for path in self.files:
            os.link(path, str(path) + ".staged")

    def restage(self) -> None:
        """Put back what a round consumed: a link, not a rewrite."""
        for path in self.files:
            if not path.exists():
                os.link(str(path) + ".staged", path)
        self.storage.mark_download_round(self.host_id)


def _path_by_epochs(name: str, got: list, want) -> None:
    for lo in range(0, len(want), max(len(want) // 4, 1)):
        hi = min(lo + max(len(want) // 4, 1), len(want))
        print(f"{name} loss path epochs {lo}-{hi}: gap {reference.path_gap(got[lo:hi], want[lo:hi])!r}", flush=True)


def _hold_fit(name: str, last: dict, ref: dict, follow: list, limits: dict) -> list:
    """A fit's history, over the epochs ``follow`` = [from, to), and its
    registered parameters against its replay."""
    got = last[f"{name}_losses"]
    _path_by_epochs(name, got, ref["history"])
    lo, hi = follow
    path = reference.path_gap(got[lo:hi], ref["history"][lo:hi]) if len(got) >= hi > lo else math.inf
    params = last["params"].get(name)
    update = reference_fits.update_gap(params, ref) if params is not None else math.inf
    print(f"{name} replay: {ref['steps']} steps of {ref['batch']}, loss {ref['history'][0]:.6f} -> {ref['history'][-1]:.6f}", flush=True)
    return [
        (f"{name}_loss_path_gap", path, limits[f"{name}_loss_path_gap"]),
        (f"{name}_update_gap", update, limits[f"{name}_update_gap"]),
    ]


def _hold_gnn(good: list, last: dict, fed: dict, topology: list, cfg: dict, limits: dict) -> list:
    """Every round's GraphSAGE fit was handed the whole staged graph; the
    last round's graph, loss path and registered parameters against the
    plain graph build and replay."""
    graph = reference.probe_graph(topology, cfg["max_degree"])
    want = (graph["features"].shape[0], len(graph["src"]))
    gap = float(sum(abs(r["gnn_nodes_edges"][0] - want[0]) + abs(r["gnn_nodes_edges"][1] - want[1]) for r in good))
    g = fed.get("gnn")
    gap += math.inf if g is None else reference_fits.mismatches(
        (g.node_features, graph["features"]), (g.edge_src, graph["src"]), (g.edge_dst, graph["dst"]),
        (g.edge_rtt_log_ms, graph["rtt_log"]), (g.neighbors, graph["neighbors"]),
        (g.neighbor_mask, graph["mask"]),
    )
    ref = reference_fits.fit_gnn(
        graph, hidden=tuple(cfg["hidden_dims"]), epochs=cfg["epochs"], batch=cfg["batch_size"],
        learning_rate=cfg["learning_rate"], weight_decay=cfg["weight_decay"],
    )
    # the last epoch's mean loss against the float32 loss of the
    # registered parameters over that epoch's stated batches: no
    # trajectory between the two, so it is steady where the path is not
    params, got = last["params"].get("gnn"), last["gnn_losses"]
    end = math.inf
    if params is not None and got:
        want = reference_fits.gnn_loss_at(graph, params, ref["last_epoch_rows"])
        end = abs(got[-1] - want) / want
        print(f"gnn end loss: program {got[-1]!r} float32 at its registered parameters {want!r}", flush=True)
    return (
        [("gnn_graph_gap", gap, 0.0)]
        + _hold_fit("gnn", last, ref, cfg["follow_epochs"], limits)
        + [("gnn_end_loss_gap", end, limits["gnn_end_loss_gap"])]
    )


def _hold_gru(good: list, last: dict, fed: dict, records: list, repeats: int, cfg: dict, limits: dict) -> list:
    """Every round's GRU fit was handed the newest sequences of the whole
    upload, up to its cap; the last round's sequences, loss path and
    registered parameters against the plain extraction and replay."""
    seqs, labels, lengths = (
        reference_fits.newest(a, repeats, cfg["max_sequences"])
        for a in reference_fits.piece_sequences(records)
    )
    gap = float(sum(abs(r["gru_sequences"] - seqs.shape[0]) for r in good))
    got = fed.get("gru")
    gap += math.inf if got is None else reference_fits.mismatches(
        (got[0], seqs), (got[1], labels), (got[2], lengths)
    )
    ref = reference_fits.fit_gru(
        seqs, labels, lengths, hidden=cfg["hidden_dims"][0], epochs=cfg["epochs"],
        batch=cfg["batch_size"], learning_rate=cfg["learning_rate"], weight_decay=cfg["weight_decay"],
    )
    return [("gru_sequences_gap", gap, 0.0)] + _hold_fit("gru", last, ref, cfg["follow_epochs"], limits)


def _hold_resident_mlp(good: list, last: dict, fed: dict, records: list, repeats: int, cfg: dict, limits: dict) -> list:
    """Every round's resident MLP fit was handed every pair of the whole
    upload; the last round's rows, its loss per epoch, its registered
    parameters and the holdout error it registered against the plain
    pairs and their replay."""
    x, y = reference.record_pairs(records)
    want_pairs = x.shape[0] * repeats
    gap = float(sum(abs(r["mlp_pairs"] - want_pairs) for r in good))
    got = fed.get("mlp")
    rows = math.inf
    if got is not None and got[0].shape == (want_pairs, x.shape[1]):
        rows = reference_fits.mismatches(
            (got[0].reshape(repeats, *x.shape), np.broadcast_to(x, (repeats, *x.shape))),
            (got[1].reshape(repeats, -1), np.broadcast_to(y, (repeats, y.shape[0]))),
        )
    ref = reference_fits.fit_mlp(
        x, y, repeats, hidden=tuple(cfg["hidden_dims"]), epochs=cfg["epochs"], batch=cfg["batch_size"],
        learning_rate=cfg["learning_rate"], weight_decay=cfg["weight_decay"],
    )
    params, registered = last["params"].get("mlp"), last["evaluations"].get("mlp", {})
    held = math.inf
    if params is not None and "mse" in registered:
        want = reference_fits.mlp_holdout_mse(x, y, repeats, params)
        held = abs(registered["mse"] - want) / want
        print(f"mlp holdout mse: registered {registered['mse']!r} float32 at its registered parameters {want!r}", flush=True)
    return (
        [("mlp_pairs_gap", gap, 0.0), ("mlp_rows_mismatch", rows, 0.0)]
        + _hold_fit("mlp", last, ref, [0, cfg["epochs"]], limits)
        + [("mlp_holdout_mse_gap", held, limits["mlp_holdout_mse_gap"])]
    )


def run(ctx) -> dict:
    from dragonfly2_tpu.trainer import ingest as ingest_mod
    from dragonfly2_tpu.trainer import train as train_mod
    from dragonfly2_tpu.trainer import training as training_mod
    from dragonfly2_tpu.trainer.server import TrainerServer, TrainerServerConfig
    from dragonfly2_tpu.utils.idgen import host_id_v2

    cell, traffic = ctx.cell, ctx.cell.traffic
    ctx.marks["imports_and_chip"] = time.perf_counter()
    want = cell.config["trainer"]
    streaming = want["mlp"]["streaming"]
    # the knobs the server's own config exposes come from the file; the
    # rest are its defaults, which check_config holds the file to
    srv = TrainerServer(
        TrainerServerConfig(
            data_dir=os.path.join(ctx.workdir, "trainer"),
            mlp_epochs=want["mlp"]["epochs"],
            mlp_batch_size=want["mlp"]["batch_size"],
            gnn_epochs=want["gnn"]["epochs"],
            streaming=streaming,
            streaming_workers=want["mlp"]["streaming_workers"],
        )
    )
    training = srv.training
    check_config(training, cell.config)
    n_dev = len(ctx.devices)
    mesh = training.mesh
    if (mesh is None) != (n_dev == 1) or (mesh is not None and dict(mesh.shape) != {"dp": n_dev}):
        raise SystemExit(f"fit mesh {mesh} does not span the {n_dev} chip(s) of the cell")
    registry = Registry()
    training.manager_client = registry
    host_id = host_id_v2(IP, HOSTNAME)
    stage = Stage(srv.storage, host_id, traffic, ctx.seed)
    # how often a round goes over its records: the streamed fit's passes,
    # or the resident fit's epochs
    passes = training.config.streaming_passes if streaming else training.config.mlp.epochs
    ctx.marks["staged"] = time.perf_counter()

    streamed, resident, gnn, gru = [], [], [], []
    rounds: list = []  # one dict per timed round
    # what the last round's fits were handed: the resident MLP fit (the
    # pairs and their labels), the GraphSAGE fit (the graph) and the GRU
    # fit (the sequences, their labels and lengths)
    fed: dict = {}

    def one_round() -> dict:
        registry.round = []
        del streamed[:], resident[:], gnn[:], gru[:]
        fed.clear()  # last round's arrays go before this round's are made
        before = taps.prom_series()
        t0 = time.perf_counter()
        outcome = training.train(IP, HOSTNAME)
        wall = time.perf_counter() - t0
        moved = taps.series_delta(before, taps.prom_series())
        fits = {
            m: sum(
                v for k, v in moved.items()
                if "trainer_fit_duration_seconds_sum" in k and f'"{m}"' in k
            )
            for m in ("mlp", "gnn", "gru")
        }
        stats = streamed[0][1] if streamed else None
        mlp_fit = resident[0][2] if resident else None
        gnn_fit = gnn[0][2] if gnn else None
        gru_fit = gru[0][2] if gru else None
        if resident:
            fed["mlp"] = resident[0][0][:2]
        if gnn:
            fed["gnn"] = gnn[0][0][0]
        if gru:
            fed["gru"] = (*gru[0][0][:2], gru[0][1].get("lengths"))
        mlp_losses = list(stats.losses) if stats is not None else list(mlp_fit.history) if mlp_fit is not None else []
        faults = [
            name
            for name, sound in (
                (f"outcome {outcome!r}", outcome.ok and outcome.gru_error is None),
                ("three versions registered", sorted(t for t, _, _ in registry.round) == ["gnn", "gru", "mlp"]),
                ("mlp fit streamed" if streaming else "mlp fit resident",
                 (stats is not None and mlp_fit is None) if streaming else (mlp_fit is not None and stats is None)),
                ("mlp loss finite and lower", decreased(mlp_losses)),
                ("gnn loss finite and lower", gnn_fit is not None and decreased(gnn_fit.history)),
                ("gru loss finite and lower", gru_fit is not None and decreased(gru_fit.history)),
            )
            if not sound
        ]
        ok = not faults
        return {
            "wall_s": wall,
            "ok": ok,
            "outcome": "; ".join(faults),
            "fits": fits,
            "stats": None if stats is None else {
                f.name: getattr(stats, f.name)
                for f in dataclasses.fields(stats)
                if f.name not in ("losses", "metrics", "feed_devices")
            },
            "feed_devices": [] if stats is None else list(stats.feed_devices),
            "params": {t: p for t, p, _ in registry.round},
            "evaluations": {t: e for t, _, e in registry.round},
            "mlp_losses": mlp_losses,
            "mlp_pairs": fed["mlp"][0].shape[0] if resident else 0,
            "gnn_losses": [] if gnn_fit is None else list(gnn_fit.history),
            "gru_losses": [] if gru_fit is None else list(gru_fit.history),
            "gnn_nodes_edges": (fed["gnn"].num_nodes, len(fed["gnn"].edge_src)) if gnn else (0, 0),
            "gru_sequences": fed["gru"][0].shape[0] if gru else 0,
        }

    with (
        taps.spy(ingest_mod, "stream_train_mlp", streamed),
        taps.spy(training_mod, "train_mlp", resident, with_args=True),
        taps.spy(training_mod, "train_gnn", gnn, with_args=True),
        taps.spy(train_mod, "train_gru", gru, with_args=True),
    ):
        warm = one_round()  # compiles every fit; not timed
        if not warm["ok"]:
            raise SystemExit(f"the warm-up round failed: {warm['outcome']}")
        ctx.marks["warm_up_round"] = time.perf_counter()
        stage.restage()
        ctx.window_opens()
        # a traced run records two seconds from the start of the first
        # round, before the GRU fit reaches the chip: the profiler takes
        # minutes to write out a stretch that holds its 70,000 scan steps
        tracer_thread = ctx.tracer.record_later(traffic["trace_from_s"], traffic["trace_seconds"])
        spent = 0.0
        while spent < ctx.seconds:
            r = one_round()
            rounds.append(r)
            spent += r["wall_s"]
            stage.restage()
        if tracer_thread is not None:
            tracer_thread.join()
        ctx.window_closes()

    good = [r for r in rounds if r["ok"]]
    records_per_round, chunk_bytes = stage.records_per_round, stage.chunk_bytes
    # the resident fit's count is held to the upload by mlp_pairs_gap
    consumed = sum(r["stats"]["download_records"] if streaming else records_per_round * passes for r in good)
    metrics = {
        "train_records_per_s": consumed / sum(r["wall_s"] for r in good) if good else 0.0
    }
    probes = {"fit_duration": {m: [r["fits"][m] for r in good] for m in ("mlp", "gnn", "gru")}}
    if streaming:
        probes["stream_stats"] = [r["stats"] for r in good]
    last = rounds[-1]
    block_records = stage.block_records
    records, batch = stage.records, training.config.mlp.batch_size
    topology, trainer_cfg, limits = stage.topology, cell.config["trainer"], cell.config["limits"]
    body_repeats = traffic["body_repeats_per_chunk"] * traffic["chunks"]
    hidden = tuple(training.config.mlp.hidden_dims)
    lr, wd = training.config.mlp.learning_rate, training.config.mlp.weight_decay
    eval_every = max(2, round(1.0 / training.config.mlp.eval_fraction))
    feed_ok = ctx.devices[0].platform != "tpu" or (
        all(
            len(r["feed_devices"]) == n_dev and all("tpu" in d.lower() for d in r["feed_devices"])
            for r in good
        )
        if streaming
        else all(
            d.platform == "tpu"
            for fit in resident
            for leaf in _leaves(fit[2].params)
            for d in leaf.devices()
        )
    )
    del srv, training, registry, stage, resident[:]

    def streamed_mlp() -> list:
        x, y = reference.record_pairs(records)
        block_pairs = x.shape[0] * block_records // len(records)
        pairs_per_pass = x.shape[0] * records_per_round // len(records)
        want_records = records_per_round * passes
        want_pairs = pairs_per_pass * passes
        checks = [
            ("records_gap", float(max(abs(r["stats"]["download_records"] - want_records) for r in good)) if good else math.inf, 0.0),
            ("pairs_gap", float(max(abs(r["stats"]["pairs"] - want_pairs) for r in good)) if good else math.inf, 0.0),
        ]
        rows_x, rows_y, bias = reference.staged_rows(x, y, block_pairs, eval_every)
        kw = dict(batch=batch, hidden=hidden, learning_rate=lr, weight_decay=wd, pad_to=x.shape[0])
        follow = min(traffic["follow_steps"], len(last["mlp_losses"]))
        want = reference.replay_losses(rows_x, rows_y, bias, steps=follow, **kw)
        for lo, hi in ((0, 64), (64, 128), (128, 256), (256, follow)):
            hi = min(hi, follow)
            if hi > lo:
                print(
                    f"loss path steps {lo}-{hi}: gap"
                    f" {reference.path_gap(last['mlp_losses'][lo:hi], want[lo:hi])!r}",
                    flush=True,
                )
        checks.append(
            ("mlp_loss_path_gap", reference.path_gap(last["mlp_losses"][:follow], want) if follow else math.inf,
             limits["mlp_loss_path_gap"])
        )
        if last["params"].get("mlp") is not None:
            steps = reference.fit_steps(pairs_per_pass, passes, batch, eval_every)
            ref = reference.fit_mlp(
                x, y, seed=ctx.seed, steps=steps, batch=batch, hidden=hidden,
                learning_rate=lr, weight_decay=wd,
            )
            got = {"layers": [{k: np.asarray(v, np.float32) for k, v in l.items()} for l in last["params"]["mlp"]["layers"]]}
            mse_prog, mse_ref = reference.mse(got, x, y), reference.mse(ref, x, y)
            print(f"reference fit: mse program {mse_prog:.6f} reference {mse_ref:.6f} over {x.shape[0]} pairs, {steps} steps", flush=True)
            checks.append(("mlp_mse_log_ratio", abs(math.log(mse_prog / mse_ref)), limits["mlp_mse_log_ratio"]))
        else:
            checks.append(("mlp_mse_log_ratio", math.inf, limits["mlp_mse_log_ratio"]))
        return checks

    def after_window() -> list:
        """The comparison with the plain reference, once the program's
        state is freed."""
        checks = [
            ("rounds_failed", float(len(rounds) - len(good)), 0.0),
            ("feed_off_chip", 0.0 if feed_ok else 1.0, 0.0),
        ]
        if streaming:
            checks.extend(streamed_mlp())
        else:
            checks.extend(_hold_resident_mlp(good, last, fed, records, body_repeats, trainer_cfg["mlp"], limits))
        checks.extend(_hold_gnn(good, last, fed, topology, trainer_cfg["gnn"], limits))
        checks.extend(_hold_gru(good, last, fed, records, body_repeats, trainer_cfg["gru"], limits))
        return checks

    return {
        "metrics": metrics,
        "probes": probes,
        "attempted": len(rounds),
        "failed": len(rounds) - len(good),
        "after_window": after_window,
        "notes": {
            "rounds": len(rounds),
            "round_walls_s": [round(r["wall_s"], 4) for r in rounds],
            "records_per_round": records_per_round,
            "chunk_mib": round(chunk_bytes / (1 << 20), 2),
        },
    }


def _leaves(tree) -> list:
    import jax

    return [leaf for leaf in jax.tree_util.tree_leaves(tree) if hasattr(leaf, "devices")]
