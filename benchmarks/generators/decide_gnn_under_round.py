"""``decide_under_round`` with the GraphSAGE scorer in the batched serving
slot: the one-chip cluster serving the north-star model beside the round
that fits it.

Everything ``decide_under_round`` does is done here by the same pieces
(its ``build``, ``Beside``, ``ResidentFits``, ``hold_mlp``, the staged
upload of ``rounds``, the swarm and arrivals of ``open_loop_decisions``);
what differs is the model that ranks. The registry stand-in activates the
newest GraphSAGE version as soon as it is registered, as it does the
MLP's (the configuration's ``assumed``: an operator's policy), so every
``refresh_once()`` after a round installs that round's GraphSAGE version
in the scoring service, its embeddings computed at the swap from the
scheduler's live probe graph, and the round's MLP version as the per-call
rung under it.

**Two graphs, two orders.** The upload names its hosts source by source
(``synth.topology_records``: host 0 and its ten targets, host 1, ...),
and the fit learns one row a host in that order. The scheduler's engine
is filled here as probes reach a scheduler, in time order and not grouped
by source: the same edges in a permutation drawn from the seed
(``fill_topology``). Its export walks sources in the order of their first
probe and keeps each source's five freshest targets (the record's width),
so the graph an install embeds has the upload's hosts in another order
and half its edges. Set-up refuses to measure if the two orders agree:
the cell could then not see a row served to the wrong host.

``correct``: every check of ``decide_under_round`` that does not name the
served model, and for the decisions ``open_loop_decisions.judge`` with
``GnnReference`` at the float32 weights of the version in force when each
was scored, embedded on the records that version's install read, its
learned rows placed here by host id through the reference's own graph
build (``placed_weights``; nothing of the program's placement is read).
Every version, the warm-up round's too, is first held to the float32
replay of the GraphSAGE fit (``hold_gnn``), and the host ids it carries
to the reference's order of the upload. After every install each host's
row in the installed scorer is compared with the row fitted for its id
(``gnn_rows_misplaced``).
"""

from __future__ import annotations

import gc
import math
import sys
import time

import numpy as np

from benchmarks.generators import decide_under_round as rud
from benchmarks.generators import open_loop_decisions as old
from benchmarks.generators import rounds
from benchmarks.harness import reference, reference_fits
from benchmarks.harness import swarm as swarm_mod
from benchmarks.harness import taps
from benchmarks.harness.layer_readers import percentile

INSTALL_SERIES = 'dragonfly_scheduler_gnn_install_total{result="%s"}'
ROWS_SERIES = 'dragonfly_scheduler_gnn_rows_total{row="%s"}'


class Registry(rud.Registry):
    """``decide_under_round.Registry`` with the GraphSAGE versions kept
    and the newest active beside the newest MLP's; the GRU's stay
    inactive. Weights leave as the manager's client serializes them."""

    def __init__(self):
        super().__init__()
        self.gnn: list = []  # every GraphSAGE version ever registered: (model id, params)

    def create_model(self, model_id, model_type, ip, hostname, params, evaluation):
        super().create_model(model_id, model_type, ip, hostname, params, evaluation)
        if model_type == "gnn":
            self.gnn.append((model_id, params))

    def ListModels(self, request):
        import manager_pb2

        resp = super().ListModels(request)
        if self.gnn:
            version = len(self.gnn)
            resp.models.append(
                manager_pb2.Model(
                    model_id=self.gnn[-1][0], type="gnn", version=version, state="active",
                    created_at_ns=version, updated_at_ns=version,
                )
            )
        return resp

    def GetModelWeights(self, request):
        import manager_pb2

        from dragonfly2_tpu.trainer.serving import serialize_params

        if self.gnn and request.model_id == self.gnn[-1][0]:
            model_id, params = self.gnn[request.version - 1]
            return manager_pb2.ModelWeights(
                model_id=model_id, version=request.version, type="gnn", weights=serialize_params(params)
            )
        return super().GetModelWeights(request)


def build(ctx):
    """``decide_under_round.build`` with this module's registry."""
    trainer, scheduler, _, refresher = rud.build(ctx)
    registry = Registry()
    trainer.training.manager_client = registry
    refresher.manager = registry
    return trainer, scheduler, registry, refresher


def check_config(trainer, scheduler, cfg: dict) -> None:
    """``decide_under_round.check_config`` for a file whose served model
    is the GraphSAGE scorer, and what only that model states."""
    from dragonfly2_tpu.schema.records import MAX_DEST_HOSTS
    from dragonfly2_tpu.trainer.serving import BUCKET_LADDER, node_capacity

    rounds.check_config(trainer.training, cfg)
    old.check_config(scheduler, cfg)
    s, g = cfg["scheduler"], cfg["served_gnn"]
    for key, have, want in (
        ("algorithm", scheduler.cfg.algorithm, s["algorithm"]),
        ("serving_ladder", list(BUCKET_LADDER), s["serving_ladder"]),
        ("served_model", "gnn", cfg["served_model"]),  # the registry activates GraphSAGE versions
        ("switch_interval_ms", round(sys.getswitchinterval() * 1e3, 6), cfg["interpreter"]["switch_interval_ms"]),
        ("served_gnn.node_capacity", node_capacity(cfg["scale"]["hosts"]), g["node_capacity"]),
        ("served_gnn.export_dests_per_source", MAX_DEST_HOSTS, g["export_dests_per_source"]),
    ):
        if have != want:
            raise SystemExit(f"configuration drift: {key} is {have!r}, file says {want!r}")


def fill_topology(engine, desc: dict, seed: int) -> None:
    """The probe graph into the engine as probes reach a scheduler: the
    description's edges (already averaged) in an order of arrival drawn
    from the seed, then one flush."""
    now = time.time()
    ids = [h.id for h in desc["hosts"]]
    for k in np.random.default_rng([seed, 15]).permutation(len(desc["edges"])):
        s, t, rtt_ns = desc["edges"][int(k)]
        engine.adopt(ids[s], ids[t], float(rtt_ns), now)
    engine.flush()


def setup(ctx):
    """``decide_under_round.setup`` with the engine filled in arrival
    order; shared with the sweep."""
    from dragonfly2_tpu import colocated  # noqa: F401
    from dragonfly2_tpu.scheduler.model_refresher import _serving_rungs
    from dragonfly2_tpu.utils.idgen import host_id_v2

    cell, traffic = ctx.cell, ctx.cell.traffic
    ctx.marks["imports_and_chip"] = time.perf_counter()
    trainer, scheduler, registry, refresher = build(ctx)
    check_config(trainer, scheduler, cell.config)
    if trainer.training.mesh is not None or len(ctx.devices) != 1:
        raise SystemExit(f"fit mesh {trainer.training.mesh}: the colocated cell runs on one chip")
    stage = rounds.Stage(trainer.storage, host_id_v2(rounds.IP, rounds.HOSTNAME), traffic, ctx.seed)
    ctx.marks["staged"] = time.perf_counter()
    desc = swarm_mod.describe(traffic, ctx.seed)
    gc.freeze()  # the harness's own description, out of the collector's reach (decide_under_round.setup)
    children = old.build_live(desc, scheduler)
    fill_topology(scheduler.topology_engine, desc, ctx.seed)
    scheduler.scoring_service.start()
    ids = [h.id for h in desc["hosts"]]
    for rows in _serving_rungs(scheduler.scoring_service):
        if rows <= 2 * cell.config["scheduler"]["filter_parent_limit"]:
            scheduler.topology_engine.rtt_affinity_pairs([ids[0]] * rows, ids[1 : rows + 1])
    ctx.marks["swarm"] = time.perf_counter()
    return trainer, scheduler, registry, refresher, stage, desc, children


def host_weights(params: dict) -> dict:
    """The float32 host copy of a GraphSAGE version as the reference
    reads it, and the host ids its rows were fitted for."""
    return {
        "sage": [{k: np.asarray(v, np.float32) for k, v in l.items()} for l in params["sage"]],
        "head": rud.host_weights(params["head"]),
        "node_embed": np.asarray(params["node_embed"], np.float32),
        "node_ids": list(params.get("node_ids") or ()),
    }


def placed_weights(weights: dict, fitted_order: dict, records: list, by: str = "id") -> dict:
    """``weights`` for ``GnnReference`` over the graph of ``records``:
    the learned row of every node of that graph, in the reference's own
    node order, taken from the row ``fitted_order`` (host id → row, the
    reference's order of the upload) gives its id; a host the fit never
    saw gets the zero row. ``by="position"`` is the control: row ``i`` of
    the fitted table to node ``i`` of this graph."""
    order = reference.probe_graph(records)["order"]
    embed = np.zeros((len(order), weights["node_embed"].shape[1]), np.float32)
    for hid, i in order.items():
        j = fitted_order.get(hid) if by == "id" else (i if i < len(weights["node_embed"]) else None)
        if j is not None:
            embed[i] = weights["node_embed"][j]
    return {"sage": weights["sage"], "head": weights["head"], "node_embed": embed}


def rows_misplaced(installed: dict, weights: dict, fitted_order: dict) -> int:
    """Hosts of an installed scorer whose learned row is not the one
    fitted for their id (the zero row for a host the fit never saw)."""
    zero = np.zeros(weights["node_embed"].shape[1], np.float32)
    wrong = 0
    for hid, row in installed.items():
        j = fitted_order.get(hid)
        wrong += not np.array_equal(row, zero if j is None else weights["node_embed"][j])
    return wrong


def hold_gnn(fits: list, good: list, fed: dict, topology: list, cfg: dict, limits: dict) -> list:
    """What ``rounds._hold_gnn`` holds the last round's GraphSAGE fit to,
    by the same primitives, and the worst of its three gaps over every
    round ``fits`` whose version ranked decisions, the warm-up round's
    too (``gnn_versions_*``); and every version's host ids against the
    reference's order of the upload's hosts."""
    graph = reference.probe_graph(topology, cfg["max_degree"])
    want = (graph["features"].shape[0], len(graph["src"]))
    gap = float(sum(abs(r["gnn_nodes_edges"][0] - want[0]) + abs(r["gnn_nodes_edges"][1] - want[1]) for r in good))
    g = fed.get("gnn")
    gap += math.inf if g is None else reference_fits.mismatches(
        (g.node_features, graph["features"]), (g.edge_src, graph["src"]), (g.edge_dst, graph["dst"]),
        (g.edge_rtt_log_ms, graph["rtt_log"]), (g.neighbors, graph["neighbors"]), (g.neighbor_mask, graph["mask"]),
    )
    ref = reference_fits.fit_gnn(
        graph, hidden=tuple(cfg["hidden_dims"]), epochs=cfg["epochs"], batch=cfg["batch_size"],
        learning_rate=cfg["learning_rate"], weight_decay=cfg["weight_decay"],
    )
    ids = list(graph["order"])
    per_round, ids_gap = [], 0.0
    for r in fits:
        (_, path, _), (_, update, _) = rounds._hold_fit("gnn", r, ref, cfg["follow_epochs"], limits)
        params, got, end = r["params"].get("gnn"), r["gnn_losses"], math.inf
        if params is not None and got:
            at = reference_fits.gnn_loss_at(graph, {k: v for k, v in params.items() if k != "node_ids"}, ref["last_epoch_rows"])
            end = abs(got[-1] - at) / at
        per_round.append({"loss_path": path, "update": update, "end_loss": end})
        ids_gap += math.inf if params is None else float(list(params.get("node_ids") or ()) != ids)
    return (
        [("gnn_graph_gap", gap, 0.0), ("gnn_node_ids_gap", ids_gap, 0.0)]
        + [(f"gnn_{k}_gap", v, limits[f"gnn_{k}_gap"]) for k, v in per_round[-1].items()]
        + [(f"gnn_versions_{k}_gap", max(p[k] for p in per_round), limits[f"gnn_{k}_gap"]) for k in per_round[-1]]
    )


def run(ctx) -> dict:
    from dragonfly2_tpu.trainer import train as train_mod
    from dragonfly2_tpu.trainer import training as training_mod

    cell, traffic = ctx.cell, ctx.cell.traffic
    rate = float(cell.params["rate_per_s"])
    trainer, scheduler, registry, refresher, stage, desc, children = setup(ctx)
    training, svc = trainer.training, scheduler.scoring_service

    resident = rud.ResidentFits(training_mod)
    gnn, gru = [], []
    fed: dict = {}  # what the GraphSAGE and GRU fits of the last round were handed

    def one_round() -> dict:
        """``decide_under_round.run``'s round."""
        registry.round = []
        del gnn[:], gru[:]
        fed.clear()
        fits_before = len(resident.calls)
        before = taps.prom_series()
        t0 = time.perf_counter()
        outcome = training.train(rounds.IP, rounds.HOSTNAME)
        wall = time.perf_counter() - t0
        moved = taps.series_delta(before, taps.prom_series())
        mine = resident.calls[fits_before:]
        mlp_fit = mine[0][3] if mine else None
        gnn_fit = gnn[0][2] if gnn else None
        gru_fit = gru[0][2] if gru else None
        if gnn:
            fed["gnn"] = gnn[0][0][0]
        if gru:
            fed["gru"] = (*gru[0][0][:2], gru[0][1].get("lengths"))
        faults = [
            name
            for name, sound in (
                (f"outcome {outcome!r}", outcome.ok and outcome.gru_error is None),
                ("three versions registered", sorted(t for t, _, _ in registry.round) == ["gnn", "gru", "mlp"]),
                ("mlp fit resident", mlp_fit is not None),
                ("mlp loss finite and lower", mlp_fit is not None and rounds.decreased(mlp_fit.history)),
                ("gnn loss finite and lower", gnn_fit is not None and rounds.decreased(gnn_fit.history)),
                ("gru loss finite and lower", gru_fit is not None and rounds.decreased(gru_fit.history)),
            )
            if not sound
        ]
        return {
            "began": t0,
            "wall_s": wall,
            "ok": not faults,
            "outcome": "; ".join(faults),
            "fits": {
                m: sum(v for k, v in moved.items() if "trainer_fit_duration_seconds_sum" in k and f'"{m}"' in k)
                for m in ("mlp", "gnn", "gru")
            },
            "params": {t: p for t, p, _ in registry.round},
            "evaluations": {t: e for t, _, e in registry.round},
            "mlp_losses": [] if mlp_fit is None else list(mlp_fit.history),
            "mlp_pairs": mine[0][0] if mine else 0,
            "gnn_losses": [] if gnn_fit is None else list(gnn_fit.history),
            "gru_losses": [] if gru_fit is None else list(gru_fit.history),
            "gnn_nodes_edges": (fed["gnn"].num_nodes, len(fed["gnn"].edge_src)) if gnn else (0, 0),
            "gru_sequences": fed["gru"][0].shape[0] if gru else 0,
        }

    # what every install read and built, kept for the comparison: the
    # records its export returned and the scorer it put in the slot
    exported: list = []
    scorers: list = []
    real_export, real_build = scheduler.networktopology.export_records, refresher._build_gnn_scorer

    def export_records(*args, **kwargs):
        exported.append(real_export(*args, **kwargs))
        return exported[-1]

    def build_gnn_scorer(params):
        scorers.append(real_build(params))
        return scorers[-1]

    scheduler.networktopology.export_records = export_records
    refresher._build_gnn_scorer = build_gnn_scorer

    installs: list = []  # (began, ended, version) of the window's, on perf_counter's clock
    read_by: dict = {}  # GraphSAGE version -> (the records its install read, host id -> the row installed)
    install_faults: list = []

    def install() -> None:
        """One refresher round: the newest GraphSAGE version into the
        scoring service and the newest MLP version under it, every rung
        of both warmed first."""
        version, n_exports, n_scorers = len(registry.gnn), len(exported), len(scorers)
        began = time.perf_counter()
        installed = refresher.refresh_once()
        installs.append((began, time.perf_counter(), version))
        want = f"{registry.gnn[-1][0]}/v{version}"
        snap = svc.snapshot()
        if (
            not installed or snap["model_version"] != want or snap["model_kind"] != "gnn"
            or len(exported) != n_exports + 1 or len(scorers) != n_scorers + 1 or scorers[-1] is None
            or refresher.loaded_version != (registry.mlp[-1][0], len(registry.mlp))
        ):
            install_faults.append(
                f"refresh_once {installed}, serving {snap['model_kind']} {snap['model_version']!r}, registry {want!r},"
                f" mlp under it {refresher.loaded_version}"
            )
            return
        read_by[version] = (exported[-1], scorers[-1].node_rows())

    def decisions(seed: int) -> rud.Beside:
        due, task_idx, child_idx = swarm_mod.arrivals(traffic, seed, rate, traffic["horizon_seconds"])
        return rud.Beside(scheduler.scheduling, children, due, task_idx, child_idx, traffic["workers"])

    def rungs() -> dict:
        series = taps.prom_series()
        return {r: series.get(rud.RUNG_SERIES % r, 0.0) for r in ("serving", "mlp", "base")}

    phase_names = sorted(
        {m["reader"]["phase"] for m in cell.per_layer if m["reader"]["kind"] == "prof_phase"}
        | {"scheduler.gnn_export", "scheduler.gnn_graph_build", "scheduler.gnn_embed", "scheduler.gnn_install"}
    )
    timed: list = []  # one dict per timed round
    series0 = taps.prom_series()
    with (
        resident,
        taps.spy(training_mod, "train_gnn", gnn, with_args=True),
        taps.spy(train_mod, "train_gru", gru, with_args=True),
    ):
        # the warm-up: one round with the decisions already arriving (no
        # version yet: the base evaluator ranks them), its versions
        # installed (the embed and every rung's edge head compile here),
        # then decisions ranked by its GraphSAGE version
        warm_win = decisions(ctx.seed + 1)
        warm_win.open()
        warm = one_round()
        if not warm["ok"]:
            raise SystemExit(f"the warm-up round failed: {warm['outcome']}")
        ctx.marks["warm_up_round"] = time.perf_counter()
        install()
        below0 = rungs()
        time.sleep(traffic["warmup_seconds"])
        warm_win.close(2.0)
        below = {r: v - below0[r] for r, v in rungs().items()}
        if warm_win.errors or install_faults or below["mlp"] or below["base"] or not below["serving"]:
            raise SystemExit(
                f"the warm-up failed: {warm_win.errors[:3]} {install_faults} by rung after the install {below}"
            )
        at_open = installs[-1][2]
        live_order = list(read_by[at_open][1])
        fitted_ids = list(registry.gnn[-1][1].get("node_ids") or ())
        if live_order == fitted_ids[: len(live_order)]:
            raise SystemExit("the live graph is in the upload's order: the cell cannot see a row served to the wrong host")
        del installs[:]
        stage.restage()
        ctx.marks["warm_up_decisions"] = time.perf_counter()

        snap0 = svc.snapshot()
        ph0, prom0 = taps.phase_counts(phase_names), taps.prom_series()
        ctx.window_opens()
        tracer_thread = rud.record_from_phase(ctx.tracer, traffic["trace_from_phase"], traffic["trace_seconds"])
        win = decisions(ctx.seed)
        win.open(heartbeat=ctx.trace)
        spent = 0.0
        while spent < ctx.seconds:
            r = one_round()
            timed.append(r)
            spent += r["wall_s"]
            install()
            stage.restage()
        win.close(traffic["drain_seconds"])
        if tracer_thread is not None:
            tracer_thread.join()
        ctx.window_closes()
    snap1 = svc.snapshot()
    phases = taps.phase_delta(ph0, taps.phase_counts(phase_names))
    prom = taps.series_delta(prom0, taps.prom_series())
    whole_run = taps.series_delta(series0, taps.prom_series())

    good = [r for r in timed if r["ok"]]
    passes = training.config.mlp.epochs
    metrics = {
        "train_records_per_s": stage.records_per_round * passes * len(good) / sum(r["wall_s"] for r in good)
        if good else 0.0
    }
    # a decision the scoring service did not answer inside its window plus
    # grace was ranked a rung down: a failed operation, counted and left
    # out of the latencies and of the comparison
    timeout_s = svc.cfg.window_s + svc.cfg.service_grace_s
    answered = np.array([r is not None and len(r) > 0 for r in win.returned], bool) & (win.end > 0)
    done = answered & ((win.end - win.start) < timeout_s)
    by_rung = {r: prom.get(rud.RUNG_SERIES % r, 0.0) for r in ("serving", "mlp", "base")}
    fell = int(by_rung["mlp"] + by_rung["base"])
    failed_decisions = max(int(win.n - done.sum()), min(fell, win.n))
    lat_us = (win.end - (win.t0 + win.due))[done] * 1e6
    took = (win.end - win.start) * answered  # what the grace is held against
    service_us = took[answered] * 1e6
    slowest = [
        [round(float(win.start[i] - max(r["began"] for r in timed if r["began"] <= win.start[i])), 2), round(float(took[i]), 3)]
        for i in np.argsort(-took)[:5]
        if answered[i] and win.start[i] >= timed[0]["began"]
    ]
    # the longest decision that began inside an install, and how many did
    in_install = np.zeros(win.n, bool)
    for began, ended, _ in installs:
        in_install |= (win.start <= ended) & (win.end >= began)
    wait_us = (win.start - (win.t0 + win.due)) * 1e6
    installs_failed = whole_run.get(INSTALL_SERIES % "failed", 0.0) + whole_run.get(INSTALL_SERIES % "skipped", 0.0)
    probes = {
        "fit_duration": {m: [r["fits"][m] for r in good] for m in ("mlp", "gnn", "gru")},
        "serving_snapshot": {
            "batches": snap1["batches"] - snap0["batches"],
            "rows_scored": snap1["rows_scored"] - snap0["rows_scored"],
            "window_s": win.seconds,
        },
        "prof_phase": phases,
        "prom_series": {
            **prom, "window_s": win.seconds,
            "decisions_ranked": sum(by_rung.values()), "decisions_below_serving": float(fell),
        },
        "harness_clock": {
            "decision_latency_us": lat_us.tolist(),
            "decision_service_us": service_us.tolist(),
            "process_pause_us": win.pauses_us,
            "queue_wait_us": wait_us[done].tolist(),
            "generator_lateness_us": wait_us[win.slept & done].tolist(),
        },
    }
    # a sample of the window's decisions, drawn from the seed, with the
    # largest candidate sets in it; one whose interval holds an install is
    # left out (either version may have ranked it), and counted
    version = rud.versions_in_force(win.start, win.end, installs, at_open)
    idx = np.nonzero(done & (version > 0))[0]
    rng = np.random.default_rng([ctx.seed, 14])
    pick = set(rng.choice(idx, size=min(traffic["sample_decisions"], idx.size), replace=False).tolist())
    sizes = np.array([len(desc["tasks"][k]["peers"]) for k in win.task_idx])
    pick.update(idx[np.argsort(-sizes[idx], kind="stable")[:50]].tolist())
    pick = sorted(pick)
    sample = [(int(version[i]), (int(win.task_idx[i]), int(win.child_idx[i])), win.returned[i]) for i in pick]
    over_an_install = int((done & (version == 0)).sum())
    weights = {v + 1: host_weights(p) for v, (_, p) in enumerate(registry.gnn)}
    errors, lost, n_decisions = list(win.errors), win.lost, win.n
    last = timed[-1]
    records, topology = stage.records, stage.topology
    records_per_round, chunk_bytes = stage.records_per_round, stage.chunk_bytes
    config, limits, trainer_cfg = cell.config, cell.config["limits"], cell.config["trainer"]
    body_repeats = traffic["body_repeats_per_chunk"] * traffic["chunks"]
    feed_ok = ctx.devices[0].platform != "tpu" or all(
        d.platform == "tpu" for call in resident.calls for leaf in rounds._leaves(call[3].params) for d in leaf.devices()
    )
    sampled = list(resident.calls)  # the warm-up round's and the timed rounds'
    rows_by = {row: whole_run.get(ROWS_SERIES % row, 0.0) for row in ("placed", "default", "dropped")}
    svc.stop()
    del scorers[:], exported[:]
    del trainer, training, scheduler, refresher, registry, stage, children, win, warm_win, resident

    def after_window() -> list:
        """Both comparisons with the plain reference, once the program's
        state is freed."""
        checks = [
            ("rounds_failed", float(len(timed) - len(good)), 0.0),
            ("installs_failed", float(len(install_faults)), 0.0),
            ("gnn_installs_failed", float(installs_failed), 0.0),
            ("decisions_below_serving", float(fell), 0.0),
            ("feed_off_chip", 0.0 if feed_ok else 1.0, 0.0),
        ]
        rows = rud.sampled_mismatch(sampled, records) if len(sampled) == 1 + len(timed) else math.inf
        x_decisions = rud.candidate_rows(desc, config, [s[1] for s in sample]) if sample else np.zeros((0, 0), np.float32)
        checks.extend(rud.hold_mlp([warm] + timed, good, rows, records, body_repeats, trainer_cfg["mlp"], limits, x_decisions))
        checks.extend(hold_gnn([warm] + timed, good, fed, topology, trainer_cfg["gnn"], limits))
        checks.extend(rounds._hold_gru(good, last, fed, records, body_repeats, trainer_cfg["gru"], limits))
        # every installed scorer's rows against the rows fitted for those ids
        fitted_order = reference.probe_graph(topology, trainer_cfg["gnn"]["max_degree"])["order"]
        misplaced = sum(rows_misplaced(read_by[v][1], weights[v], fitted_order) for v in read_by)
        misplaced += 0 if len(read_by) == 1 + len(timed) else math.inf
        checks.append(("gnn_rows_misplaced", float(misplaced), 0.0))
        got = {"rank_gap": 0.0 if sample else math.inf, "wrong_count": 0, "illegal": 0, "rows": 0}
        for v in sorted({s[0] for s in sample}):
            mine = [s for s in sample if s[0] == v]
            read = read_by[v][0]
            one = old.judge(
                {**desc, "topology_records": read}, placed_weights(weights[v], fitted_order, read), config,
                [s[1] for s in mine], lambda n: mine[n][2],
            )
            print(f"reference: GraphSAGE version {v}: {len(mine)} decisions, {one['rows']} candidate pairs, rank_gap {one['rank_gap']!r}", flush=True)
            got = {
                "rank_gap": max(got["rank_gap"], one["rank_gap"]),
                **{k: got[k] + one[k] for k in ("wrong_count", "illegal", "rows")},
            }
        return checks + [
            ("decisions_errored", float(len(errors) + lost), 0.0),
            ("parents_outside_the_rules", float(got["illegal"]), 0.0),
            ("parent_count_wrong", float(got["wrong_count"]), 0.0),
            ("rank_gap", got["rank_gap"], limits["rank_gap"]),
        ]

    swap = {
        k.removeprefix("scheduler.gnn_"): [phases[k]["count"], round(phases[k]["total_s"] / max(phases[k]["count"], 1), 4)]
        for k in phases if k.startswith("scheduler.gnn_")
    }
    return {
        "metrics": metrics,
        "probes": probes,
        "attempted": n_decisions + len(timed),
        "failed": failed_decisions + len(timed) - len(good) + len(install_faults) + int(installs_failed),
        "after_window": after_window,
        "notes": {
            "rounds": len(timed),
            "round_walls_s": [round(r["wall_s"], 4) for r in timed],
            "records_per_round": records_per_round,
            "chunk_mib": round(chunk_bytes / (1 << 20), 2),
            "rate_per_s": rate,
            "decisions": n_decisions,
            "decision_window_s": round(probes["serving_snapshot"]["window_s"], 2),
            "by_rung": by_rung,
            "fell_a_rung": fell,
            "answered_late": int(answered.sum() - done.sum()),
            "errors": errors[:3],
            "installs": [[round(b - installs[0][0], 3), round(e - b, 3), v] for b, e, v in installs],
            "install_faults": install_faults,
            "swap_count_mean_s": swap,
            "gnn_rows": rows_by,
            "decisions_over_an_install": over_an_install,
            "service_us_max_over_an_install": float((took * in_install).max() * 1e6) if in_install.any() else 0.0,
            "latency_us": {q: percentile(lat_us, q) for q in (50, 90, 95, 99)} if lat_us.size else {},
            "service_us": {"99": percentile(service_us, 99), "99.9": percentile(service_us, 99.9), "max": float(service_us.max())}
            if service_us.size else {},
            "slowest_at_round_s_took_s": slowest,
        },
    }
