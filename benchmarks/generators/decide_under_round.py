"""Whole resident rounds with parent decisions served beside them: the
one-chip cluster, both users at once.

Set-up builds the trainer and the scheduler as the colocated service
builds them (``dragonfly2_tpu.colocated``: one process, one backend,
resident fits, the ``ml`` evaluator), without ``serve()``. The manager
they share is an in-process registry with the client's own methods
(``rounds.Registry`` grown by what ``ModelRefresher`` asks of a manager),
which activates the newest MLP version as soon as it is registered. The
upload and the probe graph are staged as ``rounds`` stages them, the swarm
is built as ``open_loop_decisions`` builds it, and one warm-up round runs
with the decisions already arriving; its MLP version is installed through
the refresher, and a few hundred decisions are ranked by it.

The window: open-loop decisions from the open to the close, and beside
them whole rounds back to back until ``--seconds`` of round walls, each
followed by ``ModelRefresher.refresh_once()``, so every window holds as
many installs as rounds. A model reaches the scoring service by the
program's own path and no other. The round's number is ``rounds``' own
(``train_records_per_s``); the decisions' latencies, the share that fell a
rung and where a scoring batch's time went are per-layer metrics.

The trainer frees the pairs it was handed a slice at a time when it is
their only holder, so the generator keeps no round's arrays alive: every
round's, the warm-up's too, are held to the body by a strided sample
copied before the fit runs and compared after the window
(``mlp_rows_sampled_mismatch``; nothing of the comparison is inside
``setup_s``). A traced run records the
stretch in which the two planes meet: from the first timed round's entry
into ``trainer.mlp_feed`` (the epoch's puts, its slices on the chip, the
holdout), not from the round's start.

``correct`` is held to both comparisons the benchmark has: every check
of a resident round (the GraphSAGE and GRU legs' by import, the MLP leg's
composed from the same primitives in ``hold_mlp``, since
``rounds._hold_resident_mlp`` wants a round's arrays kept), and
``open_loop_decisions.judge`` with
the float32 host copy of the parameters of the version in force over each
sampled decision (a decision whose interval holds an install is left out
of the sample, and counted). Those parameters are the program's, so each
version that serves as a reference, the warm-up round's too, is first held
to the plain replay of the resident fit as ``rounds`` holds the last
round's: its loss, how far it moved, and its holdout error in float32
(every round fits the same upload from the same start). The replay's own
weights cannot be the decisions' reference decision by decision: the
program's fit (bfloat16 matmuls) and its float32 replay end at the same
loss and still rank the swarm's candidates nearly as far apart, at the
worst decision, as fp8 ranks them from float32 (PERF.md §6). Over all the
sampled decisions' candidate rows together they can: ``score_gap`` holds
each version's float32 costs there to the replay's
(``mlp_versions_score_gap``), so a version that matches the replay in
loss and in how far it moved, and still scores the swarm another way, is
not its own referee.
"""

from __future__ import annotations

import gc
import math
import os
import sys
import threading
import time

import numpy as np

from benchmarks.generators import open_loop_decisions as old
from benchmarks.generators import rounds
from benchmarks.harness import reference, reference_fits
from benchmarks.harness import swarm as swarm_mod
from benchmarks.harness import taps
from benchmarks.harness.layer_readers import percentile

RUNG_SERIES = 'dragonfly_scheduler_decision_rung_total{rung="%s"}'


def host_weights(params: dict) -> dict:
    """The float32 host copy of a parameter tree, as the reference reads it."""
    return {"layers": [{k: np.asarray(v, np.float32) for k, v in l.items()} for l in params["layers"]]}


class Registry(rounds.Registry):
    """``rounds.Registry`` (the trainer's ``create_model``) grown by what
    ``ModelRefresher`` asks of a manager: ``ListModels`` and
    ``GetModelWeights``, the weights serialized as the manager's client
    serializes them. The newest MLP version is active as soon as it is
    registered (the configuration's ``assumed``); GraphSAGE and GRU
    versions are registered and stay inactive."""

    def __init__(self):
        super().__init__()
        self.mlp: list = []  # every MLP version ever registered: (model id, params)

    def create_model(self, model_id, model_type, ip, hostname, params, evaluation):
        super().create_model(model_id, model_type, ip, hostname, params, evaluation)
        if model_type == "mlp":
            self.mlp.append((model_id, params))

    def ListModels(self, request):
        import manager_pb2

        models = []
        if self.mlp:
            version = len(self.mlp)
            models.append(
                manager_pb2.Model(
                    model_id=self.mlp[-1][0], type="mlp", version=version, state="active",
                    created_at_ns=version, updated_at_ns=version,
                )
            )
        return manager_pb2.ListModelsResponse(models=models)

    def GetModelWeights(self, request):
        import manager_pb2

        from dragonfly2_tpu.trainer.serving import serialize_params

        model_id, params = self.mlp[request.version - 1]
        return manager_pb2.ModelWeights(
            model_id=model_id, version=request.version, type="mlp", weights=serialize_params(params)
        )


SAMPLE_STRIDE = 101  # of a round's rows: 545,052 of 55M (44 MB), a prime so that it walks the body


class ResidentFits:
    """``training.train_mlp`` spied without keeping any round's arrays
    alive (the trainer frees them a slice at a time when it alone holds
    them, and a window whose first round lands in memory the warm-up did
    not touch stalls on a machine that backs its memory lazily): every
    call's are kept as every ``SAMPLE_STRIDE``-th row."""

    def __init__(self, module):
        self.module, self.real = module, module.train_mlp
        self.calls: list = []  # per call: rows, sampled features, sampled labels, the fit's result

    def __enter__(self):
        def spied(features, labels, **kwargs):
            sample = (features.shape[0], features[::SAMPLE_STRIDE].copy(), labels[::SAMPLE_STRIDE].copy())
            self.calls.append((*sample, self.real(features, labels, **kwargs)))
            return self.calls[-1][3]

        self.module.train_mlp = spied
        return self

    def __exit__(self, *exc):
        self.module.train_mlp = self.real


def sampled_mismatch(calls: list, records: list) -> float:
    """Sampled rows of every round's pairs against the upload's
    body, which repeats: row ``i`` is row ``i % len(body)`` of it."""
    x, y = reference.record_pairs(records)
    total = 0.0
    for rows, got_x, got_y, _ in calls:
        pick = np.arange(0, rows, SAMPLE_STRIDE) % x.shape[0]
        total += reference_fits.mismatches((got_x, x[pick]), (got_y, y[pick]))
    return total


class Beside(old.Window):
    """An open-loop window whose end is not known when it opens: it is
    closed when the rounds beside it are done. Arrivals are drawn for a
    horizon; those due by the close are the window's, and a worker that
    holds a later one drops it."""

    cut: "int | None" = None

    def _work(self) -> None:
        find = self.scheduling.find_candidate_parents
        while True:
            i = self._claim()
            if i >= self.n:
                return
            due = self.t0 + self.due[i]
            now = time.perf_counter()
            while now < due:
                self.slept[i] = True
                time.sleep(due - now)
                now = time.perf_counter()
            if self.cut is not None and i >= self.cut:
                return
            self.start[i] = now
            try:
                parents, found = find(self.children[self.task_idx[i]][self.child_idx[i]])
                self.returned[i] = [p.id for p in parents] if found else []
            except Exception as e:  # a decision that raises is a failed one
                self.errors.append(repr(e))
            self.end[i] = time.perf_counter()

    def open(self, heartbeat: bool = False) -> None:
        """``heartbeat``: ``Window.pause_meter`` beside the workers, as
        ``Window.run`` starts it in a traced run."""
        self.pauses_us: list = []
        self._stop_heart = threading.Event()
        self._threads = [
            threading.Thread(target=self._work, name=f"bench.worker-{k}", daemon=True)
            for k in range(self.workers)
        ]
        if heartbeat:
            threading.Thread(target=self.pause_meter, args=(self._stop_heart,), name="bench.heartbeat", daemon=True).start()
        self.t0 = time.perf_counter() + 0.05
        for t in self._threads:
            t.start()

    def close(self, drain_s: float) -> None:
        """Nothing due after this instant is offered. What was due is given
        ``drain_s`` to be claimed; what is then still unclaimed is lost,
        and what is in flight is waited for (a decision is bounded by the
        service's own grace). The arrays are cut to the arrivals offered."""
        closed = time.perf_counter()
        self.cut = int(np.searchsorted(self.due, closed - self.t0, side="right"))
        if self.cut >= self.n:
            raise SystemExit("the arrival schedule ran out before the rounds did: raise horizon_seconds")
        for t in self._threads:
            t.join(timeout=max(closed + drain_s - time.perf_counter(), 0.0))
        with self._lock:
            self.lost = max(self.cut - self._next, 0)
            self._next = self.n
        for t in self._threads:
            t.join()
        self._stop_heart.set()
        self.n = self.cut
        self.seconds = closed - self.t0
        for name in ("due", "task_idx", "child_idx", "start", "end", "slept", "returned"):
            setattr(self, name, getattr(self, name)[: self.n])


def record_from_phase(tracer, phase: str, seconds: float) -> "threading.Thread | None":
    """Record ``seconds`` of trace from the next entry into the program's
    phase ``phase``, on a thread of its own; None when not tracing."""
    if not tracer.enabled:
        return None
    from dragonfly2_tpu.utils import profiling

    ph = profiling.phase_type(phase)
    entered = ph.snapshot()["count"]

    def record():
        while True:
            snap = ph.snapshot()
            if snap["active"] or snap["count"] > entered:
                break
            time.sleep(0.002)
        tracer.start()
        time.sleep(seconds)
        tracer.stop()

    thread = threading.Thread(target=record, name="bench.tracer", daemon=True)
    thread.start()
    return thread


def build(ctx):
    """The two servers as the colocated service builds them, without
    ``serve()``; the refresher wired to the registry as the scheduler
    wires it to a manager's channel."""
    from dragonfly2_tpu import colocated
    from dragonfly2_tpu.colocated import server as assembly
    from dragonfly2_tpu.scheduler.model_refresher import ModelRefresher
    from dragonfly2_tpu.scheduler.server import SchedulerServer
    from dragonfly2_tpu.trainer.server import TrainerServer

    want = ctx.cell.config["trainer"]
    cfg = colocated.ColocatedConfig(
        data_dir=os.path.join(ctx.workdir, "colocated"),
        # the knobs the servers' own configs expose come from the file;
        # the rest are their defaults, which check_config holds the file to
        trainer={
            "mlp_epochs": want["mlp"]["epochs"],
            "mlp_batch_size": want["mlp"]["batch_size"],
            "gnn_epochs": want["gnn"]["epochs"],
            "streaming_workers": want["mlp"]["streaming_workers"],
        },
        scheduler={"hostname": rounds.HOSTNAME, "topology_backend": ctx.cell.config["scheduler"]["topology_backend"]},
    )
    trainer = TrainerServer(assembly.trainer_config(cfg))
    scheduler = SchedulerServer(assembly.scheduler_config(cfg, trainer_address=""))
    assembly.settle()  # as its serve() does, once both are built
    registry = Registry()
    trainer.training.manager_client = registry
    refresher = ModelRefresher(
        registry, scheduler.evaluator, scheduler_cluster_id=scheduler.cfg.cluster_id,
        serving=scheduler.scoring_service, networktopology=scheduler.networktopology,
    )
    return trainer, scheduler, registry, refresher


def check_config(trainer, scheduler, cfg: dict) -> None:
    """Both generators' checks of the file against the program's defaults,
    and what only this deployment states."""
    from dragonfly2_tpu.trainer.serving import BUCKET_LADDER

    rounds.check_config(trainer.training, cfg)
    old.check_config(scheduler, cfg)
    s = cfg["scheduler"]
    for key, have, want in (
        ("algorithm", scheduler.cfg.algorithm, s["algorithm"]),
        ("serving_ladder", list(BUCKET_LADDER), s["serving_ladder"]),
        ("served_model", "mlp", cfg["served_model"]),  # the registry activates MLP versions only
        ("switch_interval_ms", round(sys.getswitchinterval() * 1e3, 6), cfg["interpreter"]["switch_interval_ms"]),
    ):
        if have != want:
            raise SystemExit(f"configuration drift: {key} is {have!r}, file says {want!r}")


def versions_in_force(start, end, installs: list, at_open: int) -> np.ndarray:
    """For decisions over ``[start, end]``: the version that ranked each,
    0 where an install's interval touches the decision's (either version
    may have). ``installs`` is ``[(began, ended, version)]`` in order;
    ``at_open`` is the version installed before the window."""
    version = np.full(len(start), at_open, np.int64)
    for began, ended, v in installs:
        version[start >= ended] = v
    for began, ended, _ in installs:
        version[(start <= ended) & (end >= began)] = 0
    return version


def candidate_rows(desc: dict, cfg: dict, picks: list) -> np.ndarray:
    """The feature rows of every legal candidate of the decisions
    ``picks`` (task index, child index), as ``open_loop_decisions.judge``
    builds them for the MLP: the swarm's own distribution of rows, which
    the upload's is not."""
    rtt = reference.RttReference(
        len(desc["hosts"]), desc["edges"],
        landmarks=cfg["scheduler"]["topology_landmarks"], iters=cfg["scheduler"]["topology_landmark_iters"],
    )
    rows = []
    for k, c in picks:
        task = desc["tasks"][k]
        child = task["children"][c]
        for p in reference.legal_parents(task, child, desc["hosts"], desc["uploads_now"]):
            rows.append(
                reference.pair_features(
                    desc["hosts"][p["host"]], desc["hosts"][child["host"]],
                    p["finished"], task["total_pieces"], task["content_length"],
                    p["state"] == "Succeeded", rtt.affinity(child["host"], p["host"]),
                    upload_count_now=desc["uploads_now"][p["host"]],
                )
            )
    return np.stack(rows)


def score_gap(weights: dict, replay: dict, x: np.ndarray, precision: str = "float32") -> float:
    """How far ``weights`` score the rows ``x`` from how the replay's
    weights score them: the root mean square of the difference of the two
    cost vectors, each about its own mean (a ranking does not see an
    offset), over the spread of the replay's. 0 for the same scorer;
    about 1.4 for one that has nothing to do with the replay's."""
    if not len(x):
        return math.inf
    got = reference.mlp_forward(weights, x, precision).astype(np.float64)
    want = reference.mlp_forward(replay, x, "float32").astype(np.float64)
    if not np.isfinite(got).all() or want.std() == 0.0:
        return math.inf
    return float(np.sqrt(np.mean(((got - got.mean()) - (want - want.mean())) ** 2)) / want.std())


def hold_mlp(fits: list, good: list, rows: float, records: list, repeats: int, cfg: dict, limits: dict, x_decisions: np.ndarray) -> list:
    """What ``rounds`` holds a resident round's MLP fit to, by the same
    primitives: every good round was handed every pair of the upload; the
    rows (``rows``: every round's, sampled); the last round's loss,
    registered parameters and holdout error against the plain replay; the
    worst of those three over every round ``fits`` whose version ranked
    decisions, the warm-up round's too (``mlp_versions_*``); and how far
    each of those versions scores the decisions' candidate rows
    ``x_decisions`` from how the replay's weights do."""
    x, y = reference.record_pairs(records)
    ref = reference_fits.fit_mlp(
        x, y, repeats, hidden=tuple(cfg["hidden_dims"]), epochs=cfg["epochs"], batch=cfg["batch_size"],
        learning_rate=cfg["learning_rate"], weight_decay=cfg["weight_decay"],
    )
    per_round = []
    for r in fits:
        (_, path, _), (_, update, _) = rounds._hold_fit("mlp", r, ref, [0, cfg["epochs"]], limits)
        params, registered = r["params"].get("mlp"), r["evaluations"].get("mlp", {})
        held = math.inf
        if params is not None and "mse" in registered:
            want = reference_fits.mlp_holdout_mse(x, y, repeats, params)
            held = abs(registered["mse"] - want) / want
        score = math.inf if params is None else score_gap(host_weights(params), host_weights(ref["params"]), x_decisions)
        per_round.append({"loss_path": path, "update": update, "holdout_mse": held, "score": score})
    want_pairs = x.shape[0] * repeats
    return (
        [
            ("mlp_pairs_gap", float(sum(abs(r["mlp_pairs"] - want_pairs) for r in good)), 0.0),
            ("mlp_rows_sampled_mismatch", rows, 0.0),
        ]
        + [(f"mlp_{k}_gap", v, limits[f"mlp_{k}_gap"]) for k, v in per_round[-1].items() if k != "score"]
        + [(f"mlp_versions_{k}_gap", max(g[k] for g in per_round), limits[f"mlp_{k}_gap"]) for k in per_round[-1]]
    )


def setup(ctx):
    """Everything before the warm-up round; shared with the sweep. Returns
    the two servers, the registry, the refresher, the staged upload, the
    swarm's description and its live children."""
    # the deployment first: a program without the service stops here
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
    # the harness's own description of the upload and of the swarm (some
    # 190,000 objects) is not the program's: it goes out of the collector's
    # reach, as start-up's did in the service's settle(), so that a full
    # collection beside a round walks the program's live state (the swarm
    # built next, what the fits trace) and not the benchmark's
    gc.freeze()
    children = old.build_live(desc, scheduler)
    old.fill_topology(scheduler.topology_engine, desc)
    scheduler.scoring_service.start()
    # the rtt join at every rung a decision's candidate count can reach
    ids = [h.id for h in desc["hosts"]]
    for rows in _serving_rungs(scheduler.scoring_service):
        if rows <= 2 * cell.config["scheduler"]["filter_parent_limit"]:
            scheduler.topology_engine.rtt_affinity_pairs([ids[0]] * rows, ids[1 : rows + 1])
    ctx.marks["swarm"] = time.perf_counter()
    return trainer, scheduler, registry, refresher, stage, desc, children


def run(ctx) -> dict:
    from dragonfly2_tpu.trainer import train as train_mod
    from dragonfly2_tpu.trainer import training as training_mod

    cell, traffic = ctx.cell, ctx.cell.traffic
    rate = float(cell.params["rate_per_s"])
    trainer, scheduler, registry, refresher, stage, desc, children = setup(ctx)
    training, svc = trainer.training, scheduler.scoring_service

    resident = ResidentFits(training_mod)
    gnn, gru = [], []
    fed: dict = {}  # what the GraphSAGE and GRU fits of the last round were handed

    def one_round() -> dict:
        """``rounds.run``'s round, for a trainer pinned to resident fits."""
        registry.round = []
        del gnn[:], gru[:]
        fed.clear()  # last round's arrays go before this round's are made
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

    installs: list = []  # (began, ended, version) of the window's, on perf_counter's clock
    install_faults: list = []

    def install() -> None:
        """One refresher round: the newest registered MLP version into the
        evaluator and the scoring service, every rung warmed first."""
        version = len(registry.mlp)
        began = time.perf_counter()
        installed = refresher.refresh_once()
        installs.append((began, time.perf_counter(), version))
        want = f"{registry.mlp[-1][0]}/v{version}"
        got = svc.snapshot()["model_version"]
        if not installed or got != want:
            install_faults.append(f"refresh_once {installed}, serving {got!r}, registry {want!r}")

    def decisions(seed: int) -> Beside:
        due, task_idx, child_idx = swarm_mod.arrivals(traffic, seed, rate, traffic["horizon_seconds"])
        return Beside(scheduler.scheduling, children, due, task_idx, child_idx, traffic["workers"])

    def rungs() -> dict:
        series = taps.prom_series()
        return {r: series.get(RUNG_SERIES % r, 0.0) for r in ("serving", "mlp", "base")}

    phase_names = sorted(
        {m["reader"]["phase"] for m in cell.per_layer if m["reader"]["kind"] == "prof_phase"}
    )
    timed: list = []  # one dict per timed round
    with (
        resident,
        taps.spy(training_mod, "train_gnn", gnn, with_args=True),
        taps.spy(train_mod, "train_gru", gru, with_args=True),
    ):
        # the warm-up: one round with the decisions already arriving (no
        # version yet: the base evaluator ranks them), its version
        # installed, then decisions ranked by it
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
        del installs[:]
        stage.restage()
        ctx.marks["warm_up_decisions"] = time.perf_counter()

        snap0 = svc.snapshot()
        ph0, prom0 = taps.phase_counts(phase_names), taps.prom_series()
        ctx.window_opens()
        # a traced run records the stretch in which the two planes meet:
        # from the first timed round's entry into trainer.mlp_feed (its
        # GRU leg is long done: the profiler takes minutes to write out a
        # stretch that holds that leg's 70,000 scan steps)
        tracer_thread = record_from_phase(ctx.tracer, traffic["trace_from_phase"], traffic["trace_seconds"])
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

    # the round's number, by rounds' own definition
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
    by_rung = {r: prom.get(RUNG_SERIES % r, 0.0) for r in ("serving", "mlp", "base")}
    fell = int(by_rung["mlp"] + by_rung["base"])
    failed_decisions = max(int(win.n - done.sum()), min(fell, win.n))
    lat_us = (win.end - (win.t0 + win.due))[done] * 1e6
    took = (win.end - win.start) * answered  # what the grace is held against
    service_us = took[answered] * 1e6
    # the five longest, each with how far into its round it began: which
    # of the round's phases held it
    slowest = [
        [round(float(win.start[i] - max(r["began"] for r in timed if r["began"] <= win.start[i])), 2), round(float(took[i]), 3)]
        for i in np.argsort(-took)[:5]
        if answered[i] and win.start[i] >= timed[0]["began"]
    ]
    wait_us = (win.start - (win.t0 + win.due)) * 1e6
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
            # from a decision's own start: what the service's grace is held against
            "decision_service_us": service_us.tolist(),
            "process_pause_us": win.pauses_us,
            "queue_wait_us": wait_us[done].tolist(),
            "generator_lateness_us": wait_us[win.slept & done].tolist(),
        },
    }
    # a sample of the window's decisions, drawn from the seed, with the
    # largest candidate sets in it; one whose interval holds an install is
    # left out (either version may have ranked it), and counted
    version = versions_in_force(win.start, win.end, installs, at_open)
    idx = np.nonzero(done & (version > 0))[0]
    rng = np.random.default_rng([ctx.seed, 14])
    pick = set(rng.choice(idx, size=min(traffic["sample_decisions"], idx.size), replace=False).tolist())
    sizes = np.array([len(desc["tasks"][k]["peers"]) for k in win.task_idx])
    pick.update(idx[np.argsort(-sizes[idx], kind="stable")[:50]].tolist())
    pick = sorted(pick)
    sample = [(int(version[i]), (int(win.task_idx[i]), int(win.child_idx[i])), win.returned[i]) for i in pick]
    over_an_install = int((done & (version == 0)).sum())
    weights = {v + 1: host_weights(p) for v, (_, p) in enumerate(registry.mlp)}
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
    svc.stop()
    del trainer, training, scheduler, refresher, registry, stage, children, win, warm_win, resident

    def after_window() -> list:
        """Both comparisons with the plain reference, once the program's
        state is freed."""
        checks = [
            ("rounds_failed", float(len(timed) - len(good)), 0.0),
            ("installs_failed", float(len(install_faults)), 0.0),
            ("feed_off_chip", 0.0 if feed_ok else 1.0, 0.0),
        ]
        # every version the decisions are held to is first held to the
        # replay of the resident fit; the last round's as ``rounds`` holds it
        rows = sampled_mismatch(sampled, records) if len(sampled) == 1 + len(timed) else math.inf
        x_decisions = candidate_rows(desc, config, [s[1] for s in sample]) if sample else np.zeros((0, 0), np.float32)
        checks.extend(hold_mlp([warm] + timed, good, rows, records, body_repeats, trainer_cfg["mlp"], limits, x_decisions))
        checks.extend(rounds._hold_gnn(good, last, fed, topology, trainer_cfg["gnn"], limits))
        checks.extend(rounds._hold_gru(good, last, fed, records, body_repeats, trainer_cfg["gru"], limits))
        got = {"rank_gap": 0.0 if sample else math.inf, "wrong_count": 0, "illegal": 0, "rows": 0}
        for v in sorted({s[0] for s in sample}):
            mine = [s for s in sample if s[0] == v]
            one = old.judge(desc, weights[v], config, [s[1] for s in mine], lambda n: mine[n][2])
            print(f"reference: version {v}: {len(mine)} decisions, {one['rows']} candidate rows, rank_gap {one['rank_gap']!r}", flush=True)
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

    return {
        "metrics": metrics,
        "probes": probes,
        "attempted": n_decisions + len(timed),
        "failed": failed_decisions + len(timed) - len(good) + len(install_faults),
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
            "decisions_over_an_install": over_an_install,
            "latency_us": {q: percentile(lat_us, q) for q in (50, 90, 95, 99)} if lat_us.size else {},
            "service_us": {"99": percentile(service_us, 99), "99.9": percentile(service_us, 99.9), "max": float(service_us.max())}
            if service_us.size else {},
            "slowest_at_round_s_took_s": slowest,
        },
    }
