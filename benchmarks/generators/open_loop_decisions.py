"""Parent decisions on an arrival schedule: the peer's path.

Set-up builds the scheduler as the ``SchedulerServer`` binary does
(without ``serve()``), fills its resource managers and topology engine
from the seeded swarm description, installs the served model with every
reachable rung warmed, as ``ModelRefresher`` does, and runs a few hundred
untimed decisions. The timed operation is
``Scheduling.find_candidate_parents(child)``: the six filter rules, the
feature build, the rtt join on the device, the ``ScoringService`` batch
and the ranking. It reads swarm state and changes none, so the traffic
is stationary.

Open loop: every decision has a due time on a schedule drawn from the
seed, and its latency counts from that instant. A pool of workers (the
gRPC server's ``max_workers``) takes arrivals in order; one that finds
every worker busy has been waiting since it was due.
"""

from __future__ import annotations

import math
import threading
import time

import numpy as np

from benchmarks.harness import reference, swarm as swarm_mod, synth, taps

PHASES = ["scheduler.evaluate", "scheduler.serving_wait", "scheduler.serving_batch"]


class CountingScorer:
    """The per-call MLP the evaluator falls back to when the serving
    path fails a decision. Every call here is one decision that dropped
    a rung, so it is counted; the served path never comes through it."""

    def __init__(self, scorer):
        self._scorer = scorer
        self.calls = 0
        self._lock = threading.Lock()

    @property
    def feature_dim(self):
        return self._scorer.feature_dim

    def predict(self, features):
        with self._lock:
            self.calls += 1
        return self._scorer.predict(features)


def check_config(srv, cfg: dict) -> None:
    s = cfg["scheduler"]
    have = {
        "candidate_parent_limit": srv.scheduling.config.candidate_parent_limit,
        "filter_parent_limit": srv.scheduling.config.filter_parent_limit,
        "serving_window_ms": srv.scoring_service.cfg.window_s * 1e3,
        "serving_queue_depth": srv.scoring_service.cfg.queue_depth,
        "serving_max_rows": srv.scoring_service.cfg.max_rows,
        "topology_landmarks": srv.topology_engine.cfg.num_landmarks,
        "topology_landmark_iters": srv.topology_engine.cfg.landmark_iters,
        "topology_flush_threshold": srv.topology_engine.cfg.flush_threshold,
    }
    for key, value in have.items():
        if s[key] != value:
            raise SystemExit(f"configuration drift: {key} is {value!r}, file says {s[key]!r}")


def build_live(desc: dict, srv) -> list:
    """The scheduler's resource objects from the description. Returns
    the children, one list per task."""
    from dragonfly2_tpu.scheduler import resource as res

    hosts = []
    for rec in desc["hosts"]:
        h = res.Host(
            id=rec.id, type=res.HostType(rec.type), hostname=rec.hostname, ip=rec.ip,
            port=rec.port, download_port=rec.download_port, os=rec.os,
            concurrent_upload_limit=rec.concurrent_upload_limit,
            concurrent_upload_count=rec.concurrent_upload_count,
            upload_count=rec.upload_count, upload_failed_count=rec.upload_failed_count,
            cpu=rec.cpu, memory=rec.memory, network=rec.network, disk=rec.disk,
        )
        srv.resource.host_manager.store(h)
        hosts.append(h)
    steps = {
        "ReceivedNormal": [res.PEER_EVENT_REGISTER_NORMAL],
        "Running": [res.PEER_EVENT_REGISTER_NORMAL, res.PEER_EVENT_DOWNLOAD],
        "Succeeded": [
            res.PEER_EVENT_REGISTER_NORMAL, res.PEER_EVENT_DOWNLOAD,
            res.PEER_EVENT_DOWNLOAD_SUCCEEDED,
        ],
    }
    children = []
    for t in desc["tasks"]:
        task = res.Task(t["id"], f"https://origin.example.com/{t['id']}")
        task.content_length = t["content_length"]
        task.total_piece_count = t["total_pieces"]
        srv.resource.task_manager.store(task)
        live = {}
        for p in t["peers"]:
            peer = res.Peer(p["id"], task, hosts[p["host"]])
            for ev in steps[p["state"]]:
                peer.fsm.event(ev)
            peer.finished_pieces = set(range(p["finished"]))
            peer.piece_costs_ms = list(p["piece_costs"])
            task.store_peer(peer)
            hosts[p["host"]].store_peer(peer)
            srv.resource.peer_manager.store(peer)
            live[p["id"]] = peer
        for p in t["peers"]:
            if p["fed_by"]:
                task.add_peer_edge(live[p["fed_by"]], live[p["id"]])
        children.append([live[c["id"]] for c in t["children"]])
    for i, rec in enumerate(desc["hosts"]):
        if hosts[i].concurrent_upload_count != desc["uploads_now"][i]:
            raise SystemExit("live upload counts differ from the description")
    return children


def fill_topology(engine, desc: dict) -> None:
    """The probe graph into the engine by its hydration call (edges
    already averaged), then one flush: one build at the final capacity."""
    now = time.time()
    ids = [h.id for h in desc["hosts"]]
    for s, t, rtt_ns in desc["edges"]:
        engine.adopt(ids[s], ids[t], float(rtt_ns), now)
    engine.flush()


def install_model(srv, cfg: dict, seed: int, desc: dict):
    """Seeded weights at the published widths into the serving slot, with
    every reachable rung compiled first, as ``ModelRefresher`` does."""
    from dragonfly2_tpu.scheduler.model_refresher import _serving_rungs
    from dragonfly2_tpu.scheduler.serving import MLPServed
    from dragonfly2_tpu.trainer.serving import MLPScorer

    dims = [reference.MLP_FEATURE_DIM, *cfg["trainer"]["mlp"]["hidden_dims"], 1]
    weights = synth.mlp_weights(seed, dims)
    scorer = MLPScorer(weights)
    rungs = _serving_rungs(srv.scoring_service)
    for rows in rungs:
        x = np.zeros((rows, dims[0]), np.float32)
        scorer.predict(x)
        scorer.predict_ranked(x, np.zeros((rows,), np.int32))
    fallback = CountingScorer(scorer)
    srv.evaluator.set_model(fallback)
    if cfg["served_model"] == "gnn":
        weights = install_gnn(srv, cfg, seed, desc, rungs)
    else:
        srv.scoring_service.install(MLPServed(scorer), version=f"bench-seed-{seed}")
    return weights, fallback, rungs


def install_gnn(srv, cfg: dict, seed: int, desc: dict, rungs: list) -> dict:
    """The GraphSAGE rung: seeded weights at the published widths, the
    embeddings computed here, at swap time, from the probe graph's
    records, and the pairwise head compiled at every rung first."""
    from dragonfly2_tpu.schema.columnar import records_to_columns
    from dragonfly2_tpu.schema.features import build_probe_graph
    from dragonfly2_tpu.scheduler.serving import GNNServed
    from dragonfly2_tpu.trainer.serving import GNNScorer

    gnn = cfg["trainer"]["gnn"]
    graph = build_probe_graph(
        records_to_columns(desc["topology_records"]), max_degree=gnn["max_degree"]
    )
    weights = synth.gnn_weights(seed, graph.num_nodes, hidden=tuple(gnn["hidden_dims"]))
    scorer = GNNScorer(weights, graph)
    for rows in rungs:
        scorer.predict_rtt_log_ms([graph.node_ids[0]] * rows, [graph.node_ids[1]] * rows)
    srv.scoring_service.install(GNNServed(scorer), version=f"bench-gnn-seed-{seed}")
    return weights


class Window:
    """One open-loop window: arrivals claimed in order by a worker pool."""

    def __init__(self, scheduling, children, due, task_idx, child_idx, workers: int):
        self.scheduling, self.children = scheduling, children
        self.due, self.task_idx, self.child_idx = due, task_idx, child_idx
        self.n = len(due)
        self.start = np.zeros(self.n)
        self.end = np.zeros(self.n)
        self.slept = np.zeros(self.n, bool)
        self.returned: list = [None] * self.n
        self.errors: list = []
        self._next = 0
        self._lock = threading.Lock()
        self.workers = workers
        self.t0 = 0.0

    def _claim(self) -> int:
        with self._lock:
            i = self._next
            self._next += 1
            return i

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
            self.start[i] = now
            try:
                parents, found = find(self.children[self.task_idx[i]][self.child_idx[i]])
                self.returned[i] = [p.id for p in parents] if found else []
            except Exception as e:  # a decision that raises is a failed one
                self.errors.append(repr(e))
            self.end[i] = time.perf_counter()

    def pause_meter(self, stop: threading.Event) -> None:
        """A thread that only sleeps a millisecond at a time: when it
        wakes late, the whole process was held up (the machine, or
        something holding the interpreter lock), not one decision."""
        last = time.perf_counter()
        while not stop.is_set():
            time.sleep(0.001)
            now = time.perf_counter()
            self.pauses_us.append((now - last) * 1e6)
            last = now

    def run(self, drain_s: float, heartbeat=None) -> None:
        """``heartbeat(window, stop)`` runs beside the workers when given
        (``Window.pause_meter`` in a traced run, the pause diagnosis's
        own): it takes the interpreter lock a thousand times a second, so
        an untraced run has none."""
        stop = threading.Event()
        self.pauses_us: list = []
        heart = None
        if heartbeat is not None:
            heart = threading.Thread(target=heartbeat, args=(self, stop), name="bench.heartbeat", daemon=True)
            heart.start()
        threads = [
            threading.Thread(target=self._work, name=f"bench.worker-{k}", daemon=True)
            for k in range(self.workers)
        ]
        self.t0 = time.perf_counter() + 0.05
        for t in threads:
            t.start()
        deadline = self.t0 + float(self.due[-1]) + drain_s
        for t in threads:
            t.join(timeout=max(deadline - time.perf_counter(), 0.0))
        # past the deadline nothing more is started: what is still
        # unclaimed is lost, and what is in flight is waited for (a
        # decision is bounded by the service's own grace), so that no
        # thread outlives the window
        with self._lock:
            self.lost = max(self.n - self._next, 0)
            self._next = self.n
        for t in threads:
            t.join()
        stop.set()
        if heart is not None:
            heart.join()


def judge(desc: dict, weights: dict, cfg: dict, picks: list, returned_of, precision: str = "float32") -> dict:
    """Hold decisions against the reference. ``picks`` is a list of
    (task index, child index); ``returned_of(n)`` gives the parent ids
    the timed path returned for the n-th pick, or None to put the
    reference itself, computed at ``precision``, in the program's place
    (the control)."""
    limit = cfg["scheduler"]["candidate_parent_limit"]
    filter_limit = cfg["scheduler"]["filter_parent_limit"]
    rtt = reference.RttReference(
        len(desc["hosts"]), desc["edges"],
        landmarks=cfg["scheduler"]["topology_landmarks"],
        iters=cfg["scheduler"]["topology_landmark_iters"],
    )
    gnn = cfg["served_model"] == "gnn"
    rows, spans, legal_sets = [], [], []
    for k, c in picks:
        task = desc["tasks"][k]
        child = task["children"][c]
        legal = reference.legal_parents(task, child, desc["hosts"], desc["uploads_now"])
        legal_sets.append(legal)
        spans.append((len(rows), len(rows) + len(legal)))
        for p in legal:
            if gnn:
                rows.append((child["host"], p["host"]))
                continue
            rows.append(
                reference.pair_features(
                    desc["hosts"][p["host"]], desc["hosts"][child["host"]],
                    p["finished"], task["total_pieces"], task["content_length"],
                    p["state"] == "Succeeded", rtt.affinity(child["host"], p["host"]),
                    upload_count_now=desc["uploads_now"][p["host"]],
                )
            )
    if gnn:
        index = {h.id: i for i, h in enumerate(desc["hosts"])}
        pairs = ([a for a, _ in rows], [b for _, b in rows])
        degree = cfg["trainer"]["gnn"]["max_degree"]

        def score(prec):
            return reference.GnnReference(
                desc["topology_records"], index, weights, degree, prec
            ).costs(*pairs)
    else:
        x = np.stack(rows)

        def score(prec):
            return reference.mlp_forward(weights, x, prec)

    ref_costs = score("float32")
    low_costs = ref_costs if precision == "float32" else score(precision)
    worst_gap, wrong_count, illegal = 0.0, 0, 0
    for n, (k, c) in enumerate(picks):
        lo, hi = spans[n]
        ids = [p["id"] for p in legal_sets[n]]
        costs = dict(zip(ids, ref_costs[lo:hi].tolist()))
        closed = len(desc["tasks"][k]["peers"]) <= filter_limit
        got = returned_of(n)
        if got is None:
            order = np.argsort(low_costs[lo:hi], kind="stable")[:limit]
            got = [ids[j] for j in order]
        gap = reference.rank_gap(got, costs, closed)
        if math.isinf(gap):
            illegal += 1
        else:
            worst_gap = max(worst_gap, gap)
        want = min(limit, len(ids))
        if (closed and len(got) != want) or not (1 <= len(got) <= want):
            wrong_count += 1
    return {"rank_gap": worst_gap, "wrong_count": wrong_count, "illegal": illegal, "rows": len(rows)}


def setup(ctx):
    """Everything before the first decision; shared with the sweep."""
    import os

    from dragonfly2_tpu.scheduler.server import SchedulerServer, SchedulerServerConfig

    cell = ctx.cell
    marks = ctx.marks
    marks["imports_and_chip"] = time.perf_counter()
    srv = SchedulerServer(
        SchedulerServerConfig(
            data_dir=os.path.join(ctx.workdir, "scheduler"),
            hostname="bench-scheduler",
            algorithm="ml",
            topology_backend="jax",
        )
    )
    check_config(srv, cell.config)
    desc = swarm_mod.describe(cell.traffic, ctx.seed)
    marks["describe"] = time.perf_counter()
    children = build_live(desc, srv)
    marks["build_live"] = time.perf_counter()
    fill_topology(srv.topology_engine, desc)
    marks["topology"] = time.perf_counter()
    srv.scoring_service.start()
    weights, fallback, rungs = install_model(srv, cell.config, ctx.seed, desc)
    marks["install"] = time.perf_counter()
    # the rtt join at every rung a decision's candidate count can reach
    ids = [h.id for h in desc["hosts"]]
    for rows in rungs:
        if rows <= 2 * cell.config["scheduler"]["filter_parent_limit"]:
            srv.topology_engine.rtt_affinity_pairs([ids[0]] * rows, ids[1 : rows + 1])
    return srv, desc, children, weights, fallback


def drive(ctx, srv, children, rate: float, seconds: float, seed: int, drain_s: float,
          heartbeat=None) -> Window:
    due, task_idx, child_idx = swarm_mod.arrivals(ctx.cell.traffic, seed, rate, seconds)
    w = Window(srv.scheduling, children, due, task_idx, child_idx, ctx.cell.traffic["workers"])
    w.run(drain_s, heartbeat)
    return w


def run(ctx) -> dict:
    cell, traffic = ctx.cell, ctx.cell.traffic
    rate = float(cell.params["rate_per_s"])
    srv, desc, children, weights, fallback = setup(ctx)
    warm = drive(ctx, srv, children, rate, traffic["warmup_seconds"], ctx.seed + 1, 2.0)
    ctx.marks["warm_up"] = time.perf_counter()
    if warm.errors or fallback.calls:
        raise SystemExit(f"the warm-up failed: {warm.errors[:3]} fallbacks={fallback.calls}")

    svc = srv.scoring_service
    snap0 = svc.snapshot()
    ph0, prom0 = taps.phase_counts(PHASES), taps.prom_series()
    ctx.window_opens()
    tracer_thread = ctx.tracer.record_later(traffic["trace_from_s"], traffic["trace_seconds"])
    win = drive(
        ctx, srv, children, rate, ctx.seconds, ctx.seed, traffic["drain_seconds"],
        heartbeat=Window.pause_meter if ctx.trace else None,
    )
    if tracer_thread is not None:
        tracer_thread.join()
    ctx.window_closes()
    snap1 = svc.snapshot()
    phases = taps.phase_delta(ph0, taps.phase_counts(PHASES))
    prom = taps.series_delta(prom0, taps.prom_series())

    # a decision the scoring service did not answer inside its window plus
    # grace was ranked a rung down: it is a failed operation, counted and
    # left out of the latencies and of the comparison (its ranking is
    # another model's)
    timeout_s = svc.cfg.window_s + svc.cfg.service_grace_s
    answered = np.array([r is not None and len(r) > 0 for r in win.returned]) & (win.end > 0)
    done = answered & ((win.end - win.start) < timeout_s)
    fell = fallback.calls + int(
        sum(v for k, v in prom.items() if "scheduler_serving_fallback_total" in k)
        + sum(v for k, v in prom.items() if k.startswith("dragonfly_scheduler_serving_errors_total"))
    )
    failed = max(int(win.n - done.sum()), min(fell, win.n))
    lat_us = (win.end - (win.t0 + win.due))[done] * 1e6
    from benchmarks.harness.layer_readers import percentile

    metrics = {
        "decisions_per_s": (win.n - failed) / ctx.seconds,
        "decision_p50_us": percentile(lat_us, 50) if lat_us.size else 0.0,
    }
    wait_us = (win.start - (win.t0 + win.due)) * 1e6
    probes = {
        "serving_snapshot": {
            "batches": snap1["batches"] - snap0["batches"],
            "rows_scored": snap1["rows_scored"] - snap0["rows_scored"],
            "window_s": ctx.seconds,
        },
        "prof_phase": phases,
        "prom_series": {**prom, "window_s": ctx.seconds},
        "harness_clock": {
            "decision_latency_us": lat_us.tolist(),
            "process_pause_us": win.pauses_us,
            "queue_wait_us": wait_us[done].tolist(),
            "generator_lateness_us": wait_us[win.slept & done].tolist(),
        },
    }
    # a sample of the window's decisions, drawn from the seed, with the
    # largest candidate sets in it
    rng = np.random.default_rng([ctx.seed, 14])
    idx = np.nonzero(done)[0]
    pick = set(rng.choice(idx, size=min(traffic["sample_decisions"], idx.size), replace=False).tolist())
    sizes = np.array([len(desc["tasks"][k]["peers"]) for k in win.task_idx])
    pick.update(idx[np.argsort(-sizes[idx], kind="stable")[:50]].tolist())
    pick = sorted(pick)
    picks = [(int(win.task_idx[i]), int(win.child_idx[i])) for i in pick]
    returned = [win.returned[i] for i in pick]
    errors, lost, n, pauses = list(win.errors), win.lost, win.n, win.pauses_us
    limits = cell.config["limits"]
    config = cell.config
    srv.scoring_service.stop()
    del srv, children, win

    def after_window() -> list:
        got = judge(desc, weights, config, picks, lambda n: returned[n])
        print(f"reference: {len(picks)} decisions, {got['rows']} candidate rows compared", flush=True)
        return [
            ("decisions_errored", float(len(errors) + lost), 0.0),
            ("parents_outside_the_rules", float(got["illegal"]), 0.0),
            ("parent_count_wrong", float(got["wrong_count"]), 0.0),
            ("rank_gap", got["rank_gap"], limits["rank_gap"]),
        ]

    return {
        "metrics": metrics,
        "probes": probes,
        "attempted": n,
        "failed": failed,
        "after_window": after_window,
        "notes": {
            "rate_per_s": rate,
            "errors": errors[:3],
            "latency_us": {q: percentile(lat_us, q) for q in (50, 90, 95, 99)} if lat_us.size else {},
            "process_pause_us_max": max(pauses) if pauses else 0.0,
            "fell_a_rung": fell,
            "answered_late": int(answered.sum() - done.sum()),
        },
    }
