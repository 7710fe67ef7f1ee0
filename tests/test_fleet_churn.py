"""A fleet that joins and leaves under the served GraphSAGE scorer and the
topology engine (ISSUE 36, deployment ``autoscaled-cluster-gnn``):

- ``ModelRefresher`` embeds the loaded version again at the first poll
  after the live graph has moved, and not when it has not; the re-embed
  places rows by id on a graph with joined, departed and surviving hosts
  and meets the plain reference (``membership.Replay`` + ``GnnReference``),
  with the fp8 and rows-by-position controls failing in its place;
- a fleet that grows across a capacity rung compiles nothing at the
  crossing, in the served scorer and in the engine;
- a leave is a delta: ``delete_host`` runs no kernel and rebuilds no
  arrays, its host is gone for every reader at once, its slot goes to the
  next host that joins, and capacities follow the live count under
  turnover;
- the engine's estimates after joins, leaves and a flush meet a NumPy
  landmark estimate on the replayed edge set;
- a decision dropped from the GraphSAGE rung for a host outside the
  served graph is counted by that reason;
- once ``LeaveHost`` has returned no decision names that host, under
  concurrent decisions.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from benchmarks.generators import decide_gnn_under_round as gur
from benchmarks.harness import membership, reference, synth
from dragonfly2_tpu.scheduler import metrics as M
from dragonfly2_tpu.scheduler.evaluator import MLEvaluator
from dragonfly2_tpu.scheduler.model_refresher import ModelRefresher
from dragonfly2_tpu.scheduler.networktopology import NetworkTopology
from dragonfly2_tpu.scheduler.resource import Host, HostManager, HostType
from dragonfly2_tpu.scheduler.serving import GNNServed, ScoringService, ServingConfig
from dragonfly2_tpu.schema.columnar import records_to_columns
from dragonfly2_tpu.schema.features import build_probe_graph
from dragonfly2_tpu.topology import TopologyConfig, TopologyEngine
from dragonfly2_tpu.topology import metrics as TM
from dragonfly2_tpu.trainer import serving
from dragonfly2_tpu.trainer.serving import GNNScorer, node_capacity, past_prepare_share
from dragonfly2_tpu.utils import profiling
from dragonfly2_tpu.utils.kvstore import KVStore
from test_gnn_rows_by_id import Manager

N, SEED = 40, 5
RANK_GAP_LIMIT = 0.07  # the served configuration's, at seeded weights


def _fleet(n_hosts: int, n_fitted: int):
    hosts = synth.fleet(n_hosts, SEED)
    edges = synth.probe_edges(n_fitted, SEED)
    return hosts, edges


class Cluster:
    """A host manager, an engine and a refresher over them, with the log
    ``membership.Replay`` reads written beside every call."""

    def __init__(self, hosts, backend="numpy", landmarks=4):
        self.hosts = hosts
        self.hm = HostManager()
        self.engine = TopologyEngine(TopologyConfig(backend=backend, flush_threshold=10**9, num_landmarks=landmarks))
        self.nt = NetworkTopology(KVStore(), self.hm, None, engine=self.engine)
        self.log: list = []
        self.t0 = time.time()  # probes carry wall-clock times: the engine ages and purges by them
        self.replay_args = (landmarks, 3, 5)
        real_flush, real_export = self.engine.flush, self.nt.export_records

        def flush(*a, **k):
            n = real_flush(*a, **k)
            self.log.append(["flush", 0, 0, n])
            return n

        def export(*a, **k):
            out = real_export(*a, **k)
            self.log.append(["export", 0, 0])
            return out

        self.engine.flush, self.nt.export_records = flush, export

    def announce(self, i):
        h = self.hosts[i]
        self.hm.store(Host(id=h.id, type=HostType(h.type), hostname=h.hostname, ip=h.ip, port=h.port, network=h.network))
        self.log.append(["announce", 0, 0, h.id])

    def adopt(self, s, t, rtt, at=1.0):
        at += self.t0
        self.engine.adopt(self.hosts[s].id, self.hosts[t].id, float(rtt), at)
        self.log.append(["adopt", 0, 0, self.hosts[s].id, self.hosts[t].id, float(rtt), at])

    def probe(self, s, t, rtt, at=2.0):
        at += self.t0
        self.log.append(["probe", 0, 0, self.hosts[s].id, self.hosts[t].id, float(rtt), at])
        self.engine.enqueue(self.hosts[s].id, self.hosts[t].id, int(rtt), at)

    def leave(self, i):
        """What the service's LeaveHost does to these two."""
        self.hm.delete(self.hosts[i].id)
        self.nt.delete_host(self.hosts[i].id)
        self.log.append(["leave", 0, 0, self.hosts[i].id])

    def replay(self) -> membership.Replay:
        r = membership.Replay(*self.replay_args)
        for entry in self.log:
            r.apply(entry)
        assert not r.faults, r.faults
        return r


@pytest.fixture(scope="module")
def version():
    """Seeded weights at the served widths that name the fitted hosts."""
    from dragonfly2_tpu.models.gnn import NodeIds

    hosts, edges = _fleet(N + 6, N)
    upload = synth.topology_records(hosts[:N], edges)
    graph = build_probe_graph(records_to_columns(upload))
    seeded = synth.gnn_weights(SEED, graph.num_nodes, node_features=graph.node_features.shape[1])
    return {**seeded, "node_ids": NodeIds(graph.node_ids)}, upload, hosts, edges


def _refresher(manager, nt):
    svc = ScoringService(ServingConfig(window_s=0.002))
    refresher = ModelRefresher(manager, MLEvaluator(serving=svc), serving=svc, networktopology=nt)
    built = []
    real = refresher._build_gnn_scorer
    refresher._build_gnn_scorer = lambda p: built.append(real(p)) or built[-1]
    return svc, refresher, built


def _count(counter, label):
    return counter.labels(label).value


def test_the_loaded_version_is_embedded_again_when_the_live_graph_has_moved_and_only_then(version):
    params, upload, hosts, edges = version
    cluster = Cluster(hosts)
    for i in range(N + 2):  # two hosts the upload never saw are live from the start
        cluster.announce(i)
    rng = np.random.default_rng(SEED)
    for k in rng.permutation(len(edges)):
        cluster.adopt(*edges[int(k)])
    cluster.adopt(N, 1, 9e6), cluster.adopt(N + 1, 3, 7e6), cluster.adopt(4, N + 1, 8e6)
    cluster.engine.flush()
    manager = Manager()
    manager.create_model("m-gnn", "gnn", "10.0.0.1", "sched", params, {})
    svc, refresher, built = _refresher(manager, cluster.nt)
    svc.start()
    fetches = []
    real_get = manager.GetModelWeights
    manager.GetModelWeights = lambda req: fetches.append(req.version) or real_get(req)
    assert refresher.refresh_once() and built[-1].rows == {"placed": N, "default": 2, "dropped": 0}
    ok0 = _count(M.GNN_REEMBED_TOTAL, "ok")
    rows0 = {r: _count(M.GNN_ROWS_TOTAL, r) for r in ("placed", "default", "dropped")}
    spans0 = profiling.phase_type("scheduler.gnn_reembed").snapshot()["count"]

    # nothing has moved: a poll embeds nothing, fetches nothing
    assert not refresher.refresh_once() and len(built) == 1 and fetches == [1]
    # a probe that only moves an edge's average moves neither set
    s, t, rtt = edges[0]
    cluster.probe(s, t, rtt + 1e6)
    assert not refresher.refresh_once() and len(built) == 1

    # two hosts join (announce, probes both ways), a fitted host leaves
    for j, (a, b) in ((N + 2, (5, 6)), (N + 3, (7, 8))):
        cluster.announce(j)
        cluster.probe(j, a, 5e6 + j), cluster.probe(b, j, 6e6 + j)
    gone = 9
    cluster.leave(gone)
    assert refresher.refresh_once() and len(built) == 2 and fetches == [1]  # the weights it already held
    assert _count(M.GNN_REEMBED_TOTAL, "ok") == ok0 + 1
    assert profiling.phase_type("scheduler.gnn_reembed").snapshot()["count"] == spans0 + 1
    scorer = built[-1]
    assert scorer.rows == {"placed": N - 1, "default": 4, "dropped": 1}
    assert {r: _count(M.GNN_ROWS_TOTAL, r) - rows0[r] for r in rows0} == scorer.rows
    assert svc.snapshot()["model_version"] == "m-gnn/v1" and not scorer.has_host(hosts[gone].id) and scorer.has_host(hosts[N + 3].id)
    # again nothing has moved
    assert not refresher.refresh_once() and len(built) == 2

    # against the replay: the same hosts in the same order, the same counts, every row by id
    replay = cluster.replay()
    should = replay.exports[-1]
    fitted = reference.probe_graph(upload)["order"]
    assert list(scorer.node_rows()) == membership.nodes_of(should)
    assert membership.row_counts(should, fitted) == scorer.rows
    weights = gur.host_weights(params)
    assert gur.rows_misplaced(scorer.node_rows(), weights, fitted) == 0
    index = {h.id: i for i, h in enumerate(hosts)}
    read = membership.records_of(should, lambda hid: hosts[index[hid]])
    by_id = reference.GnnReference(read, index, gur.placed_weights(weights, fitted, read))
    alive = [index[h] for h in membership.nodes_of(should)]

    def worst_gap(costs_in_place):
        worst = 0.0
        for child in alive[::3]:
            parents = [p for p in alive if p != child]
            ref = dict(zip(parents, by_id.costs([child] * len(parents), parents).tolist()))
            ranked = [parents[j] for j in np.argsort(costs_in_place(child, parents), kind="stable")[:4]]
            worst = max(worst, reference.rank_gap(ranked, ref, closed=True))
        return worst

    program = lambda c, ps: scorer.predict_rtt_log_ms([hosts[c].id] * len(ps), [hosts[p].id for p in ps])  # noqa: E731
    assert worst_gap(program) < RANK_GAP_LIMIT
    fp8 = reference.GnnReference(read, index, gur.placed_weights(weights, fitted, read), precision="fp8")
    assert worst_gap(lambda c, ps: fp8.costs([c] * len(ps), ps)) > RANK_GAP_LIMIT
    by_position = reference.GnnReference(read, index, gur.placed_weights(weights, fitted, read, by="position"))
    assert worst_gap(lambda c, ps: by_position.costs([c] * len(ps), ps)) > RANK_GAP_LIMIT
    svc.stop()


def test_a_failed_reembed_is_counted_and_keeps_the_embed_in_force(version, monkeypatch):
    params, upload, hosts, edges = version
    cluster = Cluster(hosts)
    for i in range(N):
        cluster.announce(i)
    for e in edges:
        cluster.adopt(*e)
    cluster.engine.flush()
    manager = Manager()
    manager.create_model("m-gnn", "gnn", "10.0.0.1", "sched", params, {})
    svc, refresher, built = _refresher(manager, cluster.nt)
    svc.start()
    assert refresher.refresh_once()
    in_force = svc._served
    cluster.leave(3)
    failed0 = _count(M.GNN_REEMBED_TOTAL, "failed")
    monkeypatch.setattr(serving, "place_node_rows", lambda p, ids: (_ for _ in ()).throw(ValueError("no rows")))
    assert not refresher.refresh_once() and _count(M.GNN_REEMBED_TOTAL, "failed") == failed0 + 1
    assert svc._served is in_force
    svc.stop()


def test_a_fleet_that_grows_across_a_capacity_rung_compiles_nothing_at_the_crossing(version):
    """The served scorer: the install that finds the fleet past three
    quarters of its rung compiles the next rung's embed and edge heads
    (``scheduler.gnn_rung_prepare``); the re-embed after the crossing
    compiles nothing. The engine on the jax backend likewise."""
    from hack.dfanalyze import jitwitness

    params, upload, _, _ = version
    n0 = 60  # of 64: past the share; the next capacity is 128
    hosts = synth.fleet(80, SEED + 1)
    assert node_capacity(n0) == 64 and past_prepare_share(n0, 64) and not past_prepare_share(65, 128)
    cluster = Cluster(hosts, backend="jax")
    rng = np.random.default_rng(SEED)
    for i in range(n0):
        cluster.announce(i)
    for i in range(n0):
        for t in rng.choice(n0 - 1, size=2, replace=False):  # 120 edges of 128: the edge capacity is crossed too
            cluster.adopt(i, int(t) + (int(t) >= i), 1e6 * (2 + int(t) % 7))
    cluster.engine.flush()
    ids = [h.id for h in hosts]
    for rows in (8, 16):
        cluster.engine.rtt_affinity_pairs([ids[0]] * rows, ids[1 : rows + 1])
    cluster.engine.est_rtt_ns(ids[0], ids[n0 - 1])
    cluster.engine.flush()  # past the share: the kernels for 128 nodes, at the rungs just met, on a thread of their own
    assert cluster.engine.wait_prepared(120.0)

    manager = Manager()
    manager.create_model("m-gnn", "gnn", "10.0.0.1", "sched", {k: v for k, v in params.items() if k != "node_ids"} | {"node_ids": type(params["node_ids"])(ids[:40])}, {})
    svc, refresher, built = _refresher(manager, cluster.nt)
    svc.start()
    prepared0 = profiling.phase_type("scheduler.gnn_rung_prepare").snapshot()["count"]
    assert refresher.refresh_once() and built[-1].capacity == 64
    assert profiling.phase_type("scheduler.gnn_rung_prepare").snapshot()["count"] == prepared0 + 1

    with jitwitness.compile_tap() as tap:
        for j in range(n0, n0 + 10):  # the fleet passes 64
            cluster.announce(j)
            cluster.probe(j, j - n0, 3e6 + j), cluster.probe(j - n0 + 1, j, 4e6 + j)
        assert refresher.refresh_once() and built[-1].capacity == 128  # flush at the new capacity, embed, every rung
        for rows in (8, 16):
            cluster.engine.rtt_affinity_pairs([ids[0]] * rows, ids[n0 : n0 + rows])
        cluster.engine.est_rtt_ns(ids[0], ids[n0 + 1])
    assert tap.count == 0, tap.names
    assert built[-1].rows["default"] == 30 and len(cluster.engine.store.index) == 70
    assert (TM.CAPACITY_GAUGE.labels("nodes").value, TM.CAPACITY_GAUGE.labels("edges").value) == (128, 256)
    # after the crossing the fleet stands at half its rung: nothing more is prepared
    assert profiling.phase_type("scheduler.gnn_rung_prepare").snapshot()["count"] == prepared0 + 1
    svc.stop()


def test_delete_host_runs_no_kernel_and_the_host_is_gone_for_every_reader_at_once():
    hosts, edges = _fleet(N, N)
    cluster = Cluster(hosts)
    for i in range(N):
        cluster.announce(i)
    for e in edges:
        cluster.adopt(*e)
    cluster.engine.flush()
    engine, ids = cluster.engine, [h.id for h in hosts]
    calls = []
    for name in ("decay_weights", "khop_rtt", "landmark_distances"):
        real = getattr(engine.kernels, name)
        setattr(engine.kernels, name, lambda *a, _r=real, _n=name, **k: calls.append(_n) or _r(*a, **k))
    builds = []
    real_build = engine.store.build_arrays
    engine.store.build_arrays = lambda *a, **k: builds.append(1) or real_build(*a, **k)
    gone = 5
    neighbor = next(s for s, t, _ in edges if t == gone)
    assert engine.est_rtt_ns(ids[gone], ids[neighbor]) is not None
    edges_dict = engine.store.edges
    spans0 = profiling.phase_type("topology.delete_host").snapshot()["count"]
    cluster.leave(gone)
    assert not calls and not builds and engine.store.edges is edges_dict  # no kernel, no build, no dict rebuilt
    assert profiling.phase_type("topology.delete_host").snapshot()["count"] == spans0 + 1
    # every reader, before any flush
    assert engine.est_rtt_ns(ids[gone], ids[neighbor]) is None and engine.est_rtt_ns(ids[neighbor], ids[gone]) is None
    assert engine.rtt_affinity_pairs([ids[gone], ids[neighbor]], [ids[neighbor], ids[gone]]).tolist() == [0.0, 0.0]
    assert engine.neighbors(ids[gone]) == [] and all(n["host_id"] != ids[gone] for n in engine.neighbors(ids[neighbor]))
    assert engine.khop_rtt_log_ms(ids[gone]) is None
    exported = cluster.nt.export_records()
    assert all(r.host.id != ids[gone] and all(d.id != ids[gone] for d in r.dest_hosts) for r in exported)
    assert all(c["host_id"] != ids[gone] for c in engine.centrality())
    # a probe that was waiting for a flush does not bring it back
    cluster.probe(gone, 1, 5e6)
    cluster.leave(gone)
    engine.flush()
    assert ids[gone] not in engine.store.index and engine.stats()["hosts"] == N - 1


def test_a_departed_hosts_slot_goes_to_the_next_host_that_joins_after_a_flush():
    hosts, edges = _fleet(N + 4, N)
    cluster = Cluster(hosts)
    for i in range(N):
        cluster.announce(i)
    for e in edges:
        cluster.adopt(*e)
    cluster.engine.flush()
    store = cluster.engine.store
    slot = store.index[hosts[7].id]
    cluster.leave(7)
    # held back while the arrays in force still carry the departed host's row
    cluster.announce(N), cluster.adopt(N, 1, 4e6)
    assert store.index[hosts[N].id] == N and store.free_slots == 1
    cluster.engine.flush()
    cluster.announce(N + 1), cluster.adopt(N + 1, 2, 4e6)
    assert store.index[hosts[N + 1].id] == slot and store.free_slots == 0 and store.ids[slot] == hosts[N + 1].id
    # the newcomer does not inherit the departed host's landmark row
    assert cluster.engine.rtt_affinity_pairs([hosts[N + 1].id], [hosts[30].id])[0] == cluster.replay().affinity(hosts[N + 1].id, hosts[30].id)
    cluster.engine.flush()
    assert TM.HOST_GAUGE.labels("live").value == N + 1 and TM.HOST_GAUGE.labels("free_slots").value == 0


def test_a_fleet_that_turns_over_three_times_its_size_keeps_its_capacities():
    n = 48  # node capacity 64; five edges a host: edge capacity 256
    hosts = synth.fleet(n + 3 * n, SEED)
    cluster = Cluster(hosts)
    rng = np.random.default_rng(SEED)
    live = list(range(n))
    for i in live:
        cluster.announce(i)
    for i in live:
        for t in rng.choice(n - 1, size=3, replace=False):
            cluster.adopt(i, int(t) + (int(t) >= i), 2e6)
    cluster.engine.flush()
    caps = (TM.CAPACITY_GAUGE.labels("nodes").value, TM.CAPACITY_GAUGE.labels("edges").value)
    assert caps[0] == 64
    for j in range(n, 4 * n):  # one leaves, one joins, a flush every eighth turn
        cluster.leave(live.pop(int(rng.integers(0, len(live)))))
        cluster.announce(j)
        for t in rng.choice(len(live), size=3, replace=False):
            cluster.probe(j, live[int(t)], 2e6)
        live.append(j)
        if j % 8 == 0:
            cluster.engine.flush()
    cluster.engine.flush()
    store = cluster.engine.store
    assert len(store.index) == n and store.num_hosts <= 64  # slots ever handed out: the live count and the leaves between two flushes
    assert (TM.CAPACITY_GAUGE.labels("nodes").value, TM.CAPACITY_GAUGE.labels("edges").value) == caps
    assert set(store.index) == cluster.replay().engine_hosts() == {hosts[i].id for i in live}


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_the_engines_estimates_after_joins_leaves_and_a_flush_meet_the_replays(backend):
    hosts, edges = _fleet(N + 6, N)
    cluster = Cluster(hosts, backend=backend, landmarks=4)
    for i in range(N):
        cluster.announce(i)
    for e in edges:
        cluster.adopt(*e)
    cluster.engine.flush()
    rng = np.random.default_rng(SEED)
    live = list(range(N))
    for j in range(N, N + 6):
        cluster.leave(live.pop(int(rng.integers(0, len(live)))))
        cluster.announce(j)
        for t in rng.choice(len(live), size=3, replace=False):
            cluster.probe(j, live[int(t)], 1e6 * float(rng.uniform(2, 40)))
        cluster.probe(live[int(rng.integers(0, len(live)))], j, 1e6 * float(rng.uniform(2, 40)))
        live.append(j)
        if j == N + 3:
            cluster.engine.flush()

    def compare():
        replay = cluster.replay()
        ids = [hosts[i].id for i in range(N + 6)]  # departed and not yet flushed hosts among them
        src = [ids[int(i)] for i in rng.integers(0, len(ids), 300)]
        dst = [ids[int(i)] for i in rng.integers(0, len(ids), 300)]
        got = cluster.engine.rtt_affinity_pairs(src, dst)
        want = np.array([replay.affinity(a, b) for a, b in zip(src, dst)], np.float32)
        assert np.abs(got - want).max() < 1e-5, np.abs(got - want).max()
        assert (want > 0).sum() > 100 and (want == 0).sum() > 5
        for a, b in list(zip(src, dst))[:40]:
            ns = cluster.engine.est_rtt_ns(a, b)
            aff = 0.0 if ns is None else float(np.log1p(ns / 1e6) / 10.0)
            assert aff == pytest.approx(replay.affinity(a, b), abs=1e-5)

    compare()  # two joins still wait for a flush
    cluster.engine.flush()
    compare()


def test_a_decision_dropped_for_a_host_outside_the_served_graph_is_counted_by_that_reason(version):
    params, upload, hosts, _ = version
    graph = build_probe_graph(records_to_columns(upload))
    svc = ScoringService(ServingConfig(window_s=0.002))
    svc.install(GNNServed(GNNScorer(params, graph)), version="m-gnn/v1")
    svc.start()
    known, stranger = [h.id for h in hosts[:6]], hosts[N + 1].id
    feats = np.zeros((5, 19), np.float32)
    before = M.GNN_UNKNOWN_HOST_TOTAL.value
    inside = [(known[0], p) for p in known[1:4]]
    outside = [(known[0], known[1]), (known[0], stranger)]
    out = svc.score_wave(feats, inside + outside, [3, 2])
    assert out[0] is not None and out[1] is None and M.GNN_UNKNOWN_HOST_TOTAL.value == before + 1
    from dragonfly2_tpu.scheduler.serving import ServingUnsupported

    with pytest.raises(ServingUnsupported):
        svc.score_wave(feats[:2], outside, [2])
    with pytest.raises(ServingUnsupported):
        svc.score(feats[:2], outside)
    assert M.GNN_UNKNOWN_HOST_TOTAL.value == before + 3
    svc.score_wave(feats[:3], inside, [3])
    assert M.GNN_UNKNOWN_HOST_TOTAL.value == before + 3
    svc.stop()


def test_once_leave_host_has_returned_no_decision_names_that_host():
    """Sixteen threads ask for parents while hosts leave through the
    service's handler: a parent on a host whose ``LeaveHost`` had returned
    before the decision began is never returned."""
    import scheduler_pb2

    from dragonfly2_tpu.scheduler import resource as res
    from dragonfly2_tpu.scheduler.evaluator import BaseEvaluator
    from dragonfly2_tpu.scheduler.scheduling import Scheduling, SchedulingConfig
    from dragonfly2_tpu.scheduler.service import SchedulerService

    resource = res.Resource()
    engine = TopologyEngine(TopologyConfig(backend="numpy", flush_threshold=10**9))
    nt = NetworkTopology(KVStore(), resource.host_manager, None, engine=engine)
    scheduling = Scheduling(BaseEvaluator(), SchedulingConfig(retry_interval=0.01))
    service = SchedulerService(resource, scheduling, networktopology=nt)
    hosts = [res.Host(id=f"h{i}", hostname=f"h{i}", ip="10.0.0.1", port=8002) for i in range(60)]
    for h in hosts:
        resource.host_manager.store(h)
        engine.adopt(h.id, hosts[(int(h.id[1:]) + 1) % 60].id, 5e6, time.time())
    engine.flush()
    task = res.Task("t", "https://origin.example.com/t")
    task.total_piece_count, task.content_length = 8, 8 << 20
    resource.task_manager.store(task)
    host_of = {}
    for i, h in enumerate(hosts[:50]):
        p = res.Peer(f"p{i}", task, h)
        for ev in (res.PEER_EVENT_REGISTER_NORMAL, res.PEER_EVENT_DOWNLOAD, res.PEER_EVENT_DOWNLOAD_SUCCEEDED):
            p.fsm.event(ev)
        p.finished_pieces = set(range(8))
        resource.peer_manager.store(p)
        host_of[p.id] = h.id
    children = []
    for i, h in enumerate(hosts[50:]):
        c = res.Peer(f"c{i}", task, h)
        c.fsm.event(res.PEER_EVENT_REGISTER_NORMAL)
        resource.peer_manager.store(c)
        children.append(c)
    left_at: dict = {}
    seen: list = []  # (decision began, parent host ids)
    stop = threading.Event()

    def decide(k):
        while not stop.is_set():
            t0 = time.perf_counter()
            parents, found = scheduling.find_candidate_parents(children[k % len(children)])
            if found:
                seen.append((t0, [host_of[p.id] for p in parents]))

    workers = [threading.Thread(target=decide, args=(k,), daemon=True) for k in range(16)]
    for w in workers:
        w.start()
    for h in hosts[:40]:
        service.LeaveHost(scheduler_pb2.LeaveHostRequest(host_id=h.id), None)
        left_at[h.id] = time.perf_counter()
        assert resource.host_manager.load(h.id) is None and h.id not in engine.store.index
        time.sleep(0.002)
    time.sleep(0.05)
    stop.set()
    for w in workers:
        w.join(timeout=10.0)
    assert len(seen) > 100
    late = [(t0, hid) for t0, hids in seen for hid in hids if hid in left_at and left_at[hid] < t0]
    assert not late, late[:3]
    after = [hids for t0, hids in seen if t0 > max(left_at.values())]
    assert after and all(set(hids) <= {h.id for h in hosts[40:50]} for hids in after)
