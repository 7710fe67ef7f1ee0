"""A fitted GraphSAGE version served on another graph than the one it was
fitted on: the version carries its hosts' ids from the fit through
``create_model``'s serializer and the registry, ``ModelRefresher`` installs
it on the scheduler's live probe graph (the same hosts in another order,
two joined, one gone) with every learned row placed by host id, the served
costs meet the plain float32 reference (``benchmarks.harness.reference.GnnReference``)
with the rows placed the same way, and the two controls in the program's
place (rows by position; the reference in fp8) fail ``rank_gap``. A host
that joins inside the capacity rung retraces nothing; a failed install is
counted; the engine's export holds its lock for a copy, not for the walk.
"""

from __future__ import annotations

import gc
import threading
import time

import numpy as np
import pytest

from benchmarks.generators import decide_gnn_under_round as gur
from benchmarks.harness import reference, synth
from dragonfly2_tpu.models.gnn import NodeIds
from dragonfly2_tpu.scheduler import metrics as M
from dragonfly2_tpu.scheduler.evaluator import MLEvaluator
from dragonfly2_tpu.scheduler.model_refresher import ModelRefresher
from dragonfly2_tpu.scheduler.networktopology import NetworkTopology
from dragonfly2_tpu.scheduler.resource import Host, HostManager
from dragonfly2_tpu.scheduler.serving import ScoringService, ServingConfig
from dragonfly2_tpu.schema.columnar import records_to_columns
from dragonfly2_tpu.schema.features import build_probe_graph
from dragonfly2_tpu.topology import TopologyConfig, TopologyEngine
from dragonfly2_tpu.trainer import serving
from dragonfly2_tpu.trainer.serving import (
    GNNScorer,
    deserialize_params_auto,
    node_capacity,
    place_node_rows,
    serialize_params,
)
from dragonfly2_tpu.utils.kvstore import KVStore

N, SEED = 40, 3
GONE = 7  # a host of the upload that has left the live fleet
RANK_GAP_LIMIT = 0.07  # the served configuration's


class Manager:
    """The slice of the manager both ends call: ``create_model`` as the
    trainer's client serializes it, ``ListModels`` / ``GetModelWeights``
    as the refresher reads them; every GraphSAGE version active on arrival."""

    def __init__(self):
        self.versions: list = []

    def create_model(self, model_id, model_type, ip, hostname, params, evaluation):
        self.versions.append((model_id, model_type, serialize_params(params)))

    def ListModels(self, request):
        import manager_pb2

        models = [
            manager_pb2.Model(model_id=mid, type=t, version=v + 1, state="active", created_at_ns=v + 1, updated_at_ns=v + 1)
            for v, (mid, t, _) in enumerate(self.versions)
        ]
        return manager_pb2.ListModelsResponse(models=models)

    def GetModelWeights(self, request):
        import manager_pb2

        mid, t, blob = self.versions[request.version - 1]
        return manager_pb2.ModelWeights(model_id=mid, version=request.version, type=t, weights=blob)


@pytest.fixture(scope="module")
def fleet():
    hosts = synth.fleet(N + 2, SEED)  # the last two join after the upload was taken
    edges = synth.probe_edges(N, SEED)
    upload = synth.topology_records(hosts[:N], edges)
    # the live probes: the upload's without the host that left, two more
    # from each host that joined, in an order of arrival of their own
    rng = np.random.default_rng(SEED)
    live_edges = [e for e in edges if GONE not in e[:2]]
    live_edges += [(N, 1, 9_000_000), (N, 2, 11_000_000), (N + 1, 3, 7_000_000), (4, N + 1, 8_000_000)]
    live_edges = [live_edges[i] for i in rng.permutation(len(live_edges))]
    return hosts, upload, live_edges


@pytest.fixture(scope="module")
def version(fleet):
    """A version as a round registers it: fitted on the upload's graph
    (two epochs: its structure and its ids are what matter here), the
    learned numbers replaced by seeded ones at the same shapes so that
    the costs spread as a long fit's do."""
    from dragonfly2_tpu.trainer.train import GNNFitConfig, train_gnn
    from dragonfly2_tpu.trainer.training import _to_host

    _, upload, _ = fleet
    graph = build_probe_graph(records_to_columns(upload))
    fit = train_gnn(graph, config=GNNFitConfig(epochs=2))
    params = _to_host(fit.params)
    assert isinstance(params["node_ids"], NodeIds) and list(params["node_ids"]) == list(graph.node_ids)
    seeded = synth.gnn_weights(SEED, graph.num_nodes, node_features=graph.node_features.shape[1])
    assert {k: np.shape(v) for k, v in seeded["sage"][0].items()} == {k: np.shape(v) for k, v in params["sage"][0].items()}
    return {**seeded, "node_ids": params["node_ids"]}, graph


def _scheduler(fleet, hosts_alive):
    hosts, _, live_edges = fleet
    hm = HostManager()
    for i in hosts_alive:
        h = hosts[i]
        hm.store(Host(id=h.id, hostname=h.hostname, ip=h.ip, port=h.port))
    engine = TopologyEngine(TopologyConfig(backend="numpy", flush_threshold=10**9, num_landmarks=4))
    now = time.time()
    for s, t, rtt in live_edges:
        if s in hosts_alive and t in hosts_alive:
            engine.adopt(hosts[s].id, hosts[t].id, float(rtt), now)
    engine.flush()
    return NetworkTopology(KVStore(), hm, None, engine=engine)


def _install(manager, nt):
    """One refresher round over ``manager``; returns the scorer it built
    and the records its export read."""
    svc = ScoringService(ServingConfig(window_s=0.002))
    refresher = ModelRefresher(manager, MLEvaluator(serving=svc), serving=svc, networktopology=nt)
    read, built = [], []
    real_export, real_build = nt.export_records, refresher._build_gnn_scorer
    nt.export_records = lambda *a, **k: read.append(real_export(*a, **k)) or read[-1]
    refresher._build_gnn_scorer = lambda p: built.append(real_build(p)) or built[-1]
    installed = refresher.refresh_once()
    return installed, svc, (built[-1] if built else None), (read[-1] if read else None)


def _counter(c, label):
    return c.labels(label).value


def test_a_version_keeps_its_host_ids_through_the_serializer(version):
    params, graph = version
    import jax

    # no leaf: every walk over the version's arrays sees what it saw before ids were carried
    assert len(jax.tree_util.tree_leaves(params)) == len(jax.tree_util.tree_leaves({k: v for k, v in params.items() if k != "node_ids"}))
    back = deserialize_params_auto(serialize_params(params))
    assert isinstance(back["node_ids"], NodeIds) and back["node_ids"] == tuple(graph.node_ids)
    assert np.array_equal(back["node_embed"], params["node_embed"])
    moved = jax.tree_util.tree_map(lambda a: a + 0, back)
    assert moved["node_ids"] == back["node_ids"]


def test_install_places_every_learned_row_by_host_id_on_the_live_graph(fleet, version):
    hosts, upload, _ = fleet
    params, graph = version
    manager = Manager()
    manager.create_model("m-gnn", "gnn", "10.0.0.1", "sched", params, {})
    alive = [i for i in range(N + 2) if i != GONE]
    before = {r: _counter(M.GNN_ROWS_TOTAL, r) for r in ("placed", "default", "dropped")}
    ok0 = _counter(M.GNN_INSTALL_TOTAL, "ok")
    installed, svc, scorer, read = _install(manager, _scheduler(fleet, alive))
    assert installed and svc.snapshot()["model_kind"] == "gnn" and svc.snapshot()["model_version"] == "m-gnn/v1"
    assert _counter(M.GNN_INSTALL_TOTAL, "ok") == ok0 + 1
    moved = {r: _counter(M.GNN_ROWS_TOTAL, r) - before[r] for r in before}
    assert moved == {"placed": N - 1, "default": 2, "dropped": 1} == scorer.rows
    # the live graph is in another order than the upload's, and every host holds its own row
    live = build_probe_graph(records_to_columns(read))
    assert set(live.node_ids) == {hosts[i].id for i in alive}
    assert list(live.node_ids)[:10] != list(graph.node_ids)[:10]
    fitted = {hid: i for i, hid in enumerate(graph.node_ids)}
    rows = scorer.node_rows()
    for hid in live.node_ids:
        want = params["node_embed"][fitted[hid]] if hid in fitted else np.zeros(16, np.float32)
        assert np.array_equal(rows[hid], want), hid
    assert gur.rows_misplaced(rows, gur.host_weights(params), reference.probe_graph(upload)["order"]) == 0

    # served costs against the plain reference on the records that install read
    index = {h.id: i for i, h in enumerate(hosts)}
    weights, order = gur.host_weights(params), reference.probe_graph(upload)["order"]
    by_id = reference.GnnReference(read, index, gur.placed_weights(weights, order, read))
    by_position = reference.GnnReference(read, index, gur.placed_weights(weights, order, read, by="position"))
    child, parents = alive[0], alive[1:25]
    served = scorer.predict_rtt_log_ms([hosts[child].id] * len(parents), [hosts[p].id for p in parents])
    want = by_id.costs([child] * len(parents), parents)
    assert np.abs(served - want).max() < 0.03 and np.ptp(want) > 0.3
    assert np.abs(served - by_position.costs([child] * len(parents), parents)).max() > 0.1

    def worst_gap(costs_in_place):
        """The widest ``rank_gap`` over every live host asking for four
        parents among all the others, ranked by ``costs_in_place(child,
        parents)`` and held to the float32 reference with rows by id."""
        worst = 0.0
        for child in alive:
            parents = [p for p in alive if p != child]
            ref = dict(zip(parents, by_id.costs([child] * len(parents), parents).tolist()))
            ranked = [parents[j] for j in np.argsort(costs_in_place(child, parents), kind="stable")[:4]]
            worst = max(worst, reference.rank_gap(ranked, ref, closed=True))
        return worst

    program = lambda c, ps: scorer.predict_rtt_log_ms([hosts[c].id] * len(ps), [hosts[p].id for p in ps])  # noqa: E731
    assert worst_gap(program) < RANK_GAP_LIMIT
    # the two controls in the program's place
    assert worst_gap(lambda c, ps: by_position.costs([c] * len(ps), ps)) > RANK_GAP_LIMIT
    fp8 = reference.GnnReference(read, index, gur.placed_weights(weights, order, read), precision="fp8")
    assert worst_gap(lambda c, ps: fp8.costs([c] * len(ps), ps)) > RANK_GAP_LIMIT
    svc.stop()


def test_rows_by_position_on_a_graph_in_another_order_are_misplaced(fleet, version, monkeypatch):
    """The control: a scorer that joins the fitted table to the live graph
    row by row (what ``apply_graphsage`` did with whatever it was handed)."""
    hosts, upload, _ = fleet
    params, graph = version
    manager = Manager()
    manager.create_model("m-gnn", "gnn", "10.0.0.1", "sched", params, {})
    alive = list(range(N))  # the same hosts, so that the positional join has the rows to serve
    by_position = lambda p, ids: ({k: v for k, v in p.items() if k != "node_ids"}, {"placed": len(ids), "default": 0, "dropped": 0})  # noqa: E731
    monkeypatch.setattr(serving, "place_node_rows", by_position)
    installed, svc, scorer, read = _install(manager, _scheduler(fleet, alive))
    svc.stop()
    assert installed  # silently: nothing raises, every host is served some host's row
    wrong = gur.rows_misplaced(scorer.node_rows(), gur.host_weights(params), reference.probe_graph(upload)["order"])
    assert wrong > N // 2


def test_a_version_that_names_no_hosts_is_refused_on_a_graph_of_another_size(fleet, version):
    params, graph = version
    legacy = {k: v for k, v in params.items() if k != "node_ids"}
    placed, rows = place_node_rows(legacy, list(graph.node_ids))
    assert rows == {"placed": graph.num_nodes, "default": 0, "dropped": 0}
    assert np.array_equal(placed["node_embed"], params["node_embed"])
    with pytest.raises(ValueError, match="names no hosts"):
        place_node_rows(legacy, list(graph.node_ids)[:-1])
    with pytest.raises(ValueError, match="rows for"):
        place_node_rows({**params, "node_ids": NodeIds(list(params["node_ids"])[:-1])}, list(graph.node_ids))


def test_a_failed_install_is_counted_and_leaves_the_slot_alone(fleet, version):
    params, _ = version
    manager = Manager()
    manager.create_model("m-gnn", "gnn", "10.0.0.1", "sched", {k: v for k, v in params.items() if k != "node_ids"}, {})
    failed0, ok0 = _counter(M.GNN_INSTALL_TOTAL, "failed"), _counter(M.GNN_INSTALL_TOTAL, "ok")
    # a version from before ids were carried, on a live graph with two more hosts: refused
    installed, svc, scorer, _ = _install(manager, _scheduler(fleet, list(range(N + 2))))
    assert not installed and scorer is None and svc.snapshot()["model_kind"] == ""
    assert _counter(M.GNN_INSTALL_TOTAL, "failed") == failed0 + 1 and _counter(M.GNN_INSTALL_TOTAL, "ok") == ok0
    # no probe-graph source: skipped, and counted as that
    skipped0 = _counter(M.GNN_INSTALL_TOTAL, "skipped")
    refresher = ModelRefresher(manager, MLEvaluator(serving=svc), serving=svc, networktopology=None)
    assert not refresher.refresh_once() and _counter(M.GNN_INSTALL_TOTAL, "skipped") == skipped0 + 1
    svc.stop()


def test_a_host_that_joins_inside_the_capacity_rung_retraces_nothing(fleet, version):
    from hack.dfanalyze import jitwitness

    hosts, upload, _ = fleet
    params, _ = version
    assert [node_capacity(n) for n in (1, 64, 65, 1040, 2048, 2049)] == [64, 64, 128, 2048, 2048, 4096]

    def scorer(n_hosts):
        recs = synth.topology_records(hosts[:n_hosts], synth.probe_edges(n_hosts, SEED))
        graph = build_probe_graph(records_to_columns(recs))
        s = GNNScorer(params, graph)
        for rows in (8, 16, 32, 64, 128):
            s.predict_rtt_log_ms([graph.node_ids[0]] * rows, [graph.node_ids[1]] * rows)
        return s

    scorer(N)  # compiles the embed and every rung's edge head at this capacity
    with jitwitness.compile_tap() as tap:
        grown = scorer(N + 2)
    assert tap.count == 0, tap.names
    assert grown.rows["default"] == 2 and node_capacity(N) == node_capacity(N + 2)


def test_export_answers_a_concurrent_rtt_join_within_a_slice(fleet):
    """The export's walk runs outside the engine's lock: while one thread
    exports a graph of some thousands of edges over and over, a decision's
    rtt join never waits a bounded slice (20 ms) for the lock."""
    hosts = synth.fleet(400, SEED)
    edges = synth.probe_edges(400, SEED)
    hm = HostManager()
    for h in hosts:
        hm.store(Host(id=h.id, hostname=h.hostname, ip=h.ip, port=h.port))
    engine = TopologyEngine(TopologyConfig(backend="numpy", flush_threshold=10**9, num_landmarks=4))
    now = time.time()
    for s, t, rtt in edges:
        engine.adopt(hosts[s].id, hosts[t].id, float(rtt), now)
    engine.flush()
    from benchmarks.tools.swap_holds import TimedLock

    holds: list = []  # (when taken, how long held) of every outermost hold
    engine._lock = TimedLock(engine._lock, holds)
    stop = threading.Event()
    exports = []

    def export():
        while not stop.is_set():
            exports.append(len(engine.export_records(hm, 5)))

    t = threading.Thread(target=export, daemon=True)
    ids = [h.id for h in hosts]
    # the flush inside an export rebuilds the arrays under the lock; what is
    # measured here is the export's own hold, so the flush's are told apart
    real_flush, engine.flush = engine.flush, lambda *a, **k: 0
    # ... and so is the collector's: the copy allocates a tuple an edge, and a
    # full collection that falls inside the hold walks everything this test
    # process has imported (60-100 ms here). The serving process freezes what
    # is alive at start-up (colocated.settle), and so does this
    gc.collect()
    gc.freeze()
    t.start()
    waits = []
    deadline = time.perf_counter() + 1.5
    while time.perf_counter() < deadline:
        t0 = time.perf_counter()
        engine.rtt_affinity_pairs([ids[0]] * 16, ids[1:17])
        waits.append(time.perf_counter() - t0)
        time.sleep(0.001)
    stop.set()
    t.join(timeout=10.0)
    gc.unfreeze()
    engine.flush = real_flush
    assert exports and exports[0] == 400
    held = [took for _, took in holds]
    assert max(held) < 0.02, max(held)  # the copy, not the walk (some tens of ms here)
    assert np.percentile(waits, 99) < 0.02
