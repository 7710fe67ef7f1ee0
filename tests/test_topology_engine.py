"""TPU-resident topology engine: device sparse adjacency fed by the
probe plane through the batching delta queue, landmark RTT inference
for unprobed pairs, staleness decay, and the consumer wiring
(NetworkTopology mirror, MLEvaluator rtt feature, seed placement,
query RPC)."""

import numpy as np
import pytest

from dragonfly2_tpu.scheduler.networktopology import NetworkTopology, Probe
from dragonfly2_tpu.scheduler.resource import Host, HostManager
from dragonfly2_tpu.topology import TopologyConfig, TopologyEngine
from dragonfly2_tpu.topology.csr import AdjacencyStore
from dragonfly2_tpu.topology.kernels import INF_MS, JaxKernels, NumpyKernels
from dragonfly2_tpu.utils.kvstore import KVStore

MS = 1_000_000  # ns per ms


def make_engine(**kw) -> TopologyEngine:
    kw.setdefault("backend", "numpy")  # the no-accelerator fallback path
    kw.setdefault("flush_threshold", 10**9)  # explicit flushes only
    kw.setdefault("num_landmarks", 4)
    return TopologyEngine(TopologyConfig(**kw))


def feed_star(eng: TopologyEngine, spokes: int = 5, at: float = 1000.0) -> None:
    """Hub topology: hub↔spoke probed, spokes never probed pairwise."""
    for i in range(1, spokes + 1):
        eng.enqueue("h0", f"h{i}", rtt_ns=5 * i * MS, created_at=at)
        eng.enqueue(f"h{i}", "h0", rtt_ns=5 * i * MS, created_at=at)


class TestDeltaQueueAndCSR:
    def test_incremental_flushes_equal_from_scratch_rebuild(self):
        """Many small delta flushes must land on the same adjacency as
        one from-scratch build over the same probe sequence."""
        rng = np.random.default_rng(0)
        probes = []
        for i in range(300):
            s, d = rng.integers(0, 12, size=2)
            if s != d:
                probes.append(
                    (f"h{s}", f"h{d}", int(rng.integers(1, 50)) * MS, 1000.0 + i)
                )

        incremental = make_engine()
        for i, (s, d, r, t) in enumerate(probes):
            incremental.enqueue(s, d, r, t)
            if i % 7 == 0:
                incremental.flush(now=2000.0)
        incremental.flush(now=2000.0)

        scratch = AdjacencyStore()
        for s, d, r, t in probes:
            scratch.apply_probe(s, d, r, t)

        assert incremental.store.index == scratch.index
        assert set(incremental.store.edges) == set(scratch.edges)
        for k, v in scratch.edges.items():
            assert incremental.store.edges[k][0] == pytest.approx(v[0])

        # the built CSR arrays agree too (same capacity policy)
        a = incremental.store.build_arrays(2000.0)
        b = scratch.build_arrays(2000.0)
        e = a["num_edges"]
        assert e == b["num_edges"]
        np.testing.assert_array_equal(a["edge_src"][:e], b["edge_src"][:e])
        np.testing.assert_array_equal(a["edge_dst"][:e], b["edge_dst"][:e])
        np.testing.assert_allclose(a["rtt_log_ms"][:e], b["rtt_log_ms"][:e])

    def test_csr_row_ptr_indexes_out_edges(self):
        eng = make_engine()
        feed_star(eng)
        eng.flush(now=1000.0)
        arr = eng.store.build_arrays(1000.0)
        idx = eng.store.index["h0"]
        lo, hi = int(arr["row_ptr"][idx]), int(arr["row_ptr"][idx + 1])
        assert hi - lo == 5  # hub has 5 out-edges
        np.testing.assert_array_equal(arr["edge_src"][lo:hi], idx)

    def test_ewma_matches_kv_path(self):
        """The engine's per-edge EWMA fold must agree with the KV
        store's int-arithmetic fold exactly."""
        hm = HostManager()
        for i in range(2):
            hm.store(Host(id=f"h{i}", hostname=f"n{i}", ip="10.0.0.1", port=1))
        eng = make_engine()
        nt = NetworkTopology(KVStore(), hm, None, engine=eng)
        for rtt in (10 * MS, 20 * MS, 7 * MS, 33 * MS):
            nt.enqueue_probe("h0", Probe("h1", rtt_ns=rtt))
        eng.flush()
        s, d = eng.store.index["h0"], eng.store.index["h1"]
        assert int(eng.store.edges[(s, d)][0]) == nt.average_rtt("h0", "h1")

    def test_queue_cap_drops_oldest(self):
        import time

        eng = make_engine(max_pending=10)
        now = time.time()
        for i in range(25):
            eng.enqueue("a", "b", rtt_ns=(i + 1) * MS, created_at=now + i)
        assert len(eng.deltas) == 10
        assert eng.deltas.dropped == 15
        eng.flush()
        # the newest sample dominates the EWMA — the drops lost nothing
        # a later probe wouldn't have overwritten anyway
        assert eng.stats()["edges"] == 1


class TestLandmarkInference:
    def test_unprobed_pair_gets_finite_estimate(self):
        eng = make_engine()
        feed_star(eng)
        eng.flush(now=1000.0)
        est = eng.est_rtt_ns("h1", "h2")
        assert est is not None and np.isfinite(est)
        # min-plus through the hub: 5ms + 10ms
        assert est == pytest.approx(15 * MS, rel=0.01)

    def test_symmetric_probe_agreement(self):
        """Inference must not depend on query order for unprobed pairs."""
        eng = make_engine()
        feed_star(eng)
        eng.flush(now=1000.0)
        assert eng.est_rtt_ns("h2", "h4") == eng.est_rtt_ns("h4", "h2")

    def test_triangle_bound(self):
        """est_rtt(a,b) ≤ d(a,l) + d(l,b) for every landmark l — the
        estimate is a min over landmark paths, so no single path can
        beat it."""
        eng = make_engine()
        rng = np.random.default_rng(1)
        hosts = [f"h{i}" for i in range(8)]
        direct = {}
        for s in hosts:
            for d in hosts:
                if s < d and rng.random() < 0.5:
                    rtt = int(rng.integers(2, 40)) * MS
                    eng.enqueue(s, d, rtt, created_at=1000.0)
                    direct[(s, d)] = rtt
        eng.flush(now=1000.0)
        D = np.asarray(eng._D)
        for a in hosts:
            for b in hosts:
                if a == b:
                    continue
                ia, ib = eng.store.index[a], eng.store.index[b]
                if (ia, ib) in eng.store.edges or (ib, ia) in eng.store.edges:
                    continue  # direct EWMA wins by design; the bound is on inference
                est = eng.est_rtt_ns(a, b)
                if est is None:
                    continue
                per_landmark = D[ia] + D[ib]
                finite = per_landmark[per_landmark < INF_MS / 2]
                if len(finite):
                    assert est / MS <= finite.min() * 1.001

    def test_direct_edge_wins_over_inference(self):
        eng = make_engine()
        feed_star(eng)
        # h1↔h2 also probed directly, much slower than the hub path
        eng.enqueue("h1", "h2", rtt_ns=200 * MS, created_at=1000.0)
        eng.flush(now=1000.0)
        assert eng.est_rtt_ns("h1", "h2") == 200 * MS

    def test_disconnected_pair_is_none(self):
        eng = make_engine()
        feed_star(eng, spokes=2)
        eng.enqueue("island-a", "island-b", rtt_ns=3 * MS, created_at=1000.0)
        eng.flush(now=1000.0)
        assert eng.est_rtt_ns("h1", "island-a") is None
        assert eng.est_rtt_ns("h1", "no-such-host") is None

    def test_jax_and_numpy_backends_agree(self):
        """The jitted path and the fallback are one contract."""
        engines = {}
        for backend in ("numpy", "jax"):
            eng = TopologyEngine(
                TopologyConfig(backend=backend, flush_threshold=10**9, num_landmarks=4)
            )
            feed_star(eng)
            eng.flush(now=1000.0)
            engines[backend] = eng
        assert isinstance(engines["numpy"].kernels, NumpyKernels)
        assert isinstance(engines["jax"].kernels, JaxKernels)
        np.testing.assert_allclose(
            np.asarray(engines["numpy"]._D),
            np.asarray(engines["jax"]._D),
            rtol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(engines["numpy"]._khop_rtt),
            np.asarray(engines["jax"]._khop_rtt),
            rtol=1e-5,
        )
        for a, b in (("h0", "h1"), ("h1", "h2"), ("h2", "h5")):
            assert engines["numpy"].est_rtt_ns(a, b) == pytest.approx(
                engines["jax"].est_rtt_ns(a, b), rel=1e-5
            )

    def test_auto_raises_when_jax_imports_but_its_kernels_fail(self, monkeypatch):
        """``auto`` means "jax when installed": a jax that imports and
        then fails must surface, not hand back the numpy twin."""
        from dragonfly2_tpu.topology import kernels

        def broken(self):
            raise RuntimeError("backend refused")

        monkeypatch.setattr(kernels.JaxKernels, "__init__", broken)
        with pytest.raises(RuntimeError, match="backend refused"):
            kernels.make_kernels("auto")
        assert isinstance(kernels.make_kernels("numpy"), NumpyKernels)


class TestStalenessDecay:
    def test_quiet_edges_lose_aggregation_weight(self):
        eng = make_engine(half_life_s=60.0)
        eng.enqueue("a", "b", rtt_ns=10 * MS, created_at=1000.0)
        eng.flush(now=1000.0)
        fresh = np.asarray(eng._weights).max()
        eng.flush(now=1000.0 + 120.0)  # two half-lives later
        stale = np.asarray(eng._weights).max()
        assert fresh == pytest.approx(1.0, abs=1e-5)
        assert stale == pytest.approx(0.25, rel=1e-3)

    def test_ancient_edges_purged(self):
        eng = make_engine(max_age_s=3600.0)
        eng.enqueue("a", "b", rtt_ns=10 * MS, created_at=4000.0)
        eng.enqueue("a", "c", rtt_ns=10 * MS, created_at=5000.0)
        eng.flush(now=5000.0)
        assert eng.stats()["edges"] == 2
        eng.flush(now=4000.0 + 3601.0)  # a→b past max age, a→c still inside
        assert eng.stats()["edges"] == 1
        assert eng.est_rtt_ns("a", "b") is None


class TestDeleteHostParity:
    def test_engine_purge_matches_kv_purge(self):
        hm = HostManager()
        for i in range(4):
            hm.store(Host(id=f"h{i}", hostname=f"n{i}", ip="10.0.0.1", port=1))
        kv = KVStore()
        eng = make_engine()
        nt = NetworkTopology(kv, hm, None, engine=eng)
        for s in range(4):
            for d in range(4):
                if s != d:
                    nt.enqueue_probe(f"h{s}", Probe(f"h{d}", rtt_ns=5 * MS))
        eng.flush()
        assert eng.stats()["edges"] == 12

        nt.delete_host("h1")
        # KV side gone
        assert not nt.has_edge("h0", "h1") and not nt.has_edge("h1", "h2")
        # engine side gone too — including pending deltas and inferences
        assert eng.est_rtt_ns("h0", "h1") is None
        assert all(
            "h1" not in (eng.store.ids[s], eng.store.ids[d])
            for s, d in eng.store.edges
        )
        # both views export the same remaining edge set
        kv_edges = {
            tuple(k.split(":")[1:]) for k in kv.scan_iter("networktopology:*:*")
        }
        eng_edges = {
            (eng.store.ids[s], eng.store.ids[d]) for s, d in eng.store.edges
        }
        assert kv_edges == eng_edges

    def test_pending_deltas_do_not_resurrect_deleted_host(self):
        eng = make_engine()
        eng.enqueue("a", "b", rtt_ns=5 * MS)
        eng.enqueue("b", "c", rtt_ns=5 * MS)
        eng.delete_host("b")  # before any flush
        eng.flush()
        assert all(
            "b" not in (eng.store.ids[s], eng.store.ids[d])
            for s, d in eng.store.edges
        )


class TestExportAndSnapshot:
    def _nt(self, n=6, with_engine=True):
        hm = HostManager()
        for i in range(n):
            hm.store(Host(id=f"h{i}", hostname=f"n{i}", ip=f"10.0.0.{i}", port=1))
        eng = make_engine() if with_engine else None
        return NetworkTopology(KVStore(), hm, None, engine=eng), hm

    def test_engine_export_feeds_gnn_without_kv_walk(self):
        nt, hm = self._nt()
        for s in range(6):
            for d in range(6):
                if s != d:
                    nt.enqueue_probe(f"h{s}", Probe(f"h{d}", rtt_ns=(5 + s + d) * MS))
        nt.kv.flushall()  # prove the export never touches KV
        recs = nt.export_records()
        assert len(recs) == 6
        from dragonfly2_tpu.schema.columnar import records_to_columns
        from dragonfly2_tpu.schema.features import build_probe_graph

        g = build_probe_graph(records_to_columns(recs), max_degree=4)
        assert g.num_nodes == 6
        assert len(g.edge_src) > 0

    def test_export_prefers_freshest_edges_engine_path(self):
        import time

        nt, hm = self._nt(n=6)
        base = time.time()  # export flushes with the real clock; stale-purge must not fire
        for d in range(1, 6):  # h0 → h1..h5, h5 updated last
            nt.enqueue_probe(
                "h0", Probe(f"h{d}", rtt_ns=5 * MS, created_at=base + d)
            )
        recs = nt.export_records(dest_limit=2)
        dest_ids = [dh.id for dh in recs[0].dest_hosts]
        assert dest_ids == ["h5", "h4"]  # most recently updated first

    def test_export_prefers_freshest_edges_kv_path(self):
        nt, hm = self._nt(n=6, with_engine=False)
        base = 1000.0
        for d in range(1, 6):
            nt.enqueue_probe(
                "h0", Probe(f"h{d}", rtt_ns=5 * MS, created_at=base + d)
            )
        recs = nt.export_records(dest_limit=2)
        dest_ids = [dh.id for dh in recs[0].dest_hosts]
        assert dest_ids == ["h5", "h4"]


class TestEvaluatorIntegration:
    def test_feature_dim_rejection_guards_schema_bump(self):
        from dragonfly2_tpu.scheduler.evaluator import MLEvaluator
        from dragonfly2_tpu.schema.features import MLP_FEATURE_DIM

        class Model:
            def __init__(self, dim):
                self.feature_dim = dim

            def predict(self, feats):
                return np.zeros(feats.shape[0], np.float32)

        ev = MLEvaluator()
        ev.set_model(Model(MLP_FEATURE_DIM - 1))  # pre-bump model
        assert ev._model is None  # refused loudly, not installed
        ev.set_model(Model(MLP_FEATURE_DIM))
        assert ev._model is not None

    def test_rtt_affinity_feature_position_and_value(self):
        from dragonfly2_tpu.scheduler import resource as res
        from dragonfly2_tpu.scheduler.evaluator import pair_features
        from dragonfly2_tpu.schema.features import MLP_FEATURE_NAMES

        t = res.Task("t")
        t.total_piece_count = 4
        child = res.Peer("c", t, res.Host(id="hc"))
        parent = res.Peer("p", t, res.Host(id="hp"))
        idx = MLP_FEATURE_NAMES.index("rtt_affinity")
        assert pair_features(parent, child, 4)[idx] == 0.0  # missing-value
        assert pair_features(parent, child, 4, rtt_affinity=0.3)[idx] == pytest.approx(
            0.3
        )


class TestEndToEnd:
    def test_probes_to_adjacency_to_ranking_shift(self):
        """The acceptance demo: probes enqueued through NetworkTopology
        appear in the device adjacency after a delta flush, an unprobed
        pair returns a finite landmark-inferred RTT, and MLEvaluator
        ranking measurably shifts when that RTT feature flips — on the
        numpy fallback path (this suite runs under JAX_PLATFORMS=cpu;
        conftest pins it)."""
        from dragonfly2_tpu.scheduler import resource as res
        from dragonfly2_tpu.scheduler.evaluator import MLEvaluator
        from dragonfly2_tpu.schema.features import MLP_FEATURE_DIM, MLP_FEATURE_NAMES

        hm = HostManager()
        for hid in ("child", "near", "far"):
            hm.store(Host(id=hid, hostname=hid, ip="10.0.0.1", port=1))
        eng = make_engine(flush_threshold=4)  # exercise auto-flush too
        nt = NetworkTopology(KVStore(), hm, None, engine=eng)

        # child↔near fast through the hub "child"; far is slow
        nt.enqueue_probe("child", Probe("near", rtt_ns=2 * MS))
        nt.enqueue_probe("near", Probe("child", rtt_ns=2 * MS))
        nt.enqueue_probe("child", Probe("far", rtt_ns=90 * MS))
        nt.enqueue_probe("far", Probe("child", rtt_ns=90 * MS))
        eng.flush()
        assert eng.stats()["edges"] == 4  # probes landed in the adjacency

        # unprobed pair (near, far): finite inferred estimate
        inferred = eng.est_rtt_ns("near", "far")
        assert inferred is not None and inferred == pytest.approx(92 * MS, rel=0.01)

        # a model that scores ONLY the rtt feature: predicted cost =
        # rtt_affinity, so topology is the only thing that can reorder
        rtt_idx = MLP_FEATURE_NAMES.index("rtt_affinity")

        class RttModel:
            feature_dim = MLP_FEATURE_DIM

            def predict(self, feats):
                return feats[:, rtt_idx]

        t = res.Task("t")
        t.total_piece_count = 4
        child = res.Peer("c", t, hm.load("child"))
        p_near = res.Peer("pn", t, hm.load("near"))
        p_far = res.Peer("pf", t, hm.load("far"))

        without = MLEvaluator(RttModel())  # no topology: feature is 0/0 → tie
        with_topo = MLEvaluator(RttModel(), topology=eng)
        ranked = with_topo.evaluate_parents([p_far, p_near], child, 4)
        assert [p.id for p in ranked] == ["pn", "pf"]  # near wins on RTT
        baseline = without.evaluate_parents([p_far, p_near], child, 4)
        assert [p.id for p in baseline] == ["pf", "pn"]  # tie → input order kept

        # flip the topology: far becomes the fast host
        nt.enqueue_probe("child", Probe("far", rtt_ns=1 * MS))
        nt.enqueue_probe("child", Probe("near", rtt_ns=95 * MS))
        eng.flush()
        reranked = with_topo.evaluate_parents([p_far, p_near], child, 4)
        assert [p.id for p in reranked] == ["pf", "pn"]  # ranking flipped

    def test_seed_placement_by_rtt_centrality(self):
        from dragonfly2_tpu.scheduler.seed_placement import recommend_seeds_by_rtt

        eng = make_engine()
        # h0 is the natural seed: fast from everyone; h5 slow
        for s in range(6):
            for d in range(6):
                if s != d:
                    rtt = 2 if d == 0 else (80 if d == 5 else 20)
                    eng.enqueue(f"h{s}", f"h{d}", rtt_ns=rtt * MS, created_at=1000.0)
        eng.flush(now=1000.0)
        ranking = recommend_seeds_by_rtt(eng, k=3)
        assert ranking[0]["host_id"] == "h0"
        assert all(r["host_id"] != "h5" for r in ranking)
        sub = recommend_seeds_by_rtt(eng, k=2, candidates=["h3", "h5"])
        assert [r["host_id"] for r in sub][0] == "h3"
        with pytest.raises(ValueError):
            recommend_seeds_by_rtt(eng, candidates=["unknown-host"])

    def test_topology_rpc_service(self):
        """EstRtt / Neighbors / Stats over the real gRPC glue."""
        import grpc

        from dragonfly2_tpu.rpc import glue
        from dragonfly2_tpu.rpc.glue import TOPOLOGY_SERVICE
        from dragonfly2_tpu.scheduler.topology_service import TopologyService
        from dragonfly2_tpu.rpc import gen  # noqa: F401
        import topology_pb2

        eng = make_engine()
        feed_star(eng)
        eng.flush(now=1000.0)
        server, port = glue.serve(
            {TOPOLOGY_SERVICE: TopologyService(eng)}, "127.0.0.1:0"
        )
        try:
            channel = grpc.insecure_channel(f"127.0.0.1:{port}")
            client = glue.ServiceClient(channel, TOPOLOGY_SERVICE)
            direct = client.EstRtt(
                topology_pb2.EstRttRequest(src_host_id="h0", dest_host_id="h1")
            )
            assert direct.found and direct.source == "direct"
            assert direct.rtt_ns == 5 * MS
            inferred = client.EstRtt(
                topology_pb2.EstRttRequest(src_host_id="h1", dest_host_id="h2")
            )
            assert inferred.found and inferred.source == "inferred"
            missing = client.EstRtt(
                topology_pb2.EstRttRequest(src_host_id="h1", dest_host_id="nope")
            )
            assert not missing.found
            nbrs = client.Neighbors(
                topology_pb2.NeighborsRequest(host_id="h0", limit=3)
            )
            assert [n.host_id for n in nbrs.neighbors] == ["h1", "h2", "h3"]
            stats = client.Stats(topology_pb2.StatsRequest())
            assert stats.hosts == 6 and stats.edges == 10
            assert stats.backend == "numpy"
            channel.close()
        finally:
            server.stop(grace=0)

    def test_scheduler_server_wires_engine(self, tmp_path):
        """SchedulerServer builds the engine, mirrors SyncProbes into
        it, and serves the Topology RPC alongside the scheduling
        services."""
        import grpc

        from dragonfly2_tpu.rpc import glue
        from dragonfly2_tpu.rpc.glue import TOPOLOGY_SERVICE
        from dragonfly2_tpu.scheduler.server import (
            SchedulerServer,
            SchedulerServerConfig,
        )
        import topology_pb2

        srv = SchedulerServer(
            SchedulerServerConfig(
                data_dir=str(tmp_path), topology_backend="numpy"
            )
        )
        addr = srv.serve()
        try:
            assert srv.networktopology.engine is srv.topology_engine
            for hid in ("a", "b"):
                srv.resource.host_manager.store(
                    Host(id=hid, hostname=hid, ip="127.0.0.1", port=1)
                )
            srv.networktopology.enqueue_probe("a", Probe("b", rtt_ns=7 * MS))
            srv.topology_engine.flush()
            channel = grpc.insecure_channel(addr)
            client = glue.ServiceClient(channel, TOPOLOGY_SERVICE)
            resp = client.EstRtt(
                topology_pb2.EstRttRequest(src_host_id="a", dest_host_id="b")
            )
            assert resp.found and resp.rtt_ns == 7 * MS
            channel.close()
        finally:
            srv.stop()


class TestHydrationAndTrainJoin:
    def test_engine_adopts_peer_scheduler_edges_from_kv(self):
        """Multi-scheduler KV sharing: edges probed via a PEER scheduler
        (never through this process's enqueue_probe) must still appear
        in this scheduler's snapshot — hydration merges them from KV."""
        import time

        hm = HostManager()
        for i in range(4):
            hm.store(Host(id=f"h{i}", hostname=f"n{i}", ip="10.0.0.1", port=1))
        kv = KVStore()  # the shared store
        peer_nt = NetworkTopology(kv, hm, None)  # peer scheduler: KV only
        local_nt = NetworkTopology(kv, hm, None, engine=make_engine())

        now = time.time()
        peer_nt.enqueue_probe("h2", Probe("h3", rtt_ns=9 * MS, created_at=now))
        local_nt.enqueue_probe("h0", Probe("h1", rtt_ns=4 * MS, created_at=now))

        recs = local_nt.export_records()  # hydrates, then engine-exports
        srcs = {r.host.id for r in recs}
        assert srcs == {"h0", "h2"}  # the peer's edge made it in
        assert local_nt.engine.est_rtt_ns("h2", "h3") == 9 * MS

    def test_adopt_never_clobbers_fresher_local_state(self):
        import time

        now = time.time()
        eng = make_engine()
        assert eng.adopt("a", "b", 10 * MS, updated_at=now - 10)
        assert not eng.adopt("a", "b", 99 * MS, updated_at=now - 20)  # older
        assert eng.adopt("a", "b", 20 * MS, updated_at=now)  # newer
        eng.flush()
        assert eng.est_rtt_ns("a", "b") == 20 * MS

    def test_block_encode_joins_live_rtt_into_training_data(self, tmp_path):
        """Train/serve agreement: with the engine's lookup installed on
        scheduler Storage, the binary train blocks carry live
        rtt_affinity values — not the constant 0.0 the model could
        never learn from."""
        import time

        from dragonfly2_tpu.schema import synth, wire
        from dragonfly2_tpu.schema.features import MLP_FEATURE_NAMES

        recs = synth.make_download_records(20, seed=0)
        child_ids = {r.host.id for r in recs}
        parent_ids = {p.host.id for r in recs for p in r.parents if p.host.id}
        eng = make_engine()
        now = time.time()
        for c in child_ids:
            for p in parent_ids:
                if c != p:
                    eng.enqueue(c, p, rtt_ns=12 * MS, created_at=now)
        eng.flush()

        blk = wire.encode_train_block(recs, rtt_lookup=eng.rtt_affinity_batch)
        path = tmp_path / "t.dfb"
        path.write_bytes(blk)
        feats = None
        for feats, _, _ in wire.stream_train_pairs(path, passes=1):
            pass
        idx = MLP_FEATURE_NAMES.index("rtt_affinity")
        col = feats[:, idx]
        assert (col > 0).any(), "live rtt must reach the training tensors"
        expect = float(np.log1p(12.0) / 10.0)
        assert np.allclose(col[col > 0], expect, rtol=1e-5)

        # without the lookup the column stays at the missing-value
        blk0 = wire.encode_train_block(recs)
        path.write_bytes(blk0)
        for feats0, _, _ in wire.stream_train_pairs(path, passes=1):
            pass
        assert (feats0[:, idx] == 0.0).all()

    def test_est_rtt_detail_provenance(self):
        eng = make_engine()
        feed_star(eng, spokes=2)
        eng.flush(now=1000.0)
        assert eng.est_rtt_detail("h0", "h0") == (0, "self")
        assert eng.est_rtt_detail("h0", "h1")[1] == "direct"
        assert eng.est_rtt_detail("h1", "h2")[1] == "inferred"
        assert eng.est_rtt_detail("h1", "ghost") == (None, "none")
        # cached answers keep their provenance
        assert eng.est_rtt_detail("h1", "h2")[1] == "inferred"


class TestKVBatching:
    def test_find_probed_hosts_uses_mget_when_available(self):
        class CountingKV(KVStore):
            def __init__(self):
                super().__init__()
                self.gets = 0
                self.mgets = 0

            def get(self, key):
                self.gets += 1
                return super().get(key)

            def mget(self, keys):
                self.mgets += 1
                return [super(CountingKV, self).get(k) for k in keys]

        hm = HostManager()
        for i in range(30):
            hm.store(Host(id=f"h{i}", hostname=f"n{i}", ip="10.0.0.1", port=1))
        kv = CountingKV()
        nt = NetworkTopology(kv, hm, None)
        for _ in range(3):
            nt.enqueue_probe("h0", Probe("h1", rtt_ns=MS))
        kv.gets = kv.mgets = 0
        got = nt.find_probed_hosts("h0")
        assert len(got) == nt.probe_count
        assert kv.mgets == 1  # ONE batched read for all candidates
        assert kv.gets == 0
        assert "h1" not in [h.id for h in got]  # ordering still least-probed

    def test_remote_mget_over_kvserver(self):
        from dragonfly2_tpu.utils.kvserver import KVServer
        from dragonfly2_tpu.utils.kvstore import RemoteKVStore

        server = KVServer(host="127.0.0.1", port=0)
        port = server.serve()
        try:
            kv = RemoteKVStore(f"127.0.0.1:{port}")
            kv.set("k1", "10")
            kv.set("k3", "30")
            assert kv.mget(["k1", "missing", "k3"]) == ["10", None, "30"]
            assert kv.mget([]) == []
            kv.close()
        finally:
            server.stop()

    def test_remote_hget_batch_pipelined(self):
        """Pipelined HGET over the real RESP wire: results align with
        the key order, missing keys/fields are None."""
        from dragonfly2_tpu.utils.kvserver import KVServer
        from dragonfly2_tpu.utils.kvstore import RemoteKVStore

        server = KVServer(host="127.0.0.1", port=0)
        port = server.serve()
        try:
            kv = RemoteKVStore(f"127.0.0.1:{port}")
            kv.hset("e1", {"updatedAt": "100", "averageRTT": "5"})
            kv.hset("e2", {"updatedAt": "200"})
            got = kv.hget_batch(["e1", "nope", "e2"], "updatedAt")
            assert got == ["100", None, "200"]
            assert kv.hget_batch([], "updatedAt") == []
            kv.close()
        finally:
            server.stop()


def test_concurrent_flush_and_export_do_not_deadlock():
    """Lock-order regression: the 30s GC flush (flush: _flush_lock →
    _lock) runs concurrently with the snapshot export (which must call
    flush BEFORE taking _lock — the old under-lock call ABBA-deadlocked
    in seconds)."""
    import threading
    import time

    from dragonfly2_tpu.scheduler.resource import HostManager

    hm = HostManager()
    for i in range(8):
        hm.store(Host(id=f"h{i}", hostname=f"n{i}", ip="10.0.0.1", port=1))
    eng = make_engine()
    now = time.time()
    for s in range(8):
        for d in range(8):
            if s != d:
                eng.enqueue(f"h{s}", f"h{d}", rtt_ns=5 * MS, created_at=now)
    stop = time.time() + 2.0
    errors: list = []

    def worker(fn):
        try:
            while time.time() < stop:
                fn()
        except Exception as e:  # pragma: no cover - failure reporting
            errors.append(e)

    threads = [
        threading.Thread(target=worker, args=(eng.flush,)),
        threading.Thread(target=worker, args=(lambda: eng.export_records(hm, 5),)),
        threading.Thread(target=worker, args=(lambda: eng.centrality(),)),
        threading.Thread(target=worker, args=(lambda: eng.est_rtt_ns("h1", "h2"),)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=20.0)
    assert not errors
    assert not any(t.is_alive() for t in threads), "engine deadlocked"


@pytest.mark.slow
def test_topology_soak_large_graph():
    """Soak: a few thousand hosts through repeated delta flushes keeps
    queries finite and the flush latency bounded (marked slow: >5s)."""
    rng = np.random.default_rng(0)
    eng = make_engine(num_landmarks=16)
    n = 2000
    for i in range(40_000):
        s, d = rng.integers(0, n, size=2)
        if s == d:
            continue
        eng.enqueue(f"h{s}", f"h{d}", int(rng.integers(1, 80)) * MS, 1000.0 + i * 0.01)
        if i % 4096 == 0:
            eng.flush(now=1000.0 + i * 0.01)
    eng.flush(now=1000.0 + 40_000 * 0.01)
    stats = eng.stats()
    assert stats["hosts"] == n
    hits = 0
    for _ in range(500):
        a, b = rng.integers(0, n, size=2)
        if eng.est_rtt_ns(f"h{a}", f"h{b}") is not None:
            hits += 1
    assert hits > 400  # the landmark scheme covers most unprobed pairs
