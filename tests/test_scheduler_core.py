"""Scheduler core: FSMs, DAG peer tree, filter rules, evaluators, storage
sink — driven in-process the way the reference's table tests drive theirs."""

import numpy as np
import pytest

from dragonfly2_tpu.scheduler import resource as res
from dragonfly2_tpu.scheduler.evaluator import (
    BaseEvaluator,
    MLEvaluator,
    idc_affinity_score,
    location_affinity_score,
    new_evaluator,
    pair_features,
)
from dragonfly2_tpu.scheduler.resource.fsm import InvalidTransitionError
from dragonfly2_tpu.scheduler.scheduling import (
    NeedBackToSourceResponse,
    NormalTaskResponse,
    Scheduling,
    SchedulingConfig,
    SchedulingError,
)
from dragonfly2_tpu.scheduler.storage import Storage, build_download_record
from dragonfly2_tpu.schema.records import Network


def make_host(i: int, seed=False, idc="idc-a", location="as|cn|sh|dc1", upload_limit=50):
    h = res.Host(
        id=f"host-{i}",
        type=res.HostType.SUPER if seed else res.HostType.NORMAL,
        hostname=f"h{i}",
        ip=f"10.0.0.{i}",
        port=8002,
        download_port=8001,
        concurrent_upload_limit=upload_limit,
    )
    h.network = Network(idc=idc, location=location)
    return h


def make_peer(i: int, task, host) -> res.Peer:
    p = res.Peer(f"peer-{i}", task, host)
    task.store_peer(p)
    host.store_peer(p)
    return p


def running_parent(i, task, seed=False, back_to_source=True, **kw):
    """A parent peer in Running state that has been fed (back-to-source)."""
    p = make_peer(i, task, make_host(i, seed=seed, **kw))
    p.fsm.event(res.PEER_EVENT_REGISTER_NORMAL)
    if back_to_source:
        p.fsm.event(res.PEER_EVENT_DOWNLOAD_BACK_TO_SOURCE)
    else:
        p.fsm.event(res.PEER_EVENT_DOWNLOAD)
    return p


class CollectStream:
    def __init__(self):
        self.responses = []

    def send(self, resp):
        self.responses.append(resp)


class TestPeerFSM:
    def test_happy_path(self):
        t = res.Task("t1", "https://e.com/x")
        p = make_peer(1, t, make_host(1))
        assert p.fsm.current == res.PEER_STATE_PENDING
        p.fsm.event(res.PEER_EVENT_REGISTER_NORMAL)
        p.fsm.event(res.PEER_EVENT_DOWNLOAD)
        p.fsm.event(res.PEER_EVENT_DOWNLOAD_SUCCEEDED)
        assert p.fsm.current == res.PEER_STATE_SUCCEEDED
        p.fsm.event(res.PEER_EVENT_LEAVE)
        assert p.fsm.current == res.PEER_STATE_LEAVE

    def test_illegal_transition(self):
        t = res.Task("t1")
        p = make_peer(1, t, make_host(1))
        with pytest.raises(InvalidTransitionError):
            p.fsm.event(res.PEER_EVENT_DOWNLOAD)  # Pending can't Download
        p.fsm.event(res.PEER_EVENT_REGISTER_TINY)
        with pytest.raises(InvalidTransitionError):
            p.fsm.event(res.PEER_EVENT_REGISTER_NORMAL)

    def test_leave_from_failed(self):
        t = res.Task("t1")
        p = make_peer(1, t, make_host(1))
        p.fsm.event(res.PEER_EVENT_DOWNLOAD_FAILED)
        p.fsm.event(res.PEER_EVENT_LEAVE)
        assert p.fsm.is_state(res.PEER_STATE_LEAVE)


class TestTask:
    def test_size_scope(self):
        t = res.Task("t")
        assert t.size_scope() is res.SizeScope.UNKNOW
        t.content_length, t.total_piece_count = 0, 0
        assert t.size_scope() is res.SizeScope.EMPTY
        t.content_length, t.total_piece_count = 100, 1
        assert t.size_scope() is res.SizeScope.TINY
        t.content_length, t.total_piece_count = 4 << 20, 1
        assert t.size_scope() is res.SizeScope.SMALL
        t.content_length, t.total_piece_count = 64 << 20, 16
        assert t.size_scope() is res.SizeScope.NORMAL

    def test_back_to_source_accounting(self):
        t = res.Task("t", back_to_source_limit=2)
        assert t.can_back_to_source()
        t.back_to_source_peers |= {"a", "b", "c"}
        assert not t.can_back_to_source()
        t2 = res.Task("t2", task_type=res.TaskType.DFCACHE)
        assert not t2.can_back_to_source()  # cache tasks have no origin

    def test_peer_dag_edges_track_upload_slots(self):
        t = res.Task("t")
        parent = make_peer(1, t, make_host(1))
        child = make_peer(2, t, make_host(2))
        t.add_peer_edge(parent, child)
        assert parent.host.concurrent_upload_count == 1
        assert t.peer_in_degree(child.id) == 1
        assert not t.can_add_peer_edge(child.id, parent.id)  # cycle
        t.delete_peer_in_edges(child.id)
        assert parent.host.concurrent_upload_count == 0
        assert t.peer_in_degree(child.id) == 0

    def test_seed_peer_lookup(self):
        t = res.Task("t")
        make_peer(1, t, make_host(1))
        seed = make_peer(2, t, make_host(2, seed=True))
        assert t.load_seed_peer() is seed
        seed.fsm.event(res.PEER_EVENT_DOWNLOAD_FAILED)
        assert t.load_seed_peer() is None
        assert t.is_seed_peer_failed()


class TestEvaluator:
    def test_affinity_scores(self):
        assert idc_affinity_score("a", "A") == 1.0
        assert idc_affinity_score("a", "b") == 0.0
        assert idc_affinity_score("", "b") == 0.0
        assert location_affinity_score("as|cn|sh", "as|cn|bj") == pytest.approx(2 / 5)
        assert location_affinity_score("same", "same") == 1.0

    def test_ranking_prefers_close_fed_parents(self):
        t = res.Task("t")
        t.total_piece_count = 10
        child = make_peer(0, t, make_host(0, idc="idc-a"))
        near = running_parent(1, t, idc="idc-a")
        far = running_parent(2, t, idc="idc-z", location="eu|de|fra|dc9")
        near.finished_pieces |= {0, 1, 2, 3}
        far.finished_pieces |= {0, 1, 2, 3}
        ranked = BaseEvaluator().evaluate_parents([far, near], child, 10)
        assert ranked[0] is near

    def test_bad_node_by_state_and_stats(self):
        t = res.Task("t")
        ev = BaseEvaluator()
        pending = make_peer(1, t, make_host(1))
        assert ev.is_bad_node(pending)  # Pending is bad

        ok = running_parent(2, t)
        ok.piece_costs_ms[:] = [10.0] * 10
        assert not ev.is_bad_node(ok)

        spike = running_parent(3, t)
        spike.piece_costs_ms[:] = [10.0] * 10 + [500.0]  # > mean*20
        assert ev.is_bad_node(spike)

        sigma = running_parent(4, t)
        sigma.piece_costs_ms[:] = [10.0] * 35 + [10.5]  # zero-ish stdev, small jump
        assert ev.is_bad_node(sigma)
        sigma2 = running_parent(5, t)
        costs = list(np.linspace(8, 12, 40))
        sigma2.piece_costs_ms[:] = costs + [12.5]  # within 3 sigma
        assert not ev.is_bad_node(sigma2)

    def test_ml_evaluator_uses_model_and_falls_back(self):
        t = res.Task("t")
        t.total_piece_count = 10
        child = make_peer(0, t, make_host(0))
        a = running_parent(1, t)
        b = running_parent(2, t)

        class FakeModel:
            def predict(self, feats):
                # parent b predicted much faster
                return np.array([9.0, 1.0], dtype=np.float32)

        ev = MLEvaluator(FakeModel())
        assert ev.evaluate_parents([a, b], child, 10)[0] is b

        class BrokenModel:
            def predict(self, feats):
                raise RuntimeError("serving down")

        ev2 = MLEvaluator(BrokenModel())
        ranked = ev2.evaluate_parents([a, b], child, 10)
        assert len(ranked) == 2  # fell back to linear score, no raise

        assert isinstance(new_evaluator("ml"), MLEvaluator)
        assert isinstance(new_evaluator("default"), BaseEvaluator)

    def test_pair_feature_vector_matches_schema_dim(self):
        from dragonfly2_tpu.schema.features import MLP_FEATURE_DIM

        t = res.Task("t")
        t.total_piece_count = 4
        child = make_peer(0, t, make_host(0))
        parent = running_parent(1, t)
        f = pair_features(parent, child, 4)
        assert f.shape == (MLP_FEATURE_DIM,)
        assert np.isfinite(f).all()


class TestDecisionPhases:
    """A decision's service from inside (scheduler.find_parents around
    the rules and the ranking), and by the stretch a provider names."""

    NAMES = ("scheduler.find_parents", "scheduler.filter_parents", "scheduler.evaluate")

    def _decide(self, n: int):
        from dragonfly2_tpu.scheduler import scheduling as mod
        from dragonfly2_tpu.utils import profiling

        t = res.Task("t")
        t.total_piece_count = 10
        child = make_peer(0, t, make_host(0))
        child.fsm.event(res.PEER_EVENT_REGISTER_NORMAL)
        parent = running_parent(1, t)
        sched = Scheduling(BaseEvaluator(), SchedulingConfig(retry_interval=0.0))
        names = (*self.NAMES, *(ph.name for ph in mod.PH_FIND_PARENTS_BESIDE.values()))
        before = self.before = {name: profiling.phase_type(name).snapshot() for name in names}
        for _ in range(n):
            got, found = sched.find_candidate_parents(child)
            assert found and got == [parent]
        after = {name: profiling.phase_type(name).snapshot() for name in names}
        return {name: (after[name]["count"] - before[name]["count"], after[name]["total_s"] - before[name]["total_s"]) for name in names}

    def test_a_scheduler_alone_books_nothing_by_stretch_and_loses_nothing_else(self, monkeypatch):
        from dragonfly2_tpu.scheduler import scheduling as mod

        monkeypatch.setattr(mod, "stretch_provider", None)
        moved = self._decide(5)
        assert [moved[name][0] for name in self.NAMES] == [5, 5, 5]
        assert all(moved[ph.name] == (0, 0.0) for ph in mod.PH_FIND_PARENTS_BESIDE.values())
        find, rules, rank = (moved[name][1] for name in self.NAMES)
        assert rules > 0 and rank > 0 and rules + rank <= find  # the rest is the decision's own

    @pytest.mark.parametrize("stretch", ["walk", "assemble", "fit_shared", "fit_alone", "idle"])
    def test_a_decision_is_booked_under_the_stretch_it_began_in(self, monkeypatch, stretch):
        from dragonfly2_tpu.scheduler import scheduling as mod

        assert tuple(mod.PH_FIND_PARENTS_BESIDE) == mod.STRETCHES
        asked = []
        monkeypatch.setattr(mod, "stretch_provider", lambda: asked.append(stretch) or stretch)
        moved = self._decide(3)
        assert asked == [stretch] * 3  # read once a decision
        by_stretch = {s: moved[ph.name][0] for s, ph in mod.PH_FIND_PARENTS_BESIDE.items()}
        assert by_stretch == {s: 3 * (s == stretch) for s in mod.STRETCHES}
        assert sum(by_stretch.values()) == moved["scheduler.find_parents"][0]
        # the whole decision's seconds themselves: one clock
        assert moved[mod.PH_FIND_PARENTS_BESIDE[stretch].name][1] == pytest.approx(moved["scheduler.find_parents"][1], abs=1e-5)

    def test_a_stretch_no_one_declared_books_nothing_and_fails_no_decision(self, monkeypatch):
        from dragonfly2_tpu.scheduler import scheduling as mod

        monkeypatch.setattr(mod, "stretch_provider", lambda: "lunch")
        moved = self._decide(2)
        assert moved["scheduler.find_parents"][0] == 2
        assert all(moved[ph.name][0] == 0 for ph in mod.PH_FIND_PARENTS_BESIDE.values())

    def test_a_decision_that_raises_is_booked_whole_and_by_stretch(self, monkeypatch):
        """The counts by stretch sum to ``find_parents``' whatever way a
        decision ends."""
        from dragonfly2_tpu.scheduler import scheduling as mod

        def raising(self, peer, blocklist):
            raise RuntimeError("a rule gone wrong")

        monkeypatch.setattr(mod, "stretch_provider", lambda: "walk")
        monkeypatch.setattr(Scheduling, "_filter_candidate_parents", raising)
        with pytest.raises(RuntimeError, match="a rule gone wrong"):
            self._decide(1)
        walk, whole = mod.PH_FIND_PARENTS_BESIDE["walk"], mod.PH_FIND_PARENTS
        assert walk.snapshot()["count"] - self.before[walk.name]["count"] == 1
        assert whole.snapshot()["count"] - self.before[whole.name]["count"] == 1


class TestFilterRules:
    def _setup(self):
        t = res.Task("t")
        t.total_piece_count = 10
        child = make_peer(0, t, make_host(0))
        child.fsm.event(res.PEER_EVENT_REGISTER_NORMAL)
        sched = Scheduling(BaseEvaluator(), SchedulingConfig(retry_interval=0.0))
        return t, child, sched

    def test_happy_filter(self):
        t, child, sched = self._setup()
        parent = running_parent(1, t)
        parent.finished_pieces |= {0, 1}
        got, found = sched.find_candidate_parents(child)
        assert found and got == [parent]

    def test_blocklist_and_same_host(self):
        t, child, sched = self._setup()
        p1 = running_parent(1, t)
        got, _ = sched.find_candidate_parents(child, blocklist={p1.id})
        assert got == []
        # same host excluded
        p2 = res.Peer("peer-2", t, child.host)
        t.store_peer(p2)
        p2.fsm.event(res.PEER_EVENT_REGISTER_NORMAL)
        p2.fsm.event(res.PEER_EVENT_DOWNLOAD_BACK_TO_SOURCE)
        got, found = sched.find_candidate_parents(child, blocklist={p1.id})
        assert not found

    def test_unfed_normal_parent_rejected(self):
        t, child, sched = self._setup()
        # Running normal-host parent with no in-edges and not back-to-source
        lonely = running_parent(1, t, back_to_source=False)
        got, found = sched.find_candidate_parents(child)
        assert not found
        # same state but seed host → accepted
        seed = running_parent(2, t, seed=True, back_to_source=False)
        got, found = sched.find_candidate_parents(child)
        assert found and got == [seed]

    def test_no_free_upload_rejected(self):
        t, child, sched = self._setup()
        p = running_parent(1, t, upload_limit=1)
        p.host.acquire_upload()
        got, found = sched.find_candidate_parents(child)
        assert not found

    def test_candidate_limit_and_ordering(self):
        t, child, sched = self._setup()
        parents = [running_parent(i, t) for i in range(1, 8)]
        for i, p in enumerate(parents):
            p.finished_pieces |= set(range(i + 1))  # later parents have more pieces
        got, found = sched.find_candidate_parents(child)
        assert found and len(got) == sched.config.candidate_parent_limit
        # best parent = most finished pieces
        assert got[0] is parents[-1]

    def test_wrong_child_state_cannot_schedule(self):
        t, child, sched = self._setup()
        running_parent(1, t)
        child.fsm.event(res.PEER_EVENT_DOWNLOAD_BACK_TO_SOURCE)
        got, found = sched.find_candidate_parents(child)
        assert not found


class TestScheduleCandidateParents:
    def test_schedules_and_adds_edges(self):
        t = res.Task("t")
        t.total_piece_count = 10
        t.content_length = 10 << 20
        child = make_peer(0, t, make_host(0))
        child.fsm.event(res.PEER_EVENT_REGISTER_NORMAL)
        stream = CollectStream()
        child.store_stream(stream)
        parent = running_parent(1, t)
        sched = Scheduling(BaseEvaluator(), SchedulingConfig(retry_interval=0.0))
        sched.schedule_candidate_parents(child)
        assert len(stream.responses) == 1
        assert isinstance(stream.responses[0], NormalTaskResponse)
        assert stream.responses[0].candidate_parents == [parent]
        assert t.peer_in_degree(child.id) == 1

    def test_need_back_to_source_on_demand(self):
        t = res.Task("t")
        child = make_peer(0, t, make_host(0))
        child.fsm.event(res.PEER_EVENT_REGISTER_NORMAL)
        child.need_back_to_source = True
        stream = CollectStream()
        child.store_stream(stream)
        Scheduling(BaseEvaluator(), SchedulingConfig(retry_interval=0.0)).schedule_candidate_parents(child)
        assert isinstance(stream.responses[0], NeedBackToSourceResponse)

    def test_back_to_source_after_retries(self):
        t = res.Task("t")  # no parents at all
        child = make_peer(0, t, make_host(0))
        child.fsm.event(res.PEER_EVENT_REGISTER_NORMAL)
        stream = CollectStream()
        child.store_stream(stream)
        cfg = SchedulingConfig(retry_back_to_source_limit=2, retry_interval=0.0)
        Scheduling(BaseEvaluator(), cfg).schedule_candidate_parents(child)
        assert isinstance(stream.responses[0], NeedBackToSourceResponse)
        assert "RetryBackToSourceLimit" in stream.responses[0].description

    def test_retry_exhaustion_raises_when_no_back_to_source(self):
        t = res.Task("t", task_type=res.TaskType.DFCACHE)  # can't back-to-source
        child = make_peer(0, t, make_host(0))
        child.fsm.event(res.PEER_EVENT_REGISTER_NORMAL)
        child.store_stream(CollectStream())
        cfg = SchedulingConfig(retry_limit=2, retry_interval=0.0)
        with pytest.raises(SchedulingError):
            Scheduling(BaseEvaluator(), cfg).schedule_candidate_parents(child)


class TestManagersAndGC:
    def test_load_or_store_and_delete(self):
        r = res.Resource()
        t = res.Task("t")
        h = make_host(1)
        r.task_manager.store(t)
        r.host_manager.store(h)
        p = res.Peer("p1", t, h)
        stored, loaded = r.peer_manager.load_or_store(p)
        assert stored is p and not loaded
        again, loaded = r.peer_manager.load_or_store(res.Peer("p1", t, h))
        assert again is p and loaded
        r.peer_manager.delete("p1")
        assert r.peer_manager.load("p1") is None
        assert t.peer_count() == 0
        assert h.peer_count() == 0

    def test_gc_reclaims(self):
        r = res.Resource()
        t = res.Task("t")
        h = make_host(1)
        r.task_manager.store(t)
        r.host_manager.store(h)
        p = res.Peer("p1", t, h)
        r.peer_manager.store(p)
        p.fsm.event(res.PEER_EVENT_LEAVE)
        assert r.peer_manager.run_gc(ttl=3600) == 1
        assert r.task_manager.run_gc() == 1  # now peerless
        h.updated_at = 0.0
        assert r.host_manager.run_gc(ttl=1.0) == 1


class TestStorageSink:
    def test_download_record_roundtrip(self, tmp_path):
        t = res.Task("t", url="https://e.com/blob")
        t.total_piece_count = 4
        t.content_length = 4 << 20
        child = make_peer(0, t, make_host(0))
        child.fsm.event(res.PEER_EVENT_REGISTER_NORMAL)
        child.fsm.event(res.PEER_EVENT_DOWNLOAD)
        parent = running_parent(1, t)
        t.add_peer_edge(parent, child)
        for n in range(4):
            child.finish_piece(
                n,
                cost_ms=12.5,
                piece=res.Piece(number=n, parent_id=parent.id, length=1 << 20, cost_ms=12.5, created_at=1.0),
            )
        child.fsm.event(res.PEER_EVENT_DOWNLOAD_SUCCEEDED)

        rec = build_download_record(child)
        assert rec.id == child.id
        assert rec.state == res.PEER_STATE_SUCCEEDED
        assert len(rec.parents) == 1
        assert rec.parents[0].id == parent.id
        assert len(rec.parents[0].pieces) == 4
        assert rec.parents[0].pieces[0].cost == int(12.5e6)

        s = Storage(tmp_path, buffer_size=1)
        s.create_download(rec)
        s.flush()
        back = s.list_download()
        assert len(back) == 1 and back[0].id == child.id

        # the record feeds the MLP feature extractor
        from dragonfly2_tpu.schema.columnar import records_to_columns
        from dragonfly2_tpu.schema.features import extract_pair_features

        pairs = extract_pair_features(records_to_columns(back))
        assert pairs.features.shape[0] == 1
        assert pairs.labels[0] == pytest.approx(np.log1p(12.5), rel=1e-5)


def test_announce_task_re_learns_host_from_carried_addressing():
    """Regression (round-2 ADVICE d): a restarted scheduler must accept
    an AnnounceTask that carries full host addressing (reference
    service_v1.go:349 registers the shipped PeerHost) and only NotFound
    when there is no addressing at all."""
    import grpc
    import pytest

    from dragonfly2_tpu.rpc import gen  # noqa: F401
    import common_pb2
    import scheduler_pb2

    from dragonfly2_tpu.scheduler import resource as res
    from dragonfly2_tpu.scheduler.evaluator import BaseEvaluator
    from dragonfly2_tpu.scheduler.scheduling import Scheduling, SchedulingConfig
    from dragonfly2_tpu.scheduler.service import SchedulerService

    resource = res.Resource()
    service = SchedulerService(resource, Scheduling(BaseEvaluator(), SchedulingConfig()))

    class Ctx:
        def abort(self, code, details):
            raise _Abort(code, details)

    class _Abort(Exception):
        def __init__(self, code, details):
            self.code = code
            self.details = details

    info = common_pb2.HostInfo(
        id="host-x", type="normal", hostname="hx", ip="10.0.0.5",
        port=65000, download_port=65001,
    )
    req = scheduler_pb2.AnnounceTaskRequest(
        host_id="host-x",
        task_id="t-1",
        peer_id="p-1",
        url="https://o/x",
        content_length=100,
        piece_length=100,
        pieces=[common_pb2.PieceInfo(number=0, offset=0, length=100)],
        host=info,
    )
    service.AnnounceTask(req, Ctx())
    host = resource.host_manager.load("host-x")
    assert host is not None and host.ip == "10.0.0.5"
    peer = resource.peer_manager.load("p-1")
    assert peer is not None and peer.fsm.is_state(res.PEER_STATE_SUCCEEDED)

    # no known host, no addressing → NotFound
    bare = scheduler_pb2.AnnounceTaskRequest(
        host_id="host-unknown", task_id="t-2", peer_id="p-2", url="https://o/y",
    )
    with pytest.raises(_Abort) as e:
        service.AnnounceTask(bare, Ctx())
    assert e.value.code == grpc.StatusCode.NOT_FOUND
