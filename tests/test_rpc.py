"""RPC plane integration: real gRPC on localhost — AnnouncePeer bidi
scheduling, SyncProbes, host announce/leave, and the announcer→trainer
Train stream firing an actual fit."""

import queue
import threading
import time

import numpy as np
import pytest

import grpc

from dragonfly2_tpu.rpc import gen  # noqa: F401
import common_pb2
import scheduler_pb2
import trainer_pb2

from dragonfly2_tpu.rpc.glue import (
    SERVICES,
    ConsistentHashRing,
    ServiceClient,
    dial,
    serve,
)
from dragonfly2_tpu.scheduler import resource as res
from dragonfly2_tpu.scheduler.announcer import Announcer
from dragonfly2_tpu.scheduler.evaluator import BaseEvaluator
from dragonfly2_tpu.scheduler.networktopology import NetworkTopology
from dragonfly2_tpu.scheduler.scheduling import Scheduling, SchedulingConfig
from dragonfly2_tpu.scheduler.service import SERVICE_NAME as SCHED_SERVICE
from dragonfly2_tpu.scheduler.service import SchedulerService
from dragonfly2_tpu.scheduler.storage import Storage
from dragonfly2_tpu.trainer.service import SERVICE_NAME as TRAINER_SERVICE
from dragonfly2_tpu.trainer.service import TrainerService
from dragonfly2_tpu.trainer.storage import TrainerStorage
from dragonfly2_tpu.trainer.train import FitConfig, GNNFitConfig
from dragonfly2_tpu.trainer.training import Training, TrainingConfig
from dragonfly2_tpu.utils.kvstore import KVStore


class StreamDriver:
    """Queue-driven bidi client: push requests, read responses."""

    def __init__(self, call_fn):
        self._q = queue.Queue()
        self._responses = call_fn(iter(self._q.get, None))

    def send(self, req):
        self._q.put(req)

    def close(self):
        self._q.put(None)

    def recv(self, timeout=5.0):
        out = {}

        def read():
            try:
                out["resp"] = next(self._responses)
            except StopIteration:
                out["resp"] = None

        t = threading.Thread(target=read, daemon=True)
        t.start()
        t.join(timeout)
        if "resp" not in out:
            raise TimeoutError("no response within timeout")
        return out["resp"]


def wait_until(cond, timeout=5.0, interval=0.02):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


def make_host_info(i, seed=False):
    return common_pb2.HostInfo(
        id=f"host-{i}",
        type="super" if seed else "normal",
        hostname=f"h{i}",
        ip=f"10.0.0.{i}",
        port=8002,
        download_port=8001,
        concurrent_upload_limit=50,
        network=common_pb2.NetworkStat(idc="idc-a", location="as|cn|sh|dc1"),
    )


@pytest.fixture
def cluster(tmp_path):
    resource = res.Resource()
    storage = Storage(tmp_path / "sched", buffer_size=1)
    nt = NetworkTopology(KVStore(), resource.host_manager, storage)
    service = SchedulerService(
        resource,
        Scheduling(BaseEvaluator(), SchedulingConfig(retry_interval=0.0, retry_back_to_source_limit=1)),
        storage=storage,
        networktopology=nt,
    )
    server, port = serve({SCHED_SERVICE: service})
    channel = dial(f"127.0.0.1:{port}")
    client = ServiceClient(channel, SCHED_SERVICE)
    yield resource, storage, nt, client, service
    channel.close()
    server.stop(0)


def register_and_run_seed(client, task_id="task-1"):
    """Seed peer registers, goes back-to-source, finishes all pieces."""
    client.AnnounceHost(scheduler_pb2.AnnounceHostRequest(host=make_host_info(0, seed=True)))
    d = StreamDriver(client.AnnouncePeer)
    d.send(
        scheduler_pb2.AnnouncePeerRequest(
            host_id="host-0",
            task_id=task_id,
            peer_id="seed-peer",
            register_peer=scheduler_pb2.RegisterPeerRequest(
                task_id=task_id, peer_id="seed-peer", url="https://origin/blob"
            ),
        )
    )
    resp = d.recv()  # unknown size → normal register → no parents → back-to-source
    assert resp.WhichOneof("response") == "need_back_to_source"
    d.send(
        scheduler_pb2.AnnouncePeerRequest(
            host_id="host-0", task_id=task_id, peer_id="seed-peer",
            download_peer_back_to_source_started=scheduler_pb2.DownloadPeerBackToSourceStartedRequest(),
        )
    )
    for n in range(8):
        d.send(
            scheduler_pb2.AnnouncePeerRequest(
                host_id="host-0", task_id=task_id, peer_id="seed-peer",
                download_piece_finished=scheduler_pb2.DownloadPieceFinishedRequest(
                    piece=common_pb2.PieceInfo(
                        number=n, parent_id="", offset=n << 20, length=1 << 20,
                        traffic_type="back_to_source", cost_ns=int(5e6),
                    )
                ),
            )
        )
    d.send(
        scheduler_pb2.AnnouncePeerRequest(
            host_id="host-0", task_id=task_id, peer_id="seed-peer",
            download_peer_finished=scheduler_pb2.DownloadPeerFinishedRequest(
                content_length=8 << 20, piece_count=8, cost_ns=int(1e9)
            ),
        )
    )
    return d


class TestAnnouncePeer:
    def test_schedule_child_off_seed(self, cluster):
        resource, storage, nt, client, _ = cluster
        seed_stream = register_and_run_seed(client)
        assert wait_until(
            lambda: (p := resource.peer_manager.load("seed-peer")) is not None
            and p.fsm.current == "Succeeded"
        )
        # scheduler needs task piece metadata for scope; set after seed run
        task = resource.task_manager.load("task-1")
        task.total_piece_count = 8

        client.AnnounceHost(scheduler_pb2.AnnounceHostRequest(host=make_host_info(1)))
        d = StreamDriver(client.AnnouncePeer)
        d.send(
            scheduler_pb2.AnnouncePeerRequest(
                host_id="host-1", task_id="task-1", peer_id="child-1",
                register_peer=scheduler_pb2.RegisterPeerRequest(
                    task_id="task-1", peer_id="child-1", url="https://origin/blob"
                ),
            )
        )
        resp = d.recv()
        assert resp.WhichOneof("response") == "normal_task"
        parents = resp.normal_task.candidate_parents
        assert [p.peer_id for p in parents] == ["seed-peer"]
        assert parents[0].host.download_port == 8001
        assert list(parents[0].finished_pieces) == list(range(8))

        # piece events then completion → download record written
        d.send(
            scheduler_pb2.AnnouncePeerRequest(
                host_id="host-1", task_id="task-1", peer_id="child-1",
                download_peer_started=scheduler_pb2.DownloadPeerStartedRequest(),
            )
        )
        for n in range(8):
            d.send(
                scheduler_pb2.AnnouncePeerRequest(
                    host_id="host-1", task_id="task-1", peer_id="child-1",
                    download_piece_finished=scheduler_pb2.DownloadPieceFinishedRequest(
                        piece=common_pb2.PieceInfo(
                            number=n, parent_id="seed-peer", offset=n << 20,
                            length=1 << 20, traffic_type="remote_peer", cost_ns=int(12e6),
                        )
                    ),
                )
            )
        d.send(
            scheduler_pb2.AnnouncePeerRequest(
                host_id="host-1", task_id="task-1", peer_id="child-1",
                download_peer_finished=scheduler_pb2.DownloadPeerFinishedRequest(
                    content_length=8 << 20, piece_count=8, cost_ns=int(2e9)
                ),
            )
        )
        d.close()
        seed_stream.close()

        def child_record_written():
            storage.flush()
            return any(r.id == "child-1" for r in storage.list_download())

        assert wait_until(child_record_written)
        child_recs = [r for r in storage.list_download() if r.id == "child-1"]
        assert len(child_recs) == 1
        assert child_recs[0].parents[0].id == "seed-peer"
        assert len(child_recs[0].parents[0].pieces) == 8
        # upload outcome accounting reached the seed host
        assert resource.host_manager.load("host-0").upload_count == 8

    def test_reschedule_blocks_parent(self, cluster):
        resource, storage, nt, client, _ = cluster
        seed_stream = register_and_run_seed(client)
        assert wait_until(
            lambda: (p := resource.peer_manager.load("seed-peer")) is not None
            and p.fsm.current == "Succeeded"
        )
        resource.task_manager.load("task-1").total_piece_count = 8
        client.AnnounceHost(scheduler_pb2.AnnounceHostRequest(host=make_host_info(1)))
        d = StreamDriver(client.AnnouncePeer)
        d.send(
            scheduler_pb2.AnnouncePeerRequest(
                host_id="host-1", task_id="task-1", peer_id="child-1",
                register_peer=scheduler_pb2.RegisterPeerRequest(
                    task_id="task-1", peer_id="child-1", url="https://origin/blob"
                ),
            )
        )
        assert d.recv().WhichOneof("response") == "normal_task"
        # block the only parent → reschedule must fall to back-to-source
        d.send(
            scheduler_pb2.AnnouncePeerRequest(
                host_id="host-1", task_id="task-1", peer_id="child-1",
                reschedule=scheduler_pb2.RescheduleRequest(blocked_parent_ids=["seed-peer"]),
            )
        )
        resp = d.recv()
        assert resp.WhichOneof("response") == "need_back_to_source"
        d.close()
        seed_stream.close()

    def test_stat_and_leave(self, cluster):
        resource, _, _, client, _ = cluster
        seed_stream = register_and_run_seed(client)
        assert wait_until(
            lambda: (p := resource.peer_manager.load("seed-peer")) is not None
            and p.fsm.current == "Succeeded"
        )
        stat = client.StatPeer(scheduler_pb2.StatPeerRequest(task_id="task-1", peer_id="seed-peer"))
        assert stat.state == "Succeeded"
        assert stat.finished_piece_count == 8
        task_stat = client.StatTask(scheduler_pb2.StatTaskRequest(task_id="task-1"))
        assert task_stat.has_available_peer
        client.LeavePeer(scheduler_pb2.LeavePeerRequest(task_id="task-1", peer_id="seed-peer"))
        assert resource.peer_manager.load("seed-peer").fsm.current == "Leave"
        with pytest.raises(grpc.RpcError):
            client.StatPeer(scheduler_pb2.StatPeerRequest(task_id="task-1", peer_id="ghost"))
        seed_stream.close()

    def test_leave_host_purges_topology(self, cluster):
        resource, _, nt, client, _ = cluster
        client.AnnounceHost(scheduler_pb2.AnnounceHostRequest(host=make_host_info(5)))
        from dragonfly2_tpu.scheduler.networktopology import Probe

        nt.enqueue_probe("host-5", Probe("host-0", rtt_ns=1000))
        client.LeaveHost(scheduler_pb2.LeaveHostRequest(host_id="host-5"))
        assert resource.host_manager.load("host-5") is None
        assert not nt.has_edge("host-5", "host-0")


class TestSyncProbes:
    def test_probe_round(self, cluster):
        resource, _, nt, client, _ = cluster
        for i in range(6):
            client.AnnounceHost(scheduler_pb2.AnnounceHostRequest(host=make_host_info(i)))
        d = StreamDriver(client.SyncProbes)
        d.send(
            scheduler_pb2.SyncProbesRequest(
                host=make_host_info(0),
                probe_started=scheduler_pb2.ProbeStartedRequest(),
            )
        )
        resp = d.recv()
        targets = [h.host.id for h in resp.hosts]
        assert 0 < len(targets) <= 5 and "host-0" not in targets
        d.send(
            scheduler_pb2.SyncProbesRequest(
                host=make_host_info(0),
                probe_finished=scheduler_pb2.ProbeFinishedRequest(
                    probes=[
                        scheduler_pb2.ProbeResult(host_id=t, rtt_ns=int(3e6))
                        for t in targets
                    ]
                ),
            )
        )
        d.close()
        assert wait_until(lambda: nt.average_rtt("host-0", targets[0]) == int(3e6))


class TestTrainStream:
    def test_announcer_upload_triggers_training(self, tmp_path):
        from dragonfly2_tpu.schema import synth
        from dragonfly2_tpu.schema.columnar import write_csv

        # scheduler side: storage with datasets
        sched_storage = Storage(tmp_path / "sched", buffer_size=1)
        for r in synth.make_download_records(100, seed=1):
            sched_storage.create_download(r)
        for r in synth.make_topology_records(300, num_hosts=24, seed=2):
            sched_storage.create_network_topology(r)
        sched_storage.flush()

        # trainer side: real service, synchronous fit, recording manager
        class RecordingManager:
            def __init__(self):
                self.models = {}

            def create_model(self, model_id, model_type, ip, hostname, params, evaluation):
                self.models[model_type] = evaluation

        manager = RecordingManager()
        t_storage = TrainerStorage(tmp_path / "trainer")
        training = Training(
            t_storage,
            manager,
            TrainingConfig(
                mlp=FitConfig(hidden_dims=(16,), batch_size=128, epochs=3, seed=0),
                gnn=GNNFitConfig(hidden_dims=(16,), batch_size=256, epochs=60, learning_rate=3e-2, seed=0),
            ),
        )
        service = TrainerService(t_storage, training, synchronous=True)
        server, port = serve({TRAINER_SERVICE: service})
        channel = dial(f"127.0.0.1:{port}")

        ann = Announcer(
            sched_storage,
            ip="10.1.1.1",
            hostname="sched-A",
            trainer_channel=channel,
            upload_chunk=1 << 16,  # small chunks to exercise chunking
        )
        assert ann.train_once()
        # gru included: third family trains under production defaults (round 5)
        assert set(manager.models) == {"mlp", "gnn", "gru"}
        assert manager.models["mlp"]["mse"] > 0
        assert manager.models["gnn"]["f1"] > 0
        # scheduler's local datasets cleared after upload
        assert sched_storage.list_download() == []
        channel.close()
        server.stop(0)


class TestConsistentHash:
    def test_stable_assignment(self):
        ring = ConsistentHashRing(["s1:8002", "s2:8002", "s3:8002"])
        picks = {f"task-{i}": ring.pick(f"task-{i}") for i in range(50)}
        assert all(ring.pick(k) == v for k, v in picks.items())  # stable
        assert len(set(picks.values())) > 1  # spreads

    def test_remove_moves_only_affected(self):
        ring = ConsistentHashRing(["s1", "s2", "s3"])
        before = {f"t{i}": ring.pick(f"t{i}") for i in range(100)}
        ring.remove("s2")
        after = {k: ring.pick(k) for k in before}
        moved = [k for k in before if before[k] != after[k]]
        assert all(before[k] == "s2" for k in moved)  # only s2's keys moved
        assert all(v != "s2" for v in after.values())

    def test_empty_ring_raises(self):
        with pytest.raises(ValueError):
            ConsistentHashRing().pick("t")


def test_train_stream_accepts_a_chunk_past_grpc_default_limit():
    """The announcer ships the dataset in 128 MiB chunks. grpc servers
    refuse anything past 4 MiB unless told otherwise, and only the
    dialing side was told: every upload of real size died with
    RESOURCE_EXHAUSTED while toy-sized tests passed."""

    class Sink:
        received = 0

        def Capabilities(self, request, context):
            return trainer_pb2.CapabilitiesResponse()

        def Train(self, request_iterator, context):
            for req in request_iterator:
                self.received += len(req.train_mlp_binary.dataset)
            return trainer_pb2.TrainResponse()

    sink = Sink()
    server, port = serve({TRAINER_SERVICE: sink})
    channel = dial(f"127.0.0.1:{port}")
    try:
        chunk = b"\0" * (8 * 1024 * 1024)
        ServiceClient(channel, TRAINER_SERVICE).Train(
            iter(
                [
                    trainer_pb2.TrainRequest(
                        ip="127.0.0.1",
                        hostname="h",
                        train_mlp_binary=trainer_pb2.TrainMlpBinaryRequest(dataset=chunk),
                    )
                ]
            ),
            timeout=60,
        )
        assert sink.received == len(chunk)
    finally:
        channel.close()
        server.stop(0)
