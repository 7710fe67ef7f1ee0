"""The one-chip cluster (ISSUE 28): scheduler and trainer in one process.

The colocated service comes up as one process with both gRPC planes on
one jax backend; a version its trainer registers reaches its scoring
service by the program's own path (``create_model`` → the manager's
registry → activation → ``refresh_once()``); a decision asked during an
install is ranked wholly by one version; the spans inside a scored batch
and around the rtt gather move when they should; and the per-decision
rung counter counts every decision once, by the rung that ranked it."""

import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from dragonfly2_tpu.rpc import gen  # noqa: F401
import manager_pb2  # noqa: E402

from dragonfly2_tpu.colocated import ColocatedConfig, ColocatedServer
from dragonfly2_tpu.colocated import server as assembly
from dragonfly2_tpu.rpc import resilience
from dragonfly2_tpu.scheduler import resource as res
from dragonfly2_tpu.scheduler import scheduling as scheduling_mod
from dragonfly2_tpu.scheduler.evaluator import MLEvaluator
from dragonfly2_tpu.scheduler.serving import MLPServed, ScoringService, ServingConfig
from dragonfly2_tpu.schema.features import MLP_FEATURE_DIM
from dragonfly2_tpu.trainer.serving import MLPScorer, NumpyMLPScorer
from dragonfly2_tpu.utils import faults, profiling
from dragonfly2_tpu.utils.metrics import default_registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
IP, HOSTNAME = "10.0.0.1", "colocated-scheduler"


def _params(seed: int = 0, sign: float = 1.0) -> dict:
    rng = np.random.default_rng(seed)
    return {
        "layers": [
            {"w": rng.normal(0, 0.3, (MLP_FEATURE_DIM, 32)).astype(np.float32), "b": np.zeros(32, np.float32)},
            {"w": sign * rng.normal(0, 0.3, (32, 1)).astype(np.float32), "b": np.zeros(1, np.float32)},
        ]
    }


def _swarm(candidates: int = 6):
    task = res.Task("colocated-test-task", "https://origin/x")
    task.content_length = 64 * 1024 * 1024
    task.total_piece_count = 16
    parents = []
    for i in range(candidates):
        h = res.Host(id=f"parent-host-{i}", type=res.HostType.SUPER)
        h.network.idc = f"idc-{i % 2}"
        p = res.Peer(f"parent-{i}", task, h)
        for ev in (res.PEER_EVENT_REGISTER_NORMAL, res.PEER_EVENT_DOWNLOAD, res.PEER_EVENT_DOWNLOAD_SUCCEEDED):
            p.fsm.event(ev)
        p.finished_pieces |= set(range(i + 1))
        parents.append(p)
    child = res.Peer("child-0", task, res.Host(id="child-host-0"))
    child.fsm.event(res.PEER_EVENT_REGISTER_NORMAL)
    return parents, child, task


def _series(name: str) -> dict:
    out = {}
    for line in default_registry.expose().splitlines():
        if line.startswith(name):
            key, _, value = line.rpartition(" ")
            out[key] = float(value)
    return out


def _rungs() -> dict:
    got = _series("dragonfly_scheduler_decision_rung_total")
    return {r: got.get('dragonfly_scheduler_decision_rung_total{rung="%s"}' % r, 0.0) for r in ("serving", "mlp", "base")}


@pytest.fixture
def clean_state():
    faults.clear()
    resilience.reset()
    yield
    faults.clear()
    resilience.reset()


# -- the service --------------------------------------------------------------


def test_the_binary_prints_both_ready_lines_and_stops_both_on_a_signal(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    proc = subprocess.Popen(
        [sys.executable, "-m", "dragonfly2_tpu.colocated", "--set", f"data_dir={tmp_path}",
         "--set", "scheduler={hostname: one-chip}"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True, env=env, cwd=ROOT,
    )
    try:
        ready = {}
        deadline = time.time() + 120
        while len(ready) < 2 and time.time() < deadline:
            line = proc.stdout.readline()
            if not line:
                break
            word, *rest = line.split()
            if word == "READY":
                ready[rest[0]] = rest[1]
        assert set(ready) == {"trainer", "scheduler"} and ready["trainer"] != ready["scheduler"]
        assert list(ready) == ["trainer", "scheduler"]  # the announcer's target is up first
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=60) == 0
    finally:
        if proc.poll() is None:
            proc.kill()


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    """A manager's registry on gRPC and the colocated service beside it;
    one toy round through the service's own trainer."""
    from dragonfly2_tpu.manager.database import Database
    from dragonfly2_tpu.manager.models_registry import ModelRegistry
    from dragonfly2_tpu.manager.objectstorage import FSObjectStorage
    from dragonfly2_tpu.manager.service import SERVICE_NAME, ManagerService
    from dragonfly2_tpu.rpc.glue import ServiceClient, dial, serve
    from dragonfly2_tpu.schema import synth, wire
    from dragonfly2_tpu.utils.idgen import host_id_v2

    tmp = tmp_path_factory.mktemp("colocated")
    db = Database(tmp / "manager.db")
    manager_server, port = serve({SERVICE_NAME: ManagerService(db, ModelRegistry(db, FSObjectStorage(tmp / "objects")))})
    channel = dial(f"127.0.0.1:{port}")
    srv = ColocatedServer(
        ColocatedConfig(
            data_dir=str(tmp / "data"),
            manager_address=f"127.0.0.1:{port}",
            trainer={"auto_mesh": False, "mlp_epochs": 1, "mlp_batch_size": 256, "gnn_epochs": 2,
                     "telemetry_interval": 0.0},
            scheduler={"hostname": HOSTNAME, "telemetry_interval": 0.0, "model_refresh_interval": 3600.0},
        )
    )
    addrs = srv.serve()
    host_id = host_id_v2(IP, HOSTNAME)
    srv.trainer.storage.append_download_blocks(
        host_id, wire.encode_train_block(synth.make_download_records(256, seed=3))
    )
    srv.trainer.storage.mark_download_round(host_id)
    outcome = srv.trainer.training.train(IP, HOSTNAME)
    yield {"srv": srv, "addrs": addrs, "manager": ServiceClient(channel, SERVICE_NAME), "outcome": outcome}
    srv.stop()
    channel.close()
    manager_server.stop(0)


@pytest.mark.parametrize("plane", ["trainer", "scheduler"])
def test_both_planes_serve_from_one_process_on_one_backend(cluster, plane):
    import jax
    from jax._src import xla_bridge

    from dragonfly2_tpu.rpc.glue import DIAGNOSE_SERVICE, ServiceClient, dial
    import diagnose_pb2

    srv = cluster["srv"]
    assert set(cluster["addrs"]) == {"trainer", "scheduler"}
    ch = dial(cluster["addrs"][plane])
    try:
        snap = ServiceClient(ch, DIAGNOSE_SERVICE).Diagnose(diagnose_pb2.DiagnoseRequest())
        assert snap.ByteSize() > 0
    finally:
        ch.close()
    # one process, one backend: the trainer was built resident, the
    # scheduler ml, and whatever either plane puts on a device goes
    # through the one client jax holds
    assert srv.trainer.cfg.streaming is False and srv.scheduler.cfg.algorithm == "ml"
    assert srv.scheduler.cfg.trainer_address == cluster["addrs"]["trainer"]
    assert list(xla_bridge.backends()) == [jax.default_backend()]


def test_a_rounds_version_reaches_the_scoring_service_by_the_programs_own_path(cluster):
    srv, manager = cluster["srv"], cluster["manager"]
    svc, refresher = srv.scheduler.scoring_service, srv.scheduler.model_refresher
    listed = manager.ListModels(manager_pb2.ListModelsRequest(scheduler_cluster_id=1)).models
    mlp = [m for m in listed if m.type == "mlp"]
    assert len(mlp) == 1 and mlp[0].state != "active", cluster["outcome"]
    assert not refresher.refresh_once() and svc.snapshot()["model_version"] == ""
    manager.UpdateModel(
        manager_pb2.UpdateModelRequest(model_id=mlp[0].model_id, version=mlp[0].version, state="active")
    )
    assert refresher.refresh_once()
    snap = svc.snapshot()
    assert snap["running"] and snap["model_kind"] == "mlp"
    assert snap["model_version"] == f"{mlp[0].model_id}/v{mlp[0].version}"
    # and a decision is ranked by it
    parents, child, task = _swarm(5)
    before = _rungs()
    assert len(srv.scheduler.evaluator.evaluate_parents(parents, child, task.total_piece_count)) == 5
    after = _rungs()
    assert after["serving"] - before["serving"] == 1 and after["mlp"] == before["mlp"]


def test_the_service_settles_when_it_is_up_and_puts_it_all_back(cluster, tmp_path):
    """``settle()`` at the end of ``serve()``: the service's switch
    interval, and start-up's objects out of the collector's reach (full
    collections beside a round walk what came after); ``stop()`` hands
    both back."""
    import gc

    assert gc.get_freeze_count() > 50_000  # the module fixture's service is up
    from dragonfly2_tpu.colocated.server import SWITCH_INTERVAL_S

    assert sys.getswitchinterval() == pytest.approx(SWITCH_INTERVAL_S) == pytest.approx(0.0005)
    sys.setswitchinterval(0.005)  # what a process has before the service comes up
    second = ColocatedServer(
        ColocatedConfig(data_dir=str(tmp_path), trainer={"telemetry_interval": 0.0},
                        scheduler={"hostname": "second", "telemetry_interval": 0.0})
    )
    second.serve()
    assert sys.getswitchinterval() == pytest.approx(SWITCH_INTERVAL_S)
    # and what only this process can say: the round's stretch for the scheduler's decisions, full collections as spans
    assert scheduling_mod.stretch_provider is assembly.round_stretch
    assert gc.callbacks.count(profiling._on_collection) == 1
    second.stop()
    assert gc.get_freeze_count() == 0 and sys.getswitchinterval() == pytest.approx(0.005)
    assert scheduling_mod.stretch_provider is None
    sys.setswitchinterval(SWITCH_INTERVAL_S)
    gc.freeze()  # the fixture's service goes on as it was
    scheduling_mod.stretch_provider = assembly.round_stretch


# what has to be open for each of the provider's cases: the round's phase, the
# MLP leg's two load stretches, how many legs' fits
_STRETCH_CASES = {
    "walk": ("round", "mlp_fit", "gnn_fit", "gru_fit", "mlp_load_walk"),
    "assemble": ("round", "mlp_fit", "gru_fit", "mlp_load_assemble"),
    "fit_shared": ("round", "mlp_fit", "gru_fit"),
    "fit_alone": ("round", "gru_fit"),
    "idle": (),
}


@pytest.mark.parametrize("stretch", sorted(_STRETCH_CASES))
def test_the_provider_names_the_rounds_stretch_from_phases_other_threads_hold_open(stretch):
    """``round_stretch`` reads open-phase counts and nothing else: with
    the matching phases held open on threads of their own it names each
    of its five cases, and ``idle`` again once they close."""
    from dragonfly2_tpu.trainer import metrics as TM

    phases = {
        "round": TM.PH_ROUND, "mlp_fit": TM.PH_MLP.fit, "gnn_fit": TM.PH_GNN.fit, "gru_fit": TM.PH_GRU.fit,
        "mlp_load_walk": TM.PH_MLP.load_walk, "mlp_load_assemble": TM.PH_MLP.load_assemble,
    }
    assert assembly.round_stretch() == "idle"
    opened, release = threading.Barrier(len(_STRETCH_CASES[stretch]) + 1, timeout=30), threading.Event()

    def hold(ph):
        with ph:
            opened.wait()
            assert release.wait(timeout=30)

    holders = [threading.Thread(target=hold, args=(phases[name],)) for name in _STRETCH_CASES[stretch]]
    for t in holders:
        t.start()
    try:
        opened.wait()
        assert assembly.round_stretch() == stretch
    finally:
        release.set()
        for t in holders:
            t.join(timeout=30)
    assert assembly.round_stretch() == "idle"
    # a round with no leg's fit open (the pool's threads not yet begun, or all three done) is no shared fit
    with TM.PH_ROUND:
        assert assembly.round_stretch() == "fit_alone"


def test_decisions_beside_a_round_are_each_booked_under_one_stretch(cluster):
    """A toy window beside a toy round in the service's own process: the
    five ``find_parents_beside_*`` counts sum to ``find_parents``' own,
    decisions that began while the round ran are under its stretches,
    and those asked after it under ``idle``."""
    from dragonfly2_tpu.schema import synth, wire
    from dragonfly2_tpu.utils.idgen import host_id_v2

    srv = cluster["srv"]
    assert scheduling_mod.stretch_provider is assembly.round_stretch
    find = srv.scheduler.scheduling.find_candidate_parents
    parents, child, task = _swarm(6)
    for p in (*parents, child):
        task.store_peer(p)
    names = ("scheduler.find_parents", *(ph.name for ph in scheduling_mod.PH_FIND_PARENTS_BESIDE.values()))
    host_id = host_id_v2(IP, HOSTNAME)
    srv.trainer.storage.append_download_blocks(
        host_id, wire.encode_train_block(synth.make_download_records(256, seed=4))
    )
    srv.trainer.storage.mark_download_round(host_id)
    before = {n: _phase(n)["count"] for n in names}
    stop, asked = threading.Event(), [0, 0]

    def ask(k):
        while not stop.is_set():
            got, found = find(child)
            assert found and len(got) > 0
            asked[k] += 1
            time.sleep(0.002)

    workers = [threading.Thread(target=ask, args=(k,)) for k in range(2)]
    for t in workers:
        t.start()
    try:
        outcome = srv.trainer.training.train(IP, HOSTNAME)
    finally:
        stop.set()
        for t in workers:
            t.join(timeout=60)
    assert outcome.mlp_error is None, outcome  # the fixture stages no topology: the MLP leg's load is the round's here
    during = {n: _phase(n)["count"] - before[n] for n in names}
    for _ in range(3):
        assert find(child)[1]
    moved = {n: _phase(n)["count"] - before[n] for n in names}
    beside = {n.rsplit("beside_", 1)[1]: c for n, c in moved.items() if "beside_" in n}
    assert sum(beside.values()) == moved["scheduler.find_parents"] == sum(asked) + 3
    assert sum(during.values()) - during["scheduler.find_parents"] == during["scheduler.find_parents"]
    assert beside["idle"] >= 3 and sum(beside.values()) - beside["idle"] > 0, beside


@pytest.mark.parametrize("key, part", [("streaming", "trainer"), ("algorithm", "scheduler"), ("no_such_key", "scheduler")])
def test_a_key_the_assembly_sets_or_does_not_know_is_refused(tmp_path, key, part):
    with pytest.raises(TypeError):
        ColocatedServer(ColocatedConfig(data_dir=str(tmp_path), **{part: {key: True}}))


# -- a decision during an install ---------------------------------------------


@pytest.mark.parametrize("kind", ["numpy", "mlp"])
def test_a_decision_asked_during_an_install_is_ranked_wholly_by_one_version(clean_state, kind):
    """Two versions that rank in opposite orders are swapped in and out
    while decisions are asked; every returned order is one version's."""
    make = NumpyMLPScorer if kind == "numpy" else MLPScorer
    a, b = make(_params(1)), make(_params(1, sign=-1.0))
    svc = ScoringService(ServingConfig())
    svc.start()
    ev = MLEvaluator(serving=svc)
    parents, child, task = _swarm(7)

    def install(scorer, version):  # the refresher's two steps
        ev.set_model(scorer)
        svc.install(MLPServed(scorer, kind=kind), version=version)

    try:
        install(a, "t/v1")
        order_a = [p.id for p in ev.evaluate_parents(parents, child, task.total_piece_count)]
        install(b, "t/v2")
        order_b = [p.id for p in ev.evaluate_parents(parents, child, task.total_piece_count)]
        assert order_a == order_b[::-1] and order_a != order_b
        seen, stop = [], threading.Event()

        def ask():
            while not stop.is_set():
                seen.append(tuple(p.id for p in ev.evaluate_parents(parents, child, task.total_piece_count)))

        askers = [threading.Thread(target=ask, name=f"test.asker-{i}", daemon=True) for i in range(4)]
        for t in askers:
            t.start()
        for k in range(20):
            install(a if k % 2 else b, f"t/v{k + 3}")
            # on a loaded box the askers may be slow: every version ranks
            # some decisions before the next replaces it, however long that takes
            asked, deadline = len(seen), time.time() + 60
            while len(seen) < asked + 2 and time.time() < deadline:
                time.sleep(0.005)
        stop.set()
        for t in askers:
            t.join()
        assert len(seen) >= 40
        assert set(seen) == {tuple(order_a), tuple(order_b)}
    finally:
        svc.stop()


# -- spans ----------------------------------------------------------------------


def _phase(name: str) -> dict:
    return profiling.phase_type(name).snapshot()


@pytest.mark.parametrize("phase", ["score_pack", "score_h2d", "score_forward", "score_d2h", "score_unpack"])
def test_a_scored_batch_moves_each_of_its_phases_once(clean_state, phase):
    name = f"scheduler.{phase}"
    svc = ScoringService(ServingConfig())
    svc.start()
    try:
        svc.install(MLPServed(MLPScorer(_params(2))), version="t/v1")
        feats = np.random.default_rng(0).random((5, MLP_FEATURE_DIM)).astype(np.float32)
        svc.score_wave(feats, None, [5])  # compiles the rung
        before, batches = _phase(name), svc.batches
        out = svc.score_wave(feats, None, [3, 2])
        after = _phase(name)
        assert [len(scores) for scores, _ in out] == [3, 2]
        assert svc.batches == batches + 1
        assert after["count"] == before["count"] + 1 and after["total_s"] > before["total_s"]
    finally:
        svc.stop()


def test_a_host_scorer_has_no_device_stages(clean_state):
    """The numpy fallback rides the same batch path; it has no put, no
    device forward and no read to account."""
    names = [f"scheduler.score_{s}" for s in ("h2d", "forward", "d2h")]
    svc = ScoringService(ServingConfig())
    svc.start()
    try:
        svc.install(MLPServed(NumpyMLPScorer(_params(2)), kind="numpy"), version="t/v1")
        before = [_phase(n)["count"] for n in names]
        pack = _phase("scheduler.score_pack")["count"]
        svc.score_wave(np.zeros((4, MLP_FEATURE_DIM), np.float32), None, [4])
        assert [_phase(n)["count"] for n in names] == before
        assert _phase("scheduler.score_pack")["count"] == pack + 1
    finally:
        svc.stop()


@pytest.mark.parametrize("backend", ["jax", "numpy"])
def test_a_decision_that_joins_rtt_moves_the_gather(clean_state, backend):
    from dragonfly2_tpu.topology import TopologyConfig, TopologyEngine

    engine = TopologyEngine(TopologyConfig(backend=backend))
    parents, child, task = _swarm(4)
    now = time.time()
    hosts = [child.host.id] + [p.host.id for p in parents]
    # a ring of probes: the child reaches most parents by inference only
    for s, t in zip(hosts, hosts[1:] + hosts[:1]):
        engine.adopt(s, t, 3e6, now)
    engine.flush()
    ev = MLEvaluator(model=NumpyMLPScorer(_params(3)), topology=engine)
    before = _phase("topology.rtt_gather")
    assert len(ev.evaluate_parents(parents, child, task.total_piece_count)) == 4
    after = _phase("topology.rtt_gather")
    assert after["count"] == before["count"] + 1 and after["total_s"] > before["total_s"]
    # nothing to infer (every pair probed directly, or unknown): no gather
    direct = engine.rtt_affinity_pairs([hosts[0]], [hosts[1]])
    assert direct.shape == (1,) and _phase("topology.rtt_gather")["count"] == after["count"]


def test_the_served_forwards_are_named_apart_from_the_fits():
    """The trainer's holdout evaluation jits ``score_parents`` itself; a
    served forward is jitted as another function, and a device trace names
    an op by the jitted function it belongs to."""
    import jax

    from dragonfly2_tpu.models.mlp import score_parents
    from dragonfly2_tpu.trainer import serving

    scorer = serving.MLPScorer(_params(0))
    x = np.zeros((8, MLP_FEATURE_DIM), np.float32)
    packed = np.zeros((8, MLP_FEATURE_DIM + 1), np.float32)
    served = [
        scorer._fn.lower(_params(0), x).as_text().split("{", 1)[0],
        scorer._ranked.lower(_params(0), packed).as_text().split("{", 1)[0],
    ]
    assert "jit__served_mlp" in served[0] and "jit__score_ranked" in served[1], served
    assert "jit_score_parents" in jax.jit(score_parents).lower(_params(0), x).as_text().split("{", 1)[0]


# -- the per-decision counter -------------------------------------------------


@pytest.mark.parametrize("rung", ["serving", "mlp", "base"])
def test_every_decision_is_counted_once_by_the_rung_that_ranked_it(clean_state, rung):
    scorer = NumpyMLPScorer(_params(4))
    svc = ScoringService(ServingConfig())
    svc.start()
    ev = MLEvaluator(serving=svc)
    parents, child, task = _swarm(6)
    try:
        if rung != "base":
            ev.set_model(scorer)
            svc.install(MLPServed(scorer, kind="numpy"), version="t/v1")
        if rung == "mlp":
            # a forced ServingError: every served score fails, the
            # per-call MLP ranks
            faults.configure("scheduler.serving_score=error")
        fell = _series("dragonfly_scheduler_serving_fallback_total")
        before = _rungs()
        for _ in range(5):
            assert len(ev.evaluate_parents(parents, child, task.total_piece_count)) == 6
        ev.evaluate_parents([], child, task.total_piece_count)  # nothing to rank: not a decision ranked
        after = _rungs()
        assert {r: after[r] - before[r] for r in after} == {
            r: (5.0 if r == rung else 0.0) for r in after
        }
        # the edge-triggered counter stays what it was: one count a fall
        moved = sum(_series("dragonfly_scheduler_serving_fallback_total").values()) - sum(fell.values())
        assert moved <= 1
    finally:
        svc.stop()
