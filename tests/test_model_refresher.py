"""The train→serve loop's last hop: manager-activated models must reach a
running scheduler's MLEvaluator (reference designed this flow but left it
TODO at evaluator.go:53 / model.go:109 — see scheduler/model_refresher.py).
"""

import numpy as np
import pytest

from dragonfly2_tpu.rpc import gen  # noqa: F401
import manager_pb2  # noqa: E402

from dragonfly2_tpu.manager.database import Database
from dragonfly2_tpu.manager.models_registry import ModelRegistry
from dragonfly2_tpu.manager.objectstorage import FSObjectStorage
from dragonfly2_tpu.manager.service import SERVICE_NAME, ManagerService
from dragonfly2_tpu.rpc.glue import ServiceClient, dial, serve
from dragonfly2_tpu.scheduler.evaluator import MLEvaluator
from dragonfly2_tpu.scheduler.model_refresher import ModelRefresher
from dragonfly2_tpu.schema.features import MLP_FEATURE_NAMES
from dragonfly2_tpu.trainer.serving import serialize_params


@pytest.fixture
def manager(tmp_path):
    db = Database(tmp_path / "manager.db")
    registry = ModelRegistry(db, FSObjectStorage(tmp_path / "objects"))
    service = ManagerService(db, registry)
    server, port = serve({SERVICE_NAME: service})
    channel = dial(f"127.0.0.1:{port}")
    client = ServiceClient(channel, SERVICE_NAME)
    yield client
    channel.close()
    server.stop(0)


def _mlp_params(seed: int = 0):
    import jax

    from dragonfly2_tpu.models.mlp import init_mlp

    return init_mlp(jax.random.PRNGKey(seed), [len(MLP_FEATURE_NAMES), 16, 1])


def _upload(client, params, model_id="mlp-model", cluster_id=1):
    client.CreateModel(
        manager_pb2.CreateModelRequest(
            model_id=model_id,
            type="mlp",
            ip="10.0.0.1",
            hostname="trainer-host",
            weights=serialize_params(params),
            evaluation=manager_pb2.ModelEvaluation(mse=0.1, mae=0.2),
            scheduler_cluster_id=cluster_id,
        )
    )


def test_refresher_installs_active_model(manager):
    evaluator = MLEvaluator()
    refresher = ModelRefresher(manager, evaluator, scheduler_cluster_id=1)

    # upload v1 but do NOT activate: refresher must not install it
    params = _mlp_params()
    _upload(manager, params)
    assert not refresher.refresh_once()
    assert evaluator._model is None

    # activate → install
    manager.UpdateModel(
        manager_pb2.UpdateModelRequest(model_id="mlp-model", version=1, state="active")
    )
    assert refresher.refresh_once()
    assert refresher.loaded_version == ("mlp-model", 1)
    scorer = evaluator._model
    assert scorer is not None

    # the installed scorer must agree with direct application of the
    # uploaded params (weights round-tripped through npz + auto-structure)
    from dragonfly2_tpu.models.mlp import score_parents

    feats = np.random.default_rng(0).random((4, len(MLP_FEATURE_NAMES))).astype(np.float32)
    want = np.asarray(score_parents(params, feats))
    np.testing.assert_allclose(scorer.predict(feats), want, rtol=1e-5)

    # same version again: no reinstall
    assert not refresher.refresh_once()


def test_refresher_upgrades_and_withdraws(manager):
    evaluator = MLEvaluator()
    refresher = ModelRefresher(manager, evaluator, scheduler_cluster_id=1)

    _upload(manager, _mlp_params(0))
    manager.UpdateModel(
        manager_pb2.UpdateModelRequest(model_id="mlp-model", version=1, state="active")
    )
    assert refresher.refresh_once()

    # v2 activation flips serving to the new version
    _upload(manager, _mlp_params(1))
    manager.UpdateModel(
        manager_pb2.UpdateModelRequest(model_id="mlp-model", version=2, state="active")
    )
    assert refresher.refresh_once()
    assert refresher.loaded_version == ("mlp-model", 2)

    # corrupt v3: refresher must keep serving v2
    manager.CreateModel(
        manager_pb2.CreateModelRequest(
            model_id="mlp-model", type="mlp", weights=b"not-an-npz",
            evaluation=manager_pb2.ModelEvaluation(), scheduler_cluster_id=1,
        )
    )
    manager.UpdateModel(
        manager_pb2.UpdateModelRequest(model_id="mlp-model", version=3, state="active")
    )
    assert not refresher.refresh_once()
    assert refresher.loaded_version == ("mlp-model", 2)
    assert evaluator._model is not None


def test_reactivating_older_model_takes_effect(tmp_path):
    """Regression (round-2 ADVICE b): with two active-capable model ids,
    re-activating the OLDER one must install it — selection follows
    activation recency (updated_at), not creation time."""
    import numpy as np

    from dragonfly2_tpu.manager.database import Database
    from dragonfly2_tpu.manager.models_registry import ModelRegistry
    from dragonfly2_tpu.manager.objectstorage import FSObjectStorage
    from dragonfly2_tpu.manager.service import ManagerService
    from dragonfly2_tpu.rpc.glue import serve, dial, ServiceClient
    from dragonfly2_tpu.rpc import gen  # noqa: F401
    from dragonfly2_tpu.manager.service import SERVICE_NAME
    from dragonfly2_tpu.scheduler.evaluator import MLEvaluator
    from dragonfly2_tpu.scheduler.model_refresher import ModelRefresher
    from dragonfly2_tpu.trainer.serving import serialize_params
    from dragonfly2_tpu.models import mlp as mlp_mod
    from dragonfly2_tpu.schema.features import MLP_FEATURE_DIM
    import jax

    db = Database(tmp_path / "m.db")
    models = ModelRegistry(db, FSObjectStorage(tmp_path / "obj"))
    service = ManagerService(db, models)
    server, port = serve({SERVICE_NAME: service})
    try:
        params = mlp_mod.init_mlp(jax.random.PRNGKey(0), [MLP_FEATURE_DIM, 8, 1])
        blob = serialize_params(
            jax.tree_util.tree_map(lambda x: np.asarray(x), params)
        )
        models.create("mlp-old", "mlp", blob, {"mse": 0.5}, scheduler_cluster_id=1)
        models.create("mlp-new", "mlp", blob, {"mse": 0.4}, scheduler_cluster_id=1)
        models.activate("mlp-old", 1)
        models.activate("mlp-new", 1)

        ch = dial(f"127.0.0.1:{port}")
        ev = MLEvaluator()
        r = ModelRefresher(ServiceClient(ch, SERVICE_NAME), ev, scheduler_cluster_id=1)
        assert r.refresh_once()
        assert r.loaded_version == ("mlp-new", 1)  # newest activation

        # operator re-activates the OLDER model id: must take effect
        models.activate("mlp-old", 1)
        assert r.refresh_once()
        assert r.loaded_version == ("mlp-old", 1)
        ch.close()
    finally:
        server.stop(0)
        db.close()


def test_refresher_hot_swaps_serving_slot(manager):
    """With a scoring service attached, an MLP activation installs BOTH
    the per-call scorer (the fallback rung) and the batched serving
    model; a version flip hot-swaps serving without a restart."""
    from dragonfly2_tpu.scheduler.serving import ScoringService, ServingConfig

    evaluator = MLEvaluator()
    svc = ScoringService(ServingConfig(window_s=0.002))
    svc.start()
    try:
        refresher = ModelRefresher(
            manager, evaluator, scheduler_cluster_id=1, serving=svc
        )
        _upload(manager, _mlp_params(0))
        manager.UpdateModel(
            manager_pb2.UpdateModelRequest(
                model_id="mlp-model", version=1, state="active"
            )
        )
        assert refresher.refresh_once()
        assert svc.available() and svc.model_kind() == "mlp"
        assert svc.snapshot()["model_version"] == "mlp-model/v1"
        # the batched path scores through the freshly-installed model
        feats = np.zeros((3, len(MLP_FEATURE_NAMES)), np.float32)
        np.testing.assert_allclose(
            svc.score(feats), evaluator._model.predict(feats), rtol=1e-5
        )

        # v2 activation hot-swaps the serving slot
        _upload(manager, _mlp_params(1))
        manager.UpdateModel(
            manager_pb2.UpdateModelRequest(
                model_id="mlp-model", version=2, state="active"
            )
        )
        assert refresher.refresh_once()
        assert svc.snapshot()["model_version"] == "mlp-model/v2"

        # explicit deactivation withdraws serving too
        manager.UpdateModel(
            manager_pb2.UpdateModelRequest(
                model_id="mlp-model", version=2, state="inactive"
            )
        )
        refresher.refresh_once()
        assert not svc.available()
    finally:
        svc.stop()


def test_refresher_compiles_every_serving_rung_before_install(manager, monkeypatch):
    """A rung first met on the serving thread stalls every queued
    decision behind its XLA compile — on the chip for longer than the
    service's grace, so decisions drop a rung (chip_smoke caught it).
    The refresher therefore runs the scorer at every rung a packed batch
    can reach, plain and wave-ranked, BEFORE the swap."""
    from dragonfly2_tpu.scheduler.serving import ScoringService, ServingConfig
    from dragonfly2_tpu.trainer.serving import MLPScorer

    calls: list = []  # ("predict" | "predict_ranked", rows) … then "install"
    for name in ("predict", "predict_ranked"):
        real = getattr(MLPScorer, name)

        def spied(self, features, *rest, _real=real, _name=name):
            calls.append((_name, features.shape[0]))
            return _real(self, features, *rest)

        monkeypatch.setattr(MLPScorer, name, spied)
    svc = ScoringService(ServingConfig(max_rows=64))
    real_install = svc.install
    monkeypatch.setattr(
        svc, "install", lambda *a, **kw: (calls.append("install"), real_install(*a, **kw))
    )
    refresher = ModelRefresher(manager, MLEvaluator(), scheduler_cluster_id=1, serving=svc)
    _upload(manager, _mlp_params(0))
    manager.UpdateModel(
        manager_pb2.UpdateModelRequest(model_id="mlp-model", version=1, state="active")
    )
    assert refresher.refresh_once()
    rungs = (8, 16, 32, 64, 128)  # the ladder + one overshooting request
    assert set(calls[: calls.index("install")]) == {
        (name, rows) for name in ("predict", "predict_ranked") for rows in rungs
    }


def test_refresher_gnn_occupies_serving_and_withdraws_to_mlp(manager):
    """An active GNN takes the batched serving slot (embeddings built at
    swap time from the live probe graph); withdrawing it falls serving
    back to the loaded MLP — the ladder's top rung is an operator
    decision, the rungs below it never vanish."""
    import jax

    from dragonfly2_tpu.models.gnn import init_graphsage
    from dragonfly2_tpu.scheduler.networktopology import NetworkTopology, Probe
    from dragonfly2_tpu.scheduler.resource.host import Host
    from dragonfly2_tpu.scheduler.resource.managers import HostManager
    from dragonfly2_tpu.scheduler.serving import ScoringService, ServingConfig
    from dragonfly2_tpu.schema.features import GNN_NODE_FEATURE_DIM
    from dragonfly2_tpu.utils.kvstore import KVStore

    # a live probe graph with three hosts: the GNN's swap-time embed source
    hm = HostManager()
    for hid in ("h-a", "h-b", "h-c"):
        hm.store(Host(id=hid, hostname=hid, ip="10.0.0.1", port=1))
    nt = NetworkTopology(KVStore(), hm, None)
    ms = 1_000_000
    nt.enqueue_probe("h-a", Probe("h-b", rtt_ns=2 * ms))
    nt.enqueue_probe("h-b", Probe("h-c", rtt_ns=5 * ms))
    nt.enqueue_probe("h-c", Probe("h-a", rtt_ns=9 * ms))

    evaluator = MLEvaluator()
    svc = ScoringService(ServingConfig(window_s=0.002))
    svc.start()
    try:
        refresher = ModelRefresher(
            manager,
            evaluator,
            scheduler_cluster_id=1,
            serving=svc,
            networktopology=nt,
        )
        # MLP first: serving starts on the mlp rung
        _upload(manager, _mlp_params(0))
        manager.UpdateModel(
            manager_pb2.UpdateModelRequest(
                model_id="mlp-model", version=1, state="active"
            )
        )
        assert refresher.refresh_once()
        assert svc.model_kind() == "mlp"

        # activate a GNN: it takes the serving slot
        gnn_params = init_graphsage(
            jax.random.PRNGKey(0), GNN_NODE_FEATURE_DIM, (8,), num_nodes=3
        )
        manager.CreateModel(
            manager_pb2.CreateModelRequest(
                model_id="gnn-model",
                type="gnn",
                weights=serialize_params(gnn_params),
                evaluation=manager_pb2.ModelEvaluation(mse=0.1),
                scheduler_cluster_id=1,
            )
        )
        manager.UpdateModel(
            manager_pb2.UpdateModelRequest(
                model_id="gnn-model", version=1, state="active"
            )
        )
        assert refresher.refresh_once()
        assert svc.model_kind() == "gnn"
        assert refresher.loaded_gnn_version == ("gnn-model", 1)
        # the GNN scores known-host pairs through the batched API
        scores = svc.score(
            np.zeros((2, len(MLP_FEATURE_NAMES)), np.float32),
            pairs=[("h-a", "h-b"), ("h-a", "h-c")],
        )
        assert scores.shape == (2,) and np.isfinite(scores).all()

        # withdraw the GNN: serving falls back to the loaded MLP
        manager.UpdateModel(
            manager_pb2.UpdateModelRequest(
                model_id="gnn-model", version=1, state="inactive"
            )
        )
        refresher.refresh_once()
        assert svc.model_kind() == "mlp"
        assert refresher.loaded_gnn_version is None
    finally:
        svc.stop()


def test_gru_install_and_bad_node(tmp_path):
    """Train→serve for the GRU: a trained next-piece-cost model installs
    through the refresher and drives model-based bad-node detection —
    a parent whose last piece blew ~20x past its own history is flagged,
    a steady parent is not."""
    import numpy as np

    import manager_pb2

    from dragonfly2_tpu.scheduler import resource as res
    from dragonfly2_tpu.scheduler.evaluator import MLEvaluator
    from dragonfly2_tpu.scheduler.model_refresher import ModelRefresher
    from dragonfly2_tpu.schema.features import GRU_FEATURE_DIM, GRU_MAX_SEQ
    from dragonfly2_tpu.trainer.serving import serialize_params
    from dragonfly2_tpu.trainer.train import FitConfig, train_gru

    # train on flat sequences: next cost ≈ recent costs
    rng = np.random.default_rng(0)
    n = 512
    base = rng.uniform(2.0, 5.0, size=(n, 1))
    # variable lengths: serving histories are often shorter than the max,
    # so the model must see short sequences too
    lengths = rng.integers(3, GRU_MAX_SEQ + 1, size=n).astype(np.int32)
    seqs = np.zeros((n, GRU_MAX_SEQ, GRU_FEATURE_DIM), np.float32)
    for i in range(n):
        L = lengths[i]
        seqs[i, :L, 0] = base[i, 0] + rng.normal(0, 0.05, size=L)
        seqs[i, :L, 1] = (np.arange(L) + 1) / 10.0
    labels = (base[:, 0] + rng.normal(0, 0.05, size=n)).astype(np.float32)
    result = train_gru(
        seqs, labels, lengths=lengths,
        config=FitConfig(hidden_dims=(32,), batch_size=128, epochs=10),
    )
    blob = serialize_params(result.params)

    class FakeManager:
        def ListModels(self, req):
            return manager_pb2.ListModelsResponse(
                models=[
                    manager_pb2.Model(
                        model_id="gru-h", type="gru", version=1, state="active",
                        updated_at_ns=1,
                    )
                ]
            )

        def GetModelWeights(self, req):
            return manager_pb2.ModelWeights(weights=blob)

    evaluator = MLEvaluator()
    refresher = ModelRefresher(FakeManager(), evaluator, scheduler_cluster_id=1)
    refresher.refresh_once()
    assert refresher.loaded_gru_version == ("gru-h", 1)
    assert evaluator._gru is not None

    host = res.Host(id="h1")
    task = res.Task("t1", "https://e/x")
    steady = res.Peer("steady", task, host)
    spiky = res.Peer("spiky", task, host)
    for p in (steady, spiky):  # Pending is itself a bad state — run them
        p.fsm.event(res.PEER_EVENT_REGISTER_NORMAL)
        p.fsm.event(res.PEER_EVENT_DOWNLOAD)
    # histories in ms-scale log space ≈ exp(3..5); steady stays flat,
    # spiky's last piece is ~1000x its history
    for _ in range(6):
        steady.append_piece_cost(30.0)
        spiky.append_piece_cost(30.0)
    steady.append_piece_cost(33.0)
    spiky.append_piece_cost(30_000.0)
    assert evaluator.is_bad_node(spiky)
    assert not evaluator.is_bad_node(steady)

    # withdrawal falls back to base statistics
    class EmptyManager(FakeManager):
        def ListModels(self, req):
            return manager_pb2.ListModelsResponse(models=[])

    refresher.manager = EmptyManager()
    refresher.refresh_once()
    assert refresher.loaded_gru_version is None
    assert evaluator._gru is None
