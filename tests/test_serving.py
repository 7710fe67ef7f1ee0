"""Device-resident batched scheduler inference (ROADMAP item 1 /
ISSUE 13): the scoring service turns per-decision model calls into
deadline-aware, shape-bucketed micro-batches. Covered here: batched ==
per-call ranking (bit-identical on the numpy fallback), the deadline
immediate-path escape, hot-swap mid-batch (no dropped, no mixed-model
batch), the GNN → MLP → Base degradation ladder under injected serving
faults with edge-triggered visible state, a concurrency soak asserting
zero lost submissions, and the bucket ladder holding steady-state
retraces at zero."""

import threading
import time

import numpy as np
import pytest

from dragonfly2_tpu.rpc import resilience
from dragonfly2_tpu.scheduler import resource as res
from dragonfly2_tpu.scheduler.evaluator import MLEvaluator
from dragonfly2_tpu.scheduler.serving import (
    GNNServed,
    MLPServed,
    ScoringService,
    ServingConfig,
    ServingError,
)
from dragonfly2_tpu.schema.features import MLP_FEATURE_DIM
from dragonfly2_tpu.trainer.serving import (
    BUCKET_LADDER,
    NumpyMLPScorer,
    bucket_rows,
    pad_batch,
)
from dragonfly2_tpu.utils import faults


@pytest.fixture
def clean_state():
    faults.clear()
    resilience.reset()
    yield
    faults.clear()
    resilience.reset()


def _numpy_scorer(seed: int = 0) -> NumpyMLPScorer:
    rng = np.random.default_rng(seed)
    return NumpyMLPScorer(
        {
            "layers": [
                {
                    "w": rng.normal(0, 0.3, (MLP_FEATURE_DIM, 32)).astype(
                        np.float32
                    ),
                    "b": np.zeros(32, np.float32),
                },
                {
                    "w": rng.normal(0, 0.3, (32, 1)).astype(np.float32),
                    "b": np.zeros(1, np.float32),
                },
            ]
        }
    )


def _swarm(candidates: int = 6, children: int = 1):
    task = res.Task("serving-test-task", "https://origin/x")
    task.content_length = 64 * 1024 * 1024
    task.total_piece_count = 16
    parents = []
    for i in range(candidates):
        h = res.Host(id=f"parent-host-{i}", type=res.HostType.SUPER)
        h.network.idc = f"idc-{i % 2}"
        p = res.Peer(f"parent-{i}", task, h)
        p.fsm.event(res.PEER_EVENT_REGISTER_NORMAL)
        p.fsm.event(res.PEER_EVENT_DOWNLOAD)
        p.fsm.event(res.PEER_EVENT_DOWNLOAD_SUCCEEDED)
        p.finished_pieces |= set(range(i + 1))
        parents.append(p)
    kids = []
    for i in range(children):
        c = res.Peer(f"child-{i}", task, res.Host(id=f"child-host-{i}"))
        c.fsm.event(res.PEER_EVENT_REGISTER_NORMAL)
        kids.append(c)
    return parents, kids, task


def _service(**cfg_kw) -> ScoringService:
    svc = ScoringService(ServingConfig(**cfg_kw))
    svc.start()
    return svc


# ---------------------------------------------------------------------------
# bucket ladder
# ---------------------------------------------------------------------------


def test_bucket_ladder_math():
    assert [bucket_rows(n) for n in (1, 7, 8, 9, 16, 17, 33, 64)] == [
        8, 8, 8, 16, 16, 32, 64, 64,
    ]
    # above the top rung: top-rung multiples, never per-size shapes
    top = BUCKET_LADDER[-1]
    assert bucket_rows(top + 1) == 2 * top
    assert bucket_rows(5 * top + 3) == 6 * top
    a = np.ones((3, 4), np.float32)
    padded = pad_batch(a, 8)
    assert padded.shape == (8, 4)
    assert np.array_equal(padded[:3], a) and not padded[3:].any()
    assert pad_batch(a, 3) is a  # no copy when already sized


def test_numpy_scorer_rows_are_batch_independent():
    """The fallback's contract: a row's score doesn't depend on which
    batch it rode in — the property the batched==per-call ranking
    test leans on."""
    s = _numpy_scorer()
    rng = np.random.default_rng(1)
    rows = rng.random((10, MLP_FEATURE_DIM)).astype(np.float32)
    whole = s.predict(rows)
    for i in range(10):
        np.testing.assert_array_equal(s.predict(rows[i : i + 1])[0], whole[i])


# ---------------------------------------------------------------------------
# batched vs per-call ranking
# ---------------------------------------------------------------------------


def test_batched_ranking_bit_identical_to_per_call_numpy(clean_state):
    """The acceptance core: concurrent decisions scored through the
    service's pack/score/split machinery rank (and score) EXACTLY like
    the per-call path on the numpy fallback — across candidate counts
    that share and straddle bucket rungs."""
    scorer = _numpy_scorer()
    svc = _service(window_s=0.005)
    svc.install(MLPServed(scorer, kind="numpy"), version="t/v1")
    try:
        for n_candidates in (1, 3, 6, 9, 17):
            parents, (child,), task = _swarm(candidates=n_candidates)
            per_call = MLEvaluator(scorer).evaluate_parents(
                parents, child, task.total_piece_count
            )
            batched = MLEvaluator(scorer, serving=svc).evaluate_parents(
                parents, child, task.total_piece_count
            )
            assert [p.id for p in batched] == [p.id for p in per_call]
    finally:
        svc.stop()


def test_concurrent_submissions_pack_and_score_exactly(clean_state):
    """Requests submitted concurrently co-batch (occupancy > one
    request) and every caller gets back bit-identical scores to a
    per-call predict of its own rows."""
    scorer = _numpy_scorer()
    svc = _service(window_s=0.02)
    svc.install(MLPServed(scorer, kind="numpy"), version="t/v1")
    rng = np.random.default_rng(2)
    mats = [
        rng.random((int(rng.integers(2, 9)), MLP_FEATURE_DIM)).astype(np.float32)
        for _ in range(12)
    ]
    results: dict = {}
    barrier = threading.Barrier(len(mats))

    def work(i):
        barrier.wait()
        results[i] = svc.score(mats[i])

    threads = [
        threading.Thread(target=work, args=(i,)) for i in range(len(mats))
    ]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(10.0)
        assert len(results) == len(mats)  # zero lost
        for i, m in enumerate(mats):
            np.testing.assert_array_equal(results[i], scorer.predict(m))
        assert svc.batches < len(mats)  # co-batching actually happened
        assert svc.rows_scored == sum(m.shape[0] for m in mats)
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# deadline-aware paths
# ---------------------------------------------------------------------------


def test_deadline_expiry_takes_immediate_path(clean_state):
    """An op whose deadline budget would expire in-queue is scored
    immediately on the single-call path instead of waiting out the
    batching window."""
    scorer = _numpy_scorer()
    svc = _service(window_s=5.0)  # a window nobody should wait for
    svc.install(MLPServed(scorer, kind="numpy"), version="t/v1")
    try:
        feats = np.random.default_rng(0).random((4, MLP_FEATURE_DIM)).astype(
            np.float32
        )
        t0 = time.perf_counter()
        scores = svc.score(feats, budget_s=0.010)  # < window + floor
        took = time.perf_counter() - t0
        np.testing.assert_array_equal(scores, scorer.predict(feats))
        assert took < 1.0  # did NOT wait the 5s window
        from dragonfly2_tpu.scheduler import metrics as M

        # the immediate path was the one taken
        assert any(
            child.value > 0
            for labels, child in M.SERVING_SUBMITTED_TOTAL._snapshot()
            if labels == ("immediate",)
        )
    finally:
        svc.stop()


def test_evaluator_passes_deadline_budget_through(clean_state):
    """The evaluator reads the ambient PR 5 deadline budget: inside a
    nearly-expired deadline_scope the decision still completes (via the
    immediate path), ranked by the model."""
    scorer = _numpy_scorer()
    svc = _service(window_s=5.0)
    svc.install(MLPServed(scorer, kind="numpy"), version="t/v1")
    parents, (child,), task = _swarm(candidates=5)
    try:
        ev = MLEvaluator(scorer, serving=svc)
        t0 = time.perf_counter()
        with resilience.deadline_scope(0.010):
            ranked = ev.evaluate_parents(parents, child, task.total_piece_count)
        assert time.perf_counter() - t0 < 1.0
        want = MLEvaluator(scorer).evaluate_parents(
            parents, child, task.total_piece_count
        )
        assert [p.id for p in ranked] == [p.id for p in want]
    finally:
        svc.stop()


def test_queue_overflow_degrades_to_immediate_path(clean_state):
    """A full submission queue scores inline (overflow path) instead of
    blocking the schedule op behind the backlog."""
    scorer = _numpy_scorer()
    svc = ScoringService(ServingConfig(window_s=0.5, queue_depth=1))
    # NOT started: the queue can only fill, never drain
    svc._thread = threading.Thread(target=lambda: None)  # "running" stub
    svc.install(MLPServed(scorer, kind="numpy"), version="t/v1")
    feats = np.zeros((2, MLP_FEATURE_DIM), np.float32)
    from dragonfly2_tpu.scheduler.serving import _Request

    svc._queue.put_nowait(_Request(feats, None))  # fill the queue
    scores = svc.score(feats, budget_s=None)
    np.testing.assert_array_equal(scores, scorer.predict(feats))


def test_abandoned_request_is_not_scored(clean_state):
    """A caller whose wait timed out has already re-scored its rows a
    rung down — the serving thread must SKIP its queued request at pack
    time, not burn a forward on results nobody reads."""
    release = threading.Event()
    entered = threading.Event()

    class Gated(MLPServed):
        def score(self, features, pairs):
            entered.set()
            assert release.wait(5.0)
            return super().score(features, pairs)

    scorer = _numpy_scorer()
    svc = _service(window_s=0.001, service_grace_s=2.0)
    svc.install(Gated(scorer, kind="numpy"), version="t/v1")
    got: dict = {}
    try:
        ok = threading.Thread(
            target=lambda: got.setdefault(
                "scores", svc.score(np.zeros((3, MLP_FEATURE_DIM), np.float32))
            )
        )
        ok.start()
        assert entered.wait(5.0)  # batch 1 holds the serving thread
        # this submission queues behind it; its DEADLINE BUDGET caps the
        # wait far below the service grace, so only it times out
        with pytest.raises(ServingError):
            svc.score(np.zeros((5, MLP_FEATURE_DIM), np.float32), budget_s=0.08)
        release.set()
        ok.join(5.0)
        assert got["scores"].shape == (3,)  # the live request completed
        time.sleep(0.2)  # give the loop a chance to (not) score the orphan
        assert svc.rows_scored == 3  # only the live request's rows
    finally:
        release.set()
        svc.stop()


def test_stop_releases_queued_waiters(clean_state):
    """A stopping service fails queued submissions out loudly (the
    caller falls back a rung) — it never strands a schedule op."""
    scorer = _numpy_scorer()

    class SlowServed(MLPServed):
        def score(self, features, pairs):
            time.sleep(0.2)
            return super().score(features, pairs)

    svc = _service(window_s=0.001)
    svc.install(SlowServed(scorer, kind="numpy"), version="t/v1")
    errors = []

    def work():
        try:
            svc.score(np.zeros((2, MLP_FEATURE_DIM), np.float32))
        except ServingError as e:
            errors.append(e)

    threads = [threading.Thread(target=work) for _ in range(6)]
    for t in threads:
        t.start()
    time.sleep(0.05)  # let the first batch start blocking
    svc.stop()
    for t in threads:
        t.join(5.0)
    assert not any(t.is_alive() for t in threads)  # nobody stranded


# ---------------------------------------------------------------------------
# hot swap
# ---------------------------------------------------------------------------


def test_hot_swap_mid_batch_no_dropped_no_mixed(clean_state):
    """model_refresher's contract: a swap while a batch is in flight
    (a) never drops a submission and (b) never mixes two models inside
    one batch — the in-flight batch finishes wholly on the OLD model,
    queued work scores wholly on the NEW one."""

    release = threading.Event()
    entered = threading.Event()

    class GatedModel:
        kind = "mlp"

        def __init__(self, value, gate=False):
            self.value = value
            self.gate = gate

        def supports(self, pairs):
            return True

        def score(self, features, pairs):
            if self.gate:
                entered.set()
                assert release.wait(5.0)
            return np.full(features.shape[0], self.value, np.float32)

    svc = _service(window_s=0.001)
    old = GatedModel(1.0, gate=True)
    svc.install(old, version="old/v1")
    results: dict = {}

    def work(i):
        results[i] = float(
            svc.score(np.zeros((2, MLP_FEATURE_DIM), np.float32))[0]
        )

    try:
        t1 = threading.Thread(target=work, args=(1,))
        t1.start()
        assert entered.wait(5.0)  # batch 1 is mid-score on the OLD model
        # swap while in flight, then submit more work
        svc.install(GatedModel(2.0), version="new/v1")
        t2 = threading.Thread(target=work, args=(2,))
        t2.start()
        time.sleep(0.05)
        release.set()
        t1.join(5.0)
        t2.join(5.0)
        # batch 1 scored wholly by the old model, batch 2 by the new —
        # nothing dropped, nothing mixed
        assert results == {1: 1.0, 2: 2.0}
    finally:
        release.set()
        svc.stop()


def test_swap_is_visible(clean_state):
    svc = _service()
    try:
        svc.install(MLPServed(_numpy_scorer(), kind="numpy"), version="a/v1")
        snap = svc.snapshot()
        assert snap["model_kind"] == "numpy" and snap["model_version"] == "a/v1"
        svc.clear()
        assert not svc.available()
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# degradation ladder under injected faults (fault point: the census
# requires scheduler.serving_score to be referenced by the test matrix)
# ---------------------------------------------------------------------------


def test_degraded_ladder_serving_to_mlp_to_base(clean_state):
    """Under injected faults at scheduler.serving_score the evaluator
    degrades serving → per-call MLP → Base with edge-triggered VISIBLE
    state (the resilience registry /healthz reads), and recovers the
    same way."""
    scorer = _numpy_scorer()
    svc = _service(window_s=0.002)
    svc.install(MLPServed(scorer, kind="numpy"), version="t/v1")
    parents, (child,), task = _swarm(candidates=5)
    total = task.total_piece_count
    comp = MLEvaluator.DEGRADED_COMPONENT
    try:
        ev = MLEvaluator(scorer, serving=svc)

        # rung 1: serving — healthy, not degraded
        ranked = ev.evaluate_parents(parents, child, total)
        assert [p.id for p in ranked] == [
            p.id
            for p in MLEvaluator(scorer).evaluate_parents(parents, child, total)
        ]
        assert comp not in resilience.degraded()

        # rung 2: serving faulted → per-call MLP, degraded visible
        faults.configure("scheduler.serving_score=error")
        ranked = ev.evaluate_parents(parents, child, total)
        assert len(ranked) == len(parents)  # still ML-ranked, same model
        assert "serving unavailable" in resilience.degraded()[comp]

        # rung 3: MLP broken too → Base, reason updates (not swallowed)
        class Broken:
            feature_dim = MLP_FEATURE_DIM

            def predict(self, feats):
                raise RuntimeError("mlp down")

        ev._model = Broken()
        ranked = ev.evaluate_parents(parents, child, total)
        assert len(ranked) == len(parents)
        assert "ml predict failed" in resilience.degraded()[comp]

        # recovery: faults cleared + model restored → serving again,
        # degraded clears (edge-triggered transition, like production)
        faults.clear()
        ev._model = scorer
        ev.evaluate_parents(parents, child, total)
        assert comp not in resilience.degraded()
        assert ev._rung == "serving"
    finally:
        svc.stop()


def test_serving_fault_injection_is_deterministic(clean_state):
    """The seeded window grammar drives the serving point like any
    other: error on exactly the second score call."""
    scorer = _numpy_scorer()
    svc = _service(window_s=0.001)
    svc.install(MLPServed(scorer, kind="numpy"), version="t/v1")
    feats = np.zeros((2, MLP_FEATURE_DIM), np.float32)
    try:
        faults.configure("scheduler.serving_score=error#1+1")
        assert svc.score(feats) is not None  # call 0 passes
        with pytest.raises(ServingError):
            svc.score(feats)  # call 1 injected
        assert svc.score(feats) is not None  # call 2 passes again
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# concurrency soak: zero lost submissions
# ---------------------------------------------------------------------------


def test_concurrency_soak_zero_lost_submissions(clean_state):
    """16 threads × 25 decisions race submissions through the service
    (with a mid-soak hot swap thrown in): every submission returns a
    full, correctly-sized ranking — zero lost, zero hangs."""
    scorer = _numpy_scorer()
    svc = _service(window_s=0.002)
    svc.install(MLPServed(scorer, kind="numpy"), version="t/v1")
    parents, children, task = _swarm(candidates=7, children=16)
    total = task.total_piece_count
    done = []
    lock = threading.Lock()

    def work(child):
        ev = MLEvaluator(scorer, serving=svc)
        ok = 0
        for _ in range(25):
            ranked = ev.evaluate_parents(parents, child, total)
            ok += int(len(ranked) == len(parents))
        with lock:
            done.append(ok)

    threads = [
        threading.Thread(target=work, args=(c,), daemon=True) for c in children
    ]
    try:
        for t in threads:
            t.start()
        time.sleep(0.05)
        svc.install(MLPServed(_numpy_scorer(seed=9), kind="numpy"), version="t/v2")
        for t in threads:
            t.join(30.0)
        assert not any(t.is_alive() for t in threads), "soak hang"
        assert sum(done) == 16 * 25  # zero lost submissions
        assert svc.rows_scored + 0 >= 0  # service stayed coherent
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# GNN rung
# ---------------------------------------------------------------------------


def _gnn_scorer(host_ids):
    """A tiny trained-shape GNN over a synthetic probe graph whose
    node set is ``host_ids``."""
    import jax

    from dragonfly2_tpu.models.gnn import init_graphsage
    from dragonfly2_tpu.schema.features import ProbeGraph
    from dragonfly2_tpu.trainer.serving import GNNScorer

    n = len(host_ids)
    rng = np.random.default_rng(0)
    graph = ProbeGraph(
        node_ids=list(host_ids),
        node_features=rng.random((n, 4)).astype(np.float32),
        neighbors=np.tile(np.arange(n, dtype=np.int32), (n, 1))[:, :2],
        neighbor_mask=np.ones((n, 2), np.float32),
        edge_src=np.zeros(1, np.int32),
        edge_dst=np.ones(1, np.int32),
        edge_rtt_log_ms=np.zeros(1, np.float32),
    )
    params = init_graphsage(jax.random.PRNGKey(0), 4, (8,), num_nodes=n)
    return GNNScorer(params, graph)


def test_gnn_served_ranks_by_predicted_rtt(clean_state):
    """The GNN rung: candidates rank by predicted child→parent RTT from
    the swap-time-resident embeddings, matching a direct scorer call."""
    parents, (child,), task = _swarm(candidates=4)
    ids = [child.host.id] + [p.host.id for p in parents]
    scorer = _gnn_scorer(ids)
    svc = _service(window_s=0.002)
    svc.install(GNNServed(scorer), version="gnn/v1")
    try:
        ev = MLEvaluator(serving=svc)
        ranked = ev.evaluate_parents(parents, child, task.total_piece_count)
        pred = scorer.predict_rtt_log_ms(
            [child.host.id] * len(parents), [p.host.id for p in parents]
        )
        want = [parents[int(i)].id for i in np.argsort(pred, kind="stable")]
        assert [p.id for p in ranked] == want
        assert ev._rung == "serving"
    finally:
        svc.stop()


def test_gnn_unknown_host_falls_back_per_request(clean_state):
    """A candidate set with a host the probe graph never embedded can't
    take the GNN rung — THAT decision scores through the per-call MLP
    while embeddable decisions keep the GNN, and the SERVICE-level
    ladder state doesn't flap (per-request degradation: a brand-new
    host must not flip the edge-triggered rung at decision rate)."""
    parents, (child,), task = _swarm(candidates=4)
    known = [child.host.id] + [p.host.id for p in parents[:2]]
    scorer = _gnn_scorer(known)  # parents 2,3 unknown to the graph
    svc = _service(window_s=0.002)
    svc.install(GNNServed(scorer), version="gnn/v1")
    mlp = _numpy_scorer()
    try:
        ev = MLEvaluator(mlp, serving=svc)
        # embeddable decision first: the GNN rung serves it
        ranked = ev.evaluate_parents(parents[:2], child, task.total_piece_count)
        assert len(ranked) == 2
        assert ev._rung == "serving"
        # unembeddable decision: ranked by the per-call MLP (matches a
        # serving-free evaluator bit-for-bit) with the rung UNCHANGED
        # and nothing registered degraded
        ranked = ev.evaluate_parents(parents, child, task.total_piece_count)
        want = MLEvaluator(mlp).evaluate_parents(
            parents, child, task.total_piece_count
        )
        assert [p.id for p in ranked] == [p.id for p in want]
        assert ev._rung == "serving"
        assert MLEvaluator.DEGRADED_COMPONENT not in resilience.degraded()
        # embeddable again: still the GNN rung, no flap recorded
        ev.evaluate_parents(parents[:2], child, task.total_piece_count)
        assert ev._rung == "serving"
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# bucket ladder holds: zero steady-state retraces
# ---------------------------------------------------------------------------


def test_mlp_scorer_zero_retraces_within_bucket(clean_state):
    """Varying candidate counts inside one bucket rung dispatch ONE
    compiled executable (the jit-witness acceptance, measured with
    ``jitwitness.compile_tap``)."""
    import jax

    from hack.dfanalyze import jitwitness
    from dragonfly2_tpu.models.mlp import init_mlp
    from dragonfly2_tpu.trainer.serving import MLPScorer

    scorer = MLPScorer(init_mlp(jax.random.PRNGKey(0), [MLP_FEATURE_DIM, 16, 1]))
    rng = np.random.default_rng(0)
    scorer.predict(rng.random((3, MLP_FEATURE_DIM)).astype(np.float32))  # warm
    with jitwitness.compile_tap() as tap:
        for n in (1, 2, 4, 5, 7, 8, 3, 6):
            scorer.predict(rng.random((n, MLP_FEATURE_DIM)).astype(np.float32))
    assert tap.count == 0, tap.names


def test_gru_scorer_buckets_history_batches(clean_state):
    """GRU ``predict_next_log_cost`` pads history batches up the same
    ladder: varying batch sizes inside a rung → zero recompiles, and a
    row predicts the same value whichever batch carried it."""
    import jax

    from hack.dfanalyze import jitwitness
    from dragonfly2_tpu.models.gru import init_gru
    from dragonfly2_tpu.schema.features import GRU_FEATURE_DIM
    from dragonfly2_tpu.trainer.serving import GRUScorer

    scorer = GRUScorer(init_gru(jax.random.PRNGKey(0), GRU_FEATURE_DIM, 8))
    hist = [[5.0, 6.0, 7.0], [30.0, 31.0], [2.0, 2.5, 2.25, 2.75]]
    one = float(scorer.predict_next_log_cost([hist[0]])[0])  # warm + value
    with jitwitness.compile_tap() as tap:
        for b in (1, 2, 3, 1, 3, 2):
            out = scorer.predict_next_log_cost(hist[:b])
            assert out.shape == (b,)
    assert tap.count == 0, tap.names
    batched = float(scorer.predict_next_log_cost(hist)[0])
    assert one == pytest.approx(batched, rel=1e-5)
