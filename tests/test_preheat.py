"""Preheat plane: demand window folding, GRU demand forecasting, and
the planner's forecast→place sweep (ISSUE 17).

The jitwitness tests here are the DF_JIT_WITNESS acceptance for the
forecast path: the horizon forecast compiles once per (horizon, rung)
and steady state retraces zero times with exactly one H2D per call.
"""

import threading
import time

import numpy as np
import pytest

from dragonfly2_tpu.preheat.demand import DemandWindow
from dragonfly2_tpu.preheat.forecast import (
    DEMAND_FEATURE_DIM,
    DemandForecaster,
    demand_features,
)
from dragonfly2_tpu.preheat.planner import PreheatPlanner
from dragonfly2_tpu.scheduler.job import JobWorker
from dragonfly2_tpu.schema import records as R
from dragonfly2_tpu.trainer.serving import bucket_rows
from dragonfly2_tpu.utils import faults, tracing
from dragonfly2_tpu.utils.idgen import URLMeta, task_id_v1


@pytest.fixture
def clean_faults():
    faults.clear()
    yield
    faults.clear()


# ---------------------------------------------------------------------------
# demand window
# ---------------------------------------------------------------------------


def test_window_folds_counts_on_bucket_grid():
    w = DemandWindow(bucket_s=10.0, window_buckets=4)
    base = 1000.0  # bucket 100
    w.observe("t1", url="http://o/a", ts=base + 1)
    w.observe("t1", ts=base + 9)  # same bucket
    w.observe("t1", ts=base + 11, count=5.0)  # next bucket
    w.observe("t2", url="http://o/b", ts=base + 35)
    ids, urls, counts = w.series_batch(now=base + 35)
    assert ids == ["t1", "t2"]
    assert urls == ["http://o/a", "http://o/b"]
    # grid covers buckets [100..103], newest last
    assert counts.tolist() == [[2.0, 5.0, 0.0, 0.0], [0.0, 0.0, 0.0, 1.0]]
    assert counts.dtype == np.float32


def test_window_rolls_old_buckets_and_prunes_quiet_tasks():
    w = DemandWindow(bucket_s=1.0, window_buckets=3)
    w.observe("old", ts=100.0)
    w.observe("live", ts=100.0)
    w.observe("live", ts=104.0)  # rolls live's own window forward
    ids, _, counts = w.series_batch(now=104.0)
    # "old" went quiet for the whole window -> pruned entirely
    assert ids == ["live"]
    assert counts.tolist() == [[0.0, 0.0, 1.0]]
    assert w.task_count() == 1


def test_task_cap_drops_then_rearms_after_prune():
    w = DemandWindow(bucket_s=1.0, window_buckets=2, max_tasks=2)
    assert w.observe("a", ts=100.0)
    assert w.observe("b", ts=100.0)
    assert not w.observe("c", ts=100.0)  # cap refused a NEW series
    assert w.observe("a", ts=100.5)  # existing tasks always fold
    assert w.stats()["dropped"] == 1
    # once the resident series go quiet the prune frees cap slots
    assert w.observe("c", ts=110.0)
    assert w.task_count() == 1
    assert w.observed == 4


def test_observe_record_and_layer_sources():
    w = DemandWindow(bucket_s=10.0, window_buckets=4)
    rec = R.DownloadRecord(
        id="d1",
        task=R.TaskRecord(id="task-9", url="http://origin/blob"),
        created_at=int(2000.0 * 1e9),
    )
    w.observe_record(rec)
    w.observe_layer("sha256:abcd", "http://reg/v2/img/blobs/sha256:abcd", ts=2000.0)
    ids, urls, counts = w.series_batch(now=2000.0)
    assert ids == ["sha256:abcd", "task-9"]
    assert urls[1] == "http://origin/blob"
    assert counts[:, -1].tolist() == [1.0, 1.0]


class _LiveTask:
    """Resource-task double with the URLMeta fields observe_record folds."""

    url = "http://origin/blob?sig=x"
    tag = "ml"
    application = "batch"
    filters = ["sig"]
    url_range = ""
    digest = "sha256:beef"


def test_observe_record_captures_live_task_meta():
    """With the live resource task resolved, the series carries the
    demanded task's full URLMeta context — what the preheat job replays
    so the seed derives the demanded task id, not a planner-private one."""
    w = DemandWindow(bucket_s=10.0, window_buckets=4)
    rec = R.DownloadRecord(
        id="d1",
        task=R.TaskRecord(id="task-9", url="http://origin/blob"),
        created_at=int(2000.0 * 1e9),
    )
    w.observe_record(rec, task=_LiveTask())
    assert w.meta_for("task-9") == {
        "tag": "ml",
        "application": "batch",
        "filter": "sig",
        "digest": "sha256:beef",
    }
    _, urls, _ = w.series_batch(now=2000.0)
    assert urls == ["http://origin/blob?sig=x"]


def test_observe_layer_keys_on_task_id_when_known():
    """A layer pull whose P2P swarm identity is known folds under that
    task id (the id a demanding client joins), digest only as fallback."""
    w = DemandWindow(bucket_s=10.0, window_buckets=4)
    w.observe_layer(
        "sha256:abcd",
        "http://mirror/v2/img/blobs/sha256:abcd",
        ts=3000.0,
        task_id="a" * 64,
        meta={"tag": "registry"},
    )
    ids, _, _ = w.series_batch(now=3000.0)
    assert ids == ["a" * 64]
    assert w.meta_for("a" * 64) == {"tag": "registry"}
    assert w.meta_for("unknown") == {}


# ---------------------------------------------------------------------------
# forecaster
# ---------------------------------------------------------------------------


def _ramping_window(n_hot=4, n_cold=4, t=12, seed=0):
    """[N, T] counts: hot rows ramp upward, cold rows stay sparse."""
    rng = np.random.default_rng(seed)
    hot = np.arange(1.0, t + 1.0)[None, :] * (1.0 + rng.random((n_hot, 1)))
    cold = (rng.random((n_cold, t)) < 0.15).astype(np.float64) * 0.5
    return np.concatenate([hot, cold]).astype(np.float32)


def test_forecaster_cold_serves_zeros():
    f = DemandForecaster(window_buckets=8, use_device=False)
    assert not f.ready
    out = f.forecast_demand(np.ones((3, 8), np.float32))
    assert out.tolist() == [0.0, 0.0, 0.0]
    assert f.forecast_demand(np.zeros((0, 8), np.float32)).shape == (0,)


def test_fit_ranks_hot_above_cold_and_backends_agree():
    counts = _ramping_window(t=12)
    f = DemandForecaster(
        window_buckets=12, horizon=3, epochs=6, min_examples=4, use_device=False
    )
    metrics = f.fit(counts)
    assert metrics is not None and f.ready and f.fits == 1
    scores = f.forecast_demand(counts)
    assert scores.shape == (8,)
    # every ramping row must outrank every sparse row
    assert scores[:4].min() > scores[4:].max()
    # numpy twin is the same math on the same padded shapes
    np.testing.assert_allclose(scores, f.forecast_demand_np(counts), atol=1e-3)


def test_fit_returns_none_on_quiet_window():
    f = DemandForecaster(window_buckets=8, min_examples=4, use_device=False)
    assert f.fit(np.zeros((4, 8), np.float32)) is None
    assert not f.ready


def test_demand_features_fixed_history_rung():
    f = DemandForecaster(window_buckets=12, horizon=3, use_device=False)
    # history axis is the rung covering window + horizon, fixed per
    # instance, so the autoregressive writes never outgrow the buffer
    assert f.hist_rows == bucket_rows(12 + 3) == 16
    feats = demand_features(np.ones((2, 12), np.float32), f.hist_rows)
    assert feats.shape == (2, 16, DEMAND_FEATURE_DIM)
    assert feats[0, 11, 0] == pytest.approx(np.log1p(1.0))
    assert feats[0, 12:, 0].tolist() == [0.0] * 4  # horizon slack stays zero


def _device_forecaster(window_buckets=12, horizon=3):
    import jax

    from dragonfly2_tpu.models.gru import init_gru

    f = DemandForecaster(window_buckets=window_buckets, horizon=horizon, use_device=True)
    f.set_params(init_gru(jax.random.PRNGKey(0), DEMAND_FEATURE_DIM, f.hidden_dim))
    return f


def test_forecast_path_compiles_once_zero_steady_retraces():
    """DF_JIT_WITNESS crosscheck: one compile per (horizon, rung), then
    varying batch sizes inside the rung retrace nothing and upload
    exactly one tensor (the features) per forecast call."""
    from hack.dfanalyze import jitwitness

    f = _device_forecaster()
    counts = _ramping_window(t=12)
    f.forecast_demand(counts[:3])  # warm: compile + pin params
    with jitwitness.compile_tap() as ct, jitwitness.transfer_tap() as tt:
        for n in (1, 3, 8, 5, 2, 8):
            out = f.forecast_demand(counts[:n])
            assert out.shape == (n,)
    assert ct.count == 0, ct.names
    assert tt.h2d == 6  # the per-sweep feature upload, nothing else


def test_forecaster_raises_when_jax_imports_but_finds_no_backend(monkeypatch):
    """The numpy twin is for hosts without jax installed; a backend that
    cannot enumerate its devices is an error, not a reason to forecast
    on the host quietly."""
    import jax

    def no_backend():
        raise RuntimeError("Unable to initialize backend")

    monkeypatch.setattr(jax, "devices", no_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        DemandForecaster(window_buckets=8)
    assert DemandForecaster(window_buckets=8, use_device=False).backend == "numpy"


def test_device_and_numpy_twin_parity_on_device_backend():
    f = _device_forecaster()
    counts = _ramping_window(t=12, seed=3)
    dev = f.forecast_demand(counts)
    twin = f.forecast_demand_np(counts)
    np.testing.assert_allclose(dev, twin, atol=1e-3)


def test_gru_scorer_zero_retrace_under_forecast_horizon_shapes():
    """GRUScorer.predict_next_log_cost rides the same rung-padded
    history discipline the forecaster leans on: history lengths spanning
    a window and its horizon extensions (the shapes the autoregressive
    loop produces) stay inside one compiled executable."""
    import jax

    from hack.dfanalyze import jitwitness
    from dragonfly2_tpu.models.gru import init_gru
    from dragonfly2_tpu.schema.features import GRU_FEATURE_DIM
    from dragonfly2_tpu.trainer.serving import GRUScorer

    scorer = GRUScorer(init_gru(jax.random.PRNGKey(0), GRU_FEATURE_DIM, 8))
    window, horizon = 12, 3
    hists = [
        [float(i + 1) for i in range(length)]
        for length in range(window, window + horizon + 1)
    ]
    scorer.predict_next_log_cost([hists[0]])  # warm the rung
    with jitwitness.compile_tap() as tap:
        for h in hists:  # horizon-extended lengths, one at a time
            assert scorer.predict_next_log_cost([h]).shape == (1,)
        assert scorer.predict_next_log_cost(hists).shape == (len(hists),)
    assert tap.count == 0, tap.names


# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------


class _SumForecaster:
    """Deterministic forecaster double: score = window mass."""

    min_examples = 10**9  # planner never tries to fit this one
    ready = True

    def forecast_demand(self, series):
        return series.sum(axis=1)

    def stats(self):
        return {"backend": "stub"}


class _SeedStub:
    def __init__(self):
        self.held = set()
        self.inflight = set()
        self.refuse = False
        self.triggered = []
        self.triggered_ids = []
        self.trigger_kwargs = []

    def seed_hosts(self):
        return ["seed-a"]

    def is_inflight(self, task_id):
        return task_id in self.inflight

    def trigger(self, task_id, url, **kw):
        if self.refuse:
            return False
        self.triggered.append(url)
        self.triggered_ids.append(task_id)
        self.trigger_kwargs.append(kw)
        return True


class _TaskStub:
    def __init__(self, held):
        self._held = held

    def load_seed_peer(self):
        return object() if self._held else None


class _ResourceStub:
    def __init__(self):
        self.held = set()
        self.task_manager = self

    def load(self, task_id):
        return _TaskStub(task_id in self.held)


def _planner(demand, seed=None, resource=None, **kw):
    seed = seed if seed is not None else _SeedStub()
    worker = JobWorker(None, resource or _ResourceStub(), seed_client=seed)
    kw.setdefault("min_score", 0.5)
    kw.setdefault("interval_s", 3600)
    return (
        PreheatPlanner(
            demand,
            _SumForecaster(),
            resource=resource,
            job_worker=worker,
            seed_client=seed,
            **kw,
        ),
        seed,
    )


def _feed(demand, tasks, now, count=3.0):
    for i, tid in enumerate(tasks):
        demand.observe(tid, url=f"http://o/{tid}", ts=now, count=count + i)


def test_sweep_plans_triggers_and_links_one_trace(clean_faults):
    demand = DemandWindow(bucket_s=1.0, window_buckets=4)
    now = 500.0
    _feed(demand, ["t1", "t2"], now)
    planner, seed = _planner(demand, budget_per_sweep=4)
    out = planner.sweep_once(now=now)
    assert out["outcome"] == "planned"
    assert out["forecast"] == 2
    assert out["planned"] == 2 and out["triggered"] == 2
    assert sorted(seed.triggered) == ["http://o/t1", "http://o/t2"]
    # ONE trace: the sweep span parents forecast/plan/job, and the
    # JobWorker's inline seed-trigger span joins the same trace
    sweeps = [s for s in tracing.get("preheat").finished if s.name == "preheat.sweep"]
    assert sweeps, "sweep span must be sampled and finished"
    tid = sweeps[-1].trace_id
    names = {
        s.name
        for svc in ("preheat", "scheduler")
        for s in tracing.get(svc).finished
        if s.trace_id == tid
    }
    assert {
        "preheat.sweep",
        "preheat.forecast",
        "preheat.plan",
        "preheat.job",
        "preheat.seed_trigger",
    } <= names


def test_budget_caps_a_sweep(clean_faults):
    demand = DemandWindow(bucket_s=1.0, window_buckets=4)
    now = 600.0
    _feed(demand, [f"t{i}" for i in range(6)], now)
    planner, seed = _planner(demand, budget_per_sweep=2)
    out = planner.sweep_once(now=now)
    assert out["planned"] == 2 and len(seed.triggered) == 2
    assert out["skipped"] >= 1  # the budget skip is accounted, not silent
    # budget picks the forecast-hottest tasks, not arrival order
    assert sorted(seed.triggered) == ["http://o/t4", "http://o/t5"]


def test_skip_reasons_held_inflight_cooldown(clean_faults):
    demand = DemandWindow(bucket_s=1.0, window_buckets=4)
    now = 700.0
    _feed(demand, ["held", "inflight", "fresh"], now)
    resource = _ResourceStub()
    # held/inflight state lives under the id the preheat actually
    # triggers (derived from the series' url + meta, as the seed daemon
    # derives it) — the demand key alone would never match
    resource.held.add(task_id_v1("http://o/held"))
    seed = _SeedStub()
    seed.inflight.add(task_id_v1("http://o/inflight"))
    planner, seed = _planner(demand, seed=seed, resource=resource, budget_per_sweep=4)
    out = planner.sweep_once(now=now)
    assert out["planned"] == 1 and out["skipped"] == 2
    assert seed.triggered == ["http://o/fresh"]
    # second sweep: "fresh" now cools down; nothing new to plan
    out2 = planner.sweep_once(now=now + 1)
    assert out2["outcome"] == "empty"
    assert planner.stats()["cooling"] == 1
    # past the cooldown the same task is plannable again (fresh demand:
    # the window itself rolled past by then)
    later = now + planner.cooldown_s + 1
    _feed(demand, ["fresh"], later)
    out3 = planner.sweep_once(now=later)
    assert out3["planned"] == 1


def test_failed_job_releases_cooldown_for_retry(clean_faults):
    demand = DemandWindow(bucket_s=1.0, window_buckets=4)
    now = 800.0
    _feed(demand, ["t1"], now)
    planner, seed = _planner(demand, budget_per_sweep=4)
    seed.refuse = True  # every trigger refused -> job outcome "failed"
    out = planner.sweep_once(now=now)
    assert out["outcome"] == "planned" and out["triggered"] == 0
    # a refused job must not burn the cooldown: the next sweep retries
    assert planner.stats()["cooling"] == 0
    seed.refuse = False
    out2 = planner.sweep_once(now=now + 1)
    assert out2["triggered"] == 1 and seed.triggered == ["http://o/t1"]


def test_preheat_triggers_under_demanded_task_identity(clean_faults):
    """THE identity contract (the bug this release fixes): a series
    observed under a real task id with its URLMeta context must be
    preheated under exactly that id and meta — a planner-stamped
    tag/application would seed a swarm no demanded client joins."""
    demand = DemandWindow(bucket_s=1.0, window_buckets=4)
    now = 1000.0
    url = "http://origin/model.bin"
    meta = {"tag": "ml", "application": "batch"}
    demanded_id = task_id_v1(url, URLMeta(tag="ml", application="batch"))
    demand.observe(demanded_id, url=url, ts=now, count=5.0, meta=meta)
    planner, seed = _planner(demand, budget_per_sweep=4)
    out = planner.sweep_once(now=now)
    assert out["triggered"] == 1
    assert seed.triggered_ids == [demanded_id]
    kw = seed.trigger_kwargs[0]
    assert kw["tag"] == "ml" and kw["application"] == "batch"


def test_layer_series_without_task_id_derives_client_identity(clean_faults):
    """A digest-keyed layer series (no swarm id resolved at observe
    time) is preheated under the id a demanding client would derive
    from the URL + captured meta — never under the digest string or a
    planner-private identity."""
    demand = DemandWindow(bucket_s=1.0, window_buckets=4)
    now = 1100.0
    url = "http://mirror/v2/img/blobs/sha256:abcd"
    demand.observe_layer("sha256:abcd", url, ts=now, meta={"tag": "registry"})
    # make it forecast-hot enough to plan
    demand.observe("sha256:abcd", ts=now, count=4.0)
    planner, seed = _planner(demand, budget_per_sweep=4)
    out = planner.sweep_once(now=now)
    assert out["triggered"] == 1
    assert seed.triggered_ids == [task_id_v1(url, URLMeta(tag="registry"))]
    assert seed.trigger_kwargs[0]["tag"] == "registry"
    # dedupe consults the DERIVED id: with that id inflight, the next
    # sweep skips instead of re-preheating past the cooldown forever
    seed.inflight.add(task_id_v1(url, URLMeta(tag="registry")))
    later = now + planner.cooldown_s + 1
    demand.observe("sha256:abcd", url=url, ts=later, count=4.0)
    out2 = planner.sweep_once(now=later)
    assert out2["planned"] == 0 and out2["skipped"] == 1


def test_plan_fault_lands_in_error_outcome(clean_faults):
    """An armed preheat.plan fault must surface as the sweep's error
    outcome — never escape to kill the planner loop."""
    demand = DemandWindow(bucket_s=1.0, window_buckets=4)
    now = 900.0
    _feed(demand, ["t1"], now)
    planner, seed = _planner(demand)
    faults.configure("preheat.plan=error")
    out = planner.sweep_once(now=now)
    assert out["outcome"] == "error"
    assert seed.triggered == []
    faults.clear()
    assert planner.sweep_once(now=now)["outcome"] == "planned"


def test_planner_stats_shape(clean_faults):
    demand = DemandWindow(bucket_s=1.0, window_buckets=4)
    planner, _ = _planner(demand)
    planner.sweep_once(now=950.0)
    s = planner.stats()
    assert s["sweeps"] == 1 and s["jobs"] == 0
    assert s["demand"]["tasks"] == 0
    assert s["forecaster"] == {"backend": "stub"}


def test_planner_start_stop_runs_in_background(clean_faults):
    demand = DemandWindow(bucket_s=1.0, window_buckets=4)
    demand.observe("t1", url="http://o/t1", count=5.0)
    planner, seed = _planner(demand, interval_s=0.02)
    planner.start()
    deadline = time.time() + 5.0
    while planner.sweeps == 0 and time.time() < deadline:
        time.sleep(0.01)
    planner.stop()
    assert planner.sweeps >= 1
    assert seed.triggered == ["http://o/t1"]


def test_refit_moves_off_the_sweep_thread_single_flight(clean_faults):
    """ISSUE 19 satellite: periodic refits run on a single-flight
    worker thread — a sweep that finds one in flight skips instead of
    queueing, and the sweep itself never blocks on the fit."""
    demand = DemandWindow(bucket_s=1.0, window_buckets=4)
    planner, _ = _planner(demand)

    started = threading.Event()
    release = threading.Event()
    fits = []

    class _SlowFit:
        def fit(self, series):
            fits.append(series)
            started.set()
            assert release.wait(5.0)

    planner.forecaster = _SlowFit()
    planner._refit_async([[1.0]])
    assert started.wait(5.0)
    # second refit while the first is in flight: skipped, not queued
    planner._refit_async([[2.0]])
    assert planner.refits_async == 1
    assert planner.refits_skipped == 1
    release.set()
    # once the worker drains, the next boundary refits again
    deadline = time.time() + 5.0
    while planner._refit_flight.locked() and time.time() < deadline:
        time.sleep(0.01)
    started.clear()
    planner._refit_async([[3.0]])
    assert started.wait(5.0)  # release already set: the fit completes
    assert planner.refits_async == 2
    assert len(fits) == 2  # the skipped series never reached the fit


def test_sweep_refit_boundary_is_asynchronous(clean_faults):
    """At a refit boundary (sweeps % refit_every == 0) with a ready
    forecaster, the sweep returns while the fit is still running."""
    demand = DemandWindow(bucket_s=1.0, window_buckets=4)
    now = 990.0
    _feed(demand, ["t1", "t2"], now)
    planner, _ = _planner(demand, refit_every=1)

    release = threading.Event()

    class _ReadySlow:
        min_examples = 1
        ready = True

        def forecast_demand(self, series):
            return series.sum(axis=1)

        def fit(self, series):
            assert release.wait(5.0)

        def stats(self):
            return {"backend": "stub"}

    planner.forecaster = _ReadySlow()
    out = planner.sweep_once(now=now)  # must not block on the held fit
    assert out["outcome"] == "planned"
    assert planner.refits_async == 1
    release.set()
    s = planner.stats()
    assert s["refits_async"] == 1
