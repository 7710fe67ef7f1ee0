"""Scheduler Prometheus series (reference scheduler/metrics/metrics.go:
46-454 — the operationally-load-bearing subset: announce/register/
schedule traffic, piece/peer outcomes, record sink, probe sync)."""

from dragonfly2_tpu.utils.metrics import default_registry as _r

ANNOUNCE_PEER_TOTAL = _r.counter(
    "scheduler_announce_peer_total", "AnnouncePeer stream events", ("event",)
)
REGISTER_PEER_TOTAL = _r.counter(
    "scheduler_register_peer_total", "Peer registrations", ("size_scope",)
)
DOWNLOAD_PEER_FINISHED_TOTAL = _r.counter(
    "scheduler_download_peer_finished_total", "Peers that finished downloading"
)
DOWNLOAD_PEER_FAILURE_TOTAL = _r.counter(
    "scheduler_download_peer_failure_total", "Peers that failed downloading"
)
DOWNLOAD_PIECE_FINISHED_TOTAL = _r.counter(
    "scheduler_download_piece_finished_total", "Piece results ingested", ("traffic_type",)
)
SCHEDULE_DURATION = _r.histogram(
    "scheduler_schedule_duration_seconds", "Candidate-parent scheduling latency"
)
SCHEDULE_TOTAL = _r.counter(
    "scheduler_schedule_total", "Scheduling decisions", ("outcome",)
)
DOWNLOAD_RECORD_TOTAL = _r.counter(
    "scheduler_download_record_total", "Training Download records written"
)
SYNC_PROBES_TOTAL = _r.counter(
    "scheduler_sync_probes_total", "SyncProbes stream messages", ("kind",)
)
HOST_TOTAL = _r.counter(
    "scheduler_announce_host_total", "AnnounceHost calls"
)
LEAVE_HOST_TOTAL = _r.counter("scheduler_leave_host_total", "LeaveHost calls")
TRAIN_UPLOAD_TOTAL = _r.counter(
    "scheduler_train_upload_total", "Dataset uploads to the trainer", ("outcome",)
)
TRAFFIC_BYTES_TOTAL = _r.counter(
    "scheduler_traffic_bytes_total", "Piece bytes by traffic type", ("traffic_type",)
)
PEER_GAUGE = _r.gauge("scheduler_peers", "Live peers in the resource model", ("state",))
TASK_GAUGE = _r.gauge("scheduler_tasks", "Live tasks in the resource model")
HOST_GAUGE = _r.gauge("scheduler_hosts", "Announced hosts", ("type",))

# -- round-5 breadth to reference coverage (metrics.go:46-454) -----------
ANNOUNCE_PEER_FAILURE_TOTAL = _r.counter(
    "scheduler_announce_peer_failure_total", "AnnouncePeer stream failures"
)
REGISTER_PEER_FAILURE_TOTAL = _r.counter(
    "scheduler_register_peer_failure_total", "Failed peer registrations"
)
STAT_PEER_TOTAL = _r.counter("scheduler_stat_peer_total", "StatPeer calls")
STAT_PEER_FAILURE_TOTAL = _r.counter(
    "scheduler_stat_peer_failure_total", "StatPeer calls that failed"
)
LEAVE_PEER_TOTAL = _r.counter("scheduler_leave_peer_total", "LeavePeer/LeaveTask calls")
LEAVE_PEER_FAILURE_TOTAL = _r.counter(
    "scheduler_leave_peer_failure_total", "LeavePeer/LeaveTask calls that failed"
)
STAT_TASK_TOTAL = _r.counter("scheduler_stat_task_total", "StatTask calls")
STAT_TASK_FAILURE_TOTAL = _r.counter(
    "scheduler_stat_task_failure_total", "StatTask calls that failed"
)
DOWNLOAD_PEER_STARTED_TOTAL = _r.counter(
    "scheduler_download_peer_started_total", "Peers that started downloading"
)
DOWNLOAD_PEER_BACK_TO_SOURCE_STARTED_TOTAL = _r.counter(
    "scheduler_download_peer_back_to_source_started_total",
    "Peers that started downloading back-to-source",
)
DOWNLOAD_PIECE_FAILURE_TOTAL = _r.counter(
    "scheduler_download_piece_failure_total", "Failed piece results ingested"
)
ANNOUNCE_HOST_FAILURE_TOTAL = _r.counter(
    "scheduler_announce_host_failure_total", "AnnounceHost calls that failed"
)
LEAVE_HOST_FAILURE_TOTAL = _r.counter(
    "scheduler_leave_host_failure_total", "LeaveHost calls that failed"
)
SYNC_PROBES_FAILURE_TOTAL = _r.counter(
    "scheduler_sync_probes_failure_total", "SyncProbes stream failures"
)
# per-host traffic (reference metrics.go:244-251: the HostTraffic series
# keyed by traffic type + host). Cardinality note mirrors the reference:
# one series per (type, host) pair — bounded by cluster size.
HOST_TRAFFIC_BYTES_TOTAL = _r.counter(
    "scheduler_host_traffic_bytes_total",
    "Piece bytes by traffic type and host",
    ("traffic_type", "host_id", "host_ip"),
)
# whole-download duration by task size class (reference
# DownloadPeerDuration with CalculateSizeLevel buckets)
DOWNLOAD_PEER_DURATION_MS = _r.histogram(
    "scheduler_download_peer_duration_milliseconds",
    "Whole-download duration per finished peer",
    buckets=(100, 500, 1000, 5000, 10000, 30000, 60000, 300000),
)
CONCURRENT_SCHEDULE_GAUGE = _r.gauge(
    "scheduler_concurrent_schedule", "Scheduling passes in flight"
)

# -- batched scoring service (scheduler/serving.py, docs/serving.md) --------
SERVING_SUBMITTED_TOTAL = _r.counter(
    "scheduler_serving_submitted_total",
    "Candidate-matrix score submissions by path",
    ("path",),  # batched | immediate | overflow
)
SERVING_BATCHES_TOTAL = _r.counter(
    "scheduler_serving_batches_total", "Micro-batches scored by the serving thread"
)
SERVING_BATCH_OCCUPANCY = _r.histogram(
    "scheduler_serving_batch_occupancy",
    "Candidate feature rows packed per scored micro-batch",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128),
)
SERVING_ERRORS_TOTAL = _r.counter(
    "scheduler_serving_errors_total", "Serving-path score failures (per request)"
)
SERVING_QUEUE_DEPTH = _r.gauge(
    "scheduler_serving_queue_depth",
    "Submission queue depth observed at batch pack time",
)
SERVING_SWAPS_TOTAL = _r.counter(
    "scheduler_serving_swaps_total", "Served-model hot swaps", ("kind",)
)
SERVING_FALLBACK_TOTAL = _r.counter(
    "scheduler_serving_fallback_total",
    "Evaluator degradation-ladder rung drops",
    ("to",),  # mlp | base
)
DECISION_RUNG_TOTAL = _r.counter(
    "scheduler_decision_rung_total",
    "Decisions ranked, by the ladder rung that ranked each one",
    ("rung",),  # serving | mlp | base
)
GNN_INSTALL_TOTAL = _r.counter(
    "scheduler_gnn_install_total",
    "GraphSAGE swaps into the batched serving slot, by how each ended",
    ("result",),  # ok | failed | skipped (no probe-graph source, or a graph too small to embed)
)
GNN_REEMBED_TOTAL = _r.counter(
    "scheduler_gnn_reembed_total",
    "The loaded GraphSAGE version embedded again on a live graph that had moved, by how each ended",
    ("result",),  # ok | failed | skipped (a graph too small to embed)
)
GNN_UNKNOWN_HOST_TOTAL = _r.counter(
    "scheduler_gnn_unknown_host_total",
    "Decisions the served GraphSAGE could not rank because a pair named a host outside its graph",
)
GNN_ROWS_TOTAL = _r.counter(
    "scheduler_gnn_rows_total",
    "Learned node rows of installed and re-embedded GraphSAGE versions, by what became of each on the live graph",
    ("row",),  # placed (by host id) | default (a host the version never saw) | dropped (a host that left)
)

# -- wave scheduling (scheduler/wave.py, docs/serving.md "wave
# scheduling"): W decisions × C candidates packed into one scoring
# dispatch. A wave batch's rows are scheduler_serving_batch_occupancy's (a
# wave is a batch), its unpack the scheduler.score_unpack phase's ---------
WAVE_DECISIONS_TOTAL = _r.counter(
    "scheduler_wave_decisions_total",
    "Scheduling decisions submitted via wave packing, by path",
    ("path",),  # batched | immediate | overflow
)
# -- predictive preheat plane (dragonfly2_tpu/preheat/, docs/preheat.md):
# demand folding, forecast sweeps, planned tasks and the jobs they ride --
PREHEAT_SWEEPS_TOTAL = _r.counter(
    "scheduler_preheat_sweeps_total",
    "Planner sweeps by outcome",
    ("outcome",),  # planned | empty | error
)
PREHEAT_JOBS_TOTAL = _r.counter(
    "scheduler_preheat_jobs_total",
    "Preheat jobs submitted by the planner, by outcome",
    ("outcome",),  # succeeded | failed
)
PREHEAT_TASKS_PLANNED_TOTAL = _r.counter(
    "scheduler_preheat_tasks_planned_total",
    "Forecast-hot tasks picked for seed placement",
)
PREHEAT_FORECASTS_TOTAL = _r.counter(
    "scheduler_preheat_forecasts_total",
    "Per-task demand forecasts served by the GRU forecaster",
)
PREHEAT_SKIPPED_TOTAL = _r.counter(
    "scheduler_preheat_skipped_total",
    "Forecast-hot tasks the planner declined",
    ("reason",),  # held | inflight | cooldown | budget | no_url
)
PREHEAT_DEMAND_TASKS = _r.gauge(
    "scheduler_preheat_demand_tasks", "Task series resident in the demand window"
)
PREHEAT_DEMAND_OBSERVED_TOTAL = _r.counter(
    "scheduler_preheat_demand_observed_total",
    "Demand observations folded into the window, by source",
    ("source",),  # record | layer
)
PREHEAT_DEMAND_DROPPED_TOTAL = _r.counter(
    "scheduler_preheat_demand_dropped_total",
    "Demand arrivals refused at the window's task cap",
)
PREHEAT_SWEEP_SECONDS = _r.histogram(
    "scheduler_preheat_sweep_seconds",
    "Whole planner sweep wall (forecast + plan + job submit)",
    buckets=(1e-3, 5e-3, 0.02, 0.1, 0.5, 2.0, 10.0),
)

VERSION_GAUGE = _r.gauge(
    "scheduler_version", "Build info (value is always 1)", ("version",)
)


def set_version_info() -> None:
    from dragonfly2_tpu.version import __version__

    VERSION_GAUGE.labels(__version__).set(1)


# label values seen on previous refreshes — a group that disappears must
# be zeroed, not left at its last value (phantom peers in dashboards)
_seen_peer_states: set = set()
_seen_host_types: set = set()


def refresh_resource_gauges(resource) -> None:
    """Update cluster-state gauges from the live resource model (the
    reference exports these via promauto collectors; here a periodic
    refresh keeps the scrape path allocation-free)."""
    by_state: dict = {}
    for p in resource.peer_manager.all():
        by_state[p.fsm.current] = by_state.get(p.fsm.current, 0) + 1
    _seen_peer_states.update(by_state)
    for state in _seen_peer_states:
        PEER_GAUGE.labels(state).set(by_state.get(state, 0))
    TASK_GAUGE.set(len(resource.task_manager.all()))
    by_type: dict = {}
    for h in resource.host_manager.all():
        by_type[h.type.value] = by_type.get(h.type.value, 0) + 1
    _seen_host_types.update(by_type)
    for t in _seen_host_types:
        HOST_GAUGE.labels(t).set(by_type.get(t, 0))
