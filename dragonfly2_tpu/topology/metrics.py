"""Topology-engine Prometheus series (lands in the shared
default_registry next to the scheduler's, so one /metrics endpoint
carries both)."""

from dragonfly2_tpu.utils.metrics import default_registry as _r

EDGE_GAUGE = _r.gauge(
    "topology_edges", "Edges resident in the device adjacency"
)
HOST_GAUGE = _r.gauge(
    "topology_hosts",
    "Slots of the device adjacency by state: held by a live host, or free for the next to join",
    ("state",),  # live | free_slots
)
CAPACITY_GAUGE = _r.gauge(
    "topology_capacity",
    "Extent the device arrays are padded to (what the kernels are compiled for)",
    ("kind",),  # nodes | edges
)
DELTA_QUEUE_GAUGE = _r.gauge(
    "topology_delta_queue_depth", "Probe deltas waiting for the next flush"
)
DELTA_DROPPED_TOTAL = _r.counter(
    "topology_delta_dropped_total", "Deltas dropped by the queue cap"
)
FLUSH_TOTAL = _r.counter(
    "topology_flush_total", "Delta flushes applied to the device adjacency"
)
QUERY_TOTAL = _r.counter(
    "topology_query_total", "est_rtt queries", ("source",)
)
INFERENCE_CACHE_HIT_RATE = _r.gauge(
    "topology_inference_cache_hit_rate",
    "Fraction of est_rtt queries served from the inference cache",
)
STALE_PURGED_TOTAL = _r.counter(
    "topology_stale_edges_purged_total", "Edges dropped by staleness decay"
)
