"""Trainer Prometheus series (reference trainer/metrics/metrics.go:38-52
plus fit-duration/ingest visibility the TPU trainer adds)."""

from types import SimpleNamespace

import jax

from dragonfly2_tpu.utils import profiling
from dragonfly2_tpu.utils.metrics import default_registry as _r

TRAIN_TOTAL = _r.counter("trainer_train_total", "Train RPC streams accepted")
TRAIN_FAILURE_TOTAL = _r.counter(
    "trainer_train_failure_total", "Train RPC streams that failed"
)
FIT_TOTAL = _r.counter("trainer_fit_total", "Model fits", ("model", "outcome"))
FIT_DURATION = _r.histogram(
    "trainer_fit_duration_seconds", "Fit wall time", ("model",),
    buckets=(0.1, 0.5, 1, 5, 15, 60, 300, 1200, 3600, float("inf")),
)
# What a fit leg's block reader did with the upload, per round: blocks
# decoded (header parsed, payload CRC-checked, the leg's columns built)
# and blocks hopped over by the 16-byte preamble alone. The GRU leg
# trains on the newest gru_max_sequences and reads the upload from its
# end, so on a large upload it hops nearly every block; the resident MLP
# leg decodes every one. The streamed MLP fit counts none here.
FIT_BLOCKS_TOTAL = _r.counter(
    "trainer_fit_blocks_total",
    "Upload blocks a fit leg's reader decoded or hopped over",
    ("model", "fate"),
)
INGEST_RECORDS_TOTAL = _r.counter(
    "trainer_ingest_records_total", "Download records decoded for training"
)
# Live pipeline splits of the streaming train loop (trainer/ingest.py),
# observed per shard / per superbatch WHILE a fit runs — the same
# decode/transfer/compute attribution StreamStats totals per run, but
# scrapeable mid-fit. Exemplars carry the owning fit's trace_id
# (OpenMetrics exposition), so a slow bucket links to its trace.
_INGEST_BUCKETS = (
    0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, float("inf"),
)
INGEST_DECODE_WAIT_SECONDS = _r.histogram(
    "trainer_ingest_decode_wait_seconds",
    "Packing thread blocked on the decode queue, per shard",
    buckets=_INGEST_BUCKETS,
)
INGEST_H2D_SECONDS = _r.histogram(
    "trainer_ingest_h2d_seconds",
    "Host-to-device superbatch transfer dispatch",
    buckets=_INGEST_BUCKETS,
)
INGEST_STEP_SECONDS = _r.histogram(
    "trainer_ingest_step_seconds",
    "Compiled train-step dispatch + prior-step confirmation, per superbatch",
    buckets=_INGEST_BUCKETS,
)
# the packing thread blocked on the superbatch pool, live per
# superbatch like its decode_wait/h2d/step siblings, exemplars carrying
# the fit's trace_id the same way
INGEST_BUFFER_WAIT_SECONDS = _r.histogram(
    "trainer_ingest_buffer_wait_seconds",
    "Packing thread blocked on the superbatch buffer pool, per superbatch",
    buckets=_INGEST_BUCKETS,
)
DATASET_BYTES_TOTAL = _r.counter(
    "trainer_dataset_bytes_total", "Dataset bytes received on Train streams", ("kind",)
)
# Executables this process asked of the XLA backend: compiled, or loaded
# from the persistent compile cache (a short one). Steady state on a
# warm streamed fit is ZERO; the resident fits rebuild their epoch
# function per fit and ask for it again every round. Both are fed by
# the listener below, in every process that imports the trainer, with
# each compile's real duration; a moving count mid-fit is the retrace
# storm, visible on /metrics and /debug/prof.
JIT_RECOMPILES_TOTAL = _r.counter(
    "trainer_jit_recompiles_total",
    "Executables asked of the XLA backend (compiled or loaded from the compile cache)",
)
PH_JIT_COMPILE = profiling.phase_type("trainer.jit_compile")

# A round: Training.train() from its first line to its outcome, entered
# on the caller's thread. Open = a round is running; which of its legs
# still are, each leg's ``fit`` phase below says.
PH_ROUND = profiling.phase_type("trainer.round")

# Rounds of several schedulers on one trainer (trainer/training.py
# RoundAdmission). round_wait: entered by a round's thread from its
# arrival until it is admitted, before ``trainer.round`` and on no leg's
# thread, so it lies outside the round and in no leg's split: count =
# rounds, total = seconds waited (a round that finds the trainer empty
# leaves it in microseconds; one that finds another running walks its
# upload's headers in it, its reckoning, before it is admitted or
# waits). merge: the average of a cadence's MLP versions and its
# create_model, on the thread of the cadence's last round, after that
# round's ``trainer.round`` has closed.
PH_ROUND_WAIT = profiling.phase_type("trainer.round_wait")
PH_MERGE = profiling.phase_type("trainer.merge")
ROUND_ADMISSION_TOTAL = _r.counter(
    "trainer_round_admission_total",
    "Rounds admitted to the chip, by whether they waited for another round's return",
    ("result",),
)
ROUNDS_RUNNING = _r.gauge("trainer_rounds_running", "Rounds admitted and not yet returned")
ROUNDS_RESERVED_BYTES = _r.gauge(
    "trainer_rounds_reserved_bytes",
    "Device bytes the running rounds were reckoned to hold at their fullest",
)

# The resident fits' phases, one vocabulary for the three legs
# (trainer/training.py wraps fit, load and register, trainer/train.py the
# rest). load/split/table_put/holdout/register are entered once a fit, the
# next four once an epoch, never twice for one piece of work: total / count
# reads as seconds a fit or seconds an epoch. A stage entered inside
# another (INNER_STAGES: feed_slice in gather, load_span in load) is
# the ledger's and the trace's; the leg's split holds the outer one
# (utils/profiling.py split). A phase times the call as
# the code makes it; epoch_dispatch waits for each of its slices
# (trainer/train.py), so the device's time lies in it. A fit's order is
# drawn ahead of it on threads of its own (trainer/train.py FitOrder):
# split and gather time what the leg's thread waits for it, order what
# the drawing threads took, and 1 - (split + gather's wait) / order is
# the share of the draws that the leg did not sit out.
FIT_STAGES = (
    # the leg's whole fit as the round runs it (Training._timed_fit), around
    # every stage below and the leg's own bookkeeping, and outside its split
    "fit",
    "load",  # bytes on disk -> host arrays
    "split",  # the wait for the permutation that sets the holdout apart
    "gather",  # the wait for the epoch's permutation, then its row numbers index[perm], each slice of them handed to the device as it is composed
    "feed",  # the wait for what of the epoch's row numbers had not landed on the device when the gather ended
    "epoch_dispatch",  # the epoch call until it returns: trace, cache look-up, every slice run
    "epoch_wait",  # the read of the epoch's mean loss (on the host once the last slice is in)
    "holdout",  # holdout gather, forward and read-back
    "register",  # params to the host and create_model
    "table_put",  # the fit's table on the chip (trainer/train.py _put_table), once a fit, a slice at a time
    # the bounded slices (trainer/train.py), each entered where it is run: one
    # put and the wait for the put before it (a slice of the fit's table, 64
    # MiB, inside table_put; a slice of an epoch's row numbers, inside gather),
    # one dispatch and its wait (inside epoch_dispatch). The ledger's entries
    # say the slices engaged, total / count how long one holds its leg
    "feed_slice",
    "epoch_slice",
    # a span of the upload's blocks checked against their CRCs and copied into
    # the arrays the fit is handed (schema/wire.py TrainPairsWalk.assemble),
    # inside load_assemble: entered once a span by the worker that runs it, on
    # the worker's thread, so the count says the spans engaged, the total is
    # seconds summed over the workers, and total / load_assemble is how many
    # of them were busy
    "load_span",
    # a span's blocks checked by one call of the native library that holds no
    # interpreter lock (schema/native.py df_crc32_blocks), inside load_span:
    # entered once a span by the same worker around the call, so the count
    # says the one-call check engaged (a span a count; 0 where the library did
    # not load and zlib.crc32 ran once a block), the total is seconds
    # checking, and load_span - load_check is the copy and the first touch of
    # its pages
    "load_check",
    # a permutation drawn (the holdout's, an epoch's): entered on the drawing
    # thread, once a permutation, 1 + epochs a fit; its seconds are drawn beside
    # the leg's, so the ledger and a trace's host plane hold them and no leg's
    # split does
    "order",
)
# The two stretches of the resident MLP leg's load (schema/wire.py), inside
# load, once a fit each, and no other leg's: the walk over the upload's
# headers and the assembly, which waits for its workers' spans; load =
# load_walk + load_assemble but for the order's start. And inside the walk,
# load_walk_native: the headers read by the native library, which holds no
# interpreter lock (schema/native.py df_walk_blocks), entered around its
# calls, so the count says the library's walk engaged (1 a fit; 0 where the
# library did not load and the interpreter parsed every header), the total
# is the library's seconds, and load_walk - load_walk_native is what the
# interpreter still does: the table's arithmetic and, from a block the
# library was not sure of, the rest of the walk
MLP_LOAD_STAGES = ("load_walk", "load_assemble", "load_walk_native")
# The GRU leg's own, inside its load: the tail of the upload read through
# the native library, which holds no interpreter lock (schema/wire.py
# read_gru_tail: the range hopped by df_hop_blocks, the kept blocks checked
# by df_crc32_blocks, their three columns laid by df_gather). The library's
# calls lie either side of the kept headers' parse, which is the
# interpreter's, so the read sums their seconds and tells the phase once
# (``observe``, not ``with``: the ledger's, on no trace): the count says the
# library read the tail (1 a fit; 0 where it did not load and the
# interpreter hopped and decoded block by block), the total is the library's
# seconds, and load - load_native is what the interpreter still does: the
# file's mapping, a json.loads a kept block, the views
GRU_LOAD_STAGES = ("load_native",)
# entered inside another stage, on the leg's thread or on a worker's: in no split
INNER_STAGES = frozenset({*MLP_LOAD_STAGES, *GRU_LOAD_STAGES, "feed_slice", "epoch_slice", "load_span", "load_check"})


# What a resident fit puts on the chip: its table (every column, once a
# fit: 4.4 GB for a week's pairs) and the row numbers of each epoch and
# of the holdout (4 B a row: 0.2 GB an epoch). Before the table, every
# epoch put its gathered columns again (3.96 GB an epoch).
FIT_PUT_BYTES_TOTAL = _r.counter(
    "trainer_fit_put_bytes_total", "Bytes a resident fit leg put on the device", ("leg",)
)


class _LegPhases(SimpleNamespace):
    """A leg's phases by stage (``vars()`` is those and nothing else),
    and beside them ``put_bytes``, the leg's child of the counter above."""

    __slots__ = ("put_bytes",)


def _fit_phases(leg: str, stages: tuple = FIT_STAGES) -> _LegPhases:
    phases = _LegPhases(
        **{stage: profiling.phase_type(f"trainer.{leg}_{stage}", inner=stage in INNER_STAGES) for stage in stages}
    )
    phases.put_bytes = FIT_PUT_BYTES_TOTAL.labels(leg)
    return phases


LEG_PHASES = {
    "mlp": _fit_phases("mlp", FIT_STAGES + MLP_LOAD_STAGES),
    "gnn": _fit_phases("gnn"),
    "gru": _fit_phases("gru", FIT_STAGES + GRU_LOAD_STAGES),
}
PH_MLP, PH_GNN, PH_GRU = LEG_PHASES.values()
# unix timestamp of the last SUCCESSFUL fit per model: the telemetry
# plane's fit-freshness source (freshness = now - value; 0 = never) —
# a gauge, so the manager can compute staleness without rate math
LAST_FIT_TIMESTAMP = _r.gauge(
    "trainer_last_fit_timestamp_seconds",
    "Unix time of the last successful fit",
    ("model",),
)


def _on_compile(event: str, seconds: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        JIT_RECOMPILES_TOTAL.inc()
        # jax calls its listeners on the thread that compiled, so a fit
        # leg's open split (Training._timed_fit) books its own compiles
        PH_JIT_COMPILE.book(seconds)


# once a process: a module body runs once, and every path to a fit
# imports this module first (the benchmark builds the server and never
# serves, so TrainerServer.serve() would be too late)
jax.monitoring.register_event_duration_secs_listener(_on_compile)
