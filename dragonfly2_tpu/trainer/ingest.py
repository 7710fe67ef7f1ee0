"""Streaming ingestion: bytes-on-disk → decode → device feed → trained
params, in bounded host memory with decode/transfer/compute overlapped.

This is the hard part of the 1B-records-in-10-min north star (SURVEY §7:
~1.7M records/s sustained): the Train stream lands dataset files on the
trainer's disk (reference trainer/storage/storage.go:44-148, announcer
128 MiB-chunk upload announcer.go:39-41) in one of two payload formats,
sniffed from the file's magic bytes:

- **binary columnar blocks** (schema/wire.py, the negotiated production
  format): pair tensors precomputed scheduler-side — producer threads
  mmap block-aligned spans, verify checksums, and cast to the staging
  dtype; decode_wait collapses to I/O.
- **CSV** (the old-peer fallback): producer threads drive the fused C++
  CSV→tensor decoder (native/dfnative.cc) over newline-aligned spans
  (ctypes releases the GIL during native parsing).

Either way the consumer packs pair shards into fixed-size minibatches
and hands full superbatches to a two-stage device leg — a TRANSFER
thread issuing the H2D put and a STEP thread driving the jitted
(buffer-donating) train step — so decode, H2D, and device compute all
overlap: superbatch N+1's transfer is issued while step N executes,
and the hidden transfer wall is measured per run
(``StreamStats.h2d_overlap_s``). With a multi-chip ``mesh`` the put is
a per-device sharded upload (each chip receives only its row shard).
Multiple dataset files decode in parallel, one producer thread per
span.

Memory bound: the shard queue holds ≤ ``queue_depth`` chunks of decoded
pairs (~chunk_bytes of CSV each) plus a six-buffer packing pool
(6 × batch_size·steps_per_call superbatches: one packing, up to three
queued/in-transfer, up to two staged for the step, one awaiting
confirmation) and a capped eval holdout — independent of file size.
"""

# dfanalyze: device-hot — the dispatcher thread drives the jitted train
# step per superbatch; a fresh jit wrapper or stray host sync here costs
# a compile/transfer per dispatch

from __future__ import annotations

import os
import queue
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from dragonfly2_tpu.schema.features import MLP_FEATURE_DIM
from dragonfly2_tpu.schema import native, wire
from dragonfly2_tpu.trainer import metrics as M
from dragonfly2_tpu.utils import dflog, flight, profiling

logger = dflog.get("trainer.ingest")

# flight-recorder events: the per-superbatch h2d/step split (the live
# form of the StreamStats totals), the end-of-stream milestone with the
# full decode/transfer/compute attribution, and the stall verdicts the
# watchdogs reach — all under the owning fit's trace_id
EV_SUPERBATCH = flight.event_type("trainer.superbatch")
EV_STREAM_DONE = flight.event_type("trainer.stream_done")
EV_STALL = flight.event_type("trainer.stall")

# dfprof phase ledger: the StreamStats wall split as LIVE cross-service
# phases — buffer_wait's share of the trainer group on /debug/prof must
# agree with the per-fit StreamStats ratio (acceptance-tested)
PH_DECODE_WAIT = profiling.phase_type("trainer.decode_wait")
PH_BUFFER_WAIT = profiling.phase_type("trainer.buffer_wait")
PH_H2D = profiling.phase_type("trainer.h2d")
PH_STEP = profiling.phase_type("trainer.step")


@dataclass
class StreamStats:
    download_records: int = 0
    pairs: int = 0
    steps: int = 0
    eval_pairs: int = 0
    wall_s: float = 0.0
    truncated: bool = False  # stopped early by a time budget
    # wall-clock split of the packing thread (the pipeline's spine):
    # decode_wait_s — blocked on the decode queue (decoders too slow);
    # buffer_wait_s — blocked on the superbatch pool (device leg too
    # slow). The remainder is packing work itself. Together these say
    # WHICH stage bounded a run — recorded per run so a bench artifact
    # carries the bottleneck, not a guess.
    decode_wait_s: float = 0.0
    buffer_wait_s: float = 0.0
    # device-leg split, per superbatch, one field per pipeline stage
    # (each with a single writer thread): h2d_s — host→device transfer
    # wall, recorded on the TRANSFER stage; step_s — compiled-step
    # dispatch + the prior step's confirmation wait, recorded on the
    # STEP stage. The stages overlap (that's the point), so h2d_s no
    # longer serializes into the superbatch wall:
    # h2d_overlap_s — the portion of h2d_s spent while the step stage
    # was busy, i.e. transfer wall HIDDEN behind device compute
    # (h2d_overlap_s / h2d_s is h2d_overlap_pct below)
    h2d_s: float = 0.0
    step_s: float = 0.0
    h2d_overlap_s: float = 0.0
    # producer-side per-stage split, summed across the worker pool (so
    # with W workers the totals can exceed wall time): read_s — I/O +
    # block decode + checksum (binary) / fused read+parse (CSV, where
    # the native decoder doesn't separate them); cast_s — staging-dtype
    # conversion (binary; fused into read_s on CSV); enqueue_s — blocked
    # on the bounded shard queue (consumer too slow). When the e2e rate
    # disappoints, this names the NEXT bottleneck instead of leaving it
    # to archaeology.
    read_s: float = 0.0
    cast_s: float = 0.0
    enqueue_s: float = 0.0
    # per-dispatch training losses, most recent last (bounded to the
    # final _LOSS_KEEP dispatches so a million-step run stays O(1))
    losses: list = field(default_factory=list)
    metrics: dict = field(default_factory=dict)  # mse/mae on the holdout
    # the devices the first staged superbatch landed on: one per chip on
    # a dp mesh — a run whose batch did not divide the mesh shows one
    feed_devices: list = field(default_factory=list)

    @property
    def records_per_s(self) -> float:
        return self.download_records / self.wall_s if self.wall_s else 0.0

    @property
    def h2d_overlap_pct(self) -> float:
        """Percentage of the H2D wall hidden behind device steps — the
        overlapped pipeline's direct measure, shared by every artifact
        that reports it (soak_ingest, multichip_fit) so the key can
        never drift between them."""
        return (
            round(100.0 * self.h2d_overlap_s / self.h2d_s, 1) if self.h2d_s else 0.0
        )


_LOSS_KEEP = 1024


def default_workers(ncpu: int | None = None) -> int:
    """Producer pool size off host_cores: decode parallelism helps up to
    a point (the packing thread needs a core too), so leave one core
    free and cap the pool — beyond ~6 decoders the bounded queue, not
    decode, is the limit."""
    ncpu = ncpu or os.cpu_count() or 1
    return max(1, min(6, ncpu - 1))


def stream_shards(
    paths,
    passes: int = 1,
    max_records: int | None = None,
    queue_depth: int = 8,
    chunk_bytes: int = 8 * 1024 * 1024,
    offset: int = 0,
    end: int | None = None,
    workers: int = 1,
    half: bool = False,
    stats: "StreamStats | None" = None,
):
    """Generator of ``(feats, labels, total_rows)`` shards, decoded by
    background producer thread(s) through a bounded queue. ``total_rows``
    is the CUMULATIVE download-record count across everything yielded so
    far (per-worker deltas are summed internally), so the last yielded
    value is the whole stream's row count.

    Payload format is sniffed from the first file's magic bytes:

    - binary columnar blocks (schema/wire.py) — the zero-parse path:
      producers mmap block-aligned spans, verify checksums, and cast the
      precomputed pair tensors to the staging dtype. All residual decode
      work (CRC, f16 cast) runs IN the producer pool.
    - CSV — the fallback: producers drive the fused native parser
      (native/dfnative.cc) over newline-aligned byte spans.

    With ``workers > 1`` the dataset splits across that many producer
    threads (``workers=0`` → sized off host cores, ``default_workers``).
    Fewer files than workers is fine: files are split into aligned spans,
    so one big per-host dataset file decodes in parallel too. Shard
    order is then interleaved (fine for SGD). ``offset`` (a committed
    round boundary in the first file) is excluded on every pass, and
    ``end`` bounds the first file's read at the CURRENT round boundary —
    bytes a concurrent upload appends past it (which a failed stream's
    truncation may later remove) are never touched.
    ``stats``, when given, accumulates the producer-side read/cast/
    enqueue stage split. Abandoning the generator (consumer breaks
    early / errors) releases the producers: they observe the stop event
    instead of blocking forever on a full queue.
    """
    if isinstance(paths, (str, Path)):
        paths = [paths]
    paths = list(paths)
    if not paths:
        # an empty glob must be a clear error, not a ZeroDivisionError
        # from the span-splitting arithmetic below
        raise ValueError("stream_shards: no input files")
    if workers <= 0:
        workers = default_workers()
    binary = wire.is_block_file(paths[0])
    # resolve to (path, start, end) spans: applies the committed offset
    # once (so every pass skips consumed history) and gives each worker
    # a balanced byte share even when files < workers
    spans: list = []
    if binary:
        bounded = [
            (str(p), offset if j == 0 else 0, end if j == 0 else None)
            for j, p in enumerate(paths)
        ]
        spans = wire.split_block_spans(bounded)
    else:
        per_file = max(1, -(-workers // len(paths)))  # ceil
        for j, p in enumerate(paths):
            spans.extend(
                native.split_file_spans(
                    p,
                    per_file,
                    offset=offset if j == 0 else 0,
                    end=end if j == 0 else None,
                )
            )
    if not spans:
        return  # binary file with no complete blocks past the offset
    workers = max(1, min(workers, len(spans)))
    # queue items: per-worker rows are deltas, so interleaving is additive
    q: "queue.Queue" = queue.Queue(maxsize=queue_depth)
    stop = threading.Event()
    errors: list[BaseException] = []
    stats_lock = threading.Lock()

    def add_stage(stage: str, dt: float) -> None:
        if stats is None:
            return
        with stats_lock:
            if stage == "read":
                stats.read_s += dt
            elif stage == "cast":
                stats.cast_s += dt
            else:
                stats.enqueue_s += dt

    def produce(worker_spans):
        try:
            prev_rows = 0
            if binary:
                shard_iter = wire.stream_train_pairs(
                    worker_spans,
                    passes=passes,
                    max_records=max_records,
                    half=half,
                    stage_timer=add_stage,
                )
            else:
                # the native parser fuses file read + parse + (optional)
                # f16 emit, so its whole cost lands in read_s
                def csv_iter():
                    it = native.stream_pairs_file(
                        worker_spans,
                        passes=passes,
                        chunk_bytes=chunk_bytes,
                        max_records=max_records,
                        half=half,
                    )
                    while True:
                        t0 = time.perf_counter()
                        try:
                            item = next(it)
                        except StopIteration:
                            return
                        add_stage("read", time.perf_counter() - t0)
                        yield item

                shard_iter = csv_iter()
            for feats, labels, rows in shard_iter:
                item = (feats, labels, rows - prev_rows)
                prev_rows = rows
                t0 = time.perf_counter()
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.2)
                        break
                    except queue.Full:
                        continue
                add_stage("enqueue", time.perf_counter() - t0)
                if stop.is_set():
                    return
        except BaseException as e:  # surfaced to the consumer
            errors.append(e)
        finally:
            while not stop.is_set():
                try:
                    q.put(None, timeout=0.2)
                    break
                except queue.Full:
                    continue

    threads = []
    for w in range(workers):
        t = threading.Thread(
            target=produce,
            args=(spans[w::workers],),
            # <service>.<role> so dfprof/flight/Diagnose attribute by
            # role; the numeric suffix folds away in thread_role()
            name=f"trainer.ingest-decode-{w}",
            daemon=True,
        )
        t.start()
        threads.append(t)

    done = 0
    total_rows = 0
    try:
        while done < len(threads):
            item = q.get()
            if errors:
                # fail fast: one broken producer must abort the whole
                # stream now, not after the surviving workers finish a
                # multi-pass run whose result gets discarded anyway
                break
            if item is None:
                done += 1
                continue
            feats, labels, delta_rows = item
            if delta_rows:
                M.INGEST_RECORDS_TOTAL.inc(delta_rows)
            total_rows += delta_rows
            yield feats, labels, total_rows
            if max_records is not None and total_rows >= max_records:
                break
    finally:
        stop.set()
        # drain so producers blocked on put() can see the event and exit
        while True:
            try:
                q.get_nowait()
            except queue.Empty:
                break
        for t in threads:
            t.join(timeout=5.0)
    if errors:
        raise errors[0]


_step_cache: dict = {}


def _optimizer_and_loss(learning_rate: float, weight_decay: float, warmup_steps: int):
    """Shared by the single-step and k-step factories — the scan path's
    'identical math' guarantee rests on there being exactly one
    definition of the schedule, optimizer, and loss."""
    import jax.numpy as jnp
    import optax

    from dragonfly2_tpu.models import mlp as mlp_mod

    schedule = optax.linear_schedule(0.0, learning_rate, max(warmup_steps, 1))
    optimizer = optax.adamw(schedule, weight_decay=weight_decay)

    def loss_fn(p, xb, yb):
        pred = mlp_mod.score_parents(p, xb)
        return jnp.mean((pred - yb) ** 2)

    return optimizer, loss_fn


def _get_step(learning_rate: float, weight_decay: float, warmup_steps: int = 64):
    """(optimizer, jitted step) cached per optimizer config, so repeated
    fits (and bench warmup vs timed run) reuse one compiled executable
    per batch shape instead of retracing a fresh closure each call.

    The schedule is linear warmup → constant: the streaming horizon is
    unknown up front (records arrive as bytes decode), so the batch
    path's cosine decay has no defined endpoint here; warmup covers the
    same early-drift window (train.py warmup_fraction).

    Everything host-side the feed once did lives INSIDE the jit now —
    the staging-dtype upcast, the feature/label split — and the carried
    state (params, opt_state) is donated: XLA writes each step's updates
    into the SAME HBM buffers instead of allocating a fresh copy per
    dispatch, and the donated inputs are invalidated (re-reading them
    raises — the dp>1 test pins this). The xy superbatch is deliberately
    NOT donated: no output shares its [.., F+1] shape, so XLA could
    never alias it — donating it would only emit a "donated buffer not
    usable" warning per compile while the buffer frees at its last use
    regardless."""
    key = (learning_rate, weight_decay, warmup_steps)
    if key in _step_cache:
        return _step_cache[key]
    import jax
    import jax.numpy as jnp

    optimizer, loss_fn = _optimizer_and_loss(learning_rate, weight_decay, warmup_steps)
    import optax

    def step(params, opt_state, xy):
        # one fused [B, F+1] transfer per batch (features ‖ label column):
        # H2D calls have per-call cost, and the upcast from the reduced
        # transfer dtype is free device-side (XLA fuses it into the first
        # matmul's compute-dtype cast)
        xy = xy.astype(jnp.float32)
        xb, yb = xy[:, :MLP_FEATURE_DIM], xy[:, MLP_FEATURE_DIM]
        loss, grads = jax.value_and_grad(loss_fn)(params, xb, yb)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    step = jax.jit(step, donate_argnums=(0, 1))
    _step_cache[key] = (optimizer, step)
    return optimizer, step


def _get_scan_step(
    learning_rate: float, weight_decay: float, k: int, warmup_steps: int = 64
):
    """(optimizer, jitted k-step call): one device dispatch runs ``k``
    sequential optimizer steps via ``lax.scan`` over a [k, B, F+1]
    superbatch. Amortizes per-dispatch overhead (host→device RPC,
    transfer setup, executable launch) over k steps — the lever that
    matters when the device link has per-call latency (remote chips,
    small batches). Identical math to k calls of the single step."""
    key = (learning_rate, weight_decay, warmup_steps, "scan", k)
    if key in _step_cache:
        return _step_cache[key]
    import jax
    import jax.numpy as jnp
    import optax
    from jax import lax

    optimizer, loss_fn = _optimizer_and_loss(learning_rate, weight_decay, warmup_steps)

    def scan_step(params, opt_state, xy):
        xy = xy.astype(jnp.float32)

        def body(carry, slab):
            params, opt_state = carry
            xb, yb = slab[:, :MLP_FEATURE_DIM], slab[:, MLP_FEATURE_DIM]
            loss, grads = jax.value_and_grad(loss_fn)(params, xb, yb)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), opt_state), loss

        (params, opt_state), losses = lax.scan(body, (params, opt_state), xy)
        return params, opt_state, losses[-1]

    # same donation contract as _get_step: the carried state updates in
    # place; the [k, B, F+1] superbatch is shape-unaliasable (see above)
    scan_step = jax.jit(scan_step, donate_argnums=(0, 1))
    _step_cache[key] = (optimizer, scan_step)
    return optimizer, scan_step


def stream_train_mlp(
    paths,
    passes: int = 1,
    max_records: int | None = None,
    batch_size: int = 65_536,
    hidden_dims: tuple[int, ...] = (256, 256),
    learning_rate: float = 3e-3,
    weight_decay: float = 1e-4,
    queue_depth: int = 4,
    offset: int = 0,
    end: int | None = None,
    workers: int = 1,
    eval_every: int = 10,
    eval_max_batches: int = 16,
    params=None,
    mesh=None,
    transfer_dtype=np.float16,
    time_budget_s: float | None = None,
    steps_per_call: int = 1,
    stall_profile_dir: str = "",
) -> tuple[object, StreamStats]:
    """Fit the MLP parent scorer directly off disk bytes. Returns
    (params, StreamStats with holdout mse/mae in .metrics).

    Holdout: with ``eval_every`` > 0, pairs whose content hash lands in
    a 1/eval_every bucket are excluded from training on EVERY pass and
    scored at the end (collection capped at ``eval_max_batches`` worth of
    pairs to bound memory) — the streaming analogue of train_mlp's eval
    split. Content hashing keeps the holdout disjoint from the training
    set across multiple passes, which stream-position selection would
    not. Partial trailing
    batches are dropped when at least one full batch trained (static
    shapes keep one XLA executable hot); a dataset smaller than one batch
    trains a single ragged step so tiny hosts still fit. With ``mesh``,
    batches shard over its ``dp`` axis.

    ``transfer_dtype`` packs the host-side minibatch buffers (default
    float16): features are ratios/log-scales ≤ ~8, so halving H2D bytes
    costs ~5e-4 relative precision — upcast on device, where bf16 is the
    compute dtype anyway. Pass np.float32 for bit-exact feeds.

    ``time_budget_s`` bounds the wall clock: the stream stops consuming
    at the first shard boundary past the budget (``stats.truncated``
    set). The fit over what WAS consumed stays real — rates computed
    from ``stats.download_records`` remain honest. Benchmarks and
    interval-scheduled training rounds use this so a slow device link
    degrades to a shorter measurement, never an unbounded run.

    ``steps_per_call`` > 1 packs k minibatches into one [k, B, F+1]
    superbatch and runs k optimizer steps per device dispatch
    (``lax.scan`` device-side) — same math, 1/k the per-call overhead.
    Up to k·B trailing pairs are dropped at stream end (vs B with k=1),
    so keep k modest relative to the dataset.

    Stall watchdogs (utils/flight) ride the pipeline: a step-time or
    decode-wait observation regressing past ``DF_STALL_FACTOR`` × the
    trailing median dumps the flight rings to ``DF_DIAG_DIR`` while the
    stall is live, and — with ``stall_profile_dir`` set (the trainer
    passes its ``profile_dir``) — forces one ``jax.profiler`` capture
    of the stalled device leg.
    """
    import jax
    import jax.numpy as jnp

    from dragonfly2_tpu.models import mlp as mlp_mod

    optimizer, step = _get_step(learning_rate, weight_decay)
    k = max(1, int(steps_per_call))
    if k > 1:
        # same optimizer config (pytree-compatible opt_state); the scan
        # variant only changes how many steps one dispatch covers
        optimizer, scan_step = _get_scan_step(learning_rate, weight_decay, k)
    warm_bias = params is None  # fresh model: warm-start the output bias
    if params is None:
        params = mlp_mod.init_mlp(
            jax.random.PRNGKey(0), [MLP_FEATURE_DIM, *hidden_dims, 1]
        )
    if mesh is not None:
        from dragonfly2_tpu.parallel.sharding import replicate

        params = replicate(mesh, params)
    opt_state = None  # initialized at the first shard (after bias warm-start)

    if mesh is not None and batch_size % mesh.shape["dp"] == 0:
        from dragonfly2_tpu.parallel.sharding import shard_superbatch

        # rows shard over dp via per-device puts — each chip receives
        # ONLY its row shard (parallel.sharding.shard_superbatch; the
        # jit-witness mesh gate pins dp transfers per superbatch). The
        # superbatch's leading scan axis (k>1) stays unsharded — each
        # scan step is one dp-parallel batch.
        batch_dim = 0 if k == 1 else 1

        def put(buf):
            return shard_superbatch(mesh, buf, batch_dim=batch_dim)
    else:
        if mesh is not None:
            # a batch that doesn't divide the dp axis can't shard evenly;
            # feed replicated rather than fail the fit (the degenerate
            # twin of the ragged tiny-dataset rule below)
            logger.warning(
                "batch_size %d not divisible by dp=%d; feeding unsharded",
                batch_size,
                mesh.shape["dp"],
            )

        def put(buf):
            return jnp.asarray(buf)

    stats = StreamStats()
    # exemplar for the live pipeline histograms: the owning trace
    # (the fit span activated by Training._timed_fit) — None when no
    # sampled trace owns this run, which skips exemplar recording
    from dragonfly2_tpu.utils import tracing

    _owner = tracing.current_span()
    trace_exemplar = (
        {"trace_id": _owner.trace_id}
        if _owner is not None and _owner.sampled
        else None
    )
    # stall watchdogs: step-time regression (the device leg wedging —
    # the classic "TPU fit stalls and nobody sampled it") and decode
    # starvation. One shared profiler callback: the first stall forces
    # one jax.profiler capture via the trainer's profile_dir plumbing.
    _on_stall = (
        (lambda: flight.one_shot_profile(stall_profile_dir))
        if stall_profile_dir
        else None
    )
    step_watch = flight.StallWatchdog(
        "trainer.step", floor_s=0.25, on_stall=_on_stall, event=EV_STALL
    )
    decode_watch = flight.StallWatchdog(
        "trainer.decode_wait", floor_s=0.5, on_stall=_on_stall, event=EV_STALL
    )
    # Pipelined packing: fixed [batch_size·k, F+1] (features ‖ label)
    # buffers cycle through a free pool → packing → a TRANSFER stage →
    # a STEP stage, each stage its own thread: an H2D transfer paid on
    # the packing thread stalls the decode pipeline behind it. Splitting
    # transfer from step (ISSUE 15) removes the last serial bubble: the
    # H2D for superbatch N+1 is issued WHILE step N executes on device,
    # so transfer wall hides behind compute (measured per run as
    # stats.h2d_overlap_s). A buffer is reused only after the step
    # that read it has materialized its loss: the CPU
    # backend's asarray/device_put can be ZERO-COPY, so the
    # asynchronously dispatched step may still read the numpy buffer
    # after dispatch returns (a real TPU always copies on H2D, but
    # correctness can't depend on the backend's copy behavior) — and the
    # in-flight transfer extends the same rule: a staged device array is
    # consumed (donated) by exactly one step before its host buffer
    # recycles.
    rows_per_call = batch_size * k
    free_bufs: "queue.Queue" = queue.Queue()
    # Six buffers / filled depth 3 / staged depth 2: one packing + up to
    # three queued-or-in-transfer + up to two transferred-awaiting-step
    # + one awaiting step confirmation. In-flight superbatches let
    # decode run ahead through a slow patch instead of stalling behind
    # one delayed transfer. Memory cost:
    # 6 × k·B·(F+1) half-words (~126 MB at the bench shape) — bounded
    # and config-independent of file size.
    for _ in range(6):
        free_bufs.put(np.empty((rows_per_call, MLP_FEATURE_DIM + 1), transfer_dtype))
    filled_bufs: "queue.Queue" = queue.Queue(maxsize=3)
    staged_bufs: "queue.Queue" = queue.Queue(maxsize=2)
    disp_errors: list[BaseException] = []
    buf = free_bufs.get()
    fill = 0
    eval_cap_pairs = eval_max_batches * batch_size
    eval_x: list[np.ndarray] = []
    eval_y: list[np.ndarray] = []
    eval_collected = 0
    import collections

    loss_ring: "collections.deque" = collections.deque(maxlen=_LOSS_KEEP)
    t0 = time.perf_counter()

    # Two-stage device leg, one thread per stage, started together at
    # the first full superbatch:
    #
    #   transfer stage — consumes filled_bufs, issues the H2D put, hands
    #     (device array, host buffer, h2d wall) to staged_bufs. Because
    #     this runs on its own thread, superbatch N+1's transfer
    #     overlaps step N's execution; the overlap actually achieved is
    #     measured per put against the step stage's busy flag
    #     (stats.h2d_overlap_s).
    #   step stage — owns params/opt_state from its start to its join;
    #     dispatches the jitted (donating) step per staged superbatch
    #     and confirms the PREVIOUS step before recycling that step's
    #     host buffer (the reuse rule above).
    #
    # Each stage records ITS OWN wall (h2d on transfer, step on step) so
    # /debug/prof phases and the EV_SUPERBATCH event never double-count
    # one superbatch's wall; EV_SUPERBATCH is emitted once per
    # superbatch by the step stage, carrying the transfer stage's h2d
    # measurement forwarded through staged_bufs. stats.steps/loss_ring
    # writes are GIL-atomic with a single writer. On error either stage
    # keeps draining its input queue to the None sentinel (recycling
    # buffers) so the packing thread never deadlocks.
    state: dict = {}
    stage_threads: "list[threading.Thread]" = []
    # step-stage busy CLOCK (single writer: the step thread): "total"
    # accumulates completed busy intervals, "since" is nonzero while a
    # step is in flight. The transfer stage reads the clock at both
    # edges of each put and credits only the INTERSECTION of the put's
    # wall with step-busy time as overlap — an all-or-nothing edge
    # sample would credit a 600 ms transfer as fully hidden behind a
    # 5 ms step. Unlocked reads are safe: each field is written by one
    # thread and read whole under the GIL; a torn total/since pair can
    # only skew one put's credit, and the delta is clamped to [0, dt_h].
    step_busy = {"total": 0.0, "since": 0.0}

    def _step_busy_clock() -> float:
        t = step_busy["total"]
        since = step_busy["since"]
        if since:
            t += time.perf_counter() - since
        return t

    fn = step if k == 1 else scan_step

    def _transfer_loop():
        saw_sentinel = False
        # the owning fit span activates on this thread too (contextvars
        # don't cross threads), so the transfer-side histograms carry
        # the fit's trace_id exemplars
        span_cm = tracing.use_span(_owner)
        try:
            span_cm.__enter__()
            while True:
                b = filled_bufs.get()
                if b is None:
                    saw_sentinel = True
                    break
                if disp_errors:
                    # dead step stage: recycle so the packer unblocks,
                    # keep draining to the sentinel
                    free_bufs.put(b)
                    continue
                arg = b if k == 1 else b.reshape(k, batch_size, -1)
                busy0 = _step_busy_clock()
                t_h = time.perf_counter()
                dev = put(arg)
                dt_h = time.perf_counter() - t_h
                if not stats.feed_devices:
                    stats.feed_devices = sorted(str(d) for d in dev.devices())
                stats.h2d_s += dt_h
                # overlap = step-busy seconds elapsed DURING this put —
                # the transfer wall genuinely hidden behind device
                # compute, not an edge sample
                stats.h2d_overlap_s += min(
                    max(_step_busy_clock() - busy0, 0.0), dt_h
                )
                M.INGEST_H2D_SECONDS.observe(dt_h, exemplar=trace_exemplar)
                PH_H2D.observe(dt_h)
                staged_bufs.put((dev, b, dt_h))
        except BaseException as e:
            disp_errors.append(e)
            while not saw_sentinel:
                b = filled_bufs.get()
                if b is None:
                    break
                free_bufs.put(b)
        finally:
            # ALWAYS forward the shutdown downstream — the step stage's
            # only sentinel source is this stage
            staged_bufs.put(None)
            span_cm.__exit__(None, None, None)

    def _step_loop():
        prev_loss = prev_buf = None
        saw_sentinel = False
        span_cm = tracing.use_span(_owner)
        try:
            span_cm.__enter__()
            while True:
                item = staged_bufs.get()
                if item is None:
                    saw_sentinel = True
                    break
                dev, b, dt_h = item
                t_s = time.perf_counter()
                step_busy["since"] = t_s
                try:
                    state["params"], state["opt_state"], loss = fn(
                        state["params"], state["opt_state"], dev
                    )
                    loss_ring.append(loss)
                    stats.steps += k
                    if prev_loss is not None:
                        jax.block_until_ready(prev_loss)
                        free_bufs.put(prev_buf)
                    # step split = this dispatch + the prior step's
                    # confirmation wait: how long the device leg held
                    # the pipeline for one superbatch, as the host sees
                    # it — the h2d wall is NOT in here (it ran on the
                    # transfer stage, possibly concurrently)
                    dt_s = time.perf_counter() - t_s
                finally:
                    step_busy["total"] += time.perf_counter() - step_busy["since"]
                    step_busy["since"] = 0.0
                stats.step_s += dt_s
                M.INGEST_STEP_SECONDS.observe(dt_s, exemplar=trace_exemplar)
                PH_STEP.observe(dt_s)
                EV_SUPERBATCH(
                    h2d_s=round(dt_h, 6), step_s=round(dt_s, 6), steps=k
                )
                step_watch.observe(dt_s)
                prev_loss, prev_buf = loss, b
            if prev_loss is not None:
                jax.block_until_ready(prev_loss)
                free_bufs.put(prev_buf)
        except BaseException as e:
            disp_errors.append(e)
            if prev_buf is not None:
                free_bufs.put(prev_buf)
            # drain to the sentinel so the transfer stage never blocks
            # on staged_bufs — but only if the sentinel hasn't been
            # consumed yet: a failure in the post-sentinel tail (e.g.
            # the final block_until_ready raising on a dropped device
            # link) must not wait for a second sentinel that will never
            # come while the packer sits in join()
            while not saw_sentinel:
                item = staged_bufs.get()
                if item is None:
                    break
                free_bufs.put(item[1])
        finally:
            span_cm.__exit__(None, None, None)

    # native-side f16 emit skips the GIL-held f32→f16 numpy convert in
    # the packing loop below — the consumer thread is the bottleneck on
    # small hosts
    half = transfer_dtype == np.float16
    budget_end = None if time_budget_s is None else t0 + time_budget_s
    # the shutdown handshake lives in a finally: an exception out of the
    # packing loop (producer decode error re-raised by stream_shards, a
    # KeyboardInterrupt, …) must still send the sentinel and join, or the
    # dispatcher thread leaks blocked on filled_bufs.get() with its
    # buffers pinned — the long-lived trainer service calls this every
    # training round
    try:
        shard_iter = iter(
            stream_shards(
                paths,
                passes=passes,
                max_records=max_records,
                queue_depth=queue_depth,
                offset=offset,
                end=end,
                workers=workers,
                half=half,
                stats=stats,
            )
        )
        while True:
            w0 = time.perf_counter()
            try:
                feats, labels, rows = next(shard_iter)
            except StopIteration:
                break
            dt_w = time.perf_counter() - w0
            stats.decode_wait_s += dt_w
            M.INGEST_DECODE_WAIT_SECONDS.observe(dt_w, exemplar=trace_exemplar)
            PH_DECODE_WAIT.observe(dt_w)
            decode_watch.observe(dt_w)
            if budget_end is not None and time.perf_counter() > budget_end:
                stats.truncated = True
                break  # generator abandonment releases the producers
            if disp_errors:
                break
            stats.download_records = rows
            stats.pairs += feats.shape[0]
            if warm_bias and labels.size:
                # warm-start the output bias at (an estimate of) the label
                # mean so the regression head doesn't spend its first steps
                # drifting there (train_mlp does the same with the full-data
                # mean, train.py:137-138). dtype pinned to the init value's:
                # a weak-typed scalar fill would give the first step a
                # different jit signature than every later step — one extra
                # XLA compile mid-stream
                b = params["layers"][-1]["b"]
                params["layers"][-1]["b"] = jnp.full((1,), float(labels.mean()), dtype=b.dtype)
                warm_bias = False
            if opt_state is None:
                opt_state = optimizer.init(params)
            if eval_every > 0 and feats.shape[0]:
                # content-hash holdout: same pair → same bucket on every pass
                # (bucket assignment depends on the transfer dtype's bit
                # pattern; deterministic within a run config either way)
                u = np.uint16 if feats.dtype == np.float16 else np.uint32
                hv = feats.view(u).sum(axis=1, dtype=np.uint64)
                hv = (hv * np.uint64(2654435761) + labels.view(u)) & np.uint64(
                    0xFFFFFFFF
                )
                emask = (hv % np.uint64(eval_every)) == 0
                if emask.any():
                    if eval_collected < eval_cap_pairs:
                        # exclusion from training is the invariant that must
                        # hold on every pass; collection is cap-bounded (a
                        # later pass may re-collect a pair it already holds,
                        # which only reweights identical content in the
                        # metric, never leaks it into training)
                        ef = feats[emask]
                        eval_x.append(ef)
                        eval_y.append(labels[emask])
                        eval_collected += ef.shape[0]
                    feats = feats[~emask]
                    labels = labels[~emask]
            off = 0
            while off < feats.shape[0]:
                take = min(rows_per_call - fill, feats.shape[0] - off)
                buf[fill : fill + take, :MLP_FEATURE_DIM] = feats[off : off + take]
                buf[fill : fill + take, MLP_FEATURE_DIM] = labels[off : off + take]
                fill += take
                off += take
                if fill == rows_per_call:
                    # hand the full buffer to the device-leg stages and keep
                    # packing: transfer + step latency never stalls the
                    # decode pipeline
                    if not stage_threads:
                        state["params"], state["opt_state"] = params, opt_state
                        for target, role in (
                            (_transfer_loop, "transfer"),
                            (_step_loop, "step"),
                        ):
                            t = threading.Thread(
                                target=target,
                                name=f"trainer.ingest-{role}",
                                daemon=True,
                            )
                            t.start()
                            stage_threads.append(t)
                    w0 = time.perf_counter()
                    filled_bufs.put(buf)  # may block at queue depth
                    buf = free_bufs.get()
                    dt_b = time.perf_counter() - w0
                    stats.buffer_wait_s += dt_b
                    # the largest wall component finally has a live
                    # series + ledger phase next to its trio of siblings
                    M.INGEST_BUFFER_WAIT_SECONDS.observe(
                        dt_b, exemplar=trace_exemplar
                    )
                    PH_BUFFER_WAIT.observe(dt_b)
                    fill = 0
                    if disp_errors:
                        break
    finally:
        if stage_threads:
            # one sentinel into the head of the pipeline; the transfer
            # stage forwards it (its finally), so joining in order
            # drains both stages
            filled_bufs.put(None)
            for t in stage_threads:
                t.join()
            params, opt_state = state["params"], state["opt_state"]
    if disp_errors:
        raise disp_errors[0]
    stats.eval_pairs = eval_collected

    # Post-stream tail, in NAMED functions on purpose: the jit-witness
    # crosscheck fails any device feed attributed to stream_train_mlp's
    # own frame (the packing loop must never dispatch device work — it
    # would stall decode behind the device link), and these two run
    # once AFTER the pipeline drained, where a boundary conversion on
    # this thread is exactly right.
    def _ragged_tail(params, opt_state):
        # tiny dataset (< one batch): one ragged step so the fit is real.
        # Replicated (plain asarray), not dp-sharded — the ragged length
        # rarely divides the mesh axis, and one degenerate step doesn't
        # need data parallelism
        if opt_state is None:
            opt_state = optimizer.init(params)
        params, opt_state, pending_loss = step(
            params, opt_state, jnp.asarray(buf[:fill].copy())
        )
        loss_ring.append(pending_loss)
        stats.steps += 1
        return params, opt_state

    if stats.steps == 0 and fill > 0:
        params, opt_state = _ragged_tail(params, opt_state)
    stats.losses = [float(jax.block_until_ready(v)) for v in loss_ring]
    stats.wall_s = time.perf_counter() - t0
    # round milestone: the whole run's decode/transfer/compute split in
    # one ring entry — what bounded THIS fit, on permanent record
    EV_STREAM_DONE(
        records=stats.download_records,
        pairs=stats.pairs,
        steps=stats.steps,
        wall_s=round(stats.wall_s, 3),
        decode_wait_s=round(stats.decode_wait_s, 3),
        buffer_wait_s=round(stats.buffer_wait_s, 3),
        h2d_s=round(stats.h2d_s, 3),
        h2d_overlap_s=round(stats.h2d_overlap_s, 3),
        step_s=round(stats.step_s, 3),
        read_s=round(stats.read_s, 3),
        cast_s=round(stats.cast_s, 3),
        enqueue_s=round(stats.enqueue_s, 3),
        truncated=stats.truncated,
        stalls=step_watch.stalls + decode_watch.stalls,
    )

    def _eval_holdout():
        xe = np.concatenate(eval_x)
        ye = np.concatenate(eval_y)
        # the fit-end eval rides the shared memoized jit: a fresh
        # jax.jit wrapper per fit recompiled this same executable
        from dragonfly2_tpu.utils.jitcache import jit_once

        pred = np.asarray(jit_once(mlp_mod.score_parents)(params, jnp.asarray(xe)))
        err = pred - ye
        stats.metrics = {
            "mse": float(np.mean(err**2)),
            "mae": float(np.mean(np.abs(err))),
        }

    if eval_x:
        _eval_holdout()
    return params, stats
