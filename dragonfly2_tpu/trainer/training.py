"""Training orchestration — the component the reference shipped as a TODO
stub (reference trainer/training/training.go:33-98).

``Training.train(ip, hostname)`` runs the flow the reference's comments
promise: load the uploading scheduler's dataset from storage → preprocess
into tensors → fit (MLP on download records, GraphSAGE on the probe
graph, concurrently like the reference's errgroup) → upload both models
with their evaluation metrics to the manager (CreateModel) → clear the
consumed dataset.

A failed fit must never poison serving: models upload as inactive and the
manager's activation step gates rollout (reference
manager/models/model.go:20-26 state machine).
"""

from __future__ import annotations

import collections
import concurrent.futures
import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Protocol

import numpy as np

from dragonfly2_tpu.schema import native, wire
from dragonfly2_tpu.schema.columnar import records_to_columns
from dragonfly2_tpu.schema.features import build_probe_graph, extract_pair_features
from dragonfly2_tpu.trainer.federation import FittedVersion, holdout_sample, merge_versions
from dragonfly2_tpu.trainer.storage import TrainerStorage
from dragonfly2_tpu.trainer.train import (
    FitConfig,
    FitOrder,
    GNNFitConfig,
    release_in_pieces,
    resident_fit_bytes,
    train_gnn,
    train_mlp,
)
from dragonfly2_tpu.trainer import metrics as M
from dragonfly2_tpu.utils import dflog, flight, profiling
from dragonfly2_tpu.utils.idgen import (
    federated_model_id_v1,
    gnn_model_id_v1,
    host_id_v2,
    mlp_model_id_v1,
)

logger = dflog.get("trainer")

# round milestones in the flight ring: one event per fit leg (with its
# outcome) and one per training round — the trainer's black box
EV_FIT = flight.event_type("trainer.fit")
EV_ROUND = flight.event_type("trainer.round")


class BelowMinRecords(ValueError):
    """The dataset (or era) holds too few records / no trainable pairs
    to fit — the condition the mixed-era fall-through is allowed to
    treat as 'drop the sub-minimum tail'. Any OTHER error (corrupt
    data, decode failure) must propagate and never silently discard an
    untrained dataset."""


class ManagerClient(Protocol):
    """The slice of the manager API the trainer needs (CreateModel,
    reference manager_server_v1.go:800-899)."""

    def create_model(
        self,
        model_id: str,
        model_type: str,  # "mlp" | "gnn"
        ip: str,
        hostname: str,
        params: Any,  # parameter pytree (serialized by the client)
        evaluation: dict[str, float],
    ) -> None: ...


@dataclass
class TrainingConfig:
    mlp: FitConfig = field(default_factory=FitConfig)
    gnn: GNNFitConfig = field(default_factory=GNNFitConfig)
    gnn_max_degree: int = 16
    min_download_records: int = 1
    min_topology_records: int = 1
    clear_after_train: bool = True
    # incremental rounds: keep dataset files, commit consumed byte offsets
    # after each successful fit and decode only newly appended uploads
    # next round (implies clear_after_train=False; needs native decode)
    incremental: bool = False
    # streaming ingestion (trainer.ingest): decode/train overlapped in
    # bounded memory once the dataset file crosses the threshold — the
    # 1B-record path. Below it, the batch decode (one pass, in-memory
    # shuffle across epochs) fits fine and trains with the full FitConfig
    # schedule.
    streaming: bool = True
    streaming_threshold_bytes: int = 64 * 1024 * 1024
    streaming_passes: int = 2
    # decode producer pool; 0 = sized off host cores (ingest.default_workers)
    streaming_workers: int = 0
    # optimizer steps folded into one device dispatch (lax.scan
    # superbatch) — raise on high-latency device links
    streaming_steps_per_call: int = 1
    # wall bound for one streamed fit; None = unbounded
    streaming_time_budget_s: "float | None" = None
    # third model family: GRU next-piece-cost predictor over per-parent
    # piece-cost sequences (Download records carry up to 10 piece costs
    # per parent, reference scheduler/storage/types.go:143-176). ON by
    # default since round 5: the third model family — and the ml
    # evaluator's model-based bad-node detection that consumes it — must
    # train under production defaults, not behind a knob (round-4
    # verdict). gru_error still never gates .ok, so a host with too few
    # sequences just skips the leg.
    gru: bool = True
    gru_min_sequences: int = 8
    # RAM bound for the GRU leg: sequences kept per fit (~70 B each);
    # past this, more history stops improving the next-cost model
    gru_max_sequences: int = 1_000_000
    gru_config: FitConfig = field(
        default_factory=lambda: FitConfig(hidden_dims=(32,), batch_size=128, epochs=10)
    )
    # data-parallel fit mesh (ISSUE 15): with no explicit mesh, build a
    # pure ``dp`` mesh over every addressable device when more than one
    # chip is present — record shards train data-parallel over ICI, the
    # paper's north-star sentence, as the production DEFAULT rather than
    # a dormant parameter. Single-device hosts (and False) keep the
    # plain feed. CI's forced-host-platform 8-device image exercises the
    # dp>1 path (sharded puts, replicated params, donation, scan+dp
    # layout) through this same switch every round.
    auto_mesh: bool = True
    # jax.profiler trace dir ("" = off): one trace per round, around
    # the three fits, under <profile_dir>/round; view with TensorBoard
    profile_dir: str = ""
    # elastic restart: per-(model, host) orbax snapshots under this dir
    # (trainer/checkpoint.py) — a mid-fit crash resumes from the last
    # epoch snapshot on the next round instead of retraining from zero;
    # "" disables (the reference's behavior)
    checkpoint_dir: str = ""


@dataclass
class LegSplit:
    """One fit leg's own account of one round, measured on the leg's
    thread (``profiling.split``), so that two hosts' rounds running at
    once do not mix as they do in the process-wide ledger."""

    wall_s: float = 0.0
    phase_s: dict[str, float] = field(default_factory=dict)  # seconds by phase
    phase_n: dict[str, int] = field(default_factory=dict)  # entries by phase
    # executables the leg's thread asked of the backend, and the seconds
    # they took: spent INSIDE the phases above (the epoch's dispatch, the
    # holdout's forward), not beside them
    compiles: int = 0
    compile_s: float = 0.0
    # upload blocks the leg's reader decoded (header, CRC, the columns it
    # trains on) and blocks it hopped over by the preamble alone. The
    # streamed MLP fit decodes on its pool's threads and counts none
    # here: its account is ``stream``
    blocks_decoded: int = 0
    blocks_hopped: int = 0
    # the streamed MLP fit's own split, when the leg streamed
    stream: Any = None  # ingest.StreamStats | None

    @property
    def self_s(self) -> float:
        """What no phase covers: the leg's own bookkeeping."""
        return round(self.wall_s - sum(self.phase_s.values()), 6)

    def fields(self) -> dict:
        """As the ``trainer.fit`` event and the ``fit`` span carry it."""
        return {
            "wall_s": self.wall_s,
            "self_s": self.self_s,
            "phase_s": self.phase_s,
            "phase_n": self.phase_n,
            "compiles": self.compiles,
            "compile_s": self.compile_s,
            "blocks_decoded": self.blocks_decoded,
            "blocks_hopped": self.blocks_hopped,
        }


# What admission keeps free of the device's ``bytes_limit``: the
# runtime's own and the executables with their scratch, a scheduler's
# served models and landmark table where one shares the process
# (dragonfly2_tpu.colocated), the transfer queue's staging, and room for
# a table of gigabytes to be laid in one piece after others have come
# and gone. A constant: the rule has to admit the same cadence the same
# way in every run, on every machine of one kind.
ROUND_RESERVE_BYTES = 2 << 30
# a round's GraphSAGE and GRU fits on the chip (the GRU's table of a
# million sequences is 70-89 MB, the graph's kilobytes a host)
ROUND_SMALL_FITS_BYTES = 128 << 20
# no pair takes less of an upload, in either payload form: its 19
# features, its label and its download's index in a train block, many
# times that as CSV text. The reckoning's bound where the headers cannot
# be walked
_MIN_PAIR_BYTES = 84


@dataclass
class Admission:
    """A round's own account of how it came onto the chip."""

    host_id: str = ""
    arrival: int = 0  # its place among the trainer's arrivals
    order: int = 0  # its place among the trainer's admissions
    result: str = "at_once"  # or "waited": refused for room, admitted on another round's return
    waited_s: float = 0.0
    reserved_bytes: "int | None" = None  # what it was reckoned to hold on the chip at its fullest
    admitted: bool = False


class RoundAdmission:
    """Which rounds run side by side on the trainer's chip. A round holds
    its table there for the whole fit; every scheduler's Train stream
    forks a round at its end, and a cluster's schedulers reach their
    upload together. A round is admitted when what it was reckoned to
    hold (``Training._reckon_round_bytes``: from its upload's headers, before
    a pair is read) fits the budget beside the reckoned bytes of the
    rounds running; otherwise it waits, in arrival order, for a running
    round's return. The decision is a function of the uploads' sizes,
    the arrival order and the device's limit: never of what the device
    holds at the moment. A round alone is always admitted (an upload
    that alone does not fit is ROADMAP M2's), and no waiting round is
    dropped, cut or sent another way."""

    def __init__(self, limit: "int | None"):
        self.limit = limit  # the device's bytes_limit; None: unbounded (a backend that states none)
        self._cond = threading.Condition()
        self._waiting: collections.deque = collections.deque()  # Admission, in arrival order
        self._running: list = []
        self._arrivals = self._admissions = 0

    @property
    def budget(self) -> "int | None":
        return None if self.limit is None else self.limit - ROUND_RESERVE_BYTES

    def arrive(self, host_id: str) -> Admission:
        """Take a place in the arrival order; a round that finds none
        running and none waiting is admitted on the spot."""
        with self._cond:
            a = Admission(host_id=host_id, arrival=self._arrivals)
            self._arrivals += 1
            if self._running or self._waiting:
                self._waiting.append(a)
            else:
                self._admit(a)
            return a

    def reckoned(self, a: Admission, nbytes: int) -> None:
        with self._cond:
            a.reserved_bytes = int(nbytes)
            self._sync_gauges()
            self._cond.notify_all()

    def wait(self, a: Admission) -> None:
        """Until ``a`` is admitted: at the head of the arrivals, with
        its own reckoning and that of every running round known, and
        room for it beside them."""
        t0 = time.perf_counter()
        with self._cond:
            while not a.admitted:
                known = a.reserved_bytes is not None and all(r.reserved_bytes is not None for r in self._running)
                if self._waiting[0] is a and known:
                    beside = sum(r.reserved_bytes for r in self._running)
                    if not self._running or self.budget is None or beside + a.reserved_bytes <= self.budget:
                        self._waiting.popleft()
                        self._admit(a)
                        self._cond.notify_all()  # the next in line may fit too
                        break
                    for behind in self._waiting:  # in arrival order: whoever stands behind waits for room too
                        behind.result = "waited"
                self._cond.wait()
        a.waited_s = round(time.perf_counter() - t0, 6) if a.result == "waited" else 0.0
        M.ROUND_ADMISSION_TOTAL.labels(a.result).inc()

    def leave(self, a: Admission) -> bool:
        """``a``'s round has returned. -> whether that ends a cadence:
        no round is running and none waits."""
        with self._cond:
            (self._running if a.admitted else self._waiting).remove(a)
            self._sync_gauges()
            self._cond.notify_all()
            return not self._running and not self._waiting

    def _admit(self, a: Admission) -> None:
        self._running.append(a)
        a.admitted, a.order = True, self._admissions
        self._admissions += 1
        self._sync_gauges()

    def _sync_gauges(self) -> None:
        M.ROUNDS_RUNNING.set(len(self._running))
        M.ROUNDS_RESERVED_BYTES.set(sum(r.reserved_bytes or 0 for r in self._running))


def _device_bytes_limit(mesh) -> "int | None":
    """The smallest ``bytes_limit`` of the devices a fit lays its table
    on (under a mesh every chip holds it whole); None where the backend
    states none (the CPU's)."""
    import jax

    devices = list(mesh.devices.flat) if mesh is not None else jax.local_devices()[:1]
    limits = [(d.memory_stats() or {}).get("bytes_limit") for d in devices]
    return min(limits) if limits and all(limits) else None


@dataclass
class TrainingOutcome:
    mlp_metrics: dict[str, float] | None = None
    gnn_metrics: dict[str, float] | None = None
    gru_metrics: dict[str, float] | None = None
    mlp_error: str | None = None
    gnn_error: str | None = None
    gru_error: str | None = None  # GRU is optional; never gates .ok
    wall_s: float = 0.0  # the round: the three fits, side by side
    splits: dict[str, LegSplit] = field(default_factory=dict)  # by leg
    admission: Admission = field(default_factory=Admission)

    @property
    def ok(self) -> bool:
        return self.mlp_error is None and self.gnn_error is None


class Training:
    def __init__(
        self,
        storage: TrainerStorage,
        manager_client: ManagerClient | None = None,
        config: TrainingConfig | None = None,
        mesh=None,
    ):
        self.storage = storage
        self.manager_client = manager_client
        self.config = config or TrainingConfig()
        if mesh is None and self.config.auto_mesh:
            # every-addressable-device dp mesh (None on a single-device
            # host). A failure to build it raises: a multi-chip host that
            # quietly fits on one chip looks healthy and is not.
            from dragonfly2_tpu.parallel.mesh import auto_dp_mesh

            mesh = auto_dp_mesh()
        self.mesh = mesh
        self.admission = RoundAdmission(_device_bytes_limit(mesh))
        # the hosts' newest fitted MLP versions since the last merge, and
        # the hosts whose MLP fit failed since the last cadence's end
        self._fitted: dict[str, FittedVersion] = {}
        self._unfitted: set[str] = set()
        self._fitted_lock = threading.Lock()

    def train(self, ip: str, hostname: str) -> TrainingOutcome:
        """One scheduler host's round: admitted to the chip by what its
        upload will hold there (``RoundAdmission``), fitted, and, where
        its return ends a cadence of several hosts' rounds, followed by
        their merge."""
        host_id = host_id_v2(ip, hostname)
        admission = self.admission.arrive(host_id)
        try:
            with M.PH_ROUND_WAIT:  # from its arrival until it is admitted: a round alone passes through
                if not admission.admitted:
                    self.admission.reckoned(admission, self._reckon_round_bytes(host_id))
                self.admission.wait(admission)
            outcome = self._round(host_id, ip, hostname, admission)
        finally:
            cadence_over = self.admission.leave(admission)
        if cadence_over:
            self._merge()
        return outcome

    def _round(self, host_id: str, ip: str, hostname: str, admission: Admission) -> TrainingOutcome:
        """Fit MLP + GNN for one uploading scheduler host, concurrently
        (reference training.go:60-78 errgroup)."""
        from dragonfly2_tpu.utils import tracing

        outcome = TrainingOutcome(admission=admission)
        # the caller's span (rpc.Train when driven by the Train stream):
        # fit spans in the pool threads parent under it explicitly —
        # contextvars don't cross ThreadPoolExecutor boundaries
        parent_span = tracing.current_span()
        # which payload form the MLP leg consumed (None until decided):
        # the post-fit clear drops exactly that form, so other-era data
        # from a format switch survives to train next round
        mlp_info: dict = {}
        splits = outcome.splits
        t0 = time.perf_counter()
        with self._round_profile(), M.PH_ROUND, concurrent.futures.ThreadPoolExecutor(
            max_workers=3
        ) as pool:
            f_mlp = pool.submit(
                self._timed_fit, "mlp", parent_span, splits, self._train_mlp,
                host_id, ip, hostname, mlp_info, admission,
            )
            f_gnn = pool.submit(
                self._timed_fit, "gnn", parent_span, splits, self._train_gnn,
                host_id, ip, hostname,
            )
            f_gru = (
                pool.submit(
                    self._timed_fit, "gru", parent_span, splits, self._train_gru,
                    host_id, ip, hostname,
                )
                if self.config.gru
                else None
            )
            try:
                outcome.mlp_metrics = f_mlp.result()
            except Exception as e:
                logger.exception("trainMLP failed for %s", host_id)
                outcome.mlp_error = str(e)
                with self._fitted_lock:
                    self._fitted.pop(host_id, None)
                    self._unfitted.add(host_id)
            self._reckon_alone(admission, 0)  # a leg that ended before it knew its pairs
            try:
                outcome.gnn_metrics = f_gnn.result()
            except Exception as e:
                logger.exception("trainGNN failed for %s", host_id)
                outcome.gnn_error = str(e)
            if f_gru is not None:
                try:
                    outcome.gru_metrics = f_gru.result()
                except Exception as e:
                    logger.exception("trainGRU failed for %s", host_id)
                    outcome.gru_error = str(e)
        outcome.wall_s = round(time.perf_counter() - t0, 6)
        if "mlp" in splits:
            splits["mlp"].stream = mlp_info.get("stream")

        EV_ROUND(
            host_id=host_id,
            ok=outcome.ok,
            wall_s=outcome.wall_s,
            mlp_error=outcome.mlp_error or "",
            gnn_error=outcome.gnn_error or "",
            gru_error=outcome.gru_error or "",
            admission=admission.result,
            waited_s=admission.waited_s,
            reserved_bytes=admission.reserved_bytes or 0,
        )
        if self.config.clear_after_train and not self.config.incremental:
            # the reference retrains from scratch each round and drops
            # consumed uploads (trainer/trainer.go:156-161). Only the
            # payload form the MLP leg actually trained on is dropped —
            # after a scheduler format switch the other era's records
            # remain and train next round.
            if outcome.mlp_error is None:
                self.storage.clear_download(host_id, binary=mlp_info.get("binary"))
            if outcome.gnn_error is None:
                self.storage.clear_network_topology(host_id)
        return outcome

    def _timed_fit(self, model: str, parent_span, splits: dict, fn, host_id: str, *args):
        from dragonfly2_tpu.utils import tracing

        span = tracing.get("trainer").start_span("fit", parent=parent_span, model=model, host_id=host_id)
        t0 = time.perf_counter()
        blocks = wire.BlockTally()  # filled by the leg's block readers

        def close(mine: dict) -> dict:
            """The leg's split of this round, left in ``splits`` and
            handed back as the fields the event and the span carry."""
            compiles, compile_s = mine.pop(M.PH_JIT_COMPILE.name, (0, 0.0))
            leg = splits[model] = LegSplit(
                wall_s=round(time.perf_counter() - t0, 6),
                phase_s={k: round(v[1], 6) for k, v in mine.items()},
                phase_n={k: v[0] for k, v in mine.items()},
                compiles=compiles,
                compile_s=round(compile_s, 6),
                blocks_decoded=blocks.decoded,
                blocks_hopped=blocks.hopped,
            )
            M.FIT_BLOCKS_TOTAL.labels(model, "decoded").inc(blocks.decoded)
            M.FIT_BLOCKS_TOTAL.labels(model, "hopped").inc(blocks.hopped)
            fields = leg.fields()
            span.set(**fields)
            return fields

        # the fit span is active while fn runs so the ingest pipeline can
        # stamp its exemplars with the owning trace_id; the split is this
        # thread's, so the phases fn enters are credited to this leg; the
        # leg's own ``fit`` phase is open around the split, not in it
        with (
            M.FIT_DURATION.labels(model).time(),
            M.LEG_PHASES[model].fit,
            tracing.use_span(span),
            profiling.split() as mine,
        ):
            try:
                result = fn(host_id, *args, blocks=blocks)
            except Exception as e:
                EV_FIT(model=model, host_id=host_id, outcome="failure", error=str(e), **close(mine))
                span.end("error")
                M.FIT_TOTAL.labels(model, "failure").inc()
                raise
            EV_FIT(model=model, host_id=host_id, outcome="success", **close(mine))
        span.end("ok")
        M.FIT_TOTAL.labels(model, "success").inc()
        # fit-freshness source for the cluster telemetry plane: the SLO
        # engine alarms when (now - this) outgrows the train cadence
        M.LAST_FIT_TIMESTAMP.labels(model).set(time.time())
        return result

    @contextlib.contextmanager
    def _round_profile(self):
        """One ``jax.profiler`` trace a round when ``profile_dir`` is
        set, around the three fits — the XLA-side observability the
        reference's pprof flag provides for Go
        (cmd/dependency/dependency.go:95). JAX allows one session a
        process: a round that starts while another trace is open (a
        second host's round, the stall watchdog's capture) runs inside
        that one and opens none. The interpreter's own calls are not
        traced: the legs' phases say what the host was doing, and a
        tracer on every Python call slows the round it records many
        times over."""
        with contextlib.ExitStack() as stack:
            if self.config.profile_dir:
                import jax

                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                try:
                    stack.enter_context(
                        jax.profiler.trace(
                            f"{self.config.profile_dir}/round",
                            create_perfetto_trace=False,
                            profiler_options=options,
                        )
                    )
                except RuntimeError as e:
                    logger.warning("round runs without a trace of its own: %s", e)
            yield

    # -- trainMLP (reference training.go:92-98) ---------------------------
    def _train_mlp(
        self,
        host_id: str,
        ip: str,
        hostname: str,
        info: dict | None = None,
        admission: "Admission | None" = None,
        blocks: wire.BlockTally | None = None,
    ) -> dict[str, float]:
        # payload selection: binary columnar stream (zero-parse ingest)
        # or CSV via the native fused decoder (numpy fallback) — all
        # paths produce identical tensors (the equivalence tests pin
        # this). When BOTH eras hold pending data (the scheduler
        # switched formats), the OLDER era — CSV — drains first: it gets
        # trained and cleared this round, and the binary data trains at
        # the next round; preferring binary unconditionally would leave
        # a CSV leftover untrained (and re-merged into the GRU/FedAvg
        # legs) forever under continuous binary uploads. The consumed
        # form is reported back via ``info`` so train() clears only it.
        has_csv = self._pending_bytes(host_id, binary=False) > 0
        has_bin = self._pending_bytes(host_id, binary=True) > 0
        if has_csv and has_bin:
            try:
                return self._train_mlp_from(
                    host_id, ip, hostname, binary=False, info=info, admission=admission, blocks=blocks
                )
            except BelowMinRecords as e:
                # the CSV-era leftover alone can't train (below the
                # min-record gate / no pairs): fall through to the
                # binary era INSTEAD of failing this host every round
                # while its binary data grows unboundedly. The
                # sub-minimum tail rides out with this round's clear
                # (info["binary"]=None → both forms dropped): the
                # operator's own gate declared it too small to train on.
                logger.warning(
                    "csv-era leftover for %s untrainable (%s);"
                    " training the binary era and dropping the tail",
                    host_id,
                    e,
                )
                metrics = self._train_mlp_from(
                    host_id, ip, hostname, binary=True, info=info, admission=admission, blocks=blocks
                )
                if info is not None:
                    info["binary"] = None
                return metrics
        return self._train_mlp_from(
            host_id, ip, hostname, binary=has_bin, info=info, admission=admission, blocks=blocks
        )

    def _train_mlp_from(
        self,
        host_id: str,
        ip: str,
        hostname: str,
        binary: bool,
        info: dict | None = None,
        admission: "Admission | None" = None,
        blocks: wire.BlockTally | None = None,
    ) -> dict[str, float]:
        if info is not None:
            info["binary"] = binary
        path, offset = self._download_range(host_id, binary)
        # the boundary is marked by the Train service at stream EOF (locked
        # against appends), so the committed offset never lands mid-record
        # (mid-block for the binary file)
        boundary = self.storage.download_round_boundary(host_id, binary=binary)
        if self._use_streaming(path, offset, binary):
            self._reckon_alone(admission, 0)
            return self._train_mlp_streaming(
                host_id, ip, hostname, path, offset, boundary, binary, info
            )
        cfg = self._fit_config(self.config.mlp, "mlp", host_id)
        # the order's threads end with this block, fit or no fit
        with contextlib.ExitStack() as drawing:
            order = None
            with M.PH_MLP.load:
                if binary:
                    with M.PH_MLP.load_walk:
                        walk = wire.walk_train_pairs(
                            path, offset=offset, end=boundary, tally=blocks, native_phase=M.PH_MLP.load_walk_native
                        )
                    self._reckon_alone(admission, walk.num_pairs)
                    # the fit's order needs the pair count and not the pairs:
                    # it is drawn beside the assembly, which checks every block
                    # and raises before it hands over an array
                    order = drawing.enter_context(FitOrder(M.PH_MLP, walk.num_pairs, cfg))
                    with M.PH_MLP.load_assemble:
                        pairs = walk.assemble(span_phase=M.PH_MLP.load_span, check_phase=M.PH_MLP.load_check)
                    del walk
                else:
                    # bounded at the round boundary exactly like the binary and
                    # streaming paths: the in-flight tail past it may be
                    # truncated by a failed stream, and the offset commit below
                    # wouldn't cover it anyway
                    self._reckon_alone(admission, (boundary - offset) // _MIN_PAIR_BYTES)
                    pairs = native.decode_pairs_file(path, offset=offset, end=boundary)
                    if pairs is None:
                        recs = [
                            r
                            for chunk in self.storage.iter_download_chunks(
                                host_id, max_bytes=boundary
                            )
                            for r in chunk
                        ]
                        pairs = extract_pair_features(records_to_columns(recs))
            if pairs.num_downloads < self.config.min_download_records:
                raise BelowMinRecords(
                    f"{pairs.num_downloads} download records for host {host_id}"
                    f" < min {self.config.min_download_records}"
                )
            if pairs.features.shape[0] == 0:
                raise BelowMinRecords("no trainable (download, parent) pairs")
            if order is None:
                order = drawing.enter_context(FitOrder(M.PH_MLP, pairs.features.shape[0], cfg))
            # what a merge with other hosts' versions will be scored on, once the upload is gone
            # (taken before the fit, which ends the order)
            holdout = holdout_sample(pairs.features, pairs.labels, order.split()[1])
            result = train_mlp(pairs.features, pairs.labels, mesh=self.mesh, config=cfg, order=order)
        # the upload's pairs go a slice at a time, not in one free on return
        fitted_on = pairs.features.shape[0]
        owned = [pairs.features, pairs.labels, pairs.download_index]
        del pairs
        release_in_pieces(owned)
        self._register_mlp(host_id, ip, hostname, result.params, result.metrics, fitted_on, holdout)
        if self.config.incremental:
            # commit only after a fully successful round (incl. upload) —
            # a crashed round re-decodes from the previous offset
            self.storage.commit_download_offset(host_id, boundary, binary=binary)
        return result.metrics

    def _register_mlp(
        self, host_id: str, ip: str, hostname: str, params, metrics: dict, pairs: int, holdout: "tuple | None" = None
    ) -> None:
        """The fitted version to the manager under the host's id, and kept
        as the host's newest for the cadence's merge."""
        with M.PH_MLP.register:
            on_host = _to_host(params)
            if self.manager_client is not None:
                self.manager_client.create_model(
                    model_id=mlp_model_id_v1(ip, hostname),
                    model_type="mlp",
                    ip=ip,
                    hostname=hostname,
                    params=on_host,
                    evaluation=metrics,
                )
        with self._fitted_lock:
            self._fitted[host_id] = FittedVersion(on_host, pairs, holdout)

    @staticmethod
    def _round_bytes(pairs: int) -> int:
        """What a round whose resident MLP fit has ``pairs`` pairs holds
        on the chip at its fullest: the fit's table, row numbers and
        slices (``train.resident_fit_bytes``), and a flat allowance for
        the two small fits (a streamed fit's two superbatches too)."""
        from dragonfly2_tpu.schema.features import MLP_FEATURE_DIM

        return ROUND_SMALL_FITS_BYTES + (resident_fit_bytes(pairs, (MLP_FEATURE_DIM,), ()) if pairs else 0)

    def _reckon_alone(self, admission: "Admission | None", pairs: int) -> None:
        """A round admitted alone, on the spot, is reckoned by its MLP
        leg, from the walk the load makes anyway, for whoever arrives
        while it runs (a round that waited was reckoned before it did:
        ``_reckon_round_bytes``)."""
        if admission is not None and admission.reserved_bytes is None:
            self.admission.reckoned(admission, self._round_bytes(pairs))

    def _reckon_round_bytes(self, host_id: str) -> int:
        """What this host's round will hold on the chip at its fullest
        (``_round_bytes``), from what is pending for it in storage and
        before a pair of it is read: the pairs its upload's headers
        count. A streamed fit counts none. Where the headers cannot be
        walked (CSV text; a header the MLP leg will refuse too) the
        pairs are bounded by the bytes, from above."""
        pairs = 0
        for binary in (False, True):
            path, offset = self._download_range(host_id, binary)
            if self._pending_bytes(host_id, binary) <= 0 or self._use_streaming(path, offset, binary):
                continue
            # a round fits one payload form (the other waits for the next): the larger sets the bound
            boundary = self.storage.download_round_boundary(host_id, binary=binary)
            bound = (boundary - offset) // _MIN_PAIR_BYTES
            if binary:
                try:
                    bound = wire.walk_train_pairs(path, offset=offset, end=boundary).num_pairs
                except Exception:
                    logger.warning("upload of %s reckoned by its bytes: its headers do not walk", host_id)
            pairs = max(pairs, bound)
        return self._round_bytes(pairs)

    def _merge(self) -> "dict[str, float] | None":
        """A cadence has ended (a round returned and none runs or waits):
        where more than one host has had an MLP version fitted since the
        last merge, register their pair-weighted mean as ONE model under
        the federated id (trainer/federation.py). It waits for no host
        that uploaded nothing; a host whose fit failed is left out and
        named. One host alone merges nothing, and its version stays the
        newest for a later cadence. -> the merged model's evaluation."""
        with self._fitted_lock:
            unfitted, self._unfitted = sorted(self._unfitted), set()
            if len(self._fitted) < 2:
                return None
            fitted, self._fitted = self._fitted, {}
        if unfitted:
            logger.warning("merge without %s: their MLP fit failed this cadence", ", ".join(unfitted))
        with M.PH_MERGE:
            merged, evaluation = merge_versions(fitted)
            if self.manager_client is not None:
                self.manager_client.create_model(
                    model_id=federated_model_id_v1(),
                    model_type="mlp",
                    ip="",
                    hostname="federated",
                    params=merged,
                    evaluation=evaluation,
                )
        logger.info("merged %d hosts' MLP versions over %d pairs", len(fitted), int(evaluation["pairs"]))
        return evaluation

    def _fit_config(self, cfg, model: str, host_id: str):
        """Stamp the per-(model, host) checkpoint dir onto a fit config
        when elastic restart is enabled — the fit loop then snapshots
        every epoch and resumes from the newest snapshot after a crash
        (trainer/checkpoint.py; cleared on successful completion)."""
        if not self.config.checkpoint_dir:
            return cfg
        import os
        from dataclasses import replace

        return replace(
            cfg,
            checkpoint_dir=os.path.join(
                self.config.checkpoint_dir, f"{model}-{host_id}"
            ),
        )

    def _download_range(self, host_id: str, binary: bool) -> tuple:
        """-> (the host's download file of that payload form, the offset
        a round reads it from: what earlier rounds committed in
        incremental mode, else its start)."""
        path = (
            self.storage.download_blocks_path(host_id)
            if binary
            else self.storage.download_path(host_id)
        )
        offset = (
            self.storage.download_offset(host_id, binary=binary)
            if self.config.incremental
            else 0
        )
        return path, offset

    def _pending_bytes(self, host_id: str, binary: bool) -> int:
        import os

        path, offset = self._download_range(host_id, binary)
        try:
            return os.path.getsize(path) - offset
        except OSError:
            return 0

    def _use_streaming(self, path, offset: int, binary: bool) -> bool:
        import os

        # the binary stream needs no native library — frombuffer IS the
        # decoder; CSV streaming still rides the fused C++ parser
        if not self.config.streaming:
            return False
        if not binary and not native.available():
            return False
        try:
            pending = os.path.getsize(path) - offset
        except OSError:
            return False
        return pending >= self.config.streaming_threshold_bytes

    def _train_mlp_streaming(
        self,
        host_id: str,
        ip: str,
        hostname: str,
        path,
        offset: int,
        boundary: int,
        binary: bool = False,
        info: dict | None = None,
    ) -> dict[str, float]:
        """Large-dataset path: bounded-memory overlapped decode+train
        (trainer.ingest.stream_train_mlp) instead of materializing every
        pair in host RAM. Holdout mse/mae stands in for train_mlp's eval
        split; the model/optimizer family is identical."""
        from dragonfly2_tpu.trainer.ingest import stream_train_mlp

        cfg = self.config.mlp
        if self.config.min_download_records > 1:
            # cheap pre-gate (batch path checks before fitting too): a
            # bounded decode stops as soon as min records are seen, so a
            # sparse host fails here instead of after the full multi-pass
            # fit on the chip. Binary counts from block headers alone —
            # no payload bytes are touched.
            if binary:
                rows = wire.count_records(
                    path, offset=offset, max_records=self.config.min_download_records
                )
            else:
                rows = 0
                for _, _, rows in native.stream_pairs_file(
                    path, offset=offset, max_records=self.config.min_download_records
                ):
                    pass
            if rows < self.config.min_download_records:
                raise BelowMinRecords(
                    f"{rows} download records for host {host_id}"
                    f" < min {self.config.min_download_records}"
                )
        eval_every = (
            max(2, round(1.0 / cfg.eval_fraction)) if cfg.eval_fraction > 0 else 0
        )
        params, stats = stream_train_mlp(
            path,
            passes=self.config.streaming_passes,
            batch_size=max(cfg.batch_size, 1),
            hidden_dims=cfg.hidden_dims,
            learning_rate=cfg.learning_rate,
            weight_decay=cfg.weight_decay,
            offset=offset,
            # bound at the committed round boundary, exactly like the
            # batch path: bytes past it belong to an in-flight upload
            # whose failure may TRUNCATE them mid-read, and training
            # them would double-count records the offset commit below
            # doesn't cover
            end=boundary,
            workers=self.config.streaming_workers,
            eval_every=eval_every,
            mesh=self.mesh,
            steps_per_call=self.config.streaming_steps_per_call,
            time_budget_s=self.config.streaming_time_budget_s,
            # a stalled fit forces one jax.profiler capture through the
            # same profile_dir plumbing on-demand profiling uses
            stall_profile_dir=self.config.profile_dir,
        )
        if info is not None:
            info["stream"] = stats  # the leg's split carries it (LegSplit)
        # rows counted once per pass — gate on a single pass's worth.
        # A time-budget truncation may have stopped mid-pass; dividing
        # by the CONFIGURED pass count would then undercount what was
        # actually seen and fail a legitimately-trained fit, and the
        # pre-gate above already enforced the minimum on real rows.
        rows = stats.download_records // max(self.config.streaming_passes, 1)
        if rows < self.config.min_download_records and not stats.truncated:
            raise BelowMinRecords(
                f"{rows} download records for host {host_id}"
                f" < min {self.config.min_download_records}"
            )
        if stats.pairs == 0:
            raise BelowMinRecords("no trainable (download, parent) pairs")
        logger.info(
            "streamed fit for %s: %d records, %d pairs, %d steps, %.0f rec/s",
            host_id,
            rows,
            stats.pairs,
            stats.steps,
            stats.records_per_s,
        )
        self._register_mlp(
            host_id, ip, hostname, params, stats.metrics, stats.pairs // max(self.config.streaming_passes, 1)
        )
        if self.config.incremental:
            self.storage.commit_download_offset(host_id, boundary, binary=binary)
        return stats.metrics

    # -- trainGNN (reference training.go:82-88) ---------------------------
    def _train_gnn(
        self, host_id: str, ip: str, hostname: str, blocks: wire.BlockTally | None = None
    ) -> dict[str, float]:
        # the probe graph is cumulative state (EWMA RTT edges), so the GNN
        # always rebuilds from the whole history — no offset decode here;
        # the incremental win is on the (much larger) download stream
        bpath = self.storage.network_topology_blocks_path(host_id)
        cpath = self.storage.network_topology_path(host_id)
        has_bin = bpath.exists() and bpath.stat().st_size > 0
        has_csv = cpath.exists() and cpath.stat().st_size > 0
        with M.PH_GNN.load:
            graph = None
            if has_bin and has_csv:
                # format-switch history: merge BOTH eras (CSV rows first —
                # they predate the binary era, and edge RTT is
                # last-write-wins in the graph build)
                from dragonfly2_tpu.schema.columnar import concat_columns

                cols = concat_columns(
                    [
                        records_to_columns(self.storage.list_network_topology(host_id)),
                        wire.read_columns(
                            bpath,
                            kind=wire.KIND_TOPOLOGY,
                            end=self.storage.network_topology_round_boundary(
                                host_id, binary=True
                            ),
                            tally=blocks,
                        ),
                    ]
                )
                graph = build_probe_graph(cols, max_degree=self.config.gnn_max_degree)
            elif has_bin:
                # binary topology upload: raw record columns, decoded straight
                # into the vectorized graph build (read bounded by the round
                # boundary so a concurrent upload's tail is never decoded)
                cols = wire.read_columns(
                    bpath,
                    kind=wire.KIND_TOPOLOGY,
                    end=self.storage.network_topology_round_boundary(host_id, binary=True),
                    tally=blocks,
                )
                graph = build_probe_graph(cols, max_degree=self.config.gnn_max_degree)
            else:
                graph = native.build_probe_graph_file(
                    cpath, max_degree=self.config.gnn_max_degree
                )
            if graph is None:
                recs = self.storage.list_network_topology(host_id)
                graph = build_probe_graph(
                    records_to_columns(recs), max_degree=self.config.gnn_max_degree
                )
        if graph.num_records < self.config.min_topology_records:
            raise ValueError(
                f"{graph.num_records} network topology records for host {host_id}"
                f" < min {self.config.min_topology_records}"
            )
        result = train_gnn(
            graph, mesh=self.mesh, config=self._fit_config(self.config.gnn, "gnn", host_id)
        )
        if self.manager_client is not None:
            with M.PH_GNN.register:
                self.manager_client.create_model(
                    model_id=gnn_model_id_v1(ip, hostname),
                    model_type="gnn",
                    ip=ip,
                    hostname=hostname,
                    params=_to_host(result.params),
                    evaluation=result.metrics,
                )
        return result.metrics


    # -- trainGRU (piece time-series; our addition over the reference) -----
    def _train_gru(
        self, host_id: str, ip: str, hostname: str, blocks: wire.BlockTally | None = None
    ) -> dict[str, float]:
        from dragonfly2_tpu.schema.features import extract_piece_sequences
        from dragonfly2_tpu.trainer.train import train_gru
        from dragonfly2_tpu.utils.idgen import gru_model_id_v1

        # The fit is handed the NEWEST gru_max_sequences sequences:
        # records append in time order, so keeping the tail keeps the fit
        # tracking recent link behavior — in incremental mode the file is
        # never cleared, and an oldest-first cap would pin the model to
        # stale history forever. Nothing older is decoded: the binary
        # upload carries the sequences pre-extracted in each train block
        # and is read from its last block backwards until the cap is held
        # (wire.read_gru_tail, which CRC-checks the blocks it decodes;
        # the MLP leg's read of the whole upload checks the rest). The
        # CSV era is older than the binary one, so it is read only when
        # the blocks hold fewer than the cap: chunk-wise, re-extracting,
        # keeping its newest — a host that switched payload formats
        # keeps its whole recent history feeding the next-cost model.
        # Both reads stop at the committed round boundary (a concurrent
        # Train stream may be appending past it, same protocol as the
        # MLP leg's offset/boundary machinery), and both hold the memory
        # bound of the streaming MLP path: the cap, not the file.
        cap = self.config.gru_max_sequences
        with M.PH_GRU.load:
            seqs = extract_piece_sequences({})
            bpath = self.storage.download_blocks_path(host_id)
            if bpath.exists() and bpath.stat().st_size:
                seqs = wire.read_gru_tail(
                    bpath,
                    cap,
                    end=self.storage.download_round_boundary(host_id, binary=True),
                    tally=blocks,
                    native_phase=M.PH_GRU.load_native,
                )
            cpath = self.storage.download_path(host_id)
            if seqs.sequences.shape[0] < cap and cpath.exists() and cpath.stat().st_size:
                boundary = self.storage.download_round_boundary(host_id)
                seqs = _newest_sequences(
                    (
                        extract_piece_sequences(records_to_columns(chunk))
                        for chunk in self.storage.iter_download_chunks(
                            host_id, max_bytes=boundary
                        )
                    ),
                    seqs,
                    cap,
                )
        n = seqs.sequences.shape[0]
        if n < self.config.gru_min_sequences:
            raise ValueError(
                f"{n} piece sequences for host {host_id}"
                f" < min {self.config.gru_min_sequences}"
            )
        result = train_gru(
            seqs.sequences,
            seqs.labels,
            lengths=seqs.lengths,
            mesh=self.mesh,
            config=self._fit_config(self.config.gru_config, "gru", host_id),
        )
        if self.manager_client is not None:
            with M.PH_GRU.register:
                self.manager_client.create_model(
                    model_id=gru_model_id_v1(ip, hostname),
                    model_type="gru",
                    ip=ip,
                    hostname=hostname,
                    params=_to_host(result.params),
                    evaluation=result.metrics,
                )
        return result.metrics


def _newest_sequences(older, seqs, cap: int):
    """``seqs`` behind the newest of ``older`` (PieceSequences in time
    order, from a format that only reads forwards) that fill ``cap``:
    an older part goes as soon as what follows it holds the cap."""
    from dragonfly2_tpu.schema.features import PieceSequences

    kept = collections.deque([seqs])
    total = seqs.sequences.shape[0]
    for s in older:
        if s.sequences.shape[0]:
            kept.insert(len(kept) - 1, s)
            total += s.sequences.shape[0]
        while len(kept) > 1 and total - kept[0].sequences.shape[0] >= cap:
            total -= kept.popleft().sequences.shape[0]
    return PieceSequences(
        *(
            np.concatenate([getattr(p, f) for p in kept])[-cap:]
            for f in ("sequences", "labels", "lengths")
        )
    )


def _to_host(params) -> Any:
    """Device → host numpy pytree (for serialization/upload)."""
    import jax

    return jax.tree_util.tree_map(lambda x: np.asarray(x), params)
