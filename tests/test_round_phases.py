"""A round's time, accounted from inside: the resident legs' phases
(trainer/metrics.py FIT_STAGES), the split each leg hands back, the
profiler's clock under every ``with`` phase, compiles counted by the
program's own listener, and one ``jax.profiler`` trace a round."""

import glob
import os
import subprocess
import sys
import threading
import types

import numpy as np
import pytest

import jax

from dragonfly2_tpu.schema import native, synth, wire
from dragonfly2_tpu.trainer import metrics as M
from dragonfly2_tpu.trainer import train as train_mod
from dragonfly2_tpu.trainer.storage import TrainerStorage
from dragonfly2_tpu.trainer.train import FitConfig, GNNFitConfig
from dragonfly2_tpu.trainer.training import Training, TrainingConfig
from dragonfly2_tpu.utils import flight, profiling, tracing
from dragonfly2_tpu.utils.idgen import host_id_v2

IP, HOSTNAME = "10.0.0.7", "sched-phases"
LEGS = ("mlp", "gnn", "gru")
EPOCHS = {"mlp": 2, "gnn": 4, "gru": 3}
ONCE_A_FIT = ("load", "split", "table_put", "holdout", "register")
ONCE_AN_EPOCH = ("gather", "feed", "epoch_dispatch", "epoch_wait")
# entered inside one of those on the leg's thread: the ledger's and the trace's, in no split
INSIDE_ANOTHER = ("fit", "load_walk", "load_assemble", "load_walk_native", "load_native", "feed_slice", "epoch_slice", "load_span", "load_check")
STREAM_PHASES = ("trainer.decode_wait", "trainer.buffer_wait", "trainer.h2d", "trainer.step")


class Manager:
    def __init__(self):
        self.registered = []

    def create_model(self, model_id, model_type, ip, hostname, params, evaluation):
        self.registered.append(model_type)


def _stage(storage: TrainerStorage, host_id: str) -> None:
    """One scheduler's binary upload, as the Train stream leaves it."""
    downloads = synth.make_download_records(768, seed=5)
    topology = synth.make_topology_records(400, num_hosts=32, seed=6)
    rpb = wire.BLOCK_RECORDS
    for i in range(0, len(downloads), rpb):
        storage.append_download_blocks(host_id, wire.encode_train_block(downloads[i : i + rpb]))
    for i in range(0, len(topology), rpb):
        storage.append_network_topology_blocks(
            host_id, wire.encode_topology_block(topology[i : i + rpb])
        )
    storage.mark_download_round(host_id)


def _training(root, streaming: bool, **config) -> Training:
    cfg = TrainingConfig(
        mlp=FitConfig(hidden_dims=(32,), batch_size=256, epochs=EPOCHS["mlp"]),
        gnn=GNNFitConfig(hidden_dims=(16,), batch_size=256, epochs=EPOCHS["gnn"]),
        gru_config=FitConfig(hidden_dims=(8,), batch_size=64, epochs=EPOCHS["gru"]),
        streaming=streaming,
        streaming_threshold_bytes=0,
        auto_mesh=False,
        **config,
    )
    return Training(TrainerStorage(root), Manager(), cfg)


class _Compiles:
    """The test's own count of what the backend was asked for, beside
    the program's listener."""

    def __init__(self):
        self.by_thread: dict = {}

    def __call__(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            n, s = self.by_thread.get(threading.get_ident(), (0, 0.0))
            self.by_thread[threading.get_ident()] = (n + 1, s + seconds)

    @property
    def count(self) -> int:
        return sum(n for n, _ in self.by_thread.values())

    @property
    def seconds(self) -> float:
        return sum(s for _, s in self.by_thread.values())


@pytest.fixture(scope="module", params=["resident", "streamed"])
def round_(request, tmp_path_factory):
    """One toy round on the CPU through ``Training.train()``, after a
    first that pays the process's one-time compiles (parameter init,
    the holdout forwards), with everything a case below compares."""
    streaming = request.param == "streamed"
    training = _training(tmp_path_factory.mktemp(request.param), streaming)
    host_id = host_id_v2(IP, HOSTNAME)
    _stage(training.storage, host_id)
    assert training.train(IP, HOSTNAME).ok
    _stage(training.storage, host_id)
    names = [ph.name for leg in (M.PH_MLP, M.PH_GNN, M.PH_GRU) for ph in vars(leg).values()]
    names += [M.PH_JIT_COMPILE.name, *STREAM_PHASES]
    before = {n: profiling.phase_type(n).snapshot() for n in names}
    series0 = M.JIT_RECOMPILES_TOTAL.value
    compiles = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        with tracing.get("trainer").start_span("test-round") as root:
            outcome = training.train(IP, HOSTNAME)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
    after = {n: profiling.phase_type(n).snapshot() for n in names}
    events = [
        e for e in flight.snapshot(["trainer"])["trainer"] if e["trace_id"] == root.trace_id
    ]
    fit_events = [e for e in events if e["type"] == "trainer.fit"]
    round_events = [e for e in events if e["type"] == "trainer.round"]
    spans = [
        s for s in tracing.get("trainer").finished
        if s.name == "fit" and s.trace_id == root.trace_id
    ]
    return {
        "streaming": streaming,
        "training": training,
        "outcome": outcome,
        "ledger": {
            n: (after[n]["count"] - before[n]["count"], after[n]["total_s"] - before[n]["total_s"])
            for n in names
        },
        "series_moved": M.JIT_RECOMPILES_TOTAL.value - series0,
        "compiles": compiles,
        "fit_events": {e["model"]: e for e in fit_events},
        "round_events": round_events,
        "spans": {s.attributes["model"]: s for s in spans},
    }


def _expected_counts(leg: str, streaming: bool) -> dict:
    if leg == "mlp" and streaming:
        # the streamed fit keeps its own phases and gains the register
        return {"trainer.mlp_register": 1}
    want = {f"trainer.{leg}_{stage}": 1 for stage in ONCE_A_FIT}
    want.update({f"trainer.{leg}_{stage}": EPOCHS[leg] for stage in ONCE_AN_EPOCH})
    return want


def _expected_inside(leg: str, streaming: bool) -> dict:
    """The ledger's entries a round that no split holds: a phase open
    inside another on the leg's thread, and the leg's ``fit`` around its
    split."""
    if leg == "mlp" and streaming:
        return {"trainer.mlp_fit": 1}
    want = {
        f"trainer.{leg}_fit": 1,
        f"trainer.{leg}_feed_slice": 1 + EPOCHS[leg],  # the table's one slice at toy size, and one an epoch of row numbers
        f"trainer.{leg}_epoch_slice": EPOCHS[leg],
    }
    if leg == "mlp":
        want.update({"trainer.mlp_load_walk": 1, "trainer.mlp_load_assemble": 1, "trainer.mlp_load_span": 1})
        want["trainer.mlp_load_walk_native"] = int(native.available())  # the library's walk, where it loaded
    if leg == "gru":
        want["trainer.gru_load_native"] = int(native.available())  # the tail read through the library, streamed round or resident
    return want


@pytest.mark.parametrize("leg", LEGS)
def test_every_phase_once_a_fit_or_once_an_epoch(round_, leg):
    outcome = round_["outcome"]
    assert outcome.ok and outcome.gru_error is None, outcome
    split = outcome.splits[leg]
    want = _expected_counts(leg, round_["streaming"])
    assert split.phase_n == want
    assert all(split.phase_s[name] > 0 for name in want)
    # the process-wide ledger moved by the same entries, and by those inside them
    for name, n in {**want, **_expected_inside(leg, round_["streaming"])}.items():
        assert round_["ledger"][name][0] == n, name
    assert not {f"trainer.{leg}_{stage}" for stage in INSIDE_ANOTHER} & set(split.phase_n)
    if leg == "mlp" and round_["streaming"]:
        assert split.stream is not None and split.stream.steps > 0
        assert all(round_["ledger"][name][0] > 0 for name in STREAM_PHASES)
    else:
        assert split.stream is None


@pytest.mark.parametrize("leg", LEGS)
def test_the_order_is_drawn_beside_the_leg_and_waited_for_inside_it(round_, leg):
    """A fit's order is drawn on threads of its own, once a permutation
    (the holdout's and one an epoch): ``order`` moved by ``1 + epochs``
    in the ledger and is in no leg's split, which holds the waits,
    ``split`` and ``gather``, as before."""
    split = round_["outcome"].splits[leg]
    drawn, seconds = round_["ledger"][f"trainer.{leg}_order"]
    assert f"trainer.{leg}_order" not in split.phase_n
    if leg == "mlp" and round_["streaming"]:
        assert drawn == 0  # the streamed fit draws no order
        return
    assert drawn == 1 + EPOCHS[leg] and seconds > 0
    assert split.phase_n[f"trainer.{leg}_split"] == 1 and split.phase_n[f"trainer.{leg}_gather"] == EPOCHS[leg]


def test_a_round_below_its_minimum_leaves_no_drawing_thread(tmp_path, monkeypatch):
    """The count is known when the walk ends and the order is begun
    there, before the gate on the records can refuse the round: the
    draws under way end with their shuffle, no later epoch's is begun,
    and no thread is left."""
    from dragonfly2_tpu.trainer.training import BelowMinRecords

    def order_threads():
        return [t for t in threading.enumerate() if t.name.startswith(M.PH_MLP.order.name)]

    gate, begun, real = threading.Event(), threading.Semaphore(0), train_mod._permutation
    assemble = wire.TrainPairsWalk.assemble

    def held(rng, n):
        begun.release()
        assert gate.wait(timeout=60)
        return real(rng, n)

    def assemble_beside_both_draws(walk, **timer):
        assert begun.acquire(timeout=30) and begun.acquire(timeout=30)
        return assemble(walk, **timer)

    monkeypatch.setattr(train_mod, "_permutation", held)
    monkeypatch.setattr(wire.TrainPairsWalk, "assemble", assemble_beside_both_draws)
    training = _training(tmp_path, False, min_download_records=10**6)
    host_id = host_id_v2(IP, HOSTNAME)
    _stage(training.storage, host_id)
    before = M.PH_MLP.order.snapshot()["count"]
    try:
        with pytest.raises(BelowMinRecords, match="< min 1000000"):
            training._timed_fit("mlp", None, {}, training._train_mlp, host_id, IP, HOSTNAME)
        drawing = order_threads()
        assert len(drawing) == 2 and all(t.is_alive() for t in drawing)  # the holdout's and the first epoch's, mid-draw
    finally:
        gate.set()
    for t in drawing:
        t.join(timeout=30)
    assert not order_threads()
    assert M.PH_MLP.order.snapshot()["count"] - before == 2  # of 1 + 2 epochs: the second epoch's was never begun


def _mlp_leg(training, host_id):
    """The resident MLP leg alone, as a round runs it → its split."""
    splits: dict = {}
    training._timed_fit("mlp", None, splits, training._train_mlp, host_id, IP, HOSTNAME)
    return splits["mlp"]


@pytest.mark.parametrize("span_blocks, spans", [(128, 1), (2, 2), (1, 3)])
def test_the_load_books_a_span_once_a_span_inside_load(tmp_path, monkeypatch, span_blocks, spans):
    """The upload's three blocks are checked and copied in spans
    (``wire.TrainPairsWalk.assemble``): ``load_span`` moves by one a
    span, observed while ``load`` is open by the thread that ran the
    span (the leg's for one span, a worker's for more), and a leg's
    split, which holds ``load`` once, does not hold it as well."""
    monkeypatch.setattr(wire, "ASSEMBLY_SPAN_BLOCKS", span_blocks)
    seen, observe = [], profiling.Phase.observe

    def watched(phase, seconds):
        if phase is M.PH_MLP.load_span:
            seen.append((M.PH_MLP.load.active, threading.current_thread() is leg_thread, seconds))
        observe(phase, seconds)

    monkeypatch.setattr(profiling.Phase, "observe", watched)
    training = _training(tmp_path, False)
    host_id = host_id_v2(IP, HOSTNAME)
    _stage(training.storage, host_id)
    leg_thread = threading.current_thread()
    before = M.PH_MLP.load_span.snapshot()
    split = _mlp_leg(training, host_id)
    after = M.PH_MLP.load_span.snapshot()
    assert after["count"] - before["count"] == len(seen) == spans
    assert all(load_open == 1 and on_leg == (spans == 1) for load_open, on_leg, _ in seen)
    assert after["total_s"] - before["total_s"] == pytest.approx(sum(s for _, _, s in seen), abs=1e-4)
    assert split.phase_n[M.PH_MLP.load.name] == 1 and M.PH_MLP.load_span.name not in split.phase_n
    # a span lies inside load: the spans of one worker cannot outlast it
    assert sum(s for _, _, s in seen) <= split.phase_s[M.PH_MLP.load.name] * min(spans, wire.ASSEMBLY_THREADS)


def test_a_round_books_its_load_spans(round_):
    moved = round_["ledger"][M.PH_MLP.load_span.name][0]
    assert moved == (0 if round_["streaming"] else 1)  # three blocks: one span; the streamed fit assembles nothing
    assert all(round_["ledger"][ph.load_span.name][0] == 0 for ph in (M.PH_GNN, M.PH_GRU))


def test_a_round_books_its_load_checks(round_):
    """A span checked by the library's one call is a ``load_check``
    beside its ``load_span`` (the round's process has the library or
    has not: the count says which path ran), inside the span's seconds;
    the MLP leg alone has entries, and no leg's split holds them."""
    spans, span_s = round_["ledger"][M.PH_MLP.load_span.name]
    checks, check_s = round_["ledger"][M.PH_MLP.load_check.name]
    assert checks == (spans if native.available() else 0) and 0 <= check_s <= span_s + 1e-9
    assert all(round_["ledger"][ph.load_check.name][0] == 0 for ph in (M.PH_GNN, M.PH_GRU))
    assert M.PH_MLP.load_check.name not in round_["outcome"].splits["mlp"].phase_n


def test_the_trainer_server_loads_the_library_as_it_starts(tmp_path, monkeypatch):
    """The library is loaded (on a machine's first start: built) on a
    thread of its own while the server comes up, not inside the first
    round's ``load``."""
    from dragonfly2_tpu.trainer.server import TrainerServer, TrainerServerConfig

    loaded = []
    monkeypatch.setattr(native, "load", lambda: loaded.append(threading.current_thread().name))
    TrainerServer(TrainerServerConfig(data_dir=str(tmp_path / "trainer")))
    for t in threading.enumerate():
        if t.name == "trainer.native_load":
            t.join()
    assert loaded == ["trainer.native_load"]


@pytest.mark.parametrize("path", ["library", "per-block"])
@pytest.mark.parametrize("span_blocks, spans", [(128, 1), (2, 2), (1, 3)])
def test_the_load_books_a_check_once_a_span_where_the_library_checks(tmp_path, monkeypatch, span_blocks, spans, path):
    """``load_check`` moves by one a span on the library's path (420 a
    fit for a week's upload), observed by the thread that ran the span
    while ``load`` is open, before that span's ``load_span`` and inside
    its seconds; where the library did not load (``DF_NO_NATIVE``) the
    per-block check runs, ``load_check`` stays where it was and
    ``load_span`` counts the spans all the same."""
    monkeypatch.setattr(wire, "ASSEMBLY_SPAN_BLOCKS", span_blocks)
    if path == "per-block":
        monkeypatch.setenv("DF_NO_NATIVE", "1")
    elif not native.available():
        pytest.skip("native library unavailable (no toolchain)")
    seen, observe = [], profiling.Phase.observe

    def watched(phase, seconds):
        if phase in (M.PH_MLP.load_check, M.PH_MLP.load_span):
            seen.append((phase.name, threading.get_ident(), M.PH_MLP.load.active, seconds))
        observe(phase, seconds)

    monkeypatch.setattr(profiling.Phase, "observe", watched)
    training = _training(tmp_path, False)
    host_id = host_id_v2(IP, HOSTNAME)
    _stage(training.storage, host_id)
    before = {ph.name: ph.snapshot() for ph in (M.PH_MLP.load_check, M.PH_MLP.load_span)}
    split = _mlp_leg(training, host_id)
    moved = {name: profiling.phase_type(name).snapshot()["count"] - b["count"] for name, b in before.items()}
    assert moved == {M.PH_MLP.load_span.name: spans, M.PH_MLP.load_check.name: spans if path == "library" else 0}
    assert all(load_open == 1 for _, _, load_open, _ in seen)
    assert M.PH_MLP.load_check.name not in split.phase_n and M.PH_MLP.load_span.name not in split.phase_n
    # a thread's entries alternate: a span's check, then that span, which holds the check's seconds
    by_thread: dict = {}
    for name, thread, _, seconds in seen:
        by_thread.setdefault(thread, []).append((name, seconds))
    for entries in by_thread.values():
        if path == "library":
            assert [n for n, _ in entries] == [M.PH_MLP.load_check.name, M.PH_MLP.load_span.name] * (len(entries) // 2)
            assert all(check <= span for (_, check), (_, span) in zip(entries[::2], entries[1::2]))
        else:
            assert {n for n, _ in entries} == {M.PH_MLP.load_span.name}


@pytest.mark.parametrize("path", ["library", "per-block"])
def test_the_load_is_its_walk_and_its_assembly(tmp_path, monkeypatch, path):
    """``load_walk`` and ``load_assemble`` are entered once a fit inside
    ``load``, on the leg's thread: the ledger's, not the split's, and
    together the load to within 5%, or 50 ms where that is more (between
    them the fit's order is begun: here that is held to nothing, a toy
    upload's load being milliseconds and a thread's start as long; and
    where the suite shares its cores with five other workers the leg's
    thread can stand off the processor for milliseconds between the two
    stretches, which is no fault of the phases: the driver's run of
    PR 43's tree read 5.3% that way). The spans count as before on
    either path, entered by the workers inside the assembly."""
    import contextlib

    import dragonfly2_tpu.trainer.training as training_mod

    if path == "per-block":
        monkeypatch.setenv("DF_NO_NATIVE", "1")
    elif not native.available():
        pytest.skip("native library unavailable (no toolchain)")
    monkeypatch.setattr(wire, "ASSEMBLY_SPAN_BLOCKS", 1)
    # no order is drawn: its holdout, of which the leg keeps rows for a merge, is empty
    no_order = types.SimpleNamespace(split=lambda: (np.arange(0), np.arange(0)))
    monkeypatch.setattr(training_mod, "FitOrder", lambda *a, **kw: contextlib.nullcontext(no_order))

    class LoadedAndNoFurther(Exception):
        pass

    def no_fit(*a, **kw):  # the load is what is read here: the leg ends where its fit would begin
        raise LoadedAndNoFurther

    monkeypatch.setattr(training_mod, "train_mlp", no_fit)
    # an upload long enough that what lies between the two stretches (a few calls) is under a twentieth: 120 blocks
    training = _training(tmp_path, False)
    host_id = host_id_v2(IP, HOSTNAME)
    block = wire.encode_train_block(synth.make_download_records(wire.BLOCK_RECORDS, seed=5))
    for _ in range(120):
        training.storage.append_download_blocks(host_id, block)
    training.storage.mark_download_round(host_id)
    blocks = len(wire.scan_block_extents(training.storage.download_blocks_path(host_id)))
    stages = ("load", "load_walk", "load_assemble", "load_span", "load_check")
    phases = {stage: getattr(M.PH_MLP, stage) for stage in stages}
    open_when_entered = []
    real_enter = profiling.Phase.__enter__

    def watched(ph):
        if ph in (phases["load_walk"], phases["load_assemble"]):
            open_when_entered.append((ph.name, phases["load"].active, phases["load_walk"].active))
        elif ph is phases["load_span"]:
            open_when_entered.append((ph.name, phases["load_assemble"].active, threading.current_thread().name.startswith("wire.assemble")))
        return real_enter(ph)

    monkeypatch.setattr(profiling.Phase, "__enter__", watched)
    before = {stage: ph.snapshot() for stage, ph in phases.items()}
    splits: dict = {}
    with pytest.raises(LoadedAndNoFurther):
        training._timed_fit("mlp", None, splits, training._train_mlp, host_id, IP, HOSTNAME)
    split = splits["mlp"]
    moved = {stage: (ph.snapshot()["count"] - before[stage]["count"], ph.snapshot()["total_s"] - before[stage]["total_s"]) for stage, ph in phases.items()}
    assert [moved[stage][0] for stage in stages] == [1, 1, 1, blocks, blocks if path == "library" else 0]
    assert open_when_entered[:2] == [(phases["load_walk"].name, 1, 0), (phases["load_assemble"].name, 1, 0)]
    assert open_when_entered[2:] == [(phases["load_span"].name, 1, True)] * blocks  # on the workers, inside the assembly
    load, walk, assemble = (moved[stage][1] for stage in stages[:3])
    assert walk > 0 and assemble > 0 and load - max(0.05 * load, 0.05) <= walk + assemble <= load
    assert split.phase_n[phases["load"].name] == 1
    assert not {phases[stage].name for stage in stages[1:]} & set(split.phase_n)
    assert split.phase_s[phases["load"].name] == pytest.approx(load, abs=1e-5)


@pytest.mark.parametrize("path", ["library", "interpreter"])
def test_the_gru_tail_read_through_the_library_counts_once_a_fit_inside_the_load(tmp_path, monkeypatch, path):
    """``gru_load_native`` is told the library's seconds once a fit, by
    the leg's thread while its ``load`` is open: count 1 where the
    library read the tail, its seconds held by ``load``'s, in no split;
    0 and no seconds under ``DF_NO_NATIVE``, where the interpreter
    hopped and decoded block by block. It is the GRU leg's stage and no
    other's, and the fit is handed the same arrays either way."""
    import dragonfly2_tpu.trainer.train as train_mod

    if path == "interpreter":
        monkeypatch.setenv("DF_NO_NATIVE", "1")
    elif not native.available():
        pytest.skip("native library unavailable (no toolchain)")
    handed = []
    monkeypatch.setattr(train_mod, "train_gru", lambda sequences, labels, lengths=None, **kw: handed.append((sequences, labels, lengths)) or 1 / 0)
    training = _training(tmp_path, False, gru_max_sequences=40, gru_min_sequences=1)
    host_id = host_id_v2(IP, HOSTNAME)
    for i in range(6):
        training.storage.append_download_blocks(host_id, wire.encode_train_block(synth.make_download_records(64, seed=50 + i)))
    training.storage.mark_download_round(host_id)
    load, told = M.PH_GRU.load, M.PH_GRU.load_native
    open_when_told = []
    real_observe = profiling.Phase.observe

    def watched(ph, seconds):
        if ph is told:
            open_when_told.append((load.active, threading.current_thread().name))
        return real_observe(ph, seconds)

    monkeypatch.setattr(profiling.Phase, "observe", watched)
    before = {ph: ph.snapshot() for ph in (load, told)}
    splits: dict = {}
    with pytest.raises(ZeroDivisionError):
        training._timed_fit("gru", None, splits, training._train_gru, host_id, IP, HOSTNAME)
    moved = {ph: (ph.snapshot()["count"] - before[ph]["count"], ph.snapshot()["total_s"] - before[ph]["total_s"]) for ph in (load, told)}
    by_library = path == "library"
    assert (moved[load][0], moved[told][0]) == (1, int(by_library))
    assert open_when_told == ([(1, threading.current_thread().name)] if by_library else [])
    assert (0 < moved[told][1] <= moved[load][1]) if by_library else moved[told][1] == 0
    assert told.inner and told.name == "trainer.gru_load_native" and told.name not in splits["gru"].phase_n
    assert not hasattr(M.PH_MLP, "load_native") and not hasattr(M.PH_GNN, "load_native")
    assert 0 < splits["gru"].blocks_decoded < 6 and splits["gru"].blocks_decoded + splits["gru"].blocks_hopped == 6
    with monkeypatch.context() as m:
        m.setenv("DF_NO_NATIVE", "1")
        want = wire.read_gru_tail(training.storage.download_blocks_path(host_id), 40)
    ((sequences, labels, lengths),) = handed
    assert len(want.labels) == 40
    for got, w in zip((sequences, labels, lengths), (want.sequences, want.labels, want.lengths)):
        np.testing.assert_array_equal(np.asarray(got), w)


@pytest.mark.parametrize("path", ["library", "interpreter", "handed-over"])
def test_the_librarys_walk_counts_once_a_fit_inside_the_walk(tmp_path, monkeypatch, path):
    """``load_walk_native`` is entered around the library's walk, inside
    ``load_walk`` on the leg's thread: 1 a fit where the library loaded,
    its seconds held by ``load_walk``'s, in no split; 0 and no seconds
    where the interpreter parsed every header (``DF_NO_NATIVE``). A walk
    the library hands over at a block it is not sure of (here the
    upload's second, its labels all zeros and so ``zero``-encoded) still
    counts its call, and the fit is handed the same arrays."""
    import dragonfly2_tpu.trainer.training as training_mod

    if path == "interpreter":
        monkeypatch.setenv("DF_NO_NATIVE", "1")
    elif not native.available():
        pytest.skip("native library unavailable (no toolchain)")
    handed = []
    monkeypatch.setattr(training_mod, "train_mlp", lambda features, labels, **kw: handed.append((features, labels)) or 1 / 0)
    training = _training(tmp_path, False)
    host_id = host_id_v2(IP, HOSTNAME)
    for i in range(6):
        block = wire.encode_train_block(synth.make_download_records(wire.BLOCK_RECORDS, seed=40 + i))
        if path == "handed-over" and i == 1:
            header, cols, _ = wire.decode_block(block)
            cols = {k: np.zeros_like(v) if k == "pairs.labels" else v for k, v in cols.items()}
            block = wire.encode_block(cols, wire.KIND_TRAIN, records=header["records"], meta=header["meta"])
        training.storage.append_download_blocks(host_id, block)
    training.storage.mark_download_round(host_id)
    phases = {stage: getattr(M.PH_MLP, stage) for stage in ("load", "load_walk", "load_walk_native")}
    open_when_entered = []
    real_enter = profiling.Phase.__enter__

    def watched(ph):
        if ph is phases["load_walk_native"]:
            open_when_entered.append((phases["load_walk"].active, threading.current_thread().name))
        return real_enter(ph)

    monkeypatch.setattr(profiling.Phase, "__enter__", watched)
    before = {stage: ph.snapshot() for stage, ph in phases.items()}
    splits: dict = {}
    leg = threading.current_thread().name
    with pytest.raises(ZeroDivisionError):
        training._timed_fit("mlp", None, splits, training._train_mlp, host_id, IP, HOSTNAME)
    moved = {stage: (ph.snapshot()["count"] - before[stage]["count"], ph.snapshot()["total_s"] - before[stage]["total_s"]) for stage, ph in phases.items()}
    by_library = path != "interpreter"
    assert [moved[stage][0] for stage in phases] == [1, 1, int(by_library)]
    assert open_when_entered == ([(1, leg)] if by_library else [])
    assert (0 < moved["load_walk_native"][1] <= moved["load_walk"][1]) if by_library else moved["load_walk_native"][1] == 0
    assert phases["load_walk_native"].name not in splits["mlp"].phase_n and phases["load_walk_native"].inner
    blocks = training.storage.download_blocks_path(host_id)
    with monkeypatch.context() as m:
        m.setenv("DF_NO_NATIVE", "1")
        want = wire.read_train_pairs(blocks)
    ((features, labels),) = handed
    np.testing.assert_array_equal(np.asarray(features), want.features)
    np.testing.assert_array_equal(np.asarray(labels), want.labels)
    assert splits["mlp"].blocks_decoded == 6


def test_a_corrupt_payload_fails_the_load_in_the_assembly_and_ends_the_order(tmp_path, monkeypatch):
    """The walk reads headers alone: over an upload whose last payload
    is corrupt it ends with its counts, and the fit's order is begun
    from them. The assembly then raises, before ``train_mlp`` is
    called with anything, and the order made from the walk's count is
    closed: no draw is left running, none is begun."""
    monkeypatch.setattr(wire, "ASSEMBLY_SPAN_BLOCKS", 2)
    import dragonfly2_tpu.trainer.training as training_mod

    made, fits, walks = [], [], []

    class Order(train_mod.FitOrder):
        def __init__(self, phases, n, cfg, **kw):
            made.append((self, n))
            super().__init__(phases, n, cfg, **kw)

    walk_train_pairs = wire.walk_train_pairs

    def walked(*a, **kw):
        walks.append(walk_train_pairs(*a, **kw))
        return walks[-1]

    monkeypatch.setattr(training_mod, "FitOrder", Order)
    monkeypatch.setattr(training_mod, "train_mlp", lambda *a, **kw: fits.append(a))
    monkeypatch.setattr(wire, "walk_train_pairs", walked)
    training = _training(tmp_path, False)
    host_id = host_id_v2(IP, HOSTNAME)
    _stage(training.storage, host_id)
    path = training.storage.download_blocks_path(host_id)
    sound = wire.read_train_pairs(path)
    buf = bytearray(path.read_bytes())
    buf[-3] ^= 0xFF
    path.write_bytes(bytes(buf))
    spans_before = M.PH_MLP.load_span.snapshot()["count"]
    with pytest.raises(wire.WireError, match="block crc mismatch at byte"):
        _mlp_leg(training, host_id)
    walk, ((order, n),) = walks[-1], made  # the first walk was ``sound``'s
    assert (walk.num_pairs, walk.num_downloads) == (len(sound.labels), sound.num_downloads) and n == walk.num_pairs
    assert not fits
    assert M.PH_MLP.load_span.snapshot()["count"] - spans_before == 2  # both were entered: the sound one, and the one whose check raised
    with pytest.raises(RuntimeError, match="after shutdown"):
        order._threads.submit(int)
    assert order._split is None and not order._ahead
    for t in threading.enumerate():
        if t.name.startswith((M.PH_MLP.order.name, "wire.assemble")):
            t.join(timeout=30)
            assert not t.is_alive()


@pytest.mark.parametrize("leg", LEGS)
def test_phases_cover_the_fit_wall(round_, leg):
    """A leg's phases sum to its wall but for its own bookkeeping
    (parameter and optimizer init, the configuration): what is left is
    ``self_s``, reported beside them. The leg runs as a round runs it,
    but alone: beside two other legs on this machine's one core its
    bookkeeping waits for the interpreter more than it works."""
    split = round_["outcome"].splits[leg]
    assert split.self_s == pytest.approx(split.wall_s - sum(split.phase_s.values()), abs=1e-5)
    assert 0 <= split.self_s < split.wall_s <= round_["outcome"].wall_s
    training = round_["training"]
    host_id = host_id_v2(IP, HOSTNAME)
    _stage(training.storage, host_id)
    alone: dict = {}
    fit = getattr(training, f"_train_{leg}")
    training._timed_fit(leg, None, alone, fit, host_id, IP, HOSTNAME)
    split = alone[leg]
    assert split.phase_n == _expected_counts(leg, round_["streaming"])
    covered = sum(split.phase_s.values())
    if leg == "mlp" and round_["streaming"]:
        # the streamed fit splits its pipeline's wall itself (StreamStats,
        # handed to the outcome); its set-up and holdout around that are
        # the leg's self time, most of a toy fit and little of a real one
        assert 0 < covered < split.wall_s
    else:
        assert 0.9 * split.wall_s <= covered <= split.wall_s


@pytest.mark.parametrize("leg", LEGS)
def test_outcome_event_and_span_carry_the_same_split(round_, leg):
    fields = round_["outcome"].splits[leg].fields()
    assert set(fields) == {
        "wall_s", "self_s", "phase_s", "phase_n", "compiles", "compile_s",
        "blocks_decoded", "blocks_hopped",
    }
    event = round_["fit_events"][leg]
    assert event["outcome"] == "success"
    assert {k: event[k] for k in fields} == fields
    span = round_["spans"][leg]
    assert {k: span.attributes[k] for k in fields} == fields


def test_round_event_carries_the_rounds_wall(round_):
    (event,) = round_["round_events"]
    assert event["wall_s"] == round_["outcome"].wall_s > 0
    assert event["ok"] is True


def test_compiles_are_counted_and_booked_to_the_leg_that_asked(round_):
    """Every executable asked of the backend moves the series and the
    ``trainer.jit_compile`` phase by one, with its seconds, whatever
    thread asked; a leg's split holds those its own thread asked for
    (the streamed fit's step compiles on its stage thread, for none)."""
    tap, splits = round_["compiles"], round_["outcome"].splits
    # each resident leg rebuilds its epoch function and asks for it once (a warm streamed MLP fit asks for none)
    assert tap.count >= len(LEGS) - round_["streaming"]
    assert round_["series_moved"] == tap.count
    n, seconds = round_["ledger"][M.PH_JIT_COMPILE.name]
    assert n == tap.count
    assert seconds == pytest.approx(tap.seconds, abs=1e-4)
    booked = sorted((s.compiles, s.compile_s) for s in splits.values())
    by_thread = sorted(tap.by_thread.values())
    if not round_["streaming"]:
        assert [c for c, _ in booked] == [c for c, _ in by_thread]
        assert [s for _, s in booked] == pytest.approx([s for _, s in by_thread], abs=1e-4)
    for leg in LEGS:
        if not (leg == "mlp" and round_["streaming"]):
            assert splits[leg].compiles >= 1 and splits[leg].compile_s > 0
        assert M.PH_JIT_COMPILE.name not in splits[leg].phase_s


def _fit_inputs(leg: str):
    rng = np.random.default_rng(0)
    if leg == "mlp":
        x = rng.normal(size=(600, 6)).astype(np.float32)
        return train_mod.train_mlp, (x, x[:, 0].copy())
    if leg == "gru":
        s = rng.normal(size=(300, 10, 2)).astype(np.float32)
        return train_mod.train_gru, (s, s[:, 0, 0].copy())
    from dragonfly2_tpu.schema.columnar import records_to_columns
    from dragonfly2_tpu.schema.features import build_probe_graph

    cols = records_to_columns(synth.make_topology_records(200, num_hosts=16, seed=1))
    return train_mod.train_gnn, (build_probe_graph(cols, max_degree=8),)


@pytest.mark.parametrize("leg", LEGS)
def test_each_leg_names_its_epoch_function(leg, monkeypatch):
    """``PjitFunction(<leg>_epoch)`` and the XLA module say which leg; the
    scan body's ops sit under a scope of the same name."""
    names, lowered = [], []
    real = train_mod.make_epoch_fn

    def spy(loss_fn, optimizer):
        fn = real(loss_fn, optimizer)
        names.append(fn.__name__)

        def epoch(*args):
            lowered.append(fn.lower(*args).as_text(debug_info=True))
            return fn(*args)

        return epoch

    monkeypatch.setattr(train_mod, "make_epoch_fn", spy)
    fit, args = _fit_inputs(leg)
    small = {"hidden_dims": (8,), "batch_size": 64, "epochs": 1}
    fit(*args, config=GNNFitConfig(**small) if leg == "gnn" else FitConfig(**small))
    assert names == [f"{leg}_epoch"]
    assert f"jit_{leg}_epoch" in lowered[0]
    assert f"{leg}_epoch/" in lowered[0]  # the named scope on the step's ops


def _host_event_names(trace_dir: str) -> set:
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    return {
        ev.name
        for plane in data.planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
    }


def test_a_with_phase_is_on_the_profilers_clock(tmp_path):
    entered = profiling.phase_type("trainer.test_on_profiler_clock")
    fed = profiling.phase_type("trainer.test_ledger_only")
    with jax.profiler.trace(str(tmp_path), create_perfetto_trace=False):
        with entered:
            np.ones(1000).sum()
        fed.observe(0.001)
    names = _host_event_names(str(tmp_path))
    assert entered.name in names
    assert fed.name not in names  # observe(dt) feeds the ledger alone


def test_a_with_phase_entered_on_a_worker_thread_is_in_the_trace(tmp_path):
    """The assembly's workers enter ``load_span`` on their own threads
    while the profiler's session was opened by another: the annotation
    is the thread's that entered it, and the trace holds it by name."""
    span = profiling.phase_type("trainer.test_on_a_worker")
    done, together = [], threading.Barrier(3, timeout=30)

    def work():
        with span:
            together.wait()  # three threads alive at once: three of the system's, not one reused
            done.append(threading.current_thread().name)

    with jax.profiler.trace(str(tmp_path), create_perfetto_trace=False):
        workers = [threading.Thread(target=work, name=f"wire.assemble_{k}") for k in range(3)]
        for t in workers:
            t.start()
        for t in workers:
            t.join(timeout=30)
    assert len(done) == 3
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    entries = [
        sum(ev.name == span.name for ev in line.events)
        for plane in data.planes if plane.name.startswith("/host:") for line in plane.lines
    ]
    assert [n for n in entries if n] == [1, 1, 1]  # a line a thread, an entry each


def test_a_phase_does_not_import_jax():
    code = (
        "import sys\n"
        "from dragonfly2_tpu.utils import profiling\n"
        "ph = profiling.phase_type('daemon.test_no_jax')\n"
        "with profiling.split() as mine:\n"
        "    with ph:\n"
        "        pass\n"
        "assert ph.snapshot()['count'] == 1 and mine[ph.name][0] == 1\n"
        "assert 'jax' not in sys.modules, 'a phase imported jax'\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=root, capture_output=True, text=True, timeout=120
    )
    assert done.returncode == 0, done.stderr


def test_a_split_is_the_threads_own():
    """Two threads in the same phase at once: the ledger sums them, each
    split holds its own."""
    ph = profiling.phase_type("trainer.test_split_own")
    base = ph.snapshot()["count"]
    got = {}
    gate = threading.Barrier(2, timeout=30)

    def work(key, entries):
        with profiling.split() as mine:
            gate.wait()
            for _ in range(entries):
                with ph:
                    pass
            ph.observe(0.5)  # ledger only
        got[key] = mine

    threads = [threading.Thread(target=work, args=(k, n)) for k, n in (("a", 2), ("b", 5))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    assert got["a"][ph.name][0] == 2 and got["b"][ph.name][0] == 5
    assert ph.snapshot()["count"] - base == 9
    with ph:  # no split open: the ledger alone
        pass
    assert got["a"][ph.name][0] == 2


def test_a_round_with_profile_dir_is_one_trace(tmp_path):
    """The operator's switch, on a real round: one ``jax.profiler``
    session around the three fits (one a fit, from three threads, is
    two refused), all three legs ok, and the legs' phases and epoch
    functions named on the host plane."""
    prof = tmp_path / "prof"
    training = _training(tmp_path / "store", False, profile_dir=str(prof))
    _stage(training.storage, host_id_v2(IP, HOSTNAME))
    outcome = training.train(IP, HOSTNAME)
    assert outcome.ok and outcome.gru_error is None, outcome
    assert sorted(training.manager_client.registered) == ["gnn", "gru", "mlp"]
    assert os.listdir(prof) == ["round"]
    names = _host_event_names(str(prof / "round"))
    assert {"trainer.round", "trainer.mlp_load_walk", "trainer.mlp_load_assemble", "trainer.mlp_load_span"} <= names
    for leg in LEGS:
        assert {f"trainer.{leg}_fit", f"trainer.{leg}_table_put", f"trainer.{leg}_feed_slice", f"trainer.{leg}_epoch_slice"} <= names
        assert f"trainer.{leg}_gather" in names
        assert f"trainer.{leg}_order" in names  # from the drawing threads
        assert f"trainer.{leg}_epoch_wait" in names
        assert any(n.startswith(f"PjitFunction({leg}_epoch)") for n in names), leg
    assert not any(n.startswith("PjitFunction(epoch)") for n in names)
