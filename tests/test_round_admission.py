"""Several schedulers' rounds on one trainer: admission to the chip by
the bytes each upload will hold there (trainer/training.py
``RoundAdmission``), the fork at a stream's end as the handler and an
in-process caller both take it (``TrainerService.fit_after_stream``),
a round's account staying its own beside another's, one host's failure
staying that host's, and the merge a cadence ends with."""

import threading
import time

import pytest

from dragonfly2_tpu.schema import synth, wire
from dragonfly2_tpu.trainer import metrics as M
from dragonfly2_tpu.trainer.service import TrainerService
from dragonfly2_tpu.trainer.storage import TrainerStorage
from dragonfly2_tpu.trainer.train import FEED_SLICE_BYTES, FitConfig, GNNFitConfig, resident_fit_bytes
from dragonfly2_tpu.trainer.training import (
    ROUND_RESERVE_BYTES,
    ROUND_SMALL_FITS_BYTES,
    RoundAdmission,
    Training,
    TrainingConfig,
)
from dragonfly2_tpu.utils import flight
from dragonfly2_tpu.utils.idgen import federated_model_id_v1, host_id_v2

HOSTS = [(f"10.0.1.{k}", f"sched-{k}") for k in range(3)]
LEGS = ("mlp", "gnn", "gru")


class Manager:
    def __init__(self):
        self.registered = []  # (model id, type, hostname, params, evaluation), in the order of the calls

    def create_model(self, model_id, model_type, ip, hostname, params, evaluation):
        self.registered.append((model_id, model_type, hostname, params, dict(evaluation)))

    def of(self, hostname: str) -> list:
        return sorted(t for _, t, h, _, _ in self.registered if h == hostname)


def _stage(storage: TrainerStorage, host_id: str, seed: int, records: int = 512) -> None:
    """One scheduler's binary upload, as the Train stream leaves it
    before the handler marks the round."""
    downloads = synth.make_download_records(records, seed=seed)
    topology = synth.make_topology_records(300, num_hosts=24, seed=seed + 100)
    rpb = wire.BLOCK_RECORDS
    for i in range(0, len(downloads), rpb):
        storage.append_download_blocks(host_id, wire.encode_train_block(downloads[i : i + rpb]))
    for i in range(0, len(topology), rpb):
        storage.append_network_topology_blocks(host_id, wire.encode_topology_block(topology[i : i + rpb]))


def _trainer(root, hosts=HOSTS, records=512):
    cfg = TrainingConfig(
        mlp=FitConfig(hidden_dims=(16,), batch_size=256, epochs=1),
        gnn=GNNFitConfig(hidden_dims=(8,), batch_size=256, epochs=2),
        gru_config=FitConfig(hidden_dims=(8,), batch_size=64, epochs=1),
        streaming=False,
        auto_mesh=False,
    )
    training = Training(TrainerStorage(root), Manager(), cfg)
    for k, (ip, hostname) in enumerate(hosts):
        _stage(training.storage, host_id_v2(ip, hostname), seed=11 + k, records=records)
    return training, TrainerService(training.storage, training)


def _cadence(training, service, hosts=HOSTS, apart_s: float = 0.02) -> list:
    """Every host's stream ends, ``apart_s`` after the one before; the
    outcomes in the order the rounds returned."""
    outcomes, real = [], training.train

    def kept(ip, hostname):
        outcomes.append(real(ip, hostname))
        return outcomes[-1]

    training.train = kept
    try:
        threads = []
        for ip, hostname in hosts:
            arrived = training.admission._arrivals
            threads.append(service.fit_after_stream(ip, hostname))
            while training.admission._arrivals == arrived:  # the arrival order is the streams' order
                time.sleep(0.001)
            time.sleep(apart_s)
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
    finally:
        training.train = real
    return outcomes


def _holds(training, rounds: int) -> int:
    """A device limit under which ``rounds`` of the staged uploads fit
    side by side and one more does not."""
    one = training._reckon_round_bytes(host_id_v2(*HOSTS[0]))
    return ROUND_RESERVE_BYTES + rounds * one + one // 2


# -- the rule alone ----------------------------------------------------------


def test_the_reckoning_is_the_tables_arithmetic():
    """A week's 55,050,240 pairs: the table as ``_put_table`` lays it out
    (six pairs a 512 B row), 4 B a row of row numbers, four slices."""
    n = 55_050_240
    assert resident_fit_bytes(n, (19,), ()) == 4_697_628_672 + 4 * n + 4 * FEED_SLICE_BYTES
    assert resident_fit_bytes(6, (19,), ()) == 512 + 24 + 4 * FEED_SLICE_BYTES
    assert resident_fit_bytes(2 * n, (19,), ()) - resident_fit_bytes(n, (19,), ()) == pytest.approx(85.33 * n + 4 * n, rel=1e-3)


def test_a_round_reckons_its_upload_before_a_byte_is_read(tmp_path):
    training, _ = _trainer(tmp_path, HOSTS[:1])
    host_id = host_id_v2(*HOSTS[0])
    pairs = wire.read_train_pairs(training.storage.download_blocks_path(host_id)).features.shape[0]
    assert training._reckon_round_bytes(host_id) == ROUND_SMALL_FITS_BYTES + resident_fit_bytes(pairs, (19,), ())
    # nothing pending: the two small fits' allowance
    assert training._reckon_round_bytes(host_id_v2("10.9.9.9", "nobody")) == ROUND_SMALL_FITS_BYTES
    # a streamed fit holds two superbatches, not the upload
    training.config.streaming, training.config.streaming_threshold_bytes = True, 1
    assert training._reckon_round_bytes(host_id) == ROUND_SMALL_FITS_BYTES


def test_headers_that_do_not_walk_are_reckoned_by_their_bytes(tmp_path):
    training, _ = _trainer(tmp_path, HOSTS[:1])
    host_id = host_id_v2(*HOSTS[0])
    path = training.storage.download_blocks_path(host_id)
    data = bytearray(path.read_bytes())
    data[20:24] = b"\xff\xff\xff\xff"  # inside the first header
    path.write_bytes(bytes(data))
    want = ROUND_SMALL_FITS_BYTES + resident_fit_bytes(len(data) // 84, (19,), ())
    assert training._reckon_round_bytes(host_id) == want


@pytest.fixture()
def gauges(monkeypatch):
    """Every state the admission's two gauges went through: (rounds
    running, the bytes they were reckoned to hold)."""
    seen, real = [], RoundAdmission._sync_gauges

    def kept(self):
        real(self)
        seen.append((int(M.ROUNDS_RUNNING.value), int(M.ROUNDS_RESERVED_BYTES.value)))

    monkeypatch.setattr(RoundAdmission, "_sync_gauges", kept)
    return seen


def _settled(adm: RoundAdmission) -> bool:
    """Every round is reckoned and whoever could be admitted has been:
    nobody waits, or the first in line has been refused for room."""
    with adm._cond:
        if any(r.reserved_bytes is None for r in adm._running):
            return False
        if not adm._waiting:
            return True
        head = adm._waiting[0]
        if head.reserved_bytes is None or not adm._running or adm.budget is None:
            return False
        return sum(r.reserved_bytes for r in adm._running) + head.reserved_bytes > adm.budget and head.result == "waited"


def _drive(adm: RoundAdmission, sizes: list, returns: list) -> list:
    """Rounds of ``sizes`` arrive in order; each runs until its turn in
    ``returns`` (indices, in the order the rounds are let go), and none
    is let go before the admission has settled on the one before (however
    slowly this machine runs the threads)."""
    done = [threading.Event() for _ in sizes]
    left = [threading.Event() for _ in sizes]
    out: list = [None] * len(sizes)

    def round_(k):
        a = out[k]
        if not a.admitted:
            adm.reckoned(a, sizes[k])
        adm.wait(a)
        if a.reserved_bytes is None:
            adm.reckoned(a, sizes[k])
        done[k].wait(30)
        adm.leave(a)
        left[k].set()

    def settle():
        deadline = time.monotonic() + 30
        while not _settled(adm) and time.monotonic() < deadline:
            time.sleep(0.001)

    threads = []
    for k in range(len(sizes)):
        out[k] = adm.arrive(f"host-{k}")
        threads.append(threading.Thread(target=round_, args=(k,), daemon=True))
        threads[-1].start()
    for k in returns:
        settle()
        done[k].set()
        left[k].wait(30)
    for t in threads:
        t.join(30)
    return out


@pytest.mark.parametrize("repeat", range(20))
def test_a_limit_that_holds_two_admits_two_and_the_third_waits(repeat, gauges):
    """The same cadence is admitted the same way in every run: by the
    sizes, the arrival order and the limit."""
    adm = RoundAdmission(ROUND_RESERVE_BYTES + 250)
    assert adm.budget == 250
    a, b, c = _drive(adm, [100, 100, 100], returns=[0, 1, 2])
    assert [(x.arrival, x.order, x.result) for x in (a, b, c)] == [(0, 0, "at_once"), (1, 1, "at_once"), (2, 2, "waited")]
    assert a.waited_s == b.waited_s == 0.0 and c.waited_s > 0.0
    # never three at once, never over the budget; two stood side by side twice (a with b, b with c)
    assert max(gauges) == (2, 200) and gauges[-1] == (0, 0)
    assert [g for g, before in zip(gauges[1:], gauges) if g == (2, 200) and before != g] == [(2, 200)] * 2


def test_a_limit_that_holds_three_makes_none_wait(gauges):
    adm = RoundAdmission(ROUND_RESERVE_BYTES + 300)
    out = _drive(adm, [100, 100, 100], returns=[2, 1, 0])
    assert [x.result for x in out] == ["at_once"] * 3 and [x.order for x in out] == [0, 1, 2]
    assert max(gauges) == (3, 300)


def test_no_limit_stated_admits_all_at_once():
    out = _drive(RoundAdmission(None), [10**12, 10**12], returns=[0, 1])
    assert [x.result for x in out] == ["at_once", "at_once"]


def test_a_round_alone_is_admitted_whatever_it_holds(gauges):
    adm = RoundAdmission(ROUND_RESERVE_BYTES + 250)
    (a,) = _drive(adm, [10_000], returns=[0])
    assert (a.result, a.admitted) == ("at_once", True)
    # and behind it, the next waits its turn and is then admitted alone too
    a, b = _drive(adm, [10_000, 10_000], returns=[0, 1])
    assert (a.result, b.result) == ("at_once", "waited")
    assert max(gauges) == (1, 10_000)


def test_waiting_rounds_are_admitted_in_arrival_order(gauges):
    """A small round behind a large one does not overtake it: whoever
    stands behind a round that waits for room waits too."""
    adm = RoundAdmission(ROUND_RESERVE_BYTES + 250)
    a, b, c, d = _drive(adm, [200, 200, 40, 40], returns=[0, 1, 2, 3])
    assert [x.order for x in (a, b, c, d)] == [0, 1, 2, 3]
    assert [x.result for x in (a, b, c, d)] == ["at_once", "waited", "waited", "waited"]
    # c fits beside b (240 of 250); d has to see b go
    assert [g for g in gauges if g[0] == 2] == [(2, 240), (2, 80)] and max(n for n, _ in gauges) == 2


def test_the_gauges_follow_the_rounds_running():
    adm = RoundAdmission(None)
    a = adm.arrive("h0")
    adm.reckoned(a, 123)
    assert (M.ROUNDS_RUNNING.value, M.ROUNDS_RESERVED_BYTES.value) == (1, 123)
    b = adm.arrive("h1")
    adm.reckoned(b, 77)
    adm.wait(b)
    assert (M.ROUNDS_RUNNING.value, M.ROUNDS_RESERVED_BYTES.value) == (2, 200)
    assert adm.leave(a) is False and adm.leave(b) is True
    assert (M.ROUNDS_RUNNING.value, M.ROUNDS_RESERVED_BYTES.value) == (0, 0)


# -- the trainer under three schedulers ---------------------------------------


def _counted(result: str) -> float:
    return M.ROUND_ADMISSION_TOTAL.labels(result).value


def test_three_uploads_against_a_limit_that_holds_two(tmp_path, gauges):
    training, service = _trainer(tmp_path)
    training.admission.limit = _holds(training, 2)
    before = {r: _counted(r) for r in ("at_once", "waited")}
    wait0, merge0 = M.PH_ROUND_WAIT.snapshot(), M.PH_MERGE.snapshot()
    outcomes = _cadence(training, service)
    assert all(o.ok and o.gru_error is None for o in outcomes)
    by_arrival = sorted((o.admission for o in outcomes), key=lambda a: a.arrival)
    assert [a.host_id for a in by_arrival] == [host_id_v2(*h) for h in HOSTS]
    assert [(a.order, a.result) for a in by_arrival] == [(0, "at_once"), (1, "at_once"), (2, "waited")]
    one = by_arrival[0].reserved_bytes
    assert [a.reserved_bytes for a in by_arrival] == [one] * 3  # the first by its own leg's walk, the others before they waited
    assert max(gauges) == (2, 2 * one) and 2 * one <= training.admission.budget < 3 * one
    assert {r: _counted(r) - before[r] for r in before} == {"at_once": 2, "waited": 1}
    wait1 = M.PH_ROUND_WAIT.snapshot()
    assert wait1["count"] - wait0["count"] == 3
    assert wait1["total_s"] - wait0["total_s"] == pytest.approx(by_arrival[2].waited_s, abs=0.05)
    # nine versions under the hosts' own ids, and one merged, registered once and last
    manager = training.manager_client
    for ip, hostname in HOSTS:
        assert manager.of(hostname) == ["gnn", "gru", "mlp"]
    assert [(i, h) for i, _, h, _, _ in manager.registered if h == "federated"] == [(federated_model_id_v1(), "federated")]
    assert manager.registered[-1][2] == "federated" and len(manager.registered) == 10
    assert M.PH_MERGE.snapshot()["count"] - merge0["count"] == 1
    # the round's event and the legs' carry the host
    events = flight.snapshot(["trainer"])["trainer"]
    rounds = [e for e in events if e["type"] == "trainer.round"][-3:]
    assert sorted(e["admission"] for e in rounds) == ["at_once", "at_once", "waited"]
    fits = [e for e in events if e["type"] == "trainer.fit"][-9:]
    assert {e["host_id"] for e in fits} == {host_id_v2(*h) for h in HOSTS}


def test_a_limit_that_holds_three_runs_three_side_by_side(tmp_path, gauges):
    training, service = _trainer(tmp_path)
    training.admission.limit = _holds(training, 3)
    outcomes = _cadence(training, service)
    assert all(o.ok for o in outcomes)
    assert [o.admission.result for o in outcomes] == ["at_once"] * 3 and max(n for n, _ in gauges) == 3
    assert all(o.admission.waited_s == 0.0 for o in outcomes)


def test_one_host_runs_as_before_and_merges_nothing(tmp_path, monkeypatch):
    training, service = _trainer(tmp_path, HOSTS[:1])
    service.synchronous = True
    want = training._reckon_round_bytes(host_id_v2(*HOSTS[0]))
    walks, real_walk = [], wire.walk_train_pairs
    monkeypatch.setattr(wire, "walk_train_pairs", lambda *a, **kw: walks.append(a) or real_walk(*a, **kw))
    reckoned, real_reckoned = [], training.admission.reckoned
    monkeypatch.setattr(training.admission, "reckoned", lambda a, nbytes: reckoned.append(nbytes) or real_reckoned(a, nbytes))
    wait0 = M.PH_ROUND_WAIT.snapshot()
    merge0 = M.PH_MERGE.snapshot()["count"]
    for _ in range(2):
        assert service.fit_after_stream(*HOSTS[0]) is None  # inline: the round has returned
        _stage(training.storage, host_id_v2(*HOSTS[0]), seed=11)
    manager = training.manager_client
    assert [t for _, t, _, _, _ in manager.registered].count("mlp") == 2 and len(manager.registered) == 6
    assert {h for _, _, h, _, _ in manager.registered} == {HOSTS[0][1]}
    assert M.PH_MERGE.snapshot()["count"] == merge0
    wait1 = M.PH_ROUND_WAIT.snapshot()
    assert wait1["count"] - wait0["count"] == 2 and wait1["total_s"] - wait0["total_s"] < 0.01
    # a round admitted alone is reckoned by its MLP leg, from the one walk the load makes anyway
    assert len(walks) == 2 and reckoned == [want, want]


@pytest.mark.parametrize("path", ["binary", "csv", "streamed", "no-upload"])
def test_a_round_alone_is_reckoned_whatever_path_its_mlp_leg_takes(tmp_path, path):
    """Whoever arrives while it runs finds it reckoned: by the walk's
    pairs, by the CSV's bytes, at the allowance where the fit is
    streamed, and at the allowance by the round's own thread where the
    leg ended before it knew its pairs."""
    from dragonfly2_tpu.schema.columnar import write_csv

    training, _ = _trainer(tmp_path, HOSTS[:1] if path in ("binary", "streamed") else ())
    host_id = host_id_v2(*HOSTS[0])
    if path == "csv":
        write_csv(tmp_path / "part.csv", synth.make_download_records(200, seed=5))
        training.storage.append_download(host_id, (tmp_path / "part.csv").read_bytes())
    if path == "streamed":
        training.config.streaming, training.config.streaming_threshold_bytes = True, 1
    want = training._reckon_round_bytes(host_id)
    outcome = training.train(*HOSTS[0])
    assert outcome.admission.reserved_bytes == want
    assert (want == ROUND_SMALL_FITS_BYTES) == (path in ("streamed", "no-upload"))
    assert (outcome.mlp_error is None) == (path != "no-upload")


def test_the_handlers_fork_marks_the_round_and_hands_back_the_thread(tmp_path):
    training, service = _trainer(tmp_path, HOSTS[:1])
    host_id = host_id_v2(*HOSTS[0])
    path = training.storage.download_blocks_path(host_id)
    size = path.stat().st_size
    assert not training.storage.rounds.has(path.name)
    thread = service.fit_after_stream(*HOSTS[0])
    assert training.storage.rounds.get(path.name) == size or not path.exists()  # marked before the fork
    assert thread.name == "trainer.fit" and thread.daemon
    thread.join(120)
    assert training.manager_client.of(HOSTS[0][1]) == ["gnn", "gru", "mlp"] and service.train_failure_total == 0


def test_two_rounds_side_by_side_keep_their_own_account(tmp_path, gauges):
    """Two hosts' legs enter the same ``Phase`` objects at once: each
    round's splits count its own entries, its legs' seconds lie inside
    its own wall, and neither wall holds the other's."""
    training, service = _trainer(tmp_path, HOSTS[:2], records=1536)
    alone = _cadence(training, service, HOSTS[:1])[0]
    _stage(training.storage, host_id_v2(*HOSTS[0]), seed=11, records=1536)  # the other host's still stands
    a, b = _cadence(training, service, HOSTS[:2])
    assert max(n for n, _ in gauges) == 2  # side by side
    for o in (a, b):
        assert set(o.splits) == set(LEGS)
        for leg in LEGS:
            split, want = o.splits[leg], alone.splits[leg]
            assert split.phase_n == want.phase_n  # entries: a round's own, not the two rounds' sum
            assert 0 < split.wall_s <= o.wall_s + 0.01
            assert sum(split.phase_s.values()) <= split.wall_s + 1e-3
        assert o.wall_s >= max(s.wall_s for s in o.splits.values()) - 0.01
    # the ledger holds both rounds' entries: twice a split's
    assert a.splits["mlp"].blocks_decoded == b.splits["mlp"].blocks_decoded == alone.splits["mlp"].blocks_decoded


def test_one_hosts_corrupt_block_fails_that_round_alone(tmp_path, caplog):
    training, service = _trainer(tmp_path)
    training.admission.limit = _holds(training, 2)
    bad = host_id_v2(*HOSTS[1])
    path = training.storage.download_blocks_path(bad)
    data = bytearray(path.read_bytes())
    data[-9] ^= 0xFF  # a payload byte of the last block: its CRC no longer holds
    path.write_bytes(bytes(data))
    with caplog.at_level("WARNING"):
        outcomes = _cadence(training, service)
    by_host = {o.admission.host_id: o for o in outcomes}
    assert by_host[bad].mlp_error and not by_host[bad].ok
    assert path.exists()  # an upload that was not fitted is not cleared
    manager = training.manager_client
    assert "mlp" not in manager.of(HOSTS[1][1])
    for ip, hostname in (HOSTS[0], HOSTS[2]):
        assert by_host[host_id_v2(ip, hostname)].ok and manager.of(hostname) == ["gnn", "gru", "mlp"]
    # the merge goes on without it, and names it
    merged = [r for r in manager.registered if r[2] == "federated"]
    assert len(merged) == 1 and merged[0][4]["hosts"] == 2.0
    assert any(bad in r.getMessage() for r in caplog.records if "merge without" in r.getMessage())
    assert [o.admission.result for o in sorted(outcomes, key=lambda o: o.admission.arrival)] == ["at_once", "at_once", "waited"]


def test_a_round_that_raises_leaves_the_line(tmp_path, monkeypatch):
    """Whatever ends a round, the next in line is admitted."""
    training, service = _trainer(tmp_path, HOSTS[:2])
    training.admission.limit = _holds(training, 1)
    real = Training._round

    def broken(self, host_id, *args):
        if host_id == host_id_v2(*HOSTS[0]):
            self.admission.reckoned(args[-1], self._reckon_round_bytes(host_id))
            time.sleep(0.2)
            raise RuntimeError("the round broke")
        return real(self, host_id, *args)

    monkeypatch.setattr(Training, "_round", broken)
    outcomes = _cadence(training, service, HOSTS[:2])
    assert len(outcomes) == 1 and outcomes[0].ok and outcomes[0].admission.result == "waited"
    assert service.train_failure_total == 1
    assert training.admission._running == [] and not training.admission._waiting


# -- with one scheduler the round is what it was (ISSUE 45 (9)(i)) -----------


def _leaves_equal(a, b) -> bool:
    import jax
    import numpy as np

    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def test_a_lone_schedulers_round_is_the_parents_round(tmp_path, monkeypatch, gauges):
    """One scheduler's round through ``Training.train`` (arrival,
    admission, the round, the return, the cadence's end) against the same
    upload through the round's body alone (``_round``: the parent's
    ``train``) and against the parent's MLP leg restated here (the
    upload's pairs read whole, ``train_mlp`` handed no order: it draws
    its own on entry, and nobody takes a holdout row before it): the fit
    is handed arrays equal bit for bit, an equal order and an equal
    configuration, and the three versions registered are equal; nothing
    waited, nothing merged, and the gauges are back at 0."""
    import numpy as np

    import dragonfly2_tpu.trainer.training as training_mod
    from dragonfly2_tpu.trainer.train import _permutation, _split_eval, train_mlp

    training, service = _trainer(tmp_path, HOSTS[:1])
    service.synchronous = True
    ip, hostname = HOSTS[0]
    host_id = host_id_v2(ip, hostname)
    handed, real = [], training_mod.train_mlp

    def spied(features, labels, mesh=None, config=None, order=None):
        train_rows, held_rows = order.split()
        # an epoch taken here is drawn again for the fit, from the same seed (``FitOrder._start``)
        handed.append({
            "x": features.copy(), "y": labels.copy(), "mesh": mesh, "config": config, "for": order._drawn_for,
            "split": (train_rows.copy(), held_rows.copy()), "epoch": order.epoch(0).copy(),
        })
        return real(features, labels, mesh=mesh, config=config, order=order)

    monkeypatch.setattr(training_mod, "train_mlp", spied)
    wait0, merge0 = M.PH_ROUND_WAIT.snapshot(), M.PH_MERGE.snapshot()["count"]
    at_once0 = _counted("at_once")

    # the parent's MLP leg, restated: read before a round clears the upload
    pairs = wire.read_train_pairs(training.storage.download_blocks_path(host_id))
    cfg = training._fit_config(training.config.mlp, "mlp", host_id)
    restated = train_mlp(pairs.features, pairs.labels, mesh=training.mesh, config=cfg)

    outcome = training.train(ip, hostname)  # as TrainerService and every accepted generator call it
    manager = training.manager_client
    through_train, manager.registered = {t: p for _, t, _, p, _ in manager.registered}, []
    assert outcome.ok and outcome.gru_error is None and sorted(through_train) == sorted(LEGS)
    assert (outcome.admission.result, outcome.admission.waited_s, outcome.admission.order) == ("at_once", 0.0, 0)
    # it waited for nothing, merged nothing, and left the chip to nobody
    wait1 = M.PH_ROUND_WAIT.snapshot()
    assert wait1["count"] - wait0["count"] == 1 and wait1["total_s"] - wait0["total_s"] < 0.005  # 0 s: passed through
    assert _counted("at_once") - at_once0 == 1 and M.PH_MERGE.snapshot()["count"] == merge0
    assert not [h for _, _, h, _, _ in manager.registered if h == "federated"]
    assert max(n for n, _ in gauges) == 1 and gauges[-1] == (0, 0)
    assert (M.ROUNDS_RUNNING.value, M.ROUNDS_RESERVED_BYTES.value) == (0, 0)

    # the same upload through the round's body alone: no arrival, no admission, no return
    _stage(training.storage, host_id, seed=11)
    service.storage.mark_download_round(host_id)
    body = training._round(host_id, ip, hostname, training_mod.Admission())
    through_body = {t: p for _, t, _, p, _ in manager.registered}
    assert body.ok and sorted(through_body) == sorted(LEGS)

    a, b = handed
    for got in (a, b):
        assert np.array_equal(got["x"], pairs.features) and np.array_equal(got["y"], pairs.labels)
        assert got["x"].dtype == pairs.features.dtype and got["y"].dtype == pairs.labels.dtype
        assert got["config"] == cfg and got["mesh"] is training.mesh
        assert got["for"] == (pairs.features.shape[0], cfg.eval_fraction, cfg.seed, cfg.epochs)
    assert all(np.array_equal(x, y) for x, y in zip(a["split"], b["split"])) and np.array_equal(a["epoch"], b["epoch"])
    # the order handed in is the one a fit handed none draws for itself
    want_split = _split_eval(pairs.features.shape[0], cfg.eval_fraction, cfg.seed)
    assert all(np.array_equal(x, y) for x, y in zip(a["split"], want_split))
    assert np.array_equal(a["epoch"], _permutation(np.random.default_rng(cfg.seed + 1), len(want_split[0])))
    for leg in LEGS:
        assert _leaves_equal(through_train[leg], through_body[leg]), leg
    assert _leaves_equal(through_train["mlp"], restated.params)
    assert outcome.mlp_metrics == body.mlp_metrics == restated.metrics
