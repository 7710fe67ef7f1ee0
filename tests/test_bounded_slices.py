"""Bounded slices (ISSUE 28): a fit feeds the chip, runs its epochs and
frees its host arrays a slice at a time, and what it computes is what one
put and one scan over the whole epoch compute.

A slice is as many steps as fit ``FEED_SLICE_BYTES`` and at most
``EPOCH_SLICE_STEPS`` (trainer/train.py). With the step bound above an
epoch's step count the epoch is one slice: one put, one scan: the fit as
it was (a loop over the epoch's steps in one dispatch). The tests hold the
sliced fit to that one, bit for bit.
"""

import jax
import numpy as np
import pytest

from dragonfly2_tpu.schema import synth, wire
from dragonfly2_tpu.schema.columnar import records_to_columns
from dragonfly2_tpu.schema.features import build_probe_graph, extract_pair_features
from dragonfly2_tpu.trainer import metrics as M
from dragonfly2_tpu.trainer import train as train_mod
from dragonfly2_tpu.trainer.train import FitConfig, GNNFitConfig

LEGS = ("mlp", "gnn", "gru")
PHASES = {"mlp": M.PH_MLP, "gnn": M.PH_GNN, "gru": M.PH_GRU}


def _fit(leg: str):
    """(fit, its arguments, a config of ``steps`` steps an epoch) for a leg."""
    rng = np.random.default_rng(3)
    if leg == "mlp":
        x = rng.normal(size=(700, 5)).astype(np.float32)
        return train_mod.train_mlp, (x, (x @ rng.normal(size=5)).astype(np.float32)), FitConfig(
            hidden_dims=(8,), batch_size=63, epochs=2  # 630 training rows: 10 steps
        )
    if leg == "gru":
        s = rng.normal(size=(700, 6, 2)).astype(np.float32)
        return train_mod.train_gru, (s, s[:, 0, 0].copy()), FitConfig(hidden_dims=(8,), batch_size=63, epochs=2)
    cols = records_to_columns(synth.make_topology_records(400, num_hosts=24, seed=1))
    graph = build_probe_graph(cols, max_degree=8)
    batch = int(len(graph.edge_src) * 0.9) // 10
    return train_mod.train_gnn, (graph,), GNNFitConfig(hidden_dims=(8, 8), batch_size=batch, epochs=2)


def _run(leg: str, monkeypatch, slice_steps: int):
    monkeypatch.setattr(train_mod, "EPOCH_SLICE_STEPS", slice_steps)
    fit, args, cfg = _fit(leg)
    before = {k: getattr(PHASES[leg], k).snapshot()["count"] for k in ("feed_slice", "epoch_slice")}
    result = fit(*args, config=cfg)
    entered = {k: getattr(PHASES[leg], k).snapshot()["count"] - n for k, n in before.items()}
    return result, entered


@pytest.mark.parametrize("slice_steps", [5, 4, 1], ids=["divides", "remainder", "a-step-a-slice"])
@pytest.mark.parametrize("leg", LEGS)
def test_a_sliced_epoch_registers_what_one_scan_registers(leg, slice_steps, monkeypatch):
    """Ten steps an epoch, in slices of 5 (two dispatches), 4 (three, the
    last of 2) and 1: the parameters are the one-scan epoch's bit for bit,
    the epoch's mean loss the same to 1e-6, and the two slice phases were
    entered once a slice."""
    whole, one = _run(leg, monkeypatch, 10**9)
    sliced, many = _run(leg, monkeypatch, slice_steps)
    slices = -(-10 // slice_steps)
    assert one == {"feed_slice": 2, "epoch_slice": 2}  # two epochs, one slice each
    assert many == {"feed_slice": 2 * slices, "epoch_slice": 2 * slices}
    got, want = jax.tree_util.tree_leaves(sliced.params), jax.tree_util.tree_leaves(whole.params)
    assert len(got) == len(want) > 0
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert sliced.history == pytest.approx(whole.history, rel=1e-6)
    assert sliced.metrics == whole.metrics


@pytest.mark.parametrize("slice_bytes, slices", [(1 << 30, 1), (3 * 7 * 36, 4), (7 * 36, 10), (1, 10)])
def test_the_sliced_feed_hands_the_epoch_what_the_single_put_did(slice_bytes, slices, monkeypatch):
    """Ten steps of 7 rows, 36 B a row over two columns: the slices,
    joined and cut to the epoch's steps, are ``column[order]`` reshaped
    to ``[steps, batch, ...]``, on the device; every slice has the same
    shape (the last padded with zero rows); a slice is gathered when the
    feed asks for it, and handed over before the one before it is waited
    for: the gather and the feed are entered once, the slice phase once
    a slice."""
    monkeypatch.setattr(train_mod, "FEED_SLICE_BYTES", slice_bytes)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(90, 2, 4)).astype(np.float32)
    y = np.arange(90, dtype=np.float32)
    index = rng.permutation(90)[:80]
    order = index[np.random.default_rng(7).permutation(80)][:70]
    host = train_mod._gather_slices(index, np.random.default_rng(7), 10, 7, x, y)
    shapes = {part[0].shape for part in train_mod._gather_slices(index, np.random.default_rng(7), 10, 7, x, y)}
    assert len(shapes) == 1 and iter(host) is host  # one shape; nothing gathered until it is asked for
    before = {k: getattr(M.PH_MLP, k).snapshot()["count"] for k in ("gather", "feed", "feed_slice")}
    epoch = train_mod._feed_slices(None, host, 10, M.PH_MLP)
    xs, ys = epoch
    entered = {k: getattr(M.PH_MLP, k).snapshot()["count"] - n for k, n in before.items()}
    assert entered == {"gather": 1, "feed": 1, "feed_slice": slices} and epoch.steps == 10
    assert next(host, None) is None
    assert all(isinstance(a, jax.Array) for a in xs + ys) and len(xs) == len(ys) == slices
    got_x, got_y = (np.concatenate([np.asarray(a) for a in col]) for col in (xs, ys))
    assert np.array_equal(got_x[:10], x[order].reshape(10, 7, 2, 4)) and not got_x[10:].any()
    assert np.array_equal(got_y[:10], y[order].reshape(10, 7)) and not got_y[10:].any()


def test_the_feed_keeps_two_puts_in_flight_and_no_more(monkeypatch):
    """Slice ``i`` is handed to the device before slice ``i - 1`` is
    waited for, and slice ``i + 1`` is not gathered until it has been."""
    events = []
    real_put, real_wait = train_mod._shard_arrays, jax.block_until_ready

    def put(mesh, *arrays):
        events.append(("put", int(arrays[1][0, 0])))
        return real_put(mesh, *arrays)

    def wait(tree):
        events.extend(("wait", int(a[1][0, 0])) for a in tree if isinstance(a, tuple))
        return real_wait(tree)

    monkeypatch.setattr(train_mod, "FEED_SLICE_BYTES", 7 * 36)  # a step a slice
    monkeypatch.setattr(train_mod, "_shard_arrays", put)
    monkeypatch.setattr(train_mod.jax, "block_until_ready", wait)
    monkeypatch.setattr(train_mod, "_permutation", lambda rng, n: np.arange(n))
    x = np.zeros((28, 2, 4), np.float32)
    y = np.repeat(np.arange(4, dtype=np.float32), 7)  # step i's labels are all i
    train_mod._feed_slices(None, train_mod._gather_slices(np.arange(28), None, 4, 7, x, y), 4, M.PH_MLP)
    assert events[:7] == [("put", 0), ("put", 1), ("wait", 0), ("put", 2), ("wait", 1), ("put", 3), ("wait", 2)]
    assert ("wait", 3) in events[7:]


@pytest.mark.parametrize("n", [0, 1, 7, 1000, 40_000])
def test_the_sliced_identity_shuffles_to_numpys_permutation(n, monkeypatch):
    monkeypatch.setattr(train_mod, "FEED_SLICE_BYTES", 8 * 1024)  # 1,024 indices a slice
    for seed in (0, 1, 5):
        want = np.random.default_rng(seed).permutation(n)
        got = train_mod._permutation(np.random.default_rng(seed), n)
        assert got.dtype == want.dtype and np.array_equal(got, want)


def test_a_slice_holds_both_bounds():
    assert train_mod._slice_steps(10, 1) == 10  # an epoch under both bounds is one slice
    assert train_mod._slice_steps(1024, 1) == 512
    assert train_mod._slice_steps(1100, 1) == 367  # three slices, spread evenly: one step of padding, not 436
    per_slice = train_mod._slice_steps(6047, 8192 * 80)  # the MLP's step at its published widths
    most = train_mod.FEED_SLICE_BYTES // (8192 * 80)
    assert per_slice == 101 and most == 102 and -(-6047 // per_slice) == -(-6047 // most) == 60
    assert train_mod._slice_steps(3, 10**12) == 1  # a step over the byte bound goes alone


@pytest.mark.parametrize("leg", LEGS)
def test_a_leg_compiles_one_epoch_executable_whatever_its_step_count(leg, monkeypatch):
    """Ten steps in slices of 4: the padded last slice runs on the
    executable of the others (its step count is a traced argument)."""
    from hack.dfanalyze import jitwitness

    _run(leg, monkeypatch, 10**9)  # everything else the fit jits is compiled now
    with jitwitness.compile_tap() as whole:
        _run(leg, monkeypatch, 10**9)
    with jitwitness.compile_tap() as sliced:
        _run(leg, monkeypatch, 4)
    assert sliced.count <= whole.count + 1  # the slice's shape is new once; its remainder is not


@pytest.mark.parametrize("holder", ["sole", "shared", "view", "small"])
def test_release_in_pieces_frees_only_what_it_alone_holds(holder, monkeypatch):
    """An array the caller alone holds is shrunk away a slice a call and
    dropped; one that anyone else references, or a view, is refused by
    ``resize`` and left whole to its last holder."""
    monkeypatch.setattr(train_mod, "FEED_SLICE_BYTES", 4 * 19 * 100)  # 100 rows a slice
    a = np.arange(1050 * 19, dtype=np.float32).reshape(1050, 19).copy()
    want = a.copy()
    if holder == "sole":
        owned, kept = [a, np.ones(7, np.float32)], None
    elif holder == "shared":
        owned, kept = [a], a
    elif holder == "view":
        owned, kept = [a[:1000]], a
    else:
        owned, kept = [a[:50].copy()], None  # under a slice: nothing to shrink
    del a
    released = train_mod.release_in_pieces(owned)
    assert owned == []
    assert released == {"sole": 2, "shared": 0, "view": 0, "small": 1}[holder]
    if kept is not None:
        assert kept.shape == (1050, 19) and np.array_equal(kept, want)


def test_release_in_pieces_outlasts_a_thread_that_walks_every_frame():
    """The sampling profiler's sweep (``sys._current_frames()``) holds the
    releasing frame for an instant, and ``resize`` then counts a holder
    too many: a refusal is tried again before the array is given up."""
    import sys
    import threading

    stop = threading.Event()

    def sweep():
        while not stop.is_set():
            for frame in sys._current_frames().values():
                while frame is not None:
                    frame = frame.f_back

    walker = threading.Thread(target=sweep, daemon=True)
    walker.start()
    try:
        released = sum(
            train_mod.release_in_pieces([np.ones((40_000, 19), np.float32)]) for _ in range(50)
        )
    finally:
        stop.set()
        walker.join()
    assert released == 50


def test_resize_gives_the_tail_back_in_place():
    """What ``release_in_pieces`` rests on: shrinking the array a fit
    alone holds keeps its head where it was (no copy of what is left)."""
    a = np.ones((200_000, 19), np.float32)
    at = a.__array_interface__["data"][0]
    a.resize((100_000, 19), refcheck=False)  # the reference check has tests of its own, above
    assert a.__array_interface__["data"][0] == at and a.shape == (100_000, 19) and float(a[-1, -1]) == 1.0


def _old_index(path) -> np.ndarray:
    """``download_index`` as ``read_train_pairs`` built it before: one
    concatenate, then one ``np.repeat`` of the bases over the upload."""
    idx, bases, records = [], [], 0
    for header, cols in wire.iter_blocks(path, columns=("pairs.download_index",)):
        idx.append(np.array(cols["pairs.download_index"]))
        bases.append(records)
        records += int(header.get("records", header["rows"]))
    out = np.concatenate(idx)
    out += np.repeat(np.asarray(bases, np.int32), [len(i) for i in idx])
    return out


@pytest.mark.parametrize("blocks", [[10], [10, 10], [3, 17, 1, 9], [1] * 12])
def test_read_train_pairs_fills_the_index_a_block_at_a_time(blocks, tmp_path):
    recs = synth.make_download_records(sum(blocks), seed=21)
    p = tmp_path / "d.dfb"
    at, buf = 0, b""
    for n in blocks:
        buf += wire.encode_train_block(recs[at : at + n])
        at += n
    p.write_bytes(buf)
    got = wire.read_train_pairs(p)
    direct = extract_pair_features(records_to_columns(recs))
    want = _old_index(p)
    assert got.download_index.dtype == want.dtype and got.download_index.flags.owndata
    np.testing.assert_array_equal(got.download_index, want)
    np.testing.assert_array_equal(got.download_index, direct.download_index)
    np.testing.assert_array_equal(got.features, direct.features)
    np.testing.assert_array_equal(got.labels, direct.labels)
    assert got.features.flags.owndata and got.labels.flags.owndata  # what release_in_pieces can shrink
    assert got.num_downloads == sum(blocks)
