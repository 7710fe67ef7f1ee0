"""Bounded slices (ISSUE 28, ISSUE 29): a fit puts its columns on the chip
once, a slice at a time, runs its epochs as slices of row numbers into
that table and frees its host arrays a slice at a time, and what it
computes is what one gather on the host, one put and one scan over the
whole epoch compute.

A slice is as many steps as take ``FEED_SLICE_BYTES`` from the table and
at most ``EPOCH_SLICE_STEPS`` (trainer/train.py). With the step bound
above an epoch's step count the epoch is one slice: one put, one scan:
the fit as it was (a loop over the epoch's steps in one dispatch). The
tests hold the sliced fit to that one, bit for bit, the batches a step
takes on the chip to the host gather they replace, element for element,
and the MLP fit to the numbers the host-gathered fit gave.
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
    entered once a slice (and once more for the table, which is one)."""
    whole, one = _run(leg, monkeypatch, 10**9)
    sliced, many = _run(leg, monkeypatch, slice_steps)
    slices = -(-10 // slice_steps)
    assert one == {"feed_slice": 1 + 2, "epoch_slice": 2}  # the table; two epochs, one slice each
    assert many == {"feed_slice": 1 + 2 * slices, "epoch_slice": 2 * slices}
    got, want = jax.tree_util.tree_leaves(sliced.params), jax.tree_util.tree_leaves(whole.params)
    assert len(got) == len(want) > 0
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert sliced.history == pytest.approx(whole.history, rel=1e-6)
    assert sliced.metrics == whole.metrics


def _columns(leg: str) -> tuple:
    """A leg's columns as its fit hands them to the table: 700 rows."""
    rng = np.random.default_rng(5)
    if leg == "mlp":
        return rng.normal(size=(700, 19)).astype(np.float32), rng.normal(size=700).astype(np.float32)
    if leg == "gru":
        return (
            rng.normal(size=(700, 9, 2)).astype(np.float32),
            rng.normal(size=700).astype(np.float32),
            rng.integers(1, 10, size=700).astype(np.int32),
        )
    return (
        rng.integers(0, 1 << 30, size=700).astype(np.int32),
        rng.integers(-5, 5, size=700).astype(np.int32),
        rng.normal(size=700).astype(np.float32),
    )


def _steps_taken(epoch) -> list:
    """Per column, the ``[steps, batch, ...]`` array of the batches the
    epoch's steps take from its table, taken on the device."""
    take = jax.jit(lambda table, rows: table.take(rows))
    batches = [take(epoch.table, rows[i]) for rows in epoch.rows for i in range(rows.shape[0])]
    assert all(isinstance(a, jax.Array) for b in batches for a in b)
    return [np.stack([np.asarray(a) for a in column]) for column in zip(*batches)]


@pytest.mark.parametrize("slice_steps, slice_bytes", [(4, 200 * 80), (10**9, 1 << 30)], ids=["last-slice-padded", "one-slice"])
@pytest.mark.parametrize("leg", LEGS)
def test_a_step_takes_from_the_table_what_the_host_gathered(leg, slice_steps, slice_bytes, monkeypatch):
    """Ten steps of 63 rows over the 630 a holdout leaves of 700: step
    ``i``'s batch, taken on the chip by the epoch's row numbers, is
    ``column[index[perm]]`` of the host gather the table replaced, for
    every column of every leg, element for element (bits: a ``-0.0``
    and a NaN's payload pass through the table untouched)."""
    monkeypatch.setattr(train_mod, "EPOCH_SLICE_STEPS", slice_steps)
    monkeypatch.setattr(train_mod, "FEED_SLICE_BYTES", slice_bytes)  # 200 * 80: a table of several slices, the last padded
    columns = _columns(leg)
    floats = next(c for c in columns if c.dtype == np.float32).reshape(-1)
    floats[:2] = np.array([0x80000000, 0x7FC12345], np.uint32).view(np.float32)
    index, _ = train_mod._split_eval(700, 0.1, 0)
    table = train_mod._put_table(None, PHASES[leg], *columns)
    host = train_mod._gather_slices(index, lambda: train_mod._permutation(np.random.default_rng(7), 630), 10, 63, table.row_bytes)
    epoch = train_mod._feed_slices(None, host, table, 10, PHASES[leg])
    assert len(epoch) == len(columns) and epoch.steps == 10 and epoch.table is table
    k = train_mod._slice_steps(10, 63 * table.row_bytes)
    assert {rows.shape for rows in epoch.rows} == {(k, 63)} and (k * len(epoch.rows) > 10) == (slice_steps == 4)
    order = index[np.random.default_rng(7).permutation(630)]
    for got, column in zip(_steps_taken(epoch), columns):
        want = column[order].reshape(10, 63, *column.shape[1:])
        assert got.dtype == want.dtype and got[:10].tobytes() == want.tobytes()
        assert got[10:].tobytes() == np.broadcast_to(column[0], got[10:].shape).tobytes()  # padding: row 0


@pytest.mark.parametrize("slice_bytes, slices", [(1 << 30, 1), (3 * 7 * 36, 4), (7 * 36, 10), (1, 10)])
def test_the_sliced_feed_hands_the_epoch_what_the_single_put_did(slice_bytes, slices, monkeypatch):
    """Ten steps of 7 rows, 36 B a row over two columns: the epoch's row
    numbers, joined and cut to its steps, are ``order`` reshaped to
    ``[steps, batch]``, on the device, and the rows they name in the
    table are ``column[order]``; every slice has the same shape (the
    last padded with row 0); a slice is composed when the feed asks for
    it, and handed over before the one before it is waited for: the
    gather and the feed are entered once, the slice phase once a slice,
    and once a slice of the table before."""
    monkeypatch.setattr(train_mod, "FEED_SLICE_BYTES", slice_bytes)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(90, 2, 4)).astype(np.float32)
    y = np.arange(90, dtype=np.float32)
    index = rng.permutation(90)[:80]
    order = index[np.random.default_rng(7).permutation(80)][:70]
    before = {k: getattr(M.PH_MLP, k).snapshot()["count"] for k in ("gather", "feed", "feed_slice")}
    table = train_mod._put_table(None, M.PH_MLP, x, y)
    assert table.row_bytes == 36 and isinstance(table.packed, jax.Array) and table.packed.shape[1] == 128
    table_slices = M.PH_MLP.feed_slice.snapshot()["count"] - before["feed_slice"]
    assert table_slices == -(-90 // (max(slice_bytes // 36 // 14, 1) * 14))  # 14 rows of 9 words share 128 lanes
    assert table.packed.shape[0] == table_slices * -(-90 // (table_slices * 14))  # spread evenly, in whole table rows
    drawn = lambda: train_mod._permutation(np.random.default_rng(7), 80)  # noqa: E731
    host = train_mod._gather_slices(index, drawn, 10, 7, table.row_bytes)
    shapes = {part.shape for part in train_mod._gather_slices(index, drawn, 10, 7, 36)}
    assert len(shapes) == 1 and iter(host) is host  # one shape; nothing composed until it is asked for
    epoch = train_mod._feed_slices(None, host, table, 10, M.PH_MLP)
    entered = {k: getattr(M.PH_MLP, k).snapshot()["count"] - n for k, n in before.items()}
    assert entered == {"gather": 1, "feed": 1, "feed_slice": table_slices + slices} and epoch.steps == 10
    assert next(host, None) is None
    assert all(isinstance(a, jax.Array) and a.dtype == np.int32 for a in epoch.rows) and len(epoch.rows) == slices
    rows = np.concatenate([np.asarray(a) for a in epoch.rows])
    assert np.array_equal(rows[:10], order.reshape(10, 7)) and not rows[10:].any()
    got_x, got_y = _steps_taken(epoch)
    assert np.array_equal(got_x[:10], x[order].reshape(10, 7, 2, 4))
    assert np.array_equal(got_y[:10], y[order].reshape(10, 7))


@pytest.mark.parametrize("what", ["the-table", "the-row-numbers"])
def test_the_feed_keeps_two_puts_in_flight_and_no_more(what, monkeypatch):
    """Slice ``i`` is handed to the device before slice ``i - 1`` is
    waited for, and slice ``i + 1`` is not cut (of the table) or
    composed (of the row numbers) until it has been."""
    events = []
    real_put, real_wait = train_mod._on_mesh, jax.block_until_ready

    def put(mesh, a, *spec):
        events.append(("put", int(np.asarray(a).reshape(-1)[0])))
        return real_put(mesh, a, *spec)

    def wait(tree):
        if isinstance(tree, list):  # the slices before; not the table, which is waited for whole at the end
            events.extend(("wait", int(a.reshape(-1)[0])) for a in tree)
        return real_wait(tree)

    monkeypatch.setattr(train_mod, "FEED_SLICE_BYTES", 7 * 36)  # a step a slice; 7 rows a slice of the table
    x = np.repeat(np.arange(4, dtype=np.float32), 7 * 8).reshape(28, 2, 4)  # rows 7i to 7i+6 hold i
    table = train_mod._put_table(None, M.PH_MLP, x, x[:, 0, 0].copy())
    monkeypatch.setattr(train_mod, "_on_mesh", put)
    monkeypatch.setattr(train_mod.jax, "block_until_ready", wait)
    if what == "the-table":
        train_mod._put_table(None, M.PH_MLP, x)  # one column: 8 words a row, 16 rows of the 28 a slice
        assert events[:3] == [("put", 0), ("put", 2), ("wait", 0)] and ("wait", 2) not in events
        monkeypatch.setattr(train_mod, "FEED_SLICE_BYTES", 1)  # a table row a slice at the least: 32 rows of 4 words
        events.clear()
        train_mod._put_table(None, M.PH_MLP, np.arange(256, dtype=np.float32).reshape(64, 4))
        assert events == [("put", 0), ("put", 128), ("wait", 0)]
        return
    host = train_mod._gather_slices(7 * np.arange(4).repeat(7), lambda: np.arange(28), 4, 7, table.row_bytes)  # step i names row 7i
    train_mod._feed_slices(None, host, table, 4, M.PH_MLP)
    want = [("put", 0), ("put", 7), ("wait", 0), ("put", 14), ("wait", 7), ("put", 21), ("wait", 14)]
    assert events[:7] == want and ("wait", 21) in events[7:]


def _upload() -> tuple:
    """The seeded toy upload whose fit ``PARENT`` records."""
    rng = np.random.default_rng(29)
    x = rng.normal(size=(3000, 19)).astype(np.float32)
    return x, (np.tanh(x @ rng.normal(size=19) * 0.3) + 0.1 * rng.normal(size=3000)).astype(np.float32)


# train_mlp(*_upload(), config=PARENT_CONFIG) at commit 6940c29, where the
# host gathered every epoch's columns and the holdout's: per leaf of the
# parameters its sum and the sum of its magnitudes, in tree order
PARENT_CONFIG = dict(hidden_dims=(16, 16), batch_size=128, epochs=3, seed=4)
PARENT = {
    "history": [1.4442835308256603, 0.5796286719185966, 0.4545823505946568],
    "metrics": {"mse": 0.4499519467353821, "mae": 0.5558449029922485},
    "leaf_sums": [0.054802218452095985, 3.6622743748594075, -0.31726776575669646, -8.095840080088237,
                  0.050456833094358444, -1.266561065800488],
    "leaf_abs_sums": [0.8106217663735151, 71.40319141442887, 0.6554542020894587, 66.85680947310175,
                      0.050456833094358444, 3.3712052730843425],
}


@pytest.mark.parametrize("slice_bytes", [64 << 20, 500 * 80], ids=["one-slice", "six-slices"])
def test_train_mlp_registers_what_the_host_gathered_fit_did(slice_bytes, monkeypatch):
    """History, parameters and the holdout's error of a three-epoch fit
    equal the parent's to float32 round-off, however the table and the
    epochs are cut; the table went to the chip once: the put counter
    rose by its bytes, three epochs of row numbers and the holdout's."""
    monkeypatch.setattr(train_mod, "FEED_SLICE_BYTES", slice_bytes)
    put = M.FIT_PUT_BYTES_TOTAL.labels("mlp")
    before = put.value
    x, y = _upload()
    keep = x.copy(), y.copy()
    result = train_mod.train_mlp(x, y, config=FitConfig(**PARENT_CONFIG))
    assert np.array_equal(x, keep[0]) and np.array_equal(y, keep[1])  # the caller's arrays are left as they were
    assert result.history == pytest.approx(PARENT["history"], rel=1e-5)
    assert result.metrics == pytest.approx(PARENT["metrics"], rel=1e-5)
    leaves = [np.asarray(a, np.float64) for a in jax.tree_util.tree_leaves(result.params)]
    assert [float(a.sum()) for a in leaves] == pytest.approx(PARENT["leaf_sums"], rel=1e-4, abs=1e-5)
    assert [float(np.abs(a).sum()) for a in leaves] == pytest.approx(PARENT["leaf_abs_sums"], rel=1e-5)
    table_slices = -(-3000 // (slice_bytes // 80 // 6 * 6))  # six pairs of 20 words share a table row
    table = table_slices * -(-3000 // (table_slices * 6)) * 6 * 80  # the last slice padded to the others' rows
    k = train_mod._slice_steps(21, 128 * 80)  # 2,700 training rows: 21 steps of 128
    epoch = -(-21 // k) * k * 128 * 4
    per = min(max(slice_bytes // 80, 1), 300)
    holdout = -(-300 // per) * per * 4
    assert put.value - before == table + 3 * epoch + holdout


def test_a_table_row_wider_than_a_tile_row_takes_whole_rows_of_its_own():
    rng = np.random.default_rng(2)
    wide, tag = rng.normal(size=(50, 3, 50)).astype(np.float32), np.arange(50, dtype=np.int32)
    table = train_mod._put_table(None, M.PH_GRU, wide, tag)
    assert table.packed.shape == (50, 256) and table.row_bytes == 151 * 4
    got_wide, got_tag = jax.jit(lambda t, r: t.take(r))(table, np.array([49, 0, 7, 7], np.int32))
    assert np.array_equal(np.asarray(got_wide), wide[[49, 0, 7, 7]]) and np.asarray(got_tag).tolist() == [49, 0, 7, 7]


@pytest.mark.parametrize("dtype", [np.float16, np.int8, np.bool_])
def test_a_table_refuses_a_column_that_is_not_32_bits(dtype):
    with pytest.raises(TypeError, match="32-bit"):
        train_mod._put_table(None, M.PH_MLP, np.zeros((4, 3), np.float32), np.zeros(4, dtype))


def test_a_table_takes_64_bit_columns_as_jax_does():
    """Without x64 a 64-bit column goes to the chip as 32 bits, a slice
    at a time, as ``jnp.asarray`` of the whole would."""
    labels, ids = np.linspace(0, 1, 40), np.arange(40) * 3
    table = train_mod._put_table(None, M.PH_MLP, labels, ids)
    got = jax.jit(lambda t, r: t.take(r))(table, np.arange(40, dtype=np.int32))
    assert np.array_equal(np.asarray(got[0]), labels.astype(np.float32)) and got[0].dtype == np.float32
    assert np.array_equal(np.asarray(got[1]), ids) and got[1].dtype == np.int32


@pytest.mark.parametrize("n", [0, 1, 7, 1000, 40_000])
def test_the_sliced_identity_shuffles_to_numpys_permutation(n, monkeypatch):
    monkeypatch.setattr(train_mod, "FEED_SLICE_BYTES", 8 * 1024)  # 1,024 indices a slice
    for seed in (0, 1, 5):
        want = np.random.default_rng(seed).permutation(n)
        got = train_mod._permutation(np.random.default_rng(seed), n)
        assert got.dtype == want.dtype and np.array_equal(got, want)


# a fit's order by leg: its phases, its epochs, whether one generator is carried from epoch to epoch
ORDERS = {
    "mlp-1-epoch": (M.PH_MLP, 1, False),
    "mlp-3-epochs": (M.PH_MLP, 3, False),
    "gnn": (M.PH_GNN, 4, False),
    "gru-carried": (M.PH_GRU, 3, True),
}


def _order_as_the_fits_drew_it(n: int, eval_fraction: float, seed: int, epochs: int, carried: bool):
    """The holdout's split and every epoch's permutation as the fits
    drew them inline: numpy's own ``permutation``, ``default_rng(seed)``
    for the split, ``default_rng(seed + 1 + epoch)`` an epoch, or the
    GRU's one ``default_rng(seed + 1)`` for all its epochs in turn."""
    perm = np.random.default_rng(seed).permutation(n)
    n_eval = int(n * eval_fraction)
    carry = np.random.default_rng(seed + 1)
    rngs = [carry if carried else np.random.default_rng(seed + 1 + e) for e in range(epochs)]
    return perm[n_eval:], perm[:n_eval], [rng.permutation(n - n_eval) for rng in rngs]


@pytest.mark.parametrize("eval_fraction", [0.0, 0.1])
@pytest.mark.parametrize("n", [1, 8192, 70_001], ids=["one-row", "a-batch", "70001-rows"])
@pytest.mark.parametrize("leg", sorted(ORDERS))
def test_the_order_drawn_ahead_is_the_order_the_fits_drew(leg, n, eval_fraction):
    """Element for element, for every leg's rule of generators; and the
    ``order`` phase was entered once a permutation, on a thread that is
    not this one (this thread's split holds none of it)."""
    from dragonfly2_tpu.utils import profiling

    phases, epochs, carried = ORDERS[leg]
    cfg = FitConfig(eval_fraction=eval_fraction, seed=11, epochs=epochs)
    want_train, want_eval, want_epochs = _order_as_the_fits_drew_it(n, eval_fraction, 11, epochs, carried)
    before = phases.order.snapshot()["count"]
    with profiling.split() as mine, train_mod.FitOrder(phases, n, cfg, carried=carried) as order:
        train_idx, eval_idx = order.split()
        assert train_idx.dtype == want_train.dtype and np.array_equal(train_idx, want_train)
        assert np.array_equal(eval_idx, want_eval) and len(eval_idx) == int(n * eval_fraction)
        for epoch, want in enumerate(want_epochs):
            got = order.epoch(epoch)
            assert got.dtype == want.dtype and np.array_equal(got, want)
    assert phases.order.snapshot()["count"] - before == 1 + epochs
    assert phases.order.name not in mine


def test_an_order_asked_for_a_later_epoch_first_draws_that_one():
    """A fit resumed from a checkpoint starts at a later epoch: it gets
    that epoch's own generator, and the next is begun behind it."""
    cfg = FitConfig(eval_fraction=0.1, seed=3, epochs=4)
    with train_mod.FitOrder(M.PH_MLP, 1000, cfg) as order:
        for epoch in (2, 3):
            assert np.array_equal(order.epoch(epoch), np.random.default_rng(3 + 1 + epoch).permutation(900))


def _order_threads() -> list:
    import threading

    return [t for t in threading.enumerate() if t.name.startswith(M.PH_MLP.order.name)]


def _no_order_thread_is_left() -> bool:
    for t in _order_threads():
        t.join(timeout=30)
    return not _order_threads()


@pytest.mark.parametrize("epochs", [1, 3])
def test_train_mlp_fits_the_same_handed_its_order_or_drawing_it_on_entry(epochs):
    """An order begun before the call (as the round begins it, from the
    pair count) and one begun on entry: parameters, history and metrics
    bit for bit; either way no drawing thread outlives the fit."""
    x, y = _upload()
    cfg = FitConfig(**{**PARENT_CONFIG, "epochs": epochs})
    ahead = train_mod.FitOrder(M.PH_MLP, len(x), cfg)
    ahead.split()  # drawn before the fit is called at all
    handed = train_mod.train_mlp(x, y, config=cfg, order=ahead)
    on_entry = train_mod.train_mlp(x, y, config=cfg)
    assert handed.history == on_entry.history and len(handed.history) == epochs
    assert handed.metrics == on_entry.metrics and set(handed.metrics) == {"mse", "mae"}
    got, want = jax.tree_util.tree_leaves(handed.params), jax.tree_util.tree_leaves(on_entry.params)
    assert len(got) == len(want) > 0
    for a, b in zip(got, want):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert _no_order_thread_is_left()


def test_train_mlp_sets_aside_an_order_drawn_for_another_fit():
    """Row numbers past the table are clamped on the chip, not refused:
    an order for another row count or config (a caller that fits a part
    of what it was handed) is ended and the fit draws its own."""
    x, y = _upload()
    cfg = FitConfig(**{**PARENT_CONFIG, "epochs": 1})
    want = train_mod.train_mlp(x, y, config=cfg)
    for other in (train_mod.FitOrder(M.PH_MLP, len(x) + 1, cfg), train_mod.FitOrder(M.PH_MLP, len(x), FitConfig(seed=5))):
        got = train_mod.train_mlp(x, y, config=cfg, order=other)
        assert got.history == want.history and got.metrics == want.metrics
        assert other._split is None and not other._ahead  # ended: nothing drawn is referenced from it
    assert _no_order_thread_is_left()


def test_a_slice_holds_both_bounds():
    assert train_mod._slice_steps(10, 1) == 10  # an epoch under both bounds is one slice
    assert train_mod._slice_steps(1024, 1) == 512
    assert train_mod._slice_steps(1100, 1) == 367  # three slices, spread evenly: one step of padding, not 436
    per_slice = train_mod._slice_steps(6047, 8192 * 80)  # what the MLP's step takes from the table at its published widths
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


@pytest.mark.parametrize("leg", LEGS)
def test_a_leg_traces_its_epoch_slice_once_a_fit(leg, monkeypatch):
    """Two epochs of three slices each: the fit asks the backend for one
    executable, the slice's (its jit wrapper is the fit's own, so one it
    is, not none). The head's bias is float32 in its own right: weakly
    typed, it had the slice traced again for the second epoch and for
    the third."""
    _run(leg, monkeypatch, 4)  # everything but the fit's own wrapper is compiled now
    before = M.JIT_RECOMPILES_TOTAL.value
    fit, args, cfg = _fit(leg)
    cfg.epochs = 4
    fit(*args, config=cfg)
    assert M.JIT_RECOMPILES_TOTAL.value - before == 1


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
