"""Native ingestion decoder vs the numpy reference path.

The Python pipeline (schema/features.py) is the semantic spec; the C++
decoder (native/dfnative.cc) must produce elementwise-identical tensors,
including across embedded header lines (every trainer upload round
re-sends a CSV header, reference trainer/service demux) and quoted CSV
fields.
"""

import os
import time

import numpy as np
import pytest

from dragonfly2_tpu.schema import native
from dragonfly2_tpu.schema.columnar import records_to_columns, write_csv
from dragonfly2_tpu.schema.features import build_probe_graph, extract_pair_features
from dragonfly2_tpu.schema.synth import make_download_records, make_topology_records

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library unavailable (no toolchain)"
)


def _concat_uploads(path, *rec_lists, tmp_path):
    """Build a trainer dataset file the way the Train stream does: each
    upload round is a complete CSV (with its own header line) appended
    byte-wise, so the result contains embedded headers."""
    with open(path, "wb") as out:
        for i, recs in enumerate(rec_lists):
            part = tmp_path / f"part{i}.csv"
            write_csv(part, recs)
            out.write(part.read_bytes())


@pytest.fixture
def download_csv(tmp_path):
    """Two appended upload rounds — the second re-sends its header."""
    recs1 = make_download_records(60, seed=1)
    recs2 = make_download_records(40, seed=2)
    path = tmp_path / "download_h.csv"
    _concat_uploads(path, recs1, recs2, tmp_path=tmp_path)
    assert path.read_bytes().count(b"id,tag,application") == 2  # embedded header
    return path, recs1 + recs2


def test_pairs_match_python_path(download_csv):
    path, recs = download_csv
    got = native.decode_pairs_file(path)
    want = extract_pair_features(records_to_columns(recs))
    assert got.features.shape == want.features.shape
    np.testing.assert_array_equal(got.download_index, want.download_index)
    np.testing.assert_allclose(got.features, want.features, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(got.labels, want.labels, rtol=1e-6, atol=1e-7)


def test_pairs_quoted_fields(tmp_path):
    """Location strings with commas/quotes survive RFC4180 round-trip."""
    recs = make_download_records(5, seed=3)
    recs[0].host.network.location = 'dc|rack,1|"edge"'
    recs[0].parents[0].host.network.location = 'dc|rack,1|"edge"'
    path = tmp_path / "dl.csv"
    write_csv(path, recs)
    got = native.decode_pairs_file(path)
    want = extract_pair_features(records_to_columns(recs))
    np.testing.assert_allclose(got.features, want.features, rtol=1e-6, atol=1e-7)


def test_pairs_missing_file(tmp_path):
    assert native.decode_pairs_file(tmp_path / "nope.csv") is None


def test_pairs_quoted_newline(tmp_path):
    """A newline inside a quoted field is data, not a record break."""
    recs = make_download_records(6, seed=9)
    recs[0].host.network.location = "dc|row\nrack|x"
    recs[2].parents[0].host.network.location = "a\nb"
    path = tmp_path / "dl.csv"
    write_csv(path, recs)
    got = native.decode_pairs_file(path)
    want = extract_pair_features(records_to_columns(recs))
    assert got.num_downloads == want.num_downloads == 6
    np.testing.assert_array_equal(got.download_index, want.download_index)
    np.testing.assert_allclose(got.features, want.features, rtol=1e-6, atol=1e-7)


def test_min_record_gates_apply_on_native_path(tmp_path):
    """min_download_records applies even when the native decoder is used."""
    from dragonfly2_tpu.trainer.storage import TrainerStorage
    from dragonfly2_tpu.trainer.training import Training, TrainingConfig

    storage = TrainerStorage(tmp_path / "store")
    recs = make_download_records(3, seed=11)
    src = tmp_path / "src.csv"
    write_csv(src, recs)
    storage.append_download("h", src.read_bytes())
    training = Training(storage, config=TrainingConfig(min_download_records=100))
    with pytest.raises(ValueError, match="< min 100"):
        training._train_mlp("h", "ip", "host")


def test_topology_match_python_path(tmp_path):
    t1 = make_topology_records(80, num_hosts=24, seed=3)
    t2 = make_topology_records(50, num_hosts=24, seed=4)
    path = tmp_path / "topo.csv"
    _concat_uploads(path, t1, t2, tmp_path=tmp_path)
    got = native.build_probe_graph_file(path, max_degree=8, seed=0)
    want = build_probe_graph(records_to_columns(t1 + t2), max_degree=8, seed=0)
    assert got.node_ids == want.node_ids
    np.testing.assert_array_equal(got.edge_src, want.edge_src)
    np.testing.assert_array_equal(got.edge_dst, want.edge_dst)
    np.testing.assert_allclose(got.edge_rtt_log_ms, want.edge_rtt_log_ms, rtol=1e-6)
    np.testing.assert_allclose(got.node_features, want.node_features, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(got.neighbors, want.neighbors)
    np.testing.assert_array_equal(got.neighbor_mask, want.neighbor_mask)


def test_chunked_feed_boundary(tmp_path):
    """Chunk boundaries mid-line must not corrupt rows: feed byte-by-byte
    tiny chunks and compare."""
    recs = make_download_records(8, seed=5)
    path = tmp_path / "dl.csv"
    write_csv(path, recs)
    lib = native.load()
    data = path.read_bytes()
    handle = lib.df_pairs_new()
    try:
        for i in range(0, len(data), 97):  # prime-sized chunks split lines
            chunk = data[i : i + 97]
            lib.df_pairs_feed(handle, chunk, len(chunk))
        lib.df_pairs_finish(handle)
        m = lib.df_pairs_count(handle)
    finally:
        lib.df_pairs_free(handle)
    want = extract_pair_features(records_to_columns(recs))
    assert m == want.features.shape[0]


def test_training_uses_native(tmp_path, monkeypatch):
    """Training._train_mlp goes through the native decoder when present."""
    from dragonfly2_tpu.trainer.storage import TrainerStorage
    from dragonfly2_tpu.trainer.training import Training, TrainingConfig
    from dragonfly2_tpu.trainer.train import FitConfig

    storage = TrainerStorage(tmp_path)
    recs = make_download_records(50, seed=7)
    csv_path = tmp_path / "dl_src.csv"
    write_csv(csv_path, recs)
    storage.append_download("ip_host", csv_path.read_bytes())

    called = {}
    orig = native.decode_pairs_file

    def spy(path, offset=0, end=None):
        called["path"] = str(path)
        return orig(path, offset=offset, end=end)

    monkeypatch.setattr(native, "decode_pairs_file", spy)
    training = Training(
        storage,
        config=TrainingConfig(mlp=FitConfig(epochs=1, batch_size=256)),
    )
    metrics = training._train_mlp("ip_host", "ip", "host")
    assert "mse" in metrics
    assert called["path"].endswith("download_ip_host.csv")


def test_topo_empty_src_id_matches_python(tmp_path):
    """A topology row with an empty host.id still interns the src node —
    the numpy path does, and node indices must stay aligned."""
    import numpy as np

    import dragonfly2_tpu.schema.native as N
    from dragonfly2_tpu.schema.columnar import records_to_columns, write_csv
    from dragonfly2_tpu.schema.features import build_probe_graph
    from dragonfly2_tpu.schema.records import NetworkTopologyRecord
    from dragonfly2_tpu.schema.synth import make_topology_records

    if not N.available():
        import pytest

        pytest.skip("native unavailable")
    recs = make_topology_records(8, num_hosts=6, seed=0)
    hollow = NetworkTopologyRecord(host=recs[0].host, dest_hosts=recs[0].dest_hosts)
    hollow.host.id = ""
    recs.append(hollow)
    p = tmp_path / "topo.csv"
    write_csv(p, recs)
    want = build_probe_graph(records_to_columns(recs), max_degree=4)
    got = N.build_probe_graph_file(p, max_degree=4)
    assert got is not None
    assert got.num_nodes == want.num_nodes
    assert got.node_ids == want.node_ids
    np.testing.assert_array_equal(got.edge_src, want.edge_src)
    np.testing.assert_array_equal(got.edge_dst, want.edge_dst)


def test_f16_nan_preserved():
    """The half-precision emit keeps NaN as NaN on every build path —
    never inf (a 'nan' CSV stat must stay detectable)."""
    import math

    import numpy as np

    import dragonfly2_tpu.schema.native as N
    from dragonfly2_tpu.schema.columnar import write_csv
    from dragonfly2_tpu.schema.synth import make_download_records

    if not N.available():
        import pytest

        pytest.skip("native unavailable")
    import tempfile

    recs = make_download_records(3, seed=0)
    recs[1].host.cpu.percent = float("nan")
    with tempfile.TemporaryDirectory() as d:
        p = d + "/r.csv"
        write_csv(p, recs)
        feats = labels = None
        for f, l, _ in N.stream_pairs_file(p, half=True):
            feats = f if feats is None else np.concatenate([feats, f])
        assert feats is not None
        # the NaN flows into at least one f16 feature as NaN, not inf
        assert np.isnan(feats).any() or not np.isinf(feats).any()


# ---------------------------------------------------------------------------
# df_crc32_blocks: a span of blocks checked in one call that holds no
# interpreter lock (schema/wire.py TrainPairsWalk.assemble)
# ---------------------------------------------------------------------------

_MIB = 1 << 20


def _stated(buf, pieces):
    """``pieces`` of ``buf`` as the walk notes blocks: first byte (here
    the piece's number), payload's first byte, its length, and zlib's
    CRC-32 of it."""
    import zlib

    return np.array(
        [(i, start, n, zlib.crc32(buf[start : start + n])) for i, (start, n) in enumerate(pieces)], np.int64
    ).reshape(-1, 4)


@pytest.mark.parametrize(
    "length",
    [0, 1, 2, 7, 8, 9, 15, 16, 17, 63, 64, 65, 79, 80, 127, 128, 129, 255, 1023, 4096, 4097, 65_535, _MIB + 13, 5 * _MIB + 1],
)
def test_crc32_blocks_is_zlibs_crc32_at_every_length_and_alignment(length):
    """The routine against ``zlib.crc32`` over the same bytes: lengths
    around every stride it takes (8 bytes a table step, 16 a fold, 64 a
    round of four lanes) up to several MiB, at every alignment of the
    first byte within 16; a stated CRC one bit off is a mismatch."""
    lib = native.load()
    buf = np.random.default_rng(length).integers(0, 256, length + 16, dtype=np.uint8)
    table = _stated(buf, [(shift, length) for shift in range(16)])
    assert lib.df_crc32_blocks(buf.ctypes.data, table, len(table)) == -1
    for shift in (0, 5, 15):
        off = table[shift : shift + 1].copy()
        off[0, 3] ^= 1 << (shift * 2)
        assert lib.df_crc32_blocks(buf.ctypes.data, off, 1) == 0


def test_crc32_blocks_random_pieces_and_the_first_mismatch():
    """Pieces of random lengths at random places, overlapping or not,
    in one call: -1 when every one is what is stated, else the index of
    the first that is not, whatever comes after it."""
    lib = native.load()
    rng = np.random.default_rng(39)
    buf = rng.integers(0, 256, 3 * _MIB, dtype=np.uint8)
    lengths = rng.integers(0, 200_000, 64)
    pieces = [(int(rng.integers(0, len(buf) - n + 1)), int(n)) for n in lengths]
    table = _stated(buf, pieces)
    assert lib.df_crc32_blocks(buf.ctypes.data, table, len(table)) == -1
    assert lib.df_crc32_blocks(buf.ctypes.data, table, 0) == -1
    for wrong in ([0], [63], [17, 40], [40, 17, 63]):
        bad = table.copy()
        bad[wrong, 3] ^= 0x8000_0001
        assert lib.df_crc32_blocks(buf.ctypes.data, bad, len(bad)) == min(wrong)
    # a stated CRC that no 32 bits hold matches nothing, as in the per-block comparison
    wide = table.copy()
    wide[5, 3] += 1 << 32
    assert lib.df_crc32_blocks(buf.ctypes.data, wide, len(wide)) == 5


def test_crc32_blocks_holds_no_interpreter_lock():
    """A Python thread makes progress while another is inside one call
    of the check. The interpreter's forced hand-over is set far beyond
    the test (a thread that holds the lock keeps it until it gives it
    up), so the counting thread can only count while the checking
    thread is inside a call that has given the lock up; it offers the
    lock back after every count. No reading of a clock: a call that
    held the lock would leave the count where it was, however long."""
    import sys
    import threading

    lib = native.load()
    buf = np.random.default_rng(1).integers(0, 256, 16 * _MIB, dtype=np.uint8)
    table = np.repeat(_stated(buf, [(0, len(buf))]), 8, axis=0)  # 128 MiB a call
    counted, stop, counting = [0], threading.Event(), threading.Event()

    def count():
        while not stop.is_set():
            counted[0] += 1
            counting.set()
            time.sleep(0)  # the lock, offered

    interval = sys.getswitchinterval()
    counter = threading.Thread(target=count, name="test.counter")
    sys.setswitchinterval(3600.0)
    try:
        counter.start()
        counting.wait()  # a wait gives the lock up: the counter runs its first turns
        moved = []
        for _ in range(20):
            before = counted[0]
            assert lib.df_crc32_blocks(buf.ctypes.data, table, len(table)) == -1
            moved.append(counted[0] - before)
        # and between the calls nobody is handed the lock: a pure-Python stretch moves nothing
        before = counted[0]
        sum(range(200_000))
        held = counted[0] - before
    finally:
        stop.set()
        sys.setswitchinterval(interval)
        counter.join()
    # in some call, at the least: on a machine of one busy core a call can end before the counter is given a turn
    assert sum(moved) > 0 and held == 0, (moved, held)


# ---------------------------------------------------------------------------
# df_walk_blocks: the headers of a range of blocks read in one call that
# holds no interpreter lock (schema/wire.py walk_train_pairs)
# ---------------------------------------------------------------------------


def _train_block(rng, pairs: int, records: int) -> bytes:
    from dragonfly2_tpu.schema import wire
    from dragonfly2_tpu.schema.features import MLP_FEATURE_DIM

    cols = {
        "pairs.features": rng.random((pairs, MLP_FEATURE_DIM), np.float32) + 1,
        "pairs.labels": rng.random(pairs, np.float32) + 1,
        "pairs.download_index": np.arange(1, pairs + 1, dtype=np.int32) % records,
    }
    return wire.encode_block(cols, wire.KIND_TRAIN, records=records, meta={"feature_dim": MLP_FEATURE_DIM})


def _walked(buf: bytes, start: int, end: int, cap=None):
    """``df_walk_blocks`` over ``buf`` as the walk calls it: once for the
    count, once for the rows (``cap`` of them; None: the count) → the
    count, the rows it filled and where it stopped."""
    from dragonfly2_tpu.schema import wire

    lib, stopped = native.load(), np.empty(1, np.int64)
    held = np.frombuffer(buf, np.uint8)
    n = lib.df_walk_blocks(held.ctypes.data, start, end, None, 0, stopped)
    rows = np.full((n if cap is None else cap, wire.WALK_COLUMNS), -7, np.int64)
    filled = lib.df_walk_blocks(held.ctypes.data, start, end, rows.ctypes.data, len(rows), stopped)
    assert (rows[filled:] == -7).all()  # nothing written past the rows it says it filled
    return n, rows[:filled], int(stopped[0])


def test_walk_blocks_rows_are_the_headers_numbers():
    """Ten numbers a block, in the order ``wire.WALK_COLUMNS`` counts:
    against the header ``json.loads`` reads. The walk stops at the
    ``cap``-th row and says where; a count reads no header."""
    import json

    from dragonfly2_tpu.schema import wire

    rng = np.random.default_rng(41)
    blocks = [_train_block(rng, 3 + i, 2 + i % 3) for i in range(6)]
    blocks.insert(2, wire.encode_topology_block(make_topology_records(5, num_hosts=4, seed=1)))
    buf = b"".join(blocks)
    edges = np.cumsum([0] + [len(b) for b in blocks]).tolist()
    n, rows, stopped = _walked(buf, 0, len(buf))
    assert (n, stopped) == (7, -1) and rows.shape == (7, 10)
    for row, block, pos in zip(rows.tolist(), blocks, edges):
        _, header_len, payload_len = wire._PREAMBLE.unpack_from(block)
        header = json.loads(block[16 : 16 + header_len])
        at = {e[0]: e[4] for e in header["cols"]}
        train = header["kind"] == "train"
        assert tuple(row) == (
            pos, pos + 16 + header_len, payload_len, header["crc32"], int(train),
            *((header["rows"], header["records"], *(at[c] for c in wire._PAIR_COLUMNS)) if train else (0, 0, -1, -1, -1)),
        )
    count, some, stopped = _walked(buf, 0, len(buf), cap=3)
    assert (count, stopped) == (7, edges[3]) and some.tobytes() == rows[:3].tobytes()
    # a range from a block's edge to another's; a torn tail ends it; so do fewer bytes than a preamble
    inner = _walked(buf, edges[1], edges[-2])
    assert inner[0] == 5 and inner[1].tobytes() == rows[1:6].tobytes() and inner[2] == -1
    for short in (1, 15, 16, 17, len(blocks[-1]) - 1):
        assert _walked(buf, 0, edges[-2] + short)[::2] == (6, -1)
    assert _walked(buf, 0, 0)[::2] == (0, -1)


def test_walk_blocks_stops_where_there_is_no_magic_or_no_header_it_is_sure_of():
    """The count and the rows end at the same block, and ``stopped_at``
    is that block's first byte: the interpreter's walk goes on from
    there, to raise or to read."""
    rng = np.random.default_rng(42)
    blocks = [_train_block(rng, 4, 3) for _ in range(5)]
    edges = np.cumsum([0] + [len(b) for b in blocks]).tolist()
    buf = bytearray(b"".join(blocks))
    buf[edges[3] + 3] = ord("2")
    n, rows, stopped = _walked(bytes(buf), 0, len(buf))
    assert (n, stopped) == (3, edges[3]) and rows[:, 0].tolist() == edges[:3]
    # with only a preamble's bytes left it is still a bad magic, as in the interpreter's walk; with fewer, a torn tail
    assert _walked(bytes(buf), 0, edges[3] + 16)[::2] == (3, edges[3])
    assert _walked(bytes(buf), 0, edges[3] + 15)[::2] == (3, -1)
    # a header that is no JSON: the count hops it by its preamble, the rows stop at it
    buf = bytearray(b"".join(blocks))
    buf[edges[2] + 16] = ord("[")
    n, rows, stopped = _walked(bytes(buf), 0, len(buf))
    assert (n, len(rows), stopped) == (5, 2, edges[2])


def test_walk_blocks_holds_no_interpreter_lock(tmp_path):
    """A Python thread makes progress while another is inside the
    library's walk of some thousands of blocks: the shape of the check's
    test above. The interpreter's forced hand-over is set far beyond
    the test, so the counting thread counts only while the walking
    thread is inside a call that gave the lock up. Every wait has a
    limit of its own: nothing here can hang the run."""
    import sys
    import threading

    from dragonfly2_tpu.schema import wire

    block = _train_block(np.random.default_rng(43), 4, 3)
    path = tmp_path / "many.dfb"
    path.write_bytes(block * 20_000)
    counted, stop, counting = [0], threading.Event(), threading.Event()

    def count():
        while not stop.is_set():
            counted[0] += 1
            counting.set()
            time.sleep(0)  # the lock, offered

    interval = sys.getswitchinterval()
    counter = threading.Thread(target=count, name="test.counter", daemon=True)
    sys.setswitchinterval(3600.0)
    try:
        counter.start()
        assert counting.wait(timeout=60)
        moved, walked = [], []
        deadline = time.monotonic() + 120
        for _ in range(20):
            before = counted[0]
            walk = wire.walk_train_pairs(path)
            moved.append(counted[0] - before)
            walked.append((len(walk.table), len(walk.views), walk.num_pairs))
            assert time.monotonic() < deadline
        before = counted[0]
        sum(range(200_000))
        held = counted[0] - before
    finally:
        stop.set()
        sys.setswitchinterval(interval)
        counter.join(timeout=60)
    assert not counter.is_alive()
    assert set(walked) == {(20_000, 0, 80_000)}  # the library's, every block of it
    # in some call, at the least: on a machine of one busy core a call can end before the counter is given a turn
    assert sum(moved) > 0 and held == 0, (moved, held)


def test_no_native_is_read_at_every_call(monkeypatch):
    """``DF_NO_NATIVE`` set after the library loaded turns its callers
    to their fallbacks from the next call on, and taking it away turns
    them back: the loaded library is kept."""
    lib = native.load()
    monkeypatch.setenv("DF_NO_NATIVE", "1")
    assert native.load() is None and not native.available()
    monkeypatch.delenv("DF_NO_NATIVE")
    assert native.load() is lib
