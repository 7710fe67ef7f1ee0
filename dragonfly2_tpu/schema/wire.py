"""Columnar train-stream wire format (v1) — the binary payload that
replaces CSV on the announcer → trainer hot path.

Why this exists: with CSV on the stream the fit spent most of every
end-to-end wall waiting on decode — no consumer-side tuning can win
while the payload must be re-parsed per byte on the trainer host. The
structural fix is to move the per-record work to where
the records are born: the scheduler's sink extracts the training tensors
**in batch at block-encode time**, and the trainer's ingest is
``mmap`` + ``np.frombuffer`` + an f16 cast — no parsing at all.

Block layout (integers little-endian; see docs/columnar-wire.md)::

    magic       4 bytes  b"DFB1"
    header_len  u32      byte length of the JSON header
    payload_len u64      byte length of the payload (scanners skip a
                         block without parsing JSON)
    header      JSON     {"kind": ..., "rows": N, "records": N_src,
                          "crc32": crc32(payload), "cols": [...], "meta": {...}}
    payload     bytes    concatenated column buffers, 8-byte aligned

Column encodings (the ``cols`` table, one entry per column):

- ``raw``  — ``[name, dtype, shape, "raw", offset, nbytes]``: the array's
  native little-endian bytes; decode is one ``np.frombuffer`` view.
- ``zero`` — ``[name, dtype, shape, "zero", 0, 0]``: every element is the
  dtype's default (0 / empty string). Fixed-width padding slots (absent
  parents/pieces/dest-hosts) serialize to nothing.
- ``dict`` — ``[name, dtype, shape, "dict", offset, nbytes, uoffset, unbytes]``:
  low-cardinality strings as u32 codes + a ``\\n``-joined unique table
  (idc/location/state columns shrink ~10x and decode by one ``take``).

Block kinds:

- ``train`` — the MLP+GRU payload: precomputed pair features/labels
  (f32, f16-ready: values are bounded ratios/log-scales, so the staging
  cast to float16 is exact to ~5e-4), GRU piece-cost sequences, and the
  source download-record count in the header. Zero-parse on the trainer.
- ``networktopology`` — raw flattened topology record columns (the GNN
  rebuilds its probe graph from whole history; volume is small).

Every block is self-delimiting, so concatenating block files — which is
exactly what the chunked Train-stream upload does on the trainer side —
is always a valid stream. A torn tail (interrupted upload) leaves the
complete prefix decodable.

Negotiation: the trainer advertises ``FORMAT_NAME`` via the Capabilities
RPC; the announcer ships binary only after seeing it and falls back to
CSV for old trainers (UNIMPLEMENTED / missing token). An incompatible
schema change bumps ``FORMAT_NAME`` — old peers then keep training via
CSV instead of mis-decoding.
"""

from __future__ import annotations

import contextlib
import functools
import json
import mmap
import os
import struct
import time
import zlib
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Iterable, Iterator

import numpy as np

MAGIC = b"DFB1"
FORMAT_NAME = "columnar-v1"
CSV_FORMAT_NAME = "csv"

KIND_TRAIN = "train"
KIND_TOPOLOGY = "networktopology"

# records batched into one block by producers (scheduler sink flush,
# bench synthesis): enough to amortize per-block decode overhead (a
# header parse, a CRC call and the column views are paid once a block,
# whatever it holds) without buffering unbounded record objects in
# producer RAM. A format decision (ROADMAP S1): it sets how many
# blocks, so how many of those, a reader of an upload pays for.
BLOCK_RECORDS = 256

_PREAMBLE = struct.Struct("<4sIQ")  # magic, header_len, payload_len
_ALIGN = 8
# dictionary-encode a string column when its unique count is this small
# (u32 codes + the unique table beat N copies of the string)
_DICT_MAX_UNIQUES = 4096


class WireError(ValueError):
    """Malformed block stream (bad magic, truncated header, CRC mismatch,
    or a schema the consumer can't train from)."""


def _align(n: int) -> int:
    return (n + _ALIGN - 1) // _ALIGN * _ALIGN


# ---------------------------------------------------------------------------
# generic column-block encode / decode
# ---------------------------------------------------------------------------


def encode_block(
    cols: dict[str, np.ndarray],
    kind: str,
    records: int | None = None,
    meta: dict | None = None,
) -> bytes:
    """One column batch → one self-delimiting binary block. ``records``
    is the source download/topology record count (defaults to the row
    count) — consumers gate min-record checks on it without decoding."""
    if not cols:
        raise WireError("cannot encode an empty column batch")
    entries: list[list[Any]] = []
    bufs: list[bytes] = []
    offset = 0
    rows = None

    def put(data: bytes) -> int:
        nonlocal offset
        start = _align(offset)
        if start > offset:
            bufs.append(b"\x00" * (start - offset))
        bufs.append(data)
        offset = start + len(data)
        return start

    for name, arr in cols.items():
        arr = np.ascontiguousarray(arr)
        if rows is None:
            rows = int(arr.shape[0]) if arr.ndim else 0
        shape = list(arr.shape)
        dt = arr.dtype
        if not np.any(arr):
            # all-default column (padding slots, unset host stats):
            # nothing on the wire. np.any on <U arrays is True for any
            # non-empty string, so this is exact for strings too.
            entries.append([name, dt.str, shape, "zero", 0, 0])
            continue
        if dt.kind == "U":
            uniques, codes = np.unique(arr.ravel(), return_inverse=True)
            # the unique table is "\n"-joined, so a value CONTAINING a
            # newline (string fields arrive from peers over RPC) would
            # split into extra entries and silently shift every decoded
            # code — such columns fall through to raw encoding instead
            if (
                len(uniques) <= _DICT_MAX_UNIQUES
                and len(uniques) * 4 < arr.size * 3
                and not any("\n" in u for u in uniques.tolist())
            ):
                utable = "\n".join(uniques.tolist()).encode()
                cdata = codes.astype(np.uint32).tobytes()
                coff = put(cdata)
                uoff = put(utable)
                entries.append(
                    [name, dt.str, shape, "dict", coff, len(cdata), uoff, len(utable)]
                )
                continue
        data = arr.tobytes()
        entries.append([name, dt.str, shape, "raw", put(data), len(data)])
    payload = b"".join(bufs)
    header = json.dumps(
        {
            "kind": kind,
            "rows": int(rows or 0),
            "records": int(records if records is not None else (rows or 0)),
            "crc32": zlib.crc32(payload) & 0xFFFFFFFF,
            "cols": entries,
            "meta": meta or {},
        },
        separators=(",", ":"),
    ).encode()
    return _PREAMBLE.pack(MAGIC, len(header), len(payload)) + header + payload


def _parse_preamble(buf, pos: int, total: int) -> tuple[int, int] | None:
    """→ (header_len, payload_len), or None when fewer than a whole
    block's bytes remain (torn tail from an interrupted upload — the
    complete prefix stays usable)."""
    if pos + _PREAMBLE.size > total:
        return None
    magic, header_len, payload_len = _PREAMBLE.unpack_from(buf, pos)
    if magic != MAGIC:
        raise WireError(f"bad block magic at byte {pos}: {bytes(magic)!r}")
    if pos + _PREAMBLE.size + header_len + payload_len > total:
        return None
    return header_len, payload_len


def _decode_col(entry: list, payload: memoryview) -> np.ndarray:
    name, dtype, shape, enc = entry[0], np.dtype(entry[1]), entry[2], entry[3]
    if enc == "zero":
        return np.zeros(shape, dtype=dtype)
    if enc == "dict":
        _, _, _, _, coff, cbytes, uoff, ubytes = entry
        codes = np.frombuffer(payload, np.uint32, count=cbytes // 4, offset=coff)
        uniques = np.array(bytes(payload[uoff : uoff + ubytes]).decode().split("\n"))
        return uniques[codes].reshape(shape).astype(dtype, copy=False)
    if enc == "raw":
        _, _, _, _, off, nbytes = entry
        count = nbytes // dtype.itemsize if dtype.itemsize else 0
        return np.frombuffer(payload, dtype=dtype, count=count, offset=off).reshape(shape)
    raise WireError(f"unknown column encoding {enc!r} for {name!r}")


def _decode_body(buf, pos: int, header_len: int, payload_len: int, verify_crc, columns):
    """Header and columns of the block at ``pos``, its preamble already
    parsed. ``columns`` names the columns to build (None: all of them);
    the CRC covers the whole payload whatever is built."""
    hstart = pos + _PREAMBLE.size
    header = json.loads(bytes(buf[hstart : hstart + header_len]))
    pstart = hstart + header_len
    payload = memoryview(buf)[pstart : pstart + payload_len]
    if verify_crc and zlib.crc32(payload) & 0xFFFFFFFF != header["crc32"]:
        raise WireError(f"block crc mismatch at byte {pos}")
    cols = {
        e[0]: _decode_col(e, payload)
        for e in header["cols"]
        if columns is None or e[0] in columns
    }
    return header, cols


def decode_block(buf, pos: int = 0, verify_crc: bool = True, columns=None):
    """Decode the block at ``pos`` → (header, cols, end_pos). Only the
    columns named in ``columns`` are built (None: every column of the
    block); with ``verify_crc`` the whole payload is checked either
    way. ``raw`` column arrays are zero-copy views into ``buf``
    (read-only when it is an mmap, which a live view keeps mapped)."""
    parsed = _parse_preamble(buf, pos, len(buf))
    if parsed is None:
        raise WireError(f"truncated block at byte {pos}")
    header, cols = _decode_body(buf, pos, *parsed, verify_crc, columns)
    return header, cols, pos + _PREAMBLE.size + sum(parsed)


# ---------------------------------------------------------------------------
# file scanning (header-only — no payload decode)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class BlockSpan:
    start: int
    end: int
    rows: int
    records: int
    kind: str


def _hop_blocks(f, path, offset: int, end: int):
    """ONE definition of the preamble walk: yields
    ``(pos, header_len, payload_len, block_end)`` per complete block in
    ``[offset, end)``. A torn trailing block terminates the walk
    cleanly; garbage at a block boundary raises ``WireError``. The file
    position after each yield sits at the start of the header, so
    consumers that want it may ``f.read(header_len)`` before the next
    hop."""
    pos = offset
    while pos < end:
        f.seek(pos)
        pre = f.read(_PREAMBLE.size)
        if len(pre) < _PREAMBLE.size:
            break
        magic, header_len, payload_len = _PREAMBLE.unpack(pre)
        if magic != MAGIC:
            raise WireError(f"bad block magic at byte {pos} of {path}")
        block_end = pos + _PREAMBLE.size + header_len + payload_len
        if block_end > end:
            break  # torn tail
        yield pos, header_len, payload_len, block_end
        pos = block_end


def _clamped_end(path, end: int | None) -> int:
    size = os.path.getsize(path)
    return size if end is None or end > size else end


def scan_blocks(
    path: str | os.PathLike, offset: int = 0, end: int | None = None
) -> list[BlockSpan]:
    """Block table of ``[offset, end)`` including per-block row/record
    counts (one header JSON parse per block — consumers that only need
    extents use ``scan_block_extents``)."""
    spans: list[BlockSpan] = []
    with open(path, "rb") as f:
        for pos, header_len, _, block_end in _hop_blocks(
            f, path, offset, _clamped_end(path, end)
        ):
            h = json.loads(f.read(header_len))
            spans.append(
                BlockSpan(
                    pos, block_end, int(h["rows"]), int(h.get("records", h["rows"])), h["kind"]
                )
            )
    return spans


def scan_block_extents(
    path: str | os.PathLike, offset: int = 0, end: int | None = None
) -> list[tuple[int, int]]:
    """Block byte extents of ``[offset, end)`` from the fixed preambles
    ALONE — no header JSON is read or parsed, so splitting a
    billion-record stream into spans costs one 16-byte read per block,
    not a JSON parse per block."""
    with open(path, "rb") as f:
        return [
            (pos, block_end)
            for pos, _, _, block_end in _hop_blocks(
                f, path, offset, _clamped_end(path, end)
            )
        ]


def count_records(
    path: str | os.PathLike, offset: int = 0, max_records: int | None = None
) -> int:
    """Source record count from headers alone — the cheap min-record
    pre-gate (no payload bytes are read, and the walk STOPS as soon as
    ``max_records`` is reached instead of scanning the whole file)."""
    n = 0
    with open(path, "rb") as f:
        for _, header_len, _, _ in _hop_blocks(
            f, path, offset, _clamped_end(path, None)
        ):
            h = json.loads(f.read(header_len))
            n += int(h.get("records", h["rows"]))
            if max_records is not None and n >= max_records:
                break
    return n


def is_block_file(path: str | os.PathLike) -> bool:
    """Magic sniff — format detection never trusts file extensions."""
    try:
        with open(path, "rb") as f:
            return f.read(4) == MAGIC
    except OSError:
        return False


def split_block_spans(
    paths: Iterable[tuple[str, int, int] | str | os.PathLike],
    target_span_bytes: int = 8 * 1024 * 1024,
) -> list[tuple[str, int, int]]:
    """Resolve paths (or pre-bounded ``(path, start, end)`` triples) into
    block-aligned spans of ~``target_span_bytes`` for parallel decode —
    the binary analogue of ``native.split_file_spans``, except boundaries
    are exact block edges hopped via the fixed preambles (header-JSON
    free, so startup cost stays one tiny read per block)."""
    out: list[tuple[str, int, int]] = []
    for p in paths:
        path, start, end = p if isinstance(p, tuple) else (str(p), 0, None)
        extents = scan_block_extents(path, start, end)
        if not extents:
            continue
        acc_start = extents[0][0]
        acc = 0
        for b_start, b_end in extents:
            acc += b_end - b_start
            if acc >= target_span_bytes:
                out.append((str(path), acc_start, b_end))
                acc_start, acc = b_end, 0
        if acc:
            out.append((str(path), acc_start, extents[-1][1]))
    return out


@dataclass
class BlockTally:
    """What a reader did with the blocks of its range, added up by the
    caller that hands it in: ``decoded`` blocks had their header parsed,
    their payload CRC-checked (by ``walk_train_pairs``' reader when it
    assembles them, by every other reader as it decodes) and the
    asked-for columns built; ``hopped`` blocks were stepped over by
    their 16-byte preamble and nothing of them was read."""

    decoded: int = 0
    hopped: int = 0


@contextlib.contextmanager
def _mapped(path):
    """The file as one read-only mmap. It is NOT closed eagerly:
    consumers may still hold zero-copy views when the block ends, and
    ``mmap.close()`` raises BufferError while any exported view lives.
    Refcounting reclaims the mapping once the last view dies — the same
    lifetime model as ``np.load(mmap_mode=...)``."""
    with open(path, "rb") as f:
        mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
    try:
        yield mm
    finally:
        try:
            mm.close()
        except BufferError:
            pass  # views still alive; GC closes the mapping later


def _hop_mapped(mm, start: int, end: int):
    """The preamble walk over a mapping: ``(pos, header_len,
    payload_len)`` per complete block in ``[start, end)``, under the
    rules of ``_hop_blocks`` (a torn tail ends it, garbage at a block
    edge raises). It makes no system call, so a thread that hops a
    whole upload does not hand the interpreter lock to another on every
    block, as two calls a block do; the file scanners above keep plain
    reads, which a file truncated under them cannot fault."""
    pos = start
    while pos < end:
        parsed = _parse_preamble(mm, pos, end)
        if parsed is None:
            break  # torn tail
        yield (pos, *parsed)
        pos += _PREAMBLE.size + sum(parsed)


def iter_blocks(
    path: str | os.PathLike,
    start: int = 0,
    end: int | None = None,
    verify_crc: bool = True,
    columns=None,
    tally: BlockTally | None = None,
) -> Iterator[tuple[dict, dict[str, np.ndarray]]]:
    """Yield ``(header, cols)`` per block in ``[start, end)`` via one
    mmap: one preamble parse, one header parse and (with ``verify_crc``)
    one CRC a block, and only the columns named in ``columns`` built
    (None: all). ``raw`` columns are zero-copy views; a consumer that
    keeps one keeps the mapping alive with it."""
    end = _clamped_end(path, end)
    if start >= end:
        return
    with _mapped(path) as mm:
        for pos, header_len, payload_len in _hop_mapped(mm, start, end):
            header, cols = _decode_body(mm, pos, header_len, payload_len, verify_crc, columns)
            if tally is not None:
                tally.decoded += 1
            yield header, cols
            del header, cols  # release this block's views before the next hop


def read_columns(
    path: str | os.PathLike,
    kind: str | None = None,
    offset: int = 0,
    end: int | None = None,
    verify_crc: bool = True,
    tally: BlockTally | None = None,
) -> dict[str, np.ndarray]:
    """Concatenated columns of every block (optionally of one ``kind``)
    — the batch read for fits that want the whole dataset in memory
    (topology graph builds). Every column of every block is built."""
    from dragonfly2_tpu.schema.columnar import concat_columns

    batches = []
    for header, cols in iter_blocks(path, offset, end, verify_crc=verify_crc, tally=tally):
        if kind is None or header["kind"] == kind:
            # copy: the result must outlive the mmap
            batches.append({n: np.array(a) for n, a in cols.items()})
    return concat_columns(batches)


# ---------------------------------------------------------------------------
# train-block builders (scheduler side) and the zero-parse pair stream
# (trainer side)
# ---------------------------------------------------------------------------


def encode_train_block(recs, rtt_lookup=None) -> bytes:
    """Download records → one ``train`` block: pair features/labels for
    the MLP plus piece-cost sequences for the GRU, extracted HERE — in
    batch, on the scheduler, off the trainer's critical path. The
    extraction is the same vectorized code the CSV fallback runs
    trainer-side (schema/features.py); with ``rtt_lookup`` (the
    scheduler's topology engine) the rtt_affinity column carries live
    adjacency estimates the CSV fallback cannot reproduce — binary
    blocks are the production payload precisely because they can join
    scheduler-side state the raw records don't carry."""
    from dragonfly2_tpu.schema.columnar import records_to_columns
    from dragonfly2_tpu.schema.features import (
        MLP_FEATURE_DIM,
        extract_pair_features,
        extract_piece_sequences,
    )

    cols = records_to_columns(recs)
    pairs = extract_pair_features(cols, rtt_lookup=rtt_lookup)
    seqs = extract_piece_sequences(cols)
    out = {
        "pairs.features": pairs.features,
        "pairs.labels": pairs.labels,
        "pairs.download_index": pairs.download_index,
        "gru.sequences": seqs.sequences,
        "gru.labels": seqs.labels,
        "gru.lengths": seqs.lengths,
    }
    return encode_block(
        out, KIND_TRAIN, records=len(recs), meta={"feature_dim": MLP_FEATURE_DIM}
    )


def encode_topology_block(recs) -> bytes:
    """Topology records → one raw-column block (the GNN rebuilds its
    graph from whole history trainer-side; dict/zero encodings keep the
    repeated hostname/ip/idc strings and padding slots cheap)."""
    from dragonfly2_tpu.schema.columnar import records_to_columns

    return encode_block(records_to_columns(recs), KIND_TOPOLOGY, records=len(recs))


# what each consumer of a ``train`` block asks ``iter_blocks`` to build
_PAIR_COLUMNS = ("pairs.features", "pairs.labels", "pairs.download_index")
_GRU_COLUMNS = ("gru.sequences", "gru.labels", "gru.lengths")


def _train_tensors(header: dict, cols: dict[str, np.ndarray]):
    from dragonfly2_tpu.schema.features import MLP_FEATURE_DIM

    fdim = header.get("meta", {}).get("feature_dim")
    if fdim != MLP_FEATURE_DIM:
        raise WireError(
            f"train block feature dim {fdim} != schema {MLP_FEATURE_DIM}"
            " — incompatible peer (negotiation token should have gated this)"
        )
    return cols["pairs.features"], cols["pairs.labels"]


def stream_train_pairs(
    spans,
    passes: int = 1,
    max_records: int | None = None,
    half: bool = False,
    verify_crc: bool = True,
    stage_timer=None,
):
    """Stream ``(feats [m,F], labels [m], cumulative_records)`` shards
    from ``train`` blocks — the binary counterpart of
    ``native.stream_pairs_file``, with no parsing: every shard is one
    frombuffer view plus the staging-dtype cast. ``spans`` are paths or
    block-aligned ``(path, start, end)`` triples (split_block_spans).
    ``stage_timer``, when given, is called as ``stage_timer(stage, dt)``
    with stage ∈ {"read", "cast"} so callers can attribute wall time."""
    import time as _time

    if isinstance(spans, (str, os.PathLike)):
        spans = [spans]
    spans = [s if isinstance(s, tuple) else (str(s), 0, None) for s in spans]
    dt_out = np.float16 if half else np.float32
    total = 0
    for _ in range(max(1, passes)):
        for path, start, end in spans:
            t0 = _time.perf_counter()
            for header, cols in iter_blocks(
                path, start, end, verify_crc=verify_crc, columns=_PAIR_COLUMNS[:2]
            ):
                if header["kind"] != KIND_TRAIN:
                    continue
                feats, labels = _train_tensors(header, cols)
                t1 = _time.perf_counter()
                # the staging cast (f32 → transfer dtype) is the only
                # per-element work left on the consumer host
                feats = np.ascontiguousarray(feats, dtype=dt_out)
                labels = np.ascontiguousarray(labels, dtype=dt_out)
                total += int(header.get("records", header["rows"]))
                t2 = _time.perf_counter()
                if stage_timer is not None:
                    stage_timer("read", t1 - t0)
                    stage_timer("cast", t2 - t1)
                yield feats, labels, total
                if max_records is not None and total >= max_records:
                    return
                t0 = _time.perf_counter()


# The resident read's assembly (``TrainPairsWalk.assemble``): a span is
# this many consecutive blocks of the range, of whatever kind, checked and
# then copied by one worker; a range of more than one span is assembled by
# this many workers at a time. Span-sized units, because a worker takes
# the interpreter lock again after every call that gave it up, and beside
# a scheduler's 16 decision workers waits 0.2 ms each time to have it
# back: with the native library a span is one call for its check and one
# a column for its copies (``df_crc32_blocks``, ``df_gather``), a few
# requests a span; without it ``zlib.crc32`` and numpy's copy give the
# lock up once a block and once an array, four requests a block, 215,040
# a week's upload. Both chosen on the chip's host (13
# cores, no huge pages: a first touch is 3.9 us a 4 KiB page) by
# ``hack/load_spans.py`` over a week's upload (PERF.md §5): the assembly
# takes 7.8 s on one worker, 4.4-4.7 on two, 3.9-4.0 on three, 4.5-4.7 on
# four and 6.3-6.6 on six or eight (the page faults of more threads than
# three get in each other's way); spans of 128, 448 and 896 blocks read
# alike and spans of 32 a tenth slower.
ASSEMBLY_SPAN_BLOCKS = 128
ASSEMBLY_THREADS = 3
# The interpreter's walk in front of it (the walk where the native library
# did not load, and from the first block the library's walk is not sure
# of) is the interpreter's work alone (25-35 us a block: 1.4-1.9 s for a
# week's 53,760 blocks alone on the chip's host, 2.9-5.7 s inside a round)
# and would hold the interpreter lock until another thread's switch
# interval took it, 5 ms at a time in a trainer of its own: the other
# legs' loads stood for all of it. Once this many blocks (0.8 ms) it
# offers the lock. Not once a block: beside 16 decision workers every
# offer costs the walk 0.2 ms of waiting to have it back (once a block
# was measured: 9 s a round, PERF.md §6, PR 35). The library's walk
# (``df_walk_blocks``) holds no lock to offer.
WALK_OFFER_BLOCKS = 32

# A block's row of ``TrainPairsWalk.table``: ten numbers, as
# native/dfnative.cc df_walk_blocks writes them (its WalkColumn, name for
# name) and as the interpreter's walk fills them from the header it parsed
(
    _POS,  # the block's first byte
    _PAYLOAD,  # its payload's first byte, both counted from the mapping's
    _NBYTES,  # the payload's length
    _CRC32,  # what the header states; -1 where that is no 32 bits: it matches no payload
    _TRAIN,  # 1 for a ``train`` block: the rest is 0 and -1 for any other
    _PAIRS,
    _RECORDS,  # the source records the pairs came from: their indices count from 0 in each block
    _FEATURES,  # where each pair column lies in the payload, in a row the
    _LABELS,  # library's walk wrote; -1 in one the interpreter's did, which
    _INDEX,  # built the block's views instead (``TrainPairsWalk.views``)
    WALK_COLUMNS,
) = range(11)


def _stated_crc32(crc) -> int:
    """The ``crc32`` a header states as ``df_crc32_blocks`` takes it: a
    header is JSON and holds anything, and what is no integer of 32 bits
    is -1, which matches no payload."""
    return crc if type(crc) is int and 0 <= crc <= 0xFFFFFFFF else -1


def _gather(lib, parts: list, out: np.ndarray) -> None:
    """``np.concatenate(parts, out=out)`` for a span's column, ``out``
    contiguous: by one call of the native library ``lib`` where that is
    a copy of bytes end to end (every part of ``out``'s type and row
    shape, contiguous, and together of its length), so the lock is asked
    for once a column and not once a part; by numpy itself wherever it
    has more to do (a cast, a stride) or to refuse, and without the
    library."""
    if lib is not None and all(
        p.dtype == out.dtype and p.shape[1:] == out.shape[1:] and p.flags.c_contiguous for p in parts
    ):
        pieces = np.array([(p.ctypes.data, p.nbytes) for p in parts], np.int64).reshape(-1, 2)
        if int(pieces[:, 1].sum()) == out.nbytes:
            lib.df_gather(out.ctypes.data, pieces, len(parts))
            return
    np.concatenate(parts, out=out)


@dataclass
class TrainPairsWalk:
    """What ``walk_train_pairs`` found, before a payload byte is read: a
    row of numbers for every block of the range, whatever its kind, in
    file order (``table``: where the block and its payload lie, the
    ``crc32`` its header states, and of a ``train`` block its counts and
    where its three pair columns lie), and from them by array arithmetic
    the record count, the pair count and each ``train`` block's place in
    the arrays. ``assemble`` checks the blocks and makes the arrays a fit
    is handed; whoever needs only the counts (a fit's order is a function
    of ``num_pairs``: trainer/train.py ``FitOrder``) has them before that.

    A pair column is a view into the mapping (``mapped``; a view keeps
    it open). The library's walk builds none: the assembly copies from
    the addresses the table gives. The interpreter's walk builds a
    block's three as it parses the header (``views``: of the range's last
    ``train`` blocks, from the first block it walked; of all of them
    where the library did not load). ``features``, ``labels``,
    ``download_index`` and ``blocks`` are lists an entry a block for
    whoever reads them, made at the first read: a fit reads none."""

    table: np.ndarray  # [blocks, WALK_COLUMNS] int64
    views: list = field(default_factory=list)  # (features [m, F] float32, labels [m] float32, index [m] int32) a ``train`` block the interpreter walked
    verify_crc: bool = True
    mapped: memoryview | None = None  # the whole mapping: a payload is a slice of it

    def __post_init__(self):
        train = self.table[:, _TRAIN] != 0
        self.trains = np.flatnonzero(train)  # the ``train`` blocks' rows of the table
        # the ``train`` blocks before each block, and before the range's end
        self.trains_before = np.concatenate(([0], np.cumsum(train)))
        records, self.lengths = self.table[self.trains, _RECORDS], self.table[self.trains, _PAIRS]
        self.bases = np.cumsum(records) - records  # records before each ``train`` block: its indices' base
        # pairs before each ``train`` block and before the end: their place in the arrays
        self.pairs_before = np.concatenate(([0], np.cumsum(self.lengths)))
        self.num_downloads, self.num_pairs = int(records.sum()), int(self.pairs_before[-1])

    def train_views(self, lo: int, hi: int) -> list:
        """The three pair columns of the range's ``train`` blocks ``lo``
        to ``hi``: the interpreter's walk's where it held them, else the
        same views made from the block's row."""
        from dragonfly2_tpu.schema.features import MLP_FEATURE_DIM

        first_held = len(self.trains) - len(self.views)
        rows = self.table[self.trains[lo : min(hi, first_held)]]
        made = [
            (
                np.frombuffer(self.mapped, np.float32, m * MLP_FEATURE_DIM, at + f).reshape(m, MLP_FEATURE_DIM),
                np.frombuffer(self.mapped, np.float32, m, at + l),
                np.frombuffer(self.mapped, np.int32, m, at + i),
            )
            for at, m, f, l, i in rows[:, (_PAYLOAD, _PAIRS, _FEATURES, _LABELS, _INDEX)].tolist()
        ]
        return made + self.views[max(lo - first_held, 0) : max(hi - first_held, 0)]

    @functools.cached_property
    def _columns(self) -> tuple:
        return tuple(map(list, zip(*self.train_views(0, len(self.trains))))) or ([], [], [])

    features = property(lambda self: self._columns[0])  # a ``train`` block's [m, F] float32
    labels = property(lambda self: self._columns[1])  # a ``train`` block's [m] float32
    download_index = property(lambda self: self._columns[2])  # a ``train`` block's [m] int32, 0-based within its block's records

    @functools.cached_property
    def blocks(self) -> list:
        """Every block of the range, whatever its kind, in file order:
        its first byte, its payload's, the payload's length, the crc32
        its header states. Once read the list is what ``assemble`` checks
        by, so whoever rewrites an entry is checked by what they wrote."""
        return [tuple(row) for row in self.table[:, : _CRC32 + 1].tolist()]

    def assemble(self, span_phase=None, check_phase=None):
        """The blocks' pairs concatenated → ``PairExamples``, and with
        ``verify_crc`` every block of the range checked against the
        ``crc32`` its header states: exactly once, here, and all of them
        before an array is handed to anyone. A corrupt block anywhere in
        the range, of any kind, raises ``WireError("block crc mismatch
        at byte N")`` for the first such block in file order, and
        nothing is returned.

        The range is cut into spans of ``ASSEMBLY_SPAN_BLOCKS`` blocks.
        A span's worker checks each of its blocks, then copies the
        span's pairs to their place in the three arrays (each pair
        copied once, into the array the caller is handed: the first
        touch and fill of what an upload holds, 4.6 GB for a week's
        pairs). Check and copy run without the interpreter lock, on
        bytes no other span touches, so ``ASSEMBLY_THREADS`` spans run
        side by side; a range of one span runs on the caller's thread:
        one path, narrower or wider by the number of blocks.

        Where the native library loaded (schema/native.py), a span's
        blocks are checked by one call of it, which holds no lock from
        the span's first byte to its last and says which block failed
        first, and each of the span's three columns is copied by one
        call, from the addresses the table gives: a worker asks for the
        lock a few times a span and touches no object of a block's. A
        span that holds a block the interpreter walked copies its blocks'
        views, as ``_gather`` does. Where the library did not load,
        ``zlib.crc32`` is called once a block and ``np.concatenate``
        copies an array at a time, and either gives the lock up and asks
        for it again every time: four times a block. The same CRC-32 over
        the same bytes and the same arrays either way: the per-block path
        is what the library's is held to. ``span_phase``, when given, is
        a context manager that can be entered on several threads at once
        (a profiling phase): the thread that runs a span enters it around
        the span, once a span, and ``check_phase`` the same way around
        the library's check: not at all where the per-block loop ran."""
        from dragonfly2_tpu.schema import native
        from dragonfly2_tpu.schema.features import MLP_FEATURE_DIM, PairExamples

        span_phase = span_phase or contextlib.nullcontext()
        check_phase = check_phase or contextlib.nullcontext()
        n = len(self.table)
        lib = native.load() if n else None
        # the ``train`` blocks the library's walk read: their columns' places are in the table
        placed = len(self.trains) - len(self.views)
        features = np.empty((self.num_pairs, MLP_FEATURE_DIM), np.float32)
        labels = np.empty((self.num_pairs,), np.float32)
        download_index = np.empty((self.num_pairs,), self.views[0][2].dtype if self.views and not placed else np.int32)
        columns = (features, labels, download_index)
        bases, lengths = self.bases.astype(download_index.dtype), self.lengths
        held = self.__dict__.get("blocks")
        if held is None:
            blocks = np.ascontiguousarray(self.table[:, : _CRC32 + 1])
        else:  # a header is JSON and a list holds anything: what no crc32 is matches no payload
            blocks = np.array([(pos, start, nbytes, _stated_crc32(crc)) for pos, start, nbytes, crc in held], np.int64).reshape(-1, 4)
        if lib is not None:
            # the mapping's first byte: a payload's place counts from it
            base = np.frombuffer(self.mapped, np.uint8).ctypes.data
            rows = self.table[self.trains[:placed]]
            # a ``train`` block's piece of each column: its first byte's address and its length
            pieces = [
                np.stack([base + rows[:, _PAYLOAD] + rows[:, c], rows[:, _PAIRS] * out.strides[0]], axis=1)
                for c, out in zip((_FEATURES, _LABELS, _INDEX), columns)
            ]

        def check_span(lo: int, hi: int) -> None:
            if lib is None:
                for pos, start, nbytes, crc in blocks[lo:hi].tolist():
                    if zlib.crc32(self.mapped[start : start + nbytes]) & 0xFFFFFFFF != crc:
                        raise WireError(f"block crc mismatch at byte {pos}")
                return
            with check_phase:
                bad = lib.df_crc32_blocks(base, blocks[lo:hi], hi - lo)
            if bad >= 0:
                raise WireError(f"block crc mismatch at byte {blocks[lo + bad, 0]}")

        def assemble_span(lo: int) -> None:
            with span_phase:
                hi = min(lo + ASSEMBLY_SPAN_BLOCKS, n)
                if self.verify_crc:
                    check_span(lo, hi)
                # the span's ``train`` blocks, and where their pairs go
                t_lo, t_hi = int(self.trains_before[lo]), int(self.trains_before[hi])
                at, end = self.pairs_before[t_lo], self.pairs_before[t_hi]
                if end == at:
                    return
                if lib is not None and t_hi <= placed:
                    for out, piece in zip(columns, pieces):
                        lib.df_gather(out[at:end].ctypes.data, piece[t_lo:t_hi], t_hi - t_lo)
                else:
                    for out, parts in zip(columns, zip(*self.train_views(t_lo, t_hi))):
                        _gather(lib, list(parts), out[at:end])
                # per-block indices are 0-based within their block's record batch —
                # rebase onto the running record count so the concatenated result
                # keeps the documented "row in the source batch" invariant instead
                # of aliasing records across blocks. A span's indices are rebased
                # in the array the caller is handed, by one add over the span: a
                # few calls a span under the interpreter lock, not a pass over
                # the whole upload (``np.repeat`` of every block's base held it
                # 0.2 s at 55M pairs) and not a lock handed over once a block
                index = download_index[at:end]
                np.add(index, np.repeat(bases[t_lo:t_hi], lengths[t_lo:t_hi]), out=index)

        edges = range(0, n, ASSEMBLY_SPAN_BLOCKS)
        if len(edges) <= 1:
            for lo in edges:
                assemble_span(lo)
        else:
            # leaving the block waits for the spans under way: when an
            # error leaves it, no worker writes to the arrays any more
            with ThreadPoolExecutor(ASSEMBLY_THREADS, thread_name_prefix="wire.assemble") as pool:
                spans = [pool.submit(assemble_span, lo) for lo in edges]
                try:
                    for span in spans:  # in file order: the first corrupt block's error is the one raised
                        span.result()
                finally:
                    for span in spans:
                        span.cancel()
        return PairExamples(
            features=features,
            labels=labels,
            download_index=download_index,
            num_downloads=self.num_downloads,
        )


def _walk_interpreted(mm, start: int, end: int, views: list, tally: BlockTally | None) -> list:
    """The interpreter's walk of ``[start, end)``: every header parsed
    and of each ``train`` block the three pair columns built, as views
    into the mapping, appended to ``views`` → the blocks' rows."""
    rows = []
    for pos, header_len, payload_len in _hop_mapped(mm, start, end):
        if len(rows) % WALK_OFFER_BLOCKS == WALK_OFFER_BLOCKS - 1:
            time.sleep(0)  # the interpreter lock, offered to whoever waits for it
        header, cols = _decode_body(mm, pos, header_len, payload_len, False, _PAIR_COLUMNS)
        if tally is not None:
            tally.decoded += 1
        crc = _stated_crc32(header["crc32"])
        payload = pos + _PREAMBLE.size + header_len
        if header["kind"] != KIND_TRAIN:
            rows.append((pos, payload, payload_len, crc, 0, 0, 0, -1, -1, -1))
            continue
        f, l = _train_tensors(header, cols)
        views.append((f, l, cols["pairs.download_index"]))
        rows.append((pos, payload, payload_len, crc, 1, len(l), int(header.get("records", header["rows"])), -1, -1, -1))
    return rows


def walk_train_pairs(
    path: str | os.PathLike,
    offset: int = 0,
    end: int | None = None,
    verify_crc: bool = True,
    tally: BlockTally | None = None,
    native_phase=None,
) -> TrainPairsWalk:
    """One pass over the headers of the blocks of ``[offset, end)`` → a
    row of numbers a block (``TrainPairsWalk.table``). No payload page is
    touched and nothing is copied: when it returns, the counts are known,
    the pairs are still the file's, and no block has been checked yet.
    The walk notes where each block's payload lies and the ``crc32``
    its header states, for every block whatever its kind, and
    ``TrainPairsWalk.assemble`` checks them all (with ``verify_crc``)
    before it hands over an array: that is the round's check of the
    blocks a newest-first reader, ``read_gru_tail``, hops over.

    Where the native library loaded, the range is the library's to walk
    (``df_walk_blocks``, called to count the blocks and then to fill
    their rows), and it holds no interpreter lock from the first header
    to the last: the other legs' loads and a scheduler's decisions have
    the interpreter meanwhile, and no object is made for a block.
    ``native_phase``, when given, is a context manager entered around
    that (a profiling phase). The library reads a header only where it
    is sure of it. At the first block it is not sure of (a pair column
    not ``raw``, as ``encode_block`` writes one that is all zeros;
    another type or shape; a ``crc32`` that is no 32 bits; a key missing
    or stated twice; an escape in a string it would compare; a header it
    cannot finish; no magic at the block's edge) it stops, and the
    interpreter walks the range from that block on, to the same rows or
    the same error.

    Where it did not load, the interpreter walks the whole range: every
    header parsed and of each ``train`` block the three pair columns
    built, as views into the mapping, its work from end to end (25-35 us
    a block), where a walk that also checked gave the interpreter lock up
    at every ``crc32``: it offers the lock once ``WALK_OFFER_BLOCKS``
    blocks. The same table either way but for where a column lies, which
    only the library's rows say: the interpreter's walk is what the
    library's is held to."""
    from dragonfly2_tpu.schema import native

    end = _clamped_end(path, end)
    if offset >= end:
        return TrainPairsWalk(np.zeros((0, WALK_COLUMNS), np.int64), verify_crc=verify_crc)
    lib, views = native.load(), []
    table = np.zeros((0, WALK_COLUMNS), np.int64)
    with _mapped(path) as mm:
        mapped = memoryview(mm)
        if lib is not None:
            base = np.frombuffer(mapped, np.uint8).ctypes.data
            stopped_at = np.empty(1, np.int64)
            with native_phase or contextlib.nullcontext():
                n = lib.df_walk_blocks(base, offset, end, None, 0, stopped_at)  # the range's blocks, by their preambles
                table = np.empty((n, WALK_COLUMNS), np.int64)
                table = table[: lib.df_walk_blocks(base, offset, end, table.ctypes.data, n, stopped_at)]
            if tally is not None:
                tally.decoded += len(table)
            offset = int(stopped_at[0]) if stopped_at[0] >= 0 else end
        if offset < end:
            rows = _walk_interpreted(mm, offset, end, views, tally)
            table = np.concatenate([table, np.array(rows, np.int64).reshape(-1, WALK_COLUMNS)])
    return TrainPairsWalk(table, views, verify_crc, mapped)


def read_train_pairs(
    path: str | os.PathLike,
    offset: int = 0,
    end: int | None = None,
    verify_crc: bool = True,
    tally: BlockTally | None = None,
):
    """Every ``train`` block's pairs, concatenated → ``PairExamples`` —
    the batch read for small datasets (below the streaming threshold),
    resident fits and federation shards: the walk over the headers,
    then the assembly, which with ``verify_crc`` checks every block of
    the range once and raises before it returns an array."""
    return walk_train_pairs(path, offset, end, verify_crc, tally).assemble()


class _Stopwatch:
    """The seconds of the ``with`` blocks it was entered around, summed:
    several stretches of one call that a phase is to count as one."""

    total = 0.0

    def __enter__(self):
        self._t0 = time.perf_counter()

    def __exit__(self, *exc):
        self.total += time.perf_counter() - self._t0


def _gru_tail_interpreted(mm, cap: int, offset: int, end: int, verify_crc: bool):
    """``read_gru_tail`` where the native library did not load, and what
    its path through the library is held to: a generator hops the range,
    then ``_decode_body`` once a block from the last one backwards (the
    header parsed, ``zlib.crc32`` over the payload, the three columns'
    views) and a ``np.concatenate`` a column → the three columns (None
    where the range holds no sequence) and how many blocks were decoded
    and hopped."""
    parts: list[dict[str, np.ndarray]] = []  # newest block first
    held = 0
    blocks = list(_hop_mapped(mm, offset, end))
    at = len(blocks)
    while at and held < cap:
        at -= 1
        header, cols = _decode_body(mm, *blocks[at], verify_crc, _GRU_COLUMNS)
        if header["kind"] == KIND_TRAIN and len(cols["gru.sequences"]):
            parts.append(cols)
            held += len(cols["gru.sequences"])
    columns = [np.concatenate([p[c] for p in reversed(parts)])[-cap:] for c in _GRU_COLUMNS] if parts else None
    return columns, len(blocks) - at, at


def _gru_tail_by_the_library(lib, library, mm, cap: int, offset: int, end: int, verify_crc: bool):
    """``_gru_tail_interpreted`` with the interpreter touching only the
    blocks it keeps: the range hopped by one call of the library to a
    row of three numbers a block (``df_hop_blocks``, called to count and
    then to fill: what ``list(_hop_mapped(...))`` holds; from a byte the
    library stopped at short of the range's end, no magic there, the
    generator takes the range, to raise what it raises); the kept
    blocks decoded from the last one backwards, unchecked
    (``_decode_body``: the header parsed, the three columns made as
    views, no payload byte read); the kept blocks checked
    by one call (``df_crc32_blocks``), newest first; each column laid
    into an array made once at its final length by one call
    (``_gather``), the oldest kept block's head left out where more than
    ``cap`` are held. ``library`` is entered around each of the
    library's stretches."""
    base = np.frombuffer(mm, np.uint8).ctypes.data
    stopped_at = np.empty(1, np.int64)
    with library:
        n = lib.df_hop_blocks(base, offset, end, None, 0, stopped_at)
        blocks = np.empty((n, 3), np.int64)
        blocks = blocks[: lib.df_hop_blocks(base, offset, end, blocks.ctypes.data, n, stopped_at)]
    if stopped_at[0] >= 0:
        rest = list(_hop_mapped(mm, int(stopped_at[0]), end))
        blocks = np.concatenate([blocks, np.array(rest, np.int64).reshape(-1, 3)])
    parts: list[dict[str, np.ndarray]] = []  # newest block first
    kept: list[tuple] = []  # the same blocks as ``df_crc32_blocks`` takes them
    held, at = 0, len(blocks)
    try:
        while at and held < cap:
            at -= 1
            pos, header_len, payload_len = blocks[at].tolist()
            header, cols = _decode_body(mm, pos, header_len, payload_len, False, _GRU_COLUMNS)
            if verify_crc:
                kept.append((pos, pos + _PREAMBLE.size + header_len, payload_len, _stated_crc32(header["crc32"])))
            if header["kind"] == KIND_TRAIN and len(cols["gru.sequences"]):
                parts.append(cols)
                held += len(cols["gru.sequences"])
    finally:
        # also where a header raised: the interpreter's path has checked
        # every newer block by then, and a mismatch there is what it raises
        if kept:
            checked = np.array(kept, np.int64)
            with library:
                bad = lib.df_crc32_blocks(base, checked, len(checked))
            if bad >= 0:
                raise WireError(f"block crc mismatch at byte {checked[bad, 0]}")
    if not parts:
        return None, len(blocks) - at, at
    parts.reverse()
    rows = min(held, cap)
    columns = []
    for c in _GRU_COLUMNS:
        pieces = [p[c] for p in parts]
        pieces[0] = pieces[0][held - rows :]
        out = np.empty((rows, *pieces[0].shape[1:]), np.result_type(*{p.dtype for p in pieces}))
        with library:
            _gather(lib, pieces, out)
        columns.append(out)
    return columns, len(blocks) - at, at


def read_gru_tail(
    path: str | os.PathLike,
    cap: int,
    offset: int = 0,
    end: int | None = None,
    verify_crc: bool = True,
    tally: BlockTally | None = None,
    native_phase=None,
):
    """The newest ``cap`` piece sequences of the ``train`` blocks in
    ``[offset, end)``, in file order → ``PieceSequences`` (all of them
    when the range holds fewer): what a walk of every block that kept
    the last ``cap`` would return, element for element. Records append
    in time order, so the range is hopped once by its preambles (16
    bytes a block of the mapping, no header, no payload) and decoded
    from the last block backwards until ``cap`` sequences are held or the start is
    reached; an upload under the cap is a full read by the same code.

    Only the blocks decoded here are CRC-checked here. The blocks hopped
    over are checked by the round's MLP read of the same range, which
    decodes every block (``read_train_pairs``, ``stream_train_pairs``):
    a corrupt block anywhere still fails that fit.

    Where the native library loaded, the hop, the check and the copies
    are calls of it that hold no interpreter lock, and the interpreter
    parses the headers of the blocks it keeps and no others
    (``_gru_tail_by_the_library``): beside a scheduler's decision
    workers a week's 53,760 short iterations each waited to have the
    lock back. Where it did not, the interpreter does it all
    (``_gru_tail_interpreted``): the same arrays, tally and errors
    either way. ``native_phase``, when given, is a phase
    (utils/profiling.py) told the library's seconds, summed over its
    calls, once a read (``observe``): not at all where the library did
    not load."""
    from dragonfly2_tpu.schema import native
    from dragonfly2_tpu.schema.features import PieceSequences, extract_piece_sequences

    end = _clamped_end(path, end)
    if offset >= end:
        return extract_piece_sequences({})
    lib = native.load()
    with _mapped(path) as mm:
        if lib is None:
            columns, decoded, hopped = _gru_tail_interpreted(mm, cap, offset, end, verify_crc)
        else:
            library = _Stopwatch()
            try:
                columns, decoded, hopped = _gru_tail_by_the_library(lib, library, mm, cap, offset, end, verify_crc)
            finally:
                if native_phase is not None:
                    native_phase.observe(library.total)
    if tally is not None:
        tally.decoded += decoded
        tally.hopped += hopped
    return PieceSequences(*columns) if columns else extract_piece_sequences({})
