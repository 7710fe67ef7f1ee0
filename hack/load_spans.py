#!/usr/bin/env python3
"""What the resident load's assembly costs on a host, by the width of its
pool and the length of its spans (ISSUE 35's sweep; PERF.md §5).

    chiprun -- env JAX_PLATFORMS=cpu python3 hack/load_spans.py [--chunks 60]

No chip is used: the load is host work, and the host that counts is the
chip's. The upload is ``train-round-resident``'s (``rounds-60chunk``: a
body of 2,048 records in 256-record ``train`` blocks, 112 bodies a chunk),
written once and read from the page cache as a round reads it. Each
reading is ``wire.walk_train_pairs`` and then ``assemble()`` into arrays
no reading before it touched, with ``wire.ASSEMBLY_THREADS`` and
``wire.ASSEMBLY_SPAN_BLOCKS`` set for the reading (they are constants of
the module: this sweep is where their values come from), alone or beside
the two draws a fit's order makes in those seconds. One JSON line a
reading: the walk's seconds, the assembly's, the spans' seconds summed
over the workers and how many workers that kept busy.

``--beside N [N ...]`` (ISSUE 39) reads the walk and the assembly, at the
module's own width and span, beside N Python threads that want the
interpreter as a scheduler's decision workers do in the colocated service
(each works ``--duty`` of its time in pure Python, 1 ms at a stretch, and
sleeps the rest; the switch interval is the service's 0.5 ms), once on
each of the paths a load takes: every header parsed by the interpreter,
``zlib.crc32`` once a block and ``np.concatenate`` an array at a time
(``DF_NO_NATIVE``), and the native library's walk, its one call for the
check and one a column for the copies. The lines also carry the
library's checks and their seconds, and the seconds of the walk that
were the library's (``walk_native_s``; 0 where the interpreter walked).

``--walk`` (ISSUE 43) reads the walk alone, with no assembly behind it,
beside each of ``--beside``'s thread counts (``--walk --beside 0 16``: alone
and beside 16), on both paths in turn: a line a reading with the walk's
seconds, the library's seconds inside them, and how many of the blocks'
headers the interpreter parsed (all of them, or none).

``--gru-tail`` (ISSUE 46) reads the GRU leg's load alone,
``wire.read_gru_tail`` of the newest ``--cap`` sequences, beside each of
``--beside``'s thread counts, on both paths in turn: a line a reading with
the tail's seconds and its pieces apart, each summed over its calls from
outside (the module's names replaced by timed ones for the reading): the
hop (the generator's list, or ``df_hop_blocks``), the kept headers
(``json.loads``, and how many), the check (``zlib.crc32`` once a block, or
``df_crc32_blocks``), the copies (``np.concatenate``, or ``df_gather``),
what the read told ``native_phase`` and how often, and the tally. The first
line is the first read of the file by this process.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import threading
import time

ROOT = os.path.normpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
sys.path.insert(0, ROOT)

import numpy as np

from benchmarks.harness import synth
from dragonfly2_tpu.schema import native, wire
from dragonfly2_tpu.utils import profiling


def stage(path: str, chunks: int, bodies: int, body_records: int, seed: int) -> None:
    records = synth.download_records(body_records, seed)
    rpb = wire.BLOCK_RECORDS
    chunk = bodies * b"".join(
        wire.encode_train_block(records[i : i + rpb]) for i in range(0, len(records), rpb)
    )
    with open(path, "wb") as f:
        for _ in range(chunks):
            f.write(chunk)


def busy(stop: threading.Event, duty: float, stretch: float = 0.001) -> None:
    """A thread that wants the interpreter ``duty`` of the time."""
    while not stop.is_set():
        until = time.perf_counter() + stretch
        while time.perf_counter() < until:
            pass
        if duty < 1.0:
            time.sleep(stretch * (1.0 - duty) / duty)


@contextlib.contextmanager
def others_wanting_the_interpreter(beside: int, duty: float):
    """``beside`` threads of ``busy`` for as long as the block runs."""
    stop = threading.Event()
    others = [threading.Thread(target=busy, args=(stop, duty), daemon=True) for _ in range(beside)]
    for t in others:
        t.start()
    try:
        yield
    finally:
        stop.set()
        for t in others:
            t.join()


def use_library(library: bool) -> None:
    """The path the next reading takes: ``DF_NO_NATIVE`` is read at every call."""
    if library:
        os.environ.pop("DF_NO_NATIVE", None)
    else:
        os.environ["DF_NO_NATIVE"] = "1"


def reading(path: str, threads: int, span_blocks: int, draws: int, beside: int = 0, duty: float = 1.0, library: bool = True) -> dict:
    """``library``: the module as it is, or (False) ``DF_NO_NATIVE``."""
    wire.ASSEMBLY_THREADS, wire.ASSEMBLY_SPAN_BLOCKS = threads, span_blocks
    use_library(library)
    # phases of this reading's own, as the trainer hands the assembly its leg's
    spans, checks, walked = profiling.Phase("hack.load_span"), profiling.Phase("hack.load_check"), profiling.Phase("hack.load_walk_native")
    with others_wanting_the_interpreter(beside, duty):
        t0 = time.perf_counter()
        walk = wire.walk_train_pairs(path, native_phase=walked)
        t1 = time.perf_counter()
        # what FitOrder draws beside the assembly: a permutation of every pair, twice
        drawing = [
            threading.Thread(target=np.random.default_rng(i).permutation, args=(walk.num_pairs,))
            for i in range(draws)
        ]
        for t in drawing:
            t.start()
        pairs = walk.assemble(span_phase=spans, check_phase=checks)
        t2 = time.perf_counter()
    for t in drawing:
        t.join()
    t3 = time.perf_counter()
    out = {
        "threads": threads, "span_blocks": span_blocks, "draws": draws,
        "beside": beside, "duty": duty, "library": native.load() is not None,
        "checks": checks.count, "check_s_sum": round(checks.total_s, 3),
        "walk_s": round(t1 - t0, 4), "walk_native_s": round(walked.total_s, 4), "assemble_s": round(t2 - t1, 3), "draws_after_s": round(t3 - t2, 3),
        "spans": spans.count, "span_s_sum": round(spans.total_s, 3), "span_s_max": round(spans.max_s, 4),
        "busy_workers": round(spans.total_s / (t2 - t1), 2),
        "pairs": int(pairs.labels.shape[0]), "blocks": len(walk.table),
    }
    del walk, pairs
    return out


def walking(path: str, beside: int, duty: float, library: bool) -> dict:
    """The walk alone: ``library`` False is ``DF_NO_NATIVE``."""
    use_library(library)
    walked = profiling.Phase("hack.load_walk_native")
    with others_wanting_the_interpreter(beside, duty):
        time.sleep(0.05)  # the others under way
        t0 = time.perf_counter()
        walk = wire.walk_train_pairs(path, native_phase=walked)
        t1 = time.perf_counter()
    return {
        "walk_alone": True, "beside": beside, "duty": duty, "library": library and native.load() is not None,
        "walk_s": round(t1 - t0, 4), "walk_native_s": round(walked.total_s, 4), "walks_by_the_library": walked.count,
        "blocks": len(walk.table), "parsed_by_the_interpreter": int((walk.table[:, wire._FEATURES] < 0).sum()),
        "pairs": walk.num_pairs, "records": walk.num_downloads,
    }


def timed(into: dict, piece: str, call):
    """``call`` with its seconds and its calls summed into ``into[piece]``."""

    def timed_call(*args, **kw):
        t0 = time.perf_counter()
        try:
            return call(*args, **kw)
        finally:
            seconds, calls = into.get(piece, (0.0, 0))
            into[piece] = (seconds + time.perf_counter() - t0, calls + 1)

    return timed_call


class Timed:
    """``target`` with the calls of its attributes named in ``pieces``
    timed under the piece's name (``timed``)."""

    def __init__(self, target, pieces: dict, into: dict):
        self.target, self._pieces, self._into = target, pieces, into

    def __getattr__(self, name):
        attr = getattr(self.target, name)
        return timed(self._into, self._pieces[name], attr) if name in self._pieces else attr


def tail(path: str, cap: int, beside: int, duty: float, library: bool) -> dict:
    """The GRU tail alone, its pieces timed from outside: ``library``
    False is ``DF_NO_NATIVE``."""
    use_library(library)
    told, tally, pieces = profiling.Phase("hack.gru_load_native"), wire.BlockTally(), {}
    lib, load, hop = native.load(), native.load, wire._hop_mapped
    timed_lib = Timed(lib, {"df_hop_blocks": "hop", "df_crc32_blocks": "check", "df_gather": "copies"}, pieces)
    if lib is not None:
        native.load = lambda: timed_lib
    wire._hop_mapped = timed(pieces, "hop", lambda *a: list(hop(*a)))
    wire.json, wire.zlib = Timed(json, {"loads": "headers"}, pieces), Timed(wire.zlib, {"crc32": "check"}, pieces)
    wire.np = Timed(np, {"concatenate": "copies"}, pieces)
    try:
        with others_wanting_the_interpreter(beside, duty):
            time.sleep(0.05)  # the others under way
            t0 = time.perf_counter()
            seqs = wire.read_gru_tail(path, cap, tally=tally, native_phase=told)
            t1 = time.perf_counter()
    finally:
        native.load, wire._hop_mapped = load, hop
        wire.json, wire.zlib, wire.np = wire.json.target, wire.zlib.target, np
    out = {
        "gru_tail": True, "beside": beside, "duty": duty, "library": lib is not None, "tail_s": round(t1 - t0, 4),
        "told_native_s": round(told.total_s, 4), "told": told.count,
        **{f"{piece}_s": round(pieces.get(piece, (0.0, 0))[0], 4) for piece in ("hop", "headers", "check", "copies")},
        "calls": {piece: calls for piece, (_, calls) in sorted(pieces.items())},
        "decoded": tally.decoded, "hopped": tally.hopped, "sequences": int(seqs.labels.shape[0]),
    }
    out["rest_s"] = round(out["tail_s"] - sum(out[f"{piece}_s"] for piece in ("hop", "headers", "check", "copies")), 4)
    return out


def parts(path: str, threads: int) -> dict:
    """The assembly's pieces, each alone on ``threads`` threads: the
    CRCs, the first touch of an array of the features' size (a write a
    page), the features' copy into pages touched before, a whole fill
    of fresh pages and the copy into fresh pages."""
    import zlib
    from concurrent.futures import ThreadPoolExecutor

    walk = wire.walk_train_pairs(path)
    n, per = len(walk.features), 128
    rows = [0]
    for f in walk.features:
        rows.append(rows[-1] + len(f))
    edges = list(range(0, n, per))

    def timed(work) -> float:
        t0 = time.perf_counter()
        with ThreadPoolExecutor(threads) as pool:
            list(pool.map(work, edges))
        return round(time.perf_counter() - t0, 3)

    def crc(lo):
        for _, start, nbytes, _ in walk.blocks[lo : lo + per]:
            zlib.crc32(walk.mapped[start : start + nbytes])

    out = {"threads": threads, "crc_s": timed(crc)}
    a = np.empty((walk.num_pairs, 19), np.float32)
    flat = a.reshape(-1)

    def touch(lo):
        flat[rows[lo] * 19 : rows[min(lo + per, n)] * 19 : 1024] = 0

    out["touch_s"] = timed(touch)

    def copy(lo):
        np.concatenate(walk.features[lo : lo + per], out=a[rows[lo] : rows[min(lo + per, n)]])

    out["copy_touched_s"] = timed(copy)
    out["copy_touched_again_s"] = timed(copy)
    del a, flat
    c = np.empty((walk.num_pairs, 19), np.float32)

    def fill(lo):
        c[rows[lo] : rows[min(lo + per, n)]] = 0

    out["fill_fresh_s"] = timed(fill)
    del c
    d = np.empty((walk.num_pairs, 19), np.float32)

    def copy_d(lo):
        np.concatenate(walk.features[lo : lo + per], out=d[rows[lo] : rows[min(lo + per, n)]])

    out["copy_fresh_s"] = timed(copy_d)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chunks", type=int, default=60)
    ap.add_argument("--bodies", type=int, default=112)
    ap.add_argument("--body-records", type=int, default=2048)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--threads", type=int, nargs="+", default=[1, 2, 3, 4, 6, 8])
    ap.add_argument("--span-blocks", type=int, nargs="+", default=[32, 128, 448, 896])
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--parts", action="store_true", help="the assembly's pieces alone, by thread count, in place of the sweep")
    ap.add_argument("--beside", type=int, nargs="+", help="the walk and the assembly on each path beside this many threads that want the interpreter, in place of the sweep")
    ap.add_argument("--walk", action="store_true", help="the walk alone on both paths, beside each of --beside's thread counts (default 0 16), in place of the sweep")
    ap.add_argument("--gru-tail", action="store_true", help="the GRU leg's read of its tail alone on both paths, its pieces apart, beside each of --beside's thread counts (default 0 16), in place of the sweep")
    ap.add_argument("--cap", type=int, default=1_000_000, help="the sequences --gru-tail keeps (TrainingConfig.gru_max_sequences)")
    ap.add_argument("--duty", type=float, nargs="+", default=[0.03], help="the share of its time such a thread works in Python")
    args = ap.parse_args()
    width, span_blocks = wire.ASSEMBLY_THREADS, wire.ASSEMBLY_SPAN_BLOCKS  # the module's own, before a reading sets others
    thp = "/sys/kernel/mm/transparent_hugepage/enabled"
    print(json.dumps({
        "cpu_count": os.cpu_count(), "affinity": len(os.sched_getaffinity(0)),
        "thp": open(thp).read().strip() if os.path.exists(thp) else None,
    }), flush=True)
    with tempfile.TemporaryDirectory(dir=os.environ.get("TMPDIR")) as tmp:
        path = os.path.join(tmp, "upload.dfb")
        t0 = time.perf_counter()
        stage(path, args.chunks, args.bodies, args.body_records, args.seed)
        print(json.dumps({"staged_bytes": os.path.getsize(path), "stage_s": round(time.perf_counter() - t0, 2)}), flush=True)
        if args.gru_tail:
            print(json.dumps({**tail(path, args.cap, 0, 1.0, True), "first": True}), flush=True)
        else:
            reading(path, 1, span_blocks, 0)  # the mapping's pages, once
        if args.beside or args.walk or args.gru_tail:
            from dragonfly2_tpu.colocated.server import SWITCH_INTERVAL_S

            sys.setswitchinterval(SWITCH_INTERVAL_S)
            print(json.dumps({"switch_interval_s": SWITCH_INTERVAL_S, "library": native.available()}), flush=True)
        if args.gru_tail:
            for _ in range(args.repeats):
                for beside, duty in ((b, d) for b in args.beside or [0, 16] for d in (args.duty if b else args.duty[:1])):
                    for library in (False, True):
                        print(json.dumps(tail(path, args.cap, beside, duty, library)), flush=True)
            return 0
        if args.walk:
            for _ in range(args.repeats):
                for beside, duty in ((b, d) for b in args.beside or [0, 16] for d in (args.duty if b else args.duty[:1])):
                    for library in (False, True):
                        print(json.dumps(walking(path, beside, duty, library)), flush=True)
            return 0
        if args.beside:
            for _ in range(args.repeats):
                for beside, duty in ((b, d) for b in args.beside for d in (args.duty if b else args.duty[:1])):
                    for library in (False, True):
                        print(json.dumps(reading(path, width, span_blocks, 2, beside, duty, library)), flush=True)
            return 0
        if args.parts:
            for threads in args.threads:
                print(json.dumps(parts(path, threads)), flush=True)
            return 0
        for _ in range(args.repeats):
            for threads in args.threads:
                print(json.dumps(reading(path, threads, span_blocks, 2)), flush=True)
            for blocks in args.span_blocks:
                print(json.dumps(reading(path, width, blocks, 2)), flush=True)
            print(json.dumps(reading(path, width, span_blocks, 0)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
