"""Binary columnar train-stream, end to end: wire format round-trip,
announcer → trainer service → ingest over real gRPC, bit-identical
tensors vs the CSV path, and the CSV-fallback negotiation for old
trainers (ISSUE round 6 tentpole)."""

import json
import threading
import time
import zlib

import numpy as np
import pytest

from dragonfly2_tpu.rpc import gen  # noqa: F401
import trainer_pb2  # noqa: E402

import grpc

from dragonfly2_tpu.rpc.glue import TRAINER_SERVICE, ServiceClient, dial, serve
from dragonfly2_tpu.schema import native, synth, wire
from dragonfly2_tpu.schema.columnar import records_to_columns, write_csv
from dragonfly2_tpu.schema.features import extract_pair_features, extract_piece_sequences
from dragonfly2_tpu.scheduler.announcer import Announcer
from dragonfly2_tpu.scheduler.storage import Storage
from dragonfly2_tpu.trainer.ingest import StreamStats, stream_shards
from dragonfly2_tpu.trainer.service import TrainerService
from dragonfly2_tpu.trainer.storage import TrainerStorage
from dragonfly2_tpu.trainer.train import FitConfig, GNNFitConfig
from dragonfly2_tpu.trainer.training import Training, TrainingConfig
from dragonfly2_tpu.utils.idgen import host_id_v2


class TestWireFormat:
    def test_train_block_roundtrip_bit_identical(self):
        recs = synth.make_download_records(40, seed=3)
        cols = records_to_columns(recs)
        pairs = extract_pair_features(cols)
        seqs = extract_piece_sequences(cols)
        header, dec, end = wire.decode_block(wire.encode_train_block(recs))
        assert header["kind"] == wire.KIND_TRAIN
        assert header["records"] == 40
        np.testing.assert_array_equal(dec["pairs.features"], pairs.features)
        np.testing.assert_array_equal(dec["pairs.labels"], pairs.labels)
        np.testing.assert_array_equal(dec["pairs.download_index"], pairs.download_index)
        np.testing.assert_array_equal(dec["gru.sequences"], seqs.sequences)
        np.testing.assert_array_equal(dec["gru.labels"], seqs.labels)

    def test_topology_block_roundtrip_all_columns(self):
        recs = synth.make_topology_records(30, num_hosts=12, seed=4)
        cols = records_to_columns(recs)
        _, dec, _ = wire.decode_block(wire.encode_topology_block(recs))
        assert set(dec) == set(cols)
        for k in cols:  # dict/zero/raw encodings must all be lossless
            np.testing.assert_array_equal(dec[k], cols[k], err_msg=k)

    def test_concatenated_blocks_and_torn_tail(self, tmp_path):
        blk = wire.encode_train_block(synth.make_download_records(10, seed=5))
        p = tmp_path / "d.dfb"
        p.write_bytes(blk + blk + blk[: len(blk) // 2])  # torn tail
        spans = wire.scan_blocks(p)
        assert len(spans) == 2  # the torn trailing block is ignored
        assert wire.count_records(p) == 20
        pairs = wire.read_train_pairs(p)
        assert pairs.num_downloads == 20

    def test_crc_mismatch_raises(self, tmp_path):
        blk = bytearray(wire.encode_train_block(synth.make_download_records(5, seed=6)))
        blk[-3] ^= 0xFF  # flip a payload byte
        with pytest.raises(wire.WireError, match="crc"):
            wire.decode_block(bytes(blk))

    def test_bad_magic_raises(self, tmp_path):
        p = tmp_path / "junk.dfb"
        p.write_bytes(b"NOPE" + b"\x00" * 64)
        with pytest.raises(wire.WireError, match="magic"):
            wire.scan_blocks(p)

    def test_split_block_spans_cover_exactly(self, tmp_path):
        blk = wire.encode_train_block(synth.make_download_records(8, seed=7))
        p = tmp_path / "d.dfb"
        p.write_bytes(blk * 5)
        spans = wire.split_block_spans([str(p)], target_span_bytes=len(blk))
        assert [s[1] for s in spans] == [i * len(blk) for i in range(5)]
        assert spans[-1][2] == 5 * len(blk)


def _identical_records_both_formats(tmp_path, n=120, seed=11):
    """The same records as a CSV file and a binary block file."""
    recs = synth.make_download_records(n, seed=seed)
    csv_path = tmp_path / "d.csv"
    write_csv(csv_path, recs)
    bin_path = tmp_path / "d.dfb"
    bin_path.write_bytes(wire.encode_train_block(recs))
    return recs, csv_path, bin_path


class TestIngestEquivalence:
    @pytest.mark.parametrize("half", [False, True])
    def test_stream_shards_binary_matches_csv(self, tmp_path, half):
        """The consumer-visible stream — (features, labels) — must be
        bit-identical between payload formats, in both staging dtypes."""
        pytest.importorskip("ctypes")
        from dragonfly2_tpu.schema import native

        if not native.available():
            pytest.skip("native CSV decoder unavailable")
        _, csv_path, bin_path = _identical_records_both_formats(tmp_path)

        def collect(path, stats):
            feats, labels, total = [], [], 0
            for f, l, total in stream_shards(path, workers=2, half=half, stats=stats):
                if f.shape[0]:
                    feats.append(np.array(f))
                    labels.append(np.array(l))
            return np.concatenate(feats), np.concatenate(labels), total

        s_bin, s_csv = StreamStats(), StreamStats()
        bf, bl, brows = collect(bin_path, s_bin)
        cf, cl, crows = collect(csv_path, s_csv)
        assert brows == crows == 120
        # worker interleaving may reorder shards; compare as sorted rows
        order_b = np.lexsort(bf.T)
        order_c = np.lexsort(cf.T)
        np.testing.assert_array_equal(bf[order_b], cf[order_c])
        np.testing.assert_array_equal(bl[order_b], cl[order_c])
        assert bf.dtype == (np.float16 if half else np.float32)
        # the stage split is being recorded on the binary path
        assert s_bin.read_s > 0

    def test_read_train_pairs_rebases_indices_across_blocks(self, tmp_path):
        """Per-block download_index values are 0-based within their
        block; the concatenated read must rebase them onto the running
        record count (the 'row in the source batch' invariant)."""
        recs = synth.make_download_records(20, seed=13)
        p = tmp_path / "d.dfb"
        p.write_bytes(
            wire.encode_train_block(recs[:10]) + wire.encode_train_block(recs[10:])
        )
        merged = wire.read_train_pairs(p)
        direct = extract_pair_features(records_to_columns(recs))
        np.testing.assert_array_equal(merged.download_index, direct.download_index)
        assert merged.num_downloads == 20

    def test_batch_pairs_match(self, tmp_path):
        recs, _, bin_path = _identical_records_both_formats(tmp_path, seed=12)
        direct = extract_pair_features(records_to_columns(recs))
        via_wire = wire.read_train_pairs(bin_path)
        np.testing.assert_array_equal(via_wire.features, direct.features)
        np.testing.assert_array_equal(via_wire.labels, direct.labels)
        assert via_wire.num_downloads == direct.num_downloads == 120


class RecordingManager:
    def __init__(self):
        self.models = {}

    def create_model(self, model_id, model_type, ip, hostname, params, evaluation):
        self.models[model_type] = {"params": params, "evaluation": evaluation}


def _trainer_stack(tmp_path, name="trainer"):
    manager = RecordingManager()
    t_storage = TrainerStorage(tmp_path / name)
    training = Training(
        t_storage,
        manager,
        TrainingConfig(
            mlp=FitConfig(hidden_dims=(16,), batch_size=128, epochs=3, seed=0),
            gnn=GNNFitConfig(hidden_dims=(8,), batch_size=128, epochs=10, seed=0),
            # keep the uploaded files around so the tests can assert
            # WHICH payload format actually landed
            clear_after_train=False,
        ),
    )
    return manager, t_storage, TrainerService(t_storage, training, synchronous=True)


def _scheduler_storage(tmp_path, name, n_dl=80, n_topo=200):
    storage = Storage(tmp_path / name, buffer_size=16)
    for r in synth.make_download_records(n_dl, seed=21):
        storage.create_download(r)
    for r in synth.make_topology_records(n_topo, num_hosts=16, seed=22):
        storage.create_network_topology(r)
    storage.flush()
    return storage


class TestAnnouncerRoundTrip:
    def test_binary_negotiated_and_trains(self, tmp_path):
        """New trainer: Capabilities advertises columnar-v1 → the
        announcer ships block files → the trainer's binary ingest path
        fits all three model families."""
        manager, t_storage, service = _trainer_stack(tmp_path)
        server, port = serve({TRAINER_SERVICE: service})
        channel = dial(f"127.0.0.1:{port}")
        try:
            storage = _scheduler_storage(tmp_path, "sched")
            ann = Announcer(
                storage,
                ip="10.9.9.9",
                hostname="sched-bin",
                trainer_channel=channel,
                upload_chunk=1 << 14,  # small chunks: blocks split mid-payload
            )
            assert ann.negotiated_format() == wire.FORMAT_NAME
            assert ann.train_once()
            hid = host_id_v2("10.9.9.9", "sched-bin")
            # the payload landed as block files, no CSV
            assert t_storage.download_blocks_path(hid).exists()
            assert not t_storage.download_path(hid).exists()
            assert t_storage.network_topology_blocks_path(hid).exists()
            assert set(manager.models) == {"mlp", "gnn", "gru"}
            assert manager.models["mlp"]["evaluation"]["mse"] > 0
        finally:
            channel.close()
            server.stop(0)

    def test_old_trainer_falls_back_to_csv(self, tmp_path):
        """Old trainer: Capabilities answers UNIMPLEMENTED (the RPC
        didn't exist) → the announcer ships CSV and training still
        completes — no peer is ever stranded by the format change."""
        manager, t_storage, service = _trainer_stack(tmp_path)

        class OldTrainer:
            Train = service.Train

            def Capabilities(self, request, context):
                context.abort(grpc.StatusCode.UNIMPLEMENTED, "no such method")

        server, port = serve({TRAINER_SERVICE: OldTrainer()})
        channel = dial(f"127.0.0.1:{port}")
        try:
            storage = _scheduler_storage(tmp_path, "sched2")
            ann = Announcer(
                storage, ip="10.8.8.8", hostname="sched-old", trainer_channel=channel
            )
            assert ann.negotiated_format() == wire.CSV_FORMAT_NAME
            assert ann.train_once()
            hid = host_id_v2("10.8.8.8", "sched-old")
            assert t_storage.download_path(hid).exists()
            assert not t_storage.download_blocks_path(hid).exists()
            assert set(manager.models) == {"mlp", "gnn", "gru"}
        finally:
            channel.close()
            server.stop(0)

    def test_blocks_off_era_ships_csv_superset(self, tmp_path):
        """Records written by a previous process with write_blocks=False
        exist only as CSV; after the toggle, the CSV files are a
        SUPERSET of the blocks — shipping blocks would silently discard
        the old era, so the round ships CSV even on a binary trainer."""
        sched_dir = tmp_path / "sched"
        old = Storage(sched_dir, buffer_size=16, write_blocks=False)
        for r in synth.make_download_records(30, seed=80):
            old.create_download(r)
        old.flush()

        # restart with the block sink ON, more records arrive
        storage = Storage(sched_dir, buffer_size=16, write_blocks=True)
        for r in synth.make_download_records(20, seed=81):
            storage.create_download(r)
        storage.flush()

        manager, t_storage, service = _trainer_stack(tmp_path)
        server, port = serve({TRAINER_SERVICE: service})
        channel = dial(f"127.0.0.1:{port}")
        try:
            ann = Announcer(
                storage, ip="10.6.6.6", hostname="sched-mix", trainer_channel=channel
            )
            assert ann.negotiated_format() == wire.FORMAT_NAME  # binary-capable
            assert ann.train_once()
            hid = host_id_v2("10.6.6.6", "sched-mix")
            # CSV shipped (the superset): every record reached the trainer
            assert len(t_storage.list_download(hid)) == 50
            assert not t_storage.download_blocks_path(hid).exists()
            # the next round (clean dual-sink history) ships binary again
            for r in synth.make_download_records(10, seed=82):
                storage.create_download(r)
            storage.flush()
            assert ann.train_once()
            assert t_storage.download_blocks_path(hid).exists()
        finally:
            channel.close()
            server.stop(0)

    def test_binary_and_csv_train_to_identical_models(self, tmp_path):
        """The equivalence that matters: the SAME records uploaded via
        the binary payload and via the CSV fallback produce bit-identical
        MLP parameters (same tensors + same deterministic fit)."""
        results = {}
        for mode in ("binary", "csv"):
            manager, t_storage, service = _trainer_stack(tmp_path, f"trainer-{mode}")
            if mode == "csv":
                svc_impl = service

                class CsvOnly:
                    Train = svc_impl.Train

                    def Capabilities(self, request, context):
                        return trainer_pb2.CapabilitiesResponse(
                            train_formats=[wire.CSV_FORMAT_NAME]
                        )

                impl = CsvOnly()
            else:
                impl = service
            server, port = serve({TRAINER_SERVICE: impl})
            channel = dial(f"127.0.0.1:{port}")
            try:
                storage = _scheduler_storage(tmp_path, f"sched-{mode}")
                ann = Announcer(
                    storage, ip="10.7.7.7", hostname="sched-eq", trainer_channel=channel
                )
                assert ann.train_once()
            finally:
                channel.close()
                server.stop(0)
            results[mode] = manager.models["mlp"]["params"]
        flat_b = results["binary"]["layers"]
        flat_c = results["csv"]["layers"]
        for lb, lc in zip(flat_b, flat_c):
            np.testing.assert_array_equal(np.asarray(lb["w"]), np.asarray(lc["w"]))
            np.testing.assert_array_equal(np.asarray(lb["b"]), np.asarray(lc["b"]))


class TestFormatSwitch:
    def test_other_era_survives_clear_and_trains_next_round(self, tmp_path):
        """A host whose scheduler switched payload formats holds BOTH a
        CSV and a binary file: the round drains the OLDER (CSV) era and
        clears ONLY it — the binary era survives and trains on the
        following round instead of either era being destroyed or left
        lingering forever."""
        import csv as _csv
        import io

        from dragonfly2_tpu.schema import records as R

        manager = RecordingManager()
        t_storage = TrainerStorage(tmp_path / "t")
        training = Training(
            t_storage,
            manager,
            TrainingConfig(
                mlp=FitConfig(hidden_dims=(8,), batch_size=64, epochs=2, seed=0),
                min_topology_records=10**9,  # no topology uploaded here
            ),
        )
        hid = host_id_v2("3.3.3.3", "s3")
        # CSV era (40 records)
        recs = synth.make_download_records(40, seed=40)
        buf = io.StringIO()
        w = _csv.DictWriter(buf, fieldnames=R.headers(R.DownloadRecord))
        w.writeheader()
        for r in recs:
            w.writerow(R.flatten(r))
        t_storage.append_download(hid, buf.getvalue().encode())
        # binary era (25 records)
        t_storage.append_download_blocks(
            hid, wire.encode_train_block(synth.make_download_records(25, seed=41))
        )
        t_storage.mark_download_round(hid)

        outcome = training.train("3.3.3.3", "s3")
        assert outcome.mlp_error is None
        # older (CSV) era consumed and cleared; binary era intact
        assert not t_storage.download_path(hid).exists()
        assert t_storage.download_blocks_path(hid).exists()
        assert wire.count_records(t_storage.download_blocks_path(hid)) == 25
        # next round trains the surviving binary era, then clears it
        outcome2 = training.train("3.3.3.3", "s3")
        assert outcome2.mlp_error is None
        assert not t_storage.download_blocks_path(hid).exists()

    def test_gnn_merges_both_topology_eras(self, tmp_path, monkeypatch):
        """The probe graph is cumulative: after a format switch the GNN
        leg must build from the CSV era AND the binary era."""
        import csv as _csv
        import io

        import dragonfly2_tpu.trainer.training as training_mod
        from dragonfly2_tpu.schema import records as R

        t_storage = TrainerStorage(tmp_path / "t")
        hid = host_id_v2("4.4.4.4", "s4")
        era_a = synth.make_topology_records(30, num_hosts=8, seed=50)
        era_b = synth.make_topology_records(30, num_hosts=8, seed=51)
        s = io.StringIO()
        w = _csv.DictWriter(s, fieldnames=R.headers(R.NetworkTopologyRecord))
        w.writeheader()
        for r in era_a:
            w.writerow(R.flatten(r))
        t_storage.append_network_topology(hid, s.getvalue().encode())
        t_storage.append_network_topology_blocks(hid, wire.encode_topology_block(era_b))
        t_storage.mark_download_round(hid)

        captured = {}

        def fake_train_gnn(graph, mesh=None, config=None):
            captured["records"] = graph.num_records
            captured["nodes"] = set(graph.node_ids)

            class Result:
                params = {}
                metrics = {"f1": 1.0}

            return Result()

        monkeypatch.setattr(training_mod, "train_gnn", fake_train_gnn)
        training = Training(t_storage, None, TrainingConfig())
        metrics = training._train_gnn(hid, "4.4.4.4", "s4")
        assert metrics == {"f1": 1.0}
        assert captured["records"] == 60
        from dragonfly2_tpu.schema.features import build_probe_graph

        expected = build_probe_graph(records_to_columns(era_a + era_b))
        assert captured["nodes"] == set(expected.node_ids)


class TestTornStreamRecovery:
    def test_failed_stream_truncates_partial_round(self, tmp_path):
        manager, t_storage, service = _trainer_stack(tmp_path)
        hid = host_id_v2("1.1.1.1", "s")
        blk = wire.encode_train_block(synth.make_download_records(6, seed=30))

        def broken_stream():
            yield trainer_pb2.TrainRequest(
                ip="1.1.1.1",
                hostname="s",
                train_mlp_binary=trainer_pb2.TrainMlpBinaryRequest(
                    dataset=blk[: len(blk) // 2]
                ),
            )
            raise RuntimeError("upload died mid-chunk")

        with pytest.raises(RuntimeError):
            service.Train(broken_stream(), None)
        # the torn half-block was dropped — the file is gone (no prior round)
        assert not t_storage.download_blocks_path(hid).exists()

        # a complete round after a failed one decodes cleanly
        def good_stream():
            yield trainer_pb2.TrainRequest(
                ip="1.1.1.1",
                hostname="s",
                train_mlp_binary=trainer_pb2.TrainMlpBinaryRequest(dataset=blk),
            )

        service.Train(good_stream(), None)
        assert wire.count_records(t_storage.download_blocks_path(hid)) == 6

    def test_restart_then_failed_stream_keeps_prior_rounds(self, tmp_path):
        """Round boundaries are PERSISTED: a trainer restart followed by
        one failed upload must not destroy previously-accumulated
        complete rounds (the in-memory-only boundary map would have
        truncated everything to zero)."""
        manager, t_storage, service = _trainer_stack(tmp_path)
        hid = host_id_v2("2.2.2.2", "s2")
        blk = wire.encode_train_block(synth.make_download_records(6, seed=31))

        def good_stream():
            yield trainer_pb2.TrainRequest(
                ip="2.2.2.2",
                hostname="s2",
                train_mlp_binary=trainer_pb2.TrainMlpBinaryRequest(dataset=blk),
            )

        service.Train(good_stream(), None)

        # "restart": a fresh storage over the same directory, empty RAM state
        restarted = TrainerStorage(t_storage.dir)
        assert restarted.download_round_boundary(hid, binary=True) == len(blk)

        def broken_stream():
            yield trainer_pb2.TrainRequest(
                ip="2.2.2.2",
                hostname="s2",
                train_mlp_binary=trainer_pb2.TrainMlpBinaryRequest(
                    dataset=blk[: len(blk) // 3]
                ),
            )
            raise RuntimeError("died")

        service2 = TrainerService(restarted, service.training, synchronous=True)
        with pytest.raises(RuntimeError):
            service2.Train(broken_stream(), None)
        # the prior complete round survived; only the torn tail is gone
        assert wire.count_records(restarted.download_blocks_path(hid)) == 6

    def test_crashed_process_torn_tail_healed_on_next_append(self, tmp_path):
        """A trainer KILLED mid-stream never runs the in-process
        truncation — the next process's first append must heal the torn
        tail, or the retry's complete blocks land after it and the file
        is poisoned forever."""
        storage = TrainerStorage(tmp_path)
        hid = host_id_v2("5.5.5.5", "s5")
        blk = wire.encode_train_block(synth.make_download_records(7, seed=60))
        # simulate the dead process's half-written file directly on disk
        storage.download_blocks_path(hid).write_bytes(blk + blk[: len(blk) // 2])

        # "restarted" trainer appends the announcer's retry
        fresh = TrainerStorage(tmp_path)
        fresh.append_download_blocks(hid, blk)
        assert wire.count_records(fresh.download_blocks_path(hid)) == 14
        pairs = wire.read_train_pairs(fresh.download_blocks_path(hid))
        assert pairs.num_downloads == 14

    def test_subminimum_csv_tail_falls_through_to_binary(self, tmp_path):
        """A CSV-era leftover below min_download_records must not
        deadlock the MLP leg forever: the round falls through to the
        binary era and the sub-minimum tail is dropped with the clear."""
        import csv as _csv
        import io

        from dragonfly2_tpu.schema import records as R

        manager = RecordingManager()
        t_storage = TrainerStorage(tmp_path / "t")
        training = Training(
            t_storage,
            manager,
            TrainingConfig(
                mlp=FitConfig(hidden_dims=(8,), batch_size=64, epochs=2, seed=0),
                min_download_records=10,
                min_topology_records=10**9,
            ),
        )
        hid = host_id_v2("6.6.6.6", "s6")
        s = io.StringIO()
        w = _csv.DictWriter(s, fieldnames=R.headers(R.DownloadRecord))
        w.writeheader()
        for r in synth.make_download_records(3, seed=61):  # below min=10
            w.writerow(R.flatten(r))
        t_storage.append_download(hid, s.getvalue().encode())
        t_storage.append_download_blocks(
            hid, wire.encode_train_block(synth.make_download_records(25, seed=62))
        )
        t_storage.mark_download_round(hid)

        outcome = training.train("6.6.6.6", "s6")
        assert outcome.mlp_error is None  # binary era trained
        # both forms cleared: the binary was consumed, the tail dropped
        assert not t_storage.download_path(hid).exists()
        assert not t_storage.download_blocks_path(hid).exists()


# ---------------------------------------------------------------------------
# the resident round's block readers: each leg decodes only the blocks and
# columns it trains on (ISSUE 26). The plain full-walk readers they replaced
# are kept here as the references.
# ---------------------------------------------------------------------------


def _block(rng, pairs: int, seqs: int, records: int) -> bytes:
    """One ``train`` block of the given sizes with seeded contents (0
    pairs or 0 sequences serialize as ``zero`` columns)."""
    from dragonfly2_tpu.schema.features import GRU_FEATURE_DIM, GRU_MAX_SEQ, MLP_FEATURE_DIM

    cols = {
        "pairs.features": rng.random((pairs, MLP_FEATURE_DIM), np.float32),
        "pairs.labels": rng.random(pairs, np.float32),
        "pairs.download_index": np.sort(rng.integers(0, records, pairs)).astype(np.int32),
        "gru.sequences": rng.random((seqs, GRU_MAX_SEQ, GRU_FEATURE_DIM), np.float32),
        "gru.labels": rng.random(seqs, np.float32),
        "gru.lengths": rng.integers(1, GRU_MAX_SEQ + 1, seqs).astype(np.int32),
    }
    return wire.encode_block(
        cols, wire.KIND_TRAIN, records=records, meta={"feature_dim": MLP_FEATURE_DIM}
    )


def _topology_block(seed: int) -> bytes:
    return wire.encode_topology_block(synth.make_topology_records(6, num_hosts=4, seed=seed))


# name → (blocks as (pairs, sequences, records) or "topology", torn tail?)
_UPLOADS = {
    "even": ([(7, 5, 3)] * 6, False),
    "ragged": ([(4, 9, 2), (0, 0, 1), (11, 1, 5), (3, 0, 2), (6, 7, 3), (0, 4, 1)], False),
    "interleaved": (
        [(5, 4, 2), "topology", (8, 6, 4), "topology", "topology", (2, 3, 1), "topology"],
        False,
    ),
    "torn": ([(5, 4, 2), (9, 6, 3), (7, 2, 3)], True),
    "no-train": (["topology", "topology"], False),
    "no-sequences": ([(5, 0, 2), (3, 0, 1)], False),
}


def _write_upload(tmp_path, name):
    """→ (path, [(start, end) per whole block])."""
    return _write_blocks(tmp_path, name, *_UPLOADS[name], seed=sorted(_UPLOADS).index(name))


def _write_blocks(tmp_path, name, spec, torn=False, seed=0):
    rng = np.random.default_rng(seed)
    blocks = [
        _topology_block(i) if b == "topology" else _block(rng, *b) for i, b in enumerate(spec)
    ]
    edges = np.cumsum([0] + [len(b) for b in blocks]).tolist()
    path = tmp_path / f"{name}.dfb"
    path.write_bytes(b"".join(blocks) + (blocks[0][: len(blocks[0]) // 2] if torn else b""))
    return path, list(zip(edges[:-1], edges[1:]))


def _walk(path, offset=0, end=None):
    """Every whole block of ``[offset, end)``, all six columns, oldest
    first: the plain walk both old readers made."""
    buf = path.read_bytes()
    for start, _ in wire.scan_block_extents(path, offset, end):
        header, cols, _ = wire.decode_block(buf, start)
        yield header, cols


def _reference_gru(path, cap, offset=0, end=None):
    """The full walk with the pop-from-the-front cap, as ``_train_gru``
    ran it before the newest-first read."""
    parts, total = [], 0
    for header, cols in _walk(path, offset, end):
        if header["kind"] != wire.KIND_TRAIN:
            continue
        if cols["gru.sequences"].shape[0]:
            parts.append(cols)
            total += cols["gru.sequences"].shape[0]
        while parts and total - parts[0]["gru.sequences"].shape[0] >= cap:
            total -= parts.pop(0)["gru.sequences"].shape[0]
    if not parts:
        empty = extract_piece_sequences({})
        return empty.sequences, empty.labels, empty.lengths
    return tuple(
        np.concatenate([p[c] for p in parts])[-cap:]
        for c in ("gru.sequences", "gru.labels", "gru.lengths")
    )


def _reference_pairs(path, offset=0, end=None):
    """A copy of every block's pair columns, then one concatenate."""
    feats, labels, idx, records = [], [], [], 0
    for header, cols in _walk(path, offset, end):
        if header["kind"] != wire.KIND_TRAIN:
            continue
        feats.append(np.array(cols["pairs.features"]))
        labels.append(np.array(cols["pairs.labels"]))
        idx.append(np.asarray(cols["pairs.download_index"]) + np.int32(records))
        records += header["records"]
    if not feats:
        return None, records
    return (np.concatenate(feats), np.concatenate(labels), np.concatenate(idx)), records


def _bounds(extents, which):
    """Byte bounds by name: the whole file, or cut at block edges."""
    if which == "whole" or len(extents) < 3:
        return 0, None
    if which == "from-second-block":
        return extents[1][0], None
    if which == "to-last-block":
        return 0, extents[-1][0]
    return extents[1][0], extents[-2][1]  # "inner"


def _assert_same_array(got, want):
    np.testing.assert_array_equal(got, want)
    assert got.dtype == want.dtype and got.shape == want.shape


class TestNewestFirstGruRead:
    # caps against "even" (6 blocks of 5 sequences): inside a block, at a
    # block's edge, at the total, above it, none
    @pytest.mark.parametrize("cap", [1, 3, 5, 10, 12, 29, 30, 31, 1000, 0])
    def test_cap_below_at_and_above_the_total(self, tmp_path, cap):
        path, extents = _write_upload(tmp_path, "even")
        tally = wire.BlockTally()
        got = wire.read_gru_tail(path, cap, tally=tally)
        for g, w in zip((got.sequences, got.labels, got.lengths), _reference_gru(path, cap)):
            _assert_same_array(g, w)
        # no block is decoded that holds none of the cap
        assert tally.decoded == min(-(-cap // 5), len(extents))
        assert tally.hopped == len(extents) - tally.decoded

    @pytest.mark.parametrize("which", ["whole", "from-second-block", "to-last-block", "inner"])
    @pytest.mark.parametrize("cap", [2, 8, 1000])
    @pytest.mark.parametrize("name", sorted(_UPLOADS))
    def test_equals_the_full_walk(self, tmp_path, name, cap, which):
        """Blocks with no sequences, non-``train`` blocks between, a torn
        tail, no ``train`` block at all, under ``offset`` / ``end``."""
        path, extents = _write_upload(tmp_path, name)
        offset, end = _bounds(extents, which)
        tally = wire.BlockTally()
        got = wire.read_gru_tail(path, cap, offset=offset, end=end, tally=tally)
        want = _reference_gru(path, cap, offset, end)
        for g, w in zip((got.sequences, got.labels, got.lengths), want):
            _assert_same_array(g, w)
        assert tally.decoded + tally.hopped == len(wire.scan_block_extents(path, offset, end))

    def test_end_past_the_file_and_empty_range(self, tmp_path):
        path, extents = _write_upload(tmp_path, "even")
        whole = wire.read_gru_tail(path, 7)
        _assert_same_array(wire.read_gru_tail(path, 7, end=10**12).labels, whole.labels)
        none = wire.read_gru_tail(path, 7, offset=extents[2][0], end=extents[2][0])
        assert none.sequences.shape == extract_piece_sequences({}).sequences.shape

    @pytest.mark.parametrize("corrupt,gru_raises", [(5, True), (4, True), (3, False), (0, False)])
    def test_who_checks_which_blocks_crc(self, tmp_path, corrupt, gru_raises):
        """Cap 8 of "even" is held by the last two blocks: a flipped
        byte there fails the GRU read; before them it is the MLP leg's
        read of the whole upload that fails, as the round relies on."""
        path, extents = _write_upload(tmp_path, "even")
        buf = bytearray(path.read_bytes())
        buf[extents[corrupt][1] - 3] ^= 0xFF
        path.write_bytes(bytes(buf))
        if gru_raises:
            with pytest.raises(wire.WireError, match="crc"):
                wire.read_gru_tail(path, 8)
        else:
            assert wire.read_gru_tail(path, 8).sequences.shape[0] == 8
        with pytest.raises(wire.WireError, match="crc"):
            wire.read_train_pairs(path)
        with pytest.raises(wire.WireError, match="crc"):
            for _ in wire.stream_train_pairs(path):
                pass

    def test_garbage_at_a_block_edge_raises(self, tmp_path):
        path, extents = _write_upload(tmp_path, "even")
        buf = bytearray(path.read_bytes())
        buf[extents[1][0]] ^= 0xFF  # the second block's magic
        path.write_bytes(bytes(buf))
        with pytest.raises(wire.WireError, match="magic"):
            wire.read_gru_tail(path, 3)

    @pytest.mark.parametrize("cap_of", ["under-binary", "binary-exactly", "into-csv", "over-both"])
    def test_csv_era_then_binary_era(self, tmp_path, monkeypatch, cap_of):
        """The binary era is newer: the CSV chunks are read only when the
        blocks hold fewer than the cap, and the fit is handed what the
        old chained walk gave it."""
        import dragonfly2_tpu.trainer.train as T

        older = synth.make_download_records(30, seed=51)
        newer = [synth.make_download_records(12, seed=52 + i) for i in range(3)]
        storage = TrainerStorage(tmp_path / "t")
        hid = host_id_v2("5.5.5.5", "s5")
        write_csv(storage.download_path(hid), older)
        for recs in newer:
            storage.append_download_blocks(hid, wire.encode_train_block(recs))
        storage.mark_download_round(hid)
        chain = [extract_piece_sequences(records_to_columns(r)) for r in (older, *newer)]
        n_csv = chain[0].sequences.shape[0]
        n_bin = sum(p.sequences.shape[0] for p in chain[1:])
        assert n_csv > 3 and n_bin > 3
        cap = {
            "under-binary": n_bin - 3, "binary-exactly": n_bin,
            "into-csv": n_bin + 3, "over-both": n_bin + n_csv + 5,
        }[cap_of]
        fed = {}

        def spy(sequences, labels, lengths=None, **kw):
            fed.update(sequences=sequences, labels=labels, lengths=lengths)
            raise RuntimeError("handed over")

        monkeypatch.setattr(T, "train_gru", spy)
        chunks_read = []
        real_chunks = storage.iter_download_chunks

        def counting_chunks(*a, **kw):
            chunks_read.append(1)
            return real_chunks(*a, **kw)

        monkeypatch.setattr(storage, "iter_download_chunks", counting_chunks)
        training = Training(
            storage, None, TrainingConfig(gru_max_sequences=cap, gru_min_sequences=1, auto_mesh=False)
        )
        with pytest.raises(RuntimeError, match="handed over"):
            training._train_gru(hid, "5.5.5.5", "s5")
        for f in ("sequences", "labels", "lengths"):
            want = np.concatenate([getattr(p, f) for p in chain])[-cap:]
            _assert_same_array(fed[f], want)
        assert bool(chunks_read) == (cap > n_bin)


class TestPairsWrittenOnce:
    @pytest.mark.parametrize("which", ["whole", "from-second-block", "to-last-block", "inner"])
    @pytest.mark.parametrize("name", sorted(_UPLOADS))
    def test_equals_the_concatenation_reference(self, tmp_path, name, which):
        from dragonfly2_tpu.schema.features import MLP_FEATURE_DIM

        path, extents = _write_upload(tmp_path, name)
        offset, end = _bounds(extents, which)
        tally = wire.BlockTally()
        got = wire.read_train_pairs(path, offset=offset, end=end, tally=tally)
        want, records = _reference_pairs(path, offset, end)
        assert got.num_downloads == records
        assert (tally.decoded, tally.hopped) == (len(wire.scan_block_extents(path, offset, end)), 0)
        arrays = (got.features, got.labels, got.download_index)
        if want is None:  # no train block in the range
            want = (
                np.zeros((0, MLP_FEATURE_DIM), np.float32),
                np.zeros((0,), np.float32),
                np.zeros((0,), np.int32),
            )
        for g, w in zip(arrays, want):
            _assert_same_array(g, w)
            assert g.flags.c_contiguous and g.flags.writeable and g.flags.owndata

    @pytest.mark.parametrize(
        "name, which",
        [("interleaved", "whole"), ("torn", "whole"), ("even", "empty-range"), ("no-train", "whole"), ("ragged", "inner")],
    )
    def test_the_walk_counts_what_the_assembly_holds(self, tmp_path, name, which):
        """The walk knows the pair count and the record count before a
        pair is copied (its columns are views into the mapping), they are
        the assembled ``PairExamples``' own, and the walk followed by the
        assembly is ``read_train_pairs``: non-``train`` blocks between,
        a torn tail, an empty range, no ``train`` block, an inner cut."""
        path, extents = _write_upload(tmp_path, name)
        offset, end = (extents[2][0], extents[2][0]) if which == "empty-range" else _bounds(extents, which)
        tally = wire.BlockTally()
        walk = wire.walk_train_pairs(path, offset=offset, end=end, tally=tally)
        want, records = _reference_pairs(path, offset, end)
        assert walk.num_downloads == records
        assert walk.num_pairs == (0 if want is None else len(want[1]))
        assert (tally.decoded, tally.hopped) == (len(wire.scan_block_extents(path, offset, end)), 0)
        assert len(walk.bases) == len(walk.features) and not any(v.flags.owndata for v in walk.features if v.size)
        pairs, whole = walk.assemble(), wire.read_train_pairs(path, offset=offset, end=end)
        assert pairs.num_downloads == whole.num_downloads == walk.num_downloads
        assert pairs.features.shape[0] == len(pairs.labels) == len(pairs.download_index) == walk.num_pairs
        for field, w in zip(("features", "labels", "download_index"), want or (None,) * 3):
            _assert_same_array(getattr(pairs, field), getattr(whole, field))
            if w is not None:
                _assert_same_array(getattr(pairs, field), w)

    @pytest.mark.parametrize("columns", [None, ("gru.labels",), ("pairs.features", "pairs.labels"), ()])
    def test_only_the_asked_for_columns_are_built(self, tmp_path, columns):
        path, _ = _write_upload(tmp_path, "interleaved")
        every = list(wire.iter_blocks(path))
        asked = list(wire.iter_blocks(path, columns=columns))
        assert [h for h, _ in asked] == [h for h, _ in every]
        for (_, some), (_, full) in zip(asked, every):
            assert set(some) == (set(full) if columns is None else set(full) & set(columns))
            for name, arr in some.items():
                _assert_same_array(arr, full[name])


# uploads by how they fall into spans of 4 blocks (``span_of_4``): name → blocks
_T, _TOPO = (6, 2, 3), "topology"
_SPANNED = {
    "one-block": [_T],
    "exactly-one-span": [(5, 1, 2), (9, 0, 4), (0, 3, 1), (7, 2, 3)],
    "spans-and-a-remainder": [(3 + i % 5, i % 3, 1 + i % 4) for i in range(11)],
    # a span that ends in other blocks, one that holds no ``train`` block, one that begins with others
    "others-between": [_T, (4, 1, 2), _TOPO, _TOPO, _TOPO, _TOPO, _TOPO, _TOPO, _TOPO, (8, 0, 5), _T, _TOPO, (2, 2, 1)],
    "no-train-block": [_TOPO] * 6,
}


@pytest.fixture
def span_of_4(monkeypatch):
    monkeypatch.setattr(wire, "ASSEMBLY_SPAN_BLOCKS", 4)
    return 4


@pytest.fixture(params=["library", "per-block"])
def check_path(request, monkeypatch):
    """The two ways the assembly checks a span: one call of the native
    library (schema/native.py), or ``zlib.crc32`` once a block where the
    library did not load, as under ``DF_NO_NATIVE``. → the path's name
    and the library, loaded or None."""
    monkeypatch.delenv("DF_NO_NATIVE", raising=False)
    lib = native.load()
    if request.param == "per-block":
        monkeypatch.setenv("DF_NO_NATIVE", "1")
        assert native.load() is None and not native.available()
    elif lib is None:
        pytest.skip("native library unavailable (no toolchain)")
    return request.param, lib


def _flip(path, extent):
    """One payload byte of the block at ``extent`` flipped."""
    buf = bytearray(path.read_bytes())
    buf[extent[1] - 3] ^= 0xFF
    path.write_bytes(bytes(buf))


class _Entered:
    """What ``assemble`` takes for a phase: a context manager entered on
    whatever thread runs a span; ``left`` is (thread, seconds) an entry."""

    def __init__(self):
        self.left, self._mine = [], threading.local()

    def __enter__(self):
        self._mine.t0 = time.perf_counter()

    def __exit__(self, *exc):
        self.left.append((threading.current_thread().name, time.perf_counter() - self._mine.t0))


class TestCheckedAndCopiedASpanAtATime:
    """The resident read checks every block's CRC in its assembly, a
    span of blocks at a time, beside the copy (ISSUE 35)."""

    @pytest.mark.parametrize("which", ["whole", "inner"])
    @pytest.mark.parametrize("name", sorted(_SPANNED))
    def test_spans_equal_the_concatenation_reference(self, tmp_path, span_of_4, check_path, name, which):
        from dragonfly2_tpu.schema.features import MLP_FEATURE_DIM

        path, extents = _write_blocks(tmp_path, name, _SPANNED[name], seed=7)
        offset, end = _bounds(extents, which)
        in_range = len(wire.scan_block_extents(path, offset, end))
        tally, spans, checks = wire.BlockTally(), _Entered(), _Entered()
        walk = wire.walk_train_pairs(path, offset=offset, end=end, tally=tally)
        got = walk.assemble(span_phase=spans, check_phase=checks)
        ran_on, checked_on = spans.left, checks.left
        assert (tally.decoded, tally.hopped) == (in_range, 0)
        # once a span, by the thread that ran it: the caller's for one span, the pool's for more
        assert len(ran_on) == -(-in_range // span_of_4) and all(s >= 0 for _, s in ran_on)
        pooled = [name.startswith("wire.assemble") for name, _ in ran_on]
        assert all(pooled) if len(ran_on) > 1 else not any(pooled)
        # the library's call is entered once a span by that same thread, inside the span's seconds; the per-block loop never
        assert sorted(t for t, _ in checked_on) == (sorted(t for t, _ in ran_on) if check_path[0] == "library" else [])
        assert sum(s for _, s in checked_on) <= sum(s for _, s in ran_on)
        assert not [t for t in threading.enumerate() if t.name.startswith("wire.assemble")]
        want, records = _reference_pairs(path, offset, end)
        assert got.num_downloads == records
        if want is None:
            want = (
                np.zeros((0, MLP_FEATURE_DIM), np.float32),
                np.zeros((0,), np.float32),
                np.zeros((0,), np.int32),
            )
        whole = wire.read_train_pairs(path, offset=offset, end=end)
        for g, w, again in zip((got.features, got.labels, got.download_index), want, (whole.features, whole.labels, whole.download_index)):
            _assert_same_array(g, w)
            _assert_same_array(again, w)
            assert g.flags.c_contiguous and g.flags.writeable and g.flags.owndata

    @pytest.mark.parametrize(
        "name, corrupt",
        [
            ("one-block", [0]),
            ("spans-and-a-remainder", [0]),  # the first block
            ("spans-and-a-remainder", [5]),  # inside a span
            ("spans-and-a-remainder", [4]),  # a span's first block
            ("spans-and-a-remainder", [7]),  # a span's last block
            ("spans-and-a-remainder", [7, 6]),  # two of one span: the library says which came first
            ("spans-and-a-remainder", [10]),  # the last block, in the remainder
            ("spans-and-a-remainder", [9, 2, 6]),  # three spans fail: the first in file order is named
            ("exactly-one-span", [3, 1]),  # one span, on the caller's thread
            ("others-between", [5]),  # a non-``train`` block, in a span with no ``train`` block
            ("others-between", [11]),
            ("no-train-block", [3]),  # no pair to copy: every block is checked all the same
        ],
    )
    def test_a_corrupt_block_anywhere_hands_back_no_array(self, tmp_path, span_of_4, check_path, name, corrupt):
        path, extents = _write_blocks(tmp_path, name, _SPANNED[name], seed=8)
        for i in corrupt:
            _flip(path, extents[i])
        walk = wire.walk_train_pairs(path)  # the walk reads no payload: it ends with its counts
        sound = wire.read_train_pairs(_write_blocks(tmp_path, "sound", _SPANNED[name], seed=8)[0])
        assert (walk.num_pairs, walk.num_downloads) == (len(sound.labels), sound.num_downloads)
        handed = []
        for read in (walk.assemble, lambda: wire.read_train_pairs(path)):
            with pytest.raises(wire.WireError, match=f"block crc mismatch at byte {extents[min(corrupt)][0]}$"):
                handed.append(read())
        assert not handed
        assert not [t for t in threading.enumerate() if t.name.startswith("wire.assemble")]
        assert len(wire.read_train_pairs(path, verify_crc=False).labels) == walk.num_pairs

    @pytest.mark.parametrize("states", [1 << 32, -1, 1.5, "7", None, True])
    def test_a_header_that_states_no_crc32_is_a_mismatch_at_its_block(self, tmp_path, span_of_4, check_path, states):
        """A header is JSON and may state anything for its ``crc32``:
        what no 32 bits hold matches no payload, on either path, and the
        block named is that one (a corrupt block after it in the same
        span is not)."""
        path, extents = _write_blocks(tmp_path, "spans-and-a-remainder", _SPANNED["spans-and-a-remainder"], seed=8)
        _flip(path, extents[7])
        walk = wire.walk_train_pairs(path)
        walk.blocks[5] = (*walk.blocks[5][:3], states)
        with pytest.raises(wire.WireError, match=f"block crc mismatch at byte {extents[5][0]}$"):
            walk.assemble()

    @pytest.mark.parametrize("name, offers", [("one-block", 0), ("exactly-one-span", 1), ("spans-and-a-remainder", 3), ("no-train-block", 2)])
    def test_the_walk_offers_the_interpreter_once_a_few_blocks(self, tmp_path, monkeypatch, check_path, name, offers):
        """The interpreter's walk calls nothing that gives the
        interpreter lock up: once ``WALK_OFFER_BLOCKS`` blocks, of
        whatever kind, it does. The library's walk holds no lock to
        offer: what is offered there is offered by the interpreter's
        walk of the blocks from the first the library was not sure of
        (``exactly-one-span`` has a block of no pairs, third of four)."""
        monkeypatch.setattr(wire, "WALK_OFFER_BLOCKS", 3)
        slept = []
        monkeypatch.setattr(wire.time, "sleep", slept.append)
        path, _ = _write_blocks(tmp_path, name, _SPANNED[name], seed=10)
        walk = wire.walk_train_pairs(path)
        if check_path[0] == "library":
            sure = _read_by_the_library(path)
            offers = (len(sure) - sure.index(False)) // 3 if False in sure else 0
            assert sure[:3] == [True, True, False] if name == "exactly-one-span" else len(sure) == len(walk.table)
        assert slept == [0] * offers

    @pytest.mark.parametrize("verify_crc", [True, False])
    @pytest.mark.parametrize("which", ["whole", "from-second-block", "inner"])
    @pytest.mark.parametrize("name", ["spans-and-a-remainder", "others-between", "no-train-block"])
    def test_every_block_of_the_range_is_checked_exactly_once(
        self, tmp_path, monkeypatch, span_of_4, check_path, name, which, verify_crc
    ):
        path, extents = _write_blocks(tmp_path, name, _SPANNED[name], seed=9)
        offset, end = _bounds(extents, which)
        stated = sorted(h["crc32"] for h, _ in wire.iter_blocks(path, offset, end, verify_crc=False, columns=()))
        checked, real = [], wire.zlib.crc32

        def counting(data, *value):
            crc = real(data, *value)
            checked.append(crc)  # list.append is atomic: the pool's threads share the list
            return crc

        monkeypatch.setattr(wire.zlib, "crc32", counting)
        # the library's call: the payloads it was told to check and what their headers state, a list a call
        (path_name, lib), calls = check_path, []
        if lib is not None:
            in_one_call = lib.df_crc32_blocks

            def counting_calls(base, blocks, n):
                assert blocks.shape == (n, 4)
                calls.append([(int(nbytes), int(crc)) for _, _, nbytes, crc in blocks])
                return in_one_call(base, blocks, n)

            monkeypatch.setattr(lib, "df_crc32_blocks", counting_calls)
        walk = wire.walk_train_pairs(path, offset=offset, end=end, verify_crc=verify_crc)
        assert not checked and not calls  # the walk checks none
        walk.assemble()
        by_library = path_name == "library" and verify_crc
        assert sorted(checked) == (stated if verify_crc and not by_library else [])
        assert sorted(crc for call in calls for _, crc in call) == (stated if by_library else [])
        assert len(set(stated)) == len(stated) > 1
        # a call a span, the span's blocks in file order, each with its own payload's length
        lengths = [(nbytes, crc) for _, _, nbytes, crc in walk.blocks]
        assert sorted(calls) == sorted(lengths[lo : lo + span_of_4] for lo in range(0, len(lengths) if by_library else 0, span_of_4))


    @pytest.mark.parametrize(
        "case",
        ["as-the-walk-builds-them", "one-part", "empty-parts-between", "another-type", "a-stride", "flat-labels"],
    )
    def test_a_columns_copy_is_numpys_concatenation(self, check_path, case):
        """``wire._gather`` against ``np.concatenate(parts, out=out)``:
        parts as the walk builds them (read-only views, of one type,
        contiguous) copied by the library in one call; a part of
        another type or with a stride left to numpy, which casts or
        steps; the same array either way and on either path."""
        lib = check_path[1] if check_path[0] == "library" else None
        rng = np.random.default_rng(3)
        rows = {"one-part": [7], "empty-parts-between": [0, 5, 0, 0, 3, 0]}.get(case, [4, 1, 9, 2])
        shape = () if case == "flat-labels" else (19,)
        parts = [rng.random((n, *shape), np.float32) for n in rows]
        if case == "another-type":
            parts[2] = parts[2].astype(np.float64)
        if case == "a-stride":
            parts[1] = rng.random((2, *shape), np.float32)[::2]
        for p in parts:
            p.flags.writeable = False
        want = np.concatenate(parts).astype(np.float32)
        whole = np.full((sum(rows) + 2, *shape), -1.0, np.float32)
        wire._gather(lib, parts, whole[1:-1])
        _assert_same_array(whole[1:-1], want)
        assert (whole[0] == -1).all() and (whole[-1] == -1).all()  # nothing written past its place

    @pytest.mark.parametrize("fault", ["too-short", "too-long", "other-row-shape", "no-parts"])
    def test_a_copy_numpy_refuses_is_refused_by_numpy(self, check_path, fault):
        """Parts that do not fill ``out``, or of another row shape, are
        not the library's to copy: numpy raises as it did, and the
        library writes nothing."""
        lib = check_path[1] if check_path[0] == "library" else None
        parts = [np.ones((3, 19), np.float32), np.ones((2, 19), np.float32)]
        out = np.zeros((5, 19), np.float32)
        if fault == "too-short":
            out = np.zeros((6, 19), np.float32)
        elif fault == "too-long":
            out = np.zeros((4, 19), np.float32)
        elif fault == "other-row-shape":
            parts[1] = np.ones((2, 18), np.float32)
        else:
            parts = []
        with pytest.raises(ValueError):
            wire._gather(lib, parts, out)
        assert not out.any()

    def test_a_span_asks_for_the_lock_a_few_times_and_not_four_times_a_block(self, tmp_path, check_path):
        """What the one-call check and copies are for. The interpreter's
        forced hand-over is set far beyond the test, so a second thread
        is handed the lock only where the assembling thread gives it up.
        That thread counts one and then waits until the assembling
        thread is back in Python (its profile hook says so at every
        return from C), so a count is a hand-over: on the library's
        path a few a span (the check, a column's copy, the rebasing
        add) whatever the number of blocks, on the per-block path one
        at a ``crc32`` or an array copied. No clock is read."""
        import os
        import sys

        blocks = 127  # one span, on the caller's thread; a block's payload 250 KB
        path, _ = _write_blocks(tmp_path, "many", [(3000, 0, 64)] * blocks, seed=12)
        walk = wire.walk_train_pairs(path)
        handed, stop, counting, back = [0], threading.Event(), threading.Event(), threading.Event()

        def count():
            while not stop.is_set():
                handed[0] += 1
                counting.set()
                back.clear()
                back.wait()

        def back_in_python(frame, event, arg):
            if event == "c_return":
                back.set()

        interval = sys.getswitchinterval()
        counter = threading.Thread(target=count, name="test.counter")
        sys.setswitchinterval(3600.0)
        try:
            counter.start()
            counting.wait()
            before = handed[0]
            sys.setprofile(back_in_python)
            try:
                pairs = walk.assemble()
            finally:
                sys.setprofile(None)
            during = handed[0] - before
        finally:
            stop.set()
            back.set()
            sys.setswitchinterval(interval)
            counter.join()
        assert len(pairs.labels) == 3000 * blocks
        # the library's bound holds on any machine; the per-block path's
        # count needs a second core for the counting thread to run on
        if check_path[0] == "library":
            assert during <= 12, during
        elif len(os.sched_getaffinity(0)) > 1:
            assert during > 12, during


def _raw_block(rng, pairs: int, seqs: int, records: int) -> bytes:
    """``_block`` with every pair column ``raw``: one pair at the least
    and two records, so no column is all zeros by the draw."""
    while True:
        block = _block(rng, pairs, seqs, records)
        header, _, _ = wire.decode_block(block, columns=())
        if all(e[3] == "raw" for e in header["cols"] if e[0] in wire._PAIR_COLUMNS):
            return block


def _read_by_the_library(path, offset=0, end=None) -> list:
    """Of each block of the range, whether its header is one the
    library's walk reads: a block of another kind, or a ``train`` block
    whose three pair columns are ``raw``."""
    return [
        h["kind"] != wire.KIND_TRAIN or all(e[3] == "raw" for e in h["cols"] if e[0] in wire._PAIR_COLUMNS)
        for h, _ in wire.iter_blocks(path, offset, end, verify_crc=False, columns=())
    ]


def _reheaded(block: bytes, edit) -> bytes:
    """``block`` with its header rewritten: ``edit(header)`` → the
    header to state (a dict, dumped as ``encode_block`` dumps it, or the
    bytes themselves). The payload is left as it is."""
    _, header_len, payload_len = wire._PREAMBLE.unpack_from(block)
    header = edit(json.loads(block[16 : 16 + header_len]))
    if not isinstance(header, bytes):
        header = json.dumps(header, separators=(",", ":")).encode()
    return wire._PREAMBLE.pack(wire.MAGIC, len(header), payload_len) + header + block[16 + header_len :]


def _col(header: dict, name: str) -> list:
    return next(e for e in header["cols"] if e[0] == name)


def _set(header: dict, **keys) -> dict:
    return {**header, **keys}


def _repacked(block: bytes, name: str, lay_out) -> bytes:
    """``block`` with its column ``name`` laid out anew at the payload's
    end: ``lay_out(entry, column bytes, where)`` rewrites the column's
    entry of the header and returns the bytes to append at ``where``."""
    _, header_len, payload_len = wire._PREAMBLE.unpack_from(block)
    header = json.loads(block[16 : 16 + header_len])
    entry = _col(header, name)
    held = block[16 + header_len + entry[4] :][: entry[5]]
    at = payload_len + (-payload_len % 8)
    payload = block[16 + header_len :] + b"\0" * (at - payload_len) + lay_out(entry, held, at)
    header["crc32"] = zlib.crc32(payload) & 0xFFFFFFFF
    head = json.dumps(header, separators=(",", ":")).encode()
    return wire._PREAMBLE.pack(wire.MAGIC, len(head), len(payload)) + head + payload


def _wider_labels(block: bytes) -> bytes:
    """The block's labels stated and laid out as float64: a column of
    another type than the schema's."""

    def lay_out(entry, held, at):
        wide = np.frombuffer(held, np.float32).astype(np.float64).tobytes()
        entry[1], entry[4], entry[5] = "<f8", at, len(wide)
        return wide

    return _repacked(block, "pairs.labels", lay_out)


def _dict_index(block: bytes) -> bytes:
    """The block's ``pairs.download_index`` dictionary-encoded, as
    ``encode_block`` encodes a string column: codes and a table of the
    values as text."""

    def lay_out(entry, held, at):
        uniques, codes = np.unique(np.frombuffer(held, np.int32), return_inverse=True)
        table, codes = "\n".join(str(u) for u in uniques).encode(), codes.astype(np.uint32).tobytes()
        entry[3:] = ["dict", at, len(codes), at + len(codes), len(table)]
        return codes + table

    return _repacked(block, "pairs.download_index", lay_out)


# a header's rewriting → (the block's bytes from the block's, does the library read the header itself?)
_HEADERS = {
    # as ``encode_block`` writes them, and what json.loads reads alike
    "as-written": (lambda b: b, True),
    "keys-in-another-order": (lambda b: _reheaded(b, lambda h: dict(reversed(list(h.items())))), True),
    "cols-in-another-order": (lambda b: _reheaded(b, lambda h: _set(h, cols=h["cols"][::-1])), True),
    "spaces-between": (lambda b: _reheaded(b, lambda h: json.dumps(h, indent=1).encode()), True),
    "a-key-it-does-not-read": (lambda b: _reheaded(b, lambda h: _set(h, note={"by": ["x", 1.5e3, None, True, "\\u00e9"]}, meta={**h["meta"], "more": [-0.5]})), True),
    "no-records": (lambda b: _reheaded(b, lambda h: {k: v for k, v in h.items() if k != "records"}), True),
    # what the library is not sure of: the interpreter's from that block on
    "labels-all-zero": (None, False),  # ``encode_block`` writes the column ``zero``
    "index-dict-encoded": (_dict_index, False),
    "labels-of-another-type": (_wider_labels, False),
    "crc32-too-wide": (lambda b: _reheaded(b, lambda h: _set(h, crc32=1 << 32)), False),
    "crc32-negative": (lambda b: _reheaded(b, lambda h: _set(h, crc32=-1)), False),
    "crc32-a-fraction": (lambda b: _reheaded(b, lambda h: _set(h, crc32=1.5)), False),
    "crc32-a-string": (lambda b: _reheaded(b, lambda h: _set(h, crc32="7")), False),
    "crc32-null": (lambda b: _reheaded(b, lambda h: _set(h, crc32=None)), False),
    "crc32-true": (lambda b: _reheaded(b, lambda h: _set(h, crc32=True)), False),
    "no-crc32": (lambda b: _reheaded(b, lambda h: {k: v for k, v in h.items() if k != "crc32"}), False),
    "no-rows": (lambda b: _reheaded(b, lambda h: {k: v for k, v in h.items() if k != "rows"}), False),
    "no-meta": (lambda b: _reheaded(b, lambda h: {k: v for k, v in h.items() if k != "meta"}), False),
    "a-foreign-feature-width": (lambda b: _reheaded(b, lambda h: _set(h, meta={"feature_dim": h["meta"]["feature_dim"] - 1})), False),
    "no-labels-column": (lambda b: _reheaded(b, lambda h: _set(h, cols=[e for e in h["cols"] if e[0] != "pairs.labels"])), False),
    "an-escape-in-a-key": (lambda b: _reheaded(b, lambda h: json.dumps(h, separators=(",", ":")).replace('"kind"', '"k\\u0069nd"').encode()), False),
    "an-escape-in-a-column-name": (lambda b: _reheaded(b, lambda h: json.dumps(h, separators=(",", ":")).replace('"pairs.labels"', '"pairs\\u002elabels"').encode()), False),
    "a-key-stated-twice": (lambda b: _reheaded(b, lambda h: json.dumps(h, separators=(",", ":")).replace('{"kind"', '{"rows":1,"kind"').encode()), False),
    "a-byte-past-ascii": (lambda b: _reheaded(b, lambda h: json.dumps(_set(h, note=chr(233)), separators=(",", ":"), ensure_ascii=False).encode()), False),
    "records-a-fraction": (lambda b: _reheaded(b, lambda h: _set(h, records=float(h["records"]))), False),
    "an-unknown-encoding": (lambda b: _reheaded(b, lambda h: (_col(h, "pairs.features").__setitem__(3, "packed"), h)[1]), False),
    "a-column-past-the-payload": (lambda b: _reheaded(b, lambda h: (_col(h, "pairs.download_index").__setitem__(4, 1 << 40), h)[1]), False),
    "no-json": (lambda b: _reheaded(b, lambda h: json.dumps(h, separators=(",", ":"))[:-1].encode()), False),
    "garbage-at-the-edge": (lambda b: b"DFB2" + b[4:], False),
}


def _outcome(read):
    """What ``read()`` returned, or the error it raised by its type and
    its words: the two walks are held to both."""
    try:
        return read()
    except Exception as e:  # noqa: BLE001 - whatever it is, the other path raises the same
        return (type(e).__name__, str(e))


def _told(walk, pairs) -> dict:
    """Everything a walk says, and the arrays its assembly hands over or
    the error it raises, in forms that compare."""
    if not isinstance(walk, wire.TrainPairsWalk):
        return {"walk": walk}
    base = np.frombuffer(walk.mapped, np.uint8).ctypes.data if walk.mapped is not None else 0
    views = walk.train_views(0, len(walk.trains))
    return {
        "blocks": walk.blocks,
        "rows": walk.table[:, : wire._FEATURES].tolist(),
        "trains_before": walk.trains_before.tolist(),
        "bases": walk.bases.tolist(),
        "counts": (walk.num_pairs, walk.num_downloads),
        # where each pair column lies and how long it is; one that is no view of the mapping, by what it holds
        "columns": [
            [(v.ctypes.data - base, v.nbytes, v.dtype.str, v.shape) if not v.flags.owndata and v.base is not None and 0 <= v.ctypes.data - base < max(len(walk.mapped), 1) else (v.tobytes(), v.dtype.str, v.shape) for v in block]
            for block in views
        ],
        "pairs": pairs if isinstance(pairs, tuple) else tuple((a.tobytes(), a.dtype.str, a.shape) for a in (pairs.features, pairs.labels, pairs.download_index)) + (pairs.num_downloads,),
    }


def _both_walks(monkeypatch, path, offset=0, end=None, verify_crc=True):
    """``[offset, end)`` walked and assembled with the library and
    without it, and walked with it and assembled without → what each
    told, the tallies, and the library's walk itself."""
    told, tallies, walks = [], [], []
    for walk_by, assemble_by in (("library", "library"), ("interpreter", "interpreter"), ("library", "interpreter"), ("interpreter", "library")):
        tally = wire.BlockTally()
        with monkeypatch.context() as m:
            if walk_by == "interpreter":
                m.setenv("DF_NO_NATIVE", "1")
            walk = _outcome(lambda: wire.walk_train_pairs(path, offset=offset, end=end, verify_crc=verify_crc, tally=tally))
        with monkeypatch.context() as m:
            if assemble_by == "interpreter":
                m.setenv("DF_NO_NATIVE", "1")
            pairs = _outcome(walk.assemble) if isinstance(walk, wire.TrainPairsWalk) else None
        told.append(_told(walk, pairs))
        tallies.append((tally.decoded, tally.hopped))
        walks.append(walk)
    return told, tallies, walks[0]


@pytest.fixture
def with_the_library(monkeypatch):
    monkeypatch.delenv("DF_NO_NATIVE", raising=False)
    if native.load() is None:
        pytest.skip("native library unavailable (no toolchain)")


class TestTheLibrarysWalkIsTheInterpreters:
    """The resident read's walk is one call of the native library that
    holds no interpreter lock, held to the interpreter's walk table for
    table and error for error (ISSUE 43)."""

    @pytest.mark.parametrize("which", ["whole", "inner", "from-second-block", "to-last-block", "empty-range"])
    @pytest.mark.parametrize("name", sorted(_UPLOADS) + sorted(_SPANNED))
    def test_the_two_walks_tell_the_same(self, tmp_path, monkeypatch, with_the_library, span_of_4, name, which):
        """Uploads of mixed kinds, a torn tail, blocks of no pairs (their
        columns ``zero``: the library stops at the first), ranges cut by
        ``offset`` and ``end`` and an empty one: ``blocks``, the rows,
        the columns' places and lengths, ``bases``, ``trains_before``,
        the counts and ``tally.decoded`` equal, and ``assemble()``'s
        arrays bit for bit, whichever path walked and whichever
        assembled."""
        path, extents = _write_upload(tmp_path, name) if name in _UPLOADS else _write_blocks(tmp_path, name, _SPANNED[name], seed=21)
        offset, end = (extents[-1][0], extents[-1][0]) if which == "empty-range" else _bounds(extents, which)
        told, tallies, walk = _both_walks(monkeypatch, path, offset, end)
        assert told[0] == told[1] == told[2] == told[3] and len(set(tallies)) == 1
        in_range = wire.scan_block_extents(path, offset, end)
        assert tallies[0] == (len(in_range), 0) and [b[0] for b in walk.blocks] == [s for s, _ in in_range]
        want, records = _reference_pairs(path, offset, end)
        assert told[0]["counts"] == (0 if want is None else len(want[1]), records)
        if want is not None:
            assert told[0]["pairs"][:3] == tuple((a.tobytes(), a.dtype.str, a.shape) for a in want)
        # the library walked up to the first block with a pair column that is not ``raw``, and the interpreter from it on
        sure, train = _read_by_the_library(path, offset, end), (walk.table[:, wire._TRAIN] != 0).tolist()
        placed = sure.index(False) if False in sure else len(sure)
        assert (walk.table[:, wire._FEATURES] >= 0).tolist() == [i < placed and t for i, t in enumerate(train)]
        assert len(walk.views) == sum(train[placed:])

    @pytest.mark.parametrize("at", [0, 2, 5])
    @pytest.mark.parametrize("fault", sorted(_HEADERS))
    def test_a_header_the_library_is_not_sure_of_is_the_interpreters(self, tmp_path, monkeypatch, with_the_library, span_of_4, fault, at):
        """One block of six with its header rewritten, first, third or
        last. Where the header says the same to ``json.loads`` in other
        bytes (keys or columns in another order, spaces, keys the walk
        does not read) the library reads it. Where it does not (a pair
        column ``zero``- or ``dict``-encoded or of another type, a
        ``crc32`` that is no integer of 32 bits, a key missing, twice or
        escaped, another feature width, no JSON, no magic) the library
        stops at that block, the interpreter walks from it on, and the
        result is the interpreter's: the same tables and arrays, or the
        same error word for word, from the walk or from the assembly."""
        rng = np.random.default_rng(22)
        blocks = [_raw_block(rng, 5 + i, i % 3, 2 + i % 2) for i in range(6)]
        rewrite, read_by_the_library = _HEADERS[fault]
        if fault == "labels-all-zero":
            header, cols, _ = wire.decode_block(blocks[at])
            cols = {k: np.zeros_like(v) if k == "pairs.labels" else v for k, v in cols.items()}
            blocks[at] = wire.encode_block(cols, wire.KIND_TRAIN, records=header["records"], meta=header["meta"])
        else:
            blocks[at] = rewrite(blocks[at])
        path = tmp_path / "rewritten.dfb"
        path.write_bytes(b"".join(blocks))
        told, tallies, walk = _both_walks(monkeypatch, path)
        assert told[0] == told[1] == told[2] == told[3] and len(set(tallies)) == 1
        if read_by_the_library:
            assert not walk.views and (walk.table[:, wire._FEATURES] >= 0).all() and isinstance(told[0]["pairs"][0], tuple)
            assert told[0]["counts"] == (sum(5 + i for i in range(6)), sum(2 + i % 2 for i in range(6)) + (3 + at - at % 2 if fault == "no-records" else 0))  # ``rows`` where no ``records`` is stated
        elif isinstance(walk, wire.TrainPairsWalk):
            assert len(walk.views) == 6 - at and (walk.table[:, wire._FEATURES] >= 0).tolist() == [i < at for i in range(6)]
        # what each fault comes to, on both paths
        edge = sum(len(b) for b in blocks[:at])
        want = {
            "a-foreign-feature-width": ("WireError", "train block feature dim 18 != schema 19 — incompatible peer (negotiation token should have gated this)"),
            "garbage-at-the-edge": ("WireError", f"bad block magic at byte {edge}: b'DFB2'"),
            "an-unknown-encoding": ("WireError", "unknown column encoding 'packed' for 'pairs.features'"),
            "no-crc32": ("KeyError", "'crc32'"), "no-rows": ("KeyError", "'rows'"), "no-labels-column": ("KeyError", "'pairs.labels'"),
            "no-meta": ("WireError", "train block feature dim None != schema 19 — incompatible peer (negotiation token should have gated this)"),
        }
        if fault in want:
            assert told[0] == {"walk": want[fault]}
        elif fault.startswith("crc32-"):
            assert told[0]["pairs"] == ("WireError", f"block crc mismatch at byte {edge}") and told[0]["blocks"][at][3] == -1
        elif fault in ("no-json", "a-column-past-the-payload"):
            assert set(told[0]) == {"walk"} and told[0]["walk"][0] in ("JSONDecodeError", "ValueError")
        elif fault == "an-escape-in-a-key":
            assert told[0] == {"walk": ("KeyError", "'kind'")} or isinstance(told[0]["pairs"][0], tuple)
        else:
            assert isinstance(told[0]["pairs"][0], tuple), told[0]["pairs"]

    @pytest.mark.parametrize("verify_crc", [True, False])
    def test_a_fits_path_builds_no_view_and_no_list(self, tmp_path, monkeypatch, with_the_library, span_of_4, verify_crc):
        """With the library, the walk and the assembly make no object a
        block: no view is built (``np.frombuffer`` is called for the
        mapping's first byte alone), no list is made, no header is
        parsed. The lists are made at their first read, for whoever
        reads them."""
        rng = np.random.default_rng(23)
        path = tmp_path / "spans.dfb"
        path.write_bytes(b"".join(_raw_block(rng, 3 + i % 5, i % 3, 2 + i % 4) for i in range(11)))
        made, parsed = [], []
        frombuffer, loads = np.frombuffer, json.loads
        monkeypatch.setattr(wire.np, "frombuffer", lambda *a, **kw: (made.append(a[1:]), frombuffer(*a, **kw))[1])
        monkeypatch.setattr(wire.json, "loads", lambda *a, **kw: (parsed.append(1), loads(*a, **kw))[1])
        walk = wire.walk_train_pairs(path, verify_crc=verify_crc)
        pairs = walk.assemble()
        assert made == [(np.uint8,)] * 2 and not parsed and not walk.views  # the mapping's address: once in the walk, once in the assembly
        assert not {"blocks", "_columns"} & set(vars(walk))
        assert len(walk.features) == len(walk.labels) == len(walk.download_index) == 11 and len(made) == 2 + 3 * 11
        assert walk.features is walk.features and len(made) == 2 + 3 * 11  # made once
        assert [len(l) for l in walk.labels] == walk.lengths.tolist() and len(walk.blocks) == 11
        want, _ = _reference_pairs(path)
        for got, w in zip((pairs.features, pairs.labels, pairs.download_index), want):
            _assert_same_array(got, w)

    def test_the_librarys_walk_is_entered_once_and_the_interpreters_never(self, tmp_path, monkeypatch, check_path):
        """``native_phase`` is entered around the library's calls, once
        a walk, by the walking thread; on the interpreter's path not at
        all. An empty range enters nothing on either."""
        rng = np.random.default_rng(24)
        path = tmp_path / "spans.dfb"
        path.write_bytes(b"".join(_raw_block(rng, 3 + i % 5, i % 3, 2 + i % 4) for i in range(11)))
        extents = wire.scan_block_extents(path)
        entered = _Entered()
        walk = wire.walk_train_pairs(path, native_phase=entered)
        assert [t for t, _ in entered.left] == ([threading.current_thread().name] if check_path[0] == "library" else [])
        assert len(walk.table) == 11 and len(walk.views) == (0 if check_path[0] == "library" else 11)
        wire.walk_train_pairs(path, offset=extents[3][0], end=extents[3][0], native_phase=entered)
        assert len(entered.left) == (1 if check_path[0] == "library" else 0)


def _both_tails(monkeypatch, path, cap, offset=0, end=None, verify_crc=True) -> list:
    """``read_gru_tail`` through the native library and without it →
    what each told: the three arrays in forms that compare, or the error
    by its type and its words, and the tally."""
    told = []
    for by in ("library", "interpreter"):
        tally = wire.BlockTally()
        with monkeypatch.context() as m:
            if by == "interpreter":
                m.setenv("DF_NO_NATIVE", "1")
            got = _outcome(lambda: wire.read_gru_tail(path, cap, offset=offset, end=end, verify_crc=verify_crc, tally=tally))
        if not isinstance(got, tuple):
            got = [(a.tobytes(), a.dtype.str, a.shape) for a in (got.sequences, got.labels, got.lengths)]
        told.append((got, (tally.decoded, tally.hopped)))
    return told


def _gru_block(rng, seqs: int, **columns) -> bytes:
    """``_block`` with sequences, and any of its columns as given."""
    header, cols, _ = wire.decode_block(_block(rng, 4, seqs, 2))
    return wire.encode_block({**cols, **columns}, wire.KIND_TRAIN, records=header["records"], meta=header["meta"])


def _without_sequences(block: bytes) -> bytes:
    return _reheaded(block, lambda h: _set(h, cols=[e for e in h["cols"] if e[0] != "gru.sequences"]))


def _packed_lengths(block: bytes) -> bytes:
    return _reheaded(block, lambda h: (_col(h, "gru.lengths").__setitem__(3, "packed"), h)[1])


def _wider_gru_labels(block: bytes) -> bytes:
    def lay_out(entry, held, at):
        wide = np.frombuffer(held, np.float32).astype(np.float64).tobytes()
        entry[1], entry[4], entry[5] = "<f8", at, len(wide)
        return wide

    return _repacked(block, "gru.labels", lay_out)


class _Told:
    """What ``read_gru_tail`` takes for ``native_phase``: told seconds."""

    def __init__(self):
        self.seconds = []

    def observe(self, seconds):
        self.seconds.append((threading.current_thread().name, seconds))


class TestTheLibrarysTailIsTheInterpreters:
    """Where the native library loaded the GRU tail is read through it
    (the hop, the check and the copies a call each, the kept blocks'
    headers the interpreter's), held to the interpreter's read array for
    array, tally for tally and error for error (ISSUE 46)."""

    @pytest.mark.parametrize("cap", [0, 1, 3, 5, 10, 12, 29, 30, 31, 1000])
    def test_caps_below_at_and_above_the_total(self, tmp_path, monkeypatch, with_the_library, cap):
        path, extents = _write_upload(tmp_path, "even")  # 6 blocks of 5 sequences
        by_library, interpreted = _both_tails(monkeypatch, path, cap)
        assert by_library == interpreted
        assert by_library[1] == (min(-(-cap // 5), 6), 6 - min(-(-cap // 5), 6))
        assert by_library[0] == [(a.tobytes(), a.dtype.str, a.shape) for a in _reference_gru(path, cap)]

    @pytest.mark.parametrize("which", ["whole", "inner", "from-second-block", "to-last-block", "empty-range", "end-past-the-file"])
    @pytest.mark.parametrize("cap", [2, 8, 1000])
    @pytest.mark.parametrize("name", sorted(_UPLOADS) + sorted(_SPANNED))
    def test_the_two_tails_tell_the_same(self, tmp_path, monkeypatch, with_the_library, name, cap, which):
        """Uploads with blocks of other kinds between, ``train`` blocks
        of no sequences (passed over alike, and counted as decoded), a
        torn tail, no ``train`` block at all, under ``offset`` and
        ``end``, an empty range and an ``end`` past the file."""
        path, extents = _write_upload(tmp_path, name) if name in _UPLOADS else _write_blocks(tmp_path, name, _SPANNED[name], seed=31)
        offset, end = {"empty-range": (extents[-1][0], extents[-1][0]), "end-past-the-file": (0, 10**12)}.get(which) or _bounds(extents, which)
        by_library, interpreted = _both_tails(monkeypatch, path, cap, offset, end)
        assert by_library == interpreted
        assert by_library[0] == [(a.tobytes(), a.dtype.str, a.shape) for a in _reference_gru(path, cap, offset, end)]
        assert sum(by_library[1]) == len(wire.scan_block_extents(path, offset, end))

    @pytest.mark.parametrize("tail", [1, 7, 15, 16, 17, 40])
    def test_a_torn_tail_of_any_length_ends_the_hop(self, tmp_path, monkeypatch, with_the_library, tail):
        """Less than a preamble, a preamble alone, a preamble and part of
        the header: the whole blocks before it are the range."""
        path, extents = _write_upload(tmp_path, "even")
        whole = path.read_bytes()
        path.write_bytes(whole + whole[:tail])
        by_library, interpreted = _both_tails(monkeypatch, path, 8)
        assert by_library == interpreted and by_library[1] == (2, 4)
        assert by_library[0] == [(a.tobytes(), a.dtype.str, a.shape) for a in _reference_gru(path, 8)]

    @pytest.mark.parametrize("verify_crc", [True, False])
    @pytest.mark.parametrize("corrupt", [(5,), (4,), (4, 5), (3,), (0,), (0, 3)])
    def test_a_corrupt_kept_block_raises_and_a_hopped_one_is_not_the_tails(self, tmp_path, monkeypatch, with_the_library, corrupt, verify_crc):
        """Cap 8 of "even" keeps the last two blocks. A flipped byte
        there raises for that block (for the newest of two, the first
        the interpreter's path checks), before any array is handed back;
        one in a block hopped over is the MLP leg's to find, and with
        ``verify_crc`` off nothing is checked."""
        path, extents = _write_upload(tmp_path, "even")
        for at in corrupt:
            _flip(path, extents[at])
        by_library, interpreted = _both_tails(monkeypatch, path, 8, verify_crc=verify_crc)
        assert by_library == interpreted
        if verify_crc and max(corrupt) >= 4:
            assert by_library == (("WireError", f"block crc mismatch at byte {extents[max(corrupt)][0]}"), (0, 0))
        else:
            assert by_library[1] == (2, 4) and by_library[0][1][2] == (8,)

    @pytest.mark.parametrize("at", [5, 4, 1])
    @pytest.mark.parametrize("fault", sorted(f for f in _HEADERS if "crc32" in f))
    def test_a_header_with_no_usable_crc32(self, tmp_path, monkeypatch, with_the_library, fault, at):
        """A ``crc32`` that is no integer of 32 bits matches no payload:
        a mismatch at its block where the block is kept (and a
        ``KeyError`` where the header states none), nothing where it is
        hopped over."""
        rng = np.random.default_rng(32)
        blocks = [_block(rng, 7, 5, 3) for _ in range(6)]
        blocks[at] = _HEADERS[fault][0](blocks[at])
        path = tmp_path / "reheaded.dfb"
        path.write_bytes(b"".join(blocks))
        by_library, interpreted = _both_tails(monkeypatch, path, 8)
        assert by_library == interpreted
        edge = sum(len(b) for b in blocks[:at])
        if at == 1:
            assert by_library[1] == (2, 4)
        else:
            assert by_library == (("KeyError", "'crc32'") if fault == "no-crc32" else ("WireError", f"block crc mismatch at byte {edge}"), (0, 0))
        with_crc_off = _both_tails(monkeypatch, path, 8, verify_crc=False)
        assert with_crc_off[0] == with_crc_off[1] and with_crc_off[0][1] == (2, 4)

    @pytest.mark.parametrize("offset_past_it", [False, True])
    @pytest.mark.parametrize("at", [0, 2, 5])
    def test_garbage_at_a_block_edge(self, tmp_path, monkeypatch, with_the_library, at, offset_past_it):
        """Anything but the magic at a block's edge stops the library's
        hop, and the interpreter's takes the range from that byte: to
        the ``WireError`` it raises, wherever the block lies; a range
        that begins past it reads as if it were not there."""
        path, extents = _write_upload(tmp_path, "even")
        buf = bytearray(path.read_bytes())
        buf[extents[at][0] + 3] = ord("2")
        path.write_bytes(bytes(buf))
        offset = extents[at][1] if offset_past_it else 0
        by_library, interpreted = _both_tails(monkeypatch, path, 3, offset=offset)
        assert by_library == interpreted
        if offset_past_it:
            assert by_library[1] == ((1, 4 - at) if at < 5 else (0, 0))
        else:
            assert by_library == (("WireError", f"bad block magic at byte {extents[at][0]}: b'DFB2'"), (0, 0))

    @pytest.mark.parametrize("older_too", [False, True])
    @pytest.mark.parametrize("fault", ["no-json", "no-sequences-column", "an-unknown-encoding", "not-a-train-block-s-kind"])
    def test_a_kept_header_the_interpreter_cannot_read(self, tmp_path, monkeypatch, with_the_library, fault, older_too):
        """The kept blocks' headers are the interpreter's on both paths,
        and so are their errors. Where a newer kept block is corrupt as
        well (``older_too``: the fault lies in the older of two kept),
        the mismatch is raised, as by the path that checks a block
        before it parses the next."""
        rng = np.random.default_rng(33)
        blocks = [_block(rng, 7, 5, 3) for _ in range(6)]
        at = 4 if older_too else 5
        blocks[at] = {
            "no-json": _HEADERS["no-json"][0], "no-sequences-column": _without_sequences, "an-unknown-encoding": _packed_lengths,
            "not-a-train-block-s-kind": lambda b: _reheaded(b, lambda h: _set(h, kind="other")),
        }[fault](blocks[at])
        path = tmp_path / "reheaded.dfb"
        path.write_bytes(b"".join(blocks))
        edges = np.cumsum([0] + [len(b) for b in blocks]).tolist()
        if older_too:
            _flip(path, (edges[5], edges[6]))
        by_library, interpreted = _both_tails(monkeypatch, path, 8)
        assert by_library == interpreted
        if older_too:
            assert by_library[0] == ("WireError", f"block crc mismatch at byte {edges[5]}")
        elif fault == "not-a-train-block-s-kind":
            assert by_library[1] == (3, 3)  # passed over, and an older block kept in its place
        else:
            assert by_library[0][0] in {"no-json": ("JSONDecodeError",), "no-sequences-column": ("KeyError",), "an-unknown-encoding": ("WireError",)}[fault]

    @pytest.mark.parametrize("cap", [4, 9, 1000])
    @pytest.mark.parametrize("column", ["labels-all-zero", "lengths-all-zero", "labels-of-another-type", "as-written"])
    def test_a_column_that_is_no_view_of_the_mapping(self, tmp_path, monkeypatch, with_the_library, column, cap):
        """A kept block's column ``zero``-encoded (``encode_block``
        writes one that is all zeros so) is an array of zeros and no
        view; one stated in another type makes the whole column that of
        ``np.concatenate``'s promotion. Laid by the library where the
        pieces are bytes end to end, by numpy where they are not."""
        rng = np.random.default_rng(34)
        blocks = [_gru_block(rng, 3 + i) for i in range(5)]
        if column == "labels-all-zero":
            blocks[3] = _gru_block(rng, 6, **{"gru.labels": np.zeros(6, np.float32)})
        elif column == "lengths-all-zero":
            blocks[4] = _gru_block(rng, 7, **{"gru.lengths": np.zeros(7, np.int32)})
        elif column == "labels-of-another-type":
            blocks[3] = _wider_gru_labels(blocks[3])
        path = tmp_path / "columns.dfb"
        path.write_bytes(b"".join(blocks))
        by_library, interpreted = _both_tails(monkeypatch, path, cap)
        assert by_library == interpreted
        assert by_library[0] == [(a.tobytes(), a.dtype.str, a.shape) for a in _reference_gru(path, cap)]
        assert by_library[0][1][1] == ("<f8" if column == "labels-of-another-type" and cap > 7 else "<f4")

    def test_the_library_parses_the_kept_headers_alone_and_is_counted_once(self, tmp_path, monkeypatch, check_path):
        """With the library, ``json.loads`` runs once a kept block and
        for no block hopped over, the generator hops nothing, no
        ``zlib.crc32`` is called, and ``native_phase`` is told the
        library's seconds once a read, by the reading thread; without
        it never. An empty range tells nothing on either."""
        path, extents = _write_upload(tmp_path, "even")
        parsed, hopped, checked = [], [], []
        loads, hop, crc32 = json.loads, wire._hop_mapped, zlib.crc32
        monkeypatch.setattr(wire.json, "loads", lambda *a, **kw: (parsed.append(1), loads(*a, **kw))[1])
        monkeypatch.setattr(wire, "_hop_mapped", lambda *a: (hopped.append(1), hop(*a))[1])
        monkeypatch.setattr(wire.zlib, "crc32", lambda *a: (checked.append(1), crc32(*a))[1])
        told, tally = _Told(), wire.BlockTally()
        got = wire.read_gru_tail(path, 8, tally=tally, native_phase=told)
        assert got.labels.shape == (8,) and (tally.decoded, tally.hopped) == (2, 4) and len(parsed) == 2
        by_library = check_path[0] == "library"
        assert (len(hopped), len(checked)) == ((0, 0) if by_library else (1, 2))
        assert [t for t, _ in told.seconds] == ([threading.current_thread().name] if by_library else [])
        assert all(0 < s < 5 for _, s in told.seconds)
        wire.read_gru_tail(path, 8, offset=extents[3][0], end=extents[3][0], native_phase=told)
        assert len(told.seconds) == int(by_library)

    def test_the_library_is_told_even_where_the_read_raises(self, tmp_path, with_the_library):
        path, extents = _write_upload(tmp_path, "even")
        _flip(path, extents[5])
        told = _Told()
        with pytest.raises(wire.WireError, match=f"block crc mismatch at byte {extents[5][0]}"):
            wire.read_gru_tail(path, 8, native_phase=told)
        assert len(told.seconds) == 1


def test_round_reports_blocks_decoded_and_hopped(tmp_path):
    """A round on a multi-block upload with a small GRU cap: the GRU leg
    decodes only the blocks that hold the cap and hops the rest, the
    resident MLP leg decodes every block, and the split, the event's
    fields and the ``/metrics`` counter say so."""
    from dragonfly2_tpu.trainer import metrics as M

    hid = host_id_v2("6.6.6.6", "s6")
    storage = TrainerStorage(tmp_path / "t")
    blocks = [synth.make_download_records(16, seed=60 + i) for i in range(9)]
    for recs in blocks:
        storage.append_download_blocks(hid, wire.encode_train_block(recs))
    storage.mark_download_round(hid)
    per_block = [
        extract_piece_sequences(records_to_columns(recs)).sequences.shape[0] for recs in blocks
    ]
    cap = per_block[-1] + per_block[-2] - 1  # held by the last two blocks
    assert per_block[-1] < cap
    training = Training(
        storage,
        RecordingManager(),
        TrainingConfig(
            mlp=FitConfig(hidden_dims=(8,), batch_size=64, epochs=1),
            gru_config=FitConfig(hidden_dims=(4,), batch_size=16, epochs=1),
            gru_max_sequences=cap,
            gru_min_sequences=1,
            min_topology_records=10**9,  # no topology uploaded here
            streaming=False,
            auto_mesh=False,
        ),
    )
    series = {
        (leg, fate): M.FIT_BLOCKS_TOTAL.labels(leg, fate).value
        for leg in ("mlp", "gru")
        for fate in ("decoded", "hopped")
    }
    outcome = training.train("6.6.6.6", "s6")
    assert outcome.mlp_error is None and outcome.gru_error is None, outcome
    gru, mlp = outcome.splits["gru"], outcome.splits["mlp"]
    assert (gru.blocks_decoded, gru.blocks_hopped) == (2, len(blocks) - 2)
    assert (mlp.blocks_decoded, mlp.blocks_hopped) == (len(blocks), 0)
    assert gru.fields()["blocks_hopped"] == len(blocks) - 2
    moved = {k: M.FIT_BLOCKS_TOTAL.labels(*k).value - v for k, v in series.items()}
    assert moved == {
        ("mlp", "decoded"): len(blocks), ("mlp", "hopped"): 0,
        ("gru", "decoded"): 2, ("gru", "hopped"): len(blocks) - 2,
    }
