"""Trainer RPC service: the `Train` client-stream endpoint (reference
trainer/service/service_v1.go:59-162) plus payload-format negotiation.

First message keys the uploading scheduler (hostID = sha256(ip,hostname),
reference :87); each chunk appends to that host's dataset file — CSV
chunks to ``*.csv``, binary columnar chunks (schema/wire.py) to
``*.dfb`` — and on EOF the fit runs asynchronously (:155-159) so the
stream ack isn't held for minutes of training.

`Capabilities` advertises the payload formats this trainer accepts; the
announcer probes it before uploading and falls back to CSV when the RPC
is missing (old trainer) or the binary token is absent.
"""

from __future__ import annotations

import threading

from dragonfly2_tpu.rpc import gen  # noqa: F401
import trainer_pb2  # noqa: E402

from dragonfly2_tpu.schema import wire
from dragonfly2_tpu.trainer.storage import TrainerStorage
from dragonfly2_tpu.trainer.training import Training
from dragonfly2_tpu.trainer import metrics as M
from dragonfly2_tpu.utils import dflog
from dragonfly2_tpu.utils.idgen import host_id_v2

logger = dflog.get("trainer.rpc")

from dragonfly2_tpu.rpc.glue import TRAINER_SERVICE as SERVICE_NAME


class TrainerService:
    # newest-preferred order; Capabilities returns it verbatim
    TRAIN_FORMATS = (wire.FORMAT_NAME, wire.CSV_FORMAT_NAME)

    def __init__(self, storage: TrainerStorage, training: Training, synchronous: bool = False):
        self.storage = storage
        self.training = training
        # synchronous=True runs the fit inline (tests); production forks
        self.synchronous = synchronous
        self.train_total = 0
        self.train_failure_total = 0  # mirrored into Prometheus (metrics.py)

    def Capabilities(self, request, context):
        return trainer_pb2.CapabilitiesResponse(train_formats=list(self.TRAIN_FORMATS))

    def Train(self, request_iterator, context):
        ip = hostname = None
        host_id = None
        self.train_total += 1
        M.TRAIN_TOTAL.inc()
        try:
            for req in request_iterator:
                if host_id is None:
                    ip, hostname = req.ip, req.hostname
                    host_id = host_id_v2(ip, hostname)
                which = req.WhichOneof("request")
                if which == "train_mlp":
                    M.DATASET_BYTES_TOTAL.labels("download").inc(len(req.train_mlp.dataset))
                    self.storage.append_download(host_id, req.train_mlp.dataset)
                elif which == "train_gnn":
                    M.DATASET_BYTES_TOTAL.labels("topology").inc(len(req.train_gnn.dataset))
                    self.storage.append_network_topology(host_id, req.train_gnn.dataset)
                elif which == "train_mlp_binary":
                    M.DATASET_BYTES_TOTAL.labels("download_binary").inc(
                        len(req.train_mlp_binary.dataset)
                    )
                    self.storage.append_download_blocks(
                        host_id, req.train_mlp_binary.dataset
                    )
                elif which == "train_gnn_binary":
                    M.DATASET_BYTES_TOTAL.labels("topology_binary").inc(
                        len(req.train_gnn_binary.dataset)
                    )
                    self.storage.append_network_topology_blocks(
                        host_id, req.train_gnn_binary.dataset
                    )
        except Exception:
            self.train_failure_total += 1
            M.TRAIN_FAILURE_TOTAL.inc()
            if host_id is not None:
                # a broken stream may have landed half an upload round —
                # for the binary files a torn block would poison every
                # later append (its length prefix points into the new
                # data), so cut every file back to its last complete
                # round before the announcer retries
                self.storage.truncate_to_round(host_id)
            raise

        if host_id is not None:
            self.fit_after_stream(ip, hostname)
        return trainer_pb2.TrainResponse()

    def fit_after_stream(self, ip: str, hostname: str) -> "threading.Thread | None":
        """A host's stream is complete: everything appended so far is
        whole rounds, so mark the byte boundary incremental offsets may
        commit up to, and fork the host's round (reference
        service_v1.go:155-159) so the stream's ack is not held for
        minutes of training. ``synchronous`` runs it inline. -> the
        round's thread, for a caller that staged the upload itself and
        waits for the round (the Train handler does not)."""
        self.storage.mark_download_round(host_id_v2(ip, hostname))
        if self.synchronous:
            self.training.train(ip, hostname)
            return None
        from dragonfly2_tpu.utils import tracing

        # the async fit must stay in the uploader's trace: hand
        # the rpc.Train span to the worker thread (contextvars
        # don't cross threads on their own)
        thread = threading.Thread(
            target=self._train_safely,
            args=(ip, hostname, tracing.current_span()),
            name="trainer.fit",
            daemon=True,
        )
        thread.start()
        return thread

    def _train_safely(self, ip: str, hostname: str, parent_span=None) -> None:
        from dragonfly2_tpu.utils import tracing

        try:
            with tracing.use_span(parent_span):
                outcome = self.training.train(ip, hostname)
            if not outcome.ok:
                self.train_failure_total += 1
        except Exception:
            self.train_failure_total += 1
            logger.exception("training run failed for %s/%s", ip, hostname)
