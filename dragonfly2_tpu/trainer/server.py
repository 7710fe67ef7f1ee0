"""Trainer server assembly (reference trainer/trainer.go:49-187): manager
client + storage + training core + gRPC server, Serve/Stop lifecycle."""

from __future__ import annotations

import threading
from dataclasses import dataclass
from pathlib import Path

from dragonfly2_tpu.rpc import glue
from dragonfly2_tpu.schema import native
from dragonfly2_tpu.trainer.service import SERVICE_NAME, TrainerService
from dragonfly2_tpu.trainer.storage import TrainerStorage
from dragonfly2_tpu.trainer.train import FitConfig, GNNFitConfig
from dragonfly2_tpu.trainer.training import Training, TrainingConfig
from dragonfly2_tpu.utils import dflog, flight, profiling

logger = dflog.get("trainer.server")


@dataclass
class TrainerServerConfig:
    data_dir: str = "/tmp/dragonfly2-trainer"
    listen: str = "127.0.0.1:0"
    manager_address: str = ""
    # fit knobs (subset; full control through TrainingConfig in-process)
    mlp_epochs: int = 3
    mlp_batch_size: int = 8192
    gnn_epochs: int = 60
    min_download_records: int = 1
    min_topology_records: int = 1
    # third model family: GRU over per-(task,parent) piece-cost
    # sequences extracted from the same download records (our addition
    # over the reference's MLP+GNN pair — see trainer/training.py). ON
    # by default since round 5, matching TrainingConfig.gru: the ml
    # evaluator's model-based bad-node detection must train under
    # production defaults.
    gru: bool = True
    gru_min_sequences: int = 8
    incremental: bool = False
    streaming: bool = True
    streaming_workers: int = 1
    # data-parallel fit mesh over every addressable chip when >1 is
    # present (TrainingConfig.auto_mesh; parallel.mesh.auto_dp_mesh) —
    # the ICI data-parallel fit is the production default, disable only
    # to pin a deploy to single-device fits
    auto_mesh: bool = True
    # on-demand jax.profiler capture: a non-empty dir writes one XLA
    # trace per round under <profile_dir>/round, around the three fits
    # (view with TensorBoard); settable per-deploy via config file or
    # DF_TRAINER_PROFILE_DIR
    profile_dir: str = ""
    # elastic restart: per-(model, host) fit snapshots under this dir —
    # a crashed fit resumes from its last epoch after the process comes
    # back (trainer/checkpoint.py); "" keeps the reference's
    # retrain-from-zero behavior
    checkpoint_dir: str = ""
    # run fits inline with the Train RPC (tests/debug) instead of async
    synchronous: bool = False
    # Prometheus /metrics endpoint (reference trainer :8000): -1 = disabled
    metrics_port: int = -1
    metrics_host: str = "127.0.0.1"
    # cluster telemetry push cadence (utils/telemetry.py); <= 0 disables
    telemetry_interval: float = 15.0
    # gRPC TLS: PEM file paths; tls_client_ca_file enforces mTLS
    tls_cert_file: str = ""
    tls_key_file: str = ""
    tls_client_ca_file: str = ""
    # client-side root (and optional mTLS client pair) for the manager
    manager_tls_ca_file: str = ""
    manager_tls_server_name: str = ""
    manager_tls_client_cert_file: str = ""
    manager_tls_client_key_file: str = ""


class TrainerServer:
    def __init__(self, config: TrainerServerConfig):
        self.cfg = config
        # the native library, loaded (and on a machine's first start built:
        # make, some 4 s) while the server comes up and not inside the first
        # round's load, whose check of a span's blocks is a call of it
        # (schema/wire.py); a round that comes sooner waits in load() for the
        # build, and where no toolchain is found the rounds run without it
        threading.Thread(target=native.load, name="trainer.native_load", daemon=True).start()
        Path(config.data_dir).mkdir(parents=True, exist_ok=True)
        self.storage = TrainerStorage(config.data_dir)

        self._manager_channel = None
        manager_client = None
        if config.manager_address:
            self._manager_channel = glue.dial(
                config.manager_address,
                **glue.dial_tls_args(
                    config.manager_tls_ca_file,
                    config.manager_tls_server_name,
                    config.manager_tls_client_cert_file,
                    config.manager_tls_client_key_file,
                ),
            )
            from dragonfly2_tpu.manager.service import ManagerGrpcClientAdapter

            manager_client = ManagerGrpcClientAdapter(self._manager_channel)

        self.training = Training(
            self.storage,
            manager_client=manager_client,
            config=TrainingConfig(
                mlp=FitConfig(
                    epochs=config.mlp_epochs, batch_size=config.mlp_batch_size
                ),
                gnn=GNNFitConfig(epochs=config.gnn_epochs),
                min_download_records=config.min_download_records,
                min_topology_records=config.min_topology_records,
                gru=config.gru,
                gru_min_sequences=config.gru_min_sequences,
                incremental=config.incremental,
                clear_after_train=not config.incremental,
                streaming=config.streaming,
                streaming_workers=config.streaming_workers,
                auto_mesh=config.auto_mesh,
                profile_dir=config.profile_dir,
                checkpoint_dir=config.checkpoint_dir,
            ),
        )
        self.service = TrainerService(
            self.storage, self.training, synchronous=config.synchronous
        )
        self._grpc = None
        self.telemetry_reporter = None

    def serve(self) -> str:
        # flight recorder: stall/crash dumps + the Diagnose snapshot RPC
        flight.install("trainer")
        # continuous profiler: always-on sampler + phase ledger
        # (/debug/prof, Diagnose profile section, dump windows)
        profiling.install("trainer")
        flight.register_probe(
            "trainer.storage",
            lambda: {"host_ids": self.storage.host_ids()},
        )
        from dragonfly2_tpu.rpc.diagnose import DiagnoseService

        self._grpc, port = glue.serve(
            {SERVICE_NAME: self.service, glue.DIAGNOSE_SERVICE: DiagnoseService()},
            self.cfg.listen,
            **glue.serve_tls_args(
                self.cfg.tls_cert_file, self.cfg.tls_key_file, self.cfg.tls_client_ca_file
            ),
        )
        addr = f"{self.cfg.listen.rsplit(':', 1)[0]}:{port}"
        from dragonfly2_tpu.utils.metrics import set_build_info

        set_build_info("trainer")
        if self._manager_channel is not None and self.cfg.telemetry_interval > 0:
            # cluster telemetry: ingest throughput + fit freshness to the
            # manager over the channel already dialed for CreateModel
            from dragonfly2_tpu.utils.telemetry import TelemetryReporter
            from dragonfly2_tpu.version import __version__

            def sections():
                return {
                    "build": {"service": "trainer", "version": __version__},
                    "endpoints": {
                        "rpc": addr,
                        "metrics": getattr(self, "metrics_addr", "") or "",
                    },
                }

            self.telemetry_reporter = TelemetryReporter(
                glue.ServiceClient(self._manager_channel, glue.TELEMETRY_SERVICE),
                service="trainer",
                instance=addr,
                prefixes=("dragonfly_trainer_",),
                interval=self.cfg.telemetry_interval,
                collect_sections=sections,
            )
            self.telemetry_reporter.start()
        if self.cfg.metrics_port >= 0:
            from dragonfly2_tpu.trainer import metrics  # noqa: F401
            from dragonfly2_tpu.utils.metrics import MetricsServer, default_registry

            self._metrics = MetricsServer(default_registry, host=self.cfg.metrics_host, port=self.cfg.metrics_port)
            # liveness on the scrape port (/healthz): the gRPC plane up
            self._metrics.register_health("trainer", lambda: self._grpc is not None)
            self.metrics_addr = self._metrics.start()
            logger.info("trainer metrics on %s", self.metrics_addr)
        logger.info("trainer gRPC on %s", addr)
        return addr

    def stop(self) -> None:
        if self.telemetry_reporter is not None:
            self.telemetry_reporter.stop()
        if getattr(self, "_metrics", None) is not None:
            self._metrics.stop()
        if self._grpc is not None:
            self._grpc.stop(grace=2).wait(5)
        if self._manager_channel is not None:
            self._manager_channel.close()
        # the reference clears trainer storage on shutdown
        # (trainer/trainer.go:156-161) unless running incremental rounds
        if not self.cfg.incremental:
            self.storage.clear()


def build(config_path, overrides):
    from dragonfly2_tpu.cli.config import load_config

    cfg = load_config(
        TrainerServerConfig, config_path, env_prefix="DF_TRAINER", overrides=overrides
    )
    return TrainerServer(cfg)
