"""Colocated server assembly: one ``TrainerServer`` and one
``SchedulerServer`` (``algorithm="ml"``) in one process, the scheduler's
announcer dialing its own trainer over loopback.

Nothing here selects an implementation. Models move by the program's own
path: a round's ``create_model`` → the manager's registry → (activation,
the operator's) → ``ModelRefresher.refresh_once()`` →
``ScoringService.install`` with every reachable rung warmed before the
swap. What the two planes share is the interpreter and the device queue:
a scoring batch waits behind whatever device program the round has
enqueued (``scheduler.score_forward`` shows it), and a decision's thread
waits for whatever call of a fit holds the interpreter. The trainer
bounds both: a round feeds the chip, runs its epochs and frees its host
arrays in slices (``trainer/train.py``), so neither wait is longer than
a slice.
"""

from __future__ import annotations

import gc
import sys
from dataclasses import dataclass, field
from pathlib import Path

from dragonfly2_tpu.scheduler import scheduling
from dragonfly2_tpu.scheduler.server import SchedulerServer, SchedulerServerConfig
from dragonfly2_tpu.trainer import metrics as trainer_metrics
from dragonfly2_tpu.trainer.server import TrainerServer, TrainerServerConfig
from dragonfly2_tpu.utils import dflog, profiling

logger = dflog.get("colocated.server")


@dataclass
class ColocatedConfig:
    # the scheduler's records under <data_dir>/scheduler, the trainer's
    # uploads under <data_dir>/trainer
    data_dir: str = "/tmp/dragonfly2-colocated"
    # the scheduler's gRPC address (what peers dial) and the trainer's
    # (what this process's own announcer dials)
    listen: str = "127.0.0.1:0"
    trainer_listen: str = "127.0.0.1:0"
    manager_address: str = ""
    # one Prometheus endpoint for the process (the registry is shared):
    # -1 = disabled
    metrics_port: int = -1
    metrics_host: str = "127.0.0.1"
    # any other field of SchedulerServerConfig / TrainerServerConfig; one
    # that the assembly sets itself (below) is refused as a duplicate
    scheduler: dict = field(default_factory=dict)
    trainer: dict = field(default_factory=dict)


def trainer_config(cfg: ColocatedConfig) -> TrainerServerConfig:
    """The trainer half: resident fits (every pair of the upload on the
    chip for the epoch), registering with the same manager."""
    return TrainerServerConfig(
        **cfg.trainer,
        data_dir=str(Path(cfg.data_dir) / "trainer"),
        listen=cfg.trainer_listen,
        manager_address=cfg.manager_address,
        streaming=False,
    )


def scheduler_config(cfg: ColocatedConfig, trainer_address: str) -> SchedulerServerConfig:
    """The scheduler half: the ml evaluator, uploading to
    ``trainer_address`` (its own process's trainer, once that serves)."""
    return SchedulerServerConfig(
        **cfg.scheduler,
        data_dir=str(Path(cfg.data_dir) / "scheduler"),
        listen=cfg.listen,
        manager_address=cfg.manager_address,
        trainer_address=trainer_address,
        algorithm="ml",
        metrics_port=cfg.metrics_port,
        metrics_host=cfg.metrics_host,
    )


# sys.setswitchinterval for the process: how long a thread that wants the
# interpreter waits before the one that holds it is asked to hand it over.
# In a round's first seconds three fits trace and decode in Python, and a
# decision, which gets the interpreter back some tens of times, queues
# behind them each time: at Python's own 5 ms the decisions' p90 beside a
# round read 74-104 ms, at 0.5 ms 40-45 ms (PERF.md, PR 28). It does not
# move the longest decision: bounded slices do that. Not a setting: no
# deployment of this service has needed another value
SWITCH_INTERVAL_S = 0.0005


_ROUND = trainer_metrics.PH_ROUND
_WALK, _ASSEMBLE = trainer_metrics.PH_MLP.load_walk, trainer_metrics.PH_MLP.load_assemble
_MLP_FIT, _GNN_FIT, _GRU_FIT = (leg.fit for leg in trainer_metrics.LEG_PHASES.values())


def round_stretch() -> str:
    """What the trainer's round is doing now, for a decision that begins
    now (``scheduling.stretch_provider``): what a decision waits for
    follows it. ``walk``: the resident load walks the upload's headers,
    the interpreter's work alone. ``assemble``: the load's workers check
    and copy, off the interpreter lock. ``fit_shared``: past the load, at
    least two legs' fits still running: their epochs' slices share the
    chip's queue. ``fit_alone``: one leg left, or none. ``idle``: no round.
    A few reads of open-phase counts, no lock."""
    if not _ROUND.active:
        return "idle"
    if _WALK.active:
        return "walk"
    if _ASSEMBLE.active:
        return "assemble"
    legs = (_MLP_FIT.active > 0) + (_GNN_FIT.active > 0) + (_GRU_FIT.active > 0)
    return "fit_shared" if legs >= 2 else "fit_alone"


def settle() -> float:
    """What the process takes on once both servers are built; returns the
    switch interval that was there. The interpreter's switch interval
    becomes ``SWITCH_INTERVAL_S``. And every object alive now (the modules,
    the servers, what they jitted) lives as long as the process, so it
    is put out of the collector's reach: a full collection stops every
    thread for as long as it takes to walk what it tracks (0.2 s at
    460,000 objects beside a round, twice a round), and a decision
    waits with them; after this it walks what came since, and each such
    walk is a ``process.gc_full`` span. The scheduler's decisions are
    booked by the stretch of the trainer's round they begin in: this is
    the one process in which the scheduler can know it. All of it is the
    process's, not a server's (the benchmark's generators build the two
    servers themselves and call this); ``ColocatedServer.stop()`` puts
    back what a process that goes on would feel."""
    was = sys.getswitchinterval()
    sys.setswitchinterval(SWITCH_INTERVAL_S)
    gc.collect()
    gc.freeze()
    profiling.watch_collections()
    scheduling.stretch_provider = round_stretch
    return was


class ColocatedServer:
    def __init__(self, config: ColocatedConfig):
        self.cfg = config
        self._switch_interval_was: "float | None" = None
        self.trainer = TrainerServer(trainer_config(config))
        # built in serve(): its announcer dials the trainer's bound port
        self.scheduler: "SchedulerServer | None" = None
        scheduler_config(config, "")  # a bad scheduler key fails here, not mid-serve

    def serve(self) -> dict:
        """Serve both; returns ``{"trainer": addr, "scheduler": addr}``."""
        trainer_addr = self.trainer.serve()
        try:
            self.scheduler = SchedulerServer(scheduler_config(self.cfg, trainer_addr))
            scheduler_addr = self.scheduler.serve()
        except Exception:
            self.stop()
            raise
        if getattr(self.scheduler, "metrics_addr", None):
            self.metrics_addr = self.scheduler.metrics_addr
        self._switch_interval_was = settle()
        logger.info("colocated: trainer on %s, scheduler on %s", trainer_addr, scheduler_addr)
        return {"trainer": trainer_addr, "scheduler": scheduler_addr}

    def stop(self) -> None:
        # the scheduler first: its announcer holds a channel to the trainer
        if self.scheduler is not None:
            self.scheduler.stop()
            self.scheduler = None
        self.trainer.stop()
        # a process that goes on after the service runs and collects as before
        gc.unfreeze()
        scheduling.stretch_provider = None
        if self._switch_interval_was is not None:
            sys.setswitchinterval(self._switch_interval_was)
            self._switch_interval_was = None


def build(config_path, overrides):
    from dragonfly2_tpu.cli.config import load_config

    cfg = load_config(
        ColocatedConfig, config_path, env_prefix="DF_COLOCATED", overrides=overrides
    )
    return ColocatedServer(cfg)
