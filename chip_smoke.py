#!/usr/bin/env python3
"""Chip smoke: the train→serve loop, once, on the accelerator.

    python3 chip_smoke.py [--seed N]

Section 1 drives the main path through the server classes the binaries
use — ``ManagerServer``, ``TrainerServer`` and ``SchedulerServer`` with
``algorithm="ml"``, talking gRPC over loopback in this one process — at
the widths a default deploy has: probe plane → topology engine → record
sink → ``Announcer.train_once`` → MLP/GNN/GRU fits → ``CreateModel`` →
activation through the manager API → ``ModelRefresher`` →
``ScoringService`` scoring ``AnnouncePeer`` decisions from in-process
daemons. Section 2 compiles the kernels no service calls yet (flash,
ring, Ulysses, sharded GNN, orbax) and checks them against their oracles.

Every check prints ``ok`` or ``FAIL``; any FAIL exits non-zero. Without
an accelerator the script exits non-zero before doing any work: nothing
here may carry on on the CPU. One process holds the chip — no child is
started. A ``summary:`` line carries the per-phase walls and compile
counts; the last stdout line is the result, one JSON object with exactly
``ok`` and ``device``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import random
import shutil
import sys
import tempfile
import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from pathlib import Path

import numpy as np

MIB = 1 << 20

# bf16 matmul inputs (8 mantissa bits) against a float32 reference:
# stated tolerance on a log-cost prediction of magnitude ~1–6
MLP_BF16_ATOL = 0.1
# MXU default precision truncates f32 matmul inputs to bf16, so kernel vs
# oracle deltas sit ~1e-2 absolute on O(1) outputs (the CPU suite pins
# float32 equality at 2e-4 through the interpreter)
KERNEL_TOL = 5e-2


@dataclass(frozen=True)
class Size:
    """How much data and traffic the main path moves. Widths (model
    dims, epochs, batch sizes, probe fan-out) are not here: they are the
    servers' defaults."""

    # columnar-v1 download blocks one scheduler uploads: ≥ one reference
    # upload chunk, above the trainer's 64 MiB streaming threshold
    download_bytes: int = 128 * MIB
    # seeded records pushed one by one through the scheduler's sink; the
    # sink's own block file is then replicated up to download_bytes
    # (1.8 ms/record of host Python is not what this run is for)
    unique_records: int = 16_384
    hosts: int = 1024  # announced fleet, each syncing probes
    probe_rounds: int = 2  # SyncProbes rounds per host
    daemons: int = 16  # real in-process peers
    tasks: int = 12  # downloads per daemon per served model
    demand_tasks: int = 256  # preheat demand series


class SmokeFailure(Exception):
    """A check missed (or a phase raised); the run exits non-zero."""


class Report:
    """Prints and keeps every check, and each phase's wall and compiles."""

    def __init__(self):
        self.failed: list[str] = []
        self.phases: dict[str, dict] = {}

    def check(self, name: str, ok: bool, detail: object = "") -> bool:
        print(f"check {name}: {'ok' if ok else 'FAIL'} {detail}".rstrip(), flush=True)
        if not ok:
            self.failed.append(name)
        return bool(ok)

    def require(self, name: str, ok: bool, detail: object = "") -> None:
        """A check later phases cannot run without."""
        if not self.check(name, ok, detail):
            raise SmokeFailure(name)

    @contextlib.contextmanager
    def phase(self, name: str):
        t0, c0 = time.perf_counter(), dict(_COMPILES)
        try:
            yield
        except SmokeFailure:
            raise
        except Exception as e:
            traceback.print_exc()
            self.check(f"{name} ran to its end", False, repr(e))
            raise SmokeFailure(name) from e
        finally:
            rec = {"wall_s": round(time.perf_counter() - t0, 2)}
            rec.update(
                {k: round(_COMPILES[k] - c0[k], 2) for k in _COMPILES}
            )
            self.phases[name] = rec
            print(f"phase {name}: {json.dumps(rec)}", flush=True)


# compile accounting from jax's own monitoring events: requests that
# consulted the persistent cache, its hits and writes, and the seconds
# the backend compiler actually ran
_COMPILES = {"cache_requests": 0, "cache_hits": 0, "cache_writes": 0, "compile_s": 0.0}
_watching = False


def _watch_compiles() -> None:
    global _watching
    if _watching:
        return
    _watching = True
    import jax

    names = {
        "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_writes",
    }

    def on_event(event: str, **_):
        key = names.get(event)
        if key:
            _COMPILES[key] += 1

    def on_duration(event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILES["compile_s"] += secs

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)


def _series() -> dict[str, float]:
    """The process's metric registry as a scrape would read it."""
    from dragonfly2_tpu.utils.metrics import default_registry

    out = {}
    for line in default_registry.expose().splitlines():
        if line and not line.startswith("#"):
            name, _, value = line.rpartition(" ")
            out[name] = float(value)
    return out


def _moved(before: dict, after: dict, needle: str) -> dict[str, float]:
    return {
        k: v - before.get(k, 0.0)
        for k, v in after.items()
        if needle in k and v != before.get(k, 0.0)
    }


@contextlib.contextmanager
def _spy(module, name: str, sink: list):
    """Record what ``module.name`` returns while it stays in place for
    every caller that resolves it at call time."""
    real = getattr(module, name)

    def spied(*args, **kwargs):
        out = real(*args, **kwargs)
        sink.append(out)
        return out

    setattr(module, name, spied)
    try:
        yield
    finally:
        setattr(module, name, real)


def _placement(tree) -> dict:
    import jax

    leaves = [l for l in jax.tree_util.tree_leaves(tree) if hasattr(l, "devices")]
    devices = set().union(*(l.devices() for l in leaves)) if leaves else set()
    return {
        "platforms": sorted({d.platform for d in devices}),
        "devices": sorted(d.id for d in devices),
        "replicated": all(l.sharding.is_fully_replicated for l in leaves),
    }


def _decreased(history) -> tuple[bool, str]:
    """Finite throughout, and lower at the end than at the start (means
    over the first and last tenth, so one noisy minibatch decides nothing)."""
    h = np.asarray(history, np.float64)
    if h.size < 2 or not np.isfinite(h).all():
        return False, f"history={h[:4]}…"
    k = max(1, h.size // 10)
    head, tail = float(h[:k].mean()), float(h[-k:].mean())
    return tail < head, f"{head:.4f} → {tail:.4f} over {h.size}"


class MainPath:
    """Section 1, one method per phase; ``run`` is the order a smoke run
    takes them in. The servers stay up between phases."""

    def __init__(self, size: Size, seed: int, workdir: str, report: Report):
        import jax

        self.size, self.seed, self.report = size, seed, report
        self.work = Path(workdir)
        self.rng = np.random.default_rng(seed)
        self.devices = jax.devices()
        self.platform = self.devices[0].platform
        self.daemons: list = []
        self.manager = self.trainer = self.scheduler = None
        self._channels: list = []
        self.fits: dict[str, dict] = {}

    # -- lifecycle ---------------------------------------------------------
    def bring_up(self) -> None:
        from dragonfly2_tpu.client.daemon import Daemon, DaemonConfig
        from dragonfly2_tpu.manager.server import ManagerServer, ManagerServerConfig
        from dragonfly2_tpu.rpc import glue
        from dragonfly2_tpu.scheduler.server import (
            SchedulerServer,
            SchedulerServerConfig,
        )
        from dragonfly2_tpu.trainer.server import TrainerServer, TrainerServerConfig

        self.manager = ManagerServer(
            ManagerServerConfig(data_dir=str(self.work / "manager"))
        )
        manager_addr = self.manager.serve()
        self.trainer = TrainerServer(
            TrainerServerConfig(
                data_dir=str(self.work / "trainer"), manager_address=manager_addr
            )
        )
        trainer_addr = self.trainer.serve()
        # cadences are parked so each phase triggers its own round; every
        # width is the config's default
        self.scheduler = SchedulerServer(
            SchedulerServerConfig(
                data_dir=str(self.work / "scheduler"),
                hostname="smoke-scheduler",
                manager_address=manager_addr,
                trainer_address=trainer_addr,
                algorithm="ml",
                serving_enabled=True,
                topology_backend="jax",
                preheat=True,
                model_refresh_interval=3600.0,
                preheat_interval=3600.0,
            )
        )
        self.sched_addr = self.scheduler.serve()
        for addr, attr, service in (
            (manager_addr, "manager_api", glue.MANAGER_SERVICE),
            (self.sched_addr, "sched_api", glue.SCHEDULER_SERVICE),
        ):
            channel = glue.dial(addr)
            self._channels.append(channel)
            setattr(self, attr, glue.ServiceClient(channel, service))
        for i in range(self.size.daemons):
            d = Daemon(
                DaemonConfig(
                    data_dir=str(self.work / f"daemon-{i}"),
                    scheduler_address=self.sched_addr,
                    hostname=f"smoke-peer-{i}",
                    piece_length=16 * 1024,
                    announce_interval=3600.0,
                    collect_host_stats=False,
                )
            )
            d.start()
            self.daemons.append(d)
        mesh = self.trainer.training.mesh
        self.report.check(
            "trainer fit mesh spans every chip",
            (mesh is None) == (len(self.devices) == 1)
            and (mesh is None or dict(mesh.shape) == {"dp": len(self.devices)}),
            f"mesh={None if mesh is None else dict(mesh.shape)}",
        )

    def tear_down(self) -> None:
        for d in self.daemons:
            d.stop()
        for ch in self._channels:
            ch.close()
        for server in (self.scheduler, self.trainer, self.manager):
            if server is not None:
                server.stop()

    # -- probe plane → topology engine → GNN training records --------------
    def probe_plane(self) -> None:
        from dragonfly2_tpu.rpc import gen  # noqa: F401
        import common_pb2
        import scheduler_pb2

        from dragonfly2_tpu.schema.synth import make_download_records
        from dragonfly2_tpu.topology import TopologyConfig, TopologyEngine

        rep, size, srv = self.report, self.size, self.scheduler
        # the fleet: the seeded download records' child hosts (identity
        # and the network block GNN node features read), plus the daemons
        # already announced
        self.records = make_download_records(
            max(size.unique_records, size.hosts), seed=self.seed
        )
        for rec in self.records[: size.hosts]:
            h = rec.host
            self.sched_api.AnnounceHost(
                scheduler_pb2.AnnounceHostRequest(
                    host=common_pb2.HostInfo(
                        id=h.id, type=h.type, hostname=h.hostname, ip=h.ip, port=h.port,
                        network=common_pb2.NetworkStat(
                            tcp_connection_count=h.network.tcp_connection_count,
                            upload_tcp_connection_count=h.network.upload_tcp_connection_count,
                            location=h.network.location, idc=h.network.idc,
                        ),
                    )
                )
            )
        ids = [r.host.id for r in self.records[: size.hosts]]
        ids += [d.host_id for d in self.daemons]
        # each host follows the prober's protocol — probe_started, the
        # scheduler picks its targets (the reference fan-out),
        # probe_finished — with the ping replaced by a seeded RTT: a
        # function of latent coordinates, so the GNN has geometry to learn
        coords = dict(zip(ids, self.rng.uniform(0, 1, size=(len(ids), 2))))
        twin = TopologyEngine(TopologyConfig(backend="numpy", flush_threshold=10**9))
        fanouts = []
        for _ in range(size.probe_rounds):
            for src in ids:
                me = common_pb2.HostInfo(id=src)
                (targets,) = self.sched_api.SyncProbes(
                    iter([
                        scheduler_pb2.SyncProbesRequest(
                            host=me, probe_started=scheduler_pb2.ProbeStartedRequest()
                        )
                    ])
                )
                fanouts.append(len(targets.hosts))
                probes = []
                for t in targets.hosts:
                    dist = float(np.linalg.norm(coords[src] - coords[t.host.id]))
                    rtt_ms = 1.0 + 80.0 * dist + self.rng.exponential(2.0)
                    probes.append(
                        scheduler_pb2.ProbeResult(
                            host_id=t.host.id,
                            rtt_ns=int(rtt_ms * 1e6),
                            created_at_ns=time.time_ns(),
                        )
                    )
                    p = probes[-1]
                    twin.enqueue(src, p.host_id, p.rtt_ns, p.created_at_ns / 1e9)
                list(
                    self.sched_api.SyncProbes(
                        iter([
                            scheduler_pb2.SyncProbesRequest(
                                host=me,
                                probe_finished=scheduler_pb2.ProbeFinishedRequest(
                                    probes=probes
                                ),
                            )
                        ])
                    )
                )
        engine = srv.topology_engine
        engine.flush()
        twin.flush()
        stats = engine.stats()
        rep.check("topology backend == jax", stats["backend"] == "jax", stats)
        rep.check(
            "every host probed at the scheduler's fan-out",
            stats["hosts"] == len(ids) and min(fanouts) == max(fanouts) > 0,
            f"hosts={stats['hosts']} edges={stats['edges']} fan-out={fanouts[0]}"
            f" flushes={stats['flushes']}",
        )
        place = _placement(engine._D)
        rep.check(
            "landmark distances live on the device",
            place["platforms"] == [self.platform],
            f"{place} (the serving planes sit on one device by design)",
        )
        # est_rtt on a seeded sample of pairs against the numpy twin fed
        # the same probes: direct edges and landmark inference alike
        sample = [
            tuple(self.rng.choice(ids, size=2, replace=False)) for _ in range(64)
        ]
        got = [engine.est_rtt_detail(a, b) for a, b in sample]
        want = [twin.est_rtt_detail(a, b) for a, b in sample]
        answered = [g for g in got if g[0] is not None]
        worst = max(
            (abs(g[0] - w[0]) / max(w[0], 1) for g, w in zip(got, want) if g[0] and w[0]),
            default=0.0,
        )
        rep.check(
            "est_rtt answers and agrees with the numpy twin",
            [g[1] for g in got] == [w[1] for w in want]
            and any(g[1] == "inferred" for g in got)
            and worst < 1e-4,
            f"{len(answered)}/64 answered,"
            f" {sum(g[1] == 'inferred' for g in got)} inferred, max rel err {worst:.2e}",
        )
        # what the 2 h snapshot task does: the live probe graph becomes
        # NetworkTopology records in the sink the announcer uploads
        rows = srv.networktopology.snapshot()
        rep.require("probe graph snapshotted into the record sink", rows > 0, f"{rows} records")

    # -- preheat forecaster ------------------------------------------------
    def preheat(self) -> None:
        from dragonfly2_tpu.trainer import train as train_mod

        rep, planner = self.report, self.scheduler.preheat_planner
        demand = planner.demand
        now = time.time()
        # seeded demand history: each task ramps at its own rate over the
        # window (no URL: a forecast-hot task with nothing to fetch is
        # skipped by the plan step, so no job leaves this process)
        rates = self.rng.uniform(0.2, 4.0, size=self.size.demand_tasks)
        for i, rate in enumerate(rates):
            for b in range(demand.window_buckets):
                count = float(self.rng.poisson(rate * (1 + b / 8)))
                if count:
                    demand.observe(
                        f"demand-{i}",
                        ts=now - (demand.window_buckets - 1 - b) * demand.bucket_s,
                        count=count,
                    )
        fits: list = []
        with _spy(train_mod, "train_gru", fits):
            out = planner.sweep_once(now)
        fc = planner.forecaster.stats()
        rep.check("forecaster backend == device", fc["backend"] == "device", fc)
        rep.check(
            "forecaster fit once and forecast the horizon",
            out["outcome"] != "error" and fc["fits"] == 1 and len(fits) == 1
            and out["forecast"] >= self.size.demand_tasks and fc["forecasts"] > 0,
            out,
        )
        if fits:
            rep.check("forecaster loss decreased", *_decreased(fits[0].history))

    # -- record sink → upload → fits → model registry ----------------------
    def load_records(self) -> None:
        srv, size = self.scheduler, self.size
        for rec in self.records[: size.unique_records]:
            srv.storage.create_download(rec)
        srv.storage.flush()
        active = Path(srv.storage.dir) / "blocks" / "download.dfb"
        body = active.read_bytes()
        copies = -(-size.download_bytes // len(body))
        with open(active, "ab") as f:
            for _ in range(copies - 1):
                f.write(body)
        self.block_bytes = active.stat().st_size
        self.report.require(
            "download blocks written through the scheduler's sink",
            self.block_bytes >= size.download_bytes,
            f"{self.block_bytes / MIB:.1f} MiB columnar-v1"
            f" ({size.unique_records} seeded records × {copies})",
        )

    def train(self, timeout_s: float = 900.0) -> None:
        from dragonfly2_tpu.trainer import ingest as ingest_mod
        from dragonfly2_tpu.trainer import train as train_mod
        from dragonfly2_tpu.trainer import training as training_mod

        import manager_pb2

        rep, training = self.report, self.trainer.training
        outcomes: list = []
        done = threading.Event()
        real_train = training.train

        def train_and_tell(ip, hostname):
            try:
                outcomes.append(real_train(ip, hostname))
                return outcomes[-1]
            finally:
                done.set()

        training.train = train_and_tell
        streamed, mlp, gnn, gru = [], [], [], []
        before = _series()
        try:
            with (
                _spy(ingest_mod, "stream_train_mlp", streamed),
                _spy(training_mod, "train_mlp", mlp),
                _spy(training_mod, "train_gnn", gnn),
                _spy(train_mod, "train_gru", gru),
            ):
                rep.require("announcer uploaded", self.scheduler.announcer.train_once())
                rep.require(
                    "trainer finished the round",
                    done.wait(timeout_s),
                    f"within {timeout_s:.0f}s",
                )
        finally:
            training.train = real_train
        outcome = outcomes[0] if outcomes else None
        rep.check(
            "TrainingOutcome.ok, GRU leg included",
            outcome is not None and outcome.ok and outcome.gru_error is None,
            outcome,
        )
        moved = _moved(before, _series(), "trainer_fit_total")
        rep.check(
            "trainer_fit_total: three successes, no failure label",
            len(moved) == 3 and all('outcome="success"' in k for k in moved),
            moved,
        )
        want_stream = self.block_bytes >= training.config.streaming_threshold_bytes
        rep.check(
            "MLP fit took the streamed path iff the upload crossed the threshold",
            bool(streamed) == want_stream and bool(mlp) != want_stream,
            f"{self.block_bytes / MIB:.1f} MiB vs"
            f" {training.config.streaming_threshold_bytes / MIB:.0f} MiB",
        )
        n = len(self.devices)
        if streamed:
            params, stats = streamed[0]
            self.fits["mlp"] = {"history": stats.losses, "params": params}
            rep.check(
                "staged superbatches: one shard on every chip",
                len(stats.feed_devices) == n
                and all(self.platform in d.lower() for d in stats.feed_devices),
                f"{stats.feed_devices}; {stats.download_records} records,"
                f" {stats.pairs} pairs, {stats.steps} steps",
            )
        elif mlp:
            self.fits["mlp"] = {"history": mlp[0].history, "params": mlp[0].params}
        for name, got in (("gnn", gnn), ("gru", gru)):
            if got:
                self.fits[name] = {"history": got[0].history, "params": got[0].params}
        for name in ("mlp", "gnn", "gru"):
            fit = self.fits.get(name)
            if fit is None:
                rep.check(f"{name} fit ran", False)
                continue
            rep.check(f"{name} loss decreased", *_decreased(fit["history"]))
            place = _placement(fit["params"])
            rep.check(
                f"{name} params replicated on every chip",
                place["platforms"] == [self.platform]
                and len(place["devices"]) == n
                and place["replicated"],
                place,
            )
        listed = self.manager_api.ListModels(
            manager_pb2.ListModelsRequest(scheduler_cluster_id=1)
        ).models
        self.models = {m.type: m for m in listed}
        rep.require(
            "three model versions in the manager",
            len(listed) == 3 and set(self.models) == {"mlp", "gnn", "gru"},
            [(m.type, m.version, m.state) for m in listed],
        )

    # -- activation → refresher → scoring service --------------------------
    def _activate(self, kind: str) -> None:
        import manager_pb2

        m = self.models[kind]
        self.manager_api.UpdateModel(
            manager_pb2.UpdateModelRequest(
                model_id=m.model_id, version=m.version, state="active"
            )
        )

    def install(self, kinds: tuple[str, ...], serving_kind: str) -> None:
        """Activate through the manager API, then one refresher round."""
        rep, srv = self.report, self.scheduler
        for kind in kinds:
            self._activate(kind)
        refresher = srv.model_refresher
        rep.check(f"refresh_once installed {kinds}", refresher.refresh_once())
        loaded = {
            "mlp": refresher.loaded_version,
            "gru": refresher.loaded_gru_version,
            "gnn": refresher.loaded_gnn_version,
        }
        for kind in kinds:
            m = self.models[kind]
            rep.check(
                f"refresher loaded the {kind}",
                loaded[kind] == (m.model_id, m.version),
                loaded[kind],
            )
        snap = srv.scoring_service.snapshot()
        rep.require(
            f"scoring service serves the {serving_kind}",
            snap["running"] and snap["model_kind"] == serving_kind,
            snap,
        )

    def mlp_parity(self) -> None:
        """The served MLP through the service's batched path against
        NumpyMLPScorer in float32 on the weights the manager holds."""
        import manager_pb2

        from dragonfly2_tpu.schema.features import MLP_FEATURE_DIM
        from dragonfly2_tpu.scheduler.serving import ServingError
        from dragonfly2_tpu.trainer.serving import NumpyMLPScorer, deserialize_params_auto

        m = self.models["mlp"]
        weights = self.manager_api.GetModelWeights(
            manager_pb2.GetModelRequest(model_id=m.model_id, version=m.version)
        ).weights
        feats = self.rng.uniform(0, 1, size=(48, MLP_FEATURE_DIM)).astype(np.float32)
        want = NumpyMLPScorer(deserialize_params_auto(weights)).predict(feats)
        try:
            got = self.scheduler.scoring_service.score(feats)
        except ServingError as e:
            self.report.check("served MLP answers", False, e)
            return
        err = float(np.max(np.abs(got - want)))
        self.report.check(
            "served MLP agrees with NumpyMLPScorer (float32)",
            got.shape == want.shape and np.isfinite(got).all() and err < MLP_BF16_ATOL,
            f"max|err|={err:.4f} atol={MLP_BF16_ATOL} on scores"
            f" {want.min():.2f}…{want.max():.2f}",
        )

    def serve_decisions(self, tag: str) -> None:
        """Downloads through the daemons: every child's register arrives
        over AnnouncePeer and is ranked by the served model."""
        from dragonfly2_tpu.client import dfget

        rep, srv, size = self.report, self.scheduler, self.size
        before, snap0 = _series(), srv.scoring_service.snapshot()
        origin = self.work / f"origin-{tag}"
        origin.mkdir()  # one tag, one set of tasks
        pyrng = random.Random(self.seed)

        def pull(d, url, out):
            dfget.download(f"127.0.0.1:{d.port}", url, out)
            return os.path.getsize(out)

        short = 0
        for t in range(size.tasks):
            path = origin / f"blob-{t}.bin"
            path.write_bytes(pyrng.randbytes(4 * 16 * 1024))
            url = f"file://{path}"
            seeder = self.daemons[t % len(self.daemons)]
            children = [d for d in self.daemons if d is not seeder]
            pull(seeder, url, f"{seeder.cfg.data_dir}/{tag}-{t}.bin")
            with ThreadPoolExecutor(len(children)) as pool:
                sizes = pool.map(
                    lambda d: pull(d, url, f"{d.cfg.data_dir}/{tag}-{t}.bin"), children
                )
                short += sum(s != path.stat().st_size for s in sizes)
        after, snap = _series(), srv.scoring_service.snapshot()
        decisions = sum(_moved(before, after, "scheduler_wave_decisions_total").values())
        rows = snap["rows_scored"] - snap0["rows_scored"]
        rep.check(
            f"{tag}: decisions scored by the served model",
            rows > 0 and decisions > 0 and short == 0
            and snap["model_kind"] == snap0["model_kind"],
            f"{decisions:.0f} decisions, {rows} rows in"
            f" {snap['batches'] - snap0['batches']} batches"
            f" by kind={snap['model_kind']}",
        )
        bad = {
            **_moved(before, after, "scheduler_serving_fallback_total"),
            **_moved(before, after, "scheduler_serving_errors_total"),
        }
        if bad:
            # the evaluator's ladder and the service say why in the ring
            from dragonfly2_tpu.utils import flight

            bad["events"] = [
                e
                for e in flight.snapshot(["scheduler"]).get("scheduler", [])
                if e["type"] in ("scheduler.serving_fallback", "scheduler.serving_error")
            ][-4:]
        rep.check(f"{tag}: no serving fallback, no serving error", not bad, bad)

    def train_round(self) -> None:
        """Everything up to three inactive model versions in the manager."""
        rep = self.report
        with rep.phase("bring_up"):
            self.bring_up()
        with rep.phase("probe_plane"):
            self.probe_plane()
        with rep.phase("preheat"):
            self.preheat()
        with rep.phase("load_records"):
            self.load_records()
        with rep.phase("train"):
            self.train()

    def serve_round(self) -> None:
        rep = self.report
        with rep.phase("serve_mlp"):
            self.install(("mlp", "gru"), serving_kind="mlp")
            self.mlp_parity()
            self.serve_decisions("mlp")
        with rep.phase("serve_gnn"):
            self.install(("gnn",), serving_kind="gnn")
            self.serve_decisions("gnn")

    def run(self) -> None:
        try:
            self.train_round()
            self.serve_round()
        finally:
            with self.report.phase("tear_down"):
                self.tear_down()


def run_kernels(report: Report, seed: int) -> None:
    """Section 2: the kernels and jitted programs no service reaches yet,
    compiled for this chip and checked against their oracles, on a mesh
    over every chip the host has."""
    import jax
    import jax.numpy as jnp

    from dragonfly2_tpu.models import mlp as mlp_mod
    from dragonfly2_tpu.ops.flash import flash_attention
    from dragonfly2_tpu.ops.ring import local_attention, make_ring_attention
    from dragonfly2_tpu.ops.ulysses import make_ulysses_attention
    from dragonfly2_tpu.parallel.mesh import make_mesh
    from dragonfly2_tpu.schema.columnar import records_to_columns
    from dragonfly2_tpu.schema.features import MLP_FEATURE_DIM, build_probe_graph
    from dragonfly2_tpu.schema.synth import make_topology_records
    from dragonfly2_tpu.trainer.checkpoint import FitCheckpointer, params_equal
    from dragonfly2_tpu.trainer.train import GNNFitConfig, train_gnn_sharded

    def qkv(key, shape, dtype=jnp.float32):
        return (jax.random.normal(k, shape, dtype) for k in jax.random.split(key, 3))

    def err(a, b) -> float:
        return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))

    def close(name, got, want, tol=KERNEL_TOL):
        e = err(got, want)
        report.check(name, e < tol, f"max|err|={e:.4f} tol={tol}")

    with report.phase("flash"):
        for b, t, h, d, causal, dt in [
            (2, 512, 4, 64, True, jnp.float32),
            (2, 200, 4, 64, True, jnp.float32),  # padded tail
            (1, 333, 2, 32, False, jnp.float32),  # odd length, non-causal
            (2, 512, 4, 64, False, jnp.bfloat16),
            (1, 96, 8, 128, True, jnp.float32),  # short seq, wide head
        ]:
            q, k, v = qkv(jax.random.PRNGKey(seed + t), (b, t, h, d), dt)
            close(
                f"flash t={t} d={d} causal={causal} {dt.__name__}",
                flash_attention(q, k, v, causal=causal),
                local_attention(q, k, v, causal=causal),
            )
        # non-default block hints must stay Mosaic-legal (the LSE lane
        # rule bites when block_q isn't a multiple of 128)
        for bq, bk, t in [
            (64, 64, 512),
            (24, 16, 100),
            (32, 96, 96),
            (127, 127, 512),  # unaligned pair: must not lcm-explode t_pad
            (128, 12, 512),  # bk not a multiple of 8: sublane rule
        ]:
            q, k, v = qkv(jax.random.PRNGKey(seed + bq * t), (1, t, 2, 32))
            close(
                f"flash block_q={bq} block_k={bk} t={t}",
                flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk),
                local_attention(q, k, v, causal=True),
            )
        # backward through the kernel (custom VJP rebuilding P from LSE)
        q, k, v = qkv(jax.random.PRNGKey(seed), (2, 256, 4, 64))
        grads = [
            jax.grad(lambda *a: jnp.sum(f(*a, causal=True) ** 2), (0, 1, 2))(q, k, v)
            for f in (flash_attention, local_attention)
        ]
        for name, got, want in zip("qkv", *grads):
            close(f"flash grad d{name}", got, want, tol=2e-1)

    n = len(jax.devices())
    with report.phase("sequence_parallel"):
        mesh = make_mesh(sp=n)
        q, k, v = qkv(jax.random.PRNGKey(seed + 1), (2, 64 * n, max(2, n), 32))
        want = local_attention(q, k, v, causal=True)
        for name, make in (
            ("ring attention", lambda: make_ring_attention(mesh, "sp", causal=True)),
            (
                "ulysses+pallas",
                lambda: make_ulysses_attention(mesh, "sp", causal=True, use_pallas=True),
            ),
        ):
            out = make()(q, k, v)
            close(f"{name} sp={n}", out, want)
            report.check(
                f"{name} output spans sp={n} chips",
                len(out.sharding.device_set) == n,
                sorted(d.id for d in out.sharding.device_set),
            )

    with report.phase("gnn_sharded"):
        # the default GraphSAGE on a fleet-size probe graph, node tables
        # row-sharded over gp with ring ppermute gathers
        graph = build_probe_graph(
            records_to_columns(make_topology_records(2048, num_hosts=1024, seed=seed))
        )
        res = train_gnn_sharded(graph, make_mesh(gp=n), config=GNNFitConfig())
        report.check(f"train_gnn_sharded gp={n} loss decreased", *_decreased(res.history))
        used = [
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()
        ]
        report.check(
            f"all {n} chips report memory in use", all(u > 0 for u in used), f"peak bytes {used}"
        )

    with report.phase("orbax"):
        params = jax.device_put(
            mlp_mod.init_mlp(jax.random.PRNGKey(seed), [MLP_FEATURE_DIM, 128, 128, 1])
        )
        with tempfile.TemporaryDirectory(prefix="smoke-ckpt-") as d:
            ck = FitCheckpointer(d)
            state = {"params": params, "epoch": 3}
            ck.save(3, state)
            got = ck.restore_latest(like=state)
            ck.close()
        report.check(
            "orbax device-array round-trip",
            got is not None and got[0] == 3 and params_equal(params, got[1]["params"]),
        )


def result_line(ok: bool, device: dict) -> str:
    """The last stdout line: exactly ``ok`` and ``device`` (platform,
    kind, count as jax reports them). Everything else the run has to say
    goes on the ``summary:`` line before it."""
    return json.dumps(
        {
            "ok": bool(ok),
            "device": {
                "platform": str(device["platform"]),
                "kind": str(device["kind"]),
                "count": int(device["count"]),
            },
        }
    )


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0, help="data and weights")
    args = p.parse_args(argv)
    os.environ.setdefault("GRPC_VERBOSITY", "ERROR")

    from dragonfly2_tpu.utils.jitcache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs an accelerator, jax found platform={dev.platform!r};"
            " nothing here runs on the CPU",
            file=sys.stderr,
        )
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    print(f"platform: {dev.platform}  device_kind: {dev.device_kind}  count: {device['count']}")
    print(f"compile cache: {cache_dir}")
    _watch_compiles()

    report, size = Report(), Size()
    t0 = time.perf_counter()
    workdir = tempfile.mkdtemp(prefix="chip-smoke-")
    try:
        MainPath(size, args.seed, workdir, report).run()
        run_kernels(report, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: stopped at {e}", file=sys.stderr)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    ok = not report.failed
    summary = {
        "seed": args.seed,
        "failed": report.failed,
        "wall_s": round(time.perf_counter() - t0, 1),
        "cache_dir": cache_dir,
        "compile": {k: round(v, 1) for k, v in _COMPILES.items()},
        "phases": report.phases,
        "reduced": [
            f"{size.unique_records} seeded download records replicated to"
            f" {size.download_bytes // MIB} MiB of blocks",
            "probe RTTs drawn from a seeded model, not pinged",
        ],
        "claim": None,
    }
    print(f"summary: {json.dumps(summary)}")
    print(result_line(ok, device), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
