#!/usr/bin/env python3
"""What a GraphSAGE swap holds while decisions are asked: the engine's
lock, the interpreter, and a decision's rtt join, swap by swap.

    python3 benchmarks/tools/swap_holds.py --workload decide-gnn-under-round --swaps 6 --seed 7

The scheduler alone, built as ``open_loop_decisions`` builds it, its
engine filled in arrival order as the cell fills it, and a registry that
activates a new GraphSAGE version (seeded weights made for the live graph)
before every ``ModelRefresher.refresh_once()``. It uses nothing a tree
from before the swap's spans has not, so the same file measures both sides
of the change. Beside the swaps, all through:

- every outermost hold of ``TopologyEngine._lock`` is timed (the lock that
  ``rtt_affinity_pairs`` takes in every decision's rtt join);
- a thread that sleeps a millisecond at a time notes how late it wakes
  (something held the interpreter, or the machine stopped the process);
- four threads make a decision's rtt join (16 pairs) every 2 ms and time it.

One line: for every swap after the first (which compiles) its length and
the longest of each of the three inside it, and the same three outside any
swap. The interpreter's switch interval is the colocated service's.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading
import time

ROOT = os.path.normpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))
sys.path.insert(0, ROOT)


class TimedLock:
    """A re-entrant lock in another's place: every outermost hold is
    noted as (when it was taken, how long it was held)."""

    def __init__(self, lock, holds: list):
        self._lock, self._holds, self._mine = lock, holds, threading.local()

    def acquire(self, *args, **kwargs):
        got = self._lock.acquire(*args, **kwargs)
        if got:
            depth = getattr(self._mine, "depth", 0)
            if depth == 0:
                self._mine.t0 = time.perf_counter()
            self._mine.depth = depth + 1
        return got

    def release(self):
        self._mine.depth -= 1
        if self._mine.depth == 0:
            self._holds.append((self._mine.t0, time.perf_counter() - self._mine.t0))
        self._lock.release()

    __enter__ = acquire

    def __exit__(self, *exc):
        self.release()


def longest(events: list, spans: list, inside: bool) -> float:
    """The longest of ``events`` (when, how long) that overlaps one of
    ``spans`` (began, ended), or with ``inside`` false none of them."""
    worst = 0.0
    for t0, took in events:
        hit = any(t0 <= ended and t0 + took >= began for began, ended in spans)
        if hit == inside:
            worst = max(worst, took)
    return worst


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--swaps", type=int, default=6)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()

    import numpy as np

    from benchmarks.generators import decide_gnn_under_round as gur
    from benchmarks.generators import open_loop_decisions as old
    from benchmarks.harness import cells, device as dev, swarm, synth
    from dragonfly2_tpu.scheduler.model_refresher import ModelRefresher
    from dragonfly2_tpu.scheduler.server import SchedulerServer, SchedulerServerConfig
    from dragonfly2_tpu.trainer.serving import serialize_params
    from dragonfly2_tpu.utils.jitcache import enable_compile_cache

    import manager_pb2

    cell = cells.load_cell(args.workload)
    enable_compile_cache()
    dev.require_chips(cell.chips)
    os.makedirs(os.path.join(ROOT, ".bench_work"), exist_ok=True)
    workdir = tempfile.mkdtemp(prefix="swap-holds-", dir=os.path.join(ROOT, ".bench_work"))
    srv = SchedulerServer(
        SchedulerServerConfig(
            data_dir=os.path.join(workdir, "scheduler"), hostname="bench-scheduler", algorithm="ml",
            topology_backend=cell.config["scheduler"]["topology_backend"],
        )
    )
    desc = swarm.describe(cell.traffic, args.seed)
    old.build_live(desc, srv)
    gur.fill_topology(srv.topology_engine, desc, args.seed)
    srv.scoring_service.start()
    hidden = tuple(cell.config["trainer"]["gnn"]["hidden_dims"])
    versions: list = []

    class Registry:
        def ListModels(self, request):
            v = len(versions)
            models = [manager_pb2.Model(model_id="bench-gnn", type="gnn", version=v, state="active", created_at_ns=v, updated_at_ns=v)]
            return manager_pb2.ListModelsResponse(models=models if v else [])

        def GetModelWeights(self, request):
            return manager_pb2.ModelWeights(
                model_id="bench-gnn", version=request.version, type="gnn", weights=serialize_params(versions[request.version - 1])
            )

    refresher = ModelRefresher(
        Registry(), srv.evaluator, scheduler_cluster_id=srv.cfg.cluster_id,
        serving=srv.scoring_service, networktopology=srv.networktopology,
    )
    ids = [h.id for h in desc["hosts"]]
    srv.topology_engine.rtt_affinity_pairs([ids[0]] * 16, ids[1:17])
    sys.setswitchinterval(0.0005)  # colocated.SWITCH_INTERVAL_S

    holds: list = []
    srv.topology_engine._lock = TimedLock(srv.topology_engine._lock, holds)
    late: list = []
    joins: list = []
    stop = threading.Event()

    def sleeper():
        while not stop.is_set():
            t0 = time.perf_counter()
            time.sleep(0.001)
            late.append((t0, time.perf_counter() - t0 - 0.001))

    def joiner(k: int):
        rng = np.random.default_rng([args.seed, 16, k])
        while not stop.is_set():
            pick = rng.integers(0, len(ids), size=17)
            t0 = time.perf_counter()
            srv.topology_engine.rtt_affinity_pairs([ids[pick[0]]] * 16, [ids[i] for i in pick[1:]])
            joins.append((t0, time.perf_counter() - t0))
            time.sleep(0.002)

    threads = [threading.Thread(target=sleeper, daemon=True)] + [
        threading.Thread(target=joiner, args=(k,), daemon=True) for k in range(4)
    ]
    for t in threads:
        t.start()
    swaps: list = []
    for k in range(args.swaps + 1):
        versions.append(synth.gnn_weights(args.seed + k, len(ids), hidden=hidden))
        time.sleep(0.3)
        began = time.perf_counter()
        if not refresher.refresh_once():
            raise SystemExit(f"swap {k} did not install: {srv.scoring_service.snapshot()}")
        swaps.append((began, time.perf_counter()))
    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join()
    timed = swaps[1:]  # the first compiles the embed and every rung's edge head
    line = {
        "served": srv.scoring_service.snapshot()["model_version"],
        "hosts": len(ids), "edges_in_engine": len(desc["edges"]),
        "swap_s": [round(e - b, 4) for b, e in timed],
        "first_swap_s": round(swaps[0][1] - swaps[0][0], 4),
        "in_a_swap": {
            "engine_lock_held_max_s": [round(longest(holds, [s], True), 5) for s in timed],
            "interpreter_held_max_s": [round(longest(late, [s], True), 5) for s in timed],
            "rtt_join_max_s": [round(longest(joins, [s], True), 5) for s in timed],
        },
        "outside_any_swap": {
            "engine_lock_held_max_s": round(longest(holds, swaps, False), 5),
            "interpreter_held_max_s": round(longest(late, swaps, False), 5),
            "rtt_join_max_s": round(longest(joins, swaps, False), 5),
            "rtt_join_p50_s": round(float(np.median([took for _, took in joins])), 5),
        },
        "rtt_joins": len(joins),
    }
    print(json.dumps(line), flush=True)
    srv.scoring_service.stop()
    shutil.rmtree(workdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
