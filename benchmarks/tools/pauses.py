#!/usr/bin/env python3
"""What holds the whole process still inside a serve cell's window.

    python3 benchmarks/tools/pauses.py --workload decide-steady --seconds 30 [--idle 1]

One set-up, then one open-loop window at the cell's rate beside a thread
that sleeps a millisecond at a time. Each time it wakes it re-arms
``faulthandler.dump_traceback_later``: a watchdog in C that needs no
interpreter lock, so when the thread is kept from waking for longer than
``--stall-ms`` every thread's stack is written out *during* the pause.
On waking late it also notes how much CPU time the process used
meanwhile (a thread computing under the lock burns CPU; a machine that
stopped the process burns none) and the host's steal and pressure
counters. ``--idle 1`` swaps the decision for a 5 ms sleep: the same
harness with the program idle.
"""

from __future__ import annotations

import argparse
import faulthandler
import gc
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.normpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))
sys.path.insert(0, ROOT)


def _host_counters() -> dict:
    out = {}
    try:
        with open("/proc/stat") as f:
            cpu = f.readline().split()
        out["steal_ticks"] = int(cpu[8])
    except (OSError, IndexError, ValueError):
        pass
    try:
        with open("/proc/pressure/cpu") as f:
            out["pressure_some_us"] = int(f.readline().split("total=")[1])
    except (OSError, IndexError, ValueError):
        pass
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=9001)
    ap.add_argument("--stall-ms", type=float, default=40.0)
    ap.add_argument("--idle", type=int, default=0)
    args = ap.parse_args()

    from benchmarks import run as bench_run
    from benchmarks.generators import open_loop_decisions as old
    from benchmarks.harness import cells, device as dev
    from dragonfly2_tpu.utils.jitcache import enable_compile_cache

    cell = cells.load_cell(args.workload)
    enable_compile_cache()
    devices = dev.require_chips(cell.chips)
    os.makedirs(os.path.join(ROOT, ".bench_work"), exist_ok=True)
    workdir = tempfile.mkdtemp(prefix="pauses-", dir=os.path.join(ROOT, ".bench_work"))
    ctx = bench_run.Context(cell, args.seed, args.seconds, False, workdir, devices, dev.CompileTap(), dev.Tracer(False, ""))
    srv, desc, children, weights, fallback = old.setup(ctx)
    rate = float(cell.params["rate_per_s"])
    old.drive(ctx, srv, children, rate, 1.5, args.seed + 1, 2.0)
    if args.idle:
        srv.scheduling.find_candidate_parents = lambda child: (time.sleep(0.005), ([], False))[1]

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    label = f"{args.workload}.{'idle' if args.idle else 'live'}"
    dump_path = os.path.join(ROOT, "chiprun_out", f"pauses.{label}.stacks")
    dump = open(dump_path, "w")
    stall_s = args.stall_ms / 1e3
    pauses: list = []
    collections: list = []
    gc.callbacks.append(lambda phase, info: collections.append((phase, time.perf_counter(), info.get("generation"))))

    def heartbeat(window, stop) -> None:
        last, cpu = time.perf_counter(), time.process_time()
        host = _host_counters()
        while not stop.is_set():
            faulthandler.dump_traceback_later(stall_s, file=dump)
            time.sleep(0.001)
            now, cpu_now = time.perf_counter(), time.process_time()
            if now - last > stall_s:
                host_now = _host_counters()
                pauses.append({
                    "at_s": round(last - window.t0, 3),
                    "gap_ms": round((now - last) * 1e3, 2),
                    "process_cpu_ms": round((cpu_now - cpu) * 1e3, 2),
                    **{k: host_now[k] - host.get(k, 0) for k in host_now},
                })
                host = host_now
            last, cpu = now, cpu_now
        faulthandler.cancel_dump_traceback_later()

    t_gc0 = gc.get_stats()
    w = old.drive(ctx, srv, children, rate, args.seconds, args.seed, 5.0, heartbeat=heartbeat)
    dump.close()
    lat = sorted(((w.end - (w.t0 + w.due))[w.end > 0] * 1e6).tolist())
    q = lambda p: lat[min(len(lat) - 1, int(p / 100 * len(lat)))] if lat else 0.0  # noqa: E731
    print(json.dumps({
        "run": label, "rate": rate, "decisions": w.n, "p50_us": q(50), "p95_us": q(95), "p99_us": q(99),
        "pauses": pauses, "switch_interval_s": sys.getswitchinterval(),
        "gc_collections_in_window": [
            (g, round(t - w.t0, 3)) for ph, t, g in collections if ph == "start" and t >= w.t0
        ][:40],
        "gc_stats_before": t_gc0, "gc_stats_after": gc.get_stats(),
        "cpu_count": os.cpu_count(), "sched_affinity": len(os.sched_getaffinity(0)),
    }), flush=True)
    # the stacks written during each pause: per thread, the innermost frames
    with open(dump_path) as f:
        blocks = f.read().split("Timeout (")[1:]
    for k, block in enumerate(blocks[:3]):
        print(f"-- stacks at pause {k} --")
        for thread in block.split("\n\n"):
            lines = [ln for ln in thread.strip().splitlines() if ln.strip()]
            if lines:
                print("\n".join(lines[:4]))
    srv.scoring_service.stop()
    shutil.rmtree(workdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
