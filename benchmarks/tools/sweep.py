#!/usr/bin/env python3
"""Find the highest rate a serve cell sustains, once, on the chip.

    python3 benchmarks/tools/sweep.py --workload decide-steady --rates 1000,2000,3000 --seconds 8

One set-up, then one open-loop window per rate. A rate is sustained when
the backlog does not grow: the queue wait over the window's last third is
no worse than over its first third, and every decision completes. The
cell's rate is then written by hand into ``benchmarks/cells/<cell>.json``
as the mix's share of the knee.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.normpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=101)
    args = ap.parse_args()

    import numpy as np

    from benchmarks import run as bench_run
    from benchmarks.generators import open_loop_decisions as old
    from benchmarks.harness import cells, device as dev
    from benchmarks.harness.layer_readers import percentile
    from dragonfly2_tpu.utils.jitcache import enable_compile_cache

    cell = cells.load_cell(args.workload)
    enable_compile_cache()
    devices = dev.require_chips(cell.chips)
    os.makedirs(os.path.join(ROOT, ".bench_work"), exist_ok=True)
    workdir = tempfile.mkdtemp(prefix="sweep-", dir=os.path.join(ROOT, ".bench_work"))
    ctx = bench_run.Context(cell, args.seed, args.seconds, False, workdir, devices, dev.CompileTap(), dev.Tracer(False, ""))
    srv, desc, children, weights, fallback = old.setup(ctx)
    old.drive(ctx, srv, children, 500.0, 1.5, args.seed + 1, 2.0)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        snap0, fell0 = srv.scoring_service.snapshot(), fallback.calls
        w = old.drive(ctx, srv, children, rate, args.seconds, args.seed + 10 + k, 5.0)
        snap1 = srv.scoring_service.snapshot()
        done = np.array([r is not None and len(r) > 0 for r in w.returned]) & (w.end > 0)
        lat = (w.end - (w.t0 + w.due))[done] * 1e6
        wait = (w.start - (w.t0 + w.due)) * 1e6
        third = w.n // 3
        # the longest stretch in which no decision started or ended: at
        # these rates one does every few milliseconds, so a long one is
        # the whole process held still, not a backlog
        events = np.sort(np.concatenate([w.start[w.start > 0], w.end[w.end > 0]]))
        batches = snap1["batches"] - snap0["batches"]
        print(
            json.dumps(
                {
                    "rate": rate,
                    "offered": w.n,
                    "completed_per_s": float(done.sum()) / args.seconds,
                    "finished_by_s": float(w.end.max() - w.t0),
                    "p50_us": percentile(lat, 50),
                    "p99_us": percentile(lat, 99),
                    "wait_first_third_us_mean": float(wait[:third].mean()),
                    "wait_last_third_us_mean": float(wait[-third:].mean()),
                    "wait_max_us": float(wait.max()),
                    "longest_quiet_us": float(np.diff(events).max() * 1e6) if events.size > 1 else 0.0,
                    "lateness_p99_us": percentile(wait[w.slept], 99) if w.slept.any() else 0.0,
                    "batch_rows": (snap1["rows_scored"] - snap0["rows_scored"]) / max(batches, 1),
                    "fell_a_rung": fallback.calls - fell0,
                    "errors": len(w.errors),
                }
            ),
            flush=True,
        )
    srv.scoring_service.stop()
    shutil.rmtree(workdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
