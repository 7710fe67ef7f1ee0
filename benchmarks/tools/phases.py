#!/usr/bin/env python3
"""One run of a cell, and every dfprof phase that moved inside its window.

    python3 benchmarks/tools/phases.py --workload train-round-resident --seed 7 --seconds 51 --trace 1

The run is ``benchmarks/run.py``'s own, argument for argument. The whole
phase ledger (``utils/profiling.ledger_snapshot``) is read when the
window opens and when it closes; after the result line one more line
gives, for each phase entered in between, its entries, its seconds and
seconds an entry: what a ``prof_phase`` metric would read. It is the way
to a round's split while the ``rounds`` generator hands no ``prof_phase``
bundle (PERF.md, Open questions).
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.normpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))
sys.path.insert(0, ROOT)


def main() -> int:
    from benchmarks import run as bench_run
    from benchmarks.harness import taps
    from dragonfly2_tpu.utils import profiling

    def ledger() -> dict:
        return taps.phase_counts(list(profiling.ledger_snapshot()))

    at: dict = {}
    opens, closes = bench_run.Context.window_opens, bench_run.Context.window_closes

    def window_opens(ctx) -> None:
        opens(ctx)
        at["open"] = ledger()

    def window_closes(ctx) -> None:
        at["close"] = ledger()
        closes(ctx)

    bench_run.Context.window_opens, bench_run.Context.window_closes = window_opens, window_closes
    try:
        rc = bench_run.main()
    finally:
        bench_run.Context.window_opens, bench_run.Context.window_closes = opens, closes
    zero = {"count": 0, "total_s": 0.0}
    before = {name: at["open"].get(name, zero) for name in at["close"]}
    moved = {
        name: {**d, "s_per_entry": d["total_s"] / d["count"]}
        for name, d in sorted(taps.phase_delta(before, at["close"]).items())
        if d["count"]
    }
    print("phases over the window: " + json.dumps(moved), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
