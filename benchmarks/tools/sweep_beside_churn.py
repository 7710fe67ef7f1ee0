#!/usr/bin/env python3
"""``sweep_beside.py`` for the cell whose fleet joins and leaves
(``decide_gnn_under_churn``): the same sweep, windows, witnesses and knee,
with that generator's set-up in ``decide_under_round``'s place, its script
of host events carried out from the end of set-up to the end of the sweep,
and the refresher's own loop polling beside the sweep's install after each
round (one poll at a time).

    python3 benchmarks/tools/sweep_beside_churn.py --workload decide-gnn-under-churn --rates 100,150,200 --seconds 15 --windows 2 --at-round-start 1

After the sweep's own last line, one more: what the slot held at the end,
how many events were carried out, the fleet's size, and every step of the
swaps, re-embeds, flushes and leaves that ran beside the windows (count,
mean and longest, seconds).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

ROOT = os.path.normpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))
sys.path.insert(0, ROOT)

HORIZON_S = 1200.0  # events for as long as a sweep of a dozen rates lasts


def main() -> int:
    from benchmarks.generators import decide_gnn_under_churn as guc
    from benchmarks.generators import decide_under_round as rud
    from benchmarks.tools import sweep_beside
    from dragonfly2_tpu.utils import profiling

    built: list = []

    def setup(ctx):
        ctx.cell.traffic["events_horizon_seconds"] = HORIZON_S
        *seven, fleet = guc.setup(ctx)
        refresher = seven[3]
        one_at_a_time, real = threading.Lock(), refresher.refresh_once

        def refresh_once():
            with one_at_a_time:
                real()
            return True  # the sweep asks whether its round's install went in: a poll may have taken it

        refresher.refresh_once = refresh_once
        refresher.start()
        fleet.start(time.perf_counter())
        built.append((seven, fleet))
        return tuple(seven)

    real = rud.setup
    rud.setup = setup
    try:
        code = sweep_beside.main()
    finally:
        rud.setup = real
    seven, fleet = built[0]
    fleet.stop()
    seven[3].stop()
    steps = {}
    for name in guc.SWAP_PHASES:
        snap = profiling.phase_type(name).snapshot()
        steps[name.partition(".")[2]] = {"count": snap["count"], "mean_s": round(snap["mean_s"], 5), "max_s": round(snap["max_s"], 5)}
    scheduler = seven[1]
    print(
        json.dumps(
            {
                "served_kind": scheduler.scoring_service.snapshot()["model_kind"],
                "events_done": fleet.done, "event_errors": fleet.errors[:3],
                "engine_hosts": len(scheduler.topology_engine.store.index),
                "steps": steps,
            }
        ),
        flush=True,
    )
    return code


if __name__ == "__main__":
    sys.exit(main())
