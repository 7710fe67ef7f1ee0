#!/usr/bin/env python3
"""``sweep_beside.py`` for a cell whose served model is the round's own
GraphSAGE version (``decide_gnn_under_round``): the same sweep, windows,
witnesses and knee, with that generator's set-up in ``decide_under_round``'s
place, so that every round's install puts a GraphSAGE version in the slot.

    python3 benchmarks/tools/sweep_beside_gnn.py --workload decide-gnn-under-round --rates 100,150,200 --seconds 15 --windows 2 --at-round-start 1

After the sweep's own last line, one more: what the slot held at the end,
and every step of the swaps that ran beside the windows (count, mean and
longest, seconds; the first is the warm-up's, which compiles).
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.normpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))
sys.path.insert(0, ROOT)

STEPS = ("export", "graph_build", "embed", "install")


def main() -> int:
    from benchmarks.generators import decide_gnn_under_round as gur
    from benchmarks.generators import decide_under_round as rud
    from benchmarks.tools import sweep_beside
    from dragonfly2_tpu.utils import profiling

    built: list = []

    def setup(ctx):
        built.append(gur.setup(ctx))
        return built[-1]

    real = rud.setup
    rud.setup = setup
    try:
        code = sweep_beside.main()
    finally:
        rud.setup = real
    swaps = {}
    for step in STEPS:
        snap = profiling.phase_type(f"scheduler.gnn_{step}").snapshot()
        swaps[step] = {"count": snap["count"], "mean_s": round(snap["mean_s"], 4), "max_s": round(snap["max_s"], 4)}
    scheduler = built[0][1]
    print(json.dumps({"served_kind": scheduler.scoring_service.snapshot()["model_kind"], "swaps": swaps}), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
