#!/usr/bin/env python3
"""The control's readings for a cell whose served model is a trained one.

    python3 benchmarks/tools/readings_served.py --workload decide-under-round --seeds 1,2,3

``readings.py`` puts the fp8 reference in a serve cell's place with the
seeded random weights those cells serve. Where the served model is the
round's own (``decide_under_round``), the weights are the float32
replay's of the resident fit at the cell's own upload, which a run holds
the registered parameters to (``mlp_update_gap``). With them, every
child's candidates are ranked by float32 and by fp8 costs, and the line
gives what a run's decision checks would read in each case. And the
control of ``mlp_versions_score_gap``: the same fit replayed in fp8, its
weights' costs over every child's candidate rows against the float32
replay's (``score_gap_fp8_fit``), beside the float32 replay's own weights
scored in fp8 (``score_gap_fp8_forward``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.normpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()

    import time

    from benchmarks.generators import open_loop_decisions as old
    from benchmarks.generators import decide_under_round as rud
    from benchmarks.harness import cells, reference, reference_fits, swarm, synth

    cell = cells.load_cell(args.workload)
    cfg, traffic = cell.config, cell.traffic
    mlp = cfg["trainer"]["mlp"]
    repeats = traffic["body_repeats_per_chunk"] * traffic["chunks"]
    for seed in (int(s) for s in args.seeds.split(",")):
        x, y = reference.record_pairs(synth.download_records(traffic["body_records"], seed))
        t0 = time.perf_counter()
        fit = reference_fits.fit_mlp(
            x, y, repeats, hidden=tuple(mlp["hidden_dims"]), epochs=mlp["epochs"], batch=mlp["batch_size"],
            learning_rate=mlp["learning_rate"], weight_decay=mlp["weight_decay"],
        )
        weights = rud.host_weights(fit["params"])
        desc = swarm.describe(traffic, seed)
        picks = [(k, c) for k in range(traffic["tasks"]) for c in range(traffic["children_per_task"])]
        sound = old.judge(desc, weights, cfg, picks, lambda n: None, "float32")
        control = old.judge(desc, weights, cfg, picks, lambda n: None, "fp8")
        low = reference_fits.fit_mlp(
            x, y, repeats, hidden=tuple(mlp["hidden_dims"]), epochs=mlp["epochs"], batch=mlp["batch_size"],
            learning_rate=mlp["learning_rate"], weight_decay=mlp["weight_decay"], precision="fp8",
        )
        rows = rud.candidate_rows(desc, cfg, picks)
        print(json.dumps({
            "seed": seed, "replay_s": round(time.perf_counter() - t0, 2),
            "loss": [float(v) for v in fit["history"]],
            "reference_in_its_own_place": sound, "control_fp8": control,
            "score_gap_fp8_fit": rud.score_gap(rud.host_weights(low["params"]), weights, rows),
            "score_gap_fp8_forward": rud.score_gap(weights, weights, rows, "fp8"),
            "candidate_rows": len(rows),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
