#!/usr/bin/env python3
"""The fp8 control of ``train-rounds-three-schedulers``' MLP limits.

    python3 benchmarks/tools/readings_three_schedulers.py --seeds 7 8

For each seed and each scheduler of the cell, the float32 replay of the
scheduler's own upload (its seeded body, replicated to the cell's size,
in the fit's stated order) beside the same replay with fp8 (e4m3)
matmul inputs, the nearest precision below the bfloat16 the program
computes in, put in the program's place: the three MLP gaps a run
prints, which have to lie over their limits. No program run is needed
(``tools/readings.py`` does the same for ``train-round-resident``); on
the chip it takes about a minute a scheduler.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.normpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))
sys.path.insert(0, ROOT)

CELL = "train-rounds-three-schedulers"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()

    import jax

    from benchmarks.generators import rounds_by_scheduler as gen
    from benchmarks.harness import cells, reference, reference_fits, synth

    cell = cells.load_cell(CELL)
    traffic, mlp, limits = cell.traffic, cell.config["trainer"]["mlp"], cell.config["limits"]
    repeats = traffic["body_repeats_per_chunk"] * traffic["chunks_per_upload"]
    kw = dict(hidden=tuple(mlp["hidden_dims"]), epochs=mlp["epochs"], batch=mlp["batch_size"],
              learning_rate=mlp["learning_rate"], weight_decay=mlp["weight_decay"])
    for seed in args.seeds:
        for k in range(traffic["schedulers"]):
            records = synth.download_records(traffic["body_records"], gen.scheduler_seed(seed, k, traffic["schedulers"]))
            x, y = reference.record_pairs(records)
            t0 = time.perf_counter()
            sound = reference_fits.fit_mlp(x, y, repeats, **kw)
            control = reference_fits.fit_mlp(x, y, repeats, precision="fp8", **kw)
            held = reference_fits.mlp_holdout_mse(x, y, repeats, sound["params"])
            held_fp8 = reference_fits.mlp_holdout_mse(x, y, repeats, sound["params"], precision="fp8")
            gaps = {
                "mlp_loss_path_gap": reference.path_gap(control["history"], sound["history"]),
                "mlp_update_gap": reference_fits.update_gap(control["params"], sound),
                "mlp_holdout_mse_gap": abs(held_fp8 - held) / held,
            }
            print(json.dumps({
                "seed": seed, "scheduler": k, "platform": jax.devices()[0].platform, "steps": sound["steps"],
                "control_fp8": gaps, "limits": {name: limits[name] for name in gaps},
                "over_its_limit": {name: gaps[name] > limits[name] for name in gaps},
                "seconds": round(time.perf_counter() - t0, 1),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
