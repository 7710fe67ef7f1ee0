#!/usr/bin/env python3
"""The control's readings, which a cell's limits are set from.

    python3 benchmarks/tools/readings.py --workload <cell> --seeds 1,2,3

The control is the reference computed in fp8 (e4m3), the nearest
precision below the bfloat16 the program computes in, put in the
program's place at the cell's own size. For a serve cell it ranks every
child's candidates by fp8 costs and reads the same ``rank_gap`` a run
reads; for a train cell it fits the same model with fp8 matmul inputs and
reads the same ``mlp_mse_log_ratio``, and replays the streamed fit's first
steps and the whole GraphSAGE and GRU fits in fp8 against their float32
replays (the loss-path and update gaps a run reads). The sound readings
are the numbers ``run.py`` prints beside each limit.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

ROOT = os.path.normpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fits", default="mlp,gnn,gru", help="train cells: which fits' controls to read")
    args = ap.parse_args()

    from benchmarks.harness import cells, reference, swarm, synth

    cell = cells.load_cell(args.workload)
    cfg, traffic = cell.config, cell.traffic
    for seed in (int(s) for s in args.seeds.split(",")):
        if traffic["kind"] == "open_loop_decisions":
            from benchmarks.generators import open_loop_decisions as old

            desc = swarm.describe(traffic, seed)
            dims = [reference.MLP_FEATURE_DIM, *cfg["trainer"]["mlp"]["hidden_dims"], 1]
            if cfg["served_model"] == "gnn":
                weights = synth.gnn_weights(
                    seed, traffic["hosts"], hidden=tuple(cfg["trainer"]["gnn"]["hidden_dims"])
                )
            else:
                weights = synth.mlp_weights(seed, dims)
            picks = [
                (k, c) for k in range(traffic["tasks"]) for c in range(traffic["children_per_task"])
            ]
            sound = old.judge(desc, weights, cfg, picks, lambda n: None, "float32")
            control = old.judge(desc, weights, cfg, picks, lambda n: None, "fp8")
            print(json.dumps({"seed": seed, "reference_in_its_own_place": sound, "control_fp8": control}), flush=True)
        else:
            import jax

            records = synth.download_records(traffic["body_records"], seed)
            x, y = reference.record_pairs(records)
            per_round = len(records) * traffic["body_repeats_per_chunk"] * traffic["chunks"]
            pairs_per_pass = x.shape[0] * per_round // len(records)
            mlp = cfg["trainer"]["mlp"]
            if not mlp["streaming"]:
                # the resident fit's replay needs no program run: float32
                # against fp8 in its place, at the cell's own upload
                import time

                from benchmarks.harness import reference_fits

                repeats = traffic["body_repeats_per_chunk"] * traffic["chunks"]
                rk = dict(hidden=tuple(mlp["hidden_dims"]), epochs=mlp["epochs"], batch=mlp["batch_size"],
                          learning_rate=mlp["learning_rate"], weight_decay=mlp["weight_decay"])
                t0 = time.perf_counter()
                sound_fit = reference_fits.fit_mlp(x, y, repeats, **rk)
                t1 = time.perf_counter()
                ctl_fit = reference_fits.fit_mlp(x, y, repeats, precision="fp8", **rk)
                held = reference_fits.mlp_holdout_mse(x, y, repeats, sound_fit["params"])
                held_ctl = reference_fits.mlp_holdout_mse(x, y, repeats, sound_fit["params"], precision="fp8")
                print(json.dumps({
                    "seed": seed, "platform": jax.devices()[0].platform, "steps": sound_fit["steps"],
                    "replay_s": round(t1 - t0, 2),
                    "loss": [float(v) for v in sound_fit["history"]],
                    "control_loss": [float(v) for v in ctl_fit["history"]],
                    "control_mlp_loss_path_gap": reference.path_gap(ctl_fit["history"], sound_fit["history"]),
                    "control_mlp_update_gap": reference_fits.update_gap(ctl_fit["params"], sound_fit),
                    "holdout_mse": held,
                    "control_mlp_holdout_mse_gap": abs(held_ctl - held) / held,
                }), flush=True)
                continue
            kw = dict(
                seed=seed, batch=mlp["batch_size"], hidden=tuple(mlp["hidden_dims"]),
                steps=reference.fit_steps(
                    pairs_per_pass, mlp["streaming_passes"], mlp["batch_size"],
                    round(1.0 / mlp["eval_fraction"]),
                ),
                learning_rate=mlp["learning_rate"], weight_decay=mlp["weight_decay"],
            )
            rows_x, rows_y, bias = reference.staged_rows(
                x, y, x.shape[0] * 256 // len(records), round(1.0 / mlp["eval_fraction"])
            )
            pk = dict(
                steps=traffic["follow_steps"], batch=kw["batch"], hidden=kw["hidden"],
                learning_rate=kw["learning_rate"], weight_decay=kw["weight_decay"],
                pad_to=x.shape[0],
            )
            want = reference.replay_losses(rows_x, rows_y, bias, **pk)
            ctl_path = reference.replay_losses(rows_x, rows_y, bias, precision="fp8", **pk)
            windows = {
                f"{lo}-{hi}": reference.path_gap(ctl_path[lo:hi], want[lo:hi])
                for lo, hi in ((0, 64), (64, 128), (128, 256), (256, len(want)))
            }
            import time

            import numpy as np

            from benchmarks.harness import reference_fits

            fits = {}
            gnn, gru = cfg["trainer"]["gnn"], cfg["trainer"]["gru"]
            topo = synth.topology_records(
                synth.fleet(traffic["hosts"], seed),
                synth.probe_edges(traffic["hosts"], seed, traffic["probe_fan_out"], traffic["probe_rounds"]),
            )
            graph = reference.probe_graph(topo, gnn["max_degree"])
            gkw = dict(hidden=tuple(gnn["hidden_dims"]), epochs=gnn["epochs"], batch=gnn["batch_size"],
                       learning_rate=gnn["learning_rate"], weight_decay=gnn["weight_decay"])
            repeats = traffic["body_repeats_per_chunk"] * traffic["chunks"]
            seqs = [reference_fits.newest(a, repeats, gru["max_sequences"]) for a in reference_fits.piece_sequences(records)]
            rkw = dict(hidden=gru["hidden_dims"][0], epochs=gru["epochs"], batch=gru["batch_size"],
                       learning_rate=gru["learning_rate"], weight_decay=gru["weight_decay"])
            for name, fit, fargs, fkw in (("gnn", reference_fits.fit_gnn, (graph,), gkw), ("gru", reference_fits.fit_gru, tuple(seqs), rkw)):
                if name not in args.fits.split(","):
                    continue
                t0 = time.perf_counter()
                sound_fit = fit(*fargs, **fkw)
                t1 = time.perf_counter()
                ctl_fit = fit(*fargs, precision="fp8", **fkw)
                n = len(sound_fit["history"])
                extra = {}
                if name == "gnn":
                    # the end-loss gap needs no window: the program's own fit
                    # on the same graph gives the sound reading beside the
                    # control's
                    from dragonfly2_tpu.schema.columnar import records_to_columns
                    from dragonfly2_tpu.schema.features import build_probe_graph
                    from dragonfly2_tpu.trainer.train import GNNFitConfig, train_gnn

                    rows = sound_fit["last_epoch_rows"]
                    prog = train_gnn(
                        build_probe_graph(records_to_columns(topo), max_degree=gnn["max_degree"]),
                        config=GNNFitConfig(epochs=gnn["epochs"]),
                    )
                    prog_params = jax.tree_util.tree_map(np.asarray, prog.params)

                    def end(history, params):
                        at_end = reference_fits.gnn_loss_at(graph, params, rows)
                        return abs(history[-1] - at_end) / at_end

                    extra = {
                        "sound_end_loss_gap": end(prog.history, prog_params),
                        "sound_loss_path_gap": reference.path_gap(prog.history, sound_fit["history"]),
                        "sound_update_gap": reference_fits.update_gap(prog_params, sound_fit),
                        "control_end_loss_gap": end(ctl_fit["history"], ctl_fit["params"]),
                        "replay_end_loss_gap": end(sound_fit["history"], sound_fit["params"]),
                    }
                fits[name] = {
                    **extra,
                    "replay_s": round(t1 - t0, 2),
                    "control_loss_path_gap": reference.path_gap(
                        ctl_fit["history"][slice(*cfg["trainer"][name]["follow_epochs"])],
                        sound_fit["history"][slice(*cfg["trainer"][name]["follow_epochs"])],
                    ),
                    "control_path_gap_by_epochs": {
                        f"0-{hi}": reference.path_gap(ctl_fit["history"][:hi], sound_fit["history"][:hi])
                        for hi in sorted({max(n // 10, 1), max(n // 4, 1), max(n // 2, 1), n})
                    },
                    "control_update_gap": reference_fits.update_gap(ctl_fit["params"], sound_fit),
                    "loss_first_last": [float(sound_fit["history"][0]), float(sound_fit["history"][-1])],
                    "control_loss_first_last": [float(ctl_fit["history"][0]), float(ctl_fit["history"][-1])],
                }
            if "mlp" not in args.fits.split(","):
                print(json.dumps({"seed": seed, **fits}), flush=True)
                continue
            ref = reference.mse(reference.fit_mlp(x, y, **kw), x, y)
            ctl = reference.mse(reference.fit_mlp(x, y, precision="fp8", **kw), x, y)
            print(
                json.dumps(
                    {
                        "seed": seed, "platform": jax.devices()[0].platform, "steps": kw["steps"],
                        "mse_reference": ref, "mse_control_fp8": ctl,
                        "control_mlp_mse_log_ratio": abs(math.log(ctl / ref)),
                        "control_mlp_loss_path_gap": reference.path_gap(ctl_path, want),
                        "control_path_gap_by_steps": windows,
                        **fits,
                    }
                ),
                flush=True,
            )
    return 0


if __name__ == "__main__":
    sys.exit(main())
