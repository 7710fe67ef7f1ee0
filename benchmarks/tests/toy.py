"""A toy benchmark root for the CPU rehearsal: the real data files copied
into a scratch directory and cut to a size a test run can hold. Nothing
here is read by a run on the chip.
"""

from __future__ import annotations

import json
import os
import shutil

from benchmarks.harness import cells

TOY_TRAIN = {
    "chunks": 2, "body_records": 256, "body_repeats_per_chunk": 8,
    "hosts": 64, "trace_from_s": 0.1, "trace_seconds": 0.3, "follow_steps": 40,
}
TOY_SERVE = {
    "hosts": 96, "tasks": 12, "parents_clip": [1, 24], "workers": 4,
    "warmup_seconds": 0.3, "drain_seconds": 3.0, "sample_decisions": 60,
    "trace_from_s": 0.1, "trace_seconds": 0.3,
}


def _edit(path: str, **changes) -> None:
    with open(path) as f:
        doc = json.load(f)
    for key, value in changes.items():
        node = doc
        *parents, leaf = key.split(".")
        for p in parents:
            node = node[p]
        node[leaf] = value
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)


def _write_bench(root: str) -> None:
    """BENCHMARK.json with the cells held back by the size floor put back
    in: the rehearsal drives those too."""
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(cells.BENCH_DIR, "held_back.json")) as f:
        held = json.load(f)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        have = {e["name"]: e for e in bench[key]}
        for entry in held[key]:
            if entry["name"] not in have:
                bench[key].append(entry)
            elif "workloads" in entry and "workloads" in have[entry["name"]]:
                mine = have[entry["name"]]
                mine["workloads"] = sorted(set(mine["workloads"]) | set(entry["workloads"]))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=2)


def make_root(tmp: str) -> str:
    """Copy BENCHMARK.json and the data directories to ``tmp`` and shrink
    them: a 256-row batch, small uploads, a small swarm, a slow rate."""
    root = os.path.join(str(tmp), "root")
    bdir = os.path.join(root, "benchmarks")
    os.makedirs(bdir)
    _write_bench(root)
    for sub in ("configs", "traffic", "cells", "layer_metrics"):
        shutil.copytree(os.path.join(cells.BENCH_DIR, sub), os.path.join(bdir, sub))
    for name in os.listdir(os.path.join(bdir, "configs")):
        _edit(
            os.path.join(bdir, "configs", name),
            **{
                "trainer.mlp.batch_size": 256,
                "limits.rank_gap": 0.05,
                "limits.mlp_mse_log_ratio": 2.0,
                "limits.mlp_loss_path_gap": 0.05,
                "limits.mlp_update_gap": 0.05,
                "limits.mlp_holdout_mse_gap": 0.01,
                "limits.gnn_loss_path_gap": 0.2,
                "limits.gnn_update_gap": 0.5,
                "limits.gnn_end_loss_gap": 0.01,
                "limits.gru_loss_path_gap": 0.01,
                "limits.gru_update_gap": 0.05,
            },
        )
    _edit(os.path.join(bdir, "traffic", "rounds-4chunk.json"), **TOY_TRAIN)
    resident = {k: v for k, v in TOY_TRAIN.items() if k != "follow_steps"}
    _edit(os.path.join(bdir, "traffic", "rounds-60chunk.json"), **resident)
    _edit(os.path.join(bdir, "traffic", "decide-poisson-0.8.json"), **TOY_SERVE)
    for name in os.listdir(os.path.join(bdir, "cells")):
        _edit(os.path.join(bdir, "cells", name), rate_per_s=150.0)
    return root


def force_streaming(monkeypatch) -> None:
    """A toy upload is under the trainer's 64 MiB streaming threshold;
    the rehearsal takes the streamed path all the same."""
    from dragonfly2_tpu.trainer.training import Training

    monkeypatch.setattr(Training, "_use_streaming", lambda self, path, offset, binary: True)
