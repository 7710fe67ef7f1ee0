#!/usr/bin/env python3
"""The two controls' readings for a cell that serves the round's own
GraphSAGE version (``decide_gnn_under_round``), with trained weights.

    python3 benchmarks/tools/readings_served_gnn.py --workload decide-gnn-under-round --seeds 1,2,3

The weights are the float32 replay's of the GraphSAGE fit on the cell's
own upload graph (a run holds every registered version to it:
``gnn_update_gap``, ``gnn_end_loss_gap``). The graph they are served on is
the one an install reads: the same edges in their seeded order of arrival,
sources in the order of their first probe, the five freshest targets each
(``live_records``: the export's rule, plainly). With the rows placed on it
by host id, every child's candidates are ranked

- by the float32 reference (``reference_in_its_own_place``: gap 0),
- by the same reference in fp8 (``control_fp8``),
- by the float32 reference with the fitted table joined to the live graph
  row by row, as a scorer that ignores the ids would (``control_rows_by_position``,
  with the count of hosts that then hold another host's row),

and each line gives what a run's decision checks would read in each case.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.normpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))
sys.path.insert(0, ROOT)


def live_records(desc: dict, seed: int, dests_per_source: int) -> list:
    """What the engine's export hands an install after
    ``decide_gnn_under_round.fill_topology``: the description's edges in
    their order of arrival, a record a source in the order of first
    arrival, its first ``dests_per_source`` targets (all were adopted at
    one instant, so the freshest are the first)."""
    import numpy as np

    from benchmarks.harness import synth

    order = np.random.default_rng([seed, 15]).permutation(len(desc["edges"]))
    kept: dict = {}
    for k in order:
        s, t, rtt = desc["edges"][int(k)]
        if len(kept.setdefault(s, [])) < dests_per_source:
            kept[s].append((s, t, rtt))
    return synth.topology_records(desc["hosts"], [e for edges in kept.values() for e in edges], dests_per_source)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()

    import time

    import numpy as np

    from benchmarks.generators import decide_gnn_under_round as gur
    from benchmarks.generators import open_loop_decisions as old
    from benchmarks.harness import cells, reference, reference_fits, swarm

    cell = cells.load_cell(args.workload)
    cfg, traffic = cell.config, cell.traffic
    gnn, limit = cfg["trainer"]["gnn"], cfg["scheduler"]["candidate_parent_limit"]
    for seed in (int(s) for s in args.seeds.split(",")):
        desc = swarm.describe(traffic, seed)
        graph = reference.probe_graph(desc["topology_records"], gnn["max_degree"])
        t0 = time.perf_counter()
        fit = reference_fits.fit_gnn(
            graph, hidden=tuple(gnn["hidden_dims"]), epochs=gnn["epochs"], batch=gnn["batch_size"],
            learning_rate=gnn["learning_rate"], weight_decay=gnn["weight_decay"],
        )
        weights = gur.host_weights(fit["params"])
        live = live_records(desc, seed, cfg["served_gnn"]["export_dests_per_source"])
        served = {**desc, "topology_records": live}
        by_id = gur.placed_weights(weights, graph["order"], live)
        by_position = gur.placed_weights(weights, graph["order"], live, by="position")
        picks = [(k, c) for k in range(traffic["tasks"]) for c in range(traffic["children_per_task"])]
        sound = old.judge(served, by_id, cfg, picks, lambda n: None, "float32")
        fp8 = old.judge(served, by_id, cfg, picks, lambda n: None, "fp8")
        # the positional scorer in the program's place: it ranks every
        # child's legal candidates by its own costs and returns the best
        index = {h.id: i for i, h in enumerate(desc["hosts"])}
        scorer = reference.GnnReference(live, index, by_position, gnn["max_degree"])
        returned = []
        for k, c in picks:
            task = desc["tasks"][k]
            child = task["children"][c]
            legal = reference.legal_parents(task, child, desc["hosts"], desc["uploads_now"])
            costs = scorer.costs([child["host"]] * len(legal), [p["host"] for p in legal])
            returned.append([legal[j]["id"] for j in np.argsort(costs, kind="stable")[:limit]])
        position = old.judge(served, by_id, cfg, picks, lambda n: returned[n])
        order = reference.probe_graph(live)["order"]
        position["rows_misplaced"] = gur.rows_misplaced(
            {hid: by_position["node_embed"][i] for hid, i in order.items()}, weights, graph["order"]
        )
        print(json.dumps({
            "seed": seed, "replay_s": round(time.perf_counter() - t0, 2),
            "loss": [float(fit["history"][0]), float(fit["history"][-1])],
            "live_hosts": len(order), "live_edges": sum(len(r.dest_hosts) for r in live),
            "reference_in_its_own_place": sound, "control_fp8": fp8, "control_rows_by_position": position,
            "candidate_pairs": sound["rows"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
