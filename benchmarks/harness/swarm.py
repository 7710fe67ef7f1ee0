"""The swarm a serve cell's decisions read, as a plain description.

Everything the scheduler will hold — fleet, tasks, the peers of each
task with their states, piece counts and parent edges — is drawn here
from the traffic file and the seed, as data. ``open_loop_decisions``
builds the live objects from it; the reference reads the same
description and never the live objects.

The shape of the work is the same for every seed: ``layout_seed`` fixes
each task's popularity, its number of parents and which parent slots are
unfed, saturated or degraded. The seed draws the hosts, their statistics,
the piece counts and the order of arrivals.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import synth


def zipf_counts(n_tasks: int, s: float, total: int) -> np.ndarray:
    """``total`` arrivals split over tasks by Zipf weights, largest
    remainder, so the split is the same for every seed."""
    w = 1.0 / np.arange(1, n_tasks + 1) ** s
    exact = w / w.sum() * total
    counts = np.floor(exact).astype(np.int64)
    for i in np.argsort(-(exact - counts), kind="stable")[: total - int(counts.sum())]:
        counts[i] += 1
    return counts


def describe(traffic: dict, seed: int) -> dict:
    lay = np.random.default_rng([traffic["layout_seed"], 11])
    rng = np.random.default_rng([seed, 12])
    n_hosts, n_tasks = traffic["hosts"], traffic["tasks"]
    hosts = synth.fleet(n_hosts, seed)
    lo, hi = traffic["parents_clip"]
    n_parents = np.clip(
        np.rint(lay.lognormal(traffic["parents_log_mean"], traffic["parents_log_sigma"], n_tasks)),
        lo, hi,
    ).astype(int)
    kids = traffic["children_per_task"]
    # a share of the fleet has no free upload slot (seed peers excepted)
    saturated = set(
        int(h) for h in lay.choice(
            np.arange(16, n_hosts), size=int(traffic["saturated_host_share"] * n_hosts), replace=False
        )
    )
    uploads_now = {}
    for i, h in enumerate(hosts):
        if i in saturated:
            h.concurrent_upload_count = h.concurrent_upload_limit
        uploads_now[i] = h.concurrent_upload_count
    # a few parents sit on saturated hosts: the free-slot rule's work
    sat_list = sorted(saturated)
    tasks = []
    for k in range(n_tasks):
        total_pieces = int(rng.integers(8, 64))
        slots = lay.random(int(n_parents[k]))
        free = [h for h in rng.permutation(n_hosts) if int(h) not in saturated]
        picked = [int(h) for h in free[: int(n_parents[k]) + kids]]
        peers = []
        for j in range(int(n_parents[k])):
            u = float(slots[j])
            state, costs, host = "Succeeded", [], picked[j]
            if j > 0:  # slot 0 is always a sound, fed, succeeded parent
                if u < traffic["running_unfed_share"]:
                    state = "Running"
                elif u < traffic["running_unfed_share"] + traffic["running_fed_share"]:
                    state = "RunningFed"
                elif u > 1.0 - traffic["degraded_share"]:
                    costs = [10.0, 12.0, 9.0, 400.0]
                elif u > 1.0 - traffic["degraded_share"] - traffic["on_saturated_share"]:
                    host = sat_list[(k * 7 + j) % len(sat_list)]
            if not costs and state == "Succeeded":
                costs = [float(c) for c in rng.uniform(5, 60, size=3)]
            peers.append(
                {
                    "id": f"peer-{k}-{j}",
                    "host": host,
                    "state": state,
                    "finished": int(rng.integers(1, total_pieces + 1))
                    if state != "Succeeded" else total_pieces,
                    "piece_costs": costs,
                    "in_degree": 0,
                    "fed_by": None,
                }
            )
        for p in peers:
            if p["state"] == "RunningFed":
                p["state"], p["in_degree"], p["fed_by"] = "Running", 1, peers[0]["id"]
                uploads_now[peers[0]["host"]] += 1
        children = [
            {
                "id": f"child-{k}-{c}",
                "host": picked[int(n_parents[k]) + c],
                "state": "ReceivedNormal",
                "finished": 0,
                "piece_costs": [],
                "in_degree": 0,
                "fed_by": None,
            }
            for c in range(kids)
        ]
        tasks.append(
            {
                "id": f"task-{k}",
                "content_length": total_pieces << 20,
                "total_pieces": total_pieces,
                "peers": peers + children,
                "children": children,
            }
        )
    edges = synth.probe_edges(
        n_hosts, seed, traffic["probe_fan_out"], traffic["probe_rounds"]
    )
    return {
        "hosts": hosts,
        "tasks": tasks,
        "edges": edges,
        "uploads_now": uploads_now,
        "topology_records": synth.topology_records(hosts, edges),
    }


def arrivals(traffic: dict, seed: int, rate: float, seconds: float):
    """(due times in seconds from the window's start, task index, child
    index within the task): a Poisson process conditioned on its count,
    which is ``round(rate * seconds)`` for every seed, with the per-task
    split fixed and the order drawn from the seed."""
    rng = np.random.default_rng([seed, 13])
    total = int(round(rate * seconds))
    counts = zipf_counts(traffic["tasks"], traffic["zipf_s"], total)
    task_idx = rng.permutation(np.repeat(np.arange(traffic["tasks"]), counts))
    child_idx = rng.integers(0, traffic["children_per_task"], size=total)
    due = np.sort(rng.uniform(0.0, seconds, size=total))
    return due, task_idx, child_idx
