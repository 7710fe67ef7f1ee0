#!/usr/bin/env python3
"""One run of a decide cell, and the decisions' wait by what the round was
doing when each began (ISSUE 39; PERF.md §5).

    chiprun -- python3 hack/decisions_by_phase.py --workload decide-under-round --seed 7 --seconds 51 --trace 0

The run is ``benchmarks/run.py``'s own, argument for argument, in the tree
it is started from (the working directory: so a parent unpacked under
``.chip_trees/`` is read with this same file). The window's median is a
mixture: a decision that begins while the resident load walks the headers
(the interpreter's work) waits otherwise than one that begins while the
assembly's workers copy (no interpreter lock) or while the epoch runs (the
device shared). A change that shortens one of those stretches moves the
window's median though no decision of any stretch waits longer. After the
result line one more line gives, for each stretch of the timed rounds, its
seconds a round, the decisions that began in it and their p50 / p90 / p99
from the due time, in ms: ``walk`` (``wire.walk_train_pairs``),
``assemble`` (``TrainPairsWalk.assemble``), ``fit`` (the rest of
``Training.train``: the MLP leg's put, epoch and holdout, and whatever the
other two legs still run) and ``between`` (the install and the restage).
A second line cuts the same decisions by how many of the round's three legs
were still running when each began (``legs_3``: all of them; ``legs_1``:
the MLP leg alone), and the ``assemble`` and ``fit`` stretches by the same:
two trees read alike where the same stretch beside the same legs waits the
same, and differ in how many seconds a round are of each kind.

Since ISSUE 40 the program books the same cut itself (a decision's seconds
from inside, ``scheduler.find_parents_beside_<stretch>``, and the stretches
as ``trainer.mlp_load_walk`` / ``_load_assemble``): a third line gives, for
the same window, the in-program count and mean a stretch and the two
stretches' seconds a round, so one run shows whether inside and outside
agree (``fit`` there is ``fit_shared`` and ``fit_alone`` together, ``idle``
is ``between``). The outside cut's ``service_mean_ms`` is the mean from a
decision's own start, the queue wait left out: what the inside means are
to be held against. A tree without those phases prints zeros.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import numpy as np


def main() -> int:
    from benchmarks import run as bench_run
    from benchmarks.generators import decide_under_round as gen
    from dragonfly2_tpu.schema import wire
    from dragonfly2_tpu.trainer import training as training_mod
    from dragonfly2_tpu.utils import profiling

    inside_names = ("walk", "assemble", "fit_shared", "fit_alone", "idle")
    inside = {
        **{n: profiling.phase_type(f"scheduler.find_parents_beside_{n}") for n in inside_names},
        "walk_s": profiling.phase_type("trainer.mlp_load_walk"),
        "assemble_s": profiling.phase_type("trainer.mlp_load_assemble"),
        "find_parents": profiling.phase_type("scheduler.find_parents"),
    }
    inside_at: list = []  # the phases' (count, seconds) as each window opened and closed, the timed window's last

    marks: list = []  # (what, perf_counter)
    windows: list = []  # (t0, due, start, end) of every Beside closed, the timed window's last

    def marked(fn, before, after):
        def wrapper(*a, **kw):
            if before:
                marks.append((before, time.perf_counter()))
            try:
                return fn(*a, **kw)
            finally:
                marks.append((after, time.perf_counter()))

        return wrapper

    wire.walk_train_pairs = marked(wire.walk_train_pairs, "walk", "walk_end")
    wire.TrainPairsWalk.assemble = marked(wire.TrainPairsWalk.assemble, None, "assemble_end")
    training_mod.Training.train = marked(training_mod.Training.train, "round", "round_end")
    training_mod.Training._train_gnn = marked(training_mod.Training._train_gnn, None, "gnn_end")
    training_mod.Training._train_gru = marked(training_mod.Training._train_gru, None, "gru_end")
    close, open_ = gen.Beside.close, gen.Beside.open

    def ledger() -> dict:
        return {k: (ph.count, ph.total_s) for k, ph in inside.items()}

    def opening(self, *a, **kw):
        inside_at.append([ledger(), None])
        return open_(self, *a, **kw)

    def closing(self, *a, **kw):
        out = close(self, *a, **kw)
        inside_at[-1][1] = ledger()
        windows.append((self.t0, np.array(self.due), np.array(self.start), np.array(self.end)))
        return out

    gen.Beside.open, gen.Beside.close = opening, closing
    rc = bench_run.main()
    t0, due, start, end = windows[-1]
    answered = end > 0
    # the rounds that began inside the window: [round, walk_end, assemble_end, round_end]
    rounds, others, at = [], [], {}
    for what, t in marks:
        at[what] = t
        if what == "round_end" and at["round"] >= t0 - 1.0:  # the first begins as the window opens, 50 ms before its t0
            rounds.append([at["round"], at["walk_end"], at["assemble_end"], t])
            others.append(sorted(at.get(leg, t) for leg in ("gnn_end", "gru_end")))
    names = ("walk", "assemble", "fit", "between")
    lat: dict = {n: [] for n in names}
    service: dict = {n: [] for n in names}  # from a decision's own start: no queue wait in it
    seconds = {n: 0.0 for n in names}
    for i, r in enumerate(rounds):
        until = rounds[i + 1][0] if i + 1 < len(rounds) else r[3]
        edges = [*r, until]
        for n, lo, hi in zip(names, edges, edges[1:]):
            seconds[n] += hi - lo
            took = answered & (start >= lo) & (start < hi)
            lat[n].extend(((end - (t0 + due))[took] * 1e3).tolist())
            service[n].extend(((end - start)[took] * 1e3).tolist())
    out = {
        n: {
            "s_a_round": round(seconds[n] / max(len(rounds), 1), 3), "decisions": len(lat[n]),
            **({f"p{q}_ms": round(float(np.percentile(lat[n], q)), 2) for q in (50, 90, 99)} if lat[n] else {}),
            **({"mean_ms": round(float(np.mean(lat[n])), 2), "service_mean_ms": round(float(np.mean(service[n])), 2)} if lat[n] else {}),
        }
        for n in names
    }
    print("decisions by the round's stretch: " + json.dumps({"rounds": len(rounds), **out}), flush=True)
    # the program's own record of the same window
    was, now = inside_at[-1]
    moved = {k: (now[k][0] - was[k][0], now[k][1] - was[k][1]) for k in inside}
    fit = tuple(moved["fit_shared"][i] + moved["fit_alone"][i] for i in (0, 1))
    print(
        "decisions by the round's stretch, from inside: "
        + json.dumps({
            "find_parents": moved["find_parents"][0],
            **{
                n: {"decisions": c, "mean_ms": round(s / c * 1e3, 2) if c else None}
                for n, (c, s) in (*((n, moved[n]) for n in inside_names), ("fit", fit))
            },
            **{n: round(moved[n][1] / moved[n][0], 3) if moved[n][0] else None for n in ("walk_s", "assemble_s")},
        }),
        flush=True,
    )
    try:
        # the same decisions by the legs still running: three until the first of the other two ends, one after the second
        cut: dict = {}
        for r, (first, second) in zip(rounds, others):
            for legs, lo, hi in ((3, r[0], first), (2, first, second), (1, second, r[3])):
                for n, s_lo, s_hi in (("", lo, hi), ("assemble.", max(lo, r[1]), min(hi, r[2])), ("fit.", max(lo, r[2]), min(hi, r[3]))):
                    if s_hi <= s_lo:
                        continue
                    took = answered & (start >= s_lo) & (start < s_hi)
                    entry = cut.setdefault(f"{n}legs_{legs}", [0.0, []])
                    entry[0] += s_hi - s_lo
                    entry[1].extend(((end - (t0 + due))[took] * 1e3).tolist())
        print(
            "decisions by the legs running: "
            + json.dumps({
                k: {
                    "s_a_round": round(s / max(len(rounds), 1), 3), "decisions": len(v),
                    **({f"p{q}_ms": round(float(np.percentile(v, q)), 2) for q in (50, 90, 99)} if v else {}),
                }
                for k, (s, v) in sorted(cut.items())
            }),
            flush=True,
        )
    except Exception:  # the first line stands whatever happens to the second
        import traceback

        traceback.print_exc()
    return rc


if __name__ == "__main__":
    sys.exit(main())
