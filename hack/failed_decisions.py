#!/usr/bin/env python3
"""Runs of one cell on a given tree, and of each run the decisions that
failed or took longest, by where in the round they began (ISSUE 43;
PERF.md §7, ROADMAP S4).

    chiprun -- python3 hack/failed_decisions.py --workload decide-gnn-under-churn --seeds 11 12 [--tree .chip_trees/parent]

A tool from outside the program and outside ``benchmarks/``: each seed is
one process that runs ``benchmarks/run.py``'s own ``main``, argument for
argument, in ``--tree`` (the working directory by default: so a parent
unpacked under ``.chip_trees/`` is read with this same file), untraced
unless ``--trace 1``. Nothing a cell runs is replaced. Around the run's
timed window the process keeps what an untraced run does not say:

* a heartbeat thread that sleeps 5 ms at a time and notes, at every beat,
  the clock and the stretch of the trainer's round
  (``colocated.server.round_stretch``: ``walk``, ``assemble``,
  ``fit_shared``, ``fit_alone``, ``idle``; a tree without it reads
  ``?``). A beat that comes late is a stop of the whole process (the
  machine, or something that holds the interpreter lock): what
  ``process_pause_us_max`` says in traced runs only. 200 wake-ups a
  second, where the traced run's own heartbeat makes a thousand. That
  thread touches no memory it did not have, and a stall of whatever maps
  or first touches memory passes it by (PERF.md §7: sixteen decisions
  of 3 s with no beat late), so a second thread maps a page of its own,
  writes it and unmaps it, 200 times a second, and notes how long that
  took;
* the window's decisions as the generator's ``Beside`` holds them when it
  closes (due, start, end, answer), and when each round began and ended.

After the run's own lines (its result line, its ``notes:``), one line
``failed decisions:`` gives the rounds' walls, the decisions offered,
answered and answered inside the service's window and grace, the untraced
p50 / p99 from the due time and the longest from a decision's own start,
and then: ``failed``, every decision that was lost, raised, or took the
window and grace from its own start (ten at most), and ``longest``, the
five longest, each as [seconds it took from its own start, second of the
window it began at, the round it began in, second of that round, the
stretch]; ``heartbeat``: the longest stop as [seconds, second of the
window, round, second of the round, the stretch at the beat before it],
how many beats came 50 ms late or more, and ``longest_fresh_page``, the
longest a fresh page took to map, write and unmap, placed the same way.
``phases:`` gives the round's phases over the window as
``benchmarks/tools/phases.py`` reads them (entries, seconds), those of the
load first. The parent prints these lines for every seed, and ``--out
DIR`` keeps each run's whole output there.
"""

from __future__ import annotations

import argparse
import json
import mmap
import os
import subprocess
import sys
import threading
import time

BEAT_S = 0.005
PHASES = (
    "trainer.round", "trainer.mlp_fit", "trainer.gru_fit", "trainer.gnn_fit", "trainer.mlp_load", "trainer.mlp_load_walk",
    "trainer.mlp_load_walk_native", "trainer.mlp_load_assemble", "trainer.mlp_load_span", "trainer.mlp_load_check",
    "trainer.gru_load", "trainer.gru_load_native", "trainer.gnn_load", "trainer.mlp_table_put", "trainer.mlp_feed_slice", "trainer.mlp_epoch_dispatch",
    "trainer.gru_epoch_dispatch", "trainer.gnn_epoch_dispatch", "process.gc_full", "scheduler.find_parents",
    "scheduler.find_parents_beside_walk", "scheduler.find_parents_beside_assemble", "scheduler.find_parents_beside_fit_shared",
    "scheduler.find_parents_beside_fit_alone", "scheduler.find_parents_beside_idle", "scheduler.gnn_reembed", "topology.flush",
)


def one_run() -> int:
    """The child: ``benchmarks/run.py`` in this process, watched."""
    sys.path.insert(0, os.getcwd())
    import numpy as np

    from benchmarks import run as bench_run
    from benchmarks.generators import decide_under_round as gen
    from benchmarks.harness import taps
    from benchmarks.harness.layer_readers import percentile  # the generator's own: nearest rank
    from dragonfly2_tpu.scheduler.serving import ServingConfig
    from dragonfly2_tpu.trainer import training as training_mod

    try:
        from dragonfly2_tpu.colocated.server import round_stretch
    except ImportError:  # a tree from before the provider
        round_stretch = lambda: "?"  # noqa: E731

    beats: list = []  # (clock, the round's stretch) a beat of the timed window
    rounds: list = []  # [began, ended] of every ``Training.train``
    windows: list = []  # every ``Beside`` closed: the timed window's is the last
    ledger: dict = {}
    stop = threading.Event()

    def beat() -> None:
        while not stop.is_set():
            beats.append((time.perf_counter(), round_stretch()))
            time.sleep(BEAT_S)

    pages: list = []  # (clock, seconds a fresh page took) a beat of the timed window

    def touch() -> None:
        while not stop.is_set():
            t = time.perf_counter()
            page = mmap.mmap(-1, mmap.PAGESIZE)
            page[0] = 1
            page.close()
            pages.append((t, time.perf_counter() - t))
            time.sleep(BEAT_S)

    train = training_mod.Training.train

    def timed_round(self, *a, **kw):
        rounds.append([time.perf_counter(), None])
        try:
            return train(self, *a, **kw)
        finally:
            rounds[-1][1] = time.perf_counter()

    training_mod.Training.train = timed_round
    close = gen.Beside.close

    def closing(self, *a, **kw):
        out = close(self, *a, **kw)
        windows.append(self)
        return out

    gen.Beside.close = closing
    opens, closes = bench_run.Context.window_opens, bench_run.Context.window_closes

    def window_opens(ctx) -> None:
        opens(ctx)
        ledger["open"] = taps.phase_counts(list(PHASES))
        del windows[:]  # the warm-up's
        ledger["t0"] = time.perf_counter()
        threading.Thread(target=beat, name="hack.heartbeat", daemon=True).start()
        threading.Thread(target=touch, name="hack.fresh_page", daemon=True).start()

    def window_closes(ctx) -> None:
        stop.set()
        ledger["t1"] = time.perf_counter()
        ledger["close"] = taps.phase_counts(list(PHASES))
        closes(ctx)

    bench_run.Context.window_opens, bench_run.Context.window_closes = window_opens, window_closes
    rc = bench_run.main()
    if "close" not in ledger:
        return rc
    t_open = ledger["t0"]
    inside = [r for r in rounds if r[1] is not None and r[0] >= t_open - 1.0 and r[0] <= ledger["t1"]]

    def placed(t: float) -> list:
        """Second of the window, the round it fell in (-1: before the first), second of that round."""
        at = max((i for i, r in enumerate(inside) if r[0] <= t), default=-1)
        return [round(t - t_open, 3), at, round(t - inside[at][0], 3) if at >= 0 else None]

    clock = np.array([b[0] for b in beats])

    def stretch_at(t: float) -> str:
        i = int(np.searchsorted(clock, t, side="right")) - 1
        return beats[i][1] if i >= 0 else "?"

    out: dict = {"rounds": len(inside), "round_walls_s": [round(r[1] - r[0], 3) for r in inside]}
    gaps = np.diff(clock) - BEAT_S
    if gaps.size:
        worst = int(np.argmax(gaps))
        out["heartbeat"] = {
            "beats": len(beats), "late_50ms": int((gaps >= 0.05).sum()),
            "longest_stop": [round(float(gaps[worst]), 4), *placed(float(clock[worst])), beats[worst][1]],
        }
        if pages:
            at, took = max(pages, key=lambda p: p[1])
            out["heartbeat"]["longest_fresh_page"] = [round(took, 4), *placed(at), stretch_at(at)]
    if windows:
        win = windows[-1]
        due, start, end = np.array(win.due), np.array(win.start), np.array(win.end)
        cfg = ServingConfig()
        timeout_s = cfg.window_s + cfg.service_grace_s
        answered = np.array([r is not None and len(r) > 0 for r in win.returned], bool) & (end > 0)
        took = np.where(end > 0, end - start, np.inf)
        done = answered & (took < timeout_s)
        lat_ms = (end - (win.t0 + due))[done] * 1e3

        def told(i: int) -> list:
            began = float(start[i]) if start[i] > 0 else float(win.t0 + due[i])
            what = "answered" if done[i] else "late" if answered[i] else "lost" if start[i] <= 0 else "no answer"
            return [round(float(took[i]), 4) if np.isfinite(took[i]) else None, *placed(began), stretch_at(began), what]

        failed = np.flatnonzero(~done)
        out.update({
            "decisions": int(win.n), "answered": int(answered.sum()), "done": int(done.sum()), "lost": int(getattr(win, "lost", 0)),
            "errors": list(win.errors[:3]),
            "latency_ms": {f"p{q}": round(percentile(lat_ms, q), 2) for q in (50, 90, 99)} if lat_ms.size else {},
            "service_ms_max": round(float(took[answered].max() * 1e3), 2) if answered.any() else None,
            "failed": [told(int(i)) for i in failed[:10]], "failed_more": max(len(failed) - 10, 0),
            "longest": [told(int(i)) for i in np.argsort(-np.where(answered, took, -1.0))[:5] if answered[i]],
        })
    print("failed decisions: " + json.dumps(out), flush=True)
    moved = {
        k.split(".", 1)[1]: [d["count"], round(d["total_s"], 3)]
        for k, d in taps.phase_delta(ledger["open"], ledger["close"]).items()
        if d["count"]
    }
    print("phases: " + json.dumps(moved), flush=True)
    return rc


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+")
    ap.add_argument("--seed", type=int, help=argparse.SUPPRESS)  # the child's
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--tree", default=".", help="the checkout to run in (its benchmarks/run.py, its program)")
    ap.add_argument("--label", default="", help="a word for the output's lines and files: which side this tree is")
    ap.add_argument("--out", help="a directory that keeps each run's whole output")
    ap.add_argument("--one", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.one:
        sys.argv = [
            "run.py", "--workload", args.workload, "--seed", str(args.seed),
            "--seconds", f"{args.seconds:g}", "--trace", str(args.trace),
        ]
        return one_run()
    worst = 0
    for seed in args.seeds or []:
        cmd = [
            sys.executable, os.path.abspath(__file__), "--one", "--workload", args.workload, "--seed", str(seed),
            "--seconds", f"{args.seconds:g}", "--trace", str(args.trace),
        ]
        began = time.perf_counter()
        run = subprocess.run(cmd, cwd=args.tree, capture_output=True, text=True)
        head = {"workload": args.workload, "side": args.label or args.tree, "seed": seed, "rc": run.returncode, "process_s": round(time.perf_counter() - began, 1)}
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            name = os.path.join(args.out, f"{args.workload}.{args.label or 'tree'}.{seed}")
            with open(name + ".log", "w") as f:
                f.write(run.stdout)
            with open(name + ".err", "w") as f:
                f.write(run.stderr)
        for line in run.stdout.splitlines():
            if line.startswith("{"):
                try:
                    result = json.loads(line)
                except ValueError:
                    continue
                metrics = {k: v["value"] if isinstance(v, dict) else v for k, v in result.get("metrics", {}).items()}
                keep = ("train_records_per_s", "setup_s", "mlp_fit_s", "gru_fit_s", "gnn_fit_s", "decision_latency_us_p50", "decision_latency_us_p99", "process_pause_us_max")
                head.update({"correct": result.get("correct"), "failed": result.get("failed"), "attempted": result.get("attempted"), **{k: metrics[k] for k in keep if k in metrics}})
            elif line.startswith("notes: "):
                notes = json.loads(line.split(": ", 1)[1])
                head.update({k: notes[k] for k in ("answered_late", "fell_a_rung", "by_rung", "latency_us", "service_us") if k in notes})
        print("== " + json.dumps(head), flush=True)
        for line in run.stdout.splitlines():
            if line.startswith(("failed decisions: ", "phases: ")):
                print("   " + line, flush=True)
        if run.returncode:
            print("   stderr: " + run.stderr[-1500:].replace("\n", " | "), flush=True)
        worst = max(worst, run.returncode)
    return worst


if __name__ == "__main__":
    sys.exit(main())
