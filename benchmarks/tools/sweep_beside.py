#!/usr/bin/env python3
"""Decisions beside a running resident round, once, on the chip: the
highest rate the one-chip cluster sustains there, and what holds a
decision up.

    python3 benchmarks/tools/sweep_beside.py --workload decide-under-round --rates 35,70,140 --seconds 15 --windows 3 --at-round-start 1

One set-up (the generator's own, then a round and its install), then
whole rounds back to back on a thread of their own, each followed by the
refresher's install, as in the cell. Beside them, for each rate,
``--windows`` open-loop windows of ``--seconds`` one after another, so
that a rate meets every phase of a round (``round_phase_s`` says how far
into its round a window began); with ``--at-round-start 1`` every window
waits for the next round to begin, so that every rate meets the same
phase. A rate is sustained when in every one of its windows every
decision offered completes and the backlog does not grow. A queue the
system does not keep up with makes an arrival wait (offered / served - 1)
seconds longer for every second of the window, and the middles of a
window's first and last third lie two thirds of the window apart: so the
backlog grows when the mean queue wait over the last third exceeds that
over the first third by more than ``GROWTH`` of that distance, which says
the system served less than 99% of what it was offered. The knee is the
highest rate swept below the first that is not sustained. The cell's rate
is then written by hand into ``benchmarks/cells/<cell>.json`` as the mix's
share of the knee.

A rate written ``35@5`` runs its windows at a switch interval of 5 ms
(the interpreter's own) in place of the colocated service's 0.5 ms
(``sys.setswitchinterval``): a reading of what that setting buys, kept out
of the knee.

Two witnesses run all through, and print a line each time one sees a
wait of ``--held-ms`` or more, with how far into its round it was:

``held``    a thread that sleeps a millisecond at a time was kept from the
            interpreter that long (or the machine stopped the process);
            the line carries the innermost frames of every thread that is
            not a decision's worker: one of them made the call that held.
``busy``    over a tenth of a second that thread was kept from the
            interpreter for half of it or more, by holds too short for a
            ``held`` line each: calls that hold it a few milliseconds at
            a time, one after another.
``trip``    a decision's device legs in small, every 10 ms: the put of a
            2 KB batch, a jitted add over it waited for, the read back.
            A trip that took that long while the interpreter was not held
            says which leg waited: the transfer queue behind another
            tenant's puts, the device's queue behind its programs, or
            the read.
``gc``      one collection of the interpreter's own took a fifth of that
            or more (every thread waits for it): its generation, and how
            many objects it was tracking.

Every window's line gives the longest a decision took from its start
(``service_max_us``: what the scoring service's grace is held against)
and the worst of each witness inside the window.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback

ROOT = os.path.normpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))
sys.path.insert(0, ROOT)

GROWTH = 0.01  # of the time between the two thirds: served under 99% of offered


_one_line = threading.Lock()


def say(kind: str, line: dict) -> None:
    """One witness line, whole: the witnesses print from threads of their own."""
    with _one_line:
        sys.stdout.write(f"{kind} {json.dumps(line)}\n" if kind else json.dumps(line) + "\n")
        sys.stdout.flush()


def knee(sustained: dict) -> float:
    """The highest rate below the first that is not sustained; 0.0 if the
    lowest is not."""
    best = 0.0
    for rate in sorted(sustained):
        if not sustained[rate]:
            break
        best = rate
    return best


class Witness(threading.Thread):
    """A loop that should take ``period`` seconds a turn and notes every
    turn that took ``report_s`` longer, with ``describe()`` of the moment."""

    def __init__(self, name: str, period: float, report_s: float, into_round):
        super().__init__(name=f"bench.{name}", daemon=True)
        self.kind, self.period, self.report_s, self.into_round = name, period, report_s, into_round
        self.worst = 0.0
        self.last_over = 0.0  # perf_counter of the newest wait reported
        self.stop = threading.Event()

    def turn(self) -> None:
        time.sleep(self.period)

    def describe(self) -> dict:
        return {}

    def run(self) -> None:
        while not self.stop.is_set():
            t0 = time.perf_counter()
            self.turn()
            now = time.perf_counter()
            over = now - t0 - self.period
            self.worst = max(self.worst, over)
            if over >= self.report_s:
                self.last_over = now
                line = {"s": round(over, 3), "round_s": self.into_round(t0), **self.describe()}
                say(self.kind, line)

    def take(self) -> float:
        worst, self.worst = self.worst, 0.0
        return worst


class Held(Witness):
    """Sleeps a millisecond at a time; the stacks are read when it wakes,
    so the thread that held is still in, or just out of, the call."""

    gc_lines: list = []  # watch_gc's, printed from this thread
    _bucket = (0.0, 0.0)  # (began, seconds late) of the tenth of a second being filled

    def turn(self) -> None:
        t0 = time.perf_counter()
        time.sleep(self.period)
        late = time.perf_counter() - t0 - self.period
        began, total = self._bucket
        if t0 - began >= 0.1:
            if total >= 0.05 and t0 - began < 0.2 and self.last_over < began:
                say("busy", {"share": round(total / (t0 - began), 2), "round_s": self.into_round(began)})
            began, total = t0, 0.0
        self._bucket = (began, total + max(late, 0.0))
        while self.gc_lines:
            say("gc", self.gc_lines.pop(0))

    def describe(self) -> dict:
        names = {t.ident: t.name for t in threading.enumerate()}
        stacks = {}
        for ident, frame in sys._current_frames().items():
            name = names.get(ident, "?")
            if name.startswith("bench.") and name != "bench.rounds":
                continue
            stacks[name] = [
                f"{f.name}:{f.lineno}({os.path.basename(f.filename)})" for f in traceback.extract_stack(frame)[-4:]
            ]
        return {"stacks": stacks}


class Trip(Witness):
    """A put, a jitted add and a read, each timed. A trip that a hold of
    the interpreter also explains (``held`` reported inside it) is left
    to that witness."""

    def __init__(self, *args, held: Held):
        super().__init__(*args)
        import jax
        import numpy as np

        self._held, self._jax = held, jax
        self._host = np.zeros((64, 8), np.float32)
        self._add = jax.jit(lambda x: x + 1.0)
        np.asarray(self._add(jax.numpy.asarray(self._host)))

    def run(self) -> None:
        import numpy as np

        jax = self._jax
        while not self.stop.is_set():
            time.sleep(self.period)
            t0 = time.perf_counter()
            x = jax.block_until_ready(jax.numpy.asarray(self._host))
            t1 = time.perf_counter()
            y = jax.block_until_ready(self._add(x))
            t2 = time.perf_counter()
            np.asarray(y)
            t3 = time.perf_counter()
            if self._held.last_over >= t0 - 0.002:
                continue
            self.worst = max(self.worst, t3 - t0)
            if t3 - t0 >= self.report_s:
                line = {"s": round(t3 - t0, 3), "put": round(t1 - t0, 3), "run": round(t2 - t1, 3),
                        "read": round(t3 - t2, 3), "round_s": self.into_round(t0)}
                say("trip", line)


def watch_gc(report_s: float, into_round) -> list:
    """Note every collection that takes ``report_s`` or more, as lines
    for a witness to print (a collection can begin inside a ``print``)."""
    began = [0.0]
    lines: list = []

    def on_gc(phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            began[0] = now
        elif now - began[0] >= report_s:
            lines.append({"s": round(now - began[0], 3), "round_s": into_round(began[0]),
                          "generation": info["generation"], "collected": info["collected"]})

    # first in the list: jax's own callback (it frees what the backend has
    # queued for deletion) is then inside the time between the two phases
    gc.callbacks.insert(0, on_gc)
    return lines


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--at-round-start", type=int, choices=(0, 1), default=0)
    ap.add_argument("--held-ms", type=float, default=50.0)
    ap.add_argument("--seed", type=int, default=101)
    args = ap.parse_args()

    import numpy as np

    from benchmarks import run as bench_run
    from benchmarks.generators import decide_under_round as dur
    from benchmarks.generators import open_loop_decisions as old
    from benchmarks.generators import rounds
    from benchmarks.harness import cells, device as dev, taps
    from benchmarks.harness.layer_readers import percentile
    from dragonfly2_tpu.utils.jitcache import enable_compile_cache

    cell = cells.load_cell(args.workload)
    enable_compile_cache()
    devices = dev.require_chips(cell.chips)
    os.makedirs(os.path.join(ROOT, ".bench_work"), exist_ok=True)
    workdir = tempfile.mkdtemp(prefix="sweep-", dir=os.path.join(ROOT, ".bench_work"))
    ctx = bench_run.Context(cell, args.seed, args.seconds, False, workdir, devices, dev.CompileTap(), dev.Tracer(False, ""))
    trainer, scheduler, registry, refresher, stage, desc, children = dur.setup(ctx)
    svc = scheduler.scoring_service

    began: list = []  # when each round began
    walls: list = []
    stop = threading.Event()

    def one_round() -> None:
        began.append(time.perf_counter())
        outcome = trainer.training.train(rounds.IP, rounds.HOSTNAME)
        walls.append(time.perf_counter() - began[-1])
        if not outcome.ok or not refresher.refresh_once():
            raise RuntimeError(f"a round or its install failed: {outcome!r}")
        stage.restage()

    def back_to_back() -> None:
        while not stop.is_set():
            one_round()

    def into_round(t: float) -> float:
        return round(t - max((b for b in began if b <= t), default=t), 2)

    one_round()  # compiles every fit and every serving rung
    old.drive(ctx, scheduler, children, 100.0, 1.5, args.seed + 1, 2.0)
    say("", {"warm_up_round_s": walls[0], "served": svc.snapshot()["model_version"]})
    beside = threading.Thread(target=back_to_back, name="bench.rounds", daemon=True)
    beside.start()

    def rungs() -> dict:
        series = taps.prom_series()
        return {r: series.get(dur.RUNG_SERIES % r, 0.0) for r in ("serving", "mlp", "base")}

    held = Held("held", 0.001, args.held_ms / 1e3, into_round)
    held.gc_lines = watch_gc(max(args.held_ms / 5e3, 0.01), into_round)
    say("", {"gc_tracked_objects": len(gc.get_objects()), "gc_frozen": gc.get_freeze_count()})
    trip = Trip("trip", 0.01, args.held_ms / 1e3, into_round, held=held)
    held.start()
    trip.start()
    k = 0
    sustained: dict = {}
    own_interval = sys.getswitchinterval()
    for word in args.rates.split(","):
        rate, _, ms = word.partition("@")
        rate, interval = float(rate), float(ms) / 1e3 if ms else own_interval
        sys.setswitchinterval(interval)
        for _ in range(args.windows):
            k += 1
            rounds_begun = len(began)
            while args.at_round_start and len(began) == rounds_begun:
                time.sleep(0.01)
            held.take(), trip.take()
            snap0, rung0, t0 = svc.snapshot(), rungs(), time.perf_counter()
            w = old.drive(ctx, scheduler, children, rate, args.seconds, args.seed + 10 + k, 5.0)
            snap1, rung1 = svc.snapshot(), rungs()
            done = np.array([r is not None and len(r) > 0 for r in w.returned]) & (w.end > 0)
            lat = (w.end - (w.t0 + w.due))[done] * 1e6
            took = (w.end - w.start)[done]
            wait = (w.start - (w.t0 + w.due)) * 1e6
            third = w.n // 3
            batches = snap1["batches"] - snap0["batches"]
            growth = float(wait[-third:].mean() - wait[:third].mean()) / 1e6 / (args.seconds * 2 / 3)
            kept_up = bool(done.all()) and not w.lost and not w.errors and growth <= GROWTH
            if not ms:
                sustained[rate] = sustained.get(rate, True) and kept_up
            say(
                "",
                {
                    "rate": rate,
                    "switch_interval_ms": interval * 1e3,
                    "service_max_us": float(took.max() * 1e6),
                    "service_max_at_round_s": into_round(float(w.start[done][int(np.argmax(took))])),
                    "service_p90_us": percentile(took * 1e6, 90),
                    "service_p99_us": percentile(took * 1e6, 99),
                    "held_max_us": held.take() * 1e6,
                    "trip_max_us": trip.take() * 1e6,
                    "round_phase_s": into_round(t0),
                    "offered": w.n,
                    "completed_per_s": float(done.sum()) / args.seconds,
                    "finished_by_s": float(w.end.max() - w.t0),
                    "p50_us": percentile(lat, 50),
                    "p99_us": percentile(lat, 99),
                    "wait_first_third_us_mean": float(wait[:third].mean()),
                    "wait_last_third_us_mean": float(wait[-third:].mean()),
                    "wait_max_us": float(wait.max()),
                    "backlog_growth": growth,
                    "kept_up": kept_up,
                    "batch_rows": (snap1["rows_scored"] - snap0["rows_scored"]) / max(batches, 1),
                    "below_serving": rung1["mlp"] - rung0["mlp"] + rung1["base"] - rung0["base"],
                    "lost": w.lost,
                    "errors": len(w.errors),
                },
            )
    sys.setswitchinterval(own_interval)
    held.stop.set()
    trip.stop.set()
    stop.set()
    beside.join()
    say(
        "",
        {
            "round_walls_s": [round(x, 2) for x in walls],
            "sustained": {str(r): ok for r, ok in sorted(sustained.items())},
            "knee_per_s": knee(sustained),
        },
    )
    svc.stop()
    shutil.rmtree(workdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
