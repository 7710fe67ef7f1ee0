"""Cadences of a cluster's uploads: several schedulers, one trainer.

Set-up builds the trainer as the ``TrainerServer`` binary does (without
``serve()``) and stages every scheduler's upload with the storage calls
the Train-stream handler makes: each scheduler its own seeded records
and its own probe graph, under its own host id. One untimed cadence
compiles every fit. A cadence is what a cluster brought up by one
``kubectl apply`` sends its trainer once a week: the schedulers' streams
end within a second of each other (``stream_end_offsets_s``, in an order
drawn from the seed), and each end takes the handler's own path,
``TrainerService.fit_after_stream``: the round's boundary marked, the
round forked on a thread of its own. The trainer admits the rounds to its
chip by the bytes each will hold there, runs side by side those that
fit, and merges the MLP versions when the last has returned. The window
runs whole cadences back to back until ``--seconds`` of cadence walls
have accumulated; a cadence's wall runs from the first stream's end to
the return of its last round and its merge. Between cadences the consumed
uploads are put back by hard link, outside the walls.

What the fits were handed is kept as a sample of every round's pairs
(``decide_under_round.ResidentFits``' stride), never a round's arrays:
three whole weeks' pairs are 13.8 GB beside 22.5 GiB of files on a host
that ends a command at 40 GiB, and the trainer frees them a slice at a time when it alone holds them.
In the cadence that is expected to be the window's last, every round's
rows are also compared whole with the uploads' bodies, on a thread of
the harness beside the fit (a second or two of one core, done long
before the fit returns, so the trainer's release meets no other
holder); if a cadence that was not expected to be turns out the last,
one more is run.
"""

from __future__ import annotations

import math
import os
import threading
import time

import numpy as np

from benchmarks.generators import rounds
from benchmarks.harness import admission as plain
from benchmarks.harness import reference, reference_fits, taps

LEGS = ("mlp", "gnn", "gru")
SAMPLE_STRIDE = 101  # of a round's rows (decide_under_round's): 545,052 of 55M, a prime so that it walks the body
PHASES = ["trainer.round_wait", "trainer.merge", "trainer.round"]


def scheduler_address(k: int) -> tuple:
    return f"10.0.0.{k + 1}", f"bench-scheduler-{k}"


def scheduler_seed(seed: int, k: int, schedulers: int) -> int:
    """A seed of its own for every (run, scheduler)."""
    return seed * schedulers + k


def chunks_of(traffic: dict) -> list:
    """Each scheduler's announcer chunks an upload: ``chunks_per_upload``
    is one number for all, or (a control's) one for each."""
    chunks = traffic["chunks_per_upload"]
    return list(chunks) if isinstance(chunks, list) else [chunks] * traffic["schedulers"]


def kept_holdout_weights(n_body: int, repeats: int, stated: dict) -> np.ndarray:
    """How often each row of the body falls among the holdout rows a
    round keeps for the merge's evaluation, as the deployment states it
    (``merge.holdout_kept``): a share of the fit's holdout from the
    front of its drawn order, no fewer than so many rows (all of a
    smaller holdout)."""
    n = n_body * repeats
    held = np.random.default_rng(reference_fits.FIT_SEED).permutation(n)[: int(n * reference_fits.EVAL_FRACTION)]
    kept = held[: max(int(len(held) * stated["share_of_holdout"]), min(len(held), stated["at_least_rows"]))]
    return np.bincount(kept % n_body, minlength=n_body).astype(np.float64)


def device_bytes_limit(devices: list) -> "int | None":
    """What the chip says it holds, asked of the chip: the reference's
    own reading, not the trainer's."""
    limits = [(d.memory_stats() or {}).get("bytes_limit") for d in devices]
    return min(limits) if limits and all(limits) else None


class Registry:
    """The slice of the manager the trainer calls: what every host's
    rounds and the merge registered, cadence by cadence."""

    def __init__(self):
        self.cadence: list = []  # (hostname, model type, params, evaluation)

    def create_model(self, model_id, model_type, ip, hostname, params, evaluation):
        self.cadence.append((hostname, model_type, params, dict(evaluation)))


class Fits:
    """The three fit functions spied for one cadence at a time. Of a
    resident MLP fit every ``SAMPLE_STRIDE``-th row is copied before the
    fit; where ``whole`` is set its rows are compared whole with the
    uploads' ``bodies`` (each scheduler's ``(x, y)``) beside the fit."""

    def __init__(self, training_mod, train_mod, bodies: list):
        self.mods = ((training_mod, "train_mlp", self._mlp), (training_mod, "train_gnn", self._gnn), (train_mod, "train_gru", self._gru))
        self.real: dict = {}
        self.bodies = bodies
        self.whole = False
        self.begin()

    def begin(self) -> None:
        self.mlp, self.gnn, self.gru = [], [], []

    def _mlp(self, features, labels, **kwargs):
        call = {"rows": features.shape[0], "x": features[::SAMPLE_STRIDE].copy(), "y": labels[::SAMPLE_STRIDE].copy()}
        if self.whole:
            call["comparing"] = threading.Thread(target=self._compare_whole, args=(call, features, labels), name="bench.rows")
            call["comparing"].start()
        call["fit"] = self.real["train_mlp"](features, labels, **kwargs)
        self.mlp.append(call)
        return call["fit"]

    def _compare_whole(self, call: dict, features, labels) -> None:
        """``call["whole"]`` = (whose upload these rows are, by their
        first row; the elements that differ from that upload, whole:
        the body repeats, row ``i`` is row ``i % len(body)`` of it)."""
        call["whole"] = (None, math.inf)
        for k, (x, y) in enumerate(self.bodies):
            n = x.shape[0]
            if features.shape[0] % n == 0 and features.shape[1:] == x.shape[1:] and np.array_equal(features[0], x[0]):
                repeats, differ = features.shape[0] // n, 0.0
                for lo in range(0, repeats, 64):  # 64 repeats a look: the comparison's temporaries stay small
                    hi = min(lo + 64, repeats)
                    differ += reference_fits.mismatches(
                        (features[lo * n : hi * n].reshape(hi - lo, *x.shape), np.broadcast_to(x, (hi - lo, *x.shape))),
                        (labels[lo * n : hi * n].reshape(hi - lo, n), np.broadcast_to(y, (hi - lo, n))),
                    )
                call["whole"] = (k, differ)
                return

    def _gnn(self, graph, **kwargs):
        self.gnn.append({"graph": graph, "fit": self.real["train_gnn"](graph, **kwargs)})
        return self.gnn[-1]["fit"]

    def _gru(self, sequences, labels, **kwargs):
        fit = self.real["train_gru"](sequences, labels, **kwargs)
        self.gru.append({"fed": (sequences, labels, kwargs.get("lengths")), "fit": fit})
        return fit

    def __enter__(self):
        for module, name, spy in self.mods:
            self.real[name] = getattr(module, name)
            setattr(module, name, spy)
        return self

    def __exit__(self, *exc):
        for module, name, _ in self.mods:
            setattr(module, name, self.real[name])


def _same_tree(a, b) -> bool:
    import jax

    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def _fit_of(calls: list, params):
    """The spied call whose fit ended at the registered ``params``: a
    fit's parameters say whose round it was (every host fits its own
    records from the same start)."""
    return next((c for c in calls if params is not None and _same_tree(c["fit"].params, params)), None)


def stage_all(storage, traffic: dict, seed: int) -> list:
    """Every scheduler's upload in trainer storage, each under its own
    host id, side by side (the writes wait for the disk, not for each
    other)."""
    from dragonfly2_tpu.utils.idgen import host_id_v2

    n, chunks = traffic["schedulers"], chunks_of(traffic)
    stages: list = [None] * n

    def stage(k: int) -> None:
        mix = {**traffic, "chunks": chunks[k]}
        stages[k] = rounds.Stage(storage, host_id_v2(*scheduler_address(k)), mix, scheduler_seed(seed, k, n))

    threads = [threading.Thread(target=stage, args=(k,), name=f"bench.stage-{k}") for k in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if any(s is None for s in stages):
        raise SystemExit("an upload could not be staged")
    return stages


def run(ctx) -> dict:
    from dragonfly2_tpu.trainer import metrics as trainer_metrics
    from dragonfly2_tpu.trainer import train as train_mod
    from dragonfly2_tpu.trainer import training as training_mod
    from dragonfly2_tpu.trainer.server import TrainerServer, TrainerServerConfig
    from dragonfly2_tpu.trainer.service import TrainerService
    from dragonfly2_tpu.utils.idgen import host_id_v2

    cell, traffic = ctx.cell, ctx.cell.traffic
    ctx.marks["imports_and_chip"] = time.perf_counter()
    # a trainer that cannot fork a host's round as its handler does, or
    # that admits every caller at once, is not this deployment's: said
    # before a trainer is built (a TrainerServer starts the native
    # library's build on a thread of its own, and an exit beside it would
    # leave that make behind, running) and before a byte is staged
    if not hasattr(TrainerService, "fit_after_stream") or not hasattr(training_mod, "RoundAdmission") or not hasattr(trainer_metrics, "ROUNDS_RESERVED_BYTES"):
        raise SystemExit("this trainer has no TrainerService.fit_after_stream / Training.admission: it cannot run three schedulers' cadences")
    want, stated = cell.config["trainer"], cell.config["admission"]
    n_sched = traffic["schedulers"]
    if n_sched != cell.config["schedulers"] or len(traffic["stream_end_offsets_s"]) != n_sched or len(chunks_of(traffic)) != n_sched:
        raise SystemExit("the mix and the configuration disagree on the schedulers")
    srv = TrainerServer(
        TrainerServerConfig(
            data_dir=os.path.join(ctx.workdir, "trainer"),
            mlp_epochs=want["mlp"]["epochs"],
            mlp_batch_size=want["mlp"]["batch_size"],
            gnn_epochs=want["gnn"]["epochs"],
            streaming=want["mlp"]["streaming"],
            streaming_workers=want["mlp"]["streaming_workers"],
        )
    )
    training, service = srv.training, srv.service
    rounds.check_config(training, cell.config)
    if want["mlp"]["streaming"]:
        raise SystemExit("the cell runs resident fits: the configuration has streaming on")
    if training.mesh is not None or len(ctx.devices) != 1:
        raise SystemExit(f"fit mesh {training.mesh} on {len(ctx.devices)} device(s): the cell is one chip's")
    registry = Registry()
    training.manager_client = registry
    hosts = [scheduler_address(k) for k in range(n_sched)]
    host_ids = [host_id_v2(*h) for h in hosts]
    stages = stage_all(srv.storage, traffic, ctx.seed)
    records_per_cadence = sum(s.records_per_round for s in stages)
    ctx.marks["staged"] = time.perf_counter()
    # the order the streams end in: drawn once a run
    order = [int(k) for k in np.random.default_rng([ctx.seed, 44]).permutation(n_sched)]
    offsets = sorted(traffic["stream_end_offsets_s"])
    limit = device_bytes_limit(ctx.devices)
    room = plain.budget(limit, stated)

    outcomes: list = []  # (scheduler, arrived at, returned at, outcome) of the cadence running
    real_train = training.train

    def kept_train(ip, hostname):
        k, arrived = hosts.index((ip, hostname)), time.perf_counter()
        outcome = real_train(ip, hostname)
        outcomes.append((k, arrived, time.perf_counter(), outcome))
        return outcome

    training.train = kept_train
    bodies = [reference.record_pairs(s.records) for s in stages]  # each scheduler's (x, y): its upload repeats them
    fits = Fits(training_mod, train_mod, bodies)
    # the program's own two gauges, every value they are set to: the rounds
    # running, then the bytes they were reckoned to hold
    gauged: list = []

    def one_cadence(whole: bool) -> dict:
        registry.cadence = []
        del outcomes[:], gauged[:]
        fits.begin()  # the last cadence's arrays go before this one's are made
        fits.whole = whole
        before = taps.prom_series()
        t0 = time.perf_counter()
        threads = []
        for offset, k in zip(offsets, order):
            time.sleep(max(t0 + offset - time.perf_counter(), 0.0))
            threads.append(service.fit_after_stream(*hosts[k]))
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        for call in fits.mlp:
            if "comparing" in call:
                call.pop("comparing").join()
        moved = taps.series_delta(before, taps.prom_series())
        by_host: dict = {}
        faults: list = []
        for k, arrived, returned, outcome in sorted(outcomes):
            registered = {t: (p, e) for h, t, p, e in registry.cadence if h == hosts[k][1]}
            found = {leg: _fit_of(getattr(fits, leg), registered.get(leg, (None,))[0]) for leg in LEGS}
            mlp, gnn, gru = (found[leg] for leg in LEGS)
            sound = (
                (f"outcome {outcome!r}", outcome.ok and outcome.gru_error is None),
                ("three versions registered", sorted(registered) == sorted(LEGS)),
                ("every version the end of a fit on the chip", all(found.values())),
                ("mlp loss finite and lower", mlp is not None and rounds.decreased(mlp["fit"].history)),
                ("gnn loss finite and lower", gnn is not None and rounds.decreased(gnn["fit"].history)),
                ("gru loss finite and lower", gru is not None and rounds.decreased(gru["fit"].history)),
            )
            faults += [f"scheduler {k}: {name}" for name, ok in sound if not ok]
            a = outcome.admission
            by_host[k] = {
                "arrived": arrived - t0, "returned": returned - t0, "wall_s": outcome.wall_s,
                "admission": {
                    "arrival": a.arrival, "order": a.order, "result": a.result, "waited_s": a.waited_s,
                    "reserved_bytes": a.reserved_bytes,
                },
                "fits": {leg: outcome.splits[leg].wall_s for leg in LEGS if leg in outcome.splits},
                "params": {t: p for t, (p, _) in registered.items()},
                "evaluations": {t: e for t, (_, e) in registered.items()},
                "mlp_losses": [] if mlp is None else list(mlp["fit"].history),
                "gnn_losses": [] if gnn is None else list(gnn["fit"].history),
                "gru_losses": [] if gru is None else list(gru["fit"].history),
                "mlp_pairs": 0 if mlp is None else mlp["rows"],
                "mlp_sample": None if mlp is None else (mlp["rows"], mlp["x"], mlp["y"]),
                "gnn_nodes_edges": (0, 0) if gnn is None else (gnn["graph"].num_nodes, len(gnn["graph"].edge_src)),
                "gru_sequences": 0 if gru is None else gru["fed"][0].shape[0],
                # whose upload the fit's rows were, and how far from it, whole: (None, inf) where they were not compared
                "rows_whole": mlp.get("whole", (None, math.inf)) if mlp is not None else (None, math.inf),
                "fed": {
                    **({"gnn": gnn["graph"]} if gnn is not None else {}),
                    **({"gru": gru["fed"]} if gru is not None else {}),
                },
                "on_chip": mlp is not None and all(
                    d.platform == ctx.devices[0].platform for leaf in rounds._leaves(mlp["fit"].params) for d in leaf.devices()
                ),
            }
        if len(by_host) != n_sched:
            faults.append(f"{len(by_host)} of {n_sched} rounds returned an outcome")
        merged = [(p, e) for h, t, p, e in registry.cadence if h == "federated"]
        return {
            "wall_s": wall, "ok": not faults, "outcome": "; ".join(faults), "hosts": by_host, "whole": whole,
            "versions": sum(1 for h, *_ in registry.cadence if h != "federated"), "merged": merged,
            # (rounds running, their reckoned bytes) at every change of either
            "side_by_side": [(int(n[0][0]), int(b[0][0])) for n, b in zip(gauged[::2], gauged[1::2])],
            "waited": sum(v for s, v in moved.items() if "round_admission_total" in s and '"waited"' in s),
            "admitted": sum(v for s, v in moved.items() if "round_admission_total" in s),
        }

    def put_back() -> None:
        for stage in stages:
            stage.restage()

    with fits, taps.spy(trainer_metrics.ROUNDS_RUNNING, "set", gauged, with_args=True), taps.spy(
        trainer_metrics.ROUNDS_RESERVED_BYTES, "set", gauged, with_args=True
    ):
        warm = one_cadence(False)  # compiles every fit; not timed
        if not warm["ok"]:
            raise SystemExit(f"the warm-up cadence failed: {warm['outcome']}")
        ctx.marks["warm_up_cadence"] = time.perf_counter()
        put_back()
        phases0 = taps.phase_counts(PHASES)
        ctx.window_opens()
        # a traced run records two seconds from the start of the first
        # cadence, before a GRU fit reaches the chip: the profiler takes
        # minutes to write out a stretch that holds its 70,000 scan steps
        tracer_thread = ctx.tracer.record_later(traffic["trace_from_s"], traffic["trace_seconds"])
        cadences: list = []
        spent, shortest = 0.0, warm["wall_s"]
        while spent < ctx.seconds or not cadences[-1]["whole"]:
            c = one_cadence(whole=spent + 0.85 * shortest >= ctx.seconds)
            cadences.append(c)
            spent += c["wall_s"]
            shortest = min(shortest, c["wall_s"])
            put_back()
            if not c["ok"]:
                break  # a failed cadence is reported, not repeated
        if tracer_thread is not None:
            tracer_thread.join()
        ctx.window_closes()
    training.train = real_train
    fits.begin()

    good = [c for c in cadences if c["ok"]]
    walls = sum(c["wall_s"] for c in good)
    epochs = training.config.mlp.epochs
    metrics = {"train_records_per_s": records_per_cadence * epochs * len(good) / walls if good else 0.0}
    rounds_run = [c["hosts"][k] for c in good for k in sorted(c["hosts"])]
    probes = {
        "fit_duration": {leg: [r["fits"][leg] for r in rounds_run if leg in r["fits"]] for leg in LEGS},
        "prof_phase": taps.phase_delta(phases0, taps.phase_counts(PHASES)),
        "prom_series": {
            'dragonfly_trainer_round_admission_total{result="waited"}': float(sum(c["waited"] for c in cadences)),
            "rounds": float(sum(c["admitted"] for c in cadences)),
        },
    }
    last = cadences[-1]
    trainer_cfg, limits = cell.config["trainer"], cell.config["limits"]
    body_repeats = [traffic["body_repeats_per_chunk"] * c for c in chunks_of(traffic)]  # by scheduler
    feed_ok = all(r["on_chip"] for r in rounds_run)
    program_budget = training.admission.budget
    del srv, training, service, registry, fits

    def after_window() -> list:
        """The comparison with the plain reference, host by host, once
        the program's state is freed."""
        checks = [
            ("cadences_failed", float(len(cadences) - len(good)), 0.0),
            ("feed_off_chip", 0.0 if feed_ok else 1.0, 0.0),
            # (a) nine versions a cadence under the hosts' ids, (d) one merged
            ("versions_gap", float(sum(abs(c["versions"] - len(LEGS) * n_sched) + abs(len(c["merged"]) - 1) for c in cadences)), 0.0),
        ]
        # (b), (c): the program's own account of every admission against
        # its budget, and the order of admission against the plain replay
        beyond, gap = 0, int(program_budget != room)  # the program's budget is the deployment's stated one
        sizes = [plain.round_bytes(x.shape[0] * body_repeats[k], stated) for k, (x, _) in enumerate(bodies)]
        for c in [warm, *cadences]:
            got = sorted(c["hosts"], key=lambda k: c["hosts"][k]["admission"]["order"])
            replayed = plain.replay([(k, r["arrived"], r["returned"], sizes[k]) for k, r in c["hosts"].items()], room)
            gap += sum(a != b for a, b in zip(got, [k for k, _ in replayed])) + abs(len(got) - len(replayed))
            # the program's reckoning of every round is the deployment's stated one
            gap += sum(r["admission"]["reserved_bytes"] != sizes[k] for k, r in c["hosts"].items())
            # whenever rounds stood side by side, by the program's own gauges
            beyond += sum(n > 1 and room is not None and nbytes > room for n, nbytes in c["side_by_side"])
            notes["replayed"].append([[k, "waited" if w else "at_once"] for k, w in replayed])
        checks += [("rounds_admitted_beyond_limit", float(beyond), 0.0), ("admission_order_gap", float(gap), 0.0)]
        # every round of every cadence was handed its own host's pairs: sampled
        sampled = 0.0
        for k, body in enumerate(bodies):
            samples = [c["hosts"][k]["mlp_sample"] for c in [warm, *cadences] if k in c["hosts"] and c["hosts"][k]["mlp_sample"]]
            sampled += sampled_mismatch(samples, *body)
        checks.append(("mlp_rows_sampled_mismatch", sampled, 0.0))
        # each host's registered versions against the replay of ITS upload
        weights = {}  # by scheduler: the pairs its registered MLP version was fitted on
        for k, s in enumerate(stages):
            mine = [c["hosts"][k] for c in good if k in c["hosts"]]
            r = last["hosts"].get(k)
            if r is None:
                checks.append((f"round_missing.s{k}", 1.0, 0.0))
                continue
            print(f"scheduler {k} ({hosts[k][1]}): the replay of its own upload", flush=True)
            fed = r.pop("fed")
            held = (
                rounds._hold_resident_mlp(mine, r, fed, s.records, body_repeats[k], trainer_cfg["mlp"], limits)
                + rounds._hold_gnn(mine, r, fed, s.topology, trainer_cfg["gnn"], limits)
                + rounds._hold_gru(mine, r, fed, s.records, body_repeats[k], trainer_cfg["gru"], limits)
            )
            del fed
            # the rows were compared whole beside the fit, not kept: this scheduler's upload, element for element
            whose, differ = r["rows_whole"]
            held = [(name, differ if whose == k else math.inf, limit) if name == "mlp_rows_mismatch" else (name, value, limit) for name, value, limit in held]
            checks += [(f"{name}.s{k}", value, limit) for name, value, limit in held]
            if r["params"].get("mlp") is not None:
                weights[k] = float(r["mlp_pairs"])
        checks += hold_merged(last, weights, limits)
        return checks

    def hold_merged(c: dict, weights: dict, limits: dict) -> list:
        """(d): the registered merged parameters against the float32
        pair-weighted mean of the cadence's three registered versions,
        and the error registered with them against the float32 error of
        those merged parameters on the holdout rows the deployment says
        the rounds keep, every scheduler's pooled."""
        import jax

        # the trainer averages in the order of the hosts' ids; a mean's order moves its last bit
        ks = sorted(c["hosts"], key=lambda k: host_ids[k])
        gap = held = math.inf
        if len(c["merged"]) == 1 and len(ks) == n_sched and all(k in weights for k in ks):
            merged, evaluation = c["merged"][0]
            w = np.asarray([weights[k] for k in ks], np.float32)
            w = w / w.sum(dtype=np.float32)
            num = den = 0.0
            for got, *leaves in zip(*(jax.tree_util.tree_leaves(t) for t in [merged, *(c["hosts"][k]["params"]["mlp"] for k in ks)])):
                mean = sum(np.asarray(leaf, np.float32) * wi for leaf, wi in zip(leaves, w))
                num += float(np.sum((np.asarray(got, np.float64) - mean) ** 2))
                den += float(np.sum(np.asarray(mean, np.float64) ** 2))
            gap = math.sqrt(num / den) if den else math.inf
            plain_merged = {"layers": [{name: np.asarray(v, np.float32) for name, v in layer.items()} for layer in merged["layers"]]}
            squared = rows = 0.0
            for k in ks:
                x, y = bodies[k]
                kept = kept_holdout_weights(x.shape[0], body_repeats[k], cell.config["merge"]["holdout_kept"])
                err = reference.mlp_forward(plain_merged, x, "float32").astype(np.float64) - y
                squared, rows = squared + float((kept * err**2).sum()), rows + float(kept.sum())
            want = squared / rows
            if "mse" in evaluation and evaluation.get("holdout_rows") == rows:
                held = abs(evaluation["mse"] - want) / want
            print(f"merged holdout mse: registered {evaluation.get('mse')!r} over {evaluation.get('holdout_rows')!r} rows, float32 over the {rows:.0f} stated rows {want!r}", flush=True)
        return [("merged_gap", gap, limits["merged_gap"]), ("mlp_holdout_mse_gap.merged", held, limits["mlp_holdout_mse_gap"])]

    def sketch(c: dict) -> dict:
        return {
            "wall_s": round(c["wall_s"], 4),
            "admitted": [
                [k, r["admission"]["result"], round(r["admission"]["waited_s"], 3), round(r["wall_s"], 3)]
                for k, r in sorted(c["hosts"].items(), key=lambda kr: kr[1]["admission"]["order"])
            ],
            "most_side_by_side": list(max(c["side_by_side"], default=(0, 0))),  # rounds, their reckoned bytes
        }

    notes = {
        "cadences": len(cadences),
        "stream_end_order": order,
        "bytes_limit": limit,
        "budget_bytes": room,
        "reserved_bytes": [c["hosts"][k]["admission"]["reserved_bytes"] for c in cadences[:1] for k in sorted(c["hosts"])],
        "warm_up": sketch(warm),
        "by_cadence": [sketch(c) for c in cadences],
        "records_per_cadence": records_per_cadence,
        "failures": [c["outcome"] for c in cadences if not c["ok"]],
        "replayed": [],  # the plain replay's admissions, the warm-up's first: filled by the comparison
    }
    return {
        "metrics": metrics,
        "probes": probes,
        "attempted": len(cadences),
        "failed": len(cadences) - len(good),
        "after_window": after_window,
        "notes": notes,
    }


def sampled_mismatch(samples: list, x: np.ndarray, y: np.ndarray) -> float:
    """Sampled rows of every round's pairs (``(rows, x, y)`` a round)
    against the upload's body ``x``, ``y``, which repeats: row ``i`` is
    row ``i % len(body)`` of it."""
    total = 0.0
    for rows, got_x, got_y in samples:
        pick = np.arange(0, rows, SAMPLE_STRIDE) % x.shape[0]
        total += reference_fits.mismatches((got_x, x[pick]), (got_y, y[pick]))
    return total
