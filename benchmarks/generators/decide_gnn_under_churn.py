"""``decide_gnn_under_round`` on a fleet that joins and leaves: the
one-chip cluster of an autoscaled node pool.

Everything ``decide_gnn_under_round`` does is done here by the same
pieces (its registry, ``build``, ``hold_gnn``, ``placed_weights``,
``rows_misplaced``; ``decide_under_round``'s ``Beside``, ``ResidentFits``,
``hold_mlp``; the staged upload of ``rounds``; the swarm and arrivals of
``open_loop_decisions``). What differs:

**The fleet.** The upload is fitted on ``fitted_hosts``; the live fleet at
set-up holds ``late_hosts`` more (in the host manager, the swarm and the
engine, five probe targets each), so the first install already places
learned rows, default rows and, once a fitted host has left, drops rows.
From the window's first second hosts join and leave on an open-loop
Poisson schedule drawn from the seed (``describe``). A join is the
service's ``AnnounceHost`` handler, ten probe results into the engine's
delta queue (its five targets, and five live hosts that picked it), and
peers of a few tasks drawn from the mix's Zipf: a finished parent each,
and a child that takes over one of the task's arrival slots. A leave is
a live host drawn uniformly through the service's ``LeaveHost`` handler;
arrival slots it held go to a new child on another live host first, and
a task it would leave without a parent for some child gets one on a live
seed host (a seed peer's back-to-source download), so that no decision
fails for want of a candidate. Both handlers are called in process, as
decisions are.

**The refresher's own loop** (``ModelRefresher.start()``, interval from
the configuration) installs a round's versions at the first poll after
they are registered and re-embeds the loaded GraphSAGE version at every
poll that finds the live graph moved. ``refresh_once()`` is called nowhere
inside the window.

**The log.** Every host event, every flush of the engine and every export
is written down in the order of its effect (``membership``), events,
flushes and exports one at a time under a lock of the harness's own, which
no decision takes. ``membership.Replay`` replays it after the window.

``correct``: every check of ``decide_gnn_under_round`` but
``decisions_below_serving`` (a decision that names a host outside the
embed in force is ranked by the MLP under it by design, and counted by
that reason), each decision judged on the swarm as the replay says it
stood when the decision began: GraphSAGE-ranked ones against
``GnnReference`` on the graph the replay says the embed in force read,
MLP-ranked ones against the MLP version in force with the replay's RTT
estimates; and the new limits named in ``README-autoscaled-cluster-gnn.md``.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import sys
import threading
import time

import numpy as np

from benchmarks.generators import decide_gnn_under_round as gur
from benchmarks.generators import decide_under_round as rud
from benchmarks.generators import open_loop_decisions as old
from benchmarks.generators import rounds
from benchmarks.harness import membership, reference, synth
from benchmarks.harness import swarm as swarm_mod
from benchmarks.harness import taps
from benchmarks.harness.layer_readers import percentile

NS_PER_MS = 1e6
REEMBED_SERIES = 'dragonfly_scheduler_gnn_reembed_total{result="%s"}'
UNKNOWN_SERIES = "dragonfly_scheduler_gnn_unknown_host_total"
NODE_CAPACITY_SERIES = 'dragonfly_topology_capacity{kind="nodes"}'
SWAP_PHASES = [
    "scheduler.gnn_export", "scheduler.gnn_graph_build", "scheduler.gnn_embed", "scheduler.gnn_install",
    "scheduler.gnn_reembed", "scheduler.gnn_rung_prepare", "topology.flush", "topology.delete_host",
]


# -- the fleet and its events, from the seed --------------------------------


def describe(traffic: dict, seed: int) -> dict:
    """``swarm.describe`` over the live fleet at set-up (the fitted hosts
    first, host for host the upload's), the probe graph the engine is
    filled with (the upload's edges and five targets a late host), the
    hosts that will join, and the whole script of events: each with its
    due time, its host, its probes and the peers it brings or re-points.
    Events are carried out one at a time in this order, so the script is
    a function of the seed alone."""
    fitted, late = traffic["fitted_hosts"], traffic["late_hosts"]
    n_live = fitted + late
    if traffic["hosts"] != fitted or traffic["live_hosts_at_setup"] != n_live:
        raise SystemExit("the mix's hosts, fitted_hosts, late_hosts and live_hosts_at_setup disagree")
    desc = swarm_mod.describe({**traffic, "hosts": n_live}, seed)
    horizon = traffic["events_horizon_seconds"]
    n_join, n_leave = int(round(traffic["joins_per_s"] * horizon)), int(round(traffic["leaves_per_s"] * horizon))
    rng = np.random.default_rng([seed, 21])
    hosts = list(desc["hosts"])
    for j in range(n_join):
        rec = synth.host_record(rng, f"j{j:05d}-{seed % 100000:05d}")
        # what the scheduler knows of a host from its announce: no upload history yet
        hosts.append(dataclasses.replace(rec, concurrent_upload_count=0, upload_count=0, upload_failed_count=0))
    coords = rng.uniform(0, 1, size=(len(hosts), 2))

    def rtt_ns(a: int, b: int) -> int:
        return int((1.0 + 80.0 * float(np.linalg.norm(coords[a] - coords[b])) + rng.exponential(2.0)) * NS_PER_MS)

    fan = traffic["probe_fan_out"]
    edges = list(synth.probe_edges(fitted, seed, fan, traffic["probe_rounds"]))
    for a in range(fitted, n_live):
        for b in rng.choice(n_live - 1, size=fan, replace=False):
            b = int(b) + (int(b) >= a)
            edges.append((a, b, rtt_ns(a, b)))
    uploads_now = dict(desc["uploads_now"])
    for i in range(n_live, len(hosts)):
        uploads_now[i] = 0

    # every peer carries the event that brought it (-1: set-up) and, for a
    # child whose arrival slot another took, the event at which it left the
    # task (the peer GC's work, done by the harness at once, so that a task
    # holds its parents and one child a slot, as in the frozen cells, and
    # the filter's sample of 15 peers never comes up without a parent); a
    # task's arrival slots start with its own children
    for t in desc["tasks"]:
        for p in t["peers"]:
            p["born"] = -1
        for c, child in enumerate(t["children"]):
            child["slot"] = c
    slots = {(k, c): t["children"][c] for k, t in enumerate(desc["tasks"]) for c in range(len(t["children"]))}
    live = list(range(n_live))
    left: set = set()
    zipf = 1.0 / np.arange(1, traffic["tasks"] + 1) ** traffic["zipf_s"]
    zipf /= zipf.sum()
    seeds = [i for i, h in enumerate(hosts[:n_live]) if h.type != "normal"]
    due = np.sort(np.concatenate([rng.uniform(1.0, horizon, n_join), rng.uniform(1.0, horizon, n_leave)]))
    kinds = rng.permutation(np.array(["join"] * n_join + ["leave"] * n_leave))
    events, joined = [], 0

    def view(k: int) -> dict:
        t = desc["tasks"][k]
        return {**t, "peers": [{**p, "state": "Leave"} if p["host"] in left else p for p in t["peers"] if "gone" not in p]}

    def parent_peer(k: int, host: int, e: int, tag: str) -> dict:
        t = desc["tasks"][k]
        return {
            "id": f"{tag}-{e}-{k}", "host": host, "state": "Succeeded", "finished": t["total_pieces"],
            "piece_costs": [float(c) for c in rng.uniform(5, 60, size=3)], "in_degree": 0, "fed_by": None, "born": e,
        }

    def child_peer(k: int, c: int, host: int, e: int) -> dict:
        return {
            "id": f"child-{e}-{k}-{c}", "host": host, "state": "ReceivedNormal", "finished": 0,
            "piece_costs": [], "in_degree": 0, "fed_by": None, "born": e, "slot": c,
        }

    for e, (at, kind) in enumerate(zip(due.tolist(), kinds.tolist())):
        if kind == "join":
            h = n_live + joined
            joined += 1
            targets = [live[int(i)] for i in rng.choice(len(live), size=fan, replace=False)]
            pickers = [live[int(i)] for i in rng.choice(len(live), size=fan, replace=False)]
            probes = [(h, b, rtt_ns(h, b)) for b in targets] + [(a, h, rtt_ns(a, h)) for a in pickers]
            brought = []
            for k in rng.choice(traffic["tasks"], size=traffic["join_tasks"], replace=False, p=zipf):
                k = int(k)
                c = int(rng.integers(0, traffic["children_per_task"]))
                parent, child = parent_peer(k, h, e, "joined"), child_peer(k, c, h, e)
                desc["tasks"][k]["peers"] += [parent, child]
                slots[(k, c)]["gone"] = e  # the child it takes the slot from is done, and leaves the task
                brought.append({"task": k, "slot": c, "parent": parent, "child": child, "replaces": slots[(k, c)]["id"]})
                slots[(k, c)] = child
            live.append(h)
            events.append({"due": at, "kind": "join", "host": h, "probes": probes, "peers": brought})
            continue
        h = live.pop(int(rng.integers(0, len(live))))
        left.add(h)
        repoint, cover = [], []
        touched = sorted({k for k, t in enumerate(desc["tasks"]) if any(p["host"] == h for p in t["peers"])})
        for (k, c), child in sorted(slots.items()):
            if child["host"] == h:
                new = child_peer(k, c, live[int(rng.integers(0, len(live)))], e)
                desc["tasks"][k]["peers"].append(new)
                child["gone"] = e
                slots[(k, c)] = new
                repoint.append({"task": k, "slot": c, "child": new, "replaces": child["id"]})
        for k in touched:
            children = [slots[(k, c)] for c in range(traffic["children_per_task"])]
            if all(reference.legal_parents(view(k), ch, hosts, uploads_now) for ch in children):
                continue
            # a seed peer's back-to-source download: a parent on a live seed host no child of the task sits on
            pool = [s for s in seeds if s not in left and all(ch["host"] != s for ch in children)]
            pool = pool or [x for x in live if all(ch["host"] != x for ch in children)]
            new = parent_peer(k, pool[int(rng.integers(0, len(pool)))], e, "seed")
            desc["tasks"][k]["peers"].append(new)
            cover.append({"task": k, "parent": new})
        events.append({"due": at, "kind": "leave", "host": h, "repoint": repoint, "cover": cover, "tasks": touched})
    return {**desc, "hosts": hosts, "edges": edges, "uploads_now": uploads_now, "events": events, "live_at_setup": n_live}


def host_info(rec):
    """A host record as the daemon announces it."""
    import common_pb2

    return common_pb2.HostInfo(
        id=rec.id, type=rec.type, hostname=rec.hostname, ip=rec.ip, port=rec.port,
        download_port=rec.download_port, os=rec.os, concurrent_upload_limit=rec.concurrent_upload_limit,
        cpu=common_pb2.CpuStat(
            logical_count=rec.cpu.logical_count, percent=rec.cpu.percent, process_percent=rec.cpu.process_percent
        ),
        memory=common_pb2.MemoryStat(
            total=rec.memory.total, available=rec.memory.available, used=rec.memory.used,
            used_percent=rec.memory.used_percent,
        ),
        network=common_pb2.NetworkStat(
            tcp_connection_count=rec.network.tcp_connection_count,
            upload_tcp_connection_count=rec.network.upload_tcp_connection_count,
            location=rec.network.location, idc=rec.network.idc,
        ),
        disk=common_pb2.DiskStat(
            total=rec.disk.total, used_percent=rec.disk.used_percent, inodes_total=rec.disk.inodes_total,
            inodes_used_percent=rec.disk.inodes_used_percent,
        ),
    )


class Fleet:
    """The harness's side of the live fleet: the log, the lock under
    which events, flushes and exports take turns, and the thread that
    carries the script out."""

    def __init__(self, scheduler, desc: dict, children: list):
        self.scheduler, self.desc, self.children = scheduler, desc, children
        self.service = scheduler.service
        self.engine = scheduler.topology_engine
        self.turn = threading.RLock()
        self.log: list = []
        self.done = 0  # events carried out
        self.asking: "Beside | None" = None  # the window whose workers ask for parents beside the events
        self.late_s: list = []  # how long after its due time each began
        self.errors: list = []
        self._stop = threading.Event()
        self._thread: "threading.Thread | None" = None
        real_flush, real_export = self.engine.flush, scheduler.networktopology.export_records

        def flush(*args, **kwargs):
            with self.turn:
                t0 = time.perf_counter()
                applied = real_flush(*args, **kwargs)
                self.log.append(["flush", t0, time.perf_counter(), applied])
                return applied

        def export_records(*args, **kwargs):
            with self.turn:
                t0 = time.perf_counter()
                records = real_export(*args, **kwargs)
                self.log.append(["export", t0, time.perf_counter()])
                return records

        self.engine.flush = flush
        scheduler.networktopology.export_records = export_records

    def fill(self, seed: int) -> None:
        """Set-up's probe graph as probes reached a scheduler: the edges
        in an order of arrival drawn from the seed, then one flush."""
        ids = [h.id for h in self.desc["hosts"]]
        now = time.time()
        with self.turn:
            for h in self.desc["hosts"][: self.desc["live_at_setup"]]:
                self.log.append(["announce", 0.0, 0.0, h.id])
            for k in np.random.default_rng([seed, 15]).permutation(len(self.desc["edges"])):
                s, t, rtt = self.desc["edges"][int(k)]
                self.engine.adopt(ids[s], ids[t], float(rtt), now)
                self.log.append(["adopt", 0.0, 0.0, ids[s], ids[t], float(rtt), now])
        self.engine.flush()

    def _peer(self, p: dict, k: int):
        from dragonfly2_tpu.scheduler import resource as res

        resource = self.scheduler.resource
        task = resource.task_manager.load(self.desc["tasks"][k]["id"])
        peer = res.Peer(p["id"], task, resource.host_manager.load(self.desc["hosts"][p["host"]].id))
        peer.fsm.event(res.PEER_EVENT_REGISTER_NORMAL)
        if p["state"] == "Succeeded":
            peer.fsm.event(res.PEER_EVENT_DOWNLOAD)
            peer.fsm.event(res.PEER_EVENT_DOWNLOAD_SUCCEEDED)
        peer.finished_pieces = set(range(p["finished"]))
        peer.piece_costs_ms = list(p["piece_costs"])
        resource.peer_manager.store(peer)
        return peer

    def _retire(self, k: int, c: int, peer_id: str) -> None:
        """The child that held arrival slot ``(k, c)`` until now leaves the
        task, once the decisions already asking for it have their answer: a
        peer does not leave while it waits for parents, and one deleted
        under its own decision would come back with none."""
        win = self.asking
        if win is not None:
            busy = {w: a for w, a in list(win.asking.items()) if a is not None and a[:2] == (k, c)}
            deadline = time.perf_counter() + 2.0  # a decision's deadline and as much again
            while busy and time.perf_counter() < deadline:
                time.sleep(0.001)
                busy = {w: a for w, a in busy.items() if win.asking.get(w) == a}
        self.scheduler.resource.peer_manager.delete(peer_id)

    def carry_out(self, e: int) -> None:
        import scheduler_pb2

        ev, hosts = self.desc["events"][e], self.desc["hosts"]
        hid = hosts[ev["host"]].id
        with self.turn:
            t0 = time.perf_counter()
            if ev["kind"] == "join":
                self.service.AnnounceHost(scheduler_pb2.AnnounceHostRequest(host=host_info(hosts[ev["host"]])), None)
                self.log.append(["announce", t0, time.perf_counter(), hid])
                at = time.time()
                for a, b, rtt in ev["probes"]:
                    entry = ["probe", time.perf_counter(), 0.0, hosts[a].id, hosts[b].id, float(rtt), at]
                    self.log.append(entry)  # before the call: a flush it sets off applies it
                    self.engine.enqueue(hosts[a].id, hosts[b].id, rtt, at)
                    entry[2] = time.perf_counter()
                for b in ev["peers"]:
                    self._peer(b["parent"], b["task"])
                    self.children[b["task"]][b["slot"]] = self._peer(b["child"], b["task"])
                    self._retire(b["task"], b["slot"], b["replaces"])
                self.log.append(["peers", t0, time.perf_counter(), e])
            else:
                for b in ev["repoint"]:
                    self.children[b["task"]][b["slot"]] = self._peer(b["child"], b["task"])
                    self._retire(b["task"], b["slot"], b["replaces"])
                for b in ev["cover"]:
                    self._peer(b["parent"], b["task"])
                t1 = time.perf_counter()
                self.service.LeaveHost(scheduler_pb2.LeaveHostRequest(host_id=hid), None)
                self.log.append(["leave", t1, time.perf_counter(), hid])
                self.log.append(["peers", t0, time.perf_counter(), e])
            self.done = e + 1

    def start(self, t0: float) -> None:
        """Carry the script out from ``t0`` (perf_counter), each event at
        its due time or as soon after as its turn comes."""
        events = self.desc["events"]

        def run():
            for e, ev in enumerate(events):
                wait = t0 + ev["due"] - time.perf_counter()
                if wait > 0 and self._stop.wait(wait):
                    return
                if self._stop.is_set():
                    return
                self.late_s.append(time.perf_counter() - (t0 + ev["due"]))
                try:
                    self.carry_out(e)
                except Exception as exc:  # an event that raises is a failed operation
                    self.errors.append(f"event {e} ({ev['kind']}): {exc!r}")
            self.errors.append("the script of events ran out before the rounds did: raise events_horizon_seconds")

        self._thread = threading.Thread(target=run, name="bench.fleet", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join()


class Beside(rud.Beside):
    """``decide_under_round.Beside`` that also keeps, for each decision,
    the host pairs the evaluator handed the scoring service and whether
    the served model took them (read from the call itself, on the
    decision's own thread)."""

    def __init__(self, *args, taken: threading.local, **kwargs):
        super().__init__(*args, **kwargs)
        self._taken = taken
        self.pairs: list = [None] * self.n
        self.served: list = [None] * self.n
        # worker -> (task, slot, decision) from before it reads the slot's child until it has its answer
        self.asking: dict = {}

    def _work(self) -> None:
        find = self.scheduling.find_candidate_parents
        me = threading.get_ident()
        while True:
            i = self._claim()
            if i >= self.n:
                return
            due = self.t0 + self.due[i]
            now = time.perf_counter()
            while now < due:
                self.slept[i] = True
                time.sleep(due - now)
                now = time.perf_counter()
            if self.cut is not None and i >= self.cut:
                return
            self._taken.last = None
            k, c = int(self.task_idx[i]), int(self.child_idx[i])
            self.asking[me] = (k, c, i)
            self.start[i] = now
            try:
                parents, found = find(self.children[k][c])
                self.returned[i] = [p.id for p in parents] if found else []
            except Exception as e:  # a decision that raises is a failed one
                self.errors.append(repr(e))
            self.end[i] = time.perf_counter()
            self.asking[me] = None
            if self._taken.last is not None:
                self.pairs[i], self.served[i] = self._taken.last

    def close(self, drain_s: float) -> None:
        super().close(drain_s)
        self.pairs, self.served = self.pairs[: self.n], self.served[: self.n]


def tap_score_wave(svc) -> threading.local:
    """Each thread's newest ``score_wave`` call: its pairs, and whether
    the served model ranked the (one) decision in it."""
    from dragonfly2_tpu.scheduler.serving import ServingUnsupported

    taken = threading.local()
    real = svc.score_wave

    def score_wave(features, pairs, counts, budget_s=None):
        try:
            out = real(features, pairs, counts, budget_s=budget_s)
        except ServingUnsupported:
            taken.last = (list(pairs), False)
            raise
        except Exception:
            taken.last = (list(pairs), None)  # a failure of the service: no reason a host gives
            raise
        taken.last = (list(pairs), out[0] is not None)
        return out

    svc.score_wave = score_wave
    return taken


# -- set-up -----------------------------------------------------------------


def check_config(trainer, scheduler, refresher, cfg: dict, traffic: dict) -> None:
    """``decide_gnn_under_round.check_config`` at the live fleet's size,
    and what only this deployment states. A program without the rung
    prepared ahead or the re-embed stops here."""
    from dragonfly2_tpu.schema.records import MAX_DEST_HOSTS
    from dragonfly2_tpu.scheduler.model_refresher import PH_GNN_REEMBED  # noqa: F401
    from dragonfly2_tpu.trainer.serving import BUCKET_LADDER, PREPARE_AHEAD_SHARE, node_capacity

    rounds.check_config(trainer.training, cfg)
    old.check_config(scheduler, cfg)
    s, g, scale = cfg["scheduler"], cfg["served_gnn"], cfg["scale"]
    for key, have, want in (
        ("algorithm", scheduler.cfg.algorithm, s["algorithm"]),
        ("serving_ladder", list(BUCKET_LADDER), s["serving_ladder"]),
        ("served_model", "gnn", cfg["served_model"]),
        ("switch_interval_ms", round(sys.getswitchinterval() * 1e3, 6), cfg["interpreter"]["switch_interval_ms"]),
        ("served_gnn.node_capacity", node_capacity(scale["live_hosts_at_setup"]), g["node_capacity"]),
        ("served_gnn.export_dests_per_source", MAX_DEST_HOSTS, g["export_dests_per_source"]),
        ("served_gnn.next_capacity_prepared_past_share", PREPARE_AHEAD_SHARE, g["next_capacity_prepared_past_share"]),
        ("refresher.interval_s", refresher.interval, cfg["refresher"]["interval_s"]),
        ("scale.hosts", traffic["fitted_hosts"], scale["hosts"]),
        ("scale.live_hosts_at_setup", traffic["live_hosts_at_setup"], scale["live_hosts_at_setup"]),
    ):
        if have != want:
            raise SystemExit(f"configuration drift: {key} is {have!r}, file says {want!r}")


def build(ctx):
    """``decide_gnn_under_round.build`` with the refresher polling at the
    configuration's interval."""
    from dragonfly2_tpu.scheduler.model_refresher import ModelRefresher

    trainer, scheduler, registry, _ = gur.build(ctx)
    refresher = ModelRefresher(
        registry, scheduler.evaluator, scheduler_cluster_id=scheduler.cfg.cluster_id,
        interval=float(ctx.cell.config["refresher"]["interval_s"]),
        serving=scheduler.scoring_service, networktopology=scheduler.networktopology,
    )
    return trainer, scheduler, registry, refresher


def setup(ctx):
    """``decide_gnn_under_round.setup`` over the live fleet; shared with
    the sweep. Returns what it returns, and the fleet."""
    from dragonfly2_tpu import colocated  # noqa: F401
    from dragonfly2_tpu.scheduler.model_refresher import _serving_rungs
    from dragonfly2_tpu.utils.idgen import host_id_v2

    cell, traffic = ctx.cell, ctx.cell.traffic
    ctx.marks["imports_and_chip"] = time.perf_counter()
    trainer, scheduler, registry, refresher = build(ctx)
    check_config(trainer, scheduler, refresher, cell.config, traffic)
    if trainer.training.mesh is not None or len(ctx.devices) != 1:
        raise SystemExit(f"fit mesh {trainer.training.mesh}: the colocated cell runs on one chip")
    stage = rounds.Stage(trainer.storage, host_id_v2(rounds.IP, rounds.HOSTNAME), traffic, ctx.seed)
    ctx.marks["staged"] = time.perf_counter()
    desc = describe(traffic, ctx.seed)
    gc.freeze()  # the harness's own description, out of the collector's reach (decide_under_round.setup)
    n_live = desc["live_at_setup"]
    at_setup = [task_at(desc, k, 0, set()) for k in range(len(desc["tasks"]))]
    children = old.build_live({**desc, "hosts": desc["hosts"][:n_live], "tasks": at_setup}, scheduler)
    fleet = Fleet(scheduler, desc, children)
    fleet.fill(ctx.seed)
    scheduler.scoring_service.start()
    ids = [h.id for h in desc["hosts"]]
    engine = scheduler.topology_engine
    for rows in _serving_rungs(scheduler.scoring_service):
        if rows <= 2 * cell.config["scheduler"]["filter_parent_limit"]:
            engine.rtt_affinity_pairs([ids[0]] * rows, ids[1 : rows + 1])
    # the fleet stands past the share of its capacity at which the next
    # is compiled: the engine's kernels for it, at every rung just met
    engine.flush()
    if not engine.wait_prepared(600.0):
        raise SystemExit("the engine's next capacity was still compiling after ten minutes")
    ctx.marks["swarm"] = time.perf_counter()
    return trainer, scheduler, registry, refresher, stage, desc, children, fleet


# -- the judge --------------------------------------------------------------


def task_at(desc: dict, k: int, events_done: int, left: set) -> dict:
    """Task ``k`` as the swarm stood after ``events_done`` events: the
    peers brought by then and not yet gone, those on departed hosts in
    state Leave."""
    t = desc["tasks"][k]
    peers = [
        ({**p, "state": "Leave"} if p["host"] in left else p)
        for p in t["peers"]
        if p["born"] < events_done and not p.get("gone", events_done) < events_done
    ]
    return {**t, "peers": peers}


def judge(desc: dict, cfg: dict, picks: list, returned_of, costs_of, precision: str = "float32") -> dict:
    """``open_loop_decisions.judge`` for decisions each judged on its own
    view of the swarm. ``picks`` is ``[(task view, child)]``;
    ``costs_of(rows, precision)`` gives the reference costs of the
    ``(task view, child, parent)`` rows; ``returned_of(n)`` the parents the
    timed path returned, or None to put the reference at ``precision`` in
    the program's place (the control)."""
    limit, filter_limit = cfg["scheduler"]["candidate_parent_limit"], cfg["scheduler"]["filter_parent_limit"]
    rows, spans, legal_sets = [], [], []
    for task, child in picks:
        legal = reference.legal_parents(task, child, desc["hosts"], desc["uploads_now"])
        legal_sets.append(legal)
        spans.append((len(rows), len(rows) + len(legal)))
        rows.extend((task, child, p) for p in legal)
    ref_costs = costs_of(rows, "float32")
    low_costs = ref_costs if precision == "float32" else costs_of(rows, precision)
    worst, wrong_count, illegal = 0.0, 0, 0
    for n, (task, child) in enumerate(picks):
        lo, hi = spans[n]
        ids = [p["id"] for p in legal_sets[n]]
        costs = dict(zip(ids, ref_costs[lo:hi].tolist()))
        closed = len(task["peers"]) <= filter_limit
        got = returned_of(n)
        if got is None:
            got = [ids[j] for j in np.argsort(low_costs[lo:hi], kind="stable")[:limit]]
        gap = reference.rank_gap(got, costs, closed)
        if math.isinf(gap):
            illegal += 1
        else:
            worst = max(worst, gap)
        want = min(limit, len(ids))
        if (closed and len(got) != want) or not (1 <= len(got) <= want):
            wrong_count += 1
    return {"rank_gap": worst, "wrong_count": wrong_count, "illegal": illegal, "rows": len(rows)}


def run(ctx) -> dict:
    from dragonfly2_tpu.trainer import train as train_mod
    from dragonfly2_tpu.trainer import training as training_mod

    cell, traffic = ctx.cell, ctx.cell.traffic
    rate = float(cell.params["rate_per_s"])
    trainer, scheduler, registry, refresher, stage, desc, children, fleet = setup(ctx)
    training, svc, engine = trainer.training, scheduler.scoring_service, scheduler.topology_engine
    hosts = desc["hosts"]
    index_of = {h.id: i for i, h in enumerate(hosts)}
    host_of_peer = {p["id"]: p["host"] for t in desc["tasks"] for p in t["peers"]}

    resident = rud.ResidentFits(training_mod)
    gnn, gru = [], []
    fed: dict = {}

    def one_round() -> dict:
        """``decide_gnn_under_round.run``'s round."""
        registry.round = []
        del gnn[:], gru[:]
        fed.clear()
        fits_before = len(resident.calls)
        before = taps.prom_series()
        t0 = time.perf_counter()
        outcome = training.train(rounds.IP, rounds.HOSTNAME)
        wall = time.perf_counter() - t0
        moved = taps.series_delta(before, taps.prom_series())
        mine = resident.calls[fits_before:]
        mlp_fit = mine[0][3] if mine else None
        gnn_fit = gnn[0][2] if gnn else None
        gru_fit = gru[0][2] if gru else None
        if gnn:
            fed["gnn"] = gnn[0][0][0]
        if gru:
            fed["gru"] = (*gru[0][0][:2], gru[0][1].get("lengths"))
        faults = [
            name
            for name, sound in (
                (f"outcome {outcome!r}", outcome.ok and outcome.gru_error is None),
                ("three versions registered", sorted(t for t, _, _ in registry.round) == ["gnn", "gru", "mlp"]),
                ("mlp fit resident", mlp_fit is not None),
                ("mlp loss finite and lower", mlp_fit is not None and rounds.decreased(mlp_fit.history)),
                ("gnn loss finite and lower", gnn_fit is not None and rounds.decreased(gnn_fit.history)),
                ("gru loss finite and lower", gru_fit is not None and rounds.decreased(gru_fit.history)),
            )
            if not sound
        ]
        return {
            "began": t0,
            "wall_s": wall,
            "ok": not faults,
            "outcome": "; ".join(faults),
            "fits": {
                m: sum(v for k, v in moved.items() if "trainer_fit_duration_seconds_sum" in k and f'"{m}"' in k)
                for m in ("mlp", "gnn", "gru")
            },
            "params": {t: p for t, p, _ in registry.round},
            "evaluations": {t: e for t, _, e in registry.round},
            "mlp_losses": [] if mlp_fit is None else list(mlp_fit.history),
            "mlp_pairs": mine[0][0] if mine else 0,
            "gnn_losses": [] if gnn_fit is None else list(gnn_fit.history),
            "gru_losses": [] if gru_fit is None else list(gru_fit.history),
            "gnn_nodes_edges": (fed["gnn"].num_nodes, len(fed["gnn"].edge_src)) if gnn else (0, 0),
            "gru_sequences": fed["gru"][0].shape[0] if gru else 0,
        }

    # every embed (an install's or a re-embed's): when its scorer was
    # built, the GraphSAGE version, the rows it holds and how it counted
    # them; and every poll: its interval, what it did, the versions after
    embeds: list = []
    polls: list = []
    real_build, real_refresh = refresher._build_gnn_scorer, refresher.refresh_once

    def build_gnn_scorer(params):
        t0 = time.perf_counter()
        scorer = real_build(params)
        if scorer is not None:
            embeds.append(
                {"began": t0, "ended": time.perf_counter(), "rows": dict(scorer.rows),
                 "node_rows": scorer.node_rows(), "capacity": scorer.capacity}
            )
        return scorer

    def refresh_once():
        before = (refresher.loaded_gnn_version, refresher.loaded_version, len(embeds))
        t0 = time.perf_counter()
        out = real_refresh()
        what = None
        if len(embeds) > before[2]:
            what = "install" if refresher.loaded_gnn_version != before[0] else "reembed"
            embeds[-1]["version"] = refresher.loaded_gnn_version[1] if refresher.loaded_gnn_version else 0
            embeds[-1]["ended"] = time.perf_counter()  # the swap lies between the scorer's build and here
        polls.append(
            {"began": t0, "ended": time.perf_counter(), "what": what, "embed": len(embeds) - 1,
             "mlp": refresher.loaded_version[1] if refresher.loaded_version else 0,
             "mlp_moved": refresher.loaded_version != before[1]}
        )
        return out

    refresher._build_gnn_scorer = build_gnn_scorer
    refresher.refresh_once = refresh_once
    taken = tap_score_wave(svc)

    def decisions(seed: int) -> Beside:
        due, task_idx, child_idx = swarm_mod.arrivals(traffic, seed, rate, traffic["horizon_seconds"])
        return Beside(scheduler.scheduling, children, due, task_idx, child_idx, traffic["workers"], taken=taken)

    def rungs() -> dict:
        series = taps.prom_series()
        return {r: series.get(rud.RUNG_SERIES % r, 0.0) for r in ("serving", "mlp", "base")}

    phase_names = sorted({m["reader"]["phase"] for m in cell.per_layer if m["reader"]["kind"] == "prof_phase"} | set(SWAP_PHASES))
    timed: list = []
    series0 = taps.prom_series()
    with (
        resident,
        taps.spy(training_mod, "train_gnn", gnn, with_args=True),
        taps.spy(train_mod, "train_gru", gru, with_args=True),
    ):
        # the warm-up: one round with the decisions already arriving, its
        # versions installed by the refresher's first poll (start(): the
        # embed and every rung's edge head compile here, and the rung
        # above, since the fleet stands past the share at which it is
        # due), then decisions ranked by its GraphSAGE version
        warm_win = decisions(ctx.seed + 1)
        warm_win.open()
        warm = one_round()
        if not warm["ok"]:
            raise SystemExit(f"the warm-up round failed: {warm['outcome']}")
        ctx.marks["warm_up_round"] = time.perf_counter()
        refresher.start()
        below0 = rungs()
        time.sleep(traffic["warmup_seconds"])
        warm_win.close(2.0)
        below = {r: v - below0[r] for r, v in rungs().items()}
        snap = svc.snapshot()
        want = f"{registry.gnn[-1][0]}/v{len(registry.gnn)}"
        if (
            warm_win.errors or below["mlp"] or below["base"] or not below["serving"] or not embeds
            or snap["model_version"] != want or snap["model_kind"] != "gnn"
            or refresher.loaded_version != (registry.mlp[-1][0], len(registry.mlp))
        ):
            raise SystemExit(
                f"the warm-up failed: {warm_win.errors[:3]} serving {snap['model_kind']} {snap['model_version']!r},"
                f" registry {want!r}, mlp under it {refresher.loaded_version}, by rung after the install {below}"
            )
        live_order = list(embeds[-1]["node_rows"])
        fitted_ids = list(registry.gnn[-1][1].get("node_ids") or ())
        if live_order == fitted_ids[: len(live_order)]:
            raise SystemExit("the live graph is in the upload's order: the cell cannot see a row served to the wrong host")
        prepared = taps.phase_counts(["scheduler.gnn_rung_prepare"])["scheduler.gnn_rung_prepare"]["count"]
        if not prepared:
            raise SystemExit("set-up's install did not prepare the rung above: the fleet is not past the share at which it is due")
        stage.restage()
        ctx.marks["warm_up_decisions"] = time.perf_counter()

        snap0 = svc.snapshot()
        ph0, prom0 = taps.phase_counts(phase_names), taps.prom_series()
        polls_before, embeds_before = len(polls), len(embeds)
        capacity0 = (int(prom0.get(NODE_CAPACITY_SERIES, 0)), embeds[-1]["capacity"])
        ctx.window_opens()
        tracer_thread = rud.record_from_phase(ctx.tracer, traffic["trace_from_phase"], traffic["trace_seconds"])
        win = decisions(ctx.seed)
        win.open(heartbeat=ctx.trace)
        fleet.asking = win
        fleet.start(win.t0)
        spent = 0.0
        while spent < ctx.seconds:
            r = one_round()
            timed.append(r)
            spent += r["wall_s"]
            stage.restage()
        win.close(traffic["drain_seconds"])
        fleet.stop()
        refresher.stop()
        if tracer_thread is not None:
            tracer_thread.join()
        ctx.window_closes()
    engine.flush()
    engine_hosts = set(engine.store.index)
    snap1 = svc.snapshot()
    phases = taps.phase_delta(ph0, taps.phase_counts(phase_names))
    prom1 = taps.prom_series()
    prom = taps.series_delta(prom0, prom1)
    whole_run = taps.series_delta(series0, prom1)
    capacity1 = (int(prom1.get(NODE_CAPACITY_SERIES, 0)), embeds[-1]["capacity"])

    good = [r for r in timed if r["ok"]]
    passes = training.config.mlp.epochs
    metrics = {
        "train_records_per_s": stage.records_per_round * passes * len(good) / sum(r["wall_s"] for r in good)
        if good else 0.0
    }
    timeout_s = svc.cfg.window_s + svc.cfg.service_grace_s
    answered = np.array([r is not None and len(r) > 0 for r in win.returned], bool) & (win.end > 0)
    done = answered & ((win.end - win.start) < timeout_s)
    by_rung = {r: prom.get(rud.RUNG_SERIES % r, 0.0) for r in ("serving", "mlp", "base")}
    unknown = prom.get(UNKNOWN_SERIES, 0.0)
    below = int(by_rung["mlp"] + by_rung["base"])
    fell = max(below - int(unknown), 0)  # a rung down for any other reason than a host outside the embed
    failed_decisions = max(int(win.n - done.sum()), min(fell, win.n))
    lat_us = (win.end - (win.t0 + win.due))[done] * 1e6
    took = (win.end - win.start) * answered
    service_us = took[answered] * 1e6
    slowest = [
        [round(float(win.start[i] - max(r["began"] for r in timed if r["began"] <= win.start[i])), 2), round(float(took[i]), 3)]
        for i in np.argsort(-took)[:5]
        if answered[i] and win.start[i] >= timed[0]["began"]
    ]
    wait_us = (win.start - (win.t0 + win.due)) * 1e6
    reembed_faults = whole_run.get(REEMBED_SERIES % "failed", 0.0) + whole_run.get(REEMBED_SERIES % "skipped", 0.0)
    installs_failed = whole_run.get(gur.INSTALL_SERIES % "failed", 0.0) + whole_run.get(gur.INSTALL_SERIES % "skipped", 0.0)
    probes = {
        "fit_duration": {m: [r["fits"][m] for r in good] for m in ("mlp", "gnn", "gru")},
        "serving_snapshot": {
            "batches": snap1["batches"] - snap0["batches"],
            "rows_scored": snap1["rows_scored"] - snap0["rows_scored"],
            "window_s": win.seconds,
        },
        "prof_phase": phases,
        "prom_series": {
            **prom, "window_s": win.seconds,
            "decisions_ranked": sum(by_rung.values()), "decisions_below_serving": float(below),
        },
        "harness_clock": {
            "decision_latency_us": lat_us.tolist(),
            "decision_service_us": service_us.tolist(),
            "process_pause_us": win.pauses_us,
            "queue_wait_us": wait_us[done].tolist(),
            "generator_lateness_us": wait_us[win.slept & done].tolist(),
        },
    }

    # -- what the comparison needs, taken before the program's state goes --
    log = fleet.log
    window_polls = polls[polls_before:]
    swaps = [(p["began"], p["ended"]) for p in polls if p["what"] is not None or p["mlp_moved"]]
    n = win.n
    starts, ends = win.start.copy(), win.end.copy()
    returned, pairs_of, served_of = list(win.returned), list(win.pairs), list(win.served)
    task_idx, child_idx = win.task_idx.copy(), win.child_idx.copy()
    rng = np.random.default_rng([ctx.seed, 14])
    idx = np.nonzero(done)[0]
    pick = set(rng.choice(idx, size=min(traffic["sample_decisions"], idx.size), replace=False).tolist())
    sizes = np.array([len(desc["tasks"][k]["peers"]) for k in win.task_idx])
    pick.update(idx[np.argsort(-sizes[idx], kind="stable")[:50]].tolist())
    pick = sorted(pick)
    gnn_weights = {v + 1: gur.host_weights(p) for v, (_, p) in enumerate(registry.gnn)}
    mlp_weights = {v + 1: rud.host_weights(p) for v, (_, p) in enumerate(registry.mlp)}
    errors, lost, n_decisions, fleet_errors = list(win.errors), win.lost, win.n, list(fleet.errors)
    events_done, late_s = fleet.done, list(fleet.late_s)
    last = timed[-1]
    records, topology = stage.records, stage.topology
    records_per_round, chunk_bytes = stage.records_per_round, stage.chunk_bytes
    config, limits, trainer_cfg = cell.config, cell.config["limits"], cell.config["trainer"]
    body_repeats = traffic["body_repeats_per_chunk"] * traffic["chunks"]
    feed_ok = ctx.devices[0].platform != "tpu" or all(
        d.platform == "tpu" for call in resident.calls for leaf in rounds._leaves(call[3].params) for d in leaf.devices()
    )
    sampled = list(resident.calls)
    rows_by = {row: whole_run.get(gur.ROWS_SERIES % row, 0.0) for row in ("placed", "default", "dropped")}
    svc.stop()
    del trainer, training, scheduler, refresher, registry, stage, children, win, warm_win, resident, fleet, engine

    def after_window() -> list:
        """Both comparisons with the plain reference, on the swarm and the
        probe graph as the replay of the log says they stood."""
        checks = [
            ("rounds_failed", float(len(timed) - len(good)), 0.0),
            ("gnn_installs_failed", float(installs_failed + reembed_faults), 0.0),
            ("feed_off_chip", 0.0 if feed_ok else 1.0, 0.0),
        ]
        sched = config["scheduler"]
        replay = membership.Replay(sched["topology_landmarks"], sched["topology_landmark_iters"], config["served_gnn"]["export_dests_per_source"])
        fitted_order = reference.probe_graph(topology, trainer_cfg["gnn"]["max_degree"])["order"]
        host_rec = lambda hid: hosts[index_of[hid]]  # noqa: E731

        # one pass over the log: the replay's state at each entry the
        # sampled decisions need, the exports, who had left by when. A
        # decision stands at the first entry that ended after it began
        t1s = np.array([e[2] for e in log])
        at_entry: dict = {}
        for i in pick:
            later = t1s > starts[i]
            at_entry.setdefault(int(np.argmax(later)) if later.any() else len(log), []).append(i)
        views: dict = {}  # decision -> (events done, hosts left, the replay's rtt affinity of its pairs, its entry)
        left: set = set()
        events_then = 0
        for pos in range(len(log) + 1):
            for i in at_entry.get(pos, ()):
                aff = None
                if served_of[i] is False and pairs_of[i]:
                    aff = {(a, b): replay.affinity(a, b) for a, b in pairs_of[i]}
                views[i] = (events_then, set(left), aff, pos)
            if pos == len(log):
                break
            entry = log[pos]
            if entry[0] == "peers":
                events_then = entry[3] + 1
                continue
            replay.apply(entry)
            if entry[0] == "leave":
                left.add(index_of[entry[3]])
        counts_gap = float(len(replay.faults))
        if len(replay.exports) != len(embeds):
            counts_gap = math.inf
        misplaced = 0.0
        node_sets = []
        for e, should in zip(embeds, replay.exports):
            nodes = membership.nodes_of(should)
            node_sets.append(set(nodes))
            want = membership.row_counts(should, fitted_order)
            counts_gap += sum(abs(e["rows"][r] - want[r]) for r in want)
            counts_gap += float(list(e["node_rows"]) != nodes)  # the same hosts, in the same order
            misplaced += gur.rows_misplaced(e["node_rows"], gnn_weights[e["version"]], fitted_order) if e.get("version") else math.inf
        missed, idle = membership.polls_held([(p["began"], p["ended"], p["what"]) for p in polls], log)
        checks += [
            ("gnn_row_counts_gap", counts_gap, 0.0),
            ("gnn_rows_misplaced", float(misplaced), 0.0),
            ("reembeds_missed", float(missed), 0.0),
            ("reembeds_idle", float(idle), 0.0),
            ("engine_hosts_gap", float(len(engine_hosts ^ replay.engine_hosts())), 0.0),
        ]

        # guarantee 1, over every decision of the window
        departed = 0
        for i in range(n):
            for pid in returned[i] or ():
                gone = replay.left_at.get(hosts[host_of_peer[pid]].id)
                departed += gone is not None and gone < starts[i]
        checks.append(("parents_on_departed_hosts", float(departed), 0.0))

        # every rung drop names a host outside an embed that may have
        # been in force while the decision ran
        def embeds_in_force(i: int) -> list:
            ended = [j for j, e in enumerate(embeds) if e["ended"] <= starts[i]]
            first = ended[-1] if ended else 0
            return [j for j in range(first, len(embeds)) if embeds[j]["began"] <= ends[i]]

        unexplained = 0
        for i in range(n):
            if served_of[i] is None and pairs_of[i] is None:
                continue
            if served_of[i] is False:
                named = {h for pair in pairs_of[i] for h in pair}
                unexplained += not any(named - node_sets[j] for j in embeds_in_force(i))
            elif served_of[i] is None:
                unexplained += 1
        unexplained = max(unexplained, fell)
        checks.append(("below_serving_unexplained", float(unexplained), 0.0))
        crossed = capacity1[0] > capacity0[0] and capacity1[1] > capacity0[1]
        checks.append(("capacity_rung_not_crossed", 0.0 if crossed else 1.0, 0.0))

        rows = rud.sampled_mismatch(sampled, records) if len(sampled) == 1 + len(timed) else math.inf
        clean = [i for i in pick if not any(b <= ends[i] and e >= starts[i] for b, e in swaps)]
        # ... and whose swarm no event moved while it ran
        spans = [(e[1], e[2], e[3]) for e in log if e[0] == "peers"]
        flush_at = [(p, e[1]) for p, e in enumerate(log) if e[0] == "flush"]

        def moved_under(i: int) -> bool:
            k = int(task_idx[i])
            for b, e, ev in spans:
                if b <= ends[i] and e >= starts[i]:
                    event = desc["events"][ev]
                    tasks = [p["task"] for p in event["peers"]] if event["kind"] == "join" else event["tasks"]
                    if k in tasks:
                        return True
            return False

        clean = [i for i in clean if not moved_under(i)]
        by_gnn = [i for i in clean if served_of[i] is True]
        # an MLP-ranked decision reads the engine's estimates: none that a flush may have moved under it
        by_mlp = [
            i for i in clean
            if served_of[i] is False and not any(p >= views[i][3] and began <= ends[i] for p, began in flush_at)
        ]
        x_rows = [(int(task_idx[i]), int(child_idx[i])) for i in by_gnn + by_mlp]
        x_decisions = rud.candidate_rows(desc, config, x_rows) if x_rows else np.zeros((0, 0), np.float32)
        checks.extend(rud.hold_mlp([warm] + timed, good, rows, records, body_repeats, trainer_cfg["mlp"], limits, x_decisions))
        checks.extend(gur.hold_gnn([warm] + timed, good, fed, topology, trainer_cfg["gnn"], limits))
        checks.extend(rounds._hold_gru(good, last, fed, records, body_repeats, trainer_cfg["gru"], limits))

        def picks_of(chosen: list) -> list:
            out = []
            for i in chosen:
                k, c = int(task_idx[i]), int(child_idx[i])
                task = {**task_at(desc, k, views[i][0], views[i][1]), "decision": i}
                # the child that held the arrival slot then: the newest brought to it by then
                out.append((task, [p for p in task["peers"] if p.get("slot") == c][-1]))
            return out

        total = {"wrong_count": 0, "illegal": 0}
        gnn_gap = 0.0 if by_gnn else math.inf
        degree = trainer_cfg["gnn"]["max_degree"]
        for j, e in enumerate(embeds):
            mine = [i for i in by_gnn if embeds_in_force(i) == [j]]
            if not mine:
                continue
            read = membership.records_of(replay.exports[j], host_rec)
            placed = gur.placed_weights(gnn_weights[e["version"]], fitted_order, read)

            def costs_of(rows, precision, read=read, placed=placed):
                ref = reference.GnnReference(read, index_of, placed, degree, precision)
                # a parent outside the embed is one the scheduler's sample of a task
                # over the filter limit left out: never returned, its cost unread
                inside = [n for n, (_, ch, p) in enumerate(rows) if ch["host"] in ref.node_of and p["host"] in ref.node_of]
                out = np.full(len(rows), 1e9, np.float32)
                if inside:
                    out[inside] = ref.costs([rows[n][1]["host"] for n in inside], [rows[n][2]["host"] for n in inside])
                return out

            one = judge(desc, config, picks_of(mine), lambda m, mine=mine: returned[mine[m]], costs_of)
            print(f"reference: embed {j} (GraphSAGE version {e['version']}): {len(mine)} decisions, {one['rows']} candidate pairs, rank_gap {one['rank_gap']!r}", flush=True)
            gnn_gap = max(gnn_gap, one["rank_gap"])
            total = {k: total[k] + one[k] for k in total}
        mlp_gap = 0.0
        mlp_in_force = lambda i: max((p["mlp"] for p in polls if p["ended"] <= starts[i]), default=0)  # noqa: E731
        for v in sorted({mlp_in_force(i) for i in by_mlp}):
            mine = [i for i in by_mlp if mlp_in_force(i) == v]

            def costs_of(rows, precision, v=v):
                x = []
                for task, ch, p in rows:
                    aff = views[task["decision"]][2]  # the decision's own rtt estimates, by the replay
                    x.append(
                        reference.pair_features(
                            hosts[p["host"]], hosts[ch["host"]], p["finished"], task["total_pieces"], task["content_length"],
                            p["state"] == "Succeeded", aff.get((hosts[ch["host"]].id, hosts[p["host"]].id), 0.0),
                            upload_count_now=desc["uploads_now"][p["host"]],
                        )
                    )
                return reference.mlp_forward(mlp_weights[v], np.stack(x), precision) if x else np.zeros(0, np.float32)

            one = judge(desc, config, picks_of(mine), lambda m, mine=mine: returned[mine[m]], costs_of)
            print(f"reference: MLP version {v}: {len(mine)} decisions a host outside the embed sent down, {one['rows']} candidate rows, rank_gap {one['rank_gap']!r}", flush=True)
            mlp_gap = max(mlp_gap, one["rank_gap"])
            total = {k: total[k] + one[k] for k in total}
        return checks + [
            ("decisions_errored", float(len(errors) + lost), 0.0),
            ("parents_outside_the_rules", float(total["illegal"]), 0.0),
            ("parent_count_wrong", float(total["wrong_count"]), 0.0),
            ("rank_gap", gnn_gap, limits["rank_gap"]),
            ("rank_gap_mlp", mlp_gap, limits["rank_gap_mlp"]),
        ]

    swap = {
        k.partition(".")[2]: [phases[k]["count"], round(phases[k]["total_s"] / max(phases[k]["count"], 1), 5)]
        for k in SWAP_PHASES if k in phases
    }
    n_served_false = sum(1 for s in served_of if s is False)
    return {
        "metrics": metrics,
        "probes": probes,
        "attempted": n_decisions + len(timed) + events_done,
        "failed": failed_decisions + len(timed) - len(good) + int(installs_failed + reembed_faults) + len(fleet_errors),
        "after_window": after_window,
        "notes": {
            "rounds": len(timed),
            "round_walls_s": [round(r["wall_s"], 4) for r in timed],
            "records_per_round": records_per_round,
            "chunk_mib": round(chunk_bytes / (1 << 20), 2),
            "rate_per_s": rate,
            "decisions": n_decisions,
            "decision_window_s": round(probes["serving_snapshot"]["window_s"], 2),
            "by_rung": by_rung,
            "unknown_host": unknown,
            "sent_down_by_the_tap": n_served_false,
            "fell_a_rung": fell,
            "answered_late": int(answered.sum() - done.sum()),
            "errors": (errors + fleet_errors)[:3],
            "events": {
                "done": events_done,
                "joins": sum(1 for ev in desc["events"][:events_done] if ev["kind"] == "join"),
                "leaves": sum(1 for ev in desc["events"][:events_done] if ev["kind"] == "leave"),
                "late_s_max": round(max(late_s), 3) if late_s else 0.0,
                "late_s_p50": round(percentile(late_s, 50), 4) if late_s else 0.0,
            },
            "polls": [[round(p["began"] - timed[0]["began"], 2), round(p["ended"] - p["began"], 3), p["what"]] for p in window_polls],
            "embeds_in_window": len(embeds) - embeds_before,
            "embed_rows": [e["rows"] for e in embeds],
            "capacity_nodes_engine_gnn": {"at_open": capacity0, "at_close": capacity1},
            "count_mean_s": swap,
            "gnn_rows": rows_by,
            "latency_us": {q: percentile(lat_us, q) for q in (50, 90, 95, 99)} if lat_us.size else {},
            "service_us": {"99": percentile(service_us, 99), "99.9": percentile(service_us, 99.9), "max": float(service_us.max())}
            if service_us.size else {},
            "slowest_at_round_s_took_s": slowest,
        },
    }
