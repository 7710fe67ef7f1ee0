"""The membership replay: a run's log of host events, replayed plainly.

A generator that lets hosts join and leave writes down, in the order of
their effects, everything it did to the scheduler's fleet and everything
the program did to its probe graph's device arrays, each entry a plain
list (so a log can be written by hand, or kept as JSON):

    ["announce", t0, t1, host]                      AnnounceHost returned
    ["adopt", t0, t1, src, dst, rtt_ns, at]         an averaged edge handed to the engine (set-up's fill)
    ["probe", t0, t1, src, dst, rtt_ns, at]         a probe result queued for the engine's next flush
    ["flush", t0, t1, applied]                      a flush, and how many queued probes it applied
    ["leave", t0, t1, host]                         LeaveHost returned
    ["export", t0, t1]                              an export of the probe graph (an install's or a re-embed's)
    ["peers", t0, t1, event]                        the generator's own: the swarm's peers an event brought stand

Replayed here in Python and NumPy, the log gives the live set at any
entry, the graph any export should have read (a source's five freshest
targets among live hosts), for each embed how many learned rows should
have been placed, defaulted and dropped, and the engine's RTT estimate
between two hosts as of the newest flush. Nothing here imports the
program: what it restates of the program is its documented behaviour
(docs/topology-engine.md): a probe reaches the graph at the next flush; a
leave takes a host and its edges out at once; a departed host's slot goes
to the next host that joins after a flush has passed, lowest first;
landmarks are the live hosts of highest degree, ties to the lower slot;
an unprobed pair is estimated through the landmarks after ``iters``
min-plus relaxations.
"""

from __future__ import annotations

import heapq
import types

import numpy as np

INF_MS = 1.0e12
NS_PER_MS = 1e6
EWMA_OLD = 0.1  # a probe of an edge already known: 0.1 of the old average, 0.9 of the sample


class Replay:
    """The fleet and its probe graph after the entries applied so far."""

    def __init__(self, landmarks: int = 8, iters: int = 3, dests_per_source: int = 5):
        self.landmarks, self.iters, self.dests = landmarks, iters, dests_per_source
        self.live: dict[str, bool] = {}  # hosts announced and not left, in order
        self.left_at: dict[str, float] = {}  # host -> when its LeaveHost returned
        self.joined_at: dict[str, float] = {}
        # the engine's side: hosts it knows by slot, edges by host ids in
        # the order they first appeared, probes waiting for a flush
        self.slot: dict[str, int] = {}
        self.slots: list[str] = []
        self.free: list[int] = []
        self.leaving: list[int] = []
        self.edges: dict[tuple[str, str], list[float]] = {}
        self.pending: list[tuple[str, str, float, float]] = []
        self.D: "np.ndarray | None" = None
        self.flushes = 0
        self.exports: list[list] = []  # one export() per "export" entry
        self.faults: list[str] = []

    # -- the log -------------------------------------------------------
    def apply(self, entry: list) -> None:
        kind, t0, t1, *rest = entry
        getattr(self, f"_{kind}")(t0, t1, *rest)

    def _announce(self, t0, t1, host):
        self.live[host] = True
        self.joined_at.setdefault(host, t1)

    def _intern(self, host: str) -> int:
        s = self.slot.get(host)
        if s is None:
            if self.free:
                s = heapq.heappop(self.free)
                self.slots[s] = host
            else:
                s = len(self.slots)
                self.slots.append(host)
            self.slot[host] = s
        return s

    def _adopt(self, t0, t1, src, dst, rtt_ns, at):
        self._intern(src), self._intern(dst)
        have = self.edges.get((src, dst))
        if have is None or have[1] < at:
            self.edges[(src, dst)] = [float(rtt_ns), float(at)]

    def _probe(self, t0, t1, src, dst, rtt_ns, at):
        self.pending.append((src, dst, float(rtt_ns), float(at)))

    def _flush(self, t0, t1, applied):
        if applied != len(self.pending):
            self.faults.append(f"a flush applied {applied} probes where {len(self.pending)} were waiting")
        for src, dst, rtt_ns, at in self.pending:
            self._intern(src), self._intern(dst)
            have = self.edges.get((src, dst))
            if have is None or have[0] <= 0:
                self.edges[(src, dst)] = [rtt_ns, at]
            else:
                have[0] = float(int(EWMA_OLD * have[0] + (1 - EWMA_OLD) * rtt_ns))
                have[1] = max(have[1], at)
        self.pending = []
        self.D = self._landmark_distances()
        for s in self.leaving:
            heapq.heappush(self.free, s)
        self.leaving = []
        self.flushes += 1

    def _leave(self, t0, t1, host):
        self.live.pop(host, None)
        self.left_at[host] = t1
        self.pending = [p for p in self.pending if host not in p[:2]]
        s = self.slot.pop(host, None)
        if s is not None:
            self.slots[s] = ""
            for key in [k for k in self.edges if host in k]:
                del self.edges[key]
            self.leaving.append(s)

    def _export(self, t0, t1):
        self.exports.append(self.export())

    def _peers(self, t0, t1, event):
        """The swarm's side of an event: nothing of the fleet's or the graph's."""

    # -- the engine's estimate, as of the newest flush ---------------------
    def _landmark_distances(self) -> np.ndarray:
        n = len(self.slots)
        src = np.array([self.slot[a] for a, _ in self.edges], np.int64)
        dst = np.array([self.slot[b] for _, b in self.edges], np.int64)
        rtt_ns = np.array([v[0] for v in self.edges.values()], np.float64)
        # the engine keeps an edge as log1p(ms) in float32 and relaxes over expm1 of that
        ms = np.expm1(np.log1p(np.maximum(rtt_ns, 0.0) / NS_PER_MS).astype(np.float32)).astype(np.float32)
        deg = np.bincount(src, minlength=n) + np.bincount(dst, minlength=n)
        held = np.array([bool(h) for h in self.slots], bool)
        ranked = [int(i) for i in np.argsort(-np.where(held, deg, -1), kind="stable")[: self.landmarks] if held[i]]
        D = np.full((n, self.landmarks), np.float32(INF_MS), np.float32)
        D[ranked, np.arange(len(ranked))] = 0.0
        a, b = np.concatenate([src, dst]), np.concatenate([dst, src])
        cost = np.concatenate([ms, ms])
        for _ in range(self.iters):
            relaxed = np.full_like(D, np.float32(INF_MS))
            np.minimum.at(relaxed, a, cost[:, None] + D[b])
            D = np.minimum(D, relaxed)
        return D

    def affinity(self, a: str, b: str) -> float:
        """``rtt_affinity`` of a pair as the engine answers it now: a
        direct probe in either direction as it stands in the graph; else
        the landmark estimate of the newest flush; 0.0 for a host the
        engine does not know (one whose probes still wait for a flush is
        such a host), for a pair with no path and for a host with itself."""
        if a == b:
            return 0.0
        sa, sb = self.slot.get(a), self.slot.get(b)
        if sa is None or sb is None:
            return 0.0
        direct = self.edges.get((a, b)) or self.edges.get((b, a))
        if direct is not None:
            return float(np.log1p(np.float32(direct[0] / NS_PER_MS)) / np.float32(10.0))
        if self.D is None or max(sa, sb) >= self.D.shape[0]:
            return 0.0
        est = np.min(self.D[sa] + self.D[sb])
        if est >= INF_MS / 2:
            return 0.0
        return float(np.log1p(np.float32(est)) / np.float32(10.0))

    def engine_hosts(self) -> set:
        return set(self.slot)

    def node_capacity_needed(self) -> int:
        return len(self.slots)

    # -- what an export should read ------------------------------------
    def export(self) -> list:
        """``[(source, [(target, rtt_ns, updated_at), ...]), ...]``:
        sources in the order of their first edge, each with its freshest
        ``dests_per_source`` targets (ties to the older edge), hosts that
        are not live left out."""
        by_src: dict[str, list] = {}
        for (a, b), (rtt_ns, at) in self.edges.items():
            by_src.setdefault(a, []).append((b, rtt_ns, at))
        out = []
        for a, dests in by_src.items():
            if a not in self.live:
                continue
            dests = sorted(dests, key=lambda d: -d[2])[: self.dests]
            dests = [d for d in dests if d[0] in self.live]
            if dests:
                out.append((a, dests))
        return out


def nodes_of(export: list) -> list:
    """The hosts of an exported graph in node order: a source, then its
    targets, by first appearance."""
    order: dict[str, int] = {}
    for a, dests in export:
        order.setdefault(a, len(order))
        for b, _, _ in dests:
            order.setdefault(b, len(order))
    return list(order)


def row_counts(export: list, fitted_ids) -> dict:
    """How many learned rows an embed on ``export`` places by id, how
    many hosts get the default row, how many fitted rows are dropped."""
    nodes, fitted = set(nodes_of(export)), set(fitted_ids)
    return {"placed": len(nodes & fitted), "default": len(nodes - fitted), "dropped": len(fitted - nodes)}


def records_of(export: list, host_of) -> list:
    """An export as the records ``reference.probe_graph`` reads;
    ``host_of(id)`` gives a host's description (``type``, ``network``)."""
    def end(hid, **more):
        h = host_of(hid)
        return types.SimpleNamespace(id=hid, type=h.type, network=h.network, **more)

    return [
        types.SimpleNamespace(
            host=end(a),
            dest_hosts=[end(b, probes=types.SimpleNamespace(average_rtt=int(rtt_ns))) for b, rtt_ns, _ in dests],
        )
        for a, dests in export
    ]


def polls_held(polls: list, log: list) -> "tuple[int, int]":
    """Guarantee 4 over a run's polls. ``polls`` is ``[(began, ended,
    what)]`` in order, ``what`` ``"install"`` (a new version: embedded
    whatever the graph did), ``"reembed"`` or None; the log's ``export``
    entries are those embeds' reads. Returns (polls that should have
    embedded and did not, polls that re-embedded a graph that had not
    moved). A host event or a flush that
    applied probes moves the graph; one whose interval touches the
    previous read's or the poll's start may fall on either side and
    decides nothing."""
    moves = [(e[1], e[2]) for e in log if e[0] in ("leave", "adopt") or (e[0] == "flush" and e[3] > 0)]
    pending = [(e[1], e[2]) for e in log if e[0] == "probe"]
    reads = [(e[1], e[2]) for e in log if e[0] == "export"]
    missed = idle = 0
    for began, ended, what in polls:
        before = [r for r in reads if r[1] <= began]
        if not before:
            continue
        r0, r1 = before[-1]
        # surely after the last read and surely before this poll: a move, or a probe
        # (the poll's own flush applies it before it compares)
        sure = any(t0 >= r1 and t1 <= began for t0, t1 in moves + pending)
        maybe = any(t1 > r0 and t0 < ended for t0, t1 in moves + pending)
        missed += sure and what is None
        idle += what == "reembed" and not maybe
    return missed, idle
