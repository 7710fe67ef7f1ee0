"""Host-side adjacency store + padded CSR build.

The store is the exact mutable truth (EWMA fold per probe, host purge);
the CSR build turns it into fixed-capacity arrays the jitted kernels
consume. Capacities only grow, by doubling — static shapes are what let
the kernels stay compiled (TPU tiling wants fixed array extents; a
per-flush shape change would recompile every flush). A departed host's
slot goes to the next host that joins, so under turnover the capacities
follow the live count and not the count of hosts ever seen.

Padding convention: unused edge slots carry ``src = dst = 0`` with
``weight = 0`` — in-bounds for gathers (the pallas/XLA static-bound
masking idiom), zeroed out of every reduction by the weight.
"""

from __future__ import annotations

import heapq

import numpy as np

from dragonfly2_tpu.scheduler.networktopology import EWMA_OLD_WEIGHT

NS_PER_MS = 1e6


def _next_capacity(needed: int, current: int) -> int:
    cap = max(current, 8)
    while cap < needed:
        cap *= 2
    return cap


class AdjacencyStore:
    """Interned directed edge store: (src_idx, dst_idx) → EWMA RTT +
    update time, with the same EWMA the KV path applies
    (networktopology.enqueue_probe), so both views of a probe sequence
    agree exactly."""

    def __init__(self):
        self.index: dict[str, int] = {}
        self.ids: list[str] = []  # slot -> host id; "" for a slot nobody holds
        # (src_idx, dst_idx) -> [avg_rtt_ns, updated_at_s]
        self.edges: dict[tuple[int, int], list[float]] = {}
        # slot -> the keys of the edges that touch it: a purge walks a
        # host's own edges, not the whole dict
        self._touching: dict[int, set[tuple[int, int]]] = {}
        # slots of departed hosts: ``_leaving`` until a build without
        # them has been installed (``release``: until then arrays on the
        # device still hold the departed host's row under that slot),
        # then ``_free``, lowest first, for the next host that joins
        self._leaving: list[int] = []
        self._free: list[int] = []
        # bumped whenever the host set or the edge set changes (a probe
        # that only moves an edge's average does not): what a reader
        # that embedded this graph compares to learn that it has moved
        self.version = 0

    # -- interning --------------------------------------------------------
    def intern(self, host_id: str) -> int:
        idx = self.index.get(host_id)
        if idx is None:
            if self._free:
                idx = heapq.heappop(self._free)
                self.ids[idx] = host_id
            else:
                idx = len(self.ids)
                self.ids.append(host_id)
            self.index[host_id] = idx
            self.version += 1
        return idx

    @property
    def num_hosts(self) -> int:
        """Slots in use or not yet reusable: the node arrays' extent."""
        return len(self.ids)

    @property
    def free_slots(self) -> int:
        return len(self._free) + len(self._leaving)

    @property
    def num_edges(self) -> int:
        return len(self.edges)

    # -- mutation ---------------------------------------------------------
    def apply_probe(self, src: str, dest: str, rtt_ns: float, at: float) -> None:
        s, d = self.intern(src), self.intern(dest)
        e = self.edges.get((s, d))
        if e is None:
            self._add_edge(s, d, [float(rtt_ns), at])
        elif e[0] <= 0:
            e[0], e[1] = float(rtt_ns), at
        else:
            e[0] = float(
                int(EWMA_OLD_WEIGHT * e[0] + (1 - EWMA_OLD_WEIGHT) * rtt_ns)
            )
            e[1] = max(e[1], at)

    def adopt_edge(
        self, src: str, dest: str, avg_rtt_ns: float, updated_at: float
    ) -> bool:
        """Install an already-averaged edge (KV hydration / cross-
        scheduler merge) — no EWMA fold, and never clobber a fresher
        locally-maintained value."""
        s, d = self.intern(src), self.intern(dest)
        e = self.edges.get((s, d))
        if e is not None and e[1] >= updated_at:
            return False
        if e is None:
            self._add_edge(s, d, [float(avg_rtt_ns), updated_at])
        else:
            e[0], e[1] = float(avg_rtt_ns), updated_at
        return True

    def _add_edge(self, s: int, d: int, value: list) -> None:
        self.edges[(s, d)] = value
        self._touching.setdefault(s, set()).add((s, d))
        self._touching.setdefault(d, set()).add((s, d))
        self.version += 1

    def _drop_edge(self, key: tuple[int, int]) -> None:
        del self.edges[key]
        for end in key:
            self._touching[end].discard(key)
        self.version += 1

    def purge_host(self, host_id: str) -> bool:
        """Remove a host's node and every incident edge, by the host's
        own edges alone. Its slot is tombstoned (``ids[slot] = ""``) and
        held back until :meth:`release`; edge keys of other hosts stay
        valid throughout."""
        idx = self.index.pop(host_id, None)
        if idx is None:
            return False
        self.ids[idx] = ""
        for key in list(self._touching.get(idx, ())):
            self._drop_edge(key)
        self._touching.pop(idx, None)
        self._leaving.append(idx)
        self.version += 1
        return True

    def leaving(self) -> list[int]:
        """Slots purged and not yet released, as of now: a build made
        now holds none of them."""
        return list(self._leaving)

    def release(self, slots: list[int]) -> None:
        """``slots`` (a former :meth:`leaving`) may be interned again:
        the arrays in force were built after their hosts were purged."""
        if not slots:
            return
        gone = set(slots)
        self._leaving = [i for i in self._leaving if i not in gone]
        for i in slots:
            heapq.heappush(self._free, i)

    def purge_stale(self, now: float, max_age_s: float) -> int:
        """Drop edges whose last update is older than ``max_age_s`` —
        the terminal stage of staleness decay: quiet edges first lose
        aggregation weight (kernels.decay_weights), then disappear."""
        stale = [k for k, v in self.edges.items() if now - v[1] > max_age_s]
        for k in stale:
            self._drop_edge(k)
        return len(stale)

    # -- CSR build --------------------------------------------------------
    def build_arrays(
        self, now: float, node_cap: int = 0, edge_cap: int = 0
    ) -> dict[str, np.ndarray]:
        """→ padded CSR + COO arrays (numpy; the engine ships them to the
        device).

        Keys: ``row_ptr`` [node_cap+1], ``edge_src``/``edge_dst``
        [edge_cap] (CSR order: sorted by src, so ``col_idx`` ==
        ``edge_dst``), ``rtt_log_ms`` [edge_cap], ``age_s`` [edge_cap],
        ``valid`` [edge_cap] float32 mask.
        """
        n = self.num_hosts
        node_cap = _next_capacity(max(n, 1), node_cap)
        edge_cap = _next_capacity(max(self.num_edges, 1), edge_cap)

        e = self.num_edges
        src = np.zeros(edge_cap, dtype=np.int32)
        dst = np.zeros(edge_cap, dtype=np.int32)
        rtt = np.zeros(edge_cap, dtype=np.float32)
        age = np.zeros(edge_cap, dtype=np.float32)
        valid = np.zeros(edge_cap, dtype=np.float32)
        if e:
            # the caller holds the engine's lock: two C-level walks of the
            # dict and a sort in numpy, no Python per edge
            keys = np.array(list(self.edges), dtype=np.int64)
            vals = np.array(list(self.edges.values()), dtype=np.float64)
            csr = np.lexsort((keys[:, 1], keys[:, 0]))  # CSR order: by src, then dst
            keys, vals = keys[csr], vals[csr]
            src[:e] = keys[:, 0]
            dst[:e] = keys[:, 1]
            rtt[:e] = np.log1p(np.maximum(vals[:, 0], 0.0) / NS_PER_MS)
            age[:e] = np.maximum(now - vals[:, 1], 0.0)
            valid[:e] = 1.0

        row_ptr = np.zeros(node_cap + 1, dtype=np.int32)
        if e:
            counts = np.bincount(src[:e], minlength=node_cap)
            row_ptr[1:] = np.cumsum(counts)
        return {
            "row_ptr": row_ptr,
            "edge_src": src,
            "edge_dst": dst,
            "rtt_log_ms": rtt,
            "age_s": age,
            "valid": valid,
            "num_nodes": n,
            "num_edges": e,
        }
