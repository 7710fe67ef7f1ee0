"""TopologyEngine: the device-resident probe graph and its query surface.

Lifecycle: ``NetworkTopology.enqueue_probe`` → ``enqueue`` (delta queue)
→ ``flush`` (drain, EWMA fold into the host store, staleness purge,
padded CSR build, device refresh, landmark re-selection + distance
solve) → queries (``est_rtt_ns``, ``neighbors``, ``rtt_affinity``,
``centrality``, ``stats``) served from the resident arrays, never the
KV store.

RTT inference (unprobed pairs): L landmark hosts (highest fresh degree)
keep min-plus distances to every host; est_rtt(a,b) = min over
landmarks of d(a,l)+d(l,b). Direct fresh edges win over inference.
Staleness: edges lose aggregation weight with a freshness half-life and
are purged outright past ``max_age_s`` — a departed or quiet edge fades
instead of pinning its last EWMA forever.
"""

# dfanalyze: hot — est_rtt_ns/rtt_affinity run per schedule decision
# dfanalyze: device-hot — queries dispatch the jitted kernels against
# the resident arrays; a whole-array host pull per query multiplies

from __future__ import annotations

import bisect
import threading
import time
import uuid
from dataclasses import dataclass

import numpy as np

from dragonfly2_tpu.schema import records as R
from dragonfly2_tpu.topology import metrics as TM
from dragonfly2_tpu.topology.csr import NS_PER_MS, AdjacencyStore
from dragonfly2_tpu.topology.delta import DeltaQueue, EdgeDelta
from dragonfly2_tpu.topology.kernels import INF_MS, make_kernels
from dragonfly2_tpu.trainer.serving import (
    bucket_rows,
    pad_batch,
    past_prepare_share,
)
from dragonfly2_tpu.utils import dflog, flight, profiling

logger = dflog.get("topology.engine")

# flight-recorder events: every flush (the device-array refresh — the
# moment a wrong RTT estimate was born), plus the non-direct inference
# outcomes (the estimates worth re-probing); direct/cache hits are too
# hot and too boring for a permanent record
EV_FLUSH = flight.event_type("topology.flush")
EV_INFERENCE = flight.event_type("topology.inference")

# dfprof phase: the wave join's round trip to the backend — the puts of
# the padded index vectors, the gather kernel, the blocking read
PH_RTT_GATHER = profiling.phase_type("topology.rtt_gather")
# one flush whole (drain, build, kernels, swap), and one host's purge
PH_FLUSH = profiling.phase_type("topology.flush")
PH_DELETE_HOST = profiling.phase_type("topology.delete_host")


@dataclass
class TopologyConfig:
    backend: str = "auto"  # jax | numpy | auto
    num_landmarks: int = 8
    landmark_iters: int = 3  # min-plus relaxation rounds ≈ hop radius
    khop: int = 2
    # deltas buffered before an automatic flush (callers can flush
    # explicitly any time; the snapshot/export paths always do)
    flush_threshold: int = 256
    # staleness decay: half-life for aggregation weight, hard purge age
    half_life_s: float = 30 * 60.0
    max_age_s: float = 4 * 3600.0
    max_pending: int = 100_000
    inference_cache_size: int = 8192


class TopologyEngine:
    def __init__(self, config: TopologyConfig | None = None):
        self.cfg = config or TopologyConfig()
        self.kernels = make_kernels(self.cfg.backend)
        self.store = AdjacencyStore()
        self.deltas = DeltaQueue(self.cfg.max_pending)
        self._lock = threading.RLock()
        # serializes flushes so the kernel work can run OUTSIDE _lock
        # (queries keep reading the previous arrays meanwhile) without
        # two flushes racing the swap
        self._flush_lock = threading.Lock()
        # host-side numpy CSR/COO build (the query surface reads these
        # directly); only the COPIES _to_backend hands the kernels live
        # on device — keep it that way, or neighbors() grows a per-query
        # D2H pull back
        self._arrays: dict | None = None
        self._weights = None  # freshness weights at last flush
        self._D = None  # [node_cap, L] landmark distances (ms)
        self._khop_rtt = None  # [node_cap] aggregate (log-ms)
        self._landmark_idx: np.ndarray | None = None
        self._flush_count = 0
        self._dropped_seen = 0
        self._last_flush_at = 0.0
        # bumped on every out-of-flush store mutation (adopt,
        # delete_host): a flush whose build predates the bump builds
        # again instead of installing pre-mutation arrays
        self._store_version = 0
        # the store's version as the newest export copied it
        self._exported_version: int | None = None
        # (node capacity, edge capacity) pairs compiled ahead, each with
        # the gather's row rungs compiled for it; the rungs the wave join
        # has met; the pairs still to compile, and the thread that does
        self._compiled_caps: dict[tuple[int, int], set[int]] = {}
        self._gather_rungs: set[int] = set()
        self._prepare_lock = threading.Lock()
        self._prepare_due: list[tuple[int, int]] = []
        self._prepare_thread: threading.Thread | None = None
        # (src, dest) → (rtt_ns | None, provenance)
        self._cache: dict[tuple[str, str], tuple[float | None, str]] = {}
        self._cache_hits = 0
        self._cache_misses = 0
        self._query_lat_ms: list[float] = []  # sorted ring for p50/p99

    # ------------------------------------------------------------------
    # ingestion
    # ------------------------------------------------------------------
    def enqueue(
        self, src: str, dest: str, rtt_ns: int, created_at: float | None = None
    ) -> None:
        self.deltas.put(
            EdgeDelta(src, dest, rtt_ns, created_at if created_at is not None else time.time())
        )
        TM.DELTA_QUEUE_GAUGE.set(len(self.deltas))
        if len(self.deltas) >= self.cfg.flush_threshold:
            self.flush()

    def adopt(
        self, src: str, dest: str, avg_rtt_ns: float, updated_at: float
    ) -> bool:
        """Adopt an already-EWMA'd edge from the durable KV graph —
        hydration after a restart, and the merge path for edges probed
        via OTHER schedulers sharing the KV store (this process never
        saw their raw probes). Newer local state wins; the next flush
        folds adopted edges into the device arrays."""
        with self._lock:
            adopted = self.store.adopt_edge(src, dest, avg_rtt_ns, updated_at)
            if adopted:
                self._store_version += 1
            return adopted

    def delete_host(self, host_id: str) -> None:
        """Purge parity with NetworkTopology.delete_host: edges, pending
        deltas and cached inferences touching the host all go, at once
        for every reader (they resolve a host through the store's index
        and an edge through its dict, and the host is in neither when
        this returns). The device arrays are left to the next flush,
        like a join's deltas: the departed host's row stays in them,
        unreachable, and its slot is not given out again until a build
        without it is in force."""
        with PH_DELETE_HOST, self._lock:
            self.deltas.discard_host(host_id)
            if self.store.purge_host(host_id):
                self._store_version += 1
            self._cache.clear()

    # ------------------------------------------------------------------
    # flush: deltas → host store → device arrays
    # ------------------------------------------------------------------
    def flush(self, now: float | None = None) -> int:
        """Apply queued deltas and refresh the device arrays. Returns the
        number of deltas applied. The rebuild always runs — edge AGE
        advances between flushes, so skipping it would freeze staleness
        decay on a quiet probe plane. The kernel work runs OUTSIDE the
        query lock (``_flush_lock`` serializes flushes): est_rtt callers
        keep reading the previous arrays until the swap."""
        now = time.time() if now is None else now
        with self._flush_lock, PH_FLUSH:
            t0 = time.perf_counter()
            batch = self.deltas.drain()
            with self._lock:
                for d in batch:
                    self.store.apply_probe(d.src, d.dest, d.rtt_ns, d.created_at)
                purged = self.store.purge_stale(now, self.cfg.max_age_s)
            # an adopt or a delete_host that lands mid-kernel makes the
            # build stale: build again, outside the lock as before. The
            # last try is installed whatever happened meanwhile (readers
            # resolve hosts and direct edges through the store, so arrays
            # a mutation behind are safe, and the next flush catches up)
            for last in (False, False, True):
                with self._lock:
                    arr = self._build_arrays(now)
                    built_version = self._store_version
                computed = self._run_kernels(arr)
                with self._lock:
                    if last or self._store_version == built_version:
                        self._swap(arr, computed)
                        self._flush_count += 1
                        self._last_flush_at = now
                        break
            self._prepare_ahead(arr)
            if purged:
                TM.STALE_PURGED_TOTAL.inc(purged)
            TM.FLUSH_TOTAL.inc()
            EV_FLUSH(
                applied=len(batch),
                purged=purged,
                hosts=len(self.store.index),
                edges=self.store.num_edges,
                wall_ms=round((time.perf_counter() - t0) * 1e3, 3),
            )
            TM.DELTA_QUEUE_GAUGE.set(len(self.deltas))
            dropped = self.deltas.dropped
            if dropped > self._dropped_seen:
                TM.DELTA_DROPPED_TOTAL.inc(dropped - self._dropped_seen)
                self._dropped_seen = dropped
            return len(batch)

    # ------------------------------------------------------------------
    # the next capacity, compiled before the fleet reaches it
    # ------------------------------------------------------------------
    def _prepare_ahead(self, arr: dict) -> None:
        """Once the live hosts or edges of a build are past the share
        of their capacity at which the next is due (``past_prepare_share``,
        the served GraphSAGE's rule), the decay, k-hop, landmark and
        gather kernels for the doubled capacity are compiled on a thread
        of their own, on blank arrays of that size: the flush that first
        needs them, and the rtt joins after it, find them compiled. A
        row rung the wave join meets later is added at the next flush."""
        if self.kernels.backend != "jax":
            return
        ncap, ecap = len(arr["row_ptr"]) - 1, len(arr["edge_src"])
        grow_n = past_prepare_share(len(self.store.index), ncap)
        grow_e = past_prepare_share(arr["num_edges"], ecap)
        with self._prepare_lock:
            self._prepare_due = [
                caps
                for caps, wanted in (
                    ((2 * ncap, ecap), grow_n),
                    ((ncap, 2 * ecap), grow_e),
                    ((2 * ncap, 2 * ecap), grow_n and grow_e),
                )
                if wanted and (caps not in self._compiled_caps or not self._gather_rungs <= self._compiled_caps[caps])
            ]
            if self._prepare_due and self._prepare_thread is None:
                self._prepare_thread = threading.Thread(
                    target=self._compile_due, name="topology.prepare", daemon=True
                )
                self._prepare_thread.start()

    def _compile_due(self) -> None:
        L = self.cfg.num_landmarks
        while True:
            with self._prepare_lock:
                if not self._prepare_due:
                    self._prepare_thread = None
                    return
                ncap, ecap = self._prepare_due.pop(0)
            rungs = set(self._gather_rungs)
            try:
                blank = {
                    "row_ptr": np.zeros(ncap + 1, np.int32),
                    "edge_src": np.zeros(ecap, np.int32),
                    "edge_dst": np.zeros(ecap, np.int32),
                    "rtt_log_ms": np.zeros(ecap, np.float32),
                    "age_s": np.zeros(ecap, np.float32),
                    "valid": np.zeros(ecap, np.float32),
                    "landmark_idx": np.zeros(L, np.int32),
                    "landmark_valid": np.zeros(L, np.float32),
                }
                D = self._run_kernels(blank)["D"]
                self.kernels.est_from_landmarks(D, *self._to_backend_idx(0, 0))
                for rows in sorted(rungs):
                    self._gather(D, *(np.zeros(rows, t) for t in (np.int32, np.int32, np.float32, np.float32, np.float32)))
            except Exception:
                logger.warning("compiling the kernels for capacity %s ahead failed", (ncap, ecap), exc_info=True)
            finally:
                self._compiled_caps[(ncap, ecap)] = rungs  # compiled, or failed: not again at every flush

    def wait_prepared(self, timeout: float | None = None) -> bool:
        """Wait for the capacities being compiled ahead, if any; False
        if they are still compiling after ``timeout``."""
        t = self._prepare_thread
        if t is not None:
            t.join(timeout)
            return not t.is_alive()
        return True

    def graph_version(self) -> int:
        """A version of the host and edge sets: moves when a host joins
        or leaves or an edge appears or goes, and not when a probe only
        moves an edge's average. Pending deltas are applied first, so
        two equal readings mean the same graph."""
        if len(self.deltas):
            self.flush()
        with self._lock:
            return self.store.version

    def exported_version(self) -> int | None:
        """:meth:`graph_version` as the newest export read it."""
        return self._exported_version

    def _build_arrays(self, now: float) -> dict:
        """Padded CSR + landmark selection from the host store (caller
        holds ``_lock``)."""
        prev_ncap = len(self._arrays["row_ptr"]) - 1 if self._arrays else 0
        prev_ecap = len(self._arrays["edge_src"]) if self._arrays else 0
        arr = self.store.build_arrays(now, prev_ncap, prev_ecap)
        arr["leaving"] = self.store.leaving()
        ncap = len(arr["row_ptr"]) - 1

        # landmarks: highest fresh-degree hosts (deterministic: degree
        # desc, index asc), computed host-side — tiny, control-flow-y
        e = arr["num_edges"]
        deg = np.bincount(arr["edge_src"][:e], minlength=ncap) + np.bincount(
            arr["edge_dst"][:e], minlength=ncap
        )
        live = np.zeros(ncap, dtype=bool)
        ids = self.store.ids  # tombstoned hosts keep their slot, not their rank
        live[: len(ids)] = np.fromiter(map(bool, ids), bool, len(ids))
        deg = np.where(live, deg, -1)
        L = self.cfg.num_landmarks
        order = np.argsort(-deg, kind="stable")[:L]
        lm_idx = np.zeros(L, dtype=np.int32)
        lm_valid = np.zeros(L, dtype=np.float32)
        n_lm = 0
        for idx in order:
            if deg[idx] >= 0 and live[idx]:
                lm_idx[n_lm] = idx
                lm_valid[n_lm] = 1.0
                n_lm += 1
        arr["landmark_idx"] = lm_idx
        arr["landmark_valid"] = lm_valid
        arr["num_landmarks"] = n_lm
        return arr

    def _run_kernels(self, arr: dict) -> dict:
        """Decay → k-hop aggregate → landmark distances over built
        arrays — pure array math, no engine state, safe outside
        ``_lock``."""
        ncap = len(arr["row_ptr"]) - 1
        xp = self.kernels
        dev = self._to_backend(arr)
        w = xp.decay_weights(dev["age_s"], dev["valid"], self.cfg.half_life_s)
        khop = xp.khop_rtt(
            dev["edge_src"], dev["edge_dst"], dev["rtt_log_ms"], w,
            num_nodes=ncap, k=self.cfg.khop,
        )

        # symmetrized edge list for distance inference: probes are
        # directed but RTT is (to first order) symmetric, and min-plus
        # needs to traverse an edge both ways
        sym_src = np.concatenate([arr["edge_src"], arr["edge_dst"]])
        sym_dst = np.concatenate([arr["edge_dst"], arr["edge_src"]])
        rtt_ms = np.expm1(arr["rtt_log_ms"]).astype(np.float32)
        sym_rtt = np.concatenate([rtt_ms, rtt_ms])
        sym_w = np.concatenate([arr["valid"], arr["valid"]])
        sd = self._to_backend(
            {"src": sym_src, "dst": sym_dst, "rtt": sym_rtt, "w": sym_w}
        )
        lm = self._to_backend(
            {"li": arr["landmark_idx"], "lv": arr["landmark_valid"]}
        )
        D = xp.landmark_distances(
            sd["src"], sd["dst"], sd["rtt"], sd["w"],
            lm["li"], lm["lv"],
            num_nodes=ncap, iters=self.cfg.landmark_iters,
        )
        return {"weights": w, "khop": khop, "D": D}

    def _swap(self, arr: dict, computed: dict) -> None:
        """Install a finished build (caller holds ``_lock``)."""
        self._arrays = arr
        self._weights = computed["weights"]
        # khop lands host-side HERE, once per flush: its only consumer
        # (khop_rtt_log_ms) reads single elements per query, and pulling
        # the whole device array back per query was a D2H round trip on
        # the schedule-decision path
        self._khop_rtt = np.asarray(computed["khop"])
        self._D = computed["D"]
        self._landmark_idx = arr["landmark_idx"][: arr["num_landmarks"]].copy()
        self._cache.clear()
        # the slots purged before this build are in no array any more
        self.store.release(arr["leaving"])
        TM.EDGE_GAUGE.set(self.store.num_edges)
        TM.HOST_GAUGE.labels("live").set(len(self.store.index))
        TM.HOST_GAUGE.labels("free_slots").set(self.store.free_slots)
        TM.CAPACITY_GAUGE.labels("nodes").set(len(arr["row_ptr"]) - 1)
        TM.CAPACITY_GAUGE.labels("edges").set(len(arr["edge_src"]))

    def _to_backend(self, arrays: dict) -> dict:
        """numpy → device arrays on the jax backend (HBM when an
        accelerator is attached); identity on the numpy backend."""
        if self.kernels.backend != "jax":
            return arrays
        import jax.numpy as jnp

        return {
            k: (jnp.asarray(v) if isinstance(v, np.ndarray) else v)
            for k, v in arrays.items()
        }

    # ------------------------------------------------------------------
    # queries
    # ------------------------------------------------------------------
    def est_rtt_ns(self, src: str, dest: str) -> int | None:
        """Best RTT estimate: direct fresh edge (EWMA) → landmark
        inference → None (host unknown or no path). Symmetric on input
        order for inferred pairs by construction."""
        return self.est_rtt_detail(src, dest)[0]

    def est_rtt_detail(self, src: str, dest: str) -> tuple[int | None, str]:
        """(rtt_ns, provenance) where provenance ∈ {"self", "direct",
        "inferred", "none"} — resolved under one lock so the answer and
        its provenance can't disagree (a flush or delete between two
        lookups)."""
        if src == dest:
            return 0, "self"
        t0 = time.perf_counter()
        with self._lock:
            key = (src, dest)
            if key in self._cache:
                self._cache_hits += 1
                TM.QUERY_TOTAL.labels("cache").inc()
                self._note_latency(t0)
                out, source = self._cache[key]
                return self._intify(out), source
            self._cache_misses += 1
            out, source = self._est_rtt_locked(src, dest)
            if source != "direct":
                # the inferred/no-path answers are the ones an operator
                # wants on record (an inferred estimate says "probe this
                # pair to confirm"); direct hits would flood the ring
                EV_INFERENCE(
                    src=src,
                    dest=dest,
                    provenance=source,
                    rtt_ns=self._intify(out),
                )
            if len(self._cache) >= self.cfg.inference_cache_size:
                self._cache.clear()
            self._cache[key] = (out, source)
            self._note_latency(t0)
            return self._intify(out), source

    def _est_rtt_locked(self, src: str, dest: str) -> tuple[float | None, str]:
        s = self.store.index.get(src)
        d = self.store.index.get(dest)
        if s is None or d is None:
            TM.QUERY_TOTAL.labels("unknown").inc()
            return None, "none"
        edge = self.store.edges.get((s, d)) or self.store.edges.get((d, s))
        if edge is not None:
            TM.QUERY_TOTAL.labels("direct").inc()
            return float(edge[0]), "direct"
        if self._D is None or max(s, d) >= self._D.shape[0]:
            # no landmark row yet (a host interned past the arrays in
            # force; the gather would clamp to another host's row)
            return None, "none"
        est_ms = float(
            np.asarray(
                self.kernels.est_from_landmarks(
                    self._D, *self._to_backend_idx(s, d)
                )
            )[0]
        )
        if est_ms >= INF_MS / 2:
            TM.QUERY_TOTAL.labels("no_path").inc()
            return None, "none"
        TM.QUERY_TOTAL.labels("inferred").inc()
        return est_ms * NS_PER_MS, "inferred"

    def _to_backend_idx(self, s: int, d: int):
        a = np.array([s], dtype=np.int32)
        b = np.array([d], dtype=np.int32)
        out = self._to_backend({"a": a, "b": b})
        return out["a"], out["b"]

    @staticmethod
    def _intify(v: float | None) -> int | None:
        return None if v is None else int(v)

    def neighbors(self, host_id: str, limit: int = 32) -> list[dict]:
        """Fresh out-edges of ``host_id`` from the CSR rows, nearest
        first: [{host_id, avg_rtt_ns, age_s}]."""
        if self._arrays is None:
            # outside _lock: flush takes _flush_lock → _lock, so calling
            # it under _lock would invert the order (ABBA deadlock with
            # a concurrent flusher)
            self.flush()
        with self._lock:
            idx = self.store.index.get(host_id)
            if idx is None:
                return []
            # the built arrays are host numpy by construction (_swap
            # installs the build dict; only the kernel inputs go to the
            # backend) — no conversion on the query path
            arr = self._arrays
            row_ptr = arr["row_ptr"]
            lo, hi = int(row_ptr[idx]), int(row_ptr[idx + 1])
            dst = arr["edge_dst"][lo:hi]
            out = []
            for d in dst:
                e = self.store.edges.get((idx, int(d)))
                if e is None:
                    continue
                out.append(
                    {
                        "host_id": self.store.ids[int(d)],
                        "avg_rtt_ns": int(e[0]),
                        "age_s": max(time.time() - e[1], 0.0),
                    }
                )
            out.sort(key=lambda r: r["avg_rtt_ns"])
            return out[:limit]

    def rtt_affinity(self, src: str, dest: str) -> float:
        """The MLP feature: log1p(est RTT in ms)/10 — same normalization
        family as the tcp-connection features — 0.0 when unknown (the
        missing-value the schema documents, so live and trained
        distributions agree on the missing case)."""
        rtt = self.est_rtt_ns(src, dest)
        if rtt is None:
            return 0.0
        return float(np.log1p(rtt / NS_PER_MS) / 10.0)

    def rtt_affinity_pairs(self, src_ids, dst_ids) -> np.ndarray:
        """[N] src (child) host ids × [N] dst (parent) host ids → [N]
        rtt_affinity in ONE lock hold and ONE rung-padded gather
        dispatch — the wave-join form of :meth:`rtt_affinity`.
        Per-pair resolution order matches the scalar path (self →
        direct fresh edge → landmark inference → 0.0 missing-value);
        what it skips is the per-pair machinery (inference cache,
        EV_INFERENCE ring, per-query metrics) — a W×C wave would flood
        all three, and the scalar path remains the provenance story.
        The pair arrays ride the serving BUCKET_LADDER so steady-state
        waves never retrace the gather kernel."""
        n = len(src_ids)
        out = np.zeros(n, dtype=np.float32)
        if n == 0:
            return out
        need_src = np.zeros(n, dtype=np.int32)
        need_dst = np.zeros(n, dtype=np.int32)
        known = np.zeros(n, dtype=bool)
        direct_ms = np.zeros(n, dtype=np.float32)
        has_direct = np.zeros(n, dtype=bool)
        with self._lock:
            index = self.store.index
            edges = self.store.edges
            D = self._D  # immutable snapshot: _swap installs new arrays
            rows_in_force = 0 if D is None else D.shape[0]
            for i in range(n):
                src, dst = src_ids[i], dst_ids[i]
                if src == dst:
                    # self pair: a 0 ms direct edge ⇒ affinity 0.0
                    known[i] = has_direct[i] = True
                    continue
                s = index.get(src)
                d = index.get(dst)
                if s is None or d is None:
                    continue
                known[i] = True
                edge = edges.get((s, d)) or edges.get((d, s))
                if edge is not None:
                    has_direct[i] = True
                    direct_ms[i] = edge[0] / NS_PER_MS
                elif max(s, d) >= rows_in_force:
                    # interned past the arrays in force: no landmark
                    # row to infer from until the next flush
                    known[i] = False
                else:
                    need_src[i] = s
                    need_dst[i] = d
        if D is None or not bool(np.any(known & ~has_direct)):
            # nothing to infer: direct-only affinity, no kernel dispatch
            m = known & has_direct
            out[m] = np.log1p(direct_ms[m]) / np.float32(10.0)
            return out
        rows = bucket_rows(n)
        self._gather_rungs.add(rows)  # what a capacity compiled ahead is compiled for
        with PH_RTT_GATHER:
            padded = self._gather(
                D,
                pad_batch(need_src, rows),
                pad_batch(need_dst, rows),
                pad_batch(direct_ms, rows),
                pad_batch(has_direct.astype(np.float32), rows),
                pad_batch(known.astype(np.float32), rows),
            )
            # whole-rung D2H then host slice (allowlisted host-pull): a
            # device [:n] would retrace a dynamic_slice per distinct n
            aff = np.asarray(padded)[:n]
        return aff.astype(np.float32, copy=False)

    def _gather(self, D, src, dst, direct_ms, has_direct, known):
        """The puts of one rung-padded wave and the gather kernel."""
        dev = self._to_backend(
            {"src": src, "dst": dst, "direct_ms": direct_ms, "has_direct": has_direct, "known": known}
        )
        return self.kernels.gather_rtt_affinity(
            D, dev["src"], dev["dst"], dev["direct_ms"], dev["has_direct"], dev["known"]
        )

    def rtt_affinity_batch(
        self, child_ids: np.ndarray, parent_ids: np.ndarray
    ) -> np.ndarray:
        """[N] child host ids × [N, P] parent host ids → [N, P]
        rtt_affinity — the block-encode-time join (scheduler Storage)
        that puts the same feature distribution into the training data
        the live evaluator feeds the model. One flattened
        :meth:`rtt_affinity_pairs` gather for the whole block — the
        per-distinct-pair scalar loop paid one engine lock round-trip
        per pair; empty ids resolve to the 0.0 missing-value either
        way."""
        child_ids = np.asarray(child_ids)
        parent_ids = np.asarray(parent_ids)
        if parent_ids.size == 0:
            return np.zeros(parent_ids.shape, dtype=np.float32)
        n, p = parent_ids.shape
        src = [str(c) for c in np.repeat(child_ids, p)]
        dst = [str(q) for q in parent_ids.reshape(-1)]
        return self.rtt_affinity_pairs(src, dst).reshape(n, p)

    def centrality(self, candidates: list[str] | None = None) -> list[dict]:
        """Mean inferred RTT from every live host to each candidate,
        ascending (the seed-placement ranking): [{host_id,
        mean_rtt_ms}]. Pairs with no path are excluded from the mean;
        candidates unreachable from everywhere are dropped.

        Snapshots the store under ``_lock``, then does the O(C·H)
        array math UNLOCKED — a background seed-recommendation job must
        not stall the evaluator's est_rtt hot path. ``flush`` runs
        before taking ``_lock`` (flush takes _flush_lock → _lock; a
        flush call under _lock would invert that order and deadlock
        against a concurrent flusher)."""
        if self._arrays is None:
            self.flush()
        with self._lock:
            if self._D is None:
                return []
            D = np.asarray(self._D)
            live = list(self.store.index.items())
            index = dict(self.store.index)
            edges = [(s, d, v[0]) for (s, d), v in self.store.edges.items()]
        if not live:
            return []
        pool = (
            [(h, index[h]) for h in candidates if h in index]
            if candidates is not None
            else live
        )
        idxs = np.array([i for _, i in live], dtype=np.int32)
        pos = {int(i): p for p, i in enumerate(idxs)}
        # direct fresh edges beat inference, as in est_rtt_ns: index
        # them per node once (O(E)) instead of probing every pair
        touch: dict[int, list[tuple[int, float]]] = {}
        for s, d, rtt_ns in edges:
            touch.setdefault(s, []).append((d, rtt_ns))
            touch.setdefault(d, []).append((s, rtt_ns))
        out = []
        for hid, i in pool:
            est = np.min(D[idxs] + D[i][None, :], axis=-1)  # [H] landmark est
            for j, rtt_ns in touch.get(i, ()):
                p = pos.get(int(j))
                if p is not None:
                    est[p] = min(est[p], rtt_ns / NS_PER_MS)
            est[pos[int(i)]] = INF_MS  # self is not a fleet member to average
            finite = est[est < INF_MS / 2]
            if len(finite) == 0:
                continue
            out.append({"host_id": hid, "mean_rtt_ms": round(float(finite.mean()), 4)})
        out.sort(key=lambda r: r["mean_rtt_ms"])
        return out

    def khop_rtt_log_ms(self, host_id: str) -> float | None:
        """The k-hop EWMA-RTT aggregate for one host (log-ms)."""
        with self._lock:
            idx = self.store.index.get(host_id)
            if idx is None or self._khop_rtt is None:
                return None
            return float(self._khop_rtt[idx])  # host copy since _swap

    def stats(self) -> dict:
        with self._lock:
            total = self._cache_hits + self._cache_misses
            hit_rate = self._cache_hits / total if total else 0.0
            TM.INFERENCE_CACHE_HIT_RATE.set(hit_rate)
            return {
                "backend": self.kernels.backend,
                "hosts": len(self.store.index),
                "edges": self.store.num_edges,
                "pending_deltas": len(self.deltas),
                "dropped_deltas": self.deltas.dropped,
                "flushes": self._flush_count,
                "landmarks": int(len(self._landmark_idx))
                if self._landmark_idx is not None
                else 0,
                "cache_hit_rate": round(hit_rate, 4),
                "query_p50_ms": self.query_p50_ms(),
                "last_flush_at": self._last_flush_at,
            }

    # ------------------------------------------------------------------
    # export: the snapshot path reads the adjacency, not the KV store
    # ------------------------------------------------------------------
    def export_records(self, host_manager, dest_limit: int) -> list:
        """NetworkTopologyRecord rows straight from the resident
        adjacency — the trainer-bound GNN snapshot without a KV walk.
        Freshest ``dest_limit`` dests per source (parity with
        NetworkTopology.export_records' recency preference)."""
        # flush BEFORE taking _lock (flush's order is _flush_lock →
        # _lock; the reverse would ABBA-deadlock with a concurrent
        # flusher, e.g. the 30s GC flush task)
        self.flush()
        # the hold is the copy alone (two C-level walks, no Python per
        # edge): the walk below runs outside it, so a decision's rtt join
        # (rtt_affinity_pairs takes this lock) never waits for an export.
        # An edge's [avg, updated] list is shared with the store; a probe
        # folded in meanwhile shows as the newer measurement, which is
        # what the next export would carry anyway
        with self._lock:
            edges = list(self.store.edges.items())
            ids = list(self.store.ids)
            self._exported_version = self.store.version
        by_src: dict[int, list[tuple[int, list[float]]]] = {}
        for (s, d), v in edges:
            by_src.setdefault(s, []).append((d, [v[0], v[1]]))

        out = []
        now_ns = int(time.time() * 1e9)
        for s, dests in by_src.items():
            sh = host_manager.load(ids[s])
            if sh is None:
                continue
            dests.sort(key=lambda t: -t[1][1])  # most recently updated first
            dest_hosts = []
            for d, v in dests[:dest_limit]:
                dh = host_manager.load(ids[d])
                if dh is None:
                    continue
                dest_hosts.append(
                    R.DestHost(
                        id=dh.id,
                        type=dh.type.value,
                        hostname=dh.hostname,
                        ip=dh.ip,
                        port=dh.port,
                        network=dh.network,
                        probes=R.ProbesRecord(
                            average_rtt=int(v[0]),
                            created_at=int(v[1] * 1e9),
                            updated_at=int(v[1] * 1e9),
                        ),
                    )
                )
            if not dest_hosts:
                continue
            out.append(
                R.NetworkTopologyRecord(
                    id=str(uuid.uuid4()),
                    host=R.SrcHost(
                        id=sh.id,
                        type=sh.type.value,
                        hostname=sh.hostname,
                        ip=sh.ip,
                        port=sh.port,
                        network=sh.network,
                    ),
                    dest_hosts=dest_hosts,
                    created_at=now_ns,
                )
            )
        return out

    # ------------------------------------------------------------------
    def _note_latency(self, t0: float) -> None:
        ms = (time.perf_counter() - t0) * 1e3
        bisect.insort(self._query_lat_ms, ms)
        if len(self._query_lat_ms) > 4096:
            # drop extremes pairwise so the ring stays a sample, not a
            # monotone accumulation
            self._query_lat_ms = self._query_lat_ms[1:-1]

    def query_p50_ms(self) -> float:
        with self._lock:
            if not self._query_lat_ms:
                return 0.0
            return round(self._query_lat_ms[len(self._query_lat_ms) // 2], 6)
