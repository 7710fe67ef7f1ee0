"""Device kernels over the padded adjacency: staleness decay, k-hop
EWMA-RTT aggregation, landmark min-plus RTT inference.

Two implementations of one contract: a jitted jax path (runs in HBM on
an accelerator; XLA:CPU otherwise) and a numpy twin for deployments
with no usable jax at all. Tests assert elementwise agreement, so the
numpy path is the semantic spec (same pattern as schema/native.py).

All shapes are static: arrays arrive padded to capacity with a
``valid`` mask (csr.AdjacencyStore.build_arrays), loop trip counts
(``k`` hops, ``iters`` relaxations) are compile-time constants — the
static-bound-with-masking idiom TPU tiling requires.

Distance math is LINEAR milliseconds — min-plus composition
d(a,l)+d(l,b) adds RTTs, which log-space would silently turn into a
product. Aggregation math is log1p-ms like every other RTT feature in
schema/features.py.
"""

# dfanalyze: device-hot — these kernels run per topology flush and per
# inference query; wrapper churn or host syncs here tax every schedule

from __future__ import annotations

import numpy as np

# distances at or above this are "no path" (float32-safe headroom)
INF_MS = 1e12


def _freshness(age_s, valid, half_life_s: float, xp):
    """Staleness decay: weight = valid · 2^(−age/half-life). A quiet
    edge fades smoothly out of every aggregate instead of pinning its
    last EWMA forever; purge (csr.purge_stale) is the terminal stage."""
    return valid * xp.exp2(-age_s / half_life_s)


def _segment_sum_np(data, seg, n):
    out = np.zeros((n,) + data.shape[1:], dtype=data.dtype)
    np.add.at(out, seg, data)
    return out


def _segment_min_np(data, seg, n):
    out = np.full((n,) + data.shape[1:], np.float32(INF_MS), dtype=data.dtype)
    np.minimum.at(out, seg, data)
    return out


class NumpyKernels:
    """Reference implementation; also the no-accelerator fallback."""

    backend = "numpy"

    def decay_weights(self, age_s, valid, half_life_s: float):
        return _freshness(
            np.asarray(age_s, np.float32), np.asarray(valid, np.float32),
            half_life_s, np,
        )

    def khop_rtt(self, edge_src, edge_dst, rtt_log_ms, weights, num_nodes: int, k: int):
        """[node_cap] per-node k-hop EWMA-RTT aggregate (log-ms).

        Hop 0 is the freshness-weighted mean of a node's own out-edge
        RTTs; each further hop mixes in the neighbors' aggregate at 0.5
        (EWMA over hop distance), so a node with few probes inherits
        structure from its neighborhood. Nodes with no fresh edges → 0.
        """
        w_rtt = _segment_sum_np(weights * rtt_log_ms, edge_src, num_nodes)
        w_tot = _segment_sum_np(weights, edge_src, num_nodes)
        h0 = w_rtt / np.maximum(w_tot, 1e-9)
        has = (w_tot > 1e-9).astype(np.float32)
        h = h0 * has
        for _ in range(k):
            nbr = _segment_sum_np(weights * h[edge_dst], edge_src, num_nodes)
            nbr = nbr / np.maximum(w_tot, 1e-9)
            h = (0.5 * h0 + 0.5 * nbr) * has
        return h

    def landmark_distances(
        self, edge_src, edge_dst, rtt_ms, weights,
        landmark_idx, landmark_valid, num_nodes: int, iters: int,
    ):
        """[node_cap, L] min-plus distances to each landmark over the
        (symmetrized) fresh adjacency. ``iters`` relaxation rounds ≈
        hop radius of the inference; unreached pairs stay INF_MS."""
        L = len(landmark_idx)
        cost = np.where(weights > 0, rtt_ms, np.float32(INF_MS)).astype(np.float32)
        D = np.full((num_nodes, L), np.float32(INF_MS), dtype=np.float32)
        D[landmark_idx, np.arange(L)] = np.where(
            landmark_valid > 0, np.float32(0), np.float32(INF_MS)
        )
        for _ in range(iters):
            cand = cost[:, None] + D[edge_dst]
            relaxed = _segment_min_np(cand, edge_src, num_nodes)
            D = np.minimum(D, relaxed)
        return D

    def est_from_landmarks(self, D, src_idx, dst_idx):
        """est[i] = min_l D[src_i, l] + D[dst_i, l]  (linear ms)."""
        return np.min(D[src_idx] + D[dst_idx], axis=-1)

    def gather_rtt_affinity(
        self, D, src_idx, dst_idx, direct_ms, has_direct, known
    ):
        """[N] rtt_affinity gathered straight from the resident
        adjacency — the wave-join feature column in one dispatch:
        landmark min-plus estimate per (src, dst) index pair, direct
        probe EWMAs (``direct_ms``, linear ms, masked by
        ``has_direct``) winning over inference, log1p-ms/10 normalized
        with the schema's 0.0 missing-value for unknown hosts
        (``known`` ≤ 0) and no-path pairs. A self pair is encoded by
        the caller as a 0 ms direct edge (affinity 0.0)."""
        D = np.asarray(D, np.float32)
        est_ms = np.min(D[src_idx] + D[dst_idx], axis=-1)
        ms = np.where(has_direct > 0, direct_ms, est_ms)
        miss = (np.asarray(known) <= 0) | (
            (np.asarray(has_direct) <= 0) & (est_ms >= np.float32(INF_MS / 2))
        )
        aff = np.log1p(np.maximum(ms, np.float32(0.0))) / np.float32(10.0)
        return np.where(miss, np.float32(0.0), aff).astype(np.float32)


_jit_cache: dict = {}


def _jitted_kernels():
    """The four jitted kernels, built once per PROCESS (not per
    JaxKernels instance): every engine, bench, and test instance shares
    one compiled-executable cache per (capacity, trip-count) tuple —
    the per-instance form recompiled identical kernels on every engine
    construction. Lazy so the numpy backend never imports jax."""
    fns = _jit_cache.get("kernels")
    if fns is not None:
        return fns
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("half_life_s",))
    def decay(age_s, valid, half_life_s):
        return _freshness(age_s, valid, half_life_s, jnp)

    @functools.partial(jax.jit, static_argnames=("num_nodes", "k"))
    def khop(edge_src, edge_dst, rtt_log_ms, weights, num_nodes, k):
        seg = functools.partial(
            jax.ops.segment_sum, num_segments=num_nodes
        )
        w_rtt = seg(weights * rtt_log_ms, edge_src)
        w_tot = seg(weights, edge_src)
        h0 = w_rtt / jnp.maximum(w_tot, 1e-9)
        has = (w_tot > 1e-9).astype(jnp.float32)
        h0 = h0 * has

        def hop(h, _):
            nbr = seg(weights * h[edge_dst], edge_src) / jnp.maximum(w_tot, 1e-9)
            return (0.5 * h0 + 0.5 * nbr) * has, None

        h, _ = jax.lax.scan(hop, h0, None, length=k)
        return h

    @functools.partial(jax.jit, static_argnames=("num_nodes", "iters"))
    def landmarks(
        edge_src, edge_dst, rtt_ms, weights,
        landmark_idx, landmark_valid, num_nodes, iters,
    ):
        L = landmark_idx.shape[0]
        cost = jnp.where(weights > 0, rtt_ms, INF_MS).astype(jnp.float32)
        D = jnp.full((num_nodes, L), INF_MS, dtype=jnp.float32)
        D = D.at[landmark_idx, jnp.arange(L)].min(
            jnp.where(landmark_valid > 0, 0.0, INF_MS).astype(jnp.float32)
        )

        def relax(D, _):
            cand = cost[:, None] + D[edge_dst]
            relaxed = jax.ops.segment_min(cand, edge_src, num_segments=num_nodes)
            return jnp.minimum(D, relaxed), None

        D, _ = jax.lax.scan(relax, D, None, length=iters)
        return D

    @jax.jit
    def est(D, src_idx, dst_idx):
        return jnp.min(D[src_idx] + D[dst_idx], axis=-1)

    @jax.jit
    def gather_aff(D, src_idx, dst_idx, direct_ms, has_direct, known):
        est_ms = jnp.min(D[src_idx] + D[dst_idx], axis=-1)
        ms = jnp.where(has_direct > 0, direct_ms, est_ms)
        miss = (known <= 0) | ((has_direct <= 0) & (est_ms >= INF_MS / 2))
        aff = jnp.log1p(jnp.maximum(ms, 0.0)) / 10.0
        return jnp.where(miss, 0.0, aff).astype(jnp.float32)

    fns = _jit_cache["kernels"] = (decay, khop, landmarks, est, gather_aff)
    return fns


class JaxKernels:
    """jitted twins — compiled once per (capacity, trip-count) tuple,
    shared process-wide (``_jitted_kernels``)."""

    backend = "jax"

    def __init__(self):
        (
            self._decay,
            self._khop,
            self._landmarks,
            self._est,
            self._gather_aff,
        ) = _jitted_kernels()

    def decay_weights(self, age_s, valid, half_life_s: float):
        return self._decay(age_s, valid, half_life_s=float(half_life_s))

    def khop_rtt(self, edge_src, edge_dst, rtt_log_ms, weights, num_nodes: int, k: int):
        return self._khop(
            edge_src, edge_dst, rtt_log_ms, weights, num_nodes=num_nodes, k=k
        )

    def landmark_distances(
        self, edge_src, edge_dst, rtt_ms, weights,
        landmark_idx, landmark_valid, num_nodes: int, iters: int,
    ):
        return self._landmarks(
            edge_src, edge_dst, rtt_ms, weights,
            landmark_idx, landmark_valid, num_nodes=num_nodes, iters=iters,
        )

    def est_from_landmarks(self, D, src_idx, dst_idx):
        return self._est(D, src_idx, dst_idx)

    def gather_rtt_affinity(
        self, D, src_idx, dst_idx, direct_ms, has_direct, known
    ):
        import jax.numpy as jnp

        # explicit boundary conversion (no-op for resident arrays): the
        # engine hands device copies, but direct callers (tests, tools)
        # pass numpy — make the transfer visible, not implicit in jit
        return self._gather_aff(
            jnp.asarray(D),
            jnp.asarray(src_idx),
            jnp.asarray(dst_idx),
            jnp.asarray(direct_ms),
            jnp.asarray(has_direct),
            jnp.asarray(known),
        )


def make_kernels(backend: str = "auto"):
    """``jax`` | ``numpy`` | ``auto`` (jax if importable, else numpy).
    Under ``JAX_PLATFORMS=cpu`` the jax path compiles for XLA:CPU — the
    numpy twin is for environments where jax itself is not installed. A
    jax that imports but cannot build its kernels is an error to raise,
    not a reason to serve from the host silently."""
    if backend == "auto":
        try:
            import jax  # noqa: F401
        except ImportError:
            backend = "numpy"
    return NumpyKernels() if backend == "numpy" else JaxKernels()
