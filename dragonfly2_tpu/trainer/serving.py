"""Model serving: the consumption side of the train→serve loop.

The reference declared (but never wired) Triton serving for trained models
(reference manager/types/model.go:36-37 `tensorrt_plan` configs, the
undialed inference client pkg/rpc/inference/client/client_v1.go). Here the
equivalent is in-process XLA serving: the scheduler's ml evaluator loads
the params pytree the trainer uploaded and scores candidate parents with a
jitted forward — no sidecar, no extra hop, same XLA compiler on CPU or
chip.

Serialization: flat ``{dotted/path: ndarray}`` npz — same trick as the
columnar codec, readable anywhere numpy exists.
"""

# dfanalyze: device-hot — scorers dispatch jitted forwards per schedule
# decision; a per-instance jit wrapper recompiles on every model refresh

from __future__ import annotations

import contextlib
import io
from typing import Any

import numpy as np

# one compiled wrapper per forward function, shared across scorer
# instances: model_refresher installs a fresh scorer per refresh, and a
# per-instance jax.jit would recompile the same forward on every hot swap
from dragonfly2_tpu.utils.jitcache import jit_once as _jit_once

# -- shape-bucket ladder ------------------------------------------------------
# Every serving forward pads its batch dimension UP to a rung of this
# ladder, so the jitted executable compiles once per rung instead of once
# per candidate-set size (the per-batch retrace class ROADMAP item 1's
# jit-witness allowlist entries tracked). Above the top rung, sizes round
# up to the next multiple of the top — huge batches stay bounded at
# one extra compile per 64-row step, never one per size.
BUCKET_LADDER = (8, 16, 32, 64)


def bucket_rows(n: int) -> int:
    """Smallest ladder rung ≥ ``n`` (multiples of the top rung above it)."""
    for b in BUCKET_LADDER:
        if n <= b:
            return b
    top = BUCKET_LADDER[-1]
    return ((n + top - 1) // top) * top


def pad_batch(a: np.ndarray, rows: int) -> np.ndarray:
    """Zero-pad axis 0 up to ``rows`` (no copy when already there)."""
    n = a.shape[0]
    if n == rows:
        return a
    out = np.zeros((rows,) + a.shape[1:], a.dtype)
    out[:n] = a
    return out


# A scorer's dispatch has three stages: the put of the padded batch,
# the forward until its result is ready on the device, the read back to
# the host. A caller that accounts them apart (the scoring service's
# ``scheduler.score_*`` phases) passes three context managers as
# ``stages``; every other caller runs the same code under these.
NO_STAGES = (contextlib.nullcontext(),) * 3


def _served_mlp(params, x):
    """``score_parents`` under a name of its own: the trainer's holdout
    evaluation jits the same function, and a device trace names an op by
    the jitted function it belongs to, so this is what tells a served
    forward from a fit's op there."""
    from dragonfly2_tpu.models.mlp import score_parents

    return score_parents(params, x)


def _served_gnn_edge(params, emb, src, dst):
    from dragonfly2_tpu.models.gnn import predict_edge

    return predict_edge(params, emb, src, dst)


def _served_gnn_embed(params, node_features, neighbors, neighbor_mask):
    """``apply_graphsage`` under a name of its own, as ``_served_mlp``:
    the GraphSAGE fit runs the same forward every step, and this is what
    tells a swap's embed from a fit's op in a device trace."""
    from dragonfly2_tpu.models.gnn import apply_graphsage

    return apply_graphsage(params, node_features, neighbors, neighbor_mask)


def node_capacity(n: int) -> int:
    """The node count a served probe graph is padded to: the power of two
    at or above ``n``, from 64. The embed's and the edge head's traced
    shapes follow this and not the live host count, so a host that joins
    changes neither until the fleet outgrows its rung."""
    return max(64, 1 << max(n - 1, 0).bit_length())


# The share of a capacity rung past which the next rung is compiled
# ahead. Three quarters: a fleet that doubles takes a quarter of its
# size to get from there to the rung's end, which is minutes to days of
# joins against the seconds a compile takes; and right after a crossing
# a fleet stands at half of its new rung, so nothing is compiled for a
# rung it may never reach (a lower share would compile two rungs ahead
# of a fleet that has just crossed one).
PREPARE_AHEAD_SHARE = 0.75


def past_prepare_share(n: int, capacity: int) -> bool:
    """Whether ``n`` live entries of ``capacity`` are far enough up the
    rung for the next one to be compiled now (the served GraphSAGE's
    node tables and the topology engine's arrays share the rule)."""
    return n > PREPARE_AHEAD_SHARE * capacity


def place_node_rows(params: Any, node_ids: list) -> "tuple[dict, dict]":
    """A GraphSAGE version's learned rows on another graph than the one
    it was fitted on: ``params`` without its ``node_ids`` and with
    ``node_embed`` one row a node of ``node_ids``, in that order, each
    row the one fitted for that host's id. A host the version never saw
    gets the zero row (``init_graphsage``: the embedding localizes a
    known host; an unknown one is placed by its features alone); a host
    that has left is dropped. Returns the tree and how many rows were
    ``placed``, ``default`` and ``dropped``.

    A tree that names no hosts (seeded weights made for this very graph,
    a version registered before versions carried ids) is taken to be in
    the graph's own order, and refused when the counts differ."""
    params = dict(params)
    fitted = params.pop("node_ids", None)
    embed = params.get("node_embed")
    n = len(node_ids)
    if embed is None:
        return params, {"placed": 0, "default": 0, "dropped": 0}
    embed = np.asarray(embed, np.float32)
    if fitted is None:
        if embed.shape[0] != n:
            raise ValueError(
                f"node_embed has {embed.shape[0]} rows and names no hosts; the graph has {n} nodes"
            )
        return params, {"placed": n, "default": 0, "dropped": 0}
    if len(fitted) != embed.shape[0]:
        raise ValueError(f"node_embed has {embed.shape[0]} rows for {len(fitted)} node_ids")
    row_of = {hid: i for i, hid in enumerate(fitted)}
    take = np.fromiter((row_of.get(hid, -1) for hid in node_ids), np.int64, n)
    seen = take >= 0
    placed = np.zeros((n, embed.shape[1]), np.float32)
    placed[seen] = embed[take[seen]]
    params["node_embed"] = placed
    known = int(seen.sum())
    return params, {"placed": known, "default": n - known, "dropped": len(row_of) - known}


def _device_params(params: Any) -> Any:
    """Pin a parameter pytree on device ONCE, at scorer construction.
    The deserialized pytree is numpy, and feeding numpy leaves into a
    jitted forward re-uploads the whole model on EVERY predict — the
    implicit-transfer class the jit witness flags. Resident params ride
    HBM across predicts; only the features move per call."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(jnp.asarray, params)


def serialize_params(params: Any) -> bytes:
    """Parameter pytree (dicts/lists of arrays) → npz bytes."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    arrays = {}
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        arrays[key] = np.asarray(leaf)
    if isinstance(params, dict) and params.get("node_ids") is not None:
        # models.gnn.NodeIds: part of the tree's structure, no leaf of it
        arrays["node_ids"] = np.asarray(list(params["node_ids"]), dtype=np.str_)
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    return buf.getvalue()


def _with_node_ids(tree: Any, z) -> Any:
    """``tree`` with the host ids ``serialize_params`` wrote beside its arrays."""
    if isinstance(tree, dict) and "node_ids" in z.files:
        from dragonfly2_tpu.models.gnn import NodeIds

        tree["node_ids"] = NodeIds(z["node_ids"].tolist())
    return tree


def deserialize_params(blob: bytes, like: Any) -> Any:
    """npz bytes → pytree with the structure of ``like``."""
    import jax

    with np.load(io.BytesIO(blob)) as z:
        flat_like, treedef = jax.tree_util.tree_flatten_with_path(like)
        leaves = []
        for path, leaf in flat_like:
            key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
            leaves.append(z[key])
        return _with_node_ids(jax.tree_util.tree_unflatten(treedef, leaves), z)


def deserialize_params_auto(blob: bytes) -> Any:
    """npz bytes → pytree, structure reconstructed from the flat keys
    alone (all-integer dict levels become lists). The serving side needs
    this because a downloaded model's layer count/dims aren't known until
    the weights arrive."""

    def listify(node):
        if not isinstance(node, dict):
            return node
        if node and all(k.isdigit() for k in node):
            return [listify(node[k]) for k in sorted(node, key=int)]
        return {k: listify(v) for k, v in node.items()}

    with np.load(io.BytesIO(blob)) as z:
        tree: dict = {}
        for key in z.files:
            if key == "node_ids":
                continue
            node = tree
            parts = key.split("/")
            for part in parts[:-1]:
                node = node.setdefault(part, {})
            node[parts[-1]] = z[key]
        return _with_node_ids(listify(tree), z)


def _score_ranked(params, packed):
    """Fused forward + segment-grouped rank: scores AND the lexsort
    permutation (segment primary, score ascending, row index as the
    stable tie-break — scheduler.wave.rank_order's contract) leave the
    device in one dispatch. ``packed`` is [rows, F+1]: the feature
    matrix with the segment-id vector as a trailing float column, so
    the whole wave rides ONE host→device upload (the jit-witness
    one-feature-upload-per-wave contract)."""
    import jax.numpy as jnp

    x = packed[:, :-1]
    seg = packed[:, -1]
    s = _served_mlp(params, x)
    return s, jnp.lexsort((jnp.arange(s.shape[0]), s, seg))


class MLPScorer:
    """Jitted parent scorer around trained MLP params — the object the
    scheduler's MLEvaluator calls ``predict`` on."""

    def __init__(self, params: Any):
        self._params = _device_params(params)
        self._fn = _jit_once(_served_mlp)
        self._ranked = _jit_once(_score_ranked)

    @property
    def feature_dim(self) -> int:
        """Input width the model was trained for — MLEvaluator.set_model
        refuses a scorer whose dim doesn't match the live schema."""
        return int(self._params["layers"][0]["w"].shape[0])

    def predict(self, features: np.ndarray, stages=NO_STAGES) -> np.ndarray:
        import jax
        import jax.numpy as jnp

        # bucketed dispatch: the forward sees ladder shapes only, so a
        # steady-state serve path compiles once per rung regardless of
        # the candidate count (retired the score_parents retrace entry)
        n = features.shape[0]
        padded = pad_batch(np.asarray(features, np.float32), bucket_rows(n))
        h2d, forward, d2h = stages
        with h2d:
            x = jnp.asarray(padded)
        with forward:
            s = jax.block_until_ready(self._fn(self._params, x))
        with d2h:
            return np.asarray(s)[:n]

    def predict_ranked(
        self, features: np.ndarray, seg_ids: np.ndarray, stages=NO_STAGES
    ) -> "tuple[np.ndarray, np.ndarray]":
        """Wave scoring: [n, F] flattened candidate rows whose
        non-decreasing ``seg_ids`` mark decision boundaries → (scores
        [n], segment-grouped rank permutation [n]) from ONE fused
        dispatch — the wave unpack never host-sorts C floats per child.
        Bucketed like ``predict``: pad rows ride a sentinel segment
        that sorts strictly last and is sliced off, so the fused
        executable compiles once per ladder rung. The segment vector is
        packed as a trailing float column on the padded matrix — one
        upload per wave, not two (float32 holds segment ids exactly up
        to 2^24; a wave is bounded far below that)."""
        import jax
        import jax.numpy as jnp

        n = features.shape[0]
        rows = bucket_rows(n)
        sentinel = int(seg_ids[-1]) + 1 if n else 0
        packed = np.full(
            (rows, features.shape[1] + 1), 0.0, np.float32
        )
        packed[:n, :-1] = np.asarray(features, np.float32)
        packed[:, -1] = sentinel
        packed[:n, -1] = np.asarray(seg_ids, np.float32)
        h2d, forward, d2h = stages
        with h2d:
            x = jnp.asarray(packed)
        with forward:
            s, order = jax.block_until_ready(self._ranked(self._params, x))
        # whole-rung D2H then host slice: a device-side [:n] would
        # compile one dynamic_slice per distinct n — the retrace class
        # the ladder exists to kill (allowlisted host-pull, like predict)
        with d2h:
            return np.asarray(s)[:n], np.asarray(order)[:n]


def _np_gelu(x: np.ndarray) -> np.ndarray:
    """The tanh-approximate gelu jax.nn.gelu defaults to, in numpy."""
    return 0.5 * x * (1.0 + np.tanh(0.7978845608028654 * (x + 0.044715 * x**3)))


class NumpyMLPScorer:
    """Pure-numpy MLP parent scorer with the IDENTICAL batched API as
    :class:`MLPScorer` (bucket-padded ``predict``), so deployments (and
    tier-1) without a usable XLA backend exercise the exact same
    submit/pack/score/return machinery the device path runs — only the
    forward itself differs. Row-wise deterministic: scores for a given
    feature row don't depend on which batch the row rode in."""

    def __init__(self, params: Any):
        self._layers = [
            (np.asarray(l["w"], np.float32), np.asarray(l["b"], np.float32))
            for l in params["layers"]
        ]

    @property
    def feature_dim(self) -> int:
        return int(self._layers[0][0].shape[0])

    def predict(self, features: np.ndarray, stages=NO_STAGES) -> np.ndarray:
        # ``stages``: a host forward has no put and no read to account
        n = features.shape[0]
        # same bucket discipline as the jitted twin: the pad is free
        # correctness-wise (rows are independent) and keeps the two
        # implementations behaviorally interchangeable under the service
        h = pad_batch(np.asarray(features, np.float32), bucket_rows(n))
        last = len(self._layers) - 1
        for i, (w, b) in enumerate(self._layers):
            h = h @ w + b
            if i != last:
                h = _np_gelu(h)
        return np.ascontiguousarray(h[:n, 0])

    def predict_ranked(
        self, features: np.ndarray, seg_ids: np.ndarray, stages=NO_STAGES
    ) -> "tuple[np.ndarray, np.ndarray]":
        """Numpy twin of :meth:`MLPScorer.predict_ranked`: same
        (scores, segment-grouped permutation) contract, same lexsort
        keys, so the service's wave unpack is backend-independent."""
        scores = self.predict(features)
        order = np.lexsort(
            (np.arange(scores.shape[0]), scores, np.asarray(seg_ids))
        )
        return scores, order


def _np_sigmoid(x: np.ndarray) -> np.ndarray:
    return 1.0 / (1.0 + np.exp(-x))


def np_predict_next_cost(params: Any, x: np.ndarray, lengths=None) -> np.ndarray:
    """Pure-numpy twin of ``models.gru.predict_next_cost`` — the same
    masked GRU recurrence and gelu head on ``[B, T, F]`` histories, so
    GRU-backed serving (bad-node detection, the preheat demand
    forecaster) has the same CI-parity fallback NumpyMLPScorer gives the
    MLP path. Accepts numpy or device params (leaves are converted)."""
    wz, uz, bz = (np.asarray(params[k], np.float32) for k in ("wz", "uz", "bz"))
    wr, ur, br = (np.asarray(params[k], np.float32) for k in ("wr", "ur", "br"))
    wh, uh, bh = (np.asarray(params[k], np.float32) for k in ("wh", "uh", "bh"))
    x = np.asarray(x, np.float32)
    b, t, _ = x.shape
    if lengths is None:
        lengths = np.full((b,), t, np.int32)
    else:
        lengths = np.asarray(lengths, np.int32)
    h = np.zeros((b, uz.shape[0]), np.float32)
    for step in range(t):
        xt = x[:, step, :]
        z = _np_sigmoid(xt @ wz + h @ uz + bz)
        r = _np_sigmoid(xt @ wr + h @ ur + br)
        n = np.tanh(xt @ wh + (r * h) @ uh + bh)
        h_new = (1.0 - z) * n + z * h
        # state stops updating past a sequence's length, exactly like
        # the scan's keep mask: the final hidden is the last REAL step
        h = np.where((step < lengths)[:, None], h_new, h)
    layers = params["head"]["layers"]
    out = h
    last = len(layers) - 1
    for i, layer in enumerate(layers):
        out = out @ np.asarray(layer["w"], np.float32) + np.asarray(
            layer["b"], np.float32
        )
        if i != last:
            out = _np_gelu(out)
    return out[:, 0]


class GNNScorer:
    """Edge-RTT predictor over a fixed probe graph: scores (src, dst) host
    pairs by predicted RTT (for seed placement / cross-host ranking, and
    the batched scoring service's GNN rung).

    ``graph`` is the graph being served, which is as a rule not the one
    ``params`` were fitted on: the learned rows are placed on it by host
    id (``place_node_rows``; ``rows`` says how many were placed, given
    the default row, dropped), and its node tables are padded to
    ``node_capacity`` so that the traced shapes follow the capacity rung
    and not the host count (a padded node has zero features, no
    neighbors and is nobody's neighbor).

    Embeddings are computed ONCE at construction — swap time in the
    model-refresher's lifecycle — and stay resident on device next to
    the params; per predict only the (src, dst) index vectors move. With
    a multi-device ``mesh`` the embed forward runs graph-parallel
    (models.gnn_sharded): node tables row-sharded over ``mesh[axis]``,
    so a fleet-scale graph never materializes on one chip."""

    def __init__(self, params: Any, graph, mesh=None, axis: str = "gp"):
        import jax
        import jax.numpy as jnp

        params, self.rows = place_node_rows(params, graph.node_ids)
        self._node_index = {hid: i for i, hid in enumerate(graph.node_ids)}
        if mesh is not None and dict(getattr(mesh, "shape", {})).get(axis, 1) > 1:
            self._params = _device_params(params)
            self._emb = self._sharded_embed(graph, mesh, axis)
        else:
            from dragonfly2_tpu.models.gnn_sharded import pad_node_arrays

            # the padding of the sharded embed, to the capacity rung in
            # place of a shard multiple (the graph never outgrows its rung)
            cap = self.capacity = node_capacity(graph.num_nodes)
            feats, neighbors, mask = pad_node_arrays(graph, cap)
            self._widths = (feats.shape[1], neighbors.shape[1])
            if "node_embed" in params:
                params["node_embed"] = pad_batch(params["node_embed"], cap)
            self._params = _device_params(params)
            self._emb = jax.block_until_ready(
                _jit_once(_served_gnn_embed)(
                    self._params, jnp.asarray(feats), jnp.asarray(neighbors), jnp.asarray(mask)
                )
            )
        self._predict = _jit_once(_served_gnn_edge)

    # the node capacity the one-device embed was padded to; None under a mesh
    capacity: "int | None" = None

    def prepare_capacity(self, capacity: int, rungs) -> None:
        """Compile the embed and the edge head of every row rung of
        ``rungs`` for node tables of ``capacity`` rows, on blank tables
        of this scorer's widths: the scorer built when the fleet has
        outgrown this one's rung then finds them compiled."""
        import jax
        import jax.numpy as jnp

        params = dict(self._params)
        feats, degree = self._widths
        if "node_embed" in params:
            params["node_embed"] = jnp.zeros((capacity, params["node_embed"].shape[1]), jnp.float32)
        emb = _jit_once(_served_gnn_embed)(
            params,
            jnp.zeros((capacity, feats), jnp.float32),
            jnp.zeros((capacity, degree), jnp.int32),
            jnp.zeros((capacity, degree), jnp.float32),
        )
        for rows in rungs:
            idx = jnp.zeros((rows,), jnp.int32)
            jax.block_until_ready(self._predict(params, emb, idx, idx))

    def _sharded_embed(self, graph, mesh, axis: str):
        """Graph-parallel embed at swap time: pad node tables to the
        shard multiple, run the ring-gather SAGE forward, keep only the
        real rows (padded nodes self-neighbor with zero mask — inert)."""
        from dragonfly2_tpu.models.gnn_sharded import (
            make_sharded_embed,
            pad_node_arrays,
        )

        shards = dict(mesh.shape)[axis]
        feats, nbrs, mask = pad_node_arrays(graph, shards)
        dense = {k: v for k, v in self._params.items() if k != "node_embed"}
        embed = self._params.get("node_embed")
        if embed is not None:
            import jax.numpy as jnp

            embed = jnp.asarray(pad_batch(np.asarray(embed), feats.shape[0]))
        emb = make_sharded_embed(mesh, axis)(dense, embed, feats, nbrs, mask)
        return emb[: graph.num_nodes]

    def has_host(self, host_id: str) -> bool:
        return host_id in self._node_index

    def node_index(self) -> "dict[str, int]":
        """host id → node of the served graph."""
        return self._node_index

    def node_rows(self) -> "dict[str, np.ndarray]":
        """host id → the learned row this scorer holds for it (a host
        copy of the table on the device): what a check that every host
        of the served graph got the row fitted for its own id reads."""
        embed = self._params.get("node_embed")
        if embed is None:
            return {}
        embed = np.asarray(embed)
        return {hid: embed[i] for hid, i in self._node_index.items()}

    def predict_rtt_log_ms(
        self, src_ids: list[str], dst_ids: list[str], stages=NO_STAGES
    ) -> np.ndarray:
        import jax
        import jax.numpy as jnp

        # bucketed like every serving forward: the pairwise head compiles
        # once per ladder rung, not once per candidate-set size. Pads
        # point at node 0 — scored and discarded by the slice.
        n = len(src_ids)
        rows = bucket_rows(n)
        src = np.zeros((rows,), np.int32)
        dst = np.zeros((rows,), np.int32)
        src[:n] = [self._node_index[s] for s in src_ids]
        dst[:n] = [self._node_index[d] for d in dst_ids]
        h2d, forward, d2h = stages
        with h2d:
            s, d = jnp.asarray(src), jnp.asarray(dst)
        with forward:
            out = jax.block_until_ready(self._predict(self._params, self._emb, s, d))
        with d2h:
            return np.asarray(out)[:n]


class GRUScorer:
    """Next-piece-cost predictor around trained GRU params — the
    scheduler's ml evaluator consults it for model-based bad-node
    detection (a parent whose latest piece cost blows far past the
    prediction from its own history is flagged)."""

    def __init__(self, params: Any):
        from dragonfly2_tpu.models.gru import predict_next_cost

        self._params = _device_params(params)
        self._fn = _jit_once(predict_next_cost)

    def predict_next_log_cost(self, cost_prefixes_ms: list) -> np.ndarray:
        """[B] predicted next log1p piece cost (ms) from per-parent piece
        cost history prefixes — features built exactly like the offline
        extractor (schema/features.extract_piece_sequences: log1p cost,
        normalized piece position)."""
        import jax.numpy as jnp

        from dragonfly2_tpu.schema.features import (
            GRU_FEATURE_DIM,
            GRU_MAX_SEQ,
        )
        from dragonfly2_tpu.schema.records import MAX_PIECES_PER_PARENT

        b = len(cost_prefixes_ms)
        # bucketed history batch: pad rows are all-zero sequences with
        # length 0 (the scan keeps h0 for them), sliced off below — the
        # recurrence compiles once per ladder rung, not once per batch
        # size (retired the predict_next_cost retrace entry)
        rows = bucket_rows(b)
        seqs = np.zeros((rows, GRU_MAX_SEQ, GRU_FEATURE_DIM), np.float32)
        lengths = np.zeros((rows,), np.int32)
        # positions trained on are (true piece index + 1)/MAX, capped at
        # GRU_MAX_SEQ pieces per record — long live histories are tail-
        # truncated to the most recent costs with their TRUE positions,
        # clipped to the trained range (records never exceed MAX pieces,
        # so larger positions would be out-of-distribution)
        pos_cap = GRU_MAX_SEQ / MAX_PIECES_PER_PARENT
        for i, prefix in enumerate(cost_prefixes_ms):
            full = np.asarray(prefix, np.float64)
            start = max(0, len(full) - GRU_MAX_SEQ)
            p = full[start:]
            L = len(p)
            seqs[i, :L, 0] = np.log1p(p)
            pos = (start + np.arange(L) + 1) / MAX_PIECES_PER_PARENT
            seqs[i, :L, 1] = np.minimum(pos, pos_cap)
            lengths[i] = L
        return np.asarray(
            self._fn(self._params, jnp.asarray(seqs), jnp.asarray(lengths))
        )[:b]
