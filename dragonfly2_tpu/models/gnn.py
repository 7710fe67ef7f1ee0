"""GraphSAGE GNN over the probe graph.

The first real implementation of the reference's `trainGNN` stub
(reference trainer/training/training.go:82-88). Hosts are nodes, probe
measurements are edges (EWMA RTT, reference probes.go:174-212). The model
learns host embeddings whose pairwise head predicts edge RTT — usable both
for parent ranking (predict RTT to unprobed candidates) and seed-peer
placement link prediction (BASELINE.json configs).

TPU form: aggregation over a fixed-degree sampled neighbor table [N, K]
(schema.features.sample_neighbors) — dense gathers + masked means, static
shapes, no sparse dynamic ops inside jit. For graphs sharded over devices,
the gather runs through ops.ring.ring_gather_rows so the full feature
table never materializes on one chip.
"""

# dfanalyze: device-hot — jitted/device-feeding compute plane

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from dragonfly2_tpu.models.mlp import apply_mlp, init_mlp
from dragonfly2_tpu.ops.segment import aggregate_neighbors

Params = dict


class NodeIds(tuple):
    """The host ids a fitted ``node_embed`` was learned for, row by row.

    A version's rows mean nothing without them: the fit interns hosts in
    the order its upload names them, the scheduler's live graph in the
    order its probes arrived, and ``apply_graphsage`` joins the rows to a
    graph by position. They ride the parameter tree under ``node_ids`` as
    a node with no leaves (the ids are the tree's structure, not its
    numbers), so every walk over a version's arrays — ``tree_map`` to the
    host, a norm per leaf, the serializer's flatten — sees the same
    leaves as before; ``trainer.serving`` writes and reads them beside
    the arrays, and ``GNNScorer`` takes them out before anything is
    traced."""

    __slots__ = ()


jax.tree_util.register_pytree_node(
    NodeIds, lambda ids: ((), tuple(ids)), lambda ids, _: NodeIds(ids)
)


def init_graphsage(
    key: jax.Array,
    in_dim: int,
    hidden_dims: Sequence[int],
    head_hidden: int = 64,
    num_nodes: int | None = None,
    embed_dim: int = 16,
    dtype=jnp.float32,
) -> Params:
    """SAGE layers + pairwise edge head.

    Layer l: h' = act(W_self·h + W_nbr·mean_{u∈N(v)} h_u + b).
    Edge head: MLP([h_src, h_dst, h_src⊙h_dst]) → scalar log-RTT.

    ``num_nodes`` adds a learnable per-node embedding table concatenated to
    the input features — host stats alone don't localize a host in the RTT
    geometry, the embedding learns its position (transductive over the
    known host set; unseen hosts get the zero embedding).
    """
    params_embed = None
    if num_nodes is not None:
        key, ek = jax.random.split(key)
        params_embed = jax.random.normal(ek, (num_nodes, embed_dim), dtype) * 0.1
        in_dim = in_dim + embed_dim
    layers = []
    d = in_dim
    for h in hidden_dims:
        key, k1, k2 = jax.random.split(key, 3)
        scale = jnp.sqrt(2.0 / d).astype(dtype)
        layers.append(
            {
                "w_self": jax.random.normal(k1, (d, h), dtype) * scale,
                "w_nbr": jax.random.normal(k2, (d, h), dtype) * scale,
                "b": jnp.zeros((h,), dtype),
            }
        )
        d = h
    key, hk = jax.random.split(key)
    head = init_mlp(hk, [3 * d, head_hidden, 1], dtype)
    out: Params = {"sage": layers, "head": head}
    if params_embed is not None:
        out["node_embed"] = params_embed
    return out


def apply_graphsage(
    params: Params,
    node_features: jax.Array,  # [N, F]
    neighbors: jax.Array,  # [N, K] int32
    neighbor_mask: jax.Array,  # [N, K]
    compute_dtype=jnp.bfloat16,
) -> jax.Array:
    """→ [N, H] node embeddings (L2-normalized, GraphSAGE convention)."""
    h = node_features
    if "node_embed" in params:
        h = jnp.concatenate([h, params["node_embed"]], axis=-1)
    for layer in params["sage"]:
        agg = aggregate_neighbors(h, neighbors, neighbor_mask)
        z = jnp.dot(
            h.astype(compute_dtype),
            layer["w_self"].astype(compute_dtype),
            preferred_element_type=jnp.float32,
        ) + jnp.dot(
            agg.astype(compute_dtype),
            layer["w_nbr"].astype(compute_dtype),
            preferred_element_type=jnp.float32,
        )
        h = jax.nn.relu(z + layer["b"].astype(jnp.float32))
    norm = jnp.linalg.norm(h, axis=-1, keepdims=True)
    return h / jnp.maximum(norm, 1e-6)


def predict_edge(
    params: Params, embeddings: jax.Array, src: jax.Array, dst: jax.Array
) -> jax.Array:
    """Pairwise head: predicted log-RTT for edges (src[i] → dst[i])."""
    hs = jnp.take(embeddings, src, axis=0)
    hd = jnp.take(embeddings, dst, axis=0)
    pair = jnp.concatenate([hs, hd, hs * hd], axis=-1)
    return apply_mlp(params["head"], pair)[..., 0]


def forward_edge_rtt(
    params: Params,
    node_features: jax.Array,
    neighbors: jax.Array,
    neighbor_mask: jax.Array,
    src: jax.Array,
    dst: jax.Array,
) -> jax.Array:
    """Full forward: features → embeddings → edge log-RTT predictions."""
    emb = apply_graphsage(params, node_features, neighbors, neighbor_mask)
    return predict_edge(params, emb, src, dst)
