"""All-to-all (Ulysses-style) sequence parallelism.

The second long-context pattern next to ring attention (ops/ring.py):
instead of rotating K/V blocks around an ICI ring, two ``all_to_all``
collectives reshard the activations — sequence-sharded → head-sharded —
so every device runs EXACT attention over the full sequence for its head
subset, then reshards back. Trade-off vs ring: 2 collectives total
instead of axis_size-1 ppermute hops (better at moderate sequence
lengths on all-to-all-capable fabrics), but requires heads % axis_size
== 0 and holds the full sequence per device for the local heads.
"""

from __future__ import annotations

import functools

import jax
from jax import lax


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = False,
    use_pallas: bool = False,
    pallas_interpret: bool = False,
) -> jax.Array:
    """Per-device shards [B, T/sp, H, D] (sequence-sharded along the mesh
    axis, shards concatenated in axis order form the global sequence) →
    [B, T/sp, H, D]. Heads must divide evenly by the axis size.

    ``use_pallas`` runs the head-sharded exact attention through the
    fused Pallas kernel (ops/flash.py) — the hot per-device compute —
    instead of the jnp oracle."""
    from dragonfly2_tpu.ops.ring import local_attention

    axis_size = lax.psum(1, axis_name)
    h = q.shape[2]
    if h % axis_size != 0:
        raise ValueError(
            f"ulysses needs heads % axis_size == 0, got {h} % {axis_size}"
        )

    def seq_to_heads(x):
        # [B, T/sp, H, D] → [B, T, H/sp, D]: split heads, gather sequence
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def heads_to_seq(x):
        # inverse reshard
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    # exact attention: the full sequence is local, only heads are sharded,
    # so no online-softmax machinery is needed at this layer (the Pallas
    # kernel does its own blockwise softmax internally)
    if use_pallas:
        from dragonfly2_tpu.ops.flash import flash_attention

        oh = flash_attention(qh, kh, vh, causal=causal, interpret=pallas_interpret)
    else:
        oh = local_attention(qh, kh, vh, causal=causal)
    return heads_to_seq(oh)


def make_ulysses_attention(
    mesh, axis_name: str, causal: bool = False, use_pallas: bool = False
):
    """shard_map-wrapped all-to-all attention over ``mesh[axis_name]``
    (same calling convention as ops.ring.make_ring_attention). With
    ``use_pallas`` the per-device compute is the fused kernel — compiled
    on TPU, interpreter elsewhere (CI runs on CPU)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    # interpreter only on CPU (the CI parity harness), judged from the
    # devices this mesh will actually run on
    interpret = mesh.devices.flat[0].platform == "cpu"
    spec = P(None, axis_name, None, None)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    def _ulysses(q, k, v):
        return ulysses_attention(
            q,
            k,
            v,
            axis_name=axis_name,
            causal=causal,
            use_pallas=use_pallas,
            pallas_interpret=interpret,
        )

    return _ulysses
