"""Pallas fused attention (ops/flash.py) vs the jnp oracle — interpret
mode on CPU is the parity harness; the same kernel compiles on TPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dragonfly2_tpu.ops.flash import flash_attention
from dragonfly2_tpu.ops.ring import local_attention


def _qkv(b, t, h, d, dtype=jnp.float32, seed=0):
    key = jax.random.PRNGKey(seed)
    return tuple(
        jax.random.normal(k, (b, t, h, d), dtype) for k in jax.random.split(key, 3)
    )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize(
    "shape",
    [
        (2, 128, 4, 64),  # block-aligned
        (2, 200, 4, 64),  # T not a block multiple → padded keys masked
        (1, 64, 2, 32),   # smaller than one default block
    ],
)
def test_matches_oracle(shape, causal):
    q, k, v = _qkv(*shape)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    want = local_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_bfloat16_inputs():
    q, k, v = _qkv(2, 256, 4, 64, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    want = local_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=2e-2
    )


def test_small_blocks_exercise_online_softmax():
    """Multiple k blocks per q block force the running max/normalizer
    path (not a single-block shortcut)."""
    q, k, v = _qkv(1, 256, 2, 32, seed=7)
    out = flash_attention(
        q, k, v, causal=True, block_q=64, block_k=32, interpret=True
    )
    want = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_non_dividing_block_sizes_keep_tail_keys():
    """Regression: block_k not dividing the padded length must not drop
    tail keys — padding rounds to a common multiple of both blocks."""
    q, k, v = _qkv(1, 100, 2, 32, seed=11)
    out = flash_attention(
        q, k, v, causal=False, block_q=64, block_k=48, interpret=True
    )
    want = local_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_ulysses_with_pallas_kernel():
    """The sp all-to-all path with the fused kernel as its per-device
    compute matches the oracle end-to-end."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dragonfly2_tpu.ops.ulysses import make_ulysses_attention
    from dragonfly2_tpu.parallel.mesh import make_mesh

    sp_mesh = make_mesh(jax.devices()[:8], sp=8)
    q, k, v = _qkv(2, 16 * 8, 8, 32, seed=5)
    spec = NamedSharding(sp_mesh, P(None, "sp", None, None))
    fn = make_ulysses_attention(sp_mesh, "sp", causal=True, use_pallas=True)
    out = fn(*(jax.device_put(x, spec) for x in (q, k, v)))
    want = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t", [64, 70, 256])
def test_flash_gradients_match_oracle(causal, t):
    """Training through the fused kernel: VJP (lse-rebuilt flash
    backward over KV tiles) must match the oracle's gradients, including
    ragged lengths that exercise the padding path."""
    b, h, d = 2, 2, 16
    key = jax.random.PRNGKey(t + int(causal))
    q, k, v = (
        jax.random.normal(kk, (b, t, h, d), jnp.float32)
        for kk in jax.random.split(key, 3)
    )
    g = jax.random.normal(jax.random.PRNGKey(9), (b, t, h, d), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, interpret=True) * g)

    def loss_oracle(q, k, v):
        return jnp.sum(local_attention(q, k, v, causal=causal) * g)

    got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_oracle, argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip("qkv", got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=2e-3, rtol=2e-3,
            err_msg=f"d{name} diverges",
        )


def test_ulysses_pallas_path_trains():
    """The Ulysses sequence-parallel path with the Pallas kernel is
    differentiable end-to-end, and its gradients MATCH the non-Pallas
    Ulysses path's (grad flows through the all-to-alls AND the custom
    VJP without dropping a scale or swapping dk/dv)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dragonfly2_tpu.ops.ulysses import make_ulysses_attention
    from dragonfly2_tpu.parallel.mesh import make_mesh

    n = min(4, jax.device_count())
    mesh = make_mesh(jax.devices()[:n], sp=n)
    b, t, h, d = 2, 16 * n, max(2, n), 8
    key = jax.random.PRNGKey(3)
    q, k, v = (
        jax.random.normal(kk, (b, t, h, d), jnp.float32)
        for kk in jax.random.split(key, 3)
    )
    spec = NamedSharding(mesh, P(None, "sp", None, None))
    qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))
    uly_pl = make_ulysses_attention(mesh, "sp", causal=True, use_pallas=True)
    uly_xla = make_ulysses_attention(mesh, "sp", causal=True, use_pallas=False)

    got = jax.grad(lambda *a: jnp.sum(uly_pl(*a) ** 2), argnums=(0, 1, 2))(qs, ks, vs)
    want = jax.grad(lambda *a: jnp.sum(uly_xla(*a) ** 2), argnums=(0, 1, 2))(qs, ks, vs)
    for name, a, b_ in zip("qkv", got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=2e-3, rtol=2e-3,
            err_msg=f"d{name} diverges between Pallas and XLA Ulysses paths",
        )


def test_block_hint_legalization_properties():
    """Every caller hint must canonicalize to Mosaic-legal tiles with
    bounded padding: sublane dims multiples of 8, the LSE lane dim a
    multiple of 128 or equal to t_pad, bk dividing bq, and t_pad within
    one block of t. (The TPU lowering rules the CPU interpreter cannot
    enforce — chip_smoke.py compiles a sample of these on the chip;
    this pins the arithmetic for the whole space.)"""
    hypothesis = pytest.importorskip("hypothesis")
    given, settings, st = hypothesis.given, hypothesis.settings, hypothesis.strategies

    from dragonfly2_tpu.ops.flash import _legal_blocks

    @settings(max_examples=300, deadline=None)
    @given(
        st.integers(min_value=1, max_value=512),
        st.integers(min_value=1, max_value=512),
        st.integers(min_value=1, max_value=4096),
    )
    def prop(block_q, block_k, t):
        bq, bk, t_pad = _legal_blocks(block_q, block_k, t)
        assert bq % 8 == 0 and bk % 8 == 0  # sublane rule
        assert bq % 128 == 0 or bq == t_pad  # LSE lane rule
        assert bq % bk == 0  # no lcm blowup
        assert t_pad % bq == 0 and t_pad % bk == 0  # grid divides
        assert t <= t_pad <= t + max(bq, bk)  # bounded padding

    prop()
