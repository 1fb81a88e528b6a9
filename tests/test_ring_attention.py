"""Ring attention / context parallelism tests — 8-virtual-device CPU mesh.

Capability-parity-plus (the reference has no ring attention, SURVEY.md §2.5):
ring + Ulysses(sep) attention must match dense attention exactly and
differentiate correctly through the ring.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.ops.ring_attention import (
    ring_attention, ring_attention_shard, sep_attention_shard)


def _dense_ref(q, k, v, causal):
    D = q.shape[-1]
    s = np.einsum("bqhd,bkhd->bhqk", q, k).astype(np.float64) / np.sqrt(D)
    if causal:
        T = q.shape[1]
        mask = np.tril(np.ones((T, T), bool))
        s = np.where(mask[None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v.astype(np.float64)).astype(
        np.float32)


def _qkv(B=2, T=16, H=4, D=8, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.normal(size=(B, T, H, D)).astype(np.float32),
            rng.normal(size=(B, T, H, D)).astype(np.float32),
            rng.normal(size=(B, T, H, D)).astype(np.float32))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("n", [2, 4, 8])
def test_ring_matches_dense(causal, n):
    q, k, v = _qkv(T=16)
    mesh = Mesh(np.asarray(jax.devices()[:n]), ("cp",))

    def per_shard(q, k, v):
        return ring_attention_shard(q, k, v, "cp", causal=causal)

    f = jax.jit(jax.shard_map(per_shard, mesh=mesh,
                              in_specs=(P(None, "cp"),) * 3,
                              out_specs=P(None, "cp"), check_vma=False))
    sharding = NamedSharding(mesh, P(None, "cp"))
    out = f(*(jax.device_put(x, sharding) for x in (q, k, v)))
    ref = _dense_ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_sep_matches_dense(causal):
    q, k, v = _qkv(T=16, H=4)
    n = 4
    mesh = Mesh(np.asarray(jax.devices()[:n]), ("sep",))

    def per_shard(q, k, v):
        return sep_attention_shard(q, k, v, "sep", causal=causal)

    f = jax.jit(jax.shard_map(per_shard, mesh=mesh,
                              in_specs=(P(None, "sep"),) * 3,
                              out_specs=P(None, "sep"), check_vma=False))
    sharding = NamedSharding(mesh, P(None, "sep"))
    out = f(*(jax.device_put(x, sharding) for x in (q, k, v)))
    ref = _dense_ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)


def test_ring_gradients_match_dense():
    """jax.grad through the ring (ppermute transposes) == dense grads."""
    q, k, v = _qkv(B=1, T=8, H=2, D=4)
    n = 4
    mesh = Mesh(np.asarray(jax.devices()[:n]), ("cp",))

    def ring_loss(q, k, v):
        def per_shard(q, k, v):
            return ring_attention_shard(q, k, v, "cp", causal=True)

        f = jax.shard_map(per_shard, mesh=mesh, in_specs=(P(None, "cp"),) * 3,
                          out_specs=P(None, "cp"), check_vma=False)
        return jnp.sum(f(q, k, v) ** 2)

    def dense_loss(q, k, v):
        D = q.shape[-1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(1.0 * D)
        T = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.einsum("bhqk,bkhd->bqhd", p, v) ** 2)

    # one program each: un-jitted, every operation of the ring and of its
    # transpose is dispatched, and compiled for four devices, on its own
    g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    g_dense = jax.jit(jax.grad(dense_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


def test_eager_ring_attention_api():
    q, k, v = _qkv(T=16)
    g = dist.new_group(list(range(4)))
    out = ring_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                         paddle.to_tensor(v), group=g, causal=True)
    ref = _dense_ref(q, k, v, True)
    np.testing.assert_allclose(out.numpy(), ref, rtol=2e-4, atol=2e-4)
    # sep impl through the same API
    out2 = ring_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                          paddle.to_tensor(v), group=g, impl="sep")
    np.testing.assert_allclose(out2.numpy(), ref, rtol=2e-4, atol=2e-4)


def test_eager_ring_attention_backward():
    q, k, v = _qkv(B=1, T=8, H=2, D=4)
    g = dist.new_group(list(range(4)))
    qt, kt, vt = (paddle.to_tensor(x) for x in (q, k, v))
    for t in (qt, kt, vt):
        t.stop_gradient = False
    out = ring_attention(qt, kt, vt, group=g, causal=True)
    out.sum().backward()
    assert qt.grad is not None and kt.grad is not None and vt.grad is not None
    assert np.abs(qt.grad.numpy()).sum() > 0


def test_ring_degenerate_single_rank():
    q, k, v = _qkv(T=8)
    g = dist.new_group([0])
    out = ring_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                         paddle.to_tensor(v), group=g, causal=True)
    ref = _dense_ref(q, k, v, True)
    np.testing.assert_allclose(out.numpy(), ref, rtol=2e-4, atol=2e-4)
