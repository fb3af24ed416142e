"""Shared pieces of the plain references: float32, ``highest`` precision,
no cache, no kernels, no batching tricks."""

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def mm(x, w):
    return jnp.matmul(x, w, precision=HI)


def layer_norm(x, gamma, beta, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * gamma + beta


def causal_attention(q, k, v, block=1024):
    """q ``[B, T, H, D]``, k/v ``[B, T, KV, D]`` (H a multiple of KV; query
    head h reads kv head h // (H // KV)); softmax in float32.  Queries go
    ``block`` at a time so that the scores of a context of several thousand
    positions fit beside a deployment; the arithmetic is the plain one."""
    b, t, h, d = q.shape
    kv = k.shape[2]
    q = q.reshape(b, t, kv, h // kv, d)
    out = []
    for lo in range(0, t, block):
        hi = min(lo + block, t)
        s = jnp.einsum("btkgd,bskd->bkgts", q[:, lo:hi], k[:, :hi],
                       precision=HI) / jnp.sqrt(jnp.float32(d))
        mask = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bkgts,bskd->btkgd", p, v[:, :hi],
                              precision=HI))
    return jnp.concatenate(out, axis=1).reshape(b, t, h * d)
