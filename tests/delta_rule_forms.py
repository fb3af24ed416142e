"""Operands for the delta rule's chunked form and the recurrence it must
equal, in float64 — shared by ``tests/test_delta_rule_chunk.py`` (interpret
mode, toy heads) and ``scripts/delta_chunk_bench.py`` (the chip, real
heads: where a one-pass bf16 product would show)."""

import jax.numpy as jnp
import numpy as np

from flexflow_tpu.serve.batch_config import BatchConfig
from flexflow_tpu.serve.hybrid_ops import Segments


def segments(req, pos, slots):
    """``Segments`` of a flat batch: ``req`` the rows' slots (-1: a pad),
    ``pos`` their positions."""
    req, pos = jnp.asarray(req, jnp.int32), jnp.asarray(pos, jnp.int32)
    bc = BatchConfig(tokens=pos, request_index=req, token_position=pos,
                     num_tokens=jnp.int32(len(req)),
                     seq_lens=jnp.zeros((slots,), jnp.int32))
    return Segments(bc, slots)


def layout(parts):
    """``(req, pos)`` of a flat batch from ``[(slot or -1, first position,
    rows)]`` in row order."""
    req = [r for r, _, n in parts for _ in range(n)]
    pos = [p + i if r >= 0 else 0 for r, p, n in parts for i in range(n)]
    return req, pos


def draw(rng, rows, heads, d, keys="drawn", decay=None, beta=None):
    """``q, k, v, g, beta`` as the layer hands them over: unit keys, queries
    of size ``d^-1/2``, log decays <= 0, ``beta`` in (0, 2).  ``keys``
    ``repeated`` / ``alternating``: ONE unit vector through every row (with
    alternating sign) — the solve's worst case."""
    normal = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(normal(rows, heads, d)) * d ** -0.5
    k = unit(normal(rows, heads, d))
    if keys != "drawn":
        k = jnp.broadcast_to(k[:1], k.shape)
    if keys == "alternating":
        k = k * jnp.where(jnp.arange(rows) % 2 == 0, 1.0, -1.0)[:, None, None]
    v = normal(rows, heads, d)
    g = -0.3 * jnp.abs(normal(rows, heads, d)) if decay is None else \
        jnp.full((rows, heads, d), np.log(decay), jnp.float32)
    b = 2 * jnp.asarray(rng.uniform(0.02, 0.98, (rows, heads)), jnp.float32) \
        if beta is None else jnp.full((rows, heads), beta, jnp.float32)
    return q, k, v, g, b


def recurrence64(q, k, v, g, beta, req, pos, kda):
    """The per-row recurrence in float64 over a flat batch: ``(outputs,
    states)`` — a row whose position is 0 starts from zeros, any other
    segment from its slot's stored state; pads get 0."""
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    states = np.asarray(kda, np.float64).copy()
    out = np.zeros(q.shape)
    for t, (r, p) in enumerate(zip(req, pos)):
        if r < 0:
            continue
        s = np.zeros(states.shape[1:]) if p == 0 else states[r]
        s = s * np.exp(g[t])[..., None]
        u = v[t] - np.sum(s * k[t][..., None], axis=-2)
        s = s + (beta[t][..., None] * k[t])[..., None] * u[..., None, :]
        out[t] = np.sum(s * q[t][..., None], axis=-2)
        states[r] = s
    return out, states
