"""``_prefill_kernel`` as it stood before PR 60, kept as an ORACLE: the
causal (or ring) mask built and applied in EVERY live block, float32 copies
of q, K and V into both contractions, the running max and sum read as ONE
lane of their scratch and broadcast back over it.  ``tests/test_prefill.py``
holds the kernel to it to the bit on float32 caches;
``scripts/prefill_kernel_bench.py`` times it beside the kernel on the chip,
with the half-way forms: ``native_operands=True`` (the cache's operands) and
``replicated=True`` (the statistics kept in every lane, as the kernel
keeps them).
"""

import functools
from unittest import mock

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from flexflow_tpu.ops.pallas import attention
from flexflow_tpu.ops.pallas.attention import NEG_INF


def masked_everywhere_kernel(rows_ref, pstart_ref, fmax_ref, *refs, block_s,
                             num_kv, gq, m_rows, scale, kv_quant,
                             paged=False, window=0, s_len=0,
                             native_operands=False, replicated=False):
    if paged:
        refs = refs[1:]
    q_ref, k_ref, v_ref, *rest = refs
    if kv_quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    g = pl.program_id(0)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    fmax = fmax_ref[g]
    pstart = pstart_ref[g]
    base = s * block_s
    if window:
        run = attention._ring_blocks(fmax, block_s, s_len,
                                     window + m_rows // gq - 1)[0](s)
    else:
        run = base <= fmax
    operand = attention.prefill_operand_dtype(q_ref.dtype, k_ref.dtype) \
        if native_operands else jnp.float32

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(operand)
        k = k_ref[0].astype(operand)
        sc = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        if kv_quant:
            sc = sc * ks_ref[0][:, None, :]
        qpos = pstart + jax.lax.broadcasted_iota(
            jnp.int32, (m_rows, block_s), 0) // gq
        key_pos = base + jax.lax.broadcasted_iota(
            jnp.int32, (m_rows, block_s), 1)
        if window:
            age = qpos % s_len - key_pos
            age = jnp.where(age < 0, age + s_len, age)
            seen = age < jnp.minimum(qpos + 1, window)
        else:
            seen = key_pos <= qpos
        live = jnp.broadcast_to(seen[None], sc.shape)
        sc = jnp.where(live, sc, NEG_INF)
        lanes = attention._lanes_to if replicated else (lambda x, n: x)
        m_prev = m_ref[...] if replicated else m_ref[:, :, 0:1]
        l_prev = l_ref[...] if replicated else l_ref[:, :, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(live, jnp.exp(sc - lanes(m_new, block_s)), 0.0)
        l_new = alpha * l_prev + jnp.sum(p, -1, keepdims=True)
        v = v_ref[0].astype(operand)
        if kv_quant:
            p = p * vs_ref[0][:, None, :]
        pv = jax.lax.dot_general(
            p.astype(operand), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * lanes(alpha, acc_ref.shape[-1]) + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(s == pl.num_programs(2) - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :, 0:1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def no_body_kernel(rows_ref, pstart_ref, fmax_ref, *refs, kv_quant,
                   paged=False, **plan):
    """The grid, its index maps and its copies alone."""
    if paged:
        refs = refs[1:]
    q_ref, o_ref = refs[0], refs[5 if kv_quant else 3]

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _write():
        o_ref[...] = q_ref[...]


def prefill_attention_with(kernel, *args, **kwargs):
    """``prefill_attention(*args, **kwargs)`` traced afresh with ``kernel``
    in ``_prefill_kernel``'s place (the same plan, grid and BlockSpecs)."""
    with mock.patch.object(attention, "_prefill_kernel", kernel):
        return attention.prefill_attention.__wrapped__(*args, **kwargs)


PARENT = masked_everywhere_kernel
NATIVE_MASKED = functools.partial(masked_everywhere_kernel,
                                  native_operands=True)
REPLICATED = functools.partial(masked_everywhere_kernel, replicated=True)
