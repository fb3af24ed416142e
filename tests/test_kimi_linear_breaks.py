"""Kimi Linear through the normal serve path with ONE thing wrong at a time:
each break of the new mechanisms must move the logits far past the tolerance
``tests/test_kimi_linear.py`` holds (its helpers and toy configuration are
imported from there; a file of its own so that the 14 deployments built here
run beside that file's tests, not after them)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_kimi_linear import (CAP, HF, LISTS, SLOTS, TOL, build, decode_scan,
                              feed_flat, flat_step, reference_logprobs,
                              seeded, tokens)

from flexflow_tpu.serve import hybrid_ops
from flexflow_tpu.serve.hybrid_ops import (CausalConv1d, KimiDeltaAttention,
                                           LatentAttention)


def _row_scan(self, q, k, v, g, beta, kda, seg, *ctx, correction=True):
    """The delta rule row by row over a flat batch (the program's ``_step``
    or ``_chunked`` replaced by it); ``correction=False``: ``S' + beta k
    v^T``, plain gated linear attention."""
    def row(carry, r):
        s, kda = carry
        q_r, k_r, v_r, g_r, b_r, start, fresh, at, store = r
        own = jax.lax.dynamic_index_in_dim(kda, at, keepdims=False)
        s = jnp.where(start, jnp.where(fresh, 0.0, own), s)
        s = s * jnp.exp(g_r)[..., None]
        u = v_r - jnp.sum(s * k_r[..., None], axis=-2) if correction else v_r
        s = s + (b_r[..., None] * k_r)[..., None] * u[..., None, :]
        zero = jnp.int32(0)
        kda = jax.lax.dynamic_update_slice(kda, s[None],
                                           (store, zero, zero, zero))
        return (s, kda), jnp.sum(s * q_r[..., None], axis=-2)

    (_, kda), o = jax.lax.scan(
        row, (jnp.zeros(kda.shape[1:], kda.dtype), kda),
        (q, k, v, g, beta, seg.start, seg.fresh, seg.rows, seg.store))
    o = jnp.where(seg.live[:, None, None], o, 0.0)
    return (o, kda, "row_scan") if ctx else (o, kda)


def _break(broken, monkeypatch):
    """Break the PROGRAM (the reference stays the published model); returns
    the program's configuration and a hook run on the built deployment."""
    hf, after = dict(HF), lambda im: None
    kda = KimiDeltaAttention

    def inputs(change):
        """Both forms with ``(q, k, v, g, beta)`` changed first."""
        def changed(sound):
            return lambda self, q, k, v, g, beta, *rest: sound(
                self, *change(q, k, v, g, beta), *rest)

        for name in ("_step", "_chunked"):
            monkeypatch.setattr(kda, name, changed(getattr(kda, name)))

    if broken == "nothing":
        for name in ("_step", "_chunked"):
            monkeypatch.setattr(kda, name, _row_scan)
    elif broken == "decay_a_scalar_a_head":
        inputs(lambda q, k, v, g, b: (q, k, v, jnp.broadcast_to(
            jnp.mean(g, -1, keepdims=True), g.shape), b))
    elif broken == "decay_dropped":
        inputs(lambda q, k, v, g, b: (q, k, v, jnp.zeros_like(g), b))
    elif broken == "beta_fixed_at_1":
        inputs(lambda q, k, v, g, b: (q, k, v, g, jnp.ones_like(b)))
    elif broken == "delta_correction_dropped":
        for name in ("_step", "_chunked"):
            monkeypatch.setattr(kda, name, functools.partialmethod(
                _row_scan, correction=False))
    elif broken == "q_k_not_normalised":
        monkeypatch.setattr(kda, "_unit", lambda self, a: a)
    elif broken == "conv_without_its_silu":
        sound = CausalConv1d.lower

        def bare(self, ctx, inputs, params):
            with monkeypatch.context() as m:
                m.setattr(jax.nn, "silu", lambda x: x)
                return sound(self, ctx, inputs, params)

        monkeypatch.setattr(CausalConv1d, "lower", bare)
    elif broken == "conv_tails_of_another_stream":
        sound = CausalConv1d.lower

        def rolled(self, ctx, inputs, params):
            state = ctx.extras["state"]
            third = self.channels // 3          # q reads k's tail, ...
            ctx.extras["state"] = dict(state, conv=jnp.roll(
                state["conv"], third, axis=-1))
            return sound(self, ctx, inputs, params)

        monkeypatch.setattr(CausalConv1d, "lower", rolled)
    elif broken == "head_norm_after_the_gate":
        monkeypatch.setattr(
            kda, "_gated_norm", lambda self, o, gate, gain:
            hybrid_ops._rms_norm(o * jax.nn.sigmoid(gate),
                                 gain.astype(jnp.float32), self.eps))
    elif broken == "latent_layer_rotated":
        def after(im):
            for n in im.model.graph.nodes:
                if isinstance(n.op, LatentAttention):
                    n.op.use_rope = True
    elif broken == "layer_lists_read_0_based":
        # what a 0-based reading of [1, 2, 3, 5] / [4] builds: entry e is
        # layer e of 0 .. 4, i.e. the 1-based lists [2, 3, 4] + the one left
        # over / [5]
        hf["linear_attn_config"] = dict(LISTS, kda_layers=[1, 2, 3, 4],
                                        full_attn_layers=[5])
    elif broken == "top_8_not_renormalised":
        hf["moe_renormalize"] = False
    elif broken == "scaling_factor_dropped":
        hf["routed_scaling_factor"] = 1.0
    elif broken == "dense_layer_given_a_mixture":
        hf["first_k_dense_replace"] = 0
    else:
        raise ValueError(broken)
    return hf, after


BREAKS = ["decay_a_scalar_a_head", "decay_dropped", "beta_fixed_at_1",
          "delta_correction_dropped", "q_k_not_normalised",
          "conv_without_its_silu", "conv_tails_of_another_stream",
          "head_norm_after_the_gate", "latent_layer_rotated",
          "layer_lists_read_0_based", "top_8_not_renormalised",
          "scaling_factor_dropped", "dense_layer_given_a_mixture"]


@pytest.mark.parametrize("broken", ["nothing"] + BREAKS)
def test_a_break_is_seen(broken, monkeypatch):
    """Each way of getting the new mechanisms wrong moves the logits by far
    more than the tolerance the other tests hold — the routed path's too,
    though only one of a row's eight choices lands on a held expert — and
    ``nothing`` (both forms replaced by a row-by-row scan, the oracle the
    ``delta_correction_dropped`` break is a variant of) moves them not at
    all.  A prompt of 59 in two flat chunks (the second enters with a state
    and a tail), one decode-scan step, one flat step."""
    hf, after = _break(broken, monkeypatch)
    im = build(hf=hf)
    after(im)
    seeded(im, hf=hf)
    prompt = tokens(59, salt=50)
    seq_lens = [0] * SLOTS
    feed_flat(im, 0, prompt[:-1], [CAP], seq_lens)
    _, toks = flat_step(im, [(0, prompt[-1:], 58)], seq_lens)
    made = decode_scan(im, 0, int(toks[0]), 59, 1)
    full = prompt + [int(toks[0])] + made
    want, _ = reference_logprobs(full)
    seq_lens[0] = 60
    (got,), _ = flat_step(im, [(0, [full[60]], 60)], seq_lens)
    err = np.abs(got[0] - want[60]).max()
    if broken == "nothing":
        assert err < TOL, err
    else:
        assert err > 20 * TOL, (broken, err)
