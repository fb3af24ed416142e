"""int8 KV cache: fused in-kernel dequant + end-to-end serve equivalence.

The tentpole gates (VERDICT r5 #4): the int8-KV path must match the bf16-KV
path within a stated tolerance on BOTH the flat (gather) and Pallas
attention paths, with the dequant fused into the kernels (int8 KV never
materializes as bf16 in HBM on the Pallas path), across the
prefill -> decode continuation; and the capacity planner must admit the
full-depth 32-layer llama2-7b-shape config (int8 weights + int8 KV) within
one v5e chip's 16 GB HBM.

Kernel logic runs in interpret mode on the CPU test mesh (the strategy of
test_pallas_attention.py); the compile for a v5e is exercised by
tests/test_tpu_aot_compile.py, the chip by ``benchmark/control.py``'s
``int8_kv`` control.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.ops.pallas.attention import (
    decode_attention,
    prefill_attention,
    tree_attention,
)
from flexflow_tpu.serve import GenerationConfig, RequestManager
from flexflow_tpu.serve.batch_config import BatchConfig

from test_pallas_attention import ref_attention
from test_serve import TINY, make_im, ref_greedy_decode

# Stated tolerance for int8-KV vs fp-KV logits: per-vector symmetric int8
# quantization bounds each K/V element's error by scale/2 (~0.4% of the
# vector's absmax); through softmax attention + 2 decoder layers that
# stays within a few percent of the logit scale on the TINY config.
LOGIT_RTOL, LOGIT_ATOL = 0.05, 0.2


def quantize_cache(rng, r, kv, s, d):
    """A random fp cache plus its per-(row, head, position) int8 form."""
    c = rng.normal(size=(r, kv, s, d)).astype(np.float32)
    scale = np.abs(c).max(axis=-1) / 127.0
    denom = np.where(scale > 0, scale, 1.0)
    q = np.clip(np.round(c / denom[..., None]), -127, 127).astype(np.int8)
    deq = q.astype(np.float32) * scale[..., None]
    return (jnp.asarray(q), jnp.asarray(scale.astype(np.float32)),
            jnp.asarray(deq))


# ---------------------------------------------------------------------------
# kernel level: fused dequant == dequantize-then-attend
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("qh,kv,d,s,block", [
    (4, 2, 8, 32, 16),    # GQA, multi-block
    (4, 4, 8, 32, 32),    # MHA, single block
    (8, 1, 16, 64, 16),   # MQA
])
def test_decode_kernel_fused_dequant_matches_reference(qh, kv, d, s, block):
    rng = np.random.default_rng(0)
    t, r = 3, 4
    q = jnp.asarray(rng.normal(size=(t, qh, d)), jnp.float32)
    kc8, ks, kcf = quantize_cache(rng, r, kv, s, d)
    vc8, vs, vcf = quantize_cache(rng, r, kv, s, d)
    rows = jnp.asarray([0, 2, 1], jnp.int32)
    pos = jnp.asarray([5, 0, s - 1], jnp.int32)
    scale = 1.0 / np.sqrt(d)
    got = decode_attention(q, kc8, vc8, rows, pos, scale, block_s=block,
                           interpret=True, k_scale=ks, v_scale=vs)
    want = ref_attention(q, kcf, vcf, rows, pos, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("q_dtype", ["float32", "bfloat16"])
def test_prefill_kernel_fused_dequant_matches_reference(q_dtype):
    """The int8 values reach the contractions in the QUERIES' type (exact for
    |x| <= 127), the scale planes folded into the scores and into ``p``:
    float32 queries give the float32 arithmetic of the kernel before PR 60,
    bf16 queries its bf16-operand form."""
    import prefill_kernel_forms as forms

    rng = np.random.default_rng(1)
    qh, kv, d, s, bq, block = 4, 2, 8, 64, 8, 16
    g = 3
    t = g * bq
    q = jnp.asarray(rng.normal(size=(g, bq, qh, d)), q_dtype)
    kc8, ks, kcf = quantize_cache(rng, 4, kv, s, d)
    vc8, vs, vcf = quantize_cache(rng, 4, kv, s, d)
    rows = jnp.asarray([0, 2, 1], jnp.int32)
    pstart = jnp.asarray([8, 0, s - bq], jnp.int32)
    kw = dict(scale=1.0 / np.sqrt(d), block_s=block, interpret=True,
              k_scale=ks, v_scale=vs)
    got = prefill_attention(q, kc8, vc8, rows, pstart, **kw)
    assert got.dtype == q.dtype
    flat_rows = jnp.repeat(rows, bq)
    flat_pos = (pstart[:, None] + jnp.arange(bq)[None, :]).reshape(-1)
    want = ref_attention(q.astype(jnp.float32).reshape(t, qh, d), kcf, vcf,
                         flat_rows, flat_pos, kw["scale"])
    tol = 1e-5 if q_dtype == "float32" else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32).reshape(t, qh, d), np.asarray(want),
        atol=tol, rtol=tol,
    )
    # ...and the kernel before PR 60 with these operands (off the chip the
    # interpreter's fused multiply-adds differ by a float32 ulp where a
    # block runs without its mask)
    form = forms.PARENT if q_dtype == "float32" else forms.NATIVE_MASKED
    ulp = 1e-6 if q_dtype == "float32" else 2 ** -7
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(forms.prefill_attention_with(
            form, q, kc8, vc8, rows, pstart, **kw), np.float32),
        atol=ulp, rtol=ulp)


def test_tree_kernel_fused_dequant_matches_fp_cache():
    """tree_attention with an int8 committed cache == the same kernel on
    the dequantized fp cache (the spec-tree segment stays fp in both)."""
    rng = np.random.default_rng(2)
    qh, kv, d, s, p = 4, 2, 8, 32, 4
    t, r = 3, 4
    q = jnp.asarray(rng.normal(size=(t, qh, d)), jnp.float32)
    kc8, ks, kcf = quantize_cache(rng, r, kv, s, d)
    vc8, vs, vcf = quantize_cache(rng, r, kv, s, d)
    sk = jnp.asarray(rng.normal(size=(r, kv, p, d)), jnp.float32)
    sv = jnp.asarray(rng.normal(size=(r, kv, p, d)), jnp.float32)
    rows = jnp.asarray([0, 2, 1], jnp.int32)
    clens = jnp.asarray([5, 0, s - 1], jnp.int32)
    amask = jnp.asarray(rng.integers(0, 2, size=(t, p)), bool).at[:, 0].set(True)
    scale = 1.0 / np.sqrt(d)
    got = tree_attention(q, kc8, vc8, sk, sv, rows, clens, amask, scale,
                         block_s=16, interpret=True, k_scale=ks, v_scale=vs)
    want = tree_attention(q, kcf, vcf, sk, sv, rows, clens, amask, scale,
                          block_s=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# serve level: flat + Pallas paths, prefill -> decode continuation
# ---------------------------------------------------------------------------
def _teacher_forced_logits(im, tokens, prompt_len):
    """Per-step logits_max for a fixed token sequence: one prefill step for
    the prompt, then single-token decode steps feeding the GIVEN tokens
    (teacher forcing), so fp and int8 runs see identical inputs and the
    comparison isolates cache-representation error from argmax drift."""
    im.reset()
    outs = []
    bc = BatchConfig.build(
        tokens[:prompt_len], [0] * prompt_len, list(range(prompt_len)),
        [prompt_len], max_tokens=im.max_tokens, max_requests=im.max_requests,
    )
    r = im.step(bc)
    outs.append(np.asarray(r.logits_max)[prompt_len - 1])
    for i in range(prompt_len, len(tokens)):
        bc = BatchConfig.build(
            [tokens[i]], [0], [i], [i + 1],
            max_tokens=im.max_tokens, max_requests=im.max_requests,
        )
        r = im.step(bc)
        outs.append(np.asarray(r.logits_max)[0])
    return np.asarray(outs)


def test_kv_int8_flat_matches_fp_within_tolerance():
    im_fp = make_im(max_tokens=16, max_requests=2, max_seq=32,
                    use_pallas=False)
    im_q = make_im(max_tokens=16, max_requests=2, max_seq=32,
                   use_pallas=False, kv_dtype="int8")
    im_q.params = im_fp.params  # same weights
    # the int8 state really is int8 (the capacity savings are real)
    bufs = im_q.state[next(iter(im_q.state))]
    assert bufs["k"].dtype == jnp.int8 and "k_scale" in bufs
    prompt = [3, 11, 25, 40, 7]
    cont = ref_greedy_decode(im_fp.params, TINY, prompt, 6)
    seq = prompt + cont
    a = _teacher_forced_logits(im_fp, seq, len(prompt))
    b = _teacher_forced_logits(im_q, seq, len(prompt))
    np.testing.assert_allclose(b, a, rtol=LOGIT_RTOL, atol=LOGIT_ATOL)


def test_kv_int8_pallas_equals_flat():
    """The fused-dequant Pallas path and the dequantizing gather path see
    the SAME quantized cache, so their generations must agree exactly —
    and both match the fp golden on this config (prefill -> decode through
    the RequestManager, chunked so the tiled prefill path runs)."""
    prompt = [5, 9, 2, 11, 3, 7, 1, 4, 4, 8, 2]  # > max_tokens: chunks
    outs = {}
    for pallas in (False, True):
        im = make_im(max_tokens=8, max_requests=2, max_seq=32,
                     use_pallas=pallas, kv_dtype="int8")
        rm = RequestManager(im, GenerationConfig(max_new_tokens=6))
        outs[pallas] = rm.generate([prompt])[0]
        if pallas:
            want = ref_greedy_decode(im.params, TINY, prompt, 6)
    assert outs[True] == outs[False], (
        f"pallas {outs[True]} != flat {outs[False]}")
    assert outs[True] == want, f"int8 {outs[True]} != fp golden {want}"


def test_kv_int8_decode_scan_matches_stepwise():
    """The on-device decode scan (donated int8 caches + scale buffers)
    produces the same tokens as host-driven steps."""
    im = make_im(max_tokens=4, max_requests=2, max_seq=64,
                 use_pallas=True, kv_dtype="int8")
    prompt = [3, 11, 25, 40, 7]
    rm = RequestManager(im, GenerationConfig(max_new_tokens=1))
    first = rm.generate([prompt], max_new_tokens=1)[0][-1]
    bc = BatchConfig.build(
        [first], [0], [len(prompt)], [len(prompt) + 1],
        max_tokens=4, max_requests=2,
    )
    tokens, live, _ = im.decode_scan(bc, 5)
    got = [first] + [int(t) for t in np.asarray(tokens)[:, 0]]
    want = [first] + ref_greedy_decode(
        im.params, TINY, prompt + [first], 5)
    assert got == want
    assert np.asarray(live)[:, 0].all()


def test_kv_int8_spec_infer_matches_incremental():
    """Tree-verify + commit on int8 committed caches: speculative decoding
    must still exactly reproduce incremental decoding (the spec buffers
    stay fp; accepted KV is quantized at commit by the same quantizer the
    incremental path uses, so the caches agree bit-for-bit)."""
    from flexflow_tpu.serve import ServeModelConfig, SpecInferManager

    tiny_ssm = ServeModelConfig(
        model_type="llama", vocab_size=TINY.vocab_size, hidden_size=16,
        intermediate_size=32, num_hidden_layers=1, num_attention_heads=2,
        num_key_value_heads=2,
    )
    prompts = [[3, 11, 25, 40, 7], [2, 4, 6, 8]]
    incr = make_im(max_tokens=32, max_requests=2, max_seq=64,
                   kv_dtype="int8")
    want = RequestManager(
        incr, GenerationConfig(max_new_tokens=8)).generate(prompts)
    llm = make_im(max_tokens=32, max_requests=2, max_seq=64, max_spec=8,
                  kv_dtype="int8")
    ssm = make_im(max_tokens=32, max_requests=2, max_seq=64, max_spec=8,
                  cfg=tiny_ssm, topk=2, seed=123, kv_dtype="int8")
    sm = SpecInferManager(
        llm, ssm, GenerationConfig(max_new_tokens=8), width=2, depth=2)
    got = sm.generate(prompts)
    assert got == want, f"spec int8 {got} != incr int8 {want}"


# ---------------------------------------------------------------------------
# capacity planning: the full-depth 32-layer config
# ---------------------------------------------------------------------------
def test_capacity_planner_admits_full_depth_int8():
    """plan_memory_bytes admits the FULL 32-layer llama2-7b shape (bs=8,
    ctx=2048) within one v5e chip's 16 GB HBM with int8 weights + int8 KV —
    and rejects it when either half stays bf16 (the arithmetic that makes
    the int8 KV cache the unlock for full-depth serving)."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.core.pcg import PCG
    from flexflow_tpu.parallel.mesh import make_mesh
    from flexflow_tpu.search.simulator import plan_memory_bytes
    from flexflow_tpu.serve import (
        InferenceManager,
        ServeModelConfig,
        annotate_int8,
        build_model,
    )

    hbm = 16e9  # v5e
    cfg = ServeModelConfig(
        model_type="llama", vocab_size=32000, hidden_size=4096,
        intermediate_size=11008, num_hidden_layers=32,
        num_attention_heads=32, num_key_value_heads=32, dtype="bfloat16",
    )
    mesh = make_mesh({"tp": 1}, jax.devices()[:1])
    ff = FFModel(FFConfig(), mesh=mesh)
    logits = build_model(ff, cfg, 8)
    # symbolic only: InferenceManager plans but never allocates here
    im = InferenceManager(
        ff, max_requests=8, max_tokens_per_batch=8, max_seq_len=2048,
        outputs=logits, kv_dtype="int8", use_pallas=False,
    )
    bf16_w = plan_memory_bytes(im.plan, training=False)
    n = annotate_int8(ff.graph)
    assert n >= 32 * 4 + 1  # per-layer linears + attention + lm head
    both_int8 = plan_memory_bytes(im.plan, training=False)
    assert both_int8 < hbm, (
        f"int8+int8 plan {both_int8/1e9:.1f} GB does not fit 16 GB")
    assert bf16_w > hbm, "bf16 weights + int8 KV should NOT fit"
    # int8 weights + bf16 KV also must not fit (KV is the binding half)
    for node in ff.graph.nodes:
        if hasattr(node.op, "kv_dtype"):
            node.op.kv_dtype = None
    int8_w_bf16_kv = plan_memory_bytes(im.plan, training=False)
    assert int8_w_bf16_kv > hbm, "int8 weights + bf16 KV should NOT fit"


def test_state_specs_int8_shapes_and_sharding():
    """The op's state_specs carry the int8 caches + f32 scale buffers,
    sharded over the kv-head dim like the caches they describe."""
    from flexflow_tpu.serve.ops import IncMultiHeadSelfAttention

    op = IncMultiHeadSelfAttention(embed_dim=32, num_q_heads=4,
                                   num_kv_heads=2)
    op.kv_dtype = "int8"
    specs = op.state_specs(2, 48, 0, head_axes=("tp",))
    assert specs["k"][1] == "int8" and specs["v"][1] == "int8"
    assert specs["k_scale"][0] == (3, 2, 48)
    assert specs["k_scale"][1] == "float32"
    # scale sharding follows the cache's head dim
    assert specs["k_scale"][2].dims[1].axes == ("tp",)
    op.kv_dtype = None
    specs = op.state_specs(2, 48, 0)
    assert "k_scale" not in specs and specs["k"][1] != "int8"
