"""Pallas decode-attention kernel: equivalence with the pure-JAX path.

Runs in interpret mode on the CPU test mesh (same kernel logic, no TPU
needed); the compile for a v5e is exercised by
tests/test_tpu_aot_compile.py, the chip by ``chip_smoke.py`` and
``benchmark/run.py``.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.ops.pallas.attention import decode_attention, tree_attention
from flexflow_tpu.serve import GenerationConfig, RequestManager
from flexflow_tpu.serve.ops import alibi_slopes

from test_serve import TINY, make_im, ref_greedy_decode


def ref_attention(q, kc, vc, rows, pos, scale, slopes=None):
    """The gather-based formulation (what serve/ops.py falls back to)."""
    k_tok = kc[rows]  # [T, KV, S, D] (kv-head-major cache)
    v_tok = vc[rows]
    t, kv, s, d = k_tok.shape
    qh = q.shape[1]
    gq = qh // kv
    qr = q.reshape(t, kv, gq, d)
    sc = jnp.einsum("tkgd,tksd->tkgs", qr, k_tok).astype(jnp.float32) * scale
    if slopes is not None:
        rel = (jnp.arange(s)[None, :] - pos[:, None]).astype(jnp.float32)
        sc = sc + slopes.reshape(kv, gq)[None, :, :, None] * rel[:, None, None, :]
    mask = jnp.arange(s)[None, :] <= pos[:, None]
    sc = jnp.where(mask[:, None, None, :], sc, -1e30)
    w = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("tkgs,tksd->tkgd", w, v_tok.astype(w.dtype))
    return out.reshape(t, qh, d)


@pytest.mark.parametrize("qh,kv,d,s,block", [
    (4, 2, 8, 32, 16),    # GQA
    (4, 4, 8, 32, 32),    # MHA, single block
    (8, 1, 16, 64, 16),   # MQA
    (4, 2, 8, 40, 16),    # non-dividing seq len -> padded tail block
])
def test_kernel_matches_reference(qh, kv, d, s, block):
    rng = np.random.default_rng(0)
    t, r = 6, 3
    q = jnp.asarray(rng.normal(size=(t, qh, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(r + 1, kv, s, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(r + 1, kv, s, d)), jnp.float32)
    rows = jnp.asarray([0, 1, 2, 1, 0, 3], jnp.int32)  # 3 = pad scratch row
    pos = jnp.asarray([5, 17, 0, 18, 6, 0], jnp.int32)
    scale = 1.0 / np.sqrt(d)
    got = decode_attention(q, kc, vc, rows, pos, scale,
                           block_s=block, interpret=True)
    want = ref_attention(q, kc, vc, rows, pos, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_kernel_alibi_matches_reference():
    rng = np.random.default_rng(1)
    t, r, qh, kv, d, s = 5, 2, 4, 2, 8, 32
    q = jnp.asarray(rng.normal(size=(t, qh, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(r + 1, kv, s, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(r + 1, kv, s, d)), jnp.float32)
    rows = jnp.asarray([0, 1, 0, 1, 2], jnp.int32)
    pos = jnp.asarray([3, 9, 4, 10, 0], jnp.int32)
    slopes = alibi_slopes(qh)
    got = decode_attention(q, kc, vc, rows, pos, 0.35, slopes=slopes,
                           use_alibi=True, block_s=16, interpret=True)
    want = ref_attention(q, kc, vc, rows, pos, 0.35, slopes=slopes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def ref_tree_attention(q, kc, vc, sk, sv, rows, clens, amask, scale):
    """Gather-based two-segment formulation (serve/ops.py's fallback)."""
    k_tok, v_tok = kc[rows], vc[rows]      # [T, KV, S, D]
    ks_tok, vs_tok = sk[rows], sv[rows]    # [T, KV, P, D]
    t, kv, s, d = k_tok.shape
    qh = q.shape[1]
    gq = qh // kv
    qr = q.reshape(t, kv, gq, d)
    sc_c = jnp.einsum("tkgd,tksd->tkgs", qr, k_tok).astype(jnp.float32) * scale
    sc_p = jnp.einsum("tkgd,tkpd->tkgp", qr, ks_tok).astype(jnp.float32) * scale
    cmask = jnp.arange(s)[None, :] < clens[:, None]
    sc_c = jnp.where(cmask[:, None, None, :], sc_c, -1e30)
    sc_p = jnp.where(amask[:, None, None, :], sc_p, -1e30)
    w = jax.nn.softmax(jnp.concatenate([sc_c, sc_p], -1), axis=-1)
    v_all = jnp.concatenate([v_tok, vs_tok], axis=2).astype(w.dtype)
    out = jnp.einsum("tkgs,tksd->tkgd", w, v_all)
    return out.reshape(t, qh, d)


@pytest.mark.parametrize("qh,kv,d,s,p,block", [
    (4, 2, 8, 32, 8, 16),    # GQA
    (4, 4, 8, 32, 8, 32),    # MHA, single block
    (8, 1, 16, 64, 16, 16),  # MQA, deeper tree buffer
    (4, 2, 8, 40, 8, 16),    # non-dividing seq len -> padded tail block
])
def test_tree_kernel_matches_reference(qh, kv, d, s, p, block):
    rng = np.random.default_rng(2)
    t, r = 7, 3
    q = jnp.asarray(rng.normal(size=(t, qh, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(r + 1, kv, s, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(r + 1, kv, s, d)), jnp.float32)
    sk = jnp.asarray(rng.normal(size=(r + 1, kv, p, d)), jnp.float32)
    sv = jnp.asarray(rng.normal(size=(r + 1, kv, p, d)), jnp.float32)
    rows = jnp.asarray([0, 0, 1, 2, 1, 0, 3], jnp.int32)  # 3 = scratch row
    # mix: mid-cache, empty committed cache (pure tree), full cache
    clens = jnp.asarray([5, 5, 0, s, 0, 17, 0], jnp.int32)
    # random root-path-style masks incl. always-self plus a few ancestors
    amask = rng.random((t, p)) < 0.4
    amask[:, 0] = True
    amask = jnp.asarray(amask)
    scale = 1.0 / np.sqrt(d)
    got = tree_attention(q, kc, vc, sk, sv, rows, clens, amask, scale,
                         block_s=block, interpret=True)
    want = ref_tree_attention(q, kc, vc, sk, sv, rows, clens, amask, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_e2e_spec_infer_with_pallas_kernel():
    # whole SpecInfer stack with the tree kernel on (interpret mode on CPU):
    # outputs must match plain incremental decoding exactly, and the LLM's
    # verify steps must actually take the Pallas path (use_pallas=True).
    from flexflow_tpu.serve import ServeModelConfig, SpecInferManager

    prompts = [[3, 11, 25, 40, 7], [2, 4, 6, 8]]
    im = make_im(max_tokens=32, max_requests=2, max_seq=64)
    want = RequestManager(im, GenerationConfig(max_new_tokens=10)).generate(prompts)

    tiny_ssm = ServeModelConfig(
        model_type="llama", vocab_size=TINY.vocab_size, hidden_size=16,
        intermediate_size=32, num_hidden_layers=1, num_attention_heads=2,
        num_key_value_heads=2,
    )
    llm = make_im(max_tokens=32, max_requests=2, max_seq=64, max_spec=8,
                  use_pallas=True)
    ssm = make_im(max_tokens=32, max_requests=2, max_seq=64, max_spec=8,
                  cfg=tiny_ssm, topk=2, seed=123, use_pallas=True)
    assert llm.use_pallas and ssm.use_pallas
    sm = SpecInferManager(
        llm, ssm, GenerationConfig(max_new_tokens=10), width=2, depth=2
    )
    got = sm.generate(prompts)
    assert got == want


@pytest.mark.parametrize("qh,kv,d,s,p,pb", [
    (4, 2, 8, 32, 4, 8),    # GQA, tree smaller than buffer
    (8, 1, 16, 64, 3, 8),   # MQA, odd tree size
])
def test_batched_tree_kernel_matches_flat(qh, kv, d, s, p, pb):
    from flexflow_tpu.ops.pallas.attention import tree_attention_batched

    rng = np.random.default_rng(5)
    r = 3
    q = jnp.asarray(rng.normal(size=(r, p, qh, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(r + 1, kv, s, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(r + 1, kv, s, d)), jnp.float32)
    sk = jnp.asarray(rng.normal(size=(r + 1, kv, pb, d)), jnp.float32)
    sv = jnp.asarray(rng.normal(size=(r + 1, kv, pb, d)), jnp.float32)
    rows = jnp.asarray([0, 2, 3], jnp.int32)       # incl. scratch row
    clens = jnp.asarray([7, 0, s], jnp.int32)
    amask = rng.random((r, p, pb)) < 0.4
    amask[:, :, 0] = True
    amask = jnp.asarray(amask)
    scale = 1.0 / np.sqrt(d)
    got = tree_attention_batched(q, kc, vc, sk, sv, rows, clens, amask,
                                 scale, block_s=16, interpret=True)
    # flat reference: expand to per-token arrays
    rows_t = jnp.repeat(rows, p)
    clens_t = jnp.repeat(clens, p)
    want = ref_tree_attention(
        q.reshape(r * p, qh, d), kc, vc, sk, sv, rows_t, clens_t,
        amask.reshape(r * p, pb), scale,
    ).reshape(r, p, qh, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_tp_serving_with_pallas_kernel():
    # tensor-parallel serving with the kernels wrapped in shard_map over the
    # kv-head axis: tokens must match the single-device pure-JAX golden.
    im1 = make_im({"tp": 1})
    im2 = make_im({"tp": 2}, use_pallas=True)
    assert im2.use_pallas
    prompt = [3, 11, 25, 40, 7]
    out1 = RequestManager(im1, GenerationConfig(max_new_tokens=8)).generate(
        [prompt])[0]
    out2 = RequestManager(im2, GenerationConfig(max_new_tokens=8)).generate(
        [prompt])[0]
    assert out1 == out2


def test_tp_spec_infer_with_pallas_kernel():
    # TP x speculation: tree-verify kernel under shard_map
    from flexflow_tpu.serve import ServeModelConfig, SpecInferManager

    prompts = [[3, 11, 25, 40, 7], [2, 4, 6, 8]]
    im = make_im(max_tokens=32, max_requests=2, max_seq=64)
    want = RequestManager(im, GenerationConfig(max_new_tokens=8)).generate(prompts)

    tiny_ssm = ServeModelConfig(
        model_type="llama", vocab_size=TINY.vocab_size, hidden_size=16,
        intermediate_size=32, num_hidden_layers=1, num_attention_heads=2,
        num_key_value_heads=2,
    )
    llm = make_im({"tp": 2}, max_tokens=32, max_requests=2, max_seq=64,
                  max_spec=8, use_pallas=True)
    ssm = make_im({"tp": 2}, max_tokens=32, max_requests=2, max_seq=64,
                  max_spec=8, cfg=tiny_ssm, topk=2, seed=123, use_pallas=True)
    sm = SpecInferManager(
        llm, ssm, GenerationConfig(max_new_tokens=8), width=2, depth=2
    )
    assert sm.generate(prompts) == want


def test_e2e_decode_with_pallas_kernel():
    # whole serving stack with the kernel on (interpret mode on CPU):
    # tokens must match the pure-JAX golden exactly.  The flag is init-only
    # (baked into the jitted step), so it is passed at construction.
    from test_serve import FFConfig, FFModel, InferenceManager, build_model
    from flexflow_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"tp": 1}, jax.devices()[:1])
    ff = FFModel(FFConfig(), mesh=mesh)
    build_model(ff, TINY, 16)
    im = InferenceManager(
        ff, max_requests=2, max_tokens_per_batch=16, max_seq_len=32,
        use_pallas=True,
    )
    im.init_operators_inference(rng=jax.random.PRNGKey(7))
    assert im.use_pallas
    rm = RequestManager(im, GenerationConfig(max_new_tokens=8))
    prompt = [3, 11, 25, 40, 7]
    got = rm.generate([prompt], max_new_tokens=8)[0]
    want = ref_greedy_decode(im.params, TINY, prompt, 8)
    assert got == want


# ---- the prefill chunk's block write (kv_block_write) -------------------
def _bits(a):
    return np.asarray(a).view(
        {1: np.uint8, 2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def _chain_reference(kc, vc, k, v, rows, start, count, tile):
    """The chain ``kv_block_write`` replaced, as ``ops._prefill_attend`` and
    ``hybrid_ops._put_blocks`` held it: the chunk re-laid out head-major, cast
    to the cache's type, tail pads zeroed, one ``dynamic_update_slice`` per
    tile and cache."""
    g = k.shape[0] // tile
    valid = (jnp.arange(tile)[None, :] < count[:, None]).reshape(
        g, 1, tile, 1)
    block = lambda a, dt: jnp.where(
        valid, a.reshape((g, tile) + a.shape[1:]).transpose(0, 2, 1, 3)
        .astype(dt), 0)
    kb, vb = block(k, kc.dtype), block(v, vc.dtype)
    zero = jnp.int32(0)
    for i in range(g):
        at = (rows[i], zero, start[i], zero)
        kc = jax.lax.dynamic_update_slice(kc, kb[i][None], at)
        vc = jax.lax.dynamic_update_slice(vc, vb[i][None], at)
    return kc, vc


def _block_write_case(kv, d, tile, cache_dt, fresh_dt, slots=3, seed=0):
    """Caches that hold something everywhere, and a chunk of four tiles: two
    of one request (the second tail-padded), one of another request, one
    fully pad (scratch row, count 0)."""
    rng = np.random.default_rng(seed)
    s = 4 * tile

    def draw(shape, dt):
        if jnp.dtype(dt) == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)
        return jnp.asarray(rng.normal(size=shape), dt)

    kc, vc = (draw((slots + 1, kv, s, d), cache_dt) for _ in range(2))
    k, v = (draw((4 * tile, kv, d), fresh_dt) for _ in range(2))
    rows = jnp.asarray([1, 1, 0, slots], jnp.int32)
    start = jnp.asarray([tile, 2 * tile, 3 * tile, 0], jnp.int32)
    count = jnp.asarray([tile, tile - 5, tile, 0], jnp.int32)
    return kc, vc, k, v, rows, start, count


@pytest.mark.parametrize("kv,d,tile,cache_dt,fresh_dt", [
    (32, 128, 128, jnp.bfloat16, jnp.bfloat16),   # OPT-6.7B
    (1, 128, 128, jnp.bfloat16, jnp.bfloat16),    # StarCoderBase (MQA)
    (10, 128, 128, jnp.bfloat16, jnp.bfloat16),   # phi-4-mini-flash: pairs
    (32, 128, 128, jnp.int8, jnp.int8),           # int8 values
    (2, 128, 32, jnp.bfloat16, jnp.float32),      # the cast to the cache's
    (2, 16, 8, jnp.float32, jnp.float32),         # the CPU tests' toy widths
], ids=["opt", "starcoder_mqa", "phi4_pairs", "int8", "cast", "toy"])
def test_block_write_equals_the_chain(kv, d, tile, cache_dt, fresh_dt):
    """ONE aliased call writes what the chain of per-tile
    dynamic-update-slices wrote, bit for bit: same values, same zeros for
    tail pads, same positions, the rest of both caches as it was."""
    from flexflow_tpu.ops.pallas.attention import kv_block_write

    kc, vc, k, v, rows, start, count = _block_write_case(
        kv, d, tile, cache_dt, fresh_dt)
    want_k, want_v = _chain_reference(kc, vc, k, v, rows, start, count, tile)
    got_k, got_v = kv_block_write(kc, vc, k, v, rows, start, count,
                                  tile=tile, interpret=True)
    assert got_k.dtype == kc.dtype and got_v.dtype == vc.dtype
    np.testing.assert_array_equal(_bits(got_k), _bits(want_k))
    np.testing.assert_array_equal(_bits(got_v), _bits(want_v))
    # the write landed: the tail-padded tile holds its rows, then zeros
    tail = np.asarray(got_k[1, :, 2 * tile:3 * tile].astype(jnp.float32))
    assert np.any(tail[:, :tile - 5] != 0) and not np.any(tail[:, tile - 5:])
    assert not np.any(np.asarray(got_v[-1, :, :tile].astype(jnp.float32)))


def test_block_write_lowers_to_one_call_that_aliases_both_caches():
    """Lowered for the TPU (no chip needed to LOWER): one Mosaic call whose
    two outputs are the two cache operands — XLA updates the caches where
    they lie; a cache-sized copy would be an operand that is not aliased."""
    import functools
    import re

    from flexflow_tpu.ops.pallas.attention import kv_block_write

    kc, vc, k, v, rows, start, count = (
        jax.ShapeDtypeStruct(a.shape, a.dtype) for a in _block_write_case(
            32, 128, 128, jnp.bfloat16, jnp.bfloat16))
    text = jax.jit(functools.partial(kv_block_write, tile=128)).trace(
        kc, vc, k, v, rows, start, count).lower(
        lowering_platforms=("tpu",)).as_text()
    calls = re.findall(r"stablehlo\.custom_call @tpu_custom_call.*", text)
    assert len(calls) == 1
    aliases = re.findall(
        r"output_tuple_indices = \[(\d*)\], operand_index = (\d+)", calls[0])
    # operands: rows, blocks, counts, k, v, K cache, V cache
    assert sorted(aliases) == [("0", "5"), ("1", "6")], calls[0][:400]
    assert "dynamic_update_slice" not in text


@pytest.mark.parametrize("why,extras,d,aligned,want", [
    ("kernels_on", {"pallas_decode": True, "pallas_interpret": True}, 16,
     True, "pallas"),
    ("kernels_off", {}, 128, True, "dus_chain"),
    ("head_not_whole_lanes", {"pallas_decode": True}, 64, True, "dus_chain"),
    ("starts_not_whole_tiles",
     {"pallas_decode": True, "pallas_interpret": True}, 16, False,
     "dus_chain"),
])
def test_block_write_path_is_chosen_by_what_is_observed(why, extras, d,
                                                        aligned, want):
    """``put_blocks`` takes the kernel where the kernels are on and the
    shapes and starts suit it, the chain otherwise — same caches either way — and
    says which in ``attention_paths``."""
    from flexflow_tpu.serve.ops import put_blocks

    kc, vc, k, v, rows, start, count = _block_write_case(
        2, d, 8, jnp.float32, jnp.float32)
    paths = {}
    got = put_blocks(kc, vc, k, v, rows, start, count, 8,
                     dict(extras, attention_paths=paths), aligned=aligned)
    assert paths == {("kv_block_write", "PrefillBatchConfig"): want}
    for a, b in zip(got, _chain_reference(kc, vc, k, v, rows, start, count,
                                          8)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("use_pallas", [True, False], ids=["pallas", "xla"])
def test_tiled_prefill_counts_its_block_write(use_pallas):
    """A tiled prompt chunk through the manager: with the kernels on the
    program's block write is the Pallas call and is counted as such; without
    them the chunk is a flat batch and writes no block at all."""
    from flexflow_tpu.serve.batch_config import PrefillBatchConfig

    im = make_im(max_tokens=8, max_requests=2, max_seq=32,
                 use_pallas=use_pallas)
    pbc, _ = PrefillBatchConfig.build(
        [(0, [5, 9, 2, 11, 3], 0)], [5], max(im.prefill_tile, 4),
        max_tokens=8, max_requests=2)
    im.step(pbc)
    assert im.attention_paths.get(
        ("kv_block_write", "PrefillBatchConfig")) == (
        "pallas" if use_pallas else None)


# ---- the decode scan's row write (kv_row_write) --------------------------
def _row_write_case(kv, d, cache_dt, fresh_dt, s=64, slots=8, seed=0,
                    pads="together"):
    """Caches that hold something everywhere and a decode step's rows: live
    slots once each — at a cache's first and last group of positions, at a
    group's first and last place, at a ring position ``p % ring``, at
    coordinates out of range (clamped) — and pads on the scratch row, some
    on one position."""
    rng = np.random.default_rng(seed)

    def draw(shape, dt):
        if jnp.dtype(dt) == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)
        return jnp.asarray(rng.normal(size=shape), dt)

    kc, vc = (draw((slots + 1, kv, s, d), cache_dt) for _ in range(2))
    g = 32 // jnp.dtype(cache_dt).itemsize
    live = [(1, 0), (3, g - 1), (7, s - g), (5, s - 1), (2, (s + g + 3) % s),
            (4, s + 10), (-2, g + 1), (6, -3)]
    scratch = [(slots, 5), (slots + 3, 7), (slots, 5)]
    if pads == "together":
        cells = live + scratch
    else:   # as a row that stopped mid-batch leaves them
        cells = scratch[:1] + live[:4] + scratch[1:2] + live[4:] + scratch[2:]
    rows, pos = (jnp.asarray(c, jnp.int32) for c in zip(*cells))
    k, v = (draw((len(cells), kv, d), fresh_dt) for _ in range(2))
    return kc, vc, k, v, rows, pos


def _row_chain(kc, vc, k, v, rows, pos):
    """The chain ``kv_row_write`` replaced: ``_scatter_rows_pos`` on each
    cache (cast, clamp, one ``dynamic_update_slice`` a row)."""
    from flexflow_tpu.serve.ops import IncMultiHeadSelfAttention as Attn

    return (Attn._scatter_rows_pos(kc, rows, pos, k),
            Attn._scatter_rows_pos(vc, rows, pos, v))


_ROW_WRITE_CASES = {
    # kv heads, head, the cache's type, the fresh rows'
    "command_ring_mqa": (1, 128, jnp.bfloat16, jnp.bfloat16),
    "sala_kv2": (2, 128, jnp.bfloat16, jnp.bfloat16),
    "heads8_f32": (8, 128, jnp.float32, jnp.float32),
    "opt_32": (32, 128, jnp.bfloat16, jnp.bfloat16),
    "latent_512": (1, 512, jnp.bfloat16, jnp.bfloat16),
    "cast_down": (2, 128, jnp.bfloat16, jnp.float32),
    "cast_up": (8, 128, jnp.float32, jnp.bfloat16),
    "int8": (32, 128, jnp.int8, jnp.int8),
    "toy": (2, 16, jnp.float32, jnp.float32),
}


@pytest.mark.parametrize("case", list(_ROW_WRITE_CASES))
def test_row_write_equals_the_chain(case):
    """ONE aliased call writes what the chain of one-row
    dynamic-update-slices wrote, bit for bit: same values, same cast, same
    clamped coordinates, every untouched position of both caches as it was
    — the scratch row too, where the pads are next to each other."""
    from flexflow_tpu.ops.pallas.attention import kv_row_write

    kc, vc, k, v, rows, pos = _row_write_case(*_ROW_WRITE_CASES[case])
    want_k, want_v = _row_chain(kc, vc, k, v, rows, pos)
    got_k, got_v = kv_row_write(kc, vc, k, v, rows, pos, interpret=True)
    assert got_k.dtype == kc.dtype and got_v.dtype == vc.dtype
    np.testing.assert_array_equal(_bits(got_k), _bits(want_k))
    np.testing.assert_array_equal(_bits(got_v), _bits(want_v))
    # the write landed, and moved something
    assert (_bits(got_k) != _bits(kc)).any(axis=(1, 3)).sum() == 10
    np.testing.assert_array_equal(
        _bits(got_v[5, :, -1]), _bits(v[3].astype(vc.dtype)))


@pytest.mark.parametrize("case", ["command_ring_mqa", "heads8_f32",
                                  "opt_32"])
def test_row_write_tolerates_pads_apart_on_the_scratch_row(case):
    """Pads between live rows (a request that ended mid-batch): every row
    but the scratch row is the chain's, bit for bit, and so is every
    position of the scratch row that no pad wrote; a pad's own position
    holds what it, another pad or nobody wrote there."""
    from flexflow_tpu.ops.pallas.attention import kv_row_write

    kc, vc, k, v, rows, pos = _row_write_case(*_ROW_WRITE_CASES[case],
                                              pads="apart")
    want = _row_chain(kc, vc, k, v, rows, pos)
    got = kv_row_write(kc, vc, k, v, rows, pos, interpret=True)
    for g, w, c, x in zip(got, want, (kc, vc), (k, v)):
        np.testing.assert_array_equal(_bits(g[:-1]), _bits(w[:-1]))
        quiet = np.setdiff1d(np.arange(c.shape[2]), [5, 7])
        np.testing.assert_array_equal(_bits(g[-1][:, quiet]),
                                      _bits(c[-1][:, quiet]))
        for p, writers in ((5, (0, 10)), (7, (5,))):
            held = [_bits(c[-1, :, p])] + [
                _bits(x[i].astype(c.dtype)) for i in writers]
            assert any((_bits(g[-1, :, p]) == h).all() for h in held)


def test_row_write_of_one_plane():
    """A cache with no second plane of its shape (the latent plane beside
    its narrower rotated part): ``v_cache=None`` writes the one."""
    from flexflow_tpu.ops.pallas.attention import kv_row_write

    kc, vc, k, v, rows, pos = _row_write_case(1, 512, jnp.bfloat16,
                                              jnp.bfloat16)
    got = kv_row_write(kc, None, k, None, rows, pos, interpret=True)
    np.testing.assert_array_equal(
        _bits(got), _bits(_row_chain(kc, vc, k, v, rows, pos)[0]))


def test_row_write_refuses_caches_it_cannot_tile():
    from flexflow_tpu.ops.pallas.attention import kv_row_write

    kc, vc, k, v, rows, pos = _row_write_case(2, 16, jnp.float32,
                                              jnp.float32, s=20)
    with pytest.raises(ValueError, match="whole groups"):
        kv_row_write(kc, vc, k, v, rows, pos, interpret=True)
    with pytest.raises(ValueError, match="one shape and type"):
        kv_row_write(kc[:, :, :16], vc[:, :, :8], k, v, rows, pos,
                     interpret=True)


def test_row_write_lowers_to_one_call_that_aliases_both_caches():
    """Lowered for the TPU (no chip needed to LOWER): one Mosaic call whose
    two outputs are the two cache operands, and no update-slice."""
    import re

    from flexflow_tpu.ops.pallas.attention import kv_row_write

    kc, vc, k, v, rows, pos = (
        jax.ShapeDtypeStruct(a.shape, a.dtype) for a in _row_write_case(
            32, 128, jnp.bfloat16, jnp.bfloat16))
    text = jax.jit(kv_row_write).trace(kc, vc, k, v, rows, pos).lower(
        lowering_platforms=("tpu",)).as_text()
    calls = re.findall(r"stablehlo\.custom_call @tpu_custom_call.*", text)
    assert len(calls) == 1
    aliases = re.findall(
        r"output_tuple_indices = \[(\d*)\], operand_index = (\d+)", calls[0])
    # operands: rows, positions, k, v, K cache, V cache
    assert sorted(aliases) == [("0", "4"), ("1", "5")], calls[0][:400]
    assert "dynamic_update_slice" not in text


_SCAN = {"pallas_decode": True, "pallas_interpret": True,
         "one_row_per_request": True}


@pytest.mark.parametrize("why,extras,shape,want", [
    ("scan_kernels_on", _SCAN, (2, 16, 64), "pallas"),
    ("scan_kernels_off", {"one_row_per_request": True}, (2, 128, 64),
     "dus_chain"),
    ("flat_step", {"pallas_decode": True, "pallas_interpret": True},
     (2, 16, 64), None),
    ("head_not_whole_lanes", dict(_SCAN, pallas_interpret=False),
     (2, 64, 64), "dus_chain"),
    ("seq_not_whole_groups", _SCAN, (2, 16, 20), "dus_chain"),
])
def test_row_write_path_is_chosen_by_what_is_observed(why, extras, shape,
                                                      want):
    """``put_rows`` takes the kernel inside the decode scan where the
    kernels are on and the planes suit it, the chain otherwise — same caches
    either way — and says which in ``attention_paths``; outside the scan it
    records nothing and keeps the chain."""
    from flexflow_tpu.serve.ops import put_rows

    kv, d, s = shape
    kc, vc, k, v, rows, pos = _row_write_case(kv, d, jnp.float32,
                                              jnp.float32, s=s)
    pos = pos % s
    paths = {}
    got = put_rows(kc, vc, k, v, rows, pos,
                   dict(extras, attention_paths=paths))
    assert paths == ({("kv_row_write", "one_row_per_request"): want}
                     if want else {})
    for a, b in zip(got, _row_chain(kc, vc, k, v, rows, pos)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_row_write_takes_each_plane_by_itself_where_they_differ():
    """A latent cache's two planes: the wide one by the kernel, the 64-wide
    rotated part (narrower than the lanes) by the chain, a key a plane."""
    from unittest import mock

    from flexflow_tpu.ops.pallas import attention
    from flexflow_tpu.ops.pallas.attention import kv_row_write
    from flexflow_tpu.serve.ops import put_rows

    kc, _, k, _, rows, pos = _row_write_case(1, 512, jnp.bfloat16,
                                             jnp.bfloat16)
    _, vc, _, v, _, _ = _row_write_case(1, 64, jnp.bfloat16, jnp.bfloat16)
    paths = {}
    # the chip's choice (the interpreter has no lanes), run by the
    # interpreter
    with mock.patch.object(
            attention, "kv_row_write",
            lambda *a, interpret: kv_row_write(*a, interpret=True)):
        got = put_rows(kc, vc, k, v, rows, pos,
                       dict(_SCAN, pallas_interpret=False,
                            attention_paths=paths))
    assert paths == {
        ("kv_row_write", ("one_row_per_request", 512)): "pallas",
        ("kv_row_write", ("one_row_per_request", 64)): "dus_chain"}
    for a, b in zip(got, _row_chain(kc, vc, k, v, rows, pos)):
        np.testing.assert_array_equal(_bits(a), _bits(b))


@pytest.mark.parametrize("kw", [
    dict(),
    dict(kv_dtype="int8", max_seq=64),      # the scale planes keep the chain
    dict(kv_page_size=16, max_seq=64),      # physical (row, position)
    dict(mesh_axes={"tp": 2}),              # under the head-axis shard_map
], ids=["plain", "int8", "paged", "tp2"])
def test_row_write_kernel_on_and_off_serves_the_same(kw, monkeypatch,
                                                     row_write_on_and_off):
    """``IncMultiHeadSelfAttention._write_kv`` (OPT, StarCoder, the GQA layer
    of a hybrid): the decode scan's rows by ``kv_row_write`` and by the
    chain — the same tokens, the same caches."""
    import test_serve

    def make():
        monkeypatch.setattr(test_serve, "_IM_CACHE", {})
        return make_im(use_pallas=True, **kw)

    row_write_on_and_off(make, [[3, 11, 25, 40, 7], [9, 2]])


def test_a_flat_step_asks_for_no_row_write(monkeypatch):
    """Outside the decode scan a call may hold several positions of one row
    (a prompt's): ``put_rows`` keeps the chain there and records nothing."""
    import test_serve
    from flexflow_tpu.serve.batch_config import BatchConfig

    monkeypatch.setattr(test_serve, "_IM_CACHE", {})    # one no scan ran on
    im = make_im(max_tokens=8, max_requests=2, max_seq=32, use_pallas=True)
    im.step(BatchConfig.build([5, 9, 2], [0, 0, 0], [0, 1, 2], [3, 0],
                              max_tokens=8, max_requests=2))
    assert "kv_row_write" not in {k for k, _ in im.attention_paths}


# ---- the seq block a grid step copies, planned by bytes (PR 51) ------------
_KB = 2 ** 10


@pytest.mark.parametrize("why,shape,kw,want", [
    # (KV heads, head, itemsize, int8 scales, cache seq) -> block.
    # Wide layers: the block by positions the kernel always had, to the digit
    ("opt_bf16", (32, 128, 2, False, 2048), {}, 256),
    ("opt_int8", (32, 128, 1, True, 2048), {}, 256),
    ("opt_paged_512", (32, 128, 2, False, 2048), dict(page_size=512), 256),
    ("evabyte_compacted", (32, 128, 2, False, 4096), {}, 256),
    ("phi4flash_full", (10, 128, 2, False, 8192), {}, 512),
    ("phi4flash_ring", (10, 128, 2, False, 1024), dict(window=512), 256),
    # ONE K/V head of 128 in bf16 is 256 bytes of K a position: 2048 of them
    # a copy; a ring is one block
    ("command_a_full", (1, 128, 2, False, 18432), {}, 2048),
    ("command_a_ring", (1, 128, 2, False, 4608), dict(window=4096), 4608),
    ("starcoder_mqa", (1, 128, 2, False, 8192), {}, 2048),
    ("nemotron_two_heads", (2, 128, 2, False, 8192), {}, 1024),
    ("narrow_paged", (1, 128, 2, False, 8192), dict(page_size=1024), 1024),
    ("narrow_odd_length", (1, 128, 2, False, 4608), {}, 1536),
    # a block the caller names is kept by positions, never grown
    ("named_block", (1, 128, 2, False, 8192), dict(block_s=512), 512),
    ("named_block_ring", (1, 128, 2, False, 4608),
     dict(window=4096, block_s=512), 512),
])
def test_decode_plan_follows_the_bytes_of_a_position(why, shape, kw, want):
    from flexflow_tpu.ops.pallas.attention import (_COPY_TARGET_BYTES,
                                                   _VMEM_BUDGET,
                                                   _decode_plan,
                                                   _fit_block_s)

    num_kv, d, itemsize, kv_quant, s_len = shape
    block = _decode_plan(*shape, **kw)
    assert block == want, why
    span = kw.get("page_size") or s_len
    assert span % block == 0
    pos_bytes = 2 * num_kv * d * itemsize + (8 * num_kv if kv_quant else 0)
    assert 2 * block * pos_bytes <= _VMEM_BUDGET
    window = kw.get("window", 0)
    today = _fit_block_s(min(512, max(256, window // 8)) if window else 512,
                         s_len, num_kv, d, itemsize, kv_quant, _VMEM_BUDGET)
    if today * num_kv * d * itemsize >= _COPY_TARGET_BYTES or "block_s" in kw:
        # the parent's block where its copy already was large
        assert block == math.gcd(today, span)
    else:
        assert block > today
        assert window or block * num_kv * d * itemsize <= _COPY_TARGET_BYTES


def _decode_with_block(monkeypatch, block, *args, **kw):
    """``decode_attention`` (interpreted) under a forced seq block — the
    undecorated function, so that no compiled program of another block is
    found again; ``block`` None is the plan the kernel makes."""
    from flexflow_tpu.ops.pallas import attention

    if block is not None:
        monkeypatch.setattr(attention, "_decode_plan", lambda *a, **k: block)
    return attention.decode_attention.__wrapped__(*args, interpret=True, **kw)


@pytest.mark.parametrize("kv,s_len,forced", [
    (1, 6144, None),            # the plan as made: blocks of 2048
    (1, 6144, 1024),
    (1, 6144, 3072),
    (2, 4096, None),            # two K/V heads: blocks of 1024
    (2, 4096, 2048),
])
def test_a_narrow_full_cache_is_read_in_blocks_grown_by_bytes(
        monkeypatch, kv, s_len, forced):
    """16 query heads on one or two K/V heads of 128, a bf16 cache: rows
    whose frontier falls in the first, a middle and the last quarter of a
    copied block, on the last position of a block and on the first of the
    next, at 0 and at the cache's end, and a pad row — against the gathered
    reference.  What lies past a row's frontier is NaN: a block that is
    skipped never reads it, one that is masked must not let it through."""
    from flexflow_tpu.ops.pallas.attention import _decode_plan

    block = forced or _decode_plan(kv, 128, 2, False, s_len)
    assert s_len // block >= 2 and (forced is not None or block > 512)
    part = block // 4
    rng = np.random.default_rng([51, kv, block])
    gq, d = 16 // kv, 128
    pos = np.asarray(
        [0, part // 2, part - 1, part, block - part + 7, block - 1, block,
         block + part + part // 3, 2 * block - 1, s_len - part - 1,
         s_len - 1, 0], np.int32)
    t = len(pos)
    rows = np.arange(t, dtype=np.int32)
    rows[-1] = t                                    # the pad: the scratch row
    kc = rng.normal(size=(t + 1, kv, s_len, d)).astype(np.float32)
    vc = rng.normal(size=(t + 1, kv, s_len, d)).astype(np.float32)
    kc, vc = (jnp.asarray(a, jnp.bfloat16) for a in (kc, vc))
    q = jnp.asarray(rng.normal(size=(t, kv * gq, d)), jnp.float32)
    want = ref_attention(q, kc.astype(jnp.float32), vc.astype(jnp.float32),
                         jnp.asarray(rows), jnp.asarray(pos), 0.09)
    # past the frontier: never seen.  NaN only where a whole block is past
    # it (0 * NaN of a MASKED position would poison a sum; those of the
    # frontier's own block stay finite, as every cache holds them); the
    # scratch row stays whole
    dead = np.arange(s_len)[None, :] >= (pos[:, None] // block + 1) * block
    kc_h, vc_h = (jnp.concatenate([
        jnp.where(dead[:, None, :, None], jnp.nan, a[:t]).astype(a.dtype),
        a[t:]]) for a in (kc, vc))
    got = np.asarray(_decode_with_block(
        monkeypatch, forced, q, kc_h, vc_h, jnp.asarray(rows),
        jnp.asarray(pos), 0.09))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5, rtol=2e-5)


# ---- a latent cache: decode_attention(v_cache=None) -------------------------
def ref_latent_attention(q, ckv, rows, pos, scale, q_rope=None, k_rope=None):
    """One latent a position, key AND value of every head, by gather."""
    f32 = jnp.float32
    c = ckv[rows, 0].astype(f32)                            # [T, S, D]
    sc = jnp.einsum("thd,tsd->ths", q.astype(f32), c)
    if k_rope is not None:
        sc = sc + jnp.einsum("thr,tsr->ths", q_rope.astype(f32),
                             k_rope[rows, 0].astype(f32))
    seen = jnp.arange(c.shape[1])[None, :] <= pos[:, None]
    w = jax.nn.softmax(jnp.where(seen[:, None], sc * scale, -1e30), axis=-1)
    return jnp.einsum("ths,tsd->thd", w, c)


def _latent_case(s_len, t, seed=0, slots=3, h=8, d=128, dr=64,
                 dt=jnp.float32):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), dt)
    return (draw(t, h, d), draw(slots + 1, 1, s_len, d), draw(t, h, dr),
            draw(slots + 1, 1, s_len, dr))


@pytest.mark.parametrize("s_len,rows,pos", [
    (2048, [0, 1, 2], [2047, 1030, 5]),      # one span, two blocks
    (1536, [2, 3, 0, 3], [1535, 0, 511, 0]),  # pads; three blocks of 512
    (4096, [1], [2048]),                     # one row, its span's first block
    (4096, [0, 1, 2, 0], [4095, 0, 2047, 1024]),  # two spans, unlike lengths
    (384, [0, 1], [383, 128]),               # a cache of three pieces
], ids=["two_blocks", "pads", "one_row", "two_spans", "three_pieces"])
@pytest.mark.parametrize("rope", [True, False], ids=["rope", "bare"])
def test_latent_kernel_matches_reference(s_len, rows, pos, rope):
    q, ckv, q_r, kpe = _latent_case(s_len, len(rows), seed=s_len)
    rows, pos = jnp.asarray(rows, jnp.int32), jnp.asarray(pos, jnp.int32)
    second = dict(q_rope=q_r, k_rope=kpe) if rope else {}
    got = decode_attention(q, ckv, None, rows, pos, 0.09, interpret=True,
                           **second)
    want = ref_latent_attention(q, ckv, rows, pos, 0.09, **second)
    assert got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("s_len,want", [
    (15360, (1024, 3072)),   # deepseek-v2-lite-d5.doc-decode
    (4096, (1024, 2048)),    # the largest multiple of the block that divides
    (1024, (1024, 1024)),
    (1536, (512, 1536)),     # the block divides the cache
    (384, (128, 384)),       # a short cache: a block of whole pieces
    (200, None),             # no whole piece: refused
])
def test_latent_plan_divides_the_cache(s_len, want):
    from flexflow_tpu.ops.pallas.attention import (_latent_plan,
                                                   decode_block_plan)
    cache = jax.ShapeDtypeStruct((3, 1, s_len, 512), jnp.bfloat16)
    if want is None:
        with pytest.raises(ValueError, match="whole pieces"):
            _latent_plan(s_len)
        return
    block, span = _latent_plan(s_len)
    assert (block, span) == want
    assert s_len % span == 0 and span % block == 0 and block % 128 == 0
    # the counter names the kernel and its block; a K/V cache keeps its plan
    assert decode_block_plan(cache, latent=True) == f"live{block}"
    assert decode_block_plan(cache).startswith("full")


@pytest.mark.parametrize("kw", [
    dict(window=64), dict(use_alibi=True), dict(page_size=128, paged=True),
    dict(quant=True), dict(two_heads=True), dict(rope_on_kv=True),
], ids=lambda kw: next(iter(kw)))
def test_a_latent_cache_refuses_what_it_lacks(kw):
    """A latent cache is slot-contiguous, fp, full-length, one cached head,
    no positional bias; and the second key plane is a latent cache's alone."""
    q, ckv, q_r, kpe = _latent_case(256, 2)
    rows, pos = jnp.arange(2, dtype=jnp.int32), jnp.asarray([9, 200], jnp.int32)
    value, more = None, dict(q_rope=q_r, k_rope=kpe)
    if kw.pop("paged", False):
        more["page_table"] = jnp.zeros((4, 2), jnp.int32)
    if kw.pop("quant", False):
        more["k_scale"] = more["v_scale"] = jnp.ones((4, 1, 256))
    if kw.pop("two_heads", False):
        ckv = jnp.concatenate([ckv, ckv], axis=1)
    if kw.pop("rope_on_kv", False):
        value = ckv
    with pytest.raises(ValueError, match="latent cache"):
        decode_attention(q, ckv, value, rows, pos, 0.1, interpret=True,
                         **kw, **more)
