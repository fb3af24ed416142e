"""Q-tiled Pallas prefill kernel: equality with the gather path + the
PrefillBatchConfig tiling contract.

Strategy mirrors test_pallas_attention.py: interpret mode on the CPU test
mesh for kernel logic; the compile for a v5e is exercised by
tests/test_tpu_aot_compile.py, the chip by ``chip_smoke.py`` and
``benchmark/run.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.ops.pallas.attention import prefill_attention
from flexflow_tpu.serve import (
    GenerationConfig,
    RequestManager,
    RequestStatus,
)
from flexflow_tpu.serve.batch_config import BatchConfig, PrefillBatchConfig

import prefill_kernel_forms as forms
from test_pallas_attention import ref_attention
from test_serve import TINY, make_im, ref_greedy_decode


# first positions that put a tile's diagonal AT 0, on a block's last position
# (the block is seen whole), on a block's first, inside a block, and at the
# cache's last tile — blocks of 32 in a cache of 128
_DIAGONALS = (0, 31, 32, 40, 112)


@pytest.mark.parametrize("qh,kv,d,s,bq,block,kv_chunk,dtype,pstart", [
    (4, 2, 8, 64, 8, 16, None, "float32", None),    # GQA, multi-tile
    (4, 4, 8, 32, 4, 32, None, "float32", None),    # MHA, single seq block
    (8, 1, 16, 64, 16, 16, None, "float32", None),  # MQA, whole-chunk tile
    (4, 2, 8, 40, 4, 16, None, "float32", None),    # gcd'd seq block
    (4, 4, 8, 64, 8, 16, 2, "float32", None),       # KV-HEAD-CHUNKED grid
    (4, 2, 8, 64, 8, 16, 1, "float32", None),       # one head per grid step
    # a bf16 cache (bf16 operands into both contractions) against float32
    # arithmetic on the same values: MHA and MQA, the head chunk planned
    # and forced
    (8, 8, 16, 128, 16, 32, None, "bfloat16", _DIAGONALS),
    (8, 8, 16, 128, 16, 32, 4, "bfloat16", _DIAGONALS),
    (8, 1, 16, 128, 16, 32, None, "bfloat16", _DIAGONALS),
    (8, 1, 16, 128, 16, 32, 1, "bfloat16", _DIAGONALS),
    (4, 2, 8, 128, 16, 32, None, "float32", _DIAGONALS),
])
def test_prefill_kernel_matches_reference(qh, kv, d, s, bq, block, kv_chunk,
                                          dtype, pstart):
    """Per-slot equality vs the gather formulation, pads included: the
    kernel reconstructs every slot's position as pstart + b, so comparing
    against ref_attention at those same positions checks all rows."""
    rng = np.random.default_rng(0)
    pstart = jnp.asarray(pstart or (5, 0, s - bq), jnp.int32)
    g = pstart.shape[0]
    t = g * bq
    q = jnp.asarray(rng.normal(size=(g, bq, qh, d)), dtype)
    kc = jnp.asarray(rng.normal(size=(4, kv, s, d)), dtype)
    vc = jnp.asarray(rng.normal(size=(4, kv, s, d)), dtype)
    rows = jnp.asarray([0, 2, 1, 3, 0][:g], jnp.int32)
    scale = 1.0 / np.sqrt(d)
    got = prefill_attention(q, kc, vc, rows, pstart, scale,
                            block_s=block, kv_chunk=kv_chunk, interpret=True)
    assert got.dtype == q.dtype
    flat_rows = jnp.repeat(rows, bq)
    flat_pos = (pstart[:, None] + jnp.arange(bq)[None, :]).reshape(-1)
    flat_pos = jnp.clip(flat_pos, 0, s - 1)
    f32 = lambda x: x.astype(jnp.float32)
    want = ref_attention(f32(q).reshape(t, qh, d), f32(kc), f32(vc),
                         flat_rows, flat_pos, scale)
    # bf16: ``p`` and the output round to 8 bits of mantissa
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(
        np.asarray(f32(got)).reshape(t, qh, d), np.asarray(want),
        atol=tol, rtol=tol,
    )


@pytest.mark.parametrize("dtype,form,kv,kv_chunk,window", [
    # a float32 cache: the PARENT's arithmetic (float32 operands, the mask
    # in every block), to the bit
    ("float32", forms.PARENT, 8, None, 0),
    ("float32", forms.PARENT, 8, 2, 0),
    ("float32", forms.PARENT, 1, None, 0),
    ("float32", forms.PARENT, 1, None, 48),
    # a bf16 cache: skipping the mask where a block is seen whole changes
    # no bit of the bf16-operand arithmetic
    ("bfloat16", forms.NATIVE_MASKED, 8, None, 0),
    ("bfloat16", forms.NATIVE_MASKED, 1, None, 0),
])
def test_prefill_kernel_equals_the_masked_everywhere_form_to_the_bit(
        dtype, form, kv, kv_chunk, window):
    rng = np.random.default_rng(3)
    qh, d, s, bq, block = 8, 16, 128, 16, 32
    pstart = jnp.asarray(_DIAGONALS, jnp.int32)
    g = pstart.shape[0]
    q = jnp.asarray(rng.normal(size=(g, bq, qh, d)), dtype)
    kc = jnp.asarray(rng.normal(size=(4, kv, s, d)), dtype)
    vc = jnp.asarray(rng.normal(size=(4, kv, s, d)), dtype)
    rows = jnp.asarray([0, 2, 1, 3, 0], jnp.int32)
    kw = dict(scale=1.0 / np.sqrt(d), block_s=block, kv_chunk=kv_chunk,
              interpret=True, window=window)
    got = prefill_attention(q, kc, vc, rows, pstart, **kw)
    want = forms.prefill_attention_with(form, q, kc, vc, rows, pstart, **kw)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def _dot_generals(jaxpr):
    """Every ``dot_general`` equation of ``jaxpr``, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _dot_generals(sub)


@pytest.mark.parametrize("q_dtype,kv_dtype,operand", [
    ("bfloat16", "bfloat16", "bfloat16"),   # a bf16 model: no up-cast
    ("float32", "float32", "float32"),      # a float32 model: as before
    ("float32", "bfloat16", "float32"),     # mixed: the wider of the two
    ("bfloat16", "int8", "bfloat16"),       # int8 values are exact in bf16
])
def test_prefill_kernel_contracts_in_the_caches_type(q_dtype, kv_dtype,
                                                     operand):
    """Both contractions of every branch take their operands in
    ``prefill_operand_dtype``'s type and accumulate in float32."""
    from flexflow_tpu.ops.pallas.attention import prefill_operand_dtype

    assert prefill_operand_dtype(jnp.dtype(q_dtype),
                                 jnp.dtype(kv_dtype)) == operand
    q = jnp.zeros((2, 16, 8, 16), q_dtype)
    kc = jnp.zeros((3, 8, 128, 16), kv_dtype)
    scales = {}
    if kv_dtype == "int8":
        scales = dict(k_scale=jnp.ones((3, 8, 128)),
                      v_scale=jnp.ones((3, 8, 128)))
    jaxpr = jax.make_jaxpr(lambda *a: prefill_attention(
        *a, scale=0.25, block_s=32, interpret=True, **scales))(
            q, kc, kc, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32))
    dots = list(_dot_generals(jaxpr.jaxpr))
    # q.k' and p.v, in the masked and the unmasked branch
    assert len(dots) == 4
    for eqn in dots:
        assert [v.aval.dtype for v in eqn.invars] == [operand, operand]
        assert eqn.outvars[0].aval.dtype == jnp.float32


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_prefill_kernels_operand_type_is_counted(dtype):
    """``attention_path.prefill_operands.<dtype>`` once the tiled prefill
    scan has traced ``prefill_attention``: ``bfloat16`` in a bf16 model,
    ``float32`` only in a float32 one."""
    import dataclasses

    from flexflow_tpu.obs import NULL_TELEMETRY, Telemetry

    im = make_im(max_tokens=8, max_requests=2, max_seq=32, use_pallas=True,
                 cfg=dataclasses.replace(TINY, dtype=dtype))
    tel = Telemetry()
    rm = RequestManager(im, GenerationConfig(max_new_tokens=2),
                        telemetry=tel)
    try:
        im._paths_counted = 0
        rm.generate([[5, 9, 2, 11, 3, 7, 1, 4, 6]])
        assert im.attention_paths[
            ("prefill_operands", "inc_multihead_self_attention")] == dtype
        counters = tel.metrics.snapshot()
        assert counters[f"attention_path.prefill_operands.{dtype}"] == 1
        other = {"bfloat16": "float32", "float32": "bfloat16"}[dtype]
        assert f"attention_path.prefill_operands.{other}" not in counters
    finally:
        im.telemetry = NULL_TELEMETRY


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_prefill_tiled_generation_matches_golden(chunk):
    """End-to-end: RequestManager with the PrefillBatchConfig path (interpret
    kernels) matches the independent full-context reference across chunk
    sizes — the VERDICT r3 'kernel-vs-gather equality across chunk sizes'
    criterion, at the serving level."""
    im = make_im(max_tokens=chunk, max_requests=2, max_seq=32,
                 use_pallas=True)
    assert im.prefill_tile > 1
    rm = RequestManager(im, GenerationConfig(max_new_tokens=4))
    prompts = [[5, 9, 2, 11, 3, 7, 1], [4, 4, 8]]
    out = rm.generate(prompts)
    for prompt, got in zip(prompts, out):
        want = ref_greedy_decode(im.params, TINY, prompt, 4)
        assert got == want


def test_prefill_tiled_equals_flat_path():
    """The tiled prefill step and the flat (gather) step produce identical
    caches and logits for the same chunk."""
    im_t = make_im(max_tokens=8, max_requests=2, max_seq=32, use_pallas=True)
    im_f = make_im(max_tokens=8, max_requests=2, max_seq=32, use_pallas=False)
    prompt = [5, 9, 2, 11, 3]  # 5 real tokens, 3 pad slots in the tile
    pbc, last_flat = PrefillBatchConfig.build(
        [(0, prompt, 0)], [len(prompt)], im_t.prefill_tile,
        max_tokens=8, max_requests=2,
    )
    bc = BatchConfig.build(
        prompt, [0] * 5, list(range(5)), [len(prompt)],
        max_tokens=8, max_requests=2,
    )
    im_f.params = im_t.params  # same weights
    r_t = im_t.step(pbc)
    r_f = im_f.step(bc)
    assert last_flat[0] == 4
    np.testing.assert_array_equal(
        np.asarray(r_t.token_ids)[4], np.asarray(r_f.token_ids)[4]
    )
    for name in im_t.state:
        for buf in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(im_t.state[name][buf])[:2],
                np.asarray(im_f.state[name][buf])[:2],
                atol=1e-5, rtol=1e-5,
            )


def test_prefill_batch_config_contract():
    pbc, last = PrefillBatchConfig.build(
        [(0, [1, 2, 3], 0), (1, [4, 5, 6, 7, 8], 12)],
        [3, 17], tile_size=4, max_tokens=16, max_requests=4,
    )
    base = pbc.base
    req = np.asarray(base.request_index)
    pos = np.asarray(base.token_position)
    # segment 0: one tile (3 real + 1 pad); segment 1: two tiles (5 real)
    assert list(req[:4]) == [0, 0, 0, -1]
    assert list(req[4:12]) == [1] * 5 + [-1] * 3
    assert list(pos[:3]) == [0, 1, 2]
    assert list(pos[4:9]) == [12, 13, 14, 15, 16]
    assert last == {0: 2, 1: 8}
    assert pbc.num_tiles == 4
    with pytest.raises(ValueError):
        PrefillBatchConfig.build(
            [(0, list(range(20)), 0)], [20], tile_size=4,
            max_tokens=16, max_requests=4,
        )
    # contract (d): segment starts must be tile-aligned (the attention op
    # writes each tile's KV as one block dynamic-update-slice)
    with pytest.raises(ValueError, match="aligned"):
        PrefillBatchConfig.build(
            [(0, [1, 2, 3], 10)], [13], tile_size=4,
            max_tokens=16, max_requests=4,
        )


def test_request_manager_emits_prefill_batch_config():
    im = make_im(max_tokens=16, max_requests=2, max_seq=32, use_pallas=True)
    rm = RequestManager(im, GenerationConfig(max_new_tokens=2))
    rm.register_new_request([1, 2, 3, 4, 5])
    bc, points = rm.prepare_next_batch()
    assert isinstance(bc, PrefillBatchConfig)
    assert len(points) == 1  # whole prompt fits: sample point at last token
    # follow-up step is pure decode -> flat BatchConfig
    res = im.step(bc)
    rm.process_result(res, points)
    bc2, _ = rm.prepare_next_batch()
    assert isinstance(bc2, BatchConfig)


def test_prefill_tile_divides_max_seq_len():
    """ADVICE r5 medium: the tile must divide max_seq_len so the tiled
    block-DUS contract is independent of the cache's 128-padding detail.
    36 % 16 != 0 and 36 % 8 != 0, so the tile shrinks to 4."""
    im = make_im(max_tokens=16, max_requests=2, max_seq=36, use_pallas=True)
    assert im.prefill_tile == 4
    assert 36 % im.prefill_tile == 0
    # power-of-two max_seq keeps the full tile
    im2 = make_im(max_tokens=16, max_requests=2, max_seq=64, use_pallas=True)
    assert im2.prefill_tile == 16
    # generation through the shrunken tile stays correct
    rm = RequestManager(im, GenerationConfig(max_new_tokens=3))
    prompt = [5, 9, 2, 11, 3, 7, 1]
    got = rm.generate([prompt])[0]
    assert got == ref_greedy_decode(im.params, TINY, prompt, 3)


def test_tiled_budget_starvation_falls_back_to_flat():
    """Regression (ADVICE r5 low): with max_tokens == tile and an active
    decoder, every mixed step leaves budget < one tile, which used to
    postpone prefill until the decoder finished (unbounded TTFT).  After
    ``starvation_limit`` dry steps the manager must take an unaligned flat
    chunk so the queued prompt makes progress — and its output must still
    match the golden."""
    im = make_im(max_tokens=4, max_requests=2, max_seq=64, use_pallas=True)
    assert im.prefill_tile == 4
    rm = RequestManager(im, GenerationConfig(max_new_tokens=24))
    prompt_a = [3, 11, 25, 40, 7][: im.prefill_tile]  # one-tile prompt
    rm.register_new_request(prompt_a)  # A: prefills in one step, then decodes
    bc, pts = rm.prepare_next_batch()
    rm.process_result(im.step(bc), pts)
    req_a = rm._active()[0]
    assert req_a.status is RequestStatus.DECODING
    # B arrives: every step now carries A's decode token, budget = 3 < tile
    prompt_b = [2, 4, 6, 8, 10, 12]
    rid_b = rm.register_new_request(prompt_b, max_new_tokens=2)
    steps_until_b = None
    for step in range(1, 16):
        bc, pts = rm.prepare_next_batch()
        rm.process_result(im.step(bc), pts)
        if rm.requests[rid_b].generated:
            steps_until_b = step
            break
    # without the fallback B would wait all ~23 remaining decode steps of A
    assert steps_until_b is not None and steps_until_b <= 4 + len(prompt_b), (
        f"B starved: no first token after {steps_until_b} steps")
    # drain and check correctness of both requests
    while rm.has_work():
        bc, pts = rm.prepare_next_batch()
        rm.process_result(im.step(bc), pts)
    assert rm.requests[rid_b].generated == ref_greedy_decode(
        im.params, TINY, prompt_b, 2)


def test_off_tile_prefill_realigns_in_budget_rich_step():
    """Follow-up to the starvation fallback: an off-tile offset blocks the
    tiled pure-prefill path for EVERY concurrently prefilling request (the
    alignment gate is all-or-nothing), so the first budget-rich step must
    round its take to land the offset back on a tile boundary — after
    which the manager emits PrefillBatchConfig again."""
    im = make_im(max_tokens=8, max_requests=2, max_seq=64, use_pallas=True)
    assert im.prefill_tile == 8
    rm = RequestManager(im, GenerationConfig(max_new_tokens=2))
    prompt = [(i % 50) + 1 for i in range(19)]
    rid = rm.register_new_request(prompt)
    req = rm.requests[rid]
    req.prefill_offset = 3  # as if a starvation fallback took 3 unaligned
    bc, _ = rm.prepare_next_batch()
    # off-tile: flat layout, take rounded 8 -> 5 so the offset re-aligns
    assert not isinstance(bc, PrefillBatchConfig)
    assert req.prefill_offset == 8
    bc2, _ = rm.prepare_next_batch()
    # re-aligned: the tiled Pallas path is available again
    assert isinstance(bc2, PrefillBatchConfig)
    assert req.prefill_offset == 16


def test_mixed_decode_prefill_keeps_tile_alignment():
    """Regression (r5 review): a mixed decode+prefill step must advance
    prefill offsets by whole tiles, so the later pure-prefill steps can
    take the tiled path — an unaligned offset used to crash the
    PrefillBatchConfig builder once contract (d) landed."""
    im = make_im(max_tokens=24, max_requests=2, max_seq=64, use_pallas=True)
    tile = im.prefill_tile
    assert 1 < tile < 24
    rm = RequestManager(im, GenerationConfig(max_new_tokens=6))
    prompt_a = [(i % 11) + 1 for i in range(5)]
    rm.register_new_request(prompt_a)
    # run A through prefill into decoding
    for _ in range(4):
        bc, pts = rm.prepare_next_batch()
        rm.process_result(im.step(bc), pts)
        if rm._active() and rm._active()[0].generated:
            break
    # B arrives mid-decode: the next steps mix decode(A) + prefill(B)
    prompt_b = [(i % 7) + 1 for i in range(30)]
    rid_b = rm.register_new_request(prompt_b)
    while rm.has_work():
        bc, pts = rm.prepare_next_batch()
        for req in rm._active():
            if req.status is not None and req.prefill_offset < len(req.prompt):
                assert req.prefill_offset % tile == 0 or \
                    req.prefill_offset == 0
        rm.process_result(im.step(bc), pts)
    out_b = rm.requests[rid_b].generated
    assert out_b == ref_greedy_decode(im.params, TINY, prompt_b, 6)
