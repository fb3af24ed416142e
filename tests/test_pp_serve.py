"""Pipeline-parallel serving tests: stage split + bit-identity vs the
single-stage program on the virtual mesh.

The contract (ISSUE 3 acceptance): a pp2 (and pp2 x tp2) serve step produces
bit-identical tokens/logits/caches to the single-stage InferenceManager —
for decode, tiled/gated prefill, and mixed steps, including the int8-weights
+ int8-KV configuration — and micro-batch interleave count/order never
changes results.  Stage programs carry the scoped collective-safe compiler
options (utils/platform) like every other multi-virtual-device CPU program.
"""

import dataclasses

import jax
import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.parallel.mesh import make_mesh
from flexflow_tpu.serve import (
    GenerationConfig,
    InferenceManager,
    PipelinedInferenceManager,
    RequestManager,
    build_model,
    quantize_int8,
    serve_stage_split,
)
from flexflow_tpu.serve.batch_config import BatchConfig, PrefillBatchConfig
from flexflow_tpu.serve.ops import IncMultiHeadSelfAttention

from test_serve import TINY, make_im, ref_greedy_decode

TINY4 = dataclasses.replace(TINY, num_hidden_layers=4)

_PIM_CACHE = {}


def make_pp_im(axes=None, n_micro=None, cfg=TINY, max_tokens=16,
               max_requests=2, max_seq=32, seed=7, use_pallas=True,
               kv_dtype=None, kv_page_size=None):
    axes = axes or {"pp": 2}
    key = (tuple(sorted(axes.items())), n_micro, repr(cfg), max_tokens,
           max_requests, max_seq, use_pallas, kv_dtype, kv_page_size)
    im = _PIM_CACHE.get(key)
    if im is None:
        n = int(np.prod(list(axes.values())))
        mesh = make_mesh(axes, jax.devices()[:n])
        ff = FFModel(FFConfig(), mesh=mesh)
        build_model(ff, cfg, max_tokens)
        im = PipelinedInferenceManager(
            ff, max_requests=max_requests, max_tokens_per_batch=max_tokens,
            max_seq_len=max_seq, n_micro=n_micro, use_pallas=use_pallas,
            kv_dtype=kv_dtype, kv_page_size=kv_page_size,
        )
        _PIM_CACHE[key] = im
    im.init_operators_inference(rng=jax.random.PRNGKey(seed))
    return im


def assert_states_equal(a, b):
    assert set(a) == set(b)
    for name in a:
        for buf in a[name]:
            x, y = np.asarray(a[name][buf]), np.asarray(b[name][buf])
            assert np.array_equal(x, y), f"{name}.{buf} diverged"


# ---------------------------------------------------------------------------
def test_stage_split_is_a_chain():
    im = make_im()
    g = im.model.graph
    split = serve_stage_split(g, 2)
    assert len(split) == 2
    # chain: exits feed the next stage's entries; stage 0 starts at the
    # graph input, the last stage ends at the logits
    assert split[0][1] == list(g.input_tids)
    assert split[0][2] == split[1][1]
    assert split[1][2] == [g.nodes[-1].outputs[-1]]
    # every node appears exactly once, in order
    flat = [n.nid for s in split for n in s[0]]
    assert flat == [n.nid for n in g.nodes]
    # attention layers balance 1 + 1
    for nodes, _, _ in split:
        assert sum(isinstance(n.op, IncMultiHeadSelfAttention)
                   for n in nodes) == 1
    # a serve llama graph's natural cut is two tensors wide (residual +
    # normed hidden)
    assert len(split[0][2]) == 2


def test_stage_split_rejects_oversubscription():
    im = make_im()
    with pytest.raises(ValueError, match="attention layers"):
        serve_stage_split(im.model.graph, 5)


def test_pp2_params_match_single_stage_init():
    im1 = make_im(use_pallas=True)
    pim = make_pp_im({"pp": 2})
    p1, p2 = im1.params, pim.params
    assert set(p1) == set(p2)
    for name in p1:
        for pn in p1[name]:
            assert np.array_equal(np.asarray(p1[name][pn]),
                                  np.asarray(p2[name][pn])), (name, pn)


def test_pp2_mixed_step_bit_identical():
    # mixed prefill+decode flat batch through one macro-step
    im1 = make_im(use_pallas=True)
    pim = make_pp_im({"pp": 2})
    bc = BatchConfig.build(
        [3, 5, 7, 11, 2], [0, 0, 0, 1, 1], [0, 1, 2, 0, 1], [3, 2],
        max_tokens=16, max_requests=2,
    )
    r1 = im1.step(bc)
    r2 = pim.step(bc)
    assert np.array_equal(np.asarray(r1.token_ids), np.asarray(r2.token_ids))
    assert np.array_equal(np.asarray(r1.logits_max),
                          np.asarray(r2.logits_max))
    assert_states_equal(im1.state, pim.state)


def test_pp2_tiled_gated_prefill_step_bit_identical():
    im1 = make_im(use_pallas=True)
    pim = make_pp_im({"pp": 2})
    pbc, _ = PrefillBatchConfig.build(
        [(0, [3, 5, 7], 0), (1, [11, 2], 0)], [3, 2], tile_size=8,
        max_tokens=16, max_requests=2, gate_slots=[0, 1],
    )
    r1 = im1.step(pbc)
    r2 = pim.step(pbc)
    # gated chunk: result arrays are [max_requests], indexed by slot
    assert np.array_equal(np.asarray(r1.token_ids), np.asarray(r2.token_ids))
    assert np.array_equal(np.asarray(r1.logits_max),
                          np.asarray(r2.logits_max))
    assert_states_equal(im1.state, pim.state)


@pytest.mark.slow
def test_pp2_decode_scan_matches_single_stage_scan():
    im1 = make_im(max_seq=64, use_pallas=True)
    pim = make_pp_im({"pp": 2}, max_seq=64)
    prompt = [3, 11, 25, 40, 7]
    rm = RequestManager(im1, GenerationConfig(max_new_tokens=1))
    first = rm.generate([prompt], max_new_tokens=1)[0][-1]
    rm2 = RequestManager(pim, GenerationConfig(max_new_tokens=1))
    assert rm2.generate([prompt], max_new_tokens=1)[0][-1] == first
    bc = BatchConfig.build(
        [first], [0], [len(prompt)], [len(prompt) + 1],
        max_tokens=16, max_requests=2,
    )
    t1, l1, _ = im1.decode_scan(bc, 6)
    t2, l2, _ = pim.decode_scan(bc, 6)
    # both managers' contract: a token means something only where live.
    # The single-stage scan runs one row per slot and reads 0 on the
    # padding rows it did not run; pp runs all max_tokens rows and reads
    # the padding's argmax there
    assert np.array_equal(np.asarray(l1), np.asarray(l2))
    assert np.asarray(l1).any()
    assert np.array_equal(np.where(l1, t1, 0), np.where(l2, t2, 0))
    assert_states_equal(im1.state, pim.state)


def _scan_read_back(make, sample, facade):
    """Two prompts fed flat on a fresh manager, then 5 scan steps on both
    rows through ``decode_scan`` (``facade``) or through
    ``decode_scan_async`` with what the facade would read off the batch;
    everything read back to the host."""
    im = make()
    prompts = [[3, 11, 25, 40, 7], [2, 4, 6]]
    flat = [t for p in prompts for t in p]
    reqi = [i for i, p in enumerate(prompts) for _ in p]
    pos = [j for p in prompts for j in range(len(p))]
    lens = [len(p) for p in prompts]
    res = im.step(BatchConfig.build(flat, reqi, pos, lens, max_tokens=16,
                                    max_requests=2))
    first = np.asarray(res.token_ids)[np.cumsum(lens) - 1]
    bc = BatchConfig.build([int(t) for t in first], [0, 1], lens,
                           [n + 1 for n in lens], max_tokens=16,
                           max_requests=2)
    if facade:
        tokens, live, out = im.decode_scan(bc, 5, eos=int(first[0]),
                                           sample=sample)
    else:
        tokens, live, _, out = im.decode_scan_async(
            bc, 5, eos=int(first[0]), sample=sample, allowed=None,
            max_position=max(lens))
    return (np.asarray(tokens), np.asarray(live),
            {f.name: np.asarray(getattr(out, f.name))
             for f in dataclasses.fields(out)})


@pytest.mark.parametrize("make", [
    lambda: make_im(max_seq=64, use_pallas=True),
    lambda: make_pp_im({"pp": 2}, max_seq=64),
], ids=["InferenceManager", "PipelinedInferenceManager"])
def test_decode_scan_is_the_async_scan_read_back(make):
    """``decode_scan`` is ``decode_scan_async`` with no budgets, the top
    position read off the batch, and the exit codes dropped: the ONE test
    that holds each manager's facade to its engine — tokens, ``live`` and
    the advanced batch, greedy and with a seeded sample."""
    folds = np.zeros((16, 2), np.int32)
    folds[:2] = [(0, 1), (1, 1)]
    seeded = (jax.random.PRNGKey(11), np.float32(0.8), np.float32(0.9),
              jax.numpy.asarray(folds))
    drawn = []
    for sample in (None, seeded):
        got = _scan_read_back(make, sample, facade=True)
        want = _scan_read_back(make, sample, facade=False)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])
        assert got[1][:, :2].any()
        for field, value in want[2].items():
            np.testing.assert_array_equal(got[2][field], value, field)
        drawn.append(got[0][got[1]])
    assert not np.array_equal(*drawn), "the sample was not drawn from"


def test_pp2_generate_matches_full_forward_reference():
    pim = make_pp_im({"pp": 2})
    rm = RequestManager(pim, GenerationConfig(max_new_tokens=8))
    prompt = [3, 11, 25, 40, 7]
    got = rm.generate([prompt], max_new_tokens=8)[0]
    assert got == ref_greedy_decode(pim.params, TINY, prompt, 8)
    assert rm.scan_runs >= 1, "pp decode scan path did not run"


@pytest.mark.slow
def test_pp2_microbatch_interleave_invariance():
    # decode results must not depend on the micro-batch count (1/2/4) —
    # contiguous-range splits preserve the flat batch's causal layout
    prompts = [[3, 11, 25, 40, 7], [2, 4, 6]]
    outs = []
    for m in (1, 2, 4):
        pim = make_pp_im({"pp": 2}, n_micro=m, max_requests=4)
        rm = RequestManager(pim, GenerationConfig(max_new_tokens=6))
        outs.append(rm.generate(prompts))
    assert outs[0] == outs[1] == outs[2]
    want = [ref_greedy_decode(make_im(max_requests=4, use_pallas=True).params, TINY, p, 6)
            for p in prompts]
    assert outs[0] == want


@pytest.mark.slow
def test_pp2_eos_scan_matches_single_stage():
    prompts = [[3, 11, 25, 40, 7], [2, 4, 6, 8]]
    im1 = make_im(max_seq=64, use_pallas=True)
    base = RequestManager(im1, GenerationConfig(max_new_tokens=12)) \
        .generate(prompts)
    eos = base[0][5]
    pim = make_pp_im({"pp": 2}, max_seq=64)
    got = RequestManager(
        pim, GenerationConfig(max_new_tokens=12, eos_token_id=eos)
    ).generate(prompts)
    want0 = base[0][: base[0].index(eos) + 1]
    want1 = base[1][: base[1].index(eos) + 1] if eos in base[1] else base[1]
    assert got == [want0, want1]


@pytest.mark.slow
def test_pp2_int8_weights_and_kv_match_single_stage():
    # the full-depth capacity recipe (int8 weights + int8 KV) through the
    # stage-split path: must equal the single-stage int8 program exactly
    prompts = [[3, 11, 25, 40, 7, 9, 13, 2, 5], [2, 4, 6]]
    im1 = make_im(use_pallas=True, kv_dtype="int8")
    quantize_int8(im1)
    want = RequestManager(im1, GenerationConfig(max_new_tokens=6)) \
        .generate(prompts)
    pim = make_pp_im({"pp": 2}, kv_dtype="int8")
    quantize_int8(pim)
    got = RequestManager(pim, GenerationConfig(max_new_tokens=6)) \
        .generate(prompts)
    assert got == want
    assert_states_equal(im1.state, pim.state)


@pytest.mark.slow
def test_pp2_tp2_generate_matches_single_stage():
    prompts = [[3, 11, 25, 40, 7], [2, 4, 6]]
    im1 = make_im(use_pallas=True)
    want = RequestManager(im1, GenerationConfig(max_new_tokens=6)) \
        .generate(prompts)
    pim = make_pp_im({"pp": 2, "tp": 2})
    got = RequestManager(pim, GenerationConfig(max_new_tokens=6)) \
        .generate(prompts)
    assert got == want
    # per-stage KV residency: each stage holds only its own layers' caches
    for stage in pim.stages:
        names = {n.name for n in stage.nodes}
        assert set(stage.state) == {
            n.name for n in stage.nodes
            if isinstance(n.op, IncMultiHeadSelfAttention)
        }
        assert set(stage.state) <= names


@pytest.mark.slow
def test_pp2_tp2_int8_matches_single_stage():
    prompts = [[3, 11, 25, 40, 7, 9, 13, 2, 5], [2, 4, 6]]
    im1 = make_im(use_pallas=True, kv_dtype="int8")
    quantize_int8(im1)
    want = RequestManager(im1, GenerationConfig(max_new_tokens=5)) \
        .generate(prompts)
    pim = make_pp_im({"pp": 2, "tp": 2}, kv_dtype="int8")
    quantize_int8(pim)
    got = RequestManager(pim, GenerationConfig(max_new_tokens=5)) \
        .generate(prompts)
    assert got == want


@pytest.mark.slow
def test_pp4_deeper_model_matches_reference():
    # four stages over a 4-layer model: one decoder layer per stage
    pim = make_pp_im({"pp": 4}, cfg=TINY4, max_seq=48)
    assert len(pim.stages) == 4
    rm = RequestManager(pim, GenerationConfig(max_new_tokens=5))
    prompt = [5, 9, 2, 11, 3]
    got = rm.generate([prompt], max_new_tokens=5)[0]
    assert got == ref_greedy_decode(pim.params, TINY4, prompt, 5)


def test_pp_stage_memory_accounting():
    pim = make_pp_im({"pp": 2})
    mems = pim.stage_memory_bytes()
    assert len(mems) == 2 and all(m > 0 for m in mems)
    # each stage must be lighter than the whole model's single-plan bound
    from flexflow_tpu.search.simulator import plan_memory_bytes

    im1 = make_im(use_pallas=True)
    whole = plan_memory_bytes(im1.plan, training=False)
    assert max(mems) < whole
