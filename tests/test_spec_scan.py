"""On-device speculative macro-step scan: greedy equality with incremental.

The same hard gate as test_spec_infer.py (spec output == incremental output,
token for token) but for the fully on-device loop (`SpecDecodeScan`), which
is the production TPU path — one host sync per n_macro macro-steps.
"""

import jax
import numpy as np
import pytest

from flexflow_tpu.serve import (
    GenerationConfig,
    RequestManager,
    ServeModelConfig,
)
from flexflow_tpu.serve.batch_config import BatchConfig
from flexflow_tpu.serve.spec_scan import SpecDecodeScan

from test_serve import TINY, make_im

TINY_SSM = ServeModelConfig(
    model_type="llama",
    vocab_size=TINY.vocab_size,
    hidden_size=16,
    intermediate_size=32,
    num_hidden_layers=1,
    num_attention_heads=2,
    num_key_value_heads=2,
)

PROMPTS = [[3, 11, 25, 40, 7], [2, 4, 6, 8]]


def prefill(im, prompts):
    """Host-side prompt prefill; returns the first generated token per slot."""
    toks, reqi, pos = [], [], []
    for r, p in enumerate(prompts):
        toks += p
        reqi += [r] * len(p)
        pos += list(range(len(p)))
    bc = BatchConfig.build(
        toks, reqi, pos, [len(p) for p in prompts],
        max_tokens=im.max_tokens, max_requests=im.max_requests,
    )
    res = im.step(bc)
    ids = np.asarray(res.token_ids)
    firsts, at = [], 0
    for p in prompts:
        at += len(p)
        firsts.append(int(ids[at - 1]))
    return firsts


# rigs are cached per (width, depth, use_pallas) and RESET per call: the
# jitted macro-step is the expensive part and it is identical across the
# tests below (suite-time trim, VERDICT r3 #10).  An eos variant only needs
# a new SpecDecodeScan over the same managers (same tree layout).
_RIGS = {}


def _rig(width, depth, use_pallas):
    key = (width, depth, use_pallas)
    if key not in _RIGS:
        llm = make_im(max_tokens=32, max_requests=2, max_seq=96, max_spec=8,
                      use_pallas=use_pallas)
        ssm = make_im(max_tokens=32, max_requests=2, max_seq=96, max_spec=8,
                      cfg=TINY_SSM, topk=max(width, 1), seed=123,
                      use_pallas=use_pallas)
        _RIGS[key] = (llm, ssm)
    return _RIGS[key]


def scan_generate(width, depth, n_new, prompts=PROMPTS, eos=None,
                  use_pallas="auto"):
    llm, ssm = _rig(width, depth, use_pallas)
    llm.reset()
    ssm.reset()
    llm.tree_token_layout = None  # rigs may share the llm across layouts
    firsts = prefill(llm, prompts)
    prefill(ssm, prompts)
    sc = SpecDecodeScan(llm, ssm, width=width, depth=depth, eos_token_id=eos)
    carry = sc.init_carry(
        firsts, [len(p) for p in prompts], [len(p) for p in prompts],
        [False] * len(prompts),
    )
    emitted, carry = sc.run(carry, n_macro=n_new)  # worst case 1 tok/macro
    em = np.asarray(emitted)  # [n_macro, R, D+1]
    outs = []
    for r, p in enumerate(prompts):
        seq = [firsts[r]]
        for step in range(em.shape[0]):
            for tokn in em[step, r]:
                if tokn >= 0:
                    seq.append(int(tokn))
        if eos is not None and eos in seq:
            seq = seq[: seq.index(eos) + 1]
        outs.append(seq[:n_new])
    return outs, em


@pytest.mark.parametrize("width,depth", [(1, 3), (2, 2)])
def test_scan_matches_incremental(width, depth):
    im = make_im(max_tokens=32, max_requests=2, max_seq=96)
    want = RequestManager(im, GenerationConfig(max_new_tokens=10)).generate(PROMPTS)
    got, _ = scan_generate(width, depth, n_new=10)
    assert got == want, f"scan(w={width},d={depth}) {got} != incr {want}"


def test_scan_matches_incremental_pallas():
    # production config: tree-verify + decode Pallas kernels active
    im = make_im(max_tokens=32, max_requests=2, max_seq=96)
    want = RequestManager(im, GenerationConfig(max_new_tokens=10)).generate(PROMPTS)
    got, _ = scan_generate(2, 2, n_new=10, use_pallas=True)
    assert got == want
    # a macro-step commits several positions of ONE row in a call: its
    # writes keep the chain and never ask for kv_row_write (the decode
    # scan's, one position a row)
    for rig in _rig(2, 2, True):
        assert "kv_row_write" not in {k for k, _ in rig.attention_paths}


def test_scan_eos_freezes_slot():
    im = make_im(max_tokens=32, max_requests=2, max_seq=96)
    want = RequestManager(im, GenerationConfig(max_new_tokens=10)).generate(PROMPTS)
    eos = want[0][3]  # 4th generated token of request 0
    got, em = scan_generate(2, 2, n_new=10, eos=eos)
    assert got[0] == want[0][: want[0].index(eos) + 1]
    # the other slot is unaffected (unless it also hits eos)
    w1 = want[1]
    if eos in w1:
        w1 = w1[: w1.index(eos) + 1]
    assert got[1] == w1
    # after the eos macro-step, the finished slot emits nothing
    R, Dp1 = em.shape[1], em.shape[2]
    eos_step = next(s for s in range(em.shape[0]) if eos in em[s, 0])
    assert (em[eos_step + 1:, 0] == -1).all()


def test_scan_perfect_draft_commits_depth_plus_one():
    # SSM == LLM: every macro step must commit depth+1 tokens
    llm = make_im(max_tokens=32, max_requests=2, max_seq=96, max_spec=8)
    ssm = make_im(max_tokens=32, max_requests=2, max_seq=96, max_spec=8,
                  topk=1)
    prompts = [PROMPTS[0], PROMPTS[1]]
    firsts = prefill(llm, prompts)
    prefill(ssm, prompts)
    sc = SpecDecodeScan(llm, ssm, width=1, depth=3)
    carry = sc.init_carry(
        firsts, [len(p) for p in prompts], [len(p) for p in prompts],
        [False, False],
    )
    emitted, _ = sc.run(carry, n_macro=3)
    em = np.asarray(emitted)
    assert (em >= 0).all(), f"perfect draft must fill every emit slot: {em}"

    im = make_im(max_tokens=32, max_requests=2, max_seq=96)
    want = RequestManager(im, GenerationConfig(max_new_tokens=13)).generate(prompts)
    for r in range(2):
        got = [firsts[r]] + [int(t) for t in em[:, r].reshape(-1)]
        assert got == want[r][:13]


@pytest.mark.spec
def test_scan_mixed_spec_mask_matches_incremental():
    """Mixed spec/non-spec rows in ONE on-device macro-step scan
    (``init_carry(spec_mask=...)``): with a perfect draft (SSM == LLM)
    the spec row commits depth+1 tokens per macro while the plain row in
    the SAME verify batch commits exactly one — both bit-identical to
    plain incremental decoding."""
    im = make_im(max_tokens=32, max_requests=2, max_seq=96)
    want = RequestManager(im, GenerationConfig(max_new_tokens=13)).generate(
        PROMPTS)

    llm = make_im(max_tokens=32, max_requests=2, max_seq=96, max_spec=8)
    ssm = make_im(max_tokens=32, max_requests=2, max_seq=96, max_spec=8,
                  topk=1)  # SSM == LLM: every spec-row chain drafts true
    llm.tree_token_layout = None
    firsts = prefill(llm, PROMPTS)
    prefill(ssm, PROMPTS)
    sc = SpecDecodeScan(llm, ssm, width=1, depth=3)
    n_macro = 3
    carry = sc.init_carry(
        firsts, [len(p) for p in PROMPTS], [len(p) for p in PROMPTS],
        [False] * len(PROMPTS), spec_mask=[True, False],
    )
    emitted, _ = sc.run(carry, n_macro)
    em = np.asarray(emitted)
    seq = [[firsts[r]] + [int(t) for t in em[:, r].reshape(-1) if t >= 0]
           for r in range(2)]
    # spec row: the perfect draft commits depth+1 = 4 per macro step
    assert all(int((em[s, 0] >= 0).sum()) == 4 for s in range(n_macro))
    assert seq[0] == want[0][: 1 + 4 * n_macro]
    # plain row: EXACTLY one token per macro step, same trajectory
    assert all(int((em[s, 1] >= 0).sum()) == 1 for s in range(n_macro))
    assert len(seq[1]) == 1 + n_macro
    assert seq[1] == want[1][: 1 + n_macro]


def test_scan_budget_freezes_slot_with_exit_code():
    """Device-side max-new exit for the spec path: per-slot budgets in
    the carry (``init_carry(budget=...)``) truncate emissions exactly
    where the host's ``_maybe_finish`` would, freeze the slot, and the
    carry's ``exit_code`` says why — lifecycle rides the one readback
    per ``run()`` window."""
    from flexflow_tpu.serve.inference_manager import (
        EXIT_BUDGET,
        EXIT_RUNNING,
    )

    def streams(em):
        outs = []
        for r in range(2):
            seq = []
            for step in range(em.shape[0]):
                seq += [int(t) for t in em[step, r] if t >= 0]
            outs.append(seq)
        return outs

    llm, ssm = _rig(2, 2, "auto")
    llm.reset()
    ssm.reset()
    llm.tree_token_layout = None
    firsts = prefill(llm, PROMPTS)
    prefill(ssm, PROMPTS)
    sc = SpecDecodeScan(llm, ssm, width=2, depth=2)
    # unbudgeted reference window
    carry = sc.init_carry(
        firsts, [len(p) for p in PROMPTS], [len(p) for p in PROMPTS],
        [False, False])
    em_ref, carry_ref = sc.run(carry, n_macro=8)
    full = streams(np.asarray(em_ref))
    assert len(full[0]) >= 5 and len(full[1]) >= 3
    assert np.asarray(carry_ref["exit_code"]).tolist() == [
        EXIT_RUNNING, EXIT_RUNNING]

    # budgeted run: row 0 may emit 4 more tokens, row 1 only 2 — each
    # stream is the exact prefix of the unbudgeted run, then frozen
    llm.reset()
    ssm.reset()
    llm.tree_token_layout = None
    assert prefill(llm, PROMPTS) == firsts
    prefill(ssm, PROMPTS)
    carry = sc.init_carry(
        firsts, [len(p) for p in PROMPTS], [len(p) for p in PROMPTS],
        [False, False], budget=[4, 2])
    em_b, carry_b = sc.run(carry, n_macro=8)
    got = streams(np.asarray(em_b))
    assert got[0] == full[0][:4]
    assert got[1] == full[1][:2]
    assert np.asarray(carry_b["finished"]).tolist() == [True, True]
    assert np.asarray(carry_b["exit_code"]).tolist() == [
        EXIT_BUDGET, EXIT_BUDGET]
    assert np.asarray(carry_b["budget"]).tolist() == [0, 0]
