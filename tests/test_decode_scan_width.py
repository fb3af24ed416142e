"""The decode scan runs on one row per request slot, behind the WIDE contract.

``InferenceManager._decode_scan_impl`` takes and returns everything per flat
row of a ``max_tokens_per_batch`` batch (the layout ``step``, ``join_slot``
and ``benchmark/check.py`` share), but its ``lax.scan`` body runs on
``min(max_requests, max_tokens_per_batch)`` rows: the rows that hold a request
are compacted on device before the scan and the results expanded after it.
The toy deployment here has ``max_tokens_per_batch`` 160 > ``DUS_MAX_TOKENS``
128 > ``max_requests`` 4, so before the narrowing its scan wrote KV by an
XLA scatter over 160 rows.  Pinned: tokens, ``live``, exit codes and the
advanced batch equal the per-step path's and those of a deployment whose
``max_tokens`` IS its ``max_requests``; the lowered body holds no cache
scatter and 4-row dots; rows need not be a prefix; joins splice in between
narrowed segments; the guard and the dispatch span speak of the real width.
"""

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.obs import NULL_TELEMETRY, Telemetry
from flexflow_tpu.serve import BatchConfig, GenerationConfig, RequestManager
from flexflow_tpu.serve import ops as serve_ops
from flexflow_tpu.serve.inference_manager import (
    EXIT_BUDGET,
    EXIT_EOS,
    EXIT_NOT_IN_BATCH,
    EXIT_RUNNING,
    decode_scan_width,
)

from test_serve import TINY, make_im
from test_serving_under_load import VirtualClock, poisson_arrivals

WIDE, SLOTS, SEQ, N = 160, 4, 128, 6
assert WIDE > serve_ops.DUS_MAX_TOKENS > SLOTS
PROMPTS = [[3, 5, 7, 9, 11], [2, 4, 6], [8, 1, 13, 21, 34, 55, 6], [10, 20]]
TEMPERATURE, TOP_P = 0.8, 0.9


def wide_im(**kw):
    return make_im(max_tokens=WIDE, max_requests=SLOTS, max_seq=SEQ, **kw)


def slot_im(**kw):
    """The deployment whose flat capacity IS its slot count: the scan the
    narrowing leaves alone."""
    return make_im(max_tokens=SLOTS, max_requests=SLOTS, max_seq=SEQ, **kw)


def feed(im, prompts, rows=None):
    """Prefill ``prompts`` (request ``i`` in slot ``i``) by flat steps and
    return the pure-decode batch that continues them, request ``i`` at flat
    row ``rows[i]`` (default: packed from row 0) — absent rows carry marks
    the scan has to leave alone."""
    im.fault_injector = None
    seq = np.zeros(im.max_requests, np.int32)
    first = []
    for slot, p in enumerate(prompts):
        if im.kv_page_size:
            im.kv.bind(slot, slot=slot, tokens=list(p), need=len(p) + N + 1)
            im.kv.prepare_write(slot, 0, len(p) + N + 1)
        for lo in range(0, len(p), im.max_tokens):
            part = p[lo: lo + im.max_tokens]
            seq[slot] = lo + len(part)
            res = im.step(BatchConfig.build(
                part, [slot] * len(part), range(lo, lo + len(part)), seq,
                max_tokens=im.max_tokens, max_requests=im.max_requests))
        first.append(int(np.asarray(res.token_ids)[len(part) - 1]))
    rows = list(range(len(prompts))) if rows is None else rows
    tok = np.full(im.max_tokens, 7, np.int32)
    req = np.full(im.max_tokens, -1, np.int32)
    pos = np.full(im.max_tokens, 9, np.int32)
    tok[rows], req[rows] = first, range(len(prompts))
    pos[rows] = [len(p) for p in prompts]
    seq[: len(prompts)] += 1
    return BatchConfig(jnp.asarray(tok), jnp.asarray(req), jnp.asarray(pos),
                       jnp.asarray(len(prompts), jnp.int32), jnp.asarray(seq))


def release(im):
    if im.kv_page_size:
        for slot in range(im.max_requests):
            im.kv.release(slot)


def fields(bc):
    return {k: np.asarray(getattr(bc, k)) for k in
            ("tokens", "request_index", "token_position", "seq_lens")}


def by_steps(im, bc, eos=None, allowed=None, sample=None, n=N):
    """The scan's contract with one flat ``step`` per token and the batch
    advanced on the host: tokens (0 where not live), live, exit codes and
    the batch to go on with."""
    f = fields(bc)
    tok, req, pos, seq = (f[k].copy() for k in f)
    present = req >= 0
    alive = present.copy() if allowed is None else present & (allowed > 0)
    req = np.where(alive, req, -1)
    eos_hit = np.zeros_like(present)
    toks = np.zeros((n, len(tok)), np.int32)
    lives = np.zeros((n, len(tok)), bool)
    for i in range(n):
        smp = sample and (*sample[:3], sample[3] + np.int32([0, i]))
        out = np.asarray(im.step(
            BatchConfig(jnp.asarray(tok), jnp.asarray(req), jnp.asarray(pos),
                        bc.num_tokens, jnp.asarray(seq)),
            sample=smp).token_ids)
        active = req >= 0
        lives[i], toks[i] = alive, np.where(alive, out, 0)
        if eos is not None:
            eos_hit |= alive & (out == eos)
            alive = alive & (out != eos)
        if allowed is not None:
            alive = alive & (i + 1 < allowed)
        tok = np.where(active, out, tok)
        pos = pos + active
        np.add.at(seq, req[active], 1)
        req = np.where(alive, req, -1)
    ecode = np.where(~present, EXIT_NOT_IN_BATCH, np.where(
        eos_hit, EXIT_EOS, np.where(alive, EXIT_RUNNING, EXIT_BUDGET)))
    return toks, lives, ecode, dict(tokens=tok, request_index=req,
                                    token_position=pos, seq_lens=seq)


def scan(im, bc, eos=None, allowed=None, sample=None, n=N):
    toks, live, ecode, out = im.decode_scan_async(
        bc, n, eos=eos, allowed=allowed, sample=sample,
        max_position=int(np.max(np.asarray(bc.token_position))))
    toks, live = np.asarray(toks), np.asarray(live)
    return np.where(live, toks, 0), live, np.asarray(ecode), fields(out), toks


def folds_for(im, rows):
    """The per-request key schedule ``RequestManager._sample_for`` builds:
    row ``rows[i]`` draws from (rid 40 + i, token index 1)."""
    folds = np.zeros((im.max_tokens, 2), np.int32)
    folds[rows] = [(40 + i, 1) for i in range(len(rows))]
    return (jax.random.PRNGKey(11), jnp.float32(TEMPERATURE),
            jnp.float32(TOP_P), folds)


CASES = {
    "greedy": dict(),
    "eos": dict(eos=True),
    "budgets": dict(allowed=[6, 0, 3, 1]),
    "eos+budgets": dict(eos=True, allowed=[2, 9, 9, 4]),
    "folds": dict(sampled=True),
    "folds+eos+budgets": dict(sampled=True, eos=True, allowed=[5, 2, 9, 0]),
    "int8": dict(kv_dtype="int8", allowed=[6, 3, 6, 2]),
    "int8+eos": dict(kv_dtype="int8", eos=True),
    "paged": dict(kv_page_size=32, allowed=[6, 6, 2, 4]),
    "paged+folds": dict(kv_page_size=32, sampled=True),
    "int8+folds+budgets": dict(kv_dtype="int8", sampled=True,
                               allowed=[1, 6, 0, 4]),
    "all-budgets-spent": dict(allowed=[0, 0, 0, 0]),
}


@pytest.mark.parametrize("case", CASES, ids=list(CASES))
def test_narrowed_scan_equals_per_step_path_and_slot_wide_scan(case):
    case = dict(CASES[case])
    eos, budgets = case.pop("eos", False), case.pop("allowed", None)
    sampled = case.pop("sampled", False)

    def drive(make, run, eos_id):
        im = make(**case)
        bc = feed(im, PROMPTS)
        rows = list(range(len(PROMPTS)))
        allowed = None
        if budgets is not None:
            allowed = np.zeros(im.max_tokens, np.int32)
            allowed[rows] = budgets
        sample = folds_for(im, rows) if sampled else None
        try:
            return run(im, bc, eos_id, allowed, sample)
        finally:
            release(im)

    # a token row 2 really emits at its second step, so ``eos`` freezes it
    eos_id = int(drive(slot_im, scan, None)[0][1, 2]) if eos else None
    runs = {name: drive(make, run, eos_id) for name, make, run in (
        ("steps", wide_im, by_steps), ("slots", slot_im, scan),
        ("wide", wide_im, scan))}
    toks, live, ecode, after = runs["steps"][:4]
    for name, width in (("slots", SLOTS), ("wide", WIDE)):
        got = runs[name]
        # the contract benchmark/check.py indexes: [n_steps, max_tokens]
        assert got[0].shape == got[1].shape == (N, width)
        assert got[2].shape == (width,)
        np.testing.assert_array_equal(got[0], toks[:, :width], err_msg=name)
        np.testing.assert_array_equal(got[1], live[:, :width], err_msg=name)
        np.testing.assert_array_equal(got[2], ecode[:width], err_msg=name)
        for k, v in got[3].items():
            want = after[k] if k == "seq_lens" else after[k][:width]
            np.testing.assert_array_equal(v, want, err_msg=f"{name} {k}")
    # rows the scan did not run: token 0, not live, not in the batch
    assert not runs["wide"][4][:, SLOTS:].any()
    assert (ecode[SLOTS:] == EXIT_NOT_IN_BATCH).all()
    if eos:
        assert (ecode == EXIT_EOS).any(), "the case froze no row by eos"
    if budgets is not None:
        assert (ecode == EXIT_BUDGET).any()


@pytest.mark.parametrize("rows", [[0, 5, 130], [157, 158, 159], [9, 8, 3]],
                         ids=["spread", "tail", "not-in-slot-order"])
def test_rows_need_not_be_a_prefix(rows):
    im = wide_im()
    want = scan(im, feed(im, PROMPTS[:3]))
    im = wide_im()
    bc = feed(im, PROMPTS[:3], rows=rows)
    before = fields(bc)
    got = scan(im, bc)
    assert got[1][:, rows].all() and got[1].sum() == N * 3
    for a, b in zip(got[:3], want[:3]):
        np.testing.assert_array_equal(a[..., rows], b[..., :3])
    absent = np.setdiff1d(np.arange(WIDE), rows)
    assert not got[4][:, absent].any()
    assert (got[2][absent] == EXIT_NOT_IN_BATCH).all()
    for k in ("tokens", "request_index", "token_position"):
        np.testing.assert_array_equal(got[3][k][rows], want[3][k][:3])
        # absent rows come back as they came, marks and all
        np.testing.assert_array_equal(got[3][k][absent], before[k][absent])
    np.testing.assert_array_equal(got[3]["seq_lens"], want[3]["seq_lens"])


@pytest.mark.parametrize("path", ["host_reads_the_batch", "callers_count"])
def test_more_rows_than_slots_is_refused(path):
    """``decode_scan`` counts the rows off the batch it reads anyway; the
    async path, which must not sync, is held to the caller's own ``rows``
    count (the dispatch span's) — the compaction would cut the surplus and
    report it ``EXIT_NOT_IN_BATCH`` without a word."""
    im = wide_im()
    bc = BatchConfig.build([1] * 5, [0, 1, 2, 3, 0], [3] * 5, [4] * 4,
                           max_tokens=WIDE, max_requests=SLOTS)
    with pytest.raises(ValueError, match="one row per request"):
        if path == "host_reads_the_batch":
            im.decode_scan(bc, 2)
        else:
            im.decode_scan_async(bc, 2, max_position=3,
                                 counts={"rows": SLOTS + 1})


@pytest.mark.parametrize("flat", [WIDE // 2, SLOTS // 2])
def test_batch_of_another_capacity_runs_at_its_own_width(flat):
    """A caller may hand in a batch narrower than the manager's flat
    capacity.  The width is worked out in ONE place,
    ``decode_scan_width(bc)``, from the batch in hand: the program, the
    guard and the span cannot speak of different widths."""
    prompts = PROMPTS[:2]
    im = wide_im()
    want = scan(im, feed(im, prompts))
    im = wide_im()
    im.telemetry = tel = Telemetry()
    full = feed(im, prompts)
    bc = BatchConfig(full.tokens[:flat], full.request_index[:flat],
                     full.token_position[:flat], full.num_tokens,
                     full.seq_lens)
    assert decode_scan_width(bc) == min(SLOTS, flat)
    try:
        got = scan(im, bc)
    finally:
        im.telemetry = NULL_TELEMETRY
    assert got[1][:, :2].all()
    for a, b in zip(got[:3], want[:3]):
        np.testing.assert_array_equal(a[..., :2], b[..., :2])
    (span,) = [e for e in tel.trace.trace_events()
               if e["ph"] == "X" and e["name"] == "decode_scan_dispatch"]
    assert span["args"]["width"] == min(SLOTS, flat)


def test_shared_key_form_draws_what_a_slot_wide_deployment_draws():
    """``(key, temperature, top_p)`` draws every row from ONE key, so its
    bits depend on the row count: the narrowed scan draws those of a
    deployment with ``max_tokens == max_requests``, not those the 160-row
    scan drew.  Only the per-request schedule is invariant to the width."""
    sample = (jax.random.PRNGKey(5), jnp.float32(TEMPERATURE),
              jnp.float32(TOP_P))
    im = slot_im()
    want = scan(im, feed(im, PROMPTS), sample=sample)
    im = wide_im()
    got = scan(im, feed(im, PROMPTS), sample=sample)
    np.testing.assert_array_equal(got[0][:, :SLOTS], want[0])
    greedy = scan(im, feed(wide_im(), PROMPTS))
    assert (got[0] != greedy[0]).any(), "the draw never left the argmax"


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it, a
    kernel's own body left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_scan_body_is_slot_wide_and_scatters_into_no_cache(kv_dtype):
    im = wide_im(use_pallas=True, kv_dtype=kv_dtype)
    bc = feed(im, PROMPTS)
    allowed = np.full(WIDE, 9, np.int32)
    jaxpr = jax.make_jaxpr(functools.partial(
        im._decode_scan_impl, n_steps=2, eos=None))(
            im.params, im.state, bc, None, None, allowed).jaxpr
    [loop] = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    body = list(_eqns(loop.params["jaxpr"].jaxpr))
    caches = {a.shape for bufs in im.state.values() for a in bufs.values()}
    for e in _eqns(jaxpr):
        if e.primitive.name.startswith("scatter"):
            assert e.invars[0].aval.shape not in caches, e
    names = [e.primitive.name for e in body]
    # the KV write is ONE aliased call a layer (kv_row_write); an int8
    # cache's scale planes stay on the in-place chain, one update-slice
    # per row and plane ...
    planes = 2 if kv_dtype else 0
    written = [e for e in body if e.primitive.name == "dynamic_update_slice"
               and e.invars[0].aval.shape in caches]
    assert len(written) == TINY.num_hidden_layers * planes * SLOTS
    # ... traced once per shape and CALLED from each layer, not unrolled
    # into every layer's trace (what keeps trace + lowering time flat)
    calls = [e for e in body if e.params.get("name") == "_update_rows"]
    assert len(calls) == TINY.num_hidden_layers * planes
    dots = [e for e in body if e.primitive.name == "dot_general"]
    assert dots and all(e.outvars[0].aval.shape[0] == SLOTS for e in dots)
    # the row write, then the decode kernel: both a grid step a slot
    kernels = [e for e in body if e.primitive.name == "pallas_call"]
    assert len(kernels) == 2 * TINY.num_hidden_layers, names
    assert all(k.params["grid_mapping"].grid[0] == SLOTS for k in kernels)
    assert [len(k.params["input_output_aliases"]) for k in kernels] == \
        [2, 0] * TINY.num_hidden_layers
    assert im.attention_paths[
        ("kv_row_write", "one_row_per_request")] == "pallas"
    # nothing in the body is as wide as the flat batch
    assert not any(WIDE in v.aval.shape for e in body for v in e.outvars)


def test_branch_is_skipped_where_flat_capacity_is_the_slot_count():
    """``max_tokens <= max_requests``: nothing to compact — the program
    holds no sort and is what it was before the scan was narrowed."""
    def sorts(im):
        bc = feed(im, PROMPTS)
        assert decode_scan_width(bc) == SLOTS
        jaxpr = jax.make_jaxpr(functools.partial(
            im._decode_scan_impl, n_steps=2, eos=None))(
                im.params, im.state, bc, None, None, None).jaxpr
        return sum(e.primitive.name == "sort" for e in _eqns(jaxpr))

    assert sorts(slot_im()) == 0
    assert sorts(wide_im()) == 1


@pytest.mark.parametrize("plane", ["cache", "scale"])
def test_update_slice_chain_writes_what_indexing_writes(plane):
    """``_update_rows`` serves the [R, H, S, D] caches and the int8 path's
    [R, H, S] scale planes; out-of-range rows and positions clamp."""
    attn = serve_ops.IncMultiHeadSelfAttention
    rng = np.random.RandomState(0)
    shape = (5, 2, 16, 8) if plane == "cache" else (5, 2, 16)
    dtype = jnp.bfloat16 if plane == "cache" else jnp.float32
    cache = jnp.asarray(rng.randn(*shape), dtype)
    rows, pos = np.int32([4, 0, 2, 9]), np.int32([15, 3, 40, 1])
    upd = jnp.asarray(rng.randn(len(rows), *shape[1:2], *shape[3:]), dtype)
    write = attn._scatter_rows_pos if plane == "cache" else attn._scatter_scale
    got = np.asarray(write(cache, jnp.asarray(rows), jnp.asarray(pos), upd)
                     .astype(jnp.float32))
    want = np.asarray(cache.astype(jnp.float32)).copy()
    for r, p, u in zip(np.clip(rows, 0, 4), np.clip(pos, 0, 15),
                       np.asarray(upd.astype(jnp.float32))):
        want[r, :, p] = u
    np.testing.assert_array_equal(got, want)


def _serve(im, gen, arrivals, chained):
    im.reset()
    rm = RequestManager(im, gen)
    if not chained:
        rm.scan_chunk = 1   # the reference: one flat step per token
    joins = []
    inner = rm.im.join_slot

    def join_slot(bc, tok_src, src_idx, dst, *a, **kw):
        joins.append(dst)
        return inner(bc, tok_src, src_idx, dst, *a, **kw)

    rm.im.join_slot = join_slot
    try:
        recs = rm.serve_with_arrivals(list(arrivals), clock=VirtualClock())
    finally:
        del rm.im.join_slot
    return {rid: r["tokens"] for rid, r in recs.items()}, joins


@pytest.mark.parametrize("gen", [
    GenerationConfig(max_new_tokens=6),
    GenerationConfig(max_new_tokens=6, temperature=TEMPERATURE, top_p=TOP_P,
                     seed=11),
], ids=["greedy", "seeded"])
def test_joins_between_narrowed_segments_equal_the_per_tick_loop(gen):
    arrivals = poisson_arrivals(np.random.RandomState(3), 8, rate_per_s=40.0,
                                vocab=TINY.vocab_size)
    im = wide_im()
    want, _ = _serve(im, gen, arrivals, chained=False)
    got, joins = _serve(im, gen, arrivals, chained=True)
    assert got == want
    # arrivals were spliced in at dst = len(rows), behind running rows
    assert joins and max(joins) >= 1


def test_chain_warns_on_the_scans_width_and_the_kernel_has_no_bound(
        monkeypatch):
    """The width that costs is the chain's: a scan whose rows stay on the
    update-slice chain is warned past ``SCAN_DUS_MAX_ROWS`` as its program
    is traced; one whose rows go in by ``kv_row_write`` is not held by it,
    and the guard itself has no bound on the width."""
    def trace(im):
        return jax.make_jaxpr(functools.partial(
            im._decode_scan_impl, n_steps=2, eos=None))(
                im.params, im.state, feed(im, PROMPTS), None, None,
                np.full(WIDE, 9, np.int32))

    im = wide_im()
    bc = BatchConfig.build([1], [0], [3], [4] * SLOTS,
                           max_tokens=WIDE, max_requests=SLOTS)
    monkeypatch.setattr(serve_ops, "SCAN_DUS_MAX_ROWS", SLOTS - 1)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert im._decode_scan_guards(bc, 2, max_position=3) == SLOTS
        trace(wide_im(use_pallas=True))
    with pytest.warns(UserWarning, match=f"writes {SLOTS} rows"):
        trace(im)


def test_dispatch_span_carries_the_scans_width():
    im = wide_im()
    tel = Telemetry()
    rm = RequestManager(im, GenerationConfig(max_new_tokens=6),
                        telemetry=tel)
    try:
        rm.generate([PROMPTS[0], PROMPTS[1]])
    finally:
        im.telemetry = NULL_TELEMETRY
    spans = [e for e in tel.trace.trace_events()
             if e["ph"] == "X" and e["name"] == "decode_scan_dispatch"]
    assert spans
    for e in spans:
        assert e["args"]["width"] == SLOTS
        assert 0 < e["args"]["rows"] <= e["args"]["width"]
