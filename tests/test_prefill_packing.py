"""A prefill wave lays its prompts' tiles end to end in shared chunks.

``RequestManager._prefill_chunks`` cuts a feed of several requests into
chunks of whole tiles that the requests SHARE: a request's remaining prompt
is ``ceil(left / tile)`` tiles, they fill the open chunk, the rest opens the
next, the next request starts on the next free tile.  Pinned here, at toy
size and with the kernels interpreted:

* the cutter alone, on drawn waves: every tile one request's, tile-aligned
  starts, at most one segment a slot and chunk, the chunk count, the sample
  points / logit slots / folds at the chunk and index where a prompt ends,
  and a feed of ONE request equal to the per-request cut field for field;
* a packed wave against the same wave cut per request (``PerRequest``, the
  cutter before): the same tokens and the same caches and states, for the
  toy decoder (gate on and off, sampled) and for each hybrid family the repo
  has a tiny configuration of;
* the set of prefill-scan lengths closes under what has run
  (``InferenceManager.prefill_scan``): a new length is preceded by every
  smaller power of two on all-pad chunks, which move no slot's state, and a
  later feed is cut no longer than the longest length run with every
  shorter one.
"""

import numpy as np
import pytest

from flexflow_tpu.obs import NULL_TELEMETRY, Telemetry
from flexflow_tpu.serve import GenerationConfig, RequestManager
from flexflow_tpu.serve.batch_config import PrefillBatchConfig
from flexflow_tpu.serve.request_manager import RequestStatus

from test_prompt_feed import spans
from test_serve import TINY, make_im


class PerRequest(RequestManager):
    """The cutter before: every request's prompt in chunks of ITS OWN (a
    feed of one request is cut as it always was, so feeding the requests
    one by one is that cut)."""

    def _prefill_chunks(self, gate, sampling, reqs=None, depths=None):
        out = ([], [], [], [], [])
        for req in (self._active() if reqs is None else reqs):
            cut = super()._prefill_chunks(gate, sampling, [req], depths)
            cut[3][:] = [(i + len(out[0]), ridx, rid)
                         for i, ridx, rid in cut[3]]
            for acc, part in zip(out, cut):
                acc.extend(part)
        return out


# ---------------------------------------------------------------------------
# (a) the cutter alone
# ---------------------------------------------------------------------------
SHAPES = {
    # name: (max_tokens, slots, max_seq) -> tile
    "toy": (24, 4, 128),         # tile 8, three tiles a chunk
    "bench": (512, 8, 4096),     # tile 128, four tiles a chunk: the cells'
}
WAVES = {
    "toy": [[3, 19, 30, 41], [8, 8, 8, 8], [64], [25, 1, 24], [17, 40]],
    "bench": [[1462, 1024, 1900, 1100, 1337, 1800, 1025, 1500],
              [128, 128, 128], [513, 700], [2048], [1, 2000, 129]],
}
CUTS = [(shape, i) for shape in SHAPES for i in range(len(WAVES[shape]))]


def cutter_rm(shape, lengths, cls=RequestManager, temperature=0.0):
    """A manager whose wave of ``lengths`` is admitted and not yet fed."""
    cap, slots, seq = SHAPES[shape]
    im = make_im(max_tokens=cap, max_requests=slots, max_seq=seq,
                 use_pallas=True)
    rm = cls(im, GenerationConfig(max_new_tokens=4, temperature=temperature,
                                  seed=11))
    rng = np.random.RandomState(sum(lengths))
    prompts = [rng.randint(1, TINY.vocab_size, size=n).tolist()
               for n in lengths]
    rids = [rm.register_new_request(p) for p in prompts]
    assert rm._prefill_stretch_possible()
    return rm, [rm.requests[r] for r in rids]


def per_request_reference(rm, reqs, gate, sampling):
    """The cut written out as it was before requests shared chunks: one
    segment a chunk.  Returns what ``_prefill_chunks`` returns."""
    im = rm.im
    tile, cap = im.prefill_tile, im.max_tokens
    n_rows = im.max_requests if gate else cap
    chunks, ls_chunks, fold_chunks, points, feeds = [], [], [], [], []
    seq = np.zeros(im.max_requests, np.int32)
    offset = {r.rid: 0 for r in reqs}
    for req in reqs:
        toks = req.prefill_tokens
        while offset[req.rid] < len(toks):
            start = offset[req.rid]
            take = min((cap // tile) * tile, len(toks) - start)
            seq[req.slot] = start + take
            fields, last_flat = PrefillBatchConfig.np_fields(
                [(req.slot, toks[start: start + take], start)], seq, tile,
                max_tokens=cap, max_requests=im.max_requests)
            offset[req.rid] = start + take
            done = offset[req.rid] == len(toks)
            ridx = req.slot if gate else last_flat[req.slot]
            if done:
                points.append((len(chunks), ridx, req.rid))
            if sampling:
                fc = np.zeros((n_rows, 2), np.int32)
                if done:
                    fc[ridx] = (req.rid, 0)
                fold_chunks.append(fc)
            ls_chunks.append(PrefillBatchConfig.np_logit_slots(
                [req.slot] if done else [], last_flat, im.max_requests))
            chunks.append(fields)
            feeds.append([(start, take)])
    return chunks, ls_chunks, fold_chunks, points, feeds


def assert_same_cut(got, want):
    for g, w in zip(got[:3], want[:3]):
        assert len(g) == len(w)
        for a, b in zip(g, w):
            for x, y in zip(a if isinstance(a, tuple) else (a,),
                            b if isinstance(b, tuple) else (b,)):
                np.testing.assert_array_equal(x, y)
    assert got[3] == want[3] and got[4] == want[4]


@pytest.mark.parametrize("gate", [True, False], ids=["gated", "ungated"])
@pytest.mark.parametrize("shape,wave", CUTS,
                         ids=[f"{s}-{i}" for s, i in CUTS])
def test_the_cutter_lays_tiles_end_to_end(shape, wave, gate):
    lengths = WAVES[shape][wave]
    rm, reqs = cutter_rm(shape, lengths, temperature=0.7)
    im = rm.im
    tile, cap = im.prefill_tile, im.max_tokens
    per = cap // tile
    chunks, ls_chunks, fold_chunks, points, feeds = rm._prefill_chunks(
        gate, True)
    assert all(r.prefill_offset == len(r.prefill_tokens) for r in reqs)
    tiles = sum(-(-n // tile) for n in lengths)
    assert len(chunks) == -(-tiles // per) == len(ls_chunks) \
        == len(fold_chunks) == len(feeds)
    by_slot = {r.slot: r for r in reqs}
    fed = {r.slot: [] for r in reqs}      # slot -> [(chunk, position, token)]
    ends = {}                             # rid -> (chunk, flat index)
    for c, (tokens, req, pos, n, seq_lens) in enumerate(chunks):
        assert tokens.shape == (cap,)
        segments = []                     # (slot, first tile, start, take)
        for g in range(per):
            rows = slice(g * tile, (g + 1) * tile)
            live = req[rows] >= 0
            count = int(live.sum())
            if not count:
                continue
            # one request's, at the tile's head, on a tile of its cache row
            assert live[:count].all() and len(set(req[rows][:count])) == 1
            slot = int(req[rows][0])
            start = int(pos[rows][0])
            assert start % tile == 0
            np.testing.assert_array_equal(
                pos[rows][:count], np.arange(start, start + count))
            fed[slot] += [(c, int(p), int(t)) for p, t in
                          zip(pos[rows][:count], tokens[rows][:count])]
            if segments and segments[-1][0] == slot:
                s, g0, st, take = segments[-1]
                # the same segment goes on: whole tiles so far, next tile
                assert take == (g - g0) * tile and start == st + take
                segments[-1] = (s, g0, st, take + count)
            else:
                segments.append((slot, g, start, count))
        # at most one segment a slot and chunk, laid with no gap between
        assert len({s for s, *_ in segments}) == len(segments)
        at = 0
        for slot, g0, start, take in segments:
            assert g0 * tile == at
            at += -(-take // tile) * tile
            assert seq_lens[slot] == start + take
            if start + take == len(by_slot[slot].prefill_tokens):
                ends[by_slot[slot].rid] = (c, g0 * tile + take - 1)
        assert feeds[c] == [(st, t) for _, _, st, t in segments]
        flat_end = max(g0 * tile + take for _, g0, _, take in segments)
        assert int(n) == flat_end, "num_tokens: the index past the last row"
    for r in reqs:
        got = fed[r.slot]
        assert [t for _, _, t in got] == r.prefill_tokens
        assert [p for _, p, _ in got] == list(range(len(r.prefill_tokens)))
        # consecutive chunks, in order
        cs = sorted({c for c, _, _ in got})
        assert cs == list(range(cs[0], cs[-1] + 1))
    # one sample point a prompt, at the chunk and index where it ends
    assert sorted(rid for _, _, rid in points) == sorted(r.rid for r in reqs)
    want_ls = np.full((len(chunks), im.max_requests), -1, np.int32)
    want_fold = np.zeros((len(chunks), im.max_requests if gate else cap, 2),
                         np.int32)
    for c, ridx, rid in points:
        req_ = rm.requests[rid]
        end_chunk, end_flat = ends[rid]
        assert c == end_chunk
        assert ridx == (req_.slot if gate else end_flat)
        want_ls[c, req_.slot] = end_flat
        want_fold[c, ridx] = (rid, 0)
    np.testing.assert_array_equal(np.stack(ls_chunks), want_ls)
    np.testing.assert_array_equal(np.stack(fold_chunks), want_fold)


@pytest.mark.parametrize("gate", [True, False], ids=["gated", "ungated"])
@pytest.mark.parametrize("shape,n", [("toy", 3), ("toy", 24), ("toy", 51),
                                     ("bench", 128), ("bench", 1462),
                                     ("bench", 2048)])
def test_a_feed_of_one_request_is_the_cut_it_was(shape, n, gate):
    rm, reqs = cutter_rm(shape, [n], temperature=0.7)
    want = per_request_reference(rm, reqs, gate, True)
    assert_same_cut(rm._prefill_chunks(gate, True), want)


@pytest.mark.parametrize("shape,wave", CUTS,
                         ids=[f"{s}-{i}" for s, i in CUTS])
def test_the_per_request_cut_is_the_reference(shape, wave):
    # the comparison class of the tests below IS the cut that was: the
    # packed cutter handed one request at a time
    rm, reqs = cutter_rm(shape, WAVES[shape][wave], cls=PerRequest)
    want = per_request_reference(rm, reqs, True, False)
    assert_same_cut(rm._prefill_chunks(True, False), want)


# ---------------------------------------------------------------------------
# (b), (c) a packed wave is the wave cut per request: tokens, caches, states
# ---------------------------------------------------------------------------
def slot_state(im):
    """Every per-slot buffer without its scratch row (pads land there, and
    another cut makes other pads)."""
    out = {}
    for node, bufs in im.state.items():
        for name, a in bufs.items():
            assert a.shape[0] == im.max_requests + 1, (node, name, a.shape)
            out[node, name] = np.asarray(a)[: im.max_requests]
    return out


def serve_wave(cls, im, prompts, gen, telemetry=None):
    """One wave on an empty deployment: the state its prompts leave (after
    the prefill stretch's tick) and every request's tokens."""
    im.reset()
    rm = cls(im, gen, telemetry=telemetry)
    try:
        rids = [rm.register_new_request(p) for p in prompts]
        rm._serve_tick()
        assert all(rm.requests[r].status is not RequestStatus.PREFILLING
                   and len(rm.requests[r].generated) == 1 for r in rids)
        state = slot_state(im)
        while rm.has_work():
            rm._serve_tick()
    finally:
        im.telemetry = NULL_TELEMETRY
    return state, [rm.requests[r].generated for r in rids]


def assert_packed_equals_per_request(im, prompts, gen, chunks, exact=True):
    tile, cap = im.prefill_tile, im.max_tokens
    per = cap // tile
    tel = Telemetry()
    state, toks = serve_wave(RequestManager, im, prompts, gen, tel)
    want_state, want = serve_wave(PerRequest, im, prompts, gen)
    scans = [a for a in spans(tel, "prefill_scan_dispatch")
             if not a.get("pad")]
    packed = -(-sum(-(-len(p) // tile) for p in prompts) // per)
    assert (packed, sum(-(-len(p) // (per * tile)) for p in prompts)) \
        == chunks, "the case does not pack what it says"
    assert sum(a["n_chunks"] for a in scans) == packed
    assert sum(a["segments"] for a in scans) > packed
    assert sum(a["prompt_tokens"] for a in scans) == sum(map(len, prompts))
    snap = tel.metrics.snapshot()
    assert snap["prompt_feed.tiled_chunks"] == packed
    assert 0 < snap["prompt_feed.shared_chunks"] <= packed
    assert toks == want, "sharing chunks changed a request's tokens"
    assert state.keys() == want_state.keys()
    for key, a in want_state.items():
        if exact:
            np.testing.assert_array_equal(state[key], a, err_msg=str(key))
        else:
            np.testing.assert_allclose(state[key], a, atol=2e-5, rtol=1e-4,
                                       err_msg=str(key))


def toy_prompts(lengths, seed=9):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, TINY.vocab_size, size=n).tolist() for n in lengths]


TOY_WAVES = {
    # lengths -> (packed chunks, chunks cut per request); tile 8, 3 a chunk
    "unequal": ([3, 19, 30, 41], (5, 6)),
    "two_end_in_one_chunk": ([5, 7, 40], (3, 4)),
    "crosses_a_chunk_end": ([20, 30, 9], (3, 4)),   # the 2nd: 1 + 3 tiles
    "five_tiles_each": ([33, 36, 40], (5, 6)),
}


@pytest.mark.parametrize("how", ["gated", "ungated", "sampled",
                                 "sampled_ungated"])
@pytest.mark.parametrize("wave", sorted(TOY_WAVES))
def test_a_packed_wave_is_the_wave_cut_per_request(wave, how):
    lengths, chunks = TOY_WAVES[wave]
    im = make_im(max_tokens=24, max_requests=4, max_seq=64, use_pallas=True)
    assert im.prefill_tile == 8
    gen = GenerationConfig(
        max_new_tokens=6, stop_on_eos=False, seed=3,
        temperature=0.8 if how.startswith("sampled") else 0.0, top_p=0.9)
    im.gate_lm_head = not how.endswith("ungated")
    try:
        assert_packed_equals_per_request(im, toy_prompts(lengths), gen,
                                         chunks)
    finally:
        im.gate_lm_head = True


def _phi4flash():
    import test_phi4flash as m
    return m.deployment(use_pallas=True, cap=192), m.tokens


def _evabyte():
    import test_evabyte as m
    return m.deployment(use_pallas=True, cap=24), m.tokens


def _minicpm_sala():
    import test_minicpm_sala as m
    return m.seeded(m.build(cap=48, use_pallas=True)), m.tokens


def _nemotron_h():
    import test_nemotron_h as m
    return m.seeded(m.build(cap=48, use_pallas=True)), m.tokens


def _cohere2_moe():
    import test_cohere2_moe as m
    return m.deployment(use_pallas=True), m.tokens


HYBRIDS = {
    # family: (fixture, prompt lengths, (packed chunks, per request))
    # SambaY: Mamba, window rings, the cross-decoder's shared cache; tile 64
    "phi4flash": (_phi4flash, [5, 70, 100, 40], (2, 4)),
    # the compacting cache: windows of 32 close inside and across chunks of
    # 24 rows (a step may cross one window's end, so no chunk is longer)
    "evabyte": (_evabyte, [9, 65, 25], (5, 6)),
    # sparse selection past dense_len 48, the index, lightning's state
    # (its chunked form sums in an order that follows the cut: rounding)
    "minicpm_sala": (_minicpm_sala, [20, 100, 50], (5, 6), False),
    # Mamba-2's SSD state (chunked form, as lightning's) and conv tail,
    # routed experts
    "nemotron_h": (_nemotron_h, [20, 70, 50], (4, 5), False),
    # rings of 48 beside a full-length layer, gated experts
    "cohere2_moe": (_cohere2_moe, [20, 150, 37], (5, 6)),
}


@pytest.mark.parametrize("family", sorted(HYBRIDS))
def test_a_hybrid_family_serves_a_packed_wave(family):
    fixture, lengths, chunks, *exact = HYBRIDS[family]
    im, tokens = fixture()
    assert im.prefill_tile * 3 == im.max_tokens
    prompts = [tokens(n, salt=40 + i) for i, n in enumerate(lengths)]
    gen = GenerationConfig(max_new_tokens=8, stop_on_eos=False)
    assert_packed_equals_per_request(im, prompts, gen, chunks, *exact)


# ---------------------------------------------------------------------------
# the set of scan lengths closes under what has run
# ---------------------------------------------------------------------------
def fresh_toy_im():
    """An ``InferenceManager`` of its own (the cached one has run scans)."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.model import FFModel
    from flexflow_tpu.parallel.mesh import make_mesh
    from flexflow_tpu.serve.inference_manager import InferenceManager
    from flexflow_tpu.serve.models.base import build_model
    import jax

    ff = FFModel(FFConfig(), mesh=make_mesh({"tp": 1}, jax.devices()[:1]))
    build_model(ff, TINY, 24)
    im = InferenceManager(ff, max_requests=4, max_tokens_per_batch=24,
                          max_seq_len=256, use_pallas=True)
    im.init_operators_inference(rng=jax.random.PRNGKey(7))
    return im


def scan_wave(im, lengths, gen, before=None):
    """Serve one wave of prompts of ``lengths`` on the emptied deployment
    (``before(rm)`` first): its state and tokens, the lengths of the pad
    scans and of the prompt scans, the counters."""
    tel = Telemetry()
    im.reset()
    if before is not None:
        rm = RequestManager(im, gen, telemetry=tel)
        before(rm)
        im.telemetry = NULL_TELEMETRY
    state, toks = serve_wave(RequestManager, im, toy_prompts(lengths), gen,
                             tel)
    scans = spans(tel, "prefill_scan_dispatch")
    return (state, toks,
            [a["n_chunks"] for a in scans if a.get("pad")],
            [a["n_chunks"] for a in scans if not a.get("pad")],
            tel.metrics.snapshot())


@pytest.mark.parametrize("how", ["greedy", "sampled"])
def test_the_scan_lengths_close_under_what_has_run(how):
    im = fresh_toy_im()
    sampled = how == "sampled"
    gen = GenerationConfig(max_new_tokens=3, stop_on_eos=False, seed=5,
                           temperature=0.8 if sampled else 0.0)
    # the key is the sample argument's structure, derived in one place
    this, other = ((0, 0, 0, 0), None) if sampled else (None, (0, 0, 0, 0))
    assert im.prefill_scan_longest(True, this) == 0

    def wave(lengths):
        return scan_wave(im, lengths, gen)

    # 4 x 6 tiles = 8 chunks, the first feed: 1, 2 and 4 run first, on pads
    state, toks, pads, fed, snap = wave([48, 48, 48, 48])
    assert (pads, fed) == ([1, 2, 4], [8])
    assert snap["prompt_feed.tiled_chunks"] == 8, "a pad scan is no chunk"
    assert im.prefill_scan_longest(True, this) == 8
    assert im.prefill_scan_longest(True, other) == 0
    # ... and they moved no slot's state: the same wave again, no pad now
    state2, toks2, pads, fed, _ = wave([48, 48, 48, 48])
    assert (pads, fed) == ([], [8]) and toks2 == toks
    for key, a in state.items():
        np.testing.assert_array_equal(state2[key], a, err_msg=str(key))
    # a feed of 2 n chunks is n + n, not a program of its own
    _, _, pads, fed, _ = wave([96, 96, 96, 96])
    assert (pads, fed) == ([], [8, 8])
    _, _, pads, fed, _ = wave([96, 96, 96, 30])
    assert (pads, fed) == ([], [8, 4, 2])
    # the journal counts a pad scan as a launch, and as no prompt chunk
    rm = RequestManager(fresh_toy_im(), gen)
    rm.generate(toy_prompts([48, 48]), 2)
    from flexflow_tpu.obs import journal as J

    rows = rm.journal.array()
    total = {name: int(rows[:, i].sum()) for i, name in enumerate(J.FIELDS)}
    assert total["prefill_scans"] == 3 and total["chunks"] == 4
    assert total["chunk_tokens"] == 96


def test_no_pad_scan_is_spent_on_a_length_the_feed_runs_itself():
    im = fresh_toy_im()
    gen = GenerationConfig(max_new_tokens=2, stop_on_eos=False)
    # 11 chunks = 8 + 2 + 1: the feed's own cut builds 2 and 1, a pad 4
    _, _, pads, fed, _ = scan_wave(im, [72, 72, 72, 48], gen)
    assert (pads, fed) == ([4], [8, 2, 1])
    assert im.prefill_scan_longest(True) == 8
    # a length run out of turn (a caller of ``prefill_scan`` itself) is no
    # length to cut by until every shorter one has run
    other = fresh_toy_im()
    other._pscan_ran(True, None).update({1, 2, 8})
    assert other.prefill_scan_longest(True) == 2


def test_a_deployment_asks_for_the_longest_wave_it_expects():
    im = fresh_toy_im()
    gen = GenerationConfig(max_new_tokens=2, stop_on_eos=False)
    # the first feed is short: two chunks bound every later launch
    _, _, pads, fed, _ = scan_wave(im, [24, 24], gen)
    assert (pads, fed) == ([1], [2])
    state, toks, pads, fed, _ = scan_wave(im, [48, 48, 48, 48], gen)
    assert (pads, fed) == ([], [2, 2, 2, 2])
    # ... until the deployment asks: 4 and 8 on pads, and the wave is one
    state2, toks2, pads, fed, _ = scan_wave(
        im, [48, 48, 48, 48], gen, lambda rm: rm.build_prefill_scans(8))
    assert (pads, fed) == ([4, 8], [8]) and toks2 == toks
    for key, a in state.items():
        np.testing.assert_array_equal(state2[key], a, err_msg=str(key))
    assert im.prefill_scan_longest(True) == 8
    # built already: nothing runs
    _, _, pads, fed, _ = scan_wave(
        im, [48], gen, lambda rm: rm.build_prefill_scans(8))
    assert (pads, fed) == ([], [2])


def _toy_live():
    return (make_im(max_tokens=24, max_requests=4, max_seq=128,
                    use_pallas=True),
            lambda n, salt: toy_prompts([n], seed=salt)[0])


LIVE = {
    "toy": (_toy_live, 0.0), "toy_sampled": (_toy_live, 0.8),
    **{name: (fixture, 0.0) for name, (fixture, *_) in HYBRIDS.items()},
}


@pytest.mark.parametrize("model", sorted(LIVE))
def test_a_pad_scan_moves_no_live_slot(model):
    # pad scans run under load too (a length's first feed among decoders,
    # ``build_prefill_scans``): two slots decode, the pads run, and every
    # slot's cache and state, and the tokens that follow, are what they
    # are without them
    fixture, temperature = LIVE[model]
    im, tokens = fixture()
    prompts = [tokens(n, salt=70 + n) for n in (19, 30)]
    gen = GenerationConfig(max_new_tokens=12, stop_on_eos=False, seed=2,
                           temperature=temperature)

    def serve(pad):
        im.reset()
        tel = Telemetry()
        rm = RequestManager(im, gen, telemetry=tel)
        try:
            rids = [rm.register_new_request(p) for p in prompts]
            while min(len(rm.requests[r].generated) for r in rids) < 4:
                rm._serve_tick()
            assert all(rm.requests[r].status is RequestStatus.DECODING
                       for r in rids)
            if pad:
                before = slot_state(im)
                ran = len(spans(tel, "prefill_scan_dispatch"))
                im._pscan_lengths.clear()       # as if none had run
                rm.build_prefill_scans(4)
                assert [(a["n_chunks"], a["pad"]) for a in
                        spans(tel, "prefill_scan_dispatch")[ran:]] \
                    == [(1, 1), (2, 1), (4, 1)]
                for key, a in slot_state(im).items():
                    np.testing.assert_array_equal(a, before[key],
                                                  err_msg=str(key))
            while rm.has_work():
                rm._serve_tick()
        finally:
            im.telemetry = NULL_TELEMETRY
        return [rm.requests[r].generated for r in rids]

    assert serve(True) == serve(False)
