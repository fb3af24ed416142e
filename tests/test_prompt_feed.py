"""A prompt that meets live decoders rides the tiled prefill scan.

``RequestManager`` feeds a prompt through ``im.prefill_scan`` (tile-aligned
``PrefillBatchConfig`` chunks: the Q-tiled kernel, block KV writes, a gated
LM head) wherever ``_tiled_feed`` holds, and splices it into the running
batch with ``join_slot`` — at a segment boundary of a decode stretch, and
before the first segment for requests the tick's admission slotted among
decoders.  No prompt row goes through the flat step then.  Where the
predicate says no (no Pallas, a manager without ``prefill_scan``, an
off-tile offset) the flat feed runs as it always did.
Pinned at toy size, kernels interpreted: the tokens of every request are
those of the request served alone and those of the flat feed; the dispatch
spans and the ``prompt_feed.*`` counters say which path fed what; and after
``benchmark.warmup.warm`` nothing the new path launches lowers or compiles.
"""

import functools

import jax
import numpy as np
import pytest

from flexflow_tpu.obs import Telemetry
from flexflow_tpu.serve import GenerationConfig, RequestManager
from flexflow_tpu.serve.request_manager import RequestStatus

from test_serve import TINY, make_im

CAP, SLOTS, SEQ = 24, 4, 64        # tile 8: three tiles a chunk
GEN = GenerationConfig(max_new_tokens=12)
_RNG = np.random.RandomState(5)
FIRST = _RNG.randint(1, TINY.vocab_size, size=5).tolist()
# the joiners of each case: shorter than a tile, whole tiles, longer than
# max_tokens (several chunks), two at one boundary
JOINERS = {
    "short": [3],
    "tiles": [16],
    "chunks": [2 * CAP + 3],
    "two": [7, CAP],
}
PROMPTS = {k: [_RNG.randint(1, TINY.vocab_size, size=n).tolist() for n in v]
           for k, v in JOINERS.items()}


def pallas_im():
    im = make_im(max_tokens=CAP, max_requests=SLOTS, max_seq=SEQ,
                 use_pallas=True)
    assert im.prefill_tile == 8 and im.gate_lm_head
    im.reset()
    return im


class FlatFeed(RequestManager):
    """The scheduler with the tiled feed of joiners turned away: what every
    prompt among decoders rode before."""

    def _tiled_feed(self, req, joining=False):
        return not joining and super()._tiled_feed(req)


@functools.lru_cache(maxsize=None)
def alone(prompt):
    im = pallas_im()
    return RequestManager(im, GEN).generate([list(prompt)])[0]


def serve_with_joiners(rm, prompts, where):
    """``FIRST`` decodes; ``prompts`` arrive among it — registered before
    the tick (``where`` = "tick": admitted at its start) or by the arrival
    pump at the first segment boundary ("boundary").  Returns every
    request's tokens, ``FIRST`` first."""
    rids = [rm.register_new_request(FIRST)]
    while not rm.requests[rids[0]].generated:
        rm._serve_tick()

    def arrive():
        if len(rids) == 1:
            rids.extend(rm.register_new_request(p) for p in prompts)

    if where == "tick":
        arrive()
    else:
        rm._arrival_pump = arrive
    rm._serve_tick()
    rm._arrival_pump = None
    assert len(rids) == 1 + len(prompts)
    while rm.has_work():
        rm._serve_tick()
    return [rm.requests[r].generated for r in rids]


def spans(tel, name):
    return [e["args"] for e in tel.trace.trace_events()
            if e["ph"] == "X" and e["name"] == name]


@pytest.mark.parametrize("where", ["boundary", "tick"])
@pytest.mark.parametrize("case", sorted(JOINERS))
def test_a_joiner_rides_the_tiled_scan(case, where):
    prompts = PROMPTS[case]
    want = [alone(tuple(FIRST))] + [alone(tuple(p)) for p in prompts]
    tel = Telemetry()
    got = serve_with_joiners(
        RequestManager(pallas_im(), GEN, telemetry=tel), prompts, where)
    assert got == want, "the tiled feed changed a request's tokens"
    flat = serve_with_joiners(FlatFeed(pallas_im(), GEN), prompts, where)
    assert flat == want, "the flat feed disagrees with the request alone"
    # no prompt row went through the flat step ...
    steps = spans(tel, "step_dispatch")
    assert all(a["prompt_tokens"] == 0 for a in steps), steps
    # ... every one went through the prefill scan: FIRST as a wave, the
    # joiners among one live decode row, one joiner per launch
    scans = spans(tel, "prefill_scan_dispatch")
    fed = len(FIRST) + sum(JOINERS[case])
    assert sum(a["prompt_tokens"] for a in scans) == fed
    joins = [a for a in scans if a["joiners"]]
    assert sum(a["prompt_tokens"] for a in joins) == sum(JOINERS[case])
    # (the second joiner of two meets the first as a live row)
    assert all(a["joiners"] == 1 and 1 <= a["rows"] <= len(prompts)
               for a in joins)
    assert [a["joiners"] for a in scans if a not in joins] == [0]
    # the counters read what was fed
    chunks = 1 + sum(-(-n // CAP) for n in JOINERS[case])
    snap = tel.metrics.snapshot()
    assert snap["prompt_feed.tiled_tokens"] == fed
    assert "prompt_feed.flat_tokens" not in snap
    assert snap["prompt_feed.tiled_chunks"] == chunks
    assert snap["prompt_feed.tiled_padded_rows"] == chunks * CAP - fed
    assert snap["stretch_joins"] == len(prompts)
    # the commit spans still say which program made each token
    made = {k: sum(a.get(k, 0) for a in spans(tel, "commit"))
            for k in ("scan_tokens", "join_tokens", "step_tokens",
                      "prefill_tokens")}
    assert made["join_tokens"] == len(prompts) and made["prefill_tokens"] == 1
    assert sum(made.values()) == sum(len(t) for t in got)


def off_tile(rm):
    """A prefix-cache hit or a starvation fallback leaves a feed off its
    tile: here every joiner starts 3 tokens in (their KV fed by hand)."""
    real = rm._kv_bind

    def bind(rid):
        real(rid)
        req = rm.requests[rid]
        if req.prompt != FIRST and not req.prefill_offset:
            from flexflow_tpu.serve import BatchConfig

            seq = np.zeros(rm.im.max_requests, np.int32)
            seq[req.slot] = 3
            rm.im.step(BatchConfig.build(
                req.prompt[:3], [req.slot] * 3, range(3), seq,
                max_tokens=rm.im.max_tokens,
                max_requests=rm.im.max_requests))
            req.prefill_offset = 3

    rm._kv_bind = bind


class NoPrefillScan:
    """An inference manager without ``prefill_scan`` (serve/pp.py's)."""

    def __init__(self, im):
        object.__setattr__(self, "_im", im)

    def __getattr__(self, name):
        if name == "prefill_scan":
            raise AttributeError(name)
        return getattr(self._im, name)

    def __setattr__(self, name, value):
        setattr(self._im, name, value)


FALLBACKS = {
    "off_tile_offset": dict(pallas=True, prepare=off_tile),
    "no_prefill_scan": dict(pallas=True, wrap=NoPrefillScan),
    "no_pallas": dict(pallas=False),
}


@pytest.mark.parametrize("where", ["boundary", "tick"])
@pytest.mark.parametrize("case", sorted(FALLBACKS))
def test_the_fallbacks_keep_the_flat_feed(case, where):
    spec = FALLBACKS[case]
    prompts = PROMPTS["two"]

    def fresh():
        im = pallas_im() if spec["pallas"] else make_im(
            max_tokens=CAP, max_requests=SLOTS, max_seq=SEQ)
        im.reset()
        return spec["wrap"](im) if "wrap" in spec else im

    want = [RequestManager(fresh(), GEN).generate([p])[0]
            for p in [FIRST] + prompts]
    tel = Telemetry()
    rm = RequestManager(fresh(), GEN, telemetry=tel)
    if "prepare" in spec:
        spec["prepare"](rm)
    assert serve_with_joiners(rm, prompts, where) == want
    # the joiners' prompt rows rode flat steps: all of them, except that a
    # feed the mixed step has brought back ONTO a tile (its take is rounded
    # for that) goes on tiled
    snap = tel.metrics.snapshot()
    flat = snap["prompt_feed.flat_tokens"]
    hand_fed = 3 * len(prompts) if case == "off_tile_offset" else 0
    joined = sum(len(p) for p in prompts) - hand_fed
    assert snap.get("prompt_feed.tiled_tokens", 0) + flat \
        == len(FIRST) + joined
    steps = spans(tel, "step_dispatch")
    if case == "off_tile_offset":
        first = next(a for a in steps if a.get("prompt_tokens"))
        assert 0 < first["prompt_tokens"] <= flat <= joined
    else:
        # (without the kernels the first request's prompt is flat too)
        assert flat == joined + (len(FIRST) if case == "no_pallas" else 0)
        assert not [a for a in spans(tel, "prefill_scan_dispatch")
                    if a["joiners"]]
    # (a pure-prefill step of a manager without the scan is tiled, through
    # ``im.step``: its rows are no flat rows)
    assert sum(a.get("prompt_tokens", 0) for a in steps) == flat + (
        len(FIRST) if case == "no_prefill_scan" else 0)


def test_a_one_token_trailer_does_not_send_a_prompt_flat():
    # a decoder with ONE token left, and a prompt admitted beside it: the
    # stretch is 2 steps (the trailer freezes on device after its token),
    # not a mixed flat step
    tel = Telemetry()
    rm = RequestManager(pallas_im(), GEN, telemetry=tel)
    r0 = rm.register_new_request(FIRST, 2)
    while not rm.requests[r0].generated:
        rm._serve_tick()
    assert len(rm.requests[r0].generated) == 1
    r1 = rm.register_new_request(PROMPTS["tiles"][0])
    rm._serve_tick()
    assert rm.requests[r0].status is RequestStatus.COMPLETED
    assert rm.requests[r0].generated == alone(tuple(FIRST))[:2]
    assert len(rm.requests[r1].generated) > 2
    assert not spans(tel, "step_dispatch")
    first = spans(tel, "decode_scan_dispatch")[0]
    assert first["n_steps"] == 2 and first["rows"] == 2
    while rm.has_work():
        rm._serve_tick()
    assert rm.requests[r1].generated == alone(tuple(PROMPTS["tiles"][0]))


@pytest.mark.parametrize("where", ["boundary", "tick"])
def test_a_failed_tiled_feed_requeues_the_joiner_alone(where):
    # the joiner's prefill scan faults past the retry budget: the joiner
    # goes back to the queue (recompute), the decoder it met is untouched,
    # and both end with the tokens they get alone
    from flexflow_tpu.serve.resilience import (FaultInjector,
                                               ResilienceConfig, RetryPolicy)

    prompt = PROMPTS["chunks"][0]
    im = pallas_im()
    rm = RequestManager(im, GEN, resilience=ResilienceConfig(
        retry=RetryPolicy(max_retries=0), on_dispatch_failure="requeue"))
    rm._sleep = lambda s: None
    rids = [rm.register_new_request(FIRST)]
    while not rm.requests[rids[0]].generated:
        rm._serve_tick()
    inj = FaultInjector(seed=0, p_by_site={"prefill_scan": 1.0},
                        max_faults=1)
    im.fault_injector = inj

    def arrive():
        if len(rids) == 1:
            rids.append(rm.register_new_request(prompt))

    try:
        if where == "tick":
            arrive()
        else:
            rm._arrival_pump = arrive
        rm._serve_tick()
        rm._arrival_pump = None
        joiner = rm.requests[rids[1]]
        assert inj.injected == 1 and joiner.requeues == 1
        assert rm.requests[rids[0]].requeues == 0
        while rm.has_work():
            rm._serve_tick()
    finally:
        im.fault_injector = None
    assert rm.requests[rids[0]].generated == alone(tuple(FIRST))
    assert joiner.generated == alone(tuple(prompt))


@pytest.mark.parametrize("where", ["boundary", "tick"])
def test_a_hybrid_model_joiner_starts_from_zero_state(where):
    # phi4flash at toy size: conv tail, scan state, window ring and the
    # shared cache are per slot.  A joiner fed through the prefill scan
    # into a slot a LONGER request left warm, beside a live decoder, gets
    # the tokens it gets alone — and the decoder keeps its own
    from test_phi4flash import deployment, tokens

    im = deployment(use_pallas=True)
    gen = GenerationConfig(max_new_tokens=10, stop_on_eos=False)
    first, joiner = tokens(40, salt=31), tokens(70, salt=32)
    want = []
    for p in (first, joiner):
        im.reset()
        want.append(RequestManager(im, gen).generate([p])[0])
    im.reset()
    tel = Telemetry()
    rm = RequestManager(im, gen, telemetry=tel)
    try:
        rm.generate([tokens(50, salt=33), tokens(90, salt=34)])  # warm slots
        rids = [rm.register_new_request(first)]
        while not rm.requests[rids[0]].generated:
            rm._serve_tick()

        def arrive():
            if len(rids) == 1:
                rids.append(rm.register_new_request(joiner))

        if where == "tick":
            arrive()
        else:
            rm._arrival_pump = arrive
        while rm.has_work():
            rm._serve_tick()
    finally:
        from flexflow_tpu.obs import NULL_TELEMETRY

        im.telemetry = NULL_TELEMETRY
    assert [rm.requests[r].generated for r in rids] == want
    joins = [a for a in spans(tel, "prefill_scan_dispatch") if a["joiners"]]
    assert sum(a["prompt_tokens"] for a in joins) == len(joiner)
    assert all(a.get("prompt_tokens", 0) == 0
               for a in spans(tel, "step_dispatch"))


# ---------------------------------------------------------------------------
# after the benchmark's warm-up nothing lowers or compiles
# ---------------------------------------------------------------------------
_EVENTS, _WATCHING, _LISTENING = [], [], []


def _on_duration(name, secs, **_):
    if _WATCHING and name.endswith(("jaxpr_to_mlir_module_duration",
                                    "backend_compile_duration")):
        _EVENTS.append(name.rsplit("/", 1)[-1])


@functools.lru_cache(maxsize=None)
def _warmed(longest):
    """A toy ``LLM`` with the kernels on (interpreted) and the gated LM
    head, warmed by ``benchmark.warmup.warm`` for prompts up to ``longest``
    tokens — the call list the timed runs rely on — under the listener
    ``benchmark/run.py``'s ``CompileWatch`` registers."""
    import flexflow_tpu.serve.api as api
    from benchmark import warmup
    from flexflow_tpu.serve import LLM

    if not _LISTENING:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _LISTENING.append(True)
    real = api.InferenceManager
    api.InferenceManager = functools.partial(real, use_pallas=True)
    try:
        llm = LLM(TINY).compile(
            max_requests=4, max_tokens_per_batch=48, max_seq_len=128, topk=2,
            generation_config=GenerationConfig(stop_on_eos=False))
    finally:
        api.InferenceManager = real
    assert llm.im.use_pallas and llm.im.gate_lm_head
    assert 1 < llm.im.prefill_tile < llm.im.max_tokens
    mix = {"prompt_len": {"hi": longest}}
    del _EVENTS[:]
    _WATCHING.append("warm-up")
    warmup.warm(llm, mix, TINY.vocab_size, lambda msg: None)
    _WATCHING.pop()
    assert "backend_compile_duration" in _EVENTS, "the listener is deaf"
    return llm


@pytest.fixture(scope="module")
def warmed_llm():
    return _warmed(70)


def _toy_requests(n, seed):
    rng = np.random.RandomState(seed)
    return [(rng.randint(1, TINY.vocab_size,
                         size=int(rng.randint(3, 71))).tolist(),
             int(rng.randint(2, 40))) for _ in range(n)]


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_nothing_lowers_or_compiles_after_the_warm_up(warmed_llm, loop):
    from test_serving_under_load import VirtualClock

    rm = warmed_llm.rm
    tel = Telemetry()
    rm.telemetry = rm.im.telemetry = tel
    reqs = _toy_requests(14, seed=3 if loop == "closed" else 4)
    # closed: everything queued at 0, so a slot that frees admits the next
    # request among live decoders; open: arrivals land inside stretches
    arrivals = [(0.0 if loop == "closed" else 0.004 * i, p, n)
                for i, (p, n) in enumerate(reqs)]
    del _EVENTS[:]
    _WATCHING.append(loop)
    try:
        recs = rm.serve_with_arrivals(arrivals, clock=VirtualClock())
    finally:
        _WATCHING.pop()
        from flexflow_tpu.obs import NULL_TELEMETRY

        rm.telemetry = rm.im.telemetry = NULL_TELEMETRY
    assert all(len(r["tokens"]) == n for r, (_, n) in
               zip((recs[k] for k in sorted(recs)), reqs))
    scans = spans(tel, "prefill_scan_dispatch")
    assert sum(a["joiners"] for a in scans) >= 3, \
        "the traffic never admitted a prompt among live decoders"
    assert spans(tel, "join_dispatch")
    assert all(a["prompt_tokens"] == 0
               for a in spans(tel, "step_dispatch"))
    assert _EVENTS == [], \
        f"{len(_EVENTS)} lowerings or compiles after the warm-up: {_EVENTS}"


def _wave_chunks(rm):
    """Chunks of every prefill wave in the journal, oldest first."""
    from flexflow_tpu.obs import journal as J

    rows = rm.journal.array()
    kind = rows[:, J.FIELDS.index("kind")]
    return rows[kind == J.KINDS.index("prefill_stretch"),
                J.FIELDS.index("chunks")].tolist()


# prompts up to ``hi`` tokens -> waves of an empty deployment (4 slots, 48
# rows a chunk, tile 16).  ``warm`` reckons chunks a request: for 40 it
# means waves of 4 and 3 chunks, whose prompts pack into 2 and 1 — and a
# wave of four 40-token prompts is 4 (2 + 2, not a scan of 4 built under
# load); for 70 it means 8 and 7, which pack into 6 and 5
WAVE_TRAFFIC = {
    40: [[40, 40, 40, 40], [33, 40, 17, 35], [40, 3, 40]],
    70: [[70, 70, 70, 70], [70, 33, 70, 50], [3, 19, 64, 41], [70, 70, 66]],
}


@pytest.mark.parametrize("hi", sorted(WAVE_TRAFFIC))
def test_waves_find_their_programs_built_after_the_warm_up(hi):
    # waves of several prompts on an empty deployment share chunks, so
    # their totals are none that ``benchmark.warmup.warm``'s arithmetic
    # made: the program keeps its own set of scan lengths closed
    llm = _warmed(hi)
    rm, im = llm.rm, llm.im
    warm_totals = set(_wave_chunks(rm))
    longest = im.prefill_scan_longest(True)
    assert longest == (2 if hi == 40 else 4)
    before = len(_wave_chunks(rm))
    tel = Telemetry()
    rm.telemetry = im.telemetry = tel
    rng = np.random.RandomState(hi)
    del _EVENTS[:]
    _WATCHING.append("waves")
    try:
        for lengths in WAVE_TRAFFIC[hi]:
            out = rm.generate(
                [rng.randint(1, TINY.vocab_size, size=n).tolist()
                 for n in lengths], 5)
            assert all(len(t) == 5 for t in out)
    finally:
        _WATCHING.pop()
        from flexflow_tpu.obs import NULL_TELEMETRY

        rm.telemetry = im.telemetry = NULL_TELEMETRY
    served = _wave_chunks(rm)[before:]
    tile, per = im.prefill_tile, im.max_tokens // im.prefill_tile
    assert served == [-(-sum(-(-n // tile) for n in lengths) // per)
                      for lengths in WAVE_TRAFFIC[hi]]
    assert set(served) - warm_totals, "no total that the warm-up never made"
    scans = spans(tel, "prefill_scan_dispatch")
    assert not [a for a in scans if a.get("pad")]
    assert max(a["n_chunks"] for a in scans) <= longest < max(served)
    assert any(a["segments"] > a["n_chunks"] for a in scans)
    assert tel.metrics.snapshot()["prompt_feed.shared_chunks"] > 0
    assert _EVENTS == [], \
        f"{len(_EVENTS)} lowerings or compiles after the warm-up: {_EVENTS}"
