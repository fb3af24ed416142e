"""Phi-4-mini-flash (SambaY) through the normal serve path, against the plain
reference ``benchmark/reference/phi4flash.py``.

Toy widths, the real layer pattern: 8 layers = Mamba, window, Mamba, window,
Mamba (the exporter), full attention (the cache owner), gated memory unit,
cross-attention; window 16 in a ring of 128 slots, contexts past 200, so the
ring wraps.  Weights are the benchmark's seeded ones in float32
(``seeded_weights.program_params`` also checks the program's parameter tree
against the reference's ``program_tree``, name by name and shape by shape).

Tolerances, float32 on the CPU against a float32 reference at HIGHEST
precision: the two differ by summation order alone, so a log-probability
agrees to 2e-4 nats (logits of scale ~1, 8 layers) — a dropped or stale
state moves it by 1e-2 or more.  The harness's own comparison
(``check.run_check``) reads in bf16 ulps of the logit scale; its limits here
are a hundredth of what a bf16 deployment is held to.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check, seeded_weights as sw  # noqa: E402
from benchmark.reference import phi4flash as ref  # noqa: E402
from flexflow_tpu.config import FFConfig  # noqa: E402
from flexflow_tpu.core.graph import Tensor  # noqa: E402
from flexflow_tpu.model import FFModel  # noqa: E402
from flexflow_tpu.parallel.mesh import make_mesh  # noqa: E402
from flexflow_tpu.serve import BatchConfig  # noqa: E402
from flexflow_tpu.serve.inference_manager import InferenceManager  # noqa: E402
from flexflow_tpu.serve.models.base import (  # noqa: E402
    ServeModelConfig,
    build_model,
)
from flexflow_tpu.serve.models.phi4flash import (  # noqa: E402
    dt_rank,
    layer_kind,
    mamba_inner,
)

HF = dict(model_type="phi4flash", hidden_size=64, intermediate_size=96,
          num_hidden_layers=8, num_attention_heads=8, num_key_value_heads=4,
          vocab_size=320, sliding_window=16, mb_per_layer=2,
          tie_word_embeddings=True, layer_norm_eps=1e-5,
          # std * sqrt(width) ~ 1, as 0.02 is at the published width 2560:
          # activations, B, C and the scan state are then of order one
          max_position_embeddings=4096, initializer_range=0.125,
          torch_dtype="float32")
SLOTS, CAP, SEQ = 4, 32, 256
TOL = 2e-4          # nats, see the module docstring
SEED = 1234


@functools.lru_cache(maxsize=None)
def deployment(use_pallas=False, cap=CAP, seq=SEQ):
    """One compiled deployment per shape, shared by the tests (each test
    starts its sequences at position 0 of a slot, which is all a slot needs
    to start clean)."""
    mesh = make_mesh({"tp": 1}, jax.devices()[:1])
    ff = FFModel(FFConfig(), mesh=mesh)
    build_model(ff, ServeModelConfig.from_hf_config(HF), cap)
    im = InferenceManager(ff, max_requests=SLOTS, max_tokens_per_batch=cap,
                          max_seq_len=seq, topk=HF["vocab_size"],
                          use_pallas=use_pallas)
    im.init_operators_inference()
    like = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        im.params)
    im.params = sw.program_params(ref, HF, sw.base_key(SEED), like, "float32")
    return im


@functools.lru_cache(maxsize=None)
def _ref_layer(padded_len):
    return jax.jit(lambda key, i, x: ref.layer(
        HF, sw.draw_table(key, i, ref.LAYER, HF, "float32"), x))


def reference_logprobs(ids):
    """Sorted log-probabilities at every position of ``ids``, from the
    reference's full forward pass (padding is causal-inert)."""
    key = sw.base_key(SEED)
    g = sw.draw_table(key, sw.GLOBAL_ID, ref.GLOBAL, HF, "float32")
    padded = np.zeros(-(-len(ids) // 64) * 64, np.int32)
    padded[:len(ids)] = ids
    x = ref.embed(HF, g, jnp.asarray(padded[None]))
    for i in range(ref.num_layers(HF)):
        x = _ref_layer(len(padded))(key, jnp.int32(i), x)
    logits = ref.head(HF, g, x[:, :len(ids)])[0]
    lp = jax.nn.log_softmax(logits, axis=-1)
    return np.asarray(jnp.sort(lp, axis=-1)[:, ::-1]), \
        np.asarray(jnp.argmax(logits, axis=-1))


def tokens(n, salt=0):
    rng = np.random.default_rng([SEED, salt])
    return rng.integers(3, HF["vocab_size"], size=n).tolist()


def flat_step(im, pieces, seq_lens):
    """One flat step holding ``pieces`` = [(slot, ids, start position)];
    returns the sorted log-probabilities per piece."""
    toks, slots, pos = [], [], []
    for slot, ids, start in pieces:
        toks += list(ids)
        slots += [slot] * len(ids)
        pos += list(range(start, start + len(ids)))
        seq_lens[slot] = start + len(ids)
    bc = BatchConfig.build(toks, slots, pos, seq_lens,
                           max_tokens=im.max_tokens, max_requests=SLOTS)
    res = im.step(bc)
    lp, out, at = np.asarray(res.topk_logprobs), [], 0
    for _, ids, _ in pieces:
        out.append(lp[at:at + len(ids)])
        at += len(ids)
    return out, np.asarray(res.token_ids)


def feed_flat(im, slot, ids, sizes, seq_lens):
    """``ids`` into ``slot`` by flat steps of the given sizes (cycled);
    returns the log-probabilities at every position."""
    rows, at, i = [], 0, 0
    while at < len(ids):
        take = min(sizes[i % len(sizes)], len(ids) - at)
        (lp,), _ = flat_step(im, [(slot, ids[at:at + take], at)], seq_lens)
        rows.append(lp)
        at, i = at + take, i + 1
    return np.concatenate(rows)


def feed_tiled(im, slot, ids, seq_lens):
    """``ids`` into ``slot`` by the tiled prefill scan, as the scheduler's
    prefill stretch stacks chunks of whole tiles; returns the first
    generated token."""
    seq = list(seq_lens)
    return check._prefill_scan(im, slot, ids, seq), seq


PROMPT = tokens(150)

# the selective scan's paths over a flat batch's rows: the ``lax.scan`` (the
# oracle) and the Pallas kernel in interpret mode (``use_pallas`` on the CPU)
SCAN_PATHS = pytest.mark.parametrize("use_pallas", [False, True],
                                     ids=["row_scan", "kernel"])


def scan_path(im, batch="BatchConfig"):
    return im.attention_paths.get(("selective_scan", batch))


def recurrent_state(im, slots):
    """The scan state and the conv tail the Mamba layers hold for
    ``slots``: {(node, buffer): array}."""
    return {(node, name): np.asarray(bufs[name])[list(slots)]
            for node, bufs in im.state.items()
            for name in ("ssm", "conv") if name in bufs}


def assert_same_recurrent_state(got, want):
    """Float32 rounding apart: the kernel sums ``h . C`` in another order,
    which the next layers' inputs (and so their states) feel."""
    assert got.keys() == want.keys() and len(got) == 6   # 3 Mamba layers
    for key, a in want.items():
        assert float(np.abs(a).max()) > 1e-3, key
        np.testing.assert_allclose(got[key], a, atol=2e-5, rtol=1e-4,
                                   err_msg=str(key))


@pytest.mark.parametrize("how", ["one_chunk", "even_chunks", "uneven_chunks",
                                 "tiled_scan", "tiled_scan_pallas",
                                 "even_chunks_pallas", "one_chunk_pallas",
                                 "uneven_chunks_pallas"])
def test_prompt_feeding_paths_agree_with_the_reference(how):
    """The same prompt in ONE flat chunk, in several, in uneven ones that
    cut a segment anywhere, and through the tiled prefill scan: the state
    crosses every chunk boundary, and the ring (128 slots) wraps.  With
    ``_pallas`` the selective scan's rows go through its kernel (interpret
    mode): a segment that starts from zero, segments that start mid-prompt
    from the stored state, and the pads behind a chunk's last row."""
    want, want_tok = reference_logprobs(PROMPT + tokens(3, salt=1))
    n = len(PROMPT)
    seq_lens = [0] * SLOTS
    pallas = how.endswith("pallas")
    if how.startswith("one_chunk"):
        im = deployment(use_pallas=pallas, cap=160)
        got = feed_flat(im, 1, PROMPT, [160], seq_lens)
    elif how.startswith("tiled_scan"):
        im = deployment(use_pallas=pallas)
        first, seq_lens = feed_tiled(im, 1, PROMPT, seq_lens)
        assert first == want_tok[n - 1]
        assert scan_path(im, "PrefillBatchConfig") == \
            ("kernel" if pallas else "row_scan")
        got = None
    else:
        im = deployment(use_pallas=pallas)
        sizes = [CAP] if how.startswith("even") else [7, CAP, 1, 20, 3]
        got = feed_flat(im, 1, PROMPT, sizes, seq_lens)
    if got is not None:
        np.testing.assert_allclose(got, want[:n], atol=TOL, rtol=0)
    # and what a decode step reads of the state they left behind
    for k, tok in enumerate(tokens(3, salt=1)):
        (lp,), _ = flat_step(im, [(1, [tok], n + k)], seq_lens)
        np.testing.assert_allclose(lp[0], want[n + k], atol=TOL, rtol=0)
    assert scan_path(im) == ("kernel" if pallas else "row_scan")


def three_requests_in_one_step(im):
    """Three requests fed alone, then continued side by side in ONE flat
    step; returns the step's log-probabilities per request and the cuts."""
    seqs = [tokens(40, salt=s) for s in (11, 12, 13)]
    seq_lens = [0] * SLOTS
    cuts = [(12, 9), (20, 10), (5, 11)]   # first step alone, then together
    for slot, (ids, (first, _)) in enumerate(zip(seqs, cuts)):
        feed_flat(im, slot, ids[:first], [CAP], seq_lens)
    pieces = [(slot, ids[first:first + more], first)
              for slot, (ids, (first, more)) in enumerate(zip(seqs, cuts))]
    got, _ = flat_step(im, pieces, seq_lens)
    return seqs, cuts, got


@SCAN_PATHS
def test_flat_step_holds_rows_of_three_requests(use_pallas):
    """A flat step is SEGMENTED by request: three requests' rows side by
    side, each continuing its own conv tail, scan state, ring and cache —
    three segments that start from stored states and two pads in one batch;
    the kernel leaves the three slots the states the row scan leaves."""
    im = deployment(use_pallas=use_pallas)
    seqs, cuts, got = three_requests_in_one_step(im)
    for slot, (lp, (first, more)) in enumerate(zip(got, cuts)):
        want = reference_logprobs(seqs[slot])[0]
        np.testing.assert_allclose(lp, want[first:first + more],
                                   atol=TOL, rtol=0)
    if use_pallas:
        assert scan_path(im) == "kernel"
        oracle = deployment()
        three_requests_in_one_step(oracle)
        assert_same_recurrent_state(recurrent_state(im, range(3)),
                                    recurrent_state(oracle, range(3)))


# readings here: 0.0001 ulps at most, 0.0000 nats (four decimals)
LIMITS = {"logit_rms_ulps": 0.01, "logit_max_ulps": 0.05,
          "logprob_rms": 5e-5, "logprob_max": 5e-4, "tail_logprob_rms": 5e-5,
          "token_gap_ulps": 0.05}


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_the_harness_drive_is_correct(use_pallas):
    """``benchmark/check.py``'s drive, the one that decides a cell's
    ``correct``: a 216-token prompt through the tiled prefill scan, a flat
    one, a joiner prefilled in two flat chunks and spliced by ``join_slot``
    into two chained decode scans, then flat steps holding all three rows."""
    im = deployment(use_pallas=use_pallas)
    lines = []
    ok, numbers = check.run_check(im, ref, HF, sw.base_key(SEED), "float32",
                                  77, HF["vocab_size"], LIMITS, lines.append)
    assert ok, "\n".join(lines)
    assert im.attention_paths.pop(
        ("kv_block_write", "PrefillBatchConfig"), None) == (
        "pallas" if use_pallas else None)
    # the decode scans' K/V rows: ONE aliased call a layer where the kernels
    # are on, the chain of update-slices where they are off
    assert im.attention_paths.pop(
        ("kv_row_write", "one_row_per_request")) == (
        "pallas" if use_pallas else "dus_chain")
    kinds = {k for k, _ in im.attention_paths}
    assert kinds == {"window_attention", "full_attention", "cross_attention",
                     "selective_scan", "causal_conv1d"} | (
                         {"decode_block", "prefill_operands"}
                         if use_pallas else set())
    # the conv's two forms: the decode scans step the tails in slot order,
    # the prompt's chunks and the flat steps go by rows
    assert {b: p for (k, b), p in im.attention_paths.items()
            if k == "causal_conv1d"} == {
        "one_row_per_request": "slot_order", "PrefillBatchConfig": "rows",
        "BatchConfig": "rows"}
    assert scan_path(im, "one_row_per_request") == "rows_at_once"
    if use_pallas:
        assert im.attention_paths[
            ("full_attention", "PrefillBatchConfig")] == "prefill_attention"
        assert im.attention_paths[
            ("window_attention", "PrefillBatchConfig")] == "xla_tile"
        # the full and the cross layers' tiles, on the toy's float32 caches
        assert {b: p for (k, b), p in im.attention_paths.items()
                if k == "prefill_operands"} == {
            "full_attention": "float32", "cross_attention": "float32"}
        assert im.attention_paths[
            ("window_attention", "BatchConfig")] == "decode_attention"
        assert scan_path(im, "PrefillBatchConfig") == scan_path(im) == "kernel"
        # the block decode_attention planned, by layer kind: a ring's, and
        # the two full-length caches' (the toy's are narrow: one block)
        blocks = {b[0]: p for (k, b), p in im.attention_paths.items()
                  if k == "decode_block"}
        assert set(blocks) == {"window_attention", "full_attention",
                               "cross_attention"}
        assert blocks["window_attention"].startswith("ring")
        assert blocks["full_attention"].startswith("full")
    else:
        assert {p for (k, _), p in im.attention_paths.items()
                if k not in ("selective_scan", "causal_conv1d")} == {"xla"}
        assert scan_path(im) == "row_scan"


@SCAN_PATHS
def test_a_reused_slot_starts_from_zero_state(use_pallas):
    """A slot that served a LONG request (ring wrapped, scan state warm)
    then serves a short one: the short one reads what it would alone."""
    im = deployment(use_pallas=use_pallas)
    seq_lens = [0] * SLOTS
    feed_flat(im, 2, tokens(170, salt=21), [CAP], seq_lens)
    short = tokens(23, salt=22)
    want, _ = reference_logprobs(short)
    seq_lens[2] = 0
    got = feed_flat(im, 2, short, [10], seq_lens)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("what", ["shared_cache", "exported_scan_output"])
def test_the_cross_decoder_reads_what_the_self_decoder_left(what):
    """Perturb the full-attention layer's cache at a PAST position, or the
    last Mamba layer's ``D`` (which only its scan output ``y`` feels): every
    mixer from that layer on moves, none before it does."""
    cfg = ServeModelConfig.from_hf_config(HF)
    mesh = make_mesh({"tp": 1}, jax.devices()[:1])
    ff = FFModel(FFConfig(), mesh=mesh)
    build_model(ff, cfg, 8)
    by_name = {n.name: n for n in ff.graph.nodes}
    mixers = [by_name[f"model.layers.{i}.mixer_residual"].inputs[1]
              for i in range(cfg.num_hidden_layers)]
    im = InferenceManager(ff, max_requests=2, max_tokens_per_batch=8,
                          max_seq_len=64, use_pallas=False,
                          outputs=[Tensor(ff.graph, t) for t in mixers])
    im.init_operators_inference(rng=jax.random.PRNGKey(3))

    def run(params, state, toks, start):
        bc = BatchConfig.build(toks, [0] * len(toks),
                               list(range(start, start + len(toks))),
                               [start + len(toks), 0], max_tokens=8,
                               max_requests=2)
        return im._fwd(params, {im._token_tid: bc.tokens}, state=state,
                       extras={"batch_config": bc, "pallas_decode": False})

    _, state = run(im.params, im.state, [5, 6, 7, 8, 9, 10], 0)
    base, _ = run(im.params, state, [11], 6)
    params = im.params
    if what == "shared_cache":
        owner = "model.layers.5.attn"
        moved_from = 5
        k = state[owner]["k"]
        state = {**state, owner: {**state[owner],
                                  "k": k.at[0, :, 2].add(1.0)}}
    else:
        scan = "model.layers.4.mixer.scan"
        moved_from = 4
        params = {**params, scan: {**params[scan],
                                   "D": params[scan]["D"] + 0.5}}
    got, _ = run(params, state, [11], 6)
    moved = [float(jnp.abs(a[0] - b[0]).max()) for a, b in zip(got, base)]
    assert all(m == 0.0 for m in moved[:moved_from]), moved
    assert all(m > 1e-6 for m in moved[moved_from:]), moved


def test_allocator_bytes_per_slot_by_kind():
    """Three kinds of per-slot state, priced apart; the window ring and the
    recurrent state do not grow with ``max_seq_len``, and the shared cache
    stores each K and V once (one layer's worth per token)."""
    def bytes_at(seq):
        mesh = make_mesh({"tp": 1}, jax.devices()[:1])
        ff = FFModel(FFConfig(), mesh=mesh)
        build_model(ff, ServeModelConfig.from_hf_config(HF), CAP)
        im = InferenceManager(ff, max_requests=SLOTS,
                              max_tokens_per_batch=CAP, max_seq_len=seq)
        im.allocate_kv_cache()
        return im.kv.bytes_per_slot(), im.kv.bytes_per_token()

    short, per_tok = bytes_at(SEQ)
    long, per_tok_long = bytes_at(4 * SEQ)
    kv, hd, d_i = HF["num_key_value_heads"], 8, 128
    # the allocator spreads the scratch row over the real slots
    scratch = (SLOTS + 1) / SLOTS
    assert per_tok == per_tok_long == 2 * kv * hd * 4 * scratch  # ONE layer
    assert short["kv_full"] == per_tok * SEQ
    assert long["kv_full"] == per_tok * 4 * SEQ
    ring = 128                                   # pad128(window 16 + cap 32)
    assert short["kv_window"] == long["kv_window"] == 2 * per_tok * ring
    assert short["recurrent"] == long["recurrent"] == \
        3 * (d_i * 16 * 4 + 3 * d_i * 4) * scratch


@pytest.mark.parametrize("kw,needs", [
    (dict(kv_page_size=32), "page table"),
    (dict(kv_dtype="int8"), "quantise-on-write"),
    (dict(max_spec_tokens=7), "snapshot"),
    (dict(tp=2), "sharding rule"),
    (dict(pp=2), "stage boundaries"),
])
def test_combinations_not_written_yet_raise_at_compile(kw, needs):
    kw = dict(kw)
    tp, pp = kw.pop("tp", 1), kw.pop("pp", 1)
    axes = {"pp": pp, "tp": tp} if pp > 1 else {"tp": tp}
    mesh = make_mesh(axes, jax.devices()[:tp * pp])
    ff = FFModel(FFConfig(), mesh=mesh)
    build_model(ff, ServeModelConfig.from_hf_config(HF), CAP)
    with pytest.raises(ValueError, match=needs):
        if pp > 1:
            from flexflow_tpu.serve.pp import PipelinedInferenceManager

            PipelinedInferenceManager(ff, max_requests=SLOTS,
                                      max_tokens_per_batch=CAP,
                                      max_seq_len=SEQ)
        else:
            InferenceManager(ff, max_requests=SLOTS,
                             max_tokens_per_batch=CAP, max_seq_len=SEQ, **kw)


def test_tree_batches_are_refused_by_the_ops():
    from flexflow_tpu.serve.hybrid_ops import _require
    from flexflow_tpu.core.op import OpContext

    ctx = OpContext(extras={"batch_config": object(), "state": {}})
    with pytest.raises(ValueError, match="snapshot per tree node"):
        _require(ctx, "selective_scan")


@SCAN_PATHS
def test_memory_ledger_and_path_counters_tell_the_kinds_apart(use_pallas):
    """The memory ledger holds the bytes one slot keeps of each kind of
    state, ``host_admit`` says how many slots started anew, and every
    attention layer kind — and the selective scan — leaves a counter naming
    the path it took."""
    from flexflow_tpu.obs import Telemetry
    from flexflow_tpu.serve import GenerationConfig, RequestManager

    im = deployment(use_pallas=use_pallas)
    tel = Telemetry()
    rm = RequestManager(im, GenerationConfig(stop_on_eos=False),
                        telemetry=tel)
    try:
        im._paths_counted = 0
        outs = rm.generate([tokens(9, salt=31), tokens(40, salt=32)], 5)
        assert [len(o) for o in outs] == [5, 5]
        im.publish_memory(tel)
        measured = tel.memory.report()["plans"][im.plan_key]
        per_slot = im.kv.bytes_per_slot()
        for kind in ("kv_full", "kv_window", "recurrent"):
            assert measured[f"slot_{kind}_bytes"]["measured"] == \
                per_slot[kind] > 0
        counters = tel.metrics.snapshot()
        if use_pallas:
            assert counters["attention_path.selective_scan.kernel"] >= 1
            assert "attention_path.selective_scan.row_scan" not in counters
        else:
            for kind in ("window", "full", "cross"):
                assert counters[f"attention_path.{kind}_attention.xla"] >= 1
            assert counters["attention_path.selective_scan.row_scan"] >= 1
        assert counters["attention_path.selective_scan.rows_at_once"] >= 1
        assert counters["attention_path.causal_conv1d.slot_order"] >= 1
        assert counters["attention_path.causal_conv1d.rows"] >= 1
        resets = [e["args"]["state_reset"] for e in tel.trace.trace_events()
                  if e["name"] == "host_admit"
                  and "state_reset" in e.get("args", {})]
        assert sum(resets) == 2
    finally:
        im.telemetry = type(im).telemetry


CATALOG = {  # the catalog row's ``config`` (model-configs/architectures.jsonl)
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}


def test_the_published_config_builds_the_published_model():
    """``from_hf_config`` on the catalog's ``config``: 9 Mamba / 8 window /
    1 full / 7 GMU / 7 cross layers, and 3.85 B parameters counted from the
    graph's ParamSpecs (the tied head once) — the published 3.8 B.  The
    benchmark's configuration file holds the same keys unchanged."""
    import collections
    import json
    import math

    cfg = ServeModelConfig.from_hf_config(CATALOG)
    kinds = collections.Counter(
        layer_kind(cfg, i) for i in range(cfg.num_hidden_layers))
    assert kinds == {("mamba", "recurrent"): 9,
                     ("window_attention", "kv_window"): 8,
                     ("full_attention", "kv_full"): 1, ("gmu", None): 7,
                     ("cross_attention", None): 7}
    assert layer_kind(cfg, 16) == ("mamba", "recurrent")
    assert layer_kind(cfg, 17) == ("full_attention", "kv_full")
    assert (mamba_inner(cfg), dt_rank(cfg), cfg.hdim) == (5120, 160, 64)
    ff = FFModel(FFConfig(), mesh=make_mesh({"tp": 1}, jax.devices()[:1]))
    build_model(ff, cfg, 16)
    count = sum(math.prod(p.spec.shape) for n in ff.graph.nodes
                for p in n.op.params()
                if not (cfg.tie_word_embeddings and n.name == "lm_head"))
    assert 3.84e9 < count < 3.86e9, count
    classes = collections.Counter(type(n.op).__name__ for n in ff.graph.nodes)
    assert (classes["SelectiveScan"], classes["CausalConv1d"],
            classes["WindowDiffAttention"], classes["FullDiffAttention"],
            classes["CrossDiffAttention"]) == (9, 9, 8, 1, 7)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "phi-4-mini-flash.json")) as f:
        conf = json.load(f)
    assert {k: conf[k] for k in CATALOG} == CATALOG
    assert conf["benchmark"]["reduced"] == {}
    # every cross-attention node reads the one full-attention node's cache
    owners = {n.op.state_owner for n in ff.graph.nodes
              if type(n.op).__name__ == "CrossDiffAttention"}
    assert owners == {"model.layers.17.attn"}


@SCAN_PATHS
def test_chunked_feeding_leaves_the_state_one_chunk_leaves(use_pallas):
    """The slot's recurrent state itself, not only what reads it: after the
    same prompt in one 160-row chunk and in chunks of 32, 7, 1, ... rows the
    scan state and the conv tail of every Mamba layer are equal — a state
    dropped or stale at any chunk boundary shows here whatever its weight
    in the logits.  The one chunk is always the row scan's; the parts are
    the row scan's or the kernel's."""
    whole, parts = deployment(cap=160), deployment(use_pallas=use_pallas)
    feed_flat(whole, 3, PROMPT, [160], [0] * SLOTS)
    feed_flat(parts, 3, PROMPT, [CAP, 7, 1, 20, 3], [0] * SLOTS)
    assert scan_path(whole) == "row_scan"
    assert scan_path(parts) == ("kernel" if use_pallas else "row_scan")
    assert_same_recurrent_state(recurrent_state(parts, [3]),
                                recurrent_state(whole, [3]))


def test_row_write_kernel_on_and_off_serves_the_same(row_write_on_and_off):
    """The decode scan's K/V rows by ``kv_row_write`` and by the chain it
    replaced — the rings and the one shared cache: the same tokens, the same caches."""
    row_write_on_and_off(lambda: deployment.__wrapped__(use_pallas=True),
                         [tokens(40, salt=51), tokens(9, salt=52)])
