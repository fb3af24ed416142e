"""EvaByte (EVA attention over a cache that compacts itself) through the
normal serve path, against the plain reference
``benchmark/reference/evabyte.py`` — logits, not tokens.

Toy widths, the real mechanism: hidden 64, 2 heads of 32, 2 layers, window 32,
chunk 4 (8 summaries a window), vocabulary 320, 256 positions = 8 windows in
a compact cache of 8 x 7 + 32 = 88 entries (128 with the pad).  Every
sequence here crosses window ends, so every row reads summaries, and the
cache's live length falls at each end.  Weights are the benchmark's seeded
ones in float32 (``seeded_weights.program_params`` also holds the program's
parameter tree to the reference's ``program_tree``, name by name).

The reference masks all positions and all chunks under one softmax; the
program stores the attended set as a contiguous prefix and runs plain causal
attention on it.  float32 on the CPU against float32 at HIGHEST precision:
the two differ by summation order alone, and a log-probability agrees to 2e-4
nats — a window left uncompacted, a dropped ``mu`` or uniform chunk weights
move it by 1e-2 or more (``test_a_broken_state_is_seen`` holds that).
"""

import functools
import json
import math
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
from benchmark.reference import evabyte as ref  # noqa: E402
from flexflow_tpu.config import FFConfig  # noqa: E402
from flexflow_tpu.model import FFModel  # noqa: E402
from flexflow_tpu.parallel.mesh import make_mesh  # noqa: E402
from flexflow_tpu.serve import BatchConfig  # noqa: E402
from flexflow_tpu.serve import hybrid_ops  # noqa: E402
from flexflow_tpu.serve.hybrid_ops import (  # noqa: E402
    EvaAttention,
    compact_cache_len,
    compact_len,
)
from flexflow_tpu.serve.inference_manager import InferenceManager  # noqa: E402
from flexflow_tpu.serve.models.base import (  # noqa: E402
    ServeModelConfig,
    build_model,
)

W, C = 32, 4
HF = dict(model_type="evabyte", hidden_size=64, intermediate_size=96,
          num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
          vocab_size=320, window_size=W, chunk_size=C, num_pred_heads=8,
          norm_add_unit_offset=True, rms_norm_eps=1e-5, rope_theta=100000,
          max_position_embeddings=256, fp32_skip_add=True, fp32_logits=True,
          # std * sqrt(width) ~ 1, as 0.01275 is at the published width 4096
          init_std=0.125, torch_dtype="float32")
SLOTS, CAP, SEQ = 3, 16, 256
TOL = 2e-4          # nats, see the module docstring
SEED = 4321


def build(cap=CAP, seq=SEQ, use_pallas=False, **kw):
    mesh = make_mesh({"tp": 1}, jax.devices()[:1])
    ff = FFModel(FFConfig(), mesh=mesh)
    build_model(ff, ServeModelConfig.from_hf_config(HF), cap)
    return InferenceManager(ff, max_requests=SLOTS, max_tokens_per_batch=cap,
                            max_seq_len=seq, topk=HF["vocab_size"],
                            use_pallas=use_pallas, **kw)


def seeded(im):
    im.init_operators_inference()
    like = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        im.params)
    im.params = sw.program_params(ref, HF, sw.base_key(SEED), like, "float32")
    return im


@functools.lru_cache(maxsize=None)
def deployment(use_pallas=False, cap=CAP):
    """One compiled deployment per shape, shared by the tests (each starts
    its sequences at position 0 of a slot, which is all a slot needs to
    start clean)."""
    return seeded(build(cap=cap, use_pallas=use_pallas))


@functools.lru_cache(maxsize=None)
def _ref_layer(padded_len):
    return jax.jit(lambda key, i, x: ref.layer(
        HF, sw.draw_table(key, i, ref.LAYER, HF, "float32"), x))


def reference_logprobs(ids):
    """Sorted log-probabilities at every position of ``ids``, and the
    reference's greedy tokens, from its full forward pass."""
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
    return rng.integers(4, HF["vocab_size"], size=n).tolist()


def flat_step(im, pieces, seq_lens):
    """One flat step holding ``pieces`` = [(slot, ids, start position)];
    returns the sorted log-probabilities per piece, and the tokens."""
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
    """``ids`` into ``slot`` from position 0 by flat steps of the given
    sizes (cycled); the log-probabilities at every position."""
    rows, at, i = [], 0, 0
    while at < len(ids):
        take = min(sizes[i % len(sizes)], len(ids) - at)
        (lp,), _ = flat_step(im, [(slot, ids[at:at + take], at)], seq_lens)
        rows.append(lp)
        at, i = at + take, i + 1
    return np.concatenate(rows)


def decode_scan(im, slot, first, position, steps):
    """``steps`` decode steps of ``slot`` on the device, in chained scans of
    at most 32: the tokens produced after ``first`` (fed at ``position``)."""
    seq = np.zeros(SLOTS, np.int32)
    seq[slot] = position + 1
    bc = BatchConfig.build([first], [slot], [position], seq,
                           max_tokens=im.max_tokens, max_requests=SLOTS)
    out, done = [], 0
    while done < steps:
        n = min(32, steps - done)
        allowed = np.zeros(im.max_tokens, np.int32)
        allowed[0] = steps - done
        toks, live, _, bc = im.decode_scan_async(
            bc, n, allowed=allowed, max_position=position + done)
        assert np.asarray(live)[:, 0].all()
        out += np.asarray(toks)[:, 0].tolist()
        done += n
    return out


PROMPT = tokens(150)    # crosses the ends of windows 0 .. 3 while it is fed


@pytest.mark.parametrize("how", ["one_chunk", "even_chunks", "uneven_chunks",
                                 "tiled_scan", "tiled_scan_pallas",
                                 "even_chunks_pallas"])
def test_prompt_feeding_paths_agree_with_the_reference(how):
    """The same prompt in chunks of a whole window, in even chunks of half a
    window, in uneven ones that STRADDLE window ends (rows on both sides of
    an end in one step), and through the tiled prefill scan: four windows
    close while it is fed, and a decode step then reads their summaries."""
    want, want_tok = reference_logprobs(PROMPT + tokens(3, salt=1))
    n = len(PROMPT)
    seq_lens = [0] * SLOTS
    if how == "one_chunk":
        im = deployment(cap=W)
        got = feed_flat(im, 1, PROMPT, [W], seq_lens)
    elif how.startswith("tiled_scan"):
        im = deployment(use_pallas=how.endswith("pallas"))
        seq = list(seq_lens)
        first = check._prefill_scan(im, 1, PROMPT, seq)
        assert first == want_tok[n - 1]
        got = None
    else:
        im = deployment(use_pallas=how.endswith("pallas"))
        sizes = [CAP] if how.startswith("even") else [7, CAP, 1, 13, 3]
        got = feed_flat(im, 1, PROMPT, sizes, seq_lens)
    if got is not None:
        np.testing.assert_allclose(got, want[:n], atol=TOL, rtol=0)
    for k, tok in enumerate(tokens(3, salt=1)):
        (lp,), _ = flat_step(im, [(1, [tok], n + k)], seq_lens)
        np.testing.assert_allclose(lp[0], want[n + k], atol=TOL, rtol=0)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_decode_scan_closes_windows_as_prefill_does(use_pallas):
    """A 40-token prompt, then 70 decode steps on the device in chained
    scans: the ends of windows 1 and 2 (positions 63, 95) are crossed BY THE
    DECODE SCAN.  Flat steps then read, at position 110 on, logits that
    depend on the summaries the scan's compaction made; and the cache the
    scan left is the cache the same 110 tokens leave when PREFILLED into
    another slot — summaries of windows 0 .. 2, then the open window's raw
    entries."""
    im = deployment(use_pallas=use_pallas)
    prompt = tokens(40, salt=5)
    seq_lens = [0] * SLOTS
    feed_flat(im, 0, prompt[:-1], [CAP], seq_lens)
    _, toks = flat_step(im, [(0, prompt[-1:], 39)], seq_lens)
    first = int(toks[0])
    made = decode_scan(im, 0, first, 40, 70)
    full = prompt + [first] + made                  # 111 tokens
    # teacher forcing: the reference is fed what the program produced
    want, _ = reference_logprobs(full + tokens(2, salt=6))
    seq_lens[0] = 110
    for k, tok in enumerate([full[110]] + tokens(2, salt=6)):
        (got,), _ = flat_step(im, [(0, [tok], 110 + k)], seq_lens)
        np.testing.assert_allclose(got[0], want[110 + k], atol=TOL, rtol=0)
    # the same tokens, prefilled: windows 0 .. 2 closed by the prefill path
    feed_flat(im, 2, full[:110], [CAP], seq_lens)
    live = compact_len(109, W, C) + 1
    assert live == 3 * (W // C) + 14
    for node, bufs in im.state.items():
        for name in ("ck", "cv"):
            a, b = bufs[name][0, :, :live], bufs[name][2, :, :live]
            assert float(jnp.abs(a).max()) > 1e-2, (node, name)
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)


# readings here: 0.0002 ulps at most, 0.0000 nats (four decimals)
LIMITS = {"logit_rms_ulps": 0.01, "logit_max_ulps": 0.05,
          "logprob_rms": 5e-5, "logprob_max": 5e-4, "tail_logprob_rms": 5e-5,
          "token_gap_ulps": 0.05}


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_the_harness_drive_is_correct(use_pallas):
    """``benchmark/check.py``'s drive, the one that decides a cell's
    ``correct``: 124 tokens through the tiled prefill scan (three windows
    close), a flat prompt, a joiner spliced by ``join_slot`` between two
    chained decode scans — in which row A crosses position 127, a window's
    end — then flat steps holding all three rows."""
    im = deployment(use_pallas=use_pallas)
    lines = []
    ok, _ = check.run_check(im, ref, HF, sw.base_key(SEED), "float32", 77,
                            HF["vocab_size"], LIMITS, lines.append)
    assert ok, "\n".join(lines)
    # the prompt chunk's block write is counted where the tiled path runs;
    # this toy's windows open 8 entries apart, not a whole tile: the chain
    write = im.attention_paths.pop(("kv_block_write", "PrefillBatchConfig"),
                                   None)
    assert write == ("dus_chain" if use_pallas else None)
    block = im.attention_paths.pop(
        ("decode_block", ("eva_attention", "BatchConfig")), None)
    assert (block or "full").startswith("full") and \
        (block is not None) == use_pallas
    # the decode scans' K/V rows: ONE aliased call a layer where the kernels
    # are on, the chain of update-slices where they are off
    assert im.attention_paths.pop(
        ("kv_row_write", "one_row_per_request")) == (
        "pallas" if use_pallas else "dus_chain")
    # the prompt's tiles went through ``prefill_attention`` on the toy's
    # float32 cache: float32 operands into its contractions
    assert im.attention_paths.pop(
        ("prefill_operands", "eva_attention"), None) == (
        "float32" if use_pallas else None)
    assert {k for k, _ in im.attention_paths} == {"eva_attention"}
    if use_pallas:
        assert im.attention_paths[
            ("eva_attention", "PrefillBatchConfig")] == "prefill_attention"
        assert im.attention_paths[
            ("eva_attention", "BatchConfig")] == "decode_attention"
    else:
        assert set(im.attention_paths.values()) == {"xla"}


def test_flat_step_holds_rows_on_both_sides_of_a_window_end():
    """Three requests in one flat step: one crosses its window's end inside
    the step (rows at 60 .. 67: the rows from 64 on read the summaries of a
    window whose last entries this very step wrote), one closes its window
    with the step's last row, one is nowhere near an end."""
    im = deployment()
    starts = [60, 25, 3]
    seqs = [tokens(s + 8, salt=40 + i) for i, s in enumerate(starts)]
    want = [reference_logprobs(s)[0] for s in seqs]
    seq_lens = [0] * SLOTS
    for slot, (ids, s) in enumerate(zip(seqs, starts)):
        feed_flat(im, slot, ids[:s], [CAP], seq_lens)
    sizes = [8, 7, 1]    # 60..67, 25..31 (closes window 0), 3
    got, _ = flat_step(im, [(slot, ids[s:s + n], s) for slot, (ids, s, n)
                            in enumerate(zip(seqs, starts, sizes))],
                       seq_lens)
    for slot, (lp, s, n) in enumerate(zip(got, starts, sizes)):
        np.testing.assert_allclose(lp, want[slot][s:s + n], atol=TOL, rtol=0)
    # and the step after it reads what the step left: slot 1 now sits at the
    # first position of window 1 and sees window 0 through its summaries only
    (lp,), _ = flat_step(im, [(1, seqs[1][32:33], 32)], seq_lens)
    np.testing.assert_allclose(lp[0], want[1][32], atol=TOL, rtol=0)


def test_a_reused_slot_starts_from_an_empty_cache():
    """A slot that served a long request (five windows closed, summaries all
    over the front of its cache) then serves a short one: the short one
    reads what it would alone."""
    im = deployment()
    seq_lens = [0] * SLOTS
    feed_flat(im, 2, tokens(170, salt=21), [CAP], seq_lens)
    short = tokens(45, salt=22)
    want, _ = reference_logprobs(short)
    seq_lens[2] = 0
    got = feed_flat(im, 2, short, [10], seq_lens)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def _without_compaction(self, kc, vc, params, rows, pos, closing):
    return kc, vc


_summarize = EvaAttention.summarize


def _mu_dropped(self, k, v, params):
    return _summarize(self, k, v,
                      {**params, "mu": jnp.zeros_like(params["mu"])})


def _uniform_weights(self, k, v, params):
    return _summarize(self, k, v,
                      {**params, "phi": jnp.zeros_like(params["phi"])})


@pytest.mark.parametrize("broken", ["compaction_skipped_in_the_decode_scan",
                                    "compaction_skipped", "mu_dropped",
                                    "uniform_chunk_weights",
                                    "late_rows_do_not_wait"])
def test_a_broken_state_is_seen(broken, monkeypatch):
    """Each way of getting the compact cache wrong moves the logits by far
    more than the tolerance the other tests hold: what they pass, a broken
    program would not."""
    if broken == "compaction_skipped":
        monkeypatch.setattr(EvaAttention, "_compact", _without_compaction)
    elif broken == "compaction_skipped_in_the_decode_scan":
        whole = EvaAttention._compact

        def only_outside_the_scan(self, kc, vc, params, rows, pos, closing):
            # the decode scan's rows are one per slot; every other step
            # here is as wide as the flat batch
            if rows.shape[0] == SLOTS:
                return kc, vc
            return whole(self, kc, vc, params, rows, pos, closing)

        monkeypatch.setattr(EvaAttention, "_compact", only_outside_the_scan)
    elif broken == "mu_dropped":
        monkeypatch.setattr(EvaAttention, "summarize", _mu_dropped)
    elif broken == "uniform_chunk_weights":
        monkeypatch.setattr(EvaAttention, "summarize", _uniform_weights)
    else:
        real = hybrid_ops.Segments

        class NoOffsets(real):
            def __init__(self, bc, nreq):
                super().__init__(bc, nreq)
                self.offset = jnp.zeros_like(self.offset)

        monkeypatch.setattr(hybrid_ops, "Segments", NoOffsets)
    im = seeded(build())
    seq_lens = [0] * SLOTS
    if broken == "late_rows_do_not_wait":
        ids = tokens(70, salt=50)
        want, _ = reference_logprobs(ids)
        feed_flat(im, 0, ids[:60], [CAP], seq_lens)
        (got,), _ = flat_step(im, [(0, ids[60:68], 60)], seq_lens)
        worst = np.abs(got - want[60:68]).max()
    else:
        prompt = tokens(40, salt=5)
        feed_flat(im, 0, prompt[:-1], [CAP], seq_lens)
        _, toks = flat_step(im, [(0, prompt[-1:], 39)], seq_lens)
        made = decode_scan(im, 0, int(toks[0]), 40, 30)   # crosses 63
        full = prompt + [int(toks[0])] + made
        want, _ = reference_logprobs(full)
        seq_lens[0] = 70
        (got,), _ = flat_step(im, [(0, full[70:71], 70)], seq_lens)
        worst = np.abs(got[0] - want[70]).max()
    assert worst > 50 * TOL, worst


def test_live_length_and_bytes_per_slot_against_the_hand_formula():
    """``L(t) = (W / C) floor(t / W) + t mod W + 1``: it is not the position,
    and it FALLS by ``W - W / C`` at each window's end.  A slot's cache is
    ``(W / C)(S / W - 1) + W`` entries (padded to the kernels' block), priced
    as its own kind; there is no price per position."""
    L = lambda t: compact_len(t, W, C) + 1
    assert [L(t) for t in (0, 31, 32, 63, 64, 255)] == [1, 32, 9, 40, 17, 88]
    assert L(31) - L(32) == W - W // C - 1
    assert compact_len(np.arange(3) + 62, W, C).tolist() == [38, 39, 16]
    assert compact_cache_len(256, W, C) == 128          # 88, padded
    assert compact_cache_len(32768, 2048, 16) == 4096   # 3968, padded
    assert compact_cache_len(2048, 2048, 16) == 2048

    def bytes_at(seq):
        im = build(seq=seq)
        im.allocate_kv_cache()
        return im.kv.bytes_per_slot(), im.kv.bytes_per_token()

    short, per_tok = bytes_at(256)
    long, _ = bytes_at(2048)
    heads, hd, layers = 2, 32, HF["num_hidden_layers"]
    # the allocator spreads the scratch row over the real slots
    entry = 2 * heads * hd * 4 * layers * (SLOTS + 1) / SLOTS
    assert per_tok is None
    assert short["kv_compact"] == 128 * entry
    assert long["kv_compact"] == 1024 * entry    # 8 x 63 + 32 = 536, padded
    assert short["kv_full"] == short["kv_window"] == short["recurrent"] == 0
    # admission gates in positions where a position has no price
    from flexflow_tpu.serve import GenerationConfig, RequestManager

    im = deployment()
    rm = RequestManager(im, GenerationConfig(stop_on_eos=False))
    assert rm._kv_bytes_per_token() is None
    assert rm.generate([tokens(50, salt=3)], 4)[0]


@pytest.mark.parametrize("kw,needs", [
    (dict(kv_page_size=32), "one entry a position"),
    (dict(kv_dtype="int8"), "summaries"),
    (dict(max_spec_tokens=7), "closed window"),
    (dict(tp=2), "per-head summaries"),
    (dict(pp=2), "stage boundaries"),
    (dict(cap=64), "may cross one window's end"),
])
def test_combinations_not_written_yet_raise_at_compile(kw, needs):
    kw = dict(kw)
    tp, pp, cap = kw.pop("tp", 1), kw.pop("pp", 1), kw.pop("cap", CAP)
    axes = {"pp": pp, "tp": tp} if pp > 1 else {"tp": tp}
    mesh = make_mesh(axes, jax.devices()[:tp * pp])
    ff = FFModel(FFConfig(), mesh=mesh)
    build_model(ff, ServeModelConfig.from_hf_config(HF), cap)
    with pytest.raises(ValueError, match=needs):
        if pp > 1:
            from flexflow_tpu.serve.pp import PipelinedInferenceManager

            PipelinedInferenceManager(ff, max_requests=SLOTS,
                                      max_tokens_per_batch=cap,
                                      max_seq_len=SEQ)
        else:
            InferenceManager(ff, max_requests=SLOTS, max_tokens_per_batch=cap,
                             max_seq_len=SEQ, **kw).allocate_kv_cache()


def test_spans_counters_and_the_ledger_name_the_compaction():
    """Through ``RequestManager.generate``: the dispatch spans carry the
    rows' live cache lengths and the windows a launch will close, the
    counters add them up, the memory ledger prices the kind, and the path
    the attention took is counted."""
    from flexflow_tpu.obs import Telemetry
    from flexflow_tpu.serve import GenerationConfig, RequestManager

    im = deployment()
    tel = Telemetry()
    rm = RequestManager(im, GenerationConfig(stop_on_eos=False),
                        telemetry=tel)
    try:
        im._paths_counted = 0
        outs = rm.generate([tokens(50, salt=31), tokens(9, salt=32)], 40)
        assert [len(o) for o in outs] == [40, 40]
        im.publish_memory(tel)
        measured = tel.memory.report()["plans"][im.plan_key]
        assert measured["slot_kv_compact_bytes"]["measured"] == \
            im.kv.bytes_per_slot()["kv_compact"] > 0
        counters = tel.metrics.snapshot()
        assert counters["attention_path.eva_attention.xla"] >= 1
        # 50 + 40 positions cross 31 and 63; 9 + 40 cross 31: three windows
        assert counters["eva.windows_closed"] == 3
        assert counters["eva.summaries_written"] == 3 * 2 * (W // C)
        launches = [e["args"] for e in tel.trace.trace_events()
                    if e["name"].endswith("_dispatch")
                    and "cache_len_sum" in e.get("args", {})]
        assert sum(a["compactions"] for a in launches) == 3
        scans = [a for a in launches if a.get("kind") == "decode_scan"]
        assert scans and all(0 < a["cache_len_sum"] <= a["ctx_sum"]
                             for a in scans)
        assert any(a["cache_len_sum"] < a["ctx_sum"] for a in scans)
    finally:
        im.telemetry = type(im).telemetry


CATALOG = {  # the catalog row's ``config`` (model-configs/architectures.jsonl)
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_hidden_layers": 32, "num_key_value_heads": 32,
    "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
    "window_size": 2048}


def test_the_published_config_builds_the_published_model():
    """``from_hf_config`` on the catalog's ``config``: 32 EVA layers of
    202.4 M parameters, float32 residual stream and logits, bf16-typed
    projections fed by the norms; the benchmark's configuration file holds
    the same keys unchanged but the depth it lists as reduced."""
    cfg = ServeModelConfig.from_hf_config({**CATALOG,
                                           "torch_dtype": "bfloat16"})
    assert (cfg.window_size, cfg.chunk_size, cfg.num_pred_heads,
            cfg.norm_add_unit_offset, cfg.hdim) == (2048, 16, 8, True, 128)
    ff = FFModel(FFConfig(), mesh=make_mesh({"tp": 1}, jax.devices()[:1]))
    build_model(ff, cfg, 16)
    per_layer = sum(math.prod(p.spec.shape) for n in ff.graph.nodes
                    for p in n.op.params() if ".layers.7." in n.name)
    assert per_layer == 4 * 4096 ** 2 + 3 * 4096 * 11008 + 2 * 4096 \
        + 2 * 32 * 128
    by_name = {n.name: n for n in ff.graph.nodes}
    spec = lambda name, i=0: ff.graph.tensor_specs[by_name[name].outputs[i]]
    assert spec("model.layers.3.input_layernorm", 0).dtype == jnp.float32
    assert spec("model.layers.3.input_layernorm", 1).dtype == jnp.bfloat16
    assert spec("model.layers.3.self_attn").dtype == jnp.bfloat16
    assert spec("lm_head").dtype == jnp.float32
    assert spec("lm_head").shape == (16, 320)
    assert sum(isinstance(n.op, EvaAttention) for n in ff.graph.nodes) == 32
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "evabyte-d8.json")) as f:
        conf = json.load(f)
    reduced = conf["benchmark"]["reduced"]
    assert set(reduced) == {"num_hidden_layers"}
    assert {k: conf[k] for k in CATALOG if k not in reduced} == \
        {k: v for k, v in CATALOG.items() if k not in reduced}
    assert conf["num_hidden_layers"] == 8


def test_row_write_kernel_on_and_off_serves_the_same(row_write_on_and_off):
    """The decode scan's K/V rows by ``kv_row_write`` and by the chain it
    replaced — the compacted cache, at compact indices: the same tokens, the same caches."""
    row_write_on_and_off(lambda: seeded(build(use_pallas=True)),
                         [tokens(40, salt=51), tokens(9, salt=52)])
