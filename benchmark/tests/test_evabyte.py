"""The files the ``evabyte-d8`` configuration brought: its reference's
tables against the program's tree, the cost function against a hand count,
its cell against the headroom rule, the roofline reader's silence where it
has nothing to read, and the boundary drive at toy widths (CPU; Pallas
interpreted) — sound, and NOT correct with the decode scan's compaction
skipped."""

import json
import os
import types

import jax
import pytest

from conftest import ROOT

from benchmark import boundary, costs, costs_eva, headroom, run
from benchmark import seeded_weights as sw
from benchmark.reference import evabyte as ref

W, C = 64, 4
TOY = {"model_type": "evabyte", "hidden_size": 128, "intermediate_size": 256,
       "num_attention_heads": 2, "num_key_value_heads": 2,
       "num_hidden_layers": 2, "vocab_size": 320, "window_size": W,
       "chunk_size": C, "num_pred_heads": 8, "norm_add_unit_offset": True,
       "rms_norm_eps": 1e-5, "rope_theta": 100000, "init_std": 0.09,
       "max_position_embeddings": 512, "torch_dtype": "bfloat16"}
DEP = {"chips": 1, "tp": 1, "precision": "bfloat16",
       "compile": {"max_requests": 3, "max_tokens_per_batch": 64,
                   "max_seq_len": 512, "dtype": "bfloat16", "topk": 8},
       # the toy's own (4 sound seeds, 2 broken; CPU): sound seeds read
       # logit_rms_ulps 0.44-0.76 and logprob_rms 0.008-0.012 behind the
       # boundary, maxima 1.5 ulps / 0.04 nats; with the decode scan's
       # compaction skipped 18-25 ulps, 0.21-0.22 nats, token gaps 166-203
       "correct": {"logit_rms_ulps": 1.5, "logit_max_ulps": 8.0,
                   "logprob_rms": 0.03, "logprob_max": 0.3,
                   "tail_logprob_rms": 0.03, "token_gap_ulps": 8.0}}


def real_conf():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "evabyte-d8.json")) as f:
        conf = json.load(f)
    return {k: v for k, v in conf.items() if k != "benchmark"}, \
        conf["benchmark"]


def test_the_tables_give_the_published_sizes_and_the_programs_tree():
    hf, dep = real_conf()
    shape = headroom.model_shape(ref, hf)
    assert shape["layer_params"] == 4 * 4096 ** 2 + 3 * 4096 * 11008
    assert shape["layers"] == 8
    assert (shape["q_heads"], shape["kv_heads"], shape["head_dim"]) == \
        (32, 32, 128)
    # the GLOBAL ``lm_head`` is the published one, all 8 prediction heads
    assert shape["head_params"] == 4096 * 8 * 320

    def tree(key):
        g = sw.draw_table(key, sw.GLOBAL_ID, ref.GLOBAL, hf, "bfloat16")
        layers = [sw.draw_table(key, i, ref.LAYER, hf, "bfloat16")
                  for i in range(2)]
        return ref.program_tree(hf, g, layers)

    shapes = jax.eval_shape(tree, sw.base_key(1))
    assert shapes["lm_head"]["kernel"].shape == (4096, 320)     # head 0
    attn = shapes["model.layers.1.self_attn"]
    assert attn["qkv"].shape == (4096, 32, 3, 128)
    assert attn["phi"].shape == attn["mu"].shape == (32, 128)
    assert set(shapes["model.layers.0.input_layernorm"]) == {"gamma"}
    assert dep["compile"]["max_requests"] == 16
    assert set(dep["controls"]) == {"int8_weights"}


def test_the_draw_makes_phi_mu_and_the_gains_visible():
    """``published_init``: the three kinds of tensor a program could drop
    unseen at ``init_std`` come out at the spreads the file states."""
    hf, _ = real_conf()
    w = sw.draw_table(sw.base_key(3), 0, ref.LAYER, hf, "float32")
    init = ref.published_init(hf, w)
    std = lambda a: float(jax.numpy.std(a))
    assert abs(std(init["self_attn.adaptive_phi"]) / ref.PHI_STD - 1) < 0.05
    assert abs(std(init["self_attn.adaptive_mu_k"]) / ref.MU_STD - 1) < 0.05
    assert abs(std(init["input_layernorm.weight"]) / ref.GAIN_STD - 1) < 0.05
    assert set(init) == {"input_layernorm.weight",
                         "post_attention_layernorm.weight",
                         "self_attn.adaptive_phi", "self_attn.adaptive_mu_k"}


def test_eva_decode_cost_by_hand():
    hf, _ = real_conf()
    L = costs_eva.live_entries
    assert [L(c, 2048, 16) for c in (1, 2048, 2049, 4097, 20481)] == \
        [1, 2048, 129, 257, 1281]
    # one row at position 5000 (context 5001): 2 closed windows, 904 open
    assert L(5001, 2048, 16) == 2 * 128 + 904 + 1 == 1161
    ops, nbytes = costs_eva.eva_decode_cost([5001, 2049], hf)
    entry = 2 * 32 * 128 * 2                       # one K and one V entry
    qo = 2 * 32 * 128 * 2                          # q in, o out
    assert nbytes == 8 * ((1161 + 129) * entry + 2 * entry + 2 * qo)
    assert ops == 8 * 4 * 32 * 128 * (1161 + 129)
    # a cache that did not compact would read 5.5x as much for these rows
    _, full = costs.decode_attention_cost([5001, 2049], 32, 32, 128)
    assert 5.4 < 8 * full / nbytes < 5.5


def test_the_cell_outlasts_its_window_and_no_request_can_end_in_it():
    """headroom.py over-counts this model (full-length caches, 8 heads): it
    errs towards a deeper queue, so the rule holds a fortiori.  By hand: at
    the roofline of the compact cache a step is 8.3 ms, so a 51 s window
    advances a row by ~6 100 steps — fewer than the shortest answer."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = [w for w in bench["workloads"]
            if w["name"] == "evabyte-d8.byte-longform"]
    assert cell and cell[0]["chips"] == 1
    mine = [h for name, _, h in headroom.closed_cells()
            if name == "evabyte-d8.byte-longform"]
    assert mine and all(h["ratio"] >= headroom.HEADROOM for h in mine)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "byte-longform.json")) as f:
        mix = json.load(f)
    hf, dep = real_conf()
    sched = headroom.traffic_gen.schedule(mix, bench["run_seconds"])
    slots = dep["compile"]["max_requests"]
    assert len(sched) == 48 and mix["round"] == slots
    assert all(p + o <= dep["compile"]["max_seq_len"] for _, p, o in sched)
    first = sched[:slots]
    rows = [p + o // 2 for _, p, o in first]       # mid-answer contexts
    _, nbytes = costs_eva.eva_decode_cost(rows, hf)
    weights = 2 * (8 * (4 * 4096 ** 2 + 3 * 4096 * 11008) + 2 * 320 * 4096)
    step = (nbytes + weights) / 819e9
    assert 0.0075 < step < 0.0095
    assert bench["run_seconds"] * 1.1 / step < min(o for _, _, o in sched)
    # the first wave's prompts, fed before the window (and the rehearsal's)
    assert 150e3 < sum(p for _, p, _ in first) < 180e3


def test_the_roofline_reader_is_silent_where_it_has_nothing_to_read():
    """On a program or a checkout without the cost module, or a clock that
    kept no lengths, the reader returns None and does not raise."""
    reader = run.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "node_cost_roofline_pct.py"))
    ctx = {"clock": types.SimpleNamespace(trace_lens=None),
           "reduced": {"chips": [{}]}}
    args = dict(nodes=["EvaAttention"], inside=["_decode_scan_impl"],
                flat=["_step_impl"])
    assert reader.read(ctx, cost="costs_eva:eva_decode_cost", **args) is None
    ctx["clock"].trace_lens = ({}, {})
    assert reader.read(ctx, cost="costs_nowhere:f", **args) is None
    assert reader.read(ctx, cost="costs_eva:no_such", **args) is None


@pytest.fixture(scope="module")
def toy_llm(pallas_on_cpu):
    return run.build(TOY, DEP, jax.devices()[:1])


def test_the_boundary_drive_is_sound_across_two_window_ends(toy_llm):
    """Two sequences prefilled by the tiled scan to 9 and 21 positions
    short of the ends of windows 1 and 2, decoded across them by two chained
    scans, read by flat steps behind the boundary — within the limits."""
    lines = []
    for seed in (7, 2 ** 31 + 11):
        key = run.seed_weights(toy_llm, ref, TOY, seed, "bfloat16")
        ok, numbers = boundary.run_boundary(
            toy_llm.im, ref, TOY, key, "bfloat16", seed, DEP["correct"],
            lines.append)
        assert ok, "\n".join(lines)
    seqs = boundary.sequences(7, 320, W)
    assert [len(s) for s in seqs] == [2 * W - 9, 3 * W - 21]


def test_the_boundary_drive_sees_a_scan_that_does_not_compact(
        pallas_on_cpu, monkeypatch):
    from flexflow_tpu.serve.hybrid_ops import EvaAttention

    whole = EvaAttention._compact

    def only_outside_the_scan(self, kc, vc, params, rows, pos, closing):
        if rows.shape[0] == DEP["compile"]["max_requests"]:
            return kc, vc
        return whole(self, kc, vc, params, rows, pos, closing)

    monkeypatch.setattr(EvaAttention, "_compact", only_outside_the_scan)
    llm = run.build(TOY, DEP, jax.devices()[:1])
    key = run.seed_weights(llm, ref, TOY, 7, "bfloat16")
    ok, numbers = boundary.run_boundary(
        llm.im, ref, TOY, key, "bfloat16", 7, DEP["correct"],
        lambda m: None)
    assert not ok
    assert numbers["logit_rms_ulps"] > 5 * DEP["correct"]["logit_rms_ulps"]
