"""The files the ``minicpm-sala-d8`` configuration brought: its reference's
tables against the program's tree, both cost functions against a hand count
(past ``dense_len`` and below it), its cell against the headroom rule and the
hand count of a step, the metric files' arguments, and the long-context drive
at toy widths in bfloat16 (CPU; Pallas interpreted) — sound, and NOT correct
with the newest blocks read in place of the selection."""

import json
import os

import jax
import pytest

from conftest import ROOT

from benchmark import costs_sala, headroom, longctx, run
from benchmark import seeded_weights as sw
from benchmark.reference import minicpm_sala as ref

CELL = "minicpm-sala-d8.longctx-reason"
TOY = {"model_type": "minicpm_sala", "hidden_size": 128,
       "intermediate_size": 256, "num_hidden_layers": 4,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
       "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 32,
       "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn",
                       "minicpm4"],
       "vocab_size": 512, "rms_norm_eps": 1e-6, "rope_theta": 10000,
       "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
       "dim_model_base": 32, "sparse_kernel_size": 4,
       "sparse_kernel_stride": 2, "sparse_block_size": 8, "sparse_topk": 3,
       "sparse_window_size": 16, "sparse_init_blocks": 1,
       "sparse_dense_len": 48, "init_std": 0.09,
       "max_position_embeddings": 256, "torch_dtype": "bfloat16"}
DEP = {"chips": 1, "tp": 1, "precision": "bfloat16",
       "compile": {"max_requests": 3, "max_tokens_per_batch": 64,
                   "max_seq_len": 256, "dtype": "bfloat16", "topk": 8},
       # the toy's own (3 sound seeds, 2 broken; CPU): sound seeds read
       # logit_rms_ulps 0.33-0.38, logprob_rms 0.0016-0.0031, maxima 0.7 ulps
       # / 0.015 nats; with the newest blocks read in place of the selection
       # logit_rms_ulps 1.0-6.3 and logprob_rms 0.016-0.025
       "correct": {"logit_rms_ulps": 0.8, "logit_max_ulps": 4.0,
                   "logprob_rms": 0.008, "logprob_max": 0.08,
                   "tail_logprob_rms": 0.008, "token_gap_ulps": 8.0}}


def real_conf():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "minicpm-sala-d8.json")) as f:
        conf = json.load(f)
    return {k: v for k, v in conf.items() if k != "benchmark"}, \
        conf["benchmark"]


def test_the_tables_give_the_published_sizes_and_the_programs_tree():
    hf, dep = real_conf()
    shape = headroom.model_shape(ref, hf)
    # the union of the two kinds' tensors: a lightning layer's 285.2 M and
    # the sparse layers' k and v (2 x 4096 x 256) — an over-count of 0.7 %
    # of a lightning layer, 13 % of a sparse one (PERF.md section 7)
    assert shape["layer_params"] == 5 * 4096 ** 2 + 3 * 4096 * 16384 \
        + 2 * 4096 * 256
    assert shape["layers"] == 8 and shape["head_params"] == 4096 * 73448
    assert (shape["q_heads"], shape["kv_heads"], shape["head_dim"]) == \
        (32, 2, 128)
    assert ref.layer_kinds(hf) == [ref.SPARSE] + [ref.LINEAR] * 6 \
        + [ref.SPARSE]
    assert ref.sparse_sizes(hf) == (32, 16, 64, 64, 2048, 1, 8192)

    def tree(key):
        g = sw.draw_table(key, sw.GLOBAL_ID, ref.GLOBAL, hf, "bfloat16")
        layers = [sw.draw_table(key, i, ref.LAYER, hf, "bfloat16")
                  for i in range(8)]
        return ref.program_tree(hf, g, layers)

    shapes = jax.eval_shape(tree, sw.base_key(1))
    assert shapes["lm_head"]["kernel"].shape == (4096, 73448)
    sparse = shapes["model.layers.0.self_attn"]
    assert set(sparse) == {"qkv", "o_proj"}
    assert sparse["qkv"].shape == (4096, 4096 + 2 * 256 + 4096)
    linear = shapes["model.layers.3.self_attn"]
    assert linear["qkv"].shape == (4096, 4 * 4096)
    assert linear["q_norm"].shape == linear["k_norm"].shape == (128,)
    assert linear["o_norm"].shape == (4096,)
    assert set(shapes["model.layers.7.self_attn"]) == {"qkv", "o_proj"}
    assert dep["compile"]["max_requests"] == 48
    assert set(dep["controls"]) == {"int8_weights"}
    # the draw: the sparse layers' q and k scaled, nothing else
    w = sw.draw_table(sw.base_key(3), 0, ref.LAYER, hf, "float32")
    init = ref.published_init(hf, w)
    assert set(init) == {"self_attn.sparse_q_proj", "self_attn.sparse_k_proj"}
    ratio = float(jax.numpy.std(init["self_attn.sparse_k_proj"])
                  / jax.numpy.std(w["self_attn.k_proj"]))
    assert abs(ratio / ref.SPARSE_QK_GAIN - 1) < 1e-3


@pytest.mark.parametrize("t,positions,index", [
    # past dense_len: block 0 (64), the 32 newest blocks 161..192 as far as
    # they are filled (12346 - 161 x 64 = 2042), 64 whole chosen blocks
    (12345, 64 + 2042 + 64 * 64, 770),
    # below dense_len: every position
    (5000, 5001, 311),
])
def test_sparse_decode_cost_by_hand(t, positions, index):
    hf, _ = real_conf()
    sizes = ref.sparse_sizes(hf)
    assert costs_sala.attended_positions(t + 1, sizes) == positions
    ops, nbytes = costs_sala.sparse_decode_cost([t + 1], hf)
    entry = 2 * 128 * 2                  # one K (or V, or index) entry
    qo = 2 * 32 * 128 * 2                # q in, o out
    assert nbytes == 2 * (2 * entry * (positions + 1) + entry * index + qo)
    assert ops == 2 * 4 * 32 * 128 * positions
    # two rows add up; the layers are counted from mixer_types
    both = costs_sala.sparse_decode_cost([t + 1, t + 1], hf)
    assert both == (2 * ops, 2 * nbytes)
    one_layer = dict(hf, mixer_types=["minicpm4"] + ["lightning-attn"] * 7)
    assert costs_sala.sparse_decode_cost([t + 1], one_layer) == \
        (ops // 2, nbytes // 2)


def test_attended_positions_at_the_edges():
    sizes = ref.sparse_sizes(real_conf()[0])
    at = lambda t: costs_sala.attended_positions(t + 1, sizes)
    assert [at(0), at(63), at(8191)] == [1, 64, 8192]
    # the first selecting row: block 0, blocks 97..128 (2047 + 1 live), 64
    assert at(8192) == 64 + (8193 - 97 * 64) + 4096 == 6145
    # never more than 97 blocks' worth, whatever the context
    assert max(at(t) for t in range(8192, 32768, 37)) == 97 * 64


def test_lightning_decode_cost_by_hand():
    hf, _ = real_conf()
    ops, nbytes = costs_sala.lightning_decode_cost([12346, 5001, 7], hf)
    state = 32 * 128 * 128
    assert state * 4 == 2097152
    assert nbytes == 6 * 3 * (2 * 2097152 + 4 * 32 * 128 * 2)
    assert ops == 6 * 3 * 5 * state


def test_the_cell_outlasts_its_window_and_no_request_can_end_in_it():
    """headroom.py counts every layer as a lightning layer plus the sparse k
    and v (the union table) and a full-length cache on 2 K/V heads in all 8:
    it over-counts the weights by 3 %, and says 387 s to empty the queue.
    By hand: a step streams 5.04 GB of weights and head and, per row, 39 MB
    of selected K/V, index and state: 8.4 ms at 48 rows — a 51 s window and
    its 4 s rehearsal advance a row by ~6 500 steps, fewer than the shortest
    answer."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell and cell[0]["chips"] == 1
    mine = [h for name, _, h in headroom.closed_cells() if name == CELL]
    assert mine and all(h["ratio"] >= headroom.HEADROOM for h in mine)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "longctx-reason.json")) as f:
        mix = json.load(f)
    hf, dep = real_conf()
    sched = headroom.traffic_gen.schedule(mix, bench["run_seconds"])
    slots = dep["compile"]["max_requests"]
    assert len(sched) == 96 and mix["round"] == slots == 48
    assert all(p + o <= dep["compile"]["max_seq_len"] for _, p, o in sched)
    assert min(p for _, p, _ in sched) >= 12288 > hf["sparse_dense_len"]
    first = sched[:slots]
    rows = [p + o // 2 for _, p, o in first]       # mid-answer contexts
    _, sparse = costs_sala.sparse_decode_cost(rows, hf)
    _, linear = costs_sala.lightning_decode_cost(rows, hf)
    body = 2 * (3 * 4096 ** 2 + 2 * 4096 * 256) + 6 * 5 * 4096 ** 2 \
        + 8 * 3 * 4096 * 16384
    weights = 2 * (body + 4096 * 73448)
    assert 5.03e9 < weights < 5.05e9
    assert 1.8e9 < sparse + linear < 1.95e9
    step = (sparse + linear + weights) / 819e9
    assert 0.0083 < step < 0.0086
    window = bench["run_seconds"] + mix["rehearse_s"]
    assert window * 1.1 / step < min(o for _, _, o in sched)
    # 96 requests of ~10 240 steps at 48 a step: well over 1.5 windows
    assert 96 * 10240 * step / 48 > 1.5 * window
    # the first wave's prompts, fed before the window (and the rehearsal's)
    assert 700e3 < sum(p for _, p, _ in first) < 870e3


def test_the_metric_files_name_scopes_the_program_opens():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "sparse_attn_dev_ms_per_tok.thr", "block_select_dev_ms_per_tok.thr",
        "linear_attn_dev_ms_per_tok.thr", "sparse_attn_roofline.thr",
        "linear_attn_roofline.thr"]
    from flexflow_tpu.serve import hybrid_ops

    for m in mine:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert m["moves"] == "total_tok_s"
        for node in spec["args"]["nodes"]:
            assert node == "BlockSelect" or hasattr(hybrid_ops, node)
        if "cost" in spec["args"]:
            module, _, fn = spec["args"]["cost"].partition(":")
            assert module == "costs_sala" and hasattr(costs_sala, fn)
    # the selection carries no stage scope: the roofline's ``null`` stage
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           "sparse_attn_roofline.thr.json")) as f:
        assert json.load(f)["args"]["stages"] == ["attend", "kv_write", None]


@pytest.fixture(scope="module")
def toy_llm(pallas_on_cpu):
    return run.build(TOY, DEP, jax.devices()[:1])


def test_the_long_context_drive_is_sound_at_toy_widths(toy_llm):
    lines = []
    for seed in (7, 2 ** 31 + 11):
        key = run.seed_weights(toy_llm, ref, TOY, seed, "bfloat16")
        ok, numbers, reading = longctx.run_longctx(
            toy_llm.im, ref, TOY, key, "bfloat16", seed, DEP["correct"],
            lines.append)
        assert ok, "\n".join(lines)
        assert reading[2] == 2 * 2 * 68 and reading[1] > 0.8
    assert [len(s) for s in longctx.sequences(7, 512, 8, 48)] == [52, 18]


def test_the_long_context_drive_sees_the_newest_blocks_read_instead(
        pallas_on_cpu, monkeypatch):
    import jax.numpy as jnp

    from flexflow_tpu.serve.hybrid_ops import SparseBlockAttention

    def newest(self, q, idx, pos):
        last = (pos // self.block_size)[:, None]
        b = jnp.arange(idx.shape[2] * self.kernel_stride // self.block_size)
        keep = (b <= last) & (b > last - self.max_blocks)
        return jnp.broadcast_to(keep[:, None], (q.shape[0],
                                                self.num_kv_heads,
                                                b.shape[0]))

    monkeypatch.setattr(SparseBlockAttention, "select", newest)
    llm = run.build(TOY, DEP, jax.devices()[:1])
    key = run.seed_weights(llm, ref, TOY, 7, "bfloat16")
    ok, numbers, _ = longctx.run_longctx(
        llm.im, ref, TOY, key, "bfloat16", 7, DEP["correct"], lambda m: None)
    assert not ok
