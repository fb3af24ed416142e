"""The files the ``solar-open2-d4-e40`` configuration brought: its file against
the catalog's config, its reference's tables against the published sizes and
the program's tree, the reference against a layer of each kind written out by
hand at ``beta`` > 1, the cost functions against a hand count, its cell
against the headroom rule BY HAND on the active parameters (headroom.py
OVERCOUNTS this model: both mixers and every held expert dense in every
layer), the metric files' arguments, and ``check.run_check`` at toy widths
(CPU; Pallas interpreted; float32) — sound, and NOT correct with ``beta``'s
``x 2`` or the gate dropped."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT

from benchmark import check, costs_mellum, costs_solar_open2, headroom, run
from benchmark import seeded_weights as sw
from benchmark.reference import solar_open2 as ref

NAME = "solar-open2-d4-e40"
CELL = NAME + ".longdoc-prefill"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
LISTS = {"num_heads": 4, "head_dim": 32, "short_conv_kernel_size": 4,
         "num_kv_heads": None}
TOY = {"model_type": "solar_open2", "vocab_size": 512, "hidden_size": 64,
       "num_hidden_layers": 4, "linear_attn_config": LISTS,
       "gqa_layers": [0], "gqa_interval": 3, "num_attention_heads": 8,
       "num_key_value_heads": 2, "head_dim": 16, "use_rope": False,
       "use_gqa_gate": True, "kda_allow_neg_eigval": True,
       "kda_use_full_proj": False, "intermediate_size": 96,
       "moe_intermediate_size": 24, "n_routed_experts": 4,
       "router_num_experts": 32, "expert_share_index": 0,
       "expert_share_count": 8, "num_experts_per_tok": 8,
       "n_shared_experts": 1, "first_k_dense_replace": 0,
       "norm_topk_prob": True, "routed_scaling_factor": 1,
       "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
       "init_std": 0.125, "torch_dtype": "float32"}
DEP = {"chips": 1, "tp": 1, "precision": "float32",
       "compile": {"max_requests": 3, "max_tokens_per_batch": 128,
                   "max_seq_len": 512, "dtype": "float32", "topk": 8},
       # the toy's own (CPU): sound seeds read 0.00 ulps and 0.0000 nats to
       # four decimals; a break reads logprob_rms 0.01 or more
       "correct": {"logit_rms_ulps": 0.02, "logit_max_ulps": 0.1,
                   "logprob_rms": 2e-4, "logprob_max": 2e-3,
                   "tail_logprob_rms": 2e-4, "token_gap_ulps": 0.1}}


def real_conf():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           NAME + ".json")) as f:
        conf = json.load(f)
    return {k: v for k, v in conf.items() if k != "benchmark"}, \
        conf["benchmark"]


def test_the_file_is_the_catalogs_config_but_for_what_it_lists_as_reduced():
    hf, dep = real_conf()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Solar-Open2-250B")
    assert dep["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if k not in hf
               or hf[k] != v}
    assert differs == set(dep["reduced"]) == {
        "num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size"}
    assert (hf["num_hidden_layers"], row["config"]["num_hidden_layers"]) == \
        (4, 48)
    assert hf["gqa_layers"] == [0] == row["config"]["gqa_layers"][:1]
    assert row["config"]["gqa_layers"] == list(range(0, 48, 4))  # 0-based
    assert (hf["n_routed_experts"], row["config"]["n_routed_experts"],
            hf["router_num_experts"]) == (40, 320, 320)
    assert (hf["expert_share_index"], hf["expert_share_count"]) == (0, 8)
    assert (hf["vocab_size"], row["config"]["vocab_size"]) == \
        (24576, 196608) and 8 * 24576 == 196608
    # the nested group whole, every width and count as published
    assert hf["linear_attn_config"] == row["config"]["linear_attn_config"] \
        == {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
            "num_kv_heads": None}
    for key, value in dict(
            hidden_size=4096, num_attention_heads=64, num_key_value_heads=8,
            head_dim=128, intermediate_size=10240, moe_intermediate_size=1280,
            num_experts_per_tok=8, n_shared_experts=1,
            routed_scaling_factor=1, norm_topk_prob=True, use_rope=False,
            use_gqa_gate=True, kda_allow_neg_eigval=True,
            kda_use_full_proj=False, gqa_interval=3, first_k_dense_replace=0,
            rms_norm_eps=1e-5, tie_word_embeddings=False).items():
        assert hf[key] == row["config"][key] == value, key
    assert set(hf) - set(row["config"]) == {
        "torch_dtype", "router_num_experts", "expert_share_index",
        "expert_share_count"}
    for said in ("torch_dtype", "kda_use_full_proj false READ",
                 "kda_allow_neg_eigval true READ", "the GQA gate's FORM",
                 "the 0-based reading of gqa_layers", "NO q/k norm"):
        assert any(said in a for a in dep["assumed"]), said
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"] if c["name"] == NAME][0]
    assert entry["reduced"] == list(dep["reduced"])
    assert entry["source"] == row["source_url"]
    assert dep["controls"] == {"int8_weights": {"quantize_int8": True}}
    compiled = dep["compile"]
    assert compiled["max_requests"] == 16 and compiled["topk"] == 8
    assert compiled["max_seq_len"] == 24832 >= 24576 + 32
    assert compiled["max_seq_len"] % 256 == 0
    assert compiled["max_tokens_per_batch"] in (512, 1024, 2048)
    assert "chip 0 of the 8 that share each layer" in dep["deployment"]
    assert "an eighth" in dep["reduced"]["n_routed_experts"]
    # every limit lies between its two sides' readings where both are given
    limits = dep["correct"]
    sound = limits["readings"]["check_sound_largest"]
    control = limits["readings"]["control_int8_weights_smallest"]
    for name in ("logprob_rms", "logit_rms_ulps"):
        assert sound[name] < limits[name] < control[name], name


def test_the_tables_give_the_published_sizes_and_the_programs_tree():
    hf, dep = real_conf()
    assert ref.layer_kinds(hf) == ["gqa", "kda", "kda", "kda"]
    assert not any(ref.is_dense(hf, i) for i in range(4))
    assert ref.held_experts(hf) == (0, 40)
    # headroom's heads: the one attention layer's spread over the four
    assert ref.attention_shape(hf) == (16, 2, 128)
    assert 4 * 2 * 2 * 128 * 2 == 4096           # B a cached position
    d, expert = 4096, 3 * 4096 * 1280

    def tree(key):
        g = sw.draw_table(key, sw.GLOBAL_ID, ref.GLOBAL, hf, "bfloat16")
        layers = [sw.draw_table(key, i, ref.LAYER, hf, "bfloat16")
                  for i in range(4)]
        return ref.program_tree(hf, g, layers)

    shapes = jax.eval_shape(tree, sw.base_key(1))
    assert shapes["lm_head"]["kernel"].shape == (d, 24576)
    size = lambda node: sum(a.size for a in shapes[node].values())
    for i in range(4):
        p = f"model.layers.{i}"
        attn = {k: (v.shape, str(v.dtype))
                for k, v in shapes[f"{p}.self_attn"].items()}
        if i == 0:
            assert attn == {"qkv": ((d, 8, 10, 128), "bfloat16"),
                            "g_proj": ((d, 8192), "bfloat16"),
                            "o_proj": ((8192, d), "bfloat16")}
            assert size(f"{p}.self_attn") == 109051904       # 109.1 M
        else:
            assert attn == {"f_a": ((d, 128), "bfloat16"),
                            "f_b": ((128, 8192), "bfloat16"),
                            "dt_bias": ((8192,), "float32"),
                            "A_log": ((64,), "float32"),
                            "b_proj": ((d, 64), "bfloat16"),
                            "g_a": ((d, 128), "bfloat16"),
                            "g_b": ((128, 8192), "bfloat16"),
                            "o_norm": ((128,), "bfloat16"),
                            "o_proj": ((8192, d), "bfloat16")}
            assert shapes[f"{p}.self_attn.qkv_proj"]["kernel"].shape == \
                (d, 3 * 8192)
            assert shapes[f"{p}.self_attn.qkv_conv1d"]["weight"].shape == \
                (4, 3 * 8192)
            assert round((size(f"{p}.self_attn")
                          + size(f"{p}.self_attn.qkv_proj")
                          + size(f"{p}.self_attn.qkv_conv1d")) / 1e5) == 1377
        assert {k: (v.shape, str(v.dtype))
                for k, v in shapes[f"{p}.mlp.gate"].items()} == {
            "weight": ((d, 320), "float32"),
            "e_score_correction_bias": ((320,), "float32")}
        assert {k: v.shape for k, v in shapes[f"{p}.mlp.experts"].items()} \
            == {"gate": (40, d, 1280), "up": (40, d, 1280),
                "down": (40, 1280, d)}
        assert shapes[f"{p}.mlp.shared_experts.gate_proj"]["kernel"].shape \
            == (d, 1280)
        assert f"{p}.mlp.gate_proj" not in shapes        # no dense layer
    assert expert == 15728640
    total = sum(a.size for a in jax.tree.leaves(shapes))
    assert round(total / 1e6) == 3308                    # the file's 3 308 M
    # the state beside it: 3 x (4 194 304 + 147 456) B a slot fixed, 4 096 B a
    # position in the one attention layer, 17 rows
    fixed = 3 * (64 * 128 * 128 * 4 + 3 * 3 * 8192 * 2)
    assert fixed == 13025280
    state = 17 * (fixed + 24832 * 4096)
    assert round(state / 1e9, 2) == 1.95
    assert 0.49 < (2 * total + state) / 17.18e9 < 0.51
    # the program builds the same tree (shapes only: nothing is allocated)
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.model import FFModel
    from flexflow_tpu.serve.models.base import (ServeModelConfig,
                                                build_model)

    ff = FFModel(FFConfig())
    build_model(ff, ServeModelConfig.from_hf_config(hf),
                dep["compile"]["max_tokens_per_batch"])
    built = {n.name: {p.name: tuple(p.spec.shape) for p in n.op.params()}
             for n in ff.graph.nodes if n.op.params()}
    assert built == {node: {p: tuple(a.shape) for p, a in ps.items()}
                     for node, ps in shapes.items()}
    # headroom counts the union table in every layer
    shape = headroom.model_shape(ref, hf)
    assert shape["layer_params"] > 137e6 + 109e6 + 41 * expert
    assert shape["head_params"] == 24576 * d


def test_a_layer_of_each_kind_by_hand():
    """The gated attention layer and a delta-rule layer of a two-layer toy
    written out row by row in numpy float64 — no rotation, 4 queries a K/V
    head, the gate before ``W_o``; three convs, the L2 norms, the vector
    decay, ``beta = 2 sigmoid`` (its rows over 1 counted), the delta rule,
    the gated head norm; the sigmoid top-8 of 32 renormalised with 4 held and
    the shared expert — against ``ref.layer``."""
    hf = dict(TOY, num_hidden_layers=2, gqa_interval=1)
    key = sw.base_key(3)
    t, d = 14, 64
    x = np.random.default_rng(0).standard_normal((1, t, d))
    rms = lambda v, g, eps=1e-5: v / np.sqrt(
        (v * v).mean(-1, keepdims=True) + eps) * g
    sig = lambda a: 1 / (1 + np.exp(-a))
    silu = lambda a: a * sig(a)
    over_one = 0
    for i, kind in enumerate(["gqa", "kda"]):
        drawn = sw.draw_table(key, i, ref.LAYER, hf, "float32")
        init = {n: np.asarray(a, np.float64)
                for n, a in ref.published_init(hf, drawn).items()}
        w = {n: np.asarray(a, np.float64) for n, a in drawn.items()}
        n = rms(x[0], w["input_layernorm.weight"])
        if kind == "gqa":
            h, kv, hd = 8, 2, 16
            q = (n @ w["self_attn.q_proj@gqa"]).reshape(t, h, hd)
            k = (n @ w["self_attn.k_proj@gqa"]).reshape(t, kv, hd)
            v = (n @ w["self_attn.v_proj@gqa"]).reshape(t, kv, hd)
            o = np.zeros((t, h, hd))
            for row in range(t):
                for head in range(h):
                    s = k[:row + 1, head // 4] @ q[row, head] / math.sqrt(hd)
                    p = np.exp(s - s.max())
                    o[row, head] = (p / p.sum()) @ v[:row + 1, head // 4]
            gated = o.reshape(t, h * hd) * sig(n @ w["self_attn.g_proj@gqa"])
            y = x[0] + gated @ w["self_attn.o_proj@gqa"]
        else:
            h, hd = 4, 32

            def conv(name):
                a = n @ w[f"self_attn.{name}_proj"]
                taps = init[f"self_attn.{name}_conv1d.weight"]    # [4, C]
                padded = np.concatenate([np.zeros((3, a.shape[1])), a])
                return silu(sum(padded[j:j + t] * taps[j] for j in range(4))
                            ).reshape(t, h, hd)

            q, k, v = conv("q"), conv("k"), conv("v")
            unit = lambda a: a / np.maximum(
                np.sqrt((a * a).sum(-1, keepdims=True)), 1e-6)
            q, k = unit(q) * hd ** -0.5, unit(k)
            raw = n @ w["self_attn.f_a_proj"] @ w["self_attn.f_b_proj"]
            g = -np.exp(init["self_attn.A_log"])[:, None] * np.log1p(
                np.exp(raw + init["self_attn.dt_bias"])).reshape(t, h, hd)
            beta = 2 * sig(n @ w["self_attn.b_proj"])             # [t, h]
            over_one += int((beta > 1).sum())
            assert beta.max() < 2 and beta.min() > 0
            state, o = np.zeros((h, hd, hd)), np.zeros((t, h, hd))
            for row in range(t):
                for head in range(h):
                    s = state[head] * np.exp(g[row, head])[:, None]
                    u = v[row, head] - s.T @ k[row, head]
                    s = s + beta[row, head] * np.outer(k[row, head], u)
                    state[head], o[row, head] = s, s.T @ q[row, head]
            gate = (n @ w["self_attn.g_a_proj"] @ w["self_attn.g_b_proj"]
                    ).reshape(t, h, hd)
            normed = rms(o, w["self_attn.o_norm.weight"]) * sig(gate)
            y = x[0] + normed.reshape(t, h * hd) @ init["self_attn.o_proj"]
        m = rms(y, w["post_attention_layernorm.weight"])
        out = y.copy()
        mlp = lambda r, g_, u_, d_: (silu(r @ g_) * (r @ u_)) @ d_
        for row in range(t):
            s = sig(m[row] @ init[ref.ROUTER])
            chosen = np.argsort(-(s + init[ref.ROUTER_BIAS]),
                                kind="stable")[:8]
            for e in chosen:
                if e < 4:           # held: ids 0-3 of the 32 scored
                    out[row] += s[e] / s[chosen].sum() * mlp(
                        m[row], *(w[name][e] for name in ref.EXPERTS))
            out[row] += mlp(m[row], *(w[f"mlp.shared_experts.{n_}_proj"]
                                      for n_ in ("gate", "up", "down")))
        got = ref.layer(hf, drawn, ref.Stream(jnp.asarray(x, jnp.float32),
                                              jnp.int32(i)))
        np.testing.assert_allclose(np.asarray(got.h[0]), out, atol=2e-4)
        assert int(got.layer) == i + 1
    assert 0.3 * t * 4 < over_one < 0.7 * t * 4     # about half over 1


def test_the_cost_functions_by_hand():
    hf, _ = real_conf()
    assert costs_solar_open2.routed_prefill_cost is \
        costs_mellum.routed_prefill_cost
    ops, nbytes = costs_solar_open2.routed_prefill_cost(40, 1024 * 8 // 8, hf)
    assert ops == 1024 * 6 * 4096 * 1280
    assert nbytes == 40 * 3 * 4096 * 1280 * 2 + 1024 * 2 * 4096 * 2
    # 40 experts' matrices a chunk and layer are 1.5 ms of stream; a chunk's
    # pairs on held experts (1024 of its 8192) are 0.16 ms of arithmetic
    assert nbytes / 819e9 == pytest.approx(1.56e-3, rel=0.02)
    assert ops / 197e12 == pytest.approx(0.16e-3, rel=0.03)
    # the delta rule: three KDA layers a row, the state once a segment
    ops, nbytes = costs_solar_open2.kda_prefill_cost(2048, 3, hf)
    state = 64 * 128 * 128
    assert ops == 3 * 2048 * 8 * state and ops / 2048 == 25165824  # 25 MFLOP
    row = (3 * 8192 + 8192) * 2 + (8192 + 64) * 4
    assert nbytes == 3 * (2048 * row + 3 * 2 * state * 4)
    assert costs_solar_open2.kda_prefill_cost(2048, 0, hf)[1] == \
        3 * 2048 * row
    # per 1000 prompt tokens: 0.13 ms of arithmetic, 0.36 ms of rows
    assert 1000 * 25165824 / 197e12 == pytest.approx(0.128e-3, rel=0.01)
    assert 1000 * 3 * row / 819e9 == pytest.approx(0.36e-3, rel=0.02)
    # the gated layer's attention proper: ONE layer, 4 x 64 x 128 a key
    ctx = sum(range(8192, 9216)) + 1024        # a chunk from position 8192
    ops, nbytes = costs_solar_open2.full_prefill_cost(ctx, 1024, hf)
    assert ops == 4 * 64 * 128 * ctx
    assert nbytes == 1024 * (2 * 2 * 8 * 128 * 2 + 2 * 64 * 128 * 2)


def test_the_cell_outlasts_its_window_by_hand():
    """headroom.py's ratio (8.8) rests on an overcount: both mixers and all
    40 held experts for every prompt row in every layer.  By hand, on ACTIVE
    parameters: a prompt row costs 2 x (3 x 170.5 M + 141.9 M) = 1.307 GFLOP
    in matrices (a KDA layer 137.7 M + router 1.31 M + the shared expert and
    ONE routed expert of the row's eight on average — 8 x 40 / 320 — 15.73 M
    each; the attention layer 109.1 M instead of 137.7 M), 4 x 64 x 128 a
    visible key in the one attention layer and 3 x 25.2 MFLOP of delta rule:
    the mean prompt (16 384) is 0.13 s at 197 TFLOP/s before one decode step
    — 768 requests last 103 s at the least, over 1.5 x 55 s."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell and cell[0]["chips"] == 1
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 0
    mine = [h for name, _, h in headroom.closed_cells() if name == CELL]
    assert mine and all(h["ratio"] >= headroom.HEADROOM for h in mine)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "longdoc-prefill.json")) as f:
        mix = json.load(f)
    hf, dep = real_conf()
    assert mix["loop"] == "closed" and mix["reports"] == ["total_tok_s"]
    assert mix["prompt_len"] == {"dist": "uniform", "lo": 8192, "hi": 24576}
    assert mix["output_len"] == {"dist": "uniform", "lo": 8, "hi": 32}
    assert (mix["rehearse_s"], mix["trace_span_s"]) == (4, 4)
    sched = headroom.traffic_gen.schedule(mix, bench["run_seconds"])
    assert len(sched) == mix["queue_depth"] == 4 * mix["round"] == 768
    assert dep["compile"]["max_requests"] == 16 == mix["block"]
    assert all(p + o <= dep["compile"]["max_seq_len"] for _, p, o in sched)
    assert min(p for _, p, _ in sched) >= 8192
    active = 3 * (137.7e6 + 1.31e6 + 2 * 15.73e6) + (109.1e6 + 1.31e6
                                                      + 2 * 15.73e6)
    assert active == pytest.approx(653.4e6, rel=0.001)
    least = 0.0
    for _, p, _ in sched:
        matrices = 2 * active * p
        delta = 3 * 8 * 64 * 128 * 128 * p
        full = 4 * 64 * 128 * p * (p + 1) / 2
        least += (matrices + delta + full) / 197e12
    window = bench["run_seconds"] + mix["rehearse_s"]
    assert headroom.HEADROOM * window <= least < 125
    # and what the chip's arithmetic alone makes of the mix: ~120k tokens/s
    tokens = sum(p + o for _, p, o in sched)
    assert 100e3 < tokens / least < 140e3


def test_the_metric_files_name_scopes_the_program_opens():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    from flexflow_tpu.obs import journal
    from flexflow_tpu.serve import hybrid_ops, ops, ssd_moe_ops

    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    # (two journal ratios — pieces per 1000 prompt tokens, rows a held expert
    # — are NOT here: a traced window of this cell keeps no journal record
    # that holds a prompt tick, PERF.md section 7)
    assert [m["name"] for m in mine] == [
        "kda_prefill_dev_ms_per_ktok.thr", "kda_prefill_roofline.thr",
        "gated_attn_prefill_dev_ms_per_ktok.thr",
        "gated_attn_prefill_roofline.thr", "moe_prefill_e40_roofline.thr"]
    for m in mine:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert m["moves"] == "total_tok_s"
        assert os.path.exists(os.path.join(ROOT, "benchmark",
                                           "layer_metrics", spec["reader"]))
        for node in spec["args"].get("nodes", ()):
            assert any(hasattr(mod, node)
                       for mod in (ssd_moe_ops, hybrid_ops, ops))
        if "cost" in spec["args"]:
            module, _, fn = spec["args"]["cost"].partition(":")
            assert module == "costs_solar_open2"
            assert hasattr(costs_solar_open2, fn)
        for field in spec["args"].get("num", []) + spec["args"].get("den",
                                                                    []):
            assert field in journal.FIELDS
    assert all(m["unit"] == "%" for m in mine if "roofline" in m["name"])
    assert "prompt_kda_pieces" in journal.FIELDS
    # the shared lists the cell was appended to (a later PR may append a
    # cell of its own to them: the four older families' pins of a COUNT
    # went stale that way and are not repeated here)
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ()) and len(m["workloads"]) > 1}
    assert {"prefill_dev_ms_per_ktok.thr", "decode_dev_ms_per_tok.thr",
            "full_attn_dev_ms_per_tok.thr", "moe_dev_ms_per_tok.thr",
            "program_builds.setup"} <= listed
    assert not {"prefill_chunk_fill_pct.thr",
                "window_admit_ms_per_ktok.thr"} & listed
    reports = [m["name"] for m in bench["end_to_end"]
               if CELL in m.get("workloads", (CELL,))]
    assert reports == ["total_tok_s", "setup_s"]
    # the spans the two cost readers sum carry their arguments
    import inspect

    from flexflow_tpu.serve import request_manager

    feed = inspect.getsource(request_manager.RequestManager)
    for arg in ("prompt_ctx_sum", "prompt_kda_pieces", '"segments"'):
        assert arg in feed


@pytest.fixture(scope="module")
def toy_llm(pallas_on_cpu):
    return run.build(TOY, DEP, jax.devices()[:1])


def test_the_check_is_sound_at_toy_widths(toy_llm):
    lines = []
    for seed in (7, 2 ** 31 + 11):
        key = run.seed_weights(toy_llm, ref, TOY, seed, "float32")
        ok, _ = check.run_check(
            toy_llm.im, ref, TOY, key, "float32", seed, TOY["vocab_size"],
            DEP["correct"], lines.append)
        assert ok, "\n".join(lines)
    paths = toy_llm.im.attention_paths
    assert paths[("kimi_delta_attention", "PrefillBatchConfig")] == \
        "chunked+neg_eigval"
    assert paths[("kimi_delta_attention", "one_row_per_request")] == \
        "delta_rule_step+neg_eigval"
    full = "inc_multihead_self_attention"
    for batch in ("PrefillBatchConfig", "one_row_per_request", "BatchConfig"):
        assert paths[("attention_gate", (full, batch))] == "elementwise"
    assert paths[("prefill_operands", full)] == "float32"
    # sequence A (437 positions): three 128-row chunks and a ragged one, 14
    # pieces of the chunked form a layer; the gated layer's cache is read
    # from prefill into the decode scans
    assert "contexts up to 437" in lines[-1]
    share = 4 / 3                            # the scratch row, amortised
    assert toy_llm.im.kv.bytes_per_token() == pytest.approx(
        2 * 2 * 16 * 4 * share)              # ONE attention layer
    assert toy_llm.im.kv.fixed_bytes_per_slot() == pytest.approx(
        3 * (4 * 32 * 32 * 4 + 3 * 3 * 128 * 4) * share)


@pytest.mark.parametrize("broken", ["beta_not_doubled", "gate_dropped",
                                    "top_k_not_renormalised"])
def test_the_check_sees_a_break(pallas_on_cpu, broken):
    hf = dict(TOY, **{"beta_not_doubled": {"kda_allow_neg_eigval": False},
                      "gate_dropped": {"use_gqa_gate": False},
                      "top_k_not_renormalised": {"norm_topk_prob": False}
                      }[broken])
    llm = run.build(hf, DEP, jax.devices()[:1])
    if broken == "gate_dropped":
        # the program without its gate holds no g_proj: the reference's tree
        # for the same fields has none either, and the sound fields judge
        key = run.seed_weights(llm, ref, hf, 7, "float32")
    else:
        key = run.seed_weights(llm, ref, TOY, 7, "float32")
    ok, _ = check.run_check(llm.im, ref, TOY, key, "float32", 7,
                            TOY["vocab_size"], DEP["correct"],
                            lambda m: None)
    assert not ok
