"""The files the ``kimi-linear-d5-e32`` configuration brought: its file
against the catalog, its reference's tables against the published sizes and
the program's tree, the cost functions against a hand count, its cell against
the headroom rule and the hand count of a step, the metric files' arguments,
and ``check.run_check`` at toy widths (CPU; Pallas interpreted; float32: in
bfloat16 a toy router flips a choice in a few percent of its rows against the
float32 reference, so the toy's sound readings would spread as wide as its
faults — the real widths' readings are in the configuration's file) — sound,
and NOT correct with the delta correction dropped or the decay made a scalar
a head."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from conftest import ROOT

from benchmark import check, costs_kimi_linear, headroom, run
from benchmark import seeded_weights as sw
from benchmark.reference import kimi_linear as ref

NAME = "kimi-linear-d5-e32"
CELL = NAME + ".reason-decode"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TOY = {"model_type": "kimi_linear", "vocab_size": 512, "hidden_size": 128,
       "num_hidden_layers": 5,
       "linear_attn_config": {"kda_layers": [1, 2, 3, 5],
                              "full_attn_layers": [4], "num_heads": 4,
                              "head_dim": 32, "short_conv_kernel_size": 4},
       "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 32,
       "kv_lora_rank": 64, "q_lora_rank": None, "qk_nope_head_dim": 32,
       "qk_rope_head_dim": 16, "v_head_dim": 32, "mla_use_nope": True,
       "rope_scaling": None, "rope_theta": 10000, "intermediate_size": 192,
       "moe_intermediate_size": 48, "num_experts": 4,
       "router_num_experts": 32, "expert_share_index": 0,
       "expert_share_count": 8, "num_experts_per_token": 8,
       "num_shared_experts": 1, "first_k_dense_replace": 1,
       "moe_layer_freq": 1, "moe_renormalize": True,
       "moe_router_activation_func": "sigmoid", "num_expert_group": 1,
       "topk_group": 1, "use_grouped_topk": True,
       "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5,
       "tie_word_embeddings": False, "init_std": 0.09,
       "torch_dtype": "float32"}
DEP = {"chips": 1, "tp": 1, "precision": "float32",
       "compile": {"max_requests": 3, "max_tokens_per_batch": 64,
                   "max_seq_len": 512, "dtype": "float32", "topk": 8},
       # the toy's own (CPU): sound seeds read 0.00 ulps and 0.0000 nats to
       # four decimals; a break reads logprob_rms 0.004 or more
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
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert dep["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if k not in hf
               or hf[k] != v}
    assert differs == set(dep["reduced"]) == {
        "num_hidden_layers", "linear_attn_config", "num_experts",
        "vocab_size"}
    assert (hf["num_hidden_layers"], row["config"]["num_hidden_layers"]) == \
        (5, 27)
    assert (hf["num_experts"], row["config"]["num_experts"],
            hf["router_num_experts"]) == (32, 256, 256)
    assert (hf["expert_share_index"], hf["expert_share_count"]) == (0, 8)
    assert (hf["vocab_size"], row["config"]["vocab_size"]) == \
        (20480, 163840) and 8 * 20480 == 163840
    # the nested group: the two lists cut, every width as published
    lists, published = hf["linear_attn_config"], \
        row["config"]["linear_attn_config"]
    assert {k for k in published if lists[k] != published[k]} == {
        "kda_layers", "full_attn_layers"}
    assert (lists["kda_layers"], lists["full_attn_layers"]) == \
        ([1, 2, 3, 5], [4])
    assert published["kda_layers"][:4] == [1, 2, 3, 5] and \
        published["full_attn_layers"][0] == 4 and \
        published["full_attn_layers"][-1] == 27      # 1-based
    assert (lists["num_heads"], lists["head_dim"],
            lists["short_conv_kernel_size"]) == (32, 128, 4)
    # every width and count as published
    for key, value in dict(
            hidden_size=2304, num_attention_heads=32, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512,
            intermediate_size=9216, moe_intermediate_size=1024,
            num_experts_per_token=8, num_shared_experts=1,
            routed_scaling_factor=2.446, moe_renormalize=True,
            moe_router_activation_func="sigmoid", mla_use_nope=True,
            rope_scaling=None, first_k_dense_replace=1,
            rms_norm_eps=1e-5).items():
        assert hf[key] == row["config"][key] == value, key
    assert set(hf) - set(row["config"]) == {
        "torch_dtype", "router_num_experts", "expert_share_index",
        "expert_share_count"}
    assert any(a.startswith("torch_dtype") for a in dep["assumed"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"] if c["name"] == NAME][0]
    assert entry["reduced"] == list(dep["reduced"])
    assert entry["source"] == row["source_url"]
    assert dep["controls"] == {"int8_weights": {"quantize_int8": True}}
    assert dep["compile"] == {"max_requests": 256,
                              "max_tokens_per_batch": 512,
                              "max_seq_len": 10240, "dtype": "bfloat16",
                              "topk": 8}
    # every limit lies between its two sides' readings where both are given
    limits = dep["correct"]
    sound = limits["readings"]["check_sound_largest"]
    control = limits["readings"]["control_int8_weights_smallest"]
    for name in ("logprob_rms", "logit_rms_ulps"):
        assert sound[name] < limits[name] < control[name], name


def test_the_tables_give_the_published_sizes_and_the_programs_tree():
    hf, dep = real_conf()
    assert ref.layer_kinds(hf) == ["kda", "kda", "kda", "latent", "kda"]
    assert [ref.is_dense(hf, i) for i in range(5)] == [True] + [False] * 4
    assert ref.held_experts(hf) == (0, 32)
    # headroom's bytes a cached position: never over the one latent layer's
    q_heads, kv_heads, hd = ref.attention_shape(hf)
    assert (q_heads, kv_heads, hd) == (32, 1, 57)
    assert 5 * 2 * kv_heads * hd * 2 == 1140 <= (512 + 64) * 2 == 1152
    d, expert = 2304, 3 * 2304 * 1024

    def tree(key):
        g = sw.draw_table(key, sw.GLOBAL_ID, ref.GLOBAL, hf, "bfloat16")
        layers = [sw.draw_table(key, i, ref.LAYER, hf, "bfloat16")
                  for i in range(5)]
        return ref.program_tree(hf, g, layers)

    shapes = jax.eval_shape(tree, sw.base_key(1))
    assert shapes["lm_head"]["kernel"].shape == (d, 20480)
    assert shapes["model.embed_tokens"]["weight"].shape == (20480, d)
    size = lambda node: sum(a.size for a in shapes[node].values())
    for i in range(5):
        p = f"model.layers.{i}"
        attn = {k: (v.shape, str(v.dtype))
                for k, v in shapes[f"{p}.self_attn"].items()}
        if i == 3:
            assert attn == {"q_proj": ((d, 32, 192), "bfloat16"),
                            "kv_a": ((d, 576), "bfloat16"),
                            "kv_norm": ((512,), "bfloat16"),
                            "kv_b": ((512, 32, 256), "bfloat16"),
                            "o_proj": ((4096, d), "bfloat16")}
            assert size(f"{p}.self_attn") == 29114880        # 29.11 M
        else:
            assert attn == {"f_a": ((d, 128), "bfloat16"),
                            "f_b": ((128, 4096), "bfloat16"),
                            "dt_bias": ((4096,), "float32"),
                            "A_log": ((32,), "float32"),
                            "b_proj": ((d, 32), "bfloat16"),
                            "g_a": ((d, 128), "bfloat16"),
                            "g_b": ((128, 4096), "bfloat16"),
                            "o_norm": ((128,), "bfloat16"),
                            "o_proj": ((4096, d), "bfloat16")}
            assert shapes[f"{p}.self_attn.qkv_proj"]["kernel"].shape == \
                (d, 3 * 4096)
            assert list(shapes[f"{p}.self_attn.qkv_conv1d"]) == ["weight"]
            assert shapes[f"{p}.self_attn.qkv_conv1d"]["weight"].shape == \
                (4, 3 * 4096)
            assert (size(f"{p}.self_attn") + size(f"{p}.self_attn.qkv_proj")
                    + size(f"{p}.self_attn.qkv_conv1d")) == 39514272
        if i == 0:
            assert shapes[f"{p}.mlp.gate_proj"]["kernel"].shape == (d, 9216)
            assert f"{p}.block_sparse_moe.experts" not in shapes
            continue
        moe = f"{p}.block_sparse_moe"
        assert {k: (v.shape, str(v.dtype))
                for k, v in shapes[f"{moe}.gate"].items()} == {
            "weight": ((d, 256), "float32"),
            "e_score_correction_bias": ((256,), "float32")}
        assert {k: v.shape for k, v in shapes[f"{moe}.experts"].items()} == {
            "gate": (32, d, 1024), "up": (32, d, 1024), "down": (32, 1024, d)}
        assert shapes[f"{moe}.shared_experts.gate_proj"]["kernel"].shape == \
            (d, 1024)
        assert f"{p}.mlp.gate_proj" not in shapes
    assert expert == 7077888                     # 14.16 MB in bf16
    total = sum(a.size for a in jax.tree.leaves(shapes))
    assert round(total / 1e6) == 1282            # the file's 1 282 M
    # the state beside it: 4 x (2 097 152 + 73 728) B a slot fixed, 1 152 B a
    # position in the one latent layer, 257 rows
    fixed = 4 * (32 * 128 * 128 * 4 + 3 * 3 * 4096 * 2)
    assert fixed == 8683520
    state = 257 * (fixed + 10240 * 1152)
    assert round(state / 1e9, 2) == 5.26
    assert 0.25 < (2 * total + state) / 17.18e9 < 0.50
    # the program builds the same tree (shapes only: nothing is allocated)
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.model import FFModel
    from flexflow_tpu.serve.models.base import (ServeModelConfig,
                                                build_model)

    ff = FFModel(FFConfig())
    build_model(ff, ServeModelConfig.from_hf_config(hf), 512)
    built = {n.name: {p.name: tuple(p.spec.shape) for p in n.op.params()}
             for n in ff.graph.nodes if n.op.params()}
    assert built == {node: {p: tuple(a.shape) for p, a in ps.items()}
                     for node, ps in shapes.items()}
    # headroom counts the union table in every layer (PERF.md section 7)
    shape = headroom.model_shape(ref, hf)
    kda = 39514272 - 4096 - 32 - 128           # its matrices (convs too)
    latent = 29114880 - 512
    assert shape["layer_params"] == (
        kda + latent + 3 * d * 9216 + d * 256 + 32 * expert + expert)
    assert shape["head_params"] == 20480 * d


def test_the_cost_functions_by_hand():
    hf, _ = real_conf()
    ops, nbytes = costs_kimi_linear.routed_decode_cost(32 * 4, 256 * 4, hf)
    matrix = 2304 * 1024
    assert nbytes == 128 * 3 * matrix * 2 + 1024 * 2 * 2304 * 2
    assert ops == 1024 * 6 * matrix
    # the delta rule: whatever the contexts, 4 KDA layers a row
    ops, nbytes = costs_kimi_linear.kda_decode_cost([900, 5000, 12000], hf)
    state = 32 * 128 * 128
    assert ops == 4 * 3 * 8 * state
    row = 2 * state * 4 + (2 * 3 * 12288 + 3 * 4096 + 4096 + 32 + 4096) * 2
    assert nbytes == 4 * 3 * row
    assert 2 * state * 4 == 2 * 2097152 and 0.95 < 2 * state * 4 / row < 0.96
    assert costs_kimi_linear.kda_decode_cost([1, 1, 1], hf) == (ops, nbytes)
    # the latent layer alone: ONE layer's positions
    ops, nbytes = costs_kimi_linear.latent_decode_cost([900, 5000, 12000], hf)
    positions = 900 + 5000 + 12000
    assert ops == 1 * positions * 2 * 32 * (576 + 512)
    assert nbytes == 1152 * (positions + 3) + 3 * 32 * (512 + 64 + 512) * 2
    one, two = (costs_kimi_linear.latent_decode_cost([n], hf)[1]
                for n in (1000, 1001))
    assert two - one == 1152


def test_the_cell_outlasts_its_window_and_no_request_can_end_in_it():
    """By hand, a step's least bytes at 256 rows (contexts ~1k-6k): the
    delta states 4.29 GB (+ 0.16 GB of tails and rows), the routed experts
    1.81 GB (all 4 x 32 visited), the latent cache ~0.7-1.0 GB, the other
    weights ~0.65 GB: ~9.5 ms — a 51 s window and its 4 s rehearsal advance
    a row by ~5 800 steps, fewer than the shortest answer."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell and cell[0]["chips"] == 1
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 0
    mine = [h for name, _, h in headroom.closed_cells() if name == CELL]
    assert mine and all(h["ratio"] >= headroom.HEADROOM for h in mine)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "reason-decode.json")) as f:
        mix = json.load(f)
    hf, dep = real_conf()
    sched = headroom.traffic_gen.schedule(mix, bench["run_seconds"])
    slots = dep["compile"]["max_requests"]
    assert len(sched) == 512 and mix["round"] == slots == 256
    assert all(p + o <= dep["compile"]["max_seq_len"] == 10240
               for _, p, o in sched)
    assert 512 <= min(p for _, p, _ in sched) and \
        max(p for _, p, _ in sched) <= 2048
    assert min(o for _, _, o in sched) >= 6144
    first = sched[:slots]
    rows = [p + o // 4 for _, p, o in first]     # contexts early in a window
    _, routed = costs_kimi_linear.routed_decode_cost(4 * 32, 4 * 256, hf)
    _, cache = costs_kimi_linear.latent_decode_cost(rows, hf)
    _, states = costs_kimi_linear.kda_decode_cost(rows, hf)
    d = 2304
    shared = 2 * 4 * 3 * d * 1024
    dense = 2 * 3 * d * 9216
    mixers = 2 * (4 * 39514272 + 29114880)
    head = 2 * 20480 * d
    assert 1.80e9 < routed < 1.83e9 and 4.4e9 < states < 4.5e9
    assert 0.7e9 < cache < 1.0e9 and 0.05e9 < shared < 0.06e9
    assert 0.12e9 < dense < 0.13e9 and 0.37e9 < mixers < 0.38e9
    assert 0.09e9 < head < 0.1e9
    step = (routed + states + cache + shared + dense + mixers + head) / 819e9
    assert 0.0090 < step < 0.0100
    assert 0.55 < states / (step * 819e9) < 0.60      # the mechanism's share
    window = bench["run_seconds"] + mix["rehearse_s"]
    assert window / step < min(o for _, _, o in sched)
    # the first wave's prompts, fed before the window
    assert 0.31e6 < sum(p for _, p, _ in first) < 0.35e6


def test_the_metric_files_name_scopes_the_program_opens():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "kda_dev_ms_per_tok.thr", "kda_state_roofline.thr",
        "kda_mla_decode_roofline.thr", "moe_e32_swiglu_roofline.thr",
        "expert_visit_e32_pct.thr"]
    from flexflow_tpu.obs import journal
    from flexflow_tpu.serve import hybrid_ops, ops, ssd_moe_ops

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
            assert module == "costs_kimi_linear"
            assert hasattr(costs_kimi_linear, fn)
    assert all(m["unit"] == "%" for m in mine if "roofline" in m["name"])
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           "expert_visit_e32_pct.thr.json")) as f:
        assert json.load(f)["args"]["scale"] == 100 / 32
    listed = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())]
    assert len(listed) == 13
    for field in ("experts_visited", "expert_steps", "expert_pairs"):
        assert field in journal.FIELDS
    reports = [m["name"] for m in bench["end_to_end"]
               if CELL in m.get("workloads", (CELL,))]
    assert reports == ["total_tok_s", "setup_s"]


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
    assert paths[("kimi_delta_attention", "PrefillBatchConfig")] == "chunked"
    assert paths[("kimi_delta_attention", "one_row_per_request")] == \
        "delta_rule_step"
    assert paths[("latent_attention", "BatchConfig")] == \
        "decode_attention_latent"
    share = 4 / 3                            # the scratch row, amortised
    assert toy_llm.im.kv.bytes_per_token() == pytest.approx(
        (64 + 16) * 4 * share)               # ONE latent layer
    assert toy_llm.im.kv.fixed_bytes_per_slot() == pytest.approx(
        4 * (4 * 32 * 32 * 4 + 3 * 3 * 128 * 4) * share)


@pytest.mark.parametrize("broken", ["delta_correction_dropped",
                                    "decay_a_scalar_a_head"])
def test_the_check_sees_a_break(pallas_on_cpu, monkeypatch, broken):
    from flexflow_tpu.serve import hybrid_ops

    kda = hybrid_ops.KimiDeltaAttention
    if broken == "decay_a_scalar_a_head":
        def scalar(sound):
            return lambda self, q, k, v, g, beta, *rest: sound(
                self, q, k, v, jnp.broadcast_to(
                    jnp.mean(g, -1, keepdims=True), g.shape), beta, *rest)

        for name in ("_step", "_chunked"):
            monkeypatch.setattr(kda, name, scalar(getattr(kda, name)))
    else:
        # S' + beta k v^T, plain gated linear attention, row by row in the
        # place of both forms
        def plain(self, q, k, v, g, beta, kda_, seg, *ctx):
            def row(carry, r):
                s, kda_ = carry
                q_r, k_r, v_r, g_r, b_r, start, fresh, at, store = r
                own = jax.lax.dynamic_index_in_dim(kda_, at, keepdims=False)
                s = jnp.where(start, jnp.where(fresh, 0.0, own), s)
                s = s * jnp.exp(g_r)[..., None] \
                    + (b_r[..., None] * k_r)[..., None] * v_r[..., None, :]
                zero = jnp.int32(0)
                kda_ = jax.lax.dynamic_update_slice(
                    kda_, s[None], (store, zero, zero, zero))
                return (s, kda_), jnp.sum(s * q_r[..., None], axis=-2)

            (_, kda_), o = jax.lax.scan(
                row, (jnp.zeros(kda_.shape[1:], kda_.dtype), kda_),
                (q, k, v, g, beta, seg.start, seg.fresh, seg.rows,
                 seg.store))
            o = jnp.where(seg.live[:, None, None], o, 0.0)
            return (o, kda_, "plain") if ctx else (o, kda_)

        for name in ("_step", "_chunked"):
            monkeypatch.setattr(kda, name, plain)
    llm = run.build(TOY, DEP, jax.devices()[:1])
    key = run.seed_weights(llm, ref, TOY, 7, "float32")
    ok, _ = check.run_check(llm.im, ref, TOY, key, "float32", 7,
                            TOY["vocab_size"], DEP["correct"],
                            lambda m: None)
    assert not ok
