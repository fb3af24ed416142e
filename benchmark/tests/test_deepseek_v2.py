"""The files the ``deepseek-v2-lite-d5`` configuration brought: its file
against the catalog, its reference's tables against the published sizes and
the program's tree, the cost functions against a hand count, its cell against
the headroom rule and the hand count of a step, the metric files' arguments,
and ``check.run_check`` at toy widths (CPU; Pallas interpreted; float32: in
bfloat16 a toy router flips a choice in a few percent of its rows against the
float32 reference, so the toy's sound readings would spread as wide as its
faults — the real widths' readings are in the configuration's file) — sound,
and NOT correct with the rope part dropped from the score or the top-6
renormalised."""

import json
import os

import jax
import pytest

from conftest import ROOT

from benchmark import check, costs_deepseek_v2, headroom, latentctx, run
from benchmark import routing
from benchmark import seeded_weights as sw
from benchmark.reference import deepseek_v2 as ref

CELL = "deepseek-v2-lite-d5.doc-decode"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TOY = {"model_type": "deepseek_v2", "vocab_size": 512, "hidden_size": 128,
       "num_hidden_layers": 3, "num_attention_heads": 4,
       "num_key_value_heads": 4, "kv_lora_rank": 64, "q_lora_rank": None,
       "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
       "intermediate_size": 192, "moe_intermediate_size": 48,
       "n_routed_experts": 8, "n_shared_experts": 2,
       "num_experts_per_tok": 3, "first_k_dense_replace": 1,
       "moe_layer_freq": 1, "norm_topk_prob": False,
       "scoring_func": "softmax", "topk_method": "greedy", "n_group": 1,
       "topk_group": 1, "routed_scaling_factor": 1, "rms_norm_eps": 1e-6,
       "rope_theta": 10000,
       "rope_scaling": {"type": "yarn", "factor": 40,
                        "original_max_position_embeddings": 64,
                        "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                        "mscale_all_dim": 0.707},
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
                           "deepseek-v2-lite-d5.json")) as f:
        conf = json.load(f)
    return {k: v for k, v in conf.items() if k != "benchmark"}, \
        conf["benchmark"]


def test_the_file_is_the_catalogs_config_but_for_what_it_lists_as_reduced():
    hf, dep = real_conf()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "DeepSeek-V2-Lite")
    assert dep["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if k not in hf
               or hf[k] != v}
    assert differs == set(dep["reduced"]) == {"num_hidden_layers"}
    assert (hf["num_hidden_layers"], row["config"]["num_hidden_layers"]) == \
        (5, 27)
    # every width and count as published
    for key, value in dict(
            hidden_size=2048, num_attention_heads=16, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512,
            intermediate_size=10944, n_routed_experts=64,
            moe_intermediate_size=1408, num_experts_per_tok=6,
            n_shared_experts=2, vocab_size=102400, scoring_func="softmax",
            norm_topk_prob=False, first_k_dense_replace=1).items():
        assert hf[key] == row["config"][key] == value, key
    assert hf["rope_scaling"] == row["config"]["rope_scaling"]
    assert (hf["rope_scaling"]["factor"],
            hf["rope_scaling"]["original_max_position_embeddings"]) == \
        (40, 4096)
    assert set(hf) - set(row["config"]) == {"torch_dtype"}
    assert any(a.startswith("torch_dtype") for a in dep["assumed"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == "deepseek-v2-lite-d5"][0]
    assert entry["reduced"] == list(dep["reduced"]) == ["num_hidden_layers"]
    assert entry["source"] == row["source_url"]
    assert dep["controls"] == {"int8_weights": {"quantize_int8": True}}


def test_the_tables_give_the_published_sizes_and_the_programs_tree():
    hf, dep = real_conf()
    assert [ref.is_dense(hf, i) for i in range(5)] == [True] + [False] * 4
    # headroom's bytes a cached position and layer: the latent cache's own
    q_heads, kv_heads, hd = ref.attention_shape(hf)
    assert (q_heads, kv_heads, hd) == (16, 1, 288)
    assert 2 * kv_heads * hd * 2 == (512 + 64) * 2 == 1152
    d, expert = 2048, 3 * 2048 * 1408

    def tree(key):
        g = sw.draw_table(key, sw.GLOBAL_ID, ref.GLOBAL, hf, "bfloat16")
        layers = [sw.draw_table(key, i, ref.LAYER, hf, "bfloat16")
                  for i in range(5)]
        return ref.program_tree(hf, g, layers)

    shapes = jax.eval_shape(tree, sw.base_key(1))
    assert shapes["lm_head"]["kernel"].shape == (d, 102400)
    assert shapes["model.embed_tokens"]["weight"].shape == (102400, d)
    for i in range(5):
        p = f"model.layers.{i}"
        attn = {k: v.shape for k, v in shapes[f"{p}.self_attn"].items()}
        assert attn == {"q_proj": (d, 16, 192), "kv_a": (d, 576),
                        "kv_norm": (512,), "kv_b": (512, 16, 256),
                        "o_proj": (2048, d)}
        assert sum(v.size for k, v in shapes[f"{p}.self_attn"].items()
                   if k != "kv_norm") == 13762560         # 13.76 M
        if i == 0:
            assert shapes[f"{p}.mlp.gate_proj"]["kernel"].shape == (d, 10944)
            assert f"{p}.mlp.experts" not in shapes
            continue
        gate = shapes[f"{p}.mlp.gate"]
        assert list(gate) == ["weight"]
        assert gate["weight"].shape == (d, 64)
        assert str(gate["weight"].dtype) == "float32"
        assert {k: v.shape for k, v in shapes[f"{p}.mlp.experts"].items()} \
            == {"gate": (64, d, 1408), "up": (64, d, 1408),
                "down": (64, 1408, d)}
        assert shapes[f"{p}.mlp.shared_experts.gate_proj"][
            "kernel"].shape == (d, 2816)
        assert shapes[f"{p}.mlp.shared_experts.down_proj"][
            "kernel"].shape == (2816, d)
        assert f"{p}.mlp.gate_proj" not in shapes
    assert expert == 8650752                     # 17.30 MB in bf16
    total = sum(a.size for a in jax.tree.leaves(shapes))
    assert round(total / 1e6) == 2840            # the file's 2839.8 M
    # the cache beside it: 5 x 1152 B a position, 65 rows of 15360
    cache = 65 * 15360 * 5 * 1152
    assert round(cache / 1e9, 2) == 5.75
    assert 0.25 < (2 * total + cache) / 17.18e9 < 0.70
    assert dep["compile"]["max_requests"] == 64
    # the program builds the same tree (shapes only: nothing is allocated)
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.model import FFModel
    from flexflow_tpu.serve.models.base import (ServeModelConfig,
                                                build_model)

    ff = FFModel(FFConfig())
    build_model(ff, ServeModelConfig.from_hf_config(hf), 512)
    built = {n.name: {p.name: (tuple(p.spec.shape), str(p.spec.dtype))
                      for p in n.op.params()}
             for n in ff.graph.nodes if n.op.params()}
    assert built == {
        node: {p: (tuple(a.shape), str(a.dtype)) for p, a in ps.items()}
        for node, ps in shapes.items()}
    # headroom counts the union table in every layer (PERF.md section 7)
    shape = headroom.model_shape(ref, hf)
    assert shape["layer_params"] == (
        13762560 + 3 * d * 10944 + d * 64 + 64 * expert + 2 * expert)
    assert shape["head_params"] == 102400 * d


def test_the_cost_functions_by_hand():
    hf, _ = real_conf()
    ops, nbytes = costs_deepseek_v2.routed_decode_cost(64 * 4, 64 * 6 * 4, hf)
    matrix = 2048 * 1408
    assert nbytes == 256 * 3 * matrix * 2 + 1536 * 2 * 2048 * 2
    assert ops == 1536 * 6 * matrix
    ops, nbytes = costs_deepseek_v2.latent_decode_cost([900, 5000, 12000], hf)
    positions = 900 + 5000 + 12000
    assert ops == 5 * positions * 2 * 16 * (576 + 512)
    assert nbytes == 5 * (1152 * (positions + 3)
                          + 3 * 16 * (512 + 64 + 512) * 2)
    # a position costs what it holds, whatever a layout pads: 1 152 B
    one, two = (costs_deepseek_v2.latent_decode_cost([n], hf)[1]
                for n in (1000, 1001))
    assert two - one == 5 * 1152


def test_the_cell_outlasts_its_window_and_no_request_can_end_in_it():
    """By hand, a step's least bytes at 64 rows (contexts ~6k-12k): the
    latent cache ~3.4 GB, routed experts 4.42 GB (all 4 x 64 visited), the
    head 0.42 GB, shared experts 0.14 GB, the dense FFN 0.13 GB, attention
    weights 0.14 GB: ~10.5 ms — a 51 s window and its 4 s rehearsal advance
    a row by ~5 200 steps, fewer than the shortest answer."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell and cell[0]["chips"] == 1
    mine = [h for name, _, h in headroom.closed_cells() if name == CELL]
    assert mine and all(h["ratio"] >= headroom.HEADROOM for h in mine)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "doc-decode.json")) as f:
        mix = json.load(f)
    hf, dep = real_conf()
    sched = headroom.traffic_gen.schedule(mix, bench["run_seconds"])
    slots = dep["compile"]["max_requests"]
    assert len(sched) == 128 and mix["round"] == slots == 64
    assert all(p + o <= dep["compile"]["max_seq_len"] == 15360
               for _, p, o in sched)
    assert min(p for _, p, _ in sched) >= 6144 > \
        hf["rope_scaling"]["original_max_position_embeddings"]
    assert min(o for _, _, o in sched) >= 5376
    first = sched[:slots]
    rows = [p + o // 4 for _, p, o in first]     # contexts early in a window
    _, routed = costs_deepseek_v2.routed_decode_cost(4 * 64, 4 * 64 * 6, hf)
    _, cache = costs_deepseek_v2.latent_decode_cost(rows, hf)
    d = 2048
    shared = 2 * 4 * 2 * 3 * d * 1408
    dense = 2 * 3 * d * 10944
    attn = 2 * 5 * 13762560
    head = 2 * 102400 * d
    assert 4.4e9 < routed < 4.5e9 and 0.13e9 < shared < 0.15e9
    assert 0.13e9 < dense < 0.14e9 and 0.13e9 < attn < 0.14e9
    assert 0.41e9 < head < 0.43e9 and 3.0e9 < cache < 3.7e9
    step = (routed + shared + dense + attn + head + cache) / 819e9
    assert 0.0095 < step < 0.0115
    window = bench["run_seconds"] + mix["rehearse_s"]
    assert window / step < min(o for _, _, o in sched)
    # the first wave's prompts, fed before the window
    assert 0.45e6 < sum(p for _, p, _ in first) < 0.53e6


def test_the_metric_files_name_scopes_the_program_opens():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "mla_attn_dev_ms_per_tok.thr", "mla_decode_roofline.thr",
        "moe_e64_swiglu_roofline.thr"]
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
            assert module == "costs_deepseek_v2"
            assert hasattr(costs_deepseek_v2, fn)
    assert all(m["unit"] == "%" for m in mine if "roofline" in m["name"])
    listed = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())]
    assert len(listed) == 12
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
    assert paths[("latent_attention", "PrefillBatchConfig")] == \
        "xla_tile_absorbed"
    assert paths[("latent_attention", "BatchConfig")] == \
        "decode_attention_latent"
    assert toy_llm.im.kv.bytes_per_token() == pytest.approx(
        3 * (64 + 16) * 4 * 4 / 3)


def test_the_long_drive_is_sound_past_the_original_context(toy_llm):
    """``latentctx.run_latentctx`` at toy widths: a prompt as long as the
    cache leaves room for (seven original contexts) and one 21 short of the
    original context, which the scan crosses; the routers choose what the
    reference's do in float32."""
    lines = []
    key = run.seed_weights(toy_llm, ref, TOY, 7, "float32")
    ok, numbers = latentctx.run_latentctx(
        toy_llm.im, ref, TOY, key, "float32", 7, DEP["correct"],
        lines.append)
    assert ok, "\n".join(lines)
    assert all(numbers[n] <= DEP["correct"][n] for n in numbers), numbers
    assert "prompts [443, 43] (original context 64)" in lines[-1]
    ids = list(range(4, 4 + 100))
    got = routing.program_choices(toy_llm.im, ids)
    want = latentctx.reference_choices(ref, TOY, key, "float32", ids)
    assert len(got) == len(want) == 2
    equal, common = routing.agreement(
        [got[n] for n in sorted(got, key=lambda n: int(n.split(".")[2]))],
        want)
    assert equal == 1.0 and common == 1.0


@pytest.mark.parametrize("broken", ["rope_part_dropped_from_the_score",
                                    "top_3_renormalised"])
def test_the_check_sees_a_break(pallas_on_cpu, monkeypatch, broken):
    hf = dict(TOY)
    if broken == "rope_part_dropped_from_the_score":
        from flexflow_tpu.serve import hybrid_ops

        sound = hybrid_ops.LatentAttention._project

        def no_rope_part(self, x, params, pos):
            q_n, q_r, c, k_r = sound(self, x, params, pos)
            return q_n, q_r * 0, c, k_r

        monkeypatch.setattr(hybrid_ops.LatentAttention, "_project",
                            no_rope_part)
    else:
        hf["norm_topk_prob"] = True
    llm = run.build(hf, DEP, jax.devices()[:1])
    key = run.seed_weights(llm, ref, TOY, 7, "float32")
    ok, _ = check.run_check(llm.im, ref, TOY, key, "float32", 7,
                            TOY["vocab_size"], DEP["correct"],
                            lambda m: None)
    assert not ok
