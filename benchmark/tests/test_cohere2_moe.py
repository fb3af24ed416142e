"""The files the ``command-a-plus-d4-e16`` configuration brought: its
reference's tables against the published sizes and the program's tree, the
cost functions against a hand count, its cell against the headroom rule and
the hand count of a step, the metric files' arguments, and ``check.run_check``
and ``ringwrap.run_ringwrap`` at toy widths (CPU; Pallas interpreted;
float32: in bfloat16 a toy router FLIPS a choice in a few percent of its rows
against the float32 reference, so the toy's sound readings would spread as
wide as its faults — the real widths' readings are in the configuration's
file) — sound, and NOT correct with the rotary pairs half-split or the
shared experts summed."""

import json
import os

import jax
import numpy as np
import pytest

from conftest import ROOT

from benchmark import check, costs_cohere2_moe, headroom, ringwrap, run
from benchmark import routing
from benchmark import seeded_weights as sw
from benchmark.reference import cohere2_moe as ref

CELL = "command-a-plus-d4-e16.rag-decode"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TOY = {"model_type": "cohere2_moe", "vocab_size": 512, "hidden_size": 128,
       "num_hidden_layers": 4,
       "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
       "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 32,
       "sliding_window": 64, "rope_theta": 50000,
       "position_embedding_type": "rope_gptj", "use_parallel_block": True,
       "first_k_dense_replace": 0, "intermediate_size": 128,
       "num_experts": 4, "router_num_experts": 32, "expert_share_index": 0,
       "expert_share_count": 8, "num_experts_per_tok": 8,
       "num_shared_experts": 4,
       "shared_expert_combination_strategy": "average",
       "norm_topk_prob": True, "layer_norm_eps": 1e-5, "logit_scale": 1,
       "tie_word_embeddings": True, "init_std": 0.09,
       "torch_dtype": "float32"}
DEP = {"chips": 1, "tp": 1, "precision": "float32",
       "compile": {"max_requests": 3, "max_tokens_per_batch": 64,
                   "max_seq_len": 512, "dtype": "float32", "topk": 8},
       # the toy's own (CPU): sound seeds read 0.00 ulps and 0.0000 nats to
       # four decimals; a break reads logprob_rms 0.01 or more
       "correct": {"logit_rms_ulps": 0.02, "logit_max_ulps": 0.1,
                   "logprob_rms": 2e-4, "logprob_max": 2e-3,
                   "tail_logprob_rms": 2e-4, "token_gap_ulps": 0.1}}


def real_conf():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "command-a-plus-d4-e16.json")) as f:
        conf = json.load(f)
    return {k: v for k, v in conf.items() if k != "benchmark"}, \
        conf["benchmark"]


def test_the_file_is_the_catalogs_config_but_for_what_it_lists_as_reduced():
    hf, dep = real_conf()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "command-a-plus-05-2026")
    assert dep["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if hf.get(k, v) != v
               or k not in hf}
    assert differs == set(dep["reduced"]) == {
        "num_hidden_layers", "layer_types", "num_experts",
        "num_attention_heads", "num_key_value_heads", "vocab_size"}
    assert hf["layer_types"] == row["config"]["layer_types"][:4]
    # every width as published
    for key in ("hidden_size", "head_dim", "intermediate_size",
                "num_experts_per_tok", "sliding_window", "rope_theta",
                "num_shared_experts"):
        assert hf[key] == row["config"][key], key
    assert hf["router_num_experts"] == row["config"]["num_experts"] == 128
    assert hf["num_attention_heads"] // hf["num_key_value_heads"] == \
        row["num_attention_heads"] // row["num_key_value_heads"] == 16
    assert (hf["expert_share_index"], hf["expert_share_count"]) == (0, 8)
    assert hf["vocab_size"] * 8 == row["vocab_size"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == "command-a-plus-d4-e16"][0]
    assert sorted(entry["reduced"]) == sorted(dep["reduced"])
    assert entry["source"] == row["source_url"]


def test_the_tables_give_the_published_sizes_and_the_programs_tree():
    hf, dep = real_conf()
    assert ref.layer_kinds(hf) == ["sliding_attention"] * 3 + [
        "full_attention"]
    assert ref.attention_shape(hf) == (16, 1, 128)
    assert ref.held_experts(hf) == (0, 16)
    shape = headroom.model_shape(ref, hf)
    d = 4096
    expert = 3 * d * d
    assert shape["layer_params"] == (
        2 * d * 2048 + 2 * d * 128 + d * 128 + 16 * expert + 4 * expert)
    assert shape["layers"] == 4
    # no ``lm_head`` row: the tied embedding is the head
    assert shape["head_params"] == 32768 * d

    def tree(key):
        g = sw.draw_table(key, sw.GLOBAL_ID, ref.GLOBAL, hf, "bfloat16")
        layers = [sw.draw_table(key, i, ref.LAYER, hf, "bfloat16")
                  for i in range(4)]
        return ref.program_tree(hf, g, layers)

    shapes = jax.eval_shape(tree, sw.base_key(1))
    assert shapes["lm_head"]["kernel"].shape == (d, 32768)
    assert shapes["model.embed_tokens"]["weight"].shape == (32768, d)
    for i in range(4):
        p = f"model.layers.{i}"
        assert shapes[f"{p}.self_attn"]["qkv"].shape == (d, 1, 18, 128)
        assert shapes[f"{p}.self_attn"]["o_proj"].shape == (2048, d)
        gate = shapes[f"{p}.mlp.gate"]
        assert list(gate) == ["weight"]
        assert gate["weight"].shape == (d, 128)
        assert str(gate["weight"].dtype) == "float32"
        experts = shapes[f"{p}.mlp.experts"]
        assert {k: v.shape for k, v in experts.items()} == {
            "gate": (16, d, d), "up": (16, d, d), "down": (16, d, d)}
        assert shapes[f"{p}.mlp.shared_experts.gate_proj"][
            "kernel"].shape == (d, 16384)
        assert shapes[f"{p}.mlp.shared_experts.down_proj"][
            "kernel"].shape == (16384, d)
    total = sum(a.size for a in jax.tree.leaves(shapes))
    assert round(total / 1e6) == 4368          # the file's 4.368 B
    assert dep["compile"]["max_requests"] == 128
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


def test_the_cost_functions_by_hand():
    hf, _ = real_conf()
    matrix = 4096 * 4096
    ops, nbytes = costs_cohere2_moe.routed_decode_cost(16 * 4, 128 * 4, hf)
    assert nbytes == 64 * 3 * matrix * 2 + 512 * 2 * 4096 * 2
    assert ops == 512 * 6 * matrix
    assert 3 * matrix * 2 == 100663296           # an expert: 100.7 MB
    ops, nbytes = costs_cohere2_moe.mixed_attention_decode_cost(
        [900, 5000, 12000], hf)
    positions = (3 * (900 + 4096 + 4096) + 900 + 5000 + 12000)
    assert ops == 4 * 16 * 128 * positions
    assert nbytes == 512 * (positions + 4 * 3) + 4 * 3 * 2 * 16 * 128 * 2
    ops, nbytes = costs_cohere2_moe.window_prefill_cost(512, 8192, hf)
    assert ops == 4 * 16 * 128 * 512 * 4096
    assert nbytes == 512 * (4096 + 511) + 2 * 512 * 16 * 128 * 2
    ops, _ = costs_cohere2_moe.window_prefill_cost(4, 0, hf)
    assert ops == 4 * 16 * 128 * (1 + 2 + 3 + 4)


def test_the_cell_outlasts_its_window_and_no_request_can_end_in_it():
    """By hand, a step's least bytes at 128 rows early in a window: routed
    experts 6.44 GB (all 4 x 16 visited at 8 rows an expert), shared experts
    1.61 GB, attention weights 0.14 GB, the head 0.27 GB, K/V 1.2-1.5 GB of
    which the rings are bounded at 0.8 GB: ~12 ms — a 51 s window and its
    4 s rehearsal advance a row by ~4 600 steps, fewer than the shortest
    answer; two rounds last 2 x 6144 steps x 12 ms = 147 s at least, over
    1.5 x 55 s."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell and cell[0]["chips"] == 1
    mine = [h for name, _, h in headroom.closed_cells() if name == CELL]
    assert mine and all(h["ratio"] >= headroom.HEADROOM for h in mine)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "rag-decode.json")) as f:
        mix = json.load(f)
    hf, dep = real_conf()
    sched = headroom.traffic_gen.schedule(mix, bench["run_seconds"])
    slots = dep["compile"]["max_requests"]
    assert len(sched) == 256 and mix["round"] == slots == 128
    assert all(p + o <= dep["compile"]["max_seq_len"] == 18432
               for _, p, o in sched)
    assert min(p for _, p, _ in sched) > hf["sliding_window"]
    assert min(o for _, _, o in sched) >= 6144
    first = sched[:slots]
    rows = [p + o // 8 for _, p, o in first]     # contexts early in a window
    _, routed = costs_cohere2_moe.routed_decode_cost(4 * 16, 4 * 128, hf)
    _, kv = costs_cohere2_moe.mixed_attention_decode_cost(rows, hf)
    _, rings = costs_cohere2_moe.mixed_attention_decode_cost(
        [4096] * slots, dict(hf, layer_types=["sliding_attention"] * 3,
                             num_hidden_layers=3))
    d = 4096
    shared = 2 * 4 * 4 * 3 * d * d
    attn = 2 * 4 * (2 * d * 2048 + 2 * d * 128)
    head = 2 * 32768 * d
    assert 6.4e9 < routed < 6.5e9 and 1.6e9 < shared < 1.62e9
    assert 0.13e9 < attn < 0.15e9 and 0.26e9 < head < 0.28e9
    assert 1.2e9 < kv < 1.5e9 and 0.8e9 < rings < 0.83e9
    step = (routed + shared + attn + head + kv) / 819e9
    assert 0.0115 < step < 0.0125
    window = bench["run_seconds"] + mix["rehearse_s"]
    assert window / step < min(o for _, _, o in sched)
    assert 2 * 6144 * step > 1.5 * window
    # the first wave's prompts, fed before the window
    assert 1.0e6 < sum(p for _, p, _ in first) < 1.1e6


def test_the_metric_files_name_scopes_the_program_opens():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "ring_attn_dev_ms_per_tok.thr", "full_attn_dev_ms_per_tok.thr",
        "mixed_attn_roofline.thr", "moe_gated_experts_roofline.thr",
        "shared_experts_dev_ms_per_tok.thr", "expert_visit_e16_pct.thr"]
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
            assert module == "costs_cohere2_moe"
            assert hasattr(costs_cohere2_moe, fn)
        for field in spec["args"].get("num", []) + spec["args"].get("den",
                                                                    []):
            assert field in journal.FIELDS
    listed = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())]
    assert len(listed) == 14
    assert "ring_ctx_sum" in journal.FIELDS
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
    assert paths[("sliding_window_attention", "PrefillBatchConfig")] == \
        "prefill_attention"
    assert paths[("sliding_window_attention", "one_row_per_request")
                 if ("sliding_window_attention", "one_row_per_request")
                 in paths else ("sliding_window_attention", "BatchConfig")] \
        == "decode_attention"


def test_the_ring_drive_is_sound_past_the_window_and_the_rings_end(toy_llm):
    """``ringwrap.run_ringwrap`` at toy widths: prompts of 2 rings - 9 and
    window - 21, the scan crossing the ring's end and the window."""
    assert ringwrap.ring_slots(toy_llm.im) == 128
    lines = []
    key = run.seed_weights(toy_llm, ref, TOY, 7, "float32")
    ok, numbers = ringwrap.run_ringwrap(toy_llm.im, ref, TOY, key,
                                        "float32", 7, DEP["correct"],
                                        lines.append)
    assert ok, "\n".join(lines)
    # in float32 every number is tight, the readings too
    assert all(numbers[n] <= DEP["correct"][n] for n in numbers), numbers
    assert "prompts [247, 43] (window 64, ring 128)" in lines[-1]


@pytest.mark.parametrize("broken", ["half_split_rotary",
                                    "shared_experts_summed"])
def test_the_check_sees_a_break(pallas_on_cpu, monkeypatch, broken):
    hf = dict(TOY)
    if broken == "half_split_rotary":
        from flexflow_tpu.serve import hybrid_ops, ops

        monkeypatch.setattr(
            hybrid_ops, "apply_rope",
            lambda x, pos, theta, interleaved=False:
                ops.apply_rope(x, pos, theta))
    else:
        hf["shared_expert_combination_strategy"] = "sum"
    llm = run.build(hf, DEP, jax.devices()[:1])
    key = run.seed_weights(llm, ref, TOY, 7, "float32")
    ok, _ = check.run_check(llm.im, ref, TOY, key, "float32", 7,
                            TOY["vocab_size"], DEP["correct"],
                            lambda m: None)
    assert not ok


def test_the_toy_routers_choose_what_the_reference_does_in_float32(toy_llm):
    """``ringwrap.reference_choices`` beside ``routing.program_choices``: in
    float32 the toy deployment's four routers choose the reference's sets in
    every row."""
    key = run.seed_weights(toy_llm, ref, TOY, 7, "float32")
    ids = np.random.default_rng(5).integers(4, 512, size=150).tolist()
    program = routing.program_choices(toy_llm.im, ids)
    assert sorted(program) == [f"model.layers.{i}.mlp.gate"
                               for i in range(4)]
    reference = ringwrap.reference_choices(ref, TOY, key, "float32", ids)
    assert routing.agreement([program[n] for n in sorted(program)],
                             reference) == (1.0, 1.0)
