"""The files the ``mellum2-d8`` configuration brought: its file against the
catalog's config, its reference's tables against the published sizes and the
program's tree, the reference against a layer of each kind written out by
hand, both cost functions against a hand count, its cell against the headroom
rule BY HAND on the active parameters (headroom.py OVERCOUNTS this model:
every one of the 64 experts dense for every prompt row), the metric files'
arguments, the two new readers on made-up spans, and ``check.run_check`` at
toy widths (CPU; Pallas interpreted; float32) — sound, and NOT correct with
the ``attention_factor`` dropped or the top-k left un-normalised."""

import json
import math
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT

from benchmark import check, costs_mellum, headroom, run
from benchmark import seeded_weights as sw
from benchmark.reference import mellum as ref

CELL = "mellum2-d8.repo-context"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
FACTOR = 1.2772588722239782
YARN = {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 16,
        "original_max_position_embeddings": 32, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": FACTOR}
PLAIN = {"rope_type": "default", "rope_theta": 10000.0}
TOY = {"model_type": "mellum", "vocab_size": 512, "hidden_size": 128,
       "num_hidden_layers": 4,
       "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
       "mlp_layer_types": ["sparse"] * 4,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
       "sliding_window": 64, "intermediate_size": 256,
       "moe_intermediate_size": 64, "num_experts": 16,
       "num_experts_per_tok": 4, "norm_topk_prob": True,
       "rms_norm_eps": 1e-6, "attention_bias": False,
       "tie_word_embeddings": False,
       "rope_parameters": {"full_attention": YARN,
                           "sliding_attention": PLAIN},
       "init_std": 0.09, "torch_dtype": "float32"}
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
                           "mellum2-d8.json")) as f:
        conf = json.load(f)
    return {k: v for k, v in conf.items() if k != "benchmark"}, \
        conf["benchmark"]


def test_the_file_is_the_catalogs_config_but_for_what_it_lists_as_reduced():
    hf, dep = real_conf()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    assert dep["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if hf.get(k, v) != v
               or k not in hf}
    assert differs == set(dep["reduced"]) == {
        "num_hidden_layers", "layer_types", "mlp_layer_types"}
    assert hf["layer_types"] == row["config"]["layer_types"][:8] == (
        ["sliding_attention"] * 3 + ["full_attention"]) * 2
    assert hf["mlp_layer_types"] == ["sparse"] * 8
    assert hf["rope_parameters"] == row["config"]["rope_parameters"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == "mellum2-d8"][0]
    assert sorted(entry["reduced"]) == sorted(dep["reduced"])
    assert entry["source"] == row["source_url"]
    assert dep["compile"]["max_seq_len"] % 512 == 0
    assert dep["compile"]["max_seq_len"] >= 8192 + 32


def test_the_tables_give_the_published_sizes_and_the_programs_tree():
    hf, dep = real_conf()
    assert ref.layer_kinds(hf).count(ref.SLIDING) == 6
    assert ref.attention_shape(hf) == (32, 4, 128)
    shape = headroom.model_shape(ref, hf)
    d = 2304
    attn, expert = d * 4096 + 2 * d * 512 + 4096 * d, 3 * d * 896
    # no dense layer in the list: its three matrices have no columns
    assert shape["layer_params"] == attn + d * 64 + 64 * expert == 417742848
    assert shape["layers"] == 8 and shape["head_params"] == d * 98304
    total = 8 * shape["layer_params"] + 2 * shape["head_params"]
    assert 7.58e9 < 2 * total < 7.60e9

    def tree(key):
        g = sw.draw_table(key, sw.GLOBAL_ID, ref.GLOBAL, hf, "bfloat16")
        layers = [sw.draw_table(key, i, ref.LAYER, hf, "bfloat16")
                  for i in range(8)]
        return ref.program_tree(hf, g, layers)

    shapes = jax.eval_shape(tree, sw.base_key(1))
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.core.interpreter import init_params
    from flexflow_tpu.model import FFModel
    from flexflow_tpu.serve.inference_manager import InferenceManager
    from flexflow_tpu.serve.models.base import (ServeModelConfig,
                                                build_model)

    from flexflow_tpu.parallel.mesh import make_mesh

    ff = FFModel(FFConfig(), mesh=make_mesh({"tp": 1}, jax.devices()[:1]))
    build_model(ff, ServeModelConfig.from_hf_config(hf),
                dep["compile"]["max_tokens_per_batch"])
    im = InferenceManager(ff, **{k: v for k, v in dep["compile"].items()
                                 if k != "dtype"})
    built = jax.eval_shape(lambda: init_params(
        im.model.graph, im.plan, jax.random.PRNGKey(0), dtype=None))
    as_pairs = lambda t: jax.tree.map(
        lambda a: (tuple(a.shape), str(a.dtype)), t)
    assert as_pairs(shapes) == as_pairs(built)
    assert shapes["model.layers.3.self_attn"]["qkv"].shape == \
        (d, 4, 10, 128)
    assert str(shapes["model.layers.0.mlp.gate"]["weight"].dtype) == \
        "float32"
    # per slot: two full caches and six rings of window + chunk
    state = {n.name: n.op.state_specs(16, 8704) for n in im.model.graph.nodes
             if getattr(n.op, "stateful", False)}
    assert state["model.layers.3.self_attn"]["k"][0] == (17, 4, 8704, 128)
    assert state["model.layers.0.self_attn"]["wk"][0] == (17, 4, 2048, 128)


def test_a_layer_of_each_kind_by_hand():
    """One sliding and one full layer of the toy written out row by row in
    numpy float64 — half-against-half rotary under each kind's set, the
    window, YaRN's blend and the stated factor, the softmax top-4
    renormalised — against ``ref.layer``."""
    hf = dict(TOY, num_hidden_layers=2,
              layer_types=["sliding_attention", "full_attention"],
              mlp_layer_types=["sparse"] * 2, sliding_window=5)
    key = sw.base_key(3)
    t, d, h, kv, hd = 12, 128, 4, 2, 32
    x = np.random.default_rng(0).standard_normal((1, t, d))
    rms = lambda v, g: v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-6) * g

    def turns(kind):
        f = 10000.0 ** (-np.arange(hd // 2) / (hd // 2))
        if kind == "sliding_attention":
            return f, 1.0
        corr = lambda r: hd * math.log(32 / (2 * math.pi * r)) / (
            2 * math.log(10000.0))
        low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)),
                                                      hd - 1)
        ramp = np.clip((np.arange(hd // 2) - low) / (high - low), 0, 1)
        return f * (1 - ramp) + f / 16 * ramp, FACTOR

    def rope(v, kind):                      # v [t, heads, hd]
        f, amp = turns(kind)
        ang = np.arange(t)[:, None] * f
        cos, sin = amp * np.cos(ang)[:, None], amp * np.sin(ang)[:, None]
        a, b = v[..., :hd // 2], v[..., hd // 2:]
        return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    for i, kind in enumerate(hf["layer_types"]):
        w = {n: np.asarray(a, np.float64) for n, a in sw.draw_table(
            key, i, ref.LAYER, hf, "float32").items()}
        n = rms(x[0], w["input_layernorm.weight"])
        q = rope((n @ w["self_attn.q_proj"]).reshape(t, h, hd), kind)
        k = rope((n @ w["self_attn.k_proj"]).reshape(t, kv, hd), kind)
        v = (n @ w["self_attn.v_proj"]).reshape(t, kv, hd)
        heads = np.zeros((t, h, hd))
        for row in range(t):
            lo = max(row - 5 + 1, 0) if kind == "sliding_attention" else 0
            for head in range(h):
                s = k[lo:row + 1, head // 2] @ q[row, head] / math.sqrt(hd)
                p = np.exp(s - s.max())
                heads[row, head] = (p / p.sum()) @ v[lo:row + 1, head // 2]
        y = x[0] + heads.reshape(t, h * hd) @ w["self_attn.o_proj"]
        m = rms(y, w["post_attention_layernorm.weight"])
        out = y.copy()
        for row in range(t):
            s = m[row] @ (w["mlp.gate.weight"] * ref.ROUTER_GAIN)
            p = np.exp(s - s.max())
            p /= p.sum()
            chosen = np.argsort(-p, kind="stable")[:4]
            for e in chosen:
                g = m[row] @ w["mlp.experts.gate_proj"][e]
                up = m[row] @ w["mlp.experts.up_proj"][e]
                out[row] += p[e] / p[chosen].sum() * (
                    (g / (1 + np.exp(-g)) * up)
                    @ w["mlp.experts.down_proj"][e])
        got = ref.layer(hf, sw.draw_table(key, i, ref.LAYER, hf, "float32"),
                        ref.Stream(jnp.asarray(x, jnp.float32),
                                   jnp.int32(i)))
        np.testing.assert_allclose(np.asarray(got.h[0]), out, atol=2e-4)
        assert int(got.layer) == i + 1


def test_the_cost_functions_by_hand():
    hf, _ = real_conf()
    ops, nbytes = costs_mellum.routed_prefill_cost(64, 2048 * 8, hf)
    assert ops == 16384 * 6 * 2304 * 896
    assert nbytes == 64 * 3 * 2304 * 896 * 2 + 16384 * 2 * 2304 * 2
    # one chunk of 2048 rows from position 4096: a window a row, six rings
    ops, nbytes = costs_mellum.window_prefill_cost(2048 * 1024, 2048, hf)
    assert ops == 6 * 4 * 32 * 128 * 2048 * 1024
    assert nbytes == 6 * 2048 * (2 * 2 * 4 * 128 * 2 + 2 * 32 * 128 * 2)
    # 64 experts' matrices a chunk and layer are 0.97 ms of stream; the
    # arithmetic of a chunk's pairs is 1.03 ms at 2048 rows (256 an expert:
    # the two meet there), 0.51 ms at this cell's 1024, 0.26 ms at 512
    sec = lambda o, b: (o / 197e12, b / 819e9)
    for rows, ratio in ((4096, 1.6), (2048, 0.89), (1024, 0.49), (512, 0.26)):
        c, m = sec(*costs_mellum.routed_prefill_cost(64, rows * 8, hf))
        assert c / m == pytest.approx(ratio, rel=0.05), (rows, c / m)


def test_the_cell_outlasts_its_window_by_hand():
    """headroom.py's ratio (9.0) rests on an overcount: all 64 experts for
    every prompt row.  By hand, on ACTIVE parameters: a prompt row costs
    2 x (21.23 M attention + 0.15 M router + 8 x 6.19 M experts) x 8 layers
    = 1.135 GFLOP in matrices, the rings 4 x 32 x 128 x <= 1024 x 6 and the
    full layers 4 x 32 x 128 x position x 2 more: the mean prompt (5632) is
    38 ms at 197 TFLOP/s before one decode step — 2304 requests last 88 s
    at the least, over 1.5 x 55 s."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell and cell[0]["chips"] == 1
    mine = [h for name, _, h in headroom.closed_cells() if name == CELL]
    assert mine and all(h["ratio"] >= headroom.HEADROOM for h in mine)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "repo-context.json")) as f:
        mix = json.load(f)
    hf, dep = real_conf()
    sched = headroom.traffic_gen.schedule(mix, bench["run_seconds"])
    assert len(sched) == mix["queue_depth"] == 3 * mix["round"] == 2304
    assert dep["compile"]["max_requests"] == 16 == mix["block"]
    assert all(p + o <= dep["compile"]["max_seq_len"] for _, p, o in sched)
    assert min(p for _, p, _ in sched) >= 3 * hf["sliding_window"]
    active = 21233664 + 147456 + 8 * 6193152
    least = 0.0
    for _, p, _ in sched:
        matrices = 2 * active * 8 * p
        rings = 6 * 4 * 32 * 128 * sum(min(i + 1, 1024) for i in (0, p - 1)
                                       ) / 2 * p
        fulls = 2 * 4 * 32 * 128 * p * (p + 1) / 2
        least += (matrices + rings + fulls) / 197e12
    window = bench["run_seconds"] + mix["rehearse_s"]
    assert headroom.HEADROOM * window <= least < 110
    # and what the chip's arithmetic alone makes of the mix: ~150k tokens/s
    tokens = sum(p + o for _, p, o in sched)
    assert 115e3 < tokens / least < 160e3


def test_the_metric_files_name_scopes_the_program_opens():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    from flexflow_tpu.obs import journal
    from flexflow_tpu.serve import hybrid_ops, ops, ssd_moe_ops

    mine = [m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in mine) == sorted([
        "moe_prefill_dev_ms_per_ktok.thr", "moe_prefill_e64_roofline.thr",
        "prefill_rows_per_expert.thr",
        "ring_prefill_attn_dev_ms_per_ktok.thr",
        "full_prefill_attn_dev_ms_per_ktok.thr",
        "window_prefill_roofline.thr"])
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
            assert module == "costs_mellum" and hasattr(costs_mellum, fn)
        for field in spec["args"].get("num", []) + spec["args"].get("den",
                                                                    []):
            assert field in journal.FIELDS
    assert "prompt_ring_ctx_sum" in journal.FIELDS
    reports = [m["name"] for m in bench["end_to_end"]
               if CELL in m.get("workloads", (CELL,))]
    assert reports == ["total_tok_s", "setup_s"]


def _fake_trace(monkeypatch, reader, spans, ops):
    xs = reader.xs

    class Trace:
        def device_planes(self):
            return [0]

    monkeypatch.setattr(xs, "for_run", lambda ctx: Trace())
    monkeypatch.setattr(xs, "log_run", lambda trace, ctx: None)
    monkeypatch.setattr(xs, "has_node_scopes", lambda trace: True)
    monkeypatch.setattr(xs, "program_spans", lambda trace: spans)
    monkeypatch.setattr(xs, "ops_in_programs", lambda trace, p, c: ops)


def test_the_new_readers_on_made_up_spans(monkeypatch):
    """Two prefill scans of 4 chunks x 2048 rows and a flat step of 100
    prompt rows; 8 layers' MoEExperts and 6 layers' ring attention ops of
    known durations: the readers divide what they should by what they
    should, and read nothing from a program without the counters."""
    per_ktok = run.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "scope_ms_per_ktok_fed.py"))
    roof = run.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "span_cost_roofline_pct.py"))
    xs = per_ktok.xs
    fed = 2 * 8192 + 100
    ring_sum = 2 * 8192 * 1024 + 100 * 50
    spans = [xs.Span("prefill_scan_dispatch", 0, 1, {
        "prompt_tokens": 8192, "prompt_ring_ctx_sum": 8192 * 1024})] * 2 + [
        xs.Span("step_dispatch", 0, 1, {"prompt_tokens": 100,
                                        "prompt_ring_ctx_sum": 100 * 50}),
        xs.Span("prefill_scan_dispatch", 0, 1, {"pad": 1}),
        xs.Span("commit", 0, 1, {
            "prefill_experts_visited": 64 * 8 * 9,
            "prefill_expert_pairs": fed * 8 * 8,
            "prefill_expert_chunks": 8 * 9})]
    moe = "jit(_prefill_scan_impl)/while/body/MoEExperts.layers.%d.experts"
    ring = ("jit(_prefill_scan_impl)/while/body/SlidingWindowAttention."
            "layers.%d.self_attn/attend")
    ops = [xs.Op("%gmm.1 = f32[8]", t, 3_000_000, moe % layer)
           for layer in range(8) for t in range(9)]
    ops += [xs.Op("%prefill_attention.1", t, 1_000_000, ring % layer)
            for layer in (0, 1, 2, 4, 5, 6) for t in range(9)]
    ops += [xs.Op("%fusion.2", 0, 7_000_000,
                  (ring % 0).replace("attend", "qkv_proj"))]
    hf, _ = real_conf()
    logged = []
    ctx = {"hf": hf, "log": logged.append, "clock": types.SimpleNamespace(),
           "peak": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}}
    programs = ["_prefill_scan_impl", "_step_impl"]
    launches = ["prefill_scan_dispatch", "step_dispatch"]
    for reader in (per_ktok, roof):
        _fake_trace(monkeypatch, reader, spans, ops)
    got = per_ktok.read(ctx, programs, launches, nodes=["MoEExperts"])
    assert got == pytest.approx(8 * 9 * 3.0 / (fed / 1e3))
    got = per_ktok.read(ctx, programs, launches,
                        nodes=["SlidingWindowAttention"])
    assert got == pytest.approx((6 * 9 * 1.0 + 7.0) / (fed / 1e3))
    got = roof.read(ctx, ["MoEExperts"], "costs_mellum:routed_prefill_cost",
                    programs, ["commit"],
                    ["prefill_experts_visited", "prefill_expert_pairs"])
    flops, nbytes = costs_mellum.routed_prefill_cost(64 * 72, fed * 64, hf)
    assert nbytes / 819e9 > flops / 197e12     # 227 rows an expert
    assert got == pytest.approx(100 * nbytes / 819e9 / (72 * 0.003))
    assert 0 < got < 100 and "memory-bound" in logged[-1]
    got = roof.read(ctx, ["SlidingWindowAttention"],
                    "costs_mellum:window_prefill_cost", programs, launches,
                    ["prompt_ring_ctx_sum", "prompt_tokens"],
                    stages=["attend", "kv_write"])
    flops, _ = costs_mellum.window_prefill_cost(ring_sum, fed, hf)
    assert got == pytest.approx(100 * flops / 197e12 / (54 * 0.001))
    assert 0 < got < 100
    # a program without the counters (the parent's): nothing, and no raise
    bare = [xs.Span("prefill_scan_dispatch", 0, 1, {"prompt_tokens": 8192}),
            xs.Span("commit", 0, 1, {"prefill_tokens": 3})]
    for reader in (per_ktok, roof):
        _fake_trace(monkeypatch, reader, bare, ops)
    assert roof.read(ctx, ["MoEExperts"], "costs_mellum:routed_prefill_cost",
                     programs, ["commit"], ["prefill_experts_visited",
                                            "prefill_expert_pairs"]) is None
    assert roof.read(ctx, ["SlidingWindowAttention"],
                     "costs_mellum:window_prefill_cost", programs, launches,
                     ["prompt_ring_ctx_sum", "prompt_tokens"]) is None
    assert roof.read(ctx, ["MoEExperts"], "costs_nowhere:f", programs,
                     ["commit"], ["prefill_experts_visited"]) is None
    _fake_trace(monkeypatch, per_ktok, [xs.Span("commit", 0, 1, {})], ops)
    assert per_ktok.read(ctx, programs, launches,
                         nodes=["MoEExperts"]) is None


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
    assert paths[("prefill_operands", "inc_multihead_self_attention")] == \
        "float32"
    # sequence A (437 positions) is past the ring (256 slots) and the YaRN'd
    # original context (32); the joiner is fed flat in two pieces
    assert "contexts up to 437" in lines[-1]


@pytest.mark.parametrize("broken", ["attention_factor_dropped",
                                    "top_k_not_renormalised"])
def test_the_check_sees_a_break(pallas_on_cpu, broken):
    hf = dict(TOY)
    if broken == "attention_factor_dropped":
        hf["rope_parameters"] = {"full_attention": dict(
            YARN, attention_factor=1.0), "sliding_attention": PLAIN}
    else:
        hf["norm_topk_prob"] = False
    llm = run.build(hf, DEP, jax.devices()[:1])
    key = run.seed_weights(llm, ref, TOY, 7, "float32")
    ok, _ = check.run_check(llm.im, ref, TOY, key, "float32", 7,
                            TOY["vocab_size"], DEP["correct"],
                            lambda m: None)
    assert not ok
