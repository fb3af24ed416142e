"""The files the ``nemotron-3-nano-d9-e64`` configuration brought: its
reference's tables against the published sizes and the program's tree, both
cost functions against a hand count, its cell against the headroom rule and
the hand count of a step (headroom.py OVERCOUNTS this model: the union table
x 9, every held expert dense for every row), the metric files' arguments, the
new reader on made-up commits, and ``check.run_check`` at toy widths (CPU;
Pallas interpreted; float32: in bfloat16 a toy router of 8 experts FLIPS a
choice in a few percent of its rows against the float32 reference and a flip
moves a third of the routed output, so the toy's sound readings spread as
wide as its faults — the real widths' readings are in the configuration's
file) — sound, and NOT correct with the SSD state dropped in the decode
scan."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from conftest import ROOT

from benchmark import check, costs_nemotron, headroom, run
from benchmark import seeded_weights as sw
from benchmark.reference import nemotron_h as ref

CELL = "nemotron-3-nano-d9-e64.agent-decode"
TOY = {"model_type": "nemotron_h", "vocab_size": 512, "hidden_size": 128,
       "num_hidden_layers": 5, "hybrid_override_pattern": "MEM*E",
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
       "mamba_num_heads": 8, "mamba_head_dim": 16, "n_groups": 2,
       "ssm_state_size": 16, "conv_kernel": 4, "n_routed_experts": 4,
       "router_num_experts": 8, "expert_share_index": 0,
       "num_experts_per_tok": 3, "n_shared_experts": 1,
       "moe_intermediate_size": 128,
       "moe_shared_expert_intermediate_size": 128,
       "routed_scaling_factor": 2.5, "norm_topk_prob": True,
       "layer_norm_epsilon": 1e-5, "intermediate_size": 128,
       "init_std": 0.09, "torch_dtype": "float32"}
DEP = {"chips": 1, "tp": 1, "precision": "float32",
       "compile": {"max_requests": 3, "max_tokens_per_batch": 64,
                   "max_seq_len": 256, "dtype": "float32", "topk": 8},
       # the toy's own (CPU): sound seeds read 0.00 ulps and 0.0000 nats to
       # four decimals; the state dropped reads logprob_rms 0.03 or more
       "correct": {"logit_rms_ulps": 0.02, "logit_max_ulps": 0.1,
                   "logprob_rms": 2e-4, "logprob_max": 2e-3,
                   "tail_logprob_rms": 2e-4, "token_gap_ulps": 0.1}}


def real_conf():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron-3-nano-d9-e64.json")) as f:
        conf = json.load(f)
    return {k: v for k, v in conf.items() if k != "benchmark"}, \
        conf["benchmark"]


def test_the_tables_give_the_published_sizes_and_the_programs_tree():
    hf, dep = real_conf()
    assert ref.layer_kinds(hf) == list("MEMEM*EME")
    assert ref.attention_shape(hf) == (32, 2, 128)
    assert ref.held_experts(hf) == (0, 64)
    shape = headroom.model_shape(ref, hf)
    d = 2688
    # the UNION of the four kinds' matrices, in every layer: 0.73 B a layer
    # where the model has 39 M (M), 23 M (*) or 659 M (E): PERF.md section 7
    assert shape["layer_params"] == (
        d * 10304 + 4 * 6144 + 4096 * d + d * 4096 + 2 * d * 256 + 4096 * d
        + d * 128 + 64 * 2 * d * 1856 + 2 * d * 3712 + 2 * d * 1856)
    assert shape["layers"] == 9 and shape["head_params"] == d * 65536

    def tree(key):
        g = sw.draw_table(key, sw.GLOBAL_ID, ref.GLOBAL, hf, "bfloat16")
        layers = [sw.draw_table(key, i, ref.LAYER, hf, "bfloat16")
                  for i in range(9)]
        return ref.program_tree(hf, g, layers)

    shapes = jax.eval_shape(tree, sw.base_key(1))
    assert shapes["lm_head"]["kernel"].shape == (d, 65536)
    assert shapes["backbone.layers.0.mixer.in_proj"]["kernel"].shape == \
        (d, 10304)
    assert shapes["backbone.layers.0.mixer.conv1d"]["weight"].shape == \
        (4, 6144)
    scan = shapes["backbone.layers.0.mixer.scan"]
    assert {k: (v.shape, str(v.dtype)) for k, v in scan.items()} == {
        k: ((64,), "float32") for k in ("A_log", "D", "dt_bias")}
    assert shapes["backbone.layers.5.mixer"]["qkv"].shape == (d, 2, 18, 128)
    gate = shapes["backbone.layers.1.mixer.gate"]
    assert gate["weight"].shape == (d, 128)
    assert str(gate["weight"].dtype) == "float32"
    experts = shapes["backbone.layers.1.mixer.experts"]
    assert experts["up"].shape == (64, d, 1856)
    assert experts["down"].shape == (64, 1856, d)
    total = sum(a.size for a in jax.tree.leaves(shapes))
    assert round(total / 1e6) == 3166
    assert dep["compile"]["max_requests"] == 256


def test_the_cost_functions_by_hand():
    hf, _ = real_conf()
    ops, nbytes = costs_nemotron.routed_decode_cost(64 * 4, 768 * 4, hf)
    matrix = 2688 * 1856
    assert nbytes == 256 * 2 * matrix * 2 + 3072 * 2 * 2688 * 2
    assert ops == 3072 * 4 * matrix
    ops, nbytes = costs_nemotron.mamba2_decode_cost([900, 4000, 7], hf)
    state = 64 * 64 * 128
    assert state * 4 == 2097152
    per_row = 2 * 2097152 + (2 * 3 * 6144 + 2 * 6144 + 64 + 4096) * 2
    assert nbytes == 4 * 3 * per_row and ops == 4 * 3 * 5 * state


def test_the_cell_outlasts_its_window_and_no_request_can_end_in_it():
    """headroom.py's ratio (5.66) rests on an overcount.  By hand, on ACTIVE
    parameters: a step streams 6.0 GB of weights (every held expert is
    visited at 12 rows an expert), reads and writes 4.3 GB of state and
    reads 0.3-1.3 GB of K/V: ~14 ms at 256 rows — a 51 s window and its 4 s
    rehearsal advance a row by ~3 900 steps, fewer than the shortest answer;
    two rounds last 2 x 4096 steps x 14 ms = 115 s at least, over 1.5 x
    55 s."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell and cell[0]["chips"] == 1
    mine = [h for name, _, h in headroom.closed_cells() if name == CELL]
    assert mine and all(h["ratio"] >= headroom.HEADROOM for h in mine)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "agent-decode.json")) as f:
        mix = json.load(f)
    hf, dep = real_conf()
    sched = headroom.traffic_gen.schedule(mix, bench["run_seconds"])
    slots = dep["compile"]["max_requests"]
    assert len(sched) == 512 and mix["round"] == slots == 256
    assert all(p + o <= 7680 < dep["compile"]["max_seq_len"]
               for _, p, o in sched)
    assert min(o for _, _, o in sched) >= 4096
    first = sched[:slots]
    rows = [p + o // 4 for _, p, o in first]     # contexts early in a window
    _, routed = costs_nemotron.routed_decode_cost(4 * 64, 4 * 768, hf)
    _, ssd = costs_nemotron.mamba2_decode_cost(rows, hf)
    kv = sum(rows) * 1024
    dense = 2 * (4 * (2688 * 10304 + 4096 * 2688) + 2688 * 4608
                 + 4096 * 2688 + 4 * (2 * 2688 * 3712) + 2688 * 65536)
    assert 5.0e9 < routed < 5.2e9 and 0.85e9 < dense < 0.9e9
    assert 4.3e9 < ssd < 4.5e9 and 0.3e9 < kv < 1.0e9
    step = (routed + dense + ssd + kv) / 819e9
    assert 0.0130 < step < 0.0145
    window = bench["run_seconds"] + mix["rehearse_s"]
    assert window / step < min(o for _, _, o in sched)
    assert 2 * 4096 * step > 1.5 * window
    # the first wave's prompts, fed before the window
    assert 230e3 < sum(p for _, p, _ in first) < 300e3


def test_the_metric_files_name_scopes_the_program_opens():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "moe_dev_ms_per_tok.thr", "moe_route_dev_ms_per_tok.thr",
        "moe_experts_roofline.thr", "mamba2_dev_ms_per_tok.thr",
        "mamba2_scan_roofline.thr", "expert_visit_pct.thr"]
    from flexflow_tpu.obs import journal
    from flexflow_tpu.serve import hybrid_ops, ssd_moe_ops

    for m in mine:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert m["moves"] == "total_tok_s"
        for node in spec["args"].get("nodes", ()):
            assert hasattr(ssd_moe_ops, node) or hasattr(hybrid_ops, node)
        if "cost" in spec["args"]:
            module, _, fn = spec["args"]["cost"].partition(":")
            assert module == "costs_nemotron" and hasattr(costs_nemotron, fn)
        for field in spec["args"].get("num", []) + spec["args"].get("den",
                                                                    []):
            assert field in journal.FIELDS
    listed = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())]
    assert len(listed) == 12


def test_the_roofline_reader_scales_the_commits_counts_to_the_spans_rows(
        monkeypatch):
    """Three commits speak of 96 routed layer-steps and 3 x 256 scan tokens;
    the span's clock counts 640 decode rows (5 of each of 128 requests, one
    of them a request's first token, which is no row): the least work is
    that share of the commits' sums."""
    import types

    reader = run.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "moe_roofline_pct.py"))
    xs = reader.xs
    commit = lambda **a: xs.Span("commit", 0, 1, a)
    spans = [commit(experts_visited=64 * 32, expert_pairs=700 * 32,
                    expert_steps=32, scan_tokens=256)] * 3 \
        + [xs.Span("readback", 0, 1, {})]
    scope = "jit(_decode_scan_impl)/while/body/MoEExperts.layers.%d.experts"
    ops = [xs.Op(f"%{name} = f32[8]", t, 10_000_000, scope % layer)
           for layer in (1, 3) for t in range(40)
           for name in ("gmm.1", "gmm.2", "fusion.7")]
    ops += [xs.Op("%gmm.9", 0, 5, "jit(_decode_scan_impl)/Linear.lm_head")]

    class Trace:
        def device_planes(self):
            return [0]

    monkeypatch.setattr(xs, "for_run", lambda ctx: Trace())
    monkeypatch.setattr(xs, "program_spans", lambda trace: spans)
    monkeypatch.setattr(xs, "ops_in_programs", lambda trace, p, c: ops)
    before = {rid: (100, 7) for rid in range(127)}      # 127 has made none
    after = {rid: (100, 12) for rid in range(128)}
    after[127] = (100, 6)                               # 5 rows + its first
    hf, _ = real_conf()
    logged = []
    ctx = {"hf": hf, "log": logged.append,
           "clock": types.SimpleNamespace(trace_lens=(before, after)),
           "peak": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}}
    args = (["MoEExperts"], "costs_nemotron:routed_decode_cost",
            ["_decode_scan_impl"])
    got = reader.read(ctx, *args)
    share = 640 / 768
    _, nbytes = costs_nemotron.routed_decode_cost(
        64 * 96 * share, 700 * 96 * share, hf)
    measured = 2 * 40 * 3 * 0.01
    assert got == pytest.approx(100 * nbytes / 819e9 / measured)
    assert "640 decode rows" in logged[0]
    # a program without the counters, a clock without lengths: nothing read
    monkeypatch.setattr(xs, "program_spans",
                        lambda trace: [commit(scan_tokens=5)])
    assert reader.read(ctx, *args) is None
    ctx["clock"].trace_lens = None
    monkeypatch.setattr(xs, "program_spans", lambda trace: spans)
    assert reader.read(ctx, *args) is None


@pytest.fixture(scope="module")
def toy_llm(pallas_on_cpu):
    return run.build(TOY, DEP, jax.devices()[:1])


def test_the_check_is_sound_at_toy_widths(toy_llm):
    lines = []
    for seed in (7, 2 ** 31 + 11):
        key = run.seed_weights(toy_llm, ref, TOY, seed, "float32")
        ok, numbers = check.run_check(
            toy_llm.im, ref, TOY, key, "float32", seed, TOY["vocab_size"],
            DEP["correct"], lines.append)
        assert ok, "\n".join(lines)


def test_the_check_sees_the_state_dropped_in_the_decode_scan(
        pallas_on_cpu, monkeypatch):
    from flexflow_tpu.serve.ssd_moe_ops import Mamba2Scan

    sound = Mamba2Scan._slot_order
    monkeypatch.setattr(
        Mamba2Scan, "_slot_order",
        lambda self, la, dx, b, c, ssd, seg: sound(
            self, la, dx, b, c, jnp.zeros_like(ssd), seg))
    llm = run.build(TOY, DEP, jax.devices()[:1])
    key = run.seed_weights(llm, ref, TOY, 7, "float32")
    ok, _ = check.run_check(llm.im, ref, TOY, key, "float32", 7,
                            TOY["vocab_size"], DEP["correct"],
                            lambda m: None)
    assert not ok


def test_routing_agreement_counts_sets_and_the_toy_agrees_in_float32(
        toy_llm):
    """``benchmark/routing.py``: equal sets whatever their order, a row with
    one choice of three differing counts two thirds; and in float32 the toy
    deployment's routers choose what the reference's do, in every row."""
    import numpy as np

    from benchmark import routing

    got = [np.array([[1, 2, 3], [4, 5, 6], [0, 1, 2], [7, 6, 5]])]
    want = [np.array([[3, 2, 1], [4, 5, 7], [0, 1, 2], [5, 6, 7]])]
    equal, common = routing.agreement(got, want)
    assert equal == 0.75 and common == pytest.approx(11 / 12)
    key = run.seed_weights(toy_llm, ref, TOY, 7, "float32")
    ids = np.random.default_rng(5).integers(4, 512, size=150).tolist()
    program = routing.program_choices(toy_llm.im, ids)
    assert sorted(program) == ["backbone.layers.1.mixer.gate",
                               "backbone.layers.4.mixer.gate"]
    reference = routing.reference_choices(ref, TOY, key, "float32", ids)
    assert routing.agreement([program[n] for n in sorted(program)],
                             reference) == (1.0, 1.0)
