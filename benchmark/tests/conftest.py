"""``pytest benchmark/tests`` — the benchmark's own tests (not tier-1).

They run on the CPU at toy widths; the chip is never needed and never
touched.  The kernels run in Pallas interpret mode (``use_pallas=True``
forced through the InferenceManager's own argument, in the test, not
through an option of the harness).
"""

import functools
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

COMPILE = {"max_requests": 4, "max_tokens_per_batch": 64, "max_seq_len": 256,
           "dtype": "bfloat16", "topk": 8}
# the toy's own limits, set as the real ones are (8 sound seeds, 3 of each
# control, both toys; ``init_std`` 0.1 gives the toys the query and key
# magnitudes of the real widths): sound runs read logit_rms_ulps 0.44-0.60,
# logprob_rms 0.0081-0.0111, tail_logprob_rms 0.0076-0.0102, token_gap_ulps
# 0-1.4; the int8-weight control logit_rms_ulps 0.93 and logprob_rms 0.0185
# or more, the int8-KV control 2.4 and 0.041 or more
LIMITS = {"logit_rms_ulps": 0.75, "logit_max_ulps": 8.0,
          "logprob_rms": 0.0145, "logprob_max": 0.15,
          "tail_logprob_rms": 0.02, "token_gap_ulps": 8.0}
TOY = {
    "toy-opt": {
        "model_type": "opt", "do_layer_norm_before": True, "ffn_dim": 256,
        "hidden_size": 128, "init_std": 0.1, "max_position_embeddings": 256,
        "num_attention_heads": 2, "num_hidden_layers": 2,
        "torch_dtype": "bfloat16", "vocab_size": 512,
        "word_embed_proj_dim": 128},
    "toy-bigcode": {
        "model_type": "gpt_bigcode", "multi_query": True,
        "layer_norm_epsilon": 1e-05, "n_embd": 128, "n_head": 2,
        "n_inner": 512, "n_layer": 2, "n_positions": 256,
        "torch_dtype": "bfloat16", "vocab_size": 512,
        "initializer_range": 0.1},
}


def toy_conf(name):
    return dict(TOY[name], benchmark={
        "source": "toy", "chips": 1, "tp": 1, "compile": dict(COMPILE),
        "precision": "bfloat16", "correct": dict(LIMITS),
        "controls": {"int8_weights": {"quantize_int8": True},
                     "int8_kv": {"compile": {"kv_dtype": "int8"}}}})


@pytest.fixture(scope="session")
def pallas_on_cpu():
    """``LLM.compile`` takes ``use_pallas="auto"`` (on for a TPU); the toy
    deployments here must run the kernels, interpreted."""
    import flexflow_tpu.serve.api as api

    real = api.InferenceManager
    api.InferenceManager = functools.partial(real, use_pallas=True)
    yield
    api.InferenceManager = real


@pytest.fixture()
def toy_root(tmp_path):
    """A root that holds a toy BENCHMARK.json, configurations and traffic
    files: the real file's metrics, cut to two toy cells."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    os.makedirs(tmp_path / "configs")
    os.makedirs(tmp_path / "traffic")
    bench["configs"], bench["workloads"] = [], []
    for name in TOY:
        with open(tmp_path / "configs" / f"{name}.json", "w") as f:
            json.dump(toy_conf(name), f)
        bench["configs"].append({"name": name, "source": "toy",
                                 "file": f"configs/{name}.json",
                                 "reduced": [], "why": "toy"})
    mixes = {
        "toy-closed": {
            "loop": "closed", "queue_depth": 400, "block": 4,
            "prompt_len": {"dist": "uniform", "lo": 8, "hi": 120},
            "output_len": {"dist": "uniform", "lo": 4, "hi": 40},
            "reports": ["total_tok_s"], "rehearse_s": 0.5},
        "toy-open": {
            "loop": "open", "block": 4,
            "arrivals": {"process": "poisson", "rate_per_s": 6.0},
            "prompt_len": {"dist": "lognormal", "median": 40, "sigma": 0.8,
                           "lo": 8, "hi": 150},
            "output_len": {"dist": "lognormal", "median": 6, "sigma": 1.0,
                           "lo": 1, "hi": 40},
            "reports": ["ttft_p90_ms", "tpot_pooled_ms"], "drain_s": 20,
            "rehearse_s": 0.5},
    }
    for name, mix in mixes.items():
        with open(tmp_path / "traffic" / f"{name}.json", "w") as f:
            json.dump(mix, f)
    cells = {"toy-opt.toy-closed": ("toy-opt", "toy-closed"),
             "toy-bigcode.toy-open": ("toy-bigcode", "toy-open")}
    for cell, (config, traffic) in cells.items():
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": traffic, "chips": 1,
                                   "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return str(tmp_path)
