"""How long a closed queue lasts at the chip's own roofline.

A closed-loop cell measures only while its queue outlasts the window: when
the queue runs dry the run ends without a result (run.py).  The queue
therefore has to hold more work than the chip could finish in the window
however good the program gets — not more than today's program finishes.
The rule (``tests/test_headroom.py`` holds every closed cell of
``BENCHMARK.json`` to it, so a shallow queue fails a CPU test, not a chip
run):

    least seconds to empty the queue  >=  HEADROOM x (run_seconds + rehearse_s)

The least seconds are costs.py's, request by request, on the published
widths (the reference's tables), the deployment's slots and the device's
published peaks:

  prefill   the prompt's rows through every layer's matrices, the LM head on
            the last row alone, causal attention over the prompt; the
            weights streamed once per wave of ``slots`` prompts
  decode    every answer token but the first is one row of a step that all
            ``slots`` share: the weights streamed once per step (a share of
            1/slots each), 2 x parameters FLOPs per row, and the live KV of
            its own prefix (``decode_attention_cost``)

each through ``roofline_seconds``.  Nothing an implementation adds (padding
rows, copies, host gaps, launch overheads) is in it, so no program on this
chip empties the queue sooner.

    python -m benchmark.headroom        # every closed cell's ratio
"""

import importlib
import json
import math
import os

from . import costs, traffic_gen

HEADROOM = 1.5
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _size(shape):
    return math.prod(shape)


def model_shape(ref, hf):
    """What the costs need of a configuration, from its reference's tables:
    matrix parameters per layer and of the LM head (a GLOBAL matrix named
    ``lm_head``, else the first GLOBAL matrix: the tied token embedding),
    layers, and the attention's heads."""
    per_layer = sum(_size(shape(hf)) for _, shape, kind in ref.LAYER
                    if kind == "matrix")
    matrices = [(name, _size(shape(hf))) for name, shape, kind in ref.GLOBAL
                if kind == "matrix"]
    head = dict(matrices).get("lm_head", matrices[0][1])
    q_heads, kv_heads, head_dim = ref.attention_shape(hf)
    return {"layers": ref.num_layers(hf), "layer_params": per_layer,
            "head_params": head, "q_heads": q_heads, "kv_heads": kv_heads,
            "head_dim": head_dim}


def request_least_seconds(prompt, answer, shape, slots, peak):
    """Least seconds of one chip for one request among ``slots`` in flight:
    ``(prefill, decode)``."""
    layers = shape["layers"]
    body = layers * shape["layer_params"]
    heads = (shape["q_heads"], shape["kv_heads"], shape["head_dim"])
    ops, nbytes = costs.dense_step_cost(body, prompt)
    head_ops, head_bytes = costs.dense_step_cost(shape["head_params"], 1)
    a_ops, a_bytes = costs.prefill_attention_cost(prompt, 0, *heads)
    prefill, _ = costs.roofline_seconds(
        ops + head_ops + layers * a_ops,
        (nbytes + head_bytes) / slots + layers * a_bytes, peak)
    rows = max(answer - 1, 0)
    ops, nbytes = costs.dense_step_cost(body + shape["head_params"], rows)
    a_ops, a_bytes = costs.decode_attention_cost(
        range(prompt + 1, prompt + 1 + rows), *heads)
    decode, _ = costs.roofline_seconds(
        ops + layers * a_ops,
        nbytes * rows / slots + layers * a_bytes, peak)
    return prefill, decode


def queue_headroom(mix, shape, slots, peak, run_seconds):
    """The queue of a closed-loop traffic file against the window: tokens in
    it, the least seconds to empty it, the roofline's tokens per second on
    this mix, and ``ratio`` = least seconds / (run_seconds + rehearse_s)."""
    reqs = traffic_gen.schedule(mix, run_seconds)
    tokens, prefill_s, decode_s = 0, 0.0, 0.0
    for _, prompt, answer in reqs:
        p, d = request_least_seconds(prompt, answer, shape, slots, peak)
        tokens += prompt + answer
        prefill_s, decode_s = prefill_s + p, decode_s + d
    least = prefill_s + decode_s
    window = run_seconds + float(mix.get("rehearse_s", 0))
    return {"requests": len(reqs), "tokens": tokens, "least_s": least,
            "prefill_s": prefill_s, "decode_s": decode_s,
            "roofline_tok_s": tokens / least, "ratio": least / window}


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def closed_cells(root=ROOT):
    """``(cell name, device kind, headroom)`` of every closed-loop cell of
    ``BENCHMARK.json`` on every device of ``peaks.json``, a cell on several
    chips against that many chips' peaks."""
    bench = _load(root, "BENCHMARK.json")
    peaks = {k: v for k, v in _load(HERE, "peaks.json").items()
             if not k.startswith("_")}
    files = {c["name"]: c["file"] for c in bench["configs"]}
    out = []
    for cell in bench["workloads"]:
        mix = _load(HERE, "traffic", cell["traffic"] + ".json")
        if mix["loop"] != "closed":
            continue
        conf = _load(root, files[cell["config"]])
        hf = {k: v for k, v in conf.items() if k != "benchmark"}
        ref = importlib.import_module(
            "benchmark.reference." + hf["model_type"])
        slots = conf["benchmark"]["compile"]["max_requests"]
        for kind, peak in peaks.items():
            peak = {k: v * cell["chips"] for k, v in peak.items()}
            out.append((cell["name"], kind, queue_headroom(
                mix, model_shape(ref, hf), slots, peak,
                bench["run_seconds"])))
    return out


if __name__ == "__main__":
    for name, kind, h in closed_cells():
        print(f"{name} on {kind}: {h['requests']} requests, {h['tokens']} "
              f"tokens, least {h['least_s']:.1f}s to empty (prefill "
              f"{h['prefill_s']:.1f}, decode {h['decode_s']:.1f}), roofline "
              f"{h['roofline_tok_s']:.0f} tokens/s, ratio {h['ratio']:.2f} "
              f"(needs {HEADROOM})")
