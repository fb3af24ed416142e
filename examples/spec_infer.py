"""Tree-based speculative decoding demo (reference: ``inference/spec_infer``).

Registers a small draft model (SSM) + a larger verifier (LLM), serves with
SpecInfer tree speculation, and cross-checks the output equals plain
incremental decoding (the reference's inference test gate).

    python examples/spec_infer.py --cpu 8 --width 2 --depth 3
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", type=int, default=0)
    ap.add_argument("--width", type=int, default=2)
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--max-new-tokens", type=int, default=24)
    args = ap.parse_args()
    if args.cpu:
        from flexflow_tpu.utils.platform import force_cpu

        force_cpu(args.cpu)
    else:  # persistent compile cache (not for virtual-device collectives)
        from flexflow_tpu.utils.platform import enable_compile_cache

        enable_compile_cache()
    import jax
    import numpy as np

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.parallel.mesh import make_mesh
    from flexflow_tpu.serve import (
        GenerationConfig,
        InferenceManager,
        RequestManager,
        ServeModelConfig,
        SpecInferManager,
        build_model,
    )

    vocab = 512
    llm_cfg = ServeModelConfig(
        model_type="llama", vocab_size=vocab, hidden_size=256,
        intermediate_size=768, num_hidden_layers=4,
        num_attention_heads=8, num_key_value_heads=4,
    )
    ssm_cfg = ServeModelConfig(
        model_type="llama", vocab_size=vocab, hidden_size=64,
        intermediate_size=192, num_hidden_layers=1,
        num_attention_heads=2, num_key_value_heads=2,
    )
    tree = 1 + args.width * args.depth
    max_requests, max_seq = 4, 256
    max_tokens = max_requests * tree

    def build(cfg, topk, seed):
        mesh = make_mesh({"tp": 1}, jax.devices()[:1])
        ff = FFModel(FFConfig(), mesh=mesh)
        logits = build_model(ff, cfg, max_tokens)
        im = InferenceManager(
            ff, max_requests=max_requests, max_tokens_per_batch=max_tokens,
            max_seq_len=max_seq, max_spec_tokens=tree, topk=topk,
            outputs=logits,
        )
        im.init_operators_inference(rng=jax.random.PRNGKey(seed))
        return im

    llm = build(llm_cfg, 0, 0)
    ssm = build(ssm_cfg, args.width, 1)

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, vocab, size=n).tolist() for n in (5, 11, 3, 17)]

    sm = SpecInferManager(
        llm, ssm, GenerationConfig(max_new_tokens=args.max_new_tokens),
        width=args.width, depth=args.depth,
    )
    t0 = time.perf_counter()
    spec_out = sm.generate(prompts)
    dt = time.perf_counter() - t0
    print(
        f"spec_infer: {sm.tokens_decoded} tokens, {sm.llm_steps} LLM passes, "
        f"{sm.macro_steps} macro steps, {dt:.2f}s "
        f"({sm.tokens_decoded / max(sm.llm_steps, 1):.2f} tokens/LLM-pass)"
    )

    llm.reset()
    rm = RequestManager(llm, GenerationConfig(max_new_tokens=args.max_new_tokens))
    incr_out = rm.generate(prompts)
    print(f"incr baseline: {rm.tokens_decoded} tokens in {rm.steps} steps")
    assert spec_out == incr_out, "speculative output != incremental output"
    print("OK: speculative output == incremental output")

    # ---- on-device macro-step scan (the production TPU path) ----------
    from flexflow_tpu.serve.batch_config import BatchConfig
    from flexflow_tpu.serve.spec_scan import SpecDecodeScan

    llm2, ssm2 = build(llm_cfg, 0, 0), build(ssm_cfg, args.width, 1)

    def prefill(im):
        toks, reqi, pos = [], [], []
        for r, p in enumerate(prompts):
            toks += p
            reqi += [r] * len(p)
            pos += list(range(len(p)))
        res = im.step(BatchConfig.build(
            toks, reqi, pos, [len(p) for p in prompts],
            max_tokens=max(len(toks), im.max_tokens),
            max_requests=max_requests,
        ))
        ids, out, at = np.asarray(res.token_ids), [], 0
        for p in prompts:
            at += len(p)
            out.append(int(ids[at - 1]))
        return out

    firsts = prefill(llm2)
    prefill(ssm2)
    sc = SpecDecodeScan(llm2, ssm2, width=args.width, depth=args.depth)
    carry = sc.init_carry(firsts, [len(p) for p in prompts],
                          [len(p) for p in prompts], [False] * len(prompts))
    t0 = time.perf_counter()
    n_macro = args.max_new_tokens  # worst case 1 token/macro
    emitted, _ = sc.run(carry, n_macro=n_macro)
    em = np.asarray(emitted)
    dt = time.perf_counter() - t0
    scan_out = []
    for r, p in enumerate(prompts):
        seq = [firsts[r]] + [int(t) for t in em[:, r].reshape(-1) if t >= 0]
        scan_out.append(seq[: args.max_new_tokens])
    assert scan_out == incr_out, "scan output != incremental output"
    print(f"OK: on-device spec scan matches too ({n_macro} macro steps, "
          f"one host sync, {dt:.2f}s incl. compile)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
