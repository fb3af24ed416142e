#!/usr/bin/env python3
"""Chip smoke: serve facebook/opt-6.7b widths on a TPU through the normal
entry point, and check the kernels against the plain path.

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --chips 4  # the cross-chip path only (tp=4)

The quickest proof that the system still starts on the chip.  One process,
no children (a chip belongs to one process).  It FAILS — non-zero exit, no
result line — unless JAX finds a TPU.  Widths are the published ones (HF
``config.json`` below) and are never cut; depth is the only cut on one chip
(the 32 layers are 13.3 GB of bf16 weights before any KV cache).  Weights
are random, from the library's fixed seed (``PRNGKey(0)`` in
``init_operators_inference``); prompts are token ids from ``SEED``.

One chip:
  1. ``LLM(cfg).compile(max_requests=8, max_tokens_per_batch=512,
     max_seq_len=2048, dtype="bfloat16").generate(8 prompts, 64 new tokens)``
     — RequestManager, tiled gated prefill scan, chained decode stretch,
     Pallas kernels.  Run twice: the first call pays the compiles (cold),
     the second is the serve time; both must give the same tokens.
  2. kernel vs gather: the same weights through ``use_pallas=True`` and
     ``use_pallas=False`` on the same BatchConfigs via ``im.step`` —
     ``logits_max`` / ``topk_logprobs`` within ``KERNEL_TOL_ULPS``.

``--chips 4`` (no other phase): tp=4 vs tp=1 on the depth-cut model, then
the FULL 32-layer model at tp=4 answering the same 8 requests, per-device
memory within 15 % of each other, collectives + kernel in the compiled text.

Last stdout line: ``{"ok": true, "device": {...}}``.  Numbers printed here
are one smoke run, not benchmark results.
"""

import argparse
import collections
import dataclasses
import gc
import json
import re
import sys
import time

# facebook/opt-6.7b, HF config.json (architecture fields)
OPT_6_7B = {
    "model_type": "opt",
    "hidden_size": 4096,
    "ffn_dim": 16384,
    "num_attention_heads": 32,
    "num_hidden_layers": 32,
    "vocab_size": 50272,
    "max_position_embeddings": 2048,
    "word_embed_proj_dim": 4096,
    "do_layer_norm_before": True,
}
# Depth on ONE chip: 12 of 32, the depth the benchmark's opt-6.7b-d12 cells
# run.  AOT memory_analysis of the step programs for a described v5e (PR 24
# rehearsal; nothing ran) read 17.3 GB of the 15.75 GB for the decode scan
# at 16 layers and 14.1 GB at 12 (5.7 GB weights + 3.6 GB KV + 4.8 GB
# temporaries) — but those temporaries were the scan's own doing: its
# 512-row KV write was an XLA scatter whose layout made the scan copy
# every cache (ops.DUS_MAX_TOKENS).  The scan runs on one row per slot now
# (InferenceManager._decode_scan_impl) and writes in place; the flat step
# and the prefill scan were not sized again at 16 layers, so the depth
# stands.
ONE_CHIP_LAYERS = 12
SEED = 0
# 8 prompts of mixed lengths; chosen so the 512-token prefill chunks number
# 16 (1+1+1+2+2+3+3+3) — ONE power-of-two prefill-scan segment, one compile
PROMPT_LENS = (64, 200, 500, 700, 900, 1100, 1300, 1500)
NEW_TOKENS = 64
SERVE = dict(max_requests=8, max_tokens_per_batch=512, max_seq_len=2048)
# Comparison deployments (kernel vs gather, tp=4 vs tp=1): the serve
# deployment with 128-token steps — the gather path materializes every
# token's whole cache row ([T, KV, S, D]; 2.4 GB of temporaries at T=128 by
# the AOT memory analysis), which a 512-token step cannot hold beside the
# weights.  128 tokens is still the full-width prefill tile, so the kernel
# plan (kv_chunk 16, 256-position blocks) is the one the serve phase runs.
COMPARE = dict(max_requests=8, max_tokens_per_batch=128, max_seq_len=2048)
COMPARE_PROMPTS = (384, 100)   # 3 full tiles; one padded tile
COMPARE_DECODE_STEPS = 4
TOPK = 8
# Tolerances, in bf16 ulps (eps = 2**-8, 8 mantissa bits) of the largest
# logit.  Compared on logit VALUES, not sampled ids: with random weights the
# argmax flips on rounding.  A kernel that reads a wrong block or mask, or a
# shard that is mis-ordered or not reduced, is off by the logit scale itself
# (256 ulps).
# kernel vs gather: both compute attention in f32 from the same bf16
# operands and round the result to bf16; they differ in summation order,
# which flips the last bf16 bit of a few activations per layer, and the
# flips travel down the residual stream (3.4 ulps on the v5e, PR 24 run).
KERNEL_TOL_ULPS = 8
# tp=4 vs tp=1: on top of that, each row-parallel matmul (attention output,
# fc2: two per layer) rounds its four partial sums to bf16 and adds them in
# bf16 in the all-reduce — up to 7 roundings where tp=1 has one, on EVERY
# element, not a few.
TP_TOL_ULPS = 32
BF16_EPS = 2.0 ** -8


def log(msg):
    print(msg, flush=True)


def require_tpu():
    """Device triple as JAX reports it; exit non-zero unless it is a TPU."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        sys.stderr.write(
            f"chip_smoke: needs a TPU, JAX found platform={d0.platform!r} "
            f"({d0.device_kind}); not falling back\n")
        sys.exit(2)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def model_config(layers):
    from flexflow_tpu.serve.models.base import ServeModelConfig

    cfg = ServeModelConfig.from_hf_config(OPT_6_7B)
    # no EOS: every request must run to the asked length
    return dataclasses.replace(cfg, num_hidden_layers=layers,
                               dtype="bfloat16", eos_token_id=None)


def make_prompts(lens, vocab, seed=SEED):
    import numpy as np

    rng = np.random.default_rng(seed)
    # ids >= 4: clear of OPT's special tokens
    return [rng.integers(4, vocab, size=n).tolist() for n in lens]


def memory(devices, key):
    """``key`` of ``memory_stats()`` for each device (0 where absent)."""
    return [int((d.memory_stats() or {}).get(key, 0)) for d in devices]


def log_memory(tag, devices):
    """Peak device bytes as the runtime reports them: live arrays
    (``peak_bytes_in_use``) and, separately, what compiled programs
    reserved for their temporaries (``peak_bytes_reserved``)."""
    log(f"{tag}: peak_bytes_in_use {memory(devices, 'peak_bytes_in_use')} "
        f"+ peak_bytes_reserved {memory(devices, 'peak_bytes_reserved')} "
        f"of bytes_limit {memory(devices, 'bytes_limit')[0]} per device")


def release(*ims):
    """Free params + KV caches NOW (the next phase needs the HBM)."""
    for im in ims:
        im.params = im.state = None
    gc.collect()


def step_text(im, prefill, compiled=False):
    """Text of ``im``'s jitted step program for a decode (or tiled-prefill)
    batch: lowered StableHLO, or the compiled HLO (collectives visible)."""
    from flexflow_tpu.serve.batch_config import (
        BatchConfig,
        PrefillBatchConfig,
    )

    if prefill:
        bc, _ = PrefillBatchConfig.build(
            [(0, [5] * im.prefill_tile, 0)], [im.prefill_tile],
            im.prefill_tile, max_tokens=im.max_tokens,
            max_requests=im.max_requests)
    else:
        bc = BatchConfig.build([5], [0], [0], [1], max_tokens=im.max_tokens,
                               max_requests=im.max_requests)
    lowered = im._step.lower(im.params, im.state, bc, None, None, None,
                             im._page_view())
    return lowered.compile().as_text() if compiled else lowered.as_text()


def assert_kernels(im):
    """The Pallas kernels are really in ``im``'s step programs (the
    lowered text holds each jitted kernel once, however many layers call
    it)."""
    assert im.use_pallas is True, "use_pallas is off on a TPU backend"
    assert im.pallas_interpret is False, "kernels in interpret mode"
    for prefill in (False, True):
        assert "tpu_custom_call" in step_text(im, prefill), (
            f"no tpu_custom_call in the lowered "
            f"{'prefill' if prefill else 'decode'} step")


# ---------------------------------------------------------------------------
def serve_phase(layers, tp, devices):
    """LLM.compile/generate at the serve capacities; returns (llm, report).
    The caller releases ``llm.im``."""
    import jax

    from flexflow_tpu.serve import LLM

    cfg = model_config(layers)
    t0 = time.perf_counter()
    llm = LLM(cfg).compile(tp=tp, devices=devices, dtype="bfloat16", **SERVE)
    # the random weights are drawn on the device, asynchronously: wait, or
    # their seconds are charged to the first generate
    jax.block_until_ready((llm.im.params, llm.im.state))
    build_s = time.perf_counter() - t0
    prompts = make_prompts(PROMPT_LENS, cfg.vocab_size)

    t0 = time.perf_counter()
    cold = llm.generate(prompts, NEW_TOKENS)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm = llm.generate(prompts, NEW_TOKENS)
    warm_s = time.perf_counter() - t0

    assert len(cold) == len(prompts), f"{len(cold)} results"
    for out in cold:  # generate returns the NEW tokens of each request
        assert len(out) == NEW_TOKENS, f"{len(out)} tokens, asked {NEW_TOKENS}"
        assert all(0 <= t < cfg.vocab_size for t in out), "token id range"
    assert cold == warm, "the same requests gave different tokens twice"
    assert_kernels(llm.im)
    report = dict(
        layers=layers, tp=tp, build_s=build_s, generate_cold_s=cold_s,
        generate_warm_s=warm_s, prompt_tokens=sum(PROMPT_LENS),
        tokens_generated=len(prompts) * NEW_TOKENS)
    return llm, report


def build_im(cfg, devices, tp, use_pallas, params=None):
    """One comparison deployment: what ``LLM.compile`` builds, with the
    kernel switch and top-k logprobs exposed (InferenceManager's own
    arguments — ``LLM.compile`` always takes ``use_pallas="auto"``)."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.model import FFModel
    from flexflow_tpu.parallel.mesh import make_mesh
    from flexflow_tpu.serve.inference_manager import InferenceManager
    from flexflow_tpu.serve.models.base import build_model

    caps = COMPARE
    ff = FFModel(FFConfig(), mesh=make_mesh({"tp": tp}, devices))
    logits = build_model(ff, cfg, caps["max_tokens_per_batch"])
    im = InferenceManager(ff, outputs=logits, topk=TOPK,
                          use_pallas=use_pallas, **caps)
    im.init_operators_inference(params=params, dtype="bfloat16")
    return im


def drive_steps(im, prompts, feed=None):
    """Prefill ``prompts`` chunk by chunk, then COMPARE_DECODE_STEPS decode
    steps, all through ``im.step``.  Returns per-step ``(logits_max,
    topk_logprobs, topk_ids)`` at the VALID token slots, and the tokens fed
    to each decode step: this deployment's own greedy picks, or ``feed``
    (another deployment's) so that both score the SAME batches even where
    an argmax flips on rounding."""
    import numpy as np

    from flexflow_tpu.serve.batch_config import (
        BatchConfig,
        PrefillBatchConfig,
    )

    cap, nreq, tile = im.max_tokens, im.max_requests, im.prefill_tile
    seq = [0] * len(prompts)
    outs, last_tok = [], [None] * len(prompts)
    for slot, p in enumerate(prompts):
        for start in range(0, len(p), cap):
            chunk = p[start:start + cap]
            seq[slot] = start + len(chunk)
            bc, last_flat = PrefillBatchConfig.build(
                [(slot, chunk, start)], seq, tile, max_tokens=cap,
                max_requests=nreq)
            res = im.step(bc)
            n = len(chunk)
            outs.append((np.asarray(res.logits_max)[:n],
                         np.asarray(res.topk_logprobs)[:n],
                         np.asarray(res.topk_ids)[:n]))
            last_tok[slot] = int(np.asarray(res.token_ids)[last_flat[slot]])
    fed = []
    for i in range(COMPARE_DECODE_STEPS):
        if feed is not None:
            last_tok = feed[i]
        fed.append(list(last_tok))
        pos = list(seq)
        seq = [s + 1 for s in seq]
        bc = BatchConfig.build(last_tok, list(range(len(prompts))), pos, seq,
                               max_tokens=cap, max_requests=nreq)
        res = im.step(bc)
        n = len(prompts)
        outs.append((np.asarray(res.logits_max)[:n],
                     np.asarray(res.topk_logprobs)[:n],
                     np.asarray(res.topk_ids)[:n]))
        last_tok = [int(t) for t in np.asarray(res.token_ids)[:n]]
    return outs, fed


def compare_outputs(a, b, what, tol_ulps):
    """Max differences of logits_max / topk_logprobs between two drives of
    the same batches; asserts finiteness and the stated tolerance."""
    import numpy as np

    d_lmax = d_topk = scale = 0.0
    agree = total = 0
    for (la, ta, ia), (lb, tb, ib) in zip(a, b):
        assert np.isfinite(la).all() and np.isfinite(lb).all(), \
            f"{what}: non-finite logits"
        assert np.isfinite(ta).all() and np.isfinite(tb).all(), \
            f"{what}: non-finite logprobs"
        scale = max(scale, float(np.abs(la).max()), float(np.abs(lb).max()))
        d_lmax = max(d_lmax, float(np.abs(la - lb).max()))
        # k-th largest logprob by RANK: ids may swap between near-ties
        d_topk = max(d_topk, float(np.abs(ta - tb).max()))
        agree += int((ia[:, 0] == ib[:, 0]).sum())
        total += len(ia)
    tol = tol_ulps * BF16_EPS * max(scale, 1.0)
    log(f"{what}: max|d logits_max|={d_lmax:.5f} "
        f"max|d topk_logprobs|={d_topk:.5f} tolerance={tol:.5f} "
        f"(logit scale {scale:.3f}; {tol_ulps} bf16 ulps) "
        f"argmax agree {agree}/{total}")
    assert d_lmax <= tol, f"{what}: logits_max differ by {d_lmax} > {tol}"
    assert d_topk <= tol, f"{what}: topk_logprobs differ by {d_topk} > {tol}"
    return d_lmax, d_topk


def compare_phase(layers, devices, tp_a, pallas_a, tp_b, pallas_b, what,
                  tol_ulps):
    """Drive deployment A then B on the same batches (caches of one
    released before the other is built) and compare their logits."""
    cfg = model_config(layers)
    prompts = make_prompts(COMPARE_PROMPTS, cfg.vocab_size, SEED + 1)
    im_a = build_im(cfg, devices[:tp_a], tp_a, pallas_a)
    if pallas_a:
        assert_kernels(im_a)
    outs_a, fed = drive_steps(im_a, prompts)
    # same seed, same graph -> bit-identical weights (init_params draws per
    # parameter from fold_in(PRNGKey(0), index)); on the same mesh the
    # arrays themselves are shared
    params = im_a.params if tp_a == tp_b else None
    release(im_a)
    im_b = build_im(cfg, devices[:tp_b], tp_b, pallas_b, params=params)
    if pallas_b:
        assert_kernels(im_b)
    outs_b, _ = drive_steps(im_b, prompts, feed=fed)
    release(im_b)
    return compare_outputs(outs_a, outs_b, what, tol_ulps)


# ---------------------------------------------------------------------------
def log_serve(tag, llm, rep, devices):
    log(f"{tag}: opt-6.7b widths hidden={OPT_6_7B['hidden_size']} "
        f"ffn={OPT_6_7B['ffn_dim']} heads={OPT_6_7B['num_attention_heads']}"
        f"x{OPT_6_7B['hidden_size'] // OPT_6_7B['num_attention_heads']} "
        f"vocab={OPT_6_7B['vocab_size']} depth={rep['layers']} of 32 layers "
        f"tp={rep['tp']}")
    log(f"{tag}: build+weights {rep['build_s']:.1f}s, generate cold "
        f"(trace+compile included) {rep['generate_cold_s']:.1f}s, generate "
        f"warm {rep['generate_warm_s']:.2f}s")
    log(f"{tag}: {rep['prompt_tokens']} prompt tokens, "
        f"{rep['tokens_generated']} tokens generated "
        f"({len(PROMPT_LENS)} requests x {NEW_TOKENS}), "
        f"prefill tile {llm.im.prefill_tile}")
    log_memory(tag, devices)


def one_chip(devices):
    devices = devices[:1]
    llm, rep = serve_phase(ONE_CHIP_LAYERS, 1, devices)
    log_serve("serve", llm, rep, devices)
    release(llm.im)
    compare_phase(ONE_CHIP_LAYERS, devices, 1, True, 1, False,
                  "kernel-vs-gather", KERNEL_TOL_ULPS)
    log_memory("compare", devices)


def four_chips(devices):
    assert len(devices) >= 4, f"--chips 4 needs 4 devices, have {len(devices)}"
    devices = devices[:4]
    # (a) tp=4 vs tp=1 (on device 0), depth-cut, kernels on both sides
    compare_phase(ONE_CHIP_LAYERS, devices, 4, True, 1, True, "tp4-vs-tp1",
                  TP_TOL_ULPS)
    log(f"after tp compare: bytes_in_use {memory(devices, 'bytes_in_use')}")
    # (b) the full model — what one chip cannot hold in bf16
    llm, rep = serve_phase(32, 4, devices)
    log_serve("tp4 serve", llm, rep, devices)
    # (c) spread over the four devices, collectives + kernel in one program
    used = memory(devices, "bytes_in_use")
    log(f"tp4 serve: bytes_in_use {used}")
    assert min(used) > 0 and max(used) <= 1.15 * min(used), \
        f"memory not spread evenly over the devices: {used}"
    text = step_text(llm.im, prefill=False, compiled=True)
    kernels = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    # inside shard_map the kernel sees its LOCAL cache shard: a quarter of
    # the 32 kv heads
    local = f"bf16[{SERVE['max_requests'] + 1},{32 // 4},"
    n_local = sum(local in ln and "/shard_map/" in ln for ln in kernels)
    n_ar = len(re.findall(r"all-reduce(-start)?\(", text))
    log(f"tp4 decode step (compiled): {len(kernels)} tpu_custom_call, "
        f"{n_local} under shard_map on the local {local}...] cache shard, "
        f"{n_ar} all-reduce")
    assert n_local >= 32 and n_ar >= 32, (len(kernels), n_local, n_ar)
    release(llm.im)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = the cross-chip (tp=4) path only")
    args = ap.parse_args(argv)

    import importlib.metadata as md

    import jax

    from flexflow_tpu.utils.platform import enable_compile_cache

    device = require_tpu()
    if device["count"] < args.chips:
        sys.stderr.write(f"chip_smoke: --chips {args.chips} but JAX found "
                         f"{device['count']} device(s)\n")
        return 2
    cache_dir = enable_compile_cache()
    # JAX's own account of where compile time goes: persistent-cache hits
    # and misses, and seconds spent tracing, lowering, and in the backend
    # (a compile on a miss, a load from the cache on a hit)
    counts, secs = collections.Counter(), collections.Counter()
    jax.monitoring.register_event_listener(
        lambda name, **_: counts.update([name.rsplit("/", 1)[-1]]))
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, s, **_: secs.update({name.rsplit("/", 1)[-1]: s}))
    log(f"device: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']} jax={jax.__version__} "
        f"libtpu={md.version('libtpu')}")
    log(f"compile cache: {cache_dir}")
    t0 = time.perf_counter()
    devices = jax.devices()
    if args.chips == 4:
        four_chips(devices)
    else:
        one_chip(devices)
    log(f"compile cache: {counts['cache_hits']} hits, "
        f"{counts['cache_misses']} misses; all programs: trace "
        f"{secs['jaxpr_trace_duration']:.1f}s, lower "
        f"{secs['jaxpr_to_mlir_module_duration']:.1f}s, backend compile or "
        f"cache load {secs['backend_compile_duration']:.1f}s (of which "
        f"cache retrieval {secs['cache_retrieval_time_sec']:.1f}s; "
        f"{secs['compile_time_saved_sec']:.1f}s saved by hits)")
    # built from committed files only: the git-ignored native dataloader
    # (built by `make` in a child on first use) is not on this path
    native = sys.modules.get("flexflow_tpu.data.native")
    assert native is None or native._lib is None, \
        "the serve path loaded flexflow_tpu/native/libffdl.so"
    log(f"total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
