"""Decompose the prefill chunk time on the real TPU (VERDICT r4 #2).

The r4 bench put prefill at ~23% MFU with no attribution.  This script times
the pieces of one 256-token chunk at the bench shape (8-layer 7B slice,
bs=8, ctx~900 average) separately:

* ``gemms``     — the chunk's projection/MLP/LM-head GEMM stack alone
* ``attn``      — the Q-tiled Pallas prefill kernel alone (4 tiles x 8 layers)
* ``write_dus`` — per-tile block dynamic-update-slice KV writes (r5 path)
* ``write_scatter`` — the flat-token XLA scatter the r4 path used
* ``step``      — the real full prefill step through the serve stack

Prints one JSON line; the gap between ``step`` and the sum of parts is
dispatch/fusion overhead.  Run on the TPU backend (default env).
"""

import json
import time

import numpy as np


def timeit(fn, *args, iters=20, warm=3):
    import jax

    for _ in range(warm):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main():
    import jax
    import jax.numpy as jnp

    E, KV, D, INTER, VOCAB, LAYERS = 4096, 32, 128, 11008, 32000, 8
    S, R, T, TILE = 2048, 8, 256, 64
    G = T // TILE
    key = jax.random.PRNGKey(0)
    doc = {"config": f"T={T} tile={TILE} E={E} layers={LAYERS} S={S}"}

    # ---- GEMM stack ---------------------------------------------------
    x = jax.random.normal(key, (T, E), jnp.bfloat16)
    Wqkv = jax.random.normal(key, (E, 3 * E), jnp.bfloat16) * 0.02
    Wo = jax.random.normal(key, (E, E), jnp.bfloat16) * 0.02
    Wg = jax.random.normal(key, (E, INTER), jnp.bfloat16) * 0.02
    Wu = jax.random.normal(key, (E, INTER), jnp.bfloat16) * 0.02
    Wd = jax.random.normal(key, (INTER, E), jnp.bfloat16) * 0.02
    Whead = jax.random.normal(key, (E, VOCAB), jnp.bfloat16) * 0.02

    @jax.jit
    def gemms(x):
        h = x
        for _ in range(LAYERS):
            qkv = h @ Wqkv
            h = qkv[:, :E] @ Wo
            g = jax.nn.silu(h @ Wg) * (h @ Wu)
            h = g @ Wd
        return h @ Whead

    t_gemms = timeit(gemms, x)
    flops = T * 2 * (LAYERS * (E * 3 * E + E * E + 3 * E * INTER)
                     + E * VOCAB)
    doc["gemms_ms"] = round(t_gemms * 1e3, 3)
    doc["gemms_mfu"] = round(flops / t_gemms / 197e12, 3)

    # ---- Pallas prefill attention kernel ------------------------------
    from flexflow_tpu.ops.pallas.attention import prefill_attention

    q = jax.random.normal(key, (G, TILE, KV, D), jnp.bfloat16)
    kc = jax.random.normal(key, (R + 1, KV, S, D), jnp.bfloat16)
    vc = jax.random.normal(key, (R + 1, KV, S, D), jnp.bfloat16)
    rows = jnp.arange(G, dtype=jnp.int32) % R
    pstart = jnp.full((G,), 896, jnp.int32)  # mid-context frontier

    @jax.jit
    def attn(q, kc, vc):
        out = q
        for _ in range(LAYERS):
            out = prefill_attention(
                out.reshape(G, TILE, KV, D), kc, vc, rows, pstart,
                scale=0.0883883,
            )
        return out

    t_attn = timeit(attn, q, kc, vc)
    doc["attn_ms"] = round(t_attn * 1e3, 3)

    # ---- KV write paths -----------------------------------------------
    k_new = jax.random.normal(key, (T, KV, D), jnp.bfloat16)
    flat_rows = jnp.repeat(rows, TILE)
    flat_pos = (pstart[:, None] + jnp.arange(TILE)[None, :]).reshape(-1)

    @jax.jit
    def write_dus(kc, k_new):
        kb = k_new.reshape(G, TILE, KV, D).transpose(0, 2, 1, 3)
        for i in range(G):
            kc = jax.lax.dynamic_update_slice(
                kc, kb[i][None], (rows[i], jnp.int32(0), pstart[i],
                                  jnp.int32(0)))
        return kc

    @jax.jit
    def write_scatter(kc, k_new):
        idx = jnp.stack([flat_rows, flat_pos], axis=-1)
        dnums = jax.lax.ScatterDimensionNumbers(
            update_window_dims=(1, 2), inserted_window_dims=(0, 2),
            scatter_dims_to_operand_dims=(0, 2))
        return jax.lax.scatter(
            kc, idx, k_new, dnums,
            mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)

    doc["write_dus_ms"] = round(
        timeit(write_dus, kc, k_new) * 1e3 * 2 * LAYERS, 3)  # k+v, 8 layers
    doc["write_scatter_ms"] = round(
        timeit(write_scatter, kc, k_new) * 1e3 * 2 * LAYERS, 3)

    # ---- real full step -----------------------------------------------
    import bench

    # program mode like bench.main(): arm the persistent compile cache so
    # profiling re-runs skip the full-model recompile
    from flexflow_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    im = bench.build_im(use_pallas=True, layers=LAYERS, hidden=E, heads=32,
                        kv=KV, inter=INTER, vocab=VOCAB, max_requests=R,
                        max_seq=S, max_tokens=T)
    from flexflow_tpu.serve.batch_config import PrefillBatchConfig

    seq = np.full(R, 896 + TILE, np.int32)
    segs = [(r, np.random.randint(1, VOCAB, TILE).tolist(), 896)
            for r in range(min(G, R))]
    pbc, _ = PrefillBatchConfig.build(
        segs, seq.tolist(), TILE, max_tokens=T, max_requests=R)

    def step(bc):
        return im.step(bc)

    t_step = timeit(step, pbc, iters=10)
    doc["step_ms"] = round(t_step * 1e3, 3)
    doc["parts_sum_ms"] = round(
        (t_gemms + t_attn) * 1e3 + doc["write_dus_ms"], 3)
    print(json.dumps(doc))


if __name__ == "__main__":
    main()
