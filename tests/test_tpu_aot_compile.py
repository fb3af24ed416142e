"""AOT-compile the serve path's Pallas kernels for a DESCRIBED TPU v5e.

Interpret mode (every other kernel test in this suite) cannot see what the
TPU compiler refuses: scoped-VMEM overflow, unaligned slices, kernels that
cannot be partitioned.  The installed libtpu compiles for a chip that is
described and not attached (``jax.experimental.topologies``), so these
cases hand the kernels the shapes ``serve/ops.py`` really passes — real
widths, default ``block_s``/``kv_chunk`` — and assert a Mosaic kernel
(``tpu_custom_call``) comes out.  Nothing runs; a pass here is not a chip
run.

The topology is described INSIDE a module-scoped fixture (never at import:
only one process may load libtpu, and every xdist worker imports every test
file), the compiles happen in this process, and all cases live in this one
file so one worker owns the library.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from flexflow_tpu.ops.pallas.attention import (
    decode_attention,
    kv_block_write,
    kv_row_write,
    prefill_attention,
    sparse_decode_attention,
    tree_attention,
    tree_attention_batched,
)

R = 8          # max_requests (cache rows = R + 1 scratch)
TILE = 128     # prefill query tile (pick_prefill_tile at 512-token chunks)
P_SPEC = 16    # spec-tree slots per request
PAGE = 512


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one (the next run would warn and recompile),
    so the cache is off around this module's compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _call(kernel, sh, kv, gq, d, s, variant):
    """``kernel`` at the serve path's shapes, for one cache variant: bf16,
    int8 (+ scale planes), paged-512, or a ring read through a window — the
    function, its operands' shapes, its traced keyword operands."""
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=sh)
    k = sds((R + 1, kv, s, d),
            jnp.int8 if variant == "int8" else jnp.bfloat16)
    kw, static = {}, {}  # traced / static keyword operands
    if variant == "int8":
        kw["k_scale"] = kw["v_scale"] = sds((R + 1, kv, s), jnp.float32)
    if variant == "paged":
        kw["page_table"] = sds((R + 1, s // PAGE), jnp.int32)
        static["page_size"] = PAGE
    if variant.startswith("ring"):  # a sliding-window layer's ring cache
        static["window"] = int(variant[len("ring"):])
    scale = d ** -0.5
    qh = kv * gq
    if kernel == "decode":
        t = R
        f = functools.partial(decode_attention, scale=scale, **static)
        args = (sds((t, qh, d), jnp.bfloat16), k, k,
                sds((t,), jnp.int32), sds((t,), jnp.int32))
    elif kernel == "prefill":
        g = 512 // TILE
        f = functools.partial(prefill_attention, scale=scale, **static)
        args = (sds((g, TILE, qh, d), jnp.bfloat16), k, k,
                sds((g,), jnp.int32), sds((g,), jnp.int32))
    else:
        spec = sds((R + 1, kv, P_SPEC, d), jnp.bfloat16)
        if kernel == "tree":
            t = R * P_SPEC
            f = functools.partial(tree_attention, scale=scale, **static)
            args = (sds((t, qh, d), jnp.bfloat16), k, k, spec, spec,
                    sds((t,), jnp.int32), sds((t,), jnp.int32),
                    sds((t, P_SPEC), jnp.bool_))
        else:
            f = functools.partial(tree_attention_batched, scale=scale,
                                  **static)
            args = (sds((R, P_SPEC, qh, d), jnp.bfloat16), k, k, spec, spec,
                    sds((R,), jnp.int32), sds((R,), jnp.int32),
                    sds((R, P_SPEC, P_SPEC), jnp.bool_))
    return f, args, kw


def _lower(*case):
    """Lowered (not yet compiled) :func:`_call`."""
    f, args, kw = _call(*case)
    return jax.jit(f).lower(*args, **kw)


# (kernel, KV, gq, D, S, cache variant): the 7B-class MHA geometry in every
# cache layout, the long-context and tp=4-local prefill shapes, and the MQA
# geometry (starcoder-class) for decode and prefill
_CASES = [
    (kern, 32, 1, 128, 2048, var)
    for kern in ("decode", "prefill", "tree", "tree_batched")
    for var in ("bf16", "int8", "paged")
] + [
    ("prefill", 32, 1, 128, 4096, "bf16"),
    ("prefill", 32, 1, 128, 4096, "paged"),
    ("prefill", 8, 1, 128, 2048, "bf16"),
    ("decode", 1, 16, 128, 8192, "bf16"),
    ("prefill", 1, 16, 128, 8192, "bf16"),
    # starcoderbase-3b's own: 22 query heads on the one K/V head
    ("prefill", 1, 22, 128, 8192, "bf16"),
    # differential attention (phi4flash): 10 K/V pairs cached as heads of
    # 128, four zero-padded query heads each; the one full-length cache,
    # and a 512-window in a ring of 1024 slots
    ("decode", 10, 4, 128, 8192, "bf16"),
    ("prefill", 10, 4, 128, 8192, "bf16"),
    ("decode", 10, 4, 128, 1024, "ring512"),
    # a plain ring (cohere2_moe's sliding layers, one chip's eighth: 16
    # query heads on 1 K/V head): a window of 4096 in a ring of 4608 slots,
    # read by the decode scan and — the window's lower bound in the kernel —
    # by a prompt chunk's tiles; and its full layer's cache of 18432
    ("decode", 1, 16, 128, 4608, "ring4096"),
    ("prefill", 1, 16, 128, 4608, "ring4096"),
    ("decode", 1, 16, 128, 18432, "bf16"),
    ("prefill", 1, 16, 128, 18432, "bf16"),
    # nemotron_h's one GQA layer in nine: 32 query heads on 2 K/V heads
    ("decode", 2, 16, 128, 8192, "bf16"),
    ("prefill", 2, 16, 128, 8192, "bf16"),
    # mellum's sliding layers: 32 query heads on 4 K/V heads, a window of
    # 1024 in a ring of 2048 slots (the window + a 1024-row prompt chunk):
    # half the key blocks of a tile lie outside its window
    ("decode", 4, 8, 128, 2048, "ring1024"),
    ("prefill", 4, 8, 128, 2048, "ring1024"),
]


@pytest.mark.parametrize(
    "kernel,kv,gq,d,s,variant", _CASES,
    ids=[f"{c[0]}-kv{c[1]}gq{c[2]}d{c[3]}s{c[4]}-{c[5]}" for c in _CASES])
def test_kernel_compiles_for_v5e(one_chip, kernel, kv, gq, d, s, variant):
    compiled = _lower(kernel, one_chip, kv, gq, d, s, variant).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kv,s,variant,plan", [
    (1, 18432, "bf16", "full2048"),
    (1, 8192, "bf16", "full2048"),
    (2, 8192, "bf16", "full1024"),
    (1, 4608, "ring4096", "ring4608"),
])
def test_a_block_grown_by_bytes_compiles_in_the_default_scoped_vmem(
        one_chip, kv, s, variant, plan):
    """``decode_attention`` on one or two K/V heads takes the block planned
    by bytes (PR 51) — 1 MB of K + V a grid step, a ring of 4608 slots whole
    (2.4 MB, double-buffered) — and asks the compiler for no more scoped
    VMEM than its default."""
    from flexflow_tpu.ops.pallas.attention import decode_block_plan

    window = int(variant[4:]) if variant.startswith("ring") else 0
    assert decode_block_plan(
        jax.ShapeDtypeStruct((R + 1, kv, s, 128), jnp.bfloat16),
        window=window) == plan
    text = _lower("decode", one_chip, kv, 16, 128, s, variant).compile(
        ).as_text()
    import re

    call, = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    # a ``vmem_limit_bytes`` would stand here as a scoped-memory entry
    assert re.search(r'[^_]scoped_memory_configs":\[\]', call)
    used = [int(n) for n in re.findall(
        r'used_scoped_memory_configs":\[\{"memory_space":"1","offset":"0",'
        r'"size":"(\d+)"', call)]
    assert used and 0 < max(used) < 16 * 2**20


@pytest.mark.parametrize("kv,gq,s,variant,plan", [
    (32, 1, 2048, "bf16", (16, 256)),       # opt-6.7b
    (32, 1, 2048, "int8", (16, 256)),
    (1, 22, 8192, "bf16", (1, 256)),        # starcoderbase-3b (MQA)
    (1, 16, 4608, "ring4096", (1, 512)),    # cohere2_moe's sliding layers
    (10, 4, 8192, "bf16", (2, 512)),        # phi4flash's full layer
])
def test_the_prefill_plans_vmem_count_is_never_below_the_compilers(
        one_chip, kv, gq, s, variant, plan):
    """``_prefill_plan`` answers what it answered before PR 60 at the cells'
    geometries, the kernel asks for no more scoped VMEM than the default,
    and ``_prefill_vmem_bytes`` — which the plan trusts — counts at least
    what the compiler says the call uses."""
    import re

    from flexflow_tpu.ops.pallas.attention import (_prefill_plan,
                                                   _prefill_vmem_bytes)

    quant = variant == "int8"
    item = 1 if quant else 2
    assert _prefill_plan(kv, 128, 2, item, quant, TILE * gq, 512,
                         s) == plan
    text = _lower("prefill", one_chip, kv, gq, 128, s, variant).compile(
        ).as_text()
    call, = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert re.search(r'[^_]scoped_memory_configs":\[\]', call)
    used = [int(n) for n in re.findall(
        r'used_scoped_memory_configs":\[\{"memory_space":"1","offset":"0",'
        r'"size":"(\d+)"', call)]
    assert used and 0 < max(used) <= _prefill_vmem_bytes(
        *plan[:1], TILE * gq, plan[1], 128, 2, item, quant) < 16 * 2**20


@pytest.mark.parametrize("rows", [48, 512], ids=["scan48", "flat512"])
def test_sparse_decode_kernel_compiles_for_v5e(one_chip, rows):
    """``sparse_decode_attention`` at MiniCPM-SALA's published geometry (32
    query heads on 2 K/V heads of 128, a cache of 32 768 positions, lists of
    128 blocks of 64): the decode scan's 48 rows in one call, a flat step's
    512 in calls of 64 (the block lists ride in scalar memory)."""
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    k = sds((49, 2, 32768, 128), jnp.bfloat16)
    f = functools.partial(sparse_decode_attention, scale=128 ** -0.5,
                          block=64, tail_run=2048 // 64)
    compiled = jax.jit(f).lower(
        sds((rows, 32, 128), jnp.bfloat16), k, k, sds((rows,), jnp.int32),
        sds((rows,), jnp.int32), sds((rows, 2, 128), jnp.int32),
        sds((rows, 2), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# (KV, D, S, slots, cache type, fresh type): a 512-row chunk in tiles of 128
_WRITE_CASES = {
    "opt": (32, 128, 2048, 16, jnp.bfloat16, jnp.bfloat16),
    "starcoder_mqa": (1, 128, 8192, 16, jnp.bfloat16, jnp.bfloat16),
    "phi4_pairs": (10, 128, 8192, 16, jnp.bfloat16, jnp.bfloat16),
    "int8": (32, 128, 2048, 16, jnp.int8, jnp.int8),
    "sala_kv2": (2, 128, 32768, 48, jnp.bfloat16, jnp.bfloat16),
    "cast": (8, 128, 2048, 16, jnp.bfloat16, jnp.float32),
    "command_ring": (1, 128, 4608, 128, jnp.bfloat16, jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(_WRITE_CASES))
def test_block_write_kernel_updates_the_caches_in_place(one_chip, case):
    """``kv_block_write`` at the widths the cells run: the compiler tiles
    it, and with the caches donated (the prefill scan's carry) the compiled
    program holds NO temporary — both caches are written where they lie."""
    kv, d, s, slots, cache_dt, fresh_dt = _WRITE_CASES[case]
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    cache, fresh = sds((slots + 1, kv, s, d), cache_dt), \
        sds((kv, 512, d), fresh_dt)
    tiles = sds((512 // TILE,), jnp.int32)

    def write(kc, vc, k, v, rows, start, count):
        # the fresh rows as the fused QKV projection leaves them: heads first
        return kv_block_write(kc, vc, jnp.swapaxes(k, 0, 1),
                              jnp.swapaxes(v, 0, 1), rows, start, count,
                              tile=TILE)

    compiled = jax.jit(write, donate_argnums=(0, 1)).lower(
        cache, cache, fresh, fresh, tiles, tiles, tiles).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 1
    assert "dynamic-update-slice" not in text and " copy(" not in text
    mem = compiled.memory_analysis()
    cache_bytes = (slots + 1) * kv * s * d * jnp.dtype(cache_dt).itemsize
    assert mem.alias_size_in_bytes == 2 * cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes // 64


# (KV, D, S, slots, cache type, fresh type): a decode scan's step, a row a
# slot
_ROW_WRITE_CASES = {
    "opt": (32, 128, 2048, 8, jnp.bfloat16, jnp.bfloat16),
    "starcoder_mqa": (1, 128, 8192, 16, jnp.bfloat16, jnp.bfloat16),
    "phi4_pairs": (10, 128, 8192, 32, jnp.bfloat16, jnp.bfloat16),
    "evabyte": (32, 128, 4096, 16, jnp.bfloat16, jnp.bfloat16),
    "sala_kv2": (2, 128, 32768, 48, jnp.bfloat16, jnp.bfloat16),
    "nemotron_kv2": (2, 128, 8192, 256, jnp.bfloat16, jnp.bfloat16),
    "command_ring": (1, 128, 4608, 128, jnp.bfloat16, jnp.bfloat16),
    "latent": (1, 512, 16384, 64, jnp.bfloat16, jnp.bfloat16),
    "int8": (32, 128, 2048, 8, jnp.int8, jnp.int8),
    "cast": (8, 128, 2048, 16, jnp.float32, jnp.bfloat16),
}


def _holds_no_copy_of(text, shape):
    """No ``copy`` and no ``dynamic-update-slice`` of an array of ``shape``
    in a compiled program's text."""
    dims = ",".join(map(str, shape))
    return not [ln for ln in text.splitlines()
                if (" copy(" in ln or "dynamic-update-slice(" in ln)
                and f"[{dims}]" in ln.partition(" = ")[2].partition("(")[0]]


@pytest.mark.parametrize("case", list(_ROW_WRITE_CASES))
def test_row_write_kernel_updates_the_caches_in_place(one_chip, case):
    """``kv_row_write`` at the widths the cells' decode scans run: the
    compiler tiles it (a group of positions in and out, the row set by a
    sublane compare on the cache's own packed type), and with the caches
    donated (the scan's carry) the compiled program holds no update-slice,
    no copy and NO temporary of a cache — both are written where they
    lie."""
    kv, d, s, slots, cache_dt, fresh_dt = _ROW_WRITE_CASES[case]
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    cache = sds((slots + 1, kv, s, d), cache_dt)
    fresh, at = sds((slots, kv, d), fresh_dt), sds((slots,), jnp.int32)
    compiled = jax.jit(kv_row_write, donate_argnums=(0, 1)).lower(
        cache, cache, fresh, fresh, at, at).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 1
    assert "dynamic-update-slice" not in text
    assert _holds_no_copy_of(text, cache.shape)
    mem = compiled.memory_analysis()
    cache_bytes = (slots + 1) * kv * s * d * jnp.dtype(cache_dt).itemsize
    assert mem.alias_size_in_bytes == 2 * cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes // 64


@pytest.mark.parametrize("case", ["opt", "command_ring", "phi4_pairs"])
def test_a_scan_that_writes_rows_then_attends_re_lays_no_cache(one_chip,
                                                               case):
    """A decode-scan body — ``kv_row_write``, then ``decode_attention`` on
    the caches it returned, both the scan's carry: the two kernels agree on
    the caches' layout, so the loop copies neither (what an XLA scatter
    there cost: a cache re-laid out every step)."""
    kv, d, s, slots, cache_dt, fresh_dt = _ROW_WRITE_CASES[case]
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    cache = sds((slots + 1, kv, s, d), cache_dt)
    fresh, at = sds((slots, kv, d), fresh_dt), sds((slots,), jnp.int32)

    def scan(kc, vc, k, v, rows, pos):
        def body(carry, i):
            kc, vc = kv_row_write(*carry, k, v, rows, pos + i)
            out = decode_attention(k, kc, vc, rows, pos + i, scale=d ** -0.5)
            return (kc, vc), out
        return jax.lax.scan(body, (kc, vc), jnp.arange(4))

    compiled = jax.jit(scan, donate_argnums=(0, 1)).lower(
        cache, cache, fresh, fresh, at, at).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert _holds_no_copy_of(text, cache.shape)
    mem = compiled.memory_analysis()
    cache_bytes = (slots + 1) * kv * s * d * jnp.dtype(cache_dt).itemsize
    assert mem.alias_size_in_bytes == 2 * cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes // 64


@pytest.mark.parametrize("path", ["slot_order", "gathered"])
def test_block_selection_reads_the_index_where_it_lies(one_chip, path):
    """MiniCPM-SALA's block selection at the decode scan's published shapes
    (48 rows, an index of ``[49, 2, 2048, 128]`` bf16, 512 blocks, lists of
    128) for the described chip.  In slot order the scores' ``convolution``
    reads the index parameter itself: no ``gather`` and no fusion makes an
    array of the index's size (50 MB a layer and step, PERF.md section 6,
    PR 47).  The gathered form — a flat step's, the control — holds both, so
    the reading can see them.  Either way ONE ``sort`` is left, ``top_k``'s:
    the mask comes from compares and the sorted list from ranks."""
    import re

    from flexflow_tpu.serve.hybrid_ops import SparseBlockAttention

    op = SparseBlockAttention(4096, 32, 2, 128, dtype=jnp.bfloat16)
    select = op._select_slots if path == "slot_order" else op._select_rows
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    hlo = jax.jit(lambda *a: op.block_list(select(*a))).lower(
        sds((48, 32, 128), jnp.bfloat16), sds((49, 2, 2048, 128), jnp.bfloat16),
        sds((48,), jnp.int32), sds((48,), jnp.int32)).compile().as_text()
    made, sorts = [], 0
    for ln in hlo.splitlines():
        result, _, rest = ln.partition(" = ")
        op_at = re.search(r"\s(gather|fusion|sort)\(", rest)
        if not op_at or "bitcast_fusion" in rest:
            continue
        sorts += op_at.group(1) == "sort"
        # half the index and more: XLA gathers it in two halves
        if re.search(r"bf16\[4[89],2,(1024|2048),128\]",
                     rest[:op_at.start()]):
            made.append(f"{op_at.group(1)} {result.strip()}")
    assert sorts == 1, sorts
    if path == "slot_order":
        assert not made, made
    else:
        assert {m.split()[0] for m in made} == {"gather", "fusion"}, made


def test_decode_under_shard_map_on_four_chips(topo):
    """The tp=4 serve path: the decode kernel inside ``jax.shard_map`` over
    the KV-head axis, on a mesh of the described 2x2's four devices.  Each
    device holds a quarter of the cache."""
    import numpy as np

    mesh = Mesh(np.array(topo.devices).reshape(4), ("tp",))
    kv, d, s, t = 32, 128, 2048, R

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    def attend(q, kc, vc, rows, pos):
        return decode_attention(
            q.reshape(t, -1, d), kc, vc, rows, pos, scale=d ** -0.5,
        ).reshape(q.shape)

    f = jax.shard_map(
        attend, mesh=mesh,
        in_specs=(P(None, "tp"), P(None, "tp"), P(None, "tp"), P(), P()),
        out_specs=P(None, "tp"), check_vma=False)
    cache = jax.ShapeDtypeStruct((R + 1, kv, s, d), jnp.bfloat16,
                                 sharding=ns(None, "tp"))
    idx = jax.ShapeDtypeStruct((t,), jnp.int32, sharding=ns())
    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((t, kv, 1, d), jnp.bfloat16,
                             sharding=ns(None, "tp")),
        cache, cache, idx, idx).compile()
    assert "tpu_custom_call" in compiled.as_text()
    per_device = compiled.memory_analysis().argument_size_in_bytes
    whole_cache = 2 * (R + 1) * kv * s * d * 2
    assert per_device < whole_cache / 4 * 1.05, per_device


def test_prefill_with_no_admissible_plan_raises(one_chip):
    """falcon-7b's MQA prefill geometry (KV=1, gq=71, D=64): at tile 128 the
    query tile alone is 9088 rows and ``kv_chunk`` cannot go below 1, so no
    plan fits VMEM — a ValueError naming the shape at trace time, not a
    kernel handed to a compiler that refuses it."""
    with pytest.raises(ValueError, match="prefill_attention.*m_rows"):
        _lower("prefill", one_chip, 1, 71, 64, 2048, "bf16")


# the toy SambaY of tests/test_phi4flash.py: 3 Mamba layers of 128 channels
_SAMBAY = dict(model_type="phi4flash", hidden_size=64, intermediate_size=96,
               num_hidden_layers=8, num_attention_heads=8,
               num_key_value_heads=4, vocab_size=320, sliding_window=16,
               mb_per_layer=2, tie_word_embeddings=True, layer_norm_eps=1e-5,
               max_position_embeddings=4096, initializer_range=0.125,
               torch_dtype="float32")


def _sambay(cap, slots, use_pallas):
    """The toy SambaY's manager, and a prefill scan's batch of ONE prompt
    that fills the chunk of ``cap`` rows."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.model import FFModel
    from flexflow_tpu.parallel.mesh import make_mesh
    from flexflow_tpu.serve.batch_config import (BatchConfig,
                                                 PrefillBatchConfig)
    from flexflow_tpu.serve.inference_manager import InferenceManager
    from flexflow_tpu.serve.models.base import (ServeModelConfig,
                                                build_model)

    ff = FFModel(FFConfig(), mesh=make_mesh({"tp": 1}, jax.devices()[:1]))
    build_model(ff, ServeModelConfig.from_hf_config(_SAMBAY), cap)
    im = InferenceManager(ff, max_requests=slots, max_tokens_per_batch=cap,
                          max_seq_len=1024, use_pallas=use_pallas)
    im.init_operators_inference()
    # the manager sees this process's CPU and would ask for interpret mode:
    # steered here, as the chip's compiler is what the cases are about
    im.pallas_interpret = False
    tile = im.prefill_tile
    fields, last_flat = PrefillBatchConfig.np_fields(
        [(1, list(range(3, 3 + cap)), 0)], [0, cap] + [0] * (slots - 2),
        tile, max_tokens=cap, max_requests=slots)
    bcs = PrefillBatchConfig(
        base=BatchConfig(*(jnp.asarray(f[None]) for f in fields[:5])),
        tile_size=tile,
        logit_slots=jnp.asarray(PrefillBatchConfig.np_logit_slots(
            [1], last_flat, slots)[None]) if im.gate_lm_head else None)
    return im, bcs


def _described(tree, one_chip):
    """The shapes of ``tree``'s arrays, on the described chip."""
    import numpy as np

    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        np.shape(x), x.dtype, sharding=one_chip), tree)


@pytest.mark.parametrize("use_pallas", [True, False],
                         ids=["kernel", "row_scan"])
def test_sambay_prefill_scan_program_for_v5e(one_chip, use_pallas):
    """The 512-row prefill-scan program of a toy SambaY, whole, for the
    described chip.  With the kernels on, every selective scan is ONE Mosaic
    kernel: no loop over the rows and no update-slice of the ``[C, N]`` state
    is left under a ``SelectiveScan`` scope.  The row scan (kernels off) is
    the control: it holds both, so the reading can see them."""
    im, bcs = _sambay(512, 4, use_pallas)
    args = _described((im.params, im.state, bcs), one_chip)
    hlo = jax.jit(im._prefill_scan_impl).lower(*args).compile().as_text()
    scan = [ln for ln in hlo.splitlines() if "/SelectiveScan." in ln]
    kernels = [ln for ln in scan if "tpu_custom_call" in ln]
    loops = [ln for ln in scan if " while(" in ln]
    writes = [ln for ln in scan if " dynamic-update-slice(" in ln
              or "dynamic_update_slice" in ln.split("metadata=")[-1]]
    if use_pallas:
        assert len(kernels) == 3 and not loops and not writes, \
            (len(kernels), loops[:1], writes[:1])
        assert im.attention_paths[
            ("selective_scan", "PrefillBatchConfig")] == "kernel"
    else:
        assert not kernels and loops and writes


def _conv_by_index(text, rows, k, c):
    """What a compiled program does by index under a ``CausalConv1d`` scope:
    (the scope's lines, the rows of channels each ``scatter`` /
    ``dynamic-update-slice`` under it writes, the gathers of tails under it
    — results of ``[..., K - 1, C]`` —, every array of ``[rows, K - 1, C]``
    in the program)."""
    import math
    import re

    dims = {m[1]: [int(d) for d in m[2].split(",") if d] for m in
            re.finditer(r"%([\w.\-]+) = \(?\w+\[([\d,]*)\]", text)}
    conv = [ln for ln in text.splitlines() if "/CausalConv1d." in ln]
    written, gathered = [], []
    for ln in conv:
        made = ln.partition(" = ")[2]
        m = re.search(r" (scatter|dynamic-update-slice)\(([^)]*)\)", ln)
        if m:
            operands = re.findall(r"%([\w.\-]+)", m[2])
            update = dims[operands[2 if m[1] == "scatter" else 1]]
            if update[-1:] == [c]:
                written.append(math.prod(update[:-1]))
        if " gather(" in ln and made.partition("]")[0].endswith(
                ",%d,%d" % (k - 1, c)):
            gathered.append(made.partition("{")[0])
    per_row = re.findall(r"\w+\[%d,%d,%d\]" % (rows, k - 1, c), text)
    return conv, written, gathered, per_row


@pytest.mark.parametrize("slots", [32, 160], ids=["scan32", "scan160"])
def test_sambay_decode_scan_steps_the_conv_tails_where_they_lie(one_chip,
                                                                slots):
    """The programs of the same toy SambaY, whole, for the described chip,
    on 32 slots and on 160 (either side of ``DUS_MAX_TOKENS``).  The DECODE
    SCAN: under a ``CausalConv1d`` scope NO array of channels is written by
    index — no ``dynamic-update-slice`` at all, no scatter but the two of
    one ``int32`` a slot (each slot's row and position; the step's rows
    come to their slots by a gather of ``[slots + 1, C]``, a third of a
    tail) — and no tail is gathered.  The FLAT STEP and the PREFILL SCAN
    (the row form, by segments since PR 67) are held to the same absence
    but for what puts the rows a stored tail reaches over the first pass's:
    no tail is gathered, no ``[rows, K - 1, C]`` exists, and no scatter or
    update-slice writes more than ``slots + 1`` rows of channels.  The
    control is the body before PR 67 (``tests/conv_row_forms.py``), compiled
    under the same scope name:
    the reading sees its gather and its write-back of every row."""
    from conv_row_forms import gather_scatter
    from delta_rule_forms import segments

    from flexflow_tpu.serve.batch_config import BatchConfig
    from flexflow_tpu.serve.hybrid_ops import CausalConv1d

    cap = 256
    im, bcs = _sambay(cap, slots, True)
    bc = BatchConfig.build([5] * slots, list(range(slots)), [40] * slots,
                           [41] * slots, max_tokens=cap, max_requests=slots)
    args = _described((im.params, im.state, bc), one_chip)
    tails = {tuple(bufs["conv"].shape) for bufs in im.state.values()
             if "conv" in bufs}
    assert tails == {(slots + 1, 3, 128)}
    by_index = functools.partial(_conv_by_index, rows=cap, k=4, c=128)

    scan = jax.jit(im._decode_scan_impl, static_argnames=("n_steps", "eos"),
                   donate_argnums=(1,)).lower(
        *args, None, None, None, n_steps=4, eos=None).compile().as_text()
    conv, written, gathered, per_row = by_index(scan)
    assert conv and not written and not gathered and not per_row, \
        (written, gathered[:1], per_row[:1])
    assert im.attention_paths[
        ("causal_conv1d", "one_row_per_request")] == "slot_order"

    step = jax.jit(im._step_impl, donate_argnums=(1,)).lower(
        *args).compile().as_text()
    prefill = jax.jit(im._prefill_scan_impl).lower(*_described(
        (im.params, im.state, bcs), one_chip)).compile().as_text()
    for text in (step, prefill):
        conv, written, gathered, per_row = by_index(text)
        assert conv and written and max(written) <= slots + 1, written
        assert not gathered and not per_row, (gathered[:1], per_row[:1])
    assert im.attention_paths[("causal_conv1d", "BatchConfig")] == "rows"
    assert im.attention_paths[
        ("causal_conv1d", "PrefillBatchConfig")] == "rows"

    def before(x, tails, req, pos, w):
        with jax.named_scope("CausalConv1d.control"):
            return gather_scatter(CausalConv1d(128, 4, bias=False), x, tails,
                                  segments(req, pos, slots), w, None)

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    control = jax.jit(before, donate_argnums=(1,)).lower(
        sds((cap, 128), jnp.float32), sds((slots + 1, 3, 128), jnp.float32),
        sds((cap,), jnp.int32), sds((cap,), jnp.int32),
        sds((4, 128), jnp.float32)).compile().as_text()
    conv, written, gathered, per_row = by_index(control)
    assert gathered and per_row and max(written) >= cap, \
        (written, gathered[:1], per_row[:1])


@pytest.mark.parametrize("widths", ["solar", "kimi", "kimi_flat"])
def test_the_convs_row_form_compiles_by_segments_for_v5e(one_chip, widths):
    """``CausalConv1d``'s row form at the cells' widths in bf16, K = 4, for
    the described chip — ``solar``: a 1024-row chunk of 24 576 channels
    (q | k | v of 64 heads x 128) over 17 state rows; ``kimi``: 512 rows of
    12 288 over 257; ``kimi_flat``: a flat step of 128 rows there —: NO
    array of ``[rows, K - 1, C]`` (each row's gathered tail, or the stack
    of what each row would leave), no gather of a tail, no scatter or
    update-slice of more than ``slots + 1`` rows of channels under the
    op's scope, the tails updated in place, and — where the chunk is
    larger than the tails — temporaries of at most four times the chunk."""
    from flexflow_tpu.core.op import OpContext
    from flexflow_tpu.serve.batch_config import BatchConfig
    from flexflow_tpu.serve.hybrid_ops import CausalConv1d

    rows, c, slots = {"solar": (1024, 24576, 16), "kimi": (512, 12288, 256),
                      "kimi_flat": (128, 12288, 256)}[widths]
    k = 4
    op = CausalConv1d(c, k, dtype=jnp.bfloat16, bias=False)

    def conv(x, tails, request_index, position, weight):
        bc = BatchConfig(tokens=position, request_index=request_index,
                         token_position=position,
                         num_tokens=jnp.int32(rows),
                         seq_lens=jnp.zeros((slots,), jnp.int32))
        paths = {}
        ctx = OpContext(extras={
            "node_name": "n", "batch_config": bc, "state": {"conv": tails},
            "pallas_decode": True, "attention_paths": paths})
        with jax.named_scope("CausalConv1d.n"):
            y = op.lower(ctx, [x], {"weight": weight})[0]
        assert paths == {("causal_conv1d", "BatchConfig"): "rows"}
        return y, ctx.extras["state_out"]["conv"]

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = jax.jit(conv, donate_argnums=(1,)).lower(
        sds((rows, c), jnp.bfloat16), sds((slots + 1, k - 1, c),
                                          jnp.bfloat16),
        sds((rows,), jnp.int32), sds((rows,), jnp.int32),
        sds((k, c), jnp.bfloat16)).compile()
    found, written, gathered, per_row = _conv_by_index(
        compiled.as_text(), rows, k, c)
    assert found and written and max(written) <= slots + 1, written
    assert not gathered and not per_row, (gathered[:1], per_row[:1])
    mem = compiled.memory_analysis()
    chunk, state = rows * c * 2, (slots + 1) * (k - 1) * c * 2
    assert mem.alias_size_in_bytes >= state            # updated in place
    assert mem.temp_size_in_bytes <= 4 * max(chunk, state)


@pytest.mark.parametrize("rows", [256, 512], ids=["scan256", "chunk512"])
def test_nemotron_routed_layer_compiles_for_v5e(one_chip, rows):
    """The routed-expert layer at Nemotron-3-Nano's published widths (hidden
    2688, a router over 128 experts with top-6, 64 HELD experts of width
    1856) on the decode scan's 256 rows and on a prompt chunk's 512: router,
    dispatch, both grouped GEMMs (Megablox, ragged last tiles: 1856 = 14.5 x
    128) and the combine, for the described chip."""
    from flexflow_tpu.core.op import OpContext
    from flexflow_tpu.serve.ssd_moe_ops import (MoECombine, MoEDispatch,
                                                MoEExperts, MoERouter)

    d, f, held, scored, k = 2688, 1856, 64, 128, 6

    def layer(x, gate, bias, up, down):
        ctx = lambda: OpContext(extras={"node_name": "n",
                                        "pallas_decode": True})
        ids, w = MoERouter(d, scored, k, 2.5, dtype=x.dtype).lower(
            ctx(), [x], {"weight": gate, "e_score_correction_bias": bias})
        xs, sizes, order = MoEDispatch(held).lower(ctx(), [x, ids], {})
        ys = MoEExperts(held, d, f, dtype=x.dtype,
                        num_scored=scored).lower(
            ctx(), [xs, sizes], {"up": up, "down": down})[0]
        return MoECombine(held, dtype=x.dtype).lower(
            ctx(), [ys, order, ids, w], {})[0]

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = jax.jit(layer).lower(
        sds((rows, d), jnp.bfloat16), sds((d, scored), jnp.float32),
        sds((scored,), jnp.float32), sds((held, d, f), jnp.bfloat16),
        sds((held, f, d), jnp.bfloat16)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2


@pytest.mark.parametrize("rows", [128, 512], ids=["scan128", "chunk512"])
def test_gated_routed_layer_compiles_for_v5e(one_chip, rows):
    """The routed-expert layer at Command A+'s published widths (hidden
    4096, a router over 128 experts with top-8 and no bias, 16 HELD gated
    experts of width 4096 — 100.7 MB each) on the decode scan's 128 rows and
    on a prompt chunk's 512: the three grouped GEMMs take the ``swiglu``
    tiles (the whole 4096 contraction beside a 512-wide output tile: 11 MB
    of the 16 MiB scoped VMEM; a 1024-wide one the compiler refuses)."""
    from flexflow_tpu.core.op import OpContext
    from flexflow_tpu.serve.ssd_moe_ops import (MoECombine, MoEDispatch,
                                                MoEExperts, MoERouter)

    d, f, held, scored, k = 4096, 4096, 16, 128, 8

    def layer(x, router, gate, up, down):
        ctx = lambda: OpContext(extras={"node_name": "n",
                                        "pallas_decode": True})
        ids, w = MoERouter(d, scored, k, dtype=x.dtype, bias=False).lower(
            ctx(), [x], {"weight": router})
        xs, sizes, order = MoEDispatch(held).lower(ctx(), [x, ids], {})
        ys = MoEExperts(held, d, f, dtype=x.dtype, form="swiglu",
                        num_scored=scored).lower(
            ctx(), [xs, sizes], {"gate": gate, "up": up, "down": down})[0]
        return MoECombine(held, dtype=x.dtype).lower(
            ctx(), [ys, order, ids, w], {})[0]

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = jax.jit(layer).lower(
        sds((rows, d), jnp.bfloat16), sds((d, scored), jnp.float32),
        sds((held, d, f), jnp.bfloat16), sds((held, d, f), jnp.bfloat16),
        sds((held, f, d), jnp.bfloat16)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3


@pytest.mark.parametrize("rows", [64, 512], ids=["scan64", "chunk512"])
def test_softmax_routed_layer_compiles_for_v5e(one_chip, rows):
    """The routed-expert layer at DeepSeek-V2-Lite's published widths
    (hidden 2048, a softmax router over 64 experts with top-6 un-normalised,
    ALL 64 gated experts of width 1408 = 11 x 128 held) on the decode scan's
    64 rows and on a prompt chunk's 512: the tiles ``MoEExperts.out_tile``
    plans from the shapes — 768 of 1408 into the hidden width (a ragged
    second tile), 1024 of 2048 out of it."""
    from flexflow_tpu.core.op import OpContext
    from flexflow_tpu.serve.ssd_moe_ops import (MoECombine, MoEDispatch,
                                                MoEExperts, MoERouter)

    d, f, held, k = 2048, 1408, 64, 6
    assert (MoEExperts.out_tile(d, f, 2), MoEExperts.out_tile(f, d, 2)) == \
        (768, 1024)

    def layer(x, router, gate, up, down):
        ctx = lambda: OpContext(extras={"node_name": "n",
                                        "pallas_decode": True})
        ids, w = MoERouter(d, held, k, dtype=x.dtype, bias=False,
                           norm_topk=False, scoring="softmax").lower(
            ctx(), [x], {"weight": router})
        xs, sizes, order = MoEDispatch(held).lower(ctx(), [x, ids], {})
        ys = MoEExperts(held, d, f, dtype=x.dtype, form="swiglu").lower(
            ctx(), [xs, sizes], {"gate": gate, "up": up, "down": down})[0]
        return MoECombine(held, dtype=x.dtype).lower(
            ctx(), [ys, order, ids, w], {})[0]

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = jax.jit(layer).lower(
        sds((rows, d), jnp.bfloat16), sds((d, held), jnp.float32),
        sds((held, d, f), jnp.bfloat16), sds((held, d, f), jnp.bfloat16),
        sds((held, f, d), jnp.bfloat16)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3


@pytest.mark.parametrize("rows", [16, 1024], ids=["scan16", "chunk1024"])
def test_mellum_routed_layer_compiles_for_v5e(one_chip, rows):
    """The routed-expert layer at Mellum 2's published widths (hidden 2304, a
    softmax router over 64 experts with top-8 renormalised, ALL 64 gated
    experts of width 896 = 7 x 128 held) on the decode scan's 16 rows and on
    a prompt chunk's 1024 (8 192 pairs: 128 rows an expert): the tiles
    ``MoEExperts.out_tile`` plans from the shapes — 896 whole into the hidden
    width, 2 tiles of 1152 out of it."""
    from flexflow_tpu.core.op import OpContext
    from flexflow_tpu.serve.ssd_moe_ops import (MoECombine, MoEDispatch,
                                                MoEExperts, MoERouter)

    d, f, held, k = 2304, 896, 64, 8
    assert (MoEExperts.out_tile(d, f, 2), MoEExperts.out_tile(f, d, 2)) == \
        (896, 1152)

    def layer(x, router, gate, up, down):
        ctx = lambda: OpContext(extras={"node_name": "n",
                                        "pallas_decode": True})
        ids, w = MoERouter(d, held, k, dtype=x.dtype, bias=False,
                           norm_topk=True, scoring="softmax").lower(
            ctx(), [x], {"weight": router})
        xs, sizes, order = MoEDispatch(held).lower(ctx(), [x, ids], {})
        ys = MoEExperts(held, d, f, dtype=x.dtype, form="swiglu").lower(
            ctx(), [xs, sizes], {"gate": gate, "up": up, "down": down})[0]
        return MoECombine(held, dtype=x.dtype).lower(
            ctx(), [ys, order, ids, w], {})[0]

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = jax.jit(layer).lower(
        sds((rows, d), jnp.bfloat16), sds((d, held), jnp.float32),
        sds((held, d, f), jnp.bfloat16), sds((held, d, f), jnp.bfloat16),
        sds((held, f, d), jnp.bfloat16)).compile()
    # the scan's 2 rows an expert: megablox's three calls; the chunk's 128:
    # the group-ahead plan's two (gate and up in one, the product inside)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= (3 if rows == 16 else 2)
    assert ("grouped_ffn_swiglu" in text) == (rows == 1024)


@pytest.mark.parametrize("m, d, f, e, tiles", [
    (8192, 2304, 896, 64, (896, 2304)),
    (4096, 4096, 4096, 16, (1024, 2048)),
], ids=["mellum_chunk_2304x896", "swiglu_4096x4096"])
def test_group_ahead_ffn_compiles_for_v5e(one_chip, m, d, f, e, tiles):
    """``grouped_ffn`` (ops/pallas/grouped_ffn.py) at the ``mellum`` chunk's
    shapes — 8192 sorted pairs on 64 experts of 2304 x 896: both matrices
    whole in each of two slots, 16.5 MB of weights beside the row tile —
    and at a wider ``swiglu`` whose two slots force column tiles: both
    kernels compile inside the VMEM limit the plan sets from its working
    set (the scoped default of 16 MiB would refuse either)."""
    from flexflow_tpu.ops.pallas import grouped_ffn as gf
    from flexflow_tpu.ops.pallas.attention import _VMEM_SCOPED_LIMIT

    assert (gf.out_tile(128, d, f, 2, 2, 2),
            gf.out_tile(128, f, d, 2, 4, 1)) == tiles
    assert gf.working_set(128, d, tiles[0], 2, 2, 2) > _VMEM_SCOPED_LIMIT

    def layer(xs, sizes, gate, up, down):
        h = gf.grouped_ffn(xs, (gate, up), sizes, form="swiglu",
                           out_dtype=xs.dtype)
        return gf.grouped_ffn(h, (down,), sizes, form="linear",
                              out_dtype=jnp.float32)

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    text = jax.jit(layer).lower(
        sds((m, d), jnp.bfloat16), sds((e,), jnp.int32),
        sds((e, d, f), jnp.bfloat16), sds((e, d, f), jnp.bfloat16),
        sds((e, f, d), jnp.bfloat16)).compile().as_text()
    assert "grouped_ffn_swiglu" in text and "grouped_ffn_linear" in text


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, nested ones too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
            continue
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns"):
                found += _pallas_calls(inner)
    return found


@pytest.mark.parametrize("rows", [64, 512], ids=["scan64", "flat512"])
def test_latent_decode_kernel_compiles_for_v5e(one_chip, rows):
    """``decode_attention`` over a LATENT cache at DeepSeek-V2-Lite's widths:
    16 query heads on one latent of 512 (key AND value: no V operand) beside
    a rotated key plane of 64 — not a lane multiple —, 65 cache rows of
    15 360 positions, bf16; the decode scan's 64 rows and a flat step's 512.
    One Mosaic kernel with ONE cache-sized operand per plane; the latents
    enter un-blocked (left in HBM: the kernel copies a row's live blocks
    itself), the rotated plane — which Mosaic lets no kernel slice by itself
    — in spans of 3072 positions; the ring of three blocks and the two spans
    in flight fit the default scoped VMEM with room."""
    import re

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    slots, s, h, r, dr = 64, 15360, 16, 512, 64
    f = functools.partial(decode_attention, scale=0.1147)
    call = lambda q, ckv, rw, ps, qr, kpe: f(q, ckv, None, rw, ps, q_rope=qr,
                                             k_rope=kpe)
    shapes = (
        sds((rows, h, r), jnp.bfloat16),
        sds((slots + 1, 1, s, r), jnp.bfloat16),
        sds((rows,), jnp.int32), sds((rows,), jnp.int32),
        sds((rows, h, dr), jnp.bfloat16),
        sds((slots + 1, 1, s, dr), jnp.bfloat16))
    kernel, = _pallas_calls(jax.make_jaxpr(call)(*shapes).jaxpr)
    assert kernel.params["jaxpr"].debug_info.func_name == \
        "_latent_decode_kernel"
    space = {tuple(bm.array_aval.shape):
             (str(bm.transformed_block_aval.memory_space),
              tuple(bm.transformed_block_aval.shape))
             for bm in kernel.params["grid_mapping"].block_mappings}
    assert space[(slots + 1, 1, s, r)] == ("any", (slots + 1, 1, s, r))
    assert space[(slots + 1, 1, s, dr)] == ("vmem", (1, 1, 3072, dr))
    text = jax.jit(call).lower(*shapes).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    call = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
            and "custom-call(" in ln][0]
    # the latent plane goes in ONCE: no second operand of its shape
    assert call.count(f"bf16[{slots + 1},1,{s},{r}]") == 1, call[:400]
    # no ``vmem_limit_bytes`` asked for; the ring (3 x 1 MB) and the
    # rotated plane's two spans (rows padded to 128 lanes: 2 x 768 KB)
    assert re.search(r'[^_]scoped_memory_configs":\[\]', call)
    used = [int(n) for n in re.findall(
        r'used_scoped_memory_configs":\[\{"memory_space":"1","offset":"0",'
        r'"size":"(\d+)"', call)]
    assert used and 5 * 2**20 < max(used) < 6 * 2**20


@pytest.mark.parametrize("variant", ["bf16", "ring512", "paged", "int8",
                                     "latent"])
def test_a_latent_cache_alone_leaves_the_shared_decode_kernel(variant):
    """K and V planes — plain, a ring, paged, int8 — trace ``_decode_kernel``
    and nothing else; a latent cache traces ``_latent_decode_kernel`` and
    nothing else; and the shared kernel holds no latent or rope branch."""
    import inspect

    from flexflow_tpu.ops.pallas import attention

    if variant == "latent":
        f = lambda q, c, rw, ps, qr, kr: decode_attention(
            q, c, None, rw, ps, 0.1, q_rope=qr, k_rope=kr)
        sds, s = jax.ShapeDtypeStruct, 1024
        args, kw = (sds((R, 16, 512), jnp.bfloat16),
                    sds((R + 1, 1, s, 512), jnp.bfloat16),
                    sds((R,), jnp.int32), sds((R,), jnp.int32),
                    sds((R, 16, 64), jnp.bfloat16),
                    sds((R + 1, 1, s, 64), jnp.bfloat16)), {}
    else:
        f, args, kw = _call("decode", None, 4, 4, 128, 1024, variant)
    kernels = [c.params["jaxpr"].debug_info.func_name for c in
               _pallas_calls(jax.make_jaxpr(f)(*args, **kw).jaxpr)]
    assert kernels == ["_latent_decode_kernel" if variant == "latent"
                       else "_decode_kernel"]
    shared = inspect.signature(attention._decode_kernel).parameters
    assert not {"latent", "rope"} & set(shared)
    assert "rope" in inspect.signature(
        attention._latent_decode_kernel).parameters


@pytest.mark.parametrize("form", ["slot_order", "chunked"])
def test_nemotron_ssd_scan_compiles_for_v5e(one_chip, form):
    """``Mamba2Scan`` at the published widths (64 heads of 64 in 8 groups,
    state 128; 257 state rows of 2 MB): the decode scan's 256 rows in slot
    order — ONE pass over the 539 MB state array, which the program's
    memory shows: no second array of its size among the temporaries — and a
    prompt chunk of 512 rows in the chunked form."""
    from flexflow_tpu.core.op import OpContext
    from flexflow_tpu.serve.batch_config import BatchConfig
    from flexflow_tpu.serve.ssd_moe_ops import Mamba2Scan

    h, p, g, n, slots = 64, 64, 8, 128, 256
    rows = slots if form == "slot_order" else 512
    op = Mamba2Scan(h, p, g, n, dtype=jnp.bfloat16)

    def scan(xbc, dt, ssd, request_index, position, a_log, dd, dt_bias):
        bc = BatchConfig(tokens=position, request_index=request_index,
                         token_position=position,
                         num_tokens=jnp.int32(rows),
                         seq_lens=jnp.zeros((slots,), jnp.int32))
        ctx = OpContext(extras={
            "node_name": "n", "batch_config": bc, "state": {"ssd": ssd},
            "one_row_per_request": form == "slot_order"})
        y = op.lower(ctx, [xbc, dt],
                     {"A_log": a_log, "D": dd, "dt_bias": dt_bias})[0]
        return y, ctx.extras["state_out"]["ssd"]

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    state = sds((slots + 1, h, p, n), jnp.float32)
    compiled = jax.jit(scan, donate_argnums=(2,)).lower(
        sds((rows, h * p + 2 * g * n), jnp.bfloat16),
        sds((rows, h), jnp.bfloat16), state, sds((rows,), jnp.int32),
        sds((rows,), jnp.int32), *([sds((h,), jnp.float32)] * 3)).compile()
    mem = compiled.memory_analysis()
    state_bytes = (slots + 1) * h * p * n * 4
    assert mem.alias_size_in_bytes >= state_bytes      # updated in place
    if form == "slot_order":
        assert mem.temp_size_in_bytes < state_bytes // 2


@pytest.mark.parametrize("rows", [256, 512], ids=["scan256", "chunk512"])
def test_kimi_routed_layer_compiles_for_v5e(one_chip, rows):
    """The routed-expert layer at Kimi-Linear's published widths (hidden
    2304, a sigmoid router with its correction bias over 256 experts with
    top-8 renormalised x 2.446, 32 HELD gated experts of width 1024) on the
    decode scan's 256 rows and on a prompt chunk's 512: the tiles
    ``MoEExperts.out_tile`` plans from the shapes — 512 of 1024 into the
    hidden width, 1152 of 2304 out of it, two tiles either way."""
    from flexflow_tpu.core.op import OpContext
    from flexflow_tpu.serve.ssd_moe_ops import (MoECombine, MoEDispatch,
                                                MoEExperts, MoERouter)

    d, f, held, scored, k = 2304, 1024, 32, 256, 8
    assert (MoEExperts.out_tile(d, f, 2), MoEExperts.out_tile(f, d, 2)) == \
        (512, 1152)

    def layer(x, router, bias, gate, up, down):
        ctx = lambda: OpContext(extras={"node_name": "n",
                                        "pallas_decode": True})
        ids, w = MoERouter(d, scored, k, 2.446, dtype=x.dtype).lower(
            ctx(), [x], {"weight": router, "e_score_correction_bias": bias})
        xs, sizes, order = MoEDispatch(held).lower(ctx(), [x, ids], {})
        ys = MoEExperts(held, d, f, dtype=x.dtype, form="swiglu",
                        num_scored=scored).lower(
            ctx(), [xs, sizes], {"gate": gate, "up": up, "down": down})[0]
        return MoECombine(held, dtype=x.dtype).lower(
            ctx(), [ys, order, ids, w], {})[0]

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = jax.jit(layer).lower(
        sds((rows, d), jnp.bfloat16), sds((d, scored), jnp.float32),
        sds((scored,), jnp.float32), sds((held, d, f), jnp.bfloat16),
        sds((held, d, f), jnp.bfloat16),
        sds((held, f, d), jnp.bfloat16)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3


@pytest.mark.parametrize("widths", ["kimi", "solar"])
@pytest.mark.parametrize("form", ["step_kernel", "chunked"])
def test_kimi_delta_attention_compiles_for_v5e(one_chip, form, widths):
    """``KimiDeltaAttention`` at the published widths — ``kimi``: 32 heads
    of 128 x 128 float32, 257 state rows of 2 MB, a prompt chunk of 512
    rows; ``solar``: 64 heads, 17 state rows of 4 MB, a chunk of 1024 —: the
    decode scan's rows through the step kernel ``delta_rule_step`` and a
    prompt chunk through the chunk kernel ``delta_rule_chunk`` — ONE Mosaic
    kernel each, the state array aliased in and out, and among the
    temporaries no second array of its size (``kimi``: under half of it;
    ``solar``, whose 71 MB state is smaller than the chunk's rows: under six
    ``[rows, heads, 128]`` float32 arrays — q, k, v, g and o in the kernel's
    layout, nothing of a piece's ``[32, 32, heads, 128]``)."""
    from flexflow_tpu.core.op import OpContext
    from flexflow_tpu.serve.batch_config import BatchConfig
    from flexflow_tpu.serve.hybrid_ops import KimiDeltaAttention

    e, h, slots, chunk = {"kimi": (2304, 32, 256, 512),
                          "solar": (4096, 64, 16, 1024)}[widths]
    d = 128
    rows = slots if form == "step_kernel" else chunk
    op = KimiDeltaAttention(e, h, d, dtype=jnp.bfloat16,
                            allow_neg_eigval=widths == "solar")
    names = [p.name for p in op.params()]

    def mix(qkv, x, kda, request_index, position, *weights):
        bc = BatchConfig(tokens=position, request_index=request_index,
                         token_position=position,
                         num_tokens=jnp.int32(rows),
                         seq_lens=jnp.zeros((slots,), jnp.int32))
        ctx = OpContext(extras={
            "node_name": "n", "batch_config": bc, "state": {"kda": kda},
            "pallas_decode": True,
            "one_row_per_request": form == "step_kernel"})
        y = op.lower(ctx, [qkv, x], dict(zip(names, weights)))[0]
        return y, ctx.extras["state_out"]["kda"]

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    weights = [sds(p.spec.shape, p.spec.dtype) for p in op.params()]
    compiled = jax.jit(mix, donate_argnums=(2,)).lower(
        sds((rows, 3 * h * d), jnp.bfloat16), sds((rows, e), jnp.bfloat16),
        sds((slots + 1, h, d, d), jnp.float32), sds((rows,), jnp.int32),
        sds((rows,), jnp.int32), *weights).compile()
    mem = compiled.memory_analysis()
    state_bytes = (slots + 1) * h * d * d * 4
    assert mem.alias_size_in_bytes >= state_bytes      # updated in place
    assert mem.temp_size_in_bytes < max(state_bytes // 2,
                                        6 * rows * h * d * 4)
    assert compiled.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("rows", [256, 512], ids=["scan256", "flat512"])
def test_latent_decode_kernel_on_32_heads_compiles_for_v5e(one_chip, rows):
    """The latent decode kernel at Kimi-Linear's latent layer: 32 query
    heads (twice DeepSeek-V2-Lite's head rows) on one latent of 512 beside a
    plain second key plane of 64, 257 cache rows of 10 240 positions, bf16;
    the decode scan's 256 rows and a flat step's 512: one Mosaic kernel,
    one cache-sized operand a plane."""
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    slots, s, h, r, dr = 256, 10240, 32, 512, 64
    call = lambda q, ckv, rw, ps, qr, kpe: decode_attention(
        q, ckv, None, rw, ps, scale=192 ** -0.5, q_rope=qr, k_rope=kpe)
    shapes = (
        sds((rows, h, r), jnp.bfloat16),
        sds((slots + 1, 1, s, r), jnp.bfloat16),
        sds((rows,), jnp.int32), sds((rows,), jnp.int32),
        sds((rows, h, dr), jnp.bfloat16),
        sds((slots + 1, 1, s, dr), jnp.bfloat16))
    kernel, = _pallas_calls(jax.make_jaxpr(call)(*shapes).jaxpr)
    assert kernel.params["jaxpr"].debug_info.func_name == \
        "_latent_decode_kernel"
    text = jax.jit(call).lower(*shapes).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    line = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
            and "custom-call(" in ln][0]
    assert line.count(f"bf16[{slots + 1},1,{s},{r}]") == 1, line[:400]


@pytest.mark.parametrize("c", [16, 32, 64])
def test_delta_rule_chunk_compiles_for_v5e(one_chip, c):
    """``delta_rule_chunk`` alone at Solar-Open2's widths — 1024 rows on 64
    heads of 128 x 128 float32 — at the piece in use (32) and its two
    neighbours: one Mosaic kernel inside the default scoped VMEM (a group of
    16 heads: the window's rows twice, the states, the outputs' two
    buffers), the state aliased, the pieces' count a DYNAMIC grid bound."""
    from flexflow_tpu.ops.pallas.delta_rule import delta_rule_chunk

    rows, h, d, slots = 1024, 64, 128, 16
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    call = lambda state, q, k, v, g, beta, count, *ps: delta_rule_chunk(
        state, q, k, v, g, beta, (count,) + ps, chunk=c)
    shapes = (sds((slots + 1, h, d, d), jnp.float32),
              *[sds((rows, h, d), jnp.float32)] * 4,
              sds((rows, h), jnp.float32), sds((), jnp.int32),
              *[sds((rows,), jnp.int32)] * 5)
    kernel, = _pallas_calls(jax.make_jaxpr(call)(*shapes).jaxpr)
    assert kernel.params["grid_mapping"].num_dynamic_grid_bounds == 1
    compiled = jax.jit(call, donate_argnums=(0,)).lower(*shapes).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        (slots + 1) * h * d * d * 4


@pytest.mark.parametrize("batch", ["prefill1024", "scan16"])
def test_solar_open2_gated_attention_compiles_for_v5e(one_chip, batch):
    """``IncMultiHeadSelfAttention`` at Solar-Open2's widths — 64 query heads
    of 128 on 8 K/V heads (a query group of 8), no rotation, a cache of
    24 832 positions a slot, the elementwise output gate (4096 -> 8192)
    under ``o_proj`` — on a prompt chunk of 1024 rows (the block write and
    ``prefill_attention``) and on the decode scan's 16 rows (the row write
    and ``decode_attention``): the caches updated in place, the gate
    noted among the paths."""
    from flexflow_tpu.core.op import OpContext
    from flexflow_tpu.serve.batch_config import (BatchConfig,
                                                 PrefillBatchConfig)
    from flexflow_tpu.serve.ops import IncMultiHeadSelfAttention

    e, qh, kv, d, slots, seq = 4096, 64, 8, 128, 16, 24832
    rows = 1024 if batch == "prefill1024" else slots
    op = IncMultiHeadSelfAttention(e, qh, kv, d, rotary_embedding=False,
                                   dtype=jnp.bfloat16, gate="elementwise")
    names = [p.name for p in op.params()]
    assert dict(zip(names, (p.spec.shape for p in op.params())))[
        "g_proj"] == (e, qh * d)
    paths = {}

    def attend(x, kc, vc, request_index, position, *weights):
        bc = BatchConfig(tokens=position, request_index=request_index,
                         token_position=position,
                         num_tokens=jnp.int32(rows),
                         seq_lens=jnp.zeros((slots,), jnp.int32))
        extras = {"node_name": "n", "state": {"k": kc, "v": vc},
                  "pallas_decode": True, "attention_paths": paths}
        if batch == "prefill1024":
            extras["batch_config"] = PrefillBatchConfig(base=bc,
                                                        tile_size=TILE)
        else:
            extras.update(batch_config=bc, one_row_per_request=True)
        ctx = OpContext(extras=extras)
        y = op.lower(ctx, [x], dict(zip(names, weights)))[0]
        out = ctx.extras["state_out"]
        return y, out["k"], out["v"]

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    cache = sds((slots + 1, kv, seq, d), jnp.bfloat16)
    weights = [sds(p.spec.shape, p.spec.dtype) for p in op.params()]
    lowered = jax.jit(attend, donate_argnums=(1, 2)).lower(
        sds((rows, e), jnp.bfloat16), cache, cache, sds((rows,), jnp.int32),
        sds((rows,), jnp.int32), *weights)
    compiled = lowered.compile()
    assert set(paths.values()) >= {"elementwise"}, paths   # the gate lowered
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2       # the write, the kernel
    mem = compiled.memory_analysis()
    cache_bytes = (slots + 1) * kv * seq * d * 2
    assert mem.alias_size_in_bytes >= 2 * cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes // 4


def test_solar_open2_routed_layer_compiles_for_v5e(one_chip):
    """The routed-expert layer at Solar-Open2's published widths (hidden
    4096, a sigmoid router with its correction bias over 320 experts with
    top-8 renormalised, 40 HELD gated experts of width 1280) on a prompt
    chunk's 1024 rows: 25.6 rows a held expert, under the grouped GEMM's row
    tile, so the chunk stays on megablox's ``gmm`` (PR 62's rule) with the
    tiles ``MoEExperts.out_tile`` plans from the shapes, neither of which
    divides its width: both GEMMs end in a ragged tile."""
    from flexflow_tpu.core.op import OpContext
    from flexflow_tpu.serve.ssd_moe_ops import (GMM_ROWS, MoECombine,
                                                MoEDispatch, MoEExperts,
                                                MoERouter)

    d, f, held, scored, k, rows = 4096, 1280, 40, 320, 8, 1024
    assert rows * k / scored < GMM_ROWS
    # 1280 in tiles of 512 (the last ragged: 256) into the hidden width,
    # 4096 in three of 1408 (the last ragged: 1280) out of it
    assert (MoEExperts.out_tile(d, f, 2), MoEExperts.out_tile(f, d, 2)) == \
        (512, 1408)
    paths = {}

    def layer(x, router, bias, gate, up, down):
        ctx = lambda: OpContext(extras={"node_name": "n",
                                        "pallas_decode": True,
                                        "attention_paths": paths})
        ids, w = MoERouter(d, scored, k, 1.0, dtype=x.dtype).lower(
            ctx(), [x], {"weight": router, "e_score_correction_bias": bias})
        xs, sizes, order = MoEDispatch(held).lower(ctx(), [x, ids], {})
        ys = MoEExperts(held, d, f, dtype=x.dtype, form="swiglu",
                        num_scored=scored).lower(
            ctx(), [xs, sizes], {"gate": gate, "up": up, "down": down})[0]
        return MoECombine(held, dtype=x.dtype).lower(
            ctx(), [ys, order, ids, w], {})[0]

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = jax.jit(layer).lower(
        sds((rows, d), jnp.bfloat16), sds((d, scored), jnp.float32),
        sds((scored,), jnp.float32), sds((held, d, f), jnp.bfloat16),
        sds((held, d, f), jnp.bfloat16),
        sds((held, f, d), jnp.bfloat16)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3
    assert set(paths.values()) == {"megablox_gmm"}
