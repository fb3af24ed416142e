"""Serving attention ops: incremental, speculative, and tree-verify MHA.

TPU-native re-design of the reference's serve hot path (reference:
``src/ops/inc_multihead_self_attention.{cc,cu}``,
``spec_inc_multihead_self_attention.cu``,
``tree_inc_multihead_self_attention.cu`` — fused QKV projection + RoPE +
KV-cache append + masked attention + output projection, with the KV cache
living in each op's ``IncMultiHeadSelfAttentionMeta``).

Design differences from the CUDA original, driven by TPU/XLA:

* One op class serves all three modes; the mode is picked by the *type* of the
  batch config shipped with the step (``BatchConfig`` → incremental,
  ``TreeSearchBatchConfig`` → draft-tree expansion,
  ``TreeVerifyBatchConfig`` → commit + tree-mask verification).  Each mode is
  a distinct static shape/program, so XLA compiles each exactly once — the
  analogue of the reference registering three task variants.
* The KV cache is functional state threaded through the jitted step (donated
  buffers), not a mutable ``OpMeta`` member.
* QKV is ONE fused weight in kv-head-major layout ``[embed, kv_heads,
  q_per_kv + 2, head_dim]``: a single MXU GEMM computes Q, K and V, and
  tensor parallelism is a plain shard of the ``kv_heads`` dim (GQA groups
  stay intact per shard).  The output projection is row-parallel; its result
  is marked a partial sum over the head axes so the PCG normalizer inserts
  the AllReduce — the same Megatron-style cut the reference reaches via its
  ``Reduction`` parallel op.
"""

from __future__ import annotations

import functools
import math
import warnings
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.graph import ParamSpec, TensorSpec
from ..core.op import Op, OpContext, ShardingSolution, bias_once, register_op
from ..core.sharding import TensorSharding
from .batch_config import (
    BatchConfig,
    PrefillBatchConfig,
    TreeSearchBatchConfig,
    TreeVerifyBatchConfig,
)

NEG_INF = -1e30


def _page_rows_pos(pages, rows, pos):
    """Translate LOGICAL cache coordinates (row, position) to PHYSICAL ones
    through a paged-KV block table (serve/kv_paged.py's ``PageTable``,
    shipped per step at ``ctx.extras["pages"]``).

    The physical buffers keep the slot-contiguous ``[R+1, KV, S, D]``
    shape; a page id addresses ``(row, page-slot) = divmod(pid,
    pages_per_row)``, so every existing write path (DUS chain, scatter,
    per-tile block write) runs unchanged on the translated coordinates —
    the indirection is pure index arithmetic, which is what makes the
    paged path bit-identical to the contiguous one.
    """
    ps, ppr = pages.page_size, pages.pages_per_row
    rows = jnp.clip(rows.astype(jnp.int32), 0, pages.table.shape[0] - 1)
    col = jnp.clip(pos.astype(jnp.int32) // ps, 0, ppr - 1)
    pid = pages.table[rows, col]
    return pid // ppr, (pid % ppr) * ps + pos.astype(jnp.int32) % ps


def _gather_logical_rows(cache, pages, rows):
    """``cache[rows]`` reconstructed through the block table: each token's
    LOGICAL cache row assembled from its physical pages ([T, KV, S(, D)]).
    The materialization cost matches the slot-contiguous gather fallback
    this replaces — it is the oracle path the Pallas kernels' in-VMEM
    indirection is tested against."""
    ps, ppr = pages.page_size, pages.pages_per_row
    r1 = cache.shape[0]
    pids = pages.table[jnp.clip(rows.astype(jnp.int32), 0,
                                pages.table.shape[0] - 1)]  # [T, ppr]
    prow, pslot = pids // ppr, pids % ppr
    if cache.ndim == 4:
        kvh, s, d = cache.shape[1:]
        cr = cache.reshape(r1, kvh, ppr, ps, d)
        # advanced indices split by a slice: indexed dims lead -> [T, ppr,
        # KV, ps, D]
        pg = cr[prow, :, pslot]
        return pg.transpose(0, 2, 1, 3, 4).reshape(rows.shape[0], kvh, s, d)
    kvh, s = cache.shape[1:]
    cr = cache.reshape(r1, kvh, ppr, ps)
    pg = cr[prow, :, pslot]                      # [T, ppr, KV, ps]
    return pg.transpose(0, 2, 1, 3).reshape(rows.shape[0], kvh, s)

# token-count cutoff between the per-token dynamic-update-slice chain and a
# single XLA scatter for KV-cache writes (see _scatter_rows_pos).  The
# switch is on the row count the write is TRACED at, not the live token
# count: max_tokens_per_batch in a flat step (a prefill chunk wants the
# scatter), one row per request slot in the decode scan (which compacts its
# batch: InferenceManager._decode_scan_impl), max_requests*(depth+1) in the
# spec scan.  Inside a scan the scatter's layout choice forces a per-step
# full-cache relayout, so SpecDecodeScan checks its width against it.
DUS_MAX_TOKENS = 128
# where the decode scan's rows stay on the chain (put_rows: the kernels off,
# a plane kv_row_write cannot take) it keeps the chain up to this many rows
# (one per slot): there the scatter's relayout copies the whole cache every
# step, which costs more than the longest chain; _chain_rows warns past it
SCAN_DUS_MAX_ROWS = 256


@jax.jit
def _update_rows(cache, rows, pos, upd):
    """``cache[rows[i], :, pos[i]] = upd[i]`` as a chain of in-place
    dynamic-update-slices, one per token (``cache`` [R, H, S, D] with
    ``upd`` [T, H, D], or a scale plane [R, H, S] with ``upd`` [T, H]).

    Jitted so that the chain is traced once per shape and lowered once per
    program, however many layers call it: a 36-layer model with 16 slots
    writes 1152 slices a decode-scan step, and unrolled into every
    caller's trace they tripled the scan programs' trace + lowering time
    (PERF.md section 6, PR 30).  XLA inlines the call, so the compiled
    program is the unrolled one."""
    zero = jnp.int32(0)
    for i in range(upd.shape[0]):
        start = (rows[i], zero, pos[i]) + (zero,) * (cache.ndim - 3)
        cache = jax.lax.dynamic_update_slice(
            cache, jnp.expand_dims(upd[i], (0, 2)), start)
    return cache


def tile_coords(rows, pos, tile, scratch):
    """A tiled chunk's per-row cache ``rows`` (pads on the ``scratch`` row,
    the largest index) and seq indices ``pos`` as per-tile ``(row, start,
    count)``: real rows sit at a tile's head, so ``min`` recovers its
    request (the scratch row for a fully-pad tile), its first row gives its
    start, and the rows off the scratch row are its count."""
    g = rows.shape[0] // tile
    rows = rows.reshape(g, tile)
    return (jnp.min(rows, axis=1), pos.reshape(g, tile)[:, 0],
            jnp.sum(rows != scratch, axis=1, dtype=jnp.int32))


def _tile_blocks(a, count, tile, dtype):
    """A chunk's rows ``[G * tile, H, ...]`` as head-major blocks ``[G, H,
    tile, ...]`` in ``dtype``, rows past ``count[g]`` (a tile's tail pads)
    as zeros."""
    g = a.shape[0] // tile
    b = jnp.swapaxes(a.reshape((g, tile) + a.shape[1:]), 1, 2).astype(dtype)
    real = jnp.arange(tile)[None, :] < count[:, None]            # [G, tile]
    return jnp.where(real.reshape((g, 1, tile) + (1,) * (a.ndim - 2)), b, 0)


@jax.jit
def _block_chain(cache, blocks, rows, start):
    """``cache[rows[g], :, start[g]:start[g] + tile] = blocks[g]`` as a chain
    of in-place dynamic-update-slices, one per tile (``cache`` [R, H, S, D]
    with ``blocks`` [G, H, tile, D], or a scale plane [R, H, S] with
    [G, H, tile]).  Traced once per shape, as :func:`_update_rows` is."""
    zero = jnp.int32(0)
    for i in range(blocks.shape[0]):
        at = (rows[i], zero, start[i]) + (zero,) * (cache.ndim - 3)
        cache = jax.lax.dynamic_update_slice(cache, blocks[i][None], at)
    return cache


def put_blocks(kc, vc, k, v, rows, start, count, tile, extras, wrap=None,
               aligned=True):
    """A tiled prefill chunk's fresh ``k`` / ``v`` ``[G * tile, H, D]`` into
    the caches ``[R + 1, H, S, D]``: tile ``g`` to row ``rows[g]`` from seq
    index ``start[g]`` on, its first ``count[g]`` rows as they are and its
    tail pads as zeros.  Returns the two caches.

    The callers' contract (``PrefillBatchConfig``; every tiled caller states
    it): a tile is one request's, starts on a multiple of ``tile``, and ``S``
    is whole tiles — a block never wraps or clamps.  A block write and not a
    scatter, because a chunk carries more than ``DUS_MAX_TOKENS`` rows and
    the scatter's layout choice forces a relayout copy of the whole cache per
    prefill-scan step (as :meth:`_scatter_rows_pos` says of the decode scan).

    ONE aliased Pallas call writes all tiles of both caches
    (``ops/pallas/attention.py`` ``kv_block_write``) where the kernels are on
    (``extras["pallas_decode"]``), a head is whole lanes (narrower, the TPU
    compiler re-lays the caches out around the call; the interpreter has no
    lanes) and the starts are ``aligned`` (a caller whose starts are whole
    tiles only at some sizes says at which: the kernel addresses the cache in
    tiles); else
    the chain of one ``dynamic_update_slice`` per tile and cache it replaced
    (17 us a 1 MB slice on the v5e, a tenth of the memory's pace).  The path
    taken is recorded in ``extras["attention_paths"]``.  ``wrap`` places the
    kernel under a caller's ``shard_map`` over the head axis.
    """
    from ..ops.pallas.attention import kv_block_write

    interp = bool(extras.get("pallas_interpret"))
    kernel = bool(extras.get("pallas_decode")) and aligned and (
        interp or kc.shape[-1] % 128 == 0)
    paths = extras.get("attention_paths")
    if paths is not None:
        paths[("kv_block_write", PrefillBatchConfig.__name__)] = \
            "pallas" if kernel else "dus_chain"
    if not kernel:
        return (_block_chain(kc, _tile_blocks(k, count, tile, kc.dtype),
                             rows, start),
                _block_chain(vc, _tile_blocks(v, count, tile, vc.dtype),
                             rows, start))
    write = functools.partial(kv_block_write, tile=tile, interpret=interp)
    return (wrap or (lambda f: f))(write)(kc, vc, k, v, rows, start, count)


def _chain_rows(extras, t):
    """The widest chain of update-slices a write of ``t`` rows may be
    (``_scatter_rows_pos``'s ``chain_rows``): as wide as the decode scan up
    to ``SCAN_DUS_MAX_ROWS``, past which the scan is warned of the scatter."""
    if not extras.get("one_row_per_request"):
        return None
    if t > SCAN_DUS_MAX_ROWS:
        warnings.warn(
            f"decode_scan writes {t} rows (one per request slot) > "
            f"{SCAN_DUS_MAX_ROWS} off the kv_row_write kernel: they take "
            "the scatter path and re-lay out the full cache every step",
            stacklevel=3)
    return SCAN_DUS_MAX_ROWS


def put_rows(kc, vc, k, v, rows, pos, extras, wrap=None):
    """A flat batch's fresh ``k`` / ``v`` ``[T, H, D]`` into their planes
    ``[R + 1, H, S, D]``: ``plane[rows[t], :, pos[t]] = x[t]``, cast to the
    plane's type, out-of-range coordinates clamped
    (:meth:`IncMultiHeadSelfAttention._scatter_rows_pos`).  Returns the two
    planes.  ``rows`` / ``pos`` are PHYSICAL under paging.

    Inside the decode scan (``extras["one_row_per_request"]``: a live slot's
    row is written at most once a step, pads land on the scratch row) ONE
    aliased Pallas call writes the rows of both planes
    (``ops/pallas/attention.py`` ``kv_row_write``) where the kernels are on
    (``extras["pallas_decode"]``) and a plane is 4-D, whole position groups
    (``row_write_group``) and whole lanes wide (narrower, the TPU compiler
    re-lays the caches out around the call; the interpreter has no lanes);
    two planes of one shape and type share the call, a plane the kernel
    cannot take (``deepseek_v2``'s 64-wide rotated part) stays on the chain
    beside it.  Else the chain of one ``dynamic_update_slice`` per row and
    plane (0.65-1.4 us each on the v5e whatever it moves), as wide as the
    scan up to ``SCAN_DUS_MAX_ROWS`` and ``DUS_MAX_TOKENS`` outside it, then
    a scatter: a flat step or the spec scan may write several positions of
    ONE row in a call, which the kernel's pipelined read-merge-write would
    lose.  The scan's path is recorded in ``extras["attention_paths"]``,
    a key per plane where the two differ.  ``wrap`` places the kernel under
    a caller's ``shard_map`` over the head axis.
    """
    from ..ops.pallas.attention import kv_row_write, row_write_group

    put = IncMultiHeadSelfAttention._scatter_rows_pos
    in_scan = bool(extras.get("one_row_per_request"))
    interp = bool(extras.get("pallas_interpret"))
    alike = (kc.shape, kc.dtype) == (vc.shape, vc.dtype)
    takes = [in_scan and bool(extras.get("pallas_decode"))
             and bool(row_write_group(c))
             and (interp or c.shape[-1] % 128 == 0) for c in (kc, vc)]
    if wrap is not None and not (alike and all(takes)):
        takes = [False, False]  # the caller's shard_map is of the one call
    paths = extras.get("attention_paths")
    if paths is not None and in_scan:
        for c, kernel in zip((kc, vc), takes):
            batch = "one_row_per_request" if takes[0] == takes[1] \
                else ("one_row_per_request", c.shape[-1])
            paths[("kv_row_write", batch)] = \
                "pallas" if kernel else "dus_chain"
    write = functools.partial(kv_row_write, interpret=interp)
    if alike and all(takes):
        return (wrap or (lambda f: f))(write)(kc, vc, k, v, rows, pos)
    chain_rows = _chain_rows(extras, k.shape[0])
    return tuple(
        write(c, None, x, None, rows, pos) if kernel
        else put(c, rows, pos, x, chain_rows)
        for c, x, kernel in zip((kc, vc), (k, v), takes))


def note_decode_block(extras, kind, batch, k_cache, **plan):
    """Record in ``extras["attention_paths"]`` the seq block
    ``decode_attention`` plans on ``k_cache`` for op ``kind`` under batch
    class ``batch`` — the ``attention_path.decode_block.*`` counter says
    which layers took a block grown by bytes and which the block by
    positions.  ``plan``: ``kv_quant`` / ``window`` / ``page_size`` as the
    kernel gets them; ``latent`` for a cache with no V plane."""
    paths = extras.get("attention_paths")
    if paths is not None:
        from ..ops.pallas.attention import decode_block_plan

        paths[("decode_block", (kind, batch))] = decode_block_plan(
            k_cache, **plan)


def note_prefill_operands(extras, kind, q, k_cache):
    """Record in ``extras["attention_paths"]`` the type
    ``prefill_attention``'s contractions take their operands in for op
    ``kind`` (``prefill_operand_dtype`` of the queries and the cache it is
    traced with) — the ``attention_path.prefill_operands.<dtype>`` counter:
    ``bfloat16`` in a bf16 model, ``float32`` only in a float32 one."""
    paths = extras.get("attention_paths")
    if paths is not None:
        from ..ops.pallas.attention import prefill_operand_dtype

        paths[("prefill_operands", kind)] = prefill_operand_dtype(
            q.dtype, k_cache.dtype).name


def alibi_slopes(num_heads: int) -> jax.Array:
    """ALiBi per-head slopes (Press et al.; matches HF's power-of-2 recipe)."""
    import math as _math

    n = 2 ** _math.floor(_math.log2(num_heads))
    base = jnp.arange(1, n + 1, dtype=jnp.float32)
    slopes = 2.0 ** (-8.0 * base / n)
    if n < num_heads:  # interleave the overflow heads at half offsets
        extra = jnp.arange(1, 2 * (num_heads - n) + 1, 2, dtype=jnp.float32)
        slopes = jnp.concatenate([slopes, 2.0 ** (-4.0 * extra / n)])
    return slopes[:num_heads]


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature term ``0.1 m ln s + 1`` (1 at ``s <=
    1``)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def rope_frequencies(half: int, theta: float, yarn: Optional[dict] = None):
    """The ``half`` pairs' turns per position, float32, computed once per
    trace: ``theta^(-i / half)``, or with ``yarn`` (a ``rope_scaling`` of
    type ``yarn``: ``factor`` over ``original_max_position_embeddings``,
    the ``beta_fast`` / ``beta_slow`` ramp) NTK-by-parts — pair ``i`` keeps
    its turn where it makes more than ``beta_fast`` rotations over the
    original context, takes ``1 / factor`` of it where fewer than
    ``beta_slow``, and a linear blend by pair index between the two
    (``low = floor(corr(beta_fast))``, ``high = ceil(corr(beta_slow))``,
    ``corr(r) = D ln(L / (2 pi r)) / (2 ln theta)``, clamped to ``[0, D -
    1]``).  The cos/sin factor ``yarn_mscale(s, mscale) / yarn_mscale(s,
    mscale_all_dim)`` is the caller's (1 where the two are equal)."""
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if not yarn:
        return freq
    d, factor = 2 * half, float(yarn["factor"])
    orig = float(yarn["original_max_position_embeddings"])
    corr = lambda r: d * math.log(orig / (2 * math.pi * r)) / (
        2 * math.log(theta))
    low = max(math.floor(corr(float(yarn.get("beta_fast", 32)))), 0)
    high = min(math.ceil(corr(float(yarn.get("beta_slow", 1)))), d - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return freq * (1.0 - ramp) + freq / factor * ramp


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               interleaved: bool = False,
               yarn: Optional[dict] = None) -> jax.Array:
    """Rotary embedding; x: [T, ..., D] with positions [T].  Pair ``i`` of
    the ``D / 2`` turns by ``position * theta^(-2 i / D)`` — or by YaRN's
    frequencies (``yarn``: :func:`rope_frequencies`) —: the pair is
    ``(x[i], x[i + D / 2])`` (half against half, GPT-NeoX's), or with
    ``interleaved`` ``(x[2 i], x[2 i + 1])`` (GPT-J's, ``rope_gptj``).
    Under ``yarn`` cos and sin are scaled by the ``attention_factor`` it
    STATES, else by ``yarn_mscale(s, mscale) / yarn_mscale(s,
    mscale_all_dim)``."""
    d = x.shape[-1]
    half = d // 2
    freq = rope_frequencies(half, theta, yarn)
    angles = positions.astype(jnp.float32)[:, None] * freq  # [T, half]
    # broadcast over middle dims
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos = jnp.cos(angles).reshape(shape)
    sin = jnp.sin(angles).reshape(shape)
    if yarn:
        scale = float(yarn.get("factor", 1.0))
        amp = yarn.get("attention_factor")
        if amp is None:
            amp = (yarn_mscale(scale, float(yarn.get("mscale", 1.0)))
                   / yarn_mscale(scale,
                                 float(yarn.get("mscale_all_dim", 0.0))))
        amp = float(amp)
        if amp != 1.0:
            cos, sin = cos * amp, sin * amp
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         axis=-1).reshape(x.shape).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


@register_op
class IncMultiHeadSelfAttention(Op):
    """KV-cached multi-head/grouped-query self-attention over flat token batches.

    Input:  ``x [max_tokens, embed_dim]`` (flat step tokens).
    Output: ``y [max_tokens, embed_dim]``.
    State:  ``k/v`` committed caches ``[max_requests+1, max_seq, kv_heads,
    head_dim]`` (row ``max_requests`` is the pad-token scratch row) and, when
    speculation is enabled, ``sk/sv`` spec-tree buffers
    ``[max_requests+1, max_spec, kv_heads, head_dim]``.

    ``gate`` (``solar_open2``'s ``use_gqa_gate``; None: no gate, the program
    before the option): an OUTPUT gate between the attention proper and
    ``W_o`` — ``y = o * sigmoid(x W_g)`` from the op's own input rows ``x``
    (the normed stream), ``W_g`` a parameter of its own (``g_proj``):
    ``"elementwise"`` ``[E, QH * D]``, one gate a channel of every head;
    ``"head"`` ``[E, QH]``, one gate a head, broadcast over its channels.
    The projection, the float32 sigmoid and the product lie under the stage
    ``o_proj`` in a nested scope ``gate`` — in every batch kind alike (flat
    step, tiled prefill, decode scan, tree verify: the gate is a row's own),
    so the prefill pipelining's carried q/k/v need nothing of it.
    ``g_proj`` is not among the weights ``quantize_int8`` replaces.
    """

    type_name = "inc_multihead_self_attention"
    stateful = True

    # KV-cache storage dtype override, registered by the InferenceManager
    # (``kv_dtype="int8"``): the committed k/v caches store int8 with
    # per-(row, head, position) f32 scales in sibling ``k_scale``/``v_scale``
    # buffers — quantize-on-write in the KV-update paths, dequant FUSED into
    # the Pallas kernels' score/value contractions (never a bf16 round trip
    # through HBM).  None = caches in the op's compute dtype.  The spec-tree
    # buffers (sk/sv) stay in the compute dtype: they hold <= max_spec
    # tokens per request and are rewritten every macro-step, so quantizing
    # them saves ~nothing; accepted speculative KV is quantized when
    # _commit() copies it into the committed cache.
    kv_dtype: Optional[str] = None

    # set by the InferenceManager on the graph's FIRST attention op when the
    # prefill software-pipelining prologue is recognized: lower() then takes
    # q/k/v from ctx.extras["qkv0"] (the scan carry) when present instead of
    # projecting — see project_qkv / InferenceManager._project_chunk0.
    qkv0_consumer: bool = False

    def __init__(
        self,
        embed_dim: int,
        num_q_heads: int,
        num_kv_heads: Optional[int] = None,
        head_dim: Optional[int] = None,
        rotary_embedding: bool = True,
        rope_theta: float = 10000.0,
        use_bias: bool = False,
        scaling_factor: Optional[float] = None,
        use_alibi: bool = False,
        dtype=jnp.float32,
        rope_scaling: Optional[dict] = None,
        gate: Optional[str] = None,
    ):
        if gate not in (None, "elementwise", "head"):
            raise ValueError("the attention's output gate is 'elementwise', "
                             f"'head' or none (gate {gate!r})")
        self.gate = gate
        # ``rope_scaling`` (type ``yarn``): the rotary turns by YaRN's
        # frequencies and amplitude (``apply_rope``'s ``yarn``); None: plain
        # ``rope_theta``
        if rope_scaling and rope_scaling.get(
                "rope_type", rope_scaling.get("type")) != "yarn":
            raise ValueError("the attention's rotary scaling is YaRN or none "
                             f"(rope_scaling {rope_scaling!r})")
        self.rope_scaling = dict(rope_scaling) if rope_scaling else None
        self.embed_dim = int(embed_dim)
        self.num_q_heads = int(num_q_heads)
        self.num_kv_heads = int(num_kv_heads or num_q_heads)
        self.head_dim = int(head_dim or embed_dim // num_q_heads)
        if self.num_q_heads % self.num_kv_heads:
            raise ValueError("num_q_heads must be a multiple of num_kv_heads")
        self.q_per_kv = self.num_q_heads // self.num_kv_heads
        self.rotary_embedding = bool(rotary_embedding)
        self.rope_theta = float(rope_theta)
        self.use_bias = bool(use_bias)
        self.use_alibi = bool(use_alibi)
        self.scaling_factor = (
            float(scaling_factor)
            if scaling_factor is not None
            else 1.0 / math.sqrt(self.head_dim)
        )
        self.dtype = jnp.dtype(dtype).name

    @property
    def gate_width(self) -> int:
        """Columns of ``g_proj``: a gate a channel, or a gate a head."""
        return self.num_q_heads * (self.head_dim
                                   if self.gate == "elementwise" else 1)

    def _gated(self, out, x, params):
        """``out [T, QH, D] * sigmoid(x W_g)`` (see the class docstring)."""
        with jax.named_scope("gate"):
            g = jax.nn.sigmoid(jnp.dot(x, params["g_proj"],
                                       preferred_element_type=jnp.float32))
            g = g.reshape(out.shape[0], self.num_q_heads, -1)
            return (out * g).astype(out.dtype)

    # ---- shapes / params ----------------------------------------------
    def infer_shapes(self, in_specs):
        x = in_specs[0]
        if x.shape[-1] != self.embed_dim:
            raise ValueError(f"expected embed_dim {self.embed_dim}, got {x}")
        return [TensorSpec(x.shape, jnp.dtype(self.dtype))]

    def params(self) -> List[ParamSpec]:
        g = self.q_per_kv + 2  # per kv group: q_per_kv query heads + K + V
        ps = [
            ParamSpec(
                "qkv",
                TensorSpec(
                    (self.embed_dim, self.num_kv_heads, g, self.head_dim),
                    jnp.dtype(self.dtype),
                ),
            ),
            ParamSpec(
                "o_proj",
                TensorSpec(
                    (self.num_q_heads * self.head_dim, self.embed_dim),
                    jnp.dtype(self.dtype),
                ),
            ),
        ]
        if self.gate:
            ps.append(ParamSpec("g_proj", TensorSpec(
                (self.embed_dim, self.gate_width), jnp.dtype(self.dtype))))
        if self.use_bias:
            ps.append(
                ParamSpec(
                    "qkv_bias",
                    TensorSpec(
                        (self.num_kv_heads, g, self.head_dim),
                        jnp.dtype(self.dtype),
                    ),
                )
            )
            ps.append(
                ParamSpec(
                    "o_bias",
                    TensorSpec((self.embed_dim,), jnp.dtype(self.dtype)),
                )
            )
        return ps

    # ---- state ---------------------------------------------------------
    def state_specs(
        self,
        max_requests: int,
        max_seq_len: int,
        max_spec_tokens: int = 0,
        head_axes: Tuple[str, ...] = (),
    ) -> Dict[str, Tuple[Tuple[int, ...], str, TensorSharding]]:
        """{name: (shape, dtype, sharding)} for this op's cache buffers.

        Caches are **kv-head-major** ``[rows, KV, S, D]`` so the Pallas
        decode kernel streams contiguous per-head blocks (see
        ``ops/pallas/attention.py``); the head shard axis is dim 1.
        """
        kv_shape = (max_requests + 1, self.num_kv_heads, max_seq_len, self.head_dim)
        sh = TensorSharding.from_axes(4, {1: head_axes} if head_axes else {})
        kv_dt = self.kv_dtype or self.dtype
        out = {
            "k": (kv_shape, kv_dt, sh),
            "v": (kv_shape, kv_dt, sh),
        }
        if kv_dt == "int8":
            # per-(row, head, position) f32 dequant scales; sharded over the
            # kv-head dim (dim 1) exactly like the caches they describe.
            # Zero-init (allocate_kv_cache zeros everything): an untouched
            # position dequantizes to 0 * 0 = 0, matching the fp cache's
            # zeros, so the tiled/flat write-path equivalence is preserved.
            sc_shape = kv_shape[:3]
            sc_sh = TensorSharding.from_axes(
                3, {1: head_axes} if head_axes else {}
            )
            out["k_scale"] = (sc_shape, "float32", sc_sh)
            out["v_scale"] = (sc_shape, "float32", sc_sh)
        if max_spec_tokens:
            sp_shape = (
                max_requests + 1,
                self.num_kv_heads,
                max_spec_tokens,
                self.head_dim,
            )
            out["sk"] = (sp_shape, self.dtype, sh)
            out["sv"] = (sp_shape, self.dtype, sh)
            if self.use_alibi:
                # absolute position of each spec-buffer slot (ALiBi needs key
                # positions; rope bakes them into sk at write time instead);
                # [rows, max_spec_tokens] — no head dim
                out["spec_pos"] = (
                    (sp_shape[0], sp_shape[2]), "int32",
                    TensorSharding.replicated(2),
                )
        return out

    # ---- compute -------------------------------------------------------
    def lower(self, ctx: OpContext, inputs, params):
        bc = ctx.extras.get("batch_config")
        state = ctx.extras.get("state")
        if bc is None or state is None:
            raise ValueError(
                f"{self.type_name} requires a batch_config and cache state "
                "(run it through the InferenceManager)"
            )
        x = inputs[0]  # [T, E]
        # cross-chunk software pipelining (InferenceManager.prefill_scan):
        # the FIRST attention op of the graph (qkv0_consumer, set by the
        # manager when the embedding->norm->attention prologue is
        # recognized) takes its q/k/v from the scan carry — the projection
        # was issued during the PREVIOUS chunk's step, so its weight fetch
        # can overlap that chunk's attention/MLP tail instead of stalling
        # at the while-loop iteration boundary.  The carried values are
        # computed by the same op lowers (_project_chunk0), so the paths
        # are bit-identical.
        pre = ctx.extras.get("qkv0") if self.qkv0_consumer else None
        if pre is not None:
            q, k, v = pre
        else:
            q, k, v = self.project_qkv(x, params, bc)

        # device-trace scopes: ``attend`` around the attention proper, and
        # inside it ``kv_write`` around every cache write (_write_kv, the
        # prefill path's block writes), so a trace tells the write's time
        # — copies the compiler inserts for it included — from the kernel's
        with jax.named_scope("attend"):
            if isinstance(bc, TreeVerifyBatchConfig):
                state = self._commit(state, bc,
                                     ctx.extras.get("pages") if ctx else None)
                out, state = self._tree_attend(q, k, v, state, bc, ctx)
            elif isinstance(bc, TreeSearchBatchConfig):
                out, state = self._tree_attend(q, k, v, state, bc, ctx)
            elif isinstance(bc, PrefillBatchConfig):
                out, state = self._prefill_attend(q, k, v, state, bc, ctx)
            else:
                out, state = self._inc_attend(q, k, v, state, bc, ctx)

        ctx.extras["state_out"] = state
        # [T, QH, D] -> [T, QH*D] -> o_proj (row-parallel under TP)
        t = out.shape[0]
        with jax.named_scope("o_proj"):
            if self.gate:
                out = self._gated(out, x, params)
                paths = ctx.extras.get("attention_paths")
                if paths is not None:   # beside the kernels' own notes
                    batch = "one_row_per_request" if ctx.extras.get(
                        "one_row_per_request") else type(bc).__name__
                    paths[("attention_gate", (self.type_name, batch))] = \
                        self.gate
            o_w = params["o_proj"]
            if o_w.dtype == jnp.int8:  # weight-only int8 (serve/quant.py)
                from .quant import dequant

                o_w = dequant(o_w, params["o_proj_scale"], out.dtype)
            y = jnp.dot(
                out.reshape(t, self.num_q_heads * self.head_dim),
                o_w,
                preferred_element_type=jnp.float32,
            )
            if self.use_bias:
                head = tuple(ctx.config.get("head", ())) if ctx.config else ()
                y = y + bias_once(params["o_bias"], head, ctx)
            return [y.astype(self.dtype)]

    def project_qkv(self, x, params, bc):
        """QKV projection (+ dequant + RoPE) for a step's flat tokens.

        The first stage of :meth:`lower`, also called by the
        InferenceManager's prefill software pipelining to issue the NEXT
        chunk's layer-0 projection inside the current scan step — one
        code path, so the pipelined and plain scans stay bit-identical.
        """
        with jax.named_scope("qkv_proj"):
            qkv_w = params["qkv"]
            if qkv_w.dtype == jnp.int8:  # weight-only int8 (serve/quant.py)
                from .quant import dequant

                qkv_w = dequant(qkv_w, params["qkv_scale"], x.dtype)
            return self._project(x, qkv_w, params.get("qkv_bias"), bc)

    def _project(self, x, qkv_w, qkv_b, bc):
        base = bc.base if not isinstance(bc, BatchConfig) else bc
        t = x.shape[0]
        # one MXU GEMM for Q,K,V: [T,E] x [E, KV, G, D] -> [T, KV, G, D]
        qkv = jnp.einsum(
            "te,ekgd->tkgd", x, qkv_w, preferred_element_type=jnp.float32
        ).astype(x.dtype)
        if qkv_b is not None:
            qkv = qkv + qkv_b
        q = qkv[:, :, : self.q_per_kv, :]          # [T, KV, Gq, D]
        k = qkv[:, :, self.q_per_kv, :]            # [T, KV, D]
        v = qkv[:, :, self.q_per_kv + 1, :]        # [T, KV, D]
        if self.rotary_embedding:
            pos = base.token_position
            q = apply_rope(q, pos, self.rope_theta, yarn=self.rope_scaling)
            k = apply_rope(k, pos, self.rope_theta, yarn=self.rope_scaling)
        return q, k, v

    def _rows(self, bc_base: BatchConfig, max_requests: int):
        """Cache row per flat token; pad tokens land in the scratch row."""
        r = bc_base.request_index
        return jnp.where(r >= 0, r, max_requests)

    @staticmethod
    def _scatter_rows_pos(cache, rows, pos, updates, chain_rows=None):
        """``cache[rows[t], :, pos[t]] = updates[t]`` without transposes
        (``chain_rows``: the widest chain, ``DUS_MAX_TOKENS`` if None).

        ``cache.at[rows, :, pos].set(...)`` is advanced indices split by a
        slice — NumPy semantics force jnp to transpose the whole cache to
        put the indexed dims together, which inside the decode scan copied
        the multi-GB cache every step.  A per-token ``dynamic_update_slice``
        chain updates in place AND is layout-agnostic: an XLA ``scatter``
        here makes layout assignment pick a non-default cache layout for
        the decode-scan carry, forcing a full-cache relayout copy per step
        to feed the Pallas kernel's default-layout operand.
        For large token counts (prefill chunks) the unrolled DUS chain would
        bloat compile time and serialize, so fall back to one XLA scatter —
        the layout concern only bites inside the decode/spec scans, whose
        batches are at most ``max_requests`` tokens (decode) or the commit
        descriptor's ``max_requests*(depth+1)`` entries (spec macro-step);
        the DUS_MAX_TOKENS threshold keeps both on the DUS path.
        cache: [R, H, S, D], updates: [T, H, D].
        """
        t = updates.shape[0]
        upd = updates.astype(cache.dtype)
        # Clip so both paths share the DUS path's clamped out-of-range
        # semantics: PROMISE_IN_BOUNDS on the scatter would otherwise be
        # undefined behavior for a hand-built BatchConfig with bad positions.
        rows = jnp.clip(rows.astype(jnp.int32), 0, cache.shape[0] - 1)
        pos = jnp.clip(pos.astype(jnp.int32), 0, cache.shape[2] - 1)
        if t > (chain_rows or DUS_MAX_TOKENS):
            idx = jnp.stack([rows, pos], axis=-1)
            dnums = jax.lax.ScatterDimensionNumbers(
                update_window_dims=(1, 2),
                inserted_window_dims=(0, 2),
                scatter_dims_to_operand_dims=(0, 2),
            )
            return jax.lax.scatter(
                cache, idx, upd, dnums,
                mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
            )
        return _update_rows(cache, rows, pos, upd)

    # ---- int8 KV cache (kv_dtype="int8") -------------------------------
    @staticmethod
    def _kv_quant(x):
        """Per-vector symmetric int8 quantization of fresh K/V entries.

        ``x``: [T, KV, D] compute-dtype vectors.  Returns ``(q int8[T,KV,D],
        scale f32[T,KV])`` with ``q * scale ~= x`` — one scale per (token,
        head) vector, the per-head variant the KV literature defaults to
        (per-channel would need static key statistics; per-vector absmax is
        exact-by-construction and costs 4 bytes per 2*D-byte pair).
        """
        xf = x.astype(jnp.float32)
        scale = jnp.max(jnp.abs(xf), axis=-1) / 127.0        # [T, KV]
        denom = jnp.where(scale > 0, scale, 1.0)
        q = jnp.clip(jnp.round(xf / denom[..., None]), -127, 127)
        return q.astype(jnp.int8), scale

    @staticmethod
    def _scatter_scale(cache, rows, pos, updates, chain_rows=None):
        """``cache[rows[t], :, pos[t]] = updates[t]`` for scale buffers.

        ``cache``: [R, KV, S] f32, ``updates``: [T, KV] — the 3-D sibling of
        :meth:`_scatter_rows_pos` (same DUS-vs-scatter reasoning and clamped
        out-of-range semantics).
        """
        t = updates.shape[0]
        upd = updates.astype(cache.dtype)
        rows = jnp.clip(rows.astype(jnp.int32), 0, cache.shape[0] - 1)
        pos = jnp.clip(pos.astype(jnp.int32), 0, cache.shape[2] - 1)
        if t > (chain_rows or DUS_MAX_TOKENS):
            idx = jnp.stack([rows, pos], axis=-1)
            dnums = jax.lax.ScatterDimensionNumbers(
                update_window_dims=(1,),
                inserted_window_dims=(0, 2),
                scatter_dims_to_operand_dims=(0, 2),
            )
            return jax.lax.scatter(
                cache, idx, upd, dnums,
                mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
            )
        return _update_rows(cache, rows, pos, upd)

    @jax.named_scope("kv_write")
    def _write_kv(self, state, rows, pos, k, v, pages=None, extras=None,
                  wrap=None):
        """Write this step's K/V vectors into the committed caches,
        quantizing on write when the caches are int8.  Returns the updated
        buffers as a dict of the state keys that changed.  ``extras`` /
        ``wrap``: see :func:`put_rows` (the decode scan's one aliased call,
        or its wider chain; None: a plain chain).  ``pages``
        (paged KV) translates the logical (row, position) coordinates to
        physical ones first — the scale planes ride the SAME translation,
        so int8 scales page alongside their K/V values."""
        extras = extras or {}
        if pages is not None:
            rows, pos = _page_rows_pos(pages, rows, pos)
        scales = {}
        if state["k"].dtype == jnp.int8:
            # the scale planes [R, KV, S] stay on the chain
            chain_rows = _chain_rows(extras, k.shape[0])
            k, ks = self._kv_quant(k)
            v, vs = self._kv_quant(v)
            scales = {n: self._scatter_scale(state[n], rows, pos, x,
                                             chain_rows)
                      for n, x in (("k_scale", ks), ("v_scale", vs))}
        kc, vc = put_rows(state["k"], state["v"], k, v, rows, pos, extras,
                          wrap)
        return {"k": kc, "v": vc, **scales}

    @staticmethod
    def _dequant_rows(cache_tok, sc_tok, dtype):
        """Gather-path dequant: ``cache_tok`` = the gathered [T, KV, S, D]
        int8 rows, ``sc_tok`` their [T, KV, S] scales gathered the same way
        (logical reconstruction under paging).  The materialization is
        acceptable here — this is the fallback/oracle path; the Pallas
        kernels fuse the same math in VMEM."""
        return (cache_tok.astype(jnp.float32)
                * sc_tok[..., None]).astype(dtype)

    @staticmethod
    def _gather_rows_pos(cache, rows, pos):
        """``[T, H, D] = cache[rows[t], :, pos[t]]`` (same no-transpose
        reasoning as :meth:`_scatter_rows_pos`)."""
        idx = jnp.stack(
            [jnp.clip(rows.astype(jnp.int32), 0, cache.shape[0] - 1),
             jnp.clip(pos.astype(jnp.int32), 0, cache.shape[2] - 1)], axis=-1
        )
        dnums = jax.lax.GatherDimensionNumbers(
            offset_dims=(1, 2),
            collapsed_slice_dims=(0, 2),
            start_index_map=(0, 2),
        )
        return jax.lax.gather(
            cache, idx, dnums,
            slice_sizes=(1, cache.shape[1], 1, cache.shape[3]),
            mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
        )

    def _head_shard_map(self, ctx, head_axes, in_specs, out_specs, what):
        """shard_map wrapper for a Pallas attention call under GSPMD.

        Returns the identity when the mesh is trivial (plain single-device
        call) and a ``shard_map`` partial over the kv-head axis when every
        non-trivial mesh axis is a head axis (Megatron serve TP: GQA groups
        stay intact per shard, so the kernel runs unchanged on local
        shapes).  A sharding the kernel cannot express is an ERROR on a TPU
        backend — the user asked for the kernels and cannot see them give
        way — naming ``what`` (the attention path) and the mesh axes; off
        the chip it returns ``None`` and the caller takes the gather path
        (the CPU tests' oracle).
        """
        mesh = ctx.mesh if ctx is not None else None
        if mesh is None or all(mesh.shape[a] == 1 for a in mesh.axis_names):
            return lambda f: f
        nontrivial = {a for a in mesh.axis_names if mesh.shape[a] > 1}
        if not head_axes or not nontrivial.issubset(set(head_axes)):
            if jax.default_backend() == "tpu":
                raise ValueError(
                    f"{self.type_name} {what}: the Pallas kernel shards "
                    f"over the kv-head axes {tuple(head_axes)} only "
                    f"({self.num_kv_heads} kv heads), but mesh axes "
                    f"{dict(mesh.shape)} are non-trivial; pass "
                    "use_pallas=False to serve this plan on the gather path")
            return None

        def wrap(f):
            return jax.shard_map(
                f, mesh=mesh, in_specs=tuple(in_specs),
                out_specs=out_specs, check_vma=False,
            )

        return wrap

    def _config_head_axes(self, ctx):
        return tuple(ctx.config.get("head", ())) if ctx and ctx.config else ()

    def _inc_attend(self, q, k, v, state, bc: BatchConfig, ctx=None):
        kc = state["k"]  # [R+1, KV, S, D]
        nreq = kc.shape[0] - 1
        rows = self._rows(bc, nreq)
        pos = bc.token_position
        pages = ctx.extras.get("pages") if ctx is not None else None
        in_scan = ctx is not None and ctx.extras.get("one_row_per_request")
        pallas = ctx is not None and ctx.extras.get("pallas_decode")
        h = self._config_head_axes(ctx)
        kv_sm = None
        if pallas:
            from jax.sharding import PartitionSpec as P

            from ..ops.pallas.attention import decode_attention

            kv_sm = self._head_shard_map(
                ctx, h, [P(None, h)] * 4 + [P()] * 2,
                (P(None, h), P(None, h)), "decode K/V row write")
        extras = ctx.extras if ctx is not None else None
        if pallas and kv_sm is None:
            # a sharding the kernel cannot express, off the chip: the chain
            extras = dict(extras, pallas_decode=False)
        writes = self._write_kv(state, rows, pos, k, v, pages, extras, kv_sm)
        kc, vc = writes["k"], writes["v"]
        kv_q = kc.dtype == jnp.int8
        if pallas:
            t = q.shape[0]
            interp = bool(ctx.extras.get("pallas_interpret"))
            # pad tokens (scratch row) otherwise stream a full cache row
            # each — their position is whatever the builder left there, and
            # the kernel's DMA clamp follows it; zero it so they fetch one
            # block (outputs are discarded anyway)
            pos = jnp.where(rows == nreq, 0, pos)
            slopes = alibi_slopes(self.num_q_heads).reshape(
                self.num_kv_heads, self.q_per_kv
            )  # [KV, gq]: shardable over the kv-head dim
            scales = (writes["k_scale"], writes["v_scale"]) if kv_q else ()
            pg = (pages.table,) if pages is not None else ()
            pg_size = pages.page_size if pages is not None else 0

            def attend(q_, kc_, vc_, rows_, pos_, slopes_, *rest):
                kv_l, gq = q_.shape[1], q_.shape[2]
                scales_ = rest[:len(scales)]
                pt_ = rest[len(scales)] if pg else None
                # of the shard's heads: the plan follows what ONE chip copies
                note_decode_block(
                    ctx.extras, self.type_name,
                    "one_row_per_request" if in_scan else type(bc).__name__,
                    kc_, kv_quant=kv_q, page_size=pg_size)
                return decode_attention(
                    q_.reshape(t, kv_l * gq, self.head_dim),
                    kc_, vc_, rows_, pos_,
                    scale=self.scaling_factor,
                    slopes=slopes_.reshape(-1) if self.use_alibi else None,
                    use_alibi=self.use_alibi, interpret=interp,
                    k_scale=scales_[0] if scales_ else None,
                    v_scale=scales_[1] if scales_ else None,
                    page_table=pt_, page_size=pg_size,
                ).reshape(t, kv_l, gq, self.head_dim)

            sm = self._head_shard_map(
                ctx, h,
                [P(None, h), P(None, h), P(None, h), P(), P(), P(h)]
                + [P(None, h)] * len(scales) + [P()] * len(pg),
                P(None, h), "decode attention",
            )
            if sm is not None:
                out = sm(attend)(q, kc, vc, rows, pos, slopes, *scales, *pg)
                out = out.reshape(t, self.num_q_heads, self.head_dim)
                new_state = dict(state)
                new_state.update(writes)
                return out, new_state
        # fallback: gather each token's cache row: [T, KV, S, D] (logical
        # reconstruction through the block table under paging)
        if pages is not None:
            k_tok = _gather_logical_rows(kc, pages, rows)
            v_tok = _gather_logical_rows(vc, pages, rows)
        else:
            k_tok = kc[rows]
            v_tok = vc[rows]
        if kv_q:  # dequant (the Pallas path fuses this in-kernel instead)
            ks_tok = (_gather_logical_rows(writes["k_scale"], pages, rows)
                      if pages is not None else writes["k_scale"][rows])
            vs_tok = (_gather_logical_rows(writes["v_scale"], pages, rows)
                      if pages is not None else writes["v_scale"][rows])
            k_tok = self._dequant_rows(k_tok, ks_tok, q.dtype)
            v_tok = self._dequant_rows(v_tok, vs_tok, q.dtype)
        s = k_tok.shape[2]
        # causal over absolute positions (covers prefill + decode uniformly)
        mask = jnp.arange(s)[None, :] <= pos[:, None]  # [T, S]
        scores = jnp.einsum(
            "tkgd,tksd->tkgs", q, k_tok, preferred_element_type=jnp.float32
        )
        scores = scores * self.scaling_factor
        if self.use_alibi:
            slopes = alibi_slopes(self.num_q_heads).reshape(
                self.num_kv_heads, self.q_per_kv
            )
            rel = (jnp.arange(s)[None, :] - pos[:, None]).astype(jnp.float32)
            scores = scores + slopes[None, :, :, None] * rel[:, None, None, :]
        scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum(
            "tkgs,tksd->tkgd", w, v_tok.astype(w.dtype),
            preferred_element_type=jnp.float32,
        )
        t = q.shape[0]
        out = out.reshape(t, self.num_q_heads, self.head_dim).astype(q.dtype)
        new_state = dict(state)
        new_state.update(writes)
        return out, new_state

    def _prefill_attend(self, q, k, v, state, bc: PrefillBatchConfig, ctx):
        """Prompt-phase attention over request-homogeneous query tiles.

        Routes to the Q-tiled Pallas prefill kernel (prefix blocks stream
        once per TILE, not once per token — see
        ``ops/pallas/attention.py:prefill_attention``); falls back to the
        flat gather path (``_inc_attend``) for ALiBi models or shardings
        the kernel can't express — the fallback is also the equality oracle
        the prefill tests compare against.
        """
        base = bc.base
        use_kernel = (
            ctx is not None
            and ctx.extras.get("pallas_decode")
            and not self.use_alibi
        )
        if not use_kernel:
            return self._inc_attend(q, k, v, state, base, ctx)
        from jax.sharding import PartitionSpec as P

        from ..ops.pallas.attention import prefill_attention

        kc, vc = state["k"], state["v"]
        nreq = kc.shape[0] - 1
        rows = self._rows(base, nreq)
        pos = base.token_position
        pages = ctx.extras.get("pages") if ctx is not None else None

        t = q.shape[0]
        bq = bc.tile_size
        g = t // bq
        interp = bool(ctx.extras.get("pallas_interpret"))
        kv_q = kc.dtype == jnp.int8
        h = self._config_head_axes(ctx)
        sm = self._head_shard_map(
            ctx, h,
            [P(None, h), P(None, h), P(None, h), P(), P()]
            + [P(None, h)] * (2 if kv_q else 0)
            + [P()] * (1 if pages is not None else 0),
            P(None, h), "prefill attention",
        )
        if sm is None:  # unsupported sharding off the chip: gather oracle
            return self._inc_attend(q, k, v, state, base, ctx)
        tile_rows, pstart, count = tile_coords(rows, pos, bq, nreq)
        with jax.named_scope("kv_write"):
            if pages is not None:
                # physical coordinates for the per-tile block write: a tile sits
                # inside ONE page (tile-aligned start, tile divides page — the
                # manager validates page % prefill_tile == 0), so translating
                # the tile's start translates the whole block
                w_rows, w_start = _page_rows_pos(pages, tile_rows, pstart)
            else:
                w_rows, w_start = tile_rows, pstart
            # the chunk's K/V go in as one BLOCK per tile (put_blocks says
            # why not a scatter).  PrefillBatchConfig's contract makes the
            # block write exact for real tokens: tile g is one request, its
            # positions contiguous from a TILE-ALIGNED pstart (RequestManager
            # only advances prefill_offset by whole tiles until completion), so
            # a block never clamps.  Tail-pad slots write ZEROS at the
            # request's next positions (junk-free: fresh caches are zeros, so
            # the tiled and flat paths stay bit-identical); even a non-zero
            # value there would be benign, since every future step WRITES
            # position p before any token's causal frontier reaches p.  A
            # fully-pad tile writes zeros to the scratch row.
            if kv_q:
                # quantize-on-write: the int8 VALUES ride the same block
                # write as the fp path; the per-(token, head) scales ride a
                # [1, KV, bq] block dynamic-update-slice per tile into the
                # scale planes (16 KB a tile).  Tile pads write value 0 AND
                # scale 0, so they dequantize to the zeros the fp path writes
                # (the tiled/flat bit-identity note above carries over to the
                # quantized representation).
                k, ks = self._kv_quant(k)   # int8 [T, KV, D], f32 [T, KV]
                v, vs = self._kv_quant(v)
                ksc = _block_chain(
                    state["k_scale"],                        # [R+1, KV, S]
                    _tile_blocks(ks, count, bq, jnp.float32), w_rows, w_start)
                vsc = _block_chain(
                    state["v_scale"],
                    _tile_blocks(vs, count, bq, jnp.float32), w_rows, w_start)
            kv_sm = self._head_shard_map(
                ctx, h, [P(None, h)] * 4 + [P()] * 3,
                (P(None, h), P(None, h)), "prefill K/V block write")
            kc, vc = put_blocks(kc, vc, k, v, w_rows, w_start, count, bq,
                                ctx.extras, wrap=kv_sm)
        scales = (ksc, vsc) if kv_q else ()
        pg = (pages.table,) if pages is not None else ()
        pg_size = pages.page_size if pages is not None else 0

        def attend(q_, kc_, vc_, rows_, pstart_, *rest):
            kv_l, gq = q_.shape[1], q_.shape[2]
            scales_ = rest[:len(scales)]
            pt_ = rest[len(scales)] if pg else None
            note_prefill_operands(ctx.extras, self.type_name, q_, kc_)
            return prefill_attention(
                q_.reshape(t, kv_l * gq, self.head_dim).reshape(
                    g, bq, kv_l * gq, self.head_dim
                ),
                kc_, vc_, rows_, pstart_,
                scale=self.scaling_factor, interpret=interp,
                k_scale=scales_[0] if scales_ else None,
                v_scale=scales_[1] if scales_ else None,
                page_table=pt_, page_size=pg_size,
            ).reshape(t, kv_l, gq, self.head_dim)

        out = sm(attend)(q, kc, vc, tile_rows, pstart, *scales, *pg)
        out = out.reshape(t, self.num_q_heads, self.head_dim)
        new_state = dict(state)
        new_state["k"], new_state["v"] = kc, vc
        if kv_q:
            new_state["k_scale"], new_state["v_scale"] = ksc, vsc
        return out, new_state

    def _commit(self, state, bc: TreeVerifyBatchConfig, pages=None):
        """Copy accepted speculative KV (spec buffer → committed cache).

        Reference: the ``committed_tokens`` handling at the top of
        ``tree_inc_multihead_self_attention.cu`` — the verified tokens of the
        previous macro-step become part of the causal past before the new
        tree is scored.
        """
        kc, sk, sv = state["k"], state["sk"], state["sv"]
        nreq = kc.shape[0] - 1
        rows = jnp.where(bc.commit_request_index >= 0, bc.commit_request_index, nreq)
        # _scatter/_gather_rows_pos clip rows/pos internally.  The spec
        # buffers hold compute-dtype KV; with an int8 committed cache,
        # _write_kv quantizes the accepted vectors here — the same
        # quantizer the incremental path applies, so a token's cache entry
        # is bit-identical whichever path wrote it.  The spec-buffer READ
        # stays slot-contiguous (sk/sv are never paged); only the committed
        # destination translates through the block table.
        src = bc.commit_src_spec_index
        dst = bc.commit_dst_position
        new_state = dict(state)
        new_state.update(self._write_kv(
            state, rows, dst,
            self._gather_rows_pos(sk, rows, src),
            self._gather_rows_pos(sv, rows, src),
            pages,
        ))
        return new_state

    def _tree_attend(self, q, k, v, state, bc, ctx=None):
        """Attend over committed cache (causal) + spec-tree buffer (ancestor mask).

        Used by both the draft model's expansion steps (SpecInc) and the
        LLM's verification step (TreeInc): the math is identical; only the
        batch-config contents differ.
        """
        base = bc.base
        kc, vc, sk, sv = state["k"], state["v"], state["sk"], state["sv"]
        nreq = kc.shape[0] - 1
        rows = self._rows(base, nreq)
        pages = ctx.extras.get("pages") if ctx is not None else None
        spec_idx = jnp.clip(bc.spec_index, 0, sk.shape[2] - 1)
        sk = self._scatter_rows_pos(sk, rows, spec_idx, k)
        sv = self._scatter_rows_pos(sv, rows, spec_idx, v)
        spec_pos = None
        if self.use_alibi:
            spec_pos = state["spec_pos"].at[rows, spec_idx].set(
                base.token_position
            )
        if (ctx is not None and ctx.extras.get("pallas_decode")
                and not self.use_alibi):
            from jax.sharding import PartitionSpec as P

            from ..ops.pallas.attention import (
                tree_attention,
                tree_attention_batched,
            )

            t = q.shape[0]
            interp = bool(ctx.extras.get("pallas_interpret"))
            # scratch-row (pad) tokens get a zero committed frontier so the
            # kernel's DMA clamp fetches one block for them, not the full
            # cache depth of whatever request the index clamp landed on
            clens = jnp.where(rows == nreq, 0, bc.committed_lens[rows])
            amask = bc.ancestor_mask[rows, spec_idx]
            # fixed [R, P] token layout (the on-device spec scan): all P
            # tree tokens of a request share one kernel grid row, so the
            # committed cache streams once per REQUEST, not once per token
            layout = ctx.extras.get("tree_layout")
            kv_q = kc.dtype == jnp.int8
            scales = (state["k_scale"], state["v_scale"]) if kv_q else ()
            pg = (pages.table,) if pages is not None else ()
            pg_size = pages.page_size if pages is not None else 0

            def attend(q_, kc_, vc_, sk_, sv_, rows_, clens_, amask_,
                       *rest):
                kv_l, gq = q_.shape[1], q_.shape[2]
                d = self.head_dim
                scales_ = rest[:len(scales)]
                pt_ = rest[len(scales)] if pg else None
                ks_ = scales_[0] if scales_ else None
                vs_ = scales_[1] if scales_ else None
                if layout:
                    r_t, p_t = layout
                    used = r_t * p_t
                    qf = q_.reshape(t, kv_l * gq, d)
                    ob = tree_attention_batched(
                        qf[:used].reshape(r_t, p_t, kv_l * gq, d),
                        kc_, vc_, sk_, sv_,
                        rows_[:used:p_t], clens_[:used:p_t],
                        amask_[:used].reshape(r_t, p_t, -1),
                        scale=self.scaling_factor, interpret=interp,
                        k_scale=ks_, v_scale=vs_,
                        page_table=pt_, page_size=pg_size,
                    ).reshape(used, kv_l * gq, d)
                    if used < t:  # capacity-pad tokens: outputs are ignored
                        ob = jnp.zeros((t, kv_l * gq, d), ob.dtype) \
                            .at[:used].set(ob)
                    return ob.reshape(t, kv_l, gq, d)
                return tree_attention(
                    q_.reshape(t, kv_l * gq, d),
                    kc_, vc_, sk_, sv_, rows_, clens_, amask_,
                    scale=self.scaling_factor, interpret=interp,
                    k_scale=ks_, v_scale=vs_,
                    page_table=pt_, page_size=pg_size,
                ).reshape(t, kv_l, gq, d)

            h = self._config_head_axes(ctx)
            sm = self._head_shard_map(
                ctx, h,
                [P(None, h)] * 5 + [P(), P(), P()]
                + [P(None, h)] * len(scales) + [P()] * len(pg),
                P(None, h), "tree-verify attention",
            )
            if sm is not None:
                out = sm(attend)(q, kc, vc, sk, sv, rows, clens, amask,
                                 *scales, *pg)
                out = out.reshape(t, self.num_q_heads, self.head_dim)
                new_state = dict(state)
                new_state["sk"], new_state["sv"] = sk, sv
                return out, new_state

        if pages is not None:  # logical reconstruction of committed rows
            k_cache_tok = _gather_logical_rows(kc, pages, rows)
            v_cache_tok = _gather_logical_rows(vc, pages, rows)
        else:
            k_cache_tok = kc[rows]   # [T, KV, S, D]
            v_cache_tok = vc[rows]
        if kc.dtype == jnp.int8:  # dequant (Pallas path fuses this instead)
            ks_tok = (_gather_logical_rows(state["k_scale"], pages, rows)
                      if pages is not None else state["k_scale"][rows])
            vs_tok = (_gather_logical_rows(state["v_scale"], pages, rows)
                      if pages is not None else state["v_scale"][rows])
            k_cache_tok = self._dequant_rows(k_cache_tok, ks_tok, q.dtype)
            v_cache_tok = self._dequant_rows(v_cache_tok, vs_tok, q.dtype)
        k_spec_tok = sk[rows]    # [T, KV, P, D]
        v_spec_tok = sv[rows]
        s = k_cache_tok.shape[2]

        # committed part: strictly below the committed frontier
        cmask = jnp.arange(s)[None, :] < bc.committed_lens[rows][:, None]
        # spec part: tree-topology ancestors (mask rows gathered per token)
        amask = bc.ancestor_mask[rows, spec_idx]  # [T, P]

        sc_c = jnp.einsum(
            "tkgd,tksd->tkgs", q, k_cache_tok, preferred_element_type=jnp.float32
        ) * self.scaling_factor
        sc_p = jnp.einsum(
            "tkgd,tkpd->tkgp", q, k_spec_tok, preferred_element_type=jnp.float32
        ) * self.scaling_factor
        if self.use_alibi:
            slopes = alibi_slopes(self.num_q_heads).reshape(
                self.num_kv_heads, self.q_per_kv
            )[None, :, :, None]
            qpos = base.token_position
            rel_c = (jnp.arange(s)[None, :] - qpos[:, None]).astype(jnp.float32)
            rel_p = (spec_pos[rows] - qpos[:, None]).astype(jnp.float32)
            sc_c = sc_c + slopes * rel_c[:, None, None, :]
            sc_p = sc_p + slopes * rel_p[:, None, None, :]
        sc_c = jnp.where(cmask[:, None, None, :], sc_c, NEG_INF)
        sc_p = jnp.where(amask[:, None, None, :], sc_p, NEG_INF)
        scores = jnp.concatenate([sc_c, sc_p], axis=-1)
        w = jax.nn.softmax(scores, axis=-1)
        v_all = jnp.concatenate([v_cache_tok, v_spec_tok], axis=2).astype(w.dtype)
        out = jnp.einsum(
            "tkgs,tksd->tkgd", w, v_all, preferred_element_type=jnp.float32
        )
        t = q.shape[0]
        out = out.reshape(t, self.num_q_heads, self.head_dim).astype(q.dtype)
        new_state = dict(state)  # k/v already carry any commit from _commit()
        new_state["sk"], new_state["sv"] = sk, sv
        if spec_pos is not None:
            new_state["spec_pos"] = spec_pos
        return out, new_state

    # ---- parallelization ----------------------------------------------
    def parallel_dims(self, in_specs):
        return {"sample": in_specs[0].shape[0], "head": self.num_kv_heads}

    def apply_config(self, config, in_specs, mesh, in_shardings=None):
        x = in_specs[0]
        head = tuple(config.get("head", ()))
        x_sh = TensorSharding.replicated(x.ndim)
        out_sh = TensorSharding.replicated(x.ndim)
        qkv_sh = TensorSharding.from_axes(4, {1: head} if head else {})
        o_sh = TensorSharding.from_axes(2, {0: head} if head else {})
        params = {"qkv": qkv_sh, "o_proj": o_sh}
        if self.gate:   # columns in head order, kv-head-major as the queries
            params["g_proj"] = TensorSharding.from_axes(
                2, {1: head} if head else {})
        if self.use_bias:
            params["qkv_bias"] = TensorSharding.from_axes(
                3, {0: head} if head else {}
            )
        if head:
            out_sh = out_sh.with_partial(head)
        return ShardingSolution(inputs=[x_sh], outputs=[out_sh], params=params)

    # cache depth used for costing; the InferenceManager sets this to its
    # max_seq_len at compile so the simulator sees the deployment's actual
    # attention span instead of a hard-coded constant (VERDICT r2 item 4)
    cost_seq_len: Optional[int] = None

    def flops(self, in_specs):
        t = in_specs[0].shape[0]
        e = self.embed_dim
        qh, d = self.num_q_heads, self.head_dim
        s = self.cost_seq_len or 1024
        proj = 2 * t * e * (qh + 2 * self.num_kv_heads) * d + 2 * t * qh * d * e
        if self.gate:
            proj += 2 * t * e * self.gate_width
        attn = 2 * t * qh * d * s * 2
        return proj + attn


@register_op
class PositionEmbedding(Op):
    """Learned absolute position embedding, positions from the BatchConfig.

    Reference: OPT/StarCoder serve graphs in ``inference/models/opt.cc`` /
    ``starcoder.cc`` feed per-token positions alongside token ids; here the
    positions already ride the step's BatchConfig, so this op needs no graph
    input — it adds ``weight[token_position + offset]`` (OPT uses offset 2).
    """

    type_name = "position_embedding"

    def __init__(self, num_positions: int, out_dim: int, offset: int = 0,
                 dtype=jnp.float32):
        self.num_positions = int(num_positions)
        self.out_dim = int(out_dim)
        self.offset = int(offset)
        self.dtype = jnp.dtype(dtype).name

    def infer_shapes(self, in_specs):
        x = in_specs[0]  # [T, E]: the token embedding to add to
        if x.shape[-1] != self.out_dim:
            raise ValueError(f"expected dim {self.out_dim}, got {x}")
        return [TensorSpec(x.shape, jnp.dtype(self.dtype))]

    def params(self):
        return [
            ParamSpec(
                "weight",
                TensorSpec(
                    (self.num_positions + self.offset, self.out_dim),
                    jnp.dtype(self.dtype),
                ),
            )
        ]

    def lower(self, ctx, inputs, params):
        bc = ctx.extras.get("batch_config")
        if bc is None:
            raise ValueError("position_embedding requires a batch_config")
        base = bc if isinstance(bc, BatchConfig) else bc.base
        pos = jnp.clip(
            base.token_position + self.offset, 0,
            self.num_positions + self.offset - 1,
        )
        return [inputs[0] + params["weight"][pos].astype(inputs[0].dtype)]

    def apply_config(self, config, in_specs, mesh, in_shardings=None):
        sh = TensorSharding.replicated(in_specs[0].ndim)
        return ShardingSolution(
            inputs=[sh], outputs=[sh],
            params={"weight": TensorSharding.replicated(2)},
        )


@register_op
class SpecIncMultiHeadSelfAttention(IncMultiHeadSelfAttention):
    """Parity alias: the draft model's tree-expansion attention.

    Reference: ``src/ops/spec_inc_multihead_self_attention.cu``.  Behavior is
    fully covered by :class:`IncMultiHeadSelfAttention` (mode dispatch on the
    batch-config type); the subclass exists so graphs read like the
    reference's and strategies can target it by type name.
    """

    type_name = "spec_inc_multihead_self_attention"


@register_op
class TreeIncMultiHeadSelfAttention(IncMultiHeadSelfAttention):
    """Parity alias: the verifier's tree-mask attention.

    Reference: ``src/ops/tree_inc_multihead_self_attention.cu``.
    """

    type_name = "tree_inc_multihead_self_attention"
