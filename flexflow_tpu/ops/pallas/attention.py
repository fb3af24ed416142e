"""Pallas TPU kernel: KV-cached decode attention (flash-style, flat tokens).

TPU-native replacement for the reference's fused decode-attention CUDA kernel
(reference: ``src/ops/inc_multihead_self_attention.cu`` — the per-token
"attend over my request's KV cache" hot loop).  The pure-JAX fallback in
:mod:`flexflow_tpu.serve.ops` gathers each token's full cache row
(``[T, KV, S, D]`` materialized in HBM); this kernel streams cache blocks
HBM→VMEM instead, with the per-token cache-row index scalar-prefetched so the
DMA pipeline knows where to fetch before the body runs.

Design (v2 — measured on a real v5e chip):
* cache layout is **kv-head-major**: ``[rows, KV, S, D]``.  A block is then
  ``[KV, Bs, D]`` with contiguous ``(sublane, lane)`` tiles per head, so the
  score/value contractions are single ``dot_general``s batched over the KV
  dim — no per-head slicing (which on the old ``[rows, S, KV, D]`` layout
  forced a strided relayout per head and cost ~2x).
* grid = (tokens, seq_blocks); seq is the minor (fastest) axis so the online
  softmax state (m/l/acc scratch) carries across a token's blocks.
* **causal DMA clamp**: the K/V index map clamps the block index to the
  token's causal frontier (``min(j, pos // block_s)``).  Pallas skips the
  copy when consecutive grid steps map to the same block, so blocks entirely
  in the future cost no HBM bandwidth — decode attention is bandwidth-bound,
  and this alone is worth ~2x at half-full caches.
* online softmax in f32; optional ALiBi bias (slopes passed in) so MPT-style
  models ride the same kernel.
* **the block is planned by bytes** (``_decode_plan``; the kernel alone on
  the v5e by block size: PERF.md section 6, PR 51): a grid step has a price
  of its own whatever it copies, so where a position's K is narrow the step
  copies more positions.  32 K/V heads of 128 move 2 MB of K at 256
  positions and keep that block; ONE K/V head (256 bytes of K a position)
  takes 2048 positions, two take 1024: 512 KB of K a copy.  A narrow ring,
  which is read whole once wrapped, is ONE block where K and V fit the VMEM
  budget twice.  The body is the same at every size: it scores the copied
  block whole.
* **fused int8-KV dequant**: when the cache is int8 with per-(row, head,
  position) f32 scales (``serve/ops.py`` quantize-on-write), the kernels take
  ``k_scale``/``v_scale`` operands ``[rows, KV, S]`` streamed in the same
  blocks as K/V and fold the dequant into the contractions — scores multiply
  by the key's scale after the Q·K dot, attention weights multiply by the
  value's scale before the P·V dot — so int8 KV never materializes as bf16
  in HBM; only int8 bytes (+ 4-byte scales per 2*D-byte vector pair) move.
* **paged KV (block-table indirection)**: with ``page_table`` (i32
  ``[rows, pages_per_row]``, scalar-prefetched) and a static ``page_size``,
  the cache's ``rows x seq`` space is a pool of fixed-size pages and a
  token's LOGICAL block ``j`` resolves to a physical page through its cache
  row's table entry — the vLLM/PagedAttention design
  (Kwon et al., SOSP'23) on the existing grid.  The kernel body is
  untouched: positions/masks stay logical, only the K/V (+ scale) index
  maps gather the page base per kv-chunk, so ``block_s`` is capped to
  divide ``page_size`` and a seq-block never straddles a page boundary.
  The causal DMA clamp composes: a clamped future block re-maps to the
  frontier's PHYSICAL page, whose copy Pallas then skips as before.

* **a latent cache** (``decode_attention(v_cache=None, q_rope=, k_rope=)``:
  multi-head latent attention, absorbed) has a kernel of its own,
  ``_latent_decode_kernel``: ONE latent per position is the key and the
  value of every head, beside a second, narrower key plane (the rotated
  part: score width ``D + Dr``, value width ``D``; 16 query heads on the one
  cached "head").  The latents stay in HBM and the kernel copies each row's
  LIVE blocks itself into a VMEM ring, the next copies in flight while a
  block is scored — across rows too — and the frontier block in
  128-position pieces: no grid step per block, no latent copied, awaited or
  stepped over past a row's frontier.  A block is copied once and used by
  both contractions; the online softmax is the one above.  The rotated
  plane, which Mosaic lets no kernel slice by itself (rows narrower than the
  lanes), rides the BlockSpec pipeline in spans of 3072 positions.

Under tensor parallelism the caller (serve/ops.py) wraps these kernels in a
``shard_map`` over the kv-head axis — the cache's head dim is the shard dim,
GQA groups stay intact per shard, so the kernel body is sharding-agnostic.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# VMEM budget for the K+V double-buffered block pipeline (bytes); the actual
# scoped limit is ~16MB but scratch + q/o blocks need room too.
_VMEM_BUDGET = 8 * 2**20


def _fit_block_s(block_s, s_len, num_kv, d, itemsize, kv_quant, budget):
    """Largest seq-block that keeps the double-buffered K+V (+ scale)
    pipeline under ``budget`` bytes and DIVIDES the cache seq length.

    block_s must divide s_len: for a short tail block Pallas clamps the
    block start (dynamic-slice semantics), so the kernel would read keys
    shifted from where ``base`` says they are — the causal mask can't fix
    aliased positions.  gcd keeps a dividing power-of-two when possible.
    """
    # bytes per cached position: K + V vectors, plus their two f32 scales
    # when the cache is int8 (fused-dequant operands ride the same pipeline)
    pos_bytes = 2 * num_kv * d * itemsize + (2 * num_kv * 4 if kv_quant else 0)
    while block_s > 128 and 2 * block_s * pos_bytes > budget:
        block_s //= 2
    block_s = min(block_s, s_len)
    if s_len % block_s:
        block_s = math.gcd(block_s, s_len)
    return block_s


# What ONE copy of K by a grid step of ``decode_attention`` should move where
# a layer's positions are narrow (one or two K/V heads of 128: 256 or 512
# bytes of K a position).  Chosen from the kernel alone on the v5e by block
# size, at three cells' decode shapes (``scripts/decode_kernel_bench.py``;
# PERF.md section 6, PR 51): at 128 KB of K a copy a grid step takes more
# than twice the time of its copy; from 512 KB (2048 positions on one head,
# 1024 on two) the copy is most of it, and larger blocks lose more to the
# block a row reads past its frontier (and to a pad row's one block) than
# they save in steps.
_COPY_TARGET_BYTES = 512 * 2**10


def _decode_plan(num_kv, d, itemsize, kv_quant, s_len, window=0,
                 page_size=0, block_s=None):
    """The seq block ONE grid step of :func:`decode_attention` copies and
    scores.

    The block by POSITIONS the kernel always had — 512, an eighth of a
    ring's window (at least 256), fitted to the VMEM budget and to ``s_len``
    (:func:`_fit_block_s`), inside one page when paged — is the plan
    wherever its copy of K is ``_COPY_TARGET_BYTES`` or more, and wherever
    the caller names a ``block_s``.  A narrower layer's block is planned by
    BYTES: the largest multiple of that block that still divides ``s_len``
    (the page, when paged) and, on a full cache, copies at most
    ``_COPY_TARGET_BYTES`` of K — a row reads up to a block past its
    frontier, and a pad row one whole block.  A RING is read whole once
    wrapped, whatever its blocks, so its block is bounded by
    ``_VMEM_BUDGET`` alone: the whole ring where its K and V fit twice (the
    compiler's scoped use is those buffers and ~0.3 MB: 5.05 MB for 4608
    slots of one head, tests/test_tpu_aot_compile.py).
    """
    block = block_s or 512
    if window:
        # finer blocks than a full cache's where the layer is wide: a window
        # of 512 in a ring of 1024 touches 3 blocks of 256 but all of 2
        # blocks of 512
        block = min(block, max(256, window // 8))
    block = _fit_block_s(block, s_len, num_kv, d, itemsize, kv_quant,
                         _VMEM_BUDGET)
    if page_size:
        # a seq-block must sit inside ONE page (page_size divides the padded
        # seq length by the allocator's construction-time assert, so the
        # gcd keeps a dividing block)
        block = math.gcd(block, page_size)
    k_pos = num_kv * d * itemsize
    if block_s or block * k_pos >= _COPY_TARGET_BYTES:
        return block
    if window:
        most = _VMEM_BUDGET // (4 * k_pos)  # K and V, two buffers each
    else:
        most = _COPY_TARGET_BYTES // k_pos
    span = page_size or s_len
    return max(m * block for m in range(1, span // block + 1)
               if span % (m * block) == 0 and (m == 1 or m * block <= most))


def decode_block_plan(k_cache, kv_quant=False, window=0, page_size=0,
                      latent=False):
    """The plan :func:`decode_attention` takes on this cache, as the
    ``attention_path.decode_block.*`` counter names it: ``ring4608`` (a ring
    copied whole), ``full2048``, ``full256``; ``live1024`` for a ``latent``
    cache, whose kernel copies a row's live blocks of 1024 itself."""
    _, num_kv, s_len, d = k_cache.shape
    if latent:
        return f"live{_latent_plan(s_len)[0]}"
    block = _decode_plan(num_kv, d, jnp.dtype(k_cache.dtype).itemsize,
                         kv_quant, s_len, window, page_size)
    return f"{'ring' if window else 'full'}{block}"


def _page_coords(pt, row, jc, block_s, page_size, ppr):
    """Physical (row, seq-block) coordinates of LOGICAL seq-block ``jc`` of
    cache row ``row`` through the page table — the one translation all
    three kernels' K/V index maps share.  ``block_s`` divides ``page_size``
    (the callers gcd-cap it), so a block never straddles two pages."""
    bpp = page_size // block_s
    pid = pt[row, jc // bpp]
    return pid // ppr, (pid % ppr) * bpp + jc % bpp


def _scale_plumbing(kv_map, num_kv, block_s, k_scale, v_scale):
    """BlockSpecs + operands for the int8-KV dequant scales (one shared
    construction for all three kernels).

    The [rows, KV, S] scale buffers stream in the same blocks as the K/V
    caches they describe, so their index map is the kernel's ``kv_map``
    minus its trailing head-dim coordinate — deriving it here keeps the
    causal-clamp logic in exactly one place per kernel.  Returns
    ``([], ())`` for fp caches (no scale operands).
    """
    if k_scale is None:
        return [], ()

    def scale_map(*args):
        return kv_map(*args)[:3]

    specs = [
        pl.BlockSpec((1, num_kv, block_s), scale_map, memory_space=pltpu.VMEM)
    ] * 2
    return specs, (k_scale.astype(jnp.float32), v_scale.astype(jnp.float32))


def _ring_blocks(pos, block_s, s_len, window):
    """Which seq-blocks of a RING cache a query at ``pos`` needs.

    A window layer's cache is a ring of ``s_len`` slots, position ``p`` at
    slot ``p % s_len``; a query at ``pos`` sees the ``min(pos + 1, window)``
    newest positions, a cyclic run of slots that ends at ``pos % s_len``.
    Returns ``(needed(j), first, newest)``: whether block ``j`` holds one of
    them, and the blocks of the run's oldest and newest slot.  Scalar
    arithmetic only: the index maps and the kernel body share it."""
    pm = pos % s_len
    nvalid = jnp.minimum(pos + 1, window)
    newest = pm // block_s
    first = ((pm - nvalid + 1) % s_len) // block_s

    def needed(j):
        # the youngest slot of a block that does not hold ``pm`` is its last
        youngest = jnp.where(newest == j, 0,
                             (pm - ((j + 1) * block_s - 1)) % s_len)
        return youngest < nvalid

    return needed, first, newest


def _decode_kernel(
    rows_ref,       # scalar prefetch: i32[T] cache row per token
    pos_ref,        # scalar prefetch: i32[T] absolute position per token
    *refs,          # [pt_ref (paged),] q_ref, k_ref, v_ref,
                    # [ks_ref, vs_ref,] slopes_ref, o_ref, m/l/acc scratch
    block_s: int,
    num_kv: int,
    gq: int,
    scale: float,
    use_alibi: bool,
    kv_quant: bool,
    paged: bool = False,
    window: int = 0,
    s_len: int = 0,
):
    if paged:
        # the page-table prefetch ref is consumed by the index maps only
        refs = refs[1:]
    q_ref, k_ref, v_ref, *rest = refs
    if kv_quant:
        # ks/vs: [1, KV, Bs] f32 per-position dequant scales, same block
        # index map as K/V
        ks_ref, vs_ref, slopes_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        slopes_ref, o_ref, m_ref, l_ref, acc_ref = rest
    t = pl.program_id(0)
    s = pl.program_id(1)
    last_s = pl.num_programs(1) - 1

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[t]
    base = s * block_s
    if window:
        # a ring: the block is worth computing if it holds a slot of the
        # window (the others' DMA was skipped by the index map)
        run = _ring_blocks(pos, block_s, s_len, window)[0](s)
    else:
        run = base <= pos  # blocks past the frontier: DMA already clamped

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)               # [KV, gq, D]
        k = k_ref[0].astype(jnp.float32)               # [KV, Bs, D]
        sc = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                                       # [KV, gq, Bs]
        if kv_quant:
            # fused dequant: q·(k_int8*ks) == (q·k_int8)*ks per key position
            sc = sc * ks_ref[0][:, None, :]

        key_pos = base + jax.lax.broadcasted_iota(
            jnp.int32, (num_kv, gq, block_s), 2
        )
        if use_alibi:
            slopes = slopes_ref[...][:, :, None].astype(jnp.float32)
            sc = sc + slopes * (key_pos - pos).astype(jnp.float32)
        if window:
            # ``key_pos`` is a ring SLOT here: it holds the position ``age``
            # back from ``pos``, in the window if that is one of the
            # min(pos + 1, window) newest
            age = pos % s_len - key_pos
            age = jnp.where(age < 0, age + s_len, age)
            seen = age < jnp.minimum(pos + 1, window)
        else:
            seen = key_pos <= pos
        sc = jnp.where(seen, sc, NEG_INF)

        m_prev = m_ref[:, :, 0:1]                       # [KV, gq, 1]
        m_cur = jnp.max(sc, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)                         # [KV, gq, Bs]
        # mask again post-exp: exp(NEG_INF - m) may not be exactly 0 when a
        # block is fully masked and m_new is NEG_INF (NEG_INF-NEG_INF = 0)
        p = jnp.where(seen, p, 0.0)

        l_new = alpha * l_ref[:, :, 0:1] + jnp.sum(p, -1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)                # [KV, Bs, D]
        pv = jax.lax.dot_general(
            # fused dequant: (p*vs)·v_int8 == p·(v_int8*vs); the softmax
            # denominator above uses the UNSCALED p
            p * vs_ref[0][:, None, :] if kv_quant else p,
            v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                               # [KV, gq, D]
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(s == last_s)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :, 0:1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_s", "use_alibi", "interpret",
                     "page_size", "window"),
)
def decode_attention(
    q: jax.Array,        # [T, QH, D] (RoPE already applied)
    k_cache: jax.Array,  # [R+1, KV, S, D] (current step's KV already written)
    v_cache: Optional[jax.Array],  # [R+1, KV, S, D]; None: a LATENT cache
    rows: jax.Array,     # i32[T] cache row per token
    positions: jax.Array,  # i32[T]
    scale: float,
    slopes: Optional[jax.Array] = None,  # [QH] alibi slopes
    block_s: Optional[int] = None,  # None: planned (_decode_plan)
    use_alibi: bool = False,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,  # [R+1, KV, S] int8-KV dequant
    v_scale: Optional[jax.Array] = None,  # scales (None = fp cache)
    page_table: Optional[jax.Array] = None,  # i32[R+1, S//page_size] paged KV
    page_size: int = 0,                      # static; 0 = slot-contiguous
    window: int = 0,     # static; > 0: the cache is a RING (see below)
    q_rope: Optional[jax.Array] = None,  # [T, QH, Dr] a latent score's 2nd
    k_rope: Optional[jax.Array] = None,  # term: [R+1, 1, S, Dr]
) -> jax.Array:
    """K and V planes of one head size ``D``, each read once, through the
    BlockSpec pipeline below — or, with ``v_cache=None``, a LATENT cache
    (multi-head latent attention in its absorbed form), which goes to a
    kernel of its own (:func:`_latent_decode`): ``k_cache`` holds one latent
    per position that is the key AND the value of all ``QH`` heads, and
    ``q_rope`` / ``k_rope`` add ``q_rope . k_rope`` to the score from a
    second, narrower plane (score width ``D + Dr``, value width ``D``).

    ``window > 0``: a sliding-window layer.  The cache's seq dim is then a
    ring — position ``p`` lives at slot ``p % S`` (``S`` at least the window
    plus the widest step that writes before it attends) — and a query at
    ``positions[i]`` sees the ``min(positions[i] + 1, window)`` newest
    positions.  Blocks that hold none of them are neither fetched (the index
    map sends them to a block that is) nor computed.

    ``block_s``: None plans the seq block from the bytes of a position's K
    (:func:`_decode_plan`); a number is the block by positions, fitted to
    the VMEM budget, to ``S`` and to the page, and never grown."""
    t, qh, d = q.shape
    _, num_kv, s_len, _ = k_cache.shape
    gq = qh // num_kv
    kv_quant = k_scale is not None
    paged = page_table is not None
    if window:
        if paged or use_alibi or kv_quant:
            raise ValueError("a ring cache is slot-contiguous, fp, and has "
                             "no positional bias")
    if v_cache is None:
        if paged or kv_quant or window or use_alibi or num_kv != 1:
            raise ValueError("a latent cache is slot-contiguous, fp, "
                             "full-length, one cached head, and has no "
                             "positional bias")
        return _latent_decode(q, k_cache, rows, positions, float(scale),
                              q_rope, k_rope, interpret)
    if k_rope is not None:
        raise ValueError("a second key plane belongs to a latent cache")
    block_s = _decode_plan(
        num_kv, d, jnp.dtype(k_cache.dtype).itemsize, kv_quant, s_len,
        window, page_size if paged else 0, block_s)
    n_blocks = s_len // block_s
    qr = q.reshape(t, num_kv, gq, d)
    if slopes is None:
        slopes = jnp.zeros((qh,), jnp.float32)
    slopes = slopes.astype(jnp.float32).reshape(num_kv, gq)

    if paged:
        ppr = s_len // page_size

        def kv_map(i, j, rows, pos, pt):
            # causal clamp in LOGICAL block space, then the page table
            # resolves the physical page (clamped blocks re-map to the
            # frontier's physical block, whose copy Pallas skips)
            jc = jnp.minimum(j, pos[i] // block_s)
            prow, pblk = _page_coords(pt, rows[i], jc, block_s, page_size,
                                      ppr)
            return (prow, 0, pblk, 0)

        prefetch = (rows.astype(jnp.int32), positions.astype(jnp.int32),
                    page_table.astype(jnp.int32))
    elif window:
        def kv_map(i, j, rows, pos):
            # a block outside the window re-maps to one inside it whose
            # copy Pallas then skips: the run's first block while the grid
            # has not reached the run, its newest block once past it
            needed, first, newest = _ring_blocks(pos[i], block_s, s_len,
                                                 window)
            skip_to = jnp.where(j > newest, newest, first)
            return (rows[i], 0, jnp.where(needed(j), j, skip_to), 0)

        prefetch = (rows.astype(jnp.int32), positions.astype(jnp.int32))
    else:
        def kv_map(i, j, rows, pos):
            # clamp to the causal frontier: future blocks re-map to the
            # frontier block, whose copy Pallas then skips (same index as
            # previous step)
            return (rows[i], 0, jnp.minimum(j, pos[i] // block_s), 0)

        prefetch = (rows.astype(jnp.int32), positions.astype(jnp.int32))

    scale_specs, scale_args = _scale_plumbing(
        kv_map, num_kv, block_s, k_scale, v_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(t, n_blocks),
        in_specs=[
            pl.BlockSpec(
                (1, num_kv, gq, d), lambda i, j, *_: (i, 0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, num_kv, block_s, d), kv_map, memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, num_kv, block_s, d), kv_map, memory_space=pltpu.VMEM,
            ),
            *scale_specs,
            pl.BlockSpec(
                (num_kv, gq), lambda i, j, *_: (0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, num_kv, gq, d), lambda i, j, *_: (i, 0, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((num_kv, gq, 128), jnp.float32),
            pltpu.VMEM((num_kv, gq, 128), jnp.float32),
            pltpu.VMEM((num_kv, gq, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel,
        block_s=block_s, num_kv=num_kv, gq=gq,
        scale=float(scale), use_alibi=use_alibi, kv_quant=kv_quant,
        paged=paged, window=window, s_len=s_len,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, num_kv, gq, d), q.dtype),
        interpret=interpret,
    )(*prefetch, qr, k_cache, v_cache, *scale_args, slopes)
    return out.reshape(t, qh, d)


# A latent cache's decode kernel (``_latent_decode_kernel``) copies a row's
# live latents itself: whole blocks of ``_LATENT_BLOCK`` positions into a
# VMEM ring of ``_LATENT_DEPTH`` slots, the frontier block in pieces of
# ``_LATENT_PIECE``.  The rotated key parts, a plane too narrow for a copy of
# the kernel's own (below), come through the BlockSpec pipeline in spans of
# ``_LATENT_SPAN`` positions.  Chosen from the kernel alone on the v5e at
# DeepSeek-V2-Lite's decode shape (``scripts/decode_kernel_bench.py --shapes
# doc-latent --stub``; PERF.md section 6, PR 55): the copies alone take 907 us
# a call whatever the block; the whole call 943 at 512 x 3, 909 at 1024 x 3,
# 903 at 1536 x 3, 960 at 1024 x 2.
_LATENT_BLOCK = 1024
_LATENT_DEPTH = 3
_LATENT_PIECE = 128
_LATENT_SPAN = 3072


def _latent_plan(s_len):
    """``(block, span)`` of :func:`_latent_decode` on a cache of ``s_len``
    positions: the block divides the span, the span divides ``s_len`` (the
    largest such multiple of the block up to ``_LATENT_SPAN``)."""
    block = math.gcd(_LATENT_BLOCK, s_len)
    if block % _LATENT_PIECE:
        raise ValueError("a latent cache holds whole pieces of "
                         f"{_LATENT_PIECE} positions")
    span = max(m * block for m in range(1, max(_LATENT_SPAN // block, 1) + 1)
               if s_len % (m * block) == 0)
    return block, span


def _latent_attend(q, c, rope, seen, m_ref, l_ref, acc_ref, scale):
    """One copied block ``c [block, D]`` of latents — key AND value, used
    twice from the one copy — under the running softmax of the queries ``q
    [H, D]``; ``rope``: None, or ``(q_r [H, Dr], k_r [block, Dr])`` whose
    dot joins the score.  Operands in the cache's type (bf16 products are
    exact in the float32 they accumulate in), float32 scores and statistics.
    ``seen [1, block]`` masks the frontier block's keys; None: the row sees
    them all."""
    nt = (((1,), (1,)), ((), ()))
    sc = jax.lax.dot_general(q, c, nt, preferred_element_type=jnp.float32)
    if rope is not None:
        sc = sc + jax.lax.dot_general(*rope, nt,
                                      preferred_element_type=jnp.float32)
    sc = sc * scale                                          # [H, block]
    if seen is not None:
        sc = jnp.where(seen, sc, NEG_INF)
    m_prev = m_ref[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(sc - m_new)
    if seen is not None:
        p = jnp.where(seen, p, 0.0)
    l_new = alpha * l_ref[:, 0:1] + jnp.sum(p, -1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                  # [H, D]
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)


def _latent_decode_kernel(
    rows_ref,       # scalar prefetch: i32[T] cache row per token
    pos_ref,        # scalar prefetch: i32[T] absolute position per token
    *refs,          # q_ref [1, H, D], [qr_ref [1, H, Dr],] c_hbm [R+1, 1, S,
                    # D] left in HBM, [kr_ref [1, 1, span, Dr],] o_ref [1, H,
                    # D], ring: VMEM [depth, block, D], sems: DMA semaphores
                    # [depth], cur: SMEM i32[4] — the fetch side's row and
                    # block, blocks fetched, blocks consumed (over the whole
                    # call) —, m/l/acc scratch
    rope: bool,
    block: int,
    span: int,
    depth: int,
    scale: float,
):
    if rope:
        q_ref, qr_ref, c_hbm, kr_ref, *rest = refs
    else:
        q_ref, c_hbm, *rest = refs
    o_ref, ring, sems, cur, m_ref, l_ref, acc_ref = rest
    t, g = pl.program_id(0), pl.program_id(1)
    n_rows = pl.num_programs(0)
    per_span = span // block

    def copy(source, slot, off, n, go):
        """Start (``go``) or await the copy of ``n`` latents, from ``off``
        into the block that begins at ``source`` (cache row, position), to
        the same offset of ring slot ``slot``."""
        row, start = source if go else (0, 0)
        cp = pltpu.make_async_copy(
            c_hbm.at[row, 0, pl.ds(pl.multiple_of(start + off, n), n)],
            ring.at[slot, pl.ds(off, n)], sems.at[slot])
        cp.start() if go else cp.wait()

    def frontier(source, slot, pos, go):
        """The frontier block's live pieces alone: a row reads at most
        ``_LATENT_PIECE - 1`` latents past its own."""
        for off in range(0, block, _LATENT_PIECE):
            pl.when(off <= pos % block)(functools.partial(
                copy, source, slot, off, _LATENT_PIECE, go))

    def fetch_next():
        """Start the call's next block — of this row, or the first of the
        row after it — into the slot freed longest ago."""
        row, b = cur[0], cur[1]

        @pl.when(row < n_rows)
        def _start():
            pos = pos_ref[row]
            last = b == pos // block
            source = rows_ref[row], b * block
            slot = cur[2] % depth
            pl.when(jnp.logical_not(last))(functools.partial(
                copy, source, slot, 0, block, True))
            pl.when(last)(functools.partial(frontier, source, slot, pos,
                                            True))
            cur[0] = row + last.astype(jnp.int32)
            cur[1] = jnp.where(last, 0, b + 1)
            cur[2] = cur[2] + 1

    @pl.when((t == 0) & (g == 0))
    def _open():
        # a slot's pieces past a frontier are multiplied by zero weights:
        # they must not hold a NaN's bit pattern
        ring[...] = jnp.zeros_like(ring)
        for i in range(4):
            cur[i] = 0
        for _ in range(depth - 1):
            fetch_next()

    @pl.when(g == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[t]
    last = pos // block              # the row's frontier block
    first = g * per_span             # this grid step's blocks begin here

    def one_block(j, seen):
        """Block ``first + j`` of the row: the next one in the ring."""
        fetch_next()                 # the queue stays ``depth`` blocks deep
        slot = cur[3] % depth
        if seen is None:
            copy(None, slot, 0, block, False)
        else:
            frontier(None, slot, pos, False)
        second = None
        if rope:
            kr = kr_ref[0, 0, pl.ds(pl.multiple_of(j * block, block), block)]
            second = qr_ref[0].astype(kr.dtype), kr
        _latent_attend(q_ref[0].astype(ring.dtype), ring[slot], second, seen,
                       m_ref, l_ref, acc_ref, scale)
        cur[3] = cur[3] + 1

    @pl.when(first <= last)          # a span past the frontier: nothing
    def _span():
        def interior(j, carry):
            one_block(j, None)
            return carry

        jax.lax.fori_loop(0, jnp.minimum(last - first, per_span), interior,
                          0)

        @pl.when(last < first + per_span)
        def _frontier():             # the causal mask is this block's alone
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
            one_block(last - first, last * block + lane <= pos)

    @pl.when(g == pl.num_programs(1) - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, 0:1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _latent_decode(q, ckv, rows, positions, scale, q_rope, k_rope,
                   interpret):
    """:func:`decode_attention` over a LATENT cache: ``ckv [R+1, 1, S, D]``
    holds one latent per position, key and value of all ``H`` heads of ``q
    [T, H, D]``; ``k_rope [R+1, 1, S, Dr]`` beside it (or None) adds
    ``q_rope . k_rope`` to the score.

    The latents — eight ninths of the bytes at 512 + 64 — stay in HBM and
    the kernel issues its own copies: a row's ``pos // block + 1`` LIVE
    blocks, each copied once into a VMEM ring of ``_LATENT_DEPTH`` slots
    (one DMA semaphore a slot) and used for the score and for the weighted
    sum.  The fetch side runs ``_LATENT_DEPTH - 1`` blocks ahead of the
    arithmetic ACROSS rows (its row and block ride in scalar memory): the
    next row's first block is on its way while this row's last is scored.
    The frontier block comes in ``_LATENT_PIECE``-position pieces, its live
    ones alone, and is the only block masked; a pad row (position 0) costs
    one piece.  No latent is copied, awaited or stepped over past a row's
    frontier.

    The rotated key parts cannot be copied so: Mosaic holds a plane whose
    rows are narrower than 128 lanes padded in HBM and refuses every slice
    of it in a copy of the kernel's own ("slice shape along dimension 3
    must be aligned to tiling (128), but is 64").  They come through the
    BlockSpec pipeline in SPANS of ``_LATENT_SPAN`` positions, clamped to
    the row's frontier as the K/V kernel's blocks are: the grid is ``(rows,
    S / span)`` — 5 steps a row at ``S`` 15 360 where the K/V kernel's grid
    takes 30 — and a grid step walks the ring for its span's live blocks."""
    t, h, d = q.shape
    s_len = ckv.shape[2]
    block, span = _latent_plan(s_len)
    rope = k_rope is not None
    row_spec = lambda width: pl.BlockSpec(
        (1, h, width), lambda i, j, *_: (i, 0, 0), memory_space=pltpu.VMEM)
    second = ()
    if rope:
        d_rope = k_rope.shape[-1]
        second = (row_spec(d_rope), pl.BlockSpec(
            (1, 1, span, d_rope),
            lambda i, j, rows, pos: (rows[i], 0,
                                     jnp.minimum(j, pos[i] // span), 0),
            memory_space=pltpu.VMEM))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t, s_len // span),
        in_specs=[row_spec(d), *second[:1],
                  pl.BlockSpec(memory_space=pl.ANY), *second[1:]],
        out_specs=row_spec(d),
        scratch_shapes=[
            pltpu.VMEM((_LATENT_DEPTH, block, d), ckv.dtype),
            pltpu.SemaphoreType.DMA((_LATENT_DEPTH,)),
            pltpu.SMEM((4,), jnp.int32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _latent_decode_kernel, rope=rope, block=block, span=span,
        depth=_LATENT_DEPTH, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, h, d), q.dtype),
        interpret=interpret,
    )(rows.astype(jnp.int32), positions.astype(jnp.int32), q,
      *([q_rope, ckv, k_rope] if rope else [ckv]))


def _sparse_decode_kernel(
    rows_ref,       # scalar prefetch: i32[T] cache row per token
    pos_ref,        # scalar prefetch: i32[T] absolute position per token
    blocks_ref,     # scalar prefetch: i32[T * KV * NB] attended blocks, sorted
    counts_ref,     # scalar prefetch: i32[T * KV] how many of them are real
    q_ref,          # [1, 1, gq, D]
    k_hbm,          # [(R+1) * KV * S / block, block, D], left in HBM: the
    v_hbm,          # kernel copies what the lists name
    o_ref,          # [1, 1, gq, D]
    k_ring,         # VMEM [depth * chunk, block, D]
    v_ring,         # VMEM [depth * chunk, block, D]
    sems,           # DMA semaphores [2, depth]: K and V of each ring slot
    cur,            # SMEM i32[4]: the fetch side's list and chunk, chunks
                    # fetched, chunks consumed (over the whole call)
    m_ref, l_ref, acc_ref,
    *,
    block: int,
    chunk: int,
    unroll: int,
    depth: int,
    list_len: int,
    num_kv: int,
    row_blocks: int,
    scale: float,
):
    t, g = pl.program_id(0), pl.program_id(1)
    here = t * num_kv + g            # this grid step's list
    n_lists = pl.num_programs(0) * num_kv

    def geometry(li, c):
        """Chunk ``c`` of list ``li``: chunks are cut from the list's END
        (the forced window is its last ``chunk`` entries), so the short one
        holds the list's first entries.  ``(lo, n, run)``: the entries
        ``[lo, lo + n)`` of the list, and whether their blocks are ``chunk``
        consecutive ones."""
        hi = counts_ref[li] - c * chunk
        lo = jnp.maximum(hi - chunk, 0)
        first = blocks_ref[li * list_len + lo]
        last = blocks_ref[li * list_len + jnp.maximum(hi - 1, 0)]
        run = (hi - lo == chunk) & (last - first == chunk - 1)
        return lo, hi - lo, run

    def copies(li, chunk_of, slot, go):
        """Start (``go``) or await the copies of one chunk (its
        ``geometry``) into a ring slot: ONE copy of K and one of V for a
        run, one per block otherwise.  A DMA semaphore counts bytes, so a
        whole chunk is awaited ONCE however many copies brought it."""
        lo, n, run = chunk_of
        entry = li * list_len + lo
        # the cache as blocks: this row and head's begin here
        origin = (rows_ref[li // num_kv] * num_kv + li % num_kv) * row_blocks
        to = slot * chunk

        def both(e, length):
            at = origin + blocks_ref[entry + e] if go else 0
            for ring, hbm, sem in ((k_ring, k_hbm, sems.at[0, slot]),
                                   (v_ring, v_hbm, sems.at[1, slot])):
                cp = pltpu.make_async_copy(
                    hbm.at[pl.ds(at, length)],
                    ring.at[pl.ds(to + e, length)], sem)
                cp.start() if go else cp.wait()

        full = n == chunk
        pl.when(run if go else full)(lambda: both(0, chunk))
        if go:
            @pl.when(full & jnp.logical_not(run))
            def _whole():            # straight-line: nothing to branch on
                for e in range(chunk):
                    both(e, 1)

        @pl.when(jnp.logical_not(full))
        def _short():
            def one(e, carry):
                both(e, 1)
                return carry

            jax.lax.fori_loop(0, n, one, 0)

    def fetch_next():
        """Start the next chunk the call will need — of this list, or of the
        first list after it that has one — into the slot freed longest ago."""
        def spent(s):
            li, c = s
            return (li < n_lists) & (
                c * chunk >= counts_ref[jnp.minimum(li, n_lists - 1)])

        li, c = jax.lax.while_loop(spent, lambda s: (s[0] + 1, 0),
                                   (cur[0], cur[1]))

        @pl.when(li < n_lists)
        def _start():
            copies(li, geometry(li, c), cur[2] % depth, go=True)
            cur[2] = cur[2] + 1

        cur[0] = li
        cur[1] = c + 1

    @pl.when(here == 0)
    def _open():
        # a slot's unfilled part is multiplied by zero weights: it must not
        # hold a NaN's bit pattern
        k_ring[...] = jnp.zeros_like(k_ring)
        v_ring[...] = jnp.zeros_like(v_ring)
        for i in range(4):
            cur[i] = 0
        for _ in range(depth - 1):
            fetch_next()

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    pos = pos_ref[t]
    q = q_ref[0, 0]                                          # [gq, D]
    d = q.shape[-1]

    def attend(at, blocks, seen):
        """``blocks`` ring blocks from ``at`` on under the running softmax;
        ``seen [1, blocks * block]`` masks their keys, None: the row sees
        them all."""
        k = k_ring[pl.ds(at, blocks)].reshape(blocks * block, d)
        v = v_ring[pl.ds(at, blocks)].reshape(blocks * block, d)
        # operands of one type as they are (bf16 products are exact in the
        # float32 they accumulate in), float32 otherwise
        qk = (q, k) if q.dtype == k.dtype else (q.astype(jnp.float32),
                                                k.astype(jnp.float32))
        sc = jax.lax.dot_general(
            *qk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [gq, blocks * block]
        if seen is not None:
            sc = jnp.where(seen, sc, NEG_INF)
        m_prev = m_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)
        if seen is not None:
            p = jnp.where(seen, p, 0.0)
        l_new = alpha * l_ref[:, 0:1] + jnp.sum(p, -1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [gq, D]
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    def one_chunk(c, carry):
        fetch_next()                 # the queue stays ``depth`` chunks deep
        slot = cur[3] % depth
        lo, n, run = geometry(here, c)
        copies(here, (lo, n, run), slot, go=False)
        entry = here * list_len + lo
        at = slot * chunk
        # the causal mask comes from each block's OWN position.  The list is
        # sorted: a whole chunk that ends before the row's own block needs
        # none; a run's positions are one ramp; any other chunk goes
        # ``unroll`` blocks at a time, each lane told how many of its
        # block's keys the row sees (0 for an entry past the chunk's end)
        clear = (n == chunk) & (blocks_ref[entry + chunk - 1] < pos // block)
        pl.when(clear)(lambda: attend(at, chunk, None))

        @pl.when(run & jnp.logical_not(clear))
        def _ramp():
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, chunk * block), 1)
            attend(at, chunk, blocks_ref[entry] * block + lane <= pos)

        def some(s):
            width = unroll * block
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
            sees = jnp.zeros((1, width), jnp.int32)
            for u in range(unroll):
                e = s * unroll + u
                b = blocks_ref[here * list_len
                               + jnp.minimum(lo + e, list_len - 1)]
                keys = jnp.where(e < n,
                                 jnp.clip(pos + 1 - b * block, 0, block), 0)
                sees = jnp.where(lane // block == u, keys, sees)
            attend(at + s * unroll, unroll, lane % block < sees)

        for s in range(chunk // unroll):
            pl.when(jnp.logical_not(clear | run) & (s * unroll < n))(
                functools.partial(some, s))
        cur[3] = cur[3] + 1
        return carry

    count = counts_ref[here]
    jax.lax.fori_loop(0, (count + chunk - 1) // chunk, one_chunk, 0)
    denom = jnp.maximum(l_ref[:, 0:1], 1e-30)
    o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


SPARSE_ROWS = 64    # rows per call: the block lists ride in scalar memory
_SPARSE_DEPTH = 3   # ring slots: chunks in flight while one is computed


@functools.partial(jax.jit, static_argnames=("scale", "block", "tail_run",
                                             "unroll", "interpret"))
def sparse_decode_attention(
    q: jax.Array,        # [T, QH, D]
    k_cache: jax.Array,  # [R+1, KV, S, D] (this step's K/V already written)
    v_cache: jax.Array,  # [R+1, KV, S, D]
    rows: jax.Array,     # i32[T] cache row per token
    positions: jax.Array,  # i32[T]
    blocks: jax.Array,   # i32[T, KV, NB]: the blocks each row and KV head
                         # attends, sorted and distinct up to ``counts``
    counts: jax.Array,   # i32[T, KV]
    scale: float,
    block: int = 64,
    tail_run: int = 32,
    unroll: int = 16,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention over a LIST of cache blocks per row and KV head (a
    learned sparse selection: ``serve/hybrid_ops.SparseBlockAttention``),
    where ``decode_attention`` reads a run.

    The caches stay in HBM and the kernel issues its own copies.  Grid
    ``(rows, KV heads)``; a grid step walks its list in CHUNKS of
    ``tail_run`` entries cut from the list's end, each into one slot of a
    VMEM ring (K and V, ``_SPARSE_DEPTH`` slots of ``tail_run`` blocks).  A
    chunk whose blocks are consecutive — the caller's forced window of the
    ``tail_run`` newest blocks by construction, and every whole chunk of a
    dense row — is ONE copy of K and one of V; any other chunk is a copy per
    block (a 64-position block of one head is 16 KB).  The lists ride in
    scalar memory, so the fetch side runs ``_SPARSE_DEPTH - 1`` chunks ahead
    of the arithmetic ACROSS lists: the next row-and-head's first chunks are
    on their way while this one's last is computed, and nothing is fetched
    or computed for a list's padding.  A whole chunk is one matrix product
    wide under one running softmax (float32 scores, statistics and
    accumulator); the causal mask is taken from each block's own position —
    none for a chunk that ends before the row's own block, a ramp for a
    run, per block (``unroll`` blocks at a time) for the rest.

    On the v5e a call of 48 rows x 2 heads x 97 blocks (65 single blocks
    and the window's run of 32; 305 MB) takes 0.48 ms, 0.43 with the
    arithmetic stubbed out — what the same bytes take at the 722 GB/s a
    dense list's four runs read (128 blocks: 0.56 ms); the ring's depth (2,
    3, 4) and ``unroll`` (4 .. 32) move it by under 3 %.  (An operand per
    block through the BlockSpec pipeline, 16 of K and 16 of V a grid step,
    took 1.44 ms, 1.14 of them with neither a copy nor arithmetic to do.)
    """
    t, qh, d = q.shape
    _, num_kv, s_len, _ = k_cache.shape
    gq = qh // num_kv
    if s_len % block:
        raise ValueError("the cache holds whole blocks")
    if t > SPARSE_ROWS and t % SPARSE_ROWS == 0:
        # a flat step's 512 rows: the lists of 64 rows at a time
        cut = lambda a: a.reshape((t // SPARSE_ROWS, SPARSE_ROWS)
                                  + a.shape[1:])
        out = jax.lax.map(
            lambda a: sparse_decode_attention(
                a[0], k_cache, v_cache, a[1], a[2], a[3], a[4], scale=scale,
                block=block, tail_run=tail_run, unroll=unroll,
                interpret=interpret),
            (cut(q), cut(rows), cut(positions), cut(blocks), cut(counts)))
        return out.reshape(t, qh, d)
    list_len = blocks.shape[-1]
    chunk = max(1, min(tail_run, list_len, s_len // block))
    unroll = math.gcd(unroll, chunk)
    slot_bytes = 2 * chunk * block * d * jnp.dtype(k_cache.dtype).itemsize
    if _SPARSE_DEPTH * slot_bytes > _VMEM_BUDGET:
        raise ValueError(f"a ring of {_SPARSE_DEPTH} chunks of {chunk} "
                         f"blocks does not fit {_VMEM_BUDGET} bytes")
    qr = q.reshape(t, num_kv, gq, d)
    q_spec = pl.BlockSpec((1, 1, gq, d), lambda i, g, *_: (i, g, 0, 0),
                          memory_space=pltpu.VMEM)
    ring = pltpu.VMEM((_SPARSE_DEPTH * chunk, block, d), k_cache.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(t, num_kv),
        in_specs=[q_spec, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=q_spec,
        scratch_shapes=[
            ring, ring,
            pltpu.SemaphoreType.DMA((2, _SPARSE_DEPTH)),
            pltpu.SMEM((4,), jnp.int32),
            pltpu.VMEM((gq, 128), jnp.float32),
            pltpu.VMEM((gq, 128), jnp.float32),
            pltpu.VMEM((gq, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _sparse_decode_kernel, block=block, chunk=chunk, unroll=unroll,
        depth=_SPARSE_DEPTH, list_len=list_len, num_kv=num_kv,
        row_blocks=s_len // block, scale=float(scale))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, num_kv, gq, d), q.dtype),
        interpret=interpret,
    )(rows.astype(jnp.int32), positions.astype(jnp.int32),
      blocks.astype(jnp.int32).reshape(-1),
      counts.astype(jnp.int32).reshape(-1), qr,
      k_cache.reshape(-1, block, d), v_cache.reshape(-1, block, d))
    return out.reshape(t, qh, d)


# Prefill streams K+V blocks against a Bq*gq-row query tile.  The grid
# carries a KV-HEAD-CHUNK axis: each grid step works on ``kv_chunk <= KV``
# heads, so the f32 score/softmax working set is [kv_chunk, Bq*gq, Bs] —
# chunking the heads (heads are independent softmaxes) is what lets the Q
# tile WIDEN (Bq up to 128 at the 7B shape, where the unchunked
# [32, 128, 128] plan counts 18 MiB) without shrinking the seq block below
# the DMA-efficient size.  This budget bounds the K+V block pipeline alone;
# the whole plan is then held to the compiler's limit below.
_VMEM_BUDGET_PREFILL = 4 * 2**20
# Scoped VMEM the TPU compiler grants one kernel (its default on v5e; it
# refuses the kernel outright beyond it).  :func:`_prefill_vmem_bytes`
# counts everything the compiler stacks against this number.
_VMEM_SCOPED_LIMIT = 16 * 2**20


def _prefill_vmem_bytes(kv_chunk, m_rows, block_s, d, q_itemsize,
                        kv_itemsize, kv_quant):
    """Scoped VMEM one prefill grid step needs, as the TPU compiler counts
    it: the double-buffered q and o blocks, the double-buffered K+V (+ int8
    scale) blocks, the m/l/acc scratch, and the f32 score and probability
    tiles the body materializes.  Minor dims pad to the 128-lane tile.

    Checked against the compiler's own totals for a described v5e
    (``used_scoped_memory_configs`` of the compiled call): 14.34 MiB at
    (kv_chunk 32, block 128) and 9.31 MiB at (16, 256) for m_rows 128, d
    128, bf16 — this returns 18 and 13 MiB; 6.74 MiB at (1, 256) for m_rows
    2816 against 12.6.  Those are PR 60's body, which keeps no float32 copy
    of K and V; with them the compiler counted 17.41 and 11.54 MiB (PR 24:
    the first is why the plan chunks the heads, and the plan is left as it
    is).  It errs high everywhere probed (the compiler keeps less than two
    full score tiles live), never low.
    """
    lanes = -(-d // 128) * 128
    qo = 2 * 2 * kv_chunk * m_rows * lanes * q_itemsize
    kv = 2 * 2 * kv_chunk * block_s * (lanes * kv_itemsize
                                       + (4 if kv_quant else 0))
    scratch = 4 * kv_chunk * m_rows * (128 + 128 + lanes)
    tiles = 2 * 4 * kv_chunk * m_rows * block_s
    return qo + kv + scratch + tiles


def _prefill_plan(num_kv, d, q_itemsize, kv_itemsize, kv_quant, m_rows,
                  block_s, s_len, kv_chunk=None):
    """(kv_chunk, block_s) for the prefill grid.

    Chooses the widest kv-head chunk (a divisor of ``num_kv``; ``kv_chunk``
    forces one) whose whole working set (:func:`_prefill_vmem_bytes`) fits
    the compiler's scoped-VMEM limit, with the seq block fitted under the
    K+V double-buffer budget at each candidate width
    (:func:`_fit_block_s`).  Wider Q tiles (m_rows) therefore trade
    head-parallelism per grid step for query rows.  At one head per step
    the seq block halves too (down to the 128-lane tile); when even that
    does not fit — ``m_rows`` = tile * gq is itself too large, the MQA
    geometry — no plan exists and the caller gets a ValueError at trace
    time instead of a kernel the compiler refuses.
    """
    def need(kc, bs):
        return _prefill_vmem_bytes(kc, m_rows, bs, d, q_itemsize,
                                   kv_itemsize, kv_quant)

    chunks = [kv_chunk] if kv_chunk else [
        c for c in range(num_kv, 0, -1) if num_kv % c == 0]
    for kc in chunks:
        bs = _fit_block_s(block_s, s_len, kc, d, kv_itemsize, kv_quant,
                          _VMEM_BUDGET_PREFILL)
        if kc == chunks[-1]:  # no narrower chunk left: give up seq block
            while need(kc, bs) > _VMEM_SCOPED_LIMIT and bs % 256 == 0:
                bs //= 2
        if need(kc, bs) <= _VMEM_SCOPED_LIMIT:
            return kc, bs
    raise ValueError(
        f"prefill_attention: no VMEM-admissible plan for KV={num_kv} "
        f"D={d} m_rows={m_rows} (query tile * q-heads per kv-head): "
        f"{need(kc, bs) / 2**20:.1f} MiB at kv_chunk={kc}, block_s={bs} "
        f"exceeds the {_VMEM_SCOPED_LIMIT >> 20} MiB scoped limit; this "
        "geometry needs a narrower query tile")


def prefill_operand_dtype(q_dtype, kv_dtype):
    """The type :func:`prefill_attention`'s two contractions take their
    operands in, from the types it is handed: a floating cache's own where
    the queries have it too (bf16 x bf16 in a bf16 model: the MXU's native
    width; a product of two bf16 values is exact in the float32 it
    accumulates in), the wider of the two where they differ, and the
    queries' on an int8 cache (|x| <= 127 is exact in bf16)."""
    if not jnp.issubdtype(kv_dtype, jnp.floating):
        return jnp.dtype(q_dtype)
    return jnp.promote_types(q_dtype, kv_dtype)


def _lanes_to(x, n):
    """``x [..., L]`` whose lanes all hold their row's one value, as ``[...,
    n]``: the first ``n`` lanes, or whole copies side by side — no
    cross-lane broadcast."""
    lanes = x.shape[-1]
    if n <= lanes:
        return x[..., :n]
    if n % lanes == 0:
        return jnp.concatenate([x] * (n // lanes), axis=-1)
    return jnp.broadcast_to(x[..., 0:1], x.shape[:-1] + (n,))


def _prefill_kernel(
    rows_ref,       # scalar prefetch: i32[G] cache row per tile
    pstart_ref,     # scalar prefetch: i32[G] first position in tile
    fmax_ref,       # scalar prefetch: i32[G] causal frontier (last position)
    *refs,          # [pt_ref (paged),] q_ref ([1, KC, M, D] tile queries,
                    # M = Bq*gq b-major fold), k_ref/v_ref ([1, KC, Bs, D]
                    # cache blocks), [ks_ref, vs_ref,] o_ref, m/l/acc scratch
    block_s: int,
    num_kv: int,    # heads PER GRID STEP (= kv_chunk)
    gq: int,
    m_rows: int,
    scale: float,
    kv_quant: bool,
    paged: bool = False,
    window: int = 0,
    s_len: int = 0,
):
    """One (tile, head chunk, seq block) step of :func:`prefill_attention`:
    per live block only the arithmetic that block needs.

    The running max and sum stay LANE-REPLICATED — ``m_ref`` / ``l_ref``
    ``[KC, M, 128]`` hold a row's value in every lane, are read whole, and
    ``alpha`` is computed on all 128 lanes — so a step does two cross-lane
    reductions (the block's max and sum) and nothing else across lanes: no
    one-lane slice of the scratch, no broadcast of ``m``, ``alpha`` or ``l``
    back over the lanes (``_lanes_to`` takes lanes or lays copies side by
    side).  With ``M`` = 128-2816 rows those were what a step waited for on
    the v5e (2.9 of a live step's 4.5 us at the 7B shape, 5 of 8 us on one
    K/V head: section 6 of PERF.md, PR 60); the values are the same numbers.

    Both contractions take their operands in
    :func:`prefill_operand_dtype`'s type — q, K and V as they arrive in a
    bf16 model, ``p`` cast to it for p.v as ``_latent_attend`` does — and
    accumulate in float32; scores, the running max, ``alpha``, ``l`` (the
    sum of the float32 ``p``) and the accumulator are float32.  A float32
    cache therefore runs float32 operands throughout.

    The causal mask is built only where a block needs it: a block that ends
    at or before the tile's first position (``base + block_s - 1 <=
    pstart``) is seen whole by every query of the tile and runs without the
    two iotas and the two ``where``s; the blocks from there to the frontier
    keep them.  Masking an all-seen block is the identity, so the split
    changes no bit.  A ring's age mask (``window > 0``) stays on every
    block.
    """
    if paged:
        refs = refs[1:]  # page table: index-map-only prefetch operand
    q_ref, k_ref, v_ref, *rest = refs
    if kv_quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    g = pl.program_id(0)
    # grid axis 1 is the kv-head chunk (independent softmaxes, so the
    # m/l/acc scratch simply re-initializes at s == 0 of every chunk);
    # axis 2 (seq) stays minor so the online-softmax state carries across
    # a (tile, head-chunk)'s blocks
    s = pl.program_id(2)
    last_s = pl.num_programs(2) - 1
    d = acc_ref.shape[-1]

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    fmax = fmax_ref[g]
    pstart = pstart_ref[g]
    base = s * block_s
    operand = prefill_operand_dtype(q_ref.dtype, k_ref.dtype)

    def attend(masked):
        sc = jax.lax.dot_general(
            q_ref[0].astype(operand),                   # [KV, M, D]
            k_ref[0].astype(operand),                   # [KV, Bs, D]
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                                       # [KV, M, Bs]
        if kv_quant:  # fused dequant (see _decode_kernel)
            sc = sc * ks_ref[0][:, None, :]

        if masked:
            # per-row causal mask, reconstructed from the tile's start
            # position: query row r (= b*gq + g') sits at absolute position
            # pstart + b
            qpos = pstart + jax.lax.broadcasted_iota(
                jnp.int32, (m_rows, block_s), 0
            ) // gq
            key_pos = base + jax.lax.broadcasted_iota(
                jnp.int32, (m_rows, block_s), 1
            )
            if window:
                # ``key_pos`` is a ring SLOT: it holds the position ``age``
                # back from the query's, seen if that is one of its
                # min(qpos + 1, window) newest (a later tile's keys, written
                # before any tile attends, lie a ring less a chunk back:
                # never seen)
                age = qpos % s_len - key_pos
                age = jnp.where(age < 0, age + s_len, age)
                seen = age < jnp.minimum(qpos + 1, window)
            else:
                seen = key_pos <= qpos
            live = jnp.broadcast_to(seen[None], sc.shape)
            sc = jnp.where(live, sc, NEG_INF)

        m_prev = m_ref[...]                             # [KV, M, 128]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - _lanes_to(m_new, block_s))
        if masked:
            p = jnp.where(live, p, 0.0)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, -1, keepdims=True)
        m_ref[...] = m_new
        if kv_quant:
            p = p * vs_ref[0][:, None, :]
        pv = jax.lax.dot_general(
            p.astype(operand), v_ref[0].astype(operand),  # [KV, Bs, D]
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                               # [KV, M, D]
        acc_ref[...] = acc_ref[...] * _lanes_to(alpha, d) + pv

    if window:
        # a ring: worth computing if the block holds a slot that SOME query
        # of the tile sees — the tile's queries together see the
        # ``window + tile - 1`` newest positions at ``fmax`` (the others'
        # DMA was skipped by the index map)
        run = _ring_blocks(fmax, block_s, s_len,
                           window + m_rows // gq - 1)[0](s)
        pl.when(run)(lambda: attend(True))
    else:
        # blocks past the frontier do nothing (their DMA is already
        # clamped); one that ends by ``pstart`` lies before it
        whole = base + block_s - 1 <= pstart
        pl.when(whole)(lambda: attend(False))
        pl.when(jnp.logical_not(whole) & (base <= fmax))(
            lambda: attend(True))

    @pl.when(s == last_s)
    def _finalize():
        denom = _lanes_to(jnp.maximum(l_ref[...], 1e-30), d)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_s", "kv_chunk", "interpret",
                              "page_size", "window")
)
def prefill_attention(
    q: jax.Array,        # [G, Bq, QH, D] tile queries (RoPE applied)
    k_cache: jax.Array,  # [R+1, KV, S, D] (this step's KV already written)
    v_cache: jax.Array,  # [R+1, KV, S, D]
    rows: jax.Array,     # i32[G] cache row per tile
    pstart: jax.Array,   # i32[G] first token position per tile (LOGICAL)
    scale: float,
    block_s: int = 512,
    kv_chunk: Optional[int] = None,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,  # [R+1, KV, S] int8-KV dequant
    v_scale: Optional[jax.Array] = None,  # scales (None = fp cache)
    page_table: Optional[jax.Array] = None,  # i32[R+1, S//page_size]
    page_size: int = 0,                      # static; 0 = slot-contiguous
    window: int = 0,     # static; > 0: the cache is a RING (see below)
) -> jax.Array:
    """Q-tiled prefill attention (the prompt phase of the reference's IncMHA).

    One grid row per TILE of Bq same-request tokens with contiguous
    positions (PrefillBatchConfig's contract): the committed-prefix blocks
    stream ONCE per tile instead of once per token — a Bq-fold cut in HBM
    traffic vs routing prefill through :func:`decode_attention` — and the
    score/value contractions carry Bq*gq query rows, real MXU tiles instead
    of decode's single-row vector products.  Same online-softmax core and
    causal DMA clamp as decode; tiles fold into the query-group dim exactly
    like :func:`tree_attention_batched`.  ALiBi models use the gather
    fallback (serve/ops.py routes them there).

    The grid's middle axis chunks the KV heads (``kv_chunk`` per step,
    default from :func:`_prefill_plan`'s VMEM arithmetic): heads are
    independent softmaxes, so chunking them caps the f32 score scratch and
    admits a WIDER Q tile — at the 7B shape tile 128 with kv_chunk 16 and
    256-position seq blocks, vs the old unchunked ceiling of tile 64 with
    128-position blocks: half the grid rows AND 2x the bytes per DMA wait.

    The body (:func:`_prefill_kernel`) adapts to what it is handed: the
    contractions run on the cache's own operand type
    (:func:`prefill_operand_dtype`: bf16 x bf16 into float32 in a bf16
    model, float32 throughout on a float32 cache, an int8 cache's values in
    the queries' type), the causal mask is built only in the blocks a
    tile's diagonal crosses, and the running softmax statistics stay
    lane-replicated.  On the v5e a chunk of four 128-row tiles at the 7B
    shape (36 live grid steps of 64) takes 133 us against 209 before PR 60,
    where its copies alone take 106; on one K/V head of 22 query heads
    (starcoder's: 2816 rows a tile) 110 us against 279 (PERF.md section 6).

    ``window > 0``: a sliding-window layer whose cache is a RING, as
    :func:`decode_attention`'s — position ``p`` at slot ``p % S``, ``S`` at
    least the window plus the widest chunk (the whole chunk is written
    before any tile attends, so a tile's oldest key must not lie under a
    later tile's newest) — and the query at ``p`` sees the ``min(p + 1,
    window)`` newest positions.  The window's LOWER bound is in the kernel:
    a block that holds no slot any query of the tile sees — wholly before
    ``pstart - window + 1``, or past the tile's end — is neither fetched
    (the index map sends it to one that is) nor computed, so a tile of a
    long prompt reads ``window + tile`` positions and not its whole prefix.
    """
    g, bq, qh, d = q.shape
    _, num_kv, s_len, _ = k_cache.shape
    gq = qh // num_kv
    m_rows = bq * gq
    kv_quant = k_scale is not None
    paged = page_table is not None
    if window and (paged or kv_quant):
        raise ValueError("a ring cache is slot-contiguous and fp")
    if kv_chunk is not None and num_kv % kv_chunk:  # forced chunk (tests)
        raise ValueError(f"kv_chunk {kv_chunk} must divide KV {num_kv}")
    kv_chunk, block_s = _prefill_plan(
        num_kv, d, jnp.dtype(q.dtype).itemsize,
        jnp.dtype(k_cache.dtype).itemsize, kv_quant, m_rows, block_s, s_len,
        kv_chunk)
    if paged:  # a seq-block must sit inside one page (see decode_attention)
        block_s = math.gcd(block_s, page_size)
    n_kc = num_kv // kv_chunk
    n_blocks = s_len // block_s
    # fold tiles into the query-group dim, b-major: row = b*gq + g'
    qr = q.reshape(g, bq, num_kv, gq, d).transpose(0, 2, 1, 3, 4) \
         .reshape(g, num_kv, m_rows, d)
    # a ring's positions are logical: they pass its length and never clamp
    fmax = (pstart + bq - 1).astype(jnp.int32) if window \
        else jnp.clip(pstart + bq - 1, 0, s_len - 1)

    if paged:
        ppr = s_len // page_size

        def kv_map(i, kc, j, rows, pstart, fmax, pt):
            jc = jnp.minimum(j, fmax[i] // block_s)
            prow, pblk = _page_coords(pt, rows[i], jc, block_s, page_size,
                                      ppr)
            return (prow, kc, pblk, 0)

        prefetch = (rows.astype(jnp.int32), pstart.astype(jnp.int32), fmax,
                    page_table.astype(jnp.int32))
    elif window:
        def kv_map(i, kc, j, rows, pstart, fmax):
            # as decode_attention's ring map, for the tile's whole run
            needed, first, newest = _ring_blocks(fmax[i], block_s, s_len,
                                                 window + bq - 1)
            skip_to = jnp.where(j > newest, newest, first)
            return (rows[i], kc, jnp.where(needed(j), j, skip_to), 0)

        prefetch = (rows.astype(jnp.int32), pstart.astype(jnp.int32), fmax)
    else:
        def kv_map(i, kc, j, rows, pstart, fmax):
            return (rows[i], kc, jnp.minimum(j, fmax[i] // block_s), 0)

        prefetch = (rows.astype(jnp.int32), pstart.astype(jnp.int32), fmax)

    scale_specs, scale_args = _scale_plumbing(
        kv_map, kv_chunk, block_s, k_scale, v_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(g, n_kc, n_blocks),
        in_specs=[
            pl.BlockSpec(
                (1, kv_chunk, m_rows, d),
                lambda i, kc, j, *_: (i, kc, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, kv_chunk, block_s, d), kv_map, memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, kv_chunk, block_s, d), kv_map, memory_space=pltpu.VMEM,
            ),
            *scale_specs,
        ],
        out_specs=pl.BlockSpec(
            (1, kv_chunk, m_rows, d),
            lambda i, kc, j, *_: (i, kc, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((kv_chunk, m_rows, 128), jnp.float32),
            pltpu.VMEM((kv_chunk, m_rows, 128), jnp.float32),
            pltpu.VMEM((kv_chunk, m_rows, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _prefill_kernel,
        block_s=block_s, num_kv=kv_chunk, gq=gq, m_rows=m_rows,
        scale=float(scale), kv_quant=kv_quant, paged=paged,
        window=window, s_len=s_len,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g, num_kv, m_rows, d), q.dtype),
        interpret=interpret,
    )(*prefetch, qr, k_cache, v_cache, *scale_args)
    return out.reshape(g, num_kv, bq, gq, d).transpose(0, 2, 1, 3, 4) \
        .reshape(g, bq, qh, d)


# One grid step of :func:`kv_block_write` moves this many bytes of the
# chunk's K (and as many of V) at most: whole heads of one tile.
_WRITE_BLOCK_BYTES = 512 * 2**10


def _kv_block_write_kernel(
    rows_ref,       # scalar prefetch: i32[G] cache row per tile
    blk_ref,        # scalar prefetch: i32[G] the tile's block along seq
    count_ref,      # scalar prefetch: i32[G] real tokens at the tile's head
    k_ref,          # [heads, Bq, D]: this tile's rows of the chunk's fresh
    v_ref,          # keys / values, head-major
    kc_any,         # the caches themselves (aliased to the outputs; never
    vc_any,         # read: a step writes whole blocks)
    ko_ref,         # [1, heads, Bq, D] block of the K cache
    vo_ref,         # [1, heads, Bq, D] block of the V cache
):
    del rows_ref, blk_ref, kc_any, vc_any
    row = jax.lax.broadcasted_iota(jnp.int32, k_ref.shape, 1)
    real = row < count_ref[pl.program_id(0)]
    for src, dst in ((k_ref, ko_ref), (v_ref, vo_ref)):
        x = src[...].astype(dst.dtype)
        dst[0] = jnp.where(real, x, jnp.zeros_like(x))


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def kv_block_write(
    k_cache: jax.Array,  # [R+1, KV, S, D]
    v_cache: jax.Array,  # [R+1, KV, S, D]
    k: jax.Array,        # [G * tile, KV, D] the chunk's fresh keys
    v: jax.Array,        # [G * tile, KV, D]
    rows: jax.Array,     # i32[G] cache row per tile (PHYSICAL under paging)
    start: jax.Array,    # i32[G] first seq index per tile, a multiple of tile
    count: jax.Array,    # i32[G] real tokens per tile (they sit at its head)
    tile: int,
    interpret: bool = False,
):
    """A tiled prefill chunk's K and V into their caches, IN PLACE, in one
    call: ``cache[rows[g], :, start[g]:start[g] + tile] = x[g * tile:(g + 1)
    * tile]`` head-major, cast to the cache's type, rows past ``count[g]``
    as zeros — the tile contract of :func:`prefill_attention`
    (request-homogeneous tiles, tile-aligned starts, ``S`` whole tiles, so
    that a block neither wraps nor clamps), and the values, zeros and
    positions of the chain of per-tile ``dynamic_update_slice`` operations it
    replaces (``serve/ops.py`` ``put_blocks`` keeps that chain as its
    fallback and the tests' reference).

    Both caches are aliased in and out (``input_output_aliases``), so XLA
    updates them where they lie; what a step does not visit keeps its
    contents.  Grid ``(tiles, head groups)``: the fresh rows come in as
    ``[heads, tile, D]`` blocks of the chunk seen head-major ``[KV, T, D]``
    and leave as ``[1, heads, tile, D]`` blocks of the cache, cast and
    masked on the way.  Head-major is how the fused QKV projection's output
    lies on the chip (XLA lays ``[T, KV, G, D]`` out heads first, so the view
    costs what the chain's per-tile blocks cost: one pass over K and one
    over V); handed the rows as ``[T, KV * D]`` the compiler transposed
    them first, at twice that (v5e, PR 49).  Heads of whole lanes only
    (``D % 128 == 0``; ``put_blocks`` sends the rest down the chain): at 64
    the compiler takes the kernel but re-lays both caches out around the
    call (AOT, PR 49).  Tiles that land on one block (the fully-pad tiles,
    all on the scratch row) all write zeros there.
    """
    r1, num_kv, s_len, d = k_cache.shape
    t = k.shape[0]
    g = t // tile
    if t % tile or s_len % tile:
        raise ValueError(f"the chunk ({t} rows) and the cache ({s_len} "
                         f"positions) hold whole tiles of {tile}")
    itemsize = max(jnp.dtype(a.dtype).itemsize for a in (k, k_cache))
    heads = max(c for c in range(1, num_kv + 1) if num_kv % c == 0
                and (c == 1 or c * tile * d * itemsize <= _WRITE_BLOCK_BYTES))
    fresh = pl.BlockSpec((heads, tile, d), lambda i, c, *_: (c, i, 0),
                         memory_space=pltpu.VMEM)
    block = pl.BlockSpec(
        (1, heads, tile, d),
        lambda i, c, rows, blk, count: (rows[i], c, blk[i], 0),
        memory_space=pltpu.VMEM)
    cache = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(g, num_kv // heads),
        in_specs=[fresh, fresh, cache, cache],
        out_specs=[block, block],
    )
    return pl.pallas_call(
        _kv_block_write_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                   jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype)],
        # operands count from the first scalar-prefetch argument
        input_output_aliases={5: 0, 6: 1},
        interpret=interpret,
    )(jnp.clip(rows.astype(jnp.int32), 0, r1 - 1),
      jnp.clip(start.astype(jnp.int32) // tile, 0, s_len // tile - 1),
      count.astype(jnp.int32),
      jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1), k_cache, v_cache)


# One grid step of :func:`kv_row_write` copies this many bytes of a cache
# in and out at most (a group of positions, all heads), and the fresh rows
# wait in VMEM in blocks of at most as many.
_ROW_WRITE_BLOCK_BYTES = 2**20


def row_write_group(cache) -> int:
    """Positions of ``cache`` ``[R+1, KV, S, D]`` that :func:`kv_row_write`
    reads, merges and writes back around the one it sets: those that share a
    native tile along the seq axis (8 sublanes of 32 bits: 8 positions of
    float32, 16 of bfloat16, 32 of int8).  0 where the kernel cannot take
    the cache: not 4-D, not whole groups, or a group past a grid step's
    bytes."""
    itemsize = jnp.dtype(cache.dtype).itemsize
    group = 8 * 4 // itemsize
    if cache.ndim != 4 or cache.shape[2] % group or (
            cache.shape[1] * group * cache.shape[3] * itemsize
            > _ROW_WRITE_BLOCK_BYTES):
        return 0
    return group


def _kv_row_write_kernel(
    rows_ref,       # scalar prefetch: i32[T] cache row per fresh row
    pos_ref,        # scalar prefetch: i32[T] its seq index in that row
    *refs,          # a plane: [Tb, KV, D] a block of the step's fresh rows;
                    # then a plane: [1, KV, G, D] the group of positions
                    # around pos[t] as the cache holds it (aliased to the
                    # outputs); then a plane: the same block, going back
    group: int,
):
    n = len(refs) // 3
    t = pl.program_id(0)
    tb, heads = refs[0].shape[:2]
    before = jnp.maximum(t - 1, 0)
    # the step before wrote this very block (pads, all on the scratch row):
    # Pallas then copies it neither in again nor out yet, so what that step
    # wrote is in the OUTPUT block and the input block is stale
    again = (t > 0) & (rows_ref[t] == rows_ref[before]) & (
        pos_ref[t] // group == pos_ref[before] // group)
    hit = jax.lax.broadcasted_iota(
        jnp.int32, refs[-1].shape[2:], 0) == pos_ref[t] % group     # [G, D]

    def merge(held):
        for src, base, dst in zip(refs[:n], held, refs[2 * n:]):
            for h in range(heads):
                x = jnp.broadcast_to(src[t % tb, pl.ds(h, 1), :], hit.shape)
                dst[0, h] = jnp.where(hit, x, base[0, h])

    pl.when(again)(lambda: merge(refs[2 * n:]))
    pl.when(jnp.logical_not(again))(lambda: merge(refs[n:2 * n]))


@functools.partial(jax.jit, static_argnames=("interpret",))
def kv_row_write(
    k_cache: jax.Array,            # [R+1, KV, S, D]
    v_cache: Optional[jax.Array],  # the same shape and type (None: one plane)
    k: jax.Array,                  # [T, KV, D] the step's fresh keys, a row each
    v: Optional[jax.Array],        # [T, KV, D]
    rows: jax.Array,     # i32[T] cache row per fresh row (PHYSICAL if paged)
    pos: jax.Array,      # i32[T] seq index per fresh row
    interpret: bool = False,
):
    """A decode step's K and V rows into their caches, IN PLACE, in one
    call: ``cache[rows[t], :, pos[t]] = x[t]``, cast to the cache's type,
    ``rows`` clamped to ``[0, R]`` and ``pos`` to ``[0, S - 1]`` — the
    values, positions and untouched contents of the chain of one-row
    ``dynamic_update_slice`` operations it replaces (``serve/ops.py``
    ``_update_rows``, which ``put_rows`` keeps as its fallback and the tests'
    reference: a fixed 0.65-1.4 us an operation on the v5e, 2 a row).
    Returns the two caches (one where ``v_cache`` is None).

    :func:`kv_block_write`'s shape, one position high.  A position is
    narrower than the cache's native tile (:func:`row_write_group` positions
    share one), so grid step ``t`` copies the aligned group of positions
    around ``pos[t]`` in — block ``(1, KV, G, D)`` at ``(rows[t], 0, pos[t]
    // G, 0)`` of the cache itself, aliased in and out
    (``input_output_aliases``) — sets the one position by a sublane compare
    and copies the group back; the pipeline has the next row's group in
    flight meanwhile.  On the v5e a row of both caches takes 0.31 us on one
    K/V head, 0.63 on ten, 1.2 on 32 (512 KB in and out; PERF.md section 6,
    PR 56).

    The caller's contract (the decode scan: ``one_row_per_request``): no two
    of a call's ``(rows[t], pos[t] // G)`` are the same block, EXCEPT on the
    scratch row (the last), where pads land any number of times in any
    order: a step reads its block before an earlier step's write of it has
    landed, unless that step is the one just before it.  Pads next to each
    other therefore leave the scratch row as the chain does; pads apart may
    leave one of their positions as it was.
    """
    caches = (k_cache,) if v_cache is None else (k_cache, v_cache)
    fresh = tuple(x.astype(k_cache.dtype)
                  for x in ((k,) if v_cache is None else (k, v)))
    group = row_write_group(k_cache)
    if not group or any(c.shape != k_cache.shape or c.dtype != k_cache.dtype
                        for c in caches):
        raise ValueError(
            f"caches {[(c.shape, str(c.dtype)) for c in caches]}: one shape "
            "and type, [R+1, KV, S, D] with S whole groups of positions of at "
            f"most {_ROW_WRITE_BLOCK_BYTES} bytes")
    r1, num_kv, s_len, d = k_cache.shape
    t = k.shape[0]
    n = len(caches)
    tb = max(c for c in range(1, t + 1) if t % c == 0 and (
        c == 1 or c * max(num_kv, group) * d * k_cache.dtype.itemsize
        <= _ROW_WRITE_BLOCK_BYTES))
    rows_in = pl.BlockSpec((tb, num_kv, d), lambda i, *_: (i // tb, 0, 0),
                           memory_space=pltpu.VMEM)
    block = pl.BlockSpec(
        (1, num_kv, group, d),
        lambda i, rows, pos: (rows[i], 0, pos[i] // group, 0),
        memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t,),
        in_specs=[rows_in] * n + [block] * n,
        out_specs=[block] * n,
    )
    out = pl.pallas_call(
        functools.partial(_kv_row_write_kernel, group=group),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(c.shape, c.dtype) for c in caches],
        # operands count from the first scalar-prefetch argument
        input_output_aliases={2 + n + i: i for i in range(n)},
        interpret=interpret,
    )(jnp.clip(rows.astype(jnp.int32), 0, r1 - 1),
      jnp.clip(pos.astype(jnp.int32), 0, s_len - 1), *fresh, *caches)
    return out[0] if v_cache is None else tuple(out)


def _tree_kernel(
    rows_ref,       # scalar prefetch: i32[T] cache row per token
    clens_ref,      # scalar prefetch: i32[T] committed cache depth per token
    *refs,          # [pt_ref (paged),] q_ref ([1, KV, gq, D] queries),
                    # k_ref/v_ref ([1, KV, Bs, D] committed blocks),
                    # [ks_ref, vs_ref,] sk_ref, sv_ref, bias_ref, o_ref,
                    # m/l/acc scratch — scale blocks only for int8 committed
                    # caches (the spec buffer stays in the compute dtype)
    block_s: int,
    num_kv: int,
    gq: int,
    scale: float,
    kv_quant: bool,
    paged: bool = False,
):
    if paged:
        refs = refs[1:]  # page table: index-map-only prefetch operand
    q_ref, k_ref, v_ref, *rest = refs
    if kv_quant:
        ks_ref, vs_ref, sk_ref, sv_ref, bias_ref, o_ref, \
            m_ref, l_ref, acc_ref = rest
    else:
        sk_ref, sv_ref, bias_ref, o_ref, m_ref, l_ref, acc_ref = rest
    t = pl.program_id(0)
    s = pl.program_id(1)
    last_s = pl.num_programs(1) - 1

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    clen = clens_ref[t]
    base = s * block_s

    @pl.when(base < clen)  # blocks past the committed frontier: DMA clamped
    def _committed():
        q = q_ref[0].astype(jnp.float32)               # [KV, gq, D]
        k = k_ref[0].astype(jnp.float32)               # [KV, Bs, D]
        sc = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                                       # [KV, gq, Bs]
        if kv_quant:  # fused dequant (see _decode_kernel)
            sc = sc * ks_ref[0][:, None, :]
        key_pos = base + jax.lax.broadcasted_iota(
            jnp.int32, (num_kv, gq, block_s), 2
        )
        live = key_pos < clen  # strict: committed prefix only
        sc = jnp.where(live, sc, NEG_INF)

        m_prev = m_ref[:, :, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(live, jnp.exp(sc - m_new), 0.0)
        l_new = alpha * l_ref[:, :, 0:1] + jnp.sum(p, -1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p * vs_ref[0][:, None, :] if kv_quant else p,
            v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(s == last_s)
    def _spec_and_finalize():
        q = q_ref[0].astype(jnp.float32)               # [KV, gq, D]
        ks = sk_ref[0].astype(jnp.float32)             # [KV, P, D]
        vs = sv_ref[0].astype(jnp.float32)
        # bias arrives pre-padded to the 128-lane width ([1, G, Pp] with
        # G == 1 or gq) and is kept >=2-D throughout: Mosaic gives 1-D
        # values an implicit minor dim that poisons the downstream reduce
        # ("unsupported output implicit dimension"); the K/V pad below
        # matches it — padded slots carry NEG_INF bias so they vanish.
        bias3 = bias_ref[...]                           # [1, G, Pp]
        pad = bias3.shape[-1] - ks.shape[1]
        if pad:
            ks = jnp.pad(ks, ((0, 0), (0, pad), (0, 0)))
            vs = jnp.pad(vs, ((0, 0), (0, pad), (0, 0)))
        sc = jax.lax.dot_general(
            q, ks, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                                       # [KV, gq, Pp]
        live = jnp.broadcast_to(bias3 > NEG_INF / 2, sc.shape)
        sc = sc + jnp.broadcast_to(bias3, sc.shape)

        m_prev = m_ref[:, :, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(live, jnp.exp(sc - m_new), 0.0)
        l_new = alpha * l_ref[:, :, 0:1] + jnp.sum(p, -1, keepdims=True)
        pv = jax.lax.dot_general(
            p, vs, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(s == last_s)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :, 0:1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _tree_call(qr, k_cache, v_cache, k_spec, v_spec, rows, clens, bias,
               scale, block_s, interpret, k_scale=None, v_scale=None,
               page_table=None, page_size=0):
    """Shared pallas_call for the tree kernel.

    ``qr``: [N, KV, G, D] query groups (N grid rows share one cache row);
    ``bias``: [N, Gb, Pp] pre-padded ancestor bias with Gb in {1, G}.
    Only the COMMITTED cache pages (``page_table``); the spec buffers are
    small per-request scratch rewritten every macro-step and stay
    slot-contiguous.
    """
    n, num_kv, g, d = qr.shape
    s_len = k_cache.shape[2]
    p_len = k_spec.shape[2]
    pp = bias.shape[-1]
    kv_quant = k_scale is not None
    paged = page_table is not None
    block_s = _fit_block_s(block_s, s_len, num_kv, d,
                           jnp.dtype(k_cache.dtype).itemsize, kv_quant,
                           _VMEM_BUDGET)
    if paged:  # a seq-block must sit inside one page (see decode_attention)
        block_s = math.gcd(block_s, page_size)
    n_blocks = s_len // block_s

    if paged:
        ppr = s_len // page_size

        def kv_map(i, j, rows, clens, pt):
            limit = jnp.maximum(clens[i] - 1, 0) // block_s
            jc = jnp.minimum(j, limit)
            prow, pblk = _page_coords(pt, rows[i], jc, block_s, page_size,
                                      ppr)
            return (prow, 0, pblk, 0)

        prefetch = (rows.astype(jnp.int32),
                    jnp.clip(clens, 0, s_len).astype(jnp.int32),
                    page_table.astype(jnp.int32))
    else:
        def kv_map(i, j, rows, clens):
            # clamp to the committed frontier so fully-masked blocks re-map
            # to an already-fetched block (Pallas skips the copy)
            limit = jnp.maximum(clens[i] - 1, 0) // block_s
            return (rows[i], 0, jnp.minimum(j, limit), 0)

        prefetch = (rows.astype(jnp.int32),
                    jnp.clip(clens, 0, s_len).astype(jnp.int32))

    def spec_map(i, j, rows, *_):
        return (rows[i], 0, 0, 0)

    scale_specs, scale_args = _scale_plumbing(
        kv_map, num_kv, block_s, k_scale, v_scale)
    gb = bias.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(n, n_blocks),
        in_specs=[
            pl.BlockSpec(
                (1, num_kv, g, d), lambda i, j, *_: (i, 0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, num_kv, block_s, d), kv_map, memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, num_kv, block_s, d), kv_map, memory_space=pltpu.VMEM,
            ),
            *scale_specs,
            pl.BlockSpec(
                (1, num_kv, p_len, d), spec_map, memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, num_kv, p_len, d), spec_map, memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, gb, pp), lambda i, j, *_: (i, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, num_kv, g, d), lambda i, j, *_: (i, 0, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((num_kv, g, 128), jnp.float32),
            pltpu.VMEM((num_kv, g, 128), jnp.float32),
            pltpu.VMEM((num_kv, g, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _tree_kernel,
        block_s=block_s, num_kv=num_kv, gq=g, scale=float(scale),
        kv_quant=kv_quant, paged=paged,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, num_kv, g, d), qr.dtype),
        interpret=interpret,
    )(*prefetch, qr, k_cache, v_cache, *scale_args, k_spec, v_spec, bias)


def _pad_bias(amask):
    """bool[..., P] ancestor mask -> f32[..., Pp] additive bias, lane-padded."""
    bias = jnp.where(amask, 0.0, NEG_INF).astype(jnp.float32)
    pad = (-bias.shape[-1]) % 128
    if pad:
        widths = [(0, 0)] * (bias.ndim - 1) + [(0, pad)]
        bias = jnp.pad(bias, widths, constant_values=NEG_INF)
    return bias


@functools.partial(
    jax.jit, static_argnames=("scale", "block_s", "interpret", "page_size")
)
def tree_attention(
    q: jax.Array,        # [T, QH, D] (RoPE already applied)
    k_cache: jax.Array,  # [R+1, KV, S, D] committed cache (post-commit)
    v_cache: jax.Array,  # [R+1, KV, S, D]
    k_spec: jax.Array,   # [R+1, KV, P, D] spec-tree buffer (current step's
    v_spec: jax.Array,   # KV already written)
    rows: jax.Array,     # i32[T] cache row per token
    clens: jax.Array,    # i32[T] committed depth per token (strict < mask)
    amask: jax.Array,    # bool[T, P] per-token tree-ancestor mask
    scale: float,
    block_s: int = 512,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,  # [R+1, KV, S] int8 committed-cache
    v_scale: Optional[jax.Array] = None,  # dequant scales (None = fp cache)
    page_table: Optional[jax.Array] = None,  # i32[R+1, S//page_size]
    page_size: int = 0,
) -> jax.Array:
    """Two-segment tree-verify attention (SpecInfer's TreeIncMHA hot loop).

    TPU-native replacement for the reference's
    ``tree_inc_multihead_self_attention.cu``: each tree token attends its
    request's committed cache (causal below ``clens[t]``) plus its root-path
    ancestors in the spec buffer (``amask[t]``).  Reuses the decode kernel's
    design: kv-head-major blocks, scalar-prefetched rows, causal DMA clamp
    over the committed segment, online softmax carried across seq blocks;
    the spec segment (small, one row) is folded in at the final grid step.
    ALiBi models take the gather fallback (needs per-slot key positions).

    One grid row per TOKEN: flexible for arbitrary flat batches, but tokens
    of the same request re-stream the same cache; when the token layout is
    a fixed ``[R, P]`` grid use :func:`tree_attention_batched`.
    """
    t, qh, d = q.shape
    num_kv = k_cache.shape[1]
    gq = qh // num_kv
    qr = q.reshape(t, num_kv, gq, d)
    bias = _pad_bias(amask)[:, None, :]  # [T, 1, Pp]
    out = _tree_call(qr, k_cache, v_cache, k_spec, v_spec, rows, clens,
                     bias, scale, block_s, interpret, k_scale, v_scale,
                     page_table, page_size)
    return out.reshape(t, qh, d)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_s", "interpret", "page_size")
)
def tree_attention_batched(
    q: jax.Array,        # [R, P, QH, D] per-request tree-token queries
    k_cache: jax.Array,  # [R+1, KV, S, D]
    v_cache: jax.Array,  # [R+1, KV, S, D]
    k_spec: jax.Array,   # [R+1, KV, Pb, D]
    v_spec: jax.Array,
    rows: jax.Array,     # i32[R] cache row per request
    clens: jax.Array,    # i32[R] committed depth per request
    amask: jax.Array,    # bool[R, P, Pb] per-request tree mask
    scale: float,
    block_s: int = 512,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,  # [R+1, KV, S] int8 committed-cache
    v_scale: Optional[jax.Array] = None,  # dequant scales (None = fp cache)
    page_table: Optional[jax.Array] = None,  # i32[R+1, S//page_size]
    page_size: int = 0,
) -> jax.Array:
    """Tree-verify attention for a FIXED [requests x tree-slots] layout.

    The on-device speculative scan (serve/spec_scan.py) always ships exactly
    P tree tokens per request, so all P tokens can share one grid row: the
    committed-cache blocks stream ONCE per request instead of once per
    token — a P-fold cut in the dominant HBM traffic (the committed mask is
    per-request, so the fold into the query-group dim is exact).
    """
    r, p, qh, d = q.shape
    num_kv = k_cache.shape[1]
    gq = qh // num_kv
    # [R, P, KV, gq, D] -> [R, KV, P*gq, D]: tree slots join the query-group
    # dim; kv stays dim 1 (the cache layout / TP shard dim)
    qr = q.reshape(r, p, num_kv, gq, d).transpose(0, 2, 1, 3, 4) \
         .reshape(r, num_kv, p * gq, d)
    # per-(slot, group) bias rows: [R, P, Pp] -> repeat gq -> [R, P*gq, Pp]
    bias = jnp.repeat(_pad_bias(amask), gq, axis=1)
    out = _tree_call(qr, k_cache, v_cache, k_spec, v_spec, rows, clens,
                     bias, scale, block_s, interpret, k_scale, v_scale,
                     page_table, page_size)
    return out.reshape(r, num_kv, p, gq, d).transpose(0, 2, 1, 3, 4) \
        .reshape(r, p, qh, d)
