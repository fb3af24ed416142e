"""Serving ops whose per-slot state is not a full-length K/V cache.

What a hybrid decoder (SambaY: Phi-4-mini-flash) adds to the serve graph
beside ``IncMultiHeadSelfAttention``:

* :class:`CausalConv1d` and :class:`SelectiveScan` — Mamba-1's depthwise
  causal convolution and its selective state-space scan.  State per slot: the
  last ``K - 1`` inputs of the conv, and the scan's ``[channels, N]`` float32
  state.  Neither grows with the context.
* :class:`DiffAttention` — differential attention (two softmaxes per head
  pair over a 128-wide value) in three modes: ``window`` keeps a RING of the
  last positions (``sliding_window`` + the widest step, rounded to the
  kernels' tile; independent of ``max_seq_len``), ``full`` owns an ordinary
  full-length cache, and ``cross`` projects queries only and reads the cache
  a ``full`` node (its ``state_owner``) wrote earlier in the same step.
* :class:`SlidingWindowAttention` — PLAIN grouped-query attention with
  rotary over a sliding window (``cohere2_moe``'s sliding layers), its
  cache the same ring: :class:`SlotCacheAttention` is the one ring (and
  full-length, and borrowed) cache implementation both kinds share.
* :class:`LatentAttention` — multi-head LATENT attention (``deepseek_v2``;
  un-rotated, ``kimi_linear``'s one layer in four): the cache holds one
  normed latent and one shared key part a position (rotated where the model
  rotates), shared by all heads, and nothing per head; decode reads it in
  the ABSORBED form (the per-head up-projections folded into the query and
  the output), so the latent is the key and the value at once.
* :class:`EvaAttention` — EVA attention (EvaByte): exact attention inside
  the query's own window, one summary per chunk of every earlier window, one
  softmax over both.  Its cache COMPACTS itself: when a window closes, its
  raw keys and values are replaced by their summaries, so a row's live cache
  is a contiguous prefix whose length is not the position, and falls.
* :class:`SparseBlockAttention` — InfLLM-v2 sparse attention (MiniCPM-SALA's
  ``minicpm4`` layers): a full-length K/V cache AND, beside it, an index of
  compressed keys that gains an entry every ``kernel_stride`` positions; each
  row CHOOSES by it which blocks of its cache to read (``BlockSelect``), per
  KV-head group, and reads a list of blocks, not a run.
* :class:`LightningAttention` — Lightning linear attention (its
  ``lightning-attn`` layers): the state is one ``head_dim x head_dim``
  float32 matrix per head and slot, decayed per head and updated by a
  rank-one product a position.
* :class:`KimiDeltaAttention` — a gated DELTA rule with a per-channel decay
  (``kimi_linear``'s ``kda_layers``): the same matrix state, but the update
  subtracts what the state already returns for the key — it READS the state
  it changes — and the decay is a vector a head computed from the token.

All of them run on the flat token batch every step program shares.  A flat
batch mixes rows of several requests, so state is SEGMENTED by
``request_index``: a run of rows of one request with ascending positions is
a segment; it starts from that slot's stored state and stores it back after
its last row.  A segment whose first position is 0 starts from ZERO state
whatever the slot held before — so a freed slot's conv tail, scan state and
ring can never leak into the next request, with no reset program to forget.
Rows of no request (``request_index == -1``) read zeros and write the
scratch row, as a pad token's K/V does.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.graph import ParamSpec, TensorSpec
from ..core.op import Op, OpContext, register_op
from ..core.sharding import TensorSharding
from ..ops.norm import _rms_norm
from .batch_config import BatchConfig, PrefillBatchConfig
from .ops import (DUS_MAX_TOKENS, NEG_INF, _block_chain, _tile_blocks,
                  apply_rope, note_decode_block, note_prefill_operands,
                  put_blocks, put_rows, tile_coords, yarn_mscale)
from .quant import dequant

LANE = 128  # the kernels' seq-block granule: every cache seq dim is padded to it
# prefixes of a latent cache a prompt chunk's XLA attention may be cut to
PROMPT_SPANS = 8


def _flat(bc) -> BatchConfig:
    return bc if isinstance(bc, BatchConfig) else bc.base


def _require(ctx: OpContext, what: str):
    bc = ctx.extras.get("batch_config")
    state = ctx.extras.get("state")
    if bc is None or state is None:
        raise ValueError(f"{what} requires a batch_config and its state "
                         "(run it through the InferenceManager)")
    if not isinstance(bc, (BatchConfig, PrefillBatchConfig)):
        raise ValueError(f"{what} cannot run a {type(bc).__name__}: "
                         "speculation needs a state snapshot per tree node")
    return bc, state


class Segments:
    """The flat batch cut into runs of one request's consecutive positions.

    ``rows``: state row per flat row (pads -> the scratch row ``nreq``);
    ``start`` / ``last``: the row opens / closes its segment; ``offset``:
    rows since the segment's start; ``fresh``: the segment starts at
    position 0 (or is a pad), so it starts from zero state; ``store``: the
    row to write the state left behind this flat row to — the slot's own
    after a segment's last row, the scratch row otherwise.
    """

    def __init__(self, bc: BatchConfig, nreq: int):
        r, p = bc.request_index, bc.token_position
        t = r.shape[0]
        live = r >= 0
        prev_r = jnp.concatenate([jnp.full((1,), -2, r.dtype), r[:-1]])
        prev_p = jnp.concatenate([jnp.zeros((1,), p.dtype), p[:-1]])
        self.start = (r != prev_r) | (p != prev_p + 1) | ~live
        self.last = jnp.concatenate([self.start[1:], jnp.ones((1,), bool)])
        idx = jnp.arange(t, dtype=jnp.int32)
        seg0 = jax.lax.cummax(jnp.where(self.start, idx, 0), axis=0)
        self.offset = idx - seg0
        self.live = live
        self.pos = p
        self.fresh = (p - self.offset <= 0) | ~live
        self.rows = jnp.where(live, r, nreq).astype(jnp.int32)
        self.store = jnp.where(live & self.last, self.rows, nreq)


@jax.jit
def _set_rows_chain(buf, rows, upd):
    """``buf[rows[i]] = upd[i]`` as in-place dynamic-update-slices (traced
    once per shape: see ``ops._update_rows``)."""
    zeros = (jnp.int32(0),) * (buf.ndim - 1)
    for i in range(upd.shape[0]):
        buf = jax.lax.dynamic_update_slice(buf, upd[i][None],
                                           (rows[i],) + zeros)
    return buf


def _set_rows(buf, rows, upd):
    """Per-slot state write.  The scratch row may be written many times
    (any order); a slot's own row at most once per step."""
    upd = upd.astype(buf.dtype)
    if upd.shape[0] > DUS_MAX_TOKENS:
        return buf.at[rows].set(upd)
    return _set_rows_chain(buf, rows, upd)


def _init(fn):
    """An initializer that ignores its key: ``fn(shape) -> array``."""
    return lambda key, shape, dtype: fn(shape).astype(dtype)


class _SlotStateOp(Op):
    """Common to the ops whose state is per slot and of fixed size."""

    stateful = True
    slot_state = True   # register_serve_capacities sizes it; refusals key on it
    state_owner: Optional[str] = None
    # the attributes :meth:`launch_counts` reads: a graph's nodes of one
    # class agree on them (:func:`launch_counter` holds them to it)
    launch_reads: Tuple[str, ...] = ()

    def parallel_dims(self, in_specs):
        return {}   # replicated: no sharding rule yet (tp > 1 is refused)

    def launch_counts(self, decode, prompt, layers: int, counted: bool):
        """What ONE launch means to this op's state beyond the contexts
        every launch's span carries: ``(arguments, counters)``, names to
        integers — the dispatch span's arguments, of ONE layer, and counters
        over all ``layers`` nodes of this class (``counted`` False: no one
        keeps them).  ``decode`` / ``prompt``: the positions ``[(lo, hi)]``
        the launch writes for its decode rows (``hi - lo`` steps of a decode
        scan, one of a flat step) and for its prompt segments; ``None`` for
        a kind of launch that carries no such rows (a prefill scan decodes
        nothing, a decode scan feeds no prompt): the names that speak of
        them are then left out.  Host arithmetic, no device read."""
        return {}, {}


def _writes(decode, prompt):
    """Every row or segment a launch writes, the empty ones dropped."""
    return [(lo, hi) for lo, hi in (*(decode or ()), *(prompt or ()))
            if hi > lo]


def launch_counter(graph):
    """``count(decode, prompt, counted) -> (arguments, counters)``: every
    :meth:`_SlotStateOp.launch_counts` of the graph in one pair of dicts.
    The first node of a class answers for its siblings."""
    by_class: Dict[type, List[_SlotStateOp]] = {}
    for n in graph.nodes:
        if (isinstance(n.op, _SlotStateOp) and type(n.op).launch_counts
                is not _SlotStateOp.launch_counts):
            by_class.setdefault(type(n.op), []).append(n.op)
    for cls, ops in by_class.items():
        for name in cls.launch_reads:
            assert len({getattr(op, name) for op in ops}) == 1, (cls, name)
    hooks = [(ops[0].launch_counts, len(ops)) for ops in by_class.values()]

    def count(decode, prompt, counted):
        args, counters = {}, {}
        for hook, layers in hooks:
            a, c = hook(decode, prompt, layers, counted)
            args.update(a)
            counters.update(c)
        return args, counters

    return count


@register_op
class CausalConv1d(_SlotStateOp):
    """Depthwise causal convolution over each request's own positions:
    ``y_t = silu(sum_j w[j] * x_{t-(K-1-j)} [+ b])``, positions before the
    request's first read zero; the bias ``b`` is an OPTION (``bias``: Mamba's
    convs carry one, ``kimi_linear``'s three short convs over q | k | v do
    not, and then the op has no such parameter).  Input/output
    ``[max_tokens, channels]``; state ``conv [max_requests + 1, K - 1,
    channels]``: the last ``K - 1`` inputs of each slot.

    A prompt chunk or a flat step goes by SEGMENTS (``_rows``): every row
    takes its taps from the rows before it in the batch, and the stored
    tails are read and written per SLOT — a slot's tail enters the ``K - 1``
    rows that open its segment and the segment's last rows leave the new
    one, so nothing of ``[rows, K - 1, channels]`` is gathered or scattered.
    The decode scan (``one_row_per_request``: every live row a request of
    its own) steps the tails in SLOT ORDER, where they lie.  Both sum the
    same float32 taps in the same order: the tails they leave are equal to
    the bit, and ``y`` is wherever the backend rounds the two fusions
    alike."""

    type_name = "causal_conv1d"

    def __init__(self, channels: int, kernel: int = 4, dtype=jnp.float32,
                 bias: bool = True):
        self.channels = int(channels)
        self.kernel = int(kernel)
        self.bias = bool(bias)
        self.dtype = jnp.dtype(dtype).name

    def infer_shapes(self, in_specs):
        return [TensorSpec(in_specs[0].shape, jnp.dtype(self.dtype))]

    def params(self) -> List[ParamSpec]:
        dt = jnp.dtype(self.dtype)
        ps = [ParamSpec("weight",
                        TensorSpec((self.kernel, self.channels), dt))]
        if self.bias:
            ps.append(ParamSpec("bias", TensorSpec((self.channels,), dt)))
        return ps

    def state_specs(self, max_requests, max_seq_len, max_spec_tokens=0,
                    head_axes=()):
        shape = (max_requests + 1, self.kernel - 1, self.channels)
        return {"conv": (shape, self.dtype, TensorSharding.replicated(3))}

    def flops(self, in_specs):
        return 2 * self.kernel * in_specs[0].size

    # ---- the two forms ----------------------------------------------------
    def _taps_out(self, taps, w, b):
        """``silu(b + sum_back w[K-1-back] * taps[back])`` in float32, the
        newest tap first: ONE order of summation for both forms, so that
        they round alike."""
        y = b.astype(jnp.float32) if self.bias else 0.0
        w = w.astype(jnp.float32)
        for back, tap in enumerate(taps):
            y = y + tap.astype(jnp.float32) * w[self.kernel - 1 - back]
        return jax.nn.silu(y).astype(self.dtype)

    def _slot_order(self, x, tails, seg, w, b):
        """The decode scan's step: every slot's tail read, shifted and
        written where a row of the batch is its request's, untouched where
        none is — ONE pass over the tails where they lie, no gather of every
        row's tail, no pick per tap and no scatter of the new tails back."""
        nslot, k = tails.shape[0], self.kernel
        at = seg.rows                      # pads land on the scratch row
        # each slot's row of the batch (row 0 where none is: masked below),
        # so that the rows come to their slots by a GATHER — on the chip a
        # scatter of as many rows costs twice as much — and its position
        # this step, -1 where no live row is its request's
        row = jnp.zeros((nslot,), jnp.int32).at[at].set(
            jnp.arange(x.shape[0], dtype=jnp.int32))
        pos = jnp.full((nslot,), -1, seg.pos.dtype).at[at].set(
            jnp.where(seg.live, seg.pos, -1))
        xs = x[row]
        # taps[back] = the input ``back`` positions before the row's own:
        # the slot's stored tail, or (before the request began) zero
        taps = [xs] + [jnp.where((pos >= back)[:, None],
                                 tails[:, k - 1 - back], 0)
                       for back in range(1, k)]
        y = self._taps_out(taps, w, b)
        with jax.named_scope("state_write"):
            tails = jnp.where((pos >= 0)[:, None, None],
                              jnp.stack(taps[k - 2::-1], axis=1), tails)
        return y[at], tails

    def _rows(self, x, tails, seg, w, b):
        """A prompt chunk or a flat step, by SEGMENTS (a batch holds at most
        one a slot): a first pass takes every tap from the rows before it in
        the batch; a slot's stored tail reaches only the ``K - 1`` rows that
        open its segment, which are computed again PER SLOT with the stored
        entries in place and put over the first pass's, and the new tail is
        the segment's last rows — after the stored one's newest entries
        where the segment is shorter.  Every tap is a selection, as in
        :meth:`_slot_order`; nothing is gathered or written by row."""
        k, t, nslot = self.kernel, x.shape[0], tails.shape[0]
        # a tap the segment does not reach (``offset < back``: a stored
        # entry, or before the request began) reads zero in this pass
        taps = [x] + [
            jnp.where((seg.offset >= back)[:, None], jnp.concatenate(
                [jnp.zeros_like(x[:back]), x[:-back]], axis=0), 0)
            for back in range(1, k)]
        y = self._taps_out(taps, w, b)
        # each slot's segment: its first and last row of the batch and the
        # position it opens at (a comparison a slot and row: no scatter)
        idx = jnp.arange(t, dtype=jnp.int32)
        slot = jnp.arange(nslot, dtype=jnp.int32)
        mine = seg.live & (seg.rows == slot[:, None])
        first = jnp.min(jnp.where(mine, idx, t), axis=1)
        last = jnp.max(jnp.where(mine, idx, -1), axis=1)
        length = last - first + 1                    # <= 0: no segment
        pos = seg.pos[jnp.minimum(first, t - 1)]
        # the inputs around the segment's first row, oldest first: the
        # stored tail (zero before the request began), then its first rows
        around = [jnp.where((pos + j >= k - 1)[:, None], tails[:, j], 0)
                  for j in range(k - 1)]
        around += [x[jnp.minimum(first + j, t - 1)] for j in range(k - 1)]
        with jax.named_scope("segment_open"):
            for j in range(k - 1):      # the rows the stored tail reaches
                fix = self._taps_out([around[k - 1 + j - back]
                                      for back in range(k)], w, b)
                at = jnp.where(j < length, first + j, t + slot)
                y = y.at[at].set(fix, mode="drop", unique_indices=True)
        with jax.named_scope("state_write"):
            # what the segment leaves behind: its last K-1 rows — fewer,
            # and the stored tail's newest entries stay in front of them
            left = []
            for j in range(k - 1):
                new = x[jnp.maximum(last - (k - 2) + j, 0)]
                for short in range(1, k - 1 - j):
                    new = jnp.where((length == short)[:, None],
                                    around[short + j], new)
                left.append(new)
            tails = jnp.where((length > 0)[:, None, None],
                              jnp.stack(left, axis=1).astype(tails.dtype),
                              tails)
        return y, tails

    def lower(self, ctx, inputs, params):
        bc, state = _require(ctx, self.type_name)
        tails = state["conv"]
        seg = Segments(_flat(bc), tails.shape[0] - 1)
        if ctx.extras.get("one_row_per_request"):
            form, path, batch = (self._slot_order, "slot_order",
                                 "one_row_per_request")
        else:
            form, path, batch = self._rows, "rows", type(bc).__name__
        y, tails = form(inputs[0], tails, seg, params["weight"],
                        params.get("bias"))
        paths = ctx.extras.get("attention_paths")
        if paths is not None:
            paths[(self.type_name, batch)] = path
        ctx.extras["state_out"] = {"conv": tails}
        return [y]


@register_op
class SelectiveScan(_SlotStateOp):
    """Mamba-1's selective scan over each request's own positions.

    Inputs ``xs [T, C]`` (the conv's output), ``dt [T, C]`` (the step
    projection, before its bias and softplus), ``B [T, N]``, ``C [T, N]``.
    ``delta = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
    ``h_t = exp(delta_t (x) A) * h_{t-1} + (delta_t * xs_t) (x) B_t``;
    ``y_t = h_t . C_t + D * xs_t``.  ``A_log``, ``D``, ``dt_bias`` and the
    state ``ssm [max_requests + 1, C, N]`` are float32 (the recurrence
    multiplies thousands of factors near 1).

    A flat step or a prompt chunk scans its rows in order, carrying ``h``
    across a segment and exchanging it with the slot's row at the segment's
    ends — in one Pallas kernel where the kernels are on
    (``ops/pallas/selective_scan.py``), by a ``lax.scan`` over the rows
    otherwise; the decode scan (``one_row_per_request``: every live row a
    request of its own) updates all rows at once.
    """

    type_name = "selective_scan"

    def __init__(self, channels: int, d_state: int = 16, dtype=jnp.float32):
        self.channels = int(channels)
        self.d_state = int(d_state)
        self.dtype = jnp.dtype(dtype).name

    def infer_shapes(self, in_specs):
        return [TensorSpec(in_specs[0].shape, jnp.dtype(self.dtype))]

    def params(self) -> List[ParamSpec]:
        f32 = jnp.dtype("float32")
        c, n = self.channels, self.d_state
        # Mamba's own initialisation: A = -(1 .. N) per channel, D = 1, and
        # a step bias whose softplus spreads log-uniformly over [1e-3, 1e-1]
        a_log = _init(lambda s: jnp.broadcast_to(
            jnp.log(jnp.arange(1, s[1] + 1, dtype=jnp.float32)), s))
        dt_bias = _init(lambda s: jnp.log(jnp.expm1(jnp.exp(jnp.linspace(
            math.log(1e-3), math.log(1e-1), s[0])))))
        return [ParamSpec("A_log", TensorSpec((c, n), f32), a_log,
                          pin_dtype=True),
                ParamSpec("D", TensorSpec((c,), f32), _init(jnp.ones),
                          pin_dtype=True),
                ParamSpec("dt_bias", TensorSpec((c,), f32), dt_bias,
                          pin_dtype=True)]

    def state_specs(self, max_requests, max_seq_len, max_spec_tokens=0,
                    head_axes=()):
        shape = (max_requests + 1, self.channels, self.d_state)
        return {"ssm": (shape, "float32", TensorSharding.replicated(3))}

    def flops(self, in_specs):
        return 9 * in_specs[0].size * self.d_state

    def lower(self, ctx, inputs, params):
        bc, state = _require(ctx, self.type_name)
        xs, dt, b_in, c_in = (a.astype(jnp.float32) for a in inputs)
        hs = state["ssm"]
        seg = Segments(_flat(bc), hs.shape[0] - 1)
        delta = jax.nn.softplus(dt + params["dt_bias"])
        a = -jnp.exp(params["A_log"])
        dx = delta * xs
        path = "rows_at_once"
        if ctx.extras.get("one_row_per_request"):
            h = jnp.where(seg.fresh[:, None, None], 0.0, hs[seg.rows])
            h = (jnp.exp(delta[:, :, None] * a) * h
                 + dx[:, :, None] * b_in[:, None, :])
            # (a float32 matmul would round its operands to bf16 on the MXU)
            y = jnp.sum(h * c_in[:, None, :], axis=-1)
            with jax.named_scope("state_write"):
                hs = _set_rows(hs, seg.store, h)
        elif ctx.extras.get("pallas_decode") and self.channels % LANE == 0:
            # a prompt chunk or a flat step: the rows in order, in ONE
            # kernel that keeps the state on chip across a segment's rows
            from ..ops.pallas.selective_scan import selective_scan_rows

            y, hs = selective_scan_rows(
                delta, dx, b_in, c_in, a, hs, seg.start, seg.fresh, seg.rows,
                seg.store,
                interpret=bool(ctx.extras.get("pallas_interpret")))
            path = "kernel"
        else:
            # the CPU oracle of the kernel: one scan trip per row
            def row(carry, r):
                h, hs = carry
                delta_r, dx_r, b_r, c_r, start, fresh, at, store = r
                own = jax.lax.dynamic_index_in_dim(hs, at, keepdims=False)
                h = jnp.where(start, jnp.where(fresh, 0.0, own), h)
                h = jnp.exp(delta_r[:, None] * a) * h + dx_r[:, None] * b_r
                hs = jax.lax.dynamic_update_slice(
                    hs, h[None], (store, jnp.int32(0), jnp.int32(0)))
                return (h, hs), jnp.sum(h * c_r, axis=-1)

            (_, hs), y = jax.lax.scan(
                row, (jnp.zeros(hs.shape[1:], hs.dtype), hs),
                (delta, dx, b_in, c_in, seg.start, seg.fresh, seg.rows,
                 seg.store), unroll=8)
            path = "row_scan"
        paths = ctx.extras.get("attention_paths")
        if paths is not None:
            # the decode scan's batch is a BatchConfig too: told apart, so
            # that its path and the flat step's are both counted
            batch = ("one_row_per_request" if path == "rows_at_once"
                     else type(bc).__name__)
            paths[(self.type_name, batch)] = path
        ctx.extras["state_out"] = {"ssm": hs}
        return [(y + params["D"] * xs).astype(self.dtype)]


def diff_lambda_init(layer: int) -> float:
    """The differential attention's depth-dependent constant."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


class SlotCacheAttention(_SlotStateOp):
    """What the attention ops that keep their K/V per slot share: the cache
    itself — a full-length plane (``mode`` ``full``: ``k``/``v`` ``[rows,
    heads, max_seq, D]``), a RING of the last positions (``window``:
    ``wk``/``wv`` of ``ring_len`` slots, position ``p`` at slot ``p %
    ring_len``; a query at ``t`` sees ``t - window + 1 .. t``) or another
    node's (``cross``: queries only, over what ``state_owner`` wrote) —,
    its write, the attention over it by the kernels or by XLA, and the
    skeleton of ``lower`` (``qkv_proj``, ``attend`` with ``kv_write`` inside
    it, the subclass's ``_combine``, ``o_proj``).  ONE ring implementation:
    the differential window layers (:class:`WindowDiffAttention`) and the
    plain ones (:class:`SlidingWindowAttention`) are both this.

    A subclass says how many heads the cache holds (``cache_heads``), how
    many kernel query heads read each (``q_per_cache_head``) and their size
    (``cache_dim``), projects (``_project`` -> ``q [T, cache_heads,
    q_per_cache_head, cache_dim]``, ``k``/``v`` ``[T, cache_heads,
    cache_dim]``) and turns the kernels' output into ``o_proj``'s input
    (``_combine``)."""

    mode = "full"
    # a window layer's prompt tiles through ``prefill_attention`` with the
    # window's lower bound in the kernel; off: XLA per tile (the
    # differential window layers', whose programs PR 50 left as they were)
    window_prefill_kernel = False

    @property
    def path_kind(self) -> str:
        """The op's key in ``attention_paths``."""
        return f"{self.mode}_attention"

    # ---- state ---------------------------------------------------------
    def ring_len(self, max_seq_len: int) -> int:
        """Slots of a window layer's ring: the window plus the widest step
        that writes before it attends, rounded up to the kernels' granule —
        and never more than a full-length cache would hold."""
        pad = lambda n: -(-n // LANE) * LANE
        widest = getattr(self, "cost_max_tokens", None) or max_seq_len
        return min(pad(self.window + widest), pad(max_seq_len))

    def state_specs(self, max_requests, max_seq_len, max_spec_tokens=0,
                    head_axes=()):
        if self.mode == "cross":
            return {}
        seq = max_seq_len if self.mode == "full" \
            else self.ring_len(max_seq_len)
        shape = (max_requests + 1, self.cache_heads, seq, self.cache_dim)
        sh = TensorSharding.replicated(4)
        return {n: (shape, self.dtype, sh) for n in self._state_names}

    @property
    def _state_names(self):
        return ("wk", "wv") if self.mode == "window" else ("k", "v")

    # ---- compute -------------------------------------------------------
    @jax.named_scope("kv_write")
    def _write(self, kc, vc, k, v, bc, seg, tiled, extras):
        """This step's keys and values into the cache (a ring for a window
        layer: position ``p`` at slot ``p % ring``)."""
        base = _flat(bc)
        ring = kc.shape[2] if self.mode == "window" else 0
        pos = base.token_position % ring if ring else base.token_position
        if not tiled:
            return put_rows(kc, vc, k, v, seg.rows, pos, extras)
        # a tiled prefill chunk: one block per request-homogeneous tile
        # (ops.put_blocks says why not a scatter).  A tile starts
        # tile-aligned and the ring is whole tiles, so a BLOCK never wraps
        # (a chunk may: its tiles past the ring's end land on its first
        # slots); its tail pads write zeros at positions no query of this
        # chunk sees, which a later chunk overwrites before any does.
        bq = bc.tile_size
        return put_blocks(
            kc, vc, k, v, *tile_coords(seg.rows, pos, bq, kc.shape[0] - 1),
            bq, extras)

    def _attend_xla(self, q, kc, vc, rows, pos):
        """Plain attention of query groups against their slot's cache:
        ``q [G, B, cached heads, heads on each, D]``, the group's cache row
        ``rows [G]``, positions ``pos [G, B]``.  A flat row is a group of
        one; a prefill tile is a group of ``tile`` rows, which reads its
        slot's ring ONCE.  The CPU oracle of the kernels, and on the chip
        too the prefill path of the window layers that keep
        ``window_prefill_kernel`` off."""
        kr, vr = kc[rows], vc[rows]                  # [G, heads, S, D]
        s = kr.shape[2]
        sc = jnp.einsum("gbphd,gpsd->gphbs", q, kr,
                        preferred_element_type=jnp.float32)
        sc = sc * self.scaling_factor
        slot = jnp.arange(s, dtype=jnp.int32)
        if self.mode == "window":
            # slot s holds the newest position <= t that lands on it; it is
            # in the window if that is fewer than min(t + 1, window) back
            age = (pos[..., None] % s - slot) % s
            mask = age < jnp.minimum(pos + 1, self.window)[..., None]
        else:
            mask = slot <= pos[..., None]
        sc = jnp.where(mask[:, None, None], sc, NEG_INF)
        w = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("gphbs,gpsd->gbphd", w, vr.astype(w.dtype),
                          preferred_element_type=jnp.float32)

    def _attend(self, q, kc, vc, bc, seg, ctx, tiled):
        """``[T, cached heads, heads on each, D]``: each head's softmax over
        its slot's cache times the cached head's value — in the kernels'
        output type (the queries'; their accumulator is float32) or float32
        from XLA —, and the path taken."""
        from ..ops.pallas.attention import decode_attention, prefill_attention

        base = _flat(bc)
        t = q.shape[0]
        nq, d = self.q_per_cache_head, self.cache_dim
        nreq = kc.shape[0] - 1
        pallas = bool(ctx.extras.get("pallas_decode"))
        interp = bool(ctx.extras.get("pallas_interpret"))
        if tiled:
            bq = bc.tile_size
            g = t // bq
            rows = jnp.min(seg.rows.reshape(g, bq), axis=1)
            pos = base.token_position.reshape(g, bq)
            if self.mode == "window" and not self.window_prefill_kernel:
                out = self._attend_xla(
                    q.reshape(g, bq, self.cache_heads, nq, d), kc, vc, rows,
                    pos)
                return out.reshape(t, self.cache_heads, nq, d), "xla_tile"
            out = prefill_attention(
                q.reshape(g, bq, self.cache_heads * nq, d), kc, vc, rows,
                pos[:, 0], scale=self.scaling_factor, interpret=interp,
                window=self.window)
            note_prefill_operands(ctx.extras, self.path_kind, q, kc)
            return out.reshape(t, self.cache_heads, nq, d), "prefill_attention"
        if pallas:
            # pads stream one block, not a stale row's whole prefix
            pos = jnp.where(seg.rows == nreq, 0, base.token_position)
            out = decode_attention(
                q.reshape(t, self.cache_heads * nq, d), kc, vc, seg.rows, pos,
                scale=self.scaling_factor, interpret=interp,
                window=self.window)
            note_decode_block(ctx.extras, self.path_kind, type(bc).__name__,
                              kc, window=self.window)
            return out.reshape(t, self.cache_heads, nq, d), "decode_attention"
        out = self._attend_xla(q[:, None], kc, vc, seg.rows,
                               base.token_position[:, None])
        return out[:, 0], "xla"

    def lower(self, ctx, inputs, params):
        bc, state = _require(ctx, self.type_name)
        x = inputs[0]
        names = self._state_names
        kc, vc = state[names[0]], state[names[1]]
        seg = Segments(_flat(bc), kc.shape[0] - 1)
        with jax.named_scope("qkv_proj"):
            q, k, v = self._project(x, params, _flat(bc).token_position)
        # a tiled prefill chunk takes the per-tile paths (block writes, the
        # prefill kernel) where the kernels are on; off them it is a flat
        # batch like any other (the CPU oracle)
        tiled = (isinstance(bc, PrefillBatchConfig)
                 and bool(ctx.extras.get("pallas_decode")))
        with jax.named_scope("attend"):
            if self.mode != "cross":
                kc, vc = self._write(kc, vc, k, v, bc, seg, tiled,
                                     ctx.extras)
                ctx.extras["state_out"] = {names[0]: kc, names[1]: vc}
            out, path = self._attend(q, kc, vc, bc, seg, ctx, tiled)
            paths = ctx.extras.get("attention_paths")
            if paths is not None:
                paths[(self.path_kind, type(bc).__name__)] = path
        o = self._combine(out, params, x)
        with jax.named_scope("o_proj"):
            o_w = dequant(params["o_proj"], params.get("o_proj_scale"),
                          o.dtype)
            y = jnp.dot(o, o_w, preferred_element_type=jnp.float32)
            if "o_bias" in params:
                y = y + params["o_bias"]
            return [y.astype(self.dtype)]


class DiffAttention(SlotCacheAttention):
    """Differential attention over flat token batches, cached per slot (the
    three modes are registered as classes of their own below, so that a
    device trace names the window layers, the cache owner and its readers
    apart: ``<OpClass>.<node>``).

    Heads come in pairs: query pair ``p`` (heads ``q1, q2`` of size ``hd``)
    reads K/V pair ``p // (pairs per K/V pair)``; ``A1 = softmax(q1 k1' /
    sqrt(hd))``, ``A2`` likewise from ``q2, k2`` under the same mask;
    ``o = RMSNorm((A1 - lam * A2) [v1|v2]) * (1 - lam0)`` with ``lam =
    exp(lq1 . lk1) - exp(lq2 . lk2) + lam0``; the pairs' ``2 hd``-wide
    outputs concatenate into ``o_proj``.

    How it runs on the kernels there are: a K/V pair is cached as ONE head of
    size ``2 hd`` (``[k1|k2]``, ``[v1|v2]``: the same bytes), and a query
    pair becomes two heads of that size, ``[q1|0]`` and ``[0|q2]`` — their
    scores against ``[k1|k2]`` are exactly ``q1 . k1`` and ``q2 . k2``, so
    one ordinary attention call returns ``A1 [v1|v2]`` and ``A2 [v1|v2]``
    and nothing is left out (the zero halves double the score FLOPs, which a
    bandwidth-bound decode does not feel).

    ``mode``: ``full`` (its cache ``k``/``v`` is ``[rows, pairs, max_seq,
    2 hd]``), ``window`` (a ring ``wk``/``wv`` of ``ring_len`` slots,
    position ``p`` at slot ``p % ring_len``; a query at ``t`` sees
    ``t - window + 1 .. t``), ``cross`` (queries only; reads the ``k``/``v``
    of ``state_owner``, all positions ``<= t``).
    """

    mode = "full"

    def __init__(self, embed_dim: int, num_q_heads: int, num_kv_heads: int,
                 head_dim: int, layer: int, window: int = 0,
                 state_owner: Optional[str] = None, eps: float = 1e-5,
                 dtype=jnp.float32):
        if num_q_heads % 2 or num_kv_heads % 2 or \
                (num_q_heads // 2) % (num_kv_heads // 2):
            raise ValueError("differential attention pairs its heads")
        if (self.mode == "cross") != bool(state_owner):
            raise ValueError("cross mode reads a state_owner's cache; the "
                             "other modes own theirs")
        if (self.mode == "window") != bool(window):
            raise ValueError("window mode needs its window")
        self.embed_dim = int(embed_dim)
        self.num_q_heads = int(num_q_heads)
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.layer = int(layer)
        self.window = int(window)
        self.state_owner = state_owner
        self.eps = float(eps)
        self.dtype = jnp.dtype(dtype).name
        self.kv_pairs = self.num_kv_heads // 2
        self.q_per_pair = self.num_q_heads // self.kv_pairs   # kernel heads
        self.scaling_factor = 1.0 / math.sqrt(self.head_dim)

    # ---- shapes / params ----------------------------------------------
    @property
    def pair_dim(self) -> int:
        return 2 * self.head_dim

    # what the shared cache code calls them: a K/V pair is ONE cached head
    cache_heads = property(lambda self: self.kv_pairs)
    q_per_cache_head = property(lambda self: self.q_per_pair)
    cache_dim = pair_dim

    @property
    def _qkv_cols(self) -> int:
        cols = self.q_per_pair * self.head_dim
        return cols if self.mode == "cross" else cols + 2 * self.pair_dim

    def infer_shapes(self, in_specs):
        return [TensorSpec(in_specs[0].shape, jnp.dtype(self.dtype))]

    def params(self) -> List[ParamSpec]:
        dt, f32 = jnp.dtype(self.dtype), jnp.dtype("float32")
        e, hd = self.embed_dim, self.head_dim
        proj = "q" if self.mode == "cross" else "qkv"
        # per K/V pair: its query heads (pair-major, q1 before q2), then
        # [k1|k2] and [v1|v2] — one GEMM, sliced without moving data
        ps = [
            ParamSpec(proj, TensorSpec((e, self.kv_pairs, self._qkv_cols),
                                       dt)),
            ParamSpec(f"{proj}_bias",
                      TensorSpec((self.kv_pairs, self._qkv_cols), dt),
                      _init(jnp.zeros)),
            ParamSpec("o_proj",
                      TensorSpec((self.num_q_heads * hd, e), dt)),
            ParamSpec("o_bias", TensorSpec((e,), dt), _init(jnp.zeros)),
            ParamSpec("subln", TensorSpec((self.pair_dim,), dt),
                      _init(jnp.ones)),
        ]
        normal = lambda key, shape, dtype: 0.1 * jax.random.normal(
            key, shape, dtype)
        ps += [ParamSpec(n, TensorSpec((hd,), f32), normal, pin_dtype=True)
               for n in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")]
        return ps

    def flops(self, in_specs):
        t = in_specs[0].shape[0]
        return 2 * t * self.embed_dim * (
            self.kv_pairs * self._qkv_cols + self.num_q_heads * self.head_dim)

    # ---- compute -------------------------------------------------------
    def _project(self, x, params, pos):
        del pos     # no positional term: the Mamba layers carry position
        proj = "q" if self.mode == "cross" else "qkv"
        # weight-only int8 (serve/quant.py quantises ``qkv`` and ``o_proj``)
        w = dequant(params[proj], params.get(f"{proj}_scale"), x.dtype)
        out = jnp.einsum("te,epc->tpc", x, w,
                         preferred_element_type=jnp.float32).astype(x.dtype)
        out = out + params[f"{proj}_bias"]
        t, hd, nq = x.shape[0], self.head_dim, self.q_per_pair
        q = out[:, :, : nq * hd].reshape(t, self.kv_pairs, nq // 2, 2, hd)
        zero = jnp.zeros_like(q[:, :, :, 0])
        # [q1|0] and [0|q2]: two heads of the cached pair's size
        q = jnp.stack(
            [jnp.concatenate([q[:, :, :, 0], zero], axis=-1),
             jnp.concatenate([zero, q[:, :, :, 1]], axis=-1)],
            axis=3).reshape(t, self.kv_pairs, nq, self.pair_dim)
        if self.mode == "cross":
            return q, None, None
        k = out[:, :, nq * hd: nq * hd + self.pair_dim]
        v = out[:, :, nq * hd + self.pair_dim:]
        return q, k, v

    def _combine(self, out, params, x):
        t = x.shape[0]
        with jax.named_scope("diff_combine"):
            lam0 = diff_lambda_init(self.layer)
            lam = (jnp.exp(jnp.sum(params["lambda_q1"] * params["lambda_k1"]))
                   - jnp.exp(jnp.sum(params["lambda_q2"]
                                     * params["lambda_k2"])) + lam0)
            o = out.astype(jnp.float32).reshape(
                t, self.kv_pairs, self.q_per_pair // 2, 2, self.pair_dim)
            o = o[:, :, :, 0] - lam * o[:, :, :, 1]
            o = o * jax.lax.rsqrt(
                jnp.mean(o * o, axis=-1, keepdims=True) + self.eps)
            o = o * params["subln"].astype(jnp.float32) * (1.0 - lam0)
            return o.astype(x.dtype).reshape(
                t, self.num_q_heads * self.head_dim)


@register_op
class FullDiffAttention(DiffAttention):
    """The one layer with a full-length cache, which it owns."""

    type_name = "full_diff_attention"
    mode = "full"


@register_op
class WindowDiffAttention(DiffAttention):
    """A sliding-window layer: its cache is a ring."""

    type_name = "window_diff_attention"
    mode = "window"


@register_op
class CrossDiffAttention(DiffAttention):
    """Queries only, over the cache its ``state_owner`` wrote."""

    type_name = "cross_diff_attention"
    mode = "cross"


DIFF_ATTENTION = {c.mode: c for c in (FullDiffAttention, WindowDiffAttention,
                                      CrossDiffAttention)}


@register_op
class SlidingWindowAttention(SlotCacheAttention):
    """PLAIN grouped-query attention over a sliding window, its cache a ring
    (``cohere2_moe``'s ``sliding_attention`` layers): ``q = x W_q``, ``k``,
    ``v`` likewise, no bias, rotary over the whole head — on interleaved
    pairs ``(2i, 2i + 1)`` (``rope_interleaved``: ``rope_gptj``) or half
    against half —, scale ``1 / sqrt(head size)``, query head ``i`` on K/V
    head ``i // (heads per K/V head)``, key ``j`` visible to query ``t`` iff
    ``t - window < j <= t``.  The fused projection has
    ``IncMultiHeadSelfAttention``'s layout (``qkv [embed, K/V heads, heads
    on each + 2, head size]``, ``o_proj``), so weight-only int8 and a
    parameter table written for that op fit this one.

    State kind ``kv_window`` (kv_allocator.py): ``ring_len`` slots a K/V
    head and slot, whatever ``max_seq_len``.  The decode scan reads it
    through ``decode_attention``'s ring path; a tiled prompt chunk writes it
    by ``kv_block_write`` and reads it through ``prefill_attention`` with
    the window's lower bound in the kernel."""

    type_name = "sliding_window_attention"
    mode = "window"
    window_prefill_kernel = True
    path_kind = type_name

    def __init__(self, embed_dim: int, num_q_heads: int, num_kv_heads: int,
                 head_dim: int, window: int, rope_theta: float = 10000.0,
                 rope_interleaved: bool = False, dtype=jnp.float32):
        if num_q_heads % num_kv_heads:
            raise ValueError("num_q_heads must be a multiple of num_kv_heads")
        if window <= 0:
            raise ValueError("a sliding-window layer needs its window")
        self.embed_dim = int(embed_dim)
        self.num_q_heads = int(num_q_heads)
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.window = int(window)
        self.rope_theta = float(rope_theta)
        self.rope_interleaved = bool(rope_interleaved)
        self.dtype = jnp.dtype(dtype).name
        self.scaling_factor = 1.0 / math.sqrt(self.head_dim)

    cache_heads = property(lambda self: self.num_kv_heads)
    q_per_cache_head = property(
        lambda self: self.num_q_heads // self.num_kv_heads)
    cache_dim = property(lambda self: self.head_dim)
    launch_reads = ("window",)

    def launch_counts(self, decode, prompt, layers, counted):
        """``ring_ctx_sum``: what the decode rows' RING layers read at
        launch, ``min(context, window)`` a row beside ``ctx_sum``'s whole
        contexts; ``prompt_ring_ctx_sum``: ``min(position + 1, window)`` a
        prompt row, the window kernel's least work, beside
        ``prompt_ctx_sum``."""
        w, args = self.window, {}
        if decode is not None:
            args["ring_ctx_sum"] = sum(min(lo + 1, w)
                                       for lo, hi in decode if hi > lo)
        if prompt is not None:
            tri = lambda n: n * (n + 1) // 2    # 1 + 2 + .. + n
            args["prompt_ring_ctx_sum"] = sum(
                tri(min(hi, w)) - tri(min(lo, w))
                + w * (max(hi, w) - max(lo, w)) for lo, hi in prompt)
        return args, {}

    def infer_shapes(self, in_specs):
        return [TensorSpec(in_specs[0].shape, jnp.dtype(self.dtype))]

    def params(self) -> List[ParamSpec]:
        dt = jnp.dtype(self.dtype)
        return [
            ParamSpec("qkv", TensorSpec(
                (self.embed_dim, self.num_kv_heads,
                 self.q_per_cache_head + 2, self.head_dim), dt)),
            ParamSpec("o_proj", TensorSpec(
                (self.num_q_heads * self.head_dim, self.embed_dim), dt)),
        ]

    def flops(self, in_specs):
        t = in_specs[0].shape[0]
        cols = (2 * self.num_q_heads + 2 * self.num_kv_heads) * self.head_dim
        return 2 * t * self.embed_dim * cols

    def _project(self, x, params, pos):
        w = dequant(params["qkv"], params.get("qkv_scale"), x.dtype)
        qkv = jnp.einsum("te,ekgd->tkgd", x, w,
                         preferred_element_type=jnp.float32).astype(x.dtype)
        nq = self.q_per_cache_head
        rope = lambda a: apply_rope(a, pos, self.rope_theta,
                                    interleaved=self.rope_interleaved)
        return rope(qkv[:, :, :nq]), rope(qkv[:, :, nq]), qkv[:, :, nq + 1]

    def _combine(self, out, params, x):
        return out.astype(x.dtype).reshape(
            x.shape[0], self.num_q_heads * self.head_dim)


@register_op
class LatentAttention(_SlotStateOp):
    """Multi-head LATENT attention (MLA: ``deepseek_v2``'s every layer, and
    ``kimi_linear``'s one layer in four) over flat token batches.  Per
    position the layer caches ONE latent ``c = RMSNorm(x W_kv_a[:, :r])``
    (``r = kv_rank``) and ONE shared key part ``k_r = x W_kv_a[:, r:]``
    (``rope_dim`` wide), shared by ALL heads — ``(r + rope_dim)`` values a
    position and nothing per head (1 152 B in bf16 at 512 + 64, where the
    same 16 heads as plain K/V would hold 10 240 B).

    Head ``i``: ``[q_n | q_r] = x W_q`` (``nope_dim`` + ``rope_dim``);
    ``[k_n,i | v_i] = c W_kv_b`` per head; score ``s = scale (q_n,i . k_n,i
    + q_r,i . k_r)``, ``scale = (nope_dim + rope_dim)^-1/2 m^2`` with YaRN's
    ``m = yarn_mscale(factor, mscale_all_dim)`` (1 without ``rope_scaling``);
    ``o_i = softmax(s) v_i``; the heads' ``v_dim``-wide outputs concatenate
    into ``o_proj``.  ``use_rope`` (``deepseek_v2``): ``q_r`` and ``k_r`` are
    ROTATED, on INTERLEAVED pairs with YaRN's frequencies (``rope_scaling``),
    the ``rope_dim`` part only.  ``use_rope=False`` (``kimi_linear``:
    ``mla_use_nope``): neither part is rotated — the layer has no positional
    term at all (the delta-rule layers around it carry position) and the
    "rope" plane is a plain second key part; the cache, the kernel and both
    forms are the same.

    Two forms, one result.  ABSORBED: with ``W_kv_b`` split per head into
    ``U_k [r, nope_dim]`` and ``U_v [r, v_dim]``, ``q_lat = q_n U_k'`` (in
    ``qkv_proj``), ``s = scale (q_lat . c + q_r . k_r)``, ``o_lat =
    softmax(s) c`` and ``o = o_lat U_v`` (in ``o_proj``): the latent is key
    AND value, read once — ``2 H (2 r + rope_dim)`` operations a (query,
    position) pair.  MATERIALISED: ``k_n`` and ``v`` expanded from the
    cached latents, then plain attention — ``2 H (nope_dim + rope_dim +
    v_dim)`` a pair plus ``2 r H (nope_dim + v_dim)`` a cached position
    expanded.  At the published widths (16 heads, 512 + 64, 128 / 128) that
    is 34.8k against 10.2k a pair plus 4.2M a position and query group: a
    decode row (one query a group) is absorbed, always — through
    ``decode_attention``'s latent kernel where the kernels are on, which
    leaves the latents in HBM and copies each row's LIVE blocks itself,
    once for score and value, the next copy in flight while a block is
    scored (a pad row, sent at position 0, costs one 128-position piece).
    A prompt chunk
    takes ``prompt_form`` by XLA, a request-homogeneous tile of queries
    against its slot's cache: ``absorbed`` (the default: a tile of 128
    queries x 16 heads fills the matrix unit's rows, and nothing per head is
    written to memory) or ``materialised`` (fewer operations from ~0.2 of
    the cache's length on, but the prefix's K and V per head re-expanded
    each chunk, 10 KB a position and tile in HBM) — one tile after the
    other, against the shortest of ``PROMPT_SPANS`` prefixes of the cache
    that holds the chunk's furthest position (XLA scores what it is given
    whole: no causal clamp).  The CPU oracle (kernels off) runs every batch
    in ``prompt_form``.

    State kind ``kv_latent`` (kv_allocator.py): planes ``ckv [rows, 1,
    max_seq, r]`` and ``kpe [rows, 1, max_seq, rope_dim]`` — two planes, not
    one padded to whole lanes, so that the allocated bytes are the
    mechanism's (a narrow plane's lanes are the layout's to pad).  The
    chunk's block writes go down the ``dynamic_update_slice`` chain
    (``kv_block_write`` takes K and V planes of one lane-multiple width)."""

    type_name = "latent_attention"
    path_kind = type_name
    # two planes a position, shared by all heads, that are not K and V
    # planes: what each deployment option lacks for THEM
    # (inference_manager.refuse_unsupported_slot_state)
    refusal_order = 0
    refusals = {
        "kv_page_size": (
            "; for a latent cache, pages, copy-on-write, spill and "
            "swap_signature over a latent plane and a rotated-key plane of "
            "another width (kv_paged.py pools K and V planes of one head "
            "size), and a paged mode of the latent decode kernel"),
        "kv_dtype": (
            "; for a latent cache, scale planes beside the latent and the "
            "rotated key part (one latent is key AND value of every head: a "
            "per-vector scale folds into neither contraction as the K/V "
            "kernels' do) and the latent kernel mode that reads them"),
        "max_spec_tokens": (
            "; a latent cache has no spec-tree buffers, commit copy or "
            "tree-mask kernel over latents"),
        "tp": (
            "; for latent attention a rule that shards the absorbed heads "
            "(W_q, the per-head up-projections, W_o's rows) with the latent "
            "cache replicated"),
        "pipelined": " (not a latent cache's two planes)",
    }
    # the projections weight-only int8 replaces (serve/quant.py)
    int8_params = ("q_proj", "kv_a", "kv_b", "o_proj")

    def __init__(self, embed_dim: int, num_heads: int, nope_dim: int,
                 rope_dim: int, v_dim: int, kv_rank: int,
                 rope_theta: float = 10000.0,
                 rope_scaling: Optional[dict] = None, eps: float = 1e-6,
                 prompt_form: str = "absorbed", dtype=jnp.float32,
                 use_rope: bool = True):
        if prompt_form not in ("absorbed", "materialised"):
            raise ValueError("prompt_form is 'absorbed' or 'materialised'")
        if rope_scaling and rope_scaling.get(
                "type", rope_scaling.get("rope_type")) != "yarn":
            raise ValueError("latent attention knows plain rotary and YaRN "
                             f"(rope_scaling {rope_scaling!r})")
        self.embed_dim = int(embed_dim)
        self.num_heads = int(num_heads)
        self.nope_dim = int(nope_dim)
        self.rope_dim = int(rope_dim)
        self.v_dim = int(v_dim)
        self.kv_rank = int(kv_rank)
        self.rope_theta = float(rope_theta)
        self.rope_scaling = dict(rope_scaling) if rope_scaling else None
        self.eps = float(eps)
        self.prompt_form = prompt_form
        self.use_rope = bool(use_rope)
        self.dtype = jnp.dtype(dtype).name
        m = 1.0
        if self.rope_scaling and self.rope_scaling.get("mscale_all_dim"):
            m = yarn_mscale(float(self.rope_scaling["factor"]),
                            float(self.rope_scaling["mscale_all_dim"]))
        self.scaling_factor = m * m / math.sqrt(self.nope_dim + self.rope_dim)

    # ---- shapes / params ----------------------------------------------
    def infer_shapes(self, in_specs):
        return [TensorSpec(in_specs[0].shape, jnp.dtype(self.dtype))]

    def params(self) -> List[ParamSpec]:
        dt = jnp.dtype(self.dtype)
        e, h, r = self.embed_dim, self.num_heads, self.kv_rank
        return [
            ParamSpec("q_proj", TensorSpec(
                (e, h, self.nope_dim + self.rope_dim), dt)),
            ParamSpec("kv_a", TensorSpec((e, r + self.rope_dim), dt)),
            ParamSpec("kv_norm", TensorSpec((r,), dt), _init(jnp.ones)),
            ParamSpec("kv_b", TensorSpec(
                (r, h, self.nope_dim + self.v_dim), dt)),
            ParamSpec("o_proj", TensorSpec((h * self.v_dim, e), dt)),
        ]

    def flops(self, in_specs):
        t = in_specs[0].shape[0]
        e, h, r = self.embed_dim, self.num_heads, self.kv_rank
        return 2 * t * (e * h * (self.nope_dim + self.rope_dim)
                        + e * (r + self.rope_dim)
                        + h * r * (self.nope_dim + self.v_dim)
                        + h * self.v_dim * e)

    def state_specs(self, max_requests, max_seq_len, max_spec_tokens=0,
                    head_axes=()):
        sh = TensorSharding.replicated(4)
        rows = max_requests + 1
        return {"ckv": ((rows, 1, max_seq_len, self.kv_rank), self.dtype, sh),
                "kpe": ((rows, 1, max_seq_len, self.rope_dim), self.dtype,
                        sh)}

    # ---- compute -------------------------------------------------------
    def _weight(self, params, name, dtype):
        return dequant(params[name], params.get(f"{name}_scale"), dtype)

    def _project(self, x, params, pos):
        """``(q_n [T, H, nope], q_r [T, H, rope], c [T, r] normed, k_r [T,
        rope])``, ``q_r`` and ``k_r`` rotated where the layer rotates."""
        r = self.kv_rank
        rope = (lambda a: apply_rope(a, pos, self.rope_theta,
                                     interleaved=True,
                                     yarn=self.rope_scaling)) \
            if self.use_rope else (lambda a: a)
        q = jnp.einsum("te,ehc->thc", x, self._weight(params, "q_proj",
                                                      x.dtype),
                       preferred_element_type=jnp.float32).astype(x.dtype)
        ckr = jnp.dot(x, self._weight(params, "kv_a", x.dtype),
                      preferred_element_type=jnp.float32)
        c = _rms_norm(ckr[:, :r], params["kv_norm"].astype(jnp.float32),
                      self.eps).astype(x.dtype)
        k_r = rope(ckr[:, r:].astype(x.dtype))
        return q[..., :self.nope_dim], rope(q[..., self.nope_dim:]), c, k_r

    @jax.named_scope("kv_write")
    def _write(self, ckv, kpe, c, k_r, bc, seg, tiled, extras):
        """This step's latents and shared key parts into the two planes."""
        pos = _flat(bc).token_position
        c, k_r = c[:, None], k_r[:, None]           # one cached "head"
        if not tiled:
            return put_rows(ckv, kpe, c, k_r, seg.rows, pos, extras)
        # a tiled prompt chunk: one block per request-homogeneous tile and
        # plane, tail pads as zeros (ops.put_blocks says why not a scatter)
        bq = bc.tile_size
        rows, start, count = tile_coords(seg.rows, pos, bq, ckv.shape[0] - 1)
        paths = extras.get("attention_paths")
        if paths is not None:
            paths[("kv_block_write", type(bc).__name__)] = "dus_chain"
        return (_block_chain(ckv, _tile_blocks(c, count, bq, ckv.dtype),
                             rows, start),
                _block_chain(kpe, _tile_blocks(k_r, count, bq, kpe.dtype),
                             rows, start))

    def _attend_xla(self, q_n, q_r, kv_b, ckv, kpe, rows, pos, form,
                    length=None):
        """Query groups against their slot's latent cache by XLA: ``q_n [G,
        B, H, nope]`` (``form`` ``materialised``) or the absorbed ``q_lat
        [G, B, H, r]``, ``q_r [G, B, H, rope]``, the group's cache row
        ``rows [G]``, positions ``pos [G, B]``; ``length``: the cache's
        first positions alone (every ``pos`` below it).  Returns ``[G, B, H,
        v_dim]`` (materialised) or the latent-wide ``o_lat [G, B, H, r]``,
        float32."""
        # [G, S, r], [G, S, rope]
        cr, kr = ckv[rows, 0, :length], kpe[rows, 0, :length]
        f32 = jnp.float32
        sc = jnp.einsum("gbhr,gsr->ghbs", q_r, kr, preferred_element_type=f32)
        if form == "materialised":
            kv = jnp.einsum("gsc,chn->gshn", cr, kv_b,
                            preferred_element_type=f32).astype(cr.dtype)
            keys, values = kv[..., :self.nope_dim], kv[..., self.nope_dim:]
            sc = sc + jnp.einsum("gbhn,gshn->ghbs", q_n, keys,
                                 preferred_element_type=f32)
        else:
            sc = sc + jnp.einsum("gbhc,gsc->ghbs", q_n, cr,
                                 preferred_element_type=f32)
        seen = jnp.arange(cr.shape[1], dtype=jnp.int32) <= pos[..., None]
        sc = jnp.where(seen[:, None], sc * self.scaling_factor, NEG_INF)
        w = jax.nn.softmax(sc, axis=-1)
        if form == "materialised":
            return jnp.einsum("ghbs,gshv->gbhv", w, values.astype(w.dtype),
                              preferred_element_type=f32)
        return jnp.einsum("ghbs,gsc->gbhc", w, cr.astype(w.dtype),
                          preferred_element_type=f32)

    def lower(self, ctx, inputs, params):
        from ..ops.pallas.attention import decode_attention

        bc, state = _require(ctx, self.type_name)
        x = inputs[0]
        base = _flat(bc)
        t, h = x.shape[0], self.num_heads
        ckv, kpe = state["ckv"], state["kpe"]
        nreq = ckv.shape[0] - 1
        seg = Segments(base, nreq)
        pallas = bool(ctx.extras.get("pallas_decode"))
        tiled = isinstance(bc, PrefillBatchConfig) and pallas
        # one query a group reads its whole prefix for itself: absorbed,
        # whatever the prompt form (the class's docstring has the counts)
        form = "absorbed" if pallas and not tiled else self.prompt_form
        kv_b = self._weight(params, "kv_b", x.dtype)     # [r, H, nope + v]
        with jax.named_scope("qkv_proj"):
            q_n, q_r, c, k_r = self._project(x, params, base.token_position)
            if form == "absorbed":
                q_n = jnp.einsum(
                    "thn,chn->thc", q_n, kv_b[..., :self.nope_dim],
                    preferred_element_type=jnp.float32).astype(x.dtype)
        with jax.named_scope("attend"):
            ckv, kpe = self._write(ckv, kpe, c, k_r, bc, seg, tiled,
                                   ctx.extras)
            ctx.extras["state_out"] = {"ckv": ckv, "kpe": kpe}
            if tiled:
                bq = bc.tile_size
                g = t // bq
                pos = jnp.where(seg.live, base.token_position, 0)
                per_tile = (q_n.reshape(g, bq, h, -1),
                            q_r.reshape(g, bq, h, -1),
                            jnp.min(seg.rows.reshape(g, bq), axis=1),
                            pos.reshape(g, bq))

                def tiles(length):
                    # one tile after the other: a chunk's float32 scores
                    # are [heads, tile, length] at a time, not all tiles'
                    return jax.lax.map(
                        lambda a: self._attend_xla(
                            a[0][None], a[1][None], kv_b, ckv, kpe,
                            a[2][None], a[3][None], form, length=length)[0],
                        per_tile)

                # XLA scores the cache it is given whole, so the chunk is
                # given the shortest of PROMPT_SPANS prefixes of the cache
                # that holds its furthest position: an eighth of the
                # scores' operations and float32 traffic early in a prompt
                spans = [n for n in (ckv.shape[2] * (i + 1) // PROMPT_SPANS
                                     for i in range(PROMPT_SPANS))
                         if n and n % LANE == 0] or [ckv.shape[2]]
                spans[-1] = ckv.shape[2]
                furthest = jnp.max(pos)
                out = jax.lax.switch(
                    sum((furthest >= n).astype(jnp.int32)
                        for n in spans[:-1]),
                    [functools.partial(tiles, n) for n in spans])
                out, path = out.reshape(t, h, -1), f"xla_tile_{form}"
            elif pallas:
                # pads stream one block, not a stale row's whole prefix
                pos = jnp.where(seg.rows == nreq, 0, base.token_position)
                out = decode_attention(
                    q_n, ckv, None, seg.rows, pos, scale=self.scaling_factor,
                    interpret=bool(ctx.extras.get("pallas_interpret")),
                    q_rope=q_r, k_rope=kpe)
                note_decode_block(ctx.extras, self.path_kind,
                                  type(bc).__name__, ckv, latent=True)
                path = "decode_attention_latent"
            else:
                out = self._attend_xla(
                    q_n[:, None], q_r[:, None], kv_b, ckv, kpe, seg.rows,
                    base.token_position[:, None], form)[:, 0]
                path = f"xla_{form}"
            paths = ctx.extras.get("attention_paths")
            if paths is not None:
                paths[(self.path_kind, type(bc).__name__)] = path
        with jax.named_scope("o_proj"):
            if form == "absorbed":
                out = jnp.einsum(
                    "thc,chv->thv", out.astype(x.dtype),
                    kv_b[..., self.nope_dim:],
                    preferred_element_type=jnp.float32)
            y = jnp.dot(out.astype(x.dtype).reshape(t, h * self.v_dim),
                        self._weight(params, "o_proj", x.dtype),
                        preferred_element_type=jnp.float32)
            return [y.astype(self.dtype)]


def compact_cache_len(max_seq_len: int, window: int, chunk: int) -> int:
    """Entries of a slot's compacting cache: the summaries of every window
    but the last, then one window of raw entries, padded to the decode
    kernel's seq block where the cache is longer than one (to the lane
    otherwise) so that the kernels get a dividing block."""
    per_window = window // chunk
    n = per_window * (-(-max_seq_len // window) - 1) + window
    block = 512 if n > 512 else LANE
    return -(-n // block) * block


def compact_len(position, window: int, chunk: int):
    """``L(t) - 1``: where position ``t`` sits in its slot's compacting
    cache — behind the ``window / chunk`` summaries of each closed window,
    at its offset into the open one.  A query at ``t`` reads the entries
    ``0 .. compact_len(t)``; works on ints and on arrays alike."""
    return (window // chunk) * (position // window) + position % window


@register_op
class EvaAttention(_SlotStateOp):
    """EVA attention over flat token batches (Zheng et al., "Efficient
    Attention via Control Variates", as EvaByte runs it).

    With window ``W``, chunk ``C`` and per-head learned ``phi, mu``: a chunk
    of ``C`` positions has the summary ``kbar = sum_j a_j k_j + mu``,
    ``vbar = sum_j a_j v_j`` with ``a = softmax_j(phi . k_j)`` over the
    chunk's (rotated) keys; a query at position ``t`` in window
    ``w = t // W`` attends the exact keys ``W w .. t`` and the summaries of
    every chunk of windows ``0 .. w - 1``, in ONE softmax, same scale.

    The cache ``ck`` / ``cv`` ``[rows, heads, compact_cache_len, head_dim]``
    holds that set as a contiguous prefix: the ``W / C`` summaries of each
    closed window, then the open window's raw entries — position ``t`` at
    index :func:`compact_len` ``(t)``.  So the mask is plain causal
    attention at compact indices and the kernels there are run it as it is:
    ``decode_attention`` over ``L(t)`` entries, ``prefill_attention`` on a
    tile whose queries and keys sit at compact indices (a tile never
    straddles a window: the tile divides ``W``).  RoPE turns by the
    position, the cache is indexed by the compact index; the two differ
    from the first window's end on.

    COMPACTION: after the step in which a row writes a window's last
    position, that window's ``W`` raw entries are read once, summarised and
    overwritten in place by the ``W / C`` summaries (the next window's raw
    entries then follow them).  It runs under a loop whose trip count is the
    number of rows that close a window in this step — none, nearly always
    — so a step that closes nothing streams nothing.

    A flat batch may hold one request's rows on BOTH sides of a window's end
    (the rows after it must read summaries that need the rows before it, and
    their raw entries land where the closing window's still lie): such a
    step attends in two passes, the rows of each segment's first window,
    the compaction, then the rest.  The decode scan (every row a request of
    its own) needs one.
    """

    type_name = "eva_attention"

    def __init__(self, embed_dim: int, num_heads: int, head_dim: int,
                 window: int, chunk: int, rope_theta: float = 10000.0,
                 dtype=jnp.float32):
        if window % chunk:
            raise ValueError("the window holds whole chunks")
        self.embed_dim = int(embed_dim)
        # plain MHA; both names, as the weight quantiser finds attention
        # ops by ``num_kv_heads``
        self.num_q_heads = self.num_kv_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.window = int(window)
        self.chunk = int(chunk)
        self.rope_theta = float(rope_theta)
        self.scaling_factor = 1.0 / math.sqrt(self.head_dim)
        self.dtype = jnp.dtype(dtype).name

    @property
    def per_window(self) -> int:
        return self.window // self.chunk

    launch_reads = ("window", "chunk")

    def launch_counts(self, decode, prompt, layers, counted):
        """``cache_len_sum``: the entries the rows' caches hold at launch —
        their ``L(lo)``, where ``ctx_sum`` counts positions; ``compactions``:
        the windows the launch closes.  Counted: those, and the summary
        pairs written over all layers."""
        writes = _writes(decode, prompt)
        closed = sum(hi // self.window - lo // self.window
                     for lo, hi in writes)
        args = {"cache_len_sum": sum(
                    compact_len(lo, self.window, self.chunk) + 1
                    for lo, _ in writes),
                "compactions": closed}
        if not closed:
            return args, {}
        return args, {
            "eva.windows_closed": closed,
            "eva.summaries_written": closed * layers * self.per_window}

    # ---- shapes / params ----------------------------------------------
    def infer_shapes(self, in_specs):
        return [TensorSpec(in_specs[0].shape, jnp.dtype(self.dtype))]

    def params(self) -> List[ParamSpec]:
        dt = jnp.dtype(self.dtype)
        e, h, hd = self.embed_dim, self.num_q_heads, self.head_dim
        return [
            ParamSpec("qkv", TensorSpec((e, h, 3, hd), dt)),
            ParamSpec("o_proj", TensorSpec((h * hd, e), dt)),
            ParamSpec("phi", TensorSpec((h, hd), dt)),
            ParamSpec("mu", TensorSpec((h, hd), dt)),
        ]

    def flops(self, in_specs):
        t = in_specs[0].shape[0]
        return 2 * t * self.embed_dim * 4 * self.num_q_heads * self.head_dim

    def state_specs(self, max_requests, max_seq_len, max_spec_tokens=0,
                    head_axes=()):
        if getattr(self, "cost_max_tokens", 0) > self.window:
            raise ValueError(
                f"max_tokens_per_batch {self.cost_max_tokens} > window_size "
                f"{self.window}: a step may cross one window's end, not two")
        shape = (max_requests + 1, self.num_kv_heads,
                 compact_cache_len(max_seq_len, self.window, self.chunk),
                 self.head_dim)
        sh = TensorSharding.replicated(4)
        return {"ck": (shape, self.dtype, sh), "cv": (shape, self.dtype, sh)}

    # ---- compute -------------------------------------------------------
    def _project(self, x, params, pos):
        w = dequant(params["qkv"], params.get("qkv_scale"), x.dtype)
        qkv = jnp.einsum("te,ehgd->thgd", x, w,
                         preferred_element_type=jnp.float32).astype(x.dtype)
        q = apply_rope(qkv[:, :, 0], pos, self.rope_theta)
        k = apply_rope(qkv[:, :, 1], pos, self.rope_theta)
        return q, k, qkv[:, :, 2]

    @jax.named_scope("kv_write")
    def _write(self, kc, vc, k, v, rows, at, bc, tiled, extras):
        """This pass's keys and values to ``(rows, at)``: ``at`` the compact
        index, ``rows`` the scratch row for what the pass leaves out."""
        if not tiled:
            return put_rows(kc, vc, k, v, rows, at, extras)
        # one block per tile; a tile lies inside one window, so its entries
        # are contiguous, and its tail pads land beyond the open window's
        # newest entry (which a later chunk overwrites before any query
        # reads it).  A window opens ``per_window`` entries after the last:
        # a tile's first entry is a whole number of tiles where that is one
        bq = bc.tile_size
        return put_blocks(
            kc, vc, k, v, *tile_coords(rows, at, bq, kc.shape[0] - 1),
            bq, extras, aligned=self.per_window % bq == 0)

    def _attend(self, q, kc, vc, rows, at, bc, ctx, tiled):
        """``[T, heads, D]``: causal attention of each row over its slot's
        entries ``0 .. at`` — and the path taken."""
        from ..ops.pallas.attention import decode_attention, prefill_attention

        interp = bool(ctx.extras.get("pallas_interpret"))
        if tiled:
            bq = bc.tile_size
            g = q.shape[0] // bq
            out = prefill_attention(
                q.reshape(g, bq, *q.shape[1:]), kc, vc,
                rows.reshape(g, bq)[:, 0], at.reshape(g, bq)[:, 0],
                scale=self.scaling_factor, interpret=interp)
            note_prefill_operands(ctx.extras, "eva_attention", q, kc)
            return out.reshape(q.shape), "prefill_attention"
        if ctx.extras.get("pallas_decode"):
            out = decode_attention(q, kc, vc, rows, at,
                                   scale=self.scaling_factor,
                                   interpret=interp)
            note_decode_block(ctx.extras, "eva_attention", type(bc).__name__,
                              kc)
            return out, "decode_attention"
        # the CPU oracle of the kernels
        sc = jnp.einsum("thd,thsd->ths", q, kc[rows],
                        preferred_element_type=jnp.float32)
        seen = jnp.arange(kc.shape[2], dtype=jnp.int32) <= at[:, None]
        sc = jnp.where(seen[:, None], sc * self.scaling_factor, NEG_INF)
        w = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("ths,thsd->thd", w, vc[rows].astype(w.dtype),
                          preferred_element_type=jnp.float32), "xla"

    def summarize(self, k, v, params):
        """The summaries of whole chunks: ``k, v [heads, n C, D]`` to
        ``[heads, n, D]`` each, in float32 (no matmul: a float32 one would
        round the softmax weights to bf16 on the MXU)."""
        h, s, hd = k.shape
        up = lambda a: a.astype(jnp.float32)
        k = up(k).reshape(h, s // self.chunk, self.chunk, hd)
        v = up(v).reshape(k.shape)
        a = jax.nn.softmax(
            jnp.sum(up(params["phi"])[:, None, None] * k, axis=-1), axis=-1)
        return (jnp.sum(a[..., None] * k, axis=2) + up(params["mu"])[:, None],
                jnp.sum(a[..., None] * v, axis=2))

    def _compact(self, kc, vc, params, rows, pos, closing):
        """Replace the raw entries of the windows that ``closing`` rows end
        by their summaries, one row per trip of a loop that runs as many
        trips as rows close a window."""
        w, n = self.window, self.per_window
        order = jnp.argsort(~closing, stable=True)
        zero = jnp.int32(0)

        def one(i, caches):
            kc, vc = caches
            f = order[i]
            at = (rows[f], zero, n * (pos[f] // w), zero)
            size = (1, self.num_kv_heads, w, self.head_dim)
            ks, vs = self.summarize(
                jax.lax.dynamic_slice(kc, at, size)[0],
                jax.lax.dynamic_slice(vc, at, size)[0], params)
            return (jax.lax.dynamic_update_slice(
                        kc, ks[None].astype(kc.dtype), at),
                    jax.lax.dynamic_update_slice(
                        vc, vs[None].astype(vc.dtype), at))

        # its own operator class in a device trace, apart from the node's
        with jax.named_scope("EvaCompaction.window_close"):
            return jax.lax.fori_loop(
                0, jnp.sum(closing.astype(jnp.int32)), one, (kc, vc))

    def lower(self, ctx, inputs, params):
        bc, state = _require(ctx, self.type_name)
        x = inputs[0]
        base = _flat(bc)
        kc, vc = state["ck"], state["cv"]
        nreq = kc.shape[0] - 1
        seg = Segments(base, nreq)
        pos = base.token_position
        with jax.named_scope("qkv_proj"):
            q, k, v = self._project(x, params, pos)
        tiled = (isinstance(bc, PrefillBatchConfig)
                 and bool(ctx.extras.get("pallas_decode")))
        if tiled and self.window % bc.tile_size:
            raise ValueError(f"the prefill tile {bc.tile_size} must divide "
                             f"window_size {self.window}")
        at = compact_len(pos, self.window, self.chunk)
        # rows past the end of the window their segment began in wait for
        # its compaction (none in the decode scan: a row is a segment)
        late = seg.live & (pos // self.window
                           > (pos - seg.offset) // self.window)
        passes = [~late] if ctx.extras.get("one_row_per_request") \
            else [~late, late]
        with jax.named_scope("attend"):
            for i, now in enumerate(passes):
                rows = jnp.where(seg.live & now, seg.rows, nreq)
                idx = jnp.where(rows == nreq, 0, at)
                kc, vc = self._write(kc, vc, k, v, rows, idx, bc, tiled,
                                     ctx.extras)
                o, path = self._attend(q, kc, vc, rows, idx, bc, ctx, tiled)
                if i == 0:
                    out = o
                    closing = (rows != nreq) & ((pos + 1) % self.window == 0)
                    kc, vc = self._compact(kc, vc, params, rows, pos, closing)
                else:
                    out = jnp.where(now[:, None, None], o, out)
            ctx.extras["state_out"] = {"ck": kc, "cv": vc}
            paths = ctx.extras.get("attention_paths")
            if paths is not None:
                paths[("eva_attention", type(bc).__name__)] = path
        with jax.named_scope("o_proj"):
            o_w = dequant(params["o_proj"], params.get("o_proj_scale"),
                          x.dtype)
            y = jnp.dot(out.astype(x.dtype).reshape(x.shape[0], -1), o_w,
                        preferred_element_type=jnp.float32)
            return [y.astype(self.dtype)]


@register_op
class SparseBlockAttention(_SlotStateOp):
    """InfLLM-v2 sparse attention over flat token batches (MiniCPM4 report,
    arXiv:2506.07900, as MiniCPM-SALA's ``minicpm4`` layers run it): grouped
    queries on a few K/V heads, no positional encoding, an output gate.

    Two caches a slot.  ``k`` / ``v`` ``[rows, KV, S, D]`` hold every
    position; ``kidx`` ``[rows, KV, S / stride, D]`` holds the COMPRESSED
    keys: entry ``c`` is the mean of the keys ``[stride c, stride c +
    kernel)`` and exists once position ``stride c + kernel - 1`` is written
    (the row that writes it appends the entry, in a prompt chunk and in the
    decode scan alike).

    Between ``qkv_proj`` and ``attend`` each row SELECTS, per KV-head group
    (scope ``BlockSelect.<node>``, an operator class of its own in a device
    trace): a softmax per query head over the compressed keys it can see,
    summed over the group's heads, max-pooled onto blocks of ``block``
    positions (a block's score is the largest among the kernels that overlap
    it); the row attends block 0 (``init_blocks``), the ``window / block``
    newest blocks, and the ``topk`` highest-scoring of the rest — or every
    block while its position is below ``dense_len``.  The selection is a
    mask over blocks ``[T, KV, S / block]``; attention is ONE softmax over
    the exact keys ``j <= t`` of the attended blocks.  The decode scan,
    where every live row is a slot of its own, scores in SLOT ORDER against
    the index where it lies (``_select_slots``); a flat step and a prompt
    chunk gather each row's index first (``_select_rows``) — one ``select``,
    counted as ``attention_path.block_select.slot_order`` / ``.gathered``.

    Paths: the decode scan and flat steps on the chip turn the mask into a
    sorted block list and run ``sparse_decode_attention`` (the kernel
    copies the listed blocks itself, the forced window's ``tail_run``
    consecutive blocks as one run; the causal mask from each block's own
    position); a prompt chunk and the CPU oracle compute masked-dense — all
    blocks up to the furthest row's, in a loop over key spans, the
    unselected masked per row and group (the same mathematics).
    """

    type_name = "sparse_block_attention"
    KEY_SPAN = 2048   # keys per trip of the masked-dense loop

    def __init__(self, embed_dim: int, num_q_heads: int, num_kv_heads: int,
                 head_dim: int, kernel_size: int = 32, kernel_stride: int = 16,
                 block_size: int = 64, topk: int = 64, window: int = 2048,
                 init_blocks: int = 1, dense_len: int = 8192,
                 output_gate: bool = True, dtype=jnp.float32):
        if num_q_heads % num_kv_heads:
            raise ValueError("query heads come in whole groups per K/V head")
        if kernel_size % kernel_stride or block_size % kernel_stride \
                or window % block_size:
            raise ValueError("the stride divides the kernel and the block; "
                             "the window holds whole blocks")
        self.embed_dim = int(embed_dim)
        self.num_q_heads = int(num_q_heads)
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.kernel_size = int(kernel_size)
        self.kernel_stride = int(kernel_stride)
        self.block_size = int(block_size)
        self.topk = int(topk)
        self.window = int(window)
        self.init_blocks = int(init_blocks)
        self.dense_len = int(dense_len)
        self.output_gate = bool(output_gate)
        self.scaling_factor = 1.0 / math.sqrt(self.head_dim)
        self.dtype = jnp.dtype(dtype).name

    # ---- geometry (ints and arrays alike) -------------------------------
    @property
    def group(self) -> int:
        return self.num_q_heads // self.num_kv_heads

    @property
    def max_blocks(self) -> int:
        """The most blocks a row attends: the forced and chosen ones, or
        every block below ``dense_len``."""
        return max(self.chosen_blocks,
                   -(-self.dense_len // self.block_size))

    @property
    def tail_run(self) -> int:
        """The forced window in blocks: the newest entries of a row's sorted
        list, consecutive by construction — the chunk the kernel walks a
        list in, from its end."""
        return self.window // self.block_size

    @property
    def chosen_blocks(self) -> int:
        """Blocks a row that selects attends: the forced and the chosen."""
        return self.init_blocks + self.tail_run + self.topk

    def index_len(self, position):
        """Compressed keys a row at ``position`` can see (its own included
        if it completes one)."""
        n = (position + 1 - self.kernel_size) // self.kernel_stride + 1
        return max(n, 0) if isinstance(position, int) else jnp.maximum(n, 0)

    def attended_blocks(self, position: int) -> int:
        """Blocks a row at ``position`` reads (host arithmetic for the
        dispatch spans): all of them below ``dense_len``, else the forced
        ones and ``topk`` of the rest."""
        have = position // self.block_size + 1
        if position < self.dense_len:
            return have
        return min(have, self.chosen_blocks)

    def attended_blocks_between(self, lo: int, hi: int) -> int:
        """``sum(attended_blocks(p) for p in range(lo, hi))`` in closed
        form (a launch's counters, on the host, every launch)."""
        size, chosen = self.block_size, self.chosen_blocks
        # ``p // size + 1`` blocks up to ``dense_len`` and, past it, until
        # there are more than the forced and chosen ones
        edge = max(chosen * size, self.dense_len)

        def below(n):   # sum over p < n of p // size + 1
            m = n // size
            return size * m * (m + 1) // 2 + (n - m * size) * (m + 1)

        grow = lambda a, b, stop: below(min(b, stop)) - below(min(a, stop))
        a, b = max(lo, self.dense_len), max(hi, self.dense_len)
        return (grow(lo, hi, self.dense_len) + grow(a, b, edge)
                + chosen * (max(b, edge) - max(a, edge)))

    def run_blocks(self, position: int) -> int:
        """Of ``attended_blocks(position)``, those the kernel fetches as
        whole runs (one copy of ``tail_run`` consecutive blocks): the forced
        window of a row that selects; every whole chunk, counted from the
        list's end, of a row that attends all its blocks."""
        have = position // self.block_size + 1
        if self.attended_blocks(position) < have:
            return self.tail_run
        return have // self.tail_run * self.tail_run

    def run_blocks_between(self, lo: int, hi: int) -> int:
        """``sum(run_blocks(p) for p in range(lo, hi))`` in closed form."""
        size, run = self.block_size, self.tail_run
        # every block is attended below it
        edge = max(self.chosen_blocks * size, self.dense_len)

        def whole(m):   # sum over j <= m of j // run * run
            a = m // run
            return run * (run * a * (a - 1) // 2 + a * (m - a * run + 1))

        def below(n):   # sum over p < n of (p // size + 1) // run * run
            m = n // size
            return size * whole(m) + (n - m * size) * ((m + 1) // run * run)

        return (below(min(hi, edge)) - below(min(lo, edge))
                + run * (max(hi, edge) - max(lo, edge)))

    launch_reads = ("block_size", "dense_len", "chosen_blocks", "tail_run",
                    "kernel_size", "kernel_stride")

    def launch_counts(self, decode, prompt, layers, counted):
        """``attended_blocks_sum``: the cache blocks the rows read at launch
        (a sparse layer and K/V head: every block below ``dense_len``, the
        forced and chosen ones after it); ``window_run_blocks_sum``: those
        of them fetched as whole runs (the forced window of a row that
        selects); ``index_len_sum``: the compressed keys they choose by.
        The ``sparse.*`` counters: the same over every position written."""
        writes = _writes(decode, prompt)
        args = {"attended_blocks_sum": sum(self.attended_blocks(lo)
                                           for lo, _ in writes),
                "window_run_blocks_sum": sum(self.run_blocks(lo)
                                             for lo, _ in writes),
                "index_len_sum": sum(self.index_len(lo) for lo, _ in writes)}
        if not counted:
            return args, {}
        dense = self.dense_len
        counters = {
            "sparse.blocks_attended": layers * sum(
                self.attended_blocks_between(lo, hi) for lo, hi in writes),
            "sparse.window_run_blocks": layers * sum(
                self.run_blocks_between(lo, hi) for lo, hi in writes),
            "sparse.dense_rows": layers * sum(
                min(hi, dense) - min(lo, dense) for lo, hi in writes)}
        entries = sum(self.index_len(hi - 1) - self.index_len(lo - 1)
                      for lo, hi in writes)
        if entries:
            counters["sparse.index_entries_written"] = entries * layers
        return args, counters

    # ---- shapes / params ------------------------------------------------
    @property
    def _cols(self):
        q = self.num_q_heads * self.head_dim
        kv = self.num_kv_heads * self.head_dim
        return q, kv, (q if self.output_gate else 0)

    def infer_shapes(self, in_specs):
        return [TensorSpec(in_specs[0].shape, jnp.dtype(self.dtype))]

    def params(self) -> List[ParamSpec]:
        dt = jnp.dtype(self.dtype)
        q, kv, z = self._cols
        # q | k | v | gate side by side: one GEMM, sliced
        return [ParamSpec("qkv", TensorSpec((self.embed_dim, q + 2 * kv + z),
                                            dt)),
                ParamSpec("o_proj", TensorSpec((q, self.embed_dim), dt))]

    def flops(self, in_specs):
        q, kv, z = self._cols
        return 2 * in_specs[0].shape[0] * self.embed_dim * (2 * q + 2 * kv + z)

    def state_specs(self, max_requests, max_seq_len, max_spec_tokens=0,
                    head_axes=()):
        seq = -(-max_seq_len // LANE) * LANE
        kv = (max_requests + 1, self.num_kv_heads, seq, self.head_dim)
        idx = kv[:2] + (seq // self.kernel_stride, self.head_dim)
        sh = TensorSharding.replicated(4)
        return {"k": (kv, self.dtype, sh), "v": (kv, self.dtype, sh),
                "kidx": (idx, self.dtype, sh)}

    # ---- the index ------------------------------------------------------
    @jax.named_scope("index_write")
    def _append_index(self, kidx, kc, rows, pos):
        """The compressed keys this step's rows complete, appended: a row at
        ``pos`` with ``pos + 1`` a multiple of the stride (and a whole
        kernel behind it) reads the kernel's keys back from the cache —
        which already holds this step's — and writes their mean.  One trip
        of a loop per COMPLETING row (a sixteenth of the rows, one slice of
        32 keys each): a gather over all rows made XLA re-lay the whole K
        cache for it, 0.8 GB a layer and step (PERF.md section 6, PR 44)."""
        ks, st = self.kernel_size, self.kernel_stride
        done = (rows != kc.shape[0] - 1) & ((pos + 1) % st == 0) \
            & (pos + 1 >= ks)
        order = jnp.argsort(~done, stable=True)
        zero = jnp.int32(0)

        def one(i, kidx):
            f = order[i]
            first = pos[f] + 1 - ks
            span = jax.lax.dynamic_slice(
                kc, (rows[f], zero, first, zero),
                (1, self.num_kv_heads, ks, self.head_dim))
            mean = jnp.mean(span.astype(jnp.float32), axis=2, keepdims=True)
            return jax.lax.dynamic_update_slice(
                kidx, mean.astype(kidx.dtype),
                (rows[f], zero, first // st, zero))

        return jax.lax.fori_loop(0, jnp.sum(done.astype(jnp.int32)), one,
                                 kidx)

    # ---- the selection --------------------------------------------------
    def block_scores(self, q, idx, pos):
        """``[T, KV, blocks]`` float32: each block's score for each row —
        ``q [T, QH, D]``, the rows' compressed keys ``idx [T, KV, NC, D]``,
        positions ``pos [T]``; -1 for a block no visible kernel overlaps."""
        t = q.shape[0]
        kv, g, st = self.num_kv_heads, self.group, self.kernel_stride
        nc = idx.shape[2]
        sc = jnp.einsum("tkgd,tkcd->tkgc", q.reshape(t, kv, g, -1), idx,
                        preferred_element_type=jnp.float32)
        seen = jnp.arange(nc, dtype=jnp.int32) < self.index_len(pos)[:, None]
        sc = jnp.where(seen[:, None, None], sc * self.scaling_factor, NEG_INF)
        p = jnp.where(seen[:, None, None], jax.nn.softmax(sc, axis=-1), 0.0)
        r = jnp.where(seen[:, None], jnp.sum(p, axis=2), -1.0)  # [T, KV, NC]
        # block b is overlapped by the kernels per * b - extra .. per * b +
        # per - 1 (5 kernels of 32 at stride 16 on a block of 64)
        per, extra = self.block_size // st, self.kernel_size // st - 1
        r = jnp.pad(r, ((0, 0), (0, 0), (extra, 0)), constant_values=-1.0)
        nb = nc // per
        r = r[:, :, :nb * per + extra]
        own = r[:, :, extra:].reshape(t, kv, nb, per).max(-1)
        for e in range(extra):   # the kernels that begin before the block
            own = jnp.maximum(own, r[:, :, e:e + nb * per:per])
        return own

    def select(self, q, idx, pos):
        """The blocks each row attends, as a mask ``[T, KV, blocks]``."""
        return self._attended(self.block_scores(q, idx, pos), pos)

    def _attended(self, score, pos):
        """``select``'s mask from the rows' block scores."""
        nb = score.shape[-1]
        b = jnp.arange(nb, dtype=jnp.int32)
        last = (pos // self.block_size)[:, None]
        have = b <= last
        forced = have & ((b < self.init_blocks)
                         | (b > last - self.window // self.block_size))
        free = (have & ~forced)[:, None]
        k = min(self.topk, nb)
        top, ids = jax.lax.top_k(jnp.where(free, score, -2.0), k)
        # a compare per block, not a scatter of ``k`` single elements a row
        # and group (which XLA lowers through a sort of its own on a TPU)
        ids = jnp.where(top > -2.0, ids, -1)
        chosen = jnp.any(ids[..., None] == b, axis=-2)
        sparse = forced[:, None] | chosen
        return jnp.where((pos < self.dense_len)[:, None, None],
                         have[:, None], sparse)

    def _select_rows(self, q, kidx, rows, pos):
        """``select`` for every row against its slot's index, ``ROWS`` rows
        at a time (a row's index is a megabyte at the published sizes)."""
        ROWS = 64

        def some(args):
            qs, rs, ps = args
            return self.select(qs, kidx[rs], ps)

        t = q.shape[0]
        if t <= ROWS or t % ROWS:
            return some((q, rows, pos))
        cut = lambda a: a.reshape((t // ROWS, ROWS) + a.shape[1:])
        out = jax.lax.map(some, (cut(q), cut(rows), cut(pos)))
        return out.reshape((t,) + out.shape[2:])

    def _select_slots(self, q, kidx, rows, pos):
        """``select`` where every live row is a slot of its own (the decode
        scan; pads sit on the scratch row): the queries go to their slots,
        the scores are taken against the index AS IT LIES and only they come
        back by row — ``kidx[rows]`` copied every slot's index (50 MB a layer
        and step at the published sizes) to read it once."""
        by_slot = lambda a: jnp.zeros((kidx.shape[0],) + a.shape[1:],
                                      a.dtype).at[rows].set(a)
        score = self.block_scores(by_slot(q), kidx, by_slot(pos))[rows]
        return self._attended(score, pos)

    # ---- attention ------------------------------------------------------
    def _attend_masked(self, q, kc, vc, rows, pos, mask):
        """Masked-dense attention of query groups against their slot's
        cache: ``q [G, B, QH, D]``, cache row ``rows [G]``, positions ``pos
        [G, B]``, block mask ``[G, B, KV, blocks]``.  A flat row is a group
        of one, a prefill tile a group of ``tile`` rows.  Key spans of
        ``KEY_SPAN`` go one after the other up to the furthest row's, under
        one running softmax; float32."""
        g_, b_, qh, d = q.shape
        kv, grp, bs = self.num_kv_heads, self.group, self.block_size
        s_len = kc.shape[2]
        span = min(self.KEY_SPAN, s_len)
        per = span // bs
        qr = q.reshape(g_, b_, kv, grp, d)
        zero = jnp.int32(0)

        def one(i, carry):
            m, l, acc = carry
            lo = i * span
            cut = lambda c: jax.lax.dynamic_slice(
                c, (zero, zero, lo, zero),
                (c.shape[0], kv, span, d))[rows]          # [G, KV, span, D]
            sc = jnp.einsum("gbkhd,gksd->gbkhs", qr, cut(kc),
                            preferred_element_type=jnp.float32)
            at = lo + jnp.arange(span, dtype=jnp.int32)
            sel = jax.lax.dynamic_slice_in_dim(mask, i * per, per, axis=3)
            seen = jnp.repeat(sel, bs, axis=3) \
                & (at <= pos[..., None])[:, :, None]      # [G, B, KV, span]
            sc = jnp.where(seen[:, :, :, None], sc * self.scaling_factor,
                           NEG_INF)
            m_new = jnp.maximum(m, sc.max(-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(seen[:, :, :, None], jnp.exp(sc - m_new[..., None]),
                          0.0)
            pv = jnp.einsum("gbkhs,gksd->gbkhd", p.astype(vc.dtype), cut(vc),
                            preferred_element_type=jnp.float32)
            return (m_new, alpha * l + p.sum(-1),
                    alpha[..., None] * acc + pv)

        shape = (g_, b_, kv, grp)
        init = (jnp.full(shape, NEG_INF, jnp.float32),
                jnp.zeros(shape, jnp.float32),
                jnp.zeros(shape + (d,), jnp.float32))
        trips = jnp.minimum(jnp.max(pos) // span + 1, s_len // span)
        _, l, acc = jax.lax.fori_loop(0, trips, one, init)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.reshape(g_, b_, qh, d)

    def block_list(self, mask):
        """A block mask ``[T, KV, blocks]`` as sorted lists ``[T, KV,
        max_blocks]`` and their lengths ``[T, KV]``; entries past the length
        repeat the last attended block (the kernel reads none of them)."""
        cum = jnp.cumsum(mask.astype(jnp.int32), axis=-1)
        n = cum[..., -1]
        width = min(self.max_blocks, mask.shape[-1])
        at = jnp.minimum(jnp.arange(width, dtype=jnp.int32),
                         jnp.maximum(n - 1, 0)[..., None])
        # the ``j``-th attended block has exactly ``j`` attended blocks
        # before it: its id is the count of blocks whose running count is
        # ``<= j`` (a sort of the mask says the same, at a sort's price)
        blocks = jnp.sum(cum[..., None, :] <= at[..., None], axis=-1,
                         dtype=jnp.int32)
        return jnp.where((n > 0)[..., None], blocks, 0), n

    def lower(self, ctx, inputs, params):
        bc, state = _require(ctx, self.type_name)
        x = inputs[0]
        base = _flat(bc)
        t = x.shape[0]
        kc, vc, kidx = state["k"], state["v"], state["kidx"]
        nreq = kc.shape[0] - 1
        seg = Segments(base, nreq)
        pos = jnp.where(seg.live, base.token_position, 0)
        qn, kvn, zn = self._cols
        kv, d = self.num_kv_heads, self.head_dim
        with jax.named_scope("qkv_proj"):
            w = dequant(params["qkv"], params.get("qkv_scale"), x.dtype)
            y = jnp.dot(x, w, preferred_element_type=jnp.float32
                        ).astype(x.dtype)
            q = y[:, :qn].reshape(t, self.num_q_heads, d)
            k = y[:, qn:qn + kvn].reshape(t, kv, d)
            v = y[:, qn + kvn:qn + 2 * kvn].reshape(t, kv, d)
            gate = y[:, qn + 2 * kvn:] if self.output_gate else None
        pallas = bool(ctx.extras.get("pallas_decode"))
        tiled = isinstance(bc, PrefillBatchConfig) and pallas
        with jax.named_scope("attend"):
            with jax.named_scope("kv_write"):
                if tiled:
                    bq = bc.tile_size
                    g = t // bq
                    kc, vc = put_blocks(
                        kc, vc, k, v, *tile_coords(seg.rows, pos, bq, nreq),
                        bq, ctx.extras)
                else:
                    kc, vc = put_rows(kc, vc, k, v, seg.rows, pos,
                                      ctx.extras)
                kidx = self._append_index(kidx, kc, seg.rows, pos)
            ctx.extras["state_out"] = {"k": kc, "v": vc, "kidx": kidx}
            # its own operator class in a device trace, apart from the node's
            node = ctx.extras.get("node_name", "select")
            slots = bool(ctx.extras.get("one_row_per_request"))
            with jax.named_scope(f"BlockSelect.{node}"):
                mask = (self._select_slots if slots else self._select_rows)(
                    q, kidx, seg.rows, pos)
                if pallas and not tiled:
                    blocks, count = self.block_list(mask)
                    count = jnp.where(seg.live[:, None], count, 0)
            if tiled:
                rows = jnp.min(seg.rows.reshape(g, bq), axis=1)
                out = self._attend_masked(
                    q.reshape(g, bq, self.num_q_heads, d), kc, vc, rows,
                    pos.reshape(g, bq), mask.reshape((g, bq) + mask.shape[1:]))
                out, path = out.reshape(t, -1), "masked_dense_tile"
            elif pallas:
                from ..ops.pallas.attention import sparse_decode_attention

                out = sparse_decode_attention(
                    q, kc, vc, seg.rows, pos, blocks, count,
                    scale=self.scaling_factor, block=self.block_size,
                    tail_run=self.tail_run,
                    interpret=bool(ctx.extras.get("pallas_interpret")))
                out, path = out.reshape(t, -1), "sparse_decode_attention"
            else:
                out = self._attend_masked(q[:, None], kc, vc, seg.rows,
                                          pos[:, None], mask[:, None])
                out, path = out.reshape(t, -1), "xla"
            paths = ctx.extras.get("attention_paths")
            if paths is not None:
                batch = "one_row_per_request" if slots \
                    else type(bc).__name__
                paths[(self.type_name, batch)] = path
                paths[("block_select", batch)] = \
                    "slot_order" if slots else "gathered"
        with jax.named_scope("o_proj"):
            out = out.astype(jnp.float32)
            if gate is not None:
                out = out * jax.nn.sigmoid(gate.astype(jnp.float32))
            o_w = dequant(params["o_proj"], params.get("o_proj_scale"),
                          x.dtype)
            y = jnp.dot(out.astype(x.dtype), o_w,
                        preferred_element_type=jnp.float32)
            return [y.astype(self.dtype)]


def lightning_slopes(num_heads: int):
    """Lightning Attention's per-head decay rates: ``lambda_h = exp(-slope_h)``
    with ``slope_h = 2 ** (-8 (h + 1) / H)``."""
    h = jnp.arange(1, num_heads + 1, dtype=jnp.float32)
    return jnp.exp2(-8.0 * h / num_heads)


@register_op
class LightningAttention(_SlotStateOp):
    """Lightning linear attention over flat token batches (Qin et al.,
    "Lightning Attention-2", as MiniCPM-SALA's ``lightning-attn`` layers run
    it): ``q, k`` RMS-normed per head and rotated, ``S_t = lambda_h S_{t-1}
    + k_t' v_t``, ``o_t = s q_t S_t``, an RMS norm of ``o`` per head, an
    output gate ``sigmoid(x Wz)``.

    State ``lin [rows, H, D, D]`` float32: one matrix per head and slot.
    The decode scan (every live row a request of its own) updates the whole
    state array in place, in slot order — decay, rank-one update and the
    read-out in one elementwise pass, no gather of 2 MB a row and no scatter
    back.  A prompt chunk or a flat step uses the CHUNKED form: inside the
    batch ``((Q K') * D) V`` with the decay mask ``D`` (zero across
    requests), and per request in the batch — a loop with as many trips as
    the batch holds requests — the carried state's term ``lambda^(i+1) q_i
    S0`` and the state it leaves, ``lambda^n S0 + sum_j lambda^(n-1-j) k_j'
    v_j``: matrix products, no trip per row.
    """

    type_name = "lightning_attention"

    def __init__(self, embed_dim: int, num_heads: int, head_dim: int,
                 rope_theta: float = 10000.0, use_rope: bool = True,
                 qk_norm: bool = True, output_norm: bool = True,
                 output_gate: bool = True, eps: float = 1e-6,
                 dtype=jnp.float32):
        self.embed_dim = int(embed_dim)
        self.num_q_heads = self.num_kv_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.rope_theta = float(rope_theta)
        self.use_rope = bool(use_rope)
        self.qk_norm = bool(qk_norm)
        self.output_norm = bool(output_norm)
        self.output_gate = bool(output_gate)
        self.eps = float(eps)
        self.scaling_factor = 1.0 / math.sqrt(self.head_dim)
        self.dtype = jnp.dtype(dtype).name

    def infer_shapes(self, in_specs):
        return [TensorSpec(in_specs[0].shape, jnp.dtype(self.dtype))]

    def params(self) -> List[ParamSpec]:
        dt = jnp.dtype(self.dtype)
        e, hd = self.embed_dim, self.head_dim
        width = self.num_q_heads * hd
        cols = (4 if self.output_gate else 3) * width
        ps = [ParamSpec("qkv", TensorSpec((e, cols), dt)),   # q | k | v | gate
              ParamSpec("o_proj", TensorSpec((width, e), dt))]
        if self.qk_norm:
            ps += [ParamSpec(n, TensorSpec((hd,), dt), _init(jnp.ones))
                   for n in ("q_norm", "k_norm")]
        if self.output_norm:
            ps.append(ParamSpec("o_norm", TensorSpec((width,), dt),
                                _init(jnp.ones)))
        return ps

    def flops(self, in_specs):
        width = self.num_q_heads * self.head_dim
        return 2 * in_specs[0].shape[0] * self.embed_dim * width * (
            5 if self.output_gate else 4)

    def state_specs(self, max_requests, max_seq_len, max_spec_tokens=0,
                    head_axes=()):
        shape = (max_requests + 1, self.num_q_heads, self.head_dim,
                 self.head_dim)
        return {"lin": (shape, "float32", TensorSharding.replicated(4))}

    def launch_counts(self, decode, prompt, layers, counted):
        """Counted: ``linear.state_resets``, the requests whose state starts
        from zero in this launch, over all layers."""
        fresh = counted and sum(lo == 0 for lo, _ in _writes(decode, prompt))
        return {}, ({"linear.state_resets": fresh * layers} if fresh else {})

    # ---- the three forms --------------------------------------------------
    def _slot_order(self, q, k, v, lin, seg):
        """The decode scan's step: every slot's matrix decayed, updated and
        read where a row of the batch is its request's, untouched where
        none is — ONE pass over the state array."""
        nslot, h, d = lin.shape[0], self.num_q_heads, self.head_dim
        slope = lightning_slopes(h)
        at = seg.rows                      # pads land on the scratch row
        by_slot = lambda a: jnp.zeros((nslot,) + a.shape[1:], jnp.float32
                                      ).at[at].set(a.astype(jnp.float32))
        qs, ks, vs = by_slot(q), by_slot(k), by_slot(v)
        live = jnp.zeros((nslot,), bool).at[at].set(seg.live)
        fresh = jnp.zeros((nslot,), bool).at[at].set(seg.fresh)
        keep = jnp.where(fresh, 0.0, 1.0)[:, None] \
            * jnp.exp(-slope)[None, :]                     # [slots, H]
        # (no matmul: a float32 one would round the state to bf16 on the MXU)
        new = keep[:, :, None, None] * lin \
            + ks[:, :, :, None] * vs[:, :, None, :]
        o = jnp.sum(qs[:, :, :, None] * new, axis=2) * self.scaling_factor
        with jax.named_scope("state_write"):
            lin = jnp.where(live[:, None, None, None], new, lin)
        return o[at], lin

    def _chunked(self, q, k, v, lin, seg):
        """A prompt chunk or a flat step (see the class docstring)."""
        t, h, d = q.shape
        hi = jax.lax.Precision.HIGHEST
        slope = lightning_slopes(h)
        f32 = lambda a: a.astype(jnp.float32)
        seg_id = jnp.cumsum(seg.start.astype(jnp.int32))
        i = jnp.arange(t, dtype=jnp.int32)
        back = (i[:, None] - i[None, :]).astype(jnp.float32)
        same = (seg_id[:, None] == seg_id[None, :]) & (back >= 0) \
            & seg.live[:, None]
        decay = jnp.where(same[None], jnp.exp(
            -slope[:, None, None] * jnp.maximum(back, 0.0)[None]), 0.0)
        a = jnp.einsum("ihd,jhd->hij", q, k,
                       preferred_element_type=jnp.float32) * decay
        out = jnp.einsum("hij,jhd->ihd", a, f32(v), precision=hi)
        # per request in the batch: what its stored state adds, and the
        # state it leaves behind
        first = seg.start & seg.live
        order = jnp.argsort(~first, stable=True)
        off = seg.offset.astype(jnp.float32)
        zero = jnp.int32(0)

        def one(n, carry):
            out, lin = carry
            f = order[n]
            mine = (seg_id == seg_id[f]) & seg.live
            count = jnp.sum(mine).astype(jnp.float32)
            at = (seg.rows[f], zero, zero, zero)
            s0 = jax.lax.dynamic_slice(lin, at, (1,) + lin.shape[1:])[0]
            s0 = jnp.where(seg.fresh[f], 0.0, s0)
            carried = jnp.einsum("thd,hde->the", f32(q), s0, precision=hi) \
                * jnp.exp(-(off[:, None] + 1.0) * slope[None, :])[..., None]
            out = out + jnp.where(mine[:, None, None], carried, 0.0)
            left = jnp.where(mine[:, None], jnp.exp(
                -jnp.maximum(count - 1.0 - off, 0.0)[:, None]
                * slope[None, :]), 0.0)
            s1 = jnp.exp(-count * slope)[:, None, None] * s0 + jnp.einsum(
                "thd,the->hde", f32(k) * left[..., None], f32(v),
                precision=hi)
            return out, jax.lax.dynamic_update_slice(lin, s1[None], at)

        with jax.named_scope("state_write"):
            out, lin = jax.lax.fori_loop(
                0, jnp.sum(first.astype(jnp.int32)), one, (out, lin))
        return out * self.scaling_factor, lin

    def lower(self, ctx, inputs, params):
        bc, state = _require(ctx, self.type_name)
        x = inputs[0]
        base = _flat(bc)
        t, h, d = x.shape[0], self.num_q_heads, self.head_dim
        lin = state["lin"]
        seg = Segments(base, lin.shape[0] - 1)
        with jax.named_scope("qkv_proj"):
            w = dequant(params["qkv"], params.get("qkv_scale"), x.dtype)
            y = jnp.dot(x, w, preferred_element_type=jnp.float32
                        ).astype(x.dtype)
            q, k, v = (y[:, n * h * d:(n + 1) * h * d].reshape(t, h, d)
                       for n in range(3))
            gate = y[:, 3 * h * d:] if self.output_gate else None
            if self.qk_norm:
                q = _rms_norm(q, params["q_norm"], self.eps)
                k = _rms_norm(k, params["k_norm"], self.eps)
            if self.use_rope:
                q = apply_rope(q, base.token_position, self.rope_theta)
                k = apply_rope(k, base.token_position, self.rope_theta)
        with jax.named_scope("attend"):
            if ctx.extras.get("one_row_per_request"):
                o, lin = self._slot_order(q, k, v, lin, seg)
                path, batch = "slot_order", "one_row_per_request"
            else:
                o, lin = self._chunked(q, k, v, lin, seg)
                path, batch = "chunked", type(bc).__name__
            ctx.extras["state_out"] = {"lin": lin}
            paths = ctx.extras.get("attention_paths")
            if paths is not None:
                paths[(self.type_name, batch)] = path
        with jax.named_scope("o_proj"):
            if self.output_norm:
                o = _rms_norm(o, params["o_norm"].reshape(h, d), self.eps)
            o = o.astype(jnp.float32).reshape(t, h * d)
            if gate is not None:
                o = o * jax.nn.sigmoid(gate.astype(jnp.float32))
            o_w = dequant(params["o_proj"], params.get("o_proj_scale"),
                          x.dtype)
            y = jnp.dot(o.astype(x.dtype), o_w,
                        preferred_element_type=jnp.float32)
            return [y.astype(self.dtype)]


def unit_lower_inverse(n):
    """``(I + N)^-1`` for STRICTLY lower-triangular ``N [..., C, C]`` (``C``
    a power of two), by exact block forward substitution: with the two
    halves of a block inverted, ``[[A, 0], [B, D]]^-1 = [[A^-1, 0], [-D^-1 B
    A^-1, D^-1]]`` — ``log2 C`` levels of two matrix products over all the
    blocks of a level at once (``inv`` stays block-diagonal at the level's
    size, so whole-matrix products touch nothing outside the blocks).  No
    Neumann powers: nothing is summed that cancels."""
    c = n.shape[-1]
    i = jnp.arange(c, dtype=jnp.int32)
    inv = jnp.broadcast_to(jnp.eye(c, dtype=n.dtype), n.shape)
    hi = jax.lax.Precision.HIGHEST
    b = 1
    while b < c:
        same = (i[:, None] // (2 * b)) == (i[None, :] // (2 * b))
        lower_left = same & ((i[:, None] % (2 * b)) >= b) \
            & ((i[None, :] % (2 * b)) < b)
        inv = inv - jnp.matmul(
            jnp.matmul(inv, jnp.where(lower_left, n, 0.0), precision=hi),
            inv, precision=hi)
        b *= 2
    return inv


@register_op
class KimiDeltaAttention(_SlotStateOp):
    """Kimi Delta Attention (``kimi_linear``'s ``kda_layers``) over flat
    token batches: a gated DELTA rule with a PER-CHANNEL decay.

    Inputs: ``qkv [T, 3 H D]`` — the three projections behind their three
    short depthwise convolutions with SiLU (ONE ``CausalConv1d(bias=False)``
    node over ``q | k | v``: a depthwise conv treats every channel alone, so
    one node over the 3 H D channels is the three convs' arithmetic and one
    tail) — and the normed stream ``n [T, E]``.  Per head ``h``:

        q = q / max(|q|, 1e-6) D^-1/2;   k = k / max(|k|, 1e-6)
        g = -exp(A_log_h) softplus((n W_fa W_fb)_h + dt_bias_h)   [D], float32
        beta = sigmoid((n W_beta)_h)                              scalar
               (``allow_neg_eigval``: 2 sigmoid(..), see below)
        S' = Diag(exp g) S;  S <- S' + beta k (v - S'^T k)^T;  o = S^T q
        y = RMSNorm(o, g_o) * sigmoid((n W_ga W_gb)_h);  out = concat(y) W_o

    State kind ``delta_state`` (kv_allocator.py): ``kda [rows, H, D, D]``
    float32, key channel x value channel, fixed a slot.  Unlike the repo's
    other two matrix states (``S <- a S + k v^T``) the update READS the
    state it is about to change (``S'^T k``), and the decay is a vector.

    * the decode scan (every live row a request of its own): the Pallas step
      kernel ``delta_rule_step`` — a row's matrices read once and written
      once, in place; by XLA (gather, update, scatter) where the kernels are
      off or the head is not whole lanes: the CPU oracle.
    * a prompt chunk, a join's prefill or a flat step: the CHUNKED form over
      PIECES — runs of at most ``chunk`` rows of one request (``Segments``
      has the boundaries; :meth:`_pieces` lists them) —, never a step a row:
      the Pallas kernel ``delta_rule_chunk`` — ONE call a layer for every
      piece of the batch, a head's state, the piece's decays and its 32 x 32
      systems on chip, the state array read at a segment's first piece and
      written at its last — under the rule of the step kernel (the kernels
      on, a head of whole lanes); else :meth:`_chunked`, one XLA loop trip
      a piece: the CPU oracle and the kernels-off path.  The path note
      names the FORM (``chunked``); ``("delta_pieces", <op>)`` says who ran
      the pieces (``delta_rule_chunk`` / ``xla_loop``).  Inside
      a piece entered with ``S0``, ``G_i`` the running sum of ``g`` (<= 0):
      ``A_ij = sum_d k_i k_j exp(G_i - G_j)`` (j < i), ``B_ij`` likewise with
      ``q_i`` (j <= i), both by EXPLICIT differences — every exponent is
      <= 0 as written, the worst exactly 0; no ``exp(-G)`` is ever formed —;
      the pseudo-values solve ``(I + Diag(beta) A) U = Diag(beta) (V - (K *
      exp G) S0)`` (:func:`unit_lower_inverse`); ``o = (q * exp G) S0 + B
      U``; ``S = Diag(exp G_C) S0 + (K * exp(G_C - G))^T U``.  The state's
      products at HIGHEST precision (a float32 matmul would otherwise round
      the state to bf16 on the MXU), in the kernel too — where ``A`` and
      ``B`` keep the rule by a reference row between the two halves of a
      block, level by level (``ops/pallas/delta_rule.py``'s header).

    ``allow_neg_eigval`` (``solar_open2``'s ``kda_allow_neg_eigval``; False:
    the program before the option, jaxpr for jaxpr): ``beta = 2 sigmoid(n
    W_beta)`` in (0, 2), so a step's transition ``Diag(exp g) (I - beta k
    k^T)`` has an eigenvalue in (-1, 1) along ``k`` — a state may flip sign
    along a key, not only shrink.  Both forms take ``beta`` ready-made
    (``delta_rule_step`` takes ``beta k``): nothing else changes.  The
    chunked form's solve then meets ``|beta_i A_ij|`` up to 2, not 1.  An
    ARBITRARY unit-lower matrix with such entries has an inverse that grows
    as 3^k; this one does not: entry (i, j) of ``(I + Diag(beta) A)^-1`` is
    ``-beta_i k_i^T (the transitions between j and i) k_j``, a product of
    contractions for ``beta`` in (0, 2), so it stays under 2 — and
    :func:`unit_lower_inverse` forms only such sub-inverses and products of
    three of them, nothing that cancels.  What tests/test_solar_open2.py
    reads (float32, CPU, against a float64 recurrence, relative to the
    outputs' size): on keys that REPEAT through a full 32-row piece (``k_i =
    +-k_j``, the solve's worst case), ``beta`` 1.99 and a decay of 1 or 0.999
    a step the chunked form is off by 9e-6 at the most (the float32
    recurrence itself: 8e-7; drawn keys: 7e-7); the test holds it to 5e-5.
    """

    type_name = "kimi_delta_attention"
    # a float32 matrix a head whose update READS the state it changes —
    # nothing snapshots it or rolls it back (ROADMAP B-I 5): what each
    # deployment option lacks for it
    # (inference_manager.refuse_unsupported_slot_state)
    refusal_order = 1
    refusals = {
        "kv_page_size": (
            "; for a delta state, a snapshot of the float32 matrix a head at "
            "a shared prefix's end (every token rewrites it whole: no page "
            "of it outlives a position)"),
        "kv_dtype": (
            "; a delta state is float32 by its recurrence (the correction "
            "subtracts what the state already holds): 'int8' has no reading "
            "for it"),
        "max_spec_tokens": (
            "; a delta state has no rollback at all: its update reads the "
            "state it changes, so a rejected token leaves nothing to invert"),
        "tp": (
            "; for the delta rule a rule that shards its heads (the fused "
            "projection's columns, the conv's channels, the state's head "
            "axis, W_o's rows)"),
        "pipelined": " (nor a delta state's matrices)",
    }
    # the projections weight-only int8 replaces (serve/quant.py); the
    # decay's pair and ``W_beta`` stay as they are: they are float32 paths
    int8_params = ("g_a", "g_b", "o_proj")
    NORM_EPS = 1e-6     # the L2 norms' floor

    def __init__(self, embed_dim: int, num_heads: int, head_dim: int,
                 eps: float = 1e-5, chunk: int = 32, dtype=jnp.float32,
                 allow_neg_eigval: bool = False):
        if chunk & (chunk - 1):
            raise ValueError("the chunked form's piece is a power of two")
        self.allow_neg_eigval = bool(allow_neg_eigval)
        self.embed_dim = int(embed_dim)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.eps = float(eps)
        self.chunk = int(chunk)
        self.dtype = jnp.dtype(dtype).name

    @property
    def inner(self) -> int:
        return self.num_heads * self.head_dim

    def infer_shapes(self, in_specs):
        return [TensorSpec(in_specs[1].shape, jnp.dtype(self.dtype))]

    def params(self) -> List[ParamSpec]:
        dt, f32 = jnp.dtype(self.dtype), jnp.dtype("float32")
        # the two low-rank pairs (decay, output gate) are head_dim wide
        e, r, w, h = self.embed_dim, self.head_dim, self.inner, self.num_heads
        # the family's own initialisation (as Mamba-2's): A spread over
        # [1, 16], a step bias whose softplus spreads log-uniformly over
        # [1e-3, 1e-1]
        a_log = _init(lambda s: jnp.log(jnp.linspace(1.0, 16.0, s[0])))
        dt_bias = _init(lambda s: jnp.log(jnp.expm1(jnp.exp(jnp.linspace(
            math.log(1e-3), math.log(1e-1), s[0])))))
        return [
            ParamSpec("f_a", TensorSpec((e, r), dt)),
            ParamSpec("f_b", TensorSpec((r, w), dt)),
            ParamSpec("dt_bias", TensorSpec((w,), f32), dt_bias,
                      pin_dtype=True),
            ParamSpec("A_log", TensorSpec((h,), f32), a_log, pin_dtype=True),
            ParamSpec("b_proj", TensorSpec((e, h), dt)),
            ParamSpec("g_a", TensorSpec((e, r), dt)),
            ParamSpec("g_b", TensorSpec((r, w), dt)),
            ParamSpec("o_norm", TensorSpec((self.head_dim,), dt),
                      _init(jnp.ones)),
            ParamSpec("o_proj", TensorSpec((w, e), dt)),
        ]

    def flops(self, in_specs):
        t = in_specs[1].shape[0]
        e, r, w = self.embed_dim, self.head_dim, self.inner
        return 2 * t * (2 * (e * r + r * w) + e * self.num_heads + w * e
                        + 4 * w * self.head_dim)

    def state_specs(self, max_requests, max_seq_len, max_spec_tokens=0,
                    head_axes=()):
        shape = (max_requests + 1, self.num_heads, self.head_dim,
                 self.head_dim)
        return {"kda": (shape, "float32", TensorSharding.replicated(4))}

    def _unit(self, a):
        """``a / max(|a|, NORM_EPS)`` over the head's channels."""
        return a / jnp.maximum(jnp.sqrt(jnp.sum(a * a, axis=-1,
                                                keepdims=True)),
                               self.NORM_EPS)

    def _gated_norm(self, o, gate, gain):
        """The head's RMS norm, THEN the sigmoid gate."""
        return _rms_norm(o, gain.astype(jnp.float32), self.eps) \
            * jax.nn.sigmoid(gate)

    # ---- the two forms ----------------------------------------------------
    def _kernels(self, ctx) -> bool:
        """Whether both forms go by their Pallas kernels: the flag every
        kernel obeys, and a head of whole lanes (or interpret mode)."""
        return bool(ctx.extras.get("pallas_decode")) and (
            self.head_dim % LANE == 0
            or bool(ctx.extras.get("pallas_interpret")))

    def _step(self, q, k, v, g, beta, kda, seg, ctx):
        """The decode scan's step: one row a request."""
        alpha = jnp.where(seg.fresh[:, None, None], 0.0, jnp.exp(g))
        kb = beta[..., None] * k
        if self._kernels(ctx):
            from ..ops.pallas.delta_rule import delta_rule_step

            o, kda = delta_rule_step(
                kda, alpha, k, kb, q, v, seg.rows, seg.live,
                interpret=bool(ctx.extras.get("pallas_interpret")))
            return o, kda, "delta_rule_step"
        s = kda[seg.rows] * alpha[..., None]
        u = v - jnp.sum(s * k[..., None], axis=2)
        s = s + kb[..., None] * u[:, :, None, :]
        o = jnp.sum(s * q[..., None], axis=2)
        with jax.named_scope("state_write"):
            kda = _set_rows(kda, seg.store, s)
        return jnp.where(seg.live[:, None, None], o, 0.0), kda, "xla_rows"

    def _prompt(self, q, k, v, g, beta, kda, seg, ctx):
        """A prompt chunk or a flat step: the chunked form, its pieces by
        the Pallas kernel ``delta_rule_chunk`` or by :meth:`_chunked`'s
        loop (the kernels off, a head that is not whole lanes: the CPU
        oracle) — ``(o, kda, which)``."""
        if not self._kernels(ctx):
            return (*self._chunked(q, k, v, g, beta, kda, seg), "xla_loop")
        from ..ops.pallas.delta_rule import delta_rule_chunk

        o, kda = delta_rule_chunk(
            kda, q, k, v, g, beta, self._pieces(seg), chunk=self.chunk,
            interpret=bool(ctx.extras.get("pallas_interpret")))
        # rows of no piece are whatever the output's buffer held
        return (jnp.where(seg.live[:, None, None], o, 0.0), kda,
                "delta_rule_chunk")

    launch_reads = ("chunk",)

    def launch_counts(self, decode, prompt, layers, counted):
        """``prompt_kda_pieces``: the pieces the chunked form runs for the
        launch's prompt segments (one request's consecutive rows of ONE flat
        batch or scan chunk) — ``ceil(rows / chunk)`` a segment:
        :meth:`_pieces`' rule, on the host."""
        if prompt is None:
            return {}, {}
        return {"prompt_kda_pieces": sum(-(-(hi - lo) // self.chunk)
                                         for lo, hi in prompt)}, {}

    def _pieces(self, seg):
        """The chunked form's pieces in row order, as ``delta_rule_chunk``
        prefetches them and as :meth:`_chunked`'s loop finds them: how many
        there are and, a piece, its first row, how many of the ``chunk`` rows
        from there are its own, its slot's state row, where its entering
        state is and whether it holds its segment's last row."""
        from ..ops.pallas.delta_rule import CONTINUE, STORED, ZEROS

        i32, t = jnp.int32, seg.live.shape[0]
        piece = (seg.start | (seg.offset % self.chunk == 0)) & seg.live
        first = jnp.argsort(~piece, stable=True).astype(i32)
        own = jnp.zeros((t + 1,), i32).at[jnp.cumsum(piece.astype(i32))].add(
            seg.live.astype(i32))[1:]
        init = jnp.where(seg.start, jnp.where(seg.fresh, ZEROS, STORED),
                         CONTINUE)[first]
        last = seg.last[jnp.clip(first + own - 1, 0, t - 1)]
        return (jnp.sum(piece.astype(i32)), first, own, seg.rows[first],
                init.astype(i32), last.astype(i32))

    def _chunked(self, q, k, v, g, beta, kda, seg):
        """A prompt chunk or a flat step (see the class docstring)."""
        t, h, d = q.shape
        c, nreq = self.chunk, kda.shape[0] - 1
        hi = jax.lax.Precision.HIGHEST
        piece = (seg.start | (seg.offset % c == 0)) & seg.live
        order = jnp.argsort(~piece, stable=True).astype(jnp.int32)
        piece_id = jnp.cumsum(piece.astype(jnp.int32))
        # a piece's window may run past the batch's end: rows of no piece
        pad = lambda a: jnp.concatenate(
            [a, jnp.zeros((c,) + a.shape[1:], a.dtype)])
        q, k, v, g, beta = (pad(a) for a in (q, k, v, g, beta))
        live, piece_id, last = pad(seg.live), pad(piece_id), pad(seg.last)
        i = jnp.arange(c, dtype=jnp.int32)
        causal = (i[:, None] >= i[None, :])[:, :, None, None]
        strict = (i[:, None] > i[None, :])[None]
        zero = jnp.int32(0)

        def one(p, carry):
            out, kda, s_prev = carry
            f = order[p]
            win = lambda a: jax.lax.dynamic_slice_in_dim(a, f, c, axis=0)
            mine = win(live) & (win(piece_id) == piece_id[f])       # [C]
            m3 = mine[:, None, None]
            qc, kc, vc = (jnp.where(m3, win(a), 0.0) for a in (q, k, v))
            gc = jnp.where(m3, win(g), 0.0)
            bc = jnp.where(mine[:, None], win(beta), 0.0)           # [C, H]
            at = (seg.rows[f], zero, zero, zero)
            own = jax.lax.dynamic_slice(kda, at, (1,) + kda.shape[1:])[0]
            s0 = jnp.where(seg.start[f],
                           jnp.where(seg.fresh[f], 0.0, own), s_prev)
            run = jnp.cumsum(gc, axis=0)                            # G <= 0
            # exp(G_i - G_j), j <= i: explicit differences, none above 0
            between = jnp.exp(jnp.where(
                causal, run[:, None] - run[None, :], -jnp.inf))  # [C,C,H,D]
            kk = kc[None] * between
            a = jnp.sum(kc[:, None] * kk, axis=-1).transpose(2, 0, 1)
            b = jnp.sum(qc[:, None] * kk, axis=-1).transpose(2, 0, 1)
            decayed = jnp.exp(run)
            rhs = bc[..., None] * (vc - jnp.einsum(
                "chk,hkv->chv", kc * decayed, s0, precision=hi))
            solve = unit_lower_inverse(
                jnp.where(strict, bc.T[:, :, None] * a, 0.0))       # [H,C,C]
            u = jnp.einsum("hij,jhv->ihv", solve, rhs, precision=hi)
            o = jnp.einsum("chk,hkv->chv", qc * decayed, s0, precision=hi) \
                + jnp.einsum("hij,jhv->ihv", b, u, precision=hi)
            s1 = decayed[-1][..., None] * s0 + jnp.einsum(
                "jhk,jhv->hkv", kc * jnp.exp(run[-1][None] - run), u,
                precision=hi)
            # the window's rows past the piece are later pieces' (written
            # after this one, in row order) or pads (zero)
            out = jax.lax.dynamic_update_slice(
                out, jnp.where(m3, o, 0.0), (f, zero, zero))
            # the slot's own row after a segment's last row, else scratch
            store = jnp.where(jnp.any(mine & win(last)), seg.rows[f], nreq)
            kda = jax.lax.dynamic_update_slice(
                kda, s1[None], (store, zero, zero, zero))
            return out, kda, s1

        with jax.named_scope("state_write"):
            out, kda, _ = jax.lax.fori_loop(
                0, jnp.sum(piece.astype(jnp.int32)), one,
                (jnp.zeros((t + c, h, d), jnp.float32), kda,
                 jnp.zeros(kda.shape[1:], kda.dtype)))
        return out[:t], kda

    def lower(self, ctx, inputs, params):
        bc, state = _require(ctx, self.type_name)
        qkv, x = inputs
        t, h, d = x.shape[0], self.num_heads, self.head_dim
        f32 = jnp.float32
        hi = jax.lax.Precision.HIGHEST
        kda = state["kda"]
        seg = Segments(_flat(bc), kda.shape[0] - 1)
        weight = lambda name: dequant(params[name],
                                      params.get(f"{name}_scale"), x.dtype)
        with jax.named_scope("qkv_proj"):
            q, k, v = (qkv[:, n * h * d:(n + 1) * h * d].astype(f32)
                       .reshape(t, h, d) for n in range(3))
            q, k = self._unit(q) * d ** -0.5, self._unit(k)
            # the decay and beta in float32: the low-rank pair's second
            # product on float32 operands (HIGHEST: the MXU would round
            # them to bf16)
            low = jnp.dot(x, params["f_a"], preferred_element_type=f32)
            raw = jnp.dot(low, params["f_b"].astype(f32), precision=hi)
            g = -jnp.exp(params["A_log"])[None, :, None] * jax.nn.softplus(
                raw + params["dt_bias"]).reshape(t, h, d)
            beta = jax.nn.sigmoid(jnp.dot(x, params["b_proj"],
                                          preferred_element_type=f32))
            if self.allow_neg_eigval:
                beta = 2.0 * beta
        with jax.named_scope("attend"):
            if ctx.extras.get("one_row_per_request"):
                o, kda, path = self._step(q, k, v, g, beta, kda, seg, ctx)
                batch = "one_row_per_request"
            else:
                o, kda, ran = self._prompt(q, k, v, g, beta, kda, seg, ctx)
                path, batch = "chunked", type(bc).__name__
            ctx.extras["state_out"] = {"kda": kda}
            paths = ctx.extras.get("attention_paths")
            if paths is not None:
                paths[(self.type_name, batch)] = path + (
                    "+neg_eigval" if self.allow_neg_eigval else "")
                if path == "chunked":   # the FORM; who ran its pieces:
                    paths[("delta_pieces", self.type_name)] = ran
        with jax.named_scope("o_proj"):
            gate = jnp.dot(
                jnp.dot(x, weight("g_a"), preferred_element_type=f32
                        ).astype(x.dtype),
                weight("g_b"), preferred_element_type=f32)
            o = self._gated_norm(o, gate.reshape(t, h, d), params["o_norm"])
            y = jnp.dot(o.reshape(t, h * d).astype(x.dtype), weight("o_proj"),
                        preferred_element_type=f32)
            return [y.astype(self.dtype)]
