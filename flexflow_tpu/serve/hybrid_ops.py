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
* :class:`EvaAttention` — EVA attention (EvaByte): exact attention inside
  the query's own window, one summary per chunk of every earlier window, one
  softmax over both.  Its cache COMPACTS itself: when a window closes, its
  raw keys and values are replaced by their summaries, so a row's live cache
  is a contiguous prefix whose length is not the position, and falls.

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

import math
from typing import List, Optional

import jax
import jax.numpy as jnp

from ..core.graph import ParamSpec, TensorSpec
from ..core.op import Op, OpContext, register_op
from ..core.sharding import TensorSharding
from .batch_config import BatchConfig, PrefillBatchConfig
from .ops import (DUS_MAX_TOKENS, NEG_INF, IncMultiHeadSelfAttention,
                  apply_rope)
from .quant import dequant

LANE = 128  # the kernels' seq-block granule: every cache seq dim is padded to it


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


def _put_blocks(kc, vc, kb, vb, rows, start):
    """``kb[i]`` / ``vb[i]`` (``[heads, tile, D]`` each) into row ``rows[i]``
    of the caches from seq index ``start[i]`` on: one in-place block write
    per prefill tile (ops._prefill_attend says why not a scatter)."""
    zero = jnp.int32(0)
    for i in range(kb.shape[0]):
        at = (rows[i], zero, start[i], zero)
        kc = jax.lax.dynamic_update_slice(kc, kb[i][None], at)
        vc = jax.lax.dynamic_update_slice(vc, vb[i][None], at)
    return kc, vc


def _init(fn):
    """An initializer that ignores its key: ``fn(shape) -> array``."""
    return lambda key, shape, dtype: fn(shape).astype(dtype)


class _SlotStateOp(Op):
    """Common to the ops whose state is per slot and of fixed size."""

    stateful = True
    slot_state = True   # register_serve_capacities sizes it; refusals key on it
    state_owner: Optional[str] = None

    def parallel_dims(self, in_specs):
        return {}   # replicated: no sharding rule yet (tp > 1 is refused)


@register_op
class CausalConv1d(_SlotStateOp):
    """Depthwise causal convolution over each request's own positions:
    ``y_t = silu(sum_j w[j] * x_{t-(K-1-j)} + b)``, positions before the
    request's first read zero.  Input/output ``[max_tokens, channels]``;
    state ``conv [max_requests + 1, K - 1, channels]``: the last ``K - 1``
    inputs of each slot."""

    type_name = "causal_conv1d"

    def __init__(self, channels: int, kernel: int = 4, dtype=jnp.float32):
        self.channels = int(channels)
        self.kernel = int(kernel)
        self.dtype = jnp.dtype(dtype).name

    def infer_shapes(self, in_specs):
        return [TensorSpec(in_specs[0].shape, jnp.dtype(self.dtype))]

    def params(self) -> List[ParamSpec]:
        dt = jnp.dtype(self.dtype)
        return [ParamSpec("weight",
                          TensorSpec((self.kernel, self.channels), dt)),
                ParamSpec("bias", TensorSpec((self.channels,), dt))]

    def state_specs(self, max_requests, max_seq_len, max_spec_tokens=0,
                    head_axes=()):
        shape = (max_requests + 1, self.kernel - 1, self.channels)
        return {"conv": (shape, self.dtype, TensorSharding.replicated(3))}

    def flops(self, in_specs):
        return 2 * self.kernel * in_specs[0].size

    def lower(self, ctx, inputs, params):
        bc, state = _require(ctx, self.type_name)
        x = inputs[0]
        tails = state["conv"]
        k = self.kernel
        seg = Segments(_flat(bc), tails.shape[0] - 1)
        tail = tails[seg.rows]                       # [T, K-1, C]
        # taps[j] = the input j positions back from the row's own:
        # a row of this step where the segment reaches that far, else the
        # slot's stored tail, else (before the request began) zero
        taps = [x]
        for back in range(1, k):
            here = jnp.concatenate(
                [jnp.zeros_like(x[:back]), x[:-back]], axis=0)
            at = jnp.clip(k - 1 + seg.offset - back, 0, k - 2)
            stored = jnp.take_along_axis(tail, at[:, None, None], axis=1)[:, 0]
            val = jnp.where((seg.offset >= back)[:, None], here, stored)
            taps.append(jnp.where(
                ((seg.pos >= back) & seg.live)[:, None], val, 0))
        w = params["weight"].astype(jnp.float32)
        y = params["bias"].astype(jnp.float32)
        for back, tap in enumerate(taps):
            y = y + tap.astype(jnp.float32) * w[k - 1 - back]
        y = jax.nn.silu(y)
        with jax.named_scope("state_write"):
            # what the segment leaves behind: the K-1 newest inputs as of
            # its last row (oldest first, as the tail is read)
            left = jnp.stack(taps[k - 2::-1], axis=1)
            ctx.extras["state_out"] = {
                "conv": _set_rows(tails, seg.store, left)}
        return [y.astype(self.dtype)]


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


class DiffAttention(_SlotStateOp):
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
        shape = (max_requests + 1, self.kv_pairs, seq, self.pair_dim)
        sh = TensorSharding.replicated(4)
        names = ("k", "v") if self.mode == "full" else ("wk", "wv")
        return {n: (shape, self.dtype, sh) for n in names}

    # ---- compute -------------------------------------------------------
    def _project(self, x, params):
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

    @jax.named_scope("kv_write")
    def _write(self, kc, vc, k, v, bc, seg, tiled):
        """This step's keys and values into the cache (a ring for a window
        layer: position ``p`` at slot ``p % ring``)."""
        base = _flat(bc)
        ring = kc.shape[2] if self.mode == "window" else 0
        pos = base.token_position % ring if ring else base.token_position
        if not tiled:
            put = IncMultiHeadSelfAttention._scatter_rows_pos
            return put(kc, seg.rows, pos, k), put(vc, seg.rows, pos, v)
        # a tiled prefill chunk: one block write per request-homogeneous
        # tile (ops._prefill_attend says why not a scatter).  A tile starts
        # tile-aligned and the ring is whole tiles, so a block never wraps;
        # its tail pads write zeros at positions no query of this chunk
        # sees, which a later chunk overwrites before any does.
        bq = bc.tile_size
        g = k.shape[0] // bq
        rows = jnp.min(seg.rows.reshape(g, bq), axis=1)
        start = pos.reshape(g, bq)[:, 0]
        valid = seg.live.reshape(g, 1, bq, 1)
        block = lambda a: jnp.where(
            valid, a.reshape(g, bq, self.kv_pairs, self.pair_dim)
            .transpose(0, 2, 1, 3), 0).astype(kc.dtype)
        return _put_blocks(kc, vc, block(k), block(v), rows, start)

    def _attend_xla(self, q, kc, vc, rows, pos):
        """Plain attention of query groups against their slot's cache:
        ``q [G, B, pairs, heads, D]``, the group's cache row ``rows [G]``,
        positions ``pos [G, B]``.  A flat row is a group of one; a prefill
        tile is a group of ``tile`` rows, which reads its slot's ring ONCE.
        The CPU oracle of the kernels, and the window layers' prefill path
        on the chip too."""
        kr, vr = kc[rows], vc[rows]                  # [G, pairs, S, D]
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
        """``[T, pairs, heads, D]``: each head's softmax over its slot's
        cache times the pair's value — in the kernels' output type (the
        queries'; their accumulator is float32) or float32 from XLA —, and
        the path taken.  ``lower`` subtracts the two heads of a pair in
        float32."""
        from ..ops.pallas.attention import decode_attention, prefill_attention

        base = _flat(bc)
        t = q.shape[0]
        nq, d = self.q_per_pair, self.pair_dim
        nreq = kc.shape[0] - 1
        pallas = bool(ctx.extras.get("pallas_decode"))
        interp = bool(ctx.extras.get("pallas_interpret"))
        if tiled:
            bq = bc.tile_size
            g = t // bq
            rows = jnp.min(seg.rows.reshape(g, bq), axis=1)
            pos = base.token_position.reshape(g, bq)
            if self.mode == "window":
                out = self._attend_xla(
                    q.reshape(g, bq, self.kv_pairs, nq, d), kc, vc, rows, pos)
                return out.reshape(t, self.kv_pairs, nq, d), "xla_tile"
            out = prefill_attention(
                q.reshape(g, bq, self.kv_pairs * nq, d), kc, vc, rows,
                pos[:, 0], scale=self.scaling_factor, interpret=interp)
            return out.reshape(t, self.kv_pairs, nq, d), "prefill_attention"
        if pallas:
            # pads stream one block, not a stale row's whole prefix
            pos = jnp.where(seg.rows == nreq, 0, base.token_position)
            out = decode_attention(
                q.reshape(t, self.kv_pairs * nq, d), kc, vc, seg.rows, pos,
                scale=self.scaling_factor, interpret=interp,
                window=self.window)
            return out.reshape(t, self.kv_pairs, nq, d), "decode_attention"
        out = self._attend_xla(q[:, None], kc, vc, seg.rows,
                               base.token_position[:, None])
        return out[:, 0], "xla"

    def lower(self, ctx, inputs, params):
        bc, state = _require(ctx, self.type_name)
        x = inputs[0]
        t = x.shape[0]
        names = ("wk", "wv") if self.mode == "window" else ("k", "v")
        kc, vc = state[names[0]], state[names[1]]
        seg = Segments(_flat(bc), kc.shape[0] - 1)
        with jax.named_scope("qkv_proj"):
            q, k, v = self._project(x, params)
        # a tiled prefill chunk takes the per-tile paths (block writes, the
        # prefill kernel) where the kernels are on; off them it is a flat
        # batch like any other (the CPU oracle)
        tiled = (isinstance(bc, PrefillBatchConfig)
                 and bool(ctx.extras.get("pallas_decode")))
        with jax.named_scope("attend"):
            if self.mode != "cross":
                kc, vc = self._write(kc, vc, k, v, bc, seg, tiled)
                ctx.extras["state_out"] = {names[0]: kc, names[1]: vc}
            out, path = self._attend(q, kc, vc, bc, seg, ctx, tiled)
            paths = ctx.extras.get("attention_paths")
            if paths is not None:
                paths[(f"{self.mode}_attention", type(bc).__name__)] = path
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
            o = o.astype(x.dtype).reshape(t, self.num_q_heads * self.head_dim)
        with jax.named_scope("o_proj"):
            o_w = dequant(params["o_proj"], params.get("o_proj_scale"),
                          o.dtype)
            y = jnp.dot(o, o_w, preferred_element_type=jnp.float32)
            return [(y + params["o_bias"]).astype(self.dtype)]


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


def compact_geometry(graph):
    """``(window, chunk, layers)`` of the graph's compacting caches, or
    None for a graph that has none."""
    ops = [n.op for n in graph.nodes if isinstance(n.op, EvaAttention)]
    if not ops:
        return None
    return ops[0].window, ops[0].chunk, len(ops)


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
    def _write(self, kc, vc, k, v, rows, at, bc, tiled):
        """This pass's keys and values to ``(rows, at)``: ``at`` the compact
        index, ``rows`` the scratch row for what the pass leaves out."""
        if not tiled:
            put = IncMultiHeadSelfAttention._scatter_rows_pos
            return put(kc, rows, at, k), put(vc, rows, at, v)
        # one block write per tile; a tile lies inside one window, so its
        # entries are contiguous, and its tail pads land beyond the open
        # window's newest entry (which a later chunk overwrites before any
        # query reads it)
        bq = bc.tile_size
        g = k.shape[0] // bq
        h, hd = self.num_kv_heads, self.head_dim
        valid = (rows != kc.shape[0] - 1).reshape(g, 1, bq, 1)
        block = lambda a: jnp.where(
            valid, a.reshape(g, bq, h, hd).transpose(0, 2, 1, 3),
            0).astype(kc.dtype)
        return _put_blocks(kc, vc, block(k), block(v),
                           rows.reshape(g, bq)[:, 0], at.reshape(g, bq)[:, 0])

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
            return out.reshape(q.shape), "prefill_attention"
        if ctx.extras.get("pallas_decode"):
            out = decode_attention(q, kc, vc, rows, at,
                                   scale=self.scaling_factor,
                                   interpret=interp)
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
                kc, vc = self._write(kc, vc, k, v, rows, idx, bc, tiled)
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
