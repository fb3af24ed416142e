"""Serving ops of a Mamba-2 / routed-expert hybrid (``nemotron_h``); the
routed-expert layer also serves ``cohere2_moe``'s gated experts.

What such a decoder adds to the serve graph beside ``CausalConv1d`` and
``IncMultiHeadSelfAttention``, each mechanism a class of its own so that a
device trace names it (``<OpClass>.<node>``):

* :class:`Mamba2Scan` — the state-space-duality scan (Dao & Gu 2024): per
  slot and head ONE ``[head_dim, state]`` float32 matrix under a scalar
  decay per head, B and C shared by groups of heads.  State kind
  ``ssd_state`` (kv_allocator.py), fixed per slot.
* :class:`GatedGroupNorm` — ``RMSNorm_groups(y * silu(z))`` behind it.
* :class:`MoERouter`, :class:`MoEDispatch`, :class:`MoEExperts`,
  :class:`MoECombine` — a DROPLESS routed-expert layer: sigmoid scores over
  ALL the published experts, top-k, the (row, choice) pairs that fall on the
  experts THIS chip holds sorted by expert, the experts as grouped GEMMs
  over those sorted rows in the form the model states — ``relu2``: one up,
  ``relu^2``, one down; ``swiglu``: gate and up, ``silu(gate) * up``, one
  down —, the weighted sum back in row order.  Shapes are
  static by the upper bound ``rows x k`` on pairs, never by a capacity that
  drops; a pair routed to an expert another chip holds adds nothing here.
* :class:`SharedExpertLinear` — a ``Linear`` under a name of its own, for
  the projections of experts every row visits (a device trace then tells
  them from the head's and the other dense GEMMs).

Imported where a graph uses them (``FFModel.mamba2_scan`` ...), so that no
other model pays for the import.
"""

from __future__ import annotations

import math
from typing import List

import jax
import jax.numpy as jnp

from ..core.graph import ParamSpec, TensorSpec
from ..core.op import Op, register_op
from ..core.sharding import TensorSharding
from ..ops.linear import Linear
from .hybrid_ops import Segments, _flat, _init, _require, _SlotStateOp

HI = jax.lax.Precision.HIGHEST
GMM_ROWS = 128  # the grouped GEMM's row tile: pairs are padded to it


def _note_path(ctx, kind, batch, path):
    paths = ctx.extras.get("attention_paths")
    if paths is not None:
        paths[(kind, batch)] = path


@register_op
class Mamba2Scan(_SlotStateOp):
    """Mamba-2's scan over each request's own positions.

    Inputs ``xBC [T, H P + 2 G N]`` (the conv's output: ``x [H, P]``,
    ``B [G, N]``, ``C [G, N]``; head h reads group ``h // (H / G)``) and
    ``dt [T, H]`` (before its bias and softplus).  ``delta = softplus(dt +
    dt_bias)``, ``A = -exp(A_log)`` (a scalar a head);
    ``S_t = exp(delta_t A) S_{t-1} + delta_t x_t (x) B_t``;
    ``y_t = S_t C_t + D x_t``.  State ``ssd [max_requests + 1, H, P, N]``
    float32.

    The decode scan (every live row a request of its own) updates the whole
    state array in slot order — decay, rank-one update and read-out in one
    elementwise pass.  A prompt chunk or a flat step runs the CHUNKED form:
    inside the batch ``((C B') * L) (delta x)`` with ``L`` the products of
    the decays between two rows of one request (zero across requests), and
    per request in the batch — a loop of as many trips as it holds requests
    — the carried state's term and the state left behind: matrix products,
    no trip per row.  The batch is ONE chunk (at most ``max_tokens`` rows);
    the published ``chunk_size`` is the training kernel's tile, not a part
    of the result.
    """

    type_name = "mamba2_scan"

    def __init__(self, num_heads: int, head_dim: int, n_groups: int,
                 d_state: int, dt_min: float = 1e-3, dt_max: float = 1e-1,
                 dt_floor: float = 1e-4, dtype=jnp.float32):
        if num_heads % n_groups:
            raise ValueError("the heads divide into the B/C groups")
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.n_groups = int(n_groups)
        self.d_state = int(d_state)
        self.dt_limits = (float(dt_min), float(dt_max), float(dt_floor))
        self.dtype = jnp.dtype(dtype).name

    @property
    def inner(self) -> int:
        return self.num_heads * self.head_dim

    def infer_shapes(self, in_specs):
        return [TensorSpec((in_specs[0].shape[0], self.inner),
                           jnp.dtype(self.dtype))]

    def params(self) -> List[ParamSpec]:
        f32 = jnp.dtype("float32")
        lo, hi, floor = self.dt_limits
        # Mamba-2's own initialisation: A spread over [1, 16], D = 1, a step
        # bias whose softplus spreads log-uniformly over [dt_min, dt_max]
        a_log = _init(lambda s: jnp.log(jnp.linspace(1.0, 16.0, s[0])))
        dt_bias = _init(lambda s: jnp.log(jnp.expm1(jnp.maximum(jnp.exp(
            jnp.linspace(math.log(lo), math.log(hi), s[0])), floor))))
        h = (self.num_heads,)
        return [ParamSpec("A_log", TensorSpec(h, f32), a_log, pin_dtype=True),
                ParamSpec("D", TensorSpec(h, f32), _init(jnp.ones),
                          pin_dtype=True),
                ParamSpec("dt_bias", TensorSpec(h, f32), dt_bias,
                          pin_dtype=True)]

    def state_specs(self, max_requests, max_seq_len, max_spec_tokens=0,
                    head_axes=()):
        shape = (max_requests + 1, self.num_heads, self.head_dim,
                 self.d_state)
        return {"ssd": (shape, "float32", TensorSharding.replicated(4))}

    def flops(self, in_specs):
        return 6 * in_specs[0].shape[0] * self.inner * self.d_state

    # ---- the two forms ----------------------------------------------------
    def _heads(self, a):
        """``[.., G, N]`` to ``[.., H, N]``: each head its group's row."""
        return jnp.repeat(a, self.num_heads // self.n_groups, axis=-2)

    def _slot_order(self, la, dx, b, c, ssd, seg):
        """The decode scan's step: every slot's matrices decayed, updated
        and read where a row of the batch is its request's, untouched where
        none is — ONE pass over the state array, no gather of 2 MB a row and
        no scatter back."""
        nslot = ssd.shape[0]
        at = seg.rows                      # pads land on the scratch row
        by_slot = lambda a: jnp.zeros((nslot,) + a.shape[1:], a.dtype
                                      ).at[at].set(a)
        live = by_slot(seg.live)
        keep = jnp.where(by_slot(seg.fresh)[:, None], 0.0,
                         jnp.exp(by_slot(la)))                 # [slots, H]
        bh, ch = self._heads(by_slot(b)), self._heads(by_slot(c))
        # (no matmul: a float32 one would round the state to bf16 on the MXU)
        new = keep[:, :, None, None] * ssd \
            + by_slot(dx)[:, :, :, None] * bh[:, :, None, :]
        y = jnp.sum(new * ch[:, :, None, :], axis=-1)
        with jax.named_scope("state_write"):
            ssd = jnp.where(live[:, None, None, None], new, ssd)
        return y[at], ssd

    def _chunked(self, la, dx, b, c, ssd, seg):
        """A prompt chunk or a flat step (see the class docstring)."""
        t = la.shape[0]
        seg_id = jnp.cumsum(seg.start.astype(jnp.int32))
        i = jnp.arange(t, dtype=jnp.int32)
        same = (seg_id[:, None] == seg_id[None, :]) \
            & (i[:, None] >= i[None, :]) & seg.live[:, None]
        run = jnp.cumsum(la, axis=0)                           # [T, H]
        # the decays from row j (exclusive) to row i of one request
        between = jnp.where(same[None], jnp.exp(jnp.minimum(
            run.T[:, :, None] - run.T[:, None, :], 0.0)), 0.0)  # [H, T, T]
        cb = jnp.einsum("ign,jgn->gij", c, b, precision=HI)
        scores = jnp.repeat(cb, self.num_heads // self.n_groups, axis=0) \
            * between
        out = jnp.einsum("hij,jhp->ihp", scores, dx, precision=HI)
        # per request in the batch: what its stored state adds, and the
        # state it leaves behind.  ``since``: the decays from the request's
        # first row of this batch (inclusive) to row i
        first_row = i - seg.offset
        since = run - (run[first_row] - la[first_row])         # [T, H]
        bh, ch = self._heads(b), self._heads(c)                # [T, H, N]
        first = seg.start & seg.live
        order = jnp.argsort(~first, stable=True)
        zero = jnp.int32(0)

        def one(n, carry):
            out, ssd = carry
            f = order[n]
            mine = (seg_id == seg_id[f]) & seg.live
            at = (seg.rows[f], zero, zero, zero)
            s0 = jax.lax.dynamic_slice(ssd, at, (1,) + ssd.shape[1:])[0]
            s0 = jnp.where(seg.fresh[f], 0.0, s0)
            carried = jnp.einsum("thn,hpn->thp", ch, s0, precision=HI) \
                * jnp.exp(since)[..., None]
            out = out + jnp.where(mine[:, None, None], carried, 0.0)
            whole = jnp.sum(jnp.where(mine[:, None], la, 0.0), axis=0)
            left = jnp.where(mine[:, None], jnp.exp(jnp.minimum(
                whole[None, :] - since, 0.0)), 0.0)             # [T, H]
            s1 = jnp.exp(whole)[:, None, None] * s0 + jnp.einsum(
                "thp,thn->hpn", dx * left[..., None], bh, precision=HI)
            return out, jax.lax.dynamic_update_slice(ssd, s1[None], at)

        with jax.named_scope("state_write"):
            out, ssd = jax.lax.fori_loop(
                0, jnp.sum(first.astype(jnp.int32)), one, (out, ssd))
        return out, ssd

    def lower(self, ctx, inputs, params):
        bc, state = _require(ctx, self.type_name)
        xbc, dt = (a.astype(jnp.float32) for a in inputs)
        t, h, p = xbc.shape[0], self.num_heads, self.head_dim
        gn = self.n_groups * self.d_state
        x = xbc[:, :h * p].reshape(t, h, p)
        b = xbc[:, h * p:h * p + gn].reshape(t, self.n_groups, self.d_state)
        c = xbc[:, h * p + gn:].reshape(t, self.n_groups, self.d_state)
        delta = jax.nn.softplus(dt + params["dt_bias"])        # [T, H]
        la = -delta * jnp.exp(params["A_log"])     # log of the step's decay
        dx = delta[:, :, None] * x
        ssd = state["ssd"]
        seg = Segments(_flat(bc), ssd.shape[0] - 1)
        if ctx.extras.get("one_row_per_request"):
            y, ssd = self._slot_order(la, dx, b, c, ssd, seg)
            _note_path(ctx, self.type_name, "one_row_per_request",
                       "slot_order")
        else:
            y, ssd = self._chunked(la, dx, b, c, ssd, seg)
            _note_path(ctx, self.type_name, type(bc).__name__, "chunked")
        ctx.extras["state_out"] = {"ssd": ssd}
        y = y + params["D"][:, None] * x
        return [y.reshape(t, h * p).astype(self.dtype)]


class _Replicated(Op):
    """An op with no sharding rule yet (``tp > 1`` is refused for a graph
    that holds one: ``refuse_unsupported_slot_state``)."""

    def parallel_dims(self, in_specs):
        return {}


@register_op
class GatedGroupNorm(_Replicated):
    """``RMSNorm_groups(y * silu(z)) * gamma``: the mean square over each
    of ``n_groups`` groups of channels (Mamba-2's gated norm)."""

    type_name = "gated_group_norm"

    def __init__(self, channels: int, n_groups: int, eps: float = 1e-5,
                 dtype=jnp.float32):
        if channels % n_groups:
            raise ValueError("the channels divide into the norm's groups")
        self.channels = int(channels)
        self.n_groups = int(n_groups)
        self.eps = float(eps)
        self.dtype = jnp.dtype(dtype).name

    def infer_shapes(self, in_specs):
        return [TensorSpec(in_specs[0].shape, jnp.dtype(self.dtype))]

    def params(self) -> List[ParamSpec]:
        return [ParamSpec("gamma", TensorSpec((self.channels,),
                                              jnp.dtype(self.dtype)),
                          _init(jnp.ones))]

    def flops(self, in_specs):
        return 8 * in_specs[0].size

    def lower(self, ctx, inputs, params):
        y, z = (a.astype(jnp.float32) for a in inputs)
        v = (y * jax.nn.silu(z)).reshape(y.shape[0], self.n_groups, -1)
        v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True)
                              + self.eps)
        v = v.reshape(y.shape) * params["gamma"].astype(jnp.float32)
        return [v.astype(self.dtype)]


@register_op
class MoERouter(_Replicated):
    """The router over ALL ``num_experts`` published experts, in float32:
    ``s = sigmoid(x W)`` (``scoring`` ``sigmoid``) or ``s = softmax(x W)``
    over all of them (``softmax``: ``deepseek_v2``'s ``scoring_func``); the
    ``top_k`` largest of ``s + bias`` are chosen
    (ties to the lower id: ``lax.top_k``); their weights are ``s`` (without
    the bias), normalised to sum 1 (``norm_topk``; off, a softmax router's
    chosen weights stay the shares of ALL experts' mass they are) and times
    ``scaling``.
    ``bias=False``: a router without a correction bias has no such parameter
    either (plain sigmoid top-k).  Outputs ``(ids int32 [T, k], weights float32 [T, k])``; a caller that
    hands the forward an ``extras["routing"]`` dict finds the ids there by
    node (``benchmark/routing.py`` counts the choices that differ from the
    float32 reference's)."""

    type_name = "moe_router"

    def __init__(self, embed_dim: int, num_experts: int, top_k: int,
                 scaling: float = 1.0, norm_topk: bool = True,
                 dtype=jnp.float32, bias: bool = True,
                 scoring: str = "sigmoid"):
        if scoring not in ("sigmoid", "softmax"):
            raise ValueError("a router scores by 'sigmoid' or 'softmax', "
                             f"not {scoring!r}")
        self.scoring = scoring
        self.embed_dim = int(embed_dim)
        self.num_experts = int(num_experts)
        self.top_k = int(top_k)
        self.scaling = float(scaling)
        self.norm_topk = bool(norm_topk)
        self.bias = bool(bias)
        self.dtype = jnp.dtype(dtype).name

    def infer_shapes(self, in_specs):
        rows = (in_specs[0].shape[0], self.top_k)
        return [TensorSpec(rows, jnp.dtype("int32")),
                TensorSpec(rows, jnp.dtype("float32"))]

    def params(self) -> List[ParamSpec]:
        f32 = jnp.dtype("float32")
        ps = [ParamSpec("weight", TensorSpec(
            (self.embed_dim, self.num_experts), f32), pin_dtype=True)]
        if self.bias:
            ps.append(ParamSpec("e_score_correction_bias",
                                TensorSpec((self.num_experts,), f32),
                                _init(jnp.zeros), pin_dtype=True))
        return ps

    def flops(self, in_specs):
        return 2 * in_specs[0].size * self.num_experts

    def lower(self, ctx, inputs, params):
        x = inputs[0].astype(jnp.float32)
        s = jnp.dot(x, params["weight"], precision=HI)
        s = (jax.nn.softmax(s, axis=-1) if self.scoring == "softmax"
             else jax.nn.sigmoid(s))
        _, ids = jax.lax.top_k(
            s + params["e_score_correction_bias"] if self.bias else s,
            self.top_k)
        w = jnp.take_along_axis(s, ids, axis=-1)
        if self.norm_topk:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        sink = ctx.extras.get("routing")
        if sink is not None:    # a caller of the forward that reads choices
            sink[ctx.extras["node_name"]] = ids
        return [ids.astype(jnp.int32), w * self.scaling]


def _held(ctx, ids, lo, count):
    """Which (row, choice) pairs fall on the ``count`` experts held here
    (published ids ``lo ..``) AND belong to a row of a request, and each
    pair's expert among the held ones."""
    at = ids - lo
    held = (at >= 0) & (at < count)
    bc = ctx.extras.get("batch_config")
    if bc is not None:
        held = held & (_flat(bc).request_index >= 0)[:, None]
    return held, at


@register_op
class MoEDispatch(_Replicated):
    """The (row, choice) pairs on the ``num_held`` experts this chip holds
    (published ids ``held_lo ..``), sorted by expert.  Outputs ``(the
    pairs' rows of x in that order [M, d], rows per held expert int32
    [num_held], the sorted pairs' flat indices int32 [T k])`` with ``M`` =
    ``T k`` rounded up to the grouped GEMM's row tile; the pairs of absent
    experts and of rows of no request sort last and belong to no group.

    Where the step collects them (``extras["counters"]``, the decode scan)
    it leaves ``[experts visited, pairs, the fullest expert's pairs]``."""

    type_name = "moe_dispatch"
    # one per routed layer: InferenceManager.expert_layers counts these
    counts_load = True
    # what tp and pp lack for the routed layers beside a per-slot state
    # (inference_manager.refuse_unsupported_slot_state)
    refusal_order = 2
    refusals = {
        "tp": (
            "; for the routed experts an exchange of rows between the chips "
            "that hold them (here each graph computes the experts it holds "
            "and nothing brings the rest)"),
        "pipelined": (
            " (nor does it carry the routed layers' load counters out of a "
            "stage)"),
    }

    def __init__(self, num_held: int, held_lo: int = 0):
        self.num_held = int(num_held)
        self.held_lo = int(held_lo)

    def infer_shapes(self, in_specs):
        x, ids = in_specs
        pairs = ids.shape[0] * ids.shape[1]
        m = -(-pairs // GMM_ROWS) * GMM_ROWS
        return [TensorSpec((m, x.shape[1]), x.dtype),
                TensorSpec((self.num_held,), jnp.dtype("int32")),
                TensorSpec((pairs,), jnp.dtype("int32"))]

    def flops(self, in_specs):
        return in_specs[1].size * self.num_held

    def lower(self, ctx, inputs, params):
        x, ids = inputs
        t, k = ids.shape
        held, at = _held(ctx, ids, self.held_lo, self.num_held)
        key = jnp.where(held, at, self.num_held).reshape(-1)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        sizes = jnp.sum(
            key[:, None] == jnp.arange(self.num_held, dtype=key.dtype),
            axis=0, dtype=jnp.int32)
        xs = x[order // k]
        m = -(-t * k // GMM_ROWS) * GMM_ROWS
        if m > t * k:
            xs = jnp.concatenate(
                [xs, jnp.zeros((m - t * k, x.shape[1]), x.dtype)])
        sink = ctx.extras.get("counters")
        if sink is not None:
            sink[ctx.extras["node_name"]] = jnp.stack(
                [jnp.sum(sizes > 0, dtype=jnp.int32), jnp.sum(sizes),
                 jnp.max(sizes)])
        return [xs, sizes, order]


@register_op
class MoEExperts(_Replicated):
    """The held experts on their sorted rows, as GROUPED GEMMs over the same
    sorted rows, in the ``form`` the model states:

    * ``relu2``: ``down_e(relu(up_e x)^2)`` — ``up [E, d, f]``, ``down [E,
      f, d]``;
    * ``swiglu``: ``down_e(silu(gate_e x) * up_e x)`` — ``gate`` and ``up``
      ``[E, d, f]``, ``down [E, f, d]``.

    Pallas kernels where the kernels are on — sorted groups, dropless, the
    visited experts only: a grid step is one (group, row tile) pair, and a
    visited expert's matrix is streamed once per RUN of consecutive steps of
    one group and output tile (the pipeline skips a block whose index did
    not change), no unvisited expert's ever —; ``lax.ragged_dot`` otherwise
    (the CPU oracle).  Rows past the groups' sum are whatever the kernel
    left there: ``MoECombine`` reads none of them.

    TWO PLANS, by the rows a held expert can EXPECT in the call — its sorted
    pairs over the experts the graph's router scores (``num_scored``: the
    model builder's, out of the configuration; :meth:`group_ahead`):

    * below a row tile (``GMM_ROWS``: every decode scan; a chunk of a graph
      that holds a share of its experts) nearly every step changes group and
      the GEMMs are honestly weight-bound: megablox's ``gmm``, three calls
      (gate, up, down) with the product between them in XLA.
    * a row tile or more (``mellum``'s chunk: 8192 pairs on 64 of 64) a
      group is several steps long and the pipeline's fetch of the NEXT
      group's matrix — asked for one step ahead — has one 128-row step to
      hide behind: ``ops/pallas/grouped_ffn.py``, two calls.  Gate and up
      are ONE kernel with the product in its epilogue (the row tile read
      once, float32 products, one cast, bf16 out; no ``[M, f]`` float32 in
      HBM), the down projection another; both keep the weights in HBM and
      copy a visited group's blocks into one of two VMEM slots a GROUP
      ahead.  Its working set at output tile ``t``, ``w`` matrices a call:
      two slots a matrix ``2 w c t b``, the pipeline's row tiles ``2 x 128
      c b`` and output tiles ``2 x 128 t o``, the float32 products ``(w +
      1) 512 t`` — held to 48 MiB of the v5e's 128, the call's
      ``vmem_limit_bytes`` set from the sum (the scoped default is 16 MiB).
      ``swiglu`` at 2304 x 896: gate and up 16.5 + 1.2 + 0.5 + 1.4 =
      19.5 MB, 896 whole; down 8.3 + 0.5 + 2.4 + 2.4 = 13.4 MB, 2304 whole.
      At 4096 x 4096: 4 column tiles of 1024 (37.7 MB), down 2 of 2048
      (39.8 MB).  Widths that are not whole lanes (``nemotron_h``'s 1856)
      stay on megablox: the plan's copies move whole tiles.

    Megablox's tiles (:meth:`out_tile`, from the GEMM's shapes and
    ``VMEM_BUDGET``; rows ``GMM_ROWS``): the contraction WHOLE — no k loop,
    so a row tile's block index does not change while the grid walks the
    experts that share it and it is fetched once per output tile —, the
    output in the fewest tiles whose working set fits the budget, evened out
    to whole lanes.  The working set of a GEMM ``[rows, c] x [c, n]`` at
    output tile ``t``, the pipeline's two buffers each: rows ``128 c`` and
    weights ``c t`` in the parameters' type, the float32 output tile ``128
    t``, and the float32 accumulator once: ``2 (128 c b + c t b + 512 t) +
    512 t`` bytes, held to 11.5 MB of the v5e's 16 MiB of scoped VMEM.

    * ``relu2`` at 2688 x 1856 (nemotron_h): up — ``c`` 2688, at most 823
      wide, so 3 tiles of 1856: 640 (rows 0.7 MB, weights 3.4 MB); down —
      ``c`` 1856, at most 1177, so 3 tiles of 2688: 896 (3.3 MB of
      weights): 9 MB each.
    * ``swiglu`` at 4096 x 4096 (cohere2_moe): at most 524 wide, so 8 tiles
      of 512 either way: rows 1 MB, weights 4.2 MB, output and accumulator
      0.26 MB: (1 + 4.2 + 0.26) x 2 + 0.26 = 11.2 MB (a 1024-wide tile
      would be 17 MB of weights for two buffers — over the limit).
    * ``swiglu`` at 2048 x 1408 (deepseek_v2; 1408 = 11 x 128): gate and up
      — ``c`` 2048, at most 1074 wide, so 2 tiles of 1408: 768 (the second
      ragged, 640): rows 0.5 MB, weights 3.1 MB, output 0.4 MB: 8.5 MB;
      down — ``c`` 1408, at most 1503, so 2 tiles of 2048: 1024: rows
      0.4 MB, weights 2.9 MB, output 0.5 MB: 8.0 MB.  (The widest
      lane-multiple DIVISOR of 1408 under the budget is 128: eleven tiles,
      each re-reading the row tile.)
    * ``swiglu`` at 2304 x 1024 (kimi_linear): gate and up — ``c`` 2304, at
      most 959 wide, so 2 tiles of 1024: 512: rows 0.6 MB, weights 2.4 MB,
      output 0.26 MB: (0.6 + 2.4 + 0.26) x 2 + 0.26 = 6.7 MB; down — ``c``
      1024, at most 1948, so 2 tiles of 2304: 1152: rows 0.26 MB, weights
      2.4 MB, output 0.6 MB: 7.0 MB.

    The TPU compiler takes all four, and the group-ahead plan at both of
    its shapes (tests/test_tpu_aot_compile.py)."""

    type_name = "moe_experts"
    FORMS = ("relu2", "swiglu")
    # of the 16 MiB of scoped VMEM: what a grouped GEMM's working set may take
    VMEM_BUDGET = 11.5e6

    def __init__(self, num_held: int, embed_dim: int, width: int,
                 dtype=jnp.float32, form: str = "relu2", num_scored=None):
        if form not in self.FORMS:
            raise ValueError(f"an expert's form is one of "
                             f"{sorted(self.FORMS)}, not {form!r}")
        self.num_held = int(num_held)
        # the experts the graph's router scores (the model builder's, out of
        # the configuration): a call's pairs spread over ALL of them
        self.num_scored = int(num_scored or num_held)
        self.embed_dim = int(embed_dim)
        self.width = int(width)
        self.form = form
        self.dtype = jnp.dtype(dtype).name

    def infer_shapes(self, in_specs):
        return [TensorSpec(in_specs[0].shape, jnp.dtype("float32"))]

    def params(self) -> List[ParamSpec]:
        dt = jnp.dtype(self.dtype)
        e, d, f = self.num_held, self.embed_dim, self.width
        into = ("gate", "up") if self.form == "swiglu" else ("up",)
        return [ParamSpec(n, TensorSpec((e, d, f), dt)) for n in into] + [
            ParamSpec("down", TensorSpec((e, f, d), dt))]

    def flops(self, in_specs):
        gemms = 3 if self.form == "swiglu" else 2
        return 2 * gemms * in_specs[0].shape[0] * self.embed_dim * self.width

    @classmethod
    def out_tile(cls, contraction: int, n: int, itemsize: int) -> int:
        """The output tile of a grouped GEMM ``[GMM_ROWS, contraction] x
        [contraction, n]``: ``n`` whole where its working set fits
        ``VMEM_BUDGET`` (the class's docstring has the sum); else the
        fewest tiles that do, evened out — ``n`` over that many, rounded up
        to whole lanes (the kernel's last tile is ragged where that does not
        divide ``n``)."""
        fixed = 2 * GMM_ROWS * contraction * itemsize
        per_col = 2 * (contraction * itemsize + GMM_ROWS * 4) + GMM_ROWS * 4
        most = int((cls.VMEM_BUDGET - fixed) // per_col)
        if n <= most:
            return n
        most = max(most - most % 128, 128)
        tiles = -(-n // most)
        return -(-n // (tiles * 128)) * 128

    def group_ahead(self, pairs: int) -> bool:
        """Whether a call of ``pairs`` sorted rows takes the group-ahead
        plan: a held expert can EXPECT a row tile or more — the pairs over
        the experts the router scores — and both widths are whole lanes (the
        plan's own copies move whole tiles of a matrix)."""
        return pairs // self.num_scored >= GMM_ROWS \
            and self.embed_dim % 128 == 0 and self.width % 128 == 0

    def lower(self, ctx, inputs, params):
        xs, sizes = inputs
        interp = bool(ctx.extras.get("pallas_interpret"))
        if ctx.extras.get("pallas_decode") and self.group_ahead(xs.shape[0]):
            from ..ops.pallas.grouped_ffn import grouped_ffn

            into = ("gate", "up") if self.form == "swiglu" else ("up",)
            h = grouped_ffn(xs, tuple(params[n] for n in into), sizes,
                            form=self.form, out_dtype=xs.dtype,
                            interpret=interp)
            y = grouped_ffn(h, (params["down"],), sizes, form="linear",
                            out_dtype=jnp.float32, interpret=interp)
            path = "grouped_ffn"
        else:
            if ctx.extras.get("pallas_decode"):
                from jax.experimental.pallas.ops.tpu.megablox import gmm

                grouped = lambda a, w, out_tile: gmm(
                    a, w, sizes, jnp.float32,
                    (GMM_ROWS, w.shape[1], out_tile), interpret=interp)
                path = "megablox_gmm"
            else:
                grouped = lambda a, w, out_tile: jax.lax.ragged_dot(
                    a, w, sizes, preferred_element_type=jnp.float32)
                path = "ragged_dot"
            itemsize = jnp.dtype(params["up"].dtype).itemsize
            hidden_tile = self.out_tile(self.embed_dim, self.width, itemsize)
            model_tile = self.out_tile(self.width, self.embed_dim, itemsize)
            h = grouped(xs, params["up"], hidden_tile)
            if self.form == "swiglu":
                h = jax.nn.silu(grouped(xs, params["gate"], hidden_tile)) * h
            else:
                h = jnp.square(jnp.maximum(h, 0.0))
            y = grouped(h.astype(xs.dtype), params["down"], model_tile)
        bc = ctx.extras.get("batch_config")
        batch = ("one_row_per_request"
                 if ctx.extras.get("one_row_per_request")
                 else type(bc).__name__)
        _note_path(ctx, self.type_name, batch, path)
        return [y]


@register_op
class SharedExpertLinear(Linear):
    """A projection of the experts EVERY row visits (``cohere2_moe``'s four
    shared experts side by side: gate and up ``[d, n f]``, down ``[n f,
    d]``).  ``Linear`` in everything (weight-only int8 knows it, the search
    prices it) but its class's name, which is what a device trace files its
    operations under (``SharedExpertLinear.<node>``): the shared experts'
    time can then be told from the head's and the attention's GEMMs."""

    type_name = "shared_expert_linear"


@register_op
class MoECombine(_Replicated):
    """The experts' rows back in row order, weighted: ``out_t = sum over
    t's choices on held experts of w y``.  Inputs: the experts' output
    ``[M, d]``, the sorted pairs' flat indices, the router's ids and
    weights."""

    type_name = "moe_combine"

    def __init__(self, num_held: int, held_lo: int = 0, dtype=jnp.float32):
        self.num_held = int(num_held)
        self.held_lo = int(held_lo)
        self.dtype = jnp.dtype(dtype).name

    def infer_shapes(self, in_specs):
        ys, _, ids, _ = in_specs
        return [TensorSpec((ids.shape[0], ys.shape[1]),
                           jnp.dtype(self.dtype))]

    def flops(self, in_specs):
        return 2 * in_specs[0].size

    def lower(self, ctx, inputs, params):
        ys, order, ids, w = inputs
        t, k = ids.shape
        held, _ = _held(ctx, ids, self.held_lo, self.num_held)
        # where each pair's row lies in the sorted order
        where = jnp.zeros((t * k,), jnp.int32).at[order].set(
            jnp.arange(t * k, dtype=jnp.int32), unique_indices=True)
        y = ys[where].reshape(t, k, -1)
        y = jnp.where(held[:, :, None], y * w[:, :, None], 0.0)
        return [jnp.sum(y, axis=1).astype(self.dtype)]
