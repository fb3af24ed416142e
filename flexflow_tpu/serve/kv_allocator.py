"""KVAllocator: the single owner of the serving KV-cache buffers.

Through r9 the cache buffers were InferenceManager attributes and every
consumer re-derived its own view of them: admission control walked the raw
buffer shapes (``resilience.kv_bytes_per_token``), preemption released
slots it never priced, and ``plan_memory_bytes`` predicted a capacity
nothing ever reconciled against what HBM actually held.  vLLM (Kwon et
al., SOSP'23) showed that KV accounting at sub-request granularity is what
turns memory from a cliff into a managed resource — this module is that
accounting layer for the slot-contiguous cache (and the interface the
ROADMAP's paged/prefix-shared KV item will re-implement with a block
table behind the same API):

* the vocabulary of what a slot holds (``STATE_KINDS``): K and V planes per
  head (``kv_full``), a latent cache's two planes shared by all heads
  (``kv_latent``) — both by position —, and the kinds a slot holds whole;
* :class:`StageKV` — buffers of ONE compiled plan (the single-plan
  :class:`~flexflow_tpu.serve.inference_manager.InferenceManager`, or one
  pipeline stage of the
  :class:`~flexflow_tpu.serve.pp.PipelinedInferenceManager`): allocation
  via :func:`allocate_attention_state` (the one cache-layout function),
  plus the byte arithmetic read off the REAL allocated arrays.
* :class:`KVAllocator` — the deployment-level front: composes the
  per-stage instances, owns the per-request slot→bytes attribution
  (``bind`` at slot assignment, ``observe`` with live token counts per
  serve tick, ``release`` on EVERY terminal outcome and preemption), and
  emits the live-side memory telemetry (``kv_occupancy_frac``,
  ``kv_headroom_bytes``, high-watermark, slot fragmentation) through the
  shared :class:`~flexflow_tpu.obs.telemetry.Telemetry` handle.

Everything here is host-side bookkeeping over buffer metadata — the
buffers themselves are the same arrays the jitted step donates, so owning
them here cannot change compiled executables or their outputs
(bit-identity with the memory layer on or off is pinned by
tests/test_kv_allocator.py).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

# the committed-KV buffer names (k/v planes and, under int8 KV, their f32
# scale planes) — the byte-accounting vocabulary of PLAIN K/V that the paged
# allocator, the serve search's KV-stream pricing and the pipelined hand-over
# share: what pages, scale planes, spill and ``swap_signature`` are written for
KV_BUFFER_NAMES = frozenset({"k", "v", "k_scale", "v_scale"})
# a LATENT cache (LatentAttention, serve/hybrid_ops.py: ``kv_latent``): one
# normed latent (``ckv``) and one rotated key part (``kpe``) a position and
# layer, shared by every head — it grows with the context like K/V planes
# and is NOT K and V planes: nothing per head, no value plane, two widths.
# Slot-contiguous only (paging, int8 and the stage hand-over are refused at
# compile for it: inference_manager.refuse_unsupported_slot_state).
KV_LATENT_NAMES = frozenset({"ckv", "kpe"})
# every plane that holds ONE entry per cache position: what a position is
# priced by (``bytes_per_token``), what ``allocated_bytes(kv_only=True)``
# counts, and what the allocator pads to whole lanes of positions
POSITION_NAMES = KV_BUFFER_NAMES | KV_LATENT_NAMES
# per-slot state that does NOT grow with the context: the ring a window
# attention layer keeps of its last positions, and a state-space layer's
# conv tail and scan state (serve/hybrid_ops.py).  A slot holds them whole
# from admission on, so they are priced per slot, never per token.
# ``kv_compact`` is a cache that compacts itself (EvaAttention: a window of
# raw entries behind the summaries of all closed ones): it does grow with
# the context, but by a sixteenth of an entry a position and with a sawtooth
# on top, so a position has no price — ``bytes_per_token`` leaves it out
# (it is None for a graph that holds nothing else, and admission then gates
# in positions against ``capacity_tokens``: a slot is a slot).
# ``kv_index`` is the index of compressed keys a sparse-attention layer keeps
# BESIDE its full-length cache (SparseBlockAttention: one entry every
# ``kernel_stride`` positions): it grows with the context in step with the
# cache, so a position's price includes its share (``bytes_per_token``).
# ``linear_state`` is a linear-attention layer's matrix per head
# (LightningAttention): fixed, priced per slot like ``recurrent``.
# ``ssd_state`` is a Mamba-2 layer's matrix per head (Mamba2Scan,
# serve/ssd_moe_ops.py): ``[heads, head_dim, state]`` float32, fixed, priced
# per slot; its conv tail is ``recurrent`` like Mamba-1's.  Such a graph
# keeps the PLAIN attention op's ``kv_full`` planes in some layers beside
# slot state in others: both are allocated, joined and freed by slot.
# ``delta_state`` is a delta-rule layer's matrix per head (KimiDeltaAttention:
# ``[heads, head_dim, head_dim]`` float32, key channel x value channel):
# fixed, priced per slot like the other two matrix states; its three short
# convs' one tail is ``recurrent``.  ``kimi_linear`` keeps it in three layers
# of four beside a LATENT cache (``kv_latent``, by position) in the fourth:
# ``bytes_per_token`` prices the latent layers alone, ``fixed_bytes_per_slot``
# the delta states and tails, and admission (``request_bytes``) both.
# A model may keep BOTH: a plain ring (``kv_window``: SlidingWindowAttention)
# in most layers and a full-length cache (``kv_full``) in the rest.  A
# request's bytes are then a FIXED part (what its slot holds whatever its
# context: ``FIXED_KINDS``) plus a PER-POSITION part (``bytes_per_token``),
# and admission prices both (``request_bytes``).
KV_INDEX_NAMES = frozenset({"kidx"})
FIXED_KINDS = ("kv_window", "recurrent", "linear_state", "ssd_state",
               "delta_state")
STATE_KINDS = {
    "kv_full": KV_BUFFER_NAMES,
    "kv_latent": KV_LATENT_NAMES,
    "kv_window": frozenset({"wk", "wv"}),
    "recurrent": frozenset({"conv", "ssm"}),
    "kv_compact": frozenset({"ck", "cv"}),
    "kv_index": KV_INDEX_NAMES,
    "linear_state": frozenset({"lin"}),
    "ssd_state": frozenset({"ssd"}),
    "delta_state": frozenset({"kda"}),
}


def per_device_nbytes(arr) -> float:
    """Bytes ONE device holds of a (possibly sharded) array — the worst
    device's share, so replicated arrays count full size and sharded ones
    their largest shard sum.  The per-device basis is what reconciles the
    real allocation against ``plan_memory_bytes``'s per-device contract."""
    try:
        shards = arr.addressable_shards
    except AttributeError:
        return float(getattr(arr, "nbytes", 0))
    if not shards:
        return float(arr.nbytes)
    by_dev: Dict[Any, float] = {}
    for s in shards:
        by_dev[s.device] = by_dev.get(s.device, 0.0) + s.data.nbytes
    return max(by_dev.values())


def params_nbytes(params) -> float:
    """Per-device bytes of a serve param tree (the allocated-weights side
    of the memory ledger; int8 values + f32 scales count as stored)."""
    total = 0.0
    for group in (params or {}).values():
        for arr in group.values():
            total += per_device_nbytes(arr)
    return total


def allocate_attention_state(nodes, strategy, mesh, max_requests,
                             max_seq_len, max_spec_tokens=0,
                             always_place=False):
    """Allocate the KV/spec cache buffers for the attention ops in
    ``nodes`` — the single source of the cache layout shared by the
    single-plan manager and the per-stage allocator of pipeline-parallel
    serving (so the seq-pad rule and buffer name set cannot diverge from
    the bit-identity contract the pp tests pin).

    The seq dim of k/v (+ int8 scale) and of a latent cache's planes is
    rounded up to a lane-width (128) multiple so the Pallas kernels always
    get a dividing power-of-two block; extra slots sit beyond every mask,
    and the int8 scale buffers share the caches' seq dim so they pad
    identically.

    ``always_place``: commit buffers to ``mesh`` even when it is a single
    device — per-stage KV residency is the capacity contract of PP serving
    (the default only places on multi-device meshes, matching the
    single-plan manager's historical behavior).
    """
    state: Dict[str, Any] = {}
    for node in nodes:
        op = node.op
        # any op that keeps per-slot state says so through ``state_specs``
        # (attention caches, window rings, recurrent state); one that reads
        # another node's state (``state_owner``) allocates none
        if (not getattr(op, "stateful", False)
                or not hasattr(op, "state_specs")
                or getattr(op, "state_owner", None)):
            continue
        head_axes = tuple(strategy.get(node.name, {}).get("head", ()))
        specs = op.state_specs(max_requests, max_seq_len, max_spec_tokens,
                               head_axes)
        bufs = {}
        for name, (shape, dt, sh) in specs.items():
            if name in POSITION_NAMES:
                s_pad = -(-shape[2] // 128) * 128
                shape = shape[:2] + (s_pad,) + shape[3:]
            arr = jnp.zeros(shape, jnp.dtype(dt))
            if always_place or (mesh is not None and mesh.size > 1):
                arr = jax.device_put(arr, sh.named_sharding(mesh))
            bufs[name] = arr
        state[node.name] = bufs
    return state


class StageKV:
    """Buffers of one compiled plan (a whole single-plan deployment, or
    one pipeline stage).  Holds the live state dict the jitted step
    donates and re-binds, plus the byte arithmetic over it."""

    def __init__(self, nodes, strategy, mesh, max_requests: int,
                 max_seq_len: int, max_spec_tokens: int = 0,
                 always_place: bool = False, label: str = "plan"):
        self.nodes = list(nodes)
        self.strategy = strategy or {}
        self.mesh = mesh
        self.max_requests = max_requests
        self.max_seq_len = max_seq_len
        self.max_spec_tokens = max_spec_tokens
        self.always_place = always_place
        self.label = label
        self.state: Optional[Dict[str, Dict]] = None

    def allocate(self) -> Dict[str, Dict]:
        """(Re)allocate zeroed cache buffers; returns the state dict."""
        self.state = allocate_attention_state(
            self.nodes, self.strategy, self.mesh, self.max_requests,
            self.max_seq_len, self.max_spec_tokens,
            always_place=self.always_place,
        )
        return self.state

    # ---- byte accounting over the ALLOCATED arrays --------------------
    def allocated_bytes(self, kv_only: bool = True,
                        per_device: bool = False) -> float:
        """Bytes of the allocated serve-state buffers (``kv_only``
        restricts to the per-position planes — committed k/v (+scale), or a
        latent cache's two; False adds the spec-tree buffers too).
        ``per_device`` counts one device's share (the ledger's
        reconciliation basis against per-device ``plan_memory_bytes``); the
        default is global bytes, matching the admission gate's historical
        accounting.  0.0 before :meth:`allocate`."""
        if not self.state:
            return 0.0
        total = 0.0
        for bufs in self.state.values():
            for name, arr in bufs.items():
                if kv_only and name not in POSITION_NAMES:
                    continue
                total += per_device_nbytes(arr) if per_device else arr.nbytes
        return total

    def bytes_per_token(self) -> Optional[float]:
        """Committed-KV bytes one request's cache position costs across
        this plan's attention ops — THE shape walk admission control,
        preemption pricing, and the memory ledger all share.  It is the
        PER-POSITION part of a request's bytes only: the full-length planes
        — K and V per head, or a latent cache's latent and rotated key part
        (``POSITION_NAMES``) — and an index beside them.  What a slot holds
        whatever its context — a window layer's ring, recurrent or matrix
        state — is the fixed part (:meth:`fixed_bytes_per_slot`);
        :meth:`request_bytes` is both.

        Buffers are ``[max_requests+1, heads, seq, dim]``, so the
        per-request-token price divides by the REAL request rows as well
        as the seq axis; the pad-scratch row's bytes amortize over the
        real rows, so ``per_tok * max_requests * max_seq_len``
        approximates the full cache allocation (scratch row priced in,
        lane padding beyond ``max_seq_len`` not).  None before
        :meth:`allocate`."""
        if not self.state:
            return None
        total = 0.0
        for bufs in self.state.values():
            for name, arr in bufs.items():
                rows = max(arr.shape[0] - 1, 1)  # minus the scratch row
                if name in POSITION_NAMES:
                    total += arr.nbytes / (rows * arr.shape[2])
                elif name in KV_INDEX_NAMES:
                    # an index entry per ``stride`` positions of the cache
                    # it lies beside: its bytes over that cache's positions
                    total += arr.nbytes / (rows * bufs["k"].shape[2])
        return total or None

    def bytes_per_slot(self) -> Dict[str, float]:
        """Bytes one request slot holds of each kind of state
        (``STATE_KINDS``), read off the allocated arrays: ``kv_full`` is the
        slot's whole reserved span (``bytes_per_token`` x the padded seq
        length), ``kv_window`` and ``recurrent`` do not depend on
        ``max_seq_len`` at all; ``kv_latent`` is a latent cache's reserved
        span, by position like ``kv_full``.  Zeros before :meth:`allocate`."""
        out = {kind: 0.0 for kind in STATE_KINDS}
        for bufs in (self.state or {}).values():
            for name, arr in bufs.items():
                for kind, names in STATE_KINDS.items():
                    if name in names:
                        out[kind] += arr.nbytes / max(arr.shape[0] - 1, 1)
        return out

    def fixed_bytes_per_slot(self) -> float:
        """The part of a request's bytes that does not grow with its
        context (``FIXED_KINDS`` of :meth:`bytes_per_slot`)."""
        per_slot = self.bytes_per_slot()
        return sum(per_slot[kind] for kind in FIXED_KINDS)


class KVAllocator:
    """Deployment-level KV ownership: per-stage buffers + per-request
    attribution + live-side memory telemetry.

    ``stages``: one :class:`StageKV` per compiled plan — a single-plan
    manager passes one; ``PipelinedInferenceManager`` one per pipeline
    stage (per-stage KV residency is its capacity contract).

    Attribution protocol (driven by the RequestManager):

    * :meth:`bind` when a request takes a slot;
    * :meth:`observe` once per serve tick with every live slotted
      request's cache depth — updates per-request peaks, the live
      high-watermark, and (telemetry enabled) the occupancy/headroom/
      fragmentation gauges;
    * :meth:`release` on EVERY path a request leaves its slot —
      completion, cancel, timeout, failure, preemption — returning the
      bytes attributed to the binding (peak positions held × bytes per
      token), so no terminal outcome can leak attribution
      (tests/test_kv_allocator.py pins all of r9's outcomes).
    """

    # the slot-contiguous allocator: one reserved max_seq_len span per slot.
    # The paged subclass (serve/kv_paged.py) flips this and overrides the
    # page-granular hooks below behind the SAME bind/observe/release/
    # bytes_per_token/capacity_bytes interface.
    paged = False
    # host-DRAM spill tier (serve/kv_paged.py HostPageTier); the
    # slot-contiguous allocator never tiers — None keeps every caller's
    # ``kv.host_tier is not None`` gate uniform across allocator kinds.
    host_tier = None

    def __init__(self, stages: Sequence[StageKV], max_requests: int,
                 max_seq_len: int):
        self.stages = list(stages)
        self.max_requests = max_requests
        self.max_seq_len = max_seq_len
        self._live: Dict[int, int] = {}   # rid -> last observed cache depth
        self._peak: Dict[int, int] = {}   # rid -> peak depth this binding
        self.hwm_tokens = 0
        self.hwm_bytes = 0.0

    # ---- buffer ownership ---------------------------------------------
    def allocate(self):
        """(Re)allocate every stage's buffers (zeroed).  Returns the
        single-plan state dict, or the per-stage list for pp."""
        states = [s.allocate() for s in self.stages]
        return states[0] if len(states) == 1 else states

    @property
    def state(self):
        """Single-plan convenience view (the one stage's state dict); pp
        callers address ``stages[i].state`` directly."""
        return self.stages[0].state

    @state.setter
    def state(self, value):
        self.stages[0].state = value

    def reset_attribution(self) -> None:
        """Forget all request attribution + watermarks (new serving
        session over the same buffers; rids restart from 0)."""
        self._live.clear()
        self._peak.clear()
        self.hwm_tokens = 0
        self.hwm_bytes = 0.0

    # ---- the ONE headroom arithmetic ----------------------------------
    def bytes_per_token(self) -> Optional[float]:
        """Committed-KV bytes one request-token costs across ALL stages —
        None until every stage's caches are allocated, and None again if a
        caller drops them (``im.state = None`` frees HBM between bench
        runs); always read off the LIVE buffers, never cached, so the
        price can't outlive the allocation it describes."""
        parts = [s.bytes_per_token() for s in self.stages]
        if any(p is None for p in parts):
            return None
        return sum(parts) or None

    def fixed_bytes_per_slot(self) -> float:
        """Bytes a request holds whatever its context, across all stages
        (:meth:`StageKV.fixed_bytes_per_slot`): 0 for a graph of
        full-length caches alone."""
        return sum(s.fixed_bytes_per_slot() for s in self.stages)

    def request_bytes(self, positions: int) -> Optional[float]:
        """Bytes a request of ``positions`` cache positions holds: the
        fixed part plus ``positions`` x :meth:`bytes_per_token` — the price
        admission commits for it.  None where no position has a price
        (unallocated caches, or a graph whose only cache compacts itself):
        the gate then counts positions."""
        per_tok = self.bytes_per_token()
        if per_tok is None:
            return None
        return self.fixed_bytes_per_slot() + positions * per_tok

    @property
    def capacity_tokens(self) -> int:
        """Position capacity of the slot-contiguous cache."""
        return self.max_requests * self.max_seq_len

    def capacity_bytes(self) -> float:
        """Byte capacity priced at :meth:`bytes_per_token` (falls back to
        token-slot units — 1.0/token — before caches are allocated, the
        same degradation the admission gate historically had)."""
        return self.capacity_tokens * (self.bytes_per_token() or 1.0)

    def allocated_bytes(self, kv_only: bool = True,
                        per_device: bool = False) -> float:
        """Bytes actually held by the allocated cache buffers (lane
        padding and scratch rows included) across all stages; see
        :meth:`StageKV.allocated_bytes` for the ``per_device`` basis."""
        return sum(s.allocated_bytes(kv_only=kv_only, per_device=per_device)
                   for s in self.stages)

    def bytes_per_slot(self) -> Dict[str, float]:
        """Per-slot bytes by kind of state across all stages (see
        :meth:`StageKV.bytes_per_slot`)."""
        out = {kind: 0.0 for kind in STATE_KINDS}
        for s in self.stages:
            for kind, b in s.bytes_per_slot().items():
                out[kind] += b
        return out

    # ---- per-request attribution --------------------------------------
    def bind(self, rid: int, **_) -> Optional[Dict]:
        """A request took a slot (admission or preemption-readmission).

        The slot-contiguous allocator only starts attribution; the extra
        keyword context the RequestManager supplies (``slot``, ``tokens``,
        ``need``, ``align``) is consumed by the paged subclass, which maps
        pages and returns a ``{"cached_tokens", "hit_pages"}`` prefix-reuse
        dict (None here: nothing is ever pre-cached in a dedicated span).
        """
        self._live.setdefault(int(rid), 0)
        self._peak.setdefault(int(rid), 0)
        return None

    def prepare_write(self, rid: int, lo: int, hi: int) -> None:
        """Authorize cache writes at positions ``[lo, hi)`` for ``rid``
        BEFORE the dispatch that performs them.  A no-op here — every
        slot's span is pre-reserved — but the paged subclass allocates
        missing pages and copy-on-writes shared ones, so serve loops call
        this unconditionally through RequestManager._kv_prepare."""
        return None

    def round_need(self, tokens: int) -> int:
        """Admission-gate granularity of a worst-case cache need: the
        slot-contiguous gate prices exact positions; the paged subclass
        rounds up to whole pages (a request can only hold page multiples)."""
        return int(tokens)

    def page_view(self):
        """The device-side block-table pytree for the jitted step (None =
        slot-contiguous addressing; see kv_paged.PageTable)."""
        return None

    # ---- host-tier hooks (no-ops: only the paged subclass tiers) ------
    def attach_host_tier(self, capacity_bytes: int):
        """Attach a bounded host-DRAM spill tier.  The slot-contiguous
        allocator has no page granularity to spill at — recovery stays
        recompute-based — so this is a no-op returning None; callers
        (RequestManager, migration, fleet) gate every swap path on
        ``host_tier is not None`` and need no isinstance checks."""
        return None

    def spill(self, rid: int, tokens) -> Optional[Dict]:
        return None

    def restore(self, rid: int, align: int = 1) -> Optional[Dict]:
        return None

    def has_spill(self, rid: int) -> bool:
        return False

    def drop_spill(self, rid: int) -> None:
        return None

    def adopt_spills(self, other, rids) -> int:
        return 0

    def observe(self, usage: Dict[int, int], telemetry=None) -> Dict:
        """One serve tick's live cache depths (``rid -> tokens`` for every
        slotted PREFILLING/DECODING request).  Updates peaks + watermarks
        and, when a live telemetry handle is given, publishes the gauge
        set; returns the computed snapshot either way."""
        self._live = {int(r): int(t) for r, t in usage.items()}
        for rid, t in self._live.items():
            if t > self._peak.get(rid, 0):
                self._peak[rid] = t
        per_tok = self.bytes_per_token()  # ONE buffer walk per tick
        live = sum(self._live.values())
        live_bytes = live * per_tok if per_tok else 0.0
        if live > self.hwm_tokens:
            self.hwm_tokens = live
        if live_bytes > self.hwm_bytes:
            self.hwm_bytes = live_bytes
        snap = self.snapshot(_per_tok=per_tok, _live=live)
        if telemetry is not None and getattr(telemetry, "enabled", False):
            telemetry.kv_usage(snap)
        return snap

    def snapshot(self, _per_tok: Optional[float] = None,
                 _live: Optional[int] = None) -> Dict:
        """The current occupancy/headroom/fragmentation view over the
        last-observed depths — pure read (no peak/watermark updates, no
        telemetry); :meth:`observe` is the mutating per-tick entry and
        passes its already-computed walk/sum in so the hot path prices
        the buffers exactly once per tick."""
        per_tok = self.bytes_per_token() if _per_tok is None else _per_tok
        live = sum(self._live.values()) if _live is None else _live
        live_bytes = live * per_tok if per_tok else 0.0
        cap_b = self.capacity_tokens * (per_tok or 1.0)
        bound = len(self._live)
        return {
            "live_tokens": live,
            "live_bytes": live_bytes,
            "capacity_tokens": self.capacity_tokens,
            "capacity_bytes": cap_b,
            "headroom_bytes": cap_b - (live_bytes if per_tok else live),
            "occupancy_frac": (live / self.capacity_tokens
                               if self.capacity_tokens else 0.0),
            # slot fragmentation: each bound slot reserves max_seq_len
            # contiguous positions of which only the live prefix is
            # occupied — the allocated-but-idle share the paged-KV item
            # exists to reclaim
            "fragmentation_frac": (
                1.0 - live / (bound * self.max_seq_len)
                if bound and self.max_seq_len else 0.0),
            "bound_slots": bound,
            "hwm_tokens": self.hwm_tokens,
            "hwm_bytes": self.hwm_bytes,
        }

    def live_tokens(self) -> int:
        return sum(self._live.values())

    def live_requests(self) -> int:
        """Slotted requests currently holding cache (the OOM-risk
        projection multiplies each by the expected remaining output)."""
        return len(self._live)

    def release(self, rid: int, tokens: Optional[int] = None) -> float:
        """The request left its slot (ANY terminal outcome, or a
        preemption eviction).  ``tokens`` is its final cache depth when
        the caller knows it (a request can admit and finish within one
        tick, before any :meth:`observe`); attribution is the PEAK depth
        the binding reached × bytes per token.  Safe (0.0) for rids that
        never bound — a rejected request holds no cache."""
        rid = int(rid)
        peak = self._peak.pop(rid, 0)
        last = self._live.pop(rid, 0)
        if tokens is not None:
            peak = max(peak, int(tokens))
        peak = max(peak, last)
        return peak * (self.bytes_per_token() or 0.0)

    def attributed_rids(self) -> List[int]:
        """Rids currently holding attribution — empty once every request
        reached a terminal outcome (the no-leak contract)."""
        return sorted(set(self._live) | set(self._peak))

    def teardown(self) -> List[int]:
        """Release this deployment's cache ownership entirely: every
        remaining per-request attribution releases, the watermarks reset,
        and the buffers drop (``state = None`` per stage, freeing the
        HBM).  THE incumbent-retirement hook of live plan migration
        (serve/migration.py): after a full drain every request already
        released on its slot-leaving path, so the returned list of rids
        that STILL held attribution is the refcount no-leak check —
        non-empty means some path leaked (pinned by
        tests/test_migration.py / test_kv_paged.py)."""
        leaked = self.attributed_rids()
        for rid in leaked:
            self.release(rid)
        self.reset_attribution()
        for s in self.stages:
            s.state = None
        return leaked
