"""RequestManager: request queue, continuous batching, decode orchestration.

Reference: ``src/runtime/request_manager.cc`` — ``register_new_request``,
``prepare_next_batch`` (admit/retire requests, mix prompt-prefill chunks with
single decode tokens in one flat token batch), ``serve_incr_decoding``; the
speculative path (``prepare_next_batch_beam/_verify``, ``serve_spec_infer``)
lives in :mod:`flexflow_tpu.serve.spec_infer` and reuses this class.

Host-side Python is the right tool here (the reference uses host-side C++):
the per-step compute is one jitted TPU program; this class only does queue
bookkeeping and builds the next fixed-capacity BatchConfig.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..obs.telemetry import telemetry_or_null
from .batch_config import BatchConfig, PrefillBatchConfig
from .hybrid_ops import launch_counter
from .inference_manager import EXIT_NOT_IN_BATCH
from .resilience import ResilienceConfig, TransientServeError


class RequestStatus(enum.Enum):
    PENDING = 0
    PREFILLING = 1
    DECODING = 2
    COMPLETED = 3
    # resilient-serving lifecycle (serve/resilience.py): PREEMPTED requests
    # sit back in the pending queue and recompute prompt+generated on
    # readmission; the rest are terminal.
    PREEMPTED = 4
    CANCELLED = 5
    TIMED_OUT = 6
    REJECTED = 7
    FAILED = 8


TERMINAL_STATUSES = frozenset({
    RequestStatus.COMPLETED, RequestStatus.CANCELLED,
    RequestStatus.TIMED_OUT, RequestStatus.REJECTED, RequestStatus.FAILED,
})

# terminal status -> the ``outcome`` tag serving records carry
OUTCOMES = {
    RequestStatus.COMPLETED: "ok",
    RequestStatus.CANCELLED: "cancelled",
    RequestStatus.TIMED_OUT: "timeout",
    RequestStatus.REJECTED: "rejected",
    RequestStatus.FAILED: "failed",
}

# per-request options an arrival tuple's 4th element may carry — ONE
# vocabulary/coercion for every arrival-driven loop (RequestManager and
# the fleet router), so adding an option here reaches both and a
# malformed dict rejects identically instead of drifting
ARRIVAL_OPTION_KEYS = frozenset({"priority", "ttl_s", "deadline_s", "spec",
                                 "slo_class"})


def parse_arrival_options(rest) -> Tuple[Dict, Optional[str]]:
    """Parse an arrival tuple's optional trailing options dict into
    ``register_new_request`` kwargs.  Returns ``(opts, reject_reason)``
    — malformed dicts (unknown keys, uncoercible values) yield a reject
    reason so one bad arrival registers as ``REJECTED`` instead of
    killing the serve loop."""
    if not rest:
        return {}, None
    if not isinstance(rest[0], dict) or set(rest[0]) - ARRIVAL_OPTION_KEYS:
        return {}, f"bad arrival options {rest[0]!r}"
    try:
        return {k: (int(v) if k == "priority"
                    else bool(v) if k == "spec"
                    else str(v) if k == "slo_class"
                    else float(v))
                for k, v in rest[0].items() if v is not None}, None
    except (TypeError, ValueError):
        return {}, f"bad arrival options {rest[0]!r}"


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int = 64
    status: RequestStatus = RequestStatus.PENDING
    generated: List[int] = dataclasses.field(default_factory=list)
    prefill_offset: int = 0     # prefill tokens already fed to the model
    slot: int = -1
    trace_id: str = ""          # stable per-request telemetry/trace tag
    # consecutive mixed-batch steps in which the tiled budget rounded this
    # request's prefill take to zero (starvation fallback, ADVICE r5 low)
    starved_steps: int = 0
    # resilient serving (serve/resilience.py): scheduling priority (higher
    # wins admission; preemption only ever evicts strictly-lower priority),
    # an absolute deadline on the manager's clock, the host-side cancel
    # flag reaped at step boundaries, and the terminal outcome tag
    priority: int = 0
    deadline_s: Optional[float] = None
    cancel_requested: bool = False
    outcome: str = ""
    preemptions: int = 0
    requeues: int = 0
    # preemption-and-recompute: after eviction the request re-prefills
    # ``prompt + generated`` (KV is always recomputable from them);
    # ``prefill_src`` is that feed (None = the prompt itself) and
    # ``n_prefed`` how many generated tokens it contains — the correction
    # ``seq_len`` needs while the recompute prefill is in flight.
    prefill_src: Optional[List[int]] = None
    n_prefed: int = 0
    # host-tier KV (serve/kv_paged.py): True while this binding's cache
    # was (partly) restored from a host-tier spill instead of recomputed.
    # Once the catch-up prefill completes, the lifecycle scan retires the
    # recompute feed early (prefill_src is dead weight the moment the
    # cache is whole) — only terminal paths dropped it before.
    kv_restored: bool = False
    # memory observability (serve/kv_allocator.py): peak committed-KV bytes
    # this request held across its slot bindings — stamped by the
    # allocator's release() on every slot-leaving path, carried on finish
    # telemetry and serving records
    kv_bytes: float = 0.0
    # speculative serving (serve/spec_infer.py): per-request speculation
    # mode, set at admission (``register_new_request(spec=...)``) and
    # flippable at runtime (``set_spec_mode``).  Under a SpecInferManager,
    # spec rows carry a draft-token tree and verify multi-token per macro
    # step while plain rows decode one token in the SAME verify batch;
    # under a plain RequestManager the flag is inert (everything rides the
    # incremental loop).
    spec: bool = False
    # SLO-class lanes (serve/slo.py): the traffic class this request
    # resolved to at registration ("" = no policy attached — every lane
    # knob is inert).  ``deferred_ticks`` counts brownout windows the
    # request spent queue-held at DEFER_BATCH or above (explicit,
    # observable deferral — it still ends in a terminal outcome: ok,
    # timeout, or a brownout-shed REJECTED, never FAILED).
    slo_class: str = ""
    deferred_ticks: int = 0

    @property
    def prefill_tokens(self) -> List[int]:
        """The token sequence prefill feeds (prompt, or prompt+generated
        while recovering from preemption)."""
        return self.prompt if self.prefill_src is None else self.prefill_src

    @property
    def seq_len(self) -> int:
        """Tokens currently in the KV cache (after the last step)."""
        return self.prefill_offset + len(self.generated) - self.n_prefed


@dataclasses.dataclass
class GenerationConfig:
    max_new_tokens: int = 64
    eos_token_id: Optional[int] = None
    stop_on_eos: bool = True
    # sampling (reference: GenerationConfig in flexflow/inference.py + the
    # Sampling op).  temperature <= 0 -> exact greedy argmax.  Speculative
    # serving supports it too: the verify step samples per tree node and the
    # accept walk matches drafts against the sampled tokens (spec_infer
    # ._verify_phase / spec_scan._macro_body), preserving the target
    # sampling distribution for any draft model.
    temperature: float = 0.0
    top_p: float = 1.0
    seed: int = 0


class RequestManager:
    request_cls = Request  # subclasses (SpecInferManager) extend the record
    # speculation mode new requests default to (``register_new_request``'s
    # ``spec=None``): the plain manager serves everything incrementally;
    # SpecInferManager flips this to True so its historical all-spec
    # behavior is unchanged unless a caller opts rows out per request
    default_spec_mode = False

    def __init__(self, im, gen_config: Optional[GenerationConfig] = None,
                 telemetry=None, resilience: Optional[ResilienceConfig] = None,
                 fault_injector=None, clock=None, plan_health=None,
                 profiler=None, slo=None, brownout=None):
        import time as _time

        self.im = im
        self.gen = gen_config or GenerationConfig()
        self.requests: Dict[int, Request] = {}
        self.pending: List[int] = []
        # serve-step stamp of each rid's entry into ``pending`` — read
        # by _pop_pending's bounded aging (starvation_bound_ticks)
        self._pending_since: Dict[int, int] = {}
        self.slots: List[Optional[int]] = [None] * im.max_requests
        self._next_rid = 0
        self.steps = 0
        self.tokens_decoded = 0
        # dispatch-span arguments of the batch _build_next_batch made last
        self._step_counts: Optional[Dict[str, int]] = None
        # what a launch means to the graph's ops that keep state per slot:
        # ``_op_counts`` tells the dispatch spans of it beside ``ctx_sum``
        self._launch_counter = launch_counter(im.model.graph)
        self.scan_runs = 0      # decode stretches run as on-device scans
        # ONE Telemetry handle across the serving stack: syncing it onto the
        # InferenceManager (which forwards to pipeline stages) puts request
        # lifecycle, dispatch spans, and per-stage events on one clock/ring.
        # ALWAYS synced — exactly the handle passed here (or the no-op) —
        # so a shared/cached im can never leak a previous run's live handle
        # into a manager built without one.  Host-side only — a handle can
        # never change serve outputs (tests/test_obs.py bit-identity).
        self.telemetry = telemetry_or_null(telemetry)
        im.telemetry = self.telemetry
        self._tstamps: Dict[int, Dict[str, float]] = {}  # rid -> stamps
        # step-level cost attribution (obs/profiler.py): ONE StepProfiler
        # handle shared with the InferenceManager (and every pp stage /
        # the spec draft model) exactly like the telemetry handle — and,
        # like it, ALWAYS synced so a shared/cached im can never leak a
        # previous run's live profiler.  Host-side only: phase timing +
        # deterministic counters computed from host bookkeeping, never a
        # device read — serve outputs are bit-identical with the profiler
        # on or off (tests/test_profiler.py).
        from ..obs.profiler import profiler_or_null

        self.profiler = profiler_or_null(profiler)
        im.profiler = self.profiler
        if self.profiler.enabled:
            self.profiler.install(im)
            self.profiler.bind(self.telemetry)
        # the tick journal (obs/journal.py): one bounded record per tick
        # of the serving loops, ALWAYS on — fed by the spans below and,
        # synced onto the InferenceManager like the two handles above, by
        # its launch spans.  Host-side only: integers the scheduler holds
        from ..obs.journal import TickJournal

        self.journal = TickJournal(chunk_width=im.max_tokens)
        im.journal = self.journal
        # KV ownership (serve/kv_allocator.py): a fresh manager restarts
        # rids from 0, so any attribution a previous manager left on a
        # shared/cached im must not alias the new rid space; and the
        # deployment's predicted-vs-allocated HBM is recorded into the
        # handle's memory ledger once, here (host-side only — pinned
        # bit-identical with the layer on or off).
        im.kv.reset_attribution()
        if self.telemetry.enabled:
            im.publish_memory(self.telemetry)
        # resilient serving (serve/resilience.py): admission/deadline/
        # preemption/retry policy + the seeded chaos hook.  The injector is
        # synced onto the InferenceManager like the telemetry handle (same
        # cached-im leak rationale); it is consulted at dispatch sites
        # BEFORE any work reaches the device.
        self.res = resilience or ResilienceConfig()
        if self.res.kv_gate and self.res.kv_budget_bytes is not None:
            from .resilience import kv_bytes_per_token

            # an explicit BYTE cap needs the allocated caches to price
            # requests in bytes — gating token-slot units against a byte
            # budget would silently admit everything
            if kv_bytes_per_token(im) is None:
                raise ValueError(
                    "kv_budget_bytes needs allocated KV caches to price "
                    "requests in bytes; call init_operators_inference() "
                    "before building the RequestManager (or use "
                    "kv_headroom_frac, which gates in position units)")
        self.injector = fault_injector
        im.fault_injector = fault_injector
        # host-tier KV spill/restore (serve/kv_paged.py): a positive
        # ``host_tier_bytes`` attaches the bounded host-DRAM tier under
        # the PAGED allocator — preemption/eviction then spill pages
        # instead of dropping them, and readmission restores (checksum-
        # verified) instead of re-prefilling.  No-op for the
        # slot-contiguous allocator (attach_host_tier returns None there).
        if self.res.host_tier_bytes:
            im.kv.attach_host_tier(self.res.host_tier_bytes)
        # deadline/TTL clock — serve_with_arrivals swaps in its loop clock
        # for its duration so virtual-clock tests stay hermetic; _sleep is
        # the retry backoff's wait (injectable for the same reason)
        self.clock = clock or _time.perf_counter
        self._sleep = _time.sleep
        # plan-health monitoring (obs/plan_health.py): an attached
        # PlanHealthMonitor is polled every ``health_check_every`` serve
        # ticks (and once when a serve loop drains) — host-side arithmetic
        # over the telemetry registry only, so attaching one can never
        # change serve outputs (tests/test_plan_health.py bit-identity).
        # The monitor emits ``replan_recommended``; an attached
        # MigrationController (serve/migration.py) consumes it and
        # executes the live plan switch at a tick boundary — without one,
        # the recommendation is report-only.
        # The manager's KVAllocator is handed to the monitor so its
        # OOM-risk check prices projected KV growth against REAL headroom.
        self.plan_health = plan_health
        if (plan_health is not None
                and getattr(plan_health, "kv_allocator", None) is None):
            plan_health.kv_allocator = im.kv
        self._health_ticks = 0
        # live plan migration (serve/migration.py): an attached
        # MigrationController gets a tick-boundary slot via
        # _maybe_migrate; while it drains the incumbent, admission to
        # engine slots is closed (requests still enqueue — nothing new
        # takes a slot) so the drain converges
        self.migration = None
        self.admission_closed = False
        # SLO-class lanes + brownout (serve/slo.py): an attached
        # SLOPolicy classifies requests at registration (priority band,
        # per-class bounded queue, reserved-KV-headroom gate); an
        # attached BrownoutController is evaluated every
        # ``config.check_every`` serve ticks and its level's actions
        # (defer / degrade / shed of degradable classes) apply at tick
        # boundaries.  Both default off — behavior is unchanged without
        # them.  Under a FleetRouter the FLEET owns policy + controller
        # (one ladder over the whole fleet); replicas get references for
        # their queue gates but only the fleet EVALUATES the ladder
        # (this manager's _maybe_brownout runs from its own serve loops,
        # which the fleet never drives).
        self.slo = slo
        self.brownout = brownout
        if brownout is not None and slo is None:
            self.slo = brownout.policy
        self._brownout_ticks = 0
        # an attached monitor inherits the manager's lane policy (the
        # per-class SLO checks) and ladder (batch breaches escalate
        # brownout before recommending replan) unless wired explicitly —
        # the same auto-wiring pattern as kv_allocator above
        if plan_health is not None:
            if getattr(plan_health, "slo", None) is None:
                plan_health.slo = self.slo
            if getattr(plan_health, "brownout", None) is None:
                plan_health.brownout = self.brownout

    def _span(self, name: str, phase: bool = False, **args):
        """A scheduler span below the tick (obs/trace.py ``Span``): always
        a profiler annotation and self time in the tick journal's record,
        a ring event on the ``host`` track when telemetry is on, and —
        ``phase`` — the StepProfiler phase of the same name.  ``args`` are
        ints the scheduler already holds."""
        return self.telemetry.span(
            name, cat="host", track="host",
            prof=self.profiler if phase else None, jr=self.journal, **args)

    def _device_wait(self, results) -> None:
        """First thing inside a ``readback`` span.  ``results``: every
        array the span will copy, in launch order (any pytree).  Starts
        their copies to the host — an earlier result's copy then overlaps
        the device's work on the later ones, as the ``np.asarray`` calls
        did before they stood behind this wait (without it
        ``decode-heavy`` lost 0.6 %: PERF.md section 6, PR 59) — and
        blocks until the LAST is ready (the stream runs in order) under a
        span of its own.  ``device_wait`` is then the wait for the device
        (or for the runtime to hand its results over) and ``readback``'s
        self time the copies alone: a stall reads as one or the other on
        the journal's slow-tick line."""
        leaves = jax.tree.leaves(results)
        for x in leaves:
            if hasattr(x, "copy_to_host_async"):
                x.copy_to_host_async()
        with self._span("device_wait"):
            jax.block_until_ready(leaves[-1])

    def _tick_begin(self) -> None:
        """A serve loop is about to run a tick: the profiler's mark, and
        the backlog and the slots held as the journal's record has them."""
        self.profiler.tick_begin()
        self.journal.begin(len(self.pending),
                           sum(rid is not None for rid in self.slots))

    def _launch_counts(self, spans, n_decode: int) -> Dict[str, int]:
        """Dispatch-span arguments of one flat step from its cache-write
        spans ``[(rid, lo, hi)]``, the ``n_decode`` decode rows first:
        ``ctx_sum`` is the decode rows' KV lengths, ``prompt_ctx_sum`` the
        prompt rows' (token at position p attends p + 1 entries)."""
        dec, pre = spans[:n_decode], spans[n_decode:]
        return {
            "rows": n_decode,
            "prompt_tokens": sum(hi - lo for _, lo, hi in pre),
            "ctx_sum": sum(hi for _, _, hi in dec),
            "prompt_ctx_sum": sum((hi - lo) * (hi + lo + 1) // 2
                                  for _, lo, hi in pre),
            **self._op_counts([(lo, hi) for _, lo, hi in dec],
                              [(lo, hi) for _, lo, hi in pre]),
        }

    def _op_counts(self, decode, prompt) -> Dict[str, int]:
        """Dispatch-span arguments of a launch for the per-slot state whose
        work ``ctx_sum`` does not tell, as the graph's ops give them
        (``hybrid_ops.launch_counter``: its ``decode`` and ``prompt``); the
        counters they name are counted here."""
        tel = self.telemetry
        args, counters = self._launch_counter(decode, prompt, tel.enabled)
        if tel.enabled:
            for name, n in counters.items():
                tel.metrics.counter(name).inc(n)
        return args

    def _expert_load(self) -> Dict[str, int]:
        """For a graph with routed-expert layers: what the launches read
        back since the last call counted on the device, summed over steps
        and layers — of the decode scans ``experts_visited`` (held experts
        that got a row), ``expert_pairs`` (pairs on held experts),
        ``expert_pairs_max`` (the fullest expert's pairs) and
        ``expert_steps`` (scan steps x routed layers); of the prefill scans
        and the flat steps that fed prompt rows the same four as
        ``prefill_experts_visited``, ``prefill_expert_pairs``,
        ``prefill_expert_pairs_max`` and ``prefill_expert_chunks`` — as
        arguments of the ``commit`` span (and so fields of the tick's
        journal record) and, per tick, the trace counters
        ``moe.experts_visited`` / ``moe.pairs`` / ``moe.pairs_max`` and
        ``moe.prefill_visited`` / ``moe.prefill_pairs``.  Called inside a
        ``readback`` span, after its wait: every launch it reads was
        dispatched before the result that wait was for.  Nothing for a graph
        with none, or a manager that counts none."""
        take = getattr(self.im, "take_expert_load", None)
        load = take() if take is not None else None
        if not load:
            return {}
        tel = self.telemetry
        if tel.enabled:
            for name, key in (("moe.experts_visited", "experts_visited"),
                              ("moe.pairs", "expert_pairs"),
                              ("moe.pairs_max", "expert_pairs_max"),
                              ("moe.prefill_visited",
                               "prefill_experts_visited"),
                              ("moe.prefill_pairs", "prefill_expert_pairs")):
                if key in load:
                    tel.metrics.counter(name).inc(load[key])
                    tel.trace.counter(name, load[key])
        return load

    @staticmethod
    def _fold_for(req: Request) -> Tuple[int, int]:
        """THE per-request sample-key fold: (rid, index of the token about
        to be sampled).  Every sampled dispatch path must build its folds
        through this one helper — the seeded bit-identity contract holds
        only while step, decode-scan, and prefill-stretch agree on it."""
        return (req.rid & 0x7FFFFFFF, len(req.generated))

    def _sample_for(self, points, n_rows: int):
        """Per-request sampling arg for an incremental step: ``(key,
        temperature, top_p, folds)`` with ``folds[row] = (rid, n)`` for each
        sample point — the key for request ``rid``'s ``n``-th generated
        token is ``fold_in(fold_in(PRNGKey(seed), rid), n)``.

        This schedule depends ONLY on (seed, rid, token index), so sampled
        outputs are invariant to batch composition, arrival timing,
        preemption-and-recompute, and dispatch retries — the resilient-
        serving bit-identity contract (tests/test_resilience.py).  Rows
        without a sample point draw from the (0, 0) fold; their samples are
        computed and discarded.  None for greedy.

        ``points`` entries are ``(row, rid)`` or ``(row, rid, offset)`` —
        the optional offset shifts the token index past ``len(generated)``
        (the speculative verify step samples index ``len(generated) +
        tree_depth`` per row; ONE assembly path for every sampled
        dispatch, so the fold scheme cannot silently diverge between the
        incremental and speculative paths).
        """
        if self.gen.temperature <= 0.0:
            return None
        import jax
        import jax.numpy as jnp

        with self._span("sample_for"):
            folds = np.zeros((n_rows, 2), np.int32)
            for row, rid, *off in points:
                rid_fold, idx = self._fold_for(self.requests[rid])
                folds[row] = (rid_fold, idx + (off[0] if off else 0))
            return (jax.random.PRNGKey(self.gen.seed),
                    jnp.float32(self.gen.temperature),
                    jnp.float32(self.gen.top_p), jnp.asarray(folds))

    # ------------------------------------------------------------------
    def _seq_len_needed(self, req: Request) -> int:
        """Cache depth a request may reach (overridden by speculation)."""
        return len(req.prompt) + req.max_new_tokens

    def _validate_request(self, req: Request) -> Optional[str]:
        """Host-side shape validation: the reason string, or None if OK.

        Catching these HERE (satellite of ISSUE 5) turns what used to be a
        device-side shape failure (cache writes past ``max_seq_len`` clamp
        and corrupt the last slot) into a clear host error at registration.
        """
        if not req.prompt:
            return "empty prompt"
        if req.max_new_tokens < 0:
            return f"max_new_tokens {req.max_new_tokens} < 0"
        if len(req.prompt) > self.im.max_seq_len:
            return (f"prompt length {len(req.prompt)} exceeds max_seq_len "
                    f"{self.im.max_seq_len}")
        need = self._seq_len_needed(req)
        if need > self.im.max_seq_len:
            return (f"request needs {need} cache slots (prompt "
                    f"{len(req.prompt)} + max_new_tokens "
                    f"{req.max_new_tokens}), exceeds max_seq_len "
                    f"{self.im.max_seq_len}")
        return None

    def _kv_bytes_per_token(self) -> Optional[float]:
        """Per-position committed-KV cost for the admission gate, or None
        while the caches are unallocated.  Read live from the allocator
        on every call — a cached price could disagree in UNITS with the
        capacity arithmetic (which also degrades to token-slot units)
        after a caller frees the buffers."""
        from .resilience import kv_bytes_per_token

        return kv_bytes_per_token(self.im)

    def _admission_reason(self, req: Request) -> Optional[str]:
        """Capacity gate: the rejection reason, or None to admit.

        Prices the new request's worst-case cache need against the bounded
        pending queue and the KV headroom every live (pending + slotted)
        request has already committed — ``plan_memory_bytes``-style
        arithmetic over the allocated cache buffers.
        """
        res = self.res
        if res.max_pending is not None and len(self.pending) >= res.max_pending:
            return (f"pending queue full ({len(self.pending)} >= "
                    f"{res.max_pending})")
        reason = self._lane_admission_reason(req)
        if reason is not None:
            return reason
        if res.kv_gate:
            per_tok = self._kv_bytes_per_token()
            if per_tok is None and res.kv_budget_bytes is not None:
                # an explicit BYTE cap cannot be priced without allocated
                # caches (the __init__ guard checked once, but a caller
                # can free HBM later via ``im.state = None``) — gating
                # token-slot units against a byte budget would silently
                # admit everything, so fail SAFE and reject instead
                return ("kv_budget_bytes is a byte cap but the KV caches "
                        "are unallocated (no byte price); re-allocate "
                        "caches or gate with kv_headroom_frac")
            # a request's bytes: what its slot holds whatever its context
            # (window rings, recurrent or matrix state) plus its positions
            # at the per-position price (KVAllocator.request_bytes); in
            # token-slot units (no byte price) a request is its positions
            fixed = (self.im.kv.fixed_bytes_per_slot()
                     if per_tok is not None else 0.0)
            per_tok = per_tok or 1.0  # token-slot units for the frac gate
            live = [self.requests[r] for r in self.pending] + [
                r for r in self._active()
                if r.status in (RequestStatus.PREFILLING,
                                RequestStatus.DECODING)]
            # page-granular under a paged allocator: a request can only
            # ever hold whole pages, so its worst-case need rounds up to
            # the page size (round_need is identity for slot-contiguous)
            rnd = self.im.kv.round_need
            price = lambda r: fixed + rnd(self._seq_len_needed(r)) * per_tok
            committed = sum(price(r) for r in live) + price(req)
            # the budget: an explicit byte cap when configured (this is
            # where the per-token BYTE pricing decides — int8 vs bf16 KV
            # admit differently under the same cap), else the headroom
            # fraction of the allocator's own byte capacity — ONE
            # arithmetic, owned by the KVAllocator, shared with
            # preemption pricing and the memory ledger
            cap_bytes = (res.kv_budget_bytes
                         if res.kv_budget_bytes is not None
                         else res.kv_headroom_frac
                         * (self.im.kv.capacity_tokens * per_tok
                            + self.im.kv.max_requests * fixed))
            if committed > cap_bytes:
                return (f"KV headroom: {committed / 2**20:.2f}"
                        f" MiB committed > {cap_bytes / 2**20:.2f} MiB "
                        "budget")
            # reserved-lane gate (serve/slo.py): same budget, same
            # rounded worst-case needs — each class's committed charges
            # its own reservation first, only the overflow competes for
            # the shared pool, so batch traffic can never consume the
            # latency-critical lane's reservation
            reason = self._lane_reservation_reason(req, live, cap_bytes,
                                                   price)
            if reason is not None:
                return reason
        return None

    def _lane_reservation_reason(self, req: Request, live, budget: float,
                                 price) -> Optional[str]:
        """The per-class reserved-KV-headroom check (None without a
        policy or when no class reserves anything).  ``price(r)`` is the
        SAME worst-case-need arithmetic the total gate just used."""
        slo = self.slo
        if slo is None or not any(c.kv_reservation_frac
                                  for c in slo.classes.values()):
            return None
        cls = slo.resolve(req.slo_class)
        if cls is None:
            return None
        from .slo import reservation_reason

        by_cls: Dict[str, float] = {}
        for r in live:
            rc = slo.resolve(r.slo_class)
            key = rc.name if rc is not None else r.slo_class
            by_cls[key] = by_cls.get(key, 0.0) + price(r)
        return reservation_reason(slo, by_cls, cls, price(req), budget)

    def _lane_admission_reason(self, req: Request) -> Optional[str]:
        """Lane-level admission checks: the brownout ladder's admission
        gate for degradable classes and the per-class bounded pending
        queue.  None without a policy."""
        if self.slo is None:
            return None
        cls = self.slo.resolve(req.slo_class)
        if cls is None:
            return None  # unknown class is caller invalidity, not capacity
        bo = self.brownout
        if bo is not None and not bo.admits(cls.name):
            if self.telemetry.enabled:
                self.telemetry.lane_shed(cls.name, trace_id=req.trace_id,
                                         reason=f"brownout:{bo.level.name}")
            return (f"brownout {bo.level.name}: class {cls.name!r} "
                    "admissions shed")
        if cls.max_pending is not None:
            depth = sum(1 for rid in self.pending
                        if self.requests[rid].slo_class == cls.name)
            if depth >= cls.max_pending:
                return (f"class {cls.name!r} pending queue full "
                        f"({depth} >= {cls.max_pending})")
        return None

    def register_new_request(
        self, prompt_tokens: Sequence[int],
        max_new_tokens: Optional[int] = None, *,
        priority: int = 0, ttl_s: Optional[float] = None,
        deadline_s: Optional[float] = None, reject_invalid: bool = False,
        reject_reason: Optional[str] = None, spec: Optional[bool] = None,
        slo_class: Optional[str] = None,
    ) -> int:
        """Register a request; returns its rid.

        Invalid shapes (empty prompt, negative ``max_new_tokens``, prompt or
        prompt+max_new exceeding ``max_seq_len``) raise a host-side
        ``ValueError`` — unless ``reject_invalid`` is set (the arrival loop
        uses it), in which case the request is registered with a terminal
        ``REJECTED`` outcome instead, so one malformed arrival can never
        kill the serve loop.  Admission-control rejections (bounded queue /
        KV headroom, see :class:`~.resilience.ResilienceConfig`) always
        take the explicit ``REJECTED``-outcome path.  ``ttl_s`` (relative)
        or ``deadline_s`` (absolute on the manager's clock) arm a per-
        request deadline; ``max_new_tokens=0`` completes immediately with
        an ``ok`` outcome and zero tokens.  ``spec`` sets the request's
        speculation mode (None = the manager's ``default_spec_mode``);
        meaningful under a :class:`~.spec_infer.SpecInferManager`, inert
        otherwise.  ``slo_class`` names the request's traffic lane under
        an attached :class:`~.slo.SLOPolicy` (None/"" = the policy's
        default class; an unknown name is caller invalidity, rejected
        like a bad shape); the class's priority band adds to
        ``priority``, its brownout/queue/reservation gates apply, and an
        in-force DEGRADE_BATCH output cap truncates ``max_new_tokens``
        at admission.
        """
        req = self.request_cls(
            -1,
            list(int(t) for t in prompt_tokens),
            self.gen.max_new_tokens if max_new_tokens is None else int(max_new_tokens),
        )
        req.spec = bool(self.default_spec_mode if spec is None else spec)
        band = 0
        if self.slo is not None:
            cls = self.slo.resolve(slo_class)
            if cls is None:
                req.slo_class = str(slo_class)
            else:
                req.slo_class = cls.name
                band = cls.priority_band
        # reject_reason: caller-side invalidity (e.g. malformed arrival
        # options) that must take the REJECTED path like any shape error
        err = reject_reason if reject_reason is not None \
            else self._validate_request(req)
        if err is None and self.slo is not None \
                and self.slo.resolve(slo_class) is None:
            err = f"unknown slo_class {slo_class!r}"
        if err is not None and not reject_invalid:
            raise ValueError(err)
        rid = self._next_rid
        self._next_rid += 1
        req.rid = rid
        req.trace_id = f"r{rid:05d}"
        req.priority = int(priority) + band
        self.requests[rid] = req
        tel = self.telemetry
        if tel.enabled:
            self._tstamps[rid] = {
                "enqueue": tel.request_enqueued(req.trace_id,
                                                prompt_len=len(req.prompt))
            }
        reason = err if err is not None else self._admission_reason(req)
        if reason is not None:
            req.status = RequestStatus.REJECTED
            req.outcome = "rejected"
            # shed load must not grow host memory: the prompt tokens of a
            # rejected request are never served, so drop them — the
            # retained record is a small fixed-size stub (backpressure
            # would be pointless if every shed arrival kept its payload)
            req.prompt = []
            if tel.enabled:
                tel.request_rejected(req.trace_id, reason=reason)
            return rid
        if req.max_new_tokens == 0:
            # nothing to generate: terminal immediately, never takes a slot
            req.status = RequestStatus.COMPLETED
            req.outcome = "ok"
            if tel.enabled:
                tel.request_finished(req.trace_id, n_tokens=0,
                                     slo_class=req.slo_class or None)
            return rid
        if self.brownout is not None and self.brownout.degrades(
                req.slo_class):
            # DEGRADE_BATCH in force: admit, but speculation off and the
            # class's output cap applied up front (truncation only — the
            # served tokens stay a bit-identical PREFIX of the unloaded
            # run's stream).  Counted only when something actually
            # changed — lane_degraded_total is in obs.report.compare's exact
            # class, so a no-op "degradation" must not inflate it
            changed = req.spec
            req.spec = False
            cap = self.brownout.output_cap(req.slo_class)
            if cap is not None and cap < req.max_new_tokens:
                req.max_new_tokens = cap
                changed = True
            if changed and tel.enabled:
                tel.lane_degraded(req.slo_class)
        if deadline_s is not None:
            req.deadline_s = float(deadline_s)
        else:
            ttl = ttl_s if ttl_s is not None else self.res.default_ttl_s
            if ttl is not None:
                req.deadline_s = self.clock() + float(ttl)
        self.pending.append(rid)
        self._pending_since[rid] = self.steps
        return rid

    # ------------------------------------------------------------------
    # resilient-serving lifecycle: cancel / deadline / preempt / fail
    # ------------------------------------------------------------------
    def cancel(self, rid: int) -> bool:
        """Request cancellation of ``rid``; returns whether it was live.

        Takes effect at the NEXT host step boundary (``_check_lifecycle``):
        the slot and KV release immediately there, already-committed tokens
        are kept, and in-flight device work for the current step/scan is
        never interrupted — scan results for other requests are unchanged.
        A cancel issued while a decode STRETCH is in flight therefore lands
        only when that stretch returns (up to ``scan_chunk`` steps; once
        the flag is visible before dispatch, stretches are capped at
        ``lifecycle_quantum`` steps, the same bound armed deadlines get).
        """
        req = self.requests.get(rid)
        if req is None or req.status in TERMINAL_STATUSES:
            return False
        req.cancel_requested = True
        return True

    def set_spec_mode(self, rid: int, enabled: bool) -> bool:
        """Flip a live request's speculation mode at runtime; returns
        whether it was live.  Takes effect at the next macro-step/tick
        boundary — in-flight device work is never interrupted, so a flip
        can never change already-committed tokens.  Under a plain
        RequestManager the flag is inert; SpecInferManager reacts via
        :meth:`_on_spec_flip` (draft-cache catch-up on enable)."""
        req = self.requests.get(rid)
        if req is None or req.status in TERMINAL_STATUSES:
            return False
        enabled = bool(enabled)
        if req.spec == enabled:
            return True
        req.spec = enabled
        self._on_spec_flip(req)
        if self.telemetry.enabled:
            self.telemetry.spec_mode_changed(req.trace_id, spec=enabled)
        return True

    def _on_spec_flip(self, req: Request) -> None:
        """Hook for managers that keep per-mode state (the spec manager
        rebuilds the draft model's catch-up feed on enable)."""

    def _release_slot(self, req: Request) -> None:
        if req.slot >= 0:
            self.slots[req.slot] = None
            req.slot = -1
            # EVERY slot-leaving path — completion, cancel, timeout,
            # failure, preemption — releases the request's KV attribution
            # here, so no terminal outcome can leak it (pinned by
            # tests/test_kv_allocator.py); the returned peak-bytes stamp
            # rides finish telemetry and serving records
            req.kv_bytes = max(
                req.kv_bytes,
                self.im.kv.release(req.rid, tokens=req.seq_len))

    def _terminate(self, req: Request, status: RequestStatus,
                   site: str = "") -> None:
        """Move a request to a terminal status, releasing queue slot + KV.
        The outcome tag derives from the one status->outcome table
        (``OUTCOMES``) so the two can never drift; ``site`` attributes a
        FAILED termination to the dispatch site that exhausted its
        retries."""
        if req.rid in self.pending:
            self.pending.remove(req.rid)
        self._pending_since.pop(req.rid, None)
        self._release_slot(req)
        req.prefill_src = None  # recompute feed is dead weight once terminal
        kv = self.im.kv
        if kv.host_tier is not None:
            # a terminal request's host-tier pages are garbage too — drop
            # them now instead of waiting for the tier's LRU (the no-leak
            # contract extends to the host tier per terminal outcome)
            kv.drop_spill(req.rid)
        req.status = status
        req.outcome = OUTCOMES[status]
        if status is RequestStatus.REJECTED:
            # post-registration shed (brownout): same contract as the
            # admission path — shed load must not grow host memory
            req.prompt = []
        tel = self.telemetry
        if tel.enabled:
            n = len(req.generated)
            if status is RequestStatus.CANCELLED:
                tel.request_cancelled(req.trace_id, n_tokens=n)
            elif status is RequestStatus.TIMED_OUT:
                tel.request_timed_out(req.trace_id, n_tokens=n)
            elif status is RequestStatus.REJECTED:
                tel.request_rejected(req.trace_id,
                                     reason=site or "brownout shed")
            elif status is RequestStatus.FAILED:
                tel.request_failed(req.trace_id, site=site)

    def _swap_clock(self, new_clock):
        """Switch the deadline clock, RE-BASING every live armed deadline
        so its remaining budget is preserved — a TTL armed on the default
        ``perf_counter`` clock must still fire correctly once
        ``serve_with_arrivals`` swaps in an injected loop clock (and back).
        Returns the previous clock for the symmetric restore."""
        old = self.clock
        if new_clock is old:
            return old
        live = [self.requests[r] for r in self.pending] + self._active()
        armed = [r for r in live if r.deadline_s is not None]
        if armed:
            old_now, new_now = old(), new_clock()
            for req in armed:
                req.deadline_s = new_now + (req.deadline_s - old_now)
        self.clock = new_clock
        return old

    def _check_lifecycle(self, now: Optional[float] = None) -> None:
        """Step-boundary reaping of cancellations and deadline expiries —
        the ONE place a live request can leave the engine for a reason
        other than completing (host bookkeeping only; a reap between two
        steps can never change other requests' results).

        Scans only the LIVE requests (pending queue + slots), never the
        full registration history, so per-tick cost stays O(live) over
        long serving sessions.
        """
        live = [self.requests[r] for r in self.pending] + self._active()
        # host-tier satellite: a restored request that finished its
        # (shortened) catch-up prefill retires the recompute feed HERE —
        # before this, only terminal paths dropped ``prefill_src``
        # (_terminate), so a swap-restored request would carry a
        # dead-weight prompt+generated copy for its whole decode.  The
        # rebase is seq_len-invariant: ``prefill_offset - n_prefed`` is
        # exactly the prompt-only offset the unpreempted run would hold.
        for r in live:
            if (r.kv_restored and r.prefill_src is not None
                    and r.prefill_offset >= len(r.prefill_src)):
                r.prefill_offset -= r.n_prefed
                r.n_prefed = 0
                r.prefill_src = None
                r.kv_restored = False
        expirable = [r for r in live
                     if r.cancel_requested or r.deadline_s is not None]
        if not expirable:
            return
        if now is None:
            now = self.clock()
        for req in expirable:
            if req.cancel_requested:
                self._terminate(req, RequestStatus.CANCELLED)
            elif req.deadline_s is not None and now >= req.deadline_s:
                self._terminate(req, RequestStatus.TIMED_OUT)

    def preempt(self, rid: int) -> None:
        """Evict a running request, releasing its slot + KV immediately.

        The request re-enters the pending queue (status ``PREEMPTED``) and
        on readmission RE-PREFILLS ``prompt + generated`` — after which
        its served tokens are bit-identical to an unpreempted run for
        greedy AND seeded sampling (the per-request sample-key schedule
        keys on (rid, token index) only; pinned by
        tests/test_resilience.py, incl. int8 KV).  With a host tier
        attached, the victim's written pages spill to host DRAM first:
        readmission then restores them and recomputes only the unspilled
        tail — same bit-identity contract, O(transfer) instead of
        O(prefill).
        """
        req = self.requests[rid]
        if req.status not in (RequestStatus.PREFILLING,
                              RequestStatus.DECODING):
            raise ValueError(
                f"cannot preempt request {rid} in status {req.status.name}")
        self._kv_spill(req, self.im.kv)
        self._release_slot(req)
        req.prefill_src = list(req.prompt) + list(req.generated)
        req.n_prefed = len(req.generated)
        req.prefill_offset = 0
        req.starved_steps = 0
        req.kv_restored = False
        req.status = RequestStatus.PREEMPTED
        req.preemptions += 1
        self.pending.append(rid)
        # the aging clock restarts on preemption: it measures time
        # waiting for THIS admission, not lifetime
        self._pending_since[rid] = self.steps
        tel = self.telemetry
        if tel.enabled:
            tel.request_preempted(req.trace_id,
                                  recompute_tokens=len(req.prefill_src))

    # whether dispatch-failure recovery may requeue-and-recompute by
    # re-prefilling prompt+generated — True across the serving stack
    # (SpecInferManager included since ISSUE 11: its preempt() resets the
    # spec bookkeeping and the readmission re-prefills BOTH models'
    # caches); a subclass without a recompute story would flip this off
    # to make its failures go terminal instead
    supports_recompute = True

    # fleet failover hook (serve/fleet.py): when a dispatch exhausts its
    # retry budget, an attached ``on_exhausted(rm, site, exc,
    # affected_fn)`` may take over recovery — returning True means it
    # handled the affected requests (the fleet router preempts them and
    # fails them over to a surviving replica, so exhaustion on a dying
    # replica never goes terminally ``FAILED``); returning False (or no
    # hook — the default, pinned by tests/test_resilience.py) keeps the
    # single-replica r9 behavior: requeue-on-this-manager or FAILED per
    # ``res.on_dispatch_failure``.
    on_exhausted = None

    def _rids_in_batch(self, bc) -> List[int]:
        """The rids whose tokens are actually IN a built batch (a slotted
        request can sit out a step, e.g. a prefill starved of budget —
        dispatch failure must not touch it)."""
        base = bc if isinstance(bc, BatchConfig) else bc.base
        n = int(np.asarray(base.num_tokens))
        slots = {int(s) for s in np.asarray(base.request_index)[:n]
                 if int(s) >= 0}
        return [self.slots[s] for s in sorted(slots)
                if self.slots[s] is not None]

    def _fail_inflight(self, site: str, exc: Exception,
                       affected_fn=None) -> None:
        """Dispatch exhausted its retry budget: degrade gracefully.

        Only the requests whose tokens were in the failed batch
        (``affected_fn``, defaulting to every running slotted request for
        the stretch paths, where that is exact) are affected — per
        ``res.on_dispatch_failure`` they are requeued for recompute
        (bounded by ``max_requeues``) or failed terminally; everyone else
        keeps serving.  Faults are injected/raised before dispatch, so no
        partial device state exists to clean up.
        """
        if affected_fn is not None:
            affected = [self.requests[rid] for rid in affected_fn()]
        else:
            affected = self._active()
        affected = [r for r in affected
                    if r.status in (RequestStatus.PREFILLING,
                                    RequestStatus.DECODING)]
        for req in affected:
            if (self.supports_recompute
                    and self.res.on_dispatch_failure == "requeue"
                    and req.requeues < self.res.max_requeues):
                req.requeues += 1
                self.preempt(req.rid)
            else:
                self._terminate(req, RequestStatus.FAILED, site=site)

    def _guarded(self, site: str, fn, affected_fn=None):
        """Run one dispatch under the retry policy.

        Retries :class:`~.resilience.TransientServeError` with exponential
        backoff up to ``res.retry.max_retries`` times; a retried dispatch
        replays identical compute (faults raise pre-dispatch; device KV
        writes are positional and value-deterministic, so replay is
        idempotent).  Returns ``fn()``, or None once the budget is
        exhausted — the affected requests (``affected_fn``, evaluated only
        then) were requeued or failed via :meth:`_fail_inflight` and the
        serve loop continues.
        """
        pol = self.res.retry
        tel = self.telemetry
        attempt = 0
        while True:
            try:
                return fn()
            except TransientServeError as e:
                if tel.enabled:
                    tel.fault_observed(site, detail=str(e))
                if attempt >= pol.max_retries:
                    hook = self.on_exhausted
                    if hook is not None and hook(self, site, e,
                                                 affected_fn):
                        return None
                    self._fail_inflight(site, e, affected_fn)
                    return None
                attempt += 1
                delay = pol.backoff(attempt)
                if tel.enabled:
                    tel.dispatch_retry(site, attempt=attempt,
                                       backoff_s=delay)
                if delay > 0:
                    self._sleep(delay)

    # ------------------------------------------------------------------
    def _prof_account(self, spans, passes: int = 1, logit_rows=None,
                      im=None) -> None:
        """Deterministic work accounting for one dispatch group
        (obs/profiler.py): ``spans`` are the same ``(rid, lo, hi)``
        cache-write spans ``_kv_prepare`` consumes — ``hi - lo`` tokens
        fed, reading the ``hi``-deep causally-live prefix.  Host
        arithmetic only; no-op for the null profiler."""
        prof = self.profiler
        if not prof.enabled or not spans:
            return
        prof.account(prof.card_for(im or self.im),
                     [(rid, hi - lo, hi) for rid, lo, hi in spans],
                     passes=passes, logit_rows=logit_rows)

    # bounded aging for the priority queue (the fleet router sets this
    # from ``FleetConfig.starvation_bound_ticks`` on every replica): a
    # request pending longer than this many serve steps becomes OVERDUE
    # and is admitted ahead of every priority band (FIFO among overdue),
    # so a lower-priority class behind a sustained higher-priority
    # stream is starved only up to the bound.  None (the single-manager
    # default) keeps the historical strict-priority behavior.  A
    # brownout DEFER hold is exempt — an explicit policy state with its
    # own hysteresis-bounded exit, not priority competition.
    starvation_bound_ticks: Optional[int] = None

    def _held(self, req: Request) -> bool:
        """DEFER_BATCH semantics: is this queued request held out of
        engine slots by the brownout ladder this tick?  (Explicit policy
        hold — distinct from priority starvation, which the bounded
        aging above caps.)"""
        return (self.brownout is not None
                and self.brownout.holds(req.slo_class))

    def _pop_pending(self) -> Optional[int]:
        """Highest-priority ELIGIBLE pending rid, FIFO within a priority
        class — except OVERDUE requests (pending past the aging bound),
        which jump every band, oldest first.  None when every pending
        request is brownout-held."""
        cands = []
        for i in range(len(self.pending)):
            if self._held(self.requests[self.pending[i]]):
                # hold time is EXEMPT from aging (the documented
                # contract): re-stamp so the age measures only time
                # spent losing priority competition, not policy holds —
                # otherwise a long DEFER would mark the whole held
                # backlog overdue and batch would jump the
                # latency-critical lane exactly at recovery
                self._pending_since[self.pending[i]] = self.steps
            else:
                cands.append(i)
        if not cands:
            return None
        bound = self.starvation_bound_ticks
        if bound is not None:
            # setdefault: rids whose entry was not stamped (e.g. a
            # migration successor's wholesale pending list) start aging
            # from their first admission attempt
            overdue = [i for i in cands
                       if self.steps - self._pending_since.setdefault(
                           self.pending[i], self.steps) >= bound]
            if overdue:
                best = min(overdue,
                           key=lambda i: (self._pending_since.get(
                               self.pending[i], self.steps), i))
                self._pending_since.pop(self.pending[best], None)
                return self.pending.pop(best)
        best = max(cands,
                   key=lambda i: (self.requests[self.pending[i]].priority,
                                  -i))
        self._pending_since.pop(self.pending[best], None)
        return self.pending.pop(best)

    def _fill_slots(self) -> int:
        """Give free slots to pending requests; returns how many were taken.
        A request that takes a slot starts it from ZERO state: its first
        row sits at position 0, where every op with per-slot state (K/V
        cache, window ring, conv tail, scan state) starts anew whatever the
        slot held before — the ``state_reset`` argument of ``host_admit``
        counts these."""
        taken = 0
        for i, occupant in enumerate(self.slots):
            if occupant is None and self.pending:
                rid = self._pop_pending()
                if rid is None:
                    break  # everything pending is brownout-held
                req = self.requests[rid]
                req.slot = i
                req.status = RequestStatus.PREFILLING
                self.slots[i] = rid
                taken += 1
                self._kv_bind(rid)
                tel = self.telemetry
                if tel.enabled:
                    ts = self._tstamps.setdefault(rid, {})
                    # admission telemetry fires ONCE per request: a
                    # preempted request's READMISSION must not double-count
                    # requests_admitted or push its whole first service
                    # period into the queue_wait histogram
                    if "admit" not in ts:
                        ts["admit"] = tel.request_admitted(
                            req.trace_id,
                            queue_wait_s=(tel.now() - ts["enqueue"]
                                          if "enqueue" in ts else None))
        return taken

    def _try_preempt(self) -> bool:
        """Preempt the lowest-priority DECODING request (newest first among
        equals) iff a strictly-higher-priority request is waiting and no
        slot is free.  Returns whether an eviction happened."""
        if not self.pending or any(s is None for s in self.slots):
            return False
        # brownout-held requests can neither take a slot nor evict for one
        eligible = [r for r in self.pending
                    if not self._held(self.requests[r])]
        if not eligible:
            return False
        head_pri = max(self.requests[r].priority for r in eligible)
        victims = [r for r in self._active()
                   if r.status is RequestStatus.DECODING
                   and r.priority < head_pri
                   and r.preemptions < self.res.max_preemptions]
        if not victims:
            return False
        victim = min(victims, key=lambda r: (r.priority, -r.rid))
        self.preempt(victim.rid)
        return True

    def _admit(self, span=None) -> None:
        """Admission; ``span`` (the ``host_admit`` span it runs under) is
        told how many slots were taken (``state_reset``)."""
        if self.admission_closed:
            # a migration drain is in progress: nothing new takes a slot
            # (pending requests wait; they transplant to — or readmit
            # after a rollback on — whichever manager serves next)
            return
        taken = self._fill_slots()
        if self.res.preemption:
            # bounded: each iteration either admits into a freed slot or
            # stops (no admissible victim)
            for _ in range(len(self.slots)):
                if not (self.pending and self._try_preempt()):
                    break
                taken += self._fill_slots()
        if taken and span is not None:
            span.set(state_reset=taken)

    def _active(self) -> List[Request]:
        return [
            self.requests[rid] for rid in self.slots if rid is not None
        ]

    def has_work(self) -> bool:
        return bool(self.pending) or any(
            r.status in (RequestStatus.PREFILLING, RequestStatus.DECODING)
            for r in self._active()
        )

    # ------------------------------------------------------------------
    def prepare_next_batch(self) -> Tuple[BatchConfig, List[Tuple[int, int]]]:
        """Build the next step's BatchConfig.

        Returns (bc, sample_points) where sample_points is
        ``[(flat_token_index, rid)]`` — the token slots whose model output is
        the next token of that request (last prefill token, or the decode
        token).  Mirrors ``RequestManager::prepare_next_batch``.

        What still reaches it from :meth:`_serve_tick`: a 1-step trailer
        (some decoder has one token left, or one cache position), and the
        MIXED branch — decode rows first, then prompt rows, flat — only
        where :meth:`_tiled_feed` turns a prompt away: no Pallas kernels
        (CPU, tile 1), a manager without ``prefill_scan`` (serve/pp.py),
        an off-tile ``prefill_offset``, a closed admission.  With the
        kernels on, a prompt admitted among live decoders joins the
        decode stretch instead (:meth:`_stretch_join`).

        Phase attribution (StepProfiler): admission/slot-fill runs under
        ``host_admit``, batch assembly under ``host_prepare`` — separate
        accumulators, so the time budget shows scheduling cost apart from
        batch-build cost.
        """
        with self._span("host_admit", phase=True) as admit:
            self._admit(admit)
        with self._span("host_prepare", phase=True):
            return self._build_next_batch()

    def _build_next_batch(self) -> Tuple[BatchConfig, List[Tuple[int, int]]]:
        tokens: List[int] = []
        req_idx: List[int] = []
        positions: List[int] = []
        sample_points: List[Tuple[int, int]] = []
        # cache-write spans this step will perform (rid, lo, hi) — the
        # paged allocator maps/COWs those pages BEFORE dispatch
        spans: List[Tuple[int, int, int]] = []
        budget = self.im.max_tokens

        # decode tokens first: one per DECODING request (latency-critical)
        for req in self._active():
            if req.status is RequestStatus.DECODING and budget > 0:
                pos = req.seq_len - 1
                tokens.append(req.generated[-1])
                req_idx.append(req.slot)
                positions.append(pos)
                sample_points.append((len(tokens) - 1, req.rid))
                spans.append((req.rid, pos, pos + 1))
                budget -= 1

        n_decode = len(tokens)

        # a pure-prefill step with Pallas enabled ships tile-aligned chunks
        # (PrefillBatchConfig -> the Q-tiled prefill kernel); mixed
        # decode+prefill steps keep the flat layout
        tile = self.im.prefill_tile
        if (not tokens and tile > 1 and self.im.use_pallas
                and any(r.status is RequestStatus.PREFILLING
                        for r in self._active())
                # contract (d): tiled segments need tile-aligned starts; an
                # unaligned offset (hand-driven flat steps) rides the flat
                # path instead of crashing the builder
                and all(r.prefill_offset % tile == 0
                        for r in self._active()
                        if r.status is RequestStatus.PREFILLING)):
            segments = []
            for req in self._active():
                if req.status is not RequestStatus.PREFILLING or budget < tile:
                    continue
                # cap at whole tiles so the padded segment fits the capacity
                take = min((budget // tile) * tile,
                           len(req.prefill_tokens) - req.prefill_offset)
                start = req.prefill_offset
                segments.append(
                    (req.slot, req.prefill_tokens[start: start + take], start)
                )
                spans.append((req.rid, start, start + take))
                req.prefill_offset += take
                req.starved_steps = 0
                budget -= -(-take // tile) * tile  # padded tiles consumed
                if req.prefill_offset == len(req.prefill_tokens):
                    sample_points.append((req.slot, req.rid))
            seq_lens = np.zeros(self.im.max_requests, np.int32)
            for req in self._active():
                seq_lens[req.slot] = req.seq_len
            # LM-head gating: completing segments' sample points ride the
            # chunk's logit_slots, the step computes logits ONLY there, and
            # the result arrays are indexed by SLOT (shape [max_requests])
            gate = self.im.gate_lm_head
            pbc, last_flat = PrefillBatchConfig.build(
                segments, seq_lens, tile,
                max_tokens=self.im.max_tokens,
                max_requests=self.im.max_requests,
                gate_slots=[slot for slot, _ in sample_points]
                if gate else None,
            )
            sample_points = [
                (slot if gate else last_flat[slot], rid)
                for slot, rid in sample_points
            ]
            self._kv_prepare(spans)
            self._prof_account(
                spans, logit_rows=len(sample_points) if gate else None)
            n_prefill = sum(len(s[1]) for s in segments)
            self._note_batch(0, n_prefill, seq_lens)
            self._count_feed("tiled", n_prefill, chunks=1,
                             shared=int(len(segments) > 1))
            self._step_counts = self._launch_counts(spans, 0)
            return pbc, sample_points

        # then prefill chunks fill the remaining budget.  Mid-prompt cuts
        # keep prefill_offset TILE-ALIGNED (round the take down to whole
        # tiles) so later pure-prefill steps can ride the tiled Pallas path
        # — PrefillBatchConfig's contract (d) rejects unaligned segment
        # starts.  Completing takes (remaining <= budget) need no rounding.
        for req in self._active():
            if req.status is not RequestStatus.PREFILLING or budget <= 0:
                continue
            remaining = len(req.prefill_tokens) - req.prefill_offset
            if remaining <= budget:
                take = remaining
            elif (tile > 1 and self.im.use_pallas
                    and req.prefill_offset % tile == 0):
                # only the Pallas tiled path consumes the alignment; the
                # gather path must not stall prefill for it — and a request
                # already off-tile (starvation fallback below) has nothing
                # left to protect, so it skips the rounding entirely
                take = (budget // tile) * tile
                if take == 0:
                    # budget < one tile: normally wait to keep alignment —
                    # but when decode tokens leave less than a tile of
                    # budget EVERY step, waiting starves the prompt until
                    # the decoders finish (unbounded TTFT, ADVICE r5 low).
                    # After ``starvation_limit`` consecutive dry steps, take
                    # an UNALIGNED flat chunk: the offset goes off-tile, so
                    # the tiled-branch alignment gate above routes this
                    # request's later chunks through the flat gather path —
                    # slower per token, but it makes progress every step.
                    req.starved_steps += 1
                    if req.starved_steps < self.starvation_limit:
                        continue
                    take = budget
            else:
                take = budget
                if tile > 1 and self.im.use_pallas and budget >= tile:
                    # an off-tile offset (starvation fallback above) blocks
                    # the tiled pure-prefill path for EVERY concurrently
                    # prefilling request (the alignment gate is all-or-
                    # nothing).  In budget-rich steps round the take so the
                    # offset lands back on a tile boundary: one slightly
                    # smaller take buys the Q-tiled kernel back for the
                    # whole batch.  Starved steps (budget < tile) keep the
                    # full take — progress beats re-alignment there.
                    over = (req.prefill_offset + take) % tile
                    if 0 < over < take:
                        take -= over
            start = req.prefill_offset
            for j in range(take):
                tokens.append(req.prefill_tokens[start + j])
                req_idx.append(req.slot)
                positions.append(start + j)
            if take:
                spans.append((req.rid, start, start + take))
            req.prefill_offset += take
            req.starved_steps = 0
            budget -= take
            if req.prefill_offset == len(req.prefill_tokens):
                # output at the last prefill token = next generated token
                sample_points.append((len(tokens) - 1, req.rid))

        # cache depth after this step: prefill tokens fed so far + generated
        # tokens not already in the feed (the decode token fed this step is
        # generated[-1], whose KV lands at position seq_len-1 during the
        # step) — Request.seq_len is exactly that arithmetic
        seq_lens = np.zeros(self.im.max_requests, np.int32)
        for req in self._active():
            seq_lens[req.slot] = req.seq_len
        bc = BatchConfig.build(
            tokens, req_idx, positions, seq_lens,
            max_tokens=self.im.max_tokens,
            max_requests=self.im.max_requests,
        )
        self._kv_prepare(spans)
        self._prof_account(spans)
        self._note_batch(n_decode, len(tokens) - n_decode, seq_lens)
        self._count_feed("flat", len(tokens) - n_decode)
        self._step_counts = self._launch_counts(spans, n_decode)
        return bc, sample_points

    def _note_batch(self, n_decode: int, n_prefill: int, seq_lens) -> None:
        """Batch-composition telemetry for one step (token mix, slot
        occupancy, KV utilization) — host counters only."""
        tel = self.telemetry
        if not tel.enabled:
            return
        tel.batch_composition(
            n_decode, n_prefill,
            active_requests=sum(1 for s in self.slots if s is not None),
            max_requests=self.im.max_requests,
            kv_tokens=int(np.sum(seq_lens)),
            kv_capacity=self.im.max_requests * self.im.max_seq_len,
        )

    def _append_token(self, req: Request, tok: int) -> None:
        """Commit one generated token — the ONE place the first-token
        (TTFT) telemetry stamp can live, whatever path produced the token
        (per-step result, prefill stretch, decode scan, spec verify)."""
        req.generated.append(tok)
        self.tokens_decoded += 1
        tel = self.telemetry
        if tel.enabled and len(req.generated) == 1:
            ts = self._tstamps.setdefault(req.rid, {})
            now = tel.request_first_token(
                req.trace_id,
                ttft_s=(tel.now() - ts["enqueue"]
                        if "enqueue" in ts else None),
                slo_class=req.slo_class or None)
            ts["first_token"] = now

    def process_result(self, result, sample_points) -> None:
        if not sample_points:
            # mid-prefill step: nothing to read back — leave the result on
            # device so chunked prefill dispatches stay fully async
            return
        with self._span("readback", phase=True):
            self._device_wait(result.token_ids)
            token_ids = np.asarray(result.token_ids)
            expert_load = self._expert_load()
        self.profiler.host_sync()
        with self._span("commit", **expert_load) as sp:
            before = self.tokens_decoded
            for flat_idx, rid in sample_points:
                req = self.requests[rid]
                if req.status not in (RequestStatus.PREFILLING,
                                      RequestStatus.DECODING):
                    # the request left its slot between batch build and
                    # result readback (page-pressure preemption in
                    # _kv_prepare runs AFTER the batch is built): its
                    # emission is dead — the readmission recomputes it, and
                    # appending here would double-count the token in the
                    # recompute feed
                    continue
                tok = int(token_ids[flat_idx])
                if req.status is RequestStatus.PREFILLING:
                    req.status = RequestStatus.DECODING
                self._append_token(req, tok)
                self._maybe_finish(req)
            sp.set(step_tokens=self.tokens_decoded - before)

    def _maybe_finish(self, req: Request) -> None:
        eos = self.gen.eos_token_id
        if (
            len(req.generated) >= req.max_new_tokens
            or (self.gen.stop_on_eos and eos is not None
                and req.generated and req.generated[-1] == eos)
        ):
            req.status = RequestStatus.COMPLETED
            req.outcome = "ok"
            req.prefill_src = None  # recompute feed is dead once terminal
            self._release_slot(req)
            tel = self.telemetry
            if tel.enabled:
                ts = self._tstamps.get(req.rid, {})
                now = tel.now()
                first = ts.get("first_token")
                tel.request_finished(
                    req.trace_id, n_tokens=len(req.generated),
                    tpot_s=((now - first)
                            / max(len(req.generated) - 1, 1)
                            if first is not None else None),
                    kv_bytes=req.kv_bytes or None,
                    slo_class=req.slo_class or None)

    # ------------------------------------------------------------------
    def _scan_steps_possible(self) -> int:
        """How many pure-decode steps can run as ONE on-device scan now.

        > 1 only when some request is decoding and every other active
        request is a prompt the stretch can splice in itself
        (:meth:`_tiled_feed`: it rides the tiled prefill scan and joins
        before the first segment); bounded by the decoders' smallest
        remaining token budget (so no slot overshoots max_new_tokens) and
        by cache headroom.
        """
        active = self._active()
        decoding = [r for r in active
                    if r.status is RequestStatus.DECODING]
        joiners = [r for r in active
                   if r.status is not RequestStatus.DECODING]
        if not decoding or not all(
                self._tiled_feed(r) for r in joiners):
            return 0
        if self.pending:
            # pending work blocks a stretch ONLY when the per-tick path
            # could actually act on it right now — a free slot to fill, or
            # a preemption that would fire.  Otherwise (all slots busy, no
            # victim) the queue is waiting regardless, and the stretch
            # admits mid-stretch joiners itself the moment a slot frees,
            # so the stretch proceeds
            eligible = [rid for rid in self.pending
                        if not self._held(self.requests[rid])]
            if eligible and (self.admission_closed
                             or any(s is None for s in self.slots)
                             or self._preempt_would_fire()):
                return 0
        n = min(r.max_new_tokens - len(r.generated) for r in decoding)
        if joiners:
            # a row with ONE token left must not send the joiners' prompts
            # down the flat mixed step: it rides a 2-step segment and
            # freezes on device after its token (the ``allowed`` mask)
            n = max(n, 2)
        n = min(n, self.scan_chunk,
                self.im.max_seq_len - max(r.seq_len for r in decoding) + 1)
        # armed deadlines or pending cancels bound the stretch: lifecycle
        # reaping happens at host step boundaries, so an uncapped scan
        # would overshoot a deadline by up to scan_chunk device steps.
        # (This bounds SEGMENTS, not the stretch — the chain clock-checks
        # between dispatches; see _decode_stretch.)
        if any(r.deadline_s is not None or r.cancel_requested
               for r in active):
            n = min(n, self.lifecycle_quantum)
        # round down to a power of two: n is a STATIC arg of the jitted
        # scan, so every distinct value compiles the whole n-step model —
        # quantizing bounds the compile count to ~log2(scan_chunk) variants
        if n > 1:
            n = 1 << (n.bit_length() - 1)
        return n

    def _preempt_would_fire(self) -> bool:
        """Would _try_preempt evict someone for the head of the queue?
        Mirrors its victim scan without acting — the chained stretch gate
        must fall back to the per-tick path whenever preemption could
        admit pending work (preempting a row the device is mid-scan on
        would corrupt its cache)."""
        if not self.res.preemption or not self.pending:
            return False
        eligible = [rid for rid in self.pending
                    if not self._held(self.requests[rid])]
        if not eligible:
            return False
        head_pri = max(self.requests[rid].priority for rid in eligible)
        return any(r.status is RequestStatus.DECODING
                   and r.priority < head_pri
                   and r.preemptions < self.res.max_preemptions
                   for r in self._active())

    scan_chunk = 32  # sync-amortization window for the decode scan
    # serve_with_arrivals hooks for the decode stretch: pump registers
    # newly-due arrivals at segment boundaries; stamp records
    # prefill_start_s for mid-stretch joiners
    _arrival_pump = None
    _join_stamp = None
    # rid -> device exit code of the last chained stretch (EXIT_* in
    # inference_manager.py); rebound per stretch, never mutated in place
    last_exit_codes: Dict[int, int] = {}
    # mixed decode+prefill steps whose tiled budget rounds to 0 before the
    # starved request falls back to an unaligned flat-path take (bounds the
    # TTFT inflation at ~limit decode steps; see prepare_next_batch)
    starvation_limit = 4
    # decode-scan cap while any active request carries a deadline or a
    # pending cancel: bounds how far past a deadline a stretch can run
    # (lifecycle reaping is step-boundary-granular)
    lifecycle_quantum = 8
    # serve ticks between plan-health polls when a monitor is attached
    # (each poll is host-side percentile/PSI arithmetic — cheap, but not
    # free enough for every tick of a hot decode loop)
    health_check_every = 16

    # ------------------------------------------------------------------
    def _tiled_feed(self, req: Request) -> bool:
        """Does ``req``'s remaining prompt ride the tiled prefill scan
        (``im.prefill_scan``: ``PrefillBatchConfig`` chunks, the Q-tiled
        kernel, block KV writes, a gated LM head)?  THE predicate of the
        prompt feed — a wave and a joiner ask the same one — read off what
        the manager and the request show: the Pallas kernels are on, the
        tile is a real one, the manager scans prefill chunks
        (``serve/pp.py``'s does not), and the feed starts ON a tile
        (contract (d); a prefix-cache hit or a starvation fallback can
        leave it off).  Everything else keeps the flat step."""
        im = self.im
        return (im.prefill_tile > 1
                and im.use_pallas
                and hasattr(im, "prefill_scan")
                and req.prefill_offset % im.prefill_tile == 0)

    def _count_feed(self, path: str, tokens: int, chunks: int = 0,
                    shared: int = 0) -> None:
        """Prompt tokens fed, by path (``prompt_feed.tiled_tokens`` /
        ``.flat_tokens``); the tiled feed also counts its chunks, the rows
        of them that held no prompt token, and the ``shared`` of them that
        held rows of more than one request (a wave's packed chunks; 0 where
        every feed is one request's)."""
        tel = self.telemetry
        if not tel.enabled or not tokens:
            return
        tel.metrics.counter(f"prompt_feed.{path}_tokens").inc(tokens)
        if chunks:
            tel.metrics.counter("prompt_feed.tiled_chunks").inc(chunks)
            tel.metrics.counter("prompt_feed.tiled_padded_rows").inc(
                chunks * self.im.max_tokens - tokens)
            tel.metrics.counter("prompt_feed.shared_chunks").inc(shared)

    def _prefill_stretch_possible(self) -> bool:
        """Can the whole current prefill wave run as on-device scans?

        True when every active request is PREFILLING (no decode latency to
        protect) and rides the tiled feed (:meth:`_tiled_feed`).  The
        stretch then feeds every request's remaining prompt through
        ``prefill_scan`` — one dispatch per power-of-two chunk segment and
        ONE host sync at the end, vs a dispatch per chunk (+ a host sync
        per request boundary) on the per-step path.
        """
        with self._span("host_admit", phase=True) as admit:
            self._admit(admit)
        active = self._active()
        return (
            bool(active)
            and all(r.status is RequestStatus.PREFILLING for r in active)
            and any(r.prefill_offset < len(r.prefill_tokens) for r in active)
            and all(self._tiled_feed(r) for r in active)
        )

    def _prefill_chunks(self, gate: bool, sampling: bool, reqs=None,
                        depths=None):
        """Cut the remaining feed of ``reqs`` (default: every prefilling
        request) into tile-aligned chunks, the requests' tiles laid END TO
        END: a request's remaining prompt is ``ceil(left / tile)`` tiles,
        they fill the open chunk, the rest opens the next, and the next
        request starts on the next free tile of the same chunk.  So a chunk
        holds one segment ``(slot, tokens, start)`` for every request with
        rows in it (at most one a slot; a prompt that crosses a chunk's end
        is two segments in two consecutive chunks, which the scan runs in
        order), and ONE request's feed is the chunks it always was.

        Returns, per chunk: the numpy fields, the logit slots, the sample
        folds and the segments' ``[(start, take)]``; and the sample points
        ``(chunk_idx, result_idx, rid)``, one for every prompt that ENDS in
        a chunk.  Advances ``prefill_offset``.  ``depths``: ``{slot: cache
        depth}`` of rows a running chain is ahead of the committed host
        view on (their DEVICE depths go into ``seq_lens``)."""
        im = self.im
        tile = im.prefill_tile
        cap = im.max_tokens
        room = (cap // tile) * tile  # a chunk's rows, in whole tiles
        n_rows = im.max_requests if gate else cap
        chunks: List = []  # per-chunk numpy field tuples (BatchConfig order)
        ls_chunks: List = []  # per-chunk logit_slots (gated path)
        fold_chunks: List = []  # per-chunk (rid, token-index) sample folds
        # (chunk_idx, result_idx, rid): result_idx is the SLOT when gated
        # (result arrays are [max_requests]), the flat token index otherwise
        points: List[Tuple[int, int, int]] = []
        feeds: List[List[Tuple[int, int]]] = []  # per-chunk [(start, take)]
        seq = np.zeros(im.max_requests, np.int32)
        for req in self._active():
            seq[req.slot] = req.seq_len
        for slot, depth in (depths or {}).items():
            seq[slot] = depth
        segs: List[Tuple[Request, int, int]] = []  # the open chunk's
        used = 0                                   # ... and its rows taken

        def close():
            nonlocal used
            fields, last_flat = PrefillBatchConfig.np_fields(
                [(r.slot, r.prefill_tokens[st: st + t], st)
                 for r, st, t in segs],
                seq, tile, max_tokens=cap, max_requests=im.max_requests)
            # the requests whose prompts END here, each with its result row
            done = [(r, r.slot if gate else last_flat[r.slot])
                    for r, st, t in segs if st + t == len(r.prefill_tokens)]
            points.extend((len(chunks), ridx, r.rid) for r, ridx in done)
            # deterministic accounting: one model pass per chunk; gated
            # chunks materialize logits only at the slots of the requests
            # whose prompts end in them
            self._prof_account(
                [(r.rid, st, st + t) for r, st, t in segs],
                logit_rows=len(done) if gate else None)
            if sampling:
                fc = np.zeros((n_rows, 2), np.int32)
                for r, ridx in done:
                    fc[ridx] = self._fold_for(r)
                fold_chunks.append(fc)
            ls_chunks.append(PrefillBatchConfig.np_logit_slots(
                [r.slot for r, _ in done], last_flat, im.max_requests))
            chunks.append(fields)
            feeds.append([(st, t) for _, st, t in segs])
            segs.clear()
            used = 0

        for req in (self._active() if reqs is None else reqs):
            if req.status is not RequestStatus.PREFILLING:
                continue
            while req.prefill_offset < len(req.prefill_tokens):
                start = req.prefill_offset
                take = min(room - used, len(req.prefill_tokens) - start)
                # (seq_lens as the chunk that holds this segment sees them:
                # ``close`` copies them before a later segment moves them)
                seq[req.slot] = start + take
                segs.append((req, start, take))
                req.prefill_offset += take
                used += -(-take // tile) * tile
                if used == room:
                    close()
        if segs:
            close()
        return chunks, ls_chunks, fold_chunks, points, feeds

    def _sample_args(self, fold_chunks):
        """The prefill scan's ``sample`` argument for a feed whose chunks'
        folds are ``fold_chunks`` (kept on the host: :meth:`_stack_chunks`
        ships a launch's share), or None where this manager decodes
        greedily.  The per-request key schedule: the chunk carrying request
        rid's completion samples its token n with fold (rid, n) — the same
        key whatever chunking, segmentation or preemption produced it."""
        import jax
        import jax.numpy as jnp

        if not len(fold_chunks):
            return None
        return (jax.random.PRNGKey(self.gen.seed),
                jnp.float32(self.gen.temperature),
                jnp.float32(self.gen.top_p), np.stack(fold_chunks))

    def _stack_chunks(self, chunks, ls_chunks, sample, at: int = 0):
        """One launch's arguments: ``chunks`` (numpy fields) stacked on the
        host — ONE device transfer per field per launch, not five tiny ones
        per chunk — with their logit slots (None: ungated) and the folds of
        ``sample`` from chunk ``at`` on."""
        import jax.numpy as jnp

        stacked = PrefillBatchConfig(
            base=BatchConfig(*(jnp.asarray(np.stack([c[i] for c in chunks]))
                               for i in range(5))),
            tile_size=self.im.prefill_tile,
            logit_slots=None if ls_chunks is None
            else jnp.asarray(np.stack(ls_chunks)))
        return stacked, sample and (
            *sample[:3], jnp.asarray(sample[3][at: at + len(chunks)]))

    def build_prefill_scans(self, n_chunks: int) -> None:
        """Build the prefill-scan programs of every power of two up to
        ``n_chunks`` (at most 64), as this manager's feeds ask for them
        (its LM-head gate, its sampling), on all-pad chunks, which move no
        slot's cache or state.  :meth:`_prefill_feed` cuts no feed into
        launches longer than the manager has run, so that a feed among live
        decoders never waits for a compile; a deployment whose first feeds
        are short would serve its long waves in short launches ever after.
        This is how it (or a warm-up) asks for the longest wave it expects,
        at start-up; a no-op where the lengths are built, or where no feed
        is tiled (:meth:`_tiled_feed`)."""
        im = self.im
        if not (im.prefill_tile > 1 and im.use_pallas
                and hasattr(im, "prefill_scan")):
            return
        n = 1 << (max(1, min(n_chunks, 64)).bit_length() - 1)
        gate = im.gate_lm_head
        fields, last_flat = PrefillBatchConfig.np_fields(
            (), (), im.prefill_tile, im.max_tokens, im.max_requests)
        ls = PrefillBatchConfig.np_logit_slots((), last_flat, im.max_requests)
        rows = im.max_requests if gate else im.max_tokens
        sample = self._sample_args(
            [np.zeros((rows, 2), np.int32)] * n
            if self.gen.temperature > 0.0 else [])
        if im.prefill_scan_longest(gate, sample) < n:
            im.prefill_scan(*self._stack_chunks(
                [fields] * n, [ls] * n if gate else None, sample),
                counts={"pad": 1})

    def _prefill_feed(self, joiners=None, depths=None, rows: int = 0):
        """THE tiled prompt feed, a wave's and a joiner's alike: cut the
        remaining prompts (:meth:`_prefill_chunks`: several requests' tiles
        share chunks) — of every prefilling request, or of ``joiners``,
        requests about to be spliced into a running batch of ``rows`` live
        decode rows whose device depths are ``depths`` (one a feed today:
        :meth:`_join_one` takes its token from the feed's LAST chunk) — and
        dispatch the chunks through ``im.prefill_scan``, asynchronously, in
        power-of-two segments of lengths the manager has run.  Returns
        ``(points, outs)`` — ``outs`` holds
        ``(first chunk, tokens [segment, T or R], last)`` per dispatched
        segment, all on the device (``last``: the segment's final chunk in
        ``join_slot``'s flat layout) — or None when a dispatch failed past
        the retry budget (:meth:`_fail_inflight` already requeued or
        failed the requests fed: the joiners, or every active one).
        """
        im = self.im
        gate = im.gate_lm_head
        with self._span("host_prepare", phase=True):
            chunks, ls_chunks, fold_chunks, points, feeds = \
                self._prefill_chunks(gate, self.gen.temperature > 0.0,
                                     joiners, depths)
            sample = self._sample_args(fold_chunks)
        affected = None if joiners is None else (
            lambda: [r.rid for r in joiners])
        # scan in power-of-two segments so each distinct scan length
        # compiles at most once — none longer than the longest the manager
        # has run with every shorter one (``im.prefill_scan`` keeps that
        # set closed), so a feed under load finds its programs built
        # whatever totals the warm-up made.  The price: the first feeds'
        # lengths bound every later feed's launches
        # (:meth:`build_prefill_scans` raises the bound)
        longest = im.prefill_scan_longest(gate, sample)
        cut, left = [], len(chunks)
        while left:
            cut.append(1 << (min(left, longest or 64, 64).bit_length() - 1))
            left -= cut[-1]
        outs = []
        at = 0
        for k, seg in enumerate(cut):
            with self._span("host_prepare", phase=True):
                stacked, smp = self._stack_chunks(
                    chunks[at: at + seg], ls_chunks[at: at + seg] if gate
                    else None, sample, at)
            # each request segment's start offset and size: what the
            # prefill kernel's least work is computed from (``segments``
            # above ``n_chunks``: a launch whose chunks are shared)
            parts = [p for f in feeds[at: at + seg] for p in f]
            fed = sum(t for _, t in parts)
            cnt = {"rows": rows, "joiners": len(joiners or ()),
                   "prompt_tokens": fed, "segments": len(parts),
                   "ctx_sum": sum(st for st, _ in parts),
                   # the keys the fed rows see in ONE full-length layer (a
                   # row at position p sees p + 1), as a flat step's span
                   # carries it
                   "prompt_ctx_sum": sum(t * (2 * st + t + 1) // 2
                                         for st, t in parts),
                   **self._op_counts(
                       None, [(st, st + t) for st, t in parts])}
            res = self._guarded(
                "prefill_scan",
                lambda s=stacked, a=smp, c=cnt, rest=cut[k + 1:]:
                im.prefill_scan(s, a, counts=c, flat_last=True, coming=rest),
                affected_fn=affected)
            if res is None:
                return None
            self._count_feed(
                "tiled", fed, chunks=seg,
                shared=sum(len(f) > 1 for f in feeds[at: at + seg]))
            outs.append((at, *res))
            at += seg
        return points, outs

    def _prefill_stretch(self) -> None:
        """Prefill every active request's remaining feed via prefill_scan."""
        # the whole stretch's write spans, prepared before the first
        # dispatch (the scans run back-to-back with no host boundary to
        # map pages at)
        self._kv_prepare([
            (r.rid, r.prefill_offset, len(r.prefill_tokens))
            for r in self._active()
            if r.status is RequestStatus.PREFILLING
            and r.prefill_offset < len(r.prefill_tokens)])
        fed = self._prefill_feed()
        self.scan_runs += 1
        if fed is None:
            # dispatch failed past the retry budget: _fail_inflight
            # already requeued/failed every prefilling request (their
            # advanced offsets were reset by the recompute path) — the
            # partial segments' KV is dead weight the next occupant of
            # each slot overwrites
            return
        points, outs = fed
        with self._span("readback", phase=True):
            self._device_wait([t for _, t, _ in outs])
            toks = {start: np.asarray(t) for start, t, _ in outs}  # one sync
            expert_load = self._expert_load()
        self.profiler.host_sync(len(outs))
        starts = sorted(toks)
        with self._span("commit", prefill_tokens=len(points), **expert_load):
            for chunk_idx, flat_idx, rid in points:
                start = max(s for s in starts if s <= chunk_idx)
                req = self.requests[rid]
                req.status = RequestStatus.DECODING
                self._append_token(
                    req, int(toks[start][chunk_idx - start, flat_idx]))
                self._maybe_finish(req)
        self.steps += sum(len(t) for t in toks.values())

    def _decode_stretch(self, n: int) -> None:
        """Run one decode stretch with ONE host sync.

        The stretch is a CHAIN of back-to-back ``decode_scan_async``
        segments — dispatched with no readback between them — that keeps
        running up to ``scan_chunk`` total steps while any row has budget
        left:

        * rows of UNEQUAL remaining budgets ride one stretch (the device
          freezes each row at ITS budget via the ``allowed`` mask and
          reports a per-row exit code; the host no longer stops the whole
          scan at the smallest budget);
        * armed deadlines/cancels bound SEGMENTS (the host clock-checks
          between dispatches, same ``lifecycle_quantum`` granularity)
          instead of terminating the stretch;
        * prompts JOIN the running batch (:meth:`_stretch_join`): the
          requests that hold a slot at the tick's start but have not been
          fed — admitted among live decoders — BEFORE the first segment,
          arrivals landing mid-stretch at the next segment boundary.  The
          prompt is fed asynchronously (tile-aligned chunks through the
          prefill scan; flat chunks where :meth:`_tiled_feed` says no),
          then ``join_slot`` splices the held first token into the batch
          — so pending work neither degenerates serving to one dispatch
          per token nor sends a prompt through the 512-row flat step.

        Everything materializes in ONE readback at stretch end (tokens,
        emission masks, exit codes), then commits in dispatch order —
        bit-identical to the per-tick loop by construction (same sample
        folds, same masks).  A joiner's first token therefore becomes
        visible when the stretch returns, up to ``scan_chunk`` steps
        after its prompt was fed.
        """
        im = self.im
        prof = self.profiler
        eos = self.gen.eos_token_id if self.gen.stop_on_eos else None
        # whole first-segment write spans — and the whole prompt of every
        # request that joins before it — up front, BEFORE building the
        # batch: nothing is in flight yet, so page pressure may still
        # preempt here (a victim must drop out of the batch)
        self._kv_prepare([
            (r.rid, r.seq_len - 1,
             r.seq_len - 1 + min(n, r.max_new_tokens - len(r.generated)))
            if r.status is RequestStatus.DECODING
            else (r.rid, r.prefill_offset, len(r.prefill_tokens))
            for r in self._active()])
        active = [r for r in self._active()
                  if r.status is RequestStatus.DECODING]
        if not active:
            return
        rows: List[Tuple[Request, int]] = []   # (req, flat row) in order
        sched: Dict[int, int] = {}    # rid -> tokens produced this stretch
        dev_seq: Dict[int, int] = {}  # rid -> device-side cache depth
        with self._span("host_prepare", phase=True):
            tokens, reqi, pos = [], [], []
            for req in active:
                tokens.append(req.generated[-1])
                reqi.append(req.slot)
                pos.append(req.seq_len - 1)
                rows.append((req, len(rows)))
                sched[req.rid] = 0
                dev_seq[req.rid] = req.seq_len
            seq_lens = np.zeros(im.max_requests, np.int32)
            for req in active:
                seq_lens[req.slot] = req.seq_len
            bc = BatchConfig.build(
                tokens, reqi, pos, seq_lens,
                max_tokens=im.max_tokens, max_requests=im.max_requests)

        def remaining(req):
            return req.max_new_tokens - len(req.generated) - sched[req.rid]

        def next_writes(among, seg):
            return [(req.rid, dev_seq[req.rid] - 1,
                     dev_seq[req.rid] - 1 + min(seg, remaining(req)))
                    for req, _ in among if remaining(req) > 0]

        # chronological commit log: ("scan", seg, [(flat, rid)], toks,
        # live, ecode) per dispatched segment, ("join", req, token_ids,
        # src_idx) per spliced arrival — all values LAZY until the single
        # readback below
        commits: List[Tuple] = []
        total = 0
        n_segments = 0
        seg = n
        go = True
        if len(active) < len(self._active()):
            # requests admitted among these decoders (a slot, no prompt
            # fed yet) join BEFORE the first segment; a joined row's first
            # decode writes need their pages like a later segment's do
            bc = self._stretch_join(bc, rows, sched, dev_seq, commits, eos)
            go = self._kv_prepare_nopreempt(
                next_writes(rows[len(active):], seg))
        while go:
            with self._span("host_prepare", phase=True):
                ks: Dict[int, int] = {}
                allowed = np.zeros(im.max_tokens, np.int32)
                pts = []
                cnt = {"rows": 0, "prompt_tokens": 0, "ctx_sum": 0}
                for req, flat in rows:
                    k = max(min(seg, remaining(req)), 0)
                    ks[req.rid] = k
                    # the emission budget is the row's FULL remaining, not
                    # the segment cap: a row that outlives this segment
                    # must end it alive so its exit code reads RUNNING,
                    # not BUDGET
                    allowed[flat] = max(remaining(req), 0)
                    pts.append((flat, req.rid, sched[req.rid]))
                    if k > 0:   # a live row, and its KV length at launch
                        cnt["rows"] += 1
                        cnt["ctx_sum"] += dev_seq[req.rid]
                cnt.update(self._op_counts(
                    [(dev_seq[req.rid] - 1, dev_seq[req.rid] - 1
                      + ks[req.rid]) for req, _ in rows], None))
                if prof.enabled:
                    # k_i decode steps per row: each streams the weights
                    # and reads the growing causally-live prefix
                    prof.account(
                        prof.card_for(im),
                        [(req.rid, ks[req.rid],
                          ks[req.rid] * dev_seq[req.rid]
                          + ks[req.rid] * (ks[req.rid] - 1) // 2)
                         for req, _ in rows if ks[req.rid] > 0],
                        passes=seg)
                max_pos = max(dev_seq[req.rid] - 1 + ks[req.rid]
                              for req, _ in rows) - seg
            # sample folds advance past the stretch's UNCOMMITTED tokens:
            # row i's next key is (rid_i, len(generated_i) + sched_i)
            smp = self._sample_for(pts, im.max_tokens)
            this_seg = seg
            out = self._guarded(
                "decode_scan",
                lambda: im.decode_scan_async(
                    bc, this_seg, eos=eos, sample=smp,
                    allowed=allowed, max_position=max_pos, counts=cnt))
            if out is None:
                # the whole stretch's emissions were in flight and nothing
                # was committed: the requeue recompute regenerates every
                # token deterministically, earlier segments included
                self.scan_runs += 1
                return
            toks, live, ecode, bc = out
            commits.append(("scan", this_seg,
                            [(flat, req.rid) for req, flat in rows],
                            toks, live, ecode))
            for req, _ in rows:
                sched[req.rid] += ks[req.rid]
                dev_seq[req.rid] += ks[req.rid]
            total += this_seg
            n_segments += 1

            # ---- segment boundary: extend, join, or stop --------------
            reqs = [req for req, _ in rows]
            if any(r.cancel_requested for r in reqs):
                break                      # reap at the tick boundary
            if any(r.slot < 0 or self.slots[r.slot] != r.rid
                   for r in reqs):
                break   # a clock-callback preempted/terminated a row
            if self._arrival_pump is not None:
                with self._span("host_admit", phase=True):
                    self._arrival_pump()   # register newly-due arrivals
            rem_cap = self.scan_chunk - total
            if rem_cap < 2:
                break
            if (self.pending and not self.admission_closed
                    and len(rows) < im.max_tokens
                    and any(s is None for s in self.slots)
                    and any(not self._held(self.requests[rid])
                            for rid in self.pending)):
                bc = self._stretch_join(bc, rows, sched, dev_seq,
                                        commits, eos)
            armed = [r.deadline_s for r, _ in rows
                     if r.deadline_s is not None]
            if armed and self.clock() >= min(armed):
                break                      # reap at the tick boundary
            rem = [remaining(req) for req, _ in rows]
            rem_max = max(rem) if rem else 0
            if rem_max < 2:
                break   # a 1-step trailer rides the next tick's flat
                        # step (no single-step scan compile class)
            seg = min(rem_cap, rem_max)
            if armed:
                seg = min(seg, self.lifecycle_quantum)
            seg = 1 << (seg.bit_length() - 1)
            if seg < 2:
                break
            if not self._kv_prepare_nopreempt(next_writes(rows, seg)):
                break   # page pressure resolves on the per-tick path

        # ---- single readback + chronological commit -------------------
        with self._span("readback", phase=True):
            # the scans' expert load comes out of the scans themselves:
            # nothing the span copies was launched after ``commits[-1]``
            self._device_wait([c[3:] if c[0] == "scan" else c[2]
                               for c in commits])
            ready = []
            for item in commits:
                if item[0] == "scan":
                    _, sg, pts2, toks, live, ecode = item
                    ready.append(("scan", sg, pts2, np.asarray(toks),
                                  np.asarray(live), np.asarray(ecode)))
                else:
                    _, req, token_ids, src = item
                    ready.append(("join", req,
                                  int(np.asarray(token_ids)[src])))
            expert_load = self._expert_load()
        prof.host_sync()
        codes: Dict[int, int] = {}
        # which program made each token the host now appends: the decode
        # scan, or a joiner's prompt feed spliced in by the join
        made = {"scan": 0, "join": 0}
        with self._span("commit") as sp:
            for item in ready:
                before = self.tokens_decoded
                if item[0] == "join":
                    _, req, tok = item
                    if req.status not in (RequestStatus.PREFILLING,
                                          RequestStatus.DECODING):
                        continue   # left its slot before commit: emission
                                   # is dead, the readmission recomputes it
                    if req.status is RequestStatus.PREFILLING:
                        req.status = RequestStatus.DECODING
                    self._append_token(req, tok)
                    self._maybe_finish(req)
                    made["join"] += self.tokens_decoded - before
                    continue
                _, sg, pts2, toks, live, ecode = item
                for s in range(sg):
                    for flat, rid in pts2:
                        req = self.requests[rid]
                        if (req.status is not RequestStatus.DECODING
                                or not live[s, flat]):
                            continue
                        self._append_token(req, int(toks[s, flat]))
                        self._maybe_finish(req)
                for flat, rid in pts2:
                    c = int(ecode[flat])
                    if c != EXIT_NOT_IN_BATCH:
                        codes[rid] = c   # the segment where the row ran last
                made["scan"] += self.tokens_decoded - before
            sp.set(scan_tokens=made["scan"], join_tokens=made["join"],
                   **expert_load)
        self.last_exit_codes = codes
        self.steps += total
        self.scan_runs += 1
        if prof.enabled:
            prof.note(decode_quantum=n, stretch_steps=total,
                      stretch_segments=n_segments,
                      stretch_joins=sum(c[0] == "join" for c in commits))

    def _stretch_join(self, bc, rows, sched, dev_seq, commits, eos):
        """Admit prompts INTO the stretch's batch (on-device continuous
        batching): fill free slots, then for every request that holds a
        slot but is not a row yet — taken just now at a segment boundary,
        or by the tick's admission before the first segment — feed its
        prompt asynchronously (:meth:`_stretch_prefill`, no readback) and
        splice its held first token into the batch via ``join_slot``: the
        device decodes it from the next segment on.  Page exhaustion or
        dispatch failure un-joins the request back to the queue; the
        per-tick path retries it with the full pressure machinery."""
        im = self.im
        if not self.admission_closed:
            with self._span("host_admit", phase=True) as admit:
                taken = self._fill_slots()
                if taken:
                    admit.set(state_reset=taken)
        seen = {req.rid for req, _ in rows}
        seen.update(c[1].rid for c in commits if c[0] == "join")
        stamped = []
        for req in self._active():
            if (req.status is not RequestStatus.PREFILLING
                    or req.rid in seen or len(rows) >= im.max_tokens):
                # (no flat-row capacity left: the leftover stays slotted
                # for a later boundary or the next tick)
                continue
            with self._span("join", rid=req.rid):
                bc = self._join_one(req, bc, rows, sched, dev_seq, commits,
                                    eos, stamped)
        if stamped:
            if self._join_stamp is not None:
                self._join_stamp(stamped)
            if self.telemetry.enabled:
                self.telemetry.metrics.counter("stretch_joins").inc(
                    len(stamped))
        return bc

    def _join_one(self, req, bc, rows, sched, dev_seq, commits, eos,
                  stamped):
        """One joiner of :meth:`_stretch_join`: feed its prompt, then
        splice it in.  Returns the batch the stretch goes on with."""
        live = sum(1 for r2, _ in rows
                   if r2.max_new_tokens - len(r2.generated) > sched[r2.rid])
        out = self._stretch_prefill(req, rows, dev_seq, live)
        if out is None:
            if (req.status is RequestStatus.PREFILLING
                    and req.slot >= 0
                    and self.slots[req.slot] == req.rid):
                self._unjoin(req)
            return bc
        tok_src, src = out
        stamped.append(req.rid)
        L = len(req.prefill_tokens)
        commits.append(("join", req, tok_src, src))
        if req.max_new_tokens - len(req.generated) <= 1:
            # the held token is the whole remaining budget: nothing
            # to decode — it completes at the stretch readback
            return bc
        dst = len(rows)
        bc = self.im.join_slot(bc, tok_src, src, dst, req.slot,
                               L, L + 1, dst + 1, eos=eos,
                               counts={"rows": dst + 1, "rid": req.rid})
        rows.append((req, dst))
        sched[req.rid] = 1
        dev_seq[req.rid] = L + 1
        return bc

    def _stretch_prefill(self, req, rows, dev_seq, live: int = 0):
        """Asynchronously feed one joining request's whole remaining
        prompt, results left on device, and return ``(tok_src, src_idx)``:
        a device array in ``join_slot``'s flat layout and where the
        joiner's first generated token sits in it — read back only at the
        stretch's single readback.  The prompt rides the tiled prefill
        scan (:meth:`_prefill_feed`, the wave path's cutter and dispatch)
        wherever :meth:`_tiled_feed` holds, flat chunks through ``im.step``
        otherwise.  None when the feed could not run (page-pool exhaustion
        before dispatch, or a dispatch failure after retries — the latter
        already requeued the request via the retry guard).  ``live``: the
        decode rows that wait behind this feed (a span argument)."""
        im = self.im
        feed = req.prefill_tokens
        L = len(feed)
        if not self._kv_prepare_nopreempt(
                [(req.rid, req.prefill_offset, L)]):
            return None
        # running rows' cache depths are their DEVICE depths (the chain is
        # ahead of the committed host view); only the joiner's own entry
        # is read by its feed
        depths = {r2.slot: dev_seq[r2.rid] for r2, _ in rows}
        if self._tiled_feed(req):
            fed = self._prefill_feed([req], depths, rows=live)
            if not fed or not fed[1]:
                return None   # failed, or nothing left to feed (cannot
                              # happen: the prefix cache keeps the last token)
            ((_, src, _),), outs = fed
            return outs[-1][2], src
        res = src = None
        while req.prefill_offset < L:
            start = req.prefill_offset
            take = min(im.max_tokens, L - start)
            done = start + take == L
            with self._span("host_prepare", phase=True):
                seq_lens = np.zeros(im.max_requests, np.int32)
                for slot, depth in depths.items():
                    seq_lens[slot] = depth
                seq_lens[req.slot] = start + take
                bc2 = BatchConfig.build(
                    list(feed[start: start + take]), [req.slot] * take,
                    list(range(start, start + take)), seq_lens,
                    max_tokens=im.max_tokens,
                    max_requests=im.max_requests)
            smp = (self._sample_for([(take - 1, req.rid)], im.max_tokens)
                   if done else None)
            self._prof_account([(req.rid, start, start + take)])
            cnt = self._launch_counts([(req.rid, start, start + take)], 0)
            out = self._guarded(
                "step", lambda b=bc2, s=smp, c=cnt: im.step(
                    b, sample=s, counts=c),
                affected_fn=lambda: [req.rid])
            if out is None:
                return None
            self._count_feed("flat", take)
            req.prefill_offset = start + take
            res, src = out.token_ids, take - 1
        if res is None:
            return None   # nothing left to feed (see above)
        return res, src

    def _unjoin(self, req) -> None:
        """Back a failed mid-stretch join out to the queue: release the
        slot (and its pages) and requeue at the head — the per-tick
        admission path re-admits it with preemption/page-pressure
        handling the stretch must not run."""
        self._release_slot(req)
        req.prefill_offset = 0
        req.status = (RequestStatus.PREEMPTED if req.preemptions
                      else RequestStatus.PENDING)
        self.pending.insert(0, req.rid)
        self._pending_since.setdefault(req.rid, self.steps)

    def _kv_prepare_nopreempt(self, spans, kv=None) -> bool:
        """Page preparation for a mid-stretch dispatch: the batch rows
        are live in a RUNNING chain, so pool pressure must NOT preempt
        (evicting a row the device is still decoding would corrupt its
        cache).  Returns False on exhaustion — the caller stops extending
        the stretch (or skips the join) and the per-tick path resolves
        the pressure with the full victim machinery."""
        kv = kv if kv is not None else self.im.kv
        if not kv.paged or not spans:
            return True
        from .kv_paged import PagePoolExhausted
        try:
            with self._span("kv_prepare"):
                for rid, lo, hi in spans:
                    kv.prepare_write(rid, lo, hi)
        except PagePoolExhausted:
            return False
        return True

    def _serve_tick(self) -> None:
        """One scheduling decision + dispatch of the incremental loop —
        every dispatch runs under the retry guard, so a transient fault
        degrades to requeue/reject of the affected requests instead of
        killing the loop:

        * every active request still has prompt to feed and rides the
          tiled feed: a prefill stretch (the wave);
        * someone decodes, and whoever else holds a slot can be spliced
          in (:meth:`_scan_steps_possible`): a decode stretch, which
          starts with the join of those prompts and admits later arrivals
          at its segment boundaries;
        * otherwise a single flat step: a 1-step trailer, or a mixed step
          where the tiled feed does not apply (see
          :meth:`prepare_next_batch`)."""
        tel, jr = self.telemetry, self.journal
        # ``pc_ns``: this clock (``perf_counter_ns``) at the tick's entry —
        # the one subtraction that lays perf_counter stamps (the serving
        # records, the ring) over a profiler session's time base, and the
        # journal's stamp of the tick: a record and its span join on it
        if self._prefill_stretch_possible():
            with tel.span("prefill_stretch", cat="serve", jr=jr,
                          pc_ns=jr.clock_ns()):
                self._prefill_stretch()
            return
        n = self._scan_steps_possible()
        if n > 1:
            with tel.span("decode_stretch", cat="serve", steps=n, jr=jr,
                          pc_ns=jr.clock_ns()):
                self._decode_stretch(n)
            return
        with tel.span("serve_step", cat="serve", jr=jr,
                      pc_ns=jr.clock_ns()):
            # prepare_next_batch attributes its own host_admit /
            # host_prepare phases
            bc, sample_points = self.prepare_next_batch()
            base = bc if isinstance(bc, BatchConfig) else bc.base
            with self._span("batch_sync"):
                # a device read: it waits for the batch's transfer
                n_fed = int(np.asarray(base.num_tokens))
            if n_fed == 0:
                # nothing slotted fed a token (admission closed during a
                # migration drain with only pending work): dispatching an
                # empty batch would burn a device step for nothing
                return
            gated = (isinstance(bc, PrefillBatchConfig)
                     and bc.logit_slots is not None)
            smp = self._sample_for(
                sample_points,
                self.im.max_requests if gated else self.im.max_tokens)
            result = self._guarded(
                "step", lambda: self.im.step(bc, sample=smp,
                                             counts=self._step_counts),
                affected_fn=lambda: self._rids_in_batch(bc))
            if result is not None:
                self.process_result(result, sample_points)
            self.steps += 1

    def _tick(self) -> None:
        """One unit of serving work between lifecycle checks — THE
        dispatch the serve loops (``serve_incr_decoding`` and
        ``serve_with_arrivals``) drive.  The incremental manager's tick is
        :meth:`_serve_tick`; :class:`~.spec_infer.SpecInferManager`
        overrides this with its spec-aware dispatch (a mixed speculative
        macro-step while any live request is in spec mode, the incremental
        fast path otherwise), which is what makes speculation compose with
        arrivals, deadlines, cancellation, and admission control with ONE
        lifecycle implementation."""
        self._serve_tick()

    def _kv_bind(self, rid: int) -> None:
        """Attribution hook when a request takes a slot (overridden by
        managers holding more than one deployment's caches — the spec
        manager binds the draft model's allocator too).

        Under a PAGED allocator (serve/kv_paged.py) this is also the
        prefix-reuse hook: bind() maps every registered prefix page the
        request's fed tokens match and returns the cached offset — the
        prefill resumes THERE, so a shared system prompt is prefilled
        once per fleet instead of once per request (TTFT collapses to the
        unshared suffix).  The cached offset is tile-aligned by
        construction (``align=prefill_tile``), preserving the tiled
        prefill path's contract (d).
        """
        kv = self.im.kv
        req = self.requests[rid]
        # the tile alignment only matters when the tiled Pallas prefill
        # path will consume the resumed offset; the flat gather path
        # accepts any start, so it keeps every matched token
        align = self.im.prefill_tile if self.im.use_pallas else 1
        info = kv.bind(rid, slot=req.slot, tokens=req.prefill_tokens,
                       need=self._seq_len_needed(req), align=align)
        if info is None:
            return
        cached = int(info.get("cached_tokens", 0))
        if cached:
            req.prefill_offset = cached
        # host-tier readmission: upload this rid's spilled pages onto the
        # freshly-bound row and resume the prefill at the restored write
        # frontier — recompute covers only the unrestored tail.  Prefix
        # hits already below the frontier cost nothing extra (restore
        # skips the span bind covered).
        restored = self._kv_restore(req, kv, align)
        if restored > cached:
            req.prefill_offset = restored
            req.kv_restored = True
        tel = self.telemetry
        if tel.enabled:
            if cached:
                tel.prefix_cache_hit(req.trace_id, tokens_reused=cached,
                                     pages=int(info.get("hit_pages", 0)))
            else:
                tel.prefix_cache_miss(req.trace_id)

    def _kv_spill(self, req: Request, kv) -> None:
        """Copy a victim's written pages to the host tier BEFORE its slot
        releases (every page-leaving path funnels through preempt()).
        Guarded by the retry policy at the ``kv_swap_out:<rid>`` chaos
        site; a fault schedule that exhausts the budget just skips the
        spill — the r9 recompute feed still covers recovery
        bit-identically, so a failed spill can never corrupt, only cost.
        """
        if kv.host_tier is None or req.slot < 0:
            return
        site = f"kv_swap_out:{req.rid}"
        tokens = list(req.prompt) + list(req.generated)
        pol = self.res.retry
        tel = self.telemetry
        attempt = 0
        while True:
            try:
                if self.injector is not None:
                    self.injector.maybe_fail(site)
                info = kv.spill(req.rid, tokens)
                break
            except TransientServeError as e:
                if tel.enabled:
                    tel.fault_observed(site, detail=str(e))
                if attempt >= pol.max_retries:
                    kv.drop_spill(req.rid)
                    return
                attempt += 1
                delay = pol.backoff(attempt)
                if tel.enabled:
                    tel.dispatch_retry(site, attempt=attempt,
                                       backoff_s=delay)
                if delay > 0:
                    self._sleep(delay)
        if info and tel.enabled:
            tel.kv_spilled(req.trace_id, pages=info["pages"],
                           nbytes=info["nbytes"], tokens=info["tokens"])

    def _kv_restore(self, req: Request, kv, align: int) -> int:
        """Upload ``req``'s spilled pages back after its readmission bind;
        returns the restored write frontier (0 = nothing restored — the
        recompute feed covers everything, bit-identically).  Guarded at
        the ``kv_swap_in:<rid>`` chaos site under the retry policy;
        :class:`~.kv_paged.HostTierCorruption` (checksum mismatch) is NOT
        retried — the host copy itself is damaged, so the entry drops and
        recompute takes over."""
        if kv.host_tier is None or not kv.has_spill(req.rid):
            return 0
        from .kv_paged import HostTierCorruption

        site = f"kv_swap_in:{req.rid}"
        pol = self.res.retry
        tel = self.telemetry
        attempt = 0
        while True:
            try:
                if self.injector is not None:
                    self.injector.maybe_fail(site)
                info = kv.restore(req.rid, align=align)
                break
            except HostTierCorruption as e:
                kv.drop_spill(req.rid)
                if tel.enabled:
                    tel.kv_restore_failed(req.trace_id, reason=str(e))
                return 0
            except TransientServeError as e:
                if tel.enabled:
                    tel.fault_observed(site, detail=str(e))
                if attempt >= pol.max_retries:
                    kv.drop_spill(req.rid)
                    if tel.enabled:
                        tel.kv_restore_failed(
                            req.trace_id,
                            reason=f"retry budget exhausted at {site}")
                    return 0
                attempt += 1
                delay = pol.backoff(attempt)
                if tel.enabled:
                    tel.dispatch_retry(site, attempt=attempt,
                                       backoff_s=delay)
                if delay > 0:
                    self._sleep(delay)
        if not info:
            return 0
        if tel.enabled:
            tel.kv_restored(req.trace_id, pages=info["pages"],
                            nbytes=info["nbytes"],
                            tokens_resumed=info["restored_tokens"],
                            tokens_saved=info["tokens_saved"])
        return int(info["restored_tokens"])

    def _kv_prepare(self, spans, kv=None) -> None:
        """Pre-dispatch page preparation for every (rid, lo, hi) cache
        write span the next dispatch will perform: the paged allocator
        maps missing pages and copy-on-writes shared ones HERE, so the
        block table is constant while the device works.  No-op for the
        slot-contiguous allocator.

        Pool exhaustion degrades like slot pressure does: with
        ``res.preemption`` on, the lowest-priority decoding victim is
        preempted — releasing its pages page-granularly — and the span
        retries; otherwise the exhaustion propagates (an admission gate
        sized with ``round_need`` prevents reaching it).
        """
        kv = kv if kv is not None else self.im.kv
        if not kv.paged or not spans:
            return
        from .kv_paged import PagePoolExhausted

        with self._span("kv_prepare"):
            for rid, lo, hi in spans:
                for _ in range(len(self.slots) + 1):
                    try:
                        kv.prepare_write(rid, lo, hi)
                        break
                    except PagePoolExhausted:
                        victim = self._page_pressure_victim(rid)
                        if victim is None:
                            raise
                        self.preempt(victim.rid)

    def _page_pressure_victim(self, needer_rid: int):
        """Lowest-priority DECODING request (newest first among equals,
        bounded by max_preemptions) whose priority is STRICTLY below the
        needer's — the same invariant the slot-pressure path enforces
        ("preemption only ever evicts strictly-lower priority"; a page
        shortfall must not priority-invert).  None when preemption is off
        or nothing admissible is evictable — the exhaustion then
        propagates."""
        if not self.res.preemption:
            return None
        need_pri = self.requests[needer_rid].priority
        victims = [r for r in self._active()
                   if r.status is RequestStatus.DECODING
                   and r.rid != needer_rid
                   and r.priority < need_pri
                   and r.preemptions < self.res.max_preemptions]
        if not victims:
            return None
        return min(victims, key=lambda r: (r.priority, -r.rid))

    def kv_snapshot(self) -> Dict:
        """The deployment's live KV view (pure read — see
        :meth:`KVAllocator.snapshot`); overridden by managers holding
        more than one deployment's caches (the spec manager returns the
        combined target+draft view its gauges publish)."""
        return self.im.kv.snapshot()

    def _sync_kv(self) -> None:
        """One per-tick snapshot of live cache depths into the allocator
        (per-request peaks, watermarks, occupancy/headroom/fragmentation
        gauges when telemetry is live) — host bookkeeping only."""
        self.im.kv.observe(
            {r.rid: r.seq_len for r in self._active()
             if r.status in (RequestStatus.PREFILLING,
                             RequestStatus.DECODING)},
            self.telemetry)

    def _maybe_check_health(self, force: bool = False) -> None:
        """Poll the attached plan-health monitor every
        ``health_check_every`` ticks (``force`` = loop drained: one final
        check so short runs still get evaluated exactly once)."""
        if self.plan_health is None:
            return
        self._health_ticks += 1
        if force or self._health_ticks % self.health_check_every == 0:
            self.plan_health.check()

    def apply_output_cap(self, rid: int, cap: int) -> bool:
        """Cap a live request's ``max_new_tokens`` (DEGRADE_BATCH): the
        committed stream stays a bit-identical PREFIX of the uncapped
        run.  A request already at/past the cap completes at this tick
        boundary with its committed tokens and an ``ok`` outcome.
        Returns whether the cap shortened the request."""
        req = self.requests.get(rid)
        if req is None or req.status in TERMINAL_STATUSES:
            return False
        new_max = max(int(cap), len(req.generated))
        if new_max >= req.max_new_tokens:
            return False
        req.max_new_tokens = new_max
        if req.status is RequestStatus.DECODING \
                and len(req.generated) >= req.max_new_tokens:
            self._maybe_finish(req)
        return True

    def _maybe_brownout(self) -> None:
        """Evaluate an attached BrownoutController every
        ``config.check_every`` serve ticks and apply the level's actions
        at this tick boundary (see serve/slo.py for the ladder).  Owned
        by the FLEET when serving under a FleetRouter (per-replica
        managers keep ``brownout`` None)."""
        bo = self.brownout
        if bo is None:
            return
        self._brownout_ticks += 1
        if self._brownout_ticks % bo.config.check_every:
            return
        slo = self.slo
        tel = self.telemetry
        kv = self.im.kv
        occ = (kv.live_tokens() / kv.capacity_tokens
               if kv.capacity_tokens else 0.0)
        depths: Dict[str, int] = {c: 0 for c in slo.classes}
        lc_depth = 0
        for rid in self.pending:
            req = self.requests[rid]
            cls = slo.resolve(req.slo_class)
            if cls is None:
                continue
            depths[cls.name] = depths.get(cls.name, 0) + 1
            if not cls.degradable:
                lc_depth += 1
        if tel.enabled:
            tel.lane_depths(depths)
        bo.evaluate(lc_queue_depth=lc_depth, kv_occupancy_frac=occ)
        if bo.level == 0:
            return
        # --- apply the level's actions (idempotent per window) ---------
        deferred: Dict[str, int] = {}
        for rid in list(self.pending):
            req = self.requests[rid]
            if req.status in TERMINAL_STATUSES:
                continue
            if bo.sheds_queued(req.slo_class):
                if tel.enabled:
                    tel.lane_shed(req.slo_class, trace_id=req.trace_id,
                                  reason=f"brownout:{bo.level.name}")
                self._terminate(req, RequestStatus.REJECTED)
            elif self._held(req):
                req.deferred_ticks += 1
                deferred[req.slo_class] = deferred.get(req.slo_class, 0) + 1
        if tel.enabled:
            for cname, cnt in deferred.items():
                tel.lane_deferred(cname, count=cnt)
        # --- SPILL: the rung between DEFER and DEGRADE -----------------
        # before capping or shedding anything, push degradable decoding
        # requests' pages to the host tier while KV pressure holds — each
        # preempt() below spills first (tier attached), so the freed
        # pages cost a swap on readmission, not a recompute, and the
        # bit-identical-prefix contract is untouched (preemption already
        # carries it).  Only fires with a tier attached and real page
        # pressure; the level walk/hysteresis pins stay as they are
        # because SPILL is an action DEFER_BATCH and above carry, not a
        # new enum member (fleet.py hardcodes level comparisons).
        if (kv.host_tier is not None
                and occ >= bo.config.kv_pressure_frac):
            victims = [r for r in self._active()
                       if r.status is RequestStatus.DECODING
                       and bo.spills(r.slo_class)
                       and r.preemptions < self.res.max_preemptions]
            victims.sort(key=lambda r: (r.priority, -r.rid))
            cap_toks = max(kv.capacity_tokens, 1)
            for req in victims:
                if kv.live_tokens() / cap_toks < bo.config.kv_pressure_frac:
                    break
                self.preempt(req.rid)
        for req in list(self._active()):
            if bo.sheds_live(req.slo_class):
                # CRITICAL_ONLY: evict and shed even slotted degradable
                # work — explicit REJECTED (committed tokens stay on the
                # record), never FAILED
                self._release_slot(req)
                if tel.enabled:
                    tel.lane_shed(req.slo_class, trace_id=req.trace_id,
                                  reason="brownout:CRITICAL_ONLY")
                self._terminate(req, RequestStatus.REJECTED)
            elif bo.degrades(req.slo_class):
                changed = False
                if req.spec:
                    # the r14 runtime flip: spec off for degraded lanes
                    changed = self.set_spec_mode(req.rid, False) or changed
                cap = bo.output_cap(req.slo_class)
                if cap is not None:
                    changed = self.apply_output_cap(req.rid, cap) or changed
                if changed and tel.enabled:
                    tel.lane_degraded(req.slo_class)

    def _maybe_migrate(self, idle: bool = False):
        """Tick-boundary slot for an attached
        :class:`~flexflow_tpu.serve.migration.MigrationController`:
        returns the SUCCESSOR manager when a live plan switch completed
        at this boundary (the serve loops hand off to it mid-run), else
        None.  ``idle`` = the loop has no work — a staged migration
        executes immediately there (the zero-preemption window)."""
        if self.migration is None:
            return None
        new_rm = self.migration.tick(self, idle=idle)
        return new_rm if new_rm is not None and new_rm is not self else None

    def trace_run_meta(self) -> Dict:
        """Provenance header a traffic trace (obs/replay.py) records for
        this deployment: what a ReplayHarness needs to rebuild an
        IDENTICAL run — the full gen config (sampling seed included),
        the plan key + engine shape, the fault-injector schedule, and
        the SLO-policy snapshot.  Subclasses extend (SpecInferManager
        adds its draft-tree shape)."""
        from ..obs.replay import engine_shape_of, injector_meta

        meta: Dict = {
            "driver": type(self).__name__,
            "gen": dataclasses.asdict(self.gen),
            "plan": engine_shape_of(self.im),
            "fault": injector_meta(self.injector),
        }
        if self.slo is not None and hasattr(self.slo, "snapshot"):
            meta["slo"] = self.slo.snapshot()
        return meta

    def serve_with_arrivals(self, arrivals, clock=None, record_trace=None,
                            _t0=None, _records=None, _open=None):
        """Arrival-driven serving: requests join the running admit/retire
        loop at their offered times (open-loop load; the loop every cell
        of ``benchmark/`` is driven through).

        ``arrivals``: iterable of ``(t_offset_s, prompt_tokens,
        max_new_tokens_or_None)`` — offsets from loop start; admitted once
        the clock passes them.  An optional 4th element is an options dict
        forwarded to :meth:`register_new_request` (``priority``, ``ttl_s``,
        ``deadline_s``, ``spec`` — per-request speculation mode under a
        SpecInferManager).  ``clock``: 0-arg seconds callable (injectable for
        hermetic tests; default ``time.perf_counter``); it also drives the
        deadline/TTL checks for the loop's duration.  A decode stretch
        admits arrivals into the RUNNING scan at segment boundaries
        (on-device continuous batching, see :meth:`_decode_stretch`), so
        outstanding arrivals do not cap it; cancellations and deadlines
        land at segment-boundary granularity.

        Returns ``{rid: record}`` with ``arrival_s``, ``first_token_s``
        (host-visible TTFT stamp), ``finish_s``, ``prompt_len``,
        ``trace_id``, ``tokens``, a terminal ``outcome``
        (``ok|cancelled|timeout|rejected|failed``), and the TTFT
        decomposition ``queue_wait_s`` / ``prefill_s``: ``prefill_start_s``
        is stamped at the start of the step in which the request's FIRST
        prefill token was fed to the device, so queue wait (arrival ->
        prefill actually starting: pending queue + slot wait + tiled-budget
        starvation) is reported separately from prefill compute
        (``queue_wait_s + prefill_s == first_token_s - arrival_s`` for
        ``ok`` requests).  The decomposition and outcome are ALWAYS
        emitted, including for requests that never produce a first token
        (cancelled, rejected, timed out, ``max_new_tokens=0``) — their
        ``prefill_s`` measures up to the terminal stamp instead.  All
        stamps are host-visible at step-boundary granularity.  Per-request
        outputs are INVARIANT to arrival timing (continuous batching only
        reorders work, never results), pinned by
        tests/test_serving_under_load.py.

        ``record_trace`` (a :class:`~flexflow_tpu.obs.replay.
        TrafficTraceRecorder`) captures this run as a versioned trace
        artifact: run provenance (gen/sampling seeds, plan key, fault
        schedule) on entry, every offered arrival at admit time, and
        every finished record at the tail — capture is append-only host
        bookkeeping that never reads this loop's clock, so a recorded
        run is bit-identical to an unrecorded one.

        ``_t0``/``_records``/``_open`` are the live-migration continuation
        (serve/migration.py): when a plan switch completes mid-loop, the
        SUCCESSOR manager re-enters this method with the remaining
        arrivals and the accumulated records/open set on the ORIGINAL
        time base, so one arrival session spans managers seamlessly.
        """
        import time as _time

        caller_clock = clock or _time.perf_counter

        def clock():
            # a caller's clock may be a hook that does work of its own
            # (the benchmark's walks every live request): named, so that
            # the journal and a trace do not hold it as unattributed
            with self._span("loop_clock"):
                return caller_clock()

        t0 = clock() if _t0 is None else _t0
        if record_trace is not None:
            # idempotent: a migration successor re-entering this loop
            # appends its plan provenance as a continuation, not a new
            # header
            record_trace.begin_run(self.trace_run_meta())
        pending = sorted(arrivals, key=lambda a: a[0])
        records: Dict[int, Dict] = {} if _records is None else _records
        # (the deadline checks read the caller's clock itself)
        saved_clock = self._swap_clock(caller_clock)  # rebases armed ones
        tel = self.telemetry

        # rids whose record still awaits a stamp — scanned per tick instead
        # of the full (mostly-terminal) records history, so per-step host
        # work stays O(live) over long sessions (same contract as
        # _check_lifecycle)
        open_rids: set = set() if _open is None else _open

        def admit_due():
            now = clock() - t0
            while pending and pending[0][0] <= now:
                off, prompt, mnt, *rest = pending.pop(0)
                if record_trace is not None:
                    # the RAW options element (not the parsed form), so
                    # a malformed dict replays its rejection identically
                    record_trace.record_arrival(
                        off, prompt, mnt, rest[0] if rest else None)
                # malformed arrivals — bad prompt shapes AND bad options
                # dicts — register as REJECTED records instead of raising
                # out of (and killing) the serve loop
                opts, reject = parse_arrival_options(rest)
                rid = self.register_new_request(
                    prompt, mnt, reject_invalid=True,
                    reject_reason=reject, **opts)
                records[rid] = {"arrival_s": off, "admitted_s": now,
                                "prompt_len": len(prompt),
                                "trace_id": self.requests[rid].trace_id}
                open_rids.add(rid)
            return clock() - t0

        def prefill_starters():
            # requests whose first prefill token may enter the device in
            # the NEXT step: stamped with the step's start time if it does
            # (admission itself can also happen inside the step)
            return [rid for rid in open_rids
                    if "prefill_start_s" not in records[rid]
                    and self.requests[rid].prefill_offset == 0
                    and self.requests[rid].status not in TERMINAL_STATUSES]

        def stamp(now):
            for rid in list(open_rids):
                rec = records[rid]
                req = self.requests[rid]
                if "first_token_s" not in rec and req.generated:
                    rec["first_token_s"] = now
                if ("finish_s" not in rec
                        and req.status in TERMINAL_STATUSES):
                    rec["finish_s"] = now
                if "finish_s" in rec:
                    open_rids.discard(rid)

        def continue_on(new_rm):
            # live migration completed at this boundary: the successor
            # carries every request (rids preserved) — it re-enters this
            # loop with the remaining arrivals on the original time base
            self.journal.end()   # this manager's part of the loop
            return new_rm.serve_with_arrivals(
                pending, clock=caller_clock, record_trace=record_trace,
                _t0=t0, _records=records, _open=open_rids)

        def stamp_joined(rids):
            # mid-stretch joiners started (and usually finished) prefill
            # INSIDE the tick: stamp prefill_start_s at join time, same
            # step-boundary clock the per-tick starters path uses
            now2 = clock() - t0
            for rid in rids:
                rec = records.get(rid)
                if rec is not None and "prefill_start_s" not in rec:
                    rec["prefill_start_s"] = now2
                    if tel.enabled:
                        tel.request_prefill_started(
                            self.requests[rid].trace_id)

        try:
            # the decode stretch pulls newly-due arrivals in at segment
            # boundaries itself (and stamps joiners' records)
            self._arrival_pump = admit_due
            self._join_stamp = stamp_joined
            while pending or self.has_work():
                # the loop's own work on either side of the tick (and the
                # caller's, inside its clock): device-idle time here is
                # the arrival loop's, not the scheduler's
                with self._span("loop_arrivals"):
                    now = admit_due()
                    self._check_lifecycle()
                    stamp(clock() - t0)
                    work = self.has_work()
                    starters = prefill_starters() if work else []
                if not work:
                    new_rm = self._maybe_migrate(idle=True)
                    if new_rm is not None:
                        return continue_on(new_rm)
                    # idle until the next arrival: a short bounded sleep for
                    # ANY clock — real clocks stop busy-spinning, virtual
                    # clocks (which advance per call) lose at most ~1ms of
                    # wall time per idle poll
                    if pending:
                        with self._span("loop_idle"):
                            _time.sleep(min(1e-3, max(0.0,
                                                      pending[0][0] - now)))
                    continue
                self._tick_begin()
                self._tick()
                self.profiler.tick_end()
                with self._span("loop_bookkeep"):
                    self._sync_kv()
                    self._maybe_check_health()
                    self._maybe_brownout()
                    for rid in starters:
                        # a mid-stretch join already stamped (and
                        # telemetered) its own prefill start — don't
                        # re-stamp it here
                        if (self.requests[rid].prefill_offset > 0
                                and "prefill_start_s" not in records[rid]):
                            records[rid]["prefill_start_s"] = now
                            if tel.enabled:
                                tel.request_prefill_started(
                                    self.requests[rid].trace_id)
                    stamp(clock() - t0)
                    new_rm = self._maybe_migrate()
                if new_rm is not None:
                    return continue_on(new_rm)
            self._maybe_check_health(force=True)
        finally:
            self._arrival_pump = None
            self._join_stamp = None
            self._swap_clock(saved_clock)
            self.journal.end()
        end = caller_clock() - t0
        for rid, rec in records.items():
            req = self.requests[rid]
            rec["tokens"] = req.generated
            rec["outcome"] = req.outcome or OUTCOMES.get(req.status, "ok")
            # SLO-class lanes (serve/slo.py): the lane the request rode
            # and how many brownout windows it spent queue-held — the
            # per-class report breakdown keys on these
            if req.slo_class:
                rec["slo_class"] = req.slo_class
            if req.deferred_ticks:
                rec["deferred_ticks"] = req.deferred_ticks
            # byte-side attribution: peak committed-KV this request held
            # (0.0 for rejected/never-slotted requests)
            rec["kv_bytes"] = req.kv_bytes
            # deterministic per-request work counters (obs/profiler.py):
            # flops / kv_bytes_touched / dispatches — device-free fields
            # the under-load summary totals and obs.report.compare guards
            if self.profiler.enabled:
                rec["work"] = self.profiler.request_work(rid)
            # ALWAYS emit the TTFT decomposition: queue wait runs from
            # arrival to prefill start (falling back to registration, then
            # arrival, when prefill never began); prefill runs from there
            # to the first token (falling back to the terminal stamp)
            start = rec.get("prefill_start_s",
                            rec.get("admitted_s", rec["arrival_s"]))
            stop = rec.get("first_token_s", rec.get("finish_s", end))
            rec["queue_wait_s"] = max(start - rec["arrival_s"], 0.0)
            rec["prefill_s"] = max(stop - start, 0.0)
        if record_trace is not None:
            # only the FINAL manager of a migration chain reaches this
            # tail (intermediate callers return via continue_on above),
            # so the artifact finalizes exactly once, with every record
            record_trace.finalize(records)
        return records

    def serve_incr_decoding(self) -> Dict[int, List[int]]:
        """Run the incremental-decoding loop until all requests reach a
        terminal state.

        Reference: ``RequestManager::serve_incr_decoding`` — but the pure-
        decode stretches run as ONE on-device ``lax.scan`` (EOS-masked), so
        the host sync amortizes over up to ``scan_chunk`` tokens;
        the per-step host path only handles admission/prefill boundaries.
        Cancellations and deadline expiries are reaped at every step
        boundary; transient dispatch faults retry-with-backoff and degrade
        to requeue/fail of only the affected requests.  An attached
        MigrationController (serve/migration.py) can swap the executing
        plan at any tick boundary — the loop hands off to the successor
        manager, which carries every request under its original rid.
        """
        try:
            while True:
                self._check_lifecycle()
                if not self.has_work():
                    new_rm = self._maybe_migrate(idle=True)
                    break
                self._tick_begin()
                self._tick()
                self.profiler.tick_end()
                self._sync_kv()
                self._maybe_check_health()
                self._maybe_brownout()
                new_rm = self._maybe_migrate()
                if new_rm is not None:
                    break
        finally:
            self.journal.end()   # the last record; the slow-tick report
        if new_rm is not None:
            return new_rm.serve_incr_decoding()
        self._maybe_check_health(force=True)
        return {rid: r.generated for rid, r in self.requests.items()}

    _serve = serve_incr_decoding  # overridden by SpecInferManager

    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        max_new_tokens: Optional[int] = None,
    ) -> List[List[int]]:
        rids = [
            self.register_new_request(p, max_new_tokens) for p in prompts
        ]
        from ..utils.profiling import maybe_profile
        from ..utils.runlog import log_run

        profiling = bool(getattr(self.im.model.config, "profiling", False))
        import time as _time

        # snapshot the lifetime counters so the record is per-call deltas
        tok0, step0, scan0 = self.tokens_decoded, self.steps, self.scan_runs
        t0 = _time.perf_counter()
        with maybe_profile(profiling):
            out = self._serve()
        log_run("serve", {
            "manager": type(self).__name__,
            "requests": len(rids),
            "tokens": self.tokens_decoded - tok0,
            "steps": self.steps - step0,
            "scan_runs": self.scan_runs - scan0,
            "seconds": round(_time.perf_counter() - t0, 3),
        })
        return [out[rid] for rid in rids]
