"""Step-level cost attribution: per-phase time budgets + deterministic
work counters for the serving stack.

The calibration loop (obs/calibration.py, r8/r10) reconciles predicted vs
measured at WHOLE-PLAN granularity — one ``tpot_ms`` scalar per plan key —
so when prediction error appears it cannot say whether attention, the LM
head, the ICI hop, or host overhead is mispriced.  This module is the
decomposed half:

* :class:`StepProfiler` — one handle threaded through the managers
  exactly like :class:`~flexflow_tpu.obs.telemetry.Telemetry`
  (``RequestManager(..., profiler=StepProfiler())``; the manager syncs it
  onto the InferenceManager / every pipeline stage).  It

  - **times each serve tick's phases** on the injectable clock:
    admission/slot-fill/arrival parsing (``host_admit``), host batch
    preparation (``host_prepare``), jit dispatch (``dispatch``;
    per-stage ``stage{i}`` under pp), the inter-stage activation hop
    (``hop``), and the sample readback (``readback``) — the host-side
    time-budget decomposition of a tick.  Each phase is the ``prof=``
    consumer of the ONE span entered at that boundary
    (``telemetry.span(name, prof=profiler)``, obs/trace.py ``Span``):
    ``phase_s`` is filled at the span's exit, and leaving a launch span
    (``dispatch`` / ``stage{i}``) counts one ``dispatches``;
  - **accumulates deterministic work counters** per tick and per request
    (:data:`WORK_COUNTERS`): flops executed, HBM bytes read/written, KV
    bytes touched, dispatch count, jit-recompile count, host-device
    syncs, pages mapped / copy-on-written.  "Deterministic" means the
    numbers are computed from host bookkeeping (token counts, batch
    shapes, the compiled plan) via the SAME arithmetic the serve search
    already prices with (``simulator._step_flops`` / ``Linear.flops`` /
    ``_step_param_bytes`` / the KVAllocator's ``bytes_per_token``), so
    two runs of the same workload produce identical counters with no
    device attached — the basis of :func:`~flexflow_tpu.obs.report.
    compare`'s exact class.

* :class:`PlanCostCard` — the per-deployment constants that accounting
  uses, derived once per compiled plan (per stage under pp) from the
  plan's own sharded cost arithmetic.

**Deterministic accounting model** (the contract tests/test_profiler.py
cross-checks against ``Linear.flops`` / ``plan_memory_parts``):

* ``flops`` — fed tokens × (attention + mlp per-token flops at the
  compiled batch shape) + logit rows × per-row LM-head flops;
* ``hbm_bytes_read`` — model passes × streamed weight bytes (each scan
  step / micro-batch pass re-reads the weights) + KV read bytes;
* ``hbm_bytes_written`` — fed tokens × KV bytes/token (the committed
  cache write);
* ``kv_bytes_touched`` — KV read + written bytes, where a token at cache
  depth ``d`` reads the ``d``-deep causally-live prefix (a decode
  stretch of ``n`` steps starting at depth ``s`` reads
  ``n*s + n*(n-1)/2`` positions per row);
* ``dispatches`` — host program launches (per stage per micro-batch
  under pp); per-request ``dispatches`` counts the model passes whose
  batch carried the request's tokens;
* ``recompiles_total`` — backend compiles of the registered deployments'
  programs since registration, counted from the journal's build log
  (obs/journal.py ``BuildLog``: what JAX itself reports, by program
  name; a silent steady-state recompile is the most likely invisible
  perf bug);
* ``host_syncs`` — device→host result materializations (multi-step
  decode must perform exactly ONE, the final readback — the r7 "never
  host-syncs" claim, now a pinned counter);
* ``pages_mapped`` / ``pages_cow`` — the paged allocator's cumulative
  page-table activity (serve/kv_paged.py).

**Host-side only, guaranteed.**  Nothing here is ever traced into a
jitted program and no hook reads a device value, so serve outputs are
bit-identical with the profiler on or off — pinned across
step/generate/arrivals/pp2/int8/paged/spec/migration by
tests/test_profiler.py, the same contract telemetry carries.

The per-component TIME vocabulary (:data:`COMPONENTS` →
``attention_ms``/``mlp_ms``/``lm_head_ms``/``kv_stream_ms``/``comms_ms``/
``hop_ms``/``host_overhead_ms``) is shared with the serve search's
decomposed pricing (``search.serve_search.pp_serve_cost`` returns the
same fields; ``search_serve_plan`` records them into the calibration
ledger and consults the store's component-level ``suggested_scale``
entries when re-pricing), so the CalibrationLedger reconciles
predicted-vs-executed PER COMPONENT and a mispriced hop corrects only
the hop.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

from .journal import build_log
from .trace import Span

# the per-component time vocabulary: calibration-ledger field names are
# f"{component}_ms" (TIME_COMPONENT_FIELDS).  pp_serve_cost EMITS this
# decomposition, search_serve_plan records/consults it, the profiler's
# report and trace_report's time-budget section render it — one tuple, so
# a renamed component cannot drift between the pricing and the report.
COMPONENTS = ("attention", "mlp", "lm_head", "kv_stream", "comms", "hop",
              "host_overhead")
TIME_COMPONENT_FIELDS = tuple(f"{c}_ms" for c in COMPONENTS)

# the deterministic work-counter vocabulary (see the accounting model in
# the module docstring).  report.py folds these into the under-load /
# time-budget sections and obs.report.compare treats every field
# with one of these names as an exact-by-default regression guard.
WORK_COUNTERS = (
    "flops", "hbm_bytes_read", "hbm_bytes_written", "kv_bytes_touched",
    "dispatches", "recompiles_total", "host_syncs",
    "pages_mapped", "pages_cow",
)

# per-request attribution subset (stamped into serve_with_arrivals
# records — obs.report.compare gets deterministic per-run fields
# even with no device attached)
REQUEST_WORK_COUNTERS = ("flops", "kv_bytes_touched", "dispatches")

@dataclasses.dataclass
class PlanCostCard:
    """Per-deployment accounting constants, derived from the compiled
    plan(s) with the serve search's own arithmetic:

    * ``attn_flops_per_token`` / ``mlp_flops_per_token`` — per-device
      flops per fed token at the compiled batch shape
      (``simulator._step_flops`` over the plan steps, divided by the
      graph's flat token-batch rows);
    * ``lm_head_flops_per_row`` — per LOGIT ROW (the gated-prefill unit;
      ``Linear.flops``'s ``cost_logit_rows`` discount is the same
      arithmetic);
    * ``weight_bytes`` — per-device weight bytes one model pass streams
      (summed across pp stages: a pass traverses every stage);
    * ``kv_bytes_per_token`` — the allocator's committed-KV price (int8
      scales + lane padding included — the admission gate's number);
      falls back to the plan's registered-state arithmetic before the
      caches are allocated;
    * ``routed_weight_bytes`` — the part of ``weight_bytes`` that is the
      routed experts' matrices (``routed_experts`` held per layer, each
      row choosing ``routed_top_k``): a pass streams only the experts its
      rows visit, at most ``rows x top_k`` of them a layer
      (:meth:`weight_bytes_for`); 0 for a graph without routed layers.
    """

    attn_flops_per_token: float = 0.0
    mlp_flops_per_token: float = 0.0
    lm_head_flops_per_row: float = 0.0
    weight_bytes: float = 0.0
    kv_bytes_per_token: float = 0.0
    routed_weight_bytes: float = 0.0
    routed_experts: int = 0
    routed_top_k: int = 0

    def weight_bytes_for(self, rows_per_pass: float) -> float:
        """Weight bytes ONE pass over ``rows_per_pass`` rows streams: all
        of the dense weights, and of the held experts' as many as the
        rows can visit (an upper bound: every pair on another expert)."""
        if not self.routed_experts:
            return self.weight_bytes
        visited = min(self.routed_experts, rows_per_pass * self.routed_top_k)
        return self.weight_bytes - self.routed_weight_bytes * (
            1.0 - visited / self.routed_experts)

    def flops_for(self, n_tokens: int, logit_rows: int) -> float:
        return (n_tokens * (self.attn_flops_per_token
                            + self.mlp_flops_per_token)
                + logit_rows * self.lm_head_flops_per_row)


def plan_cost_card(im) -> PlanCostCard:
    """Build a :class:`PlanCostCard` for an InferenceManager-like object
    (``im.plan`` or ``im.stage_plans``) — the ONE place the profiler's
    deterministic counters read their constants, and it reads them from
    the same ``_step_flops``/``_step_param_bytes`` the serve search
    prices with (a counter that disagreed with the search's arithmetic
    would make the reconciliation circular)."""
    from ..search.simulator import (
        HEAVY_OPS,
        _step_flops,
        _step_param_bytes,
        serve_component_of,
    )

    plans = list(getattr(im, "stage_plans", None) or [im.plan])
    rows = int(getattr(im, "max_tokens", 0)) or 1
    attn_fl = mlp_fl = lm_fl = 0.0
    lm_rows = 0
    w_bytes = routed_bytes = 0.0
    routed_experts = routed_top_k = 0
    for plan in plans:
        mesh = plan.mesh
        for step in plan.steps:
            if step.is_parallel:
                continue
            op = step.node.op
            w_bytes += _step_param_bytes(step, plan, mesh)
            if op.type_name == "moe_experts":
                routed_bytes += _step_param_bytes(step, plan, mesh)
                routed_experts = op.num_held
            elif op.type_name == "moe_router":
                routed_top_k = op.top_k
            if op.type_name not in HEAVY_OPS:
                continue
            fl = _step_flops(step, mesh)
            # ONE classifier shared with pp_serve_cost's decomposition
            # (simulator.serve_component_of) — the counters and the
            # pricing may never disagree on an op's family
            fam = serve_component_of(op)
            if fam == "attention":
                attn_fl += fl
            elif fam == "lm_head":
                lm_fl += fl
                lm_rows = min(rows, int(op.cost_logit_rows)) or 1
            else:
                mlp_fl += fl
    kv_bpt = 0.0
    kv = getattr(im, "kv", None)
    if kv is not None:
        kv_bpt = kv.bytes_per_token() or 0.0
    if not kv_bpt:
        # caches unallocated: the plan's registered serve-state buffers
        # over the row x seq capacity (unpadded — the model-side price)
        from ..search.simulator import step_state_bytes

        state = sum(
            step_state_bytes(step, plan.mesh)
            for plan in plans for step in plan.steps if not step.is_parallel
        )
        cap = (getattr(im, "max_requests", 0)
               * getattr(im, "max_seq_len", 0)) or 1
        kv_bpt = state / cap
    return PlanCostCard(
        attn_flops_per_token=attn_fl / rows,
        mlp_flops_per_token=mlp_fl / rows,
        lm_head_flops_per_row=(lm_fl / lm_rows) if lm_rows else 0.0,
        weight_bytes=w_bytes,
        kv_bytes_per_token=kv_bpt,
        routed_weight_bytes=routed_bytes,
        routed_experts=routed_experts,
        routed_top_k=routed_top_k,
    )


class StepProfiler:
    """See the module docstring.  One instance per serving session;
    shared by the RequestManager and its InferenceManager(s) like the
    Telemetry handle (and carried across a live plan migration, so one
    rid space keeps one attribution table)."""

    enabled = True

    def __init__(self, clock: Optional[Callable[[], float]] = None):
        self._clock = clock or time.perf_counter
        self.phase_s: Dict[str, float] = {}
        self.phase_counts: Dict[str, int] = {}
        self.work: Dict[str, float] = {k: 0 for k in WORK_COUNTERS}
        # rid -> {flops, kv_bytes_touched, dispatches}
        self.per_request: Dict[int, Dict[str, float]] = {}
        self.ticks = 0
        self.last_tick: Dict = {}
        self.telemetry = None   # bound via bind(); step_profile instants
        # the installed deployments' programs, by the name the build log
        # files their compiles under (names only: a retired deployment's
        # callables and buffers are not held), and how far into the log
        # ``recompiles`` has counted
        self._programs: set = set()
        self._builds_seen = build_log().emitted
        self._installed: set = set()
        # paged allocators polled for cumulative page activity:
        # id(im) -> (kv, {counter: last_seen})
        self._paged: Dict[int, Tuple[object, Dict[str, int]]] = {}
        self._cards: Dict[int, PlanCostCard] = {}
        self._tick_mark: Optional[Dict] = None
        # scheduling annotations for the CURRENT tick (note()): merged
        # into the tick's step_profile instant and last_tick, then cleared
        self._tick_notes: Dict[str, float] = {}

    # ---- wiring -------------------------------------------------------
    def bind(self, telemetry) -> None:
        """Attach a Telemetry handle: the export grows a ``profile``
        JSONL line, each tick emits a ``step_profile`` instant, and the
        ``recompiles_total`` gauge lands in the metrics registry."""
        if telemetry is not None and getattr(telemetry, "enabled", False):
            self.telemetry = telemetry
            telemetry.profiler = self

    def install(self, im) -> None:
        """Register a deployment: its jitted step programs' names join
        the recompile count and its paged allocator (if any) the page
        poll.  Idempotent per ``im``; called by the RequestManager when
        the handle is synced (and again by a migration's successor)."""
        key = id(im)
        if key in self._installed:
            return
        self._installed.add(key)
        self.recompiles()   # what was built before now is not this one's
        fns = [getattr(im, name, None)
               for name in ("_step", "_scan", "_pscan", "_advance", "_join")]
        fns += [getattr(stage, "step", None)
                for stage in getattr(im, "stages", None) or []]
        self._programs.update(fn.__name__ for fn in fns
                              if hasattr(fn, "lower"))
        kv = getattr(im, "kv", None)
        if kv is not None and getattr(kv, "paged", False):
            # baseline NOW (registration), so page activity from the very
            # first tick counts — only pre-existing history is excluded
            self._paged[key] = (kv, {
                "pages_mapped": int(getattr(kv, "pages_mapped", 0)),
                "pages_cow": int(getattr(kv, "cow_copies", 0))})

    def uninstall(self, im) -> None:
        """Release a RETIRED deployment (live-migration incumbent
        teardown): its cost card / page poll entries drop, so a
        long-migrating session does not pin every retired manager (and
        its buffers) alive.  The compiles it performed stay counted (the
        counter is monotonic), and its programs' NAMES stay registered:
        the successor's are the same."""
        key = id(im)
        self._installed.discard(key)
        self._cards.pop(key, None)
        self._paged.pop(key, None)

    def card_for(self, im) -> PlanCostCard:
        """The deployment's accounting constants, built lazily once per
        ``im`` (the KV byte price needs allocated caches to include the
        real padding/scale planes)."""
        key = id(im)
        card = self._cards.get(key)
        if card is None:
            card = self._cards[key] = plan_cost_card(im)
        return card

    # ---- phase timing -------------------------------------------------
    def phase(self, name: str) -> Span:
        """A span whose only consumer is this profiler; instrumented code
        enters ``telemetry.span(name, prof=profiler)`` instead, which
        feeds the ring and ``phase_s`` through one ``with``."""
        return Span(name, prof=self)

    def _phase_done(self, name: str, dt: float) -> None:
        self.phase_s[name] = self.phase_s.get(name, 0.0) + dt
        self.phase_counts[name] = self.phase_counts.get(name, 0) + 1
        # a launch span (``dispatch``; ``stage<i>`` under pp) is one host
        # program launch: counted where it is timed
        if name == "dispatch" or name.startswith("stage"):
            self.work["dispatches"] += 1

    # ---- deterministic counters ---------------------------------------
    def count(self, name: str, n: float = 1) -> None:
        self.work[name] = self.work.get(name, 0) + n

    def host_sync(self, n: int = 1) -> None:
        """One device→host result materialization (np.asarray of a
        dispatch's output)."""
        self.work["host_syncs"] += n

    def note(self, **kw) -> None:
        """Stamp scheduling decisions into the CURRENT tick's
        ``step_profile`` record (e.g. ``decode_quantum`` — the stretch
        length the scheduler chose — or ``stretch_segments`` /
        ``stretch_joins``).  Values must be JSON-scalar; keys are merged
        into the tick instant at ``tick_end`` and cleared per tick, so
        they never accumulate across ticks."""
        self._tick_notes.update(kw)

    def account(self, card: PlanCostCard,
                rows: Sequence[Tuple[int, int, int]],
                passes: int = 1,
                logit_rows: Optional[int] = None) -> None:
        """Fold one dispatch group's deterministic work in.

        ``rows``: ``[(rid, n_tokens_fed, kv_read_tokens)]`` — per-request
        host bookkeeping (see the module docstring's accounting model).
        ``passes``: model passes this group executes (a decode scan of n
        steps streams the weights n times and includes every row n
        times).  ``logit_rows``: logit rows materialized (gated prefill:
        the sample points; everything else: the fed tokens).
        """
        if not rows:
            return
        total = sum(n for _, n, _ in rows)
        if total <= 0:
            return
        lr = total if logit_rows is None else logit_rows
        flops = card.flops_for(total, lr)
        read_tokens = sum(r for _, _, r in rows)
        kv_w = total * card.kv_bytes_per_token
        kv_r = read_tokens * card.kv_bytes_per_token
        w = self.work
        w["flops"] += flops
        w["hbm_bytes_read"] += passes * card.weight_bytes_for(
            total / max(passes, 1)) + kv_r
        w["hbm_bytes_written"] += kv_w
        w["kv_bytes_touched"] += kv_r + kv_w
        per_tok = (card.attn_flops_per_token + card.mlp_flops_per_token
                   + (lr / total) * card.lm_head_flops_per_row)
        for rid, n, r in rows:
            rec = self.per_request.get(rid)
            if rec is None:
                rec = self.per_request[rid] = {
                    k: 0.0 for k in REQUEST_WORK_COUNTERS}
            rec["flops"] += n * per_tok
            rec["kv_bytes_touched"] += (n + r) * card.kv_bytes_per_token
            rec["dispatches"] += passes

    def request_work(self, rid: int) -> Dict[str, float]:
        """The per-request attribution (zeros for an unseen rid) —
        stamped into ``serve_with_arrivals`` records."""
        rec = self.per_request.get(rid)
        if rec is None:
            return {k: 0.0 for k in REQUEST_WORK_COUNTERS}
        return dict(rec)

    # ---- polled counters ----------------------------------------------
    def recompiles(self) -> int:
        """Backend compiles of the registered programs since their
        registration: the build log's ``compile`` events under their
        names (a cache-answered one too: the serving loop waited for it),
        counted as they come in."""
        log = build_log()
        self.work["recompiles_total"] += sum(
            b.what == "compile" and b.fun_name in self._programs
            for b in log.since(self._builds_seen))
        self._builds_seen = log.emitted
        return self.work["recompiles_total"]

    def _poll(self) -> None:
        self.recompiles()
        for kv, seen in self._paged.values():
            for name, attr in (("pages_mapped", "pages_mapped"),
                               ("pages_cow", "cow_copies")):
                cur = int(getattr(kv, attr, 0))
                if cur > seen[name]:
                    self.work[name] += cur - seen[name]
                seen[name] = cur

    # ---- tick boundaries ----------------------------------------------
    def tick_begin(self) -> None:
        # the work counters at the tick's entry.  (A tick's TIME split is
        # the tick journal's record, obs/journal.py: always on, self time
        # by span; ``phase_s`` here is the run's accumulation)
        self._tick_mark = dict(self.work)

    def tick_end(self) -> None:
        self._poll()
        self.ticks += 1
        mark = self._tick_mark or {}
        self._tick_mark = None
        dwork = {k: self.work[k] - mark.get(k, 0)
                 for k in self.work if self.work[k] != mark.get(k, 0)}
        notes = self._tick_notes
        self._tick_notes = {}
        self.last_tick = {"tick": self.ticks, "work": dwork}
        if notes:
            self.last_tick["notes"] = notes
        tel = self.telemetry
        if tel is not None and tel.enabled:
            tel.instant("step_profile", cat="profile", track="profile",
                        tick=self.ticks, **notes, **dwork)
            tel.metrics.gauge("recompiles_total").set(
                self.work["recompiles_total"])

    # ---- report -------------------------------------------------------
    def report(self) -> Dict:
        """JSON-ready accumulation: the phase time budget, the work
        counters, and the per-request attribution summary (counts only —
        the full table rides ``serve_with_arrivals`` records)."""
        self._poll()
        total_ms = sum(self.phase_s.values()) * 1e3
        phases = {
            name: {"ms": round(self.phase_s[name] * 1e3, 6),
                   "count": self.phase_counts.get(name, 0),
                   "frac": (round(self.phase_s[name] * 1e3 / total_ms, 4)
                            if total_ms else None)}
            for name in sorted(self.phase_s)
        }
        return {
            "ticks": self.ticks,
            "phases": phases,
            "work": {k: self.work[k] for k in WORK_COUNTERS},
            "requests_attributed": len(self.per_request),
        }


class NullStepProfiler:
    """No-op stand-in (the shared default): every hook returns a
    constant; ``enabled`` is False so instrumented code skips argument
    construction entirely."""

    enabled = False

    def bind(self, *a, **k):
        return None

    def install(self, *a, **k):
        return None

    def uninstall(self, *a, **k):
        return None

    def card_for(self, *a, **k):
        return None

    def phase(self, name, *a, **k):
        return Span(name)

    def count(self, *a, **k):
        return None

    def host_sync(self, *a, **k):
        return None

    def note(self, *a, **k):
        return None

    def account(self, *a, **k):
        return None

    def request_work(self, *a, **k):
        return {}

    def recompiles(self):
        return 0

    def tick_begin(self):
        return None

    def tick_end(self):
        return None

    def report(self):
        return {}


NULL_PROFILER = NullStepProfiler()


def profiler_or_null(profiler) -> "StepProfiler":
    """Normalize an optional handle: None -> the shared no-op singleton."""
    return profiler if profiler is not None else NULL_PROFILER
