"""SpecInfer: tree-based speculative decoding (SSM draft + LLM verify).

Reference: ``RequestManager::serve_spec_infer`` / ``prepare_next_batch_beam`` /
``prepare_next_batch_verify`` in ``src/runtime/request_manager.cc`` and the
SpecInfer ASPLOS'24 design: a small draft model (SSM) expands a token TREE per
request; the LLM verifies the whole tree in ONE batched step using
tree-topology causal attention; the longest root-path whose tokens match the
LLM's own greedy choices is committed, plus one "bonus" token from the LLM —
so each LLM pass can commit up to depth+1 tokens.

Per macro-step, per request (host bookkeeping; device work is 4 jitted
programs total — SSM inc/tree-search, LLM inc/tree-verify):

1. *catch-up*   — feed tokens accepted last round into the SSM's committed
   cache (plain ``BatchConfig``; the LLM's copies are committed via the
   verify step's commit descriptor instead, reusing KV computed during
   verification).
2. *draft*      — root = latest token; ``depth`` beam-expansion steps of
   width ``width`` through the SSM (``TreeSearchBatchConfig``), keeping
   per-node cumulative logprobs; nodes live in the spec KV buffer.
3. *verify*     — flatten the tree into one ``TreeVerifyBatchConfig`` step of
   the LLM (commit descriptor carries last round's accepted nodes); walk the
   result greedily root-down to find the accepted path + bonus token.

Greedy invariant (tested): output sequences are EXACTLY those of plain
incremental decoding with the LLM, for any draft model.

**Mixed spec/non-spec batches (first-class production mode).**  Speculation
is a PER-REQUEST scheduling decision: ``register_new_request(spec=...)``
sets the mode at admission (default True under this manager) and
``set_spec_mode`` flips it at runtime.  Non-spec rows join the same verify
macro-step as degenerate root-only trees — their single node is the decode
token, the accept walk trivially emits one target-sampled token — so a
heterogeneous mix runs in ONE batched LLM step: spec rows verify
multi-token, plain rows decode one token.  While NO live request is in
spec mode, the manager's tick degrades to the incremental fast path
(decode stretches/scans included) after flushing any pending spec commits
into the committed cache, so an all-plain population never pays the
macro-step overhead.

**Seeded-sampling bit-identity.**  Every sampled dispatch in the spec
phases keys on the r9 ``(rid, token_index)`` fold (a verify row at tree
depth ``d`` samples generated-token index ``len(generated) + d``), so
sampled speculative serving is BIT-IDENTICAL to sampled incremental
decoding — which is what makes mixed batches, recompute recovery, and
mode flips composable: a token's value depends only on (seed, rid, index)
and the committed prefix, never on which serving path produced it.

**Recompute recovery.**  ``supports_recompute`` is True: a dispatch fault
past the retry budget (or slot/page pressure) preempts the affected
requests through the r9 path — spec bookkeeping (tree, pending commits,
committed depths) resets, the readmission re-prefills prompt+generated
into BOTH models' caches, and the recomputed tokens are bit-identical.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .batch_config import (
    BatchConfig,
    TreeSearchBatchConfig,
    TreeVerifyBatchConfig,
)
from .inference_manager import InferenceManager
from .request_manager import (
    GenerationConfig,
    Request,
    RequestManager,
    RequestStatus,
)


@dataclasses.dataclass
class TokenTreeNode:
    token: int
    parent: int          # index into the tree's node list (-1 for root)
    depth: int
    logprob: float = 0.0  # cumulative draft logprob along the root path


@dataclasses.dataclass
class SpecRequest(Request):
    """Request + speculation bookkeeping."""

    # accepted-but-not-yet-committed (spec_index, position, token) triples;
    # committed into the LLM cache by the NEXT verify step's commit descriptor
    pending_commit: List[Tuple[int, int]] = dataclasses.field(default_factory=list)
    llm_committed: int = 0   # LLM cache depth
    ssm_committed: int = 0   # SSM cache depth
    ssm_backlog: List[Tuple[int, int]] = dataclasses.field(default_factory=list)
    tree: List[TokenTreeNode] = dataclasses.field(default_factory=list)


class SpecInferManager(RequestManager):
    """Drives speculative serving over two InferenceManagers (SSM + LLM).

    Queue/admission/stopping logic is inherited from :class:`RequestManager`
    (so incremental and speculative serving can never diverge on lifecycle
    semantics); this class replaces the per-step loop with the three-phase
    macro step.  ``width``/``depth`` bound each request's tree to
    ``1 + width*depth`` nodes; all capacities are validated up front.
    """

    request_cls = SpecRequest
    # inherited speculation semantics: requests default to spec mode (the
    # historical all-spec behavior); callers opt rows out per request
    default_spec_mode = True
    # dispatch failures recover through the r9 preemption-and-recompute
    # path: preempt() resets the spec bookkeeping and readmission
    # re-prefills prompt+generated into both models' caches (bit-identical
    # for greedy AND seeded sampling — the (rid, token_index) fold)
    supports_recompute = True

    def __init__(
        self,
        llm: InferenceManager,
        ssm: InferenceManager,
        gen_config: Optional[GenerationConfig] = None,
        width: int = 2,
        depth: int = 3,
        telemetry=None,
        resilience=None,
        fault_injector=None,
        clock=None,
        plan_health=None,
        profiler=None,
        slo=None,
        brownout=None,
    ):
        super().__init__(llm, gen_config, telemetry=telemetry,
                         resilience=resilience,
                         fault_injector=fault_injector, clock=clock,
                         plan_health=plan_health, profiler=profiler,
                         slo=slo, brownout=brownout)
        self.llm = llm
        self.ssm = ssm
        self.width = width
        self.depth = depth
        self.max_tree = 1 + width * depth
        if llm.max_spec_tokens < self.max_tree or ssm.max_spec_tokens < self.max_tree:
            raise ValueError(
                f"spec buffers too small: need {self.max_tree} slots, have "
                f"llm={llm.max_spec_tokens} ssm={ssm.max_spec_tokens}"
            )
        if llm.max_requests != ssm.max_requests:
            raise ValueError("LLM and SSM must agree on max_requests")
        if llm.max_tokens < llm.max_requests * self.max_tree:
            raise ValueError(
                "LLM max_tokens_per_batch must fit max_requests full trees "
                f"({llm.max_requests}x{self.max_tree})"
            )
        if ssm.max_tokens < ssm.max_requests * width:
            raise ValueError(
                "SSM max_tokens_per_batch must fit one frontier per request "
                f"({ssm.max_requests}x{width})"
            )
        if ssm.topk < width:
            raise ValueError(f"SSM InferenceManager needs topk >= width ({width})")
        self.macro_steps = 0
        self.llm_steps = 0
        self._kv_hwm_tokens = 0    # combined (target + draft) watermark
        self._kv_hwm_bytes = 0.0
        # the draft model is a co-resident deployment: its params + KV
        # buffers are REAL HBM, so its allocator joins the attribution
        # protocol (reset like the target's in RequestManager.__init__)
        # and its predicted-vs-allocated record lands in the memory
        # ledger under its own "_draft" plan key — same tp/pp shape as
        # the target must not collide with the target's record
        # the draft model shares the ONE profiler handle (like telemetry):
        # its dispatches/jit caches join the dispatch + recompile
        # accounting, and its work is priced with its OWN cost card
        ssm.profiler = self.profiler
        if self.profiler.enabled:
            self.profiler.install(ssm)
        kv_s = getattr(ssm, "kv", None)
        if kv_s is not None:
            kv_s.reset_attribution()
            # the base __init__ auto-wired the plan-health monitor to the
            # TARGET allocator; widen the auto-wiring to both caches so
            # the OOM projection covers the draft's growth too (an
            # explicitly-provided allocator is the caller's choice)
            kv_l = getattr(llm, "kv", None)
            if (self.plan_health is not None and kv_l is not None
                    and self.plan_health.kv_allocator is kv_l):
                self.plan_health.kv_allocator = [kv_l, kv_s]
        if self.telemetry.enabled and hasattr(ssm, "publish_memory"):
            ssm.publish_memory(self.telemetry,
                               key=ssm.plan_key + "_draft")

    def trace_run_meta(self):
        """Trace provenance (obs/replay.py): the base manager's header
        plus the draft-tree shape and the draft deployment's plan — a
        fidelity replay must rebuild the SAME speculation config, and a
        what-if replay prices spec candidates off these fields."""
        meta = super().trace_run_meta()
        from ..obs.replay import engine_shape_of

        meta["spec"] = {"width": self.width, "depth": self.depth,
                        "draft_plan": engine_shape_of(self.ssm)}
        return meta

    # ------------------------------------------------------------------
    # memory observability over TWO deployments (target + draft)
    # ------------------------------------------------------------------
    def _kv_bind(self, rid: int) -> None:
        # the target allocator gets the full prefix-reuse bind (the LLM
        # prompt prefill consumes the cached offset); the draft cache
        # binds attribution + slot only — its pages map on demand through
        # the ssm-side prepare spans, no prefix chain (the catch-up feed
        # is committed-depth-driven, not offset-driven)
        super()._kv_bind(rid)
        kv_s = getattr(self.ssm, "kv", None)
        if kv_s is not None:
            kv_s.bind(rid, slot=self.requests[rid].slot)

    def _release_slot(self, req: Request) -> None:
        if req.slot < 0:
            return
        # both deployments release on every slot-leaving path (terminal
        # outcomes AND preemption — spec requests recompute now, so a
        # request can bind more than once): the combined target+draft
        # bytes of THIS binding epoch max-combine with previous epochs'
        # stamp, so the recorded peak is what the request really held
        kv_s = getattr(self.ssm, "kv", None)
        draft = (kv_s.release(req.rid, tokens=req.ssm_committed)
                 if kv_s is not None else 0.0)
        kv_l = getattr(self.llm, "kv", None)
        target = (kv_l.release(req.rid, tokens=req.seq_len)
                  if kv_l is not None else 0.0)
        self.slots[req.slot] = None
        req.slot = -1
        req.kv_bytes = max(req.kv_bytes, target + draft)

    def preempt(self, rid: int) -> None:
        """Recompute-based spec preemption (lifts the r9 restriction):
        the slot + BOTH caches release, the tree/commit/committed-depth
        bookkeeping resets, and readmission re-prefills prompt+generated
        into the LLM AND the SSM (``_prefill_phase`` feeds
        ``prefill_tokens``), after which served tokens are bit-identical
        to an unpreempted run for greedy and seeded sampling — the spec
        phases key every sample on the same (rid, token_index) fold the
        incremental paths use."""
        super().preempt(rid)
        req = self.requests[rid]
        req.pending_commit = []
        req.tree = []
        req.llm_committed = 0
        req.ssm_committed = 0
        req.ssm_backlog = []

    def _on_spec_flip(self, req: Request) -> None:
        """Runtime mode flip.  Enabling speculation mid-decode rebuilds
        the draft model's catch-up feed when it lags (``_ssm_sync``) —
        the SSM committed cache must hold every position before the next
        draft root, and a request that served non-spec rounds left it
        behind.  Disabling needs nothing — the row stops drafting at the
        next macro step and any pending commit flows through the next
        verify batch (or the incremental-path flush)."""
        if req.spec:
            self._ssm_sync(req)

    def _token_at(self, req: Request, p: int) -> int:
        """The logical token at sequence position ``p`` (prompt, then
        generated — the one layout every cache position maps to)."""
        return (req.prompt[p] if p < len(req.prompt)
                else req.generated[p - len(req.prompt)])

    def _combine_snaps(self, snap: Dict, snap_s: Dict, kv_l, kv_s) -> Dict:
        """Fold the draft allocator's snapshot into the target's: summed
        tokens/bytes/capacity, recomputed fracs, and the manager-held
        combined watermark — the peak of the SUMMED live stream (adding
        the two allocators' independent all-time peaks could overstate:
        they may peak at different ticks — and diverge from the ledger's
        own observe_live watermark over the same summed stream).  Any
        true observation may raise the watermark, so pure-read callers
        (``kv_snapshot``) share this safely."""
        for k in ("live_tokens", "live_bytes", "capacity_tokens",
                  "capacity_bytes", "headroom_bytes"):
            snap[k] += snap_s[k]
        self._kv_hwm_tokens = max(self._kv_hwm_tokens, snap["live_tokens"])
        self._kv_hwm_bytes = max(self._kv_hwm_bytes, snap["live_bytes"])
        snap["hwm_tokens"] = self._kv_hwm_tokens
        snap["hwm_bytes"] = self._kv_hwm_bytes
        snap["occupancy_frac"] = (
            snap["live_tokens"] / snap["capacity_tokens"]
            if snap["capacity_tokens"] else 0.0)
        reserved = (kv_l.live_requests() * kv_l.max_seq_len
                    + kv_s.live_requests() * kv_s.max_seq_len)
        snap["fragmentation_frac"] = (
            1.0 - snap["live_tokens"] / reserved if reserved else 0.0)
        return snap

    def kv_snapshot(self):
        kv_l = getattr(self.llm, "kv", None)
        kv_s = getattr(self.ssm, "kv", None)
        if kv_l is None or kv_s is None:
            return super().kv_snapshot()
        return self._combine_snaps(kv_l.snapshot(), kv_s.snapshot(),
                                   kv_l, kv_s)

    def _sync_kv(self) -> None:
        """Observe BOTH allocators (per-deployment peaks + watermarks)
        and publish ONE combined live view — summed tokens/bytes/
        capacity — so the occupancy/headroom gauges and the ledger
        watermark account the draft model's KV instead of under-reporting
        live HBM by its whole share."""
        kv_l = getattr(self.llm, "kv", None)
        kv_s = getattr(self.ssm, "kv", None)
        if kv_l is None or kv_s is None:
            return super()._sync_kv()
        live = [r for r in self._active()
                if r.status in (RequestStatus.PREFILLING,
                                RequestStatus.DECODING)]
        snap = self._combine_snaps(
            kv_l.observe({r.rid: r.seq_len for r in live}, None),
            kv_s.observe({r.rid: r.ssm_committed for r in live}, None),
            kv_l, kv_s)
        if self.telemetry.enabled:
            self.telemetry.kv_usage(snap)

    def _seq_len_needed(self, req: Request) -> int:
        # verification scores up to `depth` speculative positions past the
        # last committed token, so the cache needs headroom beyond max_new
        return len(req.prompt) + req.max_new_tokens + self.depth + 1

    # ------------------------------------------------------------------
    # phase A: prompt prefill (both models) + SSM catch-up
    # ------------------------------------------------------------------
    def _prefill_phase(self):
        self._admit()
        # LLM prefill for new requests (chunked by the LLM token budget).
        # The feed is ``prefill_tokens`` — the prompt, or prompt+generated
        # while recovering from preemption (recompute), exactly like the
        # incremental prefill paths.
        while True:
            toks, reqi, pos, points, spans = [], [], [], [], []
            budget = self.llm.max_tokens
            for req in self._active():
                if req.status is not RequestStatus.PREFILLING or budget <= 0:
                    continue
                feed = req.prefill_tokens
                take = min(budget, len(feed) - req.prefill_offset)
                st = req.prefill_offset
                toks += feed[st : st + take]
                reqi += [req.slot] * take
                pos += list(range(st, st + take))
                if take:
                    spans.append((req.rid, st, st + take))
                req.prefill_offset += take
                budget -= take
                if req.prefill_offset == len(feed):
                    points.append((len(toks) - 1, req.rid))
            if not toks:
                break
            self._kv_prepare(spans)
            self._prof_account(spans)
            bc = self._plain_bc(self.llm, toks, reqi, pos)
            # per-request (rid, token_index) sample folds so the first
            # generated token (read off the last fed position's logits) is
            # bit-identical to the incremental loop's — for fresh prompts
            # AND recompute re-prefills.  All phase dispatches run under
            # the retry guard; the fold schedule is deterministic, so a
            # retried dispatch replays the identical step.
            smp = self._sample_for(points, self.llm.max_tokens)
            result = self._guarded(
                "spec_prefill",
                lambda b=bc, s=smp: self.llm.step(b, sample=s))
            if result is None:
                return
            self.llm_steps += 1
            with self._span("readback", phase=True):
                self._device_wait(result.token_ids)
                ids = np.asarray(result.token_ids)
            self.profiler.host_sync()
            for flat, rid in points:
                req = self.requests[rid]
                if req.status is not RequestStatus.PREFILLING:
                    continue  # left the slot between build and readback
                req.status = RequestStatus.DECODING
                req.llm_committed = len(req.prefill_tokens)
                self._append_token(req, int(ids[flat]))
                self._maybe_finish(req)

        # SSM prefill (prompt / recompute feed) + catch-up (tokens accepted
        # by previous rounds).  Non-spec rows skip the draft model entirely
        # — their SSM cache rebuilds from scratch on a later flip-on or
        # activation (``_ssm_sync``).
        for req in self._active():
            if req.spec:
                # a row may reach the macro path with a lagging SSM side
                # (flip-on, or incremental-path ticks before activation)
                self._ssm_sync(req)
        while True:
            toks, reqi, pos, spans = [], [], [], []
            budget = self.ssm.max_tokens
            for req in self._active():
                if budget <= 0:
                    break
                if not req.spec:
                    continue
                lo = len(pos)
                feed = req.prefill_tokens
                if req.ssm_committed < len(feed):
                    take = min(budget, len(feed) - req.ssm_committed)
                    st = req.ssm_committed
                    toks += feed[st : st + take]
                    reqi += [req.slot] * take
                    pos += list(range(st, st + take))
                    req.ssm_committed += take
                    budget -= take
                if req.ssm_backlog and budget > 0:
                    take = min(budget, len(req.ssm_backlog))
                    for t, p in req.ssm_backlog[:take]:
                        toks.append(t)
                        reqi.append(req.slot)
                        pos.append(p)
                    req.ssm_backlog = req.ssm_backlog[take:]
                    req.ssm_committed += take
                    budget -= take
                if len(pos) > lo:
                    spans.append((req.rid, min(pos[lo:]),
                                  max(pos[lo:]) + 1))
            if not toks:
                break
            self._kv_prepare(spans, kv=getattr(self.ssm, "kv", None))
            self._prof_account(spans, im=self.ssm)
            bc = self._plain_bc(self.ssm, toks, reqi, pos)
            if self._guarded("spec_ssm_prefill",
                             lambda b=bc: self.ssm.step(b)) is None:
                return

    def _plain_bc(self, im, toks, reqi, pos):
        seq_lens = np.zeros(im.max_requests, np.int32)
        for req in self._active():
            seq_lens[req.slot] = req.seq_len
        return BatchConfig.build(
            toks, reqi, pos, seq_lens,
            max_tokens=im.max_tokens, max_requests=im.max_requests,
        )

    # ------------------------------------------------------------------
    # phase B: draft-tree expansion through the SSM
    # ------------------------------------------------------------------
    def _draft_phase(self) -> List[SpecRequest]:
        """Build every DECODING request's speculation tree for this round.

        Spec-mode rows expand ``depth`` beam levels through the SSM;
        non-spec rows get a degenerate ROOT-ONLY tree (their decode token)
        — the mixed-batch lever: both populations then verify in ONE
        LLM step (:meth:`_verify_phase`), spec rows multi-token, plain
        rows one token.  Returns the full verifying list."""
        decoding = [r for r in self._active()
                    if r.status is RequestStatus.DECODING]
        if not decoding:
            return []
        P = self.ssm.max_spec_tokens
        R = self.ssm.max_requests
        masks = np.zeros((R, P, P), bool)
        for req in decoding:
            # macro-boundary invariant: the LLM's committed depth is the
            # cache prefix before the root (= seq_len - 1).  A row that
            # served incremental ticks (all-plain phases) advanced its
            # cache without this bookkeeping — resync is a no-op for rows
            # in continuous speculative service.
            req.llm_committed = req.seq_len - 1
            req.tree = [TokenTreeNode(req.generated[-1], -1, 0, 0.0)]
            masks[req.slot, 0, 0] = True

        drafting = [r for r in decoding if r.spec]
        if not drafting:
            return decoding
        frontier = {req.rid: [0] for req in drafting}  # node indices at depth d
        # feeding depth-d nodes yields depth-(d+1) children; final-depth nodes
        # are never fed (their KV is only needed by the LLM's verify pass)
        for d in range(self.depth):
            toks, reqi, pos, spec, points = [], [], [], [], []
            for req in drafting:
                for ni in frontier.get(req.rid, []):
                    node = req.tree[ni]
                    toks.append(node.token)
                    reqi.append(req.slot)
                    pos.append(req.llm_committed + node.depth)
                    spec.append(ni)
                    points.append((len(toks) - 1, req.rid, ni))
            if not toks:
                break
            bc = self._tree_bc(
                TreeSearchBatchConfig, self.ssm, toks, reqi, pos, spec, masks,
                committed_attr="ssm_committed",
            )
            prof = self.profiler
            if prof.enabled and toks:
                per: Dict[int, int] = {}
                for _, rid, _ni in points:
                    per[rid] = per.get(rid, 0) + 1
                prof.account(
                    prof.card_for(self.ssm),
                    [(rid, c, self.requests[rid].seq_len)
                     for rid, c in per.items()])
            result = self._guarded("spec_draft",
                                   lambda b=bc: self.ssm.step(b))
            if result is None:
                return []
            with self._span("readback", phase=True):
                self._device_wait((result.topk_ids, result.topk_logprobs))
                topk_ids = np.asarray(result.topk_ids)
                topk_lp = np.asarray(result.topk_logprobs)
            prof.host_sync()
            # beam-select the next frontier per request
            for req in drafting:
                cands = []
                for flat, rid, ni in points:
                    if rid != req.rid:
                        continue
                    base_lp = req.tree[ni].logprob
                    for j in range(self.width):
                        cands.append(
                            (base_lp + float(topk_lp[flat, j]),
                             int(topk_ids[flat, j]), ni)
                        )
                cands.sort(reverse=True)
                nxt = []
                for lp, tok, parent in cands[: self.width]:
                    if len(req.tree) >= self.max_tree:
                        break
                    idx = len(req.tree)
                    req.tree.append(
                        TokenTreeNode(tok, parent, req.tree[parent].depth + 1, lp)
                    )
                    # ancestor mask row = parent's row + self
                    masks[req.slot, idx] = masks[req.slot, parent]
                    masks[req.slot, idx, idx] = True
                    nxt.append(idx)
                frontier[req.rid] = nxt
        return decoding

    def _tree_bc(self, cls, im, toks, reqi, pos, spec, masks, committed_attr,
                 commit=None):
        seq_lens = np.zeros(im.max_requests, np.int32)
        committed = np.zeros(im.max_requests, np.int32)
        for req in self._active():
            seq_lens[req.slot] = req.seq_len
            committed[req.slot] = getattr(req, committed_attr)
        base = BatchConfig.build(
            toks, reqi, pos, seq_lens,
            max_tokens=im.max_tokens, max_requests=im.max_requests,
        )
        import jax.numpy as jnp

        P = im.max_spec_tokens
        si = np.zeros(im.max_tokens, np.int32)
        si[: len(spec)] = spec
        kw = dict(
            base=base,
            spec_index=jnp.asarray(si),
            ancestor_mask=jnp.asarray(masks[:, :P, :P]),
            committed_lens=jnp.asarray(committed),
        )
        if cls is TreeVerifyBatchConfig:
            n = im.max_tokens
            cri = np.full(n, -1, np.int32)
            csi = np.zeros(n, np.int32)
            cdp = np.zeros(n, np.int32)
            commit = commit or []
            for i, (slot, src, dst) in enumerate(commit):
                cri[i], csi[i], cdp[i] = slot, src, dst
            kw.update(
                commit_request_index=jnp.asarray(cri),
                commit_src_spec_index=jnp.asarray(csi),
                commit_dst_position=jnp.asarray(cdp),
            )
        return cls(**kw)

    # ------------------------------------------------------------------
    # phase C: LLM tree verification + accept walk
    # ------------------------------------------------------------------
    def _verify_phase(self, verifying: List[SpecRequest]):
        """ONE batched LLM step over every decoding row's tree — the
        mixed macro-step: spec rows ship their whole draft tree (verify
        multi-token), plain rows ship a root-only tree (decode one
        token).  The accept walk + commit bookkeeping are identical for
        both; a root-only tree trivially accepts zero children and emits
        the bonus token."""
        if not verifying:
            return
        tel = self.telemetry
        R = self.llm.max_requests
        P = self.llm.max_spec_tokens
        masks = np.zeros((R, P, P), bool)
        toks, reqi, pos, spec, index_of = [], [], [], [], {}
        commit, spans = [], []
        for req in verifying:
            for ni, node in enumerate(req.tree):
                masks[req.slot, ni, ni] = True
                if node.parent >= 0:
                    masks[req.slot, ni] |= masks[req.slot, node.parent]
                    masks[req.slot, ni, ni] = True
                index_of[(req.rid, ni)] = len(toks)
                toks.append(node.token)
                reqi.append(req.slot)
                pos.append(req.llm_committed + node.depth)
                spec.append(ni)
            for src, dst in req.pending_commit:
                commit.append((req.slot, src, dst))
            if req.pending_commit:
                # the commit descriptor writes accepted KV into the
                # committed cache at these positions (the spec-tree buffer
                # itself is never paged)
                dsts = [d for _, d in req.pending_commit]
                spans.append((req.rid, min(dsts), max(dsts) + 1))
            req.pending_commit = []
        self._kv_prepare(spans)
        bc = self._tree_bc(
            TreeVerifyBatchConfig, self.llm, toks, reqi, pos, spec, masks,
            committed_attr="llm_committed", commit=commit,
        )
        # stochastic verification: with temperature > 0 the verify step
        # SAMPLES y ~ p(target | node prefix) per tree node (seeded,
        # top-p) and the walk accepts a child iff its token equals y.
        # Each row's key folds (rid, generated-token index): a node at
        # tree depth d samples index len(generated)+d — the SAME key the
        # incremental loop would use for that token, so sampled spec
        # output is BIT-IDENTICAL to sampled incremental decoding (not
        # merely distribution-equal), which is what the mixed-batch and
        # recompute bit-identity contracts rest on.  T<=0 keeps the
        # exact-greedy walk.
        smp = self._verify_sample(verifying, index_of)
        prof = self.profiler
        if prof.enabled:
            # one verify macro-step: each row ships its whole tree (a
            # root-only tree for plain rows) and reads its live prefix
            prof.account(
                prof.card_for(self.llm),
                [(r.rid, len(r.tree), r.seq_len) for r in verifying])
        n_spec = sum(1 for r in verifying if len(r.tree) > 1)
        n_plain = len(verifying) - n_spec
        if tel.enabled:
            tel.spec_batch_mix(n_spec, n_plain)
        with tel.span("spec_verify_round", cat="spec", track="spec",
                      n_spec=n_spec, n_plain=n_plain,
                      tree_tokens=len(toks)):
            result = self._guarded(
                "spec_verify", lambda: self.llm.step(bc, sample=smp))
        if result is None:
            return
        self.llm_steps += 1
        with self._span("readback", phase=True):
            self._device_wait(result.token_ids)
            ids = np.asarray(result.token_ids)
        prof.host_sync()

        for req in verifying:
            if req.status is not RequestStatus.DECODING:
                # the request left its slot between list build and
                # readback (page-pressure preemption inside _kv_prepare
                # resets its tree; a lifecycle reap can't land here, but
                # the guard is status-based like _prefill_phase's): its
                # verify rows are dead — the readmission recomputes, and
                # walking the reset tree would index an empty list
                continue
            # accept walk from the root (greedy or vs the sampled tokens)
            ni = 0
            accepted_nodes = [0]
            while True:
                want = int(ids[index_of[(req.rid, ni)]])
                child = next(
                    (
                        j
                        for j, n in enumerate(req.tree)
                        if n.parent == ni and n.token == want
                    ),
                    None,
                )
                if child is None:
                    bonus = want
                    break
                accepted_nodes.append(child)
                ni = child
            # commit root + accepted draft nodes next round; emit their tokens
            new_tokens = []
            for k, node_idx in enumerate(accepted_nodes):
                node = req.tree[node_idx]
                posn = req.llm_committed + node.depth
                req.pending_commit.append((node_idx, posn))
                if k > 0:  # root token was already in req.generated
                    new_tokens.append(node.token)
            new_tokens.append(bonus)
            req.llm_committed += len(accepted_nodes)
            # acceptance telemetry: draft tokens that survived the walk
            # this round (the root is committed context, not a draft) —
            # feeds the workload profile's spec_acceptance histogram so
            # acceptance-rate drift is visible to the planner
            if self.telemetry.enabled and len(req.tree) > 1:
                self.telemetry.spec_acceptance(
                    len(accepted_nodes) - 1, len(req.tree) - 1)
            # SSM needs the same accepted tokens in its committed cache;
            # the root (generated[-1] pre-walk) is part of them.  Plain
            # rows skip the draft model entirely — a later flip-on
            # rebuilds the feed from scratch (``_on_spec_flip``), so
            # their backlog must not accumulate unconsumed entries.
            if req.spec:
                base_pos = req.ssm_committed + len(req.ssm_backlog)
                acc_toks = [req.tree[i].token for i in accepted_nodes]
                req.ssm_backlog += [
                    (t, base_pos + k) for k, t in enumerate(acc_toks)
                ]
            for t in new_tokens:
                self._append_token(req, t)
                self._maybe_finish(req)
                if req.status is RequestStatus.COMPLETED:
                    break

    def _verify_sample(self, verifying: List[SpecRequest], index_of):
        """Per-row sampling arg for the verify step: row ``index_of[(rid,
        ni)]`` folds ``(rid, len(generated) + depth(ni))`` — the exact key
        the incremental loop uses for that generated-token index, so
        sampled speculative output is bit-identical to sampled incremental
        decoding (rows of non-verifying slots draw from the (0, 0) fold
        and are discarded).  Assembled by the ONE ``_sample_for`` path
        (the tree depth rides the per-point index offset).  None for
        greedy — checked HERE too so the point list (which indexes each
        row's tree) is never built eagerly; rows whose request left
        DECODING between list build and this call (page-pressure
        preemption in ``_kv_prepare`` resets the tree) are skipped like
        the accept walk skips them."""
        if self.gen.temperature <= 0.0:
            return None
        return self._sample_for(
            [(row, rid, self.requests[rid].tree[ni].depth)
             for (rid, ni), row in index_of.items()
             if self.requests[rid].status is RequestStatus.DECODING],
            self.llm.max_tokens)

    # ------------------------------------------------------------------
    # the spec-aware tick: mixed macro-step, or the incremental fast path
    # ------------------------------------------------------------------
    def _spec_live(self) -> bool:
        """Any ACTIVE (slotted) request in spec mode — the per-tick
        dispatch decision.  Deliberately ignores the pending queue: a
        spec arrival stuck behind a full house of plain decoders must not
        force everyone onto the macro-step path (1 token/row/dispatch)
        while it waits — the incremental fast path keeps serving, the
        arrival admits through it, and the NEXT tick's check sees the
        active spec row (its SSM cache lazily resyncs via
        :meth:`_ssm_sync`, so incremental prefill/decode ticks before
        activation are fine)."""
        return any(r.spec for r in self._active())

    def _ssm_sync(self, req: SpecRequest) -> None:
        """Ensure the draft model's catch-up feed covers every position
        before the next draft root (``seq_len - 1``).  A spec-mode row
        can reach the macro path with a LAGGING SSM side — runtime
        flip-on, or LLM prefill/decode ticks served by the incremental
        fast path while the row waited to activate — in which case the
        feed rebuilds from scratch (value-deterministic overwrite).
        Steady-state rows (committed + backlog already reach the root)
        are untouched."""
        if req.status is not RequestStatus.DECODING:
            return
        want = req.seq_len - 1
        if req.ssm_committed + len(req.ssm_backlog) >= want:
            return
        req.ssm_committed = 0
        req.ssm_backlog = [
            (self._token_at(req, p), p)
            for p in range(len(req.prefill_tokens), want)
        ]

    def _flush_commits(self) -> bool:
        """Exit-speculation commit flush: accepted-but-uncommitted tokens
        (``pending_commit``) normally reach the committed cache through
        the NEXT verify step's commit descriptor — when the tick degrades
        to the incremental path (no live spec request) there is no next
        verify step, so the pending positions are re-fed as one plain
        batch instead (KV writes are value-deterministic, so recomputing
        them equals the descriptor's spec-buffer copy bit-for-bit).  The
        incremental step that follows then sees the complete cache
        prefix.  Runs only at speculative→incremental transitions.

        Returns whether the flush COMPLETED: a dispatch fault past the
        retry budget requeues/fails only the rows in the failed batch,
        but rows budget-deferred to a later inner batch still hold
        un-flushed commits — the caller must not run an incremental step
        over their incomplete cache prefix (the next tick retries)."""
        flush = [r for r in self._active()
                 if r.status is RequestStatus.DECODING and r.pending_commit]
        if not flush:
            return True
        while True:
            toks, reqi, pos, spans = [], [], [], []
            budget = self.llm.max_tokens
            for req in flush:
                if not req.pending_commit or budget <= 0:
                    continue
                take = min(budget, len(req.pending_commit))
                part = req.pending_commit[:take]
                req.pending_commit = req.pending_commit[take:]
                for _, dst in part:
                    toks.append(self._token_at(req, dst))
                    reqi.append(req.slot)
                    pos.append(dst)
                dsts = [d for _, d in part]
                spans.append((req.rid, min(dsts), max(dsts) + 1))
                budget -= take
            if not toks:
                break
            self._kv_prepare(spans)
            self._prof_account(spans)
            bc = self._plain_bc(self.llm, toks, reqi, pos)
            # a flush fault past the retry budget affects only the rows
            # actually IN the failed batch (a budget-limited flush may
            # have deferred other rows to a later inner batch)
            if self._guarded("spec_commit_flush",
                             lambda b=bc: self.llm.step(b),
                             affected_fn=lambda b=bc:
                             self._rids_in_batch(b)) is None:
                return False
            self.llm_steps += 1
        return True

    def flush_pending_commits(self) -> bool:
        """Public drain hook (serve/migration.py): commit every
        accepted-but-uncommitted token into the LLM cache NOW, so a
        migration drain's grace window runs over a complete cache prefix
        (the requests it then preempts recompute from scratch anyway —
        their pending commits reset in :meth:`preempt` — but rows that
        COMPLETE during the grace window must not finish on a cache
        missing their accepted tail).  Same semantics as the
        speculative→incremental transition flush."""
        return self._flush_commits()

    def _tick(self) -> None:
        """One serving tick: a mixed speculative macro-step while any
        live request is in spec mode (plain rows ride the same verify
        batch as root-only trees), otherwise — after flushing any
        pending spec commits — the inherited incremental fast path
        (decode stretches/scans included), so an all-plain population
        never pays the macro-step overhead.  Lifecycle reaping, KV sync,
        and plan-health polling stay in the shared serve loops
        (``serve_incr_decoding`` / ``serve_with_arrivals``), so
        deadlines/TTL/cancel land at spec macro-step boundaries exactly
        like the incremental loop's step boundaries."""
        if self._spec_live():
            with self.telemetry.span("spec_macro_step", cat="spec",
                                     track="spec"):
                self._prefill_phase()
                verifying = self._draft_phase()
                self._verify_phase(verifying)
            self.macro_steps += 1
        else:
            if self._flush_commits():
                self._serve_tick()

    # ------------------------------------------------------------------
    def serve_spec_infer(self) -> Dict[int, List[int]]:
        """Reference: ``RequestManager::serve_spec_infer``.

        Now literally the inherited serve loop: the spec-aware
        :meth:`_tick` is the only specialization, so cancellations,
        deadline expiries, admission control, and plan-health polling are
        ONE implementation across incremental and speculative serving —
        reaped at macro-step boundaries (the speculative analogue of the
        incremental loop's step-boundary checks)."""
        return self.serve_incr_decoding()

    _serve = serve_spec_infer
