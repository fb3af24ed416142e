"""On-device speculative-decoding macro-step scan.

TPU-first redesign of the reference's speculative serving loop (reference:
``RequestManager::serve_spec_infer`` / ``prepare_next_batch_beam`` /
``prepare_next_batch_verify`` in ``src/runtime/request_manager.cc``): the
reference re-plans every phase on the host (CPU builds a BeamSearchBatchConfig
per draft level and a TreeVerifyBatchConfig per verify, syncing results back
each time).  On TPU every host sync stalls the device for a dispatch round
trip (what one costs is for a chip run to measure), so a host-driven macro
step (depth+2 syncs per step) would be latency, not compute.

Here the ENTIRE macro step runs on device inside one ``lax.scan``:

1. *SSM catch-up* — feed the previous macro-step's accepted tokens into the
   draft model's committed cache (plain ``BatchConfig``).
2. *draft* — ``depth`` unrolled beam-expansion levels through the SSM
   (``TreeSearchBatchConfig``); per level, the global top-``width``
   candidates by cumulative logprob become the next frontier.  Because the
   beam always fills exactly ``width`` nodes per level, node indices are
   STATIC per level — tree arrays update with static slices, no scatter.
3. *verify* — one LLM ``TreeVerifyBatchConfig`` step: the commit descriptor
   carries the previous macro-step's accepted nodes (spec-buffer KV ->
   committed cache, computed once, never recomputed), then the whole tree is
   scored under the tree-topology mask (Pallas two-segment kernel).
4. *accept walk* — the greedy root-down walk, EOS masking, and the next
   step's commit/backlog bookkeeping, all as fixed-shape ``lax.scan`` steps.

The host syncs ONCE per ``n_macro`` scan: with sync latency L, per-token
overhead drops from ``(depth+2) * L / committed`` to
``L / (n_macro * committed)``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .batch_config import (
    BatchConfig,
    TreeSearchBatchConfig,
    TreeVerifyBatchConfig,
)
from .inference_manager import (
    EXIT_BUDGET,
    EXIT_EOS,
    EXIT_NOT_IN_BATCH,
    EXIT_RUNNING,
)

# per-slot budget sentinel for "no device-side max-new exit" (init_carry
# budget=None): far above any reachable emission count, so the budget
# truncation below is the identity
_NO_BUDGET = np.int32(2 ** 30)


def _pad_flat(arr, cap, fill):
    """Flatten ``arr`` and right-pad with ``fill`` to length ``cap``."""
    flat = arr.reshape(-1)
    n = flat.shape[0]
    if n > cap:
        raise ValueError(f"{n} tokens exceed batch capacity {cap}")
    out = jnp.full((cap,), fill, flat.dtype)
    return out.at[:n].set(flat)


class SpecDecodeScan:
    """Runs speculative macro-steps on device for up-to-capacity request sets.

    Built over two :class:`InferenceManager` instances (LLM + SSM) exactly
    like :class:`SpecInferManager`, but the per-macro-step work is a single
    jitted program.  Greedy invariant (tested): emitted sequences equal plain
    incremental decoding's for any draft model.
    """

    def __init__(self, llm, ssm, width: int = 2, depth: int = 3,
                 eos_token_id: Optional[int] = None):
        self.llm = llm
        self.ssm = ssm
        self.width = int(width)
        self.depth = int(depth)
        self.eos = eos_token_id
        self.n_tree = 1 + self.width * self.depth
        R = llm.max_requests
        if ssm.max_requests != R:
            raise ValueError("LLM and SSM must agree on max_requests")
        if llm.max_spec_tokens < self.n_tree or ssm.max_spec_tokens < self.n_tree:
            raise ValueError(
                f"spec buffers too small: need {self.n_tree}, have "
                f"llm={llm.max_spec_tokens} ssm={ssm.max_spec_tokens}"
            )
        if llm.max_tokens < R * self.n_tree:
            raise ValueError(
                f"LLM max_tokens_per_batch must fit {R}x{self.n_tree} tree tokens"
            )
        if ssm.max_tokens < R * max(self.width, self.depth + 1):
            raise ValueError(
                "SSM max_tokens_per_batch must fit the widest draft frontier "
                f"({R}x{self.width}) and the catch-up batch ({R}x{self.depth + 1})"
            )
        if ssm.topk < self.width:
            raise ValueError(f"SSM needs topk >= width ({self.width})")
        from .ops import DUS_MAX_TOKENS

        # _scatter_rows_pos switches paths on the flat array length the
        # step actually ships.  The scan sizes each phase's batch EXACTLY
        # (verify: R*n_tree, catch-up: R*(depth+1), draft: R*width ≤ both)
        # instead of padding to max_tokens — capacity padding multiplied the
        # per-step DUS chains / forward tokens / topk for nothing — so those
        # exact sizes are what must stay under the DUS threshold.
        for tag, cap_t in (("verify", R * self.n_tree),
                           ("catch-up", R * (self.depth + 1))):
            if cap_t > DUS_MAX_TOKENS:
                raise ValueError(
                    f"{tag} batch size ({cap_t}) exceeds the KV-write DUS "
                    f"threshold ({DUS_MAX_TOKENS}); the scatter fallback "
                    "would force a per-macro-step full-cache relayout — "
                    "use fewer request slots or a shallower/narrower tree"
                )
        # the verify batch always ships exactly n_tree tokens per request in
        # slot-major order -> the LLM can use the batched tree kernel (the
        # committed cache streams once per request, not once per tree token).
        # The layout is baked into the jitted step at first trace, so one
        # InferenceManager can serve only one (width, depth) shape.
        if llm.tree_token_layout not in (None, (R, self.n_tree)):
            raise ValueError(
                f"LLM is already bound to tree layout {llm.tree_token_layout}"
                f" != {(R, self.n_tree)}; build a separate InferenceManager"
            )
        llm.tree_token_layout = (R, self.n_tree)
        # node depth by static node index: root, then width nodes per level
        self._node_depth = np.zeros(self.n_tree, np.int32)
        for lvl in range(1, self.depth + 1):
            self._node_depth[1 + (lvl - 1) * self.width: 1 + lvl * self.width] = lvl
        from ..utils.platform import collective_safe_compiler_options

        self._scan = jax.jit(
            self._scan_impl, donate_argnums=(2,),
            static_argnames=("n_macro",),
            compiler_options=collective_safe_compiler_options(llm.model.mesh),
        )

    # ------------------------------------------------------------------
    def init_carry(self, root_tokens, llm_committed, ssm_committed, finished,
                   spec_mask=None, budget=None):
        """Build the scan carry from host bookkeeping (post-prefill).

        ``root_tokens[r]``: last generated token per slot (the tree root);
        ``llm_committed``/``ssm_committed``: committed cache depths (equal
        for active slots at macro-step boundaries); ``finished``: frozen
        slots (emit nothing, write nothing); ``spec_mask[r]`` (default
        all-True): per-slot speculation mode — False rows skip drafting
        and verify a ROOT-ONLY tree, i.e. they decode exactly one token
        per macro step in the SAME batched verify as the spec rows (the
        mixed spec/non-spec macro-step).  Plain rows still ride the
        catch-up feed, so their SSM cache stays current and a host-side
        flip between ``run()`` windows needs no rebuild.

        ``budget[r]`` (default unbounded): remaining new-token allowance
        per slot — the DEVICE-side max-new exit.  A macro-step truncates
        a row's emissions at its budget and freezes the slot, exactly
        where the host's ``_maybe_finish`` would (emission order: budget
        cut first, then EOS truncation of the survivors — first
        terminator along the token stream wins, like the per-token host
        check).  ``carry["exit_code"]`` reports why each slot froze
        (EXIT_EOS / EXIT_BUDGET; EXIT_RUNNING while live,
        EXIT_NOT_IN_BATCH for slots finished at entry) — one readback at
        window end covers lifecycle too.
        """
        R, D = self.llm.max_requests, self.depth
        if spec_mask is None:
            spec_mask = [True] * R
        if budget is None:
            budget = np.full(R, _NO_BUDGET, np.int32)
        fin0 = np.asarray(finished, bool)
        return dict(
            llm_state=self.llm.state,
            ssm_state=self.ssm.state,
            # global macro counter: the stochastic-verify key folds on THIS
            # (not the per-call scan index), so windowed run() calls sharing
            # one sample key never replay per-step keys
            macro_ctr=jnp.zeros((), jnp.int32),
            root=jnp.asarray(root_tokens, jnp.int32),
            llm_comm=jnp.asarray(llm_committed, jnp.int32),
            ssm_comm=jnp.asarray(ssm_committed, jnp.int32),
            commit_src=jnp.full((R, D + 1), -1, jnp.int32),
            commit_dst=jnp.zeros((R, D + 1), jnp.int32),
            commit_n=jnp.zeros((R,), jnp.int32),
            backlog_tok=jnp.zeros((R, D + 1), jnp.int32),
            backlog_n=jnp.zeros((R,), jnp.int32),
            finished=jnp.asarray(finished, bool),
            spec=jnp.asarray(spec_mask, bool),
            budget=jnp.asarray(budget, jnp.int32),
            exit_code=jnp.where(jnp.asarray(fin0), EXIT_NOT_IN_BATCH,
                                EXIT_RUNNING).astype(jnp.int32),
        )

    def run(self, carry, n_macro: int, sample=None):
        """Run ``n_macro`` macro-steps on device.

        Returns ``(emitted, carry)`` where ``emitted`` is
        ``i32[n_macro, R, depth+1]`` (-1 = no token) and the carry holds the
        updated KV caches + bookkeeping.  Caches are donated.  The caller
        must ensure ``llm_comm + n_macro*(depth+1) + depth < max_seq_len``.

        ``sample``: optional ``(key, temperature, top_p)`` — stochastic
        verification (see ``_macro_body``); greedy argmax walk if None.
        """
        worst = int(np.max(np.asarray(carry["llm_comm"]))) \
            + n_macro * (self.depth + 1) + self.depth
        if worst > self.llm.max_seq_len:
            raise ValueError(
                f"n_macro={n_macro} could reach position {worst} > "
                f"LLM max_seq_len {self.llm.max_seq_len}"
            )
        if worst > self.ssm.max_seq_len:
            raise ValueError(
                f"n_macro={n_macro} could reach position {worst} > "
                f"SSM max_seq_len {self.ssm.max_seq_len}"
            )
        # paged KV: committed depths advance ON DEVICE inside the scan, so
        # every page a slot's worst-case growth can reach is mapped (and
        # COW-resolved) up front — the block table is then constant for
        # the whole scan (slot-addressed: the scan has no rids)
        grow = n_macro * (self.depth + 1) + self.depth
        for im, comm_key in ((self.llm, "llm_comm"), (self.ssm, "ssm_comm")):
            kv = getattr(im, "kv", None)
            if not getattr(kv, "paged", False):
                continue
            comm = np.asarray(carry[comm_key])
            fin = np.asarray(carry["finished"])
            for r in range(im.max_requests):
                if not fin[r]:
                    kv.prepare_slot_span(
                        r, int(comm[r]),
                        min(int(comm[r]) + grow, im.max_seq_len))
        emitted, carry = self._scan(
            self.llm.params, self.ssm.params, carry, sample,
            self.llm._page_view(), self.ssm._page_view(), n_macro=n_macro
        )
        # keep the managers' views of their caches current
        self.llm.state = carry["llm_state"]
        self.ssm.state = carry["ssm_state"]
        return emitted, carry

    # ------------------------------------------------------------------
    def _scan_impl(self, llm_params, ssm_params, carry, sample,
                   llm_pages, ssm_pages, n_macro: int):
        def body(c, _):
            stp = None
            if sample is not None:
                key, temperature, top_p = sample
                stp = (jax.random.fold_in(key, c["macro_ctr"]),
                       temperature, top_p)
            return self._macro_body(llm_params, ssm_params, c, stp,
                                    llm_pages, ssm_pages)

        carry, emitted = jax.lax.scan(body, carry, None, length=n_macro)
        return emitted, carry

    def _macro_body(self, llm_params, ssm_params, c, sample=None,
                    llm_pages=None, ssm_pages=None):
        R, W, D, P = (self.llm.max_requests, self.width, self.depth,
                      self.n_tree)
        fin = c["finished"]
        smask = c["spec"]  # per-slot speculation mode (mixed macro-steps)
        slot = jnp.arange(R, dtype=jnp.int32)
        kk = jnp.arange(D + 1, dtype=jnp.int32)[None, :]          # [1, D+1]

        # ---- 1. SSM catch-up: previous macro-step's accepted tokens ----
        # every phase compiles its own program (distinct bc pytree), so
        # each uses EXACT flat sizes instead of padding to ssm.max_tokens —
        # capacity padding multiplied the per-step KV DUS chains, forward
        # tokens, and [T, vocab] topk by max_tokens/live (6x at the bench
        # shape) for no reason
        nb = jnp.where(fin, 0, c["backlog_n"])                     # [R]
        valid = kk < nb[:, None]                                   # [R, D+1]
        cap = R * (D + 1)
        bc_cu = BatchConfig(
            tokens=_pad_flat(jnp.where(valid, c["backlog_tok"], 0), cap, 0),
            request_index=_pad_flat(
                jnp.where(valid, slot[:, None], -1), cap, -1),
            token_position=_pad_flat(
                c["ssm_comm"][:, None] + kk, cap, 0),
            num_tokens=jnp.sum(valid),
            seq_lens=c["ssm_comm"] + nb,
        )
        _, ssm_state = self.ssm._step_impl(ssm_params, c["ssm_state"], bc_cu,
                                           pages=ssm_pages)
        ssm_comm = c["ssm_comm"] + nb

        # ---- 2. draft: unrolled beam levels (static node indices) ----
        Pb_s = self.ssm.max_spec_tokens
        tok = jnp.zeros((R, P), jnp.int32).at[:, 0].set(c["root"])
        par = jnp.full((R, P), -1, jnp.int32)
        cumlp = jnp.zeros((R, P), jnp.float32)
        amask = jnp.zeros((R, P, P), bool).at[:, 0, 0].set(True)

        for lvl in range(D):
            f_idx = (np.array([0], np.int32) if lvl == 0
                     else np.arange(1 + (lvl - 1) * W, 1 + lvl * W,
                                    dtype=np.int32))
            F = len(f_idx)
            ftok = tok[:, f_idx]                                   # [R, F]
            # non-spec rows never draft: their frontier tokens ship as
            # padding (no KV writes, logits ignored) — the SSM step's
            # shapes stay static, only the valid set shrinks
            reqi = jnp.broadcast_to(
                jnp.where(fin | ~smask, -1, slot)[:, None], (R, F))
            fpos = jnp.broadcast_to(
                (ssm_comm + lvl)[:, None], (R, F))
            spec = jnp.broadcast_to(jnp.asarray(f_idx)[None, :], (R, F))
            bc_d = TreeSearchBatchConfig(
                base=BatchConfig(
                    tokens=ftok.reshape(-1),        # exact R*F flat slots
                    request_index=reqi.reshape(-1),
                    token_position=fpos.reshape(-1),
                    num_tokens=jnp.sum(reqi >= 0),
                    seq_lens=ssm_comm,
                ),
                spec_index=spec.reshape(-1),
                ancestor_mask=self._pad_mask(amask, Pb_s),
                committed_lens=ssm_comm,
            )
            res, ssm_state = self.ssm._step_impl(ssm_params, ssm_state, bc_d,
                                                 pages=ssm_pages)
            k_ids = res.topk_ids[: R * F].reshape(R, F, -1)[:, :, :W]
            k_lp = res.topk_logprobs[: R * F].reshape(R, F, -1)[:, :, :W]
            cand_lp = (cumlp[:, f_idx][:, :, None] + k_lp).reshape(R, F * W)
            sel_lp, sel = jax.lax.top_k(cand_lp, W)                # [R, W]
            sel_par = jnp.asarray(f_idx)[sel // W]                 # [R, W]
            sel_tok = jnp.take_along_axis(
                k_ids.reshape(R, F * W), sel, axis=1)
            n0 = 1 + lvl * W                                       # static
            tok = jax.lax.dynamic_update_slice(tok, sel_tok, (0, n0))
            par = jax.lax.dynamic_update_slice(par, sel_par, (0, n0))
            cumlp = jax.lax.dynamic_update_slice(cumlp, sel_lp, (0, n0))
            # child mask row = parent's row + own bit (static positions)
            par_rows = jnp.take_along_axis(
                amask, sel_par[:, :, None], axis=1)                # [R, W, P]
            own = jax.nn.one_hot(
                np.arange(n0, n0 + W), P, dtype=bool)[None]        # [1, W, P]
            amask = jax.lax.dynamic_update_slice(
                amask, par_rows | own, (0, n0, 0))

        # ---- 3. LLM verify (commit descriptor from previous macro) ----
        cap_l = R * P  # exact: the verify batch is always R full trees
        depth_of = jnp.asarray(self._node_depth)                   # [P]
        # the MIXED verify batch: spec rows ship their whole tree, plain
        # rows ship the root node only (their decode token) — nodes past
        # the root become padding for non-spec slots
        node_ok = smask[:, None] | (jnp.arange(P) == 0)[None, :]   # [R, P]
        reqi_v = jnp.where(fin[:, None] | ~node_ok, -1,
                           jnp.broadcast_to(slot[:, None], (R, P)))
        pos_v = c["llm_comm"][:, None] + depth_of[None, :]
        commit_valid = kk < jnp.where(fin, 0, c["commit_n"])[:, None]
        bc_v = TreeVerifyBatchConfig(
            base=BatchConfig(
                tokens=_pad_flat(tok, cap_l, 0),
                request_index=_pad_flat(reqi_v, cap_l, -1),
                token_position=_pad_flat(pos_v, cap_l, 0),
                num_tokens=jnp.sum(reqi_v >= 0),
                seq_lens=c["llm_comm"],
            ),
            spec_index=_pad_flat(
                jnp.broadcast_to(jnp.arange(P)[None, :], (R, P)), cap_l, 0),
            ancestor_mask=self._pad_mask(amask, self.llm.max_spec_tokens),
            committed_lens=c["llm_comm"],
            commit_request_index=_pad_flat(
                jnp.where(commit_valid, slot[:, None], -1), cap_l, -1),
            commit_src_spec_index=_pad_flat(
                jnp.where(commit_valid, c["commit_src"], 0), cap_l, 0),
            commit_dst_position=_pad_flat(
                jnp.where(commit_valid, c["commit_dst"], 0), cap_l, 0),
        )
        # Stochastic verification (SpecInfer's sampling-based accept,
        # SURVEY §3.4): when ``sample`` is set, the verify step SAMPLES
        # y ~ p(target | node prefix) at every tree node (temperature +
        # top-p, seeded) instead of taking the argmax; the walk below then
        # accepts a child iff its draft token equals the sampled y.  Every
        # emitted token — accepted, correction, or bonus — is therefore a
        # fresh draw from the target conditional, so the output distribution
        # is EXACTLY the target model's sampling distribution for any draft
        # (per-node acceptance Σ p·q, vs Σ min(p,q) for the p/q-ratio
        # rejection rule — slightly lower acceptance, but no draft
        # distributions needed at verify time, and the same walk serves both
        # modes; T→0 recovers the greedy walk exactly).
        res_v, llm_state = self.llm._step_impl(
            llm_params, c["llm_state"], bc_v, sample, tree_layout=(R, P),
            pages=llm_pages)
        ids2 = res_v.token_ids[: R * P].reshape(R, P)              # [R, P]

        # ---- 4. accept walk (greedy or against the sampled tokens) ----
        def walk(wc, _):
            ni, alive = wc                                         # [R], [R]
            want = jnp.take_along_axis(ids2, ni[:, None], 1)[:, 0]
            match = (par == ni[:, None]) & (tok == want[:, None])  # [R, P]
            # non-spec rows accept no children (their tree arrays past the
            # root hold unexpanded garbage): they emit exactly the bonus
            # token per macro step — a plain decode in the shared batch
            found = match.any(1) & alive & smask
            child = jnp.argmax(match, 1).astype(jnp.int32)
            emit = jnp.where(alive, want, -1)
            src = jnp.where(found, child, -1)
            return (jnp.where(found, child, ni), found), (emit, src)

        (ni_f, alive_f), (emits, srcs) = jax.lax.scan(
            walk, (jnp.zeros((R,), jnp.int32), ~fin), None, length=D)
        emits = emits.T                                            # [R, D]
        srcs = srcs.T                                              # [R, D]
        bonus = jnp.where(
            alive_f,
            jnp.take_along_axis(ids2, ni_f[:, None], 1)[:, 0], -1)
        e = jnp.concatenate([emits, bonus[:, None]], axis=1)       # [R, D+1]
        f_cnt = jnp.sum(srcs >= 0, axis=1).astype(jnp.int32)       # children
        cnt = jnp.where(fin, 0, f_cnt + 1)   # accepted nodes incl. root

        # Device-side max-new exit: cut each row's emissions at its
        # remaining budget.  The budget cut runs BEFORE the EOS scan of
        # the survivors so the first terminator along the token stream
        # wins — exactly the host's per-token _maybe_finish order.
        bud = c["budget"]
        valid = e >= 0
        eidx = (jnp.cumsum(valid.astype(jnp.int32), axis=1)
                - valid.astype(jnp.int32))                         # [R, D+1]
        e_b = jnp.where(valid & (eidx < bud[:, None]), e, -1)

        # EOS: truncate after the first eos and freeze the slot
        if self.eos is not None:
            iseos = (e_b == self.eos) & (e_b >= 0)
            after = (jnp.cumsum(iseos.astype(jnp.int32), axis=1)
                     - iseos.astype(jnp.int32)) > 0
            e_out = jnp.where(after, -1, e_b)
            finishing = iseos.any(1)
        else:
            e_out = e_b
            finishing = jnp.zeros((R,), bool)
        n_emit = jnp.sum(e_out >= 0, axis=1).astype(jnp.int32)
        bud_new = jnp.where(fin, bud, bud - n_emit)
        hit_budget = ~fin & ~finishing & (bud_new <= 0)
        fin_new = fin | finishing | hit_budget
        cont = ~fin_new
        ecode = jnp.where(
            ~fin & finishing, EXIT_EOS,
            jnp.where(hit_budget, EXIT_BUDGET,
                      c["exit_code"])).astype(jnp.int32)

        # ---- bookkeeping for the next macro step ----
        commit_src = jnp.concatenate(
            [jnp.zeros((R, 1), jnp.int32), srcs], axis=1)          # [R, D+1]
        commit_dst = c["llm_comm"][:, None] + kk
        backlog_tok = jnp.concatenate([tok[:, :1], emits], axis=1)  # [R, D+1]
        root_new = jnp.take_along_axis(e, f_cnt[:, None], 1)[:, 0]  # bonus
        c2 = dict(
            llm_state=llm_state,
            ssm_state=ssm_state,
            macro_ctr=c["macro_ctr"] + 1,
            root=jnp.where(fin_new, c["root"], root_new),
            llm_comm=c["llm_comm"] + cnt,
            ssm_comm=ssm_comm,
            commit_src=commit_src,
            commit_dst=commit_dst,
            commit_n=jnp.where(cont, cnt, 0),
            backlog_tok=backlog_tok,
            backlog_n=jnp.where(cont, cnt, 0),
            finished=fin_new,
            spec=smask,
            budget=bud_new,
            exit_code=ecode,
        )
        return c2, e_out

    def _pad_mask(self, amask, pb: int):
        """[R, P, P] logical tree mask -> [R, pb, pb] buffer-shaped mask."""
        R, P, _ = amask.shape
        if pb == P:
            return amask
        out = jnp.zeros((R, pb, pb), bool)
        return jax.lax.dynamic_update_slice(out, amask, (0, 0, 0))
