"""Fixed-capacity batch descriptors shipped to the device each serving step.

The TPU-native analogue of FlexFlow's ``BatchConfig`` family (reference:
``include/flexflow/batch_config.h``, ``src/runtime/batch_config.cc`` and the
beam/tree variants): a POD struct of fixed-size arrays describing which
requests and tokens are in flight.  The reference ships it to every GPU as a
Legion future each step; here it is a JAX pytree of small arrays passed into
the jitted decode step.  Fixed capacities are a *feature* on TPU: every step
has identical shapes, so XLA compiles the decode program exactly once.

Layout follows the reference's flat-token design: a step processes up to
``max_tokens`` tokens belonging to up to ``max_requests`` request slots;
per-token arrays say which slot each token belongs to and at which absolute
sequence position it sits.  Prefill (many tokens of one request) and decode
(one token per request) ride the same struct — the continuous-batching mix
FlexFlow's RequestManager produces.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# Capacity defaults (analogous to the reference's BatchConfig constants).
MAX_NUM_REQUESTS = 8
MAX_NUM_TOKENS = 64
MAX_SPEC_TREE_TOKENS = 64


def _field(**meta):
    return dataclasses.field(metadata=meta)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BatchConfig:
    """One incremental-decoding step's worth of work.

    All arrays are capacity-padded; ``num_tokens`` marks the valid prefix.
    Padding token slots carry ``request_index == -1`` so their writes land in
    a scratch cache row and their logits are ignored.
    """

    tokens: jax.Array           # i32[max_tokens] input token ids
    request_index: jax.Array    # i32[max_tokens] slot per token (-1 = pad)
    token_position: jax.Array   # i32[max_tokens] absolute seq position
    num_tokens: jax.Array       # i32[] valid token count
    seq_lens: jax.Array         # i32[max_requests] cache depth AFTER this step

    @property
    def max_tokens(self) -> int:
        return self.tokens.shape[0]

    @property
    def max_requests(self) -> int:
        return self.seq_lens.shape[0]

    def advance(self, token_ids: jax.Array) -> "BatchConfig":
        """Next pure-decode step's config, computed ON DEVICE.

        For a batch where every valid slot is a decode token (one token per
        active request), the next step feeds each slot the token just
        produced for it, one position further.  This is what lets the decode
        loop run as a ``lax.scan`` entirely on device — the TPU-native
        answer to the reference's per-step host round trip through
        ``RequestManager::prepare_next_batch`` (the host only syncs every
        N steps).  Prefill/mixed batches must go through ``build``.
        """
        active = self.request_index >= 0
        req = jnp.clip(self.request_index, 0, self.max_requests - 1)
        seq_lens = self.seq_lens + jnp.zeros_like(self.seq_lens).at[req].add(
            active.astype(self.seq_lens.dtype)
        )
        return BatchConfig(
            tokens=jnp.where(active, token_ids, self.tokens),
            request_index=self.request_index,
            token_position=self.token_position + active.astype(jnp.int32),
            num_tokens=self.num_tokens,
            seq_lens=seq_lens,
        )

    def join_row(self, dst, tok, slot, pos, seq_len, num_tokens,
                 active=True) -> "BatchConfig":
        """Masked slot activation: merge ONE staged arrival into a running
        scan's batch, on device.

        A multi-step decode scan advances its BatchConfig entirely on
        device, so an arrival admitted mid-stretch cannot be spliced in by
        rebuilding the batch on host (that would force a sync).  Instead
        the host prefills the prompt asynchronously, then activates flat
        row ``dst`` for slot ``slot`` with the prefill's produced token
        ``tok`` at position ``pos`` (= prompt length): the next scan
        segment picks the row up exactly as if it had been in the batch
        from the start.  ``active=False`` installs the row pre-frozen
        (``request_index=-1``) — used when the prefill token already
        terminated the request (EOS), so the scan never decodes past it.
        All operands may be traced scalars; shapes are unchanged, so the
        consuming scan's compiled program is reused as-is.
        """
        slot_i = jnp.asarray(slot, jnp.int32)
        return BatchConfig(
            tokens=self.tokens.at[dst].set(jnp.asarray(tok, jnp.int32)),
            request_index=self.request_index.at[dst].set(
                jnp.where(jnp.asarray(active), slot_i,
                          jnp.int32(-1))),
            token_position=self.token_position.at[dst].set(
                jnp.asarray(pos, jnp.int32)),
            num_tokens=jnp.asarray(num_tokens, jnp.int32),
            seq_lens=self.seq_lens.at[slot_i].set(
                jnp.asarray(seq_len, jnp.int32)),
        )

    def split_microbatches(self, n_micro: int) -> list:
        """Split the flat token batch into ``n_micro`` contiguous ranges —
        the decode-time micro-batches pipeline-parallel serving interleaves
        across stages (Orca-style).

        Exact by construction: the builders lay a request's tokens out
        contiguously in ascending position order, so a contiguous range
        split preserves in-request ordering; a token's causal frontier only
        ever reaches KV written by earlier flat slots (same micro-batch:
        written before attending, as in the flat step) or by earlier
        micro-batches (committed before that micro-batch runs).  Each
        micro-batch keeps the full ``seq_lens`` (attention masks use
        ``token_position`` only) and clips ``num_tokens`` to its range.
        """
        if n_micro <= 1 or self.max_tokens % n_micro:
            return [self]
        k = self.max_tokens // n_micro
        out = []
        for j in range(n_micro):
            lo = j * k
            out.append(BatchConfig(
                tokens=self.tokens[lo: lo + k],
                request_index=self.request_index[lo: lo + k],
                token_position=self.token_position[lo: lo + k],
                num_tokens=jnp.clip(self.num_tokens - lo, 0, k),
                seq_lens=self.seq_lens,
            ))
        return out

    @staticmethod
    def build(
        token_ids,
        request_indices,
        positions,
        seq_lens,
        max_tokens: int = MAX_NUM_TOKENS,
        max_requests: int = MAX_NUM_REQUESTS,
    ) -> "BatchConfig":
        """Host-side constructor from variable-length lists (pads to capacity)."""
        n = len(token_ids)
        if n > max_tokens:
            raise ValueError(f"{n} tokens > capacity {max_tokens}")
        tokens = np.zeros(max_tokens, np.int32)
        req = np.full(max_tokens, -1, np.int32)
        pos = np.zeros(max_tokens, np.int32)
        tokens[:n] = token_ids
        req[:n] = request_indices
        pos[:n] = positions
        sl = np.zeros(max_requests, np.int32)
        sl[: len(seq_lens)] = seq_lens
        return BatchConfig(
            tokens=jnp.asarray(tokens),
            request_index=jnp.asarray(req),
            token_position=jnp.asarray(pos),
            num_tokens=jnp.asarray(n, jnp.int32),
            seq_lens=jnp.asarray(sl),
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PrefillBatchConfig:
    """A prompt-prefill step whose flat tokens are grouped into request-
    homogeneous tiles, unlocking the Q-tiled Pallas prefill kernel.

    The reference's IncMHA CUDA kernel serves prompt and decode phases with
    one code path (``inc_multihead_self_attention.cu``); on TPU the two
    phases want different grids — decode is one query per cache row
    (bandwidth-bound), prefill is a *block* of queries per cache row
    (MXU-bound) — so prefill ships this wrapper type and the attention op
    mode-dispatches on it like the tree variants.

    Contract (enforced by :meth:`build`): with ``Bq = tile_size`` and
    ``G = base.max_tokens // Bq``, flat slot ``g*Bq + b`` belongs to tile
    ``g``; each tile's real tokens (a) belong to ONE request, (b) sit at the
    tile's head with pad slots only at the tail, (c) have contiguous
    ascending positions, and (d) start at a TILE-ALIGNED position
    (``start_pos % Bq == 0``) — the attention op writes each tile's KV as
    one block (``ops.put_blocks``), and alignment (with the cache's seq
    capacity a multiple of the tile) guarantees the block is a whole tile
    of the cache, never clamp-shifted.  The kernel then reconstructs every per-token causal
    mask from the tile's first position alone.

    **LM-head gating** (``logit_slots``): a prefill chunk only needs logits
    at each request's LAST prompt token (the first-generated-token sample
    point); every other position's logits are computed and thrown away —
    at the 7B bench shape the LM head is ~9% of a 512-token chunk's GEMM
    flops.  When ``logit_slots`` is set (i32[max_requests]; the flat token
    index of slot r's prompt-final token in THIS chunk, -1 = this chunk
    carries no sample point for r), the LM head gathers those <=
    max_requests hidden rows and computes a [max_requests, vocab] GEMM
    instead of [max_tokens, vocab]; mid-prompt chunks (all -1) pay only
    that negligible gathered GEMM.  The step's InferenceResult arrays are
    then indexed BY SLOT, not by flat token.  ``None`` keeps the full
    per-position logits (the oracle path gating is tested against).
    """

    base: BatchConfig
    tile_size: int = dataclasses.field(metadata=dict(static=True))
    logit_slots: Optional[jax.Array] = None  # i32[max_requests] or None

    @property
    def num_tiles(self) -> int:
        return self.base.max_tokens // self.tile_size

    @staticmethod
    def build(
        segments,
        seq_lens,
        tile_size: int,
        max_tokens: int = MAX_NUM_TOKENS,
        max_requests: int = MAX_NUM_REQUESTS,
        gate_slots=None,
    ):
        """Tile-aligned constructor.

        ``segments``: iterable of ``(slot, token_ids, start_pos)`` — one
        contiguous prompt chunk per request, laid end to end in whole tiles:
        a prefill wave's chunk holds several (the tail of one prompt, whole
        short ones, the head of the next: ``RequestManager._prefill_chunks``),
        a lone request's feed one.  Returns ``(pbc, last_flat)``
        where ``last_flat[slot]`` is the flat index of that segment's final
        token (where its first-generated-token logits appear).
        ``num_tokens`` is the flat index past the last real row — the pads
        between segments included, NOT the tokens fed.

        ``gate_slots``: iterable of slots whose segment ENDS its prompt in
        this chunk — enables LM-head gating (``logit_slots`` built from
        ``last_flat``; the caller knows which segments complete, the
        builder only knows where each segment ends).  None = full logits.
        """
        fields, last_flat = PrefillBatchConfig.np_fields(
            segments, seq_lens, tile_size, max_tokens, max_requests
        )
        base = BatchConfig(*(jnp.asarray(f) for f in fields))
        ls = None
        if gate_slots is not None:
            ls = PrefillBatchConfig.np_logit_slots(
                gate_slots, last_flat, max_requests)
            ls = jnp.asarray(ls)
        return (
            PrefillBatchConfig(base=base, tile_size=tile_size,
                               logit_slots=ls),
            last_flat,
        )

    @staticmethod
    def np_logit_slots(gate_slots, last_flat, max_requests):
        """i32[max_requests] logit_slots array from the completing slots
        (host-side half, stackable like :meth:`np_fields`)."""
        ls = np.full(max_requests, -1, np.int32)
        for slot in gate_slots:
            ls[slot] = last_flat[slot]
        return ls

    @staticmethod
    def np_fields(segments, seq_lens, tile_size, max_tokens, max_requests):
        """:meth:`build`'s host-side half: the five BatchConfig fields as
        numpy arrays (field order) — callers that stack many chunks (the
        RequestManager's prefill stretch) stack these and transfer once,
        instead of shipping five tiny arrays to the device per chunk."""
        if max_tokens % tile_size:
            raise ValueError(
                f"tile_size {tile_size} must divide max_tokens {max_tokens}"
            )
        tokens = np.zeros(max_tokens, np.int32)
        req = np.full(max_tokens, -1, np.int32)
        pos = np.zeros(max_tokens, np.int32)
        last_flat = {}
        at = 0
        n = 0
        for slot, toks, start in segments:
            if start % tile_size:
                raise ValueError(
                    f"segment start {start} not aligned to tile_size "
                    f"{tile_size} (contract (d): the block KV write needs "
                    "tile-aligned positions)"
                )
            need = -(-len(toks) // tile_size) * tile_size  # round up to tiles
            if at + need > max_tokens:
                raise ValueError(
                    f"segments need {at + need} padded slots > capacity "
                    f"{max_tokens}"
                )
            tokens[at: at + len(toks)] = toks
            req[at: at + len(toks)] = slot
            pos[at: at + len(toks)] = np.arange(start, start + len(toks))
            last_flat[slot] = at + len(toks) - 1
            n = at + len(toks)
            at += need
        sl = np.zeros(max_requests, np.int32)
        sl[: len(seq_lens)] = seq_lens
        fields = (tokens, req, pos, np.asarray(n, np.int32), sl)
        return fields, last_flat


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TreeSearchBatchConfig:
    """Draft-model (SSM) tree-expansion step.

    Reference: ``BeamSearchBatchConfig``.  The step's tokens are nodes being
    added to each request's speculation tree; ``spec_index`` is the node's
    index within the per-request tree buffer, ``ancestor_mask[r, i, j]`` says
    tree node ``i`` of request ``r`` may attend tree node ``j`` (its root-path
    ancestors and itself).  Committed-cache attention stays causal on
    ``token_position``.
    """

    base: BatchConfig
    spec_index: jax.Array     # i32[max_tokens] tree-node slot per step token
    ancestor_mask: jax.Array  # bool[max_requests, max_spec, max_spec]
    committed_lens: jax.Array  # i32[max_requests] committed cache depth

    @property
    def max_spec_tokens(self) -> int:
        return self.ancestor_mask.shape[-1]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TreeVerifyBatchConfig:
    """LLM verification step over flattened speculation trees.

    Reference: ``TreeVerifyBatchConfig``.  Same tree-attention layout as
    :class:`TreeSearchBatchConfig` — the whole tree arrives in ONE step and is
    verified with the tree-topology causal mask — plus the commit descriptor:
    tokens accepted in the *previous* macro-step whose KV (saved in the spec
    buffer) must be copied into the committed cache before attending.

    **Mixed spec/non-spec batches** (ISSUE 11): a request in plain decode
    mode rides the same verify step as a DEGENERATE root-only tree — one
    node (its decode token) whose ancestor mask is just the self bit.
    The tree attention of a single root node reduces exactly to ordinary
    decode attention over the committed prefix, so spec rows verify
    multi-token while plain rows decode one token in one batched step;
    the accept walk trivially emits the plain row's sampled/argmax token
    (no children to match).  Builders: ``SpecInferManager._draft_phase``
    (host) and ``SpecDecodeScan`` with ``spec_mask`` (on-device).
    """

    base: BatchConfig
    spec_index: jax.Array      # i32[max_tokens]
    ancestor_mask: jax.Array   # bool[max_requests, max_spec, max_spec]
    committed_lens: jax.Array  # i32[max_requests]
    # commit descriptor (flat, capacity-padded, request_index -1 = pad):
    commit_request_index: jax.Array  # i32[max_commit]
    commit_src_spec_index: jax.Array  # i32[max_commit] slot in spec buffer
    commit_dst_position: jax.Array   # i32[max_commit] cache position to fill

    @property
    def max_spec_tokens(self) -> int:
        return self.ancestor_mask.shape[-1]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class InferenceResult:
    """Per-step device output consumed by the RequestManager.

    Reference: ``InferenceResult`` (token ids produced for each flat token
    slot).  ``logprobs``/``topk`` are optional extensions used by sampling and
    speculation.
    """

    token_ids: jax.Array   # i32[max_tokens] next-token id per flat slot
    logits_max: jax.Array  # f32[max_tokens] (argmax logit, diagnostics)
    topk_ids: Optional[jax.Array] = None     # i32[max_tokens, k]
    topk_logprobs: Optional[jax.Array] = None  # f32[max_tokens, k]
    # a routed graph's load this step, summed over its routed layers: i32[3]
    # [experts visited, pairs, the fullest expert's pairs] (None, and nothing
    # in the program, for a graph without such layers)
    expert_load: Optional[jax.Array] = None
