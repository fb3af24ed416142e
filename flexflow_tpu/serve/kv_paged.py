"""Paged KV cache with copy-on-write prefix sharing.

The capacity multiplier the ROADMAP names: through r12 KV is
slot-contiguous — every bound slot reserves ``max_seq_len`` positions of
which only the live prefix is occupied, so high-occupancy serving
fragments HBM and every request re-prefills its own copy of a fleet-wide
system prompt.  This module brings the vLLM/PagedAttention block-table
design (Kwon et al., SOSP'23) and SGLang/RadixAttention-style prefix
reuse (Zheng et al.) to the TPU serve stack, **behind the exact r12
KVAllocator interface** (``bind``/``observe``/``release``/
``bytes_per_token``/``capacity_bytes``), so admission control, preemption
pricing, the serve search, and the memory ledger keep consulting one
arithmetic:

* **Physical layout is unchanged.**  The cache buffers stay the
  ``[max_requests+1, KV, S_pad, D]`` arrays the jitted step donates; the
  allocator reinterprets each row's seq axis as ``S_pad / page_size``
  fixed pages, so the pool holds ``(R+1) * S_pad / page_size`` pages and
  a page id ``pid`` addresses ``(row, slot) = divmod(pid, pages_per_row)``
  in EVERY buffer of every stage simultaneously (one logical table; the
  per-stage pools are the per-stage physical planes, exactly the pp
  capacity contract).  The int8 scale planes ``[rows, KV, S]`` page
  alongside K/V — same (row, seq-range) coordinates, no separate table.
* **Block-table indirection, not data movement.**  A per-cache-row table
  ``i32[R+1, pages_per_row]`` maps logical page -> physical page.  The
  Pallas decode/prefill/tree kernels gather the page base per kv-chunk
  through a scalar-prefetched copy of the table
  (``ops/pallas/attention.py``); the KV write paths and the gather
  fallback translate (row, position) through the same table on device
  (``serve/ops.py``).  Masks and positions stay logical, the fetched
  values are identical, so the paged path is BIT-IDENTICAL to the
  slot-contiguous path — the correctness contract tests/test_kv_paged.py
  pins across decode/prefill/mixed/pp2/int8/spec.
* **On-demand pages.**  ``prepare_write(rid, lo, hi)`` (called by the
  RequestManager before every dispatch that writes) maps missing pages
  from the free pool, so a request holds ``ceil(live/page)`` pages
  instead of a ``max_seq_len`` span — ``kv_fragmentation_frac`` collapses
  from the slot-reservation waste to intra-page tail waste (~0, the
  headline before/after metric in ``obs/memory.py``).  Pool exhaustion
  raises :class:`PagePoolExhausted`; under ``ResilienceConfig.preemption``
  the manager preempts a victim, whose pages free page-granularly.
* **Refcounted copy-on-write prefix sharing.**  Pages are keyed by a
  chained hash of the page-aligned token prefix that produced them (KV at
  a position is a pure function of the token prefix), plus a
  partial-tail entry for the final non-aligned page.  ``bind`` maps the
  longest registered chain into the new request's table (refcount++), so
  N requests sharing a system prompt prefill it ONCE — the
  RequestManager starts the newcomer's prefill at the cached offset and
  TTFT collapses to the unshared suffix.  A write into a page another
  request maps (``req_refs >= 2``) copies the page first (all stages, k/v
  + int8 scales) and remaps the writer — divergence mid-decode lands on a
  private copy while sharers keep the original.  The index itself holds a
  reference so shared pages outlive their creator; index-only pages are
  the eviction pool (LRU) when free pages run out.

Why writes never corrupt a sharer: a request only ever READS positions at
or below its own causal frontier, and it WRITES every position from its
cached offset upward itself (prefill then decode, gapless); positions a
mapped page carries beyond the matched prefix are therefore always masked
(future) or already rewritten by the reader itself — and rewrites of
matched positions store bit-identical values (same tokens, same
positions, deterministic projection + quantizer).  COW is required
exactly when TWO requests would interleave writes into one physical page.

Everything here is host-side bookkeeping plus host-ORCHESTRATED device
ops (the COW page copy, the table transfer); no policy decision is traced
into a jitted program.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from .kv_allocator import KV_BUFFER_NAMES, KVAllocator, StageKV


class PagePoolExhausted(RuntimeError):
    """No free page and nothing evictable: the pool is over-committed.
    RequestManager._kv_prepare turns this into page-pressure preemption
    when ``ResilienceConfig.preemption`` is on; otherwise it propagates
    (an admission gate sized with ``round_need`` prevents it)."""


class HostTierCorruption(RuntimeError):
    """A host-tier page failed its checksum on restore.  NOT retryable
    (the host copy itself is damaged): the caller drops the entry and
    falls back to the r9 recompute feed, which is bit-identical by
    construction — swap is an optimization the correctness contract
    never depends on."""


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PageTable:
    """The device-side view of the block table, shipped with each step
    (``extras["pages"]``).  ``table[row, logical_page] = pid``;
    ``divmod(pid, pages_per_row)`` addresses the physical (row, page-slot)
    in every cache buffer.  Registered as a pytree so it rides jit args;
    the static fields key compilation like PrefillBatchConfig.tile_size."""

    table: Any                     # i32[R+1, pages_per_row]
    page_size: int = dataclasses.field(metadata=dict(static=True))
    pages_per_row: int = dataclasses.field(metadata=dict(static=True))


class _Entry:
    """One prefix-index record: a physical page whose content is keyed by
    the token prefix that produced it.  ``tokens`` is the page's actual
    registered token content — lookups VERIFY it (the chained hash is a
    lookup accelerator, not a trust anchor: Python's int-tuple hash is
    non-cryptographic, and a silent collision would map another prompt's
    KV into an unrelated request)."""

    __slots__ = ("pid", "lru", "tokens")

    def __init__(self, pid: int, lru: int, tokens: Tuple[int, ...]):
        self.pid = pid
        self.lru = lru
        self.tokens = tokens


class _HostPage:
    """One page's content copied to host DRAM: the per-buffer blocks in
    the allocator's deterministic ``_page_blocks`` walk order, plus a
    CRC32 over all of them.  The checksum is verified on EVERY restore —
    a corrupt host copy must fall back to recompute, never upload."""

    __slots__ = ("blocks", "crc", "nbytes")

    def __init__(self, blocks: List[np.ndarray], crc: int, nbytes: int):
        self.blocks = blocks
        self.crc = crc
        self.nbytes = nbytes

    def verify(self) -> bool:
        crc = 0
        for blk in self.blocks:
            crc = zlib.crc32(np.ascontiguousarray(blk).tobytes(), crc)
        return crc == self.crc

    def corrupt_for_test(self) -> None:
        """Flip one byte of the first block WITHOUT updating the checksum
        (chaos-test hook: a restore must detect this and recompute)."""
        raw = bytearray(np.ascontiguousarray(self.blocks[0]).tobytes())
        raw[0] ^= 0xFF
        self.blocks[0] = np.frombuffer(
            bytes(raw), dtype=self.blocks[0].dtype
        ).reshape(self.blocks[0].shape)


class _Spill:
    """One preempted/evicted request's spilled pages: logical pages
    ``[0, ceil(hi/page_size))`` of its row, the fed-token prefix that
    produced them (the content-identity witness restore verifies), and
    the write frontier ``hi`` the restore resumes at."""

    __slots__ = ("pages", "tokens", "hi", "nbytes", "lru")

    def __init__(self, pages: List[_HostPage], tokens: List[int], hi: int):
        self.pages = pages
        self.tokens = tokens
        self.hi = hi
        self.nbytes = sum(p.nbytes for p in pages)
        self.lru = 0


class _Demoted:
    """One prefix-index page demoted to the host tier instead of being
    forgotten at LRU eviction: content + the entry's token identity and
    protected extent, so a later bind can promote it back as if the
    index had never evicted it."""

    __slots__ = ("page", "tokens", "protected", "lru")

    def __init__(self, page: _HostPage, tokens: Tuple[int, ...],
                 protected: int):
        self.page = page
        self.tokens = tokens
        self.protected = protected
        self.lru = 0


class HostPageTier:
    """Bounded host-DRAM pool under :class:`PagedKVAllocator`: holds
    spilled request pages (``_Spill`` per rid) and demoted prefix-index
    pages (``_Demoted`` per index key) with ONE LRU across both kinds.

    Capacity is enforced at admission: storing a unit evicts
    least-recently-used units until it fits; a unit larger than the
    whole tier is refused (the caller falls back to recompute — the
    correctness contract never depends on a store succeeding).  Host
    numpy only (device pinning is a real-TPU nicety the CPU/test path
    has no analogue for); nothing here is traced into a jitted program,
    so attaching a tier can never change serve outputs.

    ``signature`` is the owning allocator's :meth:`PagedKVAllocator.
    swap_signature` — migration/fleet readmission adopts entries onto a
    successor allocator only when the signatures match exactly (same
    page geometry, same per-page buffer shapes/dtypes)."""

    def __init__(self, capacity_bytes: int, signature: Tuple = ()):
        self.capacity_bytes = int(capacity_bytes)
        self.signature = signature
        self.bytes_used = 0
        self.evictions = 0
        self._spills: Dict[int, _Spill] = {}
        self._demoted: Dict[Tuple, _Demoted] = {}
        self._lru_tick = 0

    def _stamp(self, unit) -> None:
        self._lru_tick += 1
        unit.lru = self._lru_tick

    def _unit_bytes(self, unit) -> int:
        return unit.nbytes if isinstance(unit, _Spill) else unit.page.nbytes

    def _make_room(self, need: int) -> bool:
        if need > self.capacity_bytes:
            return False
        while self.bytes_used + need > self.capacity_bytes:
            units = [(s.lru, 0, rid) for rid, s in self._spills.items()]
            units += [(d.lru, 1, key) for key, d in self._demoted.items()]
            if not units:
                return False
            _, kind, key = min(units)
            if kind == 0:
                self.drop_spill(key)
            else:
                self.drop_demoted(key)
            self.evictions += 1
        return True

    # ---- spilled requests --------------------------------------------
    def put_spill(self, rid: int, spill: _Spill) -> bool:
        self.drop_spill(rid)
        if not self._make_room(spill.nbytes):
            return False
        self._spills[int(rid)] = spill
        self.bytes_used += spill.nbytes
        self._stamp(spill)
        return True

    def get_spill(self, rid: int) -> Optional[_Spill]:
        s = self._spills.get(int(rid))
        if s is not None:
            self._stamp(s)
        return s

    def drop_spill(self, rid: int) -> None:
        s = self._spills.pop(int(rid), None)
        if s is not None:
            self.bytes_used -= s.nbytes

    def pop_spill(self, rid: int) -> Optional[_Spill]:
        s = self._spills.pop(int(rid), None)
        if s is not None:
            self.bytes_used -= s.nbytes
        return s

    # ---- demoted index pages -----------------------------------------
    def put_demoted(self, key: Tuple, rec: _Demoted) -> bool:
        self.drop_demoted(key)
        if not self._make_room(rec.page.nbytes):
            return False
        self._demoted[key] = rec
        self.bytes_used += rec.page.nbytes
        self._stamp(rec)
        return True

    def get_demoted(self, key: Tuple) -> Optional[_Demoted]:
        d = self._demoted.get(key)
        if d is not None:
            self._stamp(d)
        return d

    def drop_demoted(self, key: Tuple) -> None:
        d = self._demoted.pop(key, None)
        if d is not None:
            self.bytes_used -= d.page.nbytes

    # ---- occupancy ----------------------------------------------------
    def pages_held(self) -> int:
        return (sum(len(s.pages) for s in self._spills.values())
                + len(self._demoted))

    def snapshot(self) -> Dict:
        return {
            "host_pages": self.pages_held(),
            "host_bytes": self.bytes_used,
            "host_capacity_bytes": self.capacity_bytes,
            "host_spilled_requests": len(self._spills),
            "host_evictions": self.evictions,
        }


def validate_page_tile(page_size: int, prefill_tile: int) -> None:
    """Construction-time contract shared by both managers: the tiled
    prefill path writes each tile as ONE block, so a tile straddling
    a page boundary would scatter across two physical pages — fail here,
    not inside a kernel grid (sibling of the page/max_seq_len asserts)."""
    if page_size and page_size % prefill_tile:
        raise ValueError(
            f"kv_page_size {page_size} must be a multiple of the "
            f"prefill tile {prefill_tile} (tile-aligned block KV "
            "writes must not straddle a page boundary)")


def _common_prefix_len(a: Sequence[int], b: Sequence[int]) -> int:
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return n


class PagedKVAllocator(KVAllocator):
    """Block-table KV allocation with refcounted COW prefix sharing.

    Drop-in behind the r12 interface; see the module docstring for the
    design.  ``page_size`` defaults to 512 — the int8 dequant-fused
    kernel's block fetch granularity, so a kernel seq-block is exactly
    one page at production shapes.
    """

    paged = True

    def __init__(self, stages: Sequence[StageKV], max_requests: int,
                 max_seq_len: int, page_size: int = 512):
        super().__init__(stages, max_requests, max_seq_len)
        # satellite (mirror of the r6 prefill_tile divisibility fix): the
        # page geometry is validated HERE, at construction, instead of
        # failing deep inside a Pallas kernel grid — the page must tile
        # both the logical span (max_seq_len) and the 128-lane-padded
        # physical seq axis the buffers actually allocate.
        s_pad = -(-max_seq_len // 128) * 128
        if page_size <= 0:
            raise ValueError(f"page_size must be positive, got {page_size}")
        if max_seq_len % page_size:
            raise ValueError(
                f"page_size {page_size} must divide max_seq_len "
                f"{max_seq_len} (a request's logical span is whole pages)")
        if s_pad % page_size:
            raise ValueError(
                f"page_size {page_size} must divide the 128-lane-padded "
                f"cache seq axis {s_pad} (the physical pool is carved from "
                "the padded buffers; a non-dividing page would straddle "
                "the pad boundary inside the kernel grid)")
        self.page_size = int(page_size)
        self.seq_pad = s_pad
        self.pages_per_row = s_pad // page_size
        self.n_pages = (max_requests + 1) * self.pages_per_row
        # row max_requests is the pad-token scratch row; ONE page of it
        # stays permanently reserved as the scratch page every unmapped
        # table entry points at (reads are causally masked, writes are
        # discarded pad-token garbage) — the rest of the scratch row's
        # pages join the pool, which is why the paged pool's capacity
        # exceeds the slot-contiguous R * max_seq_len.
        self.scratch_pid = max_requests * self.pages_per_row
        # prefix-sharing / lifecycle counters (cumulative; snapshot()
        # publishes them through the paged gauge vocabulary)
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_tokens_reused = 0
        self.cow_copies = 0
        self.pages_evicted = 0
        # cumulative table-mapping count (every real page mapped into a
        # request's row, incl. COW destinations and prefix-reuse binds) —
        # the StepProfiler polls this into its deterministic
        # ``pages_mapped`` work counter (obs/profiler.py)
        self.pages_mapped = 0
        # host-tier swap counters (cumulative; the tier regression class
        # of obs.report.compare).  The tier itself is attached explicitly
        # (attach_host_tier) and survives allocate()/teardown(): KV at a
        # position is a pure function of the fed token prefix, so a host
        # copy stays valid across buffer reallocation.
        self.host_tier: Optional[HostPageTier] = None
        self.pages_spilled = 0
        self.pages_restored = 0
        self.swap_bytes = 0
        self.restore_failures = 0
        self.recompute_tokens_saved = 0
        self._init_pool()

    # ------------------------------------------------------------------
    def _init_pool(self) -> None:
        self._table = np.full((self.max_requests + 1, self.pages_per_row),
                              self.scratch_pid, np.int32)
        self._req_refs = np.zeros(self.n_pages, np.int32)
        self._idx_refs = np.zeros(self.n_pages, np.int32)
        # LIFO free pool, low pids first out (deterministic)
        self._free: List[int] = [p for p in range(self.n_pages - 1, -1, -1)
                                 if p != self.scratch_pid]
        self._slot_of: Dict[int, int] = {}
        self._chain: Dict[int, Dict] = {}
        # prefix index: ("f", chain_hash) -> full-page entry;
        # ("p", chain_hash, tail_tuple) -> partial-tail entry.
        # _partial_by_base buckets the partial keys per chain hash so a
        # bind's tail lookup scans its own bucket, not the whole index.
        self._entries: Dict[Tuple, _Entry] = {}
        self._partial_by_base: Dict[int, List[Tuple]] = {}
        self._key_of_pid: Dict[int, Tuple] = {}
        # pid -> protected extent (page offsets [0, n) whose content the
        # index vouches for): a write into a protected range by ANYONE
        # must copy-on-write, or the index would serve corrupted KV to
        # later matching binds (a sole-holder sharer diverging inside the
        # registered range is the dangerous case — see prepare_slot_span)
        self._protected: Dict[int, int] = {}
        self._lru_tick = 0
        self._device_table = None

    def allocate(self):
        """(Re)allocate zeroed buffers AND reset the page pool: zeroed
        caches invalidate every indexed page's content, so the prefix
        index must not survive a reallocation."""
        out = super().allocate()
        self._init_pool()
        return out

    def reset_attribution(self) -> None:
        """New serving session over the SAME buffers (rids restart): every
        request mapping releases, but the prefix index stays — its pages'
        content is still valid, so a fleet-wide prompt survives manager
        turnover (the whole point of index-held references)."""
        for rid in list(self._slot_of):
            self.release(rid)
        super().reset_attribution()

    # ------------------------------------------------------------------
    def _touch(self, key: Tuple) -> None:
        self._lru_tick += 1
        self._entries[key].lru = self._lru_tick

    def _invalidate_device(self) -> None:
        self._device_table = None

    def page_view(self) -> PageTable:
        """Device-side table pytree (cached; rebuilt after any mutation)."""
        if self._device_table is None:
            import jax.numpy as jnp

            self._device_table = PageTable(
                table=jnp.asarray(self._table),
                page_size=self.page_size,
                pages_per_row=self.pages_per_row,
            )
        return self._device_table

    # ---- pool primitives ----------------------------------------------
    def _alloc_page(self) -> int:
        if self._free:
            return self._free.pop()
        # evict least-recently-used index-only pages (no request maps them)
        victims = sorted(
            (e.lru, key) for key, e in self._entries.items()
            if self._req_refs[e.pid] == 0)
        if not victims:
            raise PagePoolExhausted(
                f"page pool exhausted: {self.n_pages - 1} pages all held by "
                "live requests (admission should gate on round_need; "
                "enable ResilienceConfig.preemption for page-pressure "
                "eviction)")
        _, key = victims[0]
        # demote the victim to the host tier before forgetting it: a
        # later bind matching the same chain promotes it back instead of
        # re-prefilling.  Full-page entries only — a partial tail is one
        # sub-page of recompute, not worth a tier slot.
        if self.host_tier is not None and key[0] == "f":
            e = self._entries[key]
            rec = _Demoted(self._read_page(e.pid), e.tokens,
                           int(self._protected.get(e.pid, self.page_size)))
            if self.host_tier.put_demoted(key, rec):
                self.pages_spilled += 1
                self.swap_bytes += rec.page.nbytes
        self._drop_entry(key)
        self.pages_evicted += 1
        return self._free.pop()

    def _drop_entry(self, key: Tuple) -> None:
        e = self._entries.pop(key)
        if key[0] == "p":
            bucket = self._partial_by_base.get(key[1], [])
            if key in bucket:
                bucket.remove(key)
            if not bucket:
                self._partial_by_base.pop(key[1], None)
        self._key_of_pid.pop(e.pid, None)
        self._protected.pop(e.pid, None)
        self._idx_refs[e.pid] = 0
        if self._req_refs[e.pid] == 0:
            self._free.append(e.pid)

    def _map(self, slot: int, k: int, pid: int) -> None:
        self._table[slot, k] = pid
        self._req_refs[pid] += 1
        self.pages_mapped += 1
        self._invalidate_device()

    def _unmap(self, slot: int, k: int) -> None:
        pid = int(self._table[slot, k])
        if pid == self.scratch_pid:
            return
        self._table[slot, k] = self.scratch_pid
        self._req_refs[pid] -= 1
        if self._req_refs[pid] == 0 and self._idx_refs[pid] == 0:
            self._free.append(pid)
        self._invalidate_device()

    def _copy_page(self, src: int, dst: int) -> None:
        """Device copy of one page's content (k/v + int8 scale planes)
        across EVERY stage's buffers — the COW data move.  Host-orchestrated
        lax slice/update with concrete indices; the updated arrays re-bind
        into the stage state dicts the next jitted step donates."""
        ps = self.page_size
        sr, ss = divmod(src, self.pages_per_row)
        dr, ds = divmod(dst, self.pages_per_row)
        for stage in self.stages:
            state = stage.state
            if not state:
                continue
            for bufs in state.values():
                for name in list(bufs):
                    if name not in KV_BUFFER_NAMES:
                        continue
                    arr = bufs[name]
                    tail = (0,) * (arr.ndim - 3)
                    blk = jax.lax.dynamic_slice(
                        arr, (sr, 0, ss * ps) + tail,
                        (1, arr.shape[1], ps) + arr.shape[3:])
                    bufs[name] = jax.lax.dynamic_update_slice(
                        arr, blk, (dr, 0, ds * ps) + tail)

    # ---- the r12 interface, page-granular -----------------------------
    def bind(self, rid: int, slot: Optional[int] = None, tokens=None,
             need: Optional[int] = None, align: int = 1,
             **_) -> Optional[Dict]:
        """Map a request into the table, reusing every registered prefix
        page its fed-token sequence matches.

        ``slot``: the cache row (required for mapping; a bare ``bind(rid)``
        degrades to attribution-only, the base behavior).  ``tokens``: the
        sequence prefill will feed (prompt, or prompt+generated on
        preemption readmission — KV is a pure function of it, so the chain
        hash covers recompute reuse too).  ``align``: the prefill tile —
        the returned ``cached_tokens`` is rounded down to it so the tiled
        prefill path's tile-aligned-start contract holds when the manager
        resumes feeding at the cached offset.

        Returns ``{"cached_tokens", "hit_pages"}``; ``cached_tokens`` is
        capped at ``len(tokens) - 1`` so the final fed position is always
        recomputed (its logits are the first-token sample point).
        """
        super().bind(rid)
        if slot is None:
            return None
        rid, slot = int(rid), int(slot)
        self._slot_of[rid] = slot
        toks = [int(t) for t in (tokens or [])]
        ps = self.page_size
        hashes: List[int] = []
        h = 0
        for k in range(len(toks) // ps):
            h = hash((h, tuple(toks[k * ps:(k + 1) * ps])))
            hashes.append(h)
        info = {"tokens": toks, "hashes": hashes, "written_hi": 0,
                "registered": 0, "tail_done": False}
        self._chain[rid] = info

        # longest registered full-page chain — each hit VERIFIES the
        # entry's stored tokens against the bind's own page (the chained
        # hash only routes the lookup; a non-cryptographic collision must
        # read as a miss, never as someone else's KV)
        hit_pids: List[int] = []
        for k, h_k in enumerate(hashes):
            e = self._entries.get(("f", h_k))
            if e is None and self.host_tier is not None:
                # promotion: a page the index evicted may still sit in
                # the host tier — checksum-verify and re-register it so
                # the chain keeps matching (as if never evicted)
                e = self._promote_full(
                    ("f", h_k), tuple(toks[k * ps:(k + 1) * ps]))
            if e is None or e.tokens != tuple(toks[k * ps:(k + 1) * ps]):
                break
            hit_pids.append(e.pid)
        cached_pages = len(hit_pids)
        cached = cached_pages * ps
        # partial-tail extension under the last matched chain hash: the
        # best entry is the one sharing the longest token prefix with the
        # remaining feed (content beyond the match is causally masked for
        # the reader — see the module docstring's safety argument)
        h_base = hashes[cached_pages - 1] if cached_pages else 0
        part_pid, best_c, part_key = None, 0, None
        for key in self._partial_by_base.get(h_base, ()):
            c = _common_prefix_len(key[2], toks[cached:])
            if c > best_c:
                best_c, part_pid, part_key = c, self._entries[key].pid, key
        usable = cached + best_c
        if toks:
            usable = min(usable, len(toks) - 1)
        if align > 1:
            usable -= usable % align
        if usable <= 0:
            if toks:  # a tokenless bind (attribution/on-demand pages
                      # only, e.g. the spec draft cache) is not a miss
                self.prefix_misses += 1
            return {"cached_tokens": 0, "hit_pages": 0}
        # map only the pages the resumed feed READS (those overlapping
        # [0, usable)); the page containing the resume point will be
        # partially re-fed — value-identical rewrites, COW if contended
        n_full = min(cached_pages, -(-usable // ps))
        for k in range(n_full):
            self._map(slot, k, hit_pids[k])
            self._touch(("f", hashes[k]))
        mapped = n_full
        if part_pid is not None and usable > cached:
            self._map(slot, cached_pages, part_pid)
            self._touch(part_key)
            mapped += 1
        info["written_hi"] = usable
        self.prefix_hits += 1
        self.prefix_tokens_reused += usable
        return {"cached_tokens": usable, "hit_pages": mapped}

    def _register(self, rid: int, info: Optional[Dict]) -> None:
        """Publish ``rid``'s finished pages into the prefix index: full
        pages once their span is written, the partial tail once the whole
        fed sequence is written (its content is then exactly the fed
        tokens — later decode writes only dirty positions BEYOND the
        matchable range, which lookups never trust)."""
        if info is None:
            return
        slot = self._slot_of.get(rid)
        if slot is None:
            return
        ps = self.page_size
        wh = info["written_hi"]
        hashes = info["hashes"]
        while (info["registered"] < len(hashes)
               and (info["registered"] + 1) * ps <= wh):
            k = info["registered"]
            self._register_entry(
                ("f", hashes[k]), int(self._table[slot, k]),
                tuple(info["tokens"][k * ps:(k + 1) * ps]), ps)
            info["registered"] += 1
        n_full = len(hashes)
        tail = tuple(info["tokens"][n_full * ps:])
        if (not info["tail_done"] and tail and wh >= len(info["tokens"])
                and info["registered"] == n_full
                and n_full < self.pages_per_row):
            h_base = hashes[-1] if hashes else 0
            self._register_entry(("p", h_base, tail),
                                 int(self._table[slot, n_full]),
                                 tail, len(tail))
            info["tail_done"] = True

    def _register_entry(self, key: Tuple, pid: int,
                        tokens: Tuple[int, ...], protected: int) -> None:
        """``protected``: page offsets [0, n) whose content the entry
        vouches for — any later write below it copy-on-writes (see
        prepare_slot_span)."""
        if pid == self.scratch_pid:
            return
        if key in self._entries or pid in self._key_of_pid:
            return  # same content already indexed, or page already keyed
        self._lru_tick += 1
        self._entries[key] = _Entry(pid, self._lru_tick, tokens)
        if key[0] == "p":
            self._partial_by_base.setdefault(key[1], []).append(key)
        self._key_of_pid[pid] = key
        self._idx_refs[pid] = 1
        self._protected[pid] = int(protected)

    def prepare_write(self, rid: int, lo: int, hi: int) -> None:
        """Make positions ``[lo, hi)`` of ``rid``'s row writable: allocate
        unmapped logical pages from the pool, copy-on-write pages another
        request maps.  Also the registration hook — content below the
        request's write frontier is final exactly here, BEFORE the next
        dispatch's writes, so pages publish with deterministic timing
        (a request's tail page registers at its first decode-write
        prepare; its own next write then COWs it away if someone mapped
        it meanwhile — divergence-mid-decode)."""
        rid = int(rid)
        slot = self._slot_of.get(rid)
        info = self._chain.get(rid)
        if slot is None or hi <= lo:
            return
        self._register(rid, info)
        self.prepare_slot_span(slot, lo, hi)
        if info is not None and hi > info["written_hi"]:
            info["written_hi"] = int(hi)

    def prepare_slot_span(self, slot: int, lo: int, hi: int) -> None:
        """Slot-addressed page mapping + COW for writes at ``[lo, hi)`` —
        the rid-less half of :meth:`prepare_write`, used directly by the
        on-device spec scan (which advances committed depths without
        per-step host boundaries, so it prepares each slot's worst-case
        span up front and skips the prefix-registration hook).

        COW fires when (a) another REQUEST maps the page, or (b) the
        write starts inside an index entry's PROTECTED extent.  (b) is
        load-bearing even for a sole holder: a request that mapped a
        registered page on a SHORTER match than the entry's (its tokens
        diverge inside the protected range) would otherwise overwrite
        content the index still vouches for, silently corrupting every
        later bind that matches the full entry.  A registrant's own
        forward writes start AT the protected boundary (offset ==
        extent), so the common decode path never pays the copy.
        """
        if hi <= lo:
            return
        ps = self.page_size
        for k in range(int(lo) // ps,
                       min((int(hi) - 1) // ps, self.pages_per_row - 1) + 1):
            pid = int(self._table[slot, k])
            if pid == self.scratch_pid:
                self._map(slot, k, self._alloc_page())
                continue
            off_lo = max(int(lo) - k * ps, 0)  # first written page offset
            protected = (self._protected.get(pid, 0)
                         if self._idx_refs[pid] else 0)
            if self._req_refs[pid] > 1 or off_lo < protected:
                dst = self._alloc_page()
                self._copy_page(pid, dst)
                self._unmap(slot, k)
                self._map(slot, k, dst)
                self.cow_copies += 1

    def release(self, rid: int, tokens: Optional[int] = None) -> float:
        """Unmap every page of the request's row (refcount--, zero-ref
        unindexed pages return to the pool) after a final registration
        pass, so a completed request's shareable prefix outlives it."""
        rid = int(rid)
        info = self._chain.pop(rid, None)
        if info is not None:
            self._register(rid, info)  # before the slot mapping drops
        slot = self._slot_of.pop(rid, None)
        if slot is not None:
            for k in range(self.pages_per_row):
                self._unmap(slot, k)
        return super().release(rid, tokens)

    def teardown(self):
        """Base teardown (release attribution + drop buffers) PLUS a page
        pool + prefix-index reset: unlike ``reset_attribution`` (same
        buffers, index content still valid), the buffers are gone here,
        so an index entry surviving would vouch for KV that no longer
        exists — the migration-retirement analogue of ``allocate``'s
        index invalidation."""
        leaked = super().teardown()
        self._init_pool()
        return leaked

    # ---- host-tier spill / restore ------------------------------------
    def attach_host_tier(self, capacity_bytes: int) -> Optional[HostPageTier]:
        """Attach a bounded host-DRAM tier (``ResilienceConfig.
        host_tier_bytes``).  Idempotent; 0/negative capacity detaches."""
        if capacity_bytes and int(capacity_bytes) > 0:
            if (self.host_tier is None
                    or self.host_tier.capacity_bytes != int(capacity_bytes)):
                self.host_tier = HostPageTier(int(capacity_bytes))
        else:
            self.host_tier = None
        return self.host_tier

    def _kv_buffers(self):
        """Deterministic (stage, node, buffer) walk over every KV plane —
        ONE ordering shared by spill capture, restore upload, and
        ``swap_signature``, so a host page's block list lines up with the
        buffers it re-enters."""
        for stage in self.stages:
            state = stage.state
            if not state:
                continue
            for node in sorted(state):
                bufs = state[node]
                for name in sorted(n for n in bufs
                                   if n in KV_BUFFER_NAMES):
                    yield bufs, name

    def swap_signature(self) -> Tuple:
        """Page-content compatibility key: page geometry plus every KV
        buffer's per-page block shape and dtype, in walk order.  Two
        allocators with equal signatures can exchange host pages
        (migration/fleet adoption); anything else must recompute."""
        blocks = tuple(
            (name, (int(bufs[name].shape[1]),) +
             tuple(int(d) for d in bufs[name].shape[3:]),
             str(bufs[name].dtype))
            for bufs, name in self._kv_buffers())
        return (self.page_size, blocks)

    def _read_page(self, pid: int) -> _HostPage:
        """Device -> host copy of one physical page across every KV
        buffer, with a chained CRC32 over the raw bytes."""
        ps = self.page_size
        r, s = divmod(int(pid), self.pages_per_row)
        blocks: List[np.ndarray] = []
        crc, nbytes = 0, 0
        for bufs, name in self._kv_buffers():
            arr = bufs[name]
            tail = (0,) * (arr.ndim - 3)
            blk = np.asarray(jax.lax.dynamic_slice(
                arr, (r, 0, s * ps) + tail,
                (1, arr.shape[1], ps) + arr.shape[3:]))
            crc = zlib.crc32(np.ascontiguousarray(blk).tobytes(), crc)
            blocks.append(blk)
            nbytes += blk.nbytes
        return _HostPage(blocks, crc, nbytes)

    def _write_page(self, pid: int, page: _HostPage) -> None:
        """Host -> device upload of one page (inverse of ``_read_page``;
        the updated arrays re-bind into the stage state dicts exactly
        like the COW copy)."""
        ps = self.page_size
        r, s = divmod(int(pid), self.pages_per_row)
        it = iter(page.blocks)
        for bufs, name in self._kv_buffers():
            arr = bufs[name]
            tail = (0,) * (arr.ndim - 3)
            bufs[name] = jax.lax.dynamic_update_slice(
                arr, next(it), (r, 0, s * ps) + tail)

    def spill(self, rid: int, tokens: Sequence[int]) -> Optional[Dict]:
        """Copy ``rid``'s written pages to the host tier — called BEFORE
        the mapping is released (preemption, page-pressure eviction,
        migration drain, brownout SPILL).  ``tokens`` is the
        authoritative fed sequence (prompt + generated): the chain's own
        token list only covers the bind-time feed, not decode-written
        positions, and restore verifies content identity against it.

        Returns ``{"pages", "nbytes", "tokens"}`` or None when nothing
        spilled (no tier, nothing written, or the tier refused — in
        every None case the r9 recompute feed covers recovery)."""
        tier = self.host_tier
        if tier is None:
            return None
        rid = int(rid)
        slot = self._slot_of.get(rid)
        info = self._chain.get(rid)
        if slot is None or info is None:
            return None
        toks = [int(t) for t in tokens]
        hi = min(int(info["written_hi"]), len(toks))
        if hi <= 0:
            return None
        ps = self.page_size
        pages: List[_HostPage] = []
        for k in range(-(-hi // ps)):
            pid = int(self._table[slot, k])
            if pid == self.scratch_pid:
                # unwritten hole (shouldn't happen below written_hi, but
                # truncate defensively: beyond here is recompute's job)
                hi = min(hi, k * ps)
                break
            pages.append(self._read_page(pid))
        pages = pages[:-(-hi // ps)] if hi > 0 else []
        if hi <= 0 or not pages:
            return None
        rec = _Spill(pages, toks, int(hi))
        tier.signature = self.swap_signature()
        if not tier.put_spill(rid, rec):
            return None  # larger than the whole tier: pure recompute
        self.pages_spilled += len(pages)
        self.swap_bytes += rec.nbytes
        return {"pages": len(pages), "nbytes": rec.nbytes,
                "tokens": int(hi)}

    def restore(self, rid: int, align: int = 1) -> Optional[Dict]:
        """Upload ``rid``'s spilled pages back onto its (re)bound row and
        advance the write frontier — called right after ``bind`` on
        readmission, so it only covers the span bind's prefix hits did
        not already map.  The spill entry is consumed either way.

        Content identity is verified first (the spilled token prefix
        must equal the new feed's — a stale entry from rid reuse drops
        silently, it is NOT a failure); every needed page is
        checksum-verified BEFORE the table mutates, and a corrupt page
        raises :class:`HostTierCorruption` with the bind result
        untouched so the caller falls back to recompute bit-identically.
        Pool exhaustion mid-upload degrades to a partial restore (the
        tail recomputes).  Returns ``{"restored_tokens", "pages",
        "nbytes", "tokens_saved"}`` or None."""
        tier = self.host_tier
        if tier is None:
            return None
        rid = int(rid)
        slot = self._slot_of.get(rid)
        info = self._chain.get(rid)
        if slot is None or info is None:
            return None
        ent = tier.get_spill(rid)
        if ent is None:
            return None
        toks = info["tokens"]
        ps = self.page_size
        n = min(int(ent.hi), len(toks) - 1 if toks else 0)
        if align > 1:
            n -= n % align
        if n <= 0 or ent.tokens[:n] != toks[:n]:
            tier.drop_spill(rid)  # stale (rid reuse / changed feed)
            return None
        cur = int(info["written_hi"])
        if n <= cur:
            tier.drop_spill(rid)  # prefix hits already cover the span
            return None
        try:
            k_lo, k_hi = cur // ps, (n - 1) // ps
            for k in range(k_lo, k_hi + 1):
                if not ent.pages[k].verify():
                    self.restore_failures += 1
                    raise HostTierCorruption(
                        f"rid {rid}: host page {k} failed its checksum "
                        "on restore")
            restored = n
            pages_up, nbytes = 0, 0
            try:
                for k in range(k_lo, k_hi + 1):
                    pid = int(self._table[slot, k])
                    exclusive = (pid != self.scratch_pid
                                 and self._req_refs[pid] == 1
                                 and self._idx_refs[pid] == 0)
                    if not exclusive:
                        # shared prefix page / index page / unmapped:
                        # land the upload on a fresh private page
                        dst = self._alloc_page()
                        self._unmap(slot, k)
                        self._map(slot, k, dst)
                        pid = dst
                    self._write_page(pid, ent.pages[k])
                    pages_up += 1
                    nbytes += ent.pages[k].nbytes
            except PagePoolExhausted:
                restored = min(n, k * ps)
                if align > 1:
                    restored -= restored % align
                if restored <= cur:
                    return None  # nothing gained; recompute covers it
            info["written_hi"] = max(cur, restored)
            gained = max(restored - cur, 0)
            self.pages_restored += pages_up
            self.swap_bytes += nbytes
            self.recompute_tokens_saved += gained
            return {"restored_tokens": int(restored), "pages": pages_up,
                    "nbytes": nbytes, "tokens_saved": int(gained)}
        finally:
            tier.drop_spill(rid)

    def has_spill(self, rid: int) -> bool:
        return (self.host_tier is not None
                and int(rid) in self.host_tier._spills)

    def drop_spill(self, rid: int) -> None:
        if self.host_tier is not None:
            self.host_tier.drop_spill(rid)

    def adopt_spills(self, other, rids: Sequence[int]) -> int:
        """Move ``rids``' spilled pages from another allocator's host
        tier onto this one (migration readmission, fleet failover) —
        only when the swap signatures match exactly; a shape-mismatched
        successor recomputes.  Attaches a tier here if absent (capacity
        inherited).  Returns the number of spills moved."""
        src = getattr(other, "host_tier", None)
        if src is None or other is self:
            return 0
        sig = self.swap_signature()
        if src.signature != sig:
            return 0
        if self.host_tier is None:
            self.host_tier = HostPageTier(src.capacity_bytes)
        self.host_tier.signature = sig
        moved = 0
        for rid in rids:
            s = src.pop_spill(int(rid))
            if s is not None and self.host_tier.put_spill(int(rid), s):
                moved += 1
        return moved

    def _promote_full(self, key: Tuple,
                      want: Tuple[int, ...]) -> Optional[_Entry]:
        """Re-register a demoted index page from the host tier (bind's
        hit-scan miss path).  Never evicts to make room — promotion into
        a full pool would recurse into demotion; a free page must exist
        or the bind just recomputes."""
        tier = self.host_tier
        rec = tier.get_demoted(key)
        if rec is None or rec.tokens != want:
            return None
        if not self._free:
            return None
        if not rec.page.verify():
            tier.drop_demoted(key)
            self.restore_failures += 1
            return None
        pid = self._free.pop()
        self._write_page(pid, rec.page)
        self._register_entry(key, pid, rec.tokens, rec.protected)
        e = self._entries.get(key)
        if e is None or e.pid != pid:  # registration refused (page keyed)
            self._free.append(pid)
            return None
        tier.drop_demoted(key)
        self.pages_restored += 1
        self.swap_bytes += rec.page.nbytes
        self.recompute_tokens_saved += self.page_size
        return e

    # ---- capacity / headroom, page-granular ---------------------------
    @property
    def capacity_tokens(self) -> int:
        """Token capacity of the page POOL (every non-scratch page times
        the page size) — any mix of requests can occupy it, which is the
        capacity-multiplier half of paging: the slot-contiguous cache
        could only ever fill R * max_seq_len of the same buffers."""
        return (self.n_pages - 1) * self.page_size

    def round_need(self, tokens: int) -> int:
        return -(-int(tokens) // self.page_size) * self.page_size

    def pages_held(self) -> int:
        """Pages currently mapped by live requests."""
        return int((self._req_refs > 0).sum())

    def pages_shared(self) -> int:
        """Pages with more than one holder (requests + index)."""
        return int(((self._req_refs + self._idx_refs) >= 2).sum())

    def snapshot(self, _per_tok: Optional[float] = None,
                 _live: Optional[int] = None) -> Dict:
        """The contiguous snapshot plus the page-pool vocabulary.
        Fragmentation becomes honest under paging: allocated-but-idle is
        only the intra-page tail waste of each request's last page, not a
        whole reserved slot span."""
        snap = super().snapshot(_per_tok, _live)
        per_tok = snap["capacity_bytes"] / max(self.capacity_tokens, 1)
        held = self.pages_held()
        live = snap["live_tokens"]
        free = len(self._free)
        evictable = sum(1 for e in self._entries.values()
                        if self._req_refs[e.pid] == 0)
        snap.update({
            "fragmentation_frac": (1.0 - live / (held * self.page_size)
                                   if held else 0.0),
            # free + evictable is what a new request can actually get
            "headroom_bytes": (free + evictable) * self.page_size * per_tok,
            "page_size": self.page_size,
            "pages_total": self.n_pages - 1,
            "pages_live": held,
            "pages_shared": self.pages_shared(),
            "pages_free": free,
            "pages_indexed": len(self._entries),
            "prefix_hits": self.prefix_hits,
            "prefix_misses": self.prefix_misses,
            "prefix_tokens_reused": self.prefix_tokens_reused,
            "cow_copies": self.cow_copies,
            "pages_evicted": self.pages_evicted,
            "pages_mapped_total": self.pages_mapped,
            "pages_spilled": self.pages_spilled,
            "pages_restored": self.pages_restored,
            "swap_bytes": self.swap_bytes,
            "restore_failures": self.restore_failures,
            "recompute_tokens_saved": self.recompute_tokens_saved,
        })
        if self.host_tier is not None:
            snap.update(self.host_tier.snapshot())
        return snap

    # ---- diagnostics ---------------------------------------------------
    def logical_state(self, slot: int, depth: Optional[int] = None) -> Dict:
        """Reconstruct one slot's logical cache rows through the table
        (numpy; the bit-identity tests compare this against the
        slot-contiguous run's rows).  ``depth`` truncates to the live
        prefix — positions beyond a request's frontier are unmapped or
        junk by design."""
        ps, ppr = self.page_size, self.pages_per_row
        pids = self._table[slot]
        out: Dict[str, Dict[str, np.ndarray]] = {}
        for si, stage in enumerate(self.stages):
            state = stage.state or {}
            for node, bufs in state.items():
                got: Dict[str, np.ndarray] = {}
                for name, arr in bufs.items():
                    if name not in KV_BUFFER_NAMES:
                        continue
                    a = np.asarray(arr)
                    parts = []
                    for pid in pids:
                        r, s = divmod(int(pid), ppr)
                        parts.append(a[r, :, s * ps:(s + 1) * ps])
                    row = np.concatenate(parts, axis=1)
                    got[name] = row[:, :depth] if depth is not None else row
                out[node] = got
        return out
