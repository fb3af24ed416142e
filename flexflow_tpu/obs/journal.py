"""The scheduler's tick journal: one fixed-shape record per tick of the
serving loops, in a bounded ring, always on.

A profiler session (obs/trace.py ``Span``) shows a few seconds of a run;
the telemetry ring exists only when a ``Telemetry`` handle is attached.
The journal is what is there in every run: between two step boundaries it
keeps the tick's wall time split by span (self time: a span's duration
less what its child spans cover), what the tick launched, the tokens it
committed and three process counters — and when a serving loop returns it
reports the ticks that took over three times their class's median, with
what the host was doing in them — and what it was waiting FOR: the device
(``device_wait``, the span in which a readback blocks until its last
result is ready, apart from the copies ``readback`` then makes) and the
compiler (the build log below: every trace, lowering and backend compile
JAX reports, by program, as an overlay on the record it fell into).

It is the third consumer of :class:`~.trace.Span` (``jr=``, beside
``rec`` and ``prof``): fed by the entries and exits of the spans that are
there, with no ``with`` and no clock read of its own at any call site.
The tick span's ``pc_ns`` argument IS the journal's stamp of that span's
entry (one clock read, shared), so a record and its tick span on a
profiler's host plane join on that number.

Host-side only: integers the scheduler already holds, two clock reads a
span, nothing waits for the device and nothing enters a jitted program.
Measured cost: PERF.md section 6 (PR 46; ``device_wait`` and the build
log: PR 59).

Records abut: a record ends where the next begins (the entry of
``loop_arrivals`` in ``serve_with_arrivals``, :meth:`TickJournal.begin` in
``serve_incr_decoding``), and the last ends at :meth:`TickJournal.end`, so
the records of one loop tile it with no hole.  Consecutive idle polls of
the arrival loop fold into one record (``polls`` counts them).
"""

from __future__ import annotations

import collections
import itertools
import logging
import resource
import time
import weakref
from typing import (Callable, Dict, Iterable, List, NamedTuple, Optional,
                    Sequence)

import jax.monitoring
import numpy as np

# the tick spans (they carry ``pc_ns``): a record's ``kind`` is the index
# of its tick span's name here; 0 = no tick ran (an idle poll, or the
# loop's last look), the last = a tick span of another manager
KINDS = ("idle", "prefill_stretch", "decode_stretch", "serve_step", "other")
# the spans whose SELF time a record keeps, each as ``<name>_ns``: the
# closed vocabulary of PERF.md section 3's table.  Time under the tick
# span itself, or under a span that is not here (pp's stages, the
# speculative phases), is ``unattributed_ns``
SPLIT = ("host_admit", "host_prepare", "kv_prepare", "sample_for",
         "batch_sync", "join", "step_dispatch", "decode_scan_dispatch",
         "prefill_scan_dispatch", "join_dispatch", "device_wait", "readback",
         "commit",
         "loop_arrivals", "loop_bookkeep", "loop_idle", "loop_clock")
# a routed-expert graph's load as ``commit`` sets it (0 elsewhere): of the
# decode scans, and of the prompt-feeding launches (prefill scans, flat steps)
_EXPERT_LOAD = ("experts_visited", "expert_pairs", "expert_pairs_max",
                "expert_steps", "prefill_experts_visited",
                "prefill_expert_pairs", "prefill_expert_pairs_max",
                "prefill_expert_chunks")
# what ``Span.set`` — or an argument a span below the tick is entered
# with — adds to a record, by argument name
_SET = {"state_reset": "admitted",
        **{name: name for name in ("scan_tokens", "join_tokens", "step_tokens",
                                   "prefill_tokens", *_EXPERT_LOAD)}}
# what a launch that feeds prompt rows (a flat step, a prefill scan) adds:
# its argument of the field's name — past the first, as the graph's ops
# name it (serve/hybrid_ops.py ``launch_counts``; 0: a graph without)
_PROMPT_LAUNCH = ("prompt_tokens", "prompt_ring_ctx_sum", "prompt_kda_pieces")

FIELDS = (
    # extent; ``tick_ns`` = the tick span's ``pc_ns`` (0: no tick ran)
    "seq", "t0_ns", "t1_ns", "tick_ns", "kind", "polls",
    *(f"{n}_ns" for n in SPLIT), "unattributed_ns",
    # launches, from the dispatch spans' arguments
    "step_launches", "decode_scans", "prefill_scans", "joins",
    "decode_steps", "row_steps", "width_steps", "chunks", "chunk_rows",
    "chunk_tokens", "prompt_tokens", "joiners", "ctx_sum", "ctx_rows",
    # tokens, as ``commit`` sets them; admission
    "scan_tokens", "join_tokens", "step_tokens", "prefill_tokens",
    "admitted", "pending", "live",
    # the process, over the record
    "cpu_ns", "nivcsw", "majflt",
    # the decode scans' routed-expert load (``commit``): held experts that
    # got a row, pairs on held experts, the fullest expert's pairs — summed
    # over scan steps and routed layers — and steps x layers
    "experts_visited", "expert_pairs", "expert_pairs_max", "expert_steps",
    # beside ``ctx_sum``, of the same launch: the positions its rows' plain
    # RING layers read, sum of min(context, window) (0: a graph without)
    "ring_ctx_sum",
    # the build log's events that arrived while the record was open, and
    # their seconds: an OVERLAY (the time already lies under the launch
    # span that triggered the build), not part of the split
    "builds", "build_ns",
    # the prompt-feeding launches' routed-expert load (as ``commit`` sets it:
    # the decode scans' four above, of the prefill scans' chunks and the flat
    # steps that fed prompt rows; ``prefill_expert_chunks`` = chunks x routed
    # layers), and beside ``prompt_tokens`` the positions those prompt rows'
    # plain RING layers read, sum of min(position + 1, window)
    "prefill_experts_visited", "prefill_expert_pairs",
    "prefill_expert_pairs_max", "prefill_expert_chunks",
    "prompt_ring_ctx_sum",
    # beside ``prompt_tokens``, of the same launches: the PIECES (loop trips)
    # the chunked delta form runs in ONE delta-rule layer for those prompt
    # rows — a piece starts with a launch's segment and every ``chunk`` rows
    # into it (0: a graph without such a layer)
    "prompt_kda_pieces",
)
_F = {name: i for i, name in enumerate(FIELDS)}
_SPLIT_AT = {n: _F[f"{n}_ns"] for n in SPLIT}
_SET_AT = {arg: _F[field] for arg, field in _SET.items()}
_KIND_AT = {n: i for i, n in enumerate(KINDS)}
# fields a folded idle record does not add up
_SUMMED = [i for i, n in enumerate(FIELDS)
           if n not in ("seq", "t0_ns", "t1_ns", "tick_ns", "kind", "pending",
                        "live", "ctx_sum", "ctx_rows", "ring_ctx_sum")]
(_SEQ, _T0, _T1, _TICK, _KIND, _POLLS, _UNATT, _PENDING, _LIVE, _CPU, _NIV,
 _MAJ, _BUILDS, _BUILD_NS) = (_F[n] for n in (
     "seq", "t0_ns", "t1_ns", "tick_ns", "kind", "polls", "unattributed_ns",
     "pending", "live", "cpu_ns", "nivcsw", "majflt", "builds", "build_ns"))
_SPLIT_LO, _SPLIT_HI = _F[f"{SPLIT[0]}_ns"], _F[f"{SPLIT[-1]}_ns"] + 1

# the slow-tick report: constants, not options
SLOW_FACTOR = 3       # an outlier lasts more than this many class medians
SLOW_CLASS_MIN = 8    # a class needs this many records to have a median
SLOW_LINES = 4        # lines a loop's report may write
SLOW_BUILT = 3        # builds a line names, longest first
_RUSAGE = getattr(resource, "RUSAGE_THREAD", resource.RUSAGE_SELF)
_LOG = logging.getLogger("flexflow_tpu.serve")


def _launch_step(row, args, chunk_width):
    row[_F["step_launches"]] += 1
    _feed_prompt(row, args)
    _first_ctx(row, args)


def _launch_decode_scan(row, args, chunk_width):
    n = args.get("n_steps", 0)
    row[_F["decode_scans"]] += 1
    row[_F["decode_steps"]] += n
    row[_F["row_steps"]] += n * args.get("rows", 0)
    row[_F["width_steps"]] += n * args.get("width", 0)
    _first_ctx(row, args)


def _launch_prefill_scan(row, args, chunk_width):
    n = args.get("n_steps", 0)
    fed = args.get("prompt_tokens", 0)
    row[_F["prefill_scans"]] += 1
    if args.get("pad"):
        # all-pad chunks that build the program of a scan length
        # (``InferenceManager.prefill_scan``): a launch, no prompt chunk
        return
    row[_F["chunks"]] += n
    row[_F["chunk_rows"]] += n * chunk_width
    row[_F["chunk_tokens"]] += fed
    _feed_prompt(row, args)
    row[_F["joiners"]] += args.get("joiners", 0)


def _launch_join(row, args, chunk_width):
    row[_F["joins"]] += 1


def _feed_prompt(row, args):
    for name in _PROMPT_LAUNCH:
        row[_F[name]] += args.get(name, 0)


def _first_ctx(row, args):
    """``ctx_sum`` / ``ctx_rows``: the KV lengths and the rows of the
    tick's FIRST launch that decodes (their ratio is the depth the tick
    ran at); ``ring_ctx_sum``: what that launch's ring layers read."""
    rows = args.get("rows", 0)
    if rows and not row[_F["ctx_rows"]]:
        row[_F["ctx_rows"]] = rows
        row[_F["ctx_sum"]] = args.get("ctx_sum", 0)
        row[_F["ring_ctx_sum"]] = args.get("ring_ctx_sum", 0)


_LAUNCH = {"step_dispatch": _launch_step,
           "decode_scan_dispatch": _launch_decode_scan,
           "prefill_scan_dispatch": _launch_prefill_scan,
           "join_dispatch": _launch_join}


def extent_ns(rows: np.ndarray) -> np.ndarray:
    return rows[:, _T1] - rows[:, _T0]


def slow_excess_ns(rows: np.ndarray) -> np.ndarray:
    """THE rule of the slow-tick report, per record of ``rows`` (an
    :meth:`TickJournal.array`): how far its extent lies over the median of
    its class — (``kind``, ``decode_steps``, ``chunks``) where that class
    has ``SLOW_CLASS_MIN`` records or more, else all records of its
    ``kind`` if those are as many (a stall may hit a tick of a rare shape)
    — where it lies over ``SLOW_FACTOR`` medians; 0 everywhere else, and
    for idle records."""
    out = np.zeros(len(rows), np.int64)
    ext = extent_ns(rows)
    kind = rows[:, _KIND]
    todo = kind != _KIND_AT["idle"]
    for cols in ([_KIND, _F["decode_steps"], _F["chunks"]], [_KIND]):
        if not todo.any():
            break
        _, inverse, counts = np.unique(rows[:, cols], axis=0,
                                       return_inverse=True,
                                       return_counts=True)
        inverse = inverse.reshape(-1)
        for c in np.flatnonzero(counts >= SLOW_CLASS_MIN):
            members = np.flatnonzero(inverse == c)
            judged = members[todo[members]]
            if not len(judged):
                continue
            median = int(np.median(ext[members]))
            slow = judged[ext[judged] > SLOW_FACTOR * median]
            out[slow] = ext[slow] - median
            todo[judged] = False
    return out


def as_dict(row: Sequence[int]) -> Dict:
    """One record under its field names, ``kind`` as the span's name."""
    rec = {name: int(v) for name, v in zip(FIELDS, row)}
    rec["kind"] = KINDS[rec["kind"]]
    return rec


class Build(NamedTuple):
    """One event of the build log."""
    t_ns: int        # the log's clock when the event arrived (its END)
    what: str        # "trace" | "lower" | "compile"
    fun_name: str    # the program, as ``jax.jit`` names it ("?": unnamed)
    dur_ns: int
    cached: bool     # a compile the persistent cache answered


# JAX's duration events that the log keeps, by the ``what`` it files them
# under (jax 0.9.0: jax/_src/dispatch.py); ``compile`` holds the cache
# read on a hit
BUILD_WHATS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
BUILD_LOG_CAPACITY = 1024


class BuildLog:
    """What the process waited on the compiler for: every trace, lowering
    and backend compile ``jax.monitoring`` reports, newest
    ``BUILD_LOG_CAPACITY`` kept (``dropped`` counts the rest), in every
    run — a listener costs nothing until JAX builds something.  One a
    process (:func:`build_log`); each live journal's open record counts
    the events that arrive under it (``builds`` / ``build_ns``).

    ``fun_name`` is JAX's keyword with the ``jit(...)`` its lowering and
    compile events wrap the name in taken off, so a program has ONE name
    over its three events (``_decode_scan_impl``).

    Only the OUTERMOST build is an event: the trace of a program holds a
    trace of every jitted function it calls (``add``, ``multiply``: 30
    such for one lowering in a toy run of the benchmark, 6 400 events a
    run), whose seconds the program's own already hold.  JAX reports a
    build's START as a scalar under the event's name, so the log knows
    how many are open; one that ends while another is open is dropped
    unseen.  A process's builds are then hundreds, and the seconds of any
    set of events add up without counting a second twice.
    """

    def __init__(self, capacity: int = BUILD_LOG_CAPACITY):
        self.events: collections.deque = collections.deque(maxlen=capacity)
        self.emitted = 0      # lifetime count, dropped events too
        self.clock_ns = time.perf_counter_ns   # the journal's default clock
        self.journals: "weakref.WeakSet[TickJournal]" = weakref.WeakSet()
        self._hit = False     # a cache hit arrived since the last compile
        self._open = 0        # builds that have started and not ended

    @property
    def dropped(self) -> int:
        return self.emitted - len(self.events)

    def since(self, n: int) -> List[Build]:
        """The kept events from the ``n``-th the log ever took on."""
        return list(itertools.islice(
            self.events, max(n - self.dropped, 0), None))

    def _on_event(self, event: str, **_) -> None:
        if event == _CACHE_HIT:
            self._hit = True

    def _on_scalar(self, event: str, value, **_) -> None:
        if event in BUILD_WHATS:
            self._open += 1

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        what = BUILD_WHATS.get(event)
        if what is None:
            return
        self._open = max(self._open - 1, 0)
        if self._open:
            return   # inside another build, whose seconds hold this one's
        name = str(kw.get("fun_name") or "?")
        if name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]
        cached = what == "compile" and self._hit
        if what == "compile":
            self._hit = False
        dur = int(secs * 1e9)
        self.events.append(Build(self.clock_ns(), what, name, dur, cached))
        self.emitted += 1
        for jr in self.journals:
            row = jr._row
            if row is not None:
                row[_BUILDS] += 1
                row[_BUILD_NS] += dur


_BUILD_LOG: Optional[BuildLog] = None


def build_log() -> BuildLog:
    """THE log of this process; the first call registers its listeners
    with ``jax.monitoring`` (there is no unregistering: once)."""
    global _BUILD_LOG
    if _BUILD_LOG is None:
        _BUILD_LOG = BuildLog()
        jax.monitoring.register_event_listener(_BUILD_LOG._on_event)
        jax.monitoring.register_scalar_listener(_BUILD_LOG._on_scalar)
        jax.monitoring.register_event_duration_secs_listener(
            _BUILD_LOG._on_duration)
    return _BUILD_LOG


def builds(programs: Optional[Iterable[str]] = None,
           before_ns: Optional[int] = None,
           after_ns: Optional[int] = None) -> List[Build]:
    """The build log's events, oldest first: those of ``programs`` (all),
    stamped at or before ``before_ns`` and at or after ``after_ns`` on
    ``time.perf_counter_ns``."""
    names = None if programs is None else set(programs)
    return [b for b in build_log().events
            if (names is None or b.fun_name in names)
            and (before_ns is None or b.t_ns <= before_ns)
            and (after_ns is None or b.t_ns >= after_ns)]


def slow_line(rec: Dict, loop_t0_ns: int) -> str:
    """One slow tick (a record of :meth:`TickJournal.slowest`) in one line:
    when, how long against its class, the split, the process counters, the
    backlog, the launches and — where there are any — what the NEXT record
    waited for the device (as usual: the device is healthy again; long
    too: the stall outlived the tick) and what was built under it."""
    split = sorted(((rec[f"{n}_ns"], n) for n in SPLIT), reverse=True)
    split_s = " ".join(f"{n} {v / 1e6:.1f}" for v, n in split if v)
    launches = " ".join(
        f"{n} {rec[n]}" for n in ("decode_scans", "decode_steps",
                                  "prefill_scans", "chunks",
                                  "step_launches", "joins") if rec[n])
    tail = ""
    if rec.get("next_device_wait_ns") is not None:
        tail += f"; next device_wait {rec['next_device_wait_ns'] / 1e6:.1f}"
    built = sorted(rec.get("built", ()), key=lambda b: -b.dur_ns)
    if built:
        tail += "; built: " + ", ".join(
            f"{b.fun_name} {b.what} {b.dur_ns / 1e6:.1f}"
            + (" cached" if b.cached else "") for b in built[:SLOW_BUILT])
    return (
        f"slow tick: {rec['kind']} at "
        f"{(rec['t0_ns'] - loop_t0_ns) / 1e9:.3f}s into the loop took "
        f"{(rec['t1_ns'] - rec['t0_ns']) / 1e6:.1f} ms "
        f"(class median {rec['median_ns'] / 1e6:.1f} ms); split ms: {split_s} "
        f"unattributed {rec['unattributed_ns'] / 1e6:.1f}; "
        f"cpu {rec['cpu_ns'] / 1e6:.1f} ms nivcsw {rec['nivcsw']} "
        f"majflt {rec['majflt']}; pending {rec['pending']} "
        f"live {rec['live']}; launches: {launches or 'none'}{tail}")


class TickJournal:
    """See the module docstring.  One per ``RequestManager``, synced onto
    its ``InferenceManager`` as the telemetry handle is, so the launch
    spans reach it too.

    ``capacity``: records the ring holds; older ones drop and ``dropped``
    counts them.  4096 holds twenty runs of the benchmark's shortest-tick
    cell (``opt-6.7b-d12.decode-heavy``: 189 records for warm-up,
    rehearsal and the 51 s window, 165 of them the window's; my chip
    runs, PR 46), as lists of 61 integers.
    ``chunk_width``: rows of one prefill-scan chunk
    (``im.max_tokens``; ``chunk_rows`` = chunks x this).
    ``clock_ns``: the journal's clock, and the tick spans' ``pc_ns``
    (injectable for hermetic tests).
    """

    def __init__(self, capacity: int = 4096, chunk_width: int = 0,
                 clock_ns: Optional[Callable[[], int]] = None):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.chunk_width = int(chunk_width)
        self.clock_ns = clock_ns or time.perf_counter_ns
        self.emitted = 0      # lifetime count, records the ring dropped too
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._row: Optional[List[int]] = None   # the open record
        self._stack: List[List] = []   # open spans: [name, entry, covered]
        self._begun = False            # the open record has had begin()
        self._loop_t0 = 0              # where this loop's first record began
        self._loop_seq = 0             # ... and its ``seq``
        self._proc = (0, 0, 0)         # cpu_ns, nivcsw, majflt at the open
        build_log().journals.add(self)

    # ---- the ring -------------------------------------------------------
    @property
    def dropped(self) -> int:
        return self.emitted - len(self._ring)

    def array(self, newest: Optional[int] = None) -> np.ndarray:
        """The ring's records (the ``newest`` of them), oldest first: an
        int64 array ``[records, len(FIELDS)]`` (columns by
        ``FIELDS.index``)."""
        ring = self._ring
        skip = 0 if newest is None else max(len(ring) - newest, 0)
        rows = list(itertools.islice(ring, skip, None))
        return np.array(rows, np.int64).reshape(-1, len(FIELDS))

    def records(self) -> List[Dict]:
        return [as_dict(r) for r in self.array()]

    def slowest(self, k: int = SLOW_LINES,
                newest: Optional[int] = None) -> List[Dict]:
        """The outliers among the ring's records (the ``newest`` of them)
        by :func:`slow_excess_ns`, longest first, at most ``k``; each a
        record with its class's ``median_ns``, ``next_device_wait_ns`` of
        the next record in which a tick ran, where there is one, and
        ``built``, the build log's events inside it, where it counted
        any."""
        rows = self.array(newest)
        excess = slow_excess_ns(rows)
        ext = extent_ns(rows)
        order = sorted(np.flatnonzero(excess), key=lambda i: -ext[i])[:k]
        out = []
        for i in order:
            rec = dict(as_dict(rows[i]), median_ns=int(ext[i] - excess[i]))
            ran = np.flatnonzero(rows[i + 1:, _KIND] != _KIND_AT["idle"])
            if len(ran):
                rec["next_device_wait_ns"] = int(
                    rows[i + 1 + ran[0], _SPLIT_AT["device_wait"]])
            if rec["builds"]:
                rec["built"] = builds(after_ns=rec["t0_ns"],
                                      before_ns=rec["t1_ns"])
            out.append(rec)
        return out

    # ---- the loop's boundaries -----------------------------------------
    def begin(self, pending: int, live: int) -> None:
        """A tick is about to run (the sites of ``profiler.tick_begin``):
        the backlog and the slots held at its entry.  Opens a record where
        the loop's own span (``loop_arrivals``) has not."""
        if self._row is None or self._begun:
            self._roll(self.clock_ns())
        self._begun = True
        self._row[_PENDING] = pending
        self._row[_LIVE] = live

    def end(self) -> None:
        """The serving loop returned: close the open record and write the
        loop's slow ticks, one WARNING line each."""
        if self._row is None:
            return
        self._close(self.clock_ns(), time.thread_time_ns(),
                    resource.getrusage(_RUSAGE))
        self._row = None
        self._stack.clear()
        in_loop = self.emitted - self._loop_seq
        if in_loop >= SLOW_CLASS_MIN:
            for rec in self.slowest(newest=in_loop):
                _LOG.warning(slow_line(rec, self._loop_t0))

    def _roll(self, t: int) -> None:
        """``t`` ends the open record and begins the next; the process
        counters are read once for both."""
        cpu, ru = time.thread_time_ns(), resource.getrusage(_RUSAGE)
        if self._row is None:
            self._loop_t0, self._loop_seq = t, self.emitted
        else:
            self._close(t, cpu, ru)
        row = self._row = [0] * len(FIELDS)
        row[_T0] = t
        row[_POLLS] = 1
        self._begun = False
        self._proc = (cpu, ru.ru_nivcsw, ru.ru_majflt)

    def _close(self, t: int, cpu: int, ru) -> None:
        row = self._row
        row[_T1] = t
        row[_UNATT] = t - row[_T0] - sum(row[_SPLIT_LO:_SPLIT_HI])
        cpu0, niv0, maj0 = self._proc
        row[_CPU] = cpu - cpu0
        row[_NIV] = ru.ru_nivcsw - niv0
        row[_MAJ] = ru.ru_majflt - maj0
        if row[_KIND] == 0 and self.emitted > self._loop_seq:
            last = self._ring[-1]
            if last[_KIND] == 0:
                # an idle poll after an idle poll: one record
                for i in _SUMMED:
                    last[i] += row[i]
                last[_T1] = t
                return
        row[_SEQ] = self.emitted
        self._ring.append(row)
        self.emitted += 1

    # ---- Span's hooks ---------------------------------------------------
    def _enter(self, name: str, args: Dict) -> bool:
        """A span opens.  False when no record is open and this span opens
        none: the span then skips its exit too."""
        t = (args and args.get("pc_ns")) or self.clock_ns()
        if name == "loop_arrivals":
            self._roll(t)
        row = self._row
        if row is None:
            return False
        self._stack.append([name, t, 0])
        if args:
            launch = _LAUNCH.get(name)
            if launch is not None:
                launch(row, args, self.chunk_width)
            elif "pc_ns" in args:
                row[_TICK] = t
                row[_KIND] = _KIND_AT.get(name, len(KINDS) - 1)
            else:
                self._set(args)
        return True

    def _exit(self) -> None:
        if not self._stack:
            return   # the record was closed under this span (a hand-off)
        name, t_in, covered = self._stack.pop()
        dur = self.clock_ns() - t_in
        at = _SPLIT_AT.get(name)
        if at is not None:
            self._row[at] += dur - covered
        if self._stack:
            self._stack[-1][2] += dur

    def _set(self, args: Dict) -> None:
        row = self._row
        if row is None:
            return
        for k, v in args.items():
            at = _SET_AT.get(k)
            if at is not None:
                row[at] += v
