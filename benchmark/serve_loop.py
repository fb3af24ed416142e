"""Drive the deployment's own arrival loop for one window and read it.

``RequestManager.serve_with_arrivals`` takes a clock.  The benchmark's clock
is the host's ``perf_counter`` plus a hook: the loop calls it at every
host-visible step boundary, so the hook sees what a user could see — each
request's ``generated`` list as the host has it — without a thread of its
own.  It opens and closes the window, starts and stops the device trace, and
ends the run (by ``cancel``) when the window is over.  The traced span of a
closed loop is ``trace_span_s`` long from ``trace_after_s`` into the window;
that of an open loop opens at ``trace_after_s`` and is closed once the loop
has returned, so that the profiler's stop delays no request.

Open loop: the window is the arrival schedule itself (requests due in
``[0, seconds)``); the loop then drains, and whatever is still unfinished
``drain_s`` later is cancelled and counts as failed.
Closed loop: every request is queued at 0; the window opens at the first
boundary at which every slot holds a request that has produced a token, and
closes at the first boundary of that kind ``seconds`` or more later (any
boundary, once it is a tenth of the window overdue).  Tokens are counted as
the host sees them at those two boundaries.
"""

import collections
import time

# what the host has seen at one step boundary: generated tokens, prompt
# tokens of requests that have their first token, prompt tokens fed
Stamp = collections.namedtuple("Stamp", "t generated prompt_done fed")
# how much longer than asked a closed-loop window may run while it waits
# for a boundary of the kind that opened it
ALIGN_SHARE = 0.1


class WindowClock:
    def __init__(self, rm, loop, seconds, drain_s=20.0, tracer=None,
                 trace_after_s=1.0, trace_span_s=0.0):
        self.rm, self.loop, self.seconds = rm, loop, float(seconds)
        self.drain_s = float(drain_s)
        self.tracer = tracer
        self.trace_after_s, self.trace_span_s = trace_after_s, trace_span_s
        self.first_rid = rm._next_rid
        self.t0 = None            # the loop's own zero
        self.opened = None        # Stamp at the window's open
        self.closed = None        # Stamp at its close
        self.ticks = []           # closed loop: boundary times in the window
        self.trace_at = None      # (Stamp, Stamp) around the traced span
        self.trace_lens = None    # per request (prompt, generated) at each
        self._trace_open = None
        self._steps_seen = rm.steps
        self.cancelled = False    # the clock ended the run itself
        self._waiting = set()     # rids that have never left the queue
        self._started = set()     # rids that have
        self._live = set()        # those of them that are not terminal yet
        self._seen = self.first_rid
        self._done = [0, 0, 0]

    # -- what the host has seen so far ----------------------------------
    def _totals(self):
        """(generated tokens, prompt tokens of requests that have their
        first token, prompt tokens fed to the device) over this run's
        requests, as the host has them."""
        from flexflow_tpu.serve.request_manager import TERMINAL_STATUSES

        reqs = self.rm.requests
        # a request that has never left the manager's queue has no token
        # and none fed: only those that have left it are walked (set
        # arithmetic, so a backlog of thousands costs no loop here; with a
        # manager that has no ``pending`` list of rids every request is
        # walked, as before).  Nothing preempts in the benchmark's traffic:
        # a request sent BACK to the queue before its first look would be
        # taken for one that never left
        self._waiting.update(range(self._seen, self.rm._next_rid))
        self._seen = self.rm._next_rid
        queued = self._waiting.intersection(getattr(self.rm, "pending", ()))
        left = self._waiting - queued
        self._started |= left
        self._live |= left
        self._waiting = queued
        live = [0, 0, 0]
        for rid in list(self._live):
            r = reqs[rid]
            g = len(r.generated)
            row = (g, len(r.prompt) if g else 0, r.prefill_offset)
            into = live
            if r.status in TERMINAL_STATUSES:
                into = self._done
                self._live.discard(rid)
            for i, v in enumerate(row):
                into[i] += v
        return tuple(d + v for d, v in zip(self._done, live))

    def _lengths(self):
        """``{rid: (prompt length, tokens generated)}`` of this run's
        requests that have reached a slot, as of the last ``_totals``."""
        reqs = self.rm.requests
        return {rid: (len(reqs[rid].prompt), len(reqs[rid].generated))
                for rid in self._started if reqs[rid].prefill_offset}

    def _all_slots_decoding(self):
        reqs = self.rm.requests
        return all(rid is not None and reqs[rid].generated
                   for rid in self.rm.slots)

    def _cancel_all(self):
        for rid in range(self.first_rid, self.rm._next_rid):
            self.rm.cancel(rid)
        self.cancelled = True

    # -- the clock --------------------------------------------------------
    def __call__(self):
        now = time.perf_counter()
        if self.t0 is None:
            self.t0 = now
        if self.rm.steps != self._steps_seen:
            # a tick has just returned: its results are read back and
            # committed, and nothing is in flight on the device
            self._steps_seen = self.rm.steps
            self._boundary(now)
        elif (self.loop == "open" and not self.cancelled
                and now - self.t0 >= self.seconds + self.drain_s):
            self._cancel_all()
        return now

    def _boundary(self, now):
        if self.loop == "open":
            if self.opened is None:
                self.opened = Stamp(self.t0, 0, 0, 0)
        elif self.opened is None:
            if self._all_slots_decoding():
                self.opened = Stamp(now, *self._totals())
        elif self.closed is None:
            self.ticks.append(now)
            due = now - self.opened.t - self.seconds
            # close where the window opened: at a boundary at which every
            # slot holds a request that has produced a token.  Work comes
            # in phases (a wave's prompts in one tick, its answers in the
            # next), and a window that ends between two phases reads high
            # or low by half a wave; one that spans whole waves does not.
            # A program that never shows such a boundary again is closed
            # ``ALIGN_SHARE`` of the window late, wherever it is
            if due >= 0 and (self._all_slots_decoding()
                             or due >= ALIGN_SHARE * self.seconds):
                self.closed = Stamp(now, *self._totals())
                self._cancel_all()
        self._trace(now)

    def _trace(self, now):
        if (self.tracer is None or self.opened is None
                or self.trace_at is not None):
            return
        since = now - self.opened.t
        if self._trace_open is None:
            if since >= self.trace_after_s and self.closed is None:
                self.tracer.start()
                t = time.perf_counter()
                self._trace_open = Stamp(t, *self._totals())
                self._lens_open = self._lengths()
        elif self.loop == "closed" and (
                now - self._trace_open.t >= self.trace_span_s
                or self.closed is not None):
            # stopping the trace stalls the host for seconds (PERF.md
            # section 7).  A closed queue only waits; open-loop arrivals
            # would pile up behind the stall and outlast the drain, so
            # there the trace runs on until the loop has returned: finish()
            self._stop_trace(now)

    def _stop_trace(self, now):
        totals, lens = self._totals(), self._lengths()
        self.tracer.stop()
        self.trace_at = (self._trace_open, Stamp(now, *totals))
        self.trace_lens = (self._lens_open, lens)

    def finish(self):
        """After the loop returned: close what is still open."""
        now = time.perf_counter()
        if self._trace_open is not None and self.trace_at is None:
            self._stop_trace(now)
        if self.closed is None and self.opened is not None:
            self.closed = Stamp(now, *self._totals())


def run_window(rm, requests, loop, seconds, **kw):
    """Serve ``requests`` through ``rm``; returns ``(records, clock)``."""
    clock = WindowClock(rm, loop, seconds, **kw)
    records = rm.serve_with_arrivals(requests, clock=clock)
    clock.finish()
    return records, clock
