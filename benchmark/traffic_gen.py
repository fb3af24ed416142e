"""The one traffic generator: reads ``traffic/<mix>.json`` and makes requests.

The schedule is ONE FIXED STRATIFIED SAMPLE of the file's distributions, the
same for every seed: requests come in blocks of ``block``; within a block the
prompt lengths, the output lengths and (open loop) the gaps to the next
arrival are each the ``block`` mid-quantiles of their distribution, shuffled
once by a fixed generator.  So every block holds the whole grid, any stretch
of the schedule is balanced, each block of arrivals spans exactly
``block / rate`` seconds — and the tails beyond the outermost mid-quantile
(a lognormal's clip, a long Poisson burst) are never reached.  It is a
schedule with the distribution's shape, not a draw from it (PERF.md section
7 keeps a drawn schedule, and the knee on it, for a later PR).

A closed queue may be drawn in ROUNDS (``"round": n`` in the file): round
after round draws its ``n`` prompts' shuffles, then its ``n`` answers', from
the one fixed generator, and the per-seed generator hands out token ids
request by request — so the first requests of a queue do not depend on how
deep it is, and a queue can be deepened (headroom.py says how deep it has
to be) without moving what a window that never gets past its
first round sees.  A file without ``round`` is one round: all prompts'
shuffles, then all answers', as ever.

``--seed`` moves the token ids (and the weights, and the check's sequences)
and nothing of the schedule.  Why: the serving loop is deterministic, and at
0.8 of the knee a tail over ~180 requests follows the order of arrivals — a
free permutation per seed moved the 95th percentile of first-token times by
half (PERF.md section 6) — while a closed queue entered at another place
spreads tokens per second by 1 % where one order spreads them by 0.03 %.

Distributions (``dist``): ``uniform`` (lo, hi), ``lognormal`` (median, sigma,
clipped to lo..hi).  Arrival process: ``poisson`` (exponential gaps at
``rate_per_s``).
"""

import math
from statistics import NormalDist

import numpy as np

# ids below 4 are special tokens in the published vocabularies
FIRST_TOKEN_ID = 4


def _quantile(spec, u):
    dist = spec["dist"]
    if dist == "uniform":
        return spec["lo"] + (spec["hi"] - spec["lo"]) * u
    if dist == "lognormal":
        x = spec["median"] * math.exp(spec["sigma"] * NormalDist().inv_cdf(u))
        return min(max(x, spec["lo"]), spec["hi"])
    raise ValueError(f"unknown length distribution {dist!r}")


def length_grid(spec, n):
    """``n`` whole lengths at the mid-quantiles of ``spec``."""
    return [max(int(round(_quantile(spec, (i + 0.5) / n))), 1)
            for i in range(n)]


def gap_grid(arrivals, n):
    """``n`` inter-arrival gaps (seconds) with mean 1/rate."""
    if arrivals["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    return gaps / gaps.mean() / float(arrivals["rate_per_s"])


def _cycle(grid_fn, n, block, rng):
    """``n`` values, a whole number of shuffled grids."""
    out = []
    while len(out) < n:
        out.extend(rng.permutation(grid_fn(block)).tolist())
    return out


def _schedule(grid_fns, n, block, per_round, rng):
    """One column of ``n`` values per grid, drawn round by round: each
    round draws ``per_round`` values of the first grid, then of the next.
    Every round is drawn whole, so a longer schedule starts with the
    shorter one."""
    cols = [[] for _ in grid_fns]
    for _ in range(0, n, per_round):
        for col, fn in zip(cols, grid_fns):
            col.extend(_cycle(fn, per_round, block, rng)[:per_round])
    return [col[:n] for col in cols]


def schedule(mix, seconds):
    """The fixed schedule of one run, the same for every seed: a list of
    ``(due_s, prompt length, answer length)``.

    Open loop: the arrivals due inside ``seconds`` at the file's rate.
    Closed loop: ``queue_depth`` requests, all due at 0.
    """
    fixed = np.random.default_rng(0)
    block = int(mix.get("block", 8))
    if mix["loop"] == "open":
        n = max(int(round(mix["arrivals"]["rate_per_s"] * seconds)), 1)
    elif mix["loop"] == "closed":
        n = int(mix["queue_depth"])
    else:
        raise ValueError(f"unknown loop kind {mix['loop']!r}")
    grids = [lambda b: length_grid(mix["prompt_len"], b),
             lambda b: length_grid(mix["output_len"], b)]
    if mix["loop"] == "open":
        grids.append(lambda b: gap_grid(mix["arrivals"], b))
    prompts, outputs, *gaps = _schedule(grids, n, block,
                                        int(mix.get("round", n)), fixed)
    if gaps:
        gaps = np.asarray(gaps[0])
        # the mean gap of any whole block is exactly 1/rate
        due = np.cumsum(gaps) - gaps[0]
    else:
        due = np.zeros(n)
    return [(float(t), int(p), int(o))
            for t, p, o in zip(due.tolist(), prompts, outputs)]


def make_requests(mix, seed, vocab_size, seconds, max_seq_len, stream=0):
    """Requests for one run: ``schedule`` with token ids from ``seed``, a
    list of ``(due_s, prompt_ids, max_new)`` (the count is the same for
    every seed).  ``stream`` separates the rehearsal's requests from the
    window's.
    """
    rng = np.random.default_rng([int(seed), int(stream)])
    reqs = []
    for t, p, o in schedule(mix, seconds):
        if p + o > max_seq_len:
            raise ValueError(
                f"prompt {p} + output {o} exceeds max_seq_len {max_seq_len}")
        ids = rng.integers(FIRST_TOKEN_ID, vocab_size, size=p).tolist()
        reqs.append((t, ids, o))
    return reqs
