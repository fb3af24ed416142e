#!/usr/bin/env python3
"""Find an open-loop cell's knee, once, when the cell is defined.

    python benchmark/sweep.py --workload <cell> --rates 1,2,3,4 --seconds 30

One process: builds and warms the cell's deployment as run.py does, then
offers the cell's traffic at each rate in turn (the traffic file's lengths
and arrival process, its ``rate_per_s`` overridden) and prints, per rate:
offered requests per second and those completed per second over the window's
second half (the first half fills the slots), the backlog (requests due and
not finished) at the middle and at the end of the window, the tails, and
what an open cell is judged by with what stands beside it: the mean time
from due to last token, the pooled decode pace (stats.pooled_pace) and the
share of requests whose pace reads exactly 0.
The knee is the highest rate at which completions/s >= 0.95 x offered and
the backlog at the end is no larger than at the middle.  The cell's file
then gets 0.8 of it as a number; run.py never searches.
"""

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import run as harness  # noqa: E402
from benchmark import serve_loop, traffic_gen, warmup  # noqa: E402
from benchmark.stats import (decode_spans, percentile,  # noqa: E402
                             pooled_pace, zero_pace_share)


def backlog(records, t):
    return sum(1 for r in records.values()
               if r["arrival_s"] <= t < r.get("finish_s", float("inf")))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = harness.by_name(bench["workloads"], args.workload, "workload")
    hf, dep, ref = harness.load_config(harness.ROOT, bench, cell["config"])
    mix = harness.load_json(HERE, "traffic", cell["traffic"] + ".json")
    gc.disable()
    devices, _ = harness.require_device(cell["chips"])
    from flexflow_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    llm = harness.build(hf, dep, devices)
    harness.seed_weights(llm, ref, hf, args.seed, dep["precision"])
    warmup.warm(llm, mix, hf["vocab_size"], harness.log)
    gc.collect()
    gc.freeze()
    gc.enable()
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        at = dict(mix, arrivals=dict(mix["arrivals"], rate_per_s=rate))
        reqs = traffic_gen.make_requests(
            at, args.seed + i, hf["vocab_size"], args.seconds,
            dep["compile"]["max_seq_len"])
        records, clock = serve_loop.run_window(
            llm.rm, reqs, "open", args.seconds,
            drain_s=mix.get("drain_s", 20))
        ok = [r for r in records.values() if r["outcome"] == "ok"]
        half = args.seconds / 2
        done_late = [r for r in ok if half < r["finish_s"] <= args.seconds]
        ttft = [r["first_token_s"] - r["arrival_s"] for r in ok]
        spans = decode_spans(ok)
        tpot = [s / n for s, n in spans]
        print(json.dumps({
            "rate": rate, "offered": len(reqs),
            "offered_per_s": len(reqs) / args.seconds,
            "completed_per_s_2nd_half": len(done_late) / half,
            "failed": len(records) - len(ok),
            "backlog_mid": backlog(records, args.seconds / 2),
            "backlog_end": backlog(records, args.seconds),
            "ttft_p50_ms": 1e3 * (percentile(ttft, 0.5) or 0),
            "ttft_p95_ms": 1e3 * (percentile(ttft, 0.95) or 0),
            "tpot_p50_ms": 1e3 * (percentile(tpot, 0.5) or 0),
            "tpot_p95_ms": 1e3 * (percentile(tpot, 0.95) or 0),
            "tpot_pooled_ms": 1e3 * (pooled_pace(spans) or 0),
            "zero_pace_share": zero_pace_share(spans),
            "latency_mean_ms": 1e3 * sum(
                r["finish_s"] - r["arrival_s"] for r in ok) / max(len(ok), 1),
            "loop_s": clock.closed.t - clock.t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
