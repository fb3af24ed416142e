#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no child.  Refuses (non-zero exit, no result line) unless JAX
finds a TPU whose ``device_kind`` is in ``peaks.json`` and as many chips as
the cell asks for.  Everything that belongs to one configuration, one traffic
mix or one per-layer metric is a file found by its name in ``BENCHMARK.json``
(see README.md); this file holds no branch on any of those names.

Set-up (all of it is ``setup_s``): build the deployment through
``LLM(...).compile(...)``, draw the weights from ``--seed`` on the device,
warm the programs the cell's traffic can reach, decide ``correct`` against
the plain float32 reference, rehearse a few seconds of the cell's own
traffic.  Then the window; after it, tokens the window served are read
against the reference too.  Last line of stdout: the result as one JSON
object.
"""

import time

T_START = time.perf_counter()

import argparse
import gc
import importlib
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def log(msg):
    print(msg, flush=True)


def die(msg, code=2):
    sys.stderr.write(f"benchmark: {msg}\n")
    sys.exit(code)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    die(f"no {what} named {name!r} in BENCHMARK.json")


def load_config(root, bench, name):
    """A configuration by its name: ``(published fields, the benchmark's
    own block, reference module)``."""
    conf = load_json(root, by_name(bench["configs"], name,
                                   "configuration")["file"])
    hf = {k: v for k, v in conf.items() if k != "benchmark"}
    ref = importlib.import_module("benchmark.reference." + hf["model_type"])
    return hf, conf["benchmark"], ref


def load_module(path):
    """A module from a file whose name may hold dots (metric names do)."""
    spec = importlib.util.spec_from_file_location(
        "benchmark._file_" + os.path.basename(path).replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def require_device(chips):
    """The device as JAX reports it, with its peaks; exits unless it is a
    TPU in ``peaks.json`` with at least ``chips`` chips."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        die(f"needs a TPU; JAX found platform={d0.platform!r} "
            f"({d0.device_kind}); not falling back")
    peaks = load_json(HERE, "peaks.json")
    if d0.device_kind not in peaks:
        die(f"device_kind {d0.device_kind!r} is not in peaks.json; "
            "a device without published peaks is an error, not a default")
    if len(devs) < chips:
        die(f"the cell asks for {chips} chip(s), JAX found {len(devs)}")
    return devs[:chips], peaks[d0.device_kind]


class CompileWatch:
    """Every lowering and backend compile JAX reports, with its time."""

    def __init__(self):
        import jax

        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name.endswith(("jaxpr_to_mlir_module_duration",
                          "backend_compile_duration")):
            self.events.append((time.perf_counter(), name.rsplit("/", 1)[-1],
                                secs))

    def between(self, t_lo, t_hi):
        return [e for e in self.events if t_lo <= e[0] <= t_hi]

    def seconds(self):
        out = {}
        for _, n, s in self.events:
            out[n] = out.get(n, 0.0) + s
        return out


class Tracer:
    """``jax.profiler`` around a span inside the window; device lines only
    (the Python tracer would write millions of host events)."""

    def __init__(self, trace_dir):
        self.dir = trace_dir
        self.t_start = self.t_stop = None

    def start(self):
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_start = time.perf_counter()

    def stop(self):
        import jax

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()


def build(hf, dep, devices):
    """The deployment, through the normal entry point."""
    from flexflow_tpu.serve import LLM
    from flexflow_tpu.serve.models.base import ServeModelConfig
    from flexflow_tpu.serve.request_manager import GenerationConfig

    cfg = ServeModelConfig.from_hf_config(hf)
    # every request runs to the length the traffic file asks for
    gen = GenerationConfig(stop_on_eos=False)
    return LLM(cfg).compile(tp=dep["tp"], devices=devices,
                            generation_config=gen, **dep["compile"])


def seed_weights(llm, ref, hf, seed, dtype):
    """Replace the library's own random weights (``PRNGKey(0)``;
    ``LLM.compile`` takes no seed) by the benchmark's, drawn from
    ``--seed``: the reference draws the same tensors for itself."""
    import jax

    from benchmark import seeded_weights as sw

    im = llm.im
    like = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        im.params)
    for leaf in jax.tree.leaves(im.params):
        leaf.delete()
    key = sw.base_key(seed)
    im.params = sw.program_params(ref, hf, key, like, dtype)
    jax.block_until_ready(im.params)
    return key


def peak_memory(devices):
    """Peak bytes on the fullest chip: live arrays plus what compiled
    programs reserved for their temporaries (the runtime reports the two
    apart; their sum matched the AOT analysis to 0.4 %, PR 24)."""
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0))
                   + int(st.get("peak_bytes_reserved", 0)))
    return peak


def _pct(q):
    from benchmark.stats import percentile

    return lambda xs: percentile(xs, q)


# the statistic a latency metric's name ends in: ``ttft_<statistic>`` of the
# first-token times and ``latency_<statistic>`` of the whole requests' times
# (both from the due time), ``tpot_<statistic>`` of the per-request pace.
# ``tpot_pooled_ms`` is no statistic of the per-request paces: it is the sum
# of the decode spans over the sum of their tokens (stats.pooled_pace)
STATISTICS = {"mean_ms": lambda xs: sum(xs) / len(xs), "p50_ms": _pct(0.5),
              "p90_ms": _pct(0.9), "p95_ms": _pct(0.95)}


def end_to_end(mix, records, clock, seconds):
    """The window's end-to-end metrics, by the traffic file's ``reports``.
    A request that failed counts as having waited until the loop ended.  A
    metric with nothing under it (no request with a second token) is left
    out, never reported as 0."""
    from benchmark import stats

    out, counts = {}, {}
    worst = clock.closed.t - clock.t0 if clock.closed else seconds

    def since_due(stamp):
        return [(r[stamp] - r["arrival_s"])
                if r["outcome"] == "ok" and stamp in r
                else max(worst - r["arrival_s"], 0.0)
                for r in records.values()]

    spans = stats.decode_spans(records.values())
    tails = {"ttft": since_due("first_token_s"),
             "latency": since_due("finish_s"),
             "tpot": [s / n for s, n in spans]}
    pooled = {"tpot": stats.pooled_pace(spans)}

    def in_ms(xs):
        return {k: round(1e3 * f(xs), 3) for k, f in STATISTICS.items()}

    if mix["loop"] == "open":
        # printed on every run, judged only where reported: where a first
        # token's wait was spent (the due time to the loop picking the
        # request up, and to its first prompt chunk); the whole request's
        # time; the per-request pace, the share of requests on its point
        # mass at 0 and the pooled pace that has none (PERF.md section 2)
        for label, later in (("admit late", "admitted_s"),
                             ("queue wait", "prefill_start_s")):
            xs = [r[later] - r["arrival_s"] for r in records.values()
                  if later in r]
            if xs:
                counts[f"{label} p95 ms"] = round(
                    1e3 * STATISTICS["p95_ms"](xs), 1)
        counts["latency ms"] = in_ms(tails["latency"])
        if spans:
            counts["tpot ms"] = in_ms(tails["tpot"])
            counts["tpot pooled ms"] = round(1e3 * pooled["tpot"], 3)
            counts["zero-pace share"] = round(stats.zero_pace_share(spans), 3)
    for name in mix["reports"]:
        what, _, stat = name.partition("_")
        if what not in tails or not tails[what]:
            continue
        value = (pooled[what] if stat == "pooled_ms"
                 else STATISTICS[stat](tails[what]))
        out[name] = (1e3 * value, "ms")
        counts[f"{what} samples"] = len(tails[what])
        counts.setdefault(f"{what} ms", in_ms(tails[what]))
    if "total_tok_s" in mix["reports"]:
        a, b = clock.opened, clock.closed
        generated = b.generated - a.generated
        prompt = b.prompt_done - a.prompt_done
        out["total_tok_s"] = ((generated + prompt) / (b.t - a.t), "tokens/s")
        counts["window seconds"] = round(b.t - a.t, 3)
        counts["generated tokens"] = generated
        counts["prompt tokens"] = prompt
    return out, counts


def no_window(mix, requests, clock, seconds, t_end):
    """Why a run has no window to report: it never opened, or (closed loop)
    the loop served its whole queue and returned before the window closed.
    Says the pace that emptied the queue and the depth that would have
    outlasted the window at it, with headroom.py's margin."""
    from benchmark.headroom import HEADROOM

    loop_s = t_end - clock.t0
    if clock.opened is None:
        return (f"the window never opened: the loop returned after "
                f"{loop_s:.1f}s and {len(requests)} requests, and at no step "
                "boundary did every slot hold a request that had produced a "
                "token")
    tokens = sum(len(ids) + n for _, ids, n in requests)
    until_open = clock.opened.t - clock.t0
    per_round = int(mix.get("round", 1))
    depth = HEADROOM * len(requests) * (until_open + seconds) / loop_s
    depth = per_round * int(-(-depth // per_round))
    return (f"the queue ran dry {t_end - clock.opened.t:.1f}s into a window "
            f"of {seconds:g}s: all {len(requests)} requests ({tokens} tokens)"
            f" were served in {loop_s:.1f}s, {tokens / loop_s:.0f} tokens/s; "
            f"at that pace the traffic file needs queue_depth >= {depth} "
            f"({HEADROOM} x what {until_open + seconds:.1f}s take), and "
            "benchmark/headroom.py says what the chip's roofline asks for")


def per_layer(bench, cell, ctx):
    """Every per-layer metric of this cell whose reader finds something."""
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        spec = load_json(HERE, "layer_metrics", m["name"] + ".json")
        reader = load_module(os.path.join(HERE, "layer_metrics",
                                          spec["reader"]))
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = (value, m["unit"])
    return out


def main(argv=None, root=ROOT, data=HERE, gate=require_device):
    """``root`` holds BENCHMARK.json and the configurations' files, ``data``
    the traffic files, ``gate`` admits the device: the tests under
    ``tests/`` pass toy ones; the command line always runs the real ones."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(root, "BENCHMARK.json")
    cell = by_name(bench["workloads"], args.workload, "workload")
    hf, dep, ref = load_config(root, bench, cell["config"])
    mix = load_json(data, "traffic", cell["traffic"] + ".json")
    if not os.path.isdir(os.path.join(ROOT, "flexflow_tpu")):
        die("the system under test (flexflow_tpu/) is not in this checkout")

    # the cyclic collector walks a growing heap while JAX lowers (PERF.md):
    # off during set-up, heap frozen and collector back on for the window
    gc.disable()
    import jax

    devices, peak = gate(cell["chips"])
    from flexflow_tpu.utils.platform import enable_compile_cache

    cache_dir = enable_compile_cache()
    # small programs (the join, the weights, the reference) are cached too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    watch = CompileWatch()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"device: {device} jax={jax.__version__}; compile cache {cache_dir}")
    log(f"cell: {cell['name']} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace}")

    from benchmark import check, serve_loop, traffic_gen, warmup

    marks = [("start", T_START), ("imports", time.perf_counter())]

    def mark(name):
        marks.append((name, time.perf_counter()))

    llm = build(hf, dep, devices)
    key = seed_weights(llm, ref, hf, args.seed, dep["precision"])
    mark("build+weights")
    vocab = hf["vocab_size"]
    warmup.warm(llm, mix, vocab, log)
    mark("warm-up")
    correct, numbers = check.run_check(
        llm.im, ref, hf, key, dep["precision"], args.seed, vocab,
        dep["correct"], log)
    mark("correctness")
    max_seq = dep["compile"]["max_seq_len"]
    rehearse_s = float(mix.get("rehearse_s", 0))
    if rehearse_s:
        n0 = len(watch.events)
        reqs = traffic_gen.make_requests(mix, args.seed, vocab, rehearse_s,
                                         max_seq, stream=1)
        serve_loop.run_window(llm.rm, reqs, mix["loop"], rehearse_s,
                              drain_s=mix.get("drain_s", 20))
        log(f"rehearsal: {rehearse_s}s of the cell's traffic, "
            f"{len(watch.events) - n0} lowerings or compiles in it")
        mark("rehearsal")
    requests = traffic_gen.make_requests(mix, args.seed, vocab, args.seconds,
                                         max_seq)
    mark("requests")
    gc.collect()
    gc.freeze()
    gc.enable()

    tracer, span_s = None, float(mix.get("trace_span_s", 4))
    if args.trace:
        tracer = Tracer(os.path.join(root, ".bench_trace"))
    # stopping the trace stalls the host for seconds: in the open loop the
    # span is the window's last ``trace_span_s`` seconds of arrivals and the
    # drain after them, and is closed once the loop has returned
    # (serve_loop), so that no arrival waits behind the stall
    trace_after_s = (max(args.seconds - span_s, 1.0)
                     if mix["loop"] == "open" else 1.0)
    t_window = time.perf_counter()
    setup_s = t_window - T_START
    records, clock = serve_loop.run_window(
        llm.rm, requests, mix["loop"], args.seconds,
        drain_s=mix.get("drain_s", 20), tracer=tracer,
        trace_after_s=trace_after_s, trace_span_s=span_s)
    t_end = time.perf_counter()

    # ---- what the window held -----------------------------------------
    for (a, ta), (b, tb) in zip(marks, marks[1:]):
        log(f"setup: {b} {tb - ta:.2f}s")
    log(f"setup: JAX's own account {watch.seconds()}")
    if len(clock.ticks) > 1:
        # where a stall of the host or the device would show: the longest
        # stretches between two step boundaries of the window
        gaps = sorted(((b - a, a - clock.opened.t) for a, b in
                       zip([clock.opened.t] + clock.ticks, clock.ticks)),
                      reverse=True)
        log(f"ticks: {len(gaps)} in the window, median "
            f"{gaps[len(gaps) // 2][0]:.3f}s; longest (seconds, at) "
            f"{[(round(g, 3), round(at, 1)) for g, at in gaps[:4]]}")
    compiled = watch.between(t_window, t_end)
    if compiled:
        correct = False
        log(f"NOT CORRECT: {len(compiled)} lowerings or compiles inside the "
            f"window: {compiled[:4]}")
    if clock.opened is None or (mix["loop"] == "closed"
                                and not clock.cancelled):
        msg = no_window(mix, requests, clock, args.seconds, t_end)
        log(f"benchmark: {msg}")
        die(msg)
    t_lo, t_hi = clock.opened.t - clock.t0, clock.closed.t - clock.t0
    # attempted: due in the window (open loop); reached the device before
    # it closed (closed loop: the rest of the queue only waited)
    started = "arrival_s" if mix["loop"] == "open" else "prefill_start_s"
    inside = {rid: r for rid, r in records.items()
              if r.get(started, t_hi) < t_hi}
    # requests the harness itself cancelled at the window's close were cut
    # by the measurement, not failed by the system — in the closed loop
    # only; an open-loop request still unfinished after the drain failed
    ended_by_harness = "cancelled" if mix["loop"] == "closed" else None
    failed = [rid for rid, r in inside.items()
              if r["outcome"] not in ("ok", ended_by_harness)]
    # tokens the window served, against the reference (after the window:
    # the reference's programs are not the deployment's)
    first = clock.first_rid
    served_ok, served = check.check_served(
        ref, hf, key, dep["precision"],
        {rid: r for rid, r in inside.items()
         if r.get("first_token_s", t_lo - 1) >= t_lo},
        {first + i: ids for i, (_, ids, _) in enumerate(requests)},
        check.SERVED_REQUESTS, dep["correct"], log)
    correct = correct and served_ok
    numbers.update(served)
    log(f"window: {t_lo:.2f}..{t_hi:.2f}s of the loop; {len(inside)} "
        f"requests attempted, {len(failed)} failed; outcomes "
        f"{sorted({r['outcome'] for r in inside.values()})}")

    if args.trace:
        from benchmark import trace_reduce

        xplane = trace_reduce.find_xplane(tracer.dir)
        reduced = trace_reduce.reduce_trace(xplane)
        if not reduced["chips"]:
            die("the traced span holds no operation on any device")
        # the records' own metrics: requests due before the span only (the
        # tracer's start and stop delay the loop)
        t_span = clock.trace_at[0].t - clock.t0
        before = {rid: r for rid, r in inside.items()
                  if r["arrival_s"] < t_span}
        ctx = dict(records=before, reduced=reduced, xplane=xplane,
                   clock=clock, peak=peak, hf=hf, dep=dep, mix=mix, llm=llm,
                   log=log)
        metrics = per_layer(bench, cell, ctx)
        window_s = tracer.t_stop - tracer.t_start
        chips = reduced["chips"][:len(devices)]
        device["busy_s"] = sum(c["busy_s"] for c in chips) / len(chips)
        device["window_s"] = window_s
        log(f"trace: span {window_s:.3f}s, busy {device['busy_s']:.3f}s, "
            f"idle share {1 - device['busy_s'] / window_s:.3f}")
        breakdown = trace_reduce.breakdown(reduced)
    else:
        metrics, counts = end_to_end(mix, inside, clock, args.seconds)
        metrics["setup_s"] = (setup_s, "s")
        log(f"samples: {counts}")
        breakdown = None
    device["memory_peak_bytes"] = peak_memory(devices)
    result = {
        "correct": bool(correct), "attempted": len(inside),
        "failed": len(failed),
        "metrics": {k: {"value": v, "unit": u}
                    for k, (v, u) in metrics.items()},
        "device": device,
    }
    if breakdown:
        result["breakdown"] = breakdown
    # each number compared beside its limit: the line's last key, and
    # standard error's last lines too (of a run that is not correct the
    # driver keeps the end of both)
    limits = dict(dep["correct"],
                  served_gap_ulps=dep["correct"]["token_gap_ulps"])
    result["check"] = {name: {"value": value, "limit": limits[name]}
                       for name, value in numbers.items()}
    for name, value in numbers.items():
        sys.stderr.write(f"correct: {name} = {value:.4f} "
                         f"(limit {limits[name]})\n")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
