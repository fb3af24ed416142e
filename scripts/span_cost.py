#!/usr/bin/env python3
"""What one ``Span`` costs on this host, with and without the tick journal
as its consumer (no profiler session, no telemetry ring): ns per span over
``--spans`` spans, in ticks of ``--per-tick`` spans each (a tick = one
record: ``begin``, a tick span, the spans nested in it).  Then what ONE
``readback`` costs with and without the ``device_wait`` nested in it (ISSUE
59: the start of the copy, the span and a ``jax.block_until_ready`` of a
result that is ready; the array lives on whatever backend JAX finds).  A count of host nanoseconds
from wherever it runs — never a device metric."""

import argparse
import logging
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from flexflow_tpu.obs.journal import TickJournal  # noqa: E402
from flexflow_tpu.obs.trace import Span  # noqa: E402


def run(spans, per_tick, jr):
    args = {"rows": 8, "n_steps": 32, "width": 8, "ctx_sum": 4096,
            "prompt_tokens": 0}
    t0 = time.perf_counter_ns()
    for _ in range(spans // per_tick):
        if jr is not None:
            jr.begin(100, 8)
            pc = jr.clock_ns()
        else:
            pc = time.perf_counter_ns()
        with Span("decode_stretch", {"pc_ns": pc}, jr=jr):
            for i in range(per_tick - 1):
                with Span("decode_scan_dispatch" if i % 8 == 0
                          else "host_prepare", args if i % 8 == 0 else None,
                          jr=jr):
                    pass
    if jr is not None:
        jr.end()
    return (time.perf_counter_ns() - t0) / spans


def run_readback(n, jr, wait, result):
    """``n`` ticks of one ``readback`` each, copying ``result``; ``wait``:
    with the nested ``device_wait`` the serving loops enter first."""
    import jax
    import numpy as np

    t0 = time.perf_counter_ns()
    for _ in range(n):
        jr.begin(100, 8)
        with Span("decode_stretch", {"pc_ns": jr.clock_ns()}, jr=jr):
            with Span("readback", jr=jr):
                if wait:
                    result.copy_to_host_async()
                    with Span("device_wait", jr=jr):
                        jax.block_until_ready(result)
                np.asarray(result)
    jr.end()
    return (time.perf_counter_ns() - t0) / n


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--spans", type=int, default=1_000_000)
    ap.add_argument("--per-tick", type=int, default=20)
    a = ap.parse_args()
    # empty spans of a few hundred ns: a scheduler hiccup is a "slow tick"
    logging.getLogger("flexflow_tpu.serve").setLevel(logging.ERROR)
    for label, jr in (("without", None), ("with", TickJournal()),
                      ("without", None), ("with", TickJournal())):
        ns = run(a.spans, a.per_tick, jr)
        print(f"{label} the journal: {ns:.0f} ns a span, "
              f"{ns * a.per_tick / 1e3:.2f} us a tick of {a.per_tick}")
    import jax.numpy as jnp

    result = jnp.zeros((32, 256), jnp.int32)     # a stretch's tokens: 32 KB
    result.block_until_ready()
    n = a.spans // 20
    for wait in (False, True, False, True):
        ns = run_readback(n, TickJournal(), wait, result)
        print(f"a readback of a ready {result.nbytes // 1024} KB result on "
              f"{result.devices().pop().platform}, "
              f"{'with' if wait else 'without'} device_wait: {ns:.0f} ns")


if __name__ == "__main__":
    main()
