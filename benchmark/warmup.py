"""Warm every program the cell's traffic can reach, and no other.

The scheduler quantizes its work to powers of two, so the set is small and
follows from the deployment and the traffic file:
  prefill scan   one program per power-of-two segment of 512-token chunks,
                 up to min(64, slots x chunks of the longest prompt)
  decode scan    one program per power-of-two stretch, 2..scan_chunk
  flat step      mixed steps, joins' prompts, 1-step trailers (one program;
                 the correctness check runs it too)
  join           the splice of an arrival into a running stretch
The scans are reached through the RequestManager itself (so the calls are
the scheduler's own).  The flat step and the join need a slot to free, or
an arrival to land, in the middle of other work: each is one program with
fixed shapes, called directly with a one-token batch.
"""

import numpy as np

from .traffic_gen import FIRST_TOKEN_ID


def _prompt(rng, vocab, n):
    return rng.integers(FIRST_TOKEN_ID, vocab, size=n).tolist()


def prefill_waves(slots, chunks_per_request):
    """Totals of 512-token chunks whose greedy power-of-two segments cover
    every segment length a wave of this deployment can have."""
    top = min(64, slots * chunks_per_request)
    need = {1 << k for k in range(top.bit_length()) if (1 << k) <= top}
    waves = []
    while need:
        total = min(slots * chunks_per_request, 2 * max(need) - 1)
        total = max(total, max(need))
        waves.append(total)
        at = total
        while at:
            seg = 1 << (min(at, 64).bit_length() - 1)
            need.discard(seg)
            at -= seg
    return waves


def warm(llm, mix, vocab, log):
    import jax.numpy as jnp

    from flexflow_tpu.serve.batch_config import BatchConfig

    rm, im = llm.rm, llm.im
    cap, slots, tile = im.max_tokens, im.max_requests, im.prefill_tile
    rng = np.random.default_rng(0)
    longest = int(mix["prompt_len"]["hi"])
    per_req = -(-longest // cap)
    for total in prefill_waves(slots, per_req):
        n = min(slots, total)
        counts = [total // n + (i < total % n) for i in range(n)]
        assert max(counts) <= per_req, (total, counts, per_req)
        prompts = [_prompt(rng, vocab, (c - 1) * cap + tile) for c in counts]
        rm.generate(prompts, 1)
        log(f"warm-up: prefill wave of {total} chunks in {n} requests")
    rm.generate([_prompt(rng, vocab, tile)], 2 * rm.scan_chunk - 1)
    log(f"warm-up: decode stretches 2..{rm.scan_chunk}")
    eos = rm.gen.eos_token_id if rm.gen.stop_on_eos else None
    bc = BatchConfig.build([5], [0], [0], [1], max_tokens=cap,
                           max_requests=slots)
    im.step(bc)
    log("warm-up: flat step")
    im.join_slot(bc, jnp.zeros(cap, jnp.int32), 0, 1, 1, tile, tile + 1, 2,
                 eos=eos)
    log("warm-up: join")
