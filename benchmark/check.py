"""How ``correct`` is decided: the deployment's logits and tokens against the
plain float32 reference, on seeded sequences, outside the timed window.

Three sequences are driven through the cell's own deployment by the calls
the scheduler makes (``RequestManager._prefill_stretch``, ``_decode_stretch``,
``_stretch_join``), in its order:
  A  7/8 of ``max_seq_len`` or eight 512-token chunks, whichever is less (1760
     tokens at 2048 positions, 4064 at 8192): prefilled by the TILED prefill
     scan in power-of-two segments of chunks, as the scheduler cuts them, so
     that every chunk after the first reads the KV of those before it.  That
     program returns tokens only: its cache is judged by what reads it.
  B  a bit under three tiles (356): prefilled FLAT, one logit row per position.
  C  one step and most of a tile (612): the JOINER.  While A and B decode it is
     prefilled flat in two chunks (the second reads the first's KV through the
     decode kernel) and spliced into the running batch by ``join_slot``.
The stretch is two chained ``decode_scan_async`` segments of ``SCAN_STEPS``
steps (per-row budgets, no readback between them), with C's prefill and join
between the two.  Then ``TAIL_STEPS`` flat decode steps on all three rows,
each fed its own greedy token: their logits read, at the full context, the KV
the prefill scan, the decode scan's own write and the join left behind.

The reference computes the full forward pass of each sequence's prompt +
generated tokens (teacher forcing: it is fed what the program produced).

Numbers compared (each printed beside its limit in every run):
  logit_rms_ulps   RMS of the ``logits_max`` error over all flat rows, in bf16
                   ulps (2**-8) of the logit scale
  logit_max_ulps   largest such error — a wrong block, mask or shard is off by
                   the scale itself (256 ulps)
  logprob_rms      RMS error of the top-k log-probabilities over all flat rows,
                   nats (by rank: ids swap between near-ties)
  logprob_max      largest such error
  tail_logprob_rms the same RMS over the TAIL rows alone: decode rows at the
                   longest contexts, behind the scans and the join
  token_gap_ulps   over every token a scan, a join or a flat step produced:
                   how far the reference's logit of that token lies under the
                   reference's largest, in ulps.  0 when the program picked the
                   reference's token, a few ulps at a near-tie, the logit scale
                   when a program that returns no logits went wrong
``check_served`` reads the same gap for tokens SERVED inside the window.
The limits live in the configuration's file with the readings they were set
from.
"""

import numpy as np

from .traffic_gen import FIRST_TOKEN_ID

SCAN_STEPS = 8
TAIL_STEPS = 4
SERVED_REQUESTS = 2  # served requests read against the reference per run
BF16_EPS = 2.0 ** -8
PAD_TO = 128  # the reference's sequences are padded to whole multiples
A, B, C = 0, 1, 2  # the sequences' indices, and their cache slots


def check_sequences(seed, vocab_size, tile, cap, max_seq_len):
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    part = (tile * 25) // 32  # a padded tile
    lens = (min(max_seq_len * 7 // 8, 8 * (cap // tile) * tile) - tile // 4,
            min(cap, 2 * tile + part), cap + part)
    assert lens[A] + 1 + 2 * SCAN_STEPS + TAIL_STEPS <= max_seq_len
    return [rng.integers(FIRST_TOKEN_ID, vocab_size, size=n).tolist()
            for n in lens]


def _prefill_scan(im, slot, ids, seq):
    """``ids`` into ``slot`` as ``_prefill_stretch`` feeds a prompt: chunks
    of whole tiles, stacked, scanned in power-of-two segments.  Returns the
    first generated token."""
    import jax.numpy as jnp

    from flexflow_tpu.serve.batch_config import (
        BatchConfig,
        PrefillBatchConfig,
    )

    cap, nreq, tile = im.max_tokens, im.max_requests, im.prefill_tile
    gate = bool(getattr(im, "gate_lm_head", False))
    chunks, logit_slots = [], []
    for start in range(0, len(ids), (cap // tile) * tile):
        take = min((cap // tile) * tile, len(ids) - start)
        seq[slot] = start + take
        fields, last_flat = PrefillBatchConfig.np_fields(
            [(slot, ids[start:start + take], start)], seq, tile,
            max_tokens=cap, max_requests=nreq)
        done = start + take == len(ids)
        chunks.append(fields)
        logit_slots.append(PrefillBatchConfig.np_logit_slots(
            [slot] if done else [], last_flat, nreq))
    at = 0
    while at < len(chunks):
        seg = 1 << (min(len(chunks) - at, 64).bit_length() - 1)
        stacked = PrefillBatchConfig(
            base=BatchConfig(*(
                jnp.asarray(np.stack([c[i] for c in chunks[at:at + seg]]))
                for i in range(5))),
            tile_size=tile,
            logit_slots=jnp.asarray(np.stack(logit_slots[at:at + seg]))
            if gate else None)
        toks = im.prefill_scan(stacked)
        at += seg
    last = np.asarray(toks)[-1]
    return int(last[slot] if gate else last[last_flat[slot]])


def _prefill_flat(im, slot, ids, seq, rows):
    """``ids`` into ``slot`` as ``_stretch_prefill`` feeds a joiner: flat
    steps of up to ``max_tokens``; ``seq`` holds the other rows' depths.
    Every position's logits go to ``rows`` (left on the device: see
    ``drive``).  Returns the last step's result and the row of the prompt's
    last token."""
    from flexflow_tpu.serve.batch_config import BatchConfig

    cap, nreq = im.max_tokens, im.max_requests
    for start in range(0, len(ids), cap):
        take = min(cap, len(ids) - start)
        seq[slot] = start + take
        bc = BatchConfig.build(
            list(ids[start:start + take]), [slot] * take,
            list(range(start, start + take)), seq,
            max_tokens=cap, max_requests=nreq)
        res = im.step(bc)
        rows.append((slot, list(range(start, start + take)), res,
                     list(range(take)), False))
    return res, take - 1


def drive(im, seqs):
    """Run the three sequences through ``im``.  Returns ``rows``: per flat
    row ``(sequence, position, logits_max, topk_logprobs, is_tail)``, and
    ``gen``: per sequence the tokens the program produced, in order (token
    k was produced for position ``len(prompt) + k``)."""
    from flexflow_tpu.serve.batch_config import BatchConfig

    cap, nreq = im.max_tokens, im.max_requests
    seq = np.zeros(nreq, np.int32)
    lazy, gen = [], [[], [], []]
    gen[A].append(_prefill_scan(im, A, seqs[A], seq))
    res, src = _prefill_flat(im, B, seqs[B], seq, lazy)
    gen[B].append(int(np.asarray(res.token_ids)[src]))

    # the stretch: A and B decode, C joins at the segment boundary
    budget = {A: 2 * SCAN_STEPS + TAIL_STEPS, B: 2 * SCAN_STEPS + TAIL_STEPS,
              C: SCAN_STEPS + TAIL_STEPS}
    depth = {s: len(seqs[s]) + 1 for s in (A, B)}  # device-side cache depth
    flat_rows = [A, B]
    for s in flat_rows:
        seq[s] = depth[s]
    bc = BatchConfig.build([gen[A][0], gen[B][0]], flat_rows,
                           [len(seqs[A]), len(seqs[B])], seq,
                           max_tokens=cap, max_requests=nreq)
    scans = []
    for segment in range(2):
        allowed = np.zeros(cap, np.int32)
        for flat, s in enumerate(flat_rows):
            allowed[flat] = budget[s]
        toks, live, ecode, bc = im.decode_scan_async(
            bc, SCAN_STEPS, eos=None, sample=None, allowed=allowed,
            max_position=max(depth[s] for s in flat_rows) - 1)
        scans.append((list(flat_rows), toks, live))
        for s in flat_rows:
            depth[s] += SCAN_STEPS
            budget[s] -= SCAN_STEPS
        if segment == 0:
            for s in flat_rows:
                seq[s] = depth[s]
            res, src = _prefill_flat(im, C, seqs[C], seq, lazy)
            joined = res.token_ids
            bc = im.join_slot(bc, joined, src, len(flat_rows), C,
                              len(seqs[C]), len(seqs[C]) + 1,
                              len(flat_rows) + 1, eos=None)
            flat_rows.append(C)
            depth[C] = len(seqs[C]) + 1
    # the stretch's single readback, committed in dispatch order
    gen[C].append(int(np.asarray(joined)[src]))
    for in_batch, toks, live in scans:
        toks, live = np.asarray(toks), np.asarray(live)
        assert live[:, :len(in_batch)].all(), "a row froze inside its budget"
        for flat, s in enumerate(in_batch):
            gen[s] += [int(t) for t in toks[:, flat]]

    # the tail: flat decode steps, logits at the full context
    for _ in range(TAIL_STEPS):
        pos = [depth[s] - 1 for s in flat_rows]
        for s in flat_rows:
            depth[s] += 1
            seq[s] = depth[s]
        bc = BatchConfig.build([gen[s][-1] for s in flat_rows], flat_rows,
                               pos, seq, max_tokens=cap, max_requests=nreq)
        res = im.step(bc)
        got = np.asarray(res.token_ids)
        for flat, s in enumerate(flat_rows):
            lazy.append((s, [pos[flat]], res, [flat], True))
            gen[s].append(int(got[flat]))
    # results stayed on the device until here, as the stretch leaves them
    rows = []
    for s, positions, res, at, tail in lazy:
        lm = np.asarray(res.logits_max).astype(np.float64)
        tk = np.asarray(res.topk_logprobs).astype(np.float64)
        rows += [(s, p, lm[i], tk[i], tail) for p, i in zip(positions, at)]
    return rows, gen


def reference_logits(ref, hf, key, dtype, sequences, wanted):
    """Full float32 forward pass of each of ``sequences`` (token ids, any
    lengths), one layer's weights drawn and upcast at a time; the LM head
    only at the positions ``wanted[i]``.  Returns per sequence one
    ``[len(wanted[i]), vocab]`` array, rows in the order of ``wanted[i]``."""
    import jax
    import jax.numpy as jnp

    from . import seeded_weights as sw

    g = jax.jit(lambda k: sw.draw_table(k, sw.GLOBAL_ID, ref.GLOBAL, hf,
                                        dtype))(key)

    def padded(ids):  # causal: padding is inert; fewer shapes to compile
        out = np.zeros(-(-len(ids) // PAD_TO) * PAD_TO, np.int32)
        out[:len(ids)] = ids
        return jnp.asarray(out[None])

    xs = tuple(jax.jit(lambda g, ids: ref.embed(hf, g, ids))(g, padded(ids))
               for ids in sequences)

    @jax.jit
    def layer(k, i, xs):
        w = sw.draw_table(k, i, ref.LAYER, hf, dtype)
        return tuple(ref.layer(hf, w, x) for x in xs)

    for i in range(ref.num_layers(hf)):
        xs = layer(key, jnp.int32(i), xs)
    head = jax.jit(lambda g, x: ref.head(hf, g, x))
    return [head(g, x[:, jnp.asarray(np.asarray(w, np.int32))])[0]
            for x, w in zip(xs, wanted)]


def _gaps(logits, tokens):
    """How far under the reference's largest logit each token's lies."""
    import jax.numpy as jnp

    picked = jnp.take_along_axis(
        logits, jnp.asarray(np.asarray(tokens, np.int32))[:, None], axis=-1)
    return np.asarray(jnp.max(logits, axis=-1) - picked[:, 0], np.float64)


def compare(rows, gen, seqs, logits, wanted, topk):
    """The numbers, from the deployment's ``rows`` and ``gen`` and the
    reference's ``logits`` at the positions ``wanted``."""
    import jax
    import jax.numpy as jnp

    d_logit, d_prob, d_tail, gaps, scale = [], [], [], [], 0.0
    for s, lg in enumerate(logits):
        at = {p: i for i, p in enumerate(wanted[s])}
        ref_max = np.asarray(jnp.max(lg, axis=-1), np.float64)
        ref_top = np.asarray(
            jax.lax.top_k(jax.nn.log_softmax(lg, axis=-1), topk)[0],
            np.float64)
        scale = max(scale, float(np.abs(ref_max).max()))
        for seq_i, pos, lm, tk, tail in rows:
            if seq_i != s:
                continue
            assert np.isfinite(lm) and np.isfinite(tk).all(), \
                "non-finite logits from the deployment"
            d_logit.append(lm - ref_max[at[pos]])
            d_prob.extend(tk - ref_top[at[pos]])
            if tail:
                d_tail.extend(tk - ref_top[at[pos]])
        # token k was produced for position prompt + k, from the logits at
        # the position before it
        first = len(seqs[s]) - 1
        idx = jnp.asarray([at[first + k] for k in range(len(gen[s]))])
        gaps.extend(_gaps(lg[idx], gen[s]))
    rms = lambda xs: float(np.sqrt((np.asarray(xs) ** 2).mean()))
    ulp = BF16_EPS * max(scale, 1.0)
    return {
        "logit_rms_ulps": rms(d_logit) / ulp,
        "logit_max_ulps": float(np.abs(d_logit).max() / ulp),
        "logprob_rms": rms(d_prob),
        "logprob_max": float(np.abs(d_prob).max()),
        "tail_logprob_rms": rms(d_tail),
        "token_gap_ulps": float(max(gaps) / ulp),
    }, {"rows": len(d_logit), "tokens": len(gaps), "logit_scale": scale,
        "ulp": ulp}


def _judge(numbers, limits, log, what):
    ok = True
    for name, value in numbers.items():
        within = value <= limits[name]
        ok = ok and within
        log(f"{what}: {name} = {value:.4f} (limit {limits[name]}) "
            f"{'ok' if within else 'OVER'}")
    return ok


def run_check(im, ref, hf, key, dtype, seed, vocab_size, limits, log):
    """Drive, compare, print each number beside its limit; True if all are
    within their limits."""
    seqs = check_sequences(seed, vocab_size, im.prefill_tile, im.max_tokens,
                           im.max_seq_len)
    rows, gen = drive(im, seqs)
    wanted = []
    for s in range(len(seqs)):
        need = {p for seq_i, p, *_ in rows if seq_i == s}
        need |= {len(seqs[s]) - 1 + k for k in range(len(gen[s]))}
        wanted.append(sorted(need))
    # the last generated token is fed to nobody: the reference needs it not
    logits = reference_logits(ref, hf, key, dtype,
                              [p + g[:-1] for p, g in zip(seqs, gen)], wanted)
    numbers, info = compare(rows, gen, seqs, logits, wanted, im.topk)
    ok = _judge(numbers, limits, log, "correct")
    log(f"correct: {info['rows']} flat rows and {info['tokens']} produced "
        f"tokens at contexts up to {len(seqs[A]) + len(gen[A])}, logit scale "
        f"{info['logit_scale']:.3f}, {'within' if ok else 'OUTSIDE'} limits")
    return ok, numbers


def check_served(ref, hf, key, dtype, records, prompts, how_many, limits,
                 log):
    """Tokens the window SERVED, against the reference: the first
    ``how_many`` requests (by id) that ended ``ok`` with two tokens or more;
    ``prompts[rid]`` is what the harness sent.  Returns
    ``(ok, {"served_gap_ulps": ...})``; no such request is a pass with
    nothing read."""
    picked = [(prompts[rid], r) for rid, r in sorted(records.items())
              if r["outcome"] == "ok" and len(r["tokens"]) >= 2][:how_many]
    if not picked:
        log("correct: no served request to read")
        return True, {}
    assert all(len(p) == r["prompt_len"] for p, r in picked)
    sequences = [list(p) + list(r["tokens"][:-1]) for p, r in picked]
    wanted = [list(range(len(p) - 1, len(p) - 1 + len(r["tokens"])))
              for p, r in picked]
    logits = reference_logits(ref, hf, key, dtype, sequences, wanted)
    import jax.numpy as jnp

    gaps, scale = [], 1.0
    for (_, r), lg in zip(picked, logits):
        gaps.extend(_gaps(lg, r["tokens"]))
        scale = max(scale, float(jnp.abs(jnp.max(lg, axis=-1)).max()))
    numbers = {"served_gap_ulps": float(max(gaps) / (BF16_EPS * scale))}
    ok = _judge(numbers, {"served_gap_ulps": limits["token_gap_ulps"]}, log,
                "correct")
    log(f"correct: {len(gaps)} served tokens of {len(picked)} requests "
        f"(prompts {[len(p) for p, _ in picked]})")
    return ok, numbers
