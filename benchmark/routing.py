#!/usr/bin/env python3
"""How often a routed layer's discrete choice differs from the reference's.

    python benchmark/routing.py --config nemotron-3-nano-d9-e64 --seeds 101,202

``correct`` compares logits; a router compares scores and CHOOSES.  A bf16
program and the float32 reference agree on a row's scores to a rounding, and
where rank k and rank k + 1 of them lie a rounding apart they choose
differently: one of the row's k experts is another, and that row's routed
output moves by about a k-th.  That is no fault of either side, it is what
sets the floor of the configuration's limits — so it is counted: one sequence
of ``ROWS`` seeded tokens goes through the deployment's own forward pass in
flat chunks (``InferenceManager._fwd``, the graph ``_step_impl`` runs, with
an ``extras["routing"]`` dict in which every router leaves its ids) and
through the reference layer by layer; per routed layer the share of rows
whose chosen SETS are equal, and the mean share of a row's choices that are.
One JSON line a seed.  A configuration whose reference has no ``route`` has
nothing to count.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import run as harness  # noqa: E402

ROWS = 2048


def program_choices(im, ids):
    """``{node: [len(ids), k]}``: what each router chose for the sequence
    ``ids`` fed into slot 0 in flat chunks of ``max_tokens``."""
    import jax
    import numpy as np

    from flexflow_tpu.serve.batch_config import BatchConfig

    def forward(params, state, bc):
        sink = {}
        _, state = im._fwd(params, {im._token_tid: bc.tokens}, state=state,
                           extras={"batch_config": bc,
                                   "pallas_decode": im.use_pallas,
                                   "pallas_interpret": im.pallas_interpret,
                                   "routing": sink})
        return sink, state

    step = jax.jit(forward, donate_argnums=(1,))
    cap, out = im.max_tokens, {}
    seq = np.zeros(im.max_requests, np.int32)
    for start in range(0, len(ids), cap):
        take = min(cap, len(ids) - start)
        seq[0] = start + take
        bc = BatchConfig.build(list(ids[start:start + take]), [0] * take,
                               list(range(start, start + take)), seq,
                               max_tokens=cap, max_requests=im.max_requests)
        sink, im.state = step(im.params, im.state, bc)
        for node, chosen in sink.items():
            out.setdefault(node, []).append(np.asarray(chosen)[:take])
    return {node: np.concatenate(parts) for node, parts in out.items()}


def reference_choices(ref, hf, key, dtype, ids):
    """``[[len(ids), k]]`` per routed layer, in layer order: the reference's
    router on the reference's own hidden states."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import seeded_weights as sw

    g = jax.jit(lambda k: sw.draw_table(k, sw.GLOBAL_ID, ref.GLOBAL, hf,
                                        dtype))(key)
    x = ref.embed(hf, g, jnp.asarray(np.asarray(ids, np.int32)[None]))

    @jax.jit
    def layer(k, i, x):
        w = sw.draw_table(k, i, ref.LAYER, hf, dtype)
        n = ref.rms_norm(x.h, w["norm.weight"].astype(jnp.float32),
                         hf.get("layer_norm_epsilon", 1e-5))
        return ref.layer(hf, w, x), ref.route(hf, w, n)[0]

    out = []
    for i, kind in enumerate(ref.layer_kinds(hf)):
        x, chosen = layer(key, jnp.int32(i), x)
        if kind == ref.EXPERTS:
            out.append(np.asarray(chosen)[0])
    return out


def agreement(program, reference):
    """``(share of rows whose chosen sets are equal, mean share of a row's
    choices that the other side chose too)`` over all routed layers."""
    import numpy as np

    equal, common, rows = 0, 0.0, 0
    for got, want in zip(program, reference):
        same = (got[:, :, None] == want[:, None, :]).any(-1).sum(-1)
        equal += int((same == got.shape[1]).sum())
        common += float(same.sum()) / got.shape[1]
        rows += len(got)
    return equal / rows, common / rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    hf, dep, ref = harness.load_config(harness.ROOT, bench, args.config)
    if not hasattr(ref, "route"):
        harness.die(f"{args.config}: its reference routes nothing")
    devices, _ = harness.require_device(dep["chips"])
    from flexflow_tpu.utils.platform import enable_compile_cache

    import numpy as np

    enable_compile_cache()
    llm = harness.build(hf, dep, devices)
    for seed in (int(s) for s in args.seeds.split(",") if s):
        key = harness.seed_weights(llm, ref, hf, seed, dep["precision"])
        rng = np.random.default_rng([seed, 0xF11B])
        ids = rng.integers(4, hf["vocab_size"], size=ROWS).tolist()
        got = program_choices(llm.im, ids)
        want = reference_choices(ref, hf, key, dep["precision"], ids)
        equal, common = agreement([got[n] for n in sorted(
            got, key=lambda n: int(n.split(".")[2]))], want)
        print(json.dumps({"config": args.config, "seed": seed,
                          "rows": ROWS, "routed_layers": len(want),
                          "routing_equal_share": round(equal, 4),
                          "routing_mean_overlap": round(common, 4)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
